"""Reduce a JAX profiler trace (``*.xplane.pb``) to the numbers the
per-layer metrics read.

A TPU trace holds one plane per chip (``/device:TPU:<id>``) whose line
``XLA Ops`` has one event per HLO op run (loops nest: a ``while`` event
covers its body's ops) and whose line ``XLA Modules`` has one event per
program run, plus the host plane ``/host:CPU`` with the harness's
``TraceAnnotation`` spans and, with the Python tracer on, every Python
call. Device and host events share one clock (ns from the trace start).

Matched by name, as read by hand in a v5e trace of the solver:

* the Gram kernel: ``%closed_call.13 = (f32[64,64]{...}, f32[64,1]{...})
  custom-call(...), custom_call_target="tpu_custom_call"`` -- a Pallas
  call whose outputs are an (sb, sb) and an (sb, 1) float32 block. The
  HLO name changes with the program (``closed_call.12``, ``_lambda_.1``),
  so the match is on the target and the output shapes; an op named
  ``ell_gram...`` also counts, for a kernel that names itself;
* collectives: ops whose kind is ``all-reduce``, ``all-gather``,
  ``reduce-scatter``, ``all-to-all`` or ``collective-permute``, with
  their ``-start`` / ``-done`` halves.
"""

from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re

GRAM_RE = re.compile(
    r'= \(f32\[(\d+),(\d+)\][^ ]* f32\[(\d+),1\][^ ]*\) custom-call\(.*'
    r'custom_call_target="tpu_custom_call"'
)
COLLECTIVE_RE = re.compile(
    r"\b(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)(-start|-done)?\("
)
WINDOW = "bench.window"


@dataclasses.dataclass(frozen=True)
class Ev:
    name: str
    start: int  # ns
    end: int    # ns


@dataclasses.dataclass
class Trace:
    ops: dict[int, list[Ev]]      # chip id -> "XLA Ops" events, by start
    modules: dict[int, list[Ev]]  # chip id -> "XLA Modules" events, by start
    host: list[Ev]                # host events of every thread, by start


def _events(line) -> list[Ev]:
    return sorted(
        (Ev(e.name, int(e.start_ns), int(e.start_ns + e.duration_ns)) for e in line.events),
        key=lambda e: (e.start, -e.end),
    )


def load(trace_dir: str) -> Trace:
    """Read every ``*.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True))
    if not paths:
        raise FileNotFoundError(f"no *.xplane.pb under {trace_dir}")
    tr = Trace(ops={}, modules={}, host=[])
    for path in paths:
        for plane in ProfileData.from_file(path).planes:
            m = re.fullmatch(r"/device:TPU:(\d+)", plane.name)
            for line in plane.lines:
                if m and line.name == "XLA Ops":
                    tr.ops.setdefault(int(m.group(1)), []).extend(_events(line))
                elif m and line.name == "XLA Modules":
                    tr.modules.setdefault(int(m.group(1)), []).extend(_events(line))
                elif plane.name.startswith("/host:"):
                    tr.host.extend(_events(line))
    tr.host.sort(key=lambda e: (e.start, -e.end))
    return tr


def span(tr: Trace, name: str = WINDOW) -> tuple[int, int]:
    """(start, end) of the first host span called ``name``."""
    for e in tr.host:
        if e.name == name:
            return e.start, e.end
    raise KeyError(f"no host span {name!r} in the trace")


def within(evs: list[Ev], lo: int, hi: int) -> list[Ev]:
    """Events that start inside [lo, hi), clipped to it."""
    return [Ev(e.name, e.start, min(e.end, hi)) for e in evs if lo <= e.start < hi]


def union(evs) -> list[tuple[int, int]]:
    """Merged (start, end) intervals covered by ``evs``."""
    out: list[list[int]] = []
    for s, e in sorted((ev.start, ev.end) for ev in evs):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def covered(intervals) -> int:
    return sum(e - s for s, e in intervals)


def overlap(a: list[tuple[int, int]], b: list[tuple[int, int]]) -> int:
    """Length covered by both merged interval lists."""
    i = j = tot = 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        tot += max(0, hi - lo)
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return tot


def leaves(evs: list[Ev]) -> list[Ev]:
    """Events that contain no other event (a loop's body ops, not the
    loop): ``evs`` sorted by start, longest first on ties."""
    out = []
    for k, e in enumerate(evs):
        j = k + 1
        while j < len(evs) and evs[j].start < e.end and evs[j].end > e.end:
            j += 1
        if j == len(evs) or evs[j].start >= e.end:
            out.append(e)
    return out


def is_gram(name: str) -> bool:
    m = GRAM_RE.search(name)
    if m:
        return m.group(1) == m.group(2) == m.group(3)
    return op_name(name).startswith("ell_gram")


def is_collective(name: str) -> bool:
    return COLLECTIVE_RE.search(name) is not None


def op_name(name: str) -> str:
    return name.split(" = ", 1)[0].lstrip("%")


def op_label(name: str) -> str:
    """``<op> <kind>`` (with the custom-call target) for an HLO event."""
    if " = " not in name:
        return name
    rhs = name.split(" = ", 1)[1]
    m = re.search(r"\s([a-z][a-z0-9-]*)\(", rhs)
    kind = m.group(1) if m else "?"
    t = re.search(r'custom_call_target="([^"]+)"', rhs)
    return f"{op_name(name)} {kind}" + (f":{t.group(1)}" if t else "")


def chips(tr: Trace, n: int) -> list[int]:
    """The first ``n`` chips of the trace."""
    return sorted(tr.ops)[:n]


def busy_ns(tr: Trace, chip: int, lo: int, hi: int) -> int:
    return covered(union(within(tr.ops.get(chip, []), lo, hi)))


def gaps(tr: Trace, chip: int, lo: int, hi: int) -> list[tuple[int, int]]:
    """Idle intervals of ``chip`` inside [lo, hi]."""
    out, t = [], lo
    for s, e in union(within(tr.ops.get(chip, []), lo, hi)):
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if t < hi:
        out.append((t, hi))
    return out


def host_label(tr: Trace, start: int, end: int) -> str:
    """What the host was doing in [start, end): the innermost host event
    at its middle that lasts at least half of it, with the harness span
    around that."""
    mid = (start + end) // 2
    at_mid = [e for e in tr.host if e.start <= mid < e.end and 2 * (e.end - e.start) >= end - start]
    if not at_mid:
        return "(no host span)"
    inner = min(at_mid, key=lambda e: (e.end - e.start, -e.start))
    outer = [e.name for e in at_mid if e.name.startswith("bench.")]
    label = inner.name.lstrip("$")
    if outer and outer[-1] != inner.name:
        label = f"{label} < {outer[-1]}"
    return label


def gram_ops(tr: Trace, chip: int, lo: int, hi: int) -> list[Ev]:
    return [e for e in within(tr.ops.get(chip, []), lo, hi) if is_gram(e.name)]


def exposed_collective_ns(tr: Trace, chip: int, lo: int, hi: int) -> int:
    """Time of ``chip``'s collective ops in [lo, hi] during which none
    of its other ops runs."""
    leaf = leaves(within(tr.ops.get(chip, []), lo, hi))
    coll = union(e for e in leaf if is_collective(e.name))
    comp = union(e for e in leaf if not is_collective(e.name))
    return covered(coll) - overlap(coll, comp)


def breakdown(tr: Trace, chip_ids: list[int], lo: int, hi: int, top: int = 10) -> dict:
    """The ``breakdown`` of a result line: the device ops that took the
    most time (leaf ops, seconds per chip) and the longest idle gaps,
    each labelled by what the host was doing."""
    per_op: dict[str, float] = {}
    for c in chip_ids:
        mods = tr.modules.get(c, [])
        starts = [e.start for e in mods]
        for e in leaves(within(tr.ops.get(c, []), lo, hi)):
            k = bisect.bisect_right(starts, e.start) - 1
            mod = re.sub(r"\(\d+\)$", "", mods[k].name) if k >= 0 and mods[k].end > e.start else "?"
            key = f"{mod}/{op_label(e.name)}"
            per_op[key] = per_op.get(key, 0.0) + (e.end - e.start) / 1e9 / len(chip_ids)
    idle = []
    for c in chip_ids:
        for s, e in gaps(tr, c, lo, hi):
            idle.append((e - s, s, e))
    idle.sort(reverse=True)
    return {
        "device_ops": [[k, v] for k, v in sorted(per_op.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[host_label(tr, s, e), d / 1e9] for d, s, e in idle[:top]],
    }
