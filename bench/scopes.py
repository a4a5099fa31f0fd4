"""Device time by named scope, and the program's own host spans, from
the traced run's profile.

The program names the device work of a round from inside its jitted
programs with ``jax.named_scope`` (``bundle_gram``, ``allreduce_gv``,
``corrections``, ``update``, ``param_avg``, ``loss_probe``) and marks
its layer boundaries on the host with ``repro.<category>`` annotations
(``repro.obs.trace.span``: ``repro.step``, ``repro.round``,
``repro.probe``, ``repro.copy_x``, ...).

A TPU trace keeps an op's scope path (``jit(_engine_chunk)/while/body/
.../bundle_gram/pad``) in the ``tf_op`` stat of the op's event metadata
(``XEventMetadata``), which ``jax.profiler.ProfileData`` does not
expose; so this module reads the ``.xplane.pb`` itself, with protobuf's
own parser given a descriptor of the few XSpace fields read here
(field numbers of ``tsl/profiler/protobuf/xplane.proto``). Times are
ns on the trace's clock, the same as ``bench.trace_reduce``'s.

A program without the scopes or spans reads as empty: every reader
built on this returns nothing there.
"""

from __future__ import annotations

import bisect
import dataclasses
import functools
import glob
import os
import re

from bench import trace_reduce

SCOPES = ("bundle_gram", "allreduce_gv", "corrections", "update", "param_avg", "loss_probe")
SCOPE_STAT = "tf_op"  # the stat of an op's metadata that holds its scope path
STEP_ROUNDS = "bench.step_rounds"
OUTSIDE = "outside bench.step_rounds"
NONE = "none"


@dataclasses.dataclass(frozen=True)
class Op:
    """One device op event: ``scope`` is the innermost of ``SCOPES`` in
    its scope path, or ``""``."""

    name: str
    start: int
    end: int
    scope: str


@dataclasses.dataclass(frozen=True)
class HostSpan:
    """One ``repro.*`` or ``bench.*`` host event with its args."""

    name: str
    start: int
    end: int
    args: dict


@dataclasses.dataclass
class Profile:
    ops: dict[int, list[Op]]  # chip id -> ops, by start
    host: list[HostSpan]      # by start

    def window(self) -> tuple[int, int]:
        """(start, end) of ``bench.window``."""
        for h in self.host:
            if h.name == trace_reduce.WINDOW:
                return h.start, h.end
        raise KeyError(f"no host span {trace_reduce.WINDOW!r} in the trace")


# ---- reading the .xplane.pb ------------------------------------------


def _message_classes():
    from google.protobuf import descriptor_pb2, descriptor_pool, message_factory

    F = descriptor_pb2.FieldDescriptorProto
    fdp = descriptor_pb2.FileDescriptorProto(name="bench_scopes_xplane.proto",
                                             package="bench_scopes", syntax="proto3")

    def message(name, *fields, oneof=None):
        m = fdp.message_type.add(name=name)
        if oneof:
            m.oneof_decl.add(name=oneof)
        for fname, number, ftype, *rest in fields:
            f = m.field.add(name=fname, number=number, type=ftype)
            if ftype == F.TYPE_MESSAGE:  # repeated, but for a map entry's value
                f.type_name = f".bench_scopes.{rest[0]}"
                f.label = F.LABEL_OPTIONAL if fname == "value" else F.LABEL_REPEATED
            else:
                f.label = F.LABEL_OPTIONAL
                if oneof and fname.endswith("_value"):
                    f.oneof_index = 0

    # strings are read as bytes: the parser then checks no UTF-8
    message("XSpace", ("planes", 1, F.TYPE_MESSAGE, "XPlane"))
    message("XPlane", ("id", 1, F.TYPE_INT64), ("name", 2, F.TYPE_BYTES),
            ("lines", 3, F.TYPE_MESSAGE, "XLine"),
            ("event_metadata", 4, F.TYPE_MESSAGE, "EventMetadataEntry"),
            ("stat_metadata", 5, F.TYPE_MESSAGE, "StatMetadataEntry"))
    message("EventMetadataEntry", ("key", 1, F.TYPE_INT64),
            ("value", 2, F.TYPE_MESSAGE, "XEventMetadata"))
    message("StatMetadataEntry", ("key", 1, F.TYPE_INT64),
            ("value", 2, F.TYPE_MESSAGE, "XStatMetadata"))
    message("XLine", ("name", 2, F.TYPE_BYTES), ("timestamp_ns", 3, F.TYPE_INT64),
            ("events", 4, F.TYPE_MESSAGE, "XEvent"))
    message("XEvent", ("metadata_id", 1, F.TYPE_INT64), ("offset_ps", 2, F.TYPE_INT64),
            ("duration_ps", 3, F.TYPE_INT64), ("stats", 4, F.TYPE_MESSAGE, "XStat"))
    message("XStat", ("metadata_id", 1, F.TYPE_INT64), ("double_value", 2, F.TYPE_DOUBLE),
            ("uint64_value", 3, F.TYPE_UINT64), ("int64_value", 4, F.TYPE_INT64),
            ("str_value", 5, F.TYPE_BYTES), ("bytes_value", 6, F.TYPE_BYTES),
            ("ref_value", 7, F.TYPE_UINT64), oneof="value")
    message("XEventMetadata", ("id", 1, F.TYPE_INT64), ("name", 2, F.TYPE_BYTES),
            ("stats", 5, F.TYPE_MESSAGE, "XStat"))
    message("XStatMetadata", ("id", 1, F.TYPE_INT64), ("name", 2, F.TYPE_BYTES))
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fdp)
    return message_factory.GetMessageClass(pool.FindMessageTypeByName("bench_scopes.XSpace"))


def _text(b: bytes) -> str:
    return b.decode("utf-8", "replace")


def _stats(stats, stat_names: dict[int, str]) -> dict:
    out = {}
    for st in stats:
        kind = st.WhichOneof("value")
        if kind is None:
            continue
        v = getattr(st, kind)
        if kind == "ref_value":  # an interned string: a stat metadata's name
            v = stat_names.get(v, "")
        elif kind in ("str_value", "bytes_value"):
            v = _text(v)
        out[stat_names.get(st.metadata_id, str(st.metadata_id))] = v
    return out


def innermost_scope(path: str) -> str:
    """The innermost of ``SCOPES`` among the components of a scope path."""
    for part in reversed(path.split("/")):
        part = part.split(":")[0]  # xprof's tf_op reads "<path>/<op>:<type>"
        if part in SCOPES:
            return part
    return ""


def _scope_of(stats: dict) -> str:
    path = stats.get(SCOPE_STAT)
    return innermost_scope(path) if isinstance(path, str) else ""


def parse(data: bytes) -> Profile:
    """A ``Profile`` of one serialized XSpace, events in file order."""
    space = _message_classes().FromString(data)
    prof = Profile(ops={}, host=[])
    for plane in space.planes:
        pname = _text(plane.name)
        chip = re.fullmatch(r"/device:TPU:(\d+)", pname)
        if not chip and not pname.startswith("/host:"):
            continue
        stat_names = {e.key: _text(e.value.name) for e in plane.stat_metadata}
        meta = {e.key: e.value for e in plane.event_metadata}
        for line in plane.lines:
            if chip and _text(line.name) != "XLA Ops":
                continue
            ts = line.timestamp_ns
            if chip:
                scope = {k: _scope_of(_stats(m.stats, stat_names)) for k, m in meta.items()}
                ops = prof.ops.setdefault(int(chip.group(1)), [])
                for e in line.events:
                    t0 = ts + e.offset_ps / 1000
                    ops.append(Op(_text(meta[e.metadata_id].name) if e.metadata_id in meta else "",
                                  int(t0), int(t0 + e.duration_ps / 1000),
                                  scope.get(e.metadata_id, "")))
                continue
            keep = {k: _text(m.name) for k, m in meta.items()
                    if m.name.startswith((b"repro.", b"bench."))}
            for e in line.events:
                if e.metadata_id in keep:
                    t0 = ts + e.offset_ps / 1000
                    prof.host.append(HostSpan(keep[e.metadata_id], int(t0),
                                              int(t0 + e.duration_ps / 1000),
                                              _stats(e.stats, stat_names)))
    return prof


@functools.lru_cache(maxsize=2)
def _load(paths: tuple[tuple[str, int], ...]) -> Profile:
    """The files' profiles merged, events by start (longest first)."""
    merged = Profile(ops={}, host=[])
    for path, _mtime in paths:
        with open(path, "rb") as f:
            one = parse(f.read())
        for chip, ops in one.ops.items():
            merged.ops.setdefault(chip, []).extend(ops)
        merged.host.extend(one.host)
    for ops in merged.ops.values():
        ops.sort(key=lambda o: (o.start, -o.end))
    merged.host.sort(key=lambda h: (h.start, -h.end))
    return merged


def load(trace_dir: str) -> Profile:
    """Every ``*.xplane.pb`` under ``trace_dir``, read once per file
    version."""
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True))
    if not paths:
        raise FileNotFoundError(f"no *.xplane.pb under {trace_dir}")
    return _load(tuple((p, os.stat(p).st_mtime_ns) for p in paths))


def of_run(run) -> Profile | None:
    """The profile of a traced run (``bench.harness`` writes it under
    ``.bench_out/<cell>/trace``), or None for an untraced run."""
    if run.trace is None:
        return None
    return load(str(run.cell.root / ".bench_out" / run.cell.name / "trace"))


# ---- what the readers take -------------------------------------------


def chips(prof: Profile, n: int) -> list[int]:
    return sorted(prof.ops)[:n]


def leaf_ops(prof: Profile, chip: int, lo: int, hi: int) -> list[Op]:
    """``chip``'s ops that start in [lo, hi) and contain no other op,
    clipped to it."""
    return trace_reduce.leaves([dataclasses.replace(o, end=min(o.end, hi))
                                for o in prof.ops.get(chip, []) if lo <= o.start < hi])


def scope_ns(prof: Profile, chip_ids: list[int], scope: str, lo: int, hi: int,
             skip=lambda name: False) -> list[int]:
    """Per chip, the device time of the leaf ops under ``scope`` (less
    those ``skip`` names), as the union of their intervals."""
    return [trace_reduce.covered(trace_reduce.union(
        o for o in leaf_ops(prof, c, lo, hi) if o.scope == scope and not skip(o.name)))
        for c in chip_ids]


def spans(prof: Profile, name: str, lo: int, hi: int) -> list[HostSpan]:
    """Host spans called ``name`` that start in [lo, hi), clipped to it."""
    return [dataclasses.replace(h, end=min(h.end, hi)) for h in prof.host
            if h.name == name and lo <= h.start < hi]


def per_bundle_ms(run, scope: str, skip=lambda name: False) -> float | None:
    """Device ms under ``scope`` per (G, v) call of the traced rounds on
    one chip, mean over the chips; nothing where no op carries the
    scope."""
    prof = of_run(run)
    if prof is None or not run.calls:
        return None
    lo, hi = prof.window()
    ids = chips(prof, run.chips)
    ns = scope_ns(prof, ids, scope, lo, hi, skip)
    if not any(ns):
        return None
    return sum(ns) / len(run.calls) / 1e6


def idle_by_span(run) -> dict[str, float] | None:
    """Each chip's idle time in the traced window, in ns (mean over the
    chips), split by what the host was in at each moment: the innermost
    ``repro.*`` span open, else ``none`` inside a ``bench.step_rounds``
    call, else ``outside bench.step_rounds``."""
    prof = of_run(run)
    if prof is None:
        return None
    lo, hi = prof.window()
    marks = [h for h in prof.host if h.name.startswith("repro.") or h.name == STEP_ROUNDS]
    ids = chips(prof, run.chips)
    out: dict[str, float] = {}
    for c in ids:
        gaps = trace_reduce.gaps(trace_reduce.Trace(ops=prof.ops, modules={}, host=[]),
                                 c, lo, hi)
        for label, ns in _split(gaps, marks).items():
            out[label] = out.get(label, 0.0) + ns / len(ids)
    return out


def _split(gaps: list[tuple[int, int]], marks: list[HostSpan]) -> dict[str, int]:
    """Lengths of ``gaps`` by the label of the innermost mark open over
    each piece between the marks' edges."""
    cuts = sorted({t for h in marks for t in (h.start, h.end)})
    labels = [_label(marks, (a + b) / 2) for a, b in zip(cuts, cuts[1:])]
    out: dict[str, int] = {}
    for s, e in gaps:
        i = bisect.bisect_right(cuts, s)
        points = [s] + cuts[i:bisect.bisect_left(cuts, e)] + [e]
        for a, b in zip(points, points[1:]):
            k = bisect.bisect_right(cuts, (a + b) / 2) - 1
            label = labels[k] if 0 <= k < len(labels) else OUTSIDE
            out[label] = out.get(label, 0) + (b - a)
    return out


def _label(marks: list[HostSpan], t: float) -> str:
    open_ = [h for h in marks if h.start <= t < h.end]
    if not any(h.name == STEP_ROUNDS for h in open_):
        return OUTSIDE
    inner = [h for h in open_ if h.name.startswith("repro.")]
    if not inner:
        return NONE
    return max(inner, key=lambda h: (h.start, -h.end)).name
