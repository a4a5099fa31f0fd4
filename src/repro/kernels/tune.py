"""Autotuned VMEM panel shapes for the bundle kernels.

The ELL-Gram kernel has two tiling knobs — the column-panel width
``bk`` and the row tile ``bm`` — whose best values depend on the
dataset's nnz profile (ELL width, local column count) and the device.
This module sweeps the candidate grid once per (profile, device kind),
scores candidates by **measured wall time**, on a TPU cross-checked
against the analytic roofline of that chip's ``device_kind``
(``repro.launch.roofline.panel_roofline``: a candidate that does not
fit VMEM is infeasible; a measurement below the attainable bound is a
timer glitch and is discarded), and caches the winner on disk. Off-TPU
there are no chip peaks to apply, so the filter is skipped.

Cache keying mirrors the engine's jit cache: the key is a content hash
of (profile, device kind, KERNEL_VERSION) — deterministic, so every
process that plans or builds the same spec on the same device computes
the same key, and bumping KERNEL_VERSION when the kernel math or tiling
changes invalidates every cached winner at once. One JSON file per key,
written atomically (tmp + rename), each carrying the full candidate
table and its roofline justification so a cache record is auditable.

The profile is derived from *registry statistics* (DatasetStats +
schedule + mesh), never from materialized arrays — ``plan()`` (pure,
device-free planning) and ``Session`` (the build) must compute the
identical key without touching data.

Measurement backend: on TPU the compiled Pallas kernel is timed; on CPU
Pallas runs in interpret mode, whose per-op Python dispatch makes wall
time meaningless — the blocked XLA twin
(``ell_gram_and_v_blocked``) is timed instead. It shares the panel
structure and math (it is what shard_map executes), so the relative
ranking across (bk, bm) is the quantity the cache stores.

The profile-driven gram-path choice (``select_gram_path``) also lives
here: when the ELL width is heavy-tailed (w ≫ s·b — the one-hot panel
expansion costs ~w/sb more FLOPs than densifying), the dense oracle
wins and the autotuner opts the build into it, logged once.
"""

from __future__ import annotations

import dataclasses
import hashlib
import json
import logging
import os
import tempfile
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.ell_gram import ell_gram_and_v, ell_gram_and_v_blocked
from repro.launch.roofline import ChipPeaks, panel_roofline, peaks_for

__all__ = [
    "KERNEL_VERSION",
    "PanelProfile",
    "cache_key",
    "default_cache_dir",
    "device_kind",
    "load_record",
    "lookup_panel",
    "resolve_panel",
    "select_gram_path",
    "store_record",
    "tune_panel",
]

log = logging.getLogger("repro.kernels.tune")

# Bump when ell_gram / sstep_inner math or tiling changes: the cache key
# folds this in, so every stale winner misses at once.
KERNEL_VERSION = 3

BK_CANDIDATES = (128, 256, 512, 1024)
BM_CANDIDATES = (None, 16, 32)

# Static fallback = the pre-autotune defaults (bitwise path).
FALLBACK_BK = 512
FALLBACK_BM = None


@dataclasses.dataclass(frozen=True)
class PanelProfile:
    """What the tuned shape depends on — and nothing else.

    rows      s·b, the bundle row count (the kernel's M dimension).
    width     ELL width hint — ⌈z̄⌉ from the dataset registry (the
              *mean* nnz/row: deterministic from stats, so plan() and
              the build agree; the max-width heavy-tail decision is
              separate, see ``select_gram_path``).
    n_local   per-shard column count ⌈n/p_c⌉ — the kernel walks
              min(⌈n_local/bk⌉, ⌈rows·width/bk⌉) panels (it compacts a
              bundle's columns where they fit in fewer panels than n).
    dense     registry dense flag (epsilon-style data: width = n).
    precision schedule precision ("fp32" | "bf16") — changes the MXU
              peak and the VMEM tile, so it is part of the key.
    """

    rows: int
    width: int
    n_local: int
    dense: bool = False
    precision: str = "fp32"

    @classmethod
    def from_stats(cls, stats, sched, p_c: int | None = None) -> "PanelProfile":
        """The deterministic profile of (DatasetStats, schedule, p_c).
        ``p_c`` defaults to the schedule's own (the simulated engine);
        pass the mesh's for shard_map."""
        p_c = sched.p_c if p_c is None else p_c
        return cls(
            rows=sched.s * sched.b,
            width=max(int(np.ceil(stats.zbar)), 1),
            n_local=-(-stats.n // p_c),
            dense=bool(getattr(stats, "dense", False)),
            precision=sched.precision,
        )

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)


def device_kind() -> str:
    """The cache's device axis, e.g. ``cpu:cpu`` or ``tpu:TPU v5 lite``."""
    d = jax.devices()[0]
    return f"{d.platform}:{getattr(d, 'device_kind', d.platform)}"


def cache_key(
    profile: PanelProfile,
    device: str | None = None,
    kernel_version: int = KERNEL_VERSION,
) -> str:
    """Content hash of (profile, device kind, kernel version) — the jit
    cache's keying discipline applied to tuned shapes."""
    device = device_kind() if device is None else device
    payload = json.dumps(
        {"profile": profile.to_dict(), "device": device, "kernel_version": kernel_version},
        sort_keys=True,
    )
    return hashlib.sha256(payload.encode()).hexdigest()[:16]


def default_cache_dir() -> Path:
    env = os.environ.get("REPRO_TUNE_CACHE")
    if env:
        return Path(env)
    return Path.home() / ".cache" / "repro" / "tune"


def _record_path(key: str, cache_dir: Path | None = None) -> Path:
    return (default_cache_dir() if cache_dir is None else Path(cache_dir)) / f"{key}.json"


def load_record(key: str, cache_dir: Path | None = None) -> dict | None:
    p = _record_path(key, cache_dir)
    try:
        return json.loads(p.read_text())
    except (OSError, json.JSONDecodeError):
        return None


def store_record(record: dict, cache_dir: Path | None = None) -> Path:
    """Atomic write (tmp + rename): concurrent tuners race benignly —
    both compute the same winner for the same key."""
    p = _record_path(record["key"], cache_dir)
    p.parent.mkdir(parents=True, exist_ok=True)
    fd, tmp = tempfile.mkstemp(dir=p.parent, suffix=".tmp")
    try:
        with os.fdopen(fd, "w") as f:
            json.dump(record, f, indent=1, sort_keys=True)
        os.replace(tmp, p)
    finally:
        if os.path.exists(tmp):
            os.unlink(tmp)
    return p


def _synthesize(profile: PanelProfile, max_n: int, seed: int = 0):
    """A representative ELL bundle for timing: profile shapes, capped
    panel-walk extent (timing scales linearly in n — the ranking
    doesn't need the full shard)."""
    n = max(min(profile.n_local, max_n), 8)
    width = min(profile.width, n)
    rng = np.random.default_rng(seed)
    idx = jnp.asarray(rng.integers(0, n, size=(profile.rows, width)).astype(np.int32))
    val = jnp.asarray(rng.standard_normal((profile.rows, width)).astype(np.float32))
    x = jnp.asarray(rng.standard_normal(n).astype(np.float32))
    return idx, val, x, n, width


def measured_peaks() -> ChipPeaks | None:
    """Peaks of the device the tuner times on: its ``device_kind``'s
    entry on a TPU (unknown kinds raise), None on any other platform."""
    d = jax.devices()[0]
    return peaks_for(d.device_kind) if d.platform == "tpu" else None


def _time_candidate(idx, val, x, n, bk, bm, precision, repeats: int) -> float:
    """Median wall seconds of one jitted (G, v) bundle build."""
    on_tpu = jax.devices()[0].platform == "tpu"
    if on_tpu:
        fn = jax.jit(
            lambda i, v, z: ell_gram_and_v(
                i, v, z, n=n, bk=bk, bm=bm, precision=precision
            )
        )
    else:
        fn = jax.jit(
            lambda i, v, z: ell_gram_and_v_blocked(
                i, v, z, n=n, bk=bk, bm=bm, precision=precision
            )
        )
    jax.block_until_ready(fn(idx, val, x))  # compile outside the timer
    times = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(idx, val, x))
        times.append(time.perf_counter() - t0)
    times.sort()
    return times[len(times) // 2]


def tune_panel(
    profile: PanelProfile,
    *,
    device: str | None = None,
    cache_dir: Path | None = None,
    force: bool = False,
    repeats: int = 3,
    max_n: int = 16384,
    bk_candidates: tuple = BK_CANDIDATES,
    bm_candidates: tuple = BM_CANDIDATES,
) -> dict:
    """Sweep the (bk, bm) grid for ``profile`` and cache the winner.

    Returns the cache record (reading the existing one unless ``force``):

        key, kernel_version, device, profile   — the cache identity
        bk, bm                                 — the winner
        measured_s, attainable_s, efficiency   — winner's score + bound
        candidates                             — the full audited table

    Candidate filtering: bk capped at the measured extent, bm capped at
    rows; on a TPU also VMEM-infeasible shapes dropped and any
    measurement *below* its roofline bound discarded as a timer glitch
    (the cross-check). Off-TPU the roofline fields stay None.
    """
    device = device_kind() if device is None else device
    key = cache_key(profile, device)
    if not force:
        hit = load_record(key, cache_dir)
        if hit is not None:
            return hit

    idx, val, x, n, width = _synthesize(profile, max_n)
    rows = profile.rows
    bks = sorted({min(bk, -(-n // 8) * 8) for bk in bk_candidates})
    bms = sorted({bm for bm in bm_candidates if bm is None or bm < rows},
                 key=lambda v: -1 if v is None else v)
    peaks = measured_peaks()
    table = []
    for bk in bks:
        for bm in bms:
            if peaks is None:
                t = _time_candidate(idx, val, x, n, bk, bm, profile.precision, repeats)
                table.append({"bk": bk, "bm": bm, "measured_s": t,
                              "attainable_s": None, "skipped": None})
                continue
            rl = panel_roofline(rows, width, n, bk, bm, profile.precision, peaks=peaks)
            if not rl.fits_vmem:
                table.append({"bk": bk, "bm": bm, "skipped": "vmem",
                              "vmem_bytes": rl.vmem_bytes})
                continue
            t = _time_candidate(idx, val, x, n, bk, bm, profile.precision, repeats)
            glitch = t < rl.attainable_s
            table.append({
                "bk": bk, "bm": bm, "measured_s": t,
                "attainable_s": rl.attainable_s, "dominant": rl.dominant,
                "vmem_bytes": rl.vmem_bytes,
                "skipped": "sub-roofline" if glitch else None,
            })
    feasible = [c for c in table if c.get("skipped") is None]
    if not feasible:  # every candidate filtered: static fallback, uncached
        return {
            "key": key, "kernel_version": KERNEL_VERSION, "device": device,
            "profile": profile.to_dict(), "bk": FALLBACK_BK, "bm": FALLBACK_BM,
            "measured_s": None, "attainable_s": None, "efficiency": None,
            "candidates": table, "fallback": True,
        }
    best = min(feasible, key=lambda c: c["measured_s"])
    record = {
        "key": key,
        "kernel_version": KERNEL_VERSION,
        "device": device,
        "profile": profile.to_dict(),
        "bk": best["bk"],
        "bm": best["bm"],
        "measured_s": best["measured_s"],
        "attainable_s": best["attainable_s"],
        "efficiency": (
            None if best["attainable_s"] is None
            else best["attainable_s"] / best["measured_s"]
        ),
        "candidates": table,
    }
    store_record(record, cache_dir)
    return record


def lookup_panel(
    profile: PanelProfile,
    *,
    device: str | None = None,
    cache_dir: Path | None = None,
) -> dict | None:
    """Read-only cache probe — what ``plan()`` reports from (planning
    never tunes: it stays pure)."""
    return load_record(cache_key(profile, device), cache_dir)


def resolve_panel(
    profile: PanelProfile,
    *,
    device: str | None = None,
    cache_dir: Path | None = None,
    allow_tune: bool = True,
) -> tuple[int, int | None]:
    """The build-time answer for ``bk=None``: cached winner if present,
    a fresh sweep if allowed, the static (512, None) fallback otherwise."""
    rec = lookup_panel(profile, device=device, cache_dir=cache_dir)
    if rec is None and allow_tune:
        rec = tune_panel(profile, device=device, cache_dir=cache_dir)
    if rec is None:
        return FALLBACK_BK, FALLBACK_BM
    return int(rec["bk"]), None if rec["bm"] is None else int(rec["bm"])


# ---- profile-driven gram-path selection (heavy-tailed ELL widths) ----

_GRAM_CHOICES_LOGGED: set[tuple] = set()

# w/sb above this, the one-hot panel expansion (≈ w/sb × the dense
# densify cost) loses to the dense oracle.
HEAVY_TAIL_FACTOR = 4


def select_gram_path(width: int, rows: int, requested: str = "pallas") -> str:
    """Pick the (G, v) build for an ELL block of ``width`` at bundle
    size ``rows`` = s·b. Only the default "pallas" request is ever
    overridden (an explicit gram= choice is honored); a heavy-tailed
    width (w > 4·s·b) flips to the dense oracle. Logged once per
    (width, rows, verdict)."""
    if requested != "pallas":
        return requested
    choice = "dense" if width > HEAVY_TAIL_FACTOR * rows else "pallas"
    tag = (width, rows, choice)
    if tag not in _GRAM_CHOICES_LOGGED:
        _GRAM_CHOICES_LOGGED.add(tag)
        if choice != requested:
            log.info(
                "gram auto-select: ELL width %d is heavy-tailed for s·b=%d "
                "(> %d×): using the dense oracle for (G, v)",
                width, rows, HEAVY_TAIL_FACTOR,
            )
        else:
            log.info(
                "gram auto-select: ELL width %d fits s·b=%d: keeping the "
                "pallas panel kernel", width, rows,
            )
    return choice
