"""Roofline analysis from compiled dry-run artifacts (deliverable g).

Per (arch × shape × mesh) we derive, from the per-device SPMD module:

  compute term    = HLO_FLOPs_per_device / peak_FLOP/s      (197e12 bf16)
  memory term     = HLO_bytes_per_device / HBM_bw           (819e9 B/s)
  collective term = collective_bytes_per_device / link_bw   (~50e9 B/s)

Sources: ``compiled.cost_analysis()`` for FLOPs/bytes;
``compiled.as_text()`` parsed here for collective operand bytes (they
are NOT in cost_analysis). XLA's cost analysis counts a while-loop body
ONCE, so the launcher lowers depth-1 and depth-2 *unrolled* variants
and linearly extrapolates to full depth (exact for layer-linear
models); the full scanned compile is used for memory_analysis only.

MODEL_FLOPS = 6·N·D (dense) or 6·N_active·D (MoE); the ratio
MODEL_FLOPS / HLO_FLOPs measures how much compiled compute is useful
(catches remat recompute and padding waste — with remat-everything the
expected train ratio is ≈ 6/8 = 0.75 of the no-remat value).
"""

from __future__ import annotations

import dataclasses
import re

from repro.kernels.ell_gram import panels_walked


@dataclasses.dataclass(frozen=True)
class ChipPeaks:
    """Published per-chip peaks of one accelerator ``device_kind``."""

    bf16_flops: float  # FLOP/s
    hbm_bw: float  # B/s
    hbm_bytes: int
    vmem_bytes: int  # scoped-VMEM budget the panel tiler fits in
    source: str


# Keyed by ``jax.Device.device_kind``: a TPU v5e reports "TPU v5 lite".
PEAKS = {
    "TPU v5 lite": ChipPeaks(
        bf16_flops=197e12,
        hbm_bw=819e9,
        hbm_bytes=16 * 2**30,
        vmem_bytes=16 * 2**20,
        source="Google Cloud, TPU v5e",
    ),
}


def peaks_for(device_kind: str) -> ChipPeaks:
    """The peaks of ``device_kind``; a chip missing from ``PEAKS`` is an
    error, never a default."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for device_kind={device_kind!r} "
            f"(known: {sorted(PEAKS)})"
        ) from None


# the dry-run roofline below prices the v5e production mesh
_V5E = PEAKS["TPU v5 lite"]
PEAK_FLOPS = _V5E.bf16_flops
HBM_BW = _V5E.hbm_bw
ICI_BW = 50e9  # B/s per link (effective)


_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "bf16": 2, "f16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1, "c64": 8, "c128": 16,
}

COLLECTIVE_KINDS = (
    "all-gather",
    "all-reduce",
    "reduce-scatter",
    "all-to-all",
    "collective-permute",
)

# e.g.  %x = bf16[16,128]{1,0} all-gather(...)   or tuple shapes
_OP_RE = re.compile(
    r"=\s*(?P<shape>\(?[a-z0-9]+\[[0-9,]*\][^\s]*\)?[^=]*?)\s*"
    r"(?P<kind>all-gather|all-reduce-start|all-reduce|reduce-scatter|all-to-all|collective-permute-start|collective-permute)\(",
)
_SHAPE_RE = re.compile(r"(?P<dt>[a-z][a-z0-9]+)\[(?P<dims>[0-9,]*)\]")


def _shape_bytes(shape_text: str) -> int:
    total = 0
    for m in _SHAPE_RE.finditer(shape_text):
        bytes_per = _DTYPE_BYTES.get(m.group("dt"))
        if bytes_per is None:
            continue
        dims = m.group("dims")
        count = 1
        if dims:
            for d in dims.split(","):
                count *= int(d)
        total += count * bytes_per
    return total


@dataclasses.dataclass
class CollectiveStats:
    bytes_by_kind: dict[str, int]
    count_by_kind: dict[str, int]
    in_while_body: bool  # True if any collective sits inside a while

    @property
    def total_bytes(self) -> int:
        return sum(self.bytes_by_kind.values())


def parse_collectives(hlo_text: str) -> CollectiveStats:
    """Sum output-buffer bytes of every collective in the module.

    For all-gather/all-reduce the output size equals the full (gathered/
    reduced) payload each device holds; for reduce-scatter the *input*
    is the payload — we approximate with output × group_size ≈ input by
    just using output bytes uniformly (consistent across configs, and
    the ranking/regime use is insensitive to the ≤2× convention).
    """
    bytes_by_kind: dict[str, int] = {k: 0 for k in COLLECTIVE_KINDS}
    count_by_kind: dict[str, int] = {k: 0 for k in COLLECTIVE_KINDS}
    in_while = False
    current_comp_is_body = False
    body_names: set[str] = set()
    for m in re.finditer(r"body=%?([\w.\-]+)", hlo_text):
        body_names.add(m.group(1))

    for line in hlo_text.splitlines():
        stripped = line.strip()
        if stripped.startswith(("%", "ENTRY")) and stripped.endswith("{"):
            comp_name = stripped.split(" ")[0].lstrip("%").split(".(")[0]
            comp_name = comp_name.split("(")[0].rstrip()
            current_comp_is_body = any(comp_name.startswith(b) or b.startswith(comp_name) for b in body_names)
            continue
        m = _OP_RE.search(line)
        if not m:
            continue
        kind = m.group("kind").replace("-start", "")
        nbytes = _shape_bytes(m.group("shape"))
        # all-reduce-start returns (operand, result) tuples in some
        # lowerings — halve to avoid double counting the pair
        if "-start" in m.group(0) and m.group("shape").startswith("("):
            nbytes //= 2
        bytes_by_kind[kind] = bytes_by_kind.get(kind, 0) + nbytes
        count_by_kind[kind] = count_by_kind.get(kind, 0) + 1
        if current_comp_is_body:
            in_while = True
    return CollectiveStats(bytes_by_kind, count_by_kind, in_while)


@dataclasses.dataclass
class RooflineTerms:
    arch: str
    shape: str
    mesh: str
    flops: float  # per device, full depth
    hbm_bytes: float
    collective_bytes: float
    collective_breakdown: dict[str, int]
    model_flops: float  # 6·N_active·D (global) / device
    peak_flops: float = PEAK_FLOPS
    hbm_bw: float = HBM_BW
    ici_bw: float = ICI_BW

    @property
    def compute_s(self) -> float:
        return self.flops / self.peak_flops

    @property
    def memory_s(self) -> float:
        return self.hbm_bytes / self.hbm_bw

    @property
    def collective_s(self) -> float:
        return self.collective_bytes / self.ici_bw

    @property
    def dominant(self) -> str:
        terms = {
            "compute": self.compute_s,
            "memory": self.memory_s,
            "collective": self.collective_s,
        }
        return max(terms, key=terms.get)

    @property
    def useful_flops_ratio(self) -> float:
        return self.model_flops / self.flops if self.flops else 0.0

    @property
    def bound_s(self) -> float:
        """Roofline lower bound on step time (max of the three terms)."""
        return max(self.compute_s, self.memory_s, self.collective_s)

    def row(self) -> dict:
        return {
            "arch": self.arch,
            "shape": self.shape,
            "mesh": self.mesh,
            "flops_per_dev": self.flops,
            "hbm_bytes_per_dev": self.hbm_bytes,
            "collective_bytes_per_dev": self.collective_bytes,
            "compute_s": self.compute_s,
            "memory_s": self.memory_s,
            "collective_s": self.collective_s,
            "dominant": self.dominant,
            "model_flops_per_dev": self.model_flops,
            "useful_ratio": self.useful_flops_ratio,
            "collectives": self.collective_breakdown,
        }


# ---- analytic panel roofline (repro.kernels.tune's justification) ----
#
# The ELL-Gram kernel walks min(⌈n/bk⌉, ⌈rows·width/bk⌉) column panels:
# a bundle touches at most rows·width distinct columns, and where those
# fit in fewer panels than n the kernel compacts them first
# (``repro.kernels.ell_gram.panels_walked``). Per panel it expands
# the (sb, w) ELL block into a (sb, bk) dense panel (one-hot contraction,
# 2·sb·w·bk FLOPs), accumulates G += P·Pᵀ (2·sb²·bk) and v += P·x_blk
# (2·sb·bk). The ELL block itself is re-streamed from HBM once per panel
# (it is VMEM-resident *within* a grid step, not across steps) — that
# re-read is the bk tradeoff the tuner prices: larger panels cut the
# ⌈n/bk⌉ re-reads but grow the (bm, bk) VMEM tile.


def panel_vmem_bytes(
    rows: int, width: int, bk: int, bm: int | None = None, compute_bytes: int = 4
) -> int:
    """VMEM working set of one ell_gram grid step: the (bm, bk) expanded
    panel tile at compute precision plus the resident ELL block
    (indices + values), G, v, and x panel (all f32/i32)."""
    bm = rows if bm is None or bm > rows else bm
    panel = bm * bk * compute_bytes
    resident = rows * width * (4 + 4) + rows * rows * 4 + rows * 4 + bk * 4
    return panel + resident


def panel_flops(rows: int, width: int, n: int, bk: int) -> float:
    """Total FLOPs of one (G, v) bundle build at panel width bk."""
    n_panels = panels_walked(rows, width, n, bk)
    per_panel = 2 * rows * width * bk + 2 * rows * rows * bk + 2 * rows * bk
    return float(n_panels * per_panel)


def panel_hbm_bytes(
    rows: int, width: int, n: int, bk: int, compute_bytes: int = 4
) -> float:
    """HBM traffic of one bundle build: the ELL block re-streamed once
    per panel, x streamed once, G and v written once."""
    n_panels = panels_walked(rows, width, n, bk)
    ell = n_panels * rows * width * (4 + 4)  # int32 indices + f32 values
    x = n_panels * bk * 4
    out = rows * rows * 4 + rows * 4
    return float(ell + x + out)


@dataclasses.dataclass(frozen=True)
class PanelRoofline:
    """Attainable-time bound for one (rows, width, n, bk, bm) panel
    configuration — what the autotuner cross-checks measured wall time
    against (a measurement below the bound is a timer glitch; far above
    it, headroom the next candidate may claim)."""

    rows: int
    width: int
    n: int
    bk: int
    bm: int | None
    flops: float
    hbm_bytes: float
    vmem_bytes: int
    peak_flops: float
    hbm_bw: float
    vmem_limit: int

    @property
    def compute_s(self) -> float:
        return self.flops / self.peak_flops

    @property
    def memory_s(self) -> float:
        return self.hbm_bytes / self.hbm_bw

    @property
    def attainable_s(self) -> float:
        """Roofline lower bound on the bundle build (max of the terms)."""
        return max(self.compute_s, self.memory_s)

    @property
    def dominant(self) -> str:
        return "compute" if self.compute_s >= self.memory_s else "memory"

    @property
    def fits_vmem(self) -> bool:
        return self.vmem_bytes <= self.vmem_limit


def panel_roofline(
    rows: int,
    width: int,
    n: int,
    bk: int,
    bm: int | None = None,
    precision: str = "fp32",
    *,
    peaks: ChipPeaks,
) -> PanelRoofline:
    """The attainable-FLOP/s justification for one tuner candidate on
    the chip whose ``peaks`` are given (``peaks_for(device_kind)``).

    ``precision`` prices the MXU: bf16 panels run at the full bf16 peak
    with 2-byte panel tiles; fp32 halves the peak and doubles the
    tile."""
    cb = 2 if precision == "bf16" else 4
    peak = peaks.bf16_flops if precision == "bf16" else peaks.bf16_flops / 2
    return PanelRoofline(
        rows=rows,
        width=width,
        n=n,
        bk=bk,
        bm=bm,
        flops=panel_flops(rows, width, n, bk),
        hbm_bytes=panel_hbm_bytes(rows, width, n, bk, cb),
        vmem_bytes=panel_vmem_bytes(rows, width, bk, bm, cb),
        peak_flops=peak,
        hbm_bw=peaks.hbm_bw,
        vmem_limit=peaks.vmem_bytes,
    )


def extrapolate_depth(v1: float, v2: float, n_periods: int) -> float:
    """cost(P) = base + P·per_period, measured at P=1 and P=2."""
    per = max(v2 - v1, 0.0)
    base = max(v1 - per, 0.0)
    return base + n_periods * per


def model_flops_per_step(cfg, shape, kind: str) -> float:
    """6·N_active·D global model FLOPs for the step (3 matmul passes
    fwd+bwd for train; 2·N·D for inference forward)."""
    n_active = cfg.active_param_count() - cfg.vocab_size * cfg.d_model * (
        0 if cfg.tie_embeddings else 1
    )  # lm_head counted once below; embedding lookup is a gather
    if kind == "train":
        tokens = shape.global_batch * shape.seq_len
        return 6.0 * n_active * tokens
    if kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        return 2.0 * n_active * tokens
    tokens = shape.global_batch  # decode: 1 token/seq
    return 2.0 * n_active * tokens
