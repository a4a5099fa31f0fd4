"""Implementation-neutral work of the solver, counted from the rows it
visits.

A bundle is s·b rows of diag(y)·A. Whatever computes its (G, v), the
work the method needs is fixed by which columns its rows share:

* Gram FLOPs = 2·Σ_c C(k_c, 2), where k_c rows of the bundle touch
  column c (one multiply and one add per pair of nonzeros that meet);
* v FLOPs = 2·nnz_B;
* least bytes = nnz_B·(4 + 4) for indices and values, nnz_B·4 for the
  gathered x, and (sb(sb-1)/2 + sb)·4 for the strict lower G and v
  written.

Under column sharding (p_c > 1, cyclic: column c on shard c mod p_c)
each shard's call counts its own columns. The rest of a round's useful
work is the s corrections, 2·b²·s(s-1)/2 FLOPs a bundle, the update
Yᵀu, 2·nnz_B, and the mean of the p_r teams' weights, p_r·n FLOPs when
p_r > 1. None of this depends on how the program computes it, so a
kernel that does less work than another scores higher on the same
yardstick.
"""

from __future__ import annotations

import dataclasses

import numpy as np

from bench.data import Data, RowSchedule


@dataclasses.dataclass(frozen=True)
class Call:
    """One (G, v) computation: a bundle on one column shard."""

    gram_flops: int
    v_flops: int
    least_bytes: int
    nnz: int

    @property
    def flops(self) -> int:
        return self.gram_flops + self.v_flops

    def least_seconds(self, peak_flops: float, peak_bytes_per_s: float) -> tuple[float, str]:
        """The least time the chip could take, and which bound sets it."""
        tc, tm = self.flops / peak_flops, self.least_bytes / peak_bytes_per_s
        return (tc, "compute") if tc >= tm else (tm, "memory")


def gram_flops(cols: np.ndarray) -> int:
    """2·Σ_c C(k_c, 2) over the column ids of a bundle's nonzeros."""
    _, k = np.unique(cols, return_counts=True)
    return int((k * (k - 1)).sum())


def bundle_cols(data: Data, rows: np.ndarray) -> np.ndarray:
    """Column ids of every nonzero of the bundle's rows (-1 = padding)."""
    rows = rows[rows >= 0]
    return np.concatenate([data.indices[data.indptr[r]:data.indptr[r + 1]] for r in rows]) \
        if rows.size else np.zeros(0, np.int32)


def bundle_calls(data: Data, rows: np.ndarray, sb: int, p_c: int = 1) -> list[Call]:
    """The (G, v) calls of one bundle, one per column shard."""
    cols = bundle_cols(data, rows)
    out_bytes = (sb * (sb - 1) // 2 + sb) * 4
    calls = []
    for j in range(p_c):
        c = cols[cols % p_c == j]
        calls.append(Call(gram_flops=gram_flops(c), v_flops=2 * c.size,
                          least_bytes=12 * c.size + out_bytes, nnz=int(c.size)))
    return calls


def round_calls(data: Data, sched: RowSchedule, r: int, p_c: int = 1) -> list[Call]:
    """Every (G, v) call of round ``r``."""
    return [c for team in range(sched.p_r) for t in range(sched.bundles)
            for c in bundle_calls(data, sched.bundle(r, team, t), sched.sb, p_c)]


def round_flops(data: Data, sched: RowSchedule, r: int) -> int:
    """Useful FLOPs of round ``r``: Gram, v, corrections and update of
    every bundle of every team, and the teams' mean."""
    b, s = sched.b, sched.s
    total = 0
    for team in range(sched.p_r):
        for t in range(sched.bundles):
            (call,) = bundle_calls(data, sched.bundle(r, team, t), sched.sb)
            total += call.flops + b * b * s * (s - 1) + 2 * call.nnz
    if sched.p_r > 1:
        total += sched.p_r * data.n
    return total
