"""The benchmark's own inputs: the synthetic sparse matrix and labels of
a configuration, made from the run's seed, and the order in which the
solver visits its rows.

``make_data`` draws the same numbers, in the same order, as the
program's generator for a dataset of the same statistics (column ids
from p(c) ∝ (c+1)^-α, Poisson row lengths, duplicates dropped per row,
labels from a sparse planted model), so the reference runs on exactly
the rows the program was given without taking anything from the
program. The per-row loop of the original is replaced by one sort.

``RowSchedule`` is the solver's row order: p_r contiguous row teams,
each padded with empty rows to a multiple of ``row_multiple``, walked
cyclically s·b rows per bundle.
"""

from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass(frozen=True)
class Data:
    """diag(y)·A in CSR form (float64 values) and the labels y."""

    m: int
    n: int
    indptr: np.ndarray   # (m+1,) int64
    indices: np.ndarray  # (nnz,) int32
    ya: np.ndarray       # (nnz,) float64, values scaled by their row's label
    y: np.ndarray        # (m,) ±1

    @property
    def nnz(self) -> int:
        return int(self.indices.shape[0])

    def row_ids(self) -> np.ndarray:
        return np.repeat(np.arange(self.m), np.diff(self.indptr))


def make_data(stats: dict, seed: int) -> Data:
    """The matrix and labels for ``stats`` (``m``, ``n``, ``zbar``,
    ``skew_alpha``) drawn from ``seed``."""
    m, n, zbar = int(stats["m"]), int(stats["n"]), int(stats["zbar"])
    alpha = float(stats["skew_alpha"])
    rng = np.random.default_rng(seed)
    p = np.arange(1, n + 1, dtype=np.float64) ** (-alpha)
    p = p / p.sum()
    counts = np.clip(rng.poisson(zbar, size=m), 1, min(4 * zbar, n)).astype(np.int64)
    total = int(counts.sum())
    cols = rng.choice(n, size=total, p=p).astype(np.int32)
    vals = rng.standard_normal(total) / np.sqrt(zbar)
    rows = np.repeat(np.arange(m, dtype=np.int64), counts)
    # one stable sort keeps, per row, each column's first draw
    key, first = np.unique(rows * n + cols, return_index=True)
    indices = (key % n).astype(np.int32)
    data = vals[first]
    indptr = np.zeros(m + 1, np.int64)
    np.cumsum(np.bincount(key // n, minlength=m), out=indptr[1:])

    rng = np.random.default_rng(seed + 1)
    x_true = np.zeros(n)
    support = rng.choice(n, size=max(n // 100, 10), replace=False)
    x_true[support] = rng.standard_normal(len(support)) * 3.0
    row_ids = np.repeat(np.arange(m), np.diff(indptr))
    logits = np.bincount(row_ids, weights=data * x_true[indices], minlength=m)
    scale = 2.5 / max(float(logits.std()), 1e-9)
    logits *= scale
    prob = 1.0 / (1.0 + np.exp(-logits))
    y = np.where(rng.random(m) < prob, 1.0, -1.0)
    return Data(m=m, n=n, indptr=indptr, indices=indices, ya=data * y[row_ids], y=y)


@dataclasses.dataclass(frozen=True)
class RowSchedule:
    """Which rows each bundle of each round takes.

    Team i owns rows [bounds[i], bounds[i+1]) and pads them to
    ``rows_local`` rows; bundle t of round r starts at team-local row
    ((r·τ/s + t)·s·b) mod rows_local. Padding rows are empty."""

    m: int
    p_r: int
    s: int
    b: int
    tau: int
    row_multiple: int

    @property
    def sb(self) -> int:
        return self.s * self.b

    @property
    def bundles(self) -> int:
        return self.tau // self.s

    @property
    def bounds(self) -> np.ndarray:
        return np.linspace(0, self.m, self.p_r + 1).astype(np.int64)

    @property
    def rows_local(self) -> int:
        most = int(np.diff(self.bounds).max())
        return -(-most // self.row_multiple) * self.row_multiple

    def bundle(self, r: int, team: int, t: int) -> np.ndarray:
        """Global row ids of bundle t of round r on ``team``, in bundle
        order; -1 marks a padding row."""
        lo, hi = self.bounds[team], self.bounds[team + 1]
        start = ((r * self.bundles + t) * self.sb) % self.rows_local
        local = start + np.arange(self.sb)
        return np.where(local < hi - lo, lo + local, -1)

    @classmethod
    def of(cls, m: int, schedule: dict, row_multiple: int | None = None) -> "RowSchedule":
        s, b = int(schedule["s"]), int(schedule["b"])
        return cls(m=m, p_r=int(schedule["p_r"]), s=s, b=b, tau=int(schedule["tau"]),
                   row_multiple=int(row_multiple or s * b))
