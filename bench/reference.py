"""Plain reference of the solver's first steps, computed on the host.

The same semantics as the program's s-step / HybridSGD round, written
straight from the method (arXiv:2501.07526, Algorithms 2-3) with numpy
and scipy.sparse and nothing of the program imported:

* a bundle Y is s·b rows of diag(y)·A;
* v = Y·x and G = tril(Y·Yᵀ, -1);
* the s corrections u_j = σ(-(v_j + (η/b)·G_j·u)) for j = 0..s-1, where
  G_j is the j-th block of b rows of G and u holds the earlier blocks;
* x ← x + (η/b)·Yᵀ·u;
* each of the p_r row teams runs τ/s bundles from the round's x and the
  round ends with the mean of the teams' weights;
* the loss is (1/m)·Σ log(1 + exp(-(diag(y)·A·x)_i)).

``precision`` sets the arithmetic: ``"float64"``, the yardstick, whose
own rounding lies far below the program's float32; or ``"high"``, float32
with every product made of three bfloat16 passes, the step below the
configuration's float32 at full precision, which makes the control.
``fault`` plants a defect to read what a broken program would give:
``"half_batch"`` (each step uses half its rows, scaled to their mean) or
``"no_exchange"`` (the (G, v) sum over the p_c column shards is left
out).
"""

from __future__ import annotations

import numpy as np
import scipy.sparse as sp

from bench.data import Data, RowSchedule

PRECISIONS = ("float64", "high")
FAULTS = (None, "half_batch", "no_exchange")


def _bf16_part(a: np.ndarray) -> np.ndarray:
    """float32 ``a`` rounded to bfloat16 (to nearest, ties to even), held
    in float32."""
    bits = np.ascontiguousarray(a, np.float32).view(np.uint32)
    bits = bits + np.uint32(0x7FFF) + ((bits >> 16) & np.uint32(1))
    return (bits & np.uint32(0xFFFF0000)).view(np.float32)


def _split(a):
    """a ≈ hi + lo, both bfloat16 values held in float32 (dense or sparse)."""
    if sp.issparse(a):
        a = a.tocsr()
        hi = a.copy()
        hi.data = _bf16_part(a.data)
        lo = a.copy()
        lo.data = _bf16_part(a.data - hi.data)
        return hi, lo
    hi = _bf16_part(a)
    return hi, _bf16_part(a - hi)


def _dot(a, b, precision: str):
    """a @ b. ``"high"``: hi·hi + hi·lo + lo·hi, each pass exact (products
    of bfloat16 values fit a float32) and summed in float32."""
    if precision == "float64":
        out = a @ b
        return out.toarray() if sp.issparse(out) else out
    (ah, al), (bh, bl) = _split(a), _split(b)
    out = ah @ bh + ah @ bl + al @ bh
    return out.toarray() if sp.issparse(out) else np.asarray(out, np.float32)


def _sigmoid(z):
    """σ(z), stable for large |z|."""
    e = np.exp(-np.abs(z))
    return np.where(z >= 0, 1 / (1 + e), e / (1 + e))


class Reference:
    """The reference solver on ``data`` under ``schedule`` (the spec's
    schedule dict: p_r, s, b, tau, eta)."""

    def __init__(self, data: Data, schedule: dict, row_multiple: int | None = None,
                 precision: str = "float64", fault: str | None = None, p_c: int = 1):
        if precision not in PRECISIONS:
            raise ValueError(f"precision={precision!r} not in {PRECISIONS}")
        if fault not in FAULTS:
            raise ValueError(f"fault={fault!r} not in {FAULTS}")
        self.dtype = np.float64 if precision == "float64" else np.float32
        self.rows = RowSchedule.of(data.m, schedule, row_multiple)
        self.eta = float(schedule["eta"])
        self.precision = precision
        self.fault = fault
        self.m, self.n = data.m, data.n
        self.a = sp.csr_matrix((data.ya.astype(self.dtype), data.indices, data.indptr),
                               shape=(data.m, data.n))
        cols = np.arange(data.n) % p_c
        self.shards = ([sp.diags((cols == j).astype(self.dtype)) for j in range(p_c)]
                       if fault == "no_exchange" else None)

    def loss(self, x) -> float:
        margin = _dot(self.a, x, self.precision)
        return float(np.sum(np.logaddexp(0.0, -margin), dtype=self.dtype) / self.m)

    def _bundle(self, x, rows: np.ndarray):
        rs, dt, p = self.rows, self.dtype, self.precision
        s, b = rs.s, rs.b
        k = self.eta / b
        y = sp.diags((rows >= 0).astype(dt)) @ self.a[np.maximum(rows, 0)]
        step = np.zeros_like(x)
        for ys in ([y @ d for d in self.shards] if self.shards else [y]):
            v = _dot(ys, x, p)
            g = np.tril(_dot(ys, ys.T, p), -1).astype(dt)
            u = np.zeros(s * b, dt)
            for j in range(s):
                z = v[j * b:(j + 1) * b] + dt(k) * _dot(g[j * b:(j + 1) * b], u, p)
                uj = _sigmoid(-z).astype(dt)
                if self.fault == "half_batch":
                    uj = np.where(np.arange(b) < b // 2, dt(2) * uj, dt(0))
                u[j * b:(j + 1) * b] = uj
            step += dt(k) * _dot(ys.T, u, p)
        return (x + step).astype(dt)

    def round(self, x, r: int):
        rs = self.rows
        teams = []
        for team in range(rs.p_r):
            xt = x
            for t in range(rs.bundles):
                xt = self._bundle(xt, rs.bundle(r, team, t))
            teams.append(xt)
        return np.mean(teams, axis=0, dtype=self.dtype)

    def steps(self, rounds_per_step: int, steps: int) -> list[tuple[float, np.ndarray]]:
        """(loss, weights) after each of ``steps`` steps of
        ``rounds_per_step`` rounds from x = 0."""
        x = np.zeros(self.n, self.dtype)
        out, r = [], 0
        for _ in range(steps):
            for _ in range(rounds_per_step):
                x = self.round(x, r)
                r += 1
            out.append((self.loss(x), x.copy()))
        return out

    def curve(self, rounds: int, every: int) -> list[float]:
        """The loss after every ``every`` rounds, up to ``rounds``, from
        x = 0."""
        x = np.zeros(self.n, self.dtype)
        out = []
        for r in range(rounds):
            x = self.round(x, r)
            if (r + 1) % every == 0:
                out.append(self.loss(x))
        return out
