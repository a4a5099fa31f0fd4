"""The optimization problem (paper §3), generalized to any registered
convex objective (repro.core.objective):

    min_x f(x) = (1/m) Σ_i ℓ(y_i · a_i x) + (λ/2)‖x‖²

diag(y)·A is precomputed once (the paper does the same), so the sampled
mini-batch gradient is  g = -(1/b) (S·diag(y)A)ᵀ u + λx  with
u = objective.residual(S·diag(y)A·x). The paper's logistic model is the
default objective; ``squared_hinge`` and ``least_squares`` plug into
the identical machinery.
"""

from __future__ import annotations

import dataclasses
import warnings

import jax

import jax.numpy as jnp
import numpy as np

from repro.core.objective import LOGISTIC, Objective, get_objective
from repro.sparse.csr import CSRMatrix
from repro.sparse.ell import EllBlock, ell_from_csr


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class Problem:
    """diag(y)·A in padded-ELL layout + metadata + the objective.

    ``rows_valid`` masks padded (all-zero) rows out of the loss; padded
    rows contribute zero gradient automatically (zero A-row).
    ``objective`` is static: changing the loss re-specializes the
    jitted engine exactly like changing a shape would.
    """

    ya: EllBlock  # diag(y)·A, possibly row-padded
    rows_valid: jnp.ndarray  # (padded_m,) bool
    m: int = dataclasses.field(metadata=dict(static=True))  # true sample count
    n: int = dataclasses.field(metadata=dict(static=True))
    objective: Objective = dataclasses.field(
        default=LOGISTIC, metadata=dict(static=True)
    )

    @property
    def padded_m(self) -> int:
        return self.ya.rows


# Deprecated alias (one release): the problem is no longer
# logistic-specific — construct a ``Problem`` (or pass ``objective=`` to
# ``make_problem``). Kept as a true alias so isinstance checks and
# pytree registration keep working for old imports.
LogisticProblem = Problem


def pad_rows_to(a: CSRMatrix, multiple: int) -> int:
    return -(-a.m // multiple) * multiple


def make_problem(
    a: CSRMatrix, y: np.ndarray, row_multiple: int = 1, dtype=jnp.float32,
    ell_width: int | None = None, objective: str | Objective = LOGISTIC,
) -> Problem:
    """Build the device problem. Rows are padded to ``row_multiple`` (the
    paper pads m ≡ 0 mod s_max·b so cyclic batches never wrap).
    ``objective`` is a registry name or an ``Objective`` instance."""
    obj = get_objective(objective)
    ya_csr = a.scale_rows(y)
    padded_m = pad_rows_to(a, row_multiple)
    ell = ell_from_csr(ya_csr, width=ell_width, dtype=dtype)
    if padded_m > a.m:
        pad = padded_m - a.m
        ell = EllBlock(
            indices=jnp.concatenate([ell.indices, jnp.zeros((pad, ell.width), jnp.int32)]),
            values=jnp.concatenate([ell.values, jnp.zeros((pad, ell.width), ell.values.dtype)]),
            n=ell.n,
        )
    valid = jnp.arange(padded_m) < a.m
    return Problem(ya=ell, m=a.m, n=a.n, rows_valid=valid, objective=obj)


def problem_loss(problem: Problem, x: jnp.ndarray) -> jnp.ndarray:
    """f(x) over all m samples under the problem's objective:
    (1/m) Σ ℓ(margin) + (l2/2)‖x‖², with margin = (ya·x) — the label
    sign is folded into ya = diag(y)A."""
    from repro.sparse.ell import ell_matvec

    return margin_loss(problem, ell_matvec(problem.ya, x), x)


def margin_loss(problem: Problem, margin: jnp.ndarray, x: jnp.ndarray) -> jnp.ndarray:
    """``problem_loss`` from the margins ya·x however they were computed."""
    losses = problem.objective.pointwise_loss(margin)
    losses = jnp.where(problem.rows_valid, losses, 0.0)
    f = jnp.sum(losses) / problem.m
    if problem.objective.l2:
        f = f + 0.5 * problem.objective.l2 * jnp.sum(x * x)
    return f


# ---- deprecated re-exports (one release) ----------------------------
#
# The canonical implementations moved to the objective layer
# (repro.core.objective.LogisticObjective) and ``problem_loss``. These
# wrappers keep old imports working — downstream code and
# docs/paper_map.md references don't silently break — but warn.


def sigmoid_residual(z: jnp.ndarray) -> jnp.ndarray:
    """Deprecated: use ``Objective.residual`` (the logistic instance is
    ``repro.core.objective.LOGISTIC``)."""
    warnings.warn(
        "sigmoid_residual is deprecated; use repro.core.objective.LOGISTIC"
        ".residual (or the problem's own objective)",
        DeprecationWarning,
        stacklevel=2,
    )
    return LOGISTIC.residual(z)


def full_loss(problem: Problem, x: jnp.ndarray) -> jnp.ndarray:
    """Deprecated: use ``problem_loss`` — the objective-aware full
    objective (identical values for the default logistic problem)."""
    warnings.warn(
        "full_loss is deprecated; use repro.core.problem.problem_loss",
        DeprecationWarning,
        stacklevel=2,
    )
    return problem_loss(problem, x)
