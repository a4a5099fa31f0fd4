"""Pluggable convex objectives — the paper's §3 problem statement,
generalized to the regularized GLM / margin-loss family.

The HybridSGD machinery (s-step Gram bundles, inner corrections,
row-team averaging) is derived for logistic regression but is generic
to any pointwise *margin* loss: with Y = S·diag(y)·A the sampled rows
and z = Y·x the margins, the mini-batch gradient of

    f(x) = (1/m) Σ_i ℓ(z_i) + (λ/2)‖x‖²          (λ = l2, optional)

is  g = -(1/b)·Yᵀ·u(z) + λ·x  with  u(z) = -ℓ′(z),  so one SGD step is

    x ← (1 - ηλ)·x + (η/b)·Yᵀ·u(Y·x).

Everything the engine needs from the model is therefore two pointwise
maps — ``residual(z) = -ℓ′(z)`` and ``pointwise_loss(z) = ℓ(z)`` — plus
the decay scalar ``l2``. An ``Objective`` packages exactly that; the
engine, the shard_map executor, and the loss probes consume it and
never mention a specific loss again (Devarakonda & Demmel apply the
same s-step trick to the whole regularized GLM family).

Registered losses (margins z = y·aᵀx, labels y ∈ {±1} folded into Y):

  logistic       ℓ(z) = log(1 + e^{-z});        u(z) = 1/(1 + e^{z})
  squared_hinge  ℓ(z) = max(0, 1 - z)²;         u(z) = 2·max(0, 1 - z)
  least_squares  ℓ(z) = ½(1 - z)²;              u(z) = 1 - z

L2 semantics in the s-step bundle (exact, not approximate): with
ρ = 1 - ηλ the unrolled recurrence is

    z_j = ρ^j·v_j + (η/b)·Σ_{l<j} ρ^{j-1-l}·G_{jl}·u_l
    x_s = ρ^s·x_0 + (η/b)·Yᵀ·[ρ^{s-1-l}·u_l]_l

which ``repro.core.engine.inner_corrections`` implements by carrying
the ρ-rescaled residual vector (so the returned u is already the
ρ^{s-1-l}-weighted one the Yᵀ apply needs). At λ = 0 every factor is
skipped at trace time — the logistic default routes through bitwise the
same computation as before this layer existed.
"""

from __future__ import annotations

import dataclasses
import math
from typing import ClassVar

import jax
import jax.numpy as jnp

# exp(t) for float32 t ≤ 0 by Cody-Waite range reduction, t = k·ln2 + r
# with |r| ≤ ln2/2 (ln2 split so k·_LN2_HI is exact), a degree-7 Taylor
# polynomial in r (truncation below 0.1 ulp) and 2^k from the exponent
# bits: within about 1 ulp, unbiased, from float32 multiplies and adds
# alone. A v5e's float32 exp is 16 ulps off on average and biased low,
# and every weight update is η/b·Yᵀ·u with u built on exp, so the bias
# reaches the weights (at 1e-6 relative after 12 rcv1 rounds, ten times
# float32's own rounding).
_LN2_HI, _LN2_LO = 0.693359375, -2.12194440e-4
_EXP_COEFFS = tuple(1.0 / math.factorial(i) for i in range(7, -1, -1))


def exp_nonpositive(t: jnp.ndarray) -> jnp.ndarray:
    """exp(t) for t ≤ 0, to float32's own accuracy on every backend
    (other dtypes take ``jnp.exp``). t below -87 reads as exp(-87),
    float32's least normal number, rather than a denormal or 0."""
    if t.dtype != jnp.float32:
        return jnp.exp(t)
    t = jnp.maximum(t, -87.0)
    k = jnp.round(t * (1.0 / math.log(2.0)))
    r = (t - k * _LN2_HI) - k * _LN2_LO
    p = jnp.full_like(r, _EXP_COEFFS[0])
    for c in _EXP_COEFFS[1:]:
        p = p * r + c
    two_k = jax.lax.bitcast_convert_type((k.astype(jnp.int32) + 127) << 23, jnp.float32)
    return p * two_k


@dataclasses.dataclass(frozen=True)
class Objective:
    """A pointwise margin loss + optional L2 decay.

    Frozen and hashable on purpose: objectives ride as *static* fields
    on the problem pytrees (``Problem`` / ``TeamProblem`` /
    ``Hybrid2DProblem``), so a change of objective re-specializes the
    jitted engine exactly like a change of shape would.

    l2   the ridge coefficient λ in f(x) = (1/m)Σℓ + (λ/2)‖x‖².
         0.0 (default) means unregularized — and is special-cased at
         trace time so the λ = 0 computation is bitwise identical to
         the pre-objective code path.
    """

    l2: float = 0.0
    name: ClassVar[str] = "abstract"

    def __post_init__(self):
        if not math.isfinite(self.l2) or self.l2 < 0.0:
            raise ValueError(f"l2={self.l2} must be finite and ≥ 0")

    # -- the two pointwise maps the engine consumes --

    def residual(self, z: jnp.ndarray) -> jnp.ndarray:
        """u(z) = -ℓ′(z): the batch update is x += (η/b)·Yᵀ·u(Yx)."""
        raise NotImplementedError

    def pointwise_loss(self, z: jnp.ndarray) -> jnp.ndarray:
        """ℓ(z) per sample (the L2 term is added by the problem-level
        loss, where ‖x‖² is available)."""
        raise NotImplementedError


@dataclasses.dataclass(frozen=True)
class LogisticObjective(Objective):
    """ℓ(z) = log(1 + e^{-z}) — the paper's model, computed stably."""

    name: ClassVar[str] = "logistic"

    def residual(self, z: jnp.ndarray) -> jnp.ndarray:
        # u = 1/(1+exp(z)), stable for large |z|: e^{-z}/(1+e^{-z}) for
        # z ≥ 0, 1/(1+e^{z}) below, from one exp of -|z|
        e = exp_nonpositive(-jnp.abs(z))
        return jnp.where(z >= 0, e, 1.0) / (1 + e)

    def pointwise_loss(self, z: jnp.ndarray) -> jnp.ndarray:
        return jnp.logaddexp(0.0, -z)


@dataclasses.dataclass(frozen=True)
class SquaredHingeObjective(Objective):
    """ℓ(z) = max(0, 1 - z)² — the L2-SVM loss (differentiable, convex;
    the margin form Local-SGD papers evaluate)."""

    name: ClassVar[str] = "squared_hinge"

    def residual(self, z: jnp.ndarray) -> jnp.ndarray:
        return 2.0 * jnp.maximum(0.0, 1.0 - z)

    def pointwise_loss(self, z: jnp.ndarray) -> jnp.ndarray:
        return jnp.square(jnp.maximum(0.0, 1.0 - z))


@dataclasses.dataclass(frozen=True)
class LeastSquaresObjective(Objective):
    """ℓ(z) = ½(1 - z)² — least-squares classification on ±1 labels
    (equivalently ridge regression on the margins)."""

    name: ClassVar[str] = "least_squares"

    def residual(self, z: jnp.ndarray) -> jnp.ndarray:
        return 1.0 - z

    def pointwise_loss(self, z: jnp.ndarray) -> jnp.ndarray:
        return 0.5 * jnp.square(1.0 - z)


OBJECTIVES: dict[str, type[Objective]] = {
    LogisticObjective.name: LogisticObjective,
    SquaredHingeObjective.name: SquaredHingeObjective,
    LeastSquaresObjective.name: LeastSquaresObjective,
}

LOGISTIC = LogisticObjective()


def get_objective(objective: str | Objective, l2: float = 0.0) -> Objective:
    """Resolve a registry name (+ l2) to an ``Objective`` instance.

    An already-constructed ``Objective`` passes through unchanged —
    except that asking for a *different* nonzero l2 at the same time is
    ambiguous and rejected.
    """
    if isinstance(objective, Objective):
        if l2 and objective.l2 != l2:
            raise ValueError(
                f"objective already carries l2={objective.l2}; conflicting l2={l2}"
            )
        return objective
    if objective not in OBJECTIVES:
        raise ValueError(
            f"objective={objective!r} not in registry {sorted(OBJECTIVES)}"
        )
    return OBJECTIVES[objective](l2=l2)
