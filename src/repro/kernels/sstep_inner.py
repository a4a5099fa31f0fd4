"""Pallas TPU kernel for the s-step inner correction loop (Alg 3,
lines 9-14).

After the Gram Allreduce, every rank runs s sequential corrections:

    z_j = v_j + (η/b) · G[j·b:(j+1)b, :] · u
    u_j = 1 / (1 + exp(z_j))        (u accumulates block by block)

The loop is latency-bound at b-vector granularity: s HBM round trips
for (G-row-panel, u) per bundle if expressed as XLA ops. The kernel
keeps G (sb × sb), v and the accumulating u in VMEM for the whole
bundle — one launch, zero intermediate HBM traffic.

VMEM: sb² + 2·sb f32 (sb = 512 → 1.05 MB, well inside budget).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.ell_gram import compute_dtype_for, default_interpret, dot_precision


def _inner_kernel(
    g_ref, v_ref, u_ref, *, s: int, b: int, eta_over_b: float, compute_dtype=None
):
    u_ref[...] = jnp.zeros_like(u_ref)

    def step(j, _):
        # z_j = v_j + (η/b)·G_panel·u   (u zero beyond filled blocks;
        # G is strictly lower so in-block terms multiply zeros)
        panel = g_ref[pl.dslice(j * b, b), :]  # (b, sb)
        u = u_ref[:, 0]
        if compute_dtype is not None:
            panel = panel.astype(compute_dtype)
            u = u.astype(compute_dtype)
        zj = v_ref[pl.dslice(j * b, b), 0] + eta_over_b * (
            jnp.dot(panel, u, precision=dot_precision(panel.dtype),
                    preferred_element_type=jnp.float32)
        )
        uj = jnp.where(zj >= 0, jnp.exp(-zj) / (1 + jnp.exp(-zj)), 1 / (1 + jnp.exp(zj)))
        u_ref[pl.dslice(j * b, b), 0] = uj.astype(u_ref.dtype)
        return 0

    jax.lax.fori_loop(0, s, step, 0)


def sstep_inner(
    g: jnp.ndarray,  # (sb, sb) strictly-lower Gram
    v: jnp.ndarray,  # (sb,)
    s: int,
    b: int,
    eta: float,
    *,
    precision: str = "fp32",
    interpret: bool | None = None,
) -> jnp.ndarray:
    """u (sb,) such that u_j = sigmoid_residual(v_j + (η/b) Σ_{l<j} G_{jl} u_l).

    ``precision="bf16"`` runs the G-panel·u MXU dot bf16-in /
    f32-accumulate; z, the residual, and u stay float32.
    ``interpret=None`` takes the platform's mode (``default_interpret``)."""
    cd = compute_dtype_for(precision)
    sb = s * b
    assert g.shape == (sb, sb) and v.shape == (sb,)
    out = pl.pallas_call(
        functools.partial(
            _inner_kernel, s=s, b=b, eta_over_b=eta / b, compute_dtype=cd
        ),
        grid=(1,),
        in_specs=[
            pl.BlockSpec((sb, sb), lambda i: (0, 0)),
            pl.BlockSpec((sb, 1), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((sb, 1), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((sb, 1), jnp.float32),
        interpret=default_interpret() if interpret is None else interpret,
    )(g.astype(jnp.float32), v.astype(jnp.float32)[:, None])
    return out[:, 0]


def sstep_inner_ref(g, v, s: int, b: int, eta: float) -> jnp.ndarray:
    """Pure-jnp oracle — the same loop the core solver runs (at the
    logistic default; the VMEM kernel hardcodes the logistic residual)."""
    from repro.core.objective import LOGISTIC

    def inner(u_acc, j):
        zj = jax.lax.dynamic_slice_in_dim(v, j * b, b) + (eta / b) * (
            jax.lax.dynamic_slice_in_dim(g, j * b, b, axis=0) @ u_acc
        )
        uj = LOGISTIC.residual(zj)
        return jax.lax.dynamic_update_slice_in_dim(u_acc, uj, j * b, axis=0), None

    u, _ = jax.lax.scan(inner, jnp.zeros(s * b, v.dtype), jnp.arange(s))
    return u
