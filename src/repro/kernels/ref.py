"""Pure-jnp oracles for every Pallas kernel (allclose-tested)."""

from __future__ import annotations

import jax
import jax.numpy as jnp


def bsr_matmat_ref(tiles, block_cols, x) -> jnp.ndarray:
    """Y = A @ X via dense gather-einsum on the blocked layout."""
    n_brows, max_blocks, bm, bn = tiles.shape
    k = x.shape[1]
    x_blocked = x.reshape(-1, bn, k)
    gathered = jnp.take(x_blocked, block_cols, axis=0)  # (nbr, maxb, bn, k)
    y = jnp.einsum("rjab,rjbk->rak", tiles, gathered)
    return y.reshape(n_brows * bm, k)


def bsr_matvec_ref(tiles, block_cols, x) -> jnp.ndarray:
    return bsr_matmat_ref(tiles, block_cols, x[:, None])[:, 0]


def gram_tril_ref(y) -> jnp.ndarray:
    """G = tril(Y Yᵀ, -1), f32 accumulation (matches the kernel)."""
    return jnp.tril(jnp.dot(y, y.T, preferred_element_type=jnp.float32), k=-1)


def gram_and_v_ref(y, x) -> tuple[jnp.ndarray, jnp.ndarray]:
    hi = jax.lax.Precision.HIGHEST  # the oracle is exact f32 on every chip
    return (
        jnp.tril(jnp.dot(y, y.T, precision=hi, preferred_element_type=jnp.float32), k=-1),
        jnp.dot(y, x, precision=hi, preferred_element_type=jnp.float32),
    )


def densify_bundle_ref(indices, values, n: int) -> jnp.ndarray:
    """Scatter the (sb, w) ELL bundle into a dense (sb, n) matrix.

    This is the retired inner-loop path of the pre-engine solvers, kept
    as the parity oracle for the scatter-free ELL Gram kernel (and as
    the dense baseline in benchmarks/bench_kernels.py)."""
    sb = values.shape[0]
    dense = jnp.zeros((sb, n), values.dtype)
    return dense.at[jnp.arange(sb)[:, None], indices].add(values)


def ell_gram_and_v_ref(indices, values, x, n: int) -> tuple[jnp.ndarray, jnp.ndarray]:
    """(tril(YYᵀ,-1), Y·x) via the dense scatter — the bundle oracle."""
    dense = densify_bundle_ref(indices, values.astype(jnp.float32), n)
    return gram_and_v_ref(dense, x.astype(jnp.float32))
