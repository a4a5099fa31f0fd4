"""Pallas TPU kernels for the paper's compute hot spots.

  ell_gram   — the engine's bundle primitive: fused tril(YYᵀ) + Y·x
               straight from ELL rows, scatter-free (the
               mkl_sparse_syrkd hot spot of Algorithm 3)
  sstep_inner — the s-step correction loop fused into one launch
               (G, v, u stay VMEM-resident across all s steps)

ref.py: pure-jnp oracles — including the retired (sb × n) densify
bundle path, kept only as the parity oracle. The pre-engine dense-panel
Gram (``gram.py``), the BSR matmul (``bsr_matmul.py``), and their
``ops.py`` wrappers were dead paths off the live bundle pipeline and
have been removed; ``repro.sparse.bsr`` keeps the BSR *layout* (and its
jnp reference matvec) for the format tests.
Interpret mode follows the platform (``ell_gram.default_interpret``):
the compiled Mosaic kernels on a TPU ("TPU v5 lite" is the v5e's
``device_kind``), the Pallas interpreter on every other backend.
"""

from repro.kernels.ell_gram import ell_gram_and_v, ell_gram_and_v_blocked
from repro.kernels.sstep_inner import sstep_inner

__all__ = [
    "ell_gram_and_v",
    "ell_gram_and_v_blocked",
    "sstep_inner",
]
