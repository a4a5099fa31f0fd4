"""Pallas TPU kernel: fused s-bundle (G, v) straight from ELL rows.

This is the engine's bundle primitive (Algorithm 3 lines 5-8 — the
``mkl_sparse_syrkd`` + SpMV hot spot) without ever materializing the
dense (sb × n) bundle in HBM. The old core solvers scattered the bundle
into a dense matrix every inner iteration (O(sb·n) HBM traffic per
bundle); here the dense panel only ever exists as a (sb × bk) VMEM tile,
built on the fly from the ELL (indices, values) pair:

  for each column panel k of width bk:
      panel[r, c] = Σ_a val[r, a] · [idx[r, a] == k·bk + c]
      G += panel @ panelᵀ          (MXU rank-k update)
      v += panel @ x[k·bk : k·bk+bk]

The panel build is a compare-against-iota one-hot contraction — an MXU/
VPU-friendly formulation of scatter (Pallas TPU has no in-kernel
scatter).

Column compaction (``_compact_columns``, shared by both backends): a
bundle touches at most sb·w distinct columns, so where those fit in
fewer panels than n the bundle's columns are first renumbered by one
sort of its sb·w indices, and x is gathered in that order; the walk
then covers ⌈sb·w/bk⌉ panels instead of ⌈n/bk⌉ (news20 at s·b = 64:
61, not 2,647). Columns keep their global order, so only their grouping
into panels changes. Narrow problems (sb·w ≥ n in panels) trace the
direct walk unchanged. Cost per bundle is O(sb·w·min(n, sb·w)) for the
expansion plus O(sb²·min(n, sb·w)) for the syrk, and one sort of sb·w
keys — on TPU the expansion is compute against VMEM-resident data.
Arithmetic caveat: the expansion term dominates the syrk when the ELL
width w exceeds sb, so heavy-tailed rows (w ≫ s·b, e.g. the url
dataset) favor a wider bundle or the dense oracle off-TPU — benchmarks
bench_kernels.py measures both sides.

The strict-lower mask (only l < j corrections are applied by the s-step
inner loop) lands on the final panel. Accumulation is float32
(MXU-faithful) regardless of input dtype.

Two tuning knobs, swept by ``repro.kernels.tune``:

* ``bk`` — column-panel width (the VMEM tile's second dimension);
* ``bm`` — optional row tile for the one-hot expansion: the (sb, w, bk)
  one-hot workspace is built ``bm`` rows at a time, shrinking the
  expansion working set from sb·w·bk to bm·w·bk words. ``bm=None``
  (default) is the original single-shot expansion; any ``bm`` is
  bitwise-identical to it (each row's contraction is independent).

Precision: ``precision="bf16"`` builds the panel in bfloat16 and runs
the MXU dots bf16-in / f32-accumulate (``preferred_element_type``);
G and v stay float32. ``precision="fp32"`` (default) traces exactly
the original kernel, with its dots at full f32 precision
(``dot_precision``): a TPU's default precision gives f32 operands one
bf16 pass, which on a v5e put a 3e-3 relative error on (G, v).

Interpret mode follows the platform (``default_interpret``): the
compiled Mosaic kernel on a TPU, the Pallas interpreter everywhere
else. ``interpret=False`` forces the compiled kernel, which is how the
compile-only tests build it for a described TPU from a CPU host.

VMEM per step: sb·w (idx + val) + sb·bk (one-hot workspace) + sb·sb (G)
+ bk (x panel) words.

Oracle: repro.kernels.ref.ell_gram_and_v_ref (the retired densify path).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.obs import metrics as obs_metrics

_PANELS_GAUGE = "ell_gram.panels_per_call"


def panels_walked(rows: int, width: int, n: int, bk: int) -> int:
    """Column panels one (G, v) call walks at panel width bk: ⌈n/bk⌉, or
    the ⌈rows·width/bk⌉ of its compacted columns where those are fewer."""
    return min(-(-n // bk), -(-(rows * width) // bk))


def _compact_columns(indices, x, n: int, bk: int):
    """Shared preamble for both backends: renumber the bundle's columns
    so the panel walk covers only the columns it touches.

    A bundle of sb rows of width w touches at most U = sb·w distinct
    columns. Where ⌈U/bk⌉ < ⌈n/bk⌉ (shapes are static, so this is a
    trace-time choice) the column ids are sorted, each entry gets the
    sorted position of its column's first occurrence as a local id in
    [0, U), and x is gathered in sorted order: the walk then has ⌈U/bk⌉
    panels. Columns keep their global order, so only their grouping into
    panels changes. A slot that holds a repeat of a column is referenced
    by no entry, so its panel column is zero; ELL pad entries (idx 0,
    val 0) share column 0's slot and add nothing. Otherwise the inputs
    pass through untouched and the direct walk is traced as before.

    Sets the trace-time gauge ``ell_gram.panels_per_call`` (labelled
    ``path=compacted|direct``) to the panels the traced call walks."""
    sb, w = indices.shape
    u = sb * w
    panels = panels_walked(sb, w, n, bk)
    if panels == -(-n // bk):
        obs_metrics.registry().gauge(_PANELS_GAUGE, path="direct").set(panels)
        return indices, x, n
    obs_metrics.registry().gauge(_PANELS_GAUGE, path="compacted").set(panels)
    pos = jax.lax.iota(jnp.int32, u)
    cols, order = jax.lax.sort((indices.reshape(u), pos), num_keys=1)
    first = jnp.concatenate([jnp.ones((1,), bool), cols[1:] != cols[:-1]])
    slot = jax.lax.cummax(jnp.where(first, pos, 0))
    # back to entry order by a second sort, which a v5e runs faster
    # than a scatter of the same pairs
    _, local = jax.lax.sort((order, slot), num_keys=1)
    x_c = x.at[cols].get(mode="promise_in_bounds", indices_are_sorted=True)
    return local.reshape(sb, w), x_c, u


def _prep_panels(values, x, n: int, bk: int):
    """Shared preamble for both backends: accumulation dtype + x padded
    to whole panels. f32 accumulation (MXU-faithful) for narrow dtypes;
    f64 stays f64 so the paper's FP64 Gram-conditioning runs keep their
    precision."""
    acc = jnp.float64 if values.dtype == jnp.float64 else jnp.float32
    n_pad = -(-n // bk) * bk
    x = x.astype(acc)
    if n_pad != n:
        x = jnp.pad(x, (0, n_pad - n))
    return acc, x, n_pad // bk


def default_interpret() -> bool:
    """Pallas interpret mode for the current platform: the compiled
    kernel on a TPU, the interpreter on every other backend."""
    return jax.default_backend() != "tpu"


def dot_precision(dtype):
    """Dot precision for operands of ``dtype``: full precision for f32
    and f64, the default (one MXU pass) for bf16."""
    return None if dtype == jnp.bfloat16 else jax.lax.Precision.HIGHEST


def _bf16_terms(values) -> jnp.ndarray:
    """(rows, w) f32 → (rows, 3, w) f32: three bf16-representable terms
    (hi, mid, lo) that sum back to ``values`` exactly."""
    hi = values.astype(jnp.bfloat16).astype(jnp.float32)
    rest = values - hi
    mid = rest.astype(jnp.bfloat16).astype(jnp.float32)
    return jnp.stack([hi, mid, rest - mid], axis=1)


def _panel_rows(indices, values, k, bk: int, dtype, split_f32: bool) -> jnp.ndarray:
    """One-hot contraction for one row chunk: (rows, bk) in ``dtype``.

    The contraction accumulates in at least f32 (Mosaic's matmul has no
    narrower accumulator) and the panel is cast to ``dtype`` after.
    Rows are deduplicated, so every output element has at most one
    nonzero term and the panel is exact either way. ``split_f32`` (the
    compiled kernel) builds an f32 panel from the three bf16 terms of
    each value against a bf16 one-hot in one pass: Mosaic's
    full-precision f32 contraction needs more scoped VMEM than a v5e
    grants at news20's width. Elsewhere the f32 contraction runs as is,
    which keeps XLA's rewrites of the panel's consumers, and so the
    CPU trajectories, the same on every backend."""
    local = indices - k * bk  # (rows, w)
    lanes = jax.lax.broadcasted_iota(jnp.int32, (1, 1, bk), 2)
    hit = local[:, :, None] == lanes  # (rows, w, bk)
    dims = (((2,), (1,)), ((0,), (0,)))
    if split_f32 and dtype == jnp.float32:
        terms = jax.lax.dot_general(
            _bf16_terms(values).astype(jnp.bfloat16), hit.astype(jnp.bfloat16),
            dimension_numbers=dims, preferred_element_type=jnp.float32,
        )  # (rows, 3, bk)
        return terms[:, 0, :] + terms[:, 1, :] + terms[:, 2, :]
    return jax.lax.dot_general(
        values[:, None, :].astype(dtype),  # (rows, 1, w); Mosaic cannot
        hit.astype(dtype),                 # reshape a packed bf16 vector
        dimension_numbers=dims,
        precision=dot_precision(dtype),
        preferred_element_type=jnp.promote_types(dtype, jnp.float32),
    )[:, 0, :].astype(dtype)  # (rows, bk)


def panel_from_ell(
    indices, values, k, bk: int, acc_dtype, compute_dtype=None, bm: int | None = None,
    split_f32: bool = False,
) -> jnp.ndarray:
    """Expand the ELL bundle's column panel k into a dense (sb, bk) tile.

    Panel-local one-hot contraction: entries outside [k·bk, (k+1)·bk)
    match no lane and vanish; ELL pad entries (idx 0, val 0) contribute
    zero value. Shared by the Pallas kernel body and the pure-jnp
    blocked path (shard_map-safe).

    ``compute_dtype`` (e.g. bfloat16) overrides the expansion dtype —
    None keeps ``acc_dtype``, the original path. ``bm`` tiles the
    expansion ``bm`` rows at a time (bitwise-identical: rows are
    independent); None builds all rows in one shot. ``split_f32``: see
    ``_panel_rows``."""
    dtype = acc_dtype if compute_dtype is None else compute_dtype
    sb = indices.shape[0]
    if bm is None or bm >= sb:
        return _panel_rows(indices, values, k, bk, dtype, split_f32)
    return jnp.concatenate(
        [
            _panel_rows(indices[r : r + bm], values[r : r + bm], k, bk, dtype, split_f32)
            for r in range(0, sb, bm)
        ],
        axis=0,
    )


def _ell_gram_kernel(
    idx_ref, val_ref, x_ref, g_ref, v_ref, *,
    n_panels: int, bk: int, compute_dtype=None, bm: int | None = None,
    split_f32: bool = False,
):
    k = pl.program_id(0)

    @pl.when(k == 0)
    def _init():
        g_ref[...] = jnp.zeros_like(g_ref)
        v_ref[...] = jnp.zeros_like(v_ref)

    panel = panel_from_ell(
        idx_ref[...], val_ref[...], k, bk, g_ref.dtype, compute_dtype, bm, split_f32
    )  # (sb, bk)
    xblk = x_ref[...]
    if compute_dtype is not None:
        xblk = xblk.astype(compute_dtype)
    prec = dot_precision(panel.dtype)
    g_ref[...] += jnp.dot(
        panel, panel.T, precision=prec, preferred_element_type=g_ref.dtype
    )
    v_ref[...] += jnp.dot(panel, xblk, precision=prec, preferred_element_type=v_ref.dtype)

    @pl.when(k == n_panels - 1)
    def _mask():
        sb = g_ref.shape[0]
        row = jax.lax.broadcasted_iota(jnp.int32, (sb, sb), 0)
        col = jax.lax.broadcasted_iota(jnp.int32, (sb, sb), 1)
        g_ref[...] = jnp.where(row > col, g_ref[...], 0.0)


def compute_dtype_for(precision: str):
    """The panel/MXU compute dtype for a schedule ``precision`` knob:
    None (trace the original fp32 path) or jnp.bfloat16."""
    if precision == "fp32":
        return None
    if precision == "bf16":
        return jnp.bfloat16
    raise ValueError(f"precision must be 'fp32' or 'bf16', got {precision!r}")


def ell_gram_and_v(
    indices: jnp.ndarray,  # (sb, w) int32
    values: jnp.ndarray,  # (sb, w)
    x: jnp.ndarray,  # (n,)
    *,
    n: int,
    bk: int = 512,
    bm: int | None = None,
    precision: str = "fp32",
    interpret: bool | None = None,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """(G, v) = (tril(Y Yᵀ, -1), Y·x) for the ELL bundle Y — scatter-free.

    ``n`` is the (local) column count; x is zero-padded to a multiple of
    ``bk`` so every grid step sees a full panel. ``interpret=None``
    takes the platform's mode (``default_interpret``).
    """
    sb, w = values.shape
    indices, x, n = _compact_columns(indices, x, n, bk)
    acc, x, n_panels = _prep_panels(values, x, n, bk)
    cd = compute_dtype_for(precision)
    interpret = default_interpret() if interpret is None else interpret

    g, v = pl.pallas_call(
        functools.partial(
            _ell_gram_kernel, n_panels=n_panels, bk=bk, compute_dtype=cd, bm=bm,
            split_f32=not interpret,
        ),
        grid=(n_panels,),
        in_specs=[
            pl.BlockSpec((sb, w), lambda k: (0, 0)),
            pl.BlockSpec((sb, w), lambda k: (0, 0)),
            pl.BlockSpec((bk, 1), lambda k: (k, 0)),
        ],
        out_specs=[
            pl.BlockSpec((sb, sb), lambda k: (0, 0)),
            pl.BlockSpec((sb, 1), lambda k: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((sb, sb), acc),
            jax.ShapeDtypeStruct((sb, 1), acc),
        ],
        interpret=interpret,
        name="ell_gram",
    )(indices, values.astype(acc), x[:, None])
    return g, v[:, 0]


def ell_gram_and_v_blocked(
    indices: jnp.ndarray,
    values: jnp.ndarray,
    x: jnp.ndarray,
    *,
    n: int,
    bk: int = 512,
    bm: int | None = None,
    precision: str = "fp32",
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Pure-jnp panel streaming — same scatter-free math as the Pallas
    kernel, expressed as a lax.scan over column panels.

    The XLA twin of the kernel (``gram="blocked"``): the VMEM-tile
    structure becomes an XLA loop whose working set is one (sb, bk)
    panel. The autotuner times it where the kernel would only run in
    the interpreter."""
    sb, w = values.shape
    indices, x, n = _compact_columns(indices, x, n, bk)
    acc, x, n_panels = _prep_panels(values, x, n, bk)
    cd = compute_dtype_for(precision)

    def panel_step(carry, k):
        g, v = carry
        panel = panel_from_ell(indices, values, k, bk, acc, cd, bm)
        xblk = jax.lax.dynamic_slice_in_dim(x, k * bk, bk)
        if cd is not None:
            xblk = xblk.astype(cd)
        prec = dot_precision(panel.dtype)
        return (
            g + jnp.dot(panel, panel.T, precision=prec, preferred_element_type=acc),
            v + jnp.dot(panel, xblk, precision=prec, preferred_element_type=acc),
        ), None

    (g, v), _ = jax.lax.scan(
        panel_step,
        (jnp.zeros((sb, sb), acc), jnp.zeros((sb,), acc)),
        jnp.arange(n_panels),
    )
    return jnp.tril(g, k=-1), v
