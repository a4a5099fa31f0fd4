"""Pallas kernels vs pure-jnp oracles (interpret mode), shape/dtype
sweeps per the kernel-validation contract. Covers the two live kernels
— the ELL-Gram bundle primitive and the fused s-step correction loop —
against the ``repro.kernels.ref`` oracles (the retired dense-panel and
BSR kernels are gone; their oracles remain the parity reference)."""

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.ell_gram import ell_gram_and_v, ell_gram_and_v_blocked
from repro.sparse.synthetic import make_skewed_csr


def tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 else dict(rtol=2e-4, atol=2e-4)


@settings(max_examples=20, deadline=None)
@given(
    sb=st.sampled_from([8, 32, 64]),
    n=st.integers(10, 2000),
    width=st.integers(1, 24),
    bk=st.sampled_from([128, 256, 512]),
    seed=st.integers(0, 999),
)
def test_ell_gram_sweep(sb, n, width, bk, seed):
    """Both live bundle implementations == the densify oracle over
    random ELL bundles (duplicate column ids included)."""
    rng = np.random.default_rng(seed)
    width = min(width, n)
    idx = jnp.asarray(rng.integers(0, n, size=(sb, width)).astype(np.int32))
    val = jnp.asarray(rng.standard_normal((sb, width)).astype(np.float32))
    x = jnp.asarray(rng.standard_normal(n).astype(np.float32))
    g_ref, v_ref = ref.ell_gram_and_v_ref(idx, val, x, n)
    for impl in (
        lambda: ell_gram_and_v(idx, val, x, n=n, bk=bk),
        lambda: ell_gram_and_v_blocked(idx, val, x, n=n, bk=bk),
    ):
        g, v = impl()
        np.testing.assert_allclose(np.asarray(g), np.asarray(g_ref), rtol=1e-3, atol=1e-3)
        np.testing.assert_allclose(np.asarray(v), np.asarray(v_ref), rtol=1e-3, atol=1e-3)


def test_ell_gram_is_strictly_lower():
    rng = np.random.default_rng(2)
    idx = jnp.asarray(rng.integers(0, 300, size=(32, 9)).astype(np.int32))
    val = jnp.asarray(rng.standard_normal((32, 9)).astype(np.float32))
    x = jnp.asarray(rng.standard_normal(300).astype(np.float32))
    g, _ = ell_gram_and_v(idx, val, x, n=300, bk=128)
    assert np.all(np.triu(np.asarray(g)) == 0.0)


def test_densify_oracle_matches_csr():
    """The oracle's densify == the CSR dense expansion (the retired
    scatter path, kept as the reference the live kernels verify
    against)."""
    a = make_skewed_csr(64, 257, 9, 0.5, seed=8)
    from repro.core.problem import make_problem

    prob = make_problem(a, np.ones(64), row_multiple=64)
    dense = np.asarray(
        ref.densify_bundle_ref(prob.ya.indices, prob.ya.values, 257)
    )
    np.testing.assert_allclose(dense[:64], a.to_dense().astype(np.float32), rtol=1e-6, atol=1e-6)


@settings(max_examples=15, deadline=None)
@given(
    s=st.sampled_from([1, 2, 4, 8]),
    b=st.sampled_from([4, 8, 16]),
    eta=st.floats(0.01, 1.0),
    seed=st.integers(0, 999),
)
def test_sstep_inner_kernel_sweep(s, b, eta, seed):
    """Fused correction-loop kernel == the core solver's scan (V1's
    inner loop, VMEM-resident)."""
    from repro.kernels.sstep_inner import sstep_inner, sstep_inner_ref

    rng = np.random.default_rng(seed)
    sb = s * b
    y = rng.standard_normal((sb, 200)).astype(np.float32)
    g = jnp.asarray(np.tril(y @ y.T, -1))
    v = jnp.asarray(rng.standard_normal(sb).astype(np.float32))
    got = sstep_inner(g, v, s, b, eta)
    want = sstep_inner_ref(g, v, s, b, eta)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)


def test_sstep_inner_kernel_in_solver_context():
    """End-to-end: kernel-computed u reproduces one s-step bundle's
    update inside the real solver pipeline (Gram/v from the live ELL
    kernel)."""
    from repro.core.problem import make_problem
    from repro.core.sgd import batch_rows, run_sgd
    from repro.kernels.sstep_inner import sstep_inner
    from repro.sparse.ell import ell_rmatvec

    rng = np.random.default_rng(3)
    a = make_skewed_csr(128, 300, 10, 0.8, seed=9)
    y = np.where(rng.random(128) < 0.5, 1.0, -1.0)
    s, b, eta = 4, 8, 0.1
    prob = make_problem(a, y, row_multiple=s * b)
    x = jnp.asarray(rng.standard_normal(300).astype(np.float32))

    bundle = batch_rows(prob.ya, jnp.int32(0), s * b)
    g, v = ell_gram_and_v(bundle.indices, bundle.values, x, n=300, bk=128)
    u = sstep_inner(g, v, s, b, eta)
    x_new = x + (eta / b) * ell_rmatvec(bundle, u)

    # oracle: s plain SGD steps
    x_ref, _ = run_sgd(prob, x, b, eta, s)
    np.testing.assert_allclose(np.asarray(x_new), np.asarray(x_ref), rtol=1e-4, atol=1e-4)


def _interpret_flags(fn, *args):
    """The ``interpret`` mode of every pallas_call ``fn`` traces to."""
    import jax

    eqns = jax.make_jaxpr(fn)(*args).jaxpr.eqns
    return [e.params["interpret"] for e in eqns if e.primitive.name == "pallas_call"]


def test_kernels_take_interpret_mode_from_the_platform(monkeypatch):
    """No option selects interpret mode: the kernels interpret off-TPU
    and compile on a TPU; only an explicit ``interpret=`` overrides."""
    import jax

    from repro.kernels.sstep_inner import sstep_inner

    idx, val, x = jnp.zeros((8, 4), jnp.int32), jnp.ones((8, 4)), jnp.ones(64)
    g, v = jnp.zeros((8, 8)), jnp.zeros(8)

    def gram(**kw):
        return _interpret_flags(
            lambda i, w, z: ell_gram_and_v(i, w, z, n=64, bk=32, **kw), idx, val, x
        )

    def inner():
        return _interpret_flags(lambda a, b: sstep_inner(a, b, 2, 4, 0.1), g, v)

    assert jax.default_backend() == "cpu"
    assert gram() == [True] and inner() == [True]
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert gram() == [False] and inner() == [False]
    assert gram(interpret=True) == [True]


@pytest.mark.parametrize("split_f32", [False, True], ids=["f32", "bf16-terms"])
def test_f32_panel_is_exact(split_f32):
    """Both f32 panel builds place every ELL value bit for bit: the plain
    contraction, and the three bf16 terms the compiled kernel sums
    (values over many binades, with all 24 significand bits in use)."""
    import jax

    from repro.kernels.ell_gram import _panel_rows

    rng = np.random.default_rng(5)
    rows, w, bk = 16, 24, 128
    idx = np.stack([rng.choice(bk, w, replace=False) for _ in range(rows)]).astype(np.int32)
    val = (rng.standard_normal((rows, w)) * 10.0 ** rng.integers(-6, 6, (rows, w)))
    val = val.astype(np.float32)
    panel = jax.jit(lambda i, v: _panel_rows(i, v, 0, bk, jnp.float32, split_f32))(idx, val)
    want = np.zeros((rows, bk), np.float32)
    np.put_along_axis(want, idx, val, axis=1)
    np.testing.assert_array_equal(np.asarray(panel), want)


def _wide_bundle():
    """A bundle that touches far fewer columns than n: sb·w = 96 < n =
    5,000. Rows of unequal length (ELL pad entries: idx 0, val 0), one
    column shared by many rows, the last column n−1, and small-integer
    values, so every sum of products is exact in f32 whatever the order."""
    rng = np.random.default_rng(11)
    sb, w, n = 16, 6, 5_000
    idx = rng.integers(0, n, size=(sb, w)).astype(np.int32)
    val = rng.integers(-4, 5, size=(sb, w)).astype(np.float32)
    for r in range(sb):  # row r keeps 1 + r % w entries
        idx[r, 1 + r % w:] = 0
        val[r, 1 + r % w:] = 0.0
    idx[1:9, 0] = 777  # a column shared by eight rows
    idx[0, 0], val[0, 0] = n - 1, 3.0  # the last column
    x = rng.standard_normal(n).astype(np.float32)
    return jnp.asarray(idx), jnp.asarray(val), jnp.asarray(x), n


@pytest.mark.parametrize("impl", [ell_gram_and_v, ell_gram_and_v_blocked],
                         ids=["pallas", "blocked"])
def test_compacted_walk_matches_full_walk(impl, monkeypatch):
    """Compacting the bundle's columns changes only how they are grouped
    into panels: G is the full walk's bit for bit on exact data, v within
    f32 rounding, and both match the dense oracle."""
    import repro.kernels.ell_gram as ell_gram

    idx, val, x, n = _wide_bundle()
    g_c, v_c = impl(idx, val, x, n=n, bk=32)
    monkeypatch.setattr(ell_gram, "_compact_columns", lambda i, z, n, bk: (i, z, n))
    g_full, v_full = impl(idx, val, x, n=n, bk=32)
    np.testing.assert_array_equal(np.asarray(g_c), np.asarray(g_full))
    np.testing.assert_allclose(np.asarray(v_c), np.asarray(v_full), rtol=1e-6, atol=1e-5)
    g_ref, v_ref = ref.ell_gram_and_v_ref(idx, val, x, n)
    np.testing.assert_allclose(np.asarray(g_c), np.asarray(g_ref), rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(np.asarray(v_c), np.asarray(v_ref), rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("impl", [ell_gram_and_v, ell_gram_and_v_blocked],
                         ids=["pallas", "blocked"])
def test_direct_walk_traces_no_sort(impl):
    """Where the bundle could touch every panel (sb·w ≥ n) the walk is
    traced as it always was: no sort, no gather of x. A wide n does
    compact, through one sort."""
    import jax

    def prims(sb, w, n):
        args = (jnp.zeros((sb, w), jnp.int32), jnp.ones((sb, w)), jnp.ones(n))
        jaxpr = jax.make_jaxpr(lambda i, v, z: impl(i, v, z, n=n, bk=128))(*args)
        return {e.primitive.name for e in jaxpr.jaxpr.eqns}

    narrow = prims(16, 8, 100)
    assert "sort" not in narrow and "gather" not in narrow
    assert "sort" in prims(16, 8, 5_000)


@pytest.mark.parametrize(
    "sb,w,n,bk,path,panels",
    [(16, 6, 5_000, 32, "compacted", 3), (64, 483, 1_355_191, 512, "compacted", 61),
     (16, 8, 100, 128, "direct", 1), (64, 31, 2_000, 128, "direct", 16)],
    ids=["small-compacted", "news20-compacted", "narrow-direct", "equal-panels-direct"],
)
def test_panels_per_call_gauge(sb, w, n, bk, path, panels):
    """The trace-time gauge says which walk a traced call takes and how
    many panels it has: ⌈sb·w/bk⌉ compacted, ⌈n/bk⌉ direct."""
    import jax

    from repro.obs import metrics as obs_metrics

    gauges = {
        p: obs_metrics.registry().gauge("ell_gram.panels_per_call", path=p)
        for p in ("compacted", "direct")
    }
    for g in gauges.values():
        g.set(-1)
    args = (
        jax.ShapeDtypeStruct((sb, w), jnp.int32),
        jax.ShapeDtypeStruct((sb, w), jnp.float32),
        jax.ShapeDtypeStruct((n,), jnp.float32),
    )
    jax.make_jaxpr(lambda i, v, z: ell_gram_and_v(i, v, z, n=n, bk=bk))(*args)
    assert {p: g.value for p, g in gauges.items()} == {
        p: (panels if p == path else -1) for p in gauges
    }
