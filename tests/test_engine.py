"""The unified engine: bundle-primitive parity + corner trajectories.

E1  The scatter-free Pallas ELL-Gram bundle primitive matches the dense
    densify oracle (kernels/ref.py) across (s, b, width) shapes — and
    so does the pure-jnp "blocked" variant used inside shard_map.
E2  Engine corners reproduce the legacy solver entry points
    (run_sgd / run_sstep_sgd / run_fedavg / run_hybrid_sgd)
    bit-for-bit — the wrappers and the named-corner schedules are the
    same computation.
E3  The gram backend never changes the trajectory (pallas ≡ blocked ≡
    dense through a full multi-round run).
"""

import dataclasses

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

import jax.numpy as jnp

from repro.core import (
    ParallelSGDSchedule,
    make_problem,
    run_fedavg,
    run_hybrid_sgd,
    run_parallel_sgd,
    run_sgd,
    run_sstep_sgd,
    single_team,
    stack_row_teams,
)
from repro.kernels.ell_gram import ell_gram_and_v, ell_gram_and_v_blocked
from repro.kernels.ref import ell_gram_and_v_ref
from repro.sparse.synthetic import make_skewed_csr

B, ETA = 8, 0.05


# ---------------- E1: bundle primitive vs densify oracle ----------------


@settings(max_examples=25, deadline=None)
@given(
    s=st.sampled_from([1, 2, 4, 8]),
    b=st.sampled_from([4, 8, 16]),
    width=st.integers(1, 40),
    n=st.integers(8, 1500),
    bk=st.sampled_from([128, 256, 512]),
    seed=st.integers(0, 999),
)
def test_bundle_primitive_matches_dense_ref(s, b, width, n, bk, seed):
    rng = np.random.default_rng(seed)
    sb = s * b
    idx = jnp.asarray(rng.integers(0, n, size=(sb, width)).astype(np.int32))
    val = jnp.asarray(rng.standard_normal((sb, width)).astype(np.float32))
    x = jnp.asarray(rng.standard_normal(n).astype(np.float32))
    g_ref, v_ref = ell_gram_and_v_ref(idx, val, x, n)
    for impl in (ell_gram_and_v, ell_gram_and_v_blocked):
        g, v = impl(idx, val, x, n=n, bk=bk)
        np.testing.assert_allclose(np.asarray(g), np.asarray(g_ref), rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(np.asarray(v), np.asarray(v_ref), rtol=1e-4, atol=1e-4)


def test_bundle_primitive_duplicate_columns():
    """Duplicate column ids within a row must accumulate (scatter-add
    semantics), not overwrite."""
    idx = jnp.asarray([[2, 2, 5], [0, 1, 1]], jnp.int32)
    val = jnp.asarray([[1.0, 2.0, 3.0], [4.0, 5.0, -1.0]], jnp.float32)
    x = jnp.arange(8, dtype=jnp.float32)
    g, v = ell_gram_and_v(idx, val, x, n=8, bk=4)
    g_ref, v_ref = ell_gram_and_v_ref(idx, val, x, 8)
    np.testing.assert_allclose(np.asarray(g), np.asarray(g_ref), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(v), np.asarray(v_ref), rtol=1e-6)


def test_bundle_primitive_ell_padding_is_inert():
    """ELL pad entries (idx 0, val 0) must not pollute column 0."""
    rng = np.random.default_rng(3)
    idx = rng.integers(0, 64, size=(16, 6)).astype(np.int32)
    val = rng.standard_normal((16, 6)).astype(np.float32)
    idx[:, 4:] = 0
    val[:, 4:] = 0.0
    x = jnp.asarray(rng.standard_normal(64).astype(np.float32))
    g, v = ell_gram_and_v(jnp.asarray(idx), jnp.asarray(val), x, n=64, bk=32)
    g_ref, v_ref = ell_gram_and_v_ref(jnp.asarray(idx), jnp.asarray(val), x, 64)
    np.testing.assert_allclose(np.asarray(g), np.asarray(g_ref), rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(v), np.asarray(v_ref), rtol=1e-5, atol=1e-5)


# ---------------- E2: engine corners == legacy trajectories ----------------


@pytest.fixture(scope="module")
def dataset():
    rng = np.random.default_rng(0)
    a = make_skewed_csr(256, 128, 12, 0.8, seed=3)
    y = np.where(rng.random(256) < 0.5, 1.0, -1.0)
    return a, y


def test_engine_mb_sgd_corner_bitwise(dataset):
    a, y = dataset
    prob = make_problem(a, y, row_multiple=64)
    x0 = jnp.zeros(prob.n)
    x_legacy, l_legacy = run_sgd(prob, x0, B, ETA, 64, loss_every=16)
    sched = ParallelSGDSchedule.mb_sgd(B, ETA, 64, loss_every=16)
    x_eng, l_eng = run_parallel_sgd(single_team(prob), x0, sched)
    np.testing.assert_array_equal(np.asarray(x_legacy), np.asarray(x_eng))
    np.testing.assert_array_equal(np.asarray(l_legacy), np.asarray(l_eng))


@pytest.mark.parametrize("s", [2, 4, 8])
def test_engine_sstep_corner_bitwise(dataset, s):
    a, y = dataset
    prob = make_problem(a, y, row_multiple=64)
    x0 = jnp.zeros(prob.n)
    x_legacy, _ = run_sstep_sgd(prob, x0, s, B, ETA, 64)
    sched = ParallelSGDSchedule.sstep(s, B, ETA, 64)
    x_eng, _ = run_parallel_sgd(single_team(prob), x0, sched)
    np.testing.assert_array_equal(np.asarray(x_legacy), np.asarray(x_eng))


def test_engine_fedavg_corner_bitwise(dataset):
    a, y = dataset
    tp = stack_row_teams(a, y, 4, row_multiple=B)
    x0 = jnp.zeros(tp.n)
    x_legacy, _ = run_fedavg(tp, x0, B, ETA, tau=16, rounds=4)
    sched = ParallelSGDSchedule.fedavg(4, B, ETA, tau=16, rounds=4)
    x_eng, _ = run_parallel_sgd(tp, x0, sched)
    np.testing.assert_array_equal(np.asarray(x_legacy), np.asarray(x_eng))


def test_engine_hybrid_corner_bitwise(dataset):
    a, y = dataset
    s, tau = 4, 16
    tp = stack_row_teams(a, y, 2, row_multiple=s * B)
    x0 = jnp.zeros(tp.n)
    x_legacy, _ = run_hybrid_sgd(tp, x0, s, B, ETA, tau, rounds=4)
    sched = ParallelSGDSchedule.hybrid(2, s, B, ETA, tau, rounds=4)
    x_eng, _ = run_parallel_sgd(tp, x0, sched)
    np.testing.assert_array_equal(np.asarray(x_legacy), np.asarray(x_eng))


# ---------------- E3: gram backend invariance ----------------


@pytest.mark.parametrize("gram", ["blocked", "dense"])
def test_engine_gram_backend_invariant(dataset, gram):
    a, y = dataset
    s, tau = 4, 16
    tp = stack_row_teams(a, y, 2, row_multiple=s * B)
    x0 = jnp.zeros(tp.n)
    base = ParallelSGDSchedule.hybrid(2, s, B, ETA, tau, rounds=3)
    x_pallas, _ = run_parallel_sgd(tp, x0, base)
    x_other, _ = run_parallel_sgd(tp, x0, dataclasses.replace(base, gram=gram))
    np.testing.assert_allclose(
        np.asarray(x_pallas), np.asarray(x_other), rtol=1e-6, atol=1e-7
    )


def test_schedule_validation(dataset):
    # s ∤ τ is a *solver* constraint (the NN trainer legally carries
    # s = grad-accum with no τ coupling), enforced at run time:
    a, y = dataset
    tp = stack_row_teams(a, y, 1, row_multiple=64)
    with pytest.raises(ValueError):
        run_parallel_sgd(tp, jnp.zeros(tp.n), ParallelSGDSchedule(s=3, tau=8, rounds=1))
    with pytest.raises(ValueError):
        ParallelSGDSchedule(gram="nope")
    with pytest.raises(ValueError):
        ParallelSGDSchedule.sstep(3, B, ETA, 64)  # s ∤ iters
    with pytest.raises(ValueError):
        ParallelSGDSchedule.mb_sgd(B, ETA, 2, loss_every=8)  # le ∤ rounds
    with pytest.raises(ValueError):
        ParallelSGDSchedule.fedavg(2, B, ETA, 4, rounds=10, loss_every=4)


@pytest.mark.parametrize(
    "bad",
    [
        dict(s=0), dict(s=-2), dict(b=0), dict(b=-8), dict(bk=0), dict(bk=-512),
        dict(tau=0), dict(p_r=0), dict(p_c=0), dict(rounds=0), dict(rounds=-1),
        dict(loss_every=-1), dict(eta=-0.05),
    ],
)
def test_schedule_rejects_nonpositive_knobs(bad):
    """Satellite: every loop-shape knob must be positive (loss_every ≥ 0,
    eta ≥ 0 — η = 0 is reserved for the engine's internal jit-cache
    normalization and rejected at the solver entries instead)."""
    (knob, value), = bad.items()
    with pytest.raises(ValueError, match=knob):
        ParallelSGDSchedule(**bad)


def test_solver_entries_reject_eta_zero(dataset):
    """η = 0 passes construction (the chunk cache normalizes to it) but
    no solver entry may run a zero-step schedule."""
    from repro.core.engine import run_engine_chunk

    a, y = dataset
    tp = stack_row_teams(a, y, 1, row_multiple=64)
    sched = ParallelSGDSchedule(eta=0.0, rounds=1)
    with pytest.raises(ValueError, match="eta"):
        run_parallel_sgd(tp, jnp.zeros(tp.n), sched)
    with pytest.raises(ValueError, match="eta"):
        run_engine_chunk(tp, jnp.zeros(tp.n), 0, 1, sched)


def test_eta_is_traced_not_static(dataset):
    """An η-sweep over otherwise-identical schedules must reuse one
    compiled executable (η enters as a traced operand)."""
    from repro.core.engine import _run_engine

    a, y = dataset
    tp = stack_row_teams(a, y, 2, row_multiple=32)
    x0 = jnp.zeros(tp.n)
    before = _run_engine._cache_size()
    for eta in (0.01, 0.05, 0.25):
        run_parallel_sgd(tp, x0, ParallelSGDSchedule.hybrid(2, 4, B, eta, 8, rounds=1))
    assert _run_engine._cache_size() - before <= 1


def test_legacy_hybrid_schedule_signature():
    """Old (tau, s) constructor keeps working (deprecated shim)."""
    from repro.optim import HybridSchedule

    assert HybridSchedule().tau == 10
    assert HybridSchedule(5).tau == 5
    assert HybridSchedule(s=2).s == 2 and HybridSchedule(s=2).tau == 10
    # NN grad-accum s is not coupled to τ (unlike the solver corners)
    assert HybridSchedule(tau=10, s=4).s == 4


def test_engine_rejects_mismatched_teams(dataset):
    a, y = dataset
    tp = stack_row_teams(a, y, 4, row_multiple=B)
    with pytest.raises(ValueError):
        run_parallel_sgd(tp, jnp.zeros(tp.n), ParallelSGDSchedule.fedavg(2, B, ETA, 8, 1))


def test_engine_round_with_compacted_columns_matches_dense():
    """A round whose bundles touch far fewer columns than n (s·b·w = 384
    of n = 8,192) walks compacted panels and follows the dense oracle."""
    from repro.obs import metrics as obs_metrics

    rng = np.random.default_rng(4)
    a = make_skewed_csr(256, 8192, 6, 0.8, seed=5)
    y = np.where(rng.random(256) < 0.5, 1.0, -1.0)
    s, tau = 4, 16
    tp = stack_row_teams(a, y, 2, row_multiple=s * B)
    assert s * B * tp.indices.shape[-1] < tp.n
    x0 = jnp.zeros(tp.n)
    base = ParallelSGDSchedule.hybrid(2, s, B, ETA, tau, rounds=1)
    compacted = obs_metrics.registry().gauge("ell_gram.panels_per_call", path="compacted")
    compacted.set(-1)
    x_pallas, _ = run_parallel_sgd(tp, x0, base)
    assert compacted.value == 1  # ⌈384 / 512⌉, against ⌈8192 / 512⌉ = 16
    assert np.abs(np.asarray(x_pallas)).max() > 0
    x_dense, _ = run_parallel_sgd(tp, x0, dataclasses.replace(base, gram="dense"))
    np.testing.assert_allclose(
        np.asarray(x_pallas), np.asarray(x_dense), rtol=1e-6, atol=1e-7
    )
