"""The pluggable convex-objective layer (repro.core.objective).

Cross-objective × cross-backend parity:

O1  Calculus: for every registered objective, residual(z) == -ℓ′(z) by
    jax.grad, and problem_loss matches a dense numpy computation
    (including the L2 term).
O2  Bundle math: inner_corrections (incl. the decay-aware λ > 0
    recurrence) matches a jax.grad-derived sequential-SGD oracle, per
    objective, to fp32 tolerance.
O3  Engine invariances, per objective: gram backend ("pallas" /
    "blocked" / "dense") never changes the trajectory, and chunked
    run_engine_chunk execution is bitwise-identical to the monolithic
    scan.
O4  Front door: ExperimentSpec(objective=..., l2=...) runs end-to-end
    (plan → Session.step_rounds → report) on the simulated engine and
    on the shard_map backend (1×1 mesh — the full dispatch on one real
    device; multi-device parity lives in test_distributed_subprocess),
    and the two agree.
O5  Compatibility: the default logistic spec routes through the same
    path as before (full_loss/sigmoid_residual shims agree bitwise and
    warn); the spec JSON round-trips the new fields.
"""

import dataclasses
import warnings

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.api import ExperimentSpec, MeshSpec, Session, build_problem, run
from repro.core import (
    LOGISTIC,
    OBJECTIVES,
    ParallelSGDSchedule,
    get_objective,
    inner_corrections,
    make_problem,
    problem_loss,
    run_engine_chunk,
    run_parallel_sgd,
    stack_row_teams,
)
from repro.core.objective import exp_nonpositive
from repro.kernels.ref import densify_bundle_ref, ell_gram_and_v_ref
from repro.sparse.synthetic import make_skewed_csr

OBJ_POINTS = [
    ("logistic", 0.0), ("logistic", 1e-3),
    ("squared_hinge", 0.0), ("squared_hinge", 1e-3),
    ("least_squares", 0.0), ("least_squares", 1e-3),
]
DATASET = "rcv1-sm"


@pytest.fixture(scope="module")
def dataset():
    rng = np.random.default_rng(0)
    a = make_skewed_csr(256, 128, 12, 0.8, seed=3)
    y = np.where(rng.random(256) < 0.5, 1.0, -1.0)
    return a, y


# ---------------- O1: the objective layer's calculus ----------------


@pytest.mark.parametrize("name", sorted(OBJECTIVES))
def test_residual_is_negative_loss_gradient(name):
    """residual(z) must equal -ℓ′(z) — the engine's update direction is
    defined by the loss, so autodiff is the ground truth."""
    obj = get_objective(name)
    z = jnp.linspace(-6.0, 6.0, 101)
    grad = jax.vmap(jax.grad(lambda t: obj.pointwise_loss(t)))(z)
    np.testing.assert_allclose(
        np.asarray(obj.residual(z)), -np.asarray(grad), rtol=1e-5, atol=1e-6
    )


def _ulps(got, want64):
    """Signed errors of float32 ``got`` against float64 ``want64``, in
    units of float32's spacing at the true value."""
    spacing = np.spacing(np.abs(want64.astype(np.float32))).astype(np.float64)
    return (np.asarray(got, np.float64) - want64) / spacing


def test_exp_nonpositive_is_float32_accurate_and_unbiased():
    """The logistic residual's exp: within 1.5 ulps of float64 over
    [-87, 0] and unbiased (a v5e's own float32 exp is 16 ulps off on
    average and low by 10), exactly 1 at 0, and clamped to exp(-87)
    below (float32's least normal, not a denormal)."""
    rng = np.random.default_rng(0)
    t = -np.concatenate([rng.uniform(0, 87, 1 << 18), rng.uniform(0, 1, 1 << 18)])
    t = t.astype(np.float32)
    err = _ulps(jax.jit(exp_nonpositive)(t), np.exp(t.astype(np.float64)))
    assert np.abs(err).max() <= 1.5 and abs(err.mean()) < 0.05
    edge = np.asarray(exp_nonpositive(jnp.asarray([0.0, -87.0, -200.0, -np.inf])))
    assert edge[0] == 1.0 and np.all(edge[1:] == edge[1]) and edge[1] > 0
    assert np.isnan(float(exp_nonpositive(jnp.float32(np.nan))))


def test_logistic_residual_is_float32_accurate():
    """u(z) = 1/(1 + e^z) from one exp of -|z|: within 3 ulps of float64
    on both branches, and exactly ½ at 0."""
    z = np.random.default_rng(1).uniform(-30, 30, 1 << 18).astype(np.float32)
    z64 = z.astype(np.float64)
    want = np.where(z64 >= 0, np.exp(-z64) / (1 + np.exp(-z64)), 1 / (1 + np.exp(z64)))
    err = _ulps(jax.jit(LOGISTIC.residual)(z), want)
    assert np.abs(err).max() <= 3 and abs(err.mean()) < 0.05
    assert float(LOGISTIC.residual(jnp.float32(0.0))) == 0.5


@pytest.mark.parametrize("name,l2", OBJ_POINTS)
def test_problem_loss_matches_dense_numpy(dataset, name, l2):
    a, y = dataset
    prob = make_problem(a, y, row_multiple=64, objective=get_objective(name, l2=l2))
    rng = np.random.default_rng(1)
    x = rng.standard_normal(a.n).astype(np.float32) * 0.1
    margin = (a.to_dense() * y[:, None]).astype(np.float32) @ x
    z = margin.astype(np.float64)
    if name == "logistic":
        pointwise = np.logaddexp(0.0, -z)
    elif name == "squared_hinge":
        pointwise = np.maximum(0.0, 1.0 - z) ** 2
    else:
        pointwise = 0.5 * (1.0 - z) ** 2
    expect = pointwise.mean() + 0.5 * l2 * float(x.astype(np.float64) @ x)
    got = float(problem_loss(prob, jnp.asarray(x)))
    np.testing.assert_allclose(got, expect, rtol=2e-4)


def test_registry_validation():
    with pytest.raises(ValueError, match="registry"):
        get_objective("hinge^3")
    with pytest.raises(ValueError, match="l2"):
        get_objective("logistic", l2=-1.0)
    with pytest.raises(ValueError, match="l2"):
        get_objective(get_objective("logistic", l2=0.1), l2=0.2)
    assert get_objective(LOGISTIC) is LOGISTIC
    assert get_objective("logistic") == LOGISTIC


# ---------------- O2: bundle recurrence vs autodiff oracle ----------------


@pytest.mark.parametrize("name,l2", OBJ_POINTS)
@pytest.mark.parametrize("s", [1, 2, 4])
def test_inner_corrections_match_sequential_autodiff_sgd(name, l2, s):
    """The s-step bundle (Gram + corrections + decay-folded update) is
    an algebraic identity of s sequential SGD steps on the regularized
    objective — checked against jax.grad, which knows nothing about the
    recurrence."""
    obj = get_objective(name, l2=l2)
    rng = np.random.default_rng(11)
    b, n, w = 8, 64, 6
    sb = s * b
    idx = jnp.asarray(rng.integers(0, n, size=(sb, w)).astype(np.int32))
    val = jnp.asarray(rng.standard_normal((sb, w)).astype(np.float32))
    x0 = jnp.asarray(rng.standard_normal(n).astype(np.float32))
    eta = 0.1
    dense_y = densify_bundle_ref(idx, val, n)

    def batch_loss(x, j):
        z = jax.lax.dynamic_slice_in_dim(dense_y, j * b, b) @ x
        return jnp.mean(obj.pointwise_loss(z)) + 0.5 * l2 * jnp.sum(x * x)

    x_seq = x0
    for j in range(s):
        x_seq = x_seq - eta * jax.grad(batch_loss)(x_seq, j)

    g, v = ell_gram_and_v_ref(idx, val, x0, n)
    u = inner_corrections(g, v, s, b, jnp.float32(eta), obj)
    rho_s = jnp.float32(1.0 - eta * l2) ** s
    x_bundle = rho_s * x0 + (eta / b) * (dense_y.T @ u)
    np.testing.assert_allclose(
        np.asarray(x_seq), np.asarray(x_bundle), rtol=1e-5, atol=1e-6
    )


# ---------------- O3: engine invariances per objective ----------------


@pytest.mark.parametrize("name,l2", OBJ_POINTS)
def test_gram_backend_invariant_per_objective(dataset, name, l2):
    a, y = dataset
    s, b, tau = 4, 8, 16
    tp = stack_row_teams(a, y, 2, row_multiple=s * b,
                         objective=get_objective(name, l2=l2))
    x0 = jnp.zeros(tp.n)
    base = ParallelSGDSchedule.hybrid(2, s, b, 0.05, tau, rounds=3)
    x_pallas, _ = run_parallel_sgd(tp, x0, base)
    for gram in ("blocked", "dense"):
        x_other, _ = run_parallel_sgd(tp, x0, dataclasses.replace(base, gram=gram))
        np.testing.assert_allclose(
            np.asarray(x_pallas), np.asarray(x_other), rtol=1e-6, atol=1e-7
        )


@pytest.mark.parametrize("name,l2", OBJ_POINTS)
def test_chunked_execution_bitwise_per_objective(dataset, name, l2):
    """run_engine_chunk over offsets 0,1,2,… must reproduce the
    monolithic scan bitwise under every objective (the Session's
    correctness foundation)."""
    a, y = dataset
    s, b = 2, 8
    tp = stack_row_teams(a, y, 2, row_multiple=s * b,
                         objective=get_objective(name, l2=l2))
    sched = ParallelSGDSchedule.hybrid(2, s, b, 0.05, 8, rounds=4)
    x_mono, _ = run_parallel_sgd(tp, jnp.zeros(tp.n), sched)
    x = jnp.zeros(tp.n)
    for r in range(sched.rounds):
        x = run_engine_chunk(tp, x, r, 1, sched)
    np.testing.assert_array_equal(np.asarray(x_mono), np.asarray(x))


# ---------------- O4: front door end-to-end, both backends ----------------


def spec_for(name, l2, backend="simulated"):
    return ExperimentSpec(
        dataset=DATASET,
        schedule=ParallelSGDSchedule.hybrid(1, 2, 8, 0.05, 8, rounds=4, loss_every=2),
        mesh=MeshSpec(p_r=1, p_c=1, backend=backend),
        objective=name,
        l2=l2,
        name=f"{name}-l2={l2}",
    )


@pytest.mark.parametrize("name,l2", OBJ_POINTS)
def test_spec_end_to_end_simulated(name, l2):
    spec = spec_for(name, l2)
    sess = Session(spec)
    events = []
    while not sess.done:
        events.append(sess.step_rounds(1))
    rep = sess.report()
    assert rep.spec.objective == name and rep.spec.l2 == l2
    assert rep.losses.shape == (2,)
    assert np.isfinite(rep.final_loss)
    # the streamed session equals run() bitwise (same chunked engine)
    rep2 = run(spec)
    np.testing.assert_array_equal(rep.x, rep2.x)
    np.testing.assert_array_equal(rep.losses, rep2.losses)
    # and the engine really optimizes this objective
    bundle = build_problem(spec)
    whole = make_problem(bundle.dataset.A, bundle.dataset.y, row_multiple=bundle.row_multiple,
                         objective=get_objective(name, l2=l2))
    f0 = float(problem_loss(whole, jnp.zeros(bundle.dataset.A.n)))
    assert rep.final_loss < f0


@pytest.mark.parametrize("name,l2", [("squared_hinge", 0.0), ("least_squares", 1e-3)])
def test_spec_backend_parity_1x1(name, l2):
    """Same spec, both executors, 1×1 mesh: the shard_map dispatch path
    (scatter → shard_map rounds → gather, objective threaded through
    Hybrid2DProblem) must agree with the simulated oracle."""
    r_sim = run(spec_for(name, l2, backend="simulated"))
    r_dist = run(spec_for(name, l2, backend="shard_map"))
    np.testing.assert_allclose(r_sim.x, r_dist.x, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(r_sim.losses, r_dist.losses, rtol=1e-5)


def test_make_hybrid_step_rejects_eta_zero(dataset):
    from repro import compat
    from repro.core.distributed import build_2d_problem, make_hybrid_step

    a, y = dataset
    prob, _cp = build_2d_problem(a, y, 1, 1, "cyclic", row_multiple=8)
    mesh = compat.make_mesh((1, 1), ("rows", "cols"))
    with pytest.raises(ValueError, match="eta"):
        make_hybrid_step(mesh, prob, ParallelSGDSchedule(eta=0.0))


# ---------------- O5: compatibility ----------------


def test_default_logistic_spec_unchanged_by_objective_field():
    """A spec that never mentions objectives must execute the identical
    computation as one that names the defaults explicitly (bitwise)."""
    base = ExperimentSpec(
        dataset=DATASET,
        schedule=ParallelSGDSchedule.hybrid(2, 2, 8, 0.05, 8, rounds=4, loss_every=2),
        mesh=MeshSpec(p_r=2),
    )
    explicit = dataclasses.replace(base, objective="logistic", l2=0.0)
    r1, r2 = run(base), run(explicit)
    np.testing.assert_array_equal(r1.x, r2.x)
    np.testing.assert_array_equal(r1.losses, r2.losses)


def test_spec_json_round_trips_objective_and_l2():
    spec = spec_for("squared_hinge", 1e-3)
    restored = ExperimentSpec.from_json(spec.to_json())
    assert restored == spec
    assert restored.objective == "squared_hinge" and restored.l2 == 1e-3
    # old JSON (pre-objective) still loads with the logistic default
    d = spec.to_dict()
    del d["objective"], d["l2"]
    old = ExperimentSpec.from_dict(d)
    assert old.objective == "logistic" and old.l2 == 0.0
    # the content hash keys on the objective (resume dirs never mix)
    assert old.content_hash() != spec.content_hash()


def test_spec_rejects_unknown_objective_and_bad_l2():
    sched = ParallelSGDSchedule.mb_sgd(8, 0.05, 4)
    with pytest.raises(ValueError, match="objective"):
        ExperimentSpec(dataset=DATASET, schedule=sched, objective="hinge^3")
    with pytest.raises(ValueError, match="l2"):
        ExperimentSpec(dataset=DATASET, schedule=sched, l2=-0.5)


def test_deprecated_shims_warn_and_agree(dataset):
    """Satellite: sigmoid_residual / full_loss keep working (one
    release) — same values as the objective layer, plus a
    DeprecationWarning."""
    from repro.core.problem import full_loss, sigmoid_residual

    a, y = dataset
    prob = make_problem(a, y, row_multiple=64)
    z = jnp.linspace(-4.0, 4.0, 17)
    x = jnp.asarray(np.random.default_rng(2).standard_normal(a.n).astype(np.float32))
    with pytest.warns(DeprecationWarning):
        u_old = sigmoid_residual(z)
    np.testing.assert_array_equal(np.asarray(u_old), np.asarray(LOGISTIC.residual(z)))
    with pytest.warns(DeprecationWarning):
        f_old = full_loss(prob, x)
    np.testing.assert_array_equal(np.asarray(f_old), np.asarray(problem_loss(prob, x)))
    # LogisticProblem remains importable as an alias of Problem
    from repro.core.problem import LogisticProblem, Problem

    assert LogisticProblem is Problem
    with warnings.catch_warnings():
        warnings.simplefilter("error")  # the alias itself must not warn
        assert isinstance(prob, LogisticProblem)
