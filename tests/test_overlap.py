"""The delay-D overlap pipeline (DaSGD-style delayed averaging).

Contracts under test:

* D=0 run through the delay pipeline (an empty staging FIFO) is
  bitwise the synchronous bundle scan, in the same process, so no
  refactor of either can silently move the synchronous trajectory;
* D ≥ 1 changes the iterates (it is a real staleness knob) but still
  converges, monolithic and chunked execution stay bitwise at any D,
  and the ledger's counted volume is invariant in D (overlap hides
  time, not bytes);
* the ledger's exposed/total/efficiency closed form, the Eq. 4 overlap
  pricing (max(comm, compute) per bundle) + recommend_delay, the
  issue/await span split, spec serialization compatibility, and the
  decaying-τ compensation schedule (One-Shot Averaging).
"""

import dataclasses
import json
from pathlib import Path

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.api import ExperimentSpec, MeshSpec, plan, run, run_decaying_tau
from repro.api.report import RunReport
from repro.api.session import Session
from repro.core.comm import CommLedger, CommRate
from repro.core.engine import (
    ParallelSGDSchedule,
    _team_inner_iterations,
    delayed_bundle_scan,
    run_parallel_sgd,
)
from repro.core.teams import stack_row_teams
from repro.costmodel.hockney import HybridConfig, hybrid_epoch_cost, recommend_delay
from repro.costmodel.machines import MACHINES
from repro.sparse.synthetic import make_skewed_csr


def _ref_problem():
    a = make_skewed_csr(256, 100, 12, 0.8, seed=3)
    rng = np.random.default_rng(0)
    y = np.where(rng.random(256) < 0.5, 1.0, -1.0)
    return a, y


def _hybrid_sched(delay=0):
    return ParallelSGDSchedule.hybrid(
        2, 2, 4, 0.05, 8, rounds=3, loss_every=1, delay=delay
    )


# ---- D=0: the delay pipeline is bitwise the synchronous scan ----


def _team_rounds_sync_vs_delay0(sched, rounds=3):
    """One team's iterate after ``rounds`` rounds of τ inner iterations,
    once through the synchronous bundle scan and once through
    ``delayed_bundle_scan`` at D = 0, from the same start."""
    assert sched.delay == 0  # the default stays synchronous
    a, y = _ref_problem()
    tp = stack_row_teams(a, y, sched.p_r, row_multiple=sched.s * sched.b)
    idx, val = tp.indices[0], tp.values[0]
    sb, bundles = sched.s * sched.b, sched.tau // sched.s
    eta = jnp.float32(sched.eta)

    @jax.jit
    def sync(x, r):
        return _team_inner_iterations(idx, val, tp.n, x, r, eta, sched, tp.objective)

    @jax.jit
    def delay0(x, r):
        def slice_bundle(t):
            start = ((r * bundles + t) * sb) % idx.shape[0]
            return (jax.lax.dynamic_slice_in_dim(idx, start, sb, axis=0),
                    jax.lax.dynamic_slice_in_dim(val, start, sb, axis=0))

        return delayed_bundle_scan(x, slice_bundle=slice_bundle, bundles=bundles,
                                   n=tp.n, sched=sched, eta=eta,
                                   objective=tp.objective)

    xs = xd = jnp.zeros(tp.n)
    for r in range(rounds):
        xs, xd = sync(xs, r), delay0(xd, r)
    assert float(jnp.abs(xs).max()) > 0  # the rounds moved the weights
    return np.asarray(xs), np.asarray(xd)


def test_delay0_hybrid_bitwise_vs_pinned_reference():
    xs, xd = _team_rounds_sync_vs_delay0(_hybrid_sched())
    np.testing.assert_array_equal(xd, xs)


def test_delay0_fedavg_bitwise_vs_pinned_reference():
    sched = ParallelSGDSchedule.fedavg(4, 4, 0.05, 8, rounds=3, loss_every=1)
    xs, xd = _team_rounds_sync_vs_delay0(sched)
    np.testing.assert_array_equal(xd, xs)


# ---- D ≥ 1: real staleness, still converges, chunking stays bitwise ----


@pytest.mark.parametrize("delay", [1, 2, 4])
def test_delayed_iterates_differ_but_converge(delay):
    a, y = _ref_problem()
    sched = _hybrid_sched()
    tp = stack_row_teams(a, y, 2, row_multiple=sched.s * sched.b)
    x0, l0 = run_parallel_sgd(tp, jnp.zeros(100), sched)
    xd, ld = run_parallel_sgd(
        tp, jnp.zeros(100), dataclasses.replace(sched, delay=delay)
    )
    assert not np.array_equal(np.asarray(x0), np.asarray(xd))
    # staleness costs a little loss, not convergence: monotone decrease
    # and a final objective within 1% of the synchronous run's.
    ld = np.asarray(ld)
    assert np.all(np.diff(ld) < 0)
    assert ld[-1] < ld[0]
    assert abs(float(ld[-1]) - float(np.asarray(l0)[-1])) < 0.01 * float(ld[-1])


def test_delay_validation():
    with pytest.raises(ValueError, match="delay"):
        _hybrid_sched(delay=-1)
    a, y = _ref_problem()
    sched = _hybrid_sched(delay=5)  # τ/s = 4 bundles per round
    tp = stack_row_teams(a, y, 2, row_multiple=sched.s * sched.b)
    with pytest.raises(ValueError, match="τ/s"):
        run_parallel_sgd(tp, jnp.zeros(100), sched)


@pytest.mark.parametrize("delay", [1, 3])
def test_chunked_session_bitwise_at_delay(delay):
    """Session.step_rounds(1) × rounds == the monolithic engine scan at
    D ≥ 1: the staging buffer drains inside each round, so round
    boundaries stay clean for chunking/checkpointing at any D."""
    spec = ExperimentSpec(
        dataset="rcv1-sm",
        schedule=ParallelSGDSchedule.hybrid(
            2, 2, 4, 0.05, 8, rounds=4, loss_every=0, delay=delay
        ),
        mesh=MeshSpec(p_r=2, p_c=2, backend="simulated"),
    )
    mono = run(spec)
    ses = Session(spec)
    while ses.rounds_done < spec.schedule.rounds:
        ses.step_rounds(1)
    np.testing.assert_array_equal(mono.x, ses.current_x())


# ---- ledger: closed-form volume invariant in D, exposed < total ----


def test_counted_volume_invariant_in_delay():
    """Overlap hides seconds, never bytes: counted words/calls at D > 0
    equal the Table 2–3 closed form — i.e. exactly the D=0 ledger."""
    from repro.core.engine import engine_comm_ledger
    from repro.costmodel import schedule_comm_volume

    n = 100
    for delay in (0, 2):
        sched = dataclasses.replace(_hybrid_sched(), p_c=4, delay=delay)
        led = engine_comm_ledger(sched, n)
        led.add_rounds(3)
        assert led.delay == delay
        cv = schedule_comm_volume(
            n, sched.p_r, sched.p_c, sched.s, sched.b, sched.tau, rounds=3
        )
        assert led.counted_words() == cv.words_dict()
        assert led.counted_calls()["gram_calls"] == cv.gram_calls


def _ledger(delay, gv=4.0, compute=1.5, pa=2.0, rounds=2):
    return CommLedger(
        rates=(CommRate("allreduce", "cols", 4, 272, 4),),
        rounds=rounds,
        phase_seconds={
            "bundle_compute": compute, "allreduce_gv": gv, "param_avg": pa
        },
        delay=delay,
    )


def test_exposed_comm_closed_form():
    # D=0: exposed ≡ total (the PR 8 identity)
    led0 = _ledger(0)
    assert led0.total_comm_s == pytest.approx((4.0 + 2.0) * 2)
    assert led0.exposed_comm_s == led0.total_comm_s
    assert led0.overlap_efficiency == pytest.approx(1.0)
    # D=1: gv loses one bundle-compute of exposure; param_avg stays
    led1 = _ledger(1)
    assert led1.exposed_comm_s == pytest.approx((4.0 - 1.5 + 2.0) * 2)
    assert led1.exposed_comm_s < led1.total_comm_s
    assert led1.overlap_efficiency == pytest.approx((4.0 - 1.5 + 2.0) / 6.0)
    # deep pipeline: gv fully hidden, clamped at zero — only the sync
    # param average remains exposed
    led9 = _ledger(9)
    assert led9.exposed_comm_s == pytest.approx(2.0 * 2)
    # untimed ledger: no phases → all three derived values are None
    bare = CommLedger(delay=1)
    assert bare.total_comm_s is None
    assert bare.exposed_comm_s is None
    assert bare.overlap_efficiency is None


def test_ledger_roundtrip_carries_delay():
    led = _ledger(2)
    d = led.to_dict()
    assert d["delay"] == 2
    assert d["overlap_efficiency"] == pytest.approx(led.overlap_efficiency)
    back = CommLedger.from_dict(json.loads(json.dumps(d)))
    assert back.delay == 2
    assert back.exposed_comm_s == pytest.approx(led.exposed_comm_s)
    # delay-0 ledgers serialize without the key (pre-overlap byte
    # compatibility), and load back as delay 0
    d0 = _ledger(0).to_dict()
    assert "delay" not in d0
    assert CommLedger.from_dict(d0).delay == 0


def test_timed_simulated_run_exposes_overlap():
    """A timed D=1 run on the simulated backend: exposed strictly below
    total, the efficiency ratio surfaced in RunReport.summary(), and
    the report JSON round-trips the split."""
    spec = ExperimentSpec(
        dataset="rcv1-sm",
        schedule=ParallelSGDSchedule.hybrid(
            2, 2, 4, 0.05, 8, rounds=3, loss_every=0, delay=1
        ),
        mesh=MeshSpec(p_r=2, p_c=2, backend="simulated"),
        comm_timing=True,
    )
    rep = run(spec)
    led = rep.ledger
    assert led.delay == 1
    assert led.exposed_comm_s < led.total_comm_s
    assert 0.0 < led.overlap_efficiency < 1.0
    assert "overlap-eff" in rep.summary()
    assert "delay D=1" in rep.summary()
    back = RunReport.from_dict(json.loads(json.dumps(rep.to_dict())))
    assert back.ledger.overlap_efficiency == pytest.approx(led.overlap_efficiency)


def test_issue_await_span_split_in_trace():
    """Under the obs recorder, a timed D ≥ 1 run splits the allreduce_gv
    probe span into issue (dispatch cost) + await (exposed remainder),
    and their sum never exceeds the unsplit phase."""
    from repro.obs import trace as obs_trace

    spec = ExperimentSpec(
        dataset="rcv1-sm",
        schedule=ParallelSGDSchedule.hybrid(
            2, 2, 4, 0.05, 8, rounds=2, loss_every=0, delay=1
        ),
        mesh=MeshSpec(p_r=2, p_c=2, backend="simulated"),
        comm_timing=True,
    )
    with obs_trace.install() as rec:
        rep = run(spec)
    cats = {s.category for s in rec.spans}
    assert "allreduce_gv_issue" in cats
    assert "allreduce_gv_await" in cats
    assert "allreduce_gv" not in cats  # fully replaced at D ≥ 1
    split = sum(
        s.dur for s in rec.spans
        if s.category in ("allreduce_gv_issue", "allreduce_gv_await")
    )
    assert split <= rep.ledger.phase_seconds["allreduce_gv"] + 1e-9


# ---- cost model: max(comm, compute) pricing + delay recommendation ----


def test_cost_model_overlap_pricing():
    machine = MACHINES["perlmutter-cpu"]
    m, n, zbar = 20_000, 47_000, 50.0
    cfg = HybridConfig(p_r=2, p_c=4, s=2, b=8, tau=8)
    sync = hybrid_epoch_cost(m, n, zbar, cfg, machine)
    assert sync.overlap_saved == 0.0
    over = hybrid_epoch_cost(m, n, zbar, cfg, machine, delay=1)
    assert over.overlap_saved > 0.0
    assert over.total == pytest.approx(sync.total - over.overlap_saved)
    # the decomposed terms keep their synchronous values
    for f in ("compute", "latency", "gram_bw", "sync_bw"):
        assert getattr(over, f) == getattr(sync, f)
    # savings cap: never more than the whole Gram-phase comm, and deep
    # pipelines saturate there
    deep = hybrid_epoch_cost(m, n, zbar, cfg, machine, delay=1000)
    assert deep.overlap_saved <= sync.gram_bw + sync.latency
    assert deep.overlap_saved >= over.overlap_saved
    # p_c = 1: no row-team Allreduce, nothing to hide
    cfg1 = HybridConfig(p_r=8, p_c=1, s=1, b=8, tau=8)
    assert hybrid_epoch_cost(m, n, zbar, cfg1, machine, delay=3).overlap_saved == 0.0


def test_recommend_delay_bounds():
    machine = MACHINES["perlmutter-cpu"]
    m, n, zbar = 20_000, 47_000, 50.0
    cfg = HybridConfig(p_r=2, p_c=4, s=2, b=8, tau=8)
    d = recommend_delay(m, n, zbar, cfg, machine)
    assert 1 <= d <= cfg.tau // cfg.s
    # the recommended D prices at least as well as any shallower one
    totals = [
        hybrid_epoch_cost(m, n, zbar, cfg, machine, delay=k).total
        for k in range(0, d + 1)
    ]
    assert totals[d] == min(totals)
    # p_c = 1 → 0 (stay synchronous-exact)
    assert recommend_delay(m, n, zbar, HybridConfig(8, 1, 1, 8, 8), machine) == 0


def test_plan_surfaces_delay():
    sched = ParallelSGDSchedule.hybrid(2, 2, 8, 0.05, 8, rounds=2, delay=2)
    spec = ExperimentSpec(
        dataset="rcv1-sm", schedule=sched,
        mesh=MeshSpec(p_r=2, p_c=4, backend="simulated"),
    )
    pl = plan(spec)
    assert pl.recommended_delay >= 1
    assert pl.cost.overlap_saved > 0.0
    assert "delay D=2" in pl.summary()
    # synchronous spec on the same mesh: pricing unchanged, but the
    # recommendation still surfaces what overlap would buy
    pl0 = plan(dataclasses.replace(spec, schedule=dataclasses.replace(sched, delay=0)))
    assert pl0.cost.overlap_saved == 0.0
    assert pl0.recommended_delay == pl.recommended_delay


# ---- spec serialization: delay-0 byte compatibility ----


def test_spec_serialization_compat():
    sched = _hybrid_sched()
    spec = ExperimentSpec(
        dataset="rcv1-sm", schedule=sched,
        mesh=MeshSpec(p_r=2, p_c=2, backend="simulated"),
    )
    d = spec.to_dict()
    assert "delay" not in d["schedule"]  # D=0 invisible on the wire
    assert ExperimentSpec.from_dict(d).schedule.delay == 0
    spec1 = dataclasses.replace(
        spec, schedule=dataclasses.replace(sched, delay=1)
    )
    d1 = spec1.to_dict()
    assert d1["schedule"]["delay"] == 1
    assert ExperimentSpec.from_dict(d1).schedule.delay == 1
    # the knob moves the content hash, so D ≥ 1 runs never collide with
    # synchronous resume dirs
    assert spec1.content_hash() != spec.content_hash()


def test_sweep_cli_delay_override():
    from repro.launch.sweep import load_specs

    path = Path(__file__).parent.parent / "examples" / "specs" / "overlap_mesh.json"
    (loaded,) = load_specs(path)
    assert loaded.schedule.delay == 1
    bumped = dataclasses.replace(
        loaded, schedule=dataclasses.replace(loaded.schedule, delay=2)
    )
    assert bumped.schedule.delay == 2  # what `--delay 2` applies


# ---- decaying-τ compensation (One-Shot Averaging) ----


def test_decaying_tau_converges_with_delay():
    """The compensation knob: a delayed run under the decaying-τ
    schedule (sync often early, then progressively less) reaches the
    same neighborhood as the synchronous fixed-τ run."""
    sched = ParallelSGDSchedule.hybrid(
        2, 2, 4, 0.05, 4, rounds=6, loss_every=0, delay=1
    )
    spec = ExperimentSpec(
        dataset="rcv1-sm", schedule=sched,
        mesh=MeshSpec(p_r=2, p_c=2, backend="simulated"),
    )
    reps = run_decaying_tau(spec, stages=3, growth=2)
    assert [r.spec.schedule.tau for r in reps] == [4, 8, 16]
    assert sum(r.spec.schedule.rounds for r in reps) == 6
    sync = run(
        dataclasses.replace(spec, schedule=dataclasses.replace(sched, delay=0))
    )
    assert reps[-1].final_loss < reps[0].final_loss  # still descending
    assert abs(reps[-1].final_loss - sync.final_loss) < 0.01
    with pytest.raises(ValueError, match="stages"):
        run_decaying_tau(spec, stages=0)
    with pytest.raises(ValueError, match="cannot cover"):
        run_decaying_tau(spec, stages=7)
