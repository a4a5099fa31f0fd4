"""The explicit communication plane (repro.core.comm): ledger word
counts at the four schedule corners against the Table 2–3 closed forms
(costmodel.hockney.schedule_comm_volume) across (p_r, p_c, s, τ, b)
grids, cross-backend rate parity (captured without devices), ledger
mechanics and JSON round trips, report/spec back-compat alongside the
PR 4 hash tests, and the §6.5 calibration fit.
"""

import dataclasses
import json

import numpy as np
import pytest

from repro.api import (
    CalPoint,
    Calibration,
    ExperimentSpec,
    MeshSpec,
    RunReport,
    Session,
    calibrate,
    modeled_comm_words,
    plan,
    run,
)
from repro.core import ParallelSGDSchedule, engine_comm_ledger, hybrid_comm_ledger
from repro.core.comm import COUNTING, MESH, TIMED, Collectives, CommLedger, CommRate
from repro.core.distributed import build_2d_problem
from repro.costmodel import MACHINES, schedule_comm_volume
from repro.sparse.synthetic import make_skewed_csr

DATASET = "rcv1-sm"


def _assert_ledger_matches_closed_form(sched: ParallelSGDSchedule, n: int):
    led = engine_comm_ledger(sched, n)
    cv = schedule_comm_volume(
        n, sched.p_r, sched.p_c, sched.s, sched.b, sched.tau, rounds=sched.rounds
    )
    assert led.counted_words(rounds=sched.rounds) == cv.words_dict()
    assert led.counted_calls(rounds=sched.rounds) == {
        "gram_calls": cv.gram_calls,
        "sync_calls": cv.sync_calls,
    }
    # the wire payload is bounded below by Table 3's tril information
    assert cv.gram_words_min <= cv.gram_words


# ---------------- the four corners, across knob grids ----------------


@pytest.mark.parametrize("p_c", [1, 2, 4, 8])
@pytest.mark.parametrize("b", [1, 4, 8])
def test_mbsgd_corner_counts(p_c, b):
    """MB-SGD (p_r=1, s=1, τ=1): one (b²+b)-word Gram Allreduce per
    round when columns are sharded; never a sync Allreduce."""
    sched = ParallelSGDSchedule.mb_sgd(b, 0.05, 3, p_c=p_c)
    _assert_ledger_matches_closed_form(sched, n=97)
    led = engine_comm_ledger(sched, 97)
    words = led.counted_words(rounds=3)
    assert words["sync_words"] == 0.0
    assert words["gram_words"] == (3.0 * (b * b + b) if p_c > 1 else 0.0)


@pytest.mark.parametrize("p_c", [1, 2, 8])
@pytest.mark.parametrize("s,b", [(2, 2), (2, 8), (4, 4), (8, 2)])
def test_sstep_corner_counts(p_c, s, b):
    """1D s-step (p_r=1, τ=s): one (s²b²+sb)-word bundle Allreduce per
    round — communication amortized s-fold versus MB-SGD."""
    sched = ParallelSGDSchedule.sstep(s, b, 0.05, 4 * s, p_c=p_c)
    _assert_ledger_matches_closed_form(sched, n=211)
    led = engine_comm_ledger(sched, 211)
    sb = s * b
    expected = float(sched.rounds * (sb * sb + sb)) if p_c > 1 else 0.0
    assert led.counted_words(rounds=sched.rounds)["gram_words"] == expected
    assert led.counted_words(rounds=sched.rounds)["sync_words"] == 0.0


@pytest.mark.parametrize("p_r", [1, 2, 4, 8])
@pytest.mark.parametrize("tau", [1, 4, 8])
def test_fedavg_corner_counts(p_r, tau):
    """FedAvg (s=1, p_c=1): one n-word weight average per round when
    there is more than one team; no Gram traffic ever."""
    sched = ParallelSGDSchedule.fedavg(p_r, 4, 0.05, tau, 3)
    _assert_ledger_matches_closed_form(sched, n=157)
    led = engine_comm_ledger(sched, 157)
    words = led.counted_words(rounds=3)
    assert words["gram_words"] == 0.0
    assert words["sync_words"] == (3.0 * 157 if p_r > 1 else 0.0)


@pytest.mark.parametrize("p_r,p_c", [(1, 1), (2, 1), (1, 4), (2, 2), (4, 2), (2, 8)])
@pytest.mark.parametrize("s,b,tau", [(1, 4, 4), (2, 4, 8), (4, 2, 8)])
def test_hybrid_counts_across_grid(p_r, p_c, s, b, tau):
    """The general 2D point: τ/s Gram Allreduces of (s²b²+sb) words plus
    one ⌈n/p_c⌉-word sync per round, each active only when its mesh
    axis spans more than one rank."""
    sched = ParallelSGDSchedule.hybrid(p_r, s, b, 0.05, tau, rounds=5, p_c=p_c)
    n = 301
    _assert_ledger_matches_closed_form(sched, n)
    led = engine_comm_ledger(sched, n)
    words = led.counted_words(rounds=5)
    sb = s * b
    bundles = 5 * (tau // s)
    assert words["gram_words"] == (float(bundles * (sb * sb + sb)) if p_c > 1 else 0.0)
    assert words["sync_words"] == (float(5 * -(-n // p_c)) if p_r > 1 else 0.0)


def test_modeled_comm_words_is_the_closed_form():
    """The report's modeled volume and the hockney closed form are one
    computation — the refactor must not have moved a single word."""
    spec = ExperimentSpec(
        dataset=DATASET,
        schedule=ParallelSGDSchedule.hybrid(2, 2, 4, 0.05, 8, rounds=4),
        mesh=MeshSpec(p_r=2, p_c=4),
    )
    from repro.api.spec import dataset_stats

    n = dataset_stats(DATASET).n
    sched = spec.schedule
    cv = schedule_comm_volume(n, 2, 4, sched.s, sched.b, sched.tau, rounds=4)
    assert modeled_comm_words(spec) == cv.words_dict()
    # and the rounds override scales the round-linear terms
    half = modeled_comm_words(spec, rounds=2)
    assert half["total_words"] == pytest.approx(cv.total_words / 2)


# ---------------- cross-backend rate parity (no devices) ----------------


@pytest.mark.parametrize("p_r,p_c", [(1, 1), (2, 2), (4, 2), (1, 8), (8, 1)])
def test_mesh_and_engine_capture_identical_rates(p_r, p_c):
    """hybrid_comm_ledger traces the real shard_map round body
    abstractly — no device mesh needed — and must record exactly the
    rates the simulated engine records for the same schedule (the
    acceptance identity; the subprocess test re-checks it on real
    devices end to end)."""
    rng = np.random.default_rng(0)
    a = make_skewed_csr(256, 100, 12, 0.8, seed=3)
    y = np.where(rng.random(256) < 0.5, 1.0, -1.0)
    sched = ParallelSGDSchedule.hybrid(p_r, 2, 4, 0.05, 8, rounds=3, p_c=p_c)
    prob, _cp = build_2d_problem(a, y, p_r, p_c, "cyclic", row_multiple=8)
    mesh_led = hybrid_comm_ledger(prob, sched)
    sim_led = engine_comm_ledger(sched, 100)
    assert mesh_led.rates == sim_led.rates
    assert mesh_led.counted_words(rounds=3) == sim_led.counted_words(rounds=3)


def test_s1_corner_counts_full_bundle_payload():
    """At s=1 the round body computes v alone but still reduces a zero
    (b, b) G beside it, on both backends, so both ledgers count the
    b²+b words of the Table 3 payload from its real shape."""
    rng = np.random.default_rng(0)
    a = make_skewed_csr(64, 40, 6, 0.8, seed=3)
    y = np.where(rng.random(64) < 0.5, 1.0, -1.0)
    sched = ParallelSGDSchedule.hybrid(2, 1, 4, 0.05, 4, rounds=2, p_c=2)
    prob, _cp = build_2d_problem(a, y, 2, 2, "cyclic", row_multiple=4)
    assert hybrid_comm_ledger(prob, sched).rates == engine_comm_ledger(sched, 40).rates


# ---------------- ledger + collectives mechanics ----------------


def test_ledger_accumulation_and_round_trip():
    rate = CommRate(op="allreduce", axis="cols", span=4,
                    words_per_call=72, calls_per_round=4)
    led = CommLedger(rates=(rate,))
    led.add_rounds(3)
    led.add_round_seconds(0.5)
    led.add_round_seconds(0.1)
    led.add_round_seconds(0.2)
    assert led.counted_words() == {
        "gram_words": 3 * 4 * 72.0, "sync_words": 0.0, "total_words": 864.0,
    }
    assert led.counted_calls() == {"gram_calls": 12, "sync_calls": 0}
    assert led.phases_per_round() == 4 * 2 * 2  # 4 calls × 2⌈log₂4⌉
    assert led.bytes_per_round(8) == 8 * 4 * 72.0
    assert led.seconds_per_round == 0.2  # median
    restored = CommLedger.from_dict(json.loads(json.dumps(led.to_dict())))
    assert restored == led
    # snapshot is independent
    snap = led.snapshot()
    led.add_rounds(1)
    assert snap.rounds == 3 and led.rounds == 4


def test_span1_collective_moves_nothing():
    rate = CommRate(op="allmean", axis="rows", span=1,
                    words_per_call=1000, calls_per_round=1)
    led = CommLedger(rates=(rate,), rounds=10)
    assert led.counted_words()["total_words"] == 0.0
    assert led.counted_calls() == {"gram_calls": 0, "sync_calls": 0}
    assert led.phases_per_round() == 0
    assert rate.phases_per_call == 0


def test_collectives_kinds():
    assert COUNTING.kind == "counting" and not COUNTING.on_mesh
    assert MESH.on_mesh and not MESH.timed
    assert TIMED.on_mesh and TIMED.timed
    with pytest.raises(ValueError, match="kind"):
        Collectives("no-such-kind")


# ---------------- session / report threading ----------------


@pytest.fixture(scope="module")
def small_spec():
    return ExperimentSpec(
        dataset=DATASET,
        schedule=ParallelSGDSchedule.hybrid(2, 2, 4, 0.05, 8, rounds=3, loss_every=1),
        mesh=MeshSpec(p_r=2, p_c=2),
        name="comm-sess",
    )


@pytest.fixture(scope="module")
def small_report(small_spec):
    return run(small_spec)


def test_report_counted_equals_modeled_on_simulated(small_report):
    """Closing the loop: for the simulated backend the counted volume
    must equal the Table 2–3 model exactly — the model now describes
    collectives the code demonstrably issues."""
    rep = small_report
    assert rep.ledger is not None
    assert rep.ledger.rounds == rep.rounds_completed == 3
    assert rep.ledger.counted_words() == rep.comm_words
    assert "counted" in rep.summary()


def test_round_events_carry_ledger_snapshots(small_spec):
    sess = Session(small_spec)
    ev1 = sess.step_rounds(1)
    ev2 = sess.step_rounds(2)
    assert ev1.ledger.rounds == 1 and ev2.ledger.rounds == 3
    # the event snapshot is frozen at its boundary, not a live view
    assert ev1.ledger.counted_words()["total_words"] == pytest.approx(
        ev2.ledger.counted_words()["total_words"] / 3
    )


def test_report_json_round_trips_ledger(small_report):
    rep2 = RunReport.from_json(small_report.to_json())
    assert rep2.ledger == small_report.ledger


def test_pre_ledger_report_json_loads(small_report):
    """Back-compat: a report persisted before the comm plane existed
    (no comm_ledger key) rehydrates with ledger=None and no counted
    column in its summary."""
    d = small_report.to_dict()
    del d["comm_ledger"]
    rep = RunReport.from_dict(d)
    assert rep.ledger is None
    assert "counted" not in rep.summary()
    assert rep.calibration_point() is None


def test_spec_comm_timing_back_compat(small_spec):
    """Alongside the PR 4 hash tests: comm_timing is emitted only when
    on, so old spec JSON loads with the default and default specs keep
    their content hash (checkpoints/resume dirs stay valid)."""
    d = small_spec.to_dict()
    assert "comm_timing" not in d
    restored = ExperimentSpec.from_dict(d)
    assert restored == small_spec and not restored.comm_timing
    assert restored.content_hash() == small_spec.content_hash()
    timed = dataclasses.replace(small_spec, comm_timing=True)
    assert timed.to_dict()["comm_timing"] is True
    assert ExperimentSpec.from_json(timed.to_json()) == timed
    assert timed.content_hash() != small_spec.content_hash()


def test_timed_simulated_run_measures_without_changing_iterates(small_spec, small_report):
    rep = run(dataclasses.replace(small_spec, comm_timing=True))
    np.testing.assert_array_equal(rep.x, small_report.x)
    np.testing.assert_array_equal(rep.losses, small_report.losses)
    assert len(rep.ledger.round_seconds) == 3
    assert rep.ledger.seconds_per_round > 0
    pt = rep.calibration_point()
    assert pt is not None and pt.seconds_per_round > 0
    assert pt.phases_per_round == rep.ledger.phases_per_round()


# ---------------- calibration ----------------


def test_calibrate_recovers_planted_constants():
    """Synthesize per-round times from known (α, β, γ) over a spread of
    operating points; the least-squares fit must recover them."""
    alpha, beta, gamma = 3e-6, 2e-9, 5e-11
    rng = np.random.default_rng(7)
    points = []
    for _ in range(12):
        phases = float(rng.integers(2, 40))
        byts = float(rng.integers(1_000, 1_000_000))
        flops = float(rng.integers(10_000, 10_000_000))
        t = alpha * phases + beta * byts + gamma * flops
        points.append(CalPoint(phases, byts, flops, t))
    cal = calibrate(points)
    assert cal.alpha == pytest.approx(alpha, rel=1e-6)
    assert cal.beta == pytest.approx(beta, rel=1e-6)
    assert cal.gamma == pytest.approx(gamma, rel=1e-6)
    assert cal.rel_rms == pytest.approx(0.0, abs=1e-9)
    assert cal.points == 12
    # round trip
    assert Calibration.from_dict(cal.to_dict()) == cal


def test_calibration_machine_retarget():
    cal = Calibration(alpha=1e-5, beta=4e-9, gamma=2e-11, rel_rms=0.0, points=3)
    base = MACHINES["perlmutter-cpu"]
    fitted = cal.machine(base)
    assert fitted.name == "perlmutter-cpu+calibrated"
    for q in (2, 64, 4096):
        assert fitted.alpha(q) == pytest.approx(1e-5)
        assert fitted.beta(q) == pytest.approx(4e-9)
    # γ is stored as s/B tiers; the fitted s/flop must survive the trip
    assert fitted.gamma_flop(1 << 30) == pytest.approx(2e-11)
    # unidentified terms keep the preset tables
    partial = Calibration(alpha=0.0, beta=4e-9, gamma=0.0, rel_rms=0.0, points=1)
    kept = partial.machine(base)
    assert kept.alpha(64) == base.alpha(64)
    assert kept.gamma_tiers == base.gamma_tiers


def test_calibrate_ignores_dead_columns_and_clamps():
    # no comm columns at all → only γ fits, α/β stay 0
    pts = [CalPoint(0.0, 0.0, f, 1e-9 * f) for f in (1e6, 2e6, 5e6)]
    cal = calibrate(pts)
    assert cal.alpha == 0.0 and cal.beta == 0.0
    assert cal.gamma == pytest.approx(1e-9)
    with pytest.raises(ValueError, match="at least one"):
        calibrate([])
    with pytest.raises(ValueError, match="seconds_per_round"):
        CalPoint(1.0, 1.0, 1.0, 0.0)


def test_plan_with_calibration_reranks(small_spec):
    """plan(spec, calibration=...) must predict with the fitted machine
    — a bandwidth-free calibration collapses the comm terms and can
    invert a preset ranking."""
    base = plan(small_spec)
    assert not base.calibrated
    cal = Calibration(alpha=0.0, beta=1e-3, gamma=0.0, rel_rms=0.0, points=2)
    pl = plan(small_spec, calibration=cal)
    assert pl.calibrated and "+calibrated" in pl.summary()
    # β inflated 6 orders of magnitude → bandwidth must now dominate
    assert pl.cost.total > base.cost.total
    assert pl.cost.gram_bw + pl.cost.sync_bw > base.cost.gram_bw + base.cost.sync_bw
