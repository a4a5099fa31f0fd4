"""Sweep retry/quarantine under injected faults.

A sweep point's FaultPolicy is its recovery contract: transient faults
are retried (resuming from the point's autosave, so progress is kept),
persistent faults quarantine the point after 1 + max_retries attempts,
and the rest of the sweep always completes. The quarantine lands in
``SweepReport`` and survives ``to_json()`` — the artifact CI uploads.
"""

import json

import numpy as np
import pytest

from repro.api import (
    ExperimentSpec,
    FaultPolicy,
    MeshSpec,
    Session,
    autosave_base,
    run,
    sweep,
)
from repro.core import ParallelSGDSchedule
from repro.core.faults import FaultEvent, FaultPlan, install


def _spec(name, **over):
    sched = ParallelSGDSchedule.hybrid(2, 2, 4, 0.05, 8, rounds=6, loss_every=2)
    base = dict(
        dataset="rcv1-sm",
        schedule=sched,
        mesh=MeshSpec(p_r=2, p_c=1),
        name=name,
    )
    base.update(over)
    return ExperimentSpec(**base)


def test_persistent_failure_quarantines_and_sweep_completes(tmp_path):
    doomed = _spec("doomed", faults=FaultPolicy(max_retries=2))
    fine = _spec("fine")
    plan = FaultPlan(
        events=[FaultEvent(kind="io_error", site="point", at=0, times=99)]
    )
    with install(plan) as inj:
        report = sweep([doomed, fine], resume_dir=tmp_path)

    assert [r.spec.name for r in report.reports] == ["fine"]
    assert len(report.quarantined) == 1
    q = report.quarantined[0]
    assert q.name == "doomed"
    assert q.attempts == 3  # 1 + max_retries
    assert q.spec_hash == doomed.content_hash()
    assert "TransientIOError" in q.error
    # every attempt hit the seam, none leaked into the healthy point
    assert inj.fired == [("io_error", "point", 0)] * 3

    # the quarantine survives the JSON artifact round-trip
    blob = json.loads(report.to_json())
    assert blob["quarantined"] == [q.to_dict()]
    assert [r["spec"]["name"] for r in blob["reports"]] == ["fine"]
    assert "1 quarantined" in report.summary()


def test_transient_failure_retries_and_matches_clean_run(tmp_path):
    """One injected mid-run fault: the retry resumes from the autosave
    (not round 0) and the finished point is bitwise the clean run."""
    spec = _spec("transient", faults=FaultPolicy(autosave_every=2, max_retries=2))
    clean = run(_spec("transient"))

    plan = FaultPlan(events=[FaultEvent(kind="io_error", site="round", at=4, times=1)])
    with install(plan) as inj:
        report = sweep([spec], resume_dir=tmp_path)

    assert report.attempts == [2]  # failed once, succeeded on retry
    assert report.quarantined == []
    assert inj.fired == [("io_error", "round", 4)]
    assert np.array_equal(report.reports[0].x, clean.x)
    assert np.array_equal(report.reports[0].losses, clean.losses)
    # the retry resumed *past* the faulting round: round 4 was visited
    # once (the event had times=1 left but never re-fired)
    assert report.reports[0].rounds_completed == 6
    # success spends the autosave
    assert not autosave_base(tmp_path, spec).with_suffix(".npz").exists()


def test_retry_resumes_from_autosave_round(tmp_path):
    """Directly observe the resume: after the faulted first attempt the
    autosave sits at the fault round; opening it fast-forwards there."""
    spec = _spec("resume-probe", faults=FaultPolicy(autosave_every=1, max_retries=0))
    plan = FaultPlan(events=[FaultEvent(kind="io_error", site="round", at=3, times=1)])
    with install(plan):
        report = sweep([spec], resume_dir=tmp_path)
    # max_retries=0 → quarantined on the first failure, with progress
    assert report.quarantined[0].rounds_done == 3
    sess = Session.restore(autosave_base(tmp_path, spec), spec=spec)
    assert sess.rounds_done == 3

    # a later invocation (fault cleared) picks the autosave up and
    # finishes the point from round 3
    report2 = sweep([spec], resume_dir=tmp_path)
    assert report2.attempts == [1]
    assert report2.reports[0].rounds_completed == 6
    assert np.array_equal(report2.reports[0].x, run(_spec("resume-probe")).x)


def test_corrupt_autosave_is_discarded_not_fatal(tmp_path):
    """A torn autosave (truncated payload) must not wedge the point:
    the retry discards it and restarts the point from round 0."""
    spec = _spec("torn", faults=FaultPolicy(autosave_every=2, max_retries=1))
    # seed a deliberately torn autosave where the sweep will look
    base = autosave_base(tmp_path, spec)
    sess = Session(spec, autosave_dir=tmp_path)
    sess.step_rounds(2)
    sess.save(base)
    npz = base.with_suffix(".npz")
    npz.write_bytes(npz.read_bytes()[:-128])

    report = sweep([spec], resume_dir=tmp_path)
    assert report.quarantined == []
    assert report.attempts == [1]
    assert np.array_equal(report.reports[0].x, run(_spec("torn")).x)


def test_stall_fault_slows_but_never_fails(tmp_path):
    spec = _spec("slow")
    plan = FaultPlan(
        events=[FaultEvent(kind="stall", site="round", at=None, times=3, delay_s=0.01)]
    )
    with install(plan) as inj:
        report = sweep([spec], resume_dir=tmp_path)
    assert [k for k, _, _ in inj.fired] == ["stall"] * 3
    assert report.attempts == [1]
    assert report.quarantined == []


def test_quarantined_point_consumes_a_max_points_slot(tmp_path):
    doomed = _spec("doomed", faults=FaultPolicy(max_retries=0))
    later = _spec("later")
    plan = FaultPlan(events=[FaultEvent(kind="io_error", site="point", at=0, times=99)])
    with install(plan):
        report = sweep([doomed, later], resume_dir=tmp_path, max_points=1)
    assert len(report.quarantined) == 1
    assert report.reports == []
    assert report.skipped == [later.content_hash()]


def test_keyboard_interrupt_is_not_retried(tmp_path):
    """The user hitting ^C mid-point must propagate immediately, not
    burn the retry budget."""
    spec = _spec("interrupted", faults=FaultPolicy(max_retries=5))

    calls = {"n": 0}
    real_init = Session.__init__

    def exploding_init(self, *a, **k):
        calls["n"] += 1
        raise KeyboardInterrupt

    Session.__init__ = exploding_init
    try:
        with pytest.raises(KeyboardInterrupt):
            sweep([spec], resume_dir=tmp_path)
    finally:
        Session.__init__ = real_init
    assert calls["n"] == 1


def test_sweep_cli_exits_nonzero_on_quarantine(tmp_path, capsys, monkeypatch):
    """``repro.launch.sweep`` finishes the sweep, prints the ``[quar ]``
    line, and then exits non-zero: a quarantined point is a failure."""
    from repro.launch.sweep import main

    # with the variable set, main() leaves the worker's compile cache alone
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jax_cache"))
    path = tmp_path / "sweep.json"
    path.write_text(json.dumps([
        _spec("doomed", faults=FaultPolicy(max_retries=0)).to_dict(),
        _spec("fine").to_dict(),
    ]))
    plan = FaultPlan(
        events=[FaultEvent(kind="io_error", site="point", at=0, times=99)]
    )
    with install(plan), pytest.raises(SystemExit) as ei:
        main(["--spec", str(path)])
    assert ei.value.code not in (0, None)
    out = capsys.readouterr().out
    assert "[quar ] doomed" in out and "[run  ] fine" in out
    main(["--spec", str(path)])  # no fault: the same sweep returns normally
