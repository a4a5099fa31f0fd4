"""Device time by named scope and the program's host spans
(``bench/scopes.py``) and the readers built on them, on a recorded v5e
trace and on hand-made events.

``scoped_fixtures/rcv1_sstep_scoped.xplane.pb`` is two
``bench.step_rounds`` calls of the rcv1 s-step config on one TPU v5e
chip (4 rounds of 4 bundles each, then the loss probe), from a program
with the named scopes and ``repro.*`` spans, cut down to the device
ops with their ``tf_op`` stat, the program events and the ``bench.*``
/ ``repro.*`` host spans. It sits outside ``fixtures/``, whose traces
``test_bench_trace_reduce.py`` reads whole.
"""

import dataclasses
import shutil
from pathlib import Path

import pytest

from bench import scopes
from bench import trace_reduce as tr
from bench.harness import Run, load_cell, load_metric
from bench.peaks import peaks_for
from bench.work import Call

HERE = Path(__file__).parent
READERS = ("probe_ms_per_call", "gram_prep_ms_per_bundle", "corrections_ms_per_bundle",
           "update_ms_per_bundle", "copy_x_ms_per_call")


def traced_run(tmp_path, fixture_dir: Path) -> Run:
    """A run of the news20 cell whose traced window is ``fixture_dir``'s
    trace, laid where the harness writes it."""
    cell = dataclasses.replace(load_cell("news20-sstep.t2l"), root=tmp_path)
    shutil.copytree(fixture_dir, tmp_path / ".bench_out" / cell.name / "trace")
    trace = tr.load(str(fixture_dir))
    return Run(cell=cell, chips=1, rows_per_round=256, window_s=0.05, rounds=8,
               probes=[(0.026, 4, 0.69), (0.052, 8, 0.68)], crossing=(0.052, 8),
               peaks=peaks_for("TPU v5 lite"), trace=trace, trace_span=tr.span(trace),
               traced_rounds=8, calls=[Call(0, 9472, 57000, 4736)] * 32, flops=10**8)


@pytest.fixture(scope="module")
def prof():
    return scopes.load(str(HERE / "scoped_fixtures"))


def test_fixture_holds_the_spans_and_the_scopes(prof):
    lo, hi = prof.window()
    assert 50e6 < hi - lo < 60e6  # two calls of ~27 ms
    names = [h.name for h in prof.host if lo <= h.start < hi and h.name != "bench.window"]
    for name in ("bench.step_rounds", "repro.step", "repro.round", "repro.probe",
                 "repro.copy_x"):
        assert names.count(name) == 2, name
    steps = scopes.spans(prof, "repro.step", lo, hi)
    for h in prof.host:
        if h.name in ("repro.round", "repro.probe", "repro.copy_x"):
            assert any(s.start <= h.start and h.end <= s.end for s in steps), h
    assert [s.args["step_num"] for s in steps] == [0, 4]
    leaves = scopes.leaf_ops(prof, 0, lo, hi)
    assert {o.scope for o in leaves} == {"", "bundle_gram", "corrections", "update",
                                         "loss_probe"}


def test_the_named_kernel_is_still_matched(prof):
    lo, hi = prof.window()
    grams = [o for o in prof.ops[0] if lo <= o.start < hi and tr.is_gram(o.name)]
    assert len(grams) == 2 * 4 * 4  # calls x rounds x bundles
    assert all(o.name.startswith("%ell_gram.") and o.scope == "bundle_gram" for o in grams)


def test_scoped_readers_on_the_fixture(tmp_path, capsys):
    run = traced_run(tmp_path, HERE / "scoped_fixtures")
    read = {m: load_metric(m)(run) for m in READERS + ("round_other_ms",)}
    lo, hi = run.trace_span
    loss_ms = [(m.end - m.start) / 1e6 for m in tr.within(run.trace.modules[0], lo, hi)
               if m.name.startswith("jit_engine_loss")]
    # the probe's scope agrees with the loss program's own events
    assert read["probe_ms_per_call"] == pytest.approx(sum(loss_ms) / len(loss_ms), rel=0.1)
    assert 10 < read["probe_ms_per_call"] < 20
    # the round body's scopes add up to the ops beside the kernel
    per_round = 4 * sum(read[m] for m in ("gram_prep_ms_per_bundle",
                                          "corrections_ms_per_bundle",
                                          "update_ms_per_bundle"))
    assert per_round == pytest.approx(read["round_other_ms"], rel=0.15)
    assert 0 < read["corrections_ms_per_bundle"] < read["update_ms_per_bundle"]
    assert 0.1 < read["copy_x_ms_per_call"] < 5
    err = capsys.readouterr().err
    assert err.startswith("idle_by_span: repro.probe ")
    idle = scopes.idle_by_span(run)
    named = sum(ns for label, ns in idle.items() if label != scopes.NONE)
    assert named >= 0.9 * sum(idle.values())
    assert sum(idle.values()) == pytest.approx(
        sum(e - s for s, e in tr.gaps(run.trace, 0, lo, hi)), rel=1e-5)  # ns rounding


def test_readers_read_nothing_without_scopes_or_spans(tmp_path, capsys):
    run = traced_run(tmp_path, HERE / "fixtures")
    assert {m: load_metric(m)(run) for m in READERS} == dict.fromkeys(READERS)
    assert "idle_by_span: none " in capsys.readouterr().err
    untraced = dataclasses.replace(run, trace=None)
    assert {m: load_metric(m)(untraced) for m in READERS} == dict.fromkeys(READERS)


# ---- hand-made events ------------------------------------------------


@pytest.mark.parametrize("path, scope", [
    ("jit(_engine_chunk)/while/body/closed_call/bundle_gram/broadcast_in_dim:", "bundle_gram"),
    ("jit(f)/while/body/corrections/while/body/closed_call/dot_general:", "corrections"),
    ("jit(engine_loss)/loss_probe/jit(_take)/gather:", "loss_probe"),
    ("jit(f)/update/param_avg:", "param_avg"),
    ("jit(f)/while/body/dynamic_slice:", ""),
    ("jit(f)/bundle_gram_extra/add:", ""),
])
def test_innermost_scope(path, scope):
    assert scopes.innermost_scope(path) == scope


def _space(tmp_path) -> Path:
    """One chip and one host thread: a while op holding a scoped body, a
    scoped kernel, an unscoped op; step / round / probe spans."""
    space = scopes._message_classes()()

    def plane(name):
        p = space.planes.add()
        p.name = name.encode()
        return p

    def stat(owner, key, **value):
        s = owner.stats.add()
        s.metadata_id = key
        for k, v in value.items():
            setattr(s, k, v)

    dev = plane("/device:TPU:0")
    for key, name in ((1, "tf_op"), (2, "jit(f)/while/body/update/scatter-add:")):
        e = dev.stat_metadata.add(key=key)
        e.value.name = name.encode()
    ops = {10: ("%while.1 = while()", dict(str_value=b"jit(f)/while:")),
           11: ("%ell_gram.1 = (f32[64,64], f32[64,1]) custom-call(%a)",
                dict(str_value=b"jit(f)/while/body/bundle_gram/ell_gram:")),
           12: ("%pad.1 = f32[8] pad(%x)", dict(str_value=b"jit(f)/while/body/bundle_gram/pad:")),
           13: ("%scatter.1 = f32[8] scatter(%x)", dict(ref_value=2)),
           14: ("%copy.1 = f32[8] copy(%x)", {})}
    for key, (name, value) in ops.items():
        m = dev.event_metadata.add(key=key).value
        m.name = name.encode()
        if value:
            stat(m, 1, **value)
    line = dev.lines.add(name=b"XLA Ops", timestamp_ns=1000)
    for key, start, dur in ((10, 0, 100), (12, 0, 10), (11, 10, 50), (13, 60, 30),
                            (14, 150, 20)):
        line.events.add(metadata_id=key, offset_ps=start * 1000, duration_ps=dur * 1000)

    host = plane("/host:CPU")
    for key, name in ((1, "step_num"), (2, "name")):
        e = host.stat_metadata.add(key=key)
        e.value.name = name.encode()
    for key, name in enumerate(("bench.window", "bench.step_rounds", "repro.step",
                                "repro.round", "repro.probe", "other"), start=20):
        host.event_metadata.add(key=key).value.name = name.encode()
    line = host.lines.add(name=b"python", timestamp_ns=1000)
    for key, start, end in ((20, 0, 300), (21, 0, 250), (22, 0, 250), (23, 0, 40),
                            (24, 100, 240), (25, 0, 300)):
        e = line.events.add(metadata_id=key, offset_ps=start * 1000,
                            duration_ps=(end - start) * 1000)
        if key == 22:
            stat(e, 1, int64_value=7)
        if key == 23:
            stat(e, 2, str_value=b"rounds[7+1]")
    path = tmp_path / "t" / "plugins" / "profile" / "0" / "h.xplane.pb"
    path.parent.mkdir(parents=True)
    path.write_bytes(space.SerializeToString())
    return path.parents[3]


def test_parse_reads_scopes_from_metadata_and_args_from_events(tmp_path):
    prof = scopes.load(str(_space(tmp_path)))
    assert prof.window() == (1000, 1300)
    assert [(o.name.split(" ")[0], o.start, o.end, o.scope) for o in prof.ops[0]] == [
        ("%while.1", 1000, 1100, ""), ("%pad.1", 1000, 1010, "bundle_gram"),
        ("%ell_gram.1", 1010, 1060, "bundle_gram"), ("%scatter.1", 1060, 1090, "update"),
        ("%copy.1", 1150, 1170, "")]
    assert [h.name for h in prof.host] == ["bench.window", "bench.step_rounds",
                                           "repro.step", "repro.round", "repro.probe"]
    args = {h.name: h.args for h in prof.host}
    assert args["repro.step"] == {"step_num": 7}
    assert args["repro.round"] == {"name": "rounds[7+1]"}


def test_scope_time_counts_leaves_and_idle_splits_by_innermost_span(tmp_path):
    prof = scopes.load(str(_space(tmp_path)))
    lo, hi = prof.window()
    # the while op holds the scoped body: only its leaves count
    assert scopes.scope_ns(prof, [0], "bundle_gram", lo, hi) == [60]
    assert scopes.scope_ns(prof, [0], "bundle_gram", lo, hi, skip=tr.is_gram) == [10]
    assert scopes.scope_ns(prof, [0], "update", lo, hi) == [30]
    assert scopes.scope_ns(prof, [0], "loss_probe", lo, hi) == [0]
    # idle: 1100..1150 in repro.probe, 1170..1240 in repro.probe,
    # 1240..1250 in the step only, 1250..1300 outside the harness's call
    gaps = tr.gaps(tr.Trace(ops={0: [tr.Ev(o.name, o.start, o.end) for o in prof.ops[0]]},
                            modules={}, host=[]), 0, lo, hi)
    assert scopes._split(gaps, [h for h in prof.host if h.name != "bench.window"]) == {
        "repro.probe": 120, "repro.step": 10, scopes.OUTSIDE: 50}
