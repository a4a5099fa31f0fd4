"""The trace reduction, on a recorded v5e trace and on hand-made events.

``fixtures/rcv1_sstep_call.xplane.pb`` is two ``bench.step_rounds``
calls of the rcv1 s-step cell on one TPU v5e chip (4 rounds of 4
bundles each, then the loss probe), cut down to the device ops, the
program events and the harness's and the Session's host spans.
"""

from pathlib import Path

import pytest

from bench import trace_reduce as tr
from bench.harness import Run, load_cell, load_metric
from bench.peaks import peaks_for
from bench.work import Call

FIXTURES = Path(__file__).parent / "fixtures"


@pytest.fixture(scope="module")
def trace():
    return tr.load(str(FIXTURES))


def test_fixture_has_one_chip_and_the_window(trace):
    assert sorted(trace.ops) == [0]
    lo, hi = tr.span(trace)
    assert 50e6 < hi - lo < 55e6  # two calls of ~26 ms
    busy = tr.busy_ns(trace, 0, lo, hi)
    assert 0 < busy < hi - lo
    idle = sum(e - s for s, e in tr.gaps(trace, 0, lo, hi))
    assert idle == (hi - lo) - busy


def test_gram_kernel_events_are_found_by_target_and_shapes(trace):
    lo, hi = tr.span(trace)
    grams = tr.gram_ops(trace, 0, lo, hi)
    assert len(grams) == 2 * 4 * 4  # calls x rounds x bundles
    assert all('custom_call_target="tpu_custom_call"' in e.name for e in grams)
    assert all(e.name.split(" = ")[1].startswith("(f32[64,64]") for e in grams)
    mean_ms = sum(e.end - e.start for e in grams) / len(grams) / 1e6
    assert 0.3 < mean_ms < 0.6


def test_is_gram_rejects_other_custom_calls():
    other = ('%c.1 = (f32[64,32]{1,0}, f32[64,1]{1,0}) custom-call(s32[64,111]{1,0} %a), '
             'custom_call_target="tpu_custom_call"')
    assert not tr.is_gram(other)
    assert tr.is_gram("%ell_gram.3 = (f32[64,64], f32[64,1]) custom-call(%a)")
    assert not tr.is_gram('%x = f32[8]{0} custom-call(%a), custom_call_target="ConcatBitcast"')


def test_breakdown_lists_the_kernel_and_labelled_gaps(trace):
    lo, hi = tr.span(trace)
    b = tr.breakdown(trace, [0], lo, hi)
    assert 0 < len(b["device_ops"]) <= 10 and 0 < len(b["idle_gaps"]) <= 10
    names = [name for name, _ in b["device_ops"]]
    assert any("tpu_custom_call" in n and n.startswith("jit__engine_chunk/") for n in names)
    secs = [s for _, s in b["device_ops"]]
    assert secs == sorted(secs, reverse=True)
    assert all(label.startswith("bench.") or "< bench." in label for label, _ in b["idle_gaps"])


def test_metric_readers_on_the_fixture(trace):
    cell = load_cell("news20-sstep.t2l")  # the readers take the cell's names only
    run = Run(cell=cell, chips=1, rows_per_round=256, window_s=0.05, rounds=8,
              probes=[(0.026, 4, 0.69), (0.052, 8, 0.68)], crossing=(0.052, 8),
              peaks=peaks_for("TPU v5 lite"), trace=trace, trace_span=tr.span(trace),
              traced_rounds=8, calls=[Call(0, 9472, 57000, 4736)] * 32, flops=10**8)
    read = {m: load_metric(m)(run) for m in (
        "rounds_to_target", "device_idle_share", "gram_ms_per_bundle", "gram_roofline",
        "round_other_ms", "round_mfu", "collective_exposed_share")}
    assert read["rounds_to_target"] == 8
    assert 0 < read["device_idle_share"] < 100
    assert 0.3 < read["gram_ms_per_bundle"] < 0.6
    assert 0 < read["gram_roofline"] < 100
    # the chunk program runs 4 rounds in ~8.6 ms, ~7 ms of it kernel
    assert 0.1 < read["round_other_ms"] < 1.0
    assert 0 < read["round_mfu"] < 100
    assert read["collective_exposed_share"] is None  # one chip: no collective


def _ev(name, start, end):
    return tr.Ev(name, start, end)


def test_leaves_union_overlap_and_exposed_collectives():
    ops = sorted([
        _ev("%while.1 = () while()", 0, 100),
        _ev("%fusion.1 = f32[8] fusion()", 0, 40),
        _ev("%all-reduce.1 = f32[8] all-reduce()", 30, 70),
        _ev("%fusion.2 = f32[8] fusion()", 60, 80),
        _ev("%all-reduce-done.2 = f32[8] all-reduce-done()", 120, 150),
    ], key=lambda e: (e.start, -e.end))
    assert [e.name.split(" ")[0] for e in tr.leaves(ops)] == [
        "%fusion.1", "%all-reduce.1", "%fusion.2", "%all-reduce-done.2"]
    assert tr.union(ops) == [(0, 100), (120, 150)]
    assert tr.overlap([(0, 10), (20, 30)], [(5, 25)]) == 10
    trace = tr.Trace(ops={0: ops}, modules={0: []}, host=[])
    # all-reduce.1 covers 30..70, compute covers 30..40 and 60..70: 20 exposed;
    # the done op is exposed whole: 30
    assert tr.exposed_collective_ns(trace, 0, 0, 200) == 50
    assert tr.gaps(trace, 0, 0, 200) == [(100, 120), (150, 200)]


def test_span_missing_raises():
    with pytest.raises(KeyError):
        tr.span(tr.Trace(ops={}, modules={}, host=[]))
