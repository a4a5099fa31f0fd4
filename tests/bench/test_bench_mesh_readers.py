"""The readers of the 2×2 cell (``allreduce_gv_ms_per_bundle``,
``param_avg_ms_per_round``, ``collective_exposed_share``,
``probe_wall_ms_per_call``, ``round_dispatch_ms_per_round``) on a
recorded v5e 2×2 trace.

``mesh_fixtures/rcv1_hybrid_2x2_mesh.xplane.pb`` is two
``bench.step_rounds`` calls of ``rcv1-hybrid-2x2.t2l`` on a 2×2 v5e
host (4 rounds of 4 bundles each on every chip, then the mesh loss
probe and the copy of x), cut down to the device ops with their
``tf_op`` stat, the program events and the ``bench.*`` / ``repro.*``
host spans, with ``bench.window`` narrowed to the two calls. It sits in
a directory of its own, so the traces the other tests read stay as
they are.
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
MESH = HERE / "mesh_fixtures"
READERS = ("allreduce_gv_ms_per_bundle", "param_avg_ms_per_round", "collective_exposed_share",
           "probe_wall_ms_per_call", "round_dispatch_ms_per_round")
ROUNDS, BUNDLES, CHIPS = 8, 4, 4


def traced_run(tmp_path, fixture_dir: Path, cell: str = "rcv1-hybrid-2x2.t2l",
               chips: int = CHIPS) -> Run:
    """A run of ``cell`` whose traced window is ``fixture_dir``'s trace,
    laid where the harness writes it: 8 rounds, each of 4 bundles on
    each of 2 teams × 2 column shards."""
    c = dataclasses.replace(load_cell(cell), root=tmp_path)
    shutil.copytree(fixture_dir, tmp_path / ".bench_out" / c.name / "trace")
    trace = tr.load(str(fixture_dir))
    return Run(cell=c, chips=chips, rows_per_round=512, window_s=0.026, rounds=ROUNDS,
               probes=[(0.013, 12, 0.69), (0.026, 16, 0.68)], crossing=None,
               peaks=peaks_for("TPU v5 lite"), trace=trace, trace_span=tr.span(trace),
               traced_rounds=ROUNDS, calls=[Call(0, 2368, 30000, 1184)] * (ROUNDS * BUNDLES * 4),
               flops=10**8)


@pytest.fixture(scope="module")
def prof():
    return scopes.load(str(MESH))


def test_fixture_holds_two_calls_on_four_chips(prof):
    lo, hi = prof.window()
    assert 20e6 < hi - lo < 35e6  # two calls of ~13 ms
    names = [h.name for h in prof.host if lo <= h.start < hi and h.name != "bench.window"]
    for name in ("bench.step_rounds", "repro.step", "repro.round", "repro.probe",
                 "repro.copy_x"):
        assert names.count(name) == 2, name
    probes = scopes.spans(prof, "repro.probe", lo, hi)
    assert [h.args["path"] for h in probes] == ["mesh"] * 2
    assert [h.args["rounds"] for h in scopes.spans(prof, "repro.round", lo, hi)] == [4, 4]
    assert scopes.chips(prof, 4) == [0, 1, 2, 3]
    for c in range(CHIPS):
        leaves = scopes.leaf_ops(prof, c, lo, hi)
        gv = [o for o in leaves if o.scope == "allreduce_gv"]
        # one (G, v) all-reduce a bundle, nothing else in the scope
        assert len(gv) == ROUNDS * BUNDLES and all(tr.is_collective(o.name) for o in gv)
        avg = [o for o in leaves if o.scope == "param_avg"]
        assert sum(tr.is_collective(o.name) for o in avg) == ROUNDS


def test_mesh_readers_on_the_fixture(tmp_path, prof):
    run = traced_run(tmp_path, MESH)
    read = {m: load_metric(m)(run) for m in READERS}
    lo, hi = prof.window()

    # the (G, v) psum: its all-reduce events, read apart from the scopes
    gv = [e for c in range(CHIPS) for e in tr.within(run.trace.ops[c], lo, hi)
          if tr.op_name(e.name) == "all-reduce.2"]
    assert len(gv) == CHIPS * ROUNDS * BUNDLES
    # the two parsers round each event's ends to a ns apart: 1 ns of 3 µs
    assert read["allreduce_gv_ms_per_bundle"] == pytest.approx(
        sum(e.end - e.start for e in gv) / len(gv) / 1e6, rel=1e-3)
    assert 0.001 < read["allreduce_gv_ms_per_bundle"] < 0.1

    # the row mean: its all-reduce and the divide after it, once a round
    avg = [o for c in range(CHIPS) for o in scopes.leaf_ops(prof, c, lo, hi)
           if o.scope == "param_avg"]
    assert read["param_avg_ms_per_round"] == pytest.approx(
        sum(o.end - o.start for o in avg) / CHIPS / ROUNDS / 1e6, rel=1e-6)
    assert 0.001 < read["param_avg_ms_per_round"] < 0.1

    # nothing overlaps the collectives: their exposed time is their time
    coll = [sum(e.end - e.start for e in tr.leaves(tr.within(run.trace.ops[c], lo, hi))
                if tr.is_collective(e.name)) for c in range(CHIPS)]
    share = 100.0 * sum(coll) / CHIPS / (hi - lo)
    assert 0 < read["collective_exposed_share"] <= share * (1 + 1e-9)
    assert read["collective_exposed_share"] == pytest.approx(share, rel=0.05)

    probes = scopes.spans(prof, "repro.probe", lo, hi)
    assert read["probe_wall_ms_per_call"] == pytest.approx(
        sum(h.end - h.start for h in probes) / 2 / 1e6)
    assert 2 < read["probe_wall_ms_per_call"] < 20
    rounds = scopes.spans(prof, "repro.round", lo, hi)
    assert read["round_dispatch_ms_per_round"] == pytest.approx(
        sum(h.end - h.start for h in rounds) / ROUNDS / 1e6)
    assert 0.1 < read["round_dispatch_ms_per_round"] < 5


def test_mesh_readers_read_nothing_without_their_spans_or_scopes(tmp_path):
    """A one-chip program has no mesh collectives, an untraced run and a
    trace without the program's spans and scopes have nothing at all."""
    one = traced_run(tmp_path / "one", HERE / "scoped_fixtures", "news20-sstep.t2l", chips=1)
    assert load_metric("allreduce_gv_ms_per_bundle")(one) is None
    assert load_metric("param_avg_ms_per_round")(one) is None
    assert load_metric("collective_exposed_share")(one) is None
    assert load_metric("probe_wall_ms_per_call")(one) > 0
    assert load_metric("round_dispatch_ms_per_round")(one) > 0
    bare = traced_run(tmp_path / "bare", HERE / "fixtures", "news20-sstep.t2l", chips=1)
    assert {m: load_metric(m)(bare) for m in READERS} == dict.fromkeys(READERS)
    untraced = dataclasses.replace(traced_run(tmp_path / "mesh", MESH), trace=None)
    assert {m: load_metric(m)(untraced) for m in READERS} == dict.fromkeys(READERS)
