"""Observability plane: the span-tracing seam, the metrics registry,
trace export, and the provably-free guarantee.

The load-bearing claims:

* uninstalled, the seam records nothing — a span is only its
  profiler annotation;
* traced runs are bitwise-identical to untraced runs on BOTH backends
  (spans are host-side wall intervals; compiled numerics untouched);
* ``comm_timing`` runs split the round wall into the §6.5 phases
  (``CommLedger.phase_seconds``) and derive ``exposed_comm_s``;
* both export formats (Chrome trace-event JSON, JSONL) round-trip and
  carry valid Perfetto-loadable fields.
"""

import importlib.util
import json
import os
import subprocess
import sys
import textwrap
import threading
from pathlib import Path
from unittest import mock

import jax
import numpy as np
import pytest

from repro.api import ExperimentSpec, MeshSpec, Session, StreamSpec, run, sweep
from repro.core.comm import CommLedger
from repro.core.engine import ParallelSGDSchedule
from repro.obs import export as obs_export
from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace
from repro.serve import make_stream_source
from repro.serve.controller import StageMetrics

REPO = Path(__file__).resolve().parent.parent


def spec(rounds=4, loss_every=2, p_c=2, tau=8, **kw):
    return ExperimentSpec(
        dataset="rcv1-sm",
        schedule=ParallelSGDSchedule.hybrid(
            p_r=2, s=2, b=4, eta=0.2, tau=tau, rounds=rounds, loss_every=loss_every
        ),
        mesh=MeshSpec(p_r=2, p_c=p_c, backend="simulated"),
        **kw,
    )


def run_in_subprocess(body: str, devices: int = 8) -> str:
    code = textwrap.dedent(body)
    env = {
        "XLA_FLAGS": f"--xla_force_host_platform_device_count={devices}",
        "PYTHONPATH": str(REPO / "src"),
        "PATH": "/usr/bin:/bin:/usr/local/bin",
        "JAX_PLATFORMS": "cpu",
    }
    proc = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True, env=env, timeout=600
    )
    assert proc.returncode == 0, f"stdout:\n{proc.stdout}\nstderr:\n{proc.stderr}"
    return proc.stdout


# ---------------- the tracing seam ----------------


class TestSeam:
    def test_uninstalled_is_one_shared_noop(self):
        # uninstalled, a span is only its profiler annotation: nothing
        # is recorded and no recorder appears
        assert obs_trace.active() is None
        c1 = obs_trace.span("round")
        c2 = obs_trace.span("ingest", name="whatever", rows=3)
        assert isinstance(c1, jax.profiler.TraceAnnotation)
        assert isinstance(c2, jax.profiler.TraceAnnotation)
        with c1, c2:
            pass
        assert obs_trace.active() is None

    def test_install_records_nesting_and_restores(self):
        with obs_trace.install() as rec:
            assert obs_trace.active() is rec
            with obs_trace.span("round", name="r0", idx=0):
                with obs_trace.span("ckpt_save", name="inner"):
                    pass
        assert obs_trace.active() is None
        by = {s.category: s for s in rec.spans}
        assert set(by) == {"round", "ckpt_save"}
        assert by["ckpt_save"].depth == 1 and by["round"].depth == 0
        assert rec.spans[0].category == "ckpt_save"  # inner exits first
        assert by["round"].dur >= by["ckpt_save"].dur >= 0.0
        assert by["round"].args == {"idx": 0}

    def test_nested_installs_restore_outer(self):
        with obs_trace.install() as outer:
            with obs_trace.install() as inner:
                with obs_trace.span("round"):
                    pass
                assert obs_trace.active() is inner
            assert obs_trace.active() is outer
        assert len(inner) == 1 and len(outer) == 0

    def test_unknown_category_raises(self):
        rec = obs_trace.TraceRecorder()
        with pytest.raises(ValueError, match="category"):
            with rec.span("bogus"):
                pass

    def test_worker_threads_see_installed_recorder(self):
        # ContextVars don't propagate into threading.Thread — the serve
        # plane's producer/batcher threads rely on the module fallback.
        seen = []

        def worker():
            with obs_trace.span("ingest", name="from-thread"):
                seen.append(obs_trace.active())

        with obs_trace.install() as rec:
            t = threading.Thread(target=worker)
            t.start()
            t.join()
        assert seen == [rec]
        assert rec.spans[0].category == "ingest"
        assert rec.spans[0].tid != threading.get_ident()


# ---------------- the metrics registry ----------------


class TestMetrics:
    def test_counter_gauge_histogram(self):
        reg = obs_metrics.MetricsRegistry()
        c = reg.counter("points_total")
        c.inc()
        c.inc(2)
        assert c.value == 3
        with pytest.raises(ValueError, match="decrease"):
            c.inc(-1)
        g = reg.gauge("depth")
        g.set(7)
        g.set(3)
        assert g.value == 3.0
        h = reg.histogram("rows")
        for v in (4, 1, 7):
            h.observe(v)
        assert (h.count, h.sum, h.min, h.max, h.mean) == (3, 12.0, 1.0, 7.0, 4.0)

    def test_labels_key_identity(self):
        reg = obs_metrics.MetricsRegistry()
        a = reg.gauge("wall", module="serve")
        b = reg.gauge("wall", module="comm")
        assert a is not b
        assert reg.gauge("wall", module="serve") is a
        snap = reg.snapshot()
        assert set(snap) == {"wall{module=comm}", "wall{module=serve}"}

    def test_kind_conflict_raises(self):
        reg = obs_metrics.MetricsRegistry()
        reg.counter("x")
        with pytest.raises(ValueError, match="already registered"):
            reg.gauge("x")

    def test_snapshot_delta_reset(self):
        reg = obs_metrics.MetricsRegistry()
        reg.counter("c").inc(5)
        reg.gauge("g").set(1)
        reg.histogram("h").observe(2.0)
        before = reg.snapshot()
        assert reg.delta(before) == {}
        reg.counter("c").inc(3)
        reg.gauge("g").set(9)
        reg.histogram("h").observe(4.0)
        d = reg.delta(before)
        assert d["c"] == {"kind": "counter", "value": 3}
        assert d["g"]["value"] == 9.0
        assert d["h"]["count"] == 1 and d["h"]["sum"] == 4.0
        reg.reset()
        assert reg.snapshot() == {}

    def test_process_default_registry_is_stable(self):
        assert obs_metrics.registry() is obs_metrics.registry()


# ---------------- export ----------------


def make_recorder() -> obs_trace.TraceRecorder:
    # a stepped clock: the recorder's epoch, then each span edge
    clock = iter([0.0, 0.0, 0.1, 0.2, 0.3, 0.3, 0.8])
    with mock.patch.object(obs_trace.time, "perf_counter", lambda: next(clock)):
        rec = obs_trace.TraceRecorder()
        with rec.span("round", name="rounds[0+2]", start_round=0):
            with rec.span("ckpt_save", name="swap-2"):
                pass
        with rec.span("probe", calls_per_round=2):
            pass
    return rec


class TestExport:
    def test_chrome_trace_fields(self):
        rec = make_recorder()
        blob = obs_export.chrome_trace_dict(
            rec, metrics={"m": {"kind": "counter", "value": 1}}
        )
        assert blob["schemaVersion"] == obs_export.TRACE_SCHEMA_VERSION
        json.dumps(blob)  # fully JSON-serializable
        xs = [e for e in blob["traceEvents"] if e["ph"] == "X"]
        ms = [e for e in blob["traceEvents"] if e["ph"] == "M"]
        assert len(xs) == 3
        assert {e["name"] for e in ms} == {"process_name", "thread_name"}
        pid = ms[0]["pid"]
        for e in xs:
            assert e["cat"] in obs_trace.SPAN_CATEGORIES
            assert e["pid"] == pid and isinstance(e["tid"], int)
            assert e["dur"] >= 0.0 and e["ts"] >= 0.0
        probe = next(e for e in xs if e["cat"] == "probe")
        assert probe["dur"] == pytest.approx(0.5e6)  # microseconds
        assert probe["args"]["calls_per_round"] == 2
        assert blob["otherData"]["metrics"]["m"]["value"] == 1
        assert blob["otherData"]["categories"] == list(obs_trace.SPAN_CATEGORIES)

    def test_both_formats_round_trip(self, tmp_path):
        rec = make_recorder()
        cj = obs_export.write_chrome_trace(rec, tmp_path / "t.json")
        jl = obs_export.write_jsonl(rec, tmp_path / "t.jsonl")
        a, b = obs_export.load_trace(cj), obs_export.load_trace(jl)
        assert (
            a["schemaVersion"] == b["schemaVersion"] == obs_export.TRACE_SCHEMA_VERSION
        )
        assert len(a["spans"]) == len(b["spans"]) == len(rec.spans)
        for sa, sb, s in zip(a["spans"], b["spans"], rec.spans):
            assert sa["cat"] == sb["cat"] == s.category
            assert sa["name"] == sb["name"] == s.name
            assert sa["dur"] == pytest.approx(s.dur, abs=1e-9)
            assert sb["dur"] == pytest.approx(s.dur, abs=1e-12)

    def test_category_table_and_summary_line(self):
        rec = make_recorder()
        rows = obs_export.category_table(rec.spans)
        assert sum(r["share"] for r in rows) == pytest.approx(1.0)
        assert rows[0]["category"] == "probe"  # 0.5 s dominates
        assert rows[0]["count"] == 1
        line = obs_export.summary_line(rec)
        assert line.startswith("[trace] 3 spans over ")
        assert "probe" in line and "%" in line

    def test_summarize_text(self, tmp_path):
        rec = make_recorder()
        path = obs_export.write_chrome_trace(rec, tmp_path / "t.json")
        text = obs_export.summarize_text(path)
        assert "schema v1" in text and "3 spans" in text
        assert "probe" in text and "round" in text


# ---------------- session integration (simulated backend) ----------------


class TestSessionTracing:
    def test_traced_equals_untraced_bitwise(self):
        s = spec(rounds=4)
        a = Session(s)
        while not a.done:
            a.step_rounds()
        with obs_trace.install() as rec:
            b = Session(s)
            while not b.done:
                b.step_rounds()
        assert np.array_equal(a.current_x(), b.current_x())
        assert np.array_equal(np.asarray(a.losses), np.asarray(b.losses))
        cats = rec.by_category()
        assert "compile" in cats and "round" in cats
        assert cats["compile"][0].args["start_round"] == 0

    def test_comm_timing_populates_phases_and_exposed(self):
        sess = Session(spec(rounds=4, comm_timing=True))
        while not sess.done:
            sess.step_rounds()
        led = sess.ledger
        assert set(led.phase_seconds) == {"bundle_compute", "allreduce_gv", "param_avg"}
        assert all(v >= 0.0 for v in led.phase_seconds.values())
        per_round = sum(
            v for k, v in led.phase_seconds.items() if k != "bundle_compute"
        )
        assert led.exposed_comm_s == pytest.approx(per_round * led.rounds)
        d = led.to_dict()
        assert d["exposed_comm_s"] == pytest.approx(led.exposed_comm_s)
        back = CommLedger.from_dict(d)
        assert back.phase_seconds == pytest.approx(led.phase_seconds)
        assert back.exposed_comm_s == pytest.approx(led.exposed_comm_s)

    def test_untimed_run_has_no_phase_seconds(self):
        sess = Session(spec(rounds=2))
        while not sess.done:
            sess.step_rounds()
        assert sess.ledger.phase_seconds == {}
        assert sess.ledger.exposed_comm_s is None
        assert "phase_seconds" not in sess.ledger.to_dict()

    def test_probe_spans_recorded_on_traced_timed_run(self):
        # the phase probes fill the ledger, not the trace: the trace
        # holds the Session's own boundaries, one probe per sample
        with obs_trace.install() as rec:
            sess = Session(spec(rounds=4, comm_timing=True))
            while not sess.done:
                sess.step_rounds()
        cats = rec.by_category()
        assert set(cats) == {"step", "compile", "round", "probe", "copy_x"}, sorted(cats)
        assert len(cats["probe"]) == len(sess.losses) == 2
        assert set(sess.ledger.phase_seconds) == {"bundle_compute", "allreduce_gv", "param_avg"}

    def test_report_summary_mentions_exposed(self):
        rep = run(spec(rounds=2, comm_timing=True))
        assert "exposed" in rep.summary()
        assert "exposed" not in run(spec(rounds=2)).summary()

    def test_checkpoint_spans(self, tmp_path):
        sess = Session(spec(rounds=4))
        sess.step_rounds(2)
        with obs_trace.install() as rec:
            sess.save(tmp_path / "ck")
            Session.restore(tmp_path / "ck")
        cats = rec.by_category()
        assert "ckpt_save" in cats and "ckpt_verify" in cats
        assert cats["ckpt_save"][0].args["rounds_done"] == 2

    def test_stream_ingest_spans(self):
        sp = spec(rounds=3, loss_every=0, p_c=1,
                  stream=StreamSpec(source="drift", seed=3))
        with obs_trace.install() as rec:
            sess = Session(sp)
            src = make_stream_source(sp)
            while not sess.done:
                sess.step_stream(src)
        assert len(rec.by_category().get("ingest", [])) == 3

    def test_sweep_counters(self):
        reg = obs_metrics.registry()
        before = reg.snapshot()
        sweep([spec(rounds=2, name="obs-a"), spec(rounds=2, name="obs-b")])
        d = reg.delta(before)
        assert d["sweep.points_total"]["value"] == 2


# ---------------- StageMetrics on the registry ----------------


class TestStageMetrics:
    FIELDS = {
        "rounds_done", "rounds_per_sec", "last_loss", "ingest_lag",
        "queue_depth", "predictions_per_sec", "predictions_served",
        "staleness_rounds", "model_version", "swaps", "failed_swaps",
    }

    def make(self, **kw):
        base = dict(
            rounds_done=4, rounds_per_sec=2.0, last_loss=0.5, ingest_lag=1,
            queue_depth=2, predictions_per_sec=None, predictions_served=None,
            staleness_rounds=0, model_version=3, swaps=2, failed_swaps=0,
        )
        base.update(kw)
        return StageMetrics(**base)

    def test_to_dict_keys_unchanged(self):
        # bench_serve and the serve CLI read these keys — the registry
        # re-base must not move them.
        assert set(self.make().to_dict()) == self.FIELDS

    def test_publish_mirrors_fields_into_gauges(self):
        reg = obs_metrics.MetricsRegistry()
        self.make().publish(reg)
        snap = reg.snapshot()
        assert snap["serve.stage.rounds_done"]["value"] == 4
        assert snap["serve.stage.model_version"]["value"] == 3
        # None fields are skipped, not published as 0
        assert "serve.stage.predictions_per_sec" not in snap
        self.make(predictions_per_sec=9.0).publish(reg)
        assert reg.snapshot()["serve.stage.predictions_per_sec"]["value"] == 9.0


# ---------------- shard_map backend (real 8-device mesh) ----------------


def test_shard_map_traced_bitwise_probes_and_export():
    """The whole plane on the real mesh backend, in one subprocess: a
    traced+timed run is bitwise-identical to an untraced one, the phase
    probes populate the ledger, and the trace exports round-trip."""
    out = run_in_subprocess(
        """
        import tempfile
        import numpy as np
        from pathlib import Path
        from repro.api import ExperimentSpec, MeshSpec, Session
        from repro.core.engine import ParallelSGDSchedule
        from repro.obs import export as obs_export, trace as obs_trace

        def make():
            return ExperimentSpec(
                dataset="rcv1-sm",
                schedule=ParallelSGDSchedule.hybrid(
                    p_r=2, s=2, b=4, eta=0.2, tau=4, rounds=4, loss_every=2),
                mesh=MeshSpec(p_r=2, p_c=4, backend="shard_map"),
                comm_timing=True,
            )

        a = Session(make())
        while not a.done:
            a.step_rounds()
        with obs_trace.install() as rec:
            b = Session(make())
            while not b.done:
                b.step_rounds()
        assert np.array_equal(a.current_x(), b.current_x()), "tracing changed numerics"
        assert np.array_equal(np.asarray(a.losses), np.asarray(b.losses))
        cats = set(rec.by_category())
        want = {"compile", "round", "probe", "copy_x", "step"}
        assert want <= cats, cats
        assert b.ledger.exposed_comm_s is not None and b.ledger.exposed_comm_s >= 0.0

        with tempfile.TemporaryDirectory() as td:
            p = obs_export.write_chrome_trace(rec, Path(td) / "t.json")
            jl = obs_export.write_jsonl(rec, Path(td) / "t.jsonl")
            for blob in (obs_export.load_trace(p), obs_export.load_trace(jl)):
                assert blob["schemaVersion"] == 1
                assert len(blob["spans"]) == len(rec.spans)
        print("OBS_MESH_OK", len(rec.spans))
        """
    )
    assert "OBS_MESH_OK" in out


# ---------------- the benchmark regression gate ----------------


def _load_check_regression():
    path = REPO / "benchmarks" / "check_regression.py"
    mod_spec = importlib.util.spec_from_file_location("check_regression", path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod


class TestRegressionGate:
    def test_compare_rules(self):
        cr = _load_check_regression()
        base = {"pps": 100.0, "name": "serve", "maybe": None, "ok": True,
                "nest": {"v": 2.0}}
        assert cr.compare(dict(base, pps=500.0), base, 10.0) == []
        assert cr.compare(dict(base, pps=11.0), base, 10.0) == []
        assert len(cr.compare(dict(base, pps=1.0), base, 10.0)) == 1
        missing = {k: v for k, v in base.items() if k != "nest"}
        assert any("missing" in p for p in cr.compare(missing, base, 10.0))
        # null/bool baseline leaves are never gated; strings must match
        assert cr.compare(dict(base, maybe=123, ok=False), base, 10.0) == []
        assert any("name" in p for p in cr.compare(dict(base, name="x"), base, 10.0))
        assert any("vanished" in p for p in cr.compare(dict(base, pps=0.0), base, 10.0))
        assert any("sign" in p for p in cr.compare(dict(base, pps=-100.0), base, 10.0))
        deep = cr.compare({**base, "nest": {"v": 2000.0}}, base, 10.0)
        assert len(deep) == 1 and deep[0].startswith("nest.v")

    def test_cli_pass_and_fail(self, tmp_path):
        cr = _load_check_regression()
        base, fresh = tmp_path / "base.json", tmp_path / "fresh.json"
        base.write_text(json.dumps({"v": 10.0}))
        fresh.write_text(json.dumps({"v": 20.0}))
        assert cr.main([str(fresh), str(base)]) == 0
        fresh.write_text(json.dumps({"v": 2000.0}))
        assert cr.main([str(fresh), str(base)]) == 1
        assert cr.main(["/nonexistent.json", str(base)]) == 1

    def test_committed_serve_baseline_self_compares(self):
        cr = _load_check_regression()
        base = json.loads(
            (REPO / "benchmarks" / "baselines" / "serve.json").read_text()
        )
        assert cr.compare(base, base, 10.0) == []
        # the run-varying crossover field must stay ungated (null)
        assert base["time_to_adapt_rounds"] is None


def test_bench_driver_rejects_unknown_module():
    env = dict(os.environ, PYTHONPATH=f"{REPO}/src:{REPO}")
    proc = subprocess.run(
        [sys.executable, "-m", "benchmarks.run", "--only", "nope"],
        capture_output=True, text=True, cwd=REPO, env=env, timeout=120,
    )
    assert proc.returncode != 0
    assert "unknown module" in proc.stderr
