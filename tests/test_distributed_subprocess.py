"""Multi-device shard_map equivalence — runs in a subprocess so that
XLA_FLAGS=--xla_force_host_platform_device_count is set before jax
initializes, without polluting the main test process (which must see
exactly one device)."""

import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent


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


@pytest.mark.parametrize("partitioner", ["cyclic", "rows", "nnz"])
@pytest.mark.parametrize("mesh_shape", [(2, 4), (4, 2), (1, 8), (8, 1)])
def test_hybrid_distributed_matches_simulated(partitioner, mesh_shape):
    p_r, p_c = mesh_shape
    out = run_in_subprocess(
        f"""
        import numpy as np, jax, jax.numpy as jnp
        from repro import compat
        from repro.sparse.synthetic import make_skewed_csr
        from repro.core.teams import stack_row_teams
        from repro.core.hybrid import run_hybrid_sgd
        from repro.core import ParallelSGDSchedule
        from repro.core.distributed import build_2d_problem, run_hybrid_distributed

        rng = np.random.default_rng(0)
        A = make_skewed_csr(256, 100, 12, 0.8, seed=3)
        y = np.where(rng.random(256) < 0.5, 1.0, -1.0)
        s, b, tau, eta, rounds = 2, 4, 8, 0.05, 3
        p_r, p_c = {p_r}, {p_c}
        mesh = compat.make_mesh((p_r, p_c), ("rows", "cols"))
        tp = stack_row_teams(A, y, p_r, row_multiple=s * b)
        x_sim, _ = run_hybrid_sgd(tp, jnp.zeros(100), s, b, eta, tau, rounds)
        prob, cp = build_2d_problem(A, y, p_r, p_c, "{partitioner}", row_multiple=s * b)
        sched = ParallelSGDSchedule.hybrid(p_r, s, b, eta, tau, rounds, gram="blocked")
        x_dist, _ = run_hybrid_distributed(mesh, prob, cp, np.zeros(100, np.float32), sched)
        diff = float(np.abs(np.asarray(x_sim) - x_dist).max())
        assert diff < 1e-5, diff
        print("OK", diff)
        """
    )
    assert "OK" in out


def test_distributed_fedavg_corner():
    """p_c=1, s=1 mesh executes FedAvg; cross-check against run_fedavg."""
    out = run_in_subprocess(
        """
        import numpy as np, jax, jax.numpy as jnp
        from repro import compat
        from repro.sparse.synthetic import make_skewed_csr
        from repro.core.teams import stack_row_teams
        from repro.core.fedavg import run_fedavg
        from repro.core import ParallelSGDSchedule
        from repro.core.distributed import build_2d_problem, run_hybrid_distributed

        rng = np.random.default_rng(0)
        A = make_skewed_csr(256, 100, 12, 0.8, seed=3)
        y = np.where(rng.random(256) < 0.5, 1.0, -1.0)
        b, tau, eta, rounds = 4, 8, 0.05, 3
        mesh = compat.make_mesh((8, 1), ("rows", "cols"))
        tp = stack_row_teams(A, y, 8, row_multiple=b)
        x_f, _ = run_fedavg(tp, jnp.zeros(100), b, eta, tau, rounds)
        prob, cp = build_2d_problem(A, y, 8, 1, "rows", row_multiple=b)
        sched = ParallelSGDSchedule.fedavg(8, b, eta, tau, rounds, gram="blocked")
        x_d, _ = run_hybrid_distributed(mesh, prob, cp, np.zeros(100, np.float32), sched)
        diff = float(np.abs(np.asarray(x_f) - x_d).max())
        assert diff < 1e-5, diff
        print("OK", diff)
        """
    )
    assert "OK" in out


@pytest.mark.parametrize("mesh_shape", [(2, 4), (4, 2)])
def test_front_door_backend_parity(mesh_shape):
    """The repro.api acceptance check: run(spec) with backend="simulated"
    and backend="shard_map" agree — same weights, same loss trace — on
    the same spec (only the mesh backend field differs)."""
    p_r, p_c = mesh_shape
    out = run_in_subprocess(
        f"""
        import dataclasses
        import numpy as np
        from repro.api import ExperimentSpec, MeshSpec, run
        from repro.core import ParallelSGDSchedule

        sched = ParallelSGDSchedule.hybrid({p_r}, 2, 4, 0.05, 8, rounds=3, loss_every=1)
        spec = ExperimentSpec(
            dataset="rcv1-sm",
            schedule=sched,
            mesh=MeshSpec(p_r={p_r}, p_c={p_c}, backend="simulated"),
            name="parity",
        )
        r_sim = run(spec)
        r_dist = run(dataclasses.replace(
            spec, mesh=MeshSpec(p_r={p_r}, p_c={p_c}, backend="shard_map")))
        dx = float(np.abs(r_sim.x - r_dist.x).max())
        dl = float(np.abs(r_sim.losses - r_dist.losses).max())
        assert r_sim.losses.shape == (3,), r_sim.losses.shape
        assert dx < 1e-5, dx
        assert dl < 1e-5, dl
        print("OK", dx, dl)
        """
    )
    assert "OK" in out


def test_front_door_objective_parity_multidevice():
    """Cross-objective backend parity on a real 2×2 mesh: the spec's
    objective (+ L2, exercising the decay-aware bundle recurrence under
    column-sharded psum) must produce the same weights and trace on
    both executors."""
    out = run_in_subprocess(
        """
        import dataclasses
        import numpy as np
        from repro.api import ExperimentSpec, MeshSpec, run
        from repro.core import ParallelSGDSchedule

        for obj, l2 in (("squared_hinge", 1e-3), ("least_squares", 0.0)):
            sched = ParallelSGDSchedule.hybrid(2, 2, 4, 0.05, 8, rounds=3,
                                               loss_every=1)
            spec = ExperimentSpec(
                dataset="rcv1-sm",
                schedule=sched,
                mesh=MeshSpec(p_r=2, p_c=2, backend="simulated"),
                objective=obj,
                l2=l2,
                name=f"obj-parity-{obj}",
            )
            r_sim = run(spec)
            r_dist = run(dataclasses.replace(
                spec, mesh=MeshSpec(p_r=2, p_c=2, backend="shard_map")))
            dx = float(np.abs(r_sim.x - r_dist.x).max())
            dl = float(np.abs(r_sim.losses - r_dist.losses).max())
            assert dx < 1e-5, (obj, dx)
            assert dl < 1e-5, (obj, dl)
            print("OK", obj, dx, dl)
        """,
        devices=4,
    )
    assert out.count("OK") == 2


def test_session_shard_map_mesh_stream_and_resume(tmp_path):
    """The Session lifecycle on a real 2×4 device mesh: streamed rounds
    match run() bitwise, and a save → restore mid-run (off a loss
    boundary) reproduces the uninterrupted weights and trace."""
    out = run_in_subprocess(
        f"""
        import numpy as np
        from repro.api import ExperimentSpec, MeshSpec, Session, run
        from repro.core import ParallelSGDSchedule

        sched = ParallelSGDSchedule.hybrid(2, 2, 4, 0.05, 8, rounds=4, loss_every=2)
        spec = ExperimentSpec(
            dataset="rcv1-sm",
            schedule=sched,
            mesh=MeshSpec(p_r=2, p_c=4, backend="shard_map"),
            name="sess-mesh",
        )
        full = run(spec)

        sess = Session(spec)
        while not sess.done:
            sess.step_rounds(1)
        assert np.array_equal(sess.current_x(), full.x)
        assert np.array_equal(np.asarray(sess.losses, np.float32), full.losses)

        half = Session(spec)
        half.step_rounds(3)  # mid-chunk: not a loss boundary
        half.save(r"{tmp_path}/ck")
        rep = Session.restore(r"{tmp_path}/ck").run()
        assert np.array_equal(rep.x, full.x)
        assert np.array_equal(rep.losses, full.losses)
        assert rep.stop_reason == "rounds"
        print("OK")
        """
    )
    assert "OK" in out


@pytest.mark.parametrize("mesh_shape", [(2, 4), (4, 2)])
def test_comm_ledger_backend_parity(mesh_shape):
    """The comm-plane acceptance identity on real devices: the ledger a
    shard_map Session produces — captured from the round body the mesh
    actually executes — is identical to the simulated Session's ledger
    for the same spec, and both match the Table 2–3 closed form
    (costmodel.schedule_comm_volume) exactly."""
    p_r, p_c = mesh_shape
    out = run_in_subprocess(
        f"""
        import dataclasses
        import numpy as np
        from repro.api import ExperimentSpec, MeshSpec, Session, dataset_stats
        from repro.core import ParallelSGDSchedule
        from repro.costmodel import schedule_comm_volume

        sched = ParallelSGDSchedule.hybrid({p_r}, 2, 4, 0.05, 8, rounds=3, loss_every=1)
        spec = ExperimentSpec(
            dataset="rcv1-sm",
            schedule=sched,
            mesh=MeshSpec(p_r={p_r}, p_c={p_c}, backend="simulated"),
            name="ledger-parity",
        )
        r_sim = Session(spec).run()
        r_dist = Session(dataclasses.replace(
            spec, mesh=MeshSpec(p_r={p_r}, p_c={p_c}, backend="shard_map"))).run()
        assert r_sim.ledger.rates == r_dist.ledger.rates, (
            r_sim.ledger.rates, r_dist.ledger.rates)
        assert r_sim.ledger.rounds == r_dist.ledger.rounds == 3
        counted = r_dist.ledger.counted_words()
        assert counted == r_sim.ledger.counted_words()
        n = dataset_stats("rcv1-sm").n
        cv = schedule_comm_volume(n, {p_r}, {p_c}, 2, 4, 8, rounds=3)
        assert counted == cv.words_dict(), (counted, cv.words_dict())
        assert counted == r_dist.comm_words  # counted == modeled
        print("OK", counted["total_words"])
        """
    )
    assert "OK" in out


@pytest.mark.parametrize("delay", [1, 2])
def test_delayed_pipeline_backend_parity(delay):
    """The DaSGD delay-D pipeline on a real 2×4 mesh: both executors run
    the engine's one ``round_body`` (reduce at bundle t, consume at
    t+D, drain before the round's parameter average), so the stale
    iterates must agree across backends — and must differ from the
    synchronous D=0 trajectory (the knob is real)."""
    out = run_in_subprocess(
        f"""
        import dataclasses
        import numpy as np
        from repro.api import ExperimentSpec, MeshSpec, run
        from repro.core import ParallelSGDSchedule

        sched = ParallelSGDSchedule.hybrid(2, 2, 4, 0.05, 8, rounds=3,
                                           loss_every=1, delay={delay})
        spec = ExperimentSpec(
            dataset="rcv1-sm",
            schedule=sched,
            mesh=MeshSpec(p_r=2, p_c=4, backend="simulated"),
            name="delay-parity",
        )
        r_sim = run(spec)
        r_dist = run(dataclasses.replace(
            spec, mesh=MeshSpec(p_r=2, p_c=4, backend="shard_map")))
        dx = float(np.abs(r_sim.x - r_dist.x).max())
        dl = float(np.abs(r_sim.losses - r_dist.losses).max())
        assert dx < 1e-5, dx
        assert dl < 1e-5, dl
        r_sync = run(dataclasses.replace(
            spec, schedule=dataclasses.replace(sched, delay=0)))
        assert not np.array_equal(r_sync.x, r_dist.x)
        assert r_sim.ledger.delay == r_dist.ledger.delay == {delay}
        assert r_sim.ledger.counted_words() == r_dist.ledger.counted_words()
        print("OK", dx, dl)
        """
    )
    assert "OK" in out


def test_timed_mesh_run_measures_and_calibrates():
    """comm_timing on a real 2×2 mesh: per-round wall seconds land in
    the ledger, the iterates are unchanged, and calibrate() fits from
    the measured report."""
    out = run_in_subprocess(
        """
        import dataclasses
        import numpy as np
        from repro.api import ExperimentSpec, MeshSpec, RunReport, calibrate, plan, run
        from repro.core import ParallelSGDSchedule

        sched = ParallelSGDSchedule.hybrid(2, 2, 4, 0.05, 8, rounds=3, loss_every=1)
        spec = ExperimentSpec(
            dataset="rcv1-sm",
            schedule=sched,
            mesh=MeshSpec(p_r=2, p_c=2, backend="shard_map"),
            name="timed-mesh",
        )
        base = run(spec)
        assert base.ledger.round_seconds == []  # untimed: counted only
        timed = run(dataclasses.replace(spec, comm_timing=True))
        assert np.array_equal(timed.x, base.x)
        assert len(timed.ledger.round_seconds) == 3
        assert timed.ledger.seconds_per_round > 0
        # measured report JSON → calibration point → fitted plan
        rehydrated = RunReport.from_json(timed.to_json())
        pt = rehydrated.calibration_point()
        assert pt is not None and pt.bytes_per_round > 0
        cal = calibrate([pt])
        pl = plan(spec, calibration=cal)
        assert pl.calibrated and pl.cost.total > 0
        print("OK", cal.summary())
        """,
        devices=4,
    )
    assert "OK" in out


def test_x64_strict_sstep_identity():
    """With float64 the s-step identity holds to ~1e-12 (paper runs
    FP64 for Gram conditioning)."""
    out = run_in_subprocess(
        """
        import jax
        jax.config.update("jax_enable_x64", True)
        import numpy as np, jax.numpy as jnp
        from repro.sparse.synthetic import make_skewed_csr
        from repro.core.problem import make_problem
        from repro.core.sgd import run_sgd
        from repro.core.sstep import run_sstep_sgd

        rng = np.random.default_rng(0)
        A = make_skewed_csr(256, 128, 12, 0.8, seed=3)
        y = np.where(rng.random(256) < 0.5, 1.0, -1.0)
        prob = make_problem(A, y, row_multiple=64, dtype=jnp.float64)
        x0 = jnp.zeros(128, jnp.float64)
        x_sgd, _ = run_sgd(prob, x0, 8, 0.05, 64)
        x_ss, _ = run_sstep_sgd(prob, x0, 8, 8, 0.05, 64)
        diff = float(jnp.abs(x_sgd - x_ss).max())
        assert diff < 1e-12, diff
        print("OK", diff)
        """,
        devices=1,
    )
    assert "OK" in out


def test_stream_rounds_match_simulated_and_replay_bitwise():
    """Streaming parity + determinism on the real mesh: the same drift
    stream trained through shard_map ``step_stream`` matches the
    simulated oracle, and a second shard_map run is bitwise-identical
    (the streaming door — HybridDriver.advance_stream — is as
    deterministic as the resident path)."""
    out = run_in_subprocess(
        """
        import numpy as np
        from repro.api import ExperimentSpec, MeshSpec, Session, StreamSpec
        from repro.core.engine import ParallelSGDSchedule
        from repro.serve import make_stream_source

        sched = ParallelSGDSchedule.hybrid(
            p_r=2, s=2, b=4, eta=0.2, tau=8, rounds=6, loss_every=3
        )
        base = dict(dataset="rcv1-sm", schedule=sched,
                    stream=StreamSpec(source="drift", seed=3, drift_at=3))
        sim = ExperimentSpec(mesh=MeshSpec(p_r=2, p_c=1, backend="simulated"), **base)
        dist = ExperimentSpec(mesh=MeshSpec(p_r=2, p_c=2, backend="shard_map"), **base)

        a = Session(sim)
        while not a.done:
            a.step_stream(make_stream_source(sim))
        runs = []
        for _ in range(2):
            s = Session(dist)
            while not s.done:
                s.step_stream(make_stream_source(dist))
            runs.append((s.current_x(), list(s.losses)))

        assert np.array_equal(runs[0][0], runs[1][0]), "shard_map stream not deterministic"
        assert runs[0][1] == runs[1][1]
        diff = float(np.abs(a.current_x() - runs[0][0]).max())
        assert diff == 0.0, f"stream parity broke: max |diff|={diff}"
        print("OK", diff)
        """,
        devices=4,
    )
    assert "OK" in out


def test_stream_resume_mid_stream_shard_map_bitwise(tmp_path):
    """Kill-free resume check on the mesh: autosave at round 4, restore
    in the same process, finish — bitwise equal to uninterrupted."""
    out = run_in_subprocess(
        f"""
        import numpy as np
        from pathlib import Path
        from repro.api import (ExperimentSpec, FaultPolicy, MeshSpec, Session,
                               StreamSpec)
        from repro.core.engine import ParallelSGDSchedule
        from repro.serve import make_stream_source

        d = Path({str(tmp_path)!r})
        sched = ParallelSGDSchedule.hybrid(
            p_r=2, s=2, b=4, eta=0.2, tau=8, rounds=8, loss_every=4
        )
        spec = ExperimentSpec(
            dataset="rcv1-sm", schedule=sched,
            mesh=MeshSpec(p_r=2, p_c=2, backend="shard_map"),
            stream=StreamSpec(source="drift", seed=3),
            faults=FaultPolicy(autosave_every=4),
        )
        ref = Session(spec)
        while not ref.done:
            ref.step_stream(make_stream_source(spec))

        interrupted = Session(spec, autosave_dir=d)
        interrupted.step_stream(make_stream_source(spec), 5)
        resumed = Session.restore(interrupted.autosave_path, spec=spec)
        assert resumed.rounds_done == 4, resumed.rounds_done
        while not resumed.done:
            resumed.step_stream(make_stream_source(spec))
        assert np.array_equal(ref.current_x(), resumed.current_x())
        assert ref.losses == resumed.losses
        print("OK")
        """,
        devices=4,
    )
    assert "OK" in out
