"""The repro.api front door: spec round-tripping, plan parity with the
raw cost model, run() dispatch, autotune, and the shard_map step
against the simulated engine.

Multi-device shard_map runs live in test_distributed_subprocess.py;
here the shard_map backend is exercised on the 1×1 mesh the single CPU
device can host — the full dispatch path, no fake devices needed.
"""

import dataclasses
import json
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.api import ExperimentSpec, MeshSpec, StopPolicy, build_problem, plan, run
from repro.api.spec import dataset_stats
from repro.core import ParallelSGDSchedule, run_parallel_sgd
from repro.costmodel import MACHINES, HybridConfig, hybrid_epoch_cost

DATASET = "rcv1-sm"


def hybrid_spec(**kw) -> ExperimentSpec:
    sched = kw.pop("schedule", None) or ParallelSGDSchedule.hybrid(
        2, 2, 8, 0.05, 8, rounds=4, loss_every=2
    )
    mesh = kw.pop("mesh", None) or MeshSpec(p_r=2, p_c=2)
    return ExperimentSpec(dataset=DATASET, schedule=sched, mesh=mesh, **kw)


# ---------------- spec: validation + JSON round-trip ----------------


def test_spec_json_round_trip():
    spec = hybrid_spec(name="rt", autotune=True, row_multiple=32, seed=7)
    # through a real JSON string, not just dicts
    restored = ExperimentSpec.from_json(json.dumps(spec.to_dict()))
    assert restored == spec
    # and the canonicalized schedule survives (p_c copied from the mesh)
    assert restored.schedule.p_c == spec.mesh.p_c


def test_spec_canonicalizes_schedule_p_c():
    spec = hybrid_spec(mesh=MeshSpec(p_r=2, p_c=4))
    assert spec.schedule.p_c == 4  # schedule default p_c=1 → mesh wins


def test_spec_rejects_geometry_mismatch():
    sched = ParallelSGDSchedule.hybrid(2, 2, 8, 0.05, 8, rounds=1)
    with pytest.raises(ValueError):  # p_r is numerical — must agree
        ExperimentSpec(dataset=DATASET, schedule=sched, mesh=MeshSpec(p_r=4))
    with pytest.raises(ValueError):  # conflicting explicit p_c
        ExperimentSpec(
            dataset=DATASET,
            schedule=dataclasses.replace(sched, p_c=2),
            mesh=MeshSpec(p_r=2, p_c=4),
        )


def test_spec_rejects_unknown_names():
    sched = ParallelSGDSchedule.mb_sgd(8, 0.05, 4)
    with pytest.raises(KeyError):
        ExperimentSpec(dataset="no-such-data", schedule=sched)
    with pytest.raises(ValueError):
        ExperimentSpec(dataset=DATASET, schedule=sched, machine="no-such-machine")
    with pytest.raises(ValueError):
        MeshSpec(backend="no-such-backend")
    with pytest.raises(ValueError):
        MeshSpec(partitioner="no-such-partitioner")


def test_spec_rejects_degenerate_mesh_and_gram():
    with pytest.raises(ValueError, match="1×1"):
        MeshSpec(p_r=0)
    with pytest.raises(ValueError, match="1×1"):
        MeshSpec(p_c=-1)
    with pytest.raises(ValueError, match="gram"):
        ParallelSGDSchedule(gram="no-such-gram")


def test_stop_policy_validation():
    with pytest.raises(ValueError, match="max_seconds"):
        StopPolicy(max_seconds=-1.0)
    with pytest.raises(ValueError, match="max_rounds"):
        StopPolicy(max_rounds=0)
    # target_loss is only observable on loss-sampling boundaries
    sched = ParallelSGDSchedule.hybrid(1, 2, 8, 0.05, 8, rounds=4)  # loss_every=0
    with pytest.raises(ValueError, match="loss_every"):
        ExperimentSpec(dataset=DATASET, schedule=sched,
                       stop=StopPolicy(target_loss=0.5))
    assert StopPolicy().trivial and not StopPolicy(max_rounds=1).trivial


def test_spec_json_round_trip_with_partitioner_and_stop():
    """Satellite: non-default partitioner + every StopPolicy knob must
    survive the JSON round trip (and the content hash must track it)."""
    spec = hybrid_spec(
        mesh=MeshSpec(p_r=2, p_c=4, backend="shard_map", partitioner="nnz"),
        stop=StopPolicy(target_loss=0.6, max_seconds=12.5, max_rounds=3),
        name="rt-stop",
    )
    restored = ExperimentSpec.from_json(spec.to_json())
    assert restored == spec
    assert restored.mesh.partitioner == "nnz"
    assert restored.stop == StopPolicy(target_loss=0.6, max_seconds=12.5, max_rounds=3)
    assert restored.content_hash() == spec.content_hash()
    # old spec JSON (no "stop" key) still loads, with the trivial policy
    d = spec.to_dict()
    del d["stop"]
    assert ExperimentSpec.from_dict(d).stop.trivial
    # the hash keys on content: any field change moves it
    assert (
        dataclasses.replace(spec, stop=StopPolicy()).content_hash()
        != spec.content_hash()
    )


def test_spec_and_report_predate_comm_ledger():
    """PR 5 back-compat, alongside the hash tests above: spec JSON and
    report JSON written before the comm plane existed (no comm_timing /
    comm_ledger keys) load with defaults, and a default spec's dict —
    hence its content hash, checkpoints, and sweep resume dirs — is
    byte-identical to the pre-ledger layout."""
    spec = hybrid_spec(name="pre-ledger")
    d = spec.to_dict()
    assert "comm_timing" not in d and "comm_ledger" not in d
    assert ExperimentSpec.from_dict(d) == spec
    assert ExperimentSpec.from_dict(d).content_hash() == spec.content_hash()
    # a timed spec round-trips and moves the hash (resume dirs never
    # mix timed with untimed runs)
    timed = dataclasses.replace(spec, comm_timing=True)
    assert ExperimentSpec.from_json(timed.to_json()) == timed
    assert timed.content_hash() != spec.content_hash()
    # pre-ledger report JSON: rehydrates with ledger=None
    from repro.api import RunReport

    rep = run(spec)
    old = rep.to_dict()
    del old["comm_ledger"]
    assert RunReport.from_dict(old).ledger is None
    assert RunReport.from_dict(rep.to_dict()).ledger == rep.ledger


# ---------------- plan: cost-model parity + autotune ----------------


def test_plan_matches_direct_cost_model_call():
    spec = hybrid_spec(mesh=MeshSpec(p_r=2, p_c=4))
    pl = plan(spec)
    st = dataset_stats(DATASET)
    cfg = HybridConfig(p_r=2, p_c=4, s=spec.schedule.s, b=spec.schedule.b,
                       tau=spec.schedule.tau)
    direct = hybrid_epoch_cost(st.m, st.n, st.zbar, cfg, MACHINES[spec.machine])
    assert pl.cost == direct
    assert pl.regime == direct.dominant
    assert not pl.autotuned and pl.s_star is None


def test_plan_autotune_rewrites_schedule_validly():
    spec = hybrid_spec(autotune=True)
    pl = plan(spec)
    sched = pl.spec.schedule
    assert pl.autotuned and pl.s_star is not None and pl.b_star is not None
    assert sched.s >= 1 and sched.b >= 1
    assert sched.tau % sched.s == 0  # still a runnable schedule
    # the rewritten spec must itself survive a JSON round trip
    assert ExperimentSpec.from_json(pl.spec.to_json()) == pl.spec


# ---------------- run: simulated backend ----------------


def test_run_simulated_matches_direct_engine_call():
    spec = hybrid_spec()
    rep = run(spec)
    bundle = build_problem(spec)
    x_direct, losses_direct = run_parallel_sgd(
        bundle.team, jnp.zeros(bundle.dataset.A.n), spec.schedule
    )
    np.testing.assert_array_equal(rep.x, np.asarray(x_direct))
    np.testing.assert_array_equal(rep.losses, np.asarray(losses_direct))
    assert rep.backend == "simulated"
    assert len(rep.losses) == spec.schedule.rounds // spec.schedule.loss_every
    assert rep.wall_time_s > 0
    assert rep.comm_words["total_words"] > 0
    json.dumps(rep.to_dict())  # report is JSON-serializable


def test_run_shard_map_1x1_through_front_door():
    """The full shard_map dispatch path on the single real device."""
    sched = ParallelSGDSchedule.hybrid(1, 2, 8, 0.05, 8, rounds=2, loss_every=1)
    sim = run(hybrid_spec(schedule=sched, mesh=MeshSpec(p_r=1, p_c=1)))
    dist = run(hybrid_spec(schedule=sched,
                           mesh=MeshSpec(p_r=1, p_c=1, backend="shard_map")))
    assert dist.backend == "shard_map"
    np.testing.assert_allclose(dist.x, sim.x, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(dist.losses, sim.losses, rtol=1e-5, atol=1e-6)


def test_run_shard_map_rejects_oversized_mesh():
    spec = hybrid_spec(mesh=MeshSpec(p_r=2, p_c=2, backend="shard_map"))
    with pytest.raises(RuntimeError, match="devices"):
        run(spec)  # main test process sees exactly one device


# ---------------- satellite: sstep loss_every validation ----------------


def test_sstep_loss_every_must_divide():
    # silently changing the cadence (the old max(loss_every // s, 1))
    # is now a hard error …
    with pytest.raises(ValueError, match="loss_every"):
        ParallelSGDSchedule.sstep(4, 8, 0.05, 64, loss_every=6)
    with pytest.raises(ValueError, match="loss_every"):
        ParallelSGDSchedule.sstep(8, 8, 0.05, 64, loss_every=4)
    # … while exact multiples keep the engine-round cadence
    sched = ParallelSGDSchedule.sstep(4, 8, 0.05, 64, loss_every=16)
    assert sched.loss_every == 4  # 16 iterations = 4 rounds of s=4
    assert ParallelSGDSchedule.sstep(4, 8, 0.05, 64).loss_every == 0


# ---------------- the shard_map step on a 1×1 mesh ----------------


@pytest.fixture()
def tiny_2d():
    from repro.core.distributed import build_2d_problem
    from repro.core.teams import stack_row_teams
    from repro.sparse.synthetic import make_skewed_csr
    from repro import compat

    rng = np.random.default_rng(0)
    a = make_skewed_csr(64, 50, 8, 0.8, seed=3)
    y = np.where(rng.random(64) < 0.5, 1.0, -1.0)
    prob, cp = build_2d_problem(a, y, 1, 1, "cyclic", row_multiple=8)
    mesh = compat.make_mesh((1, 1), ("rows", "cols"))
    return mesh, prob, cp, stack_row_teams(a, y, 1, row_multiple=8)


@pytest.mark.parametrize("delay", [0, 1])
@pytest.mark.parametrize("s", [1, 2])
def test_make_hybrid_step_matches_engine(tiny_2d, s, delay):
    """The shard_map step, round by round on the 1×1 mesh, gives the
    simulated engine's iterates: both run the engine's round body, here
    once with the mesh collectives and once with the counting ones."""
    from repro.core.distributed import gather_x, make_hybrid_step, scatter_x

    mesh, prob, cp, tp = tiny_2d
    sched = ParallelSGDSchedule.hybrid(1, s, 4, 0.05, 4, rounds=3, gram="blocked",
                                       delay=delay)
    x_sim, _ = run_parallel_sgd(tp, jnp.zeros(50), sched)
    step = make_hybrid_step(mesh, prob, sched)
    x_pad = jnp.asarray(scatter_x(np.zeros(50, np.float32), cp, prob.n_loc))
    for r in range(sched.rounds):
        x_pad = step(prob.indices, prob.values, x_pad, jnp.int32(r))
    x_mesh = gather_x(np.asarray(x_pad), cp, prob.n_loc, prob.n)
    assert np.abs(x_mesh).max() > 0  # the rounds moved the weights
    np.testing.assert_allclose(x_mesh, np.asarray(x_sim), rtol=0, atol=1e-5)


def test_interpret_is_not_a_schedule_or_spec_field():
    """Pallas interpret mode follows the platform; no schedule field,
    spec key or committed spec file can select it."""
    assert "interpret" not in {f.name for f in dataclasses.fields(ParallelSGDSchedule)}
    with pytest.raises(TypeError):
        ParallelSGDSchedule(interpret=True)
    d = hybrid_spec().to_dict()
    assert "interpret" not in d["schedule"]
    d["schedule"]["interpret"] = False
    with pytest.raises(TypeError):
        ExperimentSpec.from_dict(d)
    specs = sorted((Path(__file__).parent.parent / "examples" / "specs").glob("*.json"))
    assert specs
    for path in specs:
        assert "interpret" not in path.read_text(), path
        raw = json.loads(path.read_text())
        for entry in raw if isinstance(raw, list) else [raw]:
            ExperimentSpec.from_dict(entry)


def test_compile_cache_placement(monkeypatch, tmp_path):
    """Unset, the cache goes to the fixed ``<checkout>/.jax_cache``; with
    JAX_COMPILATION_CACHE_DIR set, nothing in the code sets another and
    the compiled program lands there."""
    from repro.launch import cache

    checkout = Path(__file__).resolve().parent.parent
    was = jax.config.jax_compilation_cache_dir
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    try:
        assert cache.place_compile_cache() == checkout / ".jax_cache"
        assert jax.config.jax_compilation_cache_dir == str(checkout / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", was)
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "env"))
        assert cache.place_compile_cache() == tmp_path / "env"
        assert jax.config.jax_compilation_cache_dir == was  # left to JAX
    finally:
        jax.config.update("jax_compilation_cache_dir", was)

    env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=str(tmp_path / "env"),
               PYTHONPATH=str(checkout / "src"), JAX_PLATFORMS="cpu")
    code = (
        "import jax; from repro.launch.cache import place_compile_cache; "
        "place_compile_cache(); "
        "jax.config.update('jax_persistent_cache_min_compile_time_secs', 0); "
        "print(jax.jit(lambda a: a * 3 + 1)(2.0))"
    )
    out = subprocess.run([sys.executable, "-c", code], env=env, capture_output=True,
                         text=True, timeout=300)
    assert out.returncode == 0, out.stderr
    assert any((tmp_path / "env").iterdir())
