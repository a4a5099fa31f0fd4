"""``correct`` at a size a test run holds: a sound run passes, and the
control and each fault the cells can have fail.

A tiny root holds the benchmark's files with two small cells on the
``rcv1-sm`` statistics (2,048 × 4,736): ``tiny-sstep.t2l`` (one team,
simulated) and ``tiny-hybrid.t2l`` (a 2×2 shard_map mesh, on four
virtual CPU devices in a child process). The run skips the look for a
chip and drives the rest: set-up with its warm-up call, the window
whose first steps are checked, the reference and the verdict. Limits here sit between what a sound run
reads on the CPU (``weights_gap`` ~1e-7) and what the control reads
(~3e-6).
"""

import json
import os
import shutil
import subprocess
import sys
import time
from pathlib import Path

import jax
import numpy as np
import pytest

from bench import harness
from bench.data import make_data
from bench.reference import Reference

ROOT = Path(__file__).resolve().parents[2]
SEED = 2**31 + 21
LIMITS = {
    "loss_gap": {"limit": 1e-4},
    "grad_gap": {"limit": 1e-2},
    "change_gap": {"limit": 1e-2},
    "weights_gap": {"limit": 1e-6},
}


def make_root(path: Path) -> Path:
    shutil.copytree(ROOT / "bench", path / "bench")
    b = json.loads((ROOT / "BENCHMARK.json").read_text())
    b["configs"], b["workloads"] = [], []
    for name, (p_r, p_c, backend) in {"tiny-sstep": (1, 1, "simulated"),
                                      "tiny-hybrid": (2, 2, "shard_map")}.items():
        cfg = json.loads((ROOT / "bench" / "configs" / "rcv1-sstep.json").read_text())
        cfg["name"] = name
        cfg["target_loss"] = {str(s): 0.67 for s in cfg["data_seeds"]}
        cfg["data"] = {"m": 2048, "n": 4736, "zbar": 74, "skew_alpha": 0.6}
        cfg["spec"]["dataset"] = "rcv1-sm"
        cfg["spec"]["schedule"].update(p_r=p_r, p_c=p_c)
        cfg["spec"]["mesh"].update(p_r=p_r, p_c=p_c, backend=backend)
        (path / "bench" / "configs" / f"{name}.json").write_text(json.dumps(cfg))
        (path / "bench" / "limits" / f"{name}.t2l.json").write_text(json.dumps(LIMITS))
        b["configs"].append({"name": name, "source": "x", "file": f"bench/configs/{name}.json",
                             "reduced": [], "why": "x"})
        b["workloads"].append({"name": f"{name}.t2l", "config": name, "traffic": "t2l",
                               "chips": 4 if p_c > 1 else 1, "why": "x"})
    for m in b["per_layer"]:
        m.pop("workloads", None)
    (path / "BENCHMARK.json").write_text(json.dumps(b))
    return path


def run(root: Path, cell: str) -> dict:
    c = harness.load_cell(cell, root)
    result, lines = harness.run_cell(c, seed=SEED, seconds=0.5, trace=False,
                                     t0=time.perf_counter(), devices=jax.devices()[:c.chips],
                                     log=lambda s: None)
    assert lines and all(line.startswith("check ") for line in lines)
    assert list(result)[-1] == "checks"
    return result


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return make_root(tmp_path_factory.mktemp("tiny"))


@pytest.fixture
def fresh_programs():
    """Drop compiled programs around a run whose program is patched."""
    jax.clear_caches()
    yield
    jax.clear_caches()


def test_sound_run_is_correct(root):
    r = run(root, "tiny-sstep.t2l")
    assert r["correct"] is True and r["attempted"] == 1
    assert set(r["metrics"]) == {"time_to_target_s", "rows_per_s", "setup_s"}
    assert r["window"]["programs_built"] == 0
    assert r["checks"]["weights_gap"]["value"] < 1e-6


def test_control_is_not_correct(root, monkeypatch):
    """The reference at the precision below the configuration's (three
    bfloat16 passes) in the program's place."""
    measure = harness.measure

    def control_window(sess, k, *args, **kw):
        win = measure(sess, k, *args, **kw)
        cfg = json.loads((root / "bench" / "configs" / "tiny-sstep.json").read_text())
        data = make_data(cfg["data"], sess.spec.seed)
        win["checked"] = Reference(data, cfg["spec"]["schedule"], precision="high").steps(
            k, len(win["checked"]))
        return win

    monkeypatch.setattr(harness, "measure", control_window)
    r = run(root, "tiny-sstep.t2l")
    assert r["correct"] is False
    assert r["checks"]["weights_gap"]["value"] > LIMITS["weights_gap"]["limit"]


def test_window_not_started_from_zero_is_not_correct(root, monkeypatch):
    """Set-up's warm-up call left in the state the window starts from,
    as a reset that misses a piece of state would."""
    monkeypatch.setattr(harness, "warm_up", lambda sess, k: sess.step_rounds(k))
    r = run(root, "tiny-sstep.t2l")
    assert r["correct"] is False
    assert r["checks"]["loss_gap"]["value"] > LIMITS["loss_gap"]["limit"]


def test_state_left_unchanged_is_not_correct(root, monkeypatch, fresh_programs):
    import repro.api.session as session

    monkeypatch.setattr(session, "run_engine_chunk", lambda tp, x, *a, **k: x)
    r = run(root, "tiny-sstep.t2l")
    assert r["correct"] is False
    assert r["checks"]["change_gap"]["value"] == 1.0


def test_half_batch_is_not_correct(root, monkeypatch, fresh_programs):
    import repro.core.engine as engine

    inner = engine.inner_corrections

    def half(g, v, s, b, eta, objective=engine.LOGISTIC):
        u = inner(g, v, s, b, eta, objective)
        keep = np.arange(s * b) % b < b // 2
        return jax.numpy.where(keep, 2.0 * u, 0.0)

    monkeypatch.setattr(engine, "inner_corrections", half)
    r = run(root, "tiny-sstep.t2l")
    assert r["correct"] is False
    assert r["checks"]["change_gap"]["value"] > LIMITS["change_gap"]["limit"]


CHILD = """
import json, sys, time
sys.path[:0] = [{src!r}, {root!r}]
import jax
from pathlib import Path
from bench import harness
from repro.core.comm import Collectives

def run():
    c = harness.load_cell("tiny-hybrid.t2l", Path({tiny!r}))
    r, _ = harness.run_cell(c, seed={seed}, seconds=0.5, trace=False, t0=time.perf_counter(),
                            devices=jax.devices()[:4], log=lambda s: None)
    return {{"correct": r["correct"], "weights_gap": r["checks"]["weights_gap"]["value"]}}

out = {{"sound": run()}}
Collectives.allreduce_cols = lambda self, tree, **kw: tree
jax.clear_caches()
out["no_exchange"] = run()
print(json.dumps(out))
"""


def test_mesh_sound_run_passes_and_exchange_left_out_fails(root):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env.update(JAX_PLATFORMS="cpu", XLA_FLAGS="--xla_force_host_platform_device_count=4")
    code = CHILD.format(src=str(ROOT / "src"), root=str(ROOT), tiny=str(root), seed=SEED)
    p = subprocess.run([sys.executable, "-c", code], env=env, capture_output=True, text=True,
                       timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["sound"]["correct"] is True
    assert out["no_exchange"]["correct"] is False
