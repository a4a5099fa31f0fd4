"""``BENCHMARK.json`` against the benchmark's contract, discovery of
added files by name, and the exits without a chip."""

import json
import os
import re
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from bench import harness

ROOT = Path(__file__).resolve().parents[2]
NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}")
UNIT = re.compile(r"[A-Za-z0-9_/%.-]{1,16}")


def _bench():
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def test_benchmark_json_keeps_its_contract():
    b = _bench()
    assert set(b) == {"command", "paths", "run_seconds", "configs", "workloads", "end_to_end",
                      "per_layer"}
    assert b["command"] == ["python3", "bench/run.py"]
    assert 1 <= b["run_seconds"] <= 51 and isinstance(b["run_seconds"], int)
    for p in b["paths"]:
        assert (ROOT / p).is_dir() and not p.startswith("/") and ".." not in p
    names = [c["name"] for c in b["configs"]]
    cells = [w["name"] for w in b["workloads"]]
    metrics = [m["name"] for m in b["end_to_end"] + b["per_layer"]]
    for n in names + cells + metrics:
        assert NAME.fullmatch(n), n
    assert len(set(names)) == len(names) and len(set(cells)) == len(cells)
    assert len(set(metrics)) == len(metrics)
    for c in b["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith("bench/") and (ROOT / c["file"]).is_file()
        assert c["name"] in {w["config"] for w in b["workloads"]}
    four = 0
    for w in b["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["config"] in names and w["chips"] in (1, 4) and len(w["why"]) <= 200
        assert w["name"] == f"{w['config']}.{w['traffic']}"
        assert (ROOT / "bench" / "traffic" / f"{w['traffic']}.json").is_file()
        assert (ROOT / "bench" / "limits" / f"{w['name']}.json").is_file()
        four += w["chips"] == 4
    assert four <= max(1, len(cells) // 2)
    e2e = {m["name"]: m for m in b["end_to_end"]}
    assert "setup_s" in e2e
    for m in b["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace") and 0.01 <= m["bound"] <= 0.25
        assert UNIT.fullmatch(m["unit"]) and m["better"] in ("lower", "higher")
    for m in b["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer", "moves", "workloads"}
        assert m["moves"] in e2e and UNIT.fullmatch(m["unit"])
        assert (ROOT / "bench" / "metrics" / f"{m['name']}.py").is_file()
        assert set(m.get("workloads", cells)) <= set(cells)


def test_every_cell_loads_with_what_it_reports():
    for w in _bench()["workloads"]:
        cell = harness.load_cell(w["name"])
        e2e = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in e2e and len(e2e) >= 2 and cell.per_layer
        assert all(0 < cell.target_loss(s) < 0.6932 for s in cell.config["data_seeds"])
        assert cell.limits
        assert all(v["lower"] < v["limit"] < v["upper"] for v in cell.limits.values())


def test_added_config_traffic_and_metric_are_found_by_name(tmp_path):
    shutil.copytree(ROOT / "bench", tmp_path / "bench")
    b = _bench()
    cfg = json.loads((ROOT / "bench" / "configs" / "rcv1-sstep.json").read_text())
    cfg["name"] = "dummy"
    (tmp_path / "bench" / "configs" / "dummy.json").write_text(json.dumps(cfg))
    (tmp_path / "bench" / "traffic" / "dummy-mix.json").write_text(
        json.dumps({"name": "dummy-mix", "loss_every": 64, "check_steps": 2, "trace_seconds": 1}))
    (tmp_path / "bench" / "limits" / "dummy.dummy-mix.json").write_text(
        json.dumps({"weights_gap": {"limit": 1e-6}}))
    (tmp_path / "bench" / "metrics" / "dummy_metric.py").write_text(
        "def read(run):\n    return 2.5 * run.rounds\n")
    b["configs"].append({"name": "dummy", "source": "x", "file": "bench/configs/dummy.json",
                         "reduced": [], "why": "x"})
    b["workloads"].append({"name": "dummy.dummy-mix", "config": "dummy", "traffic": "dummy-mix",
                           "chips": 1, "why": "x"})
    b["per_layer"].append({"name": "dummy_metric", "unit": "rounds", "better": "lower",
                           "source": "program_counter", "layer": "solver", "moves": "rows_per_s",
                           "workloads": ["dummy.dummy-mix"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(b))

    cell = harness.load_cell("dummy.dummy-mix", tmp_path)
    assert cell.config["name"] == "dummy" and cell.loss_every == 64
    assert cell.spec_dict(2**31 + 9)["schedule"]["loss_every"] == 64
    assert cell.limits == {"weights_gap": {"limit": 1e-6}}
    assert "dummy_metric" in [m["name"] for m in cell.per_layer]
    assert "collective_exposed_share" not in [m["name"] for m in cell.per_layer]
    run = harness.Run(cell=cell, chips=1, rows_per_round=256, window_s=1.0, rounds=4,
                      probes=[], crossing=None)
    assert harness.load_metric("dummy_metric", tmp_path)(run) == 10.0
    # the cells already there are untouched by the addition
    assert harness.load_cell("news20-sstep.t2l", tmp_path).config == \
        harness.load_cell("news20-sstep.t2l").config


def _run(cwd, *args):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    return subprocess.run([sys.executable, "bench/run.py", *args], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


@pytest.mark.parametrize("where", ["checkout", "bench_only"])
def test_no_result_without_a_tpu_or_without_the_program(tmp_path, where):
    if where == "bench_only":
        shutil.copytree(ROOT / "bench", tmp_path / "bench")
        shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
        cwd = tmp_path
    else:
        cwd = ROOT
    p = _run(cwd, "--workload", "news20-sstep.t2l", "--seed", str(2**31 + 1), "--seconds", "1")
    assert p.returncode != 0
    assert not any(line.startswith("{") for line in p.stdout.splitlines())
