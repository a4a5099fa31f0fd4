"""The benchmark's run of one cell: set-up, the measured window, the
check against the reference, and the result line.

Everything a cell needs is found by name from ``BENCHMARK.json``:

* ``bench/configs/<config>.json``: the deployment (dataset statistics,
  the ``ExperimentSpec`` it runs as, ``target_loss``);
* ``bench/traffic/<traffic>.json``: the job (check steps, traced span,
  an optional probe cadence that overrides the config's);
* ``bench/limits/<config>.<traffic>.json``: the numbers ``correct``
  compares, with their limits;
* ``bench/metrics/<name>.py``: one reader per per-layer metric,
  ``read(run) -> float | None``.

The program is driven through its front door only: ``ExperimentSpec``
→ ``repro.api.Session`` → ``Session.step_rounds``.
"""

from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import math
import shutil
import sys
import time
from pathlib import Path

import numpy as np

from bench import check, trace_reduce, work
from bench.data import RowSchedule, make_data
from bench.peaks import Peaks, peaks_for
from bench.reference import Reference

ROOT = Path(__file__).resolve().parents[1]


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    end_to_end: list[dict]
    per_layer: list[dict]
    root: Path

    @property
    def loss_every(self) -> int:
        return int(self.traffic.get("loss_every") or self.config["spec"]["schedule"]["loss_every"])

    def data_seed(self, seed: int) -> int:
        """The seed the data is made from: one of the config's
        ``data_seeds``, chosen by the run's seed. They are the seeds whose
        matrix has the config's ELL width, so every run's programs have
        the same shapes and come from the compile cache."""
        pool = self.config["data_seeds"]
        return int(pool[seed % len(pool)])

    def target_loss(self, data_seed: int) -> float:
        """The config's ``target_loss`` for ``data_seed``."""
        return float(self.config["target_loss"][str(data_seed)])

    def spec_dict(self, seed: int) -> dict:
        """The config's spec with the run's data seed and probe cadence,
        and a round budget the window never reaches."""
        spec = json.loads(json.dumps(self.config["spec"]))
        spec["seed"] = self.data_seed(seed)
        k = self.loss_every
        spec["schedule"]["loss_every"] = k
        spec["schedule"]["rounds"] = 10**7 // k * k
        return spec


def load_cell(name: str, root: Path = ROOT) -> Cell:
    """The workload ``name`` of ``root/BENCHMARK.json`` with its files."""
    bench = json.loads((root / "BENCHMARK.json").read_text())
    try:
        w = next(w for w in bench["workloads"] if w["name"] == name)
    except StopIteration:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json") from None
    cfg = next(c for c in bench["configs"] if c["name"] == w["config"])
    e2e = [m for m in bench["end_to_end"] if name in m.get("workloads", [name])]
    reported = {m["name"] for m in e2e}
    per_layer = [
        m for m in bench["per_layer"]
        if (name in m["workloads"] if "workloads" in m else m["moves"] in reported)
    ]
    return Cell(
        name=name,
        chips=int(w["chips"]),
        config=json.loads((root / cfg["file"]).read_text()),
        traffic=json.loads((root / "bench" / "traffic" / f"{w['traffic']}.json").read_text()),
        limits=json.loads((root / "bench" / "limits" / f"{name}.json").read_text()),
        end_to_end=e2e,
        per_layer=per_layer,
        root=root,
    )


def load_metric(name: str, root: Path = ROOT):
    """The ``read`` function of ``bench/metrics/<name>.py``."""
    path = root / "bench" / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def require_chips(chips: int):
    """The first ``chips`` TPU devices; raises ``SystemExit`` when JAX
    sees no TPU or fewer chips (never falls back to the CPU)."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < chips:
        raise SystemExit(
            f"this cell needs {chips} TPU chip(s); JAX sees {len(devices)} "
            f"{devices[0].platform} device(s)"
        )
    return devices[:chips]


class CompileCounter:
    """Counts programs JAX lowers or compiles while ``armed``."""

    EVENTS = (
        "/jax/core/compile/jaxpr_to_mlir_module_duration",
        "/jax/core/compile/backend_compile_duration",
    )

    def __init__(self):
        import jax

        self.armed = False
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, _secs: float, **_kw) -> None:
        if self.armed and event in self.EVENTS:
            self.count += 1


@dataclasses.dataclass
class Run:
    """What a per-layer metric reads: the window's counts and, in a
    traced run, the reduced trace with the work of the traced rounds."""

    cell: Cell
    chips: int
    rows_per_round: int
    window_s: float
    rounds: int
    probes: list[tuple[float, int, float]]
    crossing: tuple[float, int] | None
    peaks: Peaks | None = None
    trace: trace_reduce.Trace | None = None
    trace_span: tuple[int, int] | None = None
    traced_rounds: int = 0
    calls: list[work.Call] = dataclasses.field(default_factory=list)
    flops: int = 0

    @property
    def chip_ids(self) -> list[int]:
        return trace_reduce.chips(self.trace, self.chips) if self.trace else []


def warm_up(sess, k: int) -> None:
    """Set-up's one call of the window's own ``sess.step_rounds(k)``,
    which builds every program the window runs, then ``sess`` back at
    round 0 with zero weights. ``Session`` has no public reset, so this
    sets its round counter and weights; the window's first steps are
    compared with the reference from x0 = 0, so state this misses makes
    the run incorrect."""
    sess.step_rounds(k)
    x0 = np.zeros(sess.bundle.dataset.A.n, np.float32)
    if sess._driver is not None:
        sess._driver.set_x(x0)
        sess._driver.rounds_done = 0
    else:
        import jax.numpy as jnp

        sess._x = jnp.asarray(x0)
    sess.rounds_done = 0


def observed(ev) -> tuple[float, np.ndarray]:
    """What the check reads of one ``RoundEvent``: its loss and weights."""
    return float(ev.loss), np.array(ev.x, np.float32)


def measure(sess, k: int, seconds: float, steps: int, trace_dir: Path | None,
            trace_seconds: float, counter: CompileCounter) -> dict:
    """Call ``sess.step_rounds(k)`` back to back for ``seconds`` (and at
    least ``steps`` times), keeping the first ``steps`` events for the
    check; with ``trace_dir``, profile the first ``trace_seconds``."""
    import jax

    probes, checked = [], []

    def step(t_start):
        with jax.profiler.TraceAnnotation("bench.step_rounds"):
            ev = sess.step_rounds(k)
        probes.append((time.perf_counter() - t_start, ev.rounds_done, float(ev.loss)))
        if len(checked) < steps:
            checked.append(observed(ev))

    traced_rounds = 0
    counter.armed = True
    if trace_dir is not None:
        jax.profiler.start_trace(str(trace_dir))
        t_start = time.perf_counter()
        with jax.profiler.TraceAnnotation(trace_reduce.WINDOW):
            while time.perf_counter() - t_start < trace_seconds:
                step(t_start)
        traced_rounds = sess.rounds_done
        jax.profiler.stop_trace()
    else:
        t_start = time.perf_counter()
    while time.perf_counter() - t_start < seconds or len(checked) < steps:
        step(t_start)
    counter.armed = False
    return {"probes": probes, "checked": checked, "elapsed": probes[-1][0],
            "traced_rounds": traced_rounds}


def memory_peak(devices) -> int:
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0)) for d in devices)


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, t0: float, devices,
             log=print) -> tuple[dict, list[str]]:
    """One run of ``cell``: returns the result line and the check lines
    that end standard error."""
    import jax
    from repro.api import ExperimentSpec, Session
    from repro.api.run import _cached_dataset

    counter = CompileCounter()
    spec = ExperimentSpec.from_dict(cell.spec_dict(seed))
    k = cell.loss_every
    steps = int(cell.traffic["check_steps"])
    sched = cell.config["spec"]["schedule"]
    rows = RowSchedule.of(int(cell.config["data"]["m"]), sched, spec.row_multiple)

    # set-up: build once, build the window's programs with one call of
    # its own step, and put the Session back at x0 = 0
    phases = {"start": time.perf_counter() - t0}
    t = time.perf_counter()
    _cached_dataset(spec.dataset, spec.seed)  # what Session builds first; timed apart
    phases["data"] = time.perf_counter() - t
    t = time.perf_counter()
    sess = Session(spec)
    phases["build"] = time.perf_counter() - t
    t = time.perf_counter()
    warm_up(sess, k)
    phases["warm_up"] = time.perf_counter() - t
    setup_s = time.perf_counter() - t0
    log(f"[setup] {setup_s:.3f}s: " + ", ".join(f"{k_} {v:.3f}s" for k_, v in phases.items())
        + f"; {spec.dataset} n={sess.bundle.dataset.A.n}, the window's first {steps} "
        f"step(s) of {k} round(s) checked")

    trace_dir = None
    if trace:
        trace_dir = cell.root / ".bench_out" / cell.name / "trace"
        shutil.rmtree(trace_dir, ignore_errors=True)
    win = measure(sess, k, seconds, steps, trace_dir, float(cell.traffic["trace_seconds"]),
                  counter)
    peak = memory_peak(devices)
    rounds = win["probes"][-1][1]
    rows_per_round = rows.p_r * rows.tau * rows.b
    target = cell.target_loss(spec.seed)
    crossing = next(((t, r) for t, r, loss in win["probes"] if loss <= target), None)
    finite = all(math.isfinite(loss) for _, _, loss in win["probes"])
    log(f"[window] {win['elapsed']:.3f}s, {rounds} rounds, {len(win['probes'])} probes, "
        f"last loss {win['probes'][-1][2]:.6f}, target {target} "
        f"{'at %.3fs' % crossing[0] if crossing else 'not reached'}, "
        f"{counter.count} program(s) built in the window")

    out = cell.root / ".bench_out" / cell.name
    out.mkdir(parents=True, exist_ok=True)
    (out / "last_run.json").write_text(json.dumps(
        {"seed": seed, "data_seed": spec.seed, "setup": phases, "probes": win["probes"]}))

    del sess
    gc.collect()

    data = make_data(cell.config["data"], spec.seed)
    run = Run(cell=cell, chips=len(devices), rows_per_round=rows_per_round,
              window_s=win["elapsed"], rounds=rounds, probes=win["probes"], crossing=crossing)
    result = {"correct": False, "attempted": 1, "failed": int(crossing is None or not finite)}
    device = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(jax.devices()),
        "memory_peak_bytes": peak,
    }
    if trace:
        run.peaks = peaks_for(devices[0].device_kind)
        run.trace = trace_reduce.load(str(trace_dir))
        run.trace_span = trace_reduce.span(run.trace)
        run.traced_rounds = win["traced_rounds"]
        p_c = int(cell.config["spec"]["mesh"]["p_c"])
        for r in range(run.traced_rounds):
            run.calls += work.round_calls(data, rows, r, p_c)
            run.flops += work.round_flops(data, rows, r)
        lo, hi = run.trace_span
        device["busy_s"] = float(np.mean([trace_reduce.busy_ns(run.trace, c, lo, hi)
                                          for c in run.chip_ids])) / 1e9
        device["window_s"] = (hi - lo) / 1e9
        metrics = {}
        for m in cell.per_layer:
            value = load_metric(m["name"], cell.root)(run)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        result["breakdown"] = trace_reduce.breakdown(run.trace, run.chip_ids, lo, hi)
    else:
        e2e = {
            "time_to_target_s": crossing[0] if crossing else win["elapsed"],
            "rows_per_s": rounds * rows_per_round / win["elapsed"],
            "setup_s": setup_s,
        }
        metrics = {m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]} for m in cell.end_to_end}
    result["metrics"] = metrics
    result["device"] = device

    t = time.perf_counter()
    ref = Reference(data, sched, spec.row_multiple).steps(k, steps)
    numbers = check.compare(win["checked"], ref)
    correct, checks = check.verdict(numbers, cell.limits)
    log(f"[check] reference ran {steps} steps in {time.perf_counter() - t:.3f}s; "
        + ", ".join(f"{name} {v:.3e}" for name, v in numbers.items()))
    result["correct"] = bool(correct)
    result["window"] = {"programs_built": counter.count, "rounds": rounds}
    result["checks"] = checks
    lines = [f"check {name} {c['value']:.6e} limit {c['limit']:.6e} "
             f"{'ok' if c['value'] <= c['limit'] else 'FAIL'}" for name, c in checks.items()]
    return result, lines


def emit(result: dict, lines: list[str]) -> None:
    """The check lines last on standard error, then the result line
    last on standard output."""
    sys.stdout.flush()
    for line in lines:
        print(line, file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)

