"""Benchmark driver — one module per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--only table9,...]

Every row is ``name,us_per_call,derived`` CSV. Per-module wall seconds
land in the ``repro.obs`` metrics registry
(``bench.module_seconds{module=...}`` gauges plus a
``bench.modules_failed_total`` counter) and print as ``[bench]``
summary lines after the CSV. Unknown ``--only`` keys and module
failures both exit nonzero — CI gates on this.
"""

from __future__ import annotations

import argparse
import sys
import time
import traceback

MODULES = {
    "table4": "benchmarks.bench_mesh_rule",
    "table5+7+fig4": "benchmarks.bench_costmodel",
    "table9": "benchmarks.bench_partitioners",
    "table11": "benchmarks.bench_time_to_loss",
    "objectives": "benchmarks.bench_objectives",
    "comm": "benchmarks.bench_comm",
    "fig3": "benchmarks.bench_skew_sweep",
    "fig5": "benchmarks.bench_mesh_sweep",
    "kernels": "benchmarks.bench_kernels",
    "roofline": "benchmarks.bench_roofline",
    "serve": "benchmarks.bench_serve",
}


def main(argv: list[str] | None = None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None, help="comma-separated subset of: " + ",".join(MODULES))
    args = ap.parse_args(argv)
    selected = args.only.split(",") if args.only else list(MODULES)
    unknown = [k for k in selected if k not in MODULES]
    if unknown:
        ap.error(f"unknown module(s) {unknown}; choose from: {','.join(MODULES)}")

    import importlib

    from repro.launch.cache import place_compile_cache
    from repro.obs import metrics as obs_metrics

    place_compile_cache()

    reg = obs_metrics.registry()
    failures = []
    for key in selected:
        mod_name = MODULES[key]
        print(f"# ==== {key} ({mod_name}) ====", flush=True)
        t0 = time.perf_counter()
        try:
            importlib.import_module(mod_name).run()
        except Exception:
            failures.append(key)
            reg.counter("bench.modules_failed_total").inc()
            traceback.print_exc()
        reg.gauge("bench.module_seconds", module=key).set(time.perf_counter() - t0)
    for key in selected:
        wall = reg.gauge("bench.module_seconds", module=key).value
        status = "FAIL" if key in failures else "ok"
        print(f"[bench] {key:16s} {wall:8.2f}s  {status}", flush=True)
    if failures:
        print(f"# FAILED: {failures}", file=sys.stderr)
        raise SystemExit(1)
    print("# all benchmarks complete")


if __name__ == "__main__":
    main()
