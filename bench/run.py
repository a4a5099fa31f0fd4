"""Run one cell of the benchmark on the chips of this machine.

    python3 bench/run.py --workload rcv1-sstep.t2l --seed 7 --seconds 30 --trace 0

Prints the result as one JSON line, last on standard output, and the
numbers ``correct`` compared beside their limits, last on standard
error. Exits non-zero, with no result line, when JAX sees no TPU or
fewer chips than the cell asks for, or when the program is missing.
"""

import time

T0 = time.perf_counter()  # set-up is timed from here

import argparse  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True, help="a workload name of BENCHMARK.json")
    ap.add_argument("--seed", type=int, required=True, help="makes the data and the run")
    ap.add_argument("--seconds", type=float, required=True, help="length of the measured window")
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0,
                    help="1: profile the window and report the per-layer metrics")
    args = ap.parse_args(argv)

    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    from bench import harness

    cell = harness.load_cell(args.workload, ROOT)
    devices = harness.require_chips(cell.chips)

    import jax
    from repro.launch.cache import place_compile_cache

    place_compile_cache()
    # every program in the cache after a cell's first run, small ones too
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    result, lines = harness.run_cell(
        cell, seed=args.seed, seconds=args.seconds, trace=bool(args.trace), t0=T0,
        devices=devices, log=lambda s: print(s, file=sys.stderr, flush=True),
    )
    harness.emit(result, lines)
    return 0


if __name__ == "__main__":
    sys.exit(main())
