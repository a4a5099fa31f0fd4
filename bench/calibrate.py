"""Readings the limits of ``correct`` are set from, for one cell.

    python3 bench/calibrate.py --workload rcv1-sstep.t2l --seeds 1 2 3 ... [--out file]

For each seed, in one process:

* ``program``: the program's first steps as a benchmark run takes them
  (set-up's warm-up call, then ``Session.step_rounds`` from x0 = 0)
  against the float64 reference;
* ``control``: the reference computed at the precision below the
  configuration's (three bfloat16 passes), put in the program's place;
* ``half_batch`` and, on a mesh with p_c > 1, ``no_exchange``: the
  reference with that fault planted, put in the program's place.

Each prints one JSON line ``{"seed", "kind", <numbers>}``. A state left
unchanged reads 1 on ``change_gap`` and ``weights_gap`` and needs no
run. The lower reading of a number is the largest ``program`` reading;
its upper reading the smallest of the control's, and of each fault's
that is ten times the lower or more.
"""

import argparse
import gc
import json
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--out", help="also append the lines to this file")
    ap.add_argument("--save", help="also keep the program's (loss, weights) of each step in "
                    "<dir>/<data seed>.npz, to read them against another reference")
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

    from bench import check, harness
    from bench.data import make_data
    from bench.reference import Reference

    cell = harness.load_cell(args.workload, ROOT)
    harness.require_chips(cell.chips)
    from repro.api import ExperimentSpec, Session
    from repro.launch.cache import place_compile_cache

    place_compile_cache()
    k, steps = cell.loss_every, int(cell.traffic["check_steps"])
    sched = cell.config["spec"]["schedule"]
    p_c = int(cell.config["spec"]["mesh"]["p_c"])
    out = open(args.out, "a") if args.out else None
    for seed in args.seeds:
        t = time.perf_counter()
        spec = ExperimentSpec.from_dict(cell.spec_dict(seed))
        sess = Session(spec)
        harness.warm_up(sess, k)
        prog = [harness.observed(sess.step_rounds(k)) for _ in range(steps)]
        del sess
        gc.collect()
        if args.save:
            Path(args.save).mkdir(parents=True, exist_ok=True)
            np.savez(Path(args.save) / f"{spec.seed}.npz", losses=[o[0] for o in prog],
                     x=np.stack([o[1] for o in prog]))
        t_prog = time.perf_counter() - t
        data = make_data(cell.config["data"], spec.seed)

        def reference(**kw):
            return Reference(data, sched, spec.row_multiple, **kw).steps(k, steps)

        ref = reference()
        runs = {"program": prog, "control": reference(precision="high"),
                "half_batch": reference(fault="half_batch")}
        if p_c > 1:
            runs["no_exchange"] = reference(fault="no_exchange", p_c=p_c)
        for kind, obs in runs.items():
            line = json.dumps({"seed": seed, "data_seed": spec.seed, "kind": kind,
                               **check.compare(obs, ref)})
            print(line, flush=True)
            if out:
                out.write(line + "\n")
                out.flush()
        print(f"seed {seed}: program {t_prog:.1f}s, all {time.perf_counter() - t:.1f}s",
              file=sys.stderr, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
