"""The reference's loss curve for each of a config's data seeds, which
the config's per-seed ``target_loss`` is set from.

    python3 bench/targets.py --config rcv1-sstep --rounds 2400 [--out file]

Prints one JSON line per data seed: ``{"data_seed", "every", "losses"}``,
the loss after every ``loss_every`` rounds from x0 = 0. A config's
target for a seed is the midpoint of the losses at its
``target_rounds`` and one probe before, so every matrix asks for the
same number of rounds of the reference's progress.
"""

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--rounds", type=int, required=True)
    ap.add_argument("--out", help="also append the lines to this file")
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    from bench.data import make_data
    from bench.reference import Reference

    cfg = json.loads((ROOT / "bench" / "configs" / f"{args.config}.json").read_text())
    sched = cfg["spec"]["schedule"]
    every = int(sched["loss_every"])
    out = open(args.out, "a") if args.out else None
    for seed in cfg["data_seeds"]:
        t = time.perf_counter()
        ref = Reference(make_data(cfg["data"], seed), sched, cfg["spec"]["row_multiple"])
        line = json.dumps({"data_seed": seed, "every": every,
                           "losses": ref.curve(args.rounds, every)})
        print(line, flush=True)
        if out:
            out.write(line + "\n")
            out.flush()
        print(f"data seed {seed}: {time.perf_counter() - t:.1f}s", file=sys.stderr, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
