"""On-chip smoke test of the HybridSGD solver's main path.

    python chip_smoke.py              # one TPU chip
    python chip_smoke.py --chips 4    # the 2x2 host: shard_map mesh only

One chip: loads ``examples/specs/rcv1_full_hybrid.json`` (full-size
rcv1, HybridSGD with p_r = 2 row teams on the simulated backend) and
runs it through ``repro.api.plan`` and ``Session(spec).run()``. It
checks that

* the compiled round program runs the Pallas Gram kernel
  (``tpu_custom_call`` in its HLO), not the interpreter;
* the final weights match the same spec run with ``gram="dense"`` (the
  plain XLA densify oracle, ``repro.kernels.ref``) on the same chip to
  a relative L2 gap of at most ``REL_TOL``;
* the loss fell below its value at ``x0 = 0``.

``--chips 4`` runs only the mesh phase: the same schedule on a 2x2
``backend="shard_map"`` mesh, checked for the kernel and an all-reduce
in its compiled round, and compared with ``backend="simulated"`` on one
device of the same process within the same tolerance.

The last line of standard output is one JSON object,
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
Any failed check raises and the exit code is non-zero. Without a TPU,
or without the rest of this checkout beside it, the script exits
non-zero before it runs anything. The timings it prints are those of a
smoke run, not a benchmark.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
SPEC = ROOT / "examples" / "specs" / "rcv1_full_hybrid.json"
# fp32 weights after a few dozen bundles: the kernel and the oracle sum
# the same products in different orders, nothing more.
REL_TOL = 1e-4


class SmokeFailure(RuntimeError):
    """A check of the smoke run failed."""


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)
    print(f"[ok   ] {what}", flush=True)


def rel_l2(x, ref) -> float:
    import numpy as np

    return float(np.linalg.norm(x - ref) / np.linalg.norm(ref))


def timed_run(spec, label: str):
    """Build a Session for ``spec``, compile its round, run it, and
    print what it took. Returns (session, loss at x0 = 0, report, round
    HLO text); the start loss is the Session's own, as its probe
    computes it on either backend."""
    from repro.api import Session

    t0 = time.perf_counter()
    sess = Session(spec)
    build_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    hlo = sess.compile_round().as_text()
    aot_s = time.perf_counter() - t0
    loss0 = sess.report().final_loss
    rep = sess.run()
    first = spec.schedule.loss_every or spec.schedule.rounds
    steady = rep.rounds_completed - first
    rate = steady / rep.solve_time_s if steady and rep.solve_time_s > 0 else float("nan")
    print(
        f"[run  ] {label}: build={build_s:.2f}s round-compile={aot_s:.2f}s "
        f"first-chunk(compile+{first} rounds)={rep.compile_time_s:.2f}s "
        f"solve={rep.solve_time_s:.3f}s rounds={rep.rounds_completed} "
        f"smoke rounds/s={rate:.2f} (smoke timing, not a benchmark)",
        flush=True,
    )
    return sess, loss0, rep, hlo


def one_chip(spec) -> None:
    from repro.api import plan

    print(f"[plan ] {plan(spec).summary()}", flush=True)
    sess, loss0, rep, hlo = timed_run(spec, "pallas")
    a, team = sess.bundle.dataset.A, sess.bundle.team
    print(
        f"[data ] {spec.dataset}: m={a.m} n={a.n} nnz={a.nnz} "
        f"ELL {tuple(team.indices.shape)} (teams, rows/team, width)",
        flush=True,
    )
    check("tpu_custom_call" in hlo, "compiled round runs the Pallas kernel (tpu_custom_call)")
    print(f"[loss ] start={loss0:.6f} end={rep.final_loss:.6f} trace={rep.losses.tolist()}")
    check(rep.final_loss < loss0, "loss fell below its value at x0 = 0")

    dense = dataclasses.replace(
        spec, schedule=dataclasses.replace(spec.schedule, gram="dense")
    )
    _, _, rep_d, _ = timed_run(dense, "dense oracle")
    gap = rel_l2(rep.x, rep_d.x)
    print(f"[gap  ] ||x - x_dense|| / ||x_dense|| = {gap:.3e} (tolerance {REL_TOL:.0e})")
    check(gap <= REL_TOL, "weights match the gram='dense' oracle")


def four_chips(spec) -> None:
    from repro.api import MeshSpec

    p_r = spec.schedule.p_r
    mesh = dataclasses.replace(
        spec, mesh=MeshSpec(p_r=p_r, p_c=2, backend="shard_map")
    )
    _, loss0, rep, hlo = timed_run(mesh, f"shard_map {p_r}x2")
    check("tpu_custom_call" in hlo, "mesh round runs the Pallas kernel (tpu_custom_call)")
    check("all-reduce" in hlo, "mesh round holds an all-reduce")
    print(f"[loss ] start={loss0:.6f} end={rep.final_loss:.6f} trace={rep.losses.tolist()}")
    check(rep.final_loss < loss0, "loss fell below its value at x0 = 0")

    sim = dataclasses.replace(spec, mesh=MeshSpec(p_r=p_r, p_c=2, backend="simulated"))
    _, _, rep_s, _ = timed_run(sim, "simulated, one device")
    gap = rel_l2(rep.x, rep_s.x)
    print(f"[gap  ] ||x_mesh - x_sim|| / ||x_sim|| = {gap:.3e} (tolerance {REL_TOL:.0e})")
    check(gap <= REL_TOL, "shard_map weights match the simulated backend")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: the full main path on one chip; 4: only the "
                         "2x2 shard_map mesh against the simulated backend")
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {devices[0].platform}); "
              f"this check runs only on the chip", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} devices, "
              f"JAX found {len(devices)}", file=sys.stderr)
        return 2

    sys.path.insert(0, str(ROOT / "src"))
    from repro.launch import cache

    if not Path(cache.__file__).resolve().is_relative_to(ROOT / "src"):
        print(f"chip_smoke: repro imported from {cache.__file__}, not from "
              f"this checkout", file=sys.stderr)
        return 2
    print(f"[cache] {cache.place_compile_cache()}", flush=True)
    from repro.api import ExperimentSpec

    d = devices[0]
    print(f"[dev  ] platform={d.platform} device_kind={d.device_kind} "
          f"count={len(devices)} jax={jax.__version__}", flush=True)
    spec = ExperimentSpec.from_json(SPEC.read_text())
    if args.chips == 4:
        four_chips(spec)
    else:
        one_chip(spec)
    print(json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind, "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
