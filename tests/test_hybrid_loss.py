"""The loss probe on the mesh: ``HybridDriver.loss()`` computes the full
objective over the shards it has placed, and only the scalar comes to
the host.

Everything runs in one child process on four virtual CPU devices
(``XLA_FLAGS`` must be set before JAX starts, and the main test process
sees one device); each test reads its case from the child's report.

Tolerance: the mesh sums each margin as p_c partial sums and the losses
as p_r team sums, where ``problem_loss`` sums each row and then all rows
in one pass. In float32 that reorders a few hundred additions, which
moves the loss by a few 1e-7 relative; ``RTOL`` = 1e-5 leaves room for
that, and is still two orders below what one row counted twice, left
out or a pad row counted as a sample (ℓ(0) / m, about 3e-3 relative at
m = 250) would move.
"""

import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent
RTOL = 1e-5

MESHES = [(1, 4), (2, 2), (4, 1)]
PARTITIONERS = ["rows", "nnz", "cyclic"]
OBJECTIVES = [("logistic", 0.0), ("logistic", 0.01), ("squared_hinge", 0.01),
              ("least_squares", 0.0)]

CHILD = textwrap.dedent("""
    import dataclasses, json
    import numpy as np, jax, jax.numpy as jnp
    from repro import compat
    from repro.api import ExperimentSpec, MeshSpec, Session
    from repro.core import ParallelSGDSchedule
    from repro.core.distributed import HybridDriver, build_2d_problem, run_hybrid_distributed
    from repro.core.objective import get_objective
    from repro.core.problem import make_problem, problem_loss
    from repro.kernels.ell_margins import build_margin_layout
    from repro.sparse.synthetic import make_skewed_csr

    out = {"cases": {}, "kernel": {}}
    rng = np.random.default_rng(0)
    m, n, rm = 250, 100, 8  # 250 rows: every team count leaves pad rows at 8
    A = make_skewed_csr(m, n, 12, 0.8, seed=3)
    y = np.where(rng.random(m) < 0.5, 1.0, -1.0)
    x = (0.3 * rng.standard_normal(n)).astype(np.float32)
    sched = ParallelSGDSchedule.hybrid(1, 2, 4, 0.05, 8, rounds=1)
    m2, n2 = 600, 5_000
    A2 = make_skewed_csr(m2, n2, 12, 0.8, seed=5)
    y2 = np.where(rng.random(m2) < 0.5, 1.0, -1.0)
    x2 = (0.3 * rng.standard_normal(n2)).astype(np.float32)
    for p_r, p_c in MESHES:
        mesh = compat.make_mesh((p_r, p_c), ("rows", "cols"))
        for part in PARTITIONERS:
            for name, l2 in OBJECTIVES:
                obj = get_objective(name, l2=l2)
                prob, cp = build_2d_problem(A, y, p_r, p_c, part, row_multiple=rm,
                                            objective=obj)
                drv = HybridDriver(mesh, prob, cp, x, dataclasses.replace(sched, p_r=p_r))
                ref = float(problem_loss(make_problem(A, y, row_multiple=rm, objective=obj),
                                         jnp.asarray(x)))
                key = f"{p_r}x{p_c}-{part}-{name}-{l2}"
                out["cases"][key] = [drv.loss(), ref]
                # the probe with its margins from the kernel (the
                # interpreter here) over each block's own layout, on a
                # matrix whose every block has two row tiles or more and
                # two column windows or more
                prob, cp = build_2d_problem(A2, y2, p_r, p_c, part, row_multiple=rm,
                                            objective=obj)
                drv = HybridDriver(mesh, prob, cp, x2, dataclasses.replace(sched, p_r=p_r))
                lay = jax.device_put(build_margin_layout(prob.indices, prob.values, prob.n_loc),
                                     drv._data_sh)
                ref = float(problem_loss(make_problem(A2, y2, row_multiple=rm, objective=obj),
                                         jnp.asarray(x2)))
                out["kernel"][key] = [float(drv._loss(drv._idx, drv._val, drv._x_pad, lay)),
                                      ref]

    # the probe after its first call: no upload, under the guard
    # ("disallow_explicit" also refuses device_put / jnp.asarray of a
    # numpy array, which "disallow" lets through)
    mesh = compat.make_mesh((2, 2), ("rows", "cols"))
    prob, cp = build_2d_problem(A, y, 2, 2, "cyclic", row_multiple=rm)
    drv = HybridDriver(mesh, prob, cp, x, dataclasses.replace(sched, p_r=2))
    first = drv.loss()
    try:
        with jax.transfer_guard_host_to_device("disallow_explicit"):
            out["guarded"] = {"loss": drv.loss(), "first": first}
    except Exception as e:  # the guard raises on any upload
        out["guarded"] = {"error": repr(e)[:500]}

    # run_hybrid_distributed samples through the same loss
    s2 = ParallelSGDSchedule.hybrid(2, 2, 4, 0.05, 8, rounds=4, loss_every=2)
    x_d, losses = run_hybrid_distributed(mesh, prob, cp, np.zeros(n, np.float32), s2)
    gp = make_problem(A, y, row_multiple=rm)
    drv = HybridDriver(mesh, prob, cp, np.zeros(n, np.float32), s2)
    ref = []
    for _ in range(2):
        drv.advance(2)
        ref.append(float(problem_loss(gp, jnp.asarray(drv.gather()))))
    out["run_hybrid_distributed"] = [[float(v) for v in losses], ref]

    # a 2x2 Session on shard_map against the simulated backend
    for delay in (0, 1):
        s3 = ParallelSGDSchedule.hybrid(2, 2, 4, 0.05, 8, rounds=6, loss_every=2, delay=delay)
        spec = ExperimentSpec(dataset="rcv1-sm", schedule=s3,
                              mesh=MeshSpec(p_r=2, p_c=2, backend="simulated"), name="probe")
        runs = {}
        for backend in ("simulated", "shard_map"):
            sess = Session(dataclasses.replace(
                spec, mesh=MeshSpec(p_r=2, p_c=2, backend=backend)))
            losses = [sess.step_rounds(2).loss for _ in range(3)]
            rep = sess.report()
            # the largest array that lies whole on one device, against
            # the ELL blocks of the whole matrix
            blocks = sess.bundle.prob2d or sess.bundle.team
            one = [a.nbytes for a in jax.live_arrays() if len(a.sharding.device_set) == 1]
            runs[backend] = {"losses": losses, "final": rep.final_loss,
                             "x": rep.x.tolist(),
                             "largest_on_one": max(one, default=0),
                             "ell_bytes": int(blocks.indices.nbytes + blocks.values.nbytes)}
            del sess, rep
        out[f"session-delay{delay}"] = runs
    print(json.dumps(out))
""")


@pytest.fixture(scope="module")
def child():
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env.update(JAX_PLATFORMS="cpu", XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=str(REPO / "src"))
    code = (f"MESHES, PARTITIONERS, OBJECTIVES = {MESHES!r}, {PARTITIONERS!r}, "
            f"{OBJECTIVES!r}\n" + CHILD)
    p = subprocess.run([sys.executable, "-c", code], env=env, capture_output=True, text=True,
                       timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("objective", OBJECTIVES, ids=lambda o: f"{o[0]}-l2={o[1]}")
@pytest.mark.parametrize("partitioner", PARTITIONERS)
@pytest.mark.parametrize("mesh", MESHES, ids=lambda s: f"{s[0]}x{s[1]}")
def test_mesh_loss_matches_problem_loss(child, mesh, partitioner, objective):
    """The mesh loss against ``problem_loss`` on the uncut problem, at
    random weights, with pad rows in every team and pad slots in x."""
    got, ref = child["cases"][f"{mesh[0]}x{mesh[1]}-{partitioner}-{objective[0]}-{objective[1]}"]
    assert got == pytest.approx(ref, rel=RTOL)


@pytest.mark.parametrize("objective", OBJECTIVES, ids=lambda o: f"{o[0]}-l2={o[1]}")
@pytest.mark.parametrize("partitioner", PARTITIONERS)
@pytest.mark.parametrize("mesh", MESHES, ids=lambda s: f"{s[0]}x{s[1]}")
def test_mesh_kernel_loss_matches_problem_loss(child, mesh, partitioner, objective):
    """The mesh loss with each block's partial margins from the
    ``ell_margins`` kernel (as on a TPU) against ``problem_loss``, on a
    600 x 5,000 matrix: every block spans two row tiles or more and two
    column windows or more. The kernel sums a row's products in its own
    order, which the same RTOL covers."""
    got, ref = child["kernel"][f"{mesh[0]}x{mesh[1]}-{partitioner}-{objective[0]}-{objective[1]}"]
    assert got == pytest.approx(ref, rel=RTOL)


def test_mesh_probe_uploads_nothing(child):
    """No host-to-device transfer, explicit or not, in a probe after the
    first (which compiled it)."""
    guarded = child["guarded"]
    assert "error" not in guarded, guarded.get("error")
    assert guarded["loss"] == guarded["first"]


def test_run_hybrid_distributed_samples_the_mesh_loss(child):
    got, ref = child["run_hybrid_distributed"]
    assert len(got) == 2
    assert got == pytest.approx(ref, rel=RTOL)


@pytest.mark.parametrize("delay", [0, 1])
def test_mesh_session_matches_simulated(child, delay):
    """``step_rounds`` on a 2x2 mesh probes the losses the simulated
    backend probes, the final loss agrees, and the mesh session never
    built the whole-matrix problem nor put its blocks on one device."""
    import numpy as np

    runs = child[f"session-delay{delay}"]
    sim, mesh = runs["simulated"], runs["shard_map"]
    assert mesh["losses"] == pytest.approx(sim["losses"], rel=RTOL)
    assert mesh["final"] == pytest.approx(sim["final"], rel=RTOL)
    assert np.abs(np.asarray(mesh["x"]) - np.asarray(sim["x"])).max() < 1e-5
    assert mesh["largest_on_one"] < mesh["ell_bytes"] / 4
