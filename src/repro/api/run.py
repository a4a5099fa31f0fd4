"""run(spec) — build once, execute through a Session, report uniformly.

``build_problem`` subsumes the three hand-rolled construction paths the
launchers used to carry (``single_team`` / ``stack_row_teams`` /
``build_2d_problem``) behind one call keyed off the spec; ``run`` is a
thin loop over the round-incremental ``repro.api.Session``, which
dispatches the same ``ParallelSGDSchedule`` to either executor:

  backend="simulated"  repro.core.engine.run_engine_chunk — exact
                       simulated-rank semantics on one device (the
                       oracle; p_c is communication-only there).
  backend="shard_map"  repro.core.distributed.HybridDriver —
                       the production 2D device-mesh execution (needs
                       p_r·p_c addressable devices, e.g. via
                       XLA_FLAGS=--xla_force_host_platform_device_count).

Both return the same ``RunReport`` (weights, loss trace with engine
``loss_every`` semantics, wall time split into compile/solve, modeled
comm volume), so switching hardware is a one-field change in the spec —
tested for parity in tests/test_distributed_subprocess.py. Chunked
session execution is bitwise-identical to the monolithic single-scan
engine path (tests/test_session.py).
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import numpy as np

from repro import compat
from repro.api.report import RunReport
from repro.api.spec import ExperimentSpec
from repro.core.distributed import Hybrid2DProblem, build_2d_problem
from repro.sparse.partition import ColumnPartition
from repro.core.objective import get_objective
from repro.core.teams import TeamProblem, stack_row_teams
from repro.sparse.synthetic import SyntheticDataset, make_dataset


@dataclasses.dataclass
class ProblemBundle:
    """Everything ``run`` needs, built once from the spec.

    Exactly one of (team, prob2d) is populated, per the backend.
    """

    spec: ExperimentSpec
    dataset: SyntheticDataset
    row_multiple: int
    team: TeamProblem | None = None
    prob2d: Hybrid2DProblem | None = None
    cp: ColumnPartition | None = None


# Dataset materialization is deterministic in (name, seed) and is the
# dominant build cost for repeated run(spec) calls (benchmark repeats,
# sweeps over schedules on one dataset) — memoize it. The cached
# dataset is *enforced* read-only: every consumer sees the same numpy
# buffers, so an in-place write anywhere would silently corrupt every
# later run on the same (name, seed). Frozen flags turn that aliasing
# hazard into an immediate ValueError at the write site.


@functools.lru_cache(maxsize=8)
def _cached_dataset(name: str, seed: int = 0) -> SyntheticDataset:
    ds = make_dataset(name, seed=seed)
    for arr in (ds.A.indptr, ds.A.indices, ds.A.data, ds.y, ds.x_true):
        arr.flags.writeable = False
    return ds


def build_problem(spec: ExperimentSpec) -> ProblemBundle:
    """Materialize the dataset and partition it for the spec's backend.
    Row padding is ``spec.row_multiple`` (default s·b) on both paths so
    simulated and distributed sample sequences agree; the spec's
    objective (+ l2) rides on every problem object, so both executors
    and the loss probes read the same convex loss."""
    sched, mesh = spec.schedule, spec.mesh
    ds = _cached_dataset(spec.dataset, seed=spec.seed)
    rm = spec.row_multiple or sched.s * sched.b
    obj = get_objective(spec.objective, l2=spec.l2)
    bundle = ProblemBundle(spec=spec, dataset=ds, row_multiple=rm)
    if mesh.backend == "simulated":
        bundle.team = stack_row_teams(
            ds.A, ds.y, mesh.p_r, row_multiple=rm, objective=obj
        )
    else:
        bundle.prob2d, bundle.cp = build_2d_problem(
            ds.A, ds.y, mesh.p_r, mesh.p_c, mesh.partitioner, row_multiple=rm,
            objective=obj,
        )
    return bundle


def _make_device_mesh(p_r: int, p_c: int):
    need = p_r * p_c
    devices = jax.devices()
    if len(devices) < need:
        raise RuntimeError(
            f"backend='shard_map' needs {need} devices for a {p_r}×{p_c} mesh but "
            f"only {len(devices)} are visible — set "
            f"XLA_FLAGS=--xla_force_host_platform_device_count={need} (CPU) or use "
            f"backend='simulated'"
        )
    return compat.make_mesh((p_r, p_c), ("rows", "cols"), devices=devices[:need])


def run(spec: ExperimentSpec, x0: np.ndarray | None = None) -> RunReport:
    """The front door: plan (auto-tuning if asked), build, execute,
    report — now a thin loop over the round-incremental ``Session``
    (``Session(spec, x0).run()``), honoring the spec's ``StopPolicy``.
    ``wall_time_s`` covers the solver only and splits into
    ``compile_time_s`` (first chunk, includes jit) + ``solve_time_s``."""
    from repro.api.session import Session

    return Session(spec, x0=x0).run()


def run_decaying_tau(
    spec: ExperimentSpec,
    x0: np.ndarray | None = None,
    stages: int = 3,
    growth: int = 2,
) -> list[RunReport]:
    """The decaying-communication-frequency schedule of *Local SGD to
    One-Shot Averaging* (arXiv:2106.04759), as a compensation knob for
    delayed averaging: run ``stages`` consecutive segments of the spec,
    multiplying τ by ``growth`` each stage — synchronize often while
    the iterates move fast, then progressively less as they settle.
    The spec's round budget is split across the stages (earlier stages
    get the remainder) and the weights chain stage to stage, so the
    list of per-stage reports is one continuous optimization; the last
    report holds the final iterate. A ``delay`` on the schedule rides
    along unchanged — growing τ only widens its legal range (D ≤ τ/s).
    """
    if stages < 1:
        raise ValueError(f"stages={stages} must be ≥ 1")
    if growth < 1:
        raise ValueError(f"growth={growth} must be ≥ 1")
    sched = spec.schedule
    total = sched.rounds
    per = [total // stages + (1 if i < total % stages else 0) for i in range(stages)]
    if per[-1] < 1:
        raise ValueError(
            f"rounds={total} cannot cover {stages} stages with ≥ 1 round each"
        )
    base = spec.name or spec.dataset
    reports: list[RunReport] = []
    x = x0
    for k, r in enumerate(per):
        st = dataclasses.replace(
            spec,
            name=f"{base}/stage{k}-tau{sched.tau * growth**k}",
            schedule=dataclasses.replace(
                sched, tau=sched.tau * growth**k, rounds=r
            ),
        )
        rep = run(st, x0=x)
        reports.append(rep)
        x = rep.x
    return reports
