"""The paper's contribution: one 2D-parallel SGD family, in JAX.

The unified engine (repro.core.engine) implements the whole
(p_r, p_c, s, τ) family with one round body on the scatter-free Pallas
Gram path, which both backends run:

  run_parallel_sgd     the engine — any point of the family
  ParallelSGDSchedule  the knob object (corners by name: mb_sgd,
                       sstep, fedavg, hybrid)
  bundle_gram_v        the s-bundle primitive (G, v)

Configured corners, kept as thin wrappers for compatibility:

  run_sgd              Algorithm 1 — sequential mini-batch SGD
  run_sstep_sgd        Algorithm 3 — s-step (communication-avoiding) SGD
  run_fedavg           Algorithm 2 — FedAvg / local SGD
  run_hybrid_sgd       HybridSGD, exact simulated-rank semantics
  run_hybrid_distributed  HybridSGD under shard_map on a 2D device mesh
                          (consumes the same ParallelSGDSchedule; each
                          rank runs the engine's round body)
  HybridDriver         the round-incremental form of the same executor
                       (device-resident carry; advance k rounds at a
                       time — what repro.api.Session drives)
  run_engine_chunk     the simulated engine's round-incremental entry
                       (jit-cached chunk executable, traced offset)

Corner identities (tested): hybrid(p_r=1) ≡ s-step; hybrid(p_r=p, s=1)
≡ FedAvg; s-step(s=1) ≡ SGD; fedavg(τ=1) ≡ synchronous MB-SGD.

Experiment-level code should normally enter through the declarative
front door instead: repro.api (ExperimentSpec → plan → run → RunReport)
plans a spec with the cost model and dispatches it to either the
simulated engine or the shard_map executor.
"""

from repro.core.comm import (
    COUNTING,
    MESH,
    TIMED,
    Collectives,
    CommLedger,
    CommRate,
)
from repro.core.faults import (
    FaultEvent,
    FaultInjector,
    FaultPlan,
    InjectedFault,
    TransientIOError,
    WorkerKilled,
)
from repro.core.objective import (
    LOGISTIC,
    OBJECTIVES,
    LeastSquaresObjective,
    LogisticObjective,
    Objective,
    SquaredHingeObjective,
    get_objective,
)
from repro.core.problem import (
    LogisticProblem,  # deprecated alias of Problem
    Problem,
    full_loss,  # deprecated: use problem_loss
    make_problem,
    problem_loss,
    sigmoid_residual,  # deprecated: use LOGISTIC.residual
)
from repro.core.engine import (
    ParallelSGDSchedule,
    bundle_gram_v,
    engine_comm_ledger,
    inner_corrections,
    run_engine_chunk,
    run_parallel_sgd,
    single_team,
)
from repro.core.sgd import run_sgd, sgd_step
from repro.core.sstep import run_sstep_sgd
from repro.core.teams import TeamProblem, global_problem, stack_row_teams
from repro.core.fedavg import run_fedavg
from repro.core.hybrid import run_hybrid_sgd
from repro.core.distributed import (
    Hybrid2DProblem,
    HybridDriver,
    build_2d_problem,
    gather_x,
    hybrid_comm_ledger,
    make_hybrid_step,
    run_hybrid_distributed,
    scatter_x,
)

__all__ = [
    "COUNTING",
    "MESH",
    "TIMED",
    "Collectives",
    "CommLedger",
    "CommRate",
    "engine_comm_ledger",
    "hybrid_comm_ledger",
    "FaultEvent",
    "FaultInjector",
    "FaultPlan",
    "InjectedFault",
    "TransientIOError",
    "WorkerKilled",
    "LOGISTIC",
    "OBJECTIVES",
    "Objective",
    "LogisticObjective",
    "SquaredHingeObjective",
    "LeastSquaresObjective",
    "get_objective",
    "Problem",
    "problem_loss",
    "LogisticProblem",
    "full_loss",
    "make_problem",
    "sigmoid_residual",
    "ParallelSGDSchedule",
    "bundle_gram_v",
    "inner_corrections",
    "run_engine_chunk",
    "run_parallel_sgd",
    "single_team",
    "run_sgd",
    "sgd_step",
    "run_sstep_sgd",
    "TeamProblem",
    "global_problem",
    "stack_row_teams",
    "run_fedavg",
    "run_hybrid_sgd",
    "Hybrid2DProblem",
    "HybridDriver",
    "build_2d_problem",
    "gather_x",
    "make_hybrid_step",
    "run_hybrid_distributed",
    "scatter_x",
]
