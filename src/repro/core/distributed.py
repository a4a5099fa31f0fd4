"""HybridSGD over a real 2D device mesh (shard_map).

This is the production distribution of the paper's algorithm. The mesh
axes are ("rows", "cols") = (p_r, p_c):

  device (i, j) holds the ELL block of diag(y)·A for row-team i and
  column-partition j (columns locally renumbered in partition order),
  plus its n_loc-word shard of the weight vector.

Per s-bundle (the paper's row-team Allreduce):
  G_partial, v_partial computed locally via the engine's shared bundle
  primitive (repro.core.engine.bundle_gram_v — scatter-free) → psum
  over "cols" (exactly the (s²b² + sb)-word payload of Table 3); the
  weight update Yᵀu is fully local under column partitioning.
Per τ inner iterations (the paper's column Allreduce):
  x_local ← pmean over "rows" (n/p_c words per rank).

Both collectives are issued through repro.core.comm (the mesh — or,
for calibration, timed — collectives): ``hybrid_comm_ledger`` captures
the round body's exact spans and payloads into a ``CommLedger``, and
``HybridDriver`` commits rounds (and, timed, per-round wall seconds)
into it as it advances.

The execution knobs arrive as one ``ParallelSGDSchedule`` — the same
object the simulated engine consumes — so the two paths cannot drift on
plumbing. The legacy loose-scalar signatures (s=..., b=..., ...) are
kept as deprecated shims.

Numerics match repro.core.engine.run_parallel_sgd exactly (tested in a
multi-device subprocess); the simulated version is the oracle.
"""

from __future__ import annotations

import dataclasses
import time
import warnings

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.compat import shard_map
from repro.core import comm as comm_plane
from repro.core.comm import MESH, Collectives, CommLedger
from repro.core.engine import (
    ParallelSGDSchedule,
    bundle_gram_v,
    check_delay,
    delayed_bundle_scan,
    engine_loss,
    inner_corrections,
    unwire_gv,
    wire_gv,
)
from repro.core.objective import LOGISTIC, Objective, get_objective
from repro.core.problem import Problem
from repro.sparse.csr import CSRMatrix
from repro.sparse.ell import EllBlock, ell_rmatvec
from repro.sparse.partition import ColumnPartition, partition_columns, partition_rows


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class Hybrid2DProblem:
    """Device-layout HybridSGD problem.

    indices/values: (p_r, p_c, rows_local, width) — ELL blocks, column
    ids local to each column shard.
    col_sizes: (p_c,) true (unpadded) columns per shard; shards pad to
    n_loc = max(col_sizes).
    """

    indices: jnp.ndarray
    values: jnp.ndarray
    col_sizes: jnp.ndarray
    p_r: int = dataclasses.field(metadata=dict(static=True))
    p_c: int = dataclasses.field(metadata=dict(static=True))
    m: int = dataclasses.field(metadata=dict(static=True))
    n: int = dataclasses.field(metadata=dict(static=True))
    n_loc: int = dataclasses.field(metadata=dict(static=True))
    objective: Objective = dataclasses.field(
        default=LOGISTIC, metadata=dict(static=True)
    )

    @property
    def rows_local(self) -> int:
        return int(self.indices.shape[2])

    @property
    def width(self) -> int:
        return int(self.indices.shape[3])


def build_2d_problem(
    a: CSRMatrix,
    y: np.ndarray,
    p_r: int,
    p_c: int,
    partitioner: str,
    row_multiple: int = 1,
    dtype=jnp.float32,
    objective: str | Objective = LOGISTIC,
) -> tuple[Hybrid2DProblem, ColumnPartition]:
    """Partition (A, y) onto the p_r × p_c mesh. Row bounds match
    repro.core.teams.stack_row_teams so simulated and distributed
    sample sequences agree; ``objective`` is the shared convex loss."""
    obj = get_objective(objective)
    ya = a.scale_rows(np.asarray(y, dtype=np.float64))
    cp = partition_columns(a, p_c, partitioner)
    rb = partition_rows(a.m, p_r)
    rows_local = max(int(rb[i + 1] - rb[i]) for i in range(p_r))
    rows_local = -(-rows_local // row_multiple) * row_multiple
    n_loc = int(cp.n_local.max())

    blocks = []
    width = 1
    for i in range(p_r):
        row_blk = ya.row_block(int(rb[i]), int(rb[i + 1]))
        row = [row_blk.select_columns(cp.rank_cols(j)) for j in range(p_c)]
        blocks.append(row)
        for blk in row:
            if blk.nnz:
                width = max(width, int(blk.nnz_per_row.max()))

    idx = np.zeros((p_r, p_c, rows_local, width), dtype=np.int32)
    val = np.zeros((p_r, p_c, rows_local, width), dtype=np.float64)
    for i in range(p_r):
        for j in range(p_c):
            blk = blocks[i][j]
            for r in range(blk.m):
                lo, hi = int(blk.indptr[r]), int(blk.indptr[r + 1])
                k = hi - lo
                idx[i, j, r, :k] = blk.indices[lo:hi]
                val[i, j, r, :k] = blk.data[lo:hi]
    prob = Hybrid2DProblem(
        indices=jnp.asarray(idx),
        values=jnp.asarray(val, dtype=dtype),
        col_sizes=jnp.asarray(np.asarray(cp.n_local, np.int32)),
        p_r=p_r,
        p_c=p_c,
        m=a.m,
        n=a.n,
        n_loc=n_loc,
        objective=obj,
    )
    return prob, cp


def scatter_x(x: np.ndarray, cp: ColumnPartition, n_loc: int) -> np.ndarray:
    """Global (n,) weights → padded sharded layout (p_c · n_loc,)."""
    out = np.zeros(cp.p * n_loc, dtype=x.dtype)
    for j in range(cp.p):
        cols = cp.rank_cols(j)
        out[j * n_loc : j * n_loc + len(cols)] = x[cols]
    return out


def gather_x(x_pad: np.ndarray, cp: ColumnPartition, n_loc: int, n: int) -> np.ndarray:
    """Inverse of scatter_x."""
    out = np.zeros(n, dtype=x_pad.dtype)
    for j in range(cp.p):
        cols = cp.rank_cols(j)
        out[cols] = x_pad[j * n_loc : j * n_loc + len(cols)]
    return out


def _legacy_schedule(
    p_r: int, s, b, eta, tau, rounds, gram: str, caller: str
) -> ParallelSGDSchedule:
    """Adapt the pre-API loose-scalar knobs into a schedule (deprecated)."""
    warnings.warn(
        f"{caller}(s=..., b=..., tau=..., ...) with loose scalars is deprecated; "
        f"pass a repro.core.ParallelSGDSchedule (or use the repro.api front door)",
        DeprecationWarning,
        stacklevel=3,
    )
    if b is None or eta is None or tau is None:
        raise TypeError(f"legacy {caller} call is missing one of (b, eta, tau)")
    return ParallelSGDSchedule.hybrid(
        p_r, int(s), int(b), float(eta), int(tau),
        rounds=int(rounds) if rounds is not None else 1, gram=gram or "blocked",
    )


def _reject_scalars_with_schedule(caller: str, **scalars) -> None:
    """A schedule is the whole configuration — a scalar knob alongside
    it would be silently ignored, so make that a hard error."""
    extras = [k for k, v in scalars.items() if v is not None]
    if extras:
        raise TypeError(
            f"{caller}: got both a ParallelSGDSchedule and scalar knob(s) "
            f"{extras} — the schedule carries all knobs; use "
            f"dataclasses.replace(sched, ...) instead"
        )


def _build_round_fn(prob: Hybrid2DProblem, sched: ParallelSGDSchedule,
                    comm: Collectives = MESH):
    """The per-rank round body (what shard_map maps): τ inner s-step
    iterations + the column average, all communication issued through
    the ``comm`` collectives. Shared by ``make_hybrid_step`` (which
    shard_maps and jits it) and ``hybrid_comm_ledger`` (which captures
    it abstractly) — one function, so the ledger cannot drift from the
    executed collectives."""
    s, b_, eta_ = sched.s, sched.b, sched.eta
    sb = s * b_
    n_loc = prob.n_loc
    bundles = sched.tau // s
    objective = prob.objective
    lam = objective.l2

    def round_fn(idx_blk, val_blk, x_loc, round_idx):
        # shapes inside shard_map: idx/val (1, 1, rows_local, width),
        # x_loc (n_loc,)
        idx_blk = idx_blk[0, 0]
        val_blk = val_blk[0, 0]
        m_local = idx_blk.shape[0]

        if sched.delay:
            # Delay-D pipeline: the per-bundle psum is *issued* at
            # bundle t and first *consumed* at bundle t+D, so XLA's
            # async dispatch has D bundle-computes of independent work
            # to run while the reduction is in flight. The staging
            # logic is the engine's shared scan — both backends execute
            # the same pipelined math by construction.
            def slice_bundle(t):
                k0 = round_idx * bundles + t
                start = (k0 * sb) % m_local
                bi = jax.lax.dynamic_slice_in_dim(idx_blk, start, sb, axis=0)
                bv = jax.lax.dynamic_slice_in_dim(val_blk, start, sb, axis=0)
                return bi, bv

            x_loc = delayed_bundle_scan(
                x_loc, slice_bundle=slice_bundle, bundles=bundles, n=n_loc,
                sched=sched, eta=eta_, objective=objective, comm=comm,
            )
            with jax.named_scope("param_avg"):
                return comm.allmean_rows(x_loc)

        def bundle(x_loc, t):
            k0 = round_idx * bundles + t
            start = (k0 * sb) % m_local
            bi = jax.lax.dynamic_slice_in_dim(idx_blk, start, sb, axis=0)
            bv = jax.lax.dynamic_slice_in_dim(val_blk, start, sb, axis=0)
            # local partial (G, v) via the engine's shared primitive —
            # then the row-team Allreduce (paper Table 3 payload; bf16
            # words under the precision knob — the psum sums narrow
            # payloads, corrections run on the f32 upcast)
            with jax.named_scope("bundle_gram"):
                g_part, v_part = bundle_gram_v(
                    bi, bv, x_loc, n_loc, gram=sched.gram, bk=sched.bk, bm=sched.bm,
                    precision=sched.precision,
                )
            with jax.named_scope("allreduce_gv"):
                g, v = comm.allreduce_cols(
                    wire_gv((g_part, v_part), sched.precision),
                    calls_per_round=bundles,
                )
                g, v = unwire_gv((g, v), sched.precision)
            with jax.named_scope("corrections"):
                u = inner_corrections(g, v, s, b_, eta_, objective)
            # Yᵀu stays local under column partitioning
            blk = EllBlock(indices=bi, values=bv, n=n_loc)
            with jax.named_scope("update"):
                if lam == 0.0:
                    return x_loc + (eta_ / b_) * ell_rmatvec(blk, u).astype(x_loc.dtype), None
                # decay-folded update, exact under column sharding: the
                # L2 decay is elementwise, so each shard decays its own
                # slice (padded slots stay zero: ρ·0 + 0).
                rho_s = jnp.asarray(1.0 - eta_ * lam, x_loc.dtype) ** s
                return (
                    rho_s * x_loc + (eta_ / b_) * ell_rmatvec(blk, u).astype(x_loc.dtype),
                    None,
                )

        x_loc, _ = jax.lax.scan(bundle, x_loc, jnp.arange(bundles))
        # column Allreduce: FedAvg averaging across row teams (n/p_c
        # words) — the result is row-replicated, so the out_spec can
        # drop the "rows" axis.
        with jax.named_scope("param_avg"):
            return comm.allmean_rows(x_loc)

    return round_fn


def hybrid_comm_ledger(prob: Hybrid2DProblem, sched: ParallelSGDSchedule,
                       comm: Collectives = MESH) -> CommLedger:
    """Per-rank ``CommLedger`` of the shard_map execution: the *same*
    round body ``make_hybrid_step`` runs, traced abstractly
    (``jax.eval_shape`` — no devices, no mesh needed) with the comm
    recorder installed. Every psum/pmean the step will issue records its
    span and per-rank payload from the traced per-shard shapes."""
    round_fn = _build_round_fn(prob, sched, comm)
    rates = comm_plane.capture_rates(
        round_fn,
        jax.ShapeDtypeStruct((1, 1, prob.rows_local, prob.width), prob.indices.dtype),
        jax.ShapeDtypeStruct((1, 1, prob.rows_local, prob.width), prob.values.dtype),
        jax.ShapeDtypeStruct((prob.n_loc,), jnp.float32),
        jax.ShapeDtypeStruct((), jnp.int32),
        spans={"cols": prob.p_c, "rows": prob.p_r},
    )
    return CommLedger(rates=rates, delay=sched.delay)


def make_hybrid_step(
    mesh: Mesh,
    prob: Hybrid2DProblem,
    sched: ParallelSGDSchedule | int | None = None,
    b: int | None = None,
    tau: int | None = None,
    eta: float | None = None,
    gram: str | None = None,
    bk: int | None = None,
    *,
    s: int | None = None,
    comm: Collectives = MESH,
):
    """Return a jitted fn (indices, values, x_pad, round_idx) → x_pad
    executing one HybridSGD round (τ inner s-step iterations + column
    average) under shard_map on ``mesh`` (axes "rows", "cols").

    ``sched`` is the same ``ParallelSGDSchedule`` the simulated engine
    consumes; its ``gram`` selects the bundle backend, which runs per
    shard exactly as on the simulated engine (the Pallas kernel by
    default, compiled on a TPU).
    All collectives are issued through ``comm`` (repro.core.comm; the
    mesh/timed kinds run the same psum/pmean this module always issued).

    The returned step donates ``x_pad`` and pins its output to the
    ``P("cols")`` sharding of the input, so drivers can chain rounds
    without re-placing the weights (no per-round sync + copy).

    The legacy signature ``make_hybrid_step(mesh, prob, s, b, tau, eta,
    gram=..., bk=...)`` still works but emits a DeprecationWarning.
    """
    if isinstance(sched, ParallelSGDSchedule):
        _reject_scalars_with_schedule(
            "make_hybrid_step", s=s, b=b, tau=tau, eta=eta, gram=gram, bk=bk
        )
    else:
        s_val = sched if sched is not None else s
        if s_val is None:
            raise TypeError("make_hybrid_step needs a ParallelSGDSchedule (or legacy s=...)")
        sched = _legacy_schedule(prob.p_r, s_val, b, eta, tau, None, gram, "make_hybrid_step")
        if bk is not None:
            sched = dataclasses.replace(sched, bk=bk)
    if sched.tau % sched.s:
        raise ValueError(f"tau={sched.tau} must be divisible by s={sched.s}")
    if tuple(mesh.axis_names) != ("rows", "cols"):
        raise ValueError(f'mesh axes must be ("rows", "cols"), got {mesh.axis_names}')
    if dict(mesh.shape) != {"rows": prob.p_r, "cols": prob.p_c}:
        raise ValueError(
            f"mesh {dict(mesh.shape)} does not match problem layout "
            f"{prob.p_r}×{prob.p_c}"
        )
    if sched.eta <= 0:
        raise ValueError(f"eta={sched.eta} must be > 0 to run the solver")
    check_delay(sched)
    if not comm.on_mesh:
        raise ValueError(
            f"make_hybrid_step needs mesh collectives (mesh/timed), got {comm.kind!r}"
        )
    round_fn = _build_round_fn(prob, sched, comm)

    smapped = shard_map(
        round_fn,
        mesh=mesh,
        in_specs=(P("rows", "cols"), P("rows", "cols"), P("cols"), P()),
        out_specs=P("cols"),
    )

    x_sh = NamedSharding(mesh, P("cols"))
    step = jax.jit(smapped, out_shardings=x_sh, donate_argnums=(2,))
    return step


class HybridDriver:
    """Round-incremental shard_map executor — the chunkable form of the
    old run-everything loop.

    Holds the device-resident state (placed ELL blocks + the sharded,
    donated weight vector) between calls, so drivers above it — the
    ``repro.api.Session`` lifecycle, dashboards, async averaging — can
    advance the computation ``k`` rounds at a time, probe the objective,
    checkpoint, and keep going, with the same chain-of-async-dispatches
    execution the monolithic loop had (one jitted step, donated carry,
    no per-round host sync).

    The round counter is part of the carry: ``advance(k)`` runs global
    rounds ``rounds_done .. rounds_done+k-1``, so chunked execution
    reproduces the uninterrupted loop's sample sequence exactly.

    The driver owns the run's ``CommLedger``: the collectives of the
    round body are captured once at construction (``hybrid_comm_ledger``
    on the very round_fn the step executes) and committed per advanced
    round. With ``comm=TIMED`` each round blocks on completion and its
    wall seconds land in the ledger — the §6.5 calibration input
    (repro.costmodel.calibrate).
    """

    def __init__(
        self,
        mesh: Mesh,
        prob: Hybrid2DProblem,
        cp: ColumnPartition,
        x0: np.ndarray,
        sched: ParallelSGDSchedule,
        loss_problem: Problem | None = None,
        rounds_done: int = 0,
        comm: Collectives = MESH,
    ):
        self.prob = prob
        self.cp = cp
        self.sched = sched
        self.loss_problem = loss_problem
        self.rounds_done = int(rounds_done)
        self.comm = comm
        self.ledger = hybrid_comm_ledger(prob, sched, comm)
        self.ledger.rounds = self.rounds_done
        self._step = make_hybrid_step(mesh, prob, sched, comm=comm)
        self._mesh = mesh
        data_sh = NamedSharding(mesh, P("rows", "cols"))
        self._data_sh = data_sh
        self._x_sh = NamedSharding(mesh, P("cols"))
        self._idx = jax.device_put(prob.indices, data_sh)
        self._val = jax.device_put(prob.values, data_sh)
        self._x_pad = jax.device_put(
            jnp.asarray(scatter_x(np.asarray(x0), cp, prob.n_loc)), self._x_sh
        )

    def lower_step(self) -> jax.stages.Lowered:
        """The jitted one-round step ``advance`` dispatches, lowered but
        not run — to read its compiled HLO or memory analysis."""
        return self._step.lower(
            self._idx, self._val, self._x_pad, jnp.int32(self.rounds_done)
        )

    def advance(self, k: int) -> None:
        """Run ``k`` rounds; weights stay device-resident (async).
        Timed collectives block per round and record wall seconds."""
        for _ in range(int(k)):
            t0 = time.perf_counter() if self.comm.timed else 0.0
            self._x_pad = self._step(
                self._idx, self._val, self._x_pad, jnp.int32(self.rounds_done)
            )
            if self.comm.timed:
                jax.block_until_ready(self._x_pad)
                self.ledger.add_round_seconds(time.perf_counter() - t0)
            self.rounds_done += 1
        self.ledger.rounds = self.rounds_done

    def advance_stream(self, indices: np.ndarray, values: np.ndarray) -> None:
        """Run ONE round over streamed data instead of the resident
        blocks: ``(p_r, p_c, rows_local, width)`` ELL shards with
        shard-local column ids (``repro.serve.ingest.stream_shard_arrays``
        builds them from a micro-batch). The round body slices bundles
        modulo the operand's row count, so with ``rows_local = τ·b`` the
        τ/s bundles walk the fresh rows exactly once at *any* round
        index — the step function is the resident one, jit-cached per
        data shape (fixed-shape streams compile once)."""
        t0 = time.perf_counter() if self.comm.timed else 0.0
        idx = jax.device_put(jnp.asarray(indices, jnp.int32), self._data_sh)
        val = jax.device_put(jnp.asarray(values, jnp.float32), self._data_sh)
        self._x_pad = self._step(idx, val, self._x_pad, jnp.int32(self.rounds_done))
        if self.comm.timed:
            jax.block_until_ready(self._x_pad)
            self.ledger.add_round_seconds(time.perf_counter() - t0)
        self.rounds_done += 1
        self.ledger.rounds = self.rounds_done

    def sync(self) -> None:
        """Block until all dispatched rounds complete — no host copy.
        The tracing seam uses this so a round span's wall covers the
        work it dispatched (observer effect on timing only; the async
        chain and its numerics are identical either way)."""
        jax.block_until_ready(self._x_pad)

    def phase_probes(self) -> dict:
        """Jitted per-phase probes over this driver's real payload
        shapes — the §6.5 phase split, measured *outside* the training
        step so its compiled round body is never touched.

        Returns ``{phase: (fn, args, calls_per_round)}``:

          bundle_compute  one rank's local partial (G, v) over an
                          (s·b, width) ELL bundle (Eq. 4's γ term);
          allreduce_gv    the (s²b² + sb)-word psum over "cols" on the
                          real mesh (Table 3's row-team payload);
          param_avg       the n_loc-word pmean over "rows" (the column
                          weight sync).

        Probes run on zero-filled payloads of the true shapes — comm
        cost is shape-dependent, data-independent.
        """
        sched, prob, mesh = self.sched, self.prob, self._mesh
        sb = sched.s * sched.b
        bundles = sched.tau // sched.s
        reps = -(-sb // prob.rows_local)
        bi = jnp.tile(prob.indices[0, 0], (reps, 1))[:sb]
        bv = jnp.tile(prob.values[0, 0], (reps, 1))[:sb]
        x_loc = jnp.zeros((prob.n_loc,), jnp.float32)
        compute = jax.jit(
            lambda i, v, x: bundle_gram_v(
                i, v, x, prob.n_loc, gram=sched.gram, bk=sched.bk, bm=sched.bm,
                precision=sched.precision,
            )
        )
        # the probed psum carries the wire dtype: a bf16 schedule's
        # measured allreduce_gv reflects the halved payload
        gv_dt = jnp.bfloat16 if sched.precision == "bf16" else jnp.float32
        g0 = jnp.zeros((sb, sb), gv_dt)
        v0 = jnp.zeros((sb,), gv_dt)
        ar = jax.jit(shard_map(
            lambda g, v: (jax.lax.psum(g, "cols"), jax.lax.psum(v, "cols")),
            mesh=mesh, in_specs=(P(), P()), out_specs=(P(), P()),
        ))
        xp = jax.device_put(
            jnp.zeros(prob.p_c * prob.n_loc, jnp.float32), self._x_sh
        )
        pm = jax.jit(shard_map(
            lambda x: jax.lax.pmean(x, "rows"),
            mesh=mesh, in_specs=P("cols"), out_specs=P("cols"),
        ))
        return {
            "bundle_compute": (compute, (bi, bv, x_loc), bundles),
            "allreduce_gv": (ar, (g0, v0), bundles),
            "param_avg": (pm, (xp,), 1),
        }

    def gather(self) -> np.ndarray:
        """Current global weights (n,) — blocks on the dispatch chain."""
        return gather_x(np.asarray(self._x_pad), self.cp, self.prob.n_loc, self.prob.n)

    def set_x(self, x: np.ndarray) -> None:
        """Replace the weights (checkpoint restore). Padded layout slots
        never receive updates (no row references them), so a
        gather → set_x round trip is lossless."""
        self._x_pad = jax.device_put(
            jnp.asarray(scatter_x(np.asarray(x), self.cp, self.prob.n_loc)), self._x_sh
        )

    def loss(self) -> float:
        """Full global objective (under ``loss_problem``'s objective)
        at the current iterate."""
        if self.loss_problem is None:
            raise ValueError("HybridDriver was built without loss_problem")
        return float(engine_loss(self.loss_problem, jnp.asarray(self.gather())))


def run_hybrid_distributed(
    mesh: Mesh,
    prob: Hybrid2DProblem,
    cp: ColumnPartition,
    x0: np.ndarray,
    sched: ParallelSGDSchedule | int | None = None,
    b: int | None = None,
    eta: float | None = None,
    tau: int | None = None,
    rounds: int | None = None,
    gram: str | None = None,
    *,
    s: int | None = None,
    loss_problem: Problem | None = None,
):
    """Driver: place data once, run ``sched.rounds`` rounds, gather x.

    Now a thin loop over ``HybridDriver`` — one ``advance`` per
    loss-sampling chunk. Returns ``(x, losses)`` — the same contract as
    the simulated engine's ``run_parallel_sgd``: the full global
    objective is sampled every ``sched.loss_every`` rounds (empty trace
    when 0). Sampling the loss needs the global problem, so pass
    ``loss_problem`` (the repro.api front door wires this
    automatically).

    The legacy signature ``run_hybrid_distributed(mesh, prob, cp, x0,
    s, b, eta, tau, rounds, gram=...)`` still works (returning bare
    ``x``, its old contract) but emits a DeprecationWarning.
    """
    legacy = not isinstance(sched, ParallelSGDSchedule)
    if legacy:
        s_val = sched if sched is not None else s
        if s_val is None:
            raise TypeError(
                "run_hybrid_distributed needs a ParallelSGDSchedule (or legacy s=...)"
            )
        sched = _legacy_schedule(
            prob.p_r, s_val, b, eta, tau, rounds, gram, "run_hybrid_distributed"
        )
    else:
        _reject_scalars_with_schedule(
            "run_hybrid_distributed", s=s, b=b, eta=eta, tau=tau, rounds=rounds, gram=gram
        )
    if sched.loss_every and loss_problem is None:
        raise ValueError("loss_every > 0 needs loss_problem (the global Problem)")

    driver = HybridDriver(mesh, prob, cp, x0, sched, loss_problem=loss_problem)
    losses = []
    chunk = sched.loss_every if sched.loss_every else sched.rounds
    while driver.rounds_done < sched.rounds:
        driver.advance(min(chunk, sched.rounds - driver.rounds_done))
        if sched.loss_every and driver.rounds_done % sched.loss_every == 0:
            losses.append(driver.loss())
    x = driver.gather()
    if legacy:
        return x
    return x, np.asarray(losses, dtype=np.float32)
