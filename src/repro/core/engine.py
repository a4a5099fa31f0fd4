"""The unified parallel-SGD engine — one inner loop for the whole
(p_r, p_c, s, τ) family.

The paper's four algorithms are corners of a single 2D-parallel method:
p_r row teams each run τ inner iterations of s-step SGD (τ/s s-bundles)
between parameter averagings. One engine therefore subsumes them all:

  corner                      schedule
  ------------------------    ------------------------------------
  mini-batch SGD (Alg. 1)     p_r = 1, s = 1, τ = 1
  s-step SGD     (Alg. 3)     p_r = 1, τ = s         (no averaging)
  FedAvg         (Alg. 2)     s = 1                  (no Gram work)
  HybridSGD      (§4.1)       general (p_r, s, τ)

p_c is a *communication* knob, not a numerical one: it decides where
columns live (and hence what is Allreduced — see
repro.core.distributed), never what is computed. The engine here
implements the exact simulated-rank semantics on one device; the
shard_map execution in repro.core.distributed shares this module's
bundle primitive and inner-correction loop, so the two paths cannot
drift.

The s-bundle computation G = tril(Y Yᵀ, -1), v = Y x routes through the
scatter-free Pallas ELL-Gram kernel (repro.kernels.ell_gram) — the old
per-bundle densify into a (sb × n) scratch matrix survives only as the
parity oracle in repro.kernels.ref.

The *loss* is pluggable (repro.core.objective): the engine reads the
residual map u(z) = -ℓ′(z), the pointwise loss, and the optional L2
decay from the problem's ``objective`` — the logistic default routes
through bitwise the same computation as the pre-objective engine, and
λ > 0 is exact via the decay-aware correction recurrence.

*Communication* is explicit (repro.core.comm): the round body issues
its two collectives — the per-bundle row-team (G, v) Allreduce and the
per-round p_r-team average — through the counting collectives (the
identity on this backend's already-global values), so
``engine_comm_ledger`` can capture exactly what a run communicates and
reports can place it next to the Eq. 4 model's predictions.

The round body names its device work with ``jax.named_scope`` —
``bundle_gram``, ``allreduce_gv``, ``corrections``, ``update``,
``param_avg``, and ``loss_probe`` in ``engine_loss`` — the same names
as ``repro.core.distributed``'s, so a profile attributes each op to its
phase on either backend. Scopes are metadata only: the iterates are
unchanged.

repro.core.{sgd,sstep,fedavg,hybrid} re-export configured engine calls
for backwards compatibility.
"""

from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp

from repro.core import comm as comm_plane
from repro.core.comm import COUNTING, CommLedger
from repro.core.objective import LOGISTIC, Objective
from repro.core.problem import Problem, problem_loss
from repro.core.teams import TeamProblem, global_problem
from repro.kernels.ell_gram import ell_gram_and_v, ell_gram_and_v_blocked
from repro.kernels.ref import ell_gram_and_v_ref
from repro.sparse.ell import EllBlock, ell_matvec, ell_rmatvec

GRAM_METHODS = ("pallas", "blocked", "dense")


@dataclasses.dataclass(frozen=True)
class ParallelSGDSchedule:
    """The knobs of the 2D-parallel SGD family (paper Table 3 row
    "HybridSGD"; see docs/paper_map.md for the paper→code map).

    p_r     row teams (FedAvg axis); must equal the TeamProblem's p.
    s       bundle depth — SGD steps fused per Gram round-trip.
    b       mini-batch rows per SGD step (bundle = s·b rows).
    tau     inner iterations between row-team averagings; s | τ.
    eta     step size.
    rounds  outer rounds (total SGD-equivalent iterations = rounds·τ).
    loss_every   sample the full objective every this many rounds
                 (0 = never; the returned loss trace is then empty).
    gram    bundle (G, v) backend: "pallas" (scatter-free ELL kernel,
            the production path on both backends), "blocked" (same
            math as pure jnp — the kernel's XLA twin), "dense" (the
            retired densify oracle,
            kernels/ref.py — tests only; also what the profile-driven
            auto-select picks for heavy-tailed ELL widths).
    bk      column-panel width for the Gram kernels. ``None`` opts into
            the autotuner: the api layer resolves it to the cached
            tuned value at build time (repro.kernels.tune); direct
            engine callers fall back to the static 512.
    bm      optional row tile for the panel expansion (the autotuner's
            second knob). None = single-shot expansion (the original
            path, and bitwise-identical to any bm).
    precision   "fp32" (default — bitwise the pre-precision engine) or
            "bf16": panels and MXU dots run bf16-compute /
            fp32-accumulate, and the per-bundle (G, v) Allreduce ships
            bf16 words (half the β·bytes payload; word counts, and
            hence the Table 2–3 closed forms, are unchanged).
    p_c     column shards. Communication-only: it never changes the
            numerics (kept here so one object describes the full mesh;
            repro.core.distributed consumes it).
    delay   DaSGD-style staleness D (0 = synchronous, the default and
            bitwise-identical to the pre-delay engine). With D ≥ 1 the
            (G, v) collective of bundle t is *issued* at t but
            *consumed* at bundle t+D — the in-flight Allreduce rides a
            D-deep staging buffer and overlaps the next D bundles'
            Gram compute; the last D bundles drain before the round's
            parameter average, so round boundaries (checkpoints,
            chunking, averaging cadence) are unchanged. A numerical
            knob: D ≥ 1 changes the iterates (each bundle's gradient
            is D bundles stale), not the communication volume. Must
            satisfy D ≤ τ/s (the per-round bundle count).
    """

    p_r: int = 1
    s: int = 1
    b: int = 8
    tau: int = 1
    eta: float = 0.05
    rounds: int = 1
    loss_every: int = 0
    gram: str = "pallas"
    bk: int | None = 512
    p_c: int = 1
    delay: int = 0
    bm: int | None = None
    precision: str = "fp32"

    def __post_init__(self):
        # NOTE: s | τ is required by the *solver* (checked in
        # run_parallel_sgd), not here: the NN trainer reuses this object
        # with s = grad-accum microsteps, where the coupling is absent.
        # Likewise η > 0 is a solver-entry check (run_parallel_sgd /
        # make_hybrid_step): the engine internally normalizes schedules
        # to η = 0 for jit-cache keying, so only η < 0 is nonsense here.
        for knob in ("p_r", "s", "b", "tau", "rounds", "p_c"):
            v = getattr(self, knob)
            if v < 1:
                raise ValueError(f"{knob}={v!r} must be a positive integer")
        for knob in ("bk", "bm"):  # None = resolve via the autotuner
            v = getattr(self, knob)
            if v is not None and v < 1:
                raise ValueError(f"{knob}={v!r} must be a positive integer or None")
        if self.precision not in ("fp32", "bf16"):
            raise ValueError(
                f"precision={self.precision!r} must be 'fp32' or 'bf16'"
            )
        if self.loss_every < 0:
            raise ValueError(f"loss_every={self.loss_every} must be ≥ 0")
        if self.delay < 0:
            raise ValueError(f"delay={self.delay} must be ≥ 0")
        if self.eta < 0:
            raise ValueError(f"eta={self.eta} must be ≥ 0")
        if self.loss_every and self.rounds % self.loss_every:
            raise ValueError(
                f"rounds={self.rounds} must be divisible by loss_every={self.loss_every}"
            )
        if self.gram not in GRAM_METHODS:
            raise ValueError(f"gram={self.gram!r} not in {GRAM_METHODS}")

    # ---- the paper's corners, by name ----

    @classmethod
    def mb_sgd(cls, b: int, eta: float, iters: int, loss_every: int = 0, **kw):
        """Algorithm 1: synchronous mini-batch SGD."""
        return cls(p_r=1, s=1, b=b, tau=1, eta=eta, rounds=iters, loss_every=loss_every, **kw)

    @classmethod
    def sstep(cls, s: int, b: int, eta: float, iters: int, loss_every: int = 0, **kw):
        """Algorithm 3: 1D s-step SGD — iters/s bundles, one bundle per
        round, no averaging (p_r = 1).

        ``loss_every`` counts SGD-equivalent iterations (like ``iters``)
        and must be a multiple of s: one round = s iterations, so any
        other cadence cannot be sampled exactly.
        """
        if iters % s:
            raise ValueError(f"iters={iters} must be divisible by s={s}")
        if loss_every and loss_every % s:
            raise ValueError(
                f"loss_every={loss_every} must be divisible by s={s}: the loss is "
                f"sampled on round (= s-iteration) boundaries"
            )
        return cls(
            p_r=1, s=s, b=b, tau=s, eta=eta, rounds=iters // s,
            loss_every=loss_every // s, **kw,
        )

    @classmethod
    def fedavg(cls, p: int, b: int, eta: float, tau: int, rounds: int,
               loss_every: int = 0, **kw):
        """Algorithm 2: FedAvg / local SGD — s = 1, so no Gram work."""
        return cls(p_r=p, s=1, b=b, tau=tau, eta=eta, rounds=rounds,
                   loss_every=loss_every, **kw)

    @classmethod
    def hybrid(cls, p_r: int, s: int, b: int, eta: float, tau: int, rounds: int,
               loss_every: int = 0, **kw):
        """HybridSGD (§4.1): the general 2D point."""
        return cls(p_r=p_r, s=s, b=b, tau=tau, eta=eta, rounds=rounds,
                   loss_every=loss_every, **kw)


def bundle_gram_v(
    indices, values, x, n: int, *, gram: str = "pallas", bk: int | None = 512,
    bm: int | None = None, precision: str = "fp32",
):
    """The shared s-bundle primitive: local (G, v) = (tril(YYᵀ,-1), Yx)
    for the ELL bundle Y, without densifying Y to (sb, n) in HBM.

    Under column partitioning each shard computes its partial (G, v)
    with this same function and the row-team Allreduce (psum over
    "cols") sums them — tril commutes with the sum, so the simulated
    and distributed paths share one primitive.

    ``bk=None`` (the autotune sentinel, normally resolved at build time
    by the api layer) falls back to the static 512 here. The dense
    oracle has no panels, so bk/bm/precision do not apply to it — its
    (G, v) is always the fp32 reference."""
    bk = 512 if bk is None else bk
    if gram == "pallas":
        return ell_gram_and_v(
            indices, values, x, n=n, bk=bk, bm=bm, precision=precision
        )
    if gram == "blocked":
        return ell_gram_and_v_blocked(
            indices, values, x, n=n, bk=bk, bm=bm, precision=precision
        )
    if gram == "dense":
        return ell_gram_and_v_ref(indices, values, x, n)
    raise ValueError(f"gram={gram!r} not in {GRAM_METHODS}")


def wire_gv(tree, precision: str):
    """Cast a (G, v) payload to its on-wire dtype: bf16 under the bf16
    precision knob (half the collective's bytes), untouched at fp32 —
    both backends cast at the same point, so parity holds."""
    if precision != "bf16":
        return tree
    return jax.tree_util.tree_map(lambda t: t.astype(jnp.bfloat16), tree)


def unwire_gv(tree, precision: str, dtype=jnp.float32):
    """Undo ``wire_gv`` after the collective: corrections and updates
    accumulate in ``dtype`` (f32) regardless of the wire dtype."""
    if precision != "bf16":
        return tree
    return jax.tree_util.tree_map(lambda t: t.astype(dtype), tree)


def inner_corrections(
    g, v, s: int, b: int, eta: float, objective: Objective = LOGISTIC
) -> jnp.ndarray:
    """Algorithm 3 lines 9-14: the s deferred-update corrections under
    any registered objective.

    Unregularized (objective.l2 == 0 — special-cased at trace time so
    the default path is bitwise-unchanged):

        u_j = residual(v_j + (η/b) Σ_{l<j} G_{jl} u_l)

    G is strictly lower so in-block terms multiply zeros. With L2 decay
    λ > 0 and ρ = 1 - ηλ the exact unrolled recurrence is

        z_j = ρ^j·v_j + (η/b) Σ_{l<j} ρ^{j-1-l}·G_{jl}·u_l

    implemented by carrying the ρ-rescaled residual vector: after step
    j the carry holds [ρ^{j-l}·u_l]_{l≤j}, so the returned vector is
    exactly the ρ^{s-1-l}-weighted u the caller's Yᵀ apply (and ρ^s·x
    decay-fold) needs. Shared by the engine and the shard_map path (and
    mirrored VMEM-resident by repro.kernels.sstep_inner for the
    logistic default). The G·u products run at full f32 precision on
    every chip, whatever the schedule's precision."""
    lam = objective.l2

    if lam == 0.0:

        def inner(u_acc, j):
            zj = jax.lax.dynamic_slice_in_dim(v, j * b, b) + (eta / b) * jnp.dot(
                jax.lax.dynamic_slice_in_dim(g, j * b, b, axis=0), u_acc,
                precision=jax.lax.Precision.HIGHEST,
            )
            uj = objective.residual(zj)
            return jax.lax.dynamic_update_slice_in_dim(u_acc, uj, j * b, axis=0), None

        u, _ = jax.lax.scan(inner, jnp.zeros(s * b, v.dtype), jnp.arange(s))
        return u

    rho = jnp.asarray(1.0 - eta * lam, v.dtype)

    def inner_decay(carry, j):
        u_acc, rho_j = carry  # u_acc_l = ρ^{j-1-l}·u_l (l < j); rho_j = ρ^j
        zj = rho_j * jax.lax.dynamic_slice_in_dim(v, j * b, b) + (eta / b) * jnp.dot(
            jax.lax.dynamic_slice_in_dim(g, j * b, b, axis=0), u_acc,
            precision=jax.lax.Precision.HIGHEST,
        )
        uj = objective.residual(zj)
        u_acc = jax.lax.dynamic_update_slice_in_dim(rho * u_acc, uj, j * b, axis=0)
        return (u_acc, rho_j * rho), None

    carry0 = (jnp.zeros(s * b, v.dtype), jnp.ones((), v.dtype))
    (u, _), _ = jax.lax.scan(inner_decay, carry0, jnp.arange(s))
    return u


def delayed_bundle_scan(x, *, slice_bundle, bundles: int, n: int,
                        sched: ParallelSGDSchedule, eta,
                        objective: Objective = LOGISTIC,
                        comm=COUNTING):
    """The delay-D software pipeline over one round's τ/s bundles —
    the shared round-body core of both backends when ``sched.delay ≥ 1``
    (DaSGD, arXiv:2006.00441).

    At step t the body computes bundle t's local (G, v) at the current
    (D-bundle-stale) iterate and *issues* its row-team Allreduce
    (``comm.issue_allreduce_cols``); the staged result rides a D-deep
    FIFO in the scan carry and is *consumed* (``comm.await_allreduce``
    → corrections → weight update) at step t+D — so on a mesh the
    in-flight psum has the next D bundles' Gram compute to hide behind
    (the data dependency lands D iterations later, which is the window
    XLA's scheduler overlaps). After the main scan the last D staged
    entries drain synchronously, *before* the caller's parameter
    average: every round boundary carries only ``x``, so chunking,
    checkpointing, and the τ-cadence averaging are exactly where the
    synchronous schedule puts them.

    Warmup steps (t < D) consume the zero-initialized buffer and are
    masked out with ``jnp.where`` rather than ``lax.cond`` — no
    collectives inside conditionals (shard_map-safe), deterministic
    wasted work on D dummy entries per round. Exactly ``bundles``
    updates (and, under L2, exactly ``bundles`` decay folds) are
    applied per round, same as the synchronous path.

    At D = 0 the FIFO is empty and each bundle is consumed as it is
    issued: the synchronous order, through this same code.

    ``slice_bundle(t) -> (idx, val)`` supplies the (s·b, width) ELL
    bundle; ``comm`` is COUNTING on the simulated engine (identity —
    the staged value is already globally reduced) and MESH/TIMED under
    shard_map."""
    s, b = sched.s, sched.b
    sb = s * b
    d = sched.delay
    lam = objective.l2

    def compute_issue(x, t):
        idx, val = slice_bundle(t)
        with jax.named_scope("bundle_gram"):
            g, v = bundle_gram_v(idx, val, x, n, gram=sched.gram, bk=sched.bk,
                                 bm=sched.bm, precision=sched.precision)
        # issued here, consumed D bundles later (the s = 1 corner
        # stages the full (G, v) too — its distributed twin psums the
        # dense block either way, so counted payloads stay pinned).
        # Under bf16 the staged payload is the wire dtype: the FIFO
        # holds exactly what the in-flight Allreduce carries.
        with jax.named_scope("allreduce_gv"):
            g, v = comm.issue_allreduce_cols(
                wire_gv((g, v), sched.precision), calls_per_round=bundles
            )
        return idx, val, g, v

    def consume_apply(x, entry, live):
        idx, val, g, v = entry
        with jax.named_scope("allreduce_gv"):
            g, v = comm.await_allreduce((g, v))
            g, v = unwire_gv((g, v), sched.precision)
        with jax.named_scope("corrections"):
            u = inner_corrections(g, v, s, b, eta, objective)
        with jax.named_scope("update"):
            blk = EllBlock(indices=idx, values=val, n=n)
            upd = (eta / b) * ell_rmatvec(blk, u).astype(x.dtype)
            if lam == 0.0:
                return jnp.where(live, x + upd, x)
            rho_s = jnp.asarray(1.0 - eta * lam, x.dtype) ** s
            return jnp.where(live, rho_s * x + upd, x)

    # the D-deep staging FIFO: buf[0] is the oldest in-flight bundle.
    # Shapes/dtypes are written out by hand (an eval_shape through
    # compute_issue would double-record the collective under the
    # ledger's capture recorder).
    idx0, val0 = slice_bundle(0)
    width = idx0.shape[-1]
    gv_dtype = jnp.result_type(val0.dtype, x.dtype)
    if sched.precision == "bf16":
        gv_dtype = jnp.bfloat16  # the FIFO stages the wire payload
    buf = (
        jnp.zeros((d, sb, width), idx0.dtype),
        jnp.zeros((d, sb, width), val0.dtype),
        jnp.zeros((d, sb, sb), gv_dtype),
        jnp.zeros((d, sb), gv_dtype),
    )

    def body(carry, t):
        x, buf = carry
        new = compute_issue(x, t)
        if not d:  # the degenerate pipeline: each bundle consumed as issued
            return (consume_apply(x, new, True), buf), None
        oldest = jax.tree_util.tree_map(lambda a: a[0], buf)
        buf = jax.tree_util.tree_map(
            lambda a, e: jnp.concatenate([a[1:], e[None]], axis=0), buf, new
        )
        x = consume_apply(x, oldest, t >= d)
        return (x, buf), None

    (x, buf), _ = jax.lax.scan(body, (x, buf), jnp.arange(bundles))

    def drain(x, j):
        entry = jax.tree_util.tree_map(
            lambda a: jax.lax.dynamic_index_in_dim(a, j, keepdims=False), buf
        )
        return consume_apply(x, entry, jnp.bool_(True)), None

    if d:
        x, _ = jax.lax.scan(drain, x, jnp.arange(d))
    return x


def _team_inner_iterations(indices, values, n: int, x, round_idx, eta,
                           sched: ParallelSGDSchedule,
                           objective: Objective = LOGISTIC):
    """τ inner iterations (= τ/s s-bundles) on one row team's ELL rows.
    ``eta`` is a traced scalar (sweep-friendly: no recompile per value);
    ``objective`` supplies the residual and (when l2 > 0) the decay
    fold — exact on every corner, since the s-bundle recurrence in
    ``inner_corrections`` is decay-aware."""
    m_local = indices.shape[0]
    bundles = sched.tau // sched.s
    s, b = sched.s, sched.b
    sb = s * b
    lam = objective.l2

    if sched.delay:
        def slice_bundle(t):
            k0 = round_idx * bundles + t
            start = (k0 * sb) % m_local
            idx = jax.lax.dynamic_slice_in_dim(indices, start, sb, axis=0)
            val = jax.lax.dynamic_slice_in_dim(values, start, sb, axis=0)
            return idx, val

        return delayed_bundle_scan(
            x, slice_bundle=slice_bundle, bundles=bundles, n=n, sched=sched,
            eta=eta, objective=objective, comm=COUNTING,
        )

    def bundle_step(x, t):
        k0 = round_idx * bundles + t
        start = (k0 * sb) % m_local
        idx = jax.lax.dynamic_slice_in_dim(indices, start, sb, axis=0)
        val = jax.lax.dynamic_slice_in_dim(values, start, sb, axis=0)
        bundle = EllBlock(indices=idx, values=val, n=n)
        if s == 1:
            # FedAvg/MB-SGD corner: the Gram is empty (no deferred
            # updates to correct) — one SpMV + one SpMVᵀ, exactly
            # Algorithm 2's local step. The simulated body only
            # materializes v = Yx, but the distributed corner psums the
            # full (G, v) bundle even at s = 1 (G rides the wire though
            # numerically unused), so the counted payload is pinned to
            # the same sb² + sb words.
            with jax.named_scope("bundle_gram"):
                yx = ell_matvec(bundle, x)
            with jax.named_scope("allreduce_gv"):
                yx = COUNTING.allreduce_cols(
                    wire_gv(yx, sched.precision),
                    calls_per_round=bundles,
                    words_per_call=sb * sb + sb,
                )
                yx = unwire_gv(yx, sched.precision, x.dtype)
            with jax.named_scope("corrections"):
                u = objective.residual(yx)
        else:
            with jax.named_scope("bundle_gram"):
                g, v = bundle_gram_v(idx, val, x, n, gram=sched.gram, bk=sched.bk,
                                     bm=sched.bm, precision=sched.precision)
            # row-team Allreduce of the bundle (G, v) — identity here
            # (the simulated rank computes the full reduction), the
            # recorded payload when the round body is captured.
            with jax.named_scope("allreduce_gv"):
                g, v = COUNTING.allreduce_cols(
                    wire_gv((g, v), sched.precision), calls_per_round=bundles
                )
                g, v = unwire_gv((g, v), sched.precision)
            with jax.named_scope("corrections"):
                u = inner_corrections(g, v, s, b, eta, objective)
        with jax.named_scope("update"):
            if lam == 0.0:
                return x + (eta / b) * ell_rmatvec(bundle, u).astype(x.dtype), None
            # decay-folded update: x_s = ρ^s·x + (η/b)·Yᵀ·[ρ^{s-1-l}·u_l]
            # (inner_corrections already returns the ρ-weighted u; for
            # s = 1 the weight is ρ^0 = 1). Exact on the s = 1 corners.
            rho_s = jnp.asarray(1.0 - eta * lam, x.dtype) ** s
            return rho_s * x + (eta / b) * ell_rmatvec(bundle, u).astype(x.dtype), None

    x, _ = jax.lax.scan(bundle_step, x, jnp.arange(bundles))
    return x


def _one_round(tp, x, r, eta, sched):
    """One outer round: τ inner iterations per row team + the p_r-team
    average. The single shared round body — the monolithic scan and the
    chunked session path both close over exactly this function, so the
    two cannot drift (and stay bitwise-identical)."""

    def team(args):
        idx, val = args
        return _team_inner_iterations(idx, val, tp.n, x, r, eta, sched, tp.objective)

    if sched.s == 1 and not sched.delay:
        # FedAvg/MB-SGD corner: per-team working set is one (b, w)
        # batch — run all teams batched (the old run_fedavg vmap).
        # The delayed path materializes the full (G, v) even at s = 1
        # (its distributed twin psums the dense block), so it takes the
        # sequential branch like every Gram-bearing schedule.
        xs = jax.vmap(team)((tp.indices, tp.values))
    else:
        # lax.map (not vmap): teams run sequentially on one device,
        # bounding peak memory at one team's bundle working set.
        xs = jax.lax.map(team, (tp.indices, tp.values))
    # column Allreduce: the p_r-team average, issued through the comm
    # plane (numerically the same stacked mean; the per-rank payload is
    # the balanced ⌈n/p_c⌉-word weight shard — Table 3's sync column).
    with jax.named_scope("param_avg"):
        return COUNTING.allmean_teams(xs, words_per_call=-(-tp.n // sched.p_c))


@partial(jax.jit, static_argnames=("sched",))
def _run_engine(tp, x0, eta, sched):
    gp = global_problem(tp)

    chunk = sched.loss_every if sched.loss_every else sched.rounds
    n_chunks = max(sched.rounds // chunk, 1)

    def one_round(x, r):
        return _one_round(tp, x, r, eta, sched), None

    def outer(x, c):
        x, _ = jax.lax.scan(one_round, x, c * chunk + jnp.arange(chunk))
        return x, problem_loss(gp, x)

    x, losses = jax.lax.scan(outer, x0, jnp.arange(n_chunks))
    if not sched.loss_every:
        losses = jnp.zeros((0,), losses.dtype)
    return x, losses


# ---- round-incremental (chunked) execution --------------------------
#
# The Session front door (repro.api.session) advances the engine k
# rounds at a time instead of one scan over all of them. The chunk
# entry point below is jitted with a *normalized* schedule (loop-shape
# knobs zeroed) and a static chunk length, so one compiled executable
# is shared across chunks, across sessions, and across schedules that
# differ only in (rounds, loss_every, eta) — the carry in/out is just
# the weight vector, and the round index arrives as a traced operand so
# chunk r0..r0+k matches rounds r0..r0+k of the monolithic scan
# bitwise.


def check_delay(sched: ParallelSGDSchedule) -> None:
    """Solver-entry validation of the delay knob: the staging buffer
    drains inside the round, so D cannot exceed the per-round bundle
    count (entries past it would never be issued)."""
    bundles = sched.tau // sched.s
    if sched.delay > bundles:
        raise ValueError(
            f"delay={sched.delay} must be ≤ τ/s={bundles} (the per-round "
            f"bundle count): the staging buffer drains before each round's "
            f"parameter average"
        )


def _normalize_for_chunk(sched: ParallelSGDSchedule) -> ParallelSGDSchedule:
    """Zero every knob the per-round math does not read (η is traced;
    rounds/loss_every belong to the driver; p_c is communication-only)
    so the jit cache keys only on what changes the computation.
    ``delay`` is *kept*: D ≥ 1 pipelines the bundle loop and changes
    the iterates, so it must key the compiled round body."""
    return dataclasses.replace(sched, eta=0.0, rounds=1, loss_every=0, p_c=1)


@partial(jax.jit, static_argnames=("sched", "k"))
def _engine_chunk(tp, x, r0, eta, sched, k):
    """Advance rounds r0 .. r0+k-1 from carry ``x`` (chunk of the same
    scan the monolithic path runs — identical per-round graph)."""

    def one_round(x, r):
        return _one_round(tp, x, r, eta, sched), None

    x, _ = jax.lax.scan(one_round, x, r0 + jnp.arange(k))
    return x


def lower_engine_chunk(tp: TeamProblem, x: jnp.ndarray, k: int,
                       sched: ParallelSGDSchedule) -> jax.stages.Lowered:
    """The k-round program ``run_engine_chunk`` dispatches, lowered but
    not run — to read its compiled HLO or memory analysis."""
    eta = jnp.asarray(sched.eta, x.dtype)
    return _engine_chunk.lower(
        tp, x, jnp.int32(0), eta, _normalize_for_chunk(sched), int(k)
    )


@jax.jit
def engine_loss(gp, x):
    """The session's loss probe — same ``problem_loss`` (under ``gp``'s
    objective) the monolithic scan samples at chunk boundaries."""
    with jax.named_scope("loss_probe"):
        return problem_loss(gp, x)


def run_engine_chunk(
    tp: TeamProblem,
    x: jnp.ndarray,
    round_offset: int,
    k: int,
    sched: ParallelSGDSchedule,
) -> jnp.ndarray:
    """Run ``k`` rounds starting at global round ``round_offset`` and
    return the new weights (device-resident; no host sync).

    This is the carry-in/carry-out primitive under ``repro.api.Session``
    — calling it with offsets 0, k, 2k, … reproduces
    ``run_parallel_sgd``'s iterate sequence bitwise, because both paths
    scan the same ``_one_round`` body over the same round indices."""
    if sched.eta <= 0:
        raise ValueError(f"eta={sched.eta} must be > 0 to run the solver")
    check_delay(sched)
    eta = jnp.asarray(sched.eta, x.dtype)
    return _engine_chunk(
        tp, x, jnp.int32(round_offset), eta, _normalize_for_chunk(sched), int(k)
    )


def run_parallel_sgd(
    tp: TeamProblem,
    x0: jnp.ndarray,
    sched: ParallelSGDSchedule,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Run the full 2D family point described by ``sched`` on the
    stacked row teams ``tp`` (exact simulated-rank semantics).

    Each of ``sched.rounds`` outer rounds = τ inner s-step iterations
    per row team + one averaging across the p_r teams (identity when
    p_r = 1). Returns (x, losses) with the full global objective
    sampled every ``loss_every`` rounds.

    η enters the compiled computation as a traced operand, so an
    η-sweep over otherwise-identical schedules reuses one executable.
    """
    if sched.eta <= 0:
        raise ValueError(f"eta={sched.eta} must be > 0 to run the solver")
    if sched.tau % sched.s:
        raise ValueError(
            f"tau={sched.tau} must be divisible by s={sched.s} (paper requires s ≤ τ)"
        )
    check_delay(sched)
    if tp.p != sched.p_r:
        raise ValueError(f"TeamProblem has p={tp.p} teams but schedule p_r={sched.p_r}")
    if tp.rows_local % (sched.s * sched.b):
        raise ValueError(
            f"local rows {tp.rows_local} must be divisible by s·b={sched.s * sched.b}"
        )
    eta = jnp.asarray(sched.eta, x0.dtype)
    return _run_engine(tp, x0, eta, dataclasses.replace(sched, eta=0.0))


def engine_comm_ledger(
    sched: ParallelSGDSchedule,
    n: int,
    tp: TeamProblem | None = None,
    width: int = 2,
) -> CommLedger:
    """The simulated engine's per-rank ``CommLedger``: every collective
    the round body issues, captured by tracing ``_one_round`` abstractly
    (``jax.eval_shape`` — no FLOPs run, no dataset needed).

    With ``tp`` given the capture traces the real problem's shapes;
    without it a shape-only stand-in is synthesized (``width`` nonzeros
    per row, one bundle of rows per team) — the communication structure
    depends only on the schedule and n, never on the data, so both
    forms record identical rates. Spans come from the schedule's
    (p_r, p_c): the ledger of the simulated run *is* the ledger of the
    mesh execution it simulates (tested against
    ``repro.core.distributed.hybrid_comm_ledger``)."""
    if tp is None:
        sb = sched.s * sched.b
        tp = TeamProblem(
            indices=jax.ShapeDtypeStruct((sched.p_r, sb, width), jnp.int32),
            values=jax.ShapeDtypeStruct((sched.p_r, sb, width), jnp.float32),
            rows_valid=jax.ShapeDtypeStruct((sched.p_r, sb), jnp.bool_),
            p=sched.p_r,
            m=sched.p_r * sb,
            n=n,
        )
    rates = comm_plane.capture_rates(
        partial(_one_round, sched=sched),
        tp,
        jax.ShapeDtypeStruct((n,), jnp.float32),
        jax.ShapeDtypeStruct((), jnp.int32),
        jax.ShapeDtypeStruct((), jnp.float32),
        spans={"cols": sched.p_c, "rows": sched.p_r},
    )
    return CommLedger(rates=rates, delay=sched.delay)


def engine_phase_probes(tp: TeamProblem, sched: ParallelSGDSchedule) -> dict:
    """Jitted per-phase probes for the simulated backend — the §6.5
    phase split (compute vs. the two comm phases) measured on the round
    body's real payload shapes, *outside* the training step (its
    compiled numerics are never touched).

    Returns ``{phase: (fn, args, calls_per_round)}``. On this backend
    the Gram "allreduce" is the identity (the simulated ranks already
    hold globally reduced values) and the parameter average is a real
    ``jnp.mean`` over the stacked team iterates — so the probed comm
    phases measure what the one-device simulation actually pays, not
    what a mesh would."""
    sb = sched.s * sched.b
    bundles = sched.tau // sched.s
    m_local = int(tp.indices.shape[1])
    reps = -(-sb // m_local)
    bi = jnp.tile(tp.indices[0], (reps, 1))[:sb]
    bv = jnp.tile(tp.values[0], (reps, 1))[:sb]
    x0 = jnp.zeros((tp.n,), jnp.float32)
    compute = jax.jit(
        lambda i, v, x: bundle_gram_v(
            i, v, x, tp.n, gram=sched.gram, bk=sched.bk, bm=sched.bm,
            precision=sched.precision,
        )
    )
    g0 = jnp.zeros((sb, sb), jnp.float32)
    v0 = jnp.zeros((sb,), jnp.float32)
    ident = jax.jit(lambda g, v: (g + 0.0, v + 0.0))
    xs = jnp.zeros((sched.p_r, tp.n), jnp.float32)
    avg = jax.jit(lambda t: jnp.mean(t, axis=0))
    return {
        "bundle_compute": (compute, (bi, bv, x0), bundles),
        "allreduce_gv": (ident, (g0, v0), bundles),
        "param_avg": (avg, (xs,), 1),
    }


def single_team(problem: Problem) -> TeamProblem:
    """View a Problem as a 1-team TeamProblem (p_r = 1 corners); the
    objective rides along."""
    return TeamProblem(
        indices=problem.ya.indices[None],
        values=problem.ya.values[None],
        rows_valid=problem.rows_valid[None],
        p=1,
        m=problem.m,
        n=problem.n,
        objective=problem.objective,
    )
