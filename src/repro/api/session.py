"""Session — the round-incremental execution lifecycle.

``run(spec)`` used to be one opaque block: build, scan every round,
report. A ``Session`` opens that loop up at round granularity without
changing a single iterate:

    sess = Session(spec)                 # plan + build once
    while not sess.done:
        ev = sess.step_rounds(4)         # advance 4 rounds
        print(ev.rounds_done, ev.loss)   # weights-so-far, loss sample
        sess.save("ckpt/run1")           # resumable at any boundary
    report = sess.report()

    sess2 = Session.restore("ckpt/run1") # later / elsewhere
    report2 = sess2.run()                # finish under the StopPolicy

Both backends are chunkable underneath: the simulated engine advances
through ``repro.core.engine.run_engine_chunk`` (one jitted executable
shared across chunks and sessions — the carry is just the weight
vector, the round offset is traced) and the shard_map backend through
``repro.core.distributed.HybridDriver`` (device-resident donated
carry). Chunked execution reproduces the monolithic single-scan path
bitwise — both scan the same per-round body over the same global round
indices — which is what makes save/restore and early stopping safe to
use in time-to-loss experiments (tests/test_session.py enforces it).

``run()`` is a thin loop over ``step_rounds`` that honors the spec's
``StopPolicy`` (``target_loss`` / ``max_seconds`` / ``max_rounds``) —
the paper's §7.5 time-to-loss protocol as a first-class stop condition
instead of post-hoc arithmetic on a finished trace.
"""

from __future__ import annotations

import dataclasses
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from repro.api.plan import Plan, plan, replan_mesh
from repro.api.report import RunReport, modeled_comm_words
from repro.api.spec import ExperimentSpec, MeshSpec
from repro.core import faults
from repro.core.comm import MESH, TIMED, CommLedger, time_phase
from repro.core.engine import (
    engine_comm_ledger,
    engine_loss,
    lower_engine_chunk,
    run_engine_chunk,
)
from repro.core.distributed import HybridDriver
from repro.core.problem import problem_loss
from repro.core.teams import global_problem
from repro.obs import trace as obs_trace
from repro.train.checkpoint import (
    SessionCheckpoint,
    load_session_checkpoint,
    save_session_checkpoint,
)

__all__ = ["RoundEvent", "Session", "autosave_base"]


def autosave_base(directory: str | Path, spec: ExperimentSpec) -> Path:
    """Where a session autosaves inside ``directory`` — keyed by the
    spec's content hash (dot-free stem: the checkpoint layer appends
    .npz/.json via with_suffix)."""
    return Path(directory) / f"autosave-{spec.content_hash()}"


@dataclasses.dataclass
class RoundEvent:
    """What one ``step_rounds`` call observed.

    rounds_done     total rounds completed so far (cumulative).
    x               weights after those rounds (global (n,) on host).
    loss            the most recent full-objective sample taken during
                    this step, or None if no sampling boundary was
                    crossed (``schedule.loss_every`` semantics).
    wall_time_s     cumulative solver wall time.
    compile_time_s  wall accrued to first chunks (jit compile + one
                    chunk, summed across restores — each process
                    recompiles) — the split ``RunReport`` carries.
    comm_words      cumulative modeled per-rank comm volume for the
                    rounds completed (Table 3 payloads).
    ledger          snapshot of the run's CommLedger at this boundary:
                    the *counted* collectives (and, timed runs, the
                    measured per-round seconds) for the rounds done.
    stop            StopPolicy verdict at this boundary: None, or one of
                    "target_loss" / "max_seconds" / "max_rounds" /
                    "rounds" (schedule budget exhausted).
    """

    rounds_done: int
    x: np.ndarray
    loss: float | None
    wall_time_s: float
    compile_time_s: float
    comm_words: dict[str, float]
    ledger: CommLedger | None = None
    stop: str | None = None


class Session:
    """An open, resumable run of one ``ExperimentSpec``.

    Construction plans the spec (autotune included — ``self.spec`` is
    the spec as executed) and builds the problem once; every
    ``step_rounds`` call after that advances the same device-resident
    carry. The session is the single source of truth for run state:
    rounds done, loss trace, wall/compile time — ``report()`` is a pure
    read of it.
    """

    def __init__(
        self,
        spec: ExperimentSpec,
        x0: np.ndarray | None = None,
        autosave_dir: str | Path | None = None,
    ):
        # imported here: repro.api.run imports Session for its thin
        # run() wrapper, so the build machinery import must be lazy.
        from repro.api.run import build_problem, _make_device_mesh

        self.autosave_dir = Path(autosave_dir) if autosave_dir is not None else None
        self.input_spec = spec          # pre-plan (what checkpoints key on)
        self._plan: Plan = plan(spec)
        self.spec = self._plan.spec     # post-autotune (what executes)
        autotuned_panels = self.spec.schedule.bk is None
        if autotuned_panels:
            # bk=None opted into the kernel autotuner: resolve to the
            # cached (or freshly tuned) panel shape before anything
            # compiles. Checkpoints still key on input_spec, so the
            # tuned value never moves a content hash.
            from repro.api.spec import dataset_stats
            from repro.kernels import tune

            profile = tune.PanelProfile.from_stats(
                dataset_stats(self.spec.dataset),
                self.spec.schedule,
                self.spec.mesh.p_c,
            )
            bk, bm = tune.resolve_panel(profile)
            sched = dataclasses.replace(
                self.spec.schedule,
                bk=bk,
                bm=self.spec.schedule.bm if self.spec.schedule.bm is not None else bm,
            )
            self.spec = dataclasses.replace(self.spec, schedule=sched)
        self.bundle = build_problem(self.spec)
        if autotuned_panels:
            # autotune opt-in also owns the gram-path choice: a
            # heavy-tailed ELL width (w ≫ s·b) flips the bundle build
            # to the dense oracle (logged once in tune).
            from repro.kernels import tune

            sched = self.spec.schedule
            built = self.bundle.team if self.bundle.team is not None else self.bundle.prob2d
            width = int(built.indices.shape[-1])
            gram = tune.select_gram_path(width, sched.s * sched.b, sched.gram)
            if gram != sched.gram:
                self.spec = dataclasses.replace(
                    self.spec, schedule=dataclasses.replace(sched, gram=gram)
                )
        n = self.bundle.dataset.A.n
        x0 = np.zeros(n, np.float32) if x0 is None else np.asarray(x0, np.float32)

        self.rounds_done = 0
        self.losses: list[float] = []
        self.wall_time_s = 0.0
        self.compile_time_s = 0.0
        self.stop_reason: str | None = None
        # the next chunk's wall is accrued to compile_time_s (set again
        # on restore: a fresh process recompiles, and that wall must not
        # masquerade as steady-state solve time)
        self._first_chunk_pending = True

        if self.spec.mesh.backend == "simulated":
            self._driver = None
            self._x = jnp.asarray(x0)
            self._gp = global_problem(self.bundle.team)
            # the counted-comm ledger: the round body's collectives,
            # captured abstractly from the problem actually built
            self.ledger = engine_comm_ledger(
                self.spec.schedule, n, tp=self.bundle.team
            )
        else:
            mesh = _make_device_mesh(self.spec.mesh.p_r, self.spec.mesh.p_c)
            self._driver = HybridDriver(
                mesh,
                self.bundle.prob2d,
                self.bundle.cp,
                x0,
                self.spec.schedule,
                loss_problem=self.bundle.global_problem,
                comm=TIMED if self.spec.comm_timing else MESH,
            )
            self._x = None
            self._gp = None
            self.ledger = self._driver.ledger  # driver commits rounds

    # ---- state probes ----

    @property
    def total_rounds(self) -> int:
        """The schedule's round budget (the StopPolicy may end sooner)."""
        return self.spec.schedule.rounds

    @property
    def done(self) -> bool:
        return self.rounds_done >= self.total_rounds or self.stop_reason is not None

    def current_x(self) -> np.ndarray:
        """Current global weights (host copy; blocks on pending work)."""
        if self._driver is not None:
            return self._driver.gather()
        return np.asarray(self._x)

    def compile_round(self) -> jax.stages.Compiled:
        """The compiled program that advances this session one round on
        its backend — ``as_text()`` shows which kernels and collectives
        the round runs. Compiling it leaves the session's state as is."""
        if self._driver is not None:
            return self._driver.lower_step().compile()
        return lower_engine_chunk(
            self.bundle.team, self._x, 1, self.spec.schedule
        ).compile()

    # ---- the incremental core ----

    def _advance(self, k: int) -> None:
        """Run k rounds on the backend carry (no loss sampling)."""
        if self._driver is not None:
            self._driver.advance(k)  # commits (and, timed, measures) rounds
        elif self.spec.comm_timing:
            # timed collectives on the simulated engine: advance one
            # round at a time, blocking per round, so the ledger gets a
            # per-round wall — the iterate sequence is unchanged (chunked
            # execution is bitwise-identical at any chunk size).
            for i in range(int(k)):
                t0 = time.perf_counter()
                self._x = run_engine_chunk(
                    self.bundle.team, self._x, self.rounds_done + i, 1,
                    self.spec.schedule,
                )
                jax.block_until_ready(self._x)
                self.ledger.add_round_seconds(time.perf_counter() - t0)
            self.ledger.add_rounds(k)
        else:
            self._x = run_engine_chunk(
                self.bundle.team, self._x, self.rounds_done, k, self.spec.schedule
            )
            self.ledger.add_rounds(k)
        self.rounds_done += k

    def _sync(self) -> None:
        """Block on the backend carry without a host copy."""
        if self._driver is not None:
            self._driver.sync()
        else:
            jax.block_until_ready(self._x)

    def _traced_advance(self, sub: int, first: bool, stream_batch=None) -> None:
        """One sub-chunk through the tracing seam: a ``round`` (the
        first chunk: ``compile``) span around exactly the bare advance.
        In a profile its length is the host's dispatch time. With a
        recorder installed the span also blocks at its edge, so the
        recorded wall covers the dispatched work (observer effect on
        timing only — the compiled numerics are untouched)."""
        with obs_trace.span(
            "compile" if first else "round",
            name=f"rounds[{self.rounds_done}+{sub}]",
            start_round=self.rounds_done,
            rounds=sub,
        ):
            if stream_batch is not None:
                self._advance_stream(stream_batch)
            else:
                self._advance(sub)
            if obs_trace.active() is not None:
                self._sync()

    def _measure_phases(self) -> None:
        """Populate ``ledger.phase_seconds`` (→ ``exposed_comm_s``) once
        per timed run: the §6.5 phase split, measured by separate jitted
        probes over the round's real payload shapes — the training step
        itself is never split or re-traced. Runs outside the wall/compile
        accounting windows."""
        from repro.core.engine import engine_phase_probes

        if self._driver is not None:
            probes = self._driver.phase_probes()
        else:
            probes = engine_phase_probes(self.bundle.team, self.spec.schedule)
        self.ledger.set_phase_seconds({
            name: time_phase(fn, *args) * calls
            for name, (fn, args, calls) in probes.items()
        })

    def _sample_loss(self) -> float:
        with obs_trace.span("probe"):
            if self._driver is not None:
                return self._driver.loss()
            return float(engine_loss(self._gp, self._x))

    def _copy_x(self) -> np.ndarray:
        """``current_x()`` through the tracing seam: the host copy of the
        weights a ``RoundEvent`` or a checkpoint carries."""
        with obs_trace.span("copy_x", bytes=4 * self.bundle.dataset.A.n):
            return self.current_x()

    def step_rounds(self, k: int | None = None) -> RoundEvent:
        """Advance up to ``k`` rounds (default: to the next loss-sampling
        boundary, or all remaining rounds when ``loss_every`` is 0) and
        return what happened.

        Internally the advance is split at every ``loss_every`` boundary
        so the full objective is sampled exactly where the monolithic
        scan sampled it — arbitrary ``k`` never changes the trace, only
        how often control returns to the caller. The StopPolicy is
        evaluated at every boundary, so a step spanning several may end
        early (``RoundEvent.stop`` says why).
        """
        with obs_trace.span("step", step_num=self.rounds_done):
            return self._step_rounds(k)

    def _step_rounds(self, k: int | None) -> RoundEvent:
        if self.done:
            raise RuntimeError(
                f"session is finished ({self.stop_reason or 'rounds'} at round "
                f"{self.rounds_done}); nothing to step"
            )
        sched = self.spec.schedule
        budget = self.total_rounds
        if self.spec.stop.max_rounds is not None:
            budget = min(budget, self.spec.stop.max_rounds)
        remaining = budget - self.rounds_done
        if k is None:
            k = (
                sched.loss_every - self.rounds_done % sched.loss_every
                if sched.loss_every
                else remaining
            )
        k = min(int(k), remaining)
        if k < 1:
            raise ValueError(f"step_rounds needs k ≥ 1, got {k}")

        loss = None
        synced = False
        autosave_every = self.input_spec.faults.autosave_every
        autosaving = self.autosave_dir is not None and autosave_every > 0
        t0 = time.perf_counter()
        while k > 0 and self.stop_reason is None:
            if sched.loss_every:
                sub = min(k, sched.loss_every - self.rounds_done % sched.loss_every)
            else:
                sub = k
            if autosaving:
                # split at autosave boundaries too, so a cadence finer
                # than loss_every still checkpoints on time (chunk size
                # never changes the iterates).
                sub = min(sub, autosave_every - self.rounds_done % autosave_every)
            if faults.active() is not None:
                # under an installed fault plan every round is a
                # boundary, so planned events fire exactly at their
                # round index on either backend.
                sub = 1
            first = self._first_chunk_pending
            tc = time.perf_counter()
            self._traced_advance(sub, first)
            sampled = None
            if sched.loss_every and self.rounds_done % sched.loss_every == 0:
                sampled = self._sample_loss()  # blocks (device → float)
                self.losses.append(sampled)
                loss, synced = sampled, True
            else:
                synced = False
            if first:
                if sampled is None:
                    self.current_x()  # block: compile wall must be real
                    synced = True
                self.compile_time_s += time.perf_counter() - tc
                self._first_chunk_pending = False
            k -= sub
            # the policy is checked at every boundary, not once per
            # call: a target crossed mid-step stops the step there.
            self._check_stop(
                sampled, wall=self.wall_time_s + (time.perf_counter() - t0)
            )
            if autosaving and self.rounds_done % autosave_every == 0:
                # preemption-safe: the carry is durable at this boundary
                # *before* the seam below may kill/stall/fail the worker.
                self.save(self.autosave_path)
            faults.poke("round", at=self.rounds_done)
        if not synced:
            self.current_x()  # block: wall covers all dispatched work
        self.wall_time_s += time.perf_counter() - t0
        if self.spec.comm_timing and not self.ledger.phase_seconds:
            # after the wall accrual so probe time never masquerades as
            # solve/compile time.
            self._measure_phases()

        return RoundEvent(
            rounds_done=self.rounds_done,
            x=self._copy_x(),  # post-sync: a copy, not a timed stall
            loss=loss,
            wall_time_s=self.wall_time_s,
            compile_time_s=self.compile_time_s,
            comm_words=modeled_comm_words(self.spec, rounds=self.rounds_done),
            ledger=self.ledger.snapshot(),
            stop=self.stop_reason,
        )

    # ---- the streaming door ----

    def _next_stream_batch(self, source):
        """One micro-batch from ``source`` — a ``StreamFeed`` (bounded
        ingest queue; preferred) or a bare ``StreamSource`` (iterated
        lazily from the current round, re-anchored if swapped)."""
        if hasattr(source, "get"):  # StreamFeed
            return source.get()
        if getattr(self, "_stream_src", None) is not source:
            self._stream_src = source
            self._stream_iter = source.micro_batches(self.rounds_done)
        return next(self._stream_iter)

    def _advance_stream(self, batch) -> None:
        """Run ONE round over a fresh micro-batch (no loss sampling).

        The batch replaces the resident data for exactly this round:
        with ``m_local = τ·b`` rows per team, the engine's cyclic bundle
        slicing walks the fresh rows exactly once at any round index, so
        streaming reuses the offline round body (and its jit cache —
        fixed batch shapes compile once) verbatim.
        """
        from repro.serve.ingest import (
            ColumnLocalizer,
            stream_shard_arrays,
            stream_team_problem,
        )

        want = self.spec.stream_rows_per_round()
        if batch.rows != want:
            raise ValueError(
                f"micro-batch has {batch.rows} rows; one round of this schedule "
                f"consumes p_r·τ·b = {want}"
            )
        if self._driver is not None:
            if getattr(self, "_localizer", None) is None:
                self._localizer = ColumnLocalizer.from_partition(self.bundle.cp)
            idx, val = stream_shard_arrays(
                batch, self._localizer, self.spec.schedule.p_r, batch.width
            )
            self._driver.advance_stream(idx, val)  # commits the round
        else:
            tp = stream_team_problem(
                batch,
                self.spec.schedule.p_r,
                self.bundle.dataset.A.n,
                self.bundle.team.objective,
            )
            if self.spec.comm_timing:
                t0 = time.perf_counter()
                self._x = run_engine_chunk(
                    tp, self._x, self.rounds_done, 1, self.spec.schedule
                )
                jax.block_until_ready(self._x)
                self.ledger.add_round_seconds(time.perf_counter() - t0)
            else:
                self._x = run_engine_chunk(
                    tp, self._x, self.rounds_done, 1, self.spec.schedule
                )
            self.ledger.add_rounds(1)
        self.rounds_done += 1

    def step_stream(self, source, k: int | None = None) -> RoundEvent:
        """Advance up to ``k`` rounds (default: to the next loss-sampling
        boundary, or all remaining budget), each round consuming one
        fresh micro-batch from ``source``, and return what happened.

        The streaming twin of ``step_rounds`` — same loss-sampling
        boundaries (the full objective is probed on the spec's resident
        dataset, which serves as the stream session's holdout — so
        ``stop.target_loss`` keeps working), same autosave cadence, same
        StopPolicy and fault seam. What changes is the data: round r
        trains on micro-batch r instead of the resident rows.

        Exactly-once is structural: ``MicroBatch.index`` must equal the
        session's round counter (``StreamDesyncError`` otherwise), and a
        session restored from a round-r autosave re-attaches at batch r
        — sources replay deterministically, so resume continues the
        identical sequence with no duplicated or dropped batch.
        """
        with obs_trace.span("step", step_num=self.rounds_done):
            return self._step_stream(source, k)

    def _step_stream(self, source, k: int | None) -> RoundEvent:
        from repro.serve.stream import StreamDesyncError

        if self.done:
            raise RuntimeError(
                f"session is finished ({self.stop_reason or 'rounds'} at round "
                f"{self.rounds_done}); nothing to step"
            )
        sched = self.spec.schedule
        budget = self.total_rounds
        if self.spec.stop.max_rounds is not None:
            budget = min(budget, self.spec.stop.max_rounds)
        remaining = budget - self.rounds_done
        if k is None:
            k = (
                sched.loss_every - self.rounds_done % sched.loss_every
                if sched.loss_every
                else remaining
            )
        k = min(int(k), remaining)
        if k < 1:
            raise ValueError(f"step_stream needs k ≥ 1, got {k}")

        loss = None
        synced = False
        autosave_every = self.input_spec.faults.autosave_every
        autosaving = self.autosave_dir is not None and autosave_every > 0
        t0 = time.perf_counter()
        while k > 0 and self.stop_reason is None:
            # the span measures consumer-side stall: how long the
            # trainer waited on the feed for this round's batch.
            with obs_trace.span("ingest", name=f"batch[{self.rounds_done}]",
                                index=self.rounds_done):
                batch = self._next_stream_batch(source)
            if batch.index != self.rounds_done:
                raise StreamDesyncError(
                    f"micro-batch index {batch.index} != session round "
                    f"{self.rounds_done}: a batch was duplicated, dropped, or "
                    f"reordered (resume must re-attach the source at "
                    f"start={self.rounds_done})"
                )
            first = self._first_chunk_pending
            tc = time.perf_counter()
            self._traced_advance(1, first, stream_batch=batch)
            sampled = None
            if sched.loss_every and self.rounds_done % sched.loss_every == 0:
                sampled = self._sample_loss()  # blocks (device → float)
                self.losses.append(sampled)
                loss, synced = sampled, True
            else:
                synced = False
            if first:
                if sampled is None:
                    self.current_x()  # block: compile wall must be real
                    synced = True
                self.compile_time_s += time.perf_counter() - tc
                self._first_chunk_pending = False
            k -= 1
            self._check_stop(
                sampled, wall=self.wall_time_s + (time.perf_counter() - t0)
            )
            if autosaving and self.rounds_done % autosave_every == 0:
                # the carry AND the stream position (rounds_done) are
                # durable here — resume re-attaches at this batch index.
                self.save(self.autosave_path)
            faults.poke("round", at=self.rounds_done)
        if not synced:
            self.current_x()  # block: wall covers all dispatched work
        self.wall_time_s += time.perf_counter() - t0
        if self.spec.comm_timing and not self.ledger.phase_seconds:
            self._measure_phases()

        return RoundEvent(
            rounds_done=self.rounds_done,
            x=self._copy_x(),  # post-sync: a copy, not a timed stall
            loss=loss,
            wall_time_s=self.wall_time_s,
            compile_time_s=self.compile_time_s,
            comm_words=modeled_comm_words(self.spec, rounds=self.rounds_done),
            ledger=self.ledger.snapshot(),
            stop=self.stop_reason,
        )

    def _check_stop(self, loss: float | None, wall: float | None = None) -> None:
        # target_loss is checked first: a crossing on the final budgeted
        # round is still a hit (the §7.5 verdict the benchmarks persist),
        # not a budget exhaustion.
        stop = self.spec.stop
        wall = self.wall_time_s if wall is None else wall
        if (
            stop.target_loss is not None
            and loss is not None
            and loss <= stop.target_loss
        ):
            self.stop_reason = "target_loss"
        elif self.rounds_done >= self.total_rounds:
            self.stop_reason = "rounds"
        elif stop.max_rounds is not None and self.rounds_done >= stop.max_rounds:
            self.stop_reason = "max_rounds"
        elif stop.max_seconds is not None and wall >= stop.max_seconds:
            self.stop_reason = "max_seconds"

    def run(self) -> RunReport:
        """Drive the session to its stop condition and report — the
        whole old ``run(spec)``, now a loop anything can interleave
        with."""
        while not self.done:
            self.step_rounds()
        return self.report()

    def report(self) -> RunReport:
        """The uniform ``RunReport`` for the rounds completed so far."""
        x = self.current_x()
        final_loss = float(problem_loss(self.bundle.global_problem, jnp.asarray(x)))
        return RunReport(
            spec=self.spec,
            plan=self._plan,
            backend=self.spec.mesh.backend,
            x=x,
            losses=np.asarray(self.losses, np.float32),
            final_loss=final_loss,
            wall_time_s=self.wall_time_s,
            comm_words=modeled_comm_words(self.spec, rounds=self.rounds_done),
            compile_time_s=self.compile_time_s,
            solve_time_s=max(self.wall_time_s - self.compile_time_s, 0.0),
            rounds_completed=self.rounds_done,
            stop_reason=self.stop_reason,
            ledger=self.ledger.snapshot(),
        )

    # ---- checkpoint / resume ----

    @property
    def autosave_path(self) -> Path:
        """Where this session autosaves (``autosave_dir`` keyed by the
        input spec's content hash); raises when no dir was given."""
        if self.autosave_dir is None:
            raise ValueError(
                "session has no autosave_dir — pass Session(spec, autosave_dir=...)"
            )
        return autosave_base(self.autosave_dir, self.input_spec)

    def save(self, path) -> None:
        """Checkpoint the session carry at the current round boundary
        (atomic; keyed by the input spec's content hash)."""
        save_session_checkpoint(
            path,
            spec_dict=self.input_spec.to_dict(),
            spec_hash=self.input_spec.content_hash(),
            rounds_done=self.rounds_done,
            x=self._copy_x(),
            losses=np.asarray(self.losses, np.float32),
            wall_time_s=self.wall_time_s,
            compile_time_s=self.compile_time_s,
        )

    @classmethod
    def restore(
        cls,
        path,
        spec: ExperimentSpec | None = None,
        autosave_dir: str | Path | None = None,
    ) -> "Session":
        """Reopen a saved session and fast-forward to its round.

        With ``spec`` given, its ``content_hash()`` must equal the hash
        the checkpoint was written under (``SpecMismatchError``
        otherwise — the message names both hashes and the first
        differing spec field) — resuming under a different experiment is
        always a hard error. With ``spec`` omitted, the spec is rebuilt
        from the checkpoint itself.

        The restored session continues the identical round sequence:
        the round counter is part of the carry, so rounds r, r+1, …
        sample exactly what the uninterrupted run would have.
        """
        ck = load_session_checkpoint(
            path,
            expect_spec_hash=spec.content_hash() if spec is not None else None,
            expect_spec_dict=spec.to_dict() if spec is not None else None,
        )
        restored_spec = (
            spec if spec is not None else ExperimentSpec.from_dict(ck.spec_dict)
        )
        sess = cls(restored_spec, x0=ck.x, autosave_dir=autosave_dir)
        return cls._fast_forward(sess, ck)

    @classmethod
    def restore_elastic(
        cls,
        path,
        devices: int | None = None,
        mesh: MeshSpec | None = None,
        calibration=None,
        autosave_dir: str | Path | None = None,
    ) -> "Session":
        """Reopen a saved session on a *different* mesh — the elastic
        door for shrink/grow after a preemption.

        Exactly one of ``devices`` / ``mesh`` picks the new geometry:
        with ``devices``, ``replan_mesh`` prices every (p_r, p_c)
        factorization under the (optionally §6.5-``calibration``-fitted)
        cost model and the cheapest wins; with ``mesh``, that geometry
        is used as given. The checkpoint's weights are re-scattered onto
        the new layout (the ELL shards are rebuilt for the new
        partition when the session constructs its problem), the loss
        trace and round counter carry over, and the run continues from
        the last round boundary.

        At an *unchanged* mesh this is exactly ``restore`` (bitwise-
        identical continuation). At a changed p_c the numerics are
        unchanged by construction (p_c is communication-only); a changed
        p_r re-teams the rows, so the resumed trajectory is a different
        — equally valid — member of the (p_r, p_c, s, τ) family that
        converges to the same objective, not a bitwise replay.
        """
        if (devices is None) == (mesh is None):
            raise ValueError("restore_elastic needs exactly one of devices= / mesh=")
        ck = load_session_checkpoint(path)  # deliberately un-keyed: elastic
        old_spec = ExperimentSpec.from_dict(ck.spec_dict)
        if mesh is None:
            new_spec = replan_mesh(old_spec, devices, calibration=calibration).spec
        else:
            new_spec = dataclasses.replace(
                old_spec,
                schedule=dataclasses.replace(
                    old_spec.schedule, p_r=mesh.p_r, p_c=mesh.p_c
                ),
                mesh=mesh,
            )
        sess = cls(new_spec, x0=ck.x, autosave_dir=autosave_dir)
        return cls._fast_forward(sess, ck)

    @staticmethod
    def _fast_forward(sess: "Session", ck: SessionCheckpoint) -> "Session":
        """Advance a freshly built session's counters to the checkpoint:
        round counter (part of the carry — the sample sequence
        continues exactly), loss-trace prefix, and accumulated wall.
        The counted-comm side of the ledger fast-forwards too (the run,
        as opposed to this process, has communicated ck.rounds_done
        rounds' worth); measured per-round seconds stay per-process — a
        fresh process recompiles and re-times."""
        sess.rounds_done = ck.rounds_done
        if sess._driver is not None:
            sess._driver.rounds_done = ck.rounds_done
        sess.ledger.rounds = ck.rounds_done
        sess.losses = [float(v) for v in ck.losses]
        sess.wall_time_s = ck.wall_time_s
        sess.compile_time_s = ck.compile_time_s
        sess._first_chunk_pending = True  # this process must recompile
        sess._check_stop(sess.losses[-1] if sess.losses else None)
        return sess
