"""The explicit communication plane — one collectives layer under both
backends, with a per-round communication ledger.

The paper's thesis is that communication, not compute, bounds parallel
SGD (Eq. 4, Tables 2–3). This module makes that quantity first-class:
every collective either backend issues goes through one ``Collectives``
object, and the structure of what was issued — op, mesh axis, span,
payload words, calls per round — is recorded into a ``CommLedger`` that
reports can place next to the Hockney model's predictions.

Three implementations, one protocol:

  counting   the simulated engine's ops. Numerically the identity /
             plain team mean (the simulated ranks already hold globally
             reduced values), but the call sites are the same ones the
             mesh path reduces over — so counting them *is* counting
             the algorithm's communication.
  mesh       shard_map execution: real ``psum`` over the "cols" axis
             (row-team Gram Allreduce) and ``pmean`` over "rows" (the
             column weight sync) — exactly the collectives
             repro.core.distributed issued before this layer existed,
             bitwise.
  timed      mesh + host-side per-round wall timing (the driver blocks
             after each round and appends seconds to the ledger) — the
             §6.5 calibration input (repro.costmodel.calibrate).

Ledger capture is *structural*, not statistical: ``capture_rates`` runs
the actual round body once under ``jax.eval_shape`` (abstract — no
FLOPs, no devices) with a recorder installed; every collective call
records its span and payload from the real traced shapes. A collective
added to (or dropped from) a round body is therefore seen immediately —
the ledger cannot drift from the code the way a hand-maintained formula
can. Real jit traces never record (the recorder is a ContextVar that is
only set inside ``capture_rates``), so compiled numerics are untouched.

Accounting conventions (shared with the Table 2–3 closed forms in
``repro.costmodel.hockney.schedule_comm_volume``):

* words are **per rank** per call, counted from the buffers actually
  reduced — the dense (sb, sb) Gram block plus the (sb,) residual, i.e.
  s²b² + sb words per bundle (the strictly-lower-triangular s(s-1)b²/2
  of Table 3 is the payload's information content; the wire carries the
  dense block);
* a collective whose span is 1 rank moves nothing: it is recorded (the
  call exists) but contributes zero words and zero calls to the counted
  totals;
* the column weight-sync payload is the per-rank weight shard —
  ⌈n/p_c⌉ words under a balanced partition. Unbalanced partitioners pad
  shards to the max (n_loc ≥ ⌈n/p_c⌉) and the mesh ledger counts that
  real padded payload, so counted-vs-modeled exposes padding overhead.
"""

from __future__ import annotations

import dataclasses
import math
import statistics
from contextvars import ContextVar

import jax
import jax.numpy as jnp

__all__ = [
    "COUNTING",
    "MESH",
    "TIMED",
    "Collectives",
    "CommLedger",
    "CommRate",
    "capture_rates",
    "time_phase",
]

COLLECTIVE_KINDS = ("counting", "mesh", "timed")


@dataclasses.dataclass(frozen=True)
class CommRate:
    """One collective call site of a round body, as captured.

    op              "allreduce" (sum) or "allmean" (average).
    axis            mesh axis reduced over: "cols" (row-team Gram
                    Allreduce) or "rows" (column weight sync).
    span            ranks the collective spans (p_c for "cols", p_r for
                    "rows"); span 1 moves no bytes.
    words_per_call  per-rank payload words of one call.
    calls_per_round how many times the site executes per outer round
                    (the s-bundle loop issues τ/s Gram Allreduces).
    word_bytes      on-wire bytes per word of this payload, captured
                    from the traced leaf dtype (2 for a bf16 (G, v)
                    collective, 4 for fp32 — the default). The word
                    *counts* above stay the Table 2–3 closed forms
                    regardless of precision; this is the β multiplier's
                    other factor.
    """

    op: str
    axis: str
    span: int
    words_per_call: int
    calls_per_round: int
    word_bytes: int = 4

    @property
    def phases_per_call(self) -> int:
        """Hockney latency phases: 2⌈log₂ span⌉ (reduce-scatter +
        all-gather), 0 when the span is a single rank."""
        if self.span <= 1:
            return 0
        return 2 * math.ceil(math.log2(self.span))

    def to_dict(self) -> dict:
        d = dataclasses.asdict(self)
        if self.word_bytes == 4:
            # emitted only when non-default: fp32 ledgers serialize
            # byte-identically to every pre-precision release.
            del d["word_bytes"]
        return d

    @classmethod
    def from_dict(cls, d: dict) -> "CommRate":
        return cls(**d)


@dataclasses.dataclass
class CommLedger:
    """What a run communicated: captured per-round rates × committed
    rounds, plus (timed runs) host-measured per-round wall seconds.

    rates          the round body's collective call sites (captured
                   once at build; identical every round — the schedule
                   is static).
    rounds         rounds accounted so far (the driver commits them as
                   it advances).
    round_seconds  per-round wall seconds, appended by the timed
                   executor; empty for counting/mesh runs.
    phase_seconds  per-round seconds attributed to each §6.5 phase
                   ("bundle_compute" / "allreduce_gv" / "param_avg"),
                   measured once per timed run by the phase probes
                   (separate jitted probes over the round's real payload
                   shapes — the training step itself is never split, so
                   its compiled numerics stay untouched).
    delay          the schedule's staleness D. D ≥ 1 pipelines the
                   (G, v) Allreduce D bundles deep, so each collective
                   has D bundle-computes to hide behind — the exposed
                   (critical-path) comm time drops below the total
                   while the counted volume is unchanged.
    """

    rates: tuple[CommRate, ...] = ()
    rounds: int = 0
    round_seconds: list[float] = dataclasses.field(default_factory=list)
    phase_seconds: dict[str, float] = dataclasses.field(default_factory=dict)
    delay: int = 0

    # ---- accumulation (driver-side) ----

    def add_rounds(self, k: int) -> None:
        self.rounds += int(k)

    def add_round_seconds(self, dt: float) -> None:
        self.round_seconds.append(float(dt))

    def set_phase_seconds(self, phases: dict[str, float]) -> None:
        self.phase_seconds = {k: float(v) for k, v in phases.items()}

    def snapshot(self) -> "CommLedger":
        """An independent copy (what RoundEvent/RunReport carry)."""
        return CommLedger(
            rates=self.rates,
            rounds=self.rounds,
            round_seconds=list(self.round_seconds),
            phase_seconds=dict(self.phase_seconds),
            delay=self.delay,
        )

    # ---- counted totals (span-1 collectives move nothing) ----

    def _per_round(self, axis: str, field: str) -> int:
        return sum(
            getattr(r, field) * (r.calls_per_round if field != "calls_per_round" else 1)
            for r in self.rates
            if r.axis == axis and r.span > 1
        )

    def counted_words(self, rounds: int | None = None) -> dict[str, float]:
        """Per-rank communicated words over ``rounds`` (default: the
        committed count) — same keys as the modeled dict, so reports can
        print the two side by side."""
        r = self.rounds if rounds is None else int(rounds)
        gram = float(r * self._per_round("cols", "words_per_call"))
        sync = float(r * self._per_round("rows", "words_per_call"))
        return {"gram_words": gram, "sync_words": sync, "total_words": gram + sync}

    def counted_calls(self, rounds: int | None = None) -> dict[str, int]:
        """Collective calls that actually spanned >1 rank."""
        r = self.rounds if rounds is None else int(rounds)
        return {
            "gram_calls": r * self._per_round("cols", "calls_per_round"),
            "sync_calls": r * self._per_round("rows", "calls_per_round"),
        }

    def phases_per_round(self) -> int:
        """Hockney α-phases per round: Σ calls · 2⌈log₂ span⌉."""
        return sum(
            r.calls_per_round * r.phases_per_call for r in self.rates if r.span > 1
        )

    def bytes_per_round(self, word_bytes: int | None = None) -> float:
        """On-wire bytes per rank per round (the β multiplier).

        With ``word_bytes=None`` each call site is priced at its own
        captured ``word_bytes`` (so a bf16 (G, v) Allreduce counts half
        the fp32 bytes); an explicit ``word_bytes`` keeps the legacy
        uniform override (every word priced at the machine's word)."""
        if word_bytes is None:
            return float(sum(
                r.words_per_call * r.calls_per_round * r.word_bytes
                for r in self.rates
                if r.span > 1
            ))
        return float(word_bytes) * (
            self._per_round("cols", "words_per_call")
            + self._per_round("rows", "words_per_call")
        )

    def counted_bytes(self, rounds: int | None = None) -> dict[str, float]:
        """Per-rank on-wire bytes over ``rounds``, at each call site's
        captured ``word_bytes`` — the precision-aware twin of
        ``counted_words`` (whose word counts are invariant)."""
        r = self.rounds if rounds is None else int(rounds)

        def axis_bytes(axis):
            return float(r * sum(
                rt.words_per_call * rt.calls_per_round * rt.word_bytes
                for rt in self.rates
                if rt.axis == axis and rt.span > 1
            ))

        gram, sync = axis_bytes("cols"), axis_bytes("rows")
        return {"gram_bytes": gram, "sync_bytes": sync, "total_bytes": gram + sync}

    # ---- measured (timed runs) ----

    @property
    def seconds_per_round(self) -> float | None:
        """Median measured round wall (None when the run was untimed)."""
        if not self.round_seconds:
            return None
        return statistics.median(self.round_seconds)

    @property
    def total_comm_s(self) -> float | None:
        """Total communication time over the committed rounds: the
        per-round comm phases ("allreduce_gv" + "param_avg") × rounds —
        what the run pays on the wire regardless of overlap. None until
        the phase probes have run."""
        comm = [v for k, v in self.phase_seconds.items() if k != "bundle_compute"]
        if not comm:
            return None
        return float(sum(comm)) * self.rounds

    @property
    def exposed_comm_s(self) -> float | None:
        """Communication time on the *critical path* over the committed
        rounds. At delay 0 nothing overlaps, so exposed ≡ total. At
        delay D ≥ 1 each per-bundle (G, v) Allreduce is consumed D
        bundles after it is issued, so it has D bundle-computes to hide
        behind: the exposed Gram-phase remainder per round is
        max(allreduce_gv − D · bundle_compute, 0) (the positive part
        commutes with the per-round scaling, since both phases count
        the same τ/s calls). The parameter average stays synchronous at
        the round boundary and is always exposed. None until the phase
        probes have run."""
        comm = {k: v for k, v in self.phase_seconds.items() if k != "bundle_compute"}
        if not comm:
            return None
        gv = comm.pop("allreduce_gv", 0.0)
        if self.delay:
            compute = self.phase_seconds.get("bundle_compute", 0.0)
            gv = max(gv - self.delay * compute, 0.0)
        return float(gv + sum(comm.values())) * self.rounds

    @property
    def overlap_efficiency(self) -> float | None:
        """exposed_comm_s / total_comm_s — the fraction of paid comm
        time still on the critical path (1.0 = nothing hidden, the
        delay-0 value; lower is better). None until the phase probes
        have run."""
        total = self.total_comm_s
        exposed = self.exposed_comm_s
        if total is None or exposed is None:
            return None
        if total <= 0.0:
            return 1.0
        return exposed / total

    # ---- serialization ----

    def to_dict(self) -> dict:
        d = {
            "rates": [r.to_dict() for r in self.rates],
            "rounds": self.rounds,
            "round_seconds": list(self.round_seconds),
            # derived, for human-readable reports (ignored on load)
            "counted": self.counted_words(),
        }
        if any(r.word_bytes != 4 for r in self.rates):
            # bytes are derived too, but only interesting (and only
            # emitted) when some payload is narrower than a word —
            # fp32 ledgers keep their pre-precision serialization.
            d["counted_bytes"] = self.counted_bytes()
        if self.delay:
            # emitted only when nonzero: delay-0 ledgers serialize
            # byte-identically to every pre-overlap release.
            d["delay"] = self.delay
        if self.phase_seconds:
            d["phase_seconds"] = dict(self.phase_seconds)
            # derived trio, for human-readable reports (ignored on load)
            d["exposed_comm_s"] = self.exposed_comm_s
            d["total_comm_s"] = self.total_comm_s
            d["overlap_efficiency"] = self.overlap_efficiency
        return d

    @classmethod
    def from_dict(cls, d: dict) -> "CommLedger":
        return cls(
            rates=tuple(CommRate.from_dict(r) for r in d.get("rates", ())),
            rounds=int(d.get("rounds", 0)),
            round_seconds=[float(v) for v in d.get("round_seconds", ())],
            phase_seconds={
                k: float(v) for k, v in d.get("phase_seconds", {}).items()
            },
            delay=int(d.get("delay", 0)),
        )


# ---- capture machinery -------------------------------------------------
#
# Recording is scoped to capture_rates via a ContextVar: inside it the
# collective ops append a CommRate (from the traced payload shapes) and
# return their input unchanged — the abstract trace needs no mesh axes.
# Outside it (every real trace and execution) the ops are exactly the
# pre-layer computation.


@dataclasses.dataclass
class _Recorder:
    spans: dict[str, int]
    rates: list[CommRate]


_RECORDER: ContextVar[_Recorder | None] = ContextVar("repro_comm_recorder", default=None)


def capture_rates(fn, *abstract_args, spans: dict[str, int]) -> tuple[CommRate, ...]:
    """Trace ``fn`` abstractly (``jax.eval_shape`` — no FLOPs, no
    devices) with recording on, and return every collective call site it
    issued. ``spans`` maps mesh axis name → rank count ({"cols": p_c,
    "rows": p_r})."""
    rec = _Recorder(spans=dict(spans), rates=[])
    token = _RECORDER.set(rec)
    try:
        jax.eval_shape(fn, *abstract_args)
    finally:
        _RECORDER.reset(token)
    return tuple(rec.rates)


def _tree_words(tree) -> int:
    return int(sum(math.prod(leaf.shape) for leaf in jax.tree_util.tree_leaves(tree)))


def _tree_word_bytes(tree) -> int:
    """On-wire bytes per word, from the traced leaf dtypes (the widest
    leaf prices the payload; 4 when the tree carries no leaves)."""
    sizes = [leaf.dtype.itemsize for leaf in jax.tree_util.tree_leaves(tree)]
    return int(max(sizes)) if sizes else 4


@dataclasses.dataclass(frozen=True)
class Collectives:
    """The collective ops a round body issues, by kind.

    Frozen and stateless: instances hash and compare by ``kind``, so
    closing a jitted round body over one never fragments the jit cache.
    The module singletons ``COUNTING`` / ``MESH`` / ``TIMED`` are the
    three implementations; ``TIMED`` shares ``MESH``'s ops — the timing
    itself is host-side, in the driver (``HybridDriver.advance`` /
    ``Session._advance`` block per round and append to the ledger).
    """

    kind: str = "counting"

    def __post_init__(self):
        if self.kind not in COLLECTIVE_KINDS:
            raise ValueError(f"kind={self.kind!r} not in {COLLECTIVE_KINDS}")

    @property
    def timed(self) -> bool:
        return self.kind == "timed"

    @property
    def on_mesh(self) -> bool:
        return self.kind in ("mesh", "timed")

    # ---- the row-team (Gram) Allreduce: sum over column shards ----

    def allreduce_cols(self, tree, *, calls_per_round: int = 1,
                       words_per_call: int | None = None):
        """Sum ``tree`` across the "cols" mesh axis (the per-bundle
        (G, v) Allreduce — Table 3's row-team payload).

        counting: identity — the simulated ranks compute the full (G, v)
        directly, so the reduced value is already in hand. mesh/timed:
        one ``psum`` per leaf (separate binds, exactly the two psum
        calls the pre-layer code issued — bitwise-identical HLO).

        ``words_per_call`` overrides the payload derived from the traced
        leaf shapes — the s = 1 engine corner uses it to account the
        full (G, v) payload its distributed twin puts on the wire even
        though the simulated body only materializes v.
        """
        rec = _RECORDER.get()
        if rec is not None:
            words = words_per_call if words_per_call is not None else _tree_words(tree)
            rec.rates.append(CommRate(
                op="allreduce",
                axis="cols",
                span=rec.spans.get("cols", 1),
                words_per_call=int(words),
                calls_per_round=int(calls_per_round),
                word_bytes=_tree_word_bytes(tree),
            ))
            return tree
        if not self.on_mesh:
            return tree
        return jax.tree_util.tree_map(lambda t: jax.lax.psum(t, "cols"), tree)

    # ---- the async-dispatch-shaped split of the Gram Allreduce ----
    #
    # JAX collectives are dispatched asynchronously: the Python call
    # returns a future-backed array and the host only blocks when a
    # value is needed. The delay-D pipeline makes that explicit at the
    # call-site level — ``issue_allreduce_cols`` at bundle k starts the
    # reduction, ``await_allreduce`` at bundle k+D marks where its value
    # is first consumed. Under XLA the issue *is* the psum (recorded
    # once, same payload accounting as the fused call) and the await is
    # the identity: the D bundle-computes the scheduler runs in between
    # are what actually hides the transfer.

    def issue_allreduce_cols(self, tree, *, calls_per_round: int = 1,
                             words_per_call: int | None = None):
        """Start the per-bundle (G, v) Allreduce for a delayed schedule.
        Same reduction, recording, and payload conventions as
        ``allreduce_cols`` — the split exists so traces and ledgers can
        attribute the in-flight window."""
        return self.allreduce_cols(
            tree, calls_per_round=calls_per_round, words_per_call=words_per_call
        )

    def await_allreduce(self, tree):
        """Consume a previously issued Allreduce. Identity on every
        kind and never recorded — the payload was counted at issue
        time; this marks the critical-path join point."""
        return tree

    # ---- the column Allreduce: average weights across row teams ----

    def allmean_rows(self, x, *, calls_per_round: int = 1,
                     words_per_call: int | None = None):
        """Average the per-shard weight slab across the "rows" mesh axis
        (the per-τ-iterations FedAvg sync — Table 3's column payload).
        Mesh/timed only; the simulated engine's stacked form is
        ``allmean_teams``."""
        rec = _RECORDER.get()
        if rec is not None:
            words = words_per_call if words_per_call is not None else _tree_words(x)
            rec.rates.append(CommRate(
                op="allmean",
                axis="rows",
                span=rec.spans.get("rows", 1),
                words_per_call=int(words),
                calls_per_round=int(calls_per_round),
                word_bytes=_tree_word_bytes(x),
            ))
            return x
        if not self.on_mesh:
            return x
        return jax.lax.pmean(x, "rows")

    def allmean_teams(self, xs, *, words_per_call: int,
                      calls_per_round: int = 1):
        """The simulated form of ``allmean_rows``: the p_r team iterates
        arrive stacked as ``xs`` (p_r, n) and the mean over the leading
        axis *is* the collective (exact SPMD semantics on one device).
        ``words_per_call`` is the per-rank shard payload ⌈n/p_c⌉ — the
        stacked shape carries the global n, not the per-rank slab, so
        the caller supplies it."""
        rec = _RECORDER.get()
        if rec is not None:
            rec.rates.append(CommRate(
                op="allmean",
                axis="rows",
                span=rec.spans.get("rows", 1),
                words_per_call=int(words_per_call),
                calls_per_round=int(calls_per_round),
                word_bytes=_tree_word_bytes(xs),
            ))
        return jnp.mean(xs, axis=0)


COUNTING = Collectives("counting")
MESH = Collectives("mesh")
TIMED = Collectives("timed")


def time_phase(fn, *args, repeats: int = 5) -> float:
    """Median wall seconds of one call to a compiled phase probe
    (blocks on the result; one unmeasured warmup call absorbs the
    compile). The §6.5 per-phase measurement primitive."""
    import time as _time

    jax.block_until_ready(fn(*args))  # warmup / compile
    walls = []
    for _ in range(int(repeats)):
        t0 = _time.perf_counter()
        jax.block_until_ready(fn(*args))
        walls.append(_time.perf_counter() - t0)
    return statistics.median(walls)
