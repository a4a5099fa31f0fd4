"""Structured span tracing — the repo's observability seam.

This is the tracing half of ``repro.obs``. Instrumented sites across
train/sweep/serve call the module-level ``span(category, name, **args)``
around a layer boundary, and every span goes to up to two sinks:

* the JAX profiler, always: the span opens a
  ``jax.profiler.TraceAnnotation`` named ``repro.<category>`` with
  ``name`` and ``args`` as its stats (``step`` opens a
  ``StepTraceAnnotation``, so xprof's step view splits on it). Any
  ``jax.profiler`` trace taken around the program therefore holds the
  Session's layer boundaries on the device trace's own clock. With no
  profiler running an annotation costs one small object and an
  enabled-check; it encodes its args only while the profiler records.
* a ``TraceRecorder``, when one is ``install``-ed: ``Span``s — named,
  categorized, nested wall-clock intervals — kept in memory for the
  operator's exports (``repro.launch.sweep --trace``). The install
  seam follows ``repro.core.faults``: a contextmanager + ContextVar,
  plus a module-level fallback so worker threads (the serve plane's
  feed producer and prediction batcher) record too.

Nothing is ever recorded from inside jit: spans are host-side
intervals, so compiled numerics are untouched and traced runs are
bitwise-identical to untraced ones. The device work of a round is named
from inside the jitted programs instead, by ``jax.named_scope``
(``bundle_gram``, ``allreduce_gv``, ``corrections``, ``update``,
``param_avg``, ``loss_probe``; see ``repro.core.engine``).

Span categories are a closed set (``SPAN_CATEGORIES``); an unknown
category is a programming error and raises immediately. ``step`` wraps
one ``Session.step_rounds`` call; ``round``/``compile`` each sub-chunk
advance inside it; ``probe`` the loss probe (dispatch + device-to-host
read); ``copy_x`` the host copy of the weights a ``RoundEvent`` or
checkpoint carries; ``ckpt_save``/``ckpt_verify``/``swap`` the
durability plane; ``ingest``/``predict_batch`` the serve plane.
"""

from __future__ import annotations

import contextlib
import dataclasses
import threading
import time
from contextvars import ContextVar

from jax.profiler import StepTraceAnnotation, TraceAnnotation

__all__ = [
    "SPAN_CATEGORIES",
    "Span",
    "TraceRecorder",
    "active",
    "install",
    "span",
]

SPAN_CATEGORIES = (
    "step",
    "round",
    "probe",
    "copy_x",
    "ckpt_save",
    "ckpt_verify",
    "swap",
    "ingest",
    "predict_batch",
    "compile",
)


@dataclasses.dataclass(frozen=True)
class Span:
    """One recorded wall-clock interval.

    category  one of ``SPAN_CATEGORIES``.
    name      instance label ("rounds[8+4]", "swap-12", ...).
    t0        start, seconds since the recorder's epoch (perf_counter
              clock — monotonic; the recorder also stamps a unix epoch
              so exports can place spans in absolute time).
    dur       duration in seconds.
    tid       recording thread id (spans from the feed producer and the
              prediction batcher land on their own tracks).
    depth     nesting depth within the recording thread (0 = top).
    args      small JSON-safe payload (round counts, paths, row counts).
    """

    category: str
    name: str
    t0: float
    dur: float
    tid: int
    depth: int
    args: dict = dataclasses.field(default_factory=dict)


class TraceRecorder:
    """Collects spans from every instrumented seam while installed.

    Thread-safe: instrumented sites run on the session thread, the
    stream feed's producer thread, and the prediction service's batcher
    thread; each appends under one lock and nests against its own
    per-thread depth stack.
    """

    def __init__(self):
        self.epoch_perf = time.perf_counter()
        self.epoch_unix = time.time()
        self.spans: list[Span] = []
        self._lock = threading.Lock()
        self._local = threading.local()

    # ---- recording ----

    def _depth(self) -> int:
        return getattr(self._local, "depth", 0)

    @contextlib.contextmanager
    def span(self, category: str, name: str | None = None, **args):
        """Record the with-block as one span. ``args`` must be
        JSON-safe (they land verbatim in the exported trace)."""
        if category not in SPAN_CATEGORIES:
            raise ValueError(f"category={category!r} not in {SPAN_CATEGORIES}")
        depth = self._depth()
        self._local.depth = depth + 1
        t0 = time.perf_counter() - self.epoch_perf
        try:
            yield self
        finally:
            dur = (time.perf_counter() - self.epoch_perf) - t0
            self._local.depth = depth
            self._append(Span(
                category=category,
                name=name if name is not None else category,
                t0=t0,
                dur=dur,
                tid=threading.get_ident(),
                depth=depth,
                args=args,
            ))

    def _append(self, s: Span) -> None:
        with self._lock:
            self.spans.append(s)

    # ---- inspection ----

    def by_category(self) -> dict[str, list[Span]]:
        out: dict[str, list[Span]] = {}
        with self._lock:
            spans = list(self.spans)
        for s in spans:
            out.setdefault(s.category, []).append(s)
        return out

    def total_seconds(self, category: str) -> float:
        return sum(s.dur for s in self.by_category().get(category, ()))

    def __len__(self) -> int:
        with self._lock:
            return len(self.spans)


# ---- the seam ----------------------------------------------------------
#
# ContextVar for the common single-threaded case, plus a module-level
# fallback: ContextVars do NOT propagate into threading.Thread, and the
# serve plane's producer/batcher threads are exactly where queue-depth
# and batch spans come from. install() sets both; active() prefers the
# ContextVar (correct nesting of scoped installs on one thread) and
# falls back to the global for threads started inside the scope.

_ACTIVE: ContextVar[TraceRecorder | None] = ContextVar("trace_recorder", default=None)
_GLOBAL: TraceRecorder | None = None


def active() -> TraceRecorder | None:
    """The installed recorder, or None (the normal, untraced case)."""
    rec = _ACTIVE.get()
    if rec is not None:
        return rec
    return _GLOBAL


@contextlib.contextmanager
def install(recorder: TraceRecorder | None = None):
    """Install a recorder for the dynamic extent of the with-block and
    yield it (make one when not given). Worker threads started inside
    the scope see it too, via the module-level fallback."""
    global _GLOBAL
    rec = TraceRecorder() if recorder is None else recorder
    token = _ACTIVE.set(rec)
    prev_global = _GLOBAL
    _GLOBAL = rec
    try:
        yield rec
    finally:
        _ACTIVE.reset(token)
        _GLOBAL = prev_global


def span(category: str, name: str | None = None, **args):
    """A span at an instrumented site: a ``repro.<category>`` annotation
    in any running ``jax.profiler`` trace, with ``name`` and ``args`` as
    its stats, and a ``Span`` in the installed recorder, if any.
    ``step`` spans take a ``step_num`` arg (xprof's step number)."""
    if category not in SPAN_CATEGORIES:
        raise ValueError(f"category={category!r} not in {SPAN_CATEGORIES}")
    if category == "step":
        ann = StepTraceAnnotation("repro.step", **args)
    else:
        ann = TraceAnnotation(f"repro.{category}",
                              name=category if name is None else name, **args)
    rec = active()
    if rec is None:
        return ann
    return _both(ann, rec.span(category, name, **args))


@contextlib.contextmanager
def _both(annotation, recorded):
    with annotation, recorded as rec:
        yield rec
