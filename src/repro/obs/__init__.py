"""repro.obs — the observability plane.

``trace``    the span seam: ``repro.<category>`` annotations in any
             ``jax.profiler`` trace, plus a ``TraceRecorder`` when one
             is installed (contextmanager + ContextVar); the closed
             span-category set.
``metrics``  process-wide registry of typed Counter/Gauge/Histogram
             instruments with labeled snapshots and deltas.
``export``   Chrome trace-event JSON (Perfetto-loadable) and JSONL
             event-log export, both schema-versioned.
"""

from repro.obs.metrics import MetricsRegistry, registry
from repro.obs.trace import SPAN_CATEGORIES, Span, TraceRecorder, active, install, span

__all__ = [
    "MetricsRegistry",
    "SPAN_CATEGORIES",
    "Span",
    "TraceRecorder",
    "active",
    "install",
    "registry",
    "span",
]
