"""Device: the share of the traced window in which no op ran, 1 - (union
of the chip's op intervals / window), averaged over the cell's chips,
in %."""

from bench import trace_reduce


def read(run):
    if run.trace is None or not run.chip_ids:
        return None
    lo, hi = run.trace_span
    busy = [trace_reduce.busy_ns(run.trace, c, lo, hi) for c in run.chip_ids]
    return 100.0 * (1.0 - sum(busy) / len(busy) / (hi - lo))
