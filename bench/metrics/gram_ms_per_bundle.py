"""Kernel layer (``repro.kernels.ell_gram``): the summed device time of
the Gram kernel's events in the traced window over their count, in ms.
Nothing when no event matches the kernel."""

from bench import trace_reduce


def read(run):
    if run.trace is None:
        return None
    lo, hi = run.trace_span
    evs = [e for c in run.chip_ids for e in trace_reduce.gram_ops(run.trace, c, lo, hi)]
    if not evs:
        return None
    return sum(e.end - e.start for e in evs) / len(evs) / 1e6
