"""Collectives (``repro.core.comm`` on the mesh): the device time of the
collective ops during which no other op runs on that chip, over the
traced window, averaged over the chips, in %. Nothing when the trace
holds no collective."""

from bench import trace_reduce


def read(run):
    if run.trace is None:
        return None
    lo, hi = run.trace_span
    if not any(trace_reduce.is_collective(e.name) for c in run.chip_ids
               for e in trace_reduce.within(run.trace.ops[c], lo, hi)):
        return None
    exposed = [trace_reduce.exposed_collective_ns(run.trace, c, lo, hi) for c in run.chip_ids]
    return 100.0 * sum(exposed) / len(exposed) / (hi - lo)
