"""Round body (``repro.core.engine`` / ``repro.core.distributed``): the
device time per round of the round program's ops other than the Gram
kernel (corrections, update, averaging, bundle slicing), in ms. The
round program is every program run (``XLA Modules`` event) that holds
a Gram kernel event; its time is the union of its leaf ops less the
kernel's, per chip, averaged over the chips, over the traced rounds."""

from bench import trace_reduce


def read(run):
    if run.trace is None or not run.traced_rounds:
        return None
    lo, hi = run.trace_span
    per_chip = []
    for c in run.chip_ids:
        other = 0
        grams = trace_reduce.gram_ops(run.trace, c, lo, hi)
        for mod in trace_reduce.within(run.trace.modules.get(c, []), lo, hi):
            mine = [g for g in grams if mod.start <= g.start < mod.end]
            if not mine:
                continue
            ops = trace_reduce.leaves(trace_reduce.within(run.trace.ops[c], mod.start, mod.end))
            other += (trace_reduce.covered(trace_reduce.union(ops))
                      - trace_reduce.covered(trace_reduce.union(mine)))
        per_chip.append(other)
    if not any(per_chip):
        return None
    return sum(per_chip) / len(per_chip) / run.traced_rounds / 1e6
