"""Round body: useful FLOPs of the traced rounds (``bench.work``: Gram,
v, corrections and update of every bundle, the teams' mean; the same
whatever computes them) over the traced window × chips × the chip's
bf16 peak, in %."""


def read(run):
    if run.trace is None or not run.flops:
        return None
    lo, hi = run.trace_span
    return 100.0 * run.flops / ((hi - lo) / 1e9 * run.chips * run.peaks.bf16_flops)
