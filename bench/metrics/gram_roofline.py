"""Kernel layer: the Gram kernel's share of its roofline, in %: the
least time the chip needs for the (G, v) calls of the traced rounds
(``bench.work``: implementation-neutral FLOPs and bytes against the
chip's peaks) over the kernel's device time in the traced window.
Prints which bound, compute or memory, sets the least time. Nothing
when no event matches the kernel."""

import sys

from bench import trace_reduce


def read(run):
    if run.trace is None or not run.calls:
        return None
    lo, hi = run.trace_span
    kernel_ns = sum(e.end - e.start for c in run.chip_ids
                    for e in trace_reduce.gram_ops(run.trace, c, lo, hi))
    if not kernel_ns:
        return None
    least = [call.least_seconds(run.peaks.bf16_flops, run.peaks.hbm_bytes_per_s)
             for call in run.calls]
    memory = sum(1 for _, bound in least if bound == "memory")
    print(f"gram_roofline: memory bound on {memory} of {len(least)} calls, "
          f"least {sum(t for t, _ in least):.6e}s against {kernel_ns / 1e9:.6e}s of kernel",
          file=sys.stderr)
    return 100.0 * sum(t for t, _ in least) / (kernel_ns / 1e9)
