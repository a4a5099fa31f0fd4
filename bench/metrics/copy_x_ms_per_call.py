"""Host (``Session`` → ``RoundEvent.x``): the host time in the program's
``repro.copy_x`` spans in the traced window over its ``repro.step``
spans, in ms. Prints on standard error where each chip's idle time went:
split by the innermost ``repro.*`` span open, else ``none`` inside a
``bench.step_rounds`` call, else outside one. Nothing where the program
has neither span."""

import sys

from bench import scopes


def read(run):
    prof = scopes.of_run(run)
    if prof is None:
        return None
    lo, hi = prof.window()
    idle = scopes.idle_by_span(run)
    total = sum(idle.values())
    print("idle_by_span: " + ", ".join(
        f"{label} {ns / 1e6:.3f}ms ({100.0 * ns / total:.1f}%)"
        for label, ns in sorted(idle.items(), key=lambda kv: -kv[1])) if total else
        "idle_by_span: no idle time", file=sys.stderr)
    copies = scopes.spans(prof, "repro.copy_x", lo, hi)
    steps = scopes.spans(prof, "repro.step", lo, hi)
    if not copies or not steps:
        return None
    return sum(h.end - h.start for h in copies) / len(steps) / 1e6
