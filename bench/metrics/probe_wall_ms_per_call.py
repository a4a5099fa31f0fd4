"""Solver probe (``Session._sample_loss``): the host time in the
program's ``repro.probe`` spans in the traced window over their count,
in ms: from the loss's dispatch to its scalar on the host, whatever the
probe runs on and moves. Nothing where the program has no such span."""

from bench import scopes


def read(run):
    prof = scopes.of_run(run)
    if prof is None:
        return None
    lo, hi = prof.window()
    probes = scopes.spans(prof, "repro.probe", lo, hi)
    if not probes:
        return None
    return sum(h.end - h.start for h in probes) / len(probes) / 1e6
