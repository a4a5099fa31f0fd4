"""Collectives (``repro.core.comm`` on the mesh): the device time of the
ops under the program's ``param_avg`` scope, the mean of the row teams'
weights over the "rows" axis, per traced round, mean over the chips, in
ms. Nothing where no op carries the scope."""

from bench import scopes


def read(run):
    prof = scopes.of_run(run)
    if prof is None or not run.traced_rounds:
        return None
    lo, hi = prof.window()
    ns = scopes.scope_ns(prof, scopes.chips(prof, run.chips), "param_avg", lo, hi)
    if not any(ns):
        return None
    return sum(ns) / len(ns) / run.traced_rounds / 1e6
