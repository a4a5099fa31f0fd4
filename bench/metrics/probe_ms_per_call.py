"""Solver probe (``Session._sample_loss``, ``engine_loss``): the device
time of the ops under the program's ``loss_probe`` scope in the traced
window, summed over the chips, over the ``repro.probe`` spans there, in
ms. Nothing where the program has neither."""

from bench import scopes


def read(run):
    prof = scopes.of_run(run)
    if prof is None:
        return None
    lo, hi = prof.window()
    probes = scopes.spans(prof, "repro.probe", lo, hi)
    ns = sum(scopes.scope_ns(prof, scopes.chips(prof, run.chips), "loss_probe", lo, hi))
    if not probes or not ns:
        return None
    return ns / len(probes) / 1e6
