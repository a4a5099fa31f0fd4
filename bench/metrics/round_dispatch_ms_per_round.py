"""Host dispatch (``Session._advance`` → the backend's advance): the
host time in the program's ``repro.round`` spans in the traced window
over the rounds they advanced (each span's ``rounds`` arg), in ms. On
the mesh ``HybridDriver.advance`` dispatches one program a round, so
this is what a round costs the host. Nothing where the program has no
such span."""

from bench import scopes


def read(run):
    prof = scopes.of_run(run)
    if prof is None:
        return None
    lo, hi = prof.window()
    rounds = scopes.spans(prof, "repro.round", lo, hi)
    advanced = sum(int(h.args.get("rounds", 0)) for h in rounds)
    if not advanced:
        return None
    return sum(h.end - h.start for h in rounds) / advanced / 1e6
