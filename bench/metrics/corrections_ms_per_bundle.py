"""Round body (``repro.core.engine.inner_corrections``): the device time
of the ops under the program's ``corrections`` scope per (G, v) call of
the traced rounds, in ms. Nothing where no op carries the scope."""

from bench import scopes


def read(run):
    return scopes.per_bundle_ms(run, "corrections")
