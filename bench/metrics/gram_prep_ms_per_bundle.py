"""Kernel layer (``repro.kernels.ell_gram`` panel preparation): the
device time of the ops under the program's ``bundle_gram`` scope other
than the Gram kernel's events (padding x, x as a column, the values'
cast) per (G, v) call of the traced rounds, in ms. Nothing where no op
carries the scope."""

from bench import scopes, trace_reduce


def read(run):
    return scopes.per_bundle_ms(run, "bundle_gram", skip=trace_reduce.is_gram)
