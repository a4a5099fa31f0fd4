"""Collectives (``repro.core.comm`` on the mesh): the device time of the
ops under the program's ``allreduce_gv`` scope, the (G, v) sum over the
"cols" axis, per (G, v) call of the traced rounds on one chip, mean
over the chips, in ms. Nothing where no op carries the scope (one
device: the sum is the identity)."""

from bench import scopes


def read(run):
    return scopes.per_bundle_ms(run, "allreduce_gv")
