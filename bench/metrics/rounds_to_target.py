"""Solver layer (``repro.api.Session`` and its schedule): the rounds
done at the first loss probe at or below the config's ``target_loss``,
as ``Session.rounds_done`` counts them. Nothing when the window never
reached the target."""


def read(run):
    return None if run.crossing is None else run.crossing[1]
