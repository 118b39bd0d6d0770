"""stage_client_ms (ms/round): device time per round, per chip, of the
round-program leaf ops the program names ``stream.client``
(core/stream.py ``produce``: each client's batch, forward and backward).
Read through bench/scopes.py."""

from bench.scopes import stage_ms


def read(view):
    return stage_ms(view, ("stream.client",))
