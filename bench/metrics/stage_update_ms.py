"""stage_update_ms (ms/round): device time per round, per chip, of the
leaf ops under ``fl.update``: the server update (kernel
``adaptive_update``), the dead-round selects and the round metrics'
norms. Read through bench/scopes.py."""

from bench.scopes import stage_ms


def read(view):
    return stage_ms(view, ("fl.update",))
