"""stage_unnamed_ms (ms/round): device time per round, per chip, of the
round-program leaf ops under none of the program's stage names: the
guard that the names still cover the round (a stage dropped or renamed
in the program shows here). Read through bench/scopes.py."""

from bench.scopes import stage_ms


def read(view):
    return stage_ms(view, (None,))
