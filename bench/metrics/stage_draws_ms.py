"""stage_draws_ms (ms/round): device time per round, per chip, of the
leaf ops under ``stream.draws``: participation, fading, the
interference's CMS inputs and the rounding draws (core/channel.py,
core/ota.py). Read through bench/scopes.py."""

from bench.scopes import stage_ms


def read(view):
    return stage_ms(view, ("stream.draws",))
