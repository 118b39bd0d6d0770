"""stage_transmit_ms (ms/round): device time per round, per chip, of the
leaf ops under ``stream.transmit``: the streamed transmit launches
(kernel ``ota_transmit_stream``) with their operand padding. Read
through bench/scopes.py."""

from bench.scopes import stage_ms


def read(view):
    return stage_ms(view, ("stream.transmit",))
