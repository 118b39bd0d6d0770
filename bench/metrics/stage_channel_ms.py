"""stage_channel_ms (ms/round): device time per round, per chip, of the
leaf ops under ``stream.channel``: the finishing channel launch (kernel
``ota_channel``, or ``ota_transmit_quant`` and ``ota_receive`` on a
quantized uplink) with its operand padding. Read through
bench/scopes.py."""

from bench.scopes import stage_ms


def read(view):
    return stage_ms(view, ("stream.channel",))
