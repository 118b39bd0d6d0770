"""mac_ms (ms/round): device time of the MAC's Pallas calls per round,
per chip: the streamed transmit launches and the finishing channel
launch (kernels/ota_channel.py). They are the round's Pallas calls other
than the update (bench/trace.py ``Round``)."""


def read(view):
    return view.per_round_ms(lambda r: r.mac)
