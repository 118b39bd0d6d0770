"""draws_ms (ms/round): device time of the channel's random draws per
round, per chip (core/ota.py ``_cms_slab_inputs``, core/channel.py
``cms_inputs``: threefry bits, uniform angles, exponential magnitudes
per leaf, packed into slabs): the leaf ops between the last streamed
transmit and the finishing channel launch, where the compiler places
them."""


def read(view):
    return view.per_round_ms(lambda r: r.draws)
