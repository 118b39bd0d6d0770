"""client_ms (ms/round): device time of the clients' forward and backward
passes per round, per chip (models/, core/stream.py ``produce``): the
leaf ops of the round's client phase (up to its last streamed transmit)
that are neither Pallas calls nor of the state slab's size (0.9 of its
entries or more: the model broadcast, the gradient packing and the
clean-sum pass are slab work)."""

from bench.trace import is_pallas, out_entries


def read(view):
    slab_like = 0.9 * view.facts["slab"]
    return view.per_round_ms(lambda r: [
        o for o in r.client
        if not is_pallas(o[0]) and out_entries(o[0]) < slab_like])
