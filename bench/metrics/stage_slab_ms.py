"""stage_slab_ms (ms/round): device time per round, per chip, of the
slab-sized glue around the kernels that the program names
``fl.broadcast`` (the weight slab unpacked into the clients' pytree),
``stream.pack`` (gradient packing, the clean and loss sums) and
``fl.cast`` (the storage-dtype round trip of the weights). Read through
bench/scopes.py."""

from bench.scopes import stage_ms


def read(view):
    return stage_ms(view, ("fl.broadcast", "stream.pack", "fl.cast"))
