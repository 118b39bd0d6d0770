"""comm_roofline (%): the least time a round's exchange needs on this
chip's links (bench/counts.py ``exchange_bytes``: (P - 1) / P of each
float32 slab gathered or scattered, at the chip's inter-chip bandwidth)
over the device time of the round's collective ops on the chip the
readers read."""


def read(view):
    f = view.facts
    ms = view.collective_ms()
    if not ms or not f["comm_bytes"]:
        return None
    return 100.0 * f["comm_bytes"] / f["peak"]["ici_bytes_s"] / (ms * 1e-3)
