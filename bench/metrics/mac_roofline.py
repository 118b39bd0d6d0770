"""mac_roofline (%): the least time the MAC's work needs on this chip
(bench/counts.py: every client's gradient read once at its storage
dtype, the aggregate written once in f32, the interference transform's
operations) over the device time of the MAC's Pallas calls."""

from bench.counts import least_seconds


def read(view):
    f = view.facts
    ms = view.per_round_ms(lambda r: r.mac)
    if not ms:
        return None
    return 100.0 * least_seconds(f["mac_bytes"], f["mac_ops"],
                                 f["peak"]) / (ms * 1e-3)
