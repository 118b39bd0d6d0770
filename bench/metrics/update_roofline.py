"""update_roofline (%): the least time of the server update's work
(bench/counts.py: w, Delta, v in f32, each read and written once) over
the device time of the update's Pallas call (kernels/adaptive_update.py;
the round's Pallas call that returns a tuple of state slabs)."""

from bench.counts import least_seconds


def read(view):
    f = view.facts
    ms = view.per_round_ms(lambda r: r.update)
    if not ms:
        return None
    return 100.0 * least_seconds(f["update_bytes"], 0, f["peak"]) / (ms * 1e-3)
