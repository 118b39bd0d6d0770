"""gap_feed_ms (ms/chunk): the part of the device-idle gap between
successive round programs (the gap ``host_gap_ms`` measures) that the
round loop's host spans ``fl.keys``, ``fl.batch`` and ``fl.dispatch``
cover (core/fl.py ``run_rounds_slab``: round keys, client batches, the
call). Read through bench/scopes.py."""

from bench.scopes import gap_ms


def read(view):
    return gap_ms(view, ("fl.keys", "fl.batch", "fl.dispatch"))
