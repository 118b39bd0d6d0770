"""gap_sync_ms (ms/chunk): the part of the device-idle gap between
successive round programs that the round loop's host spans ``fl.sync``
and ``fl.history`` cover (core/fl.py ``run_rounds_slab``: reading the
round metrics back, the history records). Read through
bench/scopes.py."""

from bench.scopes import gap_ms


def read(view):
    return gap_ms(view, ("fl.sync", "fl.history"))
