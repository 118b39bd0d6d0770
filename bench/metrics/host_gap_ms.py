"""host_gap_ms (ms/chunk): mean device-idle time between successive
executions of the round program (core/fl.py ``run_rounds_slab``: the
host builds the next batch, stacks the keys, dispatches, and syncs on the
round metrics). The breakdown's ``idle_gaps`` names each gap by the
benchmark host span (bench.batch, bench.run_chunk) that covers most of
it, else "host other"."""

from bench.trace import union_ns


def read(view):
    gaps = []
    for dev in view.devices:
        runs = view.main_module(dev)
        ops = view.ops(dev)
        for (_, e0), (s1, _) in zip(runs, runs[1:]):
            inside = [(max(s, e0), min(s + d, s1) - max(s, e0))
                      for _, s, d, _ in ops if s < s1 and s + d > e0]
            gaps.append((s1 - e0) - union_ns(inside))
    return 1e-6 * sum(gaps) / len(gaps) if gaps else None
