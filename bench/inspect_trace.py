#!/usr/bin/env python3
"""Print what a profiler trace holds, to read it by hand before writing
a reader against it, and keep its plain form.

    python3 bench/inspect_trace.py <trace dir> [--keep out.json.gz]

Prints the planes and lines with their event counts, every stat key the
device ops carry with an example, the device ops that took most time
with their JAX name stacks, and the benchmark's host spans.
"""

from __future__ import annotations

import argparse
import collections
import gzip
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from bench import trace as tr  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("trace_dir")
    ap.add_argument("--keep", default=None,
                    help="write the plain form (bench/trace.py load) here")
    ap.add_argument("--top", type=int, default=60)
    args = ap.parse_args(argv)
    from jax.profiler import ProfileData
    path = tr.xplane_file(args.trace_dir)
    print(f"{path}: {os.path.getsize(path)} bytes")
    pd = ProfileData.from_file(path)
    for plane in pd.planes:
        lines = [(ln.name, sum(1 for _ in ln.events)) for ln in plane.lines]
        print(f"plane {plane.name}: {lines}")
        if not plane.name.startswith("/device:"):
            continue
        for ln in plane.lines:
            keys = {}
            for ev in ln.events:
                for k, v in ev.stats:
                    keys.setdefault(k, str(v)[:160])
            print(f"  line {ln.name!r} stat keys:")
            for k, v in keys.items():
                print(f"    {k} = {v}")
    raw = tr.load(path)
    for dev, d in sorted(raw["devices"].items()):
        agg = collections.defaultdict(lambda: [0, 0, ""])
        for n, _, dur, st in d["ops"]:
            a = agg[n]
            a[0] += dur
            a[1] += 1
            a[2] = a[2] or st
        tot = sum(a[0] for a in agg.values())
        print(f"{dev}: {len(d['ops'])} ops, {tot * 1e-9:.4f} s of op time, "
              f"{len(d['modules'])} module executions "
              f"{collections.Counter(m[0] for m in d['modules']).most_common(8)}")
        for n, (dur, cnt, st) in sorted(agg.items(),
                                       key=lambda kv: -kv[1][0])[:args.top]:
            print(f"  {dur * 1e-9:10.5f} s {cnt:7d}x  {n[:70]}  | {st[:200]}")
    hosts = collections.Counter(h[0] for h in raw["host"])
    print(f"host spans: {dict(hosts)}")
    if args.keep:
        with gzip.open(args.keep, "wt") as f:
            json.dump(raw, f)
        print(f"kept {args.keep}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
