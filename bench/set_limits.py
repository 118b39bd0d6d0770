#!/usr/bin/env python3
"""Set a cell's limits from the readings of bench/control.py.

    python3 bench/set_limits.py readings.jsonl --workload hymba-l2.silo8

For every number compared (bench/compare.py):

* lower: the largest reading of the sound program over its seeds;
* upper: the smallest reading of each control (``control``: model and
  state one step down; ``control_model``: the model alone), where it is
  three times the lower or more; of each planted fault's (half of the
  batch left out; on several chips, the exchange left out), where ten
  times or more; and 1.0 for ``dw_gap`` (a state left unchanged reads 1
  there), where three times or more; the least of these;
* limit: ``lower * (upper / lower) ** 0.6``, two significant digits: above
  the lower and below the upper, with more room above the lower.

A number with no upper reading gets no limit and is printed only. Writes
``bench/limits/<workload>.json`` with each limit's readings, and prints
for every control and fault reading whether it fails a limit: each has
to fail one number, not each.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

NUMBERS = ("loss_gap", "gnorm_gap", "grad1_gap", "dw_gap")
CONTROLS = ("control", "control_model")
FAULTS = {"half": "half-batch fault", "exchange": "exchange left out"}
SHARE = 0.6


def limits_from(records: list) -> dict:
    out = {}
    by = {}
    for r in records:
        by.setdefault(r["kind"], []).append(r)
    for n in NUMBERS:
        prog = [r[n] for r in by.get("program", [])]
        if not prog:
            continue
        lower = max(prog)
        cands = []
        ctl = {}
        for kind in CONTROLS:
            got = [r[n] for r in by.get(kind, [])]
            if got:
                ctl[kind] = min(got)
                if min(got) >= 3 * lower:
                    cands.append((kind, min(got)))
        faults = {}
        for kind, label in FAULTS.items():
            got = [r[n] for r in by.get(kind, [])]
            faults[kind] = min(got) if got else None
            if got and min(got) >= 10 * lower:
                cands.append((label, min(got)))
        if n == "dw_gap" and 1.0 >= 3 * lower:
            cands.append(("state left unchanged", 1.0))
        entry = {"lower": lower, "seeds": len(prog),
                 **{k: ctl.get(k) for k in CONTROLS},
                 "half_batch": faults["half"]}
        if faults["exchange"] is not None:
            entry["exchange_left_out"] = faults["exchange"]
        if cands:
            src, upper = min(cands, key=lambda c: c[1])
            lim = float(f"{lower * (upper / lower) ** SHARE:.2g}")
            entry.update(limit=lim, upper=upper, upper_from=src)
        else:
            entry.update(limit=None, upper=None, upper_from=None)
        out[n] = entry
    return out


def failures(records: list, limits: dict) -> list:
    """(kind, seed, [numbers over their limits]) of every control and
    fault reading."""
    return [(r["kind"], r["seed"],
             [n for n, v in limits.items() if not r[n] <= v["limit"]])
            for r in records if r["kind"] != "program"]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("readings")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--out-dir", default=os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "limits"))
    args = ap.parse_args(argv)
    with open(args.readings) as f:
        recs = [json.loads(line) for line in f if line.strip()]
    lim = limits_from(recs)
    kept = {k: v for k, v in lim.items() if v["limit"] is not None}
    os.makedirs(args.out_dir, exist_ok=True)
    with open(os.path.join(args.out_dir, args.workload + ".json"), "w") as f:
        json.dump(kept, f, indent=1)
    json.dump(lim, sys.stdout, indent=1)
    print()
    for kind, seed, over in failures(recs, kept):
        print(f"{kind} seed {seed}: over the limit on {over or 'nothing'}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
