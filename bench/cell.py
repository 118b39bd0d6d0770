"""Resolve a cell of ``BENCHMARK.json`` to its files, by name alone.

A cell (an entry of ``workloads``) names a configuration and a traffic
mix. Everything that belongs to one of them, or to one per-layer metric,
is a file of its own that is found here by its name:

    configs[i].file                  the configuration's sizes (JSON); its
                                     ``reference`` key names the plain
                                     reference module under bench/reference/
    bench/traffic/<traffic>.json     the traffic mix
    bench/limits/<workload>.json     the limits of the numbers compared
    bench/metrics/<metric>.py        the reader of a per-layer metric

Adding a cell, a mix or a metric adds files and entries; no code here
names one.
"""

from __future__ import annotations

import dataclasses
import importlib
import importlib.util
import json
import os
import re

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    conf: dict             # the configuration file's contents
    mix: dict              # the traffic file's contents
    limits: dict           # number name -> limit
    end_to_end: list       # metric entries this cell reports
    per_layer: list
    reference: object      # the configuration's reference module
    readers: dict          # per-layer metric name -> read(ctx) function


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _reports(metric: dict, cell: str, cell_e2e: set) -> bool:
    if "workloads" in metric:
        return cell in metric["workloads"]
    return metric.get("moves", metric["name"]) in cell_e2e


def resolve(workload: str, bench_file: str | None = None,
            data_dir: str = BENCH_DIR) -> Cell:
    """The cell ``workload`` of ``bench_file`` (default: the checkout's
    BENCHMARK.json); traffic and limits files are read from ``data_dir``
    (default: bench/)."""
    bench_file = bench_file or os.path.join(ROOT, "BENCHMARK.json")
    with open(bench_file) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in {bench_file}; "
                       f"known: {sorted(cells)}")
    w = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    with open(os.path.join(ROOT, configs[w["config"]]["file"])) as f:
        conf = json.load(f)
    with open(os.path.join(data_dir, "traffic", w["traffic"] + ".json")) as f:
        mix = json.load(f)
    limits = {}
    lim_path = os.path.join(data_dir, "limits", workload + ".json")
    if os.path.exists(lim_path):
        with open(lim_path) as f:
            limits = {k: v["limit"] for k, v in json.load(f).items()}
    e2e = [m for m in bench["end_to_end"]
           if "workloads" not in m or workload in m["workloads"]]
    e2e_names = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if _reports(m, workload, e2e_names)]
    readers = {}
    for m in per_layer:
        mod = load_module(os.path.join(BENCH_DIR, "metrics", m["name"] + ".py"),
                          "bench_metric_" + m["name"].replace(".", "_"))
        readers[m["name"]] = mod.read
    ref = importlib.import_module("bench.reference." + conf["reference"])
    return Cell(workload, int(w["chips"]), conf, mix, limits, e2e,
                per_layer, ref, readers)
