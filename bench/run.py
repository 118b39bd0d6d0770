#!/usr/bin/env python3
"""Benchmark of federated over-the-air training on the chip: one cell, one
seed, one line of JSON.

    python3 bench/run.py --workload hymba-l2.silo8 --seed 7 --seconds 10 --trace 0

Set-up builds the program's round runner and its state from the seed,
compiles (or loads from the compile cache in ``.bench_cache/``) the round
program, and drives rounds 0..2 through the program's own round loop;
those three rounds are the warm-up and the readings that ``correct`` is
decided on. The window then runs the same loop, one scan chunk per
call, until ``--seconds`` have passed. Once it has closed and the
program's state is freed, the plain float32 reference follows the same
three rounds and the two are compared (``bench/compare.py``).

``--trace 0`` reports the cell's end-to-end metrics; ``--trace 1``
profiles the window and reports its per-layer metrics. The last lines of
standard error, and the ``checks`` key that ends the JSON line, give
every number compared beside its limit. Without a TPU, or with fewer
chips than the cell asks for, it exits non-zero and prints no result.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
CACHE_DIR = os.path.join(ROOT, ".bench_cache")
# libtpu logs to /tmp/tpu_logs unless told otherwise; write nothing there.
os.environ.setdefault("TPU_LOG_DIR", "disabled")
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)


class Refused(Exception):
    """The run cannot measure: no result line, non-zero exit."""


class CompileClock:
    """Backend compiles and persistent-cache hits and misses, counted
    from registration on (``jax.monitoring`` events)."""

    def __init__(self):
        import jax
        self.compiles, self.compile_s, self.hits, self.misses = 0, 0.0, 0, 0

        def on_duration(event, duration, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self.compiles += 1
                self.compile_s += duration

        def on_event(event, **_):
            if event == "/jax/compilation_cache/cache_hits":
                self.hits += 1
            elif event == "/jax/compilation_cache/cache_misses":
                self.misses += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    def snapshot(self) -> dict:
        return {"compiles": self.compiles, "compile_s": self.compile_s,
                "cache_hits": self.hits, "cache_misses": self.misses}


_CLOCK = []


def compile_clock() -> CompileClock:
    if not _CLOCK:
        _CLOCK.append(CompileClock())
    return _CLOCK[0]


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--trace-dir", default=None,
                    help="keep the raw profile here (default: a temporary "
                         "directory, removed once it is read)")
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be a non-negative whole number")
    return args


def enable_cache() -> str:
    """The compile cache at a fixed path inside the checkout, unbounded
    in size, caching every program (the program's own
    ``enable_compile_cache`` then takes this directory)."""
    import jax
    from repro.launch.compile_cache import enable_compile_cache
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_compilation_cache_max_size", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return enable_compile_cache()


def check_devices(chips: int, need_tpu: bool):
    import jax
    from repro.kernels.interpret import INTERPRET_ENV, resolve_interpret
    devs = jax.devices()
    if need_tpu:
        if devs[0].platform != "tpu":
            raise Refused(f"no TPU: jax's first device is "
                          f"{devs[0].platform!r}")
        if resolve_interpret(None):
            raise Refused(f"Pallas interpret mode is forced "
                          f"({INTERPRET_ENV}={os.environ.get(INTERPRET_ENV)})")
    if len(devs) < chips:
        raise Refused(f"the cell needs {chips} chips; jax sees {len(devs)}")
    return devs


def facts_of(cell, program, rounds: int, kind: str) -> dict:
    from bench import counts
    fed = cell.mix["federation"]
    layout = cell.reference.layout(cell.conf)
    dims = cell.reference.dims(cell.conf, int(fed["seq"]))
    n = int(fed["clients_per_round"])
    chunk = int(cell.mix["engine"]["client_chunk"])
    return {"rounds": rounds, "chips": cell.chips,
            "mac_calls": -(-n // chunk) + 1,
            "peak": counts.peaks(kind),
            "flops_round": n * counts.client_flops(
                layout, dims, int(fed["batch"]), int(fed["seq"])),
            "mac_bytes": counts.mac_bytes(layout, n),
            "mac_ops": counts.mac_ops(layout),
            "update_bytes": counts.update_bytes(layout),
            "comm_bytes": counts.exchange_bytes(program.spec.padded,
                                                cell.chips),
            "slab": program.spec.padded}


def run(args, bench_file=None, data_dir=None, need_tpu=True, cache=True,
        loss_fn=None, wrap_chunk=None) -> dict:
    """One run of a cell; returns the result object. ``loss_fn`` and
    ``wrap_chunk`` replace the client loss and wrap the round call, so
    that a test can break the timed path underneath."""
    from bench.cell import resolve
    cell = resolve(args.workload, bench_file, data_dir or BENCH_DIR)
    import jax
    if cache:
        enable_cache()
    devs = check_devices(cell.chips, need_tpu)
    clock = compile_clock()
    from bench import compare, program as prog
    from bench.traffic import Traffic
    import jax.numpy as jnp

    c0 = clock.snapshot()
    marks = [("imports and devices", time.perf_counter())]
    layout = cell.reference.layout(cell.conf)
    program = prog.Program(cell.conf, cell.mix, cell.chips, layout,
                           loss_fn=loss_fn)
    init_key, key_fn = prog.round_keys(args.seed)
    traffic = Traffic(cell.mix["federation"], cell.conf["vocab"], args.seed)

    def batch_fn(t, _key):
        with jax.profiler.TraceAnnotation("bench.batch"):
            return {"tokens": jnp.asarray(traffic.batch_of(t))}

    marks.append(("build", time.perf_counter()))
    state = program.init_state(init_key)
    marks.append(("init", time.perf_counter()))
    call, memory = prog.compile_round_program(program, state, key_fn,
                                              batch_fn)
    marks.append(("round program", time.perf_counter()))
    if wrap_chunk is not None:
        call = wrap_chunk(call)
    loop = prog.RoundLoop(program, state, key_fn, batch_fn, call)
    del state
    read = prog.setup_rounds(loop, program, init_key)
    setup_s = time.perf_counter() - T_START
    marks.append(("set-up rounds", T_START + setup_s))
    c1 = clock.snapshot()
    log("set-up phases (s): " + ", ".join(
        f"{name} {t - (marks[i - 1][1] if i else T_START):.3f}"
        for i, (name, t) in enumerate(marks)))
    log(f"set-up {setup_s:.3f} s: {c1['compiles'] - c0['compiles']} "
        f"compiles ({c1['compile_s'] - c0['compile_s']:.1f} s), cache "
        f"{c1['cache_hits'] - c0['cache_hits']} hits "
        f"{c1['cache_misses'] - c0['cache_misses']} misses; round program "
        f"plans {memory} bytes")

    trace_dir = None
    if args.trace:
        trace_dir = args.trace_dir or tempfile.mkdtemp(prefix="bench-trace-")
        jax.profiler.start_trace(trace_dir)
    w_t0 = time.perf_counter_ns()
    wall, losses = prog.window(loop, prog.SETUP_ROUNDS, args.seconds)
    rounds = len(losses)
    w_t1 = time.perf_counter_ns()
    if args.trace:
        jax.profiler.stop_trace()
    c2 = clock.snapshot()
    window_compiles = c2["compiles"] - c1["compiles"]
    peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devs[:cell.chips])
    loop.state = None
    del loop
    gc.collect()

    t_ref = time.perf_counter()
    from bench.reference.common import init_params
    from bench.reference.fl import reference_rounds
    params0 = init_params(layout, init_key)
    ref = reference_rounds(
        lambda p, tok, pr: cell.reference.loss(cell.conf, p, tok, pr),
        params0, [traffic.batch_of(t) for t in range(prog.SETUP_ROUNDS)],
        [key_fn(t) for t in range(prog.SETUP_ROUNDS)], cell.mix,
        devices=devs[:cell.chips])
    del params0
    ref_s = time.perf_counter() - t_ref
    nums = compare.numbers(read, ref)
    nums["window_compiles"] = float(window_compiles)
    limits = dict(cell.limits, window_compiles=0.0)
    correct, rows = compare.judge(nums, limits)
    correct = correct and bool(cell.limits)
    log(f"window {rounds} rounds in {wall:.4f} s; reference {ref_s:.2f} s; "
        f"losses program {read['loss']} reference {ref['loss']}")

    dev = devs[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs), "memory_peak_bytes": peak}
    result = {"correct": correct, "attempted": rounds,
              "failed": sum(not math.isfinite(x) for x in losses)}
    if args.trace:
        from bench import trace as tr
        raw = tr.load(tr.xplane_file(trace_dir))
        raw["window_ns"] = _window_on_trace_clock(raw, w_t0, w_t1)
        view = tr.View(raw, facts_of(cell, program, rounds, dev.device_kind))
        if not args.trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)
        metrics = {}
        for m in cell.per_layer:
            v = cell.readers[m["name"]](view)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        device["busy_s"] = view.mean_busy_s()
        device["window_s"] = view.window_s
        result["breakdown"] = {"device_ops": view.top_ops(),
                               "idle_gaps": view.idle_gaps()}
    else:
        planned = (memory["argument"] + memory["output"] + memory["temp"]
                   - memory["alias"])
        values = {"round_s": wall / rounds, "hbm_gb": planned / 1e9,
                  "setup_s": setup_s}
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end}
    result["metrics"] = metrics
    result["device"] = device
    result["checks"] = {n: {"value": v, "limit": lim} for n, v, lim in rows}
    return result


def _window_on_trace_clock(raw: dict, t0_ns: int, t1_ns: int) -> list:
    """The window's span on the trace's clock: it opens with the first
    benchmark host span (the first chunk's batch) and lasts as long as
    the host clock measured it."""
    spans = [(s, s + d) for _, s, d in raw["host"]]
    if not spans:
        raise Refused("the trace holds none of the benchmark's host spans")
    start = min(s for s, _ in spans)
    return [start, start + (t1_ns - t0_ns)]


def main(argv=None) -> int:
    args = parse(argv)
    try:
        result = run(args)
    except Refused as e:
        log(f"bench/run.py: refused: {e}")
        return 2
    for name, c in result["checks"].items():
        lim = "none" if c["limit"] is None else repr(c["limit"])
        log(f"check {name} {c['value']!r} limit {lim}")
    print(json.dumps(result), flush=True)
    bad = not all(math.isfinite(m["value"]) for m in result["metrics"].values())
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
