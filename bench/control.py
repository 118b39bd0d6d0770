#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from, on the chip.

    python3 bench/control.py --workload hymba-l2.silo8 \
        --seeds 11,12,...  --control-seeds 11,12,13 --out readings.jsonl \
        [--kinds control,control_model,half]

For every seed: the program's three set-up rounds (the timed path, at
the cell's sizes, through the same compiled round program) against the
plain float32 reference, which gives the sound readings. For each
control seed also:

* ``control``: the reference put in the program's place, computed one
  precision step down from the configuration (float8 e4m3 matmul
  operands with per-tensor scales for the bfloat16 model; bfloat16 for
  the float32 aggregate and server state);
* ``control_model``: the same with the model alone one step down, the
  aggregate and server state at their float32;
* ``half``: the reference with half of every client's batch left out,
  the mean taken over the rest (a planted fault);
* ``exchange`` (cells on several chips): the program itself with the
  exchange between chips left out, each chip keeping its own slice of
  its own clients' sums (``exchange_left_out``, a planted fault).

A step that returns its state unchanged reads 1 on ``dw_gap`` by
construction and needs no run. One JSON line per seed and kind.
"""

from __future__ import annotations

import argparse
import contextlib
import gc
import json
import math
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

# (model, state) precision of each control
CONTROLS = {"control": ("fp8", "bf16"), "control_model": ("fp8", "f32")}
KINDS = (*CONTROLS, "half", "exchange")


@contextlib.contextmanager
def exchange_left_out():
    """Plant a fault in the sharded round built inside the block: its
    reduce-scatter (``repro.core.shard.psum_scatter_slab``) hands each
    chip its own slice of its own partial sums, unsummed over chips."""
    import jax
    from repro.core import shard
    from repro.core.ota import linear_shard_index
    real = shard.psum_scatter_slab

    def local(x, axes, dim=0):
        size = x.shape[dim] // math.prod(jax.lax.axis_size(a) for a in axes)
        return jax.lax.dynamic_slice_in_dim(
            x, linear_shard_index(axes) * size, size, axis=dim)

    shard.psum_scatter_slab = local
    try:
        yield
    finally:
        shard.psum_scatter_slab = real


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--out", required=True)
    ap.add_argument("--kinds", default=",".join(KINDS),
                    help="the readings to take on each control seed")
    args = ap.parse_args(argv)
    with open(args.out, "a") as out:
        for rec in readings(args.workload,
                            [int(s) for s in args.seeds.split(",") if s],
                            {int(s) for s in args.control_seeds.split(",")
                             if s}, kinds=args.kinds.split(",")):
            out.write(json.dumps(rec) + "\n")
            out.flush()
            print(json.dumps(rec), flush=True)
    return 0


def readings(workload, seeds, cseeds, bench_file=None, data_dir=None,
             need_tpu=True, cache=True, kinds=KINDS):
    """Yield one record per seed and kind (see the module docstring)."""
    import jax.numpy as jnp

    from bench import compare
    from bench import program as prog
    from bench.cell import resolve
    from bench.reference.common import Precision, init_params
    from bench.reference.fl import reference_rounds
    from bench.run import check_devices, enable_cache
    from bench.traffic import Traffic

    from bench.cell import BENCH_DIR
    cell = resolve(workload, bench_file, data_dir or BENCH_DIR)
    if cache:
        enable_cache()
    devs = check_devices(cell.chips, need_tpu)[:cell.chips]
    layout = cell.reference.layout(cell.conf)
    program = prog.Program(cell.conf, cell.mix, cell.chips, layout)
    programs = {}          # kind -> [program, compiled call]

    def program_readings(kind, init_key, key_fn, batch_fn):
        with (exchange_left_out() if kind == "exchange"
              else contextlib.nullcontext()):
            if kind not in programs:
                programs[kind] = [program if kind == "program" else
                                  prog.Program(cell.conf, cell.mix,
                                               cell.chips, layout), None]
            p, call = programs[kind]
            state = p.init_state(init_key)
            if call is None:
                call = programs[kind][1] = prog.compile_round_program(
                    p, state, key_fn, batch_fn)[0]
        loop = prog.RoundLoop(p, state, key_fn, batch_fn, call)
        del state
        read = prog.setup_rounds(loop, p, init_key)
        loop.state = None
        del loop
        gc.collect()
        return read

    for seed in seeds:
        t0 = time.perf_counter()
        init_key, key_fn = prog.round_keys(seed)
        traffic = Traffic(cell.mix["federation"], cell.conf["vocab"], seed)

        def batch_fn(t, _key):
            return {"tokens": jnp.asarray(traffic.batch_of(t))}

        read = program_readings("program", init_key, key_fn, batch_fn)
        faulty = None
        if seed in cseeds and "exchange" in kinds and cell.chips > 1:
            faulty = program_readings("exchange", init_key, key_fn, batch_fn)
        t1 = time.perf_counter()
        batches = [traffic.batch_of(t) for t in range(prog.SETUP_ROUNDS)]
        keys = [key_fn(t) for t in range(prog.SETUP_ROUNDS)]
        params0 = init_params(layout, init_key)

        def ref_run(pr=Precision(), drop_half=False):
            return reference_rounds(
                lambda p, tok, pr: cell.reference.loss(cell.conf, p, tok, pr),
                params0, batches, keys, cell.mix, pr=pr, drop_half=drop_half,
                devices=devs)

        ref = ref_run()
        t2 = time.perf_counter()
        yield ({"seed": seed, "kind": "program",
               **compare.numbers(read, ref), "loss": read["loss"],
               "ref_loss": ref["loss"], "program_s": t1 - t0,
               "reference_s": t2 - t1})
        for kind in kinds if seed in cseeds else ():
            if kind == "exchange":
                if faulty is not None:
                    yield ({"seed": seed, "kind": kind,
                            **compare.numbers(faulty, ref),
                            "loss": faulty["loss"]})
                continue
            other = (ref_run(drop_half=True) if kind == "half" else
                     ref_run(Precision(*CONTROLS[kind])))
            yield ({"seed": seed, "kind": kind, **compare.numbers(other, ref),
                    "loss": other["loss"]})
        del params0
        gc.collect()


if __name__ == "__main__":
    sys.exit(main())
