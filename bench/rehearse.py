#!/usr/bin/env python3
"""Compile a cell's round program for a described TPU v5e, without one.

    JAX_PLATFORMS=cpu python3 bench/rehearse.py --workload minicpm3-l4.sonwp50
    JAX_PLATFORMS=cpu python3 bench/rehearse.py --workload hymba-l2.silo32-4chip

Builds the program exactly as bench/run.py does, lowers its round
program on the state, key and batch shapes of the window, compiles it
with the TPU compiler and prints ``memory_analysis()``, per chip. A
one-chip cell is placed on one chip of a described v5e:2x2; a cell on
four chips on a client mesh over all four, its state slabs split and its
client batches sharded as the round runner takes them. A program the
compiler refuses, or that does not fit, fails here at no chip time.
Nothing runs, so it gives bytes and host compile seconds, never a
device time. ``--hlo-out`` keeps the compiled program's text.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ["REPRO_PALLAS_INTERPRET"] = "0"   # the kernels as the chip runs them


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--hlo-out", default=None,
                    help="write the compiled round program's text here")
    args = ap.parse_args(argv)
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import (AxisType, NamedSharding, PartitionSpec as P,
                              SingleDeviceSharding)

    from bench.cell import resolve
    from bench.program import Program, round_keys
    from bench.reference.common import init_params
    from repro.core import init_train_state

    jax.config.update("jax_enable_compilation_cache", False)
    cell = resolve(args.workload)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    layout = cell.reference.layout(cell.conf)
    mesh = None
    if cell.chips > 1:
        # the client mesh of repro.launch.mesh.make_client_mesh, over
        # described chips
        mesh = jax.make_mesh((cell.chips,), ("data",),
                             axis_types=(AxisType.Auto,),
                             devices=topo.devices[:cell.chips])
        rep = NamedSharding(mesh, P())
        split, batch = NamedSharding(mesh, P("data")), NamedSharding(
            mesh, P(None, "data"))
    else:
        rep = split = batch = SingleDeviceSharding(topo.devices[0])
    program = Program(cell.conf, cell.mix, cell.chips, layout, mesh=mesh)
    init_key, key_fn = round_keys(0)
    spec, ad = program.spec, program.server
    st = jax.eval_shape(lambda k: init_train_state(
        ad, init_params(layout, k), spec=spec), init_key)
    st = jax.tree.map(lambda a: jax.ShapeDtypeStruct(
        a.shape, a.dtype, sharding=split if a.ndim else rep), st)
    fed = cell.mix["federation"]
    r = program.rounds_per_chunk
    ks = jax.eval_shape(lambda: jnp.stack([key_fn(t) for t in range(r)]))
    ks = jax.ShapeDtypeStruct(ks.shape, ks.dtype, sharding=rep)
    bs = {"tokens": jax.ShapeDtypeStruct(
        (r, int(fed["clients_per_round"]), int(fed["batch"]), int(fed["seq"])),
        jnp.int32, sharding=batch)}
    t0 = time.perf_counter()
    compiled = program.run_chunk.lower(st, ks, bs).compile()
    mem = compiled.memory_analysis()
    if args.hlo_out:
        with open(args.hlo_out, "w") as f:
            f.write(compiled.as_text())
    out = {"workload": args.workload, "chips": cell.chips,
           "params": spec.total, "padded": spec.padded,
           "argument": mem.argument_size_in_bytes,
           "output": mem.output_size_in_bytes,
           "temp": mem.temp_size_in_bytes,
           "alias": mem.alias_size_in_bytes,
           "generated_code": mem.generated_code_size_in_bytes,
           "host_compile_s": round(time.perf_counter() - t0, 1)}
    out["planned_gb"] = (out["argument"] + out["output"] + out["temp"]
                         - out["alias"]) / 1e9
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
