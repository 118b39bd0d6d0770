"""The system under test, driven as its users drive it.

Builds the program's round runner (``repro.core.fl.make_slab_round_runner``,
donated state), its state from ``init_train_state`` in one jitted
program, and runs rounds through the program's own round loop,
``run_rounds_slab``, one scan chunk per call. The number of chips alone
picks the engine, as ``repro.launch.train`` does: backend ``pallas`` on
one chip; on more, backend ``pallas_sharded`` over a client mesh of that
many chips, with the state slabs split over it
(``repro.core.shard.place_slab_state``) and the client batches over its
client axis. Everything the benchmark reads from the program
passes through here: the compiled round program's memory analysis, the
round metrics, and the state slabs after rounds 1 and 3.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.core import (AdaptiveConfig, FLConfig, OTAChannelConfig,
                        UplinkConfig, init_train_state, make_slab_round_runner,
                        make_slab_spec, run_rounds_slab)
from repro.core.shard import place_slab_state
from repro.launch.mesh import make_client_mesh
from repro.models.model import ModelConfig, build_model

from bench import mix as mixes
from bench.reference.common import flatten_paths, init_params

SETUP_ROUNDS = 3          # rounds the reference follows


def model_config(conf: dict) -> ModelConfig:
    """The program's ModelConfig for a configuration file: the published
    config of ``program_arch`` with every model field the file gives."""
    base = get_config(conf["program_arch"])
    fields = {f.name for f in dataclasses.fields(ModelConfig)}
    return dataclasses.replace(
        base, **{k: v for k, v in conf.items() if k in fields})


def seed_key(seed: int) -> jax.Array:
    """A threefry key from any non-negative whole number."""
    words = np.random.SeedSequence(seed).generate_state(2, np.uint32)
    return jax.random.wrap_key_data(jnp.asarray(words, jnp.uint32))


def round_keys(seed: int):
    """(init key, key_fn) of a run: the initial weights' key and the key
    of round t, both derived from the seed alone."""
    base = seed_key(seed)
    rounds = jax.random.fold_in(base, 1)
    return jax.random.fold_in(base, 0), lambda t: jax.random.fold_in(rounds, t)


def layout_of(tree: dict) -> dict:
    """(shape, dtype name) per leaf path of a params pytree."""
    return {"/".join(p): (tuple(leaf.shape), jnp.dtype(leaf.dtype).name)
            for p, leaf in flatten_paths(tree)}


class Program:
    """One cell's program: model, configs, runner and slab layout."""

    def __init__(self, conf: dict, mix: dict, chips: int, ref_layout: dict,
                 loss_fn: Optional[Callable] = None, mesh=None):
        # One chip: no mesh. More: ``mesh`` where given (bench/rehearse.py
        # gives one over described chips), else a client mesh over jax's
        # devices, which raises where it sees fewer than the cell asks for.
        if chips > 1 and mesh is None:
            mesh = make_client_mesh((chips,))
        self.mesh = mesh if chips > 1 else None
        backend = "pallas" if self.mesh is None else "pallas_sharded"
        self.cfg = model_config(conf)
        self.model = build_model(self.cfg)
        mixes.check(mix)
        fed, eng, ch, sv = (mix["federation"], mix["engine"], mix["channel"],
                            mix["server"])
        self.rounds_per_chunk = int(eng["rounds_per_chunk"])
        chan = {k: v for k, v in ch.items() if k != "uplink"}
        self.channel = OTAChannelConfig(
            backend=backend, uplink=UplinkConfig(**ch["uplink"]), **chan)
        self.server = AdaptiveConfig(backend=backend, **sv)
        self.fl = FLConfig(n_clients=int(fed["clients_per_round"]),
                           client_chunk=int(eng["client_chunk"]))
        model = self.model
        loss = loss_fn or (lambda p, b: model.loss_fn(p, b))
        self.run_chunk = make_slab_round_runner(
            loss, self.channel, self.server, self.fl, mesh=self.mesh,
            donate=True)
        shapes = jax.eval_shape(model.init, jax.random.key(0))
        got, want = layout_of(shapes), {
            "/".join(p): (tuple(s), jnp.dtype(d).name)
            for p, (s, d) in flatten_paths(ref_layout)}
        if got != want:
            diff = sorted(set(got.items()) ^ set(want.items()))[:6]
            raise ValueError(f"the program's parameter layout differs from "
                             f"the reference's: {diff}")
        self.ref_layout = ref_layout
        self.spec = make_slab_spec(shapes, shards=chips)

    def init_state(self, init_key):
        """The resident state, drawn and packed in one jitted program;
        on a mesh, split over it as the round runner returns it."""
        spec, ad, layout = self.spec, self.server, self.ref_layout
        state = jax.jit(lambda k: init_train_state(
            ad, init_params(layout, k), spec=spec))(init_key)
        return state if self.mesh is None else place_slab_state(state,
                                                                self.mesh)

    def leaf_norms(self):
        """Jitted (padded,) slab -> per-leaf L2 norms, in leaf order."""
        spec = self.spec

        def f(slab):
            return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(
                jax.lax.dynamic_slice_in_dim(slab, off, size))))
                for off, size in zip(spec.offsets, spec.sizes)])

        return jax.jit(f)

    def change_norms(self):
        """Jitted (w slab, init key) -> per-leaf norms of w - w_0."""
        spec, layout = self.spec, self.ref_layout

        def f(w, init_key):
            w0 = init_params(layout, init_key)
            out = []
            for (_, leaf), off, size in zip(flatten_paths(w0), spec.offsets,
                                            spec.sizes):
                d = (jax.lax.dynamic_slice_in_dim(w, off, size)
                     - leaf.reshape(-1).astype(jnp.float32))
                out.append(jnp.sqrt(jnp.sum(jnp.square(d))))
            return jnp.stack(out)

        return jax.jit(f)


class RoundLoop:
    """Runs the program's rounds through ``run_rounds_slab``.

    ``chunk(t)`` dispatches the scan chunk that starts at round t and
    returns its history once ``run_rounds_slab`` has read the round
    metrics back (the sync that ends every chunk)."""

    def __init__(self, program: Program, state, key_fn, batch_fn,
                 run_chunk: Callable):
        self.p, self.state, self.key_fn = program, state, key_fn
        self.batch_fn, self.run_chunk = batch_fn, run_chunk

    def chunk(self, t: int) -> list:
        r = self.p.rounds_per_chunk
        self.state, hist = run_rounds_slab(
            self.run_chunk, self.state, None, self.batch_fn, t + r, chunk=r,
            key_fn=self.key_fn, start_round=t)
        return hist


def compile_round_program(program: Program, state, key_fn, batch_fn):
    """Lower and compile the round program for the window's shapes.

    Returns ``(call, memory)``: ``call`` runs that executable (inputs put
    on the shardings it was compiled for), and ``memory`` its planned
    bytes per chip from ``memory_analysis()``."""
    r = program.rounds_per_chunk
    ks = jnp.stack([key_fn(t) for t in range(r)])
    bs = jax.tree.map(lambda *xs: jnp.stack(xs),
                      *[batch_fn(t, None) for t in range(r)])
    compiled = program.run_chunk.lower(state, ks, bs).compile()
    mem = compiled.memory_analysis()
    memory = {"argument": int(mem.argument_size_in_bytes),
              "output": int(mem.output_size_in_bytes),
              "temp": int(mem.temp_size_in_bytes),
              "alias": int(mem.alias_size_in_bytes)}
    (_, k_sh, b_sh), _ = compiled.input_shardings

    def call(state, keys, batches):
        with jax.profiler.TraceAnnotation("bench.run_chunk"):
            return compiled(state, jax.device_put(keys, k_sh),
                            jax.device_put(batches, b_sh))

    return call, memory


def setup_rounds(loop: RoundLoop, program: Program, init_key) -> dict:
    """Rounds 0..2 through the window's own call and feed, with the
    readings the reference is compared against: each round's loss and
    clean-aggregate norm, per-leaf norms of the first g_t (from the
    optimizer's first moment after one round, Delta_1 / (1 - beta1)) and
    of the weights' change after three rounds."""
    norms, change = program.leaf_norms(), program.change_norms()
    b1 = program.server.beta1
    hist = []
    read = {}
    for t in range(SETUP_ROUNDS):
        hist += loop.chunk(t)
        if t == 0:
            read["g1_leaf"] = np.asarray(norms(loop.state.opt[0])) / (1 - b1)
    read["dw_leaf"] = np.asarray(change(loop.state.w, init_key))
    read["loss"] = [h["loss"] for h in hist]
    read["clean_norm"] = [h["grad_norm"] for h in hist]
    return read


def window(loop: RoundLoop, start: int, seconds: float):
    """Chunks from round ``start`` until ``seconds`` have passed; the
    window closes on the metrics sync of the chunk in flight. Returns
    (wall seconds, the loss of every round run)."""
    t, losses = start, []
    t0 = time.perf_counter()
    while True:
        hist = loop.chunk(t)
        t += len(hist)
        losses += [h["loss"] for h in hist]
        now = time.perf_counter()
        if now - t0 >= seconds:
            return now - t0, losses
