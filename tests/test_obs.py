"""The program's own names for what a trace shows (``repro.obs``).

On a tiny two-chunk streamed cell: every stage name reaches the
``op_name`` metadata of the compiled round program, every Pallas launch
carries a kernel name, and a profiler trace of ``run_rounds_slab`` holds,
per chunk, ``fl.chunk`` around ``fl.keys``, ``fl.batch``, ``fl.dispatch``,
``fl.sync`` and ``fl.history`` in that order, with ``SPAN_RECORD``
holding the same spans. Also the compile counter, ``launch.train``'s
``--profile`` and recompile line, and the benchmark's copy of the names.
"""

import glob
import os
import sys

import jax
import jax.numpy as jnp
import pytest
from jax.extend import core as jcore

from repro import obs
from repro.core import (AdaptiveConfig, FLConfig, OTAChannelConfig,
                        UplinkConfig, init_train_state,
                        make_slab_round_runner, make_slab_spec,
                        run_rounds_slab)

REPO_ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
if REPO_ROOT not in sys.path:
    sys.path.insert(0, REPO_ROOT)

from bench import scopes  # noqa: E402

N = 2   # clients, one a chunk: the client scan runs two chunks
# a bf16 leaf, so that the round casts w through its storage dtype
SHAPES = {"a": ((3, 45), jnp.float32), "b": ((130,), jnp.bfloat16)}
LOOP = ["fl.keys", "fl.batch", "fl.dispatch", "fl.sync", "fl.history"]


def _loss(p, batch):
    x = batch["x"]
    return jnp.mean((jnp.sum(p["a"]) * x
                     - jnp.sum(p["b"].astype(jnp.float32))) ** 2)


def _cell(uplink="f32"):
    ks = jax.random.split(jax.random.key(0), len(SHAPES))
    params = {n: jax.random.normal(k, s).astype(d)
              for k, (n, (s, d)) in zip(ks, SHAPES.items())}
    ch = OTAChannelConfig(backend="pallas", uplink=UplinkConfig(mode=uplink))
    ad = AdaptiveConfig(backend="pallas")
    run = make_slab_round_runner(_loss, ch, ad,
                                 FLConfig(n_clients=N, client_chunk=1))
    state = init_train_state(ad, params, spec=make_slab_spec(params))
    return run, state


def _batch_fn(t, _key):
    return {"x": jnp.full((N, 4), 1.0 + t)}


def _one_chunk():
    return (jnp.stack([jax.random.key(1)]),
            jax.tree.map(lambda x: x[None], _batch_fn(0, None)))


def test_every_stage_names_ops_of_the_round_program():
    run, state = _cell()
    text = run.lower(state, *_one_chunk()).compile().as_text()
    assert set(scopes.stage_map(text).values()) == set(obs.STAGES)


def _kernel_names(jaxpr, out):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            out.append(eqn.params["name"])
        for v in eqn.params.values():
            for x in v if isinstance(v, (tuple, list)) else (v,):
                if isinstance(x, jcore.ClosedJaxpr):
                    _kernel_names(x.jaxpr, out)
                elif isinstance(x, jcore.Jaxpr):
                    _kernel_names(x, out)
    return out


@pytest.mark.parametrize("uplink,kernels", [
    ("f32", {"ota_transmit_stream", "ota_channel", "adaptive_update"}),
    ("int8", {"ota_transmit_stream", "ota_transmit_quant", "ota_receive",
              "adaptive_update"}),
])
def test_every_pallas_launch_is_named(uplink, kernels):
    run, state = _cell(uplink)
    names = _kernel_names(jax.make_jaxpr(run)(state, *_one_chunk()).jaxpr,
                          [])
    assert set(names) == kernels
    assert kernels <= set(obs.KERNELS)


def _host_spans(trace_dir):
    path, = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    pd = jax.profiler.ProfileData.from_file(path)
    return sorted((ev.start_ns, ev.name, ev.duration_ns, dict(ev.stats))
                  for plane in pd.planes if plane.name.startswith("/host:")
                  for line in plane.lines for ev in line.events
                  if ev.name.startswith("fl."))


def test_round_loop_spans_nest_per_chunk_in_order(tmp_path):
    run, state = _cell()
    key_fn = jax.random.key
    state, _ = run_rounds_slab(run, state, None, _batch_fn, 1, chunk=1,
                               key_fn=key_fn)          # compiles
    obs.SPAN_RECORD.clear()
    with jax.profiler.trace(str(tmp_path)):
        state, hist = run_rounds_slab(run, state, None, _batch_fn, 3,
                                      chunk=1, key_fn=key_fn, start_round=1)
    assert len(hist) == 2
    spans = _host_spans(str(tmp_path))
    chunks = [s for s in spans if s[1] == "fl.chunk"]
    assert [c[3]["step_num"] for c in chunks] == [1, 2]
    for c in chunks:
        inner = [s[1] for s in spans if s[1] != "fl.chunk"
                 and c[0] <= s[0] and s[0] + s[2] <= c[0] + c[2]]
        assert inner == LOOP
    # the record holds the same spans, in the order they closed
    rec = list(obs.SPAN_RECORD)
    assert [n for n, _, _ in rec] == (LOOP + ["fl.chunk"]) * 2
    assert all(s < e for _, s, e in rec)


def test_profile_dir_traces_the_second_chunk_only(tmp_path):
    run, state = _cell()
    hooked = []
    run_rounds_slab(run, state, None, _batch_fn, 3, chunk=1,
                    key_fn=jax.random.key, profile_dir=str(tmp_path),
                    chunk_hook=lambda t, s, h: hooked.append(t))
    assert hooked == [1, 2, 3]
    spans = _host_spans(str(tmp_path))
    chunk, = [s for s in spans if s[1] == "fl.chunk"]
    assert chunk[3]["step_num"] == 1
    assert [s[1] for s in spans if s[1] != "fl.chunk"] == LOOP + ["fl.hook"]


def test_spans_cost_nothing_outside_a_profile():
    obs.SPAN_RECORD.clear()
    with obs.span(obs.KEYS), obs.span(obs.CHUNK, step=3):
        pass
    assert not obs.SPAN_RECORD


def test_compile_counter_counts_until_closed():
    with obs.CompileCounter() as c:
        jax.jit(lambda x: x * 3.0 + 1.0)(jnp.ones(7)).block_until_ready()
        assert c.compiles >= 1 and c.compile_s > 0
        assert c.programs() == c.compiles + c.hits
    seen = c.snapshot()
    jax.jit(lambda x: x * 5.0 - 2.0)(jnp.ones(9)).block_until_ready()
    assert c.snapshot() == seen


def test_train_profiles_the_second_chunk_and_names_a_recompile(tmp_path,
                                                               capsys):
    from repro.launch import train
    prof = tmp_path / "prof"
    train.main(["--preset", "tiny", "--backend", "jnp", "--seq", "16",
                "--clients", "2", "--client-chunk", "1", "--rounds", "3",
                "--scan-rounds", "2", "--log-every", "0",
                "--profile", str(prof)])
    out = capsys.readouterr().out
    # the second chunk holds one round, not two: a new program
    assert "chunk 2 (rounds 3-3) built" in out
    assert f"profile: chunk 2 traced to {prof}; host spans (ms): " \
           "fl.chunk " in out
    spans = _host_spans(str(prof))
    assert [s[3]["step_num"] for s in spans if s[1] == "fl.chunk"] == [2]


def test_benchmark_keeps_its_own_copy_of_the_names():
    assert scopes.STAGES == obs.STAGES
    assert scopes.SPANS == obs.SPANS
