"""Compile rehearsal of every Pallas kernel the training CLI can reach,
for a TPU v5e described (not attached), at the slab length of
``chip_smoke.py``'s model.

Interpret mode runs a kernel body as plain jax and so accepts block
shapes and in-kernel relayouts that the TPU lowering refuses; these
tests catch such refusals without a chip. Each asserts that the
compiled program holds the kernel as a ``tpu_custom_call``.

The topology is described inside a module fixture (never at import):
only one process at a time may load the TPU library, and the test
workers all import this file. Where it cannot be described, the
fixture skips the file's tests.

The case ``streamed_mac_ragged_rows`` compiles a streamed round's MAC
(per-client packing, chunk launches folding into a scan carry, then the
f32 channel) at that slab length, whose lane rows are a multiple of
neither 8 nor the block rows, and reads the compiled text: no
slab-sized pad, slice or relayout feeds or reads either kernel, and
the carry is updated in place.
"""

import importlib.util
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import make_slab_spec, stack_to_slab
from repro.kernels.adaptive_update import adaptive_update_slab
from repro.kernels.ota_channel import (LANE, channel_tiles,
                                       mac_block_rows, ota_channel_slab,
                                       ota_receive_slab, ota_transmit_slab,
                                       stream_tiles)
from repro.launch.train import preset_config
from repro.models.model import build_model

REPO_ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
N = 8                     # clients of the smoke run
ROWS = 2                  # streamed client chunk


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip can be written to the persistent
    # cache but not read back without one; keep it out of the cache.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def slab_len():
    """The padded slab length of chip_smoke.py's depth-cut model."""
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(REPO_ROOT, "chip_smoke.py"))
    smoke = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(smoke)
    model = build_model(preset_config(smoke.ARCH, "full", smoke.LAYERS))
    shapes = jax.eval_shape(model.init, jax.random.key(0))
    return make_slab_spec(shapes).padded


def _leaves(d):
    """Three bf16 model leaves whose slab is d long."""
    return {"w": (1000, 777), "v": (3001,), "b": (d - 780_001,)}


def _streamed_mac(w, v, b, h, u, e):
    """A streamed round's MAC: each client's gradient leaves (fusions,
    as in the round) are packed into its slab, which folds into the scan
    carry through the streamed transmit and into the clean sum; the
    completed partial crosses the f32 channel as one row."""
    leaves = {"w": w, "v": v, "b": b}
    spec = make_slab_spec({k: jax.ShapeDtypeStruct(x.shape[1:], x.dtype)
                           for k, x in leaves.items()})

    def body(carry, c):
        acc, clean = carry
        grads = {k: jnp.sin(jax.lax.dynamic_index_in_dim(x, c))
                 for k, x in leaves.items()}
        g_stack = stack_to_slab(spec, grads)
        h_c = jax.lax.dynamic_index_in_dim(h, c)
        acc = ota_transmit_slab(g_stack, h_c, acc=acc, n_total=ROWS,
                                interpret=False)
        return (acc, clean + jnp.sum(g_stack, axis=0)), None

    zeros = jnp.zeros((spec.padded,), F32)
    (acc, clean), _ = jax.lax.scan(body, (zeros, zeros), jnp.arange(ROWS))
    return ota_channel_slab(acc[None], jnp.ones((1,), F32), u, e,
                            **_CH), clean


_HLO_LINE = re.compile(r"^\s*(?:ROOT )?%(\S+) = (\S+) ([\w-]+)\((.*?)\)")


def _hlo_instructions(text):
    """name -> (opcode, element count, operand names) of every
    instruction with an array result in compiled HLO text."""
    out = {}
    for line in text.splitlines():
        m = _HLO_LINE.match(line)
        if not m:
            continue
        dims = re.match(r"\w+\[([\d,]*)\]", m.group(2))
        if dims is None:
            continue
        size = 1
        for x in filter(None, dims.group(1).split(",")):
            size *= int(x)
        out[m.group(1)] = (m.group(3), size, re.findall(r"%([\w.-]+)",
                                                        m.group(4)))
    return out


def _assert_mac_in_place(text, d):
    """Both MAC kernels are custom calls; no slab-sized pad, slice or
    relayout (a copy, or a reduce over a unit axis) feeds or reads
    either (through bitcasts); the streamed transmit's accumulator
    (operand 2) is aliased to its output."""
    ins = _hlo_instructions(text)

    def source(name):
        while ins[name][0] == "bitcast":
            name = ins[name][2][0]
        return name

    def readers(name):
        for other, (op, _, args) in ins.items():
            if name in args:
                yield from (readers(other) if op == "bitcast" else [other])

    for kernel in ("ota_transmit_stream", "ota_channel"):
        calls = [k for k in ins if re.fullmatch(kernel + r"(\.\d+)?", k)]
        assert calls, f"no {kernel} call in the compiled program"
        for call in calls:
            assert ins[call][0] == "custom-call"
            near = ([source(a) for a in ins[call][2] if a in ins]
                    + list(readers(call)))
            bad = [k for k in near
                   if ins[k][0] in ("pad", "slice", "copy", "reduce")
                   and ins[k][1] >= d]
            assert not bad, f"slab-sized {bad} around {call}"
    stream = [line for line in text.splitlines()
              if re.search(r"%ota_transmit_stream(\.\d+)? = ", line)]
    assert stream and all("output_to_operand_aliasing={{}: (2, {})}" in line
                          for line in stream)


# name -> (kernel call, operand shapes as (shape, dtype) given d)
_CH = dict(alpha=1.5, scale=0.05, interpret=False)
_AD = dict(lr=0.02, beta1=0.9, beta2=0.3, eps=1e-8, mode="adam",
           interpret=False)
F32, BF16, I8, U32, I32 = (jnp.float32, jnp.bfloat16, jnp.int8, jnp.uint32,
                          jnp.int32)
CASES = {
    "channel": (
        lambda g, h, u, e: ota_channel_slab(g, h, u, e, **_CH),
        lambda d: [((N, d), F32), ((N,), F32), ((d,), F32), ((d,), F32)]),
    "channel_pilot_stats": (
        lambda g, h, u, e: ota_channel_slab(g, h, u, e, pilot_stats=True,
                                            **_CH),
        lambda d: [((N, d), F32), ((N,), F32), ((d,), F32), ((d,), F32)]),
    "transmit": (
        lambda g, h: ota_transmit_slab(g, h, interpret=False),
        lambda d: [((N, d), F32), ((N,), F32)]),
    "transmit_streamed": (
        lambda g, h, acc: ota_transmit_slab(g, h, acc=acc, n_total=N,
                                            interpret=False),
        lambda d: [((ROWS, d), F32), ((ROWS,), F32), ((d,), F32)]),
    # At this slab length the client axis is streamed, and the wire
    # quantizer sees the completed partial as one row.
    "transmit_int8": (
        lambda g, h, r: ota_transmit_slab(g, h, quantize=True, r=r,
                                          interpret=False),
        lambda d: [((1, d), F32), ((1,), F32), ((d,), F32)]),
    "transmit_int8_error_feedback": (
        lambda g, h, r, ef: ota_transmit_slab(
            g, h, quantize=True, r=r, ef=ef, return_residual=True,
            interpret=False),
        lambda d: [((1, d), F32), ((1,), F32), ((d,), F32), ((d,), F32)]),
    "transmit_int8_inkernel_sr": (
        lambda g, h, seed: ota_transmit_slab(g, h, quantize=True,
                                             sr_seed=seed, interpret=False),
        lambda d: [((1, d), F32), ((1,), F32), ((), I32)]),
    "transmit_sign": (
        lambda g, h: ota_transmit_slab(g, h, quantize=True, qmode="sign",
                                       zero_fold=True, interpret=False),
        lambda d: [((1, d), F32), ((1,), F32)]),
    "receive": (
        lambda q, s, u, e: ota_receive_slab(q, s, u, e, **_CH),
        lambda d: [((1, d), I8), ((1, d // LANE), F32), ((d,), F32),
                   ((d,), F32)]),
    "receive_pilot_stats": (
        lambda q, s, u, e: ota_receive_slab(q, s, u, e, pilot_stats=True,
                                            **_CH),
        lambda d: [((1, d), I8), ((1, d // LANE), F32), ((d,), F32),
                   ((d,), F32)]),
    "receive_packed_sign": (
        lambda q, s, u, e: ota_receive_slab(q, s, u, e, packed="fold",
                                            **_CH),
        lambda d: [((1, d // 32), U32), ((1, d // LANE), F32), ((d,), F32),
                   ((d,), F32)]),
    "adam_static_alpha": (
        lambda g, m, v, w: adaptive_update_slab(g, m, v, w, alpha=1.5,
                                                **_AD),
        lambda d: [((d,), F32)] * 4),
    "streamed_mac_ragged_rows": (
        _streamed_mac,
        lambda d: [((ROWS,) + shape, BF16) for shape in _leaves(d).values()]
        + [((ROWS,), F32), ((d,), F32), ((d,), F32)]),
    "adam_traced_alpha": (
        lambda a, g, m, v, w: adaptive_update_slab(g, m, v, w, alpha=a,
                                                   **_AD),
        lambda d: [((), F32)] + [((d,), F32)] * 4),
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_kernel_compiles_for_v5e(name, one_chip, slab_len):
    fn, operands = CASES[name]
    args = [jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
            for shape, dt in operands(slab_len)]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    if name == "streamed_mac_ragged_rows":
        # The slab's lane rows are a multiple of neither 8 nor the
        # block rows of either launch: both grids end on a ragged block.
        rows = slab_len // LANE
        assert rows % 8 and rows % mac_block_rows(rows, stream_tiles(1))
        assert rows % mac_block_rows(rows, channel_tiles(1))
        _assert_mac_in_place(text, slab_len)
