"""Fused ADOTA server-update Pallas kernel — all six server optimizers.

The ADOTA update (Eq. 8-11) is elementwise over every parameter:

    Delta <- b1*Delta + (1-b1)*g
    v     <- f(v, |Delta|^a)          (mode-dependent, see below)
    w     <- w - lr * Delta / (v+eps)^{1/a}

Naively chained in jnp this is ~10 HBM round-trips over 4 model-sized
arrays; the fractional |.|^a and (.)^{1/a} powers (exp/log on the VPU)
make it strictly memory-bound. The kernel performs the whole update in
ONE read-modify-write pass per block: each grid step streams a
(block_rows, 128) tile of the operands HBM->VMEM, does all the math in
VMEM/VREGs, and writes the outputs back.

Modes (matching ``repro.core.adaptive`` update rules exactly):

    adagrad   v += |Delta|^a                       (AdaGrad-OTA, Eq. 9)
    adam      v = b2 v + (1-b2)|Delta|^a           (Adam-OTA,    Eq. 10)
    amsgrad   adam v, plus vmax = max(vmax, v); step divides by vmax
    yogi      v -= (1-b2) sign(v - |Delta|^a)|Delta|^a
    momentum  Delta = b1 Delta + g; w -= lr Delta  (FedAvgM; no v)
    sgd       w -= lr g                            (FedAvg; stateless)

The operand list varies with the mode (sgd needs no state, amsgrad
carries an extra vmax slab); ``adaptive_update_slab`` assembles the
right ``pallas_call`` and always returns ``(*updated_state, w')`` in
(delta, nu, nu_max) order — 3-tuple for adagrad/adam/yogi, 4-tuple for
amsgrad, 2-tuple for momentum, 1-tuple for sgd.

TPU is the target (bf16/f32 tiles aligned to the 8x128 VPU lanes); on
this CPU container the kernel body is validated with interpret=True
against ``ref.adaptive_update_ref``. The elementwise math mirrors the
jnp reference ops exactly (same |.|** / zero-fill / maximum guards), so
interpret-mode results match the tree.map path to f32 rounding.

Sharded slab engine (``repro.core.shard``): the update is elementwise,
so each mesh device passes its OWN contiguous slab slice here and the
grid covers just that shard — P devices each run one launch of 1/P the
size instead of one device running the full-model launch. Slices are
valid operands by construction: the shard-aligned padding rule
(``make_slab_spec(..., shards=P)``) makes every slice lane-aligned, and
the zero tail stays a fixed point of all six modes (delta' = b1*0, nu
update of 0 is 0, w' = 0 - lr*0/denom = 0), so regathered slices equal
the unsharded result exactly.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro import obs

# The single source of the |.|^alpha zero-guard: the jnp/pallas backend
# parity contract depends on the kernel computing the exact same ops as
# the tree.map reference. (Import is cycle-safe: core.adaptive pulls in
# this module only lazily, inside apply_slab_update.)
from repro.core.adaptive import _abs_pow
from repro.kernels.interpret import (INTERPRET_BLOCK_CAP, coarse_block,
                                     resolve_interpret)

LANE = 128
DEFAULT_BLOCK_ROWS = 256     # (256, 128) f32 tile = 128 KiB per operand

MODES = ("adagrad", "adam", "amsgrad", "yogi", "momentum", "sgd")


def _adaptive_update_kernel(*refs, lr: float, beta1: float, beta2: float,
                            alpha, eps: float, mode: str):
    # alpha is either a static python float (baked into the kernel — the
    # alpha="static" fast path, bitwise-identical to the pre-runtime-
    # alpha code) or None, meaning the closed-loop tracked value arrives
    # as the FIRST operand: a (1, 1) f32 block replicated to every grid
    # step. Only the alpha-power family reads it.
    if alpha is None:
        alpha = refs[0][0, 0]
        refs = refs[1:]
    g = refs[0][...].astype(jnp.float32)
    if mode == "sgd":
        w_ref, w_out = refs[1:]
        w_out[...] = (w_ref[...].astype(jnp.float32) - lr * g).astype(
            w_out.dtype)
        return

    delta_ref = refs[1]
    gain = 1.0 if mode == "momentum" else (1.0 - beta1)
    delta = beta1 * delta_ref[...] + gain * g

    if mode == "momentum":
        w_ref, delta_out, w_out = refs[2:]
        delta_out[...] = delta
        w_out[...] = (w_ref[...].astype(jnp.float32) - lr * delta).astype(
            w_out.dtype)
        return

    da = _abs_pow(delta, alpha)
    if mode == "amsgrad":
        nu_ref, vmax_ref, w_ref, delta_out, nu_out, vmax_out, w_out = refs[2:]
        nu = beta2 * nu_ref[...] + (1.0 - beta2) * da
        vmax = jnp.maximum(vmax_ref[...], nu)
        vmax_out[...] = vmax
        denom_v = vmax
    else:
        nu_ref, w_ref, delta_out, nu_out, w_out = refs[2:]
        if mode == "adagrad":
            nu = nu_ref[...] + da
        elif mode == "adam":
            nu = beta2 * nu_ref[...] + (1.0 - beta2) * da
        else:  # yogi
            v = nu_ref[...]
            nu = v - (1.0 - beta2) * jnp.sign(v - da) * da
        denom_v = nu
    denom = jnp.maximum(denom_v + eps, 0.0) ** (1.0 / alpha)
    w = w_ref[...].astype(jnp.float32) - lr * delta / denom
    delta_out[...] = delta
    nu_out[...] = nu
    w_out[...] = w.astype(w_out.dtype)


def adaptive_update_slab(g: jax.Array, delta: Optional[jax.Array],
                         nu: Optional[jax.Array], w: jax.Array, *, lr: float,
                         beta1: float, beta2: float, alpha, eps: float,
                         mode: str, nu_max: Optional[jax.Array] = None,
                         block_rows: int = DEFAULT_BLOCK_ROWS,
                         interpret: Optional[bool] = None
                         ) -> Tuple[jax.Array, ...]:
    """Fused server update on a 1-D parameter slab (any length; padded to
    lanes internally).

    g/w may be bf16 or f32; delta/nu/nu_max are f32 state (ignored — pass
    None — for modes that do not carry them). For ``momentum``, ``beta1``
    is the server momentum coefficient (g enters with gain 1). Returns
    the updated slabs in ``(delta', nu', nu_max', w')`` order, dropping
    the entries the mode does not own; ``w'`` is always last.

    ``alpha`` may be a static python float (baked into the kernel — the
    historical path, bitwise-unchanged) or a traced f32 scalar (a
    ``jax.Array``): the closed-loop tracked tail index. A traced alpha
    rides in as one extra (1, 1) operand broadcast to every grid step,
    so changing the estimate between rounds re-runs, not re-compiles,
    the kernel. Modes outside the alpha-power family (momentum/sgd)
    never read alpha and always take the static path.
    """
    if mode not in MODES:
        raise ValueError(f"unknown update mode {mode!r}; options: {MODES}")
    interpret = resolve_interpret(interpret)
    traced_alpha = (isinstance(alpha, jax.Array)
                    and mode in ("adagrad", "adam", "amsgrad", "yogi"))
    n = g.shape[0]
    rows = -(-n // LANE)
    # Interpret-mode grid coarsening (cap in rows: cap * LANE elements
    # per interpreted step; the update is elementwise, so any tiling of
    # the row axis is bitwise-equivalent).
    block_rows = coarse_block(rows, block_rows, interpret,
                              cap=INTERPRET_BLOCK_CAP // LANE)
    rows_pad = -(-rows // block_rows) * block_rows
    total = rows_pad * LANE

    def shape2d(x, dt=None):
        x = jnp.pad(x, (0, total - n))
        return x.reshape(rows_pad, LANE).astype(dt or x.dtype)

    ins = [shape2d(g)]
    n_state = 0
    if mode != "sgd":
        ins.append(shape2d(delta, jnp.float32))
        n_state += 1
    if mode in ("adagrad", "adam", "amsgrad", "yogi"):
        ins.append(shape2d(nu, jnp.float32))
        n_state += 1
    if mode == "amsgrad":
        ins.append(shape2d(nu_max, jnp.float32))
        n_state += 1
    ins.append(shape2d(w))

    grid = (rows_pad // block_rows,)
    blk = pl.BlockSpec((block_rows, LANE), lambda i: (i, 0))
    in_specs = [blk] * len(ins)
    if traced_alpha:
        ins.insert(0, jnp.asarray(alpha, jnp.float32).reshape(1, 1))
        in_specs.insert(0, pl.BlockSpec((1, 1), lambda i: (0, 0)))
    kernel = functools.partial(
        _adaptive_update_kernel, lr=lr, beta1=beta1, beta2=beta2,
        alpha=None if traced_alpha else alpha, eps=eps, mode=mode)
    outs = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=[blk] * (n_state + 1),
        out_shape=[jax.ShapeDtypeStruct((rows_pad, LANE), jnp.float32)
                   ] * n_state
        + [jax.ShapeDtypeStruct((rows_pad, LANE), w.dtype)],
        interpret=interpret,
        name=obs.ADAPTIVE_UPDATE,
    )(*ins)
    if not isinstance(outs, (list, tuple)):
        outs = [outs]
    unpad = lambda x2: x2.reshape(-1)[:n]
    return tuple(unpad(o) for o in outs)
