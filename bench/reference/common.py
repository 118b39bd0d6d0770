"""Plain float32 building blocks of the benchmark's reference.

Nothing here imports the program under test. The reference follows the
equations of the configuration's architecture and of the federated
over-the-air round (fading, alpha-stable interference, adaptive server
update), written out in straightforward ``jax.numpy`` at float32 with
``highest`` matmul precision.

``Precision`` is the one knob between the reference and its control:
``model="f32"`` leaves every matmul operand at float32; ``model="fp8"``
rounds both operands and the result of every matmul to an e4m3 float8
with a per-tensor scale, and in the backward pass each of their
cotangents the same way (the step below the bfloat16 in which the
program stores its weights, the matmuls' results and their gradients).
``state="bf16"`` rounds the aggregate and the server state to bfloat16
after every operation (the step below their float32).
"""

from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp

# Largest normal of a 4-bit-exponent, 3-bit-mantissa float as
# ``reduce_precision`` rounds it (IEEE-style: e4m3fn's 448 has no room
# here), so a per-tensor scale keeps every operand in range.
E4M3_MAX = 240.0


def _round(x: jax.Array, exponent_bits: int, mantissa_bits: int):
    """x rounded to a narrower float, kept in f32. ``reduce_precision``
    and not a cast pair: XLA may fold ``convert(convert(x, bf16), f32)``
    back to x (excess precision), and then nothing is rounded."""
    return jax.lax.reduce_precision(x, exponent_bits=exponent_bits,
                                    mantissa_bits=mantissa_bits)


def _e4m3(x: jax.Array) -> jax.Array:
    """x rounded to e4m3 with a per-tensor scale, in f32."""
    amax = jnp.max(jnp.abs(x))
    s = jnp.where(amax > 0, amax / E4M3_MAX, 1.0)
    return _round(x / s, 4, 3) * s


@jax.custom_vjp
def _fp8(x: jax.Array) -> jax.Array:
    """The forward reads x at e4m3; the backward hands on the cotangent
    at e4m3, so that every backward matmul, which reads the rounded
    forward operands, reads a rounded cotangent too."""
    return _e4m3(x)


_fp8.defvjp(lambda x: (_e4m3(x), None), lambda _, g: (_e4m3(g),))


@dataclasses.dataclass(frozen=True)
class Precision:
    model: str = "f32"     # "f32" | "fp8"
    state: str = "f32"     # "f32" | "bf16"

    def operand(self, x: jax.Array) -> jax.Array:
        """A matmul operand as this precision stores it, back in f32."""
        x = x.astype(jnp.float32)
        if self.model == "f32":
            return x
        if self.model != "fp8":
            raise ValueError(f"unknown model precision {self.model!r}")
        return _fp8(x)

    def slab(self, x: jax.Array) -> jax.Array:
        """A server-side quantity as this precision stores it, in f32."""
        if self.state == "f32":
            return x
        if self.state != "bf16":
            raise ValueError(f"unknown state precision {self.state!r}")
        return _round(x, 8, 7)

    def mm(self, spec: str, a: jax.Array, b: jax.Array) -> jax.Array:
        """A matmul whose operands and result are stored at this
        precision (accumulated in f32)."""
        return self.operand(jnp.einsum(spec, self.operand(a),
                                       self.operand(b),
                                       precision=jax.lax.Precision.HIGHEST))


F32 = Precision()


def rmsnorm(x: jax.Array, scale: jax.Array, eps: float = 1e-6) -> jax.Array:
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale


def rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """Rotary embedding on the last axis, halves rotated as pairs.
    x: (B, S, H, D); positions: (S,)."""
    d = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = positions.astype(jnp.float32)[:, None] * inv[None, :]   # (S, D/2)
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def swiglu(p: dict, x: jax.Array, pr: Precision) -> jax.Array:
    gate = jax.nn.silu(pr.mm("bsd,df->bsf", x, p["gate"]["kernel"]))
    up = pr.mm("bsd,df->bsf", x, p["up"]["kernel"])
    return pr.mm("bsf,fd->bsd", gate * up, p["down"]["kernel"])


def next_token_xent(logits: jax.Array, tokens: jax.Array) -> jax.Array:
    """Mean cross-entropy of tokens[:, 1:] under logits[:, :-1]."""
    lg = logits[:, :-1]
    logz = jax.nn.logsumexp(lg, axis=-1)
    gold = jnp.take_along_axis(lg, tokens[:, 1:, None], axis=-1)[..., 0]
    return jnp.mean(logz - gold)


def causal_softmax_attend(q, k, v, allowed, pr: Precision) -> jax.Array:
    """q: (B,S,H,D); k, v: (B,S,H,Dk/Dv); allowed: (S,S) bool."""
    scale = 1.0 / math.sqrt(q.shape[-1])
    s = pr.mm("bqhd,bkhd->bhqk", q, k) * scale
    s = jnp.where(allowed[None, None], s, -jnp.inf)
    return pr.mm("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v)


# ---------------------------------------------------------------------------
# Initial weights: drawn leaf by leaf from the seed, by a rule per leaf name.
# The same function gives the program and the reference their weights.
# ---------------------------------------------------------------------------

def _leaf_rule(path: tuple, shape: tuple, stacked: bool):
    name = path[-1]
    core = shape[1:] if stacked else shape
    if name == "kernel":
        std = 1.0 / math.sqrt(core[0])
        return lambda k: jax.random.truncated_normal(
            k, -2.0, 2.0, shape, jnp.float32) * std
    if name == "table":
        std = 1.0 / math.sqrt(core[1])
        return lambda k: jax.random.normal(k, shape, jnp.float32) * std
    if name == "meta_tokens":
        return lambda k: jax.random.normal(k, shape, jnp.float32) * 0.02
    if name == "conv":
        std = 1.0 / math.sqrt(core[0])
        return lambda k: jax.random.normal(k, shape, jnp.float32) * std
    if name in ("scale", "d_skip"):
        return lambda k: jnp.ones(shape, jnp.float32)
    if name in ("bias", "conv_bias"):
        return lambda k: jnp.zeros(shape, jnp.float32)
    if name == "a_log":        # S4D-real: A = -(1, 2, ..., N) per channel
        n = core[-1]
        return lambda k: jnp.broadcast_to(
            jnp.log(jnp.arange(1, n + 1, dtype=jnp.float32)), shape)
    if name == "dt_bias":
        return lambda k: jax.random.uniform(
            k, shape, jnp.float32, math.log(1e-3), math.log(1e-1))
    raise ValueError(f"no initial-weight rule for leaf {'/'.join(path)}")


def init_params(layout: dict, key: jax.Array) -> dict:
    """Weights for ``layout`` (a nested dict of ``(shape, dtype)``), each
    leaf from ``fold_in(key, i)`` with i its place in flatten order."""
    paths = flatten_paths(layout)
    flat = []
    for i, (path, (shape, dtype)) in enumerate(paths):
        rule = _leaf_rule(path, tuple(shape), "blocks" in path)
        flat.append(rule(jax.random.fold_in(key, i)).astype(dtype))
    return unflatten_paths([p for p, _ in paths], flat)


def flatten_paths(tree: dict, prefix: tuple = ()) -> list:
    """[(path, leaf)] in sorted-key order (jax's order for dicts)."""
    out = []
    for k in sorted(tree):
        v = tree[k]
        if isinstance(v, dict):
            out.extend(flatten_paths(v, prefix + (k,)))
        else:
            out.append((prefix + (k,), v))
    return out


def unflatten_paths(paths: list, leaves: list) -> dict:
    out: dict = {}
    for path, leaf in zip(paths, leaves):
        node = out
        for k in path[:-1]:
            node = node.setdefault(k, {})
        node[path[-1]] = leaf
    return out


def layer(params: dict, i: int) -> dict:
    """Layer i of a layer-stacked parameter dict."""
    return jax.tree.map(lambda a: a[i], params)
