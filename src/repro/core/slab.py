"""Pytree <-> slab bridge: the canonical flat representation of a model.

The ADOTA round (Eqs. 7-11) is elementwise over every parameter, so the
fused Pallas kernels (``repro.kernels.adaptive_update``,
``repro.kernels.ota_channel``) operate on one contiguous 1-D f32 buffer
— a *slab* — instead of a pytree of leaves. This module owns the
contract between the two worlds:

* ``make_slab_spec(tree)`` records, **statically**, each leaf's shape,
  dtype, flat size and offset into the slab, plus the lane-padded total
  (``LANE == 128`` to line up with the TPU VPU lanes the kernels tile
  over). Shapes are static under jit, so the spec can be built inside a
  traced function at no runtime cost. ``shards=P`` rounds the padded
  length up to a multiple of ``lane * P`` — the *shard-aligned padding
  rule* of the sharded slab engine: the slab then splits into P
  contiguous, equal, lane-aligned slices, one per device of the mesh's
  client axes, and every slice is itself a valid kernel operand. The
  extra padding is zeros, so specs built with different ``shards`` agree
  on every real entry and round-trip identically.
* ``tree_to_slab(spec, tree)`` flattens every leaf, casts to f32 (the
  canonical compute dtype of the server update — the jnp reference path
  also computes in f32), concatenates in leaf order and zero-pads to the
  lane multiple. Zero padding is load-bearing: it keeps L2 norms exact
  and is a fixed point of every update mode (the kernels never leak
  padding into real entries).
* ``slab_to_tree(spec, slab)`` inverts it, slicing at the recorded
  offsets, restoring shapes and (optionally) the original leaf dtypes —
  matching the jnp path's ``.astype(w.dtype)`` on the way out.
* ``stack_to_slab(spec, tree)`` is the client-stacked variant: leaves of
  shape ``(N, *leaf_shape)`` become one ``(N, padded)`` matrix so the
  whole OTA MAC is a single ``ota_channel_slab`` launch.

Adding a new fused optimizer mode does not touch this file: the slab
layout is mode-independent; only ``repro.kernels.adaptive_update`` (the
kernel math) and ``repro.core.adaptive`` (the mode dispatch) change.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Tuple

import jax
import jax.numpy as jnp
import numpy as np

PyTree = Any

LANE = 128   # must match repro.kernels.*.LANE (TPU vector lane width)


@dataclasses.dataclass(frozen=True)
class SlabSpec:
    """Static layout of a pytree inside a 1-D slab.

    ``offsets[i]:offsets[i]+sizes[i]`` is leaf i (in ``treedef`` order);
    ``total`` is the exact element count and ``padded`` the lane-rounded
    slab length actually materialised.
    """

    treedef: Any
    shapes: Tuple[Tuple[int, ...], ...]
    dtypes: Tuple[Any, ...]
    offsets: Tuple[int, ...]
    sizes: Tuple[int, ...]
    total: int
    padded: int
    shards: int = 1

    @property
    def n_leaves(self) -> int:
        return len(self.shapes)

    @property
    def shard_len(self) -> int:
        """Length of one per-device slab slice (``padded / shards``)."""
        return self.padded // self.shards


def make_slab_spec(tree: PyTree, lane: int = LANE, shards: int = 1) -> SlabSpec:
    """Build the static slab layout of ``tree`` (arrays or ShapeDtypeStructs).

    ``shards`` > 1 applies the shard-aligned padding rule: the padded
    length becomes the smallest multiple of ``lane * shards`` holding all
    leaves, so the slab splits into ``shards`` equal lane-aligned slices.
    """
    leaves, treedef = jax.tree.flatten(tree)
    if not leaves:
        raise ValueError("cannot build a slab spec from an empty pytree")
    if shards < 1:
        raise ValueError(f"shards must be >= 1, got {shards}")
    shapes = tuple(tuple(l.shape) for l in leaves)
    dtypes = tuple(jnp.dtype(l.dtype) for l in leaves)
    sizes = tuple(int(np.prod(s, dtype=np.int64)) for s in shapes)
    offsets, off = [], 0
    for s in sizes:
        offsets.append(off)
        off += s
    quantum = lane * shards
    padded = -(-off // quantum) * quantum
    return SlabSpec(treedef=treedef, shapes=shapes, dtypes=dtypes,
                    offsets=tuple(offsets), sizes=tuple(sizes), total=off,
                    padded=padded, shards=shards)


def tree_to_slab(spec: SlabSpec, tree: PyTree,
                 dtype=jnp.float32) -> jax.Array:
    """Flatten ``tree`` into one (padded,) slab of ``dtype`` (zero tail)."""
    leaves = spec.treedef.flatten_up_to(tree)
    flat = jnp.concatenate([jnp.asarray(l).reshape(-1).astype(dtype)
                            for l in leaves])
    return jnp.pad(flat, (0, spec.padded - spec.total))


def slab_to_tree(spec: SlabSpec, slab: jax.Array, cast: bool = True) -> PyTree:
    """Invert ``tree_to_slab``: restore shapes and (if ``cast``) dtypes.

    ``cast=False`` keeps the slab dtype on every leaf — used for the f32
    optimizer state, whose leaves mirror the parameter shapes but stay
    float32 regardless of the parameter dtype.
    """
    leaves = []
    for shape, dt, off, size in zip(spec.shapes, spec.dtypes, spec.offsets,
                                    spec.sizes):
        leaf = jax.lax.dynamic_slice_in_dim(slab, off, size).reshape(shape)
        leaves.append(leaf.astype(dt) if cast else leaf)
    return jax.tree.unflatten(spec.treedef, leaves)


def stack_to_slab(spec: SlabSpec, tree: PyTree,
                  dtype=jnp.float32) -> jax.Array:
    """Flatten a client-stacked tree (leaves ``(N, *shape)``) to (N, padded).

    A one-client stack is the flat slab with a unit axis in front: a TPU
    then stores it as it stores the flat slab, where a one-row
    concatenation is tiled (1, 128) and costs a relayout pass wherever
    the slab's own layout is read."""
    leaves = spec.treedef.flatten_up_to(tree)
    n = leaves[0].shape[0]
    if n == 1:
        one = jax.tree.unflatten(spec.treedef, [l[0] for l in leaves])
        return tree_to_slab(spec, one, dtype)[None]
    flat = jnp.concatenate(
        [jnp.asarray(l).reshape(n, -1).astype(dtype) for l in leaves], axis=1)
    return jnp.pad(flat, ((0, 0), (0, spec.padded - spec.total)))


def zeros_slab(spec: SlabSpec, dtype=jnp.float32) -> jax.Array:
    return jnp.zeros((spec.padded,), dtype)
