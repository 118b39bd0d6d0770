"""Peaks of the chip, and the work each measured stage must do.

The counts are of the work the stage must do, whatever implements it,
so that no implementation can read above its roofline:

* each participating client's gradient is read once, at the dtype the
  model emits it (the storage dtype of its parameters);
* the round's aggregate is written once, in float32;
* the server state w, Delta, v is read and written once, in float32;
* the channel's random draws count as the transform's operations, not
  as bytes.
"""

from __future__ import annotations

import math

import numpy as np

from bench.reference.common import flatten_paths

# Published per-chip peaks, keyed by jax's ``device_kind``. Source: Google
# Cloud documentation, "TPU v5e" (system architecture): 197 TFLOP/s bf16,
# 393 TOP/s int8, 16 GB HBM2 at 819 GB/s, 1,600 Gbit/s inter-chip links.
PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "int8_ops": 393e12,
                    "hbm_bytes_s": 819e9, "ici_bytes_s": 200e9,
                    "hbm_bytes": 16e9},
}

# Operations of one symmetric alpha-stable draw by the CMS transform,
# counted as its arithmetic: sin, two cos, two powers (each a log and an
# exp) and about ten multiplies, adds and divides.
CMS_OPS = 20


def peaks(device_kind: str) -> dict:
    """The peaks of one chip; an unknown chip is an error, not a default."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device_kind "
                       f"{device_kind!r}; add them to bench/counts.py "
                       f"with their source") from None


def param_count(layout: dict) -> int:
    """Entries of a (shape, dtype) layout; dtypes are ignored."""
    return sum(math.prod(s) for _, (s, _) in flatten_paths(layout))


def grad_bytes(layout: dict) -> int:
    """Bytes of one client's gradient at the parameters' storage dtypes."""
    return sum(math.prod(s) * np.dtype(d).itemsize
               for _, (s, d) in flatten_paths(layout))


def mac_bytes(layout: dict, clients: int) -> int:
    """Least HBM traffic of one round's MAC: every client's gradient read
    once at its storage dtype, the aggregate written once in float32."""
    return clients * grad_bytes(layout) + 4 * param_count(layout)


def mac_ops(layout: dict) -> int:
    """Operations of the interference draw over every coordinate."""
    return CMS_OPS * param_count(layout)


def update_bytes(layout: dict) -> int:
    """Least HBM traffic of one server update: w, Delta, v in float32,
    each read and written once (the aggregate is the MAC's output)."""
    return 3 * 2 * 4 * param_count(layout)


def exchange_bytes(padded: int, chips: int, slabs: int = 3) -> float:
    """Least bytes one chip sends in a round's exchange over ``chips``
    chips: (P - 1) / P of every float32 slab of ``padded`` entries that is
    all-gathered or reduce-scattered. The sharded round gathers the model
    broadcast and scatters the faded partial and the clean sum: 3 slabs."""
    return (chips - 1) / chips * 4 * padded * slabs


def least_seconds(nbytes: float, ops: float, peak: dict) -> float:
    """The larger of the byte bound and the operation bound."""
    return max(nbytes / peak["hbm_bytes_s"], ops / peak["bf16_flops"])


def matmul_params(layout: dict, exclude=("table",)) -> tuple:
    """(per-position weights, per-output-token weights): entries of every
    matmul kernel applied at each position of the stack, and of the
    output head applied at each predicted token."""
    body = head = 0
    for path, (shape, _) in flatten_paths(layout):
        if path[-1] != "kernel" or path[-2] in exclude:
            continue
        if path[0] == "unembed":
            head += math.prod(shape)
        else:
            body += math.prod(shape)
    return body, head


def attention_pairs(seq: int, window) -> int:
    """Query-key pairs a causal (optionally windowed) attention scores."""
    if window is None or window >= seq:
        return seq * (seq + 1) // 2
    w = int(window)
    return w * (w + 1) // 2 + (seq - w) * w


def client_flops(layout: dict, dims: dict, batch: int, seq: int) -> float:
    """Model FLOPs of one client's forward and backward pass (3x the
    forward), recomputation not counted. ``dims`` (from the reference
    module) gives ``positions`` (sequence length inside the stack, e.g.
    with meta tokens), ``layers``, ``heads``, ``qk``, ``v`` and
    ``window``."""
    body, head = matmul_params(layout)
    pos = dims["positions"]
    fwd = 2.0 * batch * (body * pos + head * seq)
    pairs = attention_pairs(pos, dims["window"])
    fwd += (2.0 * batch * dims["layers"] * dims["heads"] * pairs
            * (dims["qk"] + dims["v"]))
    return 3.0 * fwd
