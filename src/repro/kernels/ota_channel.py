"""OTA uplink Pallas kernels: the MAC as a staged transmit/receive pair.

The uplink pipeline (paper Eq. 7, restructured in PR 4) is

    transmit power control -> quantize -> MAC superposition
        -> interference injection -> receiver dequantize/scale

and this module owns the kernel stages of it:

* ``ota_transmit_slab`` — the transmitter: the fading-scaled partial
  reduction ``(1/N) sum_n h[n] * G[n, :]`` over this transmitter's
  stacked client gradients (power control is upstream, folded into the
  effective ``h``). With ``quantize=True`` the kernel runs a fused
  *quantize-on-write epilogue*: each LANE-wide group of the partial sum
  (one row of the kernel's (rows, LANE) tile) gets one f32 scale
  (symmetric, max|x|/127) and is written as int8 with stochastic
  rounding (``floor(x/s + r)``, r ~ U[0,1) produced
  upstream so all backends make identical rounding decisions) — the
  payload leaves the kernel already in wire format, one read of G.

* ``ota_receive_slab`` — the server's RF front end: dequantizes R
  payload rows (R transmitters after the collective; R == 1 single-
  device), sums them, and injects the Chambers-Mallows-Stuck
  alpha-stable interference in the same VMEM tile.

* ``ota_channel_slab`` — the original single-launch fused f32 channel
  (faded reduction + CMS interference, one pass); still the f32 fast
  path: splitting it would buy nothing when there is no wire format to
  stage around, and keeping it guarantees the ``uplink="f32"`` round is
  bitwise-identical to the pre-pipeline code.

**Pilot-statistics epilogue** (``pilot_stats=True`` on the two
interference-injecting launches, PR 5): the online tail-index tracker
(paper Remark 3, ``repro.core.tail_index``) needs log-moment statistics
of the interference residual r = xi_scale * xi — which these kernels
hold in-register anyway. Rather than re-synthesizing the residual in a
second pass, each grid step reduces its tile to
``[count, sum log|r|, sum log^2|r|]`` over the NONZERO residual entries
(the padding tail synthesizes exactly 0 and drops out; a disabled
channel reduces to count == 0) and writes them into its own row of a
tiny (grid, 1, LANE) side output; the caller sums the rows. Per-step rows
instead of cross-step accumulation keep the epilogue trivially correct
under any grid execution order. The stats are subset-agnostic — a shard
slice's 3-vector simply psum-adds with its peers' — which is what lets
the sharded engine reduce them like the RoundMetrics norms. The main
output is untouched, and with ``pilot_stats=False`` (the default) the
launch is the exact pre-PR-5 ``pallas_call`` — the static-alpha path
stays bitwise.

The CMS math is ``repro.core.channel.cms_transform`` — the same guarded
expression the jnp sampler uses, so kernel and reference agree bitwise
in interpret mode: angles are clipped strictly inside (-pi/2, pi/2)
(endpoint angles made the old log-space form NaN, even at alpha == 2
where the transform reduces to the finite Gaussian 2*sin(u)*sqrt(e))
and the Exp(1) draws are floored. The tail index is validated against
the same (1, 2] range as ``OTAChannelConfig``.

Grid: the f32 channel and the streamed transmit view the slab as lane
rows — a (d,) vector as (d / 128, 128), an (N, d) stack as (N, d / 128,
128), views that cost nothing on a TPU for a vector or a one-row stack
(every slab has d % 128 == 0); a several-row stack is stored tiled
across its rows and is copied row by row — and tile them in (N,
block_rows, 128) and (block_rows, 128) blocks, 1-D over row blocks; the
N reduction runs inside the tile. The block rows follow from the launch's shapes
(``mac_block_rows``: as many rows as the VMEM budget holds, a few
thousand for one client a chunk; a launch of more than ~1,000 client
rows is refused), and the last row block may be ragged: the math is per
coordinate, the rows past the end are never written back, and the
pilot-statistics epilogue masks them. No slab-sized pad or slice
surrounds a one-row launch. The other launches (resident and quantized
transmit, receive) keep blocks ``block_cols`` coordinates wide, padded
to a block multiple.
``interpret=None`` auto-selects Pallas interpret mode from the platform
(``repro.kernels.interpret``): compiled on TPU, interpreted elsewhere.

**Streamed client axis** (PR 6): with ``acc=`` and/or ``row_chunk=`` the
transmit grid gains a CLIENT-CHUNK dimension — ``(row_blocks,
row_chunks)``, row blocks outer so each output tile is revisited
consecutively — and the kernel accumulates the faded partial sum
in-place across the row chunks (``@pl.when(r == 0)`` seeds the output
tile from the ``acc`` carry, every step adds its chunk's
``sum_rows(h*g)/n_total``). The ``acc`` operand is aliased to the
output, so a scan carry is updated in place. ``acc`` chains launches: a
round streams N clients as a ``lax.scan`` over gradient chunks, each
chunk's transmit launch folding into the running (d,) partial — peak
memory is O(chunk * d) regardless of N, and ``n_total`` keeps the 1/N
wire normalisation identical to the resident launch. With one row chunk
the accumulation is ``0 + sum(h*g)/n_total`` — bitwise-equal to the
resident kernel — so streaming with ``chunk >= N`` is a pure memory
optimization (the parity guard in tests/test_stream.py pins this).
Quantization composes by accumulating the f32 partial first and
quantizing the COMPLETED sum through a single-row ``quantize=True``
launch (one quantization step per entry, the wire contract).

**Wire-format matrix** (PR 7): the quantize epilogue speaks two wire
formats — ``qmode="int8"`` (symmetric max|x|/127 + stochastic rounding)
and ``qmode="sign"`` (1-bit signSGD: payload = sign(x) in {-1, 0, +1}
on the same int8 wire container, one f32 mean|x| magnitude per
128-block, deterministic). Both dequantize through the unchanged
``ota_receive_slab`` (payload * per-block scale). Per-transmitter error
feedback composes in the same launch: the carried residual ``ef`` joins
the faded partial before quantization and ``return_residual=True``
writes the fresh residual ``x - dequant(quant(x))`` as a third output —
the EF loop costs one extra (1, bc) read + write per tile, never a
second pass over G.

Sharded slab engine: when the round is distributed over a device mesh
(``repro.core.shard``), each device launches the transmit kernel on its
LOCAL client shard only, passing ``n_total`` = the global client count
so the 1/N normalisation matches the single-device launch; the
cross-device collective then completes the superposition (the mesh is
the multiple-access channel) and the receive kernel runs on each
device's slab slice. The grid covers just the local rows/columns, so
the launch cost scales down with the shard, not the model.

**Compiled-mode fast path** (PR 8) — two compiled-only refinements
close the gap between the byte model and what actually moves:

* *In-kernel stochastic rounding* (``sr_seed=`` on the quantized
  transmit): the epilogue draws its rounding bits inside the kernel —
  ``pltpu.prng_seed(seed, program_id)`` + ``pltpu.prng_random_bits``,
  the seed derived from the round key by
  ``repro.core.channel.sr_kernel_seed`` (the same fold chain as the
  host-drawn uniforms) — instead of streaming the (1, d) f32 host
  draws through HBM: one less d-word read per transmit. The pltpu PRNG
  only lowers on TPU, so ``sr_seed`` demands a compiled launch
  (interpret raises); the host-drawn path stays the interpret/parity
  oracle, and because the in-kernel bits are a *different* uniform
  stream, agreement with that oracle is the one-quantization-step
  contract documented in ``kernels/ref.py``, not bitwise.

* *Bit-packed sign wire* (``pack_sign_slab`` / ``unpack_sign_slab``,
  ``ota_receive_slab(packed=...)``): the {-1, 0, +1} sign payload
  leaves the transmit WRAPPER packed 32 coords per uint32 word — the
  sign plane alone when the quantizer zero-folds (``zero_fold=True``:
  q in {-1, +1}, all-zero blocks scale 0 — a true 1 bit/coord wire),
  or sign + nonzero planes (2 bits/coord) preserving arbitrary
  {-1, 0, +1} bitwise. The receive prologue unpacks before the fused
  dequantize launch. Packing sits at the XLA level rather than in the
  kernel body deliberately: a (1, block_cols // 32) uint32 output tile
  would violate the lane alignment the compiled epilogue must keep,
  and XLA fuses the word-assembly into the payload's consumer anyway.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import obs
from repro.core.channel import cms_transform
from repro.kernels.interpret import (INTERPRET_BLOCK_CAP, coarse_block,
                                     resolve_interpret)

LANE = 128
SUBLANE = 8
DEFAULT_BLOCK_COLS = 512
# Scoped VMEM a lane-dense f32 MAC launch may fill with its
# (block_rows, LANE) tiles, of the 16 MiB default on a TPU v5e.
MAC_VMEM_BYTES = 12 << 20
# (block_rows, LANE) f32 temporaries the CMS transform keeps in VMEM
# (read off the compiler's scoped-VMEM report for a v5e). The reduction
# over a tile's client rows keeps one more for each row past the first
# (read off its refusals of 2 to 128 rows).
CMS_TEMP_TILES = 5
INT8_MAX = 127.0
# Quantized transmit tiles are (rows, LANE) lane rows; an int8 payload
# tile needs 32 of them (the int8 sublane packing of one vreg).
QUANT_BLOCK_ROWS = 32


def mac_block_rows(rows: int, tiles: int) -> int:
    """Block rows of a lane-dense f32 MAC launch over ``rows`` lane rows
    that holds ``tiles`` (block_rows, LANE) f32 tiles in VMEM (each
    operand and output tile twice, for the double-buffered pipeline,
    plus the kernel's temporaries): as many rows as fit
    ``MAC_VMEM_BYTES``, in whole sublane groups, or the whole axis when
    it is shorter (a block that spans a dimension may have any
    length). A launch whose tiles overflow the budget even at one
    sublane group holds too many client rows, and is refused."""
    br = MAC_VMEM_BYTES // (tiles * LANE * 4) // SUBLANE * SUBLANE
    if br < SUBLANE:
        raise ValueError(
            f"an f32 MAC launch holding {tiles} tiles of ({SUBLANE}, "
            f"{LANE}) f32 needs {tiles * SUBLANE * LANE * 4 >> 10} KiB of "
            f"VMEM, over its {MAC_VMEM_BYTES >> 20} MiB budget: launch fewer "
            "client rows at once (a smaller client_chunk)")
    return min(rows, br)


def channel_tiles(n: int) -> int:
    """VMEM tiles of the f32 channel over ``n`` client rows: the
    gradient, u, e and output tiles, double-buffered, the CMS
    transform's temporaries and the row reduction's."""
    return 2 * (n + 3) + CMS_TEMP_TILES + n - 1


def stream_tiles(rc: int) -> int:
    """VMEM tiles of the streamed transmit over ``rc`` client rows a
    chunk: the gradient, carry and output tiles, double-buffered, and
    the row reduction's temporaries."""
    return 2 * (rc + 2) + rc - 1


def _lane_rows(x: jax.Array, fill: float = 0.0) -> jax.Array:
    """(d,) -> (ceil(d / LANE), LANE): a view for every slab (d % LANE
    == 0 by the slab contract); other lengths are padded up to the next
    LANE multiple with ``fill``. A stack (n, d) -> (n, ceil(d / LANE),
    LANE) is assembled from its rows' views: a one-row stack is then a
    view too, and a several-row one is copied row by row (the TPU
    compiler takes minutes over one relayout reshape of a large stack)."""
    if x.ndim == 2:
        return jnp.stack([_lane_rows(row, fill) for row in x])
    tail = -x.shape[0] % LANE
    if tail:
        x = jnp.pad(x, (0, tail), constant_values=fill)
    return x.reshape(-1, LANE)


def _lane_block_rows(rows: int, tiles: int, interpret: bool) -> int:
    """The launch's block rows: ``mac_block_rows``, coarsened in
    interpret mode."""
    br = coarse_block(rows, mac_block_rows(rows, tiles), interpret,
                      cap=INTERPRET_BLOCK_CAP // LANE)
    return min(rows, br)


def _residual_stats_row(xi: jax.Array, scale: float) -> jax.Array:
    """The pilot-statistics epilogue, shared by the channel and receive
    kernels: reduce this tile's interference residual ``r = scale * xi``
    to one (1, LANE) row ``[count, sum log|r|, sum log^2|r|, 0, ...]``
    over the nonzero entries (zero-mask == the padding/disabled-channel
    fixed point; a tile's rows past the array's end are zeroed by the
    caller). Runs on values already in VMEM/VREGs."""
    r = jnp.abs(scale * xi.astype(jnp.float32))
    m = r > 0.0
    logr = jnp.where(m, jnp.log(jnp.maximum(r, jnp.finfo(jnp.float32).tiny)),
                     0.0)
    cnt = jnp.sum(m.astype(jnp.float32))
    s1 = jnp.sum(logr)
    s2 = jnp.sum(logr * logr)
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, LANE), 1)
    return jnp.where(lane == 0, cnt,
                     jnp.where(lane == 1, s1,
                               jnp.where(lane == 2, s2, 0.0)))


# The per-step stats rows live in a (grid, 1, LANE) array whose block
# (None, 1, LANE) spans the last two dimensions whole: the TPU lowering
# accepts a block's last two dims only when they are (8, 128)-divisible
# or equal to the array's, and a (1, LANE) row of a (grid, LANE) array
# is neither.
_STATS_SPEC = pl.BlockSpec((None, 1, LANE), lambda i: (i, 0, 0))


def _stats_shape(steps: int) -> jax.ShapeDtypeStruct:
    return jax.ShapeDtypeStruct((steps, 1, LANE), jnp.float32)


def _sum_stats_rows(rows: jax.Array) -> jax.Array:
    """(grid, 1, LANE) per-step stats rows -> the (3,) reduced statistics."""
    return jnp.sum(rows.reshape(-1, LANE), axis=0)[:3]


def _ota_kernel(*refs, alpha: float, scale: float, n_clients: int,
                stats: bool, rows: Optional[int]):
    g_ref, h_ref, u_ref, e_ref, out_ref = refs[:5]
    g = g_ref[...].astype(jnp.float32)              # (N, br, LANE)
    h = h_ref[...].astype(jnp.float32)              # (N, 1, 1)
    agg = jnp.sum(h * g, axis=0) / n_clients        # (br, LANE)
    xi = cms_transform(u_ref[...], e_ref[...], alpha)         # (br, LANE)
    out_ref[...] = agg + scale * xi
    if stats:
        if rows is not None:
            # Ragged last block: its rows past the array's end hold
            # unspecified values; zero them so they drop out.
            br = xi.shape[0]
            row = (pl.program_id(0) * br
                   + jax.lax.broadcasted_iota(jnp.int32, xi.shape, 0))
            xi = jnp.where(row < rows, xi, 0.0)
        refs[5][...] = _residual_stats_row(xi, scale)


def ota_channel_slab(grads: jax.Array, h: jax.Array, u: jax.Array,
                     e: jax.Array, *, alpha: float, scale: float,
                     n_total: int | None = None,
                     pilot_stats: bool = False,
                     interpret: Optional[bool] = None):
    """Fused f32 channel: grads (N, d) stacked client gradients, h (N,)
    fading draws, u (d,) uniform angles in (-pi/2, pi/2), e (d,) Exp(1)
    draws. Returns the aggregated noisy gradient (d,) float32.

    ``n_total`` overrides the 1/N normalisation (defaults to the local
    row count N). The sharded engine passes the GLOBAL client count here
    while feeding only this shard's rows, so per-shard partial sums psum
    to exactly the single-device aggregate.

    ``pilot_stats=True`` additionally returns the (3,) log-moment
    statistics of the injected interference residual (the fused
    epilogue; see the module docstring) as ``(out, stats)``."""
    if not (1.0 < alpha <= 2.0):
        raise ValueError(f"tail index alpha must be in (1, 2], got {alpha}")
    interpret = resolve_interpret(interpret)
    n, d = grads.shape
    if n_total is None:
        n_total = n
    g3 = _lane_rows(grads)
    # A non-slab length pads u with 0 and e with 1: the tail then
    # synthesizes exactly 0 interference and drops out of the stats.
    u2, e2 = _lane_rows(u), _lane_rows(e, fill=1.0)
    rows = g3.shape[1]
    br = _lane_block_rows(rows, channel_tiles(n), interpret)
    steps = pl.cdiv(rows, br)
    blk = pl.BlockSpec((br, LANE), lambda i: (i, 0))
    out_specs = blk
    out_shape = jax.ShapeDtypeStruct((rows, LANE), jnp.float32)
    if pilot_stats:
        out_specs = [out_specs, _STATS_SPEC]
        out_shape = [out_shape, _stats_shape(steps)]
    outs = pl.pallas_call(
        functools.partial(_ota_kernel, alpha=alpha, scale=scale,
                          n_clients=n_total, stats=pilot_stats,
                          rows=rows if rows % br else None),
        grid=(steps,),
        in_specs=[
            pl.BlockSpec((n, br, LANE), lambda i: (0, i, 0)),
            pl.BlockSpec((n, 1, 1), lambda i: (0, 0, 0)),
            blk,
            blk,
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=interpret,
        name=obs.OTA_CHANNEL,
    )(g3, h.reshape(n, 1, 1).astype(jnp.float32), u2, e2)
    if pilot_stats:
        return outs[0].reshape(-1)[:d], _sum_stats_rows(outs[1])
    return outs.reshape(-1)[:d]


# ---------------------------------------------------------------------------
# Staged pipeline kernels: transmit (+ quantize epilogue) and receive.
# ---------------------------------------------------------------------------

def _tx_kernel(g_ref, h_ref, out_ref, *, n_clients: int):
    g = g_ref[...].astype(jnp.float32)              # (N, bc)
    h = h_ref[...].astype(jnp.float32)              # (N, 1)
    out_ref[...] = jnp.sum(h * g, axis=0, keepdims=True) / n_clients


def _tx_stream_kernel(g_ref, h_ref, acc_ref, out_ref, *, n_clients: int):
    """Streamed transmit: grid (row_blocks, row_chunks), block-outer. The
    first client chunk seeds this block's output tile from the ``acc``
    carry; every chunk then folds its faded partial in-place."""
    r = pl.program_id(1)

    @pl.when(r == 0)
    def _seed():
        out_ref[...] = acc_ref[...]

    g = g_ref[...].astype(jnp.float32)              # (rc, br, LANE)
    h = h_ref[...].astype(jnp.float32)              # (rc, 1, 1)
    out_ref[...] = out_ref[...] + jnp.sum(h * g, axis=0) / n_clients


def _tx_quant_kernel(*refs, n_clients: int, stochastic: bool, qmode: str,
                     ef: bool, resid: bool, zero_fold: bool,
                     inkernel_sr: bool):
    # Lane-row layout: every tile is (rows, LANE) — one per-block scale
    # per row — so the per-block reductions run along lanes and no
    # in-kernel reshape is needed (the compiled lowering refuses a
    # (1, bc) -> (bc // LANE, LANE) relayout). refs[2] is the
    # host-drawn SR uniforms, EXCEPT under inkernel_sr where the same
    # slot carries the (1, 1) int32 SMEM seed (the host draws are never
    # materialized then).
    if ef:
        g_ref, h_ref, r_ref, ef_ref = refs[:4]
        outs = refs[4:]
    else:
        g_ref, h_ref, r_ref = refs[:3]
        outs = refs[3:]
    q_ref, s_ref = outs[:2]
    g = g_ref[...].astype(jnp.float32)              # (N, rows, LANE)
    h = h_ref[...].astype(jnp.float32)              # (N, 1, 1)
    a = jnp.sum(h * g, axis=0) / n_clients          # (rows, LANE)
    if ef:
        # Error feedback: the residual carried from the previous round
        # joins the faded partial BEFORE quantization, so what the wire
        # loses this round is re-offered next round.
        a = a + ef_ref[...].astype(jnp.float32)
    if qmode == "sign":
        # 1-bit signSGD payload: per-block magnitude = mean|x| (the L1
        # scale that makes +/-s the least-squares sign reconstruction),
        # payload = sign(x) on the int8 wire container. Deterministic
        # (canonical EF-signSGD) — the SR draws are ignored.
        meanabs = jnp.mean(jnp.abs(a), axis=1, keepdims=True)  # (rows, 1)
        if zero_fold:
            # Zero-folding (the 1-bit packable variant): q in {-1, +1}
            # only — exact zeros fold to +1 — and all-zero blocks keep
            # scale 0, so the slab's zero tail still dequantizes to
            # exactly 0 (+1 * 0). An isolated exact zero inside a
            # nonzero block dequantizes to +s: one quantization step,
            # within the documented wire contract, and measure-zero in
            # gradient data.
            s = meanabs
            q = jnp.where(a < 0.0, -1, 1).astype(jnp.int8)
        else:
            # {-1, 0, +1} container variant: all-zero blocks keep scale
            # 1 -> payload 0, the same zero-tail fixed point as int8.
            s = jnp.where(meanabs > 0.0, meanabs, 1.0)
            q = jnp.sign(a).astype(jnp.int8)
    else:
        maxabs = jnp.max(jnp.abs(a), axis=1, keepdims=True)    # (rows, 1)
        # All-zero blocks (the slab's zero tail) keep scale 1 -> payload
        # 0, so quantization preserves the zero-padding contract exactly.
        s = jnp.where(maxabs > 0.0, maxabs / INT8_MAX, 1.0)
        y = a / s
        if stochastic and inkernel_sr:
            # Compiled-mode fast path: draw the rounding uniforms
            # in-kernel. Seeding folds the grid step in so every block
            # draws a distinct stream; the low 24 bits of each word
            # become a uniform on [0, 1) at float32's native SR
            # granularity (2^-24 = one ulp at 1.0).
            pltpu.prng_seed(r_ref[0, 0], pl.program_id(0))
            bits = pltpu.prng_random_bits(y.shape)
            u24 = jnp.bitwise_and(bits, (1 << 24) - 1)
            y = jnp.floor(y + u24.astype(jnp.float32) * (1.0 / (1 << 24)))
        elif stochastic:
            y = jnp.floor(y + r_ref[...])
        else:
            y = jnp.round(y)
        q = jnp.clip(y, -INT8_MAX, INT8_MAX).astype(jnp.int8)
    q_ref[...] = q
    s_ref[...] = s
    if resid:
        # What the wire will NOT deliver: x - dequant(quant(x)), with x
        # the EF-augmented partial — still in-register, one extra write.
        outs[2][...] = a - q.astype(jnp.float32) * s


def ota_transmit_slab(grads: jax.Array, h: jax.Array, *,
                      n_total: int | None = None, quantize: bool = False,
                      r: Optional[jax.Array] = None, stochastic: bool = True,
                      qmode: str = "int8", zero_fold: bool = False,
                      sr_seed: Optional[jax.Array] = None,
                      ef: Optional[jax.Array] = None,
                      return_residual: bool = False,
                      acc: Optional[jax.Array] = None,
                      row_chunk: Optional[int] = None,
                      block_cols: int = DEFAULT_BLOCK_COLS,
                      interpret: Optional[bool] = None):
    """Transmit stage: one fused pass over this transmitter's gradients.

    grads: (N, d) stacked client gradients; h: (N,) effective fading
    (power control — and, on the streamed path, the participation mask
    and per-client aggregation weights — already folded in). Computes
    the faded partial sum ``(1/n_total) sum_n h[n] grads[n]`` in one
    read of G.

    ``quantize=False`` returns the f32 partial (d,) — the analog wire.
    ``quantize=True`` runs the quantize-on-write epilogue and returns
    ``(payload, scales)``: int8 (d,) and one f32 scale per LANE-wide
    block ((d // 128,)); ``r`` must then be the (d,) uniform [0, 1)
    stochastic-rounding draws (``repro.core.channel.sr_inputs``) unless
    ``stochastic=False`` (round-to-nearest). d must be a multiple of
    128 in quantized mode — every slab/slice is, by the slab padding
    contract.

    ``qmode`` selects the quantizer: ``"int8"`` (symmetric max|x|/127,
    stochastic rounding) or ``"sign"`` (1-bit signSGD: payload =
    sign(x) in {-1, 0, +1} on the int8 wire, scale = blockwise mean|x|;
    deterministic, ``r`` may be None). Both dequantize through the same
    ``ota_receive_slab``. ``zero_fold=True`` (sign only) selects the
    1-bit-packable sign variant: q in {-1, +1} (exact zeros fold to
    +1), all-zero blocks scale 0 — see the module docstring.

    ``sr_seed`` (int8 + stochastic only) switches the epilogue to
    IN-KERNEL rounding draws: pass the int32 scalar from
    ``repro.core.channel.sr_kernel_seed`` instead of ``r`` (which must
    then be None — the host draws are never materialized). Compiled
    launches only; the pltpu PRNG does not lower in interpret mode, so
    ``interpret=True`` (or auto-resolving to it) raises.

    **Error feedback**: ``ef`` is this transmitter's (d,) carried
    residual — it is added into the faded partial BEFORE quantization.
    ``return_residual=True`` appends the fresh residual
    ``x - dequant(quant(x))`` (x the EF-augmented partial) to the
    return: ``(payload, scales, residual)`` — still one read of G.

    **Streamed client axis** (see the module docstring): ``acc`` is a
    (d,) f32 carry — the running partial sum of the chunks already
    transmitted — and ``row_chunk`` tiles the client rows through the
    grid's client-chunk dimension (defaults to all rows: one row step,
    whose ``0 + sum`` accumulation is bitwise-equal to the resident
    kernel). Either argument selects the accumulating kernel; both are
    f32-only (``quantize=True`` raises — quantize the completed f32
    partial through a single-row launch instead, so every entry is
    quantized exactly once). The streamed launch tiles lane rows in
    ``mac_block_rows`` of its shapes; ``block_cols`` tiles the other
    launches.
    """
    interpret = resolve_interpret(interpret)
    n, d = grads.shape
    if n_total is None:
        n_total = n
    if quantize:
        block_cols = max(block_cols, QUANT_BLOCK_ROWS * LANE)
    block_cols = coarse_block(d, block_cols, interpret)
    streamed = acc is not None or row_chunk is not None
    if streamed and quantize:
        raise ValueError(
            "quantize=True cannot stream/accumulate (acc=/row_chunk=): the "
            "quantize-on-write epilogue must see the COMPLETED partial sum "
            "(one quantization step per entry, the wire contract); "
            "accumulate the f32 partial across chunks first, then quantize "
            "it with a single-row quantize=True launch")
    h2 = h.reshape(n, 1).astype(jnp.float32)

    if streamed:
        rc = n if row_chunk is None else min(row_chunk, n)
        if rc < 1:
            raise ValueError(f"row_chunk must be >= 1, got {row_chunk}")
        if acc is None:
            acc = jnp.zeros((d,), jnp.float32)
        if acc.shape != (d,):
            raise ValueError(f"acc must be the ({d},) running partial sum, "
                             f"got {acc.shape}")
        g3 = _lane_rows(grads)
        h3 = h2.reshape(n, 1, 1)
        # Zero rows contribute exactly 0 to the accumulation, so padding
        # the client axis up to a row-chunk multiple is value-neutral.
        n_pad = -(-n // rc) * rc
        if n_pad != n:
            g3 = jnp.pad(g3, ((0, n_pad - n), (0, 0), (0, 0)))
            h3 = jnp.pad(h3, ((0, n_pad - n), (0, 0), (0, 0)))
        rows = g3.shape[1]
        br = _lane_block_rows(rows, stream_tiles(rc), interpret)
        # The grid's last row block may be ragged: the math is per
        # coordinate, and the rows past the end are never written back.
        # The carry is updated in place (acc aliases the output).
        out = pl.pallas_call(
            functools.partial(_tx_stream_kernel, n_clients=n_total),
            grid=(pl.cdiv(rows, br), n_pad // rc),
            in_specs=[
                pl.BlockSpec((rc, br, LANE), lambda j, r_: (r_, j, 0)),
                pl.BlockSpec((rc, 1, 1), lambda j, r_: (r_, 0, 0)),
                pl.BlockSpec((br, LANE), lambda j, r_: (j, 0)),
            ],
            out_specs=pl.BlockSpec((br, LANE), lambda j, r_: (j, 0)),
            out_shape=jax.ShapeDtypeStruct((rows, LANE), jnp.float32),
            input_output_aliases={2: 0},
            interpret=interpret,
            name=obs.OTA_TRANSMIT_STREAM,
        )(g3, h3, _lane_rows(acc.astype(jnp.float32)))
        return out.reshape(-1)[:d]

    if not quantize:
        d_pad = -(-d // block_cols) * block_cols
        gp = jnp.pad(grads, ((0, 0), (0, d_pad - d)))
        out = pl.pallas_call(
            functools.partial(_tx_kernel, n_clients=n_total),
            grid=(d_pad // block_cols,),
            in_specs=[
                pl.BlockSpec((n, block_cols), lambda i: (0, i)),
                pl.BlockSpec((n, 1), lambda i: (0, 0)),
            ],
            out_specs=pl.BlockSpec((1, block_cols), lambda i: (0, i)),
            out_shape=jax.ShapeDtypeStruct((1, d_pad), jnp.float32),
            interpret=interpret,
            name=obs.OTA_TRANSMIT,
        )(gp, h2)
        return out.reshape(-1)[:d]

    if d % LANE != 0:
        raise ValueError(
            f"quantized transmit needs d to be a multiple of {LANE} "
            f"(the per-block scale width), got {d}; slabs satisfy this "
            "by construction")
    if qmode not in ("int8", "sign"):
        raise ValueError(f'unknown qmode {qmode!r}; options: "int8", "sign"')
    if zero_fold and qmode != "sign":
        raise ValueError("zero_fold is a sign-quantizer variant; "
                         f"qmode is {qmode!r}")
    inkernel_sr = sr_seed is not None
    if inkernel_sr:
        if not (qmode == "int8" and stochastic):
            raise ValueError(
                "sr_seed selects in-kernel stochastic rounding: it needs "
                "qmode='int8' with stochastic=True")
        if r is not None:
            raise ValueError(
                "pass EITHER the host-drawn uniforms r (the parity "
                "oracle) OR the in-kernel seed sr_seed, not both")
        if interpret:
            raise ValueError(
                "sr_seed needs a compiled launch: the pltpu PRNG does "
                "not lower in interpret mode — use the host-drawn r "
                "path there (it is the parity oracle)")
    elif (qmode == "int8" and stochastic
            and (r is None or r.shape != (d,))):
        raise ValueError("stochastic rounding needs r of shape "
                         f"({d},), got {None if r is None else r.shape}")
    if ef is not None and ef.shape != (d,):
        raise ValueError(f"ef must be the ({d},) carried residual, "
                         f"got {ef.shape}")
    d_pad = -(-d // block_cols) * block_cols
    n_rows, rb = d_pad // LANE, block_cols // LANE

    def lane_rows(x, fill=0.0):
        return jnp.pad(x, (0, d_pad - d),
                       constant_values=fill).reshape(n_rows, LANE)

    use_ef = ef is not None
    spec_rows = pl.BlockSpec((rb, LANE), lambda i: (i, 0))
    in_specs = [
        pl.BlockSpec((n, rb, LANE), lambda i: (0, i, 0)),
        pl.BlockSpec((n, 1, 1), lambda i: (0, 0, 0)),
    ]
    operands = [jnp.pad(grads, ((0, 0), (0, d_pad - d))).reshape(
        n, n_rows, LANE), h2.reshape(n, 1, 1)]
    if inkernel_sr:
        in_specs.append(pl.BlockSpec((1, 1), lambda i: (0, 0),
                                     memory_space=pltpu.SMEM))
        operands.append(jnp.asarray(sr_seed, jnp.int32).reshape(1, 1))
    else:
        if r is None:
            r = jnp.zeros((d,), jnp.float32)
        in_specs.append(spec_rows)
        operands.append(lane_rows(r))
    if use_ef:
        in_specs.append(spec_rows)
        operands.append(lane_rows(ef.astype(jnp.float32)))
    out_specs = [spec_rows, pl.BlockSpec((rb, 1), lambda i: (i, 0))]
    out_shape = [
        jax.ShapeDtypeStruct((n_rows, LANE), jnp.int8),
        jax.ShapeDtypeStruct((n_rows, 1), jnp.float32),
    ]
    if return_residual:
        out_specs.append(spec_rows)
        out_shape.append(jax.ShapeDtypeStruct((n_rows, LANE), jnp.float32))
    outs = pl.pallas_call(
        functools.partial(_tx_quant_kernel, n_clients=n_total,
                          stochastic=stochastic, qmode=qmode, ef=use_ef,
                          resid=return_residual, zero_fold=zero_fold,
                          inkernel_sr=inkernel_sr),
        grid=(n_rows // rb,),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=interpret,
        name=obs.OTA_TRANSMIT_QUANT,
    )(*operands)
    q, s = outs[0], outs[1]
    ret = (q.reshape(-1)[:d], s.reshape(-1)[:d // LANE])
    if return_residual:
        ret = ret + (outs[2].reshape(-1)[:d],)
    return ret


def sign_words(d: int, *, planes: bool = False) -> int:
    """Packed word count for a d-coordinate sign payload: d // 32
    uint32 words for the 1-bit zero-folded wire, twice that for the
    sign + nonzero bitplane pair."""
    if d % 32 != 0:
        raise ValueError(f"packing needs d to be a multiple of 32, got {d}")
    return (2 if planes else 1) * (d // 32)


def _bit_pos():
    return jnp.arange(32, dtype=jnp.uint32)  # XLA constant-folds this


def pack_sign_slab(payload: jax.Array, *, planes: bool = False) -> jax.Array:
    """Pack a {-1, 0, +1} int8 sign payload (..., d) into uint32 words
    (..., sign_words(d, planes)) — the transmit epilogue of the packed
    sign wire (see the module docstring).

    ``planes=False``: the sign plane alone — bit j of word w is 1 iff
    ``payload[32 w + j] < 0``. 1 bit/coord; zeros pack as +1, which is
    only faithful for the ``zero_fold=True`` quantizer (whose payloads
    carry no zeros and whose all-zero blocks ship scale 0).
    ``planes=True``: sign plane words followed by nonzero-mask plane
    words along the last axis — 2 bits/coord, any {-1, 0, +1} payload
    round-trips bitwise.
    """
    d = payload.shape[-1]
    nw = sign_words(d, planes=False)
    pos = _bit_pos()

    def plane(mask):
        b = mask.astype(jnp.uint32).reshape(*payload.shape[:-1], nw, 32)
        return jnp.sum(b << pos, axis=-1, dtype=jnp.uint32)

    sign_plane = plane(payload < 0)
    if not planes:
        return sign_plane
    return jnp.concatenate([sign_plane, plane(payload != 0)], axis=-1)


def unpack_sign_slab(words: jax.Array, d: int, *,
                     planes: bool = False) -> jax.Array:
    """Inverse of ``pack_sign_slab``: (..., sign_words(d, planes))
    uint32 words back to the (..., d) int8 sign payload the receive
    kernel dequantizes. The 1-bit wire decodes to {-1, +1} only (zeros
    were folded at the quantizer); the 2-plane wire restores exact
    {-1, 0, +1}."""
    nw = sign_words(d, planes=planes)
    if words.shape[-1] != nw:
        raise ValueError(f"expected {nw} packed words for d={d} "
                         f"(planes={planes}), got {words.shape[-1]}")
    pos = _bit_pos()

    def bits(w):
        b = (w[..., None] >> pos) & jnp.uint32(1)
        return (b > 0).reshape(*w.shape[:-1], w.shape[-1] * 32)

    if not planes:
        return jnp.where(bits(words), -1, 1).astype(jnp.int8)
    neg = bits(words[..., :nw // 2])
    nz = bits(words[..., nw // 2:])
    return jnp.where(nz, jnp.where(neg, -1, 1), 0).astype(jnp.int8)


def _rx_kernel(*refs, alpha: float, scale: float, stats: bool):
    q_ref, s_ref, u_ref, e_ref, out_ref = refs[:5]
    q = q_ref[...].astype(jnp.float32)              # (R, bc)
    s = s_ref[...]                                  # (R, nb)
    rows, bc = q.shape
    deq = q.reshape(rows, bc // LANE, LANE) * s[..., None]
    agg = jnp.sum(deq, axis=0).reshape(1, bc)       # superposed payloads
    xi = cms_transform(u_ref[...], e_ref[...], alpha)
    out_ref[...] = agg + scale * xi
    if stats:
        refs[5][...] = _residual_stats_row(xi, scale)


def ota_receive_slab(payload: jax.Array, scales: jax.Array, u: jax.Array,
                     e: jax.Array, *, alpha: float, scale: float,
                     packed: Optional[str] = None,
                     pilot_stats: bool = False,
                     block_cols: int = DEFAULT_BLOCK_COLS,
                     interpret: Optional[bool] = None):
    """Receive stage: dequantize + superpose R payload rows, then inject
    the alpha-stable interference — one fused pass.

    payload: (R, d) int8 — R transmitters' wire payloads (after the MAC
    collective each device holds the R rows addressed to its slice;
    single-device R == 1); scales: (R, d // 128) f32 per-block scales;
    u, e: (d,) CMS interference inputs. ``scale == 0`` disables the
    interference (e.g. for reducing clean-gradient statistics over the
    same wire). Returns (d,) f32, or ``(out, stats)`` with the (3,)
    residual log-moment statistics when ``pilot_stats=True`` (the fused
    epilogue; on the sharded engine each device reduces its own slice
    and the 3-vectors psum).

    ``packed="fold"|"planes"`` accepts the bit-packed sign wire
    instead: payload is then the (R, sign_words(d, ...)) uint32 words
    ``pack_sign_slab`` produced (d inferred from ``scales``), unpacked
    in the prologue before the fused dequantize launch.
    """
    if not (1.0 < alpha <= 2.0):
        raise ValueError(f"tail index alpha must be in (1, 2], got {alpha}")
    interpret = resolve_interpret(interpret)
    if packed is not None:
        if packed not in ("fold", "planes"):
            raise ValueError(f'unknown packed wire {packed!r}; '
                             'options: "fold", "planes"')
        if payload.dtype != jnp.uint32:
            raise ValueError("packed payloads are uint32 words, got "
                             f"{payload.dtype}")
        d = scales.shape[1] * LANE
        payload = unpack_sign_slab(payload, d, planes=(packed == "planes"))
    rows, d = payload.shape
    if d % LANE != 0:
        raise ValueError(f"receive needs d to be a multiple of {LANE}, "
                         f"got {d}")
    if scales.shape != (rows, d // LANE):
        raise ValueError(f"scales must be ({rows}, {d // LANE}), "
                         f"got {scales.shape}")
    # A step's (R, block_cols // LANE) scales block must be lane-dense
    # (a multiple of 128 scales) for the compiled lowering.
    block_cols = coarse_block(d, max(block_cols, LANE * LANE), interpret)
    d_pad = -(-d // block_cols) * block_cols
    grid = (d_pad // block_cols,)
    nb = block_cols // LANE
    qp = jnp.pad(payload, ((0, 0), (0, d_pad - d)))
    sp = jnp.pad(scales, ((0, 0), (0, (d_pad - d) // LANE)))
    up = jnp.pad(u, (0, d_pad - d)).reshape(1, d_pad)
    ep = jnp.pad(e, (0, d_pad - d), constant_values=1.0).reshape(1, d_pad)

    out_specs = pl.BlockSpec((1, block_cols), lambda i: (0, i))
    out_shape = jax.ShapeDtypeStruct((1, d_pad), jnp.float32)
    if pilot_stats:
        out_specs = [out_specs, _STATS_SPEC]
        out_shape = [out_shape, _stats_shape(grid[0])]
    outs = pl.pallas_call(
        functools.partial(_rx_kernel, alpha=alpha, scale=scale,
                          stats=pilot_stats),
        grid=grid,
        in_specs=[
            pl.BlockSpec((rows, block_cols), lambda i: (0, i)),
            pl.BlockSpec((rows, nb), lambda i: (0, i)),
            pl.BlockSpec((1, block_cols), lambda i: (0, i)),
            pl.BlockSpec((1, block_cols), lambda i: (0, i)),
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=interpret,
        name=obs.OTA_RECEIVE,
    )(qp, sp, up, ep)
    if pilot_stats:
        return outs[0].reshape(-1)[:d], _sum_stats_rows(outs[1])
    return outs.reshape(-1)[:d]
