"""Streamed client axis: O(chunk * d) rounds, participation, weights.

The resident round materialises all N client gradients as one (N, d)
slab before the MAC — N is capped by host memory, not by the channel.
This module makes N a STREAMED axis instead (ROADMAP Open item 1): the
round scans the client population in chunks of ``FLConfig.client_chunk``
rows, each chunk's gradients are computed, faded, and folded into the
running (d,) partial sum by the accumulating transmit kernel
(``ota_transmit_slab(..., acc=...)``), and only the completed partial
crosses the channel. Peak memory is O(chunk * d) regardless of N — a
million simulated clients fit on one CPU host.

Two wireless extensions ride on the same streamed transmit stage, both
folded into the EFFECTIVE fading coefficient next to power control:

* **Partial participation** — per-round Bernoulli sampling of the
  client population (``FLConfig.sample_rate``). The mask is one full
  (N,) uniform draw keyed off the round key via the ``PART_FOLD``
  domain separator, never re-keyed per chunk or per shard — the same
  full-draws-sliced contract as fading and stochastic rounding, so all
  three backends (and every mesh shape) sample literally identical
  clients.
* **Per-client aggregation weights** — ``FLConfig.client_weights``
  (e.g. dataset sizes, arXiv 2409.07822's weighted aggregation).

With sampling/weights active the 1/N normaliser becomes
``1 / sum_n mask_n * w_n``: the transmit launches accumulate the raw
weighted faded sum (``n_total=1``) and the divisor is applied once to
the completed partial, guarded against the zero-participation round
(``norm_safe``; the round-step layer then SKIPS the server update so
the state is unchanged — see ``make_slab_round_step``). Without them
(``dynamic_norm`` False) the static ``1/n_clients`` divisor stays
in-kernel, bit for bit.

**Bitwise contract.** The finish stage pushes the completed partial
through a single-ROW launch of the same fused channel/quantize kernels
the resident path uses (``sum(1 * x)/1 == x`` exactly in f32). With
``chunk >= N``, full participation, no weights and ``uplink="f32"``
there is nothing to accumulate, so the finish launches the resident
channel kernel on the whole client stack: the streamed round executes
the exact resident op sequence and is bitwise-identical to the
resident slab round — streaming is a pure memory optimization
(tests/test_stream.py pins this un-jitted; under ``jax.jit`` XLA may
reassociate the client reduction differently between the two programs,
so jitted trajectories are pinned at 1e-5 like every other
cross-engine pair). Uniform weights ``(c, ..., c)``
likewise reduce to the 1/N path: the accumulated sum is
``sum(h * c * g)`` and the divisor ``N * c``.
"""

from __future__ import annotations

from typing import Any, Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro import obs
from repro.core.channel import (OTAChannelConfig, sample_fading,
                                sr_kernel_seed)
from repro.core.ota import (_interference_slab_inputs, restore_zero_tail,
                            uplink_sr_slab_inputs)
from repro.core.slab import SlabSpec, stack_to_slab
from repro.kernels.interpret import resolve_interpret
from repro.kernels.ota_channel import (ota_channel_slab, ota_receive_slab,
                                       ota_transmit_slab, pack_sign_slab)
from repro.kernels.ref import (ota_channel_ref, ota_receive_ref,
                               ota_transmit_ref)

PyTree = Any

# PRNG domain separator of the participation draw (the same role
# channel.SR_FOLD plays for stochastic rounding): the (N,) mask uniforms
# are always ONE full draw from fold_in(round_key, PART_FOLD), sliced by
# whoever needs a sub-range — never re-keyed — so jnp / pallas /
# pallas_sharded sample identical clients by construction.
PART_FOLD = 0xACCE


def participation_mask(key: jax.Array, n_clients: int,
                       sample_rate: float) -> jax.Array:
    """This round's (N,) participation mask as f32 {0, 1}.

    ``sample_rate >= 1`` short-circuits to all-ones WITHOUT consuming
    PRNG state, so enabling sampling never perturbs the fading /
    interference / SR draws of existing configs (and rate == 1 rounds
    stay bitwise-identical to pre-sampling code)."""
    if sample_rate >= 1.0:
        return jnp.ones((n_clients,), jnp.float32)
    u = jax.random.uniform(jax.random.fold_in(key, PART_FOLD),
                           (n_clients,), jnp.float32)
    return (u < sample_rate).astype(jnp.float32)


def client_weight_array(fl_cfg) -> Optional[jax.Array]:
    """The (N,) f32 aggregation-weight vector, or None when uniform."""
    if fl_cfg.client_weights is None:
        return None
    return jnp.asarray(fl_cfg.client_weights, jnp.float32)


def round_participation(key: jax.Array, fl_cfg):
    """(mask, gain) of this round: the {0,1} participation mask and the
    per-client transmit gain (mask * weights) that multiplies the
    fading draw. Both full (N,) — sharded callers slice their rows."""
    mask = participation_mask(key, fl_cfg.n_clients, fl_cfg.sample_rate)
    w = client_weight_array(fl_cfg)
    gain = mask if w is None else mask * w
    return mask, gain


class StreamParts(NamedTuple):
    """Everything one streamed uplink pass produces (single device)."""
    g_slab: jax.Array         # (padded,) noisy aggregate after the channel
    h: jax.Array              # (N,) raw fading draw (for metrics)
    mask: jax.Array           # (N,) participation mask
    n_participants: jax.Array  # scalar f32: sum(mask)
    norm: jax.Array           # scalar f32 normaliser: sum(mask * w)
    loss_sum: jax.Array       # sum of participating clients' losses
    clean_slab: jax.Array     # (padded,) unfaded participant gradient sum
    stats: Optional[jax.Array]  # (3,) pilot log-moments (pilot_stats=True)
    ef_new: Optional[jax.Array] = None  # (padded,) fresh EF residual
                                        # (error feedback on a quantized
                                        # uplink; None otherwise)


def streamed_round_parts(key: jax.Array, channel_cfg: OTAChannelConfig,
                         fl_cfg, spec: SlabSpec,
                         client_fn: Callable, params: PyTree,
                         client_batches: PyTree = None,
                         batch_gen: Optional[Callable] = None,
                         pilot_stats: bool = False,
                         use_kernels: bool = True,
                         ef: Optional[jax.Array] = None) -> StreamParts:
    """One streamed uplink pass: scan the client axis in chunks, fold
    each chunk into the running partial via the accumulating transmit
    kernel, then push the completed partial through the single-row
    channel (or quantize + receive) launch.

    ``client_batches`` holds materialised per-client batches (leaves
    (N, ...), sliced per chunk); ``batch_gen(key, idx)`` instead
    synthesizes the batch of the ``idx`` (chunk,)-int32 client rows
    in-graph — required for client populations too large to materialise
    (the million-client benchmark). Exactly one of the two.

    A ``client_chunk`` that does NOT divide ``n_clients`` is served by a
    RAGGED final chunk: the tail rows past N are padding — their
    effective fading is zero (so they fold exactly 0.0 into the
    partial; their batch rows re-read row N-1, whose gradient is then
    multiplied by that zero) and their mask is zero (so clean/loss sums
    ignore them). All per-client draws stay full (N,) draws, so ragged
    chunking consumes identical PRNG state to any other chunking of the
    same round.

    ``use_kernels=False`` runs the op-mirrored ``kernels.ref`` path over
    the same slab layout and the same draws (the jnp backend).
    ``ef`` is this transmitter's carried (padded,) error-feedback
    residual: it joins the completed partial before the finish-stage
    quantizer (quantized uplink only) and the fresh residual comes back
    as ``StreamParts.ef_new``.
    """
    cfg = channel_cfg
    n = fl_cfg.n_clients
    chunk = min(fl_cfg.client_chunk or n, n)
    if (client_batches is None) == (batch_gen is None):
        raise ValueError("pass exactly one of client_batches / batch_gen")
    if ef is not None and not cfg.uplink.quantized:
        raise ValueError("ef= (error feedback) needs a quantized uplink; "
                         'the "f32" payload has no residual')

    dynamic_norm = fl_cfg.dynamic_norm
    n_div = 1 if dynamic_norm else n
    n_chunks = -(-n // chunk)
    n_padded = n_chunks * chunk
    with jax.named_scope(obs.DRAWS):
        mask, gain = round_participation(key, fl_cfg)
        kh, kx = jax.random.split(key)
        h = sample_fading(kh, cfg, (n,))
        # Participation and weights fold into the EFFECTIVE fading, next
        # to power control; with neither active h_eff is h * 1.0 == h
        # bitwise and the static 1/N divisor stays in-kernel.
        h_eff = h * gain if dynamic_norm else h
        # Ragged final chunk: pad the PER-ROW operands (effective fading,
        # mask) with zero rows up to the next chunk multiple. The padded
        # rows transmit with zero gain and count for nothing; the draws
        # above were taken at full (N,) BEFORE padding, so the PRNG
        # stream is untouched. When chunk | N this is a no-op
        # (zero-length pad), keeping the divisible path bitwise-identical.
        if n_padded != n:
            h_sched = jnp.pad(h_eff, (0, n_padded - n))
            mask_sched = jnp.pad(mask, (0, n_padded - n))
        else:
            h_sched, mask_sched = h_eff, mask

    def transmit(g_stack, h_c, acc):
        with jax.named_scope(obs.TRANSMIT):
            if use_kernels:
                return ota_transmit_slab(g_stack, h_c, n_total=n_div,
                                         acc=acc, interpret=cfg.interpret)
            return ota_transmit_ref(g_stack, h_c, n_total=n_div, acc=acc)

    ragged = n_padded != n

    def produce(c):
        """Chunk c's client compute + per-chunk operand slices: the
        SLOT of the double-buffered pipeline (everything chunk c
        contributes, before any accumulator is touched)."""
        start = c * chunk
        with jax.named_scope(obs.CLIENT):
            idx = start + jnp.arange(chunk)
            if ragged:
                # Padding rows re-read row N-1; its gradient lands with
                # the zero gain/mask of the padded schedule rows, so it
                # folds exactly 0.0 into every accumulator.
                idx = jnp.minimum(idx, n - 1)
            if batch_gen is not None:
                batch = batch_gen(key, idx)
            elif ragged:
                batch = jax.tree.map(lambda b: jnp.take(b, idx, axis=0),
                                     client_batches)
            else:
                batch = jax.tree.map(
                    lambda b: jax.lax.dynamic_slice_in_dim(b, start, chunk),
                    client_batches)
            grads, losses = jax.vmap(client_fn, in_axes=(None, 0))(params,
                                                                   batch)
        with jax.named_scope(obs.PACK):
            g_stack = stack_to_slab(spec, grads)
            h_c = jax.lax.dynamic_slice_in_dim(h_sched, start, chunk)
            m_c = jax.lax.dynamic_slice_in_dim(mask_sched, start, chunk)
        return g_stack, h_c, m_c, losses

    def body(carry, c):
        acc, clean, loss_sum = carry
        g_stack, h_c, m_c, losses = produce(c)
        acc = transmit(g_stack, h_c, acc)
        with jax.named_scope(obs.PACK):
            clean = clean + jnp.sum(m_c[:, None] * g_stack, axis=0)
            loss_sum = loss_sum + jnp.sum(m_c * losses)
        return (acc, clean, loss_sum), None

    def fold(carry, slot):
        """Double-buffered fold: one fused pass folds a completed slot
        into the accumulators. The faded and clean partials reduce
        TOGETHER as a (2, chunk) @ (chunk, d) product — one read of the
        gradient stack instead of two elementwise-multiply+reduce
        passes — which reassociates the per-chunk sum (the documented
        tolerance-tier trade of ``FLConfig.double_buffer``)."""
        acc, clean, loss_sum = carry
        g_stack, h_c, m_c, losses = slot
        with jax.named_scope(obs.PACK):
            coeff = jnp.stack([h_c * (1.0 / n_div), m_c])
            both = coeff @ g_stack
            return (acc + both[0], clean + both[1],
                    loss_sum + jnp.sum(m_c * losses))

    def db_body(carry, c):
        """Two-slot pipeline step: issue chunk c's client compute, then
        fold chunk c-1's prefetched slot. The two stages share no data
        dependency, so the runtime is free to run chunk c's gradients
        while chunk c-1's accumulation is in flight."""
        acc, clean, loss_sum, slot = carry
        new_slot = produce(c)
        acc, clean, loss_sum = fold((acc, clean, loss_sum), slot)
        return (acc, clean, loss_sum, new_slot), None

    with jax.named_scope(obs.PACK):
        zeros = jnp.zeros((spec.padded,), jnp.float32)
    # One chunk, static 1/N, f32 wire: the round IS the resident round,
    # so the finish below launches the resident channel kernel on the
    # whole stack instead of accumulating and re-launching one row.
    # Same ops by construction — XLA may contract the resident launch's
    # ``agg + scale * xi`` into an FMA and not the one-row launch's.
    resident_mac = (n == chunk and not dynamic_norm
                    and not cfg.uplink.quantized)
    if n == chunk:
        # Single chunk — the chunk >= N parity configuration: no scan,
        # no dynamic slicing (a traced slice start changes how XLA
        # fuses the client-gradient graph, costing the bitwise
        # contract), just the resident compute feeding the
        # accumulating kernel once.
        with jax.named_scope(obs.CLIENT):
            batch = (batch_gen(key, jnp.arange(n)) if batch_gen is not None
                     else client_batches)
            grads, losses = jax.vmap(client_fn, in_axes=(None, 0))(params,
                                                                   batch)
        with jax.named_scope(obs.PACK):
            g_stack = stack_to_slab(spec, grads)
        acc = None if resident_mac else transmit(g_stack, h_eff, zeros)
        with jax.named_scope(obs.PACK):
            clean = jnp.sum(mask[:, None] * g_stack, axis=0)
            loss_sum = jnp.sum(mask * losses)
    elif fl_cfg.double_buffer:
        # Prologue: chunk 0 fills the slot before the pipeline starts;
        # steady state overlaps produce(c) with fold(c-1); the epilogue
        # drains the final slot. Same draws, same chunk schedule, same
        # batch selection as the serial scan — only the accumulation
        # order moves.
        carry = (zeros, zeros, jnp.zeros((), jnp.float32), produce(0))
        carry, _ = jax.lax.scan(db_body, carry,
                                jnp.arange(1, n_chunks, dtype=jnp.int32))
        acc, clean, loss_sum = fold(carry[:3], carry[3])
    else:
        carry = (zeros, zeros, jnp.zeros((), jnp.float32))
        carry, _ = jax.lax.scan(body, carry,
                                jnp.arange(n_chunks, dtype=jnp.int32))
        acc, clean, loss_sum = carry

    with jax.named_scope(obs.CHANNEL):
        n_part = jnp.sum(mask)
        norm = jnp.sum(gain) if dynamic_norm else n_part
        if dynamic_norm:
            # Zero-participation guard: a dead round divides by 1 (the
            # partial is all-zero anyway) and the round step SKIPS the
            # server update; max(norm, 1) would instead corrupt
            # legitimate fractional-weight rounds.
            norm_safe = jnp.where(norm > 0.0, norm, 1.0)
            g_pre = acc / norm_safe
        else:
            g_pre = acc

    # Finish: the round crosses the channel through the SAME fused
    # kernels as the resident round. With ``resident_mac`` that is the
    # resident launch itself, on the whole client stack. Otherwise the
    # completed partial goes as a single transmitter row — sum(1 * x)/1
    # == x exactly, so the op order of the resident launch is kept.
    with jax.named_scope(obs.DRAWS):
        u, e, scale = _interference_slab_inputs(kx, cfg, spec)
    one = jnp.ones((1,), jnp.float32)
    stats = None
    ef_new = None
    if cfg.uplink.quantized:
        qmode = cfg.uplink.mode
        zero_fold = cfg.uplink.zero_fold
        packed = cfg.uplink.packed_sign
        stochastic = cfg.uplink.stochastic_rounding and qmode == "int8"
        inkernel = (stochastic and cfg.uplink.sr_inkernel and use_kernels
                    and not resolve_interpret(cfg.interpret))
        with jax.named_scope(obs.DRAWS):
            r = (uplink_sr_slab_inputs(key, spec)[0]
                 if stochastic and not inkernel else None)
        want_ef = ef is not None
        if use_kernels:
            with jax.named_scope(obs.DRAWS):
                sr_seed = sr_kernel_seed(key)[0] if inkernel else None
            with jax.named_scope(obs.CHANNEL):
                tx = ota_transmit_slab(g_pre[None], one, n_total=1,
                                       quantize=True, r=r,
                                       stochastic=stochastic, qmode=qmode,
                                       zero_fold=zero_fold, sr_seed=sr_seed,
                                       ef=ef, return_residual=want_ef,
                                       interpret=cfg.interpret)
                payload = (pack_sign_slab(tx[0][None],
                                          planes=(packed == "planes"))
                           if packed else tx[0][None])
                g_slab = ota_receive_slab(payload, tx[1][None], u, e,
                                          alpha=cfg.alpha, scale=scale,
                                          packed=packed,
                                          pilot_stats=pilot_stats,
                                          interpret=cfg.interpret)
        else:
            with jax.named_scope(obs.CHANNEL):
                tx = ota_transmit_ref(g_pre[None], one, n_total=1,
                                      quantize=True, r=r,
                                      stochastic=stochastic, qmode=qmode,
                                      zero_fold=zero_fold,
                                      ef=ef, return_residual=want_ef)
                payload = (pack_sign_slab(tx[0][None],
                                          planes=(packed == "planes"))
                           if packed else tx[0][None])
                g_slab = ota_receive_ref(payload, tx[1][None], u, e,
                                         alpha=cfg.alpha, scale=scale,
                                         packed=packed,
                                         pilot_stats=pilot_stats)
        if want_ef:
            ef_new = tx[2]
    else:
        rows, h_rows, n_rows = ((g_stack, h_eff, n) if resident_mac
                                else (g_pre[None], one, 1))
        with jax.named_scope(obs.CHANNEL):
            if use_kernels:
                g_slab = ota_channel_slab(rows, h_rows, u, e,
                                          alpha=cfg.alpha, scale=scale,
                                          n_total=n_rows,
                                          pilot_stats=pilot_stats,
                                          interpret=cfg.interpret)
            else:
                g_slab = ota_channel_ref(rows, h_rows, u, e,
                                         alpha=cfg.alpha, scale=scale,
                                         n_total=n_rows,
                                         pilot_stats=pilot_stats)
    if pilot_stats:
        g_slab, stats = g_slab
    if cfg.uplink.quantized and cfg.uplink.zero_fold:
        with jax.named_scope(obs.CHANNEL):
            g_slab = restore_zero_tail(g_slab, spec)
            ef_new = restore_zero_tail(ef_new, spec)

    return StreamParts(g_slab=g_slab, h=h, mask=mask,
                       n_participants=n_part, norm=norm,
                       loss_sum=loss_sum, clean_slab=clean, stats=stats,
                       ef_new=ef_new)
