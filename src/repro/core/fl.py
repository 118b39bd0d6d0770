"""Federated-learning round orchestration (paper Algorithm 1).

``make_round_step`` builds a jit-compiled function executing one full
communication round of ADOTA-FL in *simulation* mode (all N clients on
this host, vmapped):

    1. CLIENTUPDATE: every client computes its local gradient (k = 1,
       the paper's algorithm) or a FedAvg-style pseudo-gradient from k
       local SGD steps (optional extension);
    2. the analog MAC aggregates: g_t = (1/N) sum_n h_n grad_n + xi_t;
    3. the server applies the ADOTA adaptive update.

Steps 2-3 run on one of two backends. ``backend="jnp"`` is the per-leaf
``tree.map`` reference. ``backend="pallas"`` is the slab engine: client
gradients are stacked into one (N, d) slab (``repro.core.slab``), the
MAC is ONE fused ``ota_channel_slab`` launch, the resulting g_t slab is
fed — still flat — into ONE fused ``adaptive_update_slab`` launch, and
only then are params/state restored to pytrees. Two kernel launches per
round over the whole model instead of dozens of per-leaf ops; results
match the jnp backend to f32 rounding (both backends consume identical
PRNG draws).

``backend="pallas_sharded"`` (requires ``mesh=``) is the distributed
slab engine (``repro.core.shard.shard_round_step``): the client axis and
the slab are partitioned over the mesh's client-carrying axes, each
device runs the two fused launches on its local clients/slab shard, and
the OTA superposition is a real cross-client collective.

Every backend routes the MAC through the staged uplink pipeline
(``OTAChannelConfig.uplink``, see ``repro.core.ota``): transmit power
control -> quantize -> superposition -> interference -> receiver
dequantize. At the default ``uplink="f32"`` the rounds are
bitwise-identical to the pre-pipeline code; ``uplink="int8"`` carries
int8 payloads + per-block f32 scales over the MAC (~4x fewer collective
bytes on the sharded mesh); ``uplink="sign"`` carries 1-bit signSGD
payloads (~32x). The quantized modes optionally carry a per-transmitter
error-feedback residual across rounds (``UplinkConfig.error_feedback``,
resident as ``SlabTrainState.ef``), and the per-round model broadcast
can itself be int8-quantized (``OTAChannelConfig.downlink="int8"`` —
clients see the reconstruction, the server keeps the f32 master). Both
live only in the slab-resident loops.

``make_sharded_round_step`` is the older per-leaf distributed twin:
clients map onto (pod, data) shard groups and step 2 becomes the
``ota_psum`` collective inside ``shard_map``.

**Slab-resident variants** (the multi-round hot path since PR 3):
``make_slab_round_step`` / ``make_slab_round_runner`` keep the training
state as a ``SlabTrainState`` — params slab + optimizer-state slabs —
ACROSS rounds, materialising pytrees only at boundaries (init, eval,
checkpoint). The runner drives R rounds as one ``jax.lax.scan`` over
the resident state (under a mesh: scan inside ``shard_map``, each
device carrying only its slab slices — no full-model regather in the
scanned body). ``run_rounds_slab`` is the host driver twin of
``run_rounds`` with identical PRNG keying, so both drivers produce the
same trajectory from the same key.
"""

from __future__ import annotations

import contextlib
import dataclasses
import math
from typing import Any, Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro import obs
from repro.core.adaptive import (AdaptiveConfig, ServerOptState,
                                 apply_slab_update, make_server_optimizer,
                                 slab_update_slabs)
from repro.core.channel import OTAChannelConfig
from repro.core.ota import (downlink_quantize_slab, downlink_sr_slab_inputs,
                            ota_aggregate_slab, ota_aggregate_stacked,
                            ota_psum)
from repro.core.slab import make_slab_spec, slab_to_tree, tree_to_slab
from repro.core.slab_state import (SlabTrainState, pack_train_state,
                                   unpack_train_state)
from repro.core.stream import streamed_round_parts
from repro.core.tail_index import effective_alpha, update_alpha_ema

PyTree = Any
LossFn = Callable[[PyTree, Any], jax.Array]   # (params, batch) -> scalar


@dataclasses.dataclass(frozen=True)
class FLConfig:
    n_clients: int = 50
    local_steps: int = 1          # k; 1 == Algorithm 1 (one grad per round)
    local_lr: float = 0.05        # local SGD lr when local_steps > 1
    # Streamed client axis (PR 6, repro.core.stream): chunk the client
    # rows through the accumulating transmit kernel so peak memory is
    # O(client_chunk * d) regardless of n_clients. None == resident
    # (all rows in one chunk; the bitwise-parity configuration).
    client_chunk: Optional[int] = None
    # Partial participation: each client joins this round i.i.d. with
    # this probability (mask keyed off the round key, identical on all
    # backends). 1.0 == everyone, the pre-sampling bitwise path. Must
    # be > 0: rate 0 would make EVERY round a dead round (nobody ever
    # transmits, the state never moves), which is a config error, not
    # a training run.
    sample_rate: float = 1.0
    # Per-client aggregation weights (e.g. dataset sizes); None ==
    # uniform. The noisy aggregate is sum_n mask_n w_n h_n g_n
    # normalised by sum_n mask_n w_n, so any uniform tuple (c, ..., c)
    # reduces to the 1/N path.
    client_weights: Optional[Tuple[float, ...]] = None
    # Double-buffered streaming (PR 9): the client scan carries a
    # two-slot pipeline — chunk c's gradients are computed while chunk
    # c-1's prefetched slot is folded into the accumulators in one
    # fused pass — so the accumulation of one chunk overlaps the
    # compute of the next. Same draws, same chunk schedule; the fold
    # reassociates the per-chunk reduction, so the double-buffered
    # round is held to the loose cross-engine tolerance tier, not the
    # bitwise one (default off == today's serial scan, bit for bit).
    # Requires client_chunk (there is no scan to pipeline without it).
    double_buffer: bool = False

    def __post_init__(self):
        if not 0.0 < self.sample_rate <= 1.0:
            raise ValueError(f"sample_rate must be in (0, 1], got "
                             f"{self.sample_rate}; a rate of 0 means no "
                             "client ever participates (every round would "
                             "be a dead round)")
        if self.client_chunk is not None and self.client_chunk < 1:
            raise ValueError(f"client_chunk must be >= 1, got "
                             f"{self.client_chunk}")
        if self.double_buffer and self.client_chunk is None:
            raise ValueError(
                "double_buffer pipelines the STREAMED client scan; set "
                "client_chunk (the resident round has no chunk schedule "
                "to double-buffer)")
        if self.client_weights is not None:
            w = tuple(float(x) for x in self.client_weights)
            if len(w) != self.n_clients:
                raise ValueError(f"client_weights must have one entry per "
                                 f"client: got {len(w)} for "
                                 f"{self.n_clients} clients")
            if not all(math.isfinite(x) and x >= 0.0 for x in w):
                raise ValueError("client_weights must be finite and >= 0")
            if sum(w) <= 0.0:
                raise ValueError("client_weights must sum to > 0")
            object.__setattr__(self, "client_weights", w)

    @property
    def dynamic_norm(self) -> bool:
        """True when the aggregate normaliser is a round-dependent
        value (sum of participating weights) instead of the static 1/N."""
        return self.sample_rate < 1.0 or self.client_weights is not None

    @property
    def dynamic_round(self) -> bool:
        """True when the round must take the streamed/participating
        path (repro.core.stream) instead of the resident one."""
        return self.client_chunk is not None or self.dynamic_norm


class RoundMetrics(NamedTuple):
    loss: jax.Array               # mean participating-client loss before
                                  # the update
    grad_norm: jax.Array          # L2 norm of the clean aggregated gradient
    noisy_grad_norm: jax.Array    # L2 norm of g_t after the channel
    fading_mean: jax.Array        # mean of this round's h draw
    alpha_hat: jax.Array          # the tail index the server update used:
                                  # the resident EMA of the fused log-moment
                                  # estimate under alpha == "auto" (0.0
                                  # until first seeded), else the static
                                  # config float
    n_participants: jax.Array     # f32 count of clients in this round's
                                  # aggregate (== n_clients without
                                  # sampling; 0.0 marks a skipped round)


def _tree_l2(t: PyTree) -> jax.Array:
    return jnp.sqrt(sum(jnp.sum(jnp.square(x.astype(jnp.float32)))
                        for x in jax.tree.leaves(t)))


def _log_round(log, t: int, rec: dict) -> None:
    """One history record, formatted identically by both drivers."""
    log(f"round {t+1:5d}  loss {rec['loss']:.4f}  "
        f"|g| {rec['grad_norm']:.3e}  |g_t| {rec['noisy_grad_norm']:.3e}"
        + (f"  acc {rec.get('accuracy', float('nan')):.4f}"
           if 'accuracy' in rec else ""))


def _client_update(loss_fn: LossFn, fl_cfg: FLConfig
                   ) -> Callable[[PyTree, Any], Tuple[PyTree, jax.Array]]:
    """Build CLIENTUPDATE: (params, client_batch) -> (grad-like, loss)."""

    if fl_cfg.local_steps == 1:
        def one(params, batch):
            loss, g = jax.value_and_grad(loss_fn)(params, batch)
            return g, loss
        return one

    def multi(params, batches):
        # batches: pytree with leading axis k (one micro-batch per step).
        def step(w, batch):
            loss, g = jax.value_and_grad(loss_fn)(w, batch)
            w = jax.tree.map(lambda p, gi: p - fl_cfg.local_lr * gi, w, g)
            return w, loss
        w_k, losses = jax.lax.scan(step, params, batches)
        denom = fl_cfg.local_lr * fl_cfg.local_steps
        pseudo = jax.tree.map(lambda a, b: (a - b) / denom, params, w_k)
        # Mean over the k local steps, so RoundMetrics.loss is comparable
        # between local_steps == 1 and > 1 (losses[0] alone would report
        # only the pre-update loss of the first micro-batch).
        return pseudo, jnp.mean(losses)

    return multi


def _resolve_backend(backend: Optional[str], channel_cfg: OTAChannelConfig,
                     adaptive_cfg: AdaptiveConfig
                     ) -> Tuple[str, OTAChannelConfig, AdaptiveConfig]:
    """Pick the round backend and force both configs onto it.

    An explicit ``backend`` argument wins; otherwise the "biggest"
    backend either config requests switches the whole round (a split
    round — slab MAC but tree.map update, or vice versa — would just pay
    both conversion costs)."""
    if backend is None:
        requested = (channel_cfg.backend, adaptive_cfg.backend)
        backend = "jnp"
        for cand in ("pallas", "pallas_sharded"):
            if cand in requested:
                backend = cand
    if backend not in ("jnp", "pallas", "pallas_sharded"):
        raise ValueError(f"unknown round backend: {backend}")
    channel_cfg = dataclasses.replace(channel_cfg, backend=backend)
    adaptive_cfg = dataclasses.replace(adaptive_cfg, backend=backend)
    return backend, channel_cfg, adaptive_cfg


def make_round_step(loss_fn: LossFn, channel_cfg: OTAChannelConfig,
                    adaptive_cfg: AdaptiveConfig, fl_cfg: FLConfig,
                    jit: bool = True, backend: Optional[str] = None,
                    mesh=None):
    """One ADOTA-FL round over vmapped clients.

    Returns ``round_step(params, opt_state, key, client_batches)`` where
    ``client_batches`` leaves have shape (N, ...) for local_steps == 1 and
    (N, k, ...) otherwise. ``backend`` overrides the configs' backend
    fields ("jnp" | "pallas" | "pallas_sharded"); with "pallas" the round
    executes exactly one ``ota_channel_slab`` and one
    ``adaptive_update_slab`` launch over the full model. With
    "pallas_sharded" the round is distributed over ``mesh``'s
    client-carrying axes (required argument then): same signature, same
    results to f32 rounding, but each device runs the two fused launches
    on its local clients/slab shard (see ``repro.core.shard``).
    """
    backend, channel_cfg, adaptive_cfg = _resolve_backend(
        backend, channel_cfg, adaptive_cfg)
    if adaptive_cfg.track_alpha:
        raise ValueError(
            'AdaptiveConfig.alpha == "auto" needs the slab-resident loop '
            '(make_slab_round_step / make_slab_round_runner, or '
            'launch.train --track-alpha): the per-round pytree API has no '
            'resident alpha_hat to carry the estimator EMA across rounds')
    if fl_cfg.dynamic_round:
        raise ValueError(
            "client_chunk / sample_rate < 1 / client_weights need the "
            "slab-resident loop (make_slab_round_step / "
            "make_slab_round_runner): the per-round pytree API has no "
            "streamed uplink path")
    if channel_cfg.uplink.error_feedback or channel_cfg.downlink != "f32":
        raise ValueError(
            "error_feedback / downlink != \"f32\" need the slab-resident "
            "loop (make_slab_round_step / make_slab_round_runner): the "
            "per-round pytree API has no resident residual slab to carry "
            "across rounds and no slab broadcast to quantize")
    alpha_const = jnp.asarray(adaptive_cfg.alpha, jnp.float32)
    if backend == "pallas_sharded":
        # repro-lint: lazy-import (cycle: core.shard imports core.fl)
        from repro.core.shard import shard_round_step
        if mesh is None:
            raise ValueError('backend="pallas_sharded" needs a mesh; pass '
                             'make_round_step(..., mesh=...)')
        return shard_round_step(loss_fn, channel_cfg, adaptive_cfg, fl_cfg,
                                mesh, jit=jit)
    if mesh is not None:
        raise ValueError(
            f'mesh= was given but the resolved backend is "{backend}", '
            'which runs single-device and would silently ignore it; use '
            'backend="pallas_sharded" for distributed rounds')
    server_opt = make_server_optimizer(adaptive_cfg)
    client_fn = _client_update(loss_fn, fl_cfg)

    def round_step_jnp(params, opt_state: ServerOptState, key, client_batches):
        grads, losses = jax.vmap(client_fn, in_axes=(None, 0))(params, client_batches)
        g_t, h = ota_aggregate_stacked(key, channel_cfg, grads)
        clean = jax.tree.map(lambda g: jnp.mean(g, axis=0), grads)
        new_params, new_state = server_opt.update(g_t, opt_state, params)
        metrics = RoundMetrics(
            loss=jnp.mean(losses),
            grad_norm=_tree_l2(clean),
            noisy_grad_norm=_tree_l2(g_t),
            fading_mean=jnp.mean(h),
            alpha_hat=alpha_const,
            n_participants=jnp.asarray(float(fl_cfg.n_clients), jnp.float32),
        )
        return new_params, new_state, metrics

    def round_step_slab(params, opt_state: ServerOptState, key, client_batches):
        grads, losses = jax.vmap(client_fn, in_axes=(None, 0))(params, client_batches)
        spec = make_slab_spec(params)
        # Kernel launch 1: fused fading reduction + interference synthesis.
        g_slab, h, grads_slab, _, _ = ota_aggregate_slab(key, channel_cfg,
                                                         grads, spec)
        # Kernel launch 2: fused server update, g_t still in slab form.
        new_params, new_state = apply_slab_update(adaptive_cfg, spec, g_slab,
                                                  opt_state, params)
        # Slab norms == tree norms: the padding tail is zero by contract.
        metrics = RoundMetrics(
            loss=jnp.mean(losses),
            grad_norm=jnp.sqrt(jnp.sum(jnp.square(
                jnp.mean(grads_slab, axis=0)))),
            noisy_grad_norm=jnp.sqrt(jnp.sum(jnp.square(g_slab))),
            fading_mean=jnp.mean(h),
            alpha_hat=alpha_const,
            n_participants=jnp.asarray(float(fl_cfg.n_clients), jnp.float32),
        )
        return new_params, new_state, metrics

    round_step = round_step_slab if backend == "pallas" else round_step_jnp
    return jax.jit(round_step) if jit else round_step


def init_server(params: PyTree, adaptive_cfg: AdaptiveConfig) -> ServerOptState:
    return make_server_optimizer(adaptive_cfg).init(params)


# ---------------------------------------------------------------------------
# Slab-resident variants: state stays a SlabTrainState across rounds.
# ---------------------------------------------------------------------------

def make_slab_round_step(loss_fn: LossFn, channel_cfg: OTAChannelConfig,
                         adaptive_cfg: AdaptiveConfig, fl_cfg: FLConfig,
                         jit: bool = True, backend: Optional[str] = None,
                         mesh=None, batch_gen=None):
    """Slab-state twin of ``make_round_step``.

    Returns ``step(state, key, client_batches) -> (state, metrics)``
    where ``state`` is a ``SlabTrainState`` (see
    ``repro.core.slab_state``). Per round the only pytree materialised
    is the parameter view the clients consume (the server's model
    broadcast); optimizer state never leaves slab form. Backends:

    * ``"pallas"`` — resident single-device slab engine: two fused
      kernel launches per round, zero pack/unpack passes in steady
      state (vs 2 full packs + 2k slab round-trips for the pytree API).
    * ``"pallas_sharded"`` (requires ``mesh=``) — each device keeps only
      its slab slices; see ``repro.core.shard.make_shard_slab_step``.
    * ``"jnp"`` — reference: materialises pytrees each round and runs
      the per-leaf update (boundary conversion per round — the parity
      oracle, not a fast path).

    All backends consume identical PRNG draws, so their multi-round
    trajectories agree to f32 rounding.

    A DYNAMIC round config (``fl_cfg.client_chunk`` / ``sample_rate``
    / ``client_weights``) routes the jnp and pallas backends through
    the streamed uplink (``repro.core.stream``): the client axis is
    scanned in O(client_chunk * d) memory, participation and weights
    fold into the effective fading, and a zero-participation round
    SKIPS the server update (state unchanged, metrics recorded with
    ``n_participants == 0``). ``batch_gen(key, idx)`` replaces
    materialised ``client_batches`` (pass None for them then) with
    in-graph batch synthesis for populations too large to hold — only
    the streamed single-device backends support it.
    """
    backend, channel_cfg, adaptive_cfg = _resolve_backend(
        backend, channel_cfg, adaptive_cfg)
    if backend == "pallas_sharded":
        # repro-lint: lazy-import (cycle: core.shard imports core.fl)
        from repro.core.shard import make_shard_slab_step
        if mesh is None:
            raise ValueError('backend="pallas_sharded" needs a mesh; pass '
                             'make_slab_round_step(..., mesh=...)')
        if batch_gen is not None:
            raise ValueError('batch_gen= is only supported by the streamed '
                             'single-device backends, not "pallas_sharded"')
        return make_shard_slab_step(loss_fn, channel_cfg, adaptive_cfg,
                                    fl_cfg, mesh, jit=jit)
    if mesh is not None:
        raise ValueError(
            f'mesh= was given but the resolved backend is "{backend}", '
            'which runs single-device and would silently ignore it; use '
            'backend="pallas_sharded" for distributed rounds')
    track = adaptive_cfg.track_alpha
    # PR 7 wire formats: error feedback carries a resident residual slab
    # (SlabTrainState.ef) and the int8 downlink quantizes the model
    # broadcast — both live only in the slab-resident loops. On the jnp
    # backend they bypass the pytree-delegation reference paths below
    # and take the generic slab step (whose MAC/update layers dispatch
    # to the kernels.ref oracles internally), so every backend runs the
    # same EF/downlink plumbing over the same draws.
    use_ef = channel_cfg.uplink.error_feedback
    dl_int8 = channel_cfg.downlink == "int8"
    client_fn = _client_update(loss_fn, fl_cfg)

    def _check_ef_state(state: SlabTrainState) -> None:
        if use_ef and state.ef is None:
            raise ValueError(
                "UplinkConfig.error_feedback=True but the SlabTrainState "
                "carries no residual rows; build it with "
                "init_train_state(..., error_feedback=True)")

    def _broadcast_slab(state: SlabTrainState, key):
        """The (padded,) weight slab the CLIENTS see this round: the f32
        master under the f32 downlink, its int8-quantized reconstruction
        under downlink="int8" (the server always keeps the master)."""
        if not dl_int8:
            return state.w
        with jax.named_scope(obs.DRAWS):
            r = downlink_sr_slab_inputs(key, state.spec.padded)
        return downlink_quantize_slab(state.w, r)

    if fl_cfg.dynamic_round:
        use_kernels = backend != "jnp"

        def step(state: SlabTrainState, key, client_batches=None):
            _check_ef_state(state)
            spec = state.spec
            with jax.named_scope(obs.BROADCAST):
                params = slab_to_tree(spec, _broadcast_slab(state, key))
            parts = streamed_round_parts(
                key, channel_cfg, fl_cfg, spec, client_fn, params,
                client_batches=client_batches, batch_gen=batch_gen,
                pilot_stats=track, use_kernels=use_kernels,
                ef=state.ef[0] if use_ef else None)
            # Zero-participation skip: nobody transmitted, so there is
            # no aggregate to apply — the server state carries over
            # unchanged (only the round counter advances) and the
            # metrics record the dead round. Only a dynamic normaliser
            # can produce a dead round; with the static 1/N divisor the
            # selects are omitted entirely (a dead ``where`` changes
            # how XLA fuses the update kernel, costing the chunk >= N
            # bitwise contract).
            with jax.named_scope(obs.UPDATE):
                can_skip = fl_cfg.dynamic_norm
                participated = parts.norm > 0.0
                if track:
                    a_new = update_alpha_ema(state.alpha_hat, parts.stats,
                                             adaptive_cfg.alpha_ema)
                    alpha_hat = (jnp.where(participated, a_new,
                                           state.alpha_hat)
                                 if can_skip else a_new)
                    alpha_arg = effective_alpha(alpha_hat)
                    alpha_metric = alpha_hat
                else:
                    alpha_hat = state.alpha_hat
                    alpha_arg = None
                    alpha_metric = jnp.asarray(adaptive_cfg.alpha,
                                               jnp.float32)
            w_in = state.w
            if any(dt != jnp.float32 for dt in spec.dtypes):
                # The round-trip mirrors the pytree backends' per-round
                # storage-dtype cast; under the int8 downlink the cast
                # still applies to the MASTER weights (the update never
                # consumes the quantized broadcast).
                with jax.named_scope(obs.CAST):
                    w_in = tree_to_slab(spec, params if not dl_int8
                                        else slab_to_tree(spec, state.w))
            with jax.named_scope(obs.UPDATE):
                new_opt, w_new = slab_update_slabs(adaptive_cfg,
                                                   parts.g_slab, state.opt,
                                                   w_in, alpha=alpha_arg)
                ef_next = parts.ef_new[None] if use_ef else state.ef
                if can_skip:
                    w_new = jnp.where(participated, w_new, state.w)
                    new_opt = tuple(jnp.where(participated, o_n, o_o)
                                    for o_n, o_o in zip(new_opt, state.opt))
                    if use_ef:
                        # A dead round transmits nothing: the residual of
                        # a transmission that never happened must not
                        # replace the carried one.
                        ef_next = jnp.where(participated, ef_next, state.ef)
                nf = jnp.maximum(parts.n_participants, 1.0)
                metrics = RoundMetrics(
                    loss=parts.loss_sum / nf,
                    grad_norm=jnp.sqrt(jnp.sum(jnp.square(
                        parts.clean_slab / nf))),
                    noisy_grad_norm=jnp.sqrt(jnp.sum(jnp.square(
                        parts.g_slab))),
                    fading_mean=jnp.mean(parts.h),
                    alpha_hat=alpha_metric,
                    n_participants=parts.n_participants,
                )
            return SlabTrainState(state.step + 1, w_new, new_opt, alpha_hat,
                                  spec, ef_next), metrics

        return jax.jit(step) if jit else step

    if batch_gen is not None:
        raise ValueError("batch_gen= needs a streamed round config "
                         "(FLConfig.client_chunk); the resident path "
                         "consumes materialised client_batches")
    # EF / int8 downlink on the jnp backend skip the pytree-delegation
    # references (which have no residual slab to carry) and fall through
    # to the generic slab step; ota_aggregate_slab dispatches its MAC to
    # the kernels.ref oracles there, so it is still a pure-jnp program.
    if backend == "jnp" and not (use_ef or dl_int8):
        if not track:
            inner = make_round_step(loss_fn, channel_cfg, adaptive_cfg,
                                    fl_cfg, jit=False, backend="jnp")

            def step(state: SlabTrainState, key, client_batches):
                params, opt_state = unpack_train_state(adaptive_cfg, state)
                p, s, m = inner(params, opt_state, key, client_batches)
                return pack_train_state(adaptive_cfg, state.spec, p, s,
                                        alpha_hat=state.alpha_hat), m

            return jax.jit(step) if jit else step

        # The tracked jnp reference: the per-leaf round with the closed
        # alpha loop — stats from the per-leaf mirror of the kernel
        # epilogues, the same resident EMA, the per-leaf update consuming
        # the tracked alpha as a traced scalar. This is the parity oracle
        # the tracked pallas/pallas_sharded engines are tested against.
        server_opt = make_server_optimizer(adaptive_cfg)

        def step(state: SlabTrainState, key, client_batches):
            params, opt_state = unpack_train_state(adaptive_cfg, state)
            grads, losses = jax.vmap(client_fn, in_axes=(None, 0))(
                params, client_batches)
            g_t, h, stats = ota_aggregate_stacked(key, channel_cfg, grads,
                                                  pilot_stats=True)
            alpha_hat = update_alpha_ema(state.alpha_hat, stats,
                                         adaptive_cfg.alpha_ema)
            clean = jax.tree.map(lambda g: jnp.mean(g, axis=0), grads)
            new_params, new_state = server_opt.update(
                g_t, opt_state, params, alpha=effective_alpha(alpha_hat))
            metrics = RoundMetrics(
                loss=jnp.mean(losses),
                grad_norm=_tree_l2(clean),
                noisy_grad_norm=_tree_l2(g_t),
                fading_mean=jnp.mean(h),
                alpha_hat=alpha_hat,
                n_participants=jnp.asarray(float(fl_cfg.n_clients),
                                           jnp.float32),
            )
            return pack_train_state(adaptive_cfg, state.spec, new_params,
                                    new_state, alpha_hat=alpha_hat), metrics

        return jax.jit(step) if jit else step

    def step(state: SlabTrainState, key, client_batches):
        _check_ef_state(state)
        spec = state.spec
        # Model broadcast: the one pytree the round materialises (the
        # clients' loss_fn consumes pytrees; original leaf dtypes).
        # Under downlink="int8" the clients see the int8-quantized
        # reconstruction; the server's master slab stays f32.
        params = slab_to_tree(spec, _broadcast_slab(state, key))
        grads, losses = jax.vmap(client_fn, in_axes=(None, 0))(params,
                                                               client_batches)
        # Kernel launch 1: fused fading reduction + interference (with
        # the pilot-stats epilogue when the alpha loop is closed; the
        # carried EF residual joins the transmit quantizer in the same
        # launch, which returns the fresh residual to carry).
        g_slab, h, grads_slab, stats, ef_new = ota_aggregate_slab(
            key, channel_cfg, grads, spec, pilot_stats=track,
            ef=state.ef[0] if use_ef else None)
        if track:
            alpha_hat = update_alpha_ema(state.alpha_hat, stats,
                                         adaptive_cfg.alpha_ema)
            alpha_arg = effective_alpha(alpha_hat)
            alpha_metric = alpha_hat
        else:
            alpha_hat = state.alpha_hat
            alpha_arg = None
            alpha_metric = jnp.asarray(adaptive_cfg.alpha, jnp.float32)
        w_in = state.w
        if any(dt != jnp.float32 for dt in spec.dtypes):
            # Non-f32 leaves round-trip through their storage dtype each
            # round on the pytree backends; mirror that for parity. The
            # cast applies to the MASTER weights — never the quantized
            # broadcast, which only the clients consume.
            w_in = tree_to_slab(spec, params if not dl_int8
                                else slab_to_tree(spec, state.w))
        # Kernel launch 2: fused server update on the RESIDENT slabs
        # (the tracked alpha rides in as a traced operand).
        new_opt, w_new = slab_update_slabs(adaptive_cfg, g_slab, state.opt,
                                           w_in, alpha=alpha_arg)
        metrics = RoundMetrics(
            loss=jnp.mean(losses),
            grad_norm=jnp.sqrt(jnp.sum(jnp.square(
                jnp.mean(grads_slab, axis=0)))),
            noisy_grad_norm=jnp.sqrt(jnp.sum(jnp.square(g_slab))),
            fading_mean=jnp.mean(h),
            alpha_hat=alpha_metric,
            n_participants=jnp.asarray(float(fl_cfg.n_clients), jnp.float32),
        )
        return SlabTrainState(state.step + 1, w_new, new_opt, alpha_hat,
                              spec, ef_new[None] if use_ef else state.ef
                              ), metrics

    return jax.jit(step) if jit else step


def make_slab_round_runner(loss_fn: LossFn, channel_cfg: OTAChannelConfig,
                           adaptive_cfg: AdaptiveConfig, fl_cfg: FLConfig,
                           jit: bool = True, backend: Optional[str] = None,
                           mesh=None, batch_gen=None, donate: bool = False):
    """R rounds as ONE ``jax.lax.scan`` over the resident state.

    Returns ``run(state, keys, client_batches) -> (state, metrics)``
    with ``keys`` a (R,) key array and ``client_batches`` leaves shaped
    (R, N, ...); metrics come back stacked (R,). Under
    ``backend="pallas_sharded"`` the scan runs *inside* ``shard_map``
    (each device scans over its resident slices — no per-round dispatch,
    no full-model regather anywhere in the scanned body).

    With ``batch_gen(key, idx)`` (streamed in-graph data synthesis, see
    ``make_slab_round_step``) there are no materialised batches: call
    ``run(state, keys)`` and the scan carries keys only — nothing in
    the round scales with N beyond O(N) scalars (fading, mask).

    ``donate=True`` donates the incoming ``SlabTrainState`` buffers to
    the call (``donate_argnums=(0,)``): the compiled executable aliases
    every state slab (w, opt, alpha_hat, ef) to its output instead of
    allocating a second copy — the resident update is genuinely
    in-place, peak state memory is 1x across the scan-chunk boundary.
    The argument is CONSUMED: reuse of the passed state raises jax's
    donated-buffer error, so only enable it in linear state-threading
    drivers (``run_rounds_slab`` threads linearly; benches that replay
    from one initial state must not donate). Verify with
    ``donation_report``. Requires ``jit``.
    """
    backend, channel_cfg, adaptive_cfg = _resolve_backend(
        backend, channel_cfg, adaptive_cfg)
    if donate and not jit:
        raise ValueError("donate=True needs jit=True: buffer donation "
                         "is a property of the compiled executable")
    if backend == "pallas_sharded":
        # repro-lint: lazy-import (cycle: core.shard imports core.fl)
        from repro.core.shard import make_shard_slab_runner
        if mesh is None:
            raise ValueError('backend="pallas_sharded" needs a mesh; pass '
                             'make_slab_round_runner(..., mesh=...)')
        if batch_gen is not None:
            raise ValueError('batch_gen= is only supported by the streamed '
                             'single-device backends, not "pallas_sharded"')
        return make_shard_slab_runner(loss_fn, channel_cfg, adaptive_cfg,
                                      fl_cfg, mesh, jit=jit, donate=donate)
    step = make_slab_round_step(loss_fn, channel_cfg, adaptive_cfg, fl_cfg,
                                jit=False, backend=backend, mesh=mesh,
                                batch_gen=batch_gen)

    if batch_gen is not None:
        def run(state: SlabTrainState, keys, client_batches=None):
            if client_batches is not None:
                raise ValueError("batch_gen= runner takes no materialised "
                                 "client_batches")

            def scanned(s, key):
                return step(s, key)

            return jax.lax.scan(scanned, state, keys)
    else:
        def run(state: SlabTrainState, keys, client_batches):
            def scanned(s, xs):
                key, batch = xs
                return step(s, key, batch)

            return jax.lax.scan(scanned, state, (keys, client_batches))

    if not jit:
        return run
    return jax.jit(run, donate_argnums=(0,)) if donate else jax.jit(run)


def donation_report(run_jit, *example_args) -> dict:
    """Lower + compile a jitted round runner on example arguments and
    report what the executable actually aliases — the check that
    ``donate=True`` buys the in-place resident update it claims.

    Returns ``{"aliased_bytes", "donated_bytes", "aliased_pairs",
    "supported", "program_bytes"}``: ``donated_bytes`` is the total byte
    size of the donatable state leaves (argument 0), ``aliased_bytes``
    what the compiled memory analysis reports as input-output aliased,
    ``aliased_pairs`` the executable's ``input_output_alias`` entries
    parsed from the HLO, and ``program_bytes`` the analysis's
    ``{"argument", "output", "temp"}`` sizes. On backends whose memory
    analysis does not expose aliasing, ``supported`` is False and the
    byte fields are None (callers/tests should skip, not fail).

    The executable is the one the runner's next call with arguments of
    the same shapes and shardings runs: jit shares it with ``lower``.
    """
    lowered = run_jit.lower(*example_args)
    compiled = lowered.compile()
    state_leaves = jax.tree.leaves(example_args[0])
    donated = sum(x.size * x.dtype.itemsize for x in state_leaves
                  if hasattr(x, "size"))
    report = {"supported": False, "aliased_bytes": None,
              "donated_bytes": donated, "aliased_pairs": None,
              "program_bytes": None}
    try:
        mem = compiled.memory_analysis()
        aliased = getattr(mem, "alias_size_in_bytes", None)
    except Exception:
        aliased = None
    if aliased is not None:
        report["supported"] = True
        report["aliased_bytes"] = int(aliased)
        report["program_bytes"] = {
            "argument": int(mem.argument_size_in_bytes),
            "output": int(mem.output_size_in_bytes),
            "temp": int(mem.temp_size_in_bytes)}
    try:
        hlo = compiled.as_text()
        import re
        m = re.search(r"input_output_alias=\{([^}]*(?:\}[^}]*)*?)\}\s*\n",
                      hlo)
        if m is None:
            m = re.search(r"input_output_alias=\{(.*?)\}\n", hlo, re.S)
        if m is not None:
            pairs = re.findall(r"\{[\d,\s]*\}:\s*\([^)]*\)", m.group(0))
            report["aliased_pairs"] = pairs
            report["supported"] = True
    except Exception:
        pass
    return report


class _DeadRoundAggregator:
    """One WARNING line per log interval instead of one per dead round.

    ``record(t)`` counts a dead round (no participants, server update
    skipped); ``flush()`` emits a single summary line — count plus the
    round span — if any were recorded since the last flush. The drivers
    flush at every ``log_every`` boundary and once at the end of the
    run, so a low ``sample_rate`` at small N (where a majority of
    rounds can be dead) cannot flood the log between loss lines.
    """

    def __init__(self, log):
        self._log = log
        self._count = 0
        self._first = self._last = 0

    def record(self, t: int) -> None:
        if self._count == 0:
            self._first = t
        self._last = t
        self._count += 1

    def flush(self) -> None:
        if not self._count:
            return
        span = (f"round {self._first + 1:5d}" if self._first == self._last
                else f"rounds {self._first + 1}-{self._last + 1}")
        self._log(f"{span}  WARNING: {self._count} dead round(s) — no "
                  "participants, server update skipped; consider a higher "
                  "sample_rate")
        self._count = 0


def run_rounds_slab(run_chunk, state: SlabTrainState, key, batch_fn,
                    n_rounds: int, chunk: int = 8,
                    adaptive_cfg: Optional[AdaptiveConfig] = None,
                    eval_fn: Optional[Callable] = None, eval_every: int = 0,
                    log_every: int = 0, log=print,
                    key_fn: Optional[Callable] = None, start_round: int = 0,
                    chunk_hook: Optional[Callable] = None,
                    align: Tuple[int, ...] = (),
                    profile_dir: Optional[str] = None):
    """Slab-resident twin of ``run_rounds`` (host driver).

    ``run_chunk`` comes from ``make_slab_round_runner``. Rounds are
    dispatched in chunks of up to ``chunk`` (one scanned device program
    per chunk); by default the per-round PRNG keying is IDENTICAL to
    ``run_rounds`` — ``key, k_round, k_data = split(key, 3)`` per round,
    ``batch_fn(t, k_data)`` feeding host-side — so both drivers produce
    the same trajectory from the same key.

    ``key_fn(t) -> round key`` replaces the sequential split with
    keying by ABSOLUTE round index (``batch_fn`` then receives
    ``k_data=None``) — required when resuming from ``start_round > 0``,
    since round t's draws must not depend on how many rounds this
    process ran. Eval (which needs pytree params) happens only at chunk
    boundaries; chunks are clipped so every ``eval_every`` multiple —
    and every multiple of each period in ``align`` — IS a boundary.
    ``chunk_hook(t, state, history)`` runs after every chunk (e.g. for
    checkpointing). Returns ``(state, history)``.

    Each chunk runs under the host spans of ``repro.obs``: ``fl.chunk``
    (a step annotation numbered by the chunk's first round) around
    ``fl.keys``, ``fl.batch``, ``fl.dispatch``, ``fl.sync``,
    ``fl.history`` and ``fl.hook``. ``profile_dir`` writes a profiler
    trace of this call's second chunk, the first one whose program is
    already compiled, to that directory.
    """
    if eval_fn is not None and adaptive_cfg is None:
        raise ValueError("eval_fn needs adaptive_cfg= to materialise params "
                         "at eval boundaries")
    if start_round and key_fn is None:
        raise ValueError("start_round > 0 needs key_fn= (absolute-index "
                         "keying); the sequential split would replay "
                         "round-0 draws")
    history = []
    dead = _DeadRoundAggregator(log)
    t = start_round
    n_chunks = 0
    while t < n_rounds:
        r = min(chunk, n_rounds - t)
        for period in (eval_every, *align):
            if period:
                r = min(r, period - t % period)
        profiled = profile_dir is not None and n_chunks == 1
        with (jax.profiler.trace(profile_dir) if profiled
              else contextlib.nullcontext()), obs.span(obs.CHUNK, step=t):
            with obs.span(obs.KEYS):
                ks, kds = [], []
                for i in range(r):
                    if key_fn is not None:
                        k_round, k_data = key_fn(t + i), None
                    else:
                        key, k_round, k_data = jax.random.split(key, 3)
                    ks.append(k_round)
                    kds.append(k_data)
                keys = jnp.stack(ks)
            with obs.span(obs.BATCH):
                bs = [batch_fn(t + i, kds[i]) for i in range(r)]
                batches = jax.tree.map(lambda *xs: jnp.stack(xs), *bs)
            with obs.span(obs.DISPATCH):
                state, ms = run_chunk(state, keys, batches)
            with obs.span(obs.SYNC):
                loss = jax.device_get(ms.loss)
                gn = jax.device_get(ms.grad_norm)
                ngn = jax.device_get(ms.noisy_grad_norm)
                ah = jax.device_get(ms.alpha_hat)
                np_ = jax.device_get(ms.n_participants)
            with obs.span(obs.HISTORY):
                for i in range(r):
                    history.append({"round": t + i, "loss": float(loss[i]),
                                    "grad_norm": float(gn[i]),
                                    "noisy_grad_norm": float(ngn[i]),
                                    "alpha_hat": float(ah[i]),
                                    "n_participants": float(np_[i])})
                    if float(np_[i]) == 0.0:
                        dead.record(t + i)
            t += r
            n_chunks += 1
            if eval_fn is not None and eval_every and t % eval_every == 0:
                params, _ = unpack_train_state(adaptive_cfg, state)
                history[-1].update(eval_fn(params))
            if log_every:
                for i in range(t - r, t):
                    if (i + 1) % log_every == 0:
                        dead.flush()
                        # history is indexed from start_round, i is absolute
                        _log_round(log, i, history[i - start_round])
            if chunk_hook is not None:
                with obs.span(obs.HOOK):
                    chunk_hook(t, state, history)
    dead.flush()
    return state, history


def make_sharded_round_step(loss_fn: LossFn, channel_cfg: OTAChannelConfig,
                            adaptive_cfg: AdaptiveConfig,
                            client_axes: Tuple[str, ...] = ("data",)):
    """Distributed round step body — call inside ``shard_map``.

    Each shard group along ``client_axes`` is one client: it computes the
    gradient on its *local* batch, then the OTA collective aggregates.
    Model-parallel axes (if any) must be handled by the caller's model code;
    this body only owns the client/data axes.
    """
    server_opt = make_server_optimizer(adaptive_cfg)

    def body(params, opt_state: ServerOptState, key, local_batch):
        loss, local_grad = jax.value_and_grad(loss_fn)(params, local_batch)
        g_t = ota_psum(local_grad, key, channel_cfg, client_axes)
        new_params, new_state = server_opt.update(g_t, opt_state, params)
        loss = jax.lax.pmean(loss, client_axes)
        return new_params, new_state, loss

    return body


def run_rounds(round_step, params, opt_state, key, batch_fn, n_rounds: int,
               eval_fn: Optional[Callable] = None, eval_every: int = 0,
               log_every: int = 0, log=print):
    """Python-level training driver (data feeding is host-side).

    ``batch_fn(round_idx, key) -> client_batches``.
    Returns (params, opt_state, history list of dicts).
    """
    history = []
    dead = _DeadRoundAggregator(log)
    for t in range(n_rounds):
        key, k_round, k_data = jax.random.split(key, 3)
        batches = batch_fn(t, k_data)
        params, opt_state, m = round_step(params, opt_state, k_round, batches)
        rec = {"round": t, "loss": float(m.loss),
               "grad_norm": float(m.grad_norm),
               "noisy_grad_norm": float(m.noisy_grad_norm),
               "alpha_hat": float(m.alpha_hat),
               "n_participants": float(m.n_participants)}
        if rec["n_participants"] == 0.0:
            dead.record(t)
        if eval_fn is not None and eval_every and (t + 1) % eval_every == 0:
            rec.update(eval_fn(params))
        history.append(rec)
        if log_every and (t + 1) % log_every == 0:
            dead.flush()
            _log_round(log, t, rec)
    dead.flush()
    return params, opt_state, history
