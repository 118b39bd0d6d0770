"""Streamed client axis (PR 6): O(chunk) rounds, participation, weights.

The acceptance contracts:

* chunk >= N, full participation, no weights reproduces the resident
  slab round BITWISE on ``uplink="f32"`` (un-jitted — identical op
  sequence; under jit XLA may reassociate the client reduction between
  the two programs, so jitted trajectories are pinned at 1e-5 like
  every other cross-engine pair);
* the participation draw is ONE full (N,) uniform keyed off the round
  key via ``PART_FOLD`` — all backends sample literally identical
  clients, and ``sample_rate >= 1`` consumes no PRNG state at all;
* a zero-participation round is well-defined: the server update is
  SKIPPED (state bitwise unchanged, only the round counter advances)
  and the metrics record ``n_participants == 0``;
* uniform weights reduce to the unweighted path; non-uniform weights
  match the closed form sum(m w h g) / sum(m w);
* the accumulating / chunked transmit kernel agrees with its op-
  mirrored jnp oracle, and refuses the quantize epilogue (which must
  see the COMPLETED partial).
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (AdaptiveConfig, FLConfig, OTAChannelConfig,
                        UplinkConfig, init_train_state, make_round_step,
                        make_slab_round_runner, make_slab_round_step,
                        make_slab_spec, participation_mask,
                        round_participation, sample_fading,
                        streamed_round_parts)

N = 8
SHAPES = [(3, 45), (130,), (1,)]


def _params(key=None):
    ks = jax.random.split(key or jax.random.key(0), len(SHAPES))
    return {f"p{i}": jax.random.normal(k, s)
            for i, (k, s) in enumerate(zip(ks, SHAPES))}


def _batches(params, n=N, key=None):
    return jax.tree.map(
        lambda p: jax.random.normal(key or jax.random.key(3),
                                    (n,) + p.shape), params)


def _loss_fn(p, batch):
    return sum(jnp.mean((x - t) ** 2)
               for x, t in zip(jax.tree.leaves(p), jax.tree.leaves(batch)))


def _configs(uplink="f32", **fl_kw):
    ch = OTAChannelConfig(alpha=1.5, xi_scale=0.1,
                          uplink=UplinkConfig(mode=uplink))
    ad = AdaptiveConfig(optimizer="adam_ota", lr=0.05, alpha=1.5, beta2=0.3)
    return ch, ad, FLConfig(n_clients=fl_kw.pop("n_clients", N), **fl_kw)


def _trajectory(ch, ad, fl, backend, rounds=3, jit=True, params=None,
                batches=None):
    params = params or _params()
    batches = batches if batches is not None else _batches(params)
    step = make_slab_round_step(_loss_fn, ch, ad, fl, backend=backend,
                                jit=jit)
    st = init_train_state(ad, params)
    ms = None
    for t in range(rounds):
        st, ms = step(st, jax.random.fold_in(jax.random.key(7), t), batches)
    return st, ms


def _state_arrays(st):
    return [st.w, *st.opt, st.alpha_hat]


# ---------------------------------------------------------------------------
# Tentpole: streamed == resident
# ---------------------------------------------------------------------------

def test_chunk_ge_n_bitwise_f32_unjitted():
    """chunk >= N + full participation + no weights executes the exact
    resident slab op sequence: BITWISE equal trajectories, f32 uplink.
    (The jnp backend's resident path is the per-leaf pytree engine — a
    different op sequence — so it is covered by the 1e-5 tier below.)"""
    ch, ad, fl_res = _configs()
    _, _, fl_str = _configs(client_chunk=N)
    st_r, m_r = _trajectory(ch, ad, fl_res, "pallas", jit=False)
    st_s, m_s = _trajectory(ch, ad, fl_str, "pallas", jit=False)
    for a, b in zip(_state_arrays(st_r), _state_arrays(st_s)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert float(m_s.n_participants) == float(N)
    np.testing.assert_allclose(float(m_r.loss), float(m_s.loss), rtol=1e-6)


@pytest.mark.parametrize("backend", ["jnp", "pallas"])
def test_chunk_ge_n_jitted_close(backend):
    """Under jit XLA may reassociate the client reduction differently
    between the two programs — 1e-5, like every cross-engine pair."""
    ch, ad, fl_res = _configs()
    _, _, fl_str = _configs(client_chunk=N)
    st_r, _ = _trajectory(ch, ad, fl_res, backend, jit=True)
    st_s, _ = _trajectory(ch, ad, fl_str, backend, jit=True)
    for a, b in zip(_state_arrays(st_r), _state_arrays(st_s)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-5)


def test_chunk_ge_n_int8_close():
    """The quantized uplink composes with streaming: the completed
    partial crosses the same quantize + receive launches."""
    ch, ad, fl_res = _configs(uplink="int8")
    _, _, fl_str = _configs(uplink="int8", client_chunk=N)
    st_r, _ = _trajectory(ch, ad, fl_res, "pallas", jit=False)
    st_s, _ = _trajectory(ch, ad, fl_str, "pallas", jit=False)
    for a, b in zip(_state_arrays(st_r), _state_arrays(st_s)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("backend", ["jnp", "pallas"])
@pytest.mark.parametrize("chunk", [2, 4])
def test_chunk_lt_n_close(backend, chunk):
    """Chunked accumulation only reorders the f32 client sum."""
    ch, ad, fl_res = _configs()
    _, _, fl_str = _configs(client_chunk=chunk)
    st_r, m_r = _trajectory(ch, ad, fl_res, backend)
    st_s, m_s = _trajectory(ch, ad, fl_str, backend)
    for a, b in zip(_state_arrays(st_r), _state_arrays(st_s)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(float(m_r.loss), float(m_s.loss), rtol=1e-5)


@pytest.mark.parametrize("backend", ["jnp", "pallas"])
def test_ragged_chunk_matches_resident(backend):
    """A chunk that does NOT divide N is legal (PR 7): the final ragged
    chunk is padded with zero-gain rows, so the trajectory matches the
    resident round like any other chunking (f32 reassociation only) and
    the padded rows fold in exactly 0.0."""
    ch, ad, fl_res = _configs()
    _, _, fl_rag = _configs(client_chunk=3)        # ceil(8/3) = 3 chunks
    st_r, m_r = _trajectory(ch, ad, fl_res, backend)
    st_s, m_s = _trajectory(ch, ad, fl_rag, backend)
    for a, b in zip(_state_arrays(st_r), _state_arrays(st_s)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-5)
    assert float(m_s.n_participants) == float(m_r.n_participants)
    np.testing.assert_allclose(float(m_r.loss), float(m_s.loss), rtol=1e-5)


def test_ragged_chunk_matches_divisible_chunk():
    """chunk=3 and chunk=2 over N=8 accumulate the same partial: the
    zero-gain padding rows of the ragged tail contribute nothing."""
    ch, ad, _ = _configs()
    _, _, fl2 = _configs(client_chunk=2)
    _, _, fl3 = _configs(client_chunk=3)
    st_a, _ = _trajectory(ch, ad, fl2, "pallas")
    st_b, _ = _trajectory(ch, ad, fl3, "pallas")
    for a, b in zip(_state_arrays(st_a), _state_arrays(st_b)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-5)


def test_pytree_api_refuses_dynamic_rounds():
    ch, ad, fl = _configs(sample_rate=0.5)
    with pytest.raises(ValueError):
        make_round_step(_loss_fn, ch, ad, fl, backend="jnp")


# ---------------------------------------------------------------------------
# Partial participation
# ---------------------------------------------------------------------------

def test_participation_mask_contract():
    key = jax.random.key(5)
    ones = participation_mask(key, 16, 1.0)
    np.testing.assert_array_equal(np.asarray(ones), np.ones(16, np.float32))
    zeros = participation_mask(key, 16, 0.0)
    np.testing.assert_array_equal(np.asarray(zeros), np.zeros(16, np.float32))
    m = np.asarray(participation_mask(key, 4096, 0.25))
    assert set(np.unique(m)) <= {0.0, 1.0}
    assert 0.15 < m.mean() < 0.35
    # Deterministic in the key, and a different round key resamples.
    m2 = np.asarray(participation_mask(key, 4096, 0.25))
    np.testing.assert_array_equal(m, m2)
    m3 = np.asarray(participation_mask(jax.random.key(6), 4096, 0.25))
    assert not np.array_equal(m, m3)


def test_rate_one_consumes_no_prng_state():
    """Enabling the sampling code path at rate 1 must not perturb any
    other draw of the round: the mask comes from a PART_FOLD-separated
    key, and rate >= 1 short-circuits before even that."""
    key = jax.random.key(9)
    ch, _, fl = _configs(sample_rate=1.0)
    h_before = sample_fading(key, ch, (N,))
    mask, gain = round_participation(key, fl)
    h_after = sample_fading(key, ch, (N,))
    np.testing.assert_array_equal(np.asarray(h_before), np.asarray(h_after))
    np.testing.assert_array_equal(np.asarray(mask), np.ones(N, np.float32))
    np.testing.assert_array_equal(np.asarray(gain), np.asarray(mask))


@pytest.mark.parametrize("chunk", [None, 2])
def test_sampling_identical_across_backends(chunk):
    """jnp and pallas sample literally identical clients (one full draw
    keyed off the round key) and agree on the trajectory at 1e-5."""
    ch, ad, fl = _configs(sample_rate=0.5, client_chunk=chunk)
    st_j, m_j = _trajectory(ch, ad, fl, "jnp")
    st_p, m_p = _trajectory(ch, ad, fl, "pallas")
    assert float(m_j.n_participants) == float(m_p.n_participants)
    assert 0.0 < float(m_j.n_participants) < N
    for a, b in zip(_state_arrays(st_j), _state_arrays(st_p)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-5)


def test_sampling_identical_on_sharded_mesh():
    """The sharded engine slices the SAME full participation draw —
    mesh shape cannot change which clients transmit."""
    from repro.launch.mesh import make_client_mesh
    if len(jax.devices()) < 1:
        pytest.skip("no devices")
    ch, ad, fl = _configs(sample_rate=0.5, client_chunk=2)
    params = _params()
    batches = _batches(params)
    run_j = make_slab_round_runner(_loss_fn, ch, ad, fl, backend="jnp")
    run_s = make_slab_round_runner(_loss_fn, ch, ad, fl,
                                   backend="pallas_sharded",
                                   mesh=make_client_mesh((1,)))
    keys = jnp.stack([jax.random.fold_in(jax.random.key(7), t)
                      for t in range(3)])
    stacked = jax.tree.map(lambda b: jnp.stack([b] * 3), batches)
    st_j, ms_j = run_j(init_train_state(ad, params), keys, stacked)
    st_s, ms_s = run_s(init_train_state(ad, params, shards=1), keys, stacked)
    np.testing.assert_array_equal(np.asarray(ms_j.n_participants),
                                  np.asarray(ms_s.n_participants))
    np.testing.assert_allclose(np.asarray(st_j.w), np.asarray(st_s.w),
                               rtol=1e-5, atol=1e-5)


def test_zero_participation_skips_update():
    """A dead round must not divide by zero or move the server: state
    carries over bitwise, the round counter advances, and the metric
    records n_participants == 0. (``sample_rate=0.0`` is rejected at
    config time since PR 7, so the dead round is produced the way it
    happens in the field: a tiny rate and an unlucky round key.)"""
    ch, ad, fl = _configs(sample_rate=0.05)
    from repro.core import round_participation
    # The first seed whose round key samples nobody (found, not pinned:
    # the draw behind a given key depends on jax's PRNG implementation).
    dead_key = next(
        k for k in map(jax.random.key, range(1000))
        if float(jnp.sum(round_participation(k, fl)[0])) == 0.0)
    params = _params()
    batches = _batches(params)
    step = make_slab_round_step(_loss_fn, ch, ad, fl, backend="pallas")
    st0 = init_train_state(ad, params)
    st1, m = step(st0, dead_key, batches)
    assert int(st1.step) == int(st0.step) + 1
    np.testing.assert_array_equal(np.asarray(st0.w), np.asarray(st1.w))
    for a, b in zip(st0.opt, st1.opt):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_array_equal(np.asarray(st0.alpha_hat),
                                  np.asarray(st1.alpha_hat))
    assert float(m.n_participants) == 0.0
    assert np.isfinite(float(m.loss))


# ---------------------------------------------------------------------------
# Per-client aggregation weights
# ---------------------------------------------------------------------------

def test_uniform_weights_reduce_to_unweighted():
    """weights == (1, ..., 1) is the unweighted path, bitwise (the
    normaliser sum(m * 1) == sum(m) and the gain m * 1 == m)."""
    ch, ad, fl_none = _configs(sample_rate=0.5, client_chunk=2)
    _, _, fl_ones = _configs(sample_rate=0.5, client_chunk=2,
                             client_weights=(1.0,) * N)
    st_a, _ = _trajectory(ch, ad, fl_none, "pallas", jit=False)
    st_b, _ = _trajectory(ch, ad, fl_ones, "pallas", jit=False)
    for a, b in zip(_state_arrays(st_a), _state_arrays(st_b)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_weighted_aggregate_matches_closed_form():
    """sum(m w h g) / sum(m w): verified against a hand-computed
    aggregate from the same draws, interference off."""
    w = tuple(float(i + 1) for i in range(N))
    ch = OTAChannelConfig(alpha=1.5, xi_scale=0.0)
    _, ad, fl = _configs(sample_rate=0.7, client_chunk=2,
                         client_weights=w)
    params = _params()
    spec = make_slab_spec(params)
    batches = _batches(params)

    def client_fn(p, b):
        g = jax.grad(_loss_fn)(p, b)
        return g, _loss_fn(p, b)

    key = jax.random.key(21)
    parts = streamed_round_parts(key, ch, fl, spec, client_fn, params,
                                 client_batches=batches, use_kernels=False)
    mask, gain = round_participation(key, fl)
    kh, _ = jax.random.split(key)
    h = sample_fading(kh, ch, (N,))
    from repro.core import stack_to_slab
    grads = jax.vmap(lambda b: jax.grad(_loss_fn)(params, b))(batches)
    g_stack = stack_to_slab(spec, grads)
    norm = float(jnp.sum(gain))
    expected = np.asarray(
        jnp.sum((h * gain)[:, None] * g_stack, axis=0) / norm)
    np.testing.assert_allclose(np.asarray(parts.g_slab), expected,
                               rtol=1e-5, atol=1e-6)
    assert float(parts.norm) == pytest.approx(norm)
    assert float(parts.n_participants) == float(jnp.sum(mask))


def test_datasize_weights_streamed_matches_jnp_oracle():
    """Regression for the ``--client-weights datasize`` launch path
    (PR 7): weights proportional to per-client dataset sizes, combined
    with partial participation AND a multi-chunk streamed round, must
    track the jnp oracle — the weight schedule is sliced per chunk from
    the SAME full (N,) gain vector on every backend."""
    sizes = (4.0, 2.0, 7.0, 1.0, 3.0, 5.0, 2.0, 8.0)   # len(parts_i)
    ch, ad, fl = _configs(sample_rate=0.5, client_chunk=3,
                          client_weights=sizes)
    st_j, m_j = _trajectory(ch, ad, fl, "jnp")
    st_p, m_p = _trajectory(ch, ad, fl, "pallas")
    assert float(m_j.n_participants) == float(m_p.n_participants)
    # RoundMetrics accounting agrees too: the loss is the mean over
    # PARTICIPATING clients and the norms are of the weighted aggregate.
    np.testing.assert_allclose(float(m_j.loss), float(m_p.loss), rtol=1e-5)
    np.testing.assert_allclose(float(m_j.grad_norm), float(m_p.grad_norm),
                               rtol=1e-5)
    np.testing.assert_allclose(float(m_j.noisy_grad_norm),
                               float(m_p.noisy_grad_norm), rtol=1e-5)
    for a, b in zip(_state_arrays(st_j), _state_arrays(st_p)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-5)
    # And the weighting changed the aggregate: uniform weights over the
    # same draws land on a different trajectory.
    _, _, fl_u = _configs(sample_rate=0.5, client_chunk=3)
    st_u, _ = _trajectory(ch, ad, fl_u, "pallas")
    assert not np.allclose(np.asarray(st_p.w), np.asarray(st_u.w),
                           rtol=1e-6, atol=1e-7)


def test_flconfig_validates_streaming_fields():
    with pytest.raises(ValueError):
        FLConfig(n_clients=4, sample_rate=1.5)
    with pytest.raises(ValueError):
        FLConfig(n_clients=4, sample_rate=-0.1)
    with pytest.raises(ValueError, match="dead"):
        FLConfig(n_clients=4, sample_rate=0.0)   # every round dead (PR 7)
    with pytest.raises(ValueError):
        FLConfig(n_clients=4, client_chunk=0)
    with pytest.raises(ValueError):
        FLConfig(n_clients=4, client_weights=(1.0, 2.0))     # wrong len
    with pytest.raises(ValueError):
        FLConfig(n_clients=4, client_weights=(1.0, -1.0, 1.0, 1.0))
    with pytest.raises(ValueError):
        FLConfig(n_clients=4, client_weights=(0.0,) * 4)     # zero sum
    fl = FLConfig(n_clients=4, client_weights=[1, 2, 3, 4])
    assert fl.client_weights == (1.0, 2.0, 3.0, 4.0)
    assert fl.dynamic_norm and fl.dynamic_round
    assert not FLConfig(n_clients=4).dynamic_round
    assert FLConfig(n_clients=4, client_chunk=2).dynamic_round
    assert not FLConfig(n_clients=4, client_chunk=2).dynamic_norm


# ---------------------------------------------------------------------------
# Kernel level: accumulating / chunked transmit
# ---------------------------------------------------------------------------

def test_transmit_acc_chaining_matches_ref():
    from repro.kernels.ota_channel import ota_transmit_slab
    from repro.kernels.ref import ota_transmit_ref
    d, n = 300, 12
    g = jax.random.normal(jax.random.key(0), (n, d))
    h = jax.random.uniform(jax.random.key(1), (n,), minval=0.5, maxval=1.5)
    full = ota_transmit_ref(g, h, n_total=n)
    # Chained accumulation across two launches == one resident launch.
    acc = ota_transmit_slab(g[:4], h[:4], n_total=n,
                            acc=jnp.zeros((d,), jnp.float32), interpret=True)
    acc = ota_transmit_slab(g[4:], h[4:], n_total=n, acc=acc, interpret=True)
    np.testing.assert_allclose(np.asarray(acc), np.asarray(full),
                               rtol=1e-6, atol=1e-6)
    # In-kernel row chunking (padded grid) == the same sum.
    out = ota_transmit_slab(g, h, n_total=n, row_chunk=5, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(full),
                               rtol=1e-6, atol=1e-6)
    ref = ota_transmit_ref(g, h, n_total=n, row_chunk=5)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(full),
                               rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("rc", [1, 3])
@pytest.mark.parametrize("rows", [13, 21])
def test_transmit_stream_ragged_block_bitwise(rc, rows, monkeypatch):
    """The streamed transmit tiles (rows, 128) lane rows in blocks of 8.
    With a ragged last block, client chunks of rc rows (3 pads 4 rows
    to 6) and the carry chained over 4 launches, every entry is bitwise
    the one-block launch's, and, with one client a chunk, the oracle's.
    Several clients a chunk reduce in the kernel's own f32 order, as
    they did in the (1, 512)-column tiling: there the oracle is held to
    1e-6."""
    import repro.kernels.ota_channel as oc
    from repro.kernels.ref import ota_transmit_ref
    d, n = rows * 128, 4
    g = jax.random.normal(jax.random.key(rows), (4, n, d))
    h = jax.random.uniform(jax.random.key(rc), (4, n), minval=0.5,
                           maxval=1.5)
    acc0 = jax.random.normal(jax.random.key(7), (d,))

    def chain(launch):
        acc = acc0
        for k in range(4):
            acc = launch(g[k], h[k], n_total=4 * n, acc=acc, row_chunk=rc)
        return np.asarray(acc)

    one_block = chain(functools.partial(oc.ota_transmit_slab,
                                        interpret=True))
    # 8-row tiles, kept under interpret (no coarsening to one block).
    monkeypatch.setattr(oc, "mac_block_rows", lambda rows, tiles: 8)
    monkeypatch.setattr(oc, "coarse_block", lambda n_, b, i, cap=None: b)
    tiled = chain(functools.partial(oc.ota_transmit_slab, interpret=True))
    ref = chain(ota_transmit_ref)
    np.testing.assert_array_equal(tiled, one_block)
    if rc == 1:
        np.testing.assert_array_equal(tiled, ref)
    else:
        np.testing.assert_allclose(tiled, ref, rtol=1e-6, atol=1e-6)


def test_transmit_acc_refuses_quantize():
    from repro.kernels.ota_channel import ota_transmit_slab
    g = jnp.ones((4, 128))
    h = jnp.ones((4,))
    with pytest.raises(ValueError, match="quantize"):
        ota_transmit_slab(g, h, n_total=4, quantize=True,
                          acc=jnp.zeros((128,), jnp.float32), interpret=True)


def test_streamed_parts_single_vs_multi_chunk():
    """The chunked scan and the single-chunk path accumulate the same
    partial (f32 reassociation only)."""
    ch, ad, _ = _configs()
    _, _, fl1 = _configs(client_chunk=N)
    _, _, fl2 = _configs(client_chunk=2)
    params = _params()
    spec = make_slab_spec(params)
    batches = _batches(params)

    def client_fn(p, b):
        return jax.grad(_loss_fn)(p, b), _loss_fn(p, b)

    key = jax.random.key(3)
    p1 = streamed_round_parts(key, ch, fl1, spec, client_fn, params,
                              client_batches=batches, use_kernels=False)
    p2 = streamed_round_parts(key, ch, fl2, spec, client_fn, params,
                              client_batches=batches, use_kernels=False)
    np.testing.assert_allclose(np.asarray(p1.g_slab), np.asarray(p2.g_slab),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(float(p1.loss_sum), float(p2.loss_sum),
                               rtol=1e-6)


def test_batch_gen_round():
    """In-graph batch synthesis: no (N, ...) batch ever materialised —
    the runner scans over keys only."""
    ch, ad, fl = _configs(n_clients=16, client_chunk=4, sample_rate=0.75)
    params = {"w": jax.random.normal(jax.random.key(0), (64,))}

    def loss_fn(p, b):
        return jnp.mean((p["w"] - jnp.sin(b["phase"])) ** 2)

    def batch_gen(key, idx):
        return {"phase": idx.astype(jnp.float32) * 0.1}

    run = make_slab_round_runner(loss_fn, ch, ad, fl, backend="pallas",
                                 batch_gen=batch_gen)
    keys = jnp.stack([jax.random.fold_in(jax.random.key(7), t)
                      for t in range(3)])
    st, ms = run(init_train_state(ad, params), keys)
    assert int(st.step) == 3
    assert np.all(np.isfinite(np.asarray(st.w)))
    n_parts = np.asarray(ms.n_participants)
    assert n_parts.shape == (3,)
    assert np.all(n_parts >= 0) and np.all(n_parts <= 16)


def test_batch_gen_requires_dynamic_round():
    ch, ad, fl = _configs()     # no chunk, no sampling: resident path
    with pytest.raises(ValueError, match="streamed"):
        make_slab_round_step(_loss_fn, ch, ad, fl, backend="pallas",
                             batch_gen=lambda k, i: {"x": i})


# ---------------------------------------------------------------------------
# Satellite: configurable forced host-device count
# ---------------------------------------------------------------------------

def test_host_device_override(monkeypatch):
    from repro.launch.hostdev import (DEFAULT_HOST_DEVICES,
                                      host_device_override,
                                      mesh_device_count)
    monkeypatch.delenv("REPRO_HOST_DEVICES", raising=False)
    assert host_device_override([]) == DEFAULT_HOST_DEVICES
    assert host_device_override(["--host-devices", "12"]) == 12
    assert host_device_override(["--host-devices=3"]) == 3
    assert host_device_override(["--host-devices", "bogus"]) == \
        DEFAULT_HOST_DEVICES
    monkeypatch.setenv("REPRO_HOST_DEVICES", "5")
    assert host_device_override([]) == 5
    assert host_device_override(["--host-devices", "12"]) == 12  # flag wins
    # mesh_device_count floors at the override but still tracks the
    # largest requested mesh.
    assert mesh_device_count(["--meshes", "2"], "--meshes") == 5
    assert mesh_device_count(["--meshes", "16"], "--meshes") == 16
    monkeypatch.delenv("REPRO_HOST_DEVICES")
    assert mesh_device_count(
        ["--meshes", "2", "--host-devices", "2"], "--meshes") == 2
