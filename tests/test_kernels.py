"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps + properties.
All kernels run in interpret mode (CPU container; TPU is the target)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels.adaptive_update import adaptive_update_slab
from repro.kernels.flash_attention import flash_attention
from repro.kernels.ota_channel import ota_channel_slab
from repro.kernels.ref import (adaptive_update_ref, flash_attention_ref,
                               ota_channel_ref)

HP = dict(lr=0.02, beta1=0.9, beta2=0.3, alpha=1.5, eps=1e-8)


@pytest.mark.parametrize("n", [1, 127, 128, 1000, 4096, 70_000])
@pytest.mark.parametrize("mode", ["adagrad", "adam"])
@pytest.mark.parametrize("wdtype", [jnp.float32, jnp.bfloat16])
def test_adaptive_update_sweep(n, mode, wdtype):
    ks = jax.random.split(jax.random.key(n), 4)
    g = jax.random.normal(ks[0], (n,), wdtype)
    d0 = jax.random.normal(ks[1], (n,), jnp.float32)
    v0 = jnp.abs(jax.random.normal(ks[2], (n,), jnp.float32))
    w0 = jax.random.normal(ks[3], (n,), wdtype)
    dn, vn, wn = adaptive_update_slab(g, d0, v0, w0, mode=mode, **HP)
    dr, vr, wr = adaptive_update_ref(g, d0, v0, w0, mode=mode, **HP)
    np.testing.assert_allclose(np.asarray(dn), np.asarray(dr),
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(vn), np.asarray(vr),
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(wn, np.float32),
                               np.asarray(wr, np.float32),
                               rtol=2e-2 if wdtype == jnp.bfloat16 else 2e-5,
                               atol=2e-2 if wdtype == jnp.bfloat16 else 2e-5)


@settings(max_examples=15, deadline=None)
@given(n=st.integers(1, 2000), alpha=st.floats(1.05, 2.0),
       beta1=st.floats(0.0, 0.99))
def test_adaptive_update_property(n, alpha, beta1):
    hp = dict(lr=0.02, beta1=beta1, beta2=0.3, alpha=alpha, eps=1e-8)
    ks = jax.random.split(jax.random.key(n), 4)
    g = jax.random.normal(ks[0], (n,))
    d0 = jax.random.normal(ks[1], (n,))
    v0 = jnp.abs(jax.random.normal(ks[2], (n,)))
    w0 = jax.random.normal(ks[3], (n,))
    dn, vn, wn = adaptive_update_slab(g, d0, v0, w0, mode="adam", **hp)
    dr, vr, wr = adaptive_update_ref(g, d0, v0, w0, mode="adam", **hp)
    np.testing.assert_allclose(np.asarray(wn), np.asarray(wr),
                               rtol=5e-5, atol=5e-5)
    # nu stays nonneg (stepsize denominator well-defined)
    assert float(jnp.min(vn)) >= 0.0


@pytest.mark.parametrize("n_clients,d", [(1, 100), (8, 513), (50, 2048)])
@pytest.mark.parametrize("alpha", [1.2, 1.7, 2.0])
def test_ota_channel_sweep(n_clients, d, alpha):
    ks = jax.random.split(jax.random.key(d + n_clients), 4)
    G = jax.random.normal(ks[0], (n_clients, d))
    h = jax.random.uniform(ks[1], (n_clients,), minval=0.1, maxval=2.0)
    u = jax.random.uniform(ks[2], (d,), minval=-1.57, maxval=1.57)
    e = -jnp.log(jax.random.uniform(ks[3], (d,), minval=1e-6))
    out = ota_channel_slab(G, h, u, e, alpha=alpha, scale=0.1)
    ref = ota_channel_ref(G, h, u, e, alpha=alpha, scale=0.1)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=3e-4, atol=3e-4)


@pytest.mark.parametrize("n_clients,rows", [(1, 13), (4, 21)])
def test_ota_channel_ragged_block_stats(n_clients, rows, monkeypatch):
    """The f32 channel on a ragged last block of 8-row lane tiles: the
    output matches the oracle, and the pilot statistics equal the
    oracle's, so the block's rows past the end add nothing. The
    interpreter fills those rows with NaN, which the stats' zero mask
    drops by itself; here it fills them with 1.0, finite garbage like
    what the chip's VMEM holds there."""
    from jax._src.pallas import primitives
    import repro.kernels.ota_channel as oc
    from repro.core.channel import cms_inputs
    monkeypatch.setattr(oc, "coarse_block", lambda n_, b, i, cap=None: b)
    monkeypatch.setattr(oc, "mac_block_rows", lambda rows, tiles: 8)
    fill = primitives.uninitialized_value
    monkeypatch.setattr(
        primitives, "uninitialized_value",
        lambda shape, dtype: (jnp.full(shape, 1.0, dtype)
                              if jnp.issubdtype(dtype, jnp.floating)
                              else fill(shape, dtype)))
    d = rows * 128
    G = jax.random.normal(jax.random.key(rows), (n_clients, d))
    h = jax.random.uniform(jax.random.key(1), (n_clients,), minval=0.5,
                           maxval=1.5)
    u, e = cms_inputs(jax.random.key(2), (d,))
    out, st = oc.ota_channel_slab(G, h, u, e, alpha=1.5, scale=0.3,
                                  pilot_stats=True, interpret=True)
    out_r, st_r = ota_channel_ref(G, h, u, e, alpha=1.5, scale=0.3,
                                  pilot_stats=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(out_r),
                               rtol=3e-4, atol=3e-4)
    assert float(st[0]) == float(st_r[0]) == d
    np.testing.assert_allclose(np.asarray(st), np.asarray(st_r), rtol=1e-5)


@pytest.mark.parametrize("launch,largest", [("channel", 1020),
                                            ("stream", 1023)])
def test_mac_block_rows_refuses_vmem_overflow(launch, largest):
    """The most client rows an f32 MAC launch takes: one sublane group
    of every tile fits the VMEM budget; one row more is refused with a
    clear error rather than left to fail in the TPU compiler."""
    import repro.kernels.ota_channel as oc
    tiles = getattr(oc, f"{launch}_tiles")
    assert oc.mac_block_rows(10_000, tiles(largest)) == 8
    assert oc.mac_block_rows(5, tiles(largest)) == 5
    with pytest.raises(ValueError, match="VMEM"):
        oc.mac_block_rows(10_000, tiles(largest + 1))


@pytest.mark.parametrize("mode", ["amsgrad", "yogi", "momentum", "sgd"])
@pytest.mark.parametrize("n", [1, 127, 1000, 70_000])
def test_adaptive_update_extended_modes(mode, n):
    """The new fused modes match the jnp oracle on the same slab."""
    ks = jax.random.split(jax.random.key(n), 5)
    g = jax.random.normal(ks[0], (n,))
    d0 = jax.random.normal(ks[1], (n,))
    v0 = jnp.abs(jax.random.normal(ks[2], (n,)))
    m0 = v0 + jnp.abs(jax.random.normal(ks[3], (n,)))
    w0 = jax.random.normal(ks[4], (n,))
    kw = dict(mode=mode, nu_max=(m0 if mode == "amsgrad" else None), **HP)
    outs = adaptive_update_slab(g, d0, v0, w0, **kw)
    refs = adaptive_update_ref(g, d0, v0, w0, **kw)
    assert len(outs) == len(refs) == {"amsgrad": 4, "yogi": 3,
                                      "momentum": 2, "sgd": 1}[mode]
    for o, r in zip(outs, refs):
        np.testing.assert_allclose(np.asarray(o), np.asarray(r),
                                   rtol=2e-5, atol=2e-5)


def test_adaptive_update_unknown_mode_rejected():
    z = jnp.zeros(8)
    with pytest.raises(ValueError):
        adaptive_update_slab(z, z, z, z, mode="rmsprop", **HP)


def test_ota_channel_alpha_guards():
    """Satellite: the CMS kernel matches core.channel's guards — tail
    index validated to (1, 2], endpoint angles finite, alpha == 2 reduces
    to the Gaussian special case 2*sin(u)*sqrt(e)."""
    import math
    G = jnp.zeros((2, 8))
    h = jnp.ones(2)
    # endpoint angles included: f32 cos(pi/2) is slightly NEGATIVE, which
    # made the unguarded transform NaN for every alpha.
    u = jnp.array([math.pi / 2, -math.pi / 2, 0.0, 1.0, -1.0, 1.5, -1.5,
                   0.5], jnp.float32)
    e = jnp.abs(jax.random.normal(jax.random.key(0), (8,))) + 0.1

    for bad in (1.0, 0.5, 2.5, -1.5):
        with pytest.raises(ValueError):
            ota_channel_slab(G, h, u, e, alpha=bad, scale=0.1)

    for alpha in (1.05, 1.5, 2.0):
        out = ota_channel_slab(G, h, u, e, alpha=alpha, scale=1.0)
        assert bool(jnp.all(jnp.isfinite(out))), alpha
        np.testing.assert_allclose(
            np.asarray(out),
            np.asarray(ota_channel_ref(G, h, u, e, alpha=alpha, scale=1.0)),
            rtol=3e-4, atol=3e-4)

    # alpha == 2: Gaussian reduction (away from the clipped endpoints).
    out2 = ota_channel_slab(G, h, u, e, alpha=2.0, scale=1.0)
    gauss = 2.0 * jnp.sin(u) * jnp.sqrt(e)
    np.testing.assert_allclose(np.asarray(out2[2:]), np.asarray(gauss[2:]),
                               rtol=1e-4, atol=1e-4)


def test_ota_channel_matches_sampler_draws():
    """Feeding the kernel the sampler's own (u, e) draws reproduces
    sample_alpha_stable exactly — the identity the pallas channel backend
    relies on for bit-parity with the jnp backend."""
    from repro.core.channel import cms_inputs, sample_alpha_stable
    key = jax.random.key(123)
    d = 3000
    u, e = cms_inputs(key, (d,))
    for alpha in (1.2, 1.7, 2.0):
        xi_ref = sample_alpha_stable(key, alpha, (d,), scale=0.3)
        out = ota_channel_slab(jnp.zeros((1, d)), jnp.zeros(1), u, e,
                               alpha=alpha, scale=0.3)
        np.testing.assert_allclose(np.asarray(out), np.asarray(xi_ref),
                                   rtol=2e-5, atol=2e-6)


FLASH_CASES = [
    # (B, Sq, Sk, H, K, D, causal, window, bq, bk)
    (1, 32, 32, 2, 2, 16, True, None, 16, 16),
    (2, 64, 64, 4, 2, 32, True, None, 32, 32),
    (1, 100, 100, 8, 8, 64, True, 48, 32, 32),
    (2, 1, 96, 4, 2, 32, False, None, 8, 32),     # decode-like
    (1, 80, 80, 6, 3, 16, True, 16, 16, 16),      # GQA group 2 + window
    (1, 33, 65, 2, 1, 8, False, None, 16, 16),    # ragged padding
]


@pytest.mark.parametrize("case", FLASH_CASES)
def test_flash_attention_sweep(case):
    b, sq, sk, h, kh, d, causal, window, bq, bk = case
    ks = jax.random.split(jax.random.key(sum(case[:6])), 3)
    q = jax.random.normal(ks[0], (b, sq, h, d), jnp.float32)
    k = jax.random.normal(ks[1], (b, sk, kh, d), jnp.float32)
    v = jax.random.normal(ks[2], (b, sk, kh, d), jnp.float32)
    out = flash_attention(q, k, v, causal=causal, window=window, bq=bq, bk=bk)
    ref = flash_attention_ref(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_flash_attention_dtypes(dtype):
    q = jax.random.normal(jax.random.key(0), (1, 64, 4, 32), dtype)
    k = jax.random.normal(jax.random.key(1), (1, 64, 2, 32), dtype)
    v = jax.random.normal(jax.random.key(2), (1, 64, 2, 32), dtype)
    out = flash_attention(q, k, v, bq=32, bk=32)
    ref = flash_attention_ref(q, k, v)
    assert out.dtype == dtype
    tol = 3e-2 if dtype == jnp.bfloat16 else 3e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=tol, atol=tol)


def test_fused_server_update_equals_optimizer():
    """ops.fused_server_update == core adam_ota.update on a pytree."""
    from repro.core.adaptive import AdaptiveConfig, adam_ota
    from repro.kernels.ops import fused_server_update
    params = {"a": jnp.ones((130,)), "b": {"c": jnp.ones((5, 60), jnp.bfloat16)}}
    g = jax.tree.map(lambda p: 0.1 * jnp.ones_like(p), params)
    cfg = AdaptiveConfig(optimizer="adam_ota", lr=0.01, beta2=0.3, alpha=1.5)
    opt = adam_ota(cfg)
    st0 = opt.init(params)
    ref_p, ref_s = opt.update(g, st0, params)
    k_p, k_s = fused_server_update(g, st0, params, lr=0.01, beta1=0.9,
                                   beta2=0.3, alpha=1.5, eps=1e-8, mode="adam")
    for a, b in zip(jax.tree.leaves(ref_p), jax.tree.leaves(k_p)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(
        np.asarray(jax.tree.leaves(ref_s.nu)[0]),
        np.asarray(jax.tree.leaves(k_s.nu)[0]), rtol=1e-5)


@pytest.mark.parametrize("name,mode", [("adagrad_ota", "adagrad"),
                                       ("amsgrad_ota", "amsgrad"),
                                       ("yogi_ota", "yogi"),
                                       ("fedavgm", "momentum"),
                                       ("fedavg", "sgd")])
def test_fused_server_update_all_modes(name, mode):
    """ops.fused_server_update handles every mode the kernel advertises
    (regression: it used to crash on amsgrad/momentum/sgd state)."""
    from repro.core.adaptive import AdaptiveConfig, make_server_optimizer
    from repro.kernels.ops import fused_server_update
    params = {"a": jnp.ones((130,)), "b": {"c": jnp.full((5, 60), 0.5)}}
    g = jax.tree.map(lambda p: 0.1 * jnp.ones_like(p), params)
    cfg = AdaptiveConfig(optimizer=name, lr=0.01, beta2=0.3, alpha=1.5)
    opt = make_server_optimizer(cfg)
    st0 = opt.init(params)
    ref_p, ref_s = opt.update(g, st0, params)
    beta1 = cfg.momentum if mode == "momentum" else cfg.beta1
    k_p, k_s = fused_server_update(g, st0, params, lr=0.01, beta1=beta1,
                                   beta2=0.3, alpha=1.5, eps=1e-8, mode=mode)
    for a, b in zip(jax.tree.leaves(ref_p), jax.tree.leaves(k_p)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-5)
    for a, b in zip(jax.tree.leaves(ref_s.nu), jax.tree.leaves(k_s.nu)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-5)
    with pytest.raises(ValueError):
        fused_server_update(g, st0, params, lr=0.01, beta1=0.9, beta2=0.3,
                            alpha=1.5, eps=1e-8, mode="rmsprop")


# ---------------------------------------------------------------------------
# Interpret-mode grid coarsening (PR 8)
# ---------------------------------------------------------------------------

def test_coarse_block_policy():
    """coarse_block only ever grows the tile under interpret, in whole
    multiples of the requested block, capped, and never past the padded
    axis."""
    from repro.kernels.interpret import INTERPRET_BLOCK_CAP, coarse_block
    # compiled mode: untouched, whatever the size
    assert coarse_block(1 << 20, 256, False) == 256
    # already a single tile: untouched
    assert coarse_block(100, 256, True) == 256
    # grows to the whole padded axis...
    assert coarse_block(1000, 256, True) == 1024
    # ...capped (in multiples of block), for huge axes
    big = coarse_block(1 << 22, 256, True)
    assert big == (INTERPRET_BLOCK_CAP // 256) * 256
    assert big % 256 == 0
    # a custom cap below the axis still yields a block multiple
    assert coarse_block(10_000, 256, True, cap=1000) == 768


def test_coarse_block_bitwise_invariant(monkeypatch):
    """The coarsened interpret launch is BITWISE identical to the
    fixed-tile launch on the channel output — per-column math and the
    per-128-block scales are invariant to the d-axis tiling (the
    assertion coarse_block's docstring promises). The pilot-stats
    scalars reduce ACROSS tiles, so their accumulation order follows
    the grid: those are held to ~1 ULP instead."""
    import repro.kernels.ota_channel as oc

    n, d = 4, 1000   # 4 x 2-row tiles when fixed, 1 tile when coarsened
    ks = jax.random.split(jax.random.key(42), 4)
    g = jax.random.normal(ks[0], (n, d))
    h = jnp.abs(jax.random.normal(ks[1], (n,))) + 0.1
    u = jax.random.uniform(ks[2], (d,), minval=-1.5, maxval=1.5)
    e = jnp.abs(jax.random.normal(ks[3], (d,))) + 0.01
    monkeypatch.setattr(oc, "mac_block_rows", lambda rows, tiles: 2)

    def launch():
        return oc.ota_channel_slab(g, h, u, e, alpha=1.5, scale=0.1,
                                   pilot_stats=True, interpret=True)

    coarse_out, coarse_stats = launch()
    orig = oc.coarse_block
    oc.coarse_block = lambda n_, b, i, cap=None: b   # fixed-tile baseline
    try:
        fixed_out, fixed_stats = launch()
    finally:
        oc.coarse_block = orig
    np.testing.assert_array_equal(np.asarray(coarse_out),
                                  np.asarray(fixed_out))
    for a, b in zip(coarse_stats, fixed_stats):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-6, atol=0)
