"""Plain float32 reference of a Hymba hybrid-head decoder.

Hymba (arXiv:2411.13676): learnable meta tokens are prepended to the
sequence; every layer feeds the same pre-normed input to an attention
branch (GQA, rotary, sliding window) and a selective state-space (Mamba)
branch in parallel, normalises each branch's output, averages the two,
and adds a SwiGLU MLP. The SSM recurrence is written here as the plain
sequential scan over time,

    h_t = exp(dt_t * A) h_{t-1} + dt_t * B_t * x_t,   y_t = C_t . h_t + D x_t,

where the program uses a parallel prefix scan. Parameter names follow
the program's checkpoint layout so that both read one set of weights.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from bench.reference.common import (Precision, causal_softmax_attend,
                                    layer, next_token_xent, rmsnorm, rope,
                                    swiglu)

BF16, F32 = "bfloat16", "float32"
D_CONV = 4


def sizes(cfg: dict) -> dict:
    d = cfg["d_model"]
    return {"hd": d // cfg["n_heads"], "inner": cfg["ssm_expand"] * d,
            "rank": -(-d // 16), "state": cfg["ssm_state"]}


def layout(cfg: dict) -> dict:
    """Parameter shapes and storage dtypes, layers stacked on axis 0."""
    d, L, v, f = cfg["d_model"], cfg["n_layers"], cfg["vocab"], cfg["d_ff"]
    h, kv = cfg["n_heads"], cfg["n_kv_heads"]
    s = sizes(cfg)
    c, r, n = s["inner"], s["rank"], s["state"]

    def k(*shape, dt=BF16):
        return ((L, *shape), dt)

    blocks = {
        "ln1": {"scale": k(d, dt=F32)},
        "ln2": {"scale": k(d, dt=F32)},
        "attn_out_norm": {"scale": k(d, dt=F32)},
        "ssm_out_norm": {"scale": k(d, dt=F32)},
        "attn": {"wq": {"kernel": k(d, h, s["hd"])},
                 "wk": {"kernel": k(d, kv, s["hd"])},
                 "wv": {"kernel": k(d, kv, s["hd"])},
                 "wo": {"kernel": k(h * s["hd"], d)}},
        "ssm": {"in_proj": {"kernel": k(d, 2 * c)},
                "conv": k(D_CONV, c), "conv_bias": k(c),
                "x_proj": {"kernel": k(c, r + 2 * n)},
                "dt_proj": {"kernel": k(r, c), "bias": k(c)},
                "dt_bias": k(c, dt=F32), "a_log": k(c, n, dt=F32),
                "d_skip": k(c, dt=F32),
                "out_proj": {"kernel": k(c, d)}},
        "mlp": {"gate": {"kernel": k(d, f)}, "up": {"kernel": k(d, f)},
                "down": {"kernel": k(f, d)}},
    }
    return {"embed": {"table": ((v, d), BF16)}, "blocks": blocks,
            "final_norm": {"scale": ((d,), F32)},
            "unembed": {"kernel": ((d, v), BF16)},
            "meta_tokens": ((cfg["n_meta_tokens"], d), BF16)}


def _attention(p, cfg, x, pr: Precision):
    s_len = x.shape[1]
    pos = jnp.arange(s_len)
    q = rope(pr.mm("bsd,dhk->bshk", x, p["wq"]["kernel"]), pos,
             cfg["rope_theta"])
    k = rope(pr.mm("bsd,dhk->bshk", x, p["wk"]["kernel"]), pos,
             cfg["rope_theta"])
    v = pr.mm("bsd,dhk->bshk", x, p["wv"]["kernel"])
    group = cfg["n_heads"] // cfg["n_kv_heads"]
    k, v = jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)
    dist = pos[:, None] - pos[None, :]
    allowed = (dist >= 0) & (dist < cfg["window"])
    o = causal_softmax_attend(q, k, v, allowed, pr)
    return pr.mm("bsf,fd->bsd", o.reshape(*o.shape[:2], -1),
                 p["wo"]["kernel"])


def _ssm(p, cfg, x, pr: Precision):
    s = sizes(cfg)
    r, n = s["rank"], s["state"]
    xi, z = jnp.split(pr.mm("bsd,dc->bsc", x, p["in_proj"]["kernel"]), 2, -1)
    w = p["conv"]
    xp = jnp.pad(xi, ((0, 0), (D_CONV - 1, 0), (0, 0)))
    conv = sum(xp[:, i:i + xi.shape[1]] * w[i] for i in range(D_CONV))
    xc = jax.nn.silu(conv + p["conv_bias"])
    proj = pr.mm("bsc,ck->bsk", xc, p["x_proj"]["kernel"])
    dt = jax.nn.softplus(pr.mm("bsr,rc->bsc", proj[..., :r],
                               p["dt_proj"]["kernel"])
                         + p["dt_proj"]["bias"] + p["dt_bias"])
    b_t, c_t = proj[..., r:r + n], proj[..., r + n:]
    a = -jnp.exp(p["a_log"])                                   # (C, N)

    def step(h, inp):
        dt_t, x_t, b, c = inp                                  # (B,C),(B,C),(B,N),(B,N)
        h = jnp.exp(dt_t[..., None] * a) * h + (dt_t * x_t)[..., None] * b[:, None, :]
        return h, jnp.einsum("bcn,bn->bc", h, c,
                             precision=jax.lax.Precision.HIGHEST)

    h0 = jnp.zeros((x.shape[0], s["inner"], n), jnp.float32)
    seq = [jnp.swapaxes(t, 0, 1) for t in (dt, xc, b_t, c_t)]
    _, y = jax.lax.scan(step, h0, seq)
    y = jnp.swapaxes(y, 0, 1) + p["d_skip"] * xc
    return pr.mm("bsc,cd->bsd", y * jax.nn.silu(z), p["out_proj"]["kernel"])


def _block(p, cfg, x, pr: Precision):
    h = rmsnorm(x, p["ln1"]["scale"])
    ya = rmsnorm(_attention(p["attn"], cfg, h, pr), p["attn_out_norm"]["scale"])
    ys = rmsnorm(_ssm(p["ssm"], cfg, h, pr), p["ssm_out_norm"]["scale"])
    x = x + 0.5 * (ya + ys)
    return x + swiglu(p["mlp"], rmsnorm(x, p["ln2"]["scale"]), pr)


def loss(cfg: dict, params: dict, tokens: jax.Array,
         pr: Precision) -> jax.Array:
    """Mean next-token cross-entropy of one client's (B, S) tokens;
    ``params`` are float32 leaves."""
    x = params["embed"]["table"][tokens]
    meta = jnp.broadcast_to(params["meta_tokens"][None],
                            (x.shape[0],) + params["meta_tokens"].shape)
    x = jnp.concatenate([meta, x], axis=1)
    block = jax.checkpoint(lambda lp, x: _block(lp, cfg, x, pr))
    for i in range(cfg["n_layers"]):
        x = block(layer(params["blocks"], i), x)
    x = rmsnorm(x, params["final_norm"]["scale"])[:, cfg["n_meta_tokens"]:]
    logits = pr.mm("bsd,dv->bsv", x, params["unembed"]["kernel"])
    return next_token_xent(logits, tokens)


def dims(cfg: dict, seq: int) -> dict:
    """Shapes the FLOP count needs (bench/counts.py ``client_flops``)."""
    hd = sizes(cfg)["hd"]
    return {"positions": seq + cfg["n_meta_tokens"], "layers": cfg["n_layers"],
            "heads": cfg["n_heads"], "qk": hd, "v": hd,
            "window": cfg["window"]}
