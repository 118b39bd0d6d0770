"""Plain float32 reference of a decoder with Multi-head Latent Attention.

MiniCPM3 / DeepSeek-V2 attention (hf:openbmb/MiniCPM3-4B): queries come
through a low-rank latent (``wq_a``, RMS-normed, ``wq_b``); keys and
values through a compressed latent ``c_kv`` (``wkv_a``, RMS-normed,
``wkv_b``) plus one rotary key part shared by all heads. Each head
scores ``q_nope . k_nope + q_pe . k_pe`` over sqrt(nope + rope), causal
softmax, values of ``v_head_dim``, then ``wo``; a SwiGLU MLP follows.
Written out with the decompressed keys and values (no absorption).

Departure from the published model, kept because the program has it
too: no ``scale_emb`` on the embedding and no ``scale_depth`` on the
residual branches (MiniCPM3's muP scalings).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from bench.reference.common import (Precision, causal_softmax_attend,
                                    layer, next_token_xent, rmsnorm, rope,
                                    swiglu)

BF16, F32 = "bfloat16", "float32"


def layout(cfg: dict) -> dict:
    d, L, v, f = cfg["d_model"], cfg["n_layers"], cfg["vocab"], cfg["d_ff"]
    h, ql, kl = cfg["n_heads"], cfg["q_lora_rank"], cfg["kv_lora_rank"]
    nope, rp, vd = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                    cfg["v_head_dim"])

    def k(*shape, dt=BF16):
        return ((L, *shape), dt)

    blocks = {
        "ln1": {"scale": k(d, dt=F32)},
        "ln2": {"scale": k(d, dt=F32)},
        "attn": {"wq_a": {"kernel": k(d, ql)},
                 "q_norm": {"scale": k(ql, dt=F32)},
                 "wq_b": {"kernel": k(ql, h, nope + rp)},
                 "wkv_a": {"kernel": k(d, kl + rp)},
                 "kv_norm": {"scale": k(kl, dt=F32)},
                 "wkv_b": {"kernel": k(kl, h, nope + vd)},
                 "wo": {"kernel": k(h * vd, d)}},
        "mlp": {"gate": {"kernel": k(d, f)}, "up": {"kernel": k(d, f)},
                "down": {"kernel": k(f, d)}},
    }
    return {"embed": {"table": ((v, d), BF16)}, "blocks": blocks,
            "final_norm": {"scale": ((d,), F32)},
            "unembed": {"kernel": ((d, v), BF16)}}


def _attention(p, cfg, x, pr: Precision):
    nope, rp = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    kl, theta = cfg["kv_lora_rank"], cfg["rope_theta"]
    pos = jnp.arange(x.shape[1])
    q_lat = rmsnorm(pr.mm("bsd,dq->bsq", x, p["wq_a"]["kernel"]),
                    p["q_norm"]["scale"])
    q = pr.mm("bsq,qhk->bshk", q_lat, p["wq_b"]["kernel"])
    q = jnp.concatenate([q[..., :nope], rope(q[..., nope:], pos, theta)], -1)
    kv_lat = pr.mm("bsd,dk->bsk", x, p["wkv_a"]["kernel"])
    c_kv = rmsnorm(kv_lat[..., :kl], p["kv_norm"]["scale"])
    k_pe = rope(kv_lat[..., None, kl:], pos, theta)            # (B,S,1,rope)
    kv = pr.mm("bsc,chk->bshk", c_kv, p["wkv_b"]["kernel"])
    k_pe = jnp.broadcast_to(k_pe, kv.shape[:3] + (rp,))
    k = jnp.concatenate([kv[..., :nope], k_pe], -1)
    allowed = pos[:, None] >= pos[None, :]
    o = causal_softmax_attend(q, k, kv[..., nope:], allowed, pr)
    return pr.mm("bsf,fd->bsd", o.reshape(*o.shape[:2], -1),
                 p["wo"]["kernel"])


def _block(p, cfg, x, pr: Precision):
    x = x + _attention(p["attn"], cfg, rmsnorm(x, p["ln1"]["scale"]), pr)
    return x + swiglu(p["mlp"], rmsnorm(x, p["ln2"]["scale"]), pr)


def loss(cfg: dict, params: dict, tokens: jax.Array,
         pr: Precision) -> jax.Array:
    x = params["embed"]["table"][tokens]
    block = jax.checkpoint(lambda lp, x: _block(lp, cfg, x, pr))
    for i in range(cfg["n_layers"]):
        x = block(layer(params["blocks"], i), x)
    x = rmsnorm(x, params["final_norm"]["scale"])
    logits = pr.mm("bsd,dv->bsv", x, params["unembed"]["kernel"])
    return next_token_xent(logits, tokens)


def dims(cfg: dict, seq: int) -> dict:
    """Shapes the FLOP count needs (bench/counts.py ``client_flops``)."""
    return {"positions": seq, "layers": cfg["n_layers"],
            "heads": cfg["n_heads"],
            "qk": cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"],
            "v": cfg["v_head_dim"], "window": None}
