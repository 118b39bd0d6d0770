"""Plain float32 reference of the federated over-the-air round.

Per round t with key k_t (Algorithm 1 of arXiv:2403.06528, one local
gradient per client):

    k_h, k_x = split(k_t)
    h_n      ~ Rayleigh with mean mu_c              (uniform(k_h, (N,)))
    xi       ~ S(alpha, 0, xi_scale) per coordinate  (CMS, per leaf i from
                                                      fold_in(k_x, i))
    g_t      = (1/N) sum_n h_n grad_n(w_t) + xi
    Delta_t  = beta1 Delta_{t-1} + (1 - beta1) g_t
    v_t      = beta2 v_{t-1} + (1 - beta2) |Delta_t|^alpha
    w_{t+1}  = w_t - lr Delta_t / (v_t + eps)^(1/alpha)

Clients read the weights in their storage dtype (bfloat16 matrices), and
the update starts from that rounded copy, as the configuration states.
Everything runs leaf by leaf, client by client, in float32. Given several
devices, client n computes on device n mod D, each device sums its own
clients, and the first device adds the D partial sums.
"""

from __future__ import annotations

import functools
import math
from typing import Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from bench import mix as mixes
from bench.reference.common import F32, Precision, flatten_paths

CMS_U_BOUND = math.pi / 2 - 1e-6
CMS_E_FLOOR = 1e-7


def rayleigh(key: jax.Array, n: int, mean: float) -> jax.Array:
    s = mean / math.sqrt(math.pi / 2.0)
    u = jax.random.uniform(key, (n,), jnp.float32,
                           minval=jnp.finfo(jnp.float32).tiny)
    return s * jnp.sqrt(-2.0 * jnp.log(u))


def alpha_stable(key: jax.Array, shape, alpha: float) -> jax.Array:
    """Chambers-Mallows-Stuck draw of a standard symmetric alpha-stable
    variable, angles kept inside (-pi/2, pi/2) and Exp(1) floored."""
    ku, kw = jax.random.split(key)
    u = jax.random.uniform(ku, shape, jnp.float32,
                           minval=-CMS_U_BOUND, maxval=CMS_U_BOUND)
    e = -jnp.log(jax.random.uniform(kw, shape, jnp.float32,
                                    minval=jnp.finfo(jnp.float32).tiny))
    e = jnp.maximum(e, CMS_E_FLOOR)
    a = alpha
    return (jnp.sin(a * u) / jnp.cos(u) ** (1.0 / a)
            * (jnp.cos((1.0 - a) * u) / e) ** ((1.0 - a) / a))


def _norms(leaves) -> np.ndarray:
    return np.array([float(jnp.sqrt(jnp.sum(jnp.square(x)))) for x in leaves])


def reference_rounds(loss: Callable, params0: dict, batches: Sequence,
                     keys: Sequence, mix: dict,
                     pr: Precision = F32, drop_half: bool = False,
                     devices: Sequence = ()) -> dict:
    """Run ``len(batches)`` rounds; return per-round loss and clean
    aggregate norm, per-leaf norms of the first round's g_t and of the
    reference's clean first gradient, and per-leaf norms of w - w_0.

    ``loss(params, tokens, pr)`` is one client's loss. ``batches[t]`` is
    round t's (N, B, S) int32 array. ``drop_half`` is a planted fault:
    every client computes on the first half of its rows only.
    ``devices`` spreads the clients over several devices (default: jax's
    first device alone).
    """
    ch, sv = mixes.check(mix)["channel"], mix["server"]
    alpha, scale = float(ch["alpha"]), float(ch["xi_scale"])
    mu_c = float(ch.get("mu_c", 1.0))
    lr, b1 = float(sv["lr"]), float(sv.get("beta1", 0.9))
    b2, eps = float(sv["beta2"]), float(sv.get("eps", 1e-8))
    a_opt = float(sv.get("alpha", alpha))

    paths = flatten_paths(params0)
    dtypes = [leaf.dtype for _, leaf in paths]
    w0 = [leaf.astype(jnp.float32) for _, leaf in paths]
    w = list(w0)
    delta = [jnp.zeros_like(x) for x in w]
    nu = [jnp.zeros_like(x) for x in w]
    treedef = jax.tree.structure(params0)
    devs = list(devices) or [jax.devices()[0]]
    spread = len(devs) > 1

    with jax.default_matmul_precision("highest"):
        vg = jax.jit(jax.value_and_grad(
            lambda ws, tok: loss(jax.tree.unflatten(treedef, ws), tok, pr)))

        @functools.partial(jax.jit, donate_argnums=(0,))
        def fold(acc, g, c):
            return [pr.slab(a + c * gi) for a, gi in zip(acc, g)]

        @jax.jit
        def noisy(acc, kx):
            return [pr.slab(a + scale * pr.slab(alpha_stable(
                jax.random.fold_in(kx, i), a.shape, alpha)))
                for i, a in enumerate(acc)]

        @functools.partial(jax.jit, donate_argnums=(1, 2))
        def update(w, delta, nu, g):
            out = []
            for wi, di, ni, gi, dt in zip(w, delta, nu, g, dtypes):
                w_in = wi.astype(dt).astype(jnp.float32)
                di = pr.slab(b1 * di + (1.0 - b1) * gi)
                ad = jnp.abs(di)
                ni = pr.slab(b2 * ni + (1.0 - b2)
                             * jnp.where(ad == 0, 0.0, ad ** a_opt))
                wi = pr.slab(w_in - lr * di / (ni + eps) ** (1.0 / a_opt))
                out.append((wi, di, ni))
            return [o[0] for o in out], [o[1] for o in out], [o[2] for o in out]

        @jax.jit
        def merge(parts):
            return [sum(p[i] for p in parts) for i in range(len(parts[0]))]

        def on_first(parts):
            """The devices' partial sums added on the first device."""
            if not spread:
                return parts[0]
            return merge([jax.device_put(p, devs[0]) for p in parts])

        out = {"loss": [], "clean_norm": []}
        for t, (tok_all, key) in enumerate(zip(batches, keys)):
            n = tok_all.shape[0]
            kh, kx = jax.random.split(key)
            h = np.asarray(rayleigh(kh, n, mu_c))
            client_w = [x.astype(dt).astype(jnp.float32)
                        for x, dt in zip(w, dtypes)]
            on = ([jax.device_put(client_w, d) for d in devs] if spread
                  else [client_w])
            del client_w
            acc = [[jnp.zeros_like(x) for x in cw] for cw in on]
            clean = [[jnp.zeros_like(x) for x in cw] for cw in on]
            losses = []
            for c in range(n):
                k = c % len(devs)
                if c >= len(devs):
                    # one client in flight a device: its gradient and
                    # activations are freed before the next is started
                    jax.block_until_ready(clean[k])
                tok = (jax.device_put(tok_all[c], devs[k]) if spread
                       else jnp.asarray(tok_all[c]))
                if drop_half:
                    tok = tok[:max(1, tok.shape[0] // 2)]
                lc, g = vg(on[k], tok)
                losses.append(lc)
                acc[k] = fold(acc[k], g, float(h[c]) / n)
                clean[k] = fold(clean[k], g, 1.0 / n)
                del g
            del on
            loss_sum = 0.0
            for lc in losses:
                loss_sum += float(lc)
            acc, clean = on_first(acc), on_first(clean)
            g_t = noisy(acc, kx)
            del acc
            out["loss"].append(loss_sum / n)
            out["clean_norm"].append(float(np.sqrt(sum(
                float(jnp.sum(jnp.square(x))) for x in clean))))
            if t == 0:
                out["g1_leaf"] = _norms(g_t)
                out["clean1_leaf"] = _norms(clean)
            del clean
            w, delta, nu = update(w, delta, nu, g_t)
            del g_t
        out["dw_leaf"] = _norms([a - b for a, b in zip(w, w0)])
    return out
