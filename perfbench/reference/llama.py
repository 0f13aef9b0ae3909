"""A Llama-architecture decoder's pooled embedding, written from the
published description (RMSNorm, rotary positions on the halves of each
head, grouped-query causal attention, SiLU-gated MLP), in float32 at
matmul precision "highest", one layer at a time.

The pooled embedding is the mean of the last block's hidden states over
the tokens > 0. ``precision="fp8"`` rounds every projection's operands to
float8 e4m3 with one absmax scale per tensor: the control, one step below
the bfloat16 the configuration states.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

F8_MAX = 448.0


def _fp8(x):
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / F8_MAX
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _rope(x, theta):
    s, hd = x.shape[1], x.shape[-1]
    half = hd // 2
    freqs = 1.0 / theta ** (jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


@partial(jax.jit, static_argnames=("eps", "theta", "fp8"))
def _pooled(params, tokens, eps, theta, fp8):
    q8 = _fp8 if fp8 else (lambda a: a)

    def mm(spec, a, w):
        return jnp.einsum(spec, q8(a), q8(w.astype(jnp.float32)),
                          precision="highest")

    x = params["embed"]["table"][tokens].astype(jnp.float32)
    blocks = params["blocks"]["p0"]
    s = tokens.shape[1]
    causal = jnp.tril(jnp.ones((s, s), bool))

    def layer(x, p):
        h = _rms(x, p["norm1"]["scale"].astype(jnp.float32), eps)
        a = p["attn"]
        q = _rope(mm("bsd,dhk->bshk", h, a["wq"]), theta)
        k = _rope(mm("bsd,dhk->bshk", h, a["wk"]), theta)
        v = mm("bsd,dhk->bshk", h, a["wv"])
        g = q.shape[2] // k.shape[2]
        k, v = jnp.repeat(k, g, axis=2), jnp.repeat(v, g, axis=2)
        att = jnp.einsum("bqhk,bshk->bhqs", q, k,
                         precision="highest") / np.sqrt(q.shape[-1])
        att = jax.nn.softmax(jnp.where(causal, att, -jnp.inf), axis=-1)
        o = jnp.einsum("bhqs,bshk->bqhk", att, v, precision="highest")
        x = x + mm("bqhk,hkd->bqd", o, a["wo"])
        h = _rms(x, p["norm2"]["scale"].astype(jnp.float32), eps)
        m = p["mlp"]
        u = mm("bsd,df->bsf", h, m["wi"]) * jax.nn.silu(
            mm("bsd,df->bsf", h, m["wg"]))
        return x + mm("bsf,fd->bsd", u, m["wo"]), None

    x, _ = jax.lax.scan(layer, x, blocks)
    mask = (tokens > 0).astype(jnp.float32)[..., None]
    return jnp.sum(x * mask, axis=1) / jnp.maximum(jnp.sum(mask, axis=1), 1.0)


def pooled(params, tokens: np.ndarray, cfg: dict, precision: str = "float32",
           block: int = 8) -> np.ndarray:
    """(n, d) pooled embeddings of ``tokens`` (n, s), ``block`` rows at a
    time."""
    fp8 = {"float32": False, "fp8": True}[precision]
    out = []
    for i in range(0, len(tokens), block):
        t = np.asarray(tokens[i:i + block], np.int32)
        pad = block - len(t)
        if pad:
            t = np.concatenate([t, np.zeros((pad, t.shape[1]), np.int32)])
        y = _pooled(params, jnp.asarray(t), float(cfg["rms_norm_eps"]),
                    float(cfg["rope_theta"]), fp8)
        out.append(np.asarray(y)[:block - pad])
    return np.concatenate(out)
