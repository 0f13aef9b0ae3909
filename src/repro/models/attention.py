"""GQA attention: full & sliding-window, train/prefill & cached decode.

Three execution strategies:
  * ``einsum``  — reference quadratic attention (smoke tests, small seqs).
  * ``blocked`` — pure-XLA online-softmax over KV blocks (flash-equivalent
    FLOPs, O(block^2) memory). ``build_model``'s default on every
    backend, TPU included.
  * ``flash``   — the same online softmax with a recompute-in-backward
    custom VJP (pure jnp; launch/steps.py picks it for training).
  The Pallas flash kernel (repro.kernels.flash_attention) is not wired
  into any of them.

Decode uses a KV cache: full caches for global layers, ring buffers for
sliding-window layers.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.config.base import ModelConfig
from repro.models.common import Params, apply_rope, dense_init

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# params
# ---------------------------------------------------------------------------

def attn_init(key, cfg: ModelConfig, dtype) -> Params:
    d, hd = cfg.d_model, cfg.head_dim
    nq, nkv = cfg.num_heads, cfg.num_kv_heads
    kq, kk, kv, ko = jax.random.split(key, 4)
    return {
        "wq": dense_init(kq, d, (nq, hd), dtype),
        "wk": dense_init(kk, d, (nkv, hd), dtype),
        "wv": dense_init(kv, d, (nkv, hd), dtype),
        "wo": dense_init(ko, nq * hd, (d,), dtype).reshape(nq, hd, d),
    }


def attn_axes(cfg: ModelConfig) -> Params:
    return {
        "wq": ("embed", "heads", "head_dim"),
        "wk": ("embed", "kv_heads", "head_dim"),
        "wv": ("embed", "kv_heads", "head_dim"),
        "wo": ("heads", "head_dim", "embed"),
    }


def cross_attn_init(key, cfg: ModelConfig, dtype) -> Params:
    return attn_init(key, cfg, dtype)


# ---------------------------------------------------------------------------
# core attention math
# ---------------------------------------------------------------------------

def _expand_kv(k: jnp.ndarray, groups: int) -> jnp.ndarray:
    """(b, s, kv, hd) -> (b, s, kv*groups, hd) by repeat (GQA share)."""
    if groups == 1:
        return k
    b, s, kv, hd = k.shape
    k = jnp.broadcast_to(k[:, :, :, None, :], (b, s, kv, groups, hd))
    return k.reshape(b, s, kv * groups, hd)


def attention_einsum(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                     mask: Optional[jnp.ndarray], scale: float) -> jnp.ndarray:
    """q: (b, sq, h, hd); k,v: (b, skv, h, hd); mask: (sq, skv) or None."""
    scores = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    if mask is not None:
        scores = jnp.where(mask[None, None, :, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs.astype(v.dtype), v)
    return out


def attention_blocked(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                      scale: float, *, causal: bool, window: int = 0,
                      q_offset: int = 0, q_block: int = 512,
                      kv_block: int = 512,
                      causal_skip: bool = False) -> jnp.ndarray:
    """Online-softmax blocked attention (flash-equivalent, pure XLA).

    q: (b, sq, h, hd); k,v: (b, skv, h, hd). ``q_offset`` is the absolute
    position of q[0] relative to k[0] (for chunked prefill).
    ``causal_skip``: unroll the q-block loop in Python with *static*
    triangular KV extents, so masked-out blocks are never computed
    (≈2x FLOP cut on causal prefill; larger HLO). Perf-iteration knob.
    """
    b, sq, h, hd = q.shape
    skv = k.shape[1]
    q_block = min(q_block, sq)
    kv_block = min(kv_block, skv)
    # pad to multiples
    pq = (-sq) % q_block
    pk = (-skv) % kv_block
    if pq:
        q = jnp.pad(q, ((0, 0), (0, pq), (0, 0), (0, 0)))
    if pk:
        k = jnp.pad(k, ((0, 0), (0, pk), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pk), (0, 0), (0, 0)))
    nq, nk = (sq + pq) // q_block, (skv + pk) // kv_block

    q = q.reshape(b, nq, q_block, h, hd)
    k = k.reshape(b, nk, kv_block, h, hd)
    v = v.reshape(b, nk, kv_block, h, hd)

    q_pos_base = jnp.arange(q_block)
    k_pos_base = jnp.arange(kv_block)

    def kv_step(carry, kv_idx_and_blocks, qi):
        m, l, acc, qblk = carry
        kv_idx, kblk, vblk = kv_idx_and_blocks
        s = jnp.einsum("bqhd,bkhd->bhqk", qblk.astype(jnp.float32),
                       kblk.astype(jnp.float32)) * scale
        qpos = q_offset + qi * q_block + q_pos_base            # (q_block,)
        kpos = kv_idx * kv_block + k_pos_base                  # (kv_block,)
        valid = kpos[None, :] < skv
        if causal:
            valid = valid & (kpos[None, :] <= qpos[:, None])
        if window > 0:
            valid = valid & (kpos[None, :] > qpos[:, None] - window)
        s = jnp.where(valid[None, None], s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new[..., None])
        l_new = l * alpha + jnp.sum(p, axis=-1)
        acc_new = acc * alpha[..., None] + jnp.einsum(
            "bhqk,bkhd->bhqd", p, vblk.astype(jnp.float32))
        return (m_new, l_new, acc_new, qblk), None

    def q_block_fn(qi, qblk, nk_for_q):
        m0 = jnp.full((b, h, q_block), NEG_INF, jnp.float32)
        l0 = jnp.zeros((b, h, q_block), jnp.float32)
        a0 = jnp.zeros((b, h, q_block, hd), jnp.float32)
        kv_idx = jnp.arange(nk_for_q)
        (m, l, acc, _), _ = jax.lax.scan(
            functools.partial(kv_step, qi=qi), (m0, l0, a0, qblk),
            (kv_idx, k[:, :nk_for_q].swapaxes(0, 1), v[:, :nk_for_q].swapaxes(0, 1)))
        out = acc / jnp.maximum(l[..., None], 1e-30)
        return out  # (b, h, q_block, hd)

    if causal_skip and causal and window == 0 and q_offset == 0:
        # static triangular extents, python-unrolled over q blocks
        outs = []
        for qi in range(nq):
            nk_for_q = min(nk, (qi + 1) * q_block // kv_block
                           + (1 if ((qi + 1) * q_block) % kv_block else 0))
            nk_for_q = max(1, min(nk, ((qi + 1) * q_block + kv_block - 1) // kv_block))
            outs.append(q_block_fn(qi, q[:, qi], nk_for_q))
        out = jnp.stack(outs, axis=1)  # (b, nq, h, q_block, hd)
        out = out.transpose(0, 1, 3, 2, 4).reshape(b, nq * q_block, h, hd)
    else:
        def scan_q(_, qi_and_blk):
            qi, qblk = qi_and_blk
            return None, q_block_fn(qi, qblk, nk)
        _, out = jax.lax.scan(scan_q, None,
                              (jnp.arange(nq), q.swapaxes(0, 1)))
        # out: (nq, b, h, q_block, hd)
        out = out.transpose(1, 0, 3, 2, 4).reshape(b, nq * q_block, h, hd)
    return out[:, :sq].astype(v.dtype)


# ---------------------------------------------------------------------------
# flash attention with custom VJP (recompute-in-backward)
# ---------------------------------------------------------------------------
#
# jax.lax.scan saves per-iteration residuals for autodiff, so differentiating
# attention_blocked would materialize every (q_block, kv_block) score tile —
# gigabytes per layer. The custom VJP below saves only (out, lse) and
# recomputes tiles in the backward pass (FlashAttention semantics); it is
# also the pure-jnp oracle for the Pallas kernel in
# repro/kernels/flash_attention.

def _flash_fwd(q, k, v, scale, causal, window, q_offset, q_block, kv_block,
               causal_skip=False):
    """Forward with online softmax; GQA-aware: q (b, sq, h, hd) vs
    k, v (b, skv, kv, hd) with g = h // kv query groups per KV head.
    ``causal_skip``: python-unroll the q-block loop with static triangular
    KV extents so masked-out tiles are never computed (~2x FLOP cut on
    causal prefill). Returns (out (b, sq, h, hd) f32, lse (b, h, sq))."""
    b, sq, h, hd = q.shape
    skv, kv = k.shape[1], k.shape[2]
    g = h // kv
    q_block = min(q_block, sq)
    kv_block = min(kv_block, skv)
    pq = (-sq) % q_block
    pk = (-skv) % kv_block
    if pq:
        q = jnp.pad(q, ((0, 0), (0, pq), (0, 0), (0, 0)))
    if pk:
        k = jnp.pad(k, ((0, 0), (0, pk), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pk), (0, 0), (0, 0)))
    nq, nk = (sq + pq) // q_block, (skv + pk) // kv_block
    qb = q.reshape(b, nq, q_block, kv, g, hd)
    kb = k.reshape(b, nk, kv_block, kv, hd)
    vb = v.reshape(b, nk, kv_block, kv, hd)

    def q_iter_fn(qi, qblk, nk_use):
        def kv_iter(carry, kv_in):
            m, l, acc = carry
            kv_idx, kblk, vblk = kv_in
            s = jnp.einsum("bqkgd,bjkd->bkgqj", qblk.astype(jnp.float32),
                           kblk.astype(jnp.float32)) * scale
            valid = _tile_mask(qi, kv_idx, q_block, kv_block, q_offset,
                               skv, causal, window)
            s = jnp.where(valid[None, None, None], s, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            alpha = jnp.exp(m - m_new)
            p = jnp.exp(s - m_new[..., None])
            l_new = l * alpha + jnp.sum(p, axis=-1)
            acc_new = acc * alpha[..., None] + jnp.einsum(
                "bkgqj,bjkd->bkgqd", p, vblk.astype(jnp.float32))
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((b, kv, g, q_block), NEG_INF, jnp.float32)
        l0 = jnp.zeros((b, kv, g, q_block), jnp.float32)
        a0 = jnp.zeros((b, kv, g, q_block, hd), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(
            kv_iter, (m0, l0, a0),
            (jnp.arange(nk_use), kb[:, :nk_use].swapaxes(0, 1),
             vb[:, :nk_use].swapaxes(0, 1)))
        out = acc / jnp.maximum(l[..., None], 1e-30)
        lse = m + jnp.log(jnp.maximum(l, 1e-30))
        return out, lse

    if causal_skip and causal and window == 0 and q_offset == 0:
        # static triangular extents: tile (qi, j) computed only if
        # j*kv_block <= (qi+1)*q_block - 1
        outs_l, lses_l = [], []
        for qi in range(nq):
            nk_use = min(nk, ((qi + 1) * q_block + kv_block - 1) // kv_block)
            o, l_ = q_iter_fn(qi, qb[:, qi], nk_use)
            outs_l.append(o)
            lses_l.append(l_)
        outs = jnp.stack(outs_l, axis=0)
        lses = jnp.stack(lses_l, axis=0)
    else:
        def q_iter(_, qi_and_blk):
            qi, qblk = qi_and_blk
            return None, q_iter_fn(qi, qblk, nk)

        _, (outs, lses) = jax.lax.scan(q_iter, None,
                                       (jnp.arange(nq), qb.swapaxes(0, 1)))
    # outs: (nq, b, kv, g, q_block, hd) -> (b, sq, h, hd)
    out = outs.transpose(1, 0, 4, 2, 3, 5).reshape(
        b, nq * q_block, h, hd)
    # lses: (nq, b, kv, g, q_block) -> (b, h, sq)
    lse = lses.transpose(1, 2, 3, 0, 4).reshape(b, h, nq * q_block)
    return out[:, :sq], lse[:, :, :sq]


def _tile_mask(qi, kv_idx, q_block, kv_block, q_offset, skv, causal, window):
    qpos = q_offset + qi * q_block + jnp.arange(q_block)
    kpos = kv_idx * kv_block + jnp.arange(kv_block)
    valid = kpos[None, :] < skv
    if causal:
        valid = valid & (kpos[None, :] <= qpos[:, None])
    if window > 0:
        valid = valid & (kpos[None, :] > qpos[:, None] - window)
    return valid


@functools.lru_cache(maxsize=None)
def _make_flash(scale, causal, window, q_offset, q_block, kv_block,
                causal_skip=False):
    @jax.custom_vjp
    def flash(q, k, v):
        out, _ = _flash_fwd(q, k, v, scale, causal, window, q_offset,
                            q_block, kv_block, causal_skip)
        return out.astype(v.dtype)

    def fwd(q, k, v):
        out, lse = _flash_fwd(q, k, v, scale, causal, window, q_offset,
                              q_block, kv_block, causal_skip)
        # residuals: unexpanded k/v, out in storage dtype, lse f32
        return out.astype(v.dtype), (q, k, v, out.astype(v.dtype), lse)

    def bwd(res, dout):
        q, k, v, out, lse = res
        b, sq, h, hd = q.shape
        skv, kv = k.shape[1], k.shape[2]
        g = h // kv
        qb_sz = min(q_block, sq)
        kb_sz = min(kv_block, skv)
        pq = (-sq) % qb_sz
        pk = (-skv) % kb_sz
        dout = dout.astype(jnp.float32)
        delta = jnp.einsum("bqhd,bqhd->bhq", dout,
                           out.astype(jnp.float32))   # (b, h, sq)

        def padq(x):
            return jnp.pad(x, ((0, 0), (0, pq), (0, 0), (0, 0))) if pq else x

        def padk(x):
            return jnp.pad(x, ((0, 0), (0, pk), (0, 0), (0, 0))) if pk else x

        qp, dop = padq(q), padq(dout)
        kp, vp = padk(k), padk(v)
        lsep = (jnp.pad(lse, ((0, 0), (0, 0), (0, pq))) if pq else lse)
        dtp = (jnp.pad(delta, ((0, 0), (0, 0), (0, pq))) if pq else delta)
        nq, nk = (sq + pq) // qb_sz, (skv + pk) // kb_sz
        qs = qp.reshape(b, nq, qb_sz, kv, g, hd)
        dos = dop.reshape(b, nq, qb_sz, kv, g, hd)
        # (b, h, nq, qb) -> (b, kv, g, nq, qb)
        lses = lsep.reshape(b, kv, g, nq, qb_sz)
        dts = dtp.reshape(b, kv, g, nq, qb_sz)
        ks = kp.reshape(b, nk, kb_sz, kv, hd)
        vs = vp.reshape(b, nk, kb_sz, kv, hd)

        def q_iter(carry, q_in):
            dk, dv = carry
            qi, qblk, doblk, lseblk, dtblk = q_in

            def kv_iter(carry2, kv_in):
                dqi, dk, dv = carry2
                kv_idx, kblk, vblk = kv_in
                s = jnp.einsum("bqkgd,bjkd->bkgqj",
                               qblk.astype(jnp.float32),
                               kblk.astype(jnp.float32)) * scale
                valid = _tile_mask(qi, kv_idx, qb_sz, kb_sz, q_offset,
                                   skv, causal, window)
                s = jnp.where(valid[None, None, None], s, NEG_INF)
                p = jnp.exp(s - lseblk[..., None])       # (b,kv,g,qb,kb)
                dvj = jnp.einsum("bkgqj,bqkgd->bjkd", p, doblk)
                dp = jnp.einsum("bqkgd,bjkd->bkgqj",
                                doblk, vblk.astype(jnp.float32))
                ds = p * (dp - dtblk[..., None]) * scale
                dqi = dqi + jnp.einsum("bkgqj,bjkd->bqkgd",
                                       ds, kblk.astype(jnp.float32))
                dkj = jnp.einsum("bkgqj,bqkgd->bjkd",
                                 ds, qblk.astype(jnp.float32))
                start = kv_idx * kb_sz
                dk = jax.lax.dynamic_update_slice_in_dim(
                    dk, jax.lax.dynamic_slice_in_dim(dk, start, kb_sz, 1)
                    + dkj, start, 1)
                dv = jax.lax.dynamic_update_slice_in_dim(
                    dv, jax.lax.dynamic_slice_in_dim(dv, start, kb_sz, 1)
                    + dvj, start, 1)
                return (dqi, dk, dv), None

            dq0 = jnp.zeros((b, qb_sz, kv, g, hd), jnp.float32)
            (dqi, dk, dv), _ = jax.lax.scan(
                kv_iter, (dq0, dk, dv),
                (jnp.arange(nk), ks.swapaxes(0, 1), vs.swapaxes(0, 1)))
            return (dk, dv), dqi

        dk0 = jnp.zeros((b, nk * kb_sz, kv, hd), jnp.float32)
        dv0 = jnp.zeros((b, nk * kb_sz, kv, hd), jnp.float32)
        (dk, dv), dqs = jax.lax.scan(
            q_iter, (dk0, dv0),
            (jnp.arange(nq), qs.swapaxes(0, 1), dos.swapaxes(0, 1),
             lses.transpose(3, 0, 1, 2, 4), dts.transpose(3, 0, 1, 2, 4)))
        dq = dqs.transpose(1, 0, 2, 3, 4, 5).reshape(
            b, nq * qb_sz, h, hd)
        return (dq[:, :sq].astype(q.dtype), dk[:, :skv].astype(k.dtype),
                dv[:, :skv].astype(v.dtype))

    flash.defvjp(fwd, bwd)
    return flash


def flash_attention(q, k, v, scale, *, causal: bool, window: int = 0,
                    q_offset: int = 0, q_block: int = 512,
                    kv_block: int = 512, causal_skip: bool = False):
    """Memory-efficient attention with recompute-in-backward and GQA-aware
    residuals. q: (b, sq, h, hd); k, v: (b, skv, kv_heads, hd) with
    h % kv_heads == 0 (kv_heads == h for MHA)."""
    fn = _make_flash(float(scale), bool(causal), int(window), int(q_offset),
                     int(q_block), int(kv_block), bool(causal_skip))
    return fn(q, k, v)


# ---------------------------------------------------------------------------
# module-level apply: train/prefill
# ---------------------------------------------------------------------------

def attn_apply(params: Params, x: jnp.ndarray, cfg: ModelConfig, *,
               positions: jnp.ndarray, causal: bool = True,
               window: int = 0, impl: str = "blocked",
               kv_out: bool = False, causal_skip: bool = False,
               use_rope: bool = True
               ) -> Tuple[jnp.ndarray, Optional[Dict[str, jnp.ndarray]]]:
    """Full-sequence attention. x: (b, s, d). Returns (y, kv or None)."""
    b, s, d = x.shape
    groups = cfg.num_heads // cfg.num_kv_heads
    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"])
    k = jnp.einsum("bsd,dhk->bshk", x, params["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, params["wv"])
    if use_rope:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    kf = _expand_kv(k, groups)
    vf = _expand_kv(v, groups)
    scale = 1.0 / (cfg.head_dim ** 0.5)
    if impl == "einsum":
        sq = jnp.arange(s)
        mask = jnp.ones((s, s), bool)
        if causal:
            mask = mask & (sq[None, :] <= sq[:, None])
        if window > 0:
            mask = mask & (sq[None, :] > sq[:, None] - window)
        out = attention_einsum(q, kf, vf, mask, scale)
    elif impl == "flash":
        out = flash_attention(q, k, v, scale, causal=causal,
                              window=window, causal_skip=causal_skip)
    else:
        out = attention_blocked(q, kf, vf, scale, causal=causal,
                                window=window, causal_skip=causal_skip)
    y = jnp.einsum("bshk,hkd->bsd", out, params["wo"])
    kv = {"k": k, "v": v} if kv_out else None
    return y, kv


def cross_attn_apply(params: Params, x: jnp.ndarray, cfg: ModelConfig, *,
                     kv: Dict[str, jnp.ndarray]) -> jnp.ndarray:
    """Cross attention against precomputed encoder K/V (no RoPE)."""
    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"])
    scale = 1.0 / (cfg.head_dim ** 0.5)
    out = flash_attention(q, kv["k"], kv["v"], scale, causal=False)
    return jnp.einsum("bshk,hkd->bsd", out, params["wo"])


def encode_kv(params: Params, x_enc: jnp.ndarray) -> Dict[str, jnp.ndarray]:
    """Project encoder output to cross-attention K/V once (cached)."""
    k = jnp.einsum("bsd,dhk->bshk", x_enc, params["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x_enc, params["wv"])
    return {"k": k, "v": v}


# ---------------------------------------------------------------------------
# KV caches + decode
# ---------------------------------------------------------------------------

def kv_cache_spec(cfg: ModelConfig, batch: int, seq_len: int, window: int,
                  dtype) -> Tuple[Dict[str, jax.ShapeDtypeStruct], Dict[str, tuple]]:
    """Cache spec for one attention layer (full or ring-buffered)."""
    length = min(window, seq_len) if window > 0 else seq_len
    shape = (batch, length, cfg.num_kv_heads, cfg.head_dim)
    sds = jax.ShapeDtypeStruct(shape, dtype)
    axes = ("batch", "kv_seq", "act_kv_heads", "head_dim")
    return ({"k": sds, "v": sds}, {"k": axes, "v": axes})


def init_kv_cache(cfg: ModelConfig, batch: int, seq_len: int, window: int,
                  dtype) -> Dict[str, jnp.ndarray]:
    spec, _ = kv_cache_spec(cfg, batch, seq_len, window, dtype)
    return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), spec)


def fill_kv_cache(cache: Dict[str, jnp.ndarray], kv: Dict[str, jnp.ndarray],
                  window: int) -> Dict[str, jnp.ndarray]:
    """Write prefill K/V (b, s, kv, hd) into the cache (ring for window)."""
    s = kv["k"].shape[1]
    if window > 0 and s > window:
        # keep last `window`, rotated so slot (p % window) holds position p
        start = s - window
        rolled = {n: jnp.roll(kv[n][:, start:], shift=(start % window),
                              axis=1) for n in ("k", "v")}
        return {n: cache[n].at[:, : rolled[n].shape[1]].set(rolled[n])
                for n in ("k", "v")}
    return {n: jax.lax.dynamic_update_slice_in_dim(cache[n], kv[n], 0, axis=1)
            for n in ("k", "v")}


def attn_decode(params: Params, x: jnp.ndarray, cfg: ModelConfig, *,
                pos: jnp.ndarray, cache: Dict[str, jnp.ndarray],
                window: int = 0, use_rope: bool = True
                ) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """One-token decode. x: (b, 1, d); pos: () int32 absolute position.

    cache k/v: (b, L, kv, hd) where L = full seq or ring window.
    """
    b = x.shape[0]
    groups = cfg.num_heads // cfg.num_kv_heads
    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"])
    k = jnp.einsum("bsd,dhk->bshk", x, params["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, params["wv"])
    if use_rope:
        posb = jnp.broadcast_to(pos[None], (b,))[:, None]   # (b,1)
        q = apply_rope(q, posb, cfg.rope_theta)
        k = apply_rope(k, posb, cfg.rope_theta)

    L = cache["k"].shape[1]
    slot = (pos % L) if window > 0 else jnp.minimum(pos, L - 1)
    ck = jax.lax.dynamic_update_slice_in_dim(cache["k"], k, slot, axis=1)
    cv = jax.lax.dynamic_update_slice_in_dim(cache["v"], v, slot, axis=1)

    idx = jnp.arange(L)
    if window > 0:
        # slot j holds position p ≡ j (mod L), p <= pos, p > pos - L
        p_of_slot = pos - ((pos - idx) % L)
        valid = (p_of_slot >= 0) & (p_of_slot > pos - window)
    else:
        valid = idx <= pos

    kf = _expand_kv(ck, groups)   # (b, L, h, hd)
    vf = _expand_kv(cv, groups)
    scale = 1.0 / (cfg.head_dim ** 0.5)
    scores = jnp.einsum("bqhk,blhk->bhql", q.astype(jnp.float32),
                        kf.astype(jnp.float32)) * scale      # (b,h,1,L)
    scores = jnp.where(valid[None, None, None, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhql,blhk->bqhk", probs, vf.astype(jnp.float32))
    y = jnp.einsum("bshk,hkd->bsd", out.astype(x.dtype), params["wo"])
    return y, {"k": ck, "v": cv}


def cross_attn_decode(params: Params, x: jnp.ndarray, cfg: ModelConfig, *,
                      kv: Dict[str, jnp.ndarray]) -> jnp.ndarray:
    """One-token cross-attention decode against cached encoder K/V."""
    groups = cfg.num_heads // cfg.num_kv_heads
    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"])
    kf = _expand_kv(kv["k"], groups)
    vf = _expand_kv(kv["v"], groups)
    scale = 1.0 / (cfg.head_dim ** 0.5)
    scores = jnp.einsum("bqhk,blhk->bhql", q.astype(jnp.float32),
                        kf.astype(jnp.float32)) * scale
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhql,blhk->bqhk", probs, vf.astype(jnp.float32))
    return jnp.einsum("bshk,hkd->bsd", out.astype(x.dtype), params["wo"])
