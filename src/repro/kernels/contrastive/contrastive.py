"""Pallas TPU kernel: fused contrastive objectives for one mini-batch.

Computes all three ScaleDoc losses (L_qsim / L_supcon / L_polar) from the
projected latents in one VMEM-resident pass: the (n, n) similarity matrix
is built once on the MXU and every masked logsumexp reduction happens
before anything is written back to HBM. Batches are small (n <= 512,
p <= 256), so a single program handles the batch:

  VMEM: zd (n, p) + sims (n, n) + masks ~= 512*256*4 + 512*512*4 < 2 MiB.

Everything the TPU compiler (Mosaic) sees is 2-D: the labels arrive both
as a column (n, 1) and as a row (1, n), so the (n, n) class mask needs
no in-kernel transpose; per-row values stay (n, 1) columns and every
reduction keeps its dims down to (1, 1). The bellwether rows of the
similarity matrix are picked with a one-hot mask-and-reduce rather than
a dynamic row index (which lowers to a ``dynamic_slice`` Mosaic does not
implement). ``tau`` and ``lam`` are compile-time constants, and the four
results leave through one lane-dense (1, 128) block.

Output: (4,) f32 = [qsim, supcon, polar, phase2 = lam*supcon+(1-lam)*polar].

The kernel sits on the training hot path: ``ops.phase2_loss`` wraps it
in a custom_vjp (kernel forward on TPU, reference VJP backward), and the
scanned proxy trainer in repro.core.trainer differentiates through that
wrapper every phase-2 step. It doubles as the fast evaluation/monitoring
path via ``ops.losses``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG = -1e30
LANES = 128


def _lse(vals, mask, axis):
    """Masked logsumexp along ``axis``, dims kept; NEG-ish when empty."""
    masked = jnp.where(mask, vals, NEG)
    m = jnp.max(masked, axis=axis, keepdims=True)
    safe = jnp.where(m > NEG / 2, m, 0.0)
    return (jnp.log(jnp.sum(jnp.where(mask, jnp.exp(masked - safe), 0.0),
                            axis=axis, keepdims=True)) + safe)


def _pick_row(sims, scores, cand_mask, pick_min: bool):
    """Row of ``sims`` at the first index where ``scores`` (n, 1) hits
    its min (or max) over ``cand_mask`` — jnp.argmin/argmax tie-breaking
    — as a (1, n) row, via a one-hot mask-and-reduce."""
    n = sims.shape[0]
    fill = jnp.inf if pick_min else -jnp.inf
    masked = jnp.where(cand_mask, scores, fill)
    best = (jnp.min if pick_min else jnp.max)(masked, axis=0, keepdims=True)
    ids = jax.lax.broadcasted_iota(jnp.int32, (n, 1), 0).astype(jnp.float32)
    first = jnp.min(jnp.where(masked == best, ids, float(n)), axis=0,
                    keepdims=True)
    return jnp.sum(jnp.where(ids == first, sims, 0.0), axis=0,
                   keepdims=True)


def _contrastive_kernel(zq_ref, zd_ref, ycol_ref, yrow_ref, out_ref, *,
                        tau: float, lam: float):
    zq = zq_ref[...]                                   # (1, p)
    zd = zd_ref[...]                                   # (n, p)
    n = zd.shape[0]

    # L2 normalize in-register
    zqn = zq / jnp.sqrt(jnp.maximum(jnp.sum(zq * zq, axis=-1,
                                            keepdims=True), 1e-16))
    zdn = zd / jnp.sqrt(jnp.maximum(jnp.sum(zd * zd, axis=-1,
                                            keepdims=True), 1e-16))
    pos_c = ycol_ref[...] > 0.5                        # (n, 1)
    pos_r = yrow_ref[...] > 0.5                        # (1, n)
    posf_c = jnp.where(pos_c, 1.0, 0.0)
    posf_r = jnp.where(pos_r, 1.0, 0.0)
    n_pos = jnp.sum(posf_c, axis=0, keepdims=True)     # (1, 1)
    any_pos = n_pos > 0.5
    any_neg = n_pos < n - 0.5

    # ---- qsim (per-positive InfoNCE, query anchor) ----
    sims_q = jnp.sum(zdn * zqn, axis=-1, keepdims=True) / tau   # (n, 1)
    everyone = jnp.ones((n, 1), jnp.bool_)
    lse_all = _lse(sims_q, everyone, 0)
    per = -(sims_q - lse_all)
    qsim = jnp.where(any_pos,
                     jnp.sum(jnp.where(pos_c, per, 0.0), axis=0,
                             keepdims=True) / jnp.maximum(n_pos, 1.0),
                     0.0)

    # ---- pairwise sims (MXU) ----
    sims = jax.lax.dot_general(
        zdn, zdn, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) / tau       # (n, n)
    rows = jax.lax.broadcasted_iota(jnp.int32, (n, n), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (n, n), 1)
    off_diag = rows != cols
    same = posf_c == posf_r

    # ---- supcon ----
    u_mask = same & off_diag
    u_count = jnp.sum(jnp.where(u_mask, 1.0, 0.0), axis=1, keepdims=True)
    lse_u = _lse(sims, u_mask, 1)
    lse_a = _lse(sims, off_diag, 1)
    per_anchor = -(lse_u - lse_a) / jnp.maximum(u_count, 1.0)
    valid = u_count > 0.5
    supcon = (jnp.sum(jnp.where(valid, per_anchor, 0.0), axis=0,
                      keepdims=True)
              / jnp.maximum(jnp.sum(jnp.where(valid, 1.0, 0.0), axis=0,
                                    keepdims=True), 1.0))

    # ---- polar (bellwether anchors) ----
    sims_bp = _pick_row(sims, sims_q, pos_c, pick_min=True)    # (1, n)
    sims_bn = _pick_row(sims, sims_q, ~pos_c, pick_min=False)
    all_r = jnp.ones((1, n), jnp.bool_)
    loss_p = -(_lse(sims_bp, pos_r, 1) - _lse(sims_bp, all_r, 1))
    loss_n = -(_lse(sims_bn, ~pos_r, 1) - _lse(sims_bn, all_r, 1))
    polar = (jnp.where(any_pos, loss_p, 0.0)
             + jnp.where(any_neg, loss_n, 0.0))

    lane = jax.lax.broadcasted_iota(jnp.int32, (1, LANES), 1)
    out_ref[...] = jnp.where(
        lane == 0, qsim, jnp.where(
            lane == 1, supcon, jnp.where(
                lane == 2, polar, jnp.where(
                    lane == 3, lam * supcon + (1.0 - lam) * polar, 0.0))))


@functools.partial(jax.jit, static_argnames=("tau", "lam", "interpret"))
def contrastive_losses(z_q: jnp.ndarray, z_d: jnp.ndarray, y: jnp.ndarray,
                       tau: float, lam: float, *,
                       interpret: bool = False) -> jnp.ndarray:
    """z_q: (p,); z_d: (n, p); y: (n,) float {0,1}; ``tau``/``lam`` are
    Python floats (static). Returns (4,) f32 [qsim, supcon, polar,
    phase2]."""
    n, p = z_d.shape
    y = y.astype(jnp.float32)
    full = lambda shape: pl.BlockSpec(shape, lambda i: (0, 0))  # noqa: E731
    out = pl.pallas_call(
        functools.partial(_contrastive_kernel, tau=float(tau),
                          lam=float(lam)),
        grid=(1,),
        in_specs=[full((1, p)), full((n, p)), full((n, 1)), full((1, n))],
        out_specs=full((1, LANES)),
        out_shape=jax.ShapeDtypeStruct((1, LANES), jnp.float32),
        interpret=interpret,
    )(z_q.astype(jnp.float32).reshape(1, p), z_d.astype(jnp.float32),
      y.reshape(n, 1), y.reshape(1, n))
    return out[0, :4]
