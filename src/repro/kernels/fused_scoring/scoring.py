"""Pallas TPU kernels: fused proxy scoring over a document tile.

The ScaleDoc online hot loop — for every query, every document embedding
runs through the 3-layer proxy MLP, is L2-normalized, and dotted with the
normalized query latent. Done naively, each stage round-trips hidden
activations through HBM; these kernels keep the whole per-tile pipeline
in VMEM:

    tile (Bn, D) -> h1 = gelu(tile @ W1 + b1)      (Bn, H)
                 -> h2 = gelu(h1 @ W2 + b2)        (Bn, H)
                 -> z  = h2 @ W3 + b3              (Bn, L)
                 -> s  = 0.5 * (1 + (z/|z|) . zq)  (Bn,)

One kernel runs that pipeline:

  * ``fused_scores_multi`` — a (Q, L) *stack* of query latents, scores
    (N, Q). The MLP (the dominant cost) runs once per tile and the final
    dot generalizes to one (Bn, L) x (L, Q) matmul, so Q predicates cost
    one encoder pass instead of Q — the engine's batched multi-predicate
    path stays inside the kernel instead of bolting a stacked z_q matmul
    on after it;
  * ``fused_scores``       — one query latent zq (L,), scores (N,): the
    same kernel at Q=1.

Grid: one program per document tile (N / BLOCK_N). Weights are small
(D*H + H*H + H*L floats) and are mapped whole into VMEM per program; the
MXU sees three back-to-back matmuls with 128-aligned contraction dims.

Memory at the ProxyConfig defaults (D=4096, H=512, L=128, BLOCK_N=128,
f32): compiled for a described TPU v5e (tests/test_tpu_compile.py), an
8192-row tile with Q=3 gives ``memory_analysis()`` = 143,923,712
argument bytes (tile + weights, HBM), 131,072 output bytes and 0
temporary bytes. It does not report VMEM; the chip's compiler accepts
the kernel at these widths.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

BLOCK_N = 128


def _scoring_kernel_multi(docs_ref, w1_ref, b1_ref, w2_ref, b2_ref, w3_ref,
                          b3_ref, zq_ref, out_ref):
    docs = docs_ref[...].astype(jnp.float32)           # (Bn, D)
    h = jnp.dot(docs, w1_ref[...].astype(jnp.float32),
                preferred_element_type=jnp.float32) + b1_ref[...]
    h = jax.nn.gelu(h)
    h = jnp.dot(h, w2_ref[...].astype(jnp.float32),
                preferred_element_type=jnp.float32) + b2_ref[...]
    h = jax.nn.gelu(h)
    z = jnp.dot(h, w3_ref[...].astype(jnp.float32),
                preferred_element_type=jnp.float32) + b3_ref[...]
    norm = jnp.sqrt(jnp.maximum(jnp.sum(z * z, axis=-1, keepdims=True),
                                1e-16))
    zq = zq_ref[...]                                    # (Qp, L) normalized
    cos = jnp.dot(z / norm, zq.T,
                  preferred_element_type=jnp.float32)   # (Bn, Qp)
    out_ref[...] = 0.5 * (1.0 + cos)


@functools.partial(jax.jit, static_argnames=("block_n", "interpret"))
def fused_scores_multi(docs: jnp.ndarray, w1, b1, w2, b2, w3, b3,
                       zq_stack: jnp.ndarray, *, block_n: int = BLOCK_N,
                       interpret: bool = False) -> jnp.ndarray:
    """docs: (N, D), zq_stack: (Q, L) unit rows -> scores (N, Q) in [0,1].

    One MLP pass per document tile regardless of Q; the query dim is
    padded to a multiple of 8 (f32 sublane) so the final matmul tiles
    cleanly, and the pad columns are sliced off before returning.
    """
    n, d = docs.shape
    h = w1.shape[1]
    l = w3.shape[1]
    q = zq_stack.shape[0]
    pad = (-n) % block_n
    if pad:
        docs = jnp.pad(docs, ((0, pad), (0, 0)))
    qpad = (-q) % 8
    if qpad:
        zq_stack = jnp.pad(zq_stack, ((0, qpad), (0, 0)))
    qp = q + qpad
    grid = ((n + pad) // block_n,)

    out = pl.pallas_call(
        _scoring_kernel_multi,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_n, d), lambda i: (i, 0)),
            pl.BlockSpec((d, h), lambda i: (0, 0)),
            pl.BlockSpec((h,), lambda i: (0,)),
            pl.BlockSpec((h, h), lambda i: (0, 0)),
            pl.BlockSpec((h,), lambda i: (0,)),
            pl.BlockSpec((h, l), lambda i: (0, 0)),
            pl.BlockSpec((l,), lambda i: (0,)),
            pl.BlockSpec((qp, l), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((block_n, qp), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n + pad, qp), jnp.float32),
        interpret=interpret,
    )(docs, w1, b1, w2, b2, w3, b3, zq_stack)
    return out[:n, :q]


@functools.partial(jax.jit, static_argnames=("block_n", "interpret"))
def fused_scores(docs: jnp.ndarray, w1, b1, w2, b2, w3, b3,
                 zq_normalized: jnp.ndarray, *, block_n: int = BLOCK_N,
                 interpret: bool = False) -> jnp.ndarray:
    """docs: (N, D) -> scores (N,) in [0, 1]. zq_normalized: (L,) unit.

    The multi-query kernel with Q=1: its (block_n, 8) output block is
    2-D, where a (block_n,) block of an f32[N] output does not match the
    layout TPU gives 1-D arrays and the chip's compiler refuses it."""
    return fused_scores_multi(docs, w1, b1, w2, b2, w3, b3,
                              zq_normalized[None, :], block_n=block_n,
                              interpret=interpret)[:, 0]
