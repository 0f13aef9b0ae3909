"""Full-collection proxy scoring — the online hot loop.

For every ad-hoc query, ScaleDoc scores *all N* document embeddings with
the freshly trained proxy: z_d = MLP(e_d); s = (1+cos(z_q, z_d))/2.

On TPU this dispatches to the fused Pallas kernels
(repro.kernels.mlp_encoder + repro.kernels.fused_scoring) so hidden
activations never leave VMEM; the pure-jnp path below is the oracle and
the CPU fallback. Batched in chunks so the working set stays bounded for
collections of millions of documents.

Two entry points:

  * ``score_collection``       — one (params, e_q) over the collection;
  * ``score_collection_multi`` — many predicates in ONE pass over the
    collection: each chunk is read from the store once, encoded once per
    distinct proxy, and all pending query vectors sharing that proxy are
    scored with a single stacked z_q matmul (with the raw-embedding
    proxy the whole batch collapses to one matmul per chunk).

These are the *reference* scoring paths. The engine's hot path is
repro.engine.executor.ScoringExecutor, which adds chunk prefetching
(double buffering), mesh sharding, and the fused multi-query Pallas
kernel — its default mode runs the exact per-chunk jitted programs
defined here, so both paths produce bit-identical scores.

``embeds`` may be a raw (N, D) array or anything exposing
``iter_chunks(chunk)`` (see repro.engine.store.DocumentStore), so
scoring streams from disk for collections that exceed RAM.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.encoder import encoder_apply, l2_normalize


def _iter_chunks(embeds, chunk: int):
    if hasattr(embeds, "iter_chunks"):
        yield from embeds.iter_chunks(chunk)
        return
    n = embeds.shape[0]
    for start in range(0, n, chunk):
        yield start, embeds[start:start + chunk]


def _num_docs(embeds) -> int:
    return len(embeds) if hasattr(embeds, "iter_chunks") else embeds.shape[0]


def score_collection(params: Dict, e_q: jnp.ndarray, embeds,
                     chunk: int = 8192, use_kernel: bool = False
                     ) -> np.ndarray:
    """Scores for all docs. embeds: (N, D) array or DocumentStore ->
    (N,) float32 in [0, 1]."""
    if use_kernel and not hasattr(embeds, "iter_chunks"):
        from repro.kernels.fused_scoring import ops as scoring_ops
        return np.asarray(scoring_ops.score_collection(params, e_q, embeds))
    z_q = l2_normalize(encoder_apply(params, e_q))
    outs = []
    for _, block in _iter_chunks(embeds, chunk):
        outs.append(np.asarray(_single_chunk_scores(params, block, z_q)))
    return np.concatenate(outs).astype(np.float32)


@jax.named_scope("proxy_score_chunk")
def _single_chunk_scores_impl(params, block, z_q):
    """block: (B, D); z_q: (latent,) normalized query latent.

    Module-level (rather than a closure) so the streaming executor
    (repro.engine.executor) runs the *same* jitted program and stays
    bit-identical to this reference path; the unjitted impl is what the
    executor wraps in shard_map for the multi-device path.
    """
    z = encoder_apply(params, block)
    cos = l2_normalize(z) @ z_q
    return (1.0 + cos) * 0.5


_single_chunk_scores = jax.jit(_single_chunk_scores_impl)


@jax.named_scope("proxy_score_chunk")
def _proxy_chunk_scores_impl(params, block, zq_t):
    """block: (B, D); zq_t: (latent, Q) of normalized query latents."""
    z = l2_normalize(encoder_apply(params, block))
    return (1.0 + z @ zq_t) * 0.5


@jax.named_scope("proxy_score_chunk")
def _raw_chunk_scores_impl(block, zq_t):
    return (1.0 + l2_normalize(block) @ zq_t) * 0.5


_proxy_chunk_scores = jax.jit(_proxy_chunk_scores_impl)
_raw_chunk_scores = jax.jit(_raw_chunk_scores_impl)


def group_jobs(jobs: Sequence[Tuple[Optional[Dict], np.ndarray]]
               ) -> Tuple[List[Tuple[Optional[Dict], List[int]]],
                          List[jnp.ndarray]]:
    """Group (params, e_q) jobs by proxy identity for batched scoring.

    Returns ``(groups, zq_stacks)``: per distinct params object (or
    None = raw cosine) the job-column indices it covers, plus the
    matching (Q_g, latent) stack of normalized query latents. Shared by
    ``score_collection_multi`` and the streaming executor so grouping
    key and column order cannot drift between the two paths.
    """
    groups: List[Tuple[Optional[Dict], List[int]]] = []
    by_id: Dict[int, int] = {}
    for j, (params, _) in enumerate(jobs):
        key = -1 if params is None else id(params)
        if key not in by_id:
            by_id[key] = len(groups)
            groups.append((params, []))
        groups[by_id[key]][1].append(j)

    zq_stacks = []
    for params, cols in groups:
        e_qs = jnp.stack([jnp.asarray(jobs[j][1]) for j in cols])
        if params is None:
            zq_stacks.append(l2_normalize(e_qs))
        else:
            zq_stacks.append(l2_normalize(encoder_apply(params, e_qs)))
    return groups, zq_stacks


def score_collection_multi(jobs: Sequence[Tuple[Optional[Dict], np.ndarray]],
                           embeds, chunk: int = 8192) -> np.ndarray:
    """Score many predicates in one streaming pass over the collection.

    jobs: sequence of (params, e_q); ``params=None`` means raw-embedding
    cosine (no proxy). Returns (N, len(jobs)) float32 scores in [0, 1],
    columns in job order. Jobs sharing the same params object are scored
    with one encoder pass and one stacked matmul per chunk.
    """
    if not jobs:
        return np.zeros((_num_docs(embeds), 0), np.float32)

    groups, zq_stacks = group_jobs(jobs)
    zq_ts = [zq.T for zq in zq_stacks]

    n = _num_docs(embeds)
    out = np.empty((n, len(jobs)), np.float32)
    for start, block in _iter_chunks(embeds, chunk):
        block = jnp.asarray(block)
        for (params, cols), zq_t in zip(groups, zq_ts):
            if params is None:
                s = _raw_chunk_scores(block, zq_t)
            else:
                s = _proxy_chunk_scores(params, block, zq_t)
            out[start:start + block.shape[0], np.asarray(cols)] = \
                np.asarray(s, np.float32)
    return out


def direct_embedding_scores(e_q: jnp.ndarray, embeds: jnp.ndarray
                            ) -> np.ndarray:
    """Baseline: off-the-shelf embedding matching (paper §6.4 / Table 3) —
    cosine between raw embeddings, no trained proxy."""
    cos = l2_normalize(embeds) @ l2_normalize(e_q)
    return np.asarray((1.0 + cos) * 0.5, dtype=np.float32)
