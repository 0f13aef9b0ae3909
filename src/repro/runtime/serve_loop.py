"""Batched serving driver — the compute side of the offline phase.

Drains a queue of documents through prefill + mean-pool, producing the
embeddings ScaleDoc's online phase consumes. Microbatches to the
compiled batch size (padding the tail), optionally splitting long
documents into chunks whose pooled states are averaged.

``EmbeddingService`` is the pure compute service: tokens in, pooled
embeddings out, nothing persisted. The durable offline *job* — writing
those embeddings append-only into a manifest-backed store directory
with commit markers and kill/resume semantics — lives in
``repro.engine.ingest``, which drives this service batch by batch
(``embed_batch``). On a pod this runs under the production mesh with
the serve shardings from launch/steps.py.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Dict, Iterable, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.config.base import ModelConfig
from repro.models import build_model


@dataclasses.dataclass
class ServeStats:
    documents: int = 0
    batches: int = 0
    pad_waste_frac: float = 0.0
    wall_s: float = 0.0


class EmbeddingService:
    """LM-as-embedder: prefill the document, mean-pool final hidden
    states. (The paper's NvEmbed role, with any assigned arch as the
    backbone.)"""

    def __init__(self, cfg: ModelConfig, params, batch_size: int = 8,
                 mesh=None):
        self.cfg = cfg
        self.model = build_model(cfg)
        self.params = params
        self.batch_size = batch_size
        self.mesh = mesh

        model = self.model

        @jax.named_scope("backbone_forward")
        def embed_batch(params, tokens):
            # teacher-forced forward; pool pre-logits hidden states.
            x = model.embed_inputs(params, tokens)
            positions = jnp.arange(x.shape[1])
            shared = params.get("shared")

            def body(x, gp):
                x, _, _ = model._group_fullseq(
                    x, gp, shared, positions=positions,
                    collect_cache=False)
                return x, None

            x, _ = jax.lax.scan(body, x, params["blocks"])
            mask = (tokens > 0).astype(x.dtype)[..., None]
            pooled = jnp.sum(x * mask, axis=1) / jnp.maximum(
                jnp.sum(mask, axis=1), 1.0)
            return pooled.astype(jnp.float32)

        self._embed = jax.jit(embed_batch)

    def embed_batch(self, batch) -> jax.Array:
        """One already-padded (B, W) int32 token batch -> (B, d_model)
        float32 pooled embeddings, on device. Rows of all-zero (pad)
        tokens pool to zero vectors; callers slice them off. The batch
        may carry any jax sharding (repro.engine.ingest row-shards it
        over a data mesh) — the jitted program follows the input
        placement."""
        return self._embed(self.params, batch)

    def embed_documents(self, docs_tokens: Iterable[np.ndarray],
                        stats: Optional[ServeStats] = None) -> np.ndarray:
        """docs_tokens: iterable of 1-D int arrays (ragged). Returns
        (N, d_model) float32 embeddings."""
        docs = list(docs_tokens)
        t0 = time.time()
        n = len(docs)
        width = max(len(d) for d in docs)
        out = np.zeros((n, self.cfg.d_model), np.float32)
        pad_total, tok_total = 0, 0
        for start in range(0, n, self.batch_size):
            chunk = docs[start:start + self.batch_size]
            bs = len(chunk)
            batch = np.zeros((self.batch_size, width), np.int32)
            for i, d in enumerate(chunk):
                batch[i, :len(d)] = d
                pad_total += width - len(d)
                tok_total += width
            emb = np.asarray(self._embed(self.params, jnp.asarray(batch)))
            out[start:start + bs] = emb[:bs]
        if stats is not None:
            stats.documents += n
            stats.batches += (n + self.batch_size - 1) // self.batch_size
            stats.pad_waste_frac = pad_total / max(tok_total, 1)
            stats.wall_s += time.time() - t0
        return out


def generate(model, params, prompt_tokens, steps: int,
             cache_len: int = 0, greedy: bool = True, key=None):
    """Autoregressive decode driver: prefill the prompt, then step the
    jitted decode function. prompt_tokens: (b, s) int32. Returns
    (b, steps) int32 generated ids."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    b, s = prompt_tokens.shape
    total = cache_len or (s + steps)
    logits, cache = model.prefill(params, jnp.asarray(prompt_tokens),
                                  cache_len=total)

    @jax.jit
    def step(params, tok, pos, cache, key):
        logits, cache = model.decode_step(params, tok, pos, cache)
        last = logits[:, -1]
        if greedy:
            nxt = jnp.argmax(last, axis=-1).astype(jnp.int32)
        else:
            nxt = jax.random.categorical(key, last).astype(jnp.int32)
        return nxt[:, None], cache

    key = key if key is not None else jax.random.PRNGKey(0)
    tok = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)[:, None]
    out = [np.asarray(tok)]
    for t in range(1, steps):
        key, sub = jax.random.split(key)
        tok, cache = step(params, tok, jnp.array(s + t - 1, jnp.int32),
                          cache, sub)
        out.append(np.asarray(tok))
    return np.concatenate(out, axis=1)
