"""Inputs made from the seed: the topic-mixture store, planted-concept
leaves and token documents.

The store follows the program's synthetic corpus (``repro.data.synthetic``:
documents are normalised mixtures of random unit topics plus noise; a
leaf's truth is a planted concept over three topics that raw cosine
matching sees only in part), rebuilt here so that the yardstick cannot
move with the program. The store is drawn on the device in one jitted
call and copied to the host once.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np


def seed_words(seed: int, *tags: int) -> np.ndarray:
    """Two 31-bit words derived from a seed of any size and some tags."""
    return np.random.SeedSequence([int(seed), *tags]).generate_state(2) & 0x7FFFFFFF


def jax_key(seed: int, *tags: int):
    a, b = seed_words(seed, *tags)
    return jax.random.fold_in(jax.random.PRNGKey(int(a)), int(b))


def program_seed(seed: int) -> int:
    """The seed handed to the program, which keys ``jax.random.PRNGKey``
    with it and so needs it to fit 31 bits."""
    return int(seed_words(seed, 0)[0])


@dataclasses.dataclass
class TopicStore:
    embeds: np.ndarray          # (N, D) float32, unit rows
    weights: np.ndarray         # (N, k) topic weights
    topics: np.ndarray          # (k, D) unit topics

    @functools.cached_property
    def affinity(self) -> np.ndarray:
        """(k, N) topic weights standardised per topic."""
        w = self.weights.T.astype(np.float64)
        return (w - w.mean(axis=1, keepdims=True)) / (
            w.std(axis=1, keepdims=True) + 1e-9)


def topic_store(seed: int, n_docs: int, dim: int, n_topics: int,
                noise_at_256d: float) -> TopicStore:
    """Unit-norm topic mixtures. The per-dimension noise is scaled so the
    noise-to-signal ratio is that of a 256-d corpus with noise
    ``noise_at_256d``, whatever ``dim`` is."""
    noise = noise_at_256d * (256.0 / dim) ** 0.5

    @jax.jit
    def make(key):
        kt, kw, kn = jax.random.split(key, 3)
        topics = jax.random.normal(kt, (n_topics, dim), jnp.float32)
        topics = topics / jnp.linalg.norm(topics, axis=1, keepdims=True)
        w = jax.random.gamma(kw, 0.5, (n_docs, n_topics), jnp.float32)
        w = w / jnp.sum(w, axis=1, keepdims=True)
        e = jnp.dot(w, topics, precision="highest") + noise * jax.random.normal(
            kn, (n_docs, dim), jnp.float32)
        e = e / jnp.linalg.norm(e, axis=1, keepdims=True)
        return e, w, topics

    e, w, topics = make(jax_key(seed, 1))
    return TopicStore(np.asarray(e), np.asarray(w), np.asarray(topics))


@dataclasses.dataclass
class Leaf:
    embed: np.ndarray           # (D,) unit query embedding
    truth: np.ndarray           # (N,) bool
    tag: tuple                  # what it was drawn from


def planted_leaf(store: TopicStore, seed: int, tag: Sequence[int],
                 selectivity: float, nonlinearity: float = 0.3,
                 query_noise: float = 0.25, neg_weight: float = 0.8) -> Leaf:
    """Truth over two driving topics, a hidden negative topic and their
    interaction, cut at the ``selectivity`` quantile; the query embedding
    points at the two driving topics, with noise."""
    rng = np.random.default_rng(np.random.SeedSequence([int(seed), 7, *tag]))
    k = store.topics.shape[0]
    ta, tb, tc = rng.choice(k, size=3, replace=False)

    z = store.affinity.__getitem__
    raw = (z(ta) + 0.6 * z(tb) - neg_weight * z(tc)
           + nonlinearity * z(ta) * z(tb))
    truth = raw > np.quantile(raw, 1.0 - selectivity)
    q = (store.topics[ta] + 0.6 * store.topics[tb]
         + query_noise * rng.normal(size=store.topics.shape[1]))
    q = (q / np.linalg.norm(q)).astype(np.float32)
    return Leaf(embed=q, truth=truth, tag=tuple(tag))


def token_docs(seed: int, n_docs: int, doc_len: int, vocab: int) -> np.ndarray:
    """(n_docs, doc_len) int32 token ids drawn uniformly from [1, vocab):
    no pad token, so every batch has the same width."""
    rng = np.random.default_rng(np.random.SeedSequence([int(seed), 3]))
    return rng.integers(1, vocab, size=(n_docs, doc_len), dtype=np.int32)


class TruthOracle:
    """The oracle LLM's stand-in: answers from the generator's truth and
    counts every document it is asked about."""

    def __init__(self, truth: np.ndarray):
        self._truth = np.asarray(truth, bool)
        self.calls = 0

    def label(self, indices) -> np.ndarray:
        idx = np.asarray(indices, np.int64)
        self.calls += len(idx)
        return self._truth[idx].copy()
