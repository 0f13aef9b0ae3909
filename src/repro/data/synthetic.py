"""Synthetic document corpora with *planted semantics*.

Real setting: NvEmbed embeddings of PubMed/BigPatent/GovReport + GPT-4o
ground truth. Offline here, we generate:

  * topic-mixture embeddings: e_d = normalize(W_d @ T + noise), W_d sparse
    Dirichlet-ish topic weights, T (k, D) random orthogonal-ish topics;
  * queries with a *nonlinear* planted concept: truth depends on an
    interaction of two topic affinities (a1*s1 + a2*s2 + a3*s1*s2 > theta)
    so raw embedding cosine is informative but imperfect (as in paper
    Table 3, trained proxies must beat direct embedding matching);
  * token sequences per document from topic-dependent unigram tables, for
    the LM-training example and the LM-as-judge oracle.

Selectivity (positive fraction) is controlled by calibrating theta.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import numpy as np


@dataclasses.dataclass
class Corpus:
    embeds: np.ndarray        # (N, D) float32, L2-normalized
    topic_weights: np.ndarray  # (N, k)
    topics: np.ndarray        # (k, D)
    tokens: Optional[np.ndarray] = None  # (N, L) int32


@dataclasses.dataclass
class Query:
    embed: np.ndarray         # (D,)
    truth: np.ndarray         # (N,) bool ground truth
    selectivity: float
    topic_a: int = 0
    topic_b: int = 0


def make_corpus(seed: int, n_docs: int = 10_000, dim: int = 256,
                n_topics: int = 16, noise: float = 0.03,
                with_tokens: bool = False, vocab: int = 256,
                doc_len: int = 64) -> Corpus:
    rng = np.random.default_rng(seed)
    topics = rng.normal(size=(n_topics, dim)).astype(np.float32)
    topics /= np.linalg.norm(topics, axis=1, keepdims=True)
    # sparse-ish topic weights (2-4 active topics per doc)
    w = rng.gamma(0.5, 1.0, size=(n_docs, n_topics)).astype(np.float32)
    w /= w.sum(axis=1, keepdims=True)
    e = w @ topics + noise * rng.normal(size=(n_docs, dim)).astype(np.float32)
    e /= np.linalg.norm(e, axis=1, keepdims=True)
    tokens = None
    if with_tokens:
        # topic-dependent unigram tables; inverse-CDF sampling by binary
        # search, a block of documents at a time, so memory stays
        # O(block * vocab) even at a full LM vocabulary
        tables = rng.dirichlet(np.full(vocab, 0.05), size=n_topics)
        u = rng.random((n_docs, doc_len))
        tokens = np.empty((n_docs, doc_len), np.int32)
        block = 256
        for lo in range(0, n_docs, block):
            probs = w[lo:lo + block] @ tables
            probs /= probs.sum(axis=1, keepdims=True)
            cdf = np.cumsum(probs, axis=1)
            for i, row in enumerate(cdf):
                tokens[lo + i] = np.searchsorted(row, u[lo + i],
                                                 side="right")
        # a draw above the last CDF entry (rounding) maps to token 0
        tokens[tokens == vocab] = 0
    return Corpus(embeds=e, topic_weights=w, topics=topics, tokens=tokens)


def make_query(corpus: Corpus, seed: int, selectivity: float = 0.3,
               nonlinearity: float = 0.3, label_noise: float = 0.0,
               query_noise: float = 0.25, neg_weight: float = 0.8) -> Query:
    """Plant a concept over three topics: two positive drivers (which the
    query embedding points at), one *hidden negative* topic plus a mild
    interaction term — both invisible to raw cosine matching but learnable
    from oracle labels (the Table-3 regime: trained proxy must beat the
    off-the-shelf embedding)."""
    rng = np.random.default_rng(seed)
    k = corpus.topics.shape[0]
    ta, tb, tc = rng.choice(k, size=3, replace=False)

    def z(i):
        s = corpus.topic_weights[:, i]
        return (s - s.mean()) / (s.std() + 1e-9)

    raw = (z(ta) + 0.6 * z(tb) - neg_weight * z(tc)
           + nonlinearity * z(ta) * z(tb))
    if label_noise > 0:
        raw = raw + label_noise * rng.normal(size=len(raw))
    theta = np.quantile(raw, 1.0 - selectivity)
    truth = raw > theta
    q = (corpus.topics[ta] + 0.6 * corpus.topics[tb]
         + query_noise * rng.normal(size=corpus.topics.shape[1]))
    q = (q / np.linalg.norm(q)).astype(np.float32)
    return Query(embed=q, truth=truth,
                 selectivity=float(truth.mean()), topic_a=int(ta),
                 topic_b=int(tb))


def make_workload(seed: int, n_docs: int = 10_000, dim: int = 256,
                  n_queries: int = 5, selectivities=None
                  ) -> Tuple[Corpus, list]:
    """A corpus + a batch of queries with varied selectivity (paper uses
    20 queries x 3 datasets; benchmarks scale this down for CPU)."""
    corpus = make_corpus(seed, n_docs=n_docs, dim=dim)
    if selectivities is None:
        rng = np.random.default_rng(seed + 1)
        selectivities = rng.uniform(0.1, 0.5, size=n_queries)
    queries = [make_query(corpus, seed + 100 + i, selectivity=float(s))
               for i, s in enumerate(selectivities)]
    return corpus, queries
