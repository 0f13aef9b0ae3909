"""Offline ingestion suite: the appendable store + resumable indexer.

Pins the three claims the offline phase makes:
  * StoreWriter durability semantics — rows are visible only after
    commit(), torn tails are truncated on reopen, and producer
    fingerprints are enforced;
  * a killed-and-resumed ingestion produces a store byte-identical to
    an uninterrupted run (the bit-identical resume guarantee);
  * engine filter decisions over the ingested MemmapStore are identical
    to the in-memory path over the same embeddings.
"""
import dataclasses
import pathlib

import jax
import numpy as np
import pytest

from repro.checkpoint import list_steps
from repro.config.base import CascadeConfig, ModelConfig, ProxyConfig
from repro.core import SimulatedOracle
from repro.data import make_corpus, make_query
from repro.engine import (InMemoryStore, Ingestor, MemmapStore,
                          ScaleDocEngine, SemanticPredicate,
                          StoreFingerprintError, StoreWriter, build_index,
                          load_manifest)
from repro.engine.ingest import CKPT_DIRNAME
from repro.engine.store import DATA_NAME
from repro.models import build_model
from repro.runtime.serve_loop import EmbeddingService

N_DOCS, DOC_LEN, BATCH = 96, 12, 8


@pytest.fixture(scope="module")
def service():
    cfg = ModelConfig(name="ingest-test", num_layers=2, d_model=32,
                      num_heads=2, num_kv_heads=2, d_ff=64, vocab_size=64,
                      dtype="float32", remat="none")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return EmbeddingService(cfg, params, batch_size=BATCH)


@pytest.fixture(scope="module")
def corpus():
    return make_corpus(seed=0, n_docs=N_DOCS, dim=16, with_tokens=True,
                       vocab=64, doc_len=DOC_LEN)


@pytest.fixture(scope="module")
def docs(corpus):
    return [corpus.tokens[i] for i in range(N_DOCS)]


def _bin_bytes(directory) -> bytes:
    return (pathlib.Path(directory) / DATA_NAME).read_bytes()


# -- StoreWriter durability semantics ----------------------------------------


def test_writer_roundtrip_and_append(tmp_path):
    rng = np.random.default_rng(0)
    a = rng.normal(size=(5, 4)).astype(np.float32)
    b = rng.normal(size=(3, 4)).astype(np.float32)
    with StoreWriter.open(tmp_path, dim=4, fingerprint={"m": "x"}) as w:
        assert w.rows == 0
        assert w.append(a) == 5
        assert w.rows == 0          # not durable until commit
        assert w.commit() == 5
        assert w.append(b) == 8 and w.commit() == 8
    store = MemmapStore.open(tmp_path)
    assert len(store) == 8 and store.dim == 4
    np.testing.assert_array_equal(store.get(np.arange(8)),
                                  np.concatenate([a, b]))
    m = store.manifest
    assert (m.rows, m.doc_id_start, m.doc_id_end) == (8, 0, 8)
    assert m.fingerprint == {"m": "x"}


def test_writer_truncates_torn_tail(tmp_path):
    rng = np.random.default_rng(1)
    a = rng.normal(size=(4, 3)).astype(np.float32)
    w = StoreWriter.open(tmp_path, dim=3)
    w.append(a)
    w.commit()
    w.append(rng.normal(size=(2, 3)).astype(np.float32))  # never committed
    w.close()                                   # "kill": tail stays on disk
    assert len(_bin_bytes(tmp_path)) == 6 * 3 * 4
    w2 = StoreWriter.open(tmp_path, dim=3)      # reopen truncates the tail
    assert w2.rows == 4
    assert len(_bin_bytes(tmp_path)) == 4 * 3 * 4
    w2.close()
    assert len(MemmapStore.open(tmp_path)) == 4


def test_writer_rejects_mismatches(tmp_path):
    w = StoreWriter.open(tmp_path, dim=4, fingerprint={"model": "a"})
    with pytest.raises(ValueError):
        w.append(np.zeros((2, 5), np.float32))          # wrong dim
    w.close()
    with pytest.raises(StoreFingerprintError):
        StoreWriter.open(tmp_path, dim=4, fingerprint={"model": "b"})
    with pytest.raises(ValueError):
        StoreWriter.open(tmp_path, dim=8,               # wrong store dim
                         fingerprint={"model": "a"})
    with pytest.raises(ValueError):
        StoreWriter.open(tmp_path, dim=4,               # wrong id range
                         fingerprint={"model": "a"}, doc_id_start=100)


# -- resumable ingestion ------------------------------------------------------


def test_interrupted_resume_is_bit_identical(service, docs, tmp_path):
    """Acceptance: kill mid-run (row-count cap), resume, and the final
    store is byte-identical to a single uninterrupted run."""
    ing = Ingestor(service, commit_every_batches=2)
    full = ing.ingest(docs, tmp_path / "full")
    assert not full.interrupted and len(full.store) == N_DOCS
    assert full.stats.docs == N_DOCS and full.stats.commits > 1

    kill_at = 37                        # mid-batch, mid-commit-group
    part = ing.ingest(docs, tmp_path / "killed", max_docs=kill_at)
    assert part.interrupted
    group = 2 * BATCH
    assert len(part.store) == (kill_at // group) * group  # last commit
    # the torn (uncommitted) tail is on disk but not in the manifest
    torn = len(_bin_bytes(tmp_path / "killed")) - part.store.manifest.nbytes
    assert torn == (kill_at - len(part.store)) * 32 * 4

    resumed = ing.ingest(docs, tmp_path / "killed")
    assert not resumed.interrupted
    assert resumed.stats.resumed_rows == len(part.store)
    assert resumed.stats.docs == N_DOCS - len(part.store)
    assert _bin_bytes(tmp_path / "killed") == _bin_bytes(tmp_path / "full")
    assert load_manifest(tmp_path / "killed").rows == N_DOCS
    # cumulative job accounting spans both runs; markers record durable
    # progress, so the killed run's torn-tail docs are not double counted
    assert resumed.job_stats.docs == N_DOCS
    assert resumed.job_stats.commits == full.stats.commits


def test_complete_store_fast_path(service, docs, tmp_path):
    ing = Ingestor(service, commit_every_batches=2)
    first = ing.ingest(docs, tmp_path)
    before = _bin_bytes(tmp_path)
    again = ing.ingest(docs, tmp_path)
    assert again.stats.docs == 0 and again.stats.batches == 0
    assert again.stats.resumed_rows == N_DOCS
    assert len(again.store) == N_DOCS
    assert _bin_bytes(tmp_path) == before
    assert again.job_stats.docs == first.stats.docs


def test_checkpoint_markers_written(service, docs, tmp_path):
    ing = Ingestor(service, commit_every_batches=2,
                   checkpoint_every_commits=2, checkpoint_keep=2)
    res = ing.ingest(docs, tmp_path)
    steps = list_steps(str(tmp_path / CKPT_DIRNAME))
    assert steps, "no checkpoint markers written"
    assert len(steps) <= 2                      # GC honors keep
    assert steps[-1] == N_DOCS                  # final completion marker
    # cadence markers (every 2nd commit) plus the completion marker
    assert res.stats.checkpoints >= res.stats.commits // 2


def test_ingest_fingerprint_guards_producer(service, docs, tmp_path):
    ing = Ingestor(service, commit_every_batches=2)
    ing.ingest(docs, tmp_path, max_docs=BATCH * 2)
    # same service, different batching geometry -> different producer
    other = Ingestor(service, commit_every_batches=4)
    with pytest.raises(StoreFingerprintError):
        other.ingest(docs, tmp_path)


def test_resume_rejects_different_corpus(service, docs, tmp_path):
    """A killed job resumed over different documents must refuse to mix
    the two corpora in one store."""
    ing = Ingestor(service, commit_every_batches=2)
    ing.ingest(docs, tmp_path, max_docs=BATCH * 2)
    other = [np.array(d) for d in docs]
    other[40] = other[40].copy()
    other[40][0] = (other[40][0] + 1) % 64          # one token differs
    with pytest.raises(StoreFingerprintError):
        ing.ingest(other, tmp_path)


# -- engine parity over the ingested store ------------------------------------


def test_engine_decisions_match_inmemory(service, corpus, docs, tmp_path):
    """Acceptance: engine filter decisions from the ingested MemmapStore
    match InMemoryStore exactly (same embeddings, same seed)."""
    res = build_index(service, docs, tmp_path, commit_every_batches=2)
    embeds = np.asarray(res.store.get(np.arange(N_DOCS)))

    query = make_query(corpus, seed=7, selectivity=0.3)
    pos = np.nonzero(query.truth)[0][:4]
    e_q = embeds[pos].mean(axis=0)
    e_q = (e_q / (np.linalg.norm(e_q) + 1e-9)).astype(np.float32)
    pcfg = ProxyConfig(embed_dim=32, hidden_dim=32, latent_dim=16,
                       proj_dim=8, phase1_steps=8, phase2_steps=8,
                       batch_size=32)
    ccfg = CascadeConfig(accuracy_target=0.85)

    results = []
    for store in (InMemoryStore(embeds), MemmapStore.open(tmp_path)):
        engine = ScaleDocEngine(store, pcfg, ccfg, chunk=32)
        oracle = SimulatedOracle(query.truth)
        results.append(engine.filter(
            SemanticPredicate(e_q, oracle, name="q"), seed=0))
    mem, mmap = results
    np.testing.assert_array_equal(mem.mask, mmap.mask)
    assert mem.oracle_calls_total == mmap.oracle_calls_total
    np.testing.assert_array_equal(mem.leaf_reports[0].scores,
                                  mmap.leaf_reports[0].scores)


def test_from_corpus_builds_and_resumes(service, corpus, docs, tmp_path):
    pcfg = ProxyConfig(embed_dim=32, hidden_dim=32, latent_dim=16,
                       proj_dim=8, phase1_steps=8, phase2_steps=8,
                       batch_size=32)
    engine = ScaleDocEngine.from_corpus(
        service, docs, tmp_path, proxy_cfg=pcfg,
        cascade_cfg=CascadeConfig(accuracy_target=0.85), chunk=32,
        ingest_kwargs=dict(commit_every_batches=2))
    assert isinstance(engine.store, MemmapStore)
    assert len(engine.store) == N_DOCS
    assert engine.ingest_result.stats.docs == N_DOCS
    assert engine.proxy_cfg.embed_dim == 32

    query = make_query(corpus, seed=7, selectivity=0.3)
    res = engine.filter(SemanticPredicate(
        engine.store.get([0]).ravel(), SimulatedOracle(query.truth)))
    assert res.mask.shape == (N_DOCS,)

    # second construction over the same path resumes the complete store
    engine2 = ScaleDocEngine.from_corpus(
        service, docs, tmp_path, proxy_cfg=pcfg,
        ingest_kwargs=dict(commit_every_batches=2))
    assert engine2.ingest_result.stats.docs == 0
    np.testing.assert_array_equal(
        engine2.store.get(np.arange(N_DOCS)),
        engine.store.get(np.arange(N_DOCS)))


_MESH_SCRIPT = r"""
import tempfile, pathlib
import jax, numpy as np
from repro.config.base import ModelConfig
from repro.data import make_corpus
from repro.engine import build_index
from repro.launch.mesh import make_scoring_mesh
from repro.models import build_model
from repro.runtime.serve_loop import EmbeddingService

cfg = ModelConfig(name="ingest-test", num_layers=2, d_model=32,
                  num_heads=2, num_kv_heads=2, d_ff=64, vocab_size=64,
                  dtype="float32", remat="none")
model = build_model(cfg)
service = EmbeddingService(cfg, model.init(jax.random.PRNGKey(0)),
                           batch_size=8)
corpus = make_corpus(seed=0, n_docs=48, dim=16, with_tokens=True,
                     vocab=64, doc_len=12)
docs = [corpus.tokens[i] for i in range(48)]
assert jax.device_count() == 4
single = build_index(service, docs, tempfile.mkdtemp(),
                     commit_every_batches=2)
mesh = make_scoring_mesh()
sharded = build_index(service, docs, tempfile.mkdtemp(),
                      commit_every_batches=2, mesh=mesh)
assert sharded.stats.devices == 4
a = np.asarray(single.store.get(np.arange(48)))
b = np.asarray(sharded.store.get(np.arange(48)))
np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-6)
print("MESH-INGEST-OK")
"""


def test_sharded_ingest_matches_single_device():
    """Runs in a subprocess: the device count is locked per process, so
    forcing 4 host devices needs a fresh interpreter. Batch rows shard
    over a ("data",) mesh; embeddings must match the 1-device run."""
    import os
    import subprocess
    import sys
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=4")
    env["JAX_PLATFORMS"] = "cpu"
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    env["PYTHONPATH"] = os.path.abspath(src) + os.pathsep \
        + env.get("PYTHONPATH", "")
    proc = subprocess.run([sys.executable, "-c", _MESH_SCRIPT],
                          capture_output=True, text=True, env=env,
                          timeout=600)
    assert proc.returncode == 0, proc.stderr
    assert "MESH-INGEST-OK" in proc.stdout


def test_ingest_stats_accounting(service, docs, tmp_path):
    res = build_index(service, docs, tmp_path, commit_every_batches=2)
    s = res.stats
    assert s.docs == N_DOCS
    assert s.batches == N_DOCS // BATCH
    assert s.bytes_written == N_DOCS * 32 * 4
    assert s.wall_seconds > 0 and s.compute_seconds > 0
    assert s.host_io_seconds > 0         # feeder time actually surfaced
    assert s.docs_per_second > 0
    assert 0.0 <= s.pad_waste_frac < 1.0
    assert 0.0 <= s.overlap_fraction <= 1.0
    merged = dataclasses.replace(s).merge(s)
    assert merged.docs == 2 * N_DOCS


def test_traced_ingest_spans_batches_and_commits(service, docs, tmp_path):
    """A traced run records one ``ingest.batch`` span per batch and one
    ``ingest.durable`` span per commit call, each with its phases, and
    writes the same bytes as an untraced run."""
    from repro.runtime.trace import Tracer

    class Counting(Ingestor):
        commit_calls = 0

        def _commit(self, *a, **kw):
            Counting.commit_calls += 1
            super()._commit(*a, **kw)

    plain = Ingestor(service, commit_every_batches=2).ingest(
        docs, tmp_path / "plain")
    tracer = Tracer()
    traced = Counting(service, commit_every_batches=2,
                      tracer=tracer).ingest(docs, tmp_path / "traced")
    assert _bin_bytes(tmp_path / "traced") == _bin_bytes(tmp_path / "plain")
    assert traced.stats.batches == plain.stats.batches == N_DOCS // BATCH
    spans = tracer.spans()
    batches = [s for s in spans if s["name"] == "ingest.batch"]
    durable = [s for s in spans if s["name"] == "ingest.durable"]
    assert len(batches) == traced.stats.batches
    assert len(durable) == Counting.commit_calls >= traced.stats.commits
    assert [s["attrs"]["batch"] for s in batches] == list(
        range(N_DOCS // BATCH))
    for s in batches:
        steps = [p[0] for p in s["attrs"]["phases"]
                 if p[0] not in ("compile", "lower")]
        assert steps == ["feed", "embed", "append"]
    assert all("commit" in {p[0] for p in s["attrs"]["phases"]}
               for s in durable)
    assert any("marker" in {p[0] for p in s["attrs"]["phases"]}
               for s in durable)
