#!/usr/bin/env python3
"""Chip smoke run: ScaleDoc's ingest -> filter -> serve -> judge path on a TPU.

    python chip_smoke.py              # one chip: phases (a)-(f)
    python chip_smoke.py --chips 4    # four chips: sharded ingest and
                                      # sharded scoring against one device

The backbone is smollm-360m at its published widths (32 layers, d_model
960, 15 heads with 5 KV heads, d_ff 2560, vocab 49152, bf16) with random
weights from the seed ``SEED``; the token documents, the queries and the
4096-d store are synthetic and made from the same seed. Every phase drives
the
entry points a user calls (``ScaleDocEngine.from_corpus``,
``ScaleDocEngine.filter``, ``PredicateServer``, ``LMOracle`` behind an
``OracleBroker``), prints what it checked on its own line and raises on
a mismatch. The last line of standard output is one JSON object naming
the device. Without a TPU the script exits non-zero before any phase.

  (a) device identity: platform, device_kind, device count.
  (b) offline phase: 4096 documents of 512 tokens through
      ``EmbeddingService`` into a ``MemmapStore``; every row finite, 8
      rows against a float32 forward of the same parameters.
  (c) online phase over that store with ``use_kernel=True``: a leaf and
      an AND/NOT compound; the fused kernel is the only scoring path,
      F1 meets the accuracy target, fused scores match the jnp path.
  (d) the same checks on one cold compound over a 100,000 x 4096 f32
      store with the ``ProxyConfig`` defaults (D=4096, H=512, L=128).
  (e) serving: 4 queries (two share a leaf) through ``PredicateServer``
      decide bitwise as serial ``filter()`` calls on fresh engines.
  (f) judge: ``LMOracle`` on the same backbone labels one leaf's
      ambiguous band through the broker; labels repeat, logits finite.

The phase functions take the configuration and the sizes, so
``tests/test_chip_smoke.py`` runs them on CPU at the smoke config with
the Pallas kernels in interpret mode.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import shutil
import sys
import tempfile
import time
from pathlib import Path
from typing import List, Optional

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.config import get_arch, replace  # noqa: E402
from repro.config.base import (CascadeConfig, ModelConfig,  # noqa: E402
                               ProxyConfig)
from repro.core.cascade import f1_score  # noqa: E402
from repro.core.encoder import encoder_init  # noqa: E402
from repro.core.oracle import (CachedOracle, LMOracle,  # noqa: E402
                               LMOracleConfig, SimulatedOracle)
from repro.data import make_corpus, make_query  # noqa: E402
from repro.engine import (InMemoryStore, ScaleDocEngine,  # noqa: E402
                          ScoringExecutor, SemanticPredicate, build_index)
from repro.kernels.contrastive import ops as contrastive_ops  # noqa: E402
from repro.launch.mesh import make_scoring_mesh  # noqa: E402
from repro.models import build_model  # noqa: E402
from repro.runtime import trace as trace_mod  # noqa: E402
from repro.runtime.compile_cache import enable_compile_cache  # noqa: E402
from repro.runtime.serve_loop import EmbeddingService  # noqa: E402
from repro.serve import OracleBroker, PredicateServer  # noqa: E402

ARCH = "smollm-360m"
SEED = 0
REF_ROWS = 8                         # store rows checked against f32
QUERY_TOKENS = 8                     # judge prompt prefix
FOUR = 4                             # devices of the --chips 4 phase
WORK_DIR = ROOT / ".smoke_work"      # git-ignored; removed after the run

# Largest relative L2 error of a pooled bf16 embedding against the
# float32 forward (precision "highest") of the same parameters. At the
# full 32-layer width a v5e shows 0.72%, the CPU backend 0.7%.
EMBED_REL_TOL = 3e-2
# Largest |fused - jnp| proxy score (scores lie in [0, 1]; the cascade's
# calibration bins are 1/64 wide).
SCORE_ATOL = 1e-2
# The --chips 4 comparisons use the tolerance of the CPU tests of the
# same paths (tests/test_ingest.py, tests/test_executor.py). Sharded
# ingest is held to it against one device at the batch each device runs;
# against one device at the full batch the bf16 backbone rounds
# differently (0.125 max |diff| on the chip), so there it is held to
# EMBED_REL_TOL per row.
SHARD_RTOL = SHARD_ATOL = 1e-6


@dataclasses.dataclass(frozen=True)
class Sizes:
    docs: int = 4096             # token documents ingested in (b)
    doc_len: int = 512
    ingest_batch: int = 32
    paper_docs: int = 100_000    # rows of the 4096-d store in (d)
    paper_dim: int = 4096
    chunk: int = 8192            # scoring tile (documents)
    judge_batch: int = 64        # documents per broker ask in (f)


CHIP_SIZES = Sizes()


class SmokeFailure(AssertionError):
    pass


def check(ok, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def say(phase: str, msg: str) -> None:
    print(f"[{phase}] {msg}", flush=True)


@dataclasses.dataclass
class Ingested:
    """What the offline phase hands to the online phases."""
    model_cfg: ModelConfig
    model: object
    params: dict
    corpus: object
    engine: ScaleDocEngine


# ---------------------------------------------------------------------------
# (a) device identity
# ---------------------------------------------------------------------------

def device_identity() -> dict:
    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    say("a", f"platform={dev['platform']} device_kind={dev['kind']} "
             f"count={dev['count']}")
    return dev


# ---------------------------------------------------------------------------
# (b) offline phase
# ---------------------------------------------------------------------------

def init_backbone(model_cfg: ModelConfig):
    # eager: jitting init unrolls all 32 layers' random draws into one
    # program that takes the chip's compiler about a minute
    model = build_model(model_cfg)
    return model, model.init(jax.random.PRNGKey(SEED))


def token_corpus(model_cfg: ModelConfig, sizes: Sizes):
    return make_corpus(SEED, n_docs=sizes.docs, dim=64, with_tokens=True,
                       vocab=model_cfg.vocab_size, doc_len=sizes.doc_len)


def f32_embeddings(model_cfg: ModelConfig, params, tokens: np.ndarray
                   ) -> np.ndarray:
    """Pooled embeddings from a float32 copy of the backbone at matmul
    precision "highest" — the reference the bf16 store is held to."""
    cfg32 = replace(model_cfg, dtype="float32")
    p32 = jax.tree.map(lambda x: x.astype(jnp.float32), params)
    svc = EmbeddingService(cfg32, p32, batch_size=len(tokens))
    with jax.default_matmul_precision("highest"):
        return np.asarray(svc.embed_batch(jnp.asarray(tokens)))


def phase_ingest(model_cfg: ModelConfig, sizes: Sizes, workdir: Path, *,
                 engine_kwargs: dict) -> Ingested:
    model, params = init_backbone(model_cfg)
    corpus = token_corpus(model_cfg, sizes)
    service = EmbeddingService(model_cfg, params,
                               batch_size=sizes.ingest_batch)
    t0 = time.perf_counter()
    jax.block_until_ready(service.embed_batch(
        jnp.zeros((sizes.ingest_batch, sizes.doc_len), jnp.int32)))
    warm_s = time.perf_counter() - t0
    engine = ScaleDocEngine.from_corpus(
        service, list(corpus.tokens), workdir / "store", **engine_kwargs)
    stats = engine.ingest_result.stats
    say("b", f"reading: warm-up (compile + 1 batch) {warm_s:.3f} s")
    say("b", f"reading: ingest {stats.docs} docs x {sizes.doc_len} "
             f"tokens in {stats.wall_seconds:.3f} s = "
             f"{stats.docs_per_second:.1f} docs/s")

    store = engine.store
    rows = store.get(np.arange(len(store)))
    check(rows.shape == (sizes.docs, model_cfg.d_model),
          f"store shape {rows.shape}")
    check(np.isfinite(rows).all(), "non-finite embedding rows")
    say("b", f"check: all {rows.shape[0]} x {rows.shape[1]} rows finite")

    pick = np.sort(np.random.default_rng(SEED).choice(
        sizes.docs, size=REF_ROWS, replace=False))
    ref = f32_embeddings(model_cfg, params, corpus.tokens[pick])
    rel = (np.linalg.norm(rows[pick] - ref, axis=1)
           / np.maximum(np.linalg.norm(ref, axis=1), 1e-12))
    check(np.isfinite(ref).all() and rel.max() <= EMBED_REL_TOL,
          f"store rows vs f32 forward: rel err {rel.max():.3e} > "
          f"{EMBED_REL_TOL}")
    say("b", f"check: {len(pick)} rows vs f32 forward (precision highest) "
             f"max rel L2 err {rel.max():.3e} <= {EMBED_REL_TOL}")
    return Ingested(model_cfg, model, params, corpus, engine)


# ---------------------------------------------------------------------------
# (c)/(d) online phase
# ---------------------------------------------------------------------------

def query_by_example(store, truth: np.ndarray, k: int = 4) -> np.ndarray:
    """Query embedding as the normalised mean of k positive rows."""
    e = store.get(np.nonzero(truth)[0][:k]).mean(axis=0)
    return (e / (np.linalg.norm(e) + 1e-9)).astype(np.float32)


class LoggedExecutor(ScoringExecutor):
    """The fused-kernel executor, noting for every scoring pass whether
    it scored trained proxies or raw cosine (the planner's selectivity
    estimate for untrained leaves, which has no MLP to fuse and runs on
    the jnp path) and which paths ran."""

    def __init__(self, **kwargs):
        super().__init__(use_kernel=True, **kwargs)
        self.passes: List[tuple] = []

    def score_multi(self, jobs, store):
        out, stats = super().score_multi(jobs, store)
        self.passes.append((all(p is None for p, _ in jobs), stats.paths))
        return out, stats

    def score(self, params, e_q, store):
        out, stats = super().score(params, e_q, store)
        if params is None:      # proxies go through score_multi
            self.passes.append((True, stats.paths))
        return out, stats


def kernel_engine(store, proxy_cfg: ProxyConfig, ccfg: CascadeConfig,
                  chunk: int, interpret: bool) -> ScaleDocEngine:
    ex = LoggedExecutor(chunk=chunk, interpret=interpret)
    return ScaleDocEngine(store, proxy_cfg, ccfg, chunk=chunk, executor=ex)


def check_filter(phase: str, label: str, engine: ScaleDocEngine, res,
                 leaves: dict, target: float) -> None:
    """The three online checks.

    * Path: every proxy pass runs the fused kernel alone; a cold compound
      adds one raw-cosine planning pass on the jnp path.
    * Accuracy: the accuracy target is the cascade's guarantee for each
      leaf, so every leaf's decisions over the documents it saw meet it
      against that leaf's truth (for a leaf query this is the query's
      F1). A compound's root F1 composes its leaves' errors; it is
      printed.
    * Scores: the fused scores match the jnp path's.

    ``leaves`` maps each leaf key to (query embedding, leaf truth)."""
    passes, engine.executor.passes = engine.executor.passes, []
    proxy = [p for is_raw, p in passes if not is_raw]
    raw = [p for is_raw, p in passes if is_raw]
    paths = res.scoring_stats.paths
    check(proxy and all(p == ("fused",) for p in proxy)
          and all(p == ("jnp",) for p in raw)
          and set(paths) == ({"fused", "jnp"} if raw else {"fused"}),
          f"{label}: scoring paths {paths}, proxy passes {proxy}, "
          f"raw-cosine passes {raw}")
    jnp_ex = ScoringExecutor(chunk=engine.executor.chunk)
    worst, leaf_f1 = 0.0, []
    for rep in res.leaf_reports:
        e_q, leaf_truth = leaves[rep.key]
        f1 = f1_score(rep.labels, leaf_truth[rep.pending])
        check(f1 >= target,
              f"{label}: leaf {rep.name} F1 {f1:.4f} < target {target}")
        leaf_f1.append(f"{rep.name}={f1:.4f}")
        if rep.scores is None:
            continue
        want, _ = jnp_ex.score(engine._proxies[rep.key], e_q, engine.store)
        worst = max(worst, float(np.abs(rep.scores
                                        - want[rep.pending]).max()))
    check(worst <= SCORE_ATOL,
          f"{label}: |fused - jnp| score {worst:.3e} > {SCORE_ATOL}")
    say(phase, f"check {label}: {len(proxy)} proxy passes fused, "
               f"{len(raw)} raw-cosine planning passes jnp, "
               f"paths={paths}; leaf F1 {' '.join(leaf_f1)} >= {target}, "
               f"root F1={res.achieved_f1:.4f}, "
               f"oracle_calls={res.oracle_calls_total}; "
               f"|fused-jnp|max={worst:.3e} <= {SCORE_ATOL}; "
               f"wall={res.wall_seconds:.3f} s")


def _pred(e_q, truth, name):
    return SemanticPredicate(e_q, SimulatedOracle(truth), name=name)


def phase_online(ing: Ingested, proxy_cfg: ProxyConfig,
                 ccfg: CascadeConfig):
    """Leaf and AND/NOT compound over the ingested store. Returns the
    leaf query's report, whose ambiguous band phase (f) judges."""
    engine, corpus = ing.engine, ing.corpus
    impl = engine.proxy_cfg.contrastive_impl
    check(contrastive_ops._use_kernel(impl),
          f"contrastive_impl={impl!r} resolves to the reference")
    q1 = make_query(corpus, SEED + 7, selectivity=0.3)
    q2 = make_query(corpus, SEED + 13, selectivity=0.4)
    e1 = query_by_example(engine.store, q1.truth)
    e2 = query_by_example(engine.store, q2.truth)

    leaf = _pred(e1, q1.truth, "p1")
    res = engine.filter(leaf, ground_truth=q1.truth, seed=SEED)
    check_filter("c", "leaf", engine, res,
                 {leaf.key: (e1, q1.truth)}, ccfg.accuracy_target)

    a, b = _pred(e1, q1.truth, "p1"), _pred(e2, q2.truth, "p2")
    truth = q1.truth & ~q2.truth
    res2 = engine.filter(a & ~b, ground_truth=truth, seed=SEED)
    check_filter("c", "p1 AND NOT p2", engine, res2,
                 {a.key: (e1, q1.truth), b.key: (e2, q2.truth)},
                 ccfg.accuracy_target)
    return res.leaf_reports[0]


def paper_corpus(sizes: Sizes):
    """The (d) store: topic-mixture embeddings at the paper's width, with
    the per-dimension noise scaled so the noise-to-signal ratio is that
    of make_corpus's default 256-d corpus. At 0.03 a dimension the
    4096-d noise outweighs the topics: raw cosine then ranks a planted
    query's positives with AUC 0.60 instead of 0.77."""
    return make_corpus(SEED + 1, n_docs=sizes.paper_docs,
                       dim=sizes.paper_dim,
                       noise=0.03 * (256 / sizes.paper_dim) ** 0.5)


def phase_paper_widths(sizes: Sizes, proxy_cfg: ProxyConfig,
                       ccfg: CascadeConfig, *,
                       interpret: bool = False) -> None:
    t0 = time.perf_counter()
    corpus = paper_corpus(sizes)
    say("d", f"store {corpus.embeds.shape} f32 "
             f"({corpus.embeds.nbytes / 1e9:.2f} GB) made in "
             f"{time.perf_counter() - t0:.3f} s")
    engine = kernel_engine(InMemoryStore(corpus.embeds), proxy_cfg, ccfg,
                           sizes.chunk, interpret)
    cfg = engine.proxy_cfg
    say("d", f"proxy D={cfg.embed_dim} H={cfg.hidden_dim} "
             f"L={cfg.latent_dim} contrastive_impl={cfg.contrastive_impl}")
    qa = make_query(corpus, SEED + 31, selectivity=0.3)
    qb = make_query(corpus, SEED + 32, selectivity=0.4)
    a, b = _pred(qa.embed, qa.truth, "a"), _pred(qb.embed, qb.truth, "b")
    truth = qa.truth & ~qb.truth
    res = engine.filter(a & ~b, ground_truth=truth, seed=SEED)
    check_filter("d", "cold a AND NOT b", engine, res,
                 {a.key: (qa.embed, qa.truth), b.key: (qb.embed, qb.truth)},
                 ccfg.accuracy_target)


# ---------------------------------------------------------------------------
# (e) serving
# ---------------------------------------------------------------------------

def phase_serving(ing: Ingested, proxy_cfg: ProxyConfig,
                  ccfg: CascadeConfig, *, chunk: int,
                  interpret: bool = False) -> None:
    store, corpus = ing.engine.store, ing.corpus
    qs = [make_query(corpus, SEED + 40 + i, selectivity=s)
          for i, s in enumerate((0.3, 0.4, 0.35, 0.25))]
    embeds = [query_by_example(store, q.truth) for q in qs]

    def workload():
        """[A, B, A AND NOT C, D]: queries 0 and 2 share leaf A. Fresh
        label caches per call, shared by that call's queries."""
        oracles = [CachedOracle(SimulatedOracle(q.truth)) for q in qs]
        p = [SemanticPredicate(e, o, name=n) for e, o, n
             in zip(embeds, oracles, "ABCD")]
        return [p[0], p[1], p[0] & ~p[2], p[3]]

    serial = [kernel_engine(store, proxy_cfg, ccfg, chunk, interpret)
              .filter(pred, seed=SEED).mask for pred in workload()]
    engine = kernel_engine(store, proxy_cfg, ccfg, chunk, interpret)
    t0 = time.perf_counter()
    with PredicateServer(engine, workers=4, optimize=True) as server:
        sessions = [server.submit(pred, seed=SEED) for pred in workload()]
        results = [s.result(timeout=900) for s in sessions]
    wall = time.perf_counter() - t0
    for i, (want, got) in enumerate(zip(serial, results)):
        check(np.array_equal(want, got.mask),
              f"served query {i} differs from serial filter()")
    say("e", f"check: 4 served queries bitwise equal to serial filter() "
             f"(accepted {[int(m.sum()) for m in serial]}, served in "
             f"{wall:.3f} s)")


# ---------------------------------------------------------------------------
# (f) judge
# ---------------------------------------------------------------------------

def ambiguous_band(report) -> np.ndarray:
    """Documents of a leaf that the cascade sent to the oracle."""
    band = np.isin(report.mech, (trace_mod.ORACLE, trace_mod.CACHED_LABEL))
    return report.pending[band]


def phase_judge(ing: Ingested, band: np.ndarray, sizes: Sizes) -> None:
    check(len(band) > 0, "the leaf has no ambiguous band to judge")
    rng = np.random.default_rng(SEED)
    query = rng.integers(3, ing.model_cfg.vocab_size,
                         size=QUERY_TOKENS).astype(np.int32)
    oracle = LMOracle(ing.model, ing.params, query, ing.corpus.tokens,
                      LMOracleConfig())
    broker = OracleBroker(max_batch=sizes.judge_batch, max_delay=0.002)
    jb = sizes.judge_batch

    def judge() -> np.ndarray:
        handle = broker.wrap_for()(CachedOracle(oracle))
        return np.concatenate([handle.label(band[i:i + jb])
                               for i in range(0, len(band), jb)])

    t0 = time.perf_counter()
    first = judge()
    wall = time.perf_counter() - t0
    second = judge()
    check(oracle.calls == 2 * len(band),
          f"judge calls {oracle.calls} != 2 x {len(band)}")
    check(np.array_equal(first, second), "judge labels differ across calls")

    prompts = np.stack([oracle._prompt(int(i)) for i in band[:jb]])
    last = jax.jit(lambda p, t: ing.model.forward(p, t)[0][:, -1])(
        ing.params, jnp.asarray(prompts))
    check(bool(jnp.isfinite(last).all()), "non-finite judge logits")
    say("f", f"check: {len(band)} band docs judged twice through the "
             f"broker, labels equal ({int(first.sum())} yes), last-position "
             f"logits finite; first pass {wall:.3f} s")


# ---------------------------------------------------------------------------
# --chips 4
# ---------------------------------------------------------------------------

def phase_four_chips(model_cfg: ModelConfig, sizes: Sizes,
                     workdir: Path) -> None:
    """Sharded ingest and sharded scoring against one device."""
    check(len(jax.devices()) == FOUR,
          f"{len(jax.devices())} devices, want {FOUR}")
    mesh = make_scoring_mesh(FOUR)
    model, params = init_backbone(model_cfg)
    docs = list(token_corpus(model_cfg, sizes).tokens)
    service = EmbeddingService(model_cfg, params,
                               batch_size=sizes.ingest_batch)
    local_bs = sizes.ingest_batch // FOUR
    single = build_index(service, docs, workdir / "single")
    local = build_index(EmbeddingService(model_cfg, params,
                                         batch_size=local_bs),
                        docs, workdir / "local")
    sharded = build_index(service, docs, workdir / "sharded", mesh=mesh)
    check(sharded.stats.devices == FOUR,
          f"sharded ingest ran on {sharded.stats.devices} devices")
    rows = np.arange(sizes.docs)
    a, b = local.store.get(rows), sharded.store.get(rows)
    err = float(np.abs(b - a).max())
    check(np.allclose(b, a, rtol=SHARD_RTOL, atol=SHARD_ATOL),
          f"sharded ingest differs from one device at batch {local_bs}: "
          f"max |diff| {err:.3e}")
    full = single.store.get(rows)
    rel = (np.linalg.norm(b - full, axis=1)
           / np.maximum(np.linalg.norm(full, axis=1), 1e-12))
    check(rel.max() <= EMBED_REL_TOL,
          f"sharded ingest vs one device at batch {sizes.ingest_batch}: "
          f"max rel L2 err {rel.max():.3e} > {EMBED_REL_TOL}")
    say("4", f"check: sharded ingest of {sizes.docs} docs on {FOUR} "
             f"devices matches one device at batch {local_bs} (max |diff| "
             f"{err:.3e}) and at batch {sizes.ingest_batch} (max rel L2 "
             f"err {rel.max():.3e} <= {EMBED_REL_TOL}); reading: "
             f"{single.stats.docs_per_second:.1f} vs "
             f"{sharded.stats.docs_per_second:.1f} docs/s")

    corpus = paper_corpus(sizes)
    store = InMemoryStore(corpus.embeds)
    proxy = encoder_init(jax.random.PRNGKey(SEED),
                         ProxyConfig(embed_dim=sizes.paper_dim))
    qa = make_query(corpus, SEED + 31)
    qb = make_query(corpus, SEED + 32)
    jobs = [(proxy, qa.embed), (proxy, qb.embed)]
    got, st = ScoringExecutor(chunk=sizes.chunk, mesh=mesh).score_multi(
        jobs, store)
    want, st1 = ScoringExecutor(chunk=sizes.chunk).score_multi(jobs, store)
    check(st.devices == FOUR and st.paths == ("shard",),
          f"sharded scoring stats devices={st.devices} paths={st.paths}")
    err = float(np.abs(got - want).max())
    check(np.allclose(got, want, rtol=SHARD_RTOL, atol=SHARD_ATOL),
          f"sharded scores differ from one device: max |diff| {err:.3e}")
    say("4", f"check: sharded scoring of {sizes.paper_docs} x "
             f"{sizes.paper_dim} docs, devices={st.devices} paths="
             f"{st.paths}, matches one device (max |diff| {err:.3e}); "
             f"reading: {st.wall_seconds:.3f} vs "
             f"{st1.wall_seconds:.3f} s")


# ---------------------------------------------------------------------------
# main
# ---------------------------------------------------------------------------

def run_one_chip(model_cfg: ModelConfig, sizes: Sizes, workdir: Path, *,
                 proxy_cfg: ProxyConfig, ccfg: CascadeConfig,
                 interpret: bool = False) -> None:
    """Phases (b)-(f) in order."""
    ing = phase_ingest(model_cfg, sizes, workdir,
                       engine_kwargs=dict(
                           proxy_cfg=proxy_cfg, cascade_cfg=ccfg,
                           chunk=sizes.chunk,
                           executor=LoggedExecutor(
                               chunk=sizes.chunk, interpret=interpret)))
    leaf_report = phase_online(ing, proxy_cfg, ccfg)
    phase_paper_widths(sizes, proxy_cfg, ccfg, interpret=interpret)
    phase_serving(ing, proxy_cfg, ccfg, chunk=sizes.chunk,
                  interpret=interpret)
    phase_judge(ing, ambiguous_band(leaf_report), sizes)


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the sharded ingest and sharded "
                         "scoring phases, against one device")
    args = ap.parse_args(argv)

    enable_compile_cache()
    device = device_identity()
    check(device["platform"] == "tpu",
          f"no TPU: JAX reports platform {device['platform']!r}")
    model_cfg = get_arch(ARCH)
    say("a", f"backbone {model_cfg.name}: {model_cfg.num_layers} layers, "
             f"d_model {model_cfg.d_model}, {model_cfg.num_heads} heads / "
             f"{model_cfg.num_kv_heads} KV, d_ff {model_cfg.d_ff}, vocab "
             f"{model_cfg.vocab_size}, {model_cfg.dtype}")
    WORK_DIR.mkdir(exist_ok=True)
    t0 = time.perf_counter()
    with tempfile.TemporaryDirectory(dir=WORK_DIR) as tmp:
        if args.chips == 4:
            phase_four_chips(model_cfg, CHIP_SIZES, Path(tmp))
        else:
            run_one_chip(model_cfg, CHIP_SIZES, Path(tmp),
                         proxy_cfg=ProxyConfig(), ccfg=CascadeConfig())
    shutil.rmtree(WORK_DIR, ignore_errors=True)
    say("done", f"all phases passed in {time.perf_counter() - t0:.3f} s")
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
