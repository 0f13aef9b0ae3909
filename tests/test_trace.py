"""Observability plane: span trees, traceparent propagation, decision
provenance, the cost ledger, and the tracing-off bit-parity gate."""
import numpy as np
import pytest

from repro.config.base import CascadeConfig, ProxyConfig
from repro.core import SimulatedOracle
from repro.core.oracle import CachedOracle
from repro.data import make_corpus, make_query
from repro.engine import (InMemoryStore, ScaleDocEngine, SemanticPredicate)
from repro.gateway import GatewayClient, PredicateGateway, Tenant
from repro.runtime import trace as trace_mod
from repro.runtime.trace import (CostLedger, ProvenanceMap, Span,
                                 SpanContext, Tracer, make_traceparent,
                                 parse_traceparent)
from repro.serve import PredicateServer

N_DOCS, DIM = 800, 32


@pytest.fixture(scope="module")
def corpus():
    return make_corpus(0, n_docs=N_DOCS, dim=DIM)


@pytest.fixture(scope="module")
def cfgs():
    pcfg = ProxyConfig(embed_dim=DIM, hidden_dim=64, latent_dim=32,
                       proj_dim=16, phase1_steps=30, phase2_steps=30)
    return pcfg, CascadeConfig(accuracy_target=0.9)


def _engine(corpus, cfgs):
    pcfg, ccfg = cfgs
    return ScaleDocEngine(InMemoryStore(corpus.embeds), pcfg, ccfg)


def _workload(corpus):
    qs = [make_query(corpus, 100 + i, selectivity=0.3) for i in range(4)]
    sims = [SimulatedOracle(q.truth) for q in qs]
    cached = [CachedOracle(s) for s in sims]
    p = [SemanticPredicate(qs[i].embed, cached[i], name=f"p{i}")
         for i in range(4)]
    preds = [p[0], p[1] & ~p[2], p[3] | p[1], p[2]]
    oracles = {f"o{i}": cached[i] for i in range(4)}
    return oracles, preds


# -- traceparent propagation -------------------------------------------------


def test_traceparent_roundtrip():
    ctx = SpanContext("ab" * 16, "cd" * 8)
    header = make_traceparent(ctx)
    assert header == f"00-{'ab' * 16}-{'cd' * 8}-01"
    back = parse_traceparent(header)
    assert back == ctx
    assert back.trace_id == ctx.trace_id
    assert back.span_id == ctx.span_id


@pytest.mark.parametrize("bad", [
    None, "", "garbage", "00-short-cdcdcdcdcdcdcdcd-01",
    "00-" + "gg" * 16 + "-" + "cd" * 8 + "-01",     # non-hex
    "00-" + "0" * 32 + "-" + "cd" * 8 + "-01",      # all-zero trace id
    "00-" + "ab" * 16 + "-" + "0" * 16 + "-01",     # all-zero span id
    "00-" + "ab" * 16 + "-" + "cd" * 8,             # 3 parts
    42,
])
def test_traceparent_malformed_degrades_to_none(bad):
    assert parse_traceparent(bad) is None


# -- span tree mechanics -----------------------------------------------------


def test_ambient_nesting_and_well_formedness():
    tracer = Tracer()
    with tracer.span("root", kind="test") as root:
        trace_mod.annotate(color="red")
        with tracer.span("child") as child:
            trace_mod.add_event("tick", n=1)
            assert trace_mod.current_span() is child
        with tracer.span("sibling"):
            pass
    assert trace_mod.current_span() is None     # stack fully popped
    spans = tracer.spans(root.ctx.trace_id)
    assert [s["name"] for s in spans] == ["child", "sibling", "root"]
    by_name = {s["name"]: s for s in spans}
    # one trace, children parented on root, all closed, clocks monotonic
    assert {s["trace_id"] for s in spans} == {root.ctx.trace_id}
    assert by_name["child"]["parent_id"] == root.ctx.span_id
    assert by_name["sibling"]["parent_id"] == root.ctx.span_id
    assert by_name["root"]["parent_id"] is None
    for s in spans:
        assert s["end"] >= s["start"] >= 0.0
        assert s["duration"] >= 0.0
    assert by_name["root"]["attrs"]["color"] == "red"
    assert by_name["child"]["events"][0]["name"] == "tick"
    assert by_name["child"]["events"][0]["attrs"] == {"n": 1}


def test_span_error_annotation():
    tracer = Tracer()
    with pytest.raises(ValueError):
        with tracer.span("boom") as span:
            raise ValueError("nope")
    rec = tracer.spans(span.ctx.trace_id)[0]
    assert "ValueError" in rec["attrs"]["error"]
    assert rec["end"] >= rec["start"]           # closed despite the raise


def test_explicit_parent_and_links():
    tracer = Tracer()
    remote = SpanContext("ef" * 16, "12" * 8)
    with tracer.span("server", parent=remote) as server:
        assert server.ctx.trace_id == remote.trace_id
    with tracer.span("flush", parent=None) as flush:
        flush.link(server.ctx)
        assert flush.ctx.trace_id != remote.trace_id   # own root
    rec = tracer.spans(flush.ctx.trace_id)[0]
    assert rec["links"] == [{"trace_id": server.ctx.trace_id,
                             "span_id": server.ctx.span_id}]


def test_disabled_tracer_is_noop():
    tracer = Tracer(enabled=False)
    with tracer.span("a") as a:
        with tracer.span("b") as b:
            assert a is b                       # one shared no-op span
            assert a.ctx is None
            a.set(x=1).event("e")               # all chainable no-ops
            trace_mod.annotate(y=2)             # ambient no-ops too
            trace_mod.add_event("z")
    snap = tracer.snapshot()
    assert snap["enabled"] is False
    assert snap["recorded"] == 0 and snap["spans"] == []
    # the shared NULL_TRACER behaves identically
    with trace_mod.NULL_TRACER.span("c") as c:
        assert c.ctx is None


def test_flight_recorder_ring_bounds():
    tracer = Tracer(capacity=8)
    for i in range(20):
        with tracer.span(f"s{i}"):
            pass
    snap = tracer.snapshot()
    assert snap["recorded"] == 20
    assert snap["retained"] == 8
    assert snap["dropped"] == 12
    assert [s["name"] for s in snap["spans"]] == [
        f"s{i}" for i in range(12, 20)]
    tracer.reset()
    assert tracer.snapshot()["recorded"] == 0


def test_chrome_trace_export_shape():
    tracer = Tracer()
    with tracer.span("outer") as outer:
        with tracer.span("inner"):
            pass
    doc = tracer.chrome_trace(outer.ctx.trace_id)
    assert set(doc) >= {"traceEvents", "displayTimeUnit"}
    events = doc["traceEvents"]
    assert {e["ph"] for e in events} >= {"X"}
    for e in events:
        if e["ph"] == "X":
            assert e["dur"] >= 0 and "ts" in e and "name" in e


# -- phases, thread CPU and compiles -----------------------------------------

def test_phases_nest_inside_their_span_in_order_with_cpu():
    tracer = Tracer()
    with tracer.span("outer") as outer:
        with outer.phase("pad"):
            sum(range(20000))
        with trace_mod.phase("run"):          # ambient: lands on outer
            sum(range(20000))
    rec = tracer.spans()[0]
    assert [p[0] for p in rec["attrs"]["phases"]] == ["pad", "run"]
    assert rec["attrs"]["cpu_s"] >= 0
    events = tracer.chrome_trace()["traceEvents"]
    span = next(e for e in events if e["name"] == "outer")
    assert "phases" not in span["args"]
    phases = [e for e in events if e["cat"] == "scaledoc.phase"]
    assert [e["name"] for e in phases] == ["pad", "run"]
    eps = 1e-3                                  # microseconds of rounding
    for e in phases:
        assert e["ph"] == "X" and e["tid"] == span["tid"]
        assert span["ts"] <= e["ts"]
        assert e["ts"] + e["dur"] <= span["ts"] + span["dur"] + eps
        assert e["args"]["cpu_s"] >= 0
    assert phases[0]["ts"] + phases[0]["dur"] <= phases[1]["ts"] + eps


def test_tally_adds_to_the_ambient_span_and_nothing_without_one():
    trace_mod.tally(h2d_bytes=5)                   # no span: no-op
    tracer = Tracer()
    with tracer.span("train"):
        trace_mod.tally(h2d_bytes=3)
        trace_mod.tally(h2d_bytes=4, blocks=1)
    with Tracer(enabled=False).span("train"):
        trace_mod.tally(h2d_bytes=9)
    assert tracer.spans()[0]["attrs"]["h2d_bytes"] == 7
    assert tracer.spans()[0]["attrs"]["blocks"] == 1
    assert len(tracer.spans()) == 1


def test_disabled_phases_are_one_singleton_and_read_no_clock(monkeypatch):
    import threading
    import time
    me, reads = threading.get_ident(), []

    def counted(real, name):
        def clock():
            if threading.get_ident() == me:    # other threads' reads aside
                reads.append(name)
            return real()
        return clock
    for name in ("perf_counter", "thread_time"):
        monkeypatch.setattr(time, name, counted(getattr(time, name), name))
    assert trace_mod.current_span() is None
    bare = trace_mod.phase("stall")
    with trace_mod.NULL_TRACER.span("score") as span:
        held = span.phase("sync")
        ambient = trace_mod.phase("sync")
        with held, ambient, bare:
            pass
    assert bare is held is ambient is trace_mod.NOOP_PHASE
    assert reads == []
    with Tracer().span("on") as on, on.phase("p"):   # the counter counts
        pass
    assert {"perf_counter", "thread_time"} <= set(reads)


def test_a_new_jit_shape_leaves_a_compile_phase():
    import jax
    import jax.numpy as jnp
    tracer = Tracer()
    with tracer.span("work"):
        jax.jit(lambda x: x * 3 + 1)(jnp.ones(23)).block_until_ready()
    phases = tracer.spans()[0]["attrs"]["phases"]
    compiles = [p for p in phases if p[0] == "compile"]
    assert compiles and all(p[2] >= p[1] and p[3] is None
                            for p in compiles)
    assert "lower" in {p[0] for p in phases}
    # under a disabled tracer there is no span to hold a phase: the
    # compile is counted by the enabled tracers, and recorded nowhere
    off = Tracer(enabled=False)
    before = tracer.snapshot()["compiles_outside_spans"]
    with off.span("work"):
        jax.jit(lambda x: x * 5 - 1)(jnp.ones(29)).block_until_ready()
    assert off.spans() == [] and off.snapshot()["recorded"] == 0
    assert tracer.snapshot()["compiles_outside_spans"] > before
    assert len(tracer.spans()) == 1


def test_each_program_carries_its_named_scope():
    """The proxy training step, the scoring chunk programs and the
    backbone forward keep a stable name in their lowered text."""
    import jax
    import jax.numpy as jnp
    from repro.config.base import ModelConfig
    from repro.core import scoring, trainer
    from repro.core.encoder import encoder_init
    from repro.models import build_model
    from repro.runtime.serve_loop import EmbeddingService
    pcfg = ProxyConfig(embed_dim=8, hidden_dim=16, latent_dim=8,
                       proj_dim=4, phase1_steps=2, phase2_steps=2,
                       batch_size=4)
    params = encoder_init(jax.random.PRNGKey(0), pcfg)
    block, z = jnp.ones((16, 8)), jnp.ones((8,))
    fn = trainer._compiled_trainer(pcfg, trainer._proxy_opt_cfg(pcfg),
                                   "two_phase", 4, multi=False,
                                   donate=False)
    mcfg = ModelConfig(name="scope-test", num_layers=1, d_model=16,
                       num_heads=2, num_kv_heads=2, d_ff=32, vocab_size=32,
                       dtype="float32", remat="none")
    service = EmbeddingService(mcfg, build_model(mcfg).init(
        jax.random.PRNGKey(0)), batch_size=2)
    lowered = {
        "proxy_train_step": fn.lower(params, jax.random.PRNGKey(1), z,
                                     block, jnp.ones(16), 16),
        "proxy_score_chunk": scoring._single_chunk_scores.lower(
            params, block, jnp.ones(pcfg.latent_dim)),
        "backbone_forward": service._embed.lower(
            service.params, jnp.ones((2, 8), jnp.int32)),
    }
    for scope, low in lowered.items():
        assert scope in low.as_text(debug_info=True), scope
    for fn in (scoring._proxy_chunk_scores, scoring._raw_chunk_scores):
        args = ((params, block, jnp.ones((pcfg.latent_dim, 2)))
                if fn is scoring._proxy_chunk_scores
                else (block, jnp.ones((8, 2))))
        assert "proxy_score_chunk" in fn.lower(*args).as_text(
            debug_info=True)


def test_traced_filter_records_every_phase_and_decides_the_same(corpus,
                                                                cfgs):
    _, preds = _workload(corpus)
    untraced = _engine(corpus, cfgs).filter(preds[1], seed=1)
    _, preds = _workload(corpus)            # fresh oracles
    engine = _engine(corpus, cfgs)
    engine._tracer = Tracer()
    traced = engine.filter(preds[1], seed=1)
    np.testing.assert_array_equal(untraced.mask, traced.mask)
    for a, b in zip(untraced.leaf_reports, traced.leaf_reports):
        np.testing.assert_array_equal(a.scores, b.scores)
    want = {"train": {"sample", "label", "rebalance", "pad", "put", "run"},
            "score": {"stall", "sync"},
            "decide": {"threshold", "known", "need", "label", "merge"}}
    spans = engine._tracer.spans()
    for name, phases in want.items():
        got = {p[0] for s in spans if s["name"] == name
               for p in s["attrs"].get("phases", ())}
        assert phases <= got, (name, got)
    assert all("cpu_s" in s["attrs"] for s in spans)
    train = next(s for s in spans if s["name"] == "train")
    for _, start, end, _ in train["attrs"]["phases"]:
        assert train["start"] <= start <= end <= train["end"]


def test_traced_train_counts_only_the_real_rows_it_copies(corpus, cfgs,
                                                         monkeypatch):
    """The ``train`` span's ``h2d_bytes`` is the two real lanes' rows,
    plus at most one block a lane, and no filler lane: at most half of
    the four-lane padded stack. Blocks smaller than the sample change no
    decision."""
    from repro.core import trainer
    from repro.engine.engine import TRAIN_BATCH_PAD
    _, preds = _workload(corpus)
    untraced = _engine(corpus, cfgs).filter(preds[1], seed=1)
    block = 16
    monkeypatch.setattr(trainer, "ROW_BLOCK", block)
    rows = []

    def spy(*args):
        out = real_rebalance(*args)
        rows.append(len(out[0]))
        return out

    real_rebalance = trainer.rebalance
    monkeypatch.setattr(trainer, "rebalance", spy)
    _, preds = _workload(corpus)
    engine = _engine(corpus, cfgs)
    engine._tracer = Tracer()
    traced = engine.filter(preds[1], seed=1)
    np.testing.assert_array_equal(untraced.mask, traced.mask)
    for a, b in zip(untraced.leaf_reports, traced.leaf_reports):
        np.testing.assert_array_equal(a.scores, b.scores)
    train = next(s for s in engine._tracer.spans() if s["name"] == "train")
    row_bytes = DIM * 4
    assert len(rows) == 2
    real = sum(rows) * row_bytes
    assert real <= train["attrs"]["h2d_bytes"] <= real + 2 * block * row_bytes
    stack = TRAIN_BATCH_PAD * trainer._bucket(max(rows)) * row_bytes
    assert train["attrs"]["h2d_bytes"] <= stack // 2


# -- provenance map ----------------------------------------------------------


def test_provenance_map_payload_and_completeness():
    class_of = np.array([trace_mod.PROXY_ACCEPT, trace_mod.PROXY_REJECT,
                         trace_mod.ORACLE, trace_mod.CACHED_LABEL],
                        dtype=np.int8)
    leaf_of = np.array([0, 0, 1, 1], dtype=np.int16)
    prov = ProvenanceMap(class_of=class_of, leaf_of=leaf_of,
                         leaf_names=["p0", "p1"])
    assert prov.complete()
    counts = prov.counts()
    assert sum(counts.values()) == 4
    assert counts["proxy_accept"] == 1 and counts["oracle"] == 1
    assert list(prov.docs_in("oracle")) == [2]
    payload = prov.to_payload(mask=np.array([1, 0, 1, 0], bool))
    assert payload["n_docs"] == 4 and payload["complete"] is True
    assert payload["accepted_count"] == 2
    assert payload["class_of"] == class_of.tolist()
    assert payload["leaves"] == ["p0", "p1"]
    assert set(payload["legend"]) >= {"proxy_accept", "oracle"}
    slim = prov.to_payload(include_docs=False)
    assert "class_of" not in slim and "leaf_of" not in slim


def test_provenance_incomplete_when_unclassified():
    """UNRESOLVED is a legitimate class (degraded defer); only the
    UNCLASSIFIED sentinel (-1) makes a map incomplete."""
    parked = np.full(3, trace_mod.UNRESOLVED, dtype=np.int8)
    prov = ProvenanceMap(class_of=parked,
                         leaf_of=np.zeros(3, np.int16), leaf_names=["p"])
    assert prov.complete()
    assert prov.counts() == {"unresolved": 3}

    blank = np.full(3, trace_mod.UNCLASSIFIED, dtype=np.int8)
    prov = ProvenanceMap(class_of=blank,
                         leaf_of=np.zeros(3, np.int16), leaf_names=["p"])
    assert not prov.complete()
    assert prov.to_payload()["complete"] is False
    assert prov.counts() == {"unclassified": 3}


# -- cost ledger -------------------------------------------------------------


def test_cost_ledger_attribution_and_defaults():
    ledger = CostLedger()
    ledger.record_session(
        session_id="q-1", tenant=None, name="p0", trace_id="t" * 32,
        leaves=[{"leaf": "p0", "oracle_docs_train": 80,
                 "oracle_docs_calib": 30, "oracle_docs_online": 10,
                 "proxy_flops": 1e9, "reused": False,
                 "cse_saved_docs": 0}],
        wall_seconds=1.5, degraded=False)
    ledger.record_session(
        session_id="q-2", tenant="acme", name="p0", trace_id="u" * 32,
        leaves=[{"leaf": "p0", "oracle_docs_train": 0,
                 "oracle_docs_calib": 0, "oracle_docs_online": 5,
                 "proxy_flops": 0.0, "reused": True,
                 "cse_saved_docs": 80}],
        wall_seconds=0.5, degraded=True)
    snap = ledger.snapshot()
    public = snap["tenants"]["public"]          # tenant None -> "public"
    assert public["oracle_docs"] == 120
    assert public["oracle_docs_train"] == 80
    assert public["oracle_flops"] == pytest.approx(120 * 50e12)
    acme = snap["tenants"]["acme"]
    assert acme["oracle_docs"] == 5
    assert acme["cse_reuses"] == 1 and acme["cse_saved_docs"] == 80
    assert acme["cse_saved_flops"] == pytest.approx(80 * 50e12)
    assert acme["degraded_sessions"] == 1
    assert snap["leaves"]["p0"]["sessions"] == 2
    recent = snap["recent_sessions"]
    assert [r["session"] for r in recent] == ["q-1", "q-2"]
    assert ledger.tenant_totals(None)["sessions"] == 1
    assert ledger.tenant_totals("missing")["sessions"] == 0


def test_cost_ledger_retry_waste_charges_infra():
    ledger = CostLedger()
    ledger.record_retry_waste(40, retries=3)
    snap = ledger.snapshot()
    infra = snap["tenants"]["_infra"]
    assert infra["retry_waste_docs"] == 40
    assert snap["tenants"].keys() == {"_infra"}


# -- engine-level: span tree + provenance for one filter ---------------------


def test_filter_emits_rooted_tree_and_complete_provenance(corpus, cfgs):
    oracles, preds = _workload(corpus)
    engine = _engine(corpus, cfgs)
    tracer = Tracer()
    engine._tracer = tracer
    result = engine.filter(preds[1], seed=1)    # compound: p1 & ~p2

    # -- provenance: every doc in exactly one class, bitwise-consistent
    prov = result.provenance
    assert prov is not None and prov.complete()
    counts = prov.counts()
    assert sum(counts.values()) == result.n_docs == N_DOCS
    mask = np.asarray(result.mask, bool)
    acc = prov.class_of == trace_mod.PROXY_ACCEPT
    rej = prov.class_of == trace_mod.PROXY_REJECT
    assert np.all(mask[acc])
    assert not np.any(mask[rej])
    # oracle-decided docs exist for a fresh compound query
    assert counts.get("oracle", 0) + counts.get("cached_label", 0) > 0

    # -- span tree: single root, every span closed + parented, monotonic
    spans = tracer.spans()
    assert spans, "filter recorded no spans"
    tid = spans[0]["trace_id"]
    assert {s["trace_id"] for s in spans} == {tid}
    roots = [s for s in spans if s["parent_id"] is None]
    assert [s["name"] for s in roots] == ["engine.filter"]
    ids = {s["span_id"] for s in spans}
    for s in spans:
        assert s["end"] >= s["start"]
        if s["parent_id"] is not None:
            assert s["parent_id"] in ids
    names = {s["name"] for s in spans}
    assert "plan" in names and "train" in names
    assert any(n.startswith("leaf:") for n in names)
    assert "score" in names and "decide" in names

    # charged accounting reconciles with the oracle cache exactly
    charged = sum(r.oracle_docs_charged + r.oracle_calls_train
                  for r in result.leaf_reports)
    purchased = sum(o.stats()["docs_purchased"] for o in oracles.values())
    assert charged == purchased


# -- server + gateway e2e ----------------------------------------------------


def test_http_propagation_e2e_four_clients(corpus, cfgs):
    """Acceptance gate: 4 remote clients, compound workload — one rooted
    span tree per session spanning gateway -> server -> engine -> broker,
    /explain classifies 100% of docs bitwise-consistently, and the
    ledger's per-tenant oracle-doc totals equal the broker's purchase
    counters."""
    oracles, preds = _workload(corpus)
    wires = [p.to_wire(oracles) for p in preds]
    tenants = [Tenant("t0", "k-0"), Tenant("t1", "k-1")]
    caller = SpanContext("ab" * 16, "cd" * 8)

    with PredicateServer(_engine(corpus, cfgs), workers=2) as server:
        with PredicateGateway(server, oracles, tenants=tenants) as gw:
            clients = [GatewayClient(gw.url, api_key="k-0"),
                       GatewayClient(gw.url, api_key="k-1")]
            sids = []
            for i, wire in enumerate(wires):
                kw = {"trace_ctx": caller} if i == 0 else {}
                sub = clients[i % 2].submit(wire, seed=i, **kw)
                assert sub["trace_id"], sub
                if i == 0:       # caller's context wins end to end
                    assert sub["trace_id"] == caller.trace_id
                sids.append(sub["id"])
            for i, sid in enumerate(sids):
                clients[i % 2].wait(sid, timeout=300, interval=0.1)

            # status round-trips the trace id
            assert (clients[0].status(sids[0])["trace_id"]
                    == caller.trace_id)

            # /explain: complete, classes sum to n_docs, bitwise-agree
            for i, sid in enumerate(sids):
                ex = clients[i % 2].explain(sid)
                assert ex["complete"] is True
                assert sum(ex["counts"].values()) == ex["n_docs"] == N_DOCS
                res = server.get_session(sid).result()
                mask = np.asarray(res.mask, bool)
                class_of = np.asarray(ex["class_of"], np.int8)
                assert np.all(mask[class_of == trace_mod.PROXY_ACCEPT])
                assert not np.any(mask[class_of == trace_mod.PROXY_REJECT])
                assert ex["accepted_count"] == int(mask.sum())

            # one rooted tree per session, gateway->server->engine kinds
            for i, sid in enumerate(sids):
                tid = clients[i % 2].status(sid)["trace_id"]
                spans = server.tracer.spans(tid)
                kinds = {s["attrs"].get("kind") for s in spans}
                assert {"gateway", "server", "engine"} <= kinds
                ids = {s["span_id"] for s in spans}
                n_roots = 0
                for s in spans:
                    assert s["end"] >= s["start"]
                    if s["parent_id"] is None or s["parent_id"] not in ids:
                        # the only out-of-tree parent allowed is the
                        # remote caller's span id (session 0)
                        if s["parent_id"] not in (None, caller.span_id):
                            pytest.fail(f"orphan span {s['name']}")
                        n_roots += 1
                assert n_roots == 1, f"session {i}: {n_roots} roots"
                assert any(s["name"] == "broker.request" for s in spans)

            # oracle flush spans are their own roots, linked back to
            # the contributing sessions
            flushes = [s for s in server.tracer.spans()
                       if s["name"] == "oracle.flush"]
            assert flushes
            assert any(f["links"] for f in flushes)

            # /v1/traces over HTTP mirrors the in-process tracer
            tr = clients[0].traces(trace_id=caller.trace_id)
            assert {s["name"] for s in tr["spans"]} == {
                s["name"] for s in server.tracer.spans(caller.trace_id)}
            chrome = clients[0].traces(trace_id=caller.trace_id,
                                       chrome=True)
            assert chrome["traceEvents"]

            # prometheus exposition of the same counters
            text = clients[0].metrics_prometheus()
            assert "# TYPE scaledoc_sessions_done counter" in text
            assert "scaledoc_session_latency_seconds_count" in text

            # ledger == broker purchase counters, per tenant and total
            m = clients[0].metrics()
            ledger = m["cost_ledger"]
            assert set(ledger["tenants"]) == {"t0", "t1"}
            total = sum(t["oracle_docs"]
                        for t in ledger["tenants"].values())
            assert total == int(m["oracle_cache"]["docs_purchased"])


def test_tracing_disabled_bitwise_parity(corpus, cfgs):
    """Tracing off must be decision-invariant: the same workload through
    a PredicateServer(trace=False) produces bitwise-identical masks, and
    records nothing."""
    oracles, preds = _workload(corpus)
    serial = [_engine(corpus, cfgs).filter(p, seed=i).mask
              for i, p in enumerate(preds)]

    oracles, preds = _workload(corpus)      # fresh oracles
    with PredicateServer(_engine(corpus, cfgs), workers=2,
                         trace=False) as server:
        sessions = [server.submit(p, seed=i)
                    for i, p in enumerate(preds)]
        masks = [s.result(timeout=300).mask for s in sessions]
        assert not server.tracer.enabled
        assert server.tracer.snapshot()["recorded"] == 0
        for s in sessions:
            assert s.trace_id is None
    for ref, got in zip(serial, masks):
        np.testing.assert_array_equal(ref, got)


def test_explain_errors(corpus, cfgs):
    oracles, preds = _workload(corpus)
    with PredicateServer(_engine(corpus, cfgs), workers=1) as server:
        with pytest.raises(KeyError):
            server.explain("nope")
        session = server.submit(preds[0], seed=0)
        session.result(timeout=300)
        payload = server.explain(session.id, include_docs=False)
        assert payload["complete"] is True and "class_of" not in payload
