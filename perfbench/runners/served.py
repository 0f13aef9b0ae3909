"""Shared-leaf traffic through ``PredicateServer``: several tenants,
closed loop, two-leaf compounds.

Every query takes one leaf from a hot pool that set-up evaluates over
the whole collection, as a running server would hold it. The collection
(the configuration's ``collection_seed``) and the hot pool
(``hot_pool_seed``) are fixed, as a running deployment's are; the run's
seed draws the fresh leaves and which hot leaves each query takes. Query ``i``
(global submission order) carries one fresh leaf when ``i %
fresh_every == 0`` and two hot leaves otherwise, combined by ``ops[i %
len(ops)]``: the mix is fixed by the index, not drawn. The window opens
at the ``warm_completions``-th completion and closes at the last
completion in time; ``served_query_s`` is the mean latency, submission
to result, of the queries completed inside it. The check compares
``check_sample`` of those queries, drawn from the seed, and retrains
``train_check`` of their leaves (fresh ones first) with the reference
trainer.
"""
from __future__ import annotations

import threading
import time

import numpy as np

from perfbench import checks as chk
from perfbench import data
from perfbench.runners.common import (Answered, Measured, Run,
                                      TrainerRecorder, compose, engine_for,
                                      log, predicate, query_answers,
                                      query_control, recording_executor,
                                      train_answers, train_check_leaves)
from perfbench.readings import Readings
from perfbench.spans import from_tracer
from perfbench.window import close_window

HOT = 1 << 20      # tag of the hot pool's leaves; fresh leaves use their index


def measure(run: Run) -> Measured:
    from repro.runtime.trace import Tracer
    from repro.serve import PredicateServer
    cfg, tr = run.config, run.traffic
    st = cfg["store"]
    store = data.topic_store(st["collection_seed"], st["n_docs"], st["embed_dim"],
                             st["n_topics"], st["topic_noise_at_256d"])
    lo, hi = tr["selectivity"]
    hot_rng = np.random.default_rng(np.random.SeedSequence([tr["hot_pool_seed"], 11]))
    hot = [data.planted_leaf(store, tr["hot_pool_seed"], (HOT, j),
                             float(hot_rng.uniform(lo, hi)))
           for j in range(tr["hot_pool"])]
    hot_oracles = [data.TruthOracle(leaf.truth) for leaf in hot]
    hot_preds = [predicate(leaf, o, f"hot{j}")
                 for j, (leaf, o) in enumerate(zip(hot, hot_oracles))]
    every, n_max = tr["fresh_every"], tr["max_queries"]
    rng = np.random.default_rng(np.random.SeedSequence([run.seed, 11]))
    fresh = {i: data.planted_leaf(store, run.seed, (i, 0), float(rng.uniform(lo, hi)))
             for i in range(0, n_max, every)}
    picks = rng.integers(0, len(hot), size=(n_max, 2))

    def make_query(i):
        op = tr["ops"][i % len(tr["ops"])]
        a = picks[i, 0]
        if i in fresh:
            oracle = data.TruthOracle(fresh[i].truth)
            leaves = [fresh[i], hot[a]]
            preds = [predicate(fresh[i], oracle, f"q{i}"), hot_preds[a]]
        else:
            b = (a + 1 + picks[i, 1] % (len(hot) - 1)) % len(hot)
            oracle = None
            leaves = [hot[a], hot[b]]
            preds = [hot_preds[a], hot_preds[b]]
        return op, leaves, oracle, preds

    queries = [make_query(i) for i in range(n_max)]
    engine, executor = engine_for(cfg, store.embeds, recording_executor())
    pseed = data.program_seed(run.seed)
    tracer = Tracer(capacity=1 << 18) if run.trace else None
    recorder = TrainerRecorder()
    server = PredicateServer(engine, optimize=True, tracer=tracer,
                             queue_depth=max(32, 2 * tr["clients"]))
    try:
        # set-up: the hot pool, each leaf over the whole collection
        for s in [server.submit(p, seed=pseed, block=True) for p in hot_preds]:
            s.result()
        dtrace = run.device_trace()
        if dtrace:
            dtrace.start()
        log(f"hot pool of {len(hot)} ready at {time.perf_counter() - run.t0:.3f} s")

        lock = threading.Lock()
        state = {"next": 0, "deadline": None, "done": [], "opening": None}
        errors = []

        def client():
            try:
                while True:
                    with lock:
                        i = state["next"]
                        deadline = state["deadline"]
                        if i >= n_max or (deadline is not None
                                          and time.perf_counter() > deadline):
                            return
                        state["next"] += 1
                    op, leaves, oracle, preds = queries[i]
                    t_sub = time.perf_counter()
                    sess = server.submit(compose(op, *preds), seed=pseed,
                                         block=True)
                    res = sess.result()
                    t_done = time.perf_counter()
                    with lock:
                        hot_calls = sum(o.calls for o in hot_oracles)
                        state["done"].append(dict(
                            i=i, t_sub=t_sub, t_done=t_done, session=sess.id,
                            calls=oracle.calls if oracle else 0,
                            hot_calls=hot_calls,
                            answer=Answered(op, leaves, [p.key for p in preds], res)))
                        if len(state["done"]) == tr["warm_completions"]:
                            state["opening"] = state["done"][-1]
                            state["deadline"] = t_done + run.seconds
            except BaseException as exc:
                errors.append(exc)

        threads = [threading.Thread(target=client, name=f"tenant-{k}")
                   for k in range(tr["clients"])]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        if errors:
            raise errors[0]
    finally:
        server.shutdown()
        recorder.close()

    done = sorted(state["done"], key=lambda d: d["t_done"])
    opening = state["opening"]
    setup_s = opening["t_done"] - run.t0
    w = close_window([(d["t_done"], k + 1) for k, d in enumerate(done)],
                     (opening["t_done"], done.index(opening) + 1), run.seconds)
    inside = [d for d in done if w.start < d["t_done"] <= w.end]
    hot_delta = inside[-1]["hot_calls"] - opening["hot_calls"]
    end_to_end = {
        "setup_s": setup_s,
        "served_query_s": float(np.mean([d["t_done"] - d["t_sub"] for d in inside])),
        "oracle_docs_per_query": (sum(d["calls"] for d in inside) + hot_delta)
        / len(inside)}
    log(f"{len(inside)} queries in {w.seconds:.3f} s")

    readings = None
    if dtrace:
        from perfbench import devtrace
        profile = devtrace.load(dtrace.stop())
        host = from_tracer(tracer.spans())
        readings = Readings(
            window=w, chips=run.chips, peak=run.peak, spans=host,
            counters={"submitted_at": {d["session"]: d["t_sub"] for d in inside}},
            device=devtrace.reduce(profile, dtrace.t_sync, w.start, w.end,
                                   run.chips, host))

    pick = np.random.default_rng(np.random.SeedSequence([run.seed, 13])).permutation(
        len(inside))[:tr["check_sample"]]
    sample = [inside[k]["answer"] for k in sorted(pick)]
    trained = train_check_leaves(sample, tr["train_check"], run.seed,
                                 last=lambda leaf: leaf.tag[0] == HOT)

    def check():
        nonlocal engine, server
        engine = server = None
        answers = query_answers(sample, executor, store.embeds)
        train = train_answers(trained, executor, recorder, store.embeds, cfg, pseed)
        return (chk.query_checks(answers, cfg["check_limits"])
                + chk.train_checks(train, cfg["check_limits"]))

    def control():
        return query_control(sample, executor, store.embeds, trained, cfg, pseed)

    return Measured(end_to_end, attempted=len(inside), failed=0,
                    readings=readings, check=check,
                    control=control)
