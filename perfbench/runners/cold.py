"""Cold compound queries: one client, closed loop, every leaf fresh.

The collection is the configuration's, made from its ``collection_seed``:
one fixed collection, as a deployment holds it, so that every seed asks
of the same data. Query ``i`` has one fresh leaf per entry of
``selectivity``, drawn from the run's seed and ``i``, combined by
``ops[i % len(ops)]``. The first query warms up every program the
window uses; the window opens at its end (with ``--trace 1``, once the
profiler runs) and closes at the last query that finished in time.
``query_s`` is the window over its queries. The check retrains
``train_check`` of the window's leaves, drawn from the seed, with the
reference trainer.
"""
from __future__ import annotations

import time


from perfbench import checks as chk
from perfbench import data, flops
from perfbench.runners.common import (Answered, Measured, Run,
                                      TrainerRecorder, compose, engine_for,
                                      log, predicate, query_answers,
                                      query_control, recording_executor,
                                      train_answers, train_check_leaves)
from perfbench.readings import Readings
from perfbench.spans import from_tracer
from perfbench.window import close_window


def make_query(store, run: Run, i: int):
    tr = run.traffic
    op = tr["ops"][i % len(tr["ops"])]
    leaves = [data.planted_leaf(store, run.seed, (i, j), s)
              for j, s in enumerate(tr["selectivity"])]
    oracles = [data.TruthOracle(leaf.truth) for leaf in leaves]
    preds = [predicate(leaf, o, f"q{i}.{j}")
             for j, (leaf, o) in enumerate(zip(leaves, oracles))]
    return op, leaves, oracles, preds


def measure(run: Run) -> Measured:
    from repro.runtime.trace import Tracer
    cfg, tr = run.config, run.traffic
    st = cfg["store"]
    store = data.topic_store(st["collection_seed"], st["n_docs"], st["embed_dim"],
                             st["n_topics"], st["topic_noise_at_256d"])
    queries = [make_query(store, run, i) for i in range(tr["max_queries"])]
    engine, executor = engine_for(cfg, store.embeds, recording_executor())
    pseed = data.program_seed(run.seed)
    tracer = Tracer(capacity=1 << 16) if run.trace else None
    view = engine.session_view(tracer=tracer, share_caches=True)

    def ask(i):
        op, leaves, oracles, preds = queries[i]
        result = view.filter(compose(op, *preds), seed=pseed)
        return Answered(op, leaves, [p.key for p in preds], result), \
            sum(o.calls for o in oracles)

    recorder = TrainerRecorder()
    try:
        ask(0)                               # warm-up: compiles every program
        dtrace, marks, answered, calls, t_open = _window(run, ask, len(queries))
    finally:
        recorder.close()
    setup_s = t_open - run.t0
    w = close_window(marks, (t_open, 0), run.seconds)
    inside = answered[:w.completions]
    end_to_end = {"setup_s": setup_s,
                  "query_s": w.seconds_per_completion,
                  "oracle_docs_per_query": sum(calls[:w.completions]) / w.completions}
    log(f"{w.completions} queries in {w.seconds:.3f} s")

    readings = None
    if dtrace:
        from perfbench import devtrace
        profile = devtrace.load(dtrace.stop())
        host = from_tracer(tracer.spans())
        per_query = len(tr["selectivity"]) * flops.cold_leaf_flops(
            cfg["proxy"], st["n_docs"])
        readings = Readings(window=w, chips=run.chips, peak=run.peak,
                            spans=host, counters={"flops_per_query": per_query},
                            device=devtrace.reduce(profile, dtrace.t_sync,
                                                   w.start, w.end, run.chips, host))
    trained = train_check_leaves(inside, tr["train_check"], run.seed)

    def check():
        nonlocal engine, view
        engine = view = None
        answers = query_answers(inside, executor, store.embeds)
        train = train_answers(trained, executor, recorder, store.embeds, cfg, pseed)
        return (chk.query_checks(answers, cfg["check_limits"])
                + chk.train_checks(train, cfg["check_limits"]))

    def control():
        return query_control(inside, executor, store.embeds, trained, cfg, pseed)

    return Measured(end_to_end, attempted=w.completions, failed=0,
                    readings=readings, check=check,
                    control=control)


def _window(run: Run, ask, n_queries: int):
    """Open the window (and the profiler, if tracing), then ask queries
    until one finishes past ``--seconds``."""
    dtrace = run.device_trace()
    if dtrace:
        dtrace.start()
    t_open = time.perf_counter()
    log(f"set-up {t_open - run.t0:.3f} s; window opens")
    marks, answered, calls = [], [], []
    for i in range(1, n_queries):
        a, c = ask(i)
        t = time.perf_counter()
        marks.append((t, i))
        answered.append(a)
        calls.append(c)
        if t > t_open + run.seconds:
            break
    else:
        log(f"the {n_queries} queries of the mix ran out before the window closed")
    return dtrace, marks, answered, calls, t_open
