"""Offline ingest through the program's ``Ingestor`` into a fresh
durable store: the program's default commit cadence, fsyncs and
markers, on a directory inside the checkout.

The window opens at the first commit and closes at the last commit that
completed within ``--seconds`` of it; ``ingest_docs_per_s`` is the rows
those commits made durable over the time between the two. The job is
stopped at the first commit past the close, exactly as a kill there
would leave it.
"""
from __future__ import annotations

import shutil
import time

import numpy as np

from perfbench import checks as chk
from perfbench import data, flops
from perfbench.runners.common import Measured, Run, log
from perfbench.readings import Readings
from perfbench.spans import HostSpan
from perfbench.weights import llama_params
from perfbench.window import close_window


class WindowClosed(Exception):
    pass


def model_config(cfg: dict):
    """The program's ModelConfig for a Llama-architecture configuration."""
    from repro.config.base import BLOCK_ATTN, ModelConfig
    d, nq = cfg["hidden_size"], cfg["num_attention_heads"]
    return ModelConfig(
        name=cfg["name"], family="dense", num_layers=cfg["num_hidden_layers"],
        d_model=d, num_heads=nq, num_kv_heads=cfg["num_key_value_heads"],
        head_dim=cfg.get("head_dim") or d // nq, d_ff=cfg["intermediate_size"],
        vocab_size=cfg["vocab_size"], rope_theta=cfg["rope_theta"],
        norm_eps=cfg["rms_norm_eps"], tie_embeddings=cfg["tie_word_embeddings"],
        act=cfg["hidden_act"], dtype=cfg["torch_dtype"],
        block_pattern=(BLOCK_ATTN,))


def timed_ingestor(seconds: float):
    """The program's Ingestor, marking the host time, the durable rows and
    the writer's seconds at every commit, and stopping the job at the
    first commit past the window."""
    from repro.engine.ingest import Ingestor

    class TimedIngestor(Ingestor):
        def __init__(self, *a, **kw):
            super().__init__(*a, **kw)
            self.marks = []             # (host time, rows, write seconds)
            self.commit_spans = []

        def _commit(self, writer, stats, *a, **kw):
            t0 = time.perf_counter()
            super()._commit(writer, stats, *a, **kw)
            t = time.perf_counter()
            self.commit_spans.append(HostSpan("ingest.commit", t0, t))
            self.marks.append((t, writer.rows, stats.write_seconds))
            if t > self.marks[0][0] + seconds:
                raise WindowClosed

    return TimedIngestor


def measure(run: Run) -> Measured:
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.runtime.serve_loop import EmbeddingService
    cfg, tr = run.config, run.traffic
    mcfg = model_config(cfg)
    mesh = None
    if run.chips > 1:
        from repro.launch.mesh import make_scoring_mesh
        mesh = make_scoring_mesh(run.chips)
    params = llama_params(cfg, data.jax_key(run.seed, 2),
                          dtype=jnp.dtype(cfg["torch_dtype"]),
                          out_sharding=NamedSharding(mesh, P()) if mesh else None)
    docs = data.token_docs(run.seed, tr["docs_per_chip"] * run.chips,
                           tr["doc_len"], cfg["vocab_size"])
    batch = tr["batch_per_chip"] * run.chips
    service = EmbeddingService(mcfg, params, batch_size=batch)
    ingestor = timed_ingestor(run.seconds)(service, mesh=mesh)
    jax.block_until_ready(service.embed_batch(
        ingestor._put_fn()(np.ones((batch, tr["doc_len"]), np.int32))))
    store_dir = run.workdir / "store"
    shutil.rmtree(store_dir, ignore_errors=True)
    dtrace = run.device_trace()
    if dtrace:
        dtrace.start()
    t_job = time.perf_counter()
    try:
        ingestor.ingest(docs, store_dir)
    except WindowClosed:
        pass
    marks = ingestor.marks
    t_open, rows_open, write_open = marks[0]
    setup_s = t_open - run.t0
    w = close_window([(t, r) for t, r, _ in marks], (t_open, rows_open), run.seconds)
    end_mark = marks[w.completions]
    log(f"set-up {setup_s:.3f} s (job start at {t_job - run.t0:.3f} s); "
        f"{int(w.units)} docs in {w.seconds:.3f} s over {w.completions} commits")
    end_to_end = {"setup_s": setup_s, "ingest_docs_per_s": w.rate}

    readings = None
    if dtrace:
        from perfbench import devtrace
        profile = devtrace.load(dtrace.stop())
        spans = ingestor.commit_spans + [HostSpan("ingest.between_commits", w.start, w.end)]
        readings = Readings(
            window=w, chips=run.chips, peak=run.peak, spans=spans,
            counters={"flops_per_doc": tr["doc_len"] * flops.llama_forward_flops_per_token(
                          cfg, tr["doc_len"]),
                      "write_seconds": end_mark[2] - write_open},
            device=devtrace.reduce(profile, dtrace.t_sync, w.start, w.end,
                                   run.chips, spans))
    acknowledged = marks[-1][1]

    def sample_rows(n):
        rng = np.random.default_rng(np.random.SeedSequence([run.seed, 5]))
        return np.sort(rng.choice(n, size=min(tr["check_rows"], n), replace=False))

    def check():
        nonlocal service, ingestor
        from perfbench.reference import llama as ref
        from repro.engine.store import MemmapStore
        service = ingestor = None
        store = MemmapStore.open(store_dir)
        rows = np.asarray(store.get(np.arange(len(store))), np.float32)
        pick = sample_rows(min(len(rows), acknowledged))
        p0 = jax.device_put(params, jax.devices()[0])
        want = ref.pooled(p0, docs[pick], cfg)
        return chk.ingest_checks(rows, acknowledged, rows[pick], want,
                                 cfg["check_limits"])

    def control():
        """The reference in fp8, in the program's place, against the
        float32 reference over the same sampled documents."""
        from perfbench.reference import llama as ref
        pick = sample_rows(acknowledged)
        p0 = jax.device_put(params, jax.devices()[0])
        want = ref.pooled(p0, docs[pick], cfg)
        return {"control.row_rel_err": chk.row_rel_err(
            ref.pooled(p0, docs[pick], cfg, precision="fp8"), want)}

    return Measured(end_to_end, attempted=int(w.units), failed=0,
                    readings=readings, check=check,
                    control=control)
