"""Whole runs of each runner on the CPU at a tiny size, past the
harness's look for a chip: a sound run comes out correct, and a run with
the timed path broken underneath comes out not correct."""
import importlib
import json

import numpy as np
import pytest

from perfbench import run as bench
from perfbench.tests import tiny

SPEC = json.loads((bench.ROOT / "BENCHMARK.json").read_text())
CELLS = {"cold": ("scaledoc-paper-4096", "cold-compound", "paper-cold-compound"),
         "served": ("scaledoc-paper-4096", "served-shared", "paper-served-shared"),
         "ingest": ("smollm-360m", "ingest-512", "smollm-ingest")}


def run_cell(kind, tmp_path):
    seconds = 2.0 if kind == "served" else 0.6
    config, traffic, workload = CELLS[kind]
    run = tiny.run(config, traffic, tmp_path, seconds=seconds)
    runner = importlib.import_module(f"perfbench.runners.{run.traffic['runner']}")
    measured = runner.measure(run)
    device = {"platform": "cpu", "kind": "cpu", "count": 1, "memory_peak_bytes": 0}
    return bench.result_line(SPEC, workload, measured, device, trace=False)


@pytest.mark.parametrize("kind", ["cold", "served", "ingest"])
def test_a_sound_run_is_correct(kind, tmp_path):
    line = run_cell(kind, tmp_path)
    assert line["correct"], line["checks"]
    assert list(line)[-1] == "checks"
    assert line["attempted"] > 0 and line["failed"] == 0
    names = {m["name"] for m in SPEC["end_to_end"]
             if "workloads" not in m or CELLS[kind][2] in m["workloads"]}
    assert set(line["metrics"]) == names
    assert all(v["value"] > 0 for v in line["metrics"].values())


def _flip_answers(monkeypatch):
    """A token of the answer altered where it is produced: the first
    document's decision of every query flipped."""
    from repro.engine import ScaleDocEngine
    real = ScaleDocEngine.filter

    def filter_(self, *a, **kw):
        res = real(self, *a, **kw)
        res.mask = res.mask.copy()
        res.mask[0] = ~res.mask[0]
        return res
    monkeypatch.setattr(ScaleDocEngine, "filter", filter_)


def _shift_scores(monkeypatch):
    """Every proxy score off by 0.05 where the executor produces it."""
    from repro.engine import ScoringExecutor
    real = ScoringExecutor.score

    def score(self, params, e_q, store):
        s, st = real(self, params, e_q, store)
        return (s + 0.05 if params is not None else s), st
    monkeypatch.setattr(ScoringExecutor, "score", score)


def _scale_rows(monkeypatch):
    """Every embedding row 5% too long where the backbone produces it."""
    from repro.runtime.serve_loop import EmbeddingService
    real = EmbeddingService.embed_batch
    monkeypatch.setattr(EmbeddingService, "embed_batch",
                        lambda self, batch: real(self, batch) * 1.05)


def _half_batch(monkeypatch):
    """The trainer takes half of each batch, the mean over the rest."""
    import dataclasses
    import repro.engine.engine as eng
    real = eng.train_proxy_multi

    def train(keys, e_qs, samples, labels, cfg):
        return real(keys, e_qs, samples, labels,
                    dataclasses.replace(cfg, batch_size=cfg.batch_size // 2))
    monkeypatch.setattr(eng, "train_proxy_multi", train)


def _fewer_steps(monkeypatch):
    """The trainer runs a third of each phase's steps."""
    import dataclasses
    import repro.engine.engine as eng
    real = eng.train_proxy_multi

    def train(keys, e_qs, samples, labels, cfg):
        return real(keys, e_qs, samples, labels, dataclasses.replace(
            cfg, phase1_steps=cfg.phase1_steps // 3, phase2_steps=cfg.phase2_steps // 3))
    monkeypatch.setattr(eng, "train_proxy_multi", train)


def _collapse_band(monkeypatch):
    """Calibration returns a band cut to its midpoint: no document goes
    to the oracle."""
    import dataclasses
    import repro.engine.engine as eng
    real = eng.get_calibrator

    def get_calibrator(strategy):
        calibrate = real(strategy)
        if calibrate is None:
            return None

        def narrowed(*a, **kw):
            spec = calibrate(*a, **kw)
            mid = (spec.l + spec.r) / 2
            return dataclasses.replace(spec, l=mid, r=mid)
        return narrowed
    monkeypatch.setattr(eng, "get_calibrator", get_calibrator)


@pytest.mark.parametrize("kind,fault,fails", [
    ("cold", _flip_answers, "answer_mismatch"),
    ("cold", _shift_scores, "score_gap"),
    ("cold", _half_batch, "train_loss_gap"),
    ("cold", _fewer_steps, "train_change_gap"),
    ("cold", _collapse_band, "leaf_f1_min"),
    ("served", _flip_answers, "answer_mismatch"),
    ("served", _shift_scores, "score_gap"),
    ("served", _half_batch, "train_loss_gap"),
    ("ingest", _scale_rows, "row_rel_err"),
])
def test_a_broken_timed_path_is_not_correct(kind, fault, fails, tmp_path,
                                            monkeypatch):
    fault(monkeypatch)
    line = run_cell(kind, tmp_path)
    assert not line["correct"]
    got = line["checks"][fails]
    assert (got["value"] > got["limit"]) if got["pass"] == "max" else (
        got["value"] < got["limit"])


def test_served_decisions_equal_cold_decisions(tmp_path):
    """Shared leaves through the server decide as direct filter() calls
    on a fresh engine do."""
    from repro.engine import ScaleDocEngine
    from repro.serve import PredicateServer
    from perfbench import data
    from perfbench.runners.common import compose, engine_for, predicate
    from perfbench.runners.common import recording_executor
    cfg = tiny.config("scaledoc-paper-4096")
    st = cfg["store"]
    store = data.topic_store(5, st["n_docs"], st["embed_dim"], 16,
                             st["topic_noise_at_256d"])
    leaves = [data.planted_leaf(store, 5, (j,), 0.3) for j in range(3)]

    def queries():
        p = [predicate(leaf, data.TruthOracle(leaf.truth), f"p{j}")
             for j, leaf in enumerate(leaves)]
        return [compose("and", p[0], p[1]), compose("and_not", p[1], p[2]),
                compose("or", p[0], p[2]), p[0]]

    engine, _ = engine_for(cfg, store.embeds, recording_executor())
    with PredicateServer(engine, optimize=True) as server:
        served = [s.result(timeout=600).mask for s in
                  [server.submit(q, seed=3, block=True) for q in queries()]]
    for q, got in zip(queries(), served):
        fresh, _ = engine_for(cfg, store.embeds, recording_executor())
        assert isinstance(fresh, ScaleDocEngine)
        assert np.array_equal(fresh.filter(q, seed=3).mask, got)


FOUR = """
import sys
sys.path[:0] = [{root!r}, {src!r}]
import importlib, json, tempfile
from perfbench.tests import tiny
from perfbench import run as bench
with tempfile.TemporaryDirectory() as tmp:
    run = tiny.run("smollm-360m", "ingest-512", tmp, seconds=0.6, chips=4)
    measured = importlib.import_module("perfbench.runners.ingest").measure(run)
    device = {{"platform": "cpu", "kind": "cpu", "count": 4, "memory_peak_bytes": 0}}
    print(json.dumps(bench.result_line(bench.json.loads(
        (bench.ROOT / "BENCHMARK.json").read_text()), "smollm-ingest",
        measured, device, trace=False)))
"""


def test_four_chip_ingest_on_four_host_devices():
    """The ingest runner's sharded path over a ("data",) mesh, on four
    virtual CPU devices in a child process (the device count is fixed
    when JAX starts)."""
    import os
    import subprocess
    import sys
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    code = FOUR.format(root=str(bench.ROOT), src=str(bench.ROOT / "src"))
    out = subprocess.run([sys.executable, "-c", code], env=env, timeout=600,
                         capture_output=True, text=True)
    assert out.returncode == 0, out.stderr[-3000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["correct"], line["checks"]
    assert set(line["metrics"]) == {"ingest_docs_per_s", "setup_s"}
    assert line["metrics"]["ingest_docs_per_s"]["value"] > 0
