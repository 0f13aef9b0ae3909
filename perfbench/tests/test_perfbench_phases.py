"""The phase readers: what they read from the program's span phases on
recorded windows, that phases leave the span readings as they were, and
that a traced tiny run of each cell reports every per-layer metric."""
import dataclasses
import importlib
import json

import pytest

from perfbench import run as bench
from perfbench.readings import Readings, read_metric
from perfbench.spans import HostSpan, from_tracer, innermost, self_time, total_time
from perfbench.tests import tiny
from perfbench.window import Window

SPEC = json.loads((bench.ROOT / "BENCHMARK.json").read_text())
PHASE_METRICS = ("train_prep_share.cold", "train_run_share.cold",
                 "score_stall_share.cold", "decide_cpu_ms.served",
                 "decide_wait_ms.served", "compile_s.cold", "compile_s.served")


def _phased(name, start, end, phases, span_id=None, parent_id=None):
    return HostSpan(name, start, end, span_id, parent_id,
                    {"cpu_s": 0.0, "phases": [list(p) for p in phases]})


def test_phases_leave_self_and_total_time_unchanged():
    """Phases are attributes: a span's self time, total time and the
    innermost span of an instant read the same with and without them."""
    from repro.runtime.trace import Tracer, phase
    tracer = Tracer()
    with tracer.span("train"):
        with phase("pad"):
            sum(range(20000))
        with tracer.span("broker.request"):
            with phase("label"):
                sum(range(20000))
        with phase("run"):
            sum(range(20000))
    spans = from_tracer(tracer.spans())
    bare = [dataclasses.replace(s, attrs={}) for s in spans]
    lo, hi = min(s.start for s in spans), max(s.end for s in spans)
    assert any(s.attrs.get("phases") for s in spans)
    for names in (["train"], ["broker.request"]):
        assert self_time(spans, names, lo, hi) == self_time(bare, names, lo, hi)
        assert total_time(spans, names, lo, hi) == total_time(bare, names, lo, hi)
    for t in (lo, (lo + hi) / 2, hi):
        assert innermost(spans, t).span_id == innermost(bare, t).span_id


def test_phase_readers_on_a_recorded_window():
    # window [10, 20] s with 4 queries; phases straddling its edges count
    # their inside part (and their CPU in proportion)
    w = Window(start=10.0, end=20.0, units=4.0, completions=4)
    spans = [
        _phased("train", 8.0, 14.0, [("sample", 8.0, 9.0, 1.0),
                                     ("label", 9.0, 11.0, 0.5),
                                     ("pad", 11.0, 12.0, 1.0),
                                     ("put", 12.0, 12.5, 0.5),
                                     ("run", 12.5, 14.0, 0.1),
                                     ("compile", 13.0, 13.5, None)]),
        _phased("score", 14.0, 16.0, [("stall", 14.0, 14.5, 0.0),
                                      ("sync", 14.5, 15.0, 0.1),
                                      ("stall", 15.0, 16.0, 0.0)]),
        _phased("decide", 16.0, 21.0, [("threshold", 16.0, 17.0, 0.5),
                                       ("known", 17.0, 18.0, 1.0),
                                       ("label", 18.0, 19.0, 0.0),
                                       ("merge", 19.0, 21.0, 2.0)]),
        _phased("plan", 9.0, 11.0, [("lower", 10.5, 11.0, None)]),
    ]
    r = Readings(window=w, chips=1, peak={}, spans=spans, counters={})
    assert read_metric("train_prep_share.cold", r) == pytest.approx(20.0)
    assert read_metric("train_run_share.cold", r) == pytest.approx(20.0)
    assert read_metric("score_stall_share.cold", r) == pytest.approx(15.0)
    # threshold, known and half of merge: 3 s of wall, 0.5 + 1 + 1 s CPU
    assert read_metric("decide_cpu_ms.served", r) == pytest.approx(625.0)
    assert read_metric("decide_wait_ms.served", r) == pytest.approx(125.0)
    # compile [13, 13.5] and the inside part of lower [10.5, 11]
    for name in ("compile_s.cold", "compile_s.served"):
        assert read_metric(name, r) == pytest.approx(1.0)
    quiet = Readings(window=w, chips=1, peak={}, counters={},
                     spans=[_phased("score", 14.0, 16.0, [])])
    assert read_metric("compile_s.cold", quiet) == 0.0
    assert read_metric("score_stall_share.cold", quiet) == 0.0
    assert read_metric("train_prep_share.cold", quiet) is None


def test_phase_readers_read_nothing_from_a_program_without_phases():
    """The program before phases: spans carry neither ``phases`` nor
    ``cpu_s``, and every phase reader reads None (left out of the line)."""
    w = Window(start=10.0, end=20.0, units=4.0, completions=4)
    spans = [HostSpan(n, 11.0, 12.0) for n in ("train", "score", "decide")]
    r = Readings(window=w, chips=1, peak={}, spans=spans, counters={})
    for name in PHASE_METRICS:
        assert read_metric(name, r) is None, name


CELLS = {"cold": ("scaledoc-paper-4096", "cold-compound", "paper-cold-compound", 0.6),
         "served": ("scaledoc-paper-4096", "served-shared", "paper-served-shared", 2.0),
         "ingest": ("smollm-360m", "ingest-512", "smollm-ingest", 0.6)}


@pytest.mark.parametrize("kind", ["cold", "served", "ingest"])
def test_a_traced_run_reads_every_per_layer_metric(kind, tmp_path, monkeypatch):
    """A traced tiny run reports each per-layer metric of its cell, the
    phase readers among them, and stays correct. The CPU has no TPU plane
    to read, so the device trace is replaced by an idle device."""
    from perfbench import devtrace
    monkeypatch.setattr(devtrace.DeviceTrace, "start",
                        lambda self: setattr(self, "t_sync", 0.0))
    monkeypatch.setattr(devtrace.DeviceTrace, "stop", lambda self: None)
    monkeypatch.setattr(devtrace, "load", lambda path: None)
    monkeypatch.setattr(devtrace, "reduce", lambda profile, t_sync, lo, hi, *a, **kw:
                        devtrace.DeviceSummary(0.0, hi - lo, [], []))
    config, traffic, workload, seconds = CELLS[kind]
    run = tiny.run(config, traffic, tmp_path, seconds=seconds, trace=True)
    runner = importlib.import_module(f"perfbench.runners.{run.traffic['runner']}")
    measured = runner.measure(run)
    device = {"platform": "cpu", "kind": "cpu", "count": 1, "memory_peak_bytes": 0}
    line = bench.result_line(SPEC, workload, measured, device, trace=True)
    assert line["correct"], line["checks"]
    names = {m["name"] for m in SPEC["per_layer"] if workload in m["workloads"]}
    assert set(line["metrics"]) == names
    assert {n for n in PHASE_METRICS if n.endswith(kind)} <= names
