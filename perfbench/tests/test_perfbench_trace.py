"""The reduction from a profiler trace and host spans to busy time, idle
gaps and per-layer shares, on a small trace of known contents."""
import pytest

from perfbench import devtrace
from perfbench.readings import Readings, read_metric
from perfbench.spans import HostSpan, self_time, total_time
from perfbench.window import Window

# Trace clock: the sync annotation at 1 us; on chip 0 two ops at
# [1.001, 1.501] ms and [2.001, 3.001] ms, the second inside a while
# loop's op of the same extent; on chip 1 one op at [1.001, 2.001] ms.
TRACE = '''
planes { id: 1 name: "/host:CPU"
  lines { id: 1 name: "python" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 1000 } }
  event_metadata { key: 1 value { id: 1 name: "perfbench.sync" } } }
planes { id: 2 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Modules" timestamp_ns: 1000
    events { metadata_id: 3 offset_ps: 0 duration_ps: 4000000000 } }
  lines { id: 2 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 1000000000 duration_ps: 500000000 }
    events { metadata_id: 4 offset_ps: 2000000000 duration_ps: 1000000000 }
    events { metadata_id: 2 offset_ps: 2000000000 duration_ps: 1000000000 } }
  event_metadata { key: 1 value { id: 1 name: "fusion.1" } }
  event_metadata { key: 2 value { id: 2 name: "%convolution.2 = f32[8]{0} convolution(a, b)" } }
  event_metadata { key: 4 value { id: 4 name: "%while.3 = (s32[]) while(t)" } }
  event_metadata { key: 3 value { id: 3 name: "jit_step" } } }
planes { id: 3 name: "/device:TPU:1"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 1000000000 duration_ps: 1000000000 } }
  event_metadata { key: 1 value { id: 1 name: "fusion.1" } } }
'''
T_SYNC = 100.0          # host clock of the sync annotation
SPANS = [HostSpan("score", 99.9, 100.0018), HostSpan("decide", 100.0025, 100.005)]


@pytest.fixture(scope="module")
def profile():
    from jax.profiler import ProfileData
    return ProfileData.from_text_proto(TRACE)


def test_busy_time_top_ops_and_idle_gaps(profile):
    s = devtrace.reduce(profile, T_SYNC, 100.0, 100.004, 1, SPANS)
    assert s.window_s == pytest.approx(0.004)
    assert s.busy_s == pytest.approx(0.0015)
    assert [n for n, _ in s.device_ops] == ["convolution.2", "fusion.1"]
    assert dict(s.device_ops)["fusion.1"] == pytest.approx(0.0005)
    gaps = dict(s.idle_gaps)
    assert gaps["score"] == pytest.approx(0.0015)
    assert gaps["decide"] == pytest.approx(0.001)


def test_ops_are_clipped_to_the_window_and_averaged_over_chips(profile):
    s = devtrace.reduce(profile, T_SYNC, 100.0012, 100.0025, 2, SPANS)
    # chip 0: [1.2, 1.5] and [2.0, 2.5] ms; chip 1: [1.2, 2.0] ms
    assert s.busy_s == pytest.approx((0.0008 + 0.0008) / 2)


def test_a_trace_without_enough_chips_is_an_error(profile):
    with pytest.raises(RuntimeError, match="TPU planes"):
        devtrace.reduce(profile, T_SYNC, 100.0, 100.004, 4, SPANS)


def test_self_time_leaves_out_children():
    spans = [HostSpan("train", 0.0, 10.0, "a"),
             HostSpan("leaf", 2.0, 5.0, "b", "a"),
             HostSpan("leaf", 4.0, 7.0, "c", "a"),
             HostSpan("train", 12.0, 14.0, "d")]
    assert self_time(spans, ["train"], 0.0, 20.0) == pytest.approx(5.0 + 2.0)
    assert self_time(spans, ["train"], 6.0, 13.0) == pytest.approx(3.0 + 1.0)
    assert total_time(spans, ["leaf"], 0.0, 20.0) == pytest.approx(6.0)


def test_per_layer_readers_on_a_recorded_window(profile):
    w = Window(start=100.0, end=100.004, units=8.0, completions=2)
    dev = devtrace.reduce(profile, T_SYNC, w.start, w.end, 1, SPANS)
    peak = {"bf16_flops_per_s": 197e12}
    r = Readings(window=w, chips=1, peak=peak, spans=SPANS,
                 counters={"flops_per_query": 0.5 * 197e12 * 0.004 / 8,
                           "write_seconds": 0.001}, device=dev)
    assert read_metric("device_idle.cold", r) == pytest.approx(62.5)
    assert read_metric("score_share.cold", r) == pytest.approx(45.0)
    assert read_metric("cascade_host_share.cold", r) == pytest.approx(37.5)
    assert read_metric("mfu.cold", r) == pytest.approx(50.0)
    assert read_metric("write_share.ingest", r) == pytest.approx(25.0)
    assert read_metric("train_share.cold", r) is None
    assert read_metric("mfu.ingest", r) is None


def test_every_per_layer_metric_has_a_reader():
    """Each per-layer metric of BENCHMARK.json finds a reader, its own or
    the one of the quantity it splits (``device_idle.cold`` ->
    ``device_idle.py``)."""
    import json
    from perfbench import run as bench
    from perfbench.readings import METRICS_DIR
    spec = json.loads((bench.ROOT / "BENCHMARK.json").read_text())
    for m in spec["per_layer"]:
        name = m["name"]
        assert ((METRICS_DIR / f"{name}.py").exists()
                or (METRICS_DIR / f"{name.split('.')[0]}.py").exists()), name
