#!/usr/bin/env python3
"""Run one cell of BENCHMARK.json once and print its result line.

    python3 perfbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell names a configuration (``perfbench/configs/<config>.json``) and
a traffic mix (``perfbench/traffic/<traffic>.json``, whose ``runner``
names the generic runner under ``perfbench/runners/``). With ``--trace
0`` the result carries the cell's end-to-end metrics, with ``--trace 1``
its per-layer metrics, each read by ``perfbench/metrics/<name>.py``.
The last line of standard output is one JSON object; the numbers the
correctness check compared, each beside its limit, close standard error
and the result line. Without a TPU, or with fewer chips than the cell
asks for, the run exits non-zero and prints no result.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent
CACHE_DIR = ROOT / ".perfbench" / "jax_cache"
WORK_DIR = ROOT / ".perfbench" / "work"


class SetupError(RuntimeError):
    pass


def load_cell(spec: dict, workload: str):
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise SetupError(f"no workload {workload!r} in BENCHMARK.json")
    cell = cells[workload]
    conf = {c["name"]: c for c in spec["configs"]}[cell["config"]]
    config = json.loads((ROOT / conf["file"]).read_text())
    traffic = json.loads((BENCH_DIR / "traffic" / f"{cell['traffic']}.json").read_text())
    return cell, config, traffic


def cell_metrics(spec: dict, key: str, workload: str, e2e_of_cell=None):
    """The cell's metrics of ``key``: those that list it, and those that
    list no cells and (per-layer) move an end-to-end metric it reports."""
    out = []
    for m in spec[key]:
        if "workloads" in m:
            if workload in m["workloads"]:
                out.append(m)
        elif e2e_of_cell is None or m["moves"] in e2e_of_cell:
            out.append(m)
    return out


def enable_cache(jax) -> None:
    """JAX's persistent compilation cache at a fixed path in the checkout
    (or where JAX_COMPILATION_CACHE_DIR says), every program kept."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        CACHE_DIR.mkdir(parents=True, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


def device_identity(jax, chips: int) -> dict:
    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    print(f"[perfbench] platform={dev['platform']} device_kind={dev['kind']} "
          f"count={dev['count']}", file=sys.stderr, flush=True)
    if dev["platform"] != "tpu":
        raise SetupError(f"no TPU: JAX reports platform {dev['platform']!r}")
    if dev["count"] < chips:
        raise SetupError(f"the cell needs {chips} chips, JAX has {dev['count']}")
    return dev


def memory_peak(jax, chips: int) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.devices()[:chips]]
    return int(max(peaks))


def result_line(spec: dict, workload: str, measured, device: dict,
                trace: bool) -> dict:
    """The run's result line: the cell's metrics, then the check against
    the reference (after the window and the memory reading), each
    number compared beside its limit on standard error and last in the
    line."""
    from perfbench.checks import checks_line
    from perfbench.runners.common import log
    e2e = cell_metrics(spec, "end_to_end", workload)
    metrics = {}
    if trace:
        from perfbench.readings import read_metric
        names = {m["name"] for m in e2e}
        for m in cell_metrics(spec, "per_layer", workload, names):
            value = read_metric(m["name"], measured.readings)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        dev = measured.readings.device
        device = dict(device, busy_s=dev.busy_s, window_s=dev.window_s)
    else:
        for m in e2e:
            metrics[m["name"]] = {"value": measured.end_to_end[m["name"]],
                                  "unit": m["unit"]}
    log("window over; checking against the reference")
    t_check = time.perf_counter()
    checks = measured.check()
    log(f"check took {time.perf_counter() - t_check:.3f} s")
    line = {"correct": measured.failed == 0 and all(c.ok for c in checks),
            "attempted": measured.attempted, "failed": measured.failed,
            "metrics": metrics, "device": device}
    if trace:
        line["breakdown"] = {"device_ops": dev.device_ops,
                             "idle_gaps": dev.idle_gaps}
    line["checks"] = checks_line(checks)
    for c in checks:
        print(f"[perfbench] check {c.name} = {c.value!r} "
              f"({'<=' if c.kind == 'max' else '>='} {c.limit!r}) "
              f"{'ok' if c.ok else 'FAIL'}", file=sys.stderr)
    sys.stderr.flush()
    return line


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    try:
        spec = json.loads((ROOT / "BENCHMARK.json").read_text())
        cell, config, traffic = load_cell(spec, args.workload)
    except (OSError, KeyError, ValueError, SetupError) as exc:
        print(f"[perfbench] {exc}", file=sys.stderr)
        return 2
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import jax
    from perfbench.runners.common import Run
    from perfbench.peaks import peak
    try:
        device = device_identity(jax, cell["chips"])
        row = peak(device["kind"])
        import repro  # noqa: F401  the program under test
    except (SetupError, KeyError, ImportError) as exc:
        print(f"[perfbench] {exc}", file=sys.stderr)
        return 3
    enable_cache(jax)

    workdir = WORK_DIR / args.workload
    shutil.rmtree(workdir, ignore_errors=True)
    workdir.mkdir(parents=True)
    run = Run(workload=args.workload, config=config, traffic=traffic,
              chips=cell["chips"], seed=args.seed, seconds=args.seconds,
              trace=bool(args.trace), t0=T0, workdir=workdir, peak=row)
    runner = importlib.import_module(f"perfbench.runners.{traffic['runner']}")
    measured = runner.measure(run)
    device["memory_peak_bytes"] = memory_peak(jax, cell["chips"])

    line = result_line(spec, args.workload, measured, device, bool(args.trace))
    shutil.rmtree(workdir, ignore_errors=True)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
