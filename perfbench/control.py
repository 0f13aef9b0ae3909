#!/usr/bin/env python3
"""Readings that set the limits of ``correct``: the program's, the
control's and the planted faults', for one cell on several seeds in one
process.

    python3 perfbench/control.py --workload <name> --seeds 1,2,3 --seconds 8

For each seed it runs the cell's runner with a short window, then prints
one JSON line with the numbers the check compares (the program's
readings) and the control's readings of the same numbers: the reference
computed one precision step below the configuration's, in the program's
place, and for the query cells the AUC of an untrained proxy. The
benchmark's own runs never call this.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path[:0] = [str(Path(__file__).resolve().parents[1])]

from perfbench import run as bench  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=8.0)
    args = ap.parse_args(argv)
    spec = json.loads((bench.ROOT / "BENCHMARK.json").read_text())
    cell, config, traffic = bench.load_cell(spec, args.workload)
    sys.path[:0] = [str(bench.ROOT / "src")]
    import jax
    device = bench.device_identity(jax, cell["chips"])
    bench.enable_cache(jax)
    from perfbench.runners.common import Run
    from perfbench.peaks import peak
    runner = importlib.import_module(f"perfbench.runners.{traffic['runner']}")
    workdir = bench.WORK_DIR / f"control.{args.workload}"
    for seed in [int(s) for s in args.seeds.split(",")]:
        shutil.rmtree(workdir, ignore_errors=True)
        workdir.mkdir(parents=True)
        run = Run(workload=args.workload, config=config, traffic=traffic,
                  chips=cell["chips"], seed=seed, seconds=args.seconds,
                  trace=False, t0=time.perf_counter(), workdir=workdir,
                  peak=peak(device["kind"]))
        measured = runner.measure(run)
        program = {c.name: c.value for c in measured.check()}
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "program": program, "control": measured.control(),
                          "end_to_end": measured.end_to_end}), flush=True)
        del measured
    shutil.rmtree(workdir, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
