# One function per paper table. Print ``name,us_per_call,derived`` CSV;
# ``--json [PATH]`` additionally writes the same rows machine-readably
# (default BENCH.json) so the repo's perf trajectory is tracked across
# PRs. bench_training.py also runs standalone and writes
# BENCH_training.json via its own ``--json`` flag.
import argparse
import sys
import time


def main() -> None:
    from benchmarks import (bench_ablation, bench_calibration, bench_cascade,
                            bench_compound, bench_gateway, bench_ingest,
                            bench_kernels, bench_live, bench_optimizer,
                            bench_resilience, bench_serve, bench_thresholds,
                            bench_trace, bench_tradeoff, bench_training)
    from benchmarks.common import Rows
    from repro.runtime.compile_cache import enable_compile_cache

    parser = argparse.ArgumentParser()
    parser.add_argument("--json", nargs="?", const="BENCH.json",
                        default=None, metavar="PATH",
                        help="also write all rows as JSON")
    args = parser.parse_args()
    enable_compile_cache()

    suites = [
        ("cascade (Fig4+Table2)", bench_cascade.run),
        ("compound (composed predicates)", bench_compound.run),
        ("ablation (Fig9+Fig11)", bench_ablation.run),
        ("calibration (Fig12+Table4)", bench_calibration.run),
        ("thresholds (Alg2)", bench_thresholds.run),
        ("tradeoff (Fig7/8/13)", bench_tradeoff.run),
        ("kernels", bench_kernels.run),
        ("training (scan trainer)", bench_training.run),
        ("ingest (offline phase)", bench_ingest.run),
        ("serve (concurrent sessions)", bench_serve.run),
        ("gateway (HTTP/SSE service plane)", bench_gateway.run),
        ("live (standing predicates, delta vs rescan)", bench_live.run),
        ("resilience (faulty oracle plane)", bench_resilience.run),
        ("optimizer (shared-leaf CSE + top-k)", bench_optimizer.run),
        ("trace (observability overhead)", bench_trace.run),
    ]
    rows = Rows()
    timings = {}
    print("name,us_per_call,derived")
    for name, fn in suites:
        t0 = time.time()
        try:
            fn(rows)
        except Exception as e:  # keep the suite running
            rows.add(f"{name}/ERROR", 0.0, repr(e)[:200])
        timings[name] = round(time.time() - t0, 1)
        print(f"# {name}: {timings[name]:.1f}s", file=sys.stderr)
    rows.emit()
    if args.json:
        rows.to_json(args.json, extra={"suite_seconds": timings})
        print(f"# wrote {args.json}", file=sys.stderr)


if __name__ == '__main__':
    main()
