"""Chip benchmark of ScaleDoc: one cell of BENCHMARK.json per run.

    python3 perfbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Everything that measures lives here: traffic generation, the exact-boundary
window arithmetic, the device-trace reduction, FLOP counts and peaks, the
plain references and the comparison that decides ``correct``. The program
under test is imported from ``src/``.
"""
