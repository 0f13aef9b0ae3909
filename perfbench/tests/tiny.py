"""Tiny cells for CPU tests: the benchmark's configurations and mixes
with every size cut so a run takes seconds on the CPU backend."""
from __future__ import annotations

import copy
import json
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
PEAK = json.loads((BENCH / "peaks.json").read_text())["TPU v5 lite"]


def config(name: str) -> dict:
    cfg = json.loads((BENCH / "configs" / f"{name}.json").read_text())
    if name == "scaledoc-paper-4096":
        cfg["store"].update(n_docs=1500, embed_dim=64)
        cfg["proxy"].update(embed_dim=64, hidden_dim=32, latent_dim=16,
                            proj_dim=8, phase1_steps=3, phase2_steps=3,
                            batch_size=16)
        cfg["chunk"] = 512
        # a calibration sample of 75 documents holds the accuracy target
        # per leaf more loosely than the 5,000 of the full collection
        cfg["check_limits"].update(score_gap=1e-4, leaf_f1_min=0.8)
    else:
        cfg.update(hidden_size=64, intermediate_size=128, num_hidden_layers=2,
                   num_attention_heads=4, num_key_value_heads=2, head_dim=16,
                   vocab_size=256, torch_dtype="float32")
        cfg["check_limits"].update(row_rel_err=1e-3)
    return cfg


def traffic(name: str) -> dict:
    tr = json.loads((BENCH / "traffic" / f"{name}.json").read_text())
    if tr["runner"] == "ingest":
        tr.update(doc_len=32, batch_per_chip=4, docs_per_chip=4096, check_rows=8)
    elif tr["runner"] == "served":
        tr.update(hot_pool=3, max_queries=96, warm_completions=2, check_sample=4)
    else:
        tr.update(max_queries=6)
    return tr


def run(config_name: str, traffic_name: str, tmp: Path, *, seed: int = 2**31 + 7,
        seconds: float = 1.0, trace: bool = False, chips: int = 1, cfg=None,
        tr=None):
    from perfbench.runners.common import Run
    return Run(workload=f"{config_name}.{traffic_name}",
               config=copy.deepcopy(cfg or config(config_name)),
               traffic=copy.deepcopy(tr or traffic(traffic_name)), chips=chips,
               seed=seed, seconds=seconds, trace=trace, t0=time.perf_counter(),
               workdir=Path(tmp), peak=PEAK)
