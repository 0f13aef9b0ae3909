"""Production training CLI.

    PYTHONPATH=src python -m repro.launch.train --arch smollm-360m \
        --steps 100 [--smoke] [--compress-grads] [--resume]

On a real pod this runs under the production mesh; in this container use
--smoke (reduced config, 1-device mesh) to exercise the identical driver.
"""
from __future__ import annotations

import argparse

from repro.config import (SHAPES_BY_NAME, InputShape, OptimizerConfig,
                          TrainConfig, get_arch, get_smoke_arch)
from repro.launch.mesh import make_production_mesh, make_test_mesh
from repro.runtime.compile_cache import enable_compile_cache
from repro.runtime.train_loop import Trainer


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-360m")
    ap.add_argument("--shape", default="train_4k")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config + test mesh (CPU container)")
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--compress-grads", action="store_true")
    ap.add_argument("--resume", action="store_true")
    args = ap.parse_args()
    enable_compile_cache()

    if args.smoke:
        cfg = get_smoke_arch(args.arch)
        shape = InputShape("smoke", seq_len=args.seq,
                           global_batch=args.batch, kind="train")
        mesh = make_test_mesh(1, 1)
    else:
        cfg = get_arch(args.arch)
        shape = SHAPES_BY_NAME[args.shape]
        mesh = make_production_mesh()

    tc = TrainConfig(
        shape=shape,
        optimizer=OptimizerConfig(lr=args.lr, warmup_steps=20,
                                  total_steps=args.steps,
                                  compress_grads=args.compress_grads),
        checkpoint_every=args.ckpt_every, checkpoint_dir=args.ckpt_dir)
    trainer = Trainer(cfg, tc, mesh,
                      metrics_path=f"{args.ckpt_dir}/metrics.jsonl")
    report = trainer.run(args.steps, resume=args.resume)
    print(f"final loss {report.final_loss:.4f} after {args.steps} steps "
          f"({report.restarts} restarts, "
          f"{report.straggler_events} straggler events)")


if __name__ == "__main__":
    main()
