"""Serving CLI: the offline representation phase (batched document
embedding) for any assigned architecture.

    PYTHONPATH=src python -m repro.launch.serve --arch smollm-360m \
        --smoke --docs 64
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.config import get_arch, get_smoke_arch
from repro.data import make_corpus
from repro.models import build_model
from repro.runtime.compile_cache import enable_compile_cache
from repro.runtime.serve_loop import EmbeddingService, ServeStats


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-360m")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--docs", type=int, default=64)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--doc-len", type=int, default=48)
    ap.add_argument("--out", default="")
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_smoke_arch(args.arch) if args.smoke else get_arch(args.arch)
    corpus = make_corpus(0, n_docs=args.docs, dim=128, with_tokens=True,
                         vocab=min(cfg.vocab_size, 256),
                         doc_len=args.doc_len)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    service = EmbeddingService(cfg, params, batch_size=args.batch)
    stats = ServeStats()
    embeds = service.embed_documents(
        [corpus.tokens[i] for i in range(args.docs)], stats)
    print(f"embedded {stats.documents} docs ({cfg.name}, d={cfg.d_model}) "
          f"in {stats.wall_s:.1f}s, {stats.batches} batches, "
          f"pad waste {stats.pad_waste_frac:.1%}")
    if args.out:
        np.save(args.out, embeds)
        print(f"saved -> {args.out}")


if __name__ == "__main__":
    main()
