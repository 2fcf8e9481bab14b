"""Serving launcher: batched generation with the reduced (or full) config.

    PYTHONPATH=src python -m repro.launch.serve --arch qwen2-7b --requests 6 --max-new 8
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.config import get_arch, list_archs
from repro.models import transformer as T
from repro.serving import BatchedEngine
from repro.utils.compile_cache import enable_compile_cache


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=list_archs())
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--max-new", type=int, default=8)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--page-size", type=int, default=16)
    ap.add_argument("--full", action="store_true")
    args = ap.parse_args()
    enable_compile_cache()

    arch = get_arch(args.arch)
    cfg = arch.model if args.full else arch.model.reduced()
    if cfg.frontend is not None:
        print("note: serving launcher demo covers text archs; "
              "VLM/audio serving paths are exercised in tests/test_serving.py")
    params, _ = T.init_model(cfg, jax.random.PRNGKey(0))
    eng = BatchedEngine(cfg, params, slots=args.slots, page_size=args.page_size)
    kind = f"paged (page_size={eng.page_size}, pool={eng.num_pages} pages)" if eng.paged else "dense fallback"
    print(f"engine: {kind}")
    rng = np.random.default_rng(0)
    for i in range(args.requests):
        eng.submit(f"req-{i}", rng.integers(0, cfg.vocab_size, (4 + i % 5,)).astype(np.int32), args.max_new)
    t0 = time.time()
    results = eng.run()
    dt = time.time() - t0
    total = sum(len(v) for v in results.values())
    print(f"served {len(results)} requests / {total} tokens in {dt:.1f}s ({total/dt:.1f} tok/s)")
    for rid in sorted(results):
        print(f"  {rid}: {results[rid].tolist()}")


if __name__ == "__main__":
    main()
