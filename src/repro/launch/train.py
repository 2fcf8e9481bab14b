"""Training launcher — a thin CLI over :class:`repro.api.Experiment`.

Single-host CPU execution runs the reduced variant of the selected
architecture for a quick end-to-end check; on a real TPU slice the same
driver runs the full config over the production mesh (the dry-run validates
that path AOT — see launch/dryrun.py).

    PYTHONPATH=src python -m repro.launch.train --arch qwen2-7b --rounds 20 \
        [--algo overlap_local_sgd] [--tau 2] [--alpha 0.6] [--workers 4] [--full]

``--algo`` accepts every two-phase strategy, including the new
``delayed_avg`` (DaSGD) and ``sparse_anchor`` (LOSCAR) variants.
"""
from __future__ import annotations

import argparse
import time

from repro import checkpoint
from repro.api import Experiment, TokenStream
from repro.config import AlgoConfig, OptimizerConfig, list_archs
from repro.core import STRATEGIES
from repro.optim import schedules
from repro.utils.compile_cache import enable_compile_cache


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=list_archs())
    ap.add_argument("--algo", default="overlap_local_sgd", choices=sorted(STRATEGIES))
    ap.add_argument("--tau", type=int, default=2)
    ap.add_argument("--alpha", type=float, default=0.6)
    ap.add_argument("--anchor-beta", type=float, default=0.7)
    ap.add_argument("--delay-steps", type=int, default=1, help="delayed_avg: consume k steps into the round")
    ap.add_argument("--sparse-k", type=float, default=1.0, help="sparse_anchor: top-k fraction transmitted")
    ap.add_argument("--workers", type=int, default=4)
    ap.add_argument("--rounds", type=int, default=20)
    ap.add_argument("--batch", type=int, default=2)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=1e-2)
    ap.add_argument("--full", action="store_true", help="use the full (not reduced) model config")
    ap.add_argument("--ckpt", default=None)
    args = ap.parse_args()
    enable_compile_cache()

    exp = Experiment(
        arch=args.arch,
        strategy=AlgoConfig(
            name=args.algo,
            tau=args.tau,
            alpha=args.alpha,
            anchor_beta=args.anchor_beta,
            delay_steps=args.delay_steps,
            sparse_k=args.sparse_k,
        ),
        optimizer=OptimizerConfig(name="sgd", lr=args.lr, momentum=0.9, nesterov=True),
        schedule=schedules.constant(args.lr),
        data=TokenStream(batch_per_worker=args.batch, seq_len=args.seq),
        workers=args.workers,
        rounds=args.rounds,
        full=args.full,
    )
    exp.build()
    print(
        f"{exp.model_cfg.name}: {exp.num_params/1e6:.1f}M params | "
        f"{args.algo} tau={exp.tau} alpha={args.alpha} m={args.workers}"
    )

    t0 = time.time()
    every = max(1, args.rounds // 10)

    def log(r, loss):
        if r % every == 0 or r == args.rounds - 1:
            print(f"round {r:4d}  loss {loss:.4f}  ({time.time()-t0:.0f}s)")

    exp.fit(log=log)
    if args.ckpt:
        checkpoint.save(args.ckpt, exp.state)
        print(f"checkpoint -> {args.ckpt}")


if __name__ == "__main__":
    main()
