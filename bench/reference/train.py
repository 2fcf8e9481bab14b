"""Plain reference of Overlap-Local-SGD rounds (arXiv:2002.09539, eqs. 4-5
with anchor momentum, eqs. 10-11) under SGD with Nesterov momentum and weight decay.

Each worker takes tau local steps on its own rows, then the boundary pulls
every worker toward the anchor launched one round earlier and launches the
next anchor from the workers' mean:

    x_i <- (1 - alpha) x_i + alpha a          (pullback, eq. 4)
    v   <- beta v + (mean_i x_i - a)          (anchor momentum, eq. 10)
    a   <- a + v                              (next anchor, eq. 11)

All arithmetic is float32 at the highest matmul precision; parameters,
momenta and anchors are stored in the configuration's dtype between
operations, as the configuration states them. ``faults`` plants one of the
faults the comparison must catch, for the calibration and the tests.
"""
from __future__ import annotations

from typing import Callable, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference import model as ref

FAULTS = ("state_unchanged", "half_batch", "no_worker_mean")


def leaf_norms(tree, lead: bool = False) -> Dict[str, float]:
    """Per-leaf L2 norms (over every worker when ``lead``) keyed by path."""
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    out = {}
    for path, t in flat:
        name = "/".join(str(p.key) for p in path)
        out[name] = float(jnp.sqrt(jnp.sum(jnp.square(t.astype(jnp.float32)))))
    return out


def run_rounds(params0, rows, cfg: ref.Cfg, job: dict, rounds: int, mm: Callable = ref.matmul,
               fault: Optional[str] = None) -> dict:
    """Run ``rounds`` rounds from ``params0`` (the configuration's dtype) on
    ``rows``: one (workers, batch, seq + 1) block per local step, in order.

    Returns the round losses (mean over tau steps and workers), the per-leaf
    norms of the momentum after the first round, of each worker's distance
    from the anchor after the first boundary, of the first step's gradient,
    and of the parameters' change after the last round."""
    m, tau = job["workers"], job["tau"]
    opt, strat = job["optimizer"], job["strategy"]
    lr, mu, nesterov, wd = opt["lr"], opt["momentum"], opt["nesterov"], opt["weight_decay"]
    alpha, beta = strat["alpha"], strat["anchor_beta"]
    dtype = jax.tree.leaves(params0)[0].dtype
    f32 = lambda t: t.astype(jnp.float32)  # noqa: E731

    def worker_loss(p, block):
        toks = jnp.asarray(block)
        if fault == "half_batch":  # the mean over the first half of the positions only
            toks = toks[:, : toks.shape[1] // 2 + 1]
        return ref.loss(jax.tree.map(f32, p), toks[:, :-1], toks[:, 1:], cfg, mm)

    grad_fn = jax.jit(jax.value_and_grad(worker_loss))

    @jax.jit
    def sgd(x, mom, g):
        g = jax.tree.map(lambda gg, xx: gg + wd * f32(xx), g, x)
        mom = jax.tree.map(lambda mo, gg: (mu * f32(mo) + gg).astype(dtype), mom, g)
        upd = jax.tree.map(lambda mo, gg: mu * f32(mo) + gg, mom, g) if nesterov else jax.tree.map(f32, mom)
        return jax.tree.map(lambda xx, u: (f32(xx) - lr * u).astype(dtype), x, upd), mom

    @jax.jit
    def boundary(xs, v, anchor):
        xs = [jax.tree.map(lambda xx, a: ((1 - alpha) * f32(xx) + alpha * f32(a)).astype(dtype), x, anchor) for x in xs]
        if fault == "no_worker_mean":
            mean = xs[0]
        else:
            mean = jax.tree.map(lambda *t: (sum(f32(u) for u in t) / len(t)).astype(dtype), *xs)
        v = jax.tree.map(lambda vv, mn, a: (beta * f32(vv) + (f32(mn) - f32(a))).astype(dtype), v, mean, anchor)
        anchor = jax.tree.map(lambda a, vv: (f32(a) + f32(vv)).astype(dtype), anchor, v)
        return xs, v, anchor

    xs = [params0] * m
    moms = [jax.tree.map(jnp.zeros_like, params0) for _ in range(m)]
    v = jax.tree.map(jnp.zeros_like, params0)
    anchor = params0
    losses, out = [], {}
    step = 0
    for r in range(rounds):
        total = 0.0
        for _ in range(tau):
            block = rows[step]
            step += 1
            for i in range(m):
                loss, g = grad_fn(xs[i], block[i])
                total += float(loss)
                if "grad" not in out and i == 0:
                    out["grad"] = leaf_norms(g)
                if fault != "state_unchanged":
                    xs[i], moms[i] = sgd(xs[i], moms[i], g)
                del g
        losses.append(total / (tau * m))
        if fault != "state_unchanged":
            xs, v, anchor = boundary(xs, v, anchor)
        if r == 0:
            out["momentum"] = stacked_norms(moms)
            out["anchor"] = {f"{k}@{i}": n for i, x in enumerate(xs)
                             for k, n in leaf_norms(jax.tree.map(lambda a, b: f32(a) - f32(b), x, anchor)).items()}
    out["change"] = stacked_norms([jax.tree.map(lambda a, b: f32(a) - f32(b), x, params0) for x in xs])
    out["losses"] = losses
    return out


def stacked_norms(trees) -> Dict[str, float]:
    """Per-leaf norms over a list of per-worker trees, as over a stacked leaf."""
    per = [leaf_norms(t) for t in trees]
    return {k: float(np.sqrt(sum(p[k] ** 2 for p in per))) for k in per[0]}
