"""Roofline share of the fused round boundary (pullback toward the anchor,
worker mean, anchor momentum) over the bf16 plane, once per round: the
worker-stacked x read and written, the anchor and its momentum read, the
next anchor and momentum written."""

KERNEL = "pullback_momentum_flat"


def bytes_moved(stacked_elements: int, workers: int) -> float:
    n = stacked_elements // workers
    return 2.0 * (2 * stacked_elements + 4 * n)


def flops(stacked_elements: int, workers: int) -> float:
    n = stacked_elements // workers
    return 4.0 * stacked_elements + 4.0 * n


def read(rec):
    from bench.trace_reduce import seconds_of

    t, n, j = rec["trace"], rec["counts"], rec["job"]
    s = seconds_of(t, KERNEL) if t else 0.0
    if not s or not n.get("rounds"):
        return None
    e, m, p = n["plane_elements"], j["workers"], rec["peak"]
    least = n["rounds"] * max(bytes_moved(e, m) / p["hbm_bytes_per_s"], flops(e, m) / p["bf16_flops"])
    return 100.0 * least / s
