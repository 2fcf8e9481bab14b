"""Roofline share of the fused SGD (Nesterov momentum) step over the
worker-stacked bf16 plane: x, g and m read, x and m written, once per local
step. It is bandwidth-bound; the least time is the bytes over HBM bandwidth."""

KERNEL = "sgd_step_flat"


def bytes_moved(elements: int) -> float:
    return 5 * 2.0 * elements


def flops(elements: int) -> float:
    return 6.0 * elements  # m = mu*m + g; u = mu*m + g; x - lr*u


def read(rec):
    from bench.trace_reduce import seconds_of

    t, n, j = rec["trace"], rec["counts"], rec["job"]
    s = seconds_of(t, KERNEL) if t else 0.0
    if not s or not n.get("rounds"):
        return None
    e, p = n["plane_elements"], rec["peak"]
    least = n["rounds"] * j["tau"] * max(bytes_moved(e) / p["hbm_bytes_per_s"], flops(e) / p["bf16_flops"])
    return 100.0 * least / s
