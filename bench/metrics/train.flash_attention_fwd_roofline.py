"""Roofline share of the flash-attention forward kernel in training: the
least time the chip needs for the attention the traced rounds asked for,
over the kernel's summed device time. Causal attention counts the scores
it needs (the lower triangle, cut by the window), not the blocks walked."""

KERNEL = "flash_attention_bhsd"


def causal_pairs(seq: int, window) -> int:
    w = window or seq
    return sum(min(i + 1, w) for i in range(seq))


def flops(c: dict, seq: int) -> float:
    """One row through one layer: QK^T and PV, 2 FLOPs a multiply-add each."""
    return 4.0 * causal_pairs(seq, c["sliding_window"]) * c["num_heads"] * c["head_dim"]


def bytes_moved(c: dict, seq: int) -> float:
    """Q and the output at every head, K and V at every KV head, bf16."""
    return 2.0 * seq * c["head_dim"] * (2 * c["num_heads"] + 2 * c["num_kv_heads"])


def read(rec):
    from bench.trace_reduce import seconds_of

    t, n, c, j = rec["trace"], rec["counts"], rec["config"], rec["job"]
    s = seconds_of(t, KERNEL) if t else 0.0
    if not s or not n.get("rounds"):
        return None
    calls = n["rounds"] * j["tau"] * j["workers"] * j["batch_per_worker"] * c["num_layers"]
    p = rec["peak"]
    least = calls * max(flops(c, j["seq_len"]) / p["bf16_flops"], bytes_moved(c, j["seq_len"]) / p["hbm_bytes_per_s"])
    return 100.0 * least / s
