"""Roofline share of the paged decode-attention kernel: the least time for
the keys and values the decoding slots hold (resident tokens only, not the
pages the kernel walks), over the kernel's summed device time."""

KERNEL = "paged_attend_decode"


def kv_bytes(c: dict, resident_tokens: int) -> float:
    """K and V of every resident token at every layer, bf16."""
    return 2.0 * 2 * resident_tokens * c["num_kv_heads"] * c["head_dim"] * c["num_layers"]


def flops(c: dict, resident_tokens: int) -> float:
    """Each decoding query against each resident key: QK^T and PV."""
    return 4.0 * resident_tokens * c["num_heads"] * c["head_dim"] * c["num_layers"]


def read(rec):
    from bench.trace_reduce import seconds_of

    t, n, c = rec["trace"], rec["counts"], rec["config"]
    s = seconds_of(t, KERNEL) if t else 0.0
    kv = n.get("decode_kv_tokens", 0)
    if not s or not kv:
        return None
    p = rec["peak"]
    return 100.0 * max(kv_bytes(c, kv) / p["hbm_bytes_per_s"], flops(c, kv) / p["bf16_flops"]) / s
