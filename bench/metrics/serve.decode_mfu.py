"""The whole decode step's share of the chip's roofline: the least time for
the traced decode steps (every weight read once a step, plus the resident
KV; or their FLOPs, if that is larger) over the device time of the program
that holds the paged decode kernel."""

KERNEL = "paged_attend_decode"


def weight_bytes(c: dict) -> float:
    """Weights a decode step reads whole, bf16: the layers and the head.
    The embedding is a gather of a few rows."""
    q, kv, d = c["num_heads"] * c["head_dim"], c["num_kv_heads"] * c["head_dim"], c["d_model"]
    per_layer = d * q + 2 * d * kv + q * d + 3 * d * c["d_ff"] + 2 * d + (q + 2 * kv if c["qkv_bias"] else 0)
    return 2.0 * (c["num_layers"] * per_layer + d * c["vocab_size"] + d)


def step_flops(c: dict, tokens: int, resident_tokens: int) -> float:
    matmul = weight_bytes(c) / 2.0
    return 2.0 * matmul * tokens + 4.0 * resident_tokens * c["num_heads"] * c["head_dim"] * c["num_layers"]


def step_bytes(c: dict, steps: int, resident_tokens: int) -> float:
    return steps * weight_bytes(c) + 2.0 * 2 * resident_tokens * c["num_kv_heads"] * c["head_dim"] * c["num_layers"]


def read(rec):
    from bench.trace_reduce import program_seconds

    t, n, c = rec["trace"], rec["counts"], rec["config"]
    s = program_seconds(t, KERNEL) if t else 0.0
    if not s or not n.get("decode_steps"):
        return None
    p = rec["peak"]
    least = max(step_bytes(c, n["decode_steps"], n["decode_kv_tokens"]) / p["hbm_bytes_per_s"],
                step_flops(c, n["decode_tokens"], n["decode_kv_tokens"]) / p["bf16_flops"])
    return 100.0 * least / s
