"""Model FLOP utilization of training: the FLOPs a token needs (forward and
backward) times the tokens per second per chip of the traced rounds (their
tokens over the traced window, from its first round's start to its last
round's loss read), over the chip's bf16 peak. Recomputed work does not
count."""


def matmul_params(c: dict) -> int:
    """Weights that multiply every token: the layers' projections and the
    head. The embedding is a gather and does not count."""
    q, kv = c["num_heads"] * c["head_dim"], c["num_kv_heads"] * c["head_dim"]
    d = c["d_model"]
    per_layer = d * q + 2 * d * kv + q * d + 3 * d * c["d_ff"]
    return c["num_layers"] * per_layer + d * c["vocab_size"]


def mean_context(seq: int, window) -> float:
    """Keys a causal query attends to, averaged over the positions of a row."""
    w = window or seq
    return sum(min(i + 1, w) for i in range(seq)) / seq


def flops_per_token(c: dict, seq: int) -> float:
    """6 per matmul weight (forward 2, backward 4), plus attention's scores
    and values: 4 * context * heads * head_dim forward, three times that
    with the backward."""
    attn = 3 * 4 * mean_context(seq, c["sliding_window"]) * c["num_heads"] * c["head_dim"] * c["num_layers"]
    return 6.0 * matmul_params(c) + attn


def read(rec):
    n, t = rec["counts"], rec["trace"]
    if not t or not n.get("tokens") or t["window_s"] <= 0:
        return None
    tok_s = n["tokens"] / t["window_s"] / rec["chips"]
    return 100.0 * flops_per_token(rec["config"], rec["job"]["seq_len"]) * tok_s / rec["peak"]["bf16_flops"]
