"""The FLOP and byte functions of the roofline and mfu metrics against
hand-worked shapes, and the peaks table's guard."""
import bench_tiny  # noqa: F401  (puts the checkout on sys.path)

import json
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]


def metric(name):
    from bench.harness import load_module

    return load_module(ROOT / "bench" / "metrics" / f"{name}.py")


def config(name):
    return json.loads((ROOT / "bench" / "configs" / f"{name}.json").read_text())


def test_danube_l4_training_flops_per_token():
    mfu = metric("train.mfu")
    c = config("h2o-danube-1.8b-l4")
    # per layer: wq 2560x2560, wk and wv 2560x640, wo 2560x2560, SwiGLU
    # 3x2560x6912; four layers and the 2560x32000 head
    assert mfu.matmul_params(c) == 4 * (2 * 2560 * 2560 + 2 * 2560 * 640 + 3 * 2560 * 6912) + 2560 * 32000
    assert mfu.matmul_params(c) == 359_792_640
    assert mfu.mean_context(2048, 4096) == pytest.approx(1024.5)
    # 6 x 359.8M = 2.1588 GFLOP, plus attention 3 x 4 x 1024.5 x 2560 x 4 = 0.1259
    assert mfu.flops_per_token(c, 2048) == 2_158_755_840 + 125_890_560  # 2.2846 GFLOP


def test_mfu_reads_the_traced_window():
    mfu = metric("train.mfu")
    c = config("h2o-danube-1.8b-l4")
    rec = dict(trace={"busy_s": 1.0, "window_s": 2.0}, counts={"tokens": 8192 * 4}, config=c,
               job={"seq_len": 2048}, peak={"bf16_flops": 197e12}, chips=1)
    assert mfu.read(rec) == pytest.approx(100 * mfu.flops_per_token(c, 2048) * 16384 / 197e12)
    assert mfu.read(dict(rec, trace=None)) is None


def test_window_cuts_the_causal_pairs():
    fa = metric("train.flash_attention_fwd_roofline")
    assert fa.causal_pairs(4, None) == 1 + 2 + 3 + 4
    assert fa.causal_pairs(6, 2) == 1 + 2 + 2 + 2 + 2 + 2
    c = {"num_heads": 32, "num_kv_heads": 8, "head_dim": 80, "sliding_window": 4096}
    assert fa.flops(c, 2048) == 4.0 * (2048 * 2049 // 2) * 32 * 80
    assert fa.bytes_moved(c, 2048) == 2.0 * 2048 * 80 * (64 + 16)


def test_paged_attention_counts_resident_tokens_only():
    pa = metric("serve.paged_attend_roofline")
    c = config("qwen2-7b-l8")
    # 3 slots holding 10, 100 and 1000 tokens: 1110 keys and values at each
    # of 8 layers, 4 KV heads of 128, bf16 -- whatever the pages walked
    assert pa.kv_bytes(c, 1110) == 2 * 2 * 1110 * 4 * 128 * 8
    assert pa.flops(c, 1110) == 4 * 1110 * 28 * 128 * 8
    trace = {"ops": {"paged_attend_decode": 1e-3}, "modules": {}}
    rec = dict(trace=trace, counts={"decode_kv_tokens": 1110}, config=c,
               peak={"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}, chips=1)
    assert pa.read(rec) == pytest.approx(100 * pa.kv_bytes(c, 1110) / 819e9 / 1e-3)
    assert pa.read(dict(rec, counts={"decode_kv_tokens": 0})) is None


def test_decode_step_reads_every_weight_once():
    dm = metric("serve.decode_mfu")
    c = config("qwen2-7b-l8")
    layer = 2 * 3584 * 3584 + 2 * 3584 * 512 + 3 * 3584 * 18944 + 2 * 3584 + 3584 + 2 * 512
    assert dm.weight_bytes(c) == 2 * (8 * layer + 3584 * 152064 + 3584)
    assert dm.step_bytes(c, 2, 0) == 2 * dm.weight_bytes(c)


def test_plane_sweeps_are_bandwidth_bound():
    sgd = metric("train.sgd_step_roofline")
    pb = metric("train.pullback_roofline")
    assert sgd.bytes_moved(1000) == 10_000  # x, g, m in; x, m out; bf16
    assert pb.bytes_moved(2000, 2) == 2 * (2 * 2000 + 4 * 1000)
    # at 197 TFLOP/s and 819 GB/s both sweeps are bound by bytes
    assert sgd.flops(1000) / 197e12 < sgd.bytes_moved(1000) / 819e9
    assert pb.flops(2000, 2) / 197e12 < pb.bytes_moved(2000, 2) / 819e9


def test_peaks_table_has_its_source():
    table = json.loads((ROOT / "bench" / "peaks.json").read_text())
    assert "TPU v5e" in table["source"]
    v5e = table["devices"]["TPU v5 lite"]
    assert (v5e["bf16_flops"], v5e["hbm_bytes_per_s"], v5e["hbm_bytes"]) == (197e12, 819e9, 16e9)
