"""Operation and byte counts of the metric readers against hand counts
at internlm2-1.8b's shapes (d 2048, 16 heads of 128, 8 KV heads,
d_ff 8192, vocab 92544; side network d 256, 2 heads, 1 KV head, d_ff
1024), batch 4 x 1024."""

import pytest

import benchcells  # noqa: F401  (puts the checkout's root on sys.path)
from bench import manifest

CELL = manifest.cell("internlm2-1.8b.capture")
CONFIG, TRAFFIC = CELL.config, CELL.traffic
PEAKS = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
T = 4 * 1024


def test_quant_matmul_calls_flops_and_bytes():
    mod = manifest.metric_module("quant_matmul_roofline")
    calls = mod.calls(CONFIG, TRAFFIC)
    assert len(calls) == 24 * 7
    # per layer: q and o 2048x2048, k and v 2048x1024, gate/up/down 2048x8192
    per_layer = 2 * T * (2 * 2048 * 2048 + 2 * 2048 * 1024 + 3 * 2048 * 8192)
    assert sum(mod.flops(*c) for c in calls) == 24 * per_layer
    # wq: f32 x in (4096x2048), int8 w (2048x2048), 16 scale columns, f32 out
    assert mod.bytes_moved(T, 2048, 2048) == 4 * T * 2048 + 2048 * 2048 + 4 * 2048 * 16 + 4 * T * 2048


def test_lmhead_ce_work():
    fwd, bwd = manifest.metric_module("lmhead_ce_roofline").work(CONFIG, TRAFFIC)
    # forward: every logit once; backward: recomputed logits and dh
    assert fwd == (2 * T * 2048 * 92544, 4 * T * 2048 + 4 * 2048 * 92544 + 12 * T)
    assert bwd == (4 * T * 2048 * 92544, 2 * 4 * T * 2048 + 4 * 2048 * 92544 + 12 * T)


def test_training_flops_per_token():
    mod = manifest.metric_module("mfu.train")
    attn = 2 * 2 * 2048 * 1025 / 2  # causal scores and values, mean over 1024 rows
    frozen = 24 * (2 * (2048 * 2048 * 2 + 2048 * 1024 * 2 + 3 * 2048 * 8192) + attn)
    head = 2 * 2 * 2048 * 92544
    side_layer = 2 * (256 * 256 * 2 + 256 * 128 * 2 + 3 * 256 * 1024) + 2 * 2 * 256 * 1025 / 2
    side = 3 * (24 * side_layer + 2 * 256 * 2048) + 2 * 25 * 2 * 2048 * 256
    assert mod.flops_per_token(CONFIG, TRAFFIC) == pytest.approx(frozen + head + side)
    cached = dict(TRAFFIC, phase="cached")
    assert mod.flops_per_token(CONFIG, cached) == pytest.approx(head + side)


def test_paged_attention_work():
    f, b = manifest.metric_module("paged_attention_roofline").step_work(CONFIG, [100, 28])
    assert f == 24 * 4 * 2048 * 128
    # int8 K and V of 128 tokens over 8 KV heads, an f32 scale each, f32 q and o rows
    assert b == 24 * (128 * 8 * (2 * 128 + 8) + 2 * 2 * 4 * 2048)


def test_roofline_share_from_a_record():
    mod = manifest.metric_module("quant_matmul_roofline")
    least = sum(max(mod.flops(*c) / PEAKS["bf16_flops"], mod.bytes_moved(*c) / PEAKS["hbm_bytes_per_s"])
                for c in mod.calls(CONFIG, TRAFFIC))
    record = {"config": CONFIG, "traffic": TRAFFIC, "peaks": PEAKS, "steps": 3,
              "trace": {"op_time_s": {"quant_matmul.1": 3 * least * 2, "fusion": 1.0},
                        "op_count": {"quant_matmul.1": 504, "fusion": 9}}}
    share, extra = mod.read(record)
    assert share == pytest.approx(50.0) and extra == {"bound": "compute"}
