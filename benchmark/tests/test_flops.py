"""``train_flops_per_token`` and the kernel counts against hand counts."""

import json
import os

import pytest

from benchmark import flops
from benchmark.manifest import HERE, load_module


def _ref(name):
    with open(os.path.join(HERE, "configs", name + ".json")) as f:
        cfg = json.load(f)
    return load_module(os.path.join(HERE, "reference", name + ".py")), cfg


def test_gpt2_small_flops_per_token_by_hand():
    ref, cfg = _ref("gpt2-small")
    # per layer forward: q,k,v,o 4*768^2 and ffn 2*768*3072 weights, 2 FLOPs
    # each = 14,155,776; causal attention 2*S*H = 1,572,864 at S=1024
    per_layer = 2 * (4 * 768 * 768 + 2 * 768 * 3072) + 2 * 1024 * 768
    assert per_layer == 15_728_640
    head = 2 * 768 * 50257                     # tied LM head: 77,194,752
    assert ref.train_flops_per_token(cfg, 1024) == 3 * (12 * per_layer + head)
    assert ref.train_flops_per_token(cfg, 1024) == 797_815_296


def test_bert_base_flops_per_token_by_hand():
    ref, cfg = _ref("bert-base-uncased")
    per_layer = 2 * (4 * 768 * 768 + 2 * 768 * 3072) + 4 * 128 * 768
    assert per_layer == 14_548_992
    # MLM head on 20 of 128 positions: transform 768^2 + decoder 768*30522
    head = 2 * (768 * 768 + 768 * 30522) * 20 / 128
    assert ref.train_flops_per_token(cfg, 128, max_predictions=20) == \
        pytest.approx(3 * (12 * per_layer + head))
    assert ref.train_flops_per_token(cfg, 128, max_predictions=20) == \
        pytest.approx(546_292_512.0)


def test_mfu_and_flash_counts():
    # 50,000 tokens/s * 797,815,296 FLOPs on one 197 TFLOP/s chip
    assert flops.mfu_pct(50_000, 797_815_296, 1, 197e12) == pytest.approx(
        20.249, abs=1e-3)
    # causal: 6*S^2*D per head and sequence
    f = flops.flash_train_flops(batch=16, heads=12, seq_len=1024,
                                head_dim=64, layers=12)
    assert f == 6 * 1024 * 1024 * 64 * 16 * 12 * 12
    full = flops.flash_train_flops(batch=16, heads=12, seq_len=1024,
                                   head_dim=64, layers=12, causal=False)
    assert full == 2 * f
    b = flops.flash_train_bytes(batch=16, heads=12, seq_len=1024,
                                head_dim=64, layers=12)
    assert b == 12 * 1024 * 64 * 2 * 16 * 12 * 12
    pct, bound = flops.roofline_pct(f, b, 0.05, 197e12, 819e9)
    assert bound == "compute"
    assert pct == pytest.approx(100 * (f / 197e12) / 0.05)
