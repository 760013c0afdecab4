"""Operation and byte counts of the llama family against numbers worked out
by hand for deepseek-7b, and the peak table."""
from __future__ import annotations

import json
from pathlib import Path

import pytest

import workcount
from cell import load_family
from peaks import peaks_for

CONFIGS = Path(__file__).resolve().parents[1] / "configs"
V5E = peaks_for("TPU v5 lite")
llama = load_family("llama")


def conf(name):
    return json.loads((CONFIGS / f"{name}.json").read_text())


def test_deepseek_layer_params():
    # MHA attention 4 * 4096^2, SwiGLU 3 * 4096 * 11008
    layer = llama.layer_matmuls(conf("deepseek-7b"))
    assert sum(k * n for _, k, n in layer) == 202_375_168


def test_rb_counts_every_logical_pass():
    dense, rb = conf("deepseek-7b"), conf("deepseek-7b-rb")
    unembed = 4096 * 102400
    def per_token(c):
        return sum(k * n for k, n in llama.token_matmuls(c))
    assert per_token(dense) == 8 * 202_375_168 + unembed
    # 10 physical blocks, each read by 3 sequential passes: 30 reads
    assert per_token(rb) == 30 * 202_375_168 + unembed


def test_decode_mvm_call_is_bandwidth_bound():
    w = workcount.mvm_call(16, 4096, 11008, V5E)
    assert w.int8_ops == 2 * 16 * 4096 * 11008 == 1_442_840_576
    assert w.bytes == 4096 * 11008 + 2 * (16 * 4096 + 16 * 11008)
    assert w.bytes == 45_572_096
    assert w.min_seconds == pytest.approx(45_572_096 / 819e9)


def test_prefill_mvm_call_is_compute_bound():
    w = workcount.mvm_call(512, 4096, 11008, V5E)
    assert w.min_seconds == pytest.approx(2 * 512 * 4096 * 11008 / 393e12)


def test_causal_attention_chunk():
    assert workcount.causal_pairs(512, 1024) == 512 * 1024 + 512 * 513 // 2
    w = llama.attention_call(512, 1024, conf("deepseek-7b"), V5E)
    assert w.bf16_flops == 4 * 32 * 128 * 655_616
    assert w.bytes == 2 * 128 * (2 * 512 * 32 + 2 * 1536 * 32)
    L = workcount.attention_calls(512, 1024, llama, conf("deepseek-7b-rb"),
                                  V5E)
    assert L.bf16_flops == 30 * w.bf16_flops


def test_ideal_seconds_splits_the_peaks():
    c = conf("deepseek-7b")
    # one query at offset 999 keeps 1000 pairs
    got = workcount.ideal_seconds(10, 2, [(1, 999)], llama, c, V5E)
    mm = 2 * (10 * 8 * 202_375_168 + 2 * 4096 * 102400)
    att = 4 * 32 * 128 * 1000 * 8
    assert got == pytest.approx(mm / 393e12 + att / 197e12)


def test_unknown_device_has_no_peaks():
    with pytest.raises(KeyError):
        peaks_for("TPU v4")
