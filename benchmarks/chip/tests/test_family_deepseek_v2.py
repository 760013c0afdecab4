"""The deepseek_v2 family: the mapping of DeepSeek-V2-Lite's file onto the
program, what it refuses, its work count by hand, and a whole run of a
tiny cell through the harness (the program against the reference, served
logits against the reference in ``tests/test_deepseek_v2.py``)."""
from __future__ import annotations

import copy
import dataclasses
import json
import time
from pathlib import Path

import jax.numpy as jnp
import numpy as np
import pytest

import weights
from cell import Cell, load_cell, load_family
from harness import run_cell
from peaks import peaks_for
from tiny import END_TO_END, tiny_conf, tiny_traffic

CHIP = Path(__file__).resolve().parents[1]
V5E = peaks_for("TPU v5 lite")
family = load_family("deepseek_v2")

SIZES = {"hidden_size": 64, "num_attention_heads": 4,
         "num_key_value_heads": 4, "kv_lora_rank": 32,
         "qk_nope_head_dim": 16, "qk_rope_head_dim": 16, "v_head_dim": 16,
         "intermediate_size": 160, "moe_intermediate_size": 24,
         "n_routed_experts": 8, "num_experts_per_tok": 3, "vocab_size": 512}
# Widest gap of a sound tiny photonic run 0.22-0.34 over three seeds, the
# int4 control 3.98 or more (CPU).
TINY_LIMIT = 0.8


def conf():
    return json.loads((CHIP / "configs" / "deepseek-v2-lite.json")
                      .read_text())


def test_the_cell_finds_the_family():
    assert load_cell("deepseek-v2-lite.longchat").family is family


def test_program_config_is_the_published_model_six_layers_deep():
    from repro.configs import get_arch
    cfg = family.program_config(conf())
    want = dataclasses.replace(get_arch("deepseek-v2-lite-16b"),
                               name="deepseek-v2-lite", num_layers=6,
                               compute_dtype="bfloat16",
                               param_dtype="bfloat16", execution="photonic")
    assert cfg == want
    assert cfg.moe.first_dense == 1 and cfg.moe.norm_topk_prob is False
    assert cfg.rope_scaling.factor == 40.0


@pytest.mark.parametrize("key,value", [
    ("q_lora_rank", 1536), ("scoring_func", "sigmoid"),
    ("topk_method", "group_limited_greedy"), ("n_group", 8),
    ("routed_scaling_factor", 16.0), ("tie_word_embeddings", True),
    ("num_key_value_heads", 8), ("moe_layer_freq", 2)])
def test_program_config_refuses_what_the_program_cannot_run(key, value):
    with pytest.raises(ValueError, match=key):
        family.program_config(dict(conf(), **{key: value}))


def test_program_config_refuses_another_rope_scaling():
    c = conf()
    c["rope_scaling"] = dict(c["rope_scaling"], type="linear")
    with pytest.raises(ValueError, match="yarn"):
        family.program_config(c)


def test_token_matmuls_by_hand():
    mm = family.token_matmuls(conf())
    att = [(2048, 3072), (2048, 576), (2048, 2048)]
    dense = [(2048, 10944), (2048, 10944), (10944, 2048)]
    moe = ([(2048, 1408), (2048, 1408), (1408, 2048)] * 6
           + [(2048, 2816), (2048, 2816), (2816, 2048)])
    assert mm == att + dense + (att + moe) * 5 + [(2048, 102400)]
    assert sum(k * n for k, n in mm) == 692977664


def test_attention_call_in_the_latent_space_by_hand():
    """One decode query at position 4095: 4096 kept pairs of 2*16*(2*512 +
    64) FLOPs, plus 2*16*512*(128 + 128) for absorbing w_ukv; 4096 keys of
    576 bf16 values, the query's 16*(512 + 64) and its 16*128 context."""
    w = family.attention_call(1, 4095, conf(), V5E)
    assert w.bf16_flops == 146800640.0
    assert w.bytes == 4741120.0
    assert w.min_seconds == max(w.bf16_flops / V5E.bf16_flops,
                                w.bytes / V5E.hbm_bytes)


def test_the_reference_draws_the_programs_leaves():
    from repro.models import transformer as tfm
    c = tiny_conf("deepseek-v2-lite", layers=3, sizes=SIZES)
    params = weights.make_params(
        tfm.abstract_params(family.program_config(c)), 5, jnp.bfloat16)
    base = weights.base_key(5)
    main = params["segments"]["main"]["l0"]
    for name, leaf in (("mixer/kv_norm/scale", main["mixer"]["kv_norm"]
                        ["scale"]),
                       ("ffn/w_up", main["ffn"]["w_up"])):
        np.testing.assert_array_equal(
            np.asarray(family._w(base, "main", name, leaf.shape[1:], 1)),
            np.asarray(leaf[1], np.float32))
    banks = family._expert_banks(base, 1, conf_key=family.conf_key(c),
                                 seg="main")
    np.testing.assert_array_equal(np.asarray(banks[2]),
                                  np.asarray(main["ffn"]["w_down"][1]))
    pre = params["segments"]["pre"]["l0"]["ffn"]["w_gate"]
    np.testing.assert_array_equal(
        np.asarray(family._w(base, "pre", "ffn/w_gate", pre.shape[1:], 0)),
        np.asarray(pre[0], np.float32))


def _tiny_cell():
    c = tiny_conf("deepseek-v2-lite", layers=3, sizes=SIZES)
    return Cell(name="tiny.deepseek-v2-lite", chips=1, conf=c, family=family,
                traffic=tiny_traffic(),
                limits={"max_logit_gap": {"limit": TINY_LIMIT}},
                end_to_end=copy.deepcopy(END_TO_END), per_layer=[])


def test_a_tiny_run_is_correct_and_the_control_is_not():
    r = run_cell(_tiny_cell(), 47, 1.5, False, time.perf_counter(),
                 control_bits=4)
    assert r["correct"]
    assert r["control"]["max_logit_gap"] > TINY_LIMIT
