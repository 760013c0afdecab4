"""A configuration's family: how a cell finds it, that every path of the
harness goes through it, and that the llama family maps, computes and
counts exactly what the harness did before families existed."""
from __future__ import annotations

import dataclasses
import hashlib
import json
import time
import types
from pathlib import Path

import numpy as np
import pytest

import harness
import stub_family
import workcount
from cell import DEFAULT_FAMILY, load_cell, load_family
from peaks import peaks_for
from tiny import tiny_cell, tiny_conf

CHIP = Path(__file__).resolve().parents[1]
ROOT = CHIP.parents[1]
V5E = peaks_for("TPU v5 lite")
llama = load_family("llama")

# The ModelConfig each configuration file mapped to before the mapping
# moved into families/llama.py.
BEFORE = {
    "deepseek-7b": "ModelConfig(name='deepseek-7b', family='dense', num_layers=8, d_model=4096, num_heads=32, num_kv_heads=32, d_ff=11008, vocab_size=102400, head_dim=128, rope_theta=10000.0, norm='rms', norm_eps=1e-06, mlp_act='swiglu', moe=None, mla=None, ssm=None, vision=None, audio=None, attn_every=1, attn_offset=0, group_size=1, reuse=None, tie_embeddings=False, compute_dtype='bfloat16', param_dtype='bfloat16', fsdp=False, sub_quadratic=False, padded_vocab=102400, execution='photonic')",  # noqa: E501
    "deepseek-7b-rb": "ModelConfig(name='deepseek-7b-rb', family='dense', num_layers=30, d_model=4096, num_heads=32, num_kv_heads=32, d_ff=11008, vocab_size=102400, head_dim=128, rope_theta=10000.0, norm='rms', norm_eps=1e-06, mlp_act='swiglu', moe=None, mla=None, ssm=None, vision=None, audio=None, attn_every=1, attn_offset=0, group_size=1, reuse=ReuseConfig(granularity='block', num_basic=10, reuse_times=3, transforms=('identity', 'shuffle', 'transpose'), shuffle_groups=8, shuffle_block=0, seed=0), tie_embeddings=False, compute_dtype='bfloat16', param_dtype='bfloat16', fsdp=False, sub_quadratic=False, padded_vocab=102400, execution='photonic')",  # noqa: E501
}

# sha256 of the float32 reference logits of three sequences at the tiny
# size (seed 2**33 + 17), as the reference computed them before it moved.
LOGITS_BEFORE = {
    ("deepseek-7b", None):
        "364a22c03bef2a989516cc7b4ee9c36ffca96faf418b6e204ab6e1d318d95c1b",
    ("deepseek-7b", 4):
        "0b417a0d618ae88b18f6843c6c30300d0ab0bd90744f79d6eda3f97b872b87ff",
    ("deepseek-7b-rb", None):
        "8fb01e1ffb32974027d9d56c847758ebec59e49458929740202b8c3fbb1efc4f",
    ("deepseek-7b-rb", 4):
        "18440866882be4347d886677e0587b2788a04ef29cf2f6a42f328aaaefb29bc8",
}

# Work counts before the move, for 5000 decode tokens and 40 prefill
# calls (_traced_work): (mvm_calls(16).min_seconds, ideal_seconds).
WORK_BEFORE = {"deepseek-7b": (0.002517494622710623, 0.14426802936903382),
               "deepseek-7b-rb": (0.008020818207570186,
                                  0.5115558665145384)}


def conf(name):
    return json.loads((CHIP / "configs" / f"{name}.json").read_text())


@pytest.mark.parametrize("name", ["deepseek-7b", "deepseek-7b-rb"])
def test_program_config_is_the_mapping_before(name):
    from repro.configs import ModelConfig
    from repro.core.prm import ReuseConfig
    before = eval(BEFORE[name], {"ModelConfig": ModelConfig,
                                 "ReuseConfig": ReuseConfig})
    assert llama.program_config(conf(name)) == before


@pytest.mark.parametrize("name", ["deepseek-7b", "deepseek-7b-rb"])
@pytest.mark.parametrize("bits", [None, 4])
def test_reference_logits_are_bitwise_the_ones_before(name, bits):
    rng = np.random.default_rng(7)
    seqs = [rng.integers(1, 512, n, dtype=np.int32) for n in (37, 90, 12)]
    rows = [np.arange(36), np.arange(50, 89), np.arange(0, 12, 3)]
    got = np.asarray(llama.logits(tiny_conf(name), 2 ** 33 + 17, seqs, rows,
                                  bits, (5, 192)), np.float32)
    assert got.shape == (79, 512)
    assert hashlib.sha256(got.tobytes()).hexdigest() == \
        LOGITS_BEFORE[(name, bits)]


def _traced_work():
    """(decode tokens' keys seen, prefill calls as (q_offset, rows))."""
    rng = np.random.default_rng(11)
    decode_ctx = [int(x) for x in rng.integers(65, 2049, 5000)]
    prefill = [(int(o), int(r)) for o, r in zip(
        rng.integers(0, 3, 40) * 512, rng.integers(1, 513, 40))]
    return decode_ctx, prefill


@pytest.mark.parametrize("name", ["deepseek-7b", "deepseek-7b-rb"])
def test_work_counts_are_exactly_the_ones_before(name):
    c = conf(name)
    decode_ctx, prefill = _traced_work()
    attention = ([(1, k - 1) for k in decode_ctx]
                 + [(r, o) for o, r in prefill])
    tokens = len(decode_ctx) + sum(r for _, r in prefill)
    got = (workcount.mvm_calls(16, llama, c, V5E).min_seconds,
           workcount.ideal_seconds(tokens, len(decode_ctx) + 17, attention,
                                   llama, c, V5E))
    assert got == WORK_BEFORE[name]


def test_a_file_without_model_type_is_llama():
    assert DEFAULT_FAMILY == "llama"
    for w in ("deepseek-7b.chat", "deepseek-7b-rb.chat-4slots"):
        assert load_cell(w).family is llama


def test_unknown_model_type_fails_in_load_cell(tmp_path):
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    bench["configs"][0]["file"] = "c.json"
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    (tmp_path / "c.json").write_text(json.dumps(
        dict(conf("deepseek-7b"), model_type="no_such_family")))
    with pytest.raises(FileNotFoundError,
                       match="families/no_such_family.py"):
        load_cell(bench["workloads"][0]["name"], root=tmp_path)
    with pytest.raises(ValueError, match="not a family name"):
        load_family("../llama")


@pytest.mark.parametrize("arch", ["deepseek-v2-lite-16b", "mamba2-780m",
                                  "granite-moe-1b-a400m",
                                  "llama-3.2-vision-11b", "whisper-medium"])
def test_llama_refuses_an_arch_of_another_family(arch):
    """Mapped onto by replacing sizes, such an arch would keep its MLA,
    experts, SSM or encoder while the llama reference computed a dense
    decoder from the same seed."""
    c = dict(conf("deepseek-7b"), architecture=arch)
    with pytest.raises(ValueError, match=r"families/<model_type>\.py"):
        llama.program_config(c)


@pytest.fixture
def stub():
    stub_family.CALLS.clear()
    return stub_family


def test_a_run_builds_and_checks_through_the_cells_family(stub):
    cell = dataclasses.replace(tiny_cell(), family=stub)
    r = harness.run_cell(cell, 5, 1.5, False, time.perf_counter())
    assert r["correct"]
    assert stub.CALLS["program_config"] == 1     # harness.build
    assert stub.CALLS["logits"] == 1             # check.reference_gaps


def test_the_work_readers_count_the_cells_family(stub):
    c = tiny_conf("deepseek-7b")
    d, vp = 128, 512
    steps = [types.SimpleNamespace(decode_steps=1, prefill=[]),
             types.SimpleNamespace(decode_steps=1,
                                   prefill=[(64, 0, 40, True)])]
    ctx = harness.LayerContext(
        conf=c, family=stub, serve={"capacity": 4}, peaks=V5E, steps=steps,
        trace=types.SimpleNamespace(
            kernel_seconds={("decode", "fused_mvm"): 1e-3}, window_s=2.0),
        decode_ctx=[10, 20])
    one = sum(workcount.mvm_call(4, k, n, V5E).min_seconds
              for k, n in ((d, 3 * d), (3 * d, d), (d, vp)))
    assert harness.read_metric("fused_mvm_roofline.decode", ctx) == \
        pytest.approx(100.0 * 2 * one / 1e-3)
    assert stub.CALLS["token_matmuls"] == 1
    # 2 decode tokens and 40 prompt tokens through both layer matmuls of
    # the stub (d x 3d twice a token), 3 unembedding rows; attention: the
    # stub's 1000 * q_len + q_offset per call, in each of 2 layers
    mm = 2.0 * (42 * 2 * 3 * d * d + 3 * d * vp)
    att = 2 * ((1000 + 9) + (1000 + 19) + 40 * 1000)
    assert harness.read_metric("mfu.decode", ctx) == pytest.approx(
        100.0 * (mm / V5E.int8_ops + att / V5E.bf16_flops) / 2.0)
    assert stub.CALLS["attention_call"] == 3
