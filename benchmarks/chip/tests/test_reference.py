"""The llama family's plain reference against the program on the CPU at a
tiny size.

In float32 with XLA execution the program computes the reference's
mathematics in another order: the logits agree to float32 rounding.  The
served configuration (bf16, photonic W8A8) against the reference, and the
control, are in ``test_faults.py``."""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import weights
from cell import load_family
from tiny import tiny_conf

llama = load_family("llama")

# float32 XLA vs the float32 reference: summation order only.
F32_REL = 1e-4


def _program(conf, seed, dtype):
    from repro.api import Program
    from repro.models import transformer as tfm
    cfg = llama.program_config(conf)
    cfg = dataclasses.replace(cfg, compute_dtype=dtype, param_dtype=dtype)
    params = weights.make_params(tfm.abstract_params(cfg), seed,
                                 jnp.bfloat16)
    params = jax.tree.map(lambda x: x.astype(dtype), params)
    return Program.build(cfg, params)


@pytest.mark.parametrize("name", ["deepseek-7b", "deepseek-7b-rb"])
def test_float32_xla_program_equals_reference(name):
    conf = dict(tiny_conf(name), execution="xla")
    seed = 2 ** 40 + 3
    prog = _program(conf, seed, "float32")
    prompt = np.random.default_rng(0).integers(1, conf["vocab_size"], 37,
                                               dtype=np.int32)
    with jax.default_matmul_precision("highest"):
        got, _ = prog.prefill({"tokens": jnp.asarray(prompt[None])}, 37)
    got = np.asarray(got[0, :conf["vocab_size"]], np.float64)
    want = np.asarray(llama.logits(conf, seed, [prompt], [[36]])[0],
                      np.float64)
    rel = np.linalg.norm(got - want) / np.linalg.norm(want)
    assert rel < F32_REL, rel


def test_weights_match_the_programs_tree():
    """The reference regenerates by name exactly the leaf the harness put
    into the program's tree."""
    from repro.models import transformer as tfm
    conf = tiny_conf("deepseek-7b-rb")
    cfg = llama.program_config(conf)
    params = weights.make_params(tfm.abstract_params(cfg), 5, jnp.bfloat16)
    stacked = params["segments"]["main"]["l0"]["ffn"]["w_down"]
    base = weights.base_key(5)
    for r in range(stacked.shape[0]):
        one = weights.leaf(base, "segments/main/l0/ffn/w_down",
                           stacked.shape[1:], jnp.bfloat16, r)
        np.testing.assert_array_equal(np.asarray(one), np.asarray(stacked[r]))
