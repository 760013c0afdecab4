"""The PRM scan reads its prepared banks in place (``core/sharing.py``).

``run_stack`` keeps ``PreparedTensor`` banks out of the scan's xs and hands
``block_fn`` a ``BankLayer`` view of each stack at the traced block index;
the fused single-device kernel reads the layer's tiles straight out of the
stack, and every other consumer takes the layer's slice.  Checked here on a
tiny R&B stack (3 blocks x 3 reuses, identity / shuffle / transpose, and a
blocked-shuffle plan) at 128-aligned widths, so that every layer matmul
qualifies:

  * Program decode and chunked-prefill logits are bitwise those of the same
    stack with every layer's bank sliced by the scan and each block's KV
    cache read through its slice ``cache[r]``;
  * ``kernel.calls{kind=fused_stacked}`` counts every layer matmul of a
    compiled cell, and only the unembedding stays ``fused``;
  * the XLA backend, a ``NoiseConfig`` and a two-device mesh take the
    sliced path, with outputs unchanged.
"""
import dataclasses
import functools
import os
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import repro.api as api
from repro.api import Program
from repro.configs.base import ModelConfig
from repro.core import backend as backend_lib
from repro.core import sharing
from repro.core.noise import NoiseConfig
from repro.core.prepared import BankLayer, prepare_tensor
from repro.core.prm import ReuseConfig
from repro.models import transformer as tfm
from repro.obs import metrics

B, W, L = 2, 8, 16          # slots, prefill chunk width, cache length
MATMULS_PER_LAYER = 7       # wq wk wv wo + w_gate w_up w_down

PLANS = {
    "group_shuffle": ReuseConfig(num_basic=3, reuse_times=3,
                                 transforms=("identity", "shuffle",
                                             "transpose"),
                                 shuffle_groups=4, seed=1),
    "block_shuffle": ReuseConfig(num_basic=3, reuse_times=3,
                                 transforms=("identity", "shuffle",
                                             "transpose"),
                                 shuffle_block=32, seed=2),
}


def rb_cfg(plan: str) -> ModelConfig:
    # MHA at 128-aligned widths: every attention projection is square (so
    # the transpose reuse applies) and every tile plan divides its axes
    return ModelConfig(name=f"rb-stack-{plan}", family="dense",
                       num_layers=9, d_model=128, num_heads=4,
                       num_kv_heads=4, d_ff=256, vocab_size=256,
                       compute_dtype="float32", reuse=PLANS[plan])


@functools.lru_cache(maxsize=None)
def rb_model(plan: str):
    cfg = rb_cfg(plan)
    params, _ = tfm.init_model(jax.random.PRNGKey(0), cfg)
    return cfg, params


def banks_in_xs(params):
    """Banks left in the scan xs: the scan slices each layer's
    ``PreparedTensor`` out of its stack.  (A plain Python loop over
    ``tree_index(params, r)`` is no bitwise reference: XLA fuses an
    unrolled stack differently from a scan body, which moves float32
    results by an ulp on any backend.)"""
    return params, lambda p_r, r: p_r


def block_cache_at(cache_leaf, r, t):
    """Reuse ``t``'s cache read through the block's slice ``cache[r]``."""
    return jax.lax.dynamic_index_in_dim(cache_leaf, r, 0, keepdims=False)[t]


def serve(prog: Program, tokens):
    """One prefill chunk into empty caches, then one decode step."""
    caches = prog.empty_caches(B, L)
    chunk_logits, caches = prog.prefill_chunk(tokens, caches, 0)
    dec_logits, _ = prog.decode(tokens[:, -1:], caches,
                                jnp.full((B,), W, jnp.int32))
    return np.asarray(chunk_logits), np.asarray(dec_logits)


def serve_sliced(prog: Program, tokens, monkeypatch):
    """``serve`` with every layer's bank sliced by the scan and each block's
    cache read through ``cache[r]`` (fresh jits of the same
    ``tfm.forward`` calls the Program cells make)."""
    cfg, backend, bank = prog.cfg, prog.backend, prog.bank
    act = api._serve_act_pspec(backend, B)

    @jax.jit
    def chunk(bank, tokens, caches):
        logits, caches, _ = tfm.forward(
            bank, cfg, {"tokens": tokens}, mode="prefill_chunk",
            caches=caches, pos=jnp.int32(0), execution=backend,
            act_pspec=act)
        return logits[:, -1], caches

    @jax.jit
    def decode(bank, tokens, caches, pos):
        logits, caches, _ = tfm.forward(
            bank, cfg, {"tokens": tokens}, mode="decode", caches=caches,
            pos=pos, execution=backend, act_pspec=act)
        return logits[:, 0], caches

    with monkeypatch.context() as m:
        m.setattr(sharing, "_split_banks", banks_in_xs)
        m.setattr(sharing, "_cache_at", block_cache_at)
        caches = prog.empty_caches(B, L)
        chunk_logits, caches = chunk(bank, tokens, caches)
        dec_logits, _ = decode(bank, tokens[:, -1:], caches,
                               jnp.full((B,), W, jnp.int32))
    return np.asarray(chunk_logits), np.asarray(dec_logits)


def kernel_calls(snapshot) -> dict:
    out = {}
    for key, v in snapshot["counters"].items():
        if key.startswith("kernel.calls{"):
            kind = key.split('kind="')[1].split('"')[0]
            out[kind] = out.get(kind, 0) + v
    return out


def _tokens(cfg):
    return jax.random.randint(jax.random.PRNGKey(3), (B, W), 1,
                              cfg.vocab_size)


def test_bank_layer_keeps_prepared_surface():
    w = jax.random.normal(jax.random.PRNGKey(0), (3, 2, 64, 32))
    stack = prepare_tensor(w, tag=7)
    for r in range(3):
        view = BankLayer(stack, jnp.int32(r))
        want = stack[r]
        assert view.shape == want.shape and view.ndim == want.ndim
        assert view.tag == 7 and view.astype(jnp.bfloat16) is view
        for f in ("wq", "scale", "wq_t", "scale_t", "w0_colsum",
                  "w0_rowsum_t"):
            np.testing.assert_array_equal(getattr(view, f),
                                          getattr(want, f))
        np.testing.assert_array_equal(view[1].wq, want[1].wq)


@pytest.mark.parametrize("plan", sorted(PLANS))
def test_photonic_stack_bitwise_and_counted(plan, monkeypatch):
    """The fused photonic cells read every layer's bank in place, and their
    logits are bitwise those of the sliced banks."""
    cfg, params = rb_model(plan)
    T = cfg.reuse.reuse_times
    prog = Program.build(cfg, params, execution="photonic")
    toks = _tokens(cfg)
    metrics.reset_default_registry()
    got = serve(prog, toks)
    calls = kernel_calls(metrics.default_registry().snapshot())
    want = serve_sliced(prog, toks, monkeypatch)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(g, w)
    # two cells (prefill chunk, decode), each traced once: every layer
    # matmul of the unrolled reuses reads in place, the unembedding does not
    assert calls == {"fused_stacked": 2 * T * MATMULS_PER_LAYER,
                     "fused": 2}


@pytest.mark.parametrize("execution", ["xla", "noise"])
def test_other_backends_take_the_slice(execution, monkeypatch):
    """Off the fused single-device kernel, the view hands out the layer's
    slice: no ``fused_stacked`` call, outputs those of the sliced banks."""
    cfg, params = rb_model("block_shuffle")
    backend = ("xla" if execution == "xla" else backend_lib.Backend(
        "photonic", noise=NoiseConfig(gain_sigma=0.02, dac_sigma=0.5,
                                      seed=5)))
    prog = Program.build(cfg, params, execution=backend)
    if execution == "xla":
        # a photonic bank under the xla backend: the dequantized fallback
        prog = dataclasses.replace(
            prog, bank=Program.build(cfg, params,
                                     execution="photonic").bank)
    toks = _tokens(cfg)
    metrics.reset_default_registry()
    got = serve(prog, toks)
    calls = kernel_calls(metrics.default_registry().snapshot())
    want = serve_sliced(prog, toks, monkeypatch)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(g, w)
    assert "fused_stacked" not in calls and "fused" not in calls
    if execution == "noise":
        assert calls.get("noisy", 0) > 0


MESH_SCRIPT = """
import sys
import numpy as np
import pytest
sys.path.insert(0, "tests")
import test_stacked_banks as t
from repro.api import Program
from repro.launch import mesh as mesh_lib
from repro.obs import metrics

cfg, params = t.rb_model("block_shuffle")
prog = Program.build(cfg, params, execution="photonic",
                     mesh=mesh_lib.parse_mesh("1x2"))
toks = t._tokens(cfg)
metrics.reset_default_registry()
got = t.serve(prog, toks)
calls = t.kernel_calls(metrics.default_registry().snapshot())
with pytest.MonkeyPatch.context() as mp:
    want = t.serve_sliced(prog, toks, mp)
for g, w in zip(got, want):
    np.testing.assert_array_equal(g, w)
assert "fused_stacked" not in calls and calls.get("sharded_fused", 0) > 0, \\
    calls
print("MESH_OK", calls)
"""


def test_mesh_takes_the_slice():
    """On a two-device mesh the kernels run under ``shard_map`` on the
    layer's slice; outputs are those of the sliced banks."""
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env.update({"XLA_FLAGS": "--xla_force_host_platform_device_count=2",
                "JAX_PLATFORMS": "cpu", "PYTHONPATH": "src"})
    out = subprocess.run(
        [sys.executable, "-c", MESH_SCRIPT], capture_output=True, text=True,
        timeout=600, env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    assert out.returncode == 0 and "MESH_OK" in out.stdout, (
        out.stdout[-3000:] + out.stderr[-3000:])
