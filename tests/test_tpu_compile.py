"""The main path's Pallas kernels compile for a TPU v5e at real widths.

Interpret mode (every other kernel test) cannot see what the chip's
compiler refuses: unaligned blocks, bf16 vector arithmetic the v5e VPU
lacks, too much VMEM.  Here each kernel is lowered and compiled by the
installed TPU compiler against a described ``v5e:2x2`` topology — no chip
attached, nothing runs — at minitron-4b widths (d_model 3072, d_ff 9216,
vocab 256000, GQA 24/8 heads of 128) in bf16, the dtype every published
config serves in.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library, and under several test workers only
the one given this file must.  Keep these tests in this one file.
"""
import functools
import os
import re

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.kernels import blend, flash_attention, photonic_mvm
from repro.kernels.photonic_mvm import tile_plan

D_MODEL, D_FF, VOCAB = 3072, 9216, 256000
DS_MODEL, DS_FF = 4096, 11008           # deepseek-7b, the benchmark's widths
BF16 = jnp.bfloat16


@pytest.fixture(scope="module")
def topo(tmp_path_factory):
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    # the TPU library logs to /tmp unless told where
    os.environ.setdefault("TPU_LOG_DIR",
                          str(tmp_path_factory.mktemp("tpu_log")))
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler installed
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)
        compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def sds(topo):
    """ShapeDtypeStruct factory placed on one described v5e chip."""
    one_chip = SingleDeviceSharding(topo.devices[0])
    return lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                     sharding=one_chip)


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


MVM_CASES = [(M, K, N, tr, act)
             for M in (8, 512)                        # decode, prefill rows
             for K, N in ((D_MODEL, D_FF), (D_FF, D_MODEL))
             for tr in (False, True)
             for act in ("none", "silu")]
MVM_CASES += [(M, D_MODEL, VOCAB, tr, "none")          # the unembedding
              for M in (8, 512) for tr in (False, True)]


@pytest.mark.parametrize("M,K,N,transpose,activation", MVM_CASES)
def test_fused_mvm_compiles(sds, M, K, N, transpose, activation):
    bm, bk, bn = tile_plan(M, K, N, cap_m=128, cap_k=512, cap_n=512)
    fn = functools.partial(
        photonic_mvm.photonic_mvm_fused, bm=bm, bk=bk, bn=bn,
        transpose=transpose, activation=activation, interpret=False,
        out_dtype=BF16)
    _compile(fn, sds((M, K), BF16),
             sds((N, K) if transpose else (K, N), jnp.int8),
             sds((), jnp.float32), sds((N,), jnp.float32))


def _stacked_fn(M, K, N, transpose):
    bm, bk, bn = tile_plan(M, K, N, cap_m=128, cap_k=512, cap_n=512)
    assert photonic_mvm.reads_stack_in_place(K, N, bk, bn)
    return functools.partial(
        photonic_mvm.photonic_mvm_fused, bm=bm, bk=bk, bn=bn,
        transpose=transpose, activation="silu", interpret=False,
        out_dtype=BF16)


@pytest.mark.parametrize("M", (16, 512))
@pytest.mark.parametrize("K,N", ((DS_MODEL, DS_FF), (DS_FF, DS_MODEL)))
@pytest.mark.parametrize("transpose", (False, True))
def test_fused_mvm_stacked_compiles(sds, M, K, N, transpose):
    """The kernel reading one layer of a stacked bank in place."""
    R = 2
    fn = _stacked_fn(M, K, N, transpose)
    _compile(lambda x, wq, xs, ws, r: fn(x, wq, xs, ws, layer=r),
             sds((M, K), BF16),
             sds((R, N, K) if transpose else (R, K, N), jnp.int8),
             sds((), jnp.float32), sds((R, N), jnp.float32),
             sds((), jnp.int32))


def _s8_copies(hlo: str) -> list:
    """int8 results of a dynamic slice, an async slice or a copy."""
    return [line for line in hlo.splitlines() if "= s8[" in line
            and re.search(r"dynamic.slice|slice-start|copy-start", line)]


def test_scan_reads_stacked_bank_in_place(sds):
    """A scan over a 2-block bank that passes the stack and the block index
    to the kernel compiles with no int8 slice or copy of the bank; the same
    scan with the bank in its xs (the per-layer slice) copies it."""
    R, M, K, N = 2, 16, DS_MODEL, DS_FF
    fn = _stacked_fn(M, K, N, False)

    def in_place(x, wq, ws):
        def body(h, r):
            y = fn(h, wq, jnp.float32(0.01), ws, layer=r)
            return h + y[:, :K], None
        return jax.lax.scan(body, x, jnp.arange(R))[0]

    def sliced(x, wq, ws):
        def body(h, w):
            y = fn(h, w[0], jnp.float32(0.01), w[1])
            return h + y[:, :K], None
        return jax.lax.scan(body, x, (wq, ws))[0]

    args = (sds((M, K), BF16), sds((R, K, N), jnp.int8),
            sds((R, N), jnp.float32))
    assert _s8_copies(_compile(in_place, *args).as_text()) == []
    assert _s8_copies(_compile(sliced, *args).as_text())


def test_rb_decode_cell_copies_no_bank_or_cache(sds, monkeypatch):
    """The whole R&B decode program (2 blocks x 3 reuses, identity /
    shuffle / transpose) at d_model 512: no int8 bank and no block of the
    KV cache is copied out of its stack, i.e. the compiled module has no
    ``dynamic-slice_bitcast_fusion`` (the op the v5e trace charged for
    both).  At this size the compiler may still prefetch a whole small
    bank or cache block into fast memory (a ``slice-start``), which the
    real widths do not fit."""
    import repro.api as api
    from repro.configs.base import ModelConfig
    from repro.core import backend as backend_lib
    from repro.core import prepared
    from repro.core.prm import ReuseConfig
    from repro.kernels import ssd
    from repro.models import transformer as tfm
    for mod in (photonic_mvm, flash_attention, blend, ssd, backend_lib):
        monkeypatch.setattr(mod, "default_interpret", lambda: False)
    cfg = ModelConfig(
        name="rb-decode-v5e", family="dense", num_layers=6, d_model=512,
        num_heads=4, num_kv_heads=4, d_ff=1024, vocab_size=1024,
        compute_dtype="bfloat16", param_dtype="bfloat16",
        execution="photonic",
        reuse=ReuseConfig(num_basic=2, reuse_times=3,
                          transforms=("identity", "shuffle", "transpose"),
                          shuffle_groups=8))
    B, L = 4, 512
    place = lambda tree: jax.tree.map(lambda a: sds(a.shape, a.dtype), tree)
    params = jax.eval_shape(lambda k: tfm.init_model(k, cfg)[0],
                            jax.random.PRNGKey(0))
    bank = place(jax.eval_shape(
        lambda p: prepared.prepare_params(p, cfg.compute_dtype, True),
        params))
    caches = place(jax.eval_shape(
        lambda: tfm.init_caches(cfg, B, L, dtype=BF16)))
    decode = api._decode_cells(False)[0]
    hlo = decode.lower(bank, sds((B, 1), jnp.int32), caches,
                       sds((B,), jnp.int32), cfg=cfg,
                       backend=backend_lib.resolve(cfg)).compile().as_text()
    assert "tpu_custom_call" in hlo
    assert "dynamic-slice_bitcast_fusion" not in hlo


def test_flash_attention_gqa_compiles(sds):
    S, H, KV, hd = 1024, 24, 8, 128
    fn = functools.partial(flash_attention.flash_attention, causal=True,
                           interpret=False)
    _compile(fn, sds((H, S, hd), BF16), sds((KV, S, hd), BF16),
             sds((KV, S, hd), BF16))


def test_resident_mvm_compiles(sds):
    T, M = 4, 8
    fn = functools.partial(photonic_mvm.photonic_mvm_resident, bm=M,
                           bn=512, interpret=False)
    _compile(fn, sds((T, M, D_MODEL), jnp.int8),
             sds((D_MODEL, D_FF), jnp.int8), sds((T,), jnp.float32),
             sds((D_FF,), jnp.float32))


def test_blend_shuffle_compiles(sds):
    block = 128
    perm = tuple(reversed(range(D_FF // block)))
    fn = functools.partial(blend.blend_shuffle, block_perm=perm, block=block,
                           bm=8, activation="silu", interpret=False)
    _compile(fn, sds((8, D_FF), BF16), sds((D_FF,), BF16))
