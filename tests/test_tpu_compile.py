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

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.kernels import blend, flash_attention, photonic_mvm
from repro.kernels.photonic_mvm import tile_plan

D_MODEL, D_FF, VOCAB = 3072, 9216, 256000
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
