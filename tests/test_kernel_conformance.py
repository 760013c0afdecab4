"""Kernel-conformance suite: every Pallas kernel against its `kernels/ref.py`
oracle across shapes, block sizes, dtypes, and non-tile-multiple padding.

Runs under the ``kernels`` marker — a separate CI job (pyproject addopts
deselect it from tier-1).  Property-based sweeps use the optional-hypothesis
shim (skip cleanly when hypothesis is absent); deterministic edge-case
sweeps run regardless.
"""
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from _optional_hypothesis import given, settings, st
from repro.core.photonic import photonic_matmul
from repro.kernels import blend as _blend
from repro.kernels import ops, ref
from repro.kernels.photonic_mvm import (photonic_mvm, photonic_mvm_resident,
                                        photonic_mvm_t)

pytestmark = pytest.mark.kernels


def _int8(key, shape):
    return jax.random.randint(key, shape, -127, 128, dtype=jnp.int8)


def _scales(key, n):
    return jax.random.uniform(key, (n,), minval=0.05, maxval=3.0)


# =====================================================================
# photonic MVM — forward, pre-swapped transpose, reuse-resident
# =====================================================================
EDGE_SHAPES = [(1, 1, 1), (3, 5, 2), (17, 129, 31), (64, 64, 64),
               (130, 257, 129), (200, 40, 7)]
BLOCKS = [(8, 8, 8), (16, 64, 32), (128, 128, 128)]


@pytest.mark.parametrize("M,K,N", EDGE_SHAPES)
@pytest.mark.parametrize("bm,bk,bn", BLOCKS)
def test_photonic_mvm_padding_grid(M, K, N, bm, bk, bn):
    ks = jax.random.split(jax.random.PRNGKey(M * 7 + K * 3 + N), 3)
    xq, wq = _int8(ks[0], (M, K)), _int8(ks[1], (K, N))
    xs, ws = jnp.float32(0.02), _scales(ks[2], N)
    got = photonic_mvm(xq, wq, xs, ws, bm=bm, bk=bk, bn=bn, interpret=True)
    want = ref.photonic_mvm_ref(xq, wq, xs, ws)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-4)


@pytest.mark.parametrize("M,K,N", EDGE_SHAPES)
@pytest.mark.parametrize("bm,bk,bn", BLOCKS)
def test_photonic_mvm_t_padding_grid(M, K, N, bm, bk, bn):
    ks = jax.random.split(jax.random.PRNGKey(M + K + N * 11), 3)
    xq, wq = _int8(ks[0], (M, K)), _int8(ks[1], (N, K))
    xs, ws = jnp.float32(0.013), _scales(ks[2], N)
    got = photonic_mvm_t(xq, wq, xs, ws, bm=bm, bk=bk, bn=bn, interpret=True)
    want = ref.photonic_mvm_t_ref(xq, wq, xs, ws)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-4)


@pytest.mark.parametrize("T,M,K,N", [(1, 4, 8, 8), (3, 17, 33, 9),
                                     (4, 130, 64, 129)])
@pytest.mark.parametrize("bm,bn", [(8, 8), (32, 128)])
def test_photonic_mvm_resident_vs_ref(T, M, K, N, bm, bn):
    ks = jax.random.split(jax.random.PRNGKey(T + M + K + N), 3)
    xq, wq = _int8(ks[0], (T, M, K)), _int8(ks[1], (K, N))
    xs = jnp.linspace(0.01, 0.05, T)
    ws = _scales(ks[2], N)
    got = photonic_mvm_resident(xq, wq, xs, ws, bm=bm, bn=bn, interpret=True)
    want = ref.photonic_mvm_resident_ref(xq, wq, xs, ws)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-4)


def test_resident_matches_per_call_kernel():
    """Residency is a schedule property: streaming T steps through one
    programmed tile must equal T independent kernel calls."""
    x = jax.random.normal(jax.random.PRNGKey(0), (3, 18, 40))
    w = jax.random.normal(jax.random.PRNGKey(1), (40, 24))
    got = ops.reuse_resident_matmul(x, w, bm=8, bn=8)
    want = jnp.stack([ops.photonic_matmul_kernel(x[t], w, bm=8, bk=16, bn=8)
                      for t in range(3)])
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_kernel_t_matches_simulator_transpose(dtype):
    """ops-level transpose wrapper == faithful simulator on w.T (the OBU
    vertical-input path), within W8A8 tolerance."""
    x = jax.random.normal(jax.random.PRNGKey(0), (20, 48)).astype(dtype)
    w = jax.random.normal(jax.random.PRNGKey(1), (40, 48))
    got = ops.photonic_matmul_kernel_t(x, w, bm=16, bk=16, bn=16)
    want = photonic_matmul(x, jnp.swapaxes(w, 0, 1))
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=4e-2, atol=4e-2)


@given(m=st.integers(1, 80), k=st.integers(1, 80), n=st.integers(1, 80),
       b=st.sampled_from([8, 16, 32]), seed=st.integers(0, 2 ** 16))
@settings(max_examples=15, deadline=None)
def test_photonic_mvm_property(m, k, n, b, seed):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    xq, wq = _int8(ks[0], (m, k)), _int8(ks[1], (k, n))
    xs, ws = jnp.float32(0.02), _scales(ks[2], n)
    got = photonic_mvm(xq, wq, xs, ws, bm=b, bk=b, bn=b, interpret=True)
    want = ref.photonic_mvm_ref(xq, wq, xs, ws)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-4)


@given(m=st.integers(1, 80), k=st.integers(1, 80), n=st.integers(1, 80),
       b=st.sampled_from([8, 16, 32]), seed=st.integers(0, 2 ** 16))
@settings(max_examples=15, deadline=None)
def test_photonic_mvm_t_property(m, k, n, b, seed):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    xq, wq = _int8(ks[0], (m, k)), _int8(ks[1], (n, k))
    xs, ws = jnp.float32(0.02), _scales(ks[2], n)
    got = photonic_mvm_t(xq, wq, xs, ws, bm=b, bk=b, bn=b, interpret=True)
    want = ref.photonic_mvm_t_ref(xq, wq, xs, ws)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-4)


@given(t=st.integers(1, 4), m=st.integers(1, 40), k=st.integers(1, 40),
       n=st.integers(1, 40), seed=st.integers(0, 2 ** 16))
@settings(max_examples=15, deadline=None)
def test_photonic_mvm_resident_property(t, m, k, n, seed):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    xq, wq = _int8(ks[0], (t, m, k)), _int8(ks[1], (k, n))
    xs = jnp.linspace(0.01, 0.05, t)
    ws = _scales(ks[2], n)
    got = photonic_mvm_resident(xq, wq, xs, ws, bm=16, bn=16, interpret=True)
    want = ref.photonic_mvm_resident_ref(xq, wq, xs, ws)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-4)


# =====================================================================
# blend (blocked shuffle + bias + activation epilogue)
# =====================================================================
@pytest.mark.parametrize("M,bm", [(16, 16), (37, 16), (100, 128), (1, 8)])
@pytest.mark.parametrize("nblk,block,act", [(4, 8, "relu"), (8, 16, "silu"),
                                            (3, 8, "none")])
def test_blend_shuffle_ragged_rows(M, bm, nblk, block, act):
    """Non-tile-multiple row counts (ragged serving batches) pad instead of
    crashing — the ISSUE-2 satellite fix."""
    C = nblk * block
    x = jax.random.normal(jax.random.PRNGKey(M + C), (M, C))
    bias = jax.random.normal(jax.random.PRNGKey(1), (C,))
    perm = np.random.default_rng(M).permutation(nblk)
    got = _blend.blend_shuffle(x, bias, perm, block=block, bm=bm,
                               activation=act, interpret=True)
    want = ref.blend_shuffle_ref(x, bias, perm, block, activation=act)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_blend_shuffle_dtypes(dtype):
    C, block = 64, 16
    x = jax.random.normal(jax.random.PRNGKey(0), (10, C)).astype(dtype)
    bias = jax.random.normal(jax.random.PRNGKey(1), (C,)).astype(dtype)
    perm = np.random.default_rng(2).permutation(C // block)
    got = ops.blend_shuffle(x, bias, perm, block=block, activation="silu")
    assert got.dtype == dtype
    want = ref.blend_shuffle_ref(x, bias, perm, block, activation="silu")
    tol = 2e-2 if dtype == jnp.bfloat16 else 1e-6
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


@given(nblk=st.integers(1, 8), block=st.sampled_from([8, 16, 32]),
       m=st.integers(1, 70),
       act=st.sampled_from(["relu", "silu", "none"]),
       seed=st.integers(0, 2 ** 16))
@settings(max_examples=15, deadline=None)
def test_blend_shuffle_property(nblk, block, m, act, seed):
    C = nblk * block
    ks = jax.random.split(jax.random.PRNGKey(seed), 2)
    x = jax.random.normal(ks[0], (m, C))
    bias = jax.random.normal(ks[1], (C,))
    perm = np.random.default_rng(seed).permutation(nblk)
    got = _blend.blend_shuffle(x, bias, perm, block=block, bm=16,
                               activation=act, interpret=True)
    want = ref.blend_shuffle_ref(x, bias, perm, block, activation=act)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6, atol=1e-6)


# =====================================================================
# flash attention
# =====================================================================
@pytest.mark.parametrize("S,hd,bq,bk,causal",
                         [(32, 8, 8, 8, True), (64, 16, 16, 32, True),
                          (96, 32, 32, 32, False), (128, 16, 128, 64, True)])
def test_flash_attention_grid(S, hd, bq, bk, causal):
    B, H = 2, 2
    ks = jax.random.split(jax.random.PRNGKey(S + hd), 3)
    q = jax.random.normal(ks[0], (B, S, H, hd))
    k = jax.random.normal(ks[1], (B, S, H, hd))
    v = jax.random.normal(ks[2], (B, S, H, hd))
    got = ops.flash_attention(q, k, v, causal=causal, bq=bq, bk=bk)
    qf = q.transpose(0, 2, 1, 3).reshape(B * H, S, hd)
    kf = k.transpose(0, 2, 1, 3).reshape(B * H, S, hd)
    vf = v.transpose(0, 2, 1, 3).reshape(B * H, S, hd)
    want = ref.flash_attention_ref(qf, kf, vf, causal=causal)
    want = want.reshape(B, H, S, hd).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


@given(nq=st.integers(1, 4), bq=st.sampled_from([8, 16]),
       hd=st.sampled_from([8, 16, 32]), causal=st.booleans(),
       seed=st.integers(0, 2 ** 16))
@settings(max_examples=15, deadline=None)
def test_flash_attention_property(nq, bq, hd, causal, seed):
    S = nq * bq                       # kernel requires S % bq == S % bk == 0
    B, H = 1, 2
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (B, S, H, hd))
    k = jax.random.normal(ks[1], (B, S, H, hd))
    v = jax.random.normal(ks[2], (B, S, H, hd))
    got = ops.flash_attention(q, k, v, causal=causal, bq=bq, bk=bq)
    qf = q.transpose(0, 2, 1, 3).reshape(B * H, S, hd)
    kf = k.transpose(0, 2, 1, 3).reshape(B * H, S, hd)
    vf = v.transpose(0, 2, 1, 3).reshape(B * H, S, hd)
    want = ref.flash_attention_ref(qf, kf, vf, causal=causal).reshape(
        B, H, S, hd).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


# =====================================================================
# SSD chunk
# =====================================================================
@pytest.mark.parametrize("L,H,P,N", [(8, 1, 4, 2), (16, 3, 8, 4),
                                     (64, 2, 16, 8)])
def test_ssd_chunk_grid(L, H, P, N):
    b, nc = 2, 2
    ks = jax.random.split(jax.random.PRNGKey(L * H + P), 4)
    x = jax.random.normal(ks[0], (b, nc, L, H, P))
    dA = -jax.nn.softplus(jax.random.normal(ks[1], (b, nc, H, L)))
    B = jax.random.normal(ks[2], (b, nc, L, H, N))
    C = jax.random.normal(ks[3], (b, nc, L, H, N))
    y_got, st_got = ops.ssd_chunk(x, dA, B, C)
    y_want, st_want = ref.ssd_chunk_ref(x, dA, B, C)
    np.testing.assert_allclose(np.asarray(y_got), np.asarray(y_want),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(st_got), np.asarray(st_want),
                               rtol=2e-4, atol=2e-4)


@given(L=st.sampled_from([8, 16, 32]), H=st.integers(1, 3),
       P=st.sampled_from([4, 8]), N=st.sampled_from([2, 4]),
       seed=st.integers(0, 2 ** 16))
@settings(max_examples=10, deadline=None)
def test_ssd_chunk_property(L, H, P, N, seed):
    b, nc = 1, 2
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    x = jax.random.normal(ks[0], (b, nc, L, H, P))
    dA = -jax.nn.softplus(jax.random.normal(ks[1], (b, nc, H, L)))
    B = jax.random.normal(ks[2], (b, nc, L, H, N))
    C = jax.random.normal(ks[3], (b, nc, L, H, N))
    y_got, st_got = ops.ssd_chunk(x, dA, B, C)
    y_want, st_want = ref.ssd_chunk_ref(x, dA, B, C)
    np.testing.assert_allclose(np.asarray(y_got), np.asarray(y_want),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(st_got), np.asarray(st_want),
                               rtol=2e-4, atol=2e-4)


# =====================================================================
# fused decode-path megakernel (in-kernel A8 + blend epilogue)
# =====================================================================
def _fused_case(seed, M, K, N, transpose=False):
    from repro.core.photonic import a8_scale
    from repro.core.prepared import quantize_weight, quantize_weight_t
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    x = jax.random.normal(k1, (M, K), jnp.float32)
    wshape = (N, K) if transpose else (K, N)
    w = jax.random.normal(k2, wshape, jnp.float32)
    wq, ws = (quantize_weight_t(w) if transpose else quantize_weight(w))
    return x, wq, ws, a8_scale(x)


@pytest.mark.parametrize("M,K,N", EDGE_SHAPES)
@pytest.mark.parametrize("bm,bk,bn", BLOCKS)
@pytest.mark.parametrize("transpose", [False, True])
def test_fused_padding_grid(M, K, N, bm, bk, bn, transpose):
    from repro.kernels.photonic_mvm import photonic_mvm_fused
    x, wq, ws, xs = _fused_case(M * 5 + K + N, M, K, N, transpose)
    got = photonic_mvm_fused(x, wq, xs, ws, bm=bm, bk=bk, bn=bn,
                             transpose=transpose, interpret=True)
    want = ref.photonic_mvm_fused_ref(x, wq, xs, ws, transpose=transpose)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-4)


@pytest.mark.parametrize("nblk,block,act", [(4, 16, "relu"), (8, 8, "silu"),
                                            (2, 32, "none")])
@pytest.mark.parametrize("M", [1, 3, 16, 130])
def test_fused_epilogue_vs_separate_blend(nblk, block, act, M):
    """Fused bias+activation+shuffle epilogue vs the split two-kernel
    pipeline across ragged row counts; bit-identity holds without bias,
    ulp-tolerance with (the fma note in photonic_mvm._finalize)."""
    from repro.kernels.photonic_mvm import photonic_mvm_fused
    K = 48
    N = nblk * block
    x, wq, ws, xs = _fused_case(M + nblk * block, M, K, N)
    bias = jax.random.normal(jax.random.PRNGKey(2), (N,), jnp.float32)
    perm = tuple(int(v) for v in
                 np.random.default_rng(M).permutation(nblk))
    got = photonic_mvm_fused(x, wq, xs, ws, bias=bias, bm=8, bk=16, bn=8,
                             block_perm=perm, block=block, activation=act,
                             interpret=True)
    y = ops.photonic_matmul_prepared(x, wq, ws, bm=8, bk=16, bn=8)
    sep = _blend.blend_shuffle(jnp.asarray(y), bias, perm, block=block,
                               bm=min(128, ops.round_up(M, 8)),
                               activation=act, interpret=True)[:M]
    np.testing.assert_allclose(np.asarray(got), np.asarray(sep),
                               rtol=1e-6, atol=1e-6)
    got0 = photonic_mvm_fused(x, wq, xs, ws, bm=8, bk=16, bn=8,
                              block_perm=perm, block=block, activation=act,
                              interpret=True)
    sep0 = _blend.blend_shuffle(jnp.asarray(y), jnp.zeros((N,)), perm,
                                block=block,
                                bm=min(128, ops.round_up(M, 8)),
                                activation=act, interpret=True)[:M]
    assert np.array_equal(np.asarray(got0), np.asarray(sep0))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_fused_dtypes(dtype):
    from repro.core.photonic import a8_scale
    from repro.core.prepared import quantize_weight
    from repro.kernels.photonic_mvm import photonic_mvm_fused
    x = jax.random.normal(jax.random.PRNGKey(0), (6, 64)).astype(dtype)
    w = jax.random.normal(jax.random.PRNGKey(1), (64, 32), jnp.float32)
    wq, ws = quantize_weight(w)
    got = photonic_mvm_fused(x, wq, a8_scale(x), ws, bm=8, bk=32, bn=32,
                             activation="silu", interpret=True,
                             out_dtype=dtype)
    assert got.dtype == dtype
    # the oracle quantizes on x's own grid (bf16 rounds in bf16, exactly
    # like quantize_symmetric), so only K-accumulation order differs
    want = ref.photonic_mvm_fused_ref(x, wq, a8_scale(x), ws,
                                      activation="silu")
    tol = 2e-2 if dtype == jnp.bfloat16 else 1e-5
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


def _pads_stack(fn, *args) -> bool:
    """Whether tracing ``fn`` pads a rank-3 (stacked) array, nested jaxprs
    included: the printed jaxpr names each pad's result type."""
    text = str(jax.make_jaxpr(fn)(*args))
    return re.search(r"\w+\[\d+,\d+,\d+\] = pad\[", text) is not None


STACKED_CASES = [(R, M, K, N, tr, act, shuffle)
                 for R in (1, 3) for M in (1, 4, 16, 130)
                 for K, N in ((256, 384),)
                 for tr in (False, True) for act in ("none", "silu")
                 for shuffle in (False, True)]
# K % bk != 0: the layer is sliced out and padded, never the whole stack
STACKED_CASES += [(3, M, 200, 384, tr, "silu", shuffle)
                  for M in (4, 130) for tr in (False, True)
                  for shuffle in (False, True)]


@pytest.mark.parametrize("R,M,K,N,transpose,act,shuffle", STACKED_CASES)
def test_fused_stacked_bank_bitwise(R, M, K, N, transpose, act, shuffle):
    """The kernel reading layer ``r`` of a stacked (R, K, N) bank in place
    is bitwise the kernel on the sliced ``wq[r]``, at every ``r``."""
    from repro.core.photonic import a8_scale
    from repro.core.prepared import quantize_weight, quantize_weight_t
    from repro.kernels.photonic_mvm import (photonic_mvm_fused,
                                            reads_stack_in_place)
    k1, k2 = jax.random.split(jax.random.PRNGKey(R * 1000 + M + K))
    x = jax.random.normal(k1, (M, K), jnp.float32)
    w = jax.random.normal(k2, (R, N, K) if transpose else (R, K, N))
    wq, ws = quantize_weight_t(w) if transpose else quantize_weight(w)
    xs = a8_scale(x)
    block = 128
    perm = (2, 0, 1) if shuffle else None
    kw = dict(bm=ops.round_up(min(M, 128), 8), bk=128, bn=128,
              transpose=transpose, activation=act, block_perm=perm,
              block=block if shuffle else 0, interpret=True)
    assert reads_stack_in_place(K, N, 128, 128) == (K % 128 == 0)
    for r in range(R):
        layer = jnp.int32(r)
        got = photonic_mvm_fused(x, wq, xs, ws, layer=layer, **kw)
        want = photonic_mvm_fused(x, wq[r], xs, ws[r], **kw)
        assert np.array_equal(np.asarray(got), np.asarray(want)), r
    fn = lambda x, wq, ws, layer: photonic_mvm_fused(x, wq, xs, ws,
                                                     layer=layer, **kw)
    assert not _pads_stack(fn, x, wq, ws, jnp.int32(0))


@given(m=st.integers(1, 80), k=st.integers(1, 80), n=st.integers(1, 80),
       transpose=st.booleans(), seed=st.integers(0, 2 ** 16))
@settings(max_examples=15, deadline=None)
def test_fused_property(m, k, n, transpose, seed):
    from repro.kernels.photonic_mvm import photonic_mvm_fused, tile_plan
    x, wq, ws, xs = _fused_case(seed, m, k, n, transpose)
    bm, bk, bn = tile_plan(m, k, n, cap_k=256, cap_n=256)
    got = photonic_mvm_fused(x, wq, xs, ws, bm=bm, bk=bk, bn=bn,
                             transpose=transpose, interpret=True)
    want = ref.photonic_mvm_fused_ref(x, wq, xs, ws, transpose=transpose)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-4)


def test_backend_adaptive_plan_matches_fixed_numerics():
    """Different tile plans reorder the fp32 K-accumulation, so adaptive
    and fixed plans agree to reduction tolerance (and each is internally
    bit-stable: fused == split at ITS plan, covered elsewhere)."""
    from repro.core.backend import Backend
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 256), jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(1), (256, 192), jnp.float32)
    ya = Backend("photonic").dot(x, w)
    yf = Backend("photonic", bm=128, bk=128, bn=128, adaptive=False,
                 fused=False).dot(x, w)
    np.testing.assert_allclose(np.asarray(ya), np.asarray(yf),
                               rtol=1e-5, atol=1e-4)
