"""Per-kernel allclose tests against the pure-jnp oracles in kernels/ref.py.

Kernels execute in interpret mode on CPU (kernel bodies run in Python);
shape/dtype sweeps cover padding paths and MXU-aligned and unaligned sizes.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import obu
from repro.core.photonic import photonic_matmul
from repro.kernels import ops, ref
from repro.kernels.photonic_mvm import photonic_mvm


# ======================================================================
# photonic MVM
# ======================================================================
@pytest.mark.parametrize("M,K,N", [(16, 32, 24), (128, 128, 128),
                                   (100, 200, 50), (1, 64, 8),
                                   (130, 257, 129)])
def test_photonic_mvm_vs_ref(M, K, N):
    k1, k2 = jax.random.split(jax.random.PRNGKey(M + K + N))
    xq = jax.random.randint(k1, (M, K), -127, 128, dtype=jnp.int8)
    wq = jax.random.randint(k2, (K, N), -127, 128, dtype=jnp.int8)
    xs = jnp.float32(0.013)
    ws = jax.random.uniform(jax.random.PRNGKey(0), (N,), minval=0.1,
                            maxval=2.0)
    got = photonic_mvm(xq, wq, xs, ws, bm=32, bk=64, bn=32, interpret=True)
    want = ref.photonic_mvm_ref(xq, wq, xs, ws)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-4)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_photonic_kernel_matches_simulator(dtype):
    """Kernel path == core.photonic.photonic_matmul (the faithful sim)."""
    x = jax.random.normal(jax.random.PRNGKey(0), (24, 48)).astype(dtype)
    w = jax.random.normal(jax.random.PRNGKey(1), (48, 40))
    got = ops.photonic_matmul_kernel(x, w, bm=16, bk=16, bn=16)
    want = photonic_matmul(x, w)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        rtol=2e-2, atol=2e-2)


def test_photonic_mvm_offset_exactness():
    """Offset decomposition inside the kernel is exact (not approximate):
    against full-range int weights the kernel equals the plain matmul."""
    xq = jnp.arange(-8, 8, dtype=jnp.int8).reshape(2, 8)
    wq = (jnp.arange(64, dtype=jnp.int32) % 255 - 127).astype(
        jnp.int8).reshape(8, 8)
    got = photonic_mvm(xq, wq, jnp.float32(1.0), jnp.ones((8,)),
                       bm=8, bk=8, bn=8, interpret=True)
    want = xq.astype(jnp.float32) @ wq.astype(jnp.float32) / 127.0
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6, atol=1e-5)


def test_photonic_mvm_t_vs_ref():
    """Pre-swapped transpose variant (OBU optical transpose) vs oracle."""
    from repro.kernels.photonic_mvm import photonic_mvm_t
    k1, k2 = jax.random.split(jax.random.PRNGKey(7))
    xq = jax.random.randint(k1, (30, 50), -127, 128, dtype=jnp.int8)
    wq = jax.random.randint(k2, (21, 50), -127, 128, dtype=jnp.int8)
    xs = jnp.float32(0.02)
    ws = jax.random.uniform(jax.random.PRNGKey(1), (21,), minval=0.1,
                            maxval=2.0)
    got = photonic_mvm_t(xq, wq, xs, ws, bm=16, bk=16, bn=16, interpret=True)
    want = ref.photonic_mvm_t_ref(xq, wq, xs, ws)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-4)


def test_photonic_mvm_resident_vs_ref():
    """Reuse-resident kernel (weight programmed once, T streams) vs oracle."""
    from repro.kernels.photonic_mvm import photonic_mvm_resident
    k1, k2 = jax.random.split(jax.random.PRNGKey(8))
    xq = jax.random.randint(k1, (3, 20, 40), -127, 128, dtype=jnp.int8)
    wq = jax.random.randint(k2, (40, 24), -127, 128, dtype=jnp.int8)
    xs = jnp.array([0.01, 0.02, 0.03])
    ws = jax.random.uniform(jax.random.PRNGKey(2), (24,), minval=0.1,
                            maxval=2.0)
    got = photonic_mvm_resident(xq, wq, xs, ws, bm=8, bn=8, interpret=True)
    want = ref.photonic_mvm_resident_ref(xq, wq, xs, ws)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-4)


# ======================================================================
# blend (blocked shuffle + bias + act)
# ======================================================================
@pytest.mark.parametrize("nblk,block,act", [(4, 8, "relu"), (8, 16, "silu"),
                                            (2, 128, "none")])
def test_blend_shuffle_vs_ref(nblk, block, act):
    C = nblk * block
    M = 16
    x = jax.random.normal(jax.random.PRNGKey(0), (M, C))
    bias = jax.random.normal(jax.random.PRNGKey(1), (C,))
    perm = np.random.default_rng(3).permutation(nblk)
    got = ops.blend_shuffle(x, bias, perm, block=block, activation=act)
    want = ref.blend_shuffle_ref(x, bias, perm, block, activation=act)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6, atol=1e-6)


def test_blend_shuffle_ragged_rows():
    """Row counts that don't divide the row block pad instead of crashing
    (ragged serving batches; ISSUE-2 satellite fix)."""
    from repro.kernels.blend import blend_shuffle as raw_blend
    C, block, M = 32, 8, 37
    x = jax.random.normal(jax.random.PRNGKey(0), (M, C))
    bias = jax.random.normal(jax.random.PRNGKey(1), (C,))
    perm = np.random.default_rng(7).permutation(C // block)
    got = raw_blend(x, bias, perm, block=block, bm=16, activation="relu",
                    interpret=True)
    want = ref.blend_shuffle_ref(x, bias, perm, block, activation="relu")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6, atol=1e-6)


def test_blend_matches_obu_blocked_permutation():
    """Kernel blocked shuffle == core.obu.blocked_random_permutation gather."""
    C, block = 64, 8
    x = jax.random.normal(jax.random.PRNGKey(0), (8, C))
    perm_c = obu.blocked_random_permutation(C, block, seed=5)
    block_perm = perm_c.reshape(-1, block)[:, 0] // block
    got = ops.blend_shuffle(x, jnp.zeros((C,)), block_perm, block=block,
                            activation="none")
    want = obu.apply_channel_permutation(x, perm_c)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want))


# ======================================================================
# flash attention
# ======================================================================
@pytest.mark.parametrize("S,hd,causal", [(64, 16, True), (128, 32, True),
                                         (64, 16, False), (256, 8, True)])
def test_flash_attention_vs_ref(S, hd, causal):
    B, H = 2, 3
    ks = jax.random.split(jax.random.PRNGKey(S + hd), 3)
    q = jax.random.normal(ks[0], (B, S, H, hd))
    k = jax.random.normal(ks[1], (B, S, H, hd))
    v = jax.random.normal(ks[2], (B, S, H, hd))
    got = ops.flash_attention(q, k, v, causal=causal, bq=32, bk=32)
    qf = q.transpose(0, 2, 1, 3).reshape(B * H, S, hd)
    kf = k.transpose(0, 2, 1, 3).reshape(B * H, S, hd)
    vf = v.transpose(0, 2, 1, 3).reshape(B * H, S, hd)
    want = ref.flash_attention_ref(qf, kf, vf, causal=causal)
    want = want.reshape(B, H, S, hd).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("causal,q_offset", [(True, None), (True, 64),
                                              (False, None)])
def test_flash_attention_scale_matches_attend_seq_xla(causal, q_offset):
    """A non-default softmax scale (MLA's mscale^2 / sqrt(hd)), with MLA's
    v head dim, on the kernel and on the einsum path alike."""
    from repro.models.attention import attend_seq_xla
    B, S, H, hd, hdv = 2, 64, 3, 24, 16
    L = S + (q_offset or 0)
    scale = 1.5896 / hd ** 0.5
    ks = jax.random.split(jax.random.PRNGKey(7), 3)
    q = jax.random.normal(ks[0], (B, S, H, hd))
    k = jax.random.normal(ks[1], (B, L, H, hd))
    v = jax.random.normal(ks[2], (B, L, H, hdv))
    got = ops.flash_attention(q, k, v, causal=causal, q_offset=q_offset,
                              bq=32, bk=32, scale=scale)
    want = attend_seq_xla(q, k, v, causal=causal, q_offset=q_offset,
                          scale=scale)
    np.testing.assert_allclose(np.asarray(got).reshape(B, S, H * hdv),
                               np.asarray(want), rtol=2e-5, atol=2e-5)
    plain = attend_seq_xla(q, k, v, causal=causal, q_offset=q_offset)
    assert np.abs(np.asarray(plain) - np.asarray(want)).max() > 1e-2


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_dtypes(dtype):
    B, S, H, hd = 1, 64, 2, 16
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (B, S, H, hd)).astype(dtype)
    k = jax.random.normal(ks[1], (B, S, H, hd)).astype(dtype)
    v = jax.random.normal(ks[2], (B, S, H, hd)).astype(dtype)
    got = ops.flash_attention(q, k, v, bq=32, bk=32)
    assert got.dtype == dtype
    qf = q.transpose(0, 2, 1, 3).reshape(B * H, S, hd)
    kf = k.transpose(0, 2, 1, 3).reshape(B * H, S, hd)
    vf = v.transpose(0, 2, 1, 3).reshape(B * H, S, hd)
    want = ref.flash_attention_ref(qf, kf, vf).reshape(
        B, H, S, hd).transpose(0, 2, 1, 3)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


# ======================================================================
# SSD chunk kernel
# ======================================================================
@pytest.mark.parametrize("L,H,P,N", [(16, 2, 8, 4), (32, 4, 16, 8),
                                     (64, 1, 32, 16)])
def test_ssd_chunk_vs_ref(L, H, P, N):
    b, nc = 2, 3
    ks = jax.random.split(jax.random.PRNGKey(L + H), 4)
    x = jax.random.normal(ks[0], (b, nc, L, H, P))
    dA = -jax.nn.softplus(jax.random.normal(ks[1], (b, nc, H, L)))
    B = jax.random.normal(ks[2], (b, nc, L, H, N))
    C = jax.random.normal(ks[3], (b, nc, L, H, N))
    y_got, st_got = ops.ssd_chunk(x, dA, B, C)
    y_want, st_want = ref.ssd_chunk_ref(x, dA, B, C)
    np.testing.assert_allclose(np.asarray(y_got), np.asarray(y_want),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(
        np.asarray(st_got), np.asarray(st_want).transpose(0, 1, 2, 3, 4),
        rtol=2e-4, atol=2e-4)


def test_ssd_chunk_composes_to_full_ssd():
    """Kernel y_diag/states + JAX inter-chunk scan == models.ssm oracle."""
    from repro.models.ssm import ssd_reference
    b, S, H, P, N, L = 1, 32, 2, 8, 4, 8
    nc = S // L
    ks = jax.random.split(jax.random.PRNGKey(9), 5)
    x = jax.random.normal(ks[0], (b, S, H, P))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, S, H)))
    A = -jnp.exp(jax.random.normal(ks[2], (H,)))
    Bm = jax.random.normal(ks[3], (b, S, 1, N))
    Cm = jax.random.normal(ks[4], (b, S, 1, N))
    # assemble chunked inputs exactly as models.ssm does
    xdt = (x * dt[..., None]).reshape(b, nc, L, H, P)
    dA = (dt * A).reshape(b, nc, L, H).transpose(0, 1, 3, 2)
    Bh = jnp.repeat(Bm, H, axis=2).reshape(b, nc, L, H, N)
    Ch = jnp.repeat(Cm, H, axis=2).reshape(b, nc, L, H, N)
    y_diag, states = ops.ssd_chunk(xdt, dA, Bh, Ch)
    # inter-chunk scan
    cs = jnp.cumsum(dA, axis=-1)
    chunk_decay = jnp.exp(cs[..., -1])
    def step(h, inp):
        st, dec = inp
        return h * dec[:, :, None, None] + st, h
    h0 = jnp.zeros((b, H, N, P))
    hT, h_prev = jax.lax.scan(
        step, h0, (states.transpose(1, 0, 2, 3, 4),
                   chunk_decay.transpose(1, 0, 2)))
    h_prev = h_prev.transpose(1, 0, 2, 3, 4)          # (b,nc,H,N,P)
    state_decay = jnp.exp(cs).transpose(0, 1, 3, 2)   # (b,nc,L,H)
    y_off = jnp.einsum("bclhn,bchnp,bclh->bclhp", Ch, h_prev, state_decay)
    y = (y_diag + y_off).reshape(b, S, H, P)
    want, hT_want = ssd_reference(x, dt, A, Bm, Cm)
    np.testing.assert_allclose(np.asarray(y), np.asarray(want),
                               rtol=5e-4, atol=5e-4)
    np.testing.assert_allclose(np.asarray(hT.transpose(0, 1, 3, 2)),
                               np.asarray(hT_want), rtol=5e-4, atol=5e-4)


# ======================================================================
# fused decode-path megakernel (ISSUE 4)
# ======================================================================
def _fused_operands(key, M, K, N, transpose=False):
    from repro.core.photonic import a8_scale
    from repro.core.prepared import quantize_weight, quantize_weight_t
    k1, k2 = jax.random.split(key)
    x = jax.random.normal(k1, (M, K), jnp.float32)
    if transpose:
        w = jax.random.normal(k2, (N, K), jnp.float32)
        wq, ws = quantize_weight_t(w)
    else:
        w = jax.random.normal(k2, (K, N), jnp.float32)
        wq, ws = quantize_weight(w)
    return x, wq, ws, a8_scale(x)


@pytest.mark.parametrize("M", [1, 2, 3, 7, 128, 130])
@pytest.mark.parametrize("transpose", [False, True])
def test_fused_ragged_m_sweep(M, transpose):
    """The serving-width sweep: fused megakernel vs oracle at every ragged
    row count, with the shape-adaptive tile plan."""
    from repro.kernels.photonic_mvm import photonic_mvm_fused, tile_plan
    K, N = 96, 64
    x, wq, ws, xs = _fused_operands(jax.random.PRNGKey(M), M, K, N,
                                    transpose)
    bm, bk, bn = tile_plan(M, K, N)
    got = photonic_mvm_fused(x, wq, xs, ws, bm=bm, bk=bk, bn=bn,
                             transpose=transpose, interpret=True)
    want = ref.photonic_mvm_fused_ref(x, wq, xs, ws, transpose=transpose)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-4)


@pytest.mark.parametrize("M", [1, 2, 7, 130])
def test_fused_in_kernel_quant_bit_identical(M):
    """In-kernel A8 quantization == quantize-outside + int8 kernel, at the
    same tile plan — bit-for-bit."""
    from repro.core.photonic import quantize_symmetric
    from repro.kernels.photonic_mvm import photonic_mvm_fused
    K, N = 64, 48
    x, wq, ws, xs = _fused_operands(jax.random.PRNGKey(M + 50), M, K, N)
    got = photonic_mvm_fused(x, wq, xs, ws, bm=8, bk=32, bn=16,
                             interpret=True)
    xq, xs2 = quantize_symmetric(x, 8)
    split = photonic_mvm(xq, wq, xs2, ws, bm=8, bk=32, bn=16,
                         interpret=True)
    assert np.array_equal(np.asarray(got), np.asarray(split))


@pytest.mark.parametrize("act", ["none", "relu", "silu"])
def test_fused_epilogue_bit_identical_to_separate_blend(act):
    """Fused blend epilogue (activation + blocked output shuffle) ==
    separate MVM kernel + blend kernel, bit-for-bit at the same plan."""
    from repro.kernels.photonic_mvm import photonic_mvm_fused
    M, K, N, block = 5, 64, 64, 16
    x, wq, ws, xs = _fused_operands(jax.random.PRNGKey(11), M, K, N)
    perm = tuple(int(v) for v in
                 np.random.default_rng(1).permutation(N // block))
    got = photonic_mvm_fused(x, wq, xs, ws, bm=8, bk=32, bn=16,
                             block_perm=perm, block=block, activation=act,
                             interpret=True)
    y = ops.photonic_matmul_prepared(x, wq, ws, bm=8, bk=32, bn=16)
    sep = ops.blend_shuffle(y, jnp.zeros((N,)), perm, block=block,
                            activation=act)
    assert np.array_equal(np.asarray(got), np.asarray(sep))


def test_fused_bias_one_ulp_of_separate():
    """The fused bias add rides the TIA-rescale fma (XLA contracts the
    mul+add pair), landing within 1 ulp of the split path's store+add."""
    from repro.kernels.photonic_mvm import photonic_mvm_fused
    M, K, N = 5, 64, 64
    x, wq, ws, xs = _fused_operands(jax.random.PRNGKey(13), M, K, N)
    bias = jax.random.normal(jax.random.PRNGKey(3), (N,), jnp.float32)
    got = photonic_mvm_fused(x, wq, xs, ws, bias=bias, bm=8, bk=32, bn=32,
                             interpret=True)
    y = ops.photonic_matmul_prepared(x, wq, ws, bm=8, bk=32, bn=32)
    want = ref.blend_shuffle_ref(y, bias, np.arange(1), N,
                                 activation="none")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6, atol=1e-6)


def test_blend_shuffle_ragged_channels_raises():
    """C % block != 0 used to silently mis-slice; now a clear ValueError
    (ISSUE-4 satellite)."""
    from repro.kernels.blend import blend_shuffle as raw_blend
    x = jax.random.normal(jax.random.PRNGKey(0), (8, 36))
    bias = jnp.zeros((36,))
    with pytest.raises(ValueError, match="multiple of block"):
        raw_blend(x, bias, np.arange(4), block=8, interpret=True)
    with pytest.raises(ValueError, match="permutation"):
        raw_blend(x, bias, np.arange(2), block=12, interpret=True)


def test_tile_plan_shapes():
    """Shape-adaptive plan: decode widths round to the 8-row sublane, whole
    aligned axes collapse to one grid step, unaligned axes keep the largest
    non-padding tile."""
    from repro.kernels.photonic_mvm import tile_plan
    assert tile_plan(2, 512, 1024) == (8, 512, 512)
    assert tile_plan(1, 64, 64) == (8, 128, 128)       # lane-rounded
    assert tile_plan(130, 512, 512) == (128, 512, 512)
    assert tile_plan(8, 640, 1280) == (8, 128, 256)    # largest divisor
    assert tile_plan(16, 512, 512, cap_k=128, cap_n=128) == (16, 128, 128)


def test_resident_bm_rounds_to_sublane():
    """reuse_resident_matmul_prepared clamps bm to the serving width but
    keeps it a multiple of 8 (ISSUE-4 satellite): 2-row streams still run
    MXU-aligned 8-row tiles, and the result matches the oracle."""
    from repro.core.prepared import quantize_weight
    x = jax.random.normal(jax.random.PRNGKey(0), (3, 2, 40))   # 2-row stream
    w = jax.random.normal(jax.random.PRNGKey(1), (40, 24))
    wq, ws = quantize_weight(w)
    got = ops.reuse_resident_matmul_prepared(x, wq, ws, bm=128, bn=24)
    want = jnp.stack([ops.photonic_matmul_kernel(x[t], w, bm=8, bk=40, bn=24)
                      for t in range(3)])
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_backend_dot_fused_vs_unfused_bit_identical(dtype):
    """Backend-level gate: the megakernel path and the split pipeline
    (same adaptive tile plan) produce bit-identical outputs through
    ``Backend.dot`` — in every activation dtype (the in-kernel A8 grid
    rounds in the input dtype, exactly like quantize_symmetric), and
    including the fused silu epilogue."""
    from repro.core.backend import Backend
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 96)).astype(dtype)
    w = jax.random.normal(jax.random.PRNGKey(1), (96, 64)).astype(dtype)
    f = Backend("photonic")
    u = Backend("photonic", fused=False)
    for kw in ({}, {"activation": "silu"}, {"transpose": True}):
        w_ = jax.random.normal(jax.random.PRNGKey(2), (64, 96)).astype(
            dtype) if kw.get("transpose") else w
        a = f.dot(x, w_, **kw)
        b = u.dot(x, w_, **kw)
        assert np.array_equal(np.asarray(a, np.float32),
                              np.asarray(b, np.float32)), (dtype, kw)


# ======================================================================
# flash attention: head-layout / masking conformance (ISSUE-10)
# ======================================================================
def _fa_rand(key, *shapes):
    ks = jax.random.split(jax.random.PRNGKey(key), len(shapes))
    return [jax.random.normal(k, s) for k, s in zip(ks, shapes)]


def _fa_check(q, k, v, **kw):
    from repro.kernels import flash_attention as fa
    got = fa.flash_attention(q, k, v, interpret=True, **kw)
    want = ref.flash_attention_ref(
        q, k, v, causal=kw.get("causal", True),
        q_offset=kw.get("q_offset") or 0, kv_len=kw.get("kv_len"))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=3e-5, atol=3e-5)


@pytest.mark.parametrize("G", [2, 4])
def test_flash_attention_gqa_groups(G):
    """Query row b reads kv row b // G — the GQA grid index map."""
    BHkv, S, hd = 2, 96, 16
    q, k, v = _fa_rand(G, (BHkv * G, S, hd), (BHkv, S, hd), (BHkv, S, hd))
    _fa_check(q, k, v, bq=32, bk=32)


def test_flash_attention_mla_vdim():
    """MLA layout: v head dim != qk head dim (and ragged S)."""
    BH, S, hd, hdv = 3, 70, 32, 24
    q, k, v = _fa_rand(1, (BH, S, hd), (BH, S, hd), (BH, S, hdv))
    _fa_check(q, k, v, bq=32, bk=32)


@pytest.mark.parametrize("S", [130, 97, 8])
def test_flash_attention_ragged_s(S):
    """Ragged Sq/L pad to the tile in the wrapper; padded keys are masked
    NEG_INF in-kernel and padded query rows sliced off (mirror of the
    fused-MVM ragged-M sweep)."""
    BH, hd = 2, 16
    q, k, v = _fa_rand(S, (BH, S, hd), (BH, S, hd), (BH, S, hd))
    _fa_check(q, k, v, bq=64, bk=64)


def test_flash_attention_q_offset_chunk():
    """Chunked-prefill masking: a 64-query chunk at absolute offset 192
    attends causally against a 256-key cache."""
    BH, hd, off, C, L = 2, 16, 192, 64, 256
    q, k, v = _fa_rand(9, (BH, C, hd), (BH, L, hd), (BH, L, hd))
    _fa_check(q, k, v, q_offset=off, bq=32, bk=32)


def test_flash_attention_kv_len_masks_staged_garbage():
    """kv_len truncation: keys beyond the staged fill are invisible even
    when the capacity buffer holds garbage there."""
    BH, hd, C, L = 2, 16, 32, 128
    q, k, v = _fa_rand(11, (BH, C, hd), (BH, L, hd), (BH, L, hd))
    kv_len = 64
    got = None
    from repro.kernels import flash_attention as fa
    got = fa.flash_attention(q, k, v, q_offset=kv_len - C, kv_len=kv_len,
                             bq=32, bk=32, interpret=True)
    # poisoning the masked tail must not change the output
    k2 = k.at[:, kv_len:].set(1e4)
    v2 = v.at[:, kv_len:].set(-1e4)
    got2 = fa.flash_attention(q, k2, v2, q_offset=kv_len - C,
                              kv_len=kv_len, bq=32, bk=32, interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(got2))
    _fa_check(q, k, v, q_offset=kv_len - C, kv_len=kv_len, bq=32, bk=32)


def test_flash_attention_noncausal_ragged():
    BH, Sq, L, hd = 2, 50, 70, 16
    q, k, v = _fa_rand(13, (BH, Sq, hd), (BH, L, hd), (BH, L, hd))
    _fa_check(q, k, v, causal=False, bq=32, bk=32)


def test_flash_attention_traced_q_offset_one_trace():
    """q_offset is a traced SMEM scalar: one jit serves every chunk
    offset (the retrace-family contract chunked prefill relies on)."""
    from repro.kernels import flash_attention as fa
    BH, C, L, hd = 2, 32, 128, 16
    q, k, v = _fa_rand(17, (BH, C, hd), (BH, L, hd), (BH, L, hd))
    traces = []

    @jax.jit
    def f(q, k, v, off):
        traces.append(1)
        return fa.flash_attention(q, k, v, q_offset=off, bq=32, bk=32,
                                  interpret=True)

    for off in (0, 32, 96):
        got = f(q, k, v, jnp.int32(off))
        want = ref.flash_attention_ref(q, k, v, q_offset=off)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=3e-5, atol=3e-5)
    assert len(traces) == 1


def test_flash_attention_default_blocks_platform():
    """interpret mode wants few fat blocks (the XLA-loop per-step constant
    dominates); TPU keeps MXU-native 128s."""
    from repro.kernels.flash_attention import default_blocks
    assert default_blocks(2048, 2048, True) == (1024, 1024)
    assert default_blocks(2048, 2048, False) == (128, 128)
    assert default_blocks(64, 40, True) == (64, 40)


def test_tile_plan_prefill_rows():
    """_fit_rows extends the adaptive plan to prefill widths: M <= cap
    rounds to the sublane; bigger M takes the largest dividing tile in
    (cap/2, cap] — and bm never changes numerics (fp32 accumulation order
    is a bk property), so the bit-identity gates keep holding."""
    from repro.kernels.photonic_mvm import tile_plan
    assert tile_plan(2048, 512, 512) == (128, 512, 512)   # even full tiles
    assert tile_plan(2048, 512, 512, cap_m=256) == (256, 512, 512)
    assert tile_plan(192, 512, 512) == (96, 512, 512)     # largest divisor
    assert tile_plan(200, 512, 512) == (128, 512, 512)    # none in range
    # prior decode behaviour unchanged
    assert tile_plan(2, 512, 1024) == (8, 512, 512)
    assert tile_plan(130, 512, 512) == (128, 512, 512)
