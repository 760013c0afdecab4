"""Public jit'd wrappers around the Pallas kernels.

Every kernel resolves ``interpret`` from the platform: off the TPU the
kernels run in the Pallas interpreter, on a TPU they lower natively.  All
shape plumbing (quantization, padding, head flattening) lives here so
callers stay tensor-shaped.

Three families of matmul entry points:

  * ``photonic_matmul_kernel`` / ``_t`` / ``reuse_resident_matmul`` — the
    legacy self-contained path: quantize the fp weight in-step, then run the
    offset-decomposed MVM.  Weight quantization is re-derived inside every
    jitted step (the per-token tax DESIGN.md §Prepared weights removes).
  * ``photonic_matmul_prepared`` / ``_prepared_t`` / ``reuse_resident_
    matmul_prepared`` — the write-once path: take a *prepared* (int8,
    scale) bank (`core/prepared.py`, built once by ``Program.build``) and
    skip straight to the kernel.  Both families share the same quantizers
    (`core.prepared.quantize_weight*`), so prepared and in-step execution
    are bit-identical.
  * ``photonic_matmul_fused`` — the decode-path megakernel (DESIGN.md
    §Fused decode path): activations enter the kernel floating (A8 grid in
    the prologue; the only pre-pass is the ``a8_scale`` abs-max reduction),
    both OBU orientations select a kernel variant, and the blend epilogue
    (bias + activation + blocked output shuffle) folds into ``_finalize``.
    Bit-identical to prepared-MVM + separate blend at the same tile plan.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.photonic import a8_scale, quantize_symmetric
from repro.core.prepared import quantize_weight, quantize_weight_t
from repro.kernels import blend as _blend
from repro.kernels import flash_attention as _fa
from repro.kernels import photonic_mvm as _pm
from repro.kernels import ssd as _ssd
from repro.kernels.photonic_mvm import round_up, tile_plan  # noqa: F401


# =========================================================================
# in-step quantize path (legacy)
# =========================================================================
def photonic_matmul_kernel(x, w, *, bm=128, bk=128, bn=128):
    """Full photonic W8A8 path: quantize -> offset-decomposed Pallas MVM."""
    wq, wscale = quantize_weight(w)
    return photonic_matmul_prepared(x, wq, wscale, bm=bm, bk=bk, bn=bn)


def photonic_matmul_kernel_t(x, w, *, bm=128, bk=128, bn=128):
    """Photonic W8A8 ``x @ w.T`` for w: (n, k) — the OBU optical-transpose
    path as a pre-swapped kernel variant (no materialized transpose; the
    weight tiles are swapped in-register inside the kernel).

    Per-output-channel weight scales run along w's ROWS here (axis 0 is the
    output channel of the transposed use)."""
    wq, wscale = quantize_weight_t(w)
    return photonic_matmul_prepared_t(x, wq, wscale, bm=bm, bk=bk, bn=bn)


def reuse_resident_matmul(x_stack, w, *, bm=128, bn=128):
    """W8A8 matmul of T independent activation streams against ONE weight.

    x_stack: (T, ..., k) — e.g. the token buffers of the T logical experts
    blended from one basic expert.  The weight is quantized/programmed once
    and stays VMEM-resident while all T streams pass through it
    (kernels/photonic_mvm.photonic_mvm_resident); activations get per-step
    A8 scales.  Returns (T, ..., n)."""
    wq, wscale = quantize_weight(w)
    return reuse_resident_matmul_prepared(x_stack, wq, wscale, bm=bm, bn=bn)


# =========================================================================
# prepared-bank path (write-once)
# =========================================================================
def _quantize_a8(x, x_scale):
    """Per-tensor A8 of ``x``: derive the scale here (``x_scale=None``) or
    quantize on a caller-supplied grid — the shard_map'd backend passes the
    GLOBAL activation's scale so every shard of a partitioned matmul
    quantizes exactly like the single-device kernel would."""
    if x_scale is None:
        return quantize_symmetric(x, 8)
    q = jnp.clip(jnp.round(x / x_scale), -128.0, 127.0)
    return q.astype(jnp.int8), x_scale


def photonic_matmul_prepared(x, wq, wscale, *, bm=128, bk=128, bn=128,
                             qmax=127.0, x_scale=None, out_dtype=None):
    """Offset-decomposed MVM against an already-programmed bank.

    wq: int8 (k, n) per-output-channel quantized; wscale: f32 (n,).  Only
    the activations are quantized here — the weight-side work (normalize,
    round, scale derivation) happened once at ``Program.build`` time.
    ``out_dtype`` defaults to x's dtype."""
    xq, xscale = _quantize_a8(x, x_scale)
    lead = x.shape[:-1]
    x2 = xq.reshape(-1, x.shape[-1])
    y = _pm.photonic_mvm(x2, wq, xscale, wscale.reshape(-1),
                         bm=bm, bk=bk, bn=bn, qmax=qmax)
    return y.reshape(*lead, wq.shape[1]).astype(out_dtype or x.dtype)


def photonic_matmul_prepared_t(x, wq, wscale, *, bm=128, bk=128, bn=128,
                               qmax=127.0, x_scale=None, out_dtype=None):
    """Prepared ``x @ w.T``: wq int8 (n, k) per-ROW quantized; wscale (n,)."""
    xq, xscale = _quantize_a8(x, x_scale)
    lead = x.shape[:-1]
    x2 = xq.reshape(-1, x.shape[-1])
    y = _pm.photonic_mvm_t(x2, wq, xscale, wscale,
                           bm=bm, bk=bk, bn=bn, qmax=qmax)
    return y.reshape(*lead, wq.shape[0]).astype(out_dtype or x.dtype)


def reuse_resident_matmul_prepared(x_stack, wq, wscale, *, bm=128, bn=128,
                                   qmax=127.0):
    """Prepared reuse-resident MVM: T streams through one programmed bank."""
    T = x_stack.shape[0]
    lead = x_stack.shape[1:-1]
    K = x_stack.shape[-1]
    x2 = x_stack.reshape(T, -1, K)
    xq, xscale = quantize_symmetric(x2, 8, axis=(1, 2))          # (T,1,1)
    # clamp the row tile to the serving width, but keep it MXU-sublane
    # aligned: a 2-row stream runs an 8-row tile, never a ragged 2-row one
    bm_eff = min(bm, round_up(x2.shape[1], 8))
    y = _pm.photonic_mvm_resident(xq, wq, xscale.reshape(T),
                                  wscale.reshape(-1),
                                  bm=bm_eff, bn=bn, qmax=qmax)
    return y.reshape(T, *lead, wq.shape[1]).astype(x_stack.dtype)


# =========================================================================
# fused decode-path megakernel (quantize + MVM + blend in one pallas_call)
# =========================================================================
def photonic_matmul_fused(x, wq, wscale, *, transpose=False, bias=None,
                          block_perm=None, block=0, activation="none",
                          bm=128, bk=128, bn=128, qmax=127.0, x_scale=None,
                          out_dtype=None, layer=None):
    """One-``pallas_call`` serving matmul against a prepared bank.

    x: fp (..., k); wq/wscale: a prepared orientation — (k, n)/per-column,
    or (n, k)/per-row with ``transpose=True``.  The A8 grid is applied in
    the kernel prologue (only ``a8_scale``'s abs-max reduction runs
    outside); ``bias``/``activation``/``block_perm`` run as the in-kernel
    blend epilogue.  Bit-identical to ``photonic_matmul_prepared*`` followed
    by ``blend_shuffle`` at the same (bm, bk, bn) — except the bias add,
    which XLA contracts into the rescale fma (<= 1 ulp; see
    ``photonic_mvm._kernel_fused``).  ``x_scale`` overrides the A8 scale
    (the shard_map'd backend passes the global activation's scale so a
    partitioned matmul's shards all quantize on the single-device grid).
    ``out_dtype`` defaults to x's dtype.  With ``layer``, wq/wscale are a
    stacked bank (R, ...) read at that layer in place."""
    xscale = a8_scale(x) if x_scale is None else x_scale
    lead = x.shape[:-1]
    x2 = x.reshape(-1, x.shape[-1])
    n_out = wq.shape[-2] if transpose else wq.shape[-1]
    perm = tuple(int(v) for v in block_perm) if block_perm is not None \
        else None
    y = _pm.photonic_mvm_fused(
        x2, wq, xscale, wscale.reshape(*wq.shape[:-2], n_out), bias=bias,
        layer=layer, bm=bm, bk=bk, bn=bn,
        qmax=qmax, transpose=transpose, activation=activation,
        block_perm=perm, block=block, out_dtype=out_dtype or x.dtype)
    return y.reshape(*lead, n_out)


def photonic_matmul_noisy(x, wq, wscale, *, noise, bank_tag=None,
                          transpose=False, bm=128, bk=128, bn=128,
                          qmax=127.0, x_scale=None):
    """Split MVM + fault model: the hardware-honest photonic matmul.

    Runs the bit-exact prepared MVM kernel, then applies the
    ``core/noise.py`` perturbation (per-tile gain error, write-age drift,
    crosstalk, DAC/TIA noise) to the RAW MVM output — after the offset
    recompose and TIA rescale, before the electronic blend epilogue, which
    is where those error sources physically enter the signal chain.  The
    Pallas kernels themselves stay bit-exact (the fault-model boundary; see
    ``kernels/photonic_mvm.py``), so the clean paths keep their bit-identity
    gates and the noise model stays backend-portable (plain jnp, no kernel
    variant per error source)."""
    from repro.core import noise as noise_lib
    mm = photonic_matmul_prepared_t if transpose else photonic_matmul_prepared
    y = mm(x, wq, wscale, bm=bm, bk=bk, bn=bn, qmax=qmax, x_scale=x_scale)
    return noise_lib.perturb_mvm_output(y, noise, tag=bank_tag,
                                        transpose=transpose)


def blend_shuffle(x, bias, block_perm, *, block=128, activation="relu"):
    lead = x.shape[:-1]
    x2 = x.reshape(-1, x.shape[-1])
    y = _blend.blend_shuffle(x2, bias, block_perm, block=block,
                             bm=min(128, round_up(x2.shape[0], 8)),
                             activation=activation)
    return y.reshape(*lead, x.shape[-1])


def flash_attention(q, k, v, *, causal=True, q_offset=None, bq=None,
                    bk=None, scale=None):
    """Tensor-shaped flash attention: q (B, Sq, H, hd); k (B, L, KV, hd);
    v (B, L, KV, hd_v) with H % KV == 0 (GQA groups; MLA's hd_v != hd rides
    on the separate v head dim).  Head flattening keeps the (B, S, KV, G)
    ordering of ``_gqa_attend`` so query row b*H + kv*G + g reads kv row
    b*KV + kv inside the kernel.  Returns (B, Sq, H, hd_v).  ``q_offset``
    shifts the causal mask for chunked prefill; ``scale`` multiplies the
    scores (None: 1/sqrt(hd)); block sizes and interpret default from the
    platform (``flash_attention.default_blocks``)."""
    B, Sq, H, hd = q.shape
    _, L, KV, hdv = v.shape
    qf = q.transpose(0, 2, 1, 3).reshape(B * H, Sq, hd)
    kf = k.transpose(0, 2, 1, 3).reshape(B * KV, L, hd)
    vf = v.transpose(0, 2, 1, 3).reshape(B * KV, L, hdv)
    o = _fa.flash_attention(qf, kf, vf, causal=causal, q_offset=q_offset,
                            bq=bq, bk=bk, scale=scale)
    return o.reshape(B, H, Sq, hdv).transpose(0, 2, 1, 3)


def ssd_chunk(x, dA, B, C):
    return _ssd.ssd_chunk(x, dA, B, C)
