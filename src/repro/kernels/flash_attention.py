"""Blocked (flash-style) attention Pallas kernel — the prefill path.

Online-softmax attention over (bq, bk) tiles with fp32 running max / sum /
accumulator in VMEM scratch.  Grid: (BH_q, Sq/bq, L/bk), K innermost.  The
kernel keeps the Sq x L score matrix out of HBM entirely; it is the compute
hot-spot of the long-sequence prefill cells.

Layout contract (what ``ops.flash_attention`` flattens down to):

  * q: (BH_q, Sq, hd) — batch*query-heads flattened, head-major within a
    batch row (head index h = kv*G + g, matching ``_gqa_attend``'s
    (B, S, KV, G, hd) reshape);
  * k: (BH_kv, L, hd), v: (BH_kv, L, hd_v) — batch*kv-heads.  GQA rides on
    the grid index map: query row b reads kv row b // G (G = BH_q / BH_kv),
    so grouped heads share K/V blocks with no materialized repeat.  MLA's
    v-head-dim != qk-head-dim falls out of the separate hd_v.
  * Ragged Sq / L pad to the tile inside this wrapper; padded keys are
    masked to NEG_INF in-kernel (``kv_len``) and padded query rows are
    sliced off the output.
  * ``q_offset`` (python int or traced scalar) places query row i at
    absolute position q_offset + i for the causal mask, so a chunked
    prefill against a partially filled KV cache masks exactly like the
    monolithic pass.  Keys run at absolute positions 0..L-1.

Causal runs skip fully-masked key blocks (first key of the block beyond the
last absolute query position) — the classic flash-attention lower-triangle
schedule, and on interpret/CPU the difference between beating the einsum
path and losing to it.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.platform import default_interpret

NEG_INF = -1e30


def default_blocks(Sq: int, L: int, interpret: bool) -> tuple[int, int]:
    """Pick (bq, bk) for a (Sq, L) attention problem.

    On TPU the MXU wants classic 128x128 tiles.  Interpret mode compiles the
    grid into an XLA loop whose per-step overhead dwarfs the tile math, so
    CPU runs want the fewest, fattest steps that still fit comfortably in
    cache — measured on the S=2048 ladder, (1024, 1024) with the causal
    block-skip beats the einsum path ~2.9x, while 128x128 loses to it 6x.
    """
    if not interpret:
        return 128, 128
    return min(1024, _round_up(Sq, 8)), min(1024, _round_up(L, 8))


def _round_up(n: int, mult: int) -> int:
    return ((n + mult - 1) // mult) * mult


def _kernel(off_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            nk: int, bq: int, bk: int, scale: float, causal: bool,
            kv_len: int):
    qb = pl.program_id(1)
    kb = pl.program_id(2)
    q_off = off_ref[0, 0]

    @pl.when(kb == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # key-block validity: blocks past kv_len hold pure padding; causal runs
    # also skip blocks entirely above the diagonal (first key of the block
    # beyond the last absolute query position of this q block)
    run = kb * bk < kv_len
    if causal:
        run = jnp.logical_and(run, kb * bk <= q_off + (qb + 1) * bq - 1)

    @pl.when(run)
    def _update():
        q = q_ref[...].astype(jnp.float32)               # (bq, hd)
        k = k_ref[...].astype(jnp.float32)               # (bk, hd)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        kj = kb * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = kj < kv_len
        if causal:
            qi = q_off + qb * bq + jax.lax.broadcasted_iota(
                jnp.int32, (bq, bk), 0)
            mask = jnp.logical_and(mask, qi >= kj)
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
            p, v_ref[...].astype(jnp.float32),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(kb == nk - 1)
    def _finalize():
        o_ref[...] = (acc_ref[...] / l_ref[...]).astype(o_ref.dtype)


def flash_attention(q, k, v, *, causal=True, q_offset=None, kv_len=None,
                    bq=None, bk=None, interpret=None, scale=None):
    """Blocked attention over flattened heads.

    q: (BH_q, Sq, hd); k: (BH_kv, L, hd); v: (BH_kv, L, hd_v) with
    BH_q % BH_kv == 0 (query row b reads kv row b // G).  Returns
    (BH_q, Sq, hd_v).  Ragged Sq / L are padded to the tile here; ``kv_len``
    (default L) masks trailing padded keys; ``q_offset`` shifts the causal
    mask for chunked prefill.  ``scale`` (a static python float)
    multiplies the scores; None is 1/sqrt(hd).  ``interpret=None`` resolves
    from the platform (the same check the MVM kernels use) instead of the
    old hardcoded True.
    """
    BHq, Sq, hd = q.shape
    BHkv, L, hdk = k.shape
    hdv = v.shape[-1]
    assert hdk == hd, (hd, hdk)
    assert v.shape[:2] == (BHkv, L), (v.shape, k.shape)
    assert BHq % BHkv == 0, (BHq, BHkv)
    G = BHq // BHkv
    if interpret is None:
        interpret = default_interpret()
    if kv_len is None:
        kv_len = L
    if bq is None or bk is None:
        dbq, dbk = default_blocks(Sq, L, interpret)
        bq = dbq if bq is None else bq
        bk = dbk if bk is None else bk
    bq = min(bq, _round_up(Sq, 8))
    bk = min(bk, _round_up(L, 8))
    Sq_p, L_p = _round_up(Sq, bq), _round_up(L, bk)
    if Sq_p != Sq:
        q = jnp.pad(q, ((0, 0), (0, Sq_p - Sq), (0, 0)))
    if L_p != L:
        k = jnp.pad(k, ((0, 0), (0, L_p - L), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, L_p - L), (0, 0)))
    off = jnp.full((1, 1), 0 if q_offset is None else q_offset, jnp.int32)
    scale = 1.0 / (hd ** 0.5) if scale is None else float(scale)
    grid = (BHq, Sq_p // bq, L_p // bk)
    out = pl.pallas_call(
        functools.partial(_kernel, nk=grid[2], bq=bq, bk=bk, scale=scale,
                          causal=causal, kv_len=kv_len),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1), lambda b, i, j: (0, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((None, bq, hd), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((None, bk, hd), lambda b, i, j: (b // G, j, 0)),
            pl.BlockSpec((None, bk, hdv), lambda b, i, j: (b // G, j, 0)),
        ],
        out_specs=pl.BlockSpec((None, bq, hdv), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((BHq, Sq_p, hdv), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, 1), jnp.float32),
                        pltpu.VMEM((bq, 1), jnp.float32),
                        pltpu.VMEM((bq, hdv), jnp.float32)],
        interpret=interpret,
    )(off, q, k, v)
    return out[:, :Sq] if Sq_p != Sq else out
