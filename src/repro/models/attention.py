"""Attention family: GQA/MHA with RoPE + KV cache, MLA (DeepSeek-V2),
cross-attention (VLM / enc-dec).

Cache layouts (per logical layer; stacked [R, T, ...] by the PRM runner):
  gqa:    {"k": (B, L, KV, hd), "v": (B, L, KV, hd)}
  mla:    {"ckv": (B, L, kv_lora), "kr": (B, L, rope_dim)}   (compressed!)
  cross:  {"ck": (B, M, KV, hd), "cv": (B, M, KV, hd)}       (encoder memory)

Decode steps take ``pos`` as either a scalar (aligned batched decode — every
slot at the same position) or a ``(B,)`` int vector (continuous batching —
each slot at its own position; DESIGN.md §Serving).  The cache mask and RoPE
angles are per-slot in the vector case.  Softmax is always fp32.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.configs.base import MLAConfig, ModelConfig
from repro.core.backend import resolve as resolve_backend
from repro.models.layers import (_dense_init, apply_norm, apply_rope,
                                 rope_angles, yarn_mscale)

NEG_INF = -1e30


def _maybe_t(x, w, transpose, backend=None, tp_hint=None):
    """OBU transpose where the matrix is square; identity path otherwise.
    Routed through the execution backend (xla dot_general | photonic Pallas
    kernel, the transpose as the pre-swapped kernel variant).  ``tp_hint``
    passes through to ``Backend.dot`` — the output projections mark
    themselves "row" so a TP mesh consumes the head-sharded context."""
    bk = resolve_backend(backend)
    if transpose and w.shape[0] == w.shape[1]:
        return bk.dot(x, w, transpose=True, tp_hint=tp_hint)
    return bk.dot(x, w, transpose=False, tp_hint=tp_hint)


def _past_valid(pos, L):
    """(B|1, L) bool mask of cache entries strictly before ``pos``.

    pos scalar -> (1, L) broadcast over the batch (aligned decode);
    pos (B,)   -> (B, L) per-slot visibility (continuous decode)."""
    pos = jnp.asarray(pos)
    if pos.ndim == 0:
        return (jnp.arange(L) < pos)[None, :]
    return jnp.arange(L)[None, :] < pos[:, None]


def _decode_positions(pos):
    """Position array for RoPE at decode: (1,) shared or (B, 1) per-slot."""
    pos = jnp.asarray(pos)
    if pos.ndim == 0:
        return jnp.reshape(pos, (1,))
    return pos[:, None]


# =========================================================================
# GQA / MHA
# =========================================================================
def init_gqa(key, cfg: ModelConfig):
    d, H, KV, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    ks = jax.random.split(key, 4)
    p = {"wq": _dense_init(ks[0], (d, H * hd)),
         "wk": _dense_init(ks[1], (d, KV * hd)),
         "wv": _dense_init(ks[2], (d, KV * hd)),
         "wo": _dense_init(ks[3], (H * hd, d))}
    s = {"wq": ("embed", "heads"), "wk": ("embed", "kv"),
         "wv": ("embed", "kv"), "wo": ("heads", "embed")}
    return p, s


CHUNKED_ATTN_THRESHOLD = 8192   # use O(S*bq) chunked attention beyond this
CHUNK_Q = 1024


def _gqa_attend(q, k, v, mask, scale=None):
    """q: (B,S,H,hd) k/v: (B,L,KV,hd) mask: (B,S,L) or (S,L) broadcastable;
    ``scale`` multiplies the scores (None: 1/sqrt(hd))."""
    B, S, H, hd = q.shape
    KV = k.shape[2]
    G = H // KV
    q = q.reshape(B, S, KV, G, hd)
    scores = jnp.einsum("bskgh,blkh->bkgsl", q, k,
                        preferred_element_type=jnp.float32)
    if scale is None:
        scores = scores / jnp.sqrt(hd).astype(jnp.float32)
    else:
        scores = scores * jnp.float32(scale)
    scores = jnp.where(mask[:, None, None, :, :] if mask.ndim == 3
                       else mask[None, None, None, :, :], scores, NEG_INF)
    att = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgsl,blkh->bskgh", att.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    hd_v = v.shape[-1]                      # MLA: v head dim != qk head dim
    return out.reshape(B, S, H * hd_v).astype(v.dtype)


def attend_seq_xla(q, k, v, *, causal: bool, q_offset=None, scale=None):
    """The einsum/scan attention reference — ``Backend.attention``'s
    fallback path (short sequences, xla execution, active meshes).

    Short query runs take the direct einsum; long ones a lax.scan over
    query chunks (peak memory O(bq * L) instead of O(S * L) — this is what
    makes the 32k-prefill cells fit in HBM; the Pallas flash kernel is the
    TPU-native realization of the same schedule).  ``q_offset`` (python int
    or traced scalar) places query row i at absolute position q_offset + i
    in the causal mask, keys at 0..L-1 — the chunked-prefill mask.
    ``scale`` multiplies the scores (None: 1/sqrt(hd))."""
    B, S, H, hd = q.shape
    L = k.shape[1]
    off = 0 if q_offset is None else q_offset
    if S <= CHUNKED_ATTN_THRESHOLD or S % CHUNK_Q != 0:
        if causal:
            mask = (off + jnp.arange(S))[:, None] >= jnp.arange(L)[None, :]
        else:
            mask = jnp.ones((S, L), dtype=bool)
        return _gqa_attend(q, k, v, mask, scale)
    nq = S // CHUNK_Q
    hd_v = v.shape[-1]
    qs = q.reshape(B, nq, CHUNK_Q, H, hd).transpose(1, 0, 2, 3, 4)

    def body(_, inp):
        qc, i = inp
        q_pos = off + i * CHUNK_Q + jnp.arange(CHUNK_Q)
        if causal:
            mask = q_pos[:, None] >= jnp.arange(L)[None, :]
        else:
            mask = jnp.ones((CHUNK_Q, L), dtype=bool)
        return None, _gqa_attend(qc, k, v, mask, scale)

    _, outs = jax.lax.scan(body, None, (qs, jnp.arange(nq)))
    return outs.transpose(1, 0, 2, 3).reshape(B, S, H * hd_v)


def _attend_seq(q, k, v, causal: bool, backend=None, q_offset=None,
                scale=None):
    """Full-sequence attention through the backend seam: the Backend
    decides flash kernel vs einsum/scan (``Backend.attention``).
    ``scale`` multiplies the scores (None: 1/sqrt(head dim))."""
    return resolve_backend(backend).attention(q, k, v, causal=causal,
                                              q_offset=q_offset, scale=scale)


def gqa_forward(p, cfg: ModelConfig, x, *, transpose=False, causal=True,
                positions=None, cache=None, backend=None):
    """Full-sequence path (train / prefill).  If ``cache`` (a pre-allocated
    capacity buffer) is given, the new K/V are written at offset 0 and the
    filled buffer is returned (prefill)."""
    B, S, d = x.shape
    H, KV, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    q = _maybe_t(x, p["wq"].astype(x.dtype), transpose,
                 backend).reshape(B, S, H, hd)
    k = _maybe_t(x, p["wk"].astype(x.dtype), transpose,
                 backend).reshape(B, S, KV, hd)
    v = _maybe_t(x, p["wv"].astype(x.dtype), transpose,
                 backend).reshape(B, S, KV, hd)
    if positions is None:
        positions = jnp.arange(S)
    cos, sin = rope_angles(positions, hd, cfg.rope_theta)
    q = apply_rope(q, cos, sin)
    k = apply_rope(k, cos, sin)
    out = _attend_seq(q, k, v, causal, backend)
    y = _maybe_t(out, p["wo"].astype(x.dtype), transpose, backend,
                  tp_hint="row")
    if cache is not None:
        ck = jax.lax.dynamic_update_slice(
            cache["k"], k.astype(cache["k"].dtype), (0, 0, 0, 0))
        cv = jax.lax.dynamic_update_slice(
            cache["v"], v.astype(cache["v"].dtype), (0, 0, 0, 0))
        return y, {"k": ck, "v": cv}
    return y, None


def gqa_prefill_chunk(p, cfg: ModelConfig, x, cache, q_offset, *,
                      transpose=False, backend=None):
    """One query chunk of a chunked prefill: x (B, C, d) holds prompt
    tokens at absolute positions q_offset..q_offset+C-1.

    The chunk's K/V are written into the capacity ``cache`` at
    ``q_offset`` (a traced scalar — one jit serves every chunk index), and
    the chunk's queries attend against the WHOLE updated buffer with the
    absolute-position causal mask (``Backend.attention``'s q_offset):
    positions beyond the chunk hold garbage, but causality masks every key
    past q_offset + C - 1, so the result is bit-comparable to the
    monolithic prefill's rows.  Returns (y, filled cache)."""
    B, C, d = x.shape
    H, KV, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    q = _maybe_t(x, p["wq"].astype(x.dtype), transpose,
                 backend).reshape(B, C, H, hd)
    k = _maybe_t(x, p["wk"].astype(x.dtype), transpose,
                 backend).reshape(B, C, KV, hd)
    v = _maybe_t(x, p["wv"].astype(x.dtype), transpose,
                 backend).reshape(B, C, KV, hd)
    positions = q_offset + jnp.arange(C)
    cos, sin = rope_angles(positions, hd, cfg.rope_theta)
    q = apply_rope(q, cos, sin)
    k = apply_rope(k, cos, sin)
    ck = jax.lax.dynamic_update_slice(
        cache["k"], k.astype(cache["k"].dtype), (0, q_offset, 0, 0))
    cv = jax.lax.dynamic_update_slice(
        cache["v"], v.astype(cache["v"].dtype), (0, q_offset, 0, 0))
    out = _attend_seq(q, ck.astype(x.dtype), cv.astype(x.dtype), True,
                      backend, q_offset)
    y = _maybe_t(out, p["wo"].astype(x.dtype), transpose, backend,
                 tp_hint="row")
    return y, {"k": ck, "v": cv}


def _attend_decode(q, ck, cv, k_new, v_new, pos):
    """Decode attention against the *past-only* cache plus the current
    token's K/V held separately — the cache is never rewritten here, so the
    PRM runner can keep it as an in-place scan carry and write only the
    one-token delta (EXPERIMENTS.md §Perf: decode traffic -> floor).

    q: (B,1,H,hd)  ck/cv: (B,L,KV,hd)  k_new/v_new: (B,1,KV,hd)."""
    B, S, H, hd = q.shape
    KV = ck.shape[2]
    G = H // KV
    L = ck.shape[1]
    qg = q.reshape(B, 1, KV, G, hd)
    scale = 1.0 / jnp.sqrt(hd).astype(jnp.float32)
    s_c = jnp.einsum("bskgh,blkh->bkgsl", qg, ck,
                     preferred_element_type=jnp.float32) * scale
    s_c = jnp.where(_past_valid(pos, L)[:, None, None, None, :],
                    s_c, NEG_INF)
    s_n = jnp.einsum("bskgh,blkh->bkgsl", qg, k_new.astype(q.dtype),
                     preferred_element_type=jnp.float32) * scale
    s = jnp.concatenate([s_c, s_n], axis=-1)
    att = jax.nn.softmax(s, axis=-1)
    out = (jnp.einsum("bkgsl,blkh->bskgh",
                      att[..., :L].astype(cv.dtype), cv,
                      preferred_element_type=jnp.float32)
           + jnp.einsum("bkgsl,blkh->bskgh",
                        att[..., L:].astype(q.dtype),
                        v_new.astype(q.dtype),
                        preferred_element_type=jnp.float32))
    hd_v = cv.shape[-1]
    return out.reshape(B, 1, H * hd_v).astype(q.dtype)


def gqa_decode(p, cfg: ModelConfig, x, cache, pos, *, transpose=False,
               backend=None):
    """Single-token decode: x (B,1,d); cache k/v (B,L,KV,hd) read-only;
    pos scalar or (B,) per-slot.  Returns the one-token cache *delta* — the
    stack runner writes it in place."""
    B, S, d = x.shape
    assert S == 1
    H, KV, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    q = _maybe_t(x, p["wq"].astype(x.dtype), transpose,
                 backend).reshape(B, 1, H, hd)
    k = _maybe_t(x, p["wk"].astype(x.dtype), transpose,
                 backend).reshape(B, 1, KV, hd)
    v = _maybe_t(x, p["wv"].astype(x.dtype), transpose,
                 backend).reshape(B, 1, KV, hd)
    cos, sin = rope_angles(_decode_positions(pos), hd, cfg.rope_theta)
    q = apply_rope(q, cos, sin)
    k = apply_rope(k, cos, sin)
    out = _attend_decode(q, cache["k"], cache["v"], k, v, pos)
    y = _maybe_t(out, p["wo"].astype(x.dtype), transpose, backend,
                  tp_hint="row")
    return y, {"k": k.astype(cache["k"].dtype),
               "v": v.astype(cache["v"].dtype)}


def gqa_decode_legacy(p, cfg: ModelConfig, x, cache, pos, *,
                      transpose=False, backend=None):
    """Baseline decode (pre-§Perf): DUS the full cache buffer inside the
    block and attend against it — kept as an A/B knob for the perf log."""
    B, S, d = x.shape
    H, KV, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    q = _maybe_t(x, p["wq"].astype(x.dtype), transpose,
                 backend).reshape(B, 1, H, hd)
    k = _maybe_t(x, p["wk"].astype(x.dtype), transpose,
                 backend).reshape(B, 1, KV, hd)
    v = _maybe_t(x, p["wv"].astype(x.dtype), transpose,
                 backend).reshape(B, 1, KV, hd)
    posv = jnp.reshape(pos, (1,))
    cos, sin = rope_angles(posv, hd, cfg.rope_theta)
    q = apply_rope(q, cos, sin)
    k = apply_rope(k, cos, sin)
    ck = jax.lax.dynamic_update_slice(cache["k"], k.astype(cache["k"].dtype),
                                      (0, pos, 0, 0))
    cv = jax.lax.dynamic_update_slice(cache["v"], v.astype(cache["v"].dtype),
                                      (0, pos, 0, 0))
    L = ck.shape[1]
    mask = (jnp.arange(L) <= pos)[None, :]
    out = _gqa_attend(q, ck, cv, mask)
    y = _maybe_t(out, p["wo"].astype(x.dtype), transpose, backend,
                  tp_hint="row")
    return y, {"k": ck, "v": cv}


def init_gqa_cache(cfg: ModelConfig, batch: int, length: int, dtype):
    KV, hd = cfg.num_kv_heads, cfg.head_dim
    z = jnp.zeros((batch, length, KV, hd), dtype=dtype)
    return {"k": z, "v": z}


# =========================================================================
# MLA — multi-head latent attention (DeepSeek-V2)
# =========================================================================
def init_mla(key, cfg: ModelConfig):
    m: MLAConfig = cfg.mla
    d, H = cfg.d_model, cfg.num_heads
    qd = m.qk_nope_dim + m.qk_rope_dim
    ks = jax.random.split(key, 4)
    p = {"wq": _dense_init(ks[0], (d, H * qd)),
         "w_dkv": _dense_init(ks[1], (d, m.kv_lora_rank + m.qk_rope_dim)),
         "kv_norm": {"scale": jnp.ones((m.kv_lora_rank,))},
         "w_ukv": _dense_init(ks[2],
                              (m.kv_lora_rank, H * (m.qk_nope_dim
                                                    + m.v_head_dim))),
         "wo": _dense_init(ks[3], (H * m.v_head_dim, d))}
    s = {"wq": ("embed", "heads"), "w_dkv": ("embed", "kv_lora"),
         "kv_norm": {"scale": ("kv_lora",)},
         "w_ukv": ("kv_lora", "heads"), "wo": ("heads", "embed")}
    return p, s


def mla_softmax_scale(cfg: ModelConfig):
    """YaRN's ``mscale(factor, mscale_all_dim)^2 / sqrt(qk_nope + qk_rope)``
    under a scaled rope (DeepSeek-V2: about 1.59 over sqrt(192)); None
    where it is the plain 1/sqrt(qk_nope + qk_rope)."""
    rs = cfg.rope_scaling
    if rs is None or not rs.mscale_all_dim:
        return None
    m = cfg.mla
    return (yarn_mscale(rs.factor, rs.mscale_all_dim) ** 2
            / math.sqrt(m.qk_nope_dim + m.qk_rope_dim))


def _up_project(ckv, w_ukv):
    """Latents (B, L, kv_lora) through ``w_ukv`` (kv_lora, H*(nope+v)).
    ``w_ukv`` is never programmed into a bank: prefill up-projects and
    decode absorbs it in the compute dtype alike, accumulating in float32
    (``core/prepared.py``)."""
    return jnp.einsum("blr,rn->bln", ckv, w_ukv.astype(ckv.dtype),
                      preferred_element_type=jnp.float32).astype(ckv.dtype)


def _mla_qkr(p, cfg, x, positions, backend=None):
    """Project q (+rope) and the compressed kv latents for new tokens; the
    latents pass the latent RMSNorm before anything caches or reads
    them."""
    bk = resolve_backend(backend)
    m = cfg.mla
    B, S, _ = x.shape
    H = cfg.num_heads
    q = bk.dot(x, p["wq"].astype(x.dtype), transpose=False)
    q = q.reshape(B, S, H, m.qk_nope_dim + m.qk_rope_dim)
    qn, qr = q[..., :m.qk_nope_dim], q[..., m.qk_nope_dim:]
    dkv = bk.dot(x, p["w_dkv"].astype(x.dtype), transpose=False)
    ckv, kr = dkv[..., :m.kv_lora_rank], dkv[..., m.kv_lora_rank:]
    ckv = apply_norm(p["kv_norm"], ckv, "rms", cfg.norm_eps)
    cos, sin = rope_angles(positions, m.qk_rope_dim, cfg.rope_theta,
                           cfg.rope_scaling)
    qr = apply_rope(qr, cos, sin)
    kr = apply_rope(kr[:, :, None, :], cos, sin)[:, :, 0, :]  # shared head
    return qn, qr, ckv, kr


def mla_forward(p, cfg: ModelConfig, x, *, transpose=False, causal=True,
                positions=None, cache=None, backend=None):
    bk = resolve_backend(backend)
    m = cfg.mla
    B, S, _ = x.shape
    H = cfg.num_heads
    if positions is None:
        positions = jnp.arange(S)
    qn, qr, ckv, kr = _mla_qkr(p, cfg, x, positions, backend)
    ukv = _up_project(ckv, p["w_ukv"])
    ukv = ukv.reshape(B, S, H, m.qk_nope_dim + m.v_head_dim)
    kn, v = ukv[..., :m.qk_nope_dim], ukv[..., m.qk_nope_dim:]
    k = jnp.concatenate([kn, jnp.broadcast_to(kr[:, :, None, :],
                                              (B, S, H, m.qk_rope_dim))],
                        axis=-1)
    q = jnp.concatenate([qn, qr], axis=-1)
    out = _attend_seq(q, k, v, causal, backend,     # KV == H here
                      scale=mla_softmax_scale(cfg))
    y = bk.dot(out, p["wo"].astype(x.dtype), transpose=False,
               tp_hint="row")
    if cache is not None:
        cc = jax.lax.dynamic_update_slice(
            cache["ckv"], ckv.astype(cache["ckv"].dtype), (0, 0, 0))
        ck = jax.lax.dynamic_update_slice(
            cache["kr"], kr.astype(cache["kr"].dtype), (0, 0, 0))
        return y, {"ckv": cc, "kr": ck}
    return y, None


def mla_prefill_chunk(p, cfg: ModelConfig, x, cache, q_offset, *,
                      transpose=False, backend=None):
    """Chunked-prefill step for MLA: the chunk's compressed latents are
    written into the cache at ``q_offset``, then the FULL cached latent
    buffer is up-projected and attended with the absolute-position causal
    mask — the same recompute-from-latents shape the absorbed decode path
    uses, at chunk width.  The up-projection of the garbage tail is wasted
    work the causal mask discards; chunking trades that for bounded
    per-step latency and a fixed jit family."""
    bk = resolve_backend(backend)
    m = cfg.mla
    B, C, _ = x.shape
    H = cfg.num_heads
    positions = q_offset + jnp.arange(C)
    qn, qr, ckv_new, kr_new = _mla_qkr(p, cfg, x, positions, backend)
    cc = jax.lax.dynamic_update_slice(
        cache["ckv"], ckv_new.astype(cache["ckv"].dtype), (0, q_offset, 0))
    ckr = jax.lax.dynamic_update_slice(
        cache["kr"], kr_new.astype(cache["kr"].dtype), (0, q_offset, 0))
    L = cc.shape[1]
    with jax.named_scope("mla.up_proj"):
        ukv = _up_project(cc.astype(x.dtype), p["w_ukv"])
    ukv = ukv.reshape(B, L, H, m.qk_nope_dim + m.v_head_dim)
    kn, v = ukv[..., :m.qk_nope_dim], ukv[..., m.qk_nope_dim:]
    k = jnp.concatenate(
        [kn, jnp.broadcast_to(ckr.astype(x.dtype)[:, :, None, :],
                              (B, L, H, m.qk_rope_dim))], axis=-1)
    q = jnp.concatenate([qn, qr], axis=-1)
    out = _attend_seq(q, k, v, True, backend, q_offset,
                      scale=mla_softmax_scale(cfg))
    y = bk.dot(out, p["wo"].astype(x.dtype), transpose=False,
               tp_hint="row")
    return y, {"ckv": cc, "kr": ckr}


def mla_decode(p, cfg: ModelConfig, x, cache, pos, *, transpose=False,
               backend=None):
    """Absorbed-matrix MLA decode: attention runs in the compressed latent
    space (scores against ``ckv`` directly), the up-projection is applied
    only to the attended context — the paper-faithful low-memory path.
    The cache is read-only; the one-token latent delta is returned for the
    stack runner to write in place."""
    bk = resolve_backend(backend)
    m = cfg.mla
    B, S, _ = x.shape
    assert S == 1
    H = cfg.num_heads
    qn, qr, ckv_new, kr_new = _mla_qkr(p, cfg, x, _decode_positions(pos),
                                       backend)
    ckv, kr = cache["ckv"], cache["kr"]
    L = ckv.shape[1]
    w_ukv = p["w_ukv"].astype(x.dtype).reshape(
        m.kv_lora_rank, H, m.qk_nope_dim + m.v_head_dim)
    w_uk = w_ukv[..., :m.qk_nope_dim]          # (lora, H, nope)
    w_uv = w_ukv[..., m.qk_nope_dim:]          # (lora, H, v)
    with jax.named_scope("mla.absorb"):
        q_lat = jnp.einsum("bshn,rhn->bshr", qn, w_uk)  # absorb W_uk into q
    scale = mla_softmax_scale(cfg)
    scale = (1.0 / jnp.sqrt(m.qk_nope_dim + m.qk_rope_dim).astype(jnp.float32)
             if scale is None else jnp.float32(scale))
    with jax.named_scope("mla.latent_attn"):
        s_c = (jnp.einsum("bshr,blr->bhsl", q_lat, ckv,
                          preferred_element_type=jnp.float32)
               + jnp.einsum("bshr,blr->bhsl", qr, kr,
                            preferred_element_type=jnp.float32)) * scale
        s_c = jnp.where(_past_valid(pos, L)[:, None, None, :], s_c, NEG_INF)
        s_n = (jnp.einsum("bshr,blr->bhsl", q_lat, ckv_new.astype(x.dtype),
                          preferred_element_type=jnp.float32)
               + jnp.einsum("bshr,blr->bhsl", qr, kr_new.astype(x.dtype),
                            preferred_element_type=jnp.float32)) * scale
        att = jax.nn.softmax(jnp.concatenate([s_c, s_n], axis=-1), axis=-1)
        ctx_lat = (jnp.einsum("bhsl,blr->bshr", att[..., :L].astype(x.dtype),
                              ckv)
                   + jnp.einsum("bhsl,blr->bshr",
                                att[..., L:].astype(x.dtype),
                                ckv_new.astype(x.dtype)))
    with jax.named_scope("mla.absorb"):
        ctx = jnp.einsum("bshr,rhv->bshv", ctx_lat, w_uv)
    y = bk.dot(ctx.reshape(B, S, H * m.v_head_dim),
               p["wo"].astype(x.dtype), transpose=False, tp_hint="row")
    return y, {"ckv": ckv_new.astype(ckv.dtype),
               "kr": kr_new.astype(kr.dtype)}


def init_mla_cache(cfg: ModelConfig, batch: int, length: int, dtype):
    m = cfg.mla
    return {"ckv": jnp.zeros((batch, length, m.kv_lora_rank), dtype=dtype),
            "kr": jnp.zeros((batch, length, m.qk_rope_dim), dtype=dtype)}


# =========================================================================
# cross-attention (VLM image layers, enc-dec decoder)
# =========================================================================
def init_cross_attn(key, cfg: ModelConfig, d_memory: int | None = None):
    d, H, KV, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    dm = d_memory or d
    ks = jax.random.split(key, 4)
    p = {"wq": _dense_init(ks[0], (d, H * hd)),
         "wk": _dense_init(ks[1], (dm, KV * hd)),
         "wv": _dense_init(ks[2], (dm, KV * hd)),
         "wo": _dense_init(ks[3], (H * hd, d))}
    s = {"wq": ("embed", "heads"), "wk": ("embed", "kv"),
         "wv": ("embed", "kv"), "wo": ("heads", "embed")}
    return p, s


def cross_attn_memory(p, cfg: ModelConfig, memory, backend=None):
    """Precompute K/V from the (frozen-per-request) memory stream."""
    bk = resolve_backend(backend)
    B, M, _ = memory.shape
    KV, hd = cfg.num_kv_heads, cfg.head_dim
    k = bk.dot(memory, p["wk"].astype(memory.dtype),
               transpose=False).reshape(B, M, KV, hd)
    v = bk.dot(memory, p["wv"].astype(memory.dtype),
               transpose=False).reshape(B, M, KV, hd)
    return {"ck": k, "cv": v}


def cross_attn_forward(p, cfg: ModelConfig, x, kv, *, transpose=False,
                       backend=None):
    """x: (B,S,d); kv: precomputed {"ck","cv"} (B,M,KV,hd)."""
    B, S, _ = x.shape
    H, hd = cfg.num_heads, cfg.head_dim
    q = _maybe_t(x, p["wq"].astype(x.dtype), transpose,
                 backend).reshape(B, S, H, hd)
    out = _attend_seq(q, kv["ck"], kv["cv"], False, backend)
    return _maybe_t(out, p["wo"].astype(x.dtype), transpose, backend,
                  tp_hint="row")
