"""Full model assembly: decoder-only LMs (dense / MoE / hybrid / VLM),
encoder–decoder (whisper), and pure-SSM stacks — all built from PRM-shared
scan segments.

A model is a list of **segments**; each segment is a homogeneous stack of
*groups* (the scan unit).  A group contains ``group_size`` layers with a fixed
intra-group pattern (jamba: 7 mamba + 1 attn; llama-vision: 4 self + 1 cross).
PRM weight sharing operates at group granularity within a segment via
``core.sharing.run_stack``.

Cache pytree (serve): {segment_name: [R, T, {"l{i}": mixer_cache}]}.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core import backend as backend_lib
from repro.core.prm import ReuseConfig
from repro.core.sharing import SharedStack, run_stack, stacked_init
from repro.models import attention as attn
from repro.models import moe as moe_lib
from repro.models import ssm as ssm_lib
from repro.models.layers import (apply_mlp, apply_norm, embed, init_embedding,
                                 init_mlp, init_norm, init_unembed, unembed,
                                 init_linear, apply_linear)


# =========================================================================
# segments
# =========================================================================
@dataclasses.dataclass(frozen=True)
class SegmentSpec:
    name: str
    num_groups: int
    group_size: int
    mixer_kinds: tuple           # per local layer: attn|ssm|cross_attn|attn_cross
    ffn_kinds: tuple             # per local layer: dense|dense_first|moe|none
    causal: bool
    reuse: Optional[ReuseConfig]
    stream: str = "decoder"      # encoder | decoder

    @property
    def depth(self) -> int:
        return self.num_groups * self.group_size


def _seg_reuse(cfg: ModelConfig, num_groups: int):
    """Apply cfg.reuse to a segment iff it covers exactly its group count."""
    r = cfg.reuse
    if r is not None and r.logical_depth == num_groups:
        return r
    return None


def build_segments(cfg: ModelConfig) -> tuple:
    if cfg.family == "audio":
        a = cfg.audio
        enc = SegmentSpec("enc", a.encoder_layers, 1, ("attn",), ("dense",),
                          causal=False, reuse=_seg_reuse(cfg, a.encoder_layers),
                          stream="encoder")
        dec = SegmentSpec("dec", cfg.num_layers, 1, ("attn_cross",),
                          ("dense",), causal=True,
                          reuse=_seg_reuse(cfg, cfg.num_layers))
        return (enc, dec)
    gs = cfg.group_size
    first_dense = cfg.moe.first_dense if cfg.moe else 0
    segs = []
    if first_dense:
        segs.append(SegmentSpec(
            "pre", first_dense, 1,
            tuple(cfg.layer_kind(i) for i in range(1)),
            ("dense_first",), causal=True, reuse=None))
    depth = cfg.num_layers - first_dense
    ngroups = depth // gs
    mixer_kinds = tuple(cfg.layer_kind(first_dense + i) for i in range(gs))
    ffn_kinds = tuple(cfg.ffn_kind(first_dense + i) for i in range(gs))
    segs.append(SegmentSpec("main", ngroups, gs, mixer_kinds, ffn_kinds,
                            causal=True, reuse=_seg_reuse(cfg, ngroups)))
    return tuple(segs)


# =========================================================================
# one layer
# =========================================================================
def _init_mixer(key, cfg: ModelConfig, kind: str):
    if kind == "attn":
        if cfg.mla is not None:
            return attn.init_mla(key, cfg)
        return attn.init_gqa(key, cfg)
    if kind == "ssm":
        return ssm_lib.init_ssm(key, cfg)
    if kind == "cross_attn":
        return attn.init_cross_attn(key, cfg)
    if kind == "attn_cross":
        k1, k2 = jax.random.split(key)
        p1, s1 = attn.init_gqa(k1, cfg)
        p2, s2 = attn.init_cross_attn(k2, cfg)
        return ({"self": p1, "cross": p2, },
                {"self": s1, "cross": s2})
    raise ValueError(kind)


def _init_ffn(key, cfg: ModelConfig, kind: str):
    if kind == "none":
        return None, None
    if kind == "moe":
        return moe_lib.init_moe(key, cfg.d_model, cfg.moe)
    d_ff = (cfg.moe.first_dense_d_ff if kind == "dense_first" and cfg.moe
            else cfg.d_ff)
    return init_mlp(key, cfg.d_model, d_ff, act=cfg.mlp_act)


def init_layer(key, cfg: ModelConfig, mixer_kind: str, ffn_kind: str):
    ks = jax.random.split(key, 4)
    p, s = {}, {}
    p["norm1"], s["norm1"] = init_norm(cfg.d_model, cfg.norm)
    p["mixer"], s["mixer"] = _init_mixer(ks[1], cfg, mixer_kind)
    if mixer_kind == "attn_cross":
        p["norm_cross"], s["norm_cross"] = init_norm(cfg.d_model, cfg.norm)
    if ffn_kind != "none":
        p["norm2"], s["norm2"] = init_norm(cfg.d_model, cfg.norm)
        p["ffn"], s["ffn"] = _init_ffn(ks[2], cfg, ffn_kind)
    return p, s


def _gather_for_prefill(y, mode, ctx):
    """Prefill takes each sublayer's output whole before the residual add:
    a tensor-parallel matmul may leave it "model"-sharded (reduce-scatter),
    and GSPMD would then partition the next norm's reduction over "model",
    changing its summation order.  Decode defers that gather to the layer
    boundary (the collective-compute overlap, ``api._serve_act_pspec``)."""
    if mode != "prefill" or ctx.get("act_pspec") is None:
        return y
    return jax.lax.with_sharding_constraint(y, ctx["act_pspec"])


def apply_layer(p, cfg: ModelConfig, h, cache, aux, *, mixer_kind, ffn_kind,
                mode, causal, pos, ctx, transpose):
    """One pre-norm residual layer.  Returns (h, cache, aux)."""
    bk = ctx.get("backend") or backend_lib.XLA
    if mode == "prefill_chunk" and mixer_kind != "attn":
        # SSM state integration and cross-attn memory streams would need
        # chunk-to-chunk state threading; the scheduler falls back to
        # monolithic prefill for those stacks (serve/scheduler.py)
        raise ValueError(f"chunked prefill supports attention mixers only, "
                         f"got {mixer_kind!r}")
    hn = apply_norm(p["norm1"], h, cfg.norm, cfg.norm_eps)
    new_cache = cache
    if mixer_kind == "attn":
        fwd = attn.mla_forward if cfg.mla is not None else attn.gqa_forward
        dec = attn.mla_decode if cfg.mla is not None else attn.gqa_decode
        if ctx.get("legacy_decode") and cfg.mla is None:
            dec = attn.gqa_decode_legacy
        if mode == "decode":
            y, new_cache = dec(p["mixer"], cfg, hn, cache, pos,
                               transpose=transpose, backend=bk)
        elif mode == "prefill_chunk":
            # ``pos`` is the chunk's q_offset (traced scalar — one jit per
            # chunk width, not per chunk index); the chunk's K/V land in
            # the capacity cache at that offset
            chunk = (attn.mla_prefill_chunk if cfg.mla is not None
                     else attn.gqa_prefill_chunk)
            y, new_cache = chunk(p["mixer"], cfg, hn, cache, pos,
                                 transpose=transpose, backend=bk)
        else:
            y, new_cache = fwd(p["mixer"], cfg, hn, transpose=transpose,
                               causal=causal,
                               cache=cache if mode == "prefill" else None,
                               backend=bk)
    elif mixer_kind == "ssm":
        if mode == "decode":
            y, new_cache = ssm_lib.ssm_decode(p["mixer"], cfg, hn, cache, pos,
                                              transpose=transpose, backend=bk)
        else:
            y, new_cache = ssm_lib.ssm_forward(
                p["mixer"], cfg, hn, transpose=transpose,
                return_cache=(mode == "prefill"), backend=bk)
    elif mixer_kind == "cross_attn":
        if mode == "decode":
            kv = cache
            y = attn.cross_attn_forward(p["mixer"], cfg, hn, kv,
                                        transpose=transpose, backend=bk)
        else:
            kv = attn.cross_attn_memory(p["mixer"], cfg, ctx["memory"],
                                        backend=bk)
            y = attn.cross_attn_forward(p["mixer"], cfg, hn, kv,
                                        transpose=transpose, backend=bk)
            if mode == "prefill":
                new_cache = jax.tree.map(lambda b, n: n.astype(b.dtype),
                                         cache, kv)
    elif mixer_kind == "attn_cross":
        if mode == "decode":
            y, self_c = attn.gqa_decode(p["mixer"]["self"], cfg, hn,
                                        cache["self"], pos,
                                        transpose=transpose, backend=bk)
            h = h + y
            hn2 = apply_norm(p["norm_cross"], h, cfg.norm, cfg.norm_eps)
            y = attn.cross_attn_forward(p["mixer"]["cross"], cfg, hn2,
                                        cache["cross"], transpose=transpose,
                                        backend=bk)
            new_cache = {"self": self_c, "cross": cache["cross"]}
        else:
            y, self_c = attn.gqa_forward(
                p["mixer"]["self"], cfg, hn, transpose=transpose,
                causal=causal,
                cache=cache["self"] if mode == "prefill" else None,
                backend=bk)
            h = h + y
            hn2 = apply_norm(p["norm_cross"], h, cfg.norm, cfg.norm_eps)
            kv = attn.cross_attn_memory(p["mixer"]["cross"], cfg,
                                        ctx["memory"], backend=bk)
            y = attn.cross_attn_forward(p["mixer"]["cross"], cfg, hn2, kv,
                                        transpose=transpose, backend=bk)
            new_cache = ({"self": self_c,
                          "cross": jax.tree.map(
                              lambda b, n: n.astype(b.dtype),
                              cache["cross"], kv)}
                         if mode == "prefill" else None)
    else:
        raise ValueError(mixer_kind)
    h = h + _gather_for_prefill(y, mode, ctx)
    if ffn_kind != "none":
        hn = apply_norm(p["norm2"], h, cfg.norm, cfg.norm_eps)
        if ffn_kind == "moe":
            # every serving mode routes dropless (models/moe.py)
            y, moe_aux = moe_lib.apply_moe(p["ffn"], hn, cfg.moe,
                                           transpose=transpose, backend=bk,
                                           dropless=mode != "train")
            aux = moe_lib.accumulate_aux(aux, moe_aux)
        else:
            y = apply_mlp(p["ffn"], hn, act=cfg.mlp_act, transpose=transpose,
                          backend=bk)
        h = h + _gather_for_prefill(y, mode, ctx)
    if ctx.get("act_pspec") is not None:
        h = jax.lax.with_sharding_constraint(h, ctx["act_pspec"])
    return h, new_cache, aux


# =========================================================================
# groups and segments
# =========================================================================
def init_group(key, cfg: ModelConfig, spec: SegmentSpec):
    p, s = {}, {}
    ks = jax.random.split(key, spec.group_size)
    for i in range(spec.group_size):
        p[f"l{i}"], s[f"l{i}"] = init_layer(ks[i], cfg, spec.mixer_kinds[i],
                                            spec.ffn_kinds[i])
    return p, s


def group_block_fn(cfg: ModelConfig, spec: SegmentSpec, mode, pos, ctx):
    def block_fn(p_r, h, cache_t, aux, *, transpose, reuse_index):
        new_cache = {} if cache_t is not None else None
        for i in range(spec.group_size):
            c_i = cache_t[f"l{i}"] if cache_t is not None else None
            h, c_i, aux = apply_layer(
                p_r[f"l{i}"], cfg, h, c_i, aux,
                mixer_kind=spec.mixer_kinds[i], ffn_kind=spec.ffn_kinds[i],
                mode=mode, causal=spec.causal, pos=pos, ctx=ctx,
                transpose=transpose)
            if new_cache is not None:
                new_cache[f"l{i}"] = c_i
        return h, new_cache, aux
    return block_fn


def segment_specs(cfg: ModelConfig, spec: SegmentSpec):
    """Logical-axis spec tree for one segment, built without materializing
    params (spec strings are captured by closure under eval_shape)."""
    holder = {}

    def probe(k):
        p, s = init_group(k, cfg, spec)
        holder["s"] = s
        return jnp.zeros(())

    jax.eval_shape(probe, jax.random.PRNGKey(0))
    return jax.tree.map(lambda ax: ("layers",) + tuple(ax), holder["s"],
                        is_leaf=lambda x: isinstance(x, tuple))


def init_segment(key, cfg: ModelConfig, spec: SegmentSpec):
    shared = SharedStack.build(
        spec.num_groups, cfg.d_model, spec.reuse)
    params = stacked_init(lambda k: init_group(k, cfg, spec)[0], key,
                          shared.num_physical)
    return params, segment_specs(cfg, spec), shared


def run_segment(params, cfg: ModelConfig, spec: SegmentSpec,
                shared: SharedStack, h, cache, aux, *, mode, pos, ctx,
                remat=False):
    block = group_block_fn(cfg, spec, mode, pos, ctx)
    use_carry = mode == "decode" and not ctx.get("legacy_decode")
    return run_stack(block, params, h, shared, cache=cache, aux0=aux,
                     remat=remat, decode_pos=pos if use_carry else None,
                     backend=ctx.get("backend"))


# =========================================================================
# whole model
# =========================================================================
def model_segments(cfg: ModelConfig):
    return build_segments(cfg)


def init_model(key, cfg: ModelConfig):
    segs = build_segments(cfg)
    ks = jax.random.split(key, len(segs) + 5)
    params: dict[str, Any] = {}
    specs: dict[str, Any] = {}
    shareds: dict[str, SharedStack] = {}
    params["embed"], specs["embed"] = init_embedding(
        ks[0], cfg.padded_vocab, cfg.d_model)
    params["final_norm"], specs["final_norm"] = init_norm(cfg.d_model,
                                                          cfg.norm)
    if not cfg.tie_embeddings:
        params["lm_head"], specs["lm_head"] = init_unembed(
            ks[1], cfg.d_model, cfg.padded_vocab)
    if cfg.family == "vlm":
        params["vision_proj"], specs["vision_proj"] = init_linear(
            ks[2], cfg.vision.d_vision, cfg.d_model,
            axes=("vision_in", "embed"))
    if cfg.family == "audio":
        params["audio_proj"], specs["audio_proj"] = init_linear(
            ks[3], cfg.audio.d_audio, cfg.d_model,
            axes=("audio_in", "embed"))
        params["enc_final_norm"], specs["enc_final_norm"] = init_norm(
            cfg.d_model, cfg.norm)
    params["segments"], specs["segments"] = {}, {}
    for i, spec in enumerate(segs):
        p, s, sh = init_segment(ks[5 + i], cfg, spec)
        params["segments"][spec.name] = p
        specs["segments"][spec.name] = s
        shareds[spec.name] = sh
    return params, specs


def model_specs(cfg: ModelConfig):
    """Logical-axis spec tree for the whole model (no params materialized)."""
    holder = {}

    def probe(k):
        _, s = init_model(k, cfg)
        holder["s"] = s
        return jnp.zeros(())

    jax.eval_shape(probe, jax.random.PRNGKey(0))
    return holder["s"]


def abstract_params(cfg: ModelConfig):
    """ShapeDtypeStruct pytree of the params (for dry-run / lowering)."""
    return jax.eval_shape(lambda k: init_model(k, cfg)[0],
                          jax.random.PRNGKey(0))


@functools.lru_cache(maxsize=64)
def _shareds_for(cfg: ModelConfig):
    return {spec.name: SharedStack.build(spec.num_groups, cfg.d_model,
                                         spec.reuse)
            for spec in build_segments(cfg)}


def _encoder_pass(params, cfg, batch, ctx, aux):
    """Whisper encoder over stub frame embeddings -> memory (B, F, d)."""
    frames = batch["audio_embeds"].astype(ctx["dtype"])
    h = apply_linear(params["audio_proj"], frames, backend=ctx.get("backend"))
    spec = build_segments(cfg)[0]
    shared = _shareds_for(cfg)[spec.name]
    h, _, aux = run_segment(params["segments"][spec.name], cfg, spec, shared,
                            h, None, aux, mode="train", pos=None, ctx=ctx,
                            remat=ctx.get("remat", False))
    h = apply_norm(params["enc_final_norm"], h, cfg.norm, cfg.norm_eps)
    return h, aux


def forward(params, cfg: ModelConfig, batch, *, mode="train", caches=None,
            pos=None, act_pspec=None, remat=False, legacy_decode=False,
            execution=None, record_routing=False):
    """Run the model.

    batch: {"tokens": (B, S)} plus modality extras:
      vlm:   {"image_embeds": (B, M, d_vision)}
      audio: {"audio_embeds": (B, F, d_audio)}
    mode: train | prefill | prefill_chunk | decode (decode: S == 1 and
      ``pos`` is a scalar — aligned batch — or a (B,) int vector of per-slot
      positions for the continuous scheduler; legacy_decode supports scalar
      ``pos`` only.  prefill_chunk: tokens (B, C) is one query chunk of a
      longer prompt, ``pos`` is its q_offset (traced scalar), and ``caches``
      must hold the partially-filled capacity buffers — attention-only
      stacks; see models/attention.gqa_prefill_chunk).
    caches: pytree {segment: [R, T, {...}]} (prefill output / decode in-out).
    execution: overrides ``cfg.execution`` ("xla" | "photonic" | Backend);
      None uses the config's backend (core/backend.py).
    params may be raw fp weights (photonic: W8 derived in-step — the legacy
      shim path) or a ``Program.build`` bank whose matmul leaves are
      prepared ``core.prepared.PreparedTensor`` banks (write-once; the
      layers dispatch transparently).  New code should call this through
      :class:`repro.api.Program` rather than threading kwargs per call.
    record_routing: on an MoE model, aux is ``moe.route_record``'s dict
      instead of the load-balance scalar: its ``route`` holds every MoE
      layer's expert ids of every row.
    Returns (logits, new_caches, aux).
    """
    dtype = jnp.dtype(cfg.compute_dtype)
    backend = backend_lib.resolve(
        execution if execution is not None else cfg)
    ctx: dict[str, Any] = {"act_pspec": act_pspec, "dtype": dtype,
                           "remat": remat, "legacy_decode": legacy_decode,
                           "backend": backend}
    aux = jnp.float32(0.0)
    if record_routing and cfg.moe is not None:
        aux = moe_lib.route_record(cfg, batch["tokens"].size)
    segs = build_segments(cfg)
    shareds = _shareds_for(cfg)
    # ---- modality memory streams ----
    if cfg.family == "vlm":
        if mode == "decode":
            ctx["memory"] = None            # cross K/V lives in the cache
        else:
            img = batch["image_embeds"].astype(dtype)
            ctx["memory"] = apply_linear(params["vision_proj"], img,
                                         backend=backend)
    if cfg.family == "audio":
        if mode == "decode":
            ctx["memory"] = None
        else:
            ctx["memory"], aux = _encoder_pass(params, cfg, batch, ctx, aux)
    h = embed(params["embed"], batch["tokens"], dtype)
    if act_pspec is not None:
        h = jax.lax.with_sharding_constraint(h, act_pspec)
    new_caches = {} if caches is not None else None
    for spec in segs:
        if spec.stream == "encoder":
            continue                         # handled by _encoder_pass
        seg_cache = caches.get(spec.name) if caches is not None else None
        h, seg_cache, aux = run_segment(
            params["segments"][spec.name], cfg, spec, shareds[spec.name], h,
            seg_cache, aux, mode=mode, pos=pos, ctx=ctx, remat=remat)
        if new_caches is not None:
            new_caches[spec.name] = seg_cache
    h = apply_norm(params["final_norm"], h, cfg.norm, cfg.norm_eps)
    if cfg.tie_embeddings:
        # x @ table.T — the OBU-transpose orientation of the embedding
        # matrix, so the photonic backend's pre-swapped kernel serves it too
        logits = backend.dot(h, params["embed"]["table"].astype(h.dtype),
                             transpose=True)
    else:
        logits = unembed(params["lm_head"], h, backend=backend)
    return logits, new_caches, aux


# =========================================================================
# cache init
# =========================================================================
def _mixer_cache(cfg: ModelConfig, kind: str, batch: int, length: int,
                 mem_len: int, dtype):
    if kind == "attn":
        if cfg.mla is not None:
            return attn.init_mla_cache(cfg, batch, length, dtype)
        return attn.init_gqa_cache(cfg, batch, length, dtype)
    if kind == "ssm":
        return ssm_lib.init_ssm_cache(cfg, batch, dtype)
    if kind == "cross_attn":
        z = jnp.zeros((batch, mem_len, cfg.num_kv_heads, cfg.head_dim),
                      dtype)
        return {"ck": z, "cv": z}
    if kind == "attn_cross":
        z = jnp.zeros((batch, mem_len, cfg.num_kv_heads, cfg.head_dim),
                      dtype)
        return {"self": attn.init_gqa_cache(cfg, batch, length, dtype),
                "cross": {"ck": z, "cv": z}}
    raise ValueError(kind)


def init_caches(cfg: ModelConfig, batch: int, length: int,
                dtype=jnp.bfloat16):
    """Zero caches shaped [R, T, ...] per segment (decoder streams only)."""
    mem_len = 0
    if cfg.family == "vlm":
        mem_len = cfg.vision.num_image_tokens
    if cfg.family == "audio":
        mem_len = cfg.audio.num_frames
    caches = {}
    for spec in build_segments(cfg):
        if spec.stream == "encoder":
            continue
        shared = _shareds_for(cfg)[spec.name]
        R, T = shared.num_physical, shared.reuse_times

        def one_group():
            return {f"l{i}": _mixer_cache(cfg, spec.mixer_kinds[i], batch,
                                          length, mem_len, dtype)
                    for i in range(spec.group_size)}

        g = one_group()
        caches[spec.name] = jax.tree.map(
            lambda x: jnp.zeros((R, T) + x.shape, x.dtype), g)
    return caches
