"""The deepseek_v2 family: DeepSeek-V2's decoder, multi-head latent
attention (MLA) without a query LoRA, YaRN RoPE, and a first dense SwiGLU
layer followed by MoE layers of routed and shared SwiGLU experts
(DeepSeek-V2-Lite is one).

The program's mapping (``program_config``), the plain float32 reference
(``logits``) and the work count (``token_matmuls``, ``attention_call``);
the embedding, the final norm, the unembedding and the matmul helpers are
shared (``reference.py``).

Reference equations (as the configuration file states; H heads, nope 128,
rope 64, v 128, latent r 512):
  h = E[tokens]
  per layer:
    x = rms(h) * s1
    q = x Wq  (per head [q_nope | q_rope]);  [c | k_rope] = x Wdkv
    c = rms(c) * s_kv                         (the latent norm)
    [k_nope | v] = c Wukv                     (per head)
    q_rope, k_rope = yarn_rope(q_rope), yarn_rope(k_rope)  (k_rope shared
      by every head; rotate-half over the stored columns)
    h = h + causal_softmax([q_nope|q_rope] [k_nope|k_rope]^T
                           * mscale(factor, mscale_all_dim)^2
                           / sqrt(nope + rope)) v Wo
    x = rms(h) * s2
    first_k_dense_replace layers: h = h + (silu(x Wg) * (x Wu)) Wd
    then: p = softmax(x Wr) over the routed experts; the top
      num_experts_per_tok by p, each weighted by its raw p (renormalised
      only under norm_topk_prob); h = h + sum_top p_e SwiGLU_e(x)
      + SwiGLU_shared(x), no capacity: no token is dropped
  logits = (rms(h) * s_f) Wlm
The reference computes attention in the expanded form above; the program
decodes it absorbed, in the latent space.
"""
from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

import weights
import workcount
from reference import (HIGHEST, Q_BLOCK, embed, gather_rows, mm, pad_tokens,
                       rms, unembed)

# FFN rows per block of the reference: the FFN runs over the real tokens
# alone, gathered out of the padded batch, in blocks of this many rows
T_BLOCK = 2048


# =========================================================================
# the program's ModelConfig
# =========================================================================
def program_config(conf: dict):
    """The program's ModelConfig for a configuration file.  Every size is
    taken from the file; a feature the program cannot run as the file
    states is an error, not a silent substitution."""
    from repro.configs import MLAConfig, get_arch
    from repro.configs.base import RopeScaling

    base = get_arch(conf["architecture"])
    if base.mla is None or base.moe is None:
        raise ValueError(f"{conf['name']}: the program's {base.name} has no "
                         f"MLA or no experts")
    refused = {
        "q_lora_rank": conf["q_lora_rank"] is not None,
        "scoring_func": conf["scoring_func"] != "softmax",
        "topk_method": conf["topk_method"] != "greedy",
        "n_group": conf["n_group"] > 1,
        "routed_scaling_factor": conf["routed_scaling_factor"] != 1,
        "tie_word_embeddings": bool(conf["tie_word_embeddings"]),
        "hidden_act": conf["hidden_act"] != "silu",
        "attention_bias": bool(conf["attention_bias"]),
        "moe_layer_freq": conf["moe_layer_freq"] != 1,
        "num_key_value_heads": (conf["num_key_value_heads"]
                                != conf["num_attention_heads"]),
    }
    bad = [k for k, v in refused.items() if v]
    if bad:
        raise ValueError(f"{conf['name']}: the program cannot run "
                         f"{', '.join(f'{k}={conf[k]!r}' for k in bad)} as "
                         f"the file states")
    rs = conf["rope_scaling"]
    scaling = None
    if rs is not None:
        if rs["type"] != "yarn":
            raise ValueError(f"{conf['name']}: rope_scaling type "
                             f"{rs['type']!r}; the program runs yarn only")
        scaling = RopeScaling(
            factor=float(rs["factor"]),
            original_max_position=int(rs["original_max_position_embeddings"]),
            beta_fast=float(rs["beta_fast"]), beta_slow=float(rs["beta_slow"]),
            mscale=float(rs["mscale"]),
            mscale_all_dim=float(rs["mscale_all_dim"]))
    f = conf["moe_intermediate_size"]
    moe = dataclasses.replace(
        base.moe, num_experts=conf["n_routed_experts"],
        top_k=conf["num_experts_per_tok"], d_ff_expert=f,
        num_shared=conf["n_shared_experts"],
        d_ff_shared=conf["n_shared_experts"] * f,
        first_dense=conf["first_k_dense_replace"],
        first_dense_d_ff=conf["intermediate_size"],
        norm_topk_prob=bool(conf["norm_topk_prob"]), moe_every=1,
        moe_offset=0, num_basic_experts=0)
    mla = MLAConfig(kv_lora_rank=conf["kv_lora_rank"],
                    qk_nope_dim=conf["qk_nope_head_dim"],
                    qk_rope_dim=conf["qk_rope_head_dim"],
                    v_head_dim=conf["v_head_dim"])
    return dataclasses.replace(
        base, name=conf["name"], num_layers=conf["num_hidden_layers"],
        d_model=conf["hidden_size"], num_heads=conf["num_attention_heads"],
        num_kv_heads=conf["num_key_value_heads"],
        head_dim=conf["qk_nope_head_dim"] + conf["qk_rope_head_dim"],
        d_ff=f, vocab_size=conf["vocab_size"], padded_vocab=0,
        rope_theta=float(conf["rope_theta"]), rope_scaling=scaling,
        norm_eps=float(conf["rms_norm_eps"]), mla=mla, moe=moe,
        compute_dtype=conf["dtype"], param_dtype=conf["dtype"],
        execution=conf["execution"], reuse=None)


# =========================================================================
# the plain reference
# =========================================================================
def _segments(conf: dict):
    """[(segment, block, dense)] per layer, as the program names them."""
    k = conf["first_k_dense_replace"]
    return ([("pre", i, True) for i in range(k)]
            + [("main", i, False)
               for i in range(conf["num_hidden_layers"] - k)])


def _w(base, seg, name, shape, r):
    return weights.leaf(base, f"segments/{seg}/l0/{name}", shape,
                        jnp.bfloat16, r).astype(jnp.float32)


def mscale(scale: float, m: float) -> float:
    return 1.0 if scale <= 1 else 0.1 * m * math.log(scale) + 1.0


def inv_freq(conf: dict) -> np.ndarray:
    """Rope frequencies of the qk_rope_head_dim dims: YaRN's blend of
    extrapolation (theta^(-2i/d)) and interpolation (that over ``factor``)
    over the linear ramp between the dims that turn beta_fast and
    beta_slow times in the original context."""
    d, theta = conf["qk_rope_head_dim"], float(conf["rope_theta"])
    extra = theta ** (-np.arange(0, d, 2, dtype=np.float64) / d)
    rs = conf["rope_scaling"]
    if rs is None:
        return extra.astype(np.float32)
    orig = rs["original_max_position_embeddings"]

    def dim(turns):
        return (d * math.log(orig / (turns * 2 * math.pi))
                / (2 * math.log(theta)))
    lo = max(math.floor(dim(rs["beta_fast"])), 0)
    hi = min(math.ceil(dim(rs["beta_slow"])), d - 1)
    ramp = np.clip((np.arange(d // 2) - lo) / max(hi - lo, 1e-3), 0.0, 1.0)
    return (extra / rs["factor"] * ramp + extra * (1.0 - ramp)).astype(
        np.float32)


def softmax_scale(conf: dict) -> float:
    s = (conf["qk_nope_head_dim"] + conf["qk_rope_head_dim"]) ** -0.5
    rs = conf["rope_scaling"]
    if rs is not None and rs["mscale_all_dim"]:
        s *= mscale(rs["factor"], rs["mscale_all_dim"]) ** 2
    return s


def _rope(x, cos, sin):
    """x (S, heads, d) rotated in halves; cos/sin (S, d/2)."""
    half = x.shape[-1] // 2
    cos, sin = cos[:, None, :], sin[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@functools.partial(jax.jit, static_argnames=("conf_key", "seg", "bits"))
def _attention_layer(h, base, r, *, conf_key, seg, bits):
    """h + MLA(rms(h)) for every sequence of h (n, S, d), one at a time."""
    conf = _key_dict(conf_key)
    d, H = conf["hidden_size"], conf["num_attention_heads"]
    nope, rope = conf["qk_nope_head_dim"], conf["qk_rope_head_dim"]
    vd, lora = conf["v_head_dim"], conf["kv_lora_rank"]
    eps = conf["rms_norm_eps"]
    S = h.shape[1]
    s1 = _w(base, seg, "norm1/scale", (d,), r)
    wq = _w(base, seg, "mixer/wq", (d, H * (nope + rope)), r)
    wdkv = _w(base, seg, "mixer/w_dkv", (d, lora + rope), r)
    skv = _w(base, seg, "mixer/kv_norm/scale", (lora,), r)
    wukv = _w(base, seg, "mixer/w_ukv", (lora, H * (nope + vd)), r)
    wo = _w(base, seg, "mixer/wo", (H * vd, d), r)
    freqs = jnp.asarray(inv_freq(conf))
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * freqs
    rs = conf["rope_scaling"]
    m = 1.0 if rs is None else (mscale(rs["factor"], rs["mscale"])
                                / mscale(rs["factor"], rs["mscale_all_dim"]))
    cos, sin = jnp.cos(ang) * m, jnp.sin(ang) * m
    scale = softmax_scale(conf)

    def one(hs):                                  # (S, d)
        x = rms(hs, s1, eps)
        q = mm(x, wq, bits).reshape(S, H, nope + rope)
        ckr = mm(x, wdkv, bits)
        c = rms(ckr[:, :lora], skv, eps)
        kv = mm(c, wukv, bits).reshape(S, H, nope + vd)
        kr = _rope(ckr[:, None, lora:], cos, sin)
        q = jnp.concatenate([q[..., :nope], _rope(q[..., nope:], cos, sin)],
                            -1)
        k = jnp.concatenate([kv[..., :nope],
                             jnp.broadcast_to(kr, (S, H, rope))], -1)
        v = kv[..., nope:]
        out = []
        for s0 in range(0, S, Q_BLOCK):           # keys up to the block's end
            e = min(s0 + Q_BLOCK, S)
            s = jnp.einsum("qhd,khd->hqk", q[s0:e], k[:e],
                           precision=HIGHEST) * scale
            mask = (s0 + jnp.arange(e - s0))[:, None] >= jnp.arange(e)
            p = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
            out.append(jnp.einsum("hqk,khd->qhd", p, v[:e],
                                  precision=HIGHEST).reshape(e - s0, H * vd))
        return hs + mm(jnp.concatenate(out), wo, bits)

    return jax.lax.map(one, h)


def _swiglu(x, wg, wu, wd, bits):
    return mm(jax.nn.silu(mm(x, wg, bits)) * mm(x, wu, bits), wd, bits)


@functools.partial(jax.jit, static_argnames=("conf_key", "seg", "bits"))
def _dense_ffn(hb, base, r, *, conf_key, seg, bits):
    """hb + SwiGLU(rms(hb)) of a block of rows (T_BLOCK, d)."""
    conf = dict(conf_key)
    d, ff = conf["hidden_size"], conf["intermediate_size"]
    x = rms(hb, _w(base, seg, "norm2/scale", (d,), r), conf["rms_norm_eps"])
    return hb + _swiglu(x, _w(base, seg, "ffn/w_gate", (d, ff), r),
                        _w(base, seg, "ffn/w_up", (d, ff), r),
                        _w(base, seg, "ffn/w_down", (ff, d), r), bits)


@functools.partial(jax.jit, static_argnames=("conf_key", "seg"))
def _expert_banks(base, r, *, conf_key, seg):
    """Every routed expert's (E, ...) gate, up and down weights (bf16)."""
    conf = dict(conf_key)
    d, f, E = (conf["hidden_size"], conf["moe_intermediate_size"],
               conf["n_routed_experts"])
    return tuple(weights.leaf(base, f"segments/{seg}/l0/ffn/{n}", s,
                              jnp.bfloat16, r)
                 for n, s in (("w_gate", (E, d, f)), ("w_up", (E, d, f)),
                              ("w_down", (E, f, d))))


@functools.partial(jax.jit, static_argnames=("conf_key", "seg", "bits"))
def _moe_ffn(hb, banks, base, r, *, conf_key, seg, bits):
    """hb + MoE(rms(hb)) of a block of rows (T_BLOCK, d): the router in
    float32 (the program's precision for it, control included), the top-k
    of its softmax with their raw probabilities as gates, every routed
    expert on every row weighted by its gate (zero off the top-k), and the
    shared experts."""
    conf = dict(conf_key)
    d, E, K = (conf["hidden_size"], conf["n_routed_experts"],
               conf["num_experts_per_tok"])
    fs = conf["n_shared_experts"] * conf["moe_intermediate_size"]
    x = rms(hb, _w(base, seg, "norm2/scale", (d,), r), conf["rms_norm_eps"])
    p = jax.nn.softmax(mm(x, _w(base, seg, "ffn/router", (d, E), r), None),
                       axis=-1)
    top, idx = jax.lax.top_k(p, K)
    if conf["norm_topk_prob"]:
        top = top / jnp.sum(top, axis=-1, keepdims=True)
    gates = jnp.zeros_like(p).at[jnp.arange(p.shape[0])[:, None],
                                 idx].set(top)
    y = _swiglu(x, _w(base, seg, "ffn/shared/w_gate", (d, fs), r),
                _w(base, seg, "ffn/shared/w_up", (d, fs), r),
                _w(base, seg, "ffn/shared/w_down", (fs, d), r), bits)
    wg, wu, wd = banks

    def expert(e, y):
        f32 = [w[e].astype(jnp.float32) for w in (wg, wu, wd)]
        return y + gates[:, e, None] * _swiglu(x, *f32, bits)

    return hb + jax.lax.fori_loop(0, E, expert, y)


CONF_KEYS = ("hidden_size", "num_attention_heads", "qk_nope_head_dim",
             "qk_rope_head_dim", "v_head_dim", "kv_lora_rank",
             "intermediate_size", "moe_intermediate_size", "n_routed_experts",
             "n_shared_experts", "num_experts_per_tok", "norm_topk_prob",
             "rms_norm_eps", "rope_theta", "first_k_dense_replace")


def conf_key(conf: dict) -> tuple:
    rs = conf["rope_scaling"]
    return tuple((k, conf[k]) for k in CONF_KEYS) + (
        ("rope_scaling", None if rs is None else tuple(sorted(rs.items()))),)


def _key_dict(key: tuple) -> dict:
    conf = dict(key)
    if conf["rope_scaling"] is not None:
        conf["rope_scaling"] = dict(conf["rope_scaling"])
    return conf


def head_conf(conf: dict) -> dict:
    """What ``reference.py``'s embedding and unembedding read."""
    return {"hidden_size": conf["hidden_size"],
            "norm_eps": conf["rms_norm_eps"],
            "vocab_size": conf["vocab_size"]}


def hidden_rows(conf: dict, seed: int, seqs, rows, bits=None, shape=None):
    """Final hidden states (before the last norm) of ``seqs`` (token id
    arrays), at ``rows[i]`` (positions) of sequence i, concatenated.
    ``shape`` (sequences, length) pads the batch to one fixed shape, so
    every run of a cell reuses one compiled reference.  The FFNs run over
    the real tokens alone (right padding is causally unseen)."""
    key = conf_key(conf)
    base = weights.base_key(seed)
    tokens = pad_tokens(seqs, shape)
    n, S = tokens.shape
    h = embed(head_conf(conf), seed, tokens)
    d = h.shape[-1]
    real = np.concatenate([i * S + np.arange(len(s))
                           for i, s in enumerate(seqs)])
    blocks = -(-len(real) // T_BLOCK)
    take = jnp.asarray(np.resize(real, blocks * T_BLOCK))
    for seg, r, dense in _segments(conf):
        r = jnp.int32(r)
        h = _attention_layer(h, base, r, conf_key=key, seg=seg, bits=bits)
        flat = h.reshape(n * S, d)
        x = flat[take].reshape(blocks, T_BLOCK, d)
        if dense:
            out = [_dense_ffn(x[b], base, r, conf_key=key, seg=seg,
                              bits=bits) for b in range(blocks)]
        else:
            banks = _expert_banks(base, r, conf_key=key, seg=seg)
            out = [_moe_ffn(x[b], banks, base, r, conf_key=key, seg=seg,
                            bits=bits) for b in range(blocks)]
            del banks
        out = jnp.concatenate(out)[:len(real)]
        h = flat.at[jnp.asarray(real)].set(out).reshape(n, S, d)
    return gather_rows(h, rows)


def logits(conf: dict, seed: int, seqs, rows, bits=None, shape=None):
    """float32 logits (sum(len(rows)), vocab) of ``seqs`` at ``rows``;
    ``bits`` runs every weight matmul but the router's in that integer
    precision instead (the control; the program runs the router in
    float32)."""
    return unembed(head_conf(conf), seed,
                   hidden_rows(conf, seed, seqs, rows, bits, shape), bits)


# =========================================================================
# the work count
# =========================================================================
def layer_matmuls(conf: dict, dense: bool) -> list[tuple[str, int, int]]:
    """(name, K, N) of each W8A8 matmul one token passes through in one
    layer: the attention projections (``w_ukv`` is absorbed in bf16 and
    counted with the attention), then the dense FFN, or the
    ``num_experts_per_tok`` routed experts it is sent to and the shared
    experts (the router runs in float32 and is not counted)."""
    d, H = conf["hidden_size"], conf["num_attention_heads"]
    qd = conf["qk_nope_head_dim"] + conf["qk_rope_head_dim"]
    att = [("wq", d, H * qd),
           ("w_dkv", d, conf["kv_lora_rank"] + conf["qk_rope_head_dim"]),
           ("wo", H * conf["v_head_dim"], d)]
    if dense:
        ff = conf["intermediate_size"]
        return att + [("w_gate", d, ff), ("w_up", d, ff), ("w_down", ff, d)]
    f = conf["moe_intermediate_size"]
    fs = conf["n_shared_experts"] * f
    routed = [("expert/w_gate", d, f), ("expert/w_up", d, f),
              ("expert/w_down", f, d)] * conf["num_experts_per_tok"]
    return att + routed + [("shared/w_gate", d, fs), ("shared/w_up", d, fs),
                           ("shared/w_down", fs, d)]


def token_matmuls(conf: dict) -> list[tuple[int, int]]:
    """(K, N) of every W8A8 matmul one token passes through, in order:
    every layer's, then the unembedding."""
    out = []
    for _, _, dense in _segments(conf):
        out += [(k, n) for _, k, n in layer_matmuls(conf, dense)]
    return out + [workcount.unembedding(conf)]


def attention_call(q_len: int, q_offset: int, conf: dict, peaks):
    """One layer's causal attention of q_len queries at q_offset in the
    latent space decode runs in.  FLOPs: per kept (query, key) pair 2H(r
    + rope) for the scores against the latent and the rope key and 2Hr for
    the context in the latent; per query 2Hr(nope + v) for absorbing
    ``w_ukv`` into the query and out of the context.  Bytes: every key
    position read once as r + rope bf16 values; the queries' latent
    queries (H(r + rope)) and contexts (Hv) in bf16.  ``w_ukv`` itself is
    read once a step for all its rows and is left out."""
    H, r = conf["num_attention_heads"], conf["kv_lora_rank"]
    rope, nope = conf["qk_rope_head_dim"], conf["qk_nope_head_dim"]
    vd = conf["v_head_dim"]
    pairs = workcount.causal_pairs(q_len, q_offset)
    flops = (2.0 * H * (2 * r + rope) * pairs
             + 2.0 * H * r * (nope + vd) * q_len)
    nbytes = workcount.ACT_BYTES * ((q_offset + q_len) * (r + rope)
                                    + q_len * H * (r + rope + vd))
    return workcount.Work(bf16_flops=flops, bytes=nbytes,
                          min_seconds=max(flops / peaks.bf16_flops,
                                          nbytes / peaks.hbm_bytes))
