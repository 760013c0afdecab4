"""The llama family: a pre-norm decoder with MHA or GQA attention, full
RoPE, RMSNorm and a SwiGLU MLP (DeepSeek LLM 7B is one), with or without
the paper's PRM reuse plan.  A configuration file that states no
``model_type`` is of this family.

The program's mapping (``program_config``), the plain float32 reference
(``logits``) and the work count (``token_matmuls``, ``attention_call``);
the embedding, the final norm, the unembedding and the matmul helpers are
shared (``reference.py``).

Reference equations (as the configuration file states):
  h = E[tokens]
  per logical layer l (PRM: physical block l // T, reuse t = l % T):
    if transforms[t] shuffles: h = h[..., perm]  (channel-group shuffle)
    x = rms(h) * s1;  q, k, v = x Wq, x Wk, x Wv  (RoPE on q and k)
    h = h + causal_softmax(q k^T / sqrt(hd)) v Wo
    x = rms(h) * s2;  h = h + (silu(x Wg) * (x Wu)) Wd
    where transforms[t] transposes, every square attention matrix (Wq
    and Wo; Wk and Wv too under MHA) is used transposed and the MLP reads
    silu(x Wd^T) * (x Wu) then Wg^T
  logits = (rms(h) * s_f) Wlm
"""
from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp

import weights
import workcount
from reference import (HIGHEST, Q_BLOCK, embed, gather_rows,
                       group_shuffle_perm, mm, pad_tokens, rms, unembed,
                       weight)

# a configuration file's MLP activation (the published config.json's
# ``hidden_act``: a gated MLP whose gate takes it) -> the program's mlp_act
MLP_ACT = {"silu": "swiglu"}
# ModelConfig fields that make a model another family's
OTHER_FAMILIES = ("mla", "moe", "ssm", "vision", "audio")


# =========================================================================
# the program's ModelConfig
# =========================================================================
def program_config(conf: dict):
    """The program's ModelConfig for a configuration file.  The file is the
    truth: every size is taken from it, and a feature the program cannot run
    as the file states is an error, not a silent substitution."""
    from repro.configs import get_arch
    from repro.core.prm import ReuseConfig

    base = get_arch(conf["architecture"])
    other = [f for f in OTHER_FAMILIES if getattr(base, f) is not None]
    if other:
        raise ValueError(
            f"{conf['name']}: the program's {base.name} carries "
            f"{'/'.join(other)}, which the llama family neither maps nor "
            f"computes; state the published model_type in the file and "
            f"write benchmarks/chip/families/<model_type>.py")
    act = MLP_ACT.get(conf["hidden_act"])
    if base.mlp_act != act or base.norm != conf["norm"]:
        raise ValueError(f"{conf['name']}: the program's {base.name} runs "
                         f"{base.mlp_act}/{base.norm}, the file states "
                         f"{conf['hidden_act']}/{conf['norm']}")
    if conf.get("partial_rotary_factor", 1.0) != 1.0:
        raise ValueError("the program applies RoPE to the whole head")
    if conf.get("tie_word_embeddings"):
        raise ValueError("the program keeps its own unembedding")
    reuse = None
    if conf.get("reuse"):
        r = conf["reuse"]
        reuse = ReuseConfig(granularity="block", num_basic=r["num_basic"],
                            reuse_times=r["reuse_times"],
                            transforms=tuple(r["transforms"]),
                            shuffle_groups=r["shuffle_groups"],
                            shuffle_block=0)
    return dataclasses.replace(
        base, name=conf["name"], num_layers=conf["num_hidden_layers"],
        d_model=conf["hidden_size"], num_heads=conf["num_attention_heads"],
        num_kv_heads=conf["num_key_value_heads"], head_dim=conf["head_dim"],
        d_ff=conf["intermediate_size"], vocab_size=conf["vocab_size"],
        padded_vocab=0, rope_theta=float(conf["rope_theta"]),
        norm_eps=float(conf["norm_eps"]), compute_dtype=conf["dtype"],
        param_dtype=conf["dtype"], execution=conf["execution"], reuse=reuse)


# =========================================================================
# the plain reference
# =========================================================================
def plan(conf: dict):
    """[(physical block, transpose, shuffle)] per logical layer."""
    L = conf["num_hidden_layers"]
    r = conf.get("reuse")
    if not r:
        return [(i, False, False) for i in range(L)]
    T, tr = r["reuse_times"], r["transforms"]
    if r["num_basic"] * T != L:
        raise ValueError("num_basic x reuse_times != num_hidden_layers")
    out = []
    for i in range(L):
        name = tr[(i % T) % len(tr)]
        out.append((i // T, "transpose" in name, "shuffle" in name))
    return out


def _rope(x, theta):
    """x: (n, S, heads, hd); rotate-half RoPE at positions 0..S-1."""
    S, hd = x.shape[1], x.shape[-1]
    half = hd // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(q, k, v):
    """Causal GQA, q: (n, S, H, hd), k/v: (n, S, KV, hd), in blocks of
    ``Q_BLOCK`` query rows; softmax in float32."""
    n, S, H, hd = q.shape
    KV = k.shape[2]
    G = H // KV
    out = []
    for s0 in range(0, S, Q_BLOCK):
        qb = q[:, s0:s0 + Q_BLOCK].reshape(n, -1, KV, G, hd)
        rows = s0 + jnp.arange(qb.shape[1])
        s = jnp.einsum("nskgh,nlkh->nkgsl", qb, k,
                       precision=HIGHEST) / math.sqrt(hd)
        mask = rows[:, None] >= jnp.arange(S)[None, :]
        s = jnp.where(mask, s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("nkgsl,nlkh->nskgh", p, v, precision=HIGHEST)
        out.append(o.reshape(n, -1, H * hd))
    return jnp.concatenate(out, axis=1)


@functools.partial(jax.jit, static_argnames=("conf_key", "transpose",
                                             "bits"))
def _layer(h, base, r, *, conf_key, transpose, bits):
    conf = dict(conf_key)
    d, ff = conf["hidden_size"], conf["intermediate_size"]
    H, KV, hd = (conf["num_attention_heads"], conf["num_key_value_heads"],
                 conf["head_dim"])
    eps, theta = conf["norm_eps"], conf["rope_theta"]
    n, S, _ = h.shape
    wq = weight(base, "mixer/wq", (d, H * hd), r)
    wk = weight(base, "mixer/wk", (d, KV * hd), r)
    wv = weight(base, "mixer/wv", (d, KV * hd), r)
    wo = weight(base, "mixer/wo", (H * hd, d), r)
    if transpose:                          # only square matrices transpose
        wq, wk, wv, wo = (w.T if w.shape[0] == w.shape[1] else w
                          for w in (wq, wk, wv, wo))
    x = rms(h, weight(base, "norm1/scale", (d,), r), eps)
    q = _rope(mm(x, wq, bits).reshape(n, S, H, hd), theta)
    k = _rope(mm(x, wk, bits).reshape(n, S, KV, hd), theta)
    v = mm(x, wv, bits).reshape(n, S, KV, hd)
    h = h + mm(_attention(q, k, v), wo, bits)
    x = rms(h, weight(base, "norm2/scale", (d,), r), eps)
    wg = weight(base, "ffn/w_gate", (d, ff), r)
    wu = weight(base, "ffn/w_up", (d, ff), r)
    wd = weight(base, "ffn/w_down", (ff, d), r)
    if transpose:
        wg, wd = wd.T, wg.T
    g = jax.nn.silu(mm(x, wg, bits))
    return h + mm(g * mm(x, wu, bits), wd, bits)


def conf_key(conf: dict) -> tuple:
    keys = ("hidden_size", "intermediate_size", "num_attention_heads",
            "num_key_value_heads", "head_dim", "norm_eps", "rope_theta",
            "vocab_size")
    return tuple((k, conf[k]) for k in keys)


def hidden_rows(conf: dict, seed: int, seqs, rows, bits=None, shape=None):
    """Final hidden states (before the last norm) of ``seqs`` (token id
    arrays), at ``rows[i]`` (positions) of sequence i, concatenated.
    ``shape`` (sequences, length) pads the batch to one fixed shape, so
    every run of a cell reuses one compiled reference."""
    key = conf_key(conf)
    base = weights.base_key(seed)
    h = embed(conf, seed, pad_tokens(seqs, shape))
    perm = None
    if conf.get("reuse"):
        perm = jnp.asarray(group_shuffle_perm(
            conf["hidden_size"], conf["reuse"]["shuffle_groups"]))
    for r, transpose, shuffle in plan(conf):
        if shuffle:
            h = h[..., perm]
        h = _layer(h, base, jnp.int32(r), conf_key=key, transpose=transpose,
                   bits=bits)
    return gather_rows(h, rows)


def logits(conf: dict, seed: int, seqs, rows, bits=None, shape=None):
    """float32 logits (sum(len(rows)), vocab) of ``seqs`` at ``rows``;
    ``bits`` runs every weight matmul in that integer precision instead
    (the control)."""
    return unembed(conf, seed, hidden_rows(conf, seed, seqs, rows, bits, shape),
                   bits)


# =========================================================================
# the work count
# =========================================================================
def layer_matmuls(conf: dict) -> list[tuple[str, int, int]]:
    """(name, K, N) of each weight matmul of one logical layer."""
    d, ff = conf["hidden_size"], conf["intermediate_size"]
    q = conf["num_attention_heads"] * conf["head_dim"]
    kv = conf["num_key_value_heads"] * conf["head_dim"]
    return [("wq", d, q), ("wk", d, kv), ("wv", d, kv), ("wo", q, d),
            ("w_gate", d, ff), ("w_up", d, ff), ("w_down", ff, d)]


def token_matmuls(conf: dict) -> list[tuple[int, int]]:
    """(K, N) of every weight matmul one token passes through, in order:
    every logical layer's, then the unembedding."""
    one = [(k, n) for _, k, n in layer_matmuls(conf)]
    return one * workcount.logical_layers(conf) + [workcount.unembedding(conf)]


def attention_call(q_len: int, q_offset: int, conf: dict, peaks):
    """One layer's causal attention of q_len queries at q_offset: 4 * H *
    head_dim FLOPs per kept pair (QK^T and PV); bytes: q and o of the
    queries, k and v of the keys up to the last query."""
    H, KV = conf["num_attention_heads"], conf["num_key_value_heads"]
    hd = conf["head_dim"]
    flops = 4.0 * H * hd * workcount.causal_pairs(q_len, q_offset)
    nbytes = workcount.ACT_BYTES * hd * (2 * q_len * H
                                         + 2 * (q_offset + q_len) * KV)
    return workcount.Work(bf16_flops=flops, bytes=nbytes,
                          min_seconds=max(flops / peaks.bf16_flops,
                                          nbytes / peaks.hbm_bytes))
