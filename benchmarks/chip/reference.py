"""The plain reference: the model of a configuration file in float32
``jax.numpy``, every matmul at ``Precision.HIGHEST``.

It imports nothing of the program.  Its weights are regenerated by name
from the run's seed (``weights.py``), the bf16 values the program is
served with, held in float32.  It runs layer by layer (each layer's
weights are drawn, used on every sequence and dropped) and attends in
blocks of query rows, so it fits on the chip once the program is freed.

Equations (pre-norm decoder, as the configuration file states):
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

``bits`` runs every weight matmul in a lower precision instead: int
``bits`` symmetric fake quantization of each activation row and each
weight output column (the control of the comparison).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

import weights

HIGHEST = jax.lax.Precision.HIGHEST
Q_BLOCK = 512           # query rows per attention block
SEG = "segments/main/l0"


def group_shuffle_perm(channels: int, groups: int) -> np.ndarray:
    """y[i] = x[perm[i]]: reshape (groups, C / groups), swap, flatten."""
    return np.arange(channels).reshape(groups, -1).T.reshape(-1)


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


def _fake_quant(x, bits, axis):
    qmax = 2.0 ** (bits - 1) - 1
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.maximum(amax, 1e-30) / qmax
    return jnp.clip(jnp.round(x / scale), -qmax - 1, qmax) * scale


def _mm(x, w, bits):
    if bits:
        x = _fake_quant(x, bits, -1)
        w = _fake_quant(w, bits, 0)
    return jnp.matmul(x, w, precision=HIGHEST)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


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


def _w(base, conf, name, shape, r):
    return weights.leaf(base, f"{SEG}/{name}", shape, jnp.bfloat16,
                        r).astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("conf_key", "transpose",
                                             "bits"))
def _layer(h, base, r, *, conf_key, transpose, bits):
    conf = dict(conf_key)
    d, ff = conf["hidden_size"], conf["intermediate_size"]
    H, KV, hd = (conf["num_attention_heads"], conf["num_key_value_heads"],
                 conf["head_dim"])
    eps, theta = conf["norm_eps"], conf["rope_theta"]
    n, S, _ = h.shape
    wq = _w(base, conf, "mixer/wq", (d, H * hd), r)
    wk = _w(base, conf, "mixer/wk", (d, KV * hd), r)
    wv = _w(base, conf, "mixer/wv", (d, KV * hd), r)
    wo = _w(base, conf, "mixer/wo", (H * hd, d), r)
    if transpose:                          # only square matrices transpose
        wq, wk, wv, wo = (w.T if w.shape[0] == w.shape[1] else w
                          for w in (wq, wk, wv, wo))
    x = _rms(h, _w(base, conf, "norm1/scale", (d,), r), eps)
    q = _rope(_mm(x, wq, bits).reshape(n, S, H, hd), theta)
    k = _rope(_mm(x, wk, bits).reshape(n, S, KV, hd), theta)
    v = _mm(x, wv, bits).reshape(n, S, KV, hd)
    h = h + _mm(_attention(q, k, v), wo, bits)
    x = _rms(h, _w(base, conf, "norm2/scale", (d,), r), eps)
    wg = _w(base, conf, "ffn/w_gate", (d, ff), r)
    wu = _w(base, conf, "ffn/w_up", (d, ff), r)
    wd = _w(base, conf, "ffn/w_down", (ff, d), r)
    if transpose:
        wg, wd = wd.T, wg.T
    g = jax.nn.silu(_mm(x, wg, bits))
    return h + _mm(g * _mm(x, wu, bits), wd, bits)


@functools.partial(jax.jit, static_argnames=("conf_key",))
def _embed(tokens, base, *, conf_key):
    conf = dict(conf_key)
    vp = -(-conf["vocab_size"] // 256) * 256
    table = weights.leaf(base, "embed/table", (vp, conf["hidden_size"]),
                         jnp.bfloat16)
    return table[tokens].astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("conf_key", "bits"))
def _logits(rows, base, *, conf_key, bits):
    conf = dict(conf_key)
    d = conf["hidden_size"]
    vp = -(-conf["vocab_size"] // 256) * 256
    s = weights.leaf(base, "final_norm/scale", (d,), jnp.bfloat16)
    w = weights.leaf(base, "lm_head/w", (d, vp), jnp.bfloat16)
    x = _rms(rows, s.astype(jnp.float32), conf["norm_eps"])
    return _mm(x, w.astype(jnp.float32), bits)[:, :conf["vocab_size"]]


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
    n, S = shape or (len(seqs), max(len(s) for s in seqs))
    if n < len(seqs) or S < max(len(s) for s in seqs):
        raise ValueError(f"{len(seqs)} sequences do not fit {shape}")
    S = -(-S // Q_BLOCK) * Q_BLOCK
    tokens = np.zeros((n, S), np.int32)
    for i, s in enumerate(seqs):
        tokens[i, :len(s)] = s               # right padding: causally unseen
    h = _embed(jnp.asarray(tokens), base, conf_key=key)
    perm = None
    if conf.get("reuse"):
        perm = jnp.asarray(group_shuffle_perm(
            conf["hidden_size"], conf["reuse"]["shuffle_groups"]))
    for r, transpose, shuffle in plan(conf):
        if shuffle:
            h = h[..., perm]
        h = _layer(h, base, jnp.int32(r), conf_key=key, transpose=transpose,
                   bits=bits)
    idx = np.concatenate([np.full(len(p), i) for i, p in enumerate(rows)])
    pos = np.concatenate([np.asarray(p) for p in rows])
    return h[jnp.asarray(idx), jnp.asarray(pos)]


def logits(conf: dict, seed: int, seqs, rows, bits=None, shape=None):
    """float32 logits (sum(len(rows)), vocab) of ``seqs`` at ``rows``."""
    h = hidden_rows(conf, seed, seqs, rows, bits, shape)
    return _logits(h, weights.base_key(seed), conf_key=conf_key(conf),
                   bits=bits)
