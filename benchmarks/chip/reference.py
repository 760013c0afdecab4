"""What every family's plain reference shares: float32 ``jax.numpy``, every
matmul at ``Precision.HIGHEST``.

It imports nothing of the program.  Its weights are regenerated by name
from the run's seed (``weights.py``), the bf16 values the program is
served with, held in float32.  A family's reference (``families/``) runs
layer by layer (each layer's weights are drawn, used on every sequence and
dropped) and attends in blocks of ``Q_BLOCK`` query rows, so it fits on
the chip once the program is freed.  This module gives it the token
batch, the embedding ``h = E[tokens]``, the unembedding ``logits =
(rms(h) * s_f) Wlm``, the weight matmul and the norm.

``bits`` runs every weight matmul in a lower precision instead: int
``bits`` symmetric fake quantization of each activation row and each
weight output column (the control of the comparison).
"""
from __future__ import annotations

import functools

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


def _fake_quant(x, bits, axis):
    qmax = 2.0 ** (bits - 1) - 1
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.maximum(amax, 1e-30) / qmax
    return jnp.clip(jnp.round(x / scale), -qmax - 1, qmax) * scale


def mm(x, w, bits):
    """x @ w in float32 at HIGHEST, or in int ``bits`` fake quantization."""
    if bits:
        x = _fake_quant(x, bits, -1)
        w = _fake_quant(w, bits, 0)
    return jnp.matmul(x, w, precision=HIGHEST)


def rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def weight(base, name, shape, r):
    """Block ``r`` of the stacked layer weight ``name``, in float32."""
    return weights.leaf(base, f"{SEG}/{name}", shape, jnp.bfloat16,
                        r).astype(jnp.float32)


def pad_tokens(seqs, shape=None) -> np.ndarray:
    """``seqs`` (token id arrays) right-padded into one (n, S) batch, S a
    multiple of ``Q_BLOCK``.  ``shape`` (sequences, length) pads to one
    fixed shape, so every run of a cell reuses one compiled reference."""
    n, S = shape or (len(seqs), max(len(s) for s in seqs))
    if n < len(seqs) or S < max(len(s) for s in seqs):
        raise ValueError(f"{len(seqs)} sequences do not fit {shape}")
    S = -(-S // Q_BLOCK) * Q_BLOCK
    tokens = np.zeros((n, S), np.int32)
    for i, s in enumerate(seqs):
        tokens[i, :len(s)] = s               # right padding: causally unseen
    return tokens


def gather_rows(h, rows):
    """h[i, rows[i]] of every sequence i, concatenated."""
    idx = np.concatenate([np.full(len(p), i) for i, p in enumerate(rows)])
    pos = np.concatenate([np.asarray(p) for p in rows])
    return h[jnp.asarray(idx), jnp.asarray(pos)]


def _head_key(conf: dict) -> tuple:
    return tuple((k, conf[k]) for k in ("hidden_size", "norm_eps",
                                        "vocab_size"))


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
    x = rms(rows, s.astype(jnp.float32), conf["norm_eps"])
    return mm(x, w.astype(jnp.float32), bits)[:, :conf["vocab_size"]]


def embed(conf: dict, seed: int, tokens: np.ndarray):
    """float32 embeddings (n, S, hidden) of a padded token batch."""
    return _embed(jnp.asarray(tokens), weights.base_key(seed),
                  conf_key=_head_key(conf))


def unembed(conf: dict, seed: int, h, bits=None):
    """float32 logits (rows, vocab) of final hidden rows (before the last
    norm)."""
    return _logits(h, weights.base_key(seed), conf_key=_head_key(conf),
                   bits=bits)
