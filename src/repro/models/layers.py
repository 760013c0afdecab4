"""Primitive layers: norms, RoPE, MLPs, embeddings.

Every init function returns ``(params, specs)`` where ``specs`` mirrors the
param pytree with tuples of *logical axis names* per dimension; the sharding
layer (repro.sharding.partition) maps logical names onto mesh axes.

Weight matmuls route through the execution backend (``core/backend.py``):
"xla" lowers to ``obu.blend_dot`` dot_generals (the OBU "optical transpose"
is a dimension swap, never a materialized transpose); "photonic" routes the
same calls through the Pallas W8A8 kernels.

A matmul weight may arrive as a raw fp array or as a *prepared bank*
(``core.prepared.PreparedTensor`` — ``Program.build``'s write-once int8
image).  The layers are agnostic: ``PreparedTensor.astype`` is a no-op (a
programmed bank has no dtype; readout gain casts) and ``Backend.dot``
dispatches on the leaf type, so the same layer code serves both.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.backend import resolve as resolve_backend


def _dense_init(key, shape, scale=None):
    scale = scale if scale is not None else (1.0 / jnp.sqrt(shape[0]))
    return jax.random.normal(key, shape, dtype=jnp.float32) * scale


# ----------------------------------------------------------------- norms
def init_norm(d: int, kind: str = "rms"):
    if kind == "rms":
        return {"scale": jnp.ones((d,))}, {"scale": ("embed",)}
    return ({"scale": jnp.ones((d,)), "bias": jnp.zeros((d,))},
            {"scale": ("embed",), "bias": ("embed",)})


def apply_norm(p, x, kind: str = "rms", eps: float = 1e-5):
    xf = x.astype(jnp.float32)
    if kind == "rms":
        var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
        y = xf * jax.lax.rsqrt(var + eps) * p["scale"]
    else:
        mean = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.var(xf, axis=-1, keepdims=True)
        y = (xf - mean) * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]
    return y.astype(x.dtype)


# ------------------------------------------------------------------ RoPE
def yarn_mscale(scale: float, mscale: float) -> float:
    """YaRN's attention temperature factor ``0.1 * m * ln(s) + 1``."""
    if scale <= 1.0:
        return 1.0
    return 0.1 * mscale * math.log(scale) + 1.0


def yarn_inv_freq(head_dim: int, theta: float, rs) -> np.ndarray:
    """YaRN frequencies (``rs``: ``configs.base.RopeScaling``): dims whose
    wavelength fits ``beta_fast`` rotations in the original context keep
    the plain frequency, those under ``beta_slow`` rotations take it
    divided by ``factor``, and a linear ramp blends the dims between."""
    half = head_dim // 2
    extra = 1.0 / (theta ** (np.arange(half, dtype=np.float64) / half))
    inter = extra / rs.factor

    def dim_of(rotations):
        return (head_dim * math.log(rs.original_max_position
                                    / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))
    lo = max(math.floor(dim_of(rs.beta_fast)), 0)
    hi = min(math.ceil(dim_of(rs.beta_slow)), head_dim - 1)
    if lo == hi:
        hi += 0.001
    ramp = np.clip((np.arange(half) - lo) / (hi - lo), 0.0, 1.0)
    return (inter * ramp + extra * (1.0 - ramp)).astype(np.float32)


def rope_angles(positions: jax.Array, head_dim: int, theta: float,
                scaling=None):
    """cos/sin tables for ``positions`` (any shape) -> (..., head_dim/2);
    ``scaling`` (a ``RopeScaling``) applies YaRN's frequencies and its
    cos/sin factor ``mscale(factor, mscale) / mscale(factor,
    mscale_all_dim)``."""
    half = head_dim // 2
    if scaling is None:
        freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32)
                                 / half))
    else:
        freqs = jnp.asarray(yarn_inv_freq(head_dim, theta, scaling))
    ang = positions.astype(jnp.float32)[..., None] * freqs
    if scaling is None:
        return jnp.cos(ang), jnp.sin(ang)
    m = (yarn_mscale(scaling.factor, scaling.mscale)
         / yarn_mscale(scaling.factor, scaling.mscale_all_dim))
    return jnp.cos(ang) * m, jnp.sin(ang) * m


def apply_rope(x: jax.Array, cos: jax.Array, sin: jax.Array):
    """x: (B, S, H, hd); cos/sin: (B, S, hd/2) or (S, hd/2)."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    if cos.ndim == 2:  # (S, half) -> broadcast over batch and heads
        cos = cos[None, :, None, :]
        sin = sin[None, :, None, :]
    else:              # (B, S, half)
        cos = cos[:, :, None, :]
        sin = sin[:, :, None, :]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1).astype(x.dtype)


# ------------------------------------------------------------------- MLP
def init_mlp(key, d_model: int, d_ff: int, act: str = "swiglu"):
    ks = jax.random.split(key, 3)
    if act == "swiglu":
        p = {"w_gate": _dense_init(ks[0], (d_model, d_ff)),
             "w_up": _dense_init(ks[1], (d_model, d_ff)),
             "w_down": _dense_init(ks[2], (d_ff, d_model))}
        s = {"w_gate": ("embed", "mlp"), "w_up": ("embed", "mlp"),
             "w_down": ("mlp", "embed")}
    else:
        p = {"w_up": _dense_init(ks[0], (d_model, d_ff)),
             "w_down": _dense_init(ks[1], (d_ff, d_model))}
        s = {"w_up": ("embed", "mlp"), "w_down": ("mlp", "embed")}
    return p, s


def apply_mlp(p, x, act: str = "swiglu", transpose: bool = False,
              backend=None):
    """FFN with OBU-transpose support.

    The transposed reuse swaps the role of the up- and down-projections
    (``W_down.T`` is a valid (d, ff) up-proj and vice versa) — the whole
    block's weight set is served by the same physical storage, matching the
    crossbar's vertical-input path.  For SwiGLU the gate <-> down pair swaps
    and ``w_up`` is consumed transposed-compatibly unchanged.
    """
    bk = resolve_backend(backend)
    if act == "swiglu":
        wg, wu, wd = p["w_gate"], p["w_up"], p["w_down"]
        # the gate's silu rides the matmul's fused blend epilogue on the
        # photonic megakernel (one pallas_call; bit-identical to the
        # separate jax.nn.silu) and is a plain post-dot silu on xla
        # the pair-second (ff -> d) projection carries tp_hint="row": on a
        # TP mesh it consumes the column-sharded gate/up intermediate
        # slice-for-slice instead of all-gathering the ff axis
        if transpose:
            g = bk.dot(x, wd, transpose=True,           # (ff, d).T : d->ff
                       activation="silu")
            u = bk.dot(x, wu, transpose=False)          # unchanged
            return bk.dot(g * u, wg, transpose=True,    # (d, ff).T : ff->d
                          tp_hint="row")
        g = bk.dot(x, wg, transpose=False, activation="silu")
        u = bk.dot(x, wu, transpose=False)
        return bk.dot(g * u, wd, transpose=False, tp_hint="row")
    # gelu stays outside the kernel: its tanh/mul chain re-rounds under
    # XLA's fma contraction, so fusing it would break the fused-vs-split
    # bit-identity guarantee the serving path relies on
    wu, wd = p["w_up"], p["w_down"]
    if transpose:
        h = jax.nn.gelu(bk.dot(x, wd, transpose=True))
        return bk.dot(h, wu, transpose=True, tp_hint="row")
    h = jax.nn.gelu(bk.dot(x, wu, transpose=False))
    return bk.dot(h, wd, transpose=False, tp_hint="row")


# ------------------------------------------------------------- embeddings
def init_embedding(key, vocab: int, d_model: int):
    p = {"table": _dense_init(key, (vocab, d_model), scale=0.02)}
    return p, {"table": ("vocab", "embed")}


def embed(p, tokens, dtype):
    return p["table"].astype(dtype)[tokens]


def init_unembed(key, d_model: int, vocab: int):
    p = {"w": _dense_init(key, (d_model, vocab))}
    return p, {"w": ("embed", "vocab")}


def unembed(p, x, backend=None):
    return resolve_backend(backend).dot(x, p["w"].astype(x.dtype),
                                        transpose=False)


def init_linear(key, d_in: int, d_out: int, axes=("embed", "embed")):
    return {"w": _dense_init(key, (d_in, d_out))}, {"w": axes}


def apply_linear(p, x, transpose: bool = False, backend=None):
    return resolve_backend(backend).dot(x, p["w"].astype(x.dtype),
                                        transpose=transpose)
