"""Mamba-2 SSD chunk kernel — the quadratic intra-chunk hot loop in Pallas.

Per (batch*chunk, head) grid cell the kernel computes, entirely in VMEM:

    cs      = cumsum(dA)            (matmul with a lower-tri ones matrix —
                                     MXU-friendly cumsum)
    Lmat    = exp(cs_i - cs_j)  masked to i >= j       (decay matrix)
    y_diag  = ((C B^T) * Lmat) @ x                     (intra-chunk output)
    state   = (B * exp(cs_L - cs))^T @ x               (chunk's state delta)

The inter-chunk recurrence (a tiny (H, P, N) scan over chunks) and the
state->output correction stay in JAX (``models.ssm``) — they are O(S/L) and
bandwidth-trivial.  x must arrive dt-folded (x * dt), matching models.ssm.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.platform import default_interpret

NEG_INF = -1e30


def _kernel(x_ref, dA_ref, b_ref, c_ref, y_ref, st_ref, *, L: int):
    x = x_ref[...].astype(jnp.float32)          # (L, P)
    dA = dA_ref[...].astype(jnp.float32)        # (1, L)
    B = b_ref[...].astype(jnp.float32)          # (L, N)
    C = c_ref[...].astype(jnp.float32)          # (L, N)
    # cumsum as lower-triangular matmul (keeps the op on the MXU)
    tril = jnp.tril(jnp.ones((L, L), jnp.float32))
    cs = jnp.dot(tril, dA.reshape(L, 1),
                 preferred_element_type=jnp.float32).reshape(L)
    seg = cs[:, None] - cs[None, :]
    ii = jax.lax.broadcasted_iota(jnp.int32, (L, L), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (L, L), 1)
    Lmat = jnp.exp(jnp.where(ii >= jj, seg, NEG_INF))
    scores = jax.lax.dot_general(C, B, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
    y_ref[...] = jnp.dot(scores * Lmat, x,
                         preferred_element_type=jnp.float32
                         ).astype(y_ref.dtype)
    decay = jnp.exp(cs[-1] - cs)                 # (L,)
    st = jax.lax.dot_general(B * decay[:, None], x,
                             (((0,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)
    st_ref[...] = st.astype(st_ref.dtype)        # (N, P)


def ssd_chunk(x, dA, B, C, *, interpret=None):
    """Intra-chunk SSD.

    x:  (b, nc, L, H, P)  dt-folded inputs
    dA: (b, nc, H, L)     per-step log decay (dt * A)
    B, C: (b, nc, L, H, N)  already head-broadcast
    Returns y_diag (b, nc, L, H, P) fp32 and states (b, nc, H, N, P) fp32.
    ``interpret=None`` resolves from the platform.
    """
    if interpret is None:
        interpret = default_interpret()
    b, nc, L, H, P = x.shape
    N = B.shape[-1]
    grid = (b * nc, H)
    xf = x.reshape(b * nc, L, H, P)
    dAf = dA.reshape(b * nc, H, L)
    Bf = B.reshape(b * nc, L, H, N)
    Cf = C.reshape(b * nc, L, H, N)
    y, st = pl.pallas_call(
        functools.partial(_kernel, L=L),
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, L, None, P), lambda g, h: (g, 0, h, 0)),
            pl.BlockSpec((None, None, L), lambda g, h: (g, h, 0)),
            pl.BlockSpec((None, L, None, N), lambda g, h: (g, 0, h, 0)),
            pl.BlockSpec((None, L, None, N), lambda g, h: (g, 0, h, 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, L, None, P), lambda g, h: (g, 0, h, 0)),
            pl.BlockSpec((None, None, N, P), lambda g, h: (g, h, 0, 0)),
        ],
        out_shape=[jax.ShapeDtypeStruct((b * nc, L, H, P), jnp.float32),
                   jax.ShapeDtypeStruct((b * nc, H, N, P), jnp.float32)],
        interpret=interpret,
    )(xf, dAf, Bf, Cf)
    return (y.reshape(b, nc, L, H, P), st.reshape(b, nc, H, N, P))
