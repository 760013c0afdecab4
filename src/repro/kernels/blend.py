"""OBU blend Pallas kernel — blocked channel shuffle fused with bias + ReLU.

The paper's OBU performs the shuffle "for free" during the mandatory O/E
conversion.  The TPU-native equivalent: a *blocked* permutation whose block
size is a multiple of the 128-wide lane dimension is pure **grid index
remapping** — the input BlockSpec's ``index_map`` reads block ``perm[j]``
while writing block ``j``, so the data movement happens inside the copy that
a fused bias+activation epilogue needed anyway.  Zero extra passes over HBM.

(The fine-grained channel-group shuffle keeps its XLA gather form in
``core.obu``; this kernel covers the paper's *blocked random shuffle* flavor.)
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.platform import default_interpret


def _kernel(perm_ref, x_ref, b_ref, o_ref, *, activation: str):
    # f32 epilogue: the v5e VPU has no bf16 arithmetic
    y = x_ref[...].astype(jnp.float32) + b_ref[...].astype(jnp.float32)
    if activation == "relu":
        y = jnp.maximum(y, 0.0)
    elif activation == "silu":
        y = y * jax.nn.sigmoid(y)
    o_ref[...] = y.astype(o_ref.dtype)


def blend_shuffle(x, bias, block_perm, *, block=128, bm=128,
                  activation="relu", interpret=None):
    """y[:, j*block:(j+1)*block] = act(x[:, perm[j]*block:...] + bias[...]).

    x: (M, C) with C == len(block_perm) * block; bias: (C,) added *after*
    the shuffle (indexed by output position).  ``block_perm`` arrives via
    TPU scalar prefetch so the input BlockSpec's index map can read it —
    the shuffle is realized purely as grid index remapping.
    ``interpret=None`` resolves from the platform.
    """
    if interpret is None:
        interpret = default_interpret()
    M, C = x.shape
    if block <= 0 or C % block != 0:
        # a ragged channel axis would silently drop the C % block tail
        # columns from every block slice — refuse instead
        raise ValueError(
            f"blend_shuffle needs the channel axis to split into whole "
            f"blocks: C={C} is not a multiple of block={block}")
    nblk = C // block
    perm = np.asarray(block_perm, dtype=np.int32)
    if sorted(perm.tolist()) != list(range(nblk)):
        raise ValueError(
            f"block_perm must be a permutation of range({nblk}), got "
            f"{perm.tolist()}")
    # ragged row counts (serving batches) are zero-padded to the row block,
    # exactly like photonic_mvm._pad_to, and sliced back after the kernel
    pad_m = (-M) % bm
    if pad_m:
        x = jnp.pad(x, ((0, pad_m), (0, 0)))
    Mp = M + pad_m
    grid = (Mp // bm, nblk)
    gridspec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=[
            # input block j is read from source block perm[j]: the shuffle IS
            # the index map.
            pl.BlockSpec((bm, block), lambda i, j, perm_ref: (i, perm_ref[j])),
            pl.BlockSpec((1, block), lambda i, j, perm_ref: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, block), lambda i, j, perm_ref: (i, j)),
    )
    out = pl.pallas_call(
        functools.partial(_kernel, activation=activation),
        grid_spec=gridspec,
        out_shape=jax.ShapeDtypeStruct((Mp, C), x.dtype),
        interpret=interpret,
    )(jnp.asarray(perm), x, bias.reshape(1, C))
    return out[:M]
