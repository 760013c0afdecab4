"""Shared-stack execution — PRM (§3.1) + OBU (§3.2) mapped onto jax.lax.scan.

A stack of ``depth = R*T`` logical blocks is executed as

    scan over R physical blocks            (fp params are scan xs; prepared
                                            banks stay loop-invariant and
                                            are read at the block index)
      unrolled loop over T reuses          (params loop-INVARIANT -> weights
                                            stay resident; OBU transform per t)

The unrolled inner loop keeps every OBU transform *static* (constant-index
gathers, dot_general dimension swaps), so XLA sees a fixed program whose HLO
size is O(T), not O(R*T).  This is the TPU-native realization of the paper's
write-once / reuse-T-times schedule: HBM weight streaming and gradient
all-reduce volume drop by the reuse factor.

Per-logical-layer state that is *not* shared (KV caches, SSM states) is passed
as scan xs with leading dims [R, T, ...].
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable

import numpy as np

import jax
import jax.numpy as jnp

from repro.core import backend as backend_lib
from repro.core import obu
from repro.core.prepared import BankLayer, PreparedTensor
from repro.core.prm import ReuseConfig, ReusePlan, no_reuse


def tree_index(tree, i):
    return jax.tree.map(lambda x: x[i], tree)


def tree_stack(trees):
    return jax.tree.map(lambda *xs: jnp.stack(xs, axis=0), *trees)


def _split_banks(params):
    """Split stacked params into (scan xs, rebuild): prepared banks leave
    the xs — a scan would dynamic-slice them, and a ``pallas_call`` cannot
    fuse that slice, so every layer's int8 bank would be copied out before
    each kernel call — and come back as ``BankLayer`` views of the whole
    stack at the traced block index ``r``."""
    is_bank = lambda v: isinstance(v, PreparedTensor)
    leaves, treedef = jax.tree.flatten(params, is_leaf=is_bank)
    banks = [v if is_bank(v) else None for v in leaves]
    xs = [None if is_bank(v) else v for v in leaves]

    def rebuild(xs_r, r):
        return treedef.unflatten([BankLayer(b, r) if b is not None else v
                                  for b, v in zip(banks, xs_r)])
    return xs, rebuild


@dataclasses.dataclass(frozen=True)
class SharedStack:
    """Static schedule for one stack: plan + resolved OBU tables."""

    plan: ReusePlan
    perm_table: np.ndarray          # (T, channels) int32
    inv_perm_table: np.ndarray      # (T, channels) int32
    transpose_flags: np.ndarray     # (T,) bool
    shuffle_active: tuple           # (T,) of python bool — skip identity gathers
    block_perm_table: tuple = ()    # (T,) of tuple block order | None — set
                                    # when perm[t] is a *blocked* shuffle, so
                                    # the photonic backend can fold it into
                                    # the blend kernel's index-map epilogue
    shuffle_block: int = 0          # block size of the blocked entries

    @staticmethod
    def build(depth: int, channels: int,
              cfg: ReuseConfig | None) -> "SharedStack":
        plan = ReusePlan.build(depth, cfg)
        c = plan.config
        perm = obu.build_transform_tables(
            channels, c.reuse_times, c.transforms, c.shuffle_groups,
            c.shuffle_block, c.seed)
        inv = np.stack([obu.invert_permutation(p) for p in perm])
        tf = obu.transpose_flags(c.reuse_times, c.transforms)
        active = tuple(bool((perm[t] != np.arange(channels)).any())
                       for t in range(c.reuse_times))
        block = (c.shuffle_block if c.shuffle_block > 0
                 and channels % c.shuffle_block == 0 else 0)
        bpt = []
        for t in range(c.reuse_times):
            bp = None
            if block and active[t]:
                p2 = perm[t].reshape(-1, block)
                order = p2[:, 0] // block
                if (p2 == order[:, None] * block
                        + np.arange(block)[None, :]).all():
                    bp = tuple(int(v) for v in order)
            bpt.append(bp)
        return SharedStack(plan=plan, perm_table=perm, inv_perm_table=inv,
                           transpose_flags=tf, shuffle_active=active,
                           block_perm_table=tuple(bpt), shuffle_block=block)

    @property
    def num_physical(self) -> int:
        return self.plan.num_physical

    @property
    def reuse_times(self) -> int:
        return self.plan.reuse_times


def identity_stack(depth: int, channels: int) -> SharedStack:
    return SharedStack.build(depth, channels, no_reuse(depth))


# ---------------------------------------------------------------------------
# stack runner
# ---------------------------------------------------------------------------
BlockFn = Callable[..., tuple]
# block_fn(params_r, x, cache_t, aux, *, transpose: bool, reuse_index: int)
#   -> (x, new_cache_t, aux)     where cache_t may be None; aux is a scalar
#   accumulator (e.g. MoE load-balance loss) threaded through the scan, or
#   a pytree of them (``models.moe.route_record``).


def _cache_at(cache_leaf, r, t):
    """``cache_leaf[r, t]`` of a carried [R, T, ...] buffer at a traced
    block ``r`` and a static reuse ``t``: one dynamic slice."""
    start = (r, t) + (0,) * (cache_leaf.ndim - 2)
    return jax.lax.dynamic_slice(cache_leaf, start,
                                 (1, 1) + cache_leaf.shape[2:])[0, 0]


def _delta_update(cache_leaf, delta, r, t, pos):
    """Write a block_fn cache update back into the carried [R, T, ...] buffer.

    If the update has the slice's full shape it replaces the [r, t] slice
    (SSM state, conv tail).  If exactly one dim is 1 where the cache has L
    (a one-token KV delta), only that token is written at ``pos`` — this is
    what keeps decode HBM traffic at ~1x cache read + epsilon write.

    ``pos`` may be a scalar (aligned decode: one position for the whole
    batch) or a (B,) vector (continuous decode: slot ``b``'s token lands at
    ``pos[b]``; the write becomes a per-row scatter)."""
    slice_shape = cache_leaf.shape[2:]
    up = delta.astype(cache_leaf.dtype)
    if tuple(up.shape) == tuple(slice_shape):
        idx = (r, t) + (0,) * len(slice_shape)
        return jax.lax.dynamic_update_slice(cache_leaf, up[None, None], idx)
    diff = [i for i, (a, b) in enumerate(zip(up.shape, slice_shape))
            if a != b]
    assert len(diff) == 1 and up.shape[diff[0]] == 1, (
        f"cache delta {up.shape} incompatible with slice {slice_shape}")
    if jnp.ndim(pos) == 1:
        # per-slot positions: the cache slice must be (B, L, ...) with the
        # one-token delta on axis 1 so each row scatters independently
        assert diff[0] == 1 and up.shape[0] == pos.shape[0], (
            f"per-slot delta {up.shape} needs batch-leading slice "
            f"{slice_shape} and one position per slot ({pos.shape})")
        B = up.shape[0]
        return cache_leaf.at[r, t, jnp.arange(B), pos].set(
            jnp.squeeze(up, axis=1))
    idx = [r, t] + [0] * len(slice_shape)
    idx[2 + diff[0]] = pos
    return jax.lax.dynamic_update_slice(cache_leaf, up[None, None],
                                        tuple(idx))


def run_stack(block_fn: BlockFn, params: Any, x: jax.Array,
              shared: SharedStack, cache: Any = None, aux0=0.0,
              unroll_scan: int = 1, remat: bool = False,
              decode_pos=None, backend=None):
    """Run a PRM-shared stack.

    Args:
      block_fn: applies ONE basic block (may itself contain several layers —
        block-wise granularity).  Receives a *static* ``transpose`` flag and
        ``reuse_index``.
      params:  pytree with leading axis R (= shared.num_physical).  Its
        ``PreparedTensor`` banks reach block_fn as ``BankLayer`` views.
      x:       activations (..., channels).
      shared:  the static schedule.
      cache:   optional pytree with leading axes [R, T, ...] of per-logical-
        layer state (KV / SSM).  Returned updated with the same shape.
      remat:   checkpoint each physical block — only the R block inputs are
        saved; the T reuses are recomputed in backward against the already-
        resident shared weights (the natural PRM remat boundary).
      backend: core.backend.Backend (or anything ``backend.resolve`` takes).
        The photonic backend applies *blocked* OBU shuffles via the blend
        kernel's index-map epilogue instead of a gather.
      decode_pos: when set (decode mode), the cache travels as the scan
        CARRY — XLA aliases loop carries in place — and block_fn cache
        returns are treated as deltas written via dynamic_update_slice
        (one token for KV caches, full slice for SSM state).  A scalar
        writes every batch row at the same position (aligned decode); a
        (B,) vector writes row ``b`` at ``decode_pos[b]`` (continuous
        slot-level decode, DESIGN.md §Serving).

    Returns (x, new_cache, aux).
    """
    T = shared.reuse_times
    have_cache = cache is not None
    if not isinstance(aux0, dict):           # a dict: moe.route_record
        aux0 = jnp.asarray(aux0, dtype=jnp.float32)
    backend = backend_lib.resolve(backend)
    bpt = shared.block_perm_table

    def one_reuse(t):
        def f(h, aux, p_r, c_t):
            if shared.shuffle_active[t]:
                h = backend.shuffle(h, shared.perm_table[t],
                                    block_perm=bpt[t] if bpt else None,
                                    block=shared.shuffle_block)
            h, c_t, aux = block_fn(p_r, h, c_t, aux,
                                   transpose=bool(shared.transpose_flags[t]),
                                   reuse_index=t)
            return h, aux, c_t
        return f

    # with remat, checkpoint at *reuse* granularity: the backward working
    # set stays one logical block regardless of T (the shared weights are
    # already resident when recomputing — the natural PRM remat boundary)
    reuse_fns = [jax.checkpoint(one_reuse(t)) if remat else one_reuse(t)
                 for t in range(T)]

    def body(h, aux, p_r, cache_at):
        new_cache = []
        for t in range(T):
            c_t = cache_at(t) if have_cache else None
            h, aux, c_t = reuse_fns[t](h, aux, p_r, c_t)
            new_cache.append(c_t)
        return h, aux, (new_cache if have_cache else None)

    R = shared.num_physical
    p_xs, rebuild = _split_banks(params)

    if have_cache and decode_pos is not None:
        # ---- decode: cache as in-place carry, delta writes ----
        def outer_carry(carry, xs):
            h, aux, cache_all = carry
            p_r, r = xs
            p_r = rebuild(p_r, r)
            # each reuse reads its own [r, t] slice of the carried cache: a
            # slice of the block's [r] (all T reuses) has T consumers, and
            # XLA copies it out whole rather than fuse it into each
            h, aux, updates = body(
                h, aux, p_r,
                lambda t: jax.tree.map(lambda c: _cache_at(c, r, t),
                                       cache_all))
            for t, up_t in enumerate(updates):
                cache_all = jax.tree.map(
                    lambda c, u: _delta_update(c, u, r, t, decode_pos),
                    cache_all, up_t)
            return (h, aux, cache_all), None

        (x, aux, cache), _ = jax.lax.scan(
            outer_carry, (x, aux0, cache), (p_xs, jnp.arange(R)),
            unroll=unroll_scan)
        return x, cache, aux

    def outer(carry, xs):
        h, aux = carry
        p_r, cache_r, r = xs
        h, aux, out_cache = body(h, aux, rebuild(p_r, r),
                                 lambda t: tree_index(cache_r, t))
        return (h, aux), (tree_stack(out_cache)
                          if out_cache is not None else None)

    (x, aux), new_cache = jax.lax.scan(outer, (x, aux0),
                                       (p_xs, cache, jnp.arange(R)),
                                       unroll=unroll_scan)
    return x, new_cache, aux


# ---------------------------------------------------------------------------
# parameter bookkeeping
# ---------------------------------------------------------------------------
def stacked_init(init_one: Callable[[jax.Array], Any], key: jax.Array,
                 num_physical: int) -> Any:
    """Initialize R independent copies of a block's params, stacked on axis 0."""
    keys = jax.random.split(key, num_physical)
    return jax.vmap(init_one)(keys)


def param_count(tree) -> int:
    return int(sum(np.prod(x.shape) for x in jax.tree.leaves(tree)))
