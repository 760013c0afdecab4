"""Seeded weights, shared by the harness and the plain reference.

Every weight is a function of ``(seed, name, index)`` alone: the harness
builds the program's parameter tree from it leaf by leaf (in one jitted
call, on the device, in the dtype the model is served in), and the
reference regenerates the same values by name, layer by layer, without
taking anything the program made.

``name`` is the leaf's path in the parameter tree (``segments/main/l0/
mixer/wq``); a leaf under ``segments/`` is stacked over the physical
blocks, and block ``index`` is drawn from its own key.
"""
from __future__ import annotations

import math
import zlib

import jax
import jax.numpy as jnp


def base_key(seed: int):
    """A PRNG key from a non-negative seed of up to 62 bits."""
    if seed < 0 or seed >= 2 ** 62:
        raise ValueError(f"seed {seed} outside [0, 2**62)")
    key = jax.random.PRNGKey(0)
    key = jax.random.fold_in(key, seed & 0x7FFFFFFF)
    return jax.random.fold_in(key, seed >> 31)


def leaf_key(base, name: str, index=None):
    key = jax.random.fold_in(base, zlib.crc32(name.encode()) & 0x7FFFFFFF)
    return key if index is None else jax.random.fold_in(key, index)


def kind_of(name: str) -> str:
    if name.rsplit("/", 1)[-1] == "scale":
        return "norm"
    if name == "embed/table":
        return "embed"
    return "dense"


def generate(key, shape, kind: str, dtype):
    """One weight: norm scales 1 + 0.1 N(0, 1), the embedding 0.02 N(0, 1),
    matrices N(0, 1) / sqrt(fan_in) with fan_in the second-to-last axis."""
    z = jax.random.normal(key, shape, jnp.float32)
    if kind == "norm":
        v = 1.0 + 0.1 * z
    elif kind == "embed":
        v = 0.02 * z
    else:
        v = z * (1.0 / math.sqrt(shape[-2]))
    return v.astype(dtype)


def leaf(base, name: str, shape, dtype, index=None):
    """The weight ``name`` (block ``index`` of a stacked leaf)."""
    return generate(leaf_key(base, name, index), tuple(shape), kind_of(name),
                    dtype)


def path_name(path) -> str:
    return "/".join(str(getattr(k, "key", k)) for k in path)


def make_params(abstract, seed: int, dtype):
    """The parameter tree shaped like ``abstract`` (ShapeDtypeStructs), on
    the default device, in ``dtype``, from ``seed``: one jitted call whose
    only operand is the seed's key, so every seed reuses one compile."""
    def build(base):
        def one(path, sds):
            name = path_name(path)
            if name.startswith("segments/"):
                return jnp.stack([leaf(base, name, sds.shape[1:], dtype, r)
                                  for r in range(sds.shape[0])])
            return leaf(base, name, sds.shape, dtype)
        return jax.tree_util.tree_map_with_path(one, abstract)
    return jax.block_until_ready(jax.jit(build)(base_key(seed)))
