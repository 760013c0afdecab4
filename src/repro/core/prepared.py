"""Prepared photonic weight banks — write-once quantization at build time.

The paper's whole premise is *program the MRR bank once, stream many
activations through it* (§3.1).  The legacy photonic path violated that in
software: ``Backend.dot`` re-derived W8 tiles + scales from the fp weights
inside every jitted step (XLA CSEs repeats within a step, not across steps),
an O(params) per-token tax the hardware pays once per calibration interval.

``PreparedTensor`` is the software image of a *programmed* bank:

  * ``wq``      int8 (..., K, N) — per-output-channel symmetric W8 tiles
                (the MRR transmission pattern, pre-offset domain);
  * ``scale``   f32  (..., N)    — per-output-channel TIA gains (``wmax``);
  * ``wq_t``    int8 (..., K, N) — the same matrix re-quantized per ROW for
                the OBU optical-transpose orientation (light on the
                orthogonal port sees rows as output channels);
  * ``scale_t`` f32  (..., K)    — per-row gains of the transposed use;
  * ``w0_colsum`` f32 (..., N)   — the offset-decomposition column sums
                ``sum_k W'[k, n]`` of the programmed bank in the MRR domain
                (``W' = wq/(2*qmax) + 0.5``, paper eq. 6).  On hardware this
                is the per-column summed transmission read back after
                programming to verify the write; here it is the bank
                checksum that ``verify_bank`` (and the conformance tests)
                recompute against.
  * ``w0_rowsum_t`` f32 (..., K) — the same read-back checksum for the
                transposed orientation: per output channel of the ``wq_t``
                image, ``sum_n W't[k, n]``.  Without it, corruption in the
                ``_t`` tiles was invisible to ``verify_bank`` (which only
                recomputed the W0 orientation); the calibration read-back
                loop (``core/noise.py``) re-measures both.

Each prepared leaf also carries a static ``tag`` — a stable 31-bit hash of
its pytree path, the bank's identity for the fault model (``core/noise.py``
keys per-bank PRNG streams on it) and the calibration loop (mapping
residency-manager bank keys to per-bank drift ages).  It rides the pytree
``aux_data``, so it is part of the treedef, survives jit, and never becomes
a traced value.

The quantization helpers below are the *single source of truth*: the
in-kernel path (`kernels/ops.py`) calls the same functions, so a bank
prepared at build time is bit-identical to what the legacy per-step path
would have derived — Program-vs-legacy outputs match exactly, not just
within tolerance.

Banks feed the fused decode-path megakernel directly (DESIGN.md §Fused
decode path): ``Backend.dot`` hands ``wq``/``scale`` (or the transposed
``wq_t``/``scale_t`` image) straight to
``kernels/photonic_mvm.photonic_mvm_fused``, whose prologue quantizes the
*activations* in-register — at serving time nothing weight-side is ever
recomputed, and nothing activation-side round-trips HBM.

Leading batch dims are free: a stacked segment's (R, K, N) weight — or a
MoE bank's (R, E, K, N) — prepares each slice exactly as the per-call path
would (the reductions run over the last two axes only).
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

QMAX = 127.0

# Crossbar-matmul weight leaves, by final pytree key.  Only these are
# programmed into banks; everything else (norm scales, biases, SSM
# A/D/dt, conv taps — including their PRM-stacked 2-D images) stays fp.
# Deliberately NOT prepared despite being matmul-ish:
#   table  — embedding gather needs the fp table (the tied lm-head matmul
#            keeps the legacy in-kernel quantize path);
#   router — MoE routing is fp32 + top-k on every backend;
#   w_ukv  — MLA's latent up-projection has one treatment on every path:
#            decode absorbs it into the latent einsums and prefill
#            up-projects the cached latents, both as compute-dtype einsums
#            (``models/attention._up_project``), never as a W8A8 bank.
MATMUL_LEAVES = frozenset({
    "wq", "wk", "wv", "wo",                      # attention projections
    "w_gate", "w_up", "w_down",                  # MLPs + MoE expert banks
    "w_dkv",                                     # MLA down-projection
    "w_in", "w_out",                             # SSM in/out projections
    "w",                                         # unembed / linear adapters
})


# =========================================================================
# canonical W8 quantization (shared with kernels/ops.py — bitwise identical)
# =========================================================================
def quantize_weight(w: jax.Array, qmax: float = QMAX):
    """Per-output-channel symmetric W8 of ``w`` (..., K, N).

    Returns (wq int8 (..., K, N), scale f32 (..., N)).  Reductions run over
    axis -2 only, so leading stack/bank dims quantize slice-wise exactly
    like the per-call kernel path does."""
    wmax = jnp.maximum(jnp.max(jnp.abs(w), axis=-2, keepdims=True), 1e-8)
    w_norm = w / wmax
    wq = jnp.clip(jnp.round(w_norm * qmax), -qmax - 1, qmax).astype(jnp.int8)
    return wq, jnp.squeeze(wmax, axis=-2).astype(jnp.float32)


def quantize_weight_t(w: jax.Array, qmax: float = QMAX):
    """Per-ROW symmetric W8 of ``w`` (..., N, K) for the transposed use
    (axis -2 is the output channel there).  Returns (wq_t int8 (..., N, K),
    scale_t f32 (..., N))."""
    wmax = jnp.maximum(jnp.max(jnp.abs(w), axis=-1), 1e-8)
    w_norm = w / wmax[..., None]
    wq = jnp.clip(jnp.round(w_norm * qmax), -qmax - 1, qmax).astype(jnp.int8)
    return wq, wmax.astype(jnp.float32)


def w0_column_sums(wq: jax.Array, qmax: float = QMAX) -> jax.Array:
    """Offset-decomposition column sums of a programmed bank: per output
    channel, ``sum_k W'[k, n]`` with ``W' = wq/(2*qmax) + 0.5`` (the MRR
    transmission domain of paper eq. 6)."""
    k = wq.shape[-2]
    s = jnp.sum(wq.astype(jnp.float32), axis=-2)
    return s / (2.0 * qmax) + 0.5 * k


def w0_row_sums(wq_t: jax.Array, qmax: float = QMAX) -> jax.Array:
    """Read-back checksum of the TRANSPOSED orientation: per output channel
    of the ``wq_t`` image (axis -2 there), ``sum_n W't[k, n]`` in the same
    MRR transmission domain.  The reduction runs over axis -1 — the
    reduction axis of the transposed use."""
    n = wq_t.shape[-1]
    s = jnp.sum(wq_t.astype(jnp.float32), axis=-1)
    return s / (2.0 * qmax) + 0.5 * n


# =========================================================================
# PreparedTensor
# =========================================================================
@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class PreparedTensor:
    """A weight matrix as a programmed photonic bank (int8 + gains).

    Behaves enough like the fp array it replaced that the model layers need
    no rewrite: ``.shape`` reports the logical (fp) shape, ``.astype`` is a
    no-op (a programmed bank has no dtype to cast — readout gain handles
    that), and ``x[i]`` slices every field's leading axis (MoE banks index
    their basic-expert dimension; the PRM scan reads the R axis through a
    :class:`BankLayer` instead)."""

    wq: jax.Array            # int8 (..., K, N), per-column quantized
    scale: jax.Array         # f32  (..., N)
    wq_t: jax.Array          # int8 (..., K, N), per-row quantized
    scale_t: jax.Array       # f32  (..., K)
    w0_colsum: jax.Array     # f32  (..., N) — programmed-bank checksum
    w0_rowsum_t: jax.Array   # f32  (..., K) — transposed-orientation checksum
    tag: int = 0             # static bank identity (pytree aux_data)

    # ---------------------------------------------------------- pytree
    def tree_flatten(self):
        # ``tag`` is aux_data: part of the treedef, never traced — two banks
        # with different tags are different pytree *structures*, which is
        # exactly what keys the per-bank noise streams into the jit cache.
        return ((self.wq, self.scale, self.wq_t, self.scale_t,
                 self.w0_colsum, self.w0_rowsum_t), self.tag)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children, tag=aux if aux is not None else 0)

    # ------------------------------------------------------- array-likeness
    @property
    def shape(self):
        return self.wq.shape

    @property
    def ndim(self):
        return self.wq.ndim

    def astype(self, dtype):
        """No-op: the bank is programmed; output dtype is set at readout
        (the kernels cast after the TIA rescale)."""
        return self

    def __getitem__(self, idx):
        # slices of a stacked bank share its identity: the tag names the
        # programmed *leaf*, not an individual matrix slice
        return PreparedTensor(self.wq[idx], self.scale[idx], self.wq_t[idx],
                              self.scale_t[idx], self.w0_colsum[idx],
                              self.w0_rowsum_t[idx], tag=self.tag)

    # ------------------------------------------------------------- sharding
    @classmethod
    def field_specs(cls, wspec: tuple, ndim: int,
                    tag: int = 0) -> "PreparedTensor":
        """Per-field PartitionSpecs from the owning weight's spec.

        ``wspec`` is the fp weight's (possibly trailing-trimmed) spec
        entries and ``ndim`` its rank.  The tiles shard exactly like the
        weight they image (``wq_t`` has the SAME array shape — the
        transposed use is an in-register swap, never a materialized
        transpose); the per-column gains/checksum (shape ``[..., N]``)
        follow the last dim's axis and the per-row gains/checksum
        (``[..., K]``) the second-to-last's.  Used by ``sharding.partition.
        bank_shardings`` so a bank placed on a mesh keeps every field of
        one programmed tile on the device that owns it.  ``tag`` must be
        the bank leaf's own tag: the spec node's treedef (aux_data) has to
        match the leaf's for ``jax.device_put(bank, shardings)``."""
        from jax.sharding import PartitionSpec as P

        entries = list(wspec) + [None] * (ndim - len(wspec))
        lead, kax, nax = entries[:-2], entries[-2], entries[-1]
        wfull = P(*entries)
        return cls(wq=wfull, scale=P(*lead, nax), wq_t=wfull,
                   scale_t=P(*lead, kax), w0_colsum=P(*lead, nax),
                   w0_rowsum_t=P(*lead, kax), tag=tag)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class BankLayer:
    """Layer ``index`` of a stacked bank, read where the bank lies.

    ``core/sharing.run_stack`` hands these to its block function instead of
    scanning over the stacked banks: a ``pallas_call`` cannot fuse a slice
    of its operand, so a per-layer ``PreparedTensor`` would be copied out of
    the stack before every kernel call.  The view keeps the
    ``PreparedTensor`` surface — per-layer ``shape``, the fields as the
    dynamic slice of the stack, ``x[i]`` — so every consumer that does not
    know it gets exactly that slice; only the fused single-device photonic
    dot (``Backend.dot_prepared``) passes ``stack`` and ``index`` to the
    kernel, which reads the layer's tiles in place.  On an expert bank
    (R, E, K, N), ``x[e]`` is expert e's view of the same kind."""

    stack: PreparedTensor    # (R, ...) stacked bank
    index: jax.Array         # traced int32 scalar, the layer

    def tree_flatten(self):
        return (self.stack, self.index), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)

    def _at(self, field: jax.Array) -> jax.Array:
        return jax.lax.dynamic_index_in_dim(field, self.index, 0,
                                            keepdims=False)

    def layer(self) -> PreparedTensor:
        return jax.tree.map(self._at, self.stack)

    wq = property(lambda self: self._at(self.stack.wq))
    scale = property(lambda self: self._at(self.stack.scale))
    wq_t = property(lambda self: self._at(self.stack.wq_t))
    scale_t = property(lambda self: self._at(self.stack.scale_t))
    w0_colsum = property(lambda self: self._at(self.stack.w0_colsum))
    w0_rowsum_t = property(lambda self: self._at(self.stack.w0_rowsum_t))

    @property
    def tag(self):
        return self.stack.tag

    @property
    def shape(self):
        return self.stack.shape[1:]

    @property
    def ndim(self):
        return self.stack.ndim - 1

    def astype(self, dtype):
        return self

    def __getitem__(self, idx):
        if isinstance(idx, int) and self.stack.ndim > 3:
            # expert ``idx`` of a stacked (R, E, K, N) bank: a view of the
            # same stack as (R*E, K, N), so it too is read in place
            n = self.stack.shape[1]
            flat = jax.tree.map(lambda f: f.reshape((-1,) + f.shape[2:]),
                                self.stack)
            return BankLayer(flat, self.index * n + idx)
        return self.layer()[idx]


def is_prepared(w: Any) -> bool:
    return isinstance(w, PreparedTensor)


def prepare_tensor(w: jax.Array, qmax: float = QMAX,
                   tag: int = 0) -> PreparedTensor:
    """Program one fp weight (..., K, N) into a PreparedTensor — both
    orientations plus their read-back checksums."""
    wq, scale = quantize_weight(w, qmax)
    wq_t, scale_t = quantize_weight_t(w, qmax)
    return PreparedTensor(wq=wq, scale=scale, wq_t=wq_t, scale_t=scale_t,
                          w0_colsum=w0_column_sums(wq, qmax),
                          w0_rowsum_t=w0_row_sums(wq_t, qmax), tag=tag)


def verify_bank(prep: PreparedTensor, qmax: float = QMAX) -> jax.Array:
    """Max |recomputed − stored| checksum error of a programmed bank over
    BOTH orientations (the hardware read-back verification; ~0 for an
    uncorrupted bank, up to fp32 reduction-order noise ~1e-5; a corrupted
    int8 tile — in either the W0 or the transposed image — shifts a sum by
    >= 1/(2*qmax) ~ 4e-3)."""
    err = jnp.max(jnp.abs(w0_column_sums(prep.wq, qmax) - prep.w0_colsum))
    err_t = jnp.max(jnp.abs(w0_row_sums(prep.wq_t, qmax)
                            - prep.w0_rowsum_t))
    return jnp.maximum(err, err_t)


# =========================================================================
# whole-params preparation
# =========================================================================
def _eligible(path, leaf) -> bool:
    if not hasattr(leaf, "ndim") or leaf.ndim < 2:
        return False
    if not jnp.issubdtype(leaf.dtype, jnp.floating):
        return False
    last = None
    for k in reversed(path):
        key = getattr(k, "key", None)
        if isinstance(key, str):
            last = key
            break
    return last in MATMUL_LEAVES


def path_tag(path) -> int:
    """Stable 31-bit bank identity from a pytree path (crc32 of the
    ``keystr`` form).  Static python at trace time, deterministic across
    processes — two Programs built from the same config give every bank the
    same tag, so noise patterns and calibration state are reproducible."""
    import zlib
    return zlib.crc32(jax.tree_util.keystr(path).encode()) & 0x7FFFFFFF


def prepare_params(params: Any, compute_dtype, photonic: bool) -> Any:
    """Build the prepared bank for a whole model.

    Every leaf is first cast fp32 -> ``compute_dtype`` (subsuming
    ``engine.cast_params``).  With ``photonic=True``, every crossbar matmul
    weight (:data:`MATMUL_LEAVES`) is then programmed into a
    :class:`PreparedTensor`; everything else stays floating point.

    The cast-then-quantize order matches the legacy in-step path exactly
    (layers cast ``p["w"].astype(x.dtype)`` before ``Backend.dot``), so the
    bank is bit-identical to what each step would have derived."""
    dtype = jnp.dtype(compute_dtype)

    def one(path, leaf):
        if hasattr(leaf, "dtype") and leaf.dtype == jnp.float32:
            leaf = leaf.astype(dtype)
        if photonic and _eligible(path, leaf):
            return prepare_tensor(leaf, tag=path_tag(path))
        return leaf

    return jax.tree_util.tree_map_with_path(one, params)


MRR_TILE = 128   # physical crossbar tile edge (paper §2: 128x128 MRR array)


def tiles_128(rows: int, cols: int) -> int:
    """128x128 MRR crossbar tiles one (rows, cols) matrix occupies — the
    unit the residency manager's array budget is denominated in."""
    return -(-rows // MRR_TILE) * -(-cols // MRR_TILE)


def bank_descriptors(bank: Any, prefix: str = "") -> list[dict]:
    """One descriptor per programmed tensor of a prepared bank: pytree
    path, logical (rows, cols) of a single matrix slice, the stacked
    slice count (leading dims — PRM R axis, MoE experts), and the
    128-tile occupancy.  This is what ``resident/mapping.py`` turns into
    :class:`~repro.resident.manager.BankSpec` budget entries."""
    leaves = jax.tree_util.tree_flatten_with_path(
        bank, is_leaf=lambda x: isinstance(x, PreparedTensor))[0]
    out = []
    for path, leaf in leaves:
        if not isinstance(leaf, PreparedTensor):
            continue
        k, n = int(leaf.wq.shape[-2]), int(leaf.wq.shape[-1])
        stacked = 1
        for d in leaf.wq.shape[:-2]:
            stacked *= int(d)
        out.append({"path": prefix + jax.tree_util.keystr(path),
                    "rows": k, "cols": n, "stacked": stacked,
                    "mrr_tiles_128": stacked * tiles_128(k, n),
                    "tag": leaf.tag})
    return out


def prepared_stats(bank: Any) -> dict:
    """Bank accounting: programmed tensors / int8 bytes / fp leaves, plus
    the physical-programming view — how many 128x128 MRR tiles the banks
    occupy and how many W0 checksum words the read-back verification
    carries.  ``Program.build`` mirrors every entry into the metrics
    registry as ``program.bank.*`` gauges."""
    n_prog = 0
    int8_bytes = 0
    fp_bytes = 0
    mrr_tiles = 0
    checksums = 0
    for leaf in jax.tree.leaves(
            bank, is_leaf=lambda x: isinstance(x, PreparedTensor)):
        if isinstance(leaf, PreparedTensor):
            n_prog += 1
            int8_bytes += leaf.wq.size + leaf.wq_t.size
            checksums += leaf.w0_colsum.size + leaf.w0_rowsum_t.size
            k, n = leaf.wq.shape[-2], leaf.wq.shape[-1]
            stacked = 1
            for d in leaf.wq.shape[:-2]:
                stacked *= int(d)
            mrr_tiles += stacked * tiles_128(k, n)
        elif hasattr(leaf, "nbytes"):
            fp_bytes += leaf.nbytes
    return {"programmed_tensors": n_prog, "int8_bytes": int8_bytes,
            "fp_bytes": fp_bytes, "mrr_tiles_128": mrr_tiles,
            "checksum_count": checksums}
