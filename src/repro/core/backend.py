"""Execution backends — the seam between the model stack and the compute
substrate (DESIGN.md §Execution backends, §Fused decode path).

Every weight matmul in ``models/*`` goes through ``Backend.dot`` (and the
PRM-blended MoE banks through ``Backend.reuse_dot``); the OBU activation
shuffle in ``core/sharing.py`` goes through ``Backend.shuffle``.  Two
backends implement the seam:

  * ``"xla"``      — ``obu.blend_dot`` dot_generals (fp accumulate; the
    transpose is a contraction-dim swap).  The default; bit-identical to the
    pre-backend code path.
  * ``"photonic"`` — the Pallas W8A8 kernels (`kernels/ops.py`): the
    offset-decomposed MVM (paper eq. 6) per matmul, fed either from a
    *prepared* bank (``core/prepared.py``, quantized once at
    ``Program.build`` — the write-once path) or by quantizing the fp weight
    in-step (legacy shims); the OBU transpose is the pre-swapped kernel
    variant (in-register tile swap); *blocked* OBU shuffles fold into an
    index-map epilogue; PRM-blended expert banks stream through the
    weight-stationary reuse-resident kernel.  On CPU the kernels run with
    ``interpret=True`` (see `kernels/ops.py`); numerics differ from "xla"
    by exactly the W8A8 quantization error, which the backend-parity tests
    bound.

**Fused decode path** (the default photonic serving configuration):

  * ``fused=True`` routes every matmul through the one-``pallas_call``
    megakernel (`kernels/photonic_mvm.photonic_mvm_fused`): A8 quantization
    happens in the kernel prologue (only the abs-max reduction runs
    outside — no separate full XLA pass materializing int8 activations),
    and the blend epilogue (bias + activation + blocked output shuffle)
    folds into the kernel's ``_finalize``.  ``fused=False`` is the split
    comparator: quantize-outside + MVM kernel + separate blend kernel, at
    the SAME tile plan — bit-identical to the fused path for the bias-free
    epilogues the model uses (the fused-vs-unfused acceptance gate).
  * ``adaptive=True`` derives ``(bm, bk, bn)`` per call from the actual
    operand shapes via :meth:`Backend.tile_plan` instead of running every
    decode-width matmul on fixed 128-tiles; each jitted cell (prefill vs
    decode) compiles with its own plan because shapes are static under
    trace.  ``adaptive=False`` pins the construction-time ``(bm, bk, bn)``
    as fixed tile sizes (note the field *defaults* are now the 512
    adaptive caps — reproducing the pre-fusion backend exactly takes
    ``Backend(bm=128, bk=128, bn=128, adaptive=False, fused=False)``).

The photonic backend is *inference-only*: quantization rounding has no
useful gradient and the Pallas calls define no VJP.  Training cells keep
``execution="xla"`` (enforced by ``launch/dryrun.py``).

Selection: ``ModelConfig.execution`` ("xla" | "photonic"), overridable
per-call via the ``execution=`` kwarg on ``transformer.forward`` and the
serve-engine steps (A/B without rebuilding configs).  ``resolve`` accepts a
``Backend``, a name, a config, or None (-> XLA).

**Prepared banks** (DESIGN.md §Prepared weights): when a weight arrives as a
``core.prepared.PreparedTensor`` — the ``Program.build`` bank, quantized
once at build time — ``dot``/``reuse_dot`` route to ``dot_prepared``/
``reuse_dot_prepared``, which skip the in-step W8 derivation entirely.  The
prepared and in-step paths share one quantizer, so they are bit-identical.
"""
from __future__ import annotations

import dataclasses
from typing import Any

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core import obu
from repro.core.photonic import a8_scale_from_amax
from repro.obs import metrics as _metrics
from repro.sharding import partition as _partition
from repro.core.prepared import (BankLayer, PreparedTensor, quantize_weight,
                                 quantize_weight_t)
from repro.kernels import flash_attention as _fa
from repro.kernels import ops
from repro.kernels.platform import default_interpret
from repro.kernels.photonic_mvm import reads_stack_in_place, tile_plan

EXECUTIONS = ("xla", "photonic")

# How a row-parallel (K-split) matmul rejoins its partial sums:
#   * "reduce_scatter" — ``psum_scatter`` leaves each shard its own output
#     slice; the epilogue runs per-slice and the full output re-joins
#     LAZILY via the model-sharded out_spec (GSPMD places the all-gather at
#     the consumer, where it overlaps the next kernel).  Bitwise identical
#     to "psum": the same partial sums are added, only their placement
#     changes (gated in ``launch/shardcheck.py --collectives``).
#   * "psum"          — the legacy full all-reduce; epilogue post-psum.
#     Still the only row-parallel option when the output slices do not
#     divide or a blocked shuffle crosses them.  Kept as the bit-identity
#     comparator for the reduce-scatter path.
#   * "ring"          — explicit ``ppermute`` reduce-scatter: tp per-chunk
#     kernels interleaved with ring sends, so each hop's transfer overlaps
#     the next chunk's compute (the collective–compute pipeline spelled
#     out; same per-shard result as "reduce_scatter").
TP_COLLECTIVES = ("reduce_scatter", "psum", "ring")


def partition_rule(tp: int, K: int, N: int, *, block_perm=None,
                   tp_hint=None, collective: str = "reduce_scatter") -> str:
    """Resolve the tensor-parallel partition rule for a (K, N)-shaped
    matmul on a mesh with ``tp`` "model" shards.

    Returns one of:

      * ``"column"``     — output channels split; no reduction collective
        (the sharded output re-joins lazily downstream);
      * ``"scatter"``    — K split; partial kernels + ``psum_scatter``,
        per-slice epilogue, lazy gather;
      * ``"ring"``       — K split; explicit ppermute reduce-scatter;
      * ``"psum"``       — K split; full all-reduce, epilogue post-psum
        (the only row-parallel form when N % tp != 0 or a blocked shuffle
        must see the full channel axis);
      * ``"replicated"`` — neither dim divides: weight stays replicated.

    ``tp_hint="row"`` marks a pair-second matmul (w_down after the
    column-parallel up/gate, wo after the column-parallel qkv): forcing
    row-parallel lets it CONSUME the model-sharded intermediate its pair
    produced instead of all-gathering it at shard_map entry (the Megatron
    pairing).  The hint is advisory — it only applies when K divides.

    Pure and trace-free, so tests can enumerate the decision table without
    building meshes."""
    if tp <= 1:
        return "replicated"
    if collective not in TP_COLLECTIVES:
        raise ValueError(f"unknown tp_collective {collective!r}; "
                         f"have {TP_COLLECTIVES}")

    def row_rule():
        # scatter/ring need the output slices to divide and the epilogue
        # to be slice-local (a blocked shuffle crosses slices)
        if collective == "psum" or N % tp != 0 or block_perm is not None:
            return "psum"
        return "ring" if collective == "ring" else "scatter"

    row_ok = K % tp == 0
    if tp_hint == "row" and row_ok:
        return row_rule()
    if N % tp == 0 and block_perm is None:
        return "column"
    if row_ok:
        return row_rule()
    return "replicated"


def _mesh_dims(mesh):
    """(data_axes, dp, tp) of a (pod, data, model) / (data, model) mesh."""
    d_axes = _partition.data_axes(mesh)
    return d_axes, _partition.dp_size(mesh), int(mesh.shape.get("model", 1))


def _data_spec_entry(d_axes):
    return d_axes if len(d_axes) > 1 else (d_axes[0] if d_axes else None)


def _apply_activation(y, activation):
    if activation in (None, "none"):
        return y
    if activation == "relu":
        return jnp.maximum(y, 0.0)
    if activation == "silu":
        return jax.nn.silu(y)
    raise ValueError(f"unknown activation {activation!r}")


def _epilogue_unfused(y, bias, block_perm, block, activation):
    """The split blend epilogue: a second Pallas pass for blocked shuffles
    (`kernels/blend.py`), plain jnp for bias/activation-only epilogues.
    Either way it runs in f32 on the stored MVM output and rounds once to
    its dtype, as the fused kernel's ``_finalize`` does."""
    if block_perm is not None:
        b = (jnp.zeros((y.shape[-1],), y.dtype) if bias is None
             else bias.astype(y.dtype))
        return ops.blend_shuffle(y, b, block_perm, block=block,
                                 activation=activation or "none")
    out = y.astype(jnp.float32)
    if bias is not None:
        out = out + bias.astype(jnp.float32)
    return _apply_activation(out, activation).astype(y.dtype)


def _epilogue_xla(y, bias, block_perm, block, activation):
    """Reference epilogue on the xla backend (gather + jnp ops)."""
    if block_perm is not None:
        perm = np.asarray(block_perm)
        C = y.shape[-1]
        if block <= 0 or C % block != 0 or perm.shape[0] * block != C:
            raise ValueError(f"blocked shuffle needs C % block == 0 and a "
                             f"full permutation, got C={C} block={block}")
        idx = (perm[:, None] * block + np.arange(block)[None, :]).reshape(-1)
        y = jnp.take(y, jnp.asarray(idx), axis=-1)
    if bias is not None:
        y = y + bias.astype(y.dtype)
    return _apply_activation(y, activation)


@dataclasses.dataclass(frozen=True)
class Backend:
    """Static (hashable, trace-time) description of the matmul substrate.

    ``bm/bk/bn`` are the tile-plan *caps* under ``adaptive=True`` and the
    exact Pallas tile sizes under ``adaptive=False`` (the pre-fusion fixed
    plan).  ``fused`` selects the megakernel vs the split
    quantize/MVM/blend pipeline (photonic only; same math either way).
    """

    execution: str = "xla"
    bm: int = 128                     # row tile cap (exact when !adaptive)
    bk: int = 512                     # reduction tile cap
    bn: int = 512                     # output-column tile cap
    fused: bool = True                # megakernel vs split pipeline
    adaptive: bool = True             # shape-adaptive tile planning
    mesh: Any = None                  # jax.sharding.Mesh | None — when set
                                      # (and > 1 device) photonic matmuls run
                                      # under shard_map on it
    tp_collective: str = "reduce_scatter"
                                      # row-parallel rejoin strategy (see
                                      # TP_COLLECTIVES): "reduce_scatter"
                                      # (default), "psum" (legacy
                                      # comparator), "ring" (explicit
                                      # ppermute pipeline)
    noise: Any = None                 # core.noise.NoiseConfig | None — the
                                      # opt-in photonic fault model.  Being
                                      # a Backend field makes it a static
                                      # jit-cell key like mesh/tp_collective:
                                      # republishing drift ages retraces the
                                      # affected cells; None / all-zero is
                                      # bit-identical to the clean path.
    flash: bool = True                # route long-sequence attention through
                                      # the Pallas flash kernel (photonic
                                      # only; xla keeps the einsum/scan)
    flash_min_seq: int = 512          # query lengths below this take the
                                      # einsum/scan path — at short S the
                                      # blocked kernel's grid overhead loses
                                      # to one fused einsum

    def __post_init__(self):
        if self.execution not in EXECUTIONS:
            raise ValueError(f"unknown execution backend "
                             f"{self.execution!r}; have {EXECUTIONS}")
        if self.tp_collective not in TP_COLLECTIVES:
            raise ValueError(f"unknown tp_collective "
                             f"{self.tp_collective!r}; have {TP_COLLECTIVES}")
        if self.noise_active and self.mesh_active:
            # the fault model perturbs the full output-channel axis; under
            # shard_map each shard sees a slice and the per-tile PRNG streams
            # would diverge from the single-device pattern — model it
            # single-device first (Program.build's replace() re-runs this)
            raise NotImplementedError(
                "NoiseConfig injection is single-device only; drop the "
                "noise or the multi-device mesh")

    @property
    def is_photonic(self) -> bool:
        return self.execution == "photonic"

    @property
    def noise_active(self) -> bool:
        """True when the fault model actually perturbs: photonic execution
        AND an enabled config.  Always False on xla — the fault model is a
        property of the photonic substrate, not of the math."""
        return (self.is_photonic and self.noise is not None
                and self.noise.enabled)

    @property
    def mesh_active(self) -> bool:
        """True when matmuls must be explicitly partitioned: a mesh with
        more than one device.  A 1x1 mesh (``single_device_mesh``) takes the
        exact unsharded code path — bit-identical to ``mesh=None``."""
        return self.mesh is not None and self.mesh.size > 1

    # ---------------------------------------------------------- tile plans
    def tile_plan(self, M: int, K: int, N: int) -> tuple:
        """Resolve ``(bm, bk, bn)`` for an (M, K) x (K, N) matmul.  Shapes
        are static at trace time, so every jitted cell (prefill, decode,
        train) compiles with its own plan."""
        if not self.adaptive:
            return self.bm, self.bk, self.bn
        return tile_plan(M, K, N, cap_m=self.bm, cap_k=self.bk,
                         cap_n=self.bn)

    # ----------------------------------------------------------- attention
    def use_flash(self, q_len: int) -> bool:
        """Whether a q_len-row attention routes through the flash kernel.

        Photonic execution only (xla keeps the reference einsum/scan), and
        only at or above ``flash_min_seq`` query rows.  Under an active mesh
        the einsum path is kept too: GSPMD partitions it for free, while the
        Pallas kernel would need an explicit shard_map schedule."""
        return (self.is_photonic and self.flash and not self.mesh_active
                and q_len >= self.flash_min_seq)

    def attention(self, q, k, v, *, causal: bool = True, q_offset=None,
                  scale=None):
        """Sequence attention under the backend seam — the prefill analogue
        of ``dot``.

        q: (B, Sq, H, hd); k: (B, L, KV, hd); v: (B, L, KV, hd_v) with
        H % KV == 0 (GQA groups; MLA rides on hd_v != hd).  Returns
        (B, Sq, H * hd_v), heads flattened like ``_gqa_attend``.

        Long photonic sequences run the blocked Pallas flash kernel
        (``kernels/flash_attention.py`` — online softmax, Sq x L scores
        never materialized, ``interpret`` resolved from the platform like
        the MVM kernels); everything else takes the einsum/scan reference
        in ``models/attention.py``.  ``q_offset`` (python int or traced
        scalar) places query row i at absolute position q_offset + i so a
        chunked prefill against a partially filled KV cache masks exactly
        like the monolithic pass.  ``scale`` (a python float) multiplies
        the scores; None is 1/sqrt(hd).  Being a ``Backend`` method, the
        routing decision (``flash``/``flash_min_seq``) is part of the
        static jit-cell key like every other field."""
        B, Sq, H, _ = q.shape
        L, hd_v = k.shape[1], v.shape[-1]
        if self.use_flash(Sq):
            bq, bk_ = _fa.default_blocks(Sq, L, default_interpret())
            _metrics.record_kernel_call("flash_attn", bq, bk_, hd_v)
            with jax.named_scope(f"photonic.flash_attn.{bq}x{bk_}"):
                o = ops.flash_attention(q, k, v, causal=causal,
                                        q_offset=q_offset, scale=scale)
            return o.reshape(B, Sq, H * hd_v)
        from repro.models import attention as _attn   # lazy: models -> core
        return _attn.attend_seq_xla(q, k, v, causal=causal,
                                    q_offset=q_offset, scale=scale)

    # ------------------------------------------------------------- matmuls
    def dot(self, x, w, *, transpose: bool = False, bias=None,
            block_perm=None, block: int = 0, activation=None, tp_hint=None):
        """``x @ w`` (w: (k, n)) or ``x @ w.T`` (w: (n, k)) — the weight
        matmul primitive every layer routes through — plus an optional
        blend epilogue (bias + activation + blocked output shuffle) that
        the photonic megakernel folds into the matmul's ``_finalize``.

        ``w`` may be a raw fp array (quantized in-step on the photonic
        backend) or a ``PreparedTensor`` bank (quantized once at
        ``Program.build``).  ``tp_hint="row"`` marks a pair-second matmul
        for the sharded dispatch (see :func:`partition_rule`); it has no
        effect off-mesh."""
        if isinstance(w, (PreparedTensor, BankLayer)):
            return self.dot_prepared(x, w, transpose=transpose, bias=bias,
                                     block_perm=block_perm, block=block,
                                     activation=activation, tp_hint=tp_hint)
        if not self.is_photonic:
            y = obu.blend_dot(x, w, transpose=transpose)
            return _epilogue_xla(y, bias, block_perm, block, activation)
        if transpose:
            if w.shape[-1] != x.shape[-1]:
                raise ValueError(f"transpose blend needs square-compatible "
                                 f"dims, got x{x.shape} w{w.shape}")
            wq, wscale = quantize_weight_t(w)
        else:
            wq, wscale = quantize_weight(w)
        return self._photonic_matmul(x, wq, wscale, transpose=transpose,
                                     bias=bias, block_perm=block_perm,
                                     block=block, activation=activation,
                                     tp_hint=tp_hint, bank_tag=None)

    def dot_prepared(self, x, prep, *, transpose: bool = False, bias=None,
                     block_perm=None, block: int = 0, activation=None,
                     tp_hint=None):
        """``dot`` against an already-programmed bank (a ``PreparedTensor``
        or a ``BankLayer`` of a stacked one): no in-step weight
        quantization.  The transposed orientation uses the bank's per-row
        image (``wq_t``/``scale_t``) — the same array the optical transpose
        illuminates from the orthogonal port.  The fused single-device
        kernel reads a ``BankLayer``'s tiles in place in its stack; every
        other path takes the layer's slice."""
        if not self.is_photonic:
            # xla fallback: dequantize the programmed image (W8 numerics
            # preserved) and run the dot_general path.  Only hit when an
            # xla Backend is pointed at a photonic-prepared bank.
            if transpose:
                w = (prep.wq_t.astype(jnp.float32)
                     * (prep.scale_t / 127.0)[..., :, None]).astype(x.dtype)
            else:
                w = (prep.wq.astype(jnp.float32)
                     * (prep.scale / 127.0)[..., None, :]).astype(x.dtype)
            y = obu.blend_dot(x, w, transpose=transpose)
            return _epilogue_xla(y, bias, block_perm, block, activation)
        if transpose and prep.shape[-1] != x.shape[-1]:
            raise ValueError(f"transpose blend needs square-compatible "
                             f"dims, got x{x.shape} w{prep.shape}")
        layer = None
        if (isinstance(prep, BankLayer) and self.fused
                and not self.mesh_active and not self.noise_active):
            layer, prep = prep.index, prep.stack
        wq, wscale = ((prep.wq_t, prep.scale_t) if transpose
                      else (prep.wq, prep.scale))
        return self._photonic_matmul(x, wq, wscale, transpose=transpose,
                                     bias=bias, block_perm=block_perm,
                                     block=block, activation=activation,
                                     tp_hint=tp_hint, bank_tag=prep.tag,
                                     layer=layer)

    def _photonic_matmul(self, x, wq, wscale, *, transpose, bias,
                         block_perm, block, activation, tp_hint=None,
                         bank_tag=None, layer=None):
        """Shared photonic dispatch: resolve the tile plan from the actual
        operand shapes, then run either the fused megakernel or the split
        quantize -> MVM -> blend pipeline at that same plan.  With
        ``layer`` (fused, single device, noise off), ``wq``/``wscale`` are a
        stacked bank that the kernel reads layer ``layer`` of in place.

        With an enabled fault model (``self.noise``), the call reroutes to
        the noisy split pipeline — bit-exact MVM, ``core/noise.py``
        perturbation on the raw output, then the unfused epilogue.
        ``bank_tag`` (the PreparedTensor's stable path hash; None for
        in-step-quantized raw weights) keys the bank's PRNG streams and
        selects its per-bank drift age."""
        if self.mesh_active:
            return self._photonic_matmul_sharded(
                x, wq, wscale, transpose=transpose, bias=bias,
                block_perm=block_perm, block=block, activation=activation,
                tp_hint=tp_hint)
        M = 1
        for d in x.shape[:-1]:
            M *= d
        K = x.shape[-1]
        N = wq.shape[-2] if transpose else wq.shape[-1]
        bm, bk, bn = self.tile_plan(M, K, N)
        if self.noise_active:
            _metrics.record_kernel_call("noisy", bm, bk, bn)
            with jax.named_scope(f"photonic.noisy.{bm}x{bk}x{bn}"):
                y = ops.photonic_matmul_noisy(
                    x, wq, wscale, noise=self.noise, bank_tag=bank_tag,
                    transpose=transpose, bm=bm, bk=bk, bn=bn)
                return _epilogue_unfused(y, bias, block_perm, block,
                                         activation)
        # trace-time kernel-call ledger: dispatch runs under jit trace, so
        # this counts the Pallas calls compiled into each cell, once per
        # (re)trace, keyed by the resolved tile plan
        kind = "fused" if self.fused else "split"
        if layer is not None and reads_stack_in_place(
                K, N, bk, bn, block if block_perm is not None else 0):
            kind = "fused_stacked"
        _metrics.record_kernel_call(kind, bm, bk, bn)
        with jax.named_scope(f"photonic.{kind}.{bm}x{bk}x{bn}"):
            if self.fused:
                return ops.photonic_matmul_fused(
                    x, wq, wscale, transpose=transpose, bias=bias,
                    block_perm=block_perm, block=block,
                    activation=activation or "none", bm=bm, bk=bk, bn=bn,
                    layer=layer)
            mm = (ops.photonic_matmul_prepared_t if transpose
                  else ops.photonic_matmul_prepared)
            y = mm(x, wq, wscale, bm=bm, bk=bk, bn=bn)
            return _epilogue_unfused(y, bias, block_perm, block, activation)

    def _photonic_matmul_sharded(self, x, wq, wscale, *, transpose, bias,
                                 block_perm, block, activation,
                                 tp_hint=None):
        """The Pallas MVM under ``shard_map`` on ``self.mesh``.

        XLA cannot auto-partition a ``pallas_call``, so on a real mesh every
        photonic matmul is explicitly mapped: rows (the leading batch dim)
        split over the data axes, and the weight splits over "model" by the
        :func:`partition_rule` its shape (and the caller's ``tp_hint``)
        admits —

          * ``"column"``: each shard runs the kernel — fused epilogue and
            all — on its slice of the output channels, scales and bias
            sharded alongside; no reduction collective.
          * ``"scatter"`` (row-parallel, the default rejoin): each shard
            computes a partial MVM over its K-slice (the offset row splits
            with it), a ``psum_scatter`` leaves it exactly its own output
            slice — tp× less reduction traffic than the old full psum —
            and the bias/activation epilogue runs on that 1/tp-wide slice.
          * ``"ring"``: the same reduce-scatter spelled out as tp per-chunk
            kernels interleaved with ``ppermute`` hops, so every transfer
            overlaps the next chunk's compute.
          * ``"psum"``: the legacy full all-reduce — still required when
            the output slices don't divide or a blocked shuffle crosses
            them, and kept as the bit-identity comparator
            (``tp_collective="psum"``).
          * ``"replicated"``: neither dim divides; only rows shard.

        For every rule with a model-sharded result (column, scatter, ring)
        the out_spec leaves the output sharded: GSPMD materializes the
        all-gather lazily at the consumer — or never, when the consumer is
        the pair-second row-parallel matmul (``tp_hint="row"``) whose
        x_spec wants exactly these slices — which is what overlaps the
        gather with the next layer's kernel.

        The per-tensor A8 scale is rebuilt IN-body: a local abs-max plus a
        ``pmax`` over the axes the activation is actually split on.  Max
        commutes with sharding, so the grid is bitwise identical to the
        single-device scale while skipping the old outside-shard_map global
        reduction pass."""
        mesh = self.mesh
        d_axes, dp, tp = _mesh_dims(mesh)
        dd = _data_spec_entry(d_axes)
        K = x.shape[-1]
        N = wq.shape[-2] if transpose else wq.shape[-1]
        row_shard = dp > 1 and x.ndim >= 2 and x.shape[0] % dp == 0
        rule = partition_rule(tp, K, N, block_perm=block_perm,
                              tp_hint=tp_hint,
                              collective=self.tp_collective)
        col_tp = rule == "column"
        red_tp = rule in ("scatter", "ring", "psum")
        out_sharded = rule in ("column", "scatter", "ring")
        bspec = dd if row_shard else None
        mid = (None,) * (x.ndim - 2)
        x_spec = P(bspec, *mid, "model" if red_tp else None)
        if transpose:                             # wq: (N, K)
            w_spec = P("model" if col_tp else None,
                       "model" if red_tp else None)
        else:                                     # wq: (K, N)
            w_spec = P("model" if red_tp else None,
                       "model" if col_tp else None)
        ws_spec = P("model" if col_tp else None)
        out_spec = P(bspec, *mid, "model" if out_sharded else None)
        in_specs = [x_spec, w_spec, ws_spec]
        operands = [x, wq, wscale]
        has_bias = bias is not None
        if has_bias:
            # column/scatter/ring epilogues see one output slice each —
            # the bias shards with it; psum/replicated see the full axis
            in_specs.append(P("model" if out_sharded else None))
            operands.append(bias)
        # axes the local activation block is split over: pmax over exactly
        # these rebuilds the global abs-max for the A8 scale
        amax_axes = (tuple(d_axes) if row_shard else ()) + (
            ("model",) if red_tp else ())
        fused, plan = self.fused, self.tile_plan
        chunk = N // tp if N % tp == 0 else N
        # record the per-shard plan in the OUTER trace (the shard_map body
        # may be re-traced internally; the local shapes are deterministic)
        M = 1
        for d in x.shape[:-1]:
            M *= d
        _metrics.record_kernel_call(
            "sharded_fused" if fused else "sharded_split",
            *plan(M // dp if row_shard else M,
                  K // tp if red_tp else K,
                  chunk if rule in ("column", "ring") else N))

        def body(xl, wl, wsl, *rest):
            bl = rest[0] if has_bias else None
            Ml = 1
            for d in xl.shape[:-1]:
                Ml *= d
            Kl = xl.shape[-1]
            amax = jnp.max(jnp.abs(xl))
            if amax_axes:
                amax = jax.lax.pmax(amax, amax_axes)
            xsl = a8_scale_from_amax(amax)

            def kernel(wql, wssl, n_cols, epilogue):
                """One per-shard Pallas call on ``n_cols`` output columns;
                ``epilogue=False`` leaves the raw (partial) MVM, in f32, for
                the reduction collective to finish: partials rounded to a
                bf16 activation dtype before the sum would lose what the
                single-device kernel's f32 accumulator keeps."""
                bm, bk, bn = plan(Ml, Kl, n_cols)
                out_dtype = None if epilogue else jnp.float32
                if fused:
                    return ops.photonic_matmul_fused(
                        xl, wql, wssl, x_scale=xsl, transpose=transpose,
                        bias=bl if epilogue else None,
                        block_perm=block_perm if epilogue else None,
                        block=block,
                        activation=(activation or "none") if epilogue
                        else "none", bm=bm, bk=bk, bn=bn,
                        out_dtype=out_dtype)
                mm = (ops.photonic_matmul_prepared_t if transpose
                      else ops.photonic_matmul_prepared)
                y = mm(xl, wql, wssl, bm=bm, bk=bk, bn=bn, x_scale=xsl,
                       out_dtype=out_dtype)
                if epilogue:
                    y = _epilogue_unfused(y, bl, block_perm, block,
                                          activation)
                return y

            if rule == "scatter":
                y = kernel(wl, wsl, N, epilogue=False)
                y = jax.lax.psum_scatter(y, "model",
                                         scatter_dimension=y.ndim - 1,
                                         tiled=True)
                # slice-local epilogue: bl is already this shard's slice
                return _epilogue_unfused(y.astype(xl.dtype), bl, None, 0,
                                         activation)
            if rule == "ring":
                me = jax.lax.axis_index("model")
                ring = [(i, (i + 1) % tp) for i in range(tp)]

                def part(idx):
                    # partial for output chunk ``idx`` on this K-slice
                    w_ax = 0 if transpose else 1
                    wc = jax.lax.dynamic_slice_in_dim(
                        wl, idx * chunk, chunk, w_ax)
                    wsc = jax.lax.dynamic_slice_in_dim(
                        wsl, idx * chunk, chunk, wsl.ndim - 1)
                    return kernel(wc, wsc, chunk, epilogue=False)

                # start on the chunk owned by the downstream neighbor, send
                # while computing the next: after tp-1 hops shard m holds
                # the fully reduced chunk m
                acc = part((me + tp - 1) % tp)
                for s in range(1, tp):
                    acc = jax.lax.ppermute(acc, "model", perm=ring)
                    acc = acc + part((me + tp - 1 - s) % tp)
                return _epilogue_unfused(acc.astype(xl.dtype), bl, None, 0,
                                         activation)
            if rule == "psum":
                y = kernel(wl, wsl, N, epilogue=False)
                y = jax.lax.psum(y, "model")
                return _epilogue_unfused(y.astype(xl.dtype), bl, block_perm,
                                         block, activation)
            # column / replicated: the kernel's own fused epilogue
            Nl = wl.shape[-2] if transpose else wl.shape[-1]
            return kernel(wl, wsl, Nl, epilogue=True)

        with jax.named_scope(f"photonic.sharded.{rule}"):
            return jax.shard_map(body, mesh=mesh, in_specs=tuple(in_specs),
                                 out_specs=out_spec,
                                 check_vma=False)(*operands)

    def reuse_dot(self, x_stack, w):
        """T independent activation streams through ONE weight: x_stack
        (T, ..., k) @ w (k, n).  Photonic: the weight is programmed once and
        stays VMEM-resident while the T streams pass (the write-once /
        reuse-T-times schedule as a kernel)."""
        if isinstance(w, (PreparedTensor, BankLayer)):
            return self.reuse_dot_prepared(x_stack, w)
        if not self.is_photonic:
            return obu.blend_dot(x_stack, w, transpose=False)
        if self.mesh_active:
            wq, wscale = quantize_weight(w)
            return self._reuse_dot_sharded(x_stack, wq, wscale)
        bm, bk, bn = self.tile_plan(
            int(np.prod(x_stack.shape[1:-1])), x_stack.shape[-1],
            w.shape[-1])
        _metrics.record_kernel_call("reuse", bm, bk, bn)
        with jax.named_scope(f"photonic.reuse.{bm}x{bn}"):
            y = ops.reuse_resident_matmul(x_stack, w, bm=bm, bn=bn)
            return self._perturb_reuse(y, bank_tag=None)

    def reuse_dot_prepared(self, x_stack, prep):
        """Reuse-resident matmul against a programmed bank (the fully
        write-once form: neither the weight fetch nor its quantization
        repeats across the T streams)."""
        if not self.is_photonic:
            w = (prep.wq.astype(jnp.float32)
                 * (prep.scale / 127.0)[..., None, :]).astype(x_stack.dtype)
            return obu.blend_dot(x_stack, w, transpose=False)
        if self.mesh_active:
            return self._reuse_dot_sharded(x_stack, prep.wq, prep.scale)
        bm, bk, bn = self.tile_plan(
            int(np.prod(x_stack.shape[1:-1])), x_stack.shape[-1],
            prep.shape[-1])
        _metrics.record_kernel_call("reuse", bm, bk, bn)
        with jax.named_scope(f"photonic.reuse.{bm}x{bn}"):
            y = ops.reuse_resident_matmul_prepared(
                x_stack, prep.wq, prep.scale, bm=bm, bn=bn)
            return self._perturb_reuse(y, bank_tag=prep.tag)

    def _perturb_reuse(self, y, *, bank_tag):
        """Fault-model hook for the reuse-resident paths: one programmed
        bank serves all T streams, so one perturbation pattern (keyed by the
        bank tag) applies across the whole stack — physically, every stream
        passes the SAME drifted rings.  No-op when noise is disabled."""
        if not self.noise_active:
            return y
        from repro.core import noise as _noise
        return _noise.perturb_mvm_output(y, self.noise, tag=bank_tag,
                                         transpose=False)

    def _reuse_dot_sharded(self, x_stack, wq, wscale):
        """Reuse-resident kernel under shard_map: the programmed bank splits
        column-parallel over "model" when the output channels divide (each
        shard keeps its slice VMEM-resident for all T streams); otherwise it
        stays replicated.  The T activation streams are never split — the
        whole point of the resident schedule is every stream passing the
        same programmed tile."""
        mesh = self.mesh
        _, _, tp = _mesh_dims(mesh)
        N = wq.shape[-1]
        col_tp = tp > 1 and N % tp == 0
        nspec = "model" if col_tp else None
        mid = (None,) * (x_stack.ndim - 1)
        plan = self.tile_plan

        def body(xl, wl, wsl):
            bm, _, bn = plan(int(np.prod(xl.shape[1:-1])), xl.shape[-1],
                             wl.shape[-1])
            return ops.reuse_resident_matmul_prepared(xl, wl, wsl,
                                                      bm=bm, bn=bn)

        _metrics.record_kernel_call(
            "sharded_reuse", *plan(int(np.prod(x_stack.shape[1:-1])),
                                   x_stack.shape[-1],
                                   N // tp if col_tp else N))
        with jax.named_scope("photonic.sharded_reuse"):
            return jax.shard_map(
                body, mesh=mesh,
                in_specs=(P(*mid, None), P(None, nspec), P(nspec)),
                out_specs=P(*mid, nspec),
                check_vma=False)(x_stack, wq, wscale)

    # -------------------------------------------------------------- shuffle
    def shuffle(self, h, perm, block_perm=None, block: int = 0):
        """OBU electronic shuffle of the channel axis.

        Photonic + blocked permutation: realized by the blend kernel's
        index-map epilogue (`kernels/blend.py` — the shuffle IS the grid
        index remapping, zero extra HBM passes).  Otherwise (group-shuffle
        flavor, or xla backend) the static constant-index gather."""
        if self.is_photonic and block_perm is not None and block > 0:
            bias = jnp.zeros((h.shape[-1],), h.dtype)
            if self.mesh_active:
                # the blend kernel permutes the FULL channel axis — keep it
                # replicated and split only the rows over the data axes
                mesh = self.mesh
                d_axes, dp, _ = _mesh_dims(mesh)
                row_ok = dp > 1 and h.ndim >= 2 and h.shape[0] % dp == 0
                bspec = _data_spec_entry(d_axes) if row_ok else None
                hs = P(bspec, *(None,) * (h.ndim - 1))
                return jax.shard_map(
                    lambda hl, bl: ops.blend_shuffle(
                        hl, bl, block_perm, block=block, activation="none"),
                    mesh=mesh, in_specs=(hs, P(None)), out_specs=hs,
                    check_vma=False)(h, bias)
            with jax.named_scope("photonic.blend_shuffle"):
                return ops.blend_shuffle(h, bias, block_perm, block=block,
                                         activation="none")
        return obu.apply_channel_permutation(h, perm)


XLA = Backend("xla")
PHOTONIC = Backend("photonic")


def resolve(spec=None) -> Backend:
    """Backend from a Backend | name | config-with-.execution | None."""
    if spec is None:
        return XLA
    if isinstance(spec, Backend):
        return spec
    if isinstance(spec, str):
        return PHOTONIC if spec == "photonic" else Backend(spec)
    return resolve(getattr(spec, "execution", None))
