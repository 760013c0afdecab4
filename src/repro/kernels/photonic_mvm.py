"""Photonic MVM Pallas kernel — the paper's compute path, TPU-native.

Implements W8A8 matmul with the **offset-matrix negative-weight
decomposition** (paper eq. 6) inside the kernel:

    y = 2 * (x_f @ W'  -  0.5 * sum_k x_f)  * w_scale * x_scale
    W' = W_q / (2*qmax) + 0.5                (MRR transmission domain [0, 1])

The crossbar tile of the paper (8x8, crosstalk-limited) becomes an MXU-aligned
(bm, bk, bn) VMEM block (DESIGN.md §2): one grid step "programs" a weight tile
into VMEM and streams an activation block through it; the rank-1 offset row
(``0.5 * sum(x)``) is tracked in a second fp32 scratch accumulator, exactly
like the hardware's shared 1xN W0 crossbar row.

Grid: (M/bm, N/bn, K/bk), K innermost; fp32 accumulation in VMEM scratch.

Two generations of kernels live here:

  * the split family (``photonic_mvm`` / ``_t`` / ``_resident``) — consume
    already-quantized int8 activations; quantization, padding, and any blend
    epilogue are separate XLA/Pallas passes around the call;
  * ``photonic_mvm_fused`` — the decode-path **megakernel**: fp activations
    stream in and are A8-quantized in the kernel prologue (the per-tensor
    scale arrives as a tiny scalar input — the only pre-pass left is an
    abs-max reduction), the weight tile may be either OBU orientation
    (in-register swap), and the blend epilogue (bias + activation + blocked
    output permutation) folds into ``_finalize``: the *output* BlockSpec's
    scalar-prefetched index map writes computed column block ``j`` to its
    shuffled position, exactly the trick ``kernels/blend.py`` plays on its
    input side.  MVM + quantize + blend = one ``pallas_call``, zero
    intermediate HBM traffic.

Tile sizes come from :func:`tile_plan` (shape-adaptive: decode-width row
counts round to 8, reduction/column tiles grow to cover small d_model in one
grid step) rather than hard-coded 128s.

**Fault-model boundary** (DESIGN.md §Noise & calibration): these kernels are
and stay BIT-EXACT — the ideal crossbar.  The hardware-honest error sources
(per-tile gain error, write-age drift, crosstalk, DAC/TIA noise) live in
``core/noise.py`` and perturb the *raw MVM output* — after the offset
recompose and TIA rescale, before the electronic blend epilogue — via
``kernels/ops.photonic_matmul_noisy``.  No kernel variant per error source,
and the clean paths keep their bit-identity gates.

**SPMD contract** (DESIGN.md §Sharded execution): every kernel here is
rank-LOCAL — it sees one shard's operands and knows nothing about the mesh.
XLA cannot auto-partition a ``pallas_call``, so on a >1-device mesh
``core/backend.py`` wraps these calls in ``shard_map`` with the collective
chosen by :func:`repro.core.backend.partition_rule`:

  * column-parallel — no collective; the output stays model-sharded and the
    all-gather is *deferred* to whatever consumes it (GSPMD places it at the
    consumer, overlapping it with unrelated compute — or elides it entirely
    when the consumer is a ``tp_hint="row"`` pair-second matmul);
  * row-parallel, default ``tp_collective="reduce_scatter"`` — the kernel
    produces the full-N partial and ``psum_scatter`` reduces each output
    slice onto its owner shard; the bias/activation epilogue then runs on
    the 1/tp-wide slice.  Bitwise identical to the legacy ``psum`` (same
    adds, different placement);
  * row-parallel, ``tp_collective="ring"`` — tp chunk-kernel calls
    interleaved with ``ppermute`` hops so each hop's transfer overlaps the
    next chunk's matmul.  The chunk kernel re-associates XLA's elementwise
    fusion, so ring is fp-noise-equivalent (~1 ulp), not bitwise;
  * row-parallel ``psum`` — legacy comparator, and the fallback whenever
    ``N % tp != 0`` or a blocked output shuffle needs the full row.

The collectives are valid because the offset row and the per-column TIA
scales both commute with the K-sum; :func:`tile_plan` resolves on the LOCAL
shapes inside the mapped body.  The one piece of global state a shard needs
is the per-tensor A8 scale, rebuilt *inside* the body from the local abs-max
plus ``jax.lax.pmax`` over the sharded axes (max commutes with sharding, so
every shard quantizes on exactly the single-device grid — see
``photonic.a8_scale_from_amax``).
"""
from __future__ import annotations

import functools
import math

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.platform import default_interpret


def _kernel(xq_ref, wq_ref, xs_ref, ws_ref, o_ref, acc_ref, xsum_ref, *,
            nk: int, qmax: float):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        xsum_ref[...] = jnp.zeros_like(xsum_ref)

    xf = xq_ref[...].astype(jnp.float32)                 # A8 block
    w_prime = wq_ref[...].astype(jnp.float32) / (2.0 * qmax) + 0.5
    acc_ref[...] += jnp.dot(xf, w_prime,
                            preferred_element_type=jnp.float32)
    xsum_ref[...] += jnp.sum(xf, axis=1, keepdims=True)  # offset row W0

    @pl.when(k == nk - 1)
    def _finalize():
        y = 2.0 * (acc_ref[...] - 0.5 * xsum_ref[...])   # BPD subtraction
        scale = xs_ref[0, 0] * ws_ref[...]               # TIA gain
        o_ref[...] = (y * scale).astype(o_ref.dtype)


def _pad_to(x, mult, axis):
    pad = (-x.shape[axis]) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def _kernel_t(xq_ref, wq_ref, xs_ref, ws_ref, o_ref, acc_ref, xsum_ref, *,
              nk: int, qmax: float):
    """Pre-swapped variant: the weight arrives as (N, K) row-major and each
    (bn, bk) tile is swapped in-register — the OBU optical transpose without
    ever materializing ``w.T`` in HBM."""
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        xsum_ref[...] = jnp.zeros_like(xsum_ref)

    xf = xq_ref[...].astype(jnp.float32)
    w_prime = wq_ref[...].astype(jnp.float32).T / (2.0 * qmax) + 0.5
    acc_ref[...] += jnp.dot(xf, w_prime,
                            preferred_element_type=jnp.float32)
    xsum_ref[...] += jnp.sum(xf, axis=1, keepdims=True)

    @pl.when(k == nk - 1)
    def _finalize():
        y = 2.0 * (acc_ref[...] - 0.5 * xsum_ref[...])
        scale = xs_ref[0, 0] * ws_ref[...]
        o_ref[...] = (y * scale).astype(o_ref.dtype)


def _kernel_resident(xq_ref, wq_ref, xs_ref, ws_ref, o_ref, *, qmax: float):
    """Reuse-resident step: the full (K, bn) weight tile is already in VMEM
    (its index map ignores the streaming grid dims) — each step only streams
    one activation row-block through it.  ``xs_ref`` is the whole (T,)
    per-step scale vector in SMEM, read at this step's reuse index."""
    xf = xq_ref[0].astype(jnp.float32)                   # (bm, K)
    w_prime = wq_ref[...].astype(jnp.float32) / (2.0 * qmax) + 0.5
    y = jnp.dot(xf, w_prime, preferred_element_type=jnp.float32)
    y = 2.0 * (y - 0.5 * jnp.sum(xf, axis=1, keepdims=True))
    xs = xs_ref[pl.program_id(1)]
    o_ref[0] = (y * xs * ws_ref[...]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bm", "bk", "bn", "qmax",
                                             "interpret", "out_dtype"))
def photonic_mvm(xq, wq, x_scale, w_scale, *, bm=128, bk=128, bn=128,
                 qmax=127.0, interpret=None, out_dtype=jnp.float32):
    """xq: (M, K) int8; wq: (K, N) int8 (symmetric, per-column scale);
    x_scale: scalar; w_scale: (N,).  Returns (M, N) ``out_dtype``.
    ``interpret=None`` resolves from the platform (off only on a TPU)."""
    if interpret is None:
        interpret = default_interpret()
    M, K = xq.shape
    K2, N = wq.shape
    assert K == K2
    xq_p = _pad_to(_pad_to(xq, bm, 0), bk, 1)
    wq_p = _pad_to(_pad_to(wq, bk, 0), bn, 1)
    ws_p = _pad_to(w_scale.reshape(1, N), bn, 1)
    Mp, Kp = xq_p.shape
    Np = wq_p.shape[1]
    grid = (Mp // bm, Np // bn, Kp // bk)
    out = pl.pallas_call(
        functools.partial(_kernel, nk=grid[2], qmax=qmax),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
            pl.BlockSpec((1, 1), lambda i, j, k: (0, 0)),
            pl.BlockSpec((1, bn), lambda i, j, k: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((Mp, Np), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32),
                        pltpu.VMEM((bm, 1), jnp.float32)],
        interpret=interpret,
    )(xq_p, wq_p, jnp.reshape(x_scale, (1, 1)).astype(jnp.float32),
      ws_p.astype(jnp.float32))
    return out[:M, :N]


@functools.partial(jax.jit, static_argnames=("bm", "bk", "bn", "qmax",
                                             "interpret", "out_dtype"))
def photonic_mvm_t(xq, wq, x_scale, w_scale, *, bm=128, bk=128, bn=128,
                   qmax=127.0, interpret=None, out_dtype=jnp.float32):
    """``xq @ wq.T`` for xq: (M, K) int8 and wq: (N, K) int8 (symmetric,
    per-ROW scale — the output channel of the transposed use); x_scale:
    scalar; w_scale: (N,).  Returns (M, N).

    The transpose is realized as a *pre-swapped kernel variant*: the weight
    BlockSpec walks (N, K) tiles and ``_kernel_t`` swaps each (bn, bk) tile
    in-register — light entering the crossbar on the orthogonal port, never
    a materialized ``w.T``."""
    if interpret is None:
        interpret = default_interpret()
    M, K = xq.shape
    N, K2 = wq.shape
    assert K == K2
    xq_p = _pad_to(_pad_to(xq, bm, 0), bk, 1)
    wq_p = _pad_to(_pad_to(wq, bn, 0), bk, 1)
    ws_p = _pad_to(w_scale.reshape(1, N), bn, 1)
    Mp, Kp = xq_p.shape
    Np = wq_p.shape[0]
    grid = (Mp // bm, Np // bn, Kp // bk)
    out = pl.pallas_call(
        functools.partial(_kernel_t, nk=grid[2], qmax=qmax),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bn, bk), lambda i, j, k: (j, k)),
            pl.BlockSpec((1, 1), lambda i, j, k: (0, 0)),
            pl.BlockSpec((1, bn), lambda i, j, k: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((Mp, Np), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32),
                        pltpu.VMEM((bm, 1), jnp.float32)],
        interpret=interpret,
    )(xq_p, wq_p, jnp.reshape(x_scale, (1, 1)).astype(jnp.float32),
      ws_p.astype(jnp.float32))
    return out[:M, :N]


@functools.partial(jax.jit, static_argnames=("bm", "bn", "qmax",
                                             "interpret", "out_dtype"))
def photonic_mvm_resident(xq, wq, x_scale, w_scale, *, bm=128, bn=128,
                          qmax=127.0, interpret=None, out_dtype=jnp.float32):
    """Reuse-resident MVM: xq: (T, M, K) int8 — T reuse steps' activations
    streamed through ONE programmed weight; wq: (K, N) int8; x_scale: (T,)
    per-step A8 scales; w_scale: (N,).  Returns (T, M, N).

    Weight-stationary schedule (the TPU analog of programming the MRR bank
    once per calibration interval, paper §3.1): grid = (N/bn, T, M/bm) with
    the weight index map *independent of (t, i)* — the full-depth (K, bn) W8
    tile is fetched into VMEM once per output column block and every one of
    the T*M/bm activation row blocks streams through it; no per-reuse
    re-fetch.  The reduction depth K must fit one VMEM tile (no K grid dim),
    which holds for every d_model/d_ff in the paper models at TPU VMEM
    sizes; the offset row is recomputed per row-block (rank-1, free)."""
    if interpret is None:
        interpret = default_interpret()
    T, M, K = xq.shape
    K2, N = wq.shape
    assert K == K2
    xq_p = _pad_to(xq, bm, 1)
    wq_p = _pad_to(wq, bn, 1)
    ws_p = _pad_to(w_scale.reshape(1, N), bn, 1)
    Tq, Mp, Kp = xq_p.shape
    Np = wq_p.shape[1]
    grid = (Np // bn, T, Mp // bm)
    out = pl.pallas_call(
        functools.partial(_kernel_resident, qmax=qmax),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bm, Kp), lambda j, t, i: (t, i, 0)),
            # weight index map ignores (t, i): programmed once, reused T*M/bm
            # times — write-once / reuse-T-times in BlockSpec form.
            pl.BlockSpec((Kp, bn), lambda j, t, i: (0, j)),
            # a (1, 1) block of a (T, 1) array breaks the (8, 128) tiling
            # rule: the T scalars ride whole in SMEM instead
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, bn), lambda j, t, i: (0, j)),
        ],
        out_specs=pl.BlockSpec((1, bm, bn), lambda j, t, i: (t, i, j)),
        out_shape=jax.ShapeDtypeStruct((T, Mp, Np), out_dtype),
        interpret=interpret,
    )(xq_p, wq_p, jnp.reshape(x_scale, (T,)).astype(jnp.float32),
      ws_p.astype(jnp.float32))
    return out[:, :M, :N]


# =========================================================================
# shape-adaptive tile planning
# =========================================================================
def round_up(v: int, mult: int) -> int:
    """Smallest multiple of ``mult`` >= v (>= one mult for v <= 0)."""
    return -(-max(v, 1) // mult) * mult


def _fit_dim(d: int, unit: int, cap: int) -> int:
    """Tile size for a length-``d`` axis: the whole (unit-rounded) axis when
    it fits under ``cap`` — one grid step, zero padding — else the largest
    multiple of ``unit`` <= cap that divides the rounded axis (no padding),
    falling back to ``unit``."""
    if cap < unit:
        return cap                       # caller pinned a sub-unit tile
    du = round_up(d, unit)
    if du <= cap:
        return du
    for t in range(cap - cap % unit, unit - 1, -unit):
        if du % t == 0:
            return t
    return unit


def _fit_rows(M: int, cap: int) -> int:
    """Row tile for an M-row matmul, covering both serving and prefill
    widths.  Serving widths (M <= cap) round to the 8-row sublane and run
    one grid step (a B=2 decode step runs an 8-row tile, not a 128-row
    one).  Prefill widths (M = B*S >> cap) prefer the largest multiple of
    8 <= cap that divides the rounded row count — zero padded rows across
    hundreds of grid steps — but never shrink below cap/2: a ragged
    prefill keeps full tiles plus one padded step instead of degrading
    every step to a sliver."""
    mu = round_up(M, 8)
    if mu <= cap:
        return mu
    lo = max(8, cap // 2)
    for t in range(cap - cap % 8, lo - 1, -8):
        if mu % t == 0:
            return t
    return cap


def tile_plan(M: int, K: int, N: int, *, cap_m: int = 128, cap_k: int = 512,
              cap_n: int = 512) -> tuple:
    """Derive ``(bm, bk, bn)`` from actual operand shapes.

    The serving-width rule of DESIGN.md §Fused decode path: ``bm`` resolves
    via :func:`_fit_rows` — sublane-rounded single step at decode widths,
    divisor-preferring full tiles at prefill widths (M = B*S) — and caps at
    ``cap_m``; ``bk``/``bn`` keep the 128 lane unit but grow to swallow a
    whole d_model/d_ff axis in one grid step when it fits the cap, which
    both feeds the MXU longer per weight fetch and eliminates the
    pad/slice HBM round-trip for already-aligned shapes.  ``bm`` choices
    never change numerics (fp32 accumulation order is a ``bk`` property),
    so the fused-vs-split bit-identity gates hold at any row plan."""
    return (_fit_rows(M, cap_m),
            _fit_dim(K, 128, cap_k),
            _fit_dim(N, 128, cap_n))


# =========================================================================
# fused decode-path megakernel
# =========================================================================
ACTIVATIONS = ("none", "relu", "silu")


def _act(y, activation: str):
    # same op set (and the same expressions) as kernels/blend.py: these stay
    # bit-identical whether they run in the blend kernel or this epilogue
    if activation == "relu":
        return jnp.maximum(y, 0.0)
    if activation == "silu":
        return y * jax.nn.sigmoid(y)
    if activation != "none":
        raise ValueError(f"unsupported fused activation {activation!r}; "
                         f"have {ACTIVATIONS}")
    return y


def _kernel_fused(oidx_ref, x_ref, wq_ref, xs_ref, ws_ref, *rest, nk: int,
                  qmax: float, transpose_w: bool, activation: str,
                  has_bias: bool):
    """Quantize-in-prologue, blend-in-epilogue MVM step.

    ``x_ref`` holds *floating* activations; the A8 grid (round / clip at the
    prefetched per-tensor scale) is applied in-register, bit-identically to
    ``core.photonic.quantize_symmetric`` with the same scale.  The epilogue
    runs on the output tile after the TIA rescale + output-dtype cast — the
    exact op order of the standalone blend kernel, so the activation /
    blocked-shuffle epilogues match separate execution bit-for-bit (bias:
    see the fma note in ``_finalize``).  ``oidx_ref`` is consumed by the
    BlockSpec index maps (output + bias), not the body."""
    if has_bias:
        b_ref, o_ref, acc_ref, xsum_ref = rest
    else:
        b_ref = None
        o_ref, acc_ref, xsum_ref = rest
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        xsum_ref[...] = jnp.zeros_like(xsum_ref)

    # in-kernel A8: divide and round in f32 (the v5e VPU has no bf16
    # arithmetic), the quotient rounded to the activation dtype in between:
    # the grid quantize_symmetric's x / scale lands on in that dtype, so
    # fused == split stays bit-identical for every activation dtype
    x_in = x_ref[...]
    q = (x_in.astype(jnp.float32) / xs_ref[0, 0]).astype(x_in.dtype)
    xq = jnp.clip(jnp.round(q.astype(jnp.float32)), -qmax - 1.0, qmax)
    w = wq_ref[...].astype(jnp.float32)
    if transpose_w:
        w = w.T                                  # OBU port swap, in-register
    w_prime = w / (2.0 * qmax) + 0.5
    acc_ref[...] += jnp.dot(xq, w_prime, preferred_element_type=jnp.float32)
    xsum_ref[...] += jnp.sum(xq, axis=1, keepdims=True)

    @pl.when(k == nk - 1)
    def _finalize():
        y = 2.0 * (acc_ref[...] - 0.5 * xsum_ref[...])
        out_scale = xs_ref[0, 0] * ws_ref[...]
        # round to the output dtype (what the split path stores), then run
        # the epilogue in f32 like the blend kernel: no bf16 VPU arithmetic
        y = (y * out_scale).astype(o_ref.dtype).astype(jnp.float32)
        if has_bias:
            # the TIA rescale product feeds this add unrounded — XLA
            # contracts the pair into an fma (even across an
            # optimization_barrier), so the fused bias lands <= 1 ulp off
            # the split path's store-then-add (and is the more accurate of
            # the two).  The bias-free epilogues (activation / blocked
            # shuffle — all the model path uses) stay bit-identical.
            y = y + b_ref[...].astype(jnp.float32)
        o_ref[...] = _act(y, activation).astype(o_ref.dtype)


def _out_block_index(block_perm, block: int, N: int, bn: int) -> np.ndarray:
    """Expand a block-level output permutation to bn-tile granularity.

    Computed column block ``j`` lands at output block ``inv_perm[j]`` (the
    blend kernel reads input block ``perm[j]`` while writing ``j``; the MVM
    side inverts that because it walks *computed* columns)."""
    perm = np.asarray(block_perm, dtype=np.int64)
    nblk = perm.shape[0]
    if sorted(perm.tolist()) != list(range(nblk)):
        raise ValueError("block_perm must be a permutation")
    if nblk * block != N:
        raise ValueError(f"block_perm covers {nblk * block} channels, "
                         f"output has {N}")
    inv = np.argsort(perm)
    r = block // bn
    fine = inv[:, None] * r + np.arange(r)[None, :]
    return fine.reshape(-1).astype(np.int32)


def reads_stack_in_place(K: int, N: int, bk: int, bn: int,
                         block: int = 0) -> bool:
    """Whether the fused kernel reads a stacked (R, K, N) bank in place at
    this tile plan: neither weight axis needs padding (padding would copy
    the whole stack).  ``block`` is the blocked-shuffle size, which narrows
    the column tile to ``gcd(bn, block)``."""
    if block > 0:
        bn = math.gcd(bn, block)
    return K % bk == 0 and N % bn == 0


def _kernel_fused_stacked(oidx_ref, layer_ref, *refs, **kw):
    """``_kernel_fused`` behind a second scalar-prefetch operand: the layer
    index is consumed by the weight and scale index maps, not the body."""
    _kernel_fused(oidx_ref, *refs, **kw)


@functools.partial(jax.jit, static_argnames=(
    "bm", "bk", "bn", "qmax", "transpose", "activation", "block_perm",
    "block", "interpret", "out_dtype"))
def photonic_mvm_fused(x, wq, x_scale, w_scale, *, bias=None, layer=None,
                       bm=128, bk=128, bn=128, qmax=127.0, transpose=False,
                       activation="none", block_perm=None, block=0,
                       interpret=None, out_dtype=jnp.float32):
    """The decode-path megakernel: one ``pallas_call`` for
    quantize -> offset-decomposed MVM -> bias -> activation -> blocked
    output shuffle.

    x: (M, K) floating; wq: int8 (K, N) per-column quantized, or (N, K)
    per-row quantized with ``transpose=True`` (the pre-swapped OBU
    orientation); x_scale: the A8 scale (from ``core.photonic.a8_scale`` —
    NOT the already-quantized activations); w_scale: (N,); bias: optional
    (N,), indexed by *output* position like ``blend_shuffle``;
    block_perm: optional tuple — output block ``q`` carries computed block
    ``block_perm[q]``, realized purely by the output BlockSpec's
    scalar-prefetched index map.  Returns (M, N) ``out_dtype``.

    With ``layer`` (a traced int32 scalar), ``wq`` is a stacked bank
    (R, K, N) — (R, N, K) transposed — with ``w_scale`` (R, N), and the
    kernel multiplies by layer ``layer``: the weight and scale index maps
    read its tiles straight out of the stack through a second
    scalar-prefetch operand, so no per-layer copy of the bank is ever
    written.  Bitwise equal to passing ``wq[layer]``, ``w_scale[layer]``.
    When a weight axis would need padding (:func:`reads_stack_in_place`)
    the layer is sliced out first instead, never the whole stack padded.
    """
    if interpret is None:
        interpret = default_interpret()
    M, K = x.shape
    if transpose:
        N, K2 = wq.shape[-2:]
    else:
        K2, N = wq.shape[-2:]
    assert K == K2
    if block_perm is not None:
        if block <= 0:
            raise ValueError("block_perm needs a positive block size")
        bn = math.gcd(bn, block)         # bn must divide the shuffle block
        if N % block != 0:
            raise ValueError(f"blocked shuffle needs C % block == 0, got "
                             f"C={N}, block={block}")
    if layer is not None and not reads_stack_in_place(K, N, bk, bn):
        wq = jax.lax.dynamic_index_in_dim(wq, layer, 0, keepdims=False)
        w_scale = jax.lax.dynamic_index_in_dim(w_scale, layer, 0,
                                               keepdims=False)
        layer = None
    stacked = layer is not None
    x_p = _pad_to(_pad_to(x, bm, 0), bk, 1)
    if stacked:
        wq_p = wq                        # aligned: read in place
        ws_p = w_scale.reshape(wq.shape[0], 1, N)
    else:
        wq_p = (_pad_to(_pad_to(wq, bn, 0), bk, 1) if transpose
                else _pad_to(_pad_to(wq, bk, 0), bn, 1))
        ws_p = _pad_to(w_scale.reshape(1, N), bn, 1)
    Np = wq_p.shape[-2] if transpose else wq_p.shape[-1]
    Mp, Kp = x_p.shape
    grid = (Mp // bm, Np // bn, Kp // bk)
    if block_perm is not None:
        oidx = _out_block_index(block_perm, block, N, bn)
    else:
        oidx = np.arange(Np // bn, dtype=np.int32)
    # index maps take every scalar-prefetch ref: (oidx,) or (oidx, layer)
    if stacked:
        w_spec = (pl.BlockSpec((None, bn, bk),
                               lambda i, j, k, oi, li: (li[0], j, k))
                  if transpose else
                  pl.BlockSpec((None, bk, bn),
                               lambda i, j, k, oi, li: (li[0], k, j)))
        ws_spec = pl.BlockSpec((None, 1, bn),
                               lambda i, j, k, oi, li: (li[0], 0, j))
    else:
        w_spec = (pl.BlockSpec((bn, bk), lambda i, j, k, *_: (j, k))
                  if transpose else
                  pl.BlockSpec((bk, bn), lambda i, j, k, *_: (k, j)))
        ws_spec = pl.BlockSpec((1, bn), lambda i, j, k, *_: (0, j))
    in_specs = [
        pl.BlockSpec((bm, bk), lambda i, j, k, *_: (i, k)),
        w_spec,
        pl.BlockSpec((1, 1), lambda i, j, k, *_: (0, 0)),
        ws_spec,
    ]
    operands = [x_p, wq_p,
                jnp.reshape(x_scale, (1, 1)).astype(jnp.float32),
                ws_p.astype(jnp.float32)]
    has_bias = bias is not None
    if has_bias:
        # bias is indexed by OUTPUT position: computed block j lands at
        # oidx[j], so its bias tile is read from there too
        in_specs.append(
            pl.BlockSpec((1, bn), lambda i, j, k, oi, *_: (0, oi[j])))
        operands.append(_pad_to(bias.reshape(1, N), bn, 1))
    prefetch = [jnp.asarray(oidx)]
    if stacked:
        # clamped like the dynamic slice it replaces
        prefetch.append(jnp.clip(jnp.reshape(layer, (1,)).astype(jnp.int32),
                                 0, wq.shape[0] - 1))
    gridspec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(prefetch),
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bm, bn),
                               lambda i, j, k, oi, *_: (i, oi[j])),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32),
                        pltpu.VMEM((bm, 1), jnp.float32)],
    )
    out = pl.pallas_call(
        functools.partial(_kernel_fused_stacked if stacked else _kernel_fused,
                          nk=grid[2], qmax=qmax, transpose_w=transpose,
                          activation=activation, has_bias=has_bias),
        grid_spec=gridspec,
        out_shape=jax.ShapeDtypeStruct((Mp, Np), out_dtype),
        interpret=interpret,
    )(*prefetch, *operands)
    if Mp == M and Np == N:
        return out                       # aligned: no slice round-trip
    return out[:M, :N]
