"""Compile-once Program API — the public entry point for inference.

The paper's discipline is *program the weight banks once, serve many steps*
(§3.1).  ``Program`` is that discipline as an API:

    prog = Program.build(cfg, params)            # resolve + prepare ONCE
    logits, caches = prog.prefill(batch, cache_len)
    logits, caches = prog.decode(tokens, caches, pos)
    out = prog.generate(prompt, max_new=32)

``build`` resolves the execution backend, casts the params to the compute
dtype (subsuming ``engine.cast_params``), and — on the photonic backend —
quantizes every matmul weight into a :class:`~repro.core.prepared.
PreparedTensor` bank: int8 tiles, per-channel TIA gains for both OBU
orientations, and the W0-row checksums, all derived exactly once.  Decode
steps then skip the per-step weight re-quantization the legacy path paid
(DESIGN.md §Prepared weights) and run the fused decode-path megakernel
(DESIGN.md §Fused decode path): because operand shapes are static under
jit, the prefill and decode cells below each compile with their own
shape-adaptive tile plan — prefill at full row tiles, decode at
``round_up(B, 8)``-row serving tiles — with A8 quantization and the blend
epilogue folded into the kernel.

**No retrace across Programs.**  The jitted cells live at module level and
key their trace cache on static ``(cfg, backend, ...)`` — two Programs with
the same config share compiled executables, and repeated ``generate`` calls
never rebuild jit closures (the bug the legacy ``engine.generate`` had).
``TRACE_COUNTS`` records actual retraces for tests.

The old kwarg-threaded surface (``transformer.forward(execution=...)``,
``engine.prefill_step/decode_step/generate``) stays alive as thin
deprecation shims; greedy outputs are token-identical to the Program
methods on both backends (tested in ``tests/test_program_api.py``).
"""
from __future__ import annotations

import dataclasses
import functools
import warnings
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding

from repro.configs.base import ModelConfig
from repro.core import backend as backend_lib
from repro.core import prepared as prepared_lib
from repro.models import transformer as tfm
from repro.obs import metrics as metrics_lib
from repro.sharding import partition
from repro.train.trainer import cross_entropy

NEG_INF = -1e30

# python-side trace counter: incremented only when a jitted cell actually
# retraces (the function body runs under trace).  Tests assert stability.
# The CounterGroup keeps the Counter/dict surface (``TRACE_COUNTS[k] += 1``,
# ``dict(TRACE_COUNTS)``) while mirroring every write into the default
# metrics registry as ``compile.trace.<cell>`` — retrace counts ride along
# in every metrics snapshot.
TRACE_COUNTS: metrics_lib.CounterGroup = metrics_lib.CounterGroup(
    "compile.trace")


@functools.lru_cache(maxsize=1)
def _donate_caches() -> bool:
    """Buffer donation frees the previous cache buffer the moment the
    decode step consumes it (the carried KV pool updates in place).  CPU
    has no donation support — skip it there to avoid per-call warnings.
    Evaluated lazily (first Program step) so importing this module never
    initializes the JAX runtime behind the caller's platform config."""
    return jax.default_backend() != "cpu"


# =========================================================================
# sampling
# =========================================================================
def sample(logits, vocab_size: int, key=None, temperature: float = 0.0):
    """Greedy (``temperature <= 0``) or temperature sampling over the
    unpadded vocabulary.  ``temperature > 0`` REQUIRES a PRNG key — the
    legacy silent fall-back to greedy is now an error."""
    if temperature > 0.0 and key is None:
        raise ValueError(
            f"sample(temperature={temperature}) needs a PRNG key; pass "
            f"key=jax.random.PRNGKey(...) or use temperature=0 for greedy")
    logits = _mask_padded(logits.astype(jnp.float32), vocab_size)
    if temperature <= 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    return jax.random.categorical(key, logits / temperature,
                                  axis=-1).astype(jnp.int32)


def _mask_padded(logits, vocab_size: int):
    padded = logits.shape[-1]
    if padded == vocab_size:
        return logits
    col = jax.lax.broadcasted_iota(jnp.int32, (padded,), 0)
    return jnp.where(col < vocab_size, logits, NEG_INF)


# =========================================================================
# functional step builders (shared by Program, the engine shims, and the
# dry-run lowering — which jits them itself with shardings)
# =========================================================================
def prefill_step_fn(cfg: ModelConfig, cache_len: int, *, act_pspec=None,
                    execution=None):
    """Pure ``fn(params, batch) -> (last_logits (B, V), caches)``."""
    def fn(params, batch):
        B = batch["tokens"].shape[0]
        caches = tfm.init_caches(cfg, B, cache_len,
                                 dtype=jnp.dtype(cfg.compute_dtype))
        logits, caches, _ = tfm.forward(params, cfg, batch, mode="prefill",
                                        caches=caches, act_pspec=act_pspec,
                                        execution=execution)
        return logits[:, -1, :], caches
    return fn


def decode_step_fn(cfg: ModelConfig, *, act_pspec=None, legacy_decode=False,
                   execution=None):
    """Pure ``fn(params, batch, caches, pos) -> (logits (B, V), caches)``."""
    def fn(params, batch, caches, pos):
        logits, caches, _ = tfm.forward(params, cfg, batch, mode="decode",
                                        caches=caches, pos=pos,
                                        act_pspec=act_pspec,
                                        legacy_decode=legacy_decode,
                                        execution=execution)
        return logits[:, 0, :], caches
    return fn


# =========================================================================
# mesh plumbing (the sharded-execution refactor)
# =========================================================================
def _backend_mesh(backend):
    """The backend's mesh when it actually partitions (> 1 device)."""
    mesh = getattr(backend, "mesh", None)
    if mesh is None or mesh.size <= 1:
        return None
    return mesh


def _constrain_caches(caches, cfg: ModelConfig, backend, B: int, L: int):
    """Pin the KV/slot cache layout to the partition rules (batch over the
    data axes, KV heads over "model") so prefill compiles data/tensor-
    parallel under the backend's mesh.  No-op off-mesh (and on a 1x1 mesh —
    the bit-identity contract with the unsharded path)."""
    mesh = _backend_mesh(backend)
    if mesh is None:
        return caches
    sh = partition.cache_shardings(cfg, mesh, B, L)
    return jax.tree.map(jax.lax.with_sharding_constraint, caches, sh)


def _mesh_act_pspec(backend, B: int):
    """Batch-over-data residual constraint (replicated d_model) for the
    train/loss cell; None when the batch does not divide the data axes."""
    mesh = _backend_mesh(backend)
    if mesh is None:
        return None
    dp = partition.dp_size(mesh)
    if dp <= 1 or B % dp != 0:
        return None
    return NamedSharding(mesh, partition.act_pspec(mesh, "replicated"))


def _serve_act_pspec(backend, B: int):
    """Residual anchor for the serving cells (prefill, chunked prefill,
    decode).

    The sharded matmul path leaves TP outputs model-sharded (reduce-scatter
    + lazy gather, `core/backend.py`); in decode this constraint tells GSPMD
    the residual must be whole again only AT the layer boundary, so the
    all-gather lands next to the residual add — after the epilogue, where
    it overlaps the next layer's kernels — instead of wherever propagation
    happens to cut it.  Monolithic prefill also applies it to each
    sublayer's output (``tfm._gather_for_prefill``).  Unlike the train-cell
    ``_mesh_act_pspec`` it also applies on pure-TP meshes (dp == 1); None
    off-mesh and on a 1x1 mesh, preserving the unsharded cells
    bit-for-bit."""
    mesh = _backend_mesh(backend)
    if mesh is None:
        return None
    dp = partition.dp_size(mesh)
    if dp > 1 and B % dp != 0:
        return None
    return NamedSharding(mesh, partition.act_pspec(mesh, "replicated"))


# =========================================================================
# module-level jit cells (trace cache shared across all Programs)
# =========================================================================
@functools.partial(jax.jit, static_argnames=("cfg", "photonic"))
def _prepare_cell(params, *, cfg: ModelConfig, photonic: bool):
    TRACE_COUNTS["prepare"] += 1
    return prepared_lib.prepare_params(params, cfg.compute_dtype, photonic)


@functools.partial(jax.jit,
                   static_argnames=("cfg", "backend", "cache_len"))
def _prefill_cell(bank, batch, last, *, cfg: ModelConfig, backend,
                  cache_len: int):
    """Prefill into fresh caches; returns each row's logits at its own
    ``last`` index (right padding beyond it is causally invisible)."""
    TRACE_COUNTS["prefill"] += 1
    B = batch["tokens"].shape[0]
    caches = tfm.init_caches(cfg, B, cache_len,
                             dtype=jnp.dtype(cfg.compute_dtype))
    caches = _constrain_caches(caches, cfg, backend, B, cache_len)
    logits, caches, _ = tfm.forward(bank, cfg, batch, mode="prefill",
                                    caches=caches, execution=backend,
                                    act_pspec=_serve_act_pspec(backend, B))
    caches = _constrain_caches(caches, cfg, backend, B, cache_len)
    return logits[jnp.arange(B), last], caches


@functools.partial(jax.jit, static_argnames=("cfg", "B", "cache_len"))
def _empty_caches_cell(*, cfg: ModelConfig, B: int, cache_len: int):
    """Zero capacity caches for the chunked-prefill entry points (the
    monolithic ``_prefill_cell`` allocates its own inside the trace)."""
    TRACE_COUNTS["init_caches"] += 1
    return tfm.init_caches(cfg, B, cache_len,
                           dtype=jnp.dtype(cfg.compute_dtype))


@functools.lru_cache(maxsize=2)
def _prefill_chunk_cells(donate: bool):
    """The chunked-prefill cell, jitted once per donation mode.

    ``q_offset`` is a TRACED operand (not a static key): one compiled cell
    serves every chunk index of every prompt, so the retrace family is one
    jit per (B, chunk width, cache_len) — bounded by the configuration —
    instead of the one-jit-per-prompt-length family monolithic exact-length
    prefill pays."""
    donate_args = (2,) if donate else ()

    @functools.partial(jax.jit, static_argnames=("cfg", "backend"),
                       donate_argnums=donate_args)
    def prefill_chunk_cell(bank, tokens, caches, q_offset, last, *,
                           cfg: ModelConfig, backend):
        TRACE_COUNTS["prefill_chunk"] += 1
        B = tokens.shape[0]
        logits, caches, _ = tfm.forward(
            bank, cfg, {"tokens": tokens}, mode="prefill_chunk",
            caches=caches, pos=q_offset, execution=backend,
            act_pspec=_serve_act_pspec(backend, B))
        return logits[jnp.arange(B), last], caches

    return prefill_chunk_cell


@functools.lru_cache(maxsize=2)
def _decode_cells(donate: bool):
    """The two decode cells, jitted once per donation mode.  The lru_cache
    hands every Program the same jitted objects, so the trace cache stays
    shared process-wide exactly as with module-level cells."""
    donate_args = (2,) if donate else ()

    @functools.partial(jax.jit, static_argnames=("cfg", "backend"),
                       donate_argnums=donate_args)
    def decode_cell(bank, tokens, caches, pos, *, cfg: ModelConfig,
                    backend):
        TRACE_COUNTS["decode"] += 1
        logits, caches, _ = tfm.forward(
            bank, cfg, {"tokens": tokens}, mode="decode", caches=caches,
            pos=pos, execution=backend,
            act_pspec=_serve_act_pspec(backend, tokens.shape[0]))
        return logits[:, 0, :], caches

    @functools.partial(jax.jit,
                       static_argnames=("cfg", "backend", "greedy"),
                       donate_argnums=donate_args)
    def decode_sample_cell(bank, tokens, caches, pos, key, temperature, *,
                           cfg: ModelConfig, backend, greedy: bool):
        """Fused decode + sample: one jitted computation per token (the
        sampler never round-trips logits through the host).  Returns
        (tokens, caches, routing): on an MoE model routing is every MoE
        layer's expert ids of every row (MoE layers, B, top_k), else
        None."""
        TRACE_COUNTS["decode_sample"] += 1
        logits, caches, aux = tfm.forward(
            bank, cfg, {"tokens": tokens}, mode="decode", caches=caches,
            pos=pos, execution=backend, record_routing=True,
            act_pspec=_serve_act_pspec(backend, tokens.shape[0]))
        routing = aux["route"] if isinstance(aux, dict) else None
        logits = _mask_padded(logits[:, 0, :].astype(jnp.float32),
                              cfg.vocab_size)
        if greedy:
            tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        else:
            tok = jax.random.categorical(key, logits / temperature,
                                         axis=-1).astype(jnp.int32)
        return tok, caches, routing

    return decode_cell, decode_sample_cell


@functools.partial(jax.jit, static_argnames=("cfg", "backend"))
def _loss_cell(bank, batch, *, cfg: ModelConfig, backend):
    TRACE_COUNTS["loss"] += 1
    logits, _, aux = tfm.forward(
        bank, cfg, batch, mode="train", execution=backend,
        act_pspec=_mesh_act_pspec(backend, batch["tokens"].shape[0]))
    ce = cross_entropy(logits[:, :-1], batch["tokens"][:, 1:],
                       cfg.vocab_size)
    return ce, aux


# =========================================================================
# Program
# =========================================================================
@dataclasses.dataclass
class Program:
    """A model compiled for serving: backend resolved, weights prepared,
    step cells jitted — all exactly once, at :meth:`build` time."""

    cfg: ModelConfig
    backend: backend_lib.Backend
    bank: Any                      # prepared params (PreparedTensor leaves
                                   # on photonic; compute-dtype fp on xla)

    # ------------------------------------------------------------ building
    @classmethod
    def build(cls, cfg: ModelConfig, params, *, execution=None,
              mesh=None) -> "Program":
        """Resolve the substrate and prepare the weight banks once.

        ``execution`` overrides ``cfg.execution`` ("xla" | "photonic" | a
        ``Backend``); on photonic, every matmul weight is quantized to its
        int8 bank here — no decode step ever re-derives W8 tiles.

        ``mesh`` makes the mesh a property of execution: the logical-axis
        rules (`sharding/partition.py`) resolve to NamedShardings for the
        params AND the prepared int8 banks (tiles/scales shard with their
        owning weight's spec), the bank is placed accordingly, and every
        step cell compiles under that mesh — photonic matmuls run the
        Pallas kernels per-shard via shard_map (`core/backend.py`), KV/slot
        caches shard batch-over-data.  ``None`` (the default) and a 1x1
        mesh (``launch.mesh.single_device_mesh``) are bit-identical to the
        unsharded path.  Rules that do not divide a concrete dim are
        REPLICATED, not an error — surfaced here as a one-line warning."""
        bk = backend_lib.resolve(execution if execution is not None else cfg)
        bk_mesh = getattr(bk, "mesh", None)
        if mesh is not None and bk_mesh is not None and bk_mesh != mesh:
            raise ValueError(
                "Program.build(mesh=...) conflicts with the mesh the "
                "execution Backend already carries — pass one or the other")
        if mesh is not None and bk_mesh is None:
            bk = dataclasses.replace(bk, mesh=mesh)
        mesh = getattr(bk, "mesh", None)
        dropped = 0
        if mesh is not None:
            # place the fp params on the mesh first, so the prepare cell
            # quantizes each shard where it lives: no device ever holds the
            # whole unsharded bank
            report = partition.PartitionReport(dropped=[])
            specs = tfm.model_specs(cfg)
            params = jax.device_put(params, partition.param_shardings(
                params, specs, mesh, cfg.fsdp, report))
            bank = _prepare_cell(params, cfg=cfg, photonic=bk.is_photonic)
            bank = jax.device_put(bank, partition.bank_shardings(
                bank, specs, mesh, cfg.fsdp))
            dropped = len(report.dropped)
            if report.dropped:
                warnings.warn(partition.dropped_summary(report),
                              stacklevel=2)
        else:
            bank = _prepare_cell(params, cfg=cfg, photonic=bk.is_photonic)
        # bank/partition accounting as registry gauges (last Program built
        # wins — builds are one-time events, not hot-path)
        reg = metrics_lib.default_registry()
        reg.counter("program.builds").inc()
        for k, v in prepared_lib.prepared_stats(bank).items():
            reg.gauge(f"program.bank.{k}").set(v)
        reg.gauge("program.partition.dropped_rules").set(dropped)
        return cls(cfg=cfg, backend=bk, bank=bank)

    @property
    def mesh(self):
        """The execution mesh (None: unsharded single-device semantics)."""
        return getattr(self.backend, "mesh", None)

    def update_noise(self, noise) -> None:
        """Swap the fault-model config on the live Program (in place).

        The calibration loop's republish step: after a drift repair it
        installs a ``NoiseConfig`` with fresh per-bank ages via
        ``noise.with_bank_ages``.  ``Backend`` is a static jit key, so the
        replace retraces exactly the step cells that run under the new
        config — the banks, caches, and every other cell stay untouched.
        ``Backend.__post_init__`` re-runs, so noise + multi-device mesh is
        rejected here too."""
        self.backend = dataclasses.replace(self.backend, noise=noise)

    # -------------------------------------------------------------- stats
    def bank_stats(self) -> dict:
        return prepared_lib.prepared_stats(self.bank)

    def verify_banks(self) -> float:
        """Max W0-row checksum error across all programmed banks (hardware
        read-back verification; ~0 — below fp32 reduction noise ~1e-5 — for
        uncorrupted banks, and exactly 0.0 for the pure-fp xla bank)."""
        errs = [float(prepared_lib.verify_bank(leaf))
                for leaf in jax.tree.leaves(
                    self.bank,
                    is_leaf=lambda x: isinstance(
                        x, prepared_lib.PreparedTensor))
                if isinstance(leaf, prepared_lib.PreparedTensor)]
        return max(errs, default=0.0)

    # -------------------------------------------------------------- steps
    def prefill(self, batch, cache_len: int, last=None):
        """Run prompts into fresh caches.  ``last`` (B,) selects each row's
        last-prompt-token logits (default: the final column, for unpadded
        prompts).  Returns (logits (B, V), caches)."""
        B = batch["tokens"].shape[0]
        if last is None:
            last = jnp.full((B,), batch["tokens"].shape[1] - 1, jnp.int32)
        if metrics_lib.enabled():         # hot-path extra: gated
            metrics_lib.counter("program.steps", kind="prefill").inc()
        return _prefill_cell(self.bank, batch, jnp.asarray(last, jnp.int32),
                             cfg=self.cfg, backend=self.backend,
                             cache_len=cache_len)

    def empty_caches(self, B: int, cache_len: int):
        """Zero capacity caches sized for ``B`` rows — the staging buffers
        the chunked-prefill cells fill in place."""
        return _empty_caches_cell(cfg=self.cfg, B=B, cache_len=cache_len)

    def prefill_chunk(self, tokens, caches, q_offset, last=None):
        """One fixed-width prefill chunk into existing capacity caches.

        tokens: (B, W) — the prompt slice [q_offset, q_offset+W).
        ``q_offset`` is traced (scalar int32): every chunk of every prompt
        reuses the one compiled cell for this (B, W, cache_len).  ``last``
        (B,) indexes logits WITHIN the chunk (default: final column).
        Caches are donated on accelerators — thread the returned ones."""
        B, W = tokens.shape[0], tokens.shape[1]
        if last is None:
            last = jnp.full((B,), W - 1, jnp.int32)
        if metrics_lib.enabled():
            metrics_lib.counter("program.steps", kind="prefill_chunk").inc()
        cell = _prefill_chunk_cells(_donate_caches())
        return cell(self.bank, tokens, caches, jnp.asarray(q_offset,
                                                           jnp.int32),
                    jnp.asarray(last, jnp.int32), cfg=self.cfg,
                    backend=self.backend)

    def prefill_chunked(self, batch, cache_len: int, chunk: int, last=None):
        """Chunked prefill over a whole batch: fixed-width query chunks
        (tail zero-padded to ``chunk``, causally invisible to real rows)
        through :meth:`prefill_chunk`.  Semantically equivalent to
        :meth:`prefill` — bit-identical on xla; within the W8A8 tolerance
        on photonic, where per-chunk activation scales differ from
        whole-prompt scales.  Returns (logits (B, V), caches)."""
        tokens = batch["tokens"]
        B, S = tokens.shape
        if last is None:
            last = jnp.full((B,), S - 1, jnp.int32)
        last = jnp.asarray(last, jnp.int32)
        S_pad = ((S + chunk - 1) // chunk) * chunk
        if S_pad != S:
            tokens = jnp.pad(tokens, ((0, 0), (0, S_pad - S)))
        caches = self.empty_caches(B, cache_len)
        out = None
        for off in range(0, S_pad, chunk):
            idx = jnp.clip(last - off, 0, chunk - 1)
            lg, caches = self.prefill_chunk(tokens[:, off:off + chunk],
                                            caches, off, last=idx)
            hit = (last >= off) & (last < off + chunk)
            out = lg if out is None else jnp.where(hit[:, None], lg, out)
        return out, caches

    def decode(self, tokens, caches, pos):
        """One token per sequence.  tokens: (B, 1); ``pos`` scalar (aligned)
        or (B,) per-slot.  Cache buffers are donated (updated in place) on
        accelerators — pass the returned caches to the next step."""
        if metrics_lib.enabled():
            metrics_lib.counter("program.steps", kind="decode").inc()
        cell, _ = _decode_cells(_donate_caches())
        return cell(self.bank, tokens, caches, pos, cfg=self.cfg,
                    backend=self.backend)

    def decode_sample(self, tokens, caches, pos, key=None,
                      temperature: float = 0.0, routing: bool = False):
        """Fused decode + sample step.  Returns (token_ids (B,), caches),
        and with ``routing`` the step's expert ids (MoE layers, B, top_k)
        as a third item (None on a model without experts)."""
        if temperature > 0.0 and key is None:
            raise ValueError("decode_sample(temperature>0) needs a PRNG key")
        if key is None:
            key = jax.random.PRNGKey(0)          # unused under greedy
        if metrics_lib.enabled():
            metrics_lib.counter("program.steps", kind="decode_sample").inc()
        _, cell = _decode_cells(_donate_caches())
        tok, caches, route = cell(
            self.bank, tokens, caches, pos, key,
            jnp.float32(max(temperature, 1e-6)), cfg=self.cfg,
            backend=self.backend, greedy=temperature <= 0.0)
        return (tok, caches, route) if routing else (tok, caches)

    def loss(self, batch):
        """Mean next-token cross-entropy of ``batch`` (eval; no gradients).
        Returns (ce, aux) scalars."""
        return _loss_cell(self.bank, batch, cfg=self.cfg,
                          backend=self.backend)

    # ----------------------------------------------------------- generate
    def generate(self, prompt, max_new: int, *, extras=None,
                 temperature: float = 0.0, seed: int = 0):
        """Host-side autoregressive loop over the pre-jitted cells.

        prompt: (B, S) int32.  Returns (B, S + max_new).  Token-identical
        to the legacy ``engine.generate`` (same key schedule)."""
        prompt = jnp.asarray(prompt)
        B, S = prompt.shape
        cache_len = S + max_new
        batch = {"tokens": prompt}
        if extras:
            batch.update(extras)
        logits, caches = self.prefill(batch, cache_len)
        key = jax.random.PRNGKey(seed)
        toks = [prompt]
        cur = sample(logits, self.cfg.vocab_size, key, temperature)[:, None]
        for i in range(max_new):
            toks.append(cur)
            if i == max_new - 1:
                break
            key, sub = jax.random.split(key)
            nxt, caches = self.decode_sample(cur, caches, S + i, key=sub,
                                             temperature=temperature)
            cur = nxt[:, None]
        return jnp.concatenate(toks, axis=1)
