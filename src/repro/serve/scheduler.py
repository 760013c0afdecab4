"""Continuous-batching scheduler over the slot-level KV pool.

Unlike the static ``WaveBatcher`` (requests grouped into lockstep waves, short
prompts padded to the wave maximum), this scheduler keeps a fixed-capacity
``SlotPool`` decoding every step and *prefills new requests into free slots
while in-flight slots keep decoding*: the decode batch is continuously
refilled, each slot carries its own position, and requests terminate
independently (per-request ``max_new`` / EOS).

This is the paper's write-once/reuse-many schedule at request granularity
(DESIGN.md §Serving): the R basic weight banks stay resident while a
continuously topped-up decode population streams through them, so the MRR
programming cost is amortized over ``active_slots x steps`` token passes
instead of one aligned wave.  ``ReuseAwareAdmission`` makes that explicit —
it uses the calibrated cost model (``core.costmodel``) to derive the minimum
decode population at which write energy is acceptably amortized, and admits
aggressively below it.

Both schedulers implement the ``Scheduler`` protocol: ``submit`` requests,
``drain`` completions.
"""
from __future__ import annotations

import collections
import dataclasses
import math
import time
from typing import Callable, Optional, Protocol, runtime_checkable

import numpy as np

import jax
import jax.numpy as jnp

from repro import api
from repro.configs.base import ModelConfig
from repro.core import costmodel
from repro.core.prm import ReusePlan
from repro.models import transformer as tfm
# ContinuousStats lives in the shared stats protocol (repro.obs.stats) —
# re-exported so historical imports keep working
from repro.obs.stats import ContinuousStats as ContinuousStats  # noqa: F401
from repro.obs.tracing import span
from repro.serve.batcher import Completion, Request
from repro.serve.slots import SlotPool, SlotState


@runtime_checkable
class Scheduler(Protocol):
    """What ``launch/serve.py`` and the benchmarks program against."""

    def submit(self, req: Request) -> None: ...

    def drain(self) -> list[Completion]: ...


# =========================================================================
# reuse-aware admission
# =========================================================================
@dataclasses.dataclass(frozen=True)
class ReuseAwareAdmission:
    """Cost-model-driven admission policy (R&B amortization, request level).

    On the photonic target the R basic banks are reprogrammed once per
    calibration interval (``refresh_steps`` decode steps — thermal drift /
    aging recalibration, §4.2.3), while every decode step streams the whole
    active population through the resident banks.  With M weight matrices of
    ~(d, d) per basic block and stack depth D, the energy efficiency at
    active population A is

        eff(A) = A * refresh_steps * D * e_comp
                 / (A * refresh_steps * D * e_comp + R * M * e_write)

    ``min_population`` is the smallest A with eff >= ``target_efficiency``.
    Below it the policy admits everything that fits (batched admissions
    rebuild amortization fastest); at or above it, at most
    ``max_admit_per_step`` per step so prefill work never starves the
    in-flight decodes.
    """

    min_population: int
    max_admit_per_step: int = 1

    @staticmethod
    def build(cfg: ModelConfig, *, tile: int = 256,
              target_efficiency: float = 0.9, refresh_steps: int = 8,
              mats_per_block: int = 6, max_admit_per_step: int = 1
              ) -> "ReuseAwareAdmission":
        R, depth = 0, 0
        for spec in tfm.build_segments(cfg):
            if spec.stream == "encoder":
                continue
            plan = ReusePlan.build(spec.num_groups, spec.reuse)
            R += plan.num_physical
            depth += spec.depth
        d = cfg.d_model
        _, e_write = costmodel.CALIBRATED.write_cost(d, d, tile)
        _, e_comp = costmodel.CALIBRATED.compute_cost(d, d, tile)
        ratio = target_efficiency / max(1.0 - target_efficiency, 1e-9)
        min_pop = math.ceil(ratio * R * mats_per_block * e_write
                            / (depth * e_comp * refresh_steps))
        return ReuseAwareAdmission(min_population=max(1, min_pop),
                                   max_admit_per_step=max_admit_per_step)

    def admit_count(self, *, queued: int, free: int, active: int) -> int:
        """How many queued requests to prefill this step."""
        if queued == 0 or free == 0:
            return 0
        if active < self.min_population:
            return min(queued, free)
        return min(queued, free, self.max_admit_per_step)


def expert_loads(route: np.ndarray, live, num_experts: int) -> np.ndarray:
    """(MoE layers, experts) rows each expert took, over the ``live`` rows
    of a decode step's expert ids ``route`` (MoE layers, rows, top_k)."""
    ids = route[:, live, :].reshape(route.shape[0], -1)
    return (ids[..., None] == np.arange(num_experts)).sum(axis=1)


# =========================================================================
# continuous scheduler
# =========================================================================
class ContinuousScheduler:
    """Slot-level continuous batching over a shared [R, T, B, L, ...] pool.

    Serves from a compile-once :class:`repro.api.Program` (pass one as the
    first argument to share its prepared banks and jit cells across
    schedulers, or the legacy ``(params, cfg)`` pair to build one here).
    Greedy outputs are token-identical to ``Program.generate`` run per
    request: prompts are left-aligned at position 0 of their slot, prefill
    pads only to a compile bucket on the *right* (causally invisible), and
    decode masks every row at its own position.

    A Program built with ``mesh=`` makes serving data-parallel: the slot
    pool's batch axis spans the mesh's data shards (capacity must divide),
    admission packs per-shard sub-batches, and each decode step runs every
    shard's sub-batch concurrently under GSPMD — same host-side loop, same
    greedy tokens.
    """

    def __init__(self, params, cfg: Optional[ModelConfig] = None, *,
                 capacity: int = 8,
                 max_len: int = 256, pad_id: int = 0,
                 temperature: float = 0.0, seed: int = 0,
                 prefill_bucket: int = 16,
                 prefill_chunk: Optional[int] = None,
                 admission: Optional[ReuseAwareAdmission] = None,
                 mesh=None,
                 on_token: Optional[Callable[[int, int], None]] = None,
                 on_complete: Optional[Callable[[Completion], None]] = None,
                 telemetry=None,
                 residency=None,
                 calibration=None):
        # compile-once entry: pass a prebuilt ``api.Program`` as the first
        # argument (backend + prepared banks resolved exactly once, shared
        # with other schedulers); or the legacy (params, cfg) pair, which
        # builds the Program here.
        if isinstance(params, api.Program):
            self.program = params
            if cfg is not None and cfg != params.cfg:
                raise ValueError("pass either a Program or (params, cfg), "
                                 "not a Program plus a different cfg")
            if mesh is not None and mesh != self.program.mesh:
                # a pool sharded on a mesh the Program's cells don't know
                # about would feed mesh-sharded caches into unsharded
                # pallas_calls — build the Program with the mesh instead
                raise ValueError(
                    "mesh= conflicts with the Program's execution mesh; "
                    "build it with Program.build(..., mesh=mesh)")
            cfg = self.program.cfg
        else:
            if cfg is None:
                raise ValueError("ContinuousScheduler(params, cfg) needs "
                                 "the model config")
            self.program = api.Program.build(cfg, params, mesh=mesh)
        self.cfg = cfg
        self.pad_id = pad_id
        self.temperature = temperature
        self.prefill_bucket = max(1, prefill_bucket)
        # global bank residency (repro.resident): an optional
        # ProgramResidency binding this Program's banks to a shared
        # BankResidencyManager — resident hits are free passes, misses and
        # evictions are priced writes.  Purely an accounting/policy layer:
        # served tokens are identical with it on or off.
        self.residency = residency
        # drift detection & repair (serve/calibration.py): an optional
        # CalibrationLoop whose on_step hook runs after the residency hook
        # each decode step — read-back happens at the ages THIS step's
        # accesses produced, mirroring hardware where verification follows
        # the compute it verifies
        self.calibration = calibration
        if admission is None and residency is not None:
            from repro.resident.cosched import ResidencyAwareAdmission
            admission = ResidencyAwareAdmission.from_base(
                ReuseAwareAdmission.build(cfg), residency)
        self.admission = admission or ReuseAwareAdmission.build(cfg)
        self.on_token = on_token
        self.on_complete = on_complete
        # data-parallel serving: the slot pool spans the data axes of the
        # Program's execution mesh, and allocation packs per-shard
        # sub-batches — see serve/slots.py
        self.mesh = self.program.mesh
        self.pool = SlotPool(cfg, capacity, max_len, mesh=self.mesh)
        # Right-padding a prefill is causally invisible to attention (masked
        # by the slot position) but NOT to recurrent state: SSM ``h`` and the
        # conv tail integrate every input token.  Models with SSM layers
        # therefore prefill at the exact prompt length (one jit per length).
        self._exact_prefill = any(
            "ssm" in spec.mixer_kinds for spec in tfm.build_segments(cfg)
            if spec.stream != "encoder")
        # chunked prefill (DESIGN.md §Prefill path): long prompts run as
        # fixed-width query chunks interleaved with decode steps, so one
        # admission never stalls in-flight decodes for a whole long prefill,
        # and the retrace family collapses to one jit per chunk width (the
        # chunk offset is a traced operand).  Attention-only stacks only:
        # SSM state and conv tails integrate every position in one scan,
        # and cross/encoder memory is not chunk-resumable.
        self.prefill_chunk = prefill_chunk
        self._chunkable = (
            prefill_chunk is not None
            and (self.mesh is None or self.mesh.size <= 1)
            and all(k == "attn"
                    for spec in tfm.build_segments(cfg)
                    if spec.stream != "encoder"
                    for k in spec.mixer_kinds))
        if prefill_chunk is not None and prefill_chunk < 1:
            raise ValueError("prefill_chunk must be >= 1")
        # slot -> in-progress chunked prefill (staging caches at pool
        # max_len, padded prompt, next chunk offset).  Slots listed here
        # are allocated but NOT decoded: the decode loop skips them until
        # their final chunk lands and write_prefill publishes the cache.
        self._prefilling: dict[int, dict] = {}
        self.queue: collections.deque[Request] = collections.deque()
        # perf_counter at submit, one per queued request in queue order:
        # the ``queued_ms`` of its ``sched.admit`` span and of the tracker
        self._queued_at: collections.deque[float] = collections.deque()
        # telemetry: an optional repro.obs.serving.ServingObs — request-
        # lifecycle latency histograms (TTFT/TPOT/e2e), Chrome-trace spans,
        # and the PhotonicMeter write-vs-reuse energy ledger.  The stats
        # counters share its registry so one snapshot carries everything.
        self.obs = telemetry
        # the program's host spans (obs.tracing.span) always reach a running
        # profiler session; this Chrome tracer also gets them when enabled
        self._tracer = telemetry.tracer if telemetry else None
        if (self.residency is not None and self.obs is not None
                and self.obs.meter is not None):
            # hand the meter's write schedule to the residency manager so
            # resident hits are never double-billed as refresh writes
            self.residency.bind_meter(self.obs.meter)
        self.stats = ContinuousStats(
            registry=telemetry.registry if telemetry else None,
            _capacity=capacity)
        self.key = jax.random.PRNGKey(seed)
        # current (unprocessed) token per slot, fed to the next decode step
        self._cur = np.full((capacity, 1), pad_id, np.int32)

    # ------------------------------------------------------------ interface
    def submit(self, req: Request) -> None:
        plen = len(req.prompt)
        if plen + req.max_new > self.pool.max_len:
            raise ValueError(
                f"request {req.rid}: prompt {plen} + max_new {req.max_new} "
                f"exceeds slot budget {self.pool.max_len}")
        if req.max_new < 1:
            raise ValueError("max_new must be >= 1")
        self.queue.append(req)
        self._queued_at.append(time.perf_counter())
        if self.obs:
            self.obs.tracker.on_submit(req.rid)

    def drain(self) -> list[Completion]:
        """Run until queue and slots are empty; completions in finish order."""
        done: list[Completion] = []
        while self.queue or self.pool.num_active:
            done.extend(self.step())
        return done

    # ------------------------------------------------------------ one step
    def step(self) -> list[Completion]:
        """Admit (policy-bounded) new requests, advance one prefill chunk
        per staging slot, then decode one token for every in-flight slot.
        Returns requests completed this step.

        Host spans (``obs.tracing.span``): ``sched.step`` around it all;
        ``sched.admit`` (children ``sched.prefill.dispatch``,
        ``sched.write_prefill``, ``sched.first_token.wait``) per admission;
        ``sched.chunk`` per prefill chunk; ``sched.decode.dispatch``,
        ``sched.decode.wait`` and ``sched.commit`` for the decode.  A
        ``*.wait`` span is the host blocked on the device; every other
        span is host work."""
        done: list[Completion] = []
        with span("sched.step", tracer=self._tracer,
                  active=self.pool.num_active, queued=len(self.queue)):
            n = self.admission.admit_count(queued=len(self.queue),
                                           free=self.pool.num_free,
                                           active=self.pool.num_active)
            for _ in range(n):
                req, t_submit = self.queue.popleft(), self._queued_at.popleft()
                comp = self._admit_one(req, t_submit)
                if comp is not None:          # max_new == 1: done at prefill
                    done.append(comp)
            if self._prefilling:
                done.extend(self._advance_chunks())
            if self.pool.num_active > len(self._prefilling):
                done.extend(self._decode_once())
            if self.obs and self.obs.tracer.enabled:
                self.obs.tracer.counter("active_slots", self.pool.num_active)
        return done

    # ------------------------------------------------------------ internals
    def _next_key(self):
        self.key, sub = jax.random.split(self.key)
        return sub

    def _bucket(self, plen: int) -> int:
        if self._exact_prefill:
            return plen
        b = self.prefill_bucket
        return min(-(-plen // b) * b, self.pool.max_len)

    def _admit_one(self, req: Request,
                   t_submit: float) -> Optional[Completion]:
        plen = len(req.prompt)
        queued_ms = (time.perf_counter() - t_submit) * 1e3
        chunked = (self._chunkable and not req.extras
                   and plen > self.prefill_chunk)
        W = self.prefill_chunk
        rows = -(-plen // W) * W if chunked else self._bucket(plen)
        with span("sched.admit", tracer=self._tracer, rid=req.rid,
                  prompt_len=plen, rows=rows, queued_ms=queued_ms):
            if chunked:
                self._start_chunked(req, rows, queued_ms)
                return None
            return self._prefill(req, rows, queued_ms)

    def _prefill(self, req: Request, bucket: int,
                 queued_ms: float) -> Optional[Completion]:
        """Monolithic prefill of one prompt at its compile bucket, straight
        into its slot; its first token is sampled here."""
        plen = len(req.prompt)
        state = SlotState(rid=req.rid, prompt_len=plen, max_new=req.max_new,
                          eos_id=req.eos_id,
                          prompt=np.asarray(req.prompt, np.int32),
                          padded_to=bucket)
        slot = self.pool.allocate(state)
        if self.obs:
            self.obs.tracker.on_admit(req.rid, plen, bucket, queued_ms)
            if self.obs.meter is not None:
                # the prefill streams `bucket` positions through the stack
                self.obs.meter.on_prefill(bucket)
        if self.residency is not None:
            # the banks must be programmed for this prefill pass: resident
            # hits ride free, misses install (priced into the meter)
            self.residency.on_prefill(bucket)
        toks = np.full((1, bucket), self.pad_id, np.int32)
        toks[0, :plen] = req.prompt
        batch = {"tokens": jnp.asarray(toks)}
        if req.extras:
            batch.update(req.extras)
        # one jitted prefill per compile bucket — the cell cache is keyed on
        # the static cache_len, shared across schedulers via repro.api
        with span("sched.prefill.dispatch", tracer=self._tracer):
            logits, caches = self.program.prefill(
                batch, bucket, last=jnp.asarray([plen - 1], jnp.int32))
        tok = self._publish(slot, caches, logits, plen)
        self.stats.requests += 1
        self.stats.prefills += 1
        self.stats.prompt_tokens += plen
        self.stats.padded_prefill_tokens += bucket - plen
        self.stats.slot_steps += bucket
        self.stats.useful_steps += plen
        return self._commit_token(slot, tok)

    def _publish(self, slot: int, caches, logits, plen: int) -> int:
        """Write a finished prefill's cache into the pool and read its first
        token back to the host."""
        with span("sched.write_prefill", tracer=self._tracer):
            self.pool.write_prefill(slot, caches, plen)
        with span("sched.first_token.wait", tracer=self._tracer):
            tok = int(np.asarray(api.sample(logits, self.cfg.vocab_size,
                                            self._next_key(),
                                            self.temperature))[0])
        self._cur[slot, 0] = tok
        return tok

    def _start_chunked(self, req: Request, padded: int,
                       queued_ms: float) -> None:
        """Allocate a slot and stage a chunked prefill: the prompt runs in
        ``prefill_chunk``-wide pieces (tail zero-padded, causally invisible),
        one chunk per scheduler step, into a batch-1 staging cache at the
        pool's max_len — so every chunk of every request reuses the one
        compiled cell per chunk width.  The slot joins the decode batch only
        when the last chunk lands (``_advance_chunks``)."""
        plen = len(req.prompt)
        state = SlotState(rid=req.rid, prompt_len=plen, max_new=req.max_new,
                          eos_id=req.eos_id,
                          prompt=np.asarray(req.prompt, np.int32),
                          padded_to=padded)
        slot = self.pool.allocate(state)
        if self.obs:
            self.obs.tracker.on_admit(req.rid, plen, padded, queued_ms)
            if self.obs.meter is not None:
                # the chunks stream `padded` positions through the stack
                self.obs.meter.on_prefill(padded)
        if self.residency is not None:
            self.residency.on_prefill(padded)
        toks = np.full((1, padded), self.pad_id, np.int32)
        toks[0, :plen] = req.prompt
        with span("sched.prefill.dispatch", tracer=self._tracer):
            caches = self.program.empty_caches(1, self.pool.max_len)
        self._prefilling[slot] = {
            "state": state, "tokens": toks, "off": 0, "caches": caches}
        self.stats.requests += 1
        self.stats.prefills += 1
        self.stats.prompt_tokens += plen
        self.stats.padded_prefill_tokens += padded - plen
        self.stats.slot_steps += padded
        self.stats.useful_steps += plen

    def _advance_chunks(self) -> list[Completion]:
        """One prefill chunk for every staging slot.  Final chunks publish:
        write the staged cache into the pool, sample the first token (TTFT
        fires here), and hand the slot to the decode loop."""
        done: list[Completion] = []
        W = self.prefill_chunk
        for slot in sorted(self._prefilling):
            st = self._prefilling[slot]
            state, off = st["state"], st["off"]
            last = off + W >= st["tokens"].shape[1]
            # plen-1 always falls inside the final (padded) chunk
            idx = state.prompt_len - 1 - off if last else W - 1
            with span("sched.chunk", tracer=self._tracer, rid=state.rid,
                      off=off, last=int(last)):
                logits, st["caches"] = self.program.prefill_chunk(
                    jnp.asarray(st["tokens"][:, off:off + W]), st["caches"],
                    off, last=jnp.asarray([idx], jnp.int32))
                st["off"] = off + W
                self.stats.prefill_chunks += 1
                if not last:
                    continue
                del self._prefilling[slot]
                tok = self._publish(slot, st["caches"], logits,
                                    state.prompt_len)
                comp = self._commit_token(slot, tok)
            if comp is not None:
                done.append(comp)
        return done

    def _commit_token(self, slot: int, tok: int) -> Optional[Completion]:
        """Record one generated token; complete/free the slot if done."""
        state = self.pool.slots[slot]
        state.tokens.append(tok)
        state.generated += 1
        self.stats.generated_tokens += 1
        if self.obs:
            # the first token comes out of prefill (TTFT); later ones are
            # decode inter-arrivals (TPOT)
            if state.generated == 1:
                self.obs.tracker.on_first_token(state.rid)
            else:
                self.obs.tracker.on_token(state.rid)
        if self.on_token is not None:
            self.on_token(state.rid, tok)
        hit_eos = state.eos_id is not None and tok == state.eos_id
        if state.generated >= state.max_new or hit_eos:
            self.pool.free(slot)
            self._cur[slot, 0] = self.pad_id
            comp = Completion(
                rid=state.rid,
                tokens=np.concatenate([state.prompt,
                                       np.asarray(state.tokens, np.int32)]),
                prompt_len=state.prompt_len, padded_to=state.padded_to,
                finish_reason="eos" if hit_eos else "length")
            if self.obs:
                self.obs.tracker.on_finish(state.rid, comp.finish_reason)
            if self.on_complete is not None:
                self.on_complete(comp)
            return comp
        return None

    def _decode_once(self) -> list[Completion]:
        tr = self._tracer
        # sched.decode.dispatch: all host work up to the decode's enqueue
        with span("sched.decode.dispatch", tracer=tr):
            # staging (chunk-prefilling) slots ride the full-pool step as
            # idle lanes: their position is 0, so the step's garbage delta
            # write at position 0 is dead data — write_prefill later
            # overwrites the whole slot — and they must not commit tokens
            # or advance
            active = [s for s in self.pool.active_slots()
                      if s not in self._prefilling]
            self.stats.observe_active(len(active))
            if self.obs and self.obs.meter is not None:
                # the fused decode step runs the FULL pool through the
                # stack — idle slots ride along padded (that waste is what
                # the occupancy histogram + idle_fraction expose)
                self.obs.meter.on_decode_step(self.pool.capacity)
            if self.residency is not None:
                self.residency.on_decode_step(self.pool.capacity)
            if self.calibration is not None:
                self.calibration.on_step()
            nxt, self.pool.caches, route = self.program.decode_sample(
                jnp.asarray(self._cur), self.pool.caches,
                self.pool.position_vector(), key=self._next_key(),
                temperature=self.temperature, routing=True)
        with span("sched.decode.wait", tracer=tr):
            nxt = np.asarray(nxt)
            if route is not None:
                # an MoE step's expert ids, ready with the tokens
                route = np.asarray(route)
        if route is not None:
            self.stats.observe_routing(
                expert_loads(route, active, self.cfg.moe.num_experts))
        self.stats.decode_steps += 1
        self.stats.slot_steps += self.pool.capacity
        self.stats.idle_slot_steps += self.pool.capacity - len(active)
        done = []
        with span("sched.commit", tracer=tr):
            for slot in active:
                # the step wrote this slot's pending token at its position
                self.pool.advance(slot)
                self.stats.useful_steps += 1
                comp = self._commit_token(slot, int(nxt[slot]))
                if comp is None:
                    self._cur[slot, 0] = int(nxt[slot])
                else:
                    done.append(comp)
        return done
