"""Wave-scheduling request batcher for the serving engine.

Groups queued requests into fixed-size *waves* (padding prompts to the wave
maximum), runs one prefill + shared decode loop per wave through
``serve.engine``, and tracks padding efficiency — the production pattern for
aligned-batch engines whose decode step shares a single position counter
(ours does: the PRM cache layout keeps all slots in lockstep).

This is deliberately a *static* scheduler: requests never join a running
wave.  It is kept as the simple fallback behind the shared ``Scheduler``
protocol; the production path is ``serve.scheduler.ContinuousScheduler``,
which decodes with per-slot positions over a ``serve.slots.SlotPool``
(DESIGN.md §Serving).
"""
from __future__ import annotations

import dataclasses
import time
from typing import Optional

import numpy as np

import jax.numpy as jnp

from repro import api
from repro.configs.base import ModelConfig
# WaveStats lives in the shared stats protocol (repro.obs.stats) now —
# re-exported here so historical imports keep working
from repro.obs.stats import WaveStats as WaveStats  # noqa: F401
from repro.obs.tracing import span


@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray             # (prompt_len,) int32
    max_new: int
    extras: Optional[dict] = None
    eos_id: Optional[int] = None   # early stop (continuous scheduler only)


@dataclasses.dataclass
class Completion:
    rid: int
    tokens: np.ndarray             # (prompt_len + n_generated,)
    prompt_len: int
    padded_to: int
    finish_reason: str = "length"  # length | eos


class WaveBatcher:
    """Admit requests, emit completions wave by wave.

    ``telemetry`` (an optional :class:`repro.obs.serving.ServingObs`)
    shares the registry with ``self.stats`` and adds request-lifecycle
    latency histograms + wave spans in the Chrome trace.  Waves run as one
    blocking ``generate``, so per-request TTFT inside a wave is not
    observable — the tracker records admission at wave start and completion
    at wave end (the continuous scheduler is the per-token path).
    """

    def __init__(self, params, cfg: ModelConfig = None, wave_size: int = 8,
                 pad_id: int = 0, temperature: float = 0.0,
                 telemetry=None):
        # accepts a prebuilt ``api.Program`` (compile-once entry) or the
        # legacy (params, cfg) pair
        if isinstance(params, api.Program):
            self.program = params
            cfg = params.cfg
        else:
            if cfg is None:
                raise ValueError("WaveBatcher(params, cfg) needs the model "
                                 "config (or pass a prebuilt Program)")
            self.program = api.Program.build(cfg, params)
        self.cfg = cfg
        self.wave_size = wave_size
        self.pad_id = pad_id
        self.temperature = temperature
        self.queue: list[Request] = []
        self._submitted: dict[int, float] = {}     # rid -> perf_counter
        self.obs = telemetry
        self.stats = WaveStats(
            registry=telemetry.registry if telemetry else None)

    def submit(self, req: Request) -> None:
        self.queue.append(req)
        self._submitted[req.rid] = time.perf_counter()
        if self.obs:
            self.obs.tracker.on_submit(req.rid)

    @staticmethod
    def _extras_match(a: Optional[dict], b: Optional[dict]) -> bool:
        """Wave-compatible extras: same keys, identical arrays.  A wave runs
        ONE batched prefill, so per-request modality inputs (image/audio
        embeddings) can only share a wave when they are equal."""
        if (a is None) != (b is None):
            return False
        if a is None:
            return True
        if set(a) != set(b):
            return False
        return all(np.array_equal(np.asarray(a[k]), np.asarray(b[k]))
                   for k in a)

    def _form_wave(self) -> list[Request]:
        # group by matching extras (never silently apply request 0's extras
        # to the whole wave), then longest-prompt-first within the queue
        # head window to minimize padding
        head = self.queue[0]
        window = [r for r in self.queue[:4 * self.wave_size]
                  if self._extras_match(r.extras, head.extras)]
        window.sort(key=lambda r: -len(r.prompt))
        wave = window[:self.wave_size]
        for r in wave:
            self.queue.remove(r)
        return wave

    def _run_wave(self, wave: list[Request]) -> list[Completion]:
        B = len(wave)
        max_prompt = max(len(r.prompt) for r in wave)
        max_new = max(r.max_new for r in wave)
        prompts = np.full((B, max_prompt), self.pad_id, np.int32)
        for i, r in enumerate(wave):
            # left-pad so every prompt ends at the same position (the
            # aligned decode then starts all slots together)
            prompts[i, max_prompt - len(r.prompt):] = r.prompt
        extras = wave[0].extras      # every wave member matches (_form_wave)
        t = time.perf_counter()
        queued_ms = {r.rid: (t - self._submitted.pop(r.rid)) * 1e3
                     for r in wave}
        if self.obs:
            for r in wave:
                self.obs.tracker.on_admit(r.rid, len(r.prompt), max_prompt,
                                          queued_ms[r.rid])
            if self.obs.meter is not None:
                self.obs.meter.on_prefill(B * max_prompt)
        tr = self.obs.tracer if self.obs else None
        with span("wave", tracer=tr, requests=B, max_prompt=max_prompt,
                  max_new=max_new):
            out = self.program.generate(jnp.asarray(prompts), max_new,
                                        extras=extras,
                                        temperature=self.temperature)
        if self.obs and self.obs.meter is not None:
            for _ in range(max_new - 1):
                self.obs.meter.on_decode_step(B)
        out = np.asarray(out)
        comps = []
        for i, r in enumerate(wave):
            toks = out[i, max_prompt - len(r.prompt):
                       max_prompt + r.max_new]
            comps.append(Completion(rid=r.rid, tokens=toks,
                                    prompt_len=len(r.prompt),
                                    padded_to=max_prompt))
            if self.obs:
                self.obs.tracker.on_finish(r.rid)
            self.stats.prompt_tokens += len(r.prompt)
            self.stats.padded_tokens += max_prompt - len(r.prompt)
            self.stats.generated_tokens += r.max_new
            # processed positions: the prompt, plus one decode lane-step per
            # generated token after the first (the first comes from prefill)
            self.stats.useful_steps += len(r.prompt) + r.max_new - 1
        self.stats.waves += 1
        self.stats.requests += B
        self.stats.slot_steps += B * (max_prompt + max_new - 1)
        return comps

    def drain(self) -> list[Completion]:
        """Run everything queued; returns completions in wave order."""
        done: list[Completion] = []
        while self.queue:
            wave = self._form_wave()
            done.extend(self._run_wave(wave))
        return done
