"""Set-up, the measured window and the checks of one run of one cell.

``run_cell`` is the whole run; ``run.py`` is its command line.  The
program under test is driven only through its public surface: weights go
into ``Program.build``, requests into ``ContinuousScheduler.submit`` and
``step``, and tokens come back through its ``on_token`` / ``on_complete``
callbacks.  Between steps the harness reads the scheduler's queue (which
requests a step admitted) and its ``ContinuousStats`` counters.
"""
from __future__ import annotations

import contextlib
import json
import dataclasses
import gc
import math
import shutil
import sys
import time
from pathlib import Path

import numpy as np

from cell import ROOT, Cell
from traffic import Traffic, percentile

# After the window closes, requests due in it are stepped until each has
# its first token; one that has none after this long counts as failed.
DRAIN_LIMIT_S = 60.0
# Before the window the traffic runs until as many requests have
# completed as the batch has slots, so that the window opens on a batch in
# steady state, not on the start burst of every client at once; the ramp
# stops after this long at most.
RAMP_LIMIT_S = 90.0
# The gap reported when no comparison could be made (fails every limit).
NO_COMPARISON = 1e30
# A traced run traces the last this-many seconds of its window.
TRACE_SECONDS = 10.0


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def now() -> float:
    return time.perf_counter()


class CompileClock:
    """Counts XLA backend compiles, traces to a jaxpr and persistent cache
    hits from JAX's monitoring events: any of them inside the window is
    work that set-up should have done."""
    EVENTS = {"/jax/core/compile/backend_compile_duration": "compiles",
              "/jax/core/compile/jaxpr_trace_duration": "traces",
              "/jax/compilation_cache/cache_hits": "cache_hits"}

    def __init__(self):
        import jax
        self.counts = dict.fromkeys(self.EVENTS.values(), 0)
        jax.monitoring.register_event_duration_secs_listener(self._on)
        jax.monitoring.register_event_listener(self._on)

    def _on(self, event: str, *_, **__) -> None:
        if event in self.EVENTS:
            self.counts[self.EVENTS[event]] += 1

    @property
    def count(self) -> int:
        return self.counts["compiles"]


@dataclasses.dataclass
class StepRec:
    """What one ``step()`` did, as seen from outside the scheduler."""
    t0: float
    t1: float
    admitted: list            # rids the step took off the queue
    decode_steps: int         # ContinuousStats.decode_steps delta (0 or 1)
    prefill_chunks: int       # ContinuousStats.prefill_chunks delta
    decode_tokens: int        # tokens committed by the step's decode
    idle_slot_steps: int      # ContinuousStats.idle_slot_steps delta
    cpu_s: float = 0.0        # the process's CPU time in the step
    gc_s: float = 0.0         # Python's garbage collection in the step
    traced: bool = False
    prefill: list = dataclasses.field(default_factory=list)
    # (rows, q_offset, real_rows, last) per prefill call: prefill_calls()


class Recorder:
    """Host timestamps of every request and token."""

    def __init__(self):
        self.due: dict[int, float] = {}
        self.submitted: dict[int, float] = {}
        self.prompt: dict[int, np.ndarray] = {}
        self.max_new: dict[int, int] = {}
        self.first: dict[int, float] = {}
        self.last: dict[int, float] = {}
        self.ntok: dict[int, int] = {}
        self.tok_times: list[float] = []
        self.gaps: list[tuple[float, float]] = []      # (time, gap)
        self.decode_ctx: list[tuple[float, int]] = []  # (time, keys seen)
        self.done: dict = {}                            # rid -> Completion
        self.slot: dict[int, int] = {}                  # rid -> its slot
        self.find_slot = None     # rid -> slot of a request being served
        self.step_tokens = 0

    def on_token(self, rid: int, tok: int) -> None:
        t = now()
        self.tok_times.append(t)
        n = self.ntok.get(rid, 0) + 1
        self.ntok[rid] = n
        if n == 1:
            self.first[rid] = t
            self.slot[rid] = self.find_slot(rid)
        else:
            self.gaps.append((t, t - self.last[rid]))
            self.decode_ctx.append((t, len(self.prompt[rid]) + n - 1))
            self.step_tokens += 1
        self.last[rid] = t


class Driver:
    """Feeds one scheduler from one traffic generator and logs each step."""

    def __init__(self, sched, traffic: Traffic, rec: Recorder, annotate):
        self.sched = sched
        self.traffic = traffic
        self.rec = rec
        self.annotate = annotate
        self.steps: list[StepRec] = []
        self.accepting = False
        self.next_rid = 0
        self._submitted_in_step = 0
        self.tracing = False
        rec.find_slot = self.slot_of
        self.gc_s = 0.0
        self._gc_t0 = 0.0
        gc.callbacks.append(self._on_gc)

    def _on_gc(self, phase: str, _info) -> None:
        if phase == "start":
            self._gc_t0 = now()
        else:
            self.gc_s += now() - self._gc_t0

    def close(self) -> None:
        gc.callbacks.remove(self._on_gc)

    def slot_of(self, rid: int) -> int:
        for i, st in enumerate(self.sched.pool.slots):
            if st is not None and st.rid == rid:
                return i
        raise KeyError(f"request {rid} holds no slot")

    def submit(self, t_due: float) -> None:
        from repro.serve.batcher import Request
        prompt, max_new = self.traffic.next_request()
        rid = self.next_rid
        self.next_rid += 1
        rec = self.rec
        rec.due[rid] = t_due
        rec.prompt[rid] = prompt
        rec.max_new[rid] = max_new
        with self.annotate("bench.submit"):
            self.sched.submit(Request(rid=rid, prompt=prompt,
                                      max_new=max_new))
        rec.submitted[rid] = now()
        self._submitted_in_step += 1

    def on_complete(self, comp) -> None:
        self.rec.done[comp.rid] = comp
        if self.accepting and self.traffic.closed:
            self.submit(now())

    def step(self) -> None:
        sched, stats = self.sched, self.sched.stats
        queued = [r.rid for r in sched.queue]
        d0, c0 = stats.decode_steps, stats.prefill_chunks
        i0 = stats.idle_slot_steps
        self._submitted_in_step = 0
        self.rec.step_tokens = 0
        gc0, cpu0 = self.gc_s, time.process_time()
        t0 = now()
        with self.annotate("bench.step"):
            sched.step()
        t1 = now()
        still = len(sched.queue) - self._submitted_in_step
        self.steps.append(StepRec(
            t0=t0, t1=t1, admitted=queued[:len(queued) - still],
            decode_steps=stats.decode_steps - d0,
            prefill_chunks=stats.prefill_chunks - c0,
            decode_tokens=self.rec.step_tokens,
            idle_slot_steps=stats.idle_slot_steps - i0,
            cpu_s=time.process_time() - cpu0, gc_s=self.gc_s - gc0,
            traced=self.tracing))

    def busy(self) -> bool:
        return bool(self.sched.queue) or self.sched.pool.num_active > 0


def warm_prompts(serve: dict, traffic: Traffic) -> list[int]:
    """One prompt length per compiled prefill shape the mix can reach:
    each monolithic bucket (prompts up to the chunk width), and one
    chunked prompt when the mix has longer ones."""
    W, b, L = serve["prefill_chunk"], serve["prefill_bucket"], serve["max_len"]
    lens = traffic.prompt_lengths()
    buckets = sorted({min(-(-p // b) * b, L) for p in lens if p <= W})
    out = [min(x, W) for x in buckets]
    longer = [p for p in lens if p > W]
    if longer:
        out.append(max(longer))
    return out


def make_scheduler(prog, serve: dict, driver=None, rec=None):
    from repro.serve.scheduler import ContinuousScheduler
    return ContinuousScheduler(
        prog, capacity=serve["capacity"], max_len=serve["max_len"],
        prefill_chunk=serve["prefill_chunk"],
        prefill_bucket=serve["prefill_bucket"],
        on_token=rec.on_token if rec else None,
        on_complete=driver.on_complete if driver else None)


def warm_up(prog, serve: dict, traffic: Traffic) -> None:
    """Compile every shape the window will run: a throwaway scheduler on
    the same Program serves one request per prefill shape, with two output
    tokens each so the decode cell runs too."""
    from repro.serve.batcher import Request
    sched = make_scheduler(prog, serve)
    rng = np.random.default_rng(0)
    for i, n in enumerate(warm_prompts(serve, traffic)):
        sched.submit(Request(rid=i, prompt=rng.integers(
            1, prog.cfg.vocab_size, n, dtype=np.int32), max_new=2))
    sched.drain()
    del sched
    gc.collect()


def build(cell: Cell, seed: int, traffic: Traffic):
    """Weights from the seed, the Program (bank prepared once), warm-up.
    Returns the Program."""
    import jax
    from repro.api import Program
    from repro.models import transformer as tfm
    import weights

    cfg = cell.family.program_config(cell.conf)
    t = now()
    params = weights.make_params(tfm.abstract_params(cfg), seed,
                                 jax.numpy.dtype(cfg.compute_dtype))
    log(f"setup: weights {now() - t!r} s")
    t = now()
    prog = Program.build(cfg, params)
    jax.block_until_ready(prog.bank)
    del params
    gc.collect()
    log(f"setup: Program.build {now() - t!r} s")
    t = now()
    warm_up(prog, cell.traffic["serve"], traffic)
    log(f"setup: warm-up {now() - t!r} s")
    return prog


def annotator(enabled: bool):
    if not enabled:
        return lambda name: contextlib.nullcontext()
    import jax
    return lambda name: jax.profiler.TraceAnnotation(name)


def start_trace(trace_dir: Path):
    import jax
    shutil.rmtree(trace_dir, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.enable_hlo_proto = False
    jax.profiler.start_trace(str(trace_dir), profiler_options=opts)


def run_window(driver: Driver, seconds: float, trace_dir=None):
    """The traffic: a ramp until as many requests have completed as the
    batch has slots, then the measured window.  Returns (t_start, t_begin,
    t_end, t_trace0, t_trace1): the traffic's start, the window's bounds
    and the traced part's.

    A closed loop's clients join one at a time, each once the request
    submitted last has its first token: started at once, every client's
    prompt would stage its prefill together, and each staging prompt
    holds a cache of the slot budget's length on the device."""
    import jax
    traffic, sched = driver.traffic, driver.sched
    t_trace = None
    t_start = now()
    driver.accepting = True
    waiting = 0                 # closed-loop clients yet to join
    if traffic.closed:
        waiting = traffic.spec["clients"] - 1
        driver.submit(t_start)
        next_due = math.inf
    else:
        next_due = t_start + traffic.next_gap()
    ramp = traffic.spec["serve"]["capacity"]
    t_begin = t_end = trace_from = math.inf
    while True:
        t = now()
        if t_begin == math.inf and (len(driver.rec.done) >= ramp
                                    or t >= t_start + RAMP_LIMIT_S):
            t_begin = t
            t_end = t_begin + seconds
            trace_from = t_end - min(seconds, TRACE_SECONDS)
        if t >= t_end:
            break
        if trace_dir is not None and t_trace is None and t >= trace_from:
            # work dispatched before the trace must not run inside it
            for x in jax.live_arrays():
                x.block_until_ready()
            start_trace(trace_dir)
            driver.tracing = True
            t_trace = now()
        while next_due <= t:
            driver.submit(next_due)
            next_due += traffic.next_gap()
        if waiting and driver.next_rid - 1 in driver.rec.first:
            driver.submit(t)
            waiting -= 1
        if driver.busy():
            driver.step()
        else:
            with driver.annotate("bench.wait"):
                time.sleep(max(0.0, min(next_due, t_end, t_start
                                        + RAMP_LIMIT_S) - now()))
    driver.accepting = False
    t_trace_end = None
    if t_trace is not None:
        jax.block_until_ready(sched.pool.caches)
        t_trace_end = now()
        jax.profiler.stop_trace()
        driver.tracing = False
    return t_start, t_begin, t_end, t_trace, t_trace_end


def drain_first_tokens(driver: Driver, rids) -> None:
    """Step on, submitting nothing, until every request in ``rids`` has its
    first token, or ``DRAIN_LIMIT_S`` has passed."""
    t_stop = now() + DRAIN_LIMIT_S
    while (any(r not in driver.rec.first for r in rids) and driver.busy()
           and now() < t_stop):
        driver.step()


def memory_peak() -> tuple[int, int]:
    """(peak bytes in use, bytes limit) on the fullest device."""
    import jax
    stats = [d.memory_stats() or {} for d in jax.devices()]
    return max((int(m.get("peak_bytes_in_use", 0)),
                int(m.get("bytes_limit", 0))) for m in stats)


def end_to_end(rec: Recorder, t_begin: float, t_end: float, due_rids,
               seconds: float, t_stop: float) -> dict:
    """A request with no first token by ``t_stop`` counts with the wait
    it had by then (it is also counted as failed)."""
    ttft = [(rec.first.get(r, t_stop) - rec.due[r]) * 1e3
            for r in due_rids]
    itl = [g * 1e3 for t, g in rec.gaps if t_begin <= t < t_end]
    toks = sum(1 for t in rec.tok_times if t_begin <= t < t_end)
    return {"ttft_p95_ms": percentile(ttft, 95),
            "ttft_p50_ms": percentile(ttft, 50),
            "itl_p95_ms": percentile(itl, 95),
            "itl_p50_ms": percentile(itl, 50),
            "output_tok_s": toks / seconds,
            "ttft_n": len(ttft), "itl_n": len(itl),
            "ttft_missing": sum(1 for r in due_rids if r not in rec.first)}


# =========================================================================
# per-layer metrics: the traced part of the window
# =========================================================================
def prefill_calls(steps, rec: Recorder, serve: dict) -> None:
    """Give every step record its prefill calls as ``(rows, q_offset,
    real_rows, last)``: a prompt up to the chunk width runs as one call of its
    bucket's rows; a longer one as chunk-wide calls, as many in a step as
    the step's ``prefill_chunks`` counter says, oldest staged prompt first
    (each staged prompt advances one chunk a step when the counter equals
    their number)."""
    W, b, L = serve["prefill_chunk"], serve["prefill_bucket"], serve["max_len"]
    staging: list[list[int]] = []           # [offset, padded, prompt_len]
    for st in steps:
        calls = []
        for rid in st.admitted:
            P = len(rec.prompt[rid])
            if P > W:
                staging.append([0, -(-P // W) * W, P])
            else:
                calls.append((min(-(-P // b) * b, L), 0, P, True))
        k = min(st.prefill_chunks, len(staging))
        for s in staging[:k]:
            calls.append((W, s[0], min(W, s[2] - s[0]), s[0] + W >= s[1]))
            s[0] += W
        staging = [s for s in staging if s[0] < s[1]]
        st.prefill = calls


@dataclasses.dataclass
class LayerContext:
    """Everything a per-layer metric reader (``metrics/<name>.py``) may
    read: the traced steps, the trace's reduction, the configuration and
    its family, the cell's serving parameters and the peaks."""
    conf: dict
    family: object            # families/<model_type>.py: the work count
    serve: dict
    peaks: object
    steps: list               # StepRec of the traced window, with .prefill
    trace: object             # trace_reduce.Summary
    decode_ctx: list          # keys seen by each decode token traced


def layer_context(cell: Cell, driver: Driver, summary, tt0, tt1,
                  peaks) -> LayerContext:
    rec = driver.rec
    prefill_calls(driver.steps, rec, cell.traffic["serve"])
    return LayerContext(
        conf=cell.conf, family=cell.family, serve=cell.traffic["serve"],
        peaks=peaks, steps=[s for s in driver.steps if s.traced],
        trace=summary,
        decode_ctx=[c for t, c in rec.decode_ctx if tt0 <= t <= tt1])


def read_metric(name: str, ctx: LayerContext):
    """Run ``metrics/<name>.py``'s ``read(ctx)``: a number, or None when
    the traced window held nothing for it to read."""
    import importlib.util
    path = Path(__file__).resolve().parent / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(ctx)


# =========================================================================
# correctness
# =========================================================================
def correctness(cell: Cell, seed: int, rec: Recorder,
                control_bits=None) -> dict:
    """The numbers compared, each with its limit (``check.py``).  With
    ``control_bits`` the control's widest gap is read too, under
    ``control_max_logit_gap`` (never part of a benchmark run)."""
    import check
    serve = cell.traffic["serve"]
    comps = check.sample(rec.done, rec.slot, seed)
    bad_prompt = sum(not np.array_equal(c.tokens[:c.prompt_len],
                                        rec.prompt[c.rid]) for c in comps)
    bad_len = sum(len(c.tokens) - c.prompt_len != rec.max_new[c.rid]
                  for c in comps)
    vocab = cell.conf["vocab_size"]
    bad_tok = sum(int(((c.tokens < 0) | (c.tokens >= vocab)).sum())
                  for c in comps)
    out = {"slots_sampled": {"value": check.slots_covered(comps, rec.slot),
                             "limit": serve["capacity"], "holds": ">="},
           "served_tokens_checked": {
               "value": int(sum(len(c.tokens) - c.prompt_len
                                for c in comps)), "limit": 1,
               "holds": ">="},
           "prompt_mismatches": {"value": bad_prompt, "limit": 0,
                                 "holds": "<="},
           "length_mismatches": {"value": bad_len, "limit": 0,
                                 "holds": "<="},
           "tokens_out_of_vocab": {"value": bad_tok, "limit": 0,
                                   "holds": "<="}}
    gap = NO_COMPARISON
    if comps and not bad_tok and not bad_prompt:
        t = now()
        # one reference shape per cell: a request from every slot and the
        # longest, at the slot budget's length
        shape = (serve["capacity"] + 1, serve["max_len"])
        g, ctl = check.reference_gaps(cell.family, cell.conf, seed, comps,
                                      control_bits, shape)
        log(f"reference: {len(comps)} requests, {len(g)} served tokens, "
            f"{now() - t!r} s")
        gap = float(np.max(g))
        if ctl is not None:
            out["control_max_logit_gap"] = {"value": float(np.max(ctl))}
            out["program_max_logit_gap"] = {"value": gap}
    out["max_logit_gap"] = {"value": gap, "holds": "<=",
                            "limit": cell.limits["max_logit_gap"]["limit"]}
    return out


def holds(c: dict) -> bool:
    v, lim = c["value"], c["limit"]
    return v <= lim if c["holds"] == "<=" else v >= lim


# =========================================================================
# one run
# =========================================================================
def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             t_process: float, control_bits=None) -> dict:
    """Set up, measure ``seconds``, check; returns the result line.
    ``control_bits`` also reads the control (``correctness``): the limit's
    readings, never a benchmark run."""
    import jax
    from peaks import peaks_for

    dev = jax.devices()[0]
    # the per-layer metrics need the device's peaks: a device with none
    # published is an error before any work is done
    peaks = peaks_for(dev.device_kind) if trace else None
    clock = CompileClock()
    traffic = Traffic(cell.traffic, seed, cell.conf["vocab_size"])
    prog = build(cell, seed, traffic)
    rec = Recorder()
    driver = Driver(None, traffic, rec, annotator(trace))
    driver.sched = make_scheduler(prog, cell.traffic["serve"], driver, rec)
    trace_dir = (ROOT / ".bench" / "trace" / f"{cell.name}-{seed}"
                 if trace else None)
    counts = dict(clock.counts)
    t_start, t_begin, t_end, tt0, tt1 = run_window(driver, seconds,
                                                   trace_dir)
    # the ramp already serves the traffic: set-up ends where it starts
    setup_s = t_start - t_process
    ramp_done = sum(1 for r in rec.done if rec.last[r] < t_begin)
    counts = {k: clock.counts[k] - counts[k] for k in counts}
    due = [r for r, t in rec.due.items() if t_begin <= t < t_end]
    drain_first_tokens(driver, due)
    e2e = end_to_end(rec, t_begin, t_end, due, seconds, now())
    peak, limit = memory_peak()
    log(f"device memory: peak {peak} of {limit} bytes")
    log(f"ramp: {t_begin - t_start!r} s, {ramp_done} requests completed")
    log(f"window: {len(due)} requests due, {len(rec.done)} finished, "
        f"{len(driver.steps)} steps; in the ramp and window: "
        f"{json.dumps(counts)}")
    win = [s for s in driver.steps if t_begin <= s.t0 < t_end]
    durs = [(s.t1 - s.t0) * 1e3 for s in win]
    log(f"step ms: p50 {percentile(durs, 50)!r} p95 {percentile(durs, 95)!r}"
        f" max {max(durs, default=0.0)!r}")
    for s in sorted(win, key=lambda s: s.t0 - s.t1)[:3]:
        log(f"slow step: {(s.t1 - s.t0) * 1e3!r} ms (process CPU "
            f"{s.cpu_s * 1e3!r} ms, GC {s.gc_s * 1e3!r} ms) at "
            f"{s.t0 - t_begin!r} s: admitted prompts "
            f"{[len(rec.prompt[r]) for r in s.admitted]}, "
            f"{s.prefill_chunks} chunks, {s.decode_steps} decode")
    if not traffic.closed:
        late = [(rec.submitted[r] - rec.due[r]) * 1e3 for r in due]
        log(f"generator lateness ms: p50 {percentile(late, 50)!r} p95 "
            f"{percentile(late, 95)!r} max {max(late, default=0.0)!r}")
    log("end to end: " + json.dumps(e2e))
    # free the program before the reference runs
    driver.close()
    driver.sched = None
    del prog
    gc.collect()

    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": peak}
    metrics, breakdown = {}, None
    if trace:
        import trace_reduce
        summary = trace_reduce.reduce_dir(trace_dir)
        shutil.rmtree(trace_dir, ignore_errors=True)
        ctx = layer_context(cell, driver, summary, tt0, tt1, peaks)
        for m in cell.per_layer:
            v = read_metric(m["name"], ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        breakdown = {"device_ops": summary.top_ops[:10],
                     "idle_gaps": summary.idle_gaps[:10]}
        log("per layer: " + json.dumps(metrics))
    else:
        values = dict(e2e, setup_s=setup_s)
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": values[m["name"]],
                                  "unit": m["unit"]}
    checks = correctness(cell, seed, rec, control_bits)
    readings = {}
    for side in ("program", "control"):
        for k in [k for k in checks if k.startswith(side + "_")]:
            readings.setdefault(side, {})[k[len(side) + 1:]] = \
                checks.pop(k)["value"]
    correct = all(holds(c) for c in checks.values())
    result = {"correct": correct, "attempted": len(due),
              "failed": e2e["ttft_missing"], "metrics": metrics,
              "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    if readings:
        result["program"] = readings["program"]
        result["control"] = dict(readings["control"], bits=control_bits)
    result["checks"] = {k: {"value": c["value"], "limit": c["limit"]}
                        for k, c in checks.items()}
    for k, c in checks.items():
        log(f"check {k}: {c['value']!r} {c['holds']} {c['limit']!r} "
            f"{'ok' if holds(c) else 'FAILED'}")
    return result
