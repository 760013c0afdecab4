"""Serving driver: compile-once Program + continuous-batching scheduler.

The model is built into ONE :class:`repro.api.Program` (backend resolved,
photonic weight banks prepared at build time) and every scheduler serves
from it — no per-request backend resolution or weight re-quantization.

CPU-scale example:
  PYTHONPATH=src python -m repro.launch.serve --arch mamba2-780m --smoke \\
      --requests 12 --max-prompt 32 --new-tokens 16

``--scheduler`` picks the serving path:
  continuous  slot-level continuous batching (default; serve/scheduler.py)
  wave        static aligned waves (fallback; serve/batcher.py)
  engine      one aligned batch straight through Program.generate
``--execution`` picks the matmul substrate (xla | photonic).
``--mesh`` picks the execution mesh: ``auto`` builds the largest
(data, model) mesh from the available devices (launch/mesh.py), ``DxM``
(e.g. ``2x2``) pins a shape, omitted = single-device.  The slot pool then
spans the data axis and TP-sharded matmuls run the Pallas kernels
per-shard (DESIGN.md §Sharded execution).
"""
from __future__ import annotations

import argparse
import json
import time
import warnings

import numpy as np

import jax
import jax.numpy as jnp

from repro.api import Program
from repro.configs import get_arch, smoke_variant
from repro.launch import mesh as mesh_lib
from repro.launch.compile_cache import enable_compile_cache
from repro.models import transformer as tfm
from repro.obs import metrics as metrics_lib
from repro.obs.serving import ServingObs
from repro.sharding import partition
from repro.serve.batcher import Request, WaveBatcher
from repro.serve.scheduler import ContinuousScheduler


def _request_extras(cfg, rid: int):
    if cfg.family == "vlm":
        v = cfg.vision
        return {"image_embeds": jax.random.normal(
            jax.random.PRNGKey(100 + rid), (1, v.num_image_tokens,
                                            v.d_vision))}
    if cfg.family == "audio":
        a = cfg.audio
        return {"audio_embeds": jax.random.normal(
            jax.random.PRNGKey(100 + rid), (1, a.num_frames, a.d_audio))}
    return None


def _make_trace(cfg, n: int, max_prompt: int, max_new: int, seed: int = 0):
    """Mixed-length request trace (the realistic serving distribution)."""
    rng = np.random.default_rng(seed)
    reqs = []
    for rid in range(n):
        plen = int(rng.integers(max(2, max_prompt // 4), max_prompt + 1))
        mn = int(rng.integers(max(1, max_new // 4), max_new + 1))
        reqs.append(Request(
            rid=rid, prompt=rng.integers(1, cfg.vocab_size, plen
                                         ).astype(np.int32),
            max_new=mn, extras=_request_extras(cfg, rid)))
    return reqs


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--reuse", action="store_true")
    ap.add_argument("--scheduler", default="continuous",
                    choices=["continuous", "wave", "engine"])
    ap.add_argument("--requests", type=int, default=12)
    ap.add_argument("--capacity", type=int, default=4,
                    help="slot-pool capacity / wave size")
    ap.add_argument("--max-prompt", type=int, default=32)
    ap.add_argument("--new-tokens", type=int, default=16)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--execution", default=None,
                    choices=["xla", "photonic"],
                    help="matmul substrate override (default: cfg.execution)")
    ap.add_argument("--mesh", default=None,
                    help="execution mesh: 'auto' (largest (data, model) "
                         "mesh from available devices), 'DxM' (e.g. 2x2), "
                         "or omit for single-device")
    ap.add_argument("--array-budget", type=int, default=0,
                    help="MRR array budget in 128x128-tile units for the "
                         "global bank residency manager (repro.resident): "
                         "layers hybrid-map into resident (stay programmed)"
                         " vs streamed (reprogram-per-pass) sets under the "
                         "budget.  0 = off (all banks statically resident, "
                         "the legacy accounting)")
    ap.add_argument("--noise", default=None,
                    help="photonic fault model (core/noise.py), e.g. "
                         "'gain=0.01,ct=0.002,dac=0.25,drift=0.05': per-tile"
                         " gain error, crosstalk, DAC noise, write-age "
                         "drift.  Single-device photonic only; default off "
                         "(bit-identical clean path)")
    ap.add_argument("--calibrate-every", type=int, default=0,
                    help="decode steps between calibration read-back sweeps"
                         " (serve/calibration.py): stale resident banks are"
                         " re-programmed and billed as calibration writes. "
                         "0 = no calibration loop.  Needs --noise")
    ap.add_argument("--stale-threshold", type=float, default=0.01,
                    help="read-back gain error above which a bank is "
                         "re-programmed by the calibration loop")
    ap.add_argument("--stats", action="store_true",
                    help="enable telemetry: periodic stats line (TTFT/TPOT "
                         "p50/p95, slot occupancy, reuse ratio, write "
                         "energy saved) + final energy report")
    ap.add_argument("--stats-every", type=int, default=8,
                    help="scheduler steps between stats lines")
    ap.add_argument("--trace-out", default=None,
                    help="write a Chrome-trace JSON (chrome://tracing) here")
    ap.add_argument("--metrics-out", default=None,
                    help="write the metrics JSON snapshot "
                         "(benchmarks/metrics_schema.json shape) here")
    args = ap.parse_args(argv)
    enable_compile_cache()
    dev = jax.devices()[0]
    where = f"on {dev.platform} {dev.device_kind}"
    cfg = smoke_variant(args.arch) if args.smoke else get_arch(
        args.arch, reuse=args.reuse)
    mesh = None
    if args.mesh == "auto":
        mesh = mesh_lib.make_mesh_auto()
    elif args.mesh:
        mesh = mesh_lib.parse_mesh(args.mesh)
    if args.calibrate_every and not args.noise:
        raise SystemExit("--calibrate-every needs --noise (nothing drifts "
                         "on the clean path)")
    execution = args.execution
    noise_cfg = None
    if args.noise:
        from repro.core import backend as backend_lib
        from repro.core.noise import NoiseConfig
        noise_cfg = NoiseConfig.parse(args.noise)
        exec_name = args.execution or cfg.execution
        if exec_name != "photonic":
            raise SystemExit("--noise models the photonic substrate; pass "
                             "--execution photonic")
        # Backend.__post_init__ rejects noise + multi-device mesh
        execution = backend_lib.Backend("photonic", noise=noise_cfg)
        print(f"[serve] photonic fault model on: {noise_cfg}")
    params, _ = tfm.init_model(jax.random.PRNGKey(0), cfg)
    # compile once: backend + (photonic) prepared weight banks + mesh —
    # surfacing any partition rules that were dropped (replicated) so
    # misdivided dims are visible in the serving log
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        prog = Program.build(cfg, params, execution=execution,
                             mesh=mesh)
    for w in caught:
        print(f"[serve] WARNING {w.message}")
    if mesh is not None:
        print(f"[serve] execution mesh {dict(mesh.shape)} "
              f"({mesh.size} devices)")
    if prog.backend.is_photonic:
        st = prog.bank_stats()
        print(f"[serve] photonic banks prepared once: "
              f"{st['programmed_tensors']} tensors, "
              f"{st['int8_bytes'] / 1e6:.2f} MB int8, "
              f"{st['mrr_tiles_128']} MRR tiles")

    # telemetry bundle: one registry + tracer + request tracker + photonic
    # meter, threaded through the scheduler (repro.obs)
    obs = None
    if args.stats or args.trace_out or args.metrics_out:
        obs = ServingObs.create(cfg, trace=bool(args.trace_out)
                                or args.stats)
        metrics_lib.enable()

    # global bank residency: bounded MRR array, hybrid layer mapping,
    # cost-model eviction (repro.resident; DESIGN.md §Bank residency)
    residency = None
    if args.array_budget:
        from repro import resident
        from repro.obs.meter import StackProfile
        specs = resident.specs_from_program(prog)
        if not specs:        # xla execution: no prepared bank — use the
            specs = resident.specs_from_profile(   # arch's stack profile
                StackProfile.from_cfg(cfg), prefix=cfg.name)
        plan = resident.plan_hybrid_mapping(specs, args.array_budget)
        manager = resident.BankResidencyManager(
            args.array_budget, registry=obs.registry if obs else None)
        residency = resident.ProgramResidency(manager, specs, plan=plan)
        print(f"[serve] residency: array budget {args.array_budget} "
              f"x128-tiles, {len(plan.resident)}/{len(specs)} banks "
              f"resident ({plan.used_tiles} tiles), hybrid-map est "
              f"E -{plan.energy_savings_frac:.1%} / "
              f"T -{plan.latency_savings_frac:.1%} vs stream-all")
        if args.scheduler != "continuous":
            print("[serve] WARNING --array-budget only drives the "
                  "continuous scheduler; ignoring")
            residency = None

    # calibration read-back loop: drift detection & repair over the
    # resident banks (serve/calibration.py; needs --noise for a drift
    # source and the continuous scheduler for the step hook)
    calibration = None
    if args.calibrate_every and args.scheduler == "continuous":
        from repro import resident
        from repro.serve.calibration import CalibrationLoop
        if residency is None:
            # the loop verifies RESIDENT banks — with no --array-budget,
            # bind the Program's banks to an unbounded manager (everything
            # statically resident, the legacy accounting)
            specs = resident.specs_from_program(prog)
            manager = resident.BankResidencyManager(
                10 ** 9, registry=obs.registry if obs else None)
            residency = resident.ProgramResidency(manager, specs)
        calibration = CalibrationLoop(
            prog, residency.manager, noise=noise_cfg,
            every_steps=args.calibrate_every,
            stale_threshold=args.stale_threshold,
            meter=obs.meter if obs else None,
            registry=obs.registry if obs else None)
        print(f"[serve] calibration loop: sweep every "
              f"{args.calibrate_every} steps, stale threshold "
              f"{args.stale_threshold}")
    elif args.calibrate_every:
        print("[serve] WARNING --calibrate-every only drives the "
              "continuous scheduler; ignoring")

    if args.scheduler == "engine":
        prompt = jax.random.randint(jax.random.PRNGKey(1),
                                    (args.capacity, args.max_prompt), 1,
                                    cfg.vocab_size)
        extras = _request_extras(cfg, 0)
        if extras:
            extras = {k: jnp.repeat(v, args.capacity, axis=0)
                      for k, v in extras.items()}
        t0 = time.time()
        out = prog.generate(prompt, args.new_tokens, extras=extras,
                            temperature=args.temperature)
        dt = time.time() - t0
        n_new = args.capacity * args.new_tokens
        print(f"[serve/engine] {cfg.name}: {n_new} tokens in {dt:.2f}s "
              f"({n_new / dt:.1f} tok/s {where})")
        print("sample row:", out[0, :].tolist()[:48])
        return

    reqs = _make_trace(cfg, args.requests, args.max_prompt, args.new_tokens)
    if args.scheduler == "wave":
        sched = WaveBatcher(prog, wave_size=args.capacity,
                            temperature=args.temperature, telemetry=obs)
    else:
        capacity = args.capacity
        if mesh is not None:
            # one per-shard sub-batch per data shard: round capacity up
            dp = partition.dp_size(mesh)
            capacity = -(-capacity // dp) * dp
            if capacity != args.capacity:
                print(f"[serve] capacity {args.capacity} -> {capacity} "
                      f"(divides over {dp} data shard(s))")
        sched = ContinuousScheduler(
            prog, capacity=capacity,
            max_len=args.max_prompt + args.new_tokens,
            temperature=args.temperature, telemetry=obs,
            residency=residency, calibration=calibration)
    for r in reqs:
        sched.submit(r)
    t0 = time.time()
    if args.scheduler == "continuous" and obs is not None and args.stats:
        # step-driven drain so the periodic stats line interleaves with
        # serving (the long-running-server view of the same loop)
        comps = []
        step_i = 0
        while sched.queue or sched.pool.num_active:
            comps.extend(sched.step())
            step_i += 1
            if step_i % max(1, args.stats_every) == 0:
                print(obs.stats_line(sched.stats, step=step_i))
    else:
        comps = sched.drain()
    dt = time.time() - t0
    st = sched.stats
    gen = st.generated_tokens
    print(f"[serve/{args.scheduler}] {cfg.name}: {len(comps)} requests, "
          f"{gen} new tokens in {dt:.2f}s ({gen / dt:.1f} tok/s {where})")
    print(f"  slot-steps executed {st.slot_steps}, useful {st.useful_steps}, "
          f"overhead {st.overhead:.1%}")
    rr = residency.manager.report() if residency is not None else None
    if obs is not None:
        if args.stats:
            print(obs.stats_line(getattr(sched, "stats", None)))
            if obs.meter is not None:
                rep = obs.meter.report()
                print(f"  energy: {rep['bank_writes']} bank writes, "
                      f"{rep['matrix_passes']} matrix passes, "
                      f"reuse {rep['reuse_ratio']:.3f}, amortization "
                      f"{rep['amortization_passes_per_write']:.1f} "
                      f"passes/write, saved "
                      f"{rep['write_energy_saved_uJ']:.1f} uJ write energy "
                      f"(-{rep['energy_savings_frac']:.1%} E, "
                      f"-{rep['latency_savings_frac']:.1%} T vs "
                      f"reprogram-per-pass)")
            if rr is not None:
                print(f"  residency: hit rate {rr['hit_rate']:.3f} "
                      f"({rr['hits']}/{rr['hits'] + rr['misses']} lookups),"
                      f" {rr['evictions']} evictions, occupancy "
                      f"{rr['used_tiles']}/{rr['budget_tiles']} tiles "
                      f"({rr['occupancy_frac']:.0%}), endurance gain "
                      f"{rr['endurance']['endurance_gain']:.1f}x")
            if calibration is not None:
                cr = calibration.report()
                print(f"  calibration: {cr['sweeps']} sweeps, "
                      f"{cr['rechecks']} rechecks, {cr['reprograms']} "
                      f"reprograms, last sweep {cr['stale_banks']} stale / "
                      f"max read-back err {cr['max_readback_err']:.4f}")
        if args.trace_out:
            obs.tracer.save(args.trace_out)
            print(f"[serve] Chrome trace -> {args.trace_out} "
                  f"({len(obs.tracer.events)} events)")
        if args.metrics_out:
            with open(args.metrics_out, "w") as f:
                json.dump(obs.snapshot(), f, indent=1)
            print(f"[serve] metrics snapshot -> {args.metrics_out}")
        metrics_lib.disable()
    comps.sort(key=lambda c: c.rid)
    if comps:
        print("  first completion:", comps[0].tokens.tolist()[:48])


if __name__ == "__main__":
    main()
