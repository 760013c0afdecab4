import os
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count="
    + os.environ.get("REPRO_SHARD_DEVICES", "8"))

DOC = """Sharded-vs-single-device parity checker (run in a fresh process).

Forces host-platform devices *before* importing jax (same trick as
launch/dryrun.py), then runs the mesh-native execution path end to end on a
small model and gates it against the single-device reference:

  * ``--mesh DxM`` — build a (data, model) host mesh, run Program prefill +
    decode through it, and require rel-L2 <= --tol (the established W8A8
    parity bound, 0.055) against the UNSHARDED reference program;
  * a 1x1 mesh must be BIT-identical to the unsharded path, and repeated
    sharded steps must not retrace (the api.TRACE_COUNTS gate);
  * ``--serve`` — data-parallel continuous batching over the mesh: greedy
    completions must be token-identical to unsharded solo generation;
  * ``--check-dropped`` — a deliberately misdivided dim must surface the
    one-line PartitionReport warning from Program.build;
  * ``--collectives`` — row-parallel collective equivalence gates:
    ``reduce_scatter`` must be BIT-identical to the legacy ``psum`` at the
    same tile plan (same adds, different placement), ``ring`` must sit
    within fp noise, the post-scatter epilogue (bias / fused activation /
    blocked shuffle) must match the unsharded backend, bf16 row-parallel
    matmuls must reduce their partials in f32, and the pipelined decode
    cell must not retrace across repeated steps.

Usage (tests/test_sharded_backend.py and the CI sharded-smoke job):
  REPRO_SHARD_DEVICES=8 python -m repro.launch.shardcheck \\
      --mesh 2x2 --execution photonic --serve
"""

import argparse  # noqa: E402  (XLA_FLAGS must precede all jax imports)
import sys
import warnings

import numpy as np

import jax
import jax.numpy as jnp

from repro import api
from repro.api import Program
from repro.configs.base import ModelConfig
from repro.launch import mesh as mesh_lib
from repro.models import transformer as tfm
from repro.sharding import partition


def _rel_l2(a, b):
    a = np.asarray(a, np.float32)
    b = np.asarray(b, np.float32)
    return float(np.linalg.norm(a - b) / (np.linalg.norm(b) + 1e-9))


def small_cfg(**kw):
    return ModelConfig(name="shard-t", family="dense", num_layers=2,
                       d_model=32, num_heads=4, num_kv_heads=2, d_ff=64,
                       vocab_size=128, compute_dtype="float32", **kw)


def check_parity(mesh_shape, execution: str, tol: float) -> list:
    """Sharded Program vs unsharded reference on one mesh shape."""
    fails = []
    cfg = small_cfg()
    params, _ = tfm.init_model(jax.random.PRNGKey(0), cfg)
    B, S, L = 4, 8, 14
    toks = jax.random.randint(jax.random.PRNGKey(1), (B, S), 1,
                              cfg.vocab_size)
    ref = Program.build(cfg, params, execution=execution)
    lr, cr = ref.prefill({"tokens": toks}, L)
    dr, _ = ref.decode(toks[:, :1], cr, S)

    mesh = mesh_lib.parse_mesh(mesh_shape)
    prog = Program.build(cfg, params, execution=execution, mesh=mesh)
    lp, cp = prog.prefill({"tokens": toks}, L)
    dp_, cp = prog.decode(toks[:, :1], cp, S)
    rel_p, rel_d = _rel_l2(lp, lr), _rel_l2(dp_, dr)
    print(f"[shardcheck] mesh {dict(mesh.shape)} {execution}: "
          f"prefill rel-L2 {rel_p:.5f}, decode rel-L2 {rel_d:.5f} "
          f"(tol {tol})")
    if rel_p > tol or rel_d > tol:
        fails.append(f"parity {mesh_shape}: rel-L2 prefill {rel_p:.5f} / "
                     f"decode {rel_d:.5f} > {tol}")

    # repeated sharded steps must hit the shared jit cells — no retrace
    before = dict(api.TRACE_COUNTS)
    l2, c2 = prog.prefill({"tokens": toks}, L)
    _, c2 = prog.decode(toks[:, :1], c2, S)
    prog2 = Program.build(cfg, params, execution=execution, mesh=mesh)
    prog2.prefill({"tokens": toks}, L)
    if dict(api.TRACE_COUNTS) != before:
        fails.append(f"retrace on repeated sharded calls: "
                     f"{before} -> {dict(api.TRACE_COUNTS)}")
    del l2

    # the 1x1 mesh is the no-op default: BIT-identical to unsharded
    one = Program.build(cfg, params, execution=execution,
                        mesh=mesh_lib.single_device_mesh())
    lo, co = one.prefill({"tokens": toks}, L)
    do, _ = one.decode(toks[:, :1], co, S)
    if not (np.array_equal(np.asarray(lo), np.asarray(lr))
            and np.array_equal(np.asarray(do), np.asarray(dr))):
        fails.append("1x1 mesh not bit-identical to the unsharded path")
    else:
        print("[shardcheck] 1x1 mesh bit-identical to unsharded: ok")
    return fails


def check_serve(mesh_shape, execution: str) -> list:
    """DP continuous batching over the mesh == unsharded solo generate."""
    from repro.serve.batcher import Request
    from repro.serve.scheduler import ContinuousScheduler

    fails = []
    cfg = small_cfg()
    params, _ = tfm.init_model(jax.random.PRNGKey(0), cfg)
    mesh = mesh_lib.parse_mesh(mesh_shape)
    dp = partition.dp_size(mesh)
    prog = Program.build(cfg, params, execution=execution, mesh=mesh)
    sched = ContinuousScheduler(prog, capacity=max(4, dp), max_len=24)
    rng = np.random.default_rng(7)
    reqs = [Request(rid=rid,
                    prompt=rng.integers(1, cfg.vocab_size,
                                        int(rng.integers(3, 9))
                                        ).astype(np.int32),
                    max_new=int(rng.integers(2, 5)))
            for rid in range(6)]
    for r in reqs:
        sched.submit(r)
    comps = {c.rid: c for c in sched.drain()}
    ref = Program.build(cfg, params, execution=execution)
    bad = []
    for r in reqs:
        solo = np.asarray(ref.generate(jnp.asarray(r.prompt)[None, :],
                                       r.max_new))[0]
        if not np.array_equal(comps[r.rid].tokens, solo):
            bad.append(r.rid)
    if bad:
        fails.append(f"DP serving tokens diverge from solo generate: "
                     f"rids {bad}")
    else:
        print(f"[shardcheck] DP serving over {dict(mesh.shape)}: "
              f"{len(reqs)} requests token-identical to solo generate")
    return fails


def check_dropped() -> list:
    """A misdivided dim must surface the one-line replication warning."""
    # 30 head channels / 90-wide d_ff do not divide a 4-wide model axis ->
    # those rules drop to replicated and Program.build must say so
    cfg = ModelConfig(
        name="shard-drop", family="dense", num_layers=2, d_model=30,
        num_heads=3, num_kv_heads=3, d_ff=90, vocab_size=128,
        compute_dtype="float32")
    params, _ = tfm.init_model(jax.random.PRNGKey(0), cfg)
    mesh = mesh_lib.make_mesh((1, 4), ("data", "model"))
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        Program.build(cfg, params, mesh=mesh)
    msgs = [str(w.message) for w in caught
            if "rule(s) dropped" in str(w.message)]
    if not msgs:
        return ["no dropped-rule warning from Program.build on a "
                "misdivided mesh"]
    print(f"[shardcheck] dropped-rule warning surfaced: {msgs[0]}")
    return []


def check_collectives(mesh_shape, execution: str, tol: float) -> list:
    """Row-parallel collective equivalence gates (the reduce-scatter path).

    ``reduce_scatter`` reorders *placement*, not arithmetic: each shard
    reduces the same per-shard partials ``psum`` would, so it must be
    bit-identical.  ``ring`` runs tp chunk-kernels instead of one full
    kernel, which re-associates XLA's elementwise fusion — fp-noise
    equivalent (~1 ulp), gated tightly but not bitwise.
    """
    from repro.core import backend as backend_lib

    fails = []
    mesh = mesh_lib.parse_mesh(mesh_shape)
    tp = dict(mesh.shape).get("model", 1)
    kx, kw, kb = jax.random.split(jax.random.PRNGKey(3), 3)
    B, K, N = 4, 64, 64
    x = jax.random.normal(kx, (B, 1, K), dtype=jnp.float32)
    w = jax.random.normal(kw, (K, N), dtype=jnp.float32) / float(np.sqrt(K))
    bias = jax.random.normal(kb, (N,), dtype=jnp.float32)
    block = 16
    perm = tuple(int(i) for i in
                 np.random.default_rng(5).permutation(N // block))
    bks = {c: backend_lib.Backend(execution, mesh=mesh, tp_collective=c)
           for c in backend_lib.TP_COLLECTIVES}
    ref_bk = backend_lib.Backend(execution)

    def run(bk, **kw):
        return np.asarray(
            jax.jit(lambda xx: bk.dot(xx, w, tp_hint="row", **kw))(x))

    cases = [("plain", {}),
             ("bias+silu", dict(bias=bias, activation="silu")),
             ("blend-shuffle", dict(bias=bias, block_perm=perm,
                                    block=block))]
    for label, kw in cases:
        rule = backend_lib.partition_rule(
            tp, K, N, block_perm=kw.get("block_perm"), tp_hint="row",
            collective="reduce_scatter")
        y_ref = np.asarray(
            jax.jit(lambda xx: ref_bk.dot(xx, w, tp_hint="row", **kw))(x))
        y_psum = run(bks["psum"], **kw)
        y_scat = run(bks["reduce_scatter"], **kw)
        y_ring = run(bks["ring"], **kw)
        if not np.array_equal(y_scat, y_psum):
            fails.append(f"collectives[{label}]: reduce_scatter not "
                         f"bit-identical to psum (rule={rule})")
        rel_ring = _rel_l2(y_ring, y_psum)
        if rel_ring > 1e-5:
            fails.append(f"collectives[{label}]: ring vs psum rel-L2 "
                         f"{rel_ring:.2e} > 1e-5")
        rel_ref = _rel_l2(y_scat, y_ref)
        if rel_ref > 1e-5:
            fails.append(f"collectives[{label}]: sharded epilogue vs "
                         f"unsharded rel-L2 {rel_ref:.2e} > 1e-5")
        if not fails:
            print(f"[shardcheck] collectives[{label}] rule={rule}: "
                  f"scatter==psum bitwise, ring rel-L2 {rel_ring:.1e}, "
                  f"vs-unsharded rel-L2 {rel_ref:.1e}")

    # bf16 activations: each shard's partial MVM reaches the reduction in
    # f32, as the single-device kernel's accumulator does; partials rounded
    # to bf16 first sit ~1 bf16 ulp (4e-3) off the unsharded result
    xb, wb = x.astype(jnp.bfloat16), w.astype(jnp.bfloat16)
    y_ref = jax.jit(lambda xx: ref_bk.dot(xx, wb, tp_hint="row"))(xb)
    for c, bk in bks.items():
        y = jax.jit(lambda xx: bk.dot(xx, wb, tp_hint="row"))(xb)
        rel_bf16 = _rel_l2(np.asarray(y, np.float32),
                           np.asarray(y_ref, np.float32))
        if rel_bf16 > 1e-3:
            fails.append(f"collectives[bf16 {c}]: sharded vs unsharded "
                         f"rel-L2 {rel_bf16:.2e} > 1e-3")
        elif not fails:
            print(f"[shardcheck] collectives[bf16 {c}]: sharded vs "
                  f"unsharded rel-L2 {rel_bf16:.1e}")

    # --- whole-model decode: scatter vs psum logits + zero-retrace gate ---
    cfg = small_cfg()
    params, _ = tfm.init_model(jax.random.PRNGKey(0), cfg)
    B, S, L = 4, 8, 14
    toks = jax.random.randint(jax.random.PRNGKey(1), (B, S), 1,
                              cfg.vocab_size)
    logits = {}
    prog = None
    for c in ("psum", "reduce_scatter", "ring"):
        prog = Program.build(cfg, params, execution=bks[c])
        lp, cache = prog.prefill({"tokens": toks}, L)
        d, cache = prog.decode(toks[:, :1], cache, S)
        logits[c] = (np.asarray(lp), np.asarray(d), cache)
    # prefill gathers at each layer boundary -> bit-identical across
    # collectives; the decode cell defers the gather (that IS the overlap),
    # which lets GSPMD re-partition the downstream norm reduction, so its
    # gate is fp-noise, not bitwise
    if not np.array_equal(logits["reduce_scatter"][0], logits["psum"][0]):
        fails.append("prefill logits: reduce_scatter not bit-identical "
                     "to psum")
    rel_dec = _rel_l2(logits["reduce_scatter"][1], logits["psum"][1])
    if rel_dec > 1e-5:
        fails.append(f"decode logits: reduce_scatter vs psum rel-L2 "
                     f"{rel_dec:.2e} > 1e-5")
    if not fails:
        print(f"[shardcheck] logits reduce_scatter vs psum: prefill "
              f"bitwise, pipelined decode rel-L2 {rel_dec:.1e}")
    rel_ring = _rel_l2(logits["ring"][1], logits["psum"][1])
    # ring's ~1 ulp kernel noise can flip A8 rounding boundaries between
    # layers, so the whole-model gate is the W8A8 parity bound, not 1e-5
    if rel_ring > tol:
        fails.append(f"decode logits: ring vs psum rel-L2 {rel_ring:.4f} "
                     f"> {tol}")
    # the pipelined decode cell (deferred-gather epilogue + act anchor)
    # must hit the same jit cell on every step — zero retrace
    _, d, cache = logits["reduce_scatter"]
    prog = Program.build(cfg, params, execution=bks["reduce_scatter"])
    before = dict(api.TRACE_COUNTS)
    for _ in range(3):
        d, cache = prog.decode(toks[:, 1:2], cache, S)
    if dict(api.TRACE_COUNTS) != before:
        fails.append(f"pipelined decode cell retraced: {before} -> "
                     f"{dict(api.TRACE_COUNTS)}")
    else:
        print("[shardcheck] pipelined decode cell: zero retrace over "
              "repeated steps")
    return fails


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=DOC)
    ap.add_argument("--mesh", default="1x2",
                    help="data x model (x pod leading for 3 dims)")
    ap.add_argument("--execution", default="photonic",
                    choices=["xla", "photonic"])
    ap.add_argument("--tol", type=float, default=0.055)
    ap.add_argument("--serve", action="store_true",
                    help="also gate DP continuous serving token-identity")
    ap.add_argument("--check-dropped", action="store_true",
                    help="also gate the PartitionReport warning")
    ap.add_argument("--collectives", action="store_true",
                    help="also gate reduce-scatter/ring vs psum "
                         "equivalence and the pipelined decode cell")
    args = ap.parse_args(argv)
    mesh_shape = tuple(int(x) for x in args.mesh.split("x"))
    fails = check_parity(mesh_shape, args.execution, args.tol)
    if args.serve:
        fails += check_serve(mesh_shape, args.execution)
    if args.check_dropped:
        fails += check_dropped()
    if args.collectives:
        fails += check_collectives(mesh_shape, args.execution, args.tol)
    for f in fails:
        print(f"[shardcheck] FAIL {f}")
    print(f"[shardcheck] {'FAIL' if fails else 'ok'}")
    return 1 if fails else 0


if __name__ == "__main__":
    sys.exit(main())
