"""Sharded-vs-single-device parity suite (the mesh-native refactor).

Two layers of coverage:

  * in-process (the single CPU device): ``Program.build(mesh=
    single_device_mesh())`` is BIT-identical to the default unsharded build
    and adds zero retraces; bank shardings follow the owning weight's spec;
    the dropped-rule report formats; DP slot packing balances shards.
  * subprocess (``XLA_FLAGS=--xla_force_host_platform_device_count=8`` via
    ``launch/shardcheck.py``, which must set the flag before jax imports):
    photonic decode/prefill logits on 1x2 and 2x2 host-device meshes within
    the established rel-L2 0.055 gate of the unsharded reference, 1x1
    bit-identity, no retraces on repeated sharded steps, DP continuous
    serving token-identity, and the PartitionReport warning.
"""
import os
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import repro.api as api
from repro.api import Program
from repro.configs.base import ModelConfig
from repro.core import prepared as prepared_lib
from repro.launch import mesh as mesh_lib
from repro.models import transformer as tfm
from repro.sharding import partition


def small_cfg(**kw):
    return ModelConfig(name="shard-t", family="dense", num_layers=2,
                       d_model=32, num_heads=4, num_kv_heads=2, d_ff=64,
                       vocab_size=128, compute_dtype="float32", **kw)


@pytest.fixture(scope="module")
def small():
    cfg = small_cfg()
    params, _ = tfm.init_model(jax.random.PRNGKey(0), cfg)
    return cfg, params


# =====================================================================
# in-process: the partition rule + collective knob (pure, no mesh)
# =====================================================================
def test_partition_rule_decision_table():
    from repro.core.backend import partition_rule

    # no model axis -> replicated, whatever else is asked for
    assert partition_rule(1, 64, 64) == "replicated"
    # column-parallel whenever N divides and no output shuffle
    assert partition_rule(2, 64, 64) == "column"
    assert partition_rule(2, 63, 64) == "column"
    # tp_hint="row" + K divides -> row-parallel via the chosen collective
    assert partition_rule(2, 64, 64, tp_hint="row") == "scatter"
    assert partition_rule(2, 64, 64, tp_hint="row",
                          collective="ring") == "ring"
    assert partition_rule(2, 64, 64, tp_hint="row",
                          collective="psum") == "psum"
    # scatter/ring need N to divide too (each shard owns an output slice);
    # otherwise row-parallel falls back to the full-psum comparator
    assert partition_rule(2, 64, 63, tp_hint="row") == "psum"
    # a blocked output shuffle needs the full row -> psum fallback
    assert partition_rule(2, 64, 64, block_perm=(1, 0),
                          tp_hint="row") == "psum"
    # row hint with a misdivided K falls through to column, then replicated
    assert partition_rule(2, 63, 64, tp_hint="row") == "column"
    assert partition_rule(2, 63, 63, tp_hint="row") == "replicated"
    # no hint, N misdivided, K divides -> row-parallel still applies
    assert partition_rule(2, 64, 63) == "psum"
    with pytest.raises(ValueError, match="collective"):
        partition_rule(2, 64, 64, collective="bogus")


def test_backend_rejects_unknown_tp_collective():
    from repro.core.backend import Backend

    with pytest.raises(ValueError, match="tp_collective"):
        Backend("photonic", tp_collective="allreduce")
    # the knob participates in the jit-cell cache key
    a = Backend("photonic", tp_collective="psum")
    b = Backend("photonic", tp_collective="reduce_scatter")
    assert a != b and hash(a) != hash(b)


# =====================================================================
# in-process: the 1x1 no-op mesh contract
# =====================================================================
def test_make_mesh_auto_single_device():
    mesh = mesh_lib.make_mesh_auto()
    assert set(mesh.axis_names) == {"data", "model"}
    assert mesh.size == len(jax.devices())


@pytest.mark.parametrize("execution", ["xla", "photonic"])
def test_single_device_mesh_bit_identical_and_no_retrace(small, execution):
    """``mesh=single_device_mesh()`` (the mesh-native default) produces
    bit-identical logits to the unsharded Program, and repeated calls add
    zero retraces (the api.TRACE_COUNTS gate)."""
    cfg, params = small
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 1,
                              cfg.vocab_size)
    ref = Program.build(cfg, params, execution=execution)
    lr, cr = ref.prefill({"tokens": toks}, 10)
    dr, _ = ref.decode(toks[:, :1], cr, 8)

    prog = Program.build(cfg, params, execution=execution,
                         mesh=mesh_lib.single_device_mesh())
    assert prog.mesh is not None
    lp, cp = prog.prefill({"tokens": toks}, 10)
    dp, cp = prog.decode(toks[:, :1], cp, 8)
    np.testing.assert_array_equal(np.asarray(lp), np.asarray(lr))
    np.testing.assert_array_equal(np.asarray(dp), np.asarray(dr))

    before = dict(api.TRACE_COUNTS)
    prog.prefill({"tokens": toks + 1}, 10)
    prog2 = Program.build(cfg, params, execution=execution,
                          mesh=mesh_lib.single_device_mesh())
    l2, c2 = prog2.prefill({"tokens": toks}, 10)
    prog2.decode(toks[:, :1], c2, 8)
    assert dict(api.TRACE_COUNTS) == before, "sharded cells retraced"
    del l2


def test_single_device_mesh_generate_token_identical(small):
    cfg, params = small
    prompt = jax.random.randint(jax.random.PRNGKey(2), (2, 6), 1,
                                cfg.vocab_size)
    ref = Program.build(cfg, params, execution="photonic")
    prog = Program.build(cfg, params, execution="photonic",
                         mesh=mesh_lib.single_device_mesh())
    np.testing.assert_array_equal(np.asarray(ref.generate(prompt, 5)),
                                  np.asarray(prog.generate(prompt, 5)))


# =====================================================================
# in-process: bank shardings + report plumbing
# =====================================================================
def test_bank_shardings_follow_weight_specs(small):
    """Prepared tiles/scales shard with their owning weight's spec: wq/wq_t
    verbatim, scale/w0_colsum on the last dim's axis, scale_t on the
    second-to-last dim's axis."""
    cfg, params = small
    prog = Program.build(cfg, params, execution="photonic")
    mesh = mesh_lib.single_device_mesh()
    sh = partition.bank_shardings(prog.bank, tfm.model_specs(cfg), mesh,
                                  cfg.fsdp)
    flat_b = jax.tree.leaves(
        prog.bank, is_leaf=lambda x: isinstance(
            x, prepared_lib.PreparedTensor))
    flat_s = jax.tree.leaves(
        sh, is_leaf=lambda x: isinstance(x, prepared_lib.PreparedTensor))
    assert len(flat_b) == len(flat_s)
    n_prep = 0
    for b, s in zip(flat_b, flat_s):
        if isinstance(b, prepared_lib.PreparedTensor):
            n_prep += 1
            assert isinstance(s, prepared_lib.PreparedTensor)
            # every field's spec rank fits its array rank
            assert len(s.scale.spec) <= b.scale.ndim
            assert len(s.wq.spec) <= b.wq.ndim
            assert s.wq.spec == s.wq_t.spec
            assert s.scale.spec == s.w0_colsum.spec
    assert n_prep > 0
    # the tree is a valid device_put target
    bank = jax.device_put(prog.bank, sh)
    assert prepared_lib.prepared_stats(bank)["programmed_tensors"] == n_prep


def test_dropped_summary_one_line():
    rep = partition.PartitionReport(
        dropped=[("heads", 30, ("model",)), ("mlp", 90, ("model",))])
    line = partition.dropped_summary(rep)
    assert "\n" not in line
    assert "2 rule(s) dropped" in line
    assert "heads:30%model" in line


# =====================================================================
# in-process: DP slot packing
# =====================================================================
def test_slot_pool_packs_per_shard_batches(small):
    """With dp shards, allocation balances active slots across the dp
    contiguous shard blocks instead of piling onto shard 0."""
    from repro.serve.slots import SlotPool, SlotState

    cfg, _ = small
    pool = SlotPool(cfg, capacity=8, max_len=16)
    pool.dp = 4                      # white-box: 4 shard blocks of 2 slots
    slots = [pool.allocate(SlotState(rid=i, prompt_len=1, max_new=1))
             for i in range(5)]
    # first four land one per shard block, the fifth wraps
    assert [s // 2 for s in slots[:4]] == [0, 1, 2, 3]
    assert slots[4] // 2 == 0
    pool.free(slots[1])              # shard 1 now emptiest -> next goes there
    nxt = pool.allocate(SlotState(rid=9, prompt_len=1, max_new=1))
    assert nxt // 2 == 1


def test_slot_pool_capacity_must_divide_mesh(small):
    from repro.serve.slots import SlotPool

    cfg, _ = small
    mesh = mesh_lib.single_device_mesh()
    # 1x1 mesh: no constraint, dp stays 1
    pool = SlotPool(cfg, capacity=3, max_len=16, mesh=mesh)
    assert pool.dp == 1


# =====================================================================
# subprocess: real multi-device meshes (forced host devices)
# =====================================================================
def _run_shardcheck(args, timeout=900):
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env.update({"REPRO_SHARD_DEVICES": "8", "PYTHONPATH": "src"})
    out = subprocess.run(
        [sys.executable, "-m", "repro.launch.shardcheck"] + args,
        capture_output=True, text=True, timeout=timeout,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        env=env)
    assert out.returncode == 0, out.stdout[-3000:] + out.stderr[-2000:]
    return out.stdout


def test_sharded_parity_1x2():
    """TP-only host mesh: photonic decode within the rel-L2 0.055 gate,
    1x1 bit-identity, dropped-rule warning surfaced, plus the collective
    gates: reduce_scatter bit-identical to psum (dot-level AND prefill
    logits), post-scatter epilogue (bias / fused activation / blocked
    shuffle) vs unsharded, bf16 row-parallel partials reduced in f32, zero
    retrace on the pipelined decode cell."""
    out = _run_shardcheck(["--mesh", "1x2", "--execution", "photonic",
                           "--check-dropped", "--collectives"])
    assert "1x1 mesh bit-identical" in out
    assert "dropped-rule warning surfaced" in out
    assert "scatter==psum bitwise" in out
    assert "collectives[blend-shuffle]" in out
    assert "collectives[bf16 reduce_scatter]" in out
    assert "prefill bitwise" in out
    assert "zero retrace" in out


def test_sharded_parity_2x2_with_dp_serving():
    """DP x TP host mesh: data-parallel continuous serving token-identity
    against unsharded solo generation, and the same collective gates as
    the 1x2 run on the dp>1 mesh."""
    out = _run_shardcheck(["--mesh", "2x2", "--execution", "photonic",
                           "--serve", "--collectives"])
    assert "token-identical to solo generate" in out
    assert "scatter==psum bitwise" in out
    assert "zero retrace" in out


@pytest.mark.slow
def test_sharded_parity_xla_2x1():
    _run_shardcheck(["--mesh", "2x1", "--execution", "xla", "--serve",
                     "--tol", "1e-5"])


# =====================================================================
# in-process: sharded scheduler wiring (mesh inherited from the Program)
# =====================================================================
def test_scheduler_inherits_program_mesh(small):
    from repro.serve.scheduler import ContinuousScheduler

    cfg, params = small
    prog = Program.build(cfg, params,
                         mesh=mesh_lib.single_device_mesh())
    sched = ContinuousScheduler(prog, capacity=2, max_len=24)
    assert sched.mesh is prog.mesh
    assert sched.pool.dp == 1

    prompt = jnp.asarray(
        np.asarray([[3, 5, 7, 9]], np.int32))
    from repro.serve.batcher import Request
    sched.submit(Request(rid=0, prompt=np.asarray([3, 5, 7, 9], np.int32),
                         max_new=3))
    comps = sched.drain()
    solo = np.asarray(prog.generate(prompt, 3))[0]
    np.testing.assert_array_equal(comps[0].tokens, solo)


def test_scheduler_legacy_path_threads_mesh(small):
    """The legacy (params, cfg) constructor builds its Program ON the given
    mesh (a pool sharded on a mesh the cells don't know about would feed
    sharded caches into unsharded pallas_calls), and a Program/mesh
    conflict is rejected."""
    from repro.serve.scheduler import ContinuousScheduler

    cfg, params = small
    mesh = mesh_lib.single_device_mesh()
    sched = ContinuousScheduler(params, cfg, capacity=2, max_len=16,
                                mesh=mesh)
    assert sched.program.mesh == mesh
    assert sched.pool.mesh == mesh

    prog = Program.build(cfg, params)          # no mesh
    with pytest.raises(ValueError, match="execution mesh"):
        ContinuousScheduler(prog, capacity=2, max_len=16, mesh=mesh)


def test_program_build_rejects_conflicting_meshes(small):
    from repro.core import backend as backend_lib

    cfg, params = small
    mesh = mesh_lib.single_device_mesh()
    bk = backend_lib.Backend("xla", mesh=mesh)
    # same mesh on both: fine
    Program.build(cfg, params, execution=bk, mesh=mesh)
    other = jax.make_mesh((1, 1), ("data", "x"), devices=jax.devices()[:1])
    with pytest.raises(ValueError, match="conflicts"):
        Program.build(cfg, params, execution=bk, mesh=other)
