"""The trace reduction on a small trace recorded on a TPU v5e: minitron-4b
at published widths cut to 2 layers, photonic, slot capacity 4; prompts of
700 (two 512-row chunks through flash attention), 100 and 300 tokens (one
monolithic prefill each at buckets 128 and 384, einsum attention), 12
output tokens each: 12 traced scheduler steps.  The counts follow from
that structure: a pass runs 15 fused MVM calls (7 per layer and the
unembedding)."""
from __future__ import annotations

import gzip
from pathlib import Path

import pytest

import trace_reduce

DATA = Path(__file__).resolve().parent / "data" / \
    "chip_trace_small.xplane.pb.gz"


@pytest.fixture(scope="module")
def summary():
    from jax.profiler import ProfileData
    return trace_reduce.reduce(ProfileData.from_serialized_xspace(
        gzip.open(DATA).read()))


def test_programs_by_phase(summary):
    assert summary.module_count["decode"] == 12
    assert summary.module_count["prefill"] == 4     # 2 monolithic, 2 chunks


def test_kernels_by_phase(summary):
    kc = summary.kernel_count
    assert kc[("decode", "fused_mvm")] == 12 * 15
    assert kc[("prefill", "fused_mvm")] == 4 * 15
    assert kc[("prefill", "flash_attn")] == 2 * 2   # 2 chunks x 2 layers
    assert ("decode", "flash_attn") not in kc


def test_times_are_consistent(summary):
    ks, ms = summary.kernel_seconds, summary.module_seconds
    assert 0 < ks[("decode", "fused_mvm")] < ms["decode"]
    assert 0 < ks[("prefill", "fused_mvm")] + ks[("prefill", "flash_attn")] \
        < ms["prefill"]
    assert 0 < summary.busy_s < summary.window_s
    assert summary.busy_s >= sum(ms.values()) * 0.9
    idle = sum(s for _, s in summary.idle_gaps)
    assert idle == pytest.approx(summary.window_s - summary.busy_s, rel=1e-6)
    assert summary.top_ops[0][0] == "photonic_mvm_fused"


def test_op_names():
    assert trace_reduce.op_base(
        "%photonic_mvm_fused.88 = bf16[128,1024]{1,0} custom-call(s32[2] "
        "%iota.73), custom_call_target=\"tpu_custom_call\"") == (
        "photonic_mvm_fused", "custom-call")
    assert trace_reduce.kernel_kind("photonic.flash_attn.128x128") == \
        "flash_attn"
    assert trace_reduce.phase_of("jit_decode_sample_cell") == "decode"
    assert trace_reduce.phase_of("jit_prefill_chunk_cell") == "prefill"
    assert trace_reduce.phase_of("jit_dynamic_update_slice") == "other"
