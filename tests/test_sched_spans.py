"""The scheduler's host spans in a JAX profiler trace (CPU): one monolithic
and one chunked admission drained through a tiny ContinuousScheduler, the
trace read back with ``ProfileData``, and the span tree checked against the
scheduler's own counters and the request tracker."""
from __future__ import annotations

import glob
import warnings

import numpy as np
import pytest

# spans that must lie inside a sched.step
IN_STEP = ("sched.decode.dispatch", "sched.decode.wait", "sched.commit",
           "sched.first_token.wait", "sched.admit", "sched.chunk")


def _cfg():
    from repro.configs.base import ModelConfig
    return ModelConfig(name="span-test-lm", family="dense", num_layers=2,
                       d_model=32, num_heads=4, num_kv_heads=2, d_ff=64,
                       vocab_size=128, compute_dtype="float32")


def _spans(path):
    """[(start_ns, end_ns, name, args)] of every ``sched.*`` host event."""
    from jax.profiler import ProfileData
    out = []
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        pd = ProfileData.from_file(path)
        for plane in pd.planes:
            if not plane.name.startswith("/host:"):
                continue
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("sched."):
                        out.append((ev.start_ns,
                                    ev.start_ns + ev.duration_ns,
                                    ev.name, dict(ev.stats)))
    return sorted(out)


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    import jax
    from repro.api import Program
    from repro.models import transformer as tfm
    from repro.obs.serving import ServingObs
    from repro.serve.batcher import Request
    from repro.serve.scheduler import ContinuousScheduler

    cfg = _cfg()
    params, _ = tfm.init_model(jax.random.PRNGKey(0), cfg)
    prog = Program.build(cfg, params)
    obs = ServingObs.create(cfg, trace=False)
    sched = ContinuousScheduler(prog, capacity=2, max_len=64,
                                prefill_bucket=8, prefill_chunk=16,
                                telemetry=obs)
    rng = np.random.default_rng(3)
    # 10 tokens: one monolithic prefill at bucket 16; 40: three chunks
    for rid, plen in enumerate((10, 40)):
        sched.submit(Request(rid=rid, prompt=rng.integers(
            1, cfg.vocab_size, plen).astype(np.int32), max_new=3))
    out = tmp_path_factory.mktemp("sched_trace")
    jax.profiler.start_trace(str(out))
    try:
        steps = 0
        while sched.queue or sched.pool.num_active:
            sched.step()
            steps += 1
    finally:
        jax.profiler.stop_trace()
    files = glob.glob(str(out / "**" / "*.xplane.pb"), recursive=True)
    assert len(files) == 1
    return sched, obs, steps, _spans(files[0])


def _named(spans, name):
    return [s for s in spans if s[2] == name]


def _inside(inner, outers):
    return any(o[0] <= inner[0] and inner[1] <= o[1] for o in outers)


def test_every_span_lies_in_a_step(traced):
    sched, _, steps, spans = traced
    outer = _named(spans, "sched.step")
    assert len(outer) == steps
    for s in spans:
        if s[2] in IN_STEP:
            assert _inside(s, outer), s


def test_admission_children_lie_in_their_admission(traced):
    _, _, _, spans = traced
    hosts = _named(spans, "sched.admit") + _named(spans, "sched.chunk")
    for name in ("sched.prefill.dispatch", "sched.write_prefill",
                 "sched.first_token.wait"):
        assert _named(spans, name)
        for s in _named(spans, name):
            assert _inside(s, hosts), s


def test_counts_follow_the_scheduler_counters(traced):
    sched, _, _, spans = traced
    st = sched.stats
    assert len(_named(spans, "sched.decode.dispatch")) == st.decode_steps
    assert len(_named(spans, "sched.decode.wait")) == st.decode_steps
    assert len(_named(spans, "sched.commit")) == st.decode_steps
    assert len(_named(spans, "sched.chunk")) == st.prefill_chunks == 3
    assert len(_named(spans, "sched.admit")) == st.prefills == 2
    # one first token per request: at its prefill or its last chunk
    assert len(_named(spans, "sched.first_token.wait")) == 2
    assert len(_named(spans, "sched.write_prefill")) == 2
    assert [s[3]["last"] for s in _named(spans, "sched.chunk")] == [0, 0, 1]
    assert [s[3]["off"] for s in _named(spans, "sched.chunk")] == [0, 16, 32]


def test_admission_args(traced):
    _, _, _, spans = traced
    admits = _named(spans, "sched.admit")
    assert [(a[3]["rid"], a[3]["prompt_len"], a[3]["rows"])
            for a in admits] == [(0, 10, 16), (1, 40, 48)]
    for s in _named(spans, "sched.step"):
        assert {"active", "queued"} <= set(s[3])


def test_queued_ms_is_the_trackers(traced):
    _, obs, _, spans = traced
    q = [a[3]["queued_ms"] for a in _named(spans, "sched.admit")]
    assert all(v >= 0.0 for v in q)
    h = obs.tracker.queue
    assert h.count == len(q)
    assert h.min == min(q) and h.max == max(q)
    assert h.total == pytest.approx(sum(q))
