"""The program's host spans in small traces recorded on a TPU v5e
(``record_trace.py``: minitron-4b widths cut to 2 layers, capacity 4,
prompts of 700, 100 and 300 tokens, 12 output tokens each, 12 traced
steps).  ``chip_trace_spans_small`` comes from a program that marks its
scheduler phases; ``chip_trace_small`` from one that did not.  The counts
follow from the structure: all three prompts are admitted in the first
step, two monolithic prefills and one staged in two chunks, and every
step decodes."""
from __future__ import annotations

import gzip
from pathlib import Path

import pytest

import host_spans
import trace_reduce

DATA = Path(__file__).resolve().parent / "data"
SPANS = DATA / "chip_trace_spans_small.xplane.pb.gz"
BARE = DATA / "chip_trace_small.xplane.pb.gz"


def _pd(path):
    from jax.profiler import ProfileData
    return ProfileData.from_serialized_xspace(gzip.open(path).read())


@pytest.fixture(scope="module")
def spanned():
    pd = _pd(SPANS)
    return pd, host_spans.reduce(pd)


@pytest.fixture(scope="module")
def bare():
    pd = _pd(BARE)
    return pd, host_spans.reduce(pd)


def test_span_counts(spanned):
    _, r = spanned
    counts = {k: v[0] for k, v in r["spans"].items()}
    assert counts == {
        "sched.step": 12, "sched.decode.dispatch": 12,
        "sched.decode.wait": 12, "sched.commit": 12,
        "sched.admit": 3, "sched.chunk": 2,
        "sched.prefill.dispatch": 3,       # 2 prefills, 1 staging cache
        "sched.write_prefill": 3, "sched.first_token.wait": 3}


def test_metrics(spanned):
    pd, r = spanned
    m = r["metrics"]
    win = host_spans.in_window(host_spans.host_spans(pd))
    step_ms = [(s.end - s.start) * 1e-6 for s in win["sched.step"]]
    assert 0 < m["sched.decode_dispatch_ms"] < m["sched.host_ms_per_step"]
    assert m["sched.host_ms_per_step"] < sum(step_ms) / len(step_ms)
    queued = [s.args["queued_ms"] for s in win["sched.admit"]]
    assert all(q >= 0 for q in queued)
    assert m["sched.queue_wait_ms"] == pytest.approx(sum(queued) / 3)


def test_idle_gaps_name_the_scheduler_phases(spanned):
    pd, r = spanned
    s = trace_reduce.reduce(pd)
    idle = s.window_s - s.busy_s
    assert r["idle_s"] == pytest.approx(idle, rel=1e-6)
    names = {k for k, _ in r["idle_gaps"]}
    assert names <= {k for k in r["spans"]} | {"bench.step",
                                                 host_spans.BETWEEN}
    assert "sched.decode.dispatch" in names
    assert r["idle_named_share"] >= 0.9


def test_bare_trace_reduces_as_trace_reduce_does(bare):
    pd, r = bare
    s = trace_reduce.reduce(pd)
    assert r["idle_gaps"] == s.idle_gaps
    assert r["window_s"] == pytest.approx(s.window_s)
    assert r["spans"] == {}
    assert set(r["metrics"].values()) == {None}


def _scan(spans, t):
    """The innermost span open at ``t`` by a scan over every span."""
    return trace_reduce._host_at([(s.start, s.end, s.name) for s in spans],
                                 t)


def test_innermost_matches_a_scan(spanned):
    pd, _ = spanned
    spans = host_spans.host_spans(pd)
    at = host_spans.Innermost(spans)
    w0, w1 = host_spans.window(spans)
    edges = sorted({t for s in spans for t in (s.start, s.end)
                    if w0 - 10 <= t <= w1 + 10})
    probes = [w0 - 5, w1 + 5] + [(a + b) / 2 for a, b in zip(edges,
                                                             edges[1:])]
    for t in probes:
        assert at.at(t) == _scan(spans, t), t


def test_innermost_nested_and_siblings():
    S = host_spans.Span
    spans = sorted([S(0, 100, "sched.step", {}),
                    S(10, 40, "sched.admit", {}),
                    S(12, 20, "sched.prefill.dispatch", {}),
                    S(20, 30, "sched.write_prefill", {}),
                    S(50, 70, "sched.decode.dispatch", {}),
                    S(70, 90, "sched.decode.wait", {}),
                    S(200, 300, "sched.step", {})],
                   key=lambda s: (s.start, -s.end))
    at = host_spans.Innermost(spans)
    assert at.at(15) == "sched.prefill.dispatch"
    assert at.at(35) == "sched.admit"
    assert at.at(45) == "sched.step"
    assert at.at(80) == "sched.decode.wait"
    assert at.at(95) == "sched.step"
    assert at.at(150) == host_spans.BETWEEN
    assert at.at(250) == "sched.step"
    assert at.at(-1) == host_spans.BETWEEN
