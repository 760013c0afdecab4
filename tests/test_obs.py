"""Telemetry subsystem tests (repro.obs, ISSUE 6).

Covers, in layer order:
  * histogram/percentile math — deterministic cases, a hypothesis sweep
    against numpy (``method="higher"`` is exactly the histogram's rank
    rule), and exact merge associativity;
  * the metrics registry + Prometheus/JSON exports + CounterGroup
    mirroring (the ``api.TRACE_COUNTS`` promotion);
  * the unified stats protocol (WaveStats / ContinuousStats keep their
    historical field surface while backing onto registry counters);
  * PhotonicMeter energy accounting against a HAND-COMPUTED
    ``core/costmodel`` trace, at a calibrated size where no clamping is
    active — the meter must price exactly what the static model prices;
  * Chrome-trace structural validity;
  * the metrics schema validator (positive + negative cases);
  * an end-to-end continuous-serving run with telemetry attached, whose
    snapshot must validate against ``benchmarks/metrics_schema.json``.
"""
import json
import math
import os

import numpy as np
import pytest

from repro.obs import metrics as metrics_lib
from repro.obs import tracing as tracing_lib
from repro.obs.check_schema import validate
from repro.obs.meter import PhotonicMeter, StackProfile
from repro.obs.serving import RequestTracker, ServingObs
from repro.obs.stats import ContinuousStats, ServingStats, WaveStats

from tests._optional_hypothesis import given, settings, st

SCHEMA_PATH = os.path.join(os.path.dirname(__file__), "..", "benchmarks",
                           "metrics_schema.json")


def load_schema():
    with open(SCHEMA_PATH) as f:
        return json.load(f)


# =========================================================================
# histogram / percentile math
# =========================================================================
class TestHistogram:
    def test_single_value_quantiles_exact(self):
        h = metrics_lib.Histogram()
        h.record(42.0, n=7)
        for q in (0.0, 0.5, 0.95, 1.0):
            assert h.quantile(q) == 42.0
        assert h.count == 7
        assert h.mean == 42.0

    def test_empty_is_nan_but_summary_finite(self):
        h = metrics_lib.Histogram()
        assert math.isnan(h.quantile(0.5))
        s = h.summary()
        assert s["count"] == 0
        assert all(s[k] == 0.0 for k in ("sum", "min", "max", "mean",
                                         "p50", "p95", "p99"))

    def test_quantiles_track_numpy_within_growth_bound(self):
        rng = np.random.default_rng(0)
        vals = rng.lognormal(mean=2.0, sigma=1.5, size=2000)
        h = metrics_lib.Histogram(lo=1e-9, growth=1.05)
        for v in vals:
            h.record(float(v))
        for q in (0.1, 0.5, 0.9, 0.95, 0.99):
            ref = float(np.quantile(vals, q, method="higher"))
            got = h.quantile(q)
            # bucket midpoint is within growth**0.5 of any member value
            assert abs(got - ref) / ref < 0.06, (q, got, ref)

    def test_negative_value_rejected(self):
        with pytest.raises(ValueError):
            metrics_lib.Histogram().record(-1.0)

    @settings(max_examples=50, deadline=None)
    @given(st.lists(st.floats(min_value=1e-3, max_value=1e9,
                              allow_nan=False, allow_infinity=False),
                    min_size=1, max_size=200),
           st.sampled_from([0.5, 0.9, 0.95, 0.99]))
    def test_hypothesis_quantile_vs_numpy(self, vals, q):
        h = metrics_lib.Histogram(lo=1e-9, growth=1.05)
        for v in vals:
            h.record(v)
        ref = float(np.quantile(np.asarray(vals), q, method="higher"))
        got = h.quantile(q)
        assert got <= max(vals) and got >= min(vals)
        assert abs(got - ref) / max(ref, 1e-12) < 0.06

    def _mk(self, seed, n):
        rng = np.random.default_rng(seed)
        h = metrics_lib.Histogram()
        for v in rng.uniform(0.01, 1e4, size=n):
            h.record(float(v))
        return h

    def test_merge_equals_combined_recording(self):
        a, b = self._mk(1, 100), self._mk(2, 150)
        rng1, rng2 = np.random.default_rng(1), np.random.default_rng(2)
        c = metrics_lib.Histogram()
        for v in list(rng1.uniform(0.01, 1e4, 100)) + list(
                rng2.uniform(0.01, 1e4, 150)):
            c.record(float(v))
        m = a.merge(b)
        assert m.buckets == c.buckets
        assert m.count == c.count
        assert m.min == c.min and m.max == c.max
        assert m.total == pytest.approx(c.total)

    def test_merge_associative_exactly(self):
        a, b, c = self._mk(1, 80), self._mk(2, 120), self._mk(3, 60)
        left = a.merge(b).merge(c)
        right = a.merge(b.merge(c))
        assert left.buckets == right.buckets
        assert left.count == right.count
        assert left.total == right.total          # exact: same additions
        assert left.min == right.min and left.max == right.max
        for q in (0.5, 0.95, 0.99):
            assert left.quantile(q) == right.quantile(q)

    def test_merge_grid_mismatch_rejected(self):
        with pytest.raises(ValueError):
            metrics_lib.Histogram(growth=1.05).merge(
                metrics_lib.Histogram(growth=1.1))


# =========================================================================
# registry + exports + CounterGroup
# =========================================================================
class TestRegistry:
    def test_labels_canonicalized_and_snapshot_shape(self):
        r = metrics_lib.MetricsRegistry()
        r.counter("kernel.calls", plan="8x128x128", kind="fused").inc(3)
        # same metric regardless of label order
        assert (r.counter("kernel.calls", kind="fused", plan="8x128x128")
                .value == 3)
        r.gauge("bank.bytes").set(1024)
        r.histogram("lat.ms", lo=1e-3).record(5.0)
        snap = r.snapshot()
        key = 'kernel.calls{kind="fused",plan="8x128x128"}'
        assert snap["counters"][key] == 3
        assert snap["gauges"]["bank.bytes"] == 1024
        assert snap["histograms"]["lat.ms"]["count"] == 1
        assert snap["histograms"]["lat.ms"]["p50"] == 5.0

    def test_prometheus_text(self):
        r = metrics_lib.MetricsRegistry()
        r.counter("serve.requests").inc(2)
        r.histogram("serve.ttft_ms", lo=1e-3).record(12.0)
        text = r.to_prometheus()
        assert "# TYPE serve_requests counter" in text
        assert "serve_requests 2" in text
        assert 'serve_ttft_ms{quantile="0.50"}' in text
        assert "serve_ttft_ms_count 1" in text

    def test_enable_switch(self):
        metrics_lib.disable()
        assert not metrics_lib.enabled()
        metrics_lib.enable()
        try:
            assert metrics_lib.enabled()
        finally:
            metrics_lib.disable()

    def test_counter_group_mirrors_default_registry(self):
        g = metrics_lib.CounterGroup("test.group")
        g["hits"] += 1
        g["hits"] += 1
        g["misses"] += 1
        assert dict(g) == {"hits": 2, "misses": 1}
        assert g["absent"] == 0                    # Counter-alike default
        reg = metrics_lib.default_registry()
        assert reg.counter("test.group.hits").value == 2.0
        assert reg.counter("test.group.misses").value == 1.0

    def test_trace_counts_is_promoted_counter_group(self):
        from repro import api
        assert isinstance(api.TRACE_COUNTS, metrics_lib.CounterGroup)
        before = api.TRACE_COUNTS["prefill"]
        api.TRACE_COUNTS["prefill"] += 1
        try:
            reg = metrics_lib.default_registry()
            assert (reg.counter("compile.trace.prefill").value
                    == api.TRACE_COUNTS["prefill"] == before + 1)
        finally:
            api.TRACE_COUNTS["prefill"] = before


# =========================================================================
# unified stats protocol
# =========================================================================
class TestStatsProtocol:
    def test_field_surface_matches_legacy_dataclass(self):
        s = ServingStats()
        s.requests += 1
        s.requests += 1
        s.prompt_tokens = 37
        s.slot_steps += 10
        s.useful_steps += 7
        assert s.requests == 2 and isinstance(s.requests, int)
        assert s.prompt_tokens == 37
        assert s.overhead == pytest.approx(0.3)
        assert s.as_dict()["overhead"] == pytest.approx(0.3)
        # the same numbers are in the registry snapshot — one bookkeeping
        snap = s.registry.snapshot()
        assert snap["counters"]["serve.requests"] == 2
        assert snap["counters"]["serve.useful_steps"] == 7

    def test_wave_stats_padding(self):
        w = WaveStats()
        w.prompt_tokens = 60
        w.padded_tokens = 20
        w.waves += 3
        assert w.padding_overhead == pytest.approx(0.25)
        assert w.waves == 3

    def test_continuous_stats_occupancy_histogram(self):
        c = ContinuousStats(_capacity=4)
        for n in (3, 4, 4, 2, 4):
            c.observe_active(n)
        assert c.occupancy_distribution == {2: 1, 3: 1, 4: 3}
        assert c.mean_occupancy == pytest.approx(17 / 5)
        snap = c.registry.snapshot()
        assert snap["histograms"]["serve.active_slots"]["count"] == 5
        assert snap["histograms"]["serve.active_slots"]["max"] == 4.0
        assert snap["gauges"]["serve.slots.active"] == 4.0
        c.decode_steps = 5
        c.idle_slot_steps = 3
        assert c.idle_fraction == pytest.approx(3 / 20)

    def test_shared_registry(self):
        reg = metrics_lib.MetricsRegistry()
        c = ContinuousStats(registry=reg, _capacity=2)
        c.generated_tokens += 5
        assert reg.counter("serve.generated_tokens").value == 5.0


# =========================================================================
# PhotonicMeter vs a hand-computed costmodel trace
# =========================================================================
class TestPhotonicMeter:
    def test_ledger_matches_hand_computed_costmodel_trace(self):
        from repro.core import costmodel
        # calibrated size: u = 256*256/256 = 256 bank cycles, far above
        # the affine fit's valid floor — the meter's non-negativity clamp
        # must be inactive and its prices EQUAL the static model's
        p = StackProfile(num_physical=2, depth=4, mats_per_block=6,
                         rows=256, cols=256, tile=256)
        m = PhotonicMeter(p, refresh_steps=4)
        wd, we = costmodel.CALIBRATED.write_cost(256, 256, 256)
        cd, ce = costmodel.CALIBRATED.compute_cost(256, 256, 256)
        assert wd > 0 and cd > 0         # clamp inactive at this size
        assert (m._wd, m._we, m._cd, m._ce) == (wd, we, cd, ce)

        m.on_prefill(10)                 # first traffic programs the banks
        for _ in range(6):               # one refresh lands at step 4
            m.on_decode_step(3)

        mats = p.num_physical * p.mats_per_block            # 12
        writes = 2 * mats                                   # program+refresh
        passes = (10 + 6 * 3) * p.depth * p.mats_per_block  # 672
        assert m.bank_writes == writes == 24
        assert m.matrix_passes == passes == 672
        assert m.reuse_hits == passes - writes
        assert m.reuse_ratio == pytest.approx((passes - writes) / passes)

        rep = m.report()
        assert rep["write_energy_uJ"] == pytest.approx(writes * we)
        assert rep["compute_energy_uJ"] == pytest.approx(passes * ce)
        assert rep["write_delay_ns"] == pytest.approx(writes * wd)
        assert rep["baseline_write_energy_uJ"] == pytest.approx(passes * we)
        assert rep["write_energy_saved_uJ"] == pytest.approx(
            (passes - writes) * we)
        e_rb = writes * we + passes * ce
        e_base = passes * we + passes * ce
        assert rep["energy_savings_frac"] == pytest.approx(1 - e_rb / e_base)
        t_rb = writes * wd + passes * cd
        t_base = passes * wd + passes * cd
        assert rep["latency_savings_frac"] == pytest.approx(
            1 - t_rb / t_base)
        assert rep["amortization_passes_per_write"] == pytest.approx(
            passes / writes)
        # the report mirrors into energy.* gauges
        snap = m.registry.snapshot()
        assert snap["gauges"]["energy.reuse_ratio"] == pytest.approx(
            rep["reuse_ratio"])

    def test_refresh_schedule(self):
        p = StackProfile(num_physical=1, depth=2, mats_per_block=6,
                         rows=256, cols=256, tile=256)
        m = PhotonicMeter(p, refresh_steps=3)
        m.on_decode_step(1)              # programs at first traffic
        assert m.bank_writes == 6
        m.on_decode_step(1)
        m.on_decode_step(1)              # 3rd step -> thermal refresh
        assert m.bank_writes == 12
        assert m.decode_steps == 3

    def test_toy_size_clamp_keeps_savings_nonnegative(self):
        # below the calibration floor the write-delay intercept goes
        # negative; the clamp must keep the per-event price (and thus the
        # savings fraction) physical
        p = StackProfile(num_physical=1, depth=2, mats_per_block=6,
                         rows=32, cols=32, tile=256)
        m = PhotonicMeter(p, refresh_steps=8)
        assert m._wd >= 0.0
        m.on_prefill(8)
        for _ in range(16):
            m.on_decode_step(4)
        rep = m.report()
        assert 0.0 <= rep["latency_savings_frac"] <= 1.0
        assert 0.0 <= rep["energy_savings_frac"] <= 1.0
        assert rep["write_energy_saved_uJ"] >= 0.0


# =========================================================================
# tracer / request tracker
# =========================================================================
class TestTracing:
    def test_chrome_trace_structure(self, tmp_path):
        tr = tracing_lib.Tracer(enabled=True)
        with tr.span("decode_step", active=3):
            pass
        tr.instant("finish", tid=7, reason="length")
        tr.counter("active_slots", 3)
        tr.thread_name(7, "req 7")
        doc = tr.chrome_trace()
        assert set(doc) == {"traceEvents", "displayTimeUnit"}
        evs = doc["traceEvents"]
        assert [e["ph"] for e in evs] == ["X", "i", "C", "M"]
        for e in evs:
            assert isinstance(e["name"], str)
            assert e["pid"] == 0 and isinstance(e["tid"], int)
        x = evs[0]
        assert x["dur"] >= 0.0 and x["ts"] >= 0.0
        assert x["args"] == {"active": 3}
        assert evs[2]["args"] == {"active_slots": 3}
        assert evs[3]["args"] == {"name": "req 7"}
        out = tmp_path / "trace.json"
        tr.save(str(out))
        assert json.loads(out.read_text())["traceEvents"] == evs

    def test_disabled_tracer_records_nothing(self):
        tr = tracing_lib.Tracer(enabled=False)
        with tr.span("x"):
            tr.instant("y")
            tr.counter("z", 1)
        assert len(tr.events) == 0

    def test_bounded_buffer(self):
        tr = tracing_lib.Tracer(maxlen=10, enabled=True)
        for i in range(25):
            tr.instant(f"e{i}")
        assert len(tr.events) == 10
        assert tr.events[0]["name"] == "e15"

    def test_request_lifecycle_histograms(self):
        reg = metrics_lib.MetricsRegistry()
        tr = tracing_lib.Tracer(enabled=True)
        t = RequestTracker(reg, tr)
        for rid in (0, 1):
            t.on_submit(rid)
            t.on_admit(rid, prompt_len=5, padded_to=8, queued_ms=0.5)
            t.on_first_token(rid)
            for _ in range(3):
                t.on_token(rid)
            t.on_finish(rid, "length")
        assert t.ttft.count == 2
        assert t.tpot.count == 6          # 3 inter-token gaps per request
        assert t.e2e.count == 2
        assert t.queue.count == 2
        assert reg.counter("serve.requests.completed").value == 2
        assert reg.counter("serve.finish_reason", reason="length").value == 2
        assert not t._live                 # finished requests popped
        names = [e["name"] for e in tr.events]
        for phase in ("queue", "prefill", "decode", "finish"):
            assert names.count(phase) == 2
        pct = t.percentiles()
        assert set(pct) == {"ttft_ms", "tpot_ms", "e2e_ms", "queue_ms"}
        assert pct["ttft_ms"]["count"] == 2

    def test_first_token_does_not_pollute_tpot(self):
        t = RequestTracker(metrics_lib.MetricsRegistry())
        t.on_submit(0)
        t.on_admit(0, 4, 4, 0.5)
        t.on_first_token(0)
        assert t.tpot.count == 0           # TTFT only — no 0ms TPOT sample
        t.on_token(0)
        assert t.tpot.count == 1


# =========================================================================
# schema validator
# =========================================================================
class TestSchema:
    def test_snapshot_validates(self):
        obs = ServingObs.create(trace=False)
        obs.tracker.on_submit(0)
        obs.tracker.on_admit(0, 4, 8, 0.5)
        obs.tracker.on_first_token(0)
        obs.tracker.on_finish(0)
        snap = obs.snapshot()
        assert validate(snap, load_schema()) == []

    def test_negative_cases(self):
        schema = load_schema()
        snap = ServingObs.create(trace=False).snapshot()
        bad = json.loads(json.dumps(snap))
        del bad["energy"]["tile"]
        assert any("missing required key 'tile'" in e
                   for e in validate(bad, schema))
        bad = json.loads(json.dumps(snap))
        bad["counters"]["serve.x"] = -1
        assert any("minimum" in e for e in validate(bad, schema))
        bad = json.loads(json.dumps(snap))
        bad["unexpected_top_level"] = {}
        assert any("unexpected key" in e for e in validate(bad, schema))
        bad = json.loads(json.dumps(snap))
        bad["histograms"]["serve.ttft_ms"] = {"count": 1}
        assert any("missing required key" in e
                   for e in validate(bad, schema))
        bad = json.loads(json.dumps(snap))
        bad["schema_version"] = "one"
        assert any("expected integer" in e for e in validate(bad, schema))

    def test_empty_histograms_omitted_and_schema_accepts_absence(self):
        # a registered-but-unsampled histogram must not export: its
        # zero-filled quantiles read as a measured 0 in trend tooling.
        # The schema accepts both the thinned dict and a snapshot with
        # no histograms key at all (absent-but-empty is valid).
        obs = ServingObs.create(trace=False)
        snap = obs.snapshot()
        assert snap["histograms"] == {}   # meters registered, no samples
        assert validate(snap, load_schema()) == []
        obs.tracker.on_submit(0)
        obs.tracker.on_admit(0, 4, 8, 0.5)
        obs.tracker.on_first_token(0)
        snap2 = obs.snapshot()
        assert "serve.ttft_ms" in snap2["histograms"]
        assert all(h["count"] >= 1
                   for h in snap2["histograms"].values())
        no_h = json.loads(json.dumps(snap))
        del no_h["histograms"]
        assert validate(no_h, load_schema()) == []


# =========================================================================
# end-to-end: continuous serving with telemetry attached
# =========================================================================
def _tiny_cfg():
    from repro.configs.base import ModelConfig
    return ModelConfig(name="obs-test-lm", family="dense", num_layers=2,
                       d_model=32, num_heads=4, num_kv_heads=2, d_ff=64,
                       vocab_size=128, compute_dtype="float32")


@pytest.fixture(scope="module")
def served_telemetry():
    import jax
    from repro.api import Program
    from repro.models import transformer as tfm
    from repro.serve.batcher import Request
    from repro.serve.scheduler import ContinuousScheduler

    cfg = _tiny_cfg()
    params, _ = tfm.init_model(jax.random.PRNGKey(0), cfg)
    prog = Program.build(cfg, params)
    obs = ServingObs.create(cfg, trace=True)
    sched = ContinuousScheduler(prog, capacity=2, max_len=24,
                                prefill_bucket=4, telemetry=obs)
    rng = np.random.default_rng(0)
    n = 3
    for rid in range(n):
        sched.submit(Request(
            rid=rid,
            prompt=rng.integers(1, cfg.vocab_size, 5 + rid).astype(np.int32),
            max_new=4))
    comps = sched.drain()
    return obs, sched, comps, n


class TestServingIntegration:
    def test_lifecycle_complete(self, served_telemetry):
        obs, sched, comps, n = served_telemetry
        assert len(comps) == n
        assert obs.tracker.ttft.count == n
        assert obs.tracker.e2e.count == n
        assert obs.tracker.queue.count == n
        assert (obs.registry.counter("serve.requests.completed").value == n)
        # 3 extra tokens per request beyond the first
        assert obs.tracker.tpot.count == n * 3

    def test_occupancy_and_meter_fed(self, served_telemetry):
        obs, sched, comps, n = served_telemetry
        assert sum(sched.stats.occupancy.values()) > 0
        assert obs.meter is not None
        assert obs.meter.bank_writes > 0
        assert obs.meter.matrix_passes > obs.meter.bank_writes
        assert 0.0 < obs.meter.reuse_ratio < 1.0

    def test_stats_line(self, served_telemetry):
        obs, sched, comps, n = served_telemetry
        line = obs.stats_line(sched.stats, step=17)
        assert line.startswith("[stats] step 17")
        for token in (f"reqs {n}/{n}", "ttft p50/p95", "tpot p50/p95",
                      "occ ", "reuse ", "writeE saved"):
            assert token in line, (token, line)

    def test_snapshot_validates_and_folds_trace_ledger(self,
                                                       served_telemetry):
        obs, sched, comps, n = served_telemetry
        snap = obs.snapshot()
        assert validate(snap, load_schema()) == []
        assert snap["energy"]["decode_steps"] > 0
        # the trace-time ledgers recorded on the DEFAULT registry by
        # Program.build / api dispatch are folded into the snapshot
        assert any(k.startswith("compile.trace.") for k in snap["counters"])
        assert snap["counters"].get("program.builds", 0) >= 1
        assert "program.bank.programmed_tensors" in snap["gauges"]

    def test_chrome_trace_has_request_rows(self, served_telemetry, tmp_path):
        obs, sched, comps, n = served_telemetry
        doc = obs.tracer.chrome_trace()
        evs = doc["traceEvents"]
        names = {e["name"] for e in evs}
        assert {"queue", "prefill", "decode", "finish", "active_slots",
                "sched.step", "sched.admit", "sched.prefill.dispatch",
                "sched.write_prefill", "sched.first_token.wait",
                "sched.decode.dispatch", "sched.decode.wait",
                "sched.commit"} <= names
        # one timeline row per request (tid == rid), named
        named_rows = {e["tid"] for e in evs if e["ph"] == "M"}
        assert named_rows == set(range(n))
        for e in evs:
            if e["ph"] == "X":
                assert e["dur"] >= 0.0
        out = tmp_path / "serve_trace.json"
        obs.tracer.save(str(out))
        assert len(json.loads(out.read_text())["traceEvents"]) == len(evs)

    def test_prometheus_dump(self, served_telemetry):
        obs, sched, comps, n = served_telemetry
        text = obs.to_prometheus()
        assert "serve_ttft_ms" in text
        assert "energy_reuse_ratio" in text
