"""Shared serving-stats protocol — WaveStats / ContinuousStats unified.

Before this module the two schedulers each carried an ad-hoc dataclass and
only agreed on the ``overhead`` waste metric *by convention*.  Both now
derive from :class:`ServingStats`: every field is a property backed by a
counter in a :class:`~repro.obs.metrics.MetricsRegistry`, so

  * the historical surface is unchanged — ``stats.requests += 1``,
    ``stats.overhead``, ``ContinuousStats(_capacity=8)`` all behave exactly
    as the old dataclasses did (``serve_bench`` comparisons stay valid);
  * the same numbers flow into the registry snapshot / Prometheus dump for
    free (one source of truth — no parallel bookkeeping to drift);
  * the shared waste metric lives ONCE, on the base class.

``ContinuousStats`` additionally records the per-step *active-slot
histogram* (``observe_active``): the occupancy distribution over time, not
just the aggregate idle counter — the signal the weight-bank residency
manager (ROADMAP) needs to place hot vs cold banks.
"""
from __future__ import annotations

import collections

from repro.obs import metrics as _metrics


def _counter_property(field: str, doc: str = ""):
    name = f"serve.{field}"

    def fget(self):
        return self._int(self.registry.counter(name).value)

    def fset(self, v):
        self.registry.counter(name).set(float(v))

    return property(fget, fset, doc=doc)


class ServingStats:
    """Registry-backed counters + the shared waste metric.

    ``slot_steps`` counts executed slot-token-steps (including padding and
    idle lanes); ``useful_steps`` the processed positions that actually
    served a request.  ``overhead`` — the wasted fraction — is THE metric
    the two schedulers compare on.
    """

    FIELDS: tuple = ("requests", "prompt_tokens", "generated_tokens",
                     "slot_steps", "useful_steps")

    def __init__(self, registry: _metrics.MetricsRegistry | None = None):
        self.registry = registry or _metrics.MetricsRegistry()

    @staticmethod
    def _int(v: float):
        i = int(v)
        return i if i == v else v

    @property
    def overhead(self) -> float:
        """Wasted fraction of executed slot-token-steps."""
        return (1.0 - self.useful_steps / self.slot_steps
                if self.slot_steps else 0.0)

    def as_dict(self) -> dict:
        d = {f: getattr(self, f) for f in self.FIELDS}
        d["overhead"] = self.overhead
        return d

    def __repr__(self):
        inner = ", ".join(f"{k}={v}" for k, v in self.as_dict().items())
        return f"{type(self).__name__}({inner})"


for _f in ServingStats.FIELDS:
    setattr(ServingStats, _f, _counter_property(_f))


class WaveStats(ServingStats):
    """Static wave scheduler: padding + lockstep-decode waste."""

    FIELDS = ServingStats.FIELDS + ("waves", "padded_tokens")

    @property
    def padding_overhead(self) -> float:
        total = self.prompt_tokens + self.padded_tokens
        return self.padded_tokens / total if total else 0.0


for _f in ("waves", "padded_tokens"):
    setattr(WaveStats, _f, _counter_property(_f))


class ContinuousStats(ServingStats):
    """Continuous batching: bucket padding + idle decode lanes, plus the
    per-step active-slot occupancy distribution.

    On a model with experts, every decode step also counts, over its live
    slots and summed over its MoE layers: ``moe_routed_tokens`` (row-expert
    assignments, live rows x top_k), ``moe_experts_hit`` (experts that took
    at least one live row: the expert banks a step needs to read) and
    ``moe_max_expert_load`` (the most live rows any one expert took), so a
    window's deltas over ``decode_steps`` give their means."""

    MOE_FIELDS = ("moe_routed_tokens", "moe_experts_hit",
                  "moe_max_expert_load")
    FIELDS = ServingStats.FIELDS + ("prefills", "decode_steps",
                                    "padded_prefill_tokens",
                                    "idle_slot_steps",
                                    "prefill_chunks") + MOE_FIELDS

    def __init__(self, registry: _metrics.MetricsRegistry | None = None,
                 _capacity: int = 1):
        super().__init__(registry)
        self._capacity = _capacity
        # exact integer distribution (residency-manager input) + registry
        # histogram (percentile export share one schema with latencies)
        self.occupancy: collections.Counter = collections.Counter()
        self._occ_hist = self.registry.histogram("serve.active_slots",
                                                 lo=0.5, growth=1.05)

    def observe_routing(self, loads) -> None:
        """Count one decode step's routing: ``loads`` (MoE layers, experts)
        live rows per expert."""
        self.moe_routed_tokens += int(loads.sum())
        self.moe_experts_hit += int((loads > 0).sum())
        self.moe_max_expert_load += int(loads.max(axis=1).sum())

    def observe_active(self, n: int) -> None:
        """Record one decode step's active-slot count."""
        self.occupancy[int(n)] += 1
        self._occ_hist.record(n)
        self.registry.gauge("serve.slots.active").set(n)

    @property
    def occupancy_distribution(self) -> dict:
        """{active_slots: steps} over all decode steps, exact."""
        return dict(sorted(self.occupancy.items()))

    @property
    def mean_occupancy(self) -> float:
        steps = sum(self.occupancy.values())
        if not steps:
            return 0.0
        return sum(k * v for k, v in self.occupancy.items()) / steps

    @property
    def idle_fraction(self) -> float:
        if not self.decode_steps:
            return 0.0
        return self.idle_slot_steps / (self.decode_steps * self._capacity)


for _f in ("prefills", "decode_steps", "padded_prefill_tokens",
           "idle_slot_steps", "prefill_chunks") + ContinuousStats.MOE_FIELDS:
    setattr(ContinuousStats, _f, _counter_property(_f))
del _f
