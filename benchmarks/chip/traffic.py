"""Seeded load generator: the one general generator every traffic mix
(``traffic/<mix>.json``) is read by.

A mix fixes a *population* of request sizes: ``population`` (prompt,
output) pairs at evenly spaced quantiles of the two length distributions,
paired by a fixed permutation.  A run's seed only orders that population
and draws the token ids, so every seed serves the same set of sizes and,
in an open loop, the same set of inter-arrival gaps: what changes between
seeds is the order, not the work.  ``order`` says how: ``shuffle`` (the
default) draws a fresh permutation for every pass over the population;
``fixed`` serves one permutation, the same for every seed, over and over,
so the seed draws only the token ids.  In a closed loop the order is part
of the work: it decides which long prompts stage their prefill together,
and so where a tail percentile of the first token lies.

Distributions: ``lognormal`` (``median``, ``sigma``), ``loguniform`` and
``uniform``, each clipped to ``[min, max]``.  Loops: ``closed`` (``clients``
callers, each sending its next request when the last completes) and
``open`` (Poisson arrivals at ``rate_per_s``: exponential gaps at evenly
spaced quantiles, shuffled per pass).
"""
from __future__ import annotations

import math
import statistics

import numpy as np


def quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def lengths(dist: dict, n: int) -> np.ndarray:
    """``n`` lengths at evenly spaced quantiles of ``dist``, clipped."""
    q = quantiles(n)
    lo, hi = dist["min"], dist["max"]
    kind = dist["dist"]
    if kind == "lognormal":
        z = np.array([statistics.NormalDist().inv_cdf(x) for x in q])
        v = dist["median"] * np.exp(dist["sigma"] * z)
    elif kind == "loguniform":
        v = lo * (hi / lo) ** q
    elif kind == "uniform":
        v = lo + q * (hi - lo)
    else:
        raise ValueError(f"unknown length distribution {kind!r}")
    return np.clip(np.rint(v), lo, hi).astype(np.int64)


def population(spec: dict) -> list[tuple[int, int]]:
    """The mix's fixed (prompt_len, max_new) pairs."""
    n = spec["population"]
    prompts = lengths(spec["prompt_len"], n)
    outputs = lengths(spec["output_len"], n)
    pairing = np.random.default_rng(0).permutation(n)
    return [(int(p), int(outputs[j])) for p, j in zip(prompts, pairing)]


def gap_population(spec: dict) -> np.ndarray:
    """Exponential inter-arrival gaps (seconds) at evenly spaced quantiles:
    their mean is 1 / rate to within the discretisation."""
    q = quantiles(spec["population"])
    return -np.log1p(-q) / spec["rate_per_s"]


class Traffic:
    """Requests and arrival gaps of one mix under one seed."""

    def __init__(self, spec: dict, seed: int, vocab: int):
        if spec["loop"] not in ("closed", "open"):
            raise ValueError(f"unknown loop {spec['loop']!r}")
        self.spec = spec
        self.vocab = vocab
        self.pop = population(spec)
        self._req_rng = np.random.default_rng([seed, 0])
        self._gap_rng = np.random.default_rng([seed, 1])
        self._order: list[int] = []
        self._gaps: list[float] = []
        self.order = spec.get("order", "shuffle")
        if self.order not in ("shuffle", "fixed"):
            raise ValueError(f"unknown order {self.order!r}")
        self._cycle = list(np.random.default_rng(1).permutation(len(self.pop)))
        self._pos = 0
        self.gap_pop = (gap_population(spec) if spec["loop"] == "open"
                        else None)

    @property
    def closed(self) -> bool:
        return self.spec["loop"] == "closed"

    def next_request(self) -> tuple[np.ndarray, int]:
        """(prompt token ids, max_new) of the next request."""
        if self.order == "fixed":
            i = self._cycle[self._pos % len(self._cycle)]
            self._pos += 1
        else:
            if not self._order:
                self._order = list(self._req_rng.permutation(len(self.pop)))
            i = self._order.pop()
        plen, max_new = self.pop[i]
        prompt = self._req_rng.integers(1, self.vocab, plen, dtype=np.int32)
        return prompt, max_new

    def next_gap(self) -> float:
        """Seconds from the previous arrival to the next (open loop)."""
        if not self._gaps:
            self._gaps = list(self._gap_rng.permutation(self.gap_pop))
        return float(self._gaps.pop())

    def prompt_lengths(self) -> list[int]:
        """Every prompt length a request of this mix can have (what
        warm-up must cover)."""
        return sorted({p for p, _ in self.pop})


def percentile(values, q: float) -> float:
    """Exact nearest-rank percentile (``q`` in [0, 100]); inf if any value
    is inf, nan for an empty sample."""
    v = sorted(values)
    if not v:
        return math.nan
    k = max(0, math.ceil(q / 100.0 * len(v)) - 1)
    return float(v[k])
