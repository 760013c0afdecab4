#!/usr/bin/env python3
"""The program's own host spans in a JAX profiler trace of a chip run.

The scheduler marks its phases with ``repro.obs.tracing.span``: host
events named ``sched.*`` on the thread that ran them, on the profiler's
clock, nested inside the harness's ``bench.step``.  A span named
``*.wait`` is the host blocked on the device; every other is host work.
From them and the device's ops this reads:

* ``idle_gaps``: each stretch of the traced window in which no op ran on
  the device, charged to the innermost ``bench.*`` or ``sched.*`` span
  open on the host at its middle (``between steps`` when none is).  On a
  trace without ``sched.*`` spans it equals ``trace_reduce``'s.
* ``sched.host_ms_per_step``: mean over the window's ``sched.step`` spans
  of the duration less the time its ``*.wait`` spans cover.
* ``sched.decode_dispatch_ms``: mean duration of ``sched.decode.dispatch``
  (host work from the decode's entry up to its enqueue).
* ``sched.queue_wait_ms``: mean ``queued_ms`` (submit to admission, on the
  scheduler's clock) of the ``sched.admit`` spans in the window.

The window is ``trace_reduce``'s: first ``bench.step`` start to last end.

    python3 benchmarks/chip/host_spans.py <trace.xplane.pb[.gz]>

prints them, with each span's count and seconds in the window, as one
JSON object.
"""
from __future__ import annotations

import bisect
import collections
import dataclasses
import gzip
import json
import sys
from pathlib import Path

from trace_reduce import _clip, _union

PREFIXES = ("bench.", "sched.")
BETWEEN = "between steps"


@dataclasses.dataclass(frozen=True)
class Span:
    start: int            # ns, the profiler's clock
    end: int
    name: str
    args: dict


def host_spans(pd) -> list[Span]:
    """Every ``bench.*`` and ``sched.*`` host event, outer before inner."""
    out = []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(PREFIXES):
                    out.append(Span(ev.start_ns, ev.start_ns + ev.duration_ns,
                                    ev.name, dict(ev.stats)))
    out.sort(key=lambda s: (s.start, -s.end))
    return out


def window(spans: list[Span]) -> tuple[int, int]:
    steps = [s for s in spans if s.name == "bench.step"]
    if not steps:
        raise ValueError("trace holds no bench.step span")
    return min(s.start for s in steps), max(s.end for s in steps)


class Innermost:
    """The innermost span open at a time, by bisection: spans nest, so
    from the latest one started it climbs to the first still open."""

    def __init__(self, spans: list[Span]):
        self.spans = spans
        self.starts = [s.start for s in spans]
        self.parent = []
        open_ = []
        for i, s in enumerate(spans):
            while open_ and spans[open_[-1]].end < s.start:
                open_.pop()
            self.parent.append(open_[-1] if open_ else -1)
            open_.append(i)

    def at(self, t: float) -> str:
        i = bisect.bisect_right(self.starts, t) - 1
        while i >= 0 and self.spans[i].end < t:
            i = self.parent[i]
        return self.spans[i].name if i >= 0 else BETWEEN


def idle_gaps(pd, spans: list[Span]) -> list:
    """``[[span name, idle seconds]]``, most first: the device's idle
    stretches of the window, each charged to the innermost host span open
    at its middle (averaged over the TPU planes, as ``trace_reduce``)."""
    w0, w1 = window(spans)
    at = Innermost(spans)
    gaps = collections.Counter()
    devices = [p for p in pd.planes if p.name.startswith("/device:TPU:")]
    for plane in devices:
        ops = {line.name: line for line in plane.lines}["XLA Ops"]
        busy = []
        for ev in ops.events:
            cs, ce = _clip(ev.start_ns, ev.start_ns + ev.duration_ns, w0, w1)
            if ce > cs:
                busy.append((cs, ce))
        prev = w0
        for s, e in _union(busy) + [[w1, w1]]:
            if s > prev:
                gaps[at.at((prev + s) / 2)] += (s - prev) * 1e-9
            prev = max(prev, e)
    n = max(len(devices), 1)
    return [[k, v / n] for k, v in gaps.most_common()]


def in_window(spans: list[Span]) -> dict:
    """``{name: [Span]}`` of the ``sched.*`` spans starting in the window."""
    w0, w1 = window(spans)
    out = collections.defaultdict(list)
    for s in spans:
        if s.name.startswith("sched.") and w0 <= s.start < w1:
            out[s.name].append(s)
    return dict(out)


def _mean(xs):
    return sum(xs) / len(xs) if xs else None


def host_ms_per_step(spans: dict):
    steps = spans.get("sched.step", [])
    waits = sorted((s for k, v in spans.items() if k.endswith(".wait")
                    for s in v), key=lambda s: s.start)
    starts = [w.start for w in waits]
    host = []
    for st in steps:
        lo = bisect.bisect_left(starts, st.start)
        hi = bisect.bisect_right(starts, st.end)
        inside = [(w.start, min(w.end, st.end)) for w in waits[lo:hi]]
        waited = sum(e - s for s, e in _union(inside))
        host.append((st.end - st.start - waited) * 1e-6)
    return _mean(host)


def decode_dispatch_ms(spans: dict):
    return _mean([(s.end - s.start) * 1e-6
                  for s in spans.get("sched.decode.dispatch", [])])


def queue_wait_ms(spans: dict):
    return _mean([s.args["queued_ms"] for s in spans.get("sched.admit", [])])


METRICS = {"sched.host_ms_per_step": host_ms_per_step,
           "sched.decode_dispatch_ms": decode_dispatch_ms,
           "sched.queue_wait_ms": queue_wait_ms}


def reduce(pd) -> dict:
    spans = host_spans(pd)
    w0, w1 = window(spans)
    win = in_window(spans)
    gaps = idle_gaps(pd, spans)
    idle = sum(v for _, v in gaps)
    named = sum(v for k, v in gaps
                if k.startswith("sched.") and k != "sched.step")
    return {"window_s": (w1 - w0) * 1e-9, "idle_s": idle,
            "idle_named_share": named / idle if idle else None,
            "idle_gaps": gaps,
            "metrics": {k: f(win) for k, f in METRICS.items()},
            "spans": {k: [len(v), sum(s.end - s.start for s in v) * 1e-9]
                      for k, v in sorted(win.items())}}


def load(path) -> object:
    from jax.profiler import ProfileData
    path = Path(path)
    if path.suffix == ".gz":
        return ProfileData.from_serialized_xspace(gzip.open(path).read())
    return ProfileData.from_file(str(path))


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    if len(argv) != 1:
        sys.exit(__doc__)
    print(json.dumps(reduce(load(argv[0]))))
    return 0


if __name__ == "__main__":
    sys.exit(main())
