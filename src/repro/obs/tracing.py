"""Program spans on the profiler's clock, and a span/event tracer with
Chrome-trace (``chrome://tracing``) JSON export.

:func:`span` is the one way the program marks host work: it always enters
``jax.profiler.TraceAnnotation``, so while a profiler session runs (``jax.
profiler.trace``, the chip benchmark's ``--trace 1``) the span lands in the
profiler trace on the same clock as the device ops, and costs about what a
``nullcontext`` does when none runs.  Given an enabled :class:`Tracer` it
also records the same ``X`` event there.

The request-lifecycle visualization layer: the serving scheduler emits one
timeline *row per request* (trace ``tid`` = request id) carrying its
``queue -> prefill -> decode`` spans, plus row 0 for its ``sched.*`` spans —
load the exported file in ``chrome://tracing`` / Perfetto and the
continuous-batching queue becomes a picture (admission waves, slot churn,
stragglers).

Events follow the Trace Event Format: ``X`` complete spans (``ts`` +
``dur``, microseconds), ``i`` instants, ``C`` counter tracks (the live
slot-occupancy graph), ``M`` metadata (thread names).  The event buffer is
a bounded deque so a long-running server cannot grow without limit; the
tracer is disabled by default and every record call is a one-bool check
when off (the serving hot path pays nothing — the <= 5% overhead budget of
``backend_bench --smoke`` is measured with it ON).
"""
from __future__ import annotations

import collections
import contextlib
import json
import time

from jax import profiler as _profiler


class Tracer:
    """Bounded in-memory trace-event buffer."""

    def __init__(self, maxlen: int = 200_000, enabled: bool = True):
        self.events: collections.deque = collections.deque(maxlen=maxlen)
        self.enabled = enabled
        self._t0 = time.monotonic()

    # -------------------------------------------------------------- clock
    def now_us(self) -> float:
        """Microseconds since tracer start (Chrome trace timebase)."""
        return (time.monotonic() - self._t0) * 1e6

    # ------------------------------------------------------------- records
    def complete(self, name: str, ts_us: float, dur_us: float, *,
                 tid: int = 0, **args) -> None:
        """An ``X`` span from explicit timestamps — how the request tracker
        emits lifecycle phases after the fact (arrive/admit/first/finish
        were recorded as the steps happened)."""
        if not self.enabled:
            return
        ev = {"name": name, "ph": "X", "pid": 0, "tid": int(tid),
              "ts": ts_us, "dur": max(dur_us, 0.0)}
        if args:
            ev["args"] = args
        self.events.append(ev)

    @contextlib.contextmanager
    def span(self, name: str, *, tid: int = 0, **args):
        """Context-managed ``X`` span around live work."""
        if not self.enabled:
            yield
            return
        t0 = self.now_us()
        try:
            yield
        finally:
            self.complete(name, t0, self.now_us() - t0, tid=tid, **args)

    def instant(self, name: str, *, tid: int = 0, **args) -> None:
        if not self.enabled:
            return
        ev = {"name": name, "ph": "i", "pid": 0, "tid": int(tid),
              "ts": self.now_us(), "s": "t"}
        if args:
            ev["args"] = args
        self.events.append(ev)

    def counter(self, name: str, value: float, *, tid: int = 0) -> None:
        """A ``C`` counter sample — chrome renders these as a filled graph
        (slot occupancy over time)."""
        if not self.enabled:
            return
        self.events.append({"name": name, "ph": "C", "pid": 0,
                            "tid": int(tid), "ts": self.now_us(),
                            "args": {name: value}})

    def thread_name(self, tid: int, name: str) -> None:
        """``M`` metadata naming a timeline row (e.g. ``req 7``)."""
        if not self.enabled:
            return
        self.events.append({"name": "thread_name", "ph": "M", "pid": 0,
                            "tid": int(tid),
                            "args": {"name": name}})

    # -------------------------------------------------------------- export
    def chrome_trace(self) -> dict:
        """The ``chrome://tracing`` JSON object (structurally validated in
        tests/test_obs.py)."""
        return {"traceEvents": list(self.events), "displayTimeUnit": "ms"}

    def save(self, path: str) -> None:
        with open(path, "w") as f:
            json.dump(self.chrome_trace(), f)


def span(name: str, *, tracer: "Tracer | None" = None, **args):
    """Context manager marking host work ``name`` with ``args`` (ints,
    floats, strings): a profiler annotation, whose args become event stats,
    and with an enabled ``tracer`` the same Chrome ``X`` span."""
    if tracer is not None and tracer.enabled:
        return _both(name, tracer, args)
    return _profiler.TraceAnnotation(name, **args)


@contextlib.contextmanager
def _both(name: str, tracer: "Tracer", args: dict):
    with _profiler.TraceAnnotation(name, **args), \
            tracer.span(name, **args):
        yield


# A process-wide disabled tracer: instrumentation sites can always call
# through it; ``enable_tracing`` flips it live.
_DEFAULT = Tracer(enabled=False)


def default_tracer() -> Tracer:
    return _DEFAULT


def enable_tracing(on: bool = True) -> Tracer:
    _DEFAULT.enabled = on
    return _DEFAULT
