"""From a JAX profiler trace (``.xplane.pb``) of a chip run to the numbers
the per-layer metrics read.

What a v5e trace holds (read by hand from a recorded one, kept under
``tests/data``):

* plane ``/device:TPU:<n>``, line ``XLA Modules``: one event per executed
  program, named ``jit_<function>(<fingerprint>)``: the serving cells are
  ``jit_decode_sample_cell``, ``jit__prefill_cell`` and
  ``jit_prefill_chunk_cell``; eager work shows as ``jit_<primitive>``.
* the same plane, line ``XLA Ops``: one event per HLO op, named by its
  HLO text ``%<name>.<n> = <shape> <opcode>(...)``.  A Pallas kernel is a
  ``custom-call`` named after the kernel: ``%photonic_mvm_fused.<n>`` for
  the fused W8A8 MVM, ``%photonic.flash_attn.<bq>x<bk>.<n>`` for flash
  attention.  A ``while`` op spans the ops of its body, which are events
  of their own.
* plane ``/host:CPU``: the host threads; the benchmark's own
  ``jax.profiler.TraceAnnotation`` spans (``bench.step``, ``bench.submit``,
  ``bench.wait``) are on the thread that ran them.  Host and device events
  share one clock (nanoseconds).

The traced window runs from the start of the first ``bench.step`` to the
end of the last.  Busy time is the union of the ``XLA Ops`` intervals in
it; an op belongs to the phase (``decode``, ``prefill``, ``other``) of the
program whose ``XLA Modules`` interval holds its start.
"""
from __future__ import annotations

import bisect
import collections
import dataclasses
import glob
import re
from pathlib import Path

KERNELS = (("photonic_mvm_fused", "fused_mvm"),
           ("photonic.flash_attn", "flash_attn"))
CONTAINERS = ("while", "conditional", "call")
_OP = re.compile(r"^%?(?P<name>[^ ]+) = .*? (?P<opcode>[a-z\-]+)\(")


@dataclasses.dataclass
class Summary:
    window_s: float
    busy_s: float
    module_seconds: dict       # phase -> device seconds of its programs
    module_count: dict         # phase -> executions
    kernel_seconds: dict       # (phase, kernel kind) -> device seconds
    kernel_count: dict
    top_ops: list              # [[op, seconds]] by total device time
    idle_gaps: list            # [[host span during the gap, seconds]]


def phase_of(module: str) -> str:
    if "decode" in module:
        return "decode"
    if "prefill" in module:
        return "prefill"
    return "other"


def op_base(event_name: str) -> tuple[str, str]:
    """(name without its ``.<n>`` suffix, opcode) of an ``XLA Ops`` event."""
    m = _OP.match(event_name)
    if not m:
        return event_name, ""
    return re.sub(r"\.\d+$", "", m.group("name")), m.group("opcode")


def kernel_kind(base: str):
    for prefix, kind in KERNELS:
        if base.startswith(prefix):
            return kind
    return None


def _union(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _clip(s, e, w0, w1):
    return max(s, w0), min(e, w1)


def reduce(pd) -> Summary:
    """Reduce a ``jax.profiler.ProfileData``."""
    host_spans = []
    devices = []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            devices.append(plane)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("bench."):
                        host_spans.append((ev.start_ns,
                                           ev.start_ns + ev.duration_ns,
                                           ev.name))
    steps = [s for s in host_spans if s[2] == "bench.step"]
    if not steps or not devices:
        raise ValueError("trace holds no bench.step span or no TPU plane")
    w0 = min(s[0] for s in steps)
    w1 = max(s[1] for s in steps)
    window = (w1 - w0) * 1e-9

    mod_sec = collections.Counter()
    mod_cnt = collections.Counter()
    ker_sec = collections.Counter()
    ker_cnt = collections.Counter()
    op_sec = collections.Counter()
    busy_total = 0.0
    gaps = collections.Counter()
    host_spans.sort()
    for plane in devices:
        lines = {line.name: line for line in plane.lines}
        mods = sorted((ev.start_ns, ev.start_ns + ev.duration_ns,
                       re.sub(r"\(\d+\)$", "", ev.name))
                      for ev in lines["XLA Modules"].events)
        starts = [m[0] for m in mods]
        for s, e, name in mods:
            cs, ce = _clip(s, e, w0, w1)
            if ce > cs:
                mod_sec[phase_of(name)] += (ce - cs) * 1e-9
            if w0 <= s < w1:
                mod_cnt[phase_of(name)] += 1
        busy = []
        for ev in lines["XLA Ops"].events:
            s, e = ev.start_ns, ev.start_ns + ev.duration_ns
            cs, ce = _clip(s, e, w0, w1)
            if ce <= cs:
                continue
            busy.append((cs, ce))
            base, opcode = op_base(ev.name)
            if opcode in CONTAINERS:
                continue
            secs = (ce - cs) * 1e-9
            op_sec[base] += secs
            kind = kernel_kind(base)
            if kind:
                i = bisect.bisect_right(starts, s) - 1
                phase = (phase_of(mods[i][2])
                         if i >= 0 and s < mods[i][1] else "other")
                ker_sec[(phase, kind)] += secs
                ker_cnt[(phase, kind)] += 1
        merged = _union(busy)
        busy_total += sum(e - s for s, e in merged) * 1e-9
        prev = w0
        for s, e in merged + [[w1, w1]]:
            if s > prev:
                gaps[_host_at(host_spans, (prev + s) / 2)] += (s - prev) * 1e-9
            prev = max(prev, e)
    n = len(devices)
    return Summary(
        window_s=window, busy_s=busy_total / n,
        module_seconds={k: v / n for k, v in mod_sec.items()},
        module_count={k: v / n for k, v in mod_cnt.items()},
        kernel_seconds={k: v / n for k, v in ker_sec.items()},
        kernel_count={k: v / n for k, v in ker_cnt.items()},
        top_ops=[[k, v / n] for k, v in op_sec.most_common(10)],
        idle_gaps=[[k, v / n] for k, v in gaps.most_common(10)])


def _host_at(spans, t: float) -> str:
    """The innermost benchmark span open at ``t`` ("between steps" when
    none is)."""
    best = None
    for s, e, name in spans:
        if s > t:
            break
        if e >= t and (best is None or e - s < best[1] - best[0]):
            best = (s, e, name)
    return best[2] if best else "between steps"


def reduce_file(path) -> Summary:
    from jax.profiler import ProfileData
    return reduce(ProfileData.from_file(str(path)))


def reduce_dir(trace_dir: Path) -> Summary:
    files = glob.glob(str(Path(trace_dir) / "**" / "*.xplane.pb"),
                      recursive=True)
    if len(files) != 1:
        raise ValueError(f"expected one .xplane.pb under {trace_dir}, "
                         f"found {len(files)}")
    return reduce_file(files[0])
