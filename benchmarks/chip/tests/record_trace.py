#!/usr/bin/env python3
"""Record the small trace the reduction tests read, on a TPU host:

    python3 benchmarks/chip/tests/record_trace.py <out.xplane.pb.gz>

minitron-4b at its published widths cut to 2 layers, photonic, weights
from seed 0, slot capacity 4, max_len 1024, bucket 128, chunk 512.
Prompts of 700, 100 and 300 tokens with 12 output tokens each are served
once untraced (every shape compiles), then again on a fresh scheduler under
the profiler: each submission inside ``bench.submit`` and each step inside
``bench.step``, as in the harness.  All three are admitted in the first
step (700 as two 512-row chunks, 100 and 300 at buckets 128 and 384), so
the trace holds 12 steps, 12 decodes and 4 prefill programs.
"""
from __future__ import annotations

import dataclasses
import glob
import gzip
import shutil
import sys
import tempfile
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parents[2] / "src"))
sys.path.insert(0, str(HERE.parent))

PROMPTS = (700, 100, 300)
NEW_TOKENS = 12
SERVE = {"capacity": 4, "max_len": 1024, "prefill_bucket": 128,
         "prefill_chunk": 512}


def requests(vocab: int):
    import numpy as np
    from repro.serve.batcher import Request
    rng = np.random.default_rng(0)
    return [Request(rid=i, prompt=rng.integers(1, vocab, n, dtype=np.int32),
                    max_new=NEW_TOKENS) for i, n in enumerate(PROMPTS)]


def record(cfg, out: Path, serve: dict = SERVE) -> None:
    import jax
    from jax.profiler import TraceAnnotation
    from repro.api import Program
    from repro.models import transformer as tfm
    from repro.serve.scheduler import ContinuousScheduler
    import harness
    import weights

    params = weights.make_params(tfm.abstract_params(cfg), 0,
                                 jax.numpy.dtype(cfg.compute_dtype))
    prog = Program.build(cfg, params)
    del params

    def scheduler():
        return ContinuousScheduler(prog, **serve)

    warm = scheduler()
    for r in requests(cfg.vocab_size):
        warm.submit(r)
    warm.drain()
    sched = scheduler()
    for x in jax.live_arrays():
        x.block_until_ready()
    tmp = Path(tempfile.mkdtemp())
    harness.start_trace(tmp)
    try:
        for r in requests(cfg.vocab_size):
            with TraceAnnotation("bench.submit"):
                sched.submit(r)
        while sched.queue or sched.pool.num_active:
            with TraceAnnotation("bench.step"):
                sched.step()
        jax.block_until_ready(sched.pool.caches)
    finally:
        jax.profiler.stop_trace()
    (pb,) = glob.glob(str(tmp / "**" / "*.xplane.pb"), recursive=True)
    with open(pb, "rb") as f, gzip.open(out, "wb") as g:
        shutil.copyfileobj(f, g)
    shutil.rmtree(tmp, ignore_errors=True)


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    if len(argv) != 1:
        sys.exit(__doc__)
    import jax
    from repro.configs import get_arch
    if jax.devices()[0].platform != "tpu":
        sys.exit("record_trace.py: JAX found no TPU")
    cfg = dataclasses.replace(get_arch("minitron-4b"), num_layers=2,
                              execution="photonic",
                              compute_dtype="bfloat16",
                              param_dtype="bfloat16")
    record(cfg, Path(argv[0]))
    return 0


if __name__ == "__main__":
    sys.exit(main())
