#!/usr/bin/env python3
"""Run one cell of the chip benchmark once.

    python3 benchmarks/chip/run.py --workload deepseek-7b.chat --seed 7 \
        --seconds 40 --trace 0

from the root of a checkout, on a machine with the chips the cell asks
for.  Set-up (weights from the seed, ``Program.build``, warm-up of every
shape the traffic uses) is timed as ``setup_s``; then the traffic runs for
``--seconds``; then the served tokens are checked against the plain
reference (``check.py``).  ``--trace 0`` reports the cell's end-to-end
metrics, ``--trace 1`` traces the end of the window with the JAX profiler
and reports its per-layer metrics.  The last line of stdout is one JSON
object; the numbers compared and their limits are the last lines of
stderr.  Exits non-zero, printing no result, off the TPU, with fewer chips
than the cell asks for, or without the program's ``src/`` beside it.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        sys.exit(f"run.py: no src/repro under {ROOT}: the program under "
                 f"test is missing")
    sys.path.insert(0, str(ROOT / "src"))
    sys.path.insert(0, str(HERE))
    # the persistent compilation cache lives at a fixed path inside the
    # checkout (its path is part of every entry's key); a cache directory
    # the environment names is honoured
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          str(ROOT / ".jax_cache"))
    from cell import load_cell
    try:
        cell = load_cell(args.workload)
    except (KeyError, FileNotFoundError) as e:
        sys.exit(f"run.py: {e}")
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit(f"run.py: JAX found no TPU (platform "
                 f"{devices[0].platform!r}); nothing was run")
    if len(devices) < cell.chips:
        sys.exit(f"run.py: {cell.name} needs {cell.chips} chips, JAX found "
                 f"{len(devices)}")
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    from harness import run_cell
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                      t_process=T_PROCESS)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
