#!/usr/bin/env python3
"""Readings that set a cell's ``max_logit_gap`` limit, on the chip.

    python3 benchmarks/chip/limits.py --workload deepseek-7b.chat \
        --seeds 11 12 13 --seconds 15

For each seed, in one process: a run of the cell as ``run.py`` makes it
(set-up, a window of ``--seconds`` at the cell's own load, the check
against the float32 reference), plus the control: the reference run with
every weight matmul in int4 (``--bits``) at the same prompts and served
tokens, read as the gap of the token it ranks first.  Prints one JSON line
per seed: the program's gap statistics (the lower readings come from
these) and the control's (the upper)."""
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
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=15.0)
    ap.add_argument("--bits", type=int, default=4)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT / "src"))
    sys.path.insert(0, str(HERE))
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          str(ROOT / ".jax_cache"))
    import jax
    if jax.devices()[0].platform != "tpu":
        sys.exit("limits.py: JAX found no TPU")
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    from cell import load_cell
    from harness import run_cell
    cell = load_cell(args.workload)
    t = T_PROCESS
    for seed in args.seeds:
        r = run_cell(cell, seed, args.seconds, False, t,
                     control_bits=args.bits)
        print(json.dumps({
            "workload": cell.name, "seed": seed, "program": r["program"],
            "control": r["control"], "correct": r["correct"],
            "metrics": r["metrics"], "checks": r["checks"]}), flush=True)
        t = time.perf_counter()
    return 0


if __name__ == "__main__":
    sys.exit(main())
