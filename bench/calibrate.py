#!/usr/bin/env python3
"""Readings that set the limits of ``correct``: the program's and the control's.

Usage, from the root of a checkout on a machine with a TPU::

    python3 bench/calibrate.py --workload serve.ar5_synth --seeds 1-12 \\
        --control-seeds 1-3 --seconds 3

For each seed, in one process, it runs the cell through ``run_cell.measure``
with a short window at the cell's own load and prints ``correct`` and every
number compared (the lower readings). For the control seeds it runs the cell
again with the driver's ``Control`` in the program's place and prints the
same (the upper readings; ``correct`` has to come out false):

* serving: every kept output replaced by the reference computed with each
  convolution's operands rounded to float8 (e4m3), one precision below the
  bfloat16 the served genes run in;
* search: fitness, evaluations and α* from ``replay.py`` with every task's
  execution time rounded to float32 (the compiled core states float64).
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path
from typing import List

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def seeds_of(text: str) -> List[int]:
    out: List[int] = []
    for part in text.split(","):
        if part:
            lo, _, hi = part.partition("-")
            out.extend(range(int(lo), int(hi or lo) + 1))
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="e.g. 1-12 or 3,5,9")
    ap.add_argument("--control-seeds", default="",
                    help="seeds also run with the control")
    ap.add_argument("--seconds", type=float, default=3.0)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(BENCH), str(ROOT / "src")]
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    import harness
    import peaks
    import run_cell
    from repro.compile_cache import use_compile_cache

    device = harness.device_info()
    if device["platform"] != "tpu":
        print(f"calibrate: needs a TPU; JAX found {device}", file=sys.stderr)
        return 3
    use_compile_cache()
    spec = harness.Spec.load()
    peak = peaks.peaks(device["kind"])
    print(f"device {device}", flush=True)
    controls = set(seeds_of(args.control_seeds))
    for seed in seeds_of(args.seeds):
        for control in (False, True) if seed in controls else (False,):
            tag = "control" if control else "program"
            cell = harness.resolve(
                spec, args.workload, seed, args.seconds, False,
                emit=lambda s, tag=tag: print(f"  {tag} {s}", flush=True)
                if s.startswith(("compare", "mismatch")) else None)
            result = run_cell.measure(spec, cell, device, peak,
                                      time.perf_counter(), control=control)
            print(f"seed {seed} {tag} correct {result['correct']} checks "
                  f"{json.dumps(result['checks'])}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
