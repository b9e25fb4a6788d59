#!/usr/bin/env python3
"""Find the serving knee of a configuration: step α, print p95 and backlog.

Usage, from the root of a checkout on a machine with a TPU::

    python3 bench/sweep_knee.py --workload serve.ar5_synth --alphas 4,3,2.5,2,1.6 \\
        --seconds 8 --seed 1

One process sets the cell up once and then serves one open-loop window per
α (periods ``α * base_period`` per group, the cell's driver and
generator). For each α it prints the p95 latency per group against the
group's period, and the backlog: the mean latency of the window's last
quarter of requests over that of its first quarter, which stays near 1 when
the queue does not grow. The knee is the smallest α at which every group's
p95 stays under its period and the backlog does not grow (ratio under
1.5); the serving cells run at ``alpha_knee / load``.
"""
from __future__ import annotations

import argparse
import math
import os
import statistics
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent

BACKLOG_GROWS = 1.5


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--alphas", required=True,
                    help="comma-separated α values, served in this order")
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(BENCH), str(ROOT / "src")]
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          str(ROOT / ".jax_cache"))
    import harness
    from repro.compile_cache import use_compile_cache

    use_compile_cache()
    spec = harness.Spec.load()
    cell = harness.resolve(spec, args.workload, args.seed, args.seconds,
                           False, emit=lambda s: print(s, flush=True))
    print(f"device {harness.device_info()}", flush=True)
    driver = harness.driver_module(cell).Driver(cell)
    driver.setup()
    print("alpha periods_ms p95_ms_per_group worst_p95_over_period "
          "backlog_ratio late_p99_ms requests failed", flush=True)
    knee = None
    for alpha in (float(a) for a in args.alphas.split(",")):
        driver.set_alpha(alpha)
        driver.requests = []
        driver.run_window(args.seconds)
        time.sleep(0.5)
        lat = driver.latencies()
        per_group = []
        for g, period in enumerate(driver.periods):
            mine = [v for r, v in zip(driver.requests, lat) if r.group == g]
            per_group.append(harness.percentile(mine, 95.0))
        q = max(1, len(lat) // 4)
        first, last = lat[:q], lat[-q:]
        backlog = (statistics.mean(last) / statistics.mean(first)
                   if all(map(math.isfinite, first + last)) else math.inf)
        worst = max(p / d for p, d in zip(per_group, driver.periods))
        late = harness.percentile(
            [r.submitted - r.due for r in driver.requests], 99.0)
        failed = sum(1 for v in lat if math.isinf(v))
        print(f"{alpha} {[round(p * 1e3, 4) for p in driver.periods]} "
              f"{[round(p * 1e3, 4) for p in per_group]} {worst:.4f} "
              f"{backlog:.4f} {late * 1e3:.4f} {len(lat)} {failed}",
              flush=True)
        if worst < 1.0 and backlog < BACKLOG_GROWS and not failed:
            knee = alpha if knee is None else min(knee, alpha)
    driver.release()
    print(f"knee {knee}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
