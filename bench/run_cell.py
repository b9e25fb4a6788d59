#!/usr/bin/env python3
"""Run one benchmark cell on the accelerator; print its result line.

Usage, from the root of a checkout on a machine with a TPU::

    python3 bench/run_cell.py --workload serve.ar5_synth --seed 7 --seconds 30 --trace 0

``--trace 0`` reports the cell's end-to-end metrics, ``--trace 1`` its
per-layer metrics from a profiled run. The last line of standard output is
one JSON object: ``correct``, ``attempted``, ``failed``, ``metrics``,
``device`` (and ``breakdown`` when traced), then ``checks``, every number
compared with its limit. The same checks are the last lines of standard
error. Without a TPU, or with fewer chips than the cell asks for, it exits
with status 3 and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
#: JAX's persistent compilation cache: a fixed path inside the checkout.
CACHE_DIR = ROOT / ".jax_cache"


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def main(argv=None) -> int:
    args = parse(argv)
    sys.path[:0] = [str(BENCH), str(ROOT / "src")]
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    import harness

    spec = harness.Spec.load()
    cell = harness.resolve(spec, args.workload, args.seed, args.seconds,
                           bool(args.trace),
                           emit=lambda s: print(s, flush=True))
    device = harness.device_info()
    chips = int(cell.workload["chips"])
    if device["platform"] != "tpu" or device["count"] < chips:
        log(f"run_cell: needs {chips} TPU chip(s); JAX found "
            f"{device['count']} {device['platform']!r} device(s)")
        return 3
    import peaks

    peak = peaks.peaks(device["kind"])
    from repro.compile_cache import use_compile_cache

    cell.emit(f"compile_cache {use_compile_cache()}")
    cell.emit(f"device {json.dumps(device)}")
    result = measure(spec, cell, device, peak, T_START)
    for name, c in result["checks"].items():
        log(f"check {name} {c['value']} limit {c['limit']} "
            f"{'ok' if c['value'] <= c['limit'] else 'FAILED'}")
    print(json.dumps(result), flush=True)
    return 0


def measure(spec, cell, device: dict, peak: dict, t_start: float,
            control: bool = False) -> dict:
    """Set up, run the window, check and read one cell: the result object.
    Everything of a run but the look for a chip. ``control`` puts the
    driver's control in the program's place."""
    import harness

    compiles = harness.CompileLog()
    try:
        module = harness.driver_module(cell)
        driver = (module.Control if control else module.Driver)(cell)
        driver.setup()
        setup_s = time.perf_counter() - t_start
        cell.emit(f"setup_s {setup_s} compiles {compiles.count} "
                  f"compile_s {compiles.seconds}")
        tracer = None
        if cell.trace:
            import tracing

            tracer = tracing.Tracer(ROOT / ".bench_trace" / cell.name)
        c0 = compiles.count
        driver.run_window(cell.seconds, tracer)
        in_window = compiles.count - c0
    finally:
        compiles.close()
    memory_peak = harness.memory_peak_bytes()
    driver.release()
    checks = [harness.Check("compiles_in_window", in_window, 0)]
    checks += driver.check()

    readings = dict(driver.readings(), peaks=peak)
    result_device = dict(device, memory_peak_bytes=memory_peak)
    breakdown = None
    if tracer is not None:
        summary = tracer.summary()
        readings["trace"] = summary
        result_device.update(busy_s=summary.busy_s,
                             window_s=summary.window_s)
        breakdown = summary.breakdown()
        metrics = harness.read_per_layer(spec, cell.name, readings)
    else:
        e2e = dict(driver.end_to_end(), setup_s=setup_s)
        metrics = {m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
                   for m in spec.end_to_end(cell.name)}
    attempted, failed = driver.attempted_failed()
    result = {"correct": failed == 0 and all(c.ok for c in checks),
              "attempted": attempted, "failed": failed,
              "metrics": metrics, "device": result_device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                        for c in checks}
    return result


if __name__ == "__main__":
    sys.exit(main())
