"""Put the benchmark's own modules and the program on the import path."""
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
for p in (BENCH, BENCH.parent / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

import time  # noqa: E402

import pytest  # noqa: E402

#: Peaks for the readings of a toy run on the host CPU (TPU v5e's).
TOY_PEAKS = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}


def toy_cell(workload: str, seed: int = 2**31 + 7, seconds: float = 1.0):
    """A cell of ``BENCHMARK.json`` at its driver's toy size (the real sizes
    are only for the chip)."""
    import harness

    cell = harness.resolve(harness.Spec.load(), workload, seed, seconds,
                           False, emit=lambda s: None)
    harness.driver_module(cell).toy(cell)
    return cell


def run_toy(cell, control: bool = False) -> dict:
    """Everything of a run but the look for a chip (``run_cell.measure``);
    ``control`` puts the driver's control in the program's place."""
    import harness
    import run_cell

    return run_cell.measure(harness.Spec.load(), cell,
                            harness.device_info(), TOY_PEAKS,
                            time.perf_counter(), control=control)


@pytest.fixture
def toy():
    return toy_cell, run_toy
