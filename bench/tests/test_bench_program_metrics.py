"""The per-layer metrics that read the program's own spans and counters:
nothing from the other kind of cell's readings or from a program without them,
the expected value from synthetic counters, and a finite value from a
traced toy run through ``run_cell.measure``."""
import math
from collections import Counter

import pytest

import harness
import tracing

SEARCH = ("tables_s.search", "lanes_s.search", "ga_host_s.search",
          "lane_occupancy.search")
SERVE = ("dispatch_us.serve", "stage_us.serve", "kernel_ms.serve")

SEARCH_READINGS = {"kind": "search", "searches": [], "traced_iters": None}
SERVE_READINGS = {"kind": "serve", "lateness_s": [], "wait_s": [],
                  "exec_s": [], "window_s": 1.0, "window_flops": 0.0,
                  "traced_work": [(1.0, 2.0)] * 4}


def read(name, readings):
    return harness.metric_module(name).read(readings)


@pytest.fixture
def counters(monkeypatch):
    """Fresh program counters in place of the process's own."""
    from repro.core import batchsim_compiled, ga
    from repro.runtime import engine

    fresh = {"batch": Counter(), "ga": Counter(), "serve": Counter()}
    monkeypatch.setattr(batchsim_compiled, "totals", fresh["batch"])
    monkeypatch.setattr(ga, "totals", fresh["ga"])
    monkeypatch.setattr(engine, "totals", fresh["serve"])
    return fresh


def fill(c):
    c["ga"].update({"puzzle.ga.run.n": 4, "puzzle.ga.mate.ns": 1e8,
                    "puzzle.ga.local.ns": 2e8, "puzzle.ga.select.ns": 5e8,
                    "puzzle.ga.eval.ns": 9e9})
    c["batch"].update({"puzzle.batch.tables.ns": 2e9,
                       "puzzle.batch.lanes.ns": 6e8,
                       "lane_events": 300, "lane_slots": 1200})
    c["serve"].update({"puzzle.serve.dispatch.ns": 4e5,
                       "puzzle.serve.dispatch.n": 10,
                       "puzzle.serve.stage.ns": 3e4,
                       "puzzle.serve.stage.n": 10})


def test_expected_values(counters):
    fill(counters)
    assert read("tables_s.search", SEARCH_READINGS) == pytest.approx(0.5)
    assert read("lanes_s.search", SEARCH_READINGS) == pytest.approx(0.15)
    assert read("ga_host_s.search", SEARCH_READINGS) == pytest.approx(0.2)
    assert read("lane_occupancy.search", SEARCH_READINGS) == \
        pytest.approx(25.0)
    assert read("dispatch_us.serve", SERVE_READINGS) == pytest.approx(40.0)
    assert read("stage_us.serve", SERVE_READINGS) == pytest.approx(3.0)


def test_kernel_ms_reads_the_named_programs():
    trace = tracing.TraceSummary(
        window_s=1.0, busy_s=0.5,
        programs={"jit_puzzle_yolov8n_0_23": 0.006,
                  "jit_puzzle_face_det_0_4": 0.002,
                  "jit_convert_element_type": 0.5})
    r = dict(SERVE_READINGS, trace=trace)
    assert read("kernel_ms.serve", r) == pytest.approx(2.0)
    # programs under the jit's own function name (no subgraph name)
    old = tracing.TraceSummary(window_s=1.0, busy_s=0.5,
                               programs={"jit_fn": 0.5, "jit_wrapped": 0.1})
    assert read("kernel_ms.serve", dict(SERVE_READINGS, trace=old)) is None
    assert read("kernel_ms.serve", SERVE_READINGS) is None


@pytest.mark.parametrize("name", SEARCH + SERVE)
def test_other_kinds_readings_read_nothing(name, counters):
    fill(counters)
    trace = tracing.TraceSummary(window_s=1.0, busy_s=0.5,
                                 programs={"jit_puzzle_a_0_1": 0.5})
    other = SERVE_READINGS if name in SEARCH else SEARCH_READINGS
    assert read(name, dict(other, trace=trace)) is None


@pytest.mark.parametrize("name", SEARCH + SERVE[:2])
def test_program_without_the_counters_reads_nothing(name, counters,
                                                    monkeypatch):
    """A program without the spans (empty counters, or no ``totals`` in
    the GA or runtime module) reads nothing and does not raise."""
    own = SEARCH_READINGS if name in SEARCH else SERVE_READINGS
    assert read(name, own) is None
    from repro.core import ga
    from repro.runtime import engine

    fill(counters)
    monkeypatch.delattr(ga, "totals")
    monkeypatch.delattr(engine, "totals")
    counters["batch"].pop("lane_slots")
    assert read(name, own) is None


@pytest.mark.parametrize("workload,names", [
    pytest.param("search.ar5_synth", SEARCH, id="search.ar5_synth"),
    pytest.param("serve.ar5_synth", SERVE[:2], id="serve.ar5_synth")])
def test_traced_toy_run_reads_each_metric(workload, names, toy, tmp_path,
                                          monkeypatch):
    """Every program metric of the cell is finite in a traced toy run on
    the host CPU (the kernel metric needs a device plane, so only the
    program's counters are read here)."""
    import run_cell

    monkeypatch.setattr(run_cell, "ROOT", tmp_path)
    toy_cell, run_toy = toy
    cell = toy_cell(workload)
    cell.trace = True
    result = run_toy(cell)
    assert result["correct"], result["checks"]
    for name in names:
        value = result["metrics"][name]["value"]
        assert math.isfinite(value) and value > 0.0, name
