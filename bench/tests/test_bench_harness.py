"""Every cell resolves its files by name; each driver runs at a toy size,
which its own ``toy`` sets; the measuring command refuses a host without a
TPU."""
import copy
import json
import os
import subprocess
import sys

import pytest

import harness

SPEC = harness.Spec.load()
WORKLOADS = sorted(SPEC.workloads)
FAMILY_FUNCTIONS = ("executables", "graphs", "reference", "worst_rel_l2",
                    "work", "macs", "toy")


@pytest.mark.parametrize("workload", WORKLOADS)
def test_workload_resolves_by_name(workload):
    w = SPEC.workloads[workload]
    cfg = SPEC.configs[w["config"]]
    assert cfg["file"].startswith("bench/")
    assert (harness.ROOT / cfg["file"]).is_file()
    assert harness.traffic_path(w["traffic"]).is_file()
    cell = harness.resolve(SPEC, workload, 1, 1.0, False)
    assert cell.config["name"] == w["config"]
    driver = harness.driver_module(cell)
    for method in ("setup", "run_window", "release", "check", "end_to_end",
                   "readings", "attempted_failed"):
        assert callable(getattr(driver.Driver, method))
    assert issubclass(driver.Control, driver.Driver)
    assert callable(driver.toy)
    family = harness.family_module(cell.config)
    for fn in FAMILY_FUNCTIONS:
        assert callable(getattr(family, fn))
    e2e = {m["name"] for m in SPEC.end_to_end(workload)}
    assert "setup_s" in e2e and len(e2e) >= 2
    layers = SPEC.per_layer(workload)
    assert layers
    for m in layers:
        assert m["moves"] in e2e
        assert callable(harness.metric_module(m["name"]).read)


def test_every_file_is_used():
    used = {m["name"] for m in SPEC.raw["per_layer"]}
    files = {p.name[:-3] for p in (harness.BENCH / "metrics").glob("*.py")}
    assert files == used
    traffics = {w["traffic"] for w in SPEC.workloads.values()}
    assert {p.stem for p in (harness.BENCH / "traffic").glob("*.json")} \
        == traffics
    drivers = {json.loads(harness.traffic_path(t).read_text())["driver"]
               for t in traffics}
    assert {p.stem for p in (harness.BENCH / "drivers").glob("*.py")} \
        == drivers
    families = {json.loads((harness.ROOT / c["file"]).read_text())
                .get("family", harness.DEFAULT_FAMILY)
                for c in SPEC.configs.values()}
    assert {p.stem for p in (harness.BENCH / "families").glob("*.py")} \
        == families | {harness.DEFAULT_FAMILY}


def test_command_refuses_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, str(harness.BENCH / "run_cell.py"), "--workload",
         WORKLOADS[0], "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=harness.ROOT, env=env, capture_output=True, text=True,
        timeout=300)
    assert out.returncode == 3
    assert "correct" not in out.stdout


@pytest.mark.parametrize("workload", WORKLOADS)
def test_driver_runs_at_toy_size(workload, toy):
    toy_cell, run_toy = toy
    result = run_toy(toy_cell(workload))
    assert result["correct"], result["checks"]
    assert result["attempted"] > 0 and result["failed"] == 0
    assert set(result["metrics"]) == {
        m["name"] for m in SPEC.end_to_end(workload)}
    assert all(v["value"] > 0 for v in result["metrics"].values())
    assert list(result)[-1] == "checks"


def test_same_seed_same_inputs(toy):
    toy_cell, _ = toy
    a = harness.driver_module(toy_cell("serve.ar5_synth")).Driver(
        toy_cell("serve.ar5_synth", seed=11))
    b = harness.driver_module(toy_cell("serve.ar5_synth")).Driver(
        toy_cell("serve.ar5_synth", seed=11))
    za, zb = a.build_zoo(), b.build_zoo()
    for name in za:
        assert (za[name].model_input() == zb[name].model_input()).all()


# The toy sizes the test fixture set itself before each driver's ``toy``
# did, kept as the record that ``toy`` must reproduce for these cells.
def _old_serve_toy(cell):
    for shape in cell.config["networks"].values():
        shape["spatial"], shape["channels"] = 16, 4
    cell.config["alpha_knee"] = 20.0
    cell.traffic["warmup_requests"] = 1
    cell.traffic["trace_seconds"] = 0.3
    cell.traffic["settle_s"] = 0.1


def _old_search_toy(cell):
    cell.traffic["ga"] = {"pop_size": 6, "generations": 2}
    cell.seconds = 0.0


@pytest.mark.parametrize("workload,old_toy", [
    ("serve.ar5_synth", _old_serve_toy),
    ("serve.heavy4_synth", _old_serve_toy),
    ("search.ar5_synth", _old_search_toy),
    ("search.heavy4_synth_faults", _old_search_toy)])
def test_driver_toy_is_the_old_toy(workload, old_toy):
    cell = harness.resolve(SPEC, workload, 5, 1.0, False)
    expected = copy.deepcopy(cell)
    old_toy(expected)
    harness.driver_module(cell).toy(cell)
    assert cell.config == expected.config
    assert cell.traffic == expected.traffic
    assert cell.seconds == expected.seconds
