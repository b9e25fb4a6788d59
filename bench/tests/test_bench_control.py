"""``correct`` fails where it should: the control in the program's place,
and faults planted in the timed path, at a toy size on the host CPU (the
chip check is skipped), each through ``run_cell.measure``."""
import numpy as np
import pytest


def test_serve_control_fails_and_program_passes(toy):
    toy_cell, run_toy = toy
    program = run_toy(toy_cell("serve.ar5_synth"))
    assert program["correct"], program["checks"]
    control = run_toy(toy_cell("serve.ar5_synth"), control=True)
    assert not control["correct"]
    rel_l2 = control["checks"]["rel_l2"]
    assert rel_l2["value"] > rel_l2["limit"]
    assert program["checks"]["rel_l2"]["value"] < rel_l2["limit"]


@pytest.mark.parametrize("workload", ["search.ar5_synth",
                                      "search.heavy4_synth_faults"])
def test_search_control_fails_and_program_passes(workload, toy):
    toy_cell, run_toy = toy
    program = run_toy(toy_cell(workload))
    assert program["correct"], program["checks"]
    control = run_toy(toy_cell(workload), control=True)
    assert not control["correct"]
    gap = control["checks"]["obj_err_over_tol"]
    assert gap["value"] > gap["limit"]


def _serve_fault(monkeypatch, fault):
    from repro.runtime import PuzzleRuntime
    from repro.runtime.engine import Engine
    from repro.zoo import ExecutableMobileModel

    if fault == "answer_altered":
        execute = Engine.execute

        def altered(self, key, inputs=None):
            return execute(self, key, inputs) * 1.25
        monkeypatch.setattr(Engine, "execute", altered)
    elif fault == "half_left_out":
        infer = PuzzleRuntime.infer

        def half(self, networks, group=0):
            return infer(self, list(networks)[:max(1, len(networks) // 2)],
                         group)
        monkeypatch.setattr(PuzzleRuntime, "infer", half)
    elif fault == "state_unchanged":
        build = ExecutableMobileModel.build_subgraph_fn

        def unchanged(self, layer_ids, dtype="fp32"):
            fn, example = build(self, layer_ids, dtype)
            return (lambda *args: args[0]), example
        monkeypatch.setattr(ExecutableMobileModel, "build_subgraph_fn",
                            unchanged)


@pytest.mark.parametrize("fault", ["answer_altered", "half_left_out",
                                   "state_unchanged"])
def test_serve_fault_is_not_correct(fault, monkeypatch, toy):
    toy_cell, run_toy = toy
    _serve_fault(monkeypatch, fault)
    result = run_toy(toy_cell("serve.ar5_synth"))
    assert not result["correct"]


def _search_fault(monkeypatch, fault):
    from repro.core import batchsim_compiled

    run = batchsim_compiled.run_batch_compiled
    if fault == "answer_altered":
        def altered(lanes, groups, processors):
            out = run(lanes, groups, processors)
            if out is not None:
                out.last_finish = out.last_finish * (1.0 + 1e-6)
            return out
        monkeypatch.setattr(batchsim_compiled, "run_batch_compiled", altered)
    elif fault == "half_left_out":
        def half(lanes, groups, processors):
            lanes = list(lanes)
            h = max(1, len(lanes) // 2)
            out = run(lanes[:h] + lanes[:len(lanes) - h], groups, processors)
            if out is not None:
                out.lanes = lanes
            return out
        monkeypatch.setattr(batchsim_compiled, "run_batch_compiled", half)
    elif fault == "state_unchanged":
        advance = batchsim_compiled.advance_fn()

        def stuck(flags, tab):
            out = advance(flags, tab)
            return out[:-1] + (tab["itercap"],)
        monkeypatch.setattr(batchsim_compiled, "advance_fn", lambda: stuck)


@pytest.mark.parametrize("fault", ["answer_altered", "half_left_out",
                                   "state_unchanged"])
def test_search_fault_is_not_correct(fault, monkeypatch, toy):
    toy_cell, run_toy = toy
    _search_fault(monkeypatch, fault)
    result = run_toy(toy_cell("search.ar5_synth"))
    assert not result["correct"]
    assert np.isfinite(result["checks"]["fallbacks"]["value"])
