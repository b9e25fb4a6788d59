"""The ``yolov8`` family and the serving load metric, at a toy size on the
host CPU: ``serve.yolo2_pub``'s float8 control and faults planted in one of
FastSAM-s' sinks each make ``correct`` false through ``run_cell.measure``;
the family's work counts and comparison; ``load_s.serve`` read from the
program's counters."""
import math
from collections import Counter

import numpy as np
import pytest

import harness
import yolov8

CELL = "serve.yolo2_pub"


def _fault(monkeypatch, where):
    """FastSAM-s' layers at ``where`` (a module path prefix) return their
    output times 1.25; every other layer, and yolov8n, is left alone."""
    from repro.zoo.yolov8 import YoloV8

    apply = YoloV8.apply_layer

    def altered(self, lid, inputs, dt):
        out = apply(self, lid, inputs, dt)
        hit = self.name == "fastsam_s" and \
            self.ops[lid].path.startswith(where)
        return out * 1.25 if hit else out
    monkeypatch.setattr(YoloV8, "apply_layer", altered)


def test_control_fails(toy):
    toy_cell, run_toy = toy
    result = run_toy(toy_cell(CELL), control=True)
    assert not result["correct"]
    rel_l2 = result["checks"]["rel_l2"]
    assert rel_l2["value"] > rel_l2["limit"]


@pytest.mark.parametrize("where", ["model.22.cv3.", "model.22.proto.cv3"],
                         ids=["classes", "prototypes"])
def test_fault_in_one_fastsam_sink_fails(where, monkeypatch, toy):
    toy_cell, run_toy = toy
    _fault(monkeypatch, where)
    result = run_toy(toy_cell(CELL))
    assert not result["correct"]
    rel_l2 = result["checks"]["rel_l2"]
    assert rel_l2["value"] > rel_l2["limit"]
    assert result["checks"]["failed_requests"]["value"] == 0


def test_traced_toy_run_reads_load_and_serving_spans(toy, tmp_path,
                                                     monkeypatch):
    import run_cell

    monkeypatch.setattr(run_cell, "ROOT", tmp_path)
    toy_cell, run_toy = toy
    cell = toy_cell(CELL)
    cell.trace = True
    result = run_toy(cell)
    assert result["correct"], result["checks"]
    for name in ("load_s.serve", "dispatch_us.serve", "stage_us.serve"):
        value = result["metrics"][name]["value"]
        assert math.isfinite(value) and value > 0.0, name


def test_load_s_reads_the_load_span(monkeypatch):
    from repro.runtime import engine

    read = harness.metric_module("load_s.serve").read
    serve = {"kind": "serve"}
    monkeypatch.setattr(engine, "totals", Counter())
    assert read(serve) is None
    engine.totals.update({"puzzle.serve.load.ns": 6e9,
                          "puzzle.serve.load.n": 4})
    assert read(serve) == pytest.approx(1.5)
    assert read({"kind": "search"}) is None
    monkeypatch.delattr(engine, "totals")
    assert read(serve) is None


NETS = {"yolov8n": dict(scale="n", nc=80, task="detect", spatial=640),
        "fastsam_s": dict(scale="s", nc=1, task="segment", spatial=640)}


@pytest.mark.parametrize("name", sorted(NETS))
def test_subgraph_work(name):
    net = NETS[name]
    n = len(yolov8.layers(net))
    flops, nbytes = yolov8.subgraph_work(net, range(n), "int8")
    assert flops == 2 * yolov8.executable_macs(net)
    # at least the input, every weight and every sink, in bfloat16
    assert nbytes > 2 * (640 * 640 * 3 + yolov8.parameter_count(net))
    # cut in two after a split: the same operations; the split's half is
    # written, and read again beside cv1's whole output
    (split,) = [i for i, lay in enumerate(yolov8.layers(net))
                if lay["path"] == "model.2.split"]
    f1, b1 = yolov8.subgraph_work(net, range(0, split + 1), "int8")
    f2, b2 = yolov8.subgraph_work(net, range(split + 1, n), "int8")
    assert f1 + f2 == flops
    assert b1 + b2 > nbytes


def test_first_conv_work_by_hand():
    net = NETS["yolov8n"]
    flops, nbytes = yolov8.subgraph_work(net, [0], "fp32")
    assert flops == 2 * 320 * 320 * 16 * 9 * 3
    assert nbytes == 4 * (640 * 640 * 3 + 9 * 3 * 16 + 16 + 320 * 320 * 16)
    # a returned concat reads its pieces and writes itself; a split half
    lays = yolov8.layers(net)
    (split,) = [i for i, lay in enumerate(lays) if lay["path"] == "model.2.split"]
    _, nbytes = yolov8.subgraph_work(net, [split], "fp32")
    assert nbytes == 4 * 2 * (160 * 160 * 16)


def test_components_and_comparison():
    family = harness.family_module({"family": "yolov8"})
    rng = np.random.default_rng(0)
    det = rng.standard_normal((1, 84, 37)).astype(np.float32)
    proto = rng.standard_normal((1, 16, 16, 32)).astype(np.float32)
    parts = yolov8.components([det, proto])
    assert sorted(parts) == ["boxes", "classes", "masks", "prototypes"]
    assert parts["classes"].shape == (1, 84, 1)
    grid, stride = yolov8.anchor_grid(64)
    np.testing.assert_allclose(parts["boxes"][..., :2],
                               det[..., :2] - grid * stride, rtol=1e-6)
    assert family.worst_rel_l2("fastsam_s", [det, proto], [det, proto]) == 0
    bad = proto * 1.1
    assert family.worst_rel_l2("fastsam_s", [det, bad], [det, proto]) == \
        pytest.approx(0.1)
    assert family.worst_rel_l2("fastsam_s", [det], [det, proto]) == math.inf
    detect = rng.standard_normal((1, 84, 84)).astype(np.float32)
    parts = yolov8.components([detect])
    assert sorted(parts) == ["boxes", "classes"]
    assert parts["classes"].shape == (1, 84, 80)
