"""Work counts, peaks and the reference's structure, on the host CPU."""
import json

import numpy as np
import pytest

import convnet
import harness
import peaks

CONFIGS = sorted(harness.Spec.load().configs)


def networks():
    spec = harness.Spec.load()
    for name in CONFIGS:
        cfg = json.loads((harness.ROOT / spec.configs[name]["file"])
                         .read_text())
        for net, shape in cfg["networks"].items():
            yield name, net, shape["spatial"], shape["channels"]


@pytest.mark.parametrize("config,net,spatial,channels", list(networks()))
def test_executable_macs_match_table6(config, net, spatial, channels):
    from repro.zoo import ExecutableMobileModel

    macs = convnet.executable_macs(net, spatial, channels)
    convs = convnet.conv_layers(net)
    assert macs == spatial ** 2 * 9 * channels ** 2 * convs
    assert abs(macs / convnet.TABLE6[net]["macs"] - 1.0) <= 0.15
    assert spatial == convnet.TABLE6[net]["input"]
    # the program's graph has exactly these convolutions
    model = ExecutableMobileModel(net, channels=1, spatial=1)
    assert sum(layer.op_type != "add_merge"
               for layer in model.graph.layers) == convs


def test_heavy4_conv_layers():
    assert [convnet.conv_layers(n) for n in
            ("fastsam_s", "mosaic", "fast_scnn", "tcmonodepth")] == [
                23, 23, 17, 18]


@pytest.mark.parametrize("net", sorted(convnet.TABLE6))
def test_structure_matches_program(net):
    from repro.zoo import ExecutableMobileModel
    from repro.zoo.profiles import MODEL_SPECS

    assert convnet.TABLE6[net]["macs"] == MODEL_SPECS[net]["macs"]
    g = ExecutableMobileModel(net, channels=1, spatial=1).graph
    preds = [sorted(e.src for e in g.in_edges[i]) for i in range(g.num_layers)]
    assert preds == [sorted(p) for p in convnet.predecessors(g.num_layers)]
    merges = [i for i, layer in enumerate(g.layers)
              if layer.op_type == "add_merge"]
    assert merges == convnet.merge_layers(g.num_layers)


def test_weights_follow_the_programs_recipe():
    from repro.zoo import ExecutableMobileModel

    model = ExecutableMobileModel("hand_det", channels=3, spatial=8, seed=5)
    ours = convnet.make_weights("hand_det", 8, 3, seed=5)
    assert sorted(ours) == sorted(model._weights)
    for i, w in ours.items():
        np.testing.assert_allclose(w, model._weights[i], rtol=1e-6,
                                   atol=1e-7)


def test_reference_matches_program_reference():
    from repro.zoo import ExecutableMobileModel

    model = ExecutableMobileModel("face_det", channels=3, spatial=12, seed=2)
    w = convnet.make_weights("face_det", 12, 3, seed=2)
    ref = convnet.reference_forward("face_det", w, model.model_input())
    assert convnet.rel_l2(ref, model.reference_forward()) < 1e-5
    ctl = convnet.reference_forward("face_det", w, model.model_input(),
                                    mode="fp8")
    assert convnet.rel_l2(ctl, ref) > 0.03


def test_subgraph_work_whole_network():
    for net in ("yolov8n", "fastsam_s"):
        n = int(convnet.TABLE6[net]["layers"])
        flops, nbytes = convnet.subgraph_work(net, range(n), 64, 8, "int8")
        assert flops == 2 * convnet.executable_macs(net, 64, 8)
        act = 64 * 64 * 8 * 2
        convs = convnet.conv_layers(net)
        merges = n - convs
        assert nbytes == convs * (2 * act + 9 * 64 * 2) + merges * act
    # split in two: a returned merge writes its sum
    f1, b1 = convnet.subgraph_work("yolov8n", range(0, 5), 16, 4, "fp32")
    f2, b2 = convnet.subgraph_work("yolov8n", range(5, 24), 16, 4, "fp32")
    whole_f, whole_b = convnet.subgraph_work("yolov8n", range(24), 16, 4,
                                             "fp32")
    assert f1 + f2 == whole_f
    assert b1 + b2 == whole_b + 16 * 16 * 4 * 4


def test_peaks_known_and_unknown():
    assert peaks.peaks("TPU v5 lite")["bf16_flops"] == 197e12
    assert peaks.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(ValueError):
        peaks.peaks("TPU v9 imaginary")
    with pytest.raises(ValueError):
        peaks.peaks("cpu")
