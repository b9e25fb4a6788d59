"""The ``yolov8`` network family: the published YOLOv8n and FastSAM-s.

A configuration's networks map to ``{"spatial": s, "scale": "n" | "s",
"nc": classes, "task": "detect" | "segment"}``; each is the program's
``repro.zoo.yolov8.YoloV8``, given the weights that ``bench/yolov8.py``
(the independent restatement) draws from the configuration's model seed
and the input it draws from ``--seed``. The functions are those listed in
``bench/families/convnet.py``.
"""
from __future__ import annotations

import functools
import json
from typing import Any, Dict, List, Sequence, Tuple

import numpy as np

import harness
import yolov8

#: The CPU tests' size of every network: the published widths at 64 x 64.
TOY_SPATIAL = 64


def _net(shape: dict) -> dict:
    return {k: shape[k] for k in ("spatial", "scale", "nc", "task")}


@functools.lru_cache(maxsize=None)
def _weights(net: str, seed: int):
    """The weights of a network entry (as JSON) and model seed, drawn once
    per process: set-up and the reference both use them."""
    return yolov8.make_weights(json.loads(net), seed)


def weights(shape: dict, seed: int):
    return _weights(json.dumps(_net(shape), sort_keys=True), seed)


def executables(config: dict, seed: int) -> Dict[str, Any]:
    from repro.zoo.yolov8 import YoloV8

    zoo = {}
    for name, shape in config["networks"].items():
        net = _net(shape)
        zoo[name] = YoloV8(
            name, net["scale"], net["nc"], net["task"], net["spatial"],
            weights=weights(net, config["model_seed"]),
            x=yolov8.make_input(net["spatial"],
                                harness.stable_seed(seed, name)))
    return zoo


def graphs(config: dict) -> Dict[str, Any]:
    from repro.zoo.yolov8 import layer_table, make_graph

    return {name: make_graph(name, layer_table(
        s["scale"], s["nc"], s["task"], s["spatial"]), s["spatial"])
        for name, s in config["networks"].items()}


def reference(config: dict, seed: int, mode: str = "f32"
              ) -> Dict[str, List[Any]]:
    out = {}
    for name, shape in config["networks"].items():
        net = _net(shape)
        x = yolov8.make_input(net["spatial"], harness.stable_seed(seed, name))
        out[name] = yolov8.reference_forward(
            net, weights(net, config["model_seed"]), x, mode=mode)
    return out


def worst_rel_l2(name: str, outs: Sequence[Any], refs: Sequence[Any]
                 ) -> float:
    """The worst relative L2 gap over the components of a network's sinks
    (``yolov8.components``: boxes, classes, and for ``segment`` masks and
    prototypes), each compared on its own."""
    if [np.shape(o) for o in outs] != [np.shape(r) for r in refs]:
        return float("inf")
    got, want = yolov8.components(outs), yolov8.components(refs)
    return max(yolov8.rel_l2(got[k], want[k]) for k in want)


def work(name: str, layer_ids: Sequence[int], shape: dict, dtype: str
         ) -> Tuple[float, float]:
    return yolov8.subgraph_work(_net(shape), layer_ids, dtype)


def macs(name: str, shape: dict) -> int:
    return yolov8.executable_macs(_net(shape))


def toy(config: dict) -> dict:
    for shape in config["networks"].values():
        shape["spatial"] = TOY_SPATIAL
    return config
