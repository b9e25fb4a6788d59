"""The ``convnet`` network family: the program zoo's synthetic conv stacks.

A configuration's networks map to ``{"spatial": s, "channels": C}``; each is
the program's ``ExecutableMobileModel`` under its Table 6 name, and
``bench/convnet.py`` is its independent restatement (work counts and the
float32 reference). This module is the adapter between the two that the
serving driver calls; it is the family of every configuration that names
none.

A family module defines:

* ``executables(config, seed)`` -- ``{name: executable}``, the program's
  objects that ``PuzzleRuntime`` serves, each with its input from ``seed``;
* ``graphs(config)`` -- ``{name: ModelGraph}`` the served schedule is
  planned on;
* ``reference(config, seed, mode)`` -- ``{name: [sink outputs]}`` in
  ``graph.sinks()`` order, from the restatement; ``mode="fp8"`` is the
  control of ``correct``;
* ``worst_rel_l2(name, outs, refs)`` -- the worst relative L2 gap over a
  network's sinks;
* ``work(name, layer_ids, shape, dtype)`` -- (operations, least HBM bytes)
  of one execute of a subgraph; ``macs(name, shape)`` -- a whole network's
  multiply-adds as executed;
* ``toy(config)`` -- every network at the size the CPU tests serve.
"""
from __future__ import annotations

from typing import Any, Dict, List, Sequence, Tuple

import convnet
import harness

#: The CPU tests' size of every network.
TOY_SHAPE = {"spatial": 16, "channels": 4}


def executables(config: dict, seed: int) -> Dict[str, Any]:
    from repro.zoo import ExecutableMobileModel

    zoo = {}
    for name, shape in config["networks"].items():
        s, c = shape["spatial"], shape["channels"]
        model = ExecutableMobileModel(name, channels=c, spatial=s,
                                      seed=config["model_seed"])
        # the weights are compiled into the served programs as constants,
        # so they stay fixed; the input varies with the seed
        model._input = convnet.make_input(s, c, harness.stable_seed(seed, name))
        zoo[name] = model
    return zoo


def graphs(config: dict) -> Dict[str, Any]:
    from repro.zoo.mobile import make_cost_graph

    return {name: make_cost_graph(name) for name in config["networks"]}


def reference(config: dict, seed: int, mode: str = "f32"
              ) -> Dict[str, List[Any]]:
    out = {}
    for name, shape in config["networks"].items():
        s, c = shape["spatial"], shape["channels"]
        w = convnet.make_weights(name, s, c, config["model_seed"])
        x = convnet.make_input(s, c, harness.stable_seed(seed, name))
        out[name] = [convnet.reference_forward(name, w, x, mode=mode)]
    return out


def worst_rel_l2(name: str, outs: Sequence[Any], refs: Sequence[Any]) -> float:
    (out,), (ref,) = outs, refs
    return convnet.rel_l2(out, ref)


def work(name: str, layer_ids: Sequence[int], shape: dict, dtype: str
         ) -> Tuple[float, float]:
    return convnet.subgraph_work(name, layer_ids, shape["spatial"],
                                 shape["channels"], dtype)


def macs(name: str, shape: dict) -> int:
    return convnet.executable_macs(name, shape["spatial"], shape["channels"])


def toy(config: dict) -> dict:
    for shape in config["networks"].values():
        shape.update(TOY_SHAPE)
    return config
