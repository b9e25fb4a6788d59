"""A network family that the serving driver has never seen, served through
``run_cell.measure`` with no edit to any driver: the family is defined in
this module, its cell and configuration are new entries, and the driver
finds the family by the configuration's ``"family"`` key.

The network has two branches and two sinks. Its schedules cut it across two
processors, so that the cut edges carry tensors of two different shapes
(or a sink leaves its subgraph beside a cut tensor). The program has to pass
against the family's own float32 reference; the family's float8 control
and a fault planted in either sink alone have to fail ``rel_l2``.
"""
import ast
import json
import sys
import time

import numpy as np
import pytest

import harness

FAMILY = "twosink_test"
NET = "twosink"
C_IN, C_MID, C_B = 4, 8, 16
#: The cost graph's MACs per layer (what the schedule is planned on), as
#: the zoo's cost graphs carry Table 6 MACs apart from the executable size.
PLAN_MACS = (80e6, 0.0, 80e6, 40e6)
#: layer -> (op, the layer it reads, -1 for the network input)
LAYERS = {0: ("stem", -1), 1: ("pool", 0), 2: ("head_a", 0),
          3: ("head_b", 1)}
EDGES = [(src, lid) for lid, (_, src) in LAYERS.items() if src >= 0]
SINKS = [2, 3]
CONFIG = {
    "name": "twosink_toy",
    "family": FAMILY,
    "groups": [[NET]],
    "networks": {NET: {"spatial": 64}},
    "model_seed": 3,
    "plan": {"pop_size": 4, "generations": 1, "seed": 1},
    "alpha_knee": 1.0,
    "limits": {"rel_l2": 0.01},
}
CELL = "serve.twosink_toy"
SEED = 2**31 + 11


# -- the family ------------------------------------------------------------
def _shapes(spatial):
    """Each layer's output shape, NHWC."""
    half = spatial // 2
    return {0: (1, spatial, spatial, C_MID), 1: (1, half, half, C_MID),
            2: (1, spatial, spatial, C_IN), 3: (1, half, half, C_B)}


def _weights(seed):
    rng = np.random.default_rng(seed)
    return {lid: (rng.standard_normal((ci, co)) / np.sqrt(ci))
            .astype(np.float32)
            for lid, (ci, co) in {0: (C_IN, C_MID), 2: (C_MID, C_IN),
                                  3: (C_MID, C_B)}.items()}


def _input(spatial, seed):
    return np.random.default_rng(harness.stable_seed(seed, NET)) \
        .standard_normal((1, spatial, spatial, C_IN), dtype=np.float32)


class TwoSinkNet:
    """The program side: a subgraph of the network as a ``jax.numpy``
    function of its boundary inputs, as ``PuzzleRuntime`` serves it."""

    def __init__(self, graph, spatial, weights, x):
        self.graph = graph
        self.shapes = _shapes(spatial)
        self.weights = weights
        self.x = x

    def model_input(self):
        return self.x

    def boundary(self, layer_ids):
        ids = sorted(layer_ids)
        inputs = [(LAYERS[lid][1], lid) for lid in ids
                  if LAYERS[lid][1] not in ids]
        outs = [lid for lid in ids if not self.graph.out_edges[lid]
                or any(e.dst not in ids for e in self.graph.out_edges[lid])]
        return inputs, outs

    def _apply(self, lid, x, dt):
        import jax.numpy as jnp

        op = LAYERS[lid][0]
        if op == "pool":
            n, h, w, c = x.shape
            return x.reshape(n, h // 2, 2, w // 2, 2, c).mean(axis=(2, 4))
        y = jnp.einsum("nhwc,cd->nhwd", x, jnp.asarray(self.weights[lid], dt))
        return y if op == "head_b" else jnp.maximum(y, 0.0)

    def build_subgraph_fn(self, layer_ids, dtype="fp32"):
        import jax.numpy as jnp

        dt = {"fp32": jnp.float32}.get(dtype, jnp.bfloat16)
        ids = sorted(layer_ids)
        inputs, outs = self.boundary(ids)

        def fn(*args):
            env = {src: a for (src, _), a in zip(inputs, args)}
            for lid in ids:
                env[lid] = self._apply(lid, env[LAYERS[lid][1]], dt)
            vals = [env[lid] for lid in outs]
            return vals[0] if len(vals) == 1 else tuple(vals)

        example = tuple(
            jnp.asarray(self.x, dt) if src < 0
            else jnp.full(self.shapes[src], 0.1, dt)
            for src, _ in inputs)
        return fn, example


def graphs(config):
    from repro.core.graph import Edge, Layer, ModelGraph

    shapes = _shapes(config["networks"][NET]["spatial"])
    layers = [Layer(index=lid, name=f"{NET}.{op}", op_type=op,
                    macs=PLAN_MACS[lid], param_bytes=4096,
                    out_bytes=4 * int(np.prod(shapes[lid])))
              for lid, (op, _) in LAYERS.items()]
    edges = [Edge(index=k, src=src, dst=dst, bytes_=layers[src].out_bytes)
             for k, (src, dst) in enumerate(EDGES)]
    return {NET: ModelGraph(NET, layers, edges)}


def executables(config, seed):
    spatial = config["networks"][NET]["spatial"]
    return {NET: TwoSinkNet(graphs(config)[NET], spatial,
                            _weights(config["model_seed"]),
                            _input(spatial, seed))}


def reference(config, seed, mode="f32"):
    """Every sink in plain numpy float32; ``fp8`` rounds each matmul's
    operands to float8 e4m3 first."""
    import ml_dtypes

    def mm(a, w):
        if mode == "fp8":
            a, w = (v.astype(ml_dtypes.float8_e4m3fn).astype(np.float32)
                    for v in (a, w))
        return np.einsum("nhwc,cd->nhwd", a, w)

    w = _weights(config["model_seed"])
    x = _input(config["networks"][NET]["spatial"], seed)
    h0 = np.maximum(mm(x, w[0]), 0.0)
    n, h, wd, c = h0.shape
    h1 = h0.reshape(n, h // 2, 2, wd // 2, 2, c).mean(axis=(2, 4))
    return {NET: [np.maximum(mm(h0, w[2]), 0.0), mm(h1, w[3])]}


def worst_rel_l2(name, outs, refs):
    assert len(outs) == len(refs) == len(SINKS)
    return max(float(np.linalg.norm(np.float64(o) - np.float64(r))
                     / np.linalg.norm(np.float64(r)))
               for o, r in zip(outs, refs))


def _layer_macs(lid, spatial):
    shapes = _shapes(spatial)
    c_in = {0: C_IN, 2: C_MID, 3: C_MID}.get(lid)
    return 0 if c_in is None else int(np.prod(shapes[lid])) * c_in


def work(name, layer_ids, shape, dtype):
    s = shape["spatial"]
    shapes = _shapes(s)
    size = 4 if dtype == "fp32" else 2
    flops = sum(2.0 * _layer_macs(lid, s) for lid in layer_ids)
    nbytes = sum(size * (np.prod(shapes[lid]) + np.prod(
        shapes[LAYERS[lid][1]] if LAYERS[lid][1] >= 0
        else (1, s, s, C_IN))) for lid in layer_ids)
    return flops, float(nbytes)


def macs(name, shape):
    return sum(_layer_macs(lid, shape["spatial"]) for lid in LAYERS)


def toy(config):
    config["networks"][NET]["spatial"] = 8
    return config


# -- serving it ------------------------------------------------------------
#: (cut bits over EDGES, preferred processor per layer, subgraphs)
SCHEDULES = {
    # {0, 1} | {2} | {3}: the cut edges carry (8, 8, 8) and (4, 4, 8)
    "two_cut_shapes": ([0, 1, 1], [0, 0, 1, 1], 3),
    # {0, 2} | {1, 3}: sink 2 leaves its subgraph beside the cut tensor
    "sink_beside_cut": ([1, 0, 0], [0, 1, 1, 1], 2),
}


@pytest.fixture
def served(monkeypatch, tmp_path):
    """Serve the two-sink cell on a schedule: ``serve(schedule,
    control=False)`` returns the result and the placement line."""
    import run_cell
    from repro.core import Solution

    me = sys.modules[__name__]
    load = harness.family_module
    monkeypatch.setattr(
        harness, "family_module",
        lambda config: me if config.get("family") == FAMILY
        else load(config))
    cfg_file = tmp_path / "twosink_toy.json"
    cfg_file.write_text(json.dumps(CONFIG))
    raw = harness.Spec.load().raw
    raw = dict(raw, configs=raw["configs"] + [
        {"name": CONFIG["name"], "source": "test", "file": str(cfg_file),
         "reduced": [], "why": "test"}],
        workloads=raw["workloads"] + [
        {"name": CELL, "config": CONFIG["name"],
         "traffic": "serve_periodic_p80", "chips": 1, "why": "test"}],
        end_to_end=[dict(m, workloads=m["workloads"] + [CELL])
                    if m["name"] == "makespan_p95_ms" else m
                    for m in raw["end_to_end"]])
    spec = harness.Spec(raw, {c["name"]: c for c in raw["configs"]},
                        {w["name"]: w for w in raw["workloads"]})

    def serve(schedule, control=False):
        cuts, mapping, _ = SCHEDULES[schedule]
        lines = []
        cell = harness.resolve(spec, CELL, SEED, 1.0, False,
                               emit=lines.append)
        driver = harness.driver_module(cell)
        driver.toy(cell)
        plan = driver.Driver.plan

        def cut_plan(self):
            analyzer, _ = plan(self)
            return analyzer, Solution([list(cuts)], [list(mapping)], [0],
                                      [0], [0])
        monkeypatch.setattr(driver.Driver, "plan", cut_plan)
        monkeypatch.setattr(harness, "driver_module", lambda c: driver)
        result = run_cell.measure(
            spec, cell, harness.device_info(),
            {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9},
            time.perf_counter(), control=control)
        (placement,) = [ast.literal_eval(s[len("placement "):])
                        for s in lines if s.startswith("placement ")]
        return result, placement

    return serve


def test_cut_edges_carry_two_shapes():
    cfg = toy(json.loads(json.dumps(CONFIG)))
    net = executables(cfg, SEED)[NET]
    assert net.graph.sinks() == SINKS
    assert EDGES == [(0, 1), (0, 2), (1, 3)]
    assert net.boundary([2])[0] == [(0, 2)]
    assert net.boundary([3])[0] == [(1, 3)]
    assert net.shapes[0] != net.shapes[1]
    assert net.boundary([0, 2]) == ([(-1, 0)], [0, 2])


@pytest.mark.parametrize("schedule", sorted(SCHEDULES))
def test_new_family_served_across_two_processors(schedule, served):
    result, placement = served(schedule)
    assert result["correct"], result["checks"]
    assert result["attempted"] > 0 and result["failed"] == 0
    assert set(result["metrics"]) == {"makespan_p95_ms", "setup_s"}
    (subgraphs,) = placement
    assert {pid for _, _, pid, _, _ in subgraphs} == {0, 1}
    assert len(subgraphs) == SCHEDULES[schedule][2]
    assert result["checks"]["rel_l2"]["value"] < 1e-5
    assert result["checks"]["networks_unchecked"]["value"] == 0


def test_family_control_fails(served):
    result, _ = served("two_cut_shapes", control=True)
    assert not result["correct"]
    rel_l2 = result["checks"]["rel_l2"]
    assert rel_l2["value"] > rel_l2["limit"]


@pytest.mark.parametrize("sink", SINKS)
def test_fault_in_one_sink_fails(sink, served, monkeypatch):
    apply = TwoSinkNet._apply

    def altered(self, lid, x, dt):
        out = apply(self, lid, x, dt)
        return out * 1.25 if lid == sink else out
    monkeypatch.setattr(TwoSinkNet, "_apply", altered)
    result, _ = served("two_cut_shapes")
    assert not result["correct"]
    rel_l2 = result["checks"]["rel_l2"]
    assert rel_l2["value"] > rel_l2["limit"]
