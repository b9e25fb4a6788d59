"""The paper's nine mobile networks as schedulable layer DAGs (Table 6).

Two faces per model:

* a **cost graph** (:class:`~repro.core.graph.ModelGraph`) with the paper's
  MAC/parameter totals distributed over a plausible conv-net layer DAG
  (backbone chain + skip/branch merges) — what the Static Analyzer
  schedules when reproducing the paper's experiments with the
  :class:`TableBackend`;
* a **synthetic executable** (:class:`ExecutableMobileModel`) — a JAX
  conv stack with the same DAG topology at one width and one resolution,
  used by the :class:`JaxExecBackend` for device-in-the-loop profiling and
  by the Runtime's engines. Its size is the caller's: a few channels at
  8-16 px in the tests, Table 6's input resolutions in the benchmark.

The published architectures of yolov8n and fastsam_s, with their real
per-layer shapes, are built by :mod:`repro.zoo.yolov8`.
"""
from __future__ import annotations

from typing import Dict, List, Sequence, Tuple

import numpy as np

from ..core.graph import Edge, Layer, ModelGraph
from .executable import DagExecutable, conv2d
from .profiles import MODEL_NAMES, MODEL_SPECS


def _mac_profile(n: int) -> np.ndarray:
    """Plausible per-layer MAC share: ramps up, peaks mid-network, tails off."""
    x = np.linspace(0.0, 1.0, n)
    w = 0.35 + np.sin(np.pi * x) ** 2 + 0.25 * x
    return w / w.sum()


def _activation_bytes(n: int, input_bytes: int) -> List[int]:
    """Activation sizes: decay from input size as resolution drops."""
    sizes = []
    for i in range(n):
        decay = 0.5 ** (3.0 * i / max(n - 1, 1))  # ~8x total reduction
        sizes.append(max(int(input_bytes * decay), 4096))
    return sizes


def _skip_positions(n: int) -> List[int]:
    """Indices whose layer merges a skip connection (FPN/residual style)."""
    if n < 8:
        return []
    return [i for i in range(4, n - 1, 5)]


def make_cost_graph(name: str) -> ModelGraph:
    """Build the schedulable cost DAG calibrated to Table 6 totals."""
    spec = MODEL_SPECS[name]
    n = int(spec["layers"])
    h, w = spec["input"][1], spec["input"][2]
    input_bytes = int(h * w * 3 * 4)
    mac_share = _mac_profile(n)
    act = _activation_bytes(n, input_bytes)
    skips = set(_skip_positions(n))
    layers: List[Layer] = []
    param_share = mac_share / mac_share.sum()
    for i in range(n):
        op = "add_merge" if i in skips else ("conv" if i % 3 else "dwconv")
        attrs: Tuple[Tuple[str, object], ...] = (("model", name),)
        if i == 0:
            attrs = attrs + (("input_bytes", input_bytes),)
        layers.append(
            Layer(
                index=i,
                name=f"{name}.{i}",
                op_type=op,
                macs=float(spec["macs"] * mac_share[i]),
                param_bytes=int(spec["params"] * 4 * param_share[i]),
                out_bytes=act[i],
                attrs=attrs,
            )
        )
    edges: List[Edge] = []
    k = 0
    for i in range(n - 1):
        edges.append(Edge(index=k, src=i, dst=i + 1, bytes_=act[i]))
        k += 1
    for s in sorted(skips):
        src = s - 3
        if src >= 0:
            edges.append(Edge(index=k, src=src, dst=s, bytes_=act[src]))
            k += 1
    return ModelGraph(name, layers, edges)


def all_cost_graphs() -> Dict[str, ModelGraph]:
    return {name: make_cost_graph(name) for name in MODEL_NAMES}


# ---------------------------------------------------------------------------
# Synthetic executables: JAX conv stacks with the same topology.
# ---------------------------------------------------------------------------


class ExecutableMobileModel(DagExecutable):
    """A synthetic JAX conv network matching a cost graph's DAG topology.

    Every layer works on NHWC tensors of the input's shape: a 3x3 "SAME"
    convolution at one width with ReLU, or an ``add_merge`` (ReLU of the
    sum of its chain and skip inputs). It is the special case of
    :class:`DagExecutable` in which every layer has the input's shape.
    """

    def __init__(self, name: str, channels: int = 8, spatial: int = 16, seed: int = 0):
        import jax

        self.name = name
        self.graph = make_cost_graph(name)
        self.channels = channels
        self.spatial = spatial
        key = jax.random.PRNGKey(seed)
        # He-normal scale: activations stay O(1) through the whole conv
        # stack, so outputs compared against the reference are not ~0
        scale = (2.0 / (9 * channels)) ** 0.5
        self._weights: Dict[int, np.ndarray] = {}
        for layer in self.graph.layers:
            key, sub = jax.random.split(key)
            if layer.op_type in ("conv", "dwconv"):
                self._weights[layer.index] = np.asarray(
                    jax.random.normal(sub, (3, 3, channels, channels)) * scale,
                    dtype=np.float32,
                )
        self._input = np.asarray(
            jax.random.normal(key, self.input_shape()), dtype=np.float32)

    # -- layer semantics -------------------------------------------------------
    def apply_layer(self, lid: int, inputs: Sequence, dt):
        import jax
        import jax.numpy as jnp

        layer = self.graph.layers[lid]
        x = inputs[0]
        if layer.op_type == "add_merge":
            out = x
            for other in inputs[1:]:
                out = out + other
            return jax.nn.relu(out)
        w = jnp.asarray(self._weights[lid], dtype=dt)
        return jax.nn.relu(conv2d(x, w))

    def input_shape(self) -> Tuple[int, int, int, int]:
        return (1, self.spatial, self.spatial, self.channels)

    def model_input(self) -> np.ndarray:
        return self._input

    def output_shape(self, lid: int) -> Tuple[int, int, int, int]:
        return self.input_shape()

    def layer_weights(self, lid: int) -> Sequence[np.ndarray]:
        w = self._weights.get(lid)
        return () if w is None else (w,)

    def reference_forward(self) -> np.ndarray:
        """The whole network in plain float32 numpy on :meth:`model_input`.

        The reference for executed schedules: layer by layer in index
        (topological) order on the host, sharing nothing with the JAX
        subgraph functions but the weights. Returns the sink's output.
        """
        g = self.graph
        vals: Dict[int, np.ndarray] = {}
        for layer in g.layers:
            preds = [e.src for e in g.in_edges[layer.index]]
            if layer.op_type == "add_merge":
                out = vals[preds[0]]
                for p in preds[1:]:
                    out = out + vals[p]
            else:
                x = vals[preds[0]] if preds else self._input
                out = _conv3x3_same(x, self._weights[layer.index])
            vals[layer.index] = np.maximum(out, 0.0)
        (sink,) = [lid for lid in vals if not g.out_edges[lid]]
        return vals[sink]


def _conv3x3_same(x: np.ndarray, w: np.ndarray) -> np.ndarray:
    """NHWC x HWIO 3x3 cross-correlation, stride 1, zero "SAME" padding."""
    xp = np.pad(x, ((0, 0), (1, 1), (1, 1), (0, 0)))
    # (N, H, W, C, 3, 3): window (i, j) at (h, w) reads x[h+i-1, w+j-1]
    patches = np.lib.stride_tricks.sliding_window_view(xp, (3, 3),
                                                       axis=(1, 2))
    return np.tensordot(patches, w, axes=([4, 5, 3], [0, 1, 2]))


def executable_zoo(
    names: Sequence[str] = MODEL_NAMES, channels: int = 8, spatial: int = 16
) -> Dict[str, ExecutableMobileModel]:
    return {n: ExecutableMobileModel(n, channels=channels, spatial=spatial) for n in names}
