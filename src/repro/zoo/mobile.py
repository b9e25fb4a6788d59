"""The paper's nine mobile networks as schedulable layer DAGs (Table 6).

Two faces per model:

* a **cost graph** (:class:`~repro.core.graph.ModelGraph`) with the paper's
  MAC/parameter totals distributed over a plausible conv-net layer DAG
  (backbone chain + skip/branch merges) — what the Static Analyzer
  schedules when reproducing the paper's experiments with the
  :class:`TableBackend`;
* an **executable reduction** (:class:`ExecutableMobileModel`) — a real JAX
  conv network with the same DAG topology, small enough to run on this
  host's CPU in milliseconds, used by the :class:`JaxExecBackend` for
  literal device-in-the-loop profiling and by the Runtime's engines.
"""
from __future__ import annotations

from typing import Callable, Dict, List, Sequence, Tuple

import numpy as np

from ..core.graph import Edge, Layer, ModelGraph
from .profiles import MODEL_NAMES, MODEL_SPECS

_DTYPE_BYTES = {"fp32": 4, "fp16": 2, "int8": 1}


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
# Executable reductions: real JAX conv nets with the same topology.
# ---------------------------------------------------------------------------


class ExecutableMobileModel:
    """A small real conv network matching a cost graph's DAG topology.

    Layers operate on NHWC tensors of fixed spatial size; `add_merge`
    layers consume (chain_input, skip_input). `build_subgraph_fn` returns a
    jit-able function computing the subgraph outputs from its boundary
    inputs — this is what the device-in-the-loop profiler times and what
    the Runtime engines execute.
    """

    def __init__(self, name: str, channels: int = 8, spatial: int = 16, seed: int = 0):
        import jax
        import jax.numpy as jnp

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
        self._jnp = jnp
        self._jax = jax

    # -- layer semantics -------------------------------------------------------
    def _apply_layer(self, lid: int, inputs: Sequence, dtype):
        jnp = self._jnp
        import jax

        layer = self.graph.layers[lid]
        x = inputs[0]
        if layer.op_type == "add_merge":
            out = x
            for other in inputs[1:]:
                out = out + other
            return jax.nn.relu(out)
        w = jnp.asarray(self._weights[lid], dtype=dtype)
        y = jax.lax.conv_general_dilated(
            x, w, window_strides=(1, 1), padding="SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
        )
        return jax.nn.relu(y)

    def _np_dtype(self, dtype: str):
        jnp = self._jnp
        return {"fp32": jnp.float32, "fp16": jnp.bfloat16, "int8": jnp.bfloat16}[dtype]

    def input_shape(self) -> Tuple[int, int, int, int]:
        return (1, self.spatial, self.spatial, self.channels)

    def model_input(self) -> np.ndarray:
        """The network's input tensor (float32, seeded)."""
        return self._input

    def boundary(
        self, layer_ids: Sequence[int]
    ) -> Tuple[List[Tuple[int, int]], List[int]]:
        """A subgraph's interface, in :meth:`build_subgraph_fn` order.

        Returns its external inputs as ``(src_layer, dst_layer)`` pairs, one
        per argument (``src_layer == -1`` is the model input), and the
        layers whose outputs it returns: every layer with an edge leaving
        the subgraph, and the network's sinks.
        """
        ids = sorted(layer_ids)
        id_set = set(ids)
        ext_inputs: List[Tuple[int, int]] = []
        for lid in ids:
            preds = [e.src for e in self.graph.in_edges[lid]]
            if not preds:
                ext_inputs.append((-1, lid))
            for p in preds:
                if p not in id_set:
                    ext_inputs.append((p, lid))
        out_ids = [lid for lid in ids
                   if not self.graph.out_edges[lid]
                   or any(e.dst not in id_set
                          for e in self.graph.out_edges[lid])]
        return ext_inputs, out_ids

    def build_subgraph_fn(
        self, layer_ids: Sequence[int], dtype: str = "fp32"
    ) -> Tuple[Callable, Tuple]:
        """(fn, example_args) computing this subgraph from boundary inputs.

        ``fn`` returns the outputs of :meth:`boundary`'s output layers (one
        array, or a tuple of several). The example of a model-input
        argument is :meth:`model_input`; the others are filled with 0.1.
        """
        jnp = self._jnp
        dt = self._np_dtype(dtype)
        ids = sorted(layer_ids)
        id_set = set(ids)
        ext_inputs, out_ids = self.boundary(ids)

        def fn(*args):
            env: Dict[int, object] = {}
            ext = {pair: a for pair, a in zip(ext_inputs, args)}
            for lid in ids:
                preds = [e.src for e in self.graph.in_edges[lid]]
                ins = []
                if not preds:
                    ins.append(ext[(-1, lid)])
                for p in preds:
                    ins.append(env[p] if p in id_set else ext[(p, lid)])
                env[lid] = self._apply_layer(lid, ins, dt)
            outs = [env[lid] for lid in out_ids]
            return outs[0] if len(outs) == 1 else tuple(outs)

        shape = self.input_shape()
        args = tuple(
            jnp.asarray(self._input, dtype=dt) if src < 0
            else jnp.zeros(shape, dtype=dt) + 0.1
            for src, _ in ext_inputs
        )
        return fn, args

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
