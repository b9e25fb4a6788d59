"""What every executable network shares: subgraph functions over a layer DAG.

An executable is a :class:`~repro.core.graph.ModelGraph` with the JAX
semantics of each layer. :class:`DagExecutable` turns any convex set of its
layers into a function of the subgraph's boundary inputs, which is what the
device-in-the-loop profiler times and what the Runtime's engines load. A
network states its layers' semantics (:meth:`DagExecutable.apply_layer`),
each layer's output shape, its weights and its input; the interface of a
subgraph, its example arguments and its weight bytes follow from those.
"""
from __future__ import annotations

from typing import Callable, Dict, List, Sequence, Tuple

import numpy as np

from ..core.graph import ModelGraph

#: Bytes per element of a served dtype gene: fp16 and int8 genes run in
#: bfloat16.
SERVED_BYTES = {"fp32": 4, "fp16": 2, "int8": 2}


def served_dtype(dtype: str):
    """The JAX dtype a subgraph with dtype gene ``dtype`` computes in."""
    import jax.numpy as jnp

    return {"fp32": jnp.float32, "fp16": jnp.bfloat16,
            "int8": jnp.bfloat16}[dtype]


def conv2d(x, w, stride: int = 1, out_dtype=None):
    """NHWC x HWIO cross-correlation at ``stride``, zero-padded by half
    the kernel on each side (PyTorch's ``padding=k // 2``; "SAME" at
    stride 1), returned in ``out_dtype`` (default: the operands')."""
    import jax

    pad = w.shape[0] // 2
    return jax.lax.conv_general_dilated(
        x, w, window_strides=(stride, stride),
        padding=((pad, pad), (pad, pad)),
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        preferred_element_type=out_dtype)


class DagExecutable:
    """Subgraph functions over ``self.graph``.

    A subclass sets ``graph`` and defines :meth:`model_input`,
    :meth:`output_shape`, :meth:`layer_weights` and :meth:`apply_layer`.
    A layer with no in-edge reads the model input; every other layer reads
    its predecessors' outputs in in-edge order.
    """

    graph: ModelGraph

    def model_input(self) -> np.ndarray:
        """The network's input tensor (float32, seeded)."""
        raise NotImplementedError

    def output_shape(self, lid: int) -> Tuple[int, ...]:
        """The shape of layer ``lid``'s output."""
        raise NotImplementedError

    def layer_weights(self, lid: int) -> Sequence[np.ndarray]:
        """The arrays layer ``lid`` compiles into a served program."""
        raise NotImplementedError

    def apply_layer(self, lid: int, inputs: Sequence, dt):
        """Layer ``lid`` on its inputs, computing in JAX dtype ``dt``."""
        raise NotImplementedError

    def boundary(
        self, layer_ids: Sequence[int]
    ) -> Tuple[List[Tuple[int, int]], List[int]]:
        """A subgraph's interface, in :meth:`build_subgraph_fn` order.

        Returns its external inputs as ``(src_layer, dst_layer)`` pairs, one
        per argument (``src_layer == -1`` is the model input), and the
        layers whose outputs it returns: every layer with an edge leaving
        the subgraph, and the network's sinks, in layer order.
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
        argument is :meth:`model_input`; each other argument takes the
        shape of the layer it comes from, filled with 0.1.
        """
        import jax.numpy as jnp

        dt = served_dtype(dtype)
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
                env[lid] = self.apply_layer(lid, ins, dt)
            outs = [env[lid] for lid in out_ids]
            return outs[0] if len(outs) == 1 else tuple(outs)

        args = tuple(
            jnp.asarray(self.model_input(), dtype=dt) if src < 0
            else jnp.zeros(self.output_shape(src), dtype=dt) + 0.1
            for src, _ in ext_inputs
        )
        return fn, args

    def param_bytes(self, layer_ids: Sequence[int], dtype: str) -> int:
        """Bytes of the weights a subgraph's program holds, at the dtype
        its gene serves in."""
        return sum(int(np.size(w)) * SERVED_BYTES[dtype]
                   for lid in layer_ids for w in self.layer_weights(lid))
