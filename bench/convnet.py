"""The served networks as the benchmark sees them: shapes, work and reference.

Each network of the zoo is executed as a synthetic stack of 3x3 "SAME"
convolutions with ReLU, at one width ``C`` and one resolution ``s``, with
residual merges (ReLU of a sum) every fifth layer from layer 4. Its depth is
the network's Table 6 layer count, and ``C`` is chosen per configuration so
that the executable's MACs, ``s^2 * 9 * C^2`` per convolution, lie within
15% of Table 6. These are not the published architectures.

This module restates that structure from the paper's Table 6 and the rules
the program documents, and imports nothing of the program:

* :func:`conv_layers`, :func:`executable_macs` -- the work of a network;
* :func:`subgraph_work` -- the operations and the least HBM bytes of one
  executed subgraph, from unpadded shapes, for the roofline;
* :func:`make_weights`, :func:`reference_forward` -- the plain float32
  reference: the weights drawn from the configuration's model seed by the
  documented recipe, then the stack run layer by layer on the device at
  ``Precision.HIGHEST``. ``mode="fp8"`` rounds every convolution's operands
  to float8 (e4m3) first: the control one precision below bfloat16.
"""
from __future__ import annotations

import functools
from typing import Dict, List, Sequence, Tuple

import numpy as np

#: arXiv:2508.17764 Table 6: MACs, layer count and input resolution.
TABLE6: Dict[str, Dict[str, float]] = {
    "face_det": {"macs": 39.2e6, "layers": 12, "input": 128},
    "selfie_seg": {"macs": 72.3e6, "layers": 14, "input": 256},
    "hand_det": {"macs": 410.8e6, "layers": 18, "input": 192},
    "pose_det": {"macs": 444.2e6, "layers": 18, "input": 224},
    "tcmonodepth": {"macs": 2313.2e6, "layers": 22, "input": 256},
    "fast_scnn": {"macs": 2358.9e6, "layers": 20, "input": 512},
    "yolov8n": {"macs": 4891.3e6, "layers": 24, "input": 640},
    "mosaic": {"macs": 22055.1e6, "layers": 28, "input": 512},
    "fastsam_s": {"macs": 22325.1e6, "layers": 28, "input": 640},
}

#: Bytes per element of a served dtype gene on the chip: fp16 and int8
#: genes run in bfloat16.
DTYPE_BYTES = {"fp32": 4, "fp16": 2, "int8": 2}


def merge_layers(n: int) -> List[int]:
    """Indices of the residual merges of an ``n``-layer stack."""
    return [] if n < 8 else list(range(4, n - 1, 5))


def predecessors(n: int) -> List[List[int]]:
    """Each layer's inputs: the previous layer, and for a merge also the
    layer three back. Layer 0 reads the network input."""
    merges = set(merge_layers(n))
    return [([i - 1] if i else []) + ([i - 3] if i in merges else [])
            for i in range(n)]


def conv_layers(name: str) -> int:
    n = int(TABLE6[name]["layers"])
    return n - len(merge_layers(n))


def executable_macs(name: str, spatial: int, channels: int) -> int:
    return spatial * spatial * 9 * channels * channels * conv_layers(name)


def subgraph_work(name: str, layer_ids: Sequence[int], spatial: int,
                  channels: int, dtype: str) -> Tuple[float, float]:
    """(operations, least HBM bytes) of one execute of a subgraph.

    Operations are the convolutions' multiply-adds, two each. Bytes count
    each convolution reading its input and weights and writing its output
    once; a merge is taken as fused into its consumer, which then reads
    the merge's second operand too, and a merge that the subgraph returns
    writes its sum. Nothing is counted for padding, so the time these give
    is a lower bound.
    """
    n = int(TABLE6[name]["layers"])
    merges = set(merge_layers(n))
    ids = set(layer_ids)
    succ: Dict[int, List[int]] = {i: [] for i in range(n)}
    for i, ps in enumerate(predecessors(n)):
        for p in ps:
            succ[p].append(i)
    act = spatial * spatial * channels * DTYPE_BYTES[dtype]
    wgt = 9 * channels * channels * DTYPE_BYTES[dtype]
    flops = 0.0
    nbytes = 0.0
    for i in ids:
        if i in merges:
            nbytes += act  # the second operand, read by the consumer
            if not succ[i] or any(s not in ids for s in succ[i]):
                nbytes += act  # a returned sum is written
            continue
        flops += 2.0 * spatial * spatial * 9 * channels * channels
        nbytes += 2 * act + wgt
    return flops, nbytes


def make_weights(name: str, spatial: int, channels: int, seed: int
                 ) -> Dict[int, np.ndarray]:
    """The executable's weights, by its documented recipe: one key split
    per layer from ``PRNGKey(seed)``, He-normal 3x3xCxC for each
    convolution. Drawn on the host CPU."""
    import jax

    cpu = jax.devices("cpu")[0]
    n = int(TABLE6[name]["layers"])
    merges = set(merge_layers(n))
    scale = (2.0 / (9 * channels)) ** 0.5
    out: Dict[int, np.ndarray] = {}
    with jax.default_device(cpu):
        key = jax.random.PRNGKey(seed)
        for i in range(n):
            key, sub = jax.random.split(key)
            if i not in merges:
                w = jax.random.normal(sub, (3, 3, channels, channels),
                                      dtype=np.float32) * scale
                out[i] = np.asarray(w, dtype=np.float32)
    return out


def make_input(spatial: int, channels: int, seed: int) -> np.ndarray:
    """A network input, NHWC float32, standard normal from ``seed``."""
    rng = np.random.default_rng(seed)
    return rng.standard_normal((1, spatial, spatial, channels),
                               dtype=np.float32)


@functools.lru_cache(maxsize=None)
def _layer_fns():
    import jax
    import jax.numpy as jnp

    def fp8(a):
        return a.astype(jnp.float8_e4m3fn).astype(jnp.float32)

    @functools.partial(jax.jit, static_argnames=("mode",))
    def conv(x, w, mode):
        if mode == "fp8":
            x, w = fp8(x), fp8(w)
        y = jax.lax.conv_general_dilated(
            x, w, window_strides=(1, 1), padding="SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32)
        return jnp.maximum(y, 0.0)

    @jax.jit
    def merge(a, b):
        return jnp.maximum(a + b, 0.0)

    return conv, merge


def reference_forward(name: str, weights: Dict[int, np.ndarray],
                      x: np.ndarray, mode: str = "f32") -> np.ndarray:
    """The sink output of the stack on input ``x``, layer by layer on the
    default device in float32 at ``Precision.HIGHEST`` (``mode="fp8"``:
    operands rounded to float8 e4m3 first). Keeps only the activations a
    later layer still reads."""
    import jax.numpy as jnp

    conv, merge = _layer_fns()
    n = int(TABLE6[name]["layers"])
    preds = predecessors(n)
    last_use = {}
    for i, ps in enumerate(preds):
        for p in ps:
            last_use[p] = i
    vals: Dict[int, object] = {}
    for i in range(n):
        if i in weights:
            src = vals[preds[i][0]] if preds[i] else jnp.asarray(x)
            vals[i] = conv(src, jnp.asarray(weights[i]), mode=mode)
        else:
            vals[i] = merge(*(vals[p] for p in preds[i]))
        for p in preds[i]:
            if last_use[p] == i:
                del vals[p]
    return np.asarray(vals[n - 1], dtype=np.float32)


def rel_l2(out: np.ndarray, ref: np.ndarray) -> float:
    """||out - ref|| / ||ref||, in float64."""
    out = np.asarray(out, np.float64)
    ref = np.asarray(ref, np.float64)
    return float(np.linalg.norm(out - ref) / np.linalg.norm(ref))
