"""YOLOv8n and FastSAM-s as published: Ultralytics' layer DAGs at real shapes.

The layer table is transcribed from Ultralytics' ``yolov8.yaml`` (detect)
and ``yolov8-seg.yaml`` (segment) with ``scale`` and ``nc`` as parameters:

* ``yolov8n`` is ``yolov8.yaml`` at scale ``n`` (depth 0.33, width 0.25,
  max_channels 1024) with 80 classes;
* ``fastsam_s`` (FastSAM-s, arXiv:2306.12156) is ``yolov8-seg.yaml`` at
  scale ``s`` (0.33, 0.50, 1024) with one class: FastSAM trains a single
  "object" class.

Each is one :class:`~repro.core.graph.ModelGraph` of leaf operations, each
named by its Ultralytics module path: every ``Conv`` (convolution, with
BatchNorm folded into a bias, then SiLU), every head ``nn.Conv2d``, the
``ConvTranspose2d`` of ``Proto``, and the tensor operations between them:
a C2f's split (``<path>.split``, the second half of ``cv1``'s channels; the
first half reaches the concat inside ``cv1``'s whole output), concats,
residual adds, SPPF's three max-pools (``<path>.m``, ``.m:2``, ``.m:3``,
one module applied three times), nearest x2 upsamples, and the head's
inference decode (``model.22``). Each layer carries its real MACs,
parameter bytes and output bytes (float32), so the paper's Table 6 rows
apply by MAC share.

:class:`YoloV8` is the executable: subgraph functions of its layers
(:class:`~repro.zoo.executable.DagExecutable`) with weights keyed by
module path, and :meth:`YoloV8.reference_forward`, the plain float32
reference, written module by module as Ultralytics' ``forward`` methods
read.
"""
from __future__ import annotations

import math
import zlib
from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..core.graph import Edge, Layer, ModelGraph
from .executable import DagExecutable, conv2d

#: scale -> (depth multiple, width multiple, max channels)
SCALES = {"n": (0.33, 0.25, 1024), "s": (0.33, 0.50, 1024)}

#: ``yolov8.yaml``: (from, repeats, module, args) per module; ``-1`` is the
#: previous module. The head's last module is Detect or Segment.
TABLE: Tuple[Tuple[object, int, str, tuple], ...] = (
    (-1, 1, "Conv", (64, 3, 2)),         # 0-P1/2
    (-1, 1, "Conv", (128, 3, 2)),        # 1-P2/4
    (-1, 3, "C2f", (128, True)),
    (-1, 1, "Conv", (256, 3, 2)),        # 3-P3/8
    (-1, 6, "C2f", (256, True)),
    (-1, 1, "Conv", (512, 3, 2)),        # 5-P4/16
    (-1, 6, "C2f", (512, True)),
    (-1, 1, "Conv", (1024, 3, 2)),       # 7-P5/32
    (-1, 3, "C2f", (1024, True)),
    (-1, 1, "SPPF", (1024, 5)),          # 9
    (-1, 1, "Upsample", (2,)),
    ((-1, 6), 1, "Concat", ()),          # cat backbone P4
    (-1, 3, "C2f", (512, False)),        # 12
    (-1, 1, "Upsample", (2,)),
    ((-1, 4), 1, "Concat", ()),          # cat backbone P3
    (-1, 3, "C2f", (256, False)),        # 15 (P3/8-small)
    (-1, 1, "Conv", (256, 3, 2)),
    ((-1, 12), 1, "Concat", ()),         # cat head P4
    (-1, 3, "C2f", (512, False)),        # 18 (P4/16-medium)
    (-1, 1, "Conv", (512, 3, 2)),
    ((-1, 9), 1, "Concat", ()),          # cat head P5
    (-1, 3, "C2f", (1024, False)),       # 21 (P5/32-large)
    ((15, 18, 21), 1, "Head", ()),       # Detect / Segment(P3, P4, P5)
)

REG_MAX = 16              # DFL bins per box side
STRIDES = (8, 16, 32)     # of P3, P4, P5
NM = 32                   # Segment's mask coefficients
NPR = 256                 # Segment's prototype channels, before width

#: The published networks of Table 6: name -> (scale, nc, task).
PUBLISHED = {"yolov8n": ("n", 80, "detect"),
             "fastsam_s": ("s", 1, "segment")}

#: Seeded weights (:func:`make_weights`): the deviation of the drawn
#: biases; the deviation and mean each Conv's folded BatchNorm gives its
#: pre-activations; the calibration batch its statistics are measured on.
BIAS_STD = 0.1
BN_GAMMA = 0.6
BN_SHIFT = 1.0
CAL_BATCH = 8


def make_divisible(x: float, divisor: int = 8) -> int:
    return int(math.ceil(x / divisor) * divisor)


@dataclass(frozen=True)
class Op:
    """One leaf operation of the layer table."""

    path: str                      # Ultralytics module path
    op: str                        # see YoloV8.apply_layer
    inputs: Tuple[int, ...]        # producing ops; () reads the model input
    shape: Tuple[int, ...]         # output shape, batch 1
    k: int = 0                     # kernel size (conv, conv2d, convT)
    stride: int = 1
    cin: int = 0                   # input channels (conv, conv2d, convT)

    @property
    def weight_shapes(self) -> Tuple[Tuple[int, ...], ...]:
        if self.op not in ("conv", "conv2d", "convT"):
            return ()
        cout = self.shape[-1]
        return (self.k, self.k, self.cin, cout), (cout,)

    @property
    def macs(self) -> int:
        if self.op == "convT":
            return int(np.prod(self.shape)) * self.cin
        if self.op in ("conv", "conv2d"):
            return int(np.prod(self.shape)) * self.k * self.k * self.cin
        return 0

    @property
    def params(self) -> int:
        return sum(int(np.prod(s)) for s in self.weight_shapes)


class _Builder:
    """Emits leaf ops, module by module."""

    def __init__(self, spatial: int):
        self.ops: List[Op] = []
        self.spatial = spatial

    def shape(self, i: int) -> Tuple[int, ...]:
        return (1, self.spatial, self.spatial, 3) if i < 0 \
            else self.ops[i].shape

    def add(self, path, op, inputs, shape, **kw) -> int:
        self.ops.append(Op(path, op, tuple(inputs), tuple(shape), **kw))
        return len(self.ops) - 1

    def conv(self, path, x, c2, k=1, s=1, op="conv") -> int:
        _, h, w, c1 = self.shape(x)
        p = k // 2
        ho, wo = (h + 2 * p - k) // s + 1, (w + 2 * p - k) // s + 1
        return self.add(path, op, () if x < 0 else (x,), (1, ho, wo, c2),
                        k=k, stride=s, cin=c1)

    def c2f(self, path, x, c2, n, shortcut) -> int:
        c = c2 // 2
        cv1 = self.conv(f"{path}.cv1", x, 2 * c)
        _, h, w, _ = self.shape(cv1)
        y = self.add(f"{path}.split", "split", (cv1,), (1, h, w, c))
        outs = [cv1]
        for j in range(n):
            a = self.conv(f"{path}.m.{j}.cv1", y, c, 3)
            b = self.conv(f"{path}.m.{j}.cv2", a, c, 3)
            y = self.add(f"{path}.m.{j}.add", "add", (y, b), (1, h, w, c)) \
                if shortcut else b
            outs.append(y)
        cat = self.add(f"{path}.cat", "concat", outs, (1, h, w, (2 + n) * c))
        return self.conv(f"{path}.cv2", cat, c2)

    def sppf(self, path, x, c2) -> int:
        c_ = self.shape(x)[-1] // 2
        y = [self.conv(f"{path}.cv1", x, c_)]
        for name in ("m", "m:2", "m:3"):
            y.append(self.add(f"{path}.{name}", "maxpool", (y[-1],),
                              self.shape(y[-1])))
        _, h, w, _ = self.shape(y[0])
        cat = self.add(f"{path}.cat", "concat", y, (1, h, w, 4 * c_))
        return self.conv(f"{path}.cv2", cat, c2)

    def branch(self, path, x, c_mid, c_out) -> int:
        """Two 3x3 Convs and a 1x1 ``nn.Conv2d``: one head branch."""
        a = self.conv(f"{path}.0", x, c_mid, 3)
        b = self.conv(f"{path}.1", a, c_mid, 3)
        return self.conv(f"{path}.2", b, c_out, 1, op="conv2d")

    def head(self, path, xs, nc, task, width) -> None:
        ch = [self.shape(x)[-1] for x in xs]
        c2 = max(16, ch[0] // 4, REG_MAX * 4)
        c3 = max(ch[0], min(nc, 100))
        c4 = max(ch[0] // 4, NM)
        outs = []
        for i, x in enumerate(xs):
            outs.append(self.branch(f"{path}.cv2.{i}", x, c2, 4 * REG_MAX))
            outs.append(self.branch(f"{path}.cv3.{i}", x, c3, nc))
            if task == "segment":
                outs.append(self.branch(f"{path}.cv4.{i}", x, c4, NM))
        anchors = sum(self.shape(x)[1] * self.shape(x)[2] for x in xs)
        self.add(path, "decode", outs,
                 (1, anchors, 4 + nc + (NM if task == "segment" else 0)))
        if task == "segment":
            npr = make_divisible(min(NPR, 1024) * width)
            a = self.conv(f"{path}.proto.cv1", xs[0], npr, 3)
            _, h, w, _ = self.shape(a)
            up = self.add(f"{path}.proto.upsample", "convT", (a,),
                          (1, 2 * h, 2 * w, npr), k=2, stride=2, cin=npr)
            b = self.conv(f"{path}.proto.cv2", up, npr, 3)
            self.conv(f"{path}.proto.cv3", b, NM)


def layer_table(scale: str, nc: int, task: str, spatial: int) -> List[Op]:
    """The leaf ops of ``yolov8.yaml`` (``task="detect"``) or
    ``yolov8-seg.yaml`` (``"segment"``) at ``scale``, ``nc`` classes and a
    ``spatial`` x ``spatial`` x 3 input, in topological order."""
    if task not in ("detect", "segment"):
        raise ValueError(f"unknown task {task!r}")
    depth, width, max_ch = SCALES[scale]
    b = _Builder(spatial)
    out: List[int] = []        # each module's output op
    for i, (f, n, m, args) in enumerate(TABLE):
        path = f"model.{i}"
        src = [out[j] if j >= 0 else (out[-1] if out else -1)
               for j in (f if isinstance(f, tuple) else (f,))]
        n = max(round(n * depth), 1) if n > 1 else n
        if m in ("Conv", "C2f", "SPPF"):
            c2 = make_divisible(min(args[0], max_ch) * width)
        if m == "Conv":
            out.append(b.conv(path, src[0], c2, args[1], args[2]))
        elif m == "C2f":
            out.append(b.c2f(path, src[0], c2, n, args[1]))
        elif m == "SPPF":
            out.append(b.sppf(path, src[0], c2))
        elif m == "Upsample":
            _, h, w, c = b.shape(src[0])
            out.append(b.add(path, "upsample", src, (1, 2 * h, 2 * w, c)))
        elif m == "Concat":
            _, h, w, _ = b.shape(src[0])
            c = sum(b.shape(s)[-1] for s in src)
            out.append(b.add(path, "concat", src, (1, h, w, c)))
        else:
            b.head(path, src, nc, task, width)
    return b.ops


def make_graph(name: str, ops: Sequence[Op], spatial: int) -> ModelGraph:
    """The schedulable cost graph: one layer per op, float32 bytes."""
    layers, edges = [], []
    for i, o in enumerate(ops):
        attrs: Tuple[Tuple[str, object], ...] = (
            ("model", name), ("path", o.path), ("shape", o.shape))
        if not o.inputs:
            attrs += (("input_bytes", 4 * spatial * spatial * 3),)
        layers.append(Layer(index=i, name=o.path, op_type=o.op,
                            macs=float(o.macs), param_bytes=4 * o.params,
                            out_bytes=4 * int(np.prod(o.shape)), attrs=attrs))
        for src in o.inputs:
            edges.append(Edge(index=len(edges), src=src, dst=i,
                              bytes_=layers[src].out_bytes))
    return ModelGraph(name, layers, edges)


def make_anchors(spatial: int) -> Tuple[np.ndarray, np.ndarray]:
    """Ultralytics' anchor points (cell centres, x then y, in grid units)
    and each anchor's stride, over P3, P4 and P5 in order."""
    points, strides = [], []
    for s in STRIDES:
        n = -(-spatial // s)
        sy, sx = np.meshgrid(np.arange(n) + 0.5, np.arange(n) + 0.5,
                             indexing="ij")
        points.append(np.stack([sx, sy], -1).reshape(-1, 2))
        strides.append(np.full((n * n, 1), s))
    return (np.concatenate(points).astype(np.float32),
            np.concatenate(strides).astype(np.float32))


class YoloV8(DagExecutable):
    """A published YOLOv8 network as an executable layer DAG.

    ``weights`` maps each parametrized module path to its (HWIO kernel,
    bias) in float32, BatchNorm folded (default: :func:`make_weights` of
    ``seed``, calibrated at ``spatial``); ``x`` is the NHWC float32 input (default: standard normal
    from ``seed``). The sinks are the decoded detections, ``(1, anchors,
    4 + nc [+ 32])`` in float32 (box centre and size in pixels, class
    probabilities, and for ``segment`` the mask coefficients), then for
    ``segment`` the prototypes, ``(1, s/4, s/4, 32)``.
    """

    def __init__(self, name: str, scale: str = "n", nc: int = 80,
                 task: str = "detect", spatial: int = 640,
                 weights: Optional[Dict[str, Tuple[np.ndarray, np.ndarray]]]
                 = None, x: Optional[np.ndarray] = None, seed: int = 0):
        self.name = name
        self.scale, self.nc, self.task, self.spatial = scale, nc, task, spatial
        self.ops = layer_table(scale, nc, task, spatial)
        self.graph = make_graph(name, self.ops, spatial)
        self.weights = make_weights(scale, nc, task, spatial, seed) \
            if weights is None else weights
        for o in self.ops:
            if o.weight_shapes:
                got = tuple(np.shape(a) for a in self.weights[o.path])
                if got != o.weight_shapes:
                    raise ValueError(f"{name} {o.path}: weights {got}, "
                                     f"expected {o.weight_shapes}")
        self._input = (np.random.default_rng(seed).standard_normal(
            self.input_shape(), dtype=np.float32) if x is None
            else np.asarray(x, np.float32))
        self._anchors, self._strides = make_anchors(spatial)

    @classmethod
    def published(cls, name: str, spatial: int = 640, **kw) -> "YoloV8":
        scale, nc, task = PUBLISHED[name]
        return cls(name, scale, nc, task, spatial, **kw)

    def input_shape(self) -> Tuple[int, int, int, int]:
        return (1, self.spatial, self.spatial, 3)

    def model_input(self) -> np.ndarray:
        return self._input

    def output_shape(self, lid: int) -> Tuple[int, ...]:
        return self.ops[lid].shape

    def layer_weights(self, lid: int) -> Sequence[np.ndarray]:
        return self.weights.get(self.ops[lid].path, ())

    # -- layer semantics -------------------------------------------------------
    def apply_layer(self, lid: int, inputs: Sequence, dt):
        """``conv``: Conv (convolution, folded bias, SiLU); ``conv2d``: a
        head ``nn.Conv2d`` (no activation); ``convT``: ConvTranspose2d,
        kernel 2 stride 2; ``split``: the second half of the channels;
        ``concat``; ``add``; ``maxpool``: 5x5, stride 1, padding 2;
        ``upsample``: nearest x2; ``decode``: the head's inference
        output."""
        import jax
        import jax.numpy as jnp

        o = self.ops[lid]
        x = inputs[0]
        if o.weight_shapes:
            # operands and result in ``dt``; accumulation, bias and SiLU
            # in float32, rounded once
            f32 = jnp.float32
            w, b = (jnp.asarray(a, dtype=dt) for a in self.weights[o.path])
            if o.op == "convT":
                n, h, wd, _ = x.shape
                y = jnp.einsum("nhwc,abco->nhawbo", x, w,
                               preferred_element_type=f32).reshape(
                    n, 2 * h, 2 * wd, w.shape[-1])
            else:
                y = conv2d(x, w, o.stride, f32)
            y = y + b.astype(f32)
            return (jax.nn.silu(y) if o.op == "conv" else y).astype(dt)
        if o.op == "split":
            return x[..., x.shape[-1] // 2:]
        if o.op == "concat":
            return jnp.concatenate(inputs, axis=-1)
        if o.op == "add":
            return inputs[0] + inputs[1]
        if o.op == "maxpool":
            return jax.lax.reduce_window(
                x, jnp.asarray(-jnp.inf, x.dtype), jax.lax.max,
                (1, 5, 5, 1), (1, 1, 1, 1), ((0, 0), (2, 2), (2, 2), (0, 0)))
        if o.op == "upsample":
            return jnp.repeat(jnp.repeat(x, 2, axis=1), 2, axis=2)
        if o.op == "decode":
            return self._decode(inputs)
        raise ValueError(f"unknown op {o.op!r}")

    def _decode(self, inputs: Sequence):
        """Box distances as the expectation of each side's 16-bin softmax
        (DFL), box centre and size on the anchor grid times the stride,
        sigmoid class probabilities, and the mask coefficients as they
        are. In float32 whatever the gene: box coordinates of up to 640 px
        need more than bfloat16's 8 bits."""
        import jax
        import jax.numpy as jnp

        f32 = jnp.float32
        per = 3 if self.task == "segment" else 2
        levels = [inputs[per * i:per * i + per] for i in range(len(STRIDES))]
        box = jnp.concatenate([lv[0].reshape(1, -1, 4, REG_MAX)
                               for lv in levels], 1).astype(f32)
        dist = jnp.sum(jax.nn.softmax(box, -1)
                       * jnp.arange(REG_MAX, dtype=f32), -1)
        lt, rb = dist[..., :2], dist[..., 2:]
        parts = [(self._anchors + (rb - lt) / 2) * self._strides,
                 (lt + rb) * self._strides,
                 jax.nn.sigmoid(jnp.concatenate(
                     [lv[1].reshape(1, -1, self.nc) for lv in levels], 1)
                     .astype(f32))]
        if self.task == "segment":
            parts.append(jnp.concatenate(
                [lv[2].reshape(1, -1, NM) for lv in levels], 1).astype(f32))
        return jnp.concatenate(parts, -1)

    # -- the reference -------------------------------------------------------
    def reference_forward(self) -> List[np.ndarray]:
        """Every sink, in ``graph.sinks()`` order, on :meth:`model_input`.

        Plain ``jax.numpy`` in float32 at ``default_matmul_precision
        ("highest")``, one operation at a time with no subgraphs and no
        jit, module by module as Ultralytics' ``forward`` methods read
        (:func:`module_forward`). It shares the layer table and the weights
        with the subgraph functions. Departures from Ultralytics: BatchNorm
        is folded into each convolution's bias; DFL is its softmax
        expectation; NMS and the assembly of masks from prototypes are host
        post-processing and are left out.
        """
        import jax
        import jax.numpy as jnp

        def conv(x, path, k, s, op):
            w, b = (jnp.asarray(a) for a in self.weights[path])
            return _conv_op(x, w, s, op) + b

        with jax.default_matmul_precision("highest"):
            return [np.asarray(v, np.float32) for v in module_forward(
                self.scale, self.nc, self.task,
                jnp.asarray(self._input), conv)]


def _conv_op(x, w, s, op):
    """A convolution as PyTorch computes it, unbiased: ``conv``/``conv2d``
    zero-padded by half the kernel, ``convT`` kernel 2 stride 2 (the HWIO
    kernel flipped for ``conv_transpose``)."""
    import jax

    hi = jax.lax.Precision.HIGHEST
    dn = ("NHWC", "HWIO", "NHWC")
    if op == "convT":
        return jax.lax.conv_transpose(x, w[::-1, ::-1], (s, s), "VALID",
                                      dimension_numbers=dn, precision=hi)
    k = w.shape[0]
    return jax.lax.conv_general_dilated(
        x, w, (s, s), [(k // 2, k // 2)] * 2, dimension_numbers=dn,
        precision=hi)


def module_forward(scale: str, nc: int, task: str, x, conv) -> list:
    """The network's sinks on ``x``, module by module as Ultralytics'
    ``forward`` methods read them, in ``jax.numpy`` float32.

    ``conv(x, path, k, s, op)`` computes the layer at module ``path``:
    a biased convolution of kernel ``k`` and stride ``s``, before any
    activation (``op`` ``conv``, ``conv2d`` or ``convT``); a ``conv``
    (Ultralytics' ``Conv``) is followed by SiLU here.
    """
    import jax
    import jax.numpy as jnp

    def cv(x, path, k=1, s=1):
        return jax.nn.silu(conv(x, path, k, s, "conv"))

    def c2f(x, path, n, shortcut):
        y = list(jnp.split(cv(x, f"{path}.cv1"), 2, axis=-1))
        for j in range(n):
            h = cv(cv(y[-1], f"{path}.m.{j}.cv1", 3), f"{path}.m.{j}.cv2", 3)
            y.append(y[-1] + h if shortcut else h)
        return cv(jnp.concatenate(y, -1), f"{path}.cv2")

    def sppf(x, path):
        y = [cv(x, f"{path}.cv1")]
        for _ in range(3):
            y.append(jax.lax.reduce_window(
                y[-1], -jnp.inf, jax.lax.max, (1, 5, 5, 1), (1, 1, 1, 1),
                ((0, 0), (2, 2), (2, 2), (0, 0))))
        return cv(jnp.concatenate(y, -1), f"{path}.cv2")

    def branch(x, path, c):
        h = cv(cv(x, f"{path}.0", 3), f"{path}.1", 3)
        return conv(h, f"{path}.2", 1, 1, "conv2d").reshape(len(x), -1, c)

    def head(xs, path):
        box = jnp.concatenate([branch(x, f"{path}.cv2.{i}", 4 * REG_MAX)
                               for i, x in enumerate(xs)], 1)
        cls = jnp.concatenate([branch(x, f"{path}.cv3.{i}", nc)
                               for i, x in enumerate(xs)], 1)
        # DFL: each side's softmax over its bins, times the bin index
        n, a, _ = box.shape
        prob = jax.nn.softmax(box.reshape(n, a, 4, REG_MAX), axis=-1)
        dist = jnp.einsum("bask,k->bas", prob,
                          jnp.arange(REG_MAX, dtype=jnp.float32))
        # make_anchors, then dist2bbox(xywh=True) times the stride
        points, strides = [], []
        for x, s in zip(xs, STRIDES):
            h, w = x.shape[1:3]
            sy, sx = jnp.meshgrid(jnp.arange(h) + 0.5, jnp.arange(w) + 0.5,
                                  indexing="ij")
            points.append(jnp.stack((sx, sy), -1).reshape(-1, 2))
            strides.append(jnp.full((h * w, 1), float(s)))
        anchor = jnp.concatenate(points)
        lt, rb = dist[..., :2], dist[..., 2:]
        x1y1, x2y2 = anchor - lt, anchor + rb
        dbox = jnp.concatenate(((x1y1 + x2y2) / 2, x2y2 - x1y1), -1) \
            * jnp.concatenate(strides)
        det = [dbox, jax.nn.sigmoid(cls)]
        if task != "segment":
            return [jnp.concatenate(det, -1)]
        mc = jnp.concatenate([branch(x, f"{path}.cv4.{i}", NM)
                              for i, x in enumerate(xs)], 1)
        p = cv(xs[0], f"{path}.proto.cv1", 3)
        p = conv(p, f"{path}.proto.upsample", 2, 2, "convT")
        p = cv(cv(p, f"{path}.proto.cv2", 3), f"{path}.proto.cv3")
        return [jnp.concatenate(det + [mc], -1), p]

    depth = SCALES[scale][0]
    y: list = []
    for i, (f, n, m, args) in enumerate(TABLE):
        path = f"model.{i}"
        src = [y[j] if j >= 0 else (y[-1] if y else x)
               for j in (f if isinstance(f, tuple) else (f,))]
        n = max(round(n * depth), 1) if n > 1 else n
        if m == "Conv":
            y.append(cv(src[0], path, args[1], args[2]))
        elif m == "C2f":
            y.append(c2f(src[0], path, n, args[1]))
        elif m == "SPPF":
            y.append(sppf(src[0], path))
        elif m == "Upsample":
            u = src[0]
            y.append(jax.image.resize(
                u, (len(u), 2 * u.shape[1], 2 * u.shape[2], u.shape[3]),
                "nearest"))
        elif m == "Concat":
            y.append(jnp.concatenate(src, -1))
        else:
            return head(src, path)
    raise AssertionError("the table ends in its head")


def make_weights(scale: str, nc: int, task: str, spatial: int, seed: int
                 ) -> Dict[str, Tuple[np.ndarray, np.ndarray]]:
    """Seeded weights keyed by module path: ``(HWIO kernel, bias)`` in
    float32, with BatchNorm folded.

    Each module draws from its own stream, seeded by ``seed`` and the
    CRC-32 of its path: a kernel N(0, 1/fan_in), then a bias N(0,
    ``BIAS_STD``^2) (zero for the class logits rather than Ultralytics'
    prior of about -8, so that no sigmoid saturates). BatchNorm is folded
    as in a deployed network, with statistics measured by one pass of
    :func:`module_forward` over a calibration batch (``CAL_BATCH`` standard
    normal inputs at ``spatial`` from ``seed``): each ``Conv``'s unbiased
    output is centred per channel and scaled by ``BN_GAMMA`` over its
    per-image deviation (averaged over the batch), and shifted by
    ``BN_SHIFT`` plus the drawn bias; each head ``nn.Conv2d`` and the
    ConvTranspose are scaled to an output RMS of 1. Activations then stay
    O(1) through the whole network, and the pre-activations sit mostly
    where SiLU is nearly linear, so that random weights do not amplify a
    rounding error layer after layer.
    """
    import jax
    import jax.numpy as jnp

    out: Dict[str, Tuple[np.ndarray, np.ndarray]] = {}

    def conv(x, path, k, s, op):
        wshape, bshape = shapes[path]
        rng = np.random.default_rng((seed, zlib.crc32(path.encode())))
        fan_in = wshape[2] * (1 if op == "convT" else k * k)
        w = rng.standard_normal(wshape, dtype=np.float32) \
            / np.float32(math.sqrt(fan_in))
        b = rng.standard_normal(bshape, dtype=np.float32) \
            * np.float32(BIAS_STD)
        if op == "conv2d" and ".cv3." in path:
            b = np.zeros(bshape, np.float32)
        z = np.asarray(_conv_op(x, jnp.asarray(w), s, op), np.float64)
        if op == "conv":
            per_image = z.reshape(len(z), -1, z.shape[-1])
            sd = per_image.std(axis=1).mean()
            mean = per_image.mean(axis=(0, 1))
            w = w * np.float32(BN_GAMMA / sd)
            b = b + np.float32(BN_SHIFT) - np.float32(mean * BN_GAMMA / sd)
        else:
            w = w / np.float32(np.sqrt(np.mean(z * z)))
        out[path] = (w.astype(np.float32), b.astype(np.float32))
        return _conv_op(x, jnp.asarray(out[path][0]), s, op) \
            + jnp.asarray(out[path][1])

    shapes = {o.path: o.weight_shapes
              for o in layer_table(scale, nc, task, spatial)}
    rng = np.random.default_rng((seed, zlib.crc32(b"calibration")))
    x = rng.standard_normal((CAL_BATCH, spatial, spatial, 3), dtype=np.float32)
    with jax.default_matmul_precision("highest"):
        module_forward(scale, nc, task, jnp.asarray(x), conv)
    return out
