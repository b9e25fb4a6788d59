"""YOLOv8n and FastSAM-s as the benchmark sees them: layers, work and reference.

The published networks of the ``yolov8`` family, restated from their
sources and imports nothing of the program:

* Ultralytics ``yolov8.yaml`` (YOLOv8, detect) and ``yolov8-seg.yaml``
  (segment) at a scale (depth, width, max_channels) and a class count:
  ``yolov8n`` is scale ``n`` (0.33, 0.25, 1024) with 80 classes;
  ``fastsam_s`` (FastSAM-s, arXiv:2306.12156) is the segment model at scale
  ``s`` (0.33, 0.50, 1024) with one class;
* Conv = convolution (padding k // 2), BatchNorm folded into a bias, SiLU.
  C2f = a 1x1 Conv to 2c, split in two, n Bottlenecks of two 3x3 Convs on
  the last piece (with a residual add in the backbone), a concat of
  (2 + n)c and a 1x1 Conv. SPPF = a 1x1 Conv, three chained 5x5 stride-1
  max-pools, a concat of four and a 1x1 Conv. Detect = per level of P3, P4,
  P5 a box branch (two 3x3 Convs, a 1x1 ``nn.Conv2d`` to 4 x 16 DFL bins)
  and a class branch (to nc), decoded at inference: each side's softmax
  expectation over its bins, ``dist2bbox`` on the anchor grid times the
  stride, sigmoid on the classes. Segment adds a mask-coefficient branch
  (to 32) per level and a Proto on P3 (a 3x3 Conv, a ConvTranspose of
  kernel 2 stride 2, a 3x3 Conv and a 1x1 Conv to 32).

What the benchmark computes from that:

* :func:`layers` -- the leaf layers in the order the program numbers them
  (every Conv, head Conv2d and ConvTranspose, each C2f's split, concat and
  add, SPPF's pools, each upsample and concat, the decode), each with its
  output shape and multiply-adds: the program's layer ids index this list;
* :func:`subgraph_work` -- operations and least HBM bytes of one execute,
  by ``convnet.subgraph_work``'s definition; :func:`executable_macs`;
* :func:`make_weights`, :func:`make_input`, :func:`reference_forward` --
  the weights drawn from the configuration's model seed by the documented
  recipe (BatchNorm folded with statistics of a seeded calibration batch),
  the input from ``--seed``, and the sinks computed layer by layer on the
  default device in float32 at ``Precision.HIGHEST``; ``mode="fp8"`` rounds
  every convolution's operands to float8 (e4m3) first;
* :func:`components` -- a network's outputs split into what ``correct``
  compares one by one.
"""
from __future__ import annotations

import functools
import json
import math
import zlib
from typing import Dict, List, Sequence, Tuple

import numpy as np

#: scale -> (depth multiple, width multiple, max channels)
SCALES = {"n": (0.33, 0.25, 1024), "s": (0.33, 0.50, 1024)}
REG_MAX = 16
STRIDES = (8, 16, 32)
NM = 32
NPR = 256
#: The weights' recipe (:func:`make_weights`).
BIAS_STD = 0.1
BN_GAMMA = 0.6
BN_SHIFT = 1.0
CAL_BATCH = 8
#: Bytes per element of a served dtype gene: fp16 and int8 run in bfloat16.
DTYPE_BYTES = {"fp32": 4, "fp16": 2, "int8": 2}

#: yolov8.yaml (the seg yaml differs only in its last module)
BACKBONE_AND_NECK = [
    ("Conv", [-1], 64, 3, 2), ("Conv", [-1], 128, 3, 2),
    ("C2f", [-1], 128, 3, True), ("Conv", [-1], 256, 3, 2),
    ("C2f", [-1], 256, 6, True), ("Conv", [-1], 512, 3, 2),
    ("C2f", [-1], 512, 6, True), ("Conv", [-1], 1024, 3, 2),
    ("C2f", [-1], 1024, 3, True), ("SPPF", [-1], 1024, 5, None),
    ("Upsample", [-1], None, None, None), ("Concat", [-1, 6], None, None, None),
    ("C2f", [-1], 512, 3, False), ("Upsample", [-1], None, None, None),
    ("Concat", [-1, 4], None, None, None), ("C2f", [-1], 256, 3, False),
    ("Conv", [-1], 256, 3, 2), ("Concat", [-1, 12], None, None, None),
    ("C2f", [-1], 512, 3, False), ("Conv", [-1], 512, 3, 2),
    ("Concat", [-1, 9], None, None, None), ("C2f", [-1], 1024, 3, False),
]
HEAD_FROM = (15, 18, 21)


def _ch(c: int, width: float, max_ch: int) -> int:
    return int(math.ceil(min(c, max_ch) * width / 8) * 8)


def layers(net: dict) -> List[dict]:
    """The leaf layers of a network entry (``scale``, ``nc``, ``task``,
    ``spatial``): dicts with ``path``, ``op``, ``src`` (the layers read;
    empty for the network input), ``shape`` (H, W, C), ``k``, ``s``,
    ``cin`` and ``macs``."""
    depth, width, max_ch = SCALES[net["scale"]]
    size = net["spatial"]
    out: List[dict] = []

    def emit(path, op, src, shape, k=0, s=1, cin=0):
        h, w, c = shape
        macs = 0
        if op in ("conv", "conv2d"):
            macs = h * w * c * k * k * cin
        elif op == "convT":
            macs = h * w * c * cin
        out.append(dict(path=path, op=op, src=list(src), shape=shape, k=k,
                        s=s, cin=cin, macs=macs))
        return len(out) - 1

    def hwc(i):
        return (size, size, 3) if i < 0 else out[i]["shape"]

    def conv(path, i, c2, k=1, s=1, op="conv"):
        h, w, c1 = hwc(i)
        p = k // 2
        return emit(path, op, [] if i < 0 else [i],
                    ((h + 2 * p - k) // s + 1, (w + 2 * p - k) // s + 1, c2),
                    k, s, c1)

    def branch(path, i, cm, co):
        return conv(f"{path}.2", conv(f"{path}.1", conv(f"{path}.0", i, cm, 3),
                                      cm, 3), co, 1, op="conv2d")

    ends: List[int] = []
    for m, (kind, frm, c, n, extra) in enumerate(BACKBONE_AND_NECK):
        path = f"model.{m}"
        srcs = [ends[-1] if f == -1 else ends[f] for f in frm] if ends \
            else [-1]
        x = srcs[0]
        if kind == "Conv":
            ends.append(conv(path, x, _ch(c, width, max_ch), n, extra))
        elif kind == "C2f":
            reps = max(round(n * depth), 1)
            c2 = _ch(c, width, max_ch)
            half = c2 // 2
            a = conv(f"{path}.cv1", x, 2 * half)
            h, w, _ = hwc(a)
            cur = emit(f"{path}.split", "split", [a], (h, w, half))
            pieces = [a]
            for j in range(reps):
                u = conv(f"{path}.m.{j}.cv1", cur, half, 3)
                v = conv(f"{path}.m.{j}.cv2", u, half, 3)
                cur = emit(f"{path}.m.{j}.add", "add", [cur, v], (h, w, half)) \
                    if extra else v
                pieces.append(cur)
            cat = emit(f"{path}.cat", "concat", pieces,
                       (h, w, (2 + reps) * half))
            ends.append(conv(f"{path}.cv2", cat, c2))
        elif kind == "SPPF":
            a = conv(f"{path}.cv1", x, hwc(x)[2] // 2)
            pools = [a]
            for name in ("m", "m:2", "m:3"):
                pools.append(emit(f"{path}.{name}", "maxpool", [pools[-1]],
                                  hwc(a)))
            h, w, ca = hwc(a)
            cat = emit(f"{path}.cat", "concat", pools, (h, w, 4 * ca))
            ends.append(conv(f"{path}.cv2", cat, _ch(c, width, max_ch)))
        elif kind == "Upsample":
            h, w, ci = hwc(x)
            ends.append(emit(path, "upsample", [x], (2 * h, 2 * w, ci)))
        else:
            h, w, _ = hwc(x)
            ends.append(emit(path, "concat", srcs,
                             (h, w, sum(hwc(i)[2] for i in srcs))))
    feats = [ends[i] for i in HEAD_FROM]
    nc, seg = net["nc"], net["task"] == "segment"
    ch0 = hwc(feats[0])[2]
    heads = []
    for lvl, f in enumerate(feats):
        heads.append(branch(f"model.22.cv2.{lvl}", f,
                            max(16, ch0 // 4, 4 * REG_MAX), 4 * REG_MAX))
        heads.append(branch(f"model.22.cv3.{lvl}", f, max(ch0, min(nc, 100)),
                            nc))
        if seg:
            heads.append(branch(f"model.22.cv4.{lvl}", f, max(ch0 // 4, NM),
                                NM))
    anchors = sum(hwc(f)[0] * hwc(f)[1] for f in feats)
    emit("model.22", "decode", heads,
         (1, anchors, 4 + nc + (NM if seg else 0)))
    if seg:
        npr = _ch(NPR, width, 1024)
        a = conv("model.22.proto.cv1", feats[0], npr, 3)
        h, w, _ = hwc(a)
        up = emit("model.22.proto.upsample", "convT", [a], (2 * h, 2 * w, npr),
                  2, 2, npr)
        conv("model.22.proto.cv3", conv("model.22.proto.cv2", up, npr, 3),
             NM)
    return out


def executable_macs(net: dict) -> int:
    return sum(lay["macs"] for lay in layers(net))


def parameter_count(net: dict) -> int:
    """Kernels and (folded) biases of every convolution."""
    return sum(lay["k"] ** 2 * lay["cin"] * lay["shape"][2] + lay["shape"][2]
               for lay in layers(net) if lay["k"])


def subgraph_work(net: dict, layer_ids: Sequence[int], dtype: str
                  ) -> Tuple[float, float]:
    """(operations, least HBM bytes) of one execute of a subgraph.

    ``convnet.subgraph_work``'s definition on this DAG. Operations are the
    convolutions' multiply-adds, two each. Bytes: each convolution reads
    its input and weights (kernel and bias) and writes its output once. A
    tensor operation (split, concat, add, max-pool, upsample, decode) is
    taken as fused into the convolutions that read it, which then read the
    convolution outputs, subgraph inputs or network input it is made of,
    each once (a split reads half of what it splits); a tensor operation
    that the subgraph returns writes its output, having read those.
    Nothing is counted for padding, so the time these give is a lower
    bound.
    """
    lays = layers(net)
    ids = set(layer_ids)
    size = DTYPE_BYTES[dtype]
    consumers: Dict[int, List[int]] = {i: [] for i in range(len(lays))}
    for i, lay in enumerate(lays):
        for s in lay["src"]:
            consumers[s].append(i)

    def numel(i):
        return int(np.prod(lays[i]["shape"]))

    def leaves(i) -> Dict[int, float]:
        """What computing layer ``i`` in a fused chain reads: the
        materialized tensors (layer id, -1 for the network input) and the
        share of each."""
        if i < 0 or i not in ids or lays[i]["k"]:
            return {i: 1.0}
        got: Dict[int, float] = {}
        for s in lays[i]["src"]:
            for leaf, share in leaves(s).items():
                share = share / 2 if lays[i]["op"] == "split" else share
                got[leaf] = max(got.get(leaf, 0.0), share)
        return got

    def read(i) -> float:
        """Elements computing layer ``i`` reads: each leaf once."""
        if lays[i]["k"]:
            got: Dict[int, float] = {}
            for s in lays[i]["src"] or [-1]:
                for leaf, share in leaves(s).items():
                    got[leaf] = max(got.get(leaf, 0.0), share)
        else:
            got = leaves(i)
        return sum(share * (net["spatial"] ** 2 * 3 if leaf < 0
                            else numel(leaf)) for leaf, share in got.items())

    flops = 0.0
    elems = 0.0
    for i in sorted(ids):
        lay = lays[i]
        returned = not consumers[i] or any(c not in ids for c in consumers[i])
        if lay["k"]:
            flops += 2.0 * lay["macs"]
            c = lay["shape"][2]
            elems += read(i) + lay["k"] ** 2 * lay["cin"] * c + c + numel(i)
        elif returned:
            elems += read(i) + numel(i)
    return flops, elems * size


def make_input(spatial: int, seed: int) -> np.ndarray:
    """A network input, NHWC float32, standard normal from ``seed``."""
    return np.random.default_rng(seed).standard_normal(
        (1, spatial, spatial, 3), dtype=np.float32)


def _conv(x, w, stride: int, op: str, mode: str):
    """Unbiased convolution, float32 at HIGHEST precision; ``mode="fp8"``
    rounds both operands to float8 e4m3 first; ``mode="stats"`` runs at
    the device's default precision (only statistics are taken from it)."""
    import jax
    import jax.numpy as jnp

    if mode == "fp8":
        x, w = (a.astype(jnp.float8_e4m3fn).astype(jnp.float32)
                for a in (x, w))
    hi = jax.lax.Precision.DEFAULT if mode == "stats" else \
        jax.lax.Precision.HIGHEST
    if op == "convT":
        # out[2i + a, 2j + b] = x[i, j] @ w[a, b]
        n, h, wd, _ = x.shape
        y = jnp.einsum("nhwc,abco->nhawbo", x, w, precision=hi,
                       preferred_element_type=jnp.float32)
        return y.reshape(n, 2 * h, 2 * wd, w.shape[-1])
    p = w.shape[0] // 2
    return jax.lax.conv_general_dilated(
        x, w, (stride, stride), ((p, p), (p, p)),
        dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=hi,
        preferred_element_type=jnp.float32)


def _forward(net: dict, x, conv) -> list:
    """Every sink of the network on ``x`` (layer order of :func:`layers`),
    with ``conv(lay, inp)`` computing a convolution layer, bias and
    activation included. Traceable: the callers jit it whole."""
    import jax
    import jax.numpy as jnp

    lays = layers(net)
    vals: Dict[int, object] = {}
    consumed = {s for lay in lays for s in lay["src"]}
    sinks = []
    for i, lay in enumerate(lays):
        ins = [vals[s] for s in lay["src"]] or [x]
        op = lay["op"]
        if op in ("conv", "conv2d", "convT"):
            y = conv(lay, ins[0])
        elif op == "split":
            y = ins[0][..., ins[0].shape[-1] // 2:]
        elif op == "concat":
            y = jnp.concatenate(ins, -1)
        elif op == "add":
            y = ins[0] + ins[1]
        elif op == "maxpool":
            y = jax.lax.reduce_window(ins[0], -jnp.inf, jax.lax.max,
                                      (1, 5, 5, 1), (1, 1, 1, 1),
                                      ((0, 0), (2, 2), (2, 2), (0, 0)))
        elif op == "upsample":
            y = jnp.repeat(jnp.repeat(ins[0], 2, axis=1), 2, axis=2)
        else:
            y = _decode(net, ins)
        vals[i] = y
        if i not in consumed:
            sinks.append(y)
    return sinks


def _decode(net: dict, heads: list):
    import jax
    import jax.numpy as jnp

    per = 3 if net["task"] == "segment" else 2
    n = heads[0].shape[0]
    cols = [[], [], []]
    for lvl in range(len(STRIDES)):
        for j in range(per):
            h = heads[per * lvl + j]
            cols[j].append(h.reshape(n, -1, h.shape[-1]))
    box, cls = (jnp.concatenate(c, 1) for c in cols[:2])
    a = box.shape[1]
    dist = jnp.einsum("nask,k->nas",
                      jax.nn.softmax(box.reshape(n, a, 4, REG_MAX), -1),
                      jnp.arange(REG_MAX, dtype=jnp.float32),
                      precision=jax.lax.Precision.HIGHEST)
    grid, stride = anchor_grid(net["spatial"])
    x1y1 = grid - dist[..., :2]
    x2y2 = grid + dist[..., 2:]
    out = [(x1y1 + x2y2) / 2 * stride, (x2y2 - x1y1) * stride,
           jax.nn.sigmoid(cls)]
    if per == 3:
        out.append(jnp.concatenate(cols[2], 1))
    return jnp.concatenate(out, -1)


def anchor_grid(spatial: int) -> Tuple[np.ndarray, np.ndarray]:
    """Anchor points (x, y in cell units, at cell centres) and their
    strides, P3 then P4 then P5, each row by row."""
    pts, strides = [], []
    for s in STRIDES:
        n = -(-spatial // s)
        ys, xs = np.divmod(np.arange(n * n), n)
        pts.append(np.stack([xs, ys], 1) + 0.5)
        strides.append(np.full((n * n, 1), s))
    return (np.concatenate(pts).astype(np.float32),
            np.concatenate(strides).astype(np.float32))


@functools.lru_cache(maxsize=None)
def _jitted(net_key: str, what: str):
    """One jitted pass over a whole network (its entry as sorted JSON):
    ``calibrate`` (raw weights, batch) -> folded weights, or ``f32`` /
    ``fp8`` (weights, input) -> sinks. Weights are arguments, not
    constants, so each compiles once per process (and once per machine
    with the persistent compile cache)."""
    import jax
    import jax.numpy as jnp

    net = json.loads(net_key)

    def calibrate(raw, x):
        out = {}

        def conv(lay, inp):
            path, op = lay["path"], lay["op"]
            w, b = raw[path]
            z = _conv(inp, w, lay["s"], op, "stats")
            if op == "conv":
                per_image = z.reshape(z.shape[0], -1, z.shape[-1])
                scale = BN_GAMMA / per_image.std(axis=1).mean()
                b = b + BN_SHIFT - per_image.mean(axis=(0, 1)) * scale
            else:
                scale = 1.0 / jnp.sqrt(jnp.mean(z * z))
            out[path] = (w * scale, b)
            y = z * scale + b
            return jax.nn.silu(y) if op == "conv" else y

        _forward(net, x, conv)
        return out

    def forward(weights, x):
        def conv(lay, inp):
            w, b = weights[lay["path"]]
            y = _conv(inp, w, lay["s"], lay["op"], what) + b
            return jax.nn.silu(y) if lay["op"] == "conv" else y

        return _forward(net, x, conv)

    return jax.jit(calibrate if what == "calibrate" else forward)


def _key(net: dict) -> str:
    return json.dumps({k: net[k] for k in ("scale", "nc", "task", "spatial")},
                      sort_keys=True)


def make_weights(net: dict, seed: int) -> Dict[str, Tuple[np.ndarray, np.ndarray]]:
    """The executable's weights by Ultralytics module path, (HWIO kernel,
    bias) in float32, by its documented recipe: each convolution draws
    from ``default_rng((seed, crc32(path)))`` a kernel N(0, 1/fan_in) and
    then a bias N(0, ``BIAS_STD``^2) (the class logits' bias is zero).
    BatchNorm is folded with statistics of one pass over a calibration
    batch (``CAL_BATCH`` standard normal inputs at the network's size from
    ``default_rng((seed, crc32("calibration")))``): each Conv's kernel
    times ``BN_GAMMA`` over the per-image deviation of its unbiased output
    (averaged over the batch), and its bias plus ``BN_SHIFT`` less each
    channel's mean times the same factor; each head Conv2d and the
    ConvTranspose over the RMS of their unbiased output. Computed on the
    default device in float32, its convolutions at the device's default
    precision: only statistics come from them, and the pass compiles in
    half the time of one at ``HIGHEST``."""
    raw = {}
    for lay in layers(net):
        if not lay["k"]:
            continue
        path, op, k, cin = lay["path"], lay["op"], lay["k"], lay["cin"]
        cout = lay["shape"][2]
        rng = np.random.default_rng((seed, zlib.crc32(path.encode())))
        fan = cin if op == "convT" else k * k * cin
        w = rng.standard_normal((k, k, cin, cout), dtype=np.float32) \
            / np.float32(math.sqrt(fan))
        b = rng.standard_normal((cout,), dtype=np.float32) \
            * np.float32(BIAS_STD)
        if op == "conv2d" and ".cv3." in path:
            b = np.zeros((cout,), np.float32)
        raw[path] = (w, b)
    size = net["spatial"]
    rng = np.random.default_rng((seed, zlib.crc32(b"calibration")))
    x = rng.standard_normal((CAL_BATCH, size, size, 3), dtype=np.float32)
    folded = _jitted(_key(net), "calibrate")(raw, x)
    return {path: (np.asarray(w, np.float32), np.asarray(b, np.float32))
            for path, (w, b) in folded.items()}


def reference_forward(net: dict, weights: Dict[str, Tuple[np.ndarray,
                                                            np.ndarray]],
                      x: np.ndarray, mode: str = "f32") -> List[np.ndarray]:
    """Every sink on input ``x``, in the program's sink order (the
    detections, then for ``segment`` the prototypes), on the default device
    in float32 at ``Precision.HIGHEST``, layer by layer as one jitted pass
    (``mode="fp8"``: each convolution's operands rounded to float8 e4m3
    first)."""
    sinks = _jitted(_key(net), mode)(weights, x)
    return [np.asarray(v, np.float32) for v in sinks]


def components(sinks: Sequence[np.ndarray]) -> Dict[str, np.ndarray]:
    """A network's sinks split into what ``correct`` compares one by one,
    each less the constant it sits on, so that the constant cannot dilute
    a gap: ``boxes`` (the centre less its anchor point times the stride;
    width and height less 15 strides, the size at which every side sits at
    its middle bin), ``classes`` (probabilities less one half, the
    sigmoid's midpoint) and, for ``segment``, ``masks`` (coefficients) and
    ``prototypes``. The layout is read from the sinks: a second sink is
    the prototypes, and the anchors' count gives the input size."""
    det = np.asarray(sinks[0], np.float64)
    segment = len(sinks) == 2
    nc = det.shape[-1] - 4 - (NM if segment else 0)
    # anchors = (s/8)^2 * (1 + 1/4 + 1/16) for s a multiple of 32
    spatial = int(round(8 * math.sqrt(det.shape[1] * 16 / 21)))
    grid, stride = anchor_grid(spatial)
    if len(grid) != det.shape[1]:
        raise ValueError(f"no input size has {det.shape[1]} anchors")
    out = {"boxes": np.concatenate(
               [det[..., :2] - grid * stride,
                det[..., 2:4] - (REG_MAX - 1) * stride], -1),
           "classes": det[..., 4:4 + nc] - 0.5}
    if segment:
        out["masks"] = det[..., 4 + nc:]
        out["prototypes"] = np.asarray(sinks[1], np.float64)
    return out


def rel_l2(out: np.ndarray, ref: np.ndarray) -> float:
    """||out - ref|| / ||ref||, in float64."""
    out = np.asarray(out, np.float64)
    ref = np.asarray(ref, np.float64)
    return float(np.linalg.norm(out - ref) / np.linalg.norm(ref))
