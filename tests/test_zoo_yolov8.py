"""The published YOLOv8n and FastSAM-s: counts against Ultralytics, the
benchmark's independent layer table, and the program against its plain
float32 reference at 64 x 64 with the published widths, whole, cut and
served by ``PuzzleRuntime``."""
import importlib.util
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.core import Solution, mobile_processors
from repro.runtime import PuzzleRuntime
from repro.zoo.yolov8 import PUBLISHED, YoloV8, layer_table, make_graph

ROOT = Path(__file__).resolve().parents[1]
SPATIAL = 64
#: bfloat16 keeps 8 significant bits, so each rounded operand or result is
#: off by up to 2^-9; the networks chain ~40 layers between input and sink,
#: and the gap of a sink measured 0.2-2% at this size (host CPU). 5% leaves
#: room for other seeds while a float8 run (~10-30%) would fail.
BF16_TOL = 0.05


def _bench_yolov8():
    """``bench/yolov8.py``, the benchmark's restatement (imports nothing
    of the program)."""
    spec = importlib.util.spec_from_file_location(
        "bench_yolov8_restatement", ROOT / "bench" / "yolov8.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _net(name, spatial):
    scale, nc, task = PUBLISHED[name]
    return dict(scale=scale, nc=nc, task=task, spatial=spatial)


def _rel_l2(out, ref):
    out, ref = np.asarray(out, np.float64), np.asarray(ref, np.float64)
    return float(np.linalg.norm(out - ref) / np.linalg.norm(ref))


@pytest.fixture(scope="module")
def models():
    return {name: YoloV8.published(name, spatial=SPATIAL, seed=3)
            for name in PUBLISHED}


@pytest.fixture(scope="module")
def references(models):
    return {name: m.reference_forward() for name, m in models.items()}


# -- counts --------------------------------------------------------------------


@pytest.mark.parametrize("name,nc,convs,macs,params", [
    ("yolov8n", 80, 63, 4_371_456_000, 3_151_888),
    ("fastsam_s", 1, 76, 19_957_606_400, 11_779_971),
    # yolov8s-seg with 80 classes: Ultralytics' fused count, 11,810,560,
    # adds DFL's 16 fixed weights
    ("fastsam_s", 80, 76, None, 11_810_544),
])
def test_counts_match_ultralytics(name, nc, convs, macs, params):
    scale, _, task = PUBLISHED[name]
    ops = layer_table(scale, nc, task, 640)
    assert sum(o.op in ("conv", "conv2d", "convT") for o in ops) == convs
    assert sum(o.params for o in ops) == params
    graph = make_graph(name, ops, 640)
    assert graph.total_param_bytes == 4 * params
    if macs is not None:
        assert graph.total_macs == macs


@pytest.mark.parametrize("spatial", [64, 640])
@pytest.mark.parametrize("name", sorted(PUBLISHED))
def test_layers_match_the_benchmarks_restatement(name, spatial):
    bench = _bench_yolov8()
    net = _net(name, spatial)
    ours = layer_table(net["scale"], net["nc"], net["task"], spatial)
    theirs = bench.layers(net)
    assert [o.path for o in ours] == [t["path"] for t in theirs]
    assert [o.op for o in ours] == [t["op"] for t in theirs]
    assert [list(o.inputs) for o in ours] == [t["src"] for t in theirs]
    assert [o.macs for o in ours] == [t["macs"] for t in theirs]
    assert [o.shape[-3:] for o in ours] == [tuple(t["shape"]) for t in theirs]
    assert sum(o.params for o in ours) == bench.parameter_count(net)


def test_layer_names_and_merkle_keys_are_distinct():
    for name in PUBLISHED:
        scale, nc, task = PUBLISHED[name]
        g = make_graph(name, layer_table(scale, nc, task, 640), 640)
        assert len({layer.name for layer in g.layers}) == g.num_layers
        assert len({layer.leaf_hash() for layer in g.layers}) == g.num_layers
        assert g.layers[2].name == "model.2.cv1"
        assert any(layer.name == "model.2.m.0.cv1" for layer in g.layers)


@pytest.mark.parametrize("name,shapes", [
    ("yolov8n", [(1, 8400, 84)]),
    ("fastsam_s", [(1, 8400, 37), (1, 160, 160, 32)])])
def test_sink_shapes_at_640(name, shapes):
    """Both sinks at Table 6's input, from the layer table and from the
    whole network's program traced abstractly (no weights drawn)."""
    scale, nc, task = PUBLISHED[name]
    ops = layer_table(scale, nc, task, 640)
    zeros = {o.path: tuple(np.zeros(s, np.float32) for s in o.weight_shapes)
             for o in ops if o.weight_shapes}
    m = YoloV8(name, scale, nc, task, 640, weights=zeros,
               x=np.zeros((1, 640, 640, 3), np.float32))
    assert [ops[s].shape for s in m.graph.sinks()] == shapes
    fn, example = m.build_subgraph_fn(range(len(ops)), "int8")
    out = jax.eval_shape(fn, *example)
    out = out if isinstance(out, tuple) else (out,)
    assert [o.shape for o in out] == shapes


# -- activations of the seeded weights ------------------------------------------


@pytest.mark.parametrize("name", sorted(PUBLISHED))
def test_activations_stay_order_one(name, models):
    """Every layer's output on the seeded input has an RMS within [0.25, 4],
    and the class probabilities are spread (no sigmoid saturates)."""
    import jax.numpy as jnp

    m = models[name]
    vals = {}
    for lid in range(m.graph.num_layers):
        ins = [vals[e.src] for e in m.graph.in_edges[lid]] or \
            [jnp.asarray(m.model_input())]
        vals[lid] = m.apply_layer(lid, ins, jnp.float32)
    for lid, o in enumerate(m.ops):
        if o.op != "decode":
            rms = float(jnp.sqrt(jnp.mean(jnp.square(vals[lid]))))
            assert 0.25 <= rms <= 4.0, (o.path, rms)
    (decode,) = [i for i, o in enumerate(m.ops) if o.op == "decode"]
    probs = np.asarray(vals[decode])[0, :, 4:4 + m.nc]
    assert probs.std() > 0.05
    assert 0.02 < probs.min() and probs.max() < 0.98


# -- the program against the reference -------------------------------------------


def _run(model, parts, dtype):
    """Run a network cut into ``parts`` (lists of layer ids, in order),
    each a jitted subgraph program fed from the outputs of the parts
    before it; the sinks in ``graph.sinks()`` order."""
    outs = {}
    for ids in parts:
        fn, example = model.build_subgraph_fn(ids, dtype)
        inputs, returned = model.boundary(ids)
        args = [example[k] if src < 0 else outs[src].astype(example[k].dtype)
                for k, (src, _) in enumerate(inputs)]
        got = jax.jit(fn)(*args)
        got = got if isinstance(got, tuple) else (got,)
        outs.update(zip(returned, got))
    return [np.asarray(outs[s], np.float32) for s in model.graph.sinks()]


def _cut(model, last_path):
    """Two parts: every layer up to ``last_path``, and the rest."""
    (k,) = [i for i, o in enumerate(model.ops) if o.path == last_path]
    n = model.graph.num_layers
    return [list(range(k + 1)), list(range(k + 1, n))]


CUTS = {"whole": None,
        # the neck reads P3, P4 and P5 from the backbone
        "backbone_neck": "model.9.cv2",
        # the rest reads cv1's whole output and its split half
        "inside_c2f": "model.2.split"}


@pytest.mark.parametrize("dtype,tol", [("fp32", 1e-5), ("fp16", BF16_TOL)],
                         ids=["fp32", "bf16"])
@pytest.mark.parametrize("cut", sorted(CUTS))
@pytest.mark.parametrize("name", sorted(PUBLISHED))
def test_program_matches_reference(name, cut, dtype, tol, models,
                                   references):
    m = models[name]
    parts = ([list(range(m.graph.num_layers))] if CUTS[cut] is None
             else _cut(m, CUTS[cut]))
    if cut == "backbone_neck":
        srcs = {src for src, _ in m.boundary(parts[1])[0]}
        assert len(srcs) == 3
        assert len({m.output_shape(s) for s in srcs}) == 3
    if cut == "inside_c2f":
        srcs = {src for src, _ in m.boundary(parts[1])[0]}
        assert len({m.output_shape(s) for s in srcs}) == 2
    got = _run(m, parts, dtype)
    ref = references[name]
    assert [g.shape for g in got] == [r.shape for r in ref]
    for g, r in zip(got, ref):
        assert _rel_l2(g, r) <= tol


def test_served_cut_schedule_matches_reference(models, references):
    """``PuzzleRuntime`` serving FastSAM-s cut between backbone and neck
    onto two processors: both sinks equal the reference."""
    m = models["fastsam_s"]
    g = m.graph
    back, _ = _cut(m, "model.9.cv2")
    cut = [int((e.src in back) != (e.dst in back)) for e in g.edges]
    mapping = [2 if lid in back else 1 for lid in range(g.num_layers)]
    sol = Solution(partition=[cut], mapping=[mapping], priority=[0],
                   dtype=[0], backend=[0])
    with PuzzleRuntime([g], sol, mobile_processors(), {g.name: m}) as rt:
        assert [p.processor for p in rt.placed[0]] == [2, 1]
        st = rt.infer_sync([0])
        sinks = []
        for s in g.sinks():
            (k,) = [k for k, p in enumerate(rt.placed[0])
                    if s in p.subgraph.layer_ids]
            out = st.outputs[(0, k)]
            ix = m.boundary(rt.placed[0][k].subgraph.layer_ids)[1].index(s)
            sinks.append(out[ix] if isinstance(out, tuple) else out)
    ref = references["fastsam_s"]
    assert len(sinks) == len(ref) == 2
    for got, want in zip(sinks, ref):
        assert _rel_l2(got, want) <= 1e-5


def test_weights_follow_the_restatements_recipe():
    """The program's seeded weights are the benchmark's, drawn by the same
    recipe from the same seed."""
    bench = _bench_yolov8()
    net = _net("yolov8n", SPATIAL)
    theirs = bench.make_weights(net, 4)
    ours = YoloV8.published("yolov8n", spatial=SPATIAL, seed=4).weights
    assert sorted(theirs) == sorted(ours)
    for path, (w, b) in theirs.items():
        np.testing.assert_allclose(ours[path][0], w, rtol=1e-3, atol=1e-5)
        np.testing.assert_allclose(ours[path][1], b, rtol=1e-3, atol=1e-5)
