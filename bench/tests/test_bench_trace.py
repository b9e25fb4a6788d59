"""The reduction from a profiler trace to busy time, programs and idle
gaps, on stand-in planes and on a small trace recorded on a TPU v5e."""
import gzip
from pathlib import Path
from types import SimpleNamespace as NS

import pytest

import tracing

#: 0.2 s of the ar5_synth serving cell traced on one TPU v5e by ``tracing.Tracer``;
#: its host spans sit on one of several host lines that share a name.
RECORDED = Path(__file__).resolve().parents[1] / "testdata" / \
    "serve_ar5_small.xplane.pb.gz"


def ev(name, start, end):
    return NS(name=name, start_ns=start, duration_ns=end - start)


def plane(name, **lines):
    return NS(name=name, lines=[NS(name=k.replace("_", " "), events=v)
                                for k, v in lines.items()])


def test_merge_clip_gaps():
    assert tracing.merge([(5, 9), (0, 2), (1, 3), (9, 10)]) == [(0, 3),
                                                                (5, 10)]
    assert tracing.clip([(0, 3), (5, 10)], 2, 7) == [(2, 3), (5, 7)]
    assert tracing.gaps([(2, 3), (5, 7)], 0, 10) == [(0, 2), (3, 5),
                                                     (7, 10)]
    assert tracing.gaps([], 0, 4) == [(0, 4)]


def test_reduce_planes():
    host = plane("/host:CPU", main=[
        ev("bench.window", 1000, 11000),
        ev("search.run_ga", 1000, 6000),
        ev("search.alpha", 6000, 11000)])
    dev = plane(
        "/device:TPU:0",
        XLA_Modules=[ev("jit_advance(123)", 2000, 4000),
                     ev("jit_advance(456)", 7000, 8000),
                     ev("jit_other(1)", 500, 1500)],
        XLA_Ops=[ev("%while.1", 2000, 3000), ev("%fusion.2", 2500, 4000),
                 ev("%while.1", 7000, 8000), ev("%x", 500, 1500)])
    sparse = plane("/device:TPU:0 SparseCore 0",
                   XLA_Ops=[ev("%y", 1000, 11000)])
    s = tracing.reduce_planes([host, dev, sparse])
    assert s.window_s == pytest.approx(10e-6)
    # ops 1000-1500 (clipped), 2000-4000, 7000-8000
    assert s.busy_s == pytest.approx(3.5e-6)
    # a program or operation counts its time inside the window only
    assert s.programs == {"jit_advance": pytest.approx(3e-6),
                          "jit_other": pytest.approx(0.5e-6)}
    assert s.ops["%x"] == pytest.approx(0.5e-6)
    assert s.program_seconds("advance") == pytest.approx(3e-6)
    assert s.ops["%while.1"] == pytest.approx(2e-6)
    # gaps 1500-2000 (in run_ga), 4000-7000 (2000 in run_ga, 1000 in
    # alpha) and 8000-11000 (in alpha), longest first
    got = [(k, round(v * 1e9)) for k, v in s.idle_gaps]
    assert sorted(got) == sorted([("search.run_ga", 500),
                                  ("search.run_ga", 3000),
                                  ("search.alpha", 3000)])
    assert s.idle_gaps[0][1] >= s.idle_gaps[-1][1]
    b = s.breakdown()
    assert set(b) == {"device_ops", "idle_gaps"}
    assert len(b["device_ops"]) <= 10


def test_reduce_needs_events():
    with pytest.raises(ValueError):
        tracing.reduce_planes([plane("/host:CPU", main=[])])


def test_window_span_is_required():
    host = plane("/host:CPU", main=[ev("other", 0, 100)])
    dev = plane("/device:TPU:0", XLA_Ops=[ev("%x", 50, 150)])
    with pytest.raises(ValueError, match="bench.window"):
        tracing.reduce_planes([host, dev])


def test_host_lines_sharing_a_name_all_count():
    host = NS(name="/host:CPU", lines=[
        NS(name="python3", events=[ev("bench.window", 0, 1000)]),
        NS(name="python3", events=[ev("serve.generator", 0, 1000)])])
    dev = plane("/device:TPU:0", XLA_Ops=[ev("%x", 100, 300)])
    s = tracing.reduce_planes([host, dev])
    assert s.window_s == pytest.approx(1e-6)
    assert {name for name, _ in s.idle_gaps} == {"serve.generator"}


def test_recorded_trace():
    import jax

    data = gzip.decompress(RECORDED.read_bytes())
    planes = jax.profiler.ProfileData.from_serialized_xspace(data).planes
    s = tracing.reduce_planes(planes)
    assert s.window_s == pytest.approx(0.20808, rel=1e-3)
    assert s.busy_s == pytest.approx(0.09968, rel=1e-3)
    assert set(s.programs) == {"jit_fn", "jit_wrapped"}
    # a program's span holds its operations and a little more
    assert s.busy_s <= sum(s.programs.values()) <= 1.01 * s.busy_s
    assert s.idle_gaps and all(name == "serve.generator"
                               for name, _ in s.idle_gaps)
    busy_and_idle = s.busy_s + sum(v for _, v in s.idle_gaps)
    assert busy_and_idle == pytest.approx(s.window_s, rel=1e-6)
