"""Program spans and counters: the span helper, lane occupancy of the
compiled core, the GA's and the serving path's spans, and the served
programs' names."""
import random
import re
import threading
import time
from collections import Counter
from pathlib import Path

import pytest

import repro.core.batchsim_compiled as bsc
from repro.core import (
    AnalyzerConfig,
    GAConfig,
    StaticAnalyzer,
    build_scenario,
    decode_solution,
    mobile_processors,
    run_batch_compiled,
)
from repro.core import ga
from repro.experiments.evaluate import EvalContext
from repro.runtime import PuzzleRuntime
from repro.runtime.engine import FastMathJitEngine, JitEngine
from repro.runtime.engine import totals as serve_totals
from repro.spans import span
from test_batchsim_compiled import PROCS, _make_lanes
from test_runtime import _solution

SRC = Path(__file__).resolve().parents[1] / "src" / "repro"

#: Spans the benchmark opens around the program: a program span must not
#: take one.
BENCHMARK_SPANS = {"bench.window", "search.run_ga", "search.alpha",
                   "serve.generator", "serve.drain"}


# -- the helper -------------------------------------------------------------


def test_span_counts_and_times():
    c = Counter()
    for _ in range(3):
        with span("puzzle.t", c):
            time.sleep(0.002)
    assert c["puzzle.t.n"] == 3
    assert c["puzzle.t.ns"] >= 3 * 2_000_000


def test_span_nests_as_self_time():
    """An inner span's time is taken out of the outer span's, so the self
    times add up to the outer wall time and nothing counts twice."""
    c = Counter()
    t0 = time.perf_counter_ns()
    with span("puzzle.outer", c):
        time.sleep(0.003)
        with span("puzzle.inner", c):
            time.sleep(0.05)
            with span("puzzle.inner", c):
                time.sleep(0.002)
    wall = time.perf_counter_ns() - t0
    assert c["puzzle.inner.n"] == 2 and c["puzzle.outer.n"] == 1
    assert c["puzzle.inner.ns"] >= 22_000_000
    assert 3_000_000 <= c["puzzle.outer.ns"] < 20_000_000
    assert c["puzzle.outer.ns"] + c["puzzle.inner.ns"] <= wall


def test_span_threads_keep_their_own_nesting():
    """A span on another thread is not an inner span of this thread's."""
    c = Counter()

    def other():
        with span("puzzle.other", c):
            time.sleep(0.03)

    with span("puzzle.outer", c):
        t = threading.Thread(target=other)
        t.start()
        t.join(timeout=10)
    assert not t.is_alive()
    assert c["puzzle.other.ns"] >= 30_000_000
    assert c["puzzle.outer.ns"] >= 30_000_000


def test_span_counts_no_update_lost_across_threads():
    import sys

    c = Counter()
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        def work():
            for _ in range(500):
                with span("puzzle.x", c):
                    pass
        threads = [threading.Thread(target=work) for _ in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
    finally:
        sys.setswitchinterval(old)
    assert not any(t.is_alive() for t in threads)
    assert c["puzzle.x.n"] == 8 * 500


def test_span_writes_a_trace_annotation(tmp_path):
    """While a profiler trace records, the span is on its host plane."""
    import jax

    jax.profiler.start_trace(str(tmp_path))
    try:
        with span("puzzle.traced", Counter()):
            jax.numpy.ones(4).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    path = sorted(tmp_path.glob("**/*.xplane.pb"))[-1]
    names = {e.name
             for plane in jax.profiler.ProfileData.from_file(str(path)).planes
             if plane.name.startswith("/host:")
             for line in plane.lines for e in line.events}
    assert "puzzle.traced" in names


def test_program_span_names():
    """Every span in the program is ``puzzle.*`` and none takes the name of
    a benchmark span, which name the trace's idle gaps."""
    names = set()
    for path in SRC.rglob("*.py"):
        names |= set(re.findall(r'\bspan\(\s*"([^"]+)"', path.read_text()))
    assert {"puzzle.batch.tables", "puzzle.batch.lanes", "puzzle.ga.run",
            "puzzle.ga.mate", "puzzle.ga.local", "puzzle.ga.select",
            "puzzle.serve.dispatch", "puzzle.serve.stage",
            "puzzle.serve.execute", "puzzle.serve.load"} <= names
    assert all(n.startswith("puzzle.") for n in names), names
    assert not names & BENCHMARK_SPANS


# -- lane occupancy of the compiled core ------------------------------------


@pytest.mark.parametrize("measured,arrivals_on,faults_on", [
    (False, False, False), (True, True, False), (True, False, True)],
    ids=["clean", "noisy_dispatch_arrivals", "noisy_dispatch_faults"])
def test_lane_events_match_lanes_run_alone(measured, arrivals_on, faults_on):
    """A one-lane batch's loop stops when that lane is done, so its
    iterations are that lane's events; a batch's ``lane_events`` is their
    sum, and ``lane_slots`` its iterations times the padded width."""
    lanes, groups = _make_lanes(random.Random(7), 5, measured, arrivals_on,
                                faults_on)
    alone = []
    for ln in lanes:
        assert run_batch_compiled([ln], groups, PROCS) is not None
        alone.append(bsc.last_stats["iters"])
    before = Counter(bsc.totals)
    assert run_batch_compiled(lanes, groups, PROCS) is not None
    iters = bsc.last_stats["iters"]
    assert bsc.totals["lane_events"] - before["lane_events"] == sum(alone)
    assert bsc.totals["lane_slots"] - before["lane_slots"] == iters * 16
    assert iters == max(alone)
    assert bsc.totals["puzzle.batch.tables.n"] == (
        before["puzzle.batch.tables.n"] + 1)


# -- the search's spans ------------------------------------------------------


def test_ga_spans_leave_evaluation_out():
    """The operators' spans hold their own work only: an evaluation slowed
    to 50 ms shows in ``puzzle.ga.eval`` and in no operator's span."""
    ctx = EvalContext()
    scenario = build_scenario("spans", [["face_det", "hand_det"]],
                              ctx.graphs)
    analyzer = StaticAnalyzer(
        scenario, ctx.processors, ctx.profiler, ctx.comm_model,
        AnalyzerConfig(ga=GAConfig(pop_size=6, min_generations=2,
                                   max_generations=2, seed=3,
                                   p_local=1.0)))
    objectives = analyzer.objectives
    slow = []

    def slowed(sol, *a, **kw):
        slow.append(1)
        time.sleep(0.05)
        return objectives(sol, *a, **kw)

    analyzer.objectives = slowed
    before = Counter(ga.totals)
    analyzer.run_ga(seeds=[analyzer.factory.random_solution()])
    d = Counter(ga.totals)
    d.subtract(before)
    assert d["puzzle.ga.run.n"] == 1
    assert d["puzzle.ga.mate.n"] == d["puzzle.ga.local.n"] == 2
    # two per generation and one for the final front
    assert d["puzzle.ga.select.n"] == 5
    assert d["puzzle.ga.eval.n"] == len(slow) > 0
    assert d["puzzle.ga.eval.ns"] >= 0.05e9 * len(slow)
    for name in ("run", "mate", "local", "select"):
        assert d[f"puzzle.ga.{name}.ns"] < 0.05e9, name


def test_batch_lanes_span_per_batch_call():
    ctx = EvalContext()
    scenario = build_scenario("spans", [["face_det", "hand_det"]],
                              ctx.graphs)
    analyzer = StaticAnalyzer(scenario, ctx.processors, ctx.profiler,
                              ctx.comm_model, AnalyzerConfig())
    sols = [analyzer.factory.random_solution() for _ in range(4)]
    before = bsc.totals["puzzle.batch.lanes.n"]
    analyzer.objectives_batch(sols)
    analyzer.score_batch([(s, 2.0) for s in sols])
    assert bsc.totals["puzzle.batch.lanes.n"] == before + 2


# -- the serving path ---------------------------------------------------------


@pytest.fixture(scope="module")
def zoo():
    from repro.zoo import executable_zoo

    return executable_zoo(names=["face_det", "selfie_seg"], channels=4,
                          spatial=8)


def test_serving_spans_count_every_task(zoo):
    graphs = [zoo["face_det"].graph, zoo["selfie_seg"].graph]
    before = Counter(serve_totals)
    with PuzzleRuntime(graphs, _solution(graphs), mobile_processors(),
                       zoo) as rt:
        for _ in range(3):
            rt.infer_sync([0, 1])
        stats = rt.stats()["spans"]
    tasks = 3 * 3   # face_det split in two, selfie_seg whole
    for name in ("puzzle.serve.dispatch", "puzzle.serve.stage",
                 "puzzle.serve.execute"):
        assert serve_totals[name + ".n"] - before[name + ".n"] == tasks
        assert stats[name]["n"] == serve_totals[name + ".n"]
        assert stats[name]["mean_us"] > 0.0


@pytest.mark.parametrize("dtype", [0, 1], ids=["fp32", "fp16"])
def test_load_span_counts_each_loaded_subgraph(zoo, dtype):
    """``puzzle.serve.load`` once per subgraph a runtime loads, and
    ``puzzle.serve.load.param_bytes`` the weight bytes of their programs at
    the served dtype."""
    graphs = [zoo["face_det"].graph, zoo["selfie_seg"].graph]
    sol = _solution(graphs)
    sol.dtype = [dtype, dtype]
    placed = decode_solution(sol, graphs)
    before = Counter(serve_totals)
    with PuzzleRuntime(graphs, sol, mobile_processors(), zoo):
        pass
    d = Counter(serve_totals)
    d.subtract(before)
    subgraphs = [p for pl in placed for p in pl]
    assert len(subgraphs) == 3
    assert d["puzzle.serve.load.n"] == 3
    assert d["puzzle.serve.load.ns"] > 0
    size = 4 if dtype == 0 else 2
    weights = sum(zoo[p.subgraph.graph.name]._weights[lid].size * size
                  for p in subgraphs for lid in p.subgraph.layer_ids
                  if lid in zoo[p.subgraph.graph.name]._weights)
    assert weights > 0
    assert d["puzzle.serve.load.param_bytes"] == weights


@pytest.mark.parametrize("engine", [JitEngine, FastMathJitEngine],
                         ids=["default", "xnnpack"])
def test_served_program_is_named_for_its_subgraph(zoo, engine):
    graphs = [zoo["face_det"].graph, zoo["selfie_seg"].graph]
    placed = decode_solution(_solution(graphs), graphs)[0]
    eng = engine()
    for p in placed:
        ids = p.subgraph.layer_ids
        fn, example = eng._handles[eng.load(p, zoo)]
        name = f"puzzle_face_det_{min(ids)}_{max(ids)}"
        assert f"@jit_{name}" in fn.lower(*example).as_text()
