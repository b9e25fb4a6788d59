"""Puzzle Runtime: coordinator/worker/engine behaviour + §5.3 optimizations.

Scheduling-behaviour tests run in **virtual-clock mode** — deterministic,
instant, no ``time.sleep`` and no wall-clock-dependent assertions — while
real-execution tests (engine agreement, tensor pool, measured costs) keep
exercising the threaded path but assert only on counts and values, never on
timing.
"""
import dataclasses
import random
import threading

import numpy as np
import pytest

from repro.core import (
    PAPER_COMM_MODEL,
    FaultSpec,
    Profiler,
    Solution,
    SolutionFactory,
    build_spec,
    decode_solution,
    mobile_processors,
)
from repro.core.profiler import AnalyticMobileBackend
from repro.core.simulator import NoiseModel
from repro.core.fastsim import FastSimulator
from repro.core.graph import branching_graph, chain_graph
from repro.runtime import (
    PuzzleRuntime,
    RuntimeConfig,
    TensorPool,
    SharedBufferTransport,
    VirtualClock,
    make_engine,
    runtime_result,
)
from repro.zoo import executable_zoo

PROCS = mobile_processors()
PROFILER = Profiler(AnalyticMobileBackend(PROCS))


@pytest.fixture(scope="module")
def zoo():
    return executable_zoo(names=["face_det", "selfie_seg"], channels=4, spatial=8)


def _solution(graphs, split_first=True):
    g0, g1 = graphs
    part0 = [0] * g0.num_edges
    if split_first:
        # cut the last chain edge: the final layers form a second subgraph
        part0[g0.num_layers - 2] = 1
    return Solution(
        partition=[part0, [0] * g1.num_edges],
        mapping=[[2] * (g0.num_layers - 1) + [1], [0] * g1.num_layers],
        priority=[0, 1],
        dtype=[0, 0],
        backend=[0, 0],
    )


def _virtual_runtime(nets, sol, noise=None, dispatch=0.0):
    spec = build_spec(decode_solution(sol, nets), PROCS, PROFILER,
                      PAPER_COMM_MODEL)
    rt = PuzzleRuntime(
        nets, sol, PROCS,
        config=RuntimeConfig(virtual=True, noise=noise,
                             dispatch_overhead=dispatch),
        spec=spec,
    )
    return rt, spec


def _random_nets():
    return [
        chain_graph("vx", [("conv", 4e6, 1000, 4000)] * 5),
        branching_graph("vy", [("conv", 2e6, 800, 2000)] * 4,
                        [(0, 1), (0, 2), (1, 3), (2, 3)]),
    ]


# -- virtual-clock scheduling behaviour (deterministic, no wall clock) -------

def test_virtual_end_to_end_inference():
    nets = _random_nets()
    sol = SolutionFactory(nets, num_processors=len(PROCS),
                          rng=random.Random(3)).random_solution()
    rt, _ = _virtual_runtime(nets, sol)
    with rt:
        st = rt.infer_sync([0, 1])
        assert st.makespan is not None and st.makespan > 0
        placed = decode_solution(sol, nets)
        assert len(st.task_records) == sum(len(p) for p in placed)
        # virtual time advanced, and deterministically so
        assert rt.clock.now() == st.finish


def test_virtual_cross_processor_dependency_order():
    """The consumer subgraph must start only after its producer finishes."""
    nets = _random_nets()
    sol = SolutionFactory(nets, num_processors=len(PROCS),
                          rng=random.Random(5)).random_solution()
    rt, _ = _virtual_runtime(nets, sol)
    with rt:
        rt.infer_sync([0, 1])
        trace = rt.coordinator.trace
        finished = {}
        for rec in trace:
            finished[(rec.network, rec.sg_index)] = rec.finished
        deps = rt.coordinator._deps
        for rec in trace:
            for producer in deps[rec.network][rec.sg_index]:
                assert rec.started >= finished[(rec.network, producer)]


def test_virtual_periodic_requests_all_complete():
    nets = _random_nets()
    sol = SolutionFactory(nets, num_processors=len(PROCS),
                          rng=random.Random(7)).random_solution()
    rt, _ = _virtual_runtime(nets, sol)
    with rt:
        res = rt.run_periodic([[0], [1]], [0.02, 0.03], num_requests=4)
        assert len(res) == 2
        for glist in res:
            assert len(glist) == 4
            for st in glist:
                assert st.makespan is not None
        # request sources fired at exactly rid × period (virtual time)
        for gid, period in enumerate([0.02, 0.03]):
            for rid, st in enumerate(res[gid]):
                assert st.submitted == rid * period


def test_virtual_runtime_matches_fastsim():
    """Virtual-clock execution is bit-identical to the fast simulator."""
    nets = _random_nets()
    sol = SolutionFactory(nets, num_processors=len(PROCS),
                          rng=random.Random(11)).random_solution()
    groups, periods, nr = [[0], [1]], [0.004, 0.006], 6
    noise = NoiseModel(seed=4)
    rt, spec = _virtual_runtime(nets, sol, noise=noise, dispatch=150e-6)
    with rt:
        states = rt.run_periodic(groups, periods, num_requests=nr)
        got = runtime_result(rt, states, periods, nr)
    want = FastSimulator(
        spec, groups=groups, periods=periods, num_requests=nr,
        noise=noise, dispatch_overhead=150e-6,
    ).run(collect_tasks=True)
    assert [(t.network, t.sg_index, t.released, t.started, t.finished,
             t.exec_time) for t in got.tasks] == \
           [(t.network, t.sg_index, t.released, t.started, t.finished,
             t.exec_time) for t in want.tasks]
    assert got.busy_time == want.busy_time
    assert [r.makespan for r in got.requests] == \
           [r.makespan for r in want.requests]


def test_virtual_runtime_is_deterministic():
    nets = _random_nets()
    sol = SolutionFactory(nets, num_processors=len(PROCS),
                          rng=random.Random(13)).random_solution()
    traces = []
    for _ in range(2):
        rt, _ = _virtual_runtime(nets, sol, noise=NoiseModel(seed=9))
        with rt:
            states = rt.run_periodic([[0, 1]], [0.01], num_requests=5)
            traces.append([
                (t.network, t.sg_index, t.released, t.started, t.finished)
                for t in rt.coordinator.trace
            ])
            assert all(st.makespan is not None for st in states[0])
    assert traces[0] == traces[1]


def test_virtual_clock_event_ordering():
    clock = VirtualClock()
    fired = []
    clock.schedule(0.5, lambda: fired.append("b"))
    clock.schedule(0.5, lambda: fired.append("c"))  # same time: push order
    clock.schedule(0.1, lambda: fired.append("a"))
    clock.schedule(2.0, lambda: fired.append("past-horizon"))
    clock.run(until=1.0)
    assert fired == ["a", "b", "c"]
    assert clock.now() == 0.5
    assert clock.pending == 1


def test_close_during_injected_fault_names_the_fault():
    """Closing a virtual runtime whose requests were stranded by an
    injected dropout must fail the pending futures with an error *naming
    the fault* — not a bare close sentinel — join every worker thread and
    drain every queue."""
    nets = _random_nets()
    sol = None
    for seed in range(64):
        cand = SolutionFactory(nets, num_processors=len(PROCS),
                               rng=random.Random(seed)).random_solution()
        if any(p.processor == 2 for pl in decode_solution(cand, nets)
               for p in pl):
            sol = cand
            break
    assert sol is not None
    faults = FaultSpec(dropouts=((2, 0.008, None),), seed=3)
    spec = build_spec(decode_solution(sol, nets), PROCS, PROFILER,
                      PAPER_COMM_MODEL)
    rt = PuzzleRuntime(
        nets, sol, PROCS,
        config=RuntimeConfig(virtual=True, faults=faults),
        spec=spec,
    )
    states = rt.run_periodic([[0, 1]], [0.004], num_requests=8)
    stranded = [st for st in states[0] if not st.future.done()]
    assert stranded, "the dropout must strand at least one request"
    rt.close()
    for st in stranded:
        with pytest.raises(RuntimeError, match=r"processor 2 dropped at "
                                               r"t=0\.008"):
            st.future.result(timeout=0)
    assert not any(w.threads_alive() for w in rt.workers.values())
    for w in rt.workers.values():
        assert not w._vstore
        assert w._queue.empty() and w._exec_queue.empty()
    rt.close()  # idempotent


# -- lifecycle: close(), thread leaks, abandoned requests --------------------

def test_close_joins_all_worker_threads(zoo):
    graphs = [zoo["face_det"].graph, zoo["selfie_seg"].graph]
    rt = PuzzleRuntime(graphs, _solution(graphs), mobile_processors(), zoo)
    threads = [t for w in rt.workers.values()
               for t in (w._quant_thread, w._exec_thread)]
    assert all(t.is_alive() for t in threads)
    rt.infer_sync([0, 1])
    rt.close()
    assert all(not t.is_alive() for t in threads)
    assert not any(w.threads_alive() for w in rt.workers.values())
    rt.close()  # idempotent


def test_close_mid_request_fails_pending_futures(zoo):
    """Abandoning a runtime mid-request must not leak threads or hang."""
    graphs = [zoo["face_det"].graph, zoo["selfie_seg"].graph]
    rt = PuzzleRuntime(graphs, _solution(graphs), mobile_processors(), zoo)
    states = [rt.infer([0, 1]) for _ in range(8)]
    rt.close()  # queues may still hold tasks: the stop sentinel outranks them
    assert not any(w.threads_alive() for w in rt.workers.values())
    for st in states:
        # either completed before the stop sentinel won the queue race,
        # or failed with the close error — never left hanging
        assert st.future.done()
    with pytest.raises(RuntimeError):
        rt.infer([0, 1])


def test_context_manager_closes(zoo):
    graphs = [zoo["face_det"].graph, zoo["selfie_seg"].graph]
    with PuzzleRuntime(graphs, _solution(graphs), mobile_processors(),
                       zoo) as rt:
        st = rt.infer_sync([0, 1])
        assert st.makespan is not None
    assert not any(w.threads_alive() for w in rt.workers.values())


def test_worker_stop_with_queued_tasks_regression(zoo):
    """stop() with a non-empty priority queue used to raise TypeError
    (None unorderable vs WorkerTask) and leak both threads."""
    graphs = [zoo["face_det"].graph]
    g = graphs[0]
    sol = Solution(partition=[[0] * g.num_edges], mapping=[[0] * g.num_layers],
                   priority=[0], dtype=[0], backend=[0])
    rt = PuzzleRuntime(graphs, sol, mobile_processors(), zoo)
    w = rt.workers[0]
    # pile tasks into the queue faster than they can drain, then stop
    for _ in range(32):
        rt.infer([0])
    rt.close()
    assert not w.threads_alive()


def test_no_leaked_threads_across_many_runtimes(zoo):
    graphs = [zoo["face_det"].graph, zoo["selfie_seg"].graph]
    base = threading.active_count()
    for _ in range(3):
        with PuzzleRuntime(graphs, _solution(graphs), mobile_processors(),
                           zoo) as rt:
            rt.infer_sync([0, 1])
    assert threading.active_count() <= base


# -- real execution: engines, memory optimizations ---------------------------

def test_end_to_end_inference(zoo):
    graphs = [zoo["face_det"].graph, zoo["selfie_seg"].graph]
    with PuzzleRuntime(graphs, _solution(graphs), mobile_processors(),
                       zoo) as rt:
        st = rt.infer_sync([0, 1])
        assert st.makespan is not None
        # face_det split into 2 subgraphs + selfie 1
        assert len(st.task_records) == 3
        out = st.outputs
        assert all(not np.any(np.isnan(np.asarray(v, np.float32)))
                   for v in out.values() if not isinstance(v, tuple))


def test_cross_processor_dependency_order(zoo):
    """Subgraph 2 (GPU) must consume subgraph 1's (NPU) output."""
    graphs = [zoo["face_det"].graph]
    g = graphs[0]
    sol = Solution(
        partition=[[1 if i == g.num_layers - 2 else 0 for i in range(g.num_edges)]],
        mapping=[[2] * (g.num_layers - 1) + [1]],
        priority=[0], dtype=[0], backend=[0],
    )
    with PuzzleRuntime(graphs, sol, mobile_processors(), zoo) as rt:
        st = rt.infer_sync([0])
        recs = {r["sg"]: r for r in st.task_records}
        assert set(recs) == {0, 1}


def test_measured_costs_keyed_by_profile_key(zoo):
    """Real execution produces per-Merkle-key medians for the feedback loop."""
    graphs = [zoo["face_det"].graph, zoo["selfie_seg"].graph]
    sol = _solution(graphs)
    with PuzzleRuntime(graphs, sol, mobile_processors(), zoo) as rt:
        for _ in range(3):
            rt.infer_sync([0, 1])
        costs = rt.measured_costs()
    placed = decode_solution(sol, graphs)
    expected_keys = {p.profile_key() for plist in placed for p in plist}
    assert set(costs) == expected_keys
    assert all(t > 0 for t in costs.values())


def test_tensor_pool_reuse():
    pool = TensorPool(enabled=True)
    a = pool.acquire((16, 16), np.float32)
    pool.release(a)
    b = pool.acquire((8, 8), np.float32)   # smaller fits the same chunk? no:
    # different rounded size -> fresh alloc; same size -> reuse
    pool.release(b)
    c = pool.acquire((16, 16), np.float32)
    assert pool.stats.reuses >= 1
    assert pool.stats.mallocs <= 2
    c[:] = 1.0  # usable memory


def test_tensor_pool_disabled_always_allocates():
    pool = TensorPool(enabled=False)
    a = pool.acquire((16,), np.float32)
    pool.release(a)
    pool.acquire((16,), np.float32)
    assert pool.stats.mallocs == 2
    assert pool.stats.reuses == 0


def test_shared_buffer_zero_copy():
    pool = TensorPool()
    t_zero = SharedBufferTransport(pool, zero_copy=True)
    t_copy = SharedBufferTransport(pool, zero_copy=False)
    src = np.ones((64,), np.float32)
    out_zero = t_zero.transfer(src)
    assert out_zero is src
    out_copy = t_copy.transfer(src)
    assert out_copy is not src
    np.testing.assert_array_equal(np.asarray(out_copy), src)
    assert t_copy.stats.staged_bytes == src.nbytes


def test_engines_agree(zoo):
    """All backends compute the same function (different kernel profiles)."""
    from repro.core import whole_model_placement
    g = zoo["face_det"].graph
    placed = whole_model_placement(g, 0, 0, 0, 0)
    outs = {}
    for name in ("default", "xnnpack", "nnapi"):
        eng = make_engine(name)
        key = eng.load(placed, zoo)
        outs[name] = np.asarray(eng.execute(key), np.float32)
        assert key in eng.exec_times and len(eng.exec_times[key]) == 1
    np.testing.assert_allclose(outs["default"], outs["nnapi"], rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(outs["default"], outs["xnnpack"], rtol=1e-2, atol=1e-3)


def test_ablation_pool_reduces_mallocs(zoo):
    """Table 5 direction: tensor pool cuts allocation counts."""
    graphs = [zoo["face_det"].graph, zoo["selfie_seg"].graph]
    sol = _solution(graphs)
    sol = Solution(
        partition=sol.partition, mapping=sol.mapping, priority=sol.priority,
        dtype=[0, 1], backend=[0, 0],   # dtype boundary forces staging copies
    )
    counts = {}
    for pool_on in (False, True):
        with PuzzleRuntime(
            graphs, sol, mobile_processors(), zoo,
            RuntimeConfig(tensor_pool=pool_on, shared_buffer=False),
        ) as rt:
            for _ in range(6):
                rt.infer_sync([0, 1])
            counts[pool_on] = rt.stats()["pool"]["mallocs"]
    assert counts[True] <= counts[False]


def _face_split(g):
    """face_det in three subgraphs on three processors; both skip edges
    (1->4, 6->9) and the chain cross subgraph boundaries."""
    cut = {(2, 3), (1, 4), (7, 8), (6, 9)}
    part = [1 if (e.src, e.dst) in cut else 0 for e in g.edges]
    mapping = [0] * 3 + [1] * 5 + [2] * 4
    return part, mapping


def _sink_output(st, placed, model, net=0):
    sink = model.graph.num_layers - 1
    for k, p in enumerate(placed[net]):
        if sink in p.subgraph.layer_ids:
            out = st.outputs[(net, k)]
            ix = model.boundary(p.subgraph.layer_ids)[1].index(sink)
            return np.asarray(out[ix] if isinstance(out, tuple) else out,
                              np.float32)
    raise AssertionError("no subgraph holds the sink")


def _rel_l2(out, ref):
    return float(np.linalg.norm(out - ref) / np.linalg.norm(ref))


@pytest.mark.parametrize("dtype,tol", [(0, 1e-5), (1, 5e-2)],
                         ids=["fp32", "fp16"])
def test_real_outputs_match_reference_forward(zoo, dtype, tol):
    """A network split across processors computes what the whole network
    computes: every subgraph argument is routed from the layer it reads."""
    model = zoo["face_det"]
    part, mapping = _face_split(model.graph)
    sol = Solution(partition=[part], mapping=[mapping], priority=[0],
                   dtype=[dtype], backend=[0])
    with PuzzleRuntime([model.graph], sol, PROCS, zoo) as rt:
        assert len(rt.placed[0]) == 3
        st = rt.infer_sync([0])
        out = _sink_output(st, rt.placed, model)
    assert _rel_l2(out, model.reference_forward()) <= tol


def test_cross_dtype_boundary_compiles_nothing_after_load(zoo):
    """An fp32 producer feeding fp16 consumers: staged inputs take the
    dtype the consumer's handle was warmed with, so serving adds no jit
    cache entry after load."""
    from repro.runtime.coordinator import Coordinator
    from repro.runtime.engine import ENGINE_REGISTRY
    from repro.runtime.worker import Worker

    model = zoo["face_det"]
    part, mapping = _face_split(model.graph)
    sol = Solution(partition=[part], mapping=[mapping], priority=[0],
                   dtype=[0], backend=[0])
    placed = decode_solution(sol, [model.graph])
    placed[0][1:] = [dataclasses.replace(p, dtype="fp16")
                     for p in placed[0][1:]]
    pool = TensorPool()
    transport = SharedBufferTransport(pool)
    coord = None
    workers = {
        p.pid: Worker(
            p.pid, p.name, {n: make_engine(n) for n in ENGINE_REGISTRY},
            pool, transport,
            lambda *a: coord.on_task_done(*a),
            on_start=lambda payload: coord.on_task_start(payload))
        for p in PROCS
    }
    coord = Coordinator(placed, workers, zoo)
    handles = [eng._handles[key][0] for w in workers.values()
               for eng in w.engines.values() for key in eng._handles]
    sizes = [h._cache_size() for h in handles]
    for w in workers.values():
        w.start()
    try:
        st = coord.submit([0])
        st.future.result(timeout=60)
    finally:
        for w in workers.values():
            w.stop()
    assert [h._cache_size() for h in handles] == sizes
    assert pool.stats.memcpy_calls == 2  # the two fp32->fp16 inputs
    out = _sink_output(st, placed, model)
    assert _rel_l2(out, model.reference_forward()) <= 5e-2
