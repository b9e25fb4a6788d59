"""Compiled-tier conformance: golden traces, differentials, fallbacks.

The jitted ``jax.lax.while_loop`` core (:mod:`repro.core.batchsim_compiled`)
is contractually *tolerance-bounded* against the bit-exact tiers:
``COMPILED_REL_TOL`` relative / ``COMPILED_ABS_TOL`` absolute per reported
float, integer fields (done counts) exact, and ``inf`` agreeing with
``inf``. These tests replay every committed golden trace and a
differential sweep (clean / measured / non-periodic arrivals / fault
ensembles) through the compiled tier against the numpy and fastsim tiers,
and pin the transparent-fallback contract of
``run_batch(engine="compiled")``. In practice the observed diff is exactly
0.0 on x86-64 (the tolerance is the contract, the zero is the
measurement); ``last_stats`` is asserted on so a silent numpy fallback
cannot masquerade as compiled coverage.
"""
import json
import math
import os
import random

import pytest

from repro.core import (
    COMPILED_ABS_TOL,
    COMPILED_REL_TOL,
    BatchLane,
    BatchSimulator,
    FastSimulator,
    FaultSpec,
    NoiseModel,
    PAPER_COMM_MODEL,
    SolutionFactory,
    build_spec,
    chain_graph,
    decode_solution,
    run_batch,
    run_batch_compiled,
)
import repro.core.batchsim_compiled as bsc
from test_batchsim_properties import (
    PROCS,
    PROFILER,
    _random_arrival,
    _random_problem,
)
from test_golden_traces import (
    GOLDEN_DIR,
    SCENARIOS,
    _nets_tri_chain,
    _solution,
)
from test_golden_traces import PROCS as GPROCS
from test_golden_traces import PROFILER as GPROFILER


def _close(a, b):
    """The documented compiled-tier tolerance, inf-aware."""
    if math.isinf(a) or math.isinf(b):
        return math.isinf(a) and math.isinf(b)
    return abs(a - b) <= COMPILED_ABS_TOL + COMPILED_REL_TOL * max(
        abs(a), abs(b))


def _assert_lane_close(ref_res, comp_res, tag):
    """Per-lane SimResult comparison under the tolerance contract."""
    assert ref_res.busy_time.keys() == comp_res.busy_time.keys(), tag
    for pid in ref_res.busy_time:
        assert _close(ref_res.busy_time[pid], comp_res.busy_time[pid]), (
            tag, "busy", pid)
    assert len(ref_res.requests) == len(comp_res.requests), tag
    for qa, qb in zip(ref_res.requests, comp_res.requests):
        assert qa.done_tasks == qb.done_tasks, (tag, qa, qb)
        assert qa.total_tasks == qb.total_tasks, (tag, qa, qb)
        assert _close(qa.arrival, qb.arrival), (tag, qa, qb)
        assert _close(qa.first_start, qb.first_start), (tag, qa, qb)
        assert _close(qa.last_finish, qb.last_finish), (tag, qa, qb)
        assert _close(qa.makespan, qb.makespan), (tag, qa, qb)


# -- golden traces ---------------------------------------------------------


def _golden_lane(name):
    (nets_fn, groups, periods, nr, noise_seed, dispatch, pin, arrivals,
     faults) = SCENARIOS[name]
    nets = nets_fn()
    sol = _solution(nets, seed=11, pin=pin)
    spec = build_spec(decode_solution(sol, nets), GPROCS, GPROFILER,
                      PAPER_COMM_MODEL)
    noise = NoiseModel(seed=noise_seed) if noise_seed is not None else None
    lane = BatchLane(spec=spec, periods=periods, num_requests=nr,
                     noise=noise, dispatch_overhead=dispatch,
                     arrivals=arrivals, faults=faults)
    return lane, groups


@pytest.mark.parametrize("name", sorted(SCENARIOS))
def test_compiled_reproduces_golden_trace(name):
    """Every committed golden trace replays through the compiled tier
    within the documented tolerance (done counts exact, inf == inf)."""
    lane, groups = _golden_lane(name)
    comp = run_batch_compiled([lane], groups, GPROCS)
    assert comp is not None
    assert bsc.last_stats["fallback"] is False, bsc.last_stats
    res = comp.result(0)
    with open(os.path.join(GOLDEN_DIR, f"{name}.json")) as f:
        golden = json.load(f)
    assert _close(res.horizon, golden["horizon"])
    assert {str(p) for p in res.busy_time} == set(golden["busy_time"])
    for pid, t in res.busy_time.items():
        assert _close(t, golden["busy_time"][str(pid)]), ("busy", pid)
    assert len(res.requests) == len(golden["requests"])
    for r, row in zip(res.requests, golden["requests"]):
        group, request, arrival, first_start, last_finish, done, total = row
        assert (r.group, r.request) == (group, request)
        assert r.done_tasks == done and r.total_tasks == total
        assert _close(r.arrival, arrival)
        assert _close(r.first_start, first_start)
        assert _close(r.last_finish, last_finish)
    for r, gm in zip(res.requests, golden["makespans"]):
        if gm is None:
            assert math.isinf(r.makespan)
        else:
            assert _close(r.makespan, gm)


# -- differential sweep: compiled vs numpy vs fastsim ----------------------


def _make_lanes(rng, n_lanes, measured, arrivals_on, faults_on):
    nets, groups, periods = _random_problem(rng)
    fac = SolutionFactory(nets, num_processors=len(PROCS),
                          rng=random.Random(rng.randrange(1 << 30)),
                          cut_prob=rng.uniform(0.1, 0.5))
    lanes = []
    for _ in range(n_lanes):
        sol = fac.random_solution()
        spec = build_spec(decode_solution(sol, nets), PROCS, PROFILER,
                          PAPER_COMM_MODEL)
        nr = rng.randint(3, 6)
        noise = NoiseModel(seed=rng.randrange(1 << 16)) if measured else None
        arr = (_random_arrival(rng, groups, periods, nr)
               if arrivals_on else None)
        faults = None
        if faults_on and rng.random() < 0.7:
            faults = FaultSpec(
                dropouts=((rng.randrange(len(PROCS)), rng.uniform(0, 0.01),
                           None if rng.random() < 0.5
                           else rng.uniform(0.001, 0.01)),),
                throttles=((rng.randrange(len(PROCS)), 0.0,
                            rng.uniform(0.002, 0.02),
                            rng.uniform(1.5, 4.0)),),
                straggler_prob=rng.choice([0.0, 0.2, 0.5]),
                straggler_shape=1.5,
                seed=rng.randrange(1 << 16),
            )
        lanes.append(BatchLane(
            spec=spec, periods=periods, num_requests=nr, noise=noise,
            dispatch_overhead=150e-6 if measured else 0.0,
            arrivals=arr, faults=faults))
    return lanes, groups


def _compare_three_tiers(tag, lanes, groups):
    ref = BatchSimulator(lanes, groups, PROCS).run()
    comp = run_batch_compiled(lanes, groups, PROCS)
    assert comp is not None, (tag, bsc.last_stats)
    assert bsc.last_stats["fallback"] is False, (tag, bsc.last_stats)
    for i, lane in enumerate(lanes):
        _assert_lane_close(ref.result(i), comp.result(i), (tag, i))
        fast = FastSimulator(
            lane.spec, groups=groups, periods=lane.periods,
            num_requests=lane.num_requests, noise=lane.noise,
            dispatch_overhead=lane.dispatch_overhead,
            arrivals=lane.arrivals, faults=lane.faults,
        ).run()
        _assert_lane_close(fast, comp.result(i), (tag, i, "fastsim"))


@pytest.mark.parametrize("seed", [0, 1])
def test_compiled_differential_clean(seed):
    rng = random.Random(5000 + seed)
    lanes, groups = _make_lanes(rng, 4, measured=False, arrivals_on=False,
                                faults_on=False)
    _compare_three_tiers(f"clean-{seed}", lanes, groups)


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_compiled_differential_arrivals(seed):
    """Jittered / poisson / trace arrivals + noise + dispatch tokens."""
    rng = random.Random(6000 + seed)
    lanes, groups = _make_lanes(rng, 4, measured=True, arrivals_on=True,
                                faults_on=False)
    _compare_three_tiers(f"arrivals-{seed}", lanes, groups)


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_compiled_differential_faults(seed):
    """Dropout + throttle + straggler ensembles on top of noise."""
    rng = random.Random(7000 + seed)
    lanes, groups = _make_lanes(rng, 4, measured=True, arrivals_on=True,
                                faults_on=True)
    _compare_three_tiers(f"faults-{seed}", lanes, groups)


def _overload_lanes():
    """Six random candidates at ~100x the arrival rate: deep queues."""
    rng = random.Random(99)
    nets, groups, periods = _random_problem(rng)
    periods = tuple(p * 0.01 for p in periods)  # ~100x arrival rate
    fac = SolutionFactory(nets, num_processors=len(PROCS),
                          rng=random.Random(2), cut_prob=0.3)
    lanes = []
    for _ in range(6):
        sol = fac.random_solution()
        spec = build_spec(decode_solution(sol, nets), PROCS, PROFILER,
                          PAPER_COMM_MODEL)
        lanes.append(BatchLane(spec=spec, periods=periods, num_requests=20,
                               dispatch_overhead=150e-6))
    return lanes, groups


def test_compiled_overload_inf_parity():
    """Deep-queue overload: dropped requests (inf makespans) and partial
    done counts agree with the numpy tier — the ready queues must hold
    every release at the host-computed bound."""
    lanes, groups = _overload_lanes()
    ref = BatchSimulator(lanes, groups, PROCS).run()
    comp = run_batch_compiled(lanes, groups, PROCS)
    assert comp is not None
    assert bsc.last_stats["fallback"] is False, bsc.last_stats
    dropped = 0
    for i in range(len(lanes)):
        _assert_lane_close(ref.result(i), comp.result(i), ("overload", i))
        dropped += sum(math.isinf(m) for m in ref.makespans(i))
    assert dropped, "overload scenario dropped no requests"


# -- ready queues: pop order and the queued counter ------------------------


def _contended_lanes(n_lanes):
    """Three chains of one request group, every layer its own subgraph on
    one worker, each lane with its own network priorities: requests
    arrive faster than the worker serves them, and the worker's dispatch
    tokens queue beside its tasks."""
    nets = _nets_tri_chain()
    lanes, sols = [], []
    for i in range(n_lanes):
        pin = i % 2
        sol = _solution(nets, seed=40 + i, pin=pin)
        spec = build_spec(decode_solution(sol, nets), GPROCS, GPROFILER,
                          PAPER_COMM_MODEL)
        lanes.append(BatchLane(spec=spec, periods=[0.004], num_requests=4,
                               dispatch_overhead=150e-6, dispatch_pid=pin))
        sols.append(sol)
    return sols, lanes, [[0, 1, 2]]


def test_pop_order_priority_then_release():
    """A worker that, while busy, receives releases of three priority
    classes from several requests, with dispatch tokens queued on it,
    serves them in the numpy tier's order: tokens, then priority, then
    release order. Checked as task records first (the lanes do contend
    like that), then lane for lane."""
    sols, lanes, groups = _contended_lanes(4)
    ref = BatchSimulator(lanes, groups, GPROCS).run(collect_tasks=True)
    for i, sol in enumerate(sols):
        waited = [r for r in ref.tasks[i] if r.started > r.released]
        assert len({sol.priority.index(r.network) for r in waited}) >= 3
        assert len({r.request for r in waited}) >= 2
        assert len({r.processor for r in waited}) == 1
    comp = run_batch_compiled(lanes, groups, GPROCS)
    assert comp is not None
    assert bsc.last_stats["fallback"] is False, bsc.last_stats
    for i in range(len(lanes)):
        _assert_lane_close(ref.result(i), comp.result(i), ("pop", i))
        for r in range(lanes[i].num_requests):
            assert ref.done[i, r] == comp.done[i, r], ("done", i, r)
        for pid in ref.pids:
            assert _close(ref.busy[i, pid], comp.busy[i, pid]), ("busy", i)


@pytest.mark.parametrize("measured,arrivals_on,faults_on",
                         [(False, False, False), (True, True, True)],
                         ids=["clean", "measured_faults"])
def test_queued_matches_lanes_run_alone(measured, arrivals_on, faults_on):
    """``totals["queued"]`` counts a batch's releases that waited for
    their worker: the sum of what its lanes count run alone."""
    lanes, groups = _make_lanes(random.Random(8), 5, measured, arrivals_on,
                                faults_on)
    alone = []
    for ln in lanes:
        before = bsc.totals["queued"]
        assert run_batch_compiled([ln], groups, PROCS) is not None
        alone.append(bsc.totals["queued"] - before)
    before = bsc.totals["queued"]
    assert run_batch_compiled(lanes, groups, PROCS) is not None
    assert bsc.totals["queued"] - before == sum(alone)


def test_queued_zero_without_contention():
    """One chain, one request at a time: no release ever finds its
    worker busy, so nothing is queued."""
    nets = [chain_graph("solo", [("conv", 4e6, 1000, 4000)] * 5)]
    sol = _solution(nets, seed=3)
    spec = build_spec(decode_solution(sol, nets), GPROCS, GPROFILER,
                      PAPER_COMM_MODEL)
    lane = BatchLane(spec=spec, periods=[1.0], num_requests=3)
    before = bsc.totals["queued"]
    comp = run_batch_compiled([lane], [[0]], GPROCS)
    assert comp is not None
    assert all(r.done_tasks == r.total_tasks for r in comp.result(0).requests)
    assert bsc.totals["queued"] == before


def test_queued_counts_overload():
    """Every lane of the overload scenario queues releases."""
    lanes, groups = _overload_lanes()
    for ln in lanes:
        before = bsc.totals["queued"]
        assert run_batch_compiled([ln], groups, PROCS) is not None
        assert bsc.totals["queued"] > before


# -- fallback contract -----------------------------------------------------


def test_run_batch_compiled_collect_tasks_falls_back_bitexact():
    """engine="compiled" with collect_tasks routes to numpy (task traces
    are python-side by design) — results bit-identical, not just close."""
    rng = random.Random(31)
    lanes, groups = _make_lanes(rng, 3, measured=True, arrivals_on=False,
                                faults_on=False)
    ref = run_batch(lanes, groups, PROCS, collect_tasks=True)
    via = run_batch(lanes, groups, PROCS, collect_tasks=True,
                    engine="compiled")
    for i in range(len(lanes)):
        assert ref.makespans(i) == via.makespans(i)
        assert ref.result(i).busy_time == via.result(i).busy_time


def test_run_batch_compiled_queue_bound_fallback():
    """A workload whose ready queues exceed QUEUE_PAIRS_MAX is declined
    before compilation; run_batch reruns it on numpy."""
    rng = random.Random(32)
    lanes, groups = _make_lanes(rng, 2, measured=False, arrivals_on=False,
                                faults_on=False)
    big = [BatchLane(spec=ln.spec, periods=ln.periods, num_requests=4000)
           for ln in lanes]
    assert run_batch_compiled(big, groups, PROCS) is None
    assert bsc.last_stats["fallback"] is True
    assert bsc.last_stats["reason"] == "queue-bound"


def test_run_batch_counts_and_logs_compiled_fallback(caplog):
    """A queue-bound fallback taken by run_batch is never silent: it is
    counted under its reason and logged, and the numpy tier answers."""
    from repro.core import batchsim

    rng = random.Random(34)
    lanes, groups = _make_lanes(rng, 1, measured=False, arrivals_on=False,
                                faults_on=False)
    big = [BatchLane(spec=ln.spec, periods=ln.periods, num_requests=4000)
           for ln in lanes]
    before = dict(batchsim.compiled_fallbacks)
    with caplog.at_level("WARNING", logger="repro.core.batchsim"):
        res = run_batch(big, groups, PROCS, engine="compiled")
    after = dict(batchsim.compiled_fallbacks)
    assert after.pop("queue-bound") == before.pop("queue-bound", 0) + 1
    assert after == before
    assert "queue-bound" in caplog.text
    assert len(res.makespans(0)) == 4000


def test_run_batch_unknown_engine_rejected():
    rng = random.Random(33)
    lanes, groups = _make_lanes(rng, 1, measured=False, arrivals_on=False,
                                faults_on=False)
    with pytest.raises(ValueError, match="unknown batch engine"):
        run_batch(lanes, groups, PROCS, engine="bogus")


def test_objectives_batch_compiled_engine_close_to_scalar():
    """Analyzer integration: cfg.batch_engine="compiled" yields objectives
    within the documented tolerance of the scalar loop."""
    from test_ga_determinism import _analyzer

    an = _analyzer()
    an.cfg.batch_engine = "compiled"
    an.factory.rng = random.Random(77)
    sols = [an.factory.random_solution() for _ in range(6)]
    batch = an.objectives_batch(sols)
    assert bsc.last_stats["fallback"] is False, bsc.last_stats
    scalar = [_analyzer().objectives(s) for s in sols]
    for b, s in zip(batch, scalar):
        assert len(b) == len(s)
        for x, y in zip(b, s):
            assert _close(x, y)


# -- the loop's read helper --------------------------------------------------


def _table(rng, dtype, shape):
    import numpy as np

    if dtype == "bool":
        return rng.random(shape) < 0.5
    if dtype == "float64":
        a = rng.normal(size=shape) * 1e3
        special = rng.integers(0, 4, size=shape)
        a = np.where(special == 0, np.inf, a)
        a = np.where(special == 1, -0.0, a)
        return a
    info = np.iinfo(dtype)
    return rng.integers(info.min, info.max, size=shape, dtype=dtype)


@pytest.mark.parametrize("path", ["one_hot", "gather"])
@pytest.mark.parametrize("form", ["lane", "lane_pair", "slots", "row"])
@pytest.mark.parametrize("dtype", ["bool", "int32", "int64", "float64"])
def test_oh_get_matches_indexing(dtype, form, path):
    """``oh_get`` equals ``x[WI, i]`` / ``x[WI, i, j]`` / ``x[WK, i]``, in
    the table's own dtype under x64, whether the axis is read with a
    one-hot mask or with a gather (axis above ``ONE_HOT_MAX``); float
    tables hold ``inf`` and ``-0.0``."""
    import jax
    import numpy as np

    rng = np.random.default_rng(sum(map(ord, dtype + form + path)))
    W, K = 5, 3
    big = path == "gather"
    d = bsc.ONE_HOT_MAX + 7 if big else 6
    if form == "lane_pair":
        dims = (3, -(-d // 3)) if big else (2, 3)
        assert (np.prod(dims) > bsc.ONE_HOT_MAX) == big
        shape = (W,) + dims
    elif form == "row":
        shape = (W, d, 4)
    else:
        shape = (W, d)
    x = _table(rng, dtype, shape)
    WI = np.arange(W)
    if form == "lane_pair":
        i = rng.integers(0, dims[0], size=W)
        j = rng.integers(0, dims[1], size=W)
        idx, want = (i, j), x[WI, i, j]
    elif form == "slots":
        i = rng.integers(0, d, size=(W, K))
        idx, want = (i,), x[WI[:, None], i]
    else:
        i = rng.integers(0, d, size=W)
        idx, want = (i,), x[WI, i]
    with jax.enable_x64(True):
        got = jax.jit(bsc.oh_get)(jax.numpy.asarray(x),
                                   *map(jax.numpy.asarray, idx))
        assert got.dtype == x.dtype
        got = np.asarray(got)
    assert got.shape == want.shape
    # value for value (a zero's sign aside: -0.0 == 0.0)
    assert np.array_equal(got, want)


@pytest.mark.parametrize("limit", [0, 1 << 30], ids=["all_gathers",
                                                      "all_one_hot"])
def test_read_paths_give_the_same_loop(limit, monkeypatch):
    """The loop's result does not depend on which axes are read with a
    gather and which with a one-hot mask: with every read a gather, and
    with every read one-hot, it equals the default's, value for value."""
    import jax
    import numpy as np

    rng = random.Random(4100)
    lanes, groups = _make_lanes(rng, 4, measured=True, arrivals_on=True,
                                faults_on=True)
    tables = bsc.build_tables(lanes, groups, PROCS)
    with jax.enable_x64(True):
        jtab = {k: jax.numpy.asarray(v) for k, v in tables.tab.items()}
        want = [np.asarray(a) for a in bsc.advance_fn()(tables.flags, jtab)]
        monkeypatch.setattr(bsc, "ONE_HOT_MAX", limit)
        got = [np.asarray(a)
               for a in bsc._advance_factory()(tables.flags, jtab)]
    assert int(want[-1]) > 0
    for a, b in zip(got, want):
        assert np.array_equal(a, b)
