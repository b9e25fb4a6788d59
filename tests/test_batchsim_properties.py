"""Property-based differential parity: batchsim == fastsim == reference DES.

Randomized scenarios, solutions and noise seeds drive all three evaluation
engines; every comparison demands *bit-identical* results (zero max-abs
diff), not approximate agreement — the fast paths are exactness-preserving
rewrites, so any ulp of drift is a bug. ``test_bulk_differential_parity``
alone covers 200+ randomized cases with deterministic seeds (independent of
whether real hypothesis is installed), and
``test_bulk_differential_parity_arrivals`` adds 100+ cases with randomized
arrival specs (jittered / Poisson / trace) replayed through all **four**
tiers including the virtual-clock PuzzleRuntime; the ``@given`` tests add
shrinking and deeper generation when hypothesis is installed.
``test_compiled_tier_differential_spot_check`` extends the differential to
the opt-in compiled (jax) tier, which is tolerance-bounded rather than
bit-exact; its exhaustive suite is ``tests/test_batchsim_compiled.py``.

Also holds the genetic-operator invariants the engines rely on: UPMX keeps
priorities a permutation, mutation keeps every gene in range.
"""
import math
import random

from _hypothesis_compat import given, settings, st

from repro.core import (
    ArrivalSpec,
    BatchLane,
    BatchSimulator,
    FastSimulator,
    NoiseModel,
    PAPER_COMM_MODEL,
    Profiler,
    RuntimeSimulator,
    SolutionFactory,
    batch_objectives,
    branching_graph,
    build_spec,
    chain_graph,
    decode_solution,
    mobile_processors,
    run_batch,
    upmx,
)
from repro.core.profiler import AnalyticMobileBackend

PROCS = mobile_processors()
PROFILER = Profiler(AnalyticMobileBackend(PROCS))


def _random_problem(rng: random.Random):
    """A small random multi-network scenario (kept tiny: the DES is slow)."""
    n_nets = rng.randint(2, 4)
    nets = []
    for n in range(n_nets):
        n_layers = rng.randint(2, 5)
        layers = [
            (rng.choice(["conv", "fc", "dw"]),
             rng.uniform(5e5, 8e6),
             rng.uniform(200, 3000),
             rng.uniform(500, 6000))
            for _ in range(n_layers)
        ]
        if rng.random() < 0.5 or n_layers < 3:
            g = chain_graph(f"n{n}", layers)
        else:
            edges = [(i, i + 1) for i in range(n_layers - 1)]
            edges += [(0, n_layers - 1)]  # one skip edge -> a diamond
            g = branching_graph(f"n{n}", layers, edges)
        nets.append(g)
    if n_nets == 2 or rng.random() < 0.4:
        groups = [list(range(n_nets))]
    else:
        cut = rng.randint(1, n_nets - 1)
        groups = [list(range(cut)), list(range(cut, n_nets))]
    periods = [rng.uniform(0.0005, 0.006) for _ in groups]
    return nets, groups, periods


def _assert_identical(ref, other, tag=""):
    assert len(ref.requests) == len(other.requests), tag
    for a, b in zip(ref.requests, other.requests):
        assert (a.group, a.request) == (b.group, b.request), tag
        assert a.arrival == b.arrival, tag
        assert a.first_start == b.first_start, tag
        assert a.last_finish == b.last_finish, tag
        assert a.done_tasks == b.done_tasks, tag
        assert a.total_tasks == b.total_tasks, tag
        assert a.makespan == b.makespan or (
            math.isinf(a.makespan) and math.isinf(b.makespan)), tag
    assert len(ref.tasks) == len(other.tasks), tag
    for a, b in zip(ref.tasks, other.tasks):
        assert (a.group, a.request, a.network, a.sg_index, a.processor) == (
            b.group, b.request, b.network, b.sg_index, b.processor), tag
        assert a.released == b.released, tag
        assert a.started == b.started, tag
        assert a.finished == b.finished, tag
        assert a.comm_time == b.comm_time, tag
        assert a.quant_time == b.quant_time, tag
        assert a.exec_time == b.exec_time, tag
    assert ref.busy_time == other.busy_time, tag
    assert ref.horizon == other.horizon, tag


def _run_three_engines(rng: random.Random, measured: bool):
    """One random case through DES, fastsim and batchsim; assert identity."""
    nets, groups, periods = _random_problem(rng)
    fac = SolutionFactory(nets, num_processors=len(PROCS),
                          rng=random.Random(rng.randrange(1 << 30)),
                          cut_prob=rng.uniform(0.1, 0.5))
    sol = fac.random_solution()
    num_requests = rng.randint(3, 6)
    noise = NoiseModel(seed=rng.randrange(1 << 16)) if measured else None
    dispatch = 150e-6 if measured else 0.0

    placed = decode_solution(sol, nets)
    ref = RuntimeSimulator(
        placed=placed, processors=PROCS, profiler=PROFILER,
        comm_model=PAPER_COMM_MODEL, groups=groups, periods=periods,
        num_requests=num_requests, noise=noise, dispatch_overhead=dispatch,
    ).run()
    spec = build_spec(placed, PROCS, PROFILER, PAPER_COMM_MODEL)
    fast = FastSimulator(
        spec, groups=groups, periods=periods, num_requests=num_requests,
        noise=noise, dispatch_overhead=dispatch,
    ).run(collect_tasks=True)
    batch = BatchSimulator(
        [BatchLane(spec=spec, periods=periods, num_requests=num_requests,
                   noise=noise, dispatch_overhead=dispatch)],
        groups, PROCS,
    ).run(collect_tasks=True)
    _assert_identical(ref, fast, "fastsim-vs-des")
    _assert_identical(ref, batch.result(0), "batchsim-vs-des")
    return ref


def test_bulk_differential_parity():
    """≥200 randomized cases, zero max-abs diff across all three engines.

    Deterministic seeds, so this guarantee does not depend on hypothesis
    being installed. Half the cases run the measured path (lognormal noise
    + dispatch-token injection) — the tie-breaking-sensitive configuration.
    """
    cases = 0
    for seed in range(100):
        _run_three_engines(random.Random(0xB47C0 + seed), measured=False)
        cases += 1
    for seed in range(100):
        _run_three_engines(random.Random(0x90153 + seed), measured=True)
        cases += 1
    assert cases >= 200


# -- arrival-process differential parity (all four tiers) ---------------------

def _random_arrival(rng: random.Random, groups, periods, num_requests):
    """A random non-trivial arrival spec (sometimes periodic as control)."""
    kind = rng.choice(("periodic", "jittered", "jittered-lognormal",
                       "poisson", "trace"))
    if kind == "periodic":
        return rng.choice((None, ArrivalSpec()))
    if kind == "jittered":
        return ArrivalSpec(kind="jittered", jitter=rng.uniform(0.05, 1.5),
                           seed=rng.randrange(1 << 16))
    if kind == "jittered-lognormal":
        return ArrivalSpec(kind="jittered", distribution="lognormal",
                           jitter=rng.uniform(0.1, 0.8),
                           sigma=rng.uniform(0.1, 0.9),
                           seed=rng.randrange(1 << 16))
    if kind == "poisson":
        return ArrivalSpec(kind="poisson", seed=rng.randrange(1 << 16))
    # trace: random timestamps incl. ties, regressions and gaps — the
    # generator's monotone-clamp path must keep all tiers in lock-step
    trace = []
    for gid, period in enumerate(periods):
        n = rng.randint(0, num_requests + 2)
        ts = [rng.uniform(0.0, num_requests * period) for _ in range(n)]
        if ts and rng.random() < 0.5:
            ts.sort()
        if ts and rng.random() < 0.3:
            ts[rng.randrange(len(ts))] = ts[0]  # force a tie
        trace.append(tuple(ts))
    return ArrivalSpec(kind="trace", trace=tuple(trace))


def _run_four_engines(rng: random.Random, measured: bool):
    """One random arrival-spec case through DES, fastsim, batchsim AND the
    virtual-clock PuzzleRuntime; assert bit-identical traces."""
    from repro.runtime.conformance import run_virtual_schedule

    nets, groups, periods = _random_problem(rng)
    fac = SolutionFactory(nets, num_processors=len(PROCS),
                          rng=random.Random(rng.randrange(1 << 30)),
                          cut_prob=rng.uniform(0.1, 0.5))
    sol = fac.random_solution()
    num_requests = rng.randint(3, 6)
    arrivals = _random_arrival(rng, groups, periods, num_requests)
    noise = NoiseModel(seed=rng.randrange(1 << 16)) if measured else None
    dispatch = 150e-6 if measured else 0.0

    placed = decode_solution(sol, nets)
    ref = RuntimeSimulator(
        placed=placed, processors=PROCS, profiler=PROFILER,
        comm_model=PAPER_COMM_MODEL, groups=groups, periods=periods,
        num_requests=num_requests, noise=noise, dispatch_overhead=dispatch,
        arrivals=arrivals,
    ).run()
    spec = build_spec(placed, PROCS, PROFILER, PAPER_COMM_MODEL)
    fast = FastSimulator(
        spec, groups=groups, periods=periods, num_requests=num_requests,
        noise=noise, dispatch_overhead=dispatch, arrivals=arrivals,
    ).run(collect_tasks=True)
    batch = BatchSimulator(
        [BatchLane(spec=spec, periods=periods, num_requests=num_requests,
                   noise=noise, dispatch_overhead=dispatch,
                   arrivals=arrivals)],
        groups, PROCS,
    ).run(collect_tasks=True)
    virtual = run_virtual_schedule(
        nets, sol, PROCS, spec, groups, periods, num_requests,
        noise=noise, dispatch_overhead=dispatch, arrivals=arrivals,
    )
    _assert_identical(ref, fast, "arrivals:fastsim-vs-des")
    _assert_identical(ref, batch.result(0), "arrivals:batchsim-vs-des")
    _assert_identical(ref, virtual, "arrivals:virtual-runtime-vs-des")
    return arrivals


def test_bulk_differential_parity_arrivals():
    """100+ randomized arrival-spec cases, zero max-abs diff across all
    FOUR engine tiers (reference DES, fastsim, batchsim, virtual-clock
    PuzzleRuntime); half measured (noise + dispatch tokens)."""
    cases = 0
    kinds = set()
    for seed in range(55):
        spec = _run_four_engines(random.Random(0xA221E + seed),
                                 measured=False)
        kinds.add(spec.kind if spec is not None else "periodic")
        cases += 1
    for seed in range(55):
        spec = _run_four_engines(random.Random(0x7A913 + seed),
                                 measured=True)
        kinds.add(spec.kind if spec is not None else "periodic")
        cases += 1
    assert cases >= 100
    # the draw actually exercised every process family
    assert kinds >= {"periodic", "jittered", "poisson", "trace"}, kinds


@given(st.integers(min_value=0, max_value=1 << 30))
@settings(max_examples=15, deadline=None)
def test_property_parity_arrivals(seed):
    rng = random.Random(seed)
    _run_four_engines(rng, measured=rng.random() < 0.5)


def test_bulk_parity_overload():
    """Dropped-request (inf makespan) cases agree across engines."""
    saw_drop = False
    for seed in range(12):
        rng = random.Random(0xD209 + seed)
        nets, groups, _ = _random_problem(rng)
        periods = [2e-6 for _ in groups]  # hopeless overload
        fac = SolutionFactory(nets, num_processors=len(PROCS),
                              rng=random.Random(seed), cut_prob=0.3)
        sol = fac.random_solution()
        placed = decode_solution(sol, nets)
        ref = RuntimeSimulator(
            placed=placed, processors=PROCS, profiler=PROFILER,
            comm_model=PAPER_COMM_MODEL, groups=groups, periods=periods,
            num_requests=40,
        ).run()
        spec = build_spec(placed, PROCS, PROFILER, PAPER_COMM_MODEL)
        batch = BatchSimulator(
            [BatchLane(spec=spec, periods=periods, num_requests=40)],
            groups, PROCS,
        ).run(collect_tasks=True)
        _assert_identical(ref, batch.result(0))
        saw_drop = saw_drop or any(math.isinf(m) for m in batch.makespans(0))
    assert saw_drop, "overload cases never dropped a request"


@given(st.integers(min_value=0, max_value=1 << 30))
@settings(max_examples=30, deadline=None)
def test_property_parity_clean(seed):
    _run_three_engines(random.Random(seed), measured=False)


@given(st.integers(min_value=0, max_value=1 << 30))
@settings(max_examples=20, deadline=None)
def test_property_parity_measured(seed):
    _run_three_engines(random.Random(seed), measured=True)


@given(st.integers(min_value=0, max_value=1 << 30))
@settings(max_examples=10, deadline=None)
def test_property_batch_width_invariance(seed):
    """A lane's result is independent of what else shares its batch, and of
    process-pool sharding — lanes are isolated."""
    rng = random.Random(seed)
    nets, groups, periods = _random_problem(rng)
    fac = SolutionFactory(nets, num_processors=len(PROCS),
                          rng=random.Random(seed), cut_prob=0.3)
    sols = [fac.random_solution() for _ in range(5)]
    specs = [build_spec(decode_solution(s, nets), PROCS, PROFILER,
                        PAPER_COMM_MODEL) for s in sols]
    lanes = [
        BatchLane(spec=sp, periods=periods, num_requests=3 + (i % 3),
                  noise=NoiseModel(seed=i) if i % 2 else None,
                  dispatch_overhead=150e-6 if i % 2 else 0.0)
        for i, sp in enumerate(specs)
    ]
    wide = BatchSimulator(lanes, groups, PROCS).run()
    for i, lane in enumerate(lanes):
        solo = BatchSimulator([lane], groups, PROCS).run()
        assert wide.makespans(i) == solo.makespans(0)
        assert wide.result(i).busy_time == solo.result(0).busy_time
    sharded = run_batch(lanes, groups, PROCS, workers=2)
    assert batch_objectives(sharded) == batch_objectives(wide)


# -- genetic-operator invariants ---------------------------------------------

@given(st.integers(min_value=0, max_value=1 << 30))
@settings(max_examples=30, deadline=None)
def test_property_upmx_keeps_permutations(seed):
    rng = random.Random(seed)
    n = rng.randint(1, 12)
    p1 = list(range(n))
    p2 = list(range(n))
    rng.shuffle(p1)
    rng.shuffle(p2)
    c1, c2 = upmx(list(p1), list(p2), rng, indpb=rng.uniform(0.0, 1.0))
    assert sorted(c1) == list(range(n))
    assert sorted(c2) == list(range(n))


@given(st.integers(min_value=0, max_value=1 << 30))
@settings(max_examples=30, deadline=None)
def test_property_crossover_mutation_invariants(seed):
    """Chromosomes stay well-formed under crossover + mutation."""
    rng = random.Random(seed)
    nets, _, _ = _random_problem(rng)
    fac = SolutionFactory(nets, num_processors=len(PROCS),
                          rng=random.Random(seed + 1), cut_prob=0.3)

    def check(sol):
        assert sorted(sol.priority) == list(range(len(nets)))
        for net, g in enumerate(nets):
            assert len(sol.partition[net]) == g.num_edges
            assert all(b in (0, 1) for b in sol.partition[net])
            assert len(sol.mapping[net]) == g.num_layers
            assert all(0 <= m < len(PROCS) for m in sol.mapping[net])
        assert all(0 <= d < fac.num_dtypes for d in sol.dtype)
        assert all(0 <= b < fac.num_backends for b in sol.backend)

    a, b = fac.random_solution(), fac.random_solution()
    check(a)
    check(b)
    c1, c2 = fac.crossover(a, b)
    check(c1)
    check(c2)
    m = fac.mutate(c1, p_bit=0.3, p_map=0.3, p_prio=0.9, p_cfg=0.5)
    check(m)
    # mutation copies: the parent is untouched
    check(c1)


def test_compiled_tier_differential_spot_check():
    """Opt-in compiled tier vs fastsim vs numpy batch on randomized cases
    (arrivals + noise + dispatch tokens + a fault ensemble), within the
    compiled tier's documented tolerance — observed diff is exactly 0.0.
    The exhaustive compiled suite (all golden traces, fallback contract)
    lives in tests/test_batchsim_compiled.py."""
    import repro.core.batchsim_compiled as bsc
    from repro.core import (
        COMPILED_ABS_TOL,
        COMPILED_REL_TOL,
        FaultSpec,
        run_batch_compiled,
    )

    def close(a, b):
        if math.isinf(a) or math.isinf(b):
            return math.isinf(a) and math.isinf(b)
        return abs(a - b) <= COMPILED_ABS_TOL + COMPILED_REL_TOL * max(
            abs(a), abs(b))

    for seed, faulted in ((0xC0119, False), (0xC011A, True)):
        rng = random.Random(seed)
        nets, groups, periods = _random_problem(rng)
        fac = SolutionFactory(nets, num_processors=len(PROCS),
                              rng=random.Random(seed + 1), cut_prob=0.3)
        lanes = []
        for i in range(3):
            spec = build_spec(decode_solution(fac.random_solution(), nets),
                              PROCS, PROFILER, PAPER_COMM_MODEL)
            nr = rng.randint(3, 6)
            faults = FaultSpec(
                dropouts=((rng.randrange(len(PROCS)), 0.0, 0.004),),
                straggler_prob=0.3, straggler_shape=1.5,
                seed=rng.randrange(1 << 16),
            ) if faulted else None
            lanes.append(BatchLane(
                spec=spec, periods=periods, num_requests=nr,
                noise=NoiseModel(seed=rng.randrange(1 << 16)),
                dispatch_overhead=150e-6,
                arrivals=_random_arrival(rng, groups, periods, nr),
                faults=faults))
        comp = run_batch_compiled(lanes, groups, PROCS)
        assert comp is not None and bsc.last_stats["fallback"] is False
        ref = BatchSimulator(lanes, groups, PROCS).run()
        for i, lane in enumerate(lanes):
            fast = FastSimulator(
                lane.spec, groups=groups, periods=lane.periods,
                num_requests=lane.num_requests, noise=lane.noise,
                dispatch_overhead=lane.dispatch_overhead,
                arrivals=lane.arrivals, faults=lane.faults,
            ).run()
            for tier in (ref.result(i), fast):
                cr = comp.result(i)
                assert len(tier.requests) == len(cr.requests)
                for qa, qb in zip(tier.requests, cr.requests):
                    assert qa.done_tasks == qb.done_tasks
                    assert close(qa.makespan, qb.makespan)
                    assert close(qa.first_start, qb.first_start)
                    assert close(qa.last_finish, qb.last_finish)
                for pid in tier.busy_time:
                    assert close(tier.busy_time[pid], cr.busy_time[pid])
