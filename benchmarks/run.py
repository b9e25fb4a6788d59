"""Benchmark harness — one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows. Sections:

* table2  — CPU execution time across (dtype, backend) configs (paper Table 2)
* table3  — best time per processor (paper Table 3)
* table4  — non-linearity: Σ(per-layer) / whole-graph ratios (paper Table 4)
* fig5    — comm microbenchmark + piecewise-linear fit (paper Fig. 5)
* fig12   — single-model-group saturation multipliers: Puzzle vs Best
            Mapping vs NPU Only (paper Fig. 12)
* fig15   — multi-model-group saturation multipliers (paper Fig. 15)
* table5  — runtime ablation: tensor pool / shared buffer (paper Table 5 / Fig. 10)
* simspeed — fast-path evaluation engine: reference DES vs array-based
             fastsim µs/eval, decode-cache effect, grid vs bisection α*,
             and an end-to-end GA + saturation speedup on a deterministic
             3-group scenario (with a makespan-parity check). ``--json``
             additionally writes BENCH_simspeed.json for regression tracking.
* prescreen — static pre-screen (repro.analysis): GA simulations avoided
            by decode-time infeasibility proofs on a memory-constrained
            scenario, the pruned chromosomes adversarially re-checked by
            provisioning through a capacity-bounded TensorPool (false
            prunes must be 0), α*-probe savings from the proven deadline
            floor, and a front-identity assertion on the unconstrained
            run. ``--json`` writes BENCH_prescreen.json (CI gates
            ``prescreen_false_prunes == 0``).
* conformance — device-in-the-loop tier: replays schedules on the
            virtual-clock PuzzleRuntime and diffs task traces against the
            FastSimulator at zero tolerance (asserted), reporting µs/replay
            for both sides.
* sweep   — randomized scenario-sweep harness (repro.experiments): per-
            scenario α* for Puzzle / Best Mapping / NPU Only and the
            aggregate frequency-gain ratios (paper §6, Fig. 11).
            ``sweep --smoke`` is the CI smoke target: 2 scenarios with a
            tiny GA, well under a minute. The default all-sections pass
            also uses smoke sizing; explicit selection (``run.py sweep``)
            or ``--full`` runs the full-size variant.
* arrivals — the arrival-process axis: the same scenario compositions
            under periodic vs jittered vs Poisson traffic, with each
            method's α*, frequency-gain ratios and satisfaction rates per
            process (smoke sizing on the default pass, like sweep).
* faults  — fault injection + graceful degradation: one deterministic
            scenario run on the virtual-clock runtime clean, faulted
            without recovery (raw drops) and faulted with the
            RecoveryPolicy (timeout/retry + dropout remap), reporting
            deadline satisfaction and dropped-request counts for each,
            the remap's recovery latency, and the analyzer-side
            ``score_under_faults`` robustness objective. Smoke sizing on
            the default pass, like sweep.
* roofline — per (arch × shape) roofline terms from the dry-run artifacts
             (EXPERIMENTS.md §Roofline)
* kernels — Pallas kernel oracle agreement

Sections can be selected positionally (``run.py sweep --smoke``) or via
``--only``. ``--full`` runs all 10 random scenarios per group setting
(default 3) — the paper's full protocol (sweep: 10 scenarios instead of 4).
"""
from __future__ import annotations

import argparse
import glob
import json
import math
import os
import statistics
import time
from typing import Dict, Tuple

import numpy as np

from repro.compile_cache import use_compile_cache
from repro.core import (
    AnalyzerConfig,
    GAConfig,
    PAPER_COMM_MODEL,
    PiecewiseLinearCommModel,
    Profiler,
    Solution,
    StaticAnalyzer,
    TableBackend,
    build_scenario,
    decode_solution,
    microbenchmark_host,
    mobile_processors,
    random_scenarios,
    whole_model_placement,
)
from repro.core.profiler import AnalyticMobileBackend, JaxExecBackend
from repro.zoo import (
    MODEL_NAMES,
    TABLE4_RATIO,
    all_cost_graphs,
    executable_zoo,
    paper_profile_tables,
)

ROW = "{name},{us:.2f},{derived}"


def emit(name: str, us: float, derived: str = "") -> None:
    print(ROW.format(name=name, us=us, derived=derived), flush=True)


def _profiler():
    procs = mobile_processors()
    backend = TableBackend(
        processors=procs, tables=paper_profile_tables(),
        fallback=AnalyticMobileBackend(procs),
    )
    return procs, Profiler(backend)


def _analyzer(groups, name="bench", seed=0):
    graphs = all_cost_graphs()
    procs, prof = _profiler()
    scen = build_scenario(name, groups, graphs)
    cfg = AnalyzerConfig(ga=GAConfig(pop_size=20, max_generations=30,
                                     min_generations=10, seed=seed))
    return StaticAnalyzer(scen, procs, prof, PAPER_COMM_MODEL, cfg)


# ---------------------------------------------------------------------------

def bench_table2(args) -> None:
    """CPU times by (dtype, backend); derived = ratio to the row minimum."""
    tables = paper_profile_tables()
    for model in MODEL_NAMES:
        cpu_rows = {k: v for k, v in tables[model].items() if k[0] == "cpu"}
        best = min(cpu_rows.values())
        for (kind, dt, be), t in sorted(cpu_rows.items()):
            emit(f"table2.{model}.{dt}.{be}", t * 1e6, f"x{t / best:.2f}")


def bench_table3(args) -> None:
    """Best configuration per processor; derived = ratio to best processor."""
    procs, prof = _profiler()
    graphs = all_cost_graphs()
    from repro.core import best_model_times
    bt = best_model_times(list(graphs.values()), procs, prof)
    for i, model in enumerate(graphs):
        best = min(t for t, _, _ in bt[i].values())
        for pid, (t, di, bi) in sorted(bt[i].items()):
            emit(f"table3.{model}.{procs[pid].name}", t * 1e6,
                 f"x{t / best:.2f}")


def bench_table4(args) -> None:
    """Non-linearity: Σ single-layer subgraphs vs whole graph (calibrated),
    plus a REAL device-in-the-loop measurement on reduced models."""
    procs, prof = _profiler()
    graphs = all_cost_graphs()
    for model in MODEL_NAMES:
        g = graphs[model]
        whole = prof.subgraph_time(whole_model_placement(g, 0, 2, 1, 0))
        sol = Solution(partition=[[1] * g.num_edges],
                       mapping=[[2] * g.num_layers],
                       priority=[0], dtype=[1], backend=[0])
        placed = decode_solution(sol, [g])[0]
        summed = sum(prof.subgraph_time(p) for p in placed)
        paper = TABLE4_RATIO[model]["npu"]
        emit(f"table4.{model}.npu", whole * 1e6,
             f"est_ratio={summed / whole:.2f};paper={paper:.2f}")
    # live measurement on this host's CPU device (real XLA fusion loss)
    zoo = executable_zoo(names=["selfie_seg"], channels=4, spatial=8)
    live = Profiler(JaxExecBackend(zoo, repeats=3))
    g = zoo["selfie_seg"].graph
    whole = live.subgraph_time(whole_model_placement(g, 0, 0, 0, 0))
    sol = Solution(partition=[[1] * g.num_edges], mapping=[[0] * g.num_layers],
                   priority=[0], dtype=[0], backend=[0])
    placed = decode_solution(sol, [g])[0]
    summed = sum(live.subgraph_time(p) for p in placed)
    emit("table4.live_cpu.selfie_seg", whole * 1e6,
         f"est_ratio={summed / whole:.2f}")


def bench_fig5(args) -> None:
    """Comm microbenchmark on this host + fitted piecewise model."""
    t0 = time.perf_counter()
    samples = microbenchmark_host()
    fit = PiecewiseLinearCommModel.fit(samples)
    for n, t in samples:
        emit(f"fig5.sample.{int(n)}B", t * 1e6, f"fit={fit.cost(n) * 1e6:.1f}us")
    emit("fig5.fit", (time.perf_counter() - t0) * 1e6,
         f"a_lo={fit.a_lo:.2e};b_lo={fit.b_lo:.2e};a_hi={fit.a_hi:.2e};"
         f"b_hi={fit.b_hi:.2e}")


def _saturation_experiment(num_groups: int, count: int, tag: str) -> None:
    scenarios = random_scenarios(
        MODEL_NAMES, count=count, models_per_scenario=6,
        num_groups=num_groups, seed=2025,
    )
    results = {"puzzle": [], "bm": [], "npu": []}
    cap = 6.0
    for i, groups in enumerate(scenarios):
        t0 = time.perf_counter()
        an = _analyzer(groups, name=f"{tag}{i}", seed=i)
        ga = an.run_ga()
        pz = an.median_saturation(ga.pareto)
        bm = an.median_saturation(an.best_mapping(max_evals=120))
        npu = an.saturation(an.npu_only()).alpha_star
        vals = {"puzzle": pz, "bm": bm, "npu": npu}
        for k, v in vals.items():
            results[k].append(min(v, cap))
        dt = time.perf_counter() - t0
        emit(f"{tag}.scenario{i}", dt * 1e6,
             f"puzzle={pz};best_mapping={bm};npu_only={npu};"
             f"ga_evals={ga.evaluations}")
    mean = {k: statistics.mean(v) for k, v in results.items()}
    sd = {k: statistics.pstdev(v) for k, v in results.items()}
    emit(f"{tag}.mean_puzzle", mean["puzzle"] * 1e6, f"sd={sd['puzzle']:.2f}")
    emit(f"{tag}.mean_best_mapping", mean["bm"] * 1e6, f"sd={sd['bm']:.2f}")
    emit(f"{tag}.mean_npu_only", mean["npu"] * 1e6, f"sd={sd['npu']:.2f}")
    paper_npu = "3.63x" if num_groups > 1 else "2.00x"
    paper_bm = "2.36x" if num_groups > 1 else "1.50x"
    emit(f"{tag}.freq_gain_vs_npu", 0.0,
         f"{mean['npu'] / mean['puzzle']:.2f}x (paper {paper_npu})")
    emit(f"{tag}.freq_gain_vs_best_mapping", 0.0,
         f"{mean['bm'] / mean['puzzle']:.2f}x (paper {paper_bm})")


def bench_fig12(args) -> None:
    """Single model group: saturation multipliers across random scenarios."""
    _saturation_experiment(1, 10 if args.full else 3, "fig12")


def bench_fig15(args) -> None:
    """Two model groups: saturation multipliers across random scenarios."""
    _saturation_experiment(2, 10 if args.full else 3, "fig15")


def bench_table5(args) -> None:
    """Runtime ablation: tensor pool / shared buffer (real execution)."""
    from repro.runtime import PuzzleRuntime, RuntimeConfig
    zoo = executable_zoo(names=["face_det", "selfie_seg", "hand_det"],
                         channels=4, spatial=8)
    graphs = [zoo[n].graph for n in ("face_det", "selfie_seg", "hand_det")]
    # split each model in two; mixed dtypes force dtype-boundary staging
    parts = []
    for g in graphs:
        bits = [0] * g.num_edges
        bits[g.num_layers // 2] = 1
        parts.append(bits)
    sol = Solution(
        partition=parts,
        mapping=[[2] * g.num_layers for g in graphs],
        priority=[0, 1, 2], dtype=[0, 1, 0], backend=[0, 0, 0],
    )
    procs = mobile_processors()
    base_ms = None
    for pool, shared, label in [(False, False, "no_opt"),
                                (True, False, "pool"),
                                (True, True, "pool+shared")]:
        rt = PuzzleRuntime(graphs, sol, procs, zoo,
                           RuntimeConfig(tensor_pool=pool, shared_buffer=shared))
        try:
            res = rt.run_periodic([[0, 1, 2]], [0.02], num_requests=12)
            ms = statistics.mean(s.makespan for s in res[0])
            stats = rt.stats()
        finally:
            rt.close()
        if base_ms is None:
            base_ms = ms
        emit(f"table5.{label}", ms * 1e6,
             f"rel_makespan={ms / base_ms:.3f};mallocs={stats['pool']['mallocs']};"
             f"memcpy_bytes={stats['pool']['memcpy_bytes']};"
             f"staged={stats['transport']['staged_copies']}")


def bench_simspeed(args) -> None:
    """Old-vs-new evaluation engine: parity, µs/eval, end-to-end speedup."""
    groups = random_scenarios(
        MODEL_NAMES, count=1, models_per_scenario=6, num_groups=3, seed=7,
    )[0]
    record: Dict[str, object] = {"scenario": [list(g) for g in groups]}

    def make_analyzer(engine: str, saturation_mode: str) -> StaticAnalyzer:
        graphs = all_cost_graphs()
        procs, prof = _profiler()
        scen = build_scenario("simspeed", groups, graphs)
        # "reference" emulates the seed path end to end: generator-coroutine
        # DES, per-simulation re-decode, pure-Python NSGA, 117-point α grid.
        cfg = AnalyzerConfig(
            engine=engine, saturation_mode=saturation_mode,
            ga=GAConfig(pop_size=20, max_generations=30, min_generations=10,
                        seed=0, vectorized_nsga=(engine == "fast")),
        )
        return StaticAnalyzer(scen, procs, prof, PAPER_COMM_MODEL, cfg)

    an = make_analyzer("fast", "bisect")
    an.factory.rng = __import__("random").Random(123)
    sols = [an.factory.random_solution() for _ in range(12)]

    # 1) parity: identical makespans on the deterministic scenario, clean
    #    and measured (noisy + dispatch overhead) paths.
    max_diff = 0.0
    for measured in (False, True):
        ref = an.simulate(sols[0], 1.0, 24, measured=measured, seed=5,
                          engine="reference")
        fast = an.simulate(sols[0], 1.0, 24, measured=measured, seed=5,
                           engine="fast")
        pairs = list(zip(ref.makespans(), fast.makespans()))
        assert pairs, "no requests simulated"
        # dropped requests are inf on both sides: inf == inf is agreement,
        # not a nan-poisoned diff
        diff = max(
            0.0 if math.isinf(a) and math.isinf(b) else abs(a - b)
            for a, b in pairs
        )
        max_diff = max(max_diff, diff)
    emit("simspeed.parity", 0.0,
         f"max_makespan_diff={max_diff:.3e};ok={max_diff == 0.0}")
    record["parity_max_diff"] = max_diff

    # 2) µs per objectives() evaluation across distinct solutions (cold
    #    decode each time for both engines).
    def time_evals(engine: str) -> float:
        a = make_analyzer(engine, "bisect")
        t0 = time.perf_counter()
        for s in sols:
            a.objectives(s, engine=engine)
        return (time.perf_counter() - t0) / len(sols)

    ref_us = time_evals("reference") * 1e6
    fast_us = time_evals("fast") * 1e6
    emit("simspeed.eval_reference", ref_us, "per objectives() call")
    emit("simspeed.eval_fastsim", fast_us,
         f"per objectives() call;speedup=x{ref_us / fast_us:.2f}")
    record["eval_us_reference"] = ref_us
    record["eval_us_fastsim"] = fast_us

    # 3) per-α score cost for a fixed solution: the decode cache amortizes
    #    decoding + cost annotation across the whole α sweep.
    alphas = [round(0.5 + 0.25 * i, 4) for i in range(16)]
    t0 = time.perf_counter()
    for a_ in alphas:
        an.score(sols[1], a_)
    sweep_fast_us = (time.perf_counter() - t0) / len(alphas) * 1e6
    an_ref = make_analyzer("reference", "grid")
    t0 = time.perf_counter()
    for a_ in alphas:
        an_ref.score(sols[1], a_)
    sweep_ref_us = (time.perf_counter() - t0) / len(alphas) * 1e6
    emit("simspeed.score_per_alpha_reference", sweep_ref_us, "36-request sims")
    emit("simspeed.score_per_alpha_fastsim", sweep_fast_us,
         f"speedup=x{sweep_ref_us / sweep_fast_us:.2f}")
    record["score_per_alpha_us_reference"] = sweep_ref_us
    record["score_per_alpha_us_fastsim"] = sweep_fast_us

    # 4) α*-search: 117-point grid vs bracket+bisect (both on fastsim).
    #    The NPU-only baseline has a well-behaved finite α*.
    sat_sol = an.npu_only()
    t0 = time.perf_counter()
    grid = an.saturation(sat_sol, mode="grid")
    grid_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    bis = an.saturation(sat_sol, mode="bisect")
    bis_s = time.perf_counter() - t0
    emit("simspeed.alpha_star_grid", grid_s * 1e6,
         f"alpha_star={grid.alpha_star};evals={len(grid.scores)}")
    emit("simspeed.alpha_star_bisect", bis_s * 1e6,
         f"alpha_star={bis.alpha_star};evals={len(bis.scores)};"
         f"agrees={bis.alpha_star == grid.alpha_star}")
    record["alpha_star_grid"] = grid.alpha_star
    record["alpha_star_bisect"] = bis.alpha_star
    record["alpha_star_evals_grid"] = len(grid.scores)
    record["alpha_star_evals_bisect"] = len(bis.scores)

    # 5) end-to-end: GA search + one saturation sweep, seed path (reference
    #    DES, per-sim re-decode, pure-Python NSGA, 117-point grid scan) vs
    #    fast path (fastsim + decode/objective caches + bisection). Wall
    #    clock is min-of-N, interleaved, with the collector paused during
    #    each timed leg (timeit-style hygiene, applied to both paths) to
    #    damp scheduler/GC noise.
    import gc

    def end_to_end(engine: str, mode: str) -> Tuple[float, float, int]:
        a = make_analyzer(engine, mode)
        gc.collect()
        gc.disable()
        try:
            t0 = time.perf_counter()
            ga = a.run_ga()
            sat = a.saturation(ga.pareto[0])
            dt = time.perf_counter() - t0
        finally:
            gc.enable()
        return dt, sat.alpha_star, ga.evaluations

    old_s = new_s = float("inf")
    for _ in range(2):  # interleave repeats so CPU-clock drift hits both paths
        t, old_alpha, old_evals = end_to_end("reference", "grid")
        old_s = min(old_s, t)
        t, new_alpha, new_evals = end_to_end("fast", "bisect")
        new_s = min(new_s, t)
    emit("simspeed.e2e_seed_path", old_s * 1e6,
         f"alpha_star={old_alpha};ga_evals={old_evals}")
    emit("simspeed.e2e_fast_path", new_s * 1e6,
         f"alpha_star={new_alpha};ga_evals={new_evals};"
         f"speedup=x{old_s / new_s:.2f}")
    record["e2e_seconds_seed_path"] = old_s
    record["e2e_seconds_fast_path"] = new_s
    record["e2e_speedup"] = old_s / new_s
    record["e2e_alpha_star"] = {"seed_path": old_alpha, "fast_path": new_alpha}

    # 6) generation-batched population evaluation (core/batchsim): evaluate
    #    one GA-realistic generation (pop_size 40 -> 40 parents + 40
    #    offspring) through (a) the per-solution fast path, (b) one
    #    in-process lock-step batch pass, (c) the batch pass sharded across
    #    a 2-process pool. All three produce bit-identical objectives
    #    (asserted); the recorded numbers are the honest population-eval
    #    throughput comparison on this host.
    import random as _random

    gen_an = make_analyzer("fast", "bisect")
    gen_an.factory.rng = _random.Random(4242)
    parents = [gen_an.factory.random_solution() for _ in range(40)]
    offspring = []
    for i in range(0, 40, 2):
        a, b = parents[i], parents[i + 1]
        c1, c2 = gen_an.factory.crossover(a, b)
        offspring.append(gen_an.factory.mutate(c1))
        offspring.append(gen_an.factory.mutate(c2))
    generation = parents + offspring

    def time_population(fn, an) -> Tuple[float, object]:
        gc.collect()
        gc.disable()
        try:
            t0 = time.perf_counter()
            out = fn(an)
            return time.perf_counter() - t0, out
        finally:
            gc.enable()

    per_s, objs_loop = time_population(
        lambda a: [a.objectives(s) for s in generation],
        make_analyzer("fast", "bisect"))
    bat_s, objs_batch = time_population(
        lambda a: a.objectives_batch(generation),
        make_analyzer("fast", "bisect"))
    # The sharded number is the *raw* 2-process cost: a GA generation sits
    # below batchsim.SHARD_MIN_LANES (the measured crossover where pickling
    # lanes across the pool starts paying), so run_batch would normally keep
    # it in-process. Force-lower the threshold for this timing only, so the
    # recorded row shows what sharding would actually cost here.
    import repro.core.batchsim as _batchsim

    an_sh = make_analyzer("fast", "bisect")
    an_sh.cfg.batch_workers = 2
    an_sh2 = make_analyzer("fast", "bisect")
    an_sh2.cfg.batch_workers = 2
    _saved_min = _batchsim.SHARD_MIN_LANES
    _batchsim.SHARD_MIN_LANES = 0
    try:
        an_sh.objectives_batch(generation[:4])  # warm the pool + caches
        an_sh2._batch_pool = an_sh._batch_pool  # reuse the live pool
        shard_s, objs_shard = time_population(
            lambda a: a.objectives_batch(generation), an_sh2)
    finally:
        _batchsim.SHARD_MIN_LANES = _saved_min
        an_sh2._batch_pool = None
        an_sh.close()
    assert objs_loop == objs_batch == objs_shard, "batch parity violated"
    n = len(generation)
    per_us, bat_us, shard_us = (x / n * 1e6 for x in (per_s, bat_s, shard_s))
    best_us = min(bat_us, shard_us)
    speedup = per_us / best_us
    emit("simspeed.pop_eval_per_solution", per_us,
         f"{n}-candidate generation;evals_per_s={1e6 / per_us:.0f}")
    emit("simspeed.pop_eval_batch", bat_us,
         f"one lock-step pass;speedup=x{per_us / bat_us:.2f}")
    emit("simspeed.pop_eval_batch_sharded", shard_us,
         f"2-process shards (forced below SHARD_MIN_LANES="
         f"{_saved_min});speedup=x{per_us / shard_us:.2f}")
    record["eval_us_population_per_solution"] = per_us
    record["eval_us_batch"] = best_us
    record["eval_us_batch_inprocess"] = bat_us
    record["eval_us_batch_sharded"] = shard_us
    record["batch_speedup"] = speedup
    record["batch_parity_ok"] = True
    record["shard_min_lanes"] = _saved_min

    # 6b) compiled (jax) leg, full 6-model scenario: the same generation
    #     through the jitted jax.lax.while_loop core. First pass pays the
    #     XLA compile (recorded separately); the warm pass is the
    #     steady-state GA cost. last_stats is asserted so a silent numpy
    #     fallback cannot fake the number, and the objective drift vs the
    #     bit-exact loop is measured and bounded by the documented
    #     tolerance. On this scenario the per-request event count is large
    #     and GA cut-count variance makes lanes heterogeneous, so the
    #     lock-step pass (max-lane iterations × full-width element work)
    #     does NOT beat the scalar loop — recorded honestly as
    #     compiled_speedup_full_scenario; the crossover leg below (6c)
    #     times all three engines on one workload and carries the gated
    #     compiled_speedup (compiled vs the numpy lock-step tier).
    import repro.core.batchsim_compiled as _bsc
    from repro.core import COMPILED_ABS_TOL, COMPILED_REL_TOL

    an_c = make_analyzer("fast", "bisect")
    an_c.cfg.batch_engine = "compiled"
    cold_s, _ = time_population(
        lambda a: a.objectives_batch(generation), an_c)
    an_c2 = make_analyzer("fast", "bisect")
    an_c2.cfg.batch_engine = "compiled"
    comp_s, objs_comp = time_population(
        lambda a: a.objectives_batch(generation), an_c2)
    assert _bsc.last_stats.get("fallback") is False, _bsc.last_stats
    comp_diff = 0.0
    for row_a, row_b in zip(objs_loop, objs_comp):
        for x, y in zip(row_a, row_b):
            if math.isinf(x) or math.isinf(y):
                assert math.isinf(x) and math.isinf(y), "inf mismatch"
                continue
            comp_diff = max(comp_diff, abs(x - y))
            assert abs(x - y) <= (
                COMPILED_ABS_TOL
                + COMPILED_REL_TOL * max(abs(x), abs(y))
            ), "compiled tolerance violated"
    comp_us = comp_s / n * 1e6
    comp_speedup = per_us / comp_us
    emit("simspeed.pop_eval_batch_compiled", comp_us,
         f"jitted while_loop;speedup=x{comp_speedup:.2f};"
         f"max_diff={comp_diff:.3e};compile_s={cold_s - comp_s:.2f}")
    record["eval_us_batch_compiled"] = comp_us
    record["compiled_speedup_full_scenario"] = comp_speedup
    record["compiled_max_diff"] = comp_diff
    record["compiled_cold_compile_s"] = cold_s - comp_s
    record["eval_us_batch"] = min(best_us, comp_us)

    # 6c) compiled crossover leg: a compact 2-group scenario at GA
    #     width (80 lanes, measured noise + dispatch, 20 requests),
    #     timed through all three batch-capable paths on identical
    #     lanes. The gated compiled_speedup is compiled vs the numpy
    #     lock-step tier it replaces on the batch path (>1 everywhere
    #     measured, ~2.5-3x here). The scalar-loop comparison is
    #     recorded separately as compiled_speedup_vs_scalar and is < 1
    #     on this CPU: FastSimulator handles an event in ~0.75 µs of
    #     python while the compiled core's masked full-width iteration
    #     has a ~2 µs/lane floor at ~1.5 events per iteration — which
    #     is the measured crossover, and why the scalar loop (not any
    #     batch tier) remains the default CPU evaluation path.
    from repro.core import (
        BatchLane,
        BatchSimulator,
        FastSimulator,
        NoiseModel,
        SolutionFactory,
        build_spec,
        chain_graph,
    )
    from repro.core.batchsim_compiled import run_batch_compiled

    procs_x, prof_x = _profiler()
    nets_x = [
        chain_graph("m0", [("conv", 6e6, 2500, 7500)] * 3),
        chain_graph("m1", [("conv", 9e6, 3000, 9000)] * 4),
        chain_graph("m2", [("fc", 4e6, 2000, 5000)] * 3),
        chain_graph("m3", [("conv", 7e6, 2800, 8000)] * 3),
    ]
    groups_x = [[0, 1], [2, 3]]
    periods_x = (0.033, 0.05)
    fac_x = SolutionFactory(nets_x, num_processors=len(procs_x),
                            rng=_random.Random(9), cut_prob=0.3)
    lanes_x = []
    for i in range(80):
        spec_x = build_spec(decode_solution(fac_x.random_solution(),
                                            nets_x),
                            procs_x, prof_x, PAPER_COMM_MODEL)
        lanes_x.append(BatchLane(
            spec=spec_x, periods=periods_x, num_requests=20,
            noise=NoiseModel(seed=i), dispatch_overhead=150e-6))
    run_batch_compiled(lanes_x, groups_x, procs_x)  # pay the compile
    gc.collect()
    t0 = time.perf_counter()
    res_x = run_batch_compiled(lanes_x, groups_x, procs_x)
    comp_x_s = time.perf_counter() - t0
    assert res_x is not None, _bsc.last_stats
    assert _bsc.last_stats.get("fallback") is False, _bsc.last_stats
    t0 = time.perf_counter()
    fast_x = [
        FastSimulator(ln.spec, groups=groups_x, periods=ln.periods,
                      num_requests=ln.num_requests, noise=ln.noise,
                      dispatch_overhead=ln.dispatch_overhead).run()
        for ln in lanes_x
    ]
    scal_x_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    BatchSimulator(lanes_x, groups_x, procs_x).run()
    np_x_s = time.perf_counter() - t0
    diff_x = 0.0
    for i, fr in enumerate(fast_x):
        for a, b in zip([q.makespan for q in fr.requests],
                        [q.makespan for q in res_x.result(i).requests]):
            if math.isinf(a) or math.isinf(b):
                assert math.isinf(a) and math.isinf(b), "inf mismatch"
                continue
            diff_x = max(diff_x, abs(a - b))
            assert abs(a - b) <= (
                COMPILED_ABS_TOL + COMPILED_REL_TOL * max(abs(a), abs(b))
            ), "compiled tolerance violated"
    emit("simspeed.compiled_crossover", comp_x_s / 80 * 1e6,
         f"compact 2-group scenario;scalar_us="
         f"{scal_x_s / 80 * 1e6:.0f};numpy_us={np_x_s / 80 * 1e6:.0f};"
         f"vs_numpy=x{np_x_s / comp_x_s:.2f};"
         f"vs_scalar=x{scal_x_s / comp_x_s:.2f};"
         f"max_diff={diff_x:.3e}")
    record["compiled_speedup"] = np_x_s / comp_x_s
    record["compiled_speedup_vs_scalar"] = scal_x_s / comp_x_s
    record["compiled_crossover_us_scalar"] = scal_x_s / 80 * 1e6
    record["compiled_crossover_us_compiled"] = comp_x_s / 80 * 1e6
    record["compiled_crossover_us_numpy"] = np_x_s / 80 * 1e6

    # batched population α*-search over a candidate set (Pareto-front shape)
    sat_cands = parents[:8]
    sat_per_s, sat_loop = time_population(
        lambda a: [a.saturation(s) for s in sat_cands],
        make_analyzer("fast", "bisect"))
    sat_bat_s, sat_batch = time_population(
        lambda a: a.population_saturation(sat_cands),
        make_analyzer("fast", "bisect"))
    assert [r.alpha_star for r in sat_loop] ==\
        [r.alpha_star for r in sat_batch], "saturation parity violated"
    emit("simspeed.pop_alpha_star_per_solution", sat_per_s / 8 * 1e6,
         "bisect per candidate")
    emit("simspeed.pop_alpha_star_batch", sat_bat_s / 8 * 1e6,
         f"batched rounds;speedup=x{sat_per_s / sat_bat_s:.2f}")
    record["alpha_star_us_population_per_solution"] = sat_per_s / 8 * 1e6
    record["alpha_star_us_population_batch"] = sat_bat_s / 8 * 1e6
    record["batch_notes"] = (
        "numpy batchsim is bit-identical to the per-solution fast path "
        "(asserted above and by the differential property suite) but each "
        "lock-step event still touches ~30 scalars, so per-solution python "
        "remains competitive at GA widths; the compiled (jax) leg fuses the "
        "whole frontier advance into one jitted while_loop and beats the "
        "numpy lock-step tier ~2.5-3x on every measured workload, but the "
        "scalar loop keeps a ~0.75 us/event floor the full-width masked "
        "iteration cannot undercut on CPU, so the scalar path stays the "
        "default and compiled is the opt-in batch backend - see "
        "ARCHITECTURE.md (engines) for the measured crossover analysis")

    if getattr(args, "json", False):
        record["timestamp"] = time.time()

        def _finite(v):
            if isinstance(v, float) and not np.isfinite(v):
                return None
            if isinstance(v, dict):
                return {k: _finite(x) for k, x in v.items()}
            if isinstance(v, list):
                return [_finite(x) for x in v]
            return v

        safe = {k: _finite(v) for k, v in record.items()}
        out = os.path.join(os.path.dirname(__file__), "..",
                           "BENCH_simspeed.json")
        with open(os.path.abspath(out), "w") as f:
            json.dump(safe, f, indent=2, sort_keys=True)
        emit("simspeed.json", 0.0, os.path.abspath(out))


def bench_conformance(args) -> None:
    """Runtime↔simulator conformance: zero-diff assertion + replay cost.

    Replays deterministic schedules of a 2-group scenario on the
    virtual-clock PuzzleRuntime (the device-in-the-loop tier's exact-replay
    mode) and diffs release/start/finish timestamps and makespans against
    FastSimulator under measured (noise + dispatch) conditions. The diff
    must be zero; the emitted rows compare the per-replay cost of the two
    tiers.
    """
    import random as _random

    from repro.core import SolutionFactory

    an = _analyzer([["face_det", "selfie_seg"], ["yolov8n", "fast_scnn"]],
                   name="conformance", seed=0)
    fac = SolutionFactory(an.scenario.graphs, num_processors=3,
                          rng=_random.Random(7))
    solutions = [fac.random_solution() for _ in range(4)]
    nr = 12 if getattr(args, "smoke", False) else 24

    reports = []
    t0 = time.perf_counter()
    for sol in solutions:
        reports.append(an.validate_on_runtime(
            sol, alpha=1.0, num_requests=nr, measured=True, seed=0))
    t_validate = (time.perf_counter() - t0) / len(solutions)
    assert all(r.passed for r in reports), "virtual runtime diverged"
    max_diff = max(max(r.max_release_diff, r.max_start_diff,
                       r.max_finish_diff, r.max_makespan_diff)
                   for r in reports)
    tasks = sum(r.runtime_tasks for r in reports)

    # replay-cost split: simulator vs virtual-clock runtime on the same spec
    t0 = time.perf_counter()
    for sol in solutions:
        an.simulate(sol, 1.0, nr, measured=True, collect_tasks=True)
    t_sim = (time.perf_counter() - t0) / len(solutions)
    from repro.runtime.conformance import run_virtual_schedule
    t0 = time.perf_counter()
    for sol in solutions:
        run_virtual_schedule(
            an.scenario.graphs, sol, an.processors, an.solution_spec(sol),
            an.scenario.groups, an.base_periods, nr,
            noise=an.cfg.noise, dispatch_overhead=an.cfg.dispatch_overhead)
    t_rt = (time.perf_counter() - t0) / len(solutions)

    emit("conformance.zero_diff", t_validate * 1e6,
         f"ok=True;max_abs_diff={max_diff};tasks={tasks}")
    emit("conformance.fastsim_replay", t_sim * 1e6, f"requests={nr}")
    emit("conformance.virtual_runtime_replay", t_rt * 1e6,
         f"overhead=x{t_rt / t_sim:.2f} vs fastsim")


def _sweep_sizing(args, section: str, explicit_count: int,
                  full_count: int = 10):
    """(scenario count, SweepConfig) for a sweep-harness-backed section.

    Full sizing when the section is selected explicitly or ``--full`` asks
    for the paper's full protocol (matching fig12/fig15); otherwise — on
    the default all-sections pass or with ``--smoke`` — a 2-scenario tiny
    GA keeps the pass quick.
    """
    from repro.experiments import SweepConfig

    explicit = getattr(args, "full", False) or section in (
        getattr(args, "section", None), getattr(args, "only", None))
    if getattr(args, "smoke", False) or not explicit:
        return 2, SweepConfig(
            pop_size=8, max_generations=6, min_generations=2, bm_max_evals=30,
        )
    return (full_count if args.full else explicit_count), SweepConfig()


def bench_sweep(args) -> None:
    """Scenario-sweep harness smoke/regression: per-scenario α* + aggregates.

    ``--smoke``: 2 scenarios, tiny GA — a sub-minute regression check that
    the harness end-to-end (generation → evaluation → aggregation) still
    works and stays deterministic. Smoke sizing is also used when this
    section runs as part of the default all-sections pass, so ``run.py``
    with no arguments stays quick; selecting the section explicitly
    (``run.py sweep`` / ``--only sweep``) runs 4 scenarios at the harness's
    real GA sizing, and ``--full`` (with or without section selection,
    matching fig12/fig15) runs 10. Always evaluates into a fresh temp run
    dir so timings reflect real compute, not a resumed directory.
    """
    import tempfile

    from repro.experiments import METHODS, generate_scenario_specs
    from repro.experiments.sweep import run_sweep

    count, config = _sweep_sizing(args, "sweep", explicit_count=4)
    specs = generate_scenario_specs(count, seed=2025)
    t0 = time.perf_counter()
    with tempfile.TemporaryDirectory(prefix="puzzle_sweep_bench_") as run_dir:
        doc = run_sweep(specs, config, run_dir=run_dir, workers=1)
    wall = time.perf_counter() - t0
    for row in doc["scenarios"]:
        stars = ";".join(
            f"{m}={'never' if row['alpha_star'][m] is None else row['alpha_star'][m]}"
            for m in METHODS
        )
        emit(f"sweep.{row['spec']['name']}", row["wall_s"] * 1e6, stars)
    agg = doc["aggregate"]
    emit("sweep.gain_vs_npu_only", wall * 1e6 / count,
         f"{agg['speedup_geomean']['vs_npu_only']:.2f}x (paper 3.7x)")
    emit("sweep.gain_vs_best_mapping", wall * 1e6 / count,
         f"{agg['speedup_geomean']['vs_best_mapping']:.2f}x (paper 2.2x)")
    sat = agg["satisfaction_rate"]
    emit("sweep.satisfaction", wall * 1e6,
         ";".join(f"{m}={sat[m]:.2f}" for m in METHODS))
    # determinism canary: regenerating the specs must reproduce the stored
    # scenario compositions bit-for-bit
    again = [s.to_json() for s in generate_scenario_specs(count, seed=2025)]
    stored = [row["spec"] for row in doc["scenarios"]]
    emit("sweep.deterministic", 0.0, f"ok={again == stored}")


def bench_arrivals(args) -> None:
    """Puzzle vs baselines under bursty load (the arrival-process axis).

    Evaluates the same randomly drawn scenario compositions under three
    arrival processes — periodic (the paper's sources), jittered (uniform
    ±25% of Φ) and Poisson (exponential inter-arrivals at rate 1/Φ) — and
    reports each method's median α*, the geo-mean frequency gains and the
    deadline-satisfaction rate at α = 1. The compositions are identical
    across processes (only the traffic changes), so the drop from the
    ``periodic`` rows to the ``poisson`` rows is the price of burstiness,
    and the gain ratios show whether Puzzle's advantage survives it.
    Smoke sizing applies on the default all-sections pass (explicit
    selection or ``--full`` runs the harness's real GA sizing).
    """
    import tempfile

    from repro.experiments import METHODS, generate_scenario_specs
    from repro.experiments.sweep import run_sweep

    count, config = _sweep_sizing(args, "arrivals", explicit_count=3,
                                  full_count=6)
    for kind in ("periodic", "jittered", "poisson"):
        specs = generate_scenario_specs(count, seed=2025, arrival=kind)
        t0 = time.perf_counter()
        with tempfile.TemporaryDirectory(
                prefix=f"puzzle_arrivals_{kind}_") as run_dir:
            doc = run_sweep(specs, config, run_dir=run_dir, workers=1)
        wall = time.perf_counter() - t0
        for row in doc["scenarios"]:
            stars = ";".join(
                f"{m}={'never' if row['alpha_star'][m] is None else row['alpha_star'][m]}"
                for m in METHODS)
            emit(f"arrivals.{kind}.{row['spec']['name']}",
                 row["wall_s"] * 1e6, stars)
        agg = doc["aggregate"]
        sat = agg["satisfaction_rate"]
        emit(f"arrivals.{kind}.gain", wall * 1e6 / count,
             f"vs_npu={agg['speedup_geomean']['vs_npu_only']:.2f}x;"
             f"vs_bm={agg['speedup_geomean']['vs_best_mapping']:.2f}x;"
             + ";".join(f"sat_{m}={sat[m]:.2f}" for m in METHODS))


def bench_faults(args) -> None:
    """Fault injection + graceful degradation on the virtual runtime.

    One deterministic 2-group scenario, one solution that places work on
    processor 2, three virtual-clock runs:

    * ``clean``      — no faults, no recovery (baseline satisfaction)
    * ``raw``        — a mid-run permanent dropout of processor 2 plus
                       heavy-tailed stragglers, no recovery: every request
                       needing the dead processor is dropped
    * ``recovered``  — same ensemble with the RecoveryPolicy: the dropout
                       triggers the fallback remap, stragglers hit the
                       timeout/retry watchdog, and no request is dropped

    Emitted per run: pooled deadline satisfaction (at a feasible α,
    calibrated so the clean baseline meets its deadlines — otherwise the
    comparison is degenerate) and the dropped count.
    ``faults.recovery_latency`` is the remap's drain time — the
    last finish among requests already in flight at the drop instant,
    minus the drop instant. The analyzer-side ``score_under_faults`` rows
    report the same degradation measured by the simulator tiers (the
    robustness objective the GA sees when a scenario carries faults).
    """
    import random as _random

    from repro.core import FaultSpec, SolutionFactory
    from repro.core.scoring import deadline_satisfaction
    from repro.runtime import PuzzleRuntime, RecoveryPolicy, RuntimeConfig

    explicit = getattr(args, "full", False) or "faults" in (
        getattr(args, "section", None), getattr(args, "only", None))
    nr = 16 if explicit and not getattr(args, "smoke", False) else 8

    an = _analyzer([["face_det", "selfie_seg"], ["yolov8n"]],
                   name="faults", seed=0)
    graphs = list(an.scenario.graphs)
    groups = [list(g) for g in an.scenario.groups]
    base_periods = list(an.base_periods)

    # a draw that actually uses processor 2, so the dropout bites
    sol = None
    for seed in range(64):
        fac = SolutionFactory(graphs, num_processors=len(an.processors),
                              rng=_random.Random(seed))
        cand = fac.random_solution()
        if any(p.processor == 2
               for pl in decode_solution(cand, graphs) for p in pl):
            sol = cand
            break
    assert sol is not None, "no draw places work on processor 2"
    spec = an.solution_spec(sol)

    def run(periods, faults, recovery):
        rt = PuzzleRuntime(
            graphs, sol, an.processors,
            config=RuntimeConfig(virtual=True, faults=faults,
                                 recovery=recovery),
            spec=spec,
        )
        t0 = time.perf_counter()
        with rt:
            states = rt.run_periodic(groups, periods, num_requests=nr)
        return rt, states, time.perf_counter() - t0

    # arrivals stay at the paper's base periods — congested, so work is
    # genuinely in flight when the dropout hits. Satisfaction deadlines
    # are calibrated per group from the clean run (at α=1 this solution
    # misses every deadline and all three numbers degenerate to 0).
    _, clean_states, t_clean = run(base_periods, None, None)
    deadlines = [1.2 * max(st.makespan for st in gl) for gl in clean_states]
    alpha = round(max(d / p for d, p in zip(deadlines, base_periods)), 2)
    emit("faults.deadlines", 0.0,
         ";".join(f"g{g}={d:.4f}s" for g, d in enumerate(deadlines))
         + f";alpha_equiv={alpha}")

    def sat(states):
        per_group = [[float("inf") if st.makespan is None else st.makespan
                      for st in gl] for gl in states]
        dropped = sum(st.makespan is None for gl in states for st in gl)
        return deadline_satisfaction(per_group, deadlines), dropped

    sat_clean, drop_clean = sat(clean_states)
    horizon = max(st.last_finish or 0.0
                  for gl in clean_states for st in gl)
    t_drop = round(0.35 * horizon, 6)
    faults = FaultSpec(dropouts=((2, t_drop, None),),
                       straggler_prob=0.1, straggler_shape=1.5, seed=2025)

    rt_raw, raw_states, t_raw = run(base_periods, faults, None)
    sat_raw, drop_raw = sat(raw_states)
    rt_rec, rec_states, t_rec = run(base_periods, faults, RecoveryPolicy())
    sat_rec, drop_rec = sat(rec_states)

    emit("faults.clean", t_clean * 1e6,
         f"satisfaction={sat_clean:.2f};dropped={drop_clean};requests={nr * 3}")
    emit("faults.raw", t_raw * 1e6,
         f"satisfaction={sat_raw:.2f};dropped={drop_raw};"
         f"delta_vs_clean={sat_clean - sat_raw:+.2f}")
    remaps = [e for e in rt_rec.recovery_events if e.kind == "remap"]
    retries = [e for e in rt_rec.recovery_events if e.kind == "retry"]
    emit("faults.recovered", t_rec * 1e6,
         f"satisfaction={sat_rec:.2f};dropped={drop_rec};"
         f"delta_vs_clean={sat_clean - sat_rec:+.2f};"
         f"remaps={len(remaps)};retries={len(retries)}")

    # recovery latency: drain time of the requests in flight at the drop
    inflight = [st for gl in rec_states for st in gl
                if st.submitted <= t_drop
                and (st.last_finish is None or st.last_finish > t_drop)]
    if inflight and all(st.last_finish is not None for st in inflight):
        latency = max(st.last_finish for st in inflight) - t_drop
        emit("faults.recovery_latency", latency * 1e6,
             f"t_drop={t_drop};inflight={len(inflight)}")
    else:
        emit("faults.recovery_latency", 0.0,
             f"t_drop={t_drop};inflight={len(inflight)};drained=False")

    # analyzer-side robustness objective (simulator tiers, measured path)
    rep = an.score_under_faults(sol, faults=faults, alpha=alpha,
                                num_requests=nr)
    emit("faults.score_under_faults", 0.0,
         f"sat_clean={rep['satisfaction_clean']:.2f};"
         f"sat_faulted={rep['satisfaction_faulted']:.2f};"
         f"dropped_clean={rep['dropped_clean']:.0f};"
         f"dropped_faulted={rep['dropped_faulted']:.0f};"
         f"score_delta={rep['score_delta']:.3f}")


def bench_roofline(args) -> None:
    """Roofline terms per (arch × shape) from the dry-run artifacts."""
    pat = os.path.join(os.path.dirname(__file__), "..", "results", "dryrun",
                       "*__single.json")
    files = sorted(glob.glob(pat))
    if not files:
        emit("roofline.missing", 0.0, "run repro.launch.dryrun first")
        return
    for f in files:
        d = json.load(open(f))
        if not d.get("ok"):
            emit(f"roofline.{d['arch']}.{d['shape']}", 0.0, "FAILED")
            continue
        dom = max(("t_compute", "t_memory", "t_collective"),
                  key=lambda k: d[k])
        emit(
            f"roofline.{d['arch']}.{d['shape']}",
            d[dom] * 1e6,
            f"bottleneck={d['bottleneck']};compute={d['t_compute']:.4f}s;"
            f"memory={d['t_memory']:.4f}s;collective={d['t_collective']:.4f}s;"
            f"useful={d['useful_ratio']:.2f}",
        )


def bench_kernels(args) -> None:
    """Kernel oracle agreement + wall time of the jnp reference path."""
    import jax
    import jax.numpy as jnp
    from repro.kernels import attention_ref, flash_attention
    from repro.models import blockwise_attention
    key = jax.random.PRNGKey(0)
    q = jax.random.normal(key, (8, 512, 128))
    k = jax.random.normal(key, (2, 512, 128))
    v = jax.random.normal(key, (2, 512, 128))
    got = flash_attention(q, k, v, q_heads_per_kv=4, interpret=True,
                          block_q=128, block_k=128)
    want = attention_ref(q, k, v, q_heads_per_kv=4)
    err = float(jnp.abs(got - want).max())
    # time the production jnp path (the kernel itself is interpret-only here)
    qb = q.reshape(1, 8, 512, 128).transpose(0, 2, 1, 3)
    kb = k.reshape(1, 2, 512, 128).transpose(0, 2, 1, 3)
    fn = jax.jit(lambda a, b: blockwise_attention(a, b, b))
    jax.block_until_ready(fn(qb, kb))
    t0 = time.perf_counter()
    for _ in range(5):
        jax.block_until_ready(fn(qb, kb))
    emit("kernels.flash_attention", (time.perf_counter() - t0) / 5 * 1e6,
         f"max_err_vs_ref={err:.2e}")


def bench_prescreen(args) -> None:
    """Static pre-screen (repro.analysis): simulations avoided per GA run.

    One deterministic 2-group scenario, twice:

    1. **Unconstrained** — prescreen on vs off must yield bit-identical
       Pareto fronts and evaluation counts (nothing is provable, so the
       pre-screen may not perturb the search). Asserted.
    2. **Memory-constrained** — the NPU gets a tensor-memory budget that
       many chromosomes provably exceed (SL020): reports how many GA
       simulations the pre-screen avoided, and adversarially re-checks
       every pruned chromosome by *actually provisioning* it through a
       capacity-bounded TensorPool — a prune whose provisioning succeeds
       would be a soundness bug (``false_prunes``, must be 0; CI gates it).

    Also measures the α*-search probe savings from the proven deadline
    lower bound (``skip_below``), asserting α* itself is unchanged.
    ``--json`` writes BENCH_prescreen.json for the CI gate.
    """
    import dataclasses

    from repro.analysis import provision_memory
    from repro.core.graph import chain_graph
    from repro.core.scenarios import Scenario

    nets = (
        chain_graph("alpha", [("conv", 4e6, 1000, 4000)] * 4),
        chain_graph("beta", [("fc", 8e6, 2000, 8000)] * 3),
        chain_graph("gamma", [("dw", 1.5e6, 600, 1800)] * 5),
    )
    scenario = Scenario(name="prescreen_bench", graphs=nets,
                        groups=((0, 1), (2,)))
    procs = mobile_processors()
    profiler = Profiler(AnalyticMobileBackend(procs))

    def make_analyzer(processors, prescreen):
        return StaticAnalyzer(
            scenario, processors, profiler, PAPER_COMM_MODEL,
            AnalyzerConfig(
                prescreen=prescreen,
                ga=GAConfig(pop_size=16, max_generations=10,
                            min_generations=5, seed=7, prescreen=prescreen),
            ),
        )

    def front_keys(result):
        return sorted(s.key() for s in result.pareto)

    # 1. unconstrained: the pre-screen must be a no-op
    t0 = time.perf_counter()
    off = make_analyzer(procs, False).run_ga()
    t_off = time.perf_counter() - t0
    t0 = time.perf_counter()
    on = make_analyzer(procs, True).run_ga()
    t_on = time.perf_counter() - t0
    fronts_identical = (front_keys(off) == front_keys(on)
                        and off.evaluations == on.evaluations)
    assert fronts_identical, "prescreen perturbed an unconstrained GA run"
    assert on.prescreen_stats["pruned"] == 0
    emit("prescreen.unconstrained.off", t_off * 1e6,
         f"evals={off.evaluations}")
    emit("prescreen.unconstrained.on", t_on * 1e6,
         f"evals={on.evaluations};checked={on.prescreen_stats['checked']};"
         f"fronts_identical={fronts_identical}")

    # 2. NPU memory budget below what whole-model-resident schedules need:
    # chromosomes packing everything onto the NPU provably OOM (SL020)
    tight_procs = [
        dataclasses.replace(p, memory_capacity=20480) if p.kind == "npu"
        else p
        for p in procs
    ]
    t0 = time.perf_counter()
    c_off = make_analyzer(tight_procs, False).run_ga()
    tc_off = time.perf_counter() - t0
    an_c = make_analyzer(tight_procs, True)
    linter = an_c.linter()
    pruned_solutions = []
    orig_prescreen = an_c.prescreen_objectives

    def recording_prescreen(sol):
        obj = orig_prescreen(sol)
        if obj is not None:
            pruned_solutions.append(sol)
        return obj

    an_c.prescreen_objectives = recording_prescreen
    t0 = time.perf_counter()
    c_on = an_c.run_ga()
    tc_on = time.perf_counter() - t0
    stats = c_on.prescreen_stats
    # adversarial ground truth: every pruned chromosome must fail to
    # provision through a real capacity-bounded TensorPool
    false_prunes = 0
    for sol in pruned_solutions:
        ok = provision_memory(linter.builder.decode(sol),
                              linter.capacities())
        if all(ok.values()):
            false_prunes += 1
    avoided_fraction = (stats["simulations_avoided"]
                        / max(1, stats["simulations_avoided"]
                              + c_on.evaluations))
    emit("prescreen.constrained.off", tc_off * 1e6,
         f"evals={c_off.evaluations}")
    emit("prescreen.constrained.on", tc_on * 1e6,
         f"evals={c_on.evaluations};pruned={stats['pruned']};"
         f"checked={stats['checked']}")
    emit("prescreen.simulations_avoided", 0.0,
         f"{stats['simulations_avoided']} ({avoided_fraction * 100:.1f}% "
         f"of GA evaluations)")
    emit("prescreen.false_prunes", 0.0, f"{false_prunes} (gate: 0)")

    # 3. α*-probe skipping: the proven deadline floor answers probes below
    # it as 0.0 without simulating. Two regimes: a feasible front solution
    # (floor below the probe path — searches must be identical), and an
    # overloaded regime (periods ÷ 8: the CPU seed's floor clears the whole
    # α lattice, so α* = inf is proven without a single simulation).
    def count_probes(an, sol):
        calls = 0
        orig_score = an.score

        def counting_score(s, alpha, **kw):
            nonlocal calls
            calls += 1
            return orig_score(s, alpha, **kw)

        an.score = counting_score
        sat = an.saturation(sol)
        an.score = orig_score
        return calls, sat.alpha_star

    probe_sol = sorted(off.pareto, key=lambda s: s.key())[0]
    counts = {}
    alpha_stars = {}
    overload = {}
    for label, prescreen in (("off", False), ("on", True)):
        an = make_analyzer(procs, prescreen)
        counts[label], alpha_stars[label] = count_probes(an, probe_sol)
        an_tight = make_analyzer(procs, prescreen)
        # overloaded regime: same scenario at 8x the request rate
        an_tight.base_periods = [p / 8.0 for p in an_tight.base_periods]
        overload[label] = count_probes(
            an_tight, an_tight.factory.seeded_solution(0))
    assert alpha_stars["off"] == alpha_stars["on"],\
        "probe skipping changed alpha*"
    assert overload["off"][1] == overload["on"][1] == float("inf")
    emit("prescreen.alpha_probes.front", 0.0,
         f"off={counts['off']};on={counts['on']};"
         f"alpha_star={alpha_stars['on']};identical=True")
    emit("prescreen.alpha_probes.overloaded", 0.0,
         f"off={overload['off'][0]};on={overload['on'][0]};"
         f"alpha_star=inf (proven without simulation)")

    if getattr(args, "json", False):
        record = {
            "timestamp": time.time(),
            "unconstrained": {
                "evals_off": off.evaluations,
                "evals_on": on.evaluations,
                "checked": on.prescreen_stats["checked"],
                "fronts_identical": fronts_identical,
            },
            "constrained": {
                "evals_off": c_off.evaluations,
                "evals_on": c_on.evaluations,
                "prescreen_stats": dict(stats),
                "prescreen_false_prunes": false_prunes,
                "simulations_avoided_fraction": avoided_fraction,
            },
            "alpha_probes": {
                "front_off": counts["off"],
                "front_on": counts["on"],
                "front_alpha_star": alpha_stars["on"],
                "overloaded_off": overload["off"][0],
                "overloaded_on": overload["on"][0],
            },
        }
        out = os.path.join(os.path.dirname(__file__), "..",
                           "BENCH_prescreen.json")
        with open(os.path.abspath(out), "w") as f:
            json.dump(record, f, indent=2, sort_keys=True)
        emit("prescreen.json", 0.0, os.path.abspath(out))


SECTIONS = {
    "table2": bench_table2,
    "table3": bench_table3,
    "table4": bench_table4,
    "fig5": bench_fig5,
    "fig12": bench_fig12,
    "fig15": bench_fig15,
    "table5": bench_table5,
    "simspeed": bench_simspeed,
    "prescreen": bench_prescreen,
    "conformance": bench_conformance,
    "sweep": bench_sweep,
    "arrivals": bench_arrivals,
    "faults": bench_faults,
    "roofline": bench_roofline,
    "kernels": bench_kernels,
}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("section", nargs="?", choices=sorted(SECTIONS),
                    default=None, help="run just this section")
    ap.add_argument("--only", choices=sorted(SECTIONS), default=None)
    ap.add_argument("--full", action="store_true",
                    help="all 10 random scenarios per group setting")
    ap.add_argument("--smoke", action="store_true",
                    help="sweep section: 2 scenarios, tiny GA (<1 min)")
    ap.add_argument("--json", action="store_true",
                    help="also write BENCH_simspeed.json (simspeed section)")
    args = ap.parse_args()
    if args.section and args.only and args.section != args.only:
        ap.error(f"conflicting sections: positional {args.section!r} "
                 f"vs --only {args.only!r}")
    selected = args.section or args.only
    use_compile_cache()
    print("name,us_per_call,derived")
    for name, fn in SECTIONS.items():
        if selected and name != selected:
            continue
        t0 = time.perf_counter()
        fn(args)
        emit(f"section.{name}.total", (time.perf_counter() - t0) * 1e6)


if __name__ == "__main__":
    main()
