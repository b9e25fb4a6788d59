"""Search driver: schedule a deployment, back to back, on the compiled core.

One search is what a user runs to schedule a deployment: a fresh
``EvalContext`` and ``StaticAnalyzer`` with the GA's candidate evaluation
and the α*-bisection on the compiled lock-step core
(``batch_engine="compiled"``), ``run_ga()`` and then
``population_saturation`` over its front.

Every run does the same work: the traffic file fixes the searches (GA seed,
and the arrival and fault seeds where the scenario has them), and
``--seed`` only rotates their order. Set-up runs each of them once, so
every program the window uses is compiled or loaded there. The window runs
rounds of the searches back to back, each search once per round, and ends
with the first round to finish after ``--seconds``: every run does the same
multiset of searches.

Correct: no compiled fallback, and the front of the last run of each
search agrees with ``replay.py``, the benchmark's own restatement of the
replay semantics, on the same candidates. Each front member's fitness has
to be one of its two evaluations on the compiled core (fast: clean, fewer
requests; accurate: measured), each of those within the compiled core's
stated tolerance of the replay's, and each α* equal to the replay's.
"""
from __future__ import annotations

import math
import time
from typing import Dict, List, Optional, Sequence

import harness


def rel_err_over_tol(a: Sequence[float], b: Sequence[float]) -> float:
    """Largest |a - b| over the compiled core's tolerance (≤ 1 passes)."""
    from repro.core import COMPILED_ABS_TOL, COMPILED_REL_TOL

    worst = 0.0
    for x, y in zip(a, b, strict=True):
        x, y = float(x), float(y)
        if math.isinf(x) or math.isinf(y):
            if x != y:
                return math.inf
            continue
        bound = COMPILED_ABS_TOL + COMPILED_REL_TOL * max(abs(x), abs(y))
        worst = max(worst, abs(x - y) / bound)
    return worst


def scenario_parts(traffic: dict, search: dict):
    """The (arrival, faults) of one search from the traffic file: as the
    program's specs, and as the plain dicts ``replay.py`` reads."""
    from repro.core import ArrivalSpec, FaultSpec

    arrival = faults = None
    plain_arrival = plain_faults = None
    if traffic.get("arrival"):
        plain_arrival = dict(traffic["arrival"], seed=search["arrival_seed"])
        arrival = ArrivalSpec(**plain_arrival)
    if traffic.get("faults"):
        plain_faults = dict(traffic["faults"], seed=search["fault_seed"])
        f = plain_faults
        faults = FaultSpec(
            dropouts=tuple(tuple(d) for d in f.get("dropouts", ())),
            throttles=tuple(tuple(t) for t in f.get("throttles", ())),
            straggler_prob=f["straggler_prob"],
            straggler_shape=f["straggler_shape"],
            seed=f["seed"])
    return (arrival, faults), (plain_arrival, plain_faults)


def analyzer_config(traffic: dict, search: dict):
    """The program's analyzer, set as the traffic file states."""
    from repro.core import AnalyzerConfig, GAConfig, NoiseModel

    ev, ga = traffic["evaluation"], traffic["ga"]
    return AnalyzerConfig(
        fast_requests=ev["fast_requests"],
        accurate_requests=ev["accurate_requests"],
        dispatch_overhead=ev["dispatch_overhead_s"],
        dispatch_pid=ev["dispatch_pid"], input_home_pid=ev["input_home_pid"],
        noise=NoiseModel(tuple(sorted(ev["noise_sigma"].items()))),
        batch_engine="compiled",
        ga=GAConfig(batch_eval="compiled", pop_size=ga["pop_size"],
                    min_generations=ga["generations"],
                    max_generations=ga["generations"],
                    seed=search["ga_seed"]))


class Driver:
    def __init__(self, cell: harness.Cell) -> None:
        self.cell = cell
        self.config = cell.config
        self.traffic = cell.traffic
        searches = list(self.traffic["searches"])
        k = cell.seed % len(searches)
        self.order = searches[k:] + searches[:k]
        self.results: List[dict] = []
        self.window_s = 0.0
        self.traced_iters: Optional[int] = None
        # fallbacks of the check's re-evaluation on the compiled core
        self.check_fallbacks = 0

    # -- one search ----------------------------------------------------------
    def _search(self, search: dict, tracer=None) -> dict:
        import jax
        from repro.core import (StaticAnalyzer, batchsim, batchsim_compiled,
                                build_scenario)
        from repro.experiments.evaluate import EvalContext

        (arrival, faults), _ = scenario_parts(self.traffic, search)
        totals = batchsim_compiled.totals
        i0 = totals["iters"]
        f0 = sum(batchsim.compiled_fallbacks.values())
        t0 = time.perf_counter()
        ctx = EvalContext()
        scenario = build_scenario(
            self.config["name"], [list(g) for g in self.config["groups"]],
            ctx.graphs, arrival=arrival, faults=faults)
        analyzer = StaticAnalyzer(
            scenario, ctx.processors, ctx.profiler, ctx.comm_model,
            analyzer_config(self.traffic, search))
        with jax.profiler.TraceAnnotation("search.run_ga"):
            result = analyzer.run_ga()
        t1 = time.perf_counter()
        if tracer is not None:
            # a whole search overflows the profiler's buffer (the loop's
            # ~150 operations per iteration are each an event): the trace
            # covers the α-search, whose iterations the counter gives
            tracer.start()
            i1 = totals["iters"]
        ta = time.perf_counter()
        with jax.profiler.TraceAnnotation("search.alpha"):
            sat = analyzer.population_saturation(result.pareto)
        tb = time.perf_counter()
        if tracer is not None:
            tracer.stop()
            self.traced_iters = totals["iters"] - i1
        t2 = time.perf_counter()
        return {
            "search": search, "start": t0, "end": t2,
            "ga_s": t1 - t0, "alpha_s": tb - ta,
            "iters": totals["iters"] - i0,
            "fallbacks": sum(batchsim.compiled_fallbacks.values()) - f0,
            "front": result.pareto,
            "alpha_star": [r.alpha_star for r in sat],
            "generations": result.generations,
            "ctx": ctx, "scenario": scenario, "analyzer": analyzer,
        }

    def _describe(self, r: dict, tag: str) -> None:
        self.cell.emit(
            f"{tag} ga_seed={r['search']['ga_seed']} "
            f"wall_s={r['end'] - r['start']} ga_s={r['ga_s']} "
            f"alpha_s={r['alpha_s']} iters={r['iters']} "
            f"generations={r['generations']} front={len(r['front'])} "
            f"fallbacks={r['fallbacks']}")

    # -- the run -------------------------------------------------------------
    def setup(self) -> None:
        for search in self.order:
            self._describe(self._search(search), "warmup")

    def run_window(self, seconds: float, tracer=None) -> None:
        t0 = time.perf_counter()
        while True:
            for search in self.order:
                first = not self.results
                r = self._search(search, tracer if first else None)
                self.results.append(r)
                self._describe(r, "search")
            if r["end"] - t0 >= seconds:
                break
        self.window_s = self.results[-1]["end"] - t0

    def release(self) -> None:
        pass

    # -- correctness -----------------------------------------------------------
    def reference(self, r: dict, exec_cost=float):
        """``replay.py`` on the search's deployment."""
        import replay

        ctx, scenario = r["ctx"], r["scenario"]
        _, (arrival, faults) = scenario_parts(self.traffic, r["search"])
        return replay.Deployment(
            scenario.graphs, scenario.groups, ctx.processors, ctx.profiler,
            ctx.comm_model, replay.Settings.of(self.traffic["evaluation"]),
            arrival, faults, exec_cost)

    def answers(self, r: dict):
        """What the program gave for the front of search ``r``: each
        member's fitness, its fast and accurate evaluations on the compiled
        core (from the analyzer's memo where the window made them), and
        its α*."""
        from repro.core import batchsim

        an, front = r["analyzer"], r["front"]
        ev = self.traffic["evaluation"]
        f0 = sum(batchsim.compiled_fallbacks.values())
        evaluations = {
            measured: an.objectives_batch(
                front, num_requests=ev["accurate_requests" if measured
                                       else "fast_requests"],
                measured=measured, engine="compiled")
            for measured in (False, True)}
        self.check_fallbacks += sum(batchsim.compiled_fallbacks.values()) - f0
        return ([tuple(s.fitness) for s in front], evaluations,
                list(r["alpha_star"]))

    def compare(self, r: dict) -> Dict[str, float]:
        """Objectives gap (over the tolerance), fitness values that are
        neither of their member's evaluations, and α* mismatches of one
        search's front against the replay."""
        ref = self.reference(r)
        fitness, evaluations, alphas = self.answers(r)
        err, unmatched, mismatched = 0.0, 0, 0
        for i, sol in enumerate(r["front"]):
            if fitness[i] not in (tuple(evaluations[False][i]),
                                  tuple(evaluations[True][i])):
                unmatched += 1
            for measured in (False, True):
                want = ref.objectives(sol, measured)
                gap = rel_err_over_tol(evaluations[measured][i], want)
                if gap > 1.0:
                    self.cell.emit(
                        f"mismatch measured={measured} program="
                        f"{list(map(float, evaluations[measured][i]))} "
                        f"reference={list(want)} gap={gap}")
                err = max(err, gap)
            ref_alpha = ref.alpha_star(sol)
            if ref_alpha != alphas[i]:
                self.cell.emit(f"mismatch alpha={alphas[i]} "
                               f"reference={ref_alpha}")
                mismatched += 1
        return {"obj_err_over_tol": err, "fitness_unmatched": unmatched,
                "alpha_mismatches": mismatched}

    def last_of_each(self) -> List[dict]:
        last: Dict[int, dict] = {}
        for r in self.results:
            last[r["search"]["ga_seed"]] = r
        return list(last.values())

    def check(self) -> List[harness.Check]:
        got: Dict[str, float] = {"obj_err_over_tol": 0.0,
                                 "fitness_unmatched": 0,
                                 "alpha_mismatches": 0}
        for r in self.last_of_each():
            one = self.compare(r)
            self.cell.emit(f"compare ga_seed={r['search']['ga_seed']} "
                           f"front={len(r['front'])} {one}")
            got["obj_err_over_tol"] = max(got["obj_err_over_tol"],
                                          one["obj_err_over_tol"])
            got["fitness_unmatched"] += one["fitness_unmatched"]
            got["alpha_mismatches"] += one["alpha_mismatches"]
        return [
            harness.Check("fallbacks", sum(r["fallbacks"]
                                           for r in self.results)
                          + self.check_fallbacks, 0),
            # the compiled core's stated tolerance is the limit
            harness.Check("obj_err_over_tol", got["obj_err_over_tol"], 1.0),
            harness.Check("fitness_unmatched", got["fitness_unmatched"], 0),
            harness.Check("alpha_mismatches", got["alpha_mismatches"], 0),
        ]

    # -- numbers -------------------------------------------------------------
    def attempted_failed(self):
        failed = sum(1 for r in self.results if r["fallbacks"])
        return len(self.results), failed

    def end_to_end(self) -> Dict[str, float]:
        return {"search_s": self.window_s / len(self.results)}

    def readings(self) -> dict:
        return {
            "kind": "search",
            # the traced search runs slower under the profiler: its times
            # are left out where the window has others
            "searches": [{k: r[k] for k in ("ga_s", "alpha_s", "iters")}
                         for r in (self.results[1:] if self.traced_iters
                                   is not None and len(self.results) > 1
                                   else self.results)],
            "traced_iters": self.traced_iters,
        }


class Control(Driver):
    """The control in the program's place: ``replay.py`` with every task's
    execution time rounded to float32 (the compiled core states float64)
    gives the fitness, the evaluations and α*."""

    def answers(self, r: dict):
        import numpy as np

        low = self.reference(r, exec_cost=lambda t: float(np.float32(t)))
        evaluations = {m: [low.objectives(s, m) for s in r["front"]]
                       for m in (False, True)}
        return (list(evaluations[True]), evaluations,
                [low.alpha_star(s) for s in r["front"]])


def toy(cell: harness.Cell) -> None:
    """Shrink a resolved cell in place to the size the CPU tests run: a GA
    of 6 over 2 generations, and a window of one round."""
    cell.traffic["ga"] = {"pop_size": 6, "generations": 2}
    cell.seconds = 0.0
