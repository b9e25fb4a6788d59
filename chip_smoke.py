#!/usr/bin/env python3
"""Run Puzzle's two main paths once on one TPU chip and check the results.

* **Search**: one §6.1 scenario through ``evaluate_scenario`` with the
  α*-searches on the compiled lock-step core, and one 80-lane
  ``StaticAnalyzer.run_ga``, each compared with the same call on the
  scalar path in this process.
* **Serving**: the five networks of ``examples/serve_multimodel.py`` at
  their Table 6 input resolutions, scheduled on the paper's profile
  tables, served by ``PuzzleRuntime``; every request's outputs are checked
  against a plain float32 forward on the host, and the serving window must
  compile nothing.

Usage, from the repository root on a machine with a TPU::

    python chip_smoke.py

Everything runs in this one process, which holds the chip. Numbers go to
stdout; the last line is a JSON object naming the device, printed only
when every check passed. Without a TPU it exits with status 2 and runs
nothing.
"""
from __future__ import annotations

import json
import math
import os
import sys
import time
import traceback
from typing import Callable, Dict, List, Sequence, Tuple

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

#: Serving networks and groups, as in ``examples/serve_multimodel.py``.
GROUPS = [["face_det", "selfie_seg", "hand_det"], ["pose_det", "yolov8n"]]

#: name -> (spatial, channels): the Table 6 input resolution, and the width
#: that puts the executable's MACs within 15% of Table 6.
SERVING_SHAPES = {
    "face_det": (128, 5),
    "selfie_seg": (256, 3),
    "hand_det": (192, 9),
    "pose_det": (224, 8),
    "yolov8n": (640, 8),
}

#: Largest relative L2 error of a served output against the float32
#: reference. bf16 keeps 8 significand bits: fp16/int8 genes store every
#: activation in bf16, and the TPU's default precision rounds each f32
#: convolution's operands to bf16. Over 12-24 layers that compounds to at
#: most 3.4% (whole networks in bf16 at these sizes, on a host CPU); a
#: tensor routed to the wrong argument is off by 70% or more.
SERVING_REL_L2_TOL = 0.1

#: Requests per group in the served window.
SERVING_REQUESTS = 8

_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"


def emit(line: str) -> None:
    print(line, flush=True)


class CompileLog:
    """Counts XLA executables built (compiled or loaded from the
    persistent cache) and the seconds spent on them."""

    def __init__(self) -> None:
        import jax

        self.count = 0
        self.seconds = 0.0
        self._jax = jax
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_: object) -> None:
        if event == _BACKEND_COMPILE:
            self.count += 1
            self.seconds += duration

    def close(self) -> None:
        self._jax.monitoring.unregister_event_duration_listener(
            self._on_event)


def _rel_err(a: Sequence[float], b: Sequence[float]) -> float:
    """Largest |a - b| over the compiled tier's tolerance (≤ 1 passes)."""
    from repro.core import COMPILED_ABS_TOL, COMPILED_REL_TOL

    worst = 0.0
    for x, y in zip(a, b, strict=True):
        if math.isinf(x) or math.isinf(y):
            if x != y:
                return math.inf
            continue
        bound = COMPILED_ABS_TOL + COMPILED_REL_TOL * max(abs(x), abs(y))
        worst = max(worst, abs(x - y) / bound)
    return worst


# -- search ------------------------------------------------------------------

def search_phase(compiles: CompileLog) -> List[str]:
    from repro.core import AnalyzerConfig, GAConfig, StaticAnalyzer
    from repro.core import batchsim, batchsim_compiled, build_scenario
    from repro.experiments import (
        SweepConfig, evaluate_scenario, generate_scenario_specs)
    from repro.experiments.evaluate import EvalContext

    failures: List[str] = []
    spec = generate_scenario_specs(1, seed=0)[0]
    ctx = EvalContext()
    emit(f"search.scenario {spec.name} groups={spec.groups}")

    def timed(fn: Callable[[], object]) -> Tuple[object, float, float, int]:
        c0, s0 = compiles.count, compiles.seconds
        t0 = time.perf_counter()
        out = fn()
        return (out, time.perf_counter() - t0, compiles.seconds - s0,
                compiles.count - c0)

    scal, scal_s, _, _ = timed(lambda: evaluate_scenario(
        spec, SweepConfig(), ctx))
    comp, comp_s, comp_cs, comp_n = timed(lambda: evaluate_scenario(
        spec, SweepConfig(use_batch=True, batch_engine="compiled"), ctx))
    emit(f"search.evaluate_scenario scalar wall_s={scal_s} "
         f"alpha_star={scal.alpha_star} alpha_star_best="
         f"{scal.alpha_star_best} satisfaction={scal.satisfaction}")
    emit(f"search.evaluate_scenario compiled wall_s={comp_s} "
         f"compile_s={comp_cs} compiles={comp_n} "
         f"alpha_star={comp.alpha_star} alpha_star_best="
         f"{comp.alpha_star_best} satisfaction={comp.satisfaction}")
    if (comp.alpha_star != scal.alpha_star
            or comp.alpha_star_best != scal.alpha_star_best):
        failures.append("evaluate_scenario: compiled α* differs from scalar")

    scenario = build_scenario(spec.name, [list(g) for g in spec.groups],
                              ctx.graphs, arrival=spec.arrival,
                              faults=spec.faults)

    def analyzer(**cfg: object) -> StaticAnalyzer:
        return StaticAnalyzer(scenario, ctx.processors, ctx.profiler,
                              ctx.comm_model, AnalyzerConfig(**cfg))

    ga_s, ga_s_wall, _, _ = timed(
        lambda: analyzer(ga=GAConfig(pop_size=40)).run_ga())
    ga_c, ga_c_wall, ga_cs, ga_n = timed(
        lambda: analyzer(ga=GAConfig(pop_size=40,
                                     batch_eval="compiled")).run_ga())
    front_s = sorted(s.fitness for s in ga_s.pareto)
    front_c = sorted(tuple(float(v) for v in s.fitness) for s in ga_c.pareto)
    emit(f"search.run_ga scalar wall_s={ga_s_wall} "
         f"generations={ga_s.generations} evaluations={ga_s.evaluations} "
         f"pareto={len(front_s)}")
    emit(f"search.run_ga compiled wall_s={ga_c_wall} compile_s={ga_cs} "
         f"compiles={ga_n} generations={ga_c.generations} "
         f"evaluations={ga_c.evaluations} pareto={len(front_c)}")
    if len(front_s) != len(front_c):
        failures.append("run_ga: compiled and scalar fronts differ in size")
    else:
        front_err = max((_rel_err(a, b) for a, b in zip(front_s, front_c)),
                        default=0.0)
        emit(f"search.run_ga front_err_over_tol={front_err}")
        if front_err > 1.0:
            failures.append("run_ga: fronts differ beyond the tolerance")

    # the device path on the compiled GA's own front, against the scalar
    # evaluation of the same candidates (fresh analyzers: no shared memo)
    cfg = AnalyzerConfig()
    for measured, n_req in ((False, cfg.fast_requests),
                            (True, cfg.accurate_requests)):
        dev = analyzer(batch_engine="compiled").objectives_batch(
            ga_c.pareto, num_requests=n_req, measured=measured)
        ref_an = analyzer()
        ref = [ref_an.objectives(s, num_requests=n_req, measured=measured)
               for s in ga_c.pareto]
        err = max(_rel_err(a, b) for a, b in zip(ref, dev))
        emit(f"search.objectives measured={measured} lanes={len(ref)} "
             f"err_over_tol={err}")
        if err > 1.0:
            failures.append(f"objectives(measured={measured}) beyond the "
                            f"compiled tolerance")

    totals = batchsim_compiled.totals
    emit(f"search.lockstep calls={totals['calls']} iters={totals['iters']} "
         f"itercap={totals['itercap']}")
    fallbacks = dict(batchsim.compiled_fallbacks)
    emit(f"search.fallbacks count={sum(fallbacks.values())} "
         f"by_reason={fallbacks}")
    if fallbacks:
        failures.append(f"compiled core fell back to numpy: {fallbacks}")
    return failures


# -- serving -----------------------------------------------------------------

def serving_plan():
    """The served scenario, its analyzer on the paper's profile tables, and
    the GA's best schedule (no device work: the tables are the costs)."""
    from repro.core import AnalyzerConfig, GAConfig, StaticAnalyzer
    from repro.core import build_scenario
    from repro.experiments.evaluate import EvalContext

    ctx = EvalContext()
    scenario = build_scenario("serve", GROUPS, ctx.graphs)
    analyzer = StaticAnalyzer(
        scenario, ctx.processors, ctx.profiler, ctx.comm_model,
        AnalyzerConfig(ga=GAConfig(pop_size=12, max_generations=10,
                                   min_generations=6, seed=1)))
    ga = analyzer.run_ga()
    best = min(ga.pareto, key=lambda s: sum(s.fitness))
    return analyzer, best


def serving_phase(compiles: CompileLog) -> List[str]:
    from repro.core import decode_solution, percentile
    from repro.core.scoring import deadline_satisfaction
    from repro.runtime import PuzzleRuntime, RuntimeConfig
    from repro.zoo import ExecutableMobileModel
    from repro.zoo.profiles import MODEL_SPECS

    failures: List[str] = []
    zoo = {name: ExecutableMobileModel(name, channels=c, spatial=s)
           for name, (s, c) in SERVING_SHAPES.items()}
    for name, m in zoo.items():
        convs = sum(layer.op_type != "add_merge" for layer in m.graph.layers)
        macs = m.spatial ** 2 * 9 * m.channels ** 2 * convs
        emit(f"serve.model {name} input={m.input_shape()} macs={macs} "
             f"table6_ratio={macs / MODEL_SPECS[name]['macs']}")

    t0 = time.perf_counter()
    analyzer, best = serving_plan()
    analyzer.executables = zoo
    graphs = list(analyzer.scenario.graphs)
    placed = decode_solution(best, graphs)
    layout = [[(p.processor, p.dtype, p.backend) for p in pl]
              for pl in placed]
    emit(f"serve.schedule search_s={time.perf_counter() - t0} "
         f"fitness={best.fitness} placement={layout}")

    t0 = time.perf_counter()
    refs = {name: m.reference_forward() for name, m in zoo.items()}
    emit(f"serve.reference host_s={time.perf_counter() - t0}")

    periods = list(analyzer.base_periods)
    groups = [list(g) for g in analyzer.scenario.groups]
    c0, s0 = compiles.count, compiles.seconds
    t0 = time.perf_counter()
    rt = PuzzleRuntime(graphs, best, analyzer.processors, zoo,
                       RuntimeConfig(tensor_pool=True, shared_buffer=True))
    try:
        emit(f"serve.load wall_s={time.perf_counter() - t0} "
             f"compile_s={compiles.seconds - s0} "
             f"executables={compiles.count - c0}")
        c0 = compiles.count
        t0 = time.perf_counter()
        states = rt.run_periodic(groups, periods,
                                 num_requests=SERVING_REQUESTS)
        window_s = time.perf_counter() - t0
        in_window = compiles.count - c0
        emit(f"serve.window wall_s={window_s} compiles={in_window} "
             f"periods_s={periods} requests_per_group={SERVING_REQUESTS}")
        if in_window:
            failures.append(f"{in_window} compilations inside the serving "
                            f"window")
        per_group = []
        for gid, glist in enumerate(states):
            ms = [st.makespan for st in glist]
            per_group.append(ms)
            emit(f"serve.group {gid} makespan_p50_s={percentile(ms, 50.0)} "
                 f"p99_s={percentile(ms, 99.0)} max_s={max(ms)}")
        emit(f"serve.satisfaction "
             f"{deadline_satisfaction(per_group, periods)}")
        emit(f"serve.runtime_stats {rt.stats()}")

        worst: Dict[str, float] = {}
        for glist in states:
            for st in glist:
                for net in st.networks:
                    model = zoo[graphs[net].name]
                    sink = graphs[net].num_layers - 1
                    for k, p in enumerate(rt.placed[net]):
                        if sink not in p.subgraph.layer_ids:
                            continue
                        out = st.outputs[(net, k)]
                        ix = model.boundary(p.subgraph.layer_ids)[1].index(
                            sink)
                        out = np.asarray(
                            out[ix] if isinstance(out, tuple) else out,
                            np.float32)
                        ref = refs[model.name]
                        err = float(np.linalg.norm(out - ref)
                                    / np.linalg.norm(ref))
                        worst[model.name] = max(worst.get(model.name, 0.0),
                                                err)
        emit(f"serve.outputs rel_l2_err={worst} tol={SERVING_REL_L2_TOL}")
        if len(worst) != len(zoo):
            failures.append(f"outputs checked for {sorted(worst)} only")
        bad = {n: e for n, e in worst.items()
               if not e <= SERVING_REL_L2_TOL}
        if bad:
            failures.append(f"served outputs off the reference: {bad}")
    finally:
        rt.close()

    t0 = time.perf_counter()
    costs = analyzer.measure_on_runtime(best)
    changed = analyzer.apply_measured_costs(costs)
    emit(f"serve.measure wall_s={time.perf_counter() - t0} "
         f"keys={len(costs)} changed={changed} measured_s={costs}")
    t0 = time.perf_counter()
    report = analyzer.validate_on_runtime(best, mode="real")
    emit(f"serve.validate wall_s={time.perf_counter() - t0} "
         f"report={report.summary()}")
    return failures


def main() -> int:
    import jax

    device = jax.devices()[0]
    if device.platform != "tpu":
        print(f"chip_smoke: no TPU; JAX found {device.platform!r}",
              file=sys.stderr)
        return 2
    from repro.compile_cache import use_compile_cache

    emit(f"compile_cache {use_compile_cache()}")
    emit(f"device platform={device.platform} kind={device.device_kind} "
         f"count={len(jax.devices())}")
    compiles = CompileLog()
    failures: List[str] = []
    try:
        for name, phase in (("search", search_phase),
                            ("serving", serving_phase)):
            t0 = time.perf_counter()
            try:
                failures += [f"{name}: {f}" for f in phase(compiles)]
            except Exception:
                traceback.print_exc()
                failures.append(f"{name}: raised")
            emit(f"{name}.phase wall_s={time.perf_counter() - t0}")
    finally:
        compiles.close()
    for f in failures:
        emit(f"FAILED {f}")
    if failures:
        return 1
    emit(json.dumps({"ok": True, "device": {
        "platform": device.platform, "kind": device.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
