"""``replay.py`` restates the program's replay semantics: on the host CPU it
gives the same objectives and α* as the program's own reference simulator,
on GA fronts and random split schedules, under each search traffic mix."""
import pytest

import harness
import replay

SEARCH_CELLS = sorted(w for w in harness.Spec.load().workloads
                      if w.startswith("search."))


@pytest.mark.parametrize("workload", SEARCH_CELLS)
def test_replay_equals_program_reference(workload):
    from repro.core import StaticAnalyzer, build_scenario
    from repro.experiments.evaluate import EvalContext

    cell = harness.resolve(harness.Spec.load(), workload, 1, 0.0, False)
    search = harness.driver_module(cell)
    first = cell.traffic["searches"][0]
    (arrival, faults), (plain_arrival, plain_faults) = search.scenario_parts(
        cell.traffic, first)
    ctx = EvalContext()
    scenario = build_scenario(cell.config["name"],
                              [list(g) for g in cell.config["groups"]],
                              ctx.graphs, arrival=arrival, faults=faults)
    cfg = search.analyzer_config(cell.traffic, first)
    cfg.engine, cfg.ga.pop_size, cfg.ga.max_generations = "reference", 6, 2
    cfg.ga.min_generations, cfg.ga.batch_eval = 2, False
    program = StaticAnalyzer(scenario, ctx.processors, ctx.profiler,
                             ctx.comm_model, cfg)
    ref = replay.Deployment(
        scenario.graphs, scenario.groups, ctx.processors, ctx.profiler,
        ctx.comm_model, replay.Settings.of(cell.traffic["evaluation"]),
        plain_arrival, plain_faults)
    assert ref.base_periods == list(program.base_periods)
    ev = cell.traffic["evaluation"]
    sols = list(program.run_ga().pareto) + [
        program.factory.random_solution() for _ in range(8)]
    for sol in sols:
        for measured in (False, True):
            n = ev["accurate_requests" if measured else "fast_requests"]
            assert ref.objectives(sol, measured) == program.objectives(
                sol, num_requests=n, measured=measured)
        assert ref.alpha_star(sol) == program.saturation(sol).alpha_star

