"""The main path's device programs compile for a described TPU v5e.

Nothing runs here: the TPU compiler, which is installed, compiles for a
chip that is described and not attached, and refuses what the chip's
compiler would refuse (unsupported ops, programs that do not fit). The
topology is described inside a fixture, never at import, so every
pytest-xdist worker collects the same tests and only the worker given
this file loads the TPU library.
"""
import os
import re

import jax
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import (
    AnalyzerConfig,
    BatchLane,
    FaultSpec,
    NoiseModel,
    StaticAnalyzer,
    build_scenario,
)
from repro.core.batchsim_compiled import advance_fn, build_tables
from repro.experiments.evaluate import EvalContext
from repro.zoo import ExecutableMobileModel

#: GA width: a generation of pop 40 is 40 parents plus 40 offspring.
LANES = 80


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # the compiler otherwise writes its logs outside the checkout
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler or library in this install
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but can never be read back without one
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was_on)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def generation():
    """80 random candidates of a four-network, two-group §6.1 scenario."""
    ctx = EvalContext()
    scenario = build_scenario(
        "compile", [["face_det", "yolov8n"], ["hand_det", "selfie_seg"]],
        ctx.graphs)
    analyzer = StaticAnalyzer(scenario, ctx.processors, ctx.profiler,
                              ctx.comm_model, AnalyzerConfig())
    sols = [analyzer.factory.random_solution() for _ in range(LANES)]
    return analyzer, sols


#: Ceiling on the lock-step loop body's fusions at GA width, each a
#: kernel launched once per iteration on the chip. Indexed reads and
#: writes gave 201, 240 and 250 (and 24-40 dynamic-update-slices);
#: one-hot reads and writes gave 156, 174 and 182; ready queues as keys
#: in the pending-count table, in place of FIFO rings, give 110, 129 and
#: 137, and the ceilings leave ~10% of room above those.
FUSIONS = {"clean": 121, "noisy_dispatch": 142, "faulted": 151}


def _body_ops(text):
    """The while body's top-level operations of an optimized HLO module:
    ``(op, result shape, ops inside a fusion)`` per instruction."""
    comps, cur = {}, None
    for line in text.splitlines():
        m = re.match(r"^(?:ENTRY )?%?([\w.\-]+) .*\{$", line)
        if m and not line.startswith(" "):
            cur = comps.setdefault(m.group(1), [])
        elif line.startswith("}"):
            cur = None
        elif cur is not None and " = " in line:
            cur.append(line.strip())

    def parse(inst):
        rhs = inst.split(" = ", 1)[1]
        m = re.search(r" ([a-z][a-z0-9\-]*)\(", rhs)
        return (m.group(1), rhs[:m.start()]) if m else ("", rhs)

    bodies = [re.search(r"body=%?([\w.\-]+)", i).group(1)
              for c in comps.values() for i in c if " while(" in i]
    assert len(bodies) == 1, bodies
    out = []
    for inst in comps[bodies[0]]:
        op, shape = parse(inst)
        inner = []
        if op == "fusion":
            called = re.search(r"calls=%?([\w.\-]+)", inst).group(1)
            inner = [parse(i) for i in comps[called]]
        out.append((op, shape, inner))
    return out


def _lanes(analyzer, sols, kind):
    cfg = analyzer.cfg
    measured = kind != "clean"
    faults = FaultSpec(dropouts=((2, 0.02, 0.05),),
                       throttles=((0, 0.01, 0.03, 2.0),),
                       straggler_prob=0.1, straggler_shape=1.5)
    return [
        BatchLane(
            spec=analyzer.solution_spec(s),
            periods=list(analyzer.base_periods),
            num_requests=(cfg.accurate_requests if measured
                          else cfg.fast_requests),
            noise=NoiseModel(cfg.noise.sigma_by_kind, seed=i)
            if measured else None,
            dispatch_overhead=cfg.dispatch_overhead if measured else 0.0,
            dispatch_pid=cfg.dispatch_pid,
            faults=faults if kind == "faulted" else None,
        )
        for i, s in enumerate(sols)
    ]


@pytest.mark.parametrize("kind", ["clean", "noisy_dispatch", "faulted"])
def test_advance_compiles_for_v5e(one_chip, generation, kind):
    """The lock-step loop at GA width, for the GA's fast (clean) and
    accurate (noise + dispatch load) evaluations and under faults
    (stragglers, a throttle and a dropout). Its body reads and writes
    its small per-lane axes with one-hot masks and keeps its ready
    queues as keys in a per-(request, subgraph) table: no
    dynamic-update-slice, no scatter, no gather but the noise and
    straggler tables' reads, and fewer fusions than the ceiling."""
    analyzer, sols = generation
    tables = build_tables(_lanes(analyzer, sols, kind),
                          analyzer.scenario.groups, analyzer.processors)
    assert tables is not None
    _, _, any_noise, any_fault, any_strag, any_dispatch = tables.flags
    measured = kind != "clean"
    assert (any_noise, any_dispatch) == (measured, measured)
    assert (any_fault, any_strag) == (kind == "faulted",) * 2
    with jax.enable_x64(True):
        args = {
            k: jax.ShapeDtypeStruct(np.shape(v), np.asarray(v).dtype,
                                    sharding=one_chip)
            for k, v in tables.tab.items()
        }
        compiled = advance_fn().lower(tables.flags, args).compile()
    assert compiled.memory_analysis() is not None

    body = _body_ops(compiled.as_text())
    every = [(op, shape) for op, shape, inner in body
             for op, shape in [(op, shape)] + inner]
    assert not [s for op, s in every if op == "dynamic-update-slice"]
    assert not [s for op, s in every if op == "scatter"]
    # a 64-bit table is read as two 32-bit halves: up to two gathers per
    # kept read (the noise and straggler tables)
    gathers = [op for op, _, inner in body
               if op == "gather" or any(o == "gather" for o, _ in inner)]
    assert len(gathers) <= 2 * (any_noise + any_strag), gathers
    fusions = sum(op == "fusion" for op, _, _ in body)
    assert fusions <= FUSIONS[kind], fusions


@pytest.fixture(scope="module")
def yolov8n():
    """yolov8n's executable at its Table 6 input resolution."""
    return ExecutableMobileModel("yolov8n", channels=8, spatial=640)


@pytest.mark.parametrize("dtype", ["fp32", "fp16"])
def test_yolov8n_subgraph_compiles_for_v5e(one_chip, yolov8n, dtype):
    fn, example = yolov8n.build_subgraph_fn([0, 1, 2, 3], dtype)
    args = [jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)
            for a in example]
    compiled = jax.jit(fn).lower(*args).compile()
    assert compiled.memory_analysis() is not None
