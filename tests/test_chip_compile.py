"""The main path's device programs compile for a described TPU v5e.

Nothing runs here: the TPU compiler, which is installed, compiles for a
chip that is described and not attached, and refuses what the chip's
compiler would refuse (unsupported ops, programs that do not fit). The
topology is described inside a fixture, never at import, so every
pytest-xdist worker collects the same tests and only the worker given
this file loads the TPU library.
"""
import os

import jax
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import (
    AnalyzerConfig,
    BatchLane,
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


@pytest.mark.parametrize("measured", [False, True],
                         ids=["clean", "noisy_dispatch"])
def test_advance_compiles_for_v5e(one_chip, generation, measured):
    """The lock-step loop at GA width, for the GA's fast (clean) and
    accurate (noise + dispatch load) evaluations."""
    analyzer, sols = generation
    cfg = analyzer.cfg
    lanes = [
        BatchLane(
            spec=analyzer.solution_spec(s),
            periods=list(analyzer.base_periods),
            num_requests=(cfg.accurate_requests if measured
                          else cfg.fast_requests),
            noise=NoiseModel(cfg.noise.sigma_by_kind, seed=i)
            if measured else None,
            dispatch_overhead=cfg.dispatch_overhead if measured else 0.0,
            dispatch_pid=cfg.dispatch_pid,
        )
        for i, s in enumerate(sols)
    ]
    tables = build_tables(lanes, analyzer.scenario.groups,
                          analyzer.processors)
    assert tables is not None
    any_noise, any_dispatch = tables.flags[4], tables.flags[7]
    assert (any_noise, any_dispatch) == (measured, measured)
    with jax.enable_x64(True):
        args = {
            k: jax.ShapeDtypeStruct(np.shape(v), np.asarray(v).dtype,
                                    sharding=one_chip)
            for k, v in tables.tab.items()
        }
        compiled = advance_fn().lower(tables.flags, args).compile()
    assert compiled.memory_analysis() is not None


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
