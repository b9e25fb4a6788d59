"""Engine: thin abstraction over execution backends (paper §5.1).

Engines hide framework details from Workers — the paper wraps Qualcomm AI
Engine Direct, ORT and TVM; here the backends are XLA-jit (``default``,
fast path), XLA-jit with a second compilation profile (``xnnpack``
analogue), and un-jitted op-by-op eval (``nnapi`` analogue — reliably the
slowest, reproducing Table 2's ordering). New engines register via
``ENGINE_REGISTRY``.
"""
from __future__ import annotations

import functools
import re
import threading
import time
from collections import Counter, deque
from typing import Any, Callable, Deque, Dict, Optional, Sequence, Tuple

import jax

from ..core.chromosome import PlacedSubgraph
from ..spans import span

#: Span totals of the serving path over every runtime in this process
#: (``<span>.ns`` self time, ``<span>.n`` count): ``puzzle.serve.dispatch``
#: (``Coordinator._dispatch``), ``puzzle.serve.stage`` (a worker's staging
#: thread, per task), ``puzzle.serve.execute`` (:meth:`Engine.execute`,
#: up to ``block_until_ready``) and ``puzzle.serve.load`` (:meth:`Engine.load`
#: of a subgraph not yet loaded: building, jitting and warming its program,
#: every compile or persistent-cache load included). The counter
#: ``puzzle.serve.load.param_bytes`` sums the weight bytes the loaded
#: programs hold, where the executable states them (``param_bytes``).
totals: Counter = Counter()


def program_name(placed: PlacedSubgraph) -> str:
    """The served program's name: ``puzzle_<network>_<first>_<last>``, its
    network and the first and last of its layers. XLA names the program
    ``jit_`` and this, so a trace's time splits by subgraph."""
    ids = placed.subgraph.layer_ids
    net = re.sub(r"\W", "_", placed.subgraph.graph.name)
    return f"puzzle_{net}_{min(ids)}_{max(ids)}"


class Engine:
    """Loads subgraphs once, executes many times (keyed by Merkle hash).

    Every execution is timed (injectable ``timer``, default
    ``time.perf_counter``) and recorded per key in ``exec_times`` — the keys
    *are* Merkle profile keys, so these samples feed straight back into the
    :class:`~repro.core.profiler.ProfileDB` as device-in-the-loop
    measurements (``PuzzleRuntime.measured_costs``). Load-time warm-up runs
    are not recorded, and only the most recent ``MAX_SAMPLES`` per key are
    kept — a long-lived serving runtime must not grow without bound.
    """

    name = "base"
    MAX_SAMPLES = 64

    def __init__(self, timer: Callable[[], float] = time.perf_counter):
        self._handles: Dict[str, Tuple[Callable, Tuple]] = {}
        self._lock = threading.Lock()
        self._timer = timer
        self.exec_times: Dict[str, Deque[float]] = {}

    def load(self, placed: PlacedSubgraph, executables: Dict[str, Any]) -> str:
        key = placed.profile_key()
        with self._lock:
            if key not in self._handles:
                model = executables[placed.subgraph.graph.name]
                ids = placed.subgraph.layer_ids
                with span("puzzle.serve.load", totals):
                    fn, example = model.build_subgraph_fn(ids, placed.dtype)
                    fn.__name__ = program_name(placed)
                    self._handles[key] = (self._prepare(fn, example), example)
                if hasattr(model, "param_bytes"):
                    totals["puzzle.serve.load.param_bytes"] += \
                        model.param_bytes(ids, placed.dtype)
        return key

    def _prepare(self, fn: Callable, example: Tuple) -> Callable:
        raise NotImplementedError

    def arg_dtypes(self, key: str) -> Tuple:
        """The argument dtypes ``key``'s handle was warmed with at load."""
        return tuple(a.dtype for a in self._handles[key][1])

    def execute(self, key: str, inputs: Optional[Sequence] = None):
        fn, example = self._handles[key]
        args = inputs if inputs is not None else example
        with span("puzzle.serve.execute", totals):
            t0 = self._timer()
            out = fn(*args)
            jax.block_until_ready(out)
            elapsed = self._timer() - t0
        samples = self.exec_times.get(key)
        if samples is None:
            samples = self.exec_times[key] = deque(maxlen=self.MAX_SAMPLES)
        samples.append(elapsed)
        return out


class JitEngine(Engine):
    """XLA-compiled execution (the Qualcomm-SDK/ORT-default analogue)."""

    name = "default"

    def _prepare(self, fn, example):
        jitted = jax.jit(fn)
        jitted(*example)  # warm the cache at load time, like AOT compilation
        return jitted


class FastMathJitEngine(Engine):
    """Second compiled profile (XNNPACK analogue): same semantics, a
    different kernel selection — reduced matmul precision."""

    name = "xnnpack"

    def _prepare(self, fn, example):
        @functools.wraps(fn)
        def wrapped(*a):
            with jax.default_matmul_precision("bfloat16"):
                return fn(*a)
        jitted = jax.jit(wrapped)
        jitted(*example)
        return jitted


class EagerEngine(Engine):
    """Un-jitted op-by-op execution — the NNAPI-like slow path."""

    name = "nnapi"

    def _prepare(self, fn, example):
        # run once at load, like the jitted engines: every op's executable
        # is then cached before the first timed execute
        jax.block_until_ready(fn(*example))
        return fn


ENGINE_REGISTRY: Dict[str, Callable[[], Engine]] = {
    "default": JitEngine,
    "xnnpack": FastMathJitEngine,
    "nnapi": EagerEngine,
}


def make_engine(backend: str) -> Engine:
    return ENGINE_REGISTRY.get(backend, JitEngine)()
