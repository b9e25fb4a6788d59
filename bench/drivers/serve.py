"""Serving driver: an open-loop periodic load on ``PuzzleRuntime``.

Everything specific to the served networks comes from the configuration's
network family (``harness.family_module``; the functions are listed in
``bench/families/convnet.py``). Set-up builds the family's executables
(weights fixed by the configuration, each network's input from
``--seed``), picks the served schedule with a GA of fixed size and seed on
the paper's profile tables over the family's graphs, and loads it into
``PuzzleRuntime``, which compiles and warms every placed subgraph. A few
requests per group are then served one at a time, and an open-loop warm-up
window at the cell's rate runs before the measured one: the first window
after loading pays one-off costs.

The window is an open loop: group ``g`` falls due every
``alpha * base_period[g]`` seconds, with ``alpha = alpha_knee / load`` (the
knee from the configuration, the load from the traffic file), and the
generator calls ``PuzzleRuntime.infer`` at each due time. A request's
latency runs from its due time to the finish of its last subgraph; a
request that fails or is not done ``drain_s`` after the window counts as
failed, with infinite latency.

The outputs of every sink of each network (``graph.sinks()``: each layer
with no out-edge) are kept for a seeded sample of requests (the first, the
last and a few drawn from ``--seed``, per group); every other request's
outputs are dropped when it completes. After the window each kept output
is compared with the family's float32 reference.
"""
from __future__ import annotations

import gc
import math
import random
import threading
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Any, Deque, Dict, List, Optional, Tuple

import harness


@dataclass
class Request:
    group: int
    index: int
    due: float
    submitted: float
    state: Any
    keep: bool
    sinks: Dict[str, List[Any]] = field(default_factory=dict)
    error: Optional[BaseException] = None


class Driver:
    def __init__(self, cell: harness.Cell) -> None:
        self.cell = cell
        self.config = cell.config
        self.traffic = cell.traffic
        self.family = harness.family_module(cell.config)
        self.requests: List[Request] = []
        self.window: Tuple[float, float] = (0.0, 0.0)
        self.trace_window: Optional[Tuple[float, float]] = None
        self.host_from: Optional[float] = None
        self._lock = threading.Lock()
        self._last: Dict[int, Deque[Request]] = {}
        self._kept: List[Request] = []

    # -- set-up ----------------------------------------------------------------
    def build_zoo(self) -> Dict[str, Any]:
        return self.family.executables(self.config, self.cell.seed)

    def plan(self):
        """The served schedule: the GA's best on the profile tables."""
        from repro.core import (AnalyzerConfig, GAConfig, StaticAnalyzer,
                                build_scenario)
        from repro.experiments.evaluate import EvalContext

        ctx = EvalContext()
        scenario = build_scenario(
            self.config["name"], [list(g) for g in self.config["groups"]],
            self.family.graphs(self.config))
        p = self.config["plan"]
        analyzer = StaticAnalyzer(
            scenario, ctx.processors, ctx.profiler, ctx.comm_model,
            AnalyzerConfig(ga=GAConfig(
                pop_size=p["pop_size"], max_generations=p["generations"],
                min_generations=p["generations"], seed=p["seed"])))
        best = min(analyzer.run_ga().pareto, key=lambda s: sum(s.fitness))
        return analyzer, best

    def setup(self) -> None:
        from repro.core import decode_solution
        from repro.runtime import PuzzleRuntime, RuntimeConfig

        self.zoo = self.build_zoo()
        analyzer, best = self.plan()
        self.graphs = list(analyzer.scenario.graphs)
        self.groups = [list(g) for g in analyzer.scenario.groups]
        placed = decode_solution(best, self.graphs)
        self.cell.emit("placement " + repr(
            [[(self.graphs[n].name, len(p.subgraph.layer_ids), p.processor,
               p.dtype, p.backend) for p in pl] for n, pl in enumerate(placed)]))
        self.base_periods = list(analyzer.base_periods)
        self.rt = PuzzleRuntime(self.graphs, best, analyzer.processors,
                                self.zoo, RuntimeConfig())
        self.alpha = self.config["alpha_knee"] / self.traffic["load"]
        self.set_alpha(self.alpha)
        # where each of a network's sinks comes out, in graph.sinks()
        # order: (subgraph, index in its outputs, None if it has one)
        self.sink_of: Dict[int, List[Tuple[int, Optional[int]]]] = {}
        self.work: Dict[Tuple[int, int], Tuple[float, float]] = {}
        for net, pl in enumerate(self.rt.placed):
            name = self.graphs[net].name
            shape = self.config["networks"][name]
            where = {}
            for k, p in enumerate(pl):
                ids = p.subgraph.layer_ids
                outs = self.zoo[name].boundary(ids)[1]
                for lid in outs:
                    where[lid] = (k, outs.index(lid) if len(outs) > 1
                                  else None)
                self.work[(net, k)] = self.family.work(name, ids, shape,
                                                       p.dtype)
            self.sink_of[net] = [where[s] for s in self.graphs[net].sinks()]
        for g, nets in enumerate(self.groups):
            for _ in range(self.traffic["warmup_requests"]):
                self.rt.infer(nets, group=g).future.result(timeout=120)
        self.run_window(self.traffic["warmup_seconds"])
        self.requests = []

    def set_alpha(self, alpha: float) -> None:
        self.alpha = alpha
        self.periods = [alpha * p for p in self.base_periods]
        self.cell.emit(f"alpha {alpha} periods_s {self.periods}")

    # -- the window ------------------------------------------------------------
    def _sample(self, seconds: float) -> List[set]:
        """Request indices per group whose outputs are kept (besides the
        last few): the first few and a few drawn from ``--seed``."""
        s = self.traffic["sample"]
        rng = random.Random(harness.stable_seed(self.cell.seed, "sample"))
        out = []
        for period in self.periods:
            n = max(1, int(seconds / period))
            picks = set(range(min(s["first"], n)))
            picks |= set(rng.sample(range(n), min(s["random"], n)))
            out.append(picks)
        return out

    def _sinks(self, st) -> Dict[str, List[Any]]:
        out = {}
        for net in st.networks:
            out[self.graphs[net].name] = [
                st.outputs[(net, k)] if ix is None
                else st.outputs[(net, k)][ix]
                for k, ix in self.sink_of[net]]
        return out

    def _on_done(self, req: Request, fut) -> None:
        st = req.state
        exc = fut.exception()
        if exc is not None:
            req.error = exc
        elif req.keep:
            req.sinks = self._sinks(st)
        else:
            with self._lock:
                last = self._last[req.group]
                if len(last) == last.maxlen:
                    last[0].sinks.clear()
                req.sinks = self._sinks(st)
                last.append(req)
        st.outputs.clear()

    def run_window(self, seconds: float, tracer=None) -> None:
        """The open-loop window. With ``tracer``, its first
        ``trace_seconds`` are traced; the host-side readings then come
        from the requests due a settling time after the trace stopped, so
        that neither the tracer's overhead nor its stop shows in them."""
        import jax

        keep = self._sample(seconds)
        self._last = {g: deque(maxlen=self.traffic["sample"]["last"])
                      for g in range(len(self.groups))}
        t0 = time.perf_counter() + 0.01
        end = t0 + seconds
        trace_end = t0 + self.traffic["trace_seconds"]
        count = [0] * len(self.groups)
        due = [t0] * len(self.groups)
        if tracer is not None:
            tracer.start()
            traced_from = time.perf_counter()
        span = jax.profiler.TraceAnnotation("serve.generator")
        span.__enter__()
        while True:
            g = min(range(len(self.groups)), key=lambda i: due[i])
            d = due[g]
            if d >= end:
                break
            if tracer is not None and self.trace_window is None \
                    and d >= trace_end:
                span.__exit__(None, None, None)
                paused = time.perf_counter()
                self._stop_trace(tracer, traced_from)
                # the generator pauses while the trace is written: the
                # schedule resumes where it stopped, with no burst of
                # requests that fell due meanwhile
                paused = time.perf_counter() - paused
                t0, end = t0 + paused, end + paused
                due = [x + paused for x in due]
                span = jax.profiler.TraceAnnotation("serve.generator")
                span.__enter__()
            wait = d - time.perf_counter()
            if wait > 0:
                time.sleep(wait)
            st = self.rt.infer(self.groups[g], group=g)
            req = Request(g, count[g], d, time.perf_counter(), st,
                          count[g] in keep[g])
            self.requests.append(req)
            st.future.add_done_callback(lambda f, r=req: self._on_done(r, f))
            count[g] += 1
            due[g] = t0 + count[g] * self.periods[g]
        closed = time.perf_counter()
        span.__exit__(None, None, None)
        with jax.profiler.TraceAnnotation("serve.drain"):
            limit = closed + self.traffic["drain_s"]
            for req in self.requests:
                try:
                    req.state.future.result(
                        timeout=max(0.0, limit - time.perf_counter()))
                except Exception as e:  # a failed request counts, below
                    req.error = req.error or e
        if tracer is not None and self.trace_window is None:
            self._stop_trace(tracer, traced_from)
            self.host_from = t0
        self.window = (t0, closed)
        lat = self.latencies()
        late = [r.submitted - r.due for r in self.requests]
        self.cell.emit(
            f"window requests={len(lat)} p50_ms="
            f"{harness.percentile(lat, 50.0) * 1e3} p95_ms="
            f"{harness.percentile(lat, 95.0) * 1e3} max_ms={max(lat) * 1e3} "
            f"late_p99_ms={harness.percentile(late, 99.0) * 1e3} "
            f"late_max_ms={max(late) * 1e3}")

    def _stop_trace(self, tracer, traced_from: float) -> None:
        self.trace_window = (traced_from, time.perf_counter())
        tracer.stop()
        self.host_from = time.perf_counter() + self.traffic["settle_s"]

    def release(self) -> None:
        """Close the runtime and free the device before the reference."""
        import numpy as np

        for req in self.requests:
            if req.sinks:
                req.sinks = {k: [np.asarray(v, np.float32) for v in vs]
                             for k, vs in req.sinks.items()}
                self._kept.append(req)
        self.records = list(self.rt.coordinator.trace)
        self.rt.close()
        del self.rt
        gc.collect()

    # -- correctness ---------------------------------------------------------
    def reference(self, mode: str = "f32") -> Dict[str, List[Any]]:
        return self.family.reference(self.config, self.cell.seed, mode)

    def rel_l2(self, refs: Dict[str, List[Any]]) -> Dict[str, float]:
        """Worst rel-L2 of each network's kept outputs against ``refs``."""
        worst: Dict[str, float] = {}
        for req in self._kept:
            for name, outs in req.sinks.items():
                err = self.family.worst_rel_l2(name, outs, refs[name])
                worst[name] = max(worst.get(name, 0.0), err)
        return worst

    def check(self) -> List[harness.Check]:
        worst = self.rel_l2(self.reference())
        self.cell.emit(f"compare kept={len(self._kept)} rel_l2={worst}")
        missing = len(self.config["networks"]) - len(worst)
        return [
            harness.Check("failed_requests", self.attempted_failed()[1], 0),
            harness.Check("networks_unchecked", missing, 0),
            harness.Check("rel_l2", max(worst.values(), default=math.inf),
                          self.config["limits"]["rel_l2"]),
        ]

    # -- numbers ---------------------------------------------------------------
    def latencies(self) -> List[float]:
        return [r.state.finish - r.due
                if r.error is None and r.state.finish is not None
                else math.inf for r in self.requests]

    def attempted_failed(self):
        lat = self.latencies()
        return len(lat), sum(1 for v in lat if math.isinf(v))

    def end_to_end(self) -> Dict[str, float]:
        lat = [v for v in self.latencies()]
        p95 = harness.percentile(lat, 95.0)
        if math.isinf(p95):  # reported as the drain limit; correct is false
            p95 = self.traffic["drain_s"]
        return {"makespan_p95_ms": p95 * 1e3}

    def readings(self) -> dict:
        t0, t1 = self.window
        if self.host_from is not None:
            t0 = min(self.host_from, t1)
        done = [r for r in self.requests if r.due >= t0
                and r.error is None and r.state.finish is not None]
        tasks = [t for r in done for t in r.state.task_records]
        macs = {name: self.family.macs(name, shape)
                for name, shape in self.config["networks"].items()}
        flops = sum(2.0 * macs[self.graphs[n].name]
                    for r in done for n in r.state.networks)
        traced = []
        if self.trace_window is not None:
            a, b = self.trace_window
            traced = [self.work[(rec.network, rec.sg_index)]
                      for rec in self.records
                      if rec.started is not None and a <= rec.started <= b]
        return {
            "kind": "serve",
            "lateness_s": [r.submitted - r.due for r in self.requests
                           if r.due >= t0],
            "wait_s": [t["wait_s"] for t in tasks],
            "exec_s": [t["exec_s"] for t in tasks],
            "window_s": t1 - t0,
            "window_flops": flops,
            "traced_work": traced,
        }


class Control(Driver):
    """The control in the program's place: every kept output is the
    family's reference in its ``fp8`` mode, one precision below the
    bfloat16 the served genes run in (for ``convnet``, each convolution's
    operands rounded to float8 e4m3)."""

    def release(self) -> None:
        super().release()
        low = self.reference("fp8")
        for req in self._kept:
            req.sinks = {name: low[name] for name in req.sinks}


def toy(cell: harness.Cell) -> None:
    """Shrink a resolved cell in place to the size the CPU tests serve:
    every network at its family's toy size, a rate far below the knee, one
    warm-up request per group and a short trace."""
    harness.family_module(cell.config).toy(cell.config)
    cell.config["alpha_knee"] = 20.0
    cell.traffic.update(warmup_requests=1, trace_seconds=0.3, settle_s=0.1)
