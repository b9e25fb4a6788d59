"""A plain replay of a schedule: the reference the search cells compare with.

It restates the runtime's replay semantics (Puzzle, paper §4.3 and §6.1-6.3)
on its own: per-group request sources (periodic, or Poisson from one seeded
stream), subgraph tasks released when their producers finish, one
non-preemptive worker per processor draining a priority queue (dispatch
work first, then task priority, then release order), communication at
processor boundaries and (de)quantization at dtype boundaries, the
lognormal execution noise of measured evaluations, stragglers, throttle
windows and dropouts, the fitness objectives, the XRBench score and the
α*-bisection. It takes from the program only its types (the decoded
placement of a chromosome, the layer graph and its edges, the processors)
and its profile tables (each subgraph's execution time, the communication
model's coefficients), and none of its simulators.

``Settings`` holds what the traffic file states about evaluation: request
counts, the dispatch load and the noise of a measured evaluation.
"""
from __future__ import annotations

import heapq
import math
import random
from dataclasses import dataclass
from typing import Callable, Dict, Generator, List, Optional, Sequence, Tuple

#: the paper's α lattice and saturation threshold (§6.2)
ALPHA_LO, ALPHA_HI, ALPHA_STEP = 0.2, 6.0, 0.05
THRESHOLD = 0.995
CONFIRM = 4
RT_K = 15.0
#: a dropped request's finite stand-in in the objectives
CAP = 1e6
#: slack of the base periods (§6.1)
EPSILON = 0.1


@dataclass(frozen=True)
class Settings:
    fast_requests: int
    accurate_requests: int
    dispatch_overhead_s: float
    dispatch_pid: int
    input_home_pid: int
    noise_sigma: Tuple[Tuple[str, float], ...]
    noise_sigma_other: float

    @classmethod
    def of(cls, evaluation: dict) -> "Settings":
        return cls(evaluation["fast_requests"],
                   evaluation["accurate_requests"],
                   evaluation["dispatch_overhead_s"],
                   evaluation["dispatch_pid"], evaluation["input_home_pid"],
                   tuple(sorted(evaluation["noise_sigma"].items())),
                   evaluation["noise_sigma_other"])

    def sigma(self, kind: str) -> float:
        return dict(self.noise_sigma).get(kind, self.noise_sigma_other)


# -- arrivals and faults -------------------------------------------------------

def arrival_times(kind: str, seed: int, periods: Sequence[float],
                  n: int) -> List[List[float]]:
    """Per-group arrival times: ``rid * period``, or Poisson with mean gap
    ``period`` from one stream drawn group by group. Each time is nudged
    up to lie strictly after the time the source realizes for the one
    before it (``prev + (t - prev)``)."""
    rng = random.Random(seed)
    out = []
    for period in periods:
        if kind == "periodic":
            raw = [rid * period for rid in range(n)]
        elif kind == "poisson":
            raw, t = [], 0.0
            for rid in range(n):
                raw.append(t)
                if rid + 1 < n and period > 0.0:
                    t = t + rng.expovariate(1.0 / period)
        else:
            raise ValueError(f"arrival kind {kind!r} is not replayed")
        times: List[float] = []
        prev: Optional[float] = None
        for t in raw:
            if prev is None:
                t = max(t, 0.0)
                real = t
            else:
                if t <= prev:
                    t = math.nextafter(prev, math.inf)
                real = prev + (t - prev)
                while real <= prev:
                    t = math.nextafter(t, math.inf)
                    real = prev + (t - prev)
            times.append(t)
            prev = real
        out.append(times)
    return out


def horizon(tables: Sequence[Sequence[float]], periods: Sequence[float],
            n: int) -> float:
    base = max((n + 2) * max(periods) * 4.0, 1.0)
    last = max([t[-1] for t in tables if t] + [0.0])
    extra = last + max(periods) * 8.0
    return base if extra <= base else extra


class Faults:
    """Stragglers (one uniform draw per delivered task, Pareto inflation
    below ``prob``), then throttle factors, then a dropout's stall."""

    def __init__(self, spec: dict, seed: int) -> None:
        self.rng = random.Random(seed)
        self.prob = float(spec.get("straggler_prob", 0.0))
        shape = float(spec.get("straggler_shape", 0.0))
        self.inv_shape = 1.0 / shape if self.prob > 0.0 and shape > 0 else 0.0
        self.drops: Dict[int, List[Tuple[float, float]]] = {}
        for pid, start, repair in sorted(
                ((int(p), float(s), r) for p, s, r in spec.get("dropouts", ())),
                key=lambda d: (d[1], d[0])):
            end = math.inf if repair is None else start + float(repair)
            self.drops.setdefault(pid, []).append((start, end))
        self.throttles: Dict[int, List[Tuple[float, float, float]]] = {}
        for pid, t0, t1, f in sorted(
                ((int(p), float(a), float(b), float(f))
                 for p, a, b, f in spec.get("throttles", ())),
                key=lambda w: (w[1], w[2], w[0])):
            self.throttles.setdefault(pid, []).append((t0, t1, f))

    def service(self, pid: int, now: float, t: float) -> Tuple[float, float]:
        if self.prob > 0.0:
            u = self.rng.random()
            if u < self.prob:
                v = u / self.prob
                if v >= 1.0:
                    v = math.nextafter(1.0, 0.0)
                t *= (1.0 - v) ** (-self.inv_shape)
        for t0, t1, f in self.throttles.get(pid, ()):
            if t0 <= now < t1:
                t *= f
        stall = 0.0
        for start, end in self.drops.get(pid, ()):
            if start <= now < end:
                stall = end - now
                break
        return t, stall


# -- costs -----------------------------------------------------------------------

def comm_cost(comm, nbytes: float) -> float:
    """RPC overhead, linear on each side of the knee, plus transfer."""
    if nbytes <= 0:
        return 0.0
    if nbytes < comm.knee:
        rpc = max(0.0, comm.a_lo + comm.b_lo * nbytes)
    else:
        rpc = max(0.0, comm.a_hi + comm.b_hi * nbytes)
    return rpc + nbytes / comm.bandwidth


def quant_cost(comm, nbytes: float) -> float:
    """One streaming read and write over the tensor, plus a fixed 10 µs."""
    if nbytes <= 0:
        return 0.0
    return 2.0 * nbytes / comm.bandwidth + 10e-6


class Schedule:
    """One decoded placement with its task costs and dependencies."""

    def __init__(self, placed, profiler, comm, input_home_pid: int,
                 exec_cost: Callable[[float], float] = float) -> None:
        self.placed = placed
        self.deps: List[List[List[int]]] = []
        self.succs: List[List[List[int]]] = []
        self.costs: List[List[float]] = []
        for net_placed in placed:
            owner = {lid: k for k, p in enumerate(net_placed)
                     for lid in p.subgraph.layer_ids}
            deps = [sorted({owner[e.src] for e in p.subgraph.in_cut_edges()})
                    for p in net_placed]
            succs: List[List[int]] = [[] for _ in net_placed]
            for k, ds in enumerate(deps):
                for d in ds:
                    succs[d].append(k)
            costs = []
            for k, p in enumerate(net_placed):
                comm_s, quant_s = 0.0, 0.0
                for e in p.subgraph.in_cut_edges():
                    prod = net_placed[owner[e.src]]
                    if prod.processor != p.processor:
                        comm_s += comm_cost(comm, e.bytes_)
                    if prod.dtype != p.dtype:
                        quant_s += quant_cost(comm, e.bytes_)
                if not deps[k] and p.processor != input_home_pid:
                    comm_s += comm_cost(comm, p.subgraph.input_bytes())
                costs.append((comm_s, quant_s,
                              exec_cost(profiler.subgraph_time(p))))
            self.deps.append(deps)
            self.succs.append(succs)
            self.costs.append(costs)


# -- the replay --------------------------------------------------------------------

class _Loop:
    """Processes are generators that yield ``("wait", seconds)`` or
    ``("get", pid)``; events at one time run in the order they were made."""

    def __init__(self) -> None:
        self.now = 0.0
        self.heap: List[tuple] = []
        self.seq = 0
        self.queues: Dict[int, List[tuple]] = {}
        self.qseq = 0
        self.getters: Dict[int, List[Generator]] = {}

    def resume_after(self, delay: float, proc: Generator, value=None) -> None:
        heapq.heappush(self.heap, (self.now + delay, self.seq, proc, value))
        self.seq += 1

    def start(self, proc: Generator) -> None:
        self.resume_after(0.0, proc)

    def put(self, pid: int, item, priority: tuple) -> None:
        q = self.queues[pid]
        heapq.heappush(q, (priority, self.qseq, item))
        self.qseq += 1
        if self.getters[pid]:
            proc = self.getters[pid].pop(0)
            self.resume_after(0.0, proc, heapq.heappop(q)[2])

    def _step(self, proc: Generator, value) -> None:
        try:
            cmd, arg = proc.send(value)
        except StopIteration:
            return
        if cmd == "wait":
            self.resume_after(arg, proc)
        elif self.queues[arg]:
            self.resume_after(0.0, proc, heapq.heappop(self.queues[arg])[2])
        else:
            self.getters[arg].append(proc)

    def run(self, until: float) -> None:
        while self.heap and self.heap[0][0] <= until:
            t, _, proc, value = heapq.heappop(self.heap)
            self.now = t
            self._step(proc, value)


def replay(schedule: Schedule, processors, groups: Sequence[Sequence[int]],
           periods: Sequence[float], n: int, settings: Settings,
           measured: bool, arrival: Optional[dict] = None,
           faults: Optional[dict] = None) -> List[List[float]]:
    """Makespans per group of ``n`` requests per group (``inf``: not done
    by the horizon). ``measured`` adds the noise (seed 0) and the
    dispatch load of a measured evaluation."""
    loop = _Loop()
    dispatch = settings.dispatch_overhead_s if measured else 0.0
    noise = random.Random(0)
    fault = (Faults(faults, faults["seed"])
             if faults and (faults.get("dropouts") or faults.get("throttles")
                            or faults.get("straggler_prob", 0.0) > 0.0)
             else None)
    placed = schedule.placed
    pids = [p.pid for p in processors]
    for pid in pids:
        loop.queues[pid] = []
        loop.getters[pid] = []
    # per request: [arrival, first start, last finish, done, total]
    req: Dict[Tuple[int, int], list] = {}
    pending: Dict[Tuple[int, int, int, int], int] = {}
    seq = [0]

    def release(g: int, r: int, net: int, k: int) -> None:
        if dispatch > 0 and settings.dispatch_pid in loop.queues:
            seq[0] += 1
            loop.put(settings.dispatch_pid, None, (-1, 0, seq[0]))
        seq[0] += 1
        p = placed[net][k]
        loop.put(p.processor, (g, r, net, k), (0, p.priority, seq[0]))

    def worker(proc) -> Generator:
        sigma = settings.sigma(proc.kind) if measured else 0.0
        while True:
            item = yield "get", proc.pid
            if item is None:
                yield "wait", dispatch
                continue
            g, r, net, k = item
            comm_s, quant_s, exec_s = schedule.costs[net][k]
            if sigma > 0.0:
                exec_s *= math.exp(noise.gauss(-0.5 * sigma * sigma, sigma))
            stall = 0.0
            if fault is not None:
                exec_s, stall = fault.service(proc.pid, loop.now, exec_s)
            rec = req[(g, r)]
            rec[1] = min(rec[1], loop.now)
            total = exec_s + quant_s + comm_s
            if stall > 0.0:
                total = stall + total
            yield "wait", total
            rec[3] += 1
            rec[2] = max(rec[2], loop.now)
            for s in schedule.succs[net][k]:
                pending[(g, r, net, s)] -= 1
                if pending[(g, r, net, s)] == 0:
                    release(g, r, net, s)

    def source(g: int, nets: Sequence[int], times: Sequence[float]):
        for r in range(n):
            if times[r] > loop.now:
                yield "wait", times[r] - loop.now
            req[(g, r)] = [loop.now, math.inf, 0.0, 0,
                           sum(len(placed[m]) for m in nets)]
            for m in nets:
                for k in range(len(placed[m])):
                    pending[(g, r, m, k)] = len(schedule.deps[m][k])
                    if not schedule.deps[m][k]:
                        release(g, r, m, k)

    tables = arrival_times(arrival["kind"] if arrival else "periodic",
                           arrival["seed"] if arrival else 0, periods, n)
    for proc in processors:
        loop.start(worker(proc))
    for g, nets in enumerate(groups):
        loop.start(source(g, nets, tables[g]))
    loop.run(horizon(tables, periods, n))
    out: List[List[float]] = [[] for _ in groups]
    for (g, _), (arr, first, last, done, total) in sorted(req.items()):
        out[g].append(last - min(first, arr) if done >= total else math.inf)
    return out


# -- scores -------------------------------------------------------------------------

def percentile(values: Sequence[float], q: float) -> float:
    """Linear interpolation between closest ranks; a sample hit exactly
    is returned as it is."""
    v = sorted(values)
    if not v:
        return math.inf
    pos = (len(v) - 1) * q / 100.0
    lo = int(math.floor(pos))
    hi = min(lo + 1, len(v) - 1)
    frac = pos - lo
    if frac == 0.0 or v[lo] == v[hi]:
        return v[lo]
    return v[lo] * (1 - frac) + v[hi] * frac


def objectives(per_group: Sequence[Sequence[float]]) -> Tuple[float, ...]:
    """(mean, p90) of each group's makespans, a dropped request at CAP."""
    out: List[float] = []
    for ms in per_group:
        ms = [min(m, CAP) for m in ms]
        out += [sum(ms) / len(ms), percentile(ms, 90.0)]
    return tuple(out)


def score(per_group: Sequence[Sequence[float]],
          deadlines: Sequence[float]) -> float:
    """XRBench: mean over groups of (mean sigmoid realtime score) x (share
    of requests within the deadline)."""
    total = 0.0
    for ms, dl in zip(per_group, deadlines):
        if not ms:
            continue
        rt = 0.0
        for m in ms:
            if math.isinf(m) or dl <= 0:
                continue
            x = RT_K * (m / dl - 1.0)
            rt += 0.0 if x > 60 else 1.0 if x < -60 else 1.0 / (1.0 + math.exp(x))
        rt /= len(ms)
        total += rt * (sum(1 for m in ms if m <= dl) / len(ms))
    return total / len(per_group) if per_group else 0.0


def alpha_star(score_at: Callable[[float], float]) -> float:
    """Smallest lattice α whose score reaches the threshold and stays there
    for the next ``CONFIRM`` lattice points (bisect, then confirm; a dip
    restarts the bisection above it); ``inf`` if the top is unsaturated."""
    n = int(round((ALPHA_HI - ALPHA_LO) / ALPHA_STEP))
    seen: Dict[int, float] = {}

    def ok(i: int) -> bool:
        if i not in seen:
            seen[i] = score_at(round(ALPHA_LO + ALPHA_STEP * i, 4))
        return seen[i] >= THRESHOLD

    if not ok(n):
        return math.inf
    floor = -1
    while True:
        a, b = floor, n
        while b - a > 1:
            mid = (a + b) // 2
            if ok(mid):
                b = mid
            else:
                a = mid
        dip = next((j for j in range(b + 1, min(b + CONFIRM + 1, n))
                    if not ok(j)), None)
        if dip is None:
            return round(ALPHA_LO + ALPHA_STEP * b, 4)
        floor = dip


# -- one deployment -------------------------------------------------------------------

class Deployment:
    """A scenario's groups and processors with the profile tables: base
    periods, and each solution's objectives and α*."""

    def __init__(self, graphs, groups, processors, profiler, comm,
                 settings: Settings, arrival: Optional[dict] = None,
                 faults: Optional[dict] = None,
                 exec_cost: Callable[[float], float] = float) -> None:
        from repro.core.chromosome import BACKENDS, DTYPES, PlacedSubgraph

        self.graphs, self.groups = list(graphs), [list(g) for g in groups]
        self.processors, self.profiler, self.comm = processors, profiler, comm
        self.settings, self.arrival, self.faults = settings, arrival, faults
        self.exec_cost = exec_cost
        best = []
        for net, graph in enumerate(self.graphs):
            whole = graph.partition([0] * graph.num_edges)[0]
            best.append(min(
                profiler.subgraph_time(PlacedSubgraph(
                    subgraph=whole, network=net, processor=p.pid, dtype=d,
                    backend=b, priority=net))
                for p in processors for d in DTYPES for b in BACKENDS))
        self.base_periods = [sum(best[m] for m in g) * len(self.groups)
                             * (1 + EPSILON) for g in self.groups]

    def schedule(self, solution) -> Schedule:
        from repro.core.chromosome import decode_solution

        return Schedule(decode_solution(solution, self.graphs), self.profiler,
                        self.comm, self.settings.input_home_pid,
                        self.exec_cost)

    def makespans(self, schedule: Schedule, alpha: float, n: int,
                  measured: bool) -> List[List[float]]:
        return replay(schedule, self.processors, self.groups,
                      [alpha * p for p in self.base_periods], n,
                      self.settings, measured, self.arrival, self.faults)

    def objectives(self, solution, measured: bool) -> Tuple[float, ...]:
        """Fitness at α 1: ``fast_requests`` clean, or ``accurate_requests``
        measured."""
        s = self.settings
        n = s.accurate_requests if measured else s.fast_requests
        return objectives(self.makespans(self.schedule(solution), 1.0, n,
                                         measured))

    def alpha_star(self, solution) -> float:
        sched = self.schedule(solution)
        n = self.settings.accurate_requests
        return alpha_star(lambda a: score(
            self.makespans(sched, a, n, True),
            [a * p for p in self.base_periods]))
