"""Profiler trace of a run, reduced to the numbers the metrics read.

:class:`Tracer` records one JAX profiler trace around the part of a window
a driver chooses, with a ``bench.window`` host span marking it on the
trace's own clock. :func:`reduce_trace` turns the trace into a
:class:`TraceSummary`:

* ``busy_s``: the union of the intervals in which an operation ran on a
  device (line ``XLA Ops`` of each ``/device:TPU:N`` plane) inside the
  window, averaged over the devices that ran anything; ``window_s`` the
  window's length;
* ``programs``: device seconds per XLA program (line ``XLA Modules``), by
  name without its numeric suffix;
* ``ops``: device seconds per operation (name and result type);
* ``idle_gaps``: the gaps between busy intervals of the first device,
  each named after the benchmark's host span that covers most of it.
"""
from __future__ import annotations

import re
import shutil
from dataclasses import dataclass, field
from pathlib import Path
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

WINDOW_SPAN = "bench.window"
#: Host spans the benchmark's drivers write; idle gaps are named by them.
HOST_SPANS = ("serve.generator", "serve.drain", "search.run_ga",
              "search.alpha")

Interval = Tuple[int, int]


@dataclass
class TraceSummary:
    window_s: float
    busy_s: float
    programs: Dict[str, float] = field(default_factory=dict)
    ops: Dict[str, float] = field(default_factory=dict)
    idle_gaps: List[Tuple[str, float]] = field(default_factory=list)

    def breakdown(self, top: int = 10) -> dict:
        ops = sorted(self.ops.items(), key=lambda kv: -kv[1])[:top]
        return {"device_ops": [[k, v] for k, v in ops],
                "idle_gaps": [[k, v] for k, v in self.idle_gaps[:top]]}

    def program_seconds(self, pattern: str) -> float:
        """Device seconds of the programs whose name matches ``pattern``."""
        rx = re.compile(pattern)
        return sum(v for k, v in self.programs.items() if rx.search(k))


def merge(intervals: Iterable[Interval]) -> List[Interval]:
    """Sorted, disjoint union of half-open intervals."""
    out: List[List[int]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def clip(intervals: Iterable[Interval], lo: int, hi: int) -> List[Interval]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if b > lo and a < hi]


def gaps(busy: Sequence[Interval], lo: int, hi: int) -> List[Interval]:
    """The complement of disjoint sorted ``busy`` within ``[lo, hi)``."""
    out = []
    t = lo
    for a, b in busy:
        if a > t:
            out.append((t, a))
        t = max(t, b)
    if t < hi:
        out.append((t, hi))
    return out


def _program_name(name: str) -> str:
    return re.sub(r"\(\d+\)$", "", name)


def _op_name(name: str) -> str:
    """An operation's name and result type, without its layout and
    operands: ``%fusion.3 = bf16[640,1,8,81,8]``."""
    head, _, rest = name.partition(" = ")
    kind = "tuple" if rest.startswith("(") else rest.split("{", 1)[0]
    return f"{head} = {kind}" if rest else head[:120]


def _events(line) -> List[Tuple[str, int, int]]:
    return [(e.name, int(e.start_ns), int(e.start_ns + e.duration_ns))
            for e in line.events]


def reduce_planes(planes) -> TraceSummary:
    """Reduce decoded planes (``ProfileData.planes`` or stand-ins with the
    same ``name``/``lines``/``events`` attributes)."""
    host: Dict[str, List[Interval]] = {}
    devices = []
    for plane in planes:
        if re.fullmatch(r"/device:TPU:\d+", plane.name):
            devices.append({ln.name: _events(ln) for ln in plane.lines})
        elif plane.name.startswith("/host:"):
            # host lines are threads, and several may share a name
            for ln in plane.lines:
                for name, a, b in _events(ln):
                    if name == WINDOW_SPAN or name in HOST_SPANS:
                        host.setdefault(name, []).append((a, b))
    if not host.get(WINDOW_SPAN):
        raise ValueError(f"trace has no {WINDOW_SPAN!r} span: the traced "
                         f"window is not marked")
    lo = min(a for a, _ in host[WINDOW_SPAN])
    hi = max(b for _, b in host[WINDOW_SPAN])
    busy_per_device = []
    programs: Dict[str, float] = {}
    ops: Dict[str, float] = {}
    first_busy: Optional[List[Interval]] = None
    for lines in devices:
        op_events = lines.get("XLA Ops", [])
        busy = merge(clip([(a, b) for _, a, b in op_events], lo, hi))
        if not busy:
            continue
        busy_per_device.append(sum(b - a for a, b in busy) / 1e9)
        if first_busy is None:
            first_busy = busy
        # operations and programs count their time inside the window only
        for name, a, b in op_events:
            if b > lo and a < hi:
                key = _op_name(name)
                ops[key] = ops.get(key, 0.0) + (min(b, hi) - max(a, lo)) / 1e9
        for name, a, b in lines.get("XLA Modules", []):
            if b > lo and a < hi:
                key = _program_name(name)
                programs[key] = (programs.get(key, 0.0)
                                 + (min(b, hi) - max(a, lo)) / 1e9)
    idle: List[Tuple[str, float]] = []
    for a, b in gaps(first_busy or [], lo, hi):
        best, best_overlap = "none", 0
        for span, ivs in host.items():
            if span == WINDOW_SPAN:
                continue
            overlap = sum(max(0, min(b, y) - max(a, x)) for x, y in ivs)
            if overlap > best_overlap:
                best, best_overlap = span, overlap
        idle.append((best, (b - a) / 1e9))
    idle.sort(key=lambda kv: -kv[1])
    busy_s = (sum(busy_per_device) / len(busy_per_device)
              if busy_per_device else 0.0)
    return TraceSummary(window_s=(hi - lo) / 1e9, busy_s=busy_s,
                        programs=programs, ops=ops, idle_gaps=idle)


def reduce_trace(path: Path) -> TraceSummary:
    """Reduce one ``.xplane.pb`` file."""
    import jax

    return reduce_planes(jax.profiler.ProfileData.from_file(str(path)).planes)


def find_trace(log_dir: Path) -> Path:
    found = sorted(Path(log_dir).glob("**/*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


class Tracer:
    """One profiler trace, started and stopped by a driver; the trace is
    reduced and its directory removed by :meth:`summary`."""

    def __init__(self, log_dir: Path) -> None:
        self.log_dir = Path(log_dir)
        self._span = None

    def start(self) -> None:
        import jax

        shutil.rmtree(self.log_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(self.log_dir), profiler_options=opts)
        self._span = jax.profiler.TraceAnnotation(WINDOW_SPAN)
        self._span.__enter__()

    def stop(self) -> None:
        import jax

        self._span.__exit__(None, None, None)
        jax.profiler.stop_trace()

    def summary(self) -> TraceSummary:
        try:
            return reduce_trace(find_trace(self.log_dir))
        finally:
            shutil.rmtree(self.log_dir, ignore_errors=True)
