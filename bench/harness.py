"""What one benchmark run is made of, found by name from ``BENCHMARK.json``.

A cell (``workloads`` entry) names a configuration and a traffic mix. The
configuration's file is given in ``configs`` and names its network family,
``bench/families/<family>.py`` (``convnet`` where it names none); the
traffic mix is ``bench/traffic/<traffic>.json`` and names its driver,
``bench/drivers/<driver>.py``; each per-layer metric is
``bench/metrics/<name>.py``. Adding any of these takes new files and new
entries only.

A driver module defines ``Driver(cell)`` with ``setup()``,
``run_window(seconds, tracer)``, ``release()``, ``check()``,
``end_to_end()`` and ``readings()``; ``Control(cell)``, the same run
with the control of ``correct`` in the program's place; and ``toy(cell)``,
which shrinks a resolved cell in place to the size the CPU tests run. A
family module's functions are listed in ``bench/families/convnet.py``. A
metric module defines ``read(readings) -> float | None`` (``None``:
nothing to read in this run).
"""
from __future__ import annotations

import importlib.util
import json
import math
import sys
from dataclasses import dataclass, field
from pathlib import Path
from types import ModuleType
from typing import Callable, Dict, List, Optional

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


@dataclass
class Cell:
    """One cell, resolved: its entries and the contents of its files."""

    name: str
    workload: dict
    config: dict
    traffic: dict
    seed: int
    seconds: float
    trace: bool
    emit: Callable[[str], None] = print


@dataclass
class Check:
    """One number compared with its limit; ``ok`` iff ``value <= limit``."""

    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return self.value <= self.limit


@dataclass
class Spec:
    """``BENCHMARK.json``, indexed by name."""

    raw: dict
    configs: Dict[str, dict] = field(default_factory=dict)
    workloads: Dict[str, dict] = field(default_factory=dict)

    @classmethod
    def load(cls, path: Path = ROOT / "BENCHMARK.json") -> "Spec":
        raw = json.loads(path.read_text())
        return cls(raw, {c["name"]: c for c in raw["configs"]},
                   {w["name"]: w for w in raw["workloads"]})

    def end_to_end(self, cell: str) -> List[dict]:
        """The end-to-end metrics this cell reports."""
        return [m for m in self.raw["end_to_end"]
                if cell in m.get("workloads", [cell])]

    def per_layer(self, cell: str) -> List[dict]:
        """The per-layer metrics read in this cell's traced run."""
        reported = {m["name"] for m in self.end_to_end(cell)}
        return [m for m in self.raw["per_layer"]
                if (cell in m["workloads"] if "workloads" in m
                    else m["moves"] in reported)]


def _load_json(rel: str) -> dict:
    return json.loads((ROOT / rel).read_text())


def traffic_path(traffic: str) -> Path:
    return BENCH / "traffic" / f"{traffic}.json"


def load_module(path: Path) -> ModuleType:
    """Import a file by path (names may hold dots, as metric names do)."""
    if not path.is_file():
        raise FileNotFoundError(f"benchmark file missing: {path}")
    spec = importlib.util.spec_from_file_location(
        f"bench_{path.parent.name}_{path.stem.replace('.', '_')}", path)
    assert spec is not None and spec.loader is not None
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod


def resolve(spec: Spec, workload: str, seed: int, seconds: float,
            trace: bool, emit: Callable[[str], None] = print) -> Cell:
    if workload not in spec.workloads:
        raise KeyError(f"unknown workload {workload!r}; "
                       f"known: {sorted(spec.workloads)}")
    w = spec.workloads[workload]
    config = _load_json(spec.configs[w["config"]]["file"])
    traffic = json.loads(traffic_path(w["traffic"]).read_text())
    return Cell(workload, w, config, traffic, seed, seconds, trace, emit)


def driver_module(cell: Cell) -> ModuleType:
    return load_module(BENCH / "drivers" / f"{cell.traffic['driver']}.py")


#: The network family of a configuration that names none.
DEFAULT_FAMILY = "convnet"


def family_module(config: dict) -> ModuleType:
    name = config.get("family", DEFAULT_FAMILY)
    return load_module(BENCH / "families" / f"{name}.py")


def metric_module(name: str) -> ModuleType:
    return load_module(BENCH / "metrics" / f"{name}.py")


def read_per_layer(spec: Spec, cell: str, readings: dict) -> Dict[str, dict]:
    """Each per-layer metric of the cell that finds something to read."""
    out: Dict[str, dict] = {}
    for m in spec.per_layer(cell):
        value = metric_module(m["name"]).read(readings)
        if value is None:
            continue
        if not math.isfinite(value):
            raise ValueError(f"metric {m['name']} read {value}")
        out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def percentile(values: List[float], q: float) -> float:
    """Nearest-rank percentile: the smallest value with at least ``q``
    percent of the values at or below it."""
    if not values:
        raise ValueError("percentile of no values")
    v = sorted(values)
    return v[max(0, math.ceil(q / 100.0 * len(v)) - 1)]


def stable_seed(*parts: object) -> int:
    """A 31-bit seed from any parts (the driver's seeds exceed 32 bits)."""
    import hashlib

    h = hashlib.sha256(repr(parts).encode()).digest()
    return int.from_bytes(h[:4], "little") & 0x7FFFFFFF


class CompileLog:
    """Counts XLA executables built (compiled or loaded from the
    persistent cache) and the seconds spent on them."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self) -> None:
        import jax

        self.count = 0
        self.seconds = 0.0
        self._jax = jax
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_: object) -> None:
        if event == self.EVENT:
            self.count += 1
            self.seconds += duration

    def close(self) -> None:
        self._jax.monitoring.unregister_event_duration_listener(
            self._on_event)


def device_info() -> dict:
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peak_bytes() -> Optional[int]:
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.devices()]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None
