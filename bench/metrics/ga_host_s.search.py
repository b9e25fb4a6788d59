"""Seconds per search of the GA's own host work: the self time of the
program's spans ``puzzle.ga.mate`` (shuffle and mating), ``puzzle.ga.local``
(local search) and ``puzzle.ga.select`` (non-dominated sorts and NSGA-III
selection), all in ``GeneticScheduler.run`` and summed in ``ga.totals``,
over the searches counted by the span ``puzzle.ga.run``.

Self time leaves out the evaluations the operators call (span
``puzzle.ga.eval``), so no device call and no compile is in it. The
counters are totals since the process started; set-up's warm-up runs each
of the cell's fixed searches once and the window runs them in whole
rounds, so the ratio per search is the same for either. A program without
the spans reads nothing."""
import importlib

SPANS = ("puzzle.ga.mate", "puzzle.ga.local", "puzzle.ga.select")


def read(r):
    if r.get("kind") != "search":
        return None
    ga = getattr(importlib.import_module("repro.core.ga"), "totals", {})
    searches = ga.get("puzzle.ga.run.n")
    if not searches or any(ga.get(f"{s}.ns") is None for s in SPANS):
        return None
    return sum(ga[f"{s}.ns"] for s in SPANS) / searches / 1e9
