"""Seconds per search spent building the host lane tables: the program's
span ``puzzle.batch.tables`` (around ``build_tables`` in
``batchsim_compiled.run_batch_compiled``, summed in
``batchsim_compiled.totals``) over the searches counted by the span
``puzzle.ga.run`` (``StaticAnalyzer.run_ga``, in ``ga.totals``).

The counters are totals since the process started. Set-up's warm-up runs
each of the cell's fixed searches once and the window runs them in whole
rounds, so the ratio per search is the same for either; the check's
re-evaluation of the fronts after the window adds its few batches. The
span holds no compile: compiles happen inside the device call, which it
leaves out. A program without the span reads nothing."""
import importlib


def read(r):
    if r.get("kind") != "search":
        return None
    batch = getattr(importlib.import_module("repro.core.batchsim_compiled"),
                    "totals", {})
    ga = getattr(importlib.import_module("repro.core.ga"), "totals", {})
    ns, searches = batch.get("puzzle.batch.tables.ns"), ga.get(
        "puzzle.ga.run.n")
    if not ns or not searches:
        return None
    return ns / searches / 1e9
