"""Mean coordinator dispatch per task, in microseconds: the program's span
``puzzle.serve.dispatch`` around ``Coordinator._dispatch`` (routing the
subgraph's inputs, its ``TaskRecord`` and the submit to its worker),
summed in ``repro.runtime.engine.totals``.

The counter is a total since the process started: set-up's warm-up
requests and warm-up window run at the cell's rate, like the window. The
span holds no compile. A program without the span reads nothing."""
import importlib


def read(r):
    if r.get("kind") != "serve":
        return None
    totals = getattr(importlib.import_module("repro.runtime.engine"),
                     "totals", {})
    ns, n = totals.get("puzzle.serve.dispatch.ns"), totals.get(
        "puzzle.serve.dispatch.n")
    if ns is None or not n:
        return None
    return ns / n / 1e3
