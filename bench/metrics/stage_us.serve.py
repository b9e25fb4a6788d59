"""Mean input staging per task on a worker's staging thread, in
microseconds: the program's span ``puzzle.serve.stage`` in
``Worker._quant_loop`` (the dtype conversion through the tensor pool and
the ``device_put`` of each input), summed in
``repro.runtime.engine.totals``. A task whose network is served whole
stages no input.

The counter is a total since the process started: set-up's warm-up
requests and warm-up window run at the cell's rate, like the window. The
span holds no compile. A program without the span reads nothing."""
import importlib


def read(r):
    if r.get("kind") != "serve":
        return None
    totals = getattr(importlib.import_module("repro.runtime.engine"),
                     "totals", {})
    ns, n = totals.get("puzzle.serve.stage.ns"), totals.get(
        "puzzle.serve.stage.n")
    if ns is None or not n:
        return None
    return ns / n / 1e3
