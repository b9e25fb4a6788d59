"""Mean load per loaded subgraph, in seconds: the program's span
``puzzle.serve.load`` around ``Engine.load``'s build, jit and warm-up of a
subgraph's program (a compile, or a load from the persistent compile cache),
summed in ``repro.runtime.engine.totals``. It is the part of ``setup_s``
that grows with the served programs and their weights, which they hold as
constants.

The counter is a total since the process started, and every load happens
in set-up. A program without the span reads nothing."""
import importlib


def read(r):
    if r.get("kind") != "serve":
        return None
    totals = getattr(importlib.import_module("repro.runtime.engine"),
                     "totals", {})
    ns, n = totals.get("puzzle.serve.load.ns"), totals.get(
        "puzzle.serve.load.n")
    if ns is None or not n:
        return None
    return ns / n / 1e9
