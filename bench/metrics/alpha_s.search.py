"""Seconds of the α*-bisection (``population_saturation``) per search,
from the benchmark's own span around the call."""
import statistics


def read(r):
    if r.get("kind") != "search" or not r["searches"]:
        return None
    return statistics.mean(s["alpha_s"] for s in r["searches"])
