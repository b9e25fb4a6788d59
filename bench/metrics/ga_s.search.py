"""Seconds of ``run_ga`` per search: the GA host loop with its candidate
evaluation, from the benchmark's own span around the call."""
import statistics


def read(r):
    if r.get("kind") != "search" or not r["searches"]:
        return None
    return statistics.mean(s["ga_s"] for s in r["searches"])
