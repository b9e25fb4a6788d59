"""How late the benchmark's generator submitted: the 99th percentile of
submit time minus due time, in milliseconds."""
import harness


def read(r):
    if r.get("kind") != "serve" or not r["lateness_s"]:
        return None
    return harness.percentile(r["lateness_s"], 99.0) * 1e3
