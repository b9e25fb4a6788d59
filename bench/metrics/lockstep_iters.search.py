"""Lock-step loop iterations per search: the program's counter
``batchsim_compiled.totals["iters"]``, read around each search."""
import statistics


def read(r):
    if r.get("kind") != "search" or not r["searches"]:
        return None
    return statistics.mean(s["iters"] for s in r["searches"])
