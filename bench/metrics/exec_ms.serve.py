"""Mean engine execute per task, in milliseconds: the program's
``task_records[].exec_s``, the host clock around ``Engine.execute`` (which
ends in ``block_until_ready``), so dispatch is included."""
import statistics


def read(r):
    if r.get("kind") != "serve" or not r["exec_s"]:
        return None
    return statistics.mean(r["exec_s"]) * 1e3
