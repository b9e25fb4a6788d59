"""Mean wait of a task in its worker's queue, from release to the start of
its execute (the program's ``task_records[].wait_s``), in milliseconds."""
import statistics


def read(r):
    if r.get("kind") != "serve" or not r["wait_s"]:
        return None
    return statistics.mean(r["wait_s"]) * 1e3
