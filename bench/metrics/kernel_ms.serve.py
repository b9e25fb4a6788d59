"""Device milliseconds per served execute: the device time of the served
subgraph programs in the trace (named ``jit_puzzle_<network>_<first
layer>_<last layer>`` by the program) over the executes that started in
the traced window. Beside ``exec_ms.serve``, the host clock around the same
executes, it splits an execute into kernel time and the host's dispatch
and wait. A program whose served programs carry no such name reads
nothing."""


def read(r):
    trace = r.get("trace")
    if r.get("kind") != "serve" or trace is None or not r["traced_work"]:
        return None
    seconds = trace.program_seconds(r"^jit_puzzle_")
    if seconds <= 0.0:
        return None
    return seconds / len(r["traced_work"]) * 1e3
