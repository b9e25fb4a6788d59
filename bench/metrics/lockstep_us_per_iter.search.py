"""Device microseconds per lock-step iteration: the device time of the
``advance`` program in the trace over the iterations of the traced search."""


def read(r):
    trace = r.get("trace")
    iters = r.get("traced_iters")
    if r.get("kind") != "search" or trace is None or not iters:
        return None
    seconds = trace.program_seconds(r"advance")
    if seconds <= 0.0:
        return None
    return seconds / iters * 1e6
