"""Share of the traced search in which no operation ran on the device."""


def read(r):
    trace = r.get("trace")
    if r.get("kind") != "search" or trace is None or trace.window_s <= 0:
        return None
    return 100.0 * (1.0 - trace.busy_s / trace.window_s)
