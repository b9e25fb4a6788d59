"""Share of the lock-step loop's lane-iterations that did work: the
program's counters ``lane_events`` (over real lanes, the iterations in
which the lane had an event at or before its horizon) over ``lane_slots``
(iterations times the padded width, what the device computed), both in
``batchsim_compiled.totals``, in percent.

The counters are totals since the process started; the search cells run a
fixed multiset of searches, so every run of a cell reads the same share. A
program without the counters reads nothing."""
import importlib


def read(r):
    if r.get("kind") != "search":
        return None
    batch = getattr(importlib.import_module("repro.core.batchsim_compiled"),
                    "totals", {})
    events, slots = batch.get("lane_events"), batch.get("lane_slots")
    if events is None or not slots:
        return None
    return 100.0 * events / slots
