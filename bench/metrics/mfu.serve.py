"""Whole served step's share of the chip's peak: two operations per MAC of
every network execute completed for the window's requests, over the
window's length times the bf16 peak."""


def read(r):
    if r.get("kind") != "serve" or r["window_s"] <= 0:
        return None
    return 100.0 * r["window_flops"] / (r["window_s"]
                                        * r["peaks"]["bf16_flops"])
