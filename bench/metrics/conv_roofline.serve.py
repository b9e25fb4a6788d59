"""Share of the chip's roofline reached by the served convolutions.

For every subgraph execute that started in the traced window, the least
time the chip could take is max(operations / peak FLOP/s, bytes / HBM
bandwidth), from unpadded shapes (``convnet.subgraph_work``). Their sum
over the device time of all programs in the traced window. Every served
subgraph in these cells is memory-bound by this count."""


def read(r):
    trace = r.get("trace")
    if r.get("kind") != "serve" or trace is None or not r["traced_work"]:
        return None
    device_s = sum(trace.programs.values())
    if device_s <= 0.0:
        return None
    peak = r["peaks"]
    least = sum(max(f / peak["bf16_flops"], b / peak["hbm_bytes_per_s"])
                for f, b in r["traced_work"])
    return 100.0 * least / device_s
