"""Model FLOPs of the traced window (``flops.py``: real nodes and edges,
no padding) over the window's length and the chip's published bf16 peak,
in %."""


def read(record):
    t, work = record["trace"], record.get("flops")
    peak = record["peak"].get("bf16_flops_per_s")
    if not t or not work or not peak or t["window_s"] <= 0:
        return None
    return 100.0 * work / t["window_s"] / (peak * t["devices"])
