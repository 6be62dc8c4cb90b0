"""p95 over every request sent in the window, each timed from when it was
due on the schedule to when its answer was seen, ms. Not the end-to-end
metric: a single program that compiles or loads inside the window (the
runtime's slab fills come in many sizes) moves it by an order of
magnitude (PERF.md)."""
import numpy as np


def read(ctx):
    lat = ctx.record.get("latency_s")
    if lat is None or len(lat) == 0:
        return None
    return float(np.percentile(np.asarray(lat) * 1e3, 95))
