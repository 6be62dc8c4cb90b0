"""Least time of the KV cascade's required work (bench/work.py) over the
device time of the decode-step program, %. The program has no named
scopes yet, so the denominator is the whole decode step: projections and
MLP included."""
from bench import layer, work


def read(ctx):
    secs, steps = layer.decode_step(ctx)
    least = work.least_time_s(layer.kv_step_work(ctx).scaled(steps),
                              ctx.peaks["int8_ops_per_s"],
                              ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * least / secs
