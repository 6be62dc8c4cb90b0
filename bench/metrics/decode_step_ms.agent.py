"""Device time of the decode-step program per step, ms."""
from bench import layer


def read(ctx):
    secs, steps = layer.decode_step(ctx)
    return secs / steps * 1e3
