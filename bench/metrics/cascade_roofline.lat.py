"""Least time of the required cascade work (bench/work.py) over the device
time of the fleet's launch programs, %."""
from bench import layer


def read(ctx):
    return layer.cascade_roofline(ctx)
