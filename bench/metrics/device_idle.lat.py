"""Share of the window in which no operation ran on the device, %."""
from bench import layer


def read(ctx):
    return layer.device_idle(ctx)
