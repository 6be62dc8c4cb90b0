"""Required INT8 operations of the window's queries per second over the
chip's INT8 peak, %: the whole serving step's share of the peak."""
from bench import layer


def read(ctx):
    return layer.retrieval_mfu(ctx)
