"""Bytes the slab cache copied in (cache_fill_bytes) per launch, KiB."""
from bench import layer


def read(ctx):
    fill = layer.counter(ctx, "cache_fill_bytes")
    if fill is None:
        return None
    v = layer.per_launch(ctx, fill)
    return None if v is None else v / 1024.0
