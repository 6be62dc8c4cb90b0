"""Share of the slab cache's lookups that hit (cache_hits over hits and
misses), %: the useful share of the cache's work."""
from bench import layer


def read(ctx):
    hits = layer.counter(ctx, "cache_hits")
    misses = layer.counter(ctx, "cache_misses")
    if hits is None or hits + misses == 0:
        return None
    return 100.0 * hits / (hits + misses)
