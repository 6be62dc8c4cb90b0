"""p95 of the runtime's serve_queue_wait_seconds (submit to launch, on the
benchmark's clock), ms. Moves query_p95_ms."""
from bench import layer


def read(ctx):
    h = layer.histogram(ctx, "serve_queue_wait_seconds")
    if h is None or h.count == 0:
        return None
    return h.percentile(95) * 1e3
