"""p95 of how late the load generator sent requests against their Poisson
schedule, ms. Moves query_p95_ms: a late generator offers less load."""
from bench import layer


def read(ctx):
    return layer.p95_ms(ctx.record.get("send_late_s", ()))
