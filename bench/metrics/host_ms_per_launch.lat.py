"""Host wall time inside the benchmark's calls into the runtime (submit,
poll, flush), per launch of the window, ms."""
from bench import layer


def read(ctx):
    v = layer.per_launch(ctx, ctx.record.get("host_call_s", 0.0))
    return None if v is None else v * 1e3
