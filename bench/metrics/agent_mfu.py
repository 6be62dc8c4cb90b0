"""Model FLOPs of the window's turns (prefill and decode) over the time
from the first turn's start to the last one's end, over the bf16 peak, %."""
from bench import layer


def read(ctx):
    rec = ctx.record
    if not rec.get("turns"):
        return None
    return (100.0 * layer.agent_flops(ctx) / rec["span_s"]
            / ctx.peaks["bf16_flops_per_s"])
