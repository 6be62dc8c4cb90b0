"""Arithmetic shared by the per-layer metric readers in `bench/metrics/`.

Each reader is `read(ctx) -> float | None`, where `ctx` is
`harness.Context`: `record` (what the window measured), `reduction` (the
trace), `config` and `peaks`. A reader that finds nothing to
read returns None, and the metric is left out of the result line.
"""
from __future__ import annotations

import numpy as np

from bench import trace, work

# The programs a fleet launch runs, by XLA module name: the cascade, the
# slab's row fills and the slab's sign plane rebuilt after a fill. Their
# device time is a fleet roofline's denominator.
FLEET_PROGRAMS = ("jit__cascade_batched", "jit__apply_fills",
                  "jit__sign_sidecar")


def p95_ms(values) -> float | None:
    values = np.asarray(values, np.float64)
    if values.size == 0:
        return None
    return float(np.percentile(values, 95) * 1e3)


def per_launch(ctx, total) -> float | None:
    launches = ctx.record.get("launches", 0)
    return None if not launches else total / launches


def histogram(ctx, name):
    reg = ctx.record.get("registry")
    return None if reg is None else reg.get("histogram", name)


def counter(ctx, name):
    reg = ctx.record.get("registry")
    if reg is None:
        return None
    c = reg.get("counter", name)
    return 0 if c is None else c.value


def fleet_query_work(ctx) -> work.Work:
    c = ctx.config
    return work.retrieval_query(
        docs_per_tenant=c["docs_per_tenant"], dim=c["dim"],
        num_clusters=c["num_clusters"], nprobe=c["nprobe"],
        prescreen_c0=c["prescreen_c0"],
        candidates=min(c["max_candidates"],
                       int(np.ceil(c["candidate_frac"] * c["tenants"]
                                   * c["docs_per_tenant"]))),
        k=c["k"])


def fleet_served(ctx) -> int:
    """Queries the window's launches served (sent in the window)."""
    return int(ctx.record.get("sent", 0))


def cascade_roofline(ctx) -> float | None:
    """Least time of the window's required cascade work over the device
    time of the fleet's launch programs, in percent."""
    dev_s = ctx.reduction.programs_s(FLEET_PROGRAMS)
    if dev_s <= 0 or not fleet_served(ctx):
        return None
    w = fleet_query_work(ctx).scaled(fleet_served(ctx))
    least = work.least_time_s(w, ctx.peaks["int8_ops_per_s"],
                              ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * least / dev_s


def retrieval_mfu(ctx) -> float | None:
    """Required INT8 operations of the queries served in the window, per
    second of window, over the chip's INT8 peak, in percent."""
    if not fleet_served(ctx):
        return None
    ops = fleet_query_work(ctx).ops * fleet_served(ctx)
    return 100.0 * ops / ctx.record["window_s"] / ctx.peaks["int8_ops_per_s"]


def device_idle(ctx) -> float:
    return 100.0 * ctx.reduction.idle_share


# The agent's decode step, by XLA module name: `RAGAgent`'s jitted lambda
# around `decode_step_quant` (serve/rag.py). The embedder's encoder is a
# jitted lambda too, but runs once a turn where the decode step runs once
# a generated token, so a program of these names counts as the decode
# step where it runs at least half as often as the most frequent one.
DECODE_PROGRAMS = ("jit__lambda",)


def decode_step(ctx) -> tuple[float, int]:
    """(device seconds, steps) of the decode step in the traced window:
    the programs named in DECODE_PROGRAMS that run once a step, summed.
    Raises when the trace holds none, so a renamed program shows."""
    red = ctx.reduction
    runs = {name: n for name, n in red.program_runs.items()
            if trace.module_base(name) in DECODE_PROGRAMS}
    if not runs:
        raise ValueError(f"no decode-step program {DECODE_PROGRAMS} in the "
                         f"trace; it holds {sorted(red.module_s)}")
    steps = max(runs.values())
    secs = sum(red.program_s[name] for name, n in runs.items()
               if 2 * n >= steps)
    return secs, steps


def kv_step_work(ctx) -> work.Work:
    """The KV cascade's required work of one decode step of the turn's
    lanes, averaged over the turn's steps (the cache grows by one
    position a step)."""
    c, rec = ctx.config, ctx.record
    kv = c["kv_cascade"]
    total = work.Work()
    for step in range(1, rec["max_new"]):
        total = total + work.kv_cascade_step(
            length=rec["prompt_len"] + step,
            layers=c["num_hidden_layers"],
            kv_heads=c["num_key_value_heads"],
            q_heads=c["num_attention_heads"],
            head_dim=c["hidden_size"] // c["num_attention_heads"],
            page_rows=kv["page_rows"], npages=kv["npages"],
            prescreen_c0=kv["prescreen_c0"], top_k=kv["top_k"])
    return total.scaled(rec["lanes"] / (rec["max_new"] - 1))


def agent_flops(ctx) -> float:
    """Model FLOPs of the window's turns: each lane's prefill over the
    prompt (causal, so each position attends half the prompt on average)
    and each decode step over the KV cascade's kept keys."""
    c, rec = ctx.config, ctx.record
    kv = c["kv_cascade"]
    shape = dict(layers=c["num_hidden_layers"], d_model=c["hidden_size"],
                 q_heads=c["num_attention_heads"],
                 kv_heads=c["num_key_value_heads"],
                 head_dim=c["hidden_size"] // c["num_attention_heads"],
                 d_ff=c["intermediate_size"], vocab=c["vocab_size"])
    s = rec["prompt_len"]
    prefill = work.dense_forward_flops(positions=s, context=(s + 1) / 2,
                                       **shape)
    decode = work.dense_forward_flops(positions=rec["max_new"] - 1,
                                      context=kv["top_k"], **shape)
    return (prefill + decode) * rec["turns"] * rec["lanes"]
