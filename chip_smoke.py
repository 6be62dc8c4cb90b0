#!/usr/bin/env python3
"""Bring-up check: the RAG agent turn on one TPU chip, at full width.

    python chip_smoke.py [--seed 0]          # one chip: the agent turn
    python chip_smoke.py --chips 4           # four chips: sharded serving

One chip drives the system's main path once, through the library's own
entry points: a `MultiTenantRAGPipeline` arena of 65,536 slots at the
paper's 512-d (8 tenants x 8,192 documents ingested online through the
FULL minilm-embedder), served by `ServingRuntime` over the cluster-pruned
cascade (sign prescreen, slab cache, precision tiers), then `RAGAgent`
turns whose decode runs qwen2-0.5b FULL (bf16 weights from --seed)
through the engine's paged KV cascade. On a TPU the served retrieval and
the KV decode run the Pallas kernels (the platform default); every phase
checks its results against the pure-jnp reference on the same data:

  ingest      every document lands in its tenant's arena segment
  retrieval   queries that copy a stored document get it back top-1, no
              result crosses tenants, and the kernel path's ids and
              scores equal the jnp reference's
  kv_select   the KV cascade's page/row selection and attention output at
              qwen2-0.5b's decode geometry are identical on both backends
  agent       two agent turns over 128-token documents (a 528-position
              KV cache of 66 pages, pruned to 16): retrieved ids, greedy
              tokens and every decode step's logits identical on both
              decode backends

With --chips 4 only the sharded path runs: `ShardedServingRuntime` over
four shards on four devices at 512-d, its single-shard parity baseline
(bit-identical ids and scores), one `fail_shard` failover (every request
resolved exactly once), and a check that every shard's arena and slab
arrays sit on that shard's own device.

The script fails (non-zero exit, no result line) when JAX finds no TPU,
and on any failed check: exceptions propagate, none is caught. Its last
line on success is the JSON object
{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}.
Data and weights are generated from --seed; nothing else is read.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.core import RetrievalConfig, quantize_int8  # noqa: E402
from repro.core import engine as engine_mod  # noqa: E402
from repro.core.clustering import ClusterParams  # noqa: E402
from repro.kernels.platform import resolve_backend  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.models import embedder, get_model  # noqa: E402
from repro.serve import (MultiTenantRAGPipeline, RAGAgent,  # noqa: E402
                         RuntimeConfig, ServingRuntime, sparse_kv)

_COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                   "/jax/core/compile/jaxpr_to_mlir_module_duration",
                   "/jax/core/compile/backend_compile_duration")


class SmokeFailure(RuntimeError):
    """A phase's result disagreed with its reference."""


def check(ok, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


class CompileClock:
    """Seconds JAX spent tracing, lowering and compiling, per phase."""

    def __init__(self):
        self.phase = "setup"
        self.seconds: dict[str, float] = {}

    def __call__(self, event: str, duration: float, **_):
        if event in _COMPILE_EVENTS:
            self.seconds[self.phase] = (self.seconds.get(self.phase, 0.0)
                                        + duration)


# ---------------------------------------------------------------------------
# Phases (sizes are arguments; main() passes the full widths)
# ---------------------------------------------------------------------------

def build_pipeline(gen_cfg, emb_cfg, *, capacity: int, doc_len: int,
                   k: int, clusters: ClusterParams, prescreen_c0: int,
                   cache_bytes: int, batch: int, seed: int,
                   backend: str | None):
    """Models from --seed, the shared arena, and the serving runtime."""
    gen_api = get_model(gen_cfg)
    # one jitted program per model instead of one dispatch per tensor
    gen_params = jax.jit(gen_api.init)(jax.random.PRNGKey(seed))
    emb_params = jax.jit(embedder.init_params, static_argnums=0)(
        emb_cfg, jax.random.PRNGKey(seed + 1))
    pipe = MultiTenantRAGPipeline.create(
        emb_cfg, emb_params, gen_api, gen_params, capacity=capacity,
        doc_len=doc_len,
        retrieval_cfg=RetrievalConfig(k=k, metric="cosine",
                                      prescreen_c0=prescreen_c0,
                                      backend=backend),
        clusters=clusters)
    runtime = ServingRuntime(pipe.index, RuntimeConfig(
        max_batch=batch, max_wait=1.0, cache_bytes=cache_bytes,
        preload=True, auto_flush=False, precision_tiers=True))
    return pipe, runtime


def phase_ingest(pipe, *, tenants: int, docs_per_tenant: int, burst: int,
                 seed: int) -> dict[int, np.ndarray]:
    """Online ingest in interleaved per-tenant bursts, then one
    cluster-grouping compaction. Returns each tenant's document tokens."""
    rng = np.random.default_rng(seed)
    vocab = min(pipe.emb_cfg.vocab_size, pipe.gen_api.cfg.vocab_size)
    doc_len = pipe.doc_tokens.shape[1]
    docs = {t: rng.integers(0, vocab, (docs_per_tenant, doc_len),
                            dtype=np.int32) for t in range(tenants)}
    for start in range(0, docs_per_tenant, burst):
        for t in range(tenants):
            pipe.ingest(t, docs[t][start:start + burst])
    pipe.compact()
    owner = np.asarray(pipe.index.arena.owner)
    for t in range(tenants):
        slots = np.asarray(pipe.index.table.slots(t))
        check(len(slots) == docs_per_tenant,
              f"tenant {t} holds {len(slots)} docs, ingested "
              f"{docs_per_tenant}")
        check(np.all(owner[slots] == t), f"tenant {t} slots mis-owned")
    check(pipe.index.num_live == tenants * docs_per_tenant,
          f"{pipe.index.num_live} live rows, expected "
          f"{tenants * docs_per_tenant}")
    return docs


def _pick_queries(docs, count: int, rng) -> tuple[np.ndarray, np.ndarray]:
    """(tenant ids, query tokens) where each query copies a stored doc."""
    tenants = sorted(docs)
    tids = rng.choice(tenants, size=count)
    toks = np.stack([docs[int(t)][rng.integers(len(docs[int(t)]))]
                     for t in tids])
    return tids.astype(np.int32), toks


def _serve(pipe, runtime, tids, codes) -> list:
    handles = [runtime.submit(int(t), codes[i]) for i, t in enumerate(tids)]
    runtime.flush()
    return [h.result() for h in handles]


def phase_retrieval(pipe, runtime, docs, *, flushes: int, batch: int,
                    seed: int) -> dict:
    """Mixed-tenant flushes through the runtime on the configured (kernel)
    path, then the same requests on the jnp reference."""
    rng = np.random.default_rng(seed + 2)
    owner = np.asarray(pipe.index.arena.owner)
    kernel_cfg = pipe.index.cfg
    served, hits, leaks, queries = [], 0, 0, 0
    for _ in range(flushes):
        tids, toks = _pick_queries(docs, batch, rng)
        codes, _ = quantize_int8(pipe._embed(jnp.asarray(toks)),
                                 per_vector=True)
        codes = np.asarray(codes)
        results = _serve(pipe, runtime, tids, codes)
        for t, tok, res in zip(tids, toks, results):
            ids = np.asarray(res.indices)
            valid = ids[ids >= 0]
            leaks += int(np.sum(owner[valid] != t))
            hits += int(len(valid) > 0
                        and np.array_equal(pipe.doc_tokens[valid[0]], tok))
            queries += 1
        served.append((tids, codes, results))
    check(leaks == 0, f"{leaks} cross-tenant results")
    check(hits == queries, f"top-1 hit {hits}/{queries}")
    pipe.index.cfg = dataclasses.replace(kernel_cfg, backend="jnp")
    try:
        for tids, codes, results in served:
            for got, want in zip(results, _serve(pipe, runtime, tids, codes)):
                check(np.array_equal(np.asarray(got.indices),
                                     np.asarray(want.indices)),
                      "kernel and jnp retrieval ids differ")
                check(np.array_equal(np.asarray(got.scores),
                                     np.asarray(want.scores)),
                      "kernel and jnp retrieval scores differ")
    finally:
        pipe.index.cfg = kernel_cfg
    return {"queries": queries, "hits": hits, "leaks": leaks,
            "launches": runtime.launches}


def phase_kv_select(gen_cfg, *, batch: int, seq_len: int, top_k: int,
                    npages: int, prescreen_c0: int, page_rows: int,
                    seed: int, backend: str | None) -> dict:
    """The KV cascade's selection at the generator's decode geometry, on
    both backends over the same random cache and queries."""
    kh, h, hd = gen_cfg.num_kv_heads, gen_cfg.num_heads, gen_cfg.hd
    key = jax.random.split(jax.random.PRNGKey(seed + 3), 3)
    kx = jax.random.normal(key[0], (batch, seq_len, kh, hd), jnp.float32)
    vx = jax.random.normal(key[1], (batch, seq_len, kh, hd), jnp.bfloat16)
    q = jax.random.normal(key[2], (batch, 1, h, hd), jnp.bfloat16)
    # ragged lengths: the last page of every lane but the first is partial
    length = jnp.asarray([seq_len - 3 * i for i in range(batch)], jnp.int32)
    cache = sparse_kv.build_page_centroids(
        sparse_kv.build_quant_cache(kx, vx), length, page_rows=page_rows)
    policy = sparse_kv.kv_policy(cache, length)
    out = {}
    for b in (backend, "jnp"):
        cfg = engine_mod.KVCascadeConfig(
            top_k=top_k, npages=npages, page_rows=page_rows,
            prescreen_c0=prescreen_c0, backend=b)
        rows, member = engine_mod.kv_selection(q, policy, cfg)
        attn = engine_mod.kv_decode_batched(q, policy, cfg)
        out[b] = (np.asarray(rows), np.asarray(member),
                  np.asarray(attn.astype(jnp.float32)))
    (rk, mk, ak), (rj, mj, aj) = out[backend], out["jnp"]
    check(np.array_equal(rk, rj), "KV row selections differ")
    check(np.array_equal(mk, mj), "KV row validity differs")
    check(np.all(np.isfinite(ak)), "non-finite KV attention output")
    # the integer stages pick the same rows and both backends run the
    # float stage-2 on them verbatim, so the outputs are equal bit for bit
    err = float(np.max(np.abs(ak - aj)))
    check(np.array_equal(ak, aj), f"KV attention outputs differ by {err}")
    return {"rows_per_lane": int(rk.shape[-1]), "attn_max_abs_diff": err}


def _record_logits(agent) -> list:
    """Wrap the agent's jitted decode step so that the logits of every
    step after the prefill (one per generated token but the first) land
    in the returned list."""
    step, seen = agent._decode_step(), []

    def recorded(params, cache, tok):
        logits, cache = step(params, cache, tok)
        seen.append(logits)
        return logits, cache

    agent._decode_jit = recorded
    return seen


def phase_agent(pipe, runtime, docs, *, turns: int, batch: int,
                max_new: int, top_k: int, npages: int, prescreen_c0: int,
                page_rows: int, seed: int, backend: str | None) -> dict:
    """Agent turns on the kernel decode path and on the jnp reference:
    one runtime schedules both retrievals, the decode backends differ."""
    rng = np.random.default_rng(seed + 4)
    knobs = dict(top_k=top_k, npages=npages, prescreen_c0=prescreen_c0,
                 page_rows=page_rows)
    agent = RAGAgent(pipeline=pipe, runtime=runtime, backend=backend,
                     **knobs)
    ref = RAGAgent(pipeline=pipe, runtime=runtime, backend="jnp", **knobs)
    got_logits, want_logits = _record_logits(agent), _record_logits(ref)
    max_err, tokens = 0.0, 0
    for _ in range(turns):
        got_logits.clear()
        want_logits.clear()
        tids, toks = _pick_queries(docs, batch, rng)
        got = agent.turn(tids, jnp.asarray(toks), max_new=max_new)
        want = ref.turn(tids, jnp.asarray(toks), max_new=max_new)
        check(np.array_equal(got.retrieved, want.retrieved),
              "agent retrievals differ between the two turns")
        for i, tok in enumerate(toks):
            top = got.retrieved[i, 0]
            check(top >= 0 and np.array_equal(pipe.doc_tokens[top], tok),
                  f"agent turn lane {i} missed its own document")
        gt, wt = np.asarray(got.tokens), np.asarray(want.tokens)
        check(gt.shape == (batch, max_new), f"token shape {gt.shape}")
        check(np.array_equal(gt, wt),
              "greedy decode tokens differ between backends")
        check(len(got_logits) == len(want_logits) == max_new - 1,
              f"{len(got_logits)} decode steps recorded")
        # the prefill is one shared program; every decode step selects
        # the same KV rows on both backends and attends them with the
        # same float ops, so the logits agree bit for bit
        for step, (lg, lw) in enumerate(zip(got_logits, want_logits)):
            lg = np.asarray(lg.astype(jnp.float32))
            lw = np.asarray(lw.astype(jnp.float32))
            check(np.all(np.isfinite(lg)), "non-finite logits")
            err = float(np.max(np.abs(lg - lw)))
            check(np.array_equal(lg, lw),
                  f"decode step {step + 1} logits differ by {err}")
            max_err = max(max_err, err)
        check(got.uj_per_token > 0 and got.uj_per_query > 0,
              "turn energy ledger empty")
        tokens += gt.size
    return {"turns": turns, "tokens": tokens, "logit_max_abs_diff": max_err,
            "decode_bytes_per_token": got.decode_bytes_per_token}


def phase_sharded(*, shards: int, devices, tenants: int,
                  docs_per_tenant: int, dim: int, rounds: int, batch: int,
                  fail_at: int, clusters: int, cache_bytes: int,
                  seed: int) -> dict:
    """Sharded serving vs its single-shard baseline, one shard failover,
    and per-shard device placement."""
    from repro.serve.sharded import (ShardedRuntimeConfig,
                                     ShardedServingRuntime)
    rng = np.random.default_rng(seed + 5)
    docs = {t: rng.integers(-40, 41, (docs_per_tenant, dim), dtype=np.int8)
            for t in range(tenants)}
    trace = [(t, docs[t][rng.integers(docs_per_tenant)])
             for t in list(range(tenants)) * rounds]
    # Exact over every row (candidate budget covers the largest tenant;
    # the cluster prune probes every cluster), so placement cannot change
    # answers; the clusters still give every shard its slab cache.
    rcfg = RetrievalConfig(k=5, metric="mips", candidate_frac=1.0,
                           max_candidates=docs_per_tenant)

    def build(n):
        rt = ShardedServingRuntime(ShardedRuntimeConfig(
            num_shards=n, capacity_per_shard=tenants * docs_per_tenant,
            dim=dim, retrieval=rcfg,
            clusters=ClusterParams(num_clusters=clusters, nprobe=clusters,
                                   block_rows=32),
            runtime=RuntimeConfig(max_batch=batch, max_wait=1.0,
                                  cache_bytes=cache_bytes,
                                  auto_flush=False)), devices=devices)
        for t in range(tenants):
            rt.ingest_codes(t, docs[t])
        return rt

    def drive(rt, fail=-1):
        handles, now, report = [], 0.0, None
        for i, (t, q) in enumerate(trace):
            if i == fail:
                report = rt.fail_shard(rt.placement.shard_of(t), now=now)
            now += 1e-3
            handles.append(rt.submit(t, q, now=now))
            if i % batch == batch - 1:
                rt.poll(now=now)
        rt.flush(now=now + 1)
        return [h.result() for h in handles], report

    base, _ = drive(build(1))
    rt = build(shards)
    got, report = drive(rt, fail=fail_at)
    check(report is not None, "no failover happened")
    for b, g in zip(base, got):
        check(np.array_equal(np.asarray(b.indices), np.asarray(g.indices)),
              "sharded ids differ from the single-shard baseline")
        check(np.array_equal(np.asarray(b.scores), np.asarray(g.scores)),
              "sharded scores differ from the single-shard baseline")
    led = rt.ledger()
    check(led["submitted"] == led["resolved"] == len(trace)
          and led["dropped"] == 0 and led["duplicated"] == 0
          and led["outstanding"] == 0,
          f"failover ledger not exactly-once: {led}")
    placed, slabs = {}, 0
    for sid in range(shards):
        shard = rt.shard(sid)
        arena = shard.index.arena
        arrays = {"msb": arena.msb_plane, "lsb": arena.lsb_plane,
                  "sign": arena.sign_plane, "norms": arena.norms_sq,
                  "owner": arena.owner}
        cache = shard.runtime.cache
        if shard.runtime.launches:      # a shard that served has a slab
            check(cache is not None and cache.slab_plane is not None,
                  f"shard {sid} served without a slab")
            arrays.update(slab=cache.slab_plane, slab_sign=cache.sign_plane)
            slabs += 1
        for name, arr in arrays.items():
            check(arr.devices() == {shard.device},
                  f"shard {sid} {name} on {arr.devices()}, not "
                  f"{shard.device}")
        placed[sid] = str(shard.device)
    check(slabs >= 2, f"only {slabs} shard(s) served")
    return {"requests": len(trace), "failover": report, "slabs": slabs,
            "ledger": {k: led[k] for k in ("submitted", "resolved",
                                           "dropped", "duplicated",
                                           "resubmitted")},
            "devices": placed}


# ---------------------------------------------------------------------------
# Entry point
# ---------------------------------------------------------------------------

def _run(clock: CompileClock, name: str, fn, *args, **kwargs):
    clock.phase = name
    t0 = time.perf_counter()
    out = fn(*args, **kwargs)
    wall = time.perf_counter() - t0
    report = out if isinstance(out, dict) and not any(
        isinstance(v, np.ndarray) for v in out.values()) else ""
    print(f"[smoke] {name:<10} pass  wall {wall:8.2f} s  compile "
          f"{clock.seconds.get(name, 0.0):7.2f} s  {report}", flush=True)
    return out


def _peak_bytes() -> list:
    return [(d.memory_stats() or {}).get("peak_bytes_in_use")
            for d in jax.devices()]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: the agent turn on one chip; 4: only the "
                         "sharded-serving path over four chips")
    args = ap.parse_args(argv)

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"[smoke] no TPU: JAX runs on {dev.platform!r}; nothing "
              f"was run", file=sys.stderr)
        return 2
    devices = jax.devices()
    if len(devices) < args.chips:
        print(f"[smoke] --chips {args.chips} needs {args.chips} devices, "
              f"JAX sees {len(devices)}", file=sys.stderr)
        return 2
    cache_dir = enable_compile_cache()
    clock = CompileClock()
    jax.monitoring.register_event_duration_secs_listener(clock)
    print(f"[smoke] jax {jax.__version__}  device {dev.device_kind} x "
          f"{len(devices)}  compile cache {cache_dir}", flush=True)

    if args.chips == 4:
        shard_devs = devices[:4]
        check(len({d.id for d in shard_devs}) == 4, "four distinct devices")
        _run(clock, "sharded", phase_sharded, shards=4, devices=shard_devs,
             tenants=16, docs_per_tenant=4096, dim=512, rounds=4, batch=8,
             fail_at=20, clusters=8, cache_bytes=4 << 20, seed=args.seed)
    else:
        check(resolve_backend(None) == "pallas",
              "the platform default does not serve the Pallas kernels")
        gen_cfg = get_config("qwen2-0.5b").with_(param_dtype="bfloat16")
        emb_cfg = get_config("minilm-embedder")
        pipe, runtime = _run(
            clock, "build", build_pipeline, gen_cfg, emb_cfg,
            capacity=65_536, doc_len=128, k=3,
            clusters=ClusterParams(num_clusters=16, nprobe=8, block_rows=32),
            prescreen_c0=256, cache_bytes=1 << 20, batch=8, seed=args.seed,
            backend=None)
        docs = _run(clock, "ingest", phase_ingest, pipe, tenants=8,
                    docs_per_tenant=8192, burst=1024, seed=args.seed)
        _run(clock, "retrieval", phase_retrieval, pipe, runtime, docs,
             flushes=4, batch=8, seed=args.seed)
        _run(clock, "kv_select", phase_kv_select, gen_cfg, batch=4,
             seq_len=2048, top_k=32, npages=16, prescreen_c0=64,
             page_rows=8, seed=args.seed, backend=None)
        _run(clock, "agent", phase_agent, pipe, runtime, docs, turns=2,
             batch=4, max_new=16, top_k=32, npages=16, prescreen_c0=64,
             page_rows=8, seed=args.seed, backend=None)
    print(f"[smoke] peak_bytes_in_use per device {_peak_bytes()}")
    print(f"[smoke] compile seconds by phase "
          f"{ {k: round(v, 2) for k, v in clock.seconds.items()} }")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
