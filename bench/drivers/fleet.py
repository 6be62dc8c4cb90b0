"""Driver of the wearable retrieval fleet: `ServingRuntime` over a shared
multi-tenant arena, with the cluster-pruned precision cascade and the
slab cache, driven in an open or a closed loop.

Data comes from the seed. Each tenant's corpus is a mixture over a set of
topics shared by the fleet (unit vectors in `dim` dimensions), each
document a topic, plus the tenant's own offset, plus noise, normalised to
unit length and put on the INT8 grid of the arena's fixed scale (a power
of two, so the arena's own quantization reproduces the codes exactly).
The embeddings go in through `MultiTenantIndex.ingest` in per-tenant
bursts, then one compaction. A query is a stored document's codes plus
integer noise, submitted as INT8 codes.

`verify()` holds every answer of the window against the plain reference
(`bench/references/fleet.py`), which knows the codes from the seed.
"""
from __future__ import annotations

import collections
import math
import time

import numpy as np

from bench import traffic as gen
from bench import warm as warm_up
from bench.harness import NewPrograms

SCALE_EXP = -9          # arena scale 2**-9: unit vectors use ~±120 codes

# every key a fleet mix may hold, and the values this driver handles
MIX_KEYS = {"loop": ("open", "closed"), "rate_per_s": None,
            "outstanding": None, "tenants": ("zipf", "uniform"),
            "zipf_s": None, "focus": ("session",), "focus_zipf_s": None,
            "sticky": None, "query_noise": None, "warmup_half_s": None,
            "warmup_max_halves": None, "warmup_quiet_halves": None,
            "warmup_uniform_s": None, "trace_seconds": None}


def make_corpus(cfg: dict, seed: int):
    """(codes (T, N, D) int8, topic of each doc (T, N)) from the seed."""
    t, n, d = cfg["tenants"], cfg["docs_per_tenant"], cfg["dim"]
    k = cfg["topics"]
    rng = gen.rng_for(seed, 1)
    # every seed gets the same topic sizes per tenant, in another order
    shapes = np.random.default_rng(0).dirichlet(np.full(k, 2.0), size=t)
    topics = rng.standard_normal((k, d))
    topics /= np.linalg.norm(topics, axis=1, keepdims=True)
    codes = np.empty((t, n, d), np.int8)
    labels = np.empty((t, n), np.int64)
    topics = topics.astype(np.float32)
    for ten in range(t):
        w = rng.permutation(shapes[ten]) * 0.8 + 0.2 / k
        lab = gen.exact_draws(rng, n, w)
        off = rng.standard_normal(d, dtype=np.float32)
        off *= cfg["tenant_offset"] / np.linalg.norm(off)
        x = rng.standard_normal((n, d), dtype=np.float32)
        x *= np.float32(cfg["doc_noise"] / math.sqrt(d))
        x += topics[lab]
        x += off
        x /= np.linalg.norm(x, axis=1, keepdims=True)
        codes[ten] = np.clip(np.rint(x * 2.0 ** -SCALE_EXP), -128, 127)
        labels[ten] = lab
    return codes, labels


class Requests:
    """The cell's request stream, made from the seed: tenant and INT8
    query codes of request i, drawn in chunks as the loop asks."""

    CHUNK = 2048

    def __init__(self, cfg: dict, traffic: dict, codes, labels, seed: int,
                 stream: int):
        self.cfg, self.traffic = cfg, traffic
        self.codes, self.labels = codes, labels
        self.rng = gen.rng_for(seed, stream)
        self.tenants = np.empty(0, np.int64)
        self.queries = np.empty((0, cfg["dim"]), np.int8)
        t, k = cfg["tenants"], cfg["topics"]
        self._by_topic = [[np.flatnonzero(labels[ten] == c)
                           for c in range(k)] for ten in range(t)]

    def ensure(self, n: int) -> None:
        while len(self.tenants) < n:
            self._grow()

    def _grow(self) -> None:
        tr, cfg, rng = self.traffic, self.cfg, self.rng
        t, m = cfg["tenants"], self.CHUNK
        if tr["tenants"] == "zipf":
            probs = gen.zipf_probs(t, tr["zipf_s"])
        else:
            probs = np.full(t, 1.0 / t)
        ten = gen.exact_draws(rng, m, probs)
        n = cfg["docs_per_tenant"]
        if tr.get("focus") == "session":
            focus = gen.session_focus(rng, ten, t, cfg["topics"],
                                      zipf_s=tr["focus_zipf_s"],
                                      sticky=tr["sticky"])
            docs = np.empty(m, np.int64)
            for i, (a, c) in enumerate(zip(ten, focus)):
                pool = self._by_topic[a][c]
                if len(pool) == 0:
                    pool = np.arange(n)
                docs[i] = pool[rng.integers(len(pool))]
        else:
            docs = rng.integers(0, n, size=m)
        noise = np.rint(rng.standard_normal((m, cfg["dim"]))
                        * tr["query_noise"])
        q = np.clip(self.codes[ten, docs].astype(np.int32) + noise,
                    -128, 127).astype(np.int8)
        self.tenants = np.concatenate([self.tenants, ten])
        self.queries = np.concatenate([self.queries, q])


class Cell:
    def __init__(self, config: dict, traffic: dict, *, seed: int,
                 trace: bool = False):
        gen.check_mix(traffic, MIX_KEYS)
        self.config, self.traffic = config, traffic
        self.seed, self.trace = seed, trace
        self.record: dict = {}
        self.setup_report: list[str] = []
        self.report: list[str] = []
        self.attempted = self.failed = 0

    # -- set-up -----------------------------------------------------------

    def setup(self) -> None:
        from repro.core import RetrievalConfig
        from repro.core.clustering import ClusterParams
        from repro.obs.metrics import MetricsRegistry
        from repro.serve import RuntimeConfig, ServingRuntime
        from repro.tenancy import MultiTenantIndex

        cfg = self.config
        t0 = time.perf_counter()
        self.codes, self.labels = make_corpus(cfg, self.seed)
        t1 = time.perf_counter()
        scale = 2.0 ** SCALE_EXP
        self.index = MultiTenantIndex(
            cfg["tenants"] * cfg["docs_per_tenant"], cfg["dim"],
            RetrievalConfig(k=cfg["k"], metric=cfg["metric"],
                            prescreen_c0=cfg["prescreen_c0"],
                            max_candidates=cfg["max_candidates"],
                            candidate_frac=cfg["candidate_frac"]),
            scale=scale,
            clusters=ClusterParams(num_clusters=cfg["num_clusters"],
                                   nprobe=cfg["nprobe"],
                                   block_rows=cfg["block_rows"]))
        n, burst = cfg["docs_per_tenant"], cfg["ingest_burst"]
        slot_of = np.empty((cfg["tenants"], n), np.int64)
        for start in range(0, n, burst):
            for ten in range(cfg["tenants"]):
                emb = self.codes[ten, start:start + burst].astype(
                    np.float32) * np.float32(scale)
                slot_of[ten, start:start + burst] = self.index.ingest(ten,
                                                                      emb)
        mapping = np.asarray(self.index.compact())
        self.slot_of = mapping[slot_of]
        t2 = time.perf_counter()
        self.registry = MetricsRegistry() if self.trace else None
        self.runtime = ServingRuntime(self.index, RuntimeConfig(
            max_batch=cfg["max_batch"], max_wait=cfg["max_wait_s"],
            cache_bytes=cfg["cache_bytes"], preload=cfg["preload"],
            precision_tiers=cfg["precision_tiers"],
            async_depth=cfg["async_depth"]), registry=self.registry)
        self._warm_up()
        t3 = time.perf_counter()
        self.setup_report += [
            f"setup data_s {t1 - t0:.3f} ingest_s {t2 - t1:.3f} "
            f"warm_s {t3 - t2:.3f}"]

    def _warm_up(self) -> None:
        """Every power-of-two batch the runtime can launch, of each
        tenant alone (a mixed batch's tables are as wide as its widest
        tenant's), the slab's fill program at every size it can take,
        then `warmup_uniform_s` of uniform closed-loop traffic, then the
        cell's own traffic (a stream the window never sees) until
        `warmup_quiet_halves` half-windows in a row bring no new program,
        reporting the slab's entries and demotions per half."""
        import jax
        tr, cfg = self.traffic, self.config
        warm = Requests(cfg, tr, self.codes, self.labels, self.seed,
                        stream=7)
        rt, nxt = self.runtime, 0
        rng = gen.rng_for(self.seed, 8)
        b = 1
        while b <= cfg["max_batch"]:
            for ten in range(cfg["tenants"]):
                docs = rng.integers(0, cfg["docs_per_tenant"], size=b)
                hs = [rt.submit(ten, self.codes[ten, d],
                                now=time.monotonic()) for d in docs]
                rt.flush(now=time.monotonic())
                jax.block_until_ready([h.result().indices for h in hs])
            b *= 2
        self.setup_report.append(
            f"warm fill programs {warm_up.fill_programs(rt)}")
        # uniform users and records, 48 outstanding: the slab's largest
        # admissions and widest tables
        uniform = {"loop": "closed", "outstanding": 3 * cfg["max_batch"],
                   "tenants": "uniform", "query_noise": tr["query_noise"]}
        if tr["warmup_uniform_s"] > 0:
            self._drive(Requests(cfg, uniform, self.codes, self.labels,
                                 self.seed, stream=9),
                        float(tr["warmup_uniform_s"]), start=0, span=None,
                        traffic=uniform)
        counter = NewPrograms()
        warm.ensure(1)
        quiet, half = 0, float(tr["warmup_half_s"])
        for i in range(int(tr["warmup_max_halves"])):
            c0, cache = counter.count, rt.cache
            d0 = cache.demotions if cache is not None else 0
            rec = self._drive(warm, half, start=nxt, span=None)
            nxt = rec["next"]
            entries = len(cache) if cache is not None else 0
            dem = (cache.demotions - d0) if cache is not None else 0
            comp = counter.count - c0
            self.setup_report.append(
                f"warm half {i} requests {rec['sent']} slab_entries "
                f"{entries} demotions_per_s {dem / half:.2f} "
                f"new_programs {comp}")
            quiet = quiet + 1 if comp == 0 else 0
            if quiet >= int(tr["warmup_quiet_halves"]):
                break

    # -- the measured window ----------------------------------------------

    def window(self, seconds: float, span) -> None:
        reqs = Requests(self.config, self.traffic, self.codes, self.labels,
                        self.seed, stream=2)
        self.requests = reqs
        launches0 = self.runtime.launches
        if self.registry is not None:
            self.registry.reset()
        rec = self._drive(reqs, seconds, start=0, span=span)
        rec["launches"] = self.runtime.launches - launches0
        if self.registry is not None:
            rec["registry"] = self.registry
        self.record = rec

    def _drive(self, reqs: Requests, seconds: float, *, start: int,
               span, traffic: dict | None = None) -> dict:
        """Serve `reqs` from index `start` for `seconds`: open loop on the
        mix's Poisson schedule, or closed loop with a fixed number of
        requests outstanding. Requests sent in the window are all waited
        for after it closes."""
        import contextlib
        tr, rt = traffic or self.traffic, self.runtime
        sp = span or (lambda name: contextlib.nullcontext())
        open_loop = tr["loop"] == "open"
        if open_loop:
            n_max = int(math.ceil(tr["rate_per_s"] * seconds * 1.5)) + 16
            reqs.ensure(start + n_max)
            gaps = gen.exponential_gaps(gen.rng_for(self.seed, 3, start),
                                        n_max, 1.0 / tr["rate_per_s"])
            due = np.cumsum(gaps) - gaps[0]
        outstanding = int(tr.get("outstanding", 0))
        handles: dict[int, object] = {}
        pending: "collections.deque[int]" = collections.deque()
        sent_at, done_at, late = {}, {}, []
        host_s = 0.0
        clock = time.monotonic
        t0 = clock()
        t_end = t0 + seconds
        i = start
        while True:
            now = clock()
            if now >= t_end:
                break
            h0 = time.perf_counter()
            sent = 0
            if open_loop:
                if i - start < n_max and t0 + due[i - start] <= now:
                    with sp("bench.submit"):
                        while (i - start < n_max
                               and t0 + due[i - start] <= now):
                            handles[i] = rt.submit(int(reqs.tenants[i]),
                                                   reqs.queries[i], now=now)
                            sent_at[i] = t0 + due[i - start]
                            late.append(now - sent_at[i])
                            pending.append(i)
                            i += 1
                            sent += 1
            elif len(pending) < outstanding:
                reqs.ensure(i + outstanding)
                with sp("bench.submit"):
                    while len(pending) < outstanding:
                        handles[i] = rt.submit(int(reqs.tenants[i]),
                                               reqs.queries[i], now=now)
                        sent_at[i] = now
                        pending.append(i)
                        i += 1
                        sent += 1
            with sp("bench.poll"):
                rt.poll(now=clock())
            host_s += time.perf_counter() - h0
            now = clock()
            done = [j for j in pending if handles[j].state == "resolved"]
            for j in done:
                done_at[j] = now
                pending.remove(j)
            if not sent and not done:
                nxt = t_end
                if open_loop and i - start < n_max:
                    nxt = min(nxt, t0 + due[i - start])
                wait = min(nxt - clock(), 5e-4)
                if wait > 0:
                    with sp("bench.idle"):
                        time.sleep(wait)
        with sp("bench.flush"):
            h0 = time.perf_counter()
            rt.flush(now=clock())
            host_s += time.perf_counter() - h0
        now = clock()
        for j in pending:
            done_at[j] = now
        ids = sorted(sent_at)
        lat = np.array([done_at[j] - sent_at[j] for j in ids])
        done_in = sum(1 for j in ids if done_at[j] <= t_end)
        return {"sent": len(ids), "next": i, "ids": ids,
                "handles": handles, "latency_s": lat,
                "completed_in_window": done_in, "window_s": seconds,
                "send_late_s": np.asarray(late), "host_call_s": host_s}

    # -- after the window ---------------------------------------------------

    def release(self) -> None:
        """Collect every answer of the window, then free the program."""
        rec, reqs = self.record, self.requests
        answers = []
        for j in rec["ids"]:
            res = rec["handles"][j].result(wait=False)
            answers.append(None if res is None else
                           (np.asarray(res.indices), np.asarray(res.scores)))
        self.answers = answers
        self.asked = (reqs.tenants[rec["ids"]], reqs.queries[rec["ids"]])
        rec.pop("handles")
        cache = self.runtime.cache
        lat = rec["latency_s"] * 1e3
        self.report.append(
            f"window sent {rec['sent']} completed_in_window "
            f"{rec['completed_in_window']} launches {rec['launches']} "
            f"slab_entries {len(cache) if cache is not None else 0} "
            f"p50_ms {np.percentile(lat, 50):.3f} p95_ms "
            f"{np.percentile(lat, 95):.3f} p99_ms {np.percentile(lat, 99):.3f}")
        del self.runtime, self.index

    def verify(self):
        from bench.harness import Check
        ref = _reference()
        nums = ref.check_answers(self.codes, self.slot_of, self.asked,
                                 self.answers, self.config["k"])
        self.attempted = len(self.answers)
        self.failed = nums["unanswered"]
        limits = self.config["limits"]
        self.report.append("reference " + " ".join(
            f"{k} {v}" for k, v in nums.items()))
        return [Check(name, nums[name], limits[name]) for name in limits]

    def end_to_end(self) -> dict:
        rec = self.record
        lat_ms = rec["latency_s"] * 1e3
        out = {"queries_per_s": rec["completed_in_window"] / rec["window_s"]}
        if len(lat_ms):
            out["query_p95_ms"] = float(np.percentile(lat_ms, 95))
            out["query_p50_ms"] = float(np.percentile(lat_ms, 50))
        return out


def _reference():
    import os
    from bench.harness import load_module
    return load_module(os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "references", "fleet.py"),
        "bench_reference_fleet")
