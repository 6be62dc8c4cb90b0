"""Driver of the RAG agent turn: `RAGAgent.turn` over a multi-tenant arena
of documents ingested through the embedder, with a qwen2 generator whose
decode runs the paged KV cascade.

One turn at a time (a closed loop of one client): each turn takes `lanes`
distinct tenants drawn uniformly, each lane's query is a copy of one of
its tenant's stored documents, and the agent retrieves `k` documents per
lane, prefills the prompt (k documents and the query) and generates
`max_new` tokens greedily.

The generator's weights are made by the benchmark from the seed
(`bench/references/qwen2.py`), in bfloat16 as served. `verify()` holds
every lane of the window against the plain reference: the logit gap of
every served token, teacher-forced over the same prompt.
"""
from __future__ import annotations

import gc
import os
import time

import numpy as np

from bench import traffic as gen
from bench import warm as warm_up
from bench.harness import Check, NewPrograms, load_module

# every key an agent mix may hold (all numbers)
MIX_KEYS = dict.fromkeys(("lanes", "max_new", "warmup_turns",
                          "trace_seconds"))

REF_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "references")


def reference():
    return load_module(os.path.join(REF_DIR, "qwen2.py"),
                       "bench_reference_qwen2")


def model_config(cfg: dict):
    """The program's ModelConfig for the configuration file's sizes."""
    from repro.configs import get_config
    return get_config("qwen2-0.5b").with_(
        num_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"],
        d_ff=cfg["intermediate_size"], vocab_size=cfg["vocab_size"],
        rope_theta=cfg["rope_theta"], norm_eps=cfg["rms_norm_eps"],
        qkv_bias=True, tie_embeddings=cfg["tie_word_embeddings"],
        param_dtype=cfg["torch_dtype"], compute_dtype=cfg["torch_dtype"])


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

    def setup(self) -> None:
        import jax
        import jax.numpy as jnp
        from repro.configs import get_config
        from repro.core import RetrievalConfig
        from repro.core.clustering import ClusterParams
        from repro.models import embedder, get_model
        from repro.serve import (MultiTenantRAGPipeline, RAGAgent,
                                 RuntimeConfig, ServingRuntime)

        cfg, tr, ar = self.config, self.traffic, self.config["arena"]
        kv = cfg["kv_cascade"]
        t0 = time.perf_counter()
        gen_cfg = model_config(cfg)
        api = get_model(gen_cfg)
        ref = reference()
        want = jax.eval_shape(api.init, jax.random.PRNGKey(0))
        params = ref.make_params(cfg, self.seed, cfg["torch_dtype"])

        def layout(tree):
            return (jax.tree.structure(tree),
                    [(a.shape, a.dtype) for a in jax.tree.leaves(tree)])
        if layout(want) != layout(params):
            raise ValueError("benchmark weights do not match the program's "
                             "parameter layout")
        emb_cfg = get_config("minilm-embedder").with_(**cfg["embedder"])
        emb_params = jax.jit(embedder.init_params, static_argnums=0)(
            emb_cfg, ref.seed_key(self.seed + 1))
        jax.block_until_ready((params, emb_params))
        t1 = time.perf_counter()
        capacity = ar["tenants"] * ar["docs_per_tenant"]
        pipe = MultiTenantRAGPipeline.create(
            emb_cfg, emb_params, api, params, capacity=capacity,
            doc_len=ar["doc_tokens"],
            retrieval_cfg=RetrievalConfig(k=ar["k"], metric="cosine",
                                          prescreen_c0=ar["prescreen_c0"],
                                          backend=ar.get("backend")),
            clusters=ClusterParams(num_clusters=ar["num_clusters"],
                                   nprobe=ar["nprobe"],
                                   block_rows=ar["block_rows"]))
        rng = gen.rng_for(self.seed, 1)
        vocab = min(emb_cfg.vocab_size, gen_cfg.vocab_size)
        n, burst = ar["docs_per_tenant"], ar["ingest_burst"]
        docs = rng.integers(0, vocab, (ar["tenants"], n, ar["doc_tokens"]),
                            dtype=np.int32)
        slot_of = np.empty((ar["tenants"], n), np.int64)
        for start in range(0, n, burst):
            for t in range(ar["tenants"]):
                slot_of[t, start:start + burst] = pipe.ingest(
                    t, docs[t, start:start + burst])
        mapping = np.asarray(pipe.compact())
        self.slot_of = mapping[slot_of]
        self.docs = docs
        self.owner = np.full(capacity, -1, np.int64)
        self.slot_doc = np.full(capacity, -1, np.int64)
        for t in range(ar["tenants"]):
            self.owner[self.slot_of[t]] = t
            self.slot_doc[self.slot_of[t]] = np.arange(n)
        t2 = time.perf_counter()
        runtime = ServingRuntime(pipe.index, RuntimeConfig(
            max_batch=ar["max_batch"], max_wait=1.0,
            cache_bytes=ar["cache_bytes"], preload=True, auto_flush=False,
            precision_tiers=True))
        self.agent = RAGAgent(pipeline=pipe, runtime=runtime,
                              top_k=kv["top_k"], npages=kv["npages"],
                              prescreen_c0=kv["prescreen_c0"],
                              page_rows=kv["page_rows"],
                              backend=kv.get("backend"))
        # warm up until a turn brings no new program, at most
        # `warmup_turns` turns; after the first, the slab's fill program
        # at every size it can take
        counter = NewPrograms()
        warm_rng = gen.rng_for(self.seed, 7)
        turns = fills = 0
        while turns < int(tr["warmup_turns"]):
            before = counter.count
            np.asarray(self._turn(warm_rng)[0].tokens)
            turns += 1
            if turns == 1:
                fills = warm_up.fill_programs(runtime)
            elif counter.count == before:
                break
        self.setup_report.append(f"warm turns {turns} fill programs "
                                 f"{fills}")
        t3 = time.perf_counter()
        self.setup_report.append(
            f"setup weights_s {t1 - t0:.3f} ingest_s {t2 - t1:.3f} "
            f"warm_s {t3 - t2:.3f}")

    def _lanes(self, rng):
        ar, tr = self.config["arena"], self.traffic
        tids = rng.permutation(ar["tenants"])[:tr["lanes"]]
        js = rng.integers(0, ar["docs_per_tenant"], size=len(tids))
        return tids.astype(np.int32), js

    def _turn(self, rng):
        import jax.numpy as jnp
        tids, js = self._lanes(rng)
        toks = self.docs[tids, js]
        rep = self.agent.turn(tids, jnp.asarray(toks),
                              max_new=self.traffic["max_new"])
        return rep, tids, js

    def window(self, seconds: float, span) -> None:
        rng = gen.rng_for(self.seed, 2)
        turns = []
        clock = time.monotonic
        t_end = clock() + seconds
        while clock() < t_end:
            start = clock()
            with span("bench.turn"):
                rep, tids, js = self._turn(rng)
                tokens = np.asarray(rep.tokens)
            turns.append({"start": start, "end": clock(), "tids": tids,
                          "docs": js, "tokens": tokens,
                          "retrieved": np.asarray(rep.retrieved)})
        self.turns = turns
        span_s = turns[-1]["end"] - turns[0]["start"]
        self.record = {"turns": len(turns), "span_s": span_s,
                       "tokens": int(sum(t["tokens"].size for t in turns)),
                       "lanes": self.traffic["lanes"],
                       "max_new": self.traffic["max_new"],
                       "prompt_len": (self.config["arena"]["k"] + 1)
                       * self.config["arena"]["doc_tokens"],
                       "window_s": seconds}

    def release(self) -> None:
        """Free the program (weights, arena, caches) before the
        reference runs, so the reference does not set the peak."""
        self.report.append(f"window turns {self.record['turns']} tokens "
                           f"{self.record['tokens']} span_s "
                           f"{self.record['span_s']:.3f}")
        del self.agent
        gc.collect()

    def verify(self):
        """Every turn of the window: its shape, tenant isolation, and that
        each lane's own record (the query is a copy of it) is retrieved
        first; then every lane's served tokens against the reference,
        teacher-forced over the prompt the agent served: the widest gap
        by which a first token (the prefill's) lies below the reference's
        best logit, and the mean gap over every served token (the prefill
        and each KV-cascade decode step). The widest gap over all tokens
        is reported: it swings with the selection's ties and no limit
        between sound runs and the control holds on it (PERF.md)."""
        tr = self.traffic
        shape_bad = leaks = self_miss = 0
        for t in self.turns:
            if t["tokens"].shape != (tr["lanes"], tr["max_new"]):
                shape_bad += 1
            own = self.slot_of[t["tids"], t["docs"]]
            for lane, row in enumerate(t["retrieved"]):
                if np.any(row < 0) or np.any(
                        self.owner[np.maximum(row, 0)] != t["tids"][lane]):
                    leaks += 1
                self_miss += int(row[0] != own[lane])
        self.attempted = len(self.turns) * tr["lanes"]
        self.failed = shape_bad * tr["lanes"]
        gaps = self.served_gaps()[0]
        nums = {"token_shape": shape_bad, "leaks": leaks,
                "self_miss": self_miss,
                "first_token_gap": float(gaps[:, 0].max()),
                "served_gap_mean": float(gaps.mean()),
                "served_gap_max": float(gaps.max())}
        self.report.append(
            f"reference lanes {gaps.shape[0]} positions {gaps.size} "
            f"agree_share {(gaps == 0).mean():.4f} served_gap_max "
            f"{nums['served_gap_max']:.4f} p90 "
            f"{np.quantile(gaps, 0.9):.4f}")
        limits = self.config["limits"]
        return [Check(name, nums[name], limits[name]) for name in limits]

    def lanes(self):
        """Every lane of the window: (prompts (N, S), served (N, M))."""
        prompts = np.concatenate([self.prompt_of(t) for t in self.turns])
        served = np.concatenate([t["tokens"] for t in self.turns])
        return prompts, served

    def served_gaps(self, *, fp8: bool = False, probe=None):
        """The reference's gaps over every lane of the window, (N, M);
        the same for `probe` tokens; the reference's argmax tokens.
        `fp8` runs the reference as the float8 control."""
        prompts, served = self.lanes()
        return reference().logit_gaps(
            self.ref_params(), prompts, served, self.config,
            self.config["kv_cascade"], fp8=fp8, probe=probe)

    def prompt_of(self, turn) -> np.ndarray:
        """The prompt the agent served, rebuilt from the benchmark's own
        documents: each retrieved slot's tokens, then the query."""
        ar = self.config["arena"]
        rows = []
        for lane, ids in enumerate(turn["retrieved"]):
            parts = [self.docs[self.owner[s], self.slot_doc[s]] if s >= 0
                     else np.zeros(ar["doc_tokens"], np.int32) for s in ids]
            parts.append(self.docs[turn["tids"][lane], turn["docs"][lane]])
            rows.append(np.concatenate(parts))
        return np.clip(np.stack(rows), 0, self.config["vocab_size"] - 1)

    def ref_params(self):
        if not hasattr(self, "_ref_params"):
            self._ref_params = reference().make_params(
                self.config, self.seed, self.config["torch_dtype"])
        return self._ref_params

    def end_to_end(self) -> dict:
        rec = self.record
        return {"agent_tokens_per_s": rec["tokens"] / rec["span_s"]}
