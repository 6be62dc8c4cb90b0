"""End-to-end RAG pipelines (Fig. 1 of the paper; single- and multi-tenant).

offline:  doc tokens --MiniLM embedder--> float embeddings --INT8 quant-->
          nibble-planar DB (optionally sharded over a mesh)
online:   query tokens -> query embedding -> INT8 codes
          -> TWO-STAGE HIERARCHICAL RETRIEVAL (the paper's core)
          -> augmented prompt = [retrieved doc tokens; query tokens]
          -> generator prefill + decode

`MultiTenantRAGPipeline` is the streaming/wearable variant: there is no
offline phase — per-user corpora are ingested online into a shared
fixed-capacity arena (repro.tenancy) and a mixed batch of users is served
by ONE segment-masked retrieval launch.

Both pipelines report the retrieval energy ledger per query batch via the
paper-calibrated cost model (core.energy), so serving logs expose the same
numbers the paper's Table II does.
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import (BitPlanarDB, RetrievalConfig, RetrievalEngine,
                        build_database, energy, quantize_int8)
from repro.core import engine as engine_mod
from repro.core.index import ShardedIndex
from repro.models import embedder as emb_mod
from repro.models.common import ModelConfig
from repro.models.registry import ModelApi
from repro.serve.sampler import generate, jitted_fns, sample_tokens
from repro.tenancy import MultiTenantIndex


@dataclasses.dataclass
class RAGPipeline:
    emb_cfg: ModelConfig
    emb_params: Any
    gen_api: ModelApi
    gen_params: Any
    retrieval_cfg: RetrievalConfig
    doc_tokens: jax.Array                  # (N, doc_len) int32
    db: BitPlanarDB | None = None          # single-host DB
    index: ShardedIndex | None = None      # pod-sharded DB (preferred)
    # index.retrieve_fn wraps shard_map in a FRESH jax.jit each time it is
    # called, so it must be built once and cached here — rebuilding it per
    # query forced a retrace+recompile on every request. The cache is a
    # (cfg, fn) pair KEYED on the config: replacing `retrieval_cfg` after
    # construction invalidates it instead of silently serving the old
    # k/metric/backend.
    _sharded_retrieve: Any = dataclasses.field(default=None, repr=False,
                                               compare=False)

    @classmethod
    def build(cls, emb_cfg, emb_params, gen_api, gen_params, doc_tokens,
              retrieval_cfg: RetrievalConfig | None = None, mesh=None,
              encode_batch: int = 64):
        """Offline phase: embed + quantize the document corpus."""
        retrieval_cfg = retrieval_cfg or RetrievalConfig()
        n = doc_tokens.shape[0]
        chunks = []
        enc = jax.jit(lambda p, t: emb_mod.encode(p, t, emb_cfg))
        for i in range(0, n, encode_batch):
            chunks.append(enc(emb_params, doc_tokens[i:i + encode_batch]))
        embs = jnp.concatenate(chunks, axis=0)
        if mesh is not None:
            index = ShardedIndex.build(embs, mesh)
            db = None
        else:
            index = None
            db = BitPlanarDB.from_quantized(build_database(embs))
        return cls(emb_cfg=emb_cfg, emb_params=emb_params, gen_api=gen_api,
                   gen_params=gen_params, retrieval_cfg=retrieval_cfg,
                   doc_tokens=doc_tokens, db=db, index=index)

    # -- retrieval ---------------------------------------------------------

    def retrieve(self, query_tokens: jax.Array):
        """query_tokens (B, L) -> (indices (B, k), energy ledger)."""
        q_emb = emb_mod.encode(self.emb_params, query_tokens, self.emb_cfg)
        q_codes, _ = quantize_int8(q_emb, per_vector=True)
        if self.index is not None:
            cached = self._sharded_retrieve
            if cached is None or cached[0] != self.retrieval_cfg:
                self._sharded_retrieve = (
                    self.retrieval_cfg,
                    self.index.retrieve_fn(self.retrieval_cfg))
            res = self._sharded_retrieve[1](q_codes)
            n_docs = self.index.n_global
        else:
            # Batch-native engine core: one launch, doc plane streamed
            # once for the whole query batch.
            res = RetrievalEngine(self.retrieval_cfg).retrieve(q_codes,
                                                               self.db)
            n_docs = self.db.num_docs
        dim = q_emb.shape[-1]
        # Charge what the engine's schedule actually streams — the
        # launch's per-stage ledger (shared-plane stage-1 bytes amortized
        # over the batch, exact stage sized by the candidate budget) —
        # not the analytic full-scan cost_hierarchical, which ignored the
        # batch amortization entirely and overcharged every multi-query
        # launch. Same pattern as MultiTenantRAGPipeline.retrieve.
        b = int(q_codes.shape[0])
        plan = engine_mod.plan(self.retrieval_cfg, num_docs=n_docs,
                               dim=dim, batch=b, kind="plain")
        ledger = energy.cost_cascade(plan.stages, dim, batch=plan.batch)
        return res, ledger

    # -- generation --------------------------------------------------------

    def answer(self, query_tokens: jax.Array, *, max_new: int = 32,
               temperature: float = 0.0, key=None):
        """Full RAG answer: retrieve, augment, generate.

        Returns (generated tokens (B, max_new), retrieved ids (B, k),
        energy ledger for the retrieval stage)."""
        res, ledger = self.retrieve(query_tokens)
        ids = res.indices                                 # (B, k)
        b, k = ids.shape
        docs = jnp.take(self.doc_tokens, ids.reshape(-1), axis=0)
        docs = docs.reshape(b, k * self.doc_tokens.shape[1])
        prompt = jnp.concatenate([docs, query_tokens], axis=1)
        vocab = self.gen_api.cfg.vocab_size
        prompt = jnp.clip(prompt, 0, vocab - 1)
        out, _ = generate(self.gen_api, self.gen_params, {"tokens": prompt},
                          max_new=max_new, temperature=temperature, key=key)
        return out, ids, ledger


@dataclasses.dataclass
class AgentTurnReport:
    """Accounting for one end-to-end agent turn (retrieve + decode)."""
    tokens: jax.Array            # (B, max_new) generated ids
    retrieved: np.ndarray        # (B, k) arena slot ids (-1 = no hit)
    retrieval_cost: Any          # energy.CostBreakdown, PER QUERY
    decode_cost: Any             # energy.CostBreakdown, PER TOKEN
    decode_plan: Any             # engine.SchedulePlan (kind="decode")
    uj_per_query: float
    uj_per_token: float
    decode_bytes_per_token: int      # measured ledger, whole batch
    dense_bytes_per_token: int       # dense-decode baseline, whole batch


@dataclasses.dataclass
class RAGAgent:
    """End-to-end agent turn: ONE `ServingRuntime` schedules both the
    retrieval launch and the decode-step KV cascade.

    The two memory-bound lookups of a wearable agent turn — corpus
    retrieval and per-step cache attention — run through the same engine
    cascade machinery and land in the same registry: retrieval publishes
    its measured `SchedulePlan` and µJ/query (as before), decode charges
    its `kv_plan` ledger via `runtime.account_decode` into µJ/token. The
    generator must be a dense-family model (the quantized-KV decode path
    lives in models/dense)."""

    pipeline: "MultiTenantRAGPipeline"
    runtime: Any                      # serve.runtime.ServingRuntime
    # decode cascade knobs (see sparse_kv.sparse_decode_attention)
    top_k: int = 64
    npages: int | None = None
    prescreen_c0: int | None = None
    page_rows: int = 8
    backend: str | None = None      # None: Pallas on a TPU, jnp elsewhere
    _decode_jit: Any = dataclasses.field(default=None, repr=False,
                                         compare=False)

    def __post_init__(self):
        api = self.pipeline.gen_api
        if api is None or api.cfg.family != "dense":
            raise ValueError("RAGAgent needs a dense-family generator "
                             "(quantized-KV decode lives in models/dense)")
        if self.runtime.index is not self.pipeline.index:
            raise ValueError("runtime must serve the pipeline's index — "
                             "one runtime schedules retrieval AND decode")

    # -- decode plumbing ---------------------------------------------------

    def _decode_step(self):
        if self._decode_jit is None:
            from repro.models import dense
            cfg = self.pipeline.gen_api.cfg
            knobs = dict(top_k=self.top_k, npages=self.npages,
                         prescreen_c0=self.prescreen_c0,
                         backend=self.backend)
            # its program's XLA name, `jit__lambda`, is how the benchmark
            # finds the decode step in a trace (bench/layer.py)
            self._decode_jit = jax.jit(
                lambda p, c, t: dense.decode_step_quant(p, c, t, cfg,
                                                        **knobs))
        return self._decode_jit

    def _total_len(self, prompt_len: int, max_new: int) -> int:
        total = prompt_len + max_new
        if self.npages is not None:
            total = -(-total // self.page_rows) * self.page_rows
        return total

    # -- the turn ----------------------------------------------------------

    def turn(self, tenant_ids, query_tokens: jax.Array, *,
             max_new: int = 16, temperature: float = 0.0, key=None,
             now: float | None = None) -> AgentTurnReport:
        """Retrieve through the runtime, generate with the KV cascade,
        charge both against one registry. Returns an AgentTurnReport.

        Each phase is a span of the runtime's tracer (`rag.retrieve`,
        `rag.prefill`, `rag.decode`, `rag.account`), so a profiler
        places the turn's host work beside the device's."""
        from repro.models import dense

        pipe = self.pipeline
        api, cfg = pipe.gen_api, pipe.gen_api.cfg
        tracer = self.runtime.tracer
        # 1. retrieval: per-request admission through the runtime (the
        # scheduler batches the tenants into one segment-masked launch).
        with tracer.span("rag.retrieve", now=now):
            q_emb = pipe._embed(jnp.asarray(query_tokens))
            q_codes, _ = quantize_int8(q_emb, per_vector=True)
            codes = np.asarray(q_codes)
            handles = [self.runtime.submit(int(t), codes[i], now=now)
                       for i, t in enumerate(np.asarray(tenant_ids))]
            self.runtime.flush(now=now)
            ids = np.stack([np.asarray(h.result().indices)
                            for h in handles])
            retrieval_cost = self.runtime.energy_ledger(q_emb.shape[-1])
        with tracer.span("rag.prefill", now=now):
            # 2. prompt assembly (invalid hits contribute zero tokens).
            b, k = ids.shape
            flat = ids.reshape(-1)
            docs = np.where((flat >= 0)[:, None],
                            pipe.doc_tokens[np.maximum(flat, 0)], 0)
            docs = jnp.asarray(docs.reshape(b, k * pipe.doc_tokens.shape[1]))
            prompt = jnp.concatenate([docs, jnp.asarray(query_tokens)],
                                     axis=1)
            prompt = jnp.clip(prompt, 0, cfg.vocab_size - 1)
            # 3. prefill (cached jit — no per-turn recompiles), then
            # convert the bf16 cache to the nibble-planar QuantCache once.
            total = self._total_len(prompt.shape[1], max_new)
            prefill_fn, _ = jitted_fns(api)
            logits, cache = prefill_fn(self.pipeline.gen_params,
                                       {"tokens": prompt}, max_len=total)
            qcache = dense.quantize_cache(
                cache, page_rows=self.page_rows if self.npages else None)
        # 4. decode loop: every step's attention is the engine cascade.
        with tracer.span("rag.decode", now=now):
            key = key if key is not None else jax.random.PRNGKey(0)
            step = self._decode_step()
            tok = sample_tokens(logits[:, -1:], key, temperature)
            outs = [tok]
            for i in range(max_new - 1):
                key = jax.random.fold_in(key, i)
                logits, qcache = step(pipe.gen_params, qcache, tok)
                tok = sample_tokens(logits, key, temperature)
                outs.append(tok)
            toks = jnp.concatenate(outs, axis=1)
        # 5. decode accounting: one kv_plan prices the run (the stage
        # geometry is fixed at the cache's allocated length), charged
        # through the SAME runtime as the retrieval launch.
        with tracer.span("rag.account", now=now):
            kv_cfg = engine_mod.KVCascadeConfig(
                top_k=self.top_k, npages=self.npages,
                page_rows=self.page_rows, prescreen_c0=self.prescreen_c0,
                backend=self.backend)
            plan = engine_mod.kv_plan(kv_cfg, batch=b,
                                      kv_heads=cfg.num_kv_heads,
                                      q_heads=cfg.num_heads, seq_len=total,
                                      head_dim=cfg.hd,
                                      layers=cfg.num_layers)
            decode_cost = self.runtime.account_decode(plan, dim=cfg.hd,
                                                      tokens=max_new)
            from repro.serve import sparse_kv
            dense_bytes = (b * cfg.num_layers * cfg.num_kv_heads
                           * sparse_kv.dense_bytes_per_step(total, cfg.hd))
        return AgentTurnReport(
            tokens=toks, retrieved=ids, retrieval_cost=retrieval_cost,
            decode_cost=decode_cost, decode_plan=plan,
            uj_per_query=retrieval_cost.total_uj,
            uj_per_token=decode_cost.total_uj,
            decode_bytes_per_token=sum(s.bytes_hbm for s in plan.stages),
            dense_bytes_per_token=dense_bytes)


@dataclasses.dataclass
class MultiTenantRAGPipeline:
    """Streaming RAG serving many per-user corpora from ONE shared arena.

    No offline build: tenants ingest documents online (encode -> fixed-scale
    INT8 quantize -> pack into free arena slots, O(rows) per ingest) and a
    mixed batch of tenants' queries runs as one vmapped segment-masked
    two-stage retrieval. Document tokens live in a host-side slot-addressed
    store kept in lockstep with the arena (including across compactions).

    The retrieval entry points are top-level jitted functions, so repeat
    calls at the same batch shape reuse the compiled executable — no
    per-request retrace.
    """

    emb_cfg: ModelConfig
    emb_params: Any
    gen_api: ModelApi | None
    gen_params: Any
    index: MultiTenantIndex
    doc_tokens: np.ndarray                 # (capacity, doc_len) int32
    _encode: Any = dataclasses.field(default=None, repr=False, compare=False)

    @classmethod
    def create(cls, emb_cfg, emb_params, gen_api, gen_params, *,
               capacity: int, doc_len: int,
               retrieval_cfg: RetrievalConfig | None = None,
               clusters=None):
        """clusters: optional repro.core.clustering.ClusterParams —
        enables the cluster-pruned cascade for this pipeline's index."""
        index = MultiTenantIndex(capacity, emb_cfg.pooled_dim,
                                 retrieval_cfg or RetrievalConfig(),
                                 clusters=clusters)
        return cls(emb_cfg=emb_cfg, emb_params=emb_params, gen_api=gen_api,
                   gen_params=gen_params, index=index,
                   doc_tokens=np.zeros((capacity, doc_len), np.int32))

    def _embed(self, tokens: jax.Array) -> jax.Array:
        if self._encode is None:
            cfg = self.emb_cfg

            def encode_tokens(p, t):
                return emb_mod.encode(p, t, cfg)
            self._encode = jax.jit(encode_tokens)
        return self._encode(self.emb_params, tokens)

    # -- online corpus mutation -------------------------------------------

    def ingest(self, tenant_id: int, doc_tokens) -> np.ndarray:
        """Add (B, doc_len) docs to one tenant's corpus; returns slot ids."""
        doc_tokens = np.asarray(doc_tokens, np.int32)
        embs = self._embed(jnp.asarray(doc_tokens))
        slots = self.index.ingest(tenant_id, embs)
        self.doc_tokens[slots] = doc_tokens
        return slots

    def delete(self, tenant_id: int, slots) -> None:
        self.index.delete(tenant_id, slots)

    def compact(self) -> np.ndarray:
        """Reclaim tombstones; remaps the token store with the arena."""
        mapping = self.index.compact()
        moved = np.nonzero(mapping >= 0)[0]
        new_tokens = np.zeros_like(self.doc_tokens)
        new_tokens[mapping[moved]] = self.doc_tokens[moved]
        self.doc_tokens = new_tokens
        return mapping

    # -- query -------------------------------------------------------------

    def retrieve(self, tenant_ids, query_tokens: jax.Array):
        """(B,) tenant ids + (B, L) query tokens -> (results, energy ledger).

        Queries of DIFFERENT tenants batch together: one embedder forward,
        one segment-masked retrieval launch over the shared arena."""
        q_emb = self._embed(jnp.asarray(query_tokens))
        # Per-vector query quantization: only the DOC rows must share the
        # arena's fixed scale; a query-side scale rescales all of one
        # query's scores equally and cannot change its ranking.
        q_codes, _ = quantize_int8(q_emb, per_vector=True)
        res = self.index.retrieve(q_codes, tenant_ids)
        # Account what the engine's schedule ACTUALLY streams: the
        # launch's per-stage SchedulePlan ledger (windowed lanes charge
        # their window, cluster-pruned lanes their probed blocks, the
        # centroid plane its K rows) instead of re-deriving traffic from
        # a full-arena scan and the default-candidates heuristic.
        plan = self.index.last_plan
        if plan is not None:
            ledger = energy.cost_cascade(plan.stages, q_emb.shape[-1],
                                         batch=plan.batch)
        else:
            ledger = energy.cost_hierarchical(self.index.capacity,
                                              q_emb.shape[-1])
        return res, ledger

    def answer(self, tenant_ids, query_tokens: jax.Array, *,
               max_new: int = 32, temperature: float = 0.0, key=None):
        """Retrieve per-tenant context and generate, one mixed batch.

        Invalid hits (tenant owns fewer than k live docs) contribute
        all-zero context tokens. Returns (tokens, slot ids, ledger)."""
        if self.gen_api is None:
            raise ValueError("pipeline was created without a generator")
        res, ledger = self.retrieve(tenant_ids, query_tokens)
        ids = np.asarray(res.indices)                     # (B, k)
        b, k = ids.shape
        flat = ids.reshape(-1)
        docs = np.where((flat >= 0)[:, None],
                        self.doc_tokens[np.maximum(flat, 0)], 0)
        docs = jnp.asarray(docs.reshape(b, k * self.doc_tokens.shape[1]))
        prompt = jnp.concatenate([docs, jnp.asarray(query_tokens)], axis=1)
        vocab = self.gen_api.cfg.vocab_size
        prompt = jnp.clip(prompt, 0, vocab - 1)
        out, _ = generate(self.gen_api, self.gen_params, {"tokens": prompt},
                          max_new=max_new, temperature=temperature, key=key)
        return out, ids, ledger
