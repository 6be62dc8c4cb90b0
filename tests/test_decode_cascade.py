"""KV-decode cascade: the engine-backed sparse-KV path (ISSUE 10).

Gates the refactor's contract:
  * the engine path is BIT-IDENTICAL to the legacy hand-rolled
    `sparse_decode_attention_ref` across lengths {0, <top_k, >=top_k},
    mixed-length batches, and both backends — including the paged /
    prescreened schedules at full coverage, where the cascade must
    degenerate to the same selection;
  * the decode StagePlan ledger reconciles with `sparse_bytes_per_step`;
  * the pruned cascade's jnp and Pallas stage kernels agree bit-for-bit;
  * page centroids maintained incrementally equal a from-scratch rebuild;
  * the runtime charges decode through the same registry as retrieval.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import energy, engine
from repro.kernels import ops as kops
from repro.kernels import ref as kref
from repro.serve import sparse_kv

B, T, H, KH, HD = 2, 64, 8, 4, 32


def make_cache(seed=0, b=B, t=T, kh=KH, hd=HD, paged=False, page_rows=8):
    rng = np.random.default_rng(seed)
    k = jnp.asarray(rng.normal(size=(b, t, kh, hd)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(b, t, kh, hd)), jnp.float32)
    cache = sparse_kv.build_quant_cache(k, v)
    if paged:
        cache = sparse_kv.build_page_centroids(
            cache, jnp.full((b,), t, jnp.int32), page_rows=page_rows)
    return cache, k, v


def make_q(seed=2, b=B, h=H, hd=HD):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.normal(size=(b, 1, h, hd)), jnp.float32)


# -- bit parity vs the legacy implementation --------------------------------

@pytest.mark.parametrize("length", [0, 3, 17, T])
def test_engine_path_bit_identical_to_legacy(length):
    cache, _, _ = make_cache()
    q = make_q()
    L = jnp.full((B,), length, jnp.int32)
    ref = sparse_kv.sparse_decode_attention_ref(q, cache, L, top_k=16)
    got = sparse_kv.sparse_decode_attention(q, cache, L, top_k=16)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))


def test_engine_path_bit_identical_mixed_lengths():
    cache, _, _ = make_cache()
    q = make_q()
    L = jnp.asarray([0, 40], jnp.int32)   # one empty lane, one live lane
    ref = sparse_kv.sparse_decode_attention_ref(q, cache, L, top_k=16)
    got = sparse_kv.sparse_decode_attention(q, cache, L, top_k=16)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))
    assert not np.any(np.isnan(np.asarray(got)))


@pytest.mark.parametrize("backend", ["jnp", "pallas"])
def test_paged_full_coverage_degenerates_to_legacy(backend):
    """npages covering every page + prescreen keeping every row must
    select exactly the legacy candidate set (survivors re-sorted
    ascending), so the cascade output is bit-identical — on BOTH the jnp
    and the Pallas stage kernels."""
    cache, _, _ = make_cache(paged=True)
    q = make_q()
    L = jnp.full((B,), T, jnp.int32)
    ref = sparse_kv.sparse_decode_attention_ref(q, cache, L, top_k=16)
    paged = sparse_kv.sparse_decode_attention(
        q, cache, L, top_k=16, npages=T // 8, backend=backend)
    np.testing.assert_array_equal(np.asarray(paged), np.asarray(ref))
    ps = sparse_kv.sparse_decode_attention(
        q, cache, L, top_k=16, npages=T // 8, prescreen_c0=T,
        backend=backend)
    np.testing.assert_array_equal(np.asarray(ps), np.asarray(ref))


@pytest.mark.parametrize("lengths", [(5, 23), (0, 0), (64, 1)])
def test_pruned_cascade_jnp_vs_pallas_bit_parity(lengths):
    """The PRUNED schedules (partial page coverage, sign prescreen) have
    no legacy twin; their contract is backend equivalence — the Pallas
    prune/prescreen kernels must select the same pages/rows as the jnp
    reference fns, making the whole cascade bit-identical."""
    cache, _, _ = make_cache(paged=True)
    q = make_q()
    L = jnp.asarray(lengths, jnp.int32)
    for kwargs in ({"npages": 4}, {"npages": 6, "prescreen_c0": 24}):
        a = sparse_kv.sparse_decode_attention(q, cache, L, top_k=8,
                                              backend="jnp", **kwargs)
        b = sparse_kv.sparse_decode_attention(q, cache, L, top_k=8,
                                              backend="pallas", **kwargs)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        assert not np.any(np.isnan(np.asarray(a)))


def test_empty_cache_paged_returns_zeros():
    cache, _, _ = make_cache(paged=True)
    q = make_q()
    out = sparse_kv.sparse_decode_attention(
        q, cache, jnp.zeros((B,), jnp.int32), top_k=8, npages=4)
    np.testing.assert_array_equal(np.asarray(out), np.zeros(q.shape))


# -- semantics the refactor must preserve -----------------------------------

def test_convergence_to_dense_as_topk_grows():
    """As top_k -> T (pages covering the cache), the cascade converges to
    exact dense attention up to INT8 key-quantization error."""
    from repro.models import attention as A
    cache, k, v = make_cache(paged=True)
    q = make_q()
    L = jnp.full((B,), T, jnp.int32)
    want = A.decode_attention(q, k, v, L)
    errs = []
    for top_k in (4, 16, T):
        got = sparse_kv.sparse_decode_attention(q, cache, L, top_k=top_k,
                                                npages=T // 8)
        errs.append(float(jnp.max(jnp.abs(got - want))))
    assert errs[-1] < 0.05                  # full top_k: quantization only
    assert errs[0] >= errs[-1]              # error shrinks as k grows


def test_gqa_group_max_selection():
    """A key relevant ONLY to the second query head of a group must still
    be selected: stage-1 takes the max over the group's scores, not head
    0's. With kh=1, h=2 the key aligned with head 1 dominates that head's
    attention, so small-top_k output must match full attention."""
    from repro.models import attention as A
    b, t, kh, hd, h = 1, 64, 1, 16, 2
    rng = np.random.default_rng(5)
    k = jnp.asarray(rng.normal(size=(b, t, kh, hd)) * 0.1, jnp.float32)
    q = jnp.asarray(rng.normal(size=(b, 1, h, hd)), jnp.float32)
    k = k.at[0, 37, 0].set(q[0, 0, 1] * 2.0)   # aligns with head 1 ONLY
    v = jnp.asarray(rng.normal(size=(b, t, kh, hd)), jnp.float32)
    L = jnp.full((b,), t, jnp.int32)
    cache = sparse_kv.build_quant_cache(k, v)
    got = sparse_kv.sparse_decode_attention(q, cache, L, top_k=8)
    want = A.decode_attention(q, k, v, L)
    # head 1 is dominated by key 37, so its top-8 output must be close to
    # exact — ONLY possible if the group-max kept the key that head 0's
    # scores alone would have discarded. (Head 0 with its relevance mass
    # spread over pruned keys is the documented approximation regime.)
    assert float(jnp.max(jnp.abs(got[:, :, 1] - want[:, :, 1]))) < 0.25
    # and dropping the group-max entirely (score with head 0 only) loses
    # key 37: head 1's output degrades
    got0 = sparse_kv.sparse_decode_attention(q.at[:, :, 1].set(q[:, :, 0]),
                                             cache, L, top_k=8)
    assert not np.allclose(np.asarray(got0[:, :, 1]),
                           np.asarray(want[:, :, 1]), atol=0.25)


# -- page-centroid maintenance ----------------------------------------------

def test_incremental_centroid_update_matches_rebuild():
    """Appending one key and refreshing ONE page incrementally must equal
    rebuilding every centroid from scratch at the new length."""
    page_rows = 8
    cache, _, _ = make_cache()
    for length in (1, 7, 8, 33):            # page starts, middles, ends
        L = jnp.full((B,), length, jnp.int32)
        full = sparse_kv.build_page_centroids(cache, L, page_rows)
        # start from the PREVIOUS length's centroids
        prev = sparse_kv.build_page_centroids(cache, L - 1, page_rows)
        cm, cs = sparse_kv.update_page_centroids(
            cache.k_msb, cache.k_lsb, cache.k_scale,
            prev.cent_msb, prev.cent_scale, L, page_rows)
        np.testing.assert_array_equal(np.asarray(cm),
                                      np.asarray(full.cent_msb))
        np.testing.assert_array_equal(np.asarray(cs),
                                      np.asarray(full.cent_scale))


def test_centroid_rows_kernel_matches_ref():
    """The named per-lane centroid kernel (KV page prune's stage-0) vs
    its oracle, bit-for-bit."""
    rng = np.random.default_rng(9)
    bq, p, d = 6, 16, 32
    q = jnp.asarray(rng.integers(-8, 8, size=(bq, d)), jnp.int8)
    rows = jnp.asarray(rng.integers(0, 256, size=(bq, p, d // 2)),
                       jnp.uint8)
    got = kops.centroid_scores_rows(q, rows)
    want = kref.centroid_scores_rows_ref(kops.pack_queries_even_odd(q),
                                         rows)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# -- ledger + pricing --------------------------------------------------------

def test_kv_plan_reconciles_with_sparse_bytes_per_step():
    """The no-prune decode ledger divided by (layers * batch * kv_heads)
    IS the byte model — same currency as the retrieval plans."""
    t, hd, k, kh, qh, b, layers = 32768, 128, 256, 8, 32, 4, 16
    plan = sparse_kv.decode_plan(k, batch=b, kv_heads=kh, q_heads=qh,
                                 seq_len=t, head_dim=hd, layers=layers)
    assert plan.kind == "decode"
    per_lane = sum(s.bytes_hbm for s in plan.stages) / (b * kh * layers)
    assert per_lane == sparse_kv.sparse_bytes_per_step(t, hd, k)


def test_kv_plan_page_prune_cuts_scan_bytes():
    cfg = engine.KVCascadeConfig(top_k=256, npages=64, page_rows=16,
                                 prescreen_c0=512)
    kw = dict(batch=4, kv_heads=8, q_heads=32, seq_len=32768, head_dim=128,
              layers=16)
    paged = engine.kv_plan(cfg, **kw)
    flat = engine.kv_plan(engine.KVCascadeConfig(top_k=256), **kw)
    names = [s.name for s in paged.stages]
    assert names == ["prune", "prescreen", "approx", "exact"]
    assert (sum(s.bytes_hbm for s in paged.stages)
            < sum(s.bytes_hbm for s in flat.stages) / 4)


def test_decode_cost_prices_like_retrieval():
    """energy.cost_cascade prices the decode ledger with the same model
    as retrieval ledgers: µJ/token falls when the schedule streams fewer
    bytes, and the dense-vs-sparse byte ratio clears 4x at k << T."""
    t, hd, k = 32768, 128, 256
    kw = dict(batch=4, kv_heads=8, q_heads=32, seq_len=t, head_dim=hd,
              layers=16)
    flat = engine.kv_plan(engine.KVCascadeConfig(top_k=k), **kw)
    paged = engine.kv_plan(engine.KVCascadeConfig(
        top_k=k, npages=64, page_rows=16), **kw)
    c_flat = energy.cost_cascade(flat.stages, hd, batch=flat.batch)
    c_paged = energy.cost_cascade(paged.stages, hd, batch=paged.batch)
    assert 0 < c_paged.total_uj < c_flat.total_uj
    dense = sparse_kv.dense_bytes_per_step(t, hd)
    assert dense / sparse_kv.sparse_bytes_per_step(t, hd, k) > 4


def test_runtime_account_decode_ledger_and_registry():
    from repro.obs import MetricsRegistry
    from repro.serve import RuntimeConfig, ServingRuntime
    from repro.tenancy import MultiTenantIndex
    from repro.core import RetrievalConfig

    idx = MultiTenantIndex(64, 32, RetrievalConfig())
    reg = MetricsRegistry()
    rt = ServingRuntime(idx, RuntimeConfig(), registry=reg)
    plan = engine.kv_plan(engine.KVCascadeConfig(top_k=16), batch=2,
                          kv_heads=2, q_heads=4, seq_len=64, head_dim=32,
                          layers=2)
    cost = rt.account_decode(plan, dim=32, tokens=10)
    assert cost.total_uj > 0
    assert rt.decode_steps == 10
    assert rt.decode_bytes_hbm == 10 * sum(s.bytes_hbm for s in plan.stages)
    hist = reg.snapshot()["histograms"]
    assert hist["energy_uj_per_token"]["count"] == 10
    # stage counters fanned out under the same names as retrieval stages
    counters = reg.snapshot()["counters"]
    assert counters["stage_bytes_hbm{stage=approx}"] > 0
    # non-decode plans are refused — retrieval stays on observe_cost
    rplan = engine.plan(RetrievalConfig(), num_docs=64, dim=32, batch=2,
                        kind="plain")
    with pytest.raises(ValueError):
        rt.account_decode(rplan, dim=32)


def test_named_scopes_leave_the_kv_cascade_bit_identical(monkeypatch):
    """`kv.*` named scopes are metadata: the paged, prescreened KV
    cascade compiled with them returns what it returns without them, and
    only the scoped program names its stages."""
    import contextlib
    cache, _, _ = make_cache(paged=True)
    q = make_q()
    L = jnp.asarray([T, 40], jnp.int32)
    cfg = engine.KVCascadeConfig(top_k=8, npages=4, page_rows=8,
                                 prescreen_c0=16)
    policy = sparse_kv.kv_policy(cache, L)

    def run():
        def cascade(q, policy, cfg):        # a new function: a new trace
            return engine._kv_cascade(q, policy, cfg)
        fn = jax.jit(cascade, static_argnames=("cfg",))
        return (np.asarray(fn(q, policy, cfg=cfg)),
                fn.lower(q, policy, cfg=cfg).compile().as_text())
    scoped, text = run()
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    plain, plain_text = run()
    np.testing.assert_array_equal(scoped, plain)
    from repro.launch.hlo_analysis import op_scopes
    assert set(op_scopes(text).values()) == {
        "kv.QueryQuant", "kv.KVPagePrune", "kv.KVSignPrescreen",
        "kv.KVApproxTopK", "kv.KVExactAttend"}
    assert op_scopes(plain_text) == {}


# -- end-to-end agent turn ---------------------------------------------------

def test_rag_agent_turn_reports_uj_per_token():
    from repro.models import embedder as emb_mod
    from repro.models.common import ModelConfig
    from repro.models.registry import get_model
    from repro.obs import MetricsRegistry, Tracer
    from repro.serve import (MultiTenantRAGPipeline, RAGAgent,
                             RuntimeConfig, ServingRuntime)

    emb_cfg = ModelConfig(name="e", family="dense", num_layers=1,
                          d_model=32, num_heads=2, num_kv_heads=2,
                          d_ff=64, vocab_size=64, pooled_dim=32)
    emb_params = emb_mod.init_params(emb_cfg, jax.random.PRNGKey(7))
    gen_cfg = ModelConfig(name="g", family="dense", num_layers=2,
                          d_model=64, num_heads=4, num_kv_heads=2,
                          d_ff=96, vocab_size=64)
    api = get_model(gen_cfg)
    gen_params = api.init(jax.random.PRNGKey(1))
    pipe = MultiTenantRAGPipeline.create(emb_cfg, emb_params, api,
                                         gen_params, capacity=64, doc_len=4)
    rng = np.random.default_rng(0)
    for t in range(2):
        pipe.ingest(t, rng.integers(0, 64, size=(6, 4)))
    reg, tracer = MetricsRegistry(), Tracer()
    rt = ServingRuntime(pipe.index,
                        RuntimeConfig(max_batch=2, auto_flush=False),
                        registry=reg, tracer=tracer)
    agent = RAGAgent(pipeline=pipe, runtime=rt, top_k=16, npages=4,
                     prescreen_c0=24, page_rows=8)
    q = jnp.asarray(rng.integers(0, 64, size=(2, 4)))
    rep = agent.turn(np.array([0, 1]), q, max_new=6, now=0.0)
    assert rep.tokens.shape == (2, 6)
    assert rep.uj_per_query > 0 and rep.uj_per_token > 0
    assert rep.decode_plan.kind == "decode"
    assert rt.decode_steps == 6
    # both workloads landed in ONE registry
    hist = reg.snapshot()["histograms"]
    assert hist["energy_uj_per_query"]["count"] >= 2
    assert hist["energy_uj_per_token"]["count"] == 6
    # the turn's phases are spans of the runtime's tracer, in order, the
    # runtime's own inside the retrieval
    assert [e.name for e in tracer.spans() if e.ph == "X"] == [
        "runtime.launch", "runtime.retire", "rag.retrieve", "rag.prefill",
        "rag.decode", "rag.account"]
    # greedy decoding is deterministic across turns (cached jits)
    rep2 = agent.turn(np.array([0, 1]), q, max_new=6, now=1.0)
    np.testing.assert_array_equal(np.asarray(rep.tokens),
                                  np.asarray(rep2.tokens))
