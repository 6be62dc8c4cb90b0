"""Serving runtime: deadline batcher, fairness, hot-cluster cache parity.

The bit-exactness contract under test: the runtime's batching, padding,
and hot-cluster cache may change WHEN work runs and WHERE stage-1 bytes
come from, but never WHAT any request retrieves — including across arena
mutations, where a stale cached view must be evicted, not served.
"""
import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import RetrievalConfig, quantize_int8
from repro.core.clustering import ClusterParams
from repro.serve.runtime import (HotClusterCache, RequestHandle,
                                 RuntimeConfig, ServingRuntime)
from repro.tenancy import MultiTenantIndex

DIM = 64


def make_clustered_index(tenants=4, docs_per_tenant=96, k=3, seed=0,
                         num_clusters=8, nprobe=2, block_rows=32,
                         capacity=1024, prescreen_c0=None):
    rng = np.random.default_rng(seed)
    idx = MultiTenantIndex(capacity, DIM,
                           RetrievalConfig(k=k, prescreen_c0=prescreen_c0),
                           clusters=ClusterParams(num_clusters=num_clusters,
                                                  nprobe=nprobe,
                                                  block_rows=block_rows))
    docs = {}
    for t in range(tenants):
        d = rng.normal(size=(docs_per_tenant, DIM)).astype(np.float32)
        idx.ingest(t, jnp.asarray(d))
        docs[t] = d
    idx.compact()
    queries = {t: np.asarray(quantize_int8(jnp.asarray(d[:8]),
                                           per_vector=True)[0])
               for t, d in docs.items()}
    return idx, queries


def make_plain_index(tenants=3, seed=0, capacity=256, k=3):
    """No clustering; interleaved ingests FRAGMENT every tenant so the
    batched path falls back to the full-arena masked scan (whose per-lane
    results are independent of batch composition by construction)."""
    rng = np.random.default_rng(seed)
    idx = MultiTenantIndex(capacity, DIM, RetrievalConfig(k=k))
    docs = {t: [] for t in range(tenants)}
    for _ in range(3):
        for t in range(tenants):
            d = rng.normal(size=(5, DIM)).astype(np.float32)
            idx.ingest(t, jnp.asarray(d))
            docs[t].append(d)
    docs = {t: np.concatenate(v) for t, v in docs.items()}
    assert any(len(idx.table.segments(t)) > 1 for t in range(tenants))
    queries = {t: np.asarray(quantize_int8(jnp.asarray(d[:6]),
                                           per_vector=True)[0])
               for t, d in docs.items()}
    return idx, queries


# ---------------------------------------------------------------------------
# Admission: deadlines, max-batch, fairness, handles
# ---------------------------------------------------------------------------

def test_deadline_admission_virtual_clock():
    idx, q = make_plain_index()
    rt = ServingRuntime(idx, RuntimeConfig(max_batch=8, max_wait=5.0,
                                           auto_flush=False))
    h = rt.submit(0, q[0][0], now=0.0)
    assert not rt.ready(now=0.0) and rt.poll(now=4.9) == []
    assert not h.done() and rt.pending() == 1
    assert rt.next_deadline() == 5.0
    launched = rt.poll(now=5.0)                 # deadline forces the launch
    assert launched == [h] and rt.pending() == 0
    assert h.result() is not None and h.done()  # result() retires the launch


def test_full_batch_launches_immediately_from_submit():
    idx, q = make_plain_index()
    rt = ServingRuntime(idx, RuntimeConfig(max_batch=2, max_wait=100.0))
    h1 = rt.submit(0, q[0][0], now=0.0)
    assert not h1.done()                        # partial batch waits
    assert h1.state == "pending"
    h2 = rt.submit(1, q[1][0], now=0.0)
    # the full batch dispatched straight from submit(); with async
    # dispatch the handles are at least in flight (resolved once the
    # device lands — result() forces that without draining the queue)
    assert rt.launches == 1
    assert h1.state in ("in_flight", "resolved")
    assert h1.result() is not None and h2.result() is not None
    assert h1.done() and h2.done() and rt.launches == 1


def test_explicit_deadline_overrides_max_wait():
    idx, q = make_plain_index()
    rt = ServingRuntime(idx, RuntimeConfig(max_batch=8, max_wait=100.0,
                                           auto_flush=False))
    h = rt.submit(0, q[0][0], now=0.0, deadline=1.0)
    assert rt.poll(now=0.5) == [] and rt.poll(now=1.0) == [h]


def test_result_wait_false_is_none_until_ready_and_drains():
    """The handle state machine: result(wait=False) is a well-defined
    None not-ready signal at every pre-resolved state (it used to raise
    on queued requests), and result() drains exactly as far as needed."""
    idx, q = make_plain_index()
    rt = ServingRuntime(idx, RuntimeConfig(max_batch=8, auto_flush=False))
    h = rt.submit(0, q[0][0], now=0.0)
    assert h.state == "pending"
    assert h.result(wait=False) is None         # queued: not ready, no raise
    assert h.state == "pending"                 # ...and no side effects
    res = h.result()                            # future-style: drains
    assert h.done() and h.state == "resolved"
    assert np.asarray(res.indices).shape == (3,)
    assert h.result(wait=False) is res          # resolved: wait irrelevant


def test_handle_states_through_async_pipeline():
    """pending -> in_flight -> resolved observable under async dispatch;
    done() is non-blocking and barrier() retires everything."""
    idx, q = make_plain_index()
    rt = ServingRuntime(idx, RuntimeConfig(max_batch=2, max_wait=100.0,
                                           auto_flush=False, async_depth=2))
    h1 = rt.submit(0, q[0][0], now=0.0)
    h2 = rt.submit(1, q[1][0], now=0.0)
    assert rt.poll(now=0.0) == [h1, h2]         # full batch: dispatched
    assert rt.launches == 1
    assert {h1.state, h2.state} <= {"in_flight", "resolved"}
    assert rt.in_flight() <= 1                  # poll may have reaped it
    rt.barrier()
    assert rt.in_flight() == 0
    assert h1.state == h2.state == "resolved"
    assert h1.done() and h2.done()


def test_async_depth_zero_is_synchronous():
    """async_depth=0 restores the legacy contract: a launch is resolved
    before the dispatching call returns."""
    idx, q = make_plain_index()
    rt = ServingRuntime(idx, RuntimeConfig(max_batch=2, max_wait=100.0,
                                           async_depth=0))
    h1 = rt.submit(0, q[0][0], now=0.0)
    h2 = rt.submit(1, q[1][0], now=0.0)         # auto_flush dispatches
    assert h1.state == h2.state == "resolved"   # ...and retires inline
    assert rt.in_flight() == 0 and h1.done() and h2.done()


def test_async_backpressure_bounds_inflight_depth():
    idx, q = make_plain_index()
    rt = ServingRuntime(idx, RuntimeConfig(max_batch=1, max_wait=100.0,
                                           auto_flush=False, async_depth=2))
    handles = [rt.submit(t % 3, q[t % 3][t % 4], now=0.0) for t in range(6)]
    rt.poll(now=1000.0)                         # 6 single-lane launches
    assert rt.launches == 6
    assert rt.in_flight() <= 2                  # never beyond async_depth
    rt.barrier()
    assert all(h.state == "resolved" for h in handles)


def test_round_robin_fairness_no_tenant_starvation():
    """A chatty tenant floods the queue; the first launch must still carry
    the quiet tenants' requests instead of 4 lanes of the flooder."""
    idx, q = make_plain_index()
    rt = ServingRuntime(idx, RuntimeConfig(max_batch=4, auto_flush=False))
    chatty = [rt.submit(0, q[0][i], now=0.0) for i in range(6)]
    quiet = [rt.submit(t, q[t][0], now=0.0) for t in (1, 2)]
    rt.flush()
    first = [h for h in chatty + quiet if h.launch_index == 0]
    assert {h.tenant_id for h in first} == {0, 1, 2}
    assert sum(h.tenant_id == 0 for h in first) == 2
    # FIFO within a tenant: the flooder's own requests resolve in order.
    order = sorted(chatty, key=lambda h: h.request_id)
    launches = [h.launch_index for h in order]
    assert launches == sorted(launches)


def test_fifo_mode_preserves_arrival_grouping():
    idx, q = make_plain_index()
    rt = ServingRuntime(idx, RuntimeConfig(max_batch=4, fairness="fifo",
                                           auto_flush=False))
    handles = [rt.submit(0, q[0][i], now=0.0) for i in range(5)]
    handles.append(rt.submit(1, q[1][0], now=0.0))
    rt.flush()
    assert [h.launch_index for h in handles] == [0, 0, 0, 0, 1, 1]


def test_partial_batch_pads_to_pow2_bucket():
    idx, q = make_plain_index()
    rt = ServingRuntime(idx, RuntimeConfig(max_batch=8, auto_flush=False))
    for i in range(3):
        rt.submit(0, q[0][i], now=0.0)
    rt.flush()
    assert rt.last_plan.batch == 4              # 3 real lanes + 1 padding
    assert rt.queries_served == 3


def test_submit_validation():
    idx, q = make_plain_index()
    rt = ServingRuntime(idx)
    with pytest.raises(ValueError, match="tenant id"):
        rt.submit(-1, q[0][0])
    with pytest.raises(ValueError, match="query must be"):
        rt.submit(0, q[0][0][:DIM // 2])
    with pytest.raises(ValueError, match="max_batch"):
        RuntimeConfig(max_batch=0)
    with pytest.raises(ValueError, match="fairness"):
        RuntimeConfig(fairness="lifo")


# ---------------------------------------------------------------------------
# Hot-cluster cache: bit-exact parity, invalidation, accounting
# ---------------------------------------------------------------------------

def run_batch(rt, idx_queries, tenants):
    handles = [rt.submit(t, idx_queries[t][i], now=0.0)
               for t in tenants for i in range(2)]
    rt.flush()
    return handles


def test_cache_hit_path_bit_identical_to_miss_path():
    """Turn 2 re-issues turn 1's queries: every cluster view comes from
    the cache, and every result must be bit-identical to the cold turn
    AND to the uncached ClusterPolicy cascade."""
    idx, q = make_clustered_index()
    rt = ServingRuntime(idx, RuntimeConfig(max_batch=8, cache_bytes=1 << 20,
                                           auto_flush=False))
    cold = run_batch(rt, q, range(4))
    assert rt.cache_stats()["misses"] > 0
    hbm_after_cold = rt.stage1_bytes_streamed
    warm = run_batch(rt, q, range(4))
    assert rt.stage1_bytes_streamed == hbm_after_cold   # fully warm: 0 HBM
    assert rt.last_plan.stage1_bytes == 0
    assert rt.last_plan.stage1_bytes_sram > 0
    # uncached reference (same grouping, direct index.retrieve)
    tids = np.asarray([t for t in range(4) for _ in range(2)], np.int32)
    Q = jnp.asarray(np.stack([q[t][i] for t in range(4) for i in range(2)]))
    ref = idx.retrieve(Q, tids)
    for lane, (c, w) in enumerate(zip(cold, warm)):
        for res in (c.result(), w.result()):
            assert jnp.array_equal(res.indices, ref.indices[lane])
            assert jnp.array_equal(res.scores, ref.scores[lane])
            assert jnp.array_equal(res.candidate_indices,
                                   ref.candidate_indices[lane])


def test_launch_phases_are_spans_on_the_runtime_clock():
    """The cached launch path's host phases are spans of the runtime's
    tracer: `runtime.launch` around the preload, the slab fill and the
    tables, then `runtime.retire`, each stamped with the caller's `now`
    (a simulated clock gives the same events on every replay)."""
    from repro.obs import Tracer

    def drive():
        idx, q = make_clustered_index()
        tracer = Tracer()
        rt = ServingRuntime(idx, RuntimeConfig(max_batch=8,
                                               cache_bytes=1 << 20,
                                               auto_flush=False,
                                               preload=True),
                            tracer=tracer)
        for t in range(4):
            rt.submit(t, q[t][0], now=2.0)
        rt.flush(now=3.0)
        return [(e.name, e.ts, e.dur) for e in tracer.spans()
                if e.ph == "X"]
    spans = drive()
    assert spans == [("runtime.preload", 3.0, 0.0),
                     ("runtime.fill", 3.0, 0.0),
                     ("runtime.tables", 3.0, 0.0),
                     ("runtime.launch", 3.0, 0.0),
                     ("runtime.retire", 3.0, 0.0)]
    assert drive() == spans


def test_cache_straddling_arena_mutation_evicts_stale_views():
    """Warm the cache, MUTATE the arena (insert + delete), query again:
    the stale generation's views must be evicted, and the results must
    equal a fresh uncached retrieval over the mutated arena."""
    rng = np.random.default_rng(7)
    idx, q = make_clustered_index(seed=7)
    rt = ServingRuntime(idx, RuntimeConfig(max_batch=8, cache_bytes=1 << 20,
                                           auto_flush=False))
    run_batch(rt, q, range(4))                      # warm
    assert len(rt.cache) > 0
    gen_before = idx.arena.generation
    new = rng.normal(size=(4, DIM)).astype(np.float32)
    idx.ingest(0, jnp.asarray(new))                 # mutation 1
    idx.delete(1, idx.table.slots(1)[:2])           # mutation 2
    assert idx.arena.generation > gen_before
    handles = run_batch(rt, q, range(4))
    assert rt.cache_stats()["stale_evictions"] > 0
    tids = np.asarray([t for t in range(4) for _ in range(2)], np.int32)
    Q = jnp.asarray(np.stack([q[t][i] for t in range(4) for i in range(2)]))
    ref = idx.retrieve(Q, tids)
    for lane, h in enumerate(handles):
        res = h.result()
        assert jnp.array_equal(res.indices, ref.indices[lane])
        assert jnp.array_equal(res.scores, ref.scores[lane])
    # a query for the newly ingested doc sees the post-mutation arena
    # exactly as the uncached cascade does (no stale view hides it)
    qn, _ = quantize_int8(jnp.asarray(new[:1]), per_vector=True)
    h = rt.submit(0, np.asarray(qn[0]), now=0.0)
    rt.flush()
    fresh = idx.retrieve(qn, np.asarray([0], np.int32))
    assert jnp.array_equal(h.result().indices, fresh.indices[0])
    assert jnp.array_equal(h.result().scores, fresh.scores[0])
    # and the tombstoned rows can never surface
    gone = np.asarray(idx.arena.owner) < 0
    for hh in handles:
        got = np.asarray(hh.result().indices)
        assert not gone[got[got >= 0]].any()


def test_cache_budget_shrinkage_monotone_hbm_bytes():
    """Shrinking the byte budget can only increase HBM traffic on the
    same trace (and never changes results)."""
    byts, results = [], []
    for budget in (1 << 20, 6 * 1024, 0):
        idx, q = make_clustered_index(seed=3)
        rt = ServingRuntime(idx, RuntimeConfig(max_batch=8,
                                               cache_bytes=budget,
                                               auto_flush=False))
        hs = []
        for _ in range(3):
            hs.extend(run_batch(rt, q, range(4)))
        byts.append(rt.stage1_bytes_streamed)
        results.append([np.asarray(h.result().indices) for h in hs])
    assert byts[0] <= byts[1] <= byts[2]
    assert byts[0] < byts[2]
    for got in results[1:]:
        for a, b in zip(results[0], got):
            np.testing.assert_array_equal(a, b)


def test_session_prior_rewarms_cache_after_mutation():
    """After a mutation invalidates the cache, the tenant's recent-cluster
    prior prefetches its session's clusters at the next flush — so the
    probes themselves hit."""
    rng = np.random.default_rng(5)
    idx, q = make_clustered_index(seed=5)
    rt = ServingRuntime(idx, RuntimeConfig(max_batch=8, cache_bytes=1 << 20,
                                           prior_clusters=8,
                                           auto_flush=False))
    run_batch(rt, q, range(4))                      # establishes priors
    idx.ingest(0, jnp.asarray(rng.normal(size=(4, DIM)).astype(np.float32)))
    hits_before = rt.cache_stats()["hits"]
    run_batch(rt, q, range(4))                      # same session turns
    assert rt.prefetch_bytes > 0
    assert rt.cache_stats()["hits"] > hits_before


def _blk_rows(*blocks, br=4):
    """Row ids of whole plane blocks (mirror-equivalent views)."""
    return np.concatenate([np.arange(br) + b * br for b in blocks])


def test_lru_cache_unit_behavior():
    """Slot-map LRU over the slab arena: two slots of 40 bytes each."""
    cache = HotClusterCache(budget_bytes=100)
    cache.configure(block_rows=4, bytes_per_row=10)   # slot = 40 B, 2 slots
    cache.sync_generation(1)
    assert cache.num_slab_blocks == 2
    assert list(cache.put(0, 0, _blk_rows(3))) == [0]  # blk 3 -> slot 0
    assert list(cache.put(0, 1, _blk_rows(5))) == [1]
    assert cache.get(0, 0) is not None                # 0 now most recent
    slots = cache.put(0, 2, _blk_rows(7))             # evicts LRU = (0, 1)
    assert slots is not None and len(slots) == 1
    assert cache.bytes_used <= 100 and len(cache) == 2
    assert cache.peek(0, 0) and not cache.peek(0, 1)
    assert cache.evictions == 1
    cache.sync_generation(2)                          # arena mutated
    assert len(cache) == 0 and cache.stale_evictions == 2
    assert len(cache._free) == 2                      # slots reclaimed
    with pytest.raises(ValueError):
        HotClusterCache(budget_bytes=-1)


def test_packed_admission_uses_fewer_slots_than_straddling_blocks():
    """A contiguous run that straddles a plane-block boundary packs into
    ceil(rows/br) slots — one fewer than mirroring its two blocks — and
    a fragmented run falls back to whole-block mirroring."""
    cache = HotClusterCache(budget_bytes=400)
    cache.configure(block_rows=4, bytes_per_row=10)
    cache.sync_generation(1)
    straddle = np.arange(2, 6)                 # rows 2..5: blocks 0 and 1
    assert len(cache.put(0, 0, straddle)) == 1          # packed: 1 slot
    assert cache._entries[(0, 0)].n_rows == 4
    fragmented = np.asarray([0, 1, 9, 10])     # two separate runs
    assert len(cache.put(0, 1, fragmented)) == 2        # mirrors 2 blocks
    assert cache.entry_blocks(straddle, 4) == 1
    assert cache.entry_blocks(fragmented, 4) == 2


def test_eviction_skips_zero_slot_empty_cluster_memos():
    """Slot pressure must evict entries that actually FREE slots: an
    empty-cluster memo holds none, so evicting it would only destroy the
    memoization (re-skewing the miss ledger) and inflate the counter."""
    cache = HotClusterCache(budget_bytes=100)
    cache.configure(block_rows=4, bytes_per_row=10)   # 2 slots
    cache.sync_generation(1)
    cache.put(0, 5, [])                 # empty-cluster memo, oldest
    cache.put(0, 0, _blk_rows(1))
    cache.put(0, 1, _blk_rows(2))
    cache.put(0, 2, _blk_rows(3))       # needs a slot: evicts (0, 0)
    assert cache.peek(0, 5)             # the zero-slot memo survived
    assert not cache.peek(0, 0) and cache.evictions == 1
    with pytest.raises(ValueError, match="preload"):
        RuntimeConfig(preload=True)     # preload needs a budget


def test_oversized_view_rejected_without_flushing_cache():
    """A view larger than the whole slab must be refused admission —
    NOT evict every resident tenant's entries on its way to nowhere."""
    cache = HotClusterCache(budget_bytes=100)
    cache.configure(block_rows=4, bytes_per_row=10)   # 2 slots
    cache.sync_generation(1)
    cache.put(0, 0, _blk_rows(1))
    cache.put(1, 0, _blk_rows(2))
    assert cache.put(2, 7, _blk_rows(3, 4, 5)) is None  # > slab: rejected
    assert cache.rejected == 1 and cache.evictions == 0
    assert cache.peek(0, 0) and cache.peek(1, 0) and not cache.peek(2, 7)
    assert cache.bytes_used == 80


def test_rejected_reput_keeps_resident_entry():
    """Regression: the oversized check must run BEFORE the resident entry
    is popped — a rejected re-put of an existing key used to destroy the
    valid cached entry and leak its bytes from the working set."""
    cache = HotClusterCache(budget_bytes=100)
    cache.configure(block_rows=4, bytes_per_row=10)   # 2 slots
    cache.sync_generation(1)
    cache.put(0, 0, _blk_rows(1))
    used = cache.bytes_used
    assert cache.put(0, 0, _blk_rows(1, 2, 3)) is None  # oversized re-put
    assert cache.rejected == 1
    assert cache.peek(0, 0)                           # entry survived
    assert cache.bytes_used == used                   # no byte leak
    entry = cache.get(0, 0)
    assert entry is not None and entry.n_rows == 4
    # and a legal re-put still replaces (old slots reclaimed, no leak)
    assert cache.put(0, 0, _blk_rows(2, 3)) is not None
    assert cache.bytes_used == 80 and len(cache) == 1


def test_empty_clusters_memoized_as_zero_byte_hits():
    """Regression: empty-cluster probes used to be uncacheable, so every
    repeat probe counted a fresh miss and skewed the hit rate. They are
    now memoized as zero-slot entries: repeats hit (for free), and the
    fully-warm plan still charges zero stage-1 HBM bytes."""
    # Tenant 3's docs all sit in ONE planted cluster, so its lanes must
    # probe nprobe=2 clusters of which at least one is empty for it.
    rng = np.random.default_rng(9)
    idx = MultiTenantIndex(1024, DIM, RetrievalConfig(k=3),
                           clusters=ClusterParams(num_clusters=8, nprobe=2,
                                                  block_rows=32))
    docs = {}
    for t in range(3):
        d = rng.normal(size=(96, DIM)).astype(np.float32)
        idx.ingest(t, jnp.asarray(d))
        docs[t] = d
    base = rng.normal(size=(1, DIM)).astype(np.float32)
    d3 = (base + 0.01 * rng.normal(size=(24, DIM))).astype(np.float32)
    idx.ingest(3, jnp.asarray(d3))
    docs[3] = d3
    idx.compact()
    labels = np.asarray(idx.arena.cluster_labels)
    owner = np.asarray(idx.arena.owner)
    assert len(set(labels[owner == 3])) < 2           # sparse tenant
    queries = {t: np.asarray(quantize_int8(jnp.asarray(d[:2]),
                                           per_vector=True)[0])
               for t, d in docs.items()}
    rt = ServingRuntime(idx, RuntimeConfig(max_batch=8, cache_bytes=1 << 20,
                                           prior_clusters=0,
                                           auto_flush=False))
    run_batch(rt, queries, range(4))                  # cold turn
    misses_cold = rt.cache_stats()["misses"]
    assert misses_cold > 0
    for _ in range(3):                                # identical re-probes
        run_batch(rt, queries, range(4))
    stats = rt.cache_stats()
    assert stats["misses"] == misses_cold             # no repeat misses
    assert stats["hits"] > 0
    assert rt.last_plan.stage1_bytes == 0             # fully warm
    # hit rate converges instead of being dragged down by empty probes
    assert stats["hits"] / (stats["hits"] + stats["misses"]) >= 0.7


def test_preload_under_slab_pressure_stays_bit_identical():
    """Regression: with the slab sized for only PART of the tenant set,
    a batch's preload admissions can evict another batch tenant's
    entries (the demand check bounds the batch, not the whole slab) —
    the runtime must then fall back to the full-width table instead of
    serving a compact table with silently holed clusters. Rotating
    batches churn admissions/evictions; every result must stay
    bit-identical to the uncached index."""
    idx, q = make_clustered_index(tenants=4)
    # Budget ~ covers roughly half the tenants' packed views at once.
    demand = sum(
        HotClusterCache.entry_blocks(rows, 32) * 32 * (DIM // 2)
        for t in range(4) for rows in idx.cluster_rows(t).values())
    rt = ServingRuntime(idx, RuntimeConfig(max_batch=8,
                                           cache_bytes=demand // 2,
                                           preload=True, auto_flush=False))
    batches = [(0,), (1,), (2, 3), (0, 1), (1, 2, 3), (0, 1, 2, 3), (0, 1)]
    for tenants in batches:
        handles = [(t, i, rt.submit(t, q[t][i], now=0.0))
                   for t in tenants for i in range(2)]
        rt.flush()
        for t, i, h in handles:
            ref = idx.retrieve(jnp.asarray(q[t][i])[None],
                               np.asarray([t], np.int32))
            res = h.result()
            assert jnp.array_equal(res.indices, ref.indices[0])
            assert jnp.array_equal(res.scores, ref.scores[0])
    assert rt.cache_stats()["evictions"] > 0    # pressure actually hit


def test_preload_serves_compact_table_when_budget_fits():
    """With the whole tenant set inside the budget, preloaded launches
    run from the compact slab table (narrower than the plane table) and
    every probe hits — still bit-identical to the uncached index."""
    idx, q = make_clustered_index()
    rt = ServingRuntime(idx, RuntimeConfig(max_batch=8, cache_bytes=1 << 20,
                                           preload=True, auto_flush=False))
    for _ in range(2):
        handles = run_batch(rt, q, range(4))
    stats = rt.cache_stats()
    assert stats["misses"] == 0                 # preload pinned everything
    assert rt.last_plan.stage1_bytes == 0
    assert rt.last_plan.stage1_bytes_sram > 0
    tids = np.asarray([t for t in range(4) for _ in range(2)], np.int32)
    Q = jnp.asarray(np.stack([q[t][i] for t in range(4) for i in range(2)]))
    ref = idx.retrieve(Q, tids)
    for lane, h in enumerate(handles):
        assert jnp.array_equal(h.result().indices, ref.indices[lane])
        assert jnp.array_equal(h.result().scores, ref.scores[lane])
    # the compact table is narrower than (or equal to) the plane table,
    # and the plan's view accounting reflects the narrower launch
    _, table = idx.cluster_layout(tids)
    compact, w = rt.cache.compact_table(tids, table.shape[1])
    assert w <= table.shape[2]


def test_max_wait_zero_means_no_deadline_launches():
    """max_wait=0 is the legacy contract: partial batches launch only
    when full or explicitly flushed, never by the clock."""
    idx, q = make_plain_index()
    rt = ServingRuntime(idx, RuntimeConfig(max_batch=4, max_wait=0.0,
                                           auto_flush=False))
    h = rt.submit(0, q[0][0], now=0.0)
    assert rt.next_deadline() is None
    assert rt.poll(now=1e9) == [] and not h.done()  # clock can't force it
    explicit = rt.submit(1, q[1][0], now=0.0, deadline=5.0)
    assert set(rt.poll(now=5.0)) == {h, explicit}   # explicit still works
    assert rt.pending() == 0


def test_runtime_ledger_matches_plan_accounting():
    idx, q = make_clustered_index()
    rt = ServingRuntime(idx, RuntimeConfig(max_batch=8, cache_bytes=1 << 20,
                                           auto_flush=False))
    run_batch(rt, q, range(4))
    plan = rt.last_plan
    assert plan.kind == "cluster"
    assert rt.stage_bytes["approx"] == plan.stage1_bytes
    assert rt.stage_bytes["prune"] == plan.stages[0].bytes_hbm
    # hits + misses account every probed byte of the launch
    run_batch(rt, q, range(4))
    plan2 = rt.last_plan
    approx = [s for s in plan2.stages if s.name == "approx"][0]
    assert approx.bytes_hbm == plan2.stage1_bytes == 0
    assert approx.bytes_sram == plan2.stage1_bytes_sram > 0
    ledger = rt.energy_ledger()
    assert ledger.total_uj > 0


def test_scheduler_wrapper_still_fifo_and_ledgered():
    """The legacy CrossTenantBatchScheduler facade keeps its contract on
    top of the runtime: int tickets, FIFO groups, byte ledgers."""
    from repro.tenancy import CrossTenantBatchScheduler
    idx, q = make_clustered_index()
    sched = CrossTenantBatchScheduler(idx, max_batch=4)
    rids = [sched.submit(t, q[t][0]) for t in range(4)]
    rids += [sched.submit(0, q[0][1])]
    assert sched.pending() == 5
    out = sched.flush()
    assert sched.pending() == 0 and sched.launches == 2
    assert set(out) == set(rids)
    assert sched.stage1_bytes_streamed > 0
    assert sched.stage_bytes == {
        s.name: s.bytes_hbm for s in idx.last_plan.stages} or \
        sum(sched.stage_bytes.values()) > 0


def test_cached_path_trace_stability():
    """The silent failure mode of shape-dependent view building is a
    recompile per launch. The slab path must compile a BOUNDED number of
    jit traces across launches with varying hit/miss patterns, batch
    sizes, and cache states: one cascade trace per pow2 batch bucket
    (hit/miss patterns only change ARRAY VALUES — the indirection table,
    never shapes) and a pow2-bounded family of fill scatters."""
    from repro.core.engine import retrieve_batched_aux
    from repro.serve.runtime import _apply_fills
    idx, q = make_clustered_index(docs_per_tenant=96)
    rt = ServingRuntime(idx, RuntimeConfig(max_batch=8,
                                           cache_bytes=24 * 1024,
                                           auto_flush=False))
    casc0 = retrieve_batched_aux._cache_size()
    fill0 = _apply_fills._cache_size()
    # Many launches: varying batch compositions (1..8 lanes), repeated
    # and disjoint tenant mixes, a tiny budget that forces eviction/
    # re-admission churn, and arena mutations in between.
    rng = np.random.default_rng(0)

    def varied_launches(turns):
        # lane counts cycle over every pow2 bucket {1, 2, 4, 8} with a
        # fixed tenant rotation (shapes deterministic per bucket) while
        # the QUERIES vary freely — so consecutive launches see fresh
        # hit/miss/eviction patterns at identical trace shapes
        for i in range(turns):
            for j in range((1, 2, 3, 8)[i % 4]):
                t = j % 4
                rt.submit(t, q[t][int(rng.integers(8))], now=0.0)
            rt.flush()

    varied_launches(12)
    idx.ingest(0, jnp.asarray(rng.normal(size=(4, DIM)).astype(np.float32)))
    varied_launches(4)
    casc_traces = retrieve_batched_aux._cache_size() - casc0
    # pow2 batch buckets {1, 2, 4, 8} x at most 2 table-width buckets
    # (full-width vs compact, and the mutation can re-bucket the block
    # table once) -> bounded, nowhere near the 16 launches.
    assert casc_traces <= 12, f"cascade recompiled per launch: {casc_traces}"
    # fill scatters: pow2 (row-count, block-count) bucket pairs,
    # logarithmic^2 in the largest fill, reused across launches
    assert _apply_fills._cache_size() - fill0 <= 24
    # The sharp property: once the shape buckets exist, MORE launches with
    # fresh hit/miss/eviction patterns compile NOTHING new — patterns only
    # change array values (the indirection table), never trace shapes.
    stable0 = retrieve_batched_aux._cache_size()
    varied_launches(8)
    assert retrieve_batched_aux._cache_size() == stable0
    assert rt.cache_stats()["hits"] > 0 and rt.cache_stats()["evictions"] > 0


def test_cache_stats_snapshot_and_reset_windows():
    """Satellite fix: cache counters were lifetime-cumulative only.
    `snapshot()` gives a plain-data view and `reset_stats()` opens a new
    window (steady-state hit rates after a fill phase) WITHOUT touching
    residency — entries, slab bytes, and results are unaffected."""
    idx, q = make_clustered_index()
    rt = ServingRuntime(idx, RuntimeConfig(max_batch=4,
                                           cache_bytes=256 * 1024,
                                           auto_flush=False))
    for turn in range(3):                    # fill phase: misses then hits
        for t in range(4):
            rt.submit(t, q[t][turn], now=0.0)
        rt.flush()
    fill = rt.cache.snapshot()
    assert fill["misses"] > 0 and fill["fill_bytes"] > 0
    assert set(fill) == {"hits", "misses", "evictions", "stale_evictions",
                         "rejected", "fill_bytes", "fill_dispatches"}
    entries_before = len(rt.cache)
    rt.cache.reset_stats()
    assert rt.cache.hits == 0 and rt.cache.misses == 0
    assert len(rt.cache) == entries_before   # residency untouched
    for turn in range(3):                    # steady state: all hits
        for t in range(4):
            rt.submit(t, q[t][turn], now=0.0)
        rt.flush()
    steady = rt.cache.snapshot()
    assert steady["hits"] > 0 and steady["misses"] == 0
    assert steady["fill_bytes"] == 0
    # cache_stats() serves the same windowed numbers
    cs = rt.cache_stats()
    assert cs["hits"] == steady["hits"] and cs["fill_bytes"] == 0
    assert cs["bytes_used"] == rt.cache.bytes_used > 0


def test_observability_zero_compiles_and_bit_parity():
    """The observability overhead contract, unit-scale: serving the SAME
    schedule with a real registry + tracer must (a) return bit-identical
    results, (b) compile ZERO additional jit traces (metrics never reach
    jitted code), and (c) leave a balanced trace whose totals match the
    registry."""
    from repro.core.engine import retrieve_batched_aux
    from repro.obs import MetricsRegistry, Tracer
    from repro.serve.runtime import _apply_fills
    idx, q = make_clustered_index(docs_per_tenant=96)
    cfg = RuntimeConfig(max_batch=8, cache_bytes=256 * 1024,
                        auto_flush=False)

    def drive(rt):
        out = []
        for turn in range(4):
            hs = [rt.submit(t, q[t][turn % 8], now=float(turn))
                  for t in range(4)]
            rt.flush()
            out.extend(h.result() for h in hs)
        return out

    base = drive(ServingRuntime(idx, cfg))   # compiles the shape buckets
    casc0 = retrieve_batched_aux._cache_size()
    fill0 = _apply_fills._cache_size()
    reg, tracer = MetricsRegistry(), Tracer()
    obs = drive(ServingRuntime(idx, cfg, registry=reg, tracer=tracer))
    assert retrieve_batched_aux._cache_size() == casc0
    assert _apply_fills._cache_size() == fill0
    for a, b in zip(base, obs):
        assert jnp.array_equal(a.indices, b.indices)
        assert jnp.array_equal(a.scores, b.scores)
        assert jnp.array_equal(a.candidate_indices, b.candidate_indices)
    assert tracer.open_spans() == []
    assert reg.get("counter", "serve_requests_submitted").value == 16
    assert reg.get("counter", "serve_requests_resolved").value == 16
    assert reg.get("counter", "serve_launches").value == 4
    assert reg.get("histogram", "serve_batch_occupancy").count == 4
    assert reg.get("histogram", "energy_uj_per_query").count == 16
    # per-stage plan fan-out reached the registry
    assert reg.get("counter", "stage_bytes_hbm", stage="approx").value > 0
    # cache counters live on the SAME registry when one is supplied
    assert reg.get("counter", "cache_misses").value > 0


# ---------------------------------------------------------------------------
# Async pipeline parity: the deferred-bookkeeping contract
# ---------------------------------------------------------------------------

def test_async_pipeline_matches_sync_seeded_schedules():
    """Deterministic counterpart of the hypothesis property in
    test_runtime_properties.py (which needs hypothesis installed): under
    seeded random submit/poll/flush schedules with mid-schedule
    result(wait=False) probes, the async pipeline's results are
    bit-identical to the synchronous path and it forms the same
    launches."""
    idx, q = make_plain_index()

    def drive(depth, seed):
        rng = np.random.default_rng(seed)
        rt = ServingRuntime(idx, RuntimeConfig(
            max_batch=int(rng.choice([1, 2, 4])), max_wait=1.0,
            auto_flush=False, async_depth=depth))
        now, handles = 0.0, []
        for _ in range(24):
            op = rng.integers(3)
            if op == 0:
                t = int(rng.integers(3))
                handles.append(rt.submit(t, q[t][int(rng.integers(6))],
                                         now=now, deadline=now + 5.0))
            elif op == 1:
                now += float(rng.uniform(0.0, 2.0))
                rt.poll(now=now)
                if handles:
                    handles[-1].result(wait=False)   # non-blocking probe
            else:
                rt.flush()
        rt.flush()
        assert rt.in_flight() == 0
        return rt.launches, [h.result() for h in handles]

    for seed in range(4):
        launches_s, res_s = drive(0, seed)
        launches_a, res_a = drive(2, seed)
        assert launches_a == launches_s
        for rs, ra in zip(res_s, res_a):
            assert jnp.array_equal(rs.indices, ra.indices)
            assert jnp.array_equal(rs.scores, ra.scores)
            assert jnp.array_equal(rs.candidate_indices, ra.candidate_indices)


def test_async_cached_path_parity_and_ledgers():
    """The slab path's DEFERRED bookkeeping (selection readback, hit/miss
    ledger, admissions, session prior all run at retire time): results
    are bit-identical to the synchronous cached run, and with a barrier
    per turn the byte ledgers match it exactly too. A multi-launch flush
    (true pipelining: launch k+1 dispatches before launch k's bookkeeping
    ran) must still be bit-identical — only the ledgers may shift, since
    admissions land one launch late."""
    idx, q = make_clustered_index(seed=7)

    def run(depth, max_batch):
        rt = ServingRuntime(idx, RuntimeConfig(
            max_batch=max_batch, cache_bytes=1 << 20, prior_clusters=8,
            auto_flush=False, async_depth=depth))
        outs = []
        for turn in range(6):
            hs = [rt.submit(t, q[t][(turn + j) % 8], now=float(turn))
                  for t in range(4) for j in range(2)]
            rt.flush()
            outs.append(np.stack([np.asarray(h.result().indices)
                                  for h in hs]))
        stats = rt.cache_stats()
        return (outs, rt.stage1_bytes_streamed, rt.stage1_bytes_sram,
                stats["hits"], stats["misses"])

    # one launch per flush: barrier after every launch => ledger parity
    outs_s, hbm_s, sram_s, hits_s, miss_s = run(0, max_batch=8)
    outs_a, hbm_a, sram_a, hits_a, miss_a = run(2, max_batch=8)
    for a, s in zip(outs_a, outs_s):
        assert np.array_equal(a, s)
    assert (hbm_a, sram_a, hits_a, miss_a) == (hbm_s, sram_s, hits_s, miss_s)

    # two launches per flush: the second dispatch overlaps the first
    # launch's deferred bookkeeping — results must not move a bit
    outs_s4, *_ = run(0, max_batch=4)
    outs_a4, *_ = run(2, max_batch=4)
    for a, s in zip(outs_a4, outs_s4):
        assert np.array_equal(a, s)


def test_handles_are_single_assignment():
    idx, q = make_plain_index()
    rt = ServingRuntime(idx, RuntimeConfig(max_batch=2))
    h = rt.submit(0, q[0][0], now=0.0)
    rt.flush()
    first = h.result()
    assert h.result() is first                      # stable after resolve
    assert isinstance(h, RequestHandle)
    assert dataclasses.is_dataclass(rt.cfg)


# ---------------------------------------------------------------------------
# Per-cluster precision tiers (adaptive-precision cascade, serving side)
# ---------------------------------------------------------------------------

def _tier_reference(idx, q, tenants):
    tids = np.asarray([t for t in tenants for _ in range(2)], np.int32)
    Q = jnp.asarray(np.stack([q[t][i] for t in tenants for i in range(2)]))
    return idx.retrieve(Q, tids)


def _assert_lanes_match(handles, ref):
    for lane, h in enumerate(handles):
        res = h.result()
        assert jnp.array_equal(res.indices, ref.indices[lane])
        assert jnp.array_equal(res.scores, ref.scores[lane])
        assert jnp.array_equal(res.candidate_indices,
                               ref.candidate_indices[lane])


def test_precision_tiers_admit_sign_promote_on_reprobe():
    """Tier lifecycle under an AMPLE budget: misses admit at the SIGN
    tier (no slab slots), a re-probe promotes to FULL (plane bytes
    charged once, as the miss they replace), and the third pass serves
    full-tier hits with ZERO stage-1 HBM bytes — every pass bit-identical
    to the uncached prescreen cascade."""
    idx, q = make_clustered_index(prescreen_c0=32)
    rt = ServingRuntime(idx, RuntimeConfig(max_batch=8, cache_bytes=1 << 20,
                                           precision_tiers=True,
                                           auto_flush=False))
    ref = _tier_reference(idx, q, range(4))

    _assert_lanes_match(run_batch(rt, q, range(4)), ref)   # pass 1: cold
    s1 = rt.cache.snapshot()
    assert s1["sign_entries"] > 0 and s1["full_entries"] == 0
    assert s1["promotions"] == 0

    _assert_lanes_match(run_batch(rt, q, range(4)), ref)   # pass 2: promote
    s2 = rt.cache.snapshot()
    assert s2["promotions"] > 0 and s2["full_entries"] > 0

    hbm_before = rt.stage1_bytes_streamed
    _assert_lanes_match(run_batch(rt, q, range(4)), ref)   # pass 3: warm
    assert rt.stage1_bytes_streamed == hbm_before    # full-tier hits: 0 HBM
    assert rt.last_plan.stage1_bytes == 0
    assert rt.last_plan.stage1_bytes_sram > 0
    s3 = rt.cache.snapshot()
    assert s3["hits"] > s2["hits"]


def test_precision_tiers_demote_under_pressure_bit_identical():
    """A slab budget far below the working set forces FULL->SIGN
    demotions instead of outright evictions; results must stay
    bit-identical to the uncached cascade and to a full-precision-cache
    runtime serving the same trace, and the sign tier (which holds no
    slab slots) must retain more residents than the slab could."""
    idx, q = make_clustered_index(prescreen_c0=32)
    tight = 4 * 32 * (DIM // 2)      # 4 slab slots; working set is ~8+
    rt = ServingRuntime(idx, RuntimeConfig(max_batch=8, cache_bytes=tight,
                                           precision_tiers=True,
                                           auto_flush=False))
    rt_full = ServingRuntime(idx, RuntimeConfig(max_batch=8,
                                                cache_bytes=tight,
                                                auto_flush=False))
    ref = _tier_reference(idx, q, range(4))
    for _ in range(3):
        _assert_lanes_match(run_batch(rt, q, range(4)), ref)
        _assert_lanes_match(run_batch(rt_full, q, range(4)), ref)
    snap = rt.cache.snapshot()
    assert snap["demotions"] > 0
    assert snap["sign_entries"] + snap["full_entries"] > rt.cache.num_slab_blocks
    # sign residency prescreens without slab slots, so the tiered cache
    # must not stream MORE stage-1 plane bytes than the thrashing
    # full-precision cache on the same trace
    assert rt.stage1_bytes_streamed <= rt_full.stage1_bytes_streamed
