"""Property tests: the serving runtime under random request schedules.

For EVERY interleaving of submit/poll/flush with arbitrary tenants,
deadlines, and clock advances, the runtime must:

  * never drop a request (every handle resolves by the final flush),
  * never duplicate one (each handle resolves exactly once, and each
    launch carries each request in exactly one lane),
  * never leak across tenants (every returned slot is owned by the
    submitting tenant), and
  * return results BIT-IDENTICAL to dispatching the same query alone
    through the index (batching/padding reorder work, never answers).

The index is built with fragmented tenants so the batched path runs the
full-arena masked scan, whose per-lane results are independent of batch
composition by construction — making the sequential reference exact.
"""
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip(
    "hypothesis", reason="property tests need hypothesis; see requirements.txt")
from hypothesis import given, settings, strategies as st  # noqa: E402

from repro.core import RetrievalConfig, quantize_int8  # noqa: E402
from repro.serve.runtime import (  # noqa: E402
    RuntimeConfig, ServingRuntime, _InFlight)
from repro.tenancy import MultiTenantIndex  # noqa: E402

DIM = 32
NUM_TENANTS = 3
NUM_QUERIES = 6


def build_index():
    """Fragmented multi-tenant index + per-tenant query pool (module-level
    singleton: hypothesis replays many schedules against one corpus)."""
    rng = np.random.default_rng(42)
    idx = MultiTenantIndex(128, DIM, RetrievalConfig(k=3))
    docs = {t: [] for t in range(NUM_TENANTS)}
    for _ in range(3):                       # interleave => fragmentation
        for t in range(NUM_TENANTS):
            d = rng.normal(size=(4, DIM)).astype(np.float32)
            idx.ingest(t, jnp.asarray(d))
            docs[t].append(d)
    assert all(len(idx.table.segments(t)) > 1 for t in range(NUM_TENANTS))
    pool = {}
    for t in range(NUM_TENANTS):
        d = np.concatenate(docs[t])[:NUM_QUERIES]
        noisy = d + 0.05 * rng.normal(size=d.shape)
        q, _ = quantize_int8(jnp.asarray(noisy.astype(np.float32)),
                             per_vector=True)
        pool[t] = np.asarray(q)
    owner = np.asarray(idx.arena.owner)
    return idx, pool, owner


_IDX, _POOL, _OWNER = build_index()

# The sequential references: one lane, one launch, no batching.
_SEQ = {
    (t, i): _IDX.retrieve(jnp.asarray(_POOL[t][i])[None],
                          np.asarray([t], np.int32))
    for t in range(NUM_TENANTS) for i in range(NUM_QUERIES)
}

schedules = st.lists(
    st.one_of(
        st.tuples(st.just("submit"),
                  st.integers(0, NUM_TENANTS - 1),      # tenant
                  st.integers(0, NUM_QUERIES - 1),      # query id
                  st.floats(0.0, 10.0)),                # deadline slack
        st.tuples(st.just("poll"),
                  st.floats(0.0, 5.0),                  # clock advance
                  st.just(0), st.just(0.0)),
        st.tuples(st.just("flush"), st.just(0), st.just(0), st.just(0.0)),
    ),
    min_size=1, max_size=30)


@settings(max_examples=20, deadline=None)
@given(schedule=schedules,
       max_batch=st.sampled_from([1, 2, 4, 8]),
       fairness=st.sampled_from(["deadline_rr", "fifo"]))
def test_runtime_never_drops_duplicates_or_leaks(schedule, max_batch,
                                                 fairness):
    rt = ServingRuntime(_IDX, RuntimeConfig(
        max_batch=max_batch, max_wait=1.0, fairness=fairness,
        auto_flush=False))
    now = 0.0
    submitted = []                           # (handle, tenant, query id)
    resolved_ids = []
    for op, a, b, c in schedule:
        if op == "submit":
            h = rt.submit(a, _POOL[a][b], now=now, deadline=now + c)
            submitted.append((h, a, b))
        elif op == "poll":
            now += a
            resolved_ids.extend(h.request_id for h in rt.poll(now=now))
        else:
            resolved_ids.extend(h.request_id for h in rt.flush())
    resolved_ids.extend(h.request_id for h in rt.flush())

    # -- never dropped, never duplicated ---------------------------------
    assert rt.pending() == 0
    assert sorted(resolved_ids) == sorted(h.request_id
                                          for h, _, _ in submitted)
    assert len(set(resolved_ids)) == len(resolved_ids)
    assert rt.queries_served == len(submitted)
    # request ids are unique across the runtime's lifetime
    assert len({h.request_id for h, _, _ in submitted}) == len(submitted)

    for h, t, qi in submitted:
        assert h.done()
        res = h.result()
        got = np.asarray(res.indices)
        valid = got[got >= 0]
        # -- no cross-tenant leak ----------------------------------------
        assert (_OWNER[valid] == t).all(), (t, valid.tolist())
        # -- bit-identical to the sequential one-lane dispatch -----------
        ref = _SEQ[(t, qi)]
        assert jnp.array_equal(res.indices, ref.indices[0])
        assert jnp.array_equal(res.scores, ref.scores[0])
        assert jnp.array_equal(res.candidate_indices,
                               ref.candidate_indices[0])


@settings(max_examples=15, deadline=None)
@given(schedule=schedules,
       max_batch=st.sampled_from([1, 2, 4]),
       fairness=st.sampled_from(["deadline_rr", "fifo"]))
def test_trace_completeness_under_random_schedules(schedule, max_batch,
                                                   fairness):
    """Every submitted request yields EXACTLY one balanced submit->resolve
    ("request" B/E) span chain under arbitrary submit/poll/flush
    interleavings — no orphan spans, no duplicates — and the span ids
    are exactly the submitted request ids. Runs under the simulated
    clock, so the whole trace (timestamps included) must be
    deterministic: replaying the schedule yields a bit-identical event
    list. poll() retires a launch once its device buffers have landed,
    which depends on how fast the device ran; the readiness probe is
    made to wait for the buffers, so every reap retires what was
    dispatched and the order of resolves is a function of the schedule
    alone."""
    from repro.obs import MetricsRegistry, Tracer

    def landed(infl):
        jax.block_until_ready(infl.res)
        return True

    def drive():
        reg, tracer = MetricsRegistry(), Tracer()
        rt = ServingRuntime(_IDX, RuntimeConfig(
            max_batch=max_batch, max_wait=1.0, fairness=fairness,
            auto_flush=False), registry=reg, tracer=tracer)
        now = 0.0
        submitted = []
        with mock.patch.object(_InFlight, "is_ready", landed):
            for op, a, b, c in schedule:
                if op == "submit":
                    submitted.append(rt.submit(a, _POOL[a][b], now=now,
                                               deadline=now + c))
                elif op == "poll":
                    now += a
                    rt.poll(now=now)
                else:
                    rt.flush()
            rt.flush()
        return reg, tracer, submitted

    reg, tracer, submitted = drive()
    assert tracer.open_spans() == []                  # nothing dangling
    begins = [e for e in tracer.spans("request") if e.ph == "B"]
    ends = [e for e in tracer.spans("request") if e.ph == "E"]
    assert len(begins) == len(ends) == len(submitted)
    want_ids = sorted(h.request_id for h in submitted)
    assert sorted(e.attrs["request"] for e in begins) == want_ids
    assert sorted(e.attrs["request"] for e in ends) == want_ids
    # ids unique in both phases => exactly one chain per request
    assert len({e.attrs["request"] for e in begins}) == len(begins)
    assert len({e.attrs["request"] for e in ends}) == len(ends)
    # resolve never precedes submit, and every resolve names its launch
    t_begin = {e.attrs["request"]: e.ts for e in begins}
    for e in ends:
        assert e.ts >= t_begin[e.attrs["request"]]
        assert e.attrs["launch"] >= 0
    # registry totals agree with the trace
    assert reg.get("counter", "serve_requests_submitted").value == \
        len(submitted)
    assert reg.get("counter", "serve_requests_resolved").value == \
        len(submitted)
    qh = reg.get("histogram", "serve_queue_wait_seconds")
    assert qh.count == len(submitted)
    # simulated clock => the trace is bit-identical on replay
    _, tracer2, _ = drive()
    key = [(e.name, e.ph, e.ts, e.tid, tuple(sorted(e.attrs.items())))
           for e in tracer.spans()]
    key2 = [(e.name, e.ph, e.ts, e.tid, tuple(sorted(e.attrs.items())))
            for e in tracer2.spans()]
    assert key == key2


@settings(max_examples=10, deadline=None)
@given(n=st.integers(1, 12), max_batch=st.sampled_from([2, 4]))
def test_deadlines_eventually_force_every_launch(n, max_batch):
    """poll() at a late-enough clock must resolve everything submitted —
    no request can be stranded behind a partial batch forever."""
    rt = ServingRuntime(_IDX, RuntimeConfig(
        max_batch=max_batch, max_wait=1.0, auto_flush=False))
    handles = [rt.submit(i % NUM_TENANTS, _POOL[i % NUM_TENANTS][0],
                         now=float(i) * 0.01) for i in range(n)]
    rt.poll(now=100.0)
    assert rt.pending() == 0                 # everything dispatched...
    assert all(h.result() is not None for h in handles)   # ...and resolvable
    assert all(h.done() for h in handles)


@settings(max_examples=15, deadline=None)
@given(schedule=schedules,
       max_batch=st.sampled_from([1, 2, 4, 8]),
       fairness=st.sampled_from(["deadline_rr", "fifo"]))
def test_async_pipeline_bit_identical_to_sync(schedule, max_batch, fairness):
    """The tail-latency pipeline contract: async dispatch (launches in
    flight as unresolved device futures, lazily retired) returns results
    BIT-IDENTICAL to the legacy synchronous path under every random
    submit/poll/flush interleaving — pipelining reorders WHEN host work
    happens, never what any request retrieves — and forms the exact same
    launches (same count, same admission order)."""
    def mk(depth):
        return ServingRuntime(_IDX, RuntimeConfig(
            max_batch=max_batch, max_wait=1.0, fairness=fairness,
            auto_flush=False, async_depth=depth))

    rt_sync, rt_async = mk(0), mk(2)
    now = 0.0
    pairs = []
    for op, a, b, c in schedule:
        if op == "submit":
            hs = rt_sync.submit(a, _POOL[a][b], now=now, deadline=now + c)
            ha = rt_async.submit(a, _POOL[a][b], now=now, deadline=now + c)
            pairs.append((hs, ha))
        elif op == "poll":
            now += a
            rt_sync.poll(now=now)
            rt_async.poll(now=now)
            if pairs:
                # mid-schedule non-blocking probe: must be None or the
                # final answer, and must never disturb the pipeline
                pairs[-1][1].result(wait=False)
        else:
            rt_sync.flush()
            rt_async.flush()
    rt_sync.flush()
    rt_async.flush()
    assert rt_async.in_flight() == 0         # flush is a barrier
    assert rt_async.launches == rt_sync.launches
    for hs, ha in pairs:
        assert hs.state == ha.state == "resolved"
        assert ha.launch_index == hs.launch_index
        rs, ra = hs.result(), ha.result()
        assert np.array_equal(rs.indices, ra.indices)
        assert np.array_equal(rs.scores, ra.scores)
        assert np.array_equal(rs.candidate_indices, ra.candidate_indices)


# The cached (slab) path's async-vs-sync parity lives in
# tests/test_serve_runtime.py::test_async_cached_path_parity_and_ledgers —
# alongside a seeded deterministic schedule-parity test — so the pipeline
# contract stays pinned even where hypothesis is unavailable.
