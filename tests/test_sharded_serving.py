"""Sharded multi-tenant serving: placement, routing, tournament merge,
elastic failover — plus the pad-row regression for the device-mesh
tournament in core/index.py.

The in-process tests run every shard on the default single device (the
routing / translation / merge / failover logic is device-count
agnostic); the @slow subprocess tests re-run the parity and failover
gates on a REAL 4-way forced-host device mesh.
"""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.retrieval import RetrievalConfig
from repro.obs import MetricsRegistry
from repro.serve.runtime import RuntimeConfig, ServingRuntime
from repro.serve.sharded import ShardedRuntimeConfig, ShardedServingRuntime
from repro.tenancy import MultiTenantIndex, PlacementTable

SRC = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "src"))

DIM = 32
K = 4
NT = 5          # tenants
ND = 20         # docs per tenant


def _corpus(seed=0):
    rng = np.random.default_rng(seed)
    docs = {t: rng.integers(-40, 41, (ND, DIM), dtype=np.int8)
            for t in range(NT)}
    qs = {t: rng.integers(-40, 41, (DIM,), dtype=np.int8)
          for t in range(NT)}
    return docs, qs


def _cfg(num_shards, spread=1, metric="mips", max_batch=4):
    # candidate_frac=1.0: the stage-1 budget covers every tenant's rows
    # in EVERY placement, the documented precondition for bit-parity
    # across shard counts.
    return ShardedRuntimeConfig(
        num_shards=num_shards, capacity_per_shard=256, dim=DIM,
        spread=spread,
        retrieval=RetrievalConfig(k=K, metric=metric, candidate_frac=1.0),
        runtime=RuntimeConfig(max_batch=max_batch, max_wait=1.0,
                              cache_bytes=0, auto_flush=False))


def _exact(docs, qs, t):
    return docs[t].astype(np.int64) @ qs[t].astype(np.int64)


def _check_scores(docs, qs, t, r):
    """Score-exact oracle (tie-tolerant on indices: the engine breaks
    exact-score ties by stage-1 candidate rank, not ordinal)."""
    exact = _exact(docs, qs, t)
    want = np.sort(exact)[::-1][:K]
    got_i, got_s = np.asarray(r.indices), np.asarray(r.scores)
    assert np.array_equal(got_s, want), (t, got_s, want)
    assert (got_i >= 0).all() and len(set(got_i.tolist())) == K
    assert np.array_equal(exact[got_i], got_s)


# ---------------------------------------------------------------------------
# PlacementTable
# ---------------------------------------------------------------------------

def test_placement_deterministic_and_minimal_movement():
    a = PlacementTable(range(4))
    b = PlacementTable(range(4))
    owners = {t: a.owners(t) for t in range(50)}
    assert owners == {t: b.owners(t) for t in range(50)}   # pure hash
    assert len({o[0] for o in owners.values()}) == 4        # uses all shards
    victim = a.shard_of(0)
    moved = a.remove_shard(victim)
    for t in range(50):
        if t in moved:
            assert victim not in a.owners(t)
        else:
            assert a.owners(t) == owners[t]                 # nobody else moves


def test_placement_spread_owners_distinct_and_doc_round_robin():
    p = PlacementTable(range(4), spread=3)
    for t in range(10):
        own = p.owners(t)
        assert len(own) == 3 and len(set(own)) == 3
        assert [p.doc_shard(t, o) for o in range(6)] == list(own) * 2


def test_placement_cannot_remove_last_shard():
    p = PlacementTable([0, 1])
    p.remove_shard(0)
    with pytest.raises(Exception):
        p.remove_shard(1)


# ---------------------------------------------------------------------------
# core/index.py pad-row regression (satellite bugfix)
# ---------------------------------------------------------------------------

def test_tournament_pad_rows_masked_for_all_negative_corpus():
    """pad_database appends zero docs (score 0). With an all-negative
    MIPS corpus, 0 beats every real doc — pre-fix the tournament returned
    the pad ids (>= n_global); the fix masks them out of both stages."""
    from jax.sharding import AxisType
    from repro.core import quantization
    from repro.core.bitplanar import BitPlanarDB
    from repro.core.index import ShardedIndex, pad_database, shard_database

    rng = np.random.default_rng(7)
    q = rng.normal(size=(64,)).astype(np.float32)
    # docs anti-correlated with q => every exact MIPS score is negative
    emb = (-q[None, :] + 0.05 * rng.normal(size=(6, 64))).astype(np.float32)
    db = quantization.build_database(jnp.asarray(emb))
    bp = BitPlanarDB.from_quantized(db)
    n_global = bp.num_docs
    mesh = jax.make_mesh((1,), ("data",), axis_types=(AxisType.Auto,))
    idx = ShardedIndex(db=shard_database(pad_database(bp, 4), mesh),
                       mesh=mesh, n_global=n_global)   # 2 pad rows
    qc = np.asarray(quantization.quantize_int8_fixed(jnp.asarray(q),
                                                     bp.scale), np.int8)
    r = idx.retrieve_fn(RetrievalConfig(k=3, metric="mips"))(qc)
    got = np.asarray(r.indices)
    assert (got < n_global).all(), f"pad rows returned: {got}"
    assert (np.asarray(r.scores) < 0).all()
    # candidates may mention pads structurally, but never the results


# ---------------------------------------------------------------------------
# Sharded runtime: routing + merge parity
# ---------------------------------------------------------------------------

def test_one_shard_sharded_matches_plain_runtime_bitwise():
    """A 1-shard ShardedServingRuntime is the plain ServingRuntime plus a
    slot->ordinal translation — indices (translated), scores, and byte
    ledgers must all be bit-identical."""
    docs, qs = _corpus()
    cfg = _cfg(1)
    srt = ShardedServingRuntime(cfg)
    idx = MultiTenantIndex(cfg.capacity_per_shard, DIM, cfg.retrieval)
    prt = ServingRuntime(idx, cfg.runtime)
    base = {}
    for t in range(NT):
        srt.ingest_codes(t, docs[t])
        slots = idx.ingest_codes(t, docs[t])
        base[t] = int(slots[0])
    hs = {t: srt.submit(t, qs[t], now=0.0) for t in range(NT)}
    hp = {t: prt.submit(t, qs[t], now=0.0) for t in range(NT)}
    srt.flush(now=0.1)
    prt.flush(now=0.1)
    for t in range(NT):
        rs, rp = hs[t].result(), hp[t].result()
        plain_ords = np.where(np.asarray(rp.indices) >= 0,
                              np.asarray(rp.indices) - base[t], -1)
        assert np.array_equal(np.asarray(rs.indices), plain_ords), t
        assert np.array_equal(np.asarray(rs.scores), np.asarray(rp.scores))
    led = srt.ledger()
    assert led["stage1_bytes_hbm"] == prt.stage1_bytes_streamed
    assert led["launches"] == prt.launches
    assert led["shard_lanes_served"] == {0: prt.queries_served}


def test_multi_shard_matches_single_shard_bitwise():
    """Placement invariance: the same trace on 1 shard and on 3 shards
    returns bit-identical (ordinals, scores) per request."""
    docs, qs = _corpus()
    results = {}
    for s in (1, 3):
        rt = ShardedServingRuntime(_cfg(s))
        for t in range(NT):
            rt.ingest_codes(t, docs[t])
        hs = {t: rt.submit(t, qs[t], now=0.0) for t in range(NT)}
        rt.flush(now=0.1)
        results[s] = {t: hs[t].result() for t in range(NT)}
        led = rt.ledger()
        assert led["dropped"] == 0 and led["duplicated"] == 0
    for t in range(NT):
        a, b = results[1][t], results[3][t]
        assert np.array_equal(np.asarray(a.indices),
                              np.asarray(b.indices)), t
        assert np.array_equal(np.asarray(a.scores), np.asarray(b.scores)), t
        _check_scores(docs, qs, t, b)


def test_spread_two_merge_matches_brute_force():
    docs, qs = _corpus(3)
    rt = ShardedServingRuntime(_cfg(3, spread=2))
    for t in range(NT):
        rt.ingest_codes(t, docs[t])
        assert len(rt.placement.owners(t)) == 2
    hs = {t: rt.submit(t, qs[t], now=0.0) for t in range(NT)}
    rt.flush(now=0.1)
    for t in range(NT):
        _check_scores(docs, qs, t, hs[t].result())
        assert len(hs[t]._req.subs) == 2        # really fanned out


def test_spread_requires_mips():
    with pytest.raises(ValueError, match="spread"):
        _cfg(3, spread=2, metric="cosine")


def test_cosine_single_owner_end_to_end():
    docs, qs = _corpus(5)
    rt = ShardedServingRuntime(_cfg(3, metric="cosine"))
    for t in range(NT):
        rt.ingest_codes(t, docs[t])
    h = rt.submit(2, qs[2], now=0.0)
    rt.flush(now=0.1)
    r = h.result()
    assert (np.asarray(r.indices) >= 0).all()
    # cosine rank oracle (scale-free): compare against float cosine
    exact = _exact(docs, qs, 2).astype(np.float64)
    cos = exact / np.sqrt((docs[2].astype(np.float64) ** 2).sum(1))
    assert set(np.asarray(r.indices).tolist()) == \
        set(np.argsort(-cos, kind="stable")[:K].tolist())


# ---------------------------------------------------------------------------
# Elastic failover
# ---------------------------------------------------------------------------

def test_failover_exactly_once_and_correct():
    docs, qs = _corpus(11)
    rt = ShardedServingRuntime(_cfg(3))
    for t in range(NT):
        rt.ingest_codes(t, docs[t])
    pre = {t: rt.submit(t, qs[t], now=0.0) for t in range(NT)}
    rt.flush(now=0.1)                      # resolve BEFORE the failure
    mid = {t: rt.submit(t, qs[t], now=0.2) for t in range(NT)}
    victim = rt.placement.shard_of(0)
    rep = rt.fail_shard(victim, now=0.3)
    assert victim not in rt.live_shards
    assert rep["requests_resubmitted"] >= 1
    assert rep["docs_restored"] == ND * len(rep["moved_tenants"])
    post = {t: rt.submit(t, qs[t], now=0.4) for t in range(NT)}
    rt.flush(now=0.5)
    for t in range(NT):
        for h in (pre[t], mid[t], post[t]):
            _check_scores(docs, qs, t, h.result())
    led = rt.ledger()
    assert led["submitted"] == 3 * NT
    assert led["resolved"] == 3 * NT
    assert led["dropped"] == 0 and led["duplicated"] == 0
    assert led["resolved_by_tenant"] == {t: 3 for t in range(NT)}
    assert led["failovers"] == 1
    assert str(victim) not in rt.monitor.workers()
    assert rt.mesh.devices.size <= len(rt.live_shards)


def test_failover_resolved_results_are_not_recomputed():
    docs, qs = _corpus(13)
    rt = ShardedServingRuntime(_cfg(2))
    for t in range(NT):
        rt.ingest_codes(t, docs[t])
    h = rt.submit(0, qs[0], now=0.0)
    rt.flush(now=0.1)
    r1 = h.result()
    rt.fail_shard(rt.placement.shard_of(0), now=0.2)
    assert h.result() is r1                 # cached, never re-run
    assert rt.ledger()["resolved"] == 1


def test_failover_skips_deleted_docs():
    docs, qs = _corpus(17)
    rt = ShardedServingRuntime(_cfg(2))
    for t in range(NT):
        rt.ingest_codes(t, docs[t])
    rt.delete(0, [0, 3])
    rt.fail_shard(rt.placement.shard_of(0), now=0.0)
    assert rt.num_docs(0) == ND - 2
    h = rt.submit(0, qs[0], now=0.1)
    rt.flush(now=0.2)
    got = np.asarray(h.result().indices)
    assert 0 not in got and 3 not in got
    exact = _exact(docs, qs, 0)
    exact[[0, 3]] = np.iinfo(np.int64).min
    assert np.array_equal(np.asarray(h.result().scores),
                          np.sort(exact)[::-1][:K])


def test_cannot_fail_last_shard_or_use_dead_shard():
    docs, qs = _corpus()
    rt = ShardedServingRuntime(_cfg(2))
    rt.ingest_codes(0, docs[0])
    rt.fail_shard(rt.placement.shard_of(0))
    with pytest.raises(RuntimeError):
        rt.fail_shard(rt.live_shards[0])


def test_per_shard_labeled_metrics():
    docs, qs = _corpus()
    reg = MetricsRegistry()
    rt = ShardedServingRuntime(_cfg(2), registry=reg)
    for t in range(NT):
        rt.ingest_codes(t, docs[t])
    for t in range(NT):
        rt.submit(t, qs[t], now=0.0)
    rt.flush(now=0.1)
    per_shard = [reg.get("counter", "serve_requests_submitted",
                         shard=str(s)) for s in (0, 1)]
    assert all(c is not None for c in per_shard)
    assert sum(c.value for c in per_shard) == NT


# ---------------------------------------------------------------------------
# Schedule fuzz: failover composed with arbitrary interleavings
# ---------------------------------------------------------------------------

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYP = True
except ImportError:                                      # pragma: no cover
    HAVE_HYP = False

if HAVE_HYP:
    _ops = st.lists(
        st.one_of(
            st.tuples(st.just("submit"), st.integers(0, NT - 1)),
            st.tuples(st.just("poll"), st.just(0)),
            st.tuples(st.just("flush"), st.just(0)),
            st.tuples(st.just("fail"), st.integers(0, 2)),
        ),
        min_size=1, max_size=25)

    @settings(max_examples=15, deadline=None)
    @given(schedule=_ops, num_shards=st.sampled_from([2, 3]))
    def test_failover_fuzz_never_drops_or_duplicates(schedule, num_shards):
        docs, qs = _corpus(23)
        rt = ShardedServingRuntime(_cfg(num_shards))
        for t in range(NT):
            rt.ingest_codes(t, docs[t])
        now, handles, fails = 0.0, [], 0
        for op, a in schedule:
            now += 0.01
            if op == "submit":
                handles.append((a, rt.submit(a, qs[a], now=now)))
            elif op == "poll":
                rt.poll(now=now)
            elif op == "flush":
                rt.flush(now=now)
            elif op == "fail" and len(rt.live_shards) > 1:
                rt.fail_shard(rt.live_shards[a % len(rt.live_shards)],
                              now=now)
                fails += 1
        rt.flush(now=now + 1)
        for t, h in handles:
            assert h.done()
            _check_scores(docs, qs, t, h.result())
        led = rt.ledger()
        assert led["submitted"] == len(handles)
        assert led["resolved"] == len(handles)
        assert led["outstanding"] == 0
        assert led["dropped"] == 0 and led["duplicated"] == 0
        assert led["failovers"] == fails


# ---------------------------------------------------------------------------
# Forced-host multi-device parity (subprocess, real 4-way mesh)
# ---------------------------------------------------------------------------

def run_sub(code: str, timeout=600):
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=8",
               PYTHONPATH=SRC)
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=timeout)
    assert out.returncode == 0, out.stdout + "\n" + out.stderr
    return out.stdout


@pytest.mark.slow
def test_sharded_serving_multidevice_parity_and_failover():
    """On a REAL 4-device mesh: 4-shard results bit-match the 1-shard
    baseline, and a mid-trace device loss completes the trace with zero
    dropped / duplicated requests."""
    run_sub("""
import numpy as np, jax
from repro.core.retrieval import RetrievalConfig
from repro.serve.runtime import RuntimeConfig
from repro.serve.sharded import ShardedRuntimeConfig, ShardedServingRuntime
assert len(jax.devices()) == 8, jax.devices()
rng = np.random.default_rng(0)
NT, ND, DIM, K = 6, 24, 32, 4
docs = {t: rng.integers(-40, 41, (ND, DIM), dtype=np.int8) for t in range(NT)}
qs = [(t, rng.integers(-40, 41, (DIM,), dtype=np.int8))
      for t in list(range(NT)) * 3]

def build(s):
    cfg = ShardedRuntimeConfig(
        num_shards=s, capacity_per_shard=256, dim=DIM,
        retrieval=RetrievalConfig(k=K, metric='mips', candidate_frac=1.0),
        runtime=RuntimeConfig(max_batch=4, max_wait=1.0, cache_bytes=0,
                              auto_flush=False))
    rt = ShardedServingRuntime(cfg, devices=jax.devices()[:s])
    for t in range(NT):
        rt.ingest_codes(t, docs[t])
    return rt

def trace(rt, fail_at=None):
    out, now = [], 0.0
    for i, (t, q) in enumerate(qs):
        if fail_at is not None and i == fail_at:
            rep = rt.fail_shard(rt.live_shards[0], now=now)
            assert rep['requests_resubmitted'] >= 0
        now += 0.01
        out.append((t, rt.submit(t, q, now=now)))
        if i % 5 == 4:
            rt.poll(now=now)
    rt.flush(now=now + 1)
    return [(t, np.asarray(h.result().indices), np.asarray(h.result().scores))
            for t, h in out]

base = trace(build(1))
four = trace(build(4))
assert len({s.device for s in build(4)._shards.values()}) == 4
for (t1, i1, s1), (t4, i4, s4) in zip(base, four):
    assert t1 == t4 and np.array_equal(i1, i4) and np.array_equal(s1, s4), t1
rt = build(4)
lost = trace(rt, fail_at=len(qs) // 2)
led = rt.ledger()
assert led['dropped'] == 0 and led['duplicated'] == 0, led
assert led['resolved'] == len(qs) and led['failovers'] == 1, led
for (t1, i1, s1), (tL, iL, sL) in zip(base, lost):
    assert t1 == tL and np.array_equal(s1, sL), (t1, s1, sL)
print('OK multidevice parity + failover')
""")
