"""RetrievalEngine parity suite: every policy, both backends, bit-for-bit.

The engine's contract is that `backend="pallas"` (interpret mode on CPU,
compiled Mosaic on TPU) and `backend="jnp"` run the SAME exact integer
arithmetic, so every policy — plain, masked, windowed, cluster-pruned —
must return identical indices, scores, and candidate sets, for cosine and
MIPS, including fragmented tenants and tenants with fewer live docs than
k. Also pins the single-query wrappers to lanes of the batched core, the
analytic per-stage SchedulePlan byte model, and the cluster cascade's
nprobe=K degeneration to the full scan.
"""
import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (BitPlanarDB, ClusterParams, MaskedPolicy,
                        PlainPolicy, RetrievalConfig, RetrievalEngine,
                        WindowedPolicy, block_table, build_database,
                        cluster_grouped_order, kmeans_int8)
from repro.core import clustering
from repro.core import engine as engine_mod
from repro.core.retrieval import (NO_TENANT, batched_retrieve,
                                  batched_retrieve_masked,
                                  cluster_pruned_retrieve,
                                  two_stage_retrieve,
                                  two_stage_retrieve_masked,
                                  windowed_retrieve_masked)
from repro.core.quantization import quantize_int8
from repro.tenancy import MultiTenantIndex

DIM = 64
N = 192


def make_arena(fragmented: bool, seed=0, k=3, metric="cosine",
               docs=(40, 40, 2)):
    """3 tenants in one arena; tenant 2 holds fewer docs than k.

    fragmented=True interleaves the ingests so tenants span multiple
    segments (only the full-scan masked policy is then correct)."""
    rng = np.random.default_rng(seed)
    idx = MultiTenantIndex(N, DIM, RetrievalConfig(k=k, metric=metric))
    per_tenant = {t: rng.normal(size=(nd, DIM)).astype(np.float32)
                  for t, nd in enumerate(docs)}
    if fragmented:
        chunks = {t: np.array_split(d, 4) for t, d in per_tenant.items()}
        for i in range(4):
            for t in per_tenant:
                if len(chunks[t][i]):
                    idx.ingest(t, jnp.asarray(chunks[t][i]))
    else:
        for t, d in per_tenant.items():
            idx.ingest(t, jnp.asarray(d))
    queries = rng.normal(size=(4, DIM)).astype(np.float32)
    q_codes, _ = quantize_int8(jnp.asarray(queries), per_vector=True)
    return idx, q_codes


def assert_results_equal(a, b):
    np.testing.assert_array_equal(np.asarray(a.indices),
                                  np.asarray(b.indices))
    np.testing.assert_array_equal(np.asarray(a.scores), np.asarray(b.scores))
    np.testing.assert_array_equal(np.asarray(a.candidate_indices),
                                  np.asarray(b.candidate_indices))


def run_both_backends(fn, cfg):
    rj = fn(cfg)
    rp = fn(dataclasses.replace(cfg, backend="pallas"))
    return rj, rp


@pytest.mark.parametrize("metric", ["cosine", "mips"])
def test_plain_policy_backend_parity(metric):
    rng = np.random.default_rng(7)
    db = BitPlanarDB.from_quantized(build_database(
        jnp.asarray(rng.normal(size=(300, DIM)).astype(np.float32))))
    q_codes, _ = quantize_int8(jnp.asarray(
        rng.normal(size=(8, DIM)).astype(np.float32)), per_vector=True)
    cfg = RetrievalConfig(k=5, metric=metric)
    rj, rp = run_both_backends(lambda c: batched_retrieve(q_codes, db, c),
                               cfg)
    assert_results_equal(rj, rp)
    # single-query wrapper == lane 0 of the batch, both backends
    sj, sp = run_both_backends(
        lambda c: two_stage_retrieve(q_codes[0], db, c), cfg)
    assert_results_equal(sj, sp)
    np.testing.assert_array_equal(np.asarray(sj.indices),
                                  np.asarray(rj.indices)[0])


@pytest.mark.parametrize("metric", ["cosine", "mips"])
@pytest.mark.parametrize("fragmented", [False, True])
def test_masked_policy_backend_parity(metric, fragmented):
    """Full-arena masked scan: fragmented tenants and a tenant with fewer
    live docs than k (lane 2), plus a NO_TENANT padding lane."""
    idx, q_codes = make_arena(fragmented, seed=11, metric=metric)
    db = idx.arena.db()
    tids = jnp.asarray([0, 1, 2, NO_TENANT], jnp.int32)
    rj, rp = run_both_backends(
        lambda c: batched_retrieve_masked(q_codes, db, idx.arena.owner,
                                          tids, c), idx.cfg)
    assert_results_equal(rj, rp)
    # the small tenant pads with -1; the padding lane returns nothing
    assert np.asarray(rj.indices)[2].tolist().count(-1) == idx.cfg.k - 2
    assert np.all(np.asarray(rj.indices)[3] == -1)
    sj, sp = run_both_backends(
        lambda c: two_stage_retrieve_masked(q_codes[0], db, idx.arena.owner,
                                            jnp.int32(0), c), idx.cfg)
    assert_results_equal(sj, sp)


@pytest.mark.parametrize("metric", ["cosine", "mips"])
@pytest.mark.parametrize("window", [8, 64])
def test_windowed_policy_backend_parity(metric, window):
    """Contiguous tenants served through per-lane windows, both backends;
    window 8 also exercises window < segment-length clamping of starts."""
    idx, q_codes = make_arena(False, seed=13, metric=metric)
    db = idx.arena.db()
    tids = np.asarray([0, 1, 2, 0], np.int32)
    starts = jnp.asarray([idx.table.segments(int(t))[0][0] for t in tids],
                         jnp.int32)
    rj, rp = run_both_backends(
        lambda c: windowed_retrieve_masked(q_codes, db, idx.arena.owner,
                                           jnp.asarray(tids), starts, c,
                                           window), idx.cfg)
    assert_results_equal(rj, rp)


@pytest.mark.parametrize("metric", ["cosine", "mips"])
def test_index_retrieve_backend_parity_end_to_end(metric):
    """MultiTenantIndex picks the policy host-side; both backends must
    agree through the whole facade (windowed AND fragmented fallback)."""
    for fragmented in (False, True):
        idx, q_codes = make_arena(fragmented, seed=29, metric=metric)
        tids = np.asarray([0, 1, 2, 1], np.int32)
        res_j = idx.retrieve(q_codes, tids)
        expected_kind = "masked" if fragmented else "windowed"
        assert idx.last_plan.kind == expected_kind
        idx.cfg = dataclasses.replace(idx.cfg, backend="pallas")
        res_p = idx.retrieve(q_codes, tids)
        assert_results_equal(res_j, res_p)


def test_windowed_and_masked_policies_agree():
    """The windowed fast path returns exactly what the full scan returns
    when tenants are contiguous (same budget, same masking)."""
    idx, q_codes = make_arena(False, seed=3)
    db = idx.arena.db()
    tids = jnp.asarray([0, 1, 2, 0], jnp.int32)
    full = batched_retrieve_masked(q_codes, db, idx.arena.owner, tids,
                                   idx.cfg)
    window = 64
    starts = jnp.asarray([idx.table.segments(int(t))[0][0] for t in tids],
                         jnp.int32)
    win = windowed_retrieve_masked(q_codes, db, idx.arena.owner, tids,
                                   starts, idx.cfg, window)
    np.testing.assert_array_equal(np.asarray(full.indices),
                                  np.asarray(win.indices))
    np.testing.assert_array_equal(np.asarray(full.scores),
                                  np.asarray(win.scores))


def test_window_smaller_than_k_rejected():
    idx, q_codes = make_arena(False, seed=5, k=5)
    db = idx.arena.db()
    with pytest.raises(ValueError, match="window"):
        windowed_retrieve_masked(q_codes, db, idx.arena.owner,
                                 jnp.zeros(4, jnp.int32),
                                 jnp.zeros(4, jnp.int32), idx.cfg, window=4)


def test_schedule_plan_byte_model():
    """The analytic model: plane-scan policies stream the MSB plane ONCE
    per batch; the vmapped-scalar path streamed it once per query."""
    cfg = RetrievalConfig(k=5)
    eng = RetrievalEngine(cfg)
    rng = np.random.default_rng(0)
    db = BitPlanarDB.from_quantized(build_database(
        jnp.asarray(rng.normal(size=(256, DIM)).astype(np.float32))))
    plane_bytes = 256 * (DIM // 2)
    for policy, kind in [(PlainPolicy(), "plain"),
                         (MaskedPolicy(jnp.zeros(256, jnp.int32),
                                       jnp.zeros(32, jnp.int32)), "masked")]:
        plan = eng.plan_for(db, 32, policy)
        assert plan.kind == kind
        assert plan.stage1_bytes == plane_bytes          # once per BATCH
        assert plan.stage1_bytes_vmapped == 32 * plane_bytes
    wplan = eng.plan_for(db, 32, WindowedPolicy(
        jnp.zeros(256, jnp.int32), jnp.zeros(32, jnp.int32),
        jnp.zeros(32, jnp.int32), window=16))
    assert wplan.kind == "windowed"
    # per-lane windows: bytes scale with B, but only over the window
    assert wplan.stage1_bytes == 32 * 16 * (DIM // 2)
    assert wplan.rows_scanned == 16


def test_engine_batched_equals_vmapped_single_lanes():
    """Lane i of one batched launch == an independent single-query call
    (the old vmapped semantics are preserved exactly)."""
    idx, q_codes = make_arena(True, seed=41)
    db = idx.arena.db()
    tids = jnp.asarray([0, 1, 2, 0], jnp.int32)
    batched = batched_retrieve_masked(q_codes, db, idx.arena.owner, tids,
                                      idx.cfg)
    for i in range(4):
        single = two_stage_retrieve_masked(q_codes[i], db, idx.arena.owner,
                                           tids[i], idx.cfg)
        np.testing.assert_array_equal(np.asarray(batched.indices)[i],
                                      np.asarray(single.indices))
        np.testing.assert_array_equal(np.asarray(batched.scores)[i],
                                      np.asarray(single.scores))


def test_layout_cache_keyed_on_cfg():
    """Replacing idx.cfg (e.g. a larger k) must not serve a stale windowed
    layout sized for the old k — the layout cache is keyed on cfg too."""
    idx, q_codes = make_arena(False, seed=31, k=3, docs=(6, 6, 6))
    tids = np.asarray([0, 1, 2, 0], np.int32)
    idx.retrieve(q_codes, tids)                    # caches window for k=3
    assert idx.last_plan.kind == "windowed"
    idx.cfg = dataclasses.replace(idx.cfg, k=16)   # window 8 would be < k
    res = idx.retrieve(q_codes, tids)              # must not raise
    assert np.asarray(res.indices).shape == (4, 16)


def test_scheduler_ledger_counts_real_requests_only():
    """The flush ledger: streamed bytes include the padded lanes (they ARE
    streamed), but the vmapped comparison counts only real requests — a
    sequential server would never dispatch padding."""
    from repro.tenancy import CrossTenantBatchScheduler
    idx, q_codes = make_arena(False, seed=19)
    sched = CrossTenantBatchScheduler(idx, max_batch=8)
    for i, t in enumerate((0, 1, 0)):          # 3 real requests, padded to 4
        sched.submit(t, np.asarray(q_codes[i]))
    sched.flush()
    plan = idx.last_plan
    assert plan.kind == "windowed" and plan.batch == 4
    window_bytes = plan.rows_scanned * (DIM // 2)
    assert sched.stage1_bytes_streamed == 4 * window_bytes
    assert sched.stage1_bytes_vmapped == 3 * window_bytes


# ---------------------------------------------------------------------------
# Cluster-pruned cascade
# ---------------------------------------------------------------------------

def make_clustered_db(n=512, dim=DIM, k_clusters=16, block_rows=32, seed=0):
    """Single-corpus clustered DB: rows packed in cluster-grouped order,
    plus the codebook / block table / labels the cascade needs."""
    rng = np.random.default_rng(seed)
    docs = rng.normal(size=(n, dim)).astype(np.float32)
    qdb = build_database(jnp.asarray(docs))
    cents, labels = kmeans_int8(np.asarray(qdb.values), k_clusters,
                                iters=4, seed=seed)
    order = cluster_grouped_order(labels)
    db = BitPlanarDB.from_quantized(
        build_database(jnp.asarray(docs[order])))
    labels = labels[order]
    table = block_table(labels, k_clusters, block_rows)
    codebook = clustering.ClusterCodebook.from_codes(cents)
    q, _ = quantize_int8(jnp.asarray(
        rng.normal(size=(4, dim)).astype(np.float32)), per_vector=True)
    return db, codebook, table, labels, q


@pytest.mark.parametrize("metric", ["cosine", "mips"])
@pytest.mark.parametrize("nprobe", [2, 16])
def test_cluster_policy_backend_parity(metric, nprobe):
    """The 3-stage cascade returns identical results on both backends
    (the gathered-scan kernel and its jnp reference are bit-equal, so the
    candidate sets — and everything downstream — agree exactly)."""
    db, codebook, table, labels, q = make_clustered_db()
    cfg = RetrievalConfig(k=5, metric=metric)
    rj, rp = run_both_backends(
        lambda c: cluster_pruned_retrieve(q, db, codebook, table, labels,
                                          c, nprobe=nprobe, block_rows=32),
        cfg)
    assert_results_equal(rj, rp)


def test_cluster_cascade_nprobe_k_recovers_full_scan():
    """Probing every cluster must recover exactly the full two-stage
    scan's top-k SET (row visit order differs, so tie-broken candidate
    sets may differ, but with the budget clamped to the whole corpus the
    exact stage rescoresthe same winners)."""
    db, codebook, table, labels, q = make_clustered_db(n=256, k_clusters=8)
    cfg = RetrievalConfig(k=5, max_candidates=256)
    full = batched_retrieve(q, db, cfg)
    pruned = cluster_pruned_retrieve(q, db, codebook, table, labels, cfg,
                                     nprobe=8, block_rows=32)
    for i in range(q.shape[0]):
        assert (set(np.asarray(full.indices)[i].tolist())
                == set(np.asarray(pruned.indices)[i].tolist()))
        np.testing.assert_array_equal(np.asarray(full.scores)[i],
                                      np.asarray(pruned.scores)[i])


def test_cluster_cascade_never_duplicates_rows():
    """Blocks at cluster boundaries are listed under BOTH clusters; the
    per-row label mask must keep each row visible exactly once, so no
    document can appear twice in one lane's results."""
    db, codebook, table, labels, q = make_clustered_db(n=300, k_clusters=8)
    cfg = RetrievalConfig(k=10, max_candidates=300)
    res = cluster_pruned_retrieve(q, db, codebook, table, labels, cfg,
                                  nprobe=8, block_rows=32)
    for lane in np.asarray(res.indices):
        live = lane[lane >= 0]
        assert len(live) == len(set(live.tolist()))


def test_cluster_schedule_plan_per_stage_ledger():
    """The cluster plan's per-stage ledger: prune streams the K-row
    centroid plane once per batch; approx streams each lane's probed
    blocks; exact streams candidates' full codes. The flat stage1_bytes
    must drop below the full-scan figure by ~K/nprobe."""
    db, codebook, table, labels, q = make_clustered_db(
        n=512, k_clusters=16, block_rows=32)
    cfg = RetrievalConfig(k=5)
    eng = RetrievalEngine(cfg)
    policy = engine_mod.ClusterPolicy(
        owner=jnp.zeros(512, jnp.int32), tenant_ids=jnp.zeros(4, jnp.int32),
        labels=jnp.asarray(labels), centroid_msb=codebook.msb_plane,
        centroid_norms=codebook.norms_sq,
        cluster_blocks=jnp.asarray(table), nprobe=2, block_rows=32)
    plan = eng.plan_for(db, 4, policy)
    assert plan.kind == "cluster"
    mb = table.shape[1]
    probe = 2 * mb * 32
    assert plan.rows_scanned == probe
    assert [s.name for s in plan.stages] == ["prune", "approx", "exact"]
    prune, approx, exact = plan.stages
    assert prune.bytes_hbm == 16 * (DIM // 2)          # codebook, per batch
    assert prune.rows == 16 and prune.bits == 4
    assert approx.bytes_hbm == 4 * probe * (DIM // 2)  # per-lane gathers
    assert approx.bytes_hbm == plan.stage1_bytes
    assert exact.bits == 8
    assert exact.bytes_hbm == plan.stage2_bytes == 4 * plan.candidates * DIM
    # the prune's point: each lane scans a cluster-sized slice, not the
    # arena (the batch-level crossover vs the shared-plane scan happens
    # once N >> B * probe — benchmarks/retrieval_bench.py checks the >=4x
    # reduction at 64k docs)
    assert plan.rows_scanned < 512
    assert plan.stage1_bytes < plan.stage1_bytes_vmapped
    assert plan.stage1_bytes_vmapped == 4 * 512 * (DIM // 2)


# ---------------------------------------------------------------------------
# Device-resident slab policy (the serving runtime's cached path)
# ---------------------------------------------------------------------------

def make_slab_setup(metric="cosine", seed=0):
    """Multi-tenant clustered index + the (policy, host table) layout a
    batched retrieve would run, with a NO_TENANT padding lane."""
    rng = np.random.default_rng(seed)
    idx = MultiTenantIndex(512, DIM, RetrievalConfig(k=3, metric=metric),
                           clusters=ClusterParams(num_clusters=8, nprobe=3,
                                                  block_rows=32))
    for t in range(3):
        idx.ingest(t, jnp.asarray(
            rng.normal(size=(96, DIM)).astype(np.float32)))
    idx.compact()
    tids = np.asarray([0, 1, 1, 2, NO_TENANT], np.int32)
    policy, table = idx.cluster_layout(tids)
    q, _ = quantize_int8(jnp.asarray(
        rng.normal(size=(5, DIM)).astype(np.float32)), per_vector=True)
    return idx, policy, table, tids, q


def make_slab_policy(idx, policy, table, tids, resident_frac, seed=0):
    """Hand-build a SlabPolicy mirroring `resident_frac` of the
    (tenant, cluster) views into a slab extension region, exactly as the
    serving runtime's HotClusterCache does (device block copies)."""
    import jax
    db = idx.arena.db()
    n, d2 = db.msb_plane.shape
    br = policy.block_rows
    rng = np.random.default_rng(seed)
    keys, seen = [], set()
    for i, t in enumerate(tids.tolist()):
        if t < 0:
            continue
        for c in range(table.shape[1]):
            bl = table[i, c]
            bl = bl[bl >= 0]
            if bl.size and (t, c) not in seen:
                seen.add((t, c))
                keys.append((t, c, bl))
    rng.shuffle(keys)
    resident = keys[: round(len(keys) * resident_frac)]
    s_blocks = max(sum(len(bl) for _, _, bl in resident), 1)
    comb = jnp.concatenate([db.msb_plane,
                            jnp.zeros((s_blocks * br, d2), jnp.uint8)])
    nf = jnp.maximum(db.norms_sq.astype(jnp.float32), 1.0)
    inv = jnp.where(db.norms_sq > 0, jax.lax.rsqrt(nf), 0.0)
    inv = jnp.concatenate([inv, jnp.zeros((s_blocks * br,), jnp.float32)])
    slab_tbl = table.copy()
    base, nxt = n // br, 0
    gid0 = np.concatenate([np.arange(base, dtype=np.int32) * br,
                           np.zeros(s_blocks, np.int32)])
    cnt = np.concatenate([np.full(base, br, np.int32),
                          np.zeros(s_blocks, np.int32)])
    src, dst = [], []
    for t, c, bl in resident:
        slots = np.arange(nxt, nxt + len(bl), dtype=np.int32)
        nxt += len(bl)
        for lane in np.nonzero(tids == t)[0]:
            slab_tbl[lane, c, :len(bl)] = slots + base
        # whole-plane-block mirrors: each slot's origin is its source
        # block's first row, at full occupancy
        gid0[slots + base] = bl * br
        cnt[slots + base] = br
        src.extend(bl.tolist())
        dst.extend((slots + base).tolist())
    if src:
        rows_s = (np.asarray(src)[:, None] * br + np.arange(br)).reshape(-1)
        rows_d = (np.asarray(dst)[:, None] * br + np.arange(br)).reshape(-1)
        comb = comb.at[jnp.asarray(rows_d)].set(comb[jnp.asarray(rows_s)])
        inv = inv.at[jnp.asarray(rows_d)].set(inv[jnp.asarray(rows_s)])
    return engine_mod.SlabPolicy(
        packed_labels=engine_mod.packed_membership(
            policy.owner, policy.labels, policy.centroid_msb.shape[0]),
        tenant_ids=policy.tenant_ids, centroid_msb=policy.centroid_msb,
        centroid_norms=policy.centroid_norms,
        cluster_valid=jnp.asarray(table[:, :, 0] >= 0),
        slab_blocks=jnp.asarray(slab_tbl), block_gid0=jnp.asarray(gid0),
        block_count=jnp.asarray(cnt), slab_plane=comb, inv_norms=inv,
        nprobe=policy.nprobe, block_rows=br)


@pytest.mark.parametrize("metric", ["cosine", "mips"])
@pytest.mark.parametrize("resident_frac", [0.0, 0.5, 1.0])
def test_slab_policy_bit_identical_to_cluster_cascade(metric, resident_frac):
    """The slab path — cold (all blocks stream from the plane region),
    mixed hit/miss, and fully warm (every probed view slab-resident) —
    must return results bit-identical to the in-graph ClusterPolicy
    cascade, on both backends, including the NO_TENANT padding lane and
    the aux selection output."""
    idx, policy, table, tids, q = make_slab_setup(metric)
    db = idx.arena.db()
    ref = idx.engine.retrieve(q, db, policy)
    slab = make_slab_policy(idx, policy, table, tids, resident_frac)
    for backend in ("jnp", "pallas"):
        eng = RetrievalEngine(dataclasses.replace(idx.cfg, backend=backend))
        res, tc = eng.retrieve_with_clusters(q, db, slab)
        assert_results_equal(ref, res)
        # selection is the SAME in-graph select_clusters the cold prune runs
        _, ref_tc = eng.retrieve_with_clusters(q, db, policy)
        np.testing.assert_array_equal(np.asarray(tc), np.asarray(ref_tc))
    # padding lane surfaces nothing
    assert np.all(np.asarray(ref.indices)[-1] == -1)


def test_slab_policy_plan_maps_to_cluster_kind():
    idx, policy, table, tids, q = make_slab_setup()
    slab = make_slab_policy(idx, policy, table, tids, 1.0)
    plan = idx.engine.plan_for(idx.arena.db(), len(tids), slab)
    ref = idx.engine.plan_for(idx.arena.db(), len(tids), policy)
    assert plan == ref and plan.kind == "cluster"


def test_multitenant_cluster_path_end_to_end():
    """MultiTenantIndex with clustering: the cascade kind is selected,
    isolation holds, both backends agree, and recall vs the same index
    without clustering stays high on clustered per-tenant corpora."""
    rng = np.random.default_rng(5)
    params = ClusterParams(num_clusters=8, nprobe=3, block_rows=32)
    idx = MultiTenantIndex(1024, DIM, RetrievalConfig(k=3),
                           clusters=params)
    ref = MultiTenantIndex(1024, DIM, RetrievalConfig(k=3))
    for t in range(3):
        docs = rng.normal(size=(120, DIM)).astype(np.float32)
        idx.ingest(t, jnp.asarray(docs))
        ref.ingest(t, jnp.asarray(docs))
    idx.compact()                       # cluster-grouped layout
    q, _ = quantize_int8(jnp.asarray(
        rng.normal(size=(4, DIM)).astype(np.float32)), per_vector=True)
    tids = np.asarray([0, 1, 2, NO_TENANT], np.int32)
    res = idx.retrieve(q, tids)
    assert idx.last_plan.kind == "cluster"
    assert [s.name for s in idx.last_plan.stages] == ["prune", "approx",
                                                      "exact"]
    owner = np.asarray(idx.arena.owner)
    ids = np.asarray(res.indices)
    for i, t in enumerate(tids):
        live = ids[i][ids[i] >= 0]
        assert (owner[live] == t).all()
    assert (ids[3] == -1).all()         # padding lane returns nothing
    idx.cfg = dataclasses.replace(idx.cfg, backend="pallas")
    assert_results_equal(res, idx.retrieve(q, tids))
    # stage-1 bytes: pruned lanes beat the full-arena masked scan
    full_plan = ref.engine.plan_for(ref.arena.db(), 4, MaskedPolicy(
        ref.arena.owner, jnp.asarray(tids)))
    assert idx.last_plan.stage1_bytes < full_plan.stage1_bytes_vmapped


def test_multitenant_cluster_falls_back_until_trained():
    """Before any ingest trains the codebook, retrieval must fall back to
    the windowed/masked paths instead of crashing."""
    idx = MultiTenantIndex(256, DIM, RetrievalConfig(k=3),
                           clusters=ClusterParams(num_clusters=4))
    docs = np.random.default_rng(0).normal(size=(20, DIM)).astype(np.float32)
    q, _ = quantize_int8(jnp.asarray(docs[:2]), per_vector=True)
    # trained already by the first ingest — so drop the codebook to
    # simulate the pre-training window
    idx.ingest(0, jnp.asarray(docs))
    idx.clusters._centroids = None
    res = idx.retrieve(q, np.asarray([0, 0], np.int32))
    assert idx.last_plan.kind in ("windowed", "masked")
    assert np.asarray(res.indices).shape == (2, 3)


def test_scheduler_per_stage_bytes_ledger():
    """Scheduler flushes accumulate the per-stage cascade ledger."""
    from repro.tenancy import CrossTenantBatchScheduler
    rng = np.random.default_rng(9)
    idx = MultiTenantIndex(512, DIM, RetrievalConfig(k=3),
                           clusters=ClusterParams(num_clusters=4, nprobe=2,
                                                  block_rows=32))
    docs = rng.normal(size=(100, DIM)).astype(np.float32)
    idx.ingest(0, jnp.asarray(docs))
    idx.compact()
    sched = CrossTenantBatchScheduler(idx, max_batch=4)
    q, _ = quantize_int8(jnp.asarray(docs[:2]), per_vector=True)
    for i in range(2):
        sched.submit(0, np.asarray(q[i]))
    sched.flush()
    plan = idx.last_plan
    assert plan.kind == "cluster"
    assert sched.stage_bytes == {s.name: s.bytes_hbm for s in plan.stages}


def test_masked_score_floor_is_comparator_safe():
    """engine.MASKED_SCORE**2 must stay below 2**62 (the comparator's limb
    budget) while ranking under every real score."""
    s = int(engine_mod.MASKED_SCORE)
    assert s * s * 1 < 2 ** 62
    assert s < -(512 * 128 * 128)       # below any D<=512 INT8 dot product


# ---------------------------------------------------------------------------
# Stage-0 sign prescreen (the adaptive-precision cascade)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("metric", ["cosine", "mips"])
@pytest.mark.parametrize("policy_kind", ["cluster", "slab"])
def test_prescreen_c0_full_view_is_bit_identical_to_no_prescreen(
        metric, policy_kind):
    """The parity anchor: with c0 >= the probe view the prescreen deletes
    nothing and re-sorts survivors into view order, so the WHOLE cascade
    is bit-identical to the prescreen-off schedule — on both backends,
    for both metrics, through both the cluster and the slab policy."""
    idx, policy, table, tids, q = make_slab_setup(metric)
    db = idx.arena.db()
    view = policy.nprobe * table.shape[2] * policy.block_rows
    cfg_on = dataclasses.replace(idx.cfg, prescreen_c0=view)
    slab = make_slab_policy(idx, policy, table, tids, 0.5)
    pol = policy if policy_kind == "cluster" else slab
    ref = RetrievalEngine(idx.cfg).retrieve(q, db, pol)
    for backend in ("jnp", "pallas"):
        eng = RetrievalEngine(dataclasses.replace(cfg_on, backend=backend))
        assert_results_equal(ref, eng.retrieve(q, db, pol))


@pytest.mark.parametrize("metric", ["cosine", "mips"])
def test_prescreen_backend_parity_and_isolation_at_small_c0(metric):
    """A thinning prescreen (c0 = view/4) changes the candidate set, so
    the anchor is cross-backend bit-parity plus the isolation contract:
    no lane ever surfaces another tenant's rows or a padding result."""
    idx, policy, table, tids, q = make_slab_setup(metric)
    db = idx.arena.db()
    view = policy.nprobe * table.shape[2] * policy.block_rows
    cfg = dataclasses.replace(idx.cfg, prescreen_c0=view // 4)
    slab = make_slab_policy(idx, policy, table, tids, 0.5)
    for pol in (policy, slab):
        rj, rp = run_both_backends(
            lambda c, p=pol: RetrievalEngine(c).retrieve(q, db, p), cfg)
        assert_results_equal(rj, rp)
        owner = np.asarray(idx.arena.owner)
        ids = np.asarray(rj.indices)
        for i, t in enumerate(tids.tolist()):
            live = ids[i][ids[i] >= 0]
            if t < 0:
                assert live.size == 0
            else:
                assert (owner[live] == t).all()


def _check_prescreen_survivors(seed: int, c0: int, deletes: int) -> None:
    """The stage-level property: run CentroidPrune + SignPrescreen in
    isolation on an arena with tombstones and verify every survivor the
    prescreen marks visible (member=True) is a live row of the lane's
    own tenant — stage 0 can never leak a foreign or tombstoned row
    into stage 1's candidate view."""
    from repro.core.bitplanar import sign_pm1
    from repro.core.quantization import msb_nibble
    rng = np.random.default_rng(seed)
    idx = MultiTenantIndex(
        512, DIM, RetrievalConfig(k=3, prescreen_c0=c0),
        clusters=ClusterParams(num_clusters=8, nprobe=3, block_rows=32))
    for t in range(3):
        idx.ingest(t, jnp.asarray(
            rng.normal(size=(96, DIM)).astype(np.float32)))
    idx.compact()
    if deletes:
        live = np.nonzero(np.asarray(idx.arena.owner) >= 0)[0]
        idx.arena.delete(rng.choice(live, size=deletes, replace=False))
    tids = np.asarray([0, 1, 1, 2], np.int32)
    policy, _ = idx.cluster_layout(tids)
    q, _ = quantize_int8(jnp.asarray(
        rng.normal(size=(4, DIM)).astype(np.float32)), per_vector=True)
    ctx = engine_mod._CascadeCtx(
        query_codes=q, q_msb=msb_nibble(q), db=idx.arena.db(),
        policy=policy, cfg=idx.cfg, fns=engine_mod.stage_fns("jnp"),
        q_sign=sign_pm1(q))
    state = engine_mod._CascadeState()
    state = engine_mod.CentroidPrune(policy.nprobe).run(state, ctx)
    state = engine_mod.SignPrescreen(idx.cfg.prescreen_c0).run(state, ctx)
    rows = np.asarray(state.rows)
    member = np.asarray(state.member)
    # the view really was thinned to the clamped budget
    assert rows.shape[1] <= max(idx.cfg.k, c0)
    owner = np.asarray(idx.arena.owner)
    for i, t in enumerate(tids.tolist()):
        surv = rows[i][member[i]]
        assert (surv >= 0).all()
        assert (owner[surv] == t).all()     # same tenant AND live
    # ...and the full cascade agrees end-to-end
    res = idx.retrieve(q, tids)
    ids = np.asarray(res.indices)
    for i, t in enumerate(tids.tolist()):
        live_ids = ids[i][ids[i] >= 0]
        assert (owner[live_ids] == t).all()


try:
    from hypothesis import given, settings
    from hypothesis import strategies as st
    _HAVE_HYPOTHESIS = True
except ImportError:          # pragma: no cover - optional dependency
    _HAVE_HYPOTHESIS = False

if _HAVE_HYPOTHESIS:
    @settings(max_examples=25, deadline=None)
    @given(seed=st.integers(0, 2 ** 16), c0=st.integers(3, 512),
           deletes=st.integers(0, 60))
    def test_prescreen_never_surfaces_foreign_or_dead_rows(seed, c0,
                                                           deletes):
        _check_prescreen_survivors(seed, c0, deletes)
else:
    @pytest.mark.parametrize("seed,c0,deletes",
                             [(0, 3, 0), (1, 16, 30), (2, 64, 60),
                              (3, 100, 17), (4, 512, 45)])
    def test_prescreen_never_surfaces_foreign_or_dead_rows(seed, c0,
                                                           deletes):
        """Seeded fallback for the hypothesis property when hypothesis
        is not installed: same check, fixed corpus of cases."""
        _check_prescreen_survivors(seed, c0, deletes)


def test_prescreen_schedule_plan_ledger():
    """The prescreen's StagePlan entry: bits=1, whole view streamed at
    D/8 bytes per row, and the downstream approx stage shrunk to the C0
    survivor budget — all exact arithmetic."""
    db, codebook, table, labels, q = make_clustered_db(
        n=512, k_clusters=16, block_rows=32)
    cfg = RetrievalConfig(k=5, prescreen_c0=128)
    eng = RetrievalEngine(cfg)
    policy = engine_mod.ClusterPolicy(
        owner=jnp.zeros(512, jnp.int32), tenant_ids=jnp.zeros(4, jnp.int32),
        labels=jnp.asarray(labels), centroid_msb=codebook.msb_plane,
        centroid_norms=codebook.norms_sq,
        cluster_blocks=jnp.asarray(table), nprobe=2, block_rows=32)
    plan = eng.plan_for(db, 4, policy)
    assert [s.name for s in plan.stages] == ["prune", "prescreen",
                                             "approx", "exact"]
    view = 2 * table.shape[1] * 32
    prune, pre, approx, exact = plan.stages
    assert pre.rows == view and pre.bits == 1
    assert pre.bytes_hbm == 4 * view * (DIM // 8)     # sign plane, per lane
    assert pre.compares == view
    assert approx.rows == 128                          # C0 survivors only
    assert approx.bytes_hbm == 4 * 128 * (DIM // 2)
    assert plan.stage1_bytes == approx.bytes_hbm
    # the cascade's total stage-0+stage-1 traffic beats the no-prescreen
    # schedule's stage-1 bytes (same policy, prescreen-off config)
    base = RetrievalEngine(RetrievalConfig(k=5)).plan_for(db, 4, policy)
    base_s1 = [s for s in base.stages if s.name == "approx"][0].bytes_hbm
    assert pre.bytes_hbm + approx.bytes_hbm < base_s1


def test_named_scopes_leave_the_cascade_bit_identical(monkeypatch):
    """`cascade.*` named scopes are metadata: the prescreened slab
    cascade compiled with them returns what it returns without them, and
    only the scoped program names its stages."""
    import contextlib

    import jax
    idx, policy, table, tids, q = make_slab_setup("cosine")
    db = idx.arena.db()
    view = policy.nprobe * table.shape[2] * policy.block_rows
    cfg = dataclasses.replace(idx.cfg, prescreen_c0=view // 4)
    pol = make_slab_policy(idx, policy, table, tids, 0.5)

    def run():
        def cascade(q, db, pol, cfg):       # a new function: a new trace
            return engine_mod._cascade_batched(q, db, pol, cfg)
        fn = jax.jit(cascade, static_argnames=("cfg",))
        return fn(q, db, pol, cfg=cfg), fn.lower(q, db, pol, cfg=cfg) \
            .compile().as_text()
    scoped, text = run()
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    plain, plain_text = run()
    assert_results_equal(scoped, plain)
    from repro.launch.hlo_analysis import op_scopes
    assert set(op_scopes(text).values()) == {
        "cascade.CentroidPrune", "cascade.SignPrescreen",
        "cascade.ApproxScan", "cascade.ExactRescore"}
    assert op_scopes(plain_text) == {}
