"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps, exact equality."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import BitPlanarDB, build_database, msb_nibble, quantize_int8
from repro.kernels import ops, ref
from repro.kernels.fused_topk import fused_topk_pallas
from repro.kernels.stage1_int4 import stage1_int4_pallas


def make(n, d, seed=0):
    rng = np.random.default_rng(seed)
    db = build_database(jnp.asarray(
        rng.normal(size=(n, d)).astype(np.float32)))
    bp = BitPlanarDB.from_quantized(db)
    q, _ = quantize_int8(jnp.asarray(rng.normal(size=(d,)).astype(np.float32)))
    return db, bp, q


@pytest.mark.parametrize("n,d,block", [(256, 512, 64), (512, 512, 256),
                                       (128, 256, 128), (1024, 128, 256),
                                       (96, 512, 32)])
def test_stage1_kernel_shape_sweep(n, d, block):
    _, bp, q = make(n, d, seed=n + d)
    q_eo = ops.pack_query_even_odd(msb_nibble(q))
    got = stage1_int4_pallas(q_eo, bp.msb_plane, block_n=block)
    want = ref.stage1_scores_ref(q_eo, bp.msb_plane)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("c,d,block", [(64, 512, 64), (50, 512, 64),
                                       (128, 256, 32), (16, 128, 8)])
def test_stage2_kernel_shape_sweep(c, d, block):
    db, bp, q = make(max(c, 64), d, seed=c + d)
    cand = jnp.arange(c, dtype=jnp.int32)
    mr = jnp.take(bp.msb_plane, cand, axis=0)
    lr = jnp.take(bp.lsb_plane, cand, axis=0)
    got = ops.stage2_scores(q, mr, lr, block_c=block)
    want = ref.stage2_scores_ref(ops.pack_query_even_odd(q), mr, lr)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    # exact INT8 ground truth
    exact = (np.asarray(db.values)[:c].astype(np.int64)
             @ np.asarray(q).astype(np.int64))
    np.testing.assert_array_equal(np.asarray(got, np.int64), exact)


@pytest.mark.parametrize("n,block,k", [(512, 128, 8), (1024, 256, 4),
                                       (256, 64, 16)])
def test_fused_topk_kernel(n, block, k):
    _, bp, q = make(n, 512, seed=n + k)
    q_eo = ops.pack_query_even_odd(msb_nibble(q))
    gs, gi = fused_topk_pallas(q_eo, bp.msb_plane, k=k, block_n=block)
    ws, wi = ref.fused_topk_ref(q_eo, bp.msb_plane, block, k)
    np.testing.assert_array_equal(np.asarray(gs), np.asarray(ws))
    np.testing.assert_array_equal(np.asarray(gi), np.asarray(wi))


def test_fused_candidates_recall():
    """With k_per_block >= c the fused kernel's candidate SET equals the
    dense stage-1 top-c exactly."""
    _, bp, q = make(1000, 512, seed=9)
    q_msb = msb_nibble(q)
    cands = ops.fused_candidates(q_msb, bp.msb_plane, c=50, k_per_block=50,
                                 block_n=256)
    from repro.core.retrieval import stage1_scores_jnp
    scores = stage1_scores_jnp(q_msb, bp.msb_plane)
    true = jax.lax.top_k(scores, 50)[1]
    assert set(np.asarray(cands).tolist()) == set(np.asarray(true).tolist())


def test_stage1_wrapper_pads_nonmultiple():
    _, bp, q = make(250, 512, seed=11)    # 250 not a block multiple
    got = ops.stage1_scores(msb_nibble(q), bp.msb_plane)
    want = ref.stage1_scores_ref(ops.pack_query_even_odd(msb_nibble(q)),
                                 bp.msb_plane)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_kernels_accept_extreme_values():
    """All-(-128) codes: the nibble decomposition edge case."""
    codes = jnp.full((64, 512), -128, jnp.int8)
    from repro.core.bitplanar import pack_nibble_planes
    msb, lsb = pack_nibble_planes(codes)
    q = jnp.full((512,), -128, jnp.int8)
    got = ops.stage2_scores(q, msb, lsb)
    np.testing.assert_array_equal(np.asarray(got, np.int64),
                                  np.full(64, 512 * 128 * 128, np.int64))


# ---------------------------------------------------------------------------
# Batch-native kernels (the engine's backends)
# ---------------------------------------------------------------------------

def make_batch(n, d, b, seed=0):
    rng = np.random.default_rng(seed)
    db = build_database(jnp.asarray(
        rng.normal(size=(n, d)).astype(np.float32)))
    bp = BitPlanarDB.from_quantized(db)
    q, _ = quantize_int8(jnp.asarray(
        rng.normal(size=(b, d)).astype(np.float32)), per_vector=True)
    return db, bp, q


@pytest.mark.parametrize("n,d,b,block", [(256, 512, 8, 64), (512, 256, 1, 256),
                                         (96, 128, 32, 32), (250, 512, 4, 64)])
def test_stage1_batched_kernel_true_matmul(n, d, b, block):
    """The batched matmul kernel == per-lane oracle == vmapped scalar kernel
    (bit-for-bit: all paths are exact integer arithmetic)."""
    _, bp, q = make_batch(n, d, b, seed=n + d + b)
    q_msb = msb_nibble(q)
    got = ops.stage1_scores_batched(q_msb, bp.msb_plane, block_n=block)
    want = ref.stage1_scores_batched_ref(ops.pack_query_panel(q_msb),
                                         bp.msb_plane)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    vmapped = jax.vmap(lambda qm: ops.stage1_scores(qm, bp.msb_plane,
                                                    block_n=block))(q_msb)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(vmapped))


@pytest.mark.parametrize("b,w,d,block", [(4, 128, 256, 64), (2, 64, 512, 64),
                                         (8, 96, 128, 32)])
def test_stage1_rows_kernel_per_lane_windows(b, w, d, block):
    """Each lane scores its OWN row block (the windowed-policy shape)."""
    _, bp, q = make_batch(w * b, d, b, seed=b + w + d)
    starts = np.arange(b) * w
    rows = jnp.stack([bp.msb_plane[s:s + w] for s in starts])
    q_msb = msb_nibble(q)
    got = ops.stage1_scores_rows(q_msb, rows, block_w=block)
    want = ref.stage1_rows_batched_ref(ops.pack_queries_even_odd(q_msb), rows)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("b,c,d,block", [(4, 50, 512, 64), (8, 64, 256, 32),
                                         (2, 16, 128, 8)])
def test_stage2_batched_kernel_one_launch(b, c, d, block):
    """(B, C) gathered candidates rescored in one launch, exact INT8."""
    db, bp, q = make_batch(max(c * b, 64), d, b, seed=b + c + d)
    rng = np.random.default_rng(b + c)
    cand = jnp.asarray(rng.integers(0, bp.num_docs, (b, c)), jnp.int32)
    mr = jnp.take(bp.msb_plane, cand, axis=0)
    lr = jnp.take(bp.lsb_plane, cand, axis=0)
    got = ops.stage2_scores_batched(q, mr, lr, block_c=block)
    want = ref.stage2_scores_batched_ref(ops.pack_queries_even_odd(q), mr, lr)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    # exact INT8 ground truth per lane
    vals = np.asarray(db.values).astype(np.int64)
    qq = np.asarray(q).astype(np.int64)
    exact = np.stack([vals[np.asarray(cand)[i]] @ qq[i] for i in range(b)])
    np.testing.assert_array_equal(np.asarray(got, np.int64), exact)


@pytest.mark.parametrize("masked", [False, True])
def test_fused_topk_batched_kernel(masked):
    """Batch grid dimension + the tenant segment mask applied IN-kernel."""
    from repro.kernels.fused_topk import fused_topk_batched_pallas
    n, d, b, block, k = 512, 256, 4, 128, 8
    _, bp, q = make_batch(n, d, b, seed=17)
    q_eo = ops.pack_queries_even_odd(msb_nibble(q))
    rng = np.random.default_rng(3)
    owner = jnp.asarray(rng.integers(-1, 3, n), jnp.int32)
    tids = jnp.asarray([0, 1, 2, -2], jnp.int32)   # incl. a padding lane
    if masked:
        gs, gi = fused_topk_batched_pallas(q_eo, bp.msb_plane, owner, tids,
                                           k=k, block_n=block)
        ws, wi = ref.fused_topk_batched_ref(q_eo, bp.msb_plane, block, k,
                                            owner, tids)
    else:
        gs, gi = fused_topk_batched_pallas(q_eo, bp.msb_plane,
                                           k=k, block_n=block)
        ws, wi = ref.fused_topk_batched_ref(q_eo, bp.msb_plane, block, k)
    np.testing.assert_array_equal(np.asarray(gs), np.asarray(ws))
    np.testing.assert_array_equal(np.asarray(gi), np.asarray(wi))


def test_fused_candidates_batched_masked_recall():
    """With k_per_block >= c the batched fused candidate SET equals each
    lane's dense masked stage-1 top-c exactly."""
    from repro.core.engine import stage1_plane_batched_jnp
    n, d, b, c = 512, 256, 3, 20
    _, bp, q = make_batch(n, d, b, seed=23)
    q_msb = msb_nibble(q)
    rng = np.random.default_rng(5)
    owner = jnp.asarray(rng.integers(0, 3, n), jnp.int32)
    tids = jnp.asarray([0, 1, 2], jnp.int32)
    cands = ops.fused_candidates_batched(q_msb, bp.msb_plane, owner, tids,
                                         c=c, k_per_block=c, block_n=128)
    scores = stage1_plane_batched_jnp(q_msb, bp.msb_plane)
    member = np.asarray(owner)[None, :] == np.asarray(tids)[:, None]
    # int64: negating INT32_MIN would overflow in int32
    masked = np.where(member, np.asarray(scores),
                      np.iinfo(np.int32).min).astype(np.int64)
    for i in range(b):
        true = set(np.argsort(-masked[i], kind="stable")[:c].tolist())
        assert set(np.asarray(cands)[i].tolist()) == true


@pytest.mark.parametrize("n,d,b,j,br", [(256, 256, 4, 6, 32),
                                        (512, 128, 8, 4, 64),
                                        (128, 512, 2, 8, 32)])
def test_stage1_gather_kernels_two_region_slab(n, d, b, j, br):
    """The scalar-prefetch gather kernel over a combined [plane | slab]
    array: slab-region blocks mirror plane blocks (the hot-cluster
    cache's fills), and scoring through either region is bit-equal to
    the oracles and to the plain-plane gather — the kernel is
    indifferent to WHICH region an id addresses."""
    _, bp, q = make_batch(n, d, b, seed=n + b)
    q_msb = msb_nibble(q)
    q_eo = ops.pack_queries_even_odd(q_msb)
    rng = np.random.default_rng(j)
    ids = jnp.asarray(rng.integers(0, n // br, (b, j)).astype(np.int32))
    # general wrapper == oracle (clamped/zero-mask convention)
    got = ops.stage1_scores_gather(q_msb, bp.msb_plane, ids, block_rows=br)
    want = ref.stage1_gather_batched_ref(q_eo, bp.msb_plane, ids, br)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    # two-region slab: copy half the referenced blocks into a slab
    # extension and remap their ids — scores must not change at all
    uniq = np.unique(np.asarray(ids))
    hot = uniq[: max(1, len(uniq) // 2)]
    slab = jnp.concatenate(
        [bp.msb_plane,
         jnp.zeros((len(hot) * br, d // 2), jnp.uint8)])
    base = n // br
    remap = {int(pb): base + s for s, pb in enumerate(hot)}
    rows_s = (hot[:, None] * br + np.arange(br)).reshape(-1)
    rows_d = np.arange(len(hot) * br) + n
    slab = slab.at[jnp.asarray(rows_d)].set(slab[jnp.asarray(rows_s)])
    sids = jnp.asarray(np.vectorize(lambda x: remap.get(int(x), int(x)))(
        np.asarray(ids)).astype(np.int32))
    got_slab = ops.stage1_scores_gather_resident(q_msb, slab, sids,
                                                 block_rows=br)
    want_slab = ref.stage1_gather_resident_ref(q_eo, slab, sids, br)
    np.testing.assert_array_equal(np.asarray(got_slab),
                                  np.asarray(want_slab))
    np.testing.assert_array_equal(np.asarray(got_slab), np.asarray(got))
    # the engine's lean jnp reference agrees too
    from repro.core.engine import stage1_gather_resident_jnp
    lean = stage1_gather_resident_jnp(q_msb, slab, sids, block_rows=br)
    np.testing.assert_array_equal(np.asarray(lean), np.asarray(got))


def test_stage1_gather_resident_rejects_partial_plane():
    _, bp, q = make_batch(96, 128, 2, seed=3)
    ids = jnp.zeros((2, 2), jnp.int32)
    with pytest.raises(ValueError, match="block multiple"):
        ops.stage1_scores_gather_resident(msb_nibble(q), bp.msb_plane, ids,
                                          block_rows=64)


# ---------------------------------------------------------------------------
# Stage-0 sign-plane kernels (the 1-bit prescreen)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n,d,b,block", [(256, 512, 8, 64), (512, 256, 1, 256),
                                         (96, 128, 32, 32), (250, 512, 4, 64)])
def test_stage0_sign_batched_kernel(n, d, b, block):
    """The 1-bit sign-agreement kernel == oracle == the int8 ground
    truth ``sum_k sign(q_k) sign(d_k)`` recomputed from the raw codes
    (all exact integer arithmetic — bit-for-bit, zero-padded tail
    blocks included via n=250)."""
    db, bp, q = make_batch(n, d, b, seed=n + d + b)
    assert bp.sign_plane is not None
    q_sign = ops.pack_query_signs(q)
    got = ops.stage0_sign_scores_batched(q_sign, bp.sign_plane,
                                         block_n=block)
    want = ref.stage0_sign_batched_ref(q_sign, bp.sign_plane)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    # ground truth from the raw int8 codes (0 counts as +1 on both sides)
    sq = np.where(np.asarray(q) < 0, -1, 1).astype(np.int64)
    sd = np.where(np.asarray(db.values) < 0, -1, 1).astype(np.int64)
    np.testing.assert_array_equal(np.asarray(got, np.int64), sq @ sd.T)


@pytest.mark.parametrize("n,d,b,j,br", [(256, 256, 4, 6, 32),
                                        (512, 128, 8, 4, 64),
                                        (250, 512, 2, 8, 32)])
def test_stage0_sign_gather_kernels_two_region_slab(n, d, b, j, br):
    """The stage-0 scalar-prefetch gather (clamped/zero-pad convention,
    n=250 forces a zero-padded tail) and its resident two-region variant:
    slab-region sign blocks mirroring plane blocks score bit-equal to
    the plain-plane gather — region-indifferent like stage 1."""
    _, bp, q = make_batch(n, d, b, seed=n + b)
    q_sign = ops.pack_query_signs(q)
    rng = np.random.default_rng(j)
    nb = -(-n // br)
    ids = jnp.asarray(rng.integers(0, nb, (b, j)).astype(np.int32))
    got = ops.stage0_sign_scores_gather(q_sign, bp.sign_plane, ids,
                                        block_rows=br)
    want = ref.stage0_sign_gather_ref(q_sign, bp.sign_plane, ids, br)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    # two-region slab: pad to a block multiple, extend, remap hot blocks
    pad = (-n) % br
    plane = jnp.pad(bp.sign_plane, ((0, pad), (0, 0)))
    uniq = np.unique(np.asarray(ids))
    hot = uniq[: max(1, len(uniq) // 2)]
    slab = jnp.concatenate(
        [plane, jnp.zeros((len(hot) * br, d // 8), jnp.uint8)])
    remap = {int(pb): nb + s for s, pb in enumerate(hot)}
    rows_s = (hot[:, None] * br + np.arange(br)).reshape(-1)
    rows_d = np.arange(len(hot) * br) + nb * br
    slab = slab.at[jnp.asarray(rows_d)].set(slab[jnp.asarray(rows_s)])
    sids = jnp.asarray(np.vectorize(lambda x: remap.get(int(x), int(x)))(
        np.asarray(ids)).astype(np.int32))
    got_slab = ops.stage0_sign_scores_gather_resident(q_sign, slab, sids,
                                                      block_rows=br)
    want_slab = ref.stage0_sign_gather_resident_ref(q_sign, slab, sids, br)
    np.testing.assert_array_equal(np.asarray(got_slab),
                                  np.asarray(want_slab))
    np.testing.assert_array_equal(np.asarray(got_slab), np.asarray(got))
    # the engine's lean jnp backends agree too
    from repro.core.engine import (stage0_sign_gather_batched_jnp,
                                   stage0_sign_gather_resident_jnp)
    lean = stage0_sign_gather_batched_jnp(q_sign, bp.sign_plane, ids,
                                          block_rows=br)
    np.testing.assert_array_equal(np.asarray(lean), np.asarray(got))
    lean_r = stage0_sign_gather_resident_jnp(q_sign, slab, sids,
                                             block_rows=br)
    np.testing.assert_array_equal(np.asarray(lean_r), np.asarray(got))


def _kv_cell_block_table(lengths, kh, pages, npages, rng):
    """Per (sequence, KV head) lane: `npages` ascending pages, drawn from
    the pages a sequence's `length` reaches (the page prune's valid set),
    filled with the lowest invalid pages when too few are valid (what
    top_k of an all -inf key gives), as flat block ids lane * pages + p."""
    table = []
    for b, length in enumerate(lengths):
        valid = -(-length // 8)
        for h in range(kh):
            if valid >= npages:
                sel = np.sort(rng.choice(valid, npages, replace=False))
            else:
                sel = np.arange(npages)
            table.append((b * kh + h) * pages + sel)
    return np.stack(table).astype(np.int32)


@pytest.mark.parametrize("lengths", [(517, 0, 576, 131), (576, 9, 0, 64)])
def test_stage0_sign_gather_gqa_shared_matches_per_head_ref(lengths):
    """The KV prescreen's gather at the agent cell's shapes (B 4, KH 2,
    G 7, hd 64, T 576, 16 of 72 pages): one gather per (sequence, KV
    head) lane, scored against the lane's 7 query heads, equals the
    per-query-head oracle bit for bit on every head. Lengths end
    mid-page and at 0; the last lane's table ends on the plane's last
    block."""
    from repro.core.bitplanar import sign_plane_from_msb
    from repro.core.engine import stage0_sign_gather_batched_jnp
    bsz, kh, g, hd, t, pr, npages = 4, 2, 7, 64, 576, 8, 16
    pages = t // pr
    rng = np.random.default_rng(sum(lengths))
    k_msb = rng.integers(0, 256, (bsz * kh * t, hd // 2)).astype(np.uint8)
    plane = sign_plane_from_msb(jnp.asarray(k_msb))           # (4608, 8)
    table = _kv_cell_block_table(lengths, kh, pages, npages, rng)
    table[-1, -1] = bsz * kh * pages - 1                      # last block
    table = jnp.asarray(np.sort(table, axis=1))
    q = jnp.asarray(np.where(rng.random((bsz * kh, g, hd)) < 0.5,
                             -1, 1).astype(np.int8))
    got = ops.stage0_sign_scores_gather(q, plane, table, block_rows=pr)
    lean = stage0_sign_gather_batched_jnp(q, plane, table, block_rows=pr)
    assert got.shape == (bsz * kh, g, npages * pr)
    for head in range(g):
        want = ref.stage0_sign_gather_ref(q[:, head], plane, table, pr)
        np.testing.assert_array_equal(np.asarray(got[:, head]),
                                      np.asarray(want))
        np.testing.assert_array_equal(np.asarray(lean[:, head]),
                                      np.asarray(want))


@pytest.mark.parametrize("j,block_rows,d8,want", [
    (16, 8, 8, 16),        # a KV lane: every page in one step
    (128, 32, 64, 64),     # a retrieval lane: half its blocks a step
    (7, 32, 64, 7), (96, 32, 512, 16), (5, 512, 512, 1)])
def test_stage0_gather_blocks_per_step(j, block_rows, d8, want):
    """All of a lane's blocks in one step while their unpacked tiles fit
    the budget, else the largest divisor of J that fits, never zero."""
    from repro.kernels.stage0_sign import gather_blocks_per_step
    got = gather_blocks_per_step(j, block_rows, d8)
    assert got == want and j % got == 0


def test_stage0_sign_plane_matches_msb_derivation():
    """pack_sign_plane(codes) == sign_plane_from_msb(pack_nibble_planes'
    msb): the identity that lets the serving slab derive its combined
    sign plane from the combined msb plane with no second fill path."""
    from repro.core.bitplanar import (pack_nibble_planes, pack_sign_plane,
                                      sign_plane_from_msb)
    rng = np.random.default_rng(29)
    codes = jnp.asarray(rng.integers(-128, 128, (96, 64)).astype(np.int8))
    msb, _ = pack_nibble_planes(codes)
    np.testing.assert_array_equal(np.asarray(pack_sign_plane(codes)),
                                  np.asarray(sign_plane_from_msb(msb)))
