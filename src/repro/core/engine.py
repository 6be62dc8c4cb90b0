"""Batch-native retrieval engine: ONE staged cascade behind every variant.

The paper's memory-access argument — stream the MSB nibble plane once and
touch full INT8 codes only for candidates — only survives batch serving if
batching is first-class all the way down, and only survives SCALE if the
first full pass itself can be pruned. This module is the single batched
implementation every retrieval variant shares, layered as:

  policy   — WHICH rows each batch lane may touch, expressed as data:
             `PlainPolicy` (every row), `MaskedPolicy` (rows whose arena
             owner matches the lane's tenant), `WindowedPolicy` (a per-lane
             contiguous arena window), `ClusterPolicy` (rows in the
             lane's top-`nprobe` clusters of an IVF-style INT8 centroid
             codebook — see repro.core.clustering). Adding a visibility
             rule means adding a policy, not a retrieval path.
  schedule — an N-stage CASCADE: an ordered tuple of stage specs executed
             by one batched driver (`_cascade_batched`). Today's stages:
             `CentroidPrune` (score K centroids, keep the top-P clusters'
             row blocks), `ApproxScan` (batched INT4 MSB scan over the
             surviving row view + per-lane candidate top-C), and
             `ExactRescore` (batched INT8 gather + exact rescore + metric
             rerank). The paper's two-stage scheme is just the 2-element
             cascade; the cluster-pruned path is the 3-element one. A new
             stage (e.g. a binary-sketch pre-prune) is a new spec in
             `cascade_stages`, not a new retrieval path.
  backend  — the batched stage primitives the schedule calls, selected by
             `RetrievalConfig.backend`: pure-jnp reference math ("jnp") or
             the batch-native Pallas TPU kernels ("pallas"). Both are
             exact integer arithmetic, so they agree bit-for-bit.

Stage-1 row views come in three shapes: the shared plane (a TRUE
(N, D/2) x (D/2, B) matmul — doc planes stream from HBM once per BATCH),
per-lane contiguous windows, and per-lane BLOCK GATHERS (the cluster
prune's output: only blocks of selected clusters are streamed, via scalar-
prefetch on the Pallas backend). `SchedulePlan` carries exact analytic
byte counts per stage; benchmarks/retrieval_bench.py measures wall-clock.

The legacy entry points in repro.core.retrieval are thin wrappers that
build a policy and call this engine.
"""
from __future__ import annotations

import dataclasses
from typing import Literal

import jax
import jax.numpy as jnp

from repro.core import bitplanar, quantization, similarity
from repro.core.retrieval import RetrievalConfig, RetrievalResult

INT32_MIN = jnp.iinfo(jnp.int32).min

# Stage-2 score assigned to out-of-segment candidates. Most-negative-plus-one
# so s*s stays below 2**62 inside the non-division comparator's int64 limbs;
# any in-segment row (even with a negative score) orders strictly above it.
MASKED_SCORE = jnp.int32(-(2 ** 31 - 1))


# ---------------------------------------------------------------------------
# Membership / window / cluster policies (pytrees: the TYPE selects the code
# path, the leaves are device data, so jit specializes per policy kind only)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class PlainPolicy:
    """Every row visible to every lane (the single-corpus case)."""


@dataclasses.dataclass(frozen=True)
class MaskedPolicy:
    """Lane i sees exactly the rows with ``owner == tenant_ids[i]``.

    owner: (N,) int32 slot -> tenant map (free/tombstoned slots hold -1).
    tenant_ids: (B,) int32; negative ids (NO_TENANT padding lanes) match
    nothing — -1 must never act as a segment key or it would resurrect
    tombstones. The fully general multi-tenant path: works for arbitrarily
    fragmented tenants at the cost of scanning the whole arena.
    """

    owner: jax.Array
    tenant_ids: jax.Array


@dataclasses.dataclass(frozen=True)
class WindowedPolicy:
    """MaskedPolicy restricted to one contiguous window per lane.

    When every requested tenant occupies a single contiguous slot run (the
    invariant bump allocation establishes and tenant-grouped compaction
    restores), lane i only streams the `window` rows at ``starts[i]`` —
    a mixed batch costs one launch AND only per-tenant work. Rows inside
    the window but outside the segment (neighbours, tombstones) are masked
    exactly like the full scan. `window` is static (callers round up to a
    power-of-two bucket to bound recompilation) and must be >= cfg.k.
    """

    owner: jax.Array
    tenant_ids: jax.Array
    starts: jax.Array
    window: int


@dataclasses.dataclass(frozen=True)
class ClusterPolicy:
    """IVF-style centroid prune: lane i scans only its top-`nprobe`
    clusters' row blocks (and, within them, only rows it owns).

    The arena rows are covered by fixed-size blocks of `block_rows` rows;
    `cluster_blocks` lists, per cluster, the ids of the blocks holding
    that cluster's rows (-1 padding): shape (K, MB) when the table is
    shared by every lane (single corpus), or (B, K, MB) when each lane
    has its own view (multi-tenant: lane i's table only lists blocks
    holding rows of ITS tenant, so foreign clusters read as empty and are
    never probed). Stage 0 scores the K centroids (same batched INT4
    kernel as stage 1 — the codebook is just another nibble plane), keeps
    the top `nprobe` valid clusters per lane, and expands their blocks
    into an explicit per-lane row view for the INT4 scan — so stage-1
    bytes drop from O(N) per batch to O(B * nprobe * rows_per_cluster).

    owner/tenant_ids mask exactly like MaskedPolicy (single-corpus callers
    pass zeros for both, which makes every gathered row visible).
    `nprobe`, `block_rows` are static; `nprobe` must be <= K and the
    expanded view must hold at least cfg.k rows.
    """

    owner: jax.Array            # (N,) int32
    tenant_ids: jax.Array       # (B,) int32
    labels: jax.Array           # (N,) int32 row -> cluster (-1 free/dead)
    centroid_msb: jax.Array     # (K, D//2) uint8 packed centroid nibbles
    centroid_norms: jax.Array   # (K,) int32 centroid squared norms
    cluster_blocks: jax.Array   # (K, MB) or (B, K, MB) int32, -1 padded
    nprobe: int
    block_rows: int


@dataclasses.dataclass(frozen=True)
class ViewPolicy:
    """An explicitly MATERIALIZED per-lane stage-1 row view.

    A generic entry point for callers that assembled the stage-1 rows
    themselves (the serving runtime's pre-slab cache path used this; the
    runtime now hands the engine a `SlabPolicy` instead so hit bytes stay
    device-resident). Bit-exact with the ClusterPolicy path by
    construction: `rows` and `member` come from the same expansion, and
    `msb_rows` holds the same plane bytes (padding regions may hold zeros
    instead of the clamped block-0 bytes the gather path streams, which
    is invisible — every padding row is masked out of both stages by
    `member`).

    rows: (B, R) global row ids of the view (-1 holes).
    member: (B, R) bool visibility mask (tenant + cluster + hole masking).
    msb_rows: (B, R, D//2) uint8 gathered stage-1 plane rows.
    """

    rows: jax.Array
    member: jax.Array
    msb_rows: jax.Array


@dataclasses.dataclass(frozen=True)
class SlabPolicy:
    """ClusterPolicy whose stage-1 blocks stream from TWO sources: the
    arena plane (misses) or a device-resident hot-cluster cache slab
    (hits) — the serving runtime's cached path.

    The slab is an EXTENSION REGION of one combined plane array,
    ``slab_plane = [arena msb_plane | cache slab rows]`` (rows >= N are
    cache-owned copies of hot clusters' rows), so "two sources" costs
    exactly one block gather: `slab_blocks` is the host-built per-launch
    indirection table — each entry either a plane block id (miss) or
    ``N/block_rows + slab block id`` (hit). Selection stays in-graph
    (the same centroid scoring + validity the cold cascade runs); the
    host only resolves the (tenant, cluster) -> slab-slot map into this
    bounded int32 table. Hit bytes are therefore never re-uploaded and a
    cluster shared by several lanes of one tenant is stored once.

    Slab blocks are DENSELY PACKED: a resident cluster's rows are copied
    contiguously into its slots instead of mirroring whole plane blocks,
    so a cluster run that straddles a plane-block boundary occupies
    ``ceil(rows/block_rows)`` slab blocks (the plane needs up to one
    more). Each combined-space block therefore carries two per-GENERATION
    scalars, `block_gid0`/`block_count`: the global plane row id of its
    first row and the number of live rows. For plane blocks these are
    ``block * block_rows`` and `block_rows`; for slab blocks the cache
    writes them at fill time. The view's global row ids and pad masking
    are derived from these in-graph — which is what lets a fully-warm
    launch run at a NARROWER static table width than the plane table
    (fewer gathered rows per probe), the slab's real latency win.

    Bit-parity with the ClusterPolicy cascade holds even though the slab
    path runs a leaner schedule:

      * the gather skips the reference path's clamp + zero-row mask —
        every id in `slab_blocks` is pre-validated (holes are clamped to
        block 0 and ride the member mask, exactly like the cold path's
        candidate masking) and `slab_plane` is a whole number of blocks;
      * `inv_norms` is a per-generation f32 sidecar of the cosine key's
        ``rsqrt(max(norm, 1))`` factor (0 for empty rows), so stage 1
        multiplies instead of gathering int64 norms and re-deriving the
        rsqrt per launch — same f32 bits, computed once;
      * `packed_labels` fuses the arena's per-row (owner, cluster label)
        pair into one int32 (`packed_membership`), so the member mask is
        one gather + one compare — injective, hence bit-identical to the
        cold path's ``own == tenant & label == cluster`` conjunction;
      * `cluster_valid` is the host-precomputed (B, K) selection
        validity — the same ``first block >= 0`` bits the in-graph prune
        derives from the plane table, so selection cannot differ between
        table widths;
      * packing preserves each cluster's ascending row order and every
        pad/hole/foreign row is masked before both top-k stages, so the
        surviving candidates and their order — and therefore the final
        outputs — are bit-identical to the cold cascade.
    """

    packed_labels: jax.Array    # (N,) int32 packed (owner, label) rows
    tenant_ids: jax.Array       # (B,) int32
    centroid_msb: jax.Array     # (K, D//2) uint8
    centroid_norms: jax.Array   # (K,) int32
    cluster_valid: jax.Array    # (B, K) bool selection validity
    slab_blocks: jax.Array      # (B, K, W) int32 combined-space blocks
    block_gid0: jax.Array       # (NB + S,) int32 first global row per block
    block_count: jax.Array      # (NB + S,) int32 live rows per block
    slab_plane: jax.Array       # (N + S*br, D//2) uint8 plane + cache slab
    inv_norms: jax.Array        # (N + S*br,) f32 rsqrt-norm sidecar
    nprobe: int
    block_rows: int
    # Adaptive-precision sidecars (None when the runtime serves without a
    # stage-0 prescreen / precision tiers — the PR 5 schedule unchanged):
    # `sign_plane` is the combined 1-bit sign plane mirroring
    # `slab_plane`'s geometry row for row (the cache derives it from the
    # combined nibble plane — sign bits are a pure bit-extraction, see
    # bitplanar.sign_plane_from_msb — so full-tier slab rows carry live
    # sign bytes without a second fill pipeline). `block_tier` is the
    # per-slot PRECISION sidecar: tier of every combined-space block
    # (0 = arena plane block, 1 = sign-tier resident — sign bytes
    # on-chip, nibble bytes still streamed from the plane, 2 = full-tier
    # slab block — both planes cache-resident). The in-graph cascade
    # reads `sign_plane`; `block_tier` feeds the runtime's exact
    # per-stage hit/miss byte ledger and the bench's tier assertions.
    sign_plane: jax.Array | None = None
    block_tier: jax.Array | None = None


jax.tree_util.register_pytree_node(
    PlainPolicy, lambda p: ((), None), lambda _, l: PlainPolicy())
jax.tree_util.register_pytree_node(
    MaskedPolicy, lambda p: ((p.owner, p.tenant_ids), None),
    lambda _, l: MaskedPolicy(*l))
jax.tree_util.register_pytree_node(
    WindowedPolicy, lambda p: ((p.owner, p.tenant_ids, p.starts), p.window),
    lambda w, l: WindowedPolicy(*l, window=w))
jax.tree_util.register_pytree_node(
    ClusterPolicy,
    lambda p: ((p.owner, p.tenant_ids, p.labels, p.centroid_msb,
                p.centroid_norms, p.cluster_blocks),
               (p.nprobe, p.block_rows)),
    lambda aux, l: ClusterPolicy(*l, nprobe=aux[0], block_rows=aux[1]))
jax.tree_util.register_pytree_node(
    ViewPolicy, lambda p: ((p.rows, p.member, p.msb_rows), None),
    lambda _, l: ViewPolicy(*l))
jax.tree_util.register_pytree_node(
    SlabPolicy,
    lambda p: ((p.packed_labels, p.tenant_ids, p.centroid_msb,
                p.centroid_norms, p.cluster_valid, p.slab_blocks,
                p.block_gid0, p.block_count, p.slab_plane, p.inv_norms,
                p.sign_plane, p.block_tier),
               (p.nprobe, p.block_rows)),
    lambda aux, l: SlabPolicy(*l[:10], nprobe=aux[0], block_rows=aux[1],
                              sign_plane=l[10], block_tier=l[11]))


def packed_membership(owner: jax.Array, labels: jax.Array,
                      num_clusters: int) -> jax.Array:
    """Fuse per-row (owner, cluster label) into one int32 sidecar.

    ``(owner + 1) * (K + 1) + (label + 1)`` — injective for owner >= -1
    and label in [-1, K), so ``packed[row] == (t + 1) * (K + 1) + c + 1``
    holds exactly when ``owner[row] == t and labels[row] == c``. Built
    once per arena generation by the serving cache; lets the slab
    cascade's member mask run as a single gather + compare."""
    k1 = num_clusters + 1
    return ((owner.astype(jnp.int32) + 1) * k1
            + labels.astype(jnp.int32) + 1)

Policy = (PlainPolicy | MaskedPolicy | WindowedPolicy | ClusterPolicy
          | ViewPolicy | SlabPolicy)


# ---------------------------------------------------------------------------
# Batched stage primitives (jnp reference backend; kernels mirror these)
# ---------------------------------------------------------------------------

def stage1_plane_batched_jnp(q_msb: jax.Array,
                             msb_plane: jax.Array) -> jax.Array:
    """Batched MSB-nibble MIPS over a shared plane: one true matmul.

    q_msb (B, D) int8 in [-8, 7]; msb_plane (N, D//2) packed uint8.
    Returns (B, N) int32. Split-query formulation as in stage1_scores_jnp:
    lo_signed . q_even + hi_signed . q_odd on the packed plane, so the
    (N, D) interleaved unpack is never materialized and the plane rows are
    read ONCE for the whole batch.
    """
    lo, hi = bitplanar.split_nibbles_signed(msb_plane)
    return (similarity.int_matmul(lo, q_msb[:, 0::2])
            + similarity.int_matmul(hi, q_msb[:, 1::2]))


def stage1_rows_batched_jnp(q_msb: jax.Array,
                            msb_rows: jax.Array) -> jax.Array:
    """Per-lane-rows stage 1 (the windowed policy's shape).

    q_msb (B, D) int8 nibbles; msb_rows (B, W, D//2) packed per-lane row
    blocks. Returns (B, W) int32 — lane i scores only its own rows.
    """
    lo, hi = bitplanar.split_nibbles_signed(msb_rows)
    return (similarity.int_bmm(lo, q_msb[:, 0::2])
            + similarity.int_bmm(hi, q_msb[:, 1::2]))


def stage1_gather_batched_jnp(q_msb: jax.Array, msb_plane: jax.Array,
                              block_ids: jax.Array, *,
                              block_rows: int) -> jax.Array:
    """Block-gathered stage 1 (the cluster prune's row view), reference.

    q_msb (B, D) int8 nibbles; msb_plane (N, D//2) packed; block_ids
    (B, J) int32 ids of `block_rows`-row plane blocks (already clamped to
    valid blocks — holes are masked downstream by the caller's member
    mask). Returns (B, J * block_rows) int32. Rows past the plane's end
    (a final partial block) score as zero rows — `bitplanar.gather_blocks`
    owns that convention, shared with the Pallas kernel's zero-padded
    plane, so the backends stay bit-equal even on the padding that
    masking later discards.
    """
    gathered, _ = bitplanar.gather_blocks(msb_plane, block_ids, block_rows)
    return stage1_rows_batched_jnp(q_msb, gathered)


def stage1_gather_resident_jnp(q_msb: jax.Array, plane: jax.Array,
                               block_ids: jax.Array, *,
                               block_rows: int) -> jax.Array:
    """Lean block-gathered stage 1 for PRE-VALIDATED ids (the slab path).

    Same contract as `stage1_gather_batched_jnp` minus the out-of-range
    convention: every id in `block_ids` must address a whole block of
    `plane` (the serving runtime guarantees this host-side — the arena
    is a block multiple and slab slots are always fully allocated), so
    the reference clamp + zero-row mask over the gathered (B, R, D//2)
    view is skipped. Bit-equal to the Pallas gather kernel, whose
    contract never included the clamp in the first place.
    """
    rows = bitplanar.expand_block_rows(block_ids, block_rows)
    return stage1_rows_batched_jnp(q_msb, jnp.take(plane, rows, axis=0))


def stage0_sign_plane_batched_jnp(q_sign: jax.Array,
                                  sign_plane: jax.Array) -> jax.Array:
    """Batched stage-0 sign-agreement scores over a shared sign plane.

    q_sign (B, D) int8 in {+1, -1}; sign_plane (N, D//8) packed uint8
    (bit k%8 of byte k//8 set == dim k negative). Returns (B, N) int32
    ``sum_k sign(q_k) * sign(d_k)`` — affinely equivalent to the XNOR-
    popcount agreement count (score = 2*agreement - D), so ranking by it
    IS ranking by popcount, in exact integer arithmetic on both backends.
    """
    docs = bitplanar.unpack_sign_pm1(sign_plane)               # (N, D) int8
    return similarity.int_matmul(docs, q_sign)


def stage0_sign_gather_batched_jnp(q_sign: jax.Array, sign_plane: jax.Array,
                                   block_ids: jax.Array, *,
                                   block_rows: int) -> jax.Array:
    """Block-gathered stage-0 sign scan (the prescreen's view), reference.

    Same gather convention as stage1_gather_batched_jnp: rows past the
    plane's end gather ZERO bytes, which unpack to all-(+1) rows scoring
    ``sum_k sign(q_k)`` — identical on both backends and masked
    downstream by membership (a sign score is never exposed unmasked).
    """
    gathered, _ = bitplanar.gather_blocks(sign_plane, block_ids, block_rows)
    return similarity.int_bmm(bitplanar.unpack_sign_pm1(gathered), q_sign)


def stage0_sign_gather_resident_jnp(q_sign: jax.Array, sign_plane: jax.Array,
                                    block_ids: jax.Array, *,
                                    block_rows: int) -> jax.Array:
    """Stage-0 gather over a PRE-VALIDATED combined sign plane (slab path):
    no clamp / zero-byte convention, mirroring stage1_gather_resident_jnp.
    """
    rows = bitplanar.expand_block_rows(block_ids, block_rows)
    docs = bitplanar.unpack_sign_pm1(jnp.take(sign_plane, rows, axis=0))
    return similarity.int_bmm(docs, q_sign)


def stage2_rows_batched_jnp(q: jax.Array, msb_rows: jax.Array,
                            lsb_rows: jax.Array) -> jax.Array:
    """Exact INT8 rescoring of gathered per-lane candidate rows.

    q (B, D) int8; msb_rows/lsb_rows (B, C, D//2) uint8. Returns (B, C).
    """
    bsz, c, d2 = msb_rows.shape
    docs = bitplanar.reconstruct_int8(msb_rows.reshape(bsz * c, d2),
                                      lsb_rows.reshape(bsz * c, d2))
    return similarity.int_bmm(docs.reshape(bsz, c, 2 * d2), q)


@dataclasses.dataclass(frozen=True)
class StageFns:
    """The cascade's batched primitives for one backend.

    plane:    stage-1 shared-plane matmul            (B, D) x (N, D/2)
    rows:     stage-1 per-lane materialized rows     (B, D) x (B, W, D/2)
    gather:   stage-1 per-lane block gather          (B, D) x plane + ids
    gather_resident: the gather over PRE-VALIDATED block ids (the slab
              path: no clamp / zero-row convention — the Pallas kernel
              unchanged, the jnp reference without the mask)
    centroid: stage-0 codebook scoring (the codebook is a nibble plane,
              so this is the plane matmul applied to (K, D/2))
    exact:    stage-2 INT8 rescore of gathered candidates
    sign_gather / sign_gather_resident: the 1-bit sign-plane prescreen's
              block gathers, mirroring gather / gather_resident over the
              packed (N, D/8) sign plane — XNOR-popcount agreement in its
              monotone ±1-dot form
    """

    plane: object
    rows: object
    gather: object
    gather_resident: object
    centroid: object
    exact: object
    sign_gather: object
    sign_gather_resident: object


def stage_fns(backend: str | None) -> StageFns:
    """The primitives for `backend`; None picks by platform (the Pallas
    kernels on a TPU, the jnp reference elsewhere)."""
    from repro.kernels.platform import resolve_backend
    if resolve_backend(backend) == "pallas":
        from repro.kernels import ops as kops

        def _sign_gather_k(q_sign, sign_plane, block_ids, block_rows):
            return kops.stage0_sign_scores_gather(q_sign, sign_plane,
                                                  block_ids,
                                                  block_rows=block_rows)

        def _sign_gather_resident_k(q_sign, sign_plane, block_ids,
                                    block_rows):
            return kops.stage0_sign_scores_gather_resident(
                q_sign, sign_plane, block_ids, block_rows=block_rows)

        return StageFns(plane=kops.stage1_scores_batched,
                        rows=kops.stage1_scores_rows,
                        gather=kops.stage1_scores_gather,
                        gather_resident=kops.stage1_scores_gather_resident,
                        centroid=kops.centroid_scores_batched,
                        exact=kops.stage2_scores_batched,
                        sign_gather=_sign_gather_k,
                        sign_gather_resident=_sign_gather_resident_k)

    def _gather(q_msb, plane, block_ids, block_rows):
        return stage1_gather_batched_jnp(q_msb, plane, block_ids,
                                         block_rows=block_rows)

    def _gather_resident(q_msb, plane, block_ids, block_rows):
        return stage1_gather_resident_jnp(q_msb, plane, block_ids,
                                          block_rows=block_rows)

    def _sign_gather(q_sign, sign_plane, block_ids, block_rows):
        return stage0_sign_gather_batched_jnp(q_sign, sign_plane, block_ids,
                                              block_rows=block_rows)

    def _sign_gather_resident(q_sign, sign_plane, block_ids, block_rows):
        return stage0_sign_gather_resident_jnp(q_sign, sign_plane,
                                               block_ids,
                                               block_rows=block_rows)

    return StageFns(plane=stage1_plane_batched_jnp,
                    rows=stage1_rows_batched_jnp,
                    gather=_gather,
                    gather_resident=_gather_resident,
                    centroid=stage1_plane_batched_jnp,
                    exact=stage2_rows_batched_jnp,
                    sign_gather=_sign_gather,
                    sign_gather_resident=_sign_gather_resident)


# ---------------------------------------------------------------------------
# The cascade schedule
# ---------------------------------------------------------------------------

def _vslice(arr: jax.Array, starts: jax.Array, window: int) -> jax.Array:
    """Per-lane dynamic windows: (N, ...) x (B,) starts -> (B, window, ...)."""
    return jax.vmap(
        lambda s: jax.lax.dynamic_slice_in_dim(arr, s, window, 0))(starts)


def _candidate_budget(cfg: RetrievalConfig, num_docs: int,
                      view_rows: int | None) -> int:
    """Stage-2 budget C (the single source both the schedule and `plan`
    use). A restricted view's budget is the SAME as the full-scan one —
    clamped to the view (window or gathered probe rows), in which case
    every visible row is a candidate and the view is rescored
    exhaustively — so results never depend on which code path the arena's
    layout state selects."""
    c = cfg.num_candidates(num_docs)
    if view_rows is not None:
        c = min(c, view_rows)
    return c


def probe_rows(policy: "ClusterPolicy | SlabPolicy") -> int:
    """Static per-lane row count of the cluster policy's gathered view."""
    table = (policy.slab_blocks if isinstance(policy, SlabPolicy)
             else policy.cluster_blocks)
    return min(policy.nprobe,
               policy.centroid_msb.shape[0]) * table.shape[-1] \
        * policy.block_rows


@dataclasses.dataclass
class _CascadeState:
    """The currency cascade stages refine: WHICH rows are still alive.

    rows:   (B, R) explicit global row ids of the current view (-1 holes;
            the slab path clamps holes instead and lets `member` carry
            them), or None when the view is implicit (plane / window).
    member: visibility mask aligned with the view (None = all visible).
    block_ids: (B, J) clamped block ids backing `rows` when the view is a
            block gather (the scalar-prefetch kernel's operand; combined
            plane+slab space under a SlabPolicy).
    comb_rows: (B, R) COMBINED plane+slab row ids aligned with `rows`,
            set by the sign prescreen under a SlabPolicy (where `rows`
            holds arena-global ids but stage 1 must keep gathering from
            the combined array so hits stay physically on the slab).
    top_clusters: (B, nprobe) selected cluster ids when a centroid prune
            ran (the serving runtime reads this back for its cache
            ledger — selection itself stays in-graph).
    result: the final RetrievalResult, set by the terminal stage.
    """

    rows: jax.Array | None = None
    member: jax.Array | None = None
    block_ids: jax.Array | None = None
    comb_rows: jax.Array | None = None
    top_clusters: jax.Array | None = None
    result: RetrievalResult | None = None


@dataclasses.dataclass
class _CascadeCtx:
    """Per-launch invariants every stage reads.

    q_sign is the (B, D) ±1 sign view of the query codes (0 maps to +1,
    matching the packed sign plane's zero-byte convention) — computed
    only when the config enables the stage-0 prescreen, else None.
    """

    query_codes: jax.Array
    q_msb: jax.Array
    db: bitplanar.BitPlanarDB
    policy: Policy
    cfg: RetrievalConfig
    fns: StageFns
    q_sign: jax.Array | None = None


def select_clusters(q_msb: jax.Array, policy: "ClusterPolicy | SlabPolicy",
                    cfg: RetrievalConfig, fns: StageFns) -> jax.Array:
    """Stage 0's cluster selection: score the K centroids and keep each
    lane's top-`nprobe` VALID clusters (a cluster with no blocks for the
    lane's tenant must not spend a probe: its first block id is -1).

    Returns (B, nprobe) int32 cluster ids in rank order. Shared between
    the in-graph CentroidPrune stage and the serving runtime's host-side
    hot-cluster-cache path, so the two can never select differently.
    """
    k_clusters = policy.centroid_msb.shape[0]
    nprobe = min(policy.nprobe, k_clusters)
    scores = fns.centroid(q_msb, policy.centroid_msb)            # (B, K)
    if isinstance(policy, SlabPolicy):
        # Host-precomputed from the same plane table (first block >= 0):
        # identical bits at any launch table width.
        valid = policy.cluster_valid
    else:
        table = policy.cluster_blocks
        if table.ndim == 2:
            valid = (table[:, 0] >= 0)[None, :]
        else:
            valid = table[:, :, 0] >= 0
    if cfg.metric == "cosine":
        key = similarity.cosine_key_f32(scores, policy.centroid_norms)
        key = jnp.where(valid, key, -jnp.inf)
    else:
        key = jnp.where(valid, scores, INT32_MIN)
    _, top_clusters = jax.lax.top_k(key, nprobe)                 # (B, P)
    return top_clusters


def expand_cluster_view(policy: ClusterPolicy, top_clusters: jax.Array,
                        num_docs: int
                        ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Expand selected clusters' blocks into an explicit per-lane row view.

    Returns (rows (B, R) int32 with -1 holes, member (B, R) bool,
    clamped_block_ids (B, J) int32) — the currency ApproxScan's gather
    branch consumes. Shared with the serving runtime so a cached view's
    bookkeeping is the in-graph prune's bookkeeping, by construction.
    """
    pol, n = policy, num_docs
    table = pol.cluster_blocks
    if table.ndim == 2:
        blocks = jnp.take(table, top_clusters, axis=0)           # (B, P, MB)
    else:
        blocks = jnp.take_along_axis(
            table, top_clusters[:, :, None], axis=1)
    b, _, max_blocks = blocks.shape
    blocks = blocks.reshape(b, -1)                               # (B, J)
    br = pol.block_rows
    clamped = jnp.maximum(blocks, 0)
    # Row ids come from the SAME expansion the gather backends use
    # (bitplanar.expand_block_rows), so the prune's bookkeeping can
    # never desynchronize from what stage 1 actually streams.
    rows = bitplanar.expand_block_rows(clamped, br)
    hole = jnp.repeat(blocks < 0, br, axis=1) | (rows >= n)
    rows = jnp.where(hole, -1, rows)
    safe = jnp.maximum(rows, 0)
    own = jnp.take(pol.owner, safe, axis=0)
    # A block at a cluster boundary is listed under BOTH clusters; a
    # row is kept only through its OWN cluster's entry, so a row can
    # never appear twice in the view (duplicates would waste candidate
    # slots and could surface one doc twice in the final top-k).
    owning = jnp.repeat(jnp.repeat(top_clusters, max_blocks, axis=1),
                        br, axis=1)                              # (B, R)
    member = (~hole & (own == pol.tenant_ids[:, None])
              & (pol.tenant_ids >= 0)[:, None]
              & (jnp.take(pol.labels, safe, axis=0) == owning))
    return rows, member, clamped


def expand_slab_view(policy: SlabPolicy, top_clusters: jax.Array
                     ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """The slab path's lean expansion of the selected clusters.

    Returns (rows (B, R) int32 CLAMPED global plane row ids — holes and
    pads point at in-range rows and ride the member mask instead of a -1
    marking, member (B, R) bool, comb_ids (B, J) int32 clamped
    COMBINED-space block ids for the gather). Row ids are derived from
    the per-block `block_gid0`/`block_count` origin scalars, so the same
    code serves both whole-plane-block mirrors (gid0 = block *
    block_rows, count = block_rows — bitwise the cold path's expansion)
    and densely packed slab blocks (gid0 = the run row the block starts
    at, count < block_rows on the tail block, pads masked by `count`).
    The final outputs are sanitized by ExactRescore's member masking, so
    the -1 row marking is redundant work; parity with the cold cascade
    is pinned by tests on both backends.
    """
    pol = policy
    comb = jnp.take_along_axis(pol.slab_blocks,
                               top_clusters[:, :, None], axis=1)
    b, _, w = comb.shape
    comb = comb.reshape(b, -1)                                   # (B, J)
    br = pol.block_rows
    hole = comb < 0
    safe_blk = jnp.maximum(comb, 0)
    gid0 = jnp.take(pol.block_gid0, safe_blk, axis=0)            # (B, J)
    cnt = jnp.take(pol.block_count, safe_blk, axis=0)            # (B, J)
    offs = jnp.arange(br, dtype=jnp.int32)
    rows = (gid0[:, :, None] + offs[None, None, :]).reshape(b, -1)
    live = (offs[None, None, :] < cnt[:, :, None]).reshape(b, -1)
    n = pol.packed_labels.shape[0]
    rows = jnp.minimum(rows, n - 1)      # tail pads stay gatherable
    owning = jnp.repeat(jnp.repeat(top_clusters, w, axis=1),
                        br, axis=1)                              # (B, R)
    k1 = pol.centroid_msb.shape[0] + 1
    expected = (pol.tenant_ids[:, None] + 1) * k1 + owning + 1
    member = (~jnp.repeat(hole, br, axis=1) & live
              & (jnp.take(pol.packed_labels, rows, axis=0) == expected)
              & (pol.tenant_ids >= 0)[:, None])
    return rows, member, safe_blk


@dataclasses.dataclass(frozen=True)
class CentroidPrune:
    """Stage 0: score the K centroids, keep the top-`nprobe` clusters'
    blocks, and expand them into an explicit per-lane row view."""

    nprobe: int

    def run(self, state: _CascadeState, ctx: _CascadeCtx) -> _CascadeState:
        top_clusters = select_clusters(ctx.q_msb, ctx.policy, ctx.cfg,
                                       ctx.fns)
        if isinstance(ctx.policy, SlabPolicy):
            rows, member, comb = expand_slab_view(ctx.policy, top_clusters)
            return dataclasses.replace(state, rows=rows, member=member,
                                       block_ids=comb,
                                       top_clusters=top_clusters)
        rows, member, clamped = expand_cluster_view(ctx.policy, top_clusters,
                                                    ctx.db.num_docs)
        return dataclasses.replace(state, rows=rows, member=member,
                                   block_ids=clamped,
                                   top_clusters=top_clusters)


@dataclasses.dataclass(frozen=True)
class SignPrescreen:
    """Stage 0.5: 1-bit sign-agreement prescreen of the pruned row view.

    Streams only the packed SIGN plane (D/8 bytes per row — 4x fewer
    than the nibble plane) over the centroid prune's gathered view,
    scores sign agreement (±1 dot == 2*popcount(XNOR) - D, monotone-
    equivalent), and keeps each lane's top-`c0` members — so the INT4
    ApproxScan that follows gathers C0 rows instead of the full probe
    view. Two invariants make this safe and testable:

      * survivors are re-sorted into VIEW ORDER (`jnp.sort` on the
        selected view-local indices after top_k): the prescreen only
        DELETES rows from the view, it never reorders it, so at
        c0 >= view_rows the output view is the identity permutation of
        the input and the whole cascade is bit-identical to the
        no-prescreen schedule — the parity anchor the tests pin;
      * non-members (holes, pads, foreign tenants, tombstones) score
        INT32_MIN before the top_k, so with c0 >= k a lane with >= k
        live members can never lose one to a masked row — masked rows
        are only selected when there aren't c0 members at all, and then
        they still carry member=False into both downstream top-ks.

    Under a SlabPolicy the sign bytes stream from the COMBINED sign
    plane (hot clusters' sign rows live on-chip next to their nibble
    slab rows), and the surviving combined row ids are forwarded as
    `comb_rows` so stage 1's per-row gather keeps reading hits from the
    slab region rather than re-streaming the arena plane.
    """

    c0: int

    def run(self, state: _CascadeState, ctx: _CascadeCtx) -> _CascadeState:
        policy, cfg = ctx.policy, ctx.cfg
        r = state.rows.shape[1]
        c0 = cfg.prescreen_budget(r)
        comb_rows = None
        if isinstance(policy, SlabPolicy):
            sign_plane = policy.sign_plane
            if sign_plane is None:
                # Runtime didn't pre-derive the combined sign plane:
                # extract it from the combined nibble plane in-graph
                # (pure bit math — identical bytes, see bitplanar).
                sign_plane = bitplanar.sign_plane_from_msb(policy.slab_plane)
            scores = ctx.fns.sign_gather_resident(
                ctx.q_sign, sign_plane, state.block_ids,
                block_rows=policy.block_rows)
            comb_rows = bitplanar.expand_block_rows(state.block_ids,
                                                    policy.block_rows)
        else:
            sign_plane = ctx.db.sign_plane
            if sign_plane is None:
                sign_plane = bitplanar.sign_plane_from_msb(ctx.db.msb_plane)
            scores = ctx.fns.sign_gather(ctx.q_sign, sign_plane,
                                         state.block_ids,
                                         block_rows=policy.block_rows)
        key0 = jnp.where(state.member, scores, INT32_MIN)
        _, sel = jax.lax.top_k(key0, c0)                       # (B, C0)
        sel = jnp.sort(sel, axis=1)      # survivors keep view order
        rows = jnp.take_along_axis(state.rows, sel, axis=1)
        member = jnp.take_along_axis(state.member, sel, axis=1)
        if comb_rows is not None:
            comb_rows = jnp.take_along_axis(comb_rows, sel, axis=1)
        return dataclasses.replace(state, rows=rows, member=member,
                                   block_ids=None, comb_rows=comb_rows)


@dataclasses.dataclass(frozen=True)
class ApproxScan:
    """Stage 1: batched INT4 MSB scan over the surviving row view, then
    per-lane candidate top-C (the approximate-retrieval stage)."""

    def run(self, state: _CascadeState, ctx: _CascadeCtx) -> _CascadeState:
        db, policy, cfg = ctx.db, ctx.policy, ctx.cfg
        n = db.num_docs
        member = state.member
        view_rows = state.rows          # view-local -> global row id map
        key1 = None                     # set directly by the slab branch
        if isinstance(policy, SlabPolicy):
            # Slab-sourced gather (the serving runtime's cached path):
            # one lean block gather over the combined plane+slab array —
            # hits stream from the cache region, misses from the plane,
            # neither is clamped or zero-masked (ids are pre-validated
            # host-side). The cosine key multiplies the per-generation
            # f32 rsqrt-norm sidecar instead of gathering int64 norms:
            # same f32 bits as cosine_key_f32 on the gathered norms (the
            # trailing + 0.0 canonicalizes the sidecar's masked-zero rows
            # to the reference's literal +0.0).
            r = state.rows.shape[1]
            if r < cfg.k:
                raise ValueError(f"slab view holds {r} rows < k="
                                 f"{cfg.k}: raise nprobe or block_rows")
            c = _candidate_budget(cfg, n, r)
            if state.block_ids is not None:
                scores = ctx.fns.gather_resident(
                    ctx.q_msb, policy.slab_plane, state.block_ids,
                    block_rows=policy.block_rows)
                comb_rows = bitplanar.expand_block_rows(state.block_ids,
                                                        policy.block_rows)
            else:
                # Prescreened view: survivors arrive as combined-space
                # row ids — gather their nibble rows by ROW from the
                # combined array (hot clusters' survivors still read the
                # slab region, cold survivors the plane) and score with
                # the per-lane rows primitive. Same plane bytes as the
                # block gather at the surviving positions, so the
                # c0 >= view_rows anchor stays bit-identical.
                comb_rows = state.comb_rows
                msb_rows = jnp.take(policy.slab_plane, comb_rows, axis=0)
                scores = ctx.fns.rows(ctx.q_msb, msb_rows)
            if cfg.metric == "cosine":
                key1 = (scores.astype(jnp.float32)
                        * jnp.take(policy.inv_norms, comb_rows, axis=0)
                        + 0.0)
                key1 = jnp.where(member, key1, -jnp.inf)
            else:
                key1 = jnp.where(member, scores, INT32_MIN)
            base = None
        elif isinstance(policy, ViewPolicy):
            # Materialized view (the serving runtime's cache path): the
            # rows arrive as data — stage 1 runs the per-lane rows
            # primitive over them; norms stay tiny sidecar reads from the
            # full array, exactly like the gathered branch.
            r = policy.rows.shape[1]
            if r < cfg.k:
                raise ValueError(f"materialized view holds {r} rows < k="
                                 f"{cfg.k}: raise nprobe or block_rows")
            c = _candidate_budget(cfg, n, r)
            scores = ctx.fns.rows(ctx.q_msb, policy.msb_rows)  # (B, R) int32
            norms = jnp.take(db.norms_sq, jnp.maximum(policy.rows, 0),
                             axis=0)
            member = policy.member
            view_rows = policy.rows
            base = None
        elif isinstance(policy, WindowedPolicy):
            if policy.window < cfg.k:
                raise ValueError(f"window {policy.window} < k={cfg.k}: "
                                 "top-k over a window needs window >= k")
            c = _candidate_budget(cfg, n, policy.window)
            starts = jnp.clip(policy.starts, 0,
                              max(n - policy.window, 0)).astype(jnp.int32)
            msb_view = _vslice(db.msb_plane, starts, policy.window)
            norms = _vslice(db.norms_sq, starts, policy.window)
            owner_view = _vslice(policy.owner, starts, policy.window)
            member = ((owner_view == policy.tenant_ids[:, None])
                      & (policy.tenant_ids >= 0)[:, None])
            scores = ctx.fns.rows(ctx.q_msb, msb_view)         # (B, W) int32
            base = starts[:, None]
        elif state.rows is not None:
            # Gathered view (the centroid prune's output): stream only the
            # selected blocks. `rows` maps view-local -> global slot ids.
            r = state.rows.shape[1]
            if r < cfg.k:
                raise ValueError(f"gathered view holds {r} rows < k="
                                 f"{cfg.k}: raise nprobe or block_rows")
            c = _candidate_budget(cfg, n, r)
            if state.block_ids is not None:
                scores = ctx.fns.gather(ctx.q_msb, db.msb_plane,
                                        state.block_ids,
                                        block_rows=policy.block_rows)
            else:
                # Prescreened cluster view: survivors are global row ids
                # (-1 holes clamp to row 0 and ride the member mask; the
                # raw score at a masked position may differ from the
                # block-gather path's zero-row convention — the masked
                # KEY below is identical, which is what parity pins).
                msb_rows = jnp.take(db.msb_plane,
                                    jnp.maximum(state.rows, 0), axis=0)
                scores = ctx.fns.rows(ctx.q_msb, msb_rows)
            norms = jnp.take(db.norms_sq, jnp.maximum(state.rows, 0),
                             axis=0)
            base = None
        else:
            c = _candidate_budget(cfg, n, None)
            scores = ctx.fns.plane(ctx.q_msb, db.msb_plane)    # (B, N) int32
            norms = db.norms_sq[None, :]
            if isinstance(policy, MaskedPolicy):
                member = ((policy.owner[None, :]
                           == policy.tenant_ids[:, None])
                          & (policy.tenant_ids >= 0)[:, None])
            base = None

        if key1 is None and cfg.metric == "cosine":
            # Approximate cosine key; norms are tiny sidecar reads (the
            # paper stores doc norms in DRAM alongside the planes).
            # Tombstoned rows carry norm 0 (key 0), so even an
            # inconsistent membership mask cannot let a dead row win.
            key1 = similarity.cosine_key_f32(scores, norms)
            if member is not None:
                key1 = jnp.where(member, key1, -jnp.inf)
        elif key1 is None:
            key1 = scores if member is None else jnp.where(member, scores,
                                                           INT32_MIN)
        _, cand_local = jax.lax.top_k(key1, c)                 # (B, C) view
        if view_rows is not None:
            cand = jnp.take_along_axis(view_rows, cand_local, axis=1)
        elif base is not None:
            cand = cand_local + base
        else:
            cand = cand_local
        cand_member = (None if member is None else
                       jnp.take_along_axis(member, cand_local, axis=1))
        return dataclasses.replace(state, rows=cand, member=cand_member,
                                   block_ids=None)


@dataclasses.dataclass(frozen=True)
class ExactRescore:
    """Terminal stage: batched gather of the candidates' full INT8 codes,
    exact rescore, metric rerank (non-division comparator for cosine,
    top-k for MIPS)."""

    def run(self, state: _CascadeState, ctx: _CascadeCtx) -> _CascadeState:
        db, cfg = ctx.db, ctx.cfg
        cand, cand_member = state.rows, state.member
        # Candidate rows are gathered from the FULL planes by global id,
        # so the LSB plane is never sliced and restricted views re-read
        # only C rows. Holes (-1) clamp to row 0 and are pinned below
        # every real candidate by the membership mask.
        safe = jnp.maximum(cand, 0)
        msb_rows = jnp.take(db.msb_plane, safe, axis=0)        # (B, C, D//2)
        lsb_rows = jnp.take(db.lsb_plane, safe, axis=0)
        exact = ctx.fns.exact(ctx.query_codes, msb_rows, lsb_rows)
        cand_norms = jnp.take(db.norms_sq, safe, axis=0)
        if cand_member is not None:
            # Out-of-segment candidates pin to (MASKED_SCORE, 1) so the
            # integer rerank comparator ranks them below every in-segment
            # candidate.
            exact = jnp.where(cand_member, exact, MASKED_SCORE)
            cand_norms = jnp.where(cand_member, cand_norms, 1)

        if cfg.metric == "cosine":
            local, top_scores = jax.vmap(
                lambda s, nn: similarity.rerank_dense_comparator(s, nn,
                                                                 cfg.k)
            )(exact, cand_norms)
        else:
            top_scores, local = jax.lax.top_k(exact, cfg.k)

        indices = jnp.take_along_axis(cand, local, axis=1)
        if cand_member is None:
            result = RetrievalResult(indices=indices, scores=top_scores,
                                     candidate_indices=cand)
        else:
            valid = jnp.take_along_axis(cand_member, local, axis=1)
            result = RetrievalResult(
                indices=jnp.where(valid, indices, -1),
                scores=jnp.where(valid, top_scores, 0),
                candidate_indices=jnp.where(cand_member, cand, -1))
        return dataclasses.replace(state, result=result)


def cascade_stages(policy: Policy, cfg: RetrievalConfig) -> tuple:
    """The stage specs one launch will run, selected by policy type.

    The two-stage scheme is the 2-element cascade; the cluster-pruned
    path prepends the centroid prune. Future stages (e.g. a binary-sketch
    pre-prune between prune and scan) slot in here.
    """
    if isinstance(policy, (ClusterPolicy, SlabPolicy)):
        head: tuple = (CentroidPrune(policy.nprobe),)
        if cfg.prescreen_c0 is not None:
            # The adaptive-precision cascade: a 1-bit sign-plane
            # prescreen thins the pruned view before the INT4 scan.
            head += (SignPrescreen(cfg.prescreen_c0),)
        return head + (ApproxScan(), ExactRescore())
    # ViewPolicy enters at ApproxScan: its prune already ran host-side
    # and the view arrives as data.
    return (ApproxScan(), ExactRescore())


def _run_cascade(query_codes: jax.Array, db: bitplanar.BitPlanarDB,
                 policy: Policy, cfg: RetrievalConfig) -> _CascadeState:
    q_sign = (bitplanar.sign_pm1(query_codes)
              if cfg.prescreen_c0 is not None else None)
    ctx = _CascadeCtx(query_codes=query_codes,
                      q_msb=quantization.msb_nibble(query_codes),
                      db=db, policy=policy, cfg=cfg,
                      fns=stage_fns(cfg.backend), q_sign=q_sign)
    state = _CascadeState()
    for stage in cascade_stages(policy, cfg):
        # named scopes are metadata: they name the stage's ops in the
        # compiled program and its profile, and change no computation
        with jax.named_scope("cascade." + type(stage).__name__):
            state = stage.run(state, ctx)
    return state


def _cascade_batched(query_codes: jax.Array, db: bitplanar.BitPlanarDB,
                     policy: Policy, cfg: RetrievalConfig
                     ) -> RetrievalResult:
    """The one batched cascade driver every retrieval variant runs.

    query_codes: (B, D) int8. Returns a batched RetrievalResult whose
    indices are global row/slot ids (-1 for lanes' unfillable positions
    under masking policies).
    """
    return _run_cascade(query_codes, db, policy, cfg).result


def _cascade_batched_aux(query_codes: jax.Array, db: bitplanar.BitPlanarDB,
                         policy: Policy, cfg: RetrievalConfig
                         ) -> tuple[RetrievalResult, jax.Array | None]:
    """The cascade plus its selection as an auxiliary output.

    Returns (result, top_clusters) — top_clusters is the (B, nprobe)
    int32 output of the in-graph CentroidPrune (None for policies without
    a prune stage). The serving runtime reads this tiny array back after
    a cached launch to maintain its slot map and hit/miss ledger, instead
    of re-running selection host-side."""
    state = _run_cascade(query_codes, db, policy, cfg)
    return state.result, state.top_clusters


retrieve_batched = jax.jit(_cascade_batched, static_argnames=("cfg",))
retrieve_batched_aux = jax.jit(_cascade_batched_aux, static_argnames=("cfg",))


# ---------------------------------------------------------------------------
# Schedule planning (host-side, analytic — the paper's bytes currency)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class StagePlan:
    """One cascade stage's exact analytic ledger for one batched launch.

    rows is per LANE (what one query's schedule scores); bytes_hbm is the
    total plane bytes the LAUNCH streams from HBM for this stage (shared-
    plane stages stream once per batch, per-lane views scale with B);
    bytes_sram is the plane bytes the launch served from ON-CHIP memory
    instead — the hot-cluster cache's hits, charged at SRAM rates by
    energy.cost_cascade (the rows still flow through the PEs: MAC counts
    are unchanged, only the fetch got cheaper); bits is the operand width
    of the stage's MACs; compares is the per-lane comparison count the
    stage's select/rerank performs.
    """

    name: str
    rows: int
    bits: int
    bytes_hbm: int
    compares: int
    bytes_sram: int = 0


@dataclasses.dataclass(frozen=True)
class SchedulePlan:
    """What one batched launch will stream, computed exactly (no timers).

    `stages` is the per-stage ledger (prune/approx/exact for the cluster
    cascade, approx/exact for the two-stage kinds) — the measured-counts
    feed for energy.cost_cascade. The flat stage1_* / stage2_* fields are
    the approx/exact stages' totals, kept because schedulers and serving
    ledgers read them directly: stage1_bytes is the batched engine's
    doc-plane traffic (for the plane-scan policies the plane is streamed
    ONCE per batch, so it does not scale with `batch`);
    stage1_bytes_vmapped is what the old one-query-at-a-time full-scan
    path streamed for the same work.
    """

    kind: Literal["plain", "masked", "windowed", "cluster", "view", "decode"]
    batch: int
    rows_scanned: int          # stage-1 rows per lane (N, window, or probe)
    candidates: int            # stage-2 budget C per lane
    stage1_bytes: int          # batched kernel: MSB-plane bytes from HBM
    stage1_bytes_vmapped: int  # the vmapped-scalar path, for comparison
    stage2_bytes: int          # gathered candidate rows (MSB+LSB planes)
    stages: tuple[StagePlan, ...] = ()
    stage1_bytes_sram: int = 0  # stage-1 bytes served from the hot cache

    def publish(self, registry) -> None:
        """Fan this launch's per-stage ledger out to a metrics registry.

        Duck-typed against repro.obs.MetricsRegistry (counter(name,
        **labels).inc(v)); a no-op for disabled registries. Host-side
        arithmetic over already-computed ints — never called from jitted
        code."""
        if not getattr(registry, "enabled", False):
            return
        for st in self.stages:
            registry.counter("stage_rows", stage=st.name).inc(
                st.rows * self.batch)
            registry.counter("stage_bytes_hbm", stage=st.name).inc(
                st.bytes_hbm)
            if st.bytes_sram:
                registry.counter("stage_bytes_sram", stage=st.name).inc(
                    st.bytes_sram)
            registry.counter("stage_compares", stage=st.name).inc(
                st.compares * self.batch)


def plan(cfg: RetrievalConfig, *, num_docs: int, dim: int, batch: int,
         kind: str = "plain", window: int | None = None,
         num_clusters: int | None = None,
         view_rows: int | None = None) -> SchedulePlan:
    """Analytic schedule for one launch of the engine.

    For "plain"/"masked" every lane scans the shared plane: the batched
    matmul kernel fetches each plane block once per BATCH (bytes = N*D/2),
    while the vmapped-scalar path fetched it once per QUERY (B*N*D/2).
    For "windowed" each lane streams its own window, so bytes scale with B
    either way — the win there is one launch + per-tenant work only.
    For "cluster" each lane streams only its `view_rows` gathered probe
    rows (O(N * nprobe / num_clusters) instead of O(N)) after a stage-0
    pass over the `num_clusters`-row centroid plane (streamed once per
    batch — the codebook is tiny and resident).
    """
    d2 = dim // 2
    if kind == "windowed":
        if window is None:
            raise ValueError("windowed plan needs a window")
        rows = min(window, num_docs)
        s1 = batch * rows * d2
        s1_vmapped = s1
        c = _candidate_budget(cfg, num_docs, window)
        stages = ()
    elif kind == "cluster":
        if num_clusters is None or view_rows is None:
            raise ValueError("cluster plan needs num_clusters and view_rows")
        rows = view_rows
        s1 = batch * rows * d2
        s1_vmapped = batch * num_docs * d2     # old path: full scan per query
        c = _candidate_budget(cfg, num_docs, view_rows)
        stages = (StagePlan(name="prune", rows=num_clusters, bits=4,
                            bytes_hbm=num_clusters * d2,
                            compares=num_clusters),)
        c0 = cfg.prescreen_budget(view_rows)
        if c0 is not None:
            # Stage-0 sign prescreen: streams the 1-bit sign plane over
            # the whole probe view (D/8 bytes/row, per lane), then the
            # INT4 approx stage gathers only the C0 survivors.
            stages += (StagePlan(name="prescreen", rows=view_rows, bits=1,
                                 bytes_hbm=batch * view_rows * (dim // 8),
                                 compares=view_rows),)
            rows = c0
            s1 = batch * c0 * d2
            c = _candidate_budget(cfg, num_docs, c0)
    elif kind == "view":
        # A materialized per-lane view (the runtime's cache path): same
        # stage-1 geometry as "cluster" but the prune ran host-side.
        if view_rows is None:
            raise ValueError("view plan needs view_rows")
        rows = view_rows
        s1 = batch * rows * d2
        s1_vmapped = batch * num_docs * d2
        c = _candidate_budget(cfg, num_docs, view_rows)
        stages = ()
    else:
        if window is not None:
            raise ValueError(f"{kind} plan does not take a window")
        rows = num_docs
        s1 = rows * d2
        s1_vmapped = batch * s1
        c = _candidate_budget(cfg, num_docs, None)
        stages = ()
    s2 = batch * c * dim
    stages += (StagePlan(name="approx", rows=rows, bits=4, bytes_hbm=s1,
                         compares=rows),
               StagePlan(name="exact", rows=c, bits=8, bytes_hbm=s2,
                         compares=c * c))
    return SchedulePlan(kind=kind, batch=batch, rows_scanned=rows,
                        candidates=c, stage1_bytes=s1,
                        stage1_bytes_vmapped=s1_vmapped,
                        stage2_bytes=s2, stages=stages)


def cache_split_plan(base: SchedulePlan, *, hbm_bytes: int,
                     sram_bytes: int,
                     prescreen_hbm: int | None = None,
                     prescreen_sram: int = 0) -> SchedulePlan:
    """Re-ledger a launch's approx stage for hot-cluster-cache service.

    The analytic plan charges the whole stage-1 view to HBM; when the
    serving runtime assembled the view partly from cached cluster slices,
    the MEASURED split is hbm_bytes (missed clusters, freshly streamed)
    vs sram_bytes (hits, served from on-chip cache). MAC/compare counts
    are untouched — the cache changes where bytes come from, not how many
    rows are scored. With the sign prescreen enabled the runtime also
    measures the stage-0 split (`prescreen_hbm`/`prescreen_sram` — sign
    bytes of resident clusters, any tier, serve on-chip); None leaves the
    analytic prescreen ledger untouched."""
    def _rewrite(s: StagePlan) -> StagePlan:
        if s.name == "approx":
            return dataclasses.replace(s, bytes_hbm=hbm_bytes,
                                       bytes_sram=sram_bytes)
        if s.name == "prescreen" and prescreen_hbm is not None:
            return dataclasses.replace(s, bytes_hbm=prescreen_hbm,
                                       bytes_sram=prescreen_sram)
        return s
    stages = tuple(_rewrite(s) for s in base.stages)
    return dataclasses.replace(base, stages=stages, stage1_bytes=hbm_bytes,
                               stage1_bytes_sram=sram_bytes)


# ---------------------------------------------------------------------------
# The engine facade
# ---------------------------------------------------------------------------

def _lane(res: RetrievalResult, i: int) -> RetrievalResult:
    return jax.tree_util.tree_map(lambda x: x[i], res)


@dataclasses.dataclass(frozen=True)
class RetrievalEngine:
    """Owns backend selection and the cascade schedule for one config.

    One engine (and thus one compiled program per batch shape and policy
    kind) serves every caller: the thin wrappers in repro.core.retrieval,
    the multi-tenant index, and the serving pipelines all funnel here.
    """

    cfg: RetrievalConfig

    def __post_init__(self):
        # Block-shape autotuning hook: if REPRO_AUTOTUNE_CACHE names a
        # valid artifact for this device, install it before any cascade
        # traces — block choice is resolved at trace time (see
        # kernels/autotune.py). No-op (deterministic DEFAULT_BLOCK_N)
        # without an artifact.
        from repro.kernels import autotune
        autotune.ensure_default_installed()

    def retrieve(self, query_codes: jax.Array, db: bitplanar.BitPlanarDB,
                 policy: Policy = PlainPolicy()) -> RetrievalResult:
        """Batched retrieval: (B, D) int8 queries -> batched result."""
        return retrieve_batched(query_codes, db, policy, self.cfg)

    def retrieve_single(self, query_codes: jax.Array,
                        db: bitplanar.BitPlanarDB,
                        policy: Policy = PlainPolicy()) -> RetrievalResult:
        """(D,) int8 query -> unbatched result (a B=1 lane of the core)."""
        return _lane(self.retrieve(query_codes[None], db, policy), 0)

    def retrieve_with_clusters(self, query_codes: jax.Array,
                               db: bitplanar.BitPlanarDB, policy: Policy
                               ) -> tuple[RetrievalResult, jax.Array | None]:
        """Batched retrieval plus the prune's (B, nprobe) cluster
        selection (None for policies without a prune stage). Same jitted
        cascade; the aux output lets the serving runtime account cache
        hits without re-deriving selection host-side."""
        return retrieve_batched_aux(query_codes, db, policy, self.cfg)

    def plan_for(self, db: bitplanar.BitPlanarDB, batch: int,
                 policy: Policy = PlainPolicy()) -> SchedulePlan:
        """The analytic SchedulePlan for one launch against `db`."""
        kind = {PlainPolicy: "plain", MaskedPolicy: "masked",
                WindowedPolicy: "windowed", ClusterPolicy: "cluster",
                ViewPolicy: "view", SlabPolicy: "cluster"}[type(policy)]
        window = policy.window if isinstance(policy, WindowedPolicy) else None
        if isinstance(policy, (ClusterPolicy, SlabPolicy)):
            num_clusters = policy.centroid_msb.shape[0]
            view_rows = probe_rows(policy)
        elif isinstance(policy, ViewPolicy):
            num_clusters = None
            view_rows = policy.rows.shape[1]
        else:
            num_clusters = view_rows = None
        return plan(self.cfg, num_docs=db.num_docs, dim=db.dim, batch=batch,
                    kind=kind, window=window, num_clusters=num_clusters,
                    view_rows=view_rows)


# ---------------------------------------------------------------------------
# The KV-cache corpus adapter: decode-step attention as a cascade
# ---------------------------------------------------------------------------
#
# A decode-step KV lookup is the same memory-bound shape as retrieval —
# score a query against N stored rows, keep k, touch full precision only
# for survivors — so it runs as the same staged cascade. The corpus is a
# KVCachePolicy (nibble-planar quantized K cache + bf16 V), the lanes are
# (batch, kv-head) pairs instead of queries, and the terminal stage is
# exact softmax ATTENTION over the survivors instead of a rerank:
#
#   KVPagePrune     — CentroidPrune over `page_rows`-sized key pages
#                     (Quest-style page selection: per-page INT8 mean-key
#                     centroids scored with the per-lane rows kernel)
#   KVSignPrescreen — SignPrescreen over the pruned pages' 1-bit sign
#                     plane via the scalar-prefetch stage-0 gather kernel
#   KVApproxTopK    — ApproxScan: f32 query x MSB-nibble keys (x per-row
#                     scale), GQA group-max, per-(batch, kv-head) top-k
#   KVExactAttend   — ExactRescore-shaped terminal: reconstruct INT8 keys
#                     for the k survivors, exact masked softmax attention
#
# With npages/prescreen off the cascade degenerates to the two-stage
# schedule serve.sparse_kv shipped originally, and is BIT-IDENTICAL to it
# (the parity suite pins this, including empty/short caches). `kv_plan`
# emits the same StagePlan ledger shape as `plan`, so energy.cost_cascade
# prices decode bytes exactly like retrieval bytes.

KV_NEG_INF = -1e30


@dataclasses.dataclass(frozen=True)
class KVCascadeConfig:
    """Static schedule knobs for one decode-attention cascade.

    top_k: exact-attention budget per (batch, kv-head) lane.
    npages: pages kept by KVPagePrune (None = no prune: every position
        enters the approx scan — the original two-stage schedule).
    page_rows: rows per key page (the prune/prescreen block size; the
        cache length T must be a multiple when either stage is on).
    prescreen_c0: survivors kept by the 1-bit sign prescreen (None = off;
        requires npages — the sign gather runs over the pruned pages).
    backend: "jnp" | "pallas" for the integer stages (the f32 approx and
        exact-attend stages are shared verbatim between backends); None
        = the Pallas kernels on a TPU, jnp elsewhere.
    scale: softmax scale (None = hd ** -0.5).
    """

    top_k: int
    npages: int | None = None
    page_rows: int = 8
    prescreen_c0: int | None = None
    backend: Literal["jnp", "pallas"] | None = None
    scale: float | None = None

    def __post_init__(self):
        if self.top_k < 1:
            raise ValueError("top_k must be >= 1")
        if self.prescreen_c0 is not None and self.npages is None:
            raise ValueError("prescreen_c0 gates the PRUNED pages' sign "
                             "gather: it needs npages")
        if self.npages is not None and self.page_rows < 1:
            raise ValueError("page_rows must be >= 1")


@dataclasses.dataclass(frozen=True)
class KVCachePolicy:
    """The decode corpus: one layer's quantized KV cache presented to the
    engine. Pure data (a pytree); the schedule is selected by the static
    KVCascadeConfig, mirroring how retrieval policies pair with
    RetrievalConfig.

    k_msb / k_lsb: (B, T, KH, hd//2) uint8 nibble planes of INT8 keys.
    k_scale: (B, T, KH) f32 per-(position, head) quant scales.
    v: (B, T, KH, hd) compute-dtype values.
    length: (B,) int32 valid positions per sequence.
    cent_msb / cent_scale: optional (B, P, KH, hd//2) / (B, P, KH) page
        centroids (P = T // page_rows) — required when npages is set.
    k_sign: optional (B, T, KH, hd//8) packed sign sidecar; the prescreen
        derives it from k_msb in-graph when absent (pure bit extraction,
        identical bytes — see bitplanar.sign_plane_from_msb).
    """

    k_msb: jax.Array
    k_lsb: jax.Array
    k_scale: jax.Array
    v: jax.Array
    length: jax.Array
    cent_msb: jax.Array | None = None
    cent_scale: jax.Array | None = None
    k_sign: jax.Array | None = None


jax.tree_util.register_pytree_node(
    KVCachePolicy,
    lambda p: ((p.k_msb, p.k_lsb, p.k_scale, p.v, p.length, p.cent_msb,
                p.cent_scale, p.k_sign), None),
    lambda _, l: KVCachePolicy(*l))


@dataclasses.dataclass
class _KVState:
    """The currency KV stages refine: WHICH cache positions are alive.

    rows:   (B, KH, R) cache position ids of the current view (None =
            implicit full view, the no-prune schedule).
    member: (B, KH, R) bool — position < length, gathered alongside rows.
    pages:  (B, KH, npages) selected page ids (ascending), kept so the
            prescreen can address the flat sign plane by block.
    out:    the (B, 1, H, hd) attention output, set by KVExactAttend.
    """

    rows: jax.Array | None = None
    member: jax.Array | None = None
    pages: jax.Array | None = None
    out: jax.Array | None = None


@dataclasses.dataclass
class _KVCtx:
    """Per-step invariants every KV stage reads. qg is the f32 grouped
    query (B, KH, G, hd); q_codes/q_scale are its per-head-vector INT8
    quantization (built only when a prune/prescreen stage needs integer
    query operands for the kernels)."""

    q: jax.Array
    qg: jax.Array
    policy: KVCachePolicy
    cfg: KVCascadeConfig
    fns: StageFns
    q_codes: jax.Array | None = None
    q_scale: jax.Array | None = None


def _kv_flat(x: jax.Array) -> jax.Array:
    """(B, T, KH, C) cache plane -> (B*KH*T, C) flat engine plane.

    Row (b*KH + kh)*T + t holds position t of lane (b, kh) — the layout
    that lets the existing scalar-prefetch gather kernels treat the whole
    batched cache as ONE corpus with per-lane block ids."""
    b, t, kh = x.shape[:3]
    return x.transpose(0, 2, 1, 3).reshape(b * kh * t, *x.shape[3:])


def _kv_flat_rows(rows: jax.Array, t: int) -> jax.Array:
    """(B, KH, R) cache positions -> flat plane row ids."""
    b, kh = rows.shape[:2]
    lane = (jnp.arange(b, dtype=jnp.int32)[:, None, None] * kh
            + jnp.arange(kh, dtype=jnp.int32)[None, :, None])
    return lane * t + rows


@dataclasses.dataclass(frozen=True)
class KVPagePrune:
    """Stage 0: score the per-page centroids, keep each (batch, kv-head)
    lane's top-`npages` valid pages, expand to an explicit position view.

    Selection mirrors CentroidPrune/select_clusters: integer centroid
    scores (per-lane rows kernel over the centroid nibble rows) scaled to
    f32 by the query and centroid scales, GQA group-max across the G
    query heads sharing the lane, invalid pages (entirely past `length`)
    masked to -inf before the top-k, and the selected pages re-sorted
    ASCENDING (the SignPrescreen convention: pruning deletes positions
    from the view, it never reorders it — so at full page coverage the
    view is the identity and the cascade converges to the unpruned
    schedule)."""

    npages: int

    def run(self, state: _KVState, ctx: _KVCtx) -> _KVState:
        pol, cfg = ctx.policy, ctx.cfg
        if pol.cent_msb is None or pol.cent_scale is None:
            raise ValueError("npages needs page centroids on the policy "
                             "(cent_msb/cent_scale — see "
                             "serve.sparse_kv.build_page_centroids)")
        b, t, kh, hd = pol.v.shape
        pr = cfg.page_rows
        if t % pr:
            raise ValueError(f"cache length {t} is not a multiple of "
                             f"page_rows={pr}")
        p = t // pr
        if pol.cent_msb.shape[1] != p:
            raise ValueError(f"centroid table holds {pol.cent_msb.shape[1]} "
                             f"pages, cache has {p}")
        npages = min(self.npages, p)
        g = ctx.qg.shape[2]
        q_nib = quantization.msb_nibble(ctx.q_codes).reshape(b * kh * g, hd)
        # Per-lane centroid rows, replicated across the lane's G query
        # heads (the codebook is tiny: P rows of hd/2 bytes).
        cent_rows = jnp.broadcast_to(
            pol.cent_msb.transpose(0, 2, 1, 3)[:, :, None],
            (b, kh, g, p, hd // 2)).reshape(b * kh * g, p, hd // 2)
        scores = ctx.fns.rows(q_nib, cent_rows)              # (B', P) int32
        key = (scores.astype(jnp.float32).reshape(b, kh, g, p)
               * ctx.q_scale.reshape(b, kh, g)[..., None]
               * pol.cent_scale.transpose(0, 2, 1)[:, :, None, :])
        key = jnp.max(key, axis=2)                           # (B, KH, P)
        first_row = jnp.arange(p, dtype=jnp.int32) * pr
        valid = first_row[None, None, :] < jnp.reshape(
            pol.length, (-1, 1, 1)).astype(jnp.int32)
        key = jnp.where(valid, key, -jnp.inf)
        _, pages = jax.lax.top_k(key, npages)                # (B, KH, NP)
        pages = jnp.sort(pages, axis=-1)     # pages keep cache order
        offs = jnp.arange(pr, dtype=jnp.int32)
        rows = (pages[..., None] * pr + offs).reshape(b, kh, npages * pr)
        member = rows < jnp.reshape(pol.length, (-1, 1, 1)).astype(jnp.int32)
        return dataclasses.replace(state, rows=rows, member=member,
                                   pages=pages)


@dataclasses.dataclass(frozen=True)
class KVSignPrescreen:
    """Stage 0.5: 1-bit sign-agreement prescreen of the pruned page view.

    Streams only the packed sign plane of the selected pages (hd/8 bytes
    per position — 4x fewer than the MSB nibble stage) through the
    stage-0 block-gather primitive over the FLAT cache plane (per-lane
    block ids address (lane, page) pairs), group-maxes the ±1-dot
    agreement across the lane's G query heads, and keeps the top-`c0`
    members. Survivors are re-sorted into view order, so at
    c0 >= view_rows the cascade is bit-identical to the no-prescreen
    schedule — the same parity anchor the retrieval SignPrescreen pins.
    """

    c0: int

    def run(self, state: _KVState, ctx: _KVCtx) -> _KVState:
        pol, cfg = ctx.policy, ctx.cfg
        b, t, kh, hd = pol.v.shape
        if hd % 8:
            raise ValueError(f"sign prescreen needs head_dim % 8 == 0, "
                             f"got {hd}")
        pr = cfg.page_rows
        g = ctx.qg.shape[2]
        r = state.rows.shape[2]
        c0 = min(self.c0, r)
        sign = pol.k_sign
        flat_sign = (bitplanar.sign_plane_from_msb(_kv_flat(pol.k_msb))
                     if sign is None else _kv_flat(sign))
        q_sign = bitplanar.sign_pm1(ctx.q_codes).reshape(b * kh * g, hd)
        lane = (jnp.arange(b, dtype=jnp.int32)[:, None, None] * kh
                + jnp.arange(kh, dtype=jnp.int32)[None, :, None])
        flat_pages = lane * (t // pr) + state.pages          # (B, KH, NP)
        blk = jnp.broadcast_to(flat_pages[:, :, None, :],
                               (b, kh, g, flat_pages.shape[-1]))
        scores = ctx.fns.sign_gather(q_sign, flat_sign,
                                     blk.reshape(b * kh * g, -1),
                                     block_rows=pr)          # (B', R) int32
        key = jnp.max(scores.reshape(b, kh, g, r), axis=2)   # (B, KH, R)
        key = jnp.where(state.member, key, INT32_MIN)
        _, sel = jax.lax.top_k(key, c0)                      # (B, KH, C0)
        sel = jnp.sort(sel, axis=-1)         # survivors keep view order
        rows = jnp.take_along_axis(state.rows, sel, axis=2)
        member = jnp.take_along_axis(state.member, sel, axis=2)
        return dataclasses.replace(state, rows=rows, member=member)


@dataclasses.dataclass(frozen=True)
class KVApproxTopK:
    """Stage 1: f32 query x MSB-nibble keys (x per-position scale), GQA
    group-max, NEG_INF masking of dead positions, per-lane top-k.

    The full-view branch is VERBATIM the original sparse_kv stage 1 (same
    einsum on the same operands), and the gathered branch reshapes its
    gathered rows into the same (B, R, KH, hd) layout before the same
    einsum — so at full page coverage both branches produce bit-identical
    scores and the selected positions match the legacy path's exactly."""

    top_k: int

    def run(self, state: _KVState, ctx: _KVCtx) -> _KVState:
        pol = ctx.policy
        b, t, kh, hd = pol.v.shape
        if state.rows is None:
            # Full view: every cached position scored from the MSB plane.
            k_msb = bitplanar.unpack_nibble_plane_signed(
                pol.k_msb.reshape(-1, hd // 2)).reshape(b, t, kh, hd)
            s1 = jnp.einsum("bkgd,btkd->bkgt", ctx.qg,
                            k_msb.astype(jnp.float32))
            s1 = s1 * pol.k_scale.transpose(0, 2, 1)[:, :, None, :]
            s1 = jnp.max(s1, axis=2)                         # (B, KH, T)
            valid = jnp.arange(t)[None, None, :] < jnp.reshape(
                pol.length, (-1, 1, 1)).astype(jnp.int32)
            s1 = jnp.where(valid, s1, KV_NEG_INF)
            k_eff = min(self.top_k, t)
            _, sel = jax.lax.top_k(s1, k_eff)                # (B, KH, k)
            member = sel < jnp.reshape(pol.length,
                                       (-1, 1, 1)).astype(jnp.int32)
            return dataclasses.replace(state, rows=sel, member=member)
        # Gathered view: stream only the surviving positions' nibble rows
        # from the flat plane, reshaped to the full branch's (B, R, KH, hd)
        # layout so the scoring expression is literally the same.
        r = state.rows.shape[2]
        fr = _kv_flat_rows(state.rows, t)
        g_msb = jnp.take(_kv_flat(pol.k_msb), fr.reshape(-1),
                         axis=0).reshape(b, kh, r, hd // 2)
        k_msb = bitplanar.unpack_nibble_plane_signed(
            g_msb.reshape(-1, hd // 2)).reshape(b, kh, r, hd)
        k_msb = k_msb.transpose(0, 2, 1, 3)                  # (B, R, KH, hd)
        scale_sel = jnp.take(_kv_flat(pol.k_scale[..., None])[:, 0],
                             fr.reshape(-1), axis=0).reshape(b, kh, r)
        s1 = jnp.einsum("bkgd,btkd->bkgt", ctx.qg,
                        k_msb.astype(jnp.float32))
        s1 = s1 * scale_sel[:, :, None, :]
        s1 = jnp.max(s1, axis=2)                             # (B, KH, R)
        s1 = jnp.where(state.member, s1, KV_NEG_INF)
        k_eff = min(self.top_k, r)
        _, sel = jax.lax.top_k(s1, k_eff)                    # view-local
        rows = jnp.take_along_axis(state.rows, sel, axis=2)
        member = jnp.take_along_axis(state.member, sel, axis=2)
        return dataclasses.replace(state, rows=rows, member=member)


@dataclasses.dataclass(frozen=True)
class KVExactAttend:
    """Terminal stage: gather the survivors' full nibble planes,
    reconstruct INT8 keys, exact masked softmax attention over them.

    Verbatim the original sparse_kv stage 2, including the masked-softmax
    zero-output fallback: when length < top_k the top-k necessarily
    selects invalid positions, and at length == 0 EVERY selected position
    is invalid — a plain softmax over the all-NEG_INF row would emit
    NaNs, so masked entries contribute exp 0 and an all-masked row
    divides by 1 and outputs exact zeros."""

    def run(self, state: _KVState, ctx: _KVCtx) -> _KVState:
        pol, cfg = ctx.policy, ctx.cfg
        b, t, kh, hd = pol.v.shape
        h = ctx.q.shape[2]
        k_eff = state.rows.shape[2]
        scale = cfg.scale or hd ** -0.5
        sel = state.rows
        bidx = jnp.arange(b)[:, None, None]
        hidx = jnp.arange(kh)[None, :, None]
        msb_sel = pol.k_msb.transpose(0, 2, 1, 3)[bidx, hidx, sel]
        lsb_sel = pol.k_lsb.transpose(0, 2, 1, 3)[bidx, hidx, sel]
        scale_sel = jnp.take_along_axis(
            pol.k_scale.transpose(0, 2, 1), sel, axis=-1)    # (B, KH, k)
        k_int = bitplanar.reconstruct_int8(
            msb_sel.reshape(-1, hd // 2),
            lsb_sel.reshape(-1, hd // 2)).reshape(b, kh, k_eff, hd)
        k_sel = k_int.astype(jnp.float32) * scale_sel[..., None]
        v_sel = pol.v.transpose(0, 2, 1, 3)[bidx, hidx,
                                            sel].astype(jnp.float32)
        s2 = jnp.einsum("bkgd,bktd->bkgt", ctx.qg, k_sel) * scale
        mask = state.member[:, :, None, :]
        s2 = jnp.where(mask, s2, KV_NEG_INF)
        e = jnp.where(mask,
                      jnp.exp(s2 - jnp.max(s2, axis=-1, keepdims=True)),
                      0.0)
        denom = jnp.sum(e, axis=-1, keepdims=True)
        p = e / jnp.where(denom > 0, denom, 1.0)
        out = jnp.einsum("bkgt,bktd->bkgd", p, v_sel)
        out = out.reshape(b, 1, h, hd).astype(ctx.q.dtype)
        return dataclasses.replace(state, out=out)


def kv_cascade_stages(cfg: KVCascadeConfig) -> tuple:
    """The stage specs one decode step runs, selected by the config."""
    stages: tuple = ()
    if cfg.npages is not None:
        stages += (KVPagePrune(cfg.npages),)
    if cfg.prescreen_c0 is not None:
        stages += (KVSignPrescreen(cfg.prescreen_c0),)
    return stages + (KVApproxTopK(cfg.top_k), KVExactAttend())


def _kv_run(q: jax.Array, policy: KVCachePolicy,
            cfg: KVCascadeConfig) -> _KVState:
    """Run one decode step's stages; the final state holds the selected
    positions and the attention output."""
    b, _, h, hd = q.shape
    kh = policy.v.shape[2]
    g = h // kh
    qg = q.reshape(b, kh, g, hd).astype(jnp.float32)
    q_codes = q_scale = None
    if cfg.npages is not None or cfg.prescreen_c0 is not None:
        # Integer query operands for the kernel stages: per-head-vector
        # INT8 quantization (a per-lane positive scale — re-applied to the
        # centroid key before group-max so heads compare on equal terms).
        with jax.named_scope("kv.QueryQuant"):
            q_codes, q_scale = quantization.quantize_int8(
                qg.reshape(b * kh * g, hd), per_vector=True)
    ctx = _KVCtx(q=q, qg=qg, policy=policy, cfg=cfg,
                 fns=stage_fns(cfg.backend), q_codes=q_codes,
                 q_scale=q_scale)
    state = _KVState()
    for stage in kv_cascade_stages(cfg):
        # metadata only, as in _run_cascade
        with jax.named_scope("kv." + type(stage).__name__):
            state = stage.run(state, ctx)
    return state


def _kv_cascade(q: jax.Array, policy: KVCachePolicy,
                cfg: KVCascadeConfig) -> jax.Array:
    """One decode step's staged KV attention.

    q (B, 1, H, hd) against the policy's cache; returns (B, 1, H, hd)."""
    return _kv_run(q, policy, cfg).out


def _kv_selection(q: jax.Array, policy: KVCachePolicy,
                  cfg: KVCascadeConfig) -> tuple[jax.Array, jax.Array]:
    """The cache positions one decode step attends over: (B, KH, k)
    position ids and their validity — what the prune, prescreen and
    approximate stages select, for comparing backends on the same data."""
    state = _kv_run(q, policy, cfg)
    return state.rows, state.member


kv_decode_batched = jax.jit(_kv_cascade, static_argnames=("cfg",))
kv_selection = jax.jit(_kv_selection, static_argnames=("cfg",))


def kv_plan(cfg: KVCascadeConfig, *, batch: int, kv_heads: int,
            q_heads: int, seq_len: int, head_dim: int,
            layers: int = 1) -> SchedulePlan:
    """Analytic StagePlan ledger for ONE decode step (all `layers`).

    Same currency as `plan`: `rows` is per LANE — here a lane is one
    SEQUENCE, so rows count every (layer, kv-head, query-head) MAC row
    the step scores for it — and `bytes_hbm` is what the whole batched
    step streams. Feed `.stages` to energy.cost_cascade with
    batch=`batch` to price µJ per TOKEN per sequence. The no-prune plan
    reconciles exactly with serve.sparse_kv.sparse_bytes_per_step (the
    pruned plans differ only by gather-block padding of the final
    partial page)."""
    t, hd, g = seq_len, head_dim, q_heads // kv_heads
    lanes = layers * kv_heads          # per sequence
    stages: tuple = ()
    r = t
    if cfg.npages is not None:
        p = -(-t // cfg.page_rows)
        npages = min(cfg.npages, p)
        stages += (StagePlan(
            name="prune", rows=lanes * g * p, bits=4,
            bytes_hbm=batch * lanes * p * (hd // 2 + 4),
            compares=lanes * p),)
        r = npages * cfg.page_rows
    if cfg.prescreen_c0 is not None:
        stages += (StagePlan(
            name="prescreen", rows=lanes * g * r, bits=1,
            bytes_hbm=batch * lanes * r * (hd // 8),
            compares=lanes * r),)
        r = min(cfg.prescreen_c0, r)
    k_eff = min(cfg.top_k, r)
    s1 = batch * lanes * r * (hd // 2 + 4)     # MSB plane + f32 scales
    # Exact stage: both nibble planes (hd bytes) + scales for the k
    # surviving keys, plus their bf16 V rows — K is reconstructed INT8,
    # V streams at compute precision.
    s2 = batch * lanes * k_eff * (hd + 4 + 2 * hd)
    stages += (StagePlan(name="approx", rows=lanes * g * r, bits=4,
                         bytes_hbm=s1, compares=lanes * r),
               StagePlan(name="exact", rows=lanes * g * 2 * k_eff, bits=8,
                         bytes_hbm=s2, compares=0))
    return SchedulePlan(kind="decode", batch=batch, rows_scanned=r,
                        candidates=k_eff, stage1_bytes=s1,
                        stage1_bytes_vmapped=s1, stage2_bytes=s2,
                        stages=stages)
