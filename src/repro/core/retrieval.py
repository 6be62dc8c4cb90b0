"""Quantization-aware two-stage hierarchical retrieval (the paper's core).

Stage 1 — MSB-INT4 approximate retrieval: score EVERY document using only
the most-significant nibble of both query and document codes (read from the
nibble-planar MSB plane — half the HBM bytes), and keep an approximate
candidate set.

Stage 2 — INT8 full-precision retrieval: gather the candidates' full INT8
codes (MSB+LSB planes), rescore exactly, and rank the final top-k with the
non-division fraction comparator (cosine) or raw integer scores (MIPS).

The candidate-set policy follows the paper's Fig. 4 operating points:
``min(max_candidates, ceil(candidate_frac * N))`` with max 50 / frac 0.2.

Every variant in this module — plain, segment-masked, windowed, batched —
is a THIN wrapper over the one batched two-stage core in repro.core.engine:
it builds the membership/window policy for its calling convention and runs
the shared schedule. `backend="jnp"` uses pure-jnp reference math;
`backend="pallas"` routes both scoring stages through the batch-native
Pallas TPU kernels in repro.kernels; left unset (None) it is keyed on the
platform — the kernels on a TPU, the jnp reference elsewhere
(`repro.kernels.platform.resolve_backend`).
"""
from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Literal

import jax
import jax.numpy as jnp

from repro.core import bitplanar, quantization, similarity


@dataclasses.dataclass(frozen=True)
class RetrievalConfig:
    k: int = 5
    metric: Literal["cosine", "mips"] = "cosine"
    max_candidates: int = 50
    candidate_frac: float = 0.2
    backend: Literal["jnp", "pallas"] | None = None
    # Stage-0 sign-plane prescreen budget: the cluster-pruned cascade
    # inserts a 1-bit sign-agreement scan between the centroid prune and
    # the INT4 scan, keeping only the top-C0 view rows per lane (clamped
    # to [k, view rows]) so stage 1 gathers C0 rows instead of the whole
    # probed view. None (the default) disables the stage entirely —
    # cascades, plans and golden pins are bit-for-bit the pre-prescreen
    # behavior. Ignored by policies without a centroid prune.
    prescreen_c0: int | None = None

    def num_candidates(self, num_docs: int) -> int:
        return max(self.k, min(self.max_candidates,
                               math.ceil(self.candidate_frac * num_docs)))

    def prescreen_budget(self, view_rows: int) -> int | None:
        """The effective stage-0 survivor count for a `view_rows`-row
        probed view (None when the prescreen is disabled) — the single
        clamp both the SignPrescreen stage and the analytic plan use."""
        if self.prescreen_c0 is None:
            return None
        return max(self.k, min(self.prescreen_c0, view_rows))


@dataclasses.dataclass(frozen=True)
class RetrievalResult:
    indices: jax.Array        # (k,) global document ids, best first
    scores: jax.Array         # (k,) exact int32 dot products
    candidate_indices: jax.Array  # (C,) stage-1 candidate ids (diagnostics)


jax.tree_util.register_pytree_node(
    RetrievalResult,
    lambda r: ((r.indices, r.scores, r.candidate_indices), None),
    lambda _, leaves: RetrievalResult(*leaves),
)


# Sentinel tenant id that matches no arena slot (free slots use -1), used to
# pad request batches: a NO_TENANT query returns all-invalid results.
NO_TENANT = -2


# ---------------------------------------------------------------------------
# Single-query stage primitives (reference math; kept as the oracles the
# kernel tests and benchmarks compare against — the serving paths run the
# engine's BATCHED primitives instead)
# ---------------------------------------------------------------------------

def stage1_scores_jnp(q_msb: jax.Array, msb_plane: jax.Array) -> jax.Array:
    """Approximate MIPS on MSB nibbles. q_msb (D,) int8 in [-8,7];
    msb_plane (N, D//2) uint8 packed. Returns (N,) int32.

    Split-query formulation: byte j of the plane packs dims (2j, 2j+1), so
    the dot product is lo_signed . q_even + hi_signed . q_odd — scoring
    runs directly on the packed plane (two (N, D/2) matvecs), never
    materializing the (N, D) interleaved unpack on the hot path.
    """
    lo, hi = bitplanar.split_nibbles_signed(msb_plane)
    return (similarity.int_matvec(lo, q_msb[0::2])
            + similarity.int_matvec(hi, q_msb[1::2]))


def stage2_scores_jnp(q: jax.Array, msb_rows: jax.Array,
                      lsb_rows: jax.Array) -> jax.Array:
    """Exact INT8 rescoring of gathered candidate rows. q (D,) int8."""
    docs = bitplanar.reconstruct_int8(msb_rows, lsb_rows)     # (C, D) int8
    return similarity.int_matvec(docs, q)


# ---------------------------------------------------------------------------
# Engine-backed retrieval variants
# ---------------------------------------------------------------------------

def two_stage_retrieve(query_codes: jax.Array, db: bitplanar.BitPlanarDB,
                       cfg: RetrievalConfig) -> RetrievalResult:
    """Run the hierarchical retrieval for one query over one DB shard.

    query_codes: (D,) int8 (already quantized by the embedder front-end).
    A B=1 lane of the batched engine core.
    """
    return _engine.RetrievalEngine(cfg).retrieve_single(query_codes, db)


def batched_retrieve(query_codes: jax.Array, db: bitplanar.BitPlanarDB,
                     cfg: RetrievalConfig) -> RetrievalResult:
    """(B, D) int8 queries -> batched RetrievalResult, ONE launch.

    Batch-native (not a vmap): stage 1 runs as one (N, D/2) x (D/2, B)
    matmul, so the doc plane streams from HBM once for the whole batch.
    """
    return _engine.retrieve_batched(query_codes, db, _engine.PlainPolicy(),
                                    cfg)


@partial(jax.jit, static_argnames=("cfg",))
def exact_retrieve(query_codes: jax.Array, db: quantization.QuantizedDB,
                   cfg: RetrievalConfig) -> RetrievalResult:
    """Single-stage full-precision INT8 retrieval (the paper's baseline)."""
    scores = similarity.int_matvec(db.values, query_codes)
    if cfg.metric == "cosine":
        key = similarity.cosine_key_f32(scores, db.norms_sq)
    else:
        key = scores
    _, idx = jax.lax.top_k(key, cfg.k)
    return RetrievalResult(indices=idx, scores=scores[idx],
                           candidate_indices=idx)


@partial(jax.jit, static_argnames=("cfg",))
def int4_retrieve(query_codes: jax.Array, db: bitplanar.BitPlanarDB,
                  cfg: RetrievalConfig) -> RetrievalResult:
    """Pure-INT4 baseline: rank directly on MSB-nibble scores (no stage 2)."""
    q_msb = quantization.msb_nibble(query_codes)
    approx = stage1_scores_jnp(q_msb, db.msb_plane)
    if cfg.metric == "cosine":
        key = similarity.cosine_key_f32(approx, db.norms_sq)
    else:
        key = approx
    _, idx = jax.lax.top_k(key, cfg.k)
    return RetrievalResult(indices=idx, scores=approx[idx],
                           candidate_indices=idx)


def cluster_pruned_retrieve(query_codes: jax.Array,
                            db: bitplanar.BitPlanarDB, codebook,
                            cluster_blocks, labels,
                            cfg: RetrievalConfig, *,
                            nprobe: int, block_rows: int,
                            owner: jax.Array | None = None,
                            tenant_ids: jax.Array | None = None
                            ) -> RetrievalResult:
    """Cluster-pruned cascade over one DB: (B, D) int8 queries, ONE launch.

    The 3-stage cascade (centroid prune -> gathered INT4 scan -> exact
    INT8 rescore): stage 0 scores the `codebook`'s K centroids
    (repro.core.clustering.ClusterCodebook), keeps each lane's top-
    `nprobe` clusters, and stage 1 streams ONLY those clusters' row
    blocks (`cluster_blocks`, from clustering.block_table; `labels` is
    the row -> cluster map the prune uses to keep each row visible only
    through its own cluster's block entry) — stage-1 bytes drop from
    O(N) to O(N * nprobe / K) per lane while stage 2 still rescores
    exactly. Single-corpus callers omit owner/tenant_ids (every gathered
    row is visible); arena callers pass them for segment masking,
    exactly as in the masked variants.
    """
    query_codes = jnp.asarray(query_codes)
    b = query_codes.shape[0]
    n = db.num_docs
    if (owner is None) != (tenant_ids is None):
        raise ValueError("owner and tenant_ids must be passed together "
                         "(segment masking needs both) or both omitted "
                         "(single corpus: every row visible)")
    if owner is None:
        owner = jnp.zeros((n,), jnp.int32)
        tenant_ids = jnp.zeros((b,), jnp.int32)
    policy = _engine.ClusterPolicy(
        owner=owner, tenant_ids=jnp.asarray(tenant_ids, jnp.int32),
        labels=jnp.asarray(labels, jnp.int32),
        centroid_msb=codebook.msb_plane, centroid_norms=codebook.norms_sq,
        cluster_blocks=jnp.asarray(cluster_blocks, jnp.int32),
        nprobe=nprobe, block_rows=block_rows)
    return _engine.retrieve_batched(query_codes, db, policy, cfg)


# ---------------------------------------------------------------------------
# Segment-masked variants (multi-tenant arenas)
# ---------------------------------------------------------------------------

def two_stage_retrieve_masked(query_codes: jax.Array,
                              db: bitplanar.BitPlanarDB,
                              owner: jax.Array, tenant_id: jax.Array,
                              cfg: RetrievalConfig) -> RetrievalResult:
    """Hierarchical retrieval restricted to one tenant's arena segments.

    owner: (N,) int32 slot->tenant map (repro.tenancy.Arena.owner; free and
    tombstoned slots hold -1). Rows with owner != tenant_id are masked to
    -inf in stage 1 and pinned to the floor score in stage 2, so a query
    can never surface another tenant's (or a dead) document. Returned
    indices are arena slot ids; slots the tenant could not fill (fewer
    live docs than k) come back as -1 with score 0.

    This is the fully general path: it scans the WHOLE arena and works for
    arbitrarily fragmented tenants. When every tenant in a batch is one
    contiguous segment, prefer `windowed_retrieve_masked`.
    """
    policy = _engine.MaskedPolicy(
        owner=owner, tenant_ids=jnp.asarray(tenant_id, jnp.int32)[None])
    return _engine.RetrievalEngine(cfg).retrieve_single(query_codes, db,
                                                        policy)


def batched_retrieve_masked(query_codes: jax.Array,
                            db: bitplanar.BitPlanarDB, owner: jax.Array,
                            tenant_ids: jax.Array,
                            cfg: RetrievalConfig) -> RetrievalResult:
    """Cross-tenant batch: (B, D) queries + (B,) tenant ids, ONE launch.

    The segment-masked batched core over the shared arena — the
    scheduler's kernel-level primitive. Stage 1 streams the arena's MSB
    plane ONCE for the whole mixed batch (true matmul, not B matvecs).
    """
    policy = _engine.MaskedPolicy(owner=owner,
                                  tenant_ids=jnp.asarray(tenant_ids,
                                                         jnp.int32))
    return _engine.retrieve_batched(query_codes, db, policy, cfg)


def windowed_retrieve_masked(query_codes: jax.Array,
                             db: bitplanar.BitPlanarDB, owner: jax.Array,
                             tenant_ids: jax.Array, starts: jax.Array,
                             cfg: RetrievalConfig,
                             window: int) -> RetrievalResult:
    """Cross-tenant batch over a tenant-CONTIGUOUS arena, one launch.

    When each requested tenant occupies a single contiguous slot run (the
    invariant bump allocation establishes and tenant-grouped compaction
    restores), batch lane i only streams the `window` rows starting at its
    tenant's segment — so a mixed batch of B users costs one launch AND
    only per-tenant work, instead of B arena-wide scans. Rows inside the
    window but outside the segment (neighbours, tombstones) are masked
    exactly like the full-scan variant. Returned indices are global arena
    slot ids.

    window: static upper bound on any requested tenant's segment length
    (callers round up to a power-of-two bucket to bound recompilation),
    and must be >= cfg.k (MultiTenantIndex guarantees this).
    """
    policy = _engine.WindowedPolicy(
        owner=owner, tenant_ids=jnp.asarray(tenant_ids, jnp.int32),
        starts=starts, window=window)
    return _engine.retrieve_batched(query_codes, db, policy, cfg)


# Bottom import: engine defines the shared batched core and imports the
# config/result types above, so this intentionally runs after they exist.
from repro.core import engine as _engine                     # noqa: E402
