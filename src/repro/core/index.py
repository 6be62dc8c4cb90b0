"""Pod-scale sharded hierarchical retrieval index.

The corpus is sharded row-wise over EVERY mesh device (the flattened
(pod, data, model) axes). One retrieval executes as:

  1. local stage-1 (MSB-nibble) scoring over the device's shard — BATCH-
     NATIVE: one (n_local, D/2) x (D/2, B) matmul via the engine's stage
     primitives, so the shard's plane streams once per batch,
  2. local top-C proposal per batch lane,
  3. all-gather of (score, global-id) proposals — O(B * C * devices)
     bytes, independent of corpus size (the "tournament"),
  4. global top-C selection (exact: the global top-C is always contained
     in the union of local top-Cs),
  5. stage-2 exact INT8 rescoring ONLY on the shard(s) owning each
     candidate — one batched (B, C) rescore — combined with a psum (each
     row owned exactly once),
  6. replicated final top-k via the non-division comparator.

The same function runs on a 1-device test mesh and the 512-device
production mesh (shard_map is mesh-polymorphic). Backend selection
(`cfg.backend`) routes the two scoring stages through the same jnp or
Pallas batched primitives the single-host engine uses.
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import bitplanar, quantization, similarity
from repro.core.engine import stage_fns
from repro.core.retrieval import RetrievalConfig, RetrievalResult


def pad_database(db: bitplanar.BitPlanarDB, num_shards: int) -> bitplanar.BitPlanarDB:
    """Pad row count to a multiple of num_shards with all-zero docs.

    Zero docs have norm 0 => cosine similarity 0 and MIPS score 0. A score
    of 0 is NOT a floor — it beats every real document whenever all true
    scores are negative (MIPS over anti-correlated queries) — so
    `_tournament_retrieve` masks pad rows (gid >= n_global) out of both
    scoring stages explicitly instead of relying on their zero score.
    """
    n = db.num_docs
    pad = (-n) % num_shards
    if pad == 0:
        return db
    def zpad(a):
        return jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1))
    return bitplanar.BitPlanarDB(
        msb_plane=zpad(db.msb_plane), lsb_plane=zpad(db.lsb_plane),
        norms_sq=zpad(db.norms_sq), scale=db.scale)


def shard_database(db: bitplanar.BitPlanarDB, mesh: Mesh) -> bitplanar.BitPlanarDB:
    """Place a (padded) database row-sharded over all mesh axes."""
    axes = tuple(mesh.axis_names)
    row_sharded = NamedSharding(mesh, P(axes))
    replicated = NamedSharding(mesh, P())
    return bitplanar.BitPlanarDB(
        msb_plane=jax.device_put(db.msb_plane, row_sharded),
        lsb_plane=jax.device_put(db.lsb_plane, row_sharded),
        norms_sq=jax.device_put(db.norms_sq, row_sharded),
        scale=jax.device_put(db.scale, replicated))


def _tournament_retrieve(q: jax.Array, msb_plane: jax.Array,
                         lsb_plane: jax.Array, norms_sq: jax.Array,
                         *, cfg: RetrievalConfig, n_global: int,
                         axis: str) -> RetrievalResult:
    """Batch-native body run per-shard under shard_map.

    q: (B, D) replicated; planes sharded. Both scoring stages run the
    engine's batched primitives — the whole batch shares one shard scan."""
    n_local = msb_plane.shape[0]
    shard_id = jax.lax.axis_index(axis)
    offset = shard_id * n_local
    c = min(cfg.num_candidates(n_global), n_global)
    c_local = min(c, n_local)
    fns = stage_fns(cfg.backend)
    s1_plane, s2_rows = fns.plane, fns.exact

    # ---- Stage 1: local batched approximate scoring + local proposals.
    q_msb = quantization.msb_nibble(q)
    approx = s1_plane(q_msb, msb_plane)                  # (B, n_local) i32
    if cfg.metric == "cosine":
        key1 = similarity.cosine_key_f32(approx, norms_sq[None, :])
    else:
        key1 = approx.astype(jnp.float32)
    # Pad rows (gid >= n_global, appended by pad_database) score 0, which
    # WINS whenever every real score is negative. -inf removes them from
    # the proposal ranking outright: each shard always holds enough real
    # rows (sum over shards of min(c_local, real rows) >= C, since every
    # shard has the same n_local), so the global top-C is pad-free.
    real = (jnp.arange(n_local, dtype=jnp.int32) + offset) < n_global
    key1 = jnp.where(real[None, :], key1, -jnp.inf)
    loc_key, loc_idx = jax.lax.top_k(key1, c_local)      # (B, c_local)
    loc_gid = (loc_idx + offset).astype(jnp.int32)

    # ---- Tournament: gather proposals, pick global top-C per lane.
    # Shard-major flattening (S * c_local) keeps the same tie-break order
    # as a per-lane all_gather would produce.
    all_key = jax.lax.all_gather(loc_key, axis)          # (S, B, c_local)
    all_gid = jax.lax.all_gather(loc_gid, axis)
    b = q.shape[0]
    all_key = jnp.moveaxis(all_key, 0, 1).reshape(b, -1)
    all_gid = jnp.moveaxis(all_gid, 0, 1).reshape(b, -1)
    top_key, sel = jax.lax.top_k(all_key, c)
    cand_gid = jnp.take_along_axis(all_gid, sel, axis=1)  # (B, C) global ids

    # ---- Stage 2: batched exact rescoring by owners only, psum-combined.
    owned = (cand_gid >= offset) & (cand_gid < offset + n_local)
    local_rows = jnp.clip(cand_gid - offset, 0, n_local - 1)
    msb_rows = jnp.take(msb_plane, local_rows, axis=0)   # (B, C, D//2)
    lsb_rows = jnp.take(lsb_plane, local_rows, axis=0)
    exact = s2_rows(q, msb_rows, lsb_rows)               # (B, C) i32
    nrm = jnp.take(norms_sq, local_rows, axis=0)
    exact = jax.lax.psum(jnp.where(owned, exact, 0), axis)
    cand_norms = jax.lax.psum(jnp.where(owned, nrm, 0), axis)
    # Defense in depth for the final rerank: should a pad gid ever reach
    # the candidate set, its exact score must not be the winning 0.
    # (INT8 dots are bounded by 127^2 * D << 2^31, so INT32_MIN is a true
    # floor; norm 1 keeps the non-division cosine comparator well-posed.)
    pad_cand = cand_gid >= n_global
    exact = jnp.where(pad_cand, jnp.iinfo(jnp.int32).min, exact)
    cand_norms = jnp.where(pad_cand, 1, cand_norms)

    # ---- Replicated final rerank per lane.
    if cfg.metric == "cosine":
        local, scores = jax.vmap(
            lambda s, nn: similarity.rerank_dense_comparator(s, nn, cfg.k)
        )(exact, cand_norms)
    else:
        scores, local = jax.lax.top_k(exact, cfg.k)
    return RetrievalResult(
        indices=jnp.take_along_axis(cand_gid, local, axis=1),
        scores=scores, candidate_indices=cand_gid)


@dataclasses.dataclass(frozen=True)
class ShardedIndex:
    """A database sharded over a mesh + a jitted retrieval entry point."""

    db: bitplanar.BitPlanarDB
    mesh: Mesh
    n_global: int

    @classmethod
    def build(cls, embeddings: jax.Array, mesh: Mesh) -> "ShardedIndex":
        qdb = quantization.build_database(embeddings)
        bp = bitplanar.BitPlanarDB.from_quantized(qdb)
        n_global = bp.num_docs
        bp = pad_database(bp, mesh.devices.size)
        return cls(db=shard_database(bp, mesh), mesh=mesh, n_global=n_global)

    def retrieve_fn(self, cfg: RetrievalConfig):
        """Returns a jittable f(query_codes (D,) or (B, D)) -> RetrievalResult."""
        axes = tuple(self.mesh.axis_names)
        flat_axis = axes if len(axes) > 1 else axes[0]
        row = P(axes)

        def body(q, msb, lsb, nrm):
            fn = partial(_tournament_retrieve, cfg=cfg,
                         n_global=self.n_global, axis=flat_axis)
            if q.ndim == 1:
                # single query = a B=1 lane of the batch-native body
                return jax.tree_util.tree_map(lambda x: x[0],
                                              fn(q[None], msb, lsb, nrm))
            return fn(q, msb, lsb, nrm)

        shmapped = jax.shard_map(
            body, mesh=self.mesh,
            in_specs=(P(), row, row, row),
            out_specs=RetrievalResult(indices=P(), scores=P(),
                                      candidate_indices=P()),
            check_vma=False)

        @jax.jit
        def retrieve(query_codes):
            return shmapped(query_codes, self.db.msb_plane,
                            self.db.lsb_plane, self.db.norms_sq)

        return retrieve
