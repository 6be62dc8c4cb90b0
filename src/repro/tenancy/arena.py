"""Fixed-capacity nibble-planar arenas with online insert/delete.

The wearable setting is streaming: a personal corpus grows continuously as
the agent monitors health signals, and the seed repo's offline
`build_database` (re-quantize + re-pack everything) is exactly the rebuild
the edge budget cannot afford. An `Arena` is a pre-allocated nibble-planar
slab — the same (msb_plane, lsb_plane, norms_sq) triple `BitPlanarDB`
streams on TPU — plus host-side slot bookkeeping:

  * insert: quantize-with-fixed-scale rows land in free slots via one
    `.at[slots].set` scatter per plane — O(rows inserted), never O(N).
  * delete: tombstone, not reshuffle. The slot's norm is zeroed (cosine
    key 0 — a dead row can never win stage 1), its planes are zeroed
    (MIPS score 0), and its owner is reset to FREE so segment masks
    exclude it. Live slot ids stay stable for in-flight readers.
  * compact: periodically repacks live rows to the slab's front (grouped
    per tenant, so each tenant becomes one contiguous segment), reclaims
    tombstones, and returns the old->new slot mapping.

The fixed quantization scale is the price of streaming: rows quantized at
different times must stay mutually comparable, so the scale is chosen once
(calibrated for unit-norm embedder outputs) instead of per-corpus.
`Arena.stats.rebuilds` counts full re-quantize passes; the online path
keeps it at zero by construction.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import bitplanar, quantization

FREE = -1  # owner value of free and tombstoned slots


class ArenaFull(RuntimeError):
    """Raised when an insert does not fit; compact() or grow a new arena."""


@dataclasses.dataclass
class ArenaStats:
    inserts: int = 0          # rows written online
    deletes: int = 0          # rows tombstoned
    compactions: int = 0      # repack passes
    rebuilds: int = 0         # full re-quantize passes (streaming path: 0)


class Arena:
    """One shared slab serving many tenants' rows side by side."""

    def __init__(self, capacity: int, dim: int, *, scale: float | None = None,
                 device=None):
        """device: commit the planes to this jax device (a serving shard's
        own chip); every later update and launch then stays on it. None
        leaves them uncommitted on the default device."""
        if dim % 2:
            raise ValueError("dim must be even for nibble-planar packing")
        self.capacity = capacity
        self.dim = dim
        self.scale = jnp.float32(scale if scale is not None
                                 else quantization.unit_norm_scale(dim))
        self.msb_plane = jnp.zeros((capacity, dim // 2), jnp.uint8)
        self.lsb_plane = jnp.zeros((capacity, dim // 2), jnp.uint8)
        # 1-bit sign plane (stage-0 prescreen operand), maintained in
        # lockstep with the nibble planes; dims that don't pack 8-per-byte
        # simply don't get one (the prescreen requires dim % 8 == 0).
        self.sign_plane = (jnp.zeros((capacity, dim // 8), jnp.uint8)
                           if dim % 8 == 0 else None)
        self.norms_sq = jnp.zeros((capacity,), jnp.int32)
        self.owner = jnp.full((capacity,), FREE, jnp.int32)
        if device is not None:
            (self.msb_plane, self.lsb_plane, self.sign_plane, self.norms_sq,
             self.owner) = jax.device_put(
                (self.msb_plane, self.lsb_plane, self.sign_plane,
                 self.norms_sq, self.owner), device)
        # slot -> cluster label (host-side; -1 = unassigned/free). The
        # arena is clustering-agnostic storage: labels are written by the
        # index layer (repro.core.clustering assigns them) and kept in
        # lockstep with the planes across delete/compact.
        self.cluster_labels = np.full((capacity,), -1, np.int32)
        self._next = 0                  # bump allocator over virgin slots
        self._tombstones = 0            # dead slots awaiting compaction
        self.generation = 0             # bumped on every mutation
        self._db_cache: tuple[int, bitplanar.BitPlanarDB] | None = None
        self.stats = ArenaStats()

    # -- capacity accounting -------------------------------------------------

    @property
    def num_live(self) -> int:
        return self._next - self._tombstones

    @property
    def num_free(self) -> int:
        """Slots insertable RIGHT NOW (tombstones only count after compact)."""
        return self.capacity - self._next

    def db(self) -> bitplanar.BitPlanarDB:
        """The slab viewed as the retrieval primitives' BitPlanarDB.

        Cached per generation: the view is rebuilt only after a mutation,
        so the query hot path hands jit a stable pytree."""
        if self._db_cache is None or self._db_cache[0] != self.generation:
            self._db_cache = (self.generation, bitplanar.BitPlanarDB(
                msb_plane=self.msb_plane, lsb_plane=self.lsb_plane,
                norms_sq=self.norms_sq, scale=self.scale,
                sign_plane=self.sign_plane))
        return self._db_cache[1]

    # -- online mutation -----------------------------------------------------

    def quantize(self, embeddings) -> jnp.ndarray:
        """Float embeddings -> INT8 codes under the arena's fixed scale."""
        return quantization.quantize_int8_fixed(embeddings, self.scale)

    def insert(self, codes, owner_id: int) -> np.ndarray:
        """Pack (B, D) int8 codes into free slots for `owner_id`.

        Returns the assigned slot ids (B,) int64. O(B) device work — the
        rest of the slab is untouched (no rebuild). Cluster labels are a
        separate second phase (`set_labels`), so a failed insert can
        never leave labeling half-applied."""
        codes = jnp.asarray(codes)
        if codes.dtype != jnp.int8:
            raise ValueError(f"codes must be int8 (got {codes.dtype}); "
                             "float embeddings go through ingest()/"
                             "quantize() first")
        b, d = codes.shape
        if d != self.dim:
            raise ValueError(f"dim mismatch: arena {self.dim}, rows {d}")
        if owner_id < 0:
            raise ValueError("tenant ids must be >= 0 (negatives are sentinels)")
        if b > self.num_free:
            raise ArenaFull(
                f"need {b} slots, have {self.num_free} "
                f"({self._tombstones} reclaimable via compact())")
        slots = np.arange(self._next, self._next + b)
        self._next += b
        idx = jnp.asarray(slots, jnp.int32)
        msb, lsb = bitplanar.pack_nibble_planes(codes)
        norms = jnp.sum(codes.astype(jnp.int32) ** 2, axis=-1)
        self.msb_plane = self.msb_plane.at[idx].set(msb)
        self.lsb_plane = self.lsb_plane.at[idx].set(lsb)
        if self.sign_plane is not None:
            self.sign_plane = self.sign_plane.at[idx].set(
                bitplanar.pack_sign_plane(codes))
        self.norms_sq = self.norms_sq.at[idx].set(norms)
        self.owner = self.owner.at[idx].set(jnp.int32(owner_id))
        self.generation += 1
        self.stats.inserts += b
        return slots

    def set_labels(self, slots, labels) -> None:
        """Label already-inserted slots with cluster ids (host-side only).

        The index layer assigns labels AFTER a successful insert (so a
        failed insert can never leave cluster bookkeeping half-updated);
        this is the API for that second phase."""
        slots = np.atleast_1d(np.asarray(slots, np.int64))
        labels = np.asarray(labels, np.int32).reshape(-1)
        if slots.shape[0] != labels.shape[0]:
            raise ValueError(f"need one label per slot ({slots.shape[0]}), "
                             f"got {labels.shape[0]}")
        if slots.size and (slots.min() < 0 or slots.max() >= self._next):
            raise IndexError("slot out of allocated range")
        self.cluster_labels[slots] = labels

    def read_codes(self, slots) -> jnp.ndarray:
        """Reconstruct the full INT8 codes of `slots` from the planes.

        Off the hot path (cluster bookkeeping on delete, diagnostics):
        O(rows read), exact inverse of the insert-time packing."""
        idx = jnp.asarray(np.atleast_1d(np.asarray(slots, np.int64)),
                          jnp.int32)
        return bitplanar.reconstruct_int8(
            jnp.take(self.msb_plane, idx, axis=0),
            jnp.take(self.lsb_plane, idx, axis=0))

    def delete(self, slots) -> None:
        """Tombstone slots: norm 0, planes 0, owner FREE.

        Ids are not recycled until compact(), so results already handed to
        callers keep pointing at (now dead, never-winning) slots.
        Duplicate and already-dead ids are counted once (liveness is read
        from the owner array, so num_live stays truthful)."""
        slots = np.unique(np.atleast_1d(np.asarray(slots, np.int64)))
        if slots.size == 0:
            return
        if slots[0] < 0 or slots[-1] >= self._next:
            raise IndexError("slot out of allocated range")
        idx = jnp.asarray(slots, jnp.int32)
        newly_dead = int(jnp.sum(jnp.take(self.owner, idx) >= 0))
        self.msb_plane = self.msb_plane.at[idx].set(0)
        self.lsb_plane = self.lsb_plane.at[idx].set(0)
        if self.sign_plane is not None:
            # A zero sign byte is the packed form of all-positive dims —
            # consistent with the zeroed nibble planes (code 0 -> bit 0).
            self.sign_plane = self.sign_plane.at[idx].set(0)
        self.norms_sq = self.norms_sq.at[idx].set(0)
        self.owner = self.owner.at[idx].set(FREE)
        self.cluster_labels[slots] = -1
        self.generation += 1
        self._tombstones += newly_dead
        self.stats.deletes += newly_dead

    def compact(self, order: np.ndarray | None = None) -> np.ndarray:
        """Repack live rows to the slab front; reclaim tombstones.

        order: optional live-slot ordering (e.g. grouped by tenant so each
        tenant ends up one contiguous segment); defaults to ascending slot.
        Returns mapping (capacity,) int64: old slot -> new slot, -1 if dead.
        Moves already-quantized rows — no re-quantization (not a rebuild).
        """
        own = np.asarray(self.owner)
        if order is None:
            live = np.nonzero(own >= 0)[0]
        else:
            live = np.asarray(order, np.int64)
            if live.size and not np.all(own[live] >= 0):
                raise ValueError("compaction order includes dead slots")
        num_live = live.size
        idx = jnp.asarray(live, jnp.int32)

        def repack(arr, fill):
            out = jnp.full_like(arr, fill)
            if num_live:
                out = out.at[:num_live].set(jnp.take(arr, idx, axis=0))
            return out

        self.msb_plane = repack(self.msb_plane, 0)
        self.lsb_plane = repack(self.lsb_plane, 0)
        if self.sign_plane is not None:
            self.sign_plane = repack(self.sign_plane, 0)
        self.norms_sq = repack(self.norms_sq, 0)
        self.owner = repack(self.owner, FREE)
        new_labels = np.full_like(self.cluster_labels, -1)
        new_labels[:num_live] = self.cluster_labels[live]
        self.cluster_labels = new_labels
        mapping = np.full(self.capacity, -1, np.int64)
        mapping[live] = np.arange(num_live)
        self._next = num_live
        self._tombstones = 0
        self.generation += 1
        self.stats.compactions += 1
        return mapping
