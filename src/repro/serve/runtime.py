"""Session-aware serving runtime: deadline batcher + hot-cluster cache.

The wearable workload is a stream of small, temporally-correlated request
bursts: T users' agents each fire a query every few seconds, and
consecutive queries of one session probe the SAME few clusters
(continuous monitoring revisits the same part of the corpus). This module
is the serving layer that exploits both properties on top of the
cluster-pruned cascade:

  * `ServingRuntime` — a dynamic batcher that grew out of the synchronous
    `tenancy.scheduler` submit/flush loop: requests get FUTURE-STYLE
    handles, admission is deadline-OR-max-batch (a batch launches the
    moment it is full, or when the oldest request's deadline arrives —
    whichever comes first), partial batches pad to power-of-two buckets
    (one compiled executable per bucket), and batch formation is
    per-tenant fair (round-robin across tenants ordered by deadline, so
    one chatty user cannot starve the rest of a flush). Launches are
    ASYNC: a dispatch leaves the batch's device arrays in flight as
    unresolved futures on a completion queue (up to `async_depth` deep,
    double-buffered by default) and the host immediately returns to
    admission — the next batch's formation, slab warming, fills and
    indirection-table build all overlap the current batch's device
    scoring. Handles resolve lazily: `done()` is a non-blocking readiness
    probe, `result(wait=False)` is a None not-ready signal, `result()`
    blocks only on the caller's own launch, and `flush()`/`barrier()`
    are full drains. The per-launch host bookkeeping of the cached path
    (the (B, nprobe) selection readback feeding the hit/miss ledger, LRU,
    miss admissions and session prior) rides the same queue one launch
    behind, so the host never sits between launches waiting on a
    readback.

  * `HotClusterCache` — an EdgeRAG-style byte-budgeted LRU of hot
    cluster views held in a DEVICE-RESIDENT SLAB: a cache-owned extension
    region of the arena's stage-1 plane (`[arena plane | slab rows]`,
    one combined array rebuilt per arena generation) plus a host-side
    (tenant, cluster) -> slab-slot map. A cached flush hands the engine a
    `SlabPolicy`: cluster selection runs IN-GRAPH (the same centroid
    scoring + validity the cold cascade runs — identical by
    construction) over a small host-built int32 indirection table that
    resolves each (lane, cluster) to either its arena plane blocks
    (miss — streamed from HBM) or its slab blocks (hit — cache-owned
    rows that are never re-uploaded and are stored once per tenant even
    when several lanes share them). Slab slots are DENSELY PACKED — a
    contiguous cluster run is copied row-contiguously, so it occupies
    ceil(rows/block_rows) slots where the plane view needs up to one
    more straddling block — and each slot carries (first row id,
    live-row count) origin scalars the cascade reads back in-graph.
    With `preload` on, a session tenant whose packed views fit the
    budget is pinned wholesale and served from the COMPACT slab table:
    narrower than the plane table, so fully-warm launches gather and
    score fewer stage-1 rows per probe — the cache's wall-clock win on
    top of its byte ledger. Fills are in-place device row copies
    (donated buffers); the host never mirrors the plane and no per-lane
    dense view is ever materialized or uploaded. Any arena mutation
    bumps the generation and invalidates every slot — a stale view can
    never be served. A per-tenant RECENT-CLUSTER prior (the clusters the
    tenant's last turns probed) warms the slab between session turns
    when preload is off or over budget, and empty clusters are memoized
    as zero-byte entries so repeat probes of them count as (free) hits
    instead of skewing the miss ledger.

  * The launch ledger (`engine.SchedulePlan` via `cache_split_plan`)
    splits stage-1 bytes into HBM misses vs SRAM hits, and
    `energy.cost_cascade` charges hits at SRAM rates — so the runtime
    reports the measured uJ/query saving of the cache, in the paper's
    own accounting currency.

Results are BIT-IDENTICAL to the uncached cascade (and to sequential
retrieval): the cache changes where stage-1 bytes come from, never what
is scored — pinned by the parity and property suites in
tests/test_serve_runtime.py and tests/test_runtime_properties.py.
"""
from __future__ import annotations

import collections
import dataclasses
import functools
import heapq
import math
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import bitplanar, energy, engine
from repro.core.retrieval import NO_TENANT, RetrievalResult
from repro.obs.metrics import MetricsRegistry, NULL_REGISTRY
from repro.obs.tracing import NULL_TRACER


@dataclasses.dataclass(frozen=True)
class RuntimeConfig:
    """Host-side serving knobs.

    max_batch: lanes per launch (full batch => immediate launch).
    max_wait: seconds a request may sit in the queue before its default
        deadline forces a (possibly partial) launch. 0 = launch only when
        full or explicitly flushed.
    fairness: "deadline_rr" interleaves tenants round-robin (ordered by
        their head request's deadline); "fifo" preserves strict arrival
        order (the legacy scheduler's grouping).
    cache_bytes: hot-cluster cache budget in bytes of cached stage-1
        plane views (0 disables caching — every flush streams from HBM).
    prior_clusters: how many recently-probed clusters to remember per
        tenant (the session prior that pre-warms the cache each flush).
    preload: EdgeRAG-style hot preload — pin every batch tenant's full
        cluster set into the slab at first contact, but ONLY when the
        whole batch fits the byte budget together (a budget under
        pressure falls back to the per-probe prior warming, never to
        admission/eviction thrash). Fully-resident tenants are then
        served from the cache's COMPACT block table: densely packed slab
        slots make it narrower than the plane table, so steady-state
        launches gather and score fewer rows per probe.
    auto_flush: launch full batches directly from submit() instead of
        waiting for poll()/flush().
    async_depth: how many dispatched launches may stay IN FLIGHT as
        unresolved device futures before the host blocks on the oldest
        one. 2 (the default) double-buffers: the host forms, warms and
        dispatches batch k+1 while the device scores batch k. 0 restores
        the legacy synchronous contract — every launch is resolved
        before `_launch` returns (the open-loop bench's baseline).
    precision_tiers: per-cluster ADAPTIVE PRECISION in the hot-cluster
        cache (the stage-0 prescreen's serving-side half). Hot clusters
        stay FULL tier (nibble plane rows slab-resident, stage-1 hits
        serve on-chip); under slot/byte pressure the LRU full entry is
        DEMOTED to the sign tier — its slab slots are freed but its
        1-bit sign bytes stay charged to the budget (stage-0 still
        serves on-chip; stage-1 re-streams the plane) — and cold misses
        are admitted at the sign tier first, promoted back to full on a
        re-probe. False (default) is the PR 5 cache unchanged: every
        entry full-tier, eviction drops entries outright.
    """

    max_batch: int = 16
    max_wait: float = 0.005
    fairness: str = "deadline_rr"
    cache_bytes: int = 0
    prior_clusters: int = 8
    preload: bool = False
    auto_flush: bool = True
    async_depth: int = 2
    precision_tiers: bool = False

    def __post_init__(self):
        if self.max_batch < 1:
            raise ValueError("max_batch must be >= 1")
        if self.max_wait < 0:
            raise ValueError("max_wait must be >= 0")
        if self.async_depth < 0:
            raise ValueError("async_depth must be >= 0 (0 = synchronous)")
        if self.fairness not in ("deadline_rr", "fifo"):
            raise ValueError(f"unknown fairness policy {self.fairness!r}")
        if self.cache_bytes < 0 or self.prior_clusters < 0:
            raise ValueError("cache_bytes/prior_clusters must be >= 0")
        if self.preload and self.cache_bytes == 0:
            raise ValueError("preload=True pins clusters into the "
                             "hot-cluster cache slab: it needs a "
                             "cache_bytes budget > 0")
        if self.precision_tiers and self.cache_bytes == 0:
            raise ValueError("precision_tiers=True tiers the hot-cluster "
                             "cache's entries: it needs a cache_bytes "
                             "budget > 0")


class RequestHandle:
    """Future-style handle for one submitted query.

    State machine (`state` property):

        pending ──admission──> admitted ──dispatch──> in_flight
                                                          │ retire
                                                          ▼
                                                      resolved

    * ``pending``: queued, not yet picked into a batch.
    * ``admitted``: picked into a batch that is being formed/dispatched
      (a transient state — observable only from inside the runtime or if
      a dispatch raises).
    * ``in_flight``: the batch's device computation was dispatched; the
      result is an unresolved device future on the completion queue.
    * ``resolved``: the launch was retired — `result()` returns numpy
      row views immediately.

    `done()` never blocks: it reports resolved, or probes the in-flight
    launch's device buffers (`jax.Array.is_ready`) and retires the
    completion queue through it when they landed. `result(wait=False)`
    returns ``None`` as the well-defined not-ready signal (it used to
    raise). `result()` (``wait=True``) blocks only as far as needed:
    in-flight requests retire their own launch, queued requests drain
    the runtime via `flush()`."""

    __slots__ = ("request_id", "tenant_id", "deadline", "launch_index",
                 "_runtime", "_result", "_inflight")

    def __init__(self, runtime: "ServingRuntime", request_id: int,
                 tenant_id: int, deadline: float):
        self.request_id = request_id
        self.tenant_id = tenant_id
        self.deadline = deadline
        self.launch_index: int | None = None   # which launch admitted it
        self._runtime = runtime
        self._result: RetrievalResult | None = None
        self._inflight: "_InFlight | None" = None

    @property
    def state(self) -> str:
        if self._result is not None:
            return "resolved"
        if self._inflight is not None:
            return "in_flight"
        if self.launch_index is not None:
            return "admitted"
        return "pending"

    def done(self) -> bool:
        """Non-blocking: True iff `result()` would return immediately.

        An in-flight request whose device buffers landed is retired here
        (along with every earlier launch on the completion queue — the
        device executes in dispatch order, so they landed too)."""
        if self._result is not None:
            return True
        infl = self._inflight
        if infl is None or not infl.is_ready():
            return False
        self._runtime._retire_through(infl)
        return True

    def result(self, *, wait: bool = True) -> RetrievalResult | None:
        if self._result is None:
            if not wait:
                return self._result if self.done() else None
            if self._inflight is not None:
                self._runtime._retire_through(self._inflight)
            else:
                self._runtime.flush()
        assert self._result is not None
        return self._result

    def __repr__(self):  # pragma: no cover - debugging aid
        return (f"RequestHandle(id={self.request_id}, "
                f"tenant={self.tenant_id}, {self.state})")


@dataclasses.dataclass
class _Pending:
    handle: RequestHandle
    query: np.ndarray             # (D,) int8
    seq: int                      # arrival order
    submit_ts: float = 0.0        # submit clock (queue-wait histogram)


@dataclasses.dataclass
class _InFlight:
    """One dispatched-but-unresolved launch on the completion queue.

    `res` holds the launch's device arrays as futures; `book` is the
    deferred host bookkeeping of the cached path (the selection readback
    + ledger/LRU/admission/prior updates), run at retire time so the
    host never blocks on a readback between dispatches. `admit_now` is
    the launch's admission clock (queue-wait histogram + trace ends stay
    on the injectable clock — deterministic under simulated schedules);
    `dispatch_t` is the real monotonic dispatch instant the resolve-lag
    histogram measures against."""

    group: list[_Pending]
    res: RetrievalResult          # device arrays (futures until retired)
    launch_index: int
    admit_now: float
    dispatch_t: float
    book: "collections.abc.Callable[[], None] | None" = None

    def is_ready(self) -> bool:
        """Non-blocking device-completion probe. All three outputs come
        from one jitted program, so probing one suffices; arrays without
        `is_ready` (e.g. already-materialized numpy) count as ready."""
        probe = getattr(self.res.indices, "is_ready", None)
        return True if probe is None else bool(probe())


# Per-cluster precision tiers (the adaptive-precision cascade's
# serving-side half). A combined block belongs to exactly one tier:
TIER_PLANE = 0   # arena plane block (not cache-managed)
TIER_SIGN = 1    # resident at 1-bit precision: only the cluster's sign
#                  bytes are budget-charged; stage-0 serves on-chip,
#                  stage-1 re-streams the nibble plane from HBM
TIER_FULL = 2    # resident at full nibble precision: slab slots hold the
#                  4-bit msb rows; stage-0 AND stage-1 serve on-chip


@dataclasses.dataclass
class _SlabEntry:
    slab_blocks: np.ndarray       # (nblk,) int32 slab-region block ids
    n_rows: int                   # live rows packed into those blocks
    nbytes: int                   # budget charge: nblk*block_rows*
    #                               bytes_per_row (full tier) or the
    #                               cluster's 1-bit sign bytes (sign tier)
    tier: int = TIER_FULL         # TIER_SIGN or TIER_FULL
    plane_blocks: np.ndarray | None = None  # the cluster's plane block
    #                               ids (sign-tier routing + tier sidecar)


def _pow2(n: int) -> int:
    return 1 << (n - 1).bit_length() if n > 1 else 1


@functools.partial(jax.jit, donate_argnums=(0, 1, 2, 3))
def _apply_fills(plane, inv_norms, block_gid0, block_count,
                 row_src_dst, blk_ids, blk_gid0, blk_count):
    """In-place admission fills on the combined plane + its sidecars.

    row_src_dst is one (2, Fr) int32 array of (source plane row ->
    destination combined row) copies — ROW granular, so densely packed
    slab blocks can draw from mid-block run starts; blk_* are the (Fb,)
    per-block origin scalars (first global row id, live-row count) of
    the filled slab blocks. All four device buffers are DONATED: a fill
    touches only the written rows/scalars instead of re-materializing
    the slab. Callers pad Fr and Fb to powers of two by repeating the
    last element — duplicate writes of identical data, so the scatters
    stay deterministic."""
    src, dst = row_src_dst[0], row_src_dst[1]
    return (plane.at[dst].set(plane[src]),
            inv_norms.at[dst].set(inv_norms[src]),
            block_gid0.at[blk_ids].set(blk_gid0),
            block_count.at[blk_ids].set(blk_count))


@functools.partial(jax.jit, static_argnames=("num_clusters",))
def _packed_sidecar(owner, labels, *, num_clusters):
    return engine.packed_membership(owner, labels, num_clusters)


@jax.jit
def _sign_sidecar(msb_plane):
    """Combined 1-bit sign plane from the combined msb plane — one tiny
    device op per plane change (slab fill / rebuild); see
    bitplanar.sign_plane_from_msb for the bit-layout identity."""
    return bitplanar.sign_plane_from_msb(msb_plane)


@jax.jit
def _inv_norm_sidecar(norms_sq):
    """The cosine key's per-row f32 factor, precomputed once per arena
    generation: rsqrt(max(norm, 1)) for live rows, 0 for empty ones —
    gathering this and multiplying reproduces cosine_key_f32's bits
    exactly (same rsqrt input values, same f32 product)."""
    n = jnp.maximum(norms_sq.astype(jnp.float32), 1.0)
    return jnp.where(norms_sq > 0, jax.lax.rsqrt(n), 0.0)


class HotClusterCache:
    """Byte-budgeted LRU of hot cluster views in a device-resident slab.

    The slab is a cache-owned EXTENSION REGION of the arena's stage-1
    plane: one combined device array ``[arena plane | slab rows]`` (plus
    f32 inverse-norm and per-block origin sidecars), carved into
    `block_rows`-row slots. Entries are keyed (tenant, cluster); each
    holds the slab slots its cluster's rows were copied into. The host
    never sees the bytes — admission copies rows plane->slab ON DEVICE
    (donated, in place), and a launch consumes the slab through an int32
    indirection table (`combined_table`/`compact_table`) that points
    each resident (lane, cluster) at its slab slots and everything else
    at the arena plane.

    Slab slots are DENSELY PACKED: a contiguous cluster run is copied
    row-contiguously into ``ceil(rows/block_rows)`` slots (a fragmented
    run falls back to mirroring its whole plane blocks), and each slot
    records (first global row id, live-row count) origin scalars the
    cascade reads back in-graph. Packing is what lets `compact_table`
    hand a fully-resident launch a NARROWER block table than the plane's
    (a straddling run needs one more plane block than slab slots) — the
    slab's wall-clock win on top of never re-streaming hit bytes.

    Entries are valid only for the arena generation they were copied
    under: `sync_generation` clears the slot map (and lazily rebuilds the
    combined array) whenever the arena mutated, so a stale view can never
    be served — correctness never depends on the eviction heuristic.
    Within a generation, eviction is least-recently-used under
    `budget_bytes` (slot-granular). Empty clusters are admissible as
    zero-slot entries so their repeat probes are hits, not fresh misses.
    """

    def __init__(self, budget_bytes: int, *, registry=None,
                 precision_tiers: bool = False):
        if budget_bytes < 0:
            raise ValueError("budget_bytes must be >= 0")
        self.precision_tiers = precision_tiers
        # Counters live in a metrics registry (the serving runtime's when
        # observability is on, a private one otherwise — a counter update
        # is one int add either way, and hits/misses/... stay readable as
        # attributes for existing callers). snapshot()/reset_stats() give
        # WINDOWED reads: a long-lived runtime or a bench section resets,
        # runs its window, and reads rates for just that window instead
        # of a lifetime-cumulative mixed-window average.
        self.registry = registry if registry is not None else (
            MetricsRegistry())
        self._hits = self.registry.counter("cache_hits")
        self._misses = self.registry.counter("cache_misses")
        self._evictions = self.registry.counter("cache_evictions")
        self._stale_evictions = self.registry.counter(
            "cache_stale_evictions")
        self._rejected = self.registry.counter("cache_rejected")
        self._fill_bytes = self.registry.counter("cache_fill_bytes")
        self._fill_dispatches = self.registry.counter(
            "cache_fill_dispatches")
        self._demotions = self.registry.counter("cache_demotions")
        self._promotions = self.registry.counter("cache_promotions")
        self.budget_bytes = budget_bytes
        self.block_rows: int | None = None
        self.bytes_per_row: int | None = None
        self.num_slab_blocks = 0
        self._entries: "collections.OrderedDict[tuple[int, int], _SlabEntry]" = (
            collections.OrderedDict())
        self._free: list[int] = []
        self._generation = -1
        # version bumps on ANY slot-map membership change (put / evict /
        # invalidation): launches key their cached indirection tables on
        # it, so a steady-state (fully warm) flush re-uses the same
        # device table with zero host work.
        self.version = 0
        self._slab_plane = None       # jnp (N + S*block_rows, D//2) uint8
        self._inv_norms = None        # jnp (N + S*block_rows,) f32
        # Combined 1-bit sign plane + per-slot tier sidecar, both derived
        # lazily and cached per plane/slot-map state (see the properties).
        self._plane_version = 0       # bumps whenever _slab_plane changes
        self._sign_cache: tuple[int, jax.Array] | None = None
        self._tier_cache: tuple[int, jax.Array] | None = None
        self._packed = None           # jnp (N,) int32 membership sidecar
        self._gid0 = None             # jnp (NB + S,) int32 block origins
        self._cnt = None              # jnp (NB + S,) int32 live-row counts
        self._plane_rows = 0
        self._table_cache: dict = {}  # key -> (version, ...) device tables
        # Incremental indirection state: per tenant, the set of resident
        # clusters and a lazily-built (host row, combined row) pair kept
        # in sync by put/evict — so a launch's table build is a handful
        # of row copies, never a loop over every resident entry.
        self._by_tenant: dict[int, set[int]] = {}
        self._nonempty: dict[int, int] = {}   # resident nonempty entries
        self._tenant_rows: dict[int, tuple[int, np.ndarray, np.ndarray]] = {}
        # Pending admission fills, keyed by DESTINATION so a slot reissued
        # before the next flush deterministically carries its newest
        # owner's rows (stale row writes land on masked pads).
        self._fill_rows: dict[int, int] = {}          # dst slab row -> src
        self._fill_blocks: dict[int, tuple[int, int]] = {}  # slot -> scalars
        self.bytes_used = 0

    def __len__(self) -> int:
        return len(self._entries)

    # Registry-backed counters, still readable as plain attributes.
    @property
    def hits(self) -> int:
        return self._hits.value

    @property
    def misses(self) -> int:
        return self._misses.value

    @property
    def evictions(self) -> int:
        return self._evictions.value

    @property
    def stale_evictions(self) -> int:
        return self._stale_evictions.value

    @property
    def rejected(self) -> int:
        """Views larger than the whole slab (refused admission)."""
        return self._rejected.value

    @property
    def demotions(self) -> int:
        """Full-tier entries squeezed down to the sign tier."""
        return self._demotions.value

    @property
    def promotions(self) -> int:
        """Sign-tier entries re-admitted at full precision on a re-probe."""
        return self._promotions.value

    def snapshot(self) -> dict:
        """Current counter values (cumulative since the last
        `reset_stats`). Pair with `reset_stats` for windowed hit rates:
        ``reset_stats(); <serve a window>; snapshot()`` reads rates for
        exactly that window, not a lifetime average over mixed phases
        (cold fill + steady state)."""
        out = {"hits": self.hits, "misses": self.misses,
               "evictions": self.evictions,
               "stale_evictions": self.stale_evictions,
               "rejected": self.rejected,
               "fill_bytes": self._fill_bytes.value,
               "fill_dispatches": self._fill_dispatches.value}
        if self.precision_tiers:
            out["demotions"] = self.demotions
            out["promotions"] = self.promotions
            out["sign_entries"] = sum(
                1 for e in self._entries.values() if e.tier == TIER_SIGN)
            out["full_entries"] = sum(
                1 for e in self._entries.values() if e.tier == TIER_FULL)
        return out

    def reset_stats(self) -> None:
        """Zero the event counters (hit/miss/eviction/fill ledgers) —
        the cache CONTENTS and byte accounting are untouched, so this
        only re-bases what `snapshot` reports."""
        for c in (self._hits, self._misses, self._evictions,
                  self._stale_evictions, self._rejected, self._fill_bytes,
                  self._fill_dispatches, self._demotions, self._promotions):
            c.reset()

    @property
    def generation(self) -> int:
        return self._generation

    @property
    def slab_plane(self):
        return self._slab_plane

    @property
    def inv_norms(self):
        return self._inv_norms

    @property
    def packed_labels(self):
        return self._packed

    def _reset_slots(self) -> None:
        self._entries.clear()
        # allocation pops from the tail: reversed so slots hand out 0, 1, ...
        self._free = list(range(self.num_slab_blocks))[::-1]
        self._table_cache.clear()
        self._by_tenant.clear()
        self._nonempty.clear()
        self._tenant_rows.clear()
        self._fill_rows.clear()
        self._fill_blocks.clear()
        self.bytes_used = 0
        self.version += 1
        self._tier_cache = None

    def configure(self, block_rows: int, bytes_per_row: int) -> None:
        """Pin the slot geometry (idempotent; a change re-carves the slab
        and invalidates every entry)."""
        if (block_rows, bytes_per_row) == (self.block_rows,
                                           self.bytes_per_row):
            return
        if self.precision_tiers and bytes_per_row % 4:
            # sign bytes per row = bytes_per_row / 4 (1 bit vs 4 bits per
            # dim): the tiers' budget arithmetic needs it to be integral.
            raise ValueError("precision_tiers needs dim % 8 == 0 "
                             f"(bytes_per_row {bytes_per_row} % 4 != 0)")
        self._stale_evictions.inc(len(self._entries))
        self.block_rows = block_rows
        self.bytes_per_row = bytes_per_row
        self.num_slab_blocks = self.budget_bytes // (block_rows
                                                     * bytes_per_row)
        self._slab_plane = self._inv_norms = self._packed = None
        self._gid0 = self._cnt = None
        self._reset_slots()

    @property
    def generation(self) -> int:
        """The arena generation the slab currently mirrors."""
        return self._generation

    def sync_generation(self, generation: int) -> None:
        """Invalidate everything copied under an older arena state."""
        if generation != self._generation:
            self._stale_evictions.inc(len(self._entries))
            self._slab_plane = self._inv_norms = self._packed = None
            self._gid0 = self._cnt = None
            self._reset_slots()
            self._generation = generation

    def ensure_slab(self, msb_plane, norms_sq, owner, labels,
                    num_clusters: int) -> None:
        """(Re)build the combined plane + sidecars for this generation.

        One device concatenation per arena mutation — this replaces the
        pre-slab design's full HOST mirror of the plane (and the per-
        launch host->device view uploads that came with it). Also builds
        the launch sidecars the slab cascade consumes instead of
        re-deriving them per launch: the f32 inverse-norm factors and
        the packed (owner, label) membership rows."""
        if self._slab_plane is not None:
            return
        if self.block_rows is None:
            raise RuntimeError("configure() the slot geometry first")
        n, d2 = msb_plane.shape
        if n % self.block_rows:
            raise ValueError(f"plane rows {n} not a multiple of "
                             f"block_rows {self.block_rows}")
        self._plane_rows = n
        slab_rows = self.num_slab_blocks * self.block_rows
        self._slab_plane = jnp.concatenate(
            [msb_plane, jnp.zeros((slab_rows, d2), jnp.uint8)])
        self._inv_norms = jnp.concatenate(
            [_inv_norm_sidecar(norms_sq),
             jnp.zeros((slab_rows,), jnp.float32)])
        self._packed = _packed_sidecar(owner, labels,
                                       num_clusters=num_clusters)
        # Per-block origin scalars: plane blocks are their own origin
        # (gid0 = block * block_rows, full count); slab blocks start
        # empty (count 0 — an unfilled slot can never surface a row) and
        # are written by admission fills.
        nb = n // self.block_rows
        self._gid0 = jnp.concatenate(
            [jnp.arange(nb, dtype=jnp.int32) * self.block_rows,
             jnp.zeros((self.num_slab_blocks,), jnp.int32)])
        self._cnt = jnp.concatenate(
            [jnp.full((nb,), self.block_rows, jnp.int32),
             jnp.zeros((self.num_slab_blocks,), jnp.int32)])
        self._plane_version += 1

    @property
    def block_gid0(self):
        return self._gid0

    @property
    def block_count(self):
        return self._cnt

    @property
    def sign_plane(self):
        """Combined 1-bit sign plane ``[arena signs | slab signs]``,
        derived from the combined msb plane (the sign of an INT4 code IS
        its msb nibble's top bit, so one derivation covers both regions
        — no second fill pipeline) and cached per plane state: a
        steady-state warm launch with no pending fills re-serves the
        same device array. None when the dim doesn't pack 8-per-byte or
        before `ensure_slab`."""
        if self._slab_plane is None or (self._slab_plane.shape[1] * 2) % 8:
            return None
        if self._sign_cache is None or \
                self._sign_cache[0] != self._plane_version:
            self._sign_cache = (self._plane_version,
                                _sign_sidecar(self._slab_plane))
        return self._sign_cache[1]

    @property
    def block_tier(self):
        """Per-combined-block precision-tier sidecar, (NB + S,) int8:
        TIER_FULL on slab slots held by full-tier entries, TIER_SIGN on
        the plane blocks of sign-tier residents, TIER_PLANE elsewhere
        (including free slots). Diagnostic/ledger metadata — the cascade
        itself routes through the indirection table, never this array.
        Cached per slot-map version."""
        if self._slab_plane is None or self.block_rows is None:
            return None
        if self._tier_cache is None or self._tier_cache[0] != self.version:
            base = self._plane_rows // self.block_rows
            tier = np.zeros(base + self.num_slab_blocks, np.int8)
            for e in self._entries.values():
                if e.tier == TIER_FULL and e.slab_blocks.size:
                    tier[e.slab_blocks + base] = TIER_FULL
                elif e.tier == TIER_SIGN and e.plane_blocks is not None:
                    tier[e.plane_blocks] = TIER_SIGN
            self._tier_cache = (self.version, jnp.asarray(tier))
        return self._tier_cache[1]

    # -- slot map -----------------------------------------------------------

    def get(self, tenant: int, cluster: int) -> _SlabEntry | None:
        entry = self._entries.get((tenant, cluster))
        if entry is None:
            self._misses.inc()
            return None
        self._entries.move_to_end((tenant, cluster))
        self._hits.inc()
        return entry

    def lookup_lane(self, tenant: int, clusters) -> tuple[int, list[int]]:
        """Bulk `get()` for one lane's probed clusters.

        Returns (hit bytes, missing cluster ids) with the same counter
        and LRU semantics as per-cluster get() calls — one hit or miss
        per probed cluster, hits refreshed most-recent in probe order —
        but via one set-membership pass per lane instead of a dict
        transaction per probe (this runs on the serving hot path for
        every launch's (B, nprobe) selection readback)."""
        resident = self._by_tenant.get(tenant)
        if not resident:
            self._misses.inc(len(clusters))
            return 0, list(clusters)
        entries = self._entries
        hit_bytes = 0
        missing: list[int] = []
        nhits = 0
        for c in clusters:
            if c in resident:
                key = (tenant, c)
                hit_bytes += entries[key].nbytes
                entries.move_to_end(key)
                nhits += 1
            else:
                missing.append(c)
        self._hits.inc(nhits)
        self._misses.inc(len(missing))
        return hit_bytes, missing

    def lookup_lane_tiers(self, tenant: int, clusters
                          ) -> tuple[int, int, list[int], list[int]]:
        """`lookup_lane` with the per-tier split the precision-tier
        ledger needs: (full-tier hit bytes, sign-tier hit bytes,
        sign-tier-resident cluster ids, missing cluster ids). Sign-tier
        residents ARE hits (their sign bytes serve stage 0 on-chip and
        their LRU position refreshes) but their stage-1 plane blocks
        still stream from HBM — the caller charges those like misses and
        promotes them back to full tier."""
        resident = self._by_tenant.get(tenant)
        if not resident:
            self._misses.inc(len(clusters))
            return 0, 0, [], list(clusters)
        entries = self._entries
        full_bytes = sign_bytes = nhits = 0
        sign_hits: list[int] = []
        missing: list[int] = []
        for c in clusters:
            if c in resident:
                key = (tenant, c)
                e = entries[key]
                if e.tier == TIER_FULL:
                    full_bytes += e.nbytes
                else:
                    sign_bytes += e.nbytes
                    sign_hits.append(c)
                entries.move_to_end(key)
                nhits += 1
            else:
                missing.append(c)
        self._hits.inc(nhits)
        self._misses.inc(len(missing))
        return full_bytes, sign_bytes, sign_hits, missing

    def peek(self, tenant: int, cluster: int) -> bool:
        """Membership check without touching hit/miss counters or LRU."""
        return (tenant, cluster) in self._entries

    def touch(self, tenant: int, cluster: int) -> None:
        """Refresh an entry's LRU position without counting a hit."""
        if (tenant, cluster) in self._entries:
            self._entries.move_to_end((tenant, cluster))

    @staticmethod
    def _pack_plan(rows: np.ndarray, block_rows: int) -> tuple[bool, int]:
        """(packed?, slab slots) one cluster's rows will occupy:
        ``ceil(rows/br)`` when the run is contiguous (dense packing), its
        distinct plane blocks when fragmented (whole-block mirroring).
        The single source of admission arithmetic — `put` and the
        preload's demand check must never disagree."""
        n_rows = int(rows.size)
        if n_rows == 0:
            return True, 0
        if int(rows[-1]) - int(rows[0]) + 1 == n_rows:
            return True, -(-n_rows // block_rows)
        return False, int(np.unique(rows // block_rows).size)

    @classmethod
    def entry_blocks(cls, rows: np.ndarray, block_rows: int) -> int:
        """Slab slots one cluster's rows will occupy (see _pack_plan)."""
        return cls._pack_plan(np.atleast_1d(np.asarray(rows, np.int64)),
                              block_rows)[1]

    def put(self, tenant: int, cluster: int, rows, *,
            tier: int = TIER_FULL) -> np.ndarray | None:
        """Admit one (tenant, cluster)'s rows into the slab.

        `rows` are the cluster's global plane row ids for that tenant,
        ASCENDING (the order the cold cascade's view streams them — what
        keeps the packed view's candidate order bit-identical). A
        contiguous run is packed densely into ``ceil(rows/block_rows)``
        slots; a fragmented one mirrors its whole plane blocks. The row
        copies and origin scalars are queued for the next `flush_fills`.

        `tier` (precision_tiers mode only): TIER_FULL copies the nibble
        rows into slab slots as always; TIER_SIGN admits the cluster at
        1-bit precision — NO slots, no fills, only its sign bytes
        charged to the budget, with the indirection table left routing
        to the plane blocks (stage-1 streams HBM; stage-0 serves the
        sign bytes on-chip). Under tiers, slot pressure DEMOTES the LRU
        full entry to the sign tier instead of dropping it, and byte
        pressure drops sign-tier entries last.

        Returns the allocated slab slot ids (empty for an empty or
        sign-tier cluster), or None when the view is larger than the
        whole slab/budget. The oversized check runs BEFORE any resident
        entry is replaced: a rejected re-put must leave the existing
        valid entry (and its accounting) untouched instead of
        destroying it on the way to nowhere."""
        if self.block_rows is None:
            raise RuntimeError("configure() the slot geometry first")
        if tier == TIER_SIGN and not self.precision_tiers:
            raise ValueError("sign-tier admission needs precision_tiers")
        br = self.block_rows
        rows = np.atleast_1d(np.asarray(rows, np.int64)).astype(np.int32)
        n_rows = int(rows.size)
        if n_rows == 0:
            tier = TIER_FULL        # zero-slot memo: tiers are moot
        packed, nblk = self._pack_plan(rows, br)
        plane_blocks = np.unique(rows // br).astype(np.int32)
        sign_bytes = nblk * br * (self.bytes_per_row // 4)
        if packed:
            src = rows
            gid0s = [int(rows[0]) + i * br for i in range(nblk)] if n_rows \
                else []
            cnts = [min(br, n_rows - i * br) for i in range(nblk)]
        else:
            blocks = plane_blocks.astype(np.int64)
            src = (blocks[:, None] * br
                   + np.arange(br, dtype=np.int64)).reshape(-1)
            gid0s = (blocks * br).tolist()
            cnts = [br] * nblk
        if (nblk > self.num_slab_blocks if tier == TIER_FULL
                else sign_bytes > self.budget_bytes):
            # Refuse admission outright: squeezing one oversized view in
            # would first flush EVERY other tenant's warm entries and
            # then evict the new entry itself — an empty cache for
            # nothing. The cluster stays re-streamed from HBM instead.
            self._rejected.inc()
            return None
        key = (tenant, cluster)
        old = self._entries.pop(key, None)
        if old is not None:
            self._drop_entry(key, old)
        nslots = nblk if tier == TIER_FULL else 0
        while len(self._free) < nslots:
            # LRU scan skipping zero-slot entries: evicting an
            # empty-cluster memo frees nothing — it would only destroy
            # the memoization and inflate the eviction counter.
            victim = next((k for k, e in self._entries.items()
                           if e.slab_blocks.size), None)
            if victim is None:
                break
            if self.precision_tiers:
                self._demote(victim)    # free the slots, keep the signs
            else:
                self._drop_entry(victim, self._entries.pop(victim))
                self._evictions.inc()
        nbytes = (nblk * br * self.bytes_per_row if tier == TIER_FULL
                  else sign_bytes)
        if self.precision_tiers:
            # Byte pressure (sign charges consume budget without holding
            # slots): demote LRU full entries first, then drop LRU
            # sign-tier entries — full precision degrades before any
            # residency is lost outright.
            while self.bytes_used + nbytes > self.budget_bytes:
                vic = next((k for k, e in self._entries.items()
                            if e.tier == TIER_FULL and e.slab_blocks.size),
                           None)
                if vic is not None:
                    self._demote(vic)
                    continue
                vic = next((k for k, e in self._entries.items()
                            if e.nbytes), None)
                if vic is None:
                    break
                self._drop_entry(vic, self._entries.pop(vic))
                self._evictions.inc()
        dst = np.asarray([self._free.pop() for _ in range(nslots)],
                         np.int32)
        self._entries[key] = _SlabEntry(slab_blocks=dst, n_rows=n_rows,
                                        nbytes=nbytes, tier=tier,
                                        plane_blocks=plane_blocks)
        self.bytes_used += nbytes
        self._by_tenant.setdefault(tenant, set()).add(cluster)
        if n_rows and tier == TIER_FULL:
            self._nonempty[tenant] = self._nonempty.get(tenant, 0) + 1
        row = self._tenant_rows.get(tenant)
        if tier == TIER_FULL:
            self._fill_bytes.inc(nbytes)
            # Queue the admission fills: row copies land at the slots'
            # rows in packed order; scalar writes record each slot's
            # origin.
            for i, slot in enumerate(dst.tolist()):
                self._fill_blocks[slot] = (gid0s[i], cnts[i])
                seg = src[i * br:(i + 1) * br].tolist()
                slot_row0 = slot * br
                for j, s in enumerate(seg):
                    self._fill_rows[slot_row0 + j] = int(s)
            if row is not None:
                base = self._plane_rows // br
                row[2][cluster, :nblk] = dst + base
                row[2][cluster, nblk:] = -1
        elif row is not None:
            # Sign tier holds no slab rows: stage-1 keeps streaming the
            # cluster's plane blocks, so the combined row stays the
            # host plane row.
            row[2][cluster] = row[1][cluster]
        self.version += 1
        return dst

    def _demote(self, key: tuple[int, int]) -> None:
        """Squeeze a full-tier entry down to the sign tier IN PLACE:
        free its slab slots and shrink its budget charge to the
        cluster's 1-bit sign bytes, keeping its LRU position and
        residency. The incremental indirection row rolls back to the
        plane blocks (stage-1 re-streams; stage-0 stays on-chip)."""
        tenant, cluster = key
        e = self._entries[key]
        self.bytes_used -= e.nbytes
        self._free.extend(int(b) for b in e.slab_blocks)
        if e.n_rows:
            self._nonempty[tenant] = self._nonempty.get(tenant, 1) - 1
        sign_bytes = (e.slab_blocks.size * self.block_rows
                      * (self.bytes_per_row // 4))
        self._entries[key] = dataclasses.replace(
            e, slab_blocks=np.empty(0, np.int32), nbytes=sign_bytes,
            tier=TIER_SIGN)
        self.bytes_used += sign_bytes
        row = self._tenant_rows.get(tenant)
        if row is not None:
            row[2][cluster] = row[1][cluster]
        self._demotions.inc()
        self.version += 1

    def promote(self, tenant: int, cluster: int, rows) -> np.ndarray | None:
        """Re-admit a sign-tier resident at full precision (re-probe =
        the cluster is hot again)."""
        self._promotions.inc()
        return self.put(tenant, cluster, rows, tier=TIER_FULL)

    def _drop_entry(self, key: tuple[int, int], entry: _SlabEntry) -> None:
        """Return an entry's slots and roll its tenant's combined row back
        to the plane blocks (the incremental inverse of admission).

        Pending fills aimed at the freed slots are left queued: they are
        keyed by destination, so a slot reissued before the next flush
        simply overwrites them with its new owner's rows, and writes to
        a slot that stays free touch rows no table references — either
        way the flush stays deterministic."""
        tenant, cluster = key
        self.bytes_used -= entry.nbytes
        self._free.extend(int(b) for b in entry.slab_blocks)
        if entry.n_rows and entry.tier == TIER_FULL:
            # `fully_resident` (the compact-table precondition) counts
            # FULL-tier views only: a sign-tier resident has no slab rows
            # to serve a compact launch from.
            self._nonempty[tenant] = self._nonempty.get(tenant, 1) - 1
        clusters = self._by_tenant.get(tenant)
        if clusters is not None:
            clusters.discard(cluster)
        row = self._tenant_rows.get(tenant)
        if row is not None:
            row[2][cluster] = row[1][cluster]

    def fully_resident(self, tenant: int, nonempty_clusters: int) -> bool:
        """Whether every one of the tenant's `nonempty_clusters` real
        cluster views is currently slab-resident (entries are only ever
        admitted from those views, so a count match is set equality) —
        the precondition for serving the tenant from a compact table."""
        return self._nonempty.get(tenant, 0) >= nonempty_clusters

    def flush_fills(self) -> None:
        """Apply every queued admission fill in ONE device dispatch, in
        place (plane bytes, inverse-norm sidecar, and the filled slots'
        origin scalars). Deferral is safe because nothing reads slab
        rows between launches and every launch flushes before it builds
        its indirection table — a slot is always written before it can
        be served; a generation sync drops the queue with the slot map.
        Row and block counts are padded to powers of two so varying fill
        sizes re-use a bounded family of compiled scatters."""
        if not self._fill_blocks or self._slab_plane is None:
            return
        self._fill_dispatches.inc()
        base_row = self._plane_rows
        base_blk = self._plane_rows // self.block_rows
        rows = sorted(self._fill_rows.items())            # (dst, src)
        blks = sorted(self._fill_blocks.items())          # (slot, (g, c))
        self._fill_rows = {}
        self._fill_blocks = {}
        fr, fb = _pow2(len(rows)), _pow2(len(blks))
        rows += [rows[-1]] * (fr - len(rows))
        blks += [blks[-1]] * (fb - len(blks))
        src_dst = np.asarray([[s for _, s in rows],
                              [d + base_row for d, _ in rows]], np.int32)
        ids = np.asarray([b + base_blk for b, _ in blks], np.int32)
        g0 = np.asarray([g for _, (g, _) in blks], np.int32)
        cn = np.asarray([c for _, (_, c) in blks], np.int32)
        (self._slab_plane, self._inv_norms, self._gid0,
         self._cnt) = _apply_fills(
            self._slab_plane, self._inv_norms, self._gid0, self._cnt,
            jnp.asarray(src_dst), jnp.asarray(ids), jnp.asarray(g0),
            jnp.asarray(cn))
        self._plane_version += 1

    def _tenant_row(self, tenant: int, host_row: np.ndarray) -> np.ndarray:
        """The tenant's (K, MB) combined-space row: its host plane row
        with every resident cluster's prefix overridden by slab blocks.
        Built once (per table width) and then kept in sync INCREMENTALLY
        by put/evict — a launch never loops over resident entries.

        Entry/table alignment is a generation invariant: entries are
        admitted FROM these same tables and every arena mutation clears
        the slot map, so the override prefixes cannot desynchronize
        within a generation."""
        cached = self._tenant_rows.get(tenant)
        if cached is not None and cached[0] == host_row.shape[1]:
            return cached[2]
        comb_row = host_row.copy()
        base = self._plane_rows // self.block_rows
        for c in self._by_tenant.get(tenant, ()):
            e = self._entries.get((tenant, c))
            if e is not None and e.slab_blocks.size:
                nblk = e.slab_blocks.size
                comb_row[c, :nblk] = e.slab_blocks + base
                # A packed entry can hold the view in FEWER blocks than
                # the plane table lists (no straddle): hole the tail so
                # the leftover plane blocks can't re-surface its rows.
                comb_row[c, nblk:] = -1
        self._tenant_rows[tenant] = (host_row.shape[1], host_row.copy(),
                                     comb_row)
        return comb_row

    def combined_table(self, tids, host_table: np.ndarray):
        """The launch's (B, K, MB) int32 indirection table, on device.

        host_table is the index's np plane block table (the SAME table
        the ClusterPolicy carries); resident (lane, cluster) prefixes are
        redirected into the slab region via the incrementally-maintained
        per-tenant rows. Cached per (slot-map version, tenant tuple): a
        fully warm steady state re-issues the same device table with
        zero host work."""
        key = tids.tobytes()
        hit = self._table_cache.get(key)
        if hit is not None and hit[0] == self.version and \
                hit[1] == id(host_table):
            return hit[2]
        comb = host_table.copy()
        for i, t in enumerate(np.asarray(tids).tolist()):
            if t >= 0 and self._by_tenant.get(t):
                comb[i] = self._tenant_row(int(t), host_table[i])
        table = jnp.asarray(comb)
        if len(self._table_cache) > 64:
            self._table_cache.clear()
        self._table_cache[key] = (self.version, id(host_table), table)
        return table

    def compact_table(self, tids, num_clusters: int):
        """The fully-resident launch's (B, K, W) indirection table, W =
        the widest RESIDENT entry's slot count (pow2-bucketed so table
        widths — and therefore compiled cascades — stay bounded).

        Because packed slab entries never straddle plane-block
        boundaries, W is typically narrower than the plane table's MB —
        the launch gathers and scores fewer rows per probe. Only valid
        when every batch tenant is fully resident (`fully_resident`);
        the caller falls back to `combined_table` otherwise. Cached per
        (slot-map version, tenant tuple) like the full-width table."""
        key = ("compact", tids.tobytes())
        hit = self._table_cache.get(key)
        if hit is not None and hit[0] == self.version:
            return hit[1], hit[2]
        base = self._plane_rows // self.block_rows
        lanes = np.asarray(tids).tolist()
        w = 1
        for t in set(lanes):
            for c in self._by_tenant.get(t, ()):
                w = max(w, self._entries[(t, c)].slab_blocks.size)
        w = _pow2(w)
        comp = np.full((len(lanes), num_clusters, w), -1, np.int32)
        for i, t in enumerate(lanes):
            for c in self._by_tenant.get(t, ()):
                e = self._entries[(t, c)]
                comp[i, c, :e.slab_blocks.size] = e.slab_blocks + base
        table = jnp.asarray(comp)
        if len(self._table_cache) > 64:
            self._table_cache.clear()
        self._table_cache[key] = (self.version, table, w)
        return table, w


class ServingRuntime:
    """Deadline-batched, cache-warmed serving loop over a MultiTenantIndex.

    The dynamic-batcher successor of `tenancy.CrossTenantBatchScheduler`
    (which is now a thin wrapper over this class): submit() returns a
    future-style RequestHandle, poll(now) launches every batch that is
    full or past its oldest deadline, flush() drains the queue. All
    ledgers accumulate in engine.SchedulePlan units (exact analytic
    bytes), split HBM vs cache-SRAM when the hot-cluster cache serves
    part of a launch's stage-1 view.
    """

    def __init__(self, index, cfg: RuntimeConfig | None = None, *,
                 registry=None, tracer=None):
        self.index = index
        self.cfg = cfg or RuntimeConfig()
        # Observability handles (repro.obs). Defaults are the null
        # implementations: every instrumentation site below is a no-op
        # call and every derived publication (plan fan-out, energy
        # pricing) is skipped behind `registry.enabled` — the
        # metrics-off hot path is the pre-observability hot path, pinned
        # by the bench's parity + zero-extra-compiles + overhead gates.
        self.registry = NULL_REGISTRY if registry is None else registry
        self.tracer = NULL_TRACER if tracer is None else tracer
        reg = self.registry
        self._m_submitted = reg.counter("serve_requests_submitted")
        self._m_resolved = reg.counter("serve_requests_resolved")
        self._m_launches = reg.counter("serve_launches")
        self._m_deferred_fills = reg.counter("serve_deferred_fill_entries")
        self._m_prefetch_bytes = reg.counter("serve_prefetch_bytes")
        self._m_queue_wait = reg.histogram("serve_queue_wait_seconds")
        self._m_occupancy = reg.histogram("serve_batch_occupancy")
        self._m_launch_wall = reg.histogram("serve_launch_wall_seconds")
        self._m_inflight = reg.gauge("serve_inflight_depth")
        self._m_resolve_lag = reg.histogram("serve_resolve_lag_seconds")
        # Per-stage energy split: handles held per stage name (like the
        # gauges above) and SAMPLED every 8th launch — the split is a
        # steady-state distribution, not a per-launch ledger, and keeping
        # it off the per-launch path holds the metrics-enabled runtime
        # inside the <=2% observability overhead contract. The headline
        # energy_uj_per_query histogram stays per-launch exact.
        self._m_stage_uj: dict[str, object] = {}
        self._stage_energy_tick = 0
        # Clock discipline: `now` is injectable everywhere (simulated
        # clocks in tests); once any caller supplies one, implicit
        # clocks (flush() via result()) reuse the last seen value so
        # traces stay deterministic instead of mixing in wall time.
        self._last_now = 0.0
        self._simulated = False
        self.cache = (HotClusterCache(self.cfg.cache_bytes,
                                      registry=(reg if reg.enabled
                                                else None),
                                      precision_tiers=(
                                          self.cfg.precision_tiers))
                      if self.cfg.cache_bytes > 0 else None)
        self._queues: "collections.OrderedDict[int, collections.deque[_Pending]]" = (
            collections.OrderedDict())
        # Completion queue: dispatched launches whose device futures are
        # still unresolved, oldest first. Bounded by cfg.async_depth.
        self._inflight: "collections.deque[_InFlight]" = collections.deque()
        self._num_pending = 0
        self._next_id = 0
        self._seq = 0
        # tenant -> recently probed clusters, most recent first (the
        # session prior that warms the cache between turns).
        self._recent: dict[int, list[int]] = {}
        # launch signature -> analytic base SchedulePlan (pure shape
        # arithmetic; identical every steady-state turn).
        self._plan_cache: dict[tuple, engine.SchedulePlan] = {}
        # (arena generation, tids) -> device (B, K) selection validity.
        self._valid_cache: dict[tuple, jax.Array] = {}
        # (generation, tenant) -> (packed demand blocks, nonempty
        # clusters): the preload's admission arithmetic, computed once
        # per arena state instead of rescanning every launch.
        self._tenant_demand: dict[tuple, tuple[int, int]] = {}
        # -- ledgers (engine.SchedulePlan units, exact bytes) --------------
        self.launches = 0
        self.queries_served = 0
        self.stage1_bytes_streamed = 0    # HBM bytes, all launches
        self.stage1_bytes_sram = 0        # cache-served bytes, all launches
        self.stage1_bytes_vmapped = 0     # the one-query-at-a-time path
        self.prefetch_bytes = 0           # prior-warming gathers (HBM)
        self.stage_bytes: dict[str, int] = {}       # per-stage HBM
        self.stage_bytes_sram: dict[str, int] = {}  # per-stage cache-SRAM
        self.last_plan: engine.SchedulePlan | None = None
        # -- decode-side ledger (engine.kv_plan units) ---------------------
        self.decode_steps = 0
        self.decode_bytes_hbm = 0
        self.last_decode_plan: engine.SchedulePlan | None = None

    # -- admission ----------------------------------------------------------

    def submit(self, tenant_id: int, query_codes, *,
               deadline: float | None = None,
               now: float | None = None) -> RequestHandle:
        """Enqueue one request; returns its future-style handle.

        deadline: absolute time (same clock as `now`) by which the
        request must be in a launch; defaults to now + cfg.max_wait."""
        if int(tenant_id) < 0:
            raise ValueError(f"tenant id must be >= 0, got {tenant_id}")
        q = np.asarray(query_codes, np.int8)
        if q.ndim != 1 or q.shape[0] != self.index.arena.dim:
            raise ValueError(f"query must be ({self.index.arena.dim},) int8")
        now = self._clock(now)
        if deadline is None:
            # max_wait == 0 means NO deadline-forced launches (the
            # legacy scheduler contract: launch only when full or
            # explicitly flushed), not launch-immediately.
            deadline = (now + self.cfg.max_wait if self.cfg.max_wait > 0
                        else math.inf)
        handle = RequestHandle(self, self._next_id, int(tenant_id), deadline)
        self._next_id += 1
        pend = _Pending(handle=handle, query=q, seq=self._seq, submit_ts=now)
        self._seq += 1
        self._queues.setdefault(int(tenant_id), collections.deque()).append(
            pend)
        self._num_pending += 1
        self._m_submitted.inc()
        self.tracer.begin("request", handle.request_id, now=now,
                          tid=int(tenant_id), request=handle.request_id)
        if self.cfg.auto_flush and self._num_pending >= self.cfg.max_batch:
            self._launch(self._form_batch(), now)
        return handle

    def _clock(self, now: float | None) -> float:
        """Resolve an optional caller-supplied timestamp.

        The first explicit `now` switches the runtime to simulated time:
        from then on calls WITHOUT a timestamp (flush() via result())
        reuse the last seen value instead of mixing in wall-clock reads,
        so queue-wait histograms and traces stay deterministic under the
        test suite's simulated schedules."""
        if now is None:
            now = self._last_now if self._simulated else time.monotonic()
        else:
            self._simulated = True
        self._last_now = now
        return now

    def pending(self) -> int:
        return self._num_pending

    def _oldest_deadline(self) -> float | None:
        heads = [q[0].handle.deadline for q in self._queues.values() if q]
        return min(heads) if heads else None

    def ready(self, now: float | None = None) -> bool:
        """Would poll() launch something right now?"""
        if self._num_pending >= self.cfg.max_batch:
            return True
        oldest = self._oldest_deadline()
        if oldest is None:
            return False
        now = time.monotonic() if now is None else now
        return oldest <= now

    def next_deadline(self) -> float | None:
        """When the queue next forces a launch (None if empty or no
        pending request carries a finite deadline)."""
        oldest = self._oldest_deadline()
        return None if oldest is None or math.isinf(oldest) else oldest

    def poll(self, now: float | None = None) -> list[RequestHandle]:
        """Launch every batch that is full or past its oldest deadline.

        Returns the handles dispatched by this call (possibly empty — a
        young partial batch keeps waiting for more traffic). Dispatched
        handles are in flight, not necessarily resolved: poll() also
        opportunistically retires launches whose device buffers already
        landed (`reap`), but never blocks on one — that is what
        `flush()`/`barrier()`/`result()` are for."""
        now = self._clock(now)
        launched: list[RequestHandle] = []
        while self._num_pending and self.ready(now):
            launched.extend(self._launch(self._form_batch(), now))
        self.reap()
        return launched

    def flush(self, now: float | None = None) -> list[RequestHandle]:
        """Drain the queue unconditionally (deadlines ignored) and
        barrier: on return every handle this runtime ever dispatched is
        resolved and every deferred ledger/cache bookkeeping has run.
        Returns the handles drained from the queue by THIS call."""
        now = self._clock(now)
        launched: list[RequestHandle] = []
        while self._num_pending:
            launched.extend(self._launch(self._form_batch(), now))
        self.barrier()
        return launched

    def barrier(self) -> int:
        """Retire every in-flight launch (blocking), oldest first.

        Returns how many launches were retired. After a barrier all
        ledgers (`last_plan`, byte counters, cache stats, session
        priors) are final for everything dispatched so far."""
        n = 0
        while self._inflight:
            self._retire(self._inflight.popleft())
            n += 1
        return n

    def reap(self) -> int:
        """Non-blocking retire: resolve launches whose device buffers
        already landed, oldest first, stopping at the first one still
        executing. Returns how many launches were retired."""
        n = 0
        while self._inflight and self._inflight[0].is_ready():
            self._retire(self._inflight.popleft())
            n += 1
        return n

    def in_flight(self) -> int:
        """How many dispatched launches are currently unresolved."""
        return len(self._inflight)

    def _retire_through(self, target: _InFlight) -> None:
        """Retire queue head through `target` inclusive (the device runs
        launches in dispatch order, so everything older landed first)."""
        while self._inflight:
            infl = self._inflight.popleft()
            self._retire(infl)
            if infl is target:
                return

    def _phase(self, name: str):
        """A span over one host phase of a launch, stamped with the
        runtime's last `now` like the launch span (deterministic under a
        simulated clock); its extent shows on the profiler's clock."""
        return self.tracer.span(name, now=self._last_now)

    def _retire(self, infl: _InFlight) -> None:
        """Resolve one launch: materialize the batch's device arrays
        (blocking if still executing), hand out numpy row views, close
        request spans, then run the launch's deferred bookkeeping —
        always in dispatch order, so the cache/ledger mutation sequence
        is the synchronous path's sequence."""
        with self._phase("runtime.retire"):
            res = infl.res
            # Materialize the batch ONCE and hand out numpy row views:
            # slicing jnp arrays per lane would dispatch 3 eager device ops
            # per request (a measurable per-flush tax at serving batch sizes).
            indices = np.asarray(res.indices)
            scores = np.asarray(res.scores)
            cands = np.asarray(res.candidate_indices)
            self._m_resolve_lag.observe(
                max(0.0, time.monotonic() - infl.dispatch_t))
            for i, req in enumerate(infl.group):
                req.handle._result = RetrievalResult(
                    indices=indices[i], scores=scores[i],
                    candidate_indices=cands[i])
                req.handle._inflight = None
                self._m_queue_wait.observe(
                    max(0.0, infl.admit_now - req.submit_ts))
                self.tracer.end(req.handle.request_id, now=infl.admit_now,
                                request=req.handle.request_id,
                                launch=infl.launch_index)
            self._m_resolved.inc(len(infl.group))
            if infl.book is not None:
                infl.book()
            self._m_inflight.set(float(len(self._inflight)))

    def _form_batch(self) -> list[_Pending]:
        """Pick up to max_batch pending requests.

        fifo: strict arrival order (the legacy scheduler's grouping).
        deadline_rr: one request per tenant, round-robin, tenants ordered
        by their head request's deadline (FIFO within a tenant) — the
        most urgent tenants are served first and no tenant can occupy
        more than its share of a contended flush."""
        group: list[_Pending] = []
        if self.cfg.fairness == "fifo":
            # k-way merge of the per-tenant FIFO queues by arrival seq:
            # O(B log T) per batch instead of a min() scan per request.
            heads = [(q[0].seq, t) for t, q in self._queues.items() if q]
            heapq.heapify(heads)
            while len(group) < self.cfg.max_batch and heads:
                _, tid = heapq.heappop(heads)
                group.append(self._pop_from(tid))
                queue = self._queues.get(tid)
                if queue:
                    heapq.heappush(heads, (queue[0].seq, tid))
        else:
            # One urgency sort per BATCH (head deadline, then arrival),
            # then round-robin passes over that order until the batch is
            # full or the queues drain.
            order = sorted(
                (t for t, q in self._queues.items() if q),
                key=lambda t: (self._queues[t][0].handle.deadline,
                               self._queues[t][0].seq))
            while len(group) < self.cfg.max_batch:
                progressed = False
                for tid in order:
                    if len(group) >= self.cfg.max_batch:
                        break
                    if self._queues.get(tid):
                        group.append(self._pop_from(tid))
                        progressed = True
                if not progressed:
                    break
        return group

    def _pop_from(self, tid: int) -> _Pending:
        """Pop a tenant's head request; drop its deque once drained so a
        long-lived runtime's admission scans stay proportional to the
        ACTIVE tenants, not every tenant ever seen."""
        queue = self._queues[tid]
        pend = queue.popleft()
        self._num_pending -= 1
        if not queue:
            del self._queues[tid]
        return pend

    # -- launching ----------------------------------------------------------

    @staticmethod
    def _bucket(n: int) -> int:
        return 1 << (n - 1).bit_length() if n > 1 else 1

    def _launch(self, group: list[_Pending],
                now: float | None = None) -> list[RequestHandle]:
        """Dispatch one batch and enqueue it on the completion queue.

        Host cost here is admission + dispatch only: the device arrays
        stay in flight as futures and every readback-dependent step
        (handing out results, the cached path's ledger/LRU/admission
        bookkeeping) is deferred to `_retire`. With async_depth=0 the
        backpressure loop below retires the launch before returning —
        the legacy synchronous contract."""
        b = len(group)
        if b == 0:
            return []
        now = self._clock(now)
        pb = self._bucket(b)
        queries = np.zeros((pb, self.index.arena.dim), np.int8)
        tids = np.full((pb,), NO_TENANT, np.int32)
        for i, req in enumerate(group):
            queries[i] = req.query
            tids[i] = req.handle.tenant_id
            req.handle.launch_index = self.launches
            self.tracer.instant("admit", now=now, tid=req.handle.tenant_id,
                                request=req.handle.request_id,
                                launch=self.launches)
        t0 = time.monotonic()
        with self.tracer.span("runtime.launch", now=now, batch=b,
                              padded=pb, index=self.launches):
            res, plan, book = self._execute(queries, tids)
        # Dispatch wall only — execution overlaps the host from here on;
        # serve_resolve_lag_seconds (observed at retire) is the other half.
        self._m_launch_wall.observe(time.monotonic() - t0)
        self._m_launches.inc()
        self._m_occupancy.observe(float(b))
        self.launches += 1
        self.queries_served += b
        if plan is not None:
            self._account_plan(plan, b)
        infl = _InFlight(group=group, res=res, launch_index=self.launches - 1,
                         admit_now=now, dispatch_t=time.monotonic(),
                         book=book)
        for req in group:
            req.handle._inflight = infl
        self._inflight.append(infl)
        self._m_inflight.set(float(len(self._inflight)))
        # Backpressure: never more than async_depth unresolved launches —
        # beyond it, block on the oldest (it is the furthest along).
        while len(self._inflight) > self.cfg.async_depth:
            self._retire(self._inflight.popleft())
        return [req.handle for req in group]

    def _account_plan(self, plan: engine.SchedulePlan, b: int) -> None:
        """Fold one launch's SchedulePlan into the runtime ledgers.

        Runs at dispatch for the uncached path (the plan is analytic)
        and inside the deferred bookkeeping for the cached path (the
        hit/miss split needs the selection readback) — either way in
        launch order, so ledgers after a barrier match the synchronous
        path exactly."""
        self.last_plan = plan
        # stage1_bytes is what the launch actually streamed from HBM
        # (padding lanes included); the vmapped comparison counts only
        # the b REAL requests — a sequential server would never have
        # dispatched the padding lanes.
        self.stage1_bytes_streamed += plan.stage1_bytes
        self.stage1_bytes_sram += plan.stage1_bytes_sram
        self.stage1_bytes_vmapped += (
            plan.stage1_bytes_vmapped // plan.batch) * b
        for s in plan.stages:
            self.stage_bytes[s.name] = (
                self.stage_bytes.get(s.name, 0) + s.bytes_hbm)
            if s.bytes_sram:
                self.stage_bytes_sram[s.name] = (
                    self.stage_bytes_sram.get(s.name, 0) + s.bytes_sram)
        if self.registry.enabled:
            # Derived publications (per-stage fan-out + energy
            # pricing) only when someone is listening: keeps the
            # metrics-off launch path byte-identical to pre-obs.
            plan.publish(self.registry)
            dim = self.index.arena.dim
            energy.observe_cost(
                self.registry,
                energy.cost_cascade(plan.stages, dim, batch=plan.batch),
                queries=b)
            # Sampled per-stage split (see __init__): every 8th launch,
            # priced by the linear fast path on held handles.
            self._stage_energy_tick += 1
            if (self._stage_energy_tick - 1) % 8 == 0:
                for s in plan.stages:
                    h = self._m_stage_uj.get(s.name)
                    if h is None:
                        h = self._m_stage_uj[s.name] = self.registry.histogram(
                            "energy_uj_per_query_stage", stage=s.name)
                    h.observe(energy.stage_cost_uj(s, dim, batch=plan.batch),
                              b)

    def _execute(self, queries: np.ndarray, tids: np.ndarray
                 ) -> tuple[RetrievalResult, engine.SchedulePlan | None,
                            "collections.abc.Callable[[], None] | None"]:
        """Dispatch one batch; returns (device result, plan-if-known,
        deferred bookkeeping). The uncached path's plan is analytic —
        known at dispatch, no bookkeeping; the cached path defers its
        readback-dependent plan + cache bookkeeping to retire time."""
        if self.cache is not None:
            layout = self.index.cluster_layout(tids)
            if layout is not None:
                return self._execute_cached(queries, tids, *layout)
        res = self.index.retrieve(jnp.asarray(queries), tids)
        return res, self.index.last_plan, None

    # -- the hot-cluster-cache path -----------------------------------------

    def _warm_from_prior(self, tids: np.ndarray) -> int:
        """Prefetch each batch tenant's recently-probed clusters into the
        slab (device row copies — the host never touches the bytes).

        Touches entries that are still resident (refreshing their LRU
        position) and re-admits ones an arena mutation invalidated — the
        bytes are charged to the launch as HBM traffic (`prefetch`), the
        win is that the session's NEXT probes hit."""
        bytes_fetched = 0
        for t in set(int(x) for x in tids.tolist()):
            if t < 0:
                continue
            recent = self._recent.get(t)
            if not recent:
                continue     # nothing to warm: skip the host row scan
            rows_of = self.index.cluster_rows(t)
            for c in recent:
                if self.cache.peek(t, c):
                    self.cache.touch(t, c)
                    continue
                slots = self.cache.put(t, c, rows_of.get(c, ()))
                if slots is None:
                    continue          # oversized: stays HBM-streamed
                bytes_fetched += len(slots) * self.cache.block_rows * \
                    self.cache.bytes_per_row
        return bytes_fetched

    def _preload_tenants(self, tids: np.ndarray) -> tuple[int, bool]:
        """EdgeRAG-style hot preload: pin every batch tenant's cluster
        set into the slab, so the launch can run from the COMPACT table.

        Admits only when the whole batch's packed demand fits the budget
        TOGETHER — a short budget keeps the per-probe prior warming
        instead of thrashing admissions against evictions. Returns
        (prefetched HBM bytes, every-batch-tenant-fully-resident). A
        steady-state call is a handful of memoized dict lookups."""
        cache = self.cache
        br = cache.block_rows
        gen = self.index.arena.generation
        tenants = sorted(set(int(x) for x in tids.tolist()) - {-1})
        demand = 0
        stats = {}
        for t in tenants:
            key = (gen, t)
            st = self._tenant_demand.get(key)
            if st is None:
                rows_of = self.index.cluster_rows(t)
                st = (sum(cache.entry_blocks(r, br)
                          for r in rows_of.values()),
                      sum(1 for r in rows_of.values() if r.size))
                if len(self._tenant_demand) > 4096:
                    self._tenant_demand.clear()
                self._tenant_demand[key] = st
            stats[t] = st
            demand += st[0]
        if demand * br * cache.bytes_per_row > cache.budget_bytes:
            return 0, False
        bytes_fetched = 0
        for t in tenants:
            if cache.fully_resident(t, stats[t][1]):
                continue
            for c, rows in self.index.cluster_rows(t).items():
                if cache.peek(t, c):
                    continue
                slots = cache.put(t, c, rows)
                if slots is not None:
                    bytes_fetched += len(slots) * br * cache.bytes_per_row
        # Residency is re-verified for EVERY batch tenant only after all
        # admissions ran: slots held by non-batch residents can force a
        # later tenant's puts to evict an earlier batch tenant's entries
        # (the demand check bounds the batch, not the whole slab), and a
        # compact table for a partially-evicted tenant would silently
        # hole its clusters. Any shortfall falls back to the full-width
        # table — slower, never wrong.
        resident = all(cache.fully_resident(t, stats[t][1])
                       for t in tenants)
        return bytes_fetched, resident

    def _cluster_valid(self, tids: np.ndarray, host_table: np.ndarray):
        """Device (B, K) selection-validity bools — the plane table's
        ``first block >= 0`` bits, precomputed host-side so selection is
        identical at ANY launch table width. Cached per (arena
        generation, tenant tuple); the host table is deterministic per
        that key."""
        key = (self.index.arena.generation, tids.tobytes())
        hit = self._valid_cache.get(key)
        if hit is not None:
            return hit
        if len(self._valid_cache) > 64:
            self._valid_cache.clear()
        valid = jnp.asarray(host_table[:, :, 0] >= 0)
        self._valid_cache[key] = valid
        return valid

    def _execute_cached(self, queries: np.ndarray, tids: np.ndarray,
                        policy: engine.ClusterPolicy,
                        host_table: np.ndarray
                        ) -> tuple[RetrievalResult, None,
                                   "collections.abc.Callable[[], None]"]:
        """One launch through the device-resident slab path.

        Host work at dispatch is a handful of dict/array lookups: pin the
        slab to the arena generation, warm the session (priors, or the
        full preload when enabled), resolve the slot map into the launch
        indirection table — the COMPACT slab table when every batch
        tenant is fully resident, the full-width plane table otherwise;
        both cached per slot-map version, zero rebuild when fully warm —
        and launch ONE jitted cascade (`SlabPolicy`). Selection runs
        in-graph; the tiny (B, nprobe) selection readback that feeds the
        hit/miss ledger, the LRU, miss admissions (device row copies)
        and the session prior is DEFERRED into the returned bookkeeping
        closure, run at retire time in launch order — so the host forms
        and dispatches the next batch instead of stalling on this one's
        selection. Pipelined launches therefore warm from priors that
        may lag by the pipeline depth; that shifts only WHERE bytes come
        from (and when admissions land), never what is scored — results
        stay bit-identical to the synchronous path, and a barrier
        (flush) drains bookkeeping in launch order so per-flush ledgers
        match it exactly. No per-lane view is ever materialized on the
        host or uploaded, and hit rows are never re-streamed."""
        index = self.index
        db = index.arena.db()
        cache = self.cache
        br = policy.block_rows
        d2 = db.msb_plane.shape[1]
        k_clusters = policy.centroid_msb.shape[0]
        cache.configure(br, d2)
        if (self._inflight
                and cache.generation != index.arena.generation):
            # An arena mutation is about to invalidate the slab: retire
            # everything dispatched against the OLD generation first, so
            # their deferred bookkeeping reads the slot map its launches
            # actually encoded (exact synchronous semantics across
            # generations; mutations are rare, the sync is off the
            # steady-state path).
            self.barrier()
        cache.sync_generation(index.arena.generation)
        cache.ensure_slab(db.msb_plane, db.norms_sq, policy.owner,
                          policy.labels, k_clusters)
        compact = False
        prefetched = 0
        with self._phase("runtime.preload"):
            if self.cfg.preload:
                prefetched, compact = self._preload_tenants(tids)
            if not compact:
                prefetched += self._warm_from_prior(tids)
        # ONE fill dispatch per launch: the previous launch's deferred
        # miss admissions plus this launch's warming, applied before the
        # indirection table can reference their slots.
        with self._phase("runtime.fill"):
            cache.flush_fills()
        with self._phase("runtime.tables"):
            if compact:
                slab_blocks, width = cache.compact_table(tids, k_clusters)
                if min(policy.nprobe, k_clusters) * width * br < index.cfg.k:
                    compact = False   # view too narrow to hold k: full width
            if not compact:
                slab_blocks = cache.combined_table(tids, host_table)
            cluster_valid = self._cluster_valid(tids, host_table)
        # Stage-0 prescreen operand: the combined sign plane (derived
        # from the combined msb plane, cached per plane state) rides
        # along whenever the config prescreens — resident clusters'
        # sign bytes then serve stage 0 from the slab region.
        prescreen = (index.cfg.prescreen_c0 is not None
                     and index.arena.dim % 8 == 0)
        spolicy = engine.SlabPolicy(
            packed_labels=cache.packed_labels,
            tenant_ids=policy.tenant_ids, centroid_msb=policy.centroid_msb,
            centroid_norms=policy.centroid_norms,
            cluster_valid=cluster_valid,
            slab_blocks=slab_blocks, block_gid0=cache.block_gid0,
            block_count=cache.block_count, slab_plane=cache.slab_plane,
            inv_norms=cache.inv_norms, nprobe=policy.nprobe, block_rows=br,
            sign_plane=(cache.sign_plane if prescreen else None),
            block_tier=(cache.block_tier if cache.precision_tiers
                        else None))
        res, top_clusters = index.engine.retrieve_with_clusters(
            jnp.asarray(queries), db, spolicy)
        # Dispatch done. Everything below needs the (B, nprobe) selection
        # readback — a device sync — so it is packaged into a closure the
        # completion queue runs at retire time (launch order), letting
        # the host overlap the NEXT batch's admission with this scoring.
        arena_gen = index.arena.generation
        b_real = int((tids >= 0).sum())
        probe_rows = engine.probe_rows(spolicy)

        c0 = (index.cfg.prescreen_budget(probe_rows) if prescreen
              else None)

        def book() -> None:
            # Admissions still run AFTER the whole hit/miss loop, so the
            # ledger reflects the slot-map snapshot at retire time; a
            # barrier per turn (flush) makes that the exact snapshot the
            # launch's table encoded, the synchronous path's ledger.
            tc = np.asarray(top_clusters)
            bsz = tc.shape[0]
            block_bytes = br * d2
            sign_block_bytes = br * (d2 // 4)   # 1-bit vs 4-bit rows
            tiers = cache.precision_tiers
            hit_bytes = miss_bytes = 0
            ps_sram = ps_hbm = 0      # stage-0 sign-byte split
            # A mutation between dispatch and retire means cluster_rows
            # now describes a DIFFERENT arena: admitting those rows into
            # this launch's (old-generation) slot map would be wrong,
            # and the next cached dispatch invalidates the slab anyway.
            stale = index.arena.generation != arena_gen
            to_admit: dict[tuple[int, int], int] = {}
            to_promote: dict[tuple[int, int], int] = {}
            for i in range(bsz):
                t = int(tids[i])
                if t < 0:
                    continue                  # padding lane: all holes
                row_table = host_table[i]
                probes = tc[i].tolist()
                if tiers:
                    (lane_full, lane_sign, sign_hits,
                     missing) = cache.lookup_lane_tiers(t, probes)
                    hit_bytes += lane_full
                    if c0 is not None:
                        # Resident probes serve stage 0 on-chip: full
                        # tier mirrors the slab's sign bytes (1/4 of its
                        # nibble charge), sign tier is the tier's whole
                        # point.
                        ps_sram += lane_full // 4 + lane_sign
                    for c in sign_hits:
                        key = (t, c)
                        if key not in to_promote:
                            to_promote[key] = int((row_table[c] >= 0).sum())
                        # sign tier holds no slab rows: stage 1 streamed
                        # the cluster's PLANE blocks from HBM
                        miss_bytes += to_promote[key] * block_bytes
                else:
                    lane_hit, missing = cache.lookup_lane(t, probes)
                    hit_bytes += lane_hit
                    if c0 is not None:
                        ps_sram += lane_hit // 4
                for c in missing:
                    key = (t, c)
                    if key not in to_admit:
                        to_admit[key] = int((row_table[c] >= 0).sum())
                    # a miss streamed the cluster's PLANE blocks from HBM
                    miss_bytes += to_admit[key] * block_bytes
                    if c0 is not None:
                        ps_hbm += to_admit[key] * sign_block_bytes
            if (to_admit or to_promote) and not stale:
                self._m_deferred_fills.inc(len(to_admit) + len(to_promote))
                for (t, c) in to_admit:
                    # Under tiers, first contact admits at 1-bit
                    # precision; a re-probe promotes to full.
                    cache.put(t, c, index.cluster_rows(t).get(c, ()),
                              tier=(TIER_SIGN if tiers else TIER_FULL))
                for (t, c) in to_promote:
                    cache.promote(t, c, index.cluster_rows(t).get(c, ()))
                    # fills applied by the NEXT launch's flush
            # Ledger: the analytic cluster plan with the approx stage
            # split into measured HBM misses (+ warming prefetches) vs
            # cache hits. The base plan is pure arithmetic over static
            # shapes — cached per launch signature so the steady state
            # doesn't rebuild an identical plan every turn.
            pkey = (db.num_docs, db.dim, bsz, k_clusters, probe_rows)
            base = self._plan_cache.get(pkey)
            if base is None:
                if len(self._plan_cache) > 256:  # num_docs moves per mutation
                    self._plan_cache.clear()
                base = engine.plan(index.cfg, num_docs=db.num_docs,
                                   dim=db.dim, batch=bsz, kind="cluster",
                                   num_clusters=k_clusters,
                                   view_rows=probe_rows)
                self._plan_cache[pkey] = base
            approx_hbm = miss_bytes + prefetched
            approx_sram = hit_bytes
            if c0 is not None and probe_rows:
                # A prescreened stage 1 gathers only the C0 survivors,
                # not the whole view: prorate the measured cluster-level
                # split by the survivor fraction (survivors spread
                # across probed clusters; an exact per-row residency
                # split would need a second selection readback).
                # Warming prefetches are real whole-cluster plane
                # copies — charged unprorated.
                frac = min(1.0, c0 / probe_rows)
                approx_hbm = int(miss_bytes * frac) + prefetched
                approx_sram = int(hit_bytes * frac)
            plan = engine.cache_split_plan(
                base, hbm_bytes=approx_hbm, sram_bytes=approx_sram,
                prescreen_hbm=(ps_hbm if c0 is not None else None),
                prescreen_sram=ps_sram)
            self.prefetch_bytes += prefetched
            self._m_prefetch_bytes.inc(prefetched)
            index.last_plan = plan
            self._account_plan(plan, b_real)
            # Refresh each tenant's session prior with the clusters this
            # turn actually probed (most recent first, bounded). Compact
            # launches skip it: the preload pins the whole session, so
            # the prior would never be consulted (it rebuilds within
            # prior_clusters turns if a budget/demand shift ever forces
            # the fallback path).
            if self.cfg.prior_clusters and not compact:
                for i in range(bsz):
                    t = int(tids[i])
                    if t < 0:
                        continue
                    fresh = list(dict.fromkeys(int(c) for c in tc[i]))
                    old = [c for c in self._recent.get(t, [])
                           if c not in fresh]
                    self._recent[t] = (fresh + old)[:self.cfg.prior_clusters]

        return res, None, book

    # -- reporting ----------------------------------------------------------

    def cache_stats(self) -> dict:
        self.barrier()    # stats are defined as of the last RETIRED launch
        if self.cache is None:
            return {"enabled": False}
        return {"enabled": True, "entries": len(self.cache),
                "bytes_used": self.cache.bytes_used,
                "budget_bytes": self.cache.budget_bytes,
                "slab_blocks": self.cache.num_slab_blocks,
                "slab_blocks_used": (self.cache.num_slab_blocks
                                     - len(self.cache._free)),
                **self.cache.snapshot()}

    def energy_ledger(self, dim: int | None = None):
        """cost_cascade of the most recent launch's measured plan."""
        self.barrier()    # the cached path's plan lands at retire time
        if self.last_plan is None:
            raise RuntimeError("no launch has run yet")
        return energy.cost_cascade(self.last_plan.stages,
                                   dim or self.index.arena.dim,
                                   batch=self.last_plan.batch)

    # -- decode accounting --------------------------------------------------

    def account_decode(self, plan: engine.SchedulePlan, *, dim: int,
                       tokens: int = 1):
        """Charge a decode run's KV-cascade ledger through this runtime.

        `plan` is ONE decode step's `engine.kv_plan` (kind="decode");
        `tokens` scales it to the whole run — the stage geometry is
        identical every step at a fixed cache length, so one plan prices
        the run the way one launch plan prices a retrieval batch. The
        scaled ledger fans out through the same `SchedulePlan.publish`
        counters as retrieval launches (stage_rows / stage_bytes_hbm per
        stage name), and the priced per-token cost lands in the
        `energy_uj_per_token` histogram — one runtime, one registry, two
        memory-bound workloads. Returns the per-token CostBreakdown."""
        if plan.kind != "decode":
            raise ValueError(f"account_decode wants a kind='decode' plan, "
                             f"got {plan.kind!r}")
        scaled = dataclasses.replace(
            plan,
            stages=tuple(dataclasses.replace(
                s, bytes_hbm=s.bytes_hbm * tokens,
                bytes_sram=s.bytes_sram * tokens,
                compares=s.compares * tokens) for s in plan.stages),
            stage1_bytes=plan.stage1_bytes * tokens,
            stage1_bytes_vmapped=plan.stage1_bytes_vmapped * tokens,
            stage2_bytes=plan.stage2_bytes * tokens)
        self.decode_steps += tokens
        self.decode_bytes_hbm += sum(s.bytes_hbm for s in scaled.stages)
        self.last_decode_plan = plan
        cost = energy.cost_cascade(plan.stages, dim, batch=plan.batch)
        if self.registry.enabled:
            scaled.publish(self.registry)
            energy.observe_decode_cost(self.registry, cost, tokens=tokens)
        return cost
