"""jit'd public wrappers around the Pallas kernels.

These expose the same signatures the pure-jnp reference engine uses
(repro.core.retrieval stage functions), handling query even/odd packing,
and row padding to block multiples. Each kernel picks its own mode
(`repro.kernels.platform.resolve_interpret`: compiled Mosaic on a TPU,
the interpreter elsewhere).

Block shapes: the tunable wrappers (stage1_* matmuls and the fused top-k)
take `block_n=None` and resolve the block at *trace time* from the
installed `repro.kernels.autotune` table (measured per device and batch
bucket), falling back deterministically to the kernel's `DEFAULT_BLOCK_N`
when no table is installed. Pass an explicit `block_n` to bypass the
table (tests and the autotuner itself do). Block choice never affects
results — only the schedule — which is pinned by the parity suites.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import autotune as _at
from repro.kernels import fused_topk as _fk
from repro.kernels import stage0_sign as _s0
from repro.kernels import stage1_gather as _sg
from repro.kernels import stage1_int4 as _s1
from repro.kernels import stage2_int8 as _s2


def pack_query_even_odd(q: jax.Array) -> jax.Array:
    """(D,) int8 -> (2, D//2) int8: row 0 = even dims, row 1 = odd dims."""
    return jnp.stack([q[0::2], q[1::2]]).astype(jnp.int8)


def pack_queries_even_odd(q: jax.Array) -> jax.Array:
    """(B, D) int8 -> (B, 2, D//2) int8 per-lane [even; odd] panels."""
    return jnp.stack([q[:, 0::2], q[:, 1::2]], axis=1).astype(jnp.int8)


def pack_query_panel(q: jax.Array) -> jax.Array:
    """(B, D) int8 -> (2, B, D//2) int8 batch panels ([even dims; odd dims])
    — the stationary operand of the batched stage-1 matmul kernel."""
    return jnp.stack([q[:, 0::2], q[:, 1::2]]).astype(jnp.int8)


def pack_query_signs(q: jax.Array) -> jax.Array:
    """(B, D) int8 -> (B, D) int8 in {+1, -1} — the stage-0 kernels'
    stationary query operand (kept dense: it is tiny, and pre-unpacking
    it sidesteps a second in-kernel bit unpack). Zero maps to +1,
    matching `bitplanar.unpack_sign_pm1` of the packed doc plane."""
    from repro.core.bitplanar import sign_pm1
    return sign_pm1(q)


def sign_bit_panels(q_sign: jax.Array, *, per_lane: bool = False
                    ) -> jax.Array:
    """{+1, -1} int8 queries -> the stage-0 kernels' bit panels: panel b
    holds dims k = 8 * j + b, so bit b of packed sign byte j pairs with
    column j. A (B, D) batch gives (8, B, D//8), the batched kernel's
    shared panel. With per_lane, the gather kernel's per-lane block:
    (B, D) -> (B, 8, 1, D//8), one query row a lane, and (B, M, D) ->
    (B, 8, M, D//8), M query rows a lane scored against the lane's one
    gather (the KV prescreen's G query heads of a KV head)."""
    if per_lane:
        rows = q_sign[:, None, :] if q_sign.ndim == 2 else q_sign
        b, m, d = rows.shape
        return rows.reshape(b, m, d // 8, 8).transpose(0, 3, 1, 2)
    b, d = q_sign.shape
    return q_sign.reshape(b, d // 8, 8).transpose(2, 0, 1)


def _pad_rows(a: jax.Array, mult: int) -> jax.Array:
    pad = (-a.shape[0]) % mult
    if pad == 0:
        return a
    return jnp.pad(a, ((0, pad), (0, 0)))


def _pad_axis1(a: jax.Array, mult: int) -> jax.Array:
    pad = (-a.shape[1]) % mult
    if pad == 0:
        return a
    return jnp.pad(a, ((0, 0), (0, pad), (0, 0)))


def stage1_scores(q_msb: jax.Array, msb_plane: jax.Array,
                  block_n: int | None = None) -> jax.Array:
    """Kernel-backed drop-in for retrieval.stage1_scores_jnp.

    q_msb: (D,) int8 signed MSB nibbles of the query.
    msb_plane: (N, D//2) packed uint8. Returns (N,) int32.
    block_n None -> the installed autotune table's choice (default 1024).
    """
    if block_n is None:
        block_n = _at.lookup("stage1_single", 1, _s1.DEFAULT_BLOCK_N)
    return _stage1_scores_jit(q_msb, msb_plane, block_n)


@functools.partial(jax.jit, static_argnames=("block_n",))
def _stage1_scores_jit(q_msb: jax.Array, msb_plane: jax.Array,
                       block_n: int) -> jax.Array:
    n = msb_plane.shape[0]
    block_n = min(block_n, max(8, n))
    plane = _pad_rows(msb_plane, block_n)
    q_eo = pack_query_even_odd(q_msb)
    out = _s1.stage1_int4_pallas(q_eo, plane, block_n=block_n)
    return out[:n]


@functools.partial(jax.jit, static_argnames=("block_c",))
def stage2_scores(q: jax.Array, msb_rows: jax.Array, lsb_rows: jax.Array,
                  block_c: int = _s2.DEFAULT_BLOCK_C) -> jax.Array:
    """Kernel-backed drop-in for retrieval.stage2_scores_jnp.

    q: (D,) int8 full-precision query codes.
    msb_rows/lsb_rows: (C, D//2) packed uint8 gathered candidates.
    Returns (C,) int32 exact scores.
    """
    c = msb_rows.shape[0]
    block_c = min(block_c, max(8, c))
    msb = _pad_rows(msb_rows, block_c)
    lsb = _pad_rows(lsb_rows, block_c)
    q_eo8 = pack_query_even_odd(q)
    out = _s2.stage2_int8_pallas(q_eo8, msb, lsb, block_c=block_c)
    return out[:c]


def stage1_scores_batched(q_msb: jax.Array, msb_plane: jax.Array,
                          block_n: int | None = None) -> jax.Array:
    """Kernel-backed drop-in for engine.stage1_plane_batched_jnp.

    q_msb: (B, D) int8 signed MSB nibbles of the whole query batch.
    msb_plane: (N, D//2) packed uint8. Returns (B, N) int32. ONE launch;
    each doc block is streamed from HBM once per BATCH, not once per query.
    block_n None -> the installed autotune table's choice for this batch
    bucket (default 1024).
    """
    if block_n is None:
        block_n = _at.lookup("stage1_batched", q_msb.shape[0],
                             _s1.DEFAULT_BLOCK_N)
    return _stage1_scores_batched_jit(q_msb, msb_plane, block_n)


@functools.partial(jax.jit, static_argnames=("block_n",))
def _stage1_scores_batched_jit(q_msb: jax.Array, msb_plane: jax.Array,
                               block_n: int) -> jax.Array:
    n = msb_plane.shape[0]
    block_n = min(block_n, max(8, n))
    plane = _pad_rows(msb_plane, block_n)
    q_panel = pack_query_panel(q_msb)
    out = _s1.stage1_int4_batched_pallas(q_panel, plane, block_n=block_n)
    return out[:, :n]


def stage1_scores_rows(q_msb: jax.Array, msb_rows: jax.Array,
                       block_w: int | None = None) -> jax.Array:
    """Kernel-backed drop-in for engine.stage1_rows_batched_jnp.

    q_msb: (B, D) int8 nibbles; msb_rows: (B, W, D//2) per-lane packed row
    blocks (e.g. each tenant's arena window). Returns (B, W) int32.
    block_w None -> the installed autotune table's choice (default 1024)."""
    if block_w is None:
        block_w = _at.lookup("stage1_rows", q_msb.shape[0],
                             _s1.DEFAULT_BLOCK_N)
    return _stage1_scores_rows_jit(q_msb, msb_rows, block_w)


@functools.partial(jax.jit, static_argnames=("block_w",))
def _stage1_scores_rows_jit(q_msb: jax.Array, msb_rows: jax.Array,
                            block_w: int) -> jax.Array:
    w = msb_rows.shape[1]
    block_w = min(block_w, max(8, w))
    rows = _pad_axis1(msb_rows, block_w)
    q_eo = pack_queries_even_odd(q_msb)
    out = _s1.stage1_int4_rows_pallas(q_eo, rows, block_w=block_w)
    return out[:, :w]


@functools.partial(jax.jit, static_argnames=("block_rows",))
def stage1_scores_gather(q_msb: jax.Array, msb_plane: jax.Array,
                         block_ids: jax.Array, *,
                         block_rows: int = _sg.DEFAULT_BLOCK_ROWS
                         ) -> jax.Array:
    """Kernel-backed drop-in for engine.stage1_gather_batched_jnp.

    q_msb: (B, D) int8 nibbles; msb_plane: (N, D//2) packed uint8;
    block_ids: (B, J) int32 ids of `block_rows`-row plane blocks (already
    clamped to valid blocks). Returns (B, J * block_rows) int32. The
    gather happens INSIDE the kernel via scalar prefetch — only the
    selected blocks stream from HBM; rows past N (the plane's zero
    padding) score 0, matching the jnp reference bit-for-bit.

    When N is not a block_rows multiple the plane is zero-padded HERE,
    which copies it every launch — serving paths size their arenas to a
    block multiple (MultiTenantIndex enforces this) so the pad is a
    no-op and only ad-hoc callers pay it."""
    plane = _pad_rows(msb_plane, block_rows)
    q_eo = pack_queries_even_odd(q_msb)
    return _sg.stage1_int4_gather_pallas(q_eo, plane, block_ids,
                                         block_rows=block_rows)


@functools.partial(jax.jit, static_argnames=("block_rows",))
def stage1_scores_gather_resident(q_msb: jax.Array, plane: jax.Array,
                                  block_ids: jax.Array, *,
                                  block_rows: int = _sg.DEFAULT_BLOCK_ROWS
                                  ) -> jax.Array:
    """The block gather over a RESIDENT, pre-validated plane (slab path).

    Kernel-backed drop-in for engine.stage1_gather_resident_jnp: the
    serving runtime's combined plane+slab array is always a whole number
    of `block_rows` blocks and every id in `block_ids` addresses a live
    block (misses point into the arena region, hits into the cache slab
    region), so the general wrapper's pad-to-multiple step is skipped
    outright instead of being a per-launch no-op check. The kernel's
    contract never included clamping — the gather IS the scan's input
    stream, two memory regions behind one scalar-prefetched id table."""
    n = plane.shape[0]
    if n % block_rows:
        raise ValueError(f"resident plane must be a block multiple, got "
                         f"{n} rows with block_rows={block_rows}")
    q_eo = pack_queries_even_odd(q_msb)
    return _sg.stage1_int4_gather_pallas(q_eo, plane, block_ids,
                                         block_rows=block_rows)


def stage0_sign_scores_batched(q_sign: jax.Array, sign_plane: jax.Array,
                               block_n: int | None = None) -> jax.Array:
    """Kernel-backed drop-in for engine.stage0_sign_plane_batched_jnp.

    q_sign: (B, D) int8 in {+1, -1} (pack_query_signs); sign_plane:
    (N, D//8) packed uint8. Returns (B, N) int32 sign-agreement scores.
    ONE launch; each sign block streams from HBM once per BATCH.
    block_n None -> the installed autotune table's choice for this batch
    bucket ("stage0_sign" family, default 1024)."""
    if block_n is None:
        block_n = _at.lookup("stage0_sign", q_sign.shape[0],
                             _s0.DEFAULT_BLOCK_N)
    return _stage0_sign_scores_batched_jit(q_sign, sign_plane, block_n)


@functools.partial(jax.jit, static_argnames=("block_n",))
def _stage0_sign_scores_batched_jit(q_sign: jax.Array, sign_plane: jax.Array,
                                    block_n: int) -> jax.Array:
    n = sign_plane.shape[0]
    block_n = min(block_n, max(8, n))
    plane = _pad_rows(sign_plane, block_n)
    out = _s0.stage0_sign_batched_pallas(sign_bit_panels(q_sign), plane,
                                         block_n=block_n)
    return out[:, :n]


@functools.partial(jax.jit, static_argnames=("block_rows",))
def stage0_sign_scores_gather(q_sign: jax.Array, sign_plane: jax.Array,
                              block_ids: jax.Array, *,
                              block_rows: int = _sg.DEFAULT_BLOCK_ROWS
                              ) -> jax.Array:
    """Kernel-backed drop-in for engine.stage0_sign_gather_batched_jnp.

    q_sign: (B, D) int8 {+1, -1}, or (B, M, D) for M query rows a lane;
    sign_plane: (N, D//8) packed uint8; block_ids: (B, J) int32 clamped
    block ids — the SAME table the stage-1 gather consumes. Returns (B,
    J * block_rows) int32, or (B, M, J * block_rows). The plane is
    zero-padded to a block multiple here (a no-op for arenas sized to a
    block multiple); zero bytes unpack to all-+1 rows on both backends
    and are masked downstream."""
    plane = _pad_rows(sign_plane, block_rows)
    out = _s0.stage0_sign_gather_pallas(
        sign_bit_panels(q_sign, per_lane=True), plane, block_ids,
        block_rows=block_rows)
    return out[:, 0] if q_sign.ndim == 2 else out


@functools.partial(jax.jit, static_argnames=("block_rows",))
def stage0_sign_scores_gather_resident(q_sign: jax.Array, plane: jax.Array,
                                       block_ids: jax.Array, *,
                                       block_rows: int = _sg.DEFAULT_BLOCK_ROWS
                                       ) -> jax.Array:
    """The stage-0 gather over a RESIDENT, pre-validated combined sign
    plane (the slab path) — same contract as
    stage1_scores_gather_resident, one plane-width narrower."""
    n = plane.shape[0]
    if n % block_rows:
        raise ValueError(f"resident sign plane must be a block multiple, "
                         f"got {n} rows with block_rows={block_rows}")
    return _s0.stage0_sign_gather_pallas(
        sign_bit_panels(q_sign, per_lane=True), plane, block_ids,
        block_rows=block_rows)[:, 0]


@functools.partial(jax.jit, static_argnames=("block_k",))
def centroid_scores_batched(q_msb: jax.Array, centroid_msb: jax.Array,
                            block_k: int = _s1.DEFAULT_BLOCK_N) -> jax.Array:
    """Batched centroid scoring for the cascade's stage-0 prune.

    The codebook is stored exactly like the corpus — a packed MSB nibble
    plane — so this IS the batched stage-1 matmul kernel applied to the
    (K, D//2) centroid plane: q_msb (B, D) int8 nibbles -> (B, K) int32.
    The whole codebook is one or two VMEM-resident blocks (K is small),
    streamed once per batch."""
    return stage1_scores_batched(q_msb, centroid_msb, block_n=block_k)


def centroid_scores_rows(q_msb: jax.Array, centroid_rows: jax.Array,
                         block_p: int | None = None) -> jax.Array:
    """Per-lane centroid scoring for the KV-decode page prune.

    Unlike the shared-codebook `centroid_scores_batched`, each query lane
    carries its OWN codebook — the page centroids of one (batch, kv-head)
    cache lane, `(B, P, D//2)` packed MSB nibbles — so this is the
    per-lane-rows stage-1 kernel applied to centroid planes:
    q_msb (B, D) int8 nibbles -> (B, P) int32. P (pages per lane) is
    small, so the codebook block is VMEM-resident per lane."""
    return stage1_scores_rows(q_msb, centroid_rows, block_w=block_p)


@functools.partial(jax.jit, static_argnames=("block_c",))
def stage2_scores_batched(q: jax.Array, msb_rows: jax.Array,
                          lsb_rows: jax.Array,
                          block_c: int = _s2.DEFAULT_BLOCK_C) -> jax.Array:
    """Kernel-backed drop-in for engine.stage2_rows_batched_jnp.

    q: (B, D) int8 full-precision queries; msb_rows/lsb_rows: (B, C, D//2)
    gathered per-lane candidate planes. Returns (B, C) int32, ONE launch."""
    c = msb_rows.shape[1]
    block_c = min(block_c, max(8, c))
    msb = _pad_axis1(msb_rows, block_c)
    lsb = _pad_axis1(lsb_rows, block_c)
    q_eo8 = pack_queries_even_odd(q)
    out = _s2.stage2_int8_batched_pallas(q_eo8, msb, lsb, block_c=block_c)
    return out[:, :c]


def fused_candidates_batched(q_msb: jax.Array, msb_plane: jax.Array,
                             owner: jax.Array | None = None,
                             tenant_ids: jax.Array | None = None, *, c: int,
                             k_per_block: int = 8,
                             block_n: int | None = None) -> jax.Array:
    """Batched fused stage-1 candidate generation (optionally masked).
    block_n None -> the installed autotune table's choice (default 512).

    q_msb: (B, D) int8 nibbles. With owner/tenant_ids, each lane's tenant
    segment mask is applied INSIDE the kernel, so out-of-segment scores
    never leave VMEM. Returns (B, c) int32 global doc ids; same exactness
    condition as `fused_candidates` per lane. Lanes whose live segment is
    smaller than c pad with masked entries (id < n but score INT32_MIN
    upstream — callers mask via membership like the dense path)."""
    if block_n is None:
        block_n = _at.lookup("fused_topk", q_msb.shape[0],
                             _fk.DEFAULT_BLOCK_N)
    return _fused_candidates_batched_jit(q_msb, msb_plane, owner, tenant_ids,
                                         c=c, k_per_block=k_per_block,
                                         block_n=block_n)


@functools.partial(jax.jit, static_argnames=("c", "k_per_block", "block_n"))
def _fused_candidates_batched_jit(q_msb: jax.Array, msb_plane: jax.Array,
                                  owner: jax.Array | None = None,
                                  tenant_ids: jax.Array | None = None, *,
                                  c: int, k_per_block: int = 8,
                                  block_n: int = _fk.DEFAULT_BLOCK_N
                                  ) -> jax.Array:
    n = msb_plane.shape[0]
    block_n = min(block_n, max(8, n))
    plane = _pad_rows(msb_plane, block_n)
    if owner is not None:
        owner = jnp.pad(owner, (0, plane.shape[0] - n),
                        constant_values=-1)           # padding rows: no owner
    q_eo = pack_queries_even_odd(q_msb)
    scores, ids = _fk.fused_topk_batched_pallas(
        q_eo, plane, owner, tenant_ids, k=k_per_block, block_n=block_n)
    flat_s = scores.reshape(scores.shape[0], -1)
    flat_i = ids.reshape(ids.shape[0], -1)
    flat_s = jnp.where(flat_i < n, flat_s, jnp.iinfo(jnp.int32).min)
    _, sel = jax.lax.top_k(flat_s, c)
    return jnp.take_along_axis(flat_i, sel, axis=1)


def fused_candidates(q_msb: jax.Array, msb_plane: jax.Array, *, c: int,
                     k_per_block: int = 8,
                     block_n: int | None = None) -> jax.Array:
    """Stage-1 candidate generation via the fused score+top-k kernel.

    Returns (c,) int32 global doc ids (approximate top-c). Exact whenever
    c <= k_per_block * num_blocks and no block contributes more than
    k_per_block of the true top-c (guaranteed when k_per_block >= c or by
    choosing k_per_block >= c / num_blocks safety factor — see tests).
    block_n None -> the installed autotune table's choice (default 512).
    """
    if block_n is None:
        block_n = _at.lookup("fused_topk", 1, _fk.DEFAULT_BLOCK_N)
    return _fused_candidates_jit(q_msb, msb_plane, c=c,
                                 k_per_block=k_per_block, block_n=block_n)


@functools.partial(jax.jit, static_argnames=("c", "k_per_block", "block_n"))
def _fused_candidates_jit(q_msb: jax.Array, msb_plane: jax.Array, *, c: int,
                          k_per_block: int = 8,
                          block_n: int = _fk.DEFAULT_BLOCK_N) -> jax.Array:
    n = msb_plane.shape[0]
    block_n = min(block_n, max(8, n))
    plane = _pad_rows(msb_plane, block_n)
    q_eo = pack_query_even_odd(q_msb)
    scores, ids = _fk.fused_topk_pallas(q_eo, plane, k=k_per_block,
                                        block_n=block_n)
    flat_s = scores.reshape(-1)
    flat_i = ids.reshape(-1)
    # padded rows score 0 with id >= n; mask them out
    flat_s = jnp.where(flat_i < n, flat_s, jnp.iinfo(jnp.int32).min)
    _, sel = jax.lax.top_k(flat_s, c)
    return flat_i[sel]
