"""Pallas TPU kernel: stage-0 sign-plane (1-bit) prescreen, query-stationary.

The adaptive-precision cascade's cheapest stage: score sign AGREEMENT over
the packed 1-bit sign plane (`bitplanar.pack_sign_plane` — 8 dims/byte,
4x fewer HBM bytes than the stage-1 MSB nibble plane) and keep only the
top-C0 survivors per lane for the INT4 scan. The classical formulation is
an XNOR + popcount; on the MXU the monotone-equivalent form is cheaper:

    agreement-score = sum_k sign(q_k) * sign(d_k) = 2 * agreements - D

so the kernel scores one bit position at a time: for bit b of every
packed doc byte it forms the {+1, -1} int8 column block (bit set =
negative = -1, `bitplanar.unpack_sign_pm1`'s convention) and runs a plain
int8 x int8 -> int32 MAC on the MXU against the query dims k with
k % 8 == b. Eight (rows, D/8) MACs sum to the full D-dim agreement, and no
in-kernel reshape interleaves the bits back into dim order. The query
operand arrives PRE-SPLIT into those eight bit panels (`ops.
sign_bit_panels` of the dense (B, D) {+1, -1} query): it is tiny, stays
pinned in VMEM across the whole grid (query-stationary, exactly like the
stage-1 kernels), and keeping it dense sidesteps a second in-kernel
unpack.

Two variants:

  * `stage0_sign_batched_pallas` — dense batched matmul over the whole
    plane, grid (num_blocks,), doc sign blocks streamed HBM->VMEM once
    per BATCH (the shape `stage1_int4_batched_pallas` uses);
  * `stage0_sign_gather_pallas` — block gather over a per-lane block-id
    table, so only selected blocks' sign bytes ever stream. Each lane
    carries M query rows (the retrieval cascade: M = 1, the cluster
    prune's table that the stage-1 gather consumes too; the KV
    prescreen: one lane per (sequence, KV head), its G query heads as
    the M rows, so a GQA group's pages stream once). The grid is (lanes,
    J / JB): the ids are scalar-prefetched, and each step starts the
    manual DMAs of its JB selected blocks together before it waits on
    any, then scores all M rows against all JB blocks in one MAC. JB is
    every block of the lane while their unpacked tiles fit a fixed VMEM
    budget (all 16 pages of a KV lane), else the largest divisor of J
    that does — so the step count follows the lanes, not the pages.

Zero bytes (the plane's padding rows and tombstoned rows) unpack to all
+1 dims and score ``sum_k sign(q_k)`` — NOT zero. That is the shared
convention with the jnp reference (`bitplanar.gather_blocks` zeroes the
BYTES, both backends unpack them identically), and every such row is
masked out downstream by the membership mask before any top-k.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.platform import resolve_interpret
from repro.kernels.stage1_int4 import mac_nt

# Same fallback block shape as the stage-1 kernels: a sign block is 4x
# fewer bytes at equal rows, so 1024 rows x D/8 bytes is comfortably
# VMEM-resident; the measured autotuner ("stage0_sign" family) owns the
# per-device choice.
DEFAULT_BLOCK_N = 1024

# VMEM one grid step of the block gather may fill with unpacked sign
# blocks (int32 tiles): every block of a KV lane (16 pages of 8 rows, 64
# KiB) and half of a retrieval lane's 128 probed 32-row blocks fit.
GATHER_STEP_VMEM_BYTES = 1 << 20


def score_sign_rows(q_bits: jax.Array, block_u8: jax.Array) -> jax.Array:
    """q_bits (8, M, D8) int8 bit panels; block_u8 (BN, D8) packed sign
    bytes (uint8, or already widened to int32) -> (M, BN) int32
    ``sum_k sign(q_k) * sign(d_k)``.

    Panel b holds the query dims k = 8 * j + b (byte-major then bit,
    matching `bitplanar.pack_sign_plane`), so bit b of doc byte j pairs
    with panel b's column j."""
    x = block_u8.astype(jnp.int32)
    s = None
    for bit in range(8):
        pm1 = (1 - 2 * ((x >> bit) & 1)).astype(jnp.int8)
        t = mac_nt(q_bits[bit], pm1)
        s = t if s is None else s + t
    return s


def _stage0_batched_kernel(q_ref, plane_ref, out_ref):
    """q_ref: (8, B, D8) int8 {+1,-1} bit panels, pinned; plane_ref:
    (BN, D8) uint8 packed sign bytes; out: (B, BN). True matmuls — each
    doc sign block is unpacked (and fetched from HBM) once per BATCH."""
    out_ref[...] = score_sign_rows(q_ref[...], plane_ref[...])


@functools.partial(jax.jit, static_argnames=("block_n", "interpret"))
def stage0_sign_batched_pallas(q_bits: jax.Array, sign_plane: jax.Array, *,
                               block_n: int = DEFAULT_BLOCK_N,
                               interpret: bool | None = None) -> jax.Array:
    """Batch-native stage 0: q_bits (8, B, D//8) int8 {+1, -1} bit panels
    (`ops.sign_bit_panels`), sign_plane (N, D//8) uint8 packed sign bits,
    N % block_n == 0. Returns (B, N) int32 sign-agreement scores
    (2 * agreements - D). The query panels are grid-invariant (stationary
    in VMEM); every sign block streams HBM->VMEM exactly once for the
    whole batch. On a TPU block_n must be a multiple of 128 or all of N."""
    n, d8 = sign_plane.shape
    b = q_bits.shape[1]
    assert n % block_n == 0, (n, block_n)
    nb = n // block_n
    return pl.pallas_call(
        _stage0_batched_kernel,
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((8, b, d8), lambda i: (0, 0, 0)),  # queries: pinned
            pl.BlockSpec((block_n, d8), lambda i: (i, 0)),  # docs: streamed
        ],
        out_specs=pl.BlockSpec((b, block_n), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((b, n), jnp.int32),
        interpret=resolve_interpret(interpret),
    )(q_bits, sign_plane)


def gather_blocks_per_step(num_blocks: int, block_rows: int,
                           d8: int) -> int:
    """Blocks one grid step of the gather holds: all of a lane's blocks
    when their unpacked int32 tiles fit `GATHER_STEP_VMEM_BYTES`, else the
    largest divisor of `num_blocks` that does (one block at the least).
    A block's footprint counts its rows and lanes padded to the (8, 128)
    int32 tile it occupies once unpacked."""
    tile_bytes = (-(-block_rows // 8) * 8) * (-(-d8 // 128) * 128) * 4
    cap = max(1, GATHER_STEP_VMEM_BYTES // tile_bytes)
    return max(k for k in range(1, min(num_blocks, cap) + 1)
               if num_blocks % k == 0)


def _stage0_gather_kernel(ids_ref, q_ref, plane_hbm, out_ref, buf, sem):
    """ids_ref: (B, J) int32 prefetched block ids (SMEM); q_ref: (1, 8, M,
    W) int8 lane bit panels; plane_hbm: (N / BR, BR, W) uint8 sign blocks,
    left in HBM; out: (1, 1, M, JB * BR); buf: (JB, BR, W) uint8 VMEM;
    sem: one DMA semaphore shared by the step's copies. W is D8 padded
    with zero bytes to whole 128-lane rows.

    Every copy of the step's JB blocks starts before any is waited on, so
    the lane's blocks are in flight together; then one MAC scores the M
    query rows against all JB * BR gathered rows."""
    i, s = pl.program_id(0), pl.program_id(1)
    jb, br, w = buf.shape
    copies = [pltpu.make_async_copy(plane_hbm.at[ids_ref[i, s * jb + k]],
                                    buf.at[k], sem)
              for k in range(jb)]
    for c in copies:
        c.start()
    for c in copies:
        c.wait()
    rows = buf[...].astype(jnp.int32).reshape(jb * br, w)
    out_ref[0, 0] = score_sign_rows(q_ref[0], rows)


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def stage0_sign_gather_pallas(q_bits: jax.Array, sign_plane: jax.Array,
                              block_ids: jax.Array, *,
                              block_rows: int,
                              interpret: bool | None = None) -> jax.Array:
    """Block-gathered stage 0: q_bits (B, 8, M, D//8) int8 {+1, -1} bit
    panels, M query rows per lane (`ops.sign_bit_panels(..., per_lane=
    True)`); sign_plane (N, D//8) uint8 with N % block_rows == 0
    (zero-padded); block_ids (B, J) int32 ids in [0, N / block_rows) —
    for the retrieval cascade the SAME clamped per-lane table the
    stage-1 gather consumes (M = 1), for the KV prescreen one row per
    (sequence, KV head) with its G query heads as the M rows. Returns
    (B, M, J * block_rows) int32 sign-agreement scores in block-table
    order.

    ONE launch, grid (B, J / JB) with JB = `gather_blocks_per_step`:
    the ids are scalar-prefetched, each step copies its JB selected
    blocks HBM->VMEM with manual DMAs all in flight at once, so only
    selected blocks ever stream and the step count does not grow with
    the blocks a lane selects while they fit the VMEM budget."""
    n, d8 = sign_plane.shape
    b, _, m, _ = q_bits.shape
    j = block_ids.shape[1]
    assert n % block_rows == 0, (n, block_rows)
    jb = gather_blocks_per_step(j, block_rows, d8)
    steps = j // jb
    # Mosaic slices an HBM ref only along whole 128-lane rows, and XLA
    # already lays a narrower byte plane out padded to 128 lanes: pad
    # plane and query panels with zero columns (zero query bits add 0).
    wide = -(-d8 // 128) * 128
    if wide != d8:
        sign_plane = jnp.pad(sign_plane, ((0, 0), (0, wide - d8)))
        q_bits = jnp.pad(q_bits, ((0, 0), (0, 0), (0, 0), (0, wide - d8)))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, steps),
        in_specs=[
            pl.BlockSpec((1, 8, m, wide), lambda i, s, ids: (i, 0, 0, 0)),
            pl.BlockSpec(memory_space=pltpu.HBM),
        ],
        out_specs=pl.BlockSpec((1, 1, m, jb * block_rows),
                               lambda i, s, ids: (i, s, 0, 0)),
        scratch_shapes=[pltpu.VMEM((jb, block_rows, wide), jnp.uint8),
                        pltpu.SemaphoreType.DMA(())],
    )
    out = pl.pallas_call(
        _stage0_gather_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, steps, m, jb * block_rows),
                                       jnp.int32),
        interpret=resolve_interpret(interpret),
    )(block_ids, q_bits,
      sign_plane.reshape(n // block_rows, block_rows, wide))
    return out.transpose(0, 2, 1, 3).reshape(b, m, j * block_rows)
