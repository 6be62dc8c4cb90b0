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

Two variants mirror the stage-1 pair:

  * `stage0_sign_batched_pallas` — dense batched matmul over the whole
    plane, grid (num_blocks,), doc sign blocks streamed HBM->VMEM once
    per BATCH (the shape `stage1_int4_batched_pallas` uses);
  * `stage0_sign_gather_pallas` — scalar-prefetch block gather driven by
    the SAME per-lane block-id table as the stage-1 gather (the cluster
    prune's output), so only selected clusters' sign blocks ever stream.

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


def score_sign_rows(q_bits: jax.Array, block_u8: jax.Array) -> jax.Array:
    """q_bits (8, M, D8) int8 bit panels; block_u8 (BN, D8) packed sign
    bytes -> (M, BN) int32 ``sum_k sign(q_k) * sign(d_k)``.

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


def _stage0_gather_kernel(ids_ref, q_ref, plane_ref, out_ref):
    """ids_ref: (B, J) int32 prefetched block ids (consumed by the
    BlockSpec index_maps); q_ref: (1, 8, 1, D8) int8 lane bit panels;
    plane_ref: (BR, D8) uint8 — the sign block the index_map selected;
    out: (1, 1, 1, BR)."""
    del ids_ref  # only read by the BlockSpec index_maps
    out_ref[0, 0] = score_sign_rows(q_ref[0], plane_ref[...])


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def stage0_sign_gather_pallas(q_bits: jax.Array, sign_plane: jax.Array,
                              block_ids: jax.Array, *,
                              block_rows: int,
                              interpret: bool | None = None) -> jax.Array:
    """Block-gathered stage 0: q_bits (B, 8, 1, D//8) int8 {+1, -1} lane
    bit panels (`ops.sign_bit_panels(..., per_lane=True)`); sign_plane
    (N, D//8) uint8 with N % block_rows == 0 (zero-padded); block_ids
    (B, J) int32 ids in [0, N / block_rows) — the SAME clamped per-lane
    table the stage-1 gather consumes, so the prescreen's view geometry
    can never drift from the scan it is pruning. Returns (B, J *
    block_rows) int32 sign-agreement scores in block-table order. ONE
    launch, grid (B, J), scalar-prefetched ids: only selected blocks
    ever stream from HBM."""
    n, d8 = sign_plane.shape
    b, j = block_ids.shape
    assert n % block_rows == 0, (n, block_rows)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, j),
        in_specs=[
            pl.BlockSpec((1, 8, 1, d8), lambda i, jj, ids: (i, 0, 0, 0)),
            pl.BlockSpec((block_rows, d8),
                         lambda i, jj, ids: (ids[i, jj], 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, 1, block_rows),
                               lambda i, jj, ids: (i, jj, 0, 0)),
    )
    out = pl.pallas_call(
        _stage0_gather_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, j, 1, block_rows), jnp.int32),
        interpret=resolve_interpret(interpret),
    )(block_ids, q_bits, sign_plane)
    return out.reshape(b, j * block_rows)
