"""Pallas TPU kernel: block-GATHERED stage-1 MSB-nibble MIPS.

The cluster-pruned cascade's stage 1 must scan only the rows of each
lane's selected clusters. Materializing that gather on the host (copy the
blocks, then run the dense per-lane kernel) would stream every selected
row TWICE — once for the copy, once for the scan. This kernel instead
uses `pltpu.PrefetchScalarGridSpec` scalar prefetch: the per-lane block-id
table is available before the kernel body runs, so each grid step's
BlockSpec index_map DMAs the selected plane block HBM->VMEM directly —
the gather IS the scan's input stream, and unselected blocks are never
touched.

Dataflow per grid step (i = batch lane, j = probe-block slot):

  * the lane's packed query pair stays resident in VMEM across its whole
    block sweep (query-stationary, as in the dense stage-1 kernels);
  * plane block `block_ids[i, j]` streams HBM->VMEM (the data-dependent
    index_map — the only difference from the dense per-lane kernel);
  * nibbles unpack in-register and the MAC runs on the MXU as a (1, D/2)
    query row against the (block_rows, D/2) block.

block_ids must be pre-clamped to valid blocks (holes -> 0); the caller
masks hole scores downstream via its membership mask, exactly like the
dense paths mask out-of-segment rows. The plane is padded to a block
multiple with zero rows, so out-of-range rows score 0 — the jnp reference
(engine.stage1_gather_batched_jnp) reproduces this bit-for-bit.

The serving runtime's hot-cluster cache drives this SAME kernel over TWO
sources at once: its `plane` operand is the combined ``[arena plane |
device-resident cache slab]`` array, and the prefetched id table mixes
arena-region block ids (cache misses — streamed from HBM) with
slab-region ids (hits — the cache-owned copies, never re-uploaded). The
kernel is indifferent: a block id is a block id; on hardware the slab
region is the natural candidate for pinning in faster memory. That path
is pre-validated host-side, so its jnp reference is the unclamped
engine.stage1_gather_resident_jnp / ref.stage1_gather_resident_ref.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.platform import resolve_interpret
from repro.kernels.stage1_int4 import score_rows

DEFAULT_BLOCK_ROWS = 64


def _stage1_gather_kernel(ids_ref, q_ref, plane_ref, out_ref):
    """ids_ref: (B, J) int32 prefetched block ids (consumed by index_maps);
    q_ref: (1, 2, D2) int8 lane query pair; plane_ref: (BR, D2) uint8 —
    the block the index_map selected; out: (1, 1, 1, BR)."""
    del ids_ref  # only read by the BlockSpec index_maps
    out_ref[0, 0] = score_rows(q_ref[0], plane_ref[...])


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def stage1_int4_gather_pallas(q_eo: jax.Array, msb_plane: jax.Array,
                              block_ids: jax.Array, *,
                              block_rows: int = DEFAULT_BLOCK_ROWS,
                              interpret: bool | None = None) -> jax.Array:
    """q_eo: (B, 2, D//2) int8 signed MSB nibble pairs (even; odd dims).
    msb_plane: (N, D//2) uint8 with N % block_rows == 0 (zero-padded).
    block_ids: (B, J) int32 ids in [0, N / block_rows) — the lane's
    selected plane blocks, already clamped (no -1 holes).
    Returns (B, J * block_rows) int32: lane i's scores over its gathered
    rows, in block-table order. ONE launch, grid (B, J); only the
    selected blocks ever stream from HBM.
    """
    n, d2 = msb_plane.shape
    b, j = block_ids.shape
    assert n % block_rows == 0, (n, block_rows)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, j),
        in_specs=[
            pl.BlockSpec((1, 2, d2), lambda i, jj, ids: (i, 0, 0)),
            pl.BlockSpec((block_rows, d2),
                         lambda i, jj, ids: (ids[i, jj], 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, 1, block_rows),
                               lambda i, jj, ids: (i, jj, 0, 0)),
    )
    out = pl.pallas_call(
        _stage1_gather_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, j, 1, block_rows), jnp.int32),
        interpret=resolve_interpret(interpret),
    )(block_ids, q_eo, msb_plane)
    return out.reshape(b, j * block_rows)
