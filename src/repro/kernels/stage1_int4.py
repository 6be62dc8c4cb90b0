"""Pallas TPU kernel: stage-1 MSB-nibble (INT4) MIPS, query-stationary.

Maps the paper's query-stationary PE dataflow onto a Pallas pipeline:

  * the packed query block's BlockSpec index_map returns (0, 0) for every
    grid step, so the query tile stays RESIDENT in VMEM (query-stationary);
  * document MSB-plane blocks stream HBM->VMEM through the grid — only the
    MSB nibble plane is ever touched (half the HBM bytes, the bit-planar
    saving);
  * nibbles are unpacked in-register (VREG) and the MAC runs on the MXU via
    int8 x int8 -> int32 dot_general with a 256-deep contraction
    (D/2 = 256 = 2 x 128, MXU-aligned).

The packed byte holds dim 2j in its low nibble and dim 2j+1 in its high
nibble, so instead of interleaving (a lane shuffle the MXU hates) we split
the QUERY into even/odd dim vectors and accumulate two matmuls:

    score = q_even . lo_nibbles^T + q_odd . hi_nibbles^T

Mosaic forms (what the TPU compiler accepts, shared by every kernel of
this package):

  * the unpack widens the packed bytes to int32, sign-extends each nibble
    with an arithmetic shift pair and narrows the result to int8 — int8
    vector arithmetic does not legalize, int32 shifts do;
  * every MAC is `mac_nt`: a 2-D (rows, K) x (cols, K) contraction over the
    LAST dim of both operands; a per-lane query is a (1, K) row, never a
    1-D vector;
  * every per-lane or per-block output is written as a 4-D array whose
    trailing (1, block) dims are the whole array's trailing dims, so any
    block width keeps the (8, 128) block rule; the wrappers reshape it
    back to the flat layout the oracles use.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.platform import resolve_interpret

# 1024 doc rows per grid step. At D=512 a block is 1024 x 256 bytes =
# 256 KiB of VMEM (512 KiB double-buffered), plus the int32/int8 unpack
# temporaries (~2 MiB) — inside v5e's 16 MiB default scoped VMEM, and a
# multiple of 128 so the batched kernels' (B, block) output tiles stay
# lane-dense. This is the deterministic FALLBACK shape: the measured
# autotuner (repro.kernels.autotune) owns the per-device, per-batch-bucket
# choice and the ops.py wrappers consult its installed table first.
DEFAULT_BLOCK_N = 1024
INT32_MIN = jnp.iinfo(jnp.int32).min

_NT = (((1,), (1,)), ((), ()))


def mac_nt(q: jax.Array, docs: jax.Array) -> jax.Array:
    """(M, K) int8 x (N, K) int8 -> (M, N) int32, contracting the last dims."""
    return jax.lax.dot_general(q, docs, _NT, preferred_element_type=jnp.int32)


def unpack_plane_even_odd(plane: jax.Array) -> tuple[jax.Array, jax.Array]:
    """(BN, D2) packed uint8 -> (even, odd) signed int8 nibble matrices.

    Shift left puts the nibble's sign bit at bit 31; the arithmetic shift
    right sign-extends it back down — exact two's complement in [-8, 7]."""
    x = plane.astype(jnp.int32)
    even = (x << 28) >> 28
    odd = (x << 24) >> 28
    return even.astype(jnp.int8), odd.astype(jnp.int8)


def score_rows(q: jax.Array, rows: jax.Array) -> jax.Array:
    """q (2, D2) int8 [even; odd] panel, rows (BN, D2) packed -> (1, BN)."""
    even, odd = unpack_plane_even_odd(rows)
    return mac_nt(q[0:1], even) + mac_nt(q[1:2], odd)


def _stage1_kernel(q_ref, plane_ref, out_ref):
    """q_ref: (2, D2) int8 pinned; plane_ref: (BN, D2) uint8;
    out: (1, 1, BN)."""
    out_ref[0] = score_rows(q_ref[...], plane_ref[...])


def _stage1_batched_kernel(q_ref, plane_ref, out_ref):
    """q_ref: (2, B, D2) int8 pinned; plane_ref: (BN, D2) uint8; out: (B, BN).

    The MAC is a TRUE matmul — (B, D2) query panel x (BN, D2) doc block —
    so the MXU sees a B-wide contraction instead of B repeated matvecs,
    and each doc block is unpacked (and fetched from HBM) once PER BATCH.
    """
    even, odd = unpack_plane_even_odd(plane_ref[...])
    out_ref[...] = mac_nt(q_ref[0], even) + mac_nt(q_ref[1], odd)


@functools.partial(jax.jit, static_argnames=("block_n", "interpret"))
def stage1_int4_batched_pallas(q_eo: jax.Array, msb_plane: jax.Array, *,
                               block_n: int = DEFAULT_BLOCK_N,
                               interpret: bool | None = None) -> jax.Array:
    """Batch-native stage 1: q_eo (2, B, D//2) int8 signed MSB nibbles
    (even dims; odd dims), msb_plane (N, D//2) uint8, N % block_n == 0.
    Returns (B, N) int32. The query panel is grid-invariant (stationary in
    VMEM); every doc block streams HBM->VMEM exactly once for the whole
    batch — the bytes-streamed win over vmapping the scalar kernel. On a
    TPU block_n must be a multiple of 128 or all of N (the output tile is
    (B, block_n))."""
    n, d2 = msb_plane.shape
    b = q_eo.shape[1]
    assert n % block_n == 0, (n, block_n)
    nb = n // block_n
    return pl.pallas_call(
        _stage1_batched_kernel,
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((2, b, d2), lambda i: (0, 0, 0)),  # queries: pinned
            pl.BlockSpec((block_n, d2), lambda i: (i, 0)),  # docs: streamed
        ],
        out_specs=pl.BlockSpec((b, block_n), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((b, n), jnp.int32),
        interpret=resolve_interpret(interpret),
    )(q_eo, msb_plane)


def _stage1_rows_kernel(q_ref, rows_ref, out_ref):
    """q_ref: (1, 2, D2) int8; rows_ref: (1, BW, D2) uint8;
    out: (1, 1, 1, BW).

    Per-lane variant for the windowed policy: grid axis 0 walks batch
    lanes (each with its OWN row block, e.g. a tenant's arena window),
    axis 1 walks that lane's row blocks."""
    out_ref[0, 0] = score_rows(q_ref[0], rows_ref[0])


@functools.partial(jax.jit, static_argnames=("block_w", "interpret"))
def stage1_int4_rows_pallas(q_eo: jax.Array, msb_rows: jax.Array, *,
                            block_w: int = DEFAULT_BLOCK_N,
                            interpret: bool | None = None) -> jax.Array:
    """Per-lane-rows stage 1: q_eo (B, 2, D//2) int8 nibbles, msb_rows
    (B, W, D//2) uint8 with W % block_w == 0. Returns (B, W) int32 — one
    launch for the whole batch (grid (B, W/block_w))."""
    b, w, d2 = msb_rows.shape
    assert w % block_w == 0, (w, block_w)
    nw = w // block_w
    out = pl.pallas_call(
        _stage1_rows_kernel,
        grid=(b, nw),
        in_specs=[
            pl.BlockSpec((1, 2, d2), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((1, block_w, d2), lambda i, j: (i, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, 1, block_w), lambda i, j: (i, j, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, nw, 1, block_w), jnp.int32),
        interpret=resolve_interpret(interpret),
    )(q_eo, msb_rows)
    return out.reshape(b, w)


@functools.partial(jax.jit, static_argnames=("block_n", "interpret"))
def stage1_int4_pallas(q_eo: jax.Array, msb_plane: jax.Array, *,
                       block_n: int = DEFAULT_BLOCK_N,
                       interpret: bool | None = None) -> jax.Array:
    """q_eo: (2, D//2) int8 signed MSB nibbles (even dims; odd dims).
    msb_plane: (N, D//2) uint8, N % block_n == 0. Returns (N,) int32."""
    n, d2 = msb_plane.shape
    assert n % block_n == 0, (n, block_n)
    nb = n // block_n
    out = pl.pallas_call(
        _stage1_kernel,
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((2, d2), lambda i: (0, 0)),       # query: stationary
            pl.BlockSpec((block_n, d2), lambda i: (i, 0)),  # docs: streamed
        ],
        out_specs=pl.BlockSpec((1, 1, block_n), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((nb, 1, block_n), jnp.int32),
        interpret=resolve_interpret(interpret),
    )(q_eo, msb_plane)
    return out.reshape(n)
