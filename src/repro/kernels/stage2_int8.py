"""Pallas TPU kernel: stage-2 full-INT8 exact rescoring of candidates.

The candidate rows (top-C from stage 1, C ~ 50) have been gathered into
dense (C, D//2) MSB and LSB planes. The kernel reconstructs the INT8
values in-register (msb*16 + lsb, exactly inverting the nibble split) and
runs the exact int8 MAC on the MXU. The query is again pinned in VMEM.

On the paper's 4-bit PEs an 8x8 multiply is decomposed into 4 nibble
products (their refs [24][25]); on TPU the MXU natively does int8, so the
reconstruction happens in VREG and the MAC is a single int8 dot — same
arithmetic result, hardware-appropriate mapping (DESIGN.md §8). The
unpack, MAC and output layout follow the Mosaic forms documented in
`stage1_int4`.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.platform import resolve_interpret
from repro.kernels.stage1_int4 import mac_nt

DEFAULT_BLOCK_C = 64


def _reconstruct_even_odd(msb: jax.Array, lsb: jax.Array):
    """Packed planes -> (even-dim, odd-dim) int8 value matrices.

    The signed MSB nibble lands in bits 4..7 (sign-extended above) and the
    unsigned LSB nibble fills bits 0..3: msb * 16 + lsb in [-128, 127]."""
    m = msb.astype(jnp.int32)
    lo = lsb.astype(jnp.int32)
    de = ((m << 28) >> 24) | (lo & 0xF)
    do = ((m << 24) >> 28 << 4) | ((lo >> 4) & 0xF)
    return de.astype(jnp.int8), do.astype(jnp.int8)


def _score_exact(q: jax.Array, msb: jax.Array, lsb: jax.Array) -> jax.Array:
    """q (2, D2) int8 [even; odd]; planes (BC, D2) uint8 -> (1, BC) int32."""
    de, do = _reconstruct_even_odd(msb, lsb)
    return mac_nt(q[0:1], de) + mac_nt(q[1:2], do)


def _stage2_kernel(q_ref, msb_ref, lsb_ref, out_ref):
    """q_ref: (2, D2) int8 pinned; planes: (BC, D2) uint8; out: (1, 1, BC)."""
    out_ref[0] = _score_exact(q_ref[...], msb_ref[...], lsb_ref[...])


def _stage2_batched_kernel(q_ref, msb_ref, lsb_ref, out_ref):
    """q_ref: (1, 2, D2) int8; planes: (1, BC, D2) uint8; out: (1, 1, 1, BC).

    Batched variant: grid axis 0 walks batch lanes (each lane rescores its
    OWN gathered candidate rows with its OWN query), axis 1 walks that
    lane's candidate blocks — the whole (B, C) rescore is ONE launch."""
    out_ref[0, 0] = _score_exact(q_ref[0], msb_ref[0], lsb_ref[0])


@functools.partial(jax.jit, static_argnames=("block_c", "interpret"))
def stage2_int8_batched_pallas(q_eo8: jax.Array, msb_rows: jax.Array,
                               lsb_rows: jax.Array, *,
                               block_c: int = DEFAULT_BLOCK_C,
                               interpret: bool | None = None) -> jax.Array:
    """q_eo8: (B, 2, D//2) int8 full query values (even dims; odd dims).
    msb_rows/lsb_rows: (B, C, D//2) uint8 gathered per-lane candidates,
    C % block_c == 0. Returns (B, C) int32 exact scores, one launch."""
    b, c, d2 = msb_rows.shape
    assert c % block_c == 0, (c, block_c)
    nb = c // block_c
    out = pl.pallas_call(
        _stage2_batched_kernel,
        grid=(b, nb),
        in_specs=[
            pl.BlockSpec((1, 2, d2), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((1, block_c, d2), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, block_c, d2), lambda i, j: (i, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, 1, block_c), lambda i, j: (i, j, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, nb, 1, block_c), jnp.int32),
        interpret=resolve_interpret(interpret),
    )(q_eo8, msb_rows, lsb_rows)
    return out.reshape(b, c)


@functools.partial(jax.jit, static_argnames=("block_c", "interpret"))
def stage2_int8_pallas(q_eo8: jax.Array, msb_rows: jax.Array,
                       lsb_rows: jax.Array, *,
                       block_c: int = DEFAULT_BLOCK_C,
                       interpret: bool | None = None) -> jax.Array:
    """q_eo8: (2, D//2) int8 full query values (even dims; odd dims).
    msb_rows/lsb_rows: (C, D//2) uint8, C % block_c == 0. Returns (C,) int32."""
    c, d2 = msb_rows.shape
    assert c % block_c == 0, (c, block_c)
    nb = c // block_c
    out = pl.pallas_call(
        _stage2_kernel,
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((2, d2), lambda i: (0, 0)),        # query: stationary
            pl.BlockSpec((block_c, d2), lambda i: (i, 0)),
            pl.BlockSpec((block_c, d2), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_c), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((nb, 1, block_c), jnp.int32),
        interpret=resolve_interpret(interpret),
    )(q_eo8, msb_rows, lsb_rows)
    return out.reshape(c)
