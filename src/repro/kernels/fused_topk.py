"""Pallas TPU kernel: fused stage-1 scoring + per-block top-k (beyond-paper).

The baseline stage-1 writes all N int32 scores back to HBM and then runs a
global top-k — an N*4-byte writeback plus an N*4-byte re-read. This kernel
keeps each block's scores in VMEM and emits only that block's top-k
(score, global-id) pairs, shrinking the score writeback from N to
(N / block_n) * k entries (e.g. 256x smaller for block_n=512, k=8 — see
EXPERIMENTS.md §Perf).

Selection is an unrolled iterative argmax over the (1, block_n) score
row (k is small and static): a lane max, then the lowest column holding
it (ties broken toward the lower index — matching ref.fused_topk_ref
bit-exactly), all in 2-D forms Mosaic lowers. The final cross-block
top-C reduction happens in the wrapper on (N/block_n)*k entries.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.platform import resolve_interpret
from repro.kernels.stage1_int4 import score_rows

DEFAULT_BLOCK_N = 512
INT32_MIN = jnp.iinfo(jnp.int32).min


def _block_topk(s: jax.Array, k: int) -> tuple[jax.Array, jax.Array]:
    """(1, BN) int32 scores -> (1, k) values and (1, k) block-local ids,
    descending, lowest index first on ties."""
    bn = s.shape[1]
    col = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    slot = jax.lax.broadcasted_iota(jnp.int32, (1, k), 1)
    vals = jnp.zeros((1, k), jnp.int32)
    idxs = jnp.zeros((1, k), jnp.int32)
    for t in range(k):
        v = jnp.max(s, axis=1, keepdims=True)
        i = jnp.min(jnp.where(s == v, col, bn), axis=1, keepdims=True)
        s = jnp.where(col == i, INT32_MIN, s)
        vals = jnp.where(slot == t, v, vals)
        idxs = jnp.where(slot == t, i, idxs)
    return vals, idxs


def _fused_kernel(q_ref, plane_ref, out_s_ref, out_i_ref, *, k: int,
                  block_n: int):
    """q_ref: (2, D2) pinned; plane_ref: (BN, D2); outs: (1, 1, k)."""
    vals, idxs = _block_topk(score_rows(q_ref[...], plane_ref[...]), k)
    out_s_ref[0] = vals
    out_i_ref[0] = pl.program_id(0) * block_n + idxs


def _fused_batched_kernel(tid_ref, q_ref, plane_ref, owner_ref, out_s_ref,
                          out_i_ref, *, k: int, block_n: int, masked: bool):
    """Batched fused stage-1 + per-block top-k, one (doc-block, lane) cell.

    The grid is (num_blocks, BATCH) with the batch axis INNERMOST: the doc
    block's BlockSpec index ignores the lane, so Pallas fetches each plane
    block from HBM once and keeps it VMEM-resident while every lane scores
    it — once-per-batch streaming. With `masked`, the lane's tenant segment
    mask is applied to the scores IN VMEM before selection, so masked rows
    never leave the kernel (no (B, N) masked-score writeback at all). The
    lanes' tenant ids are scalar-prefetched into SMEM (`tid_ref`)."""
    s = score_rows(q_ref[0], plane_ref[...])
    if masked:
        tid = tid_ref[pl.program_id(1)]
        member = (owner_ref[...] == tid) & (tid >= 0)
        s = jnp.where(member, s, INT32_MIN)
    vals, idxs = _block_topk(s, k)
    out_s_ref[0, 0] = vals
    out_i_ref[0, 0] = pl.program_id(0) * block_n + idxs


@functools.partial(jax.jit, static_argnames=("k", "block_n", "interpret"))
def fused_topk_batched_pallas(q_eo: jax.Array, msb_plane: jax.Array,
                              owner: jax.Array | None = None,
                              tenant_ids: jax.Array | None = None, *,
                              k: int = 8, block_n: int = DEFAULT_BLOCK_N,
                              interpret: bool | None = None
                              ) -> tuple[jax.Array, jax.Array]:
    """q_eo: (B, 2, D//2) int8 signed MSB nibbles; msb_plane: (N, D//2)
    uint8; optionally owner (N,) int32 + tenant_ids (B,) int32 to apply the
    per-lane segment mask inside the kernel (rows outside lane i's tenant
    score INT32_MIN and can never be emitted). Returns (scores, global_ids),
    each (B, N // block_n, k) int32."""
    n, d2 = msb_plane.shape
    b = q_eo.shape[0]
    assert n % block_n == 0, (n, block_n)
    nb = n // block_n
    masked = owner is not None
    if masked != (tenant_ids is not None):
        raise ValueError("owner and tenant_ids must be passed together")
    kernel = functools.partial(_fused_batched_kernel, k=k, block_n=block_n,
                               masked=masked)
    if not masked:  # placeholders keep one kernel signature
        owner = jnp.zeros((n,), jnp.int32)
        tenant_ids = jnp.zeros((b,), jnp.int32)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(nb, b),                                    # lanes innermost
        in_specs=[
            pl.BlockSpec((1, 2, d2), lambda i, j, t: (j, 0, 0)),  # lane query
            pl.BlockSpec((block_n, d2), lambda i, j, t: (i, 0)),  # doc block:
            # index ignores j => resident across the whole inner lane sweep
            pl.BlockSpec((1, block_n), lambda i, j, t: (0, i)),   # owner block
        ],
        out_specs=[
            pl.BlockSpec((1, 1, 1, k), lambda i, j, t: (j, i, 0, 0)),
            pl.BlockSpec((1, 1, 1, k), lambda i, j, t: (j, i, 0, 0)),
        ],
    )
    scores, ids = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((b, nb, 1, k), jnp.int32),
            jax.ShapeDtypeStruct((b, nb, 1, k), jnp.int32),
        ],
        interpret=resolve_interpret(interpret),
    )(tenant_ids.astype(jnp.int32), q_eo, msb_plane, owner.reshape(1, n))
    return scores.reshape(b, nb, k), ids.reshape(b, nb, k)


@functools.partial(jax.jit, static_argnames=("k", "block_n", "interpret"))
def fused_topk_pallas(q_eo: jax.Array, msb_plane: jax.Array, *, k: int = 8,
                      block_n: int = DEFAULT_BLOCK_N,
                      interpret: bool | None = None
                      ) -> tuple[jax.Array, jax.Array]:
    """q_eo: (2, D//2) int8 signed MSB nibbles; msb_plane: (N, D//2) uint8.
    Returns (scores, global_ids), each (N // block_n, k) int32."""
    n, d2 = msb_plane.shape
    assert n % block_n == 0, (n, block_n)
    nb = n // block_n
    kernel = functools.partial(_fused_kernel, k=k, block_n=block_n)
    scores, ids = pl.pallas_call(
        kernel,
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((2, d2), lambda i: (0, 0)),        # query: stationary
            pl.BlockSpec((block_n, d2), lambda i: (i, 0)),  # docs: streamed
        ],
        out_specs=[
            pl.BlockSpec((1, 1, k), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, 1, k), lambda i: (i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((nb, 1, k), jnp.int32),
            jax.ShapeDtypeStruct((nb, 1, k), jnp.int32),
        ],
        interpret=resolve_interpret(interpret),
    )(q_eo, msb_plane)
    return scores.reshape(nb, k), ids.reshape(nb, k)
