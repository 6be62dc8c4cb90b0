"""Every Pallas kernel compiles for a TPU v5e at the served widths.

Interpret mode accepts layouts the TPU compiler (Mosaic) refuses, so the
parity suites alone cannot show that the kernels run on the chip. These
tests compile each kernel with `interpret=False` for a DESCRIBED v5e
topology (no chip attached) at the widths the RAG agent turn serves:
512-d embeddings over a 65,536-slot arena, query batch 8, the
cluster-pruned gather at 32-row blocks, and the KV cascade of a
qwen2-0.5b decode step (head_dim 64, 8-row pages, 14 query heads over 2
KV heads at batch 4). A compile that passes here is not a chip run; it
proves only that the chip's compiler accepts the kernel and its VMEM use.

The topology is described inside a module-scoped fixture (never at
import): only one process may hold the TPU library, and pytest-xdist
workers each import this file.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import fused_topk as fk
from repro.kernels import stage0_sign as s0
from repro.kernels import stage1_gather as sg
from repro.kernels import stage1_int4 as s1
from repro.kernels import stage2_int8 as s2

D = 512                    # pooled embedding width (the paper's)
N = 65_536                 # arena slots
B = 8                      # query batch
BLOCK_ROWS = 32            # serving gather block
J = 128                    # probed blocks per lane (nprobe x blocks/cluster)
SLAB = 4_096               # hot-cluster slab rows appended to the plane
CLUSTERS = 256             # codebook rows scored by the centroid prune
CANDIDATES = 64            # stage-2 rescore rows per lane
WINDOW = 8_192             # one tenant's arena window (8 x 8,192 docs)
# KV cascade of one qwen2-0.5b decode step at batch 4 (the agent cell:
# 512-position prompts + 64 tokens = 576 positions, 16 pages kept)
HD, KVH, G, KV_B, KV_T, PAGE, KV_KEPT = 64, 2, 7, 4, 576, 8, 16
KV_LANES = KV_B * KVH * G


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """One described v5e device, with the persistent compile cache off:
    entries written for a described chip cannot be read back here."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _i8(*shape):
    return (shape, jnp.int8)


def _u8(*shape):
    return (shape, jnp.uint8)


def _i32(*shape):
    return (shape, jnp.int32)


# name -> (kernel, operand (shape, dtype) list, static kwargs)
CASES = {
    "stage0_batched": (s0.stage0_sign_batched_pallas,
                       [_i8(8, B, D // 8), _u8(N, D // 8)],
                       dict(block_n=s0.DEFAULT_BLOCK_N)),
    "stage0_gather": (s0.stage0_sign_gather_pallas,
                      [_i8(B, 8, 1, D // 8), _u8(N, D // 8), _i32(B, J)],
                      dict(block_rows=BLOCK_ROWS)),
    "stage0_resident": (s0.stage0_sign_gather_pallas,
                        [_i8(B, 8, 1, D // 8), _u8(N + SLAB, D // 8),
                         _i32(B, J)],
                        dict(block_rows=BLOCK_ROWS)),
    "stage1_batched": (s1.stage1_int4_batched_pallas,
                       [_i8(2, B, D // 2), _u8(N, D // 2)],
                       dict(block_n=s1.DEFAULT_BLOCK_N)),
    "stage1_rows": (s1.stage1_int4_rows_pallas,
                    [_i8(B, 2, D // 2), _u8(B, WINDOW, D // 2)],
                    dict(block_w=s1.DEFAULT_BLOCK_N)),
    "stage1_gather": (sg.stage1_int4_gather_pallas,
                      [_i8(B, 2, D // 2), _u8(N, D // 2), _i32(B, J)],
                      dict(block_rows=BLOCK_ROWS)),
    "stage1_resident": (sg.stage1_int4_gather_pallas,
                        [_i8(B, 2, D // 2), _u8(N + SLAB, D // 2),
                         _i32(B, J)],
                        dict(block_rows=BLOCK_ROWS)),
    "centroid": (s1.stage1_int4_batched_pallas,
                 [_i8(2, B, D // 2), _u8(CLUSTERS, D // 2)],
                 dict(block_n=CLUSTERS)),
    "stage2_batched": (s2.stage2_int8_batched_pallas,
                       [_i8(B, 2, D // 2), _u8(B, CANDIDATES, D // 2),
                        _u8(B, CANDIDATES, D // 2)],
                       dict(block_c=s2.DEFAULT_BLOCK_C)),
    "kv_rows_hd64": (s1.stage1_int4_rows_pallas,
                     [_i8(KV_LANES, 2, HD // 2),
                      _u8(KV_LANES, KV_T // PAGE, HD // 2)],
                     dict(block_w=KV_T // PAGE)),
    "kv_gather_hd64": (s0.stage0_sign_gather_pallas,
                       [_i8(KV_LANES, 8, 1, HD // 8),
                        _u8(KV_B * KVH * KV_T, HD // 8),
                        _i32(KV_LANES, KV_KEPT)],
                       dict(block_rows=PAGE)),
    # the KV prescreen's call: one lane per (sequence, KV head), its G
    # query heads as G rows against one gather of its kept pages
    "kv_gather_gqa_shared": (s0.stage0_sign_gather_pallas,
                             [_i8(KV_B * KVH, 8, G, HD // 8),
                              _u8(KV_B * KVH * KV_T, HD // 8),
                              _i32(KV_B * KVH, KV_KEPT)],
                             dict(block_rows=PAGE)),
    # kernels off the served path, kept compilable all the same
    "stage1_single": (s1.stage1_int4_pallas,
                      [_i8(2, D // 2), _u8(N, D // 2)],
                      dict(block_n=s1.DEFAULT_BLOCK_N)),
    "stage2_single": (s2.stage2_int8_pallas,
                      [_i8(2, D // 2), _u8(CANDIDATES, D // 2),
                       _u8(CANDIDATES, D // 2)],
                      dict(block_c=s2.DEFAULT_BLOCK_C)),
    "fused_topk_single": (fk.fused_topk_pallas,
                          [_i8(2, D // 2), _u8(N, D // 2)],
                          dict(k=8, block_n=fk.DEFAULT_BLOCK_N)),
    "fused_topk_batched_masked": (fk.fused_topk_batched_pallas,
                                  [_i8(B, 2, D // 2), _u8(N, D // 2),
                                   _i32(N), _i32(B)],
                                  dict(k=8, block_n=fk.DEFAULT_BLOCK_N)),
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_kernel_compiles_for_v5e(name, one_chip):
    kernel, operands, static = CASES[name]
    args = [jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
            for shape, dtype in operands]
    compiled = jax.jit(
        lambda *a: kernel(*a, interpret=False, **static)).lower(*args).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text, "no Mosaic kernel in the program"
    mem = compiled.memory_analysis()
    # Generous bound on what the program itself allocates in HBM: the
    # kernels stream their operands and write one int32 score per row.
    assert mem.temp_size_in_bytes < 64 << 20, mem
