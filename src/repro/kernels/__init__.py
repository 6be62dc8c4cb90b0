"""Pallas TPU kernels for the paper's compute hot-spots.

  stage0_sign   — 1-bit sign-plane prescreen (batched and block-gathered)
  stage1_int4   — query-stationary MSB-nibble MIPS over the whole corpus
  stage1_gather — block-GATHERED stage-1 for the cluster-pruned cascade
                  (scalar-prefetch DMA: only selected blocks stream)
  stage2_int8   — exact INT8 rescoring of the gathered candidate set
  fused_topk    — stage-1 scoring fused with per-block top-k (beyond-paper)

ops.py: jit'd wrappers (query packing, row padding, block lookup).
platform.py: the platform keying — compiled Mosaic and the kernel path on
a TPU, the interpreter and the jnp reference elsewhere.
ref.py: pure-jnp oracles; tests assert exact equality against them.
autotune.py: measured block-shape search; ops wrappers consult the
installed table (falling back to DEFAULT_BLOCK_N when none).
"""
from repro.kernels import autotune, ops, ref
from repro.kernels.stage1_int4 import stage1_int4_pallas
from repro.kernels.stage1_gather import stage1_int4_gather_pallas
from repro.kernels.stage2_int8 import stage2_int8_pallas
from repro.kernels.fused_topk import fused_topk_pallas
