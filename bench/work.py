"""Required work of the timed paths, from the cell's shapes alone.

These functions count the bytes a cascade must read and the operations it
must do, whatever implements it: they never read the program's plan
counters, its HLO or its kernel choices. A roofline share divides the
least time of this work (the larger of operations over the peak rate and
bytes over the HBM bandwidth) by measured device time; an `mfu` share
divides operations per second by the peak rate.

Operations are counted as two per multiply-accumulate, and a 1-bit sign
agreement as one multiply-accumulate per dimension, all at the int8 rate.
"""
from __future__ import annotations

import dataclasses
import math


@dataclasses.dataclass(frozen=True)
class Work:
    """Operations and bytes, additive."""

    ops: float = 0.0
    bytes: float = 0.0

    def __add__(self, other: "Work") -> "Work":
        return Work(self.ops + other.ops, self.bytes + other.bytes)

    def scaled(self, n: float) -> "Work":
        return Work(self.ops * n, self.bytes * n)


def least_time_s(work: Work, op_rate: float, byte_rate: float) -> float:
    """The least time a chip can take for `work`: the larger bound."""
    return max(work.ops / op_rate, work.bytes / byte_rate)


def retrieval_query(*, docs_per_tenant: int, dim: int, num_clusters: int,
                    nprobe: int, prescreen_c0: int, candidates: int,
                    k: int) -> Work:
    """One query through the cluster-pruned precision cascade.

    Centroid prune: K centroids as 4-bit rows plus their norms. Sign
    prescreen: the probed share of the tenant's corpus as 1-bit rows.
    INT4 scan: the C0 survivors' 4-bit rows and norms. INT8 rescore: the
    candidates' 8-bit rows and norms. Plus the query and the k results.
    """
    view = docs_per_tenant * min(nprobe, num_clusters) / num_clusters
    c0 = min(prescreen_c0, view)
    c = min(candidates, c0)
    prune = Work(2 * num_clusters * dim, num_clusters * (dim / 2 + 4))
    sign = Work(view * dim, view * dim / 8)
    scan = Work(2 * c0 * dim, c0 * (dim / 2 + 4))
    rescore = Work(2 * c * dim, c * (dim + 4))
    io = Work(0, dim + k * 8)
    return prune + sign + scan + rescore + io


def kv_cascade_step(*, length: int, layers: int, kv_heads: int,
                    q_heads: int, head_dim: int, page_rows: int,
                    npages: int, prescreen_c0: int, top_k: int) -> Work:
    """One sequence's decode step of the paged KV cascade, all layers.

    Per (layer, kv head), over a cache of `length` positions: the valid
    pages' 4-bit centroids and scales, scored by the G query heads of the
    group; the kept pages' 1-bit keys; the C0 survivors' 4-bit keys and
    scales; the top-k keys at 8 bits with scales and their bf16 values,
    attended by every query head of the group (scores and weighted sum).
    """
    g = q_heads // kv_heads
    hd = head_dim
    pages = math.ceil(length / page_rows)
    kept = min(npages, pages) * page_rows
    c0 = min(prescreen_c0, kept)
    k = min(top_k, c0)
    prune = Work(2 * g * pages * hd, pages * (hd / 2 + 4))
    sign = Work(g * kept * hd, kept * hd / 8)
    scan = Work(2 * g * c0 * hd, c0 * (hd / 2 + 4))
    attend = Work(2 * 2 * g * k * hd, k * (hd + 4 + 2 * hd))
    return (prune + sign + scan + attend).scaled(layers * kv_heads)


def dense_forward_flops(*, positions: int, context: float, layers: int,
                        d_model: int, q_heads: int, kv_heads: int,
                        head_dim: int, d_ff: int, vocab: int) -> float:
    """FLOPs of a GQA + SwiGLU decoder forward over `positions` tokens,
    each attending `context` positions on average, with the output head.
    The embedding lookup does no arithmetic and is not counted."""
    proj = d_model * (q_heads + 2 * kv_heads) * head_dim \
        + q_heads * head_dim * d_model
    mlp = 3 * d_model * d_ff
    attn = 2 * q_heads * head_dim * context
    per_token = layers * (2 * (proj + mlp) + 2 * attn) + 2 * d_model * vocab
    return positions * per_token
