"""Sparse-KV decode attention: a thin wrapper over the engine's KV cascade.

The paper's two-stage idea applied to a DIFFERENT database: the KV cache.
During decode, attending a 32k-500k entry cache is memory-bound — each
step streams the full bf16 K and V. The schedule now lives in
repro.core.engine as a first-class cascade over a `KVCachePolicy`
(KVPagePrune -> KVSignPrescreen -> KVApproxTopK -> KVExactAttend); this
module is the cache-facing adapter:

  * `QuantKVCache` — the nibble-planar INT8 K + bf16 V storage (one
    layer slice), with optional Quest-style page-centroid sidecars;
  * `sparse_decode_attention` — the public entry point, now dispatching
    into `engine.kv_decode_batched`. With the default (no-prune) config
    it is BIT-IDENTICAL to `sparse_decode_attention_ref`, the original
    hand-rolled implementation kept verbatim below as the parity oracle
    (tests gate exact equality across lengths {0, <top_k, >=top_k} on
    both backends);
  * the decode byte model (`dense_bytes_per_step` /
    `sparse_bytes_per_step`) — reconciled exactly with the engine's
    `kv_plan` StagePlan ledger, so energy.cost_cascade prices decode
    bytes the same way it prices retrieval bytes.

Traffic per step per (layer, kv-head): T*hd/2 bytes (nibble K-plane)
+ T*4 (scales) + k*(hd + 4) (exact K planes + scales) + 2*k*hd (bf16 V),
versus 2*T*hd*2 for dense — >4x less for k << T, and the page prune cuts
the T-proportional term to npages*page_rows as well. Attention with a
top-k token budget is the H2O/Quest family of approximations; the
paper's contribution here is the QUANTIZED staged filter + nibble-planar
layout, reused verbatim from repro.core.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.core import bitplanar, engine

NEG_INF = -1e30


@dataclasses.dataclass
class QuantKVCache:
    """INT8 K cache stored nibble-planar + bf16 V (per layer slice).

    k_msb / k_lsb: (B, T, KH, hd//2) uint8 nibble planes of INT8 keys.
    k_scale: (B, T, KH) f32 per-(position, head) quant scales.
    v: (B, T, KH, hd) compute-dtype values.
    cent_msb / cent_scale: optional (B, P, KH, hd//2) / (B, P, KH) page
        centroids (P = T // page_rows) enabling the engine's Quest-style
        page prune — see `build_page_centroids` / `update_page_centroids`.
    """
    k_msb: jax.Array
    k_lsb: jax.Array
    k_scale: jax.Array
    v: jax.Array
    cent_msb: jax.Array | None = None
    cent_scale: jax.Array | None = None


jax.tree_util.register_dataclass(
    QuantKVCache, data_fields=["k_msb", "k_lsb", "k_scale", "v",
                               "cent_msb", "cent_scale"],
    meta_fields=[])


def quantize_keys(k: jax.Array) -> tuple[jax.Array, jax.Array, jax.Array]:
    """k (B, T, KH, hd) -> (msb_plane, lsb_plane, scale) per (B,T,KH)."""
    b, t, kh, hd = k.shape
    amax = jnp.max(jnp.abs(k.astype(jnp.float32)), axis=-1)
    scale = jnp.maximum(amax, 1e-12) / 127.0
    codes = jnp.clip(jnp.round(k.astype(jnp.float32) / scale[..., None]),
                     -127, 127).astype(jnp.int8)
    msb, lsb = bitplanar.pack_nibble_planes(codes.reshape(-1, hd))
    return (msb.reshape(b, t, kh, hd // 2), lsb.reshape(b, t, kh, hd // 2),
            scale)


def build_quant_cache(k: jax.Array, v: jax.Array) -> QuantKVCache:
    msb, lsb, scale = quantize_keys(k)
    return QuantKVCache(k_msb=msb, k_lsb=lsb, k_scale=scale, v=v)


def _quantize_centroids(mean: jax.Array) -> tuple[jax.Array, jax.Array]:
    """(..., hd) f32 page means -> (packed msb nibbles (..., hd//2),
    scale (...,)) — the same symmetric INT8 scheme as the keys, so the
    centroid plane is just another corpus the stage-1 kernels score."""
    hd = mean.shape[-1]
    amax = jnp.max(jnp.abs(mean), axis=-1)
    scale = jnp.maximum(amax, 1e-12) / 127.0
    codes = jnp.clip(jnp.round(mean / scale[..., None]),
                     -127, 127).astype(jnp.int8)
    msb, _ = bitplanar.pack_nibble_planes(codes.reshape(-1, hd))
    return msb.reshape(*mean.shape[:-1], hd // 2), scale


def build_page_centroids(cache: QuantKVCache, length: jax.Array,
                         page_rows: int = 8) -> QuantKVCache:
    """Derive per-page mean-key centroids for the engine's page prune.

    Pages are `page_rows` consecutive positions; each centroid is the
    mean of the page's VALID (pos < length) dequantized keys, re-quantized
    to INT8 and stored MSB-nibble-packed (+ f32 scale) per (B, page, KH).
    Returns a new cache with cent_msb/cent_scale set. T must be a
    multiple of page_rows (decode caches pad their max_len up)."""
    b, t, kh, hd2 = cache.k_msb.shape
    hd = hd2 * 2
    if t % page_rows:
        raise ValueError(f"cache length {t} not a multiple of "
                         f"page_rows={page_rows}")
    p = t // page_rows
    k_int = bitplanar.reconstruct_int8(cache.k_msb.reshape(-1, hd2),
                                       cache.k_lsb.reshape(-1, hd2))
    k_f = (k_int.reshape(b, t, kh, hd).astype(jnp.float32)
           * cache.k_scale[..., None])
    pagev = k_f.reshape(b, p, page_rows, kh, hd)
    pos = (jnp.arange(p)[:, None] * page_rows
           + jnp.arange(page_rows)[None, :])                 # (P, pr)
    live = pos[None] < jnp.reshape(length, (-1, 1, 1)).astype(jnp.int32)
    cnt = jnp.sum(live, axis=2).astype(jnp.float32)          # (B, P)
    mean = (jnp.sum(jnp.where(live[..., None, None], pagev, 0.0), axis=2)
            / jnp.maximum(cnt, 1.0)[..., None, None])        # (B, P, KH, hd)
    cent_msb, cent_scale = _quantize_centroids(mean)
    return dataclasses.replace(cache, cent_msb=cent_msb,
                               cent_scale=cent_scale)


def update_page_centroids(k_msb: jax.Array, k_lsb: jax.Array,
                          k_scale: jax.Array, cent_msb: jax.Array,
                          cent_scale: jax.Array, length: jax.Array,
                          page_rows: int) -> tuple[jax.Array, jax.Array]:
    """Incrementally refresh ONE page's centroid after an append.

    The decode step writes position length-1; only that page's mean can
    change, so the online index maintenance (EdgeRAG's discipline applied
    to the KV cache) re-reads just `page_rows` quantized rows per step
    and re-quantizes one centroid — O(page_rows * hd) work, no rebuild.
    Returns the updated (cent_msb, cent_scale)."""
    b, t, kh, hd2 = k_msb.shape
    hd = hd2 * 2
    idx = (length - 1).astype(jnp.int32)                     # (B,)
    pidx = idx // page_rows
    start = pidx * page_rows
    offs = jnp.arange(page_rows, dtype=jnp.int32)
    rows = start[:, None] + offs[None, :]                    # (B, pr)
    pm = jnp.take_along_axis(k_msb, rows[:, :, None, None], axis=1)
    pl = jnp.take_along_axis(k_lsb, rows[:, :, None, None], axis=1)
    ps = jnp.take_along_axis(k_scale, rows[:, :, None], axis=1)
    k_f = (bitplanar.reconstruct_int8(pm.reshape(-1, hd2),
                                      pl.reshape(-1, hd2))
           .reshape(b, page_rows, kh, hd).astype(jnp.float32)
           * ps[..., None])
    ncnt = jnp.clip(length.astype(jnp.int32) - start, 1, page_rows)
    live = offs[None, :] < ncnt[:, None]                     # (B, pr)
    mean = (jnp.sum(jnp.where(live[:, :, None, None], k_f, 0.0), axis=1)
            / ncnt.astype(jnp.float32)[:, None, None])       # (B, KH, hd)
    nm, ns = _quantize_centroids(mean)
    rows_b = jnp.arange(b)
    return (cent_msb.at[rows_b, pidx].set(nm),
            cent_scale.at[rows_b, pidx].set(ns))


def kv_policy(cache: QuantKVCache, length: jax.Array
              ) -> engine.KVCachePolicy:
    """Present this cache slice as an engine corpus."""
    return engine.KVCachePolicy(
        k_msb=cache.k_msb, k_lsb=cache.k_lsb, k_scale=cache.k_scale,
        v=cache.v, length=jnp.asarray(length, jnp.int32),
        cent_msb=cache.cent_msb, cent_scale=cache.cent_scale)


def sparse_decode_attention(q: jax.Array, cache: QuantKVCache,
                            length: jax.Array, top_k: int,
                            scale: float | None = None, *,
                            npages: int | None = None,
                            prescreen_c0: int | None = None,
                            page_rows: int = 8,
                            backend: str | None = None) -> jax.Array:
    """q (B, 1, H, hd) against the quantized cache; returns (B, 1, H, hd).

    Dispatches into the engine's KV cascade. The default (no npages /
    prescreen) schedule is the original two-stage filter — approximate
    MSB-nibble scores, exact masked softmax over the per-(B, KH) top-k —
    and is bit-identical to `sparse_decode_attention_ref`. `npages`
    prepends the Quest-style page prune (needs cent_msb on the cache);
    `prescreen_c0` adds the 1-bit sign prescreen between prune and scan;
    `backend` selects jnp vs Pallas kernels for the integer stages (None:
    the kernels on a TPU, jnp elsewhere).
    """
    cfg = engine.KVCascadeConfig(
        top_k=top_k, npages=npages, page_rows=page_rows,
        prescreen_c0=prescreen_c0, backend=backend, scale=scale)
    return engine.kv_decode_batched(q, kv_policy(cache, length), cfg)


def sparse_decode_attention_ref(q: jax.Array, cache: QuantKVCache,
                                length: jax.Array, top_k: int,
                                scale: float | None = None) -> jax.Array:
    """The ORIGINAL hand-rolled two-stage implementation, kept verbatim
    as the bit-parity oracle for the engine-backed path (the parity suite
    gates exact equality, including the length<top_k / empty-cache
    masked-softmax edge cases)."""
    b, _, h, hd = q.shape
    t, kh = cache.v.shape[1], cache.v.shape[2]
    g = h // kh
    scale = scale or hd ** -0.5
    k_eff = min(top_k, t)

    # ---- Stage 1: approximate scores from the MSB nibble plane only.
    k_msb = bitplanar.unpack_nibble_plane_signed(
        cache.k_msb.reshape(-1, hd // 2)).reshape(b, t, kh, hd)
    qg = q.reshape(b, kh, g, hd)
    s1 = jnp.einsum("bkgd,btkd->bkgt", qg.astype(jnp.float32),
                    k_msb.astype(jnp.float32))
    s1 = s1 * cache.k_scale.transpose(0, 2, 1)[:, :, None, :]  # (B,KH,G,T)
    s1 = jnp.max(s1, axis=2)                                   # (B,KH,T) group-max
    valid = jnp.arange(t)[None, None, :] < jnp.reshape(
        length, (-1, 1, 1)).astype(jnp.int32)
    s1 = jnp.where(valid, s1, NEG_INF)
    _, sel = jax.lax.top_k(s1, k_eff)                          # (B, KH, k)

    # ---- Stage 2: exact attention on the selected positions only.
    # Gather the PLANES first, reconstruct only the k << T survivors
    # (reconstructing the full cache would re-read every LSB byte and
    # forfeit the bit-planar saving).
    bidx = jnp.arange(b)[:, None, None]
    hidx = jnp.arange(kh)[None, :, None]
    msb_sel = cache.k_msb.transpose(0, 2, 1, 3)[bidx, hidx, sel]
    lsb_sel = cache.k_lsb.transpose(0, 2, 1, 3)[bidx, hidx, sel]
    scale_sel = jnp.take_along_axis(
        cache.k_scale.transpose(0, 2, 1), sel, axis=-1)        # (B,KH,k)
    k_int = bitplanar.reconstruct_int8(
        msb_sel.reshape(-1, hd // 2),
        lsb_sel.reshape(-1, hd // 2)).reshape(b, kh, k_eff, hd)
    k_sel = k_int.astype(jnp.float32) * scale_sel[..., None]   # (B,KH,k,hd)
    v_sel = cache.v.transpose(0, 2, 1, 3)[bidx, hidx, sel].astype(jnp.float32)
    s2 = jnp.einsum("bkgd,bktd->bkgt", qg.astype(jnp.float32), k_sel) * scale
    sel_valid = sel < jnp.reshape(length, (-1, 1, 1)).astype(jnp.int32)
    mask = sel_valid[:, :, None, :]
    s2 = jnp.where(mask, s2, NEG_INF)
    # Masked softmax with a zero-output fallback: when length < top_k the
    # top_k over NEG_INF-masked stage-1 scores selects invalid positions,
    # and at length == 0 EVERY selected position is invalid — a plain
    # softmax over the all-NEG_INF row then emits NaNs (exp(0)/sum == 1/k
    # of garbage rows at best, 0/0 after masking at worst). For non-empty
    # rows this is bit-identical to jax.nn.softmax: masked entries
    # contribute exp(NEG_INF - max) == 0 either way.
    e = jnp.where(mask, jnp.exp(s2 - jnp.max(s2, axis=-1, keepdims=True)),
                  0.0)
    denom = jnp.sum(e, axis=-1, keepdims=True)
    p = e / jnp.where(denom > 0, denom, 1.0)
    out = jnp.einsum("bkgt,bktd->bkgd", p, v_sel)
    return out.reshape(b, 1, h, hd).astype(q.dtype)


def dense_bytes_per_step(t: int, hd: int, kv_bytes: int = 2) -> int:
    """HBM bytes per (layer, kv-head) for dense decode: full K + V."""
    return 2 * t * hd * kv_bytes


def sparse_bytes_per_step(t: int, hd: int, top_k: int,
                          kv_bytes: int = 2) -> int:
    """Nibble K-plane scan + scales + exact gather of the top-k rows.

    Exact accounting per (layer, kv-head) per step: the full MSB plane
    (t*hd/2) + f32 scales (4t), then BOTH nibble planes + scale for each
    of the k survivors (k*(hd+4) — K is reconstructed from INT8, never
    re-read at bf16) + their V rows at compute precision (k*hd*kv_bytes).
    Reconciles exactly with engine.kv_plan's no-prune approx+exact
    stages divided by (layers * batch * kv_heads)."""
    return t * hd // 2 + t * 4 + top_k * (hd + 4) + top_k * hd * kv_bytes


def decode_plan(cfg_or_topk, *, batch: int, kv_heads: int, q_heads: int,
                seq_len: int, head_dim: int,
                layers: int = 1) -> engine.SchedulePlan:
    """Convenience: the engine's kv_plan from either a KVCascadeConfig or
    a bare top_k (the no-prune schedule)."""
    cfg = (cfg_or_topk if isinstance(cfg_or_topk, engine.KVCascadeConfig)
           else engine.KVCascadeConfig(top_k=int(cfg_or_topk)))
    return engine.kv_plan(cfg, batch=batch, kv_heads=kv_heads,
                          q_heads=q_heads, seq_len=seq_len,
                          head_dim=head_dim, layers=layers)
