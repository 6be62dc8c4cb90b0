"""Dense decoder-only transformer (GQA + RoPE + SwiGLU, pre-RMSNorm).

Covers qwen2 (QKV bias), minitron, deepseek-coder-33b / deepseek-67b, and
the LM backbone of internvl2 (optional prefix embeddings from the stubbed
vision frontend). Layer parameters are stacked on a leading axis and the
forward pass scans over them (optionally rematerialized).
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.models import attention as attn
from repro.models.common import (ModelConfig, Params, apply_rope, constrain,
                                 constrain_kv, cross_entropy_loss,
                                 dense_init, embed_init, residual_pattern,
                                 rmsnorm, rope_tables, swiglu)


@dataclasses.dataclass
class KVCache:
    k: jax.Array        # (L, B, T, KH, hd)
    v: jax.Array        # (L, B, T, KH, hd)
    length: jax.Array   # (B,) int32 — valid positions per sequence


jax.tree_util.register_dataclass(
    KVCache, data_fields=["k", "v", "length"], meta_fields=[])


def init_params(cfg: ModelConfig, key: jax.Array) -> Params:
    l, d, h, kh, hd, f, v = (cfg.num_layers, cfg.d_model, cfg.num_heads,
                             cfg.num_kv_heads, cfg.hd, cfg.d_ff,
                             cfg.vocab_size)
    ks = jax.random.split(key, 12)
    dt = cfg.pdtype
    blocks = {
        "ln1": jnp.ones((l, d), dt),
        "wq": dense_init(ks[0], (l, d, h * hd), dt),
        "wk": dense_init(ks[1], (l, d, kh * hd), dt),
        "wv": dense_init(ks[2], (l, d, kh * hd), dt),
        "wo": dense_init(ks[3], (l, h * hd, d), dt, scale=(h * hd) ** -0.5),
        "ln2": jnp.ones((l, d), dt),
        "w_gate": dense_init(ks[4], (l, d, f), dt),
        "w_up": dense_init(ks[5], (l, d, f), dt),
        "w_down": dense_init(ks[6], (l, f, d), dt, scale=f ** -0.5),
    }
    if cfg.qkv_bias:
        blocks["bq"] = jnp.zeros((l, h * hd), dt)
        blocks["bk"] = jnp.zeros((l, kh * hd), dt)
        blocks["bv"] = jnp.zeros((l, kh * hd), dt)
    params = {
        "embed": embed_init(ks[7], (v, d), dt),
        "blocks": blocks,
        "final_norm": jnp.ones((d,), dt),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = dense_init(ks[8], (d, v), dt)
    return params


def _qkv(p, x, cfg: ModelConfig):
    b, s, _ = x.shape
    h, kh, hd = cfg.num_heads, cfg.num_kv_heads, cfg.hd
    q = jnp.einsum("bsd,de->bse", x, p["wq"].astype(x.dtype))
    k = jnp.einsum("bsd,de->bse", x, p["wk"].astype(x.dtype))
    v = jnp.einsum("bsd,de->bse", x, p["wv"].astype(x.dtype))
    if cfg.qkv_bias:
        q = q + p["bq"].astype(x.dtype)
        k = k + p["bk"].astype(x.dtype)
        v = v + p["bv"].astype(x.dtype)
    q = constrain(q.reshape(b, s, h, hd), "dp", None, "mp", None)
    k = constrain(k.reshape(b, s, kh, hd), "dp", None, "mp", None)
    v = constrain(v.reshape(b, s, kh, hd), "dp", None, "mp", None)
    return q, k, v


def block_fwd(p, x, cos, sin, cfg: ModelConfig):
    """Full-sequence (train / prefill) block. Returns (x, (k, v))."""
    hn = rmsnorm(x, p["ln1"], cfg.norm_eps)
    q, k, v = _qkv(p, hn, cfg)
    q = apply_rope(q, cos, sin)
    k = apply_rope(k, cos, sin)
    o = attn.chunked_causal_attention(q, k, v, cfg.attn_chunk)
    o = jnp.einsum("bse,ed->bsd", o.reshape(*o.shape[:2], -1),
                   p["wo"].astype(x.dtype))
    x = constrain(x + o, *residual_pattern(cfg))
    hn = rmsnorm(x, p["ln2"], cfg.norm_eps)
    x = constrain(x + swiglu(hn, p["w_gate"], p["w_up"], p["w_down"]),
                  *residual_pattern(cfg))
    return x, (k, v)


def block_decode(p, x, kc, vc, length, cos, sin, cfg: ModelConfig):
    """Single-token block against a per-layer KV cache slice.

    x (B,1,D); kc/vc (B,T,KH,hd); length (B,) = count INCLUDING this token.
    Returns (x, new_kc, new_vc).
    """
    hn = rmsnorm(x, p["ln1"], cfg.norm_eps)
    q, k, v = _qkv(p, hn, cfg)
    q = apply_rope(q, cos, sin)
    k = apply_rope(k, cos, sin)
    # write the new token at position length-1 per batch row. SCATTER, not
    # a one-hot masked rewrite: the one-hot form reads+writes the entire
    # (B, T, KH, hd) cache every step (2 extra cache passes of HBM
    # traffic); the scatter touches only B rows (§Perf C3).
    b = x.shape[0]
    idx = (length - 1).astype(jnp.int32)                      # (B,)
    rows = jnp.arange(b)
    kc = constrain_kv(kc.at[rows, idx].set(k[:, 0]))
    vc = constrain_kv(vc.at[rows, idx].set(v[:, 0]))
    o = attn.decode_attention(q, kc, vc, length)
    o = jnp.einsum("bse,ed->bsd", o.reshape(b, 1, -1),
                   p["wo"].astype(x.dtype))
    x = x + o
    hn = rmsnorm(x, p["ln2"], cfg.norm_eps)
    x = x + swiglu(hn, p["w_gate"], p["w_up"], p["w_down"])
    return x, kc, vc


def _scan_blocks(blocks, x, step_fn, cfg: ModelConfig, extra_xs=None):
    """scan over stacked layer params (+ optional per-layer xs)."""
    fn = step_fn
    if cfg.remat:
        fn = jax.checkpoint(fn)
    if cfg.scan_layers:
        xs = (blocks,) if extra_xs is None else (blocks, *extra_xs)
        return jax.lax.scan(lambda c, xs_: fn(c, *xs_), x, xs)
    carry, ys = x, []
    for i in range(cfg.num_layers):
        sl = jax.tree.map(lambda a: a[i], blocks)
        ex = () if extra_xs is None else tuple(
            jax.tree.map(lambda a: a[i], e) for e in extra_xs)
        carry, y = fn(carry, sl, *ex)
        ys.append(y)
    return carry, jax.tree.map(lambda *a: jnp.stack(a), *ys)


def embed_tokens(params, tokens, cfg: ModelConfig,
                 prefix_embeds: jax.Array | None = None) -> jax.Array:
    x = jnp.take(params["embed"], tokens, axis=0).astype(cfg.cdtype)
    if prefix_embeds is not None:
        x = jnp.concatenate([prefix_embeds.astype(cfg.cdtype), x], axis=1)
    return constrain(x, "dp", None, None)


def _logits(params, x, cfg: ModelConfig):
    x = rmsnorm(x, params["final_norm"], cfg.norm_eps)
    head = (params["embed"].T if cfg.tie_embeddings else params["lm_head"])
    return constrain(jnp.einsum("bsd,dv->bsv", x, head.astype(x.dtype)),
                     "dp", None, "mp")


def forward(params: Params, tokens: jax.Array, cfg: ModelConfig,
            prefix_embeds: jax.Array | None = None) -> jax.Array:
    """Teacher-forcing forward -> logits (B, S(+P), V)."""
    x = embed_tokens(params, tokens, cfg, prefix_embeds)
    s = x.shape[1]
    cos, sin = rope_tables(jnp.arange(s, dtype=jnp.int32), cfg.hd,
                           cfg.rope_theta)

    def step(h, p):
        h2, _ = block_fwd(p, h, cos, sin, cfg)
        return h2, None

    x, _ = _scan_blocks(params["blocks"], x, step, cfg)
    return _logits(params, x, cfg)


def loss_fn(params: Params, batch: dict, cfg: ModelConfig) -> jax.Array:
    logits = forward(params, batch["tokens"], cfg,
                     batch.get("prefix_embeds"))
    labels = batch["labels"]
    if "prefix_embeds" in batch and batch["prefix_embeds"] is not None:
        p = batch["prefix_embeds"].shape[1]
        pad = jnp.full(labels.shape[:1] + (p,), -1, labels.dtype)
        labels = jnp.concatenate([pad, labels], axis=1)
    return cross_entropy_loss(logits, labels)


def init_cache(cfg: ModelConfig, batch: int, max_len: int) -> KVCache:
    shape = (cfg.num_layers, batch, max_len, cfg.num_kv_heads, cfg.hd)
    return KVCache(k=jnp.zeros(shape, cfg.cdtype),
                   v=jnp.zeros(shape, cfg.cdtype),
                   length=jnp.zeros((batch,), jnp.int32))


def prefill(params: Params, tokens: jax.Array, cfg: ModelConfig,
            max_len: int | None = None, lengths: jax.Array | None = None,
            prefix_embeds: jax.Array | None = None
            ) -> tuple[jax.Array, KVCache]:
    """Run the prompt, return (logits, primed KV cache)."""
    x = embed_tokens(params, tokens, cfg, prefix_embeds)
    b, s, _ = x.shape
    cos, sin = rope_tables(jnp.arange(s, dtype=jnp.int32), cfg.hd,
                           cfg.rope_theta)

    def step(h, p):
        h2, kv = block_fwd(p, h, cos, sin, cfg)
        return h2, kv

    x, (ks, vs) = _scan_blocks(params["blocks"], x, step, cfg)
    logits = _logits(params, x, cfg)
    t = max_len or s
    pad = t - s
    if pad:
        ks = jnp.pad(ks, ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0)))
        vs = jnp.pad(vs, ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0)))
    if lengths is None:
        lengths = jnp.full((b,), s, jnp.int32)
    return logits, KVCache(k=ks, v=vs, length=lengths)


def decode_step(params: Params, cache: KVCache, tokens: jax.Array,
                cfg: ModelConfig) -> tuple[jax.Array, KVCache]:
    """One decode step. tokens (B, 1) -> logits (B, 1, V), updated cache."""
    x = embed_tokens(params, tokens, cfg)
    length = cache.length + 1
    pos = (length - 1).astype(jnp.int32)[:, None]              # (B, 1)
    cos, sin = rope_tables(pos, cfg.hd, cfg.rope_theta)

    def step(h, p, kc, vc):
        h2, kc2, vc2 = block_decode(p, h, kc, vc, length, cos, sin, cfg)
        return h2, (kc2, vc2)

    x, (ks, vs) = _scan_blocks(params["blocks"], x, step, cfg,
                               extra_xs=(cache.k, cache.v))
    return _logits(params, x, cfg), KVCache(k=ks, v=vs, length=length)


# ---------------------------------------------------------------------------
# Quantized-KV decode (§Perf C3 / beyond-paper): the paper's two-stage
# hierarchical idea applied to the KV-cache "database". Keys live as INT8
# nibble planes; stage 1 scores every cached key from the MSB plane only,
# stage 2 runs exact attention on the top-k survivors (serve/sparse_kv).
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class QuantCache:
    k_msb: jax.Array    # (L, B, T, KH, hd//2) uint8
    k_lsb: jax.Array    # (L, B, T, KH, hd//2) uint8
    k_scale: jax.Array  # (L, B, T, KH) f32
    v: jax.Array        # (L, B, T, KH, hd)
    length: jax.Array   # (B,)
    # Optional Quest-style page-centroid sidecars (P = T // page_rows),
    # maintained incrementally by decode_step_quant — enable the engine's
    # KVPagePrune stage so the stage-1 scan reads npages*page_rows rows
    # instead of T.
    cent_msb: jax.Array | None = None    # (L, B, P, KH, hd//2) uint8
    cent_scale: jax.Array | None = None  # (L, B, P, KH) f32
    page_rows: int = 8


jax.tree_util.register_dataclass(
    QuantCache, data_fields=["k_msb", "k_lsb", "k_scale", "v", "length",
                             "cent_msb", "cent_scale"],
    meta_fields=["page_rows"])


def init_quant_cache(cfg: ModelConfig, batch: int, max_len: int,
                     page_rows: int | None = None) -> QuantCache:
    l, kh, hd = cfg.num_layers, cfg.num_kv_heads, cfg.hd
    cent_msb = cent_scale = None
    if page_rows is not None:
        if max_len % page_rows:
            raise ValueError(f"max_len={max_len} not a multiple of "
                             f"page_rows={page_rows}")
        p = max_len // page_rows
        cent_msb = jnp.zeros((l, batch, p, kh, hd // 2), jnp.uint8)
        cent_scale = jnp.zeros((l, batch, p, kh), jnp.float32)
    return QuantCache(
        k_msb=jnp.zeros((l, batch, max_len, kh, hd // 2), jnp.uint8),
        k_lsb=jnp.zeros((l, batch, max_len, kh, hd // 2), jnp.uint8),
        k_scale=jnp.zeros((l, batch, max_len, kh), jnp.float32),
        v=jnp.zeros((l, batch, max_len, kh, hd), cfg.cdtype),
        length=jnp.zeros((batch,), jnp.int32),
        cent_msb=cent_msb, cent_scale=cent_scale,
        page_rows=page_rows or 8)


def quantize_cache(cache: KVCache, page_rows: int | None = None
                   ) -> QuantCache:
    """Convert a prefill's bf16 KVCache into the nibble-planar QuantCache
    (keys re-quantized per (position, head); V shared by reference).
    With `page_rows` the page-centroid sidecars are built too, so the
    very first decode step can run the paged cascade over the prompt."""
    from repro.serve import sparse_kv

    ms, ls, ss = jax.vmap(sparse_kv.quantize_keys)(cache.k)
    cm = cs = None
    if page_rows is not None:
        def _cent(m, l, s, v):
            c = sparse_kv.build_page_centroids(
                sparse_kv.QuantKVCache(k_msb=m, k_lsb=l, k_scale=s, v=v),
                cache.length, page_rows)
            return c.cent_msb, c.cent_scale
        cm, cs = jax.vmap(_cent)(ms, ls, ss, cache.v)
    return QuantCache(k_msb=ms, k_lsb=ls, k_scale=ss, v=cache.v,
                      length=cache.length, cent_msb=cm, cent_scale=cs,
                      page_rows=page_rows or 8)


def decode_step_quant(params: Params, cache: QuantCache, tokens: jax.Array,
                      cfg: ModelConfig, top_k: int = 256,
                      npages: int | None = None,
                      prescreen_c0: int | None = None,
                      backend: str | None = None
                      ) -> tuple[jax.Array, QuantCache]:
    """Decode against the INT8 nibble-planar K cache via the engine's KV
    cascade. Per step per layer, HBM reads are the MSB plane (T*hd/2 B)
    + scales + top_k exact rows instead of the full 2*T*hd*2 B of bf16
    K+V; with `npages` (cache built by init_quant_cache(page_rows=...))
    the scan itself shrinks to npages*page_rows rows behind the
    Quest-style page prune, and `prescreen_c0` inserts the 1-bit
    sign-plane prescreen between prune and scan. Page centroids are
    maintained incrementally — only the appended-to page is re-averaged
    each step (EdgeRAG's online-index discipline applied to the cache)."""
    from repro.serve import sparse_kv

    has_pages = cache.cent_msb is not None
    if npages is not None and not has_pages:
        raise ValueError("npages requires a paged cache — build it with "
                         "init_quant_cache(page_rows=...)")
    page_rows = cache.page_rows
    x = embed_tokens(params, tokens, cfg)
    length = cache.length + 1
    pos = (length - 1).astype(jnp.int32)[:, None]
    cos, sin = rope_tables(pos, cfg.hd, cfg.rope_theta)
    b = tokens.shape[0]
    rows = jnp.arange(b)
    idx = (length - 1).astype(jnp.int32)

    def step(h, p, msb, lsb, scl, vc, *cent):
        hn = rmsnorm(h, p["ln1"], cfg.norm_eps)
        q, k, v = _qkv(p, hn, cfg)
        q = apply_rope(q, cos, sin)
        k = apply_rope(k, cos, sin)
        nm, nl, nsc = sparse_kv.quantize_keys(k)        # (B,1,KH,hd//2) x2
        msb = msb.at[rows, idx].set(nm[:, 0])
        lsb = lsb.at[rows, idx].set(nl[:, 0])
        scl = scl.at[rows, idx].set(nsc[:, 0])
        vc = vc.at[rows, idx].set(v[:, 0])
        if cent:
            cm, cs = sparse_kv.update_page_centroids(
                msb, lsb, scl, cent[0], cent[1], length, page_rows)
            cent = (cm, cs)
        layer = sparse_kv.QuantKVCache(
            k_msb=msb, k_lsb=lsb, k_scale=scl, v=vc,
            cent_msb=cent[0] if cent else None,
            cent_scale=cent[1] if cent else None)
        o = sparse_kv.sparse_decode_attention(
            q, layer, length, top_k, npages=npages,
            prescreen_c0=prescreen_c0, page_rows=page_rows,
            backend=backend)
        o = jnp.einsum("bse,ed->bsd", o.reshape(b, 1, -1),
                       p["wo"].astype(h.dtype))
        h = h + o
        hn = rmsnorm(h, p["ln2"], cfg.norm_eps)
        h = h + swiglu(hn, p["w_gate"], p["w_up"], p["w_down"])
        return h, (msb, lsb, scl, vc, *cent)

    extra = (cache.k_msb, cache.k_lsb, cache.k_scale, cache.v)
    if has_pages:
        extra = extra + (cache.cent_msb, cache.cent_scale)
    x, ys = _scan_blocks(params["blocks"], x, step, cfg, extra_xs=extra)
    ms, ls, scs, vs = ys[:4]
    cm, cs = (ys[4], ys[5]) if has_pages else (None, None)
    return _logits(params, x, cfg), QuantCache(
        k_msb=ms, k_lsb=ls, k_scale=scs, v=vs, length=length,
        cent_msb=cm, cent_scale=cs, page_rows=page_rows)
