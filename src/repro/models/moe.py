"""Mixture-of-Experts transformer (llama4-style: top-1 routed + shared expert).

Deterministic-shape capacity-based dispatch (required under jit/pjit):
tokens pick their top-1 expert; each expert has capacity
ceil(tokens/E * capacity_factor); overflow tokens fall back to the residual
(and the shared expert). Dispatch/combine use scatter-add / gather with a
sacrificial overflow slot — no (tokens, E, capacity) one-hot tensor is ever
materialized, so dispatch costs O(tokens * d_model), not
O(tokens * E * capacity).

Expert weights are stacked (E, D, F) and shard over the `model` mesh axis
on E (expert parallelism); the scatter/gather becomes an all-to-all under
GSPMD. `moe_layer_period = k` makes every k-th layer MoE (maverick: 2,
interleaved; scout: 1, every layer); the scan unit is a superblock of
(k-1) dense layers + 1 MoE layer. Attention params are stacked for ALL
layers; dense-FFN params exist only for the dense sub-layers.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.models import attention as attn
from repro.models import dense
from repro.models.common import (ModelConfig, Params, apply_rope, constrain,
                                 cross_entropy_loss, dense_init,
                                 residual_pattern, rmsnorm, rope_tables,
                                 swiglu)

_FFN_KEYS = ("w_gate", "w_up", "w_down")


def _capacity(num_tokens: int, cfg: ModelConfig) -> int:
    return max(1, math.ceil(num_tokens / cfg.num_experts * cfg.capacity_factor))


def init_moe_ffn(cfg: ModelConfig, key) -> Params:
    e, d, f = cfg.num_experts, cfg.d_model, cfg.d_ff
    ks = jax.random.split(key, 7)
    dt = cfg.pdtype
    p = {
        "router": dense_init(ks[0], (d, e), dt, scale=d ** -0.5),
        "w_gate": dense_init(ks[1], (e, d, f), dt),
        "w_up": dense_init(ks[2], (e, d, f), dt),
        "w_down": dense_init(ks[3], (e, f, d), dt, scale=f ** -0.5),
    }
    if cfg.shared_expert:
        p["sh_gate"] = dense_init(ks[4], (d, f), dt)
        p["sh_up"] = dense_init(ks[5], (d, f), dt)
        p["sh_down"] = dense_init(ks[6], (f, d), dt, scale=f ** -0.5)
    return p


def _dp_shards() -> int:
    """Number of batch-axis shards in the ambient mesh (1 outside set_mesh)."""
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty:
        return 1
    n = 1
    for a in mesh.axis_names:
        if a != "model":
            n *= mesh.shape[a]
    return n


def moe_ffn(p: Params, x: jax.Array, cfg: ModelConfig) -> jax.Array:
    """x (B, S, D) -> (B, S, D). Top-1 routing with capacity dropping.

    SHARD-ALIGNED hierarchical dispatch (§Perf B2): on a mesh with `ns`
    batch shards, capacity is enforced PER SHARD (standard large-scale
    practice) and tokens from batch shard i receive slots in the i-th
    capacity block, so the capacity dim of the expert buffer shards
    exactly along the batch axes: the scatter/gather stays local and only
    the expert dim crosses shards (the EP exchange). Without the
    alignment, GSPMD replicated the full global expert buffer per layer
    (~2 TB/step of all-gather+all-reduce on llama4 prefill_32k).
    """
    b, s, d = x.shape
    nt = b * s
    e = cfg.num_experts
    xt = x.reshape(nt, d)

    logits = jnp.einsum("td,de->te", xt.astype(jnp.float32),
                        p["router"].astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    eidx = jnp.argmax(probs, axis=-1)                    # (nt,) top-1 expert
    gate = jnp.max(probs, axis=-1)                       # (nt,) router weight

    onehot = jax.nn.one_hot(eidx, e, dtype=jnp.int32)    # (nt, E)
    ns = _dp_shards()
    if ns > 1 and nt % ns == 0:
        ntl = nt // ns
        cap_l = _capacity(ntl, cfg)
        cap = ns * cap_l
        oh = onehot.reshape(ns, ntl, e)
        pos_b = jnp.cumsum(oh, axis=1) - oh              # per-shard position
        pos_in_e = jnp.sum(pos_b * oh, axis=-1)          # (ns, ntl)
        keep = (pos_in_e < cap_l).reshape(nt)
        blk = jnp.arange(ns, dtype=jnp.int32)[:, None]
        slot = (blk * cap_l + jnp.minimum(pos_in_e, cap_l)).reshape(nt)
        slot = jnp.where(keep, slot, cap)
    else:
        cap = _capacity(nt, cfg)
        pos = jnp.cumsum(onehot, axis=0) - onehot
        pos_in_e = jnp.sum(pos * onehot, axis=-1)        # (nt,)
        keep = pos_in_e < cap
        slot = jnp.where(keep, pos_in_e, cap)

    # scatter into (E, cap+1, D); slot `cap` swallows overflow
    buf = jnp.zeros((e, cap + 1, d), x.dtype)
    buf = buf.at[eidx, slot].add(xt)
    # (E, cap, D): experts over `model` (EP). Pinning capacity to the
    # batch axes as well ("mp","dp",None) cuts the expert-FFN FLOPs 4.4x
    # (each EP shard otherwise runs the full global capacity), but GSPMD
    # cannot see that the aligned scatter is shard-local and replicates
    # the token buffer instead (+6.8x collective bytes — measured, §Perf
    # B2/B3). Until the dispatch is expressed as an explicit shard_map
    # all-to-all, the mp-only pin is the better operating point.
    buf = constrain(buf[:, :cap], "mp", None, None)
    g = jnp.einsum("ecd,edf->ecf", buf, p["w_gate"].astype(x.dtype))
    u = jnp.einsum("ecd,edf->ecf", buf, p["w_up"].astype(x.dtype))
    y = jnp.einsum("ecf,efd->ecd", jax.nn.silu(g) * u,
                   p["w_down"].astype(x.dtype))          # (E, cap, D)
    y = constrain(y, "mp", None, None)

    out = y[eidx, jnp.minimum(slot, cap - 1)]            # (nt, D)
    out = out * (gate * keep).astype(x.dtype)[:, None]
    if cfg.shared_expert:
        out = out + swiglu(xt, p["sh_gate"], p["sh_up"], p["sh_down"])
    return out.reshape(b, s, d)


def aux_load_balance_loss(p: Params, x: jax.Array, cfg: ModelConfig) -> jax.Array:
    """Switch-style load-balancing auxiliary loss (fraction * prob per expert)."""
    b, s, d = x.shape
    xt = x.reshape(b * s, d).astype(jnp.float32)
    probs = jax.nn.softmax(xt @ p["router"].astype(jnp.float32), axis=-1)
    eidx = jnp.argmax(probs, axis=-1)
    frac = jnp.mean(jax.nn.one_hot(eidx, cfg.num_experts, dtype=jnp.float32),
                    axis=0)
    mean_prob = jnp.mean(probs, axis=0)
    return cfg.num_experts * jnp.sum(frac * mean_prob)


# ---------------------------------------------------------------------------
# Full model: superblock = (period-1) dense layers + 1 MoE layer
# ---------------------------------------------------------------------------

def _num_superblocks(cfg: ModelConfig) -> int:
    assert cfg.num_layers % cfg.moe_layer_period == 0
    return cfg.num_layers // cfg.moe_layer_period


def init_params(cfg: ModelConfig, key: jax.Array) -> Params:
    sb = _num_superblocks(cfg)
    period = cfg.moe_layer_period
    ks = jax.random.split(key, 3)

    full = dense.init_params(cfg, ks[0])
    blocks = full["blocks"]
    attn_blocks = {k: v for k, v in blocks.items() if k not in _FFN_KEYS}
    if period > 1:
        dense_ffn = {
            k: blocks[k].reshape(sb, period, *blocks[k].shape[1:])[:, :period - 1]
            for k in _FFN_KEYS}
    else:
        dense_ffn = {}

    moe_sub = [init_moe_ffn(cfg, jax.random.fold_in(ks[1], i))
               for i in range(sb)]
    moe_p = jax.tree.map(lambda *a: jnp.stack(a), *moe_sub)
    out = {"embed": full["embed"], "blocks": attn_blocks,
           "dense_ffn": dense_ffn, "moe": moe_p,
           "final_norm": full["final_norm"]}
    if "lm_head" in full:
        out["lm_head"] = full["lm_head"]
    return out


def _group_params(params, cfg: ModelConfig):
    sb = _num_superblocks(cfg)
    period = cfg.moe_layer_period
    blocks = jax.tree.map(
        lambda a: a.reshape(sb, period, *a.shape[1:]), params["blocks"])
    return blocks, params["dense_ffn"], params["moe"], sb, period


def _moe_attn_ffn(bp, mp, x, cos, sin, cfg: ModelConfig):
    """Attention + MoE FFN. bp has attention params only."""
    hn = rmsnorm(x, bp["ln1"], cfg.norm_eps)
    q, k, v = dense._qkv(bp, hn, cfg)
    q = apply_rope(q, cos, sin)
    k = apply_rope(k, cos, sin)
    o = attn.chunked_causal_attention(q, k, v, cfg.attn_chunk)
    o = jnp.einsum("bse,ed->bsd", o.reshape(*o.shape[:2], -1),
                   bp["wo"].astype(x.dtype))
    x = constrain(x + o, *residual_pattern(cfg))
    hn = rmsnorm(x, bp["ln2"], cfg.norm_eps)
    x = constrain(x + moe_ffn(mp, hn, cfg), *residual_pattern(cfg))
    return x, (k, v)


def _run(params, x, cfg: ModelConfig, collect_kv: bool):
    s = x.shape[1]
    cos, sin = rope_tables(jnp.arange(s, dtype=jnp.int32), cfg.hd,
                           cfg.rope_theta)
    blocks, dense_ffn, moe_p, sb, period = _group_params(params, cfg)

    def superblock(h, xs):
        bp, fp, mp = xs
        kvs = []
        for j in range(period - 1):
            sub = jax.tree.map(lambda a: a[j], bp)
            sub.update(jax.tree.map(lambda a: a[j], fp))
            h, kv = dense.block_fwd(sub, h, cos, sin, cfg)
            kvs.append(kv)
        sub = jax.tree.map(lambda a: a[period - 1], bp)
        h, kv = _moe_attn_ffn(sub, mp, h, cos, sin, cfg)
        kvs.append(kv)
        if not collect_kv:
            return h, None
        return h, (jnp.stack([k for k, _ in kvs]),
                   jnp.stack([v for _, v in kvs]))

    fn = jax.checkpoint(superblock) if cfg.remat else superblock
    return jax.lax.scan(fn, x, (blocks, dense_ffn, moe_p))


def forward(params: Params, tokens: jax.Array, cfg: ModelConfig,
            prefix_embeds=None) -> jax.Array:
    x = dense.embed_tokens(params, tokens, cfg, prefix_embeds)
    x, _ = _run(params, x, cfg, collect_kv=False)
    return dense._logits(params, x, cfg)


def loss_fn(params: Params, batch: dict, cfg: ModelConfig) -> jax.Array:
    logits = forward(params, batch["tokens"], cfg, batch.get("prefix_embeds"))
    return cross_entropy_loss(logits, batch["labels"])


init_cache = dense.init_cache


def prefill(params: Params, tokens: jax.Array, cfg: ModelConfig,
            max_len: int | None = None, lengths=None, prefix_embeds=None):
    x = dense.embed_tokens(params, tokens, cfg, prefix_embeds)
    b, s = x.shape[0], x.shape[1]
    x, (ks, vs) = _run(params, x, cfg, collect_kv=True)
    ks = ks.reshape(cfg.num_layers, *ks.shape[2:])
    vs = vs.reshape(cfg.num_layers, *vs.shape[2:])
    logits = dense._logits(params, x, cfg)
    t = max_len or s
    if t > s:
        ks = jnp.pad(ks, ((0, 0), (0, 0), (0, t - s), (0, 0), (0, 0)))
        vs = jnp.pad(vs, ((0, 0), (0, 0), (0, t - s), (0, 0), (0, 0)))
    if lengths is None:
        lengths = jnp.full((b,), s, jnp.int32)
    return logits, dense.KVCache(k=ks, v=vs, length=lengths)


def _moe_attn_ffn_decode(bp, mp, x, kc, vc, length, cos, sin, cfg):
    hn = rmsnorm(x, bp["ln1"], cfg.norm_eps)
    q, k, v = dense._qkv(bp, hn, cfg)
    q = apply_rope(q, cos, sin)
    k = apply_rope(k, cos, sin)
    idx = (length - 1).astype(jnp.int32)
    rows = jnp.arange(x.shape[0])
    kc = kc.at[rows, idx].set(k[:, 0])       # scatter: touches B rows only
    vc = vc.at[rows, idx].set(v[:, 0])
    o = attn.decode_attention(q, kc, vc, length)
    o = jnp.einsum("bse,ed->bsd", o.reshape(x.shape[0], 1, -1),
                   bp["wo"].astype(x.dtype))
    x = x + o
    hn = rmsnorm(x, bp["ln2"], cfg.norm_eps)
    x = x + moe_ffn(mp, hn, cfg)
    return x, kc, vc


def decode_step(params: Params, cache: dense.KVCache, tokens: jax.Array,
                cfg: ModelConfig):
    x = dense.embed_tokens(params, tokens, cfg)
    length = cache.length + 1
    pos = (length - 1).astype(jnp.int32)[:, None]
    cos, sin = rope_tables(pos, cfg.hd, cfg.rope_theta)
    blocks, dense_ffn, moe_p, sb, period = _group_params(params, cfg)
    def reshape(a):
        return a.reshape(sb, period, *a.shape[1:])
    kcs, vcs = reshape(cache.k), reshape(cache.v)

    def superblock(h, xs):
        bp, fp, mp, kc, vc = xs
        nks, nvs = [], []
        for j in range(period - 1):
            sub = jax.tree.map(lambda a: a[j], bp)
            sub.update(jax.tree.map(lambda a: a[j], fp))
            h, nk, nv = dense.block_decode(sub, h, kc[j], vc[j], length,
                                           cos, sin, cfg)
            nks.append(nk)
            nvs.append(nv)
        sub = jax.tree.map(lambda a: a[period - 1], bp)
        h, nk, nv = _moe_attn_ffn_decode(sub, mp, h, kc[period - 1],
                                         vc[period - 1], length, cos, sin, cfg)
        nks.append(nk)
        nvs.append(nv)
        return h, (jnp.stack(nks), jnp.stack(nvs))

    x, (ks, vs) = jax.lax.scan(superblock, x,
                               (blocks, dense_ffn, moe_p, kcs, vcs))
    ks = ks.reshape(cfg.num_layers, *ks.shape[2:])
    vs = vs.reshape(cfg.num_layers, *vs.shape[2:])
    return dense._logits(params, x, cfg), dense.KVCache(k=ks, v=vs,
                                                        length=length)
