"""Shared model-definition substrate: config, layers, losses, init.

Pure-JAX (no flax): parameters are nested dicts of jnp arrays; repeated
transformer blocks keep their parameters STACKED along a leading layer
axis so the forward pass can lax.scan over layers (small HLO, fast
compiles at 95 layers, remat-friendly).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Literal

import jax
import jax.numpy as jnp

Params = Any  # nested dict pytree of jnp arrays


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    family: Literal["dense", "moe", "ssm", "hybrid", "encdec", "vlm"]
    num_layers: int
    d_model: int
    num_heads: int
    num_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: int = 0              # 0 -> d_model // num_heads
    qkv_bias: bool = False         # qwen2-style QKV bias
    # --- MoE ---
    num_experts: int = 0
    moe_top_k: int = 1
    moe_layer_period: int = 1      # 1 = every layer MoE; 2 = interleaved
    shared_expert: bool = False
    capacity_factor: float = 1.25
    # --- SSM (Mamba2 / SSD) ---
    ssm_state: int = 0
    ssm_expand: int = 2
    ssm_head_dim: int = 64
    ssm_conv_width: int = 4
    ssm_chunk: int = 128
    # --- hybrid (Zamba2) ---
    hybrid_attn_period: int = 0    # shared attn block after every k SSM layers
    # --- enc-dec ---
    encoder_layers: int = 0
    # --- frontends (VLM / audio): stubbed embeddings prepended/encoded ---
    num_prefix_embeds: int = 0     # VLM: image patch embeddings per sample
    frontend_dim: int = 0          # embedding dim delivered by the stub
    # --- numerics / misc ---
    rope_theta: float = 1e6
    norm_eps: float = 1e-5
    param_dtype: str = "float32"
    compute_dtype: str = "bfloat16"
    attn_chunk: int = 2048         # flash-attention block size
    remat: bool = True
    scan_layers: bool = True
    seq_shard: bool = False        # Megatron-SP: residuals S-sharded on TP
    optimizer: Literal["adamw", "adafactor"] = "adamw"
    tie_embeddings: bool = False
    # embedder head (MiniLM-style sentence encoder)
    pooled_dim: int = 0            # >0: mean-pool + project to this dim

    @property
    def hd(self) -> int:
        return self.head_dim or self.d_model // self.num_heads

    @property
    def q_group(self) -> int:
        return self.num_heads // self.num_kv_heads

    @property
    def d_inner(self) -> int:
        return self.ssm_expand * self.d_model

    @property
    def ssm_heads(self) -> int:
        return self.d_inner // self.ssm_head_dim

    @property
    def cdtype(self):
        return jnp.dtype(self.compute_dtype)

    @property
    def pdtype(self):
        return jnp.dtype(self.param_dtype)

    def with_(self, **kw) -> "ModelConfig":
        return dataclasses.replace(self, **kw)


# ---------------------------------------------------------------------------
# Initializers
# ---------------------------------------------------------------------------

def dense_init(key, shape, dtype, *, scale: float | None = None):
    """Truncated-normal fan-in init (matches common LM practice)."""
    fan_in = shape[-2] if len(shape) >= 2 else shape[-1]
    std = scale if scale is not None else fan_in ** -0.5
    return (jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32)
            * std).astype(dtype)


def embed_init(key, shape, dtype):
    return (jax.random.normal(key, shape, jnp.float32) * 0.02).astype(dtype)


# ---------------------------------------------------------------------------
# Layers
# ---------------------------------------------------------------------------

def rmsnorm(x: jax.Array, gamma: jax.Array, eps: float) -> jax.Array:
    dt = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return (x * jax.lax.rsqrt(var + eps)).astype(dt) * gamma.astype(dt)


def swiglu(x: jax.Array, w_gate: jax.Array, w_up: jax.Array,
           w_down: jax.Array) -> jax.Array:
    g = jnp.einsum("...d,df->...f", x, w_gate.astype(x.dtype))
    u = jnp.einsum("...d,df->...f", x, w_up.astype(x.dtype))
    h = jax.nn.silu(g) * u
    if h.ndim == 3:                       # (B, S, F): TP-shard the hidden
        h = constrain(h, "dp", None, "mp")
    return jnp.einsum("...f,fd->...d", h, w_down.astype(x.dtype))


def rope_tables(positions: jax.Array, head_dim: int,
                theta: float) -> tuple[jax.Array, jax.Array]:
    """positions (...,S) int32 -> cos/sin tables (...,S, head_dim//2) f32."""
    half = head_dim // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[..., None] * freqs
    return jnp.cos(ang), jnp.sin(ang)


def apply_rope(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """x: (B, S, H, hd); cos/sin: (B, S, half) or (S, half).

    f32 rotation with a downcast at the boundary. (A bf16-rotation variant
    was tried for §Perf A1 on the hypothesis that the f32 upcast made the
    attention-input cotangents f32 before their TP all-reduce — REFUTED:
    the f32 all-reduces come from the CPU backend upcasting bf16 dot
    outputs, and the bf16 rope instead ADDED ~690 GB of resharding
    all-gathers. Reverted; see EXPERIMENTS.md.)
    """
    dt = x.dtype
    x = x.astype(jnp.float32)
    x1, x2 = jnp.split(x, 2, axis=-1)
    if cos.ndim == 2:
        cos, sin = cos[None, :, None, :], sin[None, :, None, :]
    else:
        cos, sin = cos[:, :, None, :], sin[:, :, None, :]
    return jnp.concatenate([x1 * cos - x2 * sin,
                            x1 * sin + x2 * cos], axis=-1).astype(dt)


def cross_entropy_loss(logits: jax.Array, labels: jax.Array) -> jax.Array:
    """Mean CE over positions with label >= 0 (negative = padding).

    logits (..., V) any float dtype (upcast to f32); labels (...) int32.
    """
    logits = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(
        logits, jnp.maximum(labels, 0)[..., None], axis=-1)[..., 0]
    nll = logz - gold
    mask = (labels >= 0).astype(jnp.float32)
    return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)


def param_count(params: Params) -> int:
    return sum(int(p.size) for p in jax.tree_util.tree_leaves(params))


def residual_pattern(cfg) -> tuple:
    """Sharding pins for the (B, S, D) residual stream: plain TP keeps it
    batch-sharded only; Megatron-SP (cfg.seq_shard) also shards S over the
    model axis between blocks — TP output all-reduces become
    reduce-scatters and activation memory drops TPx (§Perf A2)."""
    return ("dp", "mp", None) if cfg.seq_shard else ("dp", None, None)


# ---------------------------------------------------------------------------
# Activation sharding constraints (GSPMD hints)
# ---------------------------------------------------------------------------

def constrain(x: jax.Array, *pattern: str | None) -> jax.Array:
    """Pin an activation's sharding: pattern entries are 'dp' (batch axes),
    'mp' (model axis), or None, one per dim.

    No-op outside a `jax.set_mesh` context (tests, single-device runs).
    Every entry is divisibility-guarded so the same model code serves all
    architectures (e.g. qwen2's 14 heads simply skip the 'mp' pin). These
    pins are what keep GSPMD's propagation in the Megatron-style plan —
    weights get all-gathered, activations stay batch/TP-sharded — instead
    of all-reducing full attention-score tensors (see EXPERIMENTS.md).
    """
    from jax.sharding import PartitionSpec  # local: avoid cycles

    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty:
        return x
    names = mesh.axis_names
    mp = "model" if "model" in names else None
    dp = tuple(n for n in names if n != "model")
    spec = []
    used = set()
    for dim, want in enumerate(pattern):
        d = x.shape[dim] if dim < x.ndim else 0
        if want == "dp" and "dp" not in used and dp:
            size = 1
            for a in dp:
                size *= mesh.shape[a]
            if d % size == 0 and d > 0:
                spec.append(dp if len(dp) > 1 else dp[0])
                used.add("dp")
                continue
        if want == "mp" and "mp" not in used and mp:
            if d % mesh.shape[mp] == 0 and d > 0:
                spec.append(mp)
                used.add("mp")
                continue
        spec.append(None)
    return jax.lax.with_sharding_constraint(x, PartitionSpec(*spec))


def constrain_kv(kc: jax.Array) -> jax.Array:
    """KV-cache slice (B, T, KH, hd): B->dp; KH->mp when divisible, else
    T->mp (context-parallel decode)."""
    from jax.sharding import PartitionSpec

    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty or kc.ndim != 4:
        return kc
    names = mesh.axis_names
    mp = "model" if "model" in names else None
    dp = tuple(n for n in names if n != "model")
    b, t, kh, _ = kc.shape
    dsz = 1
    for a in dp:
        dsz *= mesh.shape[a]
    bspec = (dp if len(dp) > 1 else dp[0]) if (dp and b % dsz == 0) else None
    if mp and kh % mesh.shape[mp] == 0:
        spec = PartitionSpec(bspec, None, mp, None)
    elif mp and t % mesh.shape[mp] == 0:
        spec = PartitionSpec(bspec, mp, None, None)
    else:
        spec = PartitionSpec(bspec, None, None, None)
    return jax.lax.with_sharding_constraint(kc, spec)
