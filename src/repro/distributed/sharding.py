"""Sharding rules: param / batch / cache PartitionSpecs for any mesh.

Strategy (1000+-chip posture, DESIGN.md §4):
  * 2-D "hybrid" sharding: tensor-parallel over `model`, FSDP over the
    batch axes (`data`, plus `pod` when present).
  * Every rule is DIVISIBILITY-GUARDED: if a dim doesn't divide the mesh
    axis, the rule degrades (falls back to another dim or replication)
    instead of failing — this is what lets ONE rule set cover all 10
    assigned architectures (qwen2's 14 heads, seamless's 256206 vocab,
    mamba2's 50280 vocab, batch=1 long-context decode, ...).
  * KV caches: batch -> data; kv-heads -> model when divisible, else the
    SEQUENCE dim of the cache -> model (context-parallel decode — GSPMD
    turns the softmax into partial reductions + a small all-reduce).

Specs are derived from abstract shapes (jax.eval_shape) — nothing is
materialized, so the same code paths serve tests (1 device) and the
512-device dry-run.
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.tree_util import DictKey, GetAttrKey, SequenceKey

from repro.models.common import ModelConfig


def serving_shard_mesh(devices) -> Mesh:
    """1-D ("shard",) mesh over the serving shards' devices.

    The sharded serving runtime's topology object: one axis, one device
    per shard slot (devices may repeat when shards co-locate on a small
    host — jax meshes require distinct devices, so repeats are dropped
    and the runtime keeps its own shard->device map for dispatch). On
    elastic shrink the runtime rebuilds this mesh from the survivors —
    the same degrade-don't-fail posture as the training rules above."""
    devs = list(dict.fromkeys(devices))     # de-dupe, order-preserving
    if not devs:
        raise ValueError("need at least one device")
    return Mesh(np.asarray(devs), ("shard",))


def mesh_axes(mesh: Mesh) -> tuple[tuple[str, ...], str]:
    """Returns (batch_axes, model_axis) for our mesh layouts."""
    names = tuple(mesh.axis_names)
    if "model" in names:
        mp = "model"
        dp = tuple(n for n in names if n != "model")
    else:
        mp = None
        dp = names
    return dp, mp


def _size(mesh: Mesh, axes) -> int:
    if axes is None:
        return 1
    if isinstance(axes, str):
        axes = (axes,)
    return int(np.prod([mesh.shape[a] for a in axes]))


def _fits(dim: int, mesh: Mesh, axes) -> bool:
    return axes is not None and dim % _size(mesh, axes) == 0


def _path_names(path) -> list[str]:
    out = []
    for e in path:
        if isinstance(e, DictKey):
            out.append(str(e.key))
        elif isinstance(e, GetAttrKey):
            out.append(str(e.name))
        elif isinstance(e, SequenceKey):
            out.append(str(e.idx))
    return out


# ---------------------------------------------------------------------------
# Parameters
# ---------------------------------------------------------------------------

SERVE_REPLICATE_BYTES = 128 * 1024 * 1024   # per layer-slice per device


def param_spec(path, shape: tuple[int, ...], mesh: Mesh,
               cfg: ModelConfig, serve: bool = False,
               dtype_bytes: int = 4) -> P:
    """serve=True replicates SMALL weights over the batch axes (no FSDP):
    at decode, FSDP-sharded weights must be all-gathered EVERY step for a
    handful of tokens — the dominant serving collective (EXPERIMENTS.md
    §Perf C1). The rule is SIZE-AWARE: a tensor whose per-layer,
    per-model-shard slice exceeds SERVE_REPLICATE_BYTES (e.g. llama4
    expert banks) stays batch-sharded — replicating it would blow HBM,
    and its gather amortizes over a 32k-token prefill anyway. TP over
    `model` is always kept."""
    dp, mp = mesh_axes(mesh)
    names = _path_names(path)
    name = names[-1] if names else ""
    nd = len(shape)

    if serve and nd >= 2:
        slice_elems = 1
        for d in shape[1:] if nd >= 3 else shape:   # per stacked-layer slice
            slice_elems *= d
        per_dev = slice_elems * dtype_bytes / _size(mesh, mp)
        serve = per_dev <= SERVE_REPLICATE_BYTES

    def trailing(*pattern):
        """pattern entries: 'dp' | 'mp' | None per trailing dim; leading
        (stack) dims replicated. Divisibility-guarded, axes used once."""
        spec = [None] * nd
        used = set()
        for i, want in enumerate(pattern):
            d = nd - len(pattern) + i
            if d < 0:
                continue
            if want == "dp" and serve:
                continue
            if want == "dp" and "dp" not in used and _fits(shape[d], mesh, dp):
                spec[d] = dp if len(dp) > 1 else dp[0]
                used.add("dp")
            elif want == "mp" and "mp" not in used and _fits(shape[d], mesh, mp):
                spec[d] = mp
                used.add("mp")
        return P(*spec)

    if name == "embed":
        v, d = shape
        if _fits(v, mesh, mp):
            return trailing("mp", "dp")
        return trailing(None, "mp")            # shard d_model instead
    if name == "lm_head" or name == "proj":
        d, v = shape
        if _fits(v, mesh, mp):
            return trailing("dp", "mp")
        return trailing("mp", None)
    if name in ("wq", "wk", "wv", "w_gate", "w_up", "sh_gate", "sh_up",
                "in_proj", "xwq", "xwk", "xwv"):
        if name in ("w_gate", "w_up") and nd >= 3 and len(names) >= 2 \
                and names[-2] == "moe":
            # (SB, E, D, F): expert-parallel over model, FSDP over D
            return trailing("mp", "dp", None)
        return trailing("dp", "mp")            # (…, D, O)
    if name in ("wo", "w_down", "sh_down", "out_proj", "xwo"):
        if name == "w_down" and nd >= 3 and len(names) >= 2 \
                and names[-2] == "moe":
            return trailing("mp", None, "dp")  # (SB, E, F, D)
        return trailing("mp", "dp")            # (…, O, D)
    if name in ("bq", "bk", "bv"):
        return trailing("mp")
    if name == "router":
        return trailing("dp", None)            # (SB, D, E)
    # norms, conv, A_log, dt_bias, D, scalar state: replicated
    return P()


def param_shardings(abstract_params: Any, mesh: Mesh,
                    cfg: ModelConfig, serve: bool = False) -> Any:
    return jax.tree_util.tree_map_with_path(
        lambda p, l: NamedSharding(mesh, param_spec(
            p, l.shape, mesh, cfg, serve=serve,
            dtype_bytes=jnp.dtype(l.dtype).itemsize)),
        abstract_params)


def opt_state_shardings(abstract_opt_state: Any, abstract_params: Any,
                        mesh: Mesh, cfg: ModelConfig) -> Any:
    """Optimizer moments shard like their parameter. AdamW mu/nu mirror the
    param tree; Adafactor factored vr/vc inherit the matching param dims."""
    pspecs = jax.tree_util.tree_map_with_path(
        lambda p, l: param_spec(p, l.shape, mesh, cfg), abstract_params)
    flat_specs = {tuple(_path_names(p)): s for p, s in
                  jax.tree_util.tree_flatten_with_path(pspecs)[0]}

    def resolve(path, leaf):
        names = tuple(_path_names(path))
        if names and names[-1] == "step":
            return NamedSharding(mesh, P())
        # strip the optimizer-state prefix ("mu"/"nu"/"v") and suffix
        # ("vr"/"vc"/"v") to find the matching param path
        core = names[1:] if names and names[0] in ("mu", "nu", "v") else names
        suffix = None
        if core and core[-1] in ("vr", "vc", "v"):
            suffix = core[-1]
            core = core[:-1]
        spec = flat_specs.get(tuple(core))
        if spec is None:
            return NamedSharding(mesh, P())
        parts = list(spec) + [None] * (leaf.ndim + 2 - len(spec))
        if suffix == "vr":        # param dims minus the LAST dim
            parts = parts[:leaf.ndim]
        elif suffix == "vc":      # param dims minus the SECOND-TO-LAST dim
            parts = parts[:leaf.ndim + 1]
            parts = parts[:-2] + [parts[-1]]
        else:                     # mirrors the param exactly
            parts = parts[:leaf.ndim]
        return NamedSharding(mesh, P(*parts))

    return jax.tree_util.tree_map_with_path(resolve, abstract_opt_state)


# ---------------------------------------------------------------------------
# Batches and caches
# ---------------------------------------------------------------------------

def batch_spec(shape: tuple[int, ...], mesh: Mesh) -> P:
    dp, _ = mesh_axes(mesh)
    if shape and _fits(shape[0], mesh, dp):
        return P(dp if len(dp) > 1 else dp[0], *([None] * (len(shape) - 1)))
    return P(*([None] * len(shape)))


def batch_shardings(abstract_batch: Any, mesh: Mesh) -> Any:
    return jax.tree.map(
        lambda l: NamedSharding(mesh, batch_spec(l.shape, mesh)),
        abstract_batch)


def cache_spec(path, shape: tuple[int, ...], mesh: Mesh,
               cfg: ModelConfig) -> P:
    """KV/SSM cache sharding. Leaf names: k/v/self_k/.../state/conv/length."""
    dp, mp = mesh_axes(mesh)
    names = _path_names(path)
    name = names[-1] if names else ""
    nd = len(shape)
    if name == "length" or nd <= 1:
        return P()
    if name == "k_scale":                      # (L, B, T, KH)
        spec = [None] * nd
        if _fits(shape[1], mesh, dp):
            spec[1] = dp if len(dp) > 1 else dp[0]
        if _fits(shape[3], mesh, mp):
            spec[3] = mp
        elif _fits(shape[2], mesh, mp):
            spec[2] = mp
        return P(*spec)
    if name in ("k", "v", "self_k", "self_v", "cross_k", "cross_v",
                "k_msb", "k_lsb"):
        # (L|APPS, B, T, KH, hd)
        spec = [None] * nd
        b_dim, t_dim, kh_dim = 1, 2, 3
        used_dp = False
        if _fits(shape[b_dim], mesh, dp):
            spec[b_dim] = dp if len(dp) > 1 else dp[0]
            used_dp = True
        if _fits(shape[kh_dim], mesh, mp):
            spec[kh_dim] = mp
        elif _fits(shape[t_dim], mesh, mp):
            spec[t_dim] = mp                  # context-parallel decode
        if not used_dp:
            rem = [a for a in dp if shape[t_dim] % (mesh.shape[a]
                   * (_size(mesh, mp) if spec[t_dim] == mp else 1)) == 0]
            if rem and spec[t_dim] in (None, mp):
                extra = tuple(rem)
                spec[t_dim] = (extra + (mp,)) if spec[t_dim] == mp else (
                    extra if len(extra) > 1 else extra[0])
        return P(*spec)
    if name == "state":                        # (L, B, H, P, N)
        spec = [None] * nd
        if _fits(shape[1], mesh, dp):
            spec[1] = dp if len(dp) > 1 else dp[0]
        if _fits(shape[2], mesh, mp):
            spec[2] = mp
        return P(*spec)
    if name == "conv":                         # (L, B, W-1, C)
        spec = [None] * nd
        if _fits(shape[1], mesh, dp):
            spec[1] = dp if len(dp) > 1 else dp[0]
        if _fits(shape[3], mesh, mp):
            spec[3] = mp
        return P(*spec)
    return P()


def cache_shardings(abstract_cache: Any, mesh: Mesh, cfg: ModelConfig) -> Any:
    return jax.tree_util.tree_map_with_path(
        lambda p, l: NamedSharding(mesh, cache_spec(p, l.shape, mesh, cfg)),
        abstract_cache)
