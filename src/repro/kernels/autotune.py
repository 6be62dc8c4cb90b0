"""Measured block-shape autotuner for the stage-1 / fused-top-k kernels.

`DEFAULT_BLOCK_N`'s 256 -> 1024 crossover in `stage1_int4.py` was found by
hand on one machine: interpret-mode Pallas pays a fixed host cost per grid
step, so bigger blocks win on CPU, while a compiled TPU kernel wants blocks
sized to VMEM working sets. Neither constant is right everywhere. This
module replaces the hand-found number with a small *measured* search:

    table = autotune.autotune()          # time candidates on THIS device
    autotune.install(table)              # ops.* wrappers now consult it
    table.save("BENCH_autotune.json")    # artifact, keyed by device kind

The search grid is (kernel, batch bucket) x block_n candidates; the batch
buckets mirror the serving runtime's pow2 padding so a lookup at trace
time hits the bucket the launch was actually padded to. Results are cached
to a JSON artifact stamped with (device_kind, backend, interpret); loading
a table recorded on different hardware is refused (stale-device
invalidation) and every lookup falls back to `DEFAULT_BLOCK_N`
deterministically when no table is installed, so behavior without an
artifact is exactly the pre-autotuner behavior.

The chosen block always times at >= 1.0x the default *by construction*:
`DEFAULT_BLOCK_N` is itself a candidate and selection is argmin over
measured medians (ties prefer the default). The gather kernels'
`block_rows` is NOT tuned here — it is a layout constant baked into the
arena/slab indirection tables, not a free schedule knob.

Set ``REPRO_AUTOTUNE_CACHE=/path/to/table.json`` to have every
`RetrievalEngine` load + install the artifact at construction.
"""
from __future__ import annotations

import functools
import json
import os
import time
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import fused_topk as _fk
from repro.kernels import stage0_sign as _s0
from repro.kernels import stage1_int4 as _s1
from repro.kernels.platform import resolve_interpret

SCHEMA_VERSION = 1

#: Kernels with a free block knob. Keyed by the name used in table entries;
#: values are the ops.py wrapper each one feeds.
KERNELS = ("stage1_single", "stage1_batched", "stage1_rows", "fused_topk",
           "stage0_sign")

DEFAULT_CANDIDATES = (128, 256, 512, 1024, 2048)
DEFAULT_BATCHES = (1, 8, 32)


def device_signature() -> dict:
    """(device_kind, backend, interpret) — the key a tuned table is valid
    for. interpret tracks the backend (Mosaic on TPU, interpreter
    elsewhere), but is recorded separately: it is the single biggest
    determinant of the crossover point."""
    dev = jax.devices()[0]
    return {"device_kind": dev.device_kind, "backend": jax.default_backend(),
            "interpret": resolve_interpret(None)}


def _pow2_bucket(batch: int) -> int:
    return 1 << max(0, int(np.ceil(np.log2(max(1, int(batch))))))


class TuneTable:
    """A measured (kernel, batch bucket) -> block shape map for one device.

    entries: {"<kernel>/b<bucket>": {"kernel", "batch_bucket", "block_n",
    "timings_ms", "default_ms", "speedup_vs_default"}}.
    """

    def __init__(self, signature: dict, entries: dict | None = None,
                 meta: dict | None = None):
        self.signature = dict(signature)
        self.entries = dict(entries or {})
        self.meta = dict(meta or {})

    @staticmethod
    def key(kernel: str, batch_bucket: int) -> str:
        return f"{kernel}/b{batch_bucket}"

    def best(self, kernel: str, batch: int) -> int | None:
        """Tuned block for `kernel` at `batch`, or None if the kernel was
        never benched. Exact pow2-bucket hit first, else the nearest
        measured bucket (log distance) — the runtime pads to pow2 buckets,
        so exact hits are the common case."""
        bucket = _pow2_bucket(batch)
        hit = self.entries.get(self.key(kernel, bucket))
        if hit is not None:
            return int(hit["block_n"])
        near = [e for e in self.entries.values() if e["kernel"] == kernel]
        if not near:
            return None
        pick = min(near, key=lambda e: abs(
            np.log2(max(1, e["batch_bucket"])) - np.log2(bucket)))
        return int(pick["block_n"])

    def to_json(self) -> dict:
        return {"schema": SCHEMA_VERSION, "signature": self.signature,
                "meta": self.meta, "entries": self.entries}

    @classmethod
    def from_json(cls, obj: dict, *, require_current_device: bool = True
                  ) -> "TuneTable | None":
        """Rebuild a table from its JSON form. Returns None (never raises)
        when the payload is malformed, from a different schema, or — with
        `require_current_device` — recorded on different hardware: a stale
        artifact must degrade to the deterministic default, not steer
        block shapes measured on some other machine."""
        try:
            if obj.get("schema") != SCHEMA_VERSION:
                return None
            table = cls(obj["signature"], obj.get("entries", {}),
                        obj.get("meta", {}))
            for e in table.entries.values():
                int(e["block_n"]), str(e["kernel"]), int(e["batch_bucket"])
        except (KeyError, TypeError, ValueError):
            return None
        if require_current_device and table.signature != device_signature():
            return None
        return table

    def save(self, path: str) -> None:
        with open(path, "w") as f:
            json.dump(self.to_json(), f, indent=2, sort_keys=True)
            f.write("\n")


def load(path: str) -> TuneTable | None:
    """Load an artifact; None on missing/corrupt file or a signature that
    does not match the current device (see `TuneTable.from_json`)."""
    try:
        with open(path) as f:
            obj = json.load(f)
    except (OSError, json.JSONDecodeError):
        return None
    return TuneTable.from_json(obj)


# ---------------------------------------------------------------------------
# Install / lookup — the ops.py side of the contract
# ---------------------------------------------------------------------------

_INSTALLED: TuneTable | None = None


def install(table: TuneTable | None) -> None:
    """Make `table` the process-wide tuned-shape source consulted by the
    ops.py wrappers. Installation is trace-time only: programs already
    compiled keep the block shape they were traced with, so install before
    warming the engines you care about (the bench tunes first)."""
    global _INSTALLED
    _INSTALLED = table


def installed() -> TuneTable | None:
    return _INSTALLED


def clear_installed() -> None:
    install(None)


def lookup(kernel: str, batch: int, default: int) -> int:
    """The single resolution point: installed table's choice for (kernel,
    batch bucket), else `default` — deterministically `DEFAULT_BLOCK_N`
    from the call sites, so no artifact == pre-autotuner behavior."""
    if _INSTALLED is None:
        return default
    best = _INSTALLED.best(kernel, batch)
    return default if best is None else best


ENV_CACHE = "REPRO_AUTOTUNE_CACHE"


@functools.lru_cache(maxsize=None)
def _load_env_cache(path: str) -> TuneTable | None:
    return load(path)


def ensure_default_installed() -> TuneTable | None:
    """Engine-construction hook: if ``REPRO_AUTOTUNE_CACHE`` names a valid
    artifact for this device, install it (once — memoized per path).
    Never raises; a stale or unreadable artifact leaves the deterministic
    default in place."""
    path = os.environ.get(ENV_CACHE)
    if not path:
        return _INSTALLED
    table = _load_env_cache(path)
    if table is not None and _INSTALLED is None:
        install(table)
    return _INSTALLED


# ---------------------------------------------------------------------------
# Measurement
# ---------------------------------------------------------------------------

def _timed_ms(fn: Callable[[], object], reps: int) -> float:
    """Median wall-clock of `fn` with every rep fully synchronized —
    block_until_ready inside the timed region, or async dispatch would
    time the enqueue instead of the kernel."""
    jax.block_until_ready(fn())                       # compile + warm
    ts = []
    for _ in range(max(1, reps)):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        ts.append(time.perf_counter() - t0)
    ts.sort()
    return ts[len(ts) // 2] * 1e3


def _runner(kernel: str, rng: np.random.Generator, *, n: int, d: int,
            batch: int):
    """(make(block)->thunk, max_block) for one (kernel, batch) point, or
    (None, 0) when the point is not meaningful (e.g. batched single)."""
    from repro.kernels import ops  # deferred: ops imports this module

    plane = jnp.asarray(rng.integers(0, 256, size=(n, d // 2),
                                     dtype=np.int64).astype(np.uint8))
    q = jnp.asarray(rng.integers(-8, 8, size=(batch, d),
                                 dtype=np.int64).astype(np.int8))
    if kernel == "stage1_single":
        if batch != 1:
            return None, 0
        q0 = q[0]
        return (lambda bn: lambda: ops.stage1_scores(
            q0, plane, block_n=bn)), n
    if kernel == "stage1_batched":
        return (lambda bn: lambda: ops.stage1_scores_batched(
            q, plane, block_n=bn)), n
    if kernel == "stage1_rows":
        # per-lane row views (arena windows / gathered probe rows): the
        # knob is the per-lane block width, bounded by the view size
        w = min(n, 2048)
        rows = jnp.asarray(rng.integers(0, 256, size=(batch, w, d // 2),
                                        dtype=np.int64).astype(np.uint8))
        return (lambda bn: lambda: ops.stage1_scores_rows(
            q, rows, block_w=bn)), w
    if kernel == "fused_topk":
        # k_per_block == c keeps the fused kernel's exactness contract
        # (c <= k_per_block * num_blocks) valid at EVERY candidate block
        c = min(16, n)
        if batch == 1:
            q0 = q[0]
            return (lambda bn: lambda: ops.fused_candidates(
                q0, plane, c=c, k_per_block=c, block_n=bn)), n
        return (lambda bn: lambda: ops.fused_candidates_batched(
            q, plane, c=c, k_per_block=c, block_n=bn)), n
    if kernel == "stage0_sign":
        # 1-bit prescreen: packed sign plane + pre-unpacked {+1,-1} queries
        if d % 8:
            return None, 0
        sign_plane = jnp.asarray(rng.integers(0, 256, size=(n, d // 8),
                                              dtype=np.int64).astype(np.uint8))
        q_sign = ops.pack_query_signs(q)
        return (lambda bn: lambda: ops.stage0_sign_scores_batched(
            q_sign, sign_plane, block_n=bn)), n
    raise ValueError(f"unknown kernel {kernel!r}")


def autotune(*, n: int = 2048, d: int = 256,
             batches: tuple[int, ...] = DEFAULT_BATCHES,
             candidates: tuple[int, ...] = DEFAULT_CANDIDATES,
             reps: int = 3, seed: int = 0,
             kernels: tuple[str, ...] = KERNELS,
             verbose: bool = False) -> TuneTable:
    """Time every (kernel, batch bucket, block) point and keep the argmin.

    `DEFAULT_BLOCK_N` is always injected into the candidate set and wins
    ties, so `speedup_vs_default >= 1.0` holds at every entry by
    construction — the bench gates on exactly that invariant.
    """
    rng = np.random.default_rng(seed)
    table = TuneTable(device_signature(),
                      meta={"n": n, "d": d, "reps": reps, "seed": seed,
                            "candidates": list(candidates),
                            "default_block_n": _s1.DEFAULT_BLOCK_N,
                            "fused_default_block_n": _fk.DEFAULT_BLOCK_N})
    for kernel in kernels:
        default = {"fused_topk": _fk.DEFAULT_BLOCK_N,
                   "stage0_sign": _s0.DEFAULT_BLOCK_N}.get(
                       kernel, _s1.DEFAULT_BLOCK_N)
        for batch in batches:
            make, max_block = _runner(kernel, rng, n=n, d=d, batch=batch)
            if make is None:
                continue
            clamp = max(8, max_block)
            cands = sorted({min(int(c), clamp) for c in candidates}
                           | {min(default, clamp)})
            timings = {c: _timed_ms(make(c), reps) for c in cands}
            d_eff = min(default, clamp)
            # argmin; ties prefer the default so a flat profile keeps the
            # deterministic pre-autotuner shape
            chosen = min(cands, key=lambda c: (timings[c], c != d_eff))
            bucket = _pow2_bucket(batch)
            entry = {"kernel": kernel, "batch_bucket": bucket,
                     "block_n": chosen,
                     "timings_ms": {str(c): timings[c] for c in cands},
                     "default_block_n": d_eff,
                     "default_ms": timings[d_eff],
                     "speedup_vs_default": timings[d_eff] / timings[chosen]}
            table.entries[TuneTable.key(kernel, bucket)] = entry
            if verbose:
                print(f"  autotune {kernel:>15s} b{bucket:<3d} -> "
                      f"block {chosen:>4d} "
                      f"({entry['speedup_vs_default']:.2f}x vs default "
                      f"{d_eff})")
    return table
