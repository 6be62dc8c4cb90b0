"""Traffic generation from a seed, read from a mix's parameters.

Every seed gets the same set of sizes and gaps in another order: the
inter-arrival gaps are the quantiles of their distribution, shuffled, and
tenant draws are the exact counts of their distribution, shuffled. So two
seeds differ in order and pairing, not in how much work they offer.

The session process (`session_focus`) follows the repository's serving
benchmark (`benchmarks/retrieval_bench.py`, `_session_trace`); it is
copied here so that a change to the program cannot move the yardstick.

`check_mix` holds a mix file to the keys and values its driver reads, so
that a key no code reads, or a value no code handles, is an error rather
than a setting that silently does nothing.
"""
from __future__ import annotations

import numpy as np


def check_mix(mix: dict, keys: dict) -> None:
    """`keys` maps each key a driver reads to the tuple of values it
    handles, or to None for a number. Raises on any other key or value."""
    for key, value in mix.items():
        if key not in keys:
            raise ValueError(f"traffic key {key!r} is read by no code; "
                             f"known keys: {sorted(keys)}")
        allowed = keys[key]
        if allowed is None:
            if isinstance(value, bool) or not isinstance(value,
                                                         (int, float)):
                raise ValueError(f"traffic key {key!r} takes a number, "
                                 f"not {value!r}")
        elif value not in allowed:
            raise ValueError(f"traffic key {key!r} takes one of "
                             f"{allowed}, not {value!r}")


def rng_for(seed: int, *stream: int) -> np.random.Generator:
    """An independent generator per (seed, stream) pair."""
    return np.random.default_rng([int(seed) & 0xFFFFFFFF, int(seed) >> 32,
                                  *stream])


def exponential_gaps(rng, n: int, mean: float) -> np.ndarray:
    """`n` Poisson inter-arrival gaps: the exponential quantiles at
    (i + 0.5) / n, shuffled."""
    u = (np.arange(n) + 0.5) / n
    gaps = -mean * np.log1p(-u)
    return rng.permutation(gaps)


def zipf_probs(k: int, s: float) -> np.ndarray:
    p = 1.0 / np.arange(1, k + 1, dtype=np.float64) ** s
    return p / p.sum()


def exact_draws(rng, n: int, probs: np.ndarray) -> np.ndarray:
    """`n` draws over len(probs) outcomes whose counts are the largest-
    remainder rounding of n * probs, in shuffled order."""
    want = n * np.asarray(probs, np.float64)
    counts = np.floor(want).astype(np.int64)
    short = n - int(counts.sum())
    counts[np.argsort(counts - want)[:short]] += 1
    return rng.permutation(np.repeat(np.arange(len(probs)), counts))


def session_focus(rng, tenant_seq: np.ndarray, tenants: int,
                  num_focus: int, *, zipf_s: float = 1.1,
                  sticky: float = 0.8) -> np.ndarray:
    """Per-request focus cluster of a wearable session: on each of its
    requests a tenant keeps its current focus with probability `sticky`,
    else redraws it from a Zipf over the `num_focus` planted clusters."""
    pops = zipf_probs(num_focus, zipf_s)
    focus = rng.choice(num_focus, size=tenants, p=pops)
    out = np.empty(len(tenant_seq), np.int64)
    for i, t in enumerate(tenant_seq):
        if rng.random() >= sticky:
            focus[t] = rng.choice(num_focus, p=pops)
        out[i] = focus[t]
    return out
