"""Where the kernels run: compiled Mosaic on a TPU, the interpreter elsewhere.

One predicate keys both choices the platform decides:

  * `resolve_interpret` — a kernel called without an explicit `interpret`
    compiles for the chip on a TPU and runs the Pallas interpreter on any
    other backend, so no caller on the chip falls into the interpreter by
    omission;
  * `resolve_backend` — a cascade whose `backend` is left unset runs the
    Pallas kernels on a TPU and the pure-jnp reference elsewhere.

Both read `jax.default_backend()` when called (never at import), so the
answer follows whatever platform JAX initialised.
"""
from __future__ import annotations

import jax


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def resolve_interpret(interpret: bool | None) -> bool:
    """An explicit flag wins; None means interpret everywhere but a TPU."""
    return (not on_tpu()) if interpret is None else bool(interpret)


def resolve_backend(backend: str | None) -> str:
    """An explicit "jnp"/"pallas" wins; None means Pallas on a TPU."""
    if backend is None:
        return "pallas" if on_tpu() else "jnp"
    if backend not in ("jnp", "pallas"):
        raise ValueError(f"unknown backend {backend!r}: 'jnp' or 'pallas'")
    return backend
