"""Production mesh builders (functions, not constants — importing this
module never touches jax device state)."""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(axis_shapes, axis_names):
    """jax.make_mesh with Auto axes: the sharding rules in
    repro.distributed.sharding lean on GSPMD propagation, which Explicit
    axes (jax.make_mesh's own default) would turn off."""
    return jax.make_mesh(axis_shapes, axis_names,
                         axis_types=(AxisType.Auto,) * len(axis_names))


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: (data=16, model=16) = 256 chips. Multi-pod adds a
    leading pod axis: (pod=2, data=16, model=16) = 512 chips."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_test_mesh(data: int = 1, model: int = 1):
    """Small mesh over however many local devices exist (tests/examples)."""
    return make_mesh((data, model), ("data", "model"))


def make_train_opt_mesh(*, multi_pod: bool = False):
    """§Perf A4: rebalanced training mesh over the SAME chips — TP=4
    instead of TP=16. TP activation all-reduces scale with tokens/device
    x TP-fraction, FSDP weight gathers scale with params x passes; at
    (data=64, model=4) the two meet near the compute roofline for the
    60-400B dense models (napkin + measurement in EXPERIMENTS.md)."""
    shape = (2, 64, 4) if multi_pod else (64, 4)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)
