import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis", reason="property tests need hypothesis; see requirements.txt")
from hypothesis import given, settings, strategies as st  # noqa: E402

from repro.distributed import compression as comp


def test_int8_roundtrip_error_bound():
    x = jax.random.normal(jax.random.PRNGKey(0), (1000,)) * 5
    q, scale = comp.quantize_int8_tensor(x)
    err = jnp.max(jnp.abs(comp.dequantize_int8_tensor(q, scale) - x))
    assert float(err) <= float(scale) * 0.5 + 1e-6


def test_error_feedback_is_unbiased_over_time():
    """With error feedback, the SUM of decompressed gradients converges to
    the sum of true gradients (residual stays bounded)."""
    key = jax.random.PRNGKey(1)
    err = jnp.zeros((256,))
    total_true = jnp.zeros((256,))
    total_sent = jnp.zeros((256,))
    for i in range(50):
        key, k = jax.random.split(key)
        g = jax.random.normal(k, (256,)) * (1.0 + i % 3)
        total_true += g
        sent, err = comp.compress_decompress(g, err)
        total_sent += sent
    # everything not yet sent lives in the residual
    np.testing.assert_allclose(np.asarray(total_sent + err),
                               np.asarray(total_true), rtol=1e-4, atol=1e-3)
    assert float(jnp.max(jnp.abs(err))) < 1.0


def test_apply_error_feedback_tree():
    g = {"a": jnp.ones((8,)), "b": {"c": jnp.full((4,), -2.0)}}
    e = comp.init_error_state(g)
    out, e2 = comp.apply_error_feedback(g, e)
    assert jax.tree.structure(out) == jax.tree.structure(g)
    np.testing.assert_allclose(np.asarray(out["a"]), np.ones(8), atol=0.02)


@given(st.integers(0, 2**31 - 1))
@settings(max_examples=20, deadline=None)
def test_compress_preserves_large_values(seed):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=64).astype(np.float32) * 100)
    q, s = comp.quantize_int8_tensor(x)
    deq = comp.dequantize_int8_tensor(q, s)
    assert float(jnp.max(jnp.abs(deq - x))) <= float(s) * 0.5 + 1e-4


def test_two_level_all_reduce_single_device_mesh():
    """On a (pod=1, data=1) mesh the two-level reduction must be exact
    identity-mean (numerics of the quantize/dequantize path)."""
    from jax.sharding import AxisType
    mesh = jax.make_mesh((1, 1), ("pod", "data"),
                         axis_types=(AxisType.Auto,) * 2)
    reduce_fn = comp.make_two_level_all_reduce(mesh)
    g = {"w": jax.random.normal(jax.random.PRNGKey(0), (33,))}

    out = jax.shard_map(lambda t: reduce_fn(t), mesh=mesh,
                        in_specs=jax.sharding.PartitionSpec(),
                        out_specs=jax.sharding.PartitionSpec(),
                        check_vma=False)(g)
    scale = float(jnp.max(jnp.abs(g["w"]))) / 127.0
    np.testing.assert_allclose(np.asarray(out["w"]), np.asarray(g["w"]),
                               atol=scale * 0.5 + 1e-6)
