"""Multi-device behaviour (8 forced host devices in a SUBPROCESS, so the
main pytest process keeps its default single device)."""
import os
import subprocess
import sys

import pytest

SRC = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "src"))


def run_sub(code: str, timeout=600):
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=8",
               PYTHONPATH=SRC)
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=timeout)
    assert out.returncode == 0, out.stdout + "\n" + out.stderr
    return out.stdout


@pytest.mark.slow
def test_sharded_index_tournament_equals_single_shard():
    run_sub("""
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import AxisType
from repro.core import *
from repro.core.index import ShardedIndex
from repro.core.retrieval import RetrievalConfig, two_stage_retrieve
from repro.core.bitplanar import BitPlanarDB
rng = np.random.default_rng(1)
emb = jnp.asarray(rng.normal(size=(1000, 512)).astype(np.float32))
mesh = jax.make_mesh((4, 2), ('data', 'model'), axis_types=(AxisType.Auto,) * 2)
idx = ShardedIndex.build(emb, mesh)
db = build_database(emb); bp = BitPlanarDB.from_quantized(db)
for metric in ['cosine', 'mips']:
    cfg = RetrievalConfig(k=5, metric=metric)
    ret = idx.retrieve_fn(cfg)
    for seed in range(3):
        q, _ = quantize_int8(jnp.asarray(rng.normal(size=(512,)).astype(np.float32)))
        r = ret(q); r_ref = two_stage_retrieve(q, bp, cfg)
        assert np.array_equal(np.asarray(r.indices), np.asarray(r_ref.indices)), (metric, seed)
print('OK')
""")


@pytest.mark.slow
def test_sharded_train_step_all_families():
    run_sub("""
import jax, jax.numpy as jnp
from jax.sharding import AxisType
from repro.configs import get_config
from repro.models import get_model
from repro.train import get_optimizer, make_train_step
from repro.distributed import sharding as sh
mesh = jax.make_mesh((4, 2), ('data', 'model'), axis_types=(AxisType.Auto,) * 2)
for aid in ['minitron-4b', 'llama4-maverick-400b-a17b', 'zamba2-2.7b',
            'internvl2-26b', 'seamless-m4t-medium']:
    cfg = get_config(aid, smoke=True)
    api = get_model(cfg)
    params = api.init(jax.random.PRNGKey(0))
    aparams = jax.eval_shape(lambda: params)
    pspec = sh.param_shardings(aparams, mesh, cfg)
    params = jax.device_put(params, pspec)
    opt = get_optimizer(cfg.optimizer)
    astate = jax.eval_shape(opt.init, aparams)
    ospec = sh.opt_state_shardings(astate, aparams, mesh, cfg)
    opt_state = jax.jit(opt.init, out_shardings=ospec)(params)
    batch = {'tokens': jnp.zeros((8, 16), jnp.int32),
             'labels': jnp.zeros((8, 16), jnp.int32)}
    if cfg.family == 'encdec':
        batch['frames'] = jnp.zeros((8, 16, cfg.d_model), jnp.float32)
    if cfg.family == 'vlm':
        batch['prefix_embeds'] = jnp.zeros((8, cfg.num_prefix_embeds, cfg.d_model), jnp.float32)
    batch = jax.device_put(batch, sh.batch_shardings(jax.eval_shape(lambda: batch), mesh))
    step = make_train_step(api.loss_fn, opt)
    with jax.set_mesh(mesh):
        p2, o2, m = jax.jit(step)(params, opt_state, batch)
    loss = float(m['loss'])
    assert loss == loss, aid   # not NaN
    print(aid, loss)
print('OK')
""")


@pytest.mark.slow
def test_two_level_compressed_all_reduce_multidevice():
    run_sub("""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from jax.sharding import AxisType
from repro.distributed import compression as comp
mesh = jax.make_mesh((2, 4), ('pod', 'data'), axis_types=(AxisType.Auto,) * 2)
reduce_fn = comp.make_two_level_all_reduce(mesh)
g = jax.random.normal(jax.random.PRNGKey(0), (8, 33))
out = jax.shard_map(lambda t: reduce_fn({'w': t})['w'], mesh=mesh,
                    in_specs=P(('pod', 'data')), out_specs=P(('pod', 'data')),
                    check_vma=False)(g)
want = jnp.broadcast_to(jnp.mean(g, axis=0, keepdims=True), g.shape)
err = float(jnp.max(jnp.abs(out - want)))
scale = float(jnp.max(jnp.abs(g))) / 127.0
assert err <= scale + 1e-5, (err, scale)
print('OK', err)
""")
