"""Bring-up pieces, checked on the CPU: `chip_smoke.py`'s phases at smoke
widths (Pallas in interpret mode against the jnp reference), its refusal
to run without a TPU, the compile-cache directory rule, and the platform
keying of the kernel path."""
from __future__ import annotations

import os
import shutil
import subprocess
import sys
from pathlib import Path

import jax
import pytest

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

import chip_smoke  # noqa: E402
from repro.configs import get_config  # noqa: E402
from repro.core.clustering import ClusterParams  # noqa: E402
from repro.kernels import platform  # noqa: E402
from repro.launch import compile_cache  # noqa: E402


@pytest.fixture(scope="module")
def smoke_serving():
    gen_cfg = get_config("qwen2-0.5b", smoke=True)
    emb_cfg = get_config("minilm-embedder", smoke=True)
    pipe, runtime = chip_smoke.build_pipeline(
        gen_cfg, emb_cfg, capacity=256, doc_len=16, k=3,
        clusters=ClusterParams(num_clusters=4, nprobe=3, block_rows=8),
        prescreen_c0=32, cache_bytes=16 << 10, batch=4, seed=0,
        backend="pallas")
    docs = chip_smoke.phase_ingest(pipe, tenants=4, docs_per_tenant=64,
                                   burst=32, seed=0)
    return gen_cfg, pipe, runtime, docs


def test_smoke_ingest_fills_every_tenant(smoke_serving):
    _, pipe, _, docs = smoke_serving
    assert sorted(docs) == [0, 1, 2, 3]
    assert pipe.index.num_live == 256


def test_smoke_retrieval_phase_kernel_equals_jnp(smoke_serving):
    _, pipe, runtime, docs = smoke_serving
    out = chip_smoke.phase_retrieval(pipe, runtime, docs, flushes=2,
                                     batch=4, seed=0)
    assert out["hits"] == out["queries"] == 8 and out["leaks"] == 0


def test_smoke_kv_select_phase():
    out = chip_smoke.phase_kv_select(
        get_config("qwen2-0.5b", smoke=True), batch=2, seq_len=64,
        top_k=8, npages=4, prescreen_c0=16, page_rows=8, seed=0,
        backend="pallas")
    assert out["rows_per_lane"] == 8


def test_smoke_agent_phase_tokens_match(smoke_serving):
    _, pipe, runtime, docs = smoke_serving
    out = chip_smoke.phase_agent(pipe, runtime, docs, turns=1, batch=2,
                                 max_new=3, top_k=8, npages=2,
                                 prescreen_c0=12, page_rows=8, seed=0,
                                 backend="pallas")
    assert out["tokens"] == 6


def test_smoke_sharded_phase_on_shared_device():
    out = chip_smoke.phase_sharded(
        shards=3, devices=jax.devices()[:1], tenants=8, docs_per_tenant=32,
        dim=64, rounds=2, batch=4, fail_at=10, clusters=2,
        cache_bytes=64 << 10, seed=0)
    assert out["ledger"]["resolved"] == out["requests"] == 16


def test_check_raises_on_failure():
    with pytest.raises(chip_smoke.SmokeFailure, match="boom"):
        chip_smoke.check(False, "boom")


def test_main_refuses_without_tpu(capsys):
    assert jax.devices()[0].platform != "tpu"
    assert chip_smoke.main([]) != 0
    assert '"ok"' not in capsys.readouterr().out


def test_lone_script_fails_without_the_repo(tmp_path):
    shutil.copy(ROOT / "chip_smoke.py", tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    run = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                         env=env, capture_output=True, text=True,
                         timeout=120)
    assert run.returncode != 0
    assert '"ok"' not in run.stdout


def test_compile_cache_dir_honours_env(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.compile_cache_dir() == str(tmp_path)


def test_compile_cache_dir_defaults_inside_checkout(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert compile_cache.compile_cache_dir() == str(ROOT / ".jax_cache")
    assert compile_cache.compile_cache_dir() == \
        compile_cache.compile_cache_dir()


def test_kernel_path_keyed_on_platform(monkeypatch):
    from repro.core import engine
    from repro.kernels import ops
    assert platform.resolve_backend(None) == "jnp"
    assert platform.resolve_interpret(None) is True
    assert engine.stage_fns(None).plane is engine.stage1_plane_batched_jnp
    monkeypatch.setattr(platform, "on_tpu", lambda: True)
    assert platform.resolve_backend(None) == "pallas"
    assert platform.resolve_interpret(None) is False
    assert engine.stage_fns(None).plane is ops.stage1_scores_batched
    # explicit choices always win
    assert platform.resolve_backend("jnp") == "jnp"
    assert platform.resolve_interpret(True) is True
    with pytest.raises(ValueError, match="unknown backend"):
        platform.resolve_backend("cuda")
