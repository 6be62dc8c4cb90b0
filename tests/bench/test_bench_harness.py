"""The harness finds cells, configurations, mixes, drivers and metric
readers by name, so that adding them is adding files; and it refuses to
run without a TPU."""
import json
import os
import subprocess
import sys

import pytest

import bench_smoke
from bench import harness

SPEC = os.path.join(bench_smoke.ROOT, "BENCHMARK.json")


def test_every_entry_resolves_to_files():
    spec = harness.load_json(SPEC)
    for cell in spec["workloads"]:
        r = harness.resolve(cell["name"])
        assert r["config"]["name"] == cell["config"]
        assert hasattr(r["driver"], "Cell")
        names = {m["name"] for m in r["end_to_end"]}
        assert "setup_s" in names and len(names) >= 2
        assert r["per_layer"], cell["name"]
        for m in r["per_layer"]:
            path = os.path.join(bench_smoke.ROOT, "bench", "metrics",
                                m["name"] + ".py")
            assert hasattr(harness.load_module(path, "t_" + m["name"]
                                               .replace(".", "_")), "read")


def _copy_tree(tmp_path):
    from pathlib import Path
    return Path(bench_smoke.checkout(tmp_path / "checkout"))


def test_a_new_cell_is_files_and_entries_only(tmp_path):
    root = _copy_tree(tmp_path)
    before = {p: p.read_bytes() for p in root.rglob("*") if p.is_file()}
    cfg = json.loads((root / "bench/configs/wearable-fleet-512d.json")
                     .read_text())
    cfg.update(bench_smoke.FLEET, name="tiny-fleet")
    (root / "bench/configs/tiny-fleet.json").write_text(json.dumps(cfg))
    mix = {"loop": "closed", "outstanding": 8, "tenants": "uniform",
           "query_noise": 4.0, "warmup_half_s": 0.3,
           "warmup_max_halves": 2, "warmup_quiet_halves": 2,
           "warmup_uniform_s": 0.0}
    (root / "bench/traffic/tiny-closed.json").write_text(json.dumps(mix))
    (root / "bench/metrics/sent_per_launch.tiny.py").write_text(
        "def read(ctx):\n"
        "    return ctx.record['sent'] / max(ctx.record['launches'], 1)\n")
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "tiny-fleet", "source": "x",
                            "file": "bench/configs/tiny-fleet.json",
                            "reduced": [], "why": "test"})
    spec["workloads"].append({"name": "tiny.closed", "config": "tiny-fleet",
                              "traffic": "tiny-closed", "chips": 1,
                              "why": "test"})
    spec["end_to_end"].append({"name": "queries_per_s",
                               "unit": "queries/s", "better": "higher",
                               "bound": 0.25, "source": "host_clock",
                               "workloads": ["tiny.closed"]})
    spec["per_layer"].append({"name": "sent_per_launch.tiny",
                              "unit": "queries", "better": "higher",
                              "source": "program_counter",
                              "layer": "serving runtime",
                              "moves": "queries_per_s",
                              "workloads": ["tiny.closed"]})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    for p, data in before.items():
        if p.name != "BENCHMARK.json":
            assert p.read_bytes() == data, f"{p} was edited"

    r = harness.resolve("tiny.closed", str(root))
    assert r["config"]["name"] == "tiny-fleet"
    assert r["traffic"]["outstanding"] == 8
    assert [m["name"] for m in r["per_layer"]] == ["sent_per_launch.tiny"]
    assert {m["name"] for m in r["end_to_end"]} == {"setup_s",
                                                    "queries_per_s"}
    cell = bench_smoke.run_cell(r, seed=2**31 + 11, seconds=0.5)
    assert bench_smoke.correct(cell)
    assert cell.end_to_end()["queries_per_s"] > 0
    reader = harness.load_module(
        str(root / "bench/metrics/sent_per_launch.tiny.py"), "t_tiny")
    assert reader.read(type("Ctx", (), {"record": cell.record})) > 0


def test_run_refuses_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(bench_smoke.ROOT, "bench", "run.py"),
         "--workload", "agent.rag-turn", "--seed", "5",
         "--seconds", "1", "--trace", "0"],
        env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "nothing was run" in proc.stderr


def test_run_refuses_with_only_the_benchmark_files(tmp_path):
    root = _copy_tree(tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, str(root / "bench" / "run.py"),
         "--workload", "agent.rag-turn", "--seed", "5", "--seconds", "1",
         "--trace", "0"], env=env, cwd=str(root), capture_output=True,
        text=True, timeout=300)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""


@pytest.mark.parametrize("workload,key,value", [
    ("fleet.session-hot.r80", "arrivals", "bursty"),
    ("fleet.session-hot.r80", "tenants", "uniform_distinct"),
    ("fleet.session-hot.r80", "loop", "open_bursty"),
    ("agent.rag-turn", "clients", 2),
    ("agent.rag-turn", "lanes", "four"),
])
def test_a_mix_key_no_code_reads_is_refused(workload, key, value,
                                            tmp_path):
    root = bench_smoke.checkout(tmp_path, bench_smoke.FLEET_ENTRIES)
    r = harness.resolve(workload, root)
    mix = dict(r["traffic"], **{key: value})
    with pytest.raises(ValueError):
        r["driver"].Cell(r["config"], mix, seed=1)
    r["driver"].Cell(r["config"], r["traffic"], seed=1)   # as committed


def test_the_tracer_profiles_only_its_seconds(tmp_path):
    import time
    from bench import trace
    tracer = harness.Tracer(str(tmp_path), 0.3)
    tracer.start()
    t0 = time.monotonic()
    while time.monotonic() - t0 < 0.8:
        with tracer.span("bench.poll"):
            time.sleep(0.01)
    # the traced window closed after 0.3 s; the profiler runs to the end
    assert tracer.on and 0.3 <= tracer.traced_s < 0.6
    tracer.stop()
    assert not tracer.on
    tracer.stop()                            # a second stop is a no-op
    assert trace.find_xplane(str(tmp_path)).endswith(".xplane.pb")
    off = harness.Tracer(None, 0.3)
    off.start()
    with off.span("bench.poll"):
        pass
    off.stop()
    assert off.traced_s == 0.0


def test_every_slab_fill_size_is_listed():
    from bench import warm
    sizes = warm.fill_sizes(128, 32)         # a 1 MiB slab of 512-d rows
    assert len(sizes) == len(set(sizes)) == 48
    assert (1, 1) in sizes and (32, 1) in sizes and (4096, 128) in sizes
    assert all(fb <= fr <= 32 * fb and fr <= 4096 for fr, fb in sizes)
    assert warm.fill_sizes(3, 8) == [(1, 1), (2, 1), (4, 1), (8, 1),
                                     (2, 2), (4, 2), (8, 2), (16, 2),
                                     (4, 4), (8, 4), (16, 4), (32, 4)]
