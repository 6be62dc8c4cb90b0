"""Smoke-size cells of the benchmark for the CPU tests: the committed
configurations and mixes with their scale cut down, run through the same
drivers."""
import contextlib
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench import harness  # noqa: E402

FLEET = dict(tenants=2, docs_per_tenant=512, dim=64, num_clusters=8,
             nprobe=4, block_rows=32, prescreen_c0=64, ingest_burst=256,
             cache_bytes=1 << 16, topics=8)
AGENT = dict(num_hidden_layers=2, hidden_size=64, num_attention_heads=4,
             num_key_value_heads=2, intermediate_size=128, vocab_size=512,
             embedder=dict(num_layers=2, d_model=32, num_heads=4,
                           num_kv_heads=4, d_ff=64, vocab_size=512,
                           pooled_dim=64),
             kv_cascade=dict(page_rows=8, npages=4, prescreen_c0=16,
                             top_k=8))
AGENT_ARENA = dict(tenants=4, docs_per_tenant=128, doc_tokens=16,
                   num_clusters=8, nprobe=4, prescreen_c0=64,
                   ingest_burst=128, cache_bytes=1 << 16)


# The fleet's cell, as BENCHMARK.json entries: its files stay under bench/
# for a later benchmark PR, which adds the cell with these entries alone.
FLEET_ENTRIES = {
    "configs": [{"name": "wearable-fleet-512d",
                 "source": "https://arxiv.org/abs/2510.27107",
                 "file": "bench/configs/wearable-fleet-512d.json",
                 "reduced": ["tenants"], "why": "test"}],
    "workloads": [{"name": "fleet.session-hot.r80",
                   "config": "wearable-fleet-512d",
                   "traffic": "session-hot.r80", "chips": 1,
                   "why": "test"}],
    "end_to_end": [{"name": "query_p50_ms", "unit": "ms", "better": "lower",
                    "bound": 0.25, "source": "host_clock",
                    "workloads": ["fleet.session-hot.r80"]}],
}


def checkout(dest, entries: dict | None = None) -> str:
    """A copy of the benchmark's files (BENCHMARK.json and bench/) at
    `dest`, with `entries` appended to BENCHMARK.json's lists."""
    import json
    import shutil
    dest = str(dest)
    os.makedirs(dest, exist_ok=True)
    shutil.copytree(os.path.join(ROOT, "bench"), os.path.join(dest, "bench"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    spec = harness.load_json(ROOT, "BENCHMARK.json")
    for key, items in (entries or {}).items():
        spec[key] = spec[key] + items
    with open(os.path.join(dest, "BENCHMARK.json"), "w") as f:
        json.dump(spec, f)
    return dest


def resolved(workload: str, root: str = ROOT,
             traffic: str | None = None) -> dict:
    """`harness.resolve` with the configuration and mix cut to smoke
    size; `traffic` names another mix under bench/traffic/ in the
    cell's place."""
    r = harness.resolve(workload, root)
    if traffic is not None:
        r["traffic"] = harness.load_json(root, "bench", "traffic",
                                         traffic + ".json")
    cfg, tr = dict(r["config"]), dict(r["traffic"])
    if cfg["driver"] == "fleet":
        cfg.update(FLEET)
        tr.update(warmup_half_s=0.3, warmup_max_halves=2,
                  warmup_quiet_halves=2, warmup_uniform_s=0.3)
        if tr["loop"] == "open":
            tr["rate_per_s"] = 40.0
    else:
        cfg.update(AGENT)
        cfg["arena"] = dict(cfg["arena"], **AGENT_ARENA)
        tr.update(max_new=6, warmup_turns=1)
    r.update(config=cfg, traffic=tr)
    return r


def run_cell(r: dict, seed: int, seconds: float = 1.0, before_window=None):
    """Set-up, window, release and verify; returns the cell.
    `before_window()` runs between set-up and the window (to break the
    timed path underneath)."""
    cell = r["driver"].Cell(r["config"], r["traffic"], seed=seed)
    cell.setup()
    if before_window is not None:
        before_window()
    cell.window(seconds, lambda name: contextlib.nullcontext())
    cell.release()
    cell.checks = cell.verify()
    return cell


def correct(cell) -> bool:
    return all(c.ok for c in cell.checks) and cell.failed == 0
