"""The benchmark harness: one run of one cell, driven by data.

`BENCHMARK.json` names the cells. A cell names a configuration
(`bench/configs/<config>.json`, whose `driver` key names
`bench/drivers/<driver>.py`) and a traffic mix (`bench/traffic/<traffic>
.json`). Each per-layer metric is read by `bench/metrics/<name>.py`. A
cell, mix, configuration or metric is added by adding files and entries;
no file here needs an edit.

A driver module defines `Cell(config, traffic, seed, trace)` with
`setup()`, `window(seconds, span)`, `release()`, `verify()` and
`end_to_end()`, and the attribute `record` (what the window measured)
that metric readers read. A mix may set `trace_seconds`: a traced run's
device metrics then cover only the window's first that many seconds (the
device tracer keeps a bounded number of events, and on a busy device it
stops recording long before a 30-second window ends).
"""
from __future__ import annotations

import argparse
import contextlib
import gc
import importlib.util
import json
import logging
import math
import os
import shutil
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
WINDOW_SPAN = "bench.window"


class Check:
    """One number compared with its limit; passes when value <= limit."""

    def __init__(self, name: str, value: float, limit: float):
        self.name, self.value, self.limit = name, float(value), float(limit)

    @property
    def ok(self) -> bool:
        return math.isfinite(self.value) and self.value <= self.limit


def process_start_time() -> float:
    """Seconds since the epoch at which this process started (Linux
    /proc), so set-up counts the interpreter and imports too."""
    with open("/proc/self/stat") as f:
        ticks = int(f.read().rsplit(")", 1)[1].split()[19])
    with open("/proc/uptime") as f:
        uptime = float(f.read().split()[0])
    return time.time() - uptime + ticks / os.sysconf("SC_CLK_TCK")


def load_json(*parts) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def resolve(workload: str, root: str = ROOT) -> dict:
    """Everything one cell needs, found by name: the cell entry, its
    configuration and traffic, its driver module, and the metrics it
    reports with and without --trace."""
    spec = load_json(root, "BENCHMARK.json")
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r}; have {sorted(cells)}")
    cell = cells[workload]
    configs = {c["name"]: c for c in spec["configs"]}
    cfg_entry = configs[cell["config"]]
    config = load_json(root, cfg_entry["file"])
    traffic = load_json(root, "bench", "traffic", cell["traffic"] + ".json")
    driver = load_module(os.path.join(root, "bench", "drivers",
                                      config["driver"] + ".py"),
                         "bench_driver_" + config["driver"])

    def applies(m):
        return workload in m.get("workloads", [workload])

    e2e = [m for m in spec["end_to_end"] if applies(m)]
    e2e_names = {m["name"] for m in e2e}
    layer = [m for m in spec["per_layer"]
             if applies(m) and m["moves"] in e2e_names]
    return {"spec": spec, "cell": cell, "config": config,
            "traffic": traffic, "driver": driver, "end_to_end": e2e,
            "per_layer": layer, "root": root}


def compile_cache_dir(root: str = ROOT) -> str:
    """`$JAX_COMPILATION_CACHE_DIR` when set, else `.jax_cache` at the
    checkout root: a fixed path, so that every run of a cell finds the
    programs the first run compiled."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        root, ".jax_cache")


def enable_compile_cache(path: str) -> None:
    import jax
    os.makedirs(path, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


class CompileCounter(logging.Handler):
    """Counts programs built for the backend, `count`, of which `hits`
    were loaded from the persistent compile cache rather than compiled,
    and keeps the names JAX logs for them, so a program inside the window
    can be named."""

    def __init__(self):
        super().__init__(logging.WARNING)
        self.count = 0
        self.hits = 0
        self.names: list[str] = []

    def __call__(self, event: str, duration: float, **_):
        if event == COMPILE_EVENT:
            self.count += 1

    def hit(self, event: str, **_):
        if event == CACHE_HIT_EVENT:
            self.hits += 1

    def emit(self, record) -> None:
        msg = record.getMessage()
        if msg.startswith("Compiling "):
            self.names.append(msg.split(" with global shapes")[0][10:]
                              + " " + msg.split("types ")[-1][:160])

    def install(self) -> "CompileCounter":
        import jax
        jax.monitoring.register_event_duration_secs_listener(self)
        jax.monitoring.register_event_listener(self.hit)
        jax.config.update("jax_log_compiles", True)
        logger = logging.getLogger("jax")
        logger.addHandler(self)
        logger.propagate = False
        return self


class NewPrograms:
    """Counts programs new to this process: every trace of a jitted
    function (a persistent-cache hit is traced too, then loaded rather
    than compiled). Warm-up runs until a stretch of traffic brings none."""

    def __init__(self):
        import jax
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self)

    def __call__(self, event: str, duration: float, **_):
        if event == TRACE_EVENT:
            self.count += 1


def peak_bytes(devices) -> int:
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)


def log(*args) -> None:
    print("[bench]", *args, file=sys.stderr, flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description="Run one benchmark cell once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    t_start = process_start_time()

    r = resolve(args.workload)
    chips = int(r["cell"]["chips"])
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < chips:
        log(f"needs {chips} TPU chip(s); JAX sees {len(devices)} "
            f"{devices[0].platform!r} device(s): nothing was run")
        return 3
    return run_cell(r, args.seed, args.seconds, bool(args.trace),
                    devices[:chips], t_start)


def run_cell(r: dict, seed: int, seconds: float, trace: bool, devices,
             t_start: float) -> int:
    """Set up, measure, check and report one run of the resolved cell `r`
    on `devices` (the platform check is the caller's)."""
    import jax
    enable_compile_cache(compile_cache_dir(r["root"]))
    counter = CompileCounter().install()
    log("workload", r["cell"]["name"], "seed", seed, "seconds", seconds,
        "trace", int(trace), "jax", jax.__version__)

    cell = r["driver"].Cell(r["config"], r["traffic"], seed=seed,
                            trace=trace)
    cell.setup()
    # what set-up built stays alive all run: keep it out of the window's
    # garbage collections
    gc.collect()
    gc.freeze()
    setup_s = time.time() - t_start
    log("setup_s", setup_s, "compiles_in_setup",
        counter.count - counter.hits, "loaded_from_cache_in_setup",
        counter.hits)
    for line in getattr(cell, "setup_report", []):
        log(line)

    trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    tracer = Tracer(trace_dir, float(r["traffic"].get("trace_seconds",
                                                      seconds)))
    before, hits, named = counter.count, counter.hits, len(counter.names)
    try:
        tracer.start()
        try:
            cell.window(seconds, tracer.span)
        finally:
            tracer.stop()
        # a program compiled, or loaded from the compile cache, inside
        # the window is time the window should not hold: no run is correct
        # with one
        loaded = counter.hits - hits
        built = max(counter.count - before, loaded)
        log("compiles_in_window", built - loaded,
            "loaded_from_cache_in_window", loaded)
        for name in counter.names[named:]:
            log("program built in window:", name)
        memory_peak = peak_bytes(devices)
        cell.release()
        t_check = time.perf_counter()
        checks = cell.verify() + [Check("programs_built_in_window", built,
                                        0)]
        log("verify_s", time.perf_counter() - t_check)
        metrics, extra = {}, {}
        if trace:
            from bench import trace as trace_mod
            red = trace_mod.reduce_trace(trace_mod.find_xplane(trace_dir))
            ctx = Context(cell, red, r, devices[0].device_kind)
            for m in r["per_layer"]:
                reader = load_module(os.path.join(
                    r["root"], "bench", "metrics", m["name"] + ".py"),
                    "bench_metric_" + m["name"].replace(".", "_"))
                value = reader.read(ctx)
                if value is not None:
                    metrics[m["name"]] = {"value": float(value),
                                          "unit": m["unit"]}
            extra["busy_s"] = red.busy_s
            extra["window_s"] = red.window_s
            log("traced_s", tracer.traced_s, "of the window's", seconds,
                "device events end", red.cut_s, "s before the traced "
                "window does")
            top = sorted(red.module_s, key=lambda n: -red.module_s[n])[:8]
            log("traced programs (device s, runs):", "; ".join(
                f"{n} {red.module_s[n]:.4f} {red.module_runs[n]}"
                for n in top))
            breakdown = {"device_ops": red.device_ops(10),
                         "idle_gaps": [[n, s] for n, s in red.idle_gaps]}
        else:
            values = dict(cell.end_to_end())
            values["setup_s"] = setup_s
            for m in r["end_to_end"]:
                if m["name"] in values:
                    metrics[m["name"]] = {"value": float(values[m["name"]]),
                                          "unit": m["unit"]}
    finally:
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)

    for line in getattr(cell, "report", []):
        log(line)
    correct = all(c.ok for c in checks) and cell.failed == 0
    dev = devices[0]
    out = {"correct": correct, "attempted": cell.attempted,
           "failed": cell.failed, "metrics": metrics,
           "device": {"platform": dev.platform, "kind": dev.device_kind,
                      "count": len(devices),
                      "memory_peak_bytes": memory_peak, **extra}}
    if trace:
        out["breakdown"] = breakdown
    out["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                     for c in checks}
    for c in checks:
        log(f"check {c.name} {c.value!r} limit {c.limit!r} "
            f"{'ok' if c.ok else 'FAIL'}")
    print(json.dumps(out), flush=True)
    return 0


class Context:
    """What a per-layer metric reader sees."""

    def __init__(self, cell, reduction, resolved, device_kind: str):
        from bench import peaks
        self.record = cell.record
        self.config = resolved["config"]
        self.reduction = reduction
        self.device_kind = device_kind
        self.peaks = peaks.peaks_for(device_kind)


class Tracer:
    """The profiler over the window, with the `bench.window` span marking
    the window's first `seconds`: the traced window that the reduction
    reads. `span(name)` is the span factory handed to the driver's
    window; once `seconds` have passed it closes the `bench.window` span
    and makes no more spans, and the profiler runs on to the window's
    end (stopping it costs seconds, which the window must not hold).
    With no `trace_dir` every span is a no-op."""

    def __init__(self, trace_dir: str | None, seconds: float):
        self.trace_dir, self.seconds = trace_dir, seconds
        self.on = False
        self.traced_s = 0.0
        self._window = None

    def start(self) -> None:
        if not self.trace_dir:
            return
        import jax.profiler
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
        self._window = jax.profiler.TraceAnnotation(WINDOW_SPAN)
        self._window.__enter__()
        self.on = True
        self._t0 = time.monotonic()

    def span(self, name: str):
        if self._window is None:
            return contextlib.nullcontext()
        if time.monotonic() - self._t0 >= self.seconds:
            self._close_window()
            return contextlib.nullcontext()
        import jax.profiler
        return jax.profiler.TraceAnnotation(name)

    def _close_window(self) -> None:
        if self._window is not None:
            self._window.__exit__(None, None, None)
            self._window = None
            self.traced_s = time.monotonic() - self._t0

    def stop(self) -> None:
        if not self.on:
            return
        import jax.profiler
        self._close_window()
        self.on = False
        jax.profiler.stop_trace()
