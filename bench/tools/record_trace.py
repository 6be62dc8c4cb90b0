#!/usr/bin/env python3
"""Record a short traced window of one cell and keep its `.xplane.pb`
(the trace reduction's test trace, `bench/data/`).

    python3 bench/tools/record_trace.py --workload fleet.session-hot.r80 \
        --seed 1 --seconds 1.5 --out fleet_window.xplane.pb

Needs a TPU, and the cell's entries in BENCHMARK.json (the fleet's are
listed in `tests/bench/bench_smoke.py` `FLEET_ENTRIES`).
"""
import argparse
import os
import shutil
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import harness, trace  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    r = harness.resolve(args.workload)
    import jax
    if jax.devices()[0].platform != "tpu":
        print("needs a TPU", file=sys.stderr)
        return 3
    harness.enable_compile_cache(harness.compile_cache_dir())
    cell = r["driver"].Cell(r["config"], r["traffic"], seed=args.seed,
                            trace=True)
    cell.setup()
    tmp = tempfile.mkdtemp(prefix="bench_trace_")
    tracer = harness.Tracer(tmp, args.seconds)
    try:
        tracer.start()
        try:
            cell.window(args.seconds, tracer.span)
        finally:
            tracer.stop()
        path = trace.find_xplane(tmp)
        shutil.copy(path, args.out)
        red = trace.reduce_trace(args.out)
        print(f"{args.out}: {os.path.getsize(args.out)} bytes, window "
              f"{red.window_s:.3f} s, busy {red.busy_s:.3f} s")
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
