#!/usr/bin/env python3
"""Find the knee of an open-loop fleet cell once, by a sweep on the chip.

    python3 bench/tools/sweep.py --workload fleet.session-hot.r80 \
        --seed 1 --seconds 10 --rates 30,40,50,60,70

One set-up, then one window per offered rate on the cell's traffic; prints
per rate the completed rate, p50/p95 latency and how late the generator
ran. The knee is the highest rate whose completed rate keeps up and whose
p95 does not run away. Needs a TPU, and the cell's entries in
BENCHMARK.json (the fleet's are listed in `tests/bench/bench_smoke.py`
`FLEET_ENTRIES`).
"""
import argparse
import contextlib
import json
import os
import sys

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    r = harness.resolve(args.workload)
    import jax
    if jax.devices()[0].platform != "tpu":
        print("needs a TPU", file=sys.stderr)
        return 3
    harness.enable_compile_cache(harness.compile_cache_dir())
    cell = r["driver"].Cell(r["config"], dict(r["traffic"]), seed=args.seed)
    cell.setup()
    for rate in (float(x) for x in args.rates.split(",")):
        cell.traffic["rate_per_s"] = rate
        cell.window(args.seconds, lambda n: contextlib.nullcontext())
        rec = cell.record
        lat = rec["latency_s"] * 1e3
        print(json.dumps({
            "rate_per_s": rate, "sent": rec["sent"],
            "completed_per_s": rec["completed_in_window"] / args.seconds,
            "p50_ms": float(np.percentile(lat, 50)),
            "p95_ms": float(np.percentile(lat, 95)),
            "late_p95_ms": float(np.percentile(rec["send_late_s"], 95)
                                 * 1e3)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
