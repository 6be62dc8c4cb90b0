#!/usr/bin/env python3
"""Readings that set the limits of `correct`: the program's checked
numbers, and the control's, for several seeds in one process.

    python3 bench/tools/readings.py --workload <name> --seeds 1,2,3 \
        --seconds 10 [--control]

Each seed runs the cell's set-up and a window as `bench/run.py` does, then
prints one JSON line: the program's numbers and, with --control, the same
numbers for the reference one precision lower put in the program's place
(fleet: INT4 answers for the same requests; agent: at each position of
every lane's prompt and served tokens, the gap of the token that float8
puts first). Needs a TPU, like the benchmark.
"""
import argparse
import contextlib
import gc
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import harness  # noqa: E402


def control_numbers(cell) -> dict:
    if cell.config["driver"] == "fleet":
        ref = harness.load_module(os.path.join(
            ROOT, "bench", "references", "fleet.py"), "bench_ref_fleet_ctl")
        ans = ref.control_answers(cell.codes, cell.slot_of, cell.asked,
                                  cell.config["k"])
        return ref.check_answers(cell.codes, cell.slot_of, cell.asked, ans,
                                 cell.config["k"])
    _, _, fp8_top = cell.served_gaps(fp8=True)
    _, gaps, _ = cell.served_gaps(probe=fp8_top)
    return {"served_gap_mean": float(gaps.mean()),
            "served_gap_max": float(gaps.max()),
            "first_token_gap_max": float(gaps[:, 0].max())}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--control", action="store_true")
    args = ap.parse_args(argv)
    r = harness.resolve(args.workload)
    import jax
    if jax.devices()[0].platform != "tpu":
        print("needs a TPU", file=sys.stderr)
        return 3
    harness.enable_compile_cache(harness.compile_cache_dir())
    for seed in (int(s) for s in args.seeds.split(",")):
        cell = r["driver"].Cell(r["config"], r["traffic"], seed=seed)
        cell.setup()
        cell.window(args.seconds, lambda n: contextlib.nullcontext())
        cell.release()
        checks = cell.verify()
        out = {"seed": seed,
               "program": {c.name: c.value for c in checks},
               "e2e": cell.end_to_end(), "report": cell.report}
        if args.control:
            out["control"] = control_numbers(cell)
        print(json.dumps(out), flush=True)
        del cell
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
