"""Reduction of a profiler trace to busy time, program and op time, and
idle gaps named by the benchmark's host spans."""
import collections
import gzip
import os
import shutil

import pytest

import bench_smoke
from bench import trace

Ev = collections.namedtuple("Ev", "name start_ns duration_ns")
# 0.47 s of `fleet.session-hot.r80` on one TPU v5e, gzipped
RECORDED = os.path.join(bench_smoke.ROOT, "bench", "data",
                        "fleet_window.xplane.pb.gz")


def test_op_and_module_names():
    assert trace.op_base("%fusion.365 = s32[1024] fusion(...)") == "fusion"
    assert trace.op_base(
        "%stage0_sign_gather_pallas.9 = s32[112,16,1,8] custom-call(") \
        == "stage0_sign_gather_pallas"
    assert trace.op_base("%while.5 = (s32[]) while(") == "while"
    assert trace.module_base("jit__cascade_batched_aux(1583344931)") \
        == "jit__cascade_batched_aux"
    assert trace.module_base("jit__lambda") == "jit__lambda"


def test_self_times_leave_out_nested_ops():
    evs = [Ev("%while.1 = x", 0, 100), Ev("%a.1 = x", 10, 20),
           Ev("%b.2 = x", 40, 30), Ev("%c.3 = x", 150, 10)]
    got = {n.split(".")[0][1:]: d for n, s, e, d in
           trace._self_times(evs, 0, 1000)}
    assert got == {"while": 50, "a": 20, "b": 30, "c": 10}
    clipped = trace._self_times(evs, 50, 155)
    assert [(n[:2], s, e, d) for n, s, e, d in clipped] == [
        ("%w", 50, 100, 30), ("%b", 50, 70, 20), ("%c", 150, 155, 5)]


def test_merge_and_gap_names():
    assert trace._merge([(5, 8), (0, 3), (2, 4), (8, 9)]) == [[0, 4], [5, 9]]
    spans = [(0, 100, "bench.poll"), (40, 60, "bench.submit")]
    assert trace._name_gap(spans, 45, 55) == "bench.submit"
    assert trace._name_gap(spans, 10, 30) == "bench.poll"
    assert trace._name_gap(spans, 200, 300) == "host"


def test_recorded_fleet_trace(tmp_path):
    path = tmp_path / "fleet_window.xplane.pb"
    with gzip.open(RECORDED, "rb") as src, open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    assert trace.find_xplane(str(tmp_path)) == str(path)
    red = trace.reduce_trace(str(path))
    assert red.devices == 1
    assert 0 < red.busy_s < red.window_s
    assert 0.0 < red.idle_share < 1.0
    assert red.programs_s(("jit__cascade_batched",)) > 0
    assert red.module_runs["jit__cascade_batched_aux"] > 10
    # op self times add up to the busy time (nested ops counted once)
    assert sum(red.op_s.values()) == pytest.approx(red.busy_s, rel=1e-6)
    ops = red.device_ops(10)
    assert len(ops) == 10 and ops[0][1] >= ops[-1][1] > 0
    assert len(red.idle_gaps) == 10
    assert all(name.startswith("bench.") or name == "host"
               for name, _ in red.idle_gaps)
    assert red.idle_gaps[0][1] >= red.idle_gaps[-1][1] > 0
