"""Required-work counts, the peaks table and the mfu arithmetic."""
import math

import pytest

import bench_smoke  # noqa: F401  (puts the repository on the path)
from bench import layer, peaks, work


@pytest.mark.parametrize("n, k, nprobe, c0, cand, dim, kk, ops, nbytes", [
    # view 4096*8/16 = 2048 rows; c0 256; 50 candidates
    (4096, 16, 8, 256, 50, 512, 3,
     2 * 16 * 512 + 2048 * 512 + 2 * 256 * 512 + 2 * 50 * 512,
     16 * 260 + 2048 * 64 + 256 * 260 + 50 * 516 + 512 + 24),
    # view 1000*2/4 = 500 rows; c0 clamps to 500 -> candidates 40
    (1000, 4, 2, 600, 40, 64, 5,
     2 * 4 * 64 + 500 * 64 + 2 * 500 * 64 + 2 * 40 * 64,
     4 * 36 + 500 * 8 + 500 * 36 + 40 * 68 + 64 + 40),
])
def test_retrieval_query_work_by_hand(n, k, nprobe, c0, cand, dim, kk, ops,
                                      nbytes):
    w = work.retrieval_query(docs_per_tenant=n, dim=dim, num_clusters=k,
                             nprobe=nprobe, prescreen_c0=c0,
                             candidates=cand, k=kk)
    assert w.ops == ops
    assert w.bytes == nbytes


@pytest.mark.parametrize("length, ops, nbytes", [
    # 576 positions: 72 pages, 16 kept = 128 rows, c0 64, top-k 32;
    # per (layer, kv head) with G = 7 query heads, hd = 64
    (576, 2 * 7 * 72 * 64 + 7 * 128 * 64 + 2 * 7 * 64 * 64
     + 2 * 2 * 7 * 32 * 64,
     72 * 36 + 128 * 8 + 64 * 36 + 32 * (64 + 4 + 128)),
    # 20 positions: 3 pages, all kept = 24 rows, c0 clamps to 24, top-k 24
    (20, 2 * 7 * 3 * 64 + 7 * 24 * 64 + 2 * 7 * 24 * 64
     + 2 * 2 * 7 * 24 * 64,
     3 * 36 + 24 * 8 + 24 * 36 + 24 * (64 + 4 + 128)),
])
def test_kv_cascade_step_work_by_hand(length, ops, nbytes):
    w = work.kv_cascade_step(length=length, layers=24, kv_heads=2,
                             q_heads=14, head_dim=64, page_rows=8,
                             npages=16, prescreen_c0=64, top_k=32)
    assert w.ops == ops * 48
    assert w.bytes == nbytes * 48


def test_dense_forward_flops_by_hand():
    # one layer, d 4, 2 heads of 2, 1 kv head, ff 8, vocab 10, context 3
    proj = 4 * (2 + 2) * 2 + 2 * 2 * 4
    per = 2 * (proj + 3 * 4 * 8) + 2 * (2 * 2 * 2 * 3) + 2 * 4 * 10
    assert work.dense_forward_flops(positions=5, context=3, layers=1,
                                    d_model=4, q_heads=2, kv_heads=1,
                                    head_dim=2, d_ff=8, vocab=10) == 5 * per


def test_least_time_takes_the_larger_bound():
    w = work.Work(ops=10.0, bytes=100.0)
    assert work.least_time_s(w, 1.0, 1000.0) == 10.0
    assert work.least_time_s(w, 1000.0, 1.0) == 100.0


class _Ctx:
    def __init__(self, record, config):
        self.record, self.config = record, config
        self.peaks = peaks.peaks_for("TPU v5 lite")


def test_agent_mfu_by_hand_and_positive():
    cfg = dict(num_hidden_layers=1, hidden_size=4, num_attention_heads=2,
               num_key_value_heads=1, intermediate_size=8, vocab_size=10,
               kv_cascade=dict(page_rows=8, npages=4, prescreen_c0=16,
                               top_k=3))
    rec = dict(turns=2, lanes=3, max_new=4, prompt_len=6, span_s=0.5)
    ctx = _Ctx(rec, cfg)
    shape = dict(layers=1, d_model=4, q_heads=2, kv_heads=1, head_dim=2,
                 d_ff=8, vocab=10)
    want = (work.dense_forward_flops(positions=6, context=3.5, **shape)
            + work.dense_forward_flops(positions=3, context=3, **shape)) * 6
    assert layer.agent_flops(ctx) == want
    mfu = 100.0 * want / 0.5 / 197e12
    reader = bench_smoke.harness.load_module(
        bench_smoke.os.path.join(bench_smoke.ROOT, "bench", "metrics",
                                 "agent_mfu.py"), "t_agent_mfu")
    got = reader.read(ctx)
    assert got > 0 and math.isclose(got, mfu, rel_tol=1e-12)


def test_unknown_device_kind_raises():
    assert peaks.peaks_for("TPU v5 lite")["int8_ops_per_s"] == 393e12
    with pytest.raises(KeyError):
        peaks.peaks_for("TPU v9 imaginary")


def test_decode_program_and_its_readers_on_a_traced_agent_window():
    # programs of a traced agent window on one TPU v5e: (device s, runs);
    # the decode step and the embedder's encoder are both jitted lambdas
    class Red:
        module_s = {"jit__lambda": 7.2399, "jit_prefill": 0.2120,
                    "jit_convert_element_type": 0.0101,
                    "jit__argmax": 0.0072, "jit__cascade_batched_aux": 0.0052}
        program_s = {"jit__lambda(4211)": 7.2092, "jit__lambda(977)": 0.0307,
                     "jit_prefill(58)": 0.2120}
        program_runs = {"jit__lambda(4211)": 983, "jit__lambda(977)": 16,
                        "jit_prefill(58)": 16}
    cfg = dict(num_hidden_layers=24, hidden_size=896,
               num_attention_heads=14, num_key_value_heads=2,
               kv_cascade=dict(page_rows=8, npages=16, prescreen_c0=64,
                               top_k=32))
    ctx = _Ctx(dict(lanes=4, max_new=64, prompt_len=512), cfg)
    ctx.reduction = Red()
    assert layer.decode_step(ctx) == (7.2092, 983)
    root = bench_smoke.os.path.join(bench_smoke.ROOT, "bench", "metrics")
    step_ms = bench_smoke.harness.load_module(
        root + "/decode_step_ms.agent.py", "t_step").read(ctx)
    assert step_ms == pytest.approx(7.2092 / 983 * 1e3)
    share = bench_smoke.harness.load_module(
        root + "/kv_cascade_roofline.agent.py", "t_kv").read(ctx)
    assert 0 < share < 1.0
    # a trace without the named programs is an error, not another program
    Red.program_runs = {"jit_decode_renamed(5)": 983}
    Red.program_s = {"jit_decode_renamed(5)": 7.2}
    with pytest.raises(ValueError):
        layer.decode_step(ctx)
