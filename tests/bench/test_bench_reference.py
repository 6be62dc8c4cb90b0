"""The plain qwen2 reference computes what the program computes: with the
program in float32 at smoke size, at every served position (the prefill's
and each decode step's through the paged KV cascade: page prune, sign
prescreen, approximate scan, exact attention) the reference puts the
served token first, and no served token's logit lies below the best by
more than float32 rounding."""
import numpy as np
import pytest

import bench_smoke


@pytest.mark.parametrize("kv", [
    dict(page_rows=8, npages=4, prescreen_c0=16, top_k=8),
    dict(page_rows=8, npages=2, prescreen_c0=8, top_k=4),
])
def test_reference_matches_the_program_in_float32(kv):
    r = bench_smoke.resolved("agent.rag-turn")
    r["config"].update(torch_dtype="float32", kv_cascade=kv)
    r["traffic"]["warmup_turns"] = 0
    cell = r["driver"].Cell(r["config"], r["traffic"], seed=2**31 + 41)
    cell.setup()
    rep, tids, js = cell._turn(np.random.default_rng(0))
    turn = {"tids": tids, "docs": js, "tokens": np.asarray(rep.tokens),
            "retrieved": np.asarray(rep.retrieved)}
    ref = r["driver"].reference()
    params = ref.make_params(r["config"], cell.seed, "float32")
    gaps, _, top = ref.logit_gaps(params, cell.prompt_of(turn),
                                  turn["tokens"], r["config"], kv,
                                  lanes_per_call=3)
    assert np.array_equal(top, turn["tokens"])
    assert gaps.shape == turn["tokens"].shape
    assert gaps.max() < 1e-5
