"""The agent driver at smoke size on the CPU, with the generator in
float32 so that the program and the reference agree to rounding: a sound
run is correct, a run with a served token or a retrieval altered where it
is produced is not, and the float8 control reads far above the program
on the served tokens' logit gaps."""
import numpy as np

import bench_smoke
from repro.serve import rag
from repro.serve.runtime import ServingRuntime


def _resolved():
    r = bench_smoke.resolved("agent.rag-turn")
    r["config"]["torch_dtype"] = "float32"
    return r


def test_sound_run_is_correct_and_the_fp8_control_fails():
    r = _resolved()
    cell = bench_smoke.run_cell(r, seed=2**31 + 31)
    nums = {c.name: c.value for c in cell.checks}
    assert bench_smoke.correct(cell), nums
    assert nums["served_gap_mean"] < 1e-5 and nums["self_miss"] == 0
    assert cell.end_to_end()["agent_tokens_per_s"] > 0
    # the limits are set for the full-size model on the chip; at smoke
    # size the float8 control still reads orders of magnitude above the
    # program
    _, _, fp8_top = cell.served_gaps(fp8=True)
    _, control, _ = cell.served_gaps(probe=fp8_top)
    assert control.mean() > max(100 * nums["served_gap_mean"], 1e-3)
    assert control[:, 0].max() > max(100 * nums["first_token_gap"], 1e-2)


def test_an_altered_token_is_caught(monkeypatch):
    orig = rag.sample_tokens

    def sample(logits, key, temperature=0.0):
        tok = orig(logits, key, temperature)
        # lane 0 takes the token its logits like least
        worst = np.argmin(np.asarray(logits[:, -1]), axis=-1)
        return tok.at[0, 0].set(int(worst[0]))
    cell = bench_smoke.run_cell(
        _resolved(), seed=2**31 + 32,
        before_window=lambda: monkeypatch.setattr(rag, "sample_tokens",
                                                  sample))
    assert not bench_smoke.correct(cell)
    assert {c.name: c.ok for c in cell.checks}["first_token_gap"] is False


def test_an_altered_retrieval_is_caught(monkeypatch):
    orig = ServingRuntime._retire

    def retire(self, infl):
        orig(self, infl)
        res = infl.group[0].handle._result
        ids = np.array(res.indices)
        ids[0] = (ids[0] + self.index.capacity // 2) % self.index.capacity
        infl.group[0].handle._result = type(res)(
            indices=ids, scores=res.scores,
            candidate_indices=res.candidate_indices)
    cell = bench_smoke.run_cell(
        _resolved(), seed=2**31 + 33,
        before_window=lambda: monkeypatch.setattr(ServingRuntime, "_retire",
                                                  retire))
    assert not bench_smoke.correct(cell)
    nums = {c.name: c.value for c in cell.checks}
    assert nums["leaks"] > 0 and nums["self_miss"] > 0


def test_a_decode_step_that_leaves_its_cache_unchanged_is_caught(
        monkeypatch):
    from repro.models import dense
    orig = dense.decode_step_quant

    def stale(params, cache, tokens, cfg, **kw):
        logits, _ = orig(params, cache, tokens, cfg, **kw)
        return logits, cache
    r = _resolved()
    cell = r["driver"].Cell(r["config"], r["traffic"], seed=2**31 + 34)
    cell.setup()
    monkeypatch.setattr(dense, "decode_step_quant", stale)
    cell.agent._decode_jit = None          # retrace the step with the fault
    cell.window(1.0, lambda name: bench_smoke.contextlib.nullcontext())
    cell.release()
    cell.checks = cell.verify()
    assert not bench_smoke.correct(cell)
    # a sound run reads under 1e-5 (above)
    assert {c.name: c.value for c in cell.checks}["served_gap_mean"] > 1e-3
