"""The fleet driver at smoke size on the CPU, with the fleet's cell added
to a copy of the benchmark as entries alone (the cell is left for a later
PR): sound runs of the open-loop cell and of the closed-loop mix are
correct, and runs with the served path broken underneath are not."""
import numpy as np
import pytest

import bench_smoke
from repro.serve.runtime import ServingRuntime


HOT = "fleet.session-hot.r80"


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return bench_smoke.checkout(tmp_path_factory.mktemp("fleet"),
                                bench_smoke.FLEET_ENTRIES)


def _hot(root):
    return bench_smoke.resolved(HOT, root)


def _closed(root):
    return bench_smoke.resolved(HOT, root, traffic="uniform-cold.closed")


@pytest.mark.parametrize("workload", ["fleet.session-hot.r80",
                                      "fleet.uniform-cold.closed"])
def test_sound_run_is_correct(workload, root):
    r = _hot(root) if workload == HOT else _closed(root)
    cell = bench_smoke.run_cell(r, seed=2**31 + 21)
    assert bench_smoke.correct(cell), [(c.name, c.value) for c in
                                       cell.checks]
    assert cell.attempted > 10
    e2e = cell.end_to_end()
    assert e2e["queries_per_s"] > 0 and e2e["query_p95_ms"] > 0


def _break_retire(monkeypatch, fault):
    """Break the runtime's retire step once the set-up is done."""
    orig = ServingRuntime._retire

    def retire(self, infl):
        orig(self, infl)
        fault(infl.group)
    return lambda: monkeypatch.setattr(ServingRuntime, "_retire", retire)


def test_an_altered_answer_is_caught(monkeypatch, root):
    def alter(group):
        res = group[0].handle._result
        ids = np.array(res.indices)
        ids[0] = (ids[0] + 1) % 1024
        group[0].handle._result = type(res)(
            indices=ids, scores=res.scores,
            candidate_indices=res.candidate_indices)
    cell = bench_smoke.run_cell(
        _closed(root), seed=2**31 + 22,
        before_window=_break_retire(monkeypatch, alter))
    assert not bench_smoke.correct(cell)
    nums = {c.name: c.value for c in cell.checks}
    assert nums["score_mismatch"] > 0 or nums["leaks"] > 0


def test_half_the_batch_left_out_is_caught(monkeypatch, root):
    def drop(group):
        for req in group[len(group) // 2:]:
            req.handle._result = None
    cell = bench_smoke.run_cell(
        _hot(root), seed=2**31 + 23,
        before_window=_break_retire(monkeypatch, drop))
    assert not bench_smoke.correct(cell)
    assert cell.failed > 0


def test_the_int4_control_fails(root):
    r = _closed(root)
    cell = bench_smoke.run_cell(r, seed=2**31 + 24, seconds=0.5)
    ref = r["driver"]._reference()
    ctl = ref.control_answers(cell.codes, cell.slot_of, cell.asked,
                              r["config"]["k"])
    nums = ref.check_answers(cell.codes, cell.slot_of, cell.asked, ctl,
                             r["config"]["k"])
    limits = r["config"]["limits"]
    assert any(nums[k] > limits[k] for k in limits), nums
