"""The ``loop`` driver on the CPU at a tiny size: the real fused train phase
over the native walker pool, followed from the reset by the reference
through the recorder at the pool's edge."""

import time

import numpy as np
import pytest

from chipbench import harness, run

from chipbench_fixtures import bench_with, tiny_copy

CELL = "walker_tiny.loop"


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny_copy(str(tmp_path_factory.mktemp("cbl")))


@pytest.fixture(scope="module")
def bench():
    """No cell of ``BENCHMARK.json`` uses the ``loop`` driver yet (PERF.md,
    Open questions): the test brings the cell, its end-to-end metric and its
    per-layer metrics, as the PR that proves the cell on the chip will."""
    metrics = [harness.load_json("metrics", n)
               for n in ("phase_mfu", "device_idle.loop", "envpool_share")]
    keys = ("name", "unit", "better", "source", "layer", "moves")
    b = bench_with(CELL, "walker_tiny", "loop",
                   per_layer=[dict({k: m[k] for k in keys}, workloads=[CELL])
                              for m in metrics])
    b["end_to_end"].append({"name": "agent_steps_per_s", "unit": "steps/s",
                            "better": "higher", "bound": 0.03,
                            "source": "host_clock", "workloads": [CELL]})
    return b


def _run(root, bench, plant=None):
    return run.run_cell(CELL, 2**31 + 5, 0.3, False, plant=plant,
                        t_start=time.time(), root=root, bench=bench)


def test_sound_loop_run_is_correct_and_reports_agent_steps(root, bench):
    r = _run(root, bench)
    assert set(r["metrics"]) == {"agent_steps_per_s", "setup_s"}
    assert r["attempted"] > 0 and r["attempted"] % (4 * 4) == 0  # envs x stride
    assert r["correct"], {k: c for k, c in r["compared"].items() if not c["ok"]}
    assert {"action_gap", "row_gap", "carry_gap", "rank_gap", "loss_gap", "grad_gap",
            "update_gap", "target_gap", "priority_gap", "sample_gap",
            "slots_unmatched", "steps_gap",
            "compiles_in_window"} == set(r["compared"])
    # What lands in the arena is what crossed the pool's edge, to the bit.
    assert r["compared"]["row_gap"]["value"] <= 1e-6


@pytest.mark.parametrize("plant,expect", [
    ("bf16", {"action_gap", "carry_gap"}),
    ("frozen", {"update_gap", "steps_gap"}),
    ("half_batch", {"loss_gap", "grad_gap"}),
])
def test_planted_control_or_fault_is_not_correct_in_the_loop(root, bench, plant, expect):
    r = _run(root, bench, plant=plant)
    assert r["correct"] is False
    failed = {k for k, c in r["compared"].items() if not c["ok"]}
    assert expect <= failed, (plant, failed)


def test_recorder_keeps_what_crosses_the_pools_edge():
    from chipbench.drivers.loop import Recorder

    class Pool:
        def reset_all(self, seeds):
            return (np.zeros((2, 3)), np.zeros(2), np.ones(2), np.ones(2))

        def step_all(self, actions, repeat=1):
            return (actions.sum(1, keepdims=True) * np.ones((2, 3)), np.ones(2),
                    np.ones(2), np.zeros(2))

    pool = Pool()
    rec = Recorder(pool)
    pool.reset_all(np.arange(2))
    a = np.ones((2, 1), np.float32)
    out = pool.step_all(a, repeat=2)
    a[:] = 7.0  # the record holds copies
    assert rec.steps[0][0].tolist() == [[1.0], [1.0]] and rec.step_calls == 1
    np.testing.assert_array_equal(rec.steps[0][1], out[0])
    rec.recording = False
    pool.step_all(a)
    assert len(rec.steps) == 1 and rec.step_calls == 2 and rec.step_seconds > 0
    rec.close()
    assert pool.step_all.__self__ is pool
