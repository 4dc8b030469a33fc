"""The harness on the CPU at a tiny size: the drivers are functions, the
result line has the contract's keys, files are found by name, the command
refuses to measure off the chip, and every planted control and fault turns
``correct`` false."""

import json
import os
import re
import subprocess
import sys
import time

import pytest

from chipbench import harness, plants, run

from chipbench_fixtures import FAKE_TPU, REPO, TINY, bench_with, tiny_copy

RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device", "compared"}
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    return tiny_copy(str(tmp_path_factory.mktemp("cb")))


@pytest.fixture(scope="module")
def tiny_bench():
    return bench_with(TINY, "pendulum_tiny", "learn", like="walker_r2d2.learn")


def _run(root, bench, trace=False, plant=None, seed=2**31 + 7):
    return run.run_cell(
        TINY, seed, 0.3, trace, plant=plant, t_start=time.time(), root=root,
        bench=bench, device=dict(FAKE_TPU) if trace else None,
    )


@pytest.fixture(scope="module")
def sound(tiny_root, tiny_bench):
    return _run(tiny_root, tiny_bench)


def test_result_line_has_exactly_the_contract_keys(sound):
    assert set(sound) == RESULT_KEYS
    assert list(sound)[-1] == "compared"  # the numbers compared come last
    assert set(sound["metrics"]) == {"learner_steps_per_s", "setup_s"}
    for m in sound["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] > 0
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(sound["device"])
    assert sound["attempted"] > 0 and sound["failed"] == 0
    json.dumps(sound)


def test_sound_run_is_correct_and_prints_each_number_beside_its_limit(sound):
    assert sound["correct"] is True
    for name, c in sound["compared"].items():
        assert set(c) == {"value", "limit", "ok"}, name
        assert c["ok"], (name, c)
    assert {"loss_gap", "grad_gap", "update_gap", "target_gap", "priority_gap",
            "sample_gap", "slots_unmatched", "compiles_in_window"} <= set(sound["compared"])


def test_same_seed_same_inputs(tiny_root, tiny_bench, sound):
    again = _run(tiny_root, tiny_bench)
    for name in ("loss_gap", "grad_gap", "priority_gap"):
        assert again["compared"][name]["value"] == sound["compared"][name]["value"]


def test_traced_run_reports_per_layer_metrics_and_leaves_out_what_it_cannot_read(
    tiny_root, tiny_bench
):
    r = _run(tiny_root, tiny_bench, trace=True)
    assert set(r) == RESULT_KEYS | {"breakdown"}
    # No device plane in a CPU capture: the rooflines and the idle share
    # find nothing to read and are left out, never reported as 0.
    assert set(r["metrics"]) == {"learn_mfu"}
    assert {"busy_s", "window_s"} <= set(r["device"])
    assert set(r["breakdown"]) == {"device_ops", "idle_gaps"}


@pytest.mark.parametrize("plant", plants.PLANTS)
def test_planted_control_or_fault_is_not_correct(tiny_root, tiny_bench, plant):
    """The rest of a run with the timed path broken underneath: the two
    controls one precision down, a call that returns its state unchanged,
    half of the batch left out."""
    r = _run(tiny_root, tiny_bench, plant=plant)
    assert r["correct"] is False
    failed = {k for k, c in r["compared"].items() if not c["ok"]}
    expect = {
        "bf16": {"loss_gap", "grad_gap"},
        "sample_bf16": {"sample_gap"},
        "frozen": {"update_gap", "steps_gap"},
        "half_batch": {"loss_gap", "grad_gap"},
    }[plant]
    assert expect <= failed, (plant, failed)


def test_command_exits_nonzero_on_the_cpu_and_prints_no_metric():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", "walker_r2d2.learn",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=300,
    )
    assert p.returncode != 0
    assert "cpu" in p.stderr
    assert "metrics" not in p.stdout and "learner_steps_per_s" not in p.stdout


def test_new_cell_config_metric_driver_and_reducer_are_found_as_new_files(tmp_path):
    root = tiny_copy(str(tmp_path))
    before = {
        os.path.relpath(os.path.join(d, f), root): os.path.getmtime(os.path.join(d, f))
        for d, _, fs in os.walk(root) for f in fs
    }

    def put(rel, text):
        assert rel not in before
        with open(os.path.join(root, rel), "w") as f:
            f.write(text)

    put("drivers/echo.py", (
        "def setup(ctx):\n    return {'n': 0}\n"
        "def window(s, seconds):\n"
        "    return {'elapsed_s': 1.0, 'calls': 7, 'attempted': 7, 'failed': 0,\n"
        "            'metrics': {'echo_per_s': 7.0}}\n"
        "def check(ctx, s):\n    return []\n"))
    put("reducers/echo_calls.py",
        "def read(ctx, scale):\n    return scale * ctx.window['calls']\n")
    put("reducers/finds_nothing.py", "def read(ctx):\n    return None\n")
    put("configs/echo_cfg.json", json.dumps({"name": "echo_cfg"}))
    put("workloads/echo_cfg.echo.json", json.dumps(
        {"config": "echo_cfg", "driver": "echo", "limits": {}, "trace_seconds": 0.05}))
    put("metrics/echo_calls.json", json.dumps({"reducer": "echo_calls", "args": {"scale": 2}}))
    put("metrics/echo_silent.json", json.dumps({"reducer": "finds_nothing"}))
    bench = bench_with("echo_cfg.echo", "echo_cfg", "echo", per_layer=[
        {"name": n, "unit": "1", "better": "higher", "source": "program_counter",
         "layer": "echo", "moves": "echo_per_s", "workloads": ["echo_cfg.echo"]}
        for n in ("echo_calls", "echo_silent")])
    bench["end_to_end"].append({"name": "echo_per_s", "unit": "1/s", "better": "higher",
                                "bound": 0.03, "source": "host_clock",
                                "workloads": ["echo_cfg.echo"]})
    r = run.run_cell("echo_cfg.echo", 5, 0.05, False, t_start=time.time(),
                     root=root, bench=bench, device=dict(FAKE_TPU))
    assert set(r["metrics"]) == {"echo_per_s", "setup_s"} and r["correct"]
    r = run.run_cell("echo_cfg.echo", 5, 0.05, True, t_start=time.time(),
                     root=root, bench=bench, device=dict(FAKE_TPU))
    assert r["metrics"] == {"echo_calls": {"value": 14.0, "unit": "1"}}
    after = {k: os.path.getmtime(os.path.join(root, k)) for k in before}
    assert after == before  # nothing that was there was edited


# ------------------------------------------------------------ BENCHMARK.json
@pytest.fixture(scope="module")
def bench():
    return harness.load_benchmark()


def test_benchmark_json_keys_names_and_units(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert bench["command"] == ["python3", "chipbench/run.py"]
    assert bench["paths"] == ["chipbench", "tests/chipbench"]
    assert isinstance(bench["run_seconds"], int) and 1 <= bench["run_seconds"] <= 51
    seen = set()
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for entry in bench[group]:
            assert NAME.match(entry["name"]), entry["name"]
            assert (group, entry["name"]) not in seen
            seen.add((group, entry["name"]))
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in ("device_trace", "program_span", "program_counter", "host_clock")
    for m in bench["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source", "workloads"}
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    assert any(m["name"] == "setup_s" and "workloads" not in m for m in bench["end_to_end"])
    for m in bench["per_layer"]:
        assert set(m) == {"name", "unit", "better", "source", "layer", "moves", "workloads"}
        assert "\n" not in m["layer"] and 1 <= len(m["layer"]) <= 200


def test_benchmark_json_cells_configs_and_metric_files_agree(bench):
    cells = {w["name"]: w for w in bench["workloads"]}
    configs = {c["name"]: c for c in bench["configs"]}
    assert 1 <= len(cells) <= 24 and all(w["chips"] == 1 for w in cells.values())
    assert {w["config"] for w in cells.values()} == set(configs)
    pairs = [(w["config"], w["traffic"]) for w in cells.values()]
    assert len(set(pairs)) == len(pairs)
    for name, w in cells.items():
        assert NAME.match(w["traffic"]) and len(w["why"]) <= 200
        cell = harness.load_json("workloads", name)
        assert cell["config"] == w["config"] and cell["why"] == w["why"]
        assert os.path.isfile(os.path.join(harness.HERE, "drivers", cell["driver"] + ".py"))
        e2e = {m["name"] for m in harness.metrics_of(bench, "end_to_end", name)}
        assert "setup_s" in e2e and len(e2e) >= 2
        assert harness.metrics_of(bench, "per_layer", name)
    for c in configs.values():
        assert c["file"] == f"chipbench/configs/{c['name']}.json"
        data = harness.load_json("configs", c["name"])
        assert data["source"] == c["source"]
        assert sorted(data["changed"]) == sorted(c["reduced"])
        assert not any(k.endswith(("_dim", "_rank")) or "hidden" in k for k in c["reduced"])
    e2e_cells = {m["name"]: set(m.get("workloads", cells)) for m in bench["end_to_end"]}
    for m in bench["per_layer"]:
        spec = harness.load_json("metrics", m["name"])
        for key in ("name", "unit", "better", "source", "layer", "moves", "workloads"):
            assert spec[key] == m[key], (m["name"], key)
        assert os.path.isfile(os.path.join(harness.HERE, "reducers", spec["reducer"] + ".py"))
        assert set(m["workloads"]) <= e2e_cells[m["moves"]], m["name"]
    roofline = [m for m in bench["per_layer"] if m["name"].endswith("_roofline")]
    assert roofline and all(m["unit"] == "%" for m in roofline)
    for m in roofline:  # the whole step's share stands beside the rooflines
        assert any("mfu" in o["name"] and o["moves"] == m["moves"]
                   and set(m["workloads"]) <= set(o["workloads"])
                   for o in bench["per_layer"])
