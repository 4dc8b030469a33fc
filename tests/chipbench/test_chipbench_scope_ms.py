"""The ``learn_scope_ms.*`` metrics: ``chipbench/reducers/scope_ms.py`` reads
the entry ``scopes`` of the stage table the run already has, over the updates
the capture holds, and the five metric files name rows the program's reader
(``r2d2dpg_tpu/obs/stages.py``) can return."""

import os
import re
import time

import pytest

from chipbench import harness
from chipbench_fixtures import FAKE_TPU, REPO, TINY
from r2d2dpg_tpu.obs import stages

CAPTURE = os.path.join(harness.HERE, "testdata", "tiny.xplane.pb")
METRICS = sorted(
    f[:-5] for f in os.listdir(os.path.join(harness.HERE, "metrics"))
    if f.startswith("learn_scope_ms.")
)
WALKER, CHEETAH, SDAR = ("walker_r2d2.learn", "cheetah_pixels.learn",
                         "humanoid_sdar_moe.learn")
CELLS = {
    "learn_scope_ms.frames": [CHEETAH],
    "learn_scope_ms.diagnostics": [WALKER, CHEETAH, SDAR],
    "learn_scope_ms.loops": [WALKER, CHEETAH, SDAR],
    "learn_scope_ms.rest": [WALKER, CHEETAH, SDAR],
    "learn_scope_ms.moe_experts_recomputed": [SDAR],
}


def _ctx(learner_steps=4):
    cell = harness.load_json("workloads", WALKER)
    return harness.Context(
        cell_name=TINY, cell=cell, config={"learner_steps": learner_steps}, seed=1,
        seconds=0.1, trace=True, t_start=time.time(), device=dict(FAKE_TPU))


@pytest.fixture()
def reducer():
    return harness.load_module("reducers", "scope_ms")


@pytest.fixture()
def never_set_up(monkeypatch):
    """A second set-up or a capture of the reducer's own would go through
    these two; neither may be reached."""
    def refuse(*a, **k):
        raise AssertionError("scope_ms set a cell up or took a capture")

    monkeypatch.setattr(harness, "profile_session", refuse)
    real = harness.load_module

    def load(kind, name, root=harness.HERE):
        module = real(kind, name, root)
        if kind == "drivers":
            module.setup = refuse
        return module

    monkeypatch.setattr(harness, "load_module", load)


def _planted(**more):
    table = stages.stage_table(CAPTURE)
    table.update(updates=20, **more)
    return table


def test_reads_nothing_without_a_capture(reducer, never_set_up):
    ctx = _ctx()  # not a traced run: no steady capture
    assert reducer.read(ctx, "diagnostics") is None
    ctx = _ctx()
    ctx.steady_trace = {"devices": 0}  # a capture of the CPU: no device plane
    assert reducer.read(ctx, "loops") is None and reducer.read(ctx, "rest") is None
    assert ctx.stage_table is None


def test_reads_nothing_from_a_table_without_scopes(reducer, never_set_up):
    """The parent's reader: the stage keys and ``updates``, nothing else."""
    ctx = _ctx()
    ctx.stage_table = {k: 0.001 for k in stages.table_keys()} | {
        "busy": 0.008, "devices": 1, "updates": 4}
    assert reducer.read(ctx, "diagnostics") is None
    assert reducer.read(ctx, "moe_experts", pass_="recomputed") is None
    ctx = _ctx()
    ctx.core_stage_table = {"updates": 4, "moe_experts": 0.1}
    assert reducer.read(ctx, "moe_experts", pass_="recomputed") is None
    assert ctx.core_stage_table == {"updates": 4, "moe_experts": 0.1}


def test_reads_a_float_over_the_updates_the_capture_holds(reducer, never_set_up, capfd):
    ctx = _ctx(learner_steps=4)
    ctx.stage_table = _planted()
    scopes = ctx.stage_table["scopes"]
    # 5 whole executions of the program with most device seconds x 4 updates
    # a call: 20, whatever the host says it dispatched.
    ctx.stage_table["updates"] = 24
    for row in ("replay_sample", "loops", "rest", "unscoped"):
        v = reducer.read(ctx, row)
        assert isinstance(v, float) and v == pytest.approx(1000.0 * scopes[row]["all"] / 20)
    assert ctx.stage_table["updates_seen"] == 20
    assert reducer.read(ctx, "replay_sample", pass_="forward") == reducer.read(ctx, "replay_sample")
    assert reducer.read(ctx, "replay_sample", pass_="recomputed") == 0.0  # no event: 0, not None
    assert reducer.read(ctx, "no_such_scope") is None
    # The first call of the run logged the table and the two counts, once.
    err = capfd.readouterr().err
    assert err.count("dispatched 24 updates, the capture holds 20 (ratio 0.8333)") == 1
    assert "are scaled by 0.8333" in err and "truncated False" in err
    assert err.count("scope_ms: ms an update") == 1


def test_takes_the_core_table_where_the_driver_captured_one(reducer, never_set_up):
    ctx = _ctx(learner_steps=2)
    core = stages.stage_table(CAPTURE, stages.LEARN_STAGES + stages.CORE_STAGES)
    core["scopes"]["moe_experts"] = {"forward": 0.2, "recomputed": 0.1, "backward": 0.5, "all": 0.8}
    ctx.core_stage_table = dict(core, updates=10)
    ctx.stage_table = None  # stage_ms's is not consulted
    assert reducer.read(ctx, "moe_experts", pass_="recomputed") == pytest.approx(1000.0 * 0.1 / 10)
    assert reducer.read(ctx, "moe_experts") == pytest.approx(1000.0 * 0.8 / 10)
    # The driver captured nothing (the CPU): nothing, and no second set-up.
    ctx = _ctx()
    ctx.steady_trace = {"devices": 1}
    ctx.core_stage_table = None
    assert reducer.read(ctx, "diagnostics") is None and not hasattr(ctx, "stage_table")


def test_a_capture_that_holds_no_whole_execution_reads_nothing(reducer, never_set_up):
    ctx = _ctx()
    ctx.stage_table = _planted(programs=[])
    assert reducer.read(ctx, "rest") is None
    ctx = _ctx()
    ctx.stage_table = _planted(programs=[{"name": "jit_timed", "executions": 0, "seconds": 0.0}])
    assert reducer.read(ctx, "rest") is None


def test_there_are_five_and_the_benchmark_lists_them_last():
    bench = harness.load_benchmark()
    listed = [m["name"] for m in bench["per_layer"] if m["name"].startswith("learn_scope_ms.")]
    assert sorted(listed) == METRICS == sorted(CELLS)
    assert [m["name"] for m in bench["per_layer"][-5:]] == listed  # appended
    for m in bench["per_layer"][-5:]:
        assert m["workloads"] == CELLS[m["name"]]
        assert (m["unit"], m["better"], m["source"], m["moves"]) == (
            "ms", "lower", "program_span", "learner_steps_per_s")


@pytest.mark.parametrize("name", METRICS)
def test_metric_file_names_the_reducer_a_row_of_scopes_and_a_layer_that_exist(name):
    spec = harness.load_json("metrics", name)
    assert spec["reducer"] == "scope_ms"
    assert os.path.isfile(os.path.join(harness.HERE, "reducers", "scope_ms.py"))
    assert set(spec["args"]) <= {"scope", "pass_"} and "scope" in spec["args"]
    table = stages.stage_table(CAPTURE)
    row = table["scopes"][spec["args"]["scope"]]  # a row stage_table returns
    assert spec["args"].get("pass_", "all") in row
    assert spec["unit"] == "ms" and spec["source"] == "program_span"
    with open(os.path.join(REPO, "PERF.md")) as f:
        section = f.read().split("## 3. Layers", 1)[1].split("\n## ", 1)[0]
    layers = {m.group(1).strip() for m in re.finditer(r"^\| ([^|]+)\|", section, re.M)}
    assert spec["layer"] in layers
    assert f"`{name}`" in section
