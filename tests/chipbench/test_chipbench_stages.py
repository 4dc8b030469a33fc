"""The program's stage reader (``r2d2dpg_tpu/obs/stages.py``) on the capture
recorded on the chip, and the ``learn_stage_ms.*`` metrics that read it."""

import os
import re
import time

import pytest

from chipbench import harness, trace
from chipbench_fixtures import FAKE_TPU, REPO, TINY
from r2d2dpg_tpu.obs import stages

CAPTURE = os.path.join(harness.HERE, "testdata", "tiny.xplane.pb")
WINDOW_S = 0.00612888
TINY_STAGES = ("learn", "alpha", "replay_sample")
NEW_METRICS = sorted(
    f[:-5] for f in os.listdir(os.path.join(harness.HERE, "metrics"))
    if f.startswith("learn_stage_ms.")
)


@pytest.fixture(scope="module")
def table():
    return stages.stage_table(CAPTURE, TINY_STAGES)


def test_scopes_of_the_recorded_capture_are_found(table):
    assert table["devices"] == 1
    # The scan of matmuls, the matmul before it, the probe's cumsum.
    assert table["learn"] > table["alpha"] > 0.0 and table["replay_sample"] > 0.0
    assert table["rest"] == 0.0 and table["rest_paths"] == []


def test_a_jit_segment_is_not_a_stage():
    t = stages.stage_table(CAPTURE, ("tiny_step", "cumsum"))
    assert t["tiny_step"] == t["cumsum"] == 0.0
    assert t["rest"] > 0.0
    assert {p for p, _ in t["rest_paths"]} >= {
        "jit(tiny_step)/chipbench/alpha/dot_general",
        "jit(tiny_probe)/chipbench/replay_sample/jit(cumsum)/tiny_probe/reduce_window_sum",
    }


def test_what_the_compiler_inserted_is_unscoped(table):
    ops = {name for name, _ in table["unscoped_ops"]}
    assert "convert.1" in ops and any(o.startswith("copy") for o in ops)
    assert not any("fusion" in o or "reduce_window" in o for o in ops)
    # The loop's own time has no ``tf_op``; its path is in the program's
    # Hlo Proto.
    assert "while" not in ops
    assert 0.0 < table["unscoped"] < table["busy"]


def test_two_readers_of_one_file_agree(table):
    """``chipbench/trace.py`` reads through ``jax.profiler.ProfileData``, the
    program's reader reads the wire format itself."""
    from jax.profiler import ProfileData

    total = sum(table[k] for k in stages.table_keys(TINY_STAGES))
    busy = trace.reduce_file(CAPTURE, WINDOW_S)["busy_s"]
    plane = next(p for p in ProfileData.from_file(CAPTURE).planes
                 if trace.DEVICE_PLANE.match(p.name))
    self_ns = trace.self_seconds(
        [(s, e, trace.short_name(n)) for s, e, n in trace.events_of(plane, trace.OPS_LINE)])
    assert total == pytest.approx(busy, rel=1e-3)
    assert table["busy"] == pytest.approx(busy, rel=1e-3)
    assert total == pytest.approx(sum(self_ns.values()) / 1e9, rel=1e-3)


def _ctx():
    cell = harness.load_json("workloads", "walker_r2d2.learn")
    return harness.Context(
        cell_name=TINY, cell=cell, config={}, seed=1, seconds=0.1, trace=True,
        t_start=time.time(), device=dict(FAKE_TPU))


def test_stage_ms_reads_nothing_without_a_capture_and_a_float_with_a_table():
    reducer = harness.load_module("reducers", "stage_ms")
    ctx = _ctx()  # no steady capture: the run was not traced
    assert all(reducer.read(ctx, k) is None for k in stages.table_keys())
    ctx.steady_trace = {"devices": 0}  # a capture of the CPU: no device plane
    del ctx.stage_table
    assert reducer.read(ctx, "forward") is None
    planted = {k: 0.001 * (i + 1) for i, k in enumerate(stages.table_keys())}
    ctx.stage_table = dict(planted, busy=1.0, devices=1, updates=4)
    for k in stages.table_keys():
        v = reducer.read(ctx, k)
        assert isinstance(v, float) and v == pytest.approx(1000.0 * planted[k] / 4)
    ctx.stage_table = dict(planted, optimizer=0.0, updates=4)
    assert reducer.read(ctx, "optimizer") == 0.0  # no event: 0, not None


@pytest.mark.parametrize("name", NEW_METRICS)
def test_metric_file_names_a_reducer_a_stage_and_a_layer_that_exist(name):
    spec = harness.load_json("metrics", name)
    assert spec["reducer"] == "stage_ms"
    assert os.path.isfile(os.path.join(harness.HERE, "reducers", "stage_ms.py"))
    assert set(spec["args"]) == {"stage"}
    assert spec["args"]["stage"] in set(stages.table_keys()) - {stages.REST}
    assert spec["unit"] == "ms" and spec["source"] == "program_span"
    with open(os.path.join(REPO, "PERF.md")) as f:
        section = f.read().split("## 3. Layers", 1)[1].split("\n## ", 1)[0]
    layers = {m.group(1).strip() for m in re.finditer(r"^\| ([^|]+)\|", section, re.M)}
    assert spec["layer"] in layers


def test_there_are_seven_and_the_benchmark_lists_them():
    bench = harness.load_benchmark()
    listed = [m["name"] for m in bench["per_layer"] if m["name"].startswith("learn_stage_ms.")]
    assert sorted(listed) == NEW_METRICS and len(listed) == 7
    read = {harness.load_json("metrics", n)["args"]["stage"] for n in listed}
    assert read == set(stages.table_keys()) - {stages.REST}
