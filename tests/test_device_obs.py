"""Device-plane observability (ISSUE 14, obs/device.py).

The sentinel's contract both ways: an injected aval re-key (changed batch
width post-steady) fires EXACTLY one ``steady_recompile`` event, and
warm-up / declared-window compiles never do.  Plus the HBM gauges'
CPU-fallback behavior, the profiler capture window through a real
``jax.profiler`` session, and the flight-merge fusion that stamps the
window into the Perfetto timeline.
"""

import json
import os

import jax
import jax.numpy as jnp
import pytest

from r2d2dpg_tpu import obs
from r2d2dpg_tpu.obs import stages
from r2d2dpg_tpu.obs.device import (
    DeviceMonitor,
    get_device_monitor,
    parse_profile_window,
)
from r2d2dpg_tpu.obs.registry import Registry

pytestmark = pytest.mark.device


@pytest.fixture
def monitor():
    """A private monitor over a private registry; its listener is muted
    at teardown (jax.monitoring keeps callbacks for the process's life,
    so an unmuted one would double-count every later test's compiles)."""
    reg = Registry()
    mon = DeviceMonitor(registry=reg).install()
    mon.begin_run()
    try:
        yield reg, mon
    finally:
        mon.end_run()
        mon.uninstall()


def _compiles(reg, program=None):
    inst = reg.get("r2d2dpg_device_compile_total")
    if program is None:
        return sum(
            cell.value for _k, cell in inst._cells_snapshot()
        )
    return inst.labels(program=program).value


def test_sentinel_counts_compiles_with_program_labels(monitor):
    reg, mon = monitor
    f = jax.jit(lambda x: x * 2 + 1)
    with mon.program("unit_prog"):
        f(jnp.ones(3)).block_until_ready()
    assert _compiles(reg, "unit_prog") >= 1
    # The histogram carries the same samples (count matches the counter).
    hist = reg.get("r2d2dpg_device_compile_seconds")
    count, total, _p50, _p99 = hist.labels(program="unit_prog").snapshot()
    assert count == _compiles(reg, "unit_prog") and total >= 0.0
    # Cached second call: no new compile.
    before = _compiles(reg, "unit_prog")
    with mon.program("unit_prog"):
        f(jnp.ones(3)).block_until_ready()
    assert _compiles(reg, "unit_prog") == before
    # Run-window deltas are what the stats/bench columns read.
    assert mon.run_stats()["compile_count"] >= 1
    assert mon.run_stats()["steady_recompiles"] == 0


def test_rekey_drill_fires_exactly_one_steady_recompile(monitor):
    """The injected aval re-key drill: a changed batch width AFTER
    mark_steady is the silent recompile-stall bug class — exactly one
    alarm, with the program label in the flight event."""
    reg, mon = monitor
    rec = obs.get_flight_recorder()
    n0 = rec.recorded_total
    f = jax.jit(lambda x: (x * x).sum())
    # Inputs materialized pre-steady: the eager ones() kernels are their
    # own compiles and must not muddy the "exactly one" count.
    x4, x8 = jnp.ones(4), jnp.ones(8)
    with mon.program("drill"):
        f(x4).block_until_ready()  # warm-up: no alarm
    mon.mark_steady()
    with mon.program("drill"):
        f(x8).block_until_ready()  # re-key: ONE alarm
        f(x8).block_until_ready()  # cached: still one
    assert reg.get(
        "r2d2dpg_device_steady_recompiles_total"
    ).value == 1.0
    assert mon.run_stats()["steady_recompiles"] == 1.0
    events = [
        e
        for e in rec.events()
        if e["kind"] == "steady_recompile" and e.get("program") == "drill"
    ]
    assert len(events) == 1 and events[0]["seconds"] >= 0.0
    assert rec.recorded_total >= n0 + 1


def test_sentinel_expected_window_and_end_run_disarm(monitor):
    """Declared windows (the dp warm-compile thread, log fetches, eval)
    compile post-steady without alarming — counted and labelled, never a
    steady_recompile; end_run disarms whatever compiles next."""
    reg, mon = monitor
    f = jax.jit(lambda x: x + 2)
    mon.mark_steady()
    with mon.expected("warm_drill"), mon.program("warm_prog"):
        f(jnp.ones(5)).block_until_ready()
    assert _compiles(reg, "warm_prog") >= 1  # attributed...
    assert reg.get(
        "r2d2dpg_device_steady_recompiles_total"
    ).value == 0.0  # ...but never an alarm
    mon.end_run()
    jax.jit(lambda x: x - 7)(jnp.ones(6)).block_until_ready()
    assert reg.get(
        "r2d2dpg_device_steady_recompiles_total"
    ).value == 0.0


def test_hbm_gauges_cpu_fallback_and_peak(monitor):
    reg, mon = monitor
    keep = jnp.ones((256, 16))  # a live array the fallback must see
    mon.publish()
    in_use = reg.get("r2d2dpg_device_hbm_bytes_in_use")
    dev = str(jax.devices()[0].id)
    v1 = in_use.labels(device=dev).value
    assert v1 >= keep.nbytes
    # Peak is a running max host-side: shrinking live bytes never
    # shrinks the peak series.
    peak1 = reg.get("r2d2dpg_device_hbm_bytes_peak").labels(device=dev).value
    assert peak1 >= v1
    del keep
    mon.publish()
    peak2 = reg.get("r2d2dpg_device_hbm_bytes_peak").labels(device=dev).value
    assert peak2 >= peak1
    assert mon.run_stats()["peak_hbm_bytes"] >= peak1


def test_parse_profile_window_grammar():
    assert parse_profile_window("3:2") == (3, 2)
    for bad in ("3", "a:b", "0:2", "3:0", "1:2:3"):
        with pytest.raises(ValueError):
            parse_profile_window(bad)


def test_profile_window_start_stop_and_merge_fusion(tmp_path, monitor):
    """A real jax.profiler capture across phases 2..3, bracketed by
    flight events, fused by the merge CLI into a labelled
    profile_window span — the capture is findable from the evidence."""
    _reg, mon = monitor
    rec = obs.get_flight_recorder()
    n0 = len(rec.events())
    logdir = tmp_path / "profile_window"
    mon.arm_profile("2:2", str(logdir))
    f = jax.jit(lambda x: x * 3)
    for phase in range(1, 6):
        mon.on_phase(phase)
        f(jnp.ones(2)).block_until_ready()
    new = [e for e in rec.events()[n0:] if e["kind"].startswith("profile_")]
    kinds = [e["kind"] for e in new]
    assert kinds == ["profile_start", "profile_stop"]
    assert new[0]["phase"] == 2 and new[1]["phase"] == 4
    assert new[1]["seconds"] >= 0.0
    assert os.path.isdir(logdir)  # the profiler wrote its session here
    # The closed capture is reduced by stage of the learner call
    # (obs/stages.py): the table is on disk and in profile_stop.  A CPU
    # capture has no device plane, so every stage reads 0 there.
    table = json.loads((logdir / "stages.json").read_text())
    assert set(stages.table_keys()) | {"busy", "devices"} <= set(table)
    assert table["devices"] == 0 and table["busy"] == 0.0
    assert new[1]["stages"]["devices"] == 0
    assert set(stages.table_keys()) <= set(new[1]["stages"])
    # The merge CLI pairs the events into a labelled span (ISSUE 14:
    # the capture window is visible IN the timeline it profiles).
    from r2d2dpg_tpu.obs import flight as flight_mod

    d = tmp_path / "run"
    d.mkdir()
    with open(d / "flight.jsonl", "w") as fh:
        for e in rec.events()[n0:]:
            fh.write(json.dumps(e, default=str) + "\n")
    out = tmp_path / "fused.json"
    flight_mod.main(["merge", str(d), "--trace-out", str(out)])
    doc = json.loads(out.read_text())
    spans = [e for e in doc["traceEvents"] if e["name"] == "profile_window"]
    assert len(spans) == 1
    assert spans[0]["dur"] >= 0 and spans[0]["args"]["phase"] == 2


def test_profile_window_reader_fault_is_an_event_not_the_runs(
    tmp_path, monitor, monkeypatch
):
    """A capture the stage reader cannot read costs the run nothing: a
    ``profile_failed`` event, and ``profile_stop`` without a table."""
    _reg, mon = monitor
    rec = obs.get_flight_recorder()
    n0 = len(rec.events())

    def broken(path, stages=None):
        raise ValueError("not an XSpace")

    monkeypatch.setattr(stages, "stage_table", broken)
    mon.arm_profile("1:1", str(tmp_path / "profile_window"))
    for phase in (1, 2):
        mon.on_phase(phase)
    new = [e for e in rec.events()[n0:] if e["kind"].startswith("profile_")]
    assert [e["kind"] for e in new] == [
        "profile_start", "profile_failed", "profile_stop"
    ]
    assert "not an XSpace" in new[1]["error"]
    assert "stages" not in new[2]


def test_profile_window_span_pairing_unit():
    """profile_window_spans pairs per (file, pid) and keeps an
    unterminated start visible as a zero-duration marker."""
    from r2d2dpg_tpu.obs.flight import profile_window_spans

    events = [
        {"kind": "profile_start", "t_wall": 10.0, "pid": 1, "file": "a",
         "phase": 3, "logdir": "x"},
        {"kind": "profile_stop", "t_wall": 12.5, "pid": 1, "file": "a",
         "phase": 5},
        {"kind": "profile_start", "t_wall": 11.0, "pid": 2, "file": "b",
         "phase": 1},
        {"kind": "other", "t_wall": 11.5},
    ]
    spans = profile_window_spans(events)
    by_file = {s["file"]: s for s in spans}
    assert by_file["a"]["dur_s"] == pytest.approx(2.5)
    assert by_file["a"]["phase"] == 3
    assert by_file["b"]["dur_s"] == 0.0 and by_file["b"]["unterminated"]


def test_train_cli_profile_window_refusals():
    from r2d2dpg_tpu.train import run as train_run, parse_args

    with pytest.raises(SystemExit, match="requires --logdir"):
        train_run(
            parse_args(
                ["--config", "pendulum_tiny", "--profile-window", "1:1"]
            )
        )
    with pytest.raises(SystemExit, match="pick one"):
        train_run(
            parse_args(
                [
                    "--config", "pendulum_tiny",
                    "--profile-window", "1:1",
                    "--profile-phases", "2",
                    "--logdir", "/tmp/never_used_refused",
                ]
            )
        )
    with pytest.raises(SystemExit, match="profile-window"):
        train_run(
            parse_args(
                [
                    "--config", "pendulum_tiny",
                    "--profile-window", "nope",
                    "--logdir", "/tmp/never_used_refused",
                ]
            )
        )


def test_process_monitor_singleton_is_shared_and_armed():
    """Every learner loop installs THE process monitor — one sentinel,
    one compile ledger, whoever builds the trainer first."""
    from r2d2dpg_tpu.configs import PENDULUM_TINY

    t = PENDULUM_TINY.build()
    assert t._device is get_device_monitor()
    assert t._device._installed
