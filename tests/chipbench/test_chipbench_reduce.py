"""The trace reduction on a small capture recorded on the chip
(``chipbench/testdata/tiny.xplane.pb``), and the table of peaks."""

import os

import pytest

from chipbench import counts, harness, trace

TINY = os.path.join(harness.HERE, "testdata", "tiny.xplane.pb")
WINDOW_S = 0.00612888


@pytest.fixture(scope="module")
def reduction():
    return trace.reduce_file(TINY, WINDOW_S)


def test_interval_union_and_gaps():
    merged = trace.merge([(5, 7), (0, 2), (1, 3), (6, 9), (20, 21)])
    assert merged == [(0, 3), (5, 9), (20, 21)]
    assert trace.gaps_of(merged) == [(3, 5), (9, 20)]


def test_self_time_takes_the_body_out_of_the_loop():
    events = [(0, 100, "while"), (0, 30, "a"), (40, 70, "b"), (100, 110, "c")]
    assert trace.self_seconds(events) == {"while": 40, "a": 30, "b": 30, "c": 10}
    assert trace.short_name("%fusion.12 = f32[8]{0} fusion(%p), kind=kLoop") == "fusion.12"


def test_gap_is_named_by_the_innermost_host_span():
    spans = [(0, 100, "chipbench/window"), (40, 60, "PjitFunction(step)")]
    assert trace.label_gap((45, 55), spans) == "PjitFunction(step)"
    assert trace.label_gap((70, 90), spans) == "chipbench/window"
    assert trace.label_gap((200, 300), spans) == "no host span"


def test_a_wait_for_the_host_is_not_busy_time():
    """A device that sits in a host callback's ``recv-done`` is idle, and the
    wait still shows among the operations."""
    import types

    def ev(name, start, dur):
        return types.SimpleNamespace(name=name, start_ns=start, duration_ns=dur)

    ops = [ev("%fusion.1 = f32[8]{0} fusion(%p)", 0, 100),
           ev("%recv-done.24 = (f32[8]{0}, token[]) recv-done(%recv.24)", 100, 700),
           ev("%send-done = token[] send-done(%send)", 800, 50),
           ev("%fusion.2 = f32[8]{0} fusion(%q)", 850, 150)]
    host = [ev("PjitFunction(_train_phase)", 0, 1000)]
    profile = types.SimpleNamespace(planes=[
        types.SimpleNamespace(name="/device:TPU:0", lines=[
            types.SimpleNamespace(name="XLA Ops", events=ops)]),
        types.SimpleNamespace(name="/host:CPU", lines=[
            types.SimpleNamespace(name="main", events=host)]),
    ])
    r = trace.reduce(profile, 1e-6)
    assert r["busy_s"] == pytest.approx(250e-9)
    assert r["top_ops"][0] == ["recv-done.24", pytest.approx(700e-9)]
    assert r["top_gaps"] == [["PjitFunction(_train_phase)", pytest.approx(750e-9)]]
    assert not trace.HOST_WAIT.match("recv-done-fusion.3")


def test_busy_and_idle_share_of_the_recorded_capture(reduction):
    assert reduction["devices"] == 1
    assert reduction["window_s"] == WINDOW_S
    # Eight short programs in a 6 ms block: the chip is idle nearly all of it.
    assert 5e-6 < reduction["busy_s"] < 5e-5
    idle = 1.0 - reduction["busy_s"] / reduction["window_s"]
    assert 0.99 < idle < 1.0


def test_time_of_a_program_by_name(reduction):
    step = trace.seconds_of_program(reduction, "jit_tiny_step")
    probe = trace.seconds_of_program(reduction, "jit_tiny_probe")
    assert step[1] == 5 and probe[1] == 3
    assert 1e-6 < step[0] / 5 < 3e-6 and 5e-7 < probe[0] / 3 < 2e-6
    assert trace.seconds_of_program(reduction, "jit_absent") is None
    # Operations' self times add up to the busy time (nothing counted twice).
    total = sum(v for _, v in reduction["top_ops"])
    assert total <= reduction["busy_s"] * 1.001
    names = [n for n, _ in reduction["top_ops"]]
    assert len(names) <= 10 and all(len(n) <= 64 and " = " not in n for n in names)


def test_gaps_are_labelled_and_capped(reduction):
    gaps = reduction["top_gaps"]
    assert 1 <= len(gaps) <= 10
    assert sum(v for _, v in gaps) <= reduction["window_s"]
    assert any(n.startswith(("chipbench/", "PjitFunction")) for n, _ in gaps)


def test_unknown_device_kind_is_an_error():
    assert counts.load_peaks("TPU v5 lite")["flops_per_s"] == 197e12
    for kind in ("cpu", "TPU v99", "_note"):
        with pytest.raises(KeyError):
            counts.load_peaks(kind)


def test_least_seconds_names_its_bound():
    peaks = counts.load_peaks("TPU v5 lite")
    assert counts.least_seconds(0.0, 819e9, peaks) == (1.0, "bytes")
    assert counts.least_seconds(197e12, 1.0, peaks) == (1.0, "flops")
