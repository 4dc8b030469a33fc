"""Unified telemetry tests (ISSUE 3): instrument registry, /metrics
exporter, flight recorder, divergence watchdog, MetricLogger thread-safety
and append-only CSV, PercentileWindow edge cases, and the obs lint gate.
"""

import csv
import json
import os
import subprocess
import threading
import urllib.request

import numpy as np
import pytest

from r2d2dpg_tpu import obs
from r2d2dpg_tpu.obs.registry import Registry
from r2d2dpg_tpu.utils.metrics import MetricLogger, PercentileWindow

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ------------------------------------------------------------------ registry
def test_counter_gauge_histogram_basics():
    reg = Registry()
    c = reg.counter("x_total", "a counter")
    c.inc()
    c.inc(2.5)
    assert c.value == 3.5
    with pytest.raises(ValueError):
        c.inc(-1)

    g = reg.gauge("x_gauge")
    g.set(7)
    assert g.value == 7.0
    g.set_fn(lambda: 42.0)
    assert g.value == 42.0
    g.set(1.0)  # set() clears the callback
    assert g.value == 1.0

    h = reg.histogram("x_seconds")
    for v in (1.0, 2.0, 3.0, 4.0):
        h.observe(v)
    count, total, p50, p99 = h.snapshot()
    assert (count, total) == (4, 10.0)
    assert p50 == 2.0 and p99 == 4.0
    h.add(5.0)  # .add aliases .observe (drop-in for utils.profiling.timed)
    assert h.count == 5


def test_registry_duplicate_and_collision_errors():
    reg = Registry()
    c1 = reg.counter("dup_total", "first")
    # Same spec: idempotent — the SAME instrument comes back.
    assert reg.counter("dup_total") is c1
    # Different kind under the same name: loud error.
    with pytest.raises(ValueError, match="already registered"):
        reg.gauge("dup_total")
    # Same kind, different label set: loud error.
    with pytest.raises(ValueError, match="already registered"):
        reg.counter("dup_total", labelnames=("pool",))
    # Histogram window size is part of the spec too.
    reg.histogram("dup_seconds", window=64)
    assert reg.histogram("dup_seconds", window=64) is not None
    with pytest.raises(ValueError, match="already registered"):
        reg.histogram("dup_seconds", window=128)
    # Invalid metric / label names: rejected at registration.
    with pytest.raises(ValueError, match="invalid metric name"):
        reg.counter("bad name")
    with pytest.raises(ValueError, match="invalid label name"):
        reg.counter("ok_total", labelnames=("bad-label",))


def test_label_set_binding_and_collisions():
    reg = Registry()
    c = reg.counter("lbl_total", "labelled", labelnames=("pool",))
    c.labels(pool="native").inc(2)
    c.labels(pool="python").inc(1)
    # Same label values -> same cell.
    assert c.labels(pool="native").value == 2.0
    # Wrong / missing / extra label names: loud errors.
    with pytest.raises(ValueError, match="do not match"):
        c.labels(wrong="x")
    with pytest.raises(ValueError, match="do not match"):
        c.labels()
    with pytest.raises(ValueError, match="do not match"):
        c.labels(pool="native", extra="y")
    # Unlabeled shortcut on a labelled instrument: loud error.
    with pytest.raises(ValueError, match="declares labels"):
        c.inc()
    scalars = reg.scalars()
    assert scalars["lbl_total{pool=native}"] == 2.0
    assert scalars["lbl_total{pool=python}"] == 1.0


def test_prometheus_text_and_json_snapshot():
    reg = Registry()
    reg.counter("t_total", "help text").inc(3)
    reg.gauge("t_gauge").set(1.5)
    h = reg.histogram("t_lat_seconds", labelnames=("pool",))
    h.labels(pool="native").observe(0.5)
    text = reg.prometheus_text()
    assert "# HELP t_total help text" in text
    assert "# TYPE t_total counter" in text
    assert "t_total 3" in text
    assert "t_gauge 1.5" in text
    assert "# TYPE t_lat_seconds summary" in text
    assert 't_lat_seconds{pool="native",quantile="0.5"} 0.5' in text
    assert 't_lat_seconds_count{pool="native"} 1' in text
    snap = reg.snapshot()
    json.dumps(snap)  # JSON-able
    assert snap["t_total"]["kind"] == "counter"
    assert snap["t_lat_seconds"]["samples"][0]["labels"] == {"pool": "native"}


def test_gauge_callback_failure_is_nan_not_crash():
    reg = Registry()

    def boom():
        raise RuntimeError("dead service")

    reg.gauge("g_live").set_fn(boom)
    assert np.isnan(reg.scalars()["g_live"])
    assert "NaN" in reg.prometheus_text()


# ------------------------------------------------------------------ exporter
def test_exporter_serves_text_json_health_and_404():
    reg = Registry()
    reg.counter("exp_total").inc(5)
    ex = obs.MetricsExporter(reg, port=0)
    try:
        base = f"http://127.0.0.1:{ex.port}"
        text = urllib.request.urlopen(f"{base}/metrics").read().decode()
        assert "exp_total 5" in text
        snap = json.loads(
            urllib.request.urlopen(f"{base}/metrics.json").read()
        )
        assert snap["exp_total"]["samples"][0]["value"] == 5.0
        assert (
            urllib.request.urlopen(f"{base}/healthz").read() == b"ok\n"
        )
        with pytest.raises(urllib.error.HTTPError):
            urllib.request.urlopen(f"{base}/nope")
    finally:
        ex.stop()


def test_start_exporter_is_a_process_singleton():
    first = obs.start_exporter(0)
    try:
        assert obs.start_exporter(0) is first
        assert obs.current_exporter() is first
    finally:
        obs.stop_exporter()
    assert obs.current_exporter() is None


# ------------------------------------------------------------ flight recorder
def test_flight_recorder_ring_bound_and_dump(tmp_path):
    fr = obs.FlightRecorder(capacity=4)
    for i in range(10):
        fr.record("tick", i=i)
    events = fr.events()
    assert len(events) == 4  # bounded ring: oldest fell off
    assert [e["i"] for e in events] == [6, 7, 8, 9]
    assert fr.recorded_total == 10
    assert all(
        {"kind", "t_wall", "t_mono", "seq", "thread"} <= set(e) for e in events
    )
    path = str(tmp_path / "sub" / "flight.jsonl")  # dir created on demand
    assert fr.dump(path) == path
    lines = [json.loads(l) for l in open(path)]
    assert [e["i"] for e in lines] == [6, 7, 8, 9]
    # No installed path and no argument: dump is a no-op, not a crash.
    assert obs.FlightRecorder().dump() is None


def test_flight_event_goes_to_process_recorder():
    fr = obs.get_flight_recorder()
    before = fr.recorded_total
    obs.flight_event("unit_test_marker", x=1)
    assert fr.recorded_total == before + 1
    assert fr.events()[-1]["kind"] == "unit_test_marker"


# ------------------------------------------------------------------ watchdog
def _watchdog(**kw):
    return obs.DivergenceWatchdog(
        obs.WatchdogConfig(**kw),
        registry=Registry(),
        recorder=obs.FlightRecorder(),
    )


def test_watchdog_trips_on_nan_and_inf():
    wd = _watchdog()
    wd.check(1, {"critic_loss": 0.5, "grad_norm": 1.0})  # finite: no trip
    with pytest.raises(obs.DivergenceError, match="non-finite"):
        wd.check(2, {"critic_loss": float("nan")})
    with pytest.raises(obs.DivergenceError, match="non-finite"):
        wd.check(3, {"q_mean": float("inf")})


def test_watchdog_trips_on_norm_thresholds_and_records_flight():
    rec = obs.FlightRecorder()
    wd = obs.DivergenceWatchdog(
        obs.WatchdogConfig(grad_norm_max=10.0, param_norm_max=100.0),
        registry=Registry(),
        recorder=rec,
    )
    wd.check(1, {"grad_norm": 9.9, "param_norm": 99.0})
    with pytest.raises(obs.DivergenceError, match="grad_norm"):
        wd.check(2, {"grad_norm": 11.0})
    with pytest.raises(obs.DivergenceError, match="param_norm"):
        wd.check(3, {"param_norm": 101.0})
    kinds = [e["kind"] for e in rec.events()]
    assert kinds.count("watchdog_trip") == 2
    err = None
    try:
        wd.check(4, {"critic_loss": float("nan")})
    except obs.DivergenceError as e:
        err = e
    assert err is not None and err.step == 4
    # The trip event's scalars must be JSON-able even with NaN inside.
    json.dumps(rec.events()[-1])


# ----------------------------------------------------------- profiling.timed
def test_timed_feeds_histograms_and_windows():
    """utils.profiling.timed accepts anything with .add — both the raw
    PercentileWindow and an obs Histogram (the hybrid trainer's host-step
    timing uses it against a registry histogram)."""
    from r2d2dpg_tpu.utils.profiling import timed

    h = Registry().histogram("timed_seconds")
    w = PercentileWindow()
    with timed(h):
        pass
    with timed(w):
        pass
    assert h.count == 1 and h.total >= 0.0
    assert w.count == 1


# ------------------------------------------------- PercentileWindow edge cases
def test_percentile_window_of_one():
    w = PercentileWindow(size=1)
    w.add(3.0)
    w.add(7.0)  # evicts 3.0
    assert w.percentiles((0.0, 50.0, 100.0)) == (7.0, 7.0, 7.0)
    count, total, p50, p99 = w.snapshot()
    assert count == 2  # lifetime count survives eviction
    assert total == 10.0  # lifetime total too
    assert p50 == 7.0 and p99 == 7.0


def test_percentile_window_q0_and_q100_nearest_rank():
    w = PercentileWindow(size=8)
    for v in (5.0, 1.0, 3.0, 2.0, 4.0):
        w.add(v)
    # Nearest-rank: q=0 clamps to the minimum, q=100 is the maximum.
    assert w.percentiles((0.0,)) == (1.0,)
    assert w.percentiles((100.0,)) == (5.0,)
    assert w.percentiles((50.0,)) == (3.0,)
    # Empty window: zeros, not an exception.
    assert PercentileWindow().percentiles((0.0, 100.0)) == (0.0, 0.0)
    assert PercentileWindow().snapshot() == (0, 0.0, 0.0, 0.0)


def test_percentile_window_eviction_past_maxlen():
    w = PercentileWindow(size=4)
    for v in range(10):  # 0..9; window keeps 6,7,8,9
        w.add(float(v))
    assert w.percentiles((0.0, 100.0)) == (6.0, 9.0)
    count, total, p50, p99 = w.snapshot()
    assert count == 10 and total == 45.0  # lifetime, not windowed
    assert p50 == 7.0 and p99 == 9.0
    w.reset()
    assert w.snapshot() == (0, 0.0, 0.0, 0.0)


def test_percentile_window_invalid_size():
    with pytest.raises(ValueError):
        PercentileWindow(size=0)


# ------------------------------------------------------- MetricLogger: threads
def test_metric_logger_two_thread_hammer(tmp_path):
    """The pipelined executor's learner thread and the serving health
    logger interleave log() calls; without the lock this corrupted the
    CSV writer state (satellite #1)."""
    logdir = str(tmp_path / "hammer")
    log = MetricLogger(logdir, stdout=False, tensorboard=False)
    n, errs = 200, []

    def worker(tag):
        try:
            for i in range(n):
                row = {f"{tag}": float(i)}
                if i == 50:  # force a mid-run header change per thread
                    row[f"{tag}_extra"] = 1.0
                log.log(i, row)
                log.rates(**{f"{tag}_count": float(i)})
        except Exception as e:  # pragma: no cover - the failure under test
            errs.append(e)

    threads = [
        threading.Thread(target=worker, args=(t,)) for t in ("a", "b")
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    log.close()
    assert not errs
    with open(os.path.join(logdir, "metrics.csv")) as f:
        rows = list(csv.DictReader(f))
    assert len(rows) == 2 * n
    fields = set(rows[-1].keys())
    assert {"a", "b", "a_extra", "b_extra"} <= fields


# -------------------------------------------------- MetricLogger: append-only
def test_metric_logger_appends_without_rewrite(tmp_path, monkeypatch):
    """satellite #2: the CSV is rewritten ONLY when the header changes;
    steady-state logging appends (the old code re-read + re-wrote the whole
    file on every (re)open — O(rows^2) over a long run)."""
    logdir = str(tmp_path / "run")
    calls = []
    orig = MetricLogger._reopen_csv
    monkeypatch.setattr(
        MetricLogger,
        "_reopen_csv",
        lambda self, row: (calls.append(1), orig(self, row))[1],
    )
    with MetricLogger(logdir, stdout=False, tensorboard=False) as log:
        for i in range(50):
            log.log(i, {"a": float(i)})
        assert len(calls) == 1  # first open only
        log.log(50, {"a": 1.0, "b": 2.0})  # header change: one rewrite
        assert len(calls) == 2
        for i in range(51, 60):
            log.log(i, {"a": 1.0, "b": 2.0})
        assert len(calls) == 2  # steady state: appends

    csv_path = os.path.join(logdir, "metrics.csv")
    # Plant a text marker a rewrite would normalize away ("2.0" -> "2.00"):
    # a resume that APPENDS must leave the existing bytes untouched.
    content = open(csv_path).read()
    open(csv_path, "w").write(content.replace("2.0", "2.00", 1))
    with MetricLogger(logdir, stdout=False, tensorboard=False) as log:
        log.log(60, {"a": 9.0, "b": 9.0})
    assert "2.00" in open(csv_path).read()
    rows = list(csv.DictReader(open(csv_path)))
    assert len(rows) == 61 and rows[-1]["a"] == "9.0"


def test_metric_logger_registry_bridge(tmp_path):
    """Registry scalars fold into rows as EXTRA columns; explicit scalars
    win name collisions, so the canonical curves are unchanged."""
    reg = Registry()
    reg.gauge("bridge_gauge").set(5.0)
    reg.counter("episode_return_mean").inc(99)  # collides with a real key
    logdir = str(tmp_path / "run")
    with MetricLogger(
        logdir, stdout=False, tensorboard=False, registry=reg
    ) as log:
        log.log(1, {"episode_return_mean": 1.5})
    rows = list(csv.DictReader(open(os.path.join(logdir, "metrics.csv"))))
    assert rows[0]["bridge_gauge"] == "5.0"
    assert rows[0]["episode_return_mean"] == "1.5"  # explicit key won


# ------------------------------------------------------------------ lint gate
def test_lint_obs_clean():
    """scripts/lint_obs.sh: no bare print( in library code (CLI
    entrypoints and annotated sinks excepted)."""
    res = subprocess.run(
        ["bash", os.path.join(REPO, "scripts", "lint_obs.sh")],
        capture_output=True,
        text=True,
    )
    assert res.returncode == 0, res.stdout + res.stderr


def test_lint_obs_catches_offender(tmp_path):
    """The gate actually bites: a copy of the tree with a bare print(
    planted in library code must fail."""
    import shutil

    tree = tmp_path / "repo"
    (tree / "scripts").mkdir(parents=True)
    shutil.copy(
        os.path.join(REPO, "scripts", "lint_obs.sh"), tree / "scripts"
    )
    pkg = tree / "r2d2dpg_tpu"
    pkg.mkdir()
    (pkg / "offender.py").write_text('print("operator-invisible")\n')
    res = subprocess.run(
        ["bash", str(tree / "scripts" / "lint_obs.sh")],
        capture_output=True,
        text=True,
    )
    assert res.returncode == 1
    assert "offender.py" in res.stdout


# ------------------------------------------------------- serving integration
def test_health_snapshot_publish_refits_onto_registry():
    from r2d2dpg_tpu.serving.health import HealthSnapshot

    reg = Registry()
    snap = HealthSnapshot(
        queue_depth=3,
        batch_occupancy=0.5,
        latency_p50_ms=1.0,
        latency_p99_ms=2.0,
        step_p50_ms=0.5,
        step_p99_ms=0.9,
        params_step=17,
        params_staleness_s=4.0,
        requests_ok=100,
        requests_shed=2,
        sessions_active=5,
        sessions_evicted=1,
    )
    snap.publish(reg)
    scalars = reg.scalars()
    assert scalars["r2d2dpg_serving_queue_depth"] == 3.0
    assert scalars["r2d2dpg_serving_params_step"] == 17.0
    # Every as_scalars field made it across.
    for k in snap.as_scalars():
        assert f"r2d2dpg_serving_{k}" in scalars


# ------------------------------------------------------ env-pool integration
def test_host_pool_step_registers_envpool_instruments():
    """The dm_control fleet feeds the pool="python" label set: step
    latency + lock-wait histograms and the resets counter all move.
    Instruments bind LAZILY on the first step (so a pool whose role
    arrives after construction never registers a phantom role="train"
    cell); assertions skip when this container cannot load dm_control
    physics (no EGL — a known environment gap)."""
    pytest.importorskip("dm_control")
    from r2d2dpg_tpu.envs.dmc_host import _HostPool

    reg = obs.get_registry()
    pool = _HostPool("walker", "walk", pixels=False, camera_id=0)
    try:
        pool.reset_all(np.arange(2))
        pool.step_all(np.zeros((2, 6), np.float32))  # binds instruments
    except Exception as e:  # pragma: no cover - container-dependent
        pytest.skip(f"dm_control env unavailable here: {type(e).__name__}")
    step_h = reg.get("r2d2dpg_envpool_step_seconds").labels(
        pool="python", role="train"
    )
    lock_h = reg.get("r2d2dpg_envpool_lock_wait_seconds").labels(
        pool="python", role="train"
    )
    assert reg.get("r2d2dpg_envpool_resets_total") is not None
    before = step_h.count
    for _ in range(3):
        pool.step_all(np.zeros((2, 6), np.float32))
    assert step_h.count == before + 3
    assert lock_h.count >= 3
    text = reg.prometheus_text()
    assert (
        'r2d2dpg_envpool_step_seconds_count{pool="python",role="train"}'
        in text
    )


def test_host_pool_step_instruments_move_with_stub_envs():
    """Container-independent: drive _HostPool.step_all over stub envs (no
    dm_control physics) and watch the step/lock/reset instruments move."""
    from concurrent.futures import ThreadPoolExecutor

    from r2d2dpg_tpu.envs.dmc_host import _HostPool

    class _Obs(dict):
        pass

    class _Ts:
        def __init__(self, last):
            self.reward = 0.5
            self.discount = 1.0
            self.observation = _Obs(x=np.zeros(3, np.float32))
            self._last = last

        def last(self):
            return self._last

    class _StubEnv:
        def __init__(self):
            self.n = 0

        def step(self, action):
            self.n += 1
            return _Ts(last=(self.n % 2 == 0))  # every 2nd step ends

        def reset(self):
            return _Ts(last=False)

    pool = _HostPool("walker", "walk", pixels=False, camera_id=0)
    pool.envs = [_StubEnv(), _StubEnv()]
    pool.executor = ThreadPoolExecutor(max_workers=2)
    reg = obs.get_registry()
    out = pool.step_all(np.zeros((2, 1), np.float32))  # binds instruments
    step_h = reg.get("r2d2dpg_envpool_step_seconds").labels(
        pool="python", role="train"
    )
    resets = reg.get("r2d2dpg_envpool_resets_total").labels(
        pool="python", role="train"
    )
    s0, r0 = step_h.count, resets.value
    for _ in range(4):
        out = pool.step_all(np.zeros((2, 1), np.float32))
    assert len(out) == 4
    assert step_h.count == s0 + 4
    # Stub episodes end every 2nd step: 2 envs x 2 boundary steps = 4.
    assert resets.value == r0 + 4.0
    pool.executor.shutdown(wait=False)


# ------------------------------------------------------- trainer integration
def test_train_run_with_obs_port_exposes_trainer_and_replay(tmp_path):
    """--obs-port: a phase-locked run registers trainer + replay
    instruments and the exporter serves them as Prometheus text + JSON."""
    from r2d2dpg_tpu.train import parse_args, run

    obs.stop_exporter()  # a fresh singleton for this test
    logdir = str(tmp_path / "log")
    args = parse_args(
        [
            "--config", "pendulum_tiny",
            "--phases", "2",
            "--log-every", "1",
            "--logdir", logdir,
            "--obs-port", "0",
        ]
    )
    try:
        run(args)
        port = int(open(os.path.join(logdir, "obs_port.txt")).read())
        text = (
            urllib.request.urlopen(f"http://127.0.0.1:{port}/metrics")
            .read()
            .decode()
        )
        for family in (
            "r2d2dpg_trainer_env_steps",
            "r2d2dpg_trainer_learner_steps",
            "r2d2dpg_trainer_episodes_total",
            "r2d2dpg_replay_occupancy",
            "r2d2dpg_replay_priority_sum",
            "r2d2dpg_watchdog_checks_total",
        ):
            assert family in text, family
        snap = json.loads(
            urllib.request.urlopen(
                f"http://127.0.0.1:{port}/metrics.json"
            ).read()
        )
        assert snap["r2d2dpg_replay_occupancy"]["samples"][0]["value"] > 0
        # The CSV bridge folded registry columns into the rows.
        rows = list(
            csv.DictReader(open(os.path.join(logdir, "metrics.csv")))
        )
        assert "r2d2dpg_trainer_env_steps" in rows[-1]
        assert "episode_return_mean" in rows[-1]  # curves unchanged
    finally:
        obs.stop_exporter()


def test_nan_injection_trips_watchdog_dumps_flight_and_exits_nonzero(
    tmp_path,
):
    """Acceptance: a forced NaN in a learner update trips the watchdog,
    writes flight.jsonl with the recent event ring, points at the last
    good checkpoint, and exits non-zero — end to end through the CLI."""
    from r2d2dpg_tpu.train import parse_args, run

    logdir = str(tmp_path / "log")
    ckdir = str(tmp_path / "ck")
    args = parse_args(
        [
            "--config", "pendulum_tiny",
            "--phases", "4",
            "--log-every", "1",
            "--logdir", logdir,
            "--checkpoint-dir", ckdir,
            "--checkpoint-every", "1",
            "--nan-inject-phase", "2",
        ]
    )
    with pytest.raises(SystemExit) as exc:
        run(args)
    assert exc.value.code == 2
    flight_path = os.path.join(logdir, "flight.jsonl")
    assert os.path.exists(flight_path)
    events = [json.loads(l) for l in open(flight_path)]
    kinds = [e["kind"] for e in events]
    assert "watchdog_trip" in kinds
    assert "abort" in kinds
    assert "checkpoint_save" in kinds  # the ring kept the save trail
    trip = next(e for e in events if e["kind"] == "watchdog_trip")
    assert "non-finite" in trip["reason"]
    # A checkpoint exists on disk to resume from (the pointer target).
    from r2d2dpg_tpu.utils import CheckpointManager

    ck = CheckpointManager(ckdir)
    assert ck.latest_step is not None
    ck.close()


def test_watchdog_off_flag_does_not_trip(tmp_path):
    from r2d2dpg_tpu.train import parse_args, run

    args = parse_args(
        [
            "--config", "pendulum_tiny",
            "--phases", "3",
            "--log-every", "1",
            "--logdir", str(tmp_path / "log"),
            "--nan-inject-phase", "1",
            "--watchdog", "0",
        ]
    )
    final = run(args)  # completes despite the poison: no watchdog
    assert any(np.isnan(v) for v in final.values() if isinstance(v, float))


def test_pipeline_refuses_nan_injection():
    from r2d2dpg_tpu.train import parse_args, run

    args = parse_args(
        [
            "--config", "pendulum_tiny",
            "--phases", "1",
            "--pipeline", "1",
            "--nan-inject-phase", "1",
        ]
    )
    with pytest.raises(SystemExit, match="nan-inject"):
        run(args)


# ----------------------------------------------------- envpool role label
def test_pool_role_label_separates_instances():
    """satellite: set_role('eval') re-binds a pool's instruments to its own
    role cell, so the evaluator's fleet and the training fleet no longer
    interleave into one distribution."""
    from concurrent.futures import ThreadPoolExecutor

    from r2d2dpg_tpu.envs.dmc_host import _HostPool

    class _Ts:
        def __init__(self):
            self.reward = 0.0
            self.discount = 1.0
            self.observation = {"x": np.zeros(2, np.float32)}

        def last(self):
            return False

    class _StubEnv:
        def step(self, action):
            return _Ts()

        def reset(self):
            return _Ts()

    reg = obs.get_registry()
    pool = _HostPool("walker", "walk", pixels=False, camera_id=0)
    pool.set_role("eval")
    pool.envs = [_StubEnv()]
    pool.executor = ThreadPoolExecutor(max_workers=1)
    pool.step_all(np.zeros((1, 1), np.float32))  # lazy bind: role="eval"
    train_cell = reg.get("r2d2dpg_envpool_step_seconds").labels(
        pool="python", role="train"
    )
    eval_cell = reg.get("r2d2dpg_envpool_step_seconds").labels(
        pool="python", role="eval"
    )
    t0, e0 = train_cell.count, eval_cell.count
    pool.step_all(np.zeros((1, 1), np.float32))
    assert eval_cell.count == e0 + 1
    assert train_cell.count == t0  # the training cell did not move
    pool.executor.shutdown(wait=False)


def test_evaluator_sets_eval_role():
    """The evaluator stamps its (separate) env instance role='eval'."""
    from r2d2dpg_tpu.training.evaluator import Evaluator

    class _RoleEnv:
        batched = True

        def __init__(self):
            self.role = None

        def set_role(self, role):
            self.role = role

    env = _RoleEnv()
    # jax.jit only wraps at construction; the stub actor is never traced.
    Evaluator(env, actor=None, num_envs=1)
    assert env.role == "eval"


# ------------------------------------------------------ exporter hardening
def test_exporter_scrape_survives_raising_gauge():
    """satellite: one bad instrument must not 500 the scrape or kill the
    exporter thread — a raising set_fn renders NaN (value-level guard),
    and an instrument broken at snapshot time is omitted as a comment."""
    reg = Registry()
    reg.counter("good_total").inc(1)

    def boom():
        raise RuntimeError("dead callback")

    reg.gauge("bad_gauge").set_fn(boom)
    broken = reg.gauge("broken_gauge")
    broken.set(1.0)
    broken._cells_snapshot = lambda: (_ for _ in ()).throw(
        RuntimeError("snapshot exploded")
    )
    ex = obs.MetricsExporter(reg, port=0)
    try:
        base = f"http://127.0.0.1:{ex.port}"
        text = urllib.request.urlopen(f"{base}/metrics").read().decode()
        assert "good_total 1" in text  # scrape intact
        assert "bad_gauge NaN" in text  # value-level guard
        assert "# broken_gauge omitted: RuntimeError" in text
        assert "broken_gauge 1" not in text
        # JSON endpoint carries the error entry instead of crashing.
        snap = json.loads(
            urllib.request.urlopen(f"{base}/metrics.json").read()
        )
        assert "snapshot exploded" in snap["broken_gauge"]["error"]
        # The server thread survived: a second scrape still answers.
        assert (
            urllib.request.urlopen(f"{base}/healthz").read() == b"ok\n"
        )
    finally:
        ex.stop()


def test_render_prometheus_isolates_malformed_entries():
    """A malformed (e.g. remote) snapshot entry becomes an omitted-comment
    line; well-formed families render unaffected."""
    snap = {
        "ok_total": {
            "kind": "counter",
            "help": "fine",
            "samples": [{"labels": {}, "value": 2.0}],
        },
        "bad entry name": {"kind": "counter", "samples": []},
        "half_formed": {"kind": "histogram", "samples": [{"labels": {}}]},
    }
    text = obs.render_prometheus(snap)
    assert "ok_total 2" in text
    assert "# bad entry name omitted:" in text
    assert "# half_formed sample omitted: KeyError" in text


def test_render_prometheus_bad_remote_sample_keeps_local_series():
    """One malformed REMOTE sample merged into a healthy local family
    (version-skewed actor) omits only itself — the learner's own local
    samples of that family still render."""
    base = Registry()
    base.histogram("r2d2dpg_envpool_step_seconds").observe(0.5)
    skewed = {
        "r2d2dpg_envpool_step_seconds": {
            "kind": "histogram",
            # A histogram sample missing p99 AND a gauge-shaped sample
            # under a histogram family.
            "samples": [
                {"labels": {}, "count": 1, "total": 0.1, "p50": 0.1},
                {"labels": {}, "value": 3.0},
            ],
        }
    }
    merged = obs.merge_remote(
        base.snapshot(), [("actor:0", {"actor": "0"}, skewed)]
    )
    text = obs.render_prometheus(merged)
    # Local series survive the bad remote samples...
    assert "r2d2dpg_envpool_step_seconds_count 1" in text
    assert 'r2d2dpg_envpool_step_seconds{quantile="0.5"} 0.5' in text
    # ...which are omitted individually, not the whole family.
    assert text.count("# r2d2dpg_envpool_step_seconds sample omitted:") == 2
    assert text.count("# TYPE r2d2dpg_envpool_step_seconds") == 1


def test_merge_remote_forwards_remote_instrument_errors():
    """A remote instrument that failed at snapshot time (Registry.snapshot's
    per-instrument isolation -> an ``error`` entry) must surface in the
    merged scrape as an ATTRIBUTED sample-omitted comment — never vanish,
    and never omit other sources' healthy series sharing the family."""
    base = Registry()
    base.gauge("r2d2dpg_x_gauge").set(1.0)
    broken = {
        # Shares a family with a healthy local series...
        "r2d2dpg_x_gauge": {
            "kind": "gauge",
            "help": "",
            "error": "RuntimeError: boom",
            "samples": [],
        },
        # ...and one that exists ONLY remotely.
        "r2d2dpg_y_gauge": {"kind": "gauge", "error": "dead", "samples": []},
    }
    merged = obs.merge_remote(
        base.snapshot(), [("actor:0", {"actor": "0"}, broken)]
    )
    text = obs.render_prometheus(merged)
    assert "r2d2dpg_x_gauge 1" in text  # local series survives
    assert "# r2d2dpg_x_gauge sample omitted:" in text
    assert "boom" in text and 'actor="0"' in text  # attributed, visible
    assert "# r2d2dpg_y_gauge sample omitted:" in text
    assert "dead" in text


def test_render_prometheus_neutralizes_newlines_from_remote_strings():
    """Remote-supplied names/label keys/values with embedded newlines must
    not tear the exposition into forged lines: values get the ``\\n``
    escape, bad names/keys become single-line omitted comments."""
    snap = {
        "bad\nname_total": {
            "kind": "counter",
            "samples": [{"labels": {}, "value": 1.0}],
        },
        "r2d2dpg_ok_gauge": {
            "kind": "gauge",
            "samples": [
                {"labels": {"host": "h1\nup 1"}, "value": 2.0},
                {"labels": {"bad\nkey": "v"}, "value": 3.0},
            ],
        },
    }
    text = obs.render_prometheus(snap)
    # Every line is either a comment or a well-formed ok_gauge sample —
    # no forged "up 1" series line ever appears.
    assert "up 1" not in text.splitlines()
    for line in text.splitlines():
        assert line.startswith("#") or line.startswith("r2d2dpg_ok_gauge")
    assert 'host="h1\\nup 1"' in text  # value escaped, not emitted raw
    assert "# bad name_total omitted:" in text  # name flattened to one line
    assert "# r2d2dpg_ok_gauge sample omitted:" in text  # bad label key
    assert 'r2d2dpg_ok_gauge{host="h1\\nup 1"} 2' in text


# ----------------------------------------------------- remote mirror (leg 1)
def test_remote_mirror_update_is_idempotent_and_tracks_staleness():
    m = obs.RemoteMirror()
    reg = Registry()
    reg.counter("r2d2dpg_actor_phases_total").inc(3)
    m.update("actor:0", {"actor": "0"}, reg.snapshot())
    m.update("actor:0", {"actor": "0"}, reg.snapshot())  # reconnect: same slot
    assert len(m.sources()) == 1
    assert m.staleness_s("actor:0") is not None
    assert m.staleness_s("actor:0") < 5.0
    assert m.staleness_s("actor:9") is None
    with pytest.raises(TypeError):
        m.update("actor:1", {}, "not a snapshot")
    m.drop("actor:0")
    assert m.sources() == []


def test_merge_remote_attribution_labels_win():
    base = Registry()
    base.counter("r2d2dpg_fleet_frames_total", labelnames=("actor",)).labels(
        actor="learner-side"
    ).inc(1)
    remote = Registry()
    remote.counter("r2d2dpg_actor_phases_total").inc(7)
    remote.gauge("r2d2dpg_x_gauge", labelnames=("actor",)).labels(
        actor="lying"
    ).set(1.0)
    merged = obs.merge_remote(
        base.snapshot(), [("actor:0", {"actor": "0", "host": "h1"}, remote.snapshot())]
    )
    text = obs.render_prometheus(merged)
    # Remote unlabelled series gain the attribution labels...
    assert 'r2d2dpg_actor_phases_total{actor="0",host="h1"} 7' in text
    # ...and the aggregator's labels WIN a collision (who-reported truth).
    assert 'r2d2dpg_x_gauge{actor="0",host="h1"} 1' in text
    # Base samples are untouched, one TYPE line per family.
    assert 'r2d2dpg_fleet_frames_total{actor="learner-side"} 1' in text
    assert text.count("# TYPE r2d2dpg_fleet_frames_total") == 1


def test_exporter_merges_mirror_sources():
    reg = Registry()
    reg.counter("local_total").inc(1)
    remote = Registry()
    remote.counter("r2d2dpg_actor_phases_total").inc(5)
    mirror = obs.RemoteMirror()
    mirror.update("actor:1", {"actor": "1"}, remote.snapshot())
    ex = obs.MetricsExporter(reg, port=0, mirror=mirror)
    try:
        text = (
            urllib.request.urlopen(f"http://127.0.0.1:{ex.port}/metrics")
            .read()
            .decode()
        )
        assert "local_total 1" in text
        assert 'r2d2dpg_actor_phases_total{actor="1"} 5' in text
    finally:
        ex.stop()


def test_allgather_into_mirror_single_process_is_noop():
    m = obs.RemoteMirror()
    assert obs.allgather_into_mirror(Registry(), m) == 0
    assert m.sources() == []


# ------------------------------------------------------------ trace (leg 2)
def test_trace_sampling_and_hop_recording():
    from r2d2dpg_tpu.obs import trace as obs_trace

    assert obs_trace.maybe_start(0.0) is None  # default: literally nothing
    tr = obs_trace.maybe_start(1.0)
    assert tr is not None and tr.t_collect_start > 0
    with pytest.raises(ValueError, match="unknown trace hop"):
        obs_trace.hop_histogram("teleport")
    fr = obs.get_flight_recorder()
    n0 = len(fr.spans())
    dur = obs_trace.record_hop("collect", 10.0, 10.5, tr.trace_id, actor="0")
    assert dur == 0.5
    # Clock skew across processes clamps at zero, never negative.
    assert obs_trace.record_hop("transit", 11.0, 10.9, tr.trace_id) == 0.0
    spans = fr.spans()
    assert len(spans) == n0 + 2
    assert spans[-2]["hop"] == "collect" and spans[-2]["actor"] == "0"
    hist = obs.get_registry().get("r2d2dpg_trace_collect_seconds")
    assert hist is not None and hist.count >= 1


def test_flight_dump_trace_chrome_format(tmp_path):
    fr = obs.FlightRecorder()
    assert fr.dump_trace() is None  # nothing armed, nothing recorded
    fr.record_span("collect", 7, 100.0, 0.25, actor="0")
    fr.record_span("learn", 7, 100.5, 0.1)
    path = str(tmp_path / "trace.json")
    assert fr.dump_trace(path) == path
    doc = json.loads(open(path).read())
    names = [e["name"] for e in doc["traceEvents"]]
    assert names == ["collect", "learn"]  # t_wall-ordered
    ev = doc["traceEvents"][0]
    assert ev["ph"] == "X" and ev["ts"] == 100.0 * 1e6
    assert ev["dur"] == 0.25 * 1e6 and ev["tid"] == 7
    assert ev["args"]["actor"] == "0"
    # install() arms trace.json NEXT TO the flight path.
    fr2 = obs.FlightRecorder()
    fr2.install(str(tmp_path / "run" / "flight.jsonl"))
    fr2.record_span("decode", 1, 1.0, 0.1)
    assert fr2.dump_trace() == str(tmp_path / "run" / "trace.json")


# ------------------------------------------------------- flight merge tool
def test_flight_merge_tool_interleaves_by_t_wall(tmp_path):
    """satellite: `python -m r2d2dpg_tpu.obs.flight merge <dir>` replaces
    the docs' manual cat|sort recipe — one attributable fleet timeline."""
    from r2d2dpg_tpu.obs import flight as flight_mod

    d = tmp_path / "run"
    d.mkdir()
    (d / "flight.jsonl").write_text(
        json.dumps({"kind": "a", "t_wall": 2.0, "process_index": 0}) + "\n"
        + json.dumps({"kind": "c", "t_wall": 4.0, "process_index": 0}) + "\n"
    )
    (d / "flight_actor0.jsonl").write_text(
        "garbage-line\n"
        + json.dumps({"kind": "b", "t_wall": 3.0, "actor": 0}) + "\n"
        + json.dumps({"kind": "z", "t_wall": 1.0, "actor": 0}) + "\n"
    )
    paths = flight_mod.expand_flight_paths([str(d)])
    assert [os.path.basename(p) for p in paths] == [
        "flight.jsonl", "flight_actor0.jsonl",
    ]
    merged, skipped = flight_mod.merge_flight_files(paths)
    assert [e["kind"] for e in merged] == ["z", "a", "b", "c"]
    assert skipped == 1  # the garbage line is counted, not silently lost
    assert merged[0]["file"] == "flight_actor0.jsonl"  # attribution stamp
    out = str(tmp_path / "merged.jsonl")
    flight_mod.main(["merge", str(d), "-o", out])
    lines = [json.loads(l) for l in open(out)]
    assert [e["kind"] for e in lines] == ["z", "a", "b", "c"]
    # The module CLI entry point works end to end.
    env = {
        **os.environ,
        "JAX_PLATFORMS": "cpu",
    }
    res = subprocess.run(
        ["python", "-m", "r2d2dpg_tpu.obs.flight", "merge", str(d)],
        capture_output=True,
        text=True,
        cwd=REPO,
        env=env,
    )
    assert res.returncode == 0, res.stderr
    assert [json.loads(l)["kind"] for l in res.stdout.splitlines()] == [
        "z", "a", "b", "c",
    ]


def test_flight_merge_run_dir_discovers_trace_dumps_and_fuses(tmp_path):
    """ISSUE 13 satellite: a run DIRECTORY is a complete merge argument —
    flight*.jsonl dumps for the event timeline, and with ``--trace-out``
    every span dump too (the learner's Chrome-format trace.json AND the
    shard procs' raw trace_shard*.jsonl rings), fused into ONE Perfetto
    document with per-span ``file`` source stamps."""
    from r2d2dpg_tpu.obs import flight as flight_mod

    d = tmp_path / "run"
    d.mkdir()
    (d / "flight.jsonl").write_text(
        json.dumps({"kind": "a", "t_wall": 1.0}) + "\n"
    )
    (d / "flight_shard0.jsonl").write_text(
        json.dumps({"kind": "b", "t_wall": 2.0, "shard_proc": 0}) + "\n"
    )
    # The learner's already-rendered Chrome doc (dump_trace output)...
    (d / "trace.json").write_text(
        json.dumps(
            flight_mod.chrome_trace(
                [
                    {
                        "hop": "sample_req",
                        "trace_id": 7,
                        "t_wall": 10.0,
                        "dur_s": 0.5,
                        "pid": 100,
                    }
                ]
            )
        )
    )
    # ...and a shard proc's raw span ring, plus one garbage line.
    (d / "trace_shard0.jsonl").write_text(
        json.dumps(
            {
                "hop": "shard_draw",
                "trace_id": 7,
                "t_wall": 10.1,
                "dur_s": 0.2,
                "pid": 200,
                "shard": 0,
            }
        )
        + "\n"
        + "garbage\n"
    )
    # flight*.jsonl discovery picks up the shard dump beside the
    # learner's (the satellite: no more enumerating files by hand).
    paths = flight_mod.expand_flight_paths([str(d)])
    assert [os.path.basename(p) for p in paths] == [
        "flight.jsonl",
        "flight_shard0.jsonl",
    ]
    tpaths = flight_mod.expand_trace_paths([str(d)])
    assert sorted(os.path.basename(p) for p in tpaths) == [
        "trace.json",
        "trace_shard0.jsonl",
    ]
    spans, skipped = flight_mod.load_spans(tpaths)
    assert skipped == 1  # the garbage line is counted, never silent
    assert [s["hop"] for s in spans] == ["sample_req", "shard_draw"]
    # Source stamps: which dump each span came from survives the fuse.
    assert [s["file"] for s in spans] == ["trace.json", "trace_shard0.jsonl"]
    # The Chrome doc round-trips: ts/dur invert back to seconds exactly.
    assert spans[0]["t_wall"] == 10.0 and spans[0]["dur_s"] == 0.5
    out = d / "fused.json"
    merged_out = d / "merged.jsonl"
    flight_mod.main(
        ["merge", str(d), "-o", str(merged_out), "--trace-out", str(out)]
    )
    fused = json.loads(out.read_text())
    assert [e["name"] for e in fused["traceEvents"]] == [
        "sample_req",
        "shard_draw",
    ]
    assert all(e["ph"] == "X" for e in fused["traceEvents"])
    assert fused["traceEvents"][1]["args"]["file"] == "trace_shard0.jsonl"
    assert fused["traceEvents"][1]["args"]["shard"] == 0
    # Both products from one invocation: the event timeline still merged.
    kinds = [json.loads(l)["kind"] for l in open(merged_out)]
    assert kinds == ["a", "b"]
    # A traced-but-undumped dir refuses loudly instead of writing an
    # empty timeline.
    empty = tmp_path / "empty"
    empty.mkdir()
    with pytest.raises(SystemExit, match="no spans"):
        flight_mod.main(
            ["merge", str(empty), "--trace-out", str(tmp_path / "x.json")]
        )
    # Writing the fused doc INTO the scanned run dir under a trace* name
    # must not re-ingest it on the next run (every span would duplicate):
    # the output carries the fusedBy marker, and marked files are
    # excluded from span discovery.
    fused_in_dir = d / "trace_merged.json"
    flight_mod.main(["merge", str(d), "--trace-out", str(fused_in_dir)])
    n_first = len(json.loads(fused_in_dir.read_text())["traceEvents"])
    assert "fusedBy" in json.loads(fused_in_dir.read_text())
    flight_mod.main(["merge", str(d), "--trace-out", str(fused_in_dir)])
    assert (
        len(json.loads(fused_in_dir.read_text())["traceEvents"]) == n_first
    )
    # A marked fused doc is never a SOURCE even under a different output
    # name: fusing the same dir again elsewhere must not re-ingest it.
    other_out = d / "trace_fused_b.json"
    flight_mod.main(["merge", str(d), "--trace-out", str(other_out)])
    assert (
        len(json.loads(other_out.read_text())["traceEvents"]) == n_first
    )
    # But a REAL span dump at the target (no marker — e.g. the learner's
    # trace.json) must never be silently excluded and clobbered.
    with pytest.raises(SystemExit, match="overwrite an existing span dump"):
        flight_mod.main(["merge", str(d), "--trace-out", str(d / "trace.json")])
    assert "fusedBy" not in json.loads((d / "trace.json").read_text())


def test_load_spans_counts_malformed_chrome_event(tmp_path):
    """A Chrome event with a non-numeric ts/dur/tid (truncated, foreign,
    or version-skewed dump) is ONE bad event for the skipped tally — it
    parses as valid JSON, so it must be caught past the json.loads guard,
    never crash the whole merge."""
    from r2d2dpg_tpu.obs import flight as flight_mod

    doc = {
        "traceEvents": [
            {"ph": "X", "name": "learn", "ts": "n/a", "dur": 1, "pid": 1},
            {
                "ph": "X",
                "name": "learn",
                "ts": 2.0,
                "dur": 1.0,
                "tid": 1,
                "pid": 1,
                "args": {"trace_id": 5},
            },
        ]
    }
    p = tmp_path / "trace.json"
    p.write_text(json.dumps(doc))
    spans, skipped = flight_mod.load_spans([str(p)])
    assert [s["hop"] for s in spans] == ["learn"] and skipped == 1


def test_flight_merge_explicit_trace_file_args_route_to_span_loader(
    tmp_path,
):
    """An explicitly-named trace*.jsonl arg is a SPAN source: it feeds the
    --trace-out fuse, never the event merge (a span line parses as a
    valid event dict and would silently pollute the timeline), and naming
    one without --trace-out refuses instead of ignoring it."""
    from r2d2dpg_tpu.obs import flight as flight_mod

    d = tmp_path / "run"
    d.mkdir()
    (d / "flight.jsonl").write_text(
        json.dumps({"kind": "a", "t_wall": 1.0}) + "\n"
    )
    (d / "trace_shard0.jsonl").write_text(
        json.dumps(
            {
                "hop": "shard_draw",
                "trace_id": 3,
                "t_wall": 5.0,
                "dur_s": 0.1,
                "pid": 200,
            }
        )
        + "\n"
    )
    out = tmp_path / "fused.json"
    merged_out = tmp_path / "merged.jsonl"
    # File-only invocation: the span dump was NAMED, so the fuse must
    # consume it even though no directory arg was given...
    flight_mod.main(
        [
            "merge",
            str(d / "flight.jsonl"),
            str(d / "trace_shard0.jsonl"),
            "-o", str(merged_out),
            "--trace-out", str(out),
        ]
    )
    fused = json.loads(out.read_text())
    assert [e["name"] for e in fused["traceEvents"]] == ["shard_draw"]
    # ...and the event timeline must NOT contain the span as a bogus
    # no-kind event.
    events = [json.loads(l) for l in open(merged_out)]
    assert [e["kind"] for e in events] == ["a"]
    # A span dump without --trace-out is a refusal, not a silent drop.
    with pytest.raises(SystemExit, match="span sources"):
        flight_mod.main(["merge", str(d / "trace_shard0.jsonl")])
    # A dump named BOTH explicitly and via its run dir feeds the fusion
    # once (abspath dedup), never as duplicate lanes.
    out2 = tmp_path / "fused_dedup.json"
    flight_mod.main(
        [
            "merge",
            str(d),
            str(d / "trace_shard0.jsonl"),
            "--trace-out", str(out2),
        ]
    )
    names = [
        e["name"] for e in json.loads(out2.read_text())["traceEvents"]
    ]
    assert names == ["shard_draw"]


# --------------------------------------------------------- /health verdicts
def _snap_engine(**config):
    reg = Registry()
    engine = obs.HealthEngine(
        obs.HealthConfig(**config), registry=reg, mirror=None
    )
    return reg, engine


def test_health_engine_ok_and_learner_starving():
    reg, engine = _snap_engine(learner_wait_p99_s=0.5)
    res = engine.evaluate()
    assert res["verdict"] == "ok" and res["findings"] == []
    # An empty histogram (count 0) is absence of evidence, not starving.
    reg.histogram("r2d2dpg_sampler_wait_seconds")
    assert engine.evaluate()["verdict"] == "ok"
    reg.get("r2d2dpg_sampler_wait_seconds").observe(2.0)
    res = engine.evaluate()
    assert res["verdict"] == "degraded"
    assert [f["rule"] for f in res["findings"]] == ["learner_starving"]
    assert res["findings"][0]["value"] == 2.0
    # The verdict itself is on the scrape, zeros included.
    assert reg.get("r2d2dpg_health_status").value == 1.0
    firing = reg.get("r2d2dpg_health_rule_firing")
    assert firing.labels(rule="learner_starving").value == 1.0
    assert firing.labels(rule="telem_stale").value == 0.0


def test_health_engine_telem_stale_skew_and_churn():
    reg, engine = _snap_engine(
        telem_stale_after_s=10.0,
        eviction_churn_per_s=50.0,
        occupancy_skew_min_mean=64.0,
        # Drill the rate math itself; the burst-vs-poll-gap guard has its
        # own test below.
        eviction_rate_min_dt_s=0.0,
    )
    # Staleness over threshold, actor- and shard-flavored.
    reg.gauge(
        "r2d2dpg_shard_telem_staleness_seconds", labelnames=("shard",)
    ).labels(shard="1").set(99.0)
    reg.gauge(
        "r2d2dpg_fleet_telem_staleness_seconds", labelnames=("actor",)
    ).labels(actor="0").set(11.0)
    res = engine.evaluate()
    details = sorted(
        f["detail"] for f in res["findings"] if f["rule"] == "telem_stale"
    )
    assert len(details) == 2
    assert "actor 0" in details[0] and "shard 1" in details[1]
    # Shard skew: one shard empty while the tier holds real data —
    # but NOT during warm-up (mean below the floor).
    occ = reg.gauge(
        "r2d2dpg_replay_shard_occupancy", labelnames=("shard",)
    )
    occ.labels(shard="0").set(0.0)
    occ.labels(shard="1").set(10.0)  # mean 5 < 64: warm-up, no finding
    assert not [
        f
        for f in engine.evaluate()["findings"]
        if f["rule"] == "shard_skew"
    ]
    occ.labels(shard="1").set(500.0)
    assert [
        f
        for f in engine.evaluate()["findings"]
        if f["rule"] == "shard_skew"
    ]
    # Eviction churn is a RATE over successive evaluations.
    ev = reg.counter(
        "r2d2dpg_replay_shard_evictions_total", labelnames=("shard",)
    ).labels(shard="0")
    engine.evaluate()  # first sighting: baseline, no rate yet
    import time as _time

    _time.sleep(0.02)
    ev.inc(1e6)
    assert [
        f
        for f in engine.evaluate()["findings"]
        if f["rule"] == "eviction_churn"
    ]


def test_health_engine_eviction_churn_ignores_sub_window_poll_gaps():
    """FIFO evictions land in whole-batch bursts: a burst divided by a
    sub-second gap between two /health polls is not a sustained rate —
    closely spaced evaluations re-judge the last FULL window instead of
    flapping the verdict on a non-event."""
    reg, engine = _snap_engine(
        eviction_churn_per_s=50.0, eviction_rate_min_dt_s=5.0
    )
    ev = reg.counter(
        "r2d2dpg_replay_shard_evictions_total", labelnames=("shard",)
    ).labels(shard="0")
    engine.evaluate()  # baseline window opens
    ev.inc(64)  # one whole-batch FIFO burst...
    # ...and an operator curl racing the autoscaler poll 20ms later:
    # 64/0.02s = 3200/s >> 50/s, but the window is far below min dt.
    assert not [
        f
        for f in engine.evaluate()["findings"]
        if f["rule"] == "eviction_churn"
    ]


def test_health_engine_serve_queue_saturated_warmup_exempt_per_worker():
    """serve_queue_saturated judges each routed worker against ITS
    admission bound, but only after that worker has served >= 1 request
    — admission legitimately piles while the first bucket compiles."""
    reg, engine = _snap_engine(serve_queue_saturated_frac=0.9)
    # No routed serving workers in this process: rule disarmed.
    assert engine.evaluate()["verdict"] == "ok"
    depth = reg.gauge("r2d2dpg_serve_queue_depth", labelnames=("worker",))
    limit = reg.gauge("r2d2dpg_serve_queue_limit", labelnames=("worker",))
    served = reg.counter(
        "r2d2dpg_serve_requests_total", labelnames=("worker",)
    )
    depth.labels(worker="0").set(95.0)
    limit.labels(worker="0").set(100.0)
    # Warm-up exemption: saturated depth, zero requests served yet.
    assert not [
        f
        for f in engine.evaluate()["findings"]
        if f["rule"] == "serve_queue_saturated"
    ]
    served.labels(worker="0").inc(1)
    found = [
        f
        for f in engine.evaluate()["findings"]
        if f["rule"] == "serve_queue_saturated"
    ]
    assert len(found) == 1 and "worker 0" in found[0]["detail"]
    assert found[0]["value"] == 95.0 and found[0]["threshold"] == 90.0
    # A second, healthy worker contributes nothing (per-worker dedupe).
    depth.labels(worker="1").set(5.0)
    limit.labels(worker="1").set(100.0)
    served.labels(worker="1").inc(10)
    found = [
        f
        for f in engine.evaluate()["findings"]
        if f["rule"] == "serve_queue_saturated"
    ]
    assert len(found) == 1 and "worker 0" in found[0]["detail"]
    # Draining clears the finding; the firing series reads an explicit 0.
    depth.labels(worker="0").set(10.0)
    assert not [
        f
        for f in engine.evaluate()["findings"]
        if f["rule"] == "serve_queue_saturated"
    ]
    firing = reg.get("r2d2dpg_health_rule_firing")
    assert firing.labels(rule="serve_queue_saturated").value == 0.0


def test_health_engine_serve_shed_churn_rate_per_worker():
    """serve_shed_churn is a windowed per-worker rate over the summed
    shed codes: the finding names the shedding worker, other workers
    stay quiet, and the first sighting only opens the baseline window."""
    import time as _time

    reg, engine = _snap_engine(
        serve_shed_per_s=1.0, serve_shed_rate_min_dt_s=0.0
    )
    sheds = reg.counter(
        "r2d2dpg_serve_sheds_total", labelnames=("worker", "code")
    )
    sheds.labels(worker="0", code="shed_queue_full").inc(0)
    sheds.labels(worker="1", code="shed_queue_full").inc(0)
    # First sighting: baseline window opens, nothing fires.
    assert not [
        f
        for f in engine.evaluate()["findings"]
        if f["rule"] == "serve_shed_churn"
    ]
    _time.sleep(0.02)
    # Both shed MODES of worker 0 count toward its one rate.
    sheds.labels(worker="0", code="shed_queue_full").inc(600)
    sheds.labels(worker="0", code="shed_session_capacity").inc(400)
    found = [
        f
        for f in engine.evaluate()["findings"]
        if f["rule"] == "serve_shed_churn"
    ]
    assert len(found) == 1 and "worker 0" in found[0]["detail"]
    assert found[0]["value"] > 1.0


def test_health_engine_serve_shed_churn_ignores_sub_window_poll_gaps():
    """Sheds land in bursts (a full queue refuses a whole arrival wave):
    a burst over a sub-second poll gap re-judges the last FULL window —
    the eviction_churn burst guard, per worker."""
    reg, engine = _snap_engine(
        serve_shed_per_s=1.0, serve_shed_rate_min_dt_s=5.0
    )
    cell = reg.counter(
        "r2d2dpg_serve_sheds_total", labelnames=("worker", "code")
    ).labels(worker="0", code="shed_queue_full")
    cell.inc(0)
    engine.evaluate()  # baseline window opens
    cell.inc(64)  # one refusal burst, operator curl 20ms later
    assert not [
        f
        for f in engine.evaluate()["findings"]
        if f["rule"] == "serve_shed_churn"
    ]


def test_health_engine_telem_stale_needs_armed_cadence():
    """Staleness clocks arm at HELLO whether or not the peers were told
    to push TELEM (--telem-every rides --obs-fleet): with
    telem_expected=False a growing clock is configuration, not a wedged
    peer, and must not stamp a healthy non-obs-fleet run degraded."""
    reg, engine = _snap_engine(
        telem_stale_after_s=2.0, telem_expected=False
    )
    reg.gauge(
        "r2d2dpg_shard_telem_staleness_seconds", labelnames=("shard",)
    ).labels(shard="0").set(9999.0)
    reg.gauge(
        "r2d2dpg_fleet_telem_staleness_seconds", labelnames=("actor",)
    ).labels(actor="0").set(9999.0)
    assert not [
        f
        for f in engine.evaluate()["findings"]
        if f["rule"] == "telem_stale"
    ]


def test_health_engine_shard_skew_dedupes_mirrored_occupancy():
    """One shard's occupancy appears TWICE in a merged snapshot (learner
    advert mirror + shard-proc TELEM copy share the name): raw samples
    would defeat the single-shard len>=2 guard, and a lagging TELEM copy
    (the forced HELLO push mirrors 0) beside a climbing advert would fire
    shard_skew on a healthy one-shard run.  Dedupe per shard label, max()."""
    reg = Registry()
    mirror = obs.RemoteMirror()
    engine = obs.HealthEngine(
        obs.HealthConfig(occupancy_skew_min_mean=64.0),
        registry=reg,
        mirror=mirror,
    )
    occ = reg.gauge(
        "r2d2dpg_replay_shard_occupancy", labelnames=("shard",)
    )
    occ.labels(shard="0").set(500.0)
    remote = Registry()
    remote.gauge(
        "r2d2dpg_replay_shard_occupancy", labelnames=("shard",)
    ).labels(shard="0").set(0.0)  # stale TELEM copy of the SAME shard
    mirror.update("shard:0", {"host": "vm"}, remote.snapshot())
    assert not [
        f
        for f in engine.evaluate()["findings"]
        if f["rule"] == "shard_skew"
    ]  # one shard, two copies: never skew against itself
    # A genuinely empty SECOND shard (both copies agree) still fires.
    occ.labels(shard="1").set(0.0)
    remote.gauge(
        "r2d2dpg_replay_shard_occupancy", labelnames=("shard",)
    ).labels(shard="1").set(0.0)
    mirror.update("shard:1", {"host": "vm"}, remote.snapshot())
    assert [
        f
        for f in engine.evaluate()["findings"]
        if f["rule"] == "shard_skew"
    ]


def test_health_engine_procs_down_and_transition_events():
    reg, engine = _snap_engine(expected_shard_procs=2)
    n0 = len(obs.get_flight_recorder().events())
    # The actor target comes off the scrape itself when present.
    reg.gauge("r2d2dpg_fleet_actors_expected").set(2.0)
    alive = reg.gauge("r2d2dpg_fleet_actors_alive")
    alive.set(2.0)
    shards = reg.gauge("r2d2dpg_shard_alive")
    shards.set(2.0)
    assert engine.evaluate()["verdict"] == "ok"
    alive.set(1.0)
    res = engine.evaluate()
    assert res["verdict"] == "degraded"
    assert [f["rule"] for f in res["findings"]] == ["actors_down"]
    # Zero live shard procs: sampling is fully degraded -> critical.
    shards.set(0.0)
    res = engine.evaluate()
    assert res["verdict"] == "critical"
    assert {f["rule"] for f in res["findings"]} == {
        "actors_down",
        "shards_down",
    }
    alive.set(2.0)
    shards.set(2.0)
    assert engine.evaluate()["verdict"] == "ok"
    # Every verdict TRANSITION is a durable flight event (ok -> degraded
    # -> critical -> ok), and repeats do not re-fire.
    assert engine.evaluate()["verdict"] == "ok"
    verdicts = [
        (e.get("previous"), e["verdict"])
        for e in obs.get_flight_recorder().events()[n0:]
        if e["kind"] == "health_verdict"
    ]
    assert verdicts == [
        (None, "ok"),
        ("ok", "degraded"),
        ("degraded", "critical"),
        ("critical", "ok"),
    ]
    assert reg.get("r2d2dpg_health_transitions_total").value == 4.0


def test_health_engine_recompile_churn_fire_clear_and_warmup_exempt():
    """recompile_churn (ISSUE 14): new steady_recompile sentinel trips
    inside a window fire; a quiet full window clears; warm-up compiles
    (which grow compile_total but never the steady counter — the
    sentinel arms at mark_steady) are exempt by construction."""
    reg, engine = _snap_engine(recompile_rate_min_dt_s=0.0)
    import time as _time

    # Absence: no device monitor in this process -> rule disarmed.
    assert engine.evaluate()["verdict"] == "ok"
    # Warm-up-exempt: compile activity alone (the warm-up counter) never
    # fires the rule — only the steady counter is judged.
    reg.counter(
        "r2d2dpg_device_compile_total", labelnames=("program",)
    ).labels(program="warmup").inc(50)
    steady = reg.counter("r2d2dpg_device_steady_recompiles_total")
    assert not [
        f
        for f in engine.evaluate()["findings"]
        if f["rule"] == "recompile_churn"
    ]
    # A trip that landed BEFORE the first poll is live evidence, not a
    # rate: judged on the absolute total at first sighting.
    _time.sleep(0.01)
    steady.inc()
    res = engine.evaluate()
    fired = [f for f in res["findings"] if f["rule"] == "recompile_churn"]
    assert fired and res["verdict"] == "degraded"
    assert fired[0]["value"] == 1.0
    # A full quiet window clears the finding (the counter is monotone;
    # the rule judges NEW trips per window, not the total).
    _time.sleep(0.01)
    engine.evaluate()  # window with no new trips -> rate 0 recorded
    _time.sleep(0.01)
    assert not [
        f
        for f in engine.evaluate()["findings"]
        if f["rule"] == "recompile_churn"
    ]
    # ...and a fresh trip re-fires.
    steady.inc(2)
    _time.sleep(0.01)
    fired = [
        f
        for f in engine.evaluate()["findings"]
        if f["rule"] == "recompile_churn"
    ]
    assert fired and fired[0]["value"] == 2.0
    assert reg.get("r2d2dpg_health_rule_firing").labels(
        rule="recompile_churn"
    ).value == 1.0


def test_health_engine_recompile_churn_rejudges_sub_window_polls():
    """The burst guard (eviction_churn's rationale): polls closer than
    the min dt re-judge the last FULL window instead of flapping."""
    reg, engine = _snap_engine(recompile_rate_min_dt_s=5.0)
    steady = reg.counter("r2d2dpg_device_steady_recompiles_total")
    assert engine.evaluate()["verdict"] == "ok"  # baseline at 0
    steady.inc()
    # 0.0 s later (well under min dt): the last full window had no new
    # trips -> still ok; the trip will be judged when a window elapses.
    assert not [
        f
        for f in engine.evaluate()["findings"]
        if f["rule"] == "recompile_churn"
    ]


def test_health_engine_hbm_pressure_fire_and_absent_limit_exempt():
    """hbm_pressure (ISSUE 14): in_use over the headroom fraction of the
    device's reported limit degrades; a backend with no limit series
    (the CPU live-arrays fallback) stays non-degrading — absence of
    evidence is never degradation."""
    reg, engine = _snap_engine(hbm_pressure_frac=0.9)
    in_use = reg.gauge(
        "r2d2dpg_device_hbm_bytes_in_use", labelnames=("device",)
    )
    # CPU shape: in_use series, NO limit series -> exempt however full.
    in_use.labels(device="0").set(1e12)
    assert not [
        f
        for f in engine.evaluate()["findings"]
        if f["rule"] == "hbm_pressure"
    ]
    limit = reg.gauge(
        "r2d2dpg_device_hbm_bytes_limit", labelnames=("device",)
    )
    limit.labels(device="0").set(16e9)
    in_use.labels(device="0").set(0.5 * 16e9)  # half full: headroom
    assert not [
        f
        for f in engine.evaluate()["findings"]
        if f["rule"] == "hbm_pressure"
    ]
    in_use.labels(device="0").set(0.95 * 16e9)  # over the 0.9 bar
    res = engine.evaluate()
    fired = [f for f in res["findings"] if f["rule"] == "hbm_pressure"]
    assert fired and res["verdict"] == "degraded"
    assert fired[0]["threshold"] == pytest.approx(0.9 * 16e9)
    # Per-device: a second device under its own limit adds no finding.
    limit.labels(device="1").set(16e9)
    in_use.labels(device="1").set(1e9)
    assert (
        len(
            [
                f
                for f in engine.evaluate()["findings"]
                if f["rule"] == "hbm_pressure"
            ]
        )
        == 1
    )
    # Recovery clears (pull-time rule, no sticky state).
    in_use.labels(device="0").set(1e9)
    assert engine.evaluate()["verdict"] == "ok"


def test_health_engine_broken_rule_degrades_not_raises():
    reg, engine = _snap_engine()
    # A rule that cannot read its signal contributes an engine_error
    # finding instead of taking the endpoint down.
    reg.gauge("r2d2dpg_replay_shard_occupancy", labelnames=("shard",)).labels(
        shard="0"
    ).set_fn(lambda: (_ for _ in ()).throw(RuntimeError("boom")))
    res = engine.evaluate()
    assert res["verdict"] in ("ok", "degraded")  # never raises
    # engine_error is exported on the firing gauge like the real rules —
    # a degraded verdict must always be attributable on the scrape.
    firing = reg.get("r2d2dpg_health_rule_firing")
    assert firing.labels(rule="engine_error").value == 0.0
    engine._rules = (
        lambda snap, findings: (_ for _ in ()).throw(RuntimeError("rule")),
    )
    res = engine.evaluate()
    assert res["verdict"] == "degraded"
    assert [f["rule"] for f in res["findings"]] == ["engine_error"]
    assert firing.labels(rule="engine_error").value == 1.0


def test_health_endpoint_serves_verdict_json(tmp_path):
    """GET /health on the exporter: machine-readable verdict, HTTP 200
    even when degraded (a degraded run is an ANSWER, not a transport
    error), and a lazy default engine when none was armed."""
    reg = Registry()
    exp = obs.MetricsExporter(reg, port=0, host="127.0.0.1")
    try:
        base = f"http://127.0.0.1:{exp.port}"
        body = json.loads(urllib.request.urlopen(f"{base}/health").read())
        assert body["verdict"] == "ok" and body["findings"] == []
        assert exp.health is not None  # the lazy default engine stuck
        reg.histogram("r2d2dpg_sampler_wait_seconds").observe(30.0)
        req = urllib.request.urlopen(f"{base}/health")
        assert req.status == 200  # degraded is an answer, not an error
        body = json.loads(req.read())
        assert body["verdict"] == "degraded"
        assert body["findings"][0]["rule"] == "learner_starving"
        # arm_health replaces the lazy default (lock-shared with the
        # handler, so a configured engine can never be outraced and
        # clobbered by it) — the next GET judges with the armed config.
        armed = obs.HealthEngine(
            obs.HealthConfig(learner_wait_p99_s=60.0),
            registry=reg,
            mirror=None,
        )
        assert exp.arm_health(armed) is armed and exp.health is armed
        body = json.loads(urllib.request.urlopen(f"{base}/health").read())
        assert body["verdict"] == "ok"  # 30 s wait < the armed 60 s bar
    finally:
        exp.stop()


def test_health_config_from_args_carries_resolved_topology():
    """The teardown's health_final.json fallback and the exporter's armed
    engine build from ONE helper: the run's thresholds and expected
    process counts (HealthConfig defaults have expected_actors=0 /
    expected_shard_procs=0, which disarm actors_down/shards_down — a
    dead shard tier would stamp 'ok')."""
    from r2d2dpg_tpu import train as train_mod

    args = train_mod.parse_args(
        [
            "--config", "pendulum_tiny",
            "--actors", "3",
            "--replay-shards", "2",
            "--shard-procs", "2",
            "--health-wait-p99", "7.5",
            "--health-stale-after", "11.0",
        ]
    )
    cfg = train_mod._health_config(args)
    assert cfg.learner_wait_p99_s == 7.5
    assert cfg.telem_stale_after_s == 11.0
    assert cfg.expected_actors == 3
    assert cfg.expected_shard_procs == 2
    # telem_stale is judged only when a TELEM cadence was armed.
    assert cfg.telem_expected is False
    args2 = train_mod.parse_args(
        ["--config", "pendulum_tiny", "--actors", "3", "--obs-fleet", "1"]
    )
    assert train_mod._health_config(args2).telem_expected is True


# ------------------------------------------------------ metric-name lint
def test_lint_metric_scheme_catches_offender(tmp_path):
    """satellite: a library registration outside the documented
    r2d2dpg_<subsystem>_<metric> scheme fails the lint (allowlist file
    honored)."""
    import shutil

    tree = tmp_path / "repo"
    (tree / "scripts").mkdir(parents=True)
    shutil.copy(
        os.path.join(REPO, "scripts", "lint_obs.sh"), tree / "scripts"
    )
    pkg = tree / "r2d2dpg_tpu"
    pkg.mkdir()
    (pkg / "offender.py").write_text(
        "def setup(reg):\n"
        "    return reg.counter(\n"
        '        "my_rogue_metric", "spans lines like real registrations"\n'
        "    )\n"
    )
    res = subprocess.run(
        ["bash", str(tree / "scripts" / "lint_obs.sh")],
        capture_output=True,
        text=True,
    )
    assert res.returncode == 1
    assert "my_rogue_metric" in res.stdout
    # Allowlisting the name (with the file's comment contract) passes it.
    (tree / "scripts" / "obs_metric_allowlist.txt").write_text(
        "# fixture exemption\nmy_rogue_metric\n"
    )
    res = subprocess.run(
        ["bash", str(tree / "scripts" / "lint_obs.sh")],
        capture_output=True,
        text=True,
    )
    assert res.returncode == 0, res.stdout + res.stderr


# ------------------------------------------------------- train.py refusals
def test_train_cli_refuses_orphan_obs_fleet_and_trace_flags():
    from r2d2dpg_tpu.train import parse_args, run

    with pytest.raises(SystemExit, match="requires --actors"):
        run(parse_args(["--config", "pendulum_tiny", "--obs-fleet", "1"]))
    with pytest.raises(SystemExit, match="requires --actors N or --pipeline"):
        run(
            parse_args(
                ["--config", "pendulum_tiny", "--trace-sample", "0.5"]
            )
        )
    with pytest.raises(SystemExit, match="must be in"):
        run(
            parse_args(
                [
                    "--config", "pendulum_tiny",
                    "--pipeline", "1",
                    "--trace-sample", "1.5",
                ]
            )
        )
    # Multi-process + --pipeline has no wired allgather call site: refuse
    # rather than silently export nothing for rank > 0.
    import jax as _jax

    from unittest import mock

    with mock.patch.object(_jax, "process_count", return_value=2):
        with pytest.raises(SystemExit, match="not wired on multi-process"):
            run(
                parse_args(
                    [
                        "--config", "pendulum_tiny",
                        "--pipeline", "1",
                        "--obs-fleet", "1",
                    ]
                )
            )
