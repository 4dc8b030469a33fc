"""Device-plane observability (ISSUE 14): the chip stops being dark.

Every obs plane so far watches hosts, wires and processes; the device
itself — where the repo's hardest-won invariants live — had no witness.
Three legs, one monitor:

**Compile sentinel.**  ``jax.monitoring`` fires an event-duration sample
for every XLA backend compile in the process; the monitor folds them into
``r2d2dpg_device_compile_{total,seconds}`` labelled by the *program* the
dispatching thread declared (``program("fleet_drain")`` context manager /
``label_thread``).  Each learner loop calls ``mark_steady()`` once its
programs are warm; any compile AFTER that point — outside a declared
``expected(reason)`` window (the dp warm-compile thread, the log-cadence
eager fetches, eval, fault drills) — is a **steady recompile**: the
silent aval-re-key / coalesce-width bug class (the exact failure mode the
PR 9/11 ``out_shardings`` pins exist to prevent) becomes a runtime alarm
(``steady_recompile`` flight event + ``r2d2dpg_device_steady_recompiles_
total``), instead of a mystery 30 s stall in a bench trace.

**Memory gauges.**  ``publish()`` — called from
``Trainer._obs_publish`` on the existing log cadence, so every loop gets
it for free and no new device syncs enter the hot path — reads each local
device's ``memory_stats()`` (``bytes_in_use`` / ``peak_bytes_in_use`` /
``bytes_limit``) into ``r2d2dpg_device_hbm_*{device=}`` gauges; on
backends without allocator stats (CPU) it falls back to summing
``jax.live_arrays()`` per device (peak maintained host-side), so the
series exists everywhere and the /health ``hbm_pressure`` rule degrades
to absence-of-evidence where no ``bytes_limit`` exists.  (The chip's
utilization is not a gauge here: a compiled program's ``cost_analysis()``
counts a scan body once, so it cannot be had from the program; the
benchmark computes ``learn_mfu`` from shapes, ``chipbench/counts.py``.)

**Profiler capture windows.**  ``--profile-window P:N`` arms a
``jax.profiler`` trace for train/drain phases P..P+N-1 in WHICHEVER loop
the run resolves to (the legacy ``--profile-phases`` only knew the
phase-locked path); ``profile_start``/``profile_stop`` flight events
bracket the capture so ``obs.flight merge --trace-out`` stamps the window
as a labelled ``profile_window`` span in the fused Perfetto timeline —
the capture is findable from the run's own evidence, not tribal memory.
When the window closes the capture is reduced to device seconds by stage
of the learner call (``obs/stages.py::stage_table``: the
``utils/profiling.py::LEARN_STAGES`` scopes as the chip's trace carries
them; its entry ``scopes`` has every scope of the program by pass, and
``truncated`` says whether the capture lost the tail of its device events),
written to ``<logdir>/profile_window/stages.json`` and carried by the
``profile_stop`` event.

Lifecycle: ``install()`` registers the (idempotent) listener;
``begin_run()`` opens a run window (baselines for ``run_stats()``, steady
flag cleared); each loop calls ``mark_steady()`` at its documented warm
boundary and ``end_run()`` in its finally (post-run compiles — the next
test in a shared pytest process — must never alarm).  docs/OBSERVABILITY
.md "Device plane" is the operator contract.
"""

from __future__ import annotations

import glob
import json
import os
import threading
import time
from typing import Any, Dict, Optional, Tuple

from r2d2dpg_tpu.obs.flight import flight_event
from r2d2dpg_tpu.obs.registry import Registry, get_registry

# The device-plane metric namespace, enumerated so scripts/lint_obs.sh
# holds every name to the r2d2dpg_<subsystem>_<metric> scheme even if a
# registration ever goes non-literal (the trace-hop precedent).
METRIC_NAMES = (
    "r2d2dpg_device_compile_total",
    "r2d2dpg_device_compile_seconds",
    "r2d2dpg_device_steady_recompiles_total",
    "r2d2dpg_device_hbm_bytes_in_use",
    "r2d2dpg_device_hbm_bytes_peak",
    "r2d2dpg_device_hbm_bytes_limit",
)

# The jax.monitoring event that IS "one XLA program compiled" (suffix
# match for version tolerance; jaxpr-trace / MLIR-lower durations also
# fire but are host work, not program materialization).
_COMPILE_EVENT_SUFFIX = "backend_compile_duration"

_UNATTRIBUTED = "unattributed"

_tls = threading.local()


def parse_profile_window(spec: str) -> Tuple[int, int]:
    """``"P:N"`` -> (first phase, phase count), both >= 1.  The capture
    spans train/drain phases P..P+N-1 on the run's resolved loop."""
    parts = str(spec).split(":")
    if len(parts) != 2:
        raise ValueError(
            f"--profile-window expects 'P:N' (phase:steps), got {spec!r}"
        )
    try:
        phase, steps = int(parts[0]), int(parts[1])
    except ValueError:
        raise ValueError(
            f"--profile-window expects integers 'P:N', got {spec!r}"
        )
    if phase < 1 or steps < 1:
        raise ValueError(
            f"--profile-window phase and steps must be >= 1, got {spec!r}"
        )
    return phase, steps


class DeviceMonitor:
    """Compile sentinel + HBM gauges + profiler windows (one object).

    The process singleton (``get_device_monitor``) is what the learner
    loops wire; tests construct private instances over their own
    ``Registry`` — ``uninstall()`` turns a private instance's listener
    into a no-op (jax.monitoring has no per-listener removal)."""

    def __init__(self, registry: Optional[Registry] = None):
        reg = registry if registry is not None else get_registry()
        self._lock = threading.Lock()
        self._installed = False
        self._active = True
        self._steady = False
        # Monotone process totals (run_stats subtracts begin_run baselines).
        self._compiles_total = 0
        self._compile_seconds_total = 0.0
        self._steady_recompiles_total = 0
        self._base = (0, 0.0, 0)
        # Host-maintained HBM peaks (CPU fallback has no allocator peak).
        self._hbm_peak: Dict[str, float] = {}
        # Profiler window.
        self._profile: Optional[Tuple[int, int, str]] = None
        self._profile_active_since: Optional[Tuple[int, float, str]] = None

        self._obs_compiles = reg.counter(
            "r2d2dpg_device_compile_total",
            "XLA backend compiles, labelled by the dispatching thread's "
            "declared program",
            labelnames=("program",),
        )
        self._obs_compile_s = reg.histogram(
            "r2d2dpg_device_compile_seconds",
            "XLA backend compile durations per program (jax.monitoring "
            "event-duration samples)",
            labelnames=("program",),
        )
        self._obs_steady = reg.counter(
            "r2d2dpg_device_steady_recompiles_total",
            "compiles AFTER mark_steady() outside any declared expected "
            "window — the aval-re-key alarm (each also lands in "
            "flight.jsonl as a steady_recompile event)",
        )
        self._obs_in_use = reg.gauge(
            "r2d2dpg_device_hbm_bytes_in_use",
            "per-device allocator bytes in use (live-array sum where the "
            "backend reports no memory_stats)",
            labelnames=("device",),
        )
        self._obs_peak = reg.gauge(
            "r2d2dpg_device_hbm_bytes_peak",
            "per-device peak bytes in use (host-maintained running max "
            "on backends without allocator stats)",
            labelnames=("device",),
        )
        self._obs_limit = reg.gauge(
            "r2d2dpg_device_hbm_bytes_limit",
            "per-device allocator capacity (absent where the backend "
            "reports none — the hbm_pressure rule stays disarmed there)",
            labelnames=("device",),
        )

    # ------------------------------------------------------------- listener
    def install(self) -> "DeviceMonitor":
        """Register the jax.monitoring listener (idempotent, process-wide
        side effect; the listener itself no-ops after ``uninstall``)."""
        with self._lock:
            if self._installed:
                return self
            self._installed = True
        import jax.monitoring

        jax.monitoring.register_event_duration_secs_listener(self._on_event)
        return self

    def uninstall(self) -> None:
        """Silence this instance's listener (tests: jax.monitoring keeps
        every registered callback for the life of the process)."""
        self._active = False

    def _on_event(self, event: str, duration: float, **_kw) -> None:
        # Called synchronously inside jax's compile path: never raise.
        try:
            if not self._active or not str(event).endswith(
                _COMPILE_EVENT_SUFFIX
            ):
                return
            program = getattr(_tls, "program", None) or _UNATTRIBUTED
            expected = getattr(_tls, "expected", 0) > 0
            self._obs_compiles.labels(program=program).inc()
            self._obs_compile_s.labels(program=program).observe(
                float(duration)
            )
            with self._lock:
                self._compiles_total += 1
                self._compile_seconds_total += float(duration)
                alarm = self._steady and not expected
                if alarm:
                    self._steady_recompiles_total += 1
            if alarm:
                self._obs_steady.inc()
                flight_event(
                    "steady_recompile",
                    program=program,
                    seconds=round(float(duration), 4),
                )
        except Exception:  # noqa: BLE001 — never break a compile
            pass

    # ----------------------------------------------------- labels / windows
    class _Label:
        def __init__(self, attr: str, value):
            self._attr, self._value = attr, value

        def __enter__(self):
            self._prev = getattr(_tls, self._attr, None)
            setattr(_tls, self._attr, self._value)
            return self

        def __exit__(self, *exc):
            setattr(_tls, self._attr, self._prev)
            return False

    def program(self, label: str) -> "DeviceMonitor._Label":
        """Attribute compiles on THIS thread to ``label`` while the
        context is open (the compile happens on the dispatching thread)."""
        return self._Label("program", str(label))

    def label_thread(self, label: str) -> None:
        """Sticky per-thread default program label (worker threads that
        own one program family — the pipeline collector)."""
        _tls.program = str(label)

    class _Expected:
        def __init__(self, reason: str):
            self._reason = reason

        def __enter__(self):
            _tls.expected = getattr(_tls, "expected", 0) + 1
            return self

        def __exit__(self, *exc):
            _tls.expected = max(getattr(_tls, "expected", 1) - 1, 0)
            return False

    def expected(self, reason: str) -> "DeviceMonitor._Expected":
        """Declare a window where post-steady compiles are legitimate on
        THIS thread (warm-compile thread, log-cadence eager fetches,
        eval, fault drills).  Compiles inside it still count and label;
        they just never alarm."""
        return self._Expected(reason)

    # ------------------------------------------------------------ lifecycle
    def begin_run(self) -> None:
        """Open a run window: run_stats baselines reset, steady cleared.
        Called once by whichever loop owns the run's phase schedule."""
        with self._lock:
            self._steady = False
            self._base = (
                self._compiles_total,
                self._compile_seconds_total,
                self._steady_recompiles_total,
            )
            # Per-run peak: without this, a big previous run in the same
            # process would leak its peak into every later run's stats
            # column.  (On allocator backends peak_bytes_in_use is itself
            # process-lifetime — _publish_memory maxes it in, so the
            # column is per-run only where the fallback owns the peak.)
            self._hbm_peak = {}

    def mark_steady(self) -> None:
        """The sentinel arms: every program this loop dispatches is warm;
        further compiles outside expected windows are re-key alarms."""
        with self._lock:
            self._steady = True

    def end_run(self) -> None:
        """Close the run window: disarm the sentinel (whatever compiles
        next — another run, the next test in this process — opens its own
        window) and stop a still-open profiler capture."""
        with self._lock:
            self._steady = False
        self._stop_profile(reason="end_run")

    @property
    def steady(self) -> bool:
        with self._lock:
            return self._steady

    def run_stats(self) -> Dict[str, float]:
        """Since-``begin_run`` deltas — the stats()/bench columns.

        Refreshes the gauges first: a ``log_every=0`` run (every bench
        leg) never hits the log-cadence ``publish()``, and the peak
        would otherwise read 0 at the end of a real run."""
        self.publish()
        with self._lock:
            c0, s0, r0 = self._base
            return {
                "compile_count": float(self._compiles_total - c0),
                "compile_seconds": self._compile_seconds_total - s0,
                "steady_recompiles": float(
                    self._steady_recompiles_total - r0
                ),
                "peak_hbm_bytes": max(self._hbm_peak.values(), default=0.0),
            }

    # --------------------------------------------------------------- gauges
    def publish(self) -> None:
        """Refresh the HBM gauges.  Rides the log cadence
        (``Trainer._obs_publish``): host-side allocator reads only, no
        device syncs."""
        try:
            self._publish_memory()
        except Exception:  # noqa: BLE001 — telemetry never kills a run
            pass

    def _publish_memory(self) -> None:
        import jax

        fallback_devices = []
        for d in jax.local_devices():
            stats = None
            try:
                stats = d.memory_stats()
            except Exception:  # noqa: BLE001 — backend-dependent API
                stats = None
            if not stats:
                fallback_devices.append(d)
                continue
            dev = str(d.id)
            in_use = float(stats.get("bytes_in_use", 0.0))
            self._obs_in_use.labels(device=dev).set(in_use)
            peak = float(stats.get("peak_bytes_in_use", in_use))
            with self._lock:
                peak = max(peak, self._hbm_peak.get(dev, 0.0))
                self._hbm_peak[dev] = peak
            self._obs_peak.labels(device=dev).set(peak)
            limit = stats.get("bytes_limit")
            if limit:
                self._obs_limit.labels(device=dev).set(float(limit))
        if not fallback_devices:
            return
        # CPU (and any backend without allocator stats): per-device sums
        # over the live-array table — coarser than allocator truth (frees
        # show immediately, fragmentation never), but a real series with
        # a real peak instead of silence.
        per: Dict[str, float] = {str(d.id): 0.0 for d in fallback_devices}
        for a in jax.live_arrays():
            try:
                for sh in a.addressable_shards:
                    dev = str(sh.device.id)
                    if dev in per:
                        per[dev] += float(sh.data.nbytes)
            except Exception:  # noqa: BLE001 — deleted/donated arrays
                continue
        for dev, in_use in per.items():
            self._obs_in_use.labels(device=dev).set(in_use)
            with self._lock:
                peak = max(in_use, self._hbm_peak.get(dev, 0.0))
                self._hbm_peak[dev] = peak
            self._obs_peak.labels(device=dev).set(peak)

    # ------------------------------------------------------------- profiler
    def arm_profile(self, spec: str, logdir: str) -> Tuple[int, int]:
        """Arm ``--profile-window P:N`` into ``logdir`` (created lazily at
        capture start).  Returns the parsed (phase, steps)."""
        phase, steps = parse_profile_window(spec)
        self._profile = (phase, steps, str(logdir))
        return phase, steps

    def on_phase(self, phase: int) -> None:
        """Called by every learner loop with the 1-based index of the
        train/drain phase ABOUT to run: starts the capture at phase P,
        stops it before phase P+N.  No window armed = one int compare."""
        prof = self._profile
        if prof is None:
            return
        p0, n, logdir = prof
        if self._profile_active_since is None:
            if phase == p0:
                self._start_profile(phase, logdir)
        elif phase >= p0 + n:
            self._stop_profile(phase=phase)

    def _start_profile(self, phase: int, logdir: str) -> None:
        import jax

        try:
            os.makedirs(logdir, exist_ok=True)
            jax.profiler.start_trace(logdir)
        except Exception as e:  # noqa: BLE001 — telemetry, not the run
            flight_event(
                "profile_failed", error=f"{type(e).__name__}: {e}"
            )
            self._profile = None
            return
        self._profile_active_since = (phase, time.time(), logdir)
        flight_event("profile_start", phase=phase, logdir=logdir)

    def _stop_profile(self, phase: Optional[int] = None, reason=None) -> None:
        active = self._profile_active_since
        if active is None:
            return
        self._profile_active_since = None
        self._profile = None  # one window per run
        import jax

        try:
            jax.profiler.stop_trace()
        except Exception as e:  # noqa: BLE001
            flight_event(
                "profile_failed", error=f"{type(e).__name__}: {e}"
            )
            return
        seconds = round(time.time() - active[1], 3)
        stages = None
        try:
            stages = self._reduce_capture(active[2])
        except Exception as e:  # noqa: BLE001 — a reader fault is not the run's
            flight_event(
                "profile_failed",
                error=f"stage table: {type(e).__name__}: {e}",
            )
        flight_event(
            "profile_stop",
            phase=phase,
            start_phase=active[0],
            seconds=seconds,
            **({"reason": reason} if reason else {}),
            **({"stages": stages} if stages else {}),
        )

    @staticmethod
    def _reduce_capture(logdir: str) -> Dict[str, Any]:
        """The capture just closed, as device seconds by stage of the
        learner call (``obs/stages.py``): the whole table goes to
        ``<logdir>/stages.json``, its seconds (and ``truncated``: the
        capture lost the tail of its device events) into ``profile_stop``."""
        from r2d2dpg_tpu.obs.stages import stage_table

        found = glob.glob(
            os.path.join(logdir, "plugins", "profile", "*", "*.xplane.pb")
        )
        if not found:
            raise FileNotFoundError(f"no .xplane.pb under {logdir}")
        # The learner's own table; every other name the program has (the
        # once-an-update scopes, a sequence core's, each by pass) is a row
        # of its entry ``scopes``, less the rows that read 0 throughout.
        table = stage_table(max(found, key=os.path.getmtime))
        table["scopes"] = {
            row: passes for row, passes in table["scopes"].items()
            if passes["all"]
        }
        with open(os.path.join(logdir, "stages.json"), "w") as f:
            json.dump(table, f, indent=1)
        return {
            k: v if isinstance(v, bool) else round(v, 9)
            for k, v in table.items()
            if isinstance(v, (int, float))
        }


_MONITOR = DeviceMonitor()


def get_device_monitor() -> DeviceMonitor:
    """THE process device monitor (module singleton; every learner loop
    installs + drives it, so library consumers share one sentinel)."""
    return _MONITOR
