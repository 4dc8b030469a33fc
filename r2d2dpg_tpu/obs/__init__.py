"""Unified telemetry (ISSUE 3): registry, exporter, flight recorder, watchdog.

One process-wide namespace for every subsystem's operator signals:

- ``registry``  — typed Counter/Gauge/Histogram instruments with label
  sets (``get_registry()`` is the process singleton all subsystems
  register into).
- ``exporter``  — stdlib-HTTP scrape point (``/metrics`` Prometheus text,
  ``/metrics.json`` snapshot, ``/health`` verdict JSON) on ``--obs-port``.
- ``health``    — the /health rule engine (ISSUE 13): machine-readable
  ``{verdict, findings[]}`` over registry+mirror signals, verdict
  transitions recorded as flight events — the autoscaler's input
  contract, built as observability.
- ``flight``    — bounded ring of structured events dumped to
  ``flight.jsonl`` on exit/abort (``flight_event(kind, **fields)``).
- ``watchdog``  — NaN/Inf + grad/param-norm checks riding the log
  cadence's existing batched ``device_get``; trips abort loudly.
- ``trace``     — sampled experience-path hop spans (collect -> ... ->
  learn) feeding ``r2d2dpg_trace_*_seconds`` histograms and the flight
  recorder's ``trace.json`` dump.
- ``device``    — the device plane (ISSUE 14): compile sentinel
  (``steady_recompile`` alarms on post-warm aval re-keys), per-device
  HBM gauges, and ``--profile-window`` profiler captures stamped into
  the fused timeline and reduced to device time by stage of the learner
  call (``obs/stages.py``).
- ``hlo``       — what a compiled program does to the whole replay and to
  the batch drawn from it, and where its image convolutions run, read
  from its HLO text: ``arena_converts``, ``batch_minor_writes``,
  ``capacity_scans`` and ``loop_convolutions``, behind ``chip_smoke.py``'s
  four guards of the learner call.
- ``quality``   — the experience-quality plane (ISSUE 18): sequence
  provenance (behavior param version + collect phase) stamped at the
  actor and carried through wire/arena/shard slots, folded at batch
  assembly into policy-lag/replay-age distributions, ESS/B, IS-weight
  saturation, per-actor trained-seqs and per-shard
  evicted-before-sampled fractions (``r2d2dpg_quality_*``), judged by
  the stale_experience/priority_collapse/untrained_churn/actor_skew
  /health rules and stamped to ``quality_final.json`` at teardown.
- ``RemoteMirror`` / ``allgather_into_mirror`` — other processes'
  registry snapshots merged into this process's exporter: ONE scrape
  point per fleet (fed by fleet TELEM frames or an SPMD allgather).

See docs/OBSERVABILITY.md for the naming scheme, endpoints, event schema
and thresholds.
"""

from r2d2dpg_tpu.obs import device  # noqa: F401 - obs.device.* is the API
from r2d2dpg_tpu.obs.device import (
    DeviceMonitor,
    get_device_monitor,
)
from r2d2dpg_tpu.obs.exporter import (
    MetricsExporter,
    current_exporter,
    start_exporter,
    stop_exporter,
)
from r2d2dpg_tpu.obs.flight import (
    FlightRecorder,
    flight_event,
    get_flight_recorder,
    set_flight_identity,
)
from r2d2dpg_tpu.obs.health import (
    HealthConfig,
    HealthEngine,
)
from r2d2dpg_tpu.obs import quality  # noqa: F401 - obs.quality.* is the API
from r2d2dpg_tpu.obs.quality import (
    QualityPlane,
    get_quality_plane,
    reset_quality_plane,
)
from r2d2dpg_tpu.obs.registry import (
    Counter,
    Gauge,
    Histogram,
    Registry,
    RemoteMirror,
    allgather_into_mirror,
    get_registry,
    get_remote_mirror,
    merge_remote,
    render_prometheus,
)
from r2d2dpg_tpu.obs import trace  # noqa: F401 - obs.trace.* is the span API
from r2d2dpg_tpu.obs.watchdog import (
    DivergenceError,
    DivergenceWatchdog,
    WatchdogConfig,
)

__all__ = [
    "Counter",
    "DeviceMonitor",
    "DivergenceError",
    "DivergenceWatchdog",
    "FlightRecorder",
    "Gauge",
    "HealthConfig",
    "HealthEngine",
    "Histogram",
    "MetricsExporter",
    "QualityPlane",
    "Registry",
    "RemoteMirror",
    "WatchdogConfig",
    "allgather_into_mirror",
    "current_exporter",
    "device",
    "flight_event",
    "get_device_monitor",
    "get_flight_recorder",
    "get_quality_plane",
    "get_registry",
    "get_remote_mirror",
    "merge_remote",
    "quality",
    "reset_quality_plane",
    "render_prometheus",
    "set_flight_identity",
    "start_exporter",
    "stop_exporter",
    "trace",
]
