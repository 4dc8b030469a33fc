"""What every cell's run shares: finding a cell's files by name, the run's
phases, the profiler sessions, and the contract's result line.

Nothing here lists cells, configurations, metrics, drivers or reducers: each
is a file found by the name ``BENCHMARK.json`` gives it.

- ``workloads/<cell>.json``  the cell: ``config``, ``driver``, the traffic
  generator's parameters, the limits of ``correct``;
- ``configs/<config>.json``  the configuration as it is run;
- ``drivers/<driver>.py``    ``setup``, ``window``, ``probes``, ``check``;
- ``metrics/<metric>.json``  a per-layer metric: its reducer and arguments;
- ``reducers/<reducer>.py``  ``read(ctx, **args) -> float | None``.
"""

from __future__ import annotations

import contextlib
import dataclasses
import glob
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time
from typing import Any, Dict, Iterator, List, Optional

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)


# ------------------------------------------------------------ files by name
def load_json(kind: str, name: str, root: str = HERE) -> Dict[str, Any]:
    path = os.path.join(root, kind, name + ".json")
    if not os.path.isfile(path):
        raise FileNotFoundError(f"no {kind[:-1]} file {path}")
    with open(path) as f:
        return json.load(f)


def load_module(kind: str, name: str, root: str = HERE):
    """``<root>/<kind>/<name>.py`` as a module, found by name."""
    path = os.path.join(root, kind, name + ".py")
    if not os.path.isfile(path):
        raise FileNotFoundError(f"no {kind[:-1]} module {path}")
    mod_name = f"chipbench_{kind}_{name.replace('.', '_')}"
    spec = importlib.util.spec_from_file_location(mod_name, path)
    module = importlib.util.module_from_spec(spec)
    sys.modules[mod_name] = module
    spec.loader.exec_module(module)
    return module


def load_benchmark(repo: str = REPO) -> Dict[str, Any]:
    with open(os.path.join(repo, "BENCHMARK.json")) as f:
        return json.load(f)


def metrics_of(bench: Dict[str, Any], group: str, cell: str) -> List[Dict[str, Any]]:
    """The ``end_to_end`` or ``per_layer`` entries that ``cell`` reports: an
    entry without a ``workloads`` key is every cell's."""
    return [
        m for m in bench[group] if "workloads" not in m or cell in m["workloads"]
    ]


# ------------------------------------------------------------------- device
def device_record() -> Dict[str, Any]:
    import jax

    devices = jax.devices()
    return {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }


def memory_peak_bytes() -> int:
    """Peak bytes in use on the fullest device; where the backend keeps no
    allocator statistics (the CPU), the bytes of the live arrays."""
    import jax

    peaks = []
    for d in jax.devices():
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    if peaks:
        return max(peaks)
    return int(sum(x.nbytes for x in jax.live_arrays()))


class CompileCounter:
    """Counts XLA backend compiles through ``jax.monitoring``; the harness
    reads it around the measured window, where none may happen."""

    def __init__(self):
        import jax.monitoring

        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_kw) -> None:
        if str(event).endswith("backend_compile_duration"):
            self.count += 1


# ----------------------------------------------------------------- profiler
@contextlib.contextmanager
def profile_session() -> Iterator[Dict[str, Any]]:
    """One profiler capture into a directory under ``TMPDIR``.  Yields a dict
    that holds ``xplane`` (the file's path) and the host-clock length of the
    captured block once the block has ended; the caller reduces the file and
    then calls ``out["cleanup"]()``."""
    import jax

    out: Dict[str, Any] = {}
    logdir = tempfile.mkdtemp(prefix="chipbench_trace_")
    out["cleanup"] = lambda: shutil.rmtree(logdir, ignore_errors=True)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    jax.profiler.start_trace(logdir, profiler_options=options)
    t0 = time.perf_counter()
    try:
        yield out
    finally:
        out["seconds"] = time.perf_counter() - t0
        jax.profiler.stop_trace()
        found = glob.glob(
            os.path.join(logdir, "plugins", "profile", "*", "*.xplane.pb")
        )
        out["xplane"] = found[0] if found else None


# -------------------------------------------------------------- comparisons
@dataclasses.dataclass
class Compared:
    """One number that decides ``correct``, beside its limit."""

    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        v = float(self.value)
        return v == v and v <= float(self.limit)  # NaN fails


def compared_dict(items: List[Compared]) -> Dict[str, Dict[str, float]]:
    return {
        c.name: {"value": float(c.value), "limit": float(c.limit), "ok": c.ok}
        for c in items
    }


# ------------------------------------------------------------------ context
@dataclasses.dataclass
class Context:
    """What a driver and the reducers are given."""

    cell_name: str
    cell: Dict[str, Any]
    config: Dict[str, Any]
    seed: int
    seconds: float
    trace: bool
    plant: Optional[str] = None
    t_start: float = 0.0
    device: Dict[str, Any] = dataclasses.field(default_factory=dict)
    peaks: Optional[Dict[str, Any]] = None
    window: Dict[str, Any] = dataclasses.field(default_factory=dict)
    steady_trace: Optional[Dict[str, Any]] = None  # reduction of the steady capture
    probe_trace: Optional[Dict[str, Any]] = None  # reduction of the probes' capture
    probes: Dict[str, Any] = dataclasses.field(default_factory=dict)

    def log(self, msg: str) -> None:
        print(f"[chipbench +{time.time() - self.t_start:7.2f}s] {msg}",
              file=sys.stderr, flush=True)
