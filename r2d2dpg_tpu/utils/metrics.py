"""Metrics / logging / observability (SURVEY.md §5.5).

Reference parity: the reference logs episode returns to stdout and possibly
TensorBoard scalars (SURVEY §2.7/§5.5).  The build logs:

- TensorBoard scalars (via ``tensorboardX``) when a logdir is given;
- a CSV fallback, always (one row per log call, stable header);
- the BASELINE metric **return @ wall-clock minutes** (every scalar is
  stamped with both ``step`` and seconds-since-start, so return@30min is a
  direct read-off of the CSV/TB curve);
- **SPS** — env steps/sec and learner steps/sec — computed from deltas.
"""

from __future__ import annotations

import collections
import csv
import math
import os
import threading
import time
from typing import Dict, Iterable, Optional, Tuple

import numpy as np


def host_scalars(metrics) -> Dict[str, float]:
    """A fetched learner-metrics dict as floats for a log line.  An entry
    that is a table (the sdar core's ``moe/tokens_per_expert``) is not a
    column of a log: it is left to whoever reads the dict itself."""
    return {k: float(v) for k, v in metrics.items() if np.size(v) == 1}


class PercentileWindow:
    """Sliding window of scalar observations with percentile read-off.

    Serving health (queue wait, policy-step latency) needs p50/p99 over the
    *recent* past, not the whole process lifetime — a bounded deque of the
    last ``size`` observations is that window.  ``add`` is O(1);
    ``percentiles`` sorts the window (a few thousand floats) only when a
    snapshot is actually taken.  Thread-safe: producers (the serving worker)
    and consumers (health scrapes from request threads) run concurrently.
    """

    def __init__(self, size: int = 2048):
        if size < 1:
            raise ValueError("size must be >= 1")
        self._buf: collections.deque = collections.deque(maxlen=size)
        self._lock = threading.Lock()
        self._count = 0
        self._total = 0.0

    def add(self, value: float) -> None:
        with self._lock:
            self._buf.append(float(value))
            self._count += 1
            self._total += float(value)

    @property
    def count(self) -> int:
        """Total observations ever added (not just those still windowed)."""
        return self._count

    @property
    def total(self) -> float:
        """Running sum of ALL observations ever added (not windowed).

        The pipelined executor derives its overlap fraction from total
        stage-wait seconds over wall-clock; the window alone would forget
        waits older than ``size`` observations."""
        return self._total

    @staticmethod
    def _nearest_rank(data, qs) -> Tuple[float, ...]:
        if not data:
            return tuple(0.0 for _ in qs)
        out = []
        for q in qs:
            # Nearest-rank: ceil(q/100 * n) - 1, clamped to the window.
            rank = math.ceil(q / 100.0 * len(data)) - 1
            out.append(data[max(0, min(len(data) - 1, rank))])
        return tuple(out)

    def percentiles(self, qs: Iterable[float] = (50.0, 99.0)) -> Tuple[float, ...]:
        """Nearest-rank percentiles over the current window (0.0 if empty)."""
        with self._lock:
            data = sorted(self._buf)
        return self._nearest_rank(data, qs)

    def snapshot(self) -> Tuple[int, float, float, float]:
        """One consistent ``(count, total, p50, p99)`` read under ONE lock.

        Stats consumers (the pipelined executor's ``stats()``, the obs
        registry's histogram export) previously took three separate locked
        reads — count, total, percentiles — between which a producer could
        slip observations in, so the triple was mutually inconsistent."""
        with self._lock:
            count, total = self._count, self._total
            data = sorted(self._buf)
        p50, p99 = self._nearest_rank(data, (50.0, 99.0))
        return count, total, p50, p99

    def reset(self) -> None:
        """Drop the window AND the lifetime count/total (measurement-section
        boundaries, e.g. the pipelined executor's per-section stats)."""
        with self._lock:
            self._buf.clear()
            self._count = 0
            self._total = 0.0


class MetricLogger:
    """Scalar logger: stdout + CSV (always) + TensorBoard (if logdir given).

    ``log(step, scalars)`` stamps every row with wall-clock seconds since
    construction; ``rates(env_steps, learner_steps)`` folds steps/sec deltas
    into the next ``log`` call.

    Thread-safe: the pipelined executor's learner thread and the serving
    worker's health logger both call ``log`` concurrently with whoever owns
    the logger, so every method that touches the CSV/TB state serializes on
    one lock.

    ``registry`` (an ``obs.Registry``), when given, folds the registry's
    flat scalar snapshot into every row — extra columns only, so the
    existing return@wall-clock curves read off the CSV/TB unchanged.
    """

    def __init__(
        self,
        logdir: Optional[str] = None,
        *,
        csv_name: str = "metrics.csv",
        stdout: bool = True,
        tensorboard: bool = True,
        registry=None,
    ):
        self.logdir = logdir
        self.stdout = stdout
        self.t0 = time.monotonic()
        self._registry = registry
        self._lock = threading.RLock()
        self._csv_path: Optional[str] = None
        self._csv_file = None
        self._csv_writer = None
        self._csv_fields: Optional[list] = None
        self._tb = None
        self._last_rate_t: Optional[float] = None
        self._last_counts: Dict[str, float] = {}
        if logdir is not None:
            os.makedirs(logdir, exist_ok=True)
            self._csv_path = os.path.join(logdir, csv_name)
            if os.path.exists(self._csv_path):
                # Resume into an existing logdir: keep the old rows and
                # continue the wall-clock from where the previous run left
                # off, so the return@wall-clock curve survives a restart.
                with open(self._csv_path, newline="") as f:
                    old = list(csv.DictReader(f))
                if old:
                    self._csv_fields = list(old[0].keys())
                    try:
                        self.t0 -= max(
                            float(r["wall_seconds"]) for r in old
                            if r.get("wall_seconds")
                        )
                    except ValueError:
                        pass
            if tensorboard:
                try:
                    from tensorboardX import SummaryWriter

                    self._tb = SummaryWriter(logdir)
                except Exception:  # pragma: no cover - tbx is installed here
                    self._tb = None

    # ------------------------------------------------------------------ rates
    def rates(self, **counts: float) -> Dict[str, float]:
        """Steps/sec for monotone counters since the previous ``rates`` call.

        ``rates(env_steps=..., learner_steps=...)`` returns e.g.
        ``{"env_steps_per_sec": ..., "learner_steps_per_sec": ...}``.
        """
        with self._lock:
            now = time.monotonic()
            out: Dict[str, float] = {}
            if self._last_rate_t is not None:
                dt = max(now - self._last_rate_t, 1e-9)
                for k, v in counts.items():
                    prev = self._last_counts.get(k)
                    if prev is not None:
                        out[f"{k}_per_sec"] = (v - prev) / dt
            self._last_rate_t = now
            self._last_counts = dict(counts)
            return out

    # -------------------------------------------------------------------- log
    def log(self, step: int, scalars: Dict[str, float]) -> None:
        elapsed = time.monotonic() - self.t0
        row = {"step": step, "wall_seconds": round(elapsed, 3)}
        row.update({k: float(v) for k, v in scalars.items()})
        if self._registry is not None:
            # Bridge: registry snapshot folds in as EXTRA columns; explicit
            # scalars win a name collision (the curves stay canonical).
            for k, v in self._registry.scalars().items():
                row.setdefault(k, v)

        with self._lock:
            if self.stdout:
                body = " ".join(
                    f"{k} {v:.4g}" for k, v in row.items() if k != "step"
                )
                print(f"[{step}] {body}", flush=True)  # obs-lint: allow

            if self._csv_path is not None:
                if self._csv_writer is None or any(
                    k not in self._csv_fields for k in row
                ):
                    self._reopen_csv(row)
                self._csv_writer.writerow(
                    {k: row.get(k, "") for k in self._csv_fields}
                )
                self._csv_file.flush()

            if self._tb is not None:
                for k, v in row.items():
                    if k == "step":
                        continue
                    self._tb.add_scalar(k, v, global_step=step, walltime=None)

    def _reopen_csv(self, row: Dict[str, float]) -> None:
        """(Re)open the CSV; rewrite existing rows ONLY on a header change.

        Appending under an unchanged header is the common case (resume into
        an existing logdir, or a plain first open); the full
        read-all/rewrite-all pass — O(rows) per occurrence — happens only
        when a genuinely new column appears, not on every (re)open, so a
        long run no longer pays O(rows²) across its lifetime."""
        if self._csv_file is not None:
            self._csv_file.close()
            self._csv_file = self._csv_writer = None
        fields = list(
            dict.fromkeys(
                ["step", "wall_seconds"]
                + (self._csv_fields or [])
                + list(row)
            )
        )
        exists = os.path.exists(self._csv_path)
        if exists and self._csv_fields == fields:
            # Header already covers the row (e.g. resume): append, no rewrite.
            self._csv_file = open(self._csv_path, "a", newline="")
            self._csv_writer = csv.DictWriter(
                self._csv_file, fieldnames=fields
            )
            return
        old_rows = []
        if exists:
            with open(self._csv_path, newline="") as f:
                old_rows = list(csv.DictReader(f))
        self._csv_file = open(self._csv_path, "w", newline="")
        self._csv_writer = csv.DictWriter(self._csv_file, fieldnames=fields)
        self._csv_writer.writeheader()
        for r in old_rows:
            self._csv_writer.writerow({k: r.get(k, "") for k in fields})
        self._csv_fields = fields

    # ------------------------------------------------------------------ close
    def close(self) -> None:
        with self._lock:
            if self._csv_file is not None:
                self._csv_file.close()
                self._csv_file = self._csv_writer = None
            if self._tb is not None:
                self._tb.close()
                self._tb = None

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
