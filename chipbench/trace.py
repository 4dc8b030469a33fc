"""From a profiler capture (``.xplane.pb``) to the numbers the per-layer
metrics read: the device's busy time, its idle gaps labelled by what the
host was doing, the operations that took most time, and the device time of
a named program.

Read with ``jax.profiler.ProfileData`` alone.  On a TPU each chip is a plane
``/device:TPU:<n>`` whose line ``XLA Ops`` holds one event per executed HLO
operation and whose line ``XLA Modules`` one event per executed program
(named ``<jit name>(<fingerprint>)``); the host's threads are lines of the
plane ``/host:CPU`` and carry the ``TraceAnnotation`` spans.  An operation's
event carries no ``jax.named_scope`` path on this runtime, so time is
attributed by program name, not by scope; the host's clock runs about a
millisecond ahead of the device's, so a gap shorter than that may be named by
the span next to its own.
"""

from __future__ import annotations

import re
from typing import Any, Dict, Iterable, List, Optional, Tuple

Interval = Tuple[float, float]  # (start_ns, end_ns)

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
# Operations in which the device only waits for the host (the two halves of a
# host callback's transfers): they are left out of the busy time, so that a
# chip stalled on the host reads as idle, and stay in the list of operations.
HOST_WAIT = re.compile(r"^(recv|send)(-done)?(\.\d+)?$")
# Host spans worth naming a gap by: the benchmark's own and the program's.
HOST_SPAN = re.compile(r"^(chipbench/|trainer/|PjitFunction|pipeline/)")


def merge(intervals: Iterable[Interval]) -> List[Interval]:
    """The union of intervals as sorted, disjoint intervals."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def gaps_of(merged: List[Interval]) -> List[Interval]:
    """The idle stretches between the busy ones."""
    return [(a[1], b[0]) for a, b in zip(merged, merged[1:]) if b[0] > a[1]]


def label_gap(gap: Interval, host_spans: List[Tuple[float, float, str]]) -> str:
    """The innermost host span that covers the middle of the gap."""
    mid = 0.5 * (gap[0] + gap[1])
    best: Optional[Tuple[float, str]] = None
    for s, e, name in host_spans:
        if s <= mid <= e and (best is None or e - s < best[0]):
            best = (e - s, name)
    return best[1] if best else "no host span"


def events_of(plane, line_name: str) -> List[Tuple[float, float, str]]:
    """(start_ns, end_ns, name) of every event of a plane's line."""
    out = []
    for line in plane.lines:
        if line.name != line_name:
            continue
        for ev in line.events:
            s = float(ev.start_ns)
            out.append((s, s + float(ev.duration_ns), ev.name))
    return out


def short_name(name: str) -> str:
    """An operation's event is named by its whole HLO instruction; keep the
    instruction's own name (``%fusion.12 = ...`` -> ``fusion.12``)."""
    return name.split(" = ", 1)[0].lstrip("%")[:64]


def self_seconds(events: List[Tuple[float, float, str]]) -> Dict[str, float]:
    """Nanoseconds each operation spent itself, by name.  A ``while`` or a
    call holds its body's operations as events inside its own interval; those
    are taken out of it, so that the names add up to the busy time."""
    out: Dict[str, float] = {}
    stack: List[List[Any]] = []  # [end, name, duration, children]

    def close():
        end, name, dur, kids = stack.pop()
        out[name] = out.get(name, 0.0) + max(dur - kids, 0.0)

    for s, e, name in sorted(events, key=lambda x: (x[0], -x[1])):
        while stack and s >= stack[-1][0]:
            close()
        if stack:
            stack[-1][3] += e - s
        stack.append([e, name, e - s, 0.0])
    while stack:
        close()
    return out


def reduce(profile, window_s: float) -> Dict[str, Any]:
    """Reduce a ``ProfileData`` to what the metrics read.

    ``window_s`` is the host-clock length of the captured block; busy time is
    the union of the intervals of operations other than waits for the host
    (``HOST_WAIT``), averaged over the device planes.
    """
    device_planes = [p for p in profile.planes if DEVICE_PLANE.match(p.name)]
    host_spans: List[Tuple[float, float, str]] = []
    for p in profile.planes:
        if p.name != "/host:CPU":
            continue
        for line in p.lines:
            for ev in line.events:
                if HOST_SPAN.match(ev.name):
                    s = float(ev.start_ns)
                    host_spans.append((s, s + float(ev.duration_ns), ev.name))

    busy, by_op, modules, gap_list = [], {}, {}, []
    for plane in device_planes:
        ops = events_of(plane, OPS_LINE)
        merged = merge((s, e) for s, e, n in ops
                       if not HOST_WAIT.match(short_name(n)))
        busy.append(sum(e - s for s, e in merged))
        for name, ns in self_seconds(
            [(s, e, short_name(n)) for s, e, n in ops]
        ).items():
            by_op[name] = by_op.get(name, 0.0) + ns
        for s, e, name in events_of(plane, MODULES_LINE):
            key = name.split("(")[0]
            tot, n = modules.get(key, (0.0, 0))
            modules[key] = (tot + (e - s), n + 1)
        if plane is device_planes[0]:
            gap_list = gaps_of(merged)
    n_dev = max(len(device_planes), 1)
    top_gaps: Dict[str, float] = {}
    for g in sorted(gap_list, key=lambda g: g[0] - g[1])[:200]:
        name = label_gap(g, host_spans)
        top_gaps[name] = top_gaps.get(name, 0.0) + (g[1] - g[0])
    return {
        "devices": len(device_planes),
        "busy_s": sum(busy) / n_dev / 1e9,
        "window_s": float(window_s),
        "top_ops": [[k, v / n_dev / 1e9] for k, v in
                    sorted(by_op.items(), key=lambda kv: -kv[1])[:10]],
        "top_gaps": [[k, v / 1e9] for k, v in
                     sorted(top_gaps.items(), key=lambda kv: -kv[1])[:10]],
        "module_s": {k: (v[0] / n_dev / 1e9, v[1]) for k, v in modules.items()},
    }


def reduce_file(path: Optional[str], window_s: float) -> Dict[str, Any]:
    from jax.profiler import ProfileData

    if not path:
        raise FileNotFoundError("the profiler wrote no .xplane.pb")
    return reduce(ProfileData.from_file(path), window_s)


def seconds_of_program(reduction: Dict[str, Any], program: str) -> Optional[Tuple[float, int]]:
    """(device seconds, executions) of the program named ``program``."""
    return reduction["module_s"].get(program)
