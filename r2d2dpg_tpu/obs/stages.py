"""Device time by stage of the learner call, from a profiler capture.

``utils/profiling.py::scope`` names reach the chip's trace, but not through
``jax.profiler.ProfileData``, which shows an event's own stats only.  The
path of an executed instruction is in the capture (``.xplane.pb``, an
``XSpace`` proto) in two places:

- the device plane's **event metadata** carries the stat ``tf_op``
  (``<op_name>:<op_type>``) for every instruction the program wrote;
- the plane ``/host:metadata`` carries one ``Hlo Proto`` per executed program,
  whose instructions carry ``metadata.op_name``, the control-flow instructions
  (``while``, ``conditional``, ``call``) included, which have no ``tf_op``.

An instruction the compiler inserted at top level (a layout copy, the bf16
rounding of a matmul operand hoisted out of every loop) has neither, and is
``unscoped``; what it inserts inside a loop the TPU compiler itself names by
the loop (``jit(f)/while``, a path with no stage on it), or leaves without a
name: the reader then names it by the loop too, from the ``while`` event its
own event lies in (the slice, the re-lay and the write of the loop a large
gather becomes carried ``.../replay_sample/gather`` in one compiled program
and nothing in the next, PERF.md PR 30).

``stage_table`` is the one reader: ``DeviceMonitor`` writes its table for a
``--profile-window`` capture, and the benchmark's ``learn_stage_ms.*`` metrics
read it (``chipbench/reducers/stage_ms.py``).  The file is read with a small
wire-format reader of the few fields needed: no ``tensorflow``, no ``protobuf``.
"""

from __future__ import annotations

import re
from typing import Any, Dict, Iterator, List, Optional, Sequence, Tuple

from r2d2dpg_tpu.utils.profiling import LEARN_STAGES

# Derived keys of the table, beside the stage names themselves.
BACKWARD = "backward"  # ``forward`` under a ``transpose(...)``
REST = "rest"  # a path with none of the stage names on it
UNSCOPED = "unscoped"  # no path at all
DIFFERENTIATED = "forward"  # the stage whose transposes read as BACKWARD

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
METADATA_PLANE = "/host:metadata"
OPS_LINE = "XLA Ops"
# The device only waits for the host in these (the halves of a host
# callback's transfers): not busy time, as the benchmark's idle share has it.
HOST_WAIT = re.compile(r"^(recv|send)(-done)?(\.\d+)?$")
# Transform wrappers JAX puts around a name-stack entry.  ``jit(f)`` is not
# one: it names a function, not a scope.
_WRAPPER = re.compile(r"^(?:jvp|transpose|vmap)\((.*)\)$")
_TRANSPOSED = "transpose("


def table_keys(stages: Sequence[str] = LEARN_STAGES) -> Tuple[str, ...]:
    """Every key of ``stage_table``'s table that holds seconds of a stage."""
    derived = (BACKWARD,) if DIFFERENTIATED in stages else ()
    return tuple(stages) + derived + (UNSCOPED, REST)


def stage_of(path: Optional[str], stages: Sequence[str] = LEARN_STAGES) -> str:
    """The stage of an ``op_name`` path: the innermost segment that, with its
    transform wrappers stripped, is a stage name.  ``forward`` with a
    ``transpose(`` on that segment or inside it is ``backward``."""
    if not path:
        return UNSCOPED
    segments = path.split("/")
    for i in range(len(segments) - 1, -1, -1):
        name = segments[i]
        while True:
            m = _WRAPPER.match(name)
            if m is None:
                break
            name = m.group(1)
        if name in stages:
            if name == DIFFERENTIATED and any(
                _TRANSPOSED in s for s in segments[i:]
            ):
                return BACKWARD
            return name
    return REST


# ------------------------------------------------------------- wire format
def _varint(buf: bytes, i: int) -> Tuple[int, int]:
    result = shift = 0
    while True:
        b = buf[i]
        i += 1
        result |= (b & 0x7F) << shift
        if b < 0x80:
            return result, i
        shift += 7


def _fields(buf: bytes) -> Iterator[Tuple[int, Any]]:
    """(field number, value) of every field of a message: an int for a
    varint, the bytes of a length-delimited or fixed-width field."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value = buf[i:i + size]
            i += size
        elif wire == 1:
            value = buf[i:i + 8]
            i += 8
        elif wire == 5:
            value = buf[i:i + 4]
            i += 4
        else:
            raise ValueError(f"wire type {wire} at byte {i}: not an XSpace")
        yield key >> 3, value


def _first(buf: bytes, field: int, default=None):
    for f, v in _fields(buf):
        if f == field:
            return v
    return default


def _map_entry(buf: bytes) -> Tuple[int, bytes]:
    key, value = 0, b""
    for f, v in _fields(buf):
        if f == 1:
            key = v
        elif f == 2:
            value = v
    return key, value


class _Plane:
    """The fields of one ``XPlane`` this reader needs."""

    def __init__(self, buf: bytes):
        self.name = ""
        self.lines: List[bytes] = []
        self._event_metadata: Dict[int, bytes] = {}
        self.stat_names: Dict[int, str] = {}
        for f, v in _fields(buf):
            if f == 2:
                self.name = v.decode("utf-8", "replace")
            elif f == 3:
                self.lines.append(v)
            elif f == 4:
                k, m = _map_entry(v)
                self._event_metadata[k] = m
            elif f == 5:
                k, m = _map_entry(v)
                self.stat_names[k] = _first(m, 2, b"").decode("utf-8", "replace")

    def event_metadata(self, wanted: Sequence[str]) -> Dict[int, Dict[str, Any]]:
        """id -> ``name``, ``display_name`` and the ``wanted`` stats."""
        out = {}
        for k, m in self._event_metadata.items():
            rec: Dict[str, Any] = {"name": "", "display_name": ""}
            for f, v in _fields(m):
                if f == 2:
                    rec["name"] = v.decode("utf-8", "replace")
                elif f == 4:
                    rec["display_name"] = v.decode("utf-8", "replace")
                elif f == 5:
                    stat = dict(_fields(v))
                    name = self.stat_names.get(stat.get(1))
                    if name not in wanted:
                        continue
                    if 7 in stat:  # ref_value: a string kept as a stat name
                        rec[name] = self.stat_names.get(stat[7], "")
                    else:
                        rec[name] = next(
                            (stat[n] for n in (5, 6, 3, 4) if n in stat), None
                        )
            out[k] = rec
        return out

    def events(self, line_name: str) -> List[Tuple[int, int, int]]:
        """(start_ns, end_ns, metadata id) of every event of a line, in the
        whole nanoseconds ``jax.profiler.ProfileData`` gives (the file keeps
        picoseconds), so that this reader and one built on ``ProfileData``
        add up the same numbers."""
        out = []
        for line in self.lines:
            name, t0_ns, events = "", 0, []
            for f, v in _fields(line):
                if f == 2:
                    name = v.decode("utf-8", "replace")
                elif f == 3:
                    t0_ns = v
                elif f == 4:
                    events.append(v)
            if name != line_name:
                continue
            for ev in events:
                mid = off = dur = 0
                for f, v in _fields(ev):
                    if f == 1:
                        mid = v
                    elif f == 2:
                        off = v
                    elif f == 3:
                        dur = v
                start = t0_ns + off // 1000
                out.append((start, start + dur // 1000, mid))
        return out


def _hlo_op_names(hlo_proto: bytes) -> Dict[str, str]:
    """instruction name -> ``metadata.op_name`` of an ``HloProto``."""
    out = {}
    module = _first(hlo_proto, 1, b"")
    for f, comp in _fields(module):
        if f != 3:
            continue
        for g, instr in _fields(comp):
            if g != 2:
                continue
            name, op_name = "", ""
            for h, v in _fields(instr):
                if h == 1:
                    name = v.decode("utf-8", "replace")
                elif h == 7:
                    op_name = _first(v, 2, b"").decode("utf-8", "replace")
            if name and op_name:
                out[name.lstrip("%")] = op_name
    return out


def _short(name: str) -> str:
    """``%fusion.12 = f32[8]{0} fusion(...)`` -> ``fusion.12``."""
    return name.split(" = ", 1)[0].lstrip("%")


def _top(d: Dict[str, float], n: int, scale: float) -> List[List[Any]]:
    return [[k, v * scale] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:n]]


def stage_table(
    xplane_path: str, stages: Sequence[str] = LEARN_STAGES
) -> Dict[str, Any]:
    """Device seconds of each stage in a capture.

    Per event of the line ``XLA Ops`` of each device plane: its self time
    (the events inside its interval taken out, so that a ``while`` keeps
    only what its body does not account for), attributed to the stage of its
    path (an event without one takes the path of the event it lies in);
    waits for the host are left out.  Returns, in seconds averaged over
    the device planes: one entry per key of ``table_keys(stages)``, ``busy``
    (their sum: the time the chip spent in operations, which is the union of
    their intervals less the waits for the host inside them), and for the
    reader of the table ``devices``, ``rest_paths`` and ``unscoped_ops`` (the
    five largest of each, ``[name, seconds]``).
    """
    with open(xplane_path, "rb") as f:
        space = f.read()
    planes = [_Plane(v) for f, v in _fields(space) if f == 1]
    devices = [p for p in planes if DEVICE_PLANE.match(p.name)]

    hlo_protos: Dict[int, bytes] = {}
    for p in planes:
        if p.name == METADATA_PLANE:
            for k, rec in p.event_metadata(("Hlo Proto",)).items():
                if rec.get("Hlo Proto"):
                    hlo_protos[k] = rec["Hlo Proto"]
    op_names: Dict[int, Dict[str, str]] = {}  # program id -> parsed, on demand

    def from_program(program_id, instruction: str) -> Optional[str]:
        if program_id not in hlo_protos:
            return None
        if program_id not in op_names:
            op_names[program_id] = _hlo_op_names(hlo_protos[program_id])
        return op_names[program_id].get(instruction)

    seconds = dict.fromkeys(table_keys(stages), 0.0)
    rest_paths: Dict[str, float] = {}
    unscoped_ops: Dict[str, float] = {}
    for plane in devices:
        meta = plane.event_metadata(("tf_op", "program_id"))
        ops: Dict[int, Tuple[str, Optional[str], bool]] = {}
        for k, rec in meta.items():
            short = rec["display_name"] or _short(rec["name"])
            tf_op = rec.get("tf_op") or b""
            path = tf_op.decode("utf-8", "replace").rsplit(":", 1)[0] or None
            if path is None:
                path = from_program(rec.get("program_id"), _short(rec["name"]))
            ops[k] = (short, path, bool(HOST_WAIT.match(short)))

        events = plane.events(OPS_LINE)
        stack: List[list] = []  # [end, id, duration, children, path]

        def close():
            _, k, dur, kids, path = stack.pop()
            short, _, host_wait = ops[k]
            if host_wait:
                return
            own = max(dur - kids, 0)
            stage = stage_of(path, stages)
            seconds[stage] += own
            if stage == REST:
                rest_paths[path] = rest_paths.get(path, 0.0) + own
            elif stage == UNSCOPED:
                unscoped_ops[short] = unscoped_ops.get(short, 0.0) + own

        for s, e, k in sorted(events, key=lambda x: (x[0], -x[1])):
            while stack and s >= stack[-1][0]:
                close()
            path = ops[k][1]
            if stack:
                stack[-1][3] += e - s
                path = path or stack[-1][4]  # unnamed inside a loop: the loop's
            stack.append([e, k, e - s, 0, path])
        while stack:
            close()

    scale = 1e-9 / max(len(devices), 1)
    table: Dict[str, Any] = {k: v * scale for k, v in seconds.items()}
    table["busy"] = sum(table.values())
    table["devices"] = len(devices)
    table["rest_paths"] = _top(rest_paths, 5, scale)
    table["unscoped_ops"] = _top(unscoped_ops, 5, scale)
    return table
