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

Beside the stage keys the table holds ``scopes``: the same events folded once
more in the same pass by EVERY name the program has (``ALL_SCOPES``, the
innermost wins) and by pass.  JAX writes the pass into the path: a ``grad``
through ``jax.checkpoint`` gives ``.../jvp(forward)/core_mlp/tanh`` (forward),
``.../transpose(jvp(forward))/.../checkpoint/core_mlp/mul`` (backward) and
``.../checkpoint/rematted_computation/core_mlp/dot_general`` (the forward pass
recomputed).  (A clone the compiler's own rematerialisation made,
``fusion.12.remat2``, goes by its path like any other: the original may or
may not run as well.)  And it holds ``programs`` / ``truncated``:
what the capture holds of the stretch it was taken over, counted from the
device's own events (the line ``XLA Modules``: one event an execution).
"""

from __future__ import annotations

import re
from typing import Any, Dict, Iterator, List, Optional, Sequence, Tuple

from r2d2dpg_tpu.utils.profiling import CORE_STAGES, LEARN_STAGES, SIDE_STAGES

# Derived keys of the table, beside the stage names themselves.
BACKWARD = "backward"  # ``forward`` under a ``transpose(...)``
REST = "rest"  # a path with none of the stage names on it
UNSCOPED = "unscoped"  # no path at all
DIFFERENTIATED = "forward"  # the stage whose transposes read as BACKWARD

# ``scopes``: every name the program has, and the rows beside them.
ALL_SCOPES = LEARN_STAGES + SIDE_STAGES + CORE_STAGES
LOOPS = "loops"  # control flow's own time under no scope (``_CONTROL``)
SCOPE_ROWS = ALL_SCOPES + (LOOPS, UNSCOPED, REST)
RECOMPUTED = "recomputed"  # the forward pass again, for the backward pass
PASSES = ("forward", RECOMPUTED, BACKWARD)
ALL = "all"  # a row's passes summed

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
METADATA_PLANE = "/host:metadata"
HOST_PLANE = "/host:CPU"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"  # one event an execution of a program
# The host spans a capture is taken over, and whether the span closes on a
# drained device: the benchmark's window waits for its last result; the
# trainer's train phases (``--profile-window``) are dispatched ahead of the
# device, so there the capture may well stop inside an execution.
CAPTURED_SPANS = {"chipbench/window": True, "trainer/train_phase": False}
# The device line may end this long before the host's span does and still
# hold all of it: the host's clock runs ahead of the device's and the span
# closes after the last result is back (0.65-2.64 ms apart at the end of
# twenty-one whole captures of the four benchmark cells, PERF.md PR 36; a
# capture that overran the profiler's buffer ended 837 ms early).
CLOCK_SLACK_NS = 5_000_000
# An execution's event outlasts its last operation's (by 6-7.5 us in those
# captures): it is whole if the device line ends within a hundredth of the
# execution's own length of it.
_WHOLE_SHARE = 0.01
_ROUNDING_NS = 10  # the file keeps picoseconds; ends are whole nanoseconds
# The device only waits for the host in these (the halves of a host
# callback's transfers): not busy time, as the benchmark's idle share has it.
HOST_WAIT = re.compile(r"^(recv|send)(-done)?(\.\d+)?$")
# Transform wrappers JAX puts around a name-stack entry.  ``jit(f)`` is not
# one: it names a function, not a scope.
_WRAPPER = re.compile(r"^(?:jvp|transpose|vmap)\((.*)\)$")
_TRANSPOSED = "transpose("
# Last segments that name a control-flow construct and no operation of the
# program: the ``while`` a scan becomes, its ``body`` and ``cond``, the
# ``closed_call`` a scan's step is.  What the compiler inserts inside a loop
# or at a call's boundary (copies, the rounding of a matmul's operand) it
# names by the construct: ``jit(f)/while``, ``jit(f)/while/body/closed_call``.
_CONTROL = re.compile(r"^(while|body|cond|closed_call|call|branch_\d+_fun)$")
_REMATTED = "rematted_computation"  # ``jax.checkpoint``'s recomputation


def table_keys(stages: Sequence[str] = LEARN_STAGES) -> Tuple[str, ...]:
    """Every key of ``stage_table``'s table that holds seconds of a stage."""
    derived = (BACKWARD,) if DIFFERENTIATED in stages else ()
    return tuple(stages) + derived + (UNSCOPED, REST)


def _bare(segment: str) -> str:
    """A path segment with its transform wrappers stripped."""
    while True:
        m = _WRAPPER.match(segment)
        if m is None:
            return segment
        segment = m.group(1)


def _innermost(
    segments: Sequence[str], names: Sequence[str]
) -> Tuple[int, Optional[str]]:
    """(index, name) of the innermost segment that, with its transform
    wrappers stripped, is one of ``names``; ``(-1, None)`` without one."""
    for i in range(len(segments) - 1, -1, -1):
        name = _bare(segments[i])
        if name in names:
            return i, name
    return -1, None


def stage_of(path: Optional[str], stages: Sequence[str] = LEARN_STAGES) -> str:
    """The stage of an ``op_name`` path: the innermost segment that, with its
    transform wrappers stripped, is a stage name.  ``forward`` with a
    ``transpose(`` on that segment or inside it is ``backward``."""
    if not path:
        return UNSCOPED
    segments = path.split("/")
    i, name = _innermost(segments, stages)
    if name is None:
        return REST
    if name == DIFFERENTIATED and any(_TRANSPOSED in s for s in segments[i:]):
        return BACKWARD
    return name


def scope_of(path: Optional[str]) -> str:
    """The row of ``scopes`` a path belongs to: the innermost of
    ``ALL_SCOPES`` on it, whatever its pass; with none on it ``loops`` if the
    path ends in a control-flow construct's own name (``_CONTROL``: the
    construct itself, or what the compiler put inside it under its name),
    else ``rest``."""
    if not path:
        return UNSCOPED
    segments = path.split("/")
    name = _innermost(segments, ALL_SCOPES)[1]
    if name is None:
        return LOOPS if _CONTROL.match(_bare(segments[-1])) else REST
    return name


def pass_of(path: Optional[str]) -> str:
    """The pass an operation belongs to: ``recomputed`` if a segment of its
    path holds ``rematted_computation``, else ``backward`` if a segment holds
    ``transpose(``, else ``forward``."""
    segments = (path or "").split("/")
    if any(_REMATTED in s for s in segments):
        return RECOMPUTED
    if any(_TRANSPOSED in s for s in segments):
        return BACKWARD
    return "forward"


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

    def events(self, line_name: Optional[str]) -> List[Tuple[int, int, int]]:
        """(start_ns, end_ns, metadata id) of every event of a line (of
        every line, for ``None``), in the
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
            if line_name is not None and name != line_name:
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


def _span_end(planes: Sequence[_Plane]) -> Tuple[Optional[int], bool]:
    """The end, on the host's clock, of the last span the capture was taken
    over (``CAPTURED_SPANS``), and whether it closed on a drained device;
    ``(None, False)`` where the host wrote none."""
    ends = []
    for p in planes:
        if p.name != HOST_PLANE:
            continue
        wanted = {k: CAPTURED_SPANS[rec["name"]]
                  for k, rec in p.event_metadata(()).items()
                  if rec["name"] in CAPTURED_SPANS}
        ends += [(e, wanted[k]) for _, e, k in p.events(None) if k in wanted]
    return max(ends, default=(None, False))


def _whole_executions(plane: _Plane, ops_end: int) -> List[Tuple[int, int, int]]:
    """The events of the line ``XLA Modules`` whose operations the line
    ``XLA Ops``, which ends at ``ops_end``, holds to the end."""
    return [
        (s, e, k) for s, e, k in plane.events(MODULES_LINE)
        if e - ops_end <= max(_WHOLE_SHARE * (e - s), _ROUNDING_NS)
    ]


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

    ``scopes`` is the same self time folded by ``ALL_SCOPES`` whatever
    ``stages`` is, and by pass: ``scopes[row]`` holds ``forward``,
    ``recomputed``, ``backward`` (``pass_of``) and ``all``, for every row of
    ``SCOPE_ROWS``; under no scope, a control-flow event (one with events
    inside it) and an operation named by a control-flow construct
    (``scope_of``) are the row ``loops``; the rows' ``all`` add up to
    ``busy``.  ``scope_ops[row][pass]`` lists the five operations with
    most self time there.  ``programs`` lists each program's name, its whole
    ``executions`` inside the capture (events of the line ``XLA Modules``
    whose operations the line ``XLA Ops`` holds to the end) and their
    ``seconds``, most seconds first: the updates a capture holds are the
    first one's executions times the updates of a call, which the caller
    knows.  ``truncated`` says the capture lost the tail of its device
    events: a device line that ends more than ``CLOCK_SLACK_NS`` before
    the host's span does (``host_after_ops``: by how many seconds it ended
    before it), or, under a span that closes on a drained device, one
    that holds operations after the last whole execution.
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

    folds: Dict[Optional[str], Tuple[str, str, str]] = {}

    def fold(path: Optional[str]) -> Tuple[str, str, str]:
        """(stage, row of ``scopes``, pass) of a path, parsed once."""
        if path not in folds:
            folds[path] = (stage_of(path, stages), scope_of(path), pass_of(path))
        return folds[path]

    seconds = dict.fromkeys(table_keys(stages), 0.0)
    scopes = {row: dict.fromkeys(PASSES, 0.0) for row in SCOPE_ROWS}
    scope_ops: Dict[Tuple[str, str], Dict[str, float]] = {}
    rest_paths: Dict[str, float] = {}
    unscoped_ops: Dict[str, float] = {}
    programs: Dict[str, List[int]] = {}  # name -> [whole executions, ns]
    span_end, drained = _span_end(planes) if devices else (None, False)
    truncated, host_after_ops = False, None
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
            stage, row, pass_ = fold(path)
            seconds[stage] += own
            if stage == REST:
                rest_paths[path] = rest_paths.get(path, 0.0) + own
            elif stage == UNSCOPED:
                unscoped_ops[short] = unscoped_ops.get(short, 0.0) + own
            if row == REST and kids:
                row = LOOPS
            scopes[row][pass_] += own
            by_op = scope_ops.setdefault((row, pass_), {})
            by_op[short] = by_op.get(short, 0.0) + own

        events.sort(key=lambda x: (x[0], -x[1]))
        for s, e, k in events:
            while stack and s >= stack[-1][0]:
                close()
            path = ops[k][1]
            if stack:
                stack[-1][3] += e - s
                path = path or stack[-1][4]  # unnamed inside a loop: the loop's
            stack.append([e, k, e - s, 0, path])
        while stack:
            close()

        if not events:
            continue
        # Where the line ends: with the event that starts last.  (An
        # enclosing ``while`` may have been kept whole where the tail of
        # its body was lost.)
        ops_end = events[-1][1]
        whole = _whole_executions(plane, ops_end)
        for s, e, k in whole:
            program = programs.setdefault(meta[k]["name"], [0, 0])
            program[0] += 1
            program[1] += e - s
        if drained and whole:
            last_whole = max(e for _, e, _ in whole)
            truncated = truncated or ops_end > last_whole + _ROUNDING_NS
        if span_end is not None:
            late = span_end - ops_end
            if host_after_ops is None or late > host_after_ops:
                host_after_ops = late
            truncated = truncated or late > CLOCK_SLACK_NS

    n = max(len(devices), 1)
    scale = 1e-9 / n
    table: Dict[str, Any] = {k: v * scale for k, v in seconds.items()}
    table["busy"] = sum(table.values())
    table["devices"] = len(devices)
    table["rest_paths"] = _top(rest_paths, 5, scale)
    table["unscoped_ops"] = _top(unscoped_ops, 5, scale)
    table["scopes"] = {}
    for row, passes in scopes.items():
        by_pass = {p: v * scale for p, v in passes.items()}
        by_pass[ALL] = sum(by_pass.values())
        table["scopes"][row] = by_pass
    table["scope_ops"] = {}
    for (row, pass_), by_op in scope_ops.items():
        table["scope_ops"].setdefault(row, {})[pass_] = _top(by_op, 5, scale)
    table["programs"] = [
        {"name": name.rsplit("(", 1)[0], "executions": round(count / n),
         "seconds": ns * scale}
        for name, (count, ns) in sorted(programs.items(), key=lambda kv: -kv[1][1])
    ]
    table["truncated"] = truncated
    table["host_after_ops"] = None if host_after_ops is None else host_after_ops * 1e-9
    return table
