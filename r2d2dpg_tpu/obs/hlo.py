"""What a compiled program does to the replay and to the batch drawn from it,
read from its HLO text.

``ReplayArena.sample`` keeps the sampled batch in the arena's own dtypes so
that the TPU compiler cannot round the whole arena to bfloat16 once a call
(``replay/arena.py``, "The sampled batch is a boundary").  Only the chip's
compiler makes that rewrite, so no CPU test can show it; ``arena_converts``
is the reader of the check that can: ``chip_smoke.py``'s train leg compiles
``walker_r2d2``'s learner call on the chip and requires the list to be empty
(``docs/OBSERVABILITY.md``, "The whole-arena convert guard").

``sample`` also states the device layout of the rows it gathers: batch
major-most, so that a sequence is written once into a stretch of its own
(``replay/arena.py::_gather_rows``).  Left to the compiler, the gather's loop
keeps its ``[B, ...]`` accumulator in the arena's own order, batch minor-most,
and every one of its B iterations rewrites the whole buffer to fill one lane
of each tile (59 of cheetah's 70 ms an update, PERF.md PR 28).  A layout is
stated or it is not, and only the chip's compiler lays a loop out:
``batch_minor_writes`` reads it from the same text, and the same leg requires
that list to be empty too.

``sample`` draws its B slots from the priority vector in two levels: one
pass for the sums of blocks of slots, a CDF over those, a running sum inside
the B drawn blocks alone (``replay/arena.py::_draw_proportional``).  A running
sum over the vector itself the TPU compiler turns into a ``reduce-window`` of
128 adds an element: 13 % of walker's update at 524,288 slots, under no
scope's name (PERF.md PR 32).  ``capacity_scans`` lists the running sums as
long as the arena, and the same leg requires that there is none.

``models/sequence.py::Stepped`` takes what of a net's step does not depend on
the carry out of its scans, so that a pixel torso's convolutions run once over
the T·B frames of a pass and not once a step over B (5.9 of cheetah's 16.5 ms
an update, PERF.md PR 30).  Whether a net's prefix was taken out is a fact of
the compiled program: ``loop_convolutions`` lists the image convolutions that
sit inside ``while`` bodies and how many loops deep, and the same leg requires
that none lies deeper than the learner call's own loop over its updates.

``ReplayArena`` stores a large row as whole tiles behind a major-most slot
axis, so that one sequence is one stretch of memory
(``replay/arena.py::_storage_parts``).  Stored in the rows' own shape the
pixel leaf lay slot minor-most, and each of the B rows of a batch was read as
a slice padded to 128 times its bytes (70.8 MB for 0.55 MB; 9.4 of cheetah's
11.5 ms an update, PERF.md PR 34); gathered from the tiles by ``buf[indices]``
the compiler first slices the whole leaf in two.  ``arena_reads`` lists every
slice or copy of a ``[capacity, ...]`` value that moves more than a few rows'
bytes, with the loops around it, and the same leg requires that an update
makes none.

The learner brings the sampled frames of a pixel batch to the order its first
convolution reads ONCE an update, for all stored steps, and every pass of the
torso reads its window as a range of that one array
(``models/torsos.py::ConvTorso.prepare``, ``models/sequence.py::Stepped``).
Cut first and prepared by every pass, the same 17.7 MB of frames went through
eleven copies and slices an update, a third of cheetah's 2.78 ms (PERF.md PR
35).  ``frame_relays`` lists every ``copy``, ``slice``, ``transpose`` or
``reshape`` that writes a value of a window's frames or more, with its bytes
as laid out and the loops around it; the same leg holds the pixel
configuration's learner call to the few the preparation needs.  Since PR 39
the prepared frames are cut into blocks of ``Conv_0``'s stride, so that it
reads them as a stride-1 convolution over 16·C channels;
``frame_contractions`` says how everything that reads them contracts, and
the same leg requires a stride-1 convolution of each.

``ops/pallas/scatter.py`` writes a batch's priorities back by the lane-rows
they land in and leaves the vector where it is: the Mosaic call takes it as
an operand aliased to its result, so a vector whose length is a multiple of
128 is neither padded nor copied on its way in (a vector held whole in the
kernel's VMEM cost ``B x capacity`` selects, 10.6 % of walker's update,
PERF.md PR 37).  Whether the call updates in place, and what the compiler
moves of the vector around it, is in the compiled text alone:
``priority_writes`` lists the call and every copy, pad or slice of a vector's
length under the scope ``priority_update`` or next to the call, and the same
leg holds every learner call to a call in place inside the loop over the
updates and, where the length is a multiple of 128, to no such move that the
program waits for.

``models/ouro_loop.py`` runs 4 layers 4 times by a scan inside a scan, so that
the compiled learner call holds one copy of a block a pass and not sixteen
(its compile is part of every process's set-up).  ``loop_products`` lists the
products of a given width with the loops around each; the same leg requires
the looped configuration's to lie inside both scans, and to be few.

The TPU compiler fits a program to the chip's memory by cloning instructions
and running them again where their results are needed (its own
rematerialisation, beside what ``jax.checkpoint`` asks for): a clone carries
the name of what it copies with ``.remat`` after it.  A value a program
keeps for its backward pass past the compiler's limit comes back as such
clones (``models/sdar_moe.py::moe``'s held experts).  ``remat_clones`` lists
them; the same leg prints their number beside the compiler's own count of
the call's peak memory, and refuses nothing.
"""

from __future__ import annotations

import math
import re
from typing import Dict, List, Tuple

# ``  %convert.390 = bf16[524288,43,24]{0,2,1:T(8,128)(2,1)} convert(%x), ...``
# (``ROOT`` before the name inside a fusion, no ``%`` in some printers).
_INSTRUCTION = re.compile(
    r"^\s*(?:ROOT\s+)?%?(?P<name>[\w.\-]+)\s*=\s*"
    r"(?P<shape>\w+\[(?P<lead>\d+)(?P<rest>[\d,]*)\])"
    r"(?:\{(?P<order>[\d,]*)(?P<tiling>[^}\s]*)\})?\S*\s+(?P<opcode>[\w\-]+)\(",
    re.MULTILINE,
)


def _dims(m: "re.Match[str]") -> List[int]:
    """The dimensions of a matched instruction's result."""
    return [int(m["lead"])] + [int(d) for d in m["rest"].split(",") if d]


def arena_converts(hlo_text: str, capacity: int) -> List[Tuple[str, str]]:
    """``(name, shape)`` of every ``convert`` in ``hlo_text`` (a compiled
    program's ``as_text()``) whose result has ``capacity`` as its leading
    dimension, fused or not, in the order printed."""
    return [
        (m["name"], m["shape"])
        for m in _INSTRUCTION.finditer(hlo_text)
        if m["opcode"] == "convert" and int(m["lead"]) == capacity
    ]


def batch_minor_writes(hlo_text: str, batch: int) -> List[Tuple[str, str]]:
    """``(name, shape with its minor-to-major order)`` of every
    ``dynamic-update-slice`` in ``hlo_text`` whose result is a ``[batch, ...]``
    array of rank two or more with dimension 0 minor-most, fused or not, in
    the order printed: an insertion along the batch that has to rewrite every
    tile of its buffer."""
    return [
        (m["name"], f"{m['shape']}{{{m['order']}}}")
        for m in _INSTRUCTION.finditer(hlo_text)
        if m["opcode"] == "dynamic-update-slice"
        and int(m["lead"]) == batch
        and m["rest"]
        and (m["order"] or "").split(",")[0] == "0"
    ]


# ``%wide.region_3.12 (wide.param: (s32[], ...)) -> (s32[], ...) {`` opens a
# computation (``ENTRY`` before the program's own); a ``}`` at column 0 ends it.
_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?(?P<name>[\w.\-]+)\s+\(.*\{\s*$")
# The computations an instruction runs: ``body=%b``, ``calls=%f``,
# ``to_apply=%r``, ``branch_computations={%a, %b}``...
_CALLED = re.compile(
    r"\b(?P<how>body|condition|calls|to_apply|\w+_computations?)="
    r"(?:\{(?P<many>[^}]*)\}|%?(?P<one>[\w.\-]+))")
_WINDOW = re.compile(
    r"window=\{size=(?P<size>\d+(?:x\d+)*)(?:[^}]*?lhs_dilate=(?P<dilate>\d+(?:x\d+)*))?")


def _computations(hlo_text: str) -> Tuple[Dict[str, List[str]], Dict[str, int]]:
    """The lines of every computation of ``hlo_text`` by its name, and the
    ``while`` bodies between the program's entry and each computation, the
    most over the ways it is reached."""
    lines: Dict[str, List[str]] = {}
    current = None
    for line in hlo_text.splitlines():
        opened = _COMPUTATION.match(line)
        if opened:
            current = opened["name"]
            lines[current] = []
        elif line.startswith("}"):
            current = None
        elif current is not None:
            lines[current].append(line)

    # Loops around each computation: a walk down from the computations that
    # nothing calls (the entry; more in a text cut out of a program).
    called = {
        name: [
            (callee, m["how"] in ("body", "condition"))
            for line in body
            for m in _CALLED.finditer(line)
            for callee in re.findall(r"[\w.\-]+", m["many"] or m["one"])
        ]
        for name, body in lines.items()
    }
    callees = {callee for edges in called.values() for callee, _ in edges}
    depth: Dict[str, int] = {}
    stack = [(name, 0) for name in lines if name not in callees]
    while stack:
        name, d = stack.pop()
        if name not in lines or depth.get(name, -1) >= d:
            continue
        depth[name] = d
        stack.extend((callee, d + loop) for callee, loop in called[name])
    return lines, depth


# ``%while.3 = (s32[], f32[524288]) while(%tuple.2), condition=%cond.3, body=%body.3``
# and, in its condition, the constant the counter is held to:
# ``%constant.2 = s32[]{:T(128)} constant(524288)``.
_LOOP = re.compile(
    r"^\s*(?:ROOT\s+)?%?(?P<name>[\w.\-]+)\s*=.*\swhile\(.*"
    r"\bcondition=%?(?P<condition>[\w.\-]+)")
_BOUND = re.compile(r"=\s*[su]\d+\[\]\S*\s+constant\((?P<bound>\d+)\)")


def capacity_scans(hlo_text: str, capacity: int) -> List[Tuple[str, str]]:
    """``(name, what)`` of every running sum in ``hlo_text`` that is as long
    as the arena, in the order printed:

    - a ``reduce-window`` over more than one position whose result holds
      ``capacity`` elements or more, fused or not (a running sum's result has
      its operand's shape; ``what`` is the shape and the window,
      ``f32[4096,128] window 1x128``: the TPU compiler's ``cumsum`` of a
      ``[524288]`` vector, 128 adds an element);
    - a ``while`` whose condition holds its counter to a constant of
      ``capacity`` or more (``what`` is ``loop of 524288 steps``): a sum
      carried through a loop a slot at a time."""
    lines, _ = _computations(hlo_text)
    found = []
    for line in hlo_text.splitlines():
        m, loop = _INSTRUCTION.match(line), _LOOP.match(line)
        if m and m["opcode"] == "reduce-window" and math.prod(_dims(m)) >= capacity:
            w = _WINDOW.search(line)
            if w and any(int(n) > 1 for n in w["size"].split("x")):
                found.append((m["name"], f"{m['shape']} window {w['size']}"))
        if loop:
            bounds = [
                int(b["bound"])
                for held in lines.get(loop["condition"], [])
                for b in _BOUND.finditer(held)
            ]
            if bounds and max(bounds) >= capacity:
                found.append((loop["name"], f"loop of {max(bounds)} steps"))
    return found


_ITEMSIZE = {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2,
             "bf16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8}
_TILE = re.compile(r"T\((\d+(?:,\d+)*)\)")
_OPERAND = re.compile(r"\s*%?([\w.\-]+)")


def _itemsize(m: "re.Match[str]") -> int:
    """The bytes of one element of a matched instruction's result."""
    return _ITEMSIZE.get(m["shape"].split("[")[0], 4)


def _laid_out_bytes(m: "re.Match[str]") -> int:
    """The bytes a matched instruction's result takes as laid out: its
    minor-most dimensions padded to the layout's first tile (``T(8,128)``:
    the second-minor to 8, the minor-most to 128; a tile's own sub-tiling,
    ``(4,1)``, packs and does not pad).  Without a layout, the values'."""
    dims = _dims(m)
    order = [int(d) for d in (m["order"] or "").split(",") if d]
    tile = _TILE.search(m["tiling"] or "")
    if tile and len(order) == len(dims):
        sizes = [int(t) for t in tile[1].split(",")]
        for d, t in zip(order, reversed(sizes)):
            dims[d] = -(-dims[d] // t) * t
    return math.prod(dims) * _itemsize(m)


def arena_reads(
    hlo_text: str, capacity: int, rows: int = 4
) -> List[Tuple[str, str, int, int, int]]:
    """``(name, shape with its layout, bytes as laid out, bytes of one row,
    loops around it)`` of every ``slice``, ``dynamic-slice`` or ``copy`` in
    ``hlo_text`` whose operand has ``capacity`` as its leading dimension and
    whose result, padding included, takes more than ``rows`` rows' bytes (a
    row: the operand's other dimensions), fused or not, in the order printed.

    Two things read this way: one row taken out of a slot-minor leaf, which
    the TPU compiler pads to whole tiles of 128 slots
    (``u8[1,45,64,64,3]{0,3,4,2,1:T(8,128)(4,1)}``: 70.8 MB for a row of 0.55
    MB), and a leaf sliced or re-laid whole (``[capacity, ...]`` results).  A
    row read as it is stored takes one row's bytes and is not listed."""
    lines, depth = _computations(hlo_text)
    found = []
    for name, body in lines.items():
        # The ``[capacity, ...]`` values of this computation, by name.
        stored = {}
        for line in body:
            m = _INSTRUCTION.match(line)
            if not m:
                continue
            if m["opcode"] in ("slice", "dynamic-slice", "copy"):
                operand = _OPERAND.match(line, m.end())
                row = stored.get(operand[1]) if operand else None
                if row and _laid_out_bytes(m) > rows * row:
                    layout = f"{{{m['order']}{m['tiling']}}}" if m["order"] else ""
                    found.append((m["name"], m["shape"] + layout,
                                  _laid_out_bytes(m), row, depth.get(name, 0)))
            if int(m["lead"]) == capacity and m["rest"]:
                stored[m["name"]] = math.prod(_dims(m)[1:]) * _itemsize(m)
    return found


def arena_relays(hlo_text: str, capacity: int) -> List[Tuple[str, str, int, int]]:
    """``(name, shape with its layout, bytes as laid out, loops around it)``
    of every ``copy`` in ``hlo_text`` whose result is a whole ``[capacity,
    ...]`` value of rank two or more, of 128 elements or more a slot, laid
    out in HBM (no ``S(1)``), fused or not, in the order printed: an arena
    leaf re-laid whole.

    A small row stored in its own shape lies slot minor-most, and where the
    leaf is small enough the TPU compiler copies all of it into a slot-major
    order once a call before it gathers (``f32[12288,45,6]{0,1,2}`` to
    ``{1,2,0}``: 0.054 ms of ``cheetah_pixels``' 1.41 ms update; the two
    whole-sequence cells' ``obs`` and ``action``, 6.4 ms a call; PERF.md PR
    40).  Stored as its whole lane-rows and the rest
    (``replay/arena.py::_storage_parts``) the whole lane-rows lie slot-major
    and are gathered where they lie.  A part of fewer than 128 elements a
    slot has no shape of its own bytes that lies slot-major, and the
    compiler may re-lay it (under 512 B a slot of float32): not listed.  Nor
    is a copy into VMEM (``S(1)``), the compiler staging a leaf next to the
    gather."""
    lines, depth = _computations(hlo_text)
    found = []
    for name, body in lines.items():
        for line in body:
            m = _INSTRUCTION.match(line)
            if (m and m["opcode"] == "copy" and int(m["lead"]) == capacity
                    and m["rest"] and math.prod(_dims(m)[1:]) >= 128
                    and "S(1)" not in (m["tiling"] or "")):
                layout = f"{{{m['order']}{m['tiling']}}}" if m["order"] else ""
                found.append((m["name"], m["shape"] + layout,
                              _laid_out_bytes(m), depth.get(name, 0)))
    return found


def _written(lines: Dict[str, List[str]]) -> Dict[str, bool]:
    """Whether the instructions of each computation write their results: not
    those of a fusion's body reached only from inside another fusion's (a
    producer fused into its consumer's operand, which the consumer reads
    through and nobody writes)."""
    fused_by: Dict[str, List[str]] = {}
    for name, body in lines.items():
        for line in body:
            for m in _CALLED.finditer(line):
                if m["how"] == "calls":
                    fused_by.setdefault(m["one"] or "", []).append(name)
    return {
        name: any(caller not in fused_by for caller in fused_by.get(name, [name]))
        for name in lines
    }


_RELAYS = ("copy", "slice", "transpose", "reshape")


def frame_relays(hlo_text: str, elements: int) -> List[Tuple[str, str, int, int]]:
    """``(name, shape with its layout, bytes as laid out, loops around it)``
    of every ``copy``, ``slice``, ``transpose`` or ``reshape`` in ``hlo_text``
    whose result holds ``elements`` elements or more and is written to
    memory, in the order printed.  ``elements`` is a window of a sampled
    batch's frames (``cheetah_pixels``: 32 sequences x 20 steps x 12,288).

    What the TPU compiler prints under these four names moves bytes and
    computes nothing (a reshape that moves none is printed as a ``bitcast``).
    On its own or as part of a fusion that a program's own computation
    calls, its result is written: a window cut out of the batch, a pass's
    frames brought to another order.  Inside a fusion called from inside
    another (a convolution's fusion reading its window as a slice of the
    prepared frames) nothing is written, and it is not listed."""
    lines, depth = _computations(hlo_text)
    written = _written(lines)
    found = []
    for name, body in lines.items():
        if not written[name]:
            continue
        for line in body:
            m = _INSTRUCTION.match(line)
            if m and m["opcode"] in _RELAYS and math.prod(_dims(m)) >= elements:
                layout = f"{{{m['order']}{m['tiling']}}}" if m["order"] else ""
                found.append((m["name"], m["shape"] + layout,
                              _laid_out_bytes(m), depth.get(name, 0)))
    return found


_SCOPE = "frames"
# Any instruction, a tuple result too: its opcode is the word before the
# parenthesis that opens its operands (``fusion(%a, %b)``).
_READER = re.compile(
    r"^\s*(?:ROOT\s+)?%?(?P<name>[\w.\-]+)\s*=.*?\s(?P<opcode>[\w\-]+)\((?=%)")


def _operands(m: "re.Match[str]", line: str) -> List[str]:
    """The names a matched instruction's line takes as its operands."""
    return re.findall(r"%([\w.\-]+)", line[m.end():].split("), ")[0])


def _contraction(lines: Dict[str, List[str]], line: str) -> Tuple[str, str]:
    """How the instruction on ``line`` and what it calls contract:
    ``("convolution", window)``; ``("strided convolution", window)``, a
    stride or a kernel dilation over the input (the weight gradient of a
    strided convolution); else ``("multiply-reduce", "")`` where a
    ``reduce`` stands in for a product, or ``("no product", "")``."""
    text, todo = [], [line]
    while todo:
        text.append(todo.pop())
        todo.extend(l for c in _CALLED.finditer(text[-1]) if c["one"]
                    for l in lines.get(c["one"], []))
    for held in text:
        if re.search(r"\s(?:convolution|dot)\(", held):
            window = re.search(r"window=\{([^}]*)\}", held)
            window = window[1] if window else ""
            strided = "stride=" in window or "rhs_dilate=" in window
            return ("strided " if strided else "") + "convolution", window
    if any(re.search(r"\sreduce\(", held) for held in text):
        return "multiply-reduce", ""
    return "no product", ""


def frame_contractions(hlo_text: str, elements: int) -> List[Tuple[str, str, str, int]]:
    """``(name, how it contracts, window, loops around it)`` of every
    instruction of a program's own computation in ``hlo_text`` that reads the
    prepared frames (a value of ``elements`` elements or more made under the
    scope ``frames``, bitcasts looked through) and is not itself under that
    scope, in the order printed; ``how`` is ``_contraction``'s.

    ``models/torsos.py::ConvTorso.prepare`` cuts the frames into blocks of
    ``Conv_0``'s stride so that every pass reads them with a stride-1
    convolution of 16·C channels.  Over the raw order the weight gradient is
    a convolution with the three channels as its batch (``window={size=15x15
    rhs_dilate=4x4}``): three of the MXU's rows, 0.122 ms an update each in
    ``cheetah_pixels``, named ``multiply_reduce_fusion`` because the
    optimizer's clipping sum of squares is fused into it (PERF.md PR 39).  A
    reader that contracts the frames with a stride, or on the vector unit,
    says the blocks are not what it reads."""
    lines, depth = _computations(hlo_text)
    fused = {c["one"] for body in lines.values() for line in body
             for c in _CALLED.finditer(line) if c["how"] == "calls"}
    found = []
    for name, body in lines.items():
        if name in fused:  # a fusion's body: its caller is the reader
            continue
        made = set()
        for line in body:
            m, value = _READER.match(line), _INSTRUCTION.match(line)
            if not m:
                continue
            scoped = f"/{_SCOPE}/" in line
            if m["opcode"] == "bitcast" and made.intersection(_operands(m, line)):
                made.add(m["name"])  # the same bytes under another shape
            elif scoped and value and math.prod(_dims(value)) >= elements:
                made.add(m["name"])
            elif not scoped and made.intersection(_operands(m, line)):
                found.append((m["name"], *_contraction(lines, line),
                              depth.get(name, 0)))
    return found


def loop_convolutions(hlo_text: str) -> List[Tuple[str, str, str, int]]:
    """``(name, shape, window size, loops around it)`` of every image
    ``convolution`` in ``hlo_text`` that sits inside a ``while`` body, in a
    fusion or a call made from one or directly, in the order printed.

    The TPU compiler prints every matmul as a ``convolution`` too: with no
    window, or, where it is batched (``vmap``, attention heads), with each
    batch dimension as a window dimension dilated by its own size
    (``size=2x1 lhs_dilate=2x1``, ``size=64x4x8 lhs_dilate=64x4x8``).  An
    image convolution is one whose window, those dimensions left out, spans
    more than one position in two dimensions or more (``size=8x8``;
    ``size=4x4x2 ... lhs_dilate=1x1x2`` for one batched over stacked
    parameters).  ``loops around it`` counts the ``while`` bodies between the
    program's entry and the instruction, the most over the ways it is
    reached."""
    lines, depth = _computations(hlo_text)

    found = []
    for name, body in lines.items():
        if depth.get(name, 0) == 0:
            continue
        for line in body:
            m, w = _INSTRUCTION.match(line), _WINDOW.search(line)
            if not (m and w and m["opcode"] == "convolution"):
                continue
            size = w["size"].split("x")
            dilate = (w["dilate"] or "x".join("1" * len(size))).split("x")
            if sum(int(n) > 1 and n != d for n, d in zip(size, dilate)) >= 2:
                found.append((m["name"], m["shape"], w["size"], depth[name]))
    return found


def loop_products(hlo_text: str, width: int) -> List[Tuple[str, str, int]]:
    """``(name, shape, loops around it)`` of every matrix product in
    ``hlo_text`` (``dot``; the TPU compiler prints it as a ``convolution``)
    whose result has ``width`` among its dimensions, fused or not, in the
    order printed.

    A stack whose layers are scanned (``models/ouro_loop.py``: a scan over
    the layers inside a scan over the loop steps) compiles to ONE copy of a
    block's products a pass, inside the loops; the same stack written out in
    Python compiles to a copy for every application, at the depth of the
    call.  ``width`` picks a block's products out of the others (its MLP's
    inner width, which no other tensor of the program has)."""
    lines, depth = _computations(hlo_text)
    found = []
    for name, body in lines.items():
        for line in body:
            m = _INSTRUCTION.match(line)
            if not (m and m["opcode"] in ("convolution", "dot")):
                continue
            if width in _dims(m):
                found.append((m["name"], m["shape"], depth.get(name, 0)))
    return found


# ``%copy-start = (f32[4096,128]{1,0:T(8,128)}, f32[4096,128]{...S(1)}, u32[]) copy-start(%x)``:
# an asynchronous move's result is a tuple, its first shape what travels.
_ASYNC_MOVE = re.compile(
    r"^\s*%?(?P<name>[\w.\-]+)\s*=\s*\(+(?P<shape>\w+\[(?P<lead>\d+)(?P<rest>[\d,]*)\])"
    r".*?\s(?P<opcode>copy-start|slice-start)\(")
_MOVES = ("copy", "pad", "slice", "dynamic-slice", "copy-start", "slice-start")
_ALIASED = re.compile(r"output_to_operand_aliasing=\{\{\}: \((\d+), \{\}\)\}")


def priority_writes(
    hlo_text: str, capacity: int, scope: str = "priority_update"
) -> List[Tuple[str, str, str, int]]:
    """``(name, what, shape, loops around it)`` of what ``hlo_text`` does to
    the priority vector in the write-back, in the order printed:

    - every Mosaic call (``tpu_custom_call``) whose path holds ``scope``:
      ``what`` is ``kernel in place`` where the call carries
      ``output_to_operand_aliasing`` (the vector is its operand AND its
      result: the kernel moves the rows it writes and nothing else), else
      ``kernel out of place`` (the whole vector goes through the kernel);
    - every ``copy``, ``pad``, ``slice`` or ``dynamic-slice`` whose result
      holds ``capacity`` elements or more and whose path holds ``scope``,
      fused or not (``what`` is the opcode): the pad and slice of a length
      that is no multiple of 128;
    - the instruction that makes the call's vector operand and those that read
      its result, bitcasts looked through, where they are such a move or its
      asynchronous form (``copy-start``, ``slice-start``: the compiler's own
      traffic between HBM and VMEM carries no path).  A synchronous one is a
      pass over the vector that every update waits for; an asynchronous one
      may hide behind other work."""
    lines, depth = _computations(hlo_text)
    found = []

    for name, body in lines.items():
        made, kernels = {}, []

        def note(m, what=None):
            row = (m["name"], what or m["opcode"], m["shape"], depth.get(name, 0))
            if row not in found:
                found.append(row)

        def moves(m):
            return m["opcode"] in _MOVES and math.prod(_dims(m)) >= capacity

        for line in body:
            m = _INSTRUCTION.match(line) or _ASYNC_MOVE.match(line)
            if not m:
                continue
            made[m["name"]] = (m, line)
            if f"/{scope}/" in line or f"/{scope}\"" in line:
                if m["opcode"] == "custom-call" and "tpu_custom_call" in line:
                    kernels.append(m["name"])
                elif moves(m):
                    note(m)

        def maker(value):
            """What makes ``value``, bitcasts looked through."""
            while value in made and made[value][0]["opcode"] == "bitcast":
                value = _operands(*made[value])[0]
            return [made[value][0]] if value in made else []

        def readers(value):
            """What reads ``value``, bitcasts looked through."""
            for m, line in made.values():
                if value in _operands(m, line):
                    yield from readers(m["name"]) if m["opcode"] == "bitcast" else [m]

        for kernel in kernels:
            m, line = made[kernel]
            aliased = _ALIASED.search(line)
            note(m, "kernel in place" if aliased else "kernel out of place")
            vector = _operands(m, line)[int(aliased[1]) if aliased else -1]
            for near in maker(vector) + list(readers(kernel)):
                if moves(near):
                    note(near)
    return found


# ``%fusion.8466.remat = (bf16[...], f32[...]) fusion(...)``, ``%gte.remat.1 =
# f32[...] get-tuple-element(%fusion.8466.remat)``: the clone's own name ends
# in ``.remat``, ``.remat2``... and maybe a number; ``%remat2.869`` (a value
# ``jax.checkpoint`` named) is no clone.
_REMAT_CLONE = re.compile(
    r"^\s*(?:ROOT\s+)?%?(?P<name>[\w.\-]+\.remat\d*(?:\.\d+)?)\s*=", re.MULTILINE)


def remat_clones(hlo_text: str) -> List[str]:
    """The names of the instructions in ``hlo_text`` that the compiler's own
    rematerialisation cloned, each once, in the order printed (a clone's
    uses as an operand are not counted)."""
    return [m["name"] for m in _REMAT_CLONE.finditer(hlo_text)]
