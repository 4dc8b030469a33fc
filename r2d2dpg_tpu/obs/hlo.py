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
"""

from __future__ import annotations

import re
from typing import List, Tuple

# ``  %convert.390 = bf16[524288,43,24]{0,2,1:T(8,128)(2,1)} convert(%x), ...``
# (``ROOT`` before the name inside a fusion, no ``%`` in some printers).
_INSTRUCTION = re.compile(
    r"^\s*(?:ROOT\s+)?%?(?P<name>[\w.\-]+)\s*=\s*"
    r"(?P<shape>\w+\[(?P<lead>\d+)(?P<rest>[\d,]*)\])"
    r"(?:\{(?P<order>[\d,]*)[^}\s]*\})?\S*\s+(?P<opcode>[\w\-]+)\(",
    re.MULTILINE,
)


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
