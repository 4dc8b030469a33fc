"""What a compiled program does to the whole replay, read from its HLO text.

``ReplayArena.sample`` keeps the sampled batch in the arena's own dtypes so
that the TPU compiler cannot round the whole arena to bfloat16 once a call
(``replay/arena.py``, "The sampled batch is a boundary").  Only the chip's
compiler makes that rewrite, so no CPU test can show it; ``arena_converts``
is the reader of the check that can: ``chip_smoke.py``'s train leg compiles
``walker_r2d2``'s learner call on the chip and requires the list to be empty
(``docs/OBSERVABILITY.md``, "The whole-arena convert guard").
"""

from __future__ import annotations

import re
from typing import List, Tuple

# ``  %convert.390 = bf16[524288,43,24]{0,2,1:T(8,128)(2,1)} convert(%x), ...``
# (``ROOT`` before the name inside a fusion, no ``%`` in some printers).
_INSTRUCTION = re.compile(
    r"^\s*(?:ROOT\s+)?%?(?P<name>[\w.\-]+)\s*=\s*"
    r"(?P<shape>\w+\[(?P<lead>\d+)[\d,]*\])\S*\s+(?P<opcode>[\w\-]+)\(",
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
