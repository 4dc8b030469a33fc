"""``obs/hlo.py``: the readers behind ``chip_smoke.py``'s five compile-time
guards of the learner call (the whole-arena convert, the batch-minor write of
the sampled batch, a running sum as long as the arena, a row read out of the
arena as many rows' bytes, an image convolution run once a scan step), the
priority write-back in place (PR 37), the prepared frames' re-lays and what
reads them (PR 35, PR 39), a whole arena leaf re-laid in HBM (PR 40) and the
sixth (a looped stack's products inside its loops, one copy a pass), and the
count of an update's sequential scan steps by pass, on HLO text as the TPU
compiler prints it.  Only the
chip's compiler makes either choice, so the CPU tests the readers alone, and
the one thing that can be compiled here without a chip: ``ReplayArena.sample``
for a described v5e."""

import math

import pytest

from r2d2dpg_tpu.obs.hlo import (
    arena_converts,
    arena_reads,
    arena_relays,
    batch_minor_writes,
    capacity_scans,
    frame_contractions,
    frame_relays,
    loop_convolutions,
    loop_products,
    priority_writes,
    remat_clones,
    scan_steps,
)

CAPACITY = 524288

# Walker's learner call before the sampled batch was pinned (PR 23's trace
# names these three): top-level converts of the entry parameters, one the root
# of a fusion, one printed without the ``%``.
HOISTED = """\
%fused_computation.7 (param_0.2: f32[524288,43,6]) -> bf16[524288,43,6] {
  %param_0.2 = f32[524288,43,6]{0,1,2:T(8,128)} parameter(0)
  ROOT %convert.391 = bf16[524288,43,6]{0,1,2:T(8,128)(2,1)} convert(%param_0.2)
}

ENTRY %main.1 (arena_data_obs.1: f32[524288,43,24], arena_priority.1: f32[524288]) -> f32[] {
  %arena_data_obs.1 = f32[524288,43,24]{0,2,1:T(8,128)} parameter(0), metadata={op_name="arena.data.obs"}
  %convert.390 = bf16[524288,43,24]{0,2,1:T(8,128)(2,1)} convert(%arena_data_obs.1), backend_config={"flag_configs":[]}
  convert.396 = bf16[524288,256]{1,0:T(8,128)(2,1)} convert(arena_data_carries__actor___1_.1)
  %convert.12 = bf16[64,43,24]{0,2,1:T(8,128)(2,1)} convert(%fusion.612)
  %pow.140 = f32[524288]{0:T(1024)} power(%arena_priority.1, %pow.141), metadata={op_name="jit(timed)/while/body/closed_call/replay_sample/pow"}
}
"""

# The same call with the batch pinned: the arena is read as stored, the rows
# are rounded after the gather, the priority vector's own passes stay.
PINNED = """\
ENTRY %main.1 (arena_data_obs.1: f32[524288,43,24], arena_priority.1: f32[524288]) -> f32[] {
  %arena_data_obs.1 = f32[524288,43,24]{0,2,1:T(8,128)} parameter(0)
  %fusion.612 = f32[64,43,24]{0,2,1:T(8,128)S(1)} fusion(%arena_data_obs.1, %pad_clamp_fusion.4), kind=kCustom, calls=%fused_computation.1, metadata={op_name="jit(timed)/while/body/closed_call/replay_sample/gather"}
  %convert.12 = bf16[64,43,24]{0,2,1:T(8,128)(2,1)} convert(%fusion.612)
  %convert.13 = bf16[5242880,2]{1,0} convert(%not_the_arena)
  %pow.140 = f32[524288]{0:T(1024)} power(%arena_priority.1, %pow.141)
  %reduce-window.35 = f32[524288]{0:T(1024)} reduce-window(%pow.140, %constant.1), window={size=524288 pad=524287_0}, to_apply=%region_9
}
"""


@pytest.mark.parametrize(
    "hlo, capacity, want",
    [
        (HOISTED, CAPACITY, [
            ("convert.391", "bf16[524288,43,6]"),
            ("convert.390", "bf16[524288,43,24]"),
            ("convert.396", "bf16[524288,256]"),
        ]),
        (PINNED, CAPACITY, []),
        (HOISTED, 64, [("convert.12", "bf16[64,43,24]")]),
        ("", CAPACITY, []),
    ],
    ids=["hoisted", "pinned", "leading_dimension_only", "empty"],
)
def test_arena_converts_names_every_convert_with_the_capacity_leading(
    hlo, capacity, want
):
    assert arena_converts(hlo, capacity) == want


# ``sample``'s draw at 524,288 slots compiled for a described v5e (JAX 0.9.0,
# libtpu 0.0.34), cut to its running sums.  The parent of PR 32 summed the
# whole vector (the ledger's ``reduce-window.38``, then the sums of its 4,096
# rows and of their 32 rows); the draw in two levels keeps the two small ones
# and sums inside the 64 drawn blocks; and the same sum carried through a loop
# a slot at a time (``lax.scan``), whose length is its condition's constant.
FLAT_CDF = """\
%fused_computation.3 (param_0.9: f32[4096,128]) -> f32[4096,128] {
  ROOT %reduce-window.7 = f32[4096,128]{0,1:T(8,128)} reduce-window(%param_0.9, %constant.3), window={size=1x128 pad=0_0x127_0}, to_apply=%region_2.5
}

ENTRY %main.2 (arena_priority.1: f32[524288]) -> s32[64] {
  %copy.43 = f32[4096,128]{0,1:T(8,128)S(1)} copy(%bitcast.1311)
  %reduce-window.38 = f32[4096,128]{0,1:T(8,128)S(1)} reduce-window(%copy.43, %constant.446), window={size=1x128 pad=0_0x127_0}, to_apply=%region_2.5, metadata={op_name="jit(timed)/while/body/closed_call/replay_sample/jit(cumsum)/reduce_window_sum"}
  %reduce-window.39 = f32[32,128]{1,0:T(8,128)S(1)} reduce-window(%bitcast.1312, %constant.446), window={size=1x128 pad=0_0x127_0}, to_apply=%region_2.5.clone
  %reduce-window.40 = f32[33,1]{1,0:T(8,128)S(1)} reduce-window(%slice.689, %constant.446), window={size=32x1 pad=32_0x0_0}, to_apply=%region_2.5.clone.1
  %reduce-window.50 = f32[524288]{0:T(1024)} reduce-window(%pow.140, %constant.446), window={size=1}, to_apply=%region_2.5
}
"""

TWO_LEVEL_CDF = """\
ENTRY %main.2 (arena_priority.1: f32[524288]) -> s32[64] {
  %reduce.14 = f32[4096]{0:T(1024)S(1)} reduce(%bitcast.22, %constant.115), dimensions={1}, to_apply=%region_0.3
  %reduce-window.39 = f32[32,128]{1,0:T(8,128)S(1)} reduce-window(%bitcast.1290, %constant.434), window={size=1x128 pad=0_0x127_0}, to_apply=%region_2.6
  %reduce-window.40 = f32[33,1]{1,0:T(8,128)S(1)} reduce-window(%slice.693, %constant.434), window={size=32x1 pad=32_0x0_0}, to_apply=%region_2.6.clone
  %reduce-window.41 = f32[64,128]{1,0:T(8,128)S(1)} reduce-window(%fusion.722, %constant.434), window={size=1x128 pad=0_0x127_0}, to_apply=%region_5.13
}
"""

CARRIED_SUM = """\
%wide.region_0.2.sunk (wide.arg_tuple.0: (s32[], f32[], f32[524288], f32[524288])) -> (s32[], f32[], f32[524288], f32[524288]) {
  %add.8 = f32[]{:T(128)} add(%get-tuple-element.47, %bitcast.4)
  %dynamic_update_slice.2 = f32[524288]{0:T(1024)} dynamic-update-slice(%get-tuple-element.48, %bitcast.5, %get-tuple-element.46)
  ROOT %tuple.13 = (s32[]{:T(128)}, f32[]{:T(128)}, f32[524288]{0:T(1024)}, f32[524288]{0:T(1024)}) tuple(%add.7, %add.8, %dynamic_update_slice.2, %get-tuple-element.54)
}

%wide.region_1.3 (wide.arg_tuple.3: (s32[], f32[], f32[524288], f32[524288])) -> pred[] {
  %constant.2 = s32[]{:T(128)} constant(524288)
  ROOT %lt.0 = pred[]{:T(512)} compare(%get-tuple-element.20, %constant.2), direction=LT
}

%scan_cond.4 (param.4: (s32[], f32[64,256])) -> pred[] {
  %constant.4 = s32[]{:T(128)} constant(43)
  ROOT %lt.4 = pred[]{:T(512)} compare(%i.4, %constant.4), direction=LT
}

ENTRY %main.4 (x.1: f32[524288]) -> f32[524288] {
  %while = (s32[]{:T(128)}, f32[]{:T(128)}, f32[524288]{0:T(1024)}, f32[524288]{0:T(1024)}) while(%tuple.11), condition=%wide.region_1.3, body=%wide.region_0.2.sunk
  %while.7 = (s32[]{:T(128)}, f32[64,256]{1,0:T(8,128)}) while(%tuple.12), condition=%scan_cond.4, body=%scan_body.4
}
"""


@pytest.mark.parametrize(
    "hlo, capacity, want",
    [
        (FLAT_CDF, CAPACITY, [
            ("reduce-window.7", "f32[4096,128] window 1x128"),
            ("reduce-window.38", "f32[4096,128] window 1x128"),
        ]),
        (TWO_LEVEL_CDF, CAPACITY, []),
        (TWO_LEVEL_CDF, 8192, [("reduce-window.41", "f32[64,128] window 1x128")]),
        (CARRIED_SUM, CAPACITY, [("while", "loop of 524288 steps")]),
        (CARRIED_SUM, CAPACITY + 1, []),
        (PINNED, CAPACITY, [("reduce-window.35", "f32[524288] window 524288")]),
        ("", CAPACITY, []),
    ],
    ids=["flat", "two_level", "a_batch_as_long_as_the_arena", "carried",
         "a_longer_arena", "one_window", "empty"],
)
def test_capacity_scans_names_every_running_sum_as_long_as_the_arena(
    hlo, capacity, want
):
    """A ``reduce-window`` counts by its RESULT's elements, fused or not, and
    only over more than one position (``reduce-window.50``'s window of 1 is a
    copy); a ``while`` by the constant its condition holds the counter to (a
    scan of 43 steps is no such loop).  Sums over the sums of blocks
    (``[32,128]``, ``[33,1]``) and inside the drawn blocks (``[64,128]``) are
    short of the arena unless the arena is as short as they."""
    assert capacity_scans(hlo, capacity) == want


# ``walker_r2d2``'s learner call at the cell's 524,288 slots compiled for a
# described v5e (JAX 0.9.0, libtpu 0.0.34), cut to the priority vector's way
# through an update.  The compiler brings the vector into VMEM (``S(1)``) at
# the top of the loop's body for ``sample``'s passes, four asynchronous
# slices glued by a ``ConcatBitcast``; the kernel of PR 37 takes that copy as
# an operand aliased to its result and the 2 MB go back behind it
# (``copy-start``), under no path.
_BODY = ("%wide.region_0.180 (wide.arg_tuple.4: (s32[], f32[524288])) -> "
         "(s32[], f32[524288]) {\n")
_ENTRY = """\
}

%wide.region_146.181 (wide.arg_tuple.3: (s32[], f32[524288])) -> pred[] {
  ROOT %lt.9 = pred[]{:T(512)} compare(%get-tuple-element.1, %constant.9), direction=LT
}

ENTRY %main.192 (arena_priority.1: f32[524288]) -> f32[524288] {
  %while.844 = (s32[]{:T(128)}, f32[524288]{0:T(1024)}) while(%tuple.841), condition=%wide.region_146.181, body=%wide.region_0.180
}
"""
_PREFETCH = """\
  %get-tuple-element.13495 = f32[524288]{0:T(1024)} get-tuple-element(%wide.arg_tuple.4), index=1
  %slice-start = ((f32[524288]{0:T(1024)}), f32[131072]{0:T(1024)S(1)}, s32[]{:S(2)}) slice-start(%get-tuple-element.13495), slice={[0:131072]}
  %slice-start.1 = ((f32[524288]{0:T(1024)}), f32[131072]{0:T(1024)S(1)}, s32[]{:S(2)}) slice-start(%get-tuple-element.13495), slice={[131072:524288]}
  %slice-done = f32[131072]{0:T(1024)S(1)} slice-done(%slice-start)
  %slice-done.1 = f32[393216]{0:T(1024)S(1)} slice-done(%slice-start.1)
  %custom-call.202 = f32[524288]{0:T(1024)S(1)} custom-call(%slice-done, %slice-done.1), custom_call_target="ConcatBitcast"
  %add_maximum_fusion.3 = f32[64]{0:T(128)S(1)} fusion(%get-tuple-element.12329, %get-tuple-element.12330), kind=kLoop, calls=%fused_computation.864, metadata={op_name="jit(_learn_many)/while/body/closed_call/priority_update/max"}
  %bitcast.1291 = f32[4096,128]{1,0:T(8,128)S(1)} bitcast(%custom-call.202)
"""
WRITTEN_IN_PLACE = _BODY + _PREFETCH + """\
  %_pallas_scatter.14 = f32[4096,128]{1,0:T(8,128)S(1)} custom-call(%add_clamp_fusion.3, %add_maximum_fusion.3, %bitcast.1291), custom_call_target="tpu_custom_call", operand_layout_constraints={s32[64]{0}, f32[64]{0}, f32[4096,128]{1,0}}, output_to_operand_aliasing={{}: (2, {})}, metadata={op_name="jit(_learn_many)/while/body/closed_call/priority_update/jit(_pallas_scatter)/pallas_call"}
  %copy-start = (f32[4096,128]{1,0:T(8,128)}, f32[4096,128]{1,0:T(8,128)S(1)}, u32[]{:S(2)}) copy-start(%_pallas_scatter.14)
  %copy-done = f32[4096,128]{1,0:T(8,128)} copy-done(%copy-start)
  %bitcast.1292 = f32[524288]{0:T(1024)} bitcast(%copy-done)
  ROOT %tuple.843 = (s32[]{:T(128)}, f32[524288]{0:T(1024)}) tuple(%add.1, %bitcast.1292)
""" + _ENTRY

# The same lines of its parent: the kernel held the whole vector in its own
# VMEM, so the compiler copied it there first and nothing is aliased.
WRITTEN_OUT_OF_PLACE = _BODY + _PREFETCH + """\
  %copy.1140 = f32[4096,128]{1,0:T(8,128)S(1)} copy(%bitcast.1291)
  %_pallas_scatter.14 = f32[4096,128]{1,0:T(8,128)} custom-call(%add_clamp_fusion.3, %add_maximum_fusion.3, %copy.1140), custom_call_target="tpu_custom_call", operand_layout_constraints={s32[64]{0}, f32[64]{0}, f32[4096,128]{1,0}}, metadata={op_name="jit(_learn_many)/while/body/closed_call/priority_update/jit(_pallas_scatter)/pallas_call"}
  %bitcast.1292 = f32[524288]{0:T(1024)} bitcast(%_pallas_scatter.14)
  ROOT %tuple.843 = (s32[]{:T(128)}, f32[524288]{0:T(1024)}) tuple(%add.1, %bitcast.1292)
""" + _ENTRY

# The configuration's own 100,000 slots, no multiple of 128: the vector is
# padded into the ``[782, 128]`` view inside a fusion (the slice back is a
# bitcast under the padded tiling), and the call is in place on the result.
WRITTEN_RAGGED = """\
%fused_computation.510 (param_0.3521: f32[100000]) -> f32[782,128] {
  %param_0.3521 = f32[100000]{0:T(1024)} parameter(0)
  %pad.1034 = f32[100096]{0:T(1024)} pad(%param_0.3521, %constant.4425), padding=0_96, metadata={op_name="jit(_learn_many)/while/body/closed_call/priority_update/jit(_pallas_scatter)/jit(_pad)/pad"}
  ROOT %bitcast.1203 = f32[782,128]{1,0:T(8,128)S(1)} bitcast(%pad.1034), metadata={op_name="jit(_learn_many)/while/body/closed_call/priority_update/jit(_pallas_scatter)/reshape"}
}

""" + _BODY.replace("524288", "100000") + """\
  %copy.1200 = f32[100000]{0:T(1024)} copy(%get-tuple-element.14642)
  %pad_bitcast_fusion.11 = f32[782,128]{1,0:T(8,128)S(1)} fusion(%copy.1200), kind=kLoop, calls=%fused_computation.510, metadata={op_name="jit(_learn_many)/while/body/closed_call/priority_update/jit(_pallas_scatter)/reshape"}
  %_pallas_scatter.14 = f32[782,128]{1,0:T(8,128)S(1)} custom-call(%add_clamp_fusion.3, %add_maximum_fusion.3, %pad_bitcast_fusion.11), custom_call_target="tpu_custom_call", operand_layout_constraints={s32[64]{0}, f32[64]{0}, f32[782,128]{1,0}}, output_to_operand_aliasing={{}: (2, {})}, metadata={op_name="jit(_learn_many)/while/body/closed_call/priority_update/jit(_pallas_scatter)/pallas_call"}
  %bitcast.1344 = f32[100000]{0:T(1024)S(1)} bitcast(%_pallas_scatter.14)
  ROOT %tuple.843 = (s32[]{:T(128)}, f32[100000]{0:T(1024)}) tuple(%add.1, %bitcast.1344)
""" + _ENTRY.replace("524288", "100000")


@pytest.mark.parametrize(
    "hlo, capacity, scope, want",
    [
        (WRITTEN_IN_PLACE, CAPACITY, "priority_update", [
            ("_pallas_scatter.14", "kernel in place", "f32[4096,128]", 1),
            ("copy-start", "copy-start", "f32[4096,128]", 1),
        ]),
        (WRITTEN_OUT_OF_PLACE, CAPACITY, "priority_update", [
            ("_pallas_scatter.14", "kernel out of place", "f32[4096,128]", 1),
            ("copy.1140", "copy", "f32[4096,128]", 1),
        ]),
        (WRITTEN_RAGGED, 100000, "priority_update", [
            ("pad.1034", "pad", "f32[100096]", 1),
            ("_pallas_scatter.14", "kernel in place", "f32[782,128]", 1),
        ]),
        (WRITTEN_IN_PLACE, 2 * CAPACITY, "priority_update", [
            ("_pallas_scatter.14", "kernel in place", "f32[4096,128]", 1),
        ]),
        (WRITTEN_OUT_OF_PLACE, CAPACITY, "priority", []),
        (PINNED, CAPACITY, "priority_update", []),
        ("", CAPACITY, "priority_update", []),
    ],
    ids=["in_place", "out_of_place", "a_length_no_multiple_of_128",
         "a_longer_arena", "another_scope", "no_kernel", "empty"],
)
def test_priority_writes_names_the_kernel_and_every_move_of_the_vector_beside_it(
    hlo, capacity, scope, want
):
    """The Mosaic call under the scope counts as in place by its
    ``output_to_operand_aliasing``; a move of the vector counts by its path
    (the fused ``pad``) or by standing next to the call, bitcasts looked
    through (the parent's ``copy.1140`` into the kernel's VMEM, the
    ``copy-start`` that carries the updated vector back to HBM), if it holds
    ``capacity`` elements or more; the prefetch's slices further up and the
    copy that feeds the pad's fusion are the compiler's own and carry no
    path: not listed.  A scope is a whole segment of the path."""
    assert priority_writes(hlo, capacity, scope) == want


# The pixel gather alone at ``cheetah_pixels``'s shapes, compiled for a
# described v5e (JAX 0.9.0, libtpu 0.0.34), cut to the loop ``buf[indices]``
# becomes.  Left to the compiler (the parent of PR 28) the accumulator takes
# the arena's own order, batch minor-most, and every iteration is a
# read-modify-write of all of it: the ledger's ``dynamic-update-slice.67``.
BATCH_MINOR_GATHER = """\
%fused_computation.clone.clone (param_0.15: u8[12288,45,64,64,3], param_1.18: s32[]) -> u8[1,45,64,64,3] {
  %param_0.15 = u8[12288,45,64,64,3]{0,3,4,2,1:T(8,128)(4,1)} parameter(0)
  %param_1.18 = s32[]{:T(128)} parameter(1)
  %constant.36 = s32[]{:T(128)} constant(0)
  ROOT %dynamic-slice.9 = u8[1,45,64,64,3]{0,3,4,2,1:T(8,128)(4,1)S(1)} dynamic-slice(%param_0.15, %param_1.18, %constant.36, %constant.36, %constant.36, /*index=5*/%constant.36), dynamic_slice_sizes={1,45,64,64,3}
}

%wide.while_body.sunk (wide.param.2: (s32[], u8[12288,45,64,64,3], s32[32,1], u8[32,45,64,64,3], s32[], /*index=5*/s32[])) -> (s32[], u8[12288,45,64,64,3], s32[32,1], u8[32,45,64,64,3], s32[], /*index=5*/s32[]) {
  %get-tuple-element.57 = s32[32,1]{0,1:T(1,128)S(1)} get-tuple-element(%wide.param.2), index=2
  %get-tuple-element.52 = u8[32,45,64,64,3]{0,3,4,2,1:T(8,128)(4,1)} get-tuple-element(%wide.param.2), index=3
  %constant_dynamic-slice_fusion.5 = u8[1,45,64,64,3]{0,3,4,2,1:T(8,128)(4,1)S(1)} fusion(%get-tuple-element.56, %bitcast.4), kind=kLoop, calls=%fused_computation.clone.clone
  %dynamic-update-slice.3 = u8[32,45,64,64,3]{0,3,4,2,1:T(8,128)(4,1)} dynamic-update-slice(%get-tuple-element.52, %constant_dynamic-slice_fusion.5, %get-tuple-element.49, %constant.7..sunk, %constant.7..sunk, /*index=5*/%constant.7..sunk, %constant.7..sunk), backend_config={"flag_configs":[]}
  %dynamic-update-slice.9 = s32[32]{0:T(128)} dynamic-update-slice(%slots, %slot, %get-tuple-element.49)
  ROOT %tuple.15 = (s32[]{:T(128)}, u8[12288,45,64,64,3]{0,3,4,2,1:T(8,128)(4,1)}, s32[32,1]{0,1:T(1,128)S(1)}, u8[32,45,64,64,3]{0,3,4,2,1:T(8,128)(4,1)}, s32[]{:T(128)}, /*index=5*/s32[]{:T(128)}) tuple(%add.8, %get-tuple-element.56, %get-tuple-element.57, %dynamic-update-slice.3, %get-tuple-element.59, /*index=5*/%get-tuple-element.60)
}
"""

# The same gather with the rows' layout stated by ``sample``: the accumulator
# is batch-major, the body re-lays ONE sequence out and a fused update writes
# it into a stretch of its own.  The arena is read as before.
BATCH_MAJOR_GATHER = """\
%fused_computation.clone.clone (param_0.18: u8[32,45,64,64,3], param_1.21: u8[1,45,64,64,3], param_2.16: s32[]) -> u8[32,45,64,64,3] {
  %param_0.18 = u8[32,45,64,64,3]{3,2,4,1,0:T(8,128)(4,1)S(1)} parameter(0)
  %param_1.21 = u8[1,45,64,64,3]{3,2,4,1,0:T(8,128)(4,1)S(1)} parameter(1)
  %param_2.16 = s32[]{:T(128)} parameter(2)
  ROOT %dynamic-update-slice.4 = u8[32,45,64,64,3]{3,2,4,1,0:T(8,128)(4,1)S(1)} dynamic-update-slice(%param_0.18, %param_1.21, %param_2.16, %constant.37, %constant.37, /*index=5*/%constant.37, %constant.37), backend_config={"flag_configs":[]}
}

%wide.while_body.sunk (wide.param.2: (s32[], u8[12288,45,64,64,3], s32[32,1], u8[32,45,64,64,3], s32[])) -> (s32[], u8[12288,45,64,64,3], s32[32,1], u8[32,45,64,64,3], s32[]) {
  %get-tuple-element.49 = u8[32,45,64,64,3]{3,2,4,1,0:T(8,128)(4,1)S(1)} get-tuple-element(%wide.param.2), index=3
  %constant_dynamic-slice_fusion.5 = u8[1,45,64,64,3]{0,3,4,2,1:T(8,128)(4,1)S(1)} fusion(%get-tuple-element.52, %bitcast.4), kind=kLoop, calls=%fused_computation.1.clone.clone
  %copy.4 = u8[1,45,64,64,3]{3,2,4,1,0:T(8,128)(4,1)S(1)} copy(%constant_dynamic-slice_fusion.5)
  %constant_dynamic-update-slice_fusion.2 = u8[32,45,64,64,3]{3,2,4,1,0:T(8,128)(4,1)S(1)} fusion(%get-tuple-element.49, %copy.4, %get-tuple-element.46), kind=kLoop, calls=%fused_computation.clone.clone
  ROOT %tuple.14 = (s32[]{:T(128)}, u8[12288,45,64,64,3]{0,3,4,2,1:T(8,128)(4,1)}, s32[32,1]{0,1:T(1,128)S(1)}, u8[32,45,64,64,3]{3,2,4,1,0:T(8,128)(4,1)S(1)}, s32[]{:T(128)}) tuple(%add.8, %get-tuple-element.52, %get-tuple-element.53, %constant_dynamic-update-slice_fusion.2, %get-tuple-element.55)
}
"""


@pytest.mark.parametrize(
    "hlo, batch, want",
    [
        (BATCH_MINOR_GATHER, 32, [
            ("dynamic-update-slice.3", "u8[32,45,64,64,3]{0,3,4,2,1}"),
        ]),
        (BATCH_MAJOR_GATHER, 32, []),
        (BATCH_MINOR_GATHER, 45, []),
        (HOISTED, 64, []),
        ("", 32, []),
    ],
    ids=["batch_minor", "batch_major", "another_batch", "no_update_slice", "empty"],
)
def test_batch_minor_writes_names_every_update_slice_into_a_batch_minor_buffer(
    hlo, batch, want
):
    """A rank-1 ``[batch]`` update (the fixture's ``dynamic-update-slice.9``)
    is no such write: its one dimension is minor-most by having no other."""
    assert batch_minor_writes(hlo, batch) == want


# ``cheetah_pixels``' learner call compiled for a described v5e (JAX 0.9.0,
# libtpu 0.0.34), cut to its loops and convolutions.  The call is a loop over
# its updates (``region_0``).  In the parent of PR 30 the scans inside an
# update (``region_3``) ran the conv torso a step, on 32 frames: directly and
# as the root of a fusion.  The compiler prints matmuls as ``convolution`` too,
# plain (no window) and batched (each batch dimension a window dimension
# dilated by its own size: ``vmap``'s two members, attention's 64 x 4 heads).
CONV_IN_SCAN = """\
%fused_computation.9 (param_0.1: f32[32,15,15,32], param_1.1: f32[4,4,32,64]) -> f32[32,6,6,64] {
  %param_0.1 = f32[32,15,15,32]{0,3,2,1:T(8,128)} parameter(0)
  %param_1.1 = f32[4,4,32,64]{3,2,1,0:T(8,128)} parameter(1)
  ROOT %conv_general_dilated.173 = f32[32,6,6,64]{0,3,2,1:T(8,128)} convolution(%param_0.1, %param_1.1), window={size=4x4 stride=2x2}, dim_labels=b01f_01io->b01f
}

%region_3.12 (param.3: (s32[], f32[32,64,64,3], f32[32,256])) -> (s32[], f32[32,64,64,3], f32[32,256]) {
  %conv_general_dilated.166 = f32[32,15,15,32]{0,3,2,1:T(8,128)} convolution(%frames.1, %kernel.1), window={size=8x8 stride=4x4}, dim_labels=b01f_01io->b01f, metadata={op_name="jit(timed)/while/body/closed_call/forward/while/body/ConvTorso/Conv_0"}
  %fusion.9 = f32[32,6,6,64]{0,3,2,1:T(8,128)} fusion(%conv_general_dilated.166, %kernel.2), kind=kOutput, calls=%fused_computation.9
  %convolution.31 = f32[32,1024]{1,0:T(8,128)} convolution(%h.1, %wh.1), dim_labels=bf_io->bf
  %convolution-base-dilated.124 = f32[2,1,32,1024]{3,2,1,0:T(8,128)} convolution(%h.2, %wh.2), window={size=2x1 lhs_dilate=2x1}, dim_labels=01bf_0io1->01bf
  %convolution-base-dilated.438 = bf16[64,4,128,8,40]{4,2,3,1,0:T(8,128)(2,1)} convolution(%q.1, %k.1), window={size=64x4x8 stride=63x3x1 lhs_dilate=64x4x1}, dim_labels=01b2f_01i2o->01b2f
  ROOT %tuple.3 = (s32[], f32[32,64,64,3], f32[32,256]) tuple(%add.3, %frames.1, %convolution.31)
}

%region_4.13 (param.4: (s32[], f32[32,64,64,3], f32[32,256])) -> pred[] {
  ROOT %lt.4 = pred[] compare(%i.4, %n.4), direction=LT
}

%region_0.229 (param.0: (s32[], f32[640,64,64,3])) -> (s32[], f32[640,64,64,3]) {
  %conv_general_dilated.350 = f32[640,15,15,32]{0,3,2,1:T(8,128)} convolution(%frames.0, %kernel.1), window={size=8x8 stride=4x4}, dim_labels=b01f_01io->b01f
  %convolution-base-dilated.121 = f32[2,640,6,6,64]{1,4,0,3,2:T(8,128)} convolution(%fusion.1103, %fusion.1104), window={size=4x4x2 stride=2x2x1 lhs_dilate=1x1x2}, dim_labels=b012f_201io->2b01f
  %while.3 = (s32[], f32[32,64,64,3], f32[32,256]) while(%tuple.0), condition=%region_4.13, body=%region_3.12
  ROOT %tuple.0 = (s32[], f32[640,64,64,3]) tuple(%add.0, %frames.0)
}

%region_1.230 (param.1: (s32[], f32[640,64,64,3])) -> pred[] {
  ROOT %lt.1 = pred[] compare(%i.1, %n.1), direction=LT
}

ENTRY %main.1 (frames: f32[640,64,64,3]) -> f32[] {
  %conv_general_dilated.1 = f32[640,15,15,32]{0,3,2,1:T(8,128)} convolution(%frames, %kernel.0), window={size=8x8 stride=4x4}, dim_labels=b01f_01io->b01f
  %while.1 = (s32[], f32[640,64,64,3]) while(%tuple.1), condition=%region_1.230, body=%region_0.229
}
"""


@pytest.mark.parametrize(
    "hlo, want",
    [
        (CONV_IN_SCAN, [
            ("conv_general_dilated.173", "f32[32,6,6,64]", "4x4", 2),
            ("conv_general_dilated.166", "f32[32,15,15,32]", "8x8", 2),
            ("conv_general_dilated.350", "f32[640,15,15,32]", "8x8", 1),
            ("convolution-base-dilated.121", "f32[2,640,6,6,64]", "4x4x2", 1),
        ]),
        # The same text without the call's own loop: what was one loop deep
        # is at top level, and only the scan's convolutions are in a loop.
        (CONV_IN_SCAN.replace("body=%region_0.229", "to_apply=%region_0.229"), [
            ("conv_general_dilated.173", "f32[32,6,6,64]", "4x4", 1),
            ("conv_general_dilated.166", "f32[32,15,15,32]", "8x8", 1),
        ]),
        (BATCH_MAJOR_GATHER, []),
        ("", []),
    ],
    ids=["in_a_scan_and_in_the_call", "scan_alone", "a_loop_without_one", "empty"],
)
def test_loop_convolutions_names_every_image_convolution_inside_a_while_body(
    hlo, want
):
    """A convolution at top level (``conv_general_dilated.1``) is no finding;
    one in a loop body is, fused or not, with the loops around it; a matmul
    printed as a ``convolution`` is not, plain or batched (the sdar core's
    attention, ``size=64x4x8 lhs_dilate=64x4x1``, read as 139 image
    convolutions before its batch dimensions were left out)."""
    assert loop_convolutions(hlo) == want


# The looped stack's learner call as the TPU compiler prints it: the call's
# own loop over its updates, in it a pass's scan over the loop steps, in that
# the scan over the layers, whose body holds ONE copy of the block's products
# (the MLP's gate and up at the inner width 5632, one of them in a fusion;
# the down projection's result has no such dimension); and the same products
# written out, an application each, at the depth of the call.
ROLLED_STACK = """\
%fused_computation.4 (param_0.7: f32[64,40,2048], param_1.7: f32[2048,5632]) -> f32[64,40,5632] {
  ROOT %convolution.597 = f32[64,40,5632]{2,1,0:T(8,128)} convolution(%param_0.7, %param_1.7), dim_labels=0bf_io0->0bf
}

%layers_body.3 (param.3: (s32[], f32[64,40,2048])) -> (s32[], f32[64,40,2048]) {
  %convolution.596 = f32[64,40,5632]{2,1,0:T(8,128)} convolution(%h.3, %w_gate.3), dim_labels=0bf_io0->0bf, metadata={op_name="jit(timed)/while/body/closed_call/forward/while/body/closed_call/while/body/closed_call/checkpoint/core_mlp/dot_general"}
  %fusion.4 = f32[64,40,5632]{2,1,0:T(8,128)} fusion(%h.3, %w_up.3), kind=kOutput, calls=%fused_computation.4
  %convolution.598 = f32[64,40,2048]{2,1,0:T(8,128)} convolution(%m.3, %w_down.3), dim_labels=0bf_io0->0bf
  %convolution.601 = f32[2048,5632]{1,0:T(8,128)} convolution(%h.3, %g.3), dim_labels=0bf_0io->bf
  ROOT %tuple.3 = (s32[], f32[64,40,2048]) tuple(%add.3, %convolution.598)
}

%layers_cond.3 (param.4: (s32[], f32[64,40,2048])) -> pred[] {
  ROOT %lt.4 = pred[] compare(%i.4, %n.4), direction=LT
}

%steps_body.2 (param.2: (s32[], f32[64,40,2048])) -> (s32[], f32[64,40,2048]) {
  %while.3 = (s32[], f32[64,40,2048]) while(%tuple.2), condition=%layers_cond.3, body=%layers_body.3
  ROOT %tuple.2b = (s32[], f32[64,40,2048]) tuple(%add.2, %norm.2)
}

%steps_cond.2 (param.5: (s32[], f32[64,40,2048])) -> pred[] {
  ROOT %lt.5 = pred[] compare(%i.5, %n.5), direction=LT
}

%updates_body.1 (param.1: (s32[], f32[64,40,2048])) -> (s32[], f32[64,40,2048]) {
  %while.2 = (s32[], f32[64,40,2048]) while(%tuple.1), condition=%steps_cond.2, body=%steps_body.2
  %dot.9 = f32[64,40,5632]{2,1,0} dot(%x.1, %w.1), lhs_contracting_dims={2}, rhs_contracting_dims={0}
  ROOT %tuple.1b = (s32[], f32[64,40,2048]) tuple(%add.1, %x.1)
}

%updates_cond.1 (param.6: (s32[], f32[64,40,2048])) -> pred[] {
  ROOT %lt.6 = pred[] compare(%i.6, %n.6), direction=LT
}

ENTRY %main.1 (x: f32[64,40,2048]) -> f32[] {
  %while.1 = (s32[], f32[64,40,2048]) while(%tuple.0), condition=%updates_cond.1, body=%updates_body.1
}
"""


@pytest.mark.parametrize(
    "hlo, width, want",
    [
        (ROLLED_STACK, 5632, [
            ("convolution.597", "f32[64,40,5632]", 3),
            ("convolution.596", "f32[64,40,5632]", 3),
            ("convolution.601", "f32[2048,5632]", 3),
            ("dot.9", "f32[64,40,5632]", 1),
        ]),
        # The layers' scan written out in Python: its products sit in the
        # loop over the loop steps, one loop up.
        (ROLLED_STACK.replace("body=%layers_body.3", "to_apply=%layers_body.3"), 5632, [
            ("convolution.597", "f32[64,40,5632]", 2),
            ("convolution.596", "f32[64,40,5632]", 2),
            ("convolution.601", "f32[2048,5632]", 2),
            ("dot.9", "f32[64,40,5632]", 1),
        ]),
        (ROLLED_STACK, 768, []),
        ("", 5632, []),
    ],
    ids=["rolled", "layers_written_out", "another_width", "empty"],
)
def test_loop_products_names_every_product_of_a_width_with_the_loops_around_it(
    hlo, width, want
):
    """A product counts by a dimension of its RESULT (the down projection
    ``convolution.598`` gives the hidden width back and is none), fused or
    not, ``dot`` or the TPU printer's ``convolution``, and carries the
    ``while`` bodies between the entry and itself."""
    assert loop_products(hlo, width) == want


# ``humanoid_sdar_moe``'s learner call compiled for a described v5e (JAX
# 0.9.0, libtpu 0.0.34), cut to its clones: the compiler's rematerialisation
# copied a fusion of the held experts' products and the two results taken out
# of another; ``%remat2.869`` is a value ``jax.checkpoint`` named, and a
# clone's uses as an operand are no further clones.
REMAT_CLONES = """\
%wide.region_0.231.clone (wide.wide.wide.arg_tuple.0: (s32[], f32[8,2048,768])) -> (s32[], f32[8,2048,768]) {
  %remat2.869 = f32[8,2048,768]{1,2,0:T(8,128)} get-tuple-element(%wide.wide.wide.arg_tuple.0), index=97
  %fusion.8691.remat2 = f32[8,768,2560]{2,1,0:T(8,128)} fusion(%custom-call.99, %bitcast.12474), kind=kOutput, calls=%fused_computation.5737.clone.clone.clone.clone
  %fusion.8466.remat = (bf16[8,768,2560]{2,1,0:T(8,128)(2,1)}, f32[8,768,2560]{2,1,0:T(8,128)}) fusion(%copy-done.65, %fusion.8465, %bitcast.12754, %custom-call.100, %bitcast.12209), kind=kOutput, calls=%fused_computation.121.clone.clone.clone
  %gte.remat = bf16[8,768,2560]{2,1,0:T(8,128)(2,1)} get-tuple-element(%fusion.8466.remat), index=0
  ROOT %gte.remat.1 = f32[8,768,2560]{2,1,0:T(8,128)} get-tuple-element(%fusion.8466.remat), index=1
}
"""


@pytest.mark.parametrize(
    "hlo, want",
    [
        (REMAT_CLONES, ["fusion.8691.remat2", "fusion.8466.remat", "gte.remat",
                        "gte.remat.1"]),
        (HOISTED, []),
        ("", []),
    ],
    ids=["clones", "none", "empty"],
)
def test_remat_clones_names_every_instruction_the_compiler_cloned_once(hlo, want):
    assert remat_clones(hlo) == want


# ``cheetah_pixels``' learner call at the cell's capacity (12,288 sequences of
# 45 frames of 64x64x3 bytes, 2 updates a call) compiled for a described v5e
# (JAX 0.9.0, libtpu 0.0.34), cut to the gather of the pixel rows.  PR 34's
# parent stored the leaf in the rows' own shape: it lies slot minor-most, and
# the gather's loop takes each row out as a slice padded to 128 slots.
SLOT_MINOR_ROW_READ = """\
%fused_computation.14.clone.clone (param_0.4309: u8[12288,45,64,64,3], param_1.5532: s32[]) -> u8[1,45,64,64,3] {
  %param_0.4309 = u8[12288,45,64,64,3]{0,3,4,2,1:T(8,128)(4,1)} parameter(0)
  %param_1.5532 = s32[]{:T(128)} parameter(1)
  %constant.5378 = s32[]{:T(128)} constant(0)
  ROOT %dynamic-slice.9 = u8[1,45,64,64,3]{0,3,4,2,1:T(8,128)(4,1)S(1)} dynamic-slice(%param_0.4309, %param_1.5532, %constant.5378, %constant.5378, %constant.5378, /*index=5*/%constant.5378), dynamic_slice_sizes={1,45,64,64,3}
}

%wide.while_body.sunk (wide.param.2: (s32[], u8[12288,45,64,64,3], s32[32,1], u8[32,45,64,64,3], s32[])) -> (s32[], u8[12288,45,64,64,3], s32[32,1], u8[32,45,64,64,3], s32[]) {
  %wide.param.2 = (s32[]{:T(128)}, u8[12288,45,64,64,3]{0,3,4,2,1:T(8,128)(4,1)}, s32[32,1]{0,1:T(1,128)S(1)}, u8[32,45,64,64,3]{3,2,4,1,0:T(8,128)(4,1)}, s32[]{:T(128)}) parameter(0)
  %get-tuple-element.18327 = u8[12288,45,64,64,3]{0,3,4,2,1:T(8,128)(4,1)} get-tuple-element(%wide.param.2), index=1
  %constant_dynamic-slice_fusion.15 = u8[1,45,64,64,3]{0,3,4,2,1:T(8,128)(4,1)S(1)} fusion(%get-tuple-element.18327, %bitcast.1320), kind=kLoop, calls=%fused_computation.14.clone.clone
  %copy.536 = u8[1,45,64,64,3]{3,2,4,1,0:T(8,128)(4,1)S(1)} copy(%constant_dynamic-slice_fusion.15)
}

%region_0.231 (arg_tuple.4: (s32[], u8[12288,45,64,64,3])) -> (s32[], u8[12288,45,64,64,3]) {
  %while.863 = (s32[]{:T(128)}, u8[12288,45,64,64,3]{0,3,4,2,1:T(8,128)(4,1)}, s32[32,1]{0,1:T(1,128)S(1)}, u8[32,45,64,64,3]{3,2,4,1,0:T(8,128)(4,1)}, s32[]{:T(128)}) while(%tuple.1012), condition=%wide.while_cond, body=%wide.while_body.sunk
}

ENTRY %main.243 (arena_data_obs.1: u8[12288,45,64,64,3], arena_data_action.1: f32[12288,45,6]) -> f32[] {
  %arena_data_obs.1 = u8[12288,45,64,64,3]{0,3,4,2,1:T(8,128)(4,1)} parameter(187), metadata={op_name="arena.data.obs"}
  %arena_data_action.1 = f32[12288,45,6]{0,2,1:T(8,128)} parameter(186), metadata={op_name="arena.data.action"}
  %copy.413 = f32[12288,45,6]{1,2,0:T(8,128)} copy(%arena_data_action.1)
  %while.898 = (s32[]{:T(128)}, u8[12288,45,64,64,3]{0,3,4,2,1:T(8,128)(4,1)}) while(%tuple.1036), condition=%region_188.232, body=%region_0.231
}
"""

# The same call from PR 34 on: a step's frame is stored as three whole tiles
# behind a major-most slot axis, and the loop copies one row as it lies.
TILED_ROW_READ = """\
%fused_computation.13.clone.clone (param_0.4300: u8[32,45,3,32,128], param_1.5531: s32[], param_2.4626: u8[12288,45,3,32,128], param_3.4101: s32[]) -> u8[32,45,3,32,128] {
  %param_0.4300 = u8[32,45,3,32,128]{4,3,2,1,0:T(8,128)(4,1)S(1)} parameter(0)
  %param_2.4626 = u8[12288,45,3,32,128]{4,3,2,1,0:T(8,128)(4,1)} parameter(2)
  %param_3.4101 = s32[]{:T(128)} parameter(3)
  %constant.5375 = s32[]{:T(128)} constant(0)
  %dynamic-slice.9 = u8[1,45,3,32,128]{4,3,2,1,0:T(8,128)(4,1)} dynamic-slice(%param_2.4626, %param_3.4101, %constant.5375, %constant.5375, %constant.5375, /*index=5*/%constant.5375), dynamic_slice_sizes={1,45,3,32,128}
  %param_1.5531 = s32[]{:T(128)} parameter(1)
  ROOT %dynamic-update-slice.52 = u8[32,45,3,32,128]{4,3,2,1,0:T(8,128)(4,1)S(1)} dynamic-update-slice(%param_0.4300, %dynamic-slice.9, %param_1.5531, %constant.5375, %constant.5375, /*index=5*/%constant.5375, %constant.5375)
}

%wide.while_body.sunk (wide.param.2: (s32[], u8[12288,45,3,32,128], s32[32,1], u8[32,45,3,32,128], s32[])) -> (s32[], u8[12288,45,3,32,128], s32[32,1], u8[32,45,3,32,128], s32[]) {
  %get-tuple-element.18327 = u8[12288,45,3,32,128]{4,3,2,1,0:T(8,128)(4,1)} get-tuple-element(%wide.param.2), index=1
  %dynamic-slice_dynamic-update-slice_fusion.2 = u8[32,45,3,32,128]{4,3,2,1,0:T(8,128)(4,1)S(1)} fusion(%get-tuple-element.18324, %get-tuple-element.18321, %get-tuple-element.18327, %bitcast.1324), kind=kLoop, calls=%fused_computation.13.clone.clone
}

%region_0.231 (arg_tuple.4: (s32[], u8[12288,45,3,32,128])) -> (s32[], u8[12288,45,3,32,128]) {
  %while.863 = (s32[]{:T(128)}, u8[12288,45,3,32,128]{4,3,2,1,0:T(8,128)(4,1)}, s32[32,1]{0,1:T(1,128)S(1)}, u8[32,45,3,32,128]{4,3,2,1,0:T(8,128)(4,1)S(1)}, s32[]{:T(128)}) while(%tuple.1012), condition=%wide.while_cond, body=%wide.while_body.sunk
  %get-tuple-element.17722 = u8[32,45,3,32,128]{4,3,2,1,0:T(8,128)(4,1)S(1)} get-tuple-element(%while.863), index=3
  %copy.548 = u8[32,45,3,32,128]{1,0,4,3,2:T(8,128)(4,1)S(1)} copy(%get-tuple-element.17722)
}

ENTRY %main.243 (arena_data_obs.1: u8[12288,45,3,32,128], arena_data_action.1: f32[12288,45,6]) -> f32[] {
  %arena_data_obs.1 = u8[12288,45,3,32,128]{4,3,2,1,0:T(8,128)(4,1)} parameter(187), metadata={op_name="arena.data.obs"}
  %arena_data_action.1 = f32[12288,45,6]{0,2,1:T(8,128)} parameter(186), metadata={op_name="arena.data.action"}
  %copy.416 = f32[12288,45,6]{1,2,0:T(8,128)} copy(%arena_data_action.1)
  %while.898 = (s32[]{:T(128)}, u8[12288,45,3,32,128]{4,3,2,1,0:T(8,128)(4,1)}) while(%tuple.1036), condition=%region_188.232, body=%region_0.231
}
"""

# Stored as a row's 135 tiles in one dimension, the compiler's gather first
# cuts the WHOLE leaf in two (a dimension over 128), in every update.
WHOLE_LEAF_SLICES = """\
%region_0.231 (arg_tuple.4: (s32[], u8[12288,135,32,128])) -> (s32[], u8[12288,135,32,128]) {
  %get-tuple-element.19570 = u8[12288,135,32,128]{3,2,1,0:T(8,128)(4,1)} get-tuple-element(%arg_tuple.4), index=200
  %mini-gather-slice.8 = u8[12288,7,32,128]{3,2,1,0:T(8,128)(4,1)} slice(%get-tuple-element.19570), slice={[0:12288], [128:135], [0:32], [0:128]}
  %mini-gather-slice.9 = u8[12288,128,32,128]{3,2,1,0:T(8,128)(4,1)} slice(%get-tuple-element.19570), slice={[0:12288], [0:128], [0:32], [0:128]}
}

ENTRY %main.243 (arena_data_obs.1: u8[12288,135,32,128]) -> f32[] {
  %while.898 = (s32[]{:T(128)}, u8[12288,135,32,128]{3,2,1,0:T(8,128)(4,1)}) while(%tuple.1036), condition=%region_188.232, body=%region_0.231
}
"""


def _relaid_once_a_call(name):
    """The action leaf re-laid whole, once a call, outside the loop over the
    updates: 128 x 48 x 12,288 floats as laid out, no loop around it."""
    return (name, "f32[12288,45,6]{1,2,0:T(8,128)}", 50331648, 1080, 0)


@pytest.mark.parametrize(
    "hlo, capacity, want",
    [
        (SLOT_MINOR_ROW_READ, 12288, [
            # 128 slots x 64 x 3 x 64 x 45 bytes for a row of 552,960.
            ("dynamic-slice.9", "u8[1,45,64,64,3]{0,3,4,2,1:T(8,128)(4,1)S(1)}",
             70778880, 552960, 2),
            _relaid_once_a_call("copy.413"),
        ]),
        (TILED_ROW_READ, 12288, [_relaid_once_a_call("copy.416")]),
        (WHOLE_LEAF_SLICES, 12288, [
            ("mini-gather-slice.8", "u8[12288,7,32,128]{3,2,1,0:T(8,128)(4,1)}",
             352321536, 552960, 1),
            ("mini-gather-slice.9", "u8[12288,128,32,128]{3,2,1,0:T(8,128)(4,1)}",
             6442450944, 552960, 1),
        ]),
        (SLOT_MINOR_ROW_READ, 8000, []),  # no value of that many slots
        ("", 12288, []),
    ],
    ids=["parent_slot_minor", "tiles", "whole_leaf_slices", "another_capacity",
         "empty"],
)
def test_arena_reads_names_every_slice_or_copy_of_many_rows_bytes(hlo, capacity, want):
    import chip_smoke

    assert arena_reads(hlo, capacity) == want
    # What ``chip_smoke.py`` refuses: a read inside the loop over the updates
    # (none of these is into VMEM once an update).
    refused = chip_smoke._arena_reads_refused(
        arena_reads(hlo, capacity), 1, SMALL_LEAF_BYTES)
    assert [r[0] for r in refused] == [w[0] for w in want if w[4] >= 1]


# The pixel configuration's learner call at its own 8,000 slots, as the chip's
# compiler made it once ``Conv_0`` read blocks (PR 39: names, shapes and
# layouts from the train leg's refusal, the lines written in the printer's
# form): two ``[8000, 45]`` leaves sliced into VMEM a quarter at a time inside
# the loop over the updates, 8 x 1,024,000 B an update as laid out.
STAGED_SMALL_LEAVES = (
    "%region_0.231 (arg_tuple.4: (s32[], f32[8000,45], f32[8000,45])) -> "
    "(s32[], f32[8000,45], f32[8000,45]) {\n"
    + "".join(
        f"  %get-tuple-element.{9001 + leaf} = f32[8000,45]{{1,0:T(8,128)}} "
        f"get-tuple-element(%arg_tuple.4), index={1 + leaf}\n"
        for leaf in range(2))
    + "".join(
        f"  %slice.{871 + 2 * i} = f32[2000,45]{{1,0:T(8,128)S(1)}} "
        f"slice(%get-tuple-element.{9001 + i // 4}), "
        f"slice={{[{2000 * (i % 4)}:{2000 * (i % 4 + 1)}], [0:45]}}\n"
        for i in range(8))
    + "}\n\nENTRY %main.243 (arena_data_reward.1: f32[8000,45]) -> f32[] {\n"
    "  %while.898 = (s32[]{:T(128)}, f32[8000,45]{1,0:T(8,128)}) while(%tuple.1036), "
    "condition=%region_188.232, body=%region_0.231\n}\n")
# A slice of the pixel leaf staged the same way: 2,000 rows of 552,960 B.
STAGED_PIXEL_ROWS = STAGED_SMALL_LEAVES.replace(
    "%slice.871 = f32[2000,45]{1,0:T(8,128)S(1)} slice(%get-tuple-element.9001)",
    "%get-tuple-element.9003 = u8[8000,45,3,32,128]{4,3,2,1,0:T(8,128)(4,1)} "
    "get-tuple-element(%arg_tuple.4), index=3\n"
    "  %slice.871 = u8[2000,45,3,32,128]{4,3,2,1,0:T(8,128)(4,1)S(1)} "
    "slice(%get-tuple-element.9003)")
# The arena's leaves but the pixels, ``cheetah_pixels`` at 8,000 slots:
# action, reward, discount, reset, four LSTM carries, the slots' stamps.
SMALL_LEAF_BYTES = 8000 * (45 * 6 + 3 * 45 + 4 * 256 + 2) * 4


@pytest.mark.parametrize(
    "hlo, small_bytes, refused",
    [
        (STAGED_SMALL_LEAVES, SMALL_LEAF_BYTES, []),
        (STAGED_SMALL_LEAVES, 8 * 1024000 - 1, [f"slice.{871 + 2 * i}" for i in range(8)]),
        (STAGED_PIXEL_ROWS, SMALL_LEAF_BYTES, [f"slice.{871 + 2 * i}" for i in range(8)]),
        (STAGED_SMALL_LEAVES.replace("S(1)", ""), SMALL_LEAF_BYTES,
         [f"slice.{871 + 2 * i}" for i in range(8)]),
    ],
    ids=["small_leaves_in_vmem", "more_than_the_small_leaves", "pixel_rows_in_vmem",
         "small_leaves_in_hbm"],
)
def test_the_arena_read_guard_lets_small_leaves_staged_in_vmem_through(
    hlo, small_bytes, refused
):
    """Reads into VMEM once an update pass while together they take no more
    bytes than the arena's small leaves; a read of the pixel leaf, more bytes
    than that, or a read into HBM is refused, and all of an update's reads
    with it."""
    import chip_smoke

    reads = arena_reads(hlo, 8000)
    assert [(r[2], r[3], r[4]) for r in reads if r[1].startswith("f32")] == [
        (1024000, 180, 1)] * (7 if hlo is STAGED_PIXEL_ROWS else 8)
    assert [r[0] for r in chip_smoke._arena_reads_refused(reads, 1, small_bytes)] == refused


def test_the_small_leaves_are_the_arenas_leaves_but_the_pixels():
    """``cheetah_pixels``' arena from shapes: every ``[8000, ...]`` leaf but
    the 4.4 GB of frames."""
    import chip_smoke

    _, shapes = chip_smoke._learner_call_from_shapes(
        "cheetah_pixels", (64, 64, 3), "uint8", 6)
    assert chip_smoke._small_leaf_bytes(shapes.arena, (64, 64, 3)) == SMALL_LEAF_BYTES
    assert chip_smoke._small_leaf_bytes(shapes.arena, (12288,)) == (
        SMALL_LEAF_BYTES + 8000 * 45 * 64 * 64 * 3)


# ``cheetah_pixels``' learner call at the cell's 12,288 slots compiled for a
# described v5e (JAX 0.9.0, libtpu 0.0.34; PR 39's tree), cut to its small
# leaves: stored in the rows' own shape they lie slot minor-most, and the
# compiler re-lays three of them whole once a call before the update loop
# gathers from them (``copy.420``, the trace's 0.054 ms an update), one of
# them into VMEM (``copy.421``, ``S(1)``).
RELAID_SMALL_LEAVES = """\
ENTRY %main.243 (arena_data_action.1: f32[12288,45,6], arena_data_reward.1: f32[12288,45], arena_data_discount.1: f32[12288,45], arena_data_reset.1: f32[12288,45]) -> f32[] {
  %arena_data_action.1 = f32[12288,45,6]{0,1,2:T(8,128)} parameter(188), metadata={op_name="arena.data.action"}
  %arena_data_reward.1 = f32[12288,45]{0,1:T(8,128)} parameter(189), metadata={op_name="arena.data.reward"}
  %arena_data_discount.1 = f32[12288,45]{0,1:T(8,128)} parameter(190), metadata={op_name="arena.data.discount"}
  %arena_data_reset.1 = f32[12288,45]{0,1:T(8,128)} parameter(191), metadata={op_name="arena.data.reset"}
  %copy.420 = f32[12288,45,6]{1,2,0:T(8,128)} copy(%arena_data_action.1)
  %custom-call.172 = f32[12288,45]{0,1:T(8,128)S(1)} custom-call(%slice-done.36, %slice-done.37, %slice-done.38), custom_call_target="ConcatBitcast"
  %copy.421 = f32[12288,45]{1,0:T(8,128)S(1)} copy(%custom-call.172)
  %custom-call.173 = f32[12288,45]{0,1:T(8,128)S(1)} custom-call(%slice-done.39, %slice-done.40, %slice-done.41), custom_call_target="ConcatBitcast"
  %copy.422 = f32[12288,45]{1,0:T(8,128)} copy(%custom-call.173)
  %copy.423 = f32[12288,45]{1,0:T(8,128)} copy(%arena_data_reset.1)
  %copy.424 = f32[64,45]{1,0:T(8,128)} copy(%gather.12)
  %while.898 = (s32[]{:T(128)}, f32[12288,45,6]{1,2,0:T(8,128)}) while(%tuple.1036), condition=%region_188.232, body=%region_0.231
}
"""

# The same leaves from PR 40 on: the action's whole lane-rows flat and
# slot-major, gathered where they lie; its last 14 values and the [45] leaves
# have no shape of their own bytes that lies slot-major, and the compiler may
# re-lay them (``copy.346`` / ``.349``, under 128 elements a slot).
SPLIT_SMALL_LEAVES = """\
ENTRY %main.243 (arena_data_action_parts_0_.1: f32[12288,256], arena_data_action_parts_1_.1: f32[12288,14], arena_data_reset.1: f32[12288,45]) -> f32[] {
  %arena_data_action_parts_0_.1 = f32[12288,256]{1,0:T(8,128)} parameter(188), metadata={op_name="arena.data.action.parts[0]"}
  %arena_data_action_parts_1_.1 = f32[12288,14]{0,1:T(8,128)} parameter(189), metadata={op_name="arena.data.action.parts[1]"}
  %arena_data_reset.1 = f32[12288,45]{0,1:T(8,128)} parameter(190), metadata={op_name="arena.data.reset"}
  %copy.346 = f32[12288,14]{1,0:T(8,128)} copy(%arena_data_action_parts_1_.1)
  %copy.349 = f32[12288,45]{1,0:T(8,128)} copy(%arena_data_reset.1)
  %while.898 = (s32[]{:T(128)}, f32[12288,256]{1,0:T(8,128)}) while(%tuple.1036), condition=%region_188.232, body=%region_0.231
}
"""


@pytest.mark.parametrize(
    "hlo, capacity, want",
    [
        (RELAID_SMALL_LEAVES, 12288, [
            # 45 steps padded to 128 lanes, 6 values to 8 sublanes; the
            # [45] leaves (copy.422 / .423) are rows of under 128 elements.
            ("copy.420", "f32[12288,45,6]{1,2,0:T(8,128)}", 12288 * 128 * 8 * 4, 0),
        ]),
        (SPLIT_SMALL_LEAVES, 12288, []),
        (RELAID_SMALL_LEAVES, 8000, []),  # no value of that many slots
        ("", 12288, []),
    ],
    ids=["parent_relaid", "split", "another_capacity", "empty"],
)
def test_arena_relays_names_every_whole_leaf_copied_in_hbm(hlo, capacity, want):
    """A copy of a whole ``[capacity, ...]`` value of 128 elements a slot or
    more is listed where its result lies in HBM; the copy into VMEM
    (``copy.421``), a copy of a leaf of fewer elements a slot and a copy of
    the batch are not."""
    assert arena_relays(hlo, capacity) == want


# ``cheetah_pixels``' learner call compiled for a described v5e, cut to what
# moves the sampled frames between the gather and ``Conv_0``.  PR 35's parent
# cut the two windows out of the batch first and every pass of the torso
# converted and re-laid its own: eleven operations an update over the same
# 17.7 MB of frames (one of them, ``slice.766``, the first step of a fusion
# that writes its 20 steps as bfloat16).
PER_PASS_RELAYS = """\
%fused_computation.409.clone.clone.clone (param_0.4264: u8[25,32,64,64,3]) -> bf16[20,32,64,64,3] {
  %param_0.4264 = u8[25,32,64,64,3]{3,2,4,0,1:T(8,128)(4,1)S(1)} parameter(0)
  %slice.766 = u8[20,32,64,64,3]{3,2,4,0,1:T(8,128)(4,1)} slice(%param_0.4264), slice={[0:20], [0:32], [0:64], [0:64], [0:3]}
  %convert.1500 = f32[20,32,64,64,3]{3,2,4,0,1:T(8,128)} convert(%slice.766)
  ROOT %convert.1501 = bf16[20,32,64,64,3]{3,2,4,0,1:T(8,128)(2,1)S(1)} convert(%multiply.700)
}

%region_0.231 (arg_tuple.4: (s32[], u8[8000,45,3,32,128])) -> (s32[], u8[8000,45,3,32,128]) {
  %copy.548 = u8[32,45,3,32,128]{1,0,4,3,2:T(8,128)(4,1)S(1)} copy(%get-tuple-element.17708), metadata={op_name="jit(_learn_many)/while/body/closed_call/replay_sample/gather"}
  %bitcast.1644 = u8[32,20,64,64,3]{1,0,4,3,2:T(8,128)(4,1)S(1)} bitcast(%slice.772)
  %copy.549 = u8[32,20,64,64,3]{3,2,4,1,0:T(8,128)(4,1)S(1)} copy(%bitcast.1644)
  %copy.550 = bf16[20,32,64,64,3]{3,2,4,1,0:T(8,128)(2,1)S(1)} copy(%multiply_bitcast_fusion.3), metadata={op_name="jit(_learn_many)/while/body/closed_call/burn_in/vmap(ActorNet.encode)/torso/div"}
  %copy.551 = bf16[640,64,64,3]{0,3,2,1:T(4,128)(2,1)S(1)} copy(%bitcast.1650), metadata={op_name="jit(_learn_many)/while/body/closed_call/burn_in/vmap(ActorNet.encode)/torso/Conv_0/reshape"}
  %slice.784 = u8[32,25,64,64,3]{1,0,4,3,2:T(8,128)(4,1)S(1)} slice(%bitcast.1645), slice={[0:32], [20:45], [0:64], [0:64], [0:3]}
  %copy.558 = u8[32,25,64,64,3]{3,2,4,1,0:T(8,128)(4,1)S(1)} copy(%slice.784)
  %copy.565 = bf16[25,32,64,64,3]{3,2,4,1,0:T(8,128)(2,1)S(1)} copy(%convert_multiply_fusion.7)
  %copy.566 = bf16[800,64,64,3]{0,3,2,1:T(4,128)(2,1)S(1)} copy(%bitcast.1646)
  %convert_multiply_fusion.6 = bf16[20,32,64,64,3]{3,2,4,0,1:T(8,128)(2,1)S(1)} fusion(%bitcast.1647), kind=kLoop, calls=%fused_computation.409.clone.clone.clone
  %copy.559 = bf16[20,32,64,64,3]{3,2,4,1,0:T(8,128)(2,1)S(1)} copy(%convert_multiply_fusion.6)
  %copy.560 = bf16[640,64,64,3]{0,3,2,1:T(4,128)(2,1)S(1)} copy(%bitcast.1651)
  %copy.1832 = s32[]{:T(128)} copy(%constant.12)
  %bitcast.1650 = bf16[640,64,64,3]{3,2,4,0:T(8,128)(2,1)S(1)} bitcast(%copy.550)
}

ENTRY %main.243 (arena_data_obs.1: u8[8000,45,3,32,128]) -> f32[] {
  %copy.416 = f32[8000,45,6]{1,2,0:T(8,128)} copy(%arena_data_action.1)
  %while.898 = (s32[]{:T(128)}, u8[8000,45,3,32,128]{4,3,2,1,0:T(8,128)(4,1)}) while(%tuple.1036), condition=%region_188.232, body=%region_0.231
}
"""


# The same call from PR 35 on: the torso prepares all 45 steps once (the
# sampled bytes, time-major as the gather's loop wrote them, transposed into
# ``[96, 128, L·B]`` and converted in one pass, then the channels padded into
# ``Conv_0``'s order) and every convolution reads its window as a slice fused
# into its own fusion, which writes nothing.
PREPARED_ONCE = """\
%fused_computation.415.clone.clone (param_0.3887: bf16[64,64,3,1440]) -> bf16[64,64,3,640] {
  %param_0.3887 = bf16[64,64,3,1440]{3,2,1,0:T(4,128)(2,1)S(1)} parameter(0)
  ROOT %slice.789 = bf16[64,64,3,640]{3,2,1,0:T(4,128)(2,1)} slice(%param_0.3887), slice={[0:64], [0:64], [0:3], [0:640]}
}

%fused_computation.414.clone.clone (param_0.3888: bf16[8,8,3,64], param_1.5072: bf16[64,64,3,1440]) -> f32[640,15,15,64] {
  %param_1.5072 = bf16[64,64,3,1440]{3,2,1,0:T(4,128)(2,1)S(1)} parameter(1)
  %fusion.910 = bf16[64,64,3,640]{3,2,1,0:T(4,128)(2,1)} fusion(%param_1.5072), kind=kLoop, calls=%fused_computation.415.clone.clone
  %param_0.3888 = bf16[8,8,3,64]{3,2,1,0:T(4,128)(2,1)S(1)} parameter(0)
  ROOT %conv_general_dilated.346 = f32[640,15,15,64]{0,3,2,1:T(8,128)S(1)} convolution(%fusion.910, %param_0.3888), window={size=8x8 stride=4x4}, dim_labels=01fb_01io->b01f
}

%region_0.231 (arg_tuple.4: (s32[], u8[8000,45,3,32,128])) -> (s32[], u8[8000,45,3,32,128]) {
  %get-tuple-element.16887 = u8[32,45,3,32,128]{4,3,2,0,1:T(8,128)(4,1)S(1)} get-tuple-element(%while.828), index=3
  %bitcast.1259 = u8[96,128,1440]{1,0,2:T(8,128)(4,1)S(1)} bitcast(%get-tuple-element.16887)
  %copy.434 = bf16[96,128,1440]{2,1,0:T(8,128)(2,1)S(1)} copy(%multiply_bitcast_fusion.2), metadata={op_name="jit(_learn_many)/while/body/closed_call/frames/div"}
  %reshape.1037 = bf16[64,64,3,1440]{3,2,1,0:T(4,128)(2,1)S(1)} reshape(%copy.434), metadata={op_name="jit(_learn_many)/while/body/closed_call/frames/div"}
  %fusion.967 = f32[640,15,15,64]{0,3,2,1:T(8,128)S(1)} fusion(%reshape.1036, %reshape.1037), kind=kOutput, calls=%fused_computation.414.clone.clone
  %reshape.1036 = bf16[8,8,3,64]{3,2,1,0:T(4,128)(2,1)S(1)} reshape(%copy.433)
}

ENTRY %main.243 (arena_data_obs.1: u8[8000,45,3,32,128]) -> f32[] {
  %copy.399 = f32[8000,45,6]{1,2,0:T(8,128)} copy(%arena_data_action.1)
  %while.898 = (s32[]{:T(128)}, u8[8000,45,3,32,128]{4,3,2,1,0:T(8,128)(4,1)}) while(%tuple.1036), condition=%region_188.232, body=%region_0.231
}
"""

WINDOW = 32 * 20 * 64 * 64 * 3  # the least window of the cell's batch, in elements
# What the change reaches (``chip_smoke.py`` holds ``learner_call_pixels`` to it).
PREPARED_RELAYS = [
    ("copy.434", "bf16[96,128,1440]{2,1,0:T(8,128)(2,1)S(1)}", 37748736, 1),
    ("reshape.1037", "bf16[64,64,3,1440]{3,2,1,0:T(4,128)(2,1)S(1)}", 50331648, 1),
]


@pytest.mark.parametrize(
    "hlo, elements, want",
    [
        (PER_PASS_RELAYS, WINDOW, [
            ("slice.766", "u8[20,32,64,64,3]{3,2,4,0,1:T(8,128)(4,1)}", 15728640, 1),
            # Batch and time minor-most: 45 steps padded to 128 lanes.
            ("copy.548", "u8[32,45,3,32,128]{1,0,4,3,2:T(8,128)(4,1)S(1)}", 50331648, 1),
            ("copy.549", "u8[32,20,64,64,3]{3,2,4,1,0:T(8,128)(4,1)S(1)}", 15728640, 1),
            ("copy.550", "bf16[20,32,64,64,3]{3,2,4,1,0:T(8,128)(2,1)S(1)}", 31457280, 1),
            ("copy.551", "bf16[640,64,64,3]{0,3,2,1:T(4,128)(2,1)S(1)}", 20971520, 1),
            ("slice.784", "u8[32,25,64,64,3]{1,0,4,3,2:T(8,128)(4,1)S(1)}", 50331648, 1),
            ("copy.558", "u8[32,25,64,64,3]{3,2,4,1,0:T(8,128)(4,1)S(1)}", 19660800, 1),
            ("copy.565", "bf16[25,32,64,64,3]{3,2,4,1,0:T(8,128)(2,1)S(1)}", 39321600, 1),
            ("copy.566", "bf16[800,64,64,3]{0,3,2,1:T(4,128)(2,1)S(1)}", 29360128, 1),
            ("copy.559", "bf16[20,32,64,64,3]{3,2,4,1,0:T(8,128)(2,1)S(1)}", 31457280, 1),
            ("copy.560", "bf16[640,64,64,3]{0,3,2,1:T(4,128)(2,1)S(1)}", 20971520, 1),
        ]),
        (PREPARED_ONCE, WINDOW, PREPARED_RELAYS),
        # Counted by elements: of the 25-step window or more, five of the eleven.
        (PER_PASS_RELAYS, 32 * 25 * 64 * 64 * 3, [
            ("copy.548", "u8[32,45,3,32,128]{1,0,4,3,2:T(8,128)(4,1)S(1)}", 50331648, 1),
            ("slice.784", "u8[32,25,64,64,3]{1,0,4,3,2:T(8,128)(4,1)S(1)}", 50331648, 1),
            ("copy.558", "u8[32,25,64,64,3]{3,2,4,1,0:T(8,128)(4,1)S(1)}", 19660800, 1),
            ("copy.565", "bf16[25,32,64,64,3]{3,2,4,1,0:T(8,128)(2,1)S(1)}", 39321600, 1),
            ("copy.566", "bf16[800,64,64,3]{0,3,2,1:T(4,128)(2,1)S(1)}", 29360128, 1),
        ]),
        # The small leaf re-laid once a call is no window of frames; with a
        # threshold under it, it is listed at depth 0.
        (PREPARED_ONCE, 8000 * 45 * 6, PREPARED_RELAYS + [
            # 45 steps padded to 128 lanes, 6 values to 8 sublanes.
            ("copy.399", "f32[8000,45,6]{1,2,0:T(8,128)}", 8000 * 128 * 8 * 4, 0),
        ]),
        ("", WINDOW, []),
    ],
    ids=["parent_eleven", "prepared_once", "larger_window", "smaller_threshold",
         "empty"],
)
def test_frame_relays_names_every_written_copy_slice_or_reshape_of_a_window(
    hlo, elements, want
):
    assert frame_relays(hlo, elements) == want
    # 325 MB laid out an update on the parent, 88 MB from PR 35 on.
    if elements == WINDOW and hlo:
        assert sum(r[2] for r in frame_relays(hlo, elements)) == (
            325320704 if hlo is PER_PASS_RELAYS else 88080384)


# What reads the prepared frames in ``cheetah_pixels``' learner call before
# ``Conv_0`` read them as blocks (compiled for a described v5e, PR 37's tree;
# one of the six forward passes and one of the two weight gradients): the
# strided convolution, and its weight gradient, a convolution with the three
# channels as its batch and a dilated kernel, in a fusion that the optimizer's
# clipping sum of squares names ``multiply_reduce_fusion``.
STRIDED_READERS = """\
%fused_computation.415 (param_0.3887: bf16[64,64,3,1440]) -> bf16[64,64,3,640] {
  %param_0.3887 = bf16[64,64,3,1440]{3,2,1,0:T(4,128)(2,1)S(1)} parameter(0)
  ROOT %slice.789 = bf16[64,64,3,640]{3,2,1,0:T(4,128)(2,1)} slice(%param_0.3887), slice={[0:64], [0:64], [0:3], [0:640]}
}

%fused_computation.414.clone.clone (param_0.3888: bf16[8,8,3,64], param_1.5072: bf16[64,64,3,1440]) -> f32[640,15,15,64] {
  %param_1.5072 = bf16[64,64,3,1440]{3,2,1,0:T(4,128)(2,1)S(1)} parameter(1)
  %fusion.910 = bf16[64,64,3,640]{3,2,1,0:T(4,128)(2,1)} fusion(%param_1.5072), kind=kLoop, calls=%fused_computation.415, metadata={op_name="jit(_learn_many)/while/body/closed_call/burn_in/slice"}
  %param_0.3888 = bf16[8,8,3,64]{3,2,1,0:T(4,128)(2,1)S(1)} parameter(0)
  ROOT %conv_general_dilated.346 = f32[640,15,15,64]{0,3,2,1:T(8,128)S(1)} convolution(%fusion.910, %param_0.3888), window={size=8x8 stride=4x4}, dim_labels=01fb_01io->b01f, metadata={op_name="jit(_learn_many)/while/body/closed_call/burn_in/vmap(ActorNet.encode)/torso/Conv_0/conv_general_dilated"}
}

%fused_computation.825.clone.clone (param_0.4030: bf16[640,15,15,32], param_1.5170: bf16[64,64,3,1440]) -> (f32[], f32[8,8,3,32]) {
  %param_1.5170 = bf16[64,64,3,1440]{3,2,1,0:T(4,128)(2,1)S(1)} parameter(1)
  %fusion.266.clone.3 = bf16[64,64,3,640]{3,2,1,0:T(4,128)(2,1)} fusion(%param_1.5170), kind=kLoop, calls=%fused_computation.415, metadata={op_name="jit(_learn_many)/while/body/closed_call/forward/slice"}
  %param_0.4030 = bf16[640,15,15,32]{0,3,2,1:T(8,128)(2,1)S(1)} parameter(0)
  %conv_general_dilated.290.clone.3 = f32[8,8,3,32]{3,2,1,0:T(4,128)S(1)} convolution(%fusion.266.clone.3, %param_0.4030), window={size=15x15 rhs_dilate=4x4}, dim_labels=01bf_i01o->01bf
  %mul.8278 = f32[8,8,3,32]{3,2,1,0:T(4,128)} multiply(%conv_general_dilated.290.clone.3, %conv_general_dilated.290.clone.3), metadata={op_name="jit(_learn_many)/while/body/closed_call/optimizer/mul"}
  %constant.5061 = f32[]{:T(128)} constant(0)
  %reduce.1854 = f32[]{:T(128)} reduce(%mul.8278, %constant.5061), dimensions={0,1,2,3}, to_apply=%region_79.121, metadata={op_name="jit(_learn_many)/while/body/closed_call/optimizer/reduce_sum"}
  ROOT %tuple.839 = (f32[]{:T(128)}, f32[8,8,3,32]{3,2,1,0:T(4,128)S(1)}) tuple(%reduce.1854, %conv_general_dilated.290.clone.3)
}

%region_0.231 (arg_tuple.4: (s32[], u8[8000,45,3,32,128])) -> (s32[], u8[8000,45,3,32,128]) {
  %copy.435 = bf16[96,128,1440]{2,1,0:T(8,128)(2,1)S(1)} copy(%multiply_bitcast_fusion.2), metadata={op_name="jit(_learn_many)/while/body/closed_call/frames/ActorNet.prepare/torso.prepare/div"}
  %reshape.1037 = bf16[64,64,3,1440]{3,2,1,0:T(4,128)(2,1)S(1)} reshape(%copy.435), metadata={op_name="jit(_learn_many)/while/body/closed_call/frames/ActorNet.prepare/torso.prepare/div"}
  %fusion.967 = f32[640,15,15,64]{0,3,2,1:T(8,128)S(1)} fusion(%reshape.1036, %reshape.1037), kind=kOutput, calls=%fused_computation.414.clone.clone, metadata={op_name="jit(_learn_many)/while/body/closed_call/burn_in/vmap(ActorNet.encode)/torso/Conv_0/conv_general_dilated"}
  %multiply_reduce_fusion.216 = (f32[]{:T(128)}, f32[8,8,3,32]{3,2,1,0:T(4,128)S(1)}) fusion(%get-tuple-element.16987, %reshape.1037), kind=kOutput, calls=%fused_computation.825.clone.clone, metadata={op_name="jit(_learn_many)/while/body/closed_call/forward/transpose(jvp(ActorNet.encode))/torso/Conv_0/conv_general_dilated"}
  %fusion.1008 = bf16[640,15,15,32]{0,3,2,1:T(8,128)(2,1)S(1)} fusion(%get-tuple-element.16986), kind=kLoop, calls=%fused_computation.900, metadata={op_name="jit(_learn_many)/while/body/closed_call/forward/transpose(jvp(ActorNet.encode))/torso/Conv_1/conv_general_dilated"}
}

ENTRY %main.243 (arena_data_obs.1: u8[8000,45,3,32,128]) -> f32[] {
  %while.898 = (s32[]{:T(128)}, u8[8000,45,3,32,128]{4,3,2,1,0:T(8,128)(4,1)}) while(%tuple.1036), condition=%region_188.232, body=%region_0.231
}
"""

# The same readers from PR 39 on: the frames in 4 x 4 blocks of 48 channels,
# made by a gather of the transposed bytes' rows and converted
# (``convert_multiply_fusion.2``, read through a bitcast), and ``Conv_0`` a
# stride-1 convolution over them, forward and weight gradient.
BLOCK_READERS = """\
%fused_computation.421.clone.clone (param_0.3907: bf16[2,2,48,64], param_1.5083: bf16[16,16,48,1440]) -> f32[640,15,15,64] {
  %param_1.5083 = bf16[16,16,48,1440]{3,2,1,0:T(8,128)(2,1)S(1)} parameter(1)
  %fusion.913 = bf16[16,16,48,640]{3,2,1,0:T(8,128)(2,1)} fusion(%param_1.5083), kind=kLoop, calls=%fused_computation.422.clone.clone, metadata={op_name="jit(_learn_many)/while/body/closed_call/burn_in/slice"}
  %param_0.3907 = bf16[2,2,48,64]{2,3,1,0:T(8,128)(2,1)S(1)} parameter(0)
  ROOT %conv_general_dilated.348 = f32[640,15,15,64]{0,3,2,1:T(8,128)S(1)} convolution(%fusion.913, %param_0.3907), window={size=2x2}, dim_labels=01fb_01io->b01f, metadata={op_name="jit(_learn_many)/while/body/closed_call/burn_in/vmap(ActorNet.encode)/torso/conv_general_dilated"}
}

%fused_computation.425.clone.clone (param_0.4052: bf16[640,15,15,32], param_1.5181: bf16[16,16,48,1440]) -> f32[2,2,48,32] {
  %param_1.5181 = bf16[16,16,48,1440]{3,2,1,0:T(8,128)(2,1)S(1)} parameter(1)
  %fusion.959 = bf16[16,16,48,640]{3,2,1,0:T(8,128)(2,1)} fusion(%param_1.5181), kind=kLoop, calls=%fused_computation.426.clone.clone, metadata={op_name="jit(_learn_many)/while/body/closed_call/forward/slice"}
  %param_0.4052 = bf16[640,15,15,32]{0,3,2,1:T(8,128)(2,1)S(1)} parameter(0)
  ROOT %conv_general_dilated.356 = f32[2,2,48,32]{2,3,1,0:T(8,128)S(1)} convolution(%fusion.959, %param_0.4052), window={size=15x15}, dim_labels=01bf_i01o->01bf, metadata={op_name="jit(_learn_many)/while/body/closed_call/forward/transpose(jvp(ActorNet.encode))/torso/conv_general_dilated"}
}

%region_0.231 (arg_tuple.4: (s32[], u8[8000,45,3,32,128])) -> (s32[], u8[8000,45,3,32,128]) {
  %convert_multiply_fusion.2 = bf16[12288,1440]{1,0:T(8,128)(2,1)S(1)} fusion(%fusion.972), kind=kLoop, calls=%fused_computation.408.clone.clone, metadata={op_name="jit(_learn_many)/while/body/closed_call/frames/ActorNet.prepare/torso.prepare/div"}
  %bitcast.1354 = bf16[16,16,48,1440]{3,2,1,0:T(8,128)(2,1)S(1)} bitcast(%convert_multiply_fusion.2)
  %fusion.973 = f32[640,15,15,64]{0,3,2,1:T(8,128)S(1)} fusion(%bitcast.1462, %bitcast.1354), kind=kOutput, calls=%fused_computation.421.clone.clone, metadata={op_name="jit(_learn_many)/while/body/closed_call/burn_in/vmap(ActorNet.encode)/torso/conv_general_dilated"}
  %bitcast.1361 = bf16[16,16,48,1440]{3,2,1,0:T(8,128)(2,1)S(1)} bitcast(%convert_multiply_fusion.2)
  %fusion.1016 = f32[2,2,48,32]{2,3,1,0:T(8,128)S(1)} fusion(%get-tuple-element.17709, %bitcast.1361), kind=kOutput, calls=%fused_computation.425.clone.clone, metadata={op_name="jit(_learn_many)/while/body/closed_call/forward/transpose(jvp(ActorNet.encode))/torso/conv_general_dilated"}
}
"""

# A weight gradient over the prepared frames that the compiler contracts on
# the vector unit: products and a sum over the frames, no convolution
# (written for the test, in the printer's own form).
VECTOR_UNIT_READER = """\
%fused_computation.9 (param_0: bf16[640,15,15,32], param_1: bf16[16,16,48,1440]) -> f32[48,32] {
  %param_1 = bf16[16,16,48,1440]{3,2,1,0:T(8,128)(2,1)S(1)} parameter(1)
  %param_0 = bf16[640,15,15,32]{0,3,2,1:T(8,128)(2,1)S(1)} parameter(0)
  %broadcast.1 = f32[48,32,640]{2,1,0:T(8,128)} broadcast(%param_1), dimensions={0,2}
  %multiply.1 = f32[48,32,640]{2,1,0:T(8,128)} multiply(%broadcast.1, %broadcast.2)
  ROOT %reduce.1 = f32[48,32]{1,0:T(8,128)} reduce(%multiply.1, %constant.1), dimensions={2}, to_apply=%add
}

ENTRY %main.1 (arena_data_obs.1: u8[8000,45,3,32,128]) -> f32[] {
  %copy.528 = bf16[16,16,48,1440]{3,2,1,0:T(8,128)(2,1)S(1)} copy(%bitcast.1354), metadata={op_name="jit(_learn_many)/while/body/closed_call/frames/ActorNet.prepare/torso.prepare/div"}
  %bitcast.9 = bf16[16,16,48,1440]{3,2,1,0:T(8,128)(2,1)S(1)} bitcast(%copy.528)
  %multiply_reduce_fusion.9 = f32[48,32]{1,0:T(8,128)} fusion(%get-tuple-element.1, %bitcast.9), kind=kLoop, calls=%fused_computation.9, metadata={op_name="jit(_learn_many)/while/body/closed_call/forward/transpose(jvp(ActorNet.encode))/torso/dot_general"}
}
"""


@pytest.mark.parametrize(
    "hlo, want",
    [
        (STRIDED_READERS, [
            ("fusion.967", "strided convolution", "size=8x8 stride=4x4", 1),
            ("multiply_reduce_fusion.216", "strided convolution",
             "size=15x15 rhs_dilate=4x4", 1),
        ]),
        (BLOCK_READERS, [
            ("fusion.973", "convolution", "size=2x2", 0),
            ("fusion.1016", "convolution", "size=15x15", 0),
        ]),
        (VECTOR_UNIT_READER, [("multiply_reduce_fusion.9", "multiply-reduce", "", 0)]),
        ("", []),
    ],
    ids=["strided", "blocks", "vector_unit", "empty"],
)
def test_frame_contractions_names_how_every_reader_of_the_prepared_frames_contracts(
    hlo, want
):
    """Readers under the scope ``frames`` (``reshape.1037`` of ``copy.435``)
    and readers of other values (``fusion.1008``) are not listed; a bitcast is
    looked through."""
    assert frame_contractions(hlo, WINDOW) == want


@pytest.fixture(scope="module")
def one_chip():
    """A described v5e chip's sharding; skips where the TPU compiler cannot
    describe one (nothing of it runs while this module is imported)."""
    import os

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    os.environ.setdefault("TPU_ACCELERATOR_TYPE", "v5litepod-4")
    os.environ.setdefault("TPU_WORKER_HOSTNAMES", "localhost")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here: nothing to test
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture()
def no_compile_cache():
    """An ahead-of-time TPU program cannot be read back without a chip: keep
    it out of the persistent cache."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc

    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    cc.reset_cache()


def _sample_shapes(capacity, frame, frame_dtype, length, one_chip):
    """``(arena, state, key)``: an arena of ``capacity`` sequences of
    ``length`` steps with LSTM carries, its state and a key as shapes on
    ``one_chip``."""
    import jax
    import jax.numpy as jnp

    from r2d2dpg_tpu.replay.arena import ReplayArena, SequenceBatch

    def z(*shape, dtype=jnp.float32):
        return jnp.zeros((1,) + shape, dtype)

    def carry():
        return (z(256), z(256))

    arena = ReplayArena(capacity)
    example = SequenceBatch(
        obs=z(length, *frame, dtype=frame_dtype), action=z(length, 6),
        reward=z(length), discount=z(length), reset=z(length),
        carries={"actor": carry(), "critic": carry()})
    state, key = jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip),
        jax.eval_shape(lambda: (arena.init_state(example), jax.random.PRNGKey(0))))
    return arena, state, key


def test_sample_compiled_for_v5e_sums_nothing_as_long_as_the_arena(
    one_chip, no_compile_cache
):
    """``ReplayArena.sample`` at the walker cell's capacity, row shapes and
    batch, compiled for a described v5e: no running sum over 524,288 elements
    (the flat CDF's was ``reduce-window.38`` of the learner call, 13 % of an
    update), while the two-level draw's own short ones are there.  Nothing
    runs: a compile says nothing about results or times."""
    import jax

    arena, state, key = _sample_shapes(CAPACITY, (24,), "float32", 43, one_chip)
    hlo = jax.jit(lambda s, k: arena.sample(s, k, 64)).trace(state, key).lower(
        lowering_platforms=("tpu",)).compile().as_text()
    assert capacity_scans(hlo, CAPACITY) == []
    assert capacity_scans(hlo, 64 * 128)  # the reader does see this program's sums


@pytest.mark.parametrize("consumer", ["alone", "frames_as_floats"])
def test_sample_compiled_for_v5e_reads_rows_as_stored_into_a_batch_major_buffer(
    consumer, one_chip, no_compile_cache
):
    """``ReplayArena.sample`` at ``cheetah_pixels``'s row shape and batch,
    compiled for a described v5e: the pixel leaf lies slot major-most (a
    step's frame as three whole tiles), no row is read out of it as more than
    a row's bytes, and no sequence is inserted into a batch-minor buffer.
    The capacity is cut to compile in seconds.  Alone, the batch is the
    program's result and takes the result's layout whoever states it; it is
    a consumer that takes the frames as floats, as the conv torso does, that
    pulls an unstated layout back to a batch-minor order.  Nothing runs: a
    compile says nothing about results or times."""
    import jax
    import jax.numpy as jnp

    capacity, B, L = 256, 32, 45
    arena, state, key = _sample_shapes(capacity, (64, 64, 3), "uint8", L, one_chip)

    def program(s, k):
        res = arena.sample(s, k, B)
        if consumer == "alone":
            return res
        return res, (res.batch.obs.astype(jnp.float32) / 255.0).sum()

    hlo = jax.jit(program).trace(state, key).lower(
        lowering_platforms=("tpu",)).compile().as_text()
    assert f"u8[{capacity},{L},3,32,128]{{4,3,2,1,0:" in hlo  # slot-major tiles
    assert [r for r in arena_reads(hlo, capacity) if r[1].startswith("u8[")] == []
    assert batch_minor_writes(hlo, B) == []


@pytest.mark.parametrize(
    "capacity, frame, frame_dtype, length, batch",
    [(524288, (24,), "float32", 43, 64), (12288, (64, 64, 3), "uint8", 45, 32)],
    ids=["walker_r2d2", "cheetah_pixels"],
)
def test_sample_compiled_for_v5e_gathers_every_leaf_where_it_lies(
    capacity, frame, frame_dtype, length, batch, one_chip, no_compile_cache
):
    """``ReplayArena.sample`` at the cells' row shapes, batches and
    capacities (an ahead-of-time compile allocates nothing), its rows
    consumed as floats, compiled for a described v5e: every ``arena.data``
    leaf of 128 elements a slot or more lies slot major-most (a small row's
    whole lane-rows flat, ``{1,0:...}``; the carries; cheetah's pixels as
    tiles), and no copy or slice takes a whole ``[capacity, ...]`` leaf of
    them, in HBM or into VMEM.  What lies slot minor-most is what has no
    shape of its own bytes that lies slot-major: the rest of a row after its
    whole lane-rows (walker's observation 8 values, its action 2, cheetah's
    action 14) and the ``[L]`` leaves.  Stored in the rows' own shape,
    walker's observation lay slot minor-most (``{0,2,1:...}``) and cheetah's
    action leaf was re-laid whole once a call.  Nothing runs: a compile says
    nothing about results or times."""
    import re

    import jax
    import jax.numpy as jnp

    arena, state, key = _sample_shapes(capacity, frame, frame_dtype, length, one_chip)

    def program(s, k):
        rows = arena.sample(s, k, batch).batch
        return jax.tree_util.tree_map(lambda x: x.astype(jnp.float32).sum(), rows)

    hlo = jax.jit(program).trace(state, key).lower(
        lowering_platforms=("tpu",)).compile().as_text()
    data = re.findall(r"%\w*_data_\S+ = \w+\[(\d+)((?:,\d+)*)\]\{([\d,]+)", hlo)
    minor = []
    for lead, rest, order in data:
        dims = [int(d) for d in rest.split(",") if d]
        assert int(lead) == capacity
        if math.prod(dims) >= 128:
            assert order == ",".join(str(d) for d in reversed(range(1 + len(dims)))), dims
        else:
            minor.append(dims)
    # obs and action in two parts where they have a rest, reward, discount,
    # reset, four carries.
    if frame == (24,):
        assert len(data) == 11 and sorted(minor) == [[2], [8], [43], [43], [43]]
    else:
        assert len(data) == 10 and sorted(minor) == [[14], [45], [45], [45]]
    assert arena_relays(hlo, capacity) == []

    def slot(shape):
        return math.prod(int(d) for d in re.match(r"\w+\[(\d+(?:,\d+)*)\]", shape)[1].split(",")[1:])

    assert [r for r in arena_reads(hlo, capacity, rows=4) if slot(r[1]) >= 128] == []


def test_learner_call_compiled_for_v5e_prepares_its_frames_once(
    one_chip, no_compile_cache, monkeypatch
):
    """``cheetah_pixels``' learner call from shapes (batch 32, 45 steps of
    64 x 64 x 3 bytes, 2 updates a call), compiled for a described v5e: an
    update re-lays the sampled bytes of all 45 steps twice, the
    transposition and the gather of its rows into ``Conv_0``'s 4 x 4 blocks
    (which ``frame_relays`` counts twice: the gather's fusion transposes and
    reshapes), 18.9 MB each as laid out (PR 35's two passes wrote 88 MB as
    bfloat16, its parent's eleven 325), and every convolution that reads the
    one array they leave is a stride-1 convolution over 48 channels.
    Nothing runs: a compile says nothing about results or times."""
    import jax

    import chip_smoke

    # ``priority_scatter`` picks its branch from the backend it runs on.
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    trainer, state = chip_smoke._learner_call_from_shapes(
        "cheetah_pixels", (64, 64, 3), "uint8", 6)
    train, arena, rng = jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip),
        (state.train, state.arena, state.rng))
    hlo = jax.jit(trainer._learn_many, donate_argnums=(0, 1)).trace(
        train, arena, rng).lower(lowering_platforms=("tpu",)).compile().as_text()

    relays = frame_relays(hlo, WINDOW)
    # 1,440 frames in runs of 128: 1,536 bytes a row.
    assert sorted((r[1].split("{")[0], r[2], r[3]) for r in relays) == [
        ("u8[12288,1440]", 12288 * 1536, 1), ("u8[12288,1440]", 12288 * 1536, 1),
        ("u8[96,128,1440]", 96 * 128 * 1536, 1)]
    assert sorted(r[0].split(".")[0] for r in relays) == ["copy", "reshape", "transpose"]
    # The gather's loop writes the sampled bytes time-major itself, and the
    # six passes' first convolutions and both weight gradients read the
    # prepared frames, each over 2 x 2 positions of 48 channels.
    assert "u8[32,45,3,32,128]{4,3,2,0,1:" in hlo
    contractions = frame_contractions(hlo, WINDOW)
    assert sorted(c[1:] for c in contractions) == (
        [("convolution", "size=15x15", 1)] * 2 + [("convolution", "size=2x2", 1)] * 6)
    assert arena_reads(hlo, trainer.arena.capacity, rows=4) == [
        r for r in arena_reads(hlo, trainer.arena.capacity) if r[4] == 0]
    assert batch_minor_writes(hlo, 32) == [] and [
        c for c in loop_convolutions(hlo) if c[3] > 1] == []


@pytest.mark.parametrize(
    "config, obs_shape, obs_dtype, capacity",
    [("walker_r2d2", (24,), "float32", 524288),
     ("cheetah_pixels", (64, 64, 3), "uint8", 12288)],
    ids=["walker_r2d2", "cheetah_pixels"],
)
def test_learner_call_compiled_for_v5e_writes_its_priorities_back_in_place(
    config, obs_shape, obs_dtype, capacity, one_chip, no_compile_cache, monkeypatch
):
    """The learner call of each LSTM cell from shapes at the CELL's capacity
    (a multiple of 128; the configurations' own, which ``chip_smoke.py``
    compiles on the chip, are not), compiled for a described v5e: the
    write-back is one Mosaic call inside the loop over the updates, in place
    on the vector, and no copy, pad or slice of the vector that an update
    waits for stands beside it.  Nothing runs: a compile says nothing about
    results or times."""
    import dataclasses

    import jax

    import chip_smoke
    from r2d2dpg_tpu import configs

    get_config = configs.get_config

    def at_the_cells_capacity(name):
        exp = get_config(name)
        return dataclasses.replace(
            exp, trainer=dataclasses.replace(exp.trainer, capacity=capacity))

    monkeypatch.setattr(configs, "get_config", at_the_cells_capacity)
    # ``priority_scatter`` picks its branch from the backend it runs on.
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    trainer, state = chip_smoke._learner_call_from_shapes(
        config, obs_shape, obs_dtype, 6)
    assert trainer.arena.capacity == capacity
    train, arena, rng = jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip),
        (state.train, state.arena, state.rng))
    hlo = jax.jit(trainer._learn_many, donate_argnums=(0, 1)).trace(
        train, arena, rng).lower(lowering_platforms=("tpu",)).compile().as_text()
    written_back = priority_writes(hlo, capacity)
    assert [w[1:] for w in written_back if w[1].startswith("kernel")] == [
        ("kernel in place", f"f32[{capacity // 128},128]", 1)]
    assert [w for w in written_back
            if w[1] in ("copy", "pad", "slice", "dynamic-slice")] == []


def test_priority_kernel_compiles_for_v5e_at_twice_the_cells_capacity(
    one_chip, no_compile_cache
):
    """2**20 slots at batch 64, which the kernel could not hold while it
    kept the whole vector in VMEM (PR 37): nothing in its VMEM grows with the
    vector now.  A Mosaic call, in place.  Here and not with the benchmark's
    own compiles (``tests/chipbench/test_chipbench_aot.py``), beside the
    fixtures the other compiles of this file use.  Nothing runs: a compile
    says nothing about results or times."""
    import jax
    import jax.numpy as jnp

    from r2d2dpg_tpu.ops.pallas.scatter import _pallas_scatter

    avals = (
        jax.ShapeDtypeStruct((1 << 20,), jnp.float32, sharding=one_chip),
        jax.ShapeDtypeStruct((64,), jnp.int32, sharding=one_chip),
        jax.ShapeDtypeStruct((64,), jnp.float32, sharding=one_chip),
    )
    hlo = _pallas_scatter.trace(*avals).lower(
        lowering_platforms=("tpu",)).compile().as_text()
    assert "tpu_custom_call" in hlo and "output_to_operand_aliasing" in hlo


# ------------------------------------------------------------ scan steps
# A learner call's loop over its updates, holding one loop of each
# ``(path under the update, trips, the compiler states the count)``; a
# ``(path, trips, stated, inner)`` holds the loops of ``inner`` in its body.
def loops_hlo(loops):
    text, n = [], [0]

    def loop(path, trips, stated, inner=()):
        n[0] += 1
        i = n[0]
        inside = [loop(*x) for x in inner]
        text.append(
            f"%cond.{i} (p.{i}: (s32[])) -> pred[] {{\n"
            f"  %p.{i} = (s32[]) parameter(0)\n"
            f"  %constant.{i} = s32[]{{:T(128)}} constant({trips})\n"
            f"  ROOT %lt.{i} = pred[] compare(%p.{i}, %constant.{i}), direction=LT\n"
            f"}}\n\n"
            f"%body.{i} (q.{i}: (s32[])) -> (s32[]) {{\n"
            f"  %q.{i} = (s32[]) parameter(0)\n"
            + "".join(inside)
            + f"  ROOT %t.{i} = (s32[]) tuple(%q.{i})\n}}\n\n")
        stated = f', backend_config={{"known_trip_count":{{"n":"{trips}"}}}}' if stated else ""
        return (
            f"  %while.{i} = (s32[]{{:T(128)}}) while(%q.{i}), condition=%cond.{i}, "
            f'body=%body.{i}, metadata={{op_name="jit(timed)/{path}" '
            f"stack_frame_id={i}}}{stated}\n")

    update = loop("while", 4, False, [
        ("while/body/closed_call/" + path, trips, stated, inner)
        for path, trips, stated, *rest in loops for inner in (rest[0] if rest else (),)])
    return "".join(text) + (
        "ENTRY %main.1 (x: s32[]) -> (s32[]) {\n" + update
        + "  ROOT %r = (s32[]) tuple(%while.1)\n}\n")


ONLINE = [("burn_in/while", 20, False), ("burn_in/while", 20, True),
          ("forward/while", 23, False), ("forward/jvp()/while", 20, False),
          ("forward/transpose(jvp())/while", 20, True),
          ("replay_sample/while", 64, False), ("while", 3, False)]
ROLLED = [("forward/while", 4, False, [("forward/while/body/while", 4, False)]),
          ("forward/transpose(jvp())/while", 4, True,
           [("forward/transpose(jvp())/while/body/while", 4, False)])]


@pytest.mark.parametrize(
    "loops, want",
    [
        (ONLINE, {"burn_in": 40, "forward": 43, "backward": 20}),
        (ONLINE + [("forward/jvp()/while", 20, True),
                   ("forward/transpose(jvp())/while", 20, False)],
         {"burn_in": 40, "forward": 63, "backward": 40}),
        (ROLLED, {"burn_in": 0, "forward": 20, "backward": 20}),
        ([], {"burn_in": 0, "forward": 0, "backward": 0}),
    ],
    ids=["one_scan", "two_passes", "nested", "none"],
)
def test_scan_steps_counts_an_updates_loops_by_pass(loops, want):
    """Every loop inside the call's loop over its updates, by the pass its
    path names (``backward`` under a ``transpose(``): its trip count, stated
    by the compiler or held in its condition, times the counted loops around
    it.  A sample's loop and a loop under no stage are not counted, nor is
    the loop over the updates."""
    assert scan_steps(loops_hlo(loops)) == want


def _tiny_learner_call_steps(monkeypatch, runner):
    """``scan_steps`` of ``pendulum_tiny``'s learner call at two updates a
    call (burn-in 2, unroll 4, n-step 2), compiled for the CPU with
    ``runner`` running the sequence core."""
    import dataclasses

    import jax

    import chip_smoke
    from r2d2dpg_tpu import configs

    get_config = configs.get_config

    def two_updates(name):
        exp = get_config(name)
        return dataclasses.replace(
            exp, trainer=dataclasses.replace(exp.trainer, learner_steps=2))

    with monkeypatch.context() as m:
        m.setattr(configs, "get_config", two_updates)
        trainer, state = chip_smoke._learner_call_from_shapes(
            "pendulum_tiny", (3,), "float32", 1)
    agent = trainer.agent
    agent.seq = runner(agent.actor, agent.critic, agent.config)
    hlo = jax.jit(trainer._learn_many, donate_argnums=(0, 1)).lower(
        state.train, state.arena, state.rng).compile().as_text()
    return scan_steps(hlo)


def test_the_tiny_learner_call_runs_both_online_passes_in_one_scan(monkeypatch):
    """Compiled for the CPU, ``pendulum_tiny``'s update runs burn-in's two
    scans of 2 steps, the target pass of 6 and ONE scan of the two online
    passes (4 steps) and its transpose: 2 x unroll steps fewer than the
    same update with the online passes as two scans under two gradient
    calls."""
    from r2d2dpg_tpu.models.sequence import Stepped

    class TwoPasses(Stepped):
        joint_online = False

    one = _tiny_learner_call_steps(monkeypatch, Stepped)
    two = _tiny_learner_call_steps(monkeypatch, TwoPasses)
    assert one == {"burn_in": 4, "forward": 10, "backward": 4}
    assert two == {"burn_in": 4, "forward": 14, "backward": 8}
    assert sum(two.values()) - sum(one.values()) == 2 * 4


@pytest.mark.parametrize(
    "config, obs_shape, obs_dtype, steps",
    [("walker_r2d2", (24,), "float32", {"burn_in": 40, "forward": 43, "backward": 20}),
     ("cheetah_pixels", (64, 64, 3), "uint8",
      {"burn_in": 40, "forward": 45, "backward": 20})],
    ids=["walker_r2d2", "cheetah_pixels"],
)
def test_learner_call_compiled_for_v5e_runs_both_online_passes_in_one_scan(
    config, obs_shape, obs_dtype, steps, one_chip, no_compile_cache, monkeypatch
):
    """The LSTM cells' learner calls from shapes, compiled for a described
    v5e, keep their scans as loops: burn-in's two of 20 steps, the target
    pass of 20 + n-step, one scan of both online passes and its transpose
    (103 steps an update for walker, 105 for cheetah; 143 and 145 as two
    passes).  Nothing runs: a compile says nothing about results or
    times."""
    import jax

    import chip_smoke

    # ``priority_scatter`` picks its branch from the backend it runs on.
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    trainer, state = chip_smoke._learner_call_from_shapes(
        config, obs_shape, obs_dtype, 6)
    train, arena, rng = jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip),
        (state.train, state.arena, state.rng))
    hlo = jax.jit(trainer._learn_many, donate_argnums=(0, 1)).trace(
        train, arena, rng).lower(lowering_platforms=("tpu",)).compile().as_text()
    assert scan_steps(hlo) == steps
