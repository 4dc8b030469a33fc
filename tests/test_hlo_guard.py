"""``obs/hlo.py::arena_converts``: the reader behind ``chip_smoke.py``'s
whole-arena convert guard, on HLO text as the TPU compiler prints it.  Only
the chip's compiler makes the rewrite, so the CPU can test the reader alone."""

import pytest

from r2d2dpg_tpu.obs.hlo import arena_converts

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
