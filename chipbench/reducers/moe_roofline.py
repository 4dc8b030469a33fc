"""The held experts' share of the roofline of the work the routing asks
for: the least time the chip could take for the pairs the program's counter
``moe/tokens_per_expert`` counted in an update (``counts_sdar_moe.experts_work``: the larger of FLOPs
over the peak and bytes over the bandwidth), over the device time of the
scope ``moe_experts`` in an update.  The same work whatever implements it:
the program's dense products (every held expert over every token) spend
several times it, which is what the share shows."""

import os

from chipbench import counts, counts_sdar_moe, harness, reference_sdar_moe

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def read(ctx, stage: str):
    table = harness.load_module("reducers", "core_stage_ms", ROOT).table_of(ctx)
    loads = (ctx.window.get("moe") or {}).get("moe/tokens_per_expert")
    if table is None or not loads or table.get(stage, 0.0) <= 0.0:
        return None
    work = counts_sdar_moe.experts_work(ctx.config, loads, reference_sdar_moe.PASSES)
    least, bound = counts.least_seconds(work["flops"], work["bytes"], ctx.peaks)
    seconds = table[stage] / table["updates"]
    ctx.log(f"moe_roofline: {work['flops'] / 1e9:.2f} GFLOP and "
            f"{work['bytes'] / 1e9:.3f} GB an update, least {least * 1e3:.3f} ms "
            f"(bound by {bound}); {stage} took {seconds * 1e3:.3f} ms")
    return 100.0 * least / seconds
