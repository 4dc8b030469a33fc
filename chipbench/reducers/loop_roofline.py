"""A scope of the looped stack as a share of the roofline of the work the
mathematics asks of it: the least time the chip could take for an update's
FLOPs and bytes (``counts_ouro_loop.<work>``, from shapes: by applications,
nothing recomputed, a layer's kernels read once a pass and application), over
the device time of the scope in an update.  The same work whatever implements
it: a rematerialised forward pass spends time the count does not grant, which
is what the share shows.  A program without the scope reads ``None``."""

import os

from chipbench import counts, counts_ouro_loop, harness

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def read(ctx, stage: str, work: str):
    table = harness.load_module("reducers", "core_stage_ms", ROOT).table_of(ctx)
    if table is None or table.get(stage, 0.0) <= 0.0:
        return None
    need = getattr(counts_ouro_loop, work)(ctx.config)
    least, bound = counts.least_seconds(need["flops"], need["bytes"], ctx.peaks)
    seconds = table[stage] / table["updates"]
    ctx.log(f"loop_roofline: {need['flops'] / 1e12:.3f} TFLOP and "
            f"{need['bytes'] / 1e9:.3f} GB an update, least {least * 1e3:.3f} ms "
            f"(bound by {bound}); {stage} took {seconds * 1e3:.3f} ms")
    return 100.0 * least / seconds
