"""The whole step's share of the chip's peak for the looped-stack
configuration: ``reducers/mfu.py`` with the FLOPs of
``chipbench/counts_ouro_loop.py`` (by applications of a block, nothing
recomputed)."""

from chipbench import counts_ouro_loop


def read(ctx, flops_per_call: str):
    w = ctx.window
    if not w or not w.get("calls"):
        return None
    flops = getattr(counts_ouro_loop, flops_per_call)(ctx.config)
    rate = flops * w["calls"] / w["elapsed_s"]
    chips = max(int(ctx.device.get("count", 1)), 1)
    return 100.0 * rate / (chips * ctx.peaks["flops_per_s"])
