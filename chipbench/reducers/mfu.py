"""The whole step's share of the chip's peak: the FLOPs one timed call needs
(a function of ``chipbench/counts.py``, from shapes) times the calls per
second of the measured window, over chips times the published peak."""

from chipbench import counts


def read(ctx, flops_per_call: str):
    w = ctx.window
    if not w or not w.get("calls"):
        return None
    flops = getattr(counts, flops_per_call)(ctx.config)
    rate = flops * w["calls"] / w["elapsed_s"]
    chips = max(int(ctx.device.get("count", 1)), 1)
    return 100.0 * rate / (chips * ctx.peaks["flops_per_s"])
