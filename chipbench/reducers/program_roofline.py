"""A probed operation's share of its roofline: the least time the chip could
take for the bytes the operation's semantics need, over the device time of
the probe's program in the probes' capture."""

from chipbench import counts, trace


def read(ctx, program: str, bytes: str):
    if ctx.probe_trace is None or program not in ctx.probes:
        return None
    found = trace.seconds_of_program(ctx.probe_trace, ctx.probes[program])
    if not found or found[0] <= 0.0:
        return None
    seconds, runs = found
    least, _ = counts.least_seconds(0.0, getattr(counts, bytes)(ctx.config), ctx.peaks)
    return 100.0 * least / (seconds / runs)
