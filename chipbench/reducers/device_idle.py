"""The device's idle share of the traced stretch of the steady loop."""


def read(ctx):
    t = ctx.steady_trace
    if t is None or t["busy_s"] <= 0.0 or t["window_s"] <= 0.0:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
