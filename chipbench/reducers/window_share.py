"""A host-clock total the driver kept over the measured window, as a share of
the window: ``seconds`` names the key of the window's record."""


def read(ctx, seconds: str):
    w = ctx.window
    if not w or seconds not in w or w["elapsed_s"] <= 0.0:
        return None
    return 100.0 * w[seconds] / w["elapsed_s"]
