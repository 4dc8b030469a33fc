"""A counter of the program that the driver averaged over the measured
window's calls (``ctx.window[group][name]``)."""


def read(ctx, group: str, name: str):
    value = (ctx.window.get(group) or {}).get(name)
    return None if value is None else float(value)
