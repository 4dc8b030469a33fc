"""Device time of one scope of the learner call, by pass, per update the
CAPTURE holds, in milliseconds.

The program's reader (``r2d2dpg_tpu/obs/stages.py::stage_table``) returns,
beside the stage keys the ``*_stage_ms`` metrics read, the entry ``scopes``:
the same self times folded by every name the program has (the learner's
stages, the scopes beside them such as ``frames`` and ``diagnostics``, a
sequence core's), the rows ``loops`` (a control-flow operation's own time
under no scope), ``unscoped`` and ``rest`` beside them, each row by pass
(``forward``, ``recomputed``, ``backward``) and summed (``all``).

This reducer takes no capture and sets no cell up.  It reads the table the
run already has: the one the cell's driver captured on its live session
(``reducers/core_stage_ms.py::capture``), else ``reducers/stage_ms.py``'s.
And it divides by the updates the capture HOLDS, counted from the device's
own events (``programs``: the whole executions of the program with most
device seconds, times the configuration's ``learner_steps``), not by the
updates the host dispatched: a capture that lost the tail of its device
events would otherwise scale every time down.  The first call of a run logs
the whole table and puts the two counts side by side.  A program whose table
has no ``scopes`` (a parent of the PR that brought them) reads ``None``, as
does a capture without a device plane.
"""

import os

from chipbench import harness

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _table(ctx):
    """The run's stage table with ``updates_seen`` added, or ``None``."""
    if hasattr(ctx, "core_stage_table"):  # the cell's driver captured one
        table = harness.load_module("reducers", "core_stage_ms", ROOT).table_of(ctx)
    else:
        harness.load_module("reducers", "stage_ms", ROOT).read(ctx, "rest")
        table = ctx.stage_table
    if not table or "scopes" not in table or not table.get("programs"):
        return None
    if "updates_seen" not in table:
        table["updates_seen"] = (
            table["programs"][0]["executions"] * int(ctx.config["learner_steps"]))
        _log(ctx, table)
    return table if table["updates_seen"] else None


def _log(ctx, table):
    seen, dispatched = table["updates_seen"], table.get("updates", 0)
    per = 1000.0 / max(seen, 1)
    rows = {k: v for k, v in table["scopes"].items() if v["all"]}
    ctx.log("scope_ms: ms an update, forward / recomputed / backward / all: " + "; ".join(
        f"{row} " + " / ".join(f"{x * per:.4f}" for x in v.values())
        for row, v in rows.items())
        + f"; busy {table['busy'] * per:.4f}; rest is "
        f"{100.0 * table['scopes']['rest']['all'] / max(table['busy'], 1e-30):.2f} % of busy")
    ctx.log(f"scope_ms: operations by scope and pass {table.get('scope_ops')}")
    ctx.log(f"scope_ms: programs {table['programs']}; truncated {table.get('truncated')}; "
            f"the host's span ended {table.get('host_after_ops')} s after the device line")
    ratio = seen / max(dispatched, 1)
    ctx.log(f"scope_ms: dispatched {dispatched} updates, the capture holds {seen} "
            f"(ratio {ratio:.4f}): the *_stage_ms and stage rooflines of this run "
            f"are scaled by {ratio:.4f}")


def read(ctx, scope: str, pass_: str = None):
    table = _table(ctx)
    if table is None or scope not in table["scopes"]:
        return None
    return 1000.0 * table["scopes"][scope][pass_ or "all"] / table["updates_seen"]
