"""Device time of one scope inside the sequence core, both passes, per update,
in milliseconds.

The scopes are the program's own (``utils/profiling.py::CORE_STAGES``) inside
the learner's stages (``LEARN_STAGES``); read with both lists the innermost
name wins (``obs/stages.py::stage_of``), so the forward and the backward pass
of a core scope fold into its stage and ``forward`` / ``backward`` /
``burn_in`` keep what lies outside the core.  The whole table is logged.

The harness deletes its steady capture before the reducers run and hands a
reducer only ``ctx``; ``reducers/stage_ms.py`` therefore builds its cell a
second time.  Here the cell's driver calls ``capture`` from its ``check``
instead, while its program still holds its state: one more traced stretch
of the same steady loop, no second set-up (a 460 M-parameter model and a
50,000-row arena).  The table stays on ``ctx`` for the reducers that read it
(this one and ``moe_roofline``).  A program without the scopes reads ``None``.
"""

from chipbench import harness


def capture(ctx, window) -> None:
    """Trace a stretch of the steady loop (``window(seconds)``: the driver's
    own, on its live session) and keep its stage table on ``ctx``."""
    ctx.core_stage_table = _capture(ctx, window)


def _capture(ctx, window):
    steady = ctx.steady_trace
    if steady is None or not steady.get("devices"):
        return None  # not a traced run, or a capture without a device plane
    try:
        from r2d2dpg_tpu.obs.stages import stage_table, table_keys
        from r2d2dpg_tpu.utils.profiling import CORE_STAGES, LEARN_STAGES
    except ImportError:
        ctx.log("core_stage_ms: this program has no CORE_STAGES; nothing to read")
        return None

    stages = tuple(LEARN_STAGES) + tuple(CORE_STAGES)
    seconds = float(ctx.cell.get("trace_seconds", 1.0))
    with harness.profile_session() as cap:
        traced = window(seconds)
    try:
        if not cap["xplane"]:
            return None
        table = stage_table(cap["xplane"], stages)
    finally:
        cap["cleanup"]()
    if not table["devices"]:
        return None
    table["updates"] = traced["attempted"]

    per = 1000.0 / max(table["updates"], 1)
    keys = table_keys(stages)
    covered = sum(table[k] for k in keys if k != "rest")
    ctx.log("core_stage_ms: ms an update " + ", ".join(
        f"{k} {table[k] * per:.4f}" for k in keys)
        + f"; busy {table['busy'] * per:.4f}; the stages and unscoped cover "
        f"{100.0 * covered / max(table['busy'], 1e-30):.2f} % of busy")
    ctx.log(f"core_stage_ms: rest by path {table['rest_paths']}; "
            f"unscoped by operation {table['unscoped_ops']}")
    ctx.log(f"core_stage_ms: traced stretch {traced['metrics']} over "
            f"{traced['elapsed_s']:.3f} s; the window read {ctx.window['metrics']}")
    return table


def table_of(ctx):
    """The run's table, if its driver captured one, or ``None``."""
    table = getattr(ctx, "core_stage_table", None)
    return table if table and table.get("updates") else None


def read(ctx, stage: str):
    table = table_of(ctx)
    if table is None or stage not in table:
        return None
    return 1000.0 * table[stage] / table["updates"]
