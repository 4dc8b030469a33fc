"""Device time of one stage of the learner call, per update, in milliseconds.

The stages are the program's own scopes (``utils/profiling.py::LEARN_STAGES``)
as the chip's trace carries them; the program's reader
(``r2d2dpg_tpu/obs/stages.py::stage_table``) folds a capture by them, with
``backward`` (``forward`` under a ``transpose(``), ``unscoped`` (an operation
with no path) and ``rest`` (a path with no stage on it) beside them.

The harness deletes its steady capture before the reducers run and hands a
reducer only ``ctx``.  So the first call of a run builds the cell a second
time through its own driver (the first run's state was freed in ``check``;
the compiles come from the cache), traces a stretch of the same steady loop,
reads the table and keeps it on ``ctx`` for the other stages.  A program
without the reader (a parent of the PR that brought it) reads ``None``.
"""

import os

from chipbench import harness

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _capture(ctx):
    """``stage_table`` of a traced stretch, with ``updates`` (the learner
    updates executed in it) added; ``None`` where there is nothing to read."""
    steady = ctx.steady_trace
    if steady is None or not steady.get("devices"):
        return None  # not a traced run, or a capture without a device plane
    try:
        from r2d2dpg_tpu.obs.stages import stage_table, table_keys
    except ImportError:
        ctx.log("stage_ms: this program has no obs/stages.py; nothing to read")
        return None

    driver = harness.load_module("drivers", ctx.cell["driver"], ROOT)
    seconds = float(ctx.cell.get("trace_seconds", 1.0))
    ctx.log("stage_ms: second set-up, for a capture of its own")
    session = driver.setup(ctx)
    driver.window(session, seconds)  # the queue full before the capture
    with harness.profile_session() as cap:
        traced = driver.window(session, seconds)
    try:
        if not cap["xplane"]:
            return None
        table = stage_table(cap["xplane"])
    finally:
        cap["cleanup"]()
        session.state = None
    if not table["devices"]:
        return None
    table["updates"] = traced["attempted"]

    per = 1000.0 / max(table["updates"], 1)
    keys = table_keys()
    covered = sum(table[k] for k in keys if k != "rest")
    ctx.log("stage_ms: ms an update " + ", ".join(
        f"{k} {table[k] * per:.4f}" for k in keys)
        + f"; busy {table['busy'] * per:.4f}; the stages and unscoped cover "
        f"{100.0 * covered / max(table['busy'], 1e-30):.2f} % of busy")
    ctx.log(f"stage_ms: rest by path {table['rest_paths']}; "
            f"unscoped by operation {table['unscoped_ops']}")
    ctx.log(f"stage_ms: traced stretch {traced['metrics']} over "
            f"{traced['elapsed_s']:.3f} s; the window read {ctx.window['metrics']}")
    return table


def read(ctx, stage: str):
    if not hasattr(ctx, "stage_table"):  # the first of the run's seven calls
        ctx.stage_table = _capture(ctx)
    table = ctx.stage_table
    if not table or not table.get("updates"):
        return None
    return 1000.0 * table[stage] / table["updates"]
