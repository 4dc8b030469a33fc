"""Tracing / profiling and numeric-debug hooks (SURVEY.md §5.1–5.2).

Reference parity: the reference has no profiling or sanitizers beyond manual
timing prints (SURVEY §5.1).  The build wires the native JAX tooling:

- ``profile_trace(logdir)`` — ``jax.profiler.trace`` context manager; view
  with TensorBoard's profile plugin (installed in this image).  Wrap a few
  representative phases, not the whole run.
- ``nan_debug(True)`` — flips ``jax_debug_nans`` so any NaN produced inside
  a jitted computation raises at the op that made it (the build's answer to
  "sanitizers": there is no shared mutable host state by design — SURVEY
  §5.2 — so numeric poisoning is the failure mode worth a dedicated mode).
"""

from __future__ import annotations

import contextlib
import time
from typing import Iterator, Optional

import jax


@contextlib.contextmanager
def profile_trace(
    logdir: Optional[str], *, enabled: bool = True
) -> Iterator[None]:
    """Trace the enclosed block into ``logdir`` for the TB profile plugin."""
    if not enabled or logdir is None:
        yield
        return
    with jax.profiler.trace(logdir):
        yield


def nan_debug(enable: bool = True) -> None:
    """Raise-at-source on NaNs inside jitted code (debug runs only: it

    disables some fusions and forces extra device syncs)."""
    jax.config.update("jax_debug_nans", enable)


@contextlib.contextmanager
def annotate(name: str) -> Iterator[None]:
    """Name a HOST-side region so it shows up in profiler timelines.

    Use around dispatch sites in driver loops (``Trainer.run``, the
    pipelined executor's collect/learn threads, the hybrid trainer's host
    loop): the annotation spans the host time of the block, which for
    host-driven collect is the real work.  For regions *inside* jitted
    code use ``scope`` instead — a TraceAnnotation under tracing would
    only mark trace time, not device time."""
    with jax.profiler.TraceAnnotation(name):
        yield


# The stages of one learner update, in the order they run.  Each is a
# ``scope`` at a boundary of the learner call (``Trainer._learn_step`` /
# ``_update_step``, ``R2D2DPG.learner_step``); ``obs/stages.py`` folds a
# capture's device time by them and derives ``backward`` from ``forward``
# (JAX wraps the differentiated scope's name in ``transpose(...)``).  This
# is the only list of the names.
LEARN_STAGES = (
    "replay_sample",
    "burn_in",
    "forward",
    "optimizer",
    "priority_update",
)


# The scopes that stand BESIDE the five stages, inside the same learner call:
# ``frames`` is what ``learner_step`` does ONCE an update to the whole sampled
# batch's observations before any pass cuts a window (``agent.seq.prepare``:
# a conv torso's frames scaled and re-laid, ``models/torsos.py``; for a flat
# observation nothing, and then no operation carries the name);
# ``diagnostics`` is the program's own in-graph instrumentation of an update
# (the metrics dictionary of ``R2D2DPG.learner_step``, the walks over both
# nets' trees for ``grad_norm`` / ``param_norm`` among it, and the quality
# gauges of ``Trainer._update_step``): always on, on every update's path.
# ``stage_table``'s entry ``scopes`` folds by these names too; its five
# stage keys do not, so there their time is ``rest``.  They are not in
# ``LEARN_STAGES`` because the benchmark's ``learn_stage_ms.*`` metric files
# mirror that table's keys one for one
# (``tests/chipbench/test_chipbench_stages.py``); the benchmark reads them
# as ``learn_scope_ms.*`` (``chipbench/reducers/scope_ms.py``).
SIDE_STAGES = ("frames", "diagnostics")


# Scopes INSIDE a whole-sequence core (``models/sdar_moe.py``,
# ``models/ouro_loop.py``), under whichever learner stage runs it: attention
# (norms, projections, RoPE, scores, output projection); the sdar core's
# routing (router, top-k, gates) and held experts' products; the looped
# stack's dense MLP (its norms and three products).
# ``stage_table(path, LEARN_STAGES + CORE_STAGES)``
# folds every pass of a core scope into its stage (the innermost name wins)
# and leaves ``forward`` / ``backward`` / ``burn_in`` what lies outside the
# core; read with ``LEARN_STAGES`` alone the core's time stays in those.
# The entry ``scopes`` of either table has a core scope's three passes apart
# (forward, recomputed under ``jax.checkpoint``, backward).
CORE_STAGES = ("core_attention", "moe_route", "moe_experts", "core_mlp")


def scope(name: str):
    """Name a region of TRACED code: ops inside the block carry ``name`` in
    their HLO metadata (``op_name``, a ``/``-separated path of the enclosing
    scopes and transforms).  Safe under jit (this is ``jax.named_scope``);
    pairs with ``annotate`` which covers the host side.

    The names reach the chip's trace: the profiler stores each executed
    instruction's path as the stat ``tf_op`` of its event metadata on the
    device plane, and the program's whole ``Hlo Proto`` in the plane
    ``/host:metadata`` (``jax.profiler.ProfileData`` shows neither).
    ``obs/stages.py::stage_table`` reads them: it is what
    ``--profile-window`` writes to ``stages.json`` and what the benchmark's
    ``learn_stage_ms.*`` metrics report."""
    return jax.named_scope(name)


@contextlib.contextmanager
def timed(window) -> Iterator[None]:
    """Time the enclosed block (seconds) into a ``PercentileWindow``.

    The pipelined executor's per-stage wait instrumentation: wrap the
    queue-blocking section of each stage and read p50/p99 plus the running
    total off the window (``utils.metrics.PercentileWindow`` or an
    ``obs.Histogram`` — anything with ``add``).  ``time`` is imported at
    module scope: this context manager runs inside per-stage hot loops and
    a per-call import was measurable overhead there."""
    t0 = time.monotonic()
    try:
        yield
    finally:
        window.add(time.monotonic() - t0)
