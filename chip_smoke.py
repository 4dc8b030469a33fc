#!/usr/bin/env python3
"""The quickest proof that the system still starts on the chip.

    python3 chip_smoke.py             # one chip: kernel, train, serve
    python3 chip_smoke.py --chips 4   # one four-chip host: the mesh legs

Drives the main path once through the entry points a user would call
(``r2d2dpg_tpu.train.main``, ``r2d2dpg_tpu.serve.main``), at the full width
of ``walker_r2d2`` — no env-count, batch, hidden or capacity override; only
the number of train phases is cut — with random seeded weights, and checks
what comes out by the repo's own means (backend stamp, learner step count,
finite learn metrics, the compile sentinel, selftest answer codes).

This process is the parent and never imports JAX: a chip belongs to one
process at a time, so each leg is one child (``--leg NAME``), run in turn.
Exit 0, with ``{"ok": true, "device": {...}}`` as the last line of stdout,
only if every leg ran on a TPU and passed; progress goes to stderr.  With no
TPU the first leg says which platform JAX resolved and nothing is trained.

Work files (logs, checkpoints, one ``<leg>.json`` per leg, ``summary.json``)
land in ``chiprun_out/chip_smoke/``.
"""

from __future__ import annotations

import argparse
import contextlib
import csv
import json
import math
import os
import shutil
import signal
import subprocess
import sys
import time
import traceback

HERE = os.path.dirname(os.path.abspath(__file__))
WORK = os.path.join(HERE, "chiprun_out", "chip_smoke")
# The whole smoke must end inside the driver's 1200 s; legs share this.
BUDGET_S = 1100.0
# A leg's exit code when JAX resolved no TPU: nothing else is worth running.
EXIT_NO_TPU = 3
TRAIN_PHASES = 3
# The capacities the configs use (pendulum_tiny, cheetah_pixels,
# pendulum_*, walker/humanoid) at the learner batch they all share, the
# benchmark's walker arena (524,288) and twice that, which the kernel could
# not hold in VMEM while it held the whole vector there (PR 37).
SCATTER_CAPACITIES = (256, 8_000, 50_000, 100_000, 524_288, 1_048_576)
SCATTER_BATCH = 64


class LegFailed(Exception):
    """A check did not hold."""


def _require(cond: bool, what: str) -> None:
    if not cond:
        raise LegFailed(what)


# ------------------------------------------------------------------ children
def _samples(name: str) -> list:
    """``[(labels, sample), ...]`` of one instrument in this process's
    telemetry registry (empty if it was never registered)."""
    from r2d2dpg_tpu import obs

    entry = obs.get_registry().snapshot().get(name, {})
    return [(s["labels"], s) for s in entry.get("samples", [])]


def _train(work: str, config: str, *flags: str) -> dict:
    """``python -m r2d2dpg_tpu.train --config <config> <flags>`` for
    TRAIN_PHASES train phases (after the config's own warm-up and replay
    fill), then the checks every train leg shares."""
    from r2d2dpg_tpu import obs, train
    from r2d2dpg_tpu.configs import get_config

    logdir, ckpt = os.path.join(work, "log"), os.path.join(work, "ckpt")
    train.main(
        [
            "--config", config, "--phases", str(TRAIN_PHASES),
            "--log-every", "1", "--logdir", logdir,
            "--checkpoint-dir", ckpt, "--checkpoint-light", *flags,
        ]
    )
    with open(os.path.join(logdir, "backend.txt")) as f:
        backend = f.read().strip()
    _require(backend == "tpu", f"backend.txt says {backend!r}")

    want_steps = get_config(config).trainer.learner_steps * TRAIN_PHASES
    steps = [s["value"] for _, s in _samples("r2d2dpg_trainer_learner_steps")]
    _require(steps == [want_steps], f"learner steps {steps}, want {want_steps}")

    with open(os.path.join(logdir, "metrics.csv"), newline="") as f:
        rows = list(csv.DictReader(f))
    learn_rows = rows[-TRAIN_PHASES:]
    _require(
        len(learn_rows) == TRAIN_PHASES and "critic_loss" in learn_rows[0],
        f"metrics.csv holds no learn rows ({len(rows)} rows)",
    )
    for row in learn_rows:
        bad = {
            k: v for k, v in row.items()
            if v == "" or not math.isfinite(float(v))
        }
        _require(not bad, f"non-finite metrics at step {row['step']}: {bad}")

    flight = obs.get_flight_recorder().events()
    for kind in ("steady_recompile", "env_native_fallback"):
        hits = [e for e in flight if e["kind"] == kind]
        _require(not hits, f"{len(hits)} {kind} events: {hits[:2]}")
    return {
        "learner_steps": want_steps,
        "final_metrics": {k: float(v) for k, v in learn_rows[-1].items()},
        "checkpoint_dir": ckpt,
    }


def _require_native_pool() -> None:
    """The walker legs stepped the C++ pool built on this machine."""
    pools = {
        labels["pool"]
        for labels, s in _samples("r2d2dpg_envpool_step_seconds")
        if s["count"]
    }
    _require(pools == {"native"}, f"env pools that stepped: {sorted(pools)}")


def _fresh_native_build() -> None:
    """``native/build/`` is ignored by git but present on a copied disk, and
    ``make`` trusts timestamps: remove it so the pool this leg steps is
    built here, from ``native/envpool/env_pool.cc`` as git has it."""
    shutil.rmtree(os.path.join(HERE, "native", "build"), ignore_errors=True)


def _serve(ckpt: str, *flags: str) -> tuple:
    """``python -m r2d2dpg_tpu serve --config walker_r2d2 --selftest 64``
    over a train leg's checkpoint; returns ``(health, service)``: the
    selftest's health record and the service it drove."""
    import io

    from r2d2dpg_tpu import serve

    built = []
    build_service = serve.build_service

    def build_and_keep(args):
        service, env = build_service(args)
        built.append(service)
        return service, env

    serve.build_service = build_and_keep
    out = io.StringIO()
    try:
        with contextlib.redirect_stdout(out):
            serve.main(
                [
                    "--config", "walker_r2d2", "--checkpoint-dir", ckpt,
                    "--selftest", "64", *flags,
                ]
            )
    finally:
        serve.build_service = build_service
        sys.stderr.write(out.getvalue())
    health = json.loads(out.getvalue().strip().splitlines()[-1])
    _require(health["codes"] == {"ok": 64}, f"codes {health['codes']}")
    _require(health["worker_errors"] == 0, f"health {health}")
    return health, built[0]


def scatter_case(capacity: int) -> tuple:
    """``(priority, indices, values, want)``: one seeded write-back at the
    learner batch — both ends of the vector written, two neighbouring slots
    (the kernel fetches their lane-row twice and writes both copies back),
    one slot written four times — and what a sequential loop makes of it
    (the last write wins).
    ``tests/test_replay.py`` checks the interpreted kernel on these same
    cases, so the shapes the chip compiles are the shapes the CPU checks."""
    import numpy as np

    rng = np.random.default_rng(capacity)
    priority = rng.random(capacity, dtype=np.float32)
    indices = rng.integers(0, capacity, SCATTER_BATCH).astype(np.int32)
    indices[:2] = (0, capacity - 1)  # both ends of the padded tile
    indices[3] = indices[4] ^ 1  # two slots of one lane-row (even capacities)
    indices[-3:] = indices[5]  # four writes to one slot
    values = rng.random(SCATTER_BATCH, dtype=np.float32) + 1.0
    want = priority.copy()
    for i, v in zip(indices, values):
        want[i] = v
    return priority, indices, values, want


def _leg_kernel(work: str) -> dict:
    """``priority_scatter`` compiled by Mosaic (not interpreted, not XLA's
    scatter) at the shapes the main path uses, against the sequential
    reference."""
    import jax
    import numpy as np

    from r2d2dpg_tpu.ops.pallas.scatter import priority_scatter

    scatter = jax.jit(priority_scatter)
    for capacity in SCATTER_CAPACITIES:
        priority, indices, values, want = scatter_case(capacity)
        hlo = scatter.lower(priority, indices, values).as_text()
        _require(
            "tpu_custom_call" in hlo,
            f"capacity {capacity}: no Mosaic call in the lowered program",
        )
        got = np.asarray(scatter(priority, indices, values))
        _require(
            np.array_equal(got, want),
            f"capacity {capacity}: {int((got != want).sum())} slots differ "
            "from the sequential reference",
        )
    return {"capacities": list(SCATTER_CAPACITIES), "batch": SCATTER_BATCH}


@contextlib.contextmanager
def _built_trainers():
    """``[(trainer, state), ...]`` for every trainer ``train.main`` builds
    inside the block, ``state`` holding the ``train``, ``arena`` and ``rng``
    its ``init`` returned as shapes (dtype and sharding, no buffers: the
    run's own state is donated away)."""
    import types

    import jax

    from r2d2dpg_tpu import topology

    built: list = []
    build_trainer = topology.build_trainer

    def build_and_watch(topo, cfg):
        trainer = build_trainer(topo, cfg)
        init = trainer.init

        def init_and_note(*a, **kw):
            state = init(*a, **kw)
            shapes = jax.tree_util.tree_map(
                lambda x: jax.ShapeDtypeStruct(
                    x.shape, x.dtype, sharding=x.sharding),
                {"train": state.train, "arena": state.arena, "rng": state.rng},
            )
            built.append((trainer, types.SimpleNamespace(**shapes)))
            return state

        trainer.init = init_and_note
        return trainer

    topology.build_trainer = build_and_watch
    try:
        yield built
    finally:
        topology.build_trainer = build_trainer


# What ``models/torsos.py::ConvTorso.prepare`` leaves of re-laying between
# the gather and the first convolution in an update of a pixel
# configuration's learner call (``obs/hlo.py::frame_relays``): the
# transposition of the sampled bytes, and the gather of its rows into
# ``Conv_0``'s blocks, which the reader counts twice (the gather's fusion
# transposes and reshapes); for ``cheetah_pixels`` 18.9 MB each as laid out
# (the conversion to bfloat16 after them, 37.7 MB, is no re-lay).  Its
# parent made two, 88 MB, and the parent's parent eleven, 325 MB (PERF.md
# PR 35, PR 39).
_FRAME_RELAYS_AN_UPDATE = 3
# One byte an element, in runs of 128 frames: 56,623,104 B over 45 x 32 x
# 12,288 is 3.2.
_FRAME_RELAY_BYTES_A_FRAME_ELEMENT = 3.5


def _small_leaf_bytes(arena_state, obs_shape: tuple) -> int:
    """The bytes of the arena's ``[capacity, ...]`` leaves but an image
    observation's: for ``cheetah_pixels`` at 8,000 slots 45.8 MB beside a
    4.4 GB pixel leaf."""
    import jax

    capacity = arena_state.priority.shape[0]
    image = arena_state.data.obs if len(obs_shape) == 3 else None
    image = {id(x) for x in jax.tree_util.tree_leaves(image)}
    return sum(
        math.prod(x.shape) * x.dtype.itemsize
        for x in jax.tree_util.tree_leaves(arena_state)
        if id(x) not in image and x.ndim >= 2 and x.shape[0] == capacity
    )


def _arena_reads_refused(reads: list, call_loops: int, small_bytes: int) -> list:
    """Of ``obs/hlo.py::arena_reads``' list, the reads the guard refuses:
    those an update makes, that is inside the learner call's loop over its
    updates (``call_loops`` deep) or deeper.

    One kind is let through while it stays small: reads into VMEM (memory
    space 1, ``S(1)`` in the layout) once an update that together take no
    more bytes than the small leaves (``small_bytes``, ``_small_leaf_bytes``).
    The compiler stages a small leaf there to gather the batch's rows from
    when the update leaves VMEM free: at 8,000 slots the pixel
    configuration's learner call slices two ``[8000, 45]`` leaves into VMEM a
    quarter at a time, 8.2 MB an update as laid out (PERF.md PR 39).  A
    large leaf staged so, a staged read inside a scan, or a read into HBM is
    refused."""
    in_updates = [r for r in reads if r[4] >= call_loops]
    staged = [r for r in in_updates if r[4] == call_loops and "S(1)" in r[1]]
    if sum(r[2] for r in staged) > small_bytes:
        return in_updates
    return [r for r in in_updates if r not in staged]


def _require_learner_call_guards(trainer, state, rolled_width=None) -> dict:
    """The five compile-time guards of the learner call
    (docs/OBSERVABILITY.md): ``Trainer._learn_many``, state donated, compiled
    by this chip's compiler at the run's own shapes, rounds no
    ``[capacity, ...]`` value, inserts no sequence into a batch-minor
    ``[batch, ...]`` buffer, keeps no running sum as long as the arena, reads
    no row out of the arena as a slice or copy of many rows' bytes in an
    update (``_arena_reads_refused``) nor re-lays a whole arena leaf in HBM
    (``arena_relays``: a small row stored in its own shape lies slot
    minor-most and was copied whole once a call), and runs no image
    convolution inside a scan of an update.  Only
    the TPU compiler makes the first two choices, gives the third its cost
    (128 adds an element) and lays the arena out (the fourth: a pixel leaf in
    the rows' own shape lies slot minor-most and a row comes out padded 128
    times), so only a chip run can check that ``ReplayArena`` still takes all
    four from it; the fifth says that ``models/sequence.py::Stepped`` took the
    pixel torso out of its scans (trivially so for a configuration without
    one).

    For an image observation a sixth, which only a compile can show as well:
    the sampled frames are prepared once an update and every pass of the
    conv torso reads its window out of the one result
    (``models/torsos.py::ConvTorso.prepare``), so an update writes no more
    than ``_FRAME_RELAYS_AN_UPDATE`` copies, slices, transposes or reshapes of
    a window's frames or more, and no more bytes than the prepared frames
    take; the list is reported for every configuration (for a flat
    observation a window is a few thousand floats and the list says nothing
    about frames).  And everything that reads the prepared frames is a
    stride-1 convolution (``obs/hlo.py::frame_contractions``): ``Conv_0``
    over the frames cut into blocks of its stride, forward and weight
    gradient, and no contraction on the vector unit.

    The write-back of a batch's priorities (``ops/pallas/scatter.py``) is a
    Mosaic call inside the loop over the updates that takes the vector as an
    operand aliased to its result, and, where the vector's length is a
    multiple of 128, no copy, pad or slice of the whole vector that the
    update waits for stands under the scope ``priority_update`` or next to
    the call (``obs/hlo.py::priority_writes``; another length keeps its pad
    and slice, and what the compiler moves asynchronously between HBM and
    VMEM is listed, not refused).

    With ``rolled_width`` (the inner width of a looped stack's MLP) a seventh:
    the products of that width lie inside the stack's two scans (over the
    layers, inside over the loop steps), a copy a pass and not one an
    application: a block written out sixteen times compiles sixteen times as
    long, in every process's set-up.

    Reported, not refused: the compiler's own count of the call's peak memory
    and of its temporaries, and the instructions its rematerialisation cloned
    to fit the chip (``obs/hlo.py::remat_clones``)."""
    import jax

    from r2d2dpg_tpu.obs.hlo import (
        arena_converts,
        arena_reads,
        arena_relays,
        batch_minor_writes,
        capacity_scans,
        frame_contractions,
        frame_relays,
        loop_convolutions,
        loop_products,
        priority_writes,
        remat_clones,
    )

    call = jax.jit(trainer._learn_many, donate_argnums=(0, 1))
    compiled = call.lower(state.train, state.arena, state.rng).compile()
    hlo, memory = compiled.as_text(), compiled.memory_analysis()
    converts = arena_converts(hlo, trainer.arena.capacity)
    _require(
        not converts,
        f"the learner call converts the whole arena once a call: {converts}",
    )
    writes = batch_minor_writes(hlo, trainer.config.batch_size)
    _require(
        not writes,
        f"the learner call writes its sampled batch batch-minor: {writes}",
    )
    scans = capacity_scans(hlo, trainer.arena.capacity)
    _require(
        not scans,
        f"the learner call keeps a running sum as long as the arena: {scans}",
    )
    # The call is itself a loop over its updates (``_learn_many``'s scan,
    # which the compiler keeps where there are two or more), so an update's
    # own operations sit that one loop deep; deeper is a scan inside it.
    call_loops = int(trainer.config.learner_steps > 1)
    # What the compiler slices or copies once a call, outside that loop, is
    # listed here and not refused; a whole leaf re-laid in HBM is refused
    # below (``arena_relays``).
    reads = arena_reads(hlo, trainer.arena.capacity)
    in_updates = _arena_reads_refused(
        reads, call_loops, _small_leaf_bytes(state.arena, trainer.env.spec.obs_shape))
    _require(
        not in_updates,
        "the learner call reads the arena in slices or copies of many rows' "
        f"bytes in every update: {in_updates}",
    )
    relaid = arena_relays(hlo, trainer.arena.capacity)
    _require(
        not relaid,
        f"the learner call re-lays a whole arena leaf in HBM: {relaid}",
    )
    written_back = priority_writes(hlo, trainer.arena.capacity)
    kernels = [w for w in written_back if w[1].startswith("kernel")]
    waited_for = [w for w in written_back
                  if w[1] in ("copy", "pad", "slice", "dynamic-slice")]
    _require(
        kernels
        and all(w[1] == "kernel in place" and w[3] >= call_loops for w in kernels)
        and (trainer.arena.capacity % 128 != 0 or not waited_for),
        "the learner call does not write its priorities back in place, inside "
        f"its loop over the updates, the vector left where it is: {written_back}",
    )
    convolutions = loop_convolutions(hlo)
    in_scans = [c for c in convolutions if c[3] > call_loops]
    _require(
        not in_scans,
        f"the learner call runs an image convolution once a scan step: {in_scans}",
    )
    # A window of the sampled batch's observations: the shorter of burn-in
    # and unroll, of every sequence.
    agent, obs_shape = trainer.agent.config, trainer.env.spec.obs_shape
    steps = min(w for w in (agent.burnin, agent.unroll) if w)
    window = trainer.config.batch_size * steps * math.prod(obs_shape)
    relays = [r for r in frame_relays(hlo, window) if r[3] >= call_loops]
    if len(obs_shape) == 3:  # an image: the conv torso's frames
        allowed = int(_FRAME_RELAY_BYTES_A_FRAME_ELEMENT * window / steps
                      * agent.seq_len)
        _require(
            len(relays) <= _FRAME_RELAYS_AN_UPDATE
            and sum(r[2] for r in relays) <= allowed,
            f"the learner call re-lays its sampled frames {len(relays)} times "
            f"an update, {sum(r[2] for r in relays)} bytes (at most "
            f"{_FRAME_RELAYS_AN_UPDATE}, {allowed}): {relays}",
        )
        contractions = frame_contractions(hlo, window)
        _require(
            contractions and all(c[1] == "convolution" for c in contractions),
            "the learner call reads its prepared frames otherwise than by a "
            f"stride-1 convolution: {contractions}",
        )
    else:
        contractions = []
    rolled = {}
    if rolled_width is not None:
        products = loop_products(hlo, rolled_width)
        outside = [p for p in products if p[2] < call_loops + 2]
        # A pass holds the gate and up products, again for the rematerialised
        # forward, and their gradients: eight at most; an update has nine passes.
        _require(
            products and not outside and len(products) <= 9 * 8,
            f"the looped stack is not rolled: {len(products)} products of width "
            f"{rolled_width}, outside its two scans: {outside}",
        )
        rolled = {"rolled_width": rolled_width, "loop_products": products}
    clones = remat_clones(hlo)
    return {
        **rolled,
        "learner_call_hlo_lines": hlo.count("\n"),
        "memory_peak_bytes": memory.peak_memory_in_bytes,
        "memory_temp_bytes": memory.temp_size_in_bytes,
        "remat_clones": len(clones),
        "remat_clone_names": clones[:16],  # the first of them, as printed
        "arena_capacity": trainer.arena.capacity,
        "arena_converts": converts,
        "batch_size": trainer.config.batch_size,
        "batch_minor_writes": writes,
        "capacity_scans": scans,
        "priority_writes": written_back,
        "arena_reads": reads,
        "arena_reads_in_updates": in_updates,
        "arena_relays": relaid,
        "arena_storage": {
            jax.tree_util.keystr(path): list(leaf.shape)
            for path, leaf in jax.tree_util.tree_leaves_with_path(state.arena.data)
        },
        "loop_convolutions": convolutions,
        "convolutions_in_scans": in_scans,
        "frame_window_elements": window,
        "frame_relays_in_updates": len(relays),
        "frame_relay_bytes_in_updates": sum(r[2] for r in relays),
        "frame_relays": relays[:16],  # the first of them, as printed
        "frame_contractions": contractions,
    }


def _learner_call_from_shapes(config: str, obs_shape: tuple, obs_dtype: str,
                              act_dim: int) -> tuple:
    """``config``'s trainer and the shapes of its learner call's arguments,
    from the configuration alone: nothing is initialised (weights and arena
    stay shapes), and the learner never touches the environment, so a
    stand-in holds its two sizes."""
    import types

    import jax
    import jax.numpy as jnp

    from r2d2dpg_tpu.configs import get_config
    from r2d2dpg_tpu.replay.arena import SequenceBatch
    from r2d2dpg_tpu.training.trainer import Trainer

    exp = get_config(config)
    env = types.SimpleNamespace(
        spec=types.SimpleNamespace(action_dim=act_dim, obs_shape=obs_shape))
    trainer = Trainer(env, exp.build_agent(env), exp.trainer)
    L = exp.agent.seq_len
    obs = jnp.zeros((1,) + obs_shape, jnp.dtype(obs_dtype))

    def arena(key):
        z = lambda *shape: jnp.zeros((1, L) + shape, jnp.float32)  # noqa: E731
        agent = trainer.agent
        return trainer.arena.init_state(SequenceBatch(
            obs=jnp.zeros((1, L) + obs_shape, obs.dtype), action=z(act_dim),
            reward=z(), discount=z(), reset=z(),
            carries=trainer._stored_carries(
                agent.actor.initial_carry(1), agent.critic.initial_carry(1))))

    key = jax.random.PRNGKey(0)
    shapes = jax.eval_shape(
        lambda k: {
            "train": trainer.agent.init(k, obs, jnp.zeros((1, act_dim))),
            "arena": arena(k), "rng": k},
        key)
    return trainer, types.SimpleNamespace(**shapes)


def _held_expert_residuals(config: str) -> dict:
    """What the backward pass of a sparse-expert core's held experts keeps of
    one layer's forward pass over a window (``models/sdar_moe.py::moe`` at
    batch x unroll tokens): the ``[E, N, W]`` residuals ``jax.vjp`` holds,
    traced from shapes (nothing runs), with their bytes."""
    import jax
    import jax.numpy as jnp

    from r2d2dpg_tpu.configs import get_config
    from r2d2dpg_tpu.models import sdar_moe

    exp = get_config(config)
    cfg, tokens = exp.sdar, exp.trainer.batch_size * exp.agent.unroll
    E, H, W = cfg.experts_held, cfg.hidden, cfg.expert_width
    f32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32)  # noqa: E731
    weights = {"router": f32(H, cfg.router_experts), "w_gate": f32(E, H, W),
               "w_up": f32(E, H, W), "w_down": f32(E, W, H)}

    def residuals(h2, p):
        _, backward = jax.vjp(lambda h2, p: sdar_moe.moe(cfg, p, h2)[0], h2, p)
        return jax.tree_util.tree_leaves(backward)

    held = [r for r in jax.eval_shape(residuals, f32(tokens, H), weights)
            if r.shape == (E, tokens, W)]
    return {
        "held_residuals": [f"{r.dtype}{list(r.shape)}" for r in held],
        "held_residual_bytes": sum(r.size * r.dtype.itemsize for r in held),
    }


# The configurations whose learner call the train leg compiles from shapes
# beside ``walker_r2d2``'s own: the whole-sequence cores' (460 M and 416 M
# parameters, a 1.5 GB arena; DM-Control humanoid-run) and the pixel replay's
# (a rank-5 uint8 leaf, 552,960 bytes a sequence; DM-Control cheetah-run at
# 64x64x3).  Last, where the core is a looped stack, the inner width of its
# MLP: the products the rolled-stack guard looks for.
_LEARNER_CALLS_FROM_SHAPES = {
    "learner_call_sdar_moe": ("humanoid_sdar_moe", (67,), "float32", 21, None),
    "learner_call_pixels": ("cheetah_pixels", (64, 64, 3), "uint8", 6, None),
    "learner_call_ouro_loop": ("humanoid_ouro_loop", (67,), "float32", 21, 5632),
}


def _leg_train(work: str) -> dict:
    """Base ``Trainer``: host MuJoCo pool through ordered ``io_callback``
    inside the jitted phase, the HBM arena at capacity 100,000, the Pallas
    write-back, donated state; then the learner call alone, compiled for the
    whole-arena convert guard, the batch-minor write guard, the
    capacity-long running sum guard, the padded arena read guard, the
    whole-leaf re-lay guard, the in-place priority write-back guard, the
    convolution-in-a-scan guard and the frame re-lay guard, for
    ``walker_r2d2`` and, from shapes, for the whole-sequence cores'
    configurations ``humanoid_sdar_moe`` and ``humanoid_ouro_loop`` (the
    latter also for the rolled-stack guard) and the pixel replay's
    ``cheetah_pixels``."""
    _fresh_native_build()
    with _built_trainers() as built:
        checks = _train(work, "walker_r2d2")
    _require_native_pool()
    _require(len(built) == 1, f"{len(built)} trainers were initialised")
    checks["learner_call"] = _require_learner_call_guards(*built[0])
    from r2d2dpg_tpu.configs import get_config

    for name, (*args, rolled_width) in _LEARNER_CALLS_FROM_SHAPES.items():
        checks[name] = _require_learner_call_guards(
            *_learner_call_from_shapes(*args), rolled_width=rolled_width)
        if get_config(args[0]).sdar is not None:
            checks[name].update(_held_expert_residuals(args[0]))
    for name in ["learner_call", *_LEARNER_CALLS_FROM_SHAPES]:
        print(f"chip_smoke: {name} arena storage: "
              + json.dumps(checks[name]["arena_storage"]), flush=True)
        print(f"chip_smoke: {name} memory: " + json.dumps({
            k: checks[name][k] for k in (
                "memory_peak_bytes", "memory_temp_bytes", "remat_clones",
                "held_residuals", "held_residual_bytes") if k in checks[name]}),
            flush=True)
    return checks


def _leg_serve(work: str) -> dict:
    """The orbax partial restore onto the chip, one pinned executable per
    bucket, carries in device slabs."""
    health, _ = _serve(os.path.join(WORK, "train", "ckpt"))
    _require(health["last_reload_error"] is None, f"health {health}")
    return {k: health[k] for k in ("codes", "params_step", "requests_ok")}


def _require_spread(chips: int, arena_devices: list) -> dict:
    """Proof of placement for a mesh train leg: the arena's leaves span
    ``chips`` devices and every one of them holds live bytes."""
    _require(
        arena_devices and set(arena_devices) == {chips},
        f"arena leaves span {sorted(set(arena_devices))} devices, "
        f"want {chips}",
    )
    in_use = {
        labels["device"]: s["value"]
        for labels, s in _samples("r2d2dpg_device_hbm_bytes_in_use")
    }
    _require(
        sum(v > 0 for v in in_use.values()) >= chips,
        f"per-device HBM bytes in use: {in_use}",
    )
    return {"arena_leaf_devices": chips, "hbm_bytes_in_use": in_use}


def _mesh_train(work: str, config: str, *flags: str) -> dict:
    """A train leg on a four-device mesh, with its placement proof."""
    import jax

    with _built_trainers() as built:
        checks = _train(work, config, *flags)
    arena_devices = [
        len(leaf.sharding.device_set)
        for _, state in built
        for leaf in jax.tree_util.tree_leaves(state.arena)
    ]
    checks.update(_require_spread(4, arena_devices))
    return checks


def _leg_spmd_pendulum(work: str) -> dict:
    """``SPMDTrainer``: whole phases under ``shard_map``, the Pallas kernel
    inside it, gradient ``pmean`` over ICI."""
    return _mesh_train(work, "pendulum_r2d2", "--spmd", "4")


def _leg_spmd_walker(work: str) -> dict:
    """``HostSPMDTrainer``: host pool stepped from Python, device compute
    laid out over the mesh."""
    _fresh_native_build()
    checks = _mesh_train(work, "walker_r2d2", "--spmd", "4")
    _require_native_pool()
    return checks


def _leg_learner_dp(work: str) -> dict:
    """``DPLearnerTrainer``: capacity-sharded arena, dp-sharded batch."""
    return _mesh_train(work, "pendulum_r2d2", "--learner-dp", "4")


def _leg_serve_workers(work: str) -> dict:
    """Four routed workers, each with params and slabs on its own chip
    (the router wraps round-robin silently when devices are short)."""
    import jax

    health, router = _serve(
        os.path.join(WORK, "spmd_walker", "ckpt"), "--serve-workers", "4"
    )
    homes = []
    for svc in router.services:
        devices = {
            d
            for leaf in jax.tree_util.tree_leaves((svc._params, svc._slabs))
            for d in leaf.devices()
        }
        _require(len(devices) == 1, f"worker {svc.worker_label}: {devices}")
        homes.append(devices.pop().id)
    _require(len(set(homes)) == 4, f"workers sit on devices {homes}")
    errors = {
        w: snap["last_reload_error"]
        for w, snap in health["per_worker"].items()
        if snap["last_reload_error"]
    }
    _require(not errors, f"reload errors {errors}")
    return {
        "codes": health["codes"],
        "worker_devices": homes,
        "per_worker_requests": {
            w: snap["requests_ok"] for w, snap in health["per_worker"].items()
        },
    }


LEGS = {
    1: {"kernel": _leg_kernel, "train": _leg_train, "serve": _leg_serve},
    4: {
        "spmd_pendulum": _leg_spmd_pendulum,
        "spmd_walker": _leg_spmd_walker,
        "learner_dp": _leg_learner_dp,
        "serve_workers": _leg_serve_workers,
    },
}


def run_leg(chips: int, name: str) -> int:
    """Child body: settle the cache and the device, run one leg, leave
    ``<leg>.json``.  This is the only process that touches JAX."""
    t0 = time.monotonic()
    from r2d2dpg_tpu.utils.startup import enable_compile_cache, require_tpu

    cache_dir = enable_compile_cache()
    try:
        device = require_tpu()
    except SystemExit as e:
        print(f"chip_smoke: {e}", file=sys.stderr)
        return EXIT_NO_TPU
    from r2d2dpg_tpu import obs

    obs.get_device_monitor().install()  # counts every leg's compiles
    result = {"leg": name, "device": device, "compile_cache": cache_dir}
    work = os.path.join(WORK, name)
    os.makedirs(work, exist_ok=True)
    try:
        _require(device["count"] >= chips, f"{device['count']} devices")
        result["checks"] = LEGS[chips][name](work)
        result["ok"] = True
    except (Exception, SystemExit) as e:  # a leg's verdict, never a crash
        traceback.print_exc()
        result["ok"] = False
        result["error"] = f"{type(e).__name__}: {e}"
    compiles = [s for _, s in _samples("r2d2dpg_device_compile_seconds")]
    result["compiles"] = sum(s["count"] for s in compiles)
    result["compile_seconds"] = round(sum(s["total"] for s in compiles), 2)
    result["seconds"] = round(time.monotonic() - t0, 1)
    with open(os.path.join(WORK, f"{name}.json"), "w") as f:
        json.dump(result, f, indent=1, default=str)
    return 0 if result["ok"] else 1


# -------------------------------------------------------------------- parent
def _spawn(chips: int, name: str, timeout: float) -> dict:
    """Run one leg as a child in its own process group; whatever happens,
    nothing of it is left running."""
    log_path = os.path.join(WORK, f"{name}.log")
    cmd = [sys.executable, os.path.abspath(__file__),
           "--chips", str(chips), "--leg", name]
    with open(log_path, "w") as log:
        proc = subprocess.Popen(
            cmd, cwd=HERE, stdout=log, stderr=subprocess.STDOUT,
            start_new_session=True,
        )
        try:
            code = proc.wait(timeout=max(timeout, 1.0))
        except subprocess.TimeoutExpired:
            code = None
        finally:
            if proc.poll() is None:  # timed out, or this parent is dying
                os.killpg(proc.pid, signal.SIGKILL)
                proc.wait()
    result = {"leg": name, "ok": False}
    try:
        with open(os.path.join(WORK, f"{name}.json")) as f:
            result.update(json.load(f))
    except (OSError, ValueError):
        pass  # the leg died before its verdict: the log tail says how
    result["exit"] = code
    result["ok"] = result["ok"] and code == 0
    if code is None:
        result["error"] = f"timed out after {timeout:.0f}s"
    if not result["ok"]:
        with open(log_path, errors="replace") as log:
            tail = log.read()[-6000:]
        print(f"--- {name} log tail ---\n{tail}\n---", file=sys.stderr)
    return result


def main() -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--chips", type=int, default=1, choices=sorted(LEGS))
    p.add_argument("--leg", default=None, help=argparse.SUPPRESS)
    args = p.parse_args()
    if args.leg is not None:
        return run_leg(args.chips, args.leg)

    deadline = time.monotonic() + BUDGET_S
    shutil.rmtree(WORK, ignore_errors=True)
    os.makedirs(WORK)
    results = []
    for name in LEGS[args.chips]:
        res = _spawn(args.chips, name, deadline - time.monotonic())
        results.append(res)
        print(
            "chip_smoke: "
            + json.dumps({k: v for k, v in res.items() if k != "checks"}),
            file=sys.stderr,
            flush=True,
        )
        if res["exit"] == EXIT_NO_TPU:
            break
    with open(os.path.join(WORK, "summary.json"), "w") as f:
        json.dump(results, f, indent=1)
    # A leg only passes after require_tpu() and the device count held in
    # its own process; what is left to check here is that all legs ran,
    # passed, and saw one and the same device.
    devices = [r.get("device") for r in results]
    if not (
        len(results) == len(LEGS[args.chips])
        and all(r["ok"] for r in results)
        and all(d == devices[0] for d in devices)
    ):
        failed = [r["leg"] for r in results if not r["ok"]]
        print(f"chip_smoke: FAILED legs {failed}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": devices[0]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
