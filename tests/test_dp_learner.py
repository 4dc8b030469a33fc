"""Data-parallel learner (parallel/dp_learner.py, ISSUE 9).

Covers the dp-sharded drain/learn path on the virtual CPU mesh, the
``--learner-dp`` CLI wiring + refused knob combos, the coalesce-width
precompile (the ``fleet_coalesce`` regression fix), and the
determinism anchor extending the ``--actors 0`` bit-identical contract to
``--learner-dp 1`` — ``scripts/lib_gate.sh learner_dp_gate`` refuses to
bless ``--learner-dp N`` evidence dirs unless that anchor passes.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from r2d2dpg_tpu.configs import PENDULUM_TINY
from r2d2dpg_tpu.parallel import DPLearnerTrainer, make_mesh
from r2d2dpg_tpu.parallel.mesh import DP_AXIS
from r2d2dpg_tpu.training.assembler import emit
from r2d2dpg_tpu.training.pipeline import drain_staged, split_state
from r2d2dpg_tpu.replay.arena import StagedSequences, stack_staged

N_TRAIN = 10
LOG_EVERY = 3


def _leaves_equal(a, b):
    la, lb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    assert len(la) == len(lb)
    return [
        i
        for i, (x, y) in enumerate(zip(la, lb))
        if not np.array_equal(np.asarray(x), np.asarray(y))
    ]


def _host_staged(trainer, state):
    """A numpy staged batch shaped exactly like one fleet actor emission
    (E sequences off the trainer's own window), priorities resolved."""
    seq = jax.tree_util.tree_map(np.asarray, jax.device_get(emit(state.window)))
    b = np.shape(seq.reward)[0]
    return StagedSequences(seq=seq, priorities=np.ones((b,), np.float32))


# ------------------------------------------------------- determinism anchor
def test_learner_dp1_actors0_determinism_bit_identical(
    tmp_path, phase_locked_reference_k10
):
    """--learner-dp 1 --actors 0 == the untouched phase-locked Trainer.run,
    leaf-for-leaf bitwise, END TO END through the train.py CLI path — the
    degenerate 1-device mesh must annotate layouts without changing one
    bit of the trajectory (learner_dp_gate runs this by its 'determinism'
    name).  The reference half is the shared session fixture
    (tests/conftest.py) — the pairing assert keeps it honest."""
    from r2d2dpg_tpu import train
    from r2d2dpg_tpu.utils import CheckpointManager
    from r2d2dpg_tpu.utils.checkpoint import resume_state

    assert (N_TRAIN, LOG_EVERY) == (10, 3)  # the k10 fixture's recipe
    s1 = phase_locked_reference_k10

    train.run(
        train.parse_args(
            [
                "--config", "pendulum_tiny",
                "--learner-dp", "1",
                "--actors", "0",
                "--phases", str(N_TRAIN),
                "--log-every", str(LOG_EVERY),
                "--checkpoint-dir", str(tmp_path / "ckpt"),
                "--checkpoint-every", "-1",
                "--watchdog", "0",
            ]
        )
    )
    t2 = PENDULUM_TINY.build()
    s2 = resume_state(
        t2, CheckpointManager(str(tmp_path / "ckpt"), save_every=-1)
    )
    bad = _leaves_equal(s1, s2)
    assert not bad, f"state diverged at leaves {bad}"


# ------------------------------------------------------------ dp=2 learner
def test_dp2_drain_keeps_arena_sharded_and_layout_stable():
    """drain_staged on a dp=2 trainer: the arena stays capacity-sharded
    across donated drain calls (stable avals = stable jit cache), counters
    advance, and the learner step lands."""
    from jax.sharding import PartitionSpec as P

    t = PENDULUM_TINY.build_dp_learner(make_mesh(2), collect_local=True)
    state = t.init()
    staged = _host_staged(t, state)
    _, lstate = split_state(state)
    prog = jax.jit(
        lambda ls, st, learn: drain_staged(t, ls, st, learn=learn),
        donate_argnums=(0,),
        static_argnums=(2,),
    )
    # Absorb past min_replay (8 seqs at E=4 -> 2 absorbs), then learn.
    for _ in range(2):
        lstate, _ = prog(lstate, t._put_staged(staged), False)
    sharding_before = lstate.arena.priority.sharding
    assert sharding_before.spec == P(DP_AXIS)
    lstate, metrics = prog(lstate, t._put_staged(staged), True)
    assert lstate.arena.priority.sharding.spec == sharding_before.spec
    assert int(lstate.train.step) == t.config.learner_steps
    assert int(lstate.arena.total_added) == 12
    assert np.isfinite(float(metrics["critic_loss"]))


def test_dp2_put_staged_layouts():
    """_put_staged lays divisible widths over dp and replicates foreign
    (indivisible) widths instead of failing."""
    t = PENDULUM_TINY.build_dp_learner(make_mesh(2), collect_local=True)
    state = t.init()
    staged = _host_staged(t, state)  # B = 4, divisible by 2
    placed = t._put_staged(staged)
    assert placed.seq.obs.sharding.spec[0] == DP_AXIS
    odd = jax.tree_util.tree_map(lambda x: np.asarray(x)[:3], staged)
    placed_odd = t._put_staged(odd)
    assert not any(placed_odd.seq.obs.sharding.spec)  # replicated
    # Multi-process: divisibility is global (b * nproc), and indivisible
    # widths are refused loudly — the replicate fallback would build
    # per-process-inconsistent arrays.
    t._nproc = 3
    try:
        with pytest.raises(ValueError, match="does not divide"):
            t._put_staged(odd)  # 3 * 3 = 9 rows over a 2-device mesh
    finally:
        t._nproc = 1


def test_dp2_log_extra_refs_publish_shard_gauges():
    """The per-shard occupancy gauges ride the log-cadence fetch hooks."""
    from r2d2dpg_tpu.obs import get_registry

    t = PENDULUM_TINY.build_dp_learner(make_mesh(2), collect_local=True)
    state = t.init()
    staged = _host_staged(t, state)
    _, lstate = split_state(state)
    lstate, _ = jax.jit(
        lambda ls, st: drain_staged(t, ls, st, learn=False),
        donate_argnums=(0,),
    )(lstate, t._put_staged(staged))
    refs = t._log_extra_refs(lstate.arena)
    assert len(refs) == 1
    t._log_extra_publish(jax.device_get(refs))
    t.dp_set_learn_width(4)  # the fleet drain site's dispatch-width note
    snap = get_registry().snapshot()
    samples = snap["r2d2dpg_dp_shard_occupancy"]["samples"]
    by_shard = {s["labels"]["shard"]: s["value"] for s in samples}
    assert by_shard["0"] == 4.0 and by_shard["1"] == 0.0
    width = snap["r2d2dpg_dp_shard_learn_width"]["samples"][0]["value"]
    assert width == 2.0  # 4 rows over 2 shards


def test_dp_learner_divisibility_and_agent_axis_validation():
    from r2d2dpg_tpu.configs import ExperimentConfig  # noqa: F401 (doc)

    env = PENDULUM_TINY.env_factory()
    agent = PENDULUM_TINY.build_agent(env)
    import dataclasses

    bad = dataclasses.replace(PENDULUM_TINY.trainer, batch_size=9)
    with pytest.raises(ValueError, match="divisible"):
        DPLearnerTrainer(env, agent, bad, make_mesh(2))
    spmd_agent = PENDULUM_TINY.build_agent(env, axis_name=DP_AXIS)
    with pytest.raises(ValueError, match="axis_name"):
        DPLearnerTrainer(env, spmd_agent, PENDULUM_TINY.trainer, make_mesh(2))


# ------------------------------------------------------------- CLI wiring
def test_train_cli_refuses_learner_dp_combos():
    from r2d2dpg_tpu import train

    for flags in (
        ["--spmd", "2"],
        ["--pipeline", "1"],
        ["--overlap-learner", "1"],
    ):
        args = train.parse_args(
            ["--config", "pendulum_tiny", "--learner-dp", "2", *flags]
        )
        with pytest.raises(SystemExit, match="does not compose"):
            train.run(args)
    # Indivisible mesh (capacity 256 / batch 8 vs dp=3): refused loudly.
    args = train.parse_args(
        ["--config", "pendulum_tiny", "--learner-dp", "3"]
    )
    with pytest.raises(SystemExit, match="divisible"):
        train.run(args)


# ---------------------------------------------- coalesce-width precompile
def test_warm_drain_widths_precompiles_and_matches_jit():
    """The background coalesce precompile (fleet/ingest.py): every
    power-of-two width lands in _drain_exec keyed by TOTAL staged B,
    _coalesce_ready rises to the cap, and the AOT-compiled width-2 drain
    is BITWISE the jit path's result on identical inputs."""
    from r2d2dpg_tpu.fleet import FleetConfig, FleetLearner
    from r2d2dpg_tpu.fleet.ingest import aval_tree

    t = PENDULUM_TINY.build()
    fl = FleetLearner(t, FleetConfig(num_actors=1, drain_coalesce=4))
    state = t.init()
    _, lstate = split_state(state)
    staged = _host_staged(t, state)
    b0 = int(np.shape(staged.seq.reward)[0])

    fl._warm_drain_widths(aval_tree(lstate), staged)
    # w=1 included: when the first learn pull is coalesced, the jit
    # wrapper's width-1 entry is never populated, so width 1 needs its
    # own AOT object too (ingest.py warm loop comment).
    assert set(fl._drain_exec) == {b0, 2 * b0, 4 * b0}
    assert fl._coalesce_ready == 4

    # Two identical learner states (same seed), absorbed identically past
    # min_replay, drained width-2 through the AOT object vs the jit.
    def fresh_lstate():
        _, ls = split_state(t.init())
        for _ in range(2):
            ls, _ = drain_staged(t, ls, staged, learn=False)
        return ls

    stacked = stack_staged([staged, staged])
    out_a, m_a = fl._drain_exec[2 * b0](fresh_lstate(), stacked)
    out_b, m_b = fl._drain_prog(fresh_lstate(), stacked)
    assert not _leaves_equal(out_a, out_b)
    assert not _leaves_equal(m_a, m_b)
