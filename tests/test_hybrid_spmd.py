"""HostSPMDTrainer: DMC host-pool training sharded over the dp mesh.

Runs on the 8-device virtual CPU mesh (conftest).  Covers the previously
documented gap (docs/PARITY.md delta #3): multi-chip training with
host-backed envs — device compute pjit-sharded, env pool stepped from host.
"""

import dataclasses

import jax
import numpy as np
import pytest

from r2d2dpg_tpu.configs import WALKER_R2D2
from r2d2dpg_tpu.parallel import DP_AXIS, HostSPMDTrainer, make_mesh

# Deliberately NOT slow-marked (VERDICT r1 weak #6): this is the only default
# coverage of the host-pool multi-chip path; the whole file runs in ~30s on
# the virtual CPU mesh.

D = 4  # mesh size (of the 8 virtual devices)


def make_trainer(num_envs=4, **overrides):
    mesh = make_mesh(D)
    tiny = dict(
        num_envs=num_envs,
        stride=4,
        batch_size=4,
        capacity=64,
        min_replay=4,
        learner_steps=1,
    )
    tiny.update(overrides)
    cfg = dataclasses.replace(
        WALKER_R2D2,
        trainer=dataclasses.replace(WALKER_R2D2.trainer, **tiny),
        hidden=32,
        agent=dataclasses.replace(
            WALKER_R2D2.agent, burnin=2, unroll=4, n_step=2
        ),
    )
    trainer = cfg.build_spmd(mesh)
    assert isinstance(trainer, HostSPMDTrainer)
    return trainer


def test_hybrid_runs_and_learns_shapes():
    trainer = make_trainer()
    state = trainer.init()
    # Fleet state is laid out over the mesh.
    assert state.obs.sharding.spec == jax.sharding.PartitionSpec(DP_AXIS)
    for _ in range(trainer.window_fill_phases):
        state = trainer.collect_phase(state)
    state = trainer.fill_phase(state)
    assert int(trainer.arena.size(state.arena)) == 4
    state, metrics = trainer.train_phase(state)
    assert int(state.train.step) == 1
    for k, v in metrics.items():
        assert np.isfinite(float(v)), (k, metrics)
    # The window stays sharded; the arena is replicated by design (see
    # hybrid.py layout note).
    assert state.window.obs.sharding.spec[0] == DP_AXIS
    # (a field stored in parts, ``StoredRows``, replicates every part).
    for leaf in jax.tree_util.tree_leaves(state.arena.data.obs):
        assert leaf.sharding.is_fully_replicated
    # Params stay replicated (pjit keeps them unsharded across the mesh).
    leaf = jax.tree_util.tree_leaves(state.train.actor_params)[0]
    assert leaf.sharding.is_fully_replicated


def test_hybrid_overlap_learner_path():
    """overlap_learner=True: updates dispatched between env steps must yield
    the same step accounting and finite metrics; sampling lags one emit."""
    trainer = make_trainer(overlap_learner=True, learner_steps=3)
    state = trainer.init()
    for _ in range(trainer.window_fill_phases):
        state = trainer.collect_phase(state)
    state = trainer.fill_phase(state)
    size_before = int(trainer.arena.size(state.arena))
    state, metrics = trainer.train_phase(state)
    # All learner_steps ran, interleaved.
    assert int(state.train.step) == 3
    for k, v in metrics.items():
        assert np.isfinite(float(v)), (k, metrics)
    # The phase still emitted its sequence (after the updates).
    assert int(trainer.arena.size(state.arena)) == size_before + 4
    # A second phase keeps running (exercises pass-through aliasing of the
    # un-donated substep buffers across phases).
    state, metrics = trainer.train_phase(state)
    assert int(state.train.step) == 6


def test_hybrid_overlap_denser_than_stride():
    """learner_steps > stride (the campaign's ls192-over-stride-20 regime,
    scaled down): the even-spread dispatcher must run multiple updates per
    env-step gap and still complete exactly learner_steps of them."""
    trainer = make_trainer(overlap_learner=True, learner_steps=9)  # stride 4
    state = trainer.init()
    for _ in range(trainer.window_fill_phases):
        state = trainer.collect_phase(state)
    state = trainer.fill_phase(state)
    state, metrics = trainer.train_phase(state)
    assert int(state.train.step) == 9
    for k, v in metrics.items():
        assert np.isfinite(float(v)), (k, metrics)


def test_hybrid_per_step_jits_stop_retracing():
    """The host loop dispatches _act_step per env step and _learn_substep per
    learner update; a retrace per step or per phase (e.g. a Python int key
    index) would silently destroy collect throughput.  The first phase may
    legitimately add a second cache entry (init-produced NamedShardings vs
    jit-output GSPMDShardings hash differently; the re-trace hits the
    lowering cache, no second XLA compile) — the guard is that the cache
    stops growing once steady-state shardings flow."""
    trainer = make_trainer(overlap_learner=True, learner_steps=2)
    state = trainer.init()
    for _ in range(trainer.window_fill_phases):
        state = trainer.collect_phase(state)
    state = trainer.fill_phase(state)
    state, _ = trainer.train_phase(state)
    sizes = {
        fn: fn._cache_size()
        for fn in (trainer._act_step, trainer._learn_substep, trainer._collect_setup)
    }
    for _ in range(3):
        state, _ = trainer.train_phase(state)
    for fn, before in sizes.items():
        assert fn._cache_size() == before, (fn, before, fn._cache_size())


def test_hybrid_env_steps_and_episode_accounting():
    trainer = make_trainer()
    state = trainer.init()
    for _ in range(3):
        state = trainer.collect_phase(state)
    # 3 phases x stride 4 x 4 envs
    assert int(state.env_steps) == 48
    # Walker episodes are 500 agent steps (repeat 2): none completed yet.
    assert float(state.completed_count) == 0.0


def test_hybrid_divisibility_validation():
    with pytest.raises(ValueError, match="divisible"):
        make_trainer(num_envs=6)


def test_hybrid_rejects_pure_jax_env():
    from r2d2dpg_tpu.agents import AgentConfig, R2D2DPG
    from r2d2dpg_tpu.envs import Pendulum
    from r2d2dpg_tpu.models import ActorNet, CriticNet

    env = Pendulum()
    agent = R2D2DPG(
        ActorNet(action_dim=1, hidden=8), CriticNet(hidden=8), AgentConfig()
    )
    with pytest.raises(ValueError, match="host-pool"):
        HostSPMDTrainer(env, agent, WALKER_R2D2.trainer, make_mesh(D))
