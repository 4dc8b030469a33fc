"""Trainer integration: phase schedule, replay fill, episode metrics, and a
budgeted golden-learning run (SURVEY.md §4.3)."""

import dataclasses

import jax
import numpy as np
import pytest

from r2d2dpg_tpu.configs import PENDULUM_DDPG, PENDULUM_R2D2


def small(cfg, **trainer_kw):
    return dataclasses.replace(
        cfg, trainer=dataclasses.replace(cfg.trainer, **trainer_kw)
    )


def test_phase_schedule_and_replay_fill():
    cfg = small(PENDULUM_R2D2, num_envs=2, min_replay=4, capacity=64)
    t = cfg.build()
    s = t.init()
    assert t.window_fill_phases == 4  # seq_len 35 / stride 10
    assert t.replay_fill_phases == 2  # min_replay 4 / 2 envs
    for _ in range(t.window_fill_phases):
        s = t.collect_phase(s)
    assert int(t.arena.size(s.arena)) == 0
    s = t.fill_phase(s)
    assert int(t.arena.size(s.arena)) == 2
    s, metrics = t.train_phase(s)
    assert int(s.train.step) == cfg.trainer.learner_steps
    assert np.isfinite(float(metrics["critic_loss"]))
    # Replay keeps growing during training phases.
    assert int(t.arena.size(s.arena)) == 4


def test_a_trainer_built_beside_another_reads_its_arena():
    """The rows' shapes travel with the arena's state: a second trainer of
    the same configuration (one update a call, as the benchmark's twin),
    whose arena never ran ``init_state``, reads the rows of the state the
    first filled in their own shapes and learns from them."""
    cfg = small(PENDULUM_R2D2, num_envs=2, min_replay=4, capacity=64)
    t = cfg.build()
    twin = small(PENDULUM_R2D2, num_envs=2, min_replay=4, capacity=64,
                 learner_steps=1).build()
    s = t.init()
    for _ in range(t.window_fill_phases + 1):
        s = t.collect_phase(s)
    s = t.fill_phase(s)
    idx = jax.numpy.arange(2)
    for a, b in zip(jax.tree_util.tree_leaves(twin.arena.gather(s.arena, idx)),
                    jax.tree_util.tree_leaves(t.arena.gather(s.arena, idx))):
        assert a.shape == b.shape
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    train, _, metrics = jax.jit(twin._learn_many)(s.train, s.arena, jax.random.PRNGKey(5))
    assert int(train.step) == 1
    assert np.isfinite(np.asarray(metrics["critic_loss"])).all()


def test_run_schedule_counts_env_steps():
    cfg = small(PENDULUM_DDPG, num_envs=2, min_replay=8, capacity=64)
    t = cfg.build()
    s = t.run(12, log_every=0)
    assert int(s.env_steps) == 12 * cfg.trainer.stride * 2
    # phases: 2 window fill (seq_len 2 / stride 1) + 4 replay fill + 6 train
    assert int(s.train.step) == (12 - t.window_fill_phases - t.replay_fill_phases)


def test_episode_metrics_accumulate():
    cfg = small(PENDULUM_DDPG, num_envs=4)
    t = cfg.build()
    t_env = t.env.spec.episode_length  # 200
    s = t.init()
    for _ in range(t_env + 5):  # enough phases (stride 1) to finish episodes
        s = t.collect_phase(s)
    s, m = t.pop_episode_metrics(s)
    assert m["episodes"] >= 4  # each env completed one episode
    assert m["episode_return_mean"] < 0  # pendulum returns are negative
    s, m2 = t.pop_episode_metrics(s)
    assert m2["episodes"] == 0  # drained


def test_prioritized_priorities_change_after_training():
    cfg = small(PENDULUM_R2D2, num_envs=2, min_replay=2, capacity=32)
    t = cfg.build()
    s = t.run(t.window_fill_phases + t.replay_fill_phases + 2, log_every=0)
    prios = np.asarray(s.arena.priority)
    valid = prios[prios > 0]
    assert len(valid) >= 4
    assert valid.std() > 0  # TD-based priorities are not all equal


@pytest.mark.slow
def test_golden_learning_pendulum_ddpg():
    """Config #1 must show clear learning within a small CI budget
    (BASELINE config #1 is 'precisely this smoke slice', SURVEY §4.3).

    Full solve (>= -200) needs ~6k phases; CI asserts the curve is steeply
    improving by 5k: mean return over the last 1k phases > -800 vs a
    random-policy baseline around -1400.
    """
    t = PENDULUM_DDPG.build()
    s = t.run(4000, log_every=0)
    s, _ = t.pop_episode_metrics(s)
    s = t.run(1000, state=s, log_every=0)
    s, m = t.pop_episode_metrics(s)
    assert m["episodes"] > 0
    assert m["episode_return_mean"] > -800, m


def test_phases_compile_once_no_retrace():
    """SURVEY §4.2: each jitted phase traces exactly once across steps."""
    from r2d2dpg_tpu.configs import PENDULUM_TINY

    t = PENDULUM_TINY.build()
    s = t.init()
    for _ in range(t.window_fill_phases + 1):
        s = t.collect_phase(s)
    s = t.fill_phase(s)
    s = t.fill_phase(s)
    s, _ = t.train_phase(s)
    s, _ = t.train_phase(s)
    assert t.collect_phase._cache_size() == 1
    assert t.fill_phase._cache_size() == 1
    assert t.train_phase._cache_size() == 1
