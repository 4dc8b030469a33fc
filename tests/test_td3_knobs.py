"""Overestimation-mitigation knobs (round 3): twin critic (clipped
double-Q) and target-policy smoothing.

The config-#5 CPU evidence run collapsed from critic overestimation
(q_mean rose 0.15 -> 0.95 while eval return fell); these
knobs are the TD3-family fixes, implemented as a vmapped critic ensemble
([2] leading axis on critic leaves, TrainState structure unchanged) and
clipped noise on the bootstrap action.  Both default OFF — the plain-DDPG
path (SURVEY.md §2.4) must be bit-for-bit unaffected.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from r2d2dpg_tpu.agents import AgentConfig, R2D2DPG
from r2d2dpg_tpu.models import ActorNet, CriticNet, unroll
from r2d2dpg_tpu.replay.arena import SequenceBatch

B, OBS, ACT, HID = 4, 3, 2, 16


def make_agent(use_lstm=True, **kw):
    cfg = AgentConfig(
        burnin=kw.pop("burnin", 2 if use_lstm else 0),
        unroll=kw.pop("unroll", 3),
        n_step=kw.pop("n_step", 2),
        **kw,
    )
    actor = ActorNet(action_dim=ACT, hidden=HID, use_lstm=use_lstm)
    critic = CriticNet(hidden=HID, use_lstm=use_lstm)
    return R2D2DPG(actor, critic, cfg)


def make_batch(agent, key=0):
    L = agent.config.seq_len
    ks = jax.random.split(jax.random.PRNGKey(key), 3)
    return SequenceBatch(
        obs=jax.random.normal(ks[0], (B, L, OBS)),
        action=jax.random.uniform(ks[1], (B, L, ACT), minval=-1, maxval=1),
        reward=jax.random.normal(ks[2], (B, L)),
        discount=jnp.ones((B, L)),
        reset=jnp.zeros((B, L)),
        carries={
            "actor": agent.actor.initial_carry(B),
            "critic": agent.critic.initial_carry(B),
        },
    )


def init_state(agent, key=1):
    batch = make_batch(agent)
    return agent.init(
        jax.random.PRNGKey(key), batch.obs[:, 0], batch.action[:, 0]
    )


@pytest.mark.parametrize("use_lstm", [True, False])
def test_twin_critic_ensemble_shapes_and_step(use_lstm):
    agent = make_agent(use_lstm, twin_critic=True)
    plain = make_agent(use_lstm)
    state = init_state(agent)
    # Every critic leaf gains a leading [2] ensemble axis; actor unchanged.
    for tw, pl in zip(
        jax.tree_util.tree_leaves(state.critic_params),
        jax.tree_util.tree_leaves(init_state(plain).critic_params),
    ):
        assert tw.shape == (2,) + pl.shape
    # Members are independently initialized, not copies (check a kernel —
    # biases init to zero in both members).
    kernels = [
        leaf
        for leaf in jax.tree_util.tree_leaves(state.critic_params)
        if leaf.ndim >= 3  # [2, in, out] weight matrices
    ]
    assert kernels and not np.allclose(kernels[0][0], kernels[0][1])
    batch = make_batch(agent)
    w = jnp.ones((B,))
    new, prios, metrics = jax.jit(agent.learner_step)(state, batch, w)
    assert prios.shape == (B,)
    assert "q_spread" in metrics
    for k, v in metrics.items():
        assert np.isfinite(float(v)), (k, metrics)
    # Both members actually trained (params moved on each slice).
    for tw_new, tw_old in zip(
        jax.tree_util.tree_leaves(new.critic_params),
        jax.tree_util.tree_leaves(state.critic_params),
    ):
        assert not np.allclose(tw_new[0], tw_old[0])
        assert not np.allclose(tw_new[1], tw_old[1])


def test_twin_min_bootstrap_lowers_targets():
    """Clipped double-Q: the twin bootstrap is min(Q1', Q2'), so for the
    same member-0 target critic the twin target can only be <= the plain
    single-critic target."""
    agent = make_agent(use_lstm=False, twin_critic=True)
    plain = make_agent(use_lstm=False)
    state = init_state(agent)
    batch = make_batch(agent)
    w = slice(agent.config.burnin, agent.config.seq_len)
    obs_w = jnp.swapaxes(batch.obs[:, w], 0, 1)
    reset_w = jnp.swapaxes(batch.reset[:, w], 0, 1)
    ca, ca_tg, cc, cc_tg = agent.seq.burn_in(state, batch)
    q_twin = agent._target_q(state, ca_tg, cc_tg, obs_w, reset_w, None)
    # Plain agent with member 0's params only.
    member0 = jax.tree_util.tree_map(lambda x: x[0], state.critic_params)
    from r2d2dpg_tpu.agents.ddpg import TrainState

    state0 = TrainState(
        actor_params=state.actor_params,
        critic_params=member0,
        target_actor_params=state.target_actor_params,
        target_critic_params=jax.tree_util.tree_map(
            lambda x: x[0], state.target_critic_params
        ),
        actor_opt_state=None,
        critic_opt_state=None,
        step=state.step,
    )
    ca0, ca_tg0, cc0, cc_tg0 = plain.seq.burn_in(state0, batch)
    q_plain = plain._target_q(state0, ca_tg0, cc_tg0, obs_w, reset_w, None)
    assert np.all(np.asarray(q_twin) <= np.asarray(q_plain) + 1e-6)


def test_twin_fused_and_unfused_burnin_agree():
    """The twin agent's four burn-in carries against a reference built here,
    net by net and member by member: each unrolled alone over the prefix from
    the stored carry, the members stacked."""
    agent = make_agent(use_lstm=True, twin_critic=True)
    state = init_state(agent)
    batch = make_batch(agent)
    h = jax.random.normal(jax.random.PRNGKey(9), (B, HID))
    batch = dataclasses.replace(
        batch, reset=batch.reset.at[1, 1].set(1.0),
        carries={"actor": (h, 0.5 * h), "critic": (-h, 0.25 * h)},
    )
    n = agent.config.burnin
    tm = lambda x: jnp.swapaxes(x[:, :n], 0, 1)  # noqa: E731
    obs, act, reset = tm(batch.obs), tm(batch.action), tm(batch.reset)

    def actor(p):
        return unroll(lambda c, o, r: agent.actor.apply(p, o, c, r),
                      batch.carries["actor"], obs, reset)[1]

    def critics(p2):
        members = [
            unroll(lambda c, o, a, r: agent.critic.apply(p, o, a, c, r),
                   batch.carries["critic"], obs, act, reset)[1]
            for p in (jax.tree_util.tree_map(lambda x: x[i], p2) for i in range(2))
        ]
        return jax.tree_util.tree_map(lambda a, b: jnp.stack([a, b]), *members)

    want = (actor(state.actor_params), actor(state.target_actor_params),
            critics(state.critic_params), critics(state.target_critic_params))
    got = agent.seq.burn_in(state, batch)
    assert jax.tree_util.tree_structure(got) == jax.tree_util.tree_structure(want)
    assert not np.allclose(want[2][1][0], want[2][1][1])  # the members part
    for a, b in zip(
        jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)
    ):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)


def test_target_policy_smoothing_requires_and_uses_key():
    agent = make_agent(use_lstm=False, target_policy_sigma=0.2)
    state = init_state(agent)
    batch = make_batch(agent)
    w = jnp.ones((B,))
    with pytest.raises(ValueError, match="target_policy_sigma"):
        agent.learner_step(state, batch, w)
    k1, k2 = jax.random.split(jax.random.PRNGKey(7))
    _, p1, m1 = agent.learner_step(state, batch, w, key=k1)
    _, p2, m2 = agent.learner_step(state, batch, w, key=k2)
    for k, v in m1.items():
        assert np.isfinite(float(v)), (k, m1)
    # Different smoothing draws -> different targets -> different priorities.
    assert not np.allclose(np.asarray(p1), np.asarray(p2))


def test_knobs_off_is_plain_ddpg_bit_for_bit():
    """Default config must be unaffected by the knob plumbing: with sigma 0
    the key is ignored, and the no-key call matches round-2 semantics."""
    agent = make_agent(use_lstm=True)
    state = init_state(agent)
    batch = make_batch(agent)
    w = jnp.ones((B,))
    s1, p1, m1 = agent.learner_step(state, batch, w)
    s2, p2, m2 = agent.learner_step(state, batch, w, key=jax.random.PRNGKey(3))
    np.testing.assert_array_equal(np.asarray(p1), np.asarray(p2))
    for a, b in zip(
        jax.tree_util.tree_leaves(s1.critic_params),
        jax.tree_util.tree_leaves(s2.critic_params),
    ):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert "q_spread" not in m1


def test_twin_overlap_hybrid_trainer_smoke():
    """The campaign's config-#5 on-chip combination: twin critic + overlap
    learner in the hybrid (host-pool) trainer, via the same build() routing
    train.py uses without --spmd.  One full interleaved train phase."""
    import dataclasses

    from r2d2dpg_tpu.configs import WALKER_R2D2
    from r2d2dpg_tpu.parallel import HostSPMDTrainer

    cfg = dataclasses.replace(
        WALKER_R2D2,
        hidden=32,
        agent=dataclasses.replace(
            WALKER_R2D2.agent,
            burnin=2,
            unroll=4,
            n_step=2,
            twin_critic=True,
            target_policy_sigma=0.2,
        ),
        trainer=dataclasses.replace(
            WALKER_R2D2.trainer,
            num_envs=2,
            stride=4,
            batch_size=2,
            capacity=16,
            min_replay=2,
            learner_steps=2,
            overlap_learner=True,
        ),
    )
    trainer = cfg.build()
    assert isinstance(trainer, HostSPMDTrainer)
    state = trainer.init()
    for _ in range(trainer.window_fill_phases):
        state = trainer.collect_phase(state)
    for _ in range(trainer.replay_fill_phases):
        state = trainer.fill_phase(state)
    state, metrics = trainer.train_phase(state)
    assert int(state.train.step) == 2  # both interleaved updates ran
    assert "q_spread" in metrics
    for k, v in metrics.items():
        assert np.isfinite(float(v)), (k, metrics)


def test_twin_initial_priority_and_trainer_smoke():
    """End-to-end: a tiny pendulum trainer with both knobs on runs a full
    train phase with finite metrics (covers the trainer key plumbing)."""
    import dataclasses

    from r2d2dpg_tpu.configs import PENDULUM_TINY

    cfg = dataclasses.replace(
        PENDULUM_TINY,
        agent=dataclasses.replace(
            PENDULUM_TINY.agent, twin_critic=True, target_policy_sigma=0.2
        ),
    )
    trainer = cfg.build()
    state = trainer.init()
    for _ in range(trainer.window_fill_phases):
        state = trainer.collect_phase(state)
    state = trainer.fill_phase(state)
    state, metrics = trainer.train_phase(state)
    assert int(state.train.step) == trainer.config.learner_steps
    for k, v in metrics.items():
        assert np.isfinite(float(v)), (k, metrics)
