"""Learner-step tests: loss directions, target updates, priorities, burn-in
correctness (SURVEY.md §4.1 — "the §4.1 unit tests before anything learns")."""

import dataclasses

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from r2d2dpg_tpu.agents import AgentConfig, R2D2DPG
from r2d2dpg_tpu.models import ActorNet, CriticNet, unroll
from r2d2dpg_tpu.models.sequence import Stepped
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
    carries = {
        "actor": agent.actor.initial_carry(B),
        "critic": agent.critic.initial_carry(B),
    }
    return SequenceBatch(
        obs=jax.random.normal(ks[0], (B, L, OBS)),
        action=jax.random.uniform(ks[1], (B, L, ACT), minval=-1, maxval=1),
        reward=jax.random.normal(ks[2], (B, L)),
        discount=jnp.ones((B, L)),
        reset=jnp.zeros((B, L)),
        carries=carries,
    )


@pytest.mark.parametrize("use_lstm", [True, False])
def test_learner_step_runs_and_updates(use_lstm):
    agent = make_agent(use_lstm)
    batch = make_batch(agent)
    state = agent.init(
        jax.random.PRNGKey(0), batch.obs[:, 0], batch.action[:, 0]
    )
    new_state, prios, metrics = jax.jit(agent.learner_step)(
        state, batch, jnp.ones(B)
    )
    assert int(new_state.step) == 1
    assert prios.shape == (B,)
    assert np.all(np.asarray(prios) > 0)
    # Params actually moved.
    moved = jax.tree_util.tree_map(
        lambda a, b: float(jnp.abs(a - b).max()),
        state.critic_params,
        new_state.critic_params,
    )
    assert max(jax.tree_util.tree_leaves(moved)) > 0
    for k in ("critic_loss", "actor_loss", "q_mean", "td_abs_mean"):
        assert np.isfinite(float(metrics[k])), k


def test_target_nets_polyak_not_copy():
    agent = make_agent(False, tau=0.5)
    batch = make_batch(agent)
    state = agent.init(jax.random.PRNGKey(0), batch.obs[:, 0], batch.action[:, 0])
    new_state, _, _ = agent.learner_step(state, batch, jnp.ones(B))
    # target' = tau*online' + (1-tau)*target, with target == old online.
    leaf = lambda t: jax.tree_util.tree_leaves(t)[0]  # noqa: E731
    want = 0.5 * leaf(new_state.critic_params) + 0.5 * leaf(state.critic_params)
    np.testing.assert_allclose(
        np.asarray(leaf(new_state.target_critic_params)),
        np.asarray(want),
        rtol=1e-5,
        atol=1e-6,
    )


def test_critic_loss_decreases_on_fixed_batch():
    """Repeated steps on one batch must reduce critic TD loss (sanity)."""
    agent = make_agent(False, critic_lr=1e-2, actor_lr=0.0, tau=0.0)
    batch = make_batch(agent)
    state = agent.init(jax.random.PRNGKey(0), batch.obs[:, 0], batch.action[:, 0])
    step = jax.jit(agent.learner_step)
    first = last = None
    for _ in range(50):
        state, _, metrics = step(state, batch, jnp.ones(B))
        if first is None:
            first = float(metrics["critic_loss"])
        last = float(metrics["critic_loss"])
    assert last < first * 0.5, (first, last)


def test_is_weights_scale_critic_gradient():
    agent = make_agent(False)
    batch = make_batch(agent)
    state = agent.init(jax.random.PRNGKey(0), batch.obs[:, 0], batch.action[:, 0])
    _, _, m1 = agent.learner_step(state, batch, jnp.ones(B))
    _, _, m2 = agent.learner_step(state, batch, jnp.zeros(B))
    assert float(m2["critic_loss"]) == 0.0
    assert float(m1["critic_loss"]) > 0.0


def test_burn_in_changes_outcome_only_for_lstm():
    """Burn-in must affect the training-window carries for LSTM nets."""
    agent = make_agent(True, burnin=4, unroll=2, n_step=1)
    batch = make_batch(agent)
    state = agent.init(jax.random.PRNGKey(0), batch.obs[:, 0], batch.action[:, 0])
    _, prios_a, _ = agent.learner_step(state, batch, jnp.ones(B))

    # Different burn-in prefix -> different warmed carries -> different TDs.
    obs2 = batch.obs.at[:, : agent.config.burnin].set(
        batch.obs[:, : agent.config.burnin] + 1.0
    )
    batch2 = SequenceBatch(
        obs=obs2,
        action=batch.action,
        reward=batch.reward,
        discount=batch.discount,
        reset=batch.reset,
        carries=batch.carries,
    )
    _, prios_b, _ = agent.learner_step(state, batch2, jnp.ones(B))
    assert not np.allclose(np.asarray(prios_a), np.asarray(prios_b))


def test_reset_inside_window_isolates_past():
    """A reset at window position t makes the LSTM ignore anything before t:
    two batches differing only before the reset yield identical TDs after it
    (SURVEY §7 hard part 2 — the classic silent-correctness bug)."""
    agent = make_agent(True, burnin=2, unroll=3, n_step=1)
    L = agent.config.seq_len
    base = make_batch(agent)
    reset = jnp.zeros((B, L)).at[:, 2].set(1.0)  # reset at start of window

    def with_obs(obs):
        return SequenceBatch(
            obs=obs,
            action=base.action,
            reward=base.reward,
            discount=base.discount,
            reset=reset,
            carries=base.carries,
        )

    state = agent.init(jax.random.PRNGKey(0), base.obs[:, 0], base.action[:, 0])
    obs_b = base.obs.at[:, :2].set(base.obs[:, :2] * 3.0 + 1.0)
    _, p1, _ = agent.learner_step(state, with_obs(base.obs), jnp.ones(B))
    _, p2, _ = agent.learner_step(state, with_obs(obs_b), jnp.ones(B))
    np.testing.assert_allclose(np.asarray(p1), np.asarray(p2), rtol=1e-5)


def test_initial_priority_matches_learner_td():
    """initial_priority must equal the priority the learner would assign
    (same nets, same batch, before any update)."""
    agent = make_agent(True)
    batch = make_batch(agent)
    state = agent.init(jax.random.PRNGKey(0), batch.obs[:, 0], batch.action[:, 0])
    p_init = agent.initial_priority(state, batch)
    _, p_learn, _ = agent.learner_step(state, batch, jnp.ones(B))
    np.testing.assert_allclose(
        np.asarray(p_init), np.asarray(p_learn), rtol=1e-4, atol=1e-5
    )


def unfused_burn_in(agent, state, batch):
    """The reference burn-in: each of the four nets unrolled alone over the
    prefix, from the stored carry."""
    n = agent.config.burnin
    tm = lambda x: jnp.swapaxes(x[:, :n], 0, 1)  # noqa: E731
    obs, act, reset = tm(batch.obs), tm(batch.action), tm(batch.reset)

    def actor(p):
        return unroll(lambda c, o, r: agent.actor.apply(p, o, c, r),
                      batch.carries["actor"], obs, reset)[1]

    def critic(p):
        return unroll(lambda c, o, a, r: agent.critic.apply(p, o, a, c, r),
                      batch.carries["critic"], obs, act, reset)[1]

    return (actor(state.actor_params), actor(state.target_actor_params),
            critic(state.critic_params), critic(state.target_critic_params))


def desynced(state):
    """Targets moved off the online nets, so that a carry warmed with the
    wrong net's weights would show."""
    return dataclasses.replace(
        state,
        target_actor_params=jax.tree_util.tree_map(
            lambda x: x + 0.1, state.actor_params
        ),
        target_critic_params=jax.tree_util.tree_map(
            lambda x: x - 0.1, state.critic_params
        ),
    )


def test_fused_burnin_matches_unfused():
    """The stacked-params fused burn-in must produce the same warmed carries
    as four separate unrolls."""
    agent = make_agent(use_lstm=True, burnin=4)
    batch = make_batch(agent, key=3)
    # Non-trivial stored carries + a mid-burnin reset row.
    h = jax.random.normal(jax.random.PRNGKey(9), (B, HID))
    batch = dataclasses.replace(
        batch, reset=batch.reset.at[1, 2].set(1.0),
        carries={"actor": (h, 0.5 * h), "critic": (-h, 0.25 * h)},
    )
    state = desynced(
        agent.init(jax.random.PRNGKey(0), batch.obs[:, 0], batch.action[:, 0])
    )
    got = agent.seq.burn_in(state, batch)
    want = unfused_burn_in(agent, state, batch)
    assert not np.allclose(want[0][1], want[1][1])  # online and target part
    for g, w in zip(got, want):
        jax.tree_util.tree_map(
            lambda a, b: np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6),
            g,
            w,
        )


class LeakyActor(nn.Module):
    """A stepped net that is not ``ActorNet``: its carry is ONE array, a
    leaky integrator of the encoded observation."""

    action_dim: int
    hidden: int

    @nn.compact
    def __call__(self, obs, carry, reset):
        carry = jnp.where(reset[:, None] > 0, 0.0, carry)
        carry = 0.8 * carry + 0.2 * jnp.tanh(nn.Dense(self.hidden)(obs))
        return jnp.tanh(nn.Dense(self.action_dim)(carry)), carry

    def initial_carry(self, batch_size):
        return jnp.zeros((batch_size, self.hidden))


class LeakyCritic(nn.Module):
    hidden: int

    @nn.compact
    def __call__(self, obs, action, carry, reset):
        carry = jnp.where(reset[:, None] > 0, 0.0, carry)
        x = nn.Dense(self.hidden)(jnp.concatenate([obs, action], axis=-1))
        carry = 0.8 * carry + 0.2 * jnp.tanh(x)
        return jnp.squeeze(nn.Dense(1)(carry), axis=-1), carry

    def initial_carry(self, batch_size):
        return jnp.zeros((batch_size, self.hidden))


def test_a_stepped_core_defined_outside_the_package_needs_no_edit_to_the_learner():
    """What adding a stepped core costs: the nets.  The learner is given two
    modules it has never seen, whose carry is not ``(c, h)``, and burns in,
    updates and ranks fresh sequences with them."""
    agent = R2D2DPG(
        LeakyActor(action_dim=ACT, hidden=HID),
        LeakyCritic(hidden=HID),
        AgentConfig(burnin=3, unroll=3, n_step=2),
    )
    batch = make_batch(agent, key=5)
    h = jax.random.normal(jax.random.PRNGKey(11), (B, HID))
    batch = dataclasses.replace(
        batch, reset=batch.reset.at[2, 1].set(1.0),
        carries={"actor": h, "critic": -h},
    )
    state = desynced(
        agent.init(jax.random.PRNGKey(0), batch.obs[:, 0], batch.action[:, 0])
    )
    got = agent.seq.burn_in(state, batch)
    for g, w in zip(got, unfused_burn_in(agent, state, batch)):
        assert g.shape == (B, HID)
        np.testing.assert_allclose(g, w, rtol=1e-5, atol=1e-6)
    assert not np.allclose(got[0], h) and not np.allclose(got[0], got[1])
    assert np.all(np.asarray(got[0][2]) != 0)  # the row reset mid-prefix warmed again

    p_init = agent.initial_priority(state, batch)
    new_state, prios, metrics = jax.jit(agent.learner_step)(state, batch, jnp.ones(B))
    assert np.all(np.isfinite(prios)) and np.all(np.asarray(prios) > 0)
    np.testing.assert_allclose(p_init, prios, rtol=1e-4, atol=1e-5)
    assert all(np.isfinite(v) for v in jax.device_get(metrics).values())
    for before, after in ((state.actor_params, new_state.actor_params),
                          (state.critic_params, new_state.critic_params)):
        moved = jax.tree_util.tree_map(
            lambda a, b: float(jnp.abs(a - b).max()), before, after)
        assert all(m > 0 for m in jax.tree_util.tree_leaves(moved))


# ------------------------------------------------- the prefix / step seam
# ``models/sequence.py::Stepped`` runs what of a net's step does not depend on
# the carry once over [T, B] and scans the rest.  Every operation, against
# ``models.unroll`` of the net's own single step (``apply``), net by net.

T_SEAM, FRAME = 5, 36  # 36 x 36 is the least frame the three VALID convs take

SEAM_NETS = {
    "mlp_lstm_f32": dict(),
    "mlp_lstm_bf16": dict(dtype=jnp.bfloat16),
    "conv_lstm_f32": dict(pixels=True),
    "conv_lstm_bf16": dict(pixels=True, dtype=jnp.bfloat16),
    "mlp_dense_f32": dict(use_lstm=False),
    "mlp_dense_bf16": dict(use_lstm=False, dtype=jnp.bfloat16),
    "apply_alone": None,  # PR 29's Leaky nets: no ``encode`` to take out
}
SEAM_OPS = (
    "unroll_actor", "unroll_critic", "unroll_pi_q", "unroll_pi_q_eps",
    "unroll_pi_q_min", "unroll_online", "burn_in",
)


def seam_case(kind):
    """``(runner, state, batch)``: nets of ``kind`` under a ``Stepped``,
    desynced targets, stored carries that are not zero, one reset in
    mid-prefix and one in mid-window."""
    from r2d2dpg_tpu.models.sequence import Stepped

    kw = SEAM_NETS[kind]
    if kw is None:
        actor, critic = LeakyActor(action_dim=ACT, hidden=HID), LeakyCritic(hidden=HID)
    else:
        actor, critic = ActorNet(action_dim=ACT, hidden=HID, **kw), CriticNet(hidden=HID, **kw)
    agent = R2D2DPG(
        actor, critic,
        AgentConfig(burnin=3, unroll=T_SEAM - 1, n_step=1),
    )
    assert isinstance(agent.seq, Stepped)
    L = agent.config.seq_len
    ks = jax.random.split(jax.random.PRNGKey(7), 4)
    if kw and kw.get("pixels"):
        obs = jax.random.randint(ks[0], (B, L, FRAME, FRAME, 3), 0, 256).astype(jnp.uint8)
    else:
        obs = jax.random.normal(ks[0], (B, L, OBS))
    h = jax.random.normal(ks[1], (B, HID))
    carry = lambda net, x: jax.tree_util.tree_map(  # noqa: E731
        lambda z: x + z, net.initial_carry(B))
    batch = SequenceBatch(
        obs=obs,
        action=jax.random.uniform(ks[2], (B, L, ACT), minval=-1, maxval=1),
        reward=jnp.zeros((B, L)), discount=jnp.ones((B, L)),
        reset=jnp.zeros((B, L)).at[1, 1].set(1.0).at[2, 5].set(1.0),
        carries={"actor": carry(actor, h), "critic": carry(critic, -0.5 * h)},
    )
    state = desynced(agent.init(jax.random.PRNGKey(0), obs[:, 0], batch.action[:, 0]))
    return agent.seq, state, batch


def seam_run(op, seq, state, batch, hoisted):
    """``op`` over the batch's window through ``seq`` (``hoisted``) or through
    ``unroll`` of single steps; ``(outputs and carries, gradients)``."""
    cfg = seq.config
    tm = lambda x: jnp.swapaxes(x[:, cfg.burnin:], 0, 1)  # noqa: E731
    obs, act, reset = tm(batch.obs), tm(batch.action), tm(batch.reset)
    ca, cc = batch.carries["actor"], batch.carries["critic"]
    eps = 0.3 * jax.random.normal(jax.random.PRNGKey(5), act.shape)
    a_step = lambda p: lambda c, o, r: seq.actor.apply(p, o, c, r)  # noqa: E731
    q_step = lambda p: lambda c, o, a, r: seq.critic.apply(p, o, a, c, r)  # noqa: E731

    if op == "burn_in":
        if hoisted:
            return seq.burn_in(state, batch), ()
        return unfused_burn_in(seq, state, batch), ()
    if op == "unroll_actor":
        def f(pa):
            if hoisted:
                out = seq.unroll_actor(pa, ca, obs, reset)
            else:
                out = unroll(a_step(pa), ca, obs, reset)
            return (out[0] ** 2).sum(), out
        (_, out), grads = jax.value_and_grad(f, has_aux=True)(state.actor_params)
        return out, grads
    if op == "unroll_critic":
        def f(pc):
            if hoisted:
                out = seq.unroll_critic(pc, cc, obs, act, reset)
            else:
                out = unroll(q_step(pc), cc, obs, act, reset)
            return (out[0] ** 2).sum(), out
        (_, out), grads = jax.value_and_grad(f, has_aux=True)(state.critic_params)
        return out, grads

    if op == "unroll_online":
        def f(params):
            pa, pc = params
            if hoisted:
                out = seq.unroll_online(pa, pc, ca, cc, obs, act, reset)
            else:
                # Three scans: the critic on the replay's actions, the actor,
                # the frozen critic on the actor's actions.
                q, cc_q = unroll(q_step(pc), cc, obs, act, reset)
                a, ca_n = unroll(a_step(pa), ca, obs, reset)
                frozen = jax.lax.stop_gradient(pc)
                q_pi, cc_pi = unroll(q_step(frozen), cc, obs, a, reset)
                out = (q, q_pi, (cc_q, (ca_n, cc_pi)))
            return (out[0] ** 2).sum() - out[1].mean(), out
        (_, out), grads = jax.value_and_grad(f, has_aux=True)(
            (state.actor_params, state.critic_params))
        return out, grads

    e = eps if op == "unroll_pi_q_eps" else None
    q_min = op == "unroll_pi_q_min"
    pc0 = state.critic_params
    if q_min:  # two members: the online critic and one moved off it
        stack = lambda x, y: jax.tree_util.tree_map(  # noqa: E731
            lambda u, v: jnp.stack([u, v]), x, y)
        pc0 = stack(pc0, state.target_critic_params)
        cc = stack(cc, jax.tree_util.tree_map(lambda x: 0.5 * x, cc))

    def f(params):
        pa, pc = params
        if hoisted:
            out = seq.unroll_pi_q(pa, pc, ca, cc, obs, reset, eps_tm=e, q_min=q_min)
        else:
            # Two scans, net by net: the actor, then the critic on its actions.
            a, ca_n = unroll(a_step(pa), ca, obs, reset)
            if e is not None:
                a = jnp.clip(a + e, -1.0, 1.0)
            if q_min:
                q2, cc_n = jax.vmap(
                    lambda p, c: unroll(q_step(p), c, obs, a, reset))(pc, cc)
                q = q2.min(axis=0)
            else:
                q, cc_n = unroll(q_step(pc), cc, obs, a, reset)
            out = (a, q, (ca_n, cc_n))
        return -out[1].mean(), out
    (_, out), grads = jax.value_and_grad(f, has_aux=True)((state.actor_params, pc0))
    return out, grads


@pytest.mark.parametrize("kind", list(SEAM_NETS))
@pytest.mark.parametrize("op", SEAM_OPS)
def test_hoisted_pass_equals_the_scan_of_single_steps(op, kind):
    """Outputs, last carries and the gradients of a loss on the outputs (the
    critic's through ``unroll_critic``, the actor's through ``unroll_pi_q``,
    both through ``unroll_online``, whose critic on the policy's actions is
    frozen).
    The forward pass is the same products row by row, in either dtype.  The
    gradients sum them in another order: float32 rounding; under bfloat16 a
    weight's gradient is rounded once over T·B rows where the scan rounded it
    a step (the biases move most, by a few bfloat16 ulps of their largest)."""
    seq, state, batch = seam_case(kind)
    got, got_g = jax.jit(lambda s, b: seam_run(op, seq, s, b, True))(state, batch)
    want, want_g = jax.jit(lambda s, b: seam_run(op, seq, s, b, False))(state, batch)
    f32 = lambda t: jax.tree_util.tree_map(  # noqa: E731
        lambda x: np.asarray(x, np.float32), t)
    assert jax.tree_util.tree_structure(got) == jax.tree_util.tree_structure(want)
    assert jax.tree_util.tree_leaves(got) or (op, kind[:9]) == ("burn_in", "mlp_dense")
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6),
        f32(got), f32(want))
    tol = dict(rtol=2e-2, atol=2e-3) if "bf16" in kind else dict(rtol=1e-5, atol=1e-6)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(a, b, **tol), f32(got_g), f32(want_g))
    if op != "burn_in":
        assert any(np.abs(g).max() > 0 for g in jax.tree_util.tree_leaves(f32(got_g)))


# ------------------------------------------ the two online passes, one scan
# ``Stepped.unroll_online`` steps the critic's loss pass and the actor's in
# one scan under one gradient call; a runner that does not (``Whole``) keeps
# two passes under two calls.  The same update either way, net by net.


class TwoPasses(Stepped):
    """``Stepped`` with the online passes as two scans under two gradient
    calls, as a runner that does not step them together runs them."""

    joint_online = False


ONLINE_NETS = {
    "walker_like": dict(),
    "pixels": dict(pixels=True),
    "twin_critic": dict(twin_critic=True),
    "dense_core": dict(use_lstm=False),
    "apply_alone": None,  # the Leaky nets: ``apply`` is the whole step
}


def online_update(kind, runner):
    """One ``learner_step`` of nets of ``kind`` through ``runner``: stored
    carries that are not zero, a reset in the burn-in prefix and one in the
    training window, desynced targets, IS weights that are not ones."""
    kw = ONLINE_NETS[kind]
    cfg = AgentConfig(
        burnin=3, unroll=4, n_step=2, twin_critic=bool(kw and kw.get("twin_critic")))
    if kw is None:
        actor, critic = LeakyActor(action_dim=ACT, hidden=HID), LeakyCritic(hidden=HID)
    else:
        nets = {k: v for k, v in kw.items() if k != "twin_critic"}
        actor = ActorNet(action_dim=ACT, hidden=HID, **nets)
        critic = CriticNet(hidden=HID, **nets)
    agent = R2D2DPG(actor, critic, cfg)
    assert type(agent.seq) is Stepped
    agent.seq = runner(actor, critic, cfg)
    L = cfg.seq_len
    ks = jax.random.split(jax.random.PRNGKey(13), 5)
    if kw and kw.get("pixels"):
        obs = jax.random.randint(ks[0], (B, L, FRAME, FRAME, 3), 0, 256).astype(jnp.uint8)
    else:
        obs = jax.random.normal(ks[0], (B, L, OBS))
    h = 0.5 * jax.random.normal(ks[1], (B, HID))
    carry = lambda net, x: jax.tree_util.tree_map(  # noqa: E731
        lambda z: x + z, net.initial_carry(B))
    batch = SequenceBatch(
        obs=obs,
        action=jax.random.uniform(ks[2], (B, L, ACT), minval=-1, maxval=1),
        reward=jax.random.normal(ks[3], (B, L)),
        discount=jnp.full((B, L), 0.99),
        reset=jnp.zeros((B, L)).at[1, 1].set(1.0).at[2, cfg.burnin + 2].set(1.0),
        carries={"actor": carry(actor, h), "critic": carry(critic, -h)},
    )
    state = desynced(agent.init(jax.random.PRNGKey(0), obs[:, 0], batch.action[:, 0]))
    args = (state, batch, jnp.linspace(0.5, 1.0, B))
    scans = str(jax.make_jaxpr(agent.learner_step)(*args)).count("scan[")
    return jax.jit(agent.learner_step)(*args), scans


@pytest.mark.parametrize("kind", list(ONLINE_NETS))
def test_one_scan_of_both_online_passes_is_the_two_passes_update(kind):
    """Both losses, the td errors (their mean and the priorities made of
    them), the critic's and the actor's gradients (Adam's first moments: the
    first step's is a tenth of the clipped gradient), the new parameters and
    targets: the two-pass update's, to float32 rounding.  Where the core
    carries something, the joint form has one forward scan and one transposed
    scan fewer (a Dense core's scans carry nothing, and JAX's count of them is
    the same either way)."""
    (got, got_scans), (want, want_scans) = (
        online_update(kind, r) for r in (Stepped, TwoPasses))
    assert jax.tree_util.tree_structure(got) == jax.tree_util.tree_structure(want)
    for g, w in zip(jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(g, w, rtol=1e-5, atol=1e-6)
    new_state, prios, metrics = got
    for opt_state in (new_state.actor_opt_state, new_state.critic_opt_state):
        assert any(float(jnp.abs(m).max()) > 0
                   for m in jax.tree_util.tree_leaves(opt_state) if m.ndim)
    assert np.all(np.asarray(prios) > 0) and float(metrics["actor_loss"]) != 0
    assert got_scans == want_scans - (0 if kind == "dense_core" else 2)
