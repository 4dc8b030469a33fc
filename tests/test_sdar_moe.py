"""SDAR-30B-A3B-Chat's block as the actor-critic's core, at ``sdar_tiny`` on
the CPU: the program against the benchmark's plain reference, the expert
share against the uncut layer, the acting ring against the whole-sequence
call, burn-in under ``stop_gradient``, scopes and counters."""

import dataclasses
import json
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from chipbench import compare, harness, reference_sdar_moe as ref_moe  # noqa: E402
from r2d2dpg_tpu.configs import SDAR_TINY  # noqa: E402
from r2d2dpg_tpu.models import policy_step_fn, sdar_moe  # noqa: E402
from r2d2dpg_tpu.models.sequence import PASSES, Stepped, Whole  # noqa: E402
from r2d2dpg_tpu.obs import stages  # noqa: E402
from r2d2dpg_tpu.obs.stages import pass_of, scope_of, stage_of, table_keys  # noqa: E402
from r2d2dpg_tpu.replay.arena import SequenceBatch  # noqa: E402
from r2d2dpg_tpu.utils.metrics import host_scalars  # noqa: E402
from r2d2dpg_tpu.utils.profiling import CORE_STAGES, LEARN_STAGES  # noqa: E402

with open(os.path.join(REPO, "tests", "chipbench", "data", "configs",
                       "sdar_tiny.json")) as f:
    CFG = json.load(f)
DRIVER = harness.load_module("drivers", "learn_sdar_moe")
B, L, BURNIN, UNROLL = 8, SDAR_TINY.agent.seq_len, 2, 4


@pytest.fixture(scope="module")
def agent():
    env = SDAR_TINY.env_factory()
    return SDAR_TINY.build_agent(env)


@pytest.fixture(scope="module")
def weights():
    return DRIVER.make_weights(jax.random.PRNGKey(3), ref_moe.weight_shapes(CFG))


@pytest.fixture(scope="module")
def rows():
    spec = {"seq_len": L, "obs_shape": (3,), "action_dim": 1}
    params = {"reward_max": 1.0, "reset_prob": 0.15, "priority_scale": 0.1,
              "priority_log_sigma": 1.0}
    rows, _ = DRIVER.make_rows(jax.random.PRNGKey(5), jnp.arange(B), spec, params)
    assert float(rows["reset"][:, 1:].sum()) > 0  # boundaries inside sequences
    return rows


@pytest.fixture(scope="module")
def one_update(agent, weights, rows):
    """One learner update of the program and of the reference from the same
    weights, rows and IS weights."""
    from r2d2dpg_tpu.agents.ddpg import TrainState

    actor, critic = weights
    copy = lambda t: jax.tree_util.tree_map(jnp.copy, t)  # noqa: E731
    state = TrainState(
        actor_params=actor, critic_params=critic,
        target_actor_params=copy(actor), target_critic_params=copy(critic),
        actor_opt_state=agent.actor_tx.init(actor),
        critic_opt_state=agent.critic_tx.init(critic),
        step=jnp.zeros((), jnp.int32))
    w = jnp.linspace(0.5, 1.0, B)
    got = jax.jit(agent.learner_step)(state, SequenceBatch(**rows), w)
    hp = {k: getattr(SDAR_TINY.agent, k) for k in (
        "burnin", "unroll", "n_step", "gamma", "tau", "eta", "actor_lr",
        "critic_lr", "grad_clip")}
    plain = {k: v for k, v in rows.items() if k != "carries"}
    want = ref_moe.learner_update(
        DRIVER.reference.init_state(actor, critic), plain, w, hp, CFG)
    return got, want


def test_update_losses_and_priorities_match_the_reference(one_update):
    (_, prios, metrics), (_, ref_prios, losses) = one_update
    for name in ("critic_loss", "actor_loss"):
        assert float(metrics[name]) == pytest.approx(float(losses[name]), rel=1e-4, abs=1e-6)
    np.testing.assert_allclose(prios, ref_prios, rtol=1e-4, atol=1e-6)


def test_update_gradients_match_the_reference_leaf_by_leaf(one_update):
    (state, _, _), (ref_state, _, _) = one_update
    mu = compare.leaf_norms(jax.device_get({
        "actor": DRIVER.follow.adam_mu(state.actor_opt_state),
        "critic": DRIVER.follow.adam_mu(state.critic_opt_state)}))
    ref_mu = compare.leaf_norms(jax.device_get({
        "actor": ref_state["actor_opt"]["mu"], "critic": ref_state["critic_opt"]["mu"]}))
    gap, leaf = compare.worst_leaf_gap(mu, ref_mu)
    assert gap < 1e-4, leaf


def test_update_weights_and_targets_match_the_reference(one_update, weights):
    """By the norm of each leaf's change, as the benchmark's ``update_gap`` and
    ``target_gap``: under Adam an element whose gradient is round-off moves
    by a whole step either way."""
    (state, _, _), (ref_state, _, losses) = one_update
    actor, critic = weights
    p0 = jax.device_get({"actor": actor, "critic": critic,
                         "target_actor": actor, "target_critic": critic})
    gaps = DRIVER.follow.change_gaps(
        DRIVER.follow.train_params(state), ref_state, p0, losses["grads"])
    assert gaps["update_gap"] < 1e-3 and gaps["target_gap"] < 1e-3, gaps
    assert int(state.step) == int(ref_state["step"]) == 1


def test_routing_counters_are_the_references_own_routing(one_update):
    (_, _, metrics), (_, _, losses) = one_update
    assert PASSES == ref_moe.PASSES
    table = np.asarray(metrics["moe/tokens_per_expert"])
    np.testing.assert_array_equal(table, np.asarray(losses["loads"]))
    assert float(metrics["moe/pairs_here"]) == table.sum()
    # The burn-in passes stop before their last layer's experts.
    assert table[:4, -1].sum() == 0 and table[:4, 0].sum() > 0
    assert float(metrics["moe/load_max_over_mean"]) >= 1.0
    scalars = host_scalars(jax.device_get(metrics))
    assert "moe/pairs_here" in scalars and "moe/tokens_per_expert" not in scalars


def _layer(key, cfg):
    """One layer's expert weights for ALL the router's experts, and tokens."""
    ks = jax.random.split(key, 5)
    H, W, R = cfg.hidden, cfg.expert_width, cfg.router_experts
    u = lambda k, *s: jax.random.uniform(k, s, jnp.float32, -1, 1) * s[-2] ** -0.5  # noqa: E731
    p = {"router": u(ks[0], H, R), "w_gate": u(ks[1], R, H, W),
         "w_up": u(ks[2], R, H, W), "w_down": u(ks[3], R, W, H)}
    return p, jax.random.normal(ks[4], (40, H))


def _dense_experts(p, h2, gates):
    out = jnp.zeros_like(h2)
    for e in range(p["w_gate"].shape[0]):
        y = (jax.nn.silu(h2 @ p["w_gate"][e]) * (h2 @ p["w_up"][e])) @ p["w_down"][e]
        out = out + gates[:, e:e + 1] * y
    return out


def test_the_parts_all_shards_give_add_up_to_the_uncut_layer():
    cfg = SDAR_TINY.sdar
    p, h2 = _layer(jax.random.PRNGKey(0), cfg)
    z = dict(ref_moe.sizes(CFG), E=cfg.router_experts, first=0)
    gates, _ = ref_moe.router(h2, p["router"], z)
    whole = _dense_experts(p, h2, gates)  # every expert, the reference's routing
    E = cfg.experts_held
    parts, loads = [], []
    for shard in range(cfg.expert_shards):
        held = slice(shard * E, (shard + 1) * E)
        mine = {"router": p["router"], **{k: p[k][held] for k in ("w_gate", "w_up", "w_down")}}
        part, load = sdar_moe.moe(dataclasses.replace(cfg, expert_shard=shard), mine, h2)
        parts.append(part)
        loads.append(load)
    np.testing.assert_allclose(sum(parts), whole, rtol=1e-5, atol=1e-6)
    # Every token's k pairs are computed exactly once across the shards.
    assert int(sum(x.sum() for x in loads)) == h2.shape[0] * cfg.experts_per_token


@pytest.mark.parametrize("winners, loads", [
    ([0, 4, 5, 6], [40, 0, 0, 0]),  # one held expert takes every token
    ([0, 1, 2, 7], [40, 40, 40, 0]),  # three do
    ([0, 1, 2, 3], [40, 40, 40, 40]),  # every pair of every token is held here
])
def test_every_token_routed_to_the_same_held_experts_loses_none(winners, loads):
    cfg = SDAR_TINY.sdar
    p, h2 = _layer(jax.random.PRNGKey(1), cfg)
    # The winners take every token, whatever else it holds.
    bias = jnp.zeros((cfg.router_experts,)).at[jnp.array(winners)].set(50.0)
    h2 = h2.at[:, 0].set(1.0)
    p["router"] = p["router"].at[0].set(bias)
    E = cfg.experts_held
    mine = {"router": p["router"], **{k: p[k][:E] for k in ("w_gate", "w_up", "w_down")}}
    part, load = sdar_moe.moe(cfg, mine, h2)
    np.testing.assert_array_equal(load, loads)
    z = dict(ref_moe.sizes(CFG), E=cfg.router_experts, first=0)
    gates, _ = ref_moe.router(h2, p["router"], z)
    held = gates.at[:, E:].set(0.0)  # the held winners' parts, for all 40 tokens
    np.testing.assert_allclose(part, _dense_experts(p, h2, held), rtol=1e-5, atol=1e-6)
    assert float(jnp.abs(part).min(axis=1).max()) > 0  # no token's row is empty
    # ... and none of its gradient.
    f = lambda h: (sdar_moe.moe(cfg, mine, h)[0] ** 2).sum()  # noqa: E731
    g = lambda h: (_dense_experts(p, h, ref_moe.router(h, p["router"], z)[0].at[:, E:].set(0.0)) ** 2).sum()  # noqa: E731
    np.testing.assert_allclose(jax.grad(f)(h2), jax.grad(g)(h2), rtol=1e-4, atol=1e-5)


# ------------------------------------ what the held experts' backward keeps
def _held_case(monkeypatch, held_ffn):
    """One layer's share of the experts over 40 tokens, and the function of
    ``h2`` and the three expert kernels whose gradient is taken, with
    ``held_ffn`` the module's own or the ``expert_unapplied`` fault's wrapper
    around it (a seam looked up when ``moe`` is traced)."""
    monkeypatch.setattr(sdar_moe, "held_ffn", sdar_moe.held_ffn)  # restored after
    if held_ffn == "expert_unapplied":
        DRIVER._plant("expert_unapplied")
    cfg = SDAR_TINY.sdar
    p, h2 = _layer(jax.random.PRNGKey(2), cfg)
    E = cfg.experts_held
    p = {"router": p["router"], **{k: p[k][:E] for k in ("w_gate", "w_up", "w_down")}}

    def f(h2, w_gate, w_up, w_down):
        out, _ = sdar_moe.moe(cfg, dict(p, w_gate=w_gate, w_up=w_up, w_down=w_down), h2)
        return out

    return cfg, f, (h2, p["w_gate"], p["w_up"], p["w_down"])


def _dot_generals(jaxpr) -> int:
    """The ``dot_general`` equations of a jaxpr and of every jaxpr inside it."""
    from jax.extend.core import ClosedJaxpr, Jaxpr

    n = 0
    for eqn in jaxpr.eqns:
        n += eqn.primitive.name == "dot_general"
        for v in eqn.params.values():
            for sub in v if isinstance(v, (tuple, list)) else (v,):
                if isinstance(sub, ClosedJaxpr):
                    n += _dot_generals(sub.jaxpr)
                elif isinstance(sub, Jaxpr):
                    n += _dot_generals(sub)
    return n


def _held_grads_and_counts(f, args, cfg):
    """Gradients of ``sum(sin(f))`` by all four arguments; the ``[E, N, W]``
    residuals the backward pass keeps; the gradient's ``dot_general``s."""
    loss = lambda *a: jnp.sum(jnp.sin(f(*a)))  # noqa: E731
    grads = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3)))(*args)
    _, backward = jax.vjp(f, *args)
    enw = (cfg.experts_held, args[0].shape[0], cfg.expert_width)
    kept = [r.shape for r in jax.tree_util.tree_leaves(backward) if r.shape == enw]
    dots = _dot_generals(jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2, 3)))(*args).jaxpr)
    return grads, kept, dots


@pytest.mark.parametrize("held_ffn", ["own", "expert_unapplied"])
def test_keeping_the_up_product_changes_no_gradient(monkeypatch, held_ffn):
    """By ``h2`` and the three expert kernels, under the policy ``moe``
    applies and under a plain ``jax.checkpoint`` (no policy): the same
    products of the same operands, so equal to the last bit on the CPU."""
    cfg, f, args = _held_case(monkeypatch, held_ffn)
    kept, _, _ = _held_grads_and_counts(f, args, cfg)
    monkeypatch.setattr(sdar_moe, "HELD_KEPT", None)
    plain, _, _ = _held_grads_and_counts(f, args, cfg)
    for k, p in zip(kept, plain):
        assert float(jnp.abs(p).max()) > 0
        np.testing.assert_array_equal(k, p)


@pytest.mark.parametrize("held_ffn", ["own", "expert_unapplied"])
def test_the_backward_pass_keeps_the_up_product_and_runs_one_product_fewer(
    monkeypatch, held_ffn
):
    """The differentiated layer keeps exactly one ``[E, N, W]`` value, the up
    product, where a plain ``jax.checkpoint`` keeps none, and its gradient
    holds one ``dot_general`` fewer: the backward pass does not run that
    product again.  With ``held_ffn`` a wrapper that calls the module's own
    (as the ``expert_unapplied`` fault is), the name still reaches the
    policy."""
    cfg, f, args = _held_case(monkeypatch, held_ffn)
    _, kept, dots = _held_grads_and_counts(f, args, cfg)
    monkeypatch.setattr(sdar_moe, "HELD_KEPT", None)
    _, plain_kept, plain_dots = _held_grads_and_counts(f, args, cfg)
    enw = (cfg.experts_held, args[0].shape[0], cfg.expert_width)
    assert kept == [enw] and plain_kept == []
    assert dots == plain_dots - 1


def test_the_smoke_reports_the_residual_a_layer_keeps_at_the_learners_window():
    """``chip_smoke.py``'s report of what the held experts keep, from shapes:
    the up product over batch x unroll tokens, float32."""
    import chip_smoke

    c, exp = SDAR_TINY.sdar, SDAR_TINY
    shape = [c.experts_held, exp.trainer.batch_size * exp.agent.unroll, c.expert_width]
    assert chip_smoke._held_expert_residuals("sdar_tiny") == {
        "held_residuals": [f"float32{shape}"],
        "held_residual_bytes": 4 * int(np.prod(shape)),
    }


def test_ring_step_equals_the_whole_sequence_call_with_a_reset_inside(agent, weights):
    actor_params = weights[0]
    T = L  # from a cleared ring, one stored sequence's worth of steps
    obs = jax.random.normal(jax.random.PRNGKey(7), (B, T, 3))
    reset = jnp.zeros((B, T)).at[:, 0].set(1.0).at[::2, 3].set(1.0).at[1, 5].set(1.0)
    whole, _ = agent.actor.apply(actor_params, obs, reset, method="sequence")
    step = jax.jit(policy_step_fn(agent.actor))
    carry = agent.actor.initial_carry(B)
    assert carry["k"].shape == (B, 2, L - 1, 2, 16)
    steps = []
    for t in range(T):
        a, carry = step(actor_params, obs[:, t], carry, reset[:, t])
        steps.append(a)
    np.testing.assert_allclose(jnp.stack(steps, 1), whole, rtol=1e-4, atol=1e-6)
    # The counter is the step's index in its episode: it restarts at a reset.
    np.testing.assert_array_equal(
        carry["count"], T - np.array([3, 5, 3, 0, 3, 0, 3, 0]))
    # The replay stores none of it; the critic acts with no carry at all.
    assert agent.actor.stored_carry(carry) == () and agent.critic.initial_carry(B) == ()


def test_window_after_a_prefix_memory_equals_the_whole_sequence_call(agent, weights):
    _, critic_params = weights
    key = jax.random.PRNGKey(9)
    obs = jax.random.normal(key, (B, L, 3))
    act = jax.random.uniform(key, (B, L, 1), minval=-1, maxval=1)
    reset = jnp.zeros((B, L)).at[::3, 1].set(1.0).at[1::3, 4].set(1.0)
    whole, aux = agent.critic.apply(critic_params, obs, act, reset, method="sequence")
    _, mem = agent.critic.apply(critic_params, obs[:, :BURNIN], act[:, :BURNIN],
                                reset[:, :BURNIN], memory_only=True, method="sequence")
    tail, aux_w = agent.critic.apply(critic_params, obs[:, BURNIN:], act[:, BURNIN:],
                                     reset[:, BURNIN:], mem, method="sequence")
    np.testing.assert_allclose(tail, whole[:, BURNIN:], rtol=1e-4, atol=1e-6)
    np.testing.assert_array_equal(mem["load"][:-1] + aux_w["load"][:-1], aux["load"][:-1])


def test_gradients_vanish_on_burn_in_positions(agent, weights, rows):
    """The burn-in prefix reaches the window only through the memory, which
    is under ``stop_gradient``: no gradient flows to anything at a position
    before ``burnin``, and some does to every position of the unroll."""
    from r2d2dpg_tpu.agents.ddpg import TrainState

    actor, critic = weights
    state = TrainState(actor, critic, actor, critic, None, None, jnp.zeros((), jnp.int32))

    def q_sum(obs):
        batch = SequenceBatch(**dict(rows, obs=obs))
        _, _, cc_on, _ = agent.seq.burn_in(state, batch)
        tm = lambda x: jnp.swapaxes(x[:, BURNIN:BURNIN + UNROLL], 0, 1)  # noqa: E731
        q, _ = agent.seq.unroll_critic(critic, cc_on, tm(obs), tm(batch.action), tm(batch.reset))
        return q.sum()

    g = np.asarray(jax.grad(q_sum)(rows["obs"]))
    assert np.all(g[:, :BURNIN] == 0)
    assert np.all(np.abs(g[:, BURNIN:BURNIN + UNROLL]).sum(axis=-1) > 0)
    assert np.all(g[:, BURNIN + UNROLL:] == 0)  # the n-step tail is the targets' alone


def test_core_scopes_reach_the_learner_calls_hlo_and_fold_both_passes():
    t = SDAR_TINY.build()
    s = t.init()
    text = jax.jit(t._learn_many).lower(s.train, s.arena, jax.random.PRNGKey(0)
                                        ).compile().as_text()
    paths = set(re.findall(r'op_name="([^"]*)"', text))
    both = LEARN_STAGES + CORE_STAGES
    own = set(CORE_STAGES) - {"core_mlp"}  # the looped stack's dense MLP: not this core's
    assert own <= {stage_of(p, both) for p in paths}
    experts = [p for p in paths if stage_of(p, both) == "moe_experts"]
    assert any("transpose(" in p for p in experts) and any("transpose(" not in p for p in experts)
    assert any("/burn_in/" in p for p in experts)
    # All three passes are on its paths: the forward pass, the one
    # recomputed under ``jax.checkpoint``, the backward pass; and the routing
    # counters of an update are ``diagnostics``, not the core's.
    assert {pass_of(p) for p in experts} == set(stages.PASSES)
    assert {scope_of(p) for p in experts} == {"moe_experts"}
    assert any(scope_of(p) == "diagnostics" for p in paths)
    # Read with the learner's stages alone, the core's time stays in the five.
    assert {stage_of(p) for p in experts} <= {"burn_in", "forward", "backward"}
    assert set(CORE_STAGES) <= set(table_keys(both)) and not set(CORE_STAGES) & set(table_keys())


def test_learner_call_keeps_one_routing_table_for_each_update():
    t = SDAR_TINY.build()
    s = t.init()
    for _ in range(t.window_fill_phases + t.replay_fill_phases):
        s = t.fill_phase(s)
    _, _, metrics = jax.jit(t._learn_many)(s.train, s.arena, jax.random.PRNGKey(0))
    table = np.asarray(metrics["moe/tokens_per_expert"])
    c = SDAR_TINY.sdar
    assert table.shape == (t.config.learner_steps, len(PASSES),
                           c.layers, c.experts_held)
    assert np.issubdtype(table.dtype, np.integer)  # counts: never averaged
    # The floats are means over the call's updates, the counts behind them not.
    per_update = table.reshape(table.shape[0], -1).sum(axis=1)
    assert float(metrics["moe/pairs_here"]) == pytest.approx(per_update.mean())
    assert np.ndim(metrics["critic_loss"]) == 0
    assert "moe/tokens_per_expert" not in host_scalars(jax.device_get(metrics))


def test_train_cli_runs_sdar_tiny(tmp_path):
    from r2d2dpg_tpu.train import parse_args, run

    final = run(parse_args(["--config", "sdar_tiny", "--phases", "5", "--log-every", "1",
                            "--logdir", str(tmp_path)]))
    assert final["env_steps"] > 0
    for key in ("critic_loss", "actor_loss", "moe/pairs_here", "moe/load_max_over_mean"):
        assert np.isfinite(final[key]), (key, final)


def test_lstm_and_dense_cores_refuse_sequence_arguments_and_keep_their_trees():
    from r2d2dpg_tpu.configs import PENDULUM_DDPG, PENDULUM_TINY

    for exp, core in ((PENDULUM_TINY, {"OptimizedLSTMCell_0"}), (PENDULUM_DDPG, {"Dense_0"})):
        agent = exp.build_agent(exp.env_factory())
        st = jax.eval_shape(lambda k: agent.init(k, jnp.zeros((1, 3)), jnp.zeros((1, 1))),
                            jax.random.PRNGKey(0))
        assert set(st.actor_params["params"]["core"]) == core
        assert isinstance(agent.seq, Stepped) and agent.actor.stored_carry("c") == "c"


@pytest.mark.parametrize("knob", [{"twin_critic": True}, {"target_policy_sigma": 0.2}],
                         ids=["twin_critic", "target_policy_sigma"])
def test_the_whole_sequence_kind_refuses_the_td3_knobs_at_construction(agent, knob):
    """The ensemble min and the smoothing noise are steps of the stepped
    kind's scan; the whole-sequence kind has neither, and says so before
    anything is traced."""
    assert isinstance(agent.seq, Whole)
    exp = dataclasses.replace(
        SDAR_TINY, agent=dataclasses.replace(SDAR_TINY.agent, **knob))
    with pytest.raises(ValueError, match="not wired for a whole-sequence core"):
        exp.build_agent(SDAR_TINY.env_factory())
