"""Ouro-2.6B's looped stack as the actor-critic's core, at ``ouro_tiny`` on the
CPU: the rolled, rematerialised core against a plain loop written here (the
ring, a prefix's memory, one loop step, the unrolled form, untied copies,
resets), and the learner over it through ``sequence_runner`` against the
benchmark's plain reference."""

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

from chipbench import compare, harness, reference_ouro_loop as ref_ouro  # noqa: E402
from r2d2dpg_tpu.configs import OURO_TINY  # noqa: E402
from r2d2dpg_tpu.models import ouro_loop, policy_step_fn  # noqa: E402
from r2d2dpg_tpu.models.sequence import Whole, sequence_runner  # noqa: E402
from r2d2dpg_tpu.obs.stages import PASSES, pass_of, scope_of, stage_of  # noqa: E402
from r2d2dpg_tpu.replay.arena import SequenceBatch  # noqa: E402
from r2d2dpg_tpu.utils.metrics import host_scalars  # noqa: E402
from r2d2dpg_tpu.utils.profiling import CORE_STAGES, LEARN_STAGES  # noqa: E402

with open(os.path.join(REPO, "tests", "chipbench", "data", "configs",
                       "ouro_tiny.json")) as f:
    CFG = json.load(f)
DRIVER = harness.load_module("drivers", "learn_ouro_loop")
TINY = OURO_TINY.ouro
B, L, BURNIN = 8, OURO_TINY.agent.seq_len, 2
LEAVES = ("norm1", "norm2", "norm3", "norm4", "wq", "wk", "wv", "wo",
          "w_gate", "w_up", "w_down")


# ------------------------------------------------ the plain loop, written here
def _rms(x, scale, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _rope(x, theta):
    """``x [B, T, heads, D]`` at positions 0 .. T - 1, half-split pairing."""
    T, D = x.shape[1], x.shape[-1]
    ang = jnp.arange(T)[:, None] * theta ** (-jnp.arange(0, D, 2) / D)[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    a, b = x[..., : D // 2], x[..., D // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def _sees(reset):
    """``[B, T, T]``: t sees s iff s <= t and no reset lies in (s, t]."""
    r = np.asarray(reset) > 0
    T = r.shape[1]
    out = np.zeros(r.shape + (T,), bool)
    for t in range(T):
        for s in range(t + 1):
            out[:, t, s] = ~r[:, s + 1: t + 1].any(axis=1)
    return jnp.asarray(out)


def _plain_block(p, x, sees, cfg, stop_before=0):
    """One block; the keys and values of the steps before ``stop_before``
    take no gradient."""
    Bn, T, _ = x.shape
    h = _rms(x, p["norm1"], cfg.rms_eps)
    heads = lambda y: y.reshape(Bn, T, cfg.heads, cfg.head_dim)  # noqa: E731
    q = _rope(heads(h @ p["wq"]), cfg.rope_theta)
    k, v = _rope(heads(h @ p["wk"]), cfg.rope_theta), heads(h @ p["wv"])
    stop = (jnp.arange(T) < stop_before)[None, :, None, None]
    k = jnp.where(stop, jax.lax.stop_gradient(k), k)
    v = jnp.where(stop, jax.lax.stop_gradient(v), v)
    s = jnp.einsum("bthd,bshd->bhts", q, k) / np.sqrt(cfg.head_dim)
    w = jax.nn.softmax(jnp.where(sees[:, None], s, -jnp.inf), axis=-1)
    a = jnp.einsum("bhts,bshd->bthd", w, v).reshape(Bn, T, -1) @ p["wo"]
    x = x + _rms(a, p["norm2"], cfg.rms_eps)
    h = _rms(x, p["norm3"], cfg.rms_eps)
    m = (jax.nn.silu(h @ p["w_gate"]) * (h @ p["w_up"])) @ p["w_down"]
    return x + _rms(m, p["norm4"], cfg.rms_eps)


def _plain_core(w, x, reset, cfg, untied=False, stop_before=0):
    """``h^(R)`` by Python loops.  ``w``: the core's leaves stacked ``[L,
    ...]``, or, ``untied``, ``[R, L, ...]``: a copy of its own a loop step."""
    sees = _sees(reset)
    for r in range(cfg.loop_steps):
        for i in range(cfg.layers):
            layer = {n: (w[n][r][i] if untied else w[n][i]) for n in LEAVES}
            x = _plain_block(layer, x, sees, cfg, stop_before)
        x = _rms(x, w["final_norm"][r] if untied else w["final_norm"], cfg.rms_eps)
    return x


# ------------------------------------------------------------------ fixtures
def _core_weights(cfg, key):
    core = ouro_loop.OuroLoopCore(cfg)
    x = jnp.zeros((1, 2, cfg.hidden))
    shapes = jax.eval_shape(
        lambda k: core.init(k, x, (), jnp.zeros((1, 2)), sequence=True), key)
    return core, DRIVER.sdar.make_weights(key, shapes)


def _inputs(cfg, T=L, key=11):
    x = jax.random.normal(jax.random.PRNGKey(key), (B, T, cfg.hidden))
    reset = jnp.zeros((B, T)).at[::2, 1].set(1.0).at[1::3, T - 2].set(1.0)
    return x, reset


def _seq(core, w, x, reset, memory=(), **kw):
    return core.apply(w, x, memory, reset, sequence=True, **kw)


def _close(a, b, rtol=2e-4):
    """Leaf by leaf, to float32 rounding of the leaf's largest entry."""
    for x, y in zip(jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b), strict=True):
        np.testing.assert_allclose(x, y, rtol=rtol, atol=2e-5 * max(float(np.abs(y).max()), 0.1))


# ------------------------------------------------------------- the core alone
def test_rolled_core_equals_the_plain_loop_and_reports_the_last_steps_change():
    core, w = _core_weights(TINY, jax.random.PRNGKey(1))
    x, reset = _inputs(TINY)
    y, aux = _seq(core, w, x, reset)
    _close(y, _plain_core(w["params"], x, reset, TINY))
    assert aux["k"].shape == aux["v"].shape == (
        B, TINY.loop_steps * TINY.layers, L, TINY.heads, TINY.head_dim)
    before = _plain_core(w["params"], x, reset,
                         dataclasses.replace(TINY, loop_steps=TINY.loop_steps - 1))
    want = jnp.linalg.norm(y - before) / jnp.linalg.norm(before)
    assert float(aux["last_step_rel_change"]) == pytest.approx(float(want), rel=1e-4)


def test_one_loop_step_is_the_plain_stack_and_its_final_norm():
    cfg = dataclasses.replace(TINY, loop_steps=1)
    core, w = _core_weights(cfg, jax.random.PRNGKey(2))
    x, reset = _inputs(cfg)
    sees, p = _sees(reset), w["params"]
    want = x
    for i in range(cfg.layers):
        want = _plain_block({n: p[n][i] for n in LEAVES}, want, sees, cfg)
    _close(_seq(core, w, x, reset)[0], _rms(want, p["final_norm"], cfg.rms_eps))


@pytest.mark.parametrize("form", ["unrolled_blocks", "plain_loop"])
def test_rolled_rematerialised_core_equals_the_unrolled_form_values_and_gradients(form):
    """The same blocks in Python loops without ``jax.checkpoint`` (the
    program's own ``block``), and the plain loop written above."""
    core, w = _core_weights(TINY, jax.random.PRNGKey(3))
    x, reset = _inputs(TINY)
    target = jax.random.normal(jax.random.PRNGKey(4), x.shape)

    def unrolled(p, x):
        _, mask = ouro_loop.sequence_mask(reset)
        pos = jnp.arange(x.shape[1])
        for _ in range(TINY.loop_steps):
            for i in range(TINY.layers):
                layer = {n: p[n][i] for n in LEAVES}
                x, _, _ = ouro_loop.block(TINY, layer, x, pos, mask, (), jnp.float32)
            x = ouro_loop.rms_norm(x, p["final_norm"], TINY.rms_eps)
        return x

    other = unrolled if form == "unrolled_blocks" else (
        lambda p, x: _plain_core(p, x, reset, TINY))
    rolled = lambda p, x: _seq(core, {"params": p}, x, reset)[0]  # noqa: E731
    loss = lambda f: lambda p, x: jnp.sum(f(p, x) * target)  # noqa: E731
    got = jax.jit(jax.value_and_grad(loss(rolled), argnums=(0, 1)))(w["params"], x)
    want = jax.value_and_grad(loss(other), argnums=(0, 1))(w["params"], x)
    _close(got, want)
    assert all(float(jnp.abs(g).max()) > 0 for g in jax.tree_util.tree_leaves(got[1]))


def test_shared_weights_gradient_is_the_sum_over_four_untied_copies():
    cfg = dataclasses.replace(TINY, loop_steps=4)
    core, w = _core_weights(cfg, jax.random.PRNGKey(5))
    x, reset = _inputs(cfg)
    target = jax.random.normal(jax.random.PRNGKey(6), x.shape)
    tied = jax.grad(lambda p: jnp.sum(_seq(core, {"params": p}, x, reset)[0] * target))(
        w["params"])
    copies = jax.tree_util.tree_map(lambda a: jnp.stack([a] * 4), w["params"])
    untied = jax.grad(lambda p: jnp.sum(
        _plain_core(p, x, reset, cfg, untied=True) * target))(copies)
    for n in LEAVES + ("final_norm",):
        assert untied[n].shape == (4,) + tied[n].shape
        # Every use gives its own, different part ...
        assert float(jnp.abs(untied[n][0] - untied[n][3]).max()) > 0
        # ... and the shared weight's gradient is their sum.
        _close(tied[n], untied[n].sum(axis=0))


def test_memory_of_a_prefix_then_the_window_equals_the_whole_call_with_the_prefix_stopped():
    """Values and gradients; resets inside the memory (step 1) and inside the
    window (step L - 2)."""
    core, w = _core_weights(TINY, jax.random.PRNGKey(7))
    x, reset = _inputs(TINY)
    assert reset[:, :BURNIN].sum() > 0 and reset[:, BURNIN:].sum() > 0
    target = jax.random.normal(jax.random.PRNGKey(8), x[:, BURNIN:].shape)

    def after_memory(p, x):
        _, mem = _seq(core, {"params": p}, x[:, :BURNIN], reset[:, :BURNIN], memory_only=True)
        mem = jax.lax.stop_gradient({k: mem[k] for k in ("k", "v", "seg")})
        tail, _ = _seq(core, {"params": p}, x[:, BURNIN:], reset[:, BURNIN:], mem)
        return tail

    def whole(p, x):  # the prefix's rows feed nothing but its keys and values
        return _plain_core(p, x, reset, TINY, stop_before=BURNIN)[:, BURNIN:]

    loss = lambda f: lambda p, x: jnp.sum(f(p, x) * target)  # noqa: E731
    got = jax.value_and_grad(loss(after_memory), argnums=(0, 1))(w["params"], x)
    want = jax.value_and_grad(loss(whole), argnums=(0, 1))(w["params"], x)
    _close(got, want)
    assert np.all(np.asarray(got[1][1])[:, :BURNIN] == 0)  # nothing flows into the prefix
    # The whole-sequence call (no stop) agrees in value too.
    _close(after_memory(w["params"], x), _seq(core, w, x, reset)[0][:, BURNIN:])


# --------------------------------------------------- the nets and the learner
@pytest.fixture(scope="module")
def agent():
    return OURO_TINY.build_agent(OURO_TINY.env_factory())


@pytest.fixture(scope="module")
def weights():
    return DRIVER.sdar.make_weights(jax.random.PRNGKey(3), ref_ouro.weight_shapes(CFG))


@pytest.fixture(scope="module")
def rows():
    spec = {"seq_len": L, "obs_shape": (3,), "action_dim": 1}
    params = {"reward_max": 1.0, "reset_prob": 0.15, "priority_scale": 0.1,
              "priority_log_sigma": 1.0}
    rows, _ = DRIVER.sdar.make_rows(jax.random.PRNGKey(5), jnp.arange(B), spec, params)
    assert float(rows["reset"][:, 1:].sum()) > 0  # boundaries inside sequences
    return rows


def test_ring_step_equals_the_whole_sequence_call_with_a_reset_inside(agent, weights):
    actor_params = weights[0]
    obs = jax.random.normal(jax.random.PRNGKey(7), (B, L, 3))
    reset = jnp.zeros((B, L)).at[:, 0].set(1.0).at[::2, 3].set(1.0).at[1, 5].set(1.0)
    whole, _ = agent.actor.apply(actor_params, obs, reset, method="sequence")
    step = jax.jit(policy_step_fn(agent.actor))
    carry = agent.actor.initial_carry(B)
    assert carry["k"].shape == (B, TINY.loop_steps * TINY.layers, L - 1, 4, 16)
    steps = []
    for t in range(L):
        a, carry = step(actor_params, obs[:, t], carry, reset[:, t])
        steps.append(a)
    np.testing.assert_allclose(jnp.stack(steps, 1), whole, rtol=1e-4, atol=1e-6)
    np.testing.assert_array_equal(carry["count"], L - np.array([3, 5, 3, 0, 3, 0, 3, 0]))
    # The replay stores none of it; the critic acts with no carry at all.
    assert agent.actor.stored_carry(carry) == () and agent.critic.initial_carry(B) == ()


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
    hp = {k: getattr(OURO_TINY.agent, k) for k in (
        "burnin", "unroll", "n_step", "gamma", "tau", "eta", "actor_lr",
        "critic_lr", "grad_clip")}
    plain = {k: v for k, v in rows.items() if k != "carries"}
    # Copies throughout: the reference's update donates its state.
    ref_state = dict(DRIVER.reference.init_state(copy(actor), copy(critic)),
                     target_actor=copy(actor), target_critic=copy(critic))
    want = ref_ouro.learner_update(ref_state, plain, w, hp, CFG)
    return got, want


def test_learner_runs_the_looped_core_through_the_whole_sequence_runner(agent):
    assert isinstance(agent.seq, Whole)
    assert isinstance(sequence_runner(agent.actor, agent.critic, agent.config), Whole)
    with open(os.path.join(REPO, "r2d2dpg_tpu", "agents", "ddpg.py")) as f:
        text = f.read().lower()
    assert "ouro" not in text and "sdar" not in text  # the learner names no core


def test_update_matches_the_reference_losses_priorities_counter_and_gradients(one_update, weights):
    (state, prios, metrics), (ref_state, ref_prios, losses) = one_update
    for name in ("critic_loss", "actor_loss"):
        assert float(metrics[name]) == pytest.approx(float(losses[name]), rel=1e-4, abs=1e-6)
    np.testing.assert_allclose(prios, ref_prios, rtol=1e-4, atol=1e-6)
    assert float(metrics["loop/last_step_rel_change"]) == pytest.approx(
        float(losses["last_step_rel_change"]), rel=1e-4)
    mu = compare.leaf_norms(jax.device_get({
        "actor": DRIVER.follow.adam_mu(state.actor_opt_state),
        "critic": DRIVER.follow.adam_mu(state.critic_opt_state)}))
    ref_mu = compare.leaf_norms(jax.device_get({
        "actor": ref_state["actor_opt"]["mu"], "critic": ref_state["critic_opt"]["mu"]}))
    gap, leaf = compare.worst_leaf_gap(mu, ref_mu)
    assert gap < 1e-4, leaf
    actor, critic = weights
    p0 = jax.device_get({"actor": actor, "critic": critic,
                         "target_actor": actor, "target_critic": critic})
    gaps = DRIVER.follow.change_gaps(
        DRIVER.follow.train_params(state), ref_state, p0, losses["grads"])
    assert gaps["update_gap"] < 1e-3 and gaps["target_gap"] < 1e-3, gaps
    assert int(state.step) == int(ref_state["step"]) == 1


def test_counters_are_floats_and_the_memory_is_what_four_burn_in_passes_leave(one_update):
    (_, _, metrics), _ = one_update
    rl = TINY.loop_steps * TINY.layers
    one = B * rl * BURNIN * TINY.heads * TINY.head_dim * 4  # k, or v, of one net
    assert float(metrics["loop/memory_bytes"]) == 4 * 2 * one
    assert 0.0 < float(metrics["loop/last_step_rel_change"]) < 2.0
    scalars = host_scalars(jax.device_get(metrics))
    assert {"loop/memory_bytes", "loop/last_step_rel_change"} <= set(scalars)


def test_core_scopes_reach_the_learner_calls_hlo_inside_its_loops():
    t = OURO_TINY.build()
    s = t.init()
    text = jax.jit(t._learn_many).lower(s.train, s.arena, jax.random.PRNGKey(0)
                                        ).compile().as_text()
    paths = set(re.findall(r'op_name="([^"]*)"', text))
    both = LEARN_STAGES + CORE_STAGES
    for name in ("core_attention", "core_mlp"):
        mine = [p for p in paths if stage_of(p, both) == name]
        assert any("transpose(" in p for p in mine) and any("transpose(" not in p for p in mine)
        assert any("/burn_in/" in p for p in mine)
        # Inside the scan over the layers inside the scan over the loop steps
        # inside the call's own loop over its updates, named all the same.
        assert any(p.count("while/body") >= 3 for p in mine)
        # All three passes are on its paths: the forward pass, the one
        # recomputed under ``jax.checkpoint``, the backward pass.
        assert {pass_of(p) for p in mine} == set(PASSES)
        assert {scope_of(p) for p in mine} == {name}
        # Read with the learner's stages alone, the products stay in the five
        # (what does not depend on a scan's carry, RoPE's table, is hoisted
        # and loses the stage: nothing in time).
        products = [p for p in mine if p.endswith("dot_general")]
        assert {stage_of(p) for p in products} == {"burn_in", "forward", "backward"}
    assert not [p for p in paths if stage_of(p, both) in ("moe_route", "moe_experts")]


def test_learner_call_averages_the_loop_counters_over_its_updates():
    t = OURO_TINY.build()
    s = t.init()
    for _ in range(t.window_fill_phases + t.replay_fill_phases):
        s = t.fill_phase(s)
    _, _, metrics = jax.jit(t._learn_many)(s.train, s.arena, jax.random.PRNGKey(0))
    for name in ("loop/last_step_rel_change", "loop/memory_bytes", "critic_loss"):
        assert np.ndim(metrics[name]) == 0 and np.isfinite(metrics[name])


def test_train_cli_runs_ouro_tiny(tmp_path):
    from r2d2dpg_tpu.train import parse_args, run

    final = run(parse_args(["--config", "ouro_tiny", "--phases", "5", "--log-every", "1",
                            "--logdir", str(tmp_path)]))
    assert final["env_steps"] > 0
    for key in ("critic_loss", "actor_loss", "loop/last_step_rel_change", "loop/memory_bytes"):
        assert np.isfinite(final[key]), (key, final)


def test_an_experiment_has_one_whole_sequence_core():
    from r2d2dpg_tpu.configs import SDAR_TINY

    both = dataclasses.replace(OURO_TINY, sdar=SDAR_TINY.sdar)
    with pytest.raises(ValueError, match="one core"):
        both.build_agent(OURO_TINY.env_factory())
