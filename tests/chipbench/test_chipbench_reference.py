"""The plain reference against the program at a small size on the CPU in
float32: the learner step (loss, gradients as Adam gets them, new weights,
targets, priorities), the n-step targets, the replay operations; and that
the same step computed in bfloat16 does not pass."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import compare, follow, harness, program, reference, traffic
from chipbench.drivers import learn

from chipbench_fixtures import DATA


def _setup(pixels=False, compute_dtype="float32", seed=11):
    cfg = harness.load_json("configs", "pendulum_tiny", DATA)
    if pixels:
        cfg = dict(cfg, pixels=True, obs_shape=[64, 64, 3], obs_dtype="uint8",
                   hidden=16, batch_size=2, burnin=1, unroll=2, n_step=1)
    cfg = dict(cfg, compute_dtype=compute_dtype)
    cell = harness.load_json("workloads", "pendulum_tiny.learn", DATA)
    ctx = harness.Context(cell_name="t", cell=cell, config=cfg, seed=seed,
                          seconds=0.0, trace=False)
    exp = program.build_experiment(ctx)
    trainer = program.build_trainer(ctx, exp)
    spec = traffic.row_spec(cfg, exp.agent.seq_len, exp.hidden)
    rows, _ = traffic.make_rows(traffic.seed_key(seed, 2),
                                jnp.arange(exp.trainer.batch_size), spec, cell["traffic"])
    train = program.make_train_state(trainer, spec, cfg, seed)
    w = jnp.linspace(0.3, 1.0, exp.trainer.batch_size)
    return trainer, exp, cfg, rows, train, w


def _both(pixels=False, compute_dtype="float32"):
    trainer, exp, cfg, rows, train, w = _setup(pixels, compute_dtype)
    new, prios, metrics = jax.jit(trainer.agent.learner_step)(train, program.to_batch(rows), w)
    # The reference starts from the same seed's weights, made again.
    actor, critic = traffic.make_weights(traffic.seed_key(11, traffic.STREAM_WEIGHTS),
                                         reference.weight_shapes(cfg))
    ref0 = reference.init_state(actor, critic)
    ref, ref_prios, losses = reference.learner_update(
        ref0, rows, w, program.hyperparameters(exp))
    return new, prios, metrics, ref, ref_prios, losses


def _gaps(new, prios, metrics, ref, ref_prios, losses):
    mu = compare.leaf_norms({"actor": follow.adam_mu(new.actor_opt_state),
                             "critic": follow.adam_mu(new.critic_opt_state)})
    mu_ref = compare.leaf_norms({"actor": ref["actor_opt"]["mu"],
                                 "critic": ref["critic_opt"]["mu"]})
    return {
        "loss": max(compare.rel_gap(metrics["critic_loss"], losses["critic_loss"]),
                    compare.rel_gap(metrics["actor_loss"], losses["actor_loss"],
                                    float(losses["q_abs_mean"]))),
        "grad": compare.worst_leaf_gap(mu, mu_ref)[0],
        "priority": float(np.max(np.abs(np.asarray(prios) - np.asarray(ref_prios))
                                 / np.asarray(ref_prios))),
    }


@pytest.mark.parametrize("pixels", [False, True])
def test_learner_step_agrees_with_the_reference_in_float32(pixels):
    new, prios, metrics, ref, ref_prios, losses = _both(pixels)
    g = _gaps(new, prios, metrics, ref, ref_prios, losses)
    assert g["loss"] < 1e-5 and g["grad"] < 1e-4 and g["priority"] < 1e-4, g
    for got, want in (
        (new.actor_params, ref["actor"]), (new.critic_params, ref["critic"]),
        (new.target_actor_params, ref["target_actor"]),
        (new.target_critic_params, ref["target_critic"]),
    ):
        for a, b in zip(jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-6)
    assert int(new.step) == int(ref["step"]) == 1


def test_the_same_step_in_bfloat16_fails_the_comparison():
    sound = _gaps(*_both())
    low = _gaps(*_both(compute_dtype="bfloat16"))
    assert low["loss"] > 1e-3 > 10 * sound["loss"], (low, sound)
    assert low["grad"] > 1e-3 > 10 * sound["grad"], (low, sound)


def test_n_step_targets_cut_at_terminations_and_truncations():
    from r2d2dpg_tpu.ops import n_step_targets

    rng = np.random.default_rng(0)
    shape = (16, 9)
    rew = rng.uniform(size=shape).astype(np.float32)
    disc = (rng.uniform(size=shape) > 0.15).astype(np.float32)
    reset = (rng.uniform(size=shape) > 0.7).astype(np.float32)
    q = rng.normal(size=shape).astype(np.float32)
    for n in (1, 3, 5):
        want = n_step_targets(rew, disc, reset, q, n=n, gamma=0.97)
        got = reference.n_step_targets(jnp.asarray(rew), jnp.asarray(disc),
                                       jnp.asarray(reset), jnp.asarray(q), n, 0.97)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6, atol=1e-6)
    # One row by hand: a truncation after step 1 bootstraps at q[1].
    r = np.ones((1, 4), np.float32); d = np.ones((1, 4), np.float32)
    z = np.array([[0, 0, 1, 0]], np.float32); qq = np.array([[10., 20., 30., 40.]], np.float32)
    got = reference.n_step_targets(r, d, z, qq, 3, 0.5)
    np.testing.assert_allclose(np.asarray(got), [[1 + 0.5 * 20.0]])


def test_sampling_indices_probabilities_and_weights_for_a_fixed_key():
    from r2d2dpg_tpu.ops import importance_weights
    from r2d2dpg_tpu.replay.arena import ReplayArena

    trainer, exp, cfg, rows, train, w = _setup()
    spec = traffic.row_spec(cfg, exp.agent.seq_len, exp.hidden)
    arena = ReplayArena(64, alpha=0.6)
    rows64, prios = traffic.make_rows(jax.random.PRNGKey(5), jnp.arange(64), spec,
                                      {"reset_prob": 0.1, "priority_log_sigma": 1.0,
                                       "priority_scale": 0.1, "carry_scale": 0.5,
                                       "reward_max": 1.0})
    state = arena.add(arena.init_state(program.to_batch(
        jax.tree_util.tree_map(lambda x: x[:1], rows64))), program.to_batch(rows64), prios)
    key = jax.random.PRNGKey(9)
    res = arena.sample(state, key, 16)
    u01 = np.asarray(jax.random.uniform(key, (16,)))
    idx, probs, cdf, total = reference.sample_indices(np.asarray(state.priority), u01, 0.6)
    np.testing.assert_array_equal(np.asarray(res.indices), idx)
    np.testing.assert_allclose(np.asarray(res.probs), probs, rtol=1e-5)
    np.testing.assert_allclose(
        np.asarray(importance_weights(res.probs, 64, beta=0.4)),
        reference.is_weights(probs, 64, 0, 0.4, 100_000), rtol=1e-5)
    # Write-back: last write wins where a slot is written twice.
    widx = np.array([3, 7, 3, 9]); vals = np.array([0.5, 0.25, 0.125, 0.0], np.float32)
    got = arena.update_priorities(state, jnp.asarray(widx), jnp.asarray(vals)).priority
    want = reference.write_priorities(np.asarray(state.priority), widx, vals)
    np.testing.assert_array_equal(np.asarray(got), want)
    assert want[3] == np.float32(0.125) and want[9] == np.float32(reference.PRIORITY_EPS)


def test_draws_are_matched_to_the_slots_that_changed():
    rng = np.random.default_rng(3)
    before = rng.lognormal(size=512).astype(np.float32)
    u01 = [rng.uniform(size=8), rng.uniform(size=8)]
    after = before.copy()
    truth = []
    for k in range(2):
        idx, _, _, _ = reference.sample_indices(after if k else before, u01[k], 0.6)
        truth.append(idx)
        after[idx] = np.float32(7.0 + k)

    def assign(after, u):
        return compare.assign_draws(before, after, np.flatnonzero(before != after),
                                    u, 0.6, near=4.0)

    a = assign(after, u01)
    np.testing.assert_array_equal(a["slots"], np.stack(truth))
    assert a["gap"] < 1e-6 and a["draws_unplaced"] == 0
    # A draw shifted to the neighbouring slot reads as a gap of under a slot.
    shifted = before.copy()
    shifted[truth[0][0] + 1] = 9.0
    shifted[truth[0][1:]] = 7.0
    b = assign(shifted, [u01[0]])
    assert 0.0 < b["gap"] < 3.0
    assert set(np.unique(b["slots"])) == set(np.flatnonzero(before != shifted))
    # A slot changed where no draw fell stays without a draw.
    far = after.copy()
    far[int(np.argmin(before))] = 123.0
    c = assign(far, u01)
    assert int(np.argmin(before)) not in set(c["slots"].ravel()) or c["gap"] > 4.0
    # The unit is the mean width of the slots that carry mass: empty slots
    # behind the filled ones change no gap.
    padded = lambda v: np.concatenate([v, np.zeros(4096, np.float32)])  # noqa: E731
    e = compare.assign_draws(padded(before), padded(shifted), np.flatnonzero(before != shifted),
                             [u01[0]], 0.6, near=4.0)
    assert e["gap"] == pytest.approx(b["gap"], rel=1e-9)
    # Nothing changed at all: no draw can be placed.
    d = assign(before, u01)
    assert d["draws_unplaced"] == 16 and d["gap"] == float("inf")


def test_worst_leaf_gap_is_of_norms_against_leaf_or_median():
    ref = {"a": 1.0, "b": 100.0, "c": 1e-9}
    prog = {"a": 1.1, "b": 101.0, "c": 2e-9}
    gap, leaf = compare.worst_leaf_gap(prog, ref)
    assert leaf == "a" and abs(gap - 0.1) < 1e-9  # c is held to the median leaf
    assert compare.dead_leaves({"a": 1.0, "b": 2.0, "c": 1e-5}) == ["c"]
