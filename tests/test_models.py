"""Model tests: shapes, carried state, reset masking, scan-vs-loop equivalence
(SURVEY.md §4.1-4.2)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from r2d2dpg_tpu.models import ActorNet, CriticNet, time_major, unroll


B, OBS, ACT, HID = 3, 5, 2, 32


def make_actor(use_lstm=True, pixels=False):
    net = ActorNet(action_dim=ACT, hidden=HID, use_lstm=use_lstm, pixels=pixels)
    obs = jnp.zeros((B, 64, 64, 3)) if pixels else jnp.zeros((B, OBS))
    carry = net.initial_carry(B)
    params = net.init(jax.random.PRNGKey(0), obs, carry, jnp.zeros(B))
    return net, params, carry, obs


def make_critic(use_lstm=True):
    net = CriticNet(hidden=HID, use_lstm=use_lstm)
    obs, act = jnp.zeros((B, OBS)), jnp.zeros((B, ACT))
    carry = net.initial_carry(B)
    params = net.init(jax.random.PRNGKey(0), obs, act, carry, jnp.zeros(B))
    return net, params, carry


@pytest.mark.parametrize("use_lstm", [True, False])
def test_actor_shapes_and_bounds(use_lstm):
    net, params, carry, _ = make_actor(use_lstm)
    obs = jax.random.normal(jax.random.PRNGKey(1), (B, OBS)) * 10
    a, carry2 = net.apply(params, obs, carry, jnp.zeros(B))
    assert a.shape == (B, ACT)
    assert np.all(np.abs(np.asarray(a)) <= 1.0)
    if use_lstm:
        assert jax.tree_util.tree_leaves(carry2)[0].shape == (B, HID)
    else:
        assert carry2 == ()


@pytest.mark.parametrize("use_lstm", [True, False])
def test_critic_shapes(use_lstm):
    net, params, carry = make_critic(use_lstm)
    obs = jax.random.normal(jax.random.PRNGKey(1), (B, OBS))
    act = jax.random.normal(jax.random.PRNGKey(2), (B, ACT))
    q, _ = net.apply(params, obs, act, carry, jnp.zeros(B))
    assert q.shape == (B,)


def test_pixel_actor():
    net, params, carry, obs = make_actor(pixels=True)
    a, _ = net.apply(
        params,
        jnp.zeros((B, 64, 64, 3), jnp.uint8),
        carry,
        jnp.zeros(B),
    )
    assert a.shape == (B, ACT)


def test_lstm_state_changes_and_affects_output():
    net, params, carry, _ = make_actor()
    obs = jax.random.normal(jax.random.PRNGKey(1), (B, OBS))
    a1, carry1 = net.apply(params, obs, carry, jnp.zeros(B))
    a2, _ = net.apply(params, obs, carry1, jnp.zeros(B))
    # Same obs, different carry -> different action (state matters).
    assert not np.allclose(np.asarray(a1), np.asarray(a2))


def test_reset_masks_carry_per_row():
    net, params, carry, _ = make_actor()
    obs = jax.random.normal(jax.random.PRNGKey(1), (B, OBS))
    _, carry1 = net.apply(params, obs, carry, jnp.zeros(B))
    # Row 0 resets: its step must equal a from-zero-state step.
    reset = jnp.array([1.0, 0.0, 0.0])
    a_mixed, _ = net.apply(params, obs, carry1, reset)
    a_zero, _ = net.apply(params, obs, carry, jnp.zeros(B))
    np.testing.assert_allclose(
        np.asarray(a_mixed)[0], np.asarray(a_zero)[0], rtol=1e-5, atol=1e-6
    )
    assert not np.allclose(np.asarray(a_mixed)[1], np.asarray(a_zero)[1])


def test_unroll_equals_step_loop():
    """lax.scan unroll == step-by-step python loop (SURVEY §4.2)."""
    net, params, carry, _ = make_actor()
    T = 7
    obs_seq = jax.random.normal(jax.random.PRNGKey(3), (T, B, OBS))
    resets = jnp.zeros((T, B)).at[3, 1].set(1.0)

    outs, final = unroll(
        lambda c, o, r: net.apply(params, o, c, r), carry, obs_seq, resets
    )

    c = carry
    expected = []
    for t in range(T):
        a, c = net.apply(params, obs_seq[t], c, resets[t])
        expected.append(a)
    np.testing.assert_allclose(
        np.asarray(outs), np.asarray(jnp.stack(expected)), rtol=1e-5, atol=1e-6
    )
    np.testing.assert_allclose(
        np.asarray(jax.tree_util.tree_leaves(final)[0]),
        np.asarray(jax.tree_util.tree_leaves(c)[0]),
        rtol=1e-5,
        atol=1e-6,
    )


def test_time_major():
    x = jnp.zeros((4, 9, 2))
    assert time_major(x).shape == (9, 4, 2)


def test_jit_no_retrace():
    """Every jitted step compiles once across calls (SURVEY §4.2)."""
    net, params, carry, _ = make_actor()
    step = jax.jit(lambda p, o, c, r: net.apply(p, o, c, r))
    obs = jnp.zeros((B, OBS))
    step(params, obs, carry, jnp.zeros(B))
    n0 = step._cache_size()
    for _ in range(3):
        _, carry = step(params, obs, carry, jnp.zeros(B))
    assert step._cache_size() == n0 == 1


# ---------------------------------------------------------------- bf16 core
def test_bf16_net_keeps_fp32_carry():
    """Reduced-precision nets stream bf16 through MixedPrecisionLSTMCell's
    matmuls: the recurrent state must STAY float32 across steps (the round-3 dtype A/B
    showed bf16 state accumulation costs ~3x walker learning)."""
    net = ActorNet(action_dim=ACT, hidden=HID, use_lstm=True, dtype=jnp.bfloat16)
    obs = jnp.zeros((B, OBS))
    carry = net.initial_carry(B)
    params = net.init(jax.random.PRNGKey(0), obs, carry, jnp.zeros(B))
    for i in range(3):
        action, carry = net.apply(
            params, jnp.full((B, OBS), float(i)), carry, jnp.zeros(B)
        )
    for leaf in jax.tree_util.tree_leaves(carry):
        assert leaf.dtype == jnp.float32, leaf.dtype
    assert action.dtype == jnp.float32  # head output cast back


def test_mixed_cell_tracks_fp32_reference_better_than_bf16_state():
    """Property behind the design: with gate matmuls in bf16, keeping the
    state update in fp32 must track the all-fp32 reference much closer
    over a long unroll than also truncating the carry to bf16 each step
    (the old behavior)."""
    from r2d2dpg_tpu.models.actor_critic import MixedPrecisionLSTMCell

    T, hidden = 120, HID
    cell_ref = MixedPrecisionLSTMCell(hidden, dtype=jnp.float32)
    cell_mix = MixedPrecisionLSTMCell(hidden, dtype=jnp.bfloat16)
    x0 = jnp.zeros((B, hidden))
    c0 = (jnp.zeros((B, hidden)), jnp.zeros((B, hidden)))
    params = cell_ref.init(jax.random.PRNGKey(1), c0, x0)  # shared structure
    xs = 0.5 * jax.random.normal(jax.random.PRNGKey(2), (T, B, hidden))

    def run(cell, truncate_state):
        carry = c0
        hs = []
        for t in range(T):
            carry, h = cell.apply(params, carry, xs[t])
            if truncate_state:
                carry = jax.tree_util.tree_map(
                    lambda a: a.astype(jnp.bfloat16).astype(jnp.float32), carry
                )
            hs.append(h.astype(jnp.float32))
        return jnp.stack(hs)

    ref = run(cell_ref, False)
    mixed = run(cell_mix, False)
    old_bf16 = run(cell_mix, True)
    err_mixed = float(jnp.abs(mixed - ref).mean())
    err_old = float(jnp.abs(old_bf16 - ref).mean())
    assert err_mixed < err_old, (err_mixed, err_old)
    # And the mixed error is small in absolute terms (h is in [-1, 1]).
    assert err_mixed < 0.02, err_mixed


def test_fp32_cell_keeps_the_stock_cells_tree_init_and_step():
    """dtype=float32 ran flax's stock ``OptimizedLSTMCell`` until PR 30; it
    runs the repo's own cell now, because the stock cell offers no input
    projection to take out of the learner's scans.  What still has to hold:
    the cell's tree is the stock cell's under the stock cell's name, a seed
    draws the same weights, and a step on the same parameters is flax's
    (the same products, summed in flax's order ``(zh + b) + zx``)."""
    import flax.linen as nn

    from r2d2dpg_tpu.models.actor_critic import MixedPrecisionLSTMCell

    _, params, _, _ = make_actor()
    assert set(params["params"]["core"]) == {"OptimizedLSTMCell_0"}
    ks = jax.random.split(jax.random.PRNGKey(4), 3)
    carry = (jax.random.normal(ks[0], (B, HID)), jax.random.normal(ks[1], (B, HID)))
    x = jax.random.normal(ks[2], (B, HID))
    stock = nn.OptimizedLSTMCell(HID)
    ours = MixedPrecisionLSTMCell(HID, dtype=jnp.float32)
    want = stock.init(jax.random.PRNGKey(0), carry, x)
    got = ours.init(jax.random.PRNGKey(0), carry, x)
    assert jax.tree_util.tree_structure(got) == jax.tree_util.tree_structure(want)
    jax.tree_util.tree_map(np.testing.assert_array_equal, got, want)
    assert sorted(got["params"]) == ["hf", "hg", "hi", "ho", "if", "ig", "ii", "io"]
    # The net's own cell, on the stock cell's arithmetic.
    cell = {"params": params["params"]["core"]["OptimizedLSTMCell_0"]}
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-6),
        ours.apply(cell, carry, x), stock.apply(cell, carry, x))


def lstm_carry(use_lstm):
    return (jnp.zeros((B, HID)), jnp.zeros((B, HID))) if use_lstm else ()


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("use_lstm, pixels", [(True, False), (True, True), (False, False)])
def test_param_tree_is_the_one_checkpoints_hold(use_lstm, pixels, dtype):
    """``ActorNet`` / ``CriticNet`` path by path and shape by shape, written
    out: what every checkpoint holds and what ``chipbench/reference.py::
    init_state`` draws weights into.  Splitting a step into ``encode`` /
    ``step`` / ``readout`` (PR 30) moved no leaf."""
    dense = lambda i, o: {"kernel": (i, o), "bias": (o,)}  # noqa: E731
    if pixels:
        torso = {
            "Conv_0": {"kernel": (8, 8, 3, 32), "bias": (32,)},
            "Conv_1": {"kernel": (4, 4, 32, 64), "bias": (64,)},
            "Conv_2": {"kernel": (3, 3, 64, 64), "bias": (64,)},
            "Dense_0": dense(4 * 4 * 64, HID),
        }
        obs = jnp.zeros((B, 64, 64, 3), jnp.uint8)
    else:
        torso, obs = {"Dense_0": dense(OBS, HID)}, jnp.zeros((B, OBS))
    if use_lstm:
        core = {"OptimizedLSTMCell_0": {
            **{f"i{g}": {"kernel": (HID, HID)} for g in "ifgo"},
            **{f"h{g}": dense(HID, HID) for g in "ifgo"},
        }}
    else:
        core = {"Dense_0": dense(HID, HID)}
    want = {
        "actor": {"torso": torso, "core": core, "head": dense(HID, ACT)},
        "critic": {"torso": torso, "mix": dense(HID + ACT, HID), "core": core,
                   "head": dense(HID, 1)},
    }
    kw = dict(hidden=HID, use_lstm=use_lstm, pixels=pixels, dtype=jnp.dtype(dtype))
    actor, critic = ActorNet(action_dim=ACT, **kw), CriticNet(**kw)
    reset = jnp.zeros(B)
    got = {
        "actor": jax.eval_shape(
            actor.init, jax.random.PRNGKey(0), obs, actor.initial_carry(B), reset),
        "critic": jax.eval_shape(
            critic.init, jax.random.PRNGKey(0), obs, jnp.zeros((B, ACT)),
            lstm_carry(use_lstm), reset),
    }
    for name, tree in got.items():
        assert set(tree) == {"params"}
        assert all(leaf.dtype == jnp.float32 for leaf in jax.tree_util.tree_leaves(tree))
        shapes = jax.tree_util.tree_map(lambda leaf: leaf.shape, tree["params"])
        assert shapes == want[name], name


def test_cross_dtype_param_tree_identical():
    """THE invariant behind fp32<->bf16 checkpoint interchange (VERDICT r4
    weak #2a): dtype selects the precision of the gate matmuls, but the param
    tree — structure, leaf shapes, and leaf dtypes (params are float32 under
    both) — must be identical, exactly as models/actor_critic.py's cell
    docstring promises.
    Round 3 shipped a mixed cell violating this and every fp32 checkpoint
    became unreadable under bf16 eval; this pins the fix against flax
    upgrades and future cell edits (ADVICE r4 #1)."""
    obs = jnp.zeros((B, OBS))
    act = jnp.zeros((B, ACT))
    reset = jnp.zeros(B)

    def actor_tree(dtype):
        net = ActorNet(action_dim=ACT, hidden=HID, use_lstm=True, dtype=dtype)
        return jax.eval_shape(
            net.init, jax.random.PRNGKey(0), obs, net.initial_carry(B), reset
        )

    def critic_tree(dtype):
        net = CriticNet(hidden=HID, use_lstm=True, dtype=dtype)
        return jax.eval_shape(
            net.init, jax.random.PRNGKey(0), obs, act, net.initial_carry(B), reset
        )

    for make in (actor_tree, critic_tree):
        t32, t16 = make(jnp.float32), make(jnp.bfloat16)
        assert jax.tree_util.tree_structure(t32) == jax.tree_util.tree_structure(
            t16
        ), f"{make.__name__}: fp32/bf16 param trees differ in structure"
        by_path16 = {
            jax.tree_util.keystr(p): l
            for p, l in jax.tree_util.tree_leaves_with_path(t16)
        }
        for path, l32 in jax.tree_util.tree_leaves_with_path(t32):
            l16 = by_path16[jax.tree_util.keystr(path)]
            assert l32.shape == l16.shape, (path, l32.shape, l16.shape)
            assert l32.dtype == l16.dtype == jnp.float32, (path, l32.dtype, l16.dtype)


def test_cross_dtype_params_apply_both_ways():
    """fp32-initialized params must run under the bf16 net and vice versa
    (the apply-side half of checkpoint interchange)."""
    obs = jnp.zeros((B, OBS))
    reset = jnp.zeros(B)
    nets = {
        d: ActorNet(action_dim=ACT, hidden=HID, use_lstm=True, dtype=jnp.dtype(d))
        for d in ("float32", "bfloat16")
    }
    carry = nets["float32"].initial_carry(B)
    for src, dst in (("float32", "bfloat16"), ("bfloat16", "float32")):
        params = nets[src].init(jax.random.PRNGKey(0), obs, carry, reset)
        a, c2 = nets[dst].apply(params, obs, carry, reset)
        assert a.shape == (B, ACT) and a.dtype == jnp.float32
        # the carry contract is fp32 under both cells
        assert all(
            l.dtype == jnp.float32 for l in jax.tree_util.tree_leaves(c2)
        )
