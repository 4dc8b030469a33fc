"""The sampled pixel batch prepared once an update (PR 35): ``ConvTorso.prepare``
makes ``Frames`` of all stored steps, ``Stepped`` and ``learner_step`` cut
every pass's window out of them, and nothing else moves.

"The parent's path" is built here, in the same process: PR 35's parent had a
``ConvTorso`` that converted whatever raw window it was handed and no
preparation, so nets built over ``ParentConvTorso`` (its code, verbatim, plus
a ``prepare`` that hands the batch back) run ``learner_step`` as the parent
did: windows cut out of the raw uint8 batch, every pass converting its own."""

import dataclasses
import re
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from r2d2dpg_tpu.agents import ddpg
from r2d2dpg_tpu.agents.ddpg import AgentConfig, R2D2DPG
from r2d2dpg_tpu.models import actor_critic, sequence, torsos
from r2d2dpg_tpu.models.actor_critic import ActorNet, CriticNet, policy_step_fn
from r2d2dpg_tpu.models.sequence import Stepped, window
from r2d2dpg_tpu.models.torsos import ConvTorso, Frames, fan_in_uniform
from r2d2dpg_tpu.replay.arena import SequenceBatch

B, HID, ACT, FRAME = 4, 16, 3, 36  # 36 x 36: the least the three VALID convs take


class ParentConvTorso(nn.Module):
    """``models/torsos.py::ConvTorso`` as PR 35 found it."""

    out_size: int = 256
    dtype: Any = jnp.float32

    def prepare(self, obs):
        return obs

    @nn.compact
    def __call__(self, obs: jnp.ndarray) -> jnp.ndarray:
        x = obs.astype(self.dtype)
        if obs.dtype == jnp.uint8:
            x = x / 255.0
        for features, kernel, stride in ((32, 8, 4), (64, 4, 2), (64, 3, 1)):
            x = nn.relu(
                nn.Conv(
                    features,
                    (kernel, kernel),
                    strides=(stride, stride),
                    padding="VALID",
                    dtype=self.dtype,
                )(x)
            )
        x = x.reshape(x.shape[:-3] + (-1,))
        x = nn.relu(
            nn.Dense(self.out_size, kernel_init=fan_in_uniform(), dtype=self.dtype)(x)
        )
        return x


BRANCHES = {
    "plain": dict(),
    "twin_critic": dict(twin_critic=True),
    "target_smoothing": dict(target_policy_sigma=0.2),
}


def make_agent(monkeypatch, torso, pixels=True, **kw):
    """An agent over ``torso``.  The nets look their torso's class up when
    they are applied, so it stays patched in until the test ends: a test
    that runs both sides gives each a ``monkeypatch.context()`` of its own."""
    monkeypatch.setattr(actor_critic, "ConvTorso", torso)
    cfg = AgentConfig(**{**dict(burnin=3, unroll=3, n_step=2), **kw})
    agent = R2D2DPG(
        ActorNet(action_dim=ACT, hidden=HID, pixels=pixels),
        CriticNet(hidden=HID, pixels=pixels),
        cfg,
    )
    assert isinstance(agent.seq, Stepped)
    return agent


def make_batch(agent, pixels=True):
    L = agent.config.seq_len
    ks = jax.random.split(jax.random.PRNGKey(11), 6)
    if pixels:
        obs = jax.random.randint(ks[0], (B, L, FRAME, FRAME, 3), 0, 256).astype(jnp.uint8)
    else:
        obs = jax.random.normal(ks[0], (B, L, 5))
    carry = lambda k: (  # noqa: E731
        0.3 * jax.random.normal(k, (B, HID)), 0.3 * jax.random.normal(k, (B, HID)) + 0.1)
    return SequenceBatch(
        obs=obs,
        action=jax.random.uniform(ks[1], (B, L, ACT), minval=-1, maxval=1),
        reward=jax.random.normal(ks[2], (B, L)),
        discount=jnp.full((B, L), 0.99),
        reset=jnp.zeros((B, L)).at[1, 1].set(1.0).at[2, L - 3].set(1.0),
        carries={"actor": carry(ks[3]), "critic": carry(ks[4])},
    )


def desync(state):
    """Targets that are not the online nets, so that no pass can stand in
    for another."""
    off = lambda t: jax.tree_util.tree_map(lambda x: 0.9 * x + 0.01, t)  # noqa: E731
    return dataclasses.replace(
        state,
        target_actor_params=off(state.target_actor_params),
        target_critic_params=off(state.target_critic_params),
    )


@pytest.mark.parametrize("burnin", (0, 3), ids=("no_burn_in", "burn_in_3"))
@pytest.mark.parametrize("branch", sorted(BRANCHES))
def test_learner_step_on_prepared_frames_is_the_parents_update(
    monkeypatch, branch, burnin
):
    """Losses, priorities, gradients (Adam's first moments), new parameters
    and targets of one update: the parent's, to float32 rounding."""
    kw = dict(BRANCHES[branch], burnin=burnin)
    is_weights, key = jnp.linspace(0.5, 1.0, B), jax.random.PRNGKey(3)

    def update(torso, prepared):
        with monkeypatch.context() as m:
            agent = make_agent(m, torso, **kw)
            batch = make_batch(agent)
            state = desync(agent.init(
                jax.random.PRNGKey(0), batch.obs[:, 0], batch.action[:, 0]))
            assert isinstance(agent.seq.prepare(batch).obs, prepared)
            return jax.jit(agent.learner_step)(state, batch, is_weights, key)

    got, want = update(ConvTorso, Frames), update(ParentConvTorso, jax.Array)
    assert jax.tree_util.tree_structure(got) == jax.tree_util.tree_structure(want)
    # ``Conv_0`` over prepared frames sums each output's 192 products in
    # another order (four taps of 48 for 64 of 3): near-zero elements differ
    # by the float32 rounding of the larger terms, 1.6e-7 at most here.
    for g, w in zip(jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(g, w, rtol=2e-5, atol=3e-7)
    # The update is one: priorities and Adam's first moments are not zeros.
    _, prios, metrics = got
    assert np.all(np.asarray(prios) > 0) and float(metrics["grad_norm"]) > 0
    moments = jax.tree_util.tree_leaves(got[0].critic_opt_state)
    assert any(float(jnp.abs(m).max()) > 0 for m in moments if m.ndim)


@pytest.mark.parametrize("branch", sorted(BRANCHES) + ["no_burn_in"])
def test_flat_observations_leave_learner_step_as_it_was(monkeypatch, branch):
    """For a flat observation the preparation is the identity and the windows
    are cut as before: the jaxpr of ``learner_step`` is the one with the
    parent's own two expressions in the seam's place (against the parent's
    checkout itself the four jaxprs were equal to the character, PR 35)."""
    kw = dict(burnin=0) if branch == "no_burn_in" else BRANCHES[branch]
    agent = make_agent(monkeypatch, ConvTorso, pixels=False, **kw)
    batch = make_batch(agent, pixels=False)
    state = agent.init(jax.random.PRNGKey(0), batch.obs[:, 0], batch.action[:, 0])
    assert agent.seq.prepare(batch).obs is batch.obs
    args = (state, batch, jnp.ones(B), jax.random.PRNGKey(3))

    with_seam = str(jax.make_jaxpr(agent.learner_step)(*args))
    monkeypatch.setattr(agent.seq, "prepare", lambda b: b)
    for module in (ddpg, sequence):
        monkeypatch.setattr(
            module, "window", lambda obs, a, b: jnp.swapaxes(obs[:, a:b], 0, 1))
    assert with_seam == str(jax.make_jaxpr(agent.learner_step)(*args))
    assert "optimization_barrier" not in with_seam


@pytest.mark.parametrize("path", ("acting", "initial_priority", "serving"))
def test_raw_frames_go_through_the_torso_as_before(monkeypatch, path):
    """Acting, ``initial_priority`` and the serving step hand the torso raw
    ``[..., H, W, C]`` frames: the parent's outputs, bit for bit."""
    def run(torso):
        with monkeypatch.context() as m:
            agent = make_agent(m, torso)
            batch = make_batch(agent)
            state = desync(agent.init(
                jax.random.PRNGKey(0), batch.obs[:, 0], batch.action[:, 0]))
            obs, carry, reset = batch.obs[:, 2], batch.carries["actor"], batch.reset[:, 1]
            if path == "acting":
                return agent.actor.apply(state.actor_params, obs, carry, reset)
            if path == "initial_priority":
                return jax.jit(agent.initial_priority)(state, batch)
            step = jax.jit(policy_step_fn(agent.actor))
            return [step(state.actor_params, obs[:n], jax.tree_util.tree_map(
                lambda c: c[:n], carry), reset[:n]) for n in (1, 2, B)]

    got, want = run(ConvTorso), run(ParentConvTorso)
    for g, w in zip(jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)):
        assert g.dtype == w.dtype and np.array_equal(np.asarray(g), np.asarray(w))


FRAME_CASES = pytest.mark.parametrize(
    "frame, dtype",
    [((64, 64, 3), jnp.uint8), ((FRAME, FRAME, 3), jnp.uint8), ((8, 8, 2), jnp.float32),
     ((FRAME + 2, FRAME + 7, 3), jnp.uint8)],
    ids=("whole_tiles", "no_whole_tile", "float_frames", "cropped"),
)


def _frames(frame, dtype, seed=1):
    """``[B, 5, *frame]`` frames of ``dtype``: bytes, or floats off the grid."""
    raw = jax.random.randint(jax.random.PRNGKey(seed), (B, 5) + frame, 0, 256)
    return raw.astype(dtype) if dtype == jnp.uint8 else raw.astype(dtype) / 7.0


@FRAME_CASES
def test_frames_are_the_scaled_frames_time_major_and_cut_by_steps(frame, dtype):
    """``prepare`` makes ``[H/4, W/4, 16·C, L·B]``: the rows and columns
    ``Conv_0`` reads (``(out - 1)·4 + 8`` of each, which drops two rows and
    seven columns of a 38 x 43 frame), cut into 4 x 4 blocks, channel
    ``(4a + b)·C + c`` of block ``(i, j)`` being pixel ``(4i + a, 4j + b)``'s
    channel ``c``, with frame ``t·B + b`` minor-most and the torso's own
    conversion applied; ``frames[a:b]`` is those steps of every sequence, and
    ``window`` cuts either form time-major."""
    L = 5
    obs = _frames(frame, dtype)
    frames = ConvTorso(out_size=HID).prepare(obs)
    (height, width, channels), read = frame, [(n - 8) // 4 * 4 + 8 for n in frame[:2]]
    blocks = (read[0] // 4, read[1] // 4, 16 * channels)
    assert frames.batch == B and frames.block == 4
    assert frames.pixels.shape == blocks + (L * B,)
    assert frames.pixels.dtype == jnp.float32

    scaled = obs.astype(jnp.float32) / 255.0 if dtype == jnp.uint8 else obs
    back = frames.pixels.reshape(blocks[:2] + (4, 4, channels, L, B))
    back = back.transpose(5, 6, 0, 2, 1, 3, 4).reshape((L, B, *read, channels))
    want = jnp.swapaxes(scaled, 0, 1)[:, :, : read[0], : read[1]]
    assert np.array_equal(np.asarray(back), np.asarray(want))

    cut = window(frames, 1, 4)
    assert isinstance(cut, Frames) and cut.pixels.shape == blocks + (3 * B,)
    assert np.array_equal(
        np.asarray(cut.pixels), np.asarray(frames.pixels[..., B : 4 * B]))
    assert np.array_equal(np.asarray(cut[:2].pixels), np.asarray(frames[1:3].pixels))
    assert np.array_equal(
        np.asarray(window(obs, 1, 4)), np.asarray(jnp.swapaxes(obs[:, 1:4], 0, 1)))


@FRAME_CASES
def test_the_torso_reads_prepared_windows_as_it_reads_raw_ones(
    monkeypatch, frame, dtype
):
    """``ConvTorso`` on a window of ``Frames`` is ``ConvTorso`` on the same raw
    steps ``[T, B, H, W, C]``, features ``[T, B, out]``.  A frame under 36 x
    36 is too small for the whole stack: the torso is then ``Conv_0`` (the
    layer that reads the blocks) and the dense layer."""
    if min(frame[:2]) < FRAME:
        monkeypatch.setattr(torsos, "_CONVS", torsos._CONVS[:1])
    torso = ConvTorso(out_size=HID)
    obs = _frames(frame, dtype, seed=2)
    params = torso.init(jax.random.PRNGKey(0), obs[:, 0])
    got = torso.apply(params, torso.prepare(obs)[1:4])
    want = torso.apply(params, jnp.swapaxes(obs[:, 1:4], 0, 1))
    assert got.shape == want.shape == (3, B, HID)
    # ``Conv_0`` sums each output's products in another order over blocks
    # (four taps of 16·C for 64 of C): an element near zero differs by the
    # float32 rounding of the largest terms, 5.5e-7 of the largest output at
    # most here.
    np.testing.assert_allclose(
        got, want, rtol=1e-6, atol=2e-6 * float(jnp.abs(want).max()))


def test_conv_0_on_blocks_has_the_strided_convolutions_kernel_gradient():
    """The gradient of every parameter through the torso over prepared frames
    is its gradient through the strided ``Conv_0`` over the raw frames
    (seeded weights, a seeded cotangent), ``Conv_0/kernel`` staying ``[8, 8,
    3, 32]``."""
    torso = ConvTorso(out_size=HID)
    obs = _frames((64, 64, 3), jnp.uint8, seed=4)
    params = torso.init(jax.random.PRNGKey(5), obs[:, 0])
    frames = torso.prepare(obs)
    cotangent = jax.random.normal(jax.random.PRNGKey(6), (3, B, HID))

    def grad(x):
        return jax.grad(lambda p: jnp.sum(torso.apply(p, x) * cotangent))(params)

    got, want = grad(frames[1:4]), grad(jnp.swapaxes(obs[:, 1:4], 0, 1))
    kernel = got["params"]["Conv_0"]["kernel"]
    assert kernel.shape == want["params"]["Conv_0"]["kernel"].shape == (8, 8, 3, 32)
    assert float(jnp.abs(kernel).max()) > 0
    # The layers after ``Conv_0`` see its outputs summed in another order:
    # 6e-7 of a leaf's largest element at most here.
    for g, w in zip(jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(g, w, rtol=1e-6, atol=2e-6 * float(jnp.abs(w).max()))


def test_conv_0_on_blocks_keeps_its_name_and_its_parameters_come_from_raw_frames():
    """Over prepared frames ``Conv_0``'s convolution, forward and weight
    gradient, still carries ``torso/Conv_0`` in its path (what a trace names
    it by); the torso is initialised from raw frames, and refuses ``Frames``."""
    torso = ConvTorso(out_size=HID)
    obs = _frames((64, 64, 3), jnp.uint8, seed=7)
    params = torso.init(jax.random.PRNGKey(8), obs[:, 0])
    frames = torso.prepare(obs)

    def loss(p, x):
        return jnp.sum(torso.apply(p, x))

    text = jax.jit(jax.grad(loss)).lower(params, frames).as_text(debug_info=True)
    paths = re.findall(r'"([^"]*conv_general_dilated)"', text)
    assert any(p.endswith("Conv_0/conv_general_dilated") and "transpose(" not in p
               for p in paths)
    assert any(p.endswith("Conv_0/conv_general_dilated") and "transpose(" in p
               for p in paths)
    assert not any(p.endswith("/torso/conv_general_dilated") for p in paths)
    with pytest.raises(ValueError, match="raw frames"):
        torso.init(jax.random.PRNGKey(8), frames)


def test_nets_that_prepare_differently_are_refused(monkeypatch):
    """One preparation serves the passes of both nets."""
    monkeypatch.setattr(actor_critic, "ConvTorso", ConvTorso)
    agent = R2D2DPG(
        ActorNet(action_dim=ACT, hidden=HID, pixels=True),
        CriticNet(hidden=HID, pixels=False),
        AgentConfig(burnin=1, unroll=2, n_step=1),
    )
    batch = make_batch(agent)
    with pytest.raises(ValueError, match="prepare a batch differently"):
        agent.seq.prepare(batch)
