"""``counts.py`` against what XLA counts once the scans are unrolled, against
the arena's real leaves, and the configuration files against the program."""

import dataclasses
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import counts, harness, program, traffic
from chipbench.drivers import learn

from chipbench_fixtures import DATA

CELLS = {"walker_r2d2": "walker_r2d2.learn", "cheetah_pixels": "cheetah_pixels.learn"}
DMC = {"walker_r2d2": ("walker", "walk", False), "cheetah_pixels": ("cheetah", "run", True)}


def _ctx(config_name, cell_name, root=harness.HERE, **over):
    cfg = dict(harness.load_json("configs", config_name, root), **over)
    cell = harness.load_json("workloads", cell_name, root)
    return harness.Context(cell_name=cell_name, cell=cell, config=cfg, seed=3,
                           seconds=0.0, trace=False)


def _step_flops(ctx, unrolled, monkeypatch):
    exp = program.build_experiment(ctx)
    trainer = program.build_trainer(ctx, exp)
    spec = traffic.row_spec(ctx.config, exp.agent.seq_len, exp.hidden)
    B = exp.trainer.batch_size
    rows, _ = traffic.make_rows(jax.random.PRNGKey(0), jnp.arange(B), spec,
                                ctx.cell["traffic"])
    train = program.make_train_state(trainer, spec, ctx.config, 3)
    if unrolled:
        scan = jax.lax.scan
        monkeypatch.setattr(
            jax.lax, "scan",
            lambda f, init, xs=None, length=None, **kw: scan(
                f, init, xs, length=length, unroll=True))
    step = jax.jit(lambda t, b, w: trainer.agent.learner_step(t, b, w))
    compiled = step.lower(train, program.to_batch(rows), jnp.ones((B,))).compile()
    cost = compiled.cost_analysis()
    cost = cost[0] if isinstance(cost, (list, tuple)) else cost
    return float(cost["flops"])


@pytest.mark.parametrize("pixels", [False, True])
def test_learner_step_flops_match_xla_once_the_scans_are_unrolled(pixels, monkeypatch):
    over = dict(hidden=32, batch_size=4, burnin=3, unroll=4, n_step=2)
    if pixels:
        ctx = _ctx("cheetah_pixels", "cheetah_pixels.learn", **over)
    else:
        ctx = _ctx("walker_r2d2", "walker_r2d2.learn", **over)
    mine = counts.learner_step_flops(ctx.config)
    rolled = _step_flops(ctx, False, monkeypatch)
    unrolled = _step_flops(ctx, True, monkeypatch)
    # XLA also counts element-wise work and what the program computes beyond
    # need (heads in the burn-in, full input gradients): mine is the floor.
    assert 0.6 * unrolled <= mine <= 1.02 * unrolled, (mine, unrolled)
    # The undercount this guards against: a scan's body counted once.
    assert rolled < 0.5 * unrolled and rolled < 0.7 * mine, (rolled, unrolled, mine)


def test_replay_bytes_follow_the_shapes():
    for name in CELLS:
        cfg = harness.load_json("configs", name)
        L = counts.seq_len(cfg)
        obs = L * int(np.prod(cfg["obs_shape"])) * np.dtype(cfg["obs_dtype"]).itemsize
        row = obs + 4 * L * (cfg["action_dim"] + 3) + 4 * 4 * cfg["hidden"]
        assert counts.row_data_bytes(cfg) == row
        assert counts.sample_bytes(cfg) == 2 * cfg["batch_size"] * row
        assert counts.update_bytes(cfg) == 12 * cfg["batch_size"]
    assert counts.row_data_bytes(harness.load_json("configs", "walker_r2d2")) == 9772


@pytest.mark.parametrize("name", sorted(CELLS))
def test_arena_bytes_per_sequence_and_the_share_of_the_chip(name):
    ctx = _ctx(name, CELLS[name])
    exp = program.build_experiment(ctx)
    trainer = program.build_trainer(ctx, exp)
    spec = traffic.row_spec(ctx.config, exp.agent.seq_len, exp.hidden)
    example, _ = jax.eval_shape(lambda: traffic.make_rows(
        jax.random.PRNGKey(0), jnp.zeros((1,), jnp.int32), spec, ctx.cell["traffic"]))
    state = jax.eval_shape(lambda: trainer.arena.init_state(program.to_batch(
        jax.tree_util.tree_map(lambda s: jnp.zeros(s.shape, s.dtype), example))))
    capacity = ctx.config["capacity"]
    per_slot = sum(
        int(np.prod(leaf.shape)) * leaf.dtype.itemsize
        for leaf in jax.tree_util.tree_leaves(state) if leaf.shape[:1] == (capacity,)
    ) // capacity
    assert per_slot == counts.arena_row_bytes(ctx.config)
    share = counts.arena_bytes(ctx.config) / counts.load_peaks("TPU v5 lite")["hbm_bytes"]
    assert share >= 0.25, share


@pytest.mark.parametrize("name", sorted(CELLS))
def test_configuration_file_is_the_programs_config_but_for_what_it_lists(name):
    from r2d2dpg_tpu.configs import get_config

    cfg = harness.load_json("configs", name)
    exp = get_config(cfg["program_config"])
    flat = dict(dataclasses.asdict(exp.agent), **dataclasses.asdict(exp.trainer),
                hidden=exp.hidden, use_lstm=exp.use_lstm, pixels=exp.pixels,
                compute_dtype=exp.compute_dtype)
    differ = sorted(k for k in flat if k in cfg and cfg[k] != flat[k])
    assert differ == sorted(cfg["changed"]) == ["capacity"]
    assert cfg["changed"]["capacity"] == {
        "from": flat["capacity"], "to": cfg["capacity"],
        "why": cfg["changed"]["capacity"]["why"]}
    # What the learn driver applies is what the file says.
    built = program.build_experiment(_ctx(name, CELLS[name]))
    assert built.trainer.capacity == cfg["capacity"]
    assert built.agent == exp.agent


@pytest.mark.parametrize("name", sorted(DMC))
def test_configuration_file_shapes_equal_env_spec(name):
    from r2d2dpg_tpu.envs.dmc_host import DMCHostEnv

    domain, task, pixels = DMC[name]
    spec = DMCHostEnv(domain, task, pixels=pixels, native=False).spec
    cfg = harness.load_json("configs", name)
    assert tuple(cfg["obs_shape"]) == tuple(spec.obs_shape)
    assert cfg["action_dim"] == spec.action_dim
    assert cfg["obs_dtype"] == ("uint8" if pixels else "float32")


def test_in_place_fill_equals_add_of_every_row():
    """``learn.fill_arena`` writes the state ``init_state`` + ``add`` would."""
    ctx = _ctx("pendulum_tiny", "pendulum_tiny.learn", root=DATA)
    exp = program.build_experiment(ctx)
    trainer = program.build_trainer(ctx, exp)
    spec = traffic.row_spec(ctx.config, exp.agent.seq_len, exp.hidden)
    filled = learn.fill_arena(ctx, trainer, spec)
    key = traffic.seed_key(ctx.seed, traffic.STREAM_ROWS)
    rows, prios = traffic.make_rows(key, jnp.arange(trainer.arena.capacity), spec,
                                    ctx.cell["traffic"])
    batch = program.to_batch(rows)
    added = trainer.arena.add(
        trainer.arena.init_state(jax.tree_util.tree_map(lambda x: x[:1], batch)),
        batch, prios)
    # To the last bit but for exp() and tanh(), whose last bit follows the
    # width of the batch they are vectorised over.
    for a, b in zip(jax.tree_util.tree_leaves(filled), jax.tree_util.tree_leaves(added)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6, atol=0)


def test_train_phase_flops_add_up():
    cfg = harness.load_json("configs", "walker_r2d2")
    E = cfg["num_envs"]
    assert counts.train_phase_flops(cfg) == (
        cfg["stride"] * counts.policy_step_flops(cfg, E)
        + counts.initial_priority_flops(cfg, E)
        + cfg["learner_steps"] * counts.learner_step_flops(cfg))
    assert counts.learn_call_flops(cfg) == 4 * counts.learner_step_flops(cfg)
    assert 15e9 < counts.learner_step_flops(cfg) < 25e9
