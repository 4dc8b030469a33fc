"""SPMD tests on the virtual 8-device CPU mesh (SURVEY.md §4.4):
collective correctness, sharded training phases, sigma-ladder sharding."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from r2d2dpg_tpu.agents import AgentConfig, R2D2DPG
from r2d2dpg_tpu.configs import PENDULUM_R2D2
from r2d2dpg_tpu.models import ActorNet, CriticNet
from r2d2dpg_tpu.ops import sigma_ladder
from r2d2dpg_tpu.parallel import DP_AXIS, SPMDTrainer, make_mesh

try:
    shard_map = jax.shard_map
except AttributeError:  # pragma: no cover
    from jax.experimental.shard_map import shard_map


def build_spmd(n_devices=8, **trainer_kw):
    mesh = make_mesh(n_devices)
    env = PENDULUM_R2D2.env_factory()
    agent_cfg = dataclasses.replace(
        PENDULUM_R2D2.agent, burnin=2, unroll=4, n_step=2, axis_name=DP_AXIS
    )
    actor = ActorNet(action_dim=env.spec.action_dim, hidden=16, use_lstm=True)
    critic = CriticNet(hidden=16, use_lstm=True)
    agent = R2D2DPG(actor, critic, agent_cfg)
    tcfg = dataclasses.replace(
        PENDULUM_R2D2.trainer,
        num_envs=trainer_kw.pop("num_envs", 8),
        stride=4,
        batch_size=trainer_kw.pop("batch_size", 16),
        capacity=trainer_kw.pop("capacity", 64),
        min_replay=trainer_kw.pop("min_replay", 8),
        **trainer_kw,
    )
    return SPMDTrainer(env, agent, tcfg, mesh), mesh


def test_psum_of_known_values():
    """Collective plumbing: psum over the dp mesh sums device contributions."""
    mesh = make_mesh(8)

    def f(x):
        return jax.lax.psum(x.sum(), DP_AXIS)

    x = jnp.arange(8.0)
    out = jax.jit(
        shard_map(f, mesh=mesh, in_specs=(P(DP_AXIS),), out_specs=P())
    )(x)
    assert float(out) == 28.0


def test_spmd_phases_run_and_stay_sharded():
    t, mesh = build_spmd()
    s = t.init()
    assert s.obs.sharding.spec == P(DP_AXIS)
    assert s.arena.priority.sharding.spec == P(DP_AXIS)
    n = t.window_fill_phases + t.replay_fill_phases + 2
    s = t.run(n, log_every=0)
    assert int(s.train.step) == 2 * t.config.learner_steps
    assert int(s.env_steps) == n * 4 * 8  # stride * global envs
    # Params stay replicated and identical across devices.
    leaf = jax.tree_util.tree_leaves(s.train.actor_params)[0]
    assert leaf.sharding.is_fully_replicated


def test_spmd_learner_matches_gradient_sync():
    """After one train phase, every device holds the same params (pmean'd
    grads from different local batches -> consistent replicated update)."""
    t, mesh = build_spmd()
    s = t.run(t.window_fill_phases + t.replay_fill_phases + 1, log_every=0)
    leaf = jax.tree_util.tree_leaves(s.train.critic_params)[0]
    shards = [np.asarray(sh.data) for sh in leaf.addressable_shards]
    for other in shards[1:]:
        np.testing.assert_array_equal(shards[0], other)


def test_sigma_ladder_is_global_across_shards():
    """Each device slices its rows of the *global* ladder — exploration
    heterogeneity must span the fleet, not repeat per device."""
    t, mesh = build_spmd()

    def local_sig(_):
        return t._local_sigmas()

    out = jax.jit(
        shard_map(
            local_sig, mesh=mesh, in_specs=(P(DP_AXIS),), out_specs=P(DP_AXIS)
        )
    )(jnp.zeros(8))
    want = sigma_ladder(8, sigma_max=t.config.sigma_max, alpha=t.config.ladder_alpha)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), rtol=1e-6)


def test_divisibility_validation():
    mesh = make_mesh(8)
    env = PENDULUM_R2D2.env_factory()
    agent_cfg = dataclasses.replace(PENDULUM_R2D2.agent, axis_name=DP_AXIS)
    actor = ActorNet(action_dim=1, hidden=8, use_lstm=True)
    critic = CriticNet(hidden=8, use_lstm=True)
    agent = R2D2DPG(actor, critic, agent_cfg)
    bad = dataclasses.replace(PENDULUM_R2D2.trainer, num_envs=6)
    with pytest.raises(ValueError, match="num_envs"):
        SPMDTrainer(env, agent, bad, mesh)


def test_axis_name_required():
    mesh = make_mesh(8)
    env = PENDULUM_R2D2.env_factory()
    actor = ActorNet(action_dim=1, hidden=8, use_lstm=True)
    critic = CriticNet(hidden=8, use_lstm=True)
    agent = R2D2DPG(actor, critic, PENDULUM_R2D2.agent)  # no axis_name
    with pytest.raises(ValueError, match="axis_name"):
        SPMDTrainer(env, agent, PENDULUM_R2D2.trainer, mesh)


def test_graft_entry_dryrun():
    """The driver's multi-chip dry run must pass on the CPU mesh."""
    import __graft_entry__

    __graft_entry__.dryrun_multichip(8)


# ------------------------------------------------------- multi-host wrapper
def test_distributed_initialize_noop_and_global_mesh(monkeypatch):
    from r2d2dpg_tpu.parallel import DP_AXIS, distributed

    # No cluster env, CPU backend: must be a silent no-op.
    for var in ("JAX_COORDINATOR_ADDRESS", "JAX_NUM_PROCESSES", "JAX_PROCESS_ID"):
        monkeypatch.delenv(var, raising=False)
    distributed.initialize()
    assert jax.process_count() == 1
    assert distributed.is_primary()

    mesh = distributed.global_mesh()
    assert mesh.shape[DP_AXIS] == len(jax.devices())


def test_distributed_initialize_already_up_is_noop(monkeypatch):
    from r2d2dpg_tpu.parallel import distributed

    # Simulate an already-initialized multi-process runtime: must return
    # before touching jax.distributed.initialize.
    monkeypatch.setattr(
        jax._src.distributed.global_state, "client", object(), raising=False
    )
    monkeypatch.setenv("JAX_COORDINATOR_ADDRESS", "localhost:1234")

    def boom(**kw):  # pragma: no cover - called only on regression
        raise AssertionError("re-initialized a live distributed runtime")

    monkeypatch.setattr(jax.distributed, "initialize", boom)
    distributed.initialize()


def test_distributed_single_host_tpu_worker_hostnames_is_noop(monkeypatch):
    # TPU_WORKER_HOSTNAMES=localhost is set even on a single-host box; a
    # single worker must not trigger pod bring-up.
    from r2d2dpg_tpu.parallel import distributed

    for var in ("JAX_COORDINATOR_ADDRESS", "MEGASCALE_COORDINATOR_ADDRESS"):
        monkeypatch.delenv(var, raising=False)
    monkeypatch.setenv("TPU_WORKER_HOSTNAMES", "localhost")

    def boom(**kw):  # pragma: no cover - called only on regression
        raise AssertionError("brought up distributed runtime on single host")

    monkeypatch.setattr(jax.distributed, "initialize", boom)
    distributed.initialize()
