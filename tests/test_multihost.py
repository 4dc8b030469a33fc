"""Multi-host (multi-process) training of host-pool envs (SURVEY.md §5.8).

Launches TWO real OS processes joined through ``jax.distributed`` on CPU
(2 virtual devices each -> a 4-device global dp mesh) and runs warm-up,
fill and train phases of ``HostSPMDTrainer`` at tiny walker shapes: each
process owns a 2-env MuJoCo pool, fresh observations re-enter the mesh via
``jax.make_array_from_process_local_data``, and the jitted phases execute
as lockstep SPMD with gradient sync over the simulated DCN.

This is the closest a single box gets to a pod: real process boundary, real
collective runtime, real per-host env pools.
"""

import os
import socket
import subprocess
import sys

import pytest

pytestmark = pytest.mark.slow  # two concurrent JAX compiles on one core

_WORKER = r"""
import dataclasses, os, sys

import jax

jax.config.update("jax_platforms", "cpu")
jax.distributed.initialize(
    coordinator_address=os.environ["COORD"],
    num_processes=2,
    process_id=int(os.environ["RANK"]),
)
assert jax.process_count() == 2
assert len(jax.devices()) == 4  # 2 local x 2 processes

import numpy as np

from r2d2dpg_tpu.configs import WALKER_R2D2
from r2d2dpg_tpu.parallel import DP_AXIS, HostSPMDTrainer, make_mesh

cfg = dataclasses.replace(
    WALKER_R2D2,
    trainer=dataclasses.replace(
        WALKER_R2D2.trainer,
        num_envs=4,       # 2 per process
        stride=4,
        batch_size=4,
        capacity=64,
        min_replay=4,
        learner_steps=1,
        overlap_learner=bool(int(os.environ.get("OVERLAP", "0"))),
    ),
    hidden=32,
    agent=dataclasses.replace(WALKER_R2D2.agent, burnin=2, unroll=4, n_step=2),
)
mesh = make_mesh(4)
trainer = cfg.build_spmd(mesh)
assert isinstance(trainer, HostSPMDTrainer)
assert trainer._nproc == 2

state = trainer.init()
# The fleet is laid out over the GLOBAL mesh; this process addresses only
# its half of the rows.
assert state.obs.shape[0] == 4
assert sum(s.data.shape[0] for s in state.obs.addressable_shards) == 2

for _ in range(trainer.window_fill_phases):
    state = trainer.collect_phase(state)
state = trainer.fill_phase(state)
assert int(trainer.arena.size(state.arena)) == 4
state, metrics = trainer.train_phase(state)
assert int(state.train.step) == 1
for k, v in metrics.items():
    assert np.isfinite(float(v)), (k, metrics)
assert int(state.env_steps) == (trainer.window_fill_phases + 2) * 4 * 4

# Params identical across the global mesh after the synced update.
leaf = jax.tree_util.tree_leaves(state.train.critic_params)[0]
assert leaf.sharding.is_fully_replicated
shards = [np.asarray(s.data) for s in leaf.addressable_shards]
for other in shards[1:]:
    np.testing.assert_array_equal(shards[0], other)

print(f"RANK{os.environ['RANK']}_OK", flush=True)
"""


_SPMD_WORKER = r"""
import dataclasses, os

import jax

jax.config.update("jax_platforms", "cpu")
jax.distributed.initialize(
    coordinator_address=os.environ["COORD"],
    num_processes=2,
    process_id=int(os.environ["RANK"]),
)
assert len(jax.devices()) == 4

import numpy as np

from r2d2dpg_tpu.agents import AgentConfig, R2D2DPG
from r2d2dpg_tpu.configs import PENDULUM_R2D2
from r2d2dpg_tpu.models import ActorNet, CriticNet
from r2d2dpg_tpu.parallel import DP_AXIS, SPMDTrainer, make_mesh

env = PENDULUM_R2D2.env_factory()
agent_cfg = dataclasses.replace(
    PENDULUM_R2D2.agent, burnin=2, unroll=4, n_step=2, axis_name=DP_AXIS
)
agent = R2D2DPG(
    ActorNet(action_dim=env.spec.action_dim, hidden=16, use_lstm=True),
    CriticNet(hidden=16, use_lstm=True),
    agent_cfg,
)
tcfg = dataclasses.replace(
    PENDULUM_R2D2.trainer,
    num_envs=4, stride=4, batch_size=8, capacity=32, min_replay=4,
    learner_steps=1,
)
trainer = SPMDTrainer(env, agent, tcfg, make_mesh(4))
state = trainer.run(
    trainer.window_fill_phases + trainer.replay_fill_phases + 2, log_every=0
)
assert int(state.train.step) == 2
# Gradient pmean crossed the process boundary: params replicated identical.
leaf = jax.tree_util.tree_leaves(state.train.critic_params)[0]
shards = [np.asarray(s.data) for s in leaf.addressable_shards]
for other in shards[1:]:
    np.testing.assert_array_equal(shards[0], other)
print(f"RANK{os.environ['RANK']}_OK", flush=True)
"""


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _run_two_process(worker: str, extra_env=None):
    port = _free_port()
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    procs = []
    for rank in range(2):
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
        env["R2D2DPG_PALLAS_INTERPRET"] = "1"
        env["COORD"] = f"127.0.0.1:{port}"
        env["RANK"] = str(rank)
        env.update(extra_env or {})
        procs.append(
            subprocess.Popen(
                [sys.executable, "-c", worker],
                env=env,
                cwd=repo,
                stdout=subprocess.PIPE,
                stderr=subprocess.STDOUT,
                text=True,
            )
        )
    outs = []
    try:
        for p in procs:
            out, _ = p.communicate(timeout=900)
            outs.append(out)
    except subprocess.TimeoutExpired:
        for p in procs:
            p.kill()
        pytest.fail("multi-process run timed out:\n" + "\n".join(outs))
    for rank, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"rank {rank} failed:\n{out[-4000:]}"
        assert f"RANK{rank}_OK" in out


@pytest.mark.parametrize("overlap", [0, 1])
def test_two_process_host_pool_training(overlap):
    _run_two_process(_WORKER, {"OVERLAP": str(overlap)})


def test_two_process_spmd_training():
    """Pure-JAX env path (shard_map) across a real process boundary."""
    _run_two_process(_SPMD_WORKER)
