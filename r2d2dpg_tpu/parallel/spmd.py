"""SPMD trainer: the whole Anakin loop under ``shard_map`` over a device mesh.

Reference parity: SURVEY.md §2.8/§5.8 — the reference's only parallelism is N
actor processes on one host feeding one learner over queues; its
"communication backend" is multiprocessing + pickle + shared memory.  The
TPU-native equivalent (BASELINE north star: "actor->learner trajectory
shipping and gradient sync go over ICI via pmap/psum"):

- the env fleet, window assembler, and replay arena shard over the ``dp``
  mesh axis (each chip owns ``num_envs/D`` actors and ``capacity/D`` replay
  slots — replay-server parallelism, SURVEY §2.8 last row);
- trajectories *never move*: a sequence is assembled and stored on the chip
  whose envs produced it, so the experience path costs zero ICI traffic
  (vs. the reference's pickle-over-queue per sequence);
- the learner is data-parallel: each chip samples from its local arena shard
  and gradients are ``pmean``-ed over ICI (``AgentConfig.axis_name``);
- per-actor exploration stays *globally* heterogeneous: each chip slices its
  rows of the global sigma ladder by ``axis_index`` (SURVEY §2.3's ladder);
- everything else (params, optimizer state, counters, RNG) is replicated,
  kept consistent by construction (pmean'd grads, psum'd counters).

The same program runs on a degenerate 1-device mesh, the CI CPU mesh
(8 virtual devices), a v4-8 ICI ring, or multi-host DCN — only the Mesh
changes (SURVEY §4.4's "distributed-without-a-cluster" strategy).
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from r2d2dpg_tpu.agents.ddpg import R2D2DPG
from r2d2dpg_tpu.envs.core import Environment
from r2d2dpg_tpu.parallel.mesh import DP_AXIS
from r2d2dpg_tpu.replay.arena import ArenaState
from r2d2dpg_tpu.training.trainer import Trainer, TrainerConfig, TrainerState


def _state_spec() -> TrainerState:
    """PartitionSpec prefix-tree for TrainerState under the ``dp`` mesh."""
    dp, rep = P(DP_AXIS), P()
    return TrainerState(
        env_state=dp,
        obs=dp,
        reset=dp,
        actor_carry=dp,
        critic_carry=dp,
        noise_state=dp,
        window=dp,
        arena=ArenaState(
                data=dp, priority=dp, cursor=rep, total_added=rep, meta=dp
            ),
        train=rep,
        behavior_params=rep,
        rng=rep,
        phase_idx=rep,
        env_steps=rep,
        episode_return=dp,
        completed_return_sum=rep,
        completed_count=rep,
    )


class SPMDTrainer(Trainer):
    """Trainer whose phases run under ``shard_map`` on a ``dp`` mesh.

    ``config`` is *global* (fleet-wide env count, global batch size, total
    replay capacity); each device runs the base Trainer's logic on its
    ``1/D`` shard, coupled only through the gradient/metric collectives.
    """

    axis = DP_AXIS

    def __init__(
        self,
        env: Environment,
        agent: R2D2DPG,
        config: TrainerConfig,
        mesh: Mesh,
    ):
        if getattr(env, "batched", False):
            raise ValueError(
                "SPMDTrainer does not support host-callback (batched) envs: "
                "ordered io_callback cannot run under shard_map. Multi-chip "
                "host-env pools need one pool per host (see docs/PARITY.md)."
            )
        if agent.config.axis_name != DP_AXIS:
            raise ValueError(
                "SPMDTrainer requires AgentConfig.axis_name == "
                f"{DP_AXIS!r} so learner gradients sync over the mesh "
                f"(got {agent.config.axis_name!r})"
            )
        d = mesh.shape[DP_AXIS]
        for field in ("num_envs", "batch_size", "capacity", "min_replay"):
            if getattr(config, field) % d:
                raise ValueError(
                    f"TrainerConfig.{field}={getattr(config, field)} must "
                    f"be divisible by the mesh size {d}"
                )
        self.mesh = mesh
        self.num_devices = d
        self.global_config = config
        local = dataclasses.replace(
            config,
            num_envs=config.num_envs // d,
            batch_size=config.batch_size // d,
            capacity=config.capacity // d,
            min_replay=config.min_replay // d,
        )
        super().__init__(env, agent, local)
        self.global_envs = config.num_envs

    def _build_phases(self):
        spec = _state_spec()
        mesh = self.mesh

        def wrap(fn, out_specs):
            mapped = jax.shard_map(
                fn, mesh=mesh, in_specs=(spec,), out_specs=out_specs,
                check_vma=False,
            )
            return jax.jit(mapped, donate_argnums=(0,))

        self.collect_phase = wrap(self._collect_phase, spec)
        self.fill_phase = wrap(self._fill_phase, spec)
        self.train_phase = wrap(self._train_phase, (spec, P()))

    # ------------------------------------------------------------------ init
    def init(self, key: Optional[jax.Array] = None) -> TrainerState:
        """Build the *global* state on host, then lay it out over the mesh."""
        local_cfg = self.config
        try:
            # Trainer.init sizes everything from self.config and the arena's
            # capacity; use the global ones so the sharded axes have their
            # full extent.  The arena stays this one: ``init_state`` is where
            # it records the shapes of the rows it stores as tiles, which
            # every device's ``sample`` needs.
            self.config = self.global_config
            self.arena.capacity = self.global_config.capacity
            state = super().init(key)
        finally:
            self.config = local_cfg
            self.arena.capacity = local_cfg.capacity

        shardings = jax.tree_util.tree_map(
            lambda s: NamedSharding(self.mesh, s),
            _state_spec(),
            is_leaf=lambda x: isinstance(x, P),
        )
        return jax.device_put(state, shardings)
