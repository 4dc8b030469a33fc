"""Multi-chip training for host-backed (dm_control) env pools.

Reference parity: SURVEY.md §2.8 / §5.8.  The pure-JAX ``SPMDTrainer`` runs
whole phases under ``shard_map``, which cannot contain the ordered
``io_callback`` a host env pool needs.  This trainer closes that gap (the
"known delta #3" of docs/PARITY.md) with the pjit layout style instead:

- every device-resident piece — policy forward, exploration noise, window
  assembler, HBM replay arena, the full learner step — runs under ``jit``
  on arrays laid out over the ``dp`` mesh axis via ``NamedSharding``
  (envs, window, arena, and batch sharded; params replicated);
- gradient synchronization needs no explicit collective: with replicated
  params and a dp-sharded batch, XLA inserts the ``psum`` over ICI on its
  own (the pjit/GSPMD recipe — pick a mesh, annotate shardings, let XLA
  place collectives);
- only the MuJoCo physics step leaves the device: once per collected agent
  step the [E, act] actions cross to host, the C++/Python pool steps all E
  envs, and the [E, obs] batch crosses back, sharded straight onto the mesh.

On one host this trains the DM-Control configs across all local chips.
Multi-host (DCN): each process owns a pool of ``num_envs/process_count``
envs; actions are read from this process's addressable shards, fresh obs
re-enter the mesh via ``jax.make_array_from_process_local_data``, and the
jitted phases run as ordinary multi-process SPMD (every host dispatches the
same computation; XLA routes the gradient/arena collectives over ICI within
a host and DCN across).  Bring-up is ``parallel.distributed.initialize()``;
``tests/test_multihost.py`` validates the full path with two real processes
on a CPU mesh.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from r2d2dpg_tpu.agents.ddpg import R2D2DPG
from r2d2dpg_tpu.envs.dmc_host import DMCHostEnv
from r2d2dpg_tpu.parallel.mesh import (
    DP_AXIS,
    constrain_batch_sharded,
    constrain_replicated,
)
from r2d2dpg_tpu.parallel.spmd import _state_spec
from r2d2dpg_tpu.training.assembler import StepRecord, shift_in
from r2d2dpg_tpu.training.trainer import Trainer, TrainerConfig, TrainerState
from r2d2dpg_tpu.utils.profiling import annotate, timed


class HostSPMDTrainer(Trainer):
    """dp-sharded training with the env fleet stepped from the host.

    ``config`` is global (fleet-wide env count, global batch size, total
    capacity); jitted functions see global shapes and XLA splits the work
    across the mesh from the array shardings.
    """

    axis = None  # pjit style: no named axis, XLA inserts the collectives

    def __init__(
        self,
        env: DMCHostEnv,
        agent: R2D2DPG,
        config: TrainerConfig,
        mesh: Mesh,
    ):
        if not getattr(env, "batched", False) or not hasattr(env, "host_step"):
            raise ValueError(
                "HostSPMDTrainer is for host-pool envs (DMCHostEnv); pure-JAX "
                "envs scale with parallel.SPMDTrainer instead"
            )
        if agent.config.axis_name is not None:
            raise ValueError(
                "HostSPMDTrainer uses pjit-style gradient sync; build the "
                "agent with axis_name=None (got "
                f"{agent.config.axis_name!r})"
            )
        self._nproc = jax.process_count()
        if config.num_envs % max(self._nproc, 1):
            raise ValueError(
                f"TrainerConfig.num_envs={config.num_envs} must be divisible "
                f"by the process count {self._nproc} (one env pool per host, "
                f"each owning num_envs/process_count envs)"
            )
        d = mesh.shape[DP_AXIS]
        # The arena is replicated (see layout note in _build_phases), so only
        # the genuinely dp-sharded axes need to divide the mesh.
        for field in ("num_envs", "batch_size"):
            if getattr(config, field) % d:
                raise ValueError(
                    f"TrainerConfig.{field}={getattr(config, field)} must "
                    f"be divisible by the mesh size {d}"
                )
        self.mesh = mesh
        self.num_devices = d
        super().__init__(env, agent, config)
        # Arena buffers carry explicit mesh shardings -> XLA scatter path.
        self.arena.use_pallas = False
        # The one host<->device boundary per collected step, as seen from
        # the stride loop (pool physics + numpy marshalling); the pool's
        # own r2d2dpg_envpool_step_seconds isolates the physics share.
        from r2d2dpg_tpu.obs import get_registry

        self._obs_host_step = get_registry().histogram(
            "r2d2dpg_hybrid_host_env_step_seconds",
            "host env-step boundary latency in the hybrid stride loop",
        )

    # --------------------------------------------------------------- builds
    def _build_phases(self):
        mesh = self.mesh
        # Layout deltas vs the shard_map spec: the host pool owns the real
        # env state (the device token is a scalar -> replicated), and the
        # replay arena is REPLICATED rather than capacity-sharded — per-chip
        # memory equals the single-chip arena, global adds cost one small
        # all-gather of E fresh sequences per phase, and every chip samples
        # the same global batch whose compute is then resharded over dp
        # (``_reshard_batch``).  This keeps the arena's gather/scatter free
        # of cross-shard index collectives.
        from r2d2dpg_tpu.replay.arena import ArenaState

        spec = dataclasses.replace(
            _state_spec(),
            env_state=P(),
            arena=ArenaState(
                data=P(), priority=P(), cursor=P(), total_added=P(), meta=P()
            ),
        )
        self._shardings = jax.tree_util.tree_map(
            lambda s: NamedSharding(mesh, s),
            spec,
            is_leaf=lambda x: isinstance(x, P),
        )
        self._replicated = NamedSharding(mesh, P())
        self._dp1 = NamedSharding(mesh, P(DP_AXIS))  # [E, ...] leading axis
        self._dp2 = NamedSharding(mesh, P(None, DP_AXIS))  # [T, E] stacks
        self._act_step = jax.jit(self._act_step_impl)
        # One dispatch per phase instead of one jnp.where per param leaf
        # (ADVICE r1: _behavior_params evaluated eagerly was pure host-loop
        # overhead on the hot collect path).
        self._collect_setup = jax.jit(self._collect_setup_impl)
        # No donation: the state's obs/reset/carry buffers are also passed
        # as the t=0 entries of the per-step tuples (f(donate(a), a) is
        # rejected by PJRT on real devices).
        self._absorb = jax.jit(self._absorb_impl)
        self._emit_learn = jax.jit(self._emit_learn_impl, donate_argnums=(0,))
        self._emit_only = jax.jit(self._emit_and_add, donate_argnums=(0,))
        # Overlapped-learner substep (one prioritized update).  NO donation:
        # while substeps run, the phase's TrainerState pytree still holds
        # references to the pre-substep train/arena buffers (they ride
        # through _absorb), so donating here would invalidate live inputs.
        # Cost of out-of-place: a fresh [capacity] priority array + param
        # trees per substep — small next to the arena data, which passes
        # through update_priorities untouched (and uncopied).
        self._learn_substep = jax.jit(self._learn_substep_impl)

    # ----------------------------------------------------------------- init
    def _env_reset(self, key: jax.Array):
        """Each process resets only its LOCAL slice of the fleet (its own
        pool), with a process-diversified key so seeds differ across hosts."""
        if self._nproc > 1:
            key = jax.random.fold_in(key, jax.process_index())
        return self.env.reset(key, self.config.num_envs)

    def init(self, key: Optional[jax.Array] = None) -> TrainerState:
        if self._nproc == 1:
            state = super().init(key)  # eager io_callback reset fills the pool
            return jax.device_put(state, self._shardings)
        # Multi-host (SURVEY §5.8 / docs/PARITY.md delta #3): build a state
        # with LOCAL fleet shapes (num_envs/process_count envs in this
        # process's pool; params/arena/counters are process-identical since
        # every host runs the same seed), then assemble the global
        # TrainerState — dp-sharded leaves from each process's local rows,
        # replicated leaves from the (identical) local values.
        saved = self.config
        try:
            # Temporary local view ONLY for the eager init body; the jitted
            # phase functions trace later, against the restored global config.
            self.config = dataclasses.replace(
                saved, num_envs=saved.num_envs // self._nproc
            )
            local = super().init(key)
        finally:
            self.config = saved

        def to_global(leaf, sharding):
            arr = np.asarray(leaf)
            spec = sharding.spec
            if any(ax == DP_AXIS for ax in spec):
                gshape = tuple(
                    dim * self._nproc
                    if i < len(spec) and spec[i] == DP_AXIS
                    else dim
                    for i, dim in enumerate(arr.shape)
                )
                return jax.make_array_from_process_local_data(
                    sharding, arr, gshape
                )
            return jax.make_array_from_callback(
                arr.shape, sharding, lambda idx: arr[idx]
            )

        # ``self._shardings`` is a PREFIX pytree (one sharding can span a
        # whole subtree, as device_put accepts); broadcast it to the full
        # state structure before zipping leaf-wise.
        full_shardings = jax.tree_util.tree_broadcast(
            self._shardings,
            local,
            is_leaf=lambda x: isinstance(x, NamedSharding),
        )
        return jax.tree_util.tree_map(to_global, local, full_shardings)

    # --------------------------------------------------------- device parts
    def _collect_setup_impl(self, state: TrainerState):
        """Per-phase device prep: behavior snapshot + the stride's RNG keys.

        With ``param_sync_every > 0`` the snapshot must also PERSIST (the
        base trainer stores it before collecting so the params acted with
        are exactly the ones carried until the next sync phase); returning
        the updated state from here keeps that store inside this one jitted
        dispatch instead of an eager per-leaf ``jnp.where`` in train_phase.
        """
        rng, sk, sl = jax.random.split(state.rng, 3)
        keys = jax.random.split(sk, self.config.stride)
        lkeys = jax.random.split(sl, max(self.config.learner_steps, 1))
        behavior = self._behavior_params(state)
        if self.config.param_sync_every > 0:
            state = dataclasses.replace(state, behavior_params=behavior)
        return state, behavior, keys, lkeys, rng

    def _learn_substep_impl(self, train, arena, key):
        """One prioritized learner update, dispatchable mid-collect (the
        shared ``Trainer._learn_step`` body, as a standalone jit)."""
        return self._learn_step(train, arena, key)

    def _act_step_impl(
        self, behavior, critic_params, obs, reset, a_carry, c_carry, noise_st,
        keys, t
    ):
        """One policy step for the whole fleet (the device half of hot loop A);
        the semantics live in Trainer._policy_step, shared with the in-graph
        scan collect.  ``keys`` is the phase's [stride, key] stack and ``t``
        a traced scalar so the per-step key gather happens in-graph (no eager
        host indexing per step)."""
        return self._policy_step(
            behavior, critic_params, obs, reset, a_carry, c_carry, noise_st,
            self._local_sigmas(), keys[t],
        )

    def _absorb_impl(
        self,
        state: TrainerState,
        obs_T: Tuple[jnp.ndarray, ...],  # T x [E, obs] — pre-step obs
        reset_T: Tuple[jnp.ndarray, ...],  # T x [E] — pre-step reset flags
        act_T: Tuple[jnp.ndarray, ...],  # T x [E, A]
        a_car_T: Tuple[Any, ...],  # T x carry — pre-step carries
        c_car_T: Tuple[Any, ...],
        rew_T: jnp.ndarray,  # [T, E] from host
        disc_T: jnp.ndarray,  # [T, E]
        done_T: jnp.ndarray,  # [T, E] post-step reset flags
        obs_next: jnp.ndarray,
        reset_next: jnp.ndarray,
        a_carry,
        c_carry,
        noise_st,
        rng,
    ) -> TrainerState:
        """Fold one phase of host-collected steps into the TrainerState."""
        cfg = self.config
        stack = lambda xs: jnp.stack(xs)  # noqa: E731 — time-major [T, E, ...]
        records = StepRecord(
            obs=stack(obs_T),
            action=stack(act_T),
            reward=rew_T,
            discount=disc_T,
            reset=stack(reset_T),
            carries={
                "actor": jax.tree_util.tree_map(lambda *xs: stack(xs), *a_car_T)
                if jax.tree_util.tree_leaves(a_car_T[0])
                else a_car_T[0],
                "critic": jax.tree_util.tree_map(lambda *xs: stack(xs), *c_car_T)
                if jax.tree_util.tree_leaves(c_car_T[0])
                else c_car_T[0],
            },
        )

        def ep_step(ep, inp):
            r, done = inp
            ep = ep + r
            completed = (jnp.where(done > 0, ep, 0.0).sum(), (done > 0).sum())
            return jnp.where(done > 0, 0.0, ep), completed

        ep_ret, (comp_sum, comp_cnt) = jax.lax.scan(
            ep_step, state.episode_return, (rew_T, done_T)
        )

        return dataclasses.replace(
            state,
            obs=obs_next,
            reset=reset_next,
            actor_carry=a_carry,
            critic_carry=c_carry,
            noise_state=noise_st,
            rng=rng,
            env_steps=state.env_steps + cfg.stride * self.global_envs,
            episode_return=ep_ret,
            completed_return_sum=state.completed_return_sum + comp_sum.sum(),
            completed_count=state.completed_count + comp_cnt.sum(),
            window=shift_in(state.window, records),
            phase_idx=state.phase_idx + 1,
        )

    def _emit_learn_impl(
        self, state: TrainerState
    ) -> Tuple[TrainerState, Dict[str, jnp.ndarray]]:
        return self._learn(self._emit_and_add(state))

    # ----------------------------------------------------------- reshards
    def _reshard_add(self, seq, prios):
        """Replicate the E fresh sequences + priorities for the (replicated)
        arena add — after initial_priority ran on the dp-sharded layout."""
        return constrain_replicated((seq, prios), self.mesh)

    def _reshard_batch(self, batch):
        """Shard the sampled batch over dp so learner compute splits and XLA
        psums the gradients (params replicated + batch sharded)."""
        return constrain_batch_sharded(batch, self.mesh)

    # ------------------------------------------------------------ host loop
    def _put_fleet(self, x: np.ndarray) -> jnp.ndarray:
        """Lay a host [E_local, ...] batch out over the dp mesh axis (global
        assembly across processes when multi-host)."""
        if self._nproc == 1:
            return jax.device_put(x, self._dp1)
        return jax.make_array_from_process_local_data(
            self._dp1, x, (x.shape[0] * self._nproc,) + x.shape[1:]
        )

    def _put_stack(self, x: np.ndarray) -> jnp.ndarray:
        """[T, E_local] time-major host stack onto the dp mesh axis (axis 1)."""
        if self._nproc == 1:
            return jax.device_put(x, self._dp2)
        return jax.make_array_from_process_local_data(
            self._dp2, x, (x.shape[0], x.shape[1] * self._nproc)
        )

    def _fetch_fleet(self, arr: jnp.ndarray) -> np.ndarray:
        """Device [E, ...] fleet array -> THIS process's rows as numpy."""
        if self._nproc == 1:
            return np.asarray(arr)
        shards = sorted(
            arr.addressable_shards,
            key=lambda s: s.index[0].start if s.index[0].start else 0,
        )
        return np.concatenate([np.asarray(s.data) for s in shards], axis=0)

    def _stride_loop(
        self, state, behavior, critic_params, keys, rng, on_step=None
    ):
        """THE host stride loop: per-step policy dispatch -> action fetch ->
        optional ``on_step(t)`` hook -> batched MuJoCo step -> obs re-entry,
        then one jitted absorb of the whole phase.

        Shared by ``_host_collect`` (hook = the overlap_learner substep
        dispatch) and the pipelined executor's collector thread
        (training/pipeline.py: a ``CollectorState`` and no hook), so the
        fleet stacking / episode bookkeeping cannot drift between the two
        schedules — ``_act_step``/``_absorb`` touch only the env-side
        fields both state pytrees share."""
        obs, reset = state.obs, state.reset
        a_carry, c_carry = state.actor_carry, state.critic_carry
        noise_st = state.noise_state
        obs_T, reset_T, act_T, a_car_T, c_car_T = [], [], [], [], []
        rew_T, disc_T, done_T = [], [], []

        for t in range(self.config.stride):
            obs_T.append(obs)
            reset_T.append(reset)
            a_car_T.append(a_carry)
            c_car_T.append(c_carry)
            action, a_carry, c_carry, noise_st = self._act_step(
                behavior, critic_params, obs, reset, a_carry, c_carry,
                noise_st, keys, np.int32(t),
            )
            act_T.append(action)
            action_np = self._fetch_fleet(action)
            if on_step is not None:
                on_step(t)
            # ═══ the one host<->device boundary per collected step ═══
            with timed(self._obs_host_step), annotate("hybrid/host_env_step"):
                o, r, d, res = self.env.host_step(action_np)
            rew_T.append(r)
            disc_T.append(d)
            done_T.append(res)
            obs = self._put_fleet(o)
            reset = self._put_fleet(res)

        with annotate("hybrid/absorb"):
            return self._absorb(
                state,
                tuple(obs_T),
                tuple(reset_T),
                tuple(act_T),
                tuple(a_car_T),
                tuple(c_car_T),
                self._put_stack(np.stack(rew_T)),
                self._put_stack(np.stack(disc_T)),
                self._put_stack(np.stack(done_T)),
                obs,
                reset,
                a_carry,
                c_carry,
                noise_st,
                rng,
            )

    def _host_collect(
        self, state: TrainerState, learn: bool = False
    ) -> Tuple[TrainerState, Optional[Dict[str, jnp.ndarray]]]:
        """Step the fleet ``stride`` times from the host.

        With ``learn=True`` (the ``overlap_learner`` train path) the phase's
        ``learner_steps`` updates are dispatched one at a time BETWEEN env
        steps, spread evenly over the stride: each update executes on the
        device during the milliseconds the host spends inside the MuJoCo C
        step, so on a real TPU the learner costs ~zero wall-clock.  The
        device queue orders act_step(t+1) after the interleaved update, but
        by the time the host finishes physics for step t the update has
        drained — max(host, device) instead of host + device.

        Semantics delta vs the sequential path (intentional, documented):
        interleaved updates sample the arena as of the PREVIOUS emit — the
        sequence collected this phase enters replay after the phase's
        updates.  That one-phase sampling lag is exactly the reference's
        async actor/learner relationship (its learner never sees in-flight
        actor data either).
        """
        cfg = self.config
        state, behavior, keys, lkeys, rng = self._collect_setup(state)
        critic_params = self.agent.behavior_critic_params(state.train)
        train, arena = state.train, state.arena
        n_sub = cfg.learner_steps if learn else 0
        sub = 0
        metrics_acc = []

        def dispatch_substeps(t: int) -> None:
            # Dispatch this step's share of learner updates AFTER the action
            # crossed to host (so act_step never waits behind an update) and
            # BEFORE the physics step (so the update runs under it).
            nonlocal train, arena, sub
            while sub < n_sub and (sub + 1) * cfg.stride <= (t + 1) * n_sub:
                with annotate("hybrid/learn_substep"):
                    train, arena, m = self._learn_substep(
                        train, arena, lkeys[sub]
                    )
                metrics_acc.append(m)
                sub += 1

        state = self._stride_loop(
            state, behavior, critic_params, keys, rng,
            on_step=dispatch_substeps if n_sub else None,
        )
        if not learn:
            return state, None
        state = dataclasses.replace(state, train=train, arena=arena)
        if not metrics_acc:  # learner_steps=0: a collect-only train phase
            return state, {}
        metrics = jax.tree_util.tree_map(
            lambda *xs: jnp.stack(xs).mean(), *metrics_acc
        )
        return state, metrics

    # --------------------------------------------------------------- phases
    def collect_phase(self, state: TrainerState) -> TrainerState:
        state, _ = self._host_collect(state)
        return state

    def fill_phase(self, state: TrainerState) -> TrainerState:
        state, _ = self._host_collect(state)
        return self._emit_only(state)

    def train_phase(
        self, state: TrainerState
    ) -> Tuple[TrainerState, Dict[str, jnp.ndarray]]:
        # Behavior-snapshot persistence happens inside _collect_setup (jit).
        if not self.config.overlap_learner:
            state, _ = self._host_collect(state)
            with annotate("hybrid/emit_learn"):
                return self._emit_learn(state)
        state, metrics = self._host_collect(state, learn=True)
        with annotate("hybrid/emit_add"):
            return self._emit_only(state), metrics
