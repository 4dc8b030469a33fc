"""The Anakin-style trainer: actor phase + learner phase as one device program.

Reference parity: SURVEY.md §2.5 / §3.1 — the reference's ``main.py`` spawns
N actor processes and a learner wired by ``multiprocessing.Queue``s.  Here
the topology dissolves (SURVEY §7 "design inversion", PAPERS.md 2104.06272):

- the actor pool     -> a vmapped env batch stepped inside ``lax.scan``;
- the exp queue      -> the window assembler + an in-graph ``arena.add``;
- the param channel  -> the behavior-params snapshot (see staleness knob);
- the learner proc   -> ``learner_steps`` jitted updates per phase;
- warm-up gating     -> a *static* phase schedule (window-fill phases, then
                        replay-fill phases, then full train phases), so no
                        data-dependent control flow enters the jit graphs.

Phases:
  ``collect_phase``  env stepping + window shift only (warm-up).
  ``fill_phase``     + sequence emission into the replay arena.
  ``train_phase``    + K learner steps with prioritized sampling, IS
                     weights, priority write-back, Polyak updates.

Off-policy lag (SURVEY §7 hard part 4): with ``param_sync_every == 0``
actors always use fresh params (Anakin default — *less* lag than the
reference's stale-param actors).  Setting it to K > 0 reproduces reference
fidelity: behavior params refresh from learner params every K phases,
in-graph.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from r2d2dpg_tpu.agents.ddpg import R2D2DPG, TrainState
from r2d2dpg_tpu.envs.core import Environment
from r2d2dpg_tpu.ops import anneal_beta, gaussian_noise, importance_weights, ou_step, sigma_ladder
from r2d2dpg_tpu.replay.arena import ArenaState, ReplayArena, SequenceBatch
from r2d2dpg_tpu.training.assembler import StepRecord, emit, init_window, shift_in
from r2d2dpg_tpu.utils.metrics import host_scalars
from r2d2dpg_tpu.utils.profiling import annotate, scope


@dataclasses.dataclass(frozen=True)
class TrainerConfig:
    """Static orchestration hyperparameters (SURVEY §2.5)."""

    num_envs: int = 64
    stride: int = 20  # env steps per phase == emission stride
    learner_steps: int = 1  # learner updates per phase
    batch_size: int = 64
    capacity: int = 100_000
    prioritized: bool = True
    priority_alpha: float = 0.6
    beta0: float = 0.4
    beta_steps: int = 100_000
    min_replay: int = 1_000  # sequences before training starts
    sigma_max: float = 0.4
    ladder_alpha: float = 7.0
    ladder_kind: str = "geometric"
    noise: str = "gaussian"  # "gaussian" | "ou" | "none"
    param_sync_every: int = 0  # 0 = always-fresh behavior params (Anakin)
    initial_priority: str = "td"  # "td" | "max"  (SURVEY §2.2 initial priority)
    # Host-pool trainers only: dispatch the phase's learner steps one at a
    # time BETWEEN env steps, so each update executes on-device while the
    # host is inside the MuJoCo C step — the learner rides free under the
    # env pool instead of serializing after it (VERDICT r1 next-step #3).
    # Semantics delta (documented in parallel/hybrid.py): learner sampling
    # lags one emit, exactly the reference's async actor/learner relation.
    overlap_learner: bool = False
    seed: int = 0


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class TrainerState:
    """Everything the training program threads through phases (one pytree)."""

    env_state: Any  # vmapped env states [E, ...]
    obs: jnp.ndarray  # [E, obs]
    reset: jnp.ndarray  # [E] — 1 where obs starts a new episode
    actor_carry: Any
    critic_carry: Any
    noise_state: jnp.ndarray  # [E, A] (OU process state; zeros for gaussian)
    window: StepRecord
    arena: ArenaState
    train: TrainState
    behavior_params: Any  # stale actor params (== train.actor_params when fresh)
    rng: jax.Array
    phase_idx: jnp.ndarray
    env_steps: jnp.ndarray
    episode_return: jnp.ndarray  # [E] running returns
    completed_return_sum: jnp.ndarray
    completed_count: jnp.ndarray


class Trainer:
    """Builds the jitted phase functions for (env, agent, config).

    Distribution hooks (overridden by ``parallel.SPMDTrainer``): ``axis``
    names the mesh axis the phases run under (None = single device);
    ``global_envs`` is the fleet-wide env count (== ``config.num_envs``
    locally); ``_local_sigmas`` returns this shard's slice of the global
    noise ladder; ``_psum``/``_fold_axis`` reduce/diversify across devices.
    """

    axis: Optional[str] = None

    def __init__(self, env: Environment, agent: R2D2DPG, config: TrainerConfig):
        self.env = env
        self.agent = agent
        self.config = config
        self.seq_len = agent.config.seq_len
        self.arena = ReplayArena(
            config.capacity,
            prioritized=config.prioritized,
            alpha=config.priority_alpha,
        )
        self.global_envs = config.num_envs
        # Telemetry (obs/): registration is idempotent, so repeated Trainer
        # constructions (tests, eval) share one instrument per name.
        from r2d2dpg_tpu.obs import get_registry
        from r2d2dpg_tpu.obs.device import get_device_monitor

        # The device plane (ISSUE 14): ONE process monitor shared by every
        # loop this trainer may run under — compile sentinel, HBM
        # gauges riding the log cadence via _obs_publish.
        self._device = get_device_monitor().install()
        reg = get_registry()
        self._obs_env_steps = reg.gauge(
            "r2d2dpg_trainer_env_steps", "fleet-wide env steps collected"
        )
        self._obs_learner_steps = reg.gauge(
            "r2d2dpg_trainer_learner_steps", "learner updates applied"
        )
        self._obs_return = reg.gauge(
            "r2d2dpg_trainer_episode_return_mean",
            "mean return of episodes completed since the previous log",
        )
        self._obs_episodes = reg.counter(
            "r2d2dpg_trainer_episodes_total", "episodes completed"
        )
        self._build_phases()

    def _build_phases(self):
        donate = dict(donate_argnums=(0,))
        self.collect_phase = jax.jit(self._collect_phase, **donate)
        self.fill_phase = jax.jit(self._fill_phase, **donate)
        self.train_phase = jax.jit(self._train_phase, **donate)

    # ----------------------------------------------------- distribution hooks
    def _local_sigmas(self) -> jnp.ndarray:
        """This device's slice of the global per-actor noise ladder."""
        sigmas = sigma_ladder(
            self.global_envs,
            sigma_max=self.config.sigma_max,
            alpha=self.config.ladder_alpha,
            kind=self.config.ladder_kind,
        )
        if self.axis is None:
            return sigmas
        idx = lax.axis_index(self.axis)
        return lax.dynamic_slice(
            sigmas, (idx * self.config.num_envs,), (self.config.num_envs,)
        )

    def _psum(self, x):
        """Sum a per-device partial across the mesh (identity single-device)."""
        return x if self.axis is None else lax.psum(x, self.axis)

    def _pmean(self, x):
        return x if self.axis is None else lax.pmean(x, self.axis)

    def _fold_axis(self, key: jax.Array) -> jax.Array:
        """Diversify an (otherwise replicated) RNG key per device."""
        if self.axis is None:
            return key
        return jax.random.fold_in(key, lax.axis_index(self.axis))

    def _reshard_add(self, seq, prios):
        """Hook: relayout emitted sequences + priorities before arena.add.

        Runs AFTER the initial-priority computation so that expensive
        forward stays in the sequences' collected layout (dp-sharded in the
        hybrid trainer) rather than being replicated."""
        return seq, prios

    def _reshard_batch(self, batch):
        """Hook: relayout a sampled batch before the learner step."""
        return batch

    def _put_staged(self, staged, axis: int = 0):
        """Hook: place a host-side batch tree (numpy leaves) for a
        compiled program.  Identity here — jit's implicit device_put; the
        dp learner lays the batch out over its mesh instead
        (parallel/dp_learner.py, the hybrid trainer's ``_put_fleet``
        idiom), so fleet payloads enter the sharded drain pre-placed.

        ``axis`` names the batch dimension the dp mesh shards: 0 for
        staged fleet sequences (leaves ``[B, ...]``), 1 for the sampler
        learner's pulled batches (leaves ``[K, B, ...]`` — each dp slice
        receives its ``B/D`` rows at placement time, so the composed
        ``--actors x --replay-shards x --learner-dp`` run has no central
        reshard hop; docs/TOPOLOGY.md)."""
        return staged

    def _log_extra_refs(self, arena_state) -> list:
        """Hook: extra device refs to ride the log cadence's one batched
        ``device_get`` (no host syncs of their own).  The dp learner adds
        its per-shard occupancy vector here."""
        return []

    def _log_extra_publish(self, fetched) -> None:
        """Hook: fold the host values of ``_log_extra_refs`` onto the obs
        registry (called with the fetched tail of the batched get)."""

    # ------------------------------------------------------------------ init
    def _env_reset(self, key: jax.Array):
        """Hook: reset the whole fleet (overridden for multi-process pools,
        where each process may only reset its local slice)."""
        if getattr(self.env, "batched", False):
            return self.env.reset(key, self.config.num_envs)
        env_keys = jax.random.split(key, self.config.num_envs)
        return jax.vmap(self.env.reset)(env_keys)

    def init(self, key: Optional[jax.Array] = None) -> TrainerState:
        cfg = self.config
        key = jax.random.PRNGKey(cfg.seed) if key is None else key
        k_env, k_agent, k_run = jax.random.split(key, 3)

        env_state, ts = self._env_reset(k_env)

        e = cfg.num_envs
        a_dim = self.env.spec.action_dim
        example_action = jnp.zeros((e, a_dim))
        train = self.agent.init(k_agent, ts.obs, example_action)

        actor_carry = self.agent.actor.initial_carry(e)
        critic_carry = self.agent.critic.initial_carry(e)
        record = StepRecord(
            obs=ts.obs,
            action=example_action,
            reward=ts.reward,
            discount=ts.discount,
            reset=ts.reset,
            carries=self._stored_carries(actor_carry, critic_carry),
        )
        window = init_window(record, self.seq_len)

        example_seq = emit(window)
        arena_state = self.arena.init_state(example_seq)

        return TrainerState(
            env_state=env_state,
            obs=ts.obs,
            reset=ts.reset,
            actor_carry=actor_carry,
            critic_carry=critic_carry,
            noise_state=jnp.zeros((e, a_dim)),
            window=window,
            arena=arena_state,
            train=train,
            behavior_params=jax.tree_util.tree_map(jnp.copy, train.actor_params),
            rng=k_run,
            phase_idx=jnp.zeros((), jnp.int32),
            env_steps=jnp.zeros((), jnp.int64)
            if jax.config.jax_enable_x64
            else jnp.zeros((), jnp.int32),
            episode_return=jnp.zeros((e,)),
            completed_return_sum=jnp.zeros(()),
            completed_count=jnp.zeros(()),
        )

    # --------------------------------------------------------- phase pieces
    def _stored_carries(self, a_carry, c_carry) -> Dict[str, Any]:
        """What a sequence that starts at this step is stored with: the
        carries before the step, less what the actor's core keeps to itself
        (the sdar core's ring of keys and values is never stored)."""
        return {"actor": self.agent.actor.stored_carry(a_carry), "critic": c_carry}

    def _behavior_params(self, state: TrainerState):
        if self.config.param_sync_every == 0:
            return state.train.actor_params
        refresh = (state.phase_idx % self.config.param_sync_every) == 0
        return jax.tree_util.tree_map(
            lambda fresh, stale: jnp.where(refresh, fresh, stale),
            state.train.actor_params,
            state.behavior_params,
        )

    def _policy_step(
        self, behavior, critic_params, obs, reset, a_carry, c_carry, noise_st, sigmas, key
    ):
        """One fleet-wide policy step: action + noise + clip + carry advance.

        Shared by the in-graph scan collect (below) and the hybrid trainer's
        host-driven collect (parallel/hybrid.py) so noise/clip/reset
        semantics cannot drift between the single- and multi-chip paths.
        """
        cfg = self.config
        action, a_carry = self.agent.actor.apply(behavior, obs, a_carry, reset)
        if cfg.noise == "gaussian":
            action = action + gaussian_noise(key, action, sigmas)
        elif cfg.noise == "ou":
            noise_st = jnp.where(reset[:, None] > 0, 0.0, noise_st)
            noise_st = ou_step(key, noise_st, sigmas)
            action = action + noise_st
        action = jnp.clip(action, -1.0, 1.0)
        if jax.tree_util.tree_leaves(c_carry):  # a critic that carries a past
            _, c_carry = self.agent.critic.apply(
                critic_params, obs, action, c_carry, reset
            )
        return action, a_carry, c_carry, noise_st

    def _collect(
        self, state: TrainerState, behavior=None, critic_params=None
    ) -> TrainerState:
        """Scan ``stride`` vmapped env steps; returns time-major records.

        SURVEY §3.2's hot loop A, vectorized: policy forward (behavior
        params), exploration noise, env step, episode bookkeeping.  The
        critic also steps along so its recurrent state exists for storage
        (R2D2-DPG stores initial state for *both* nets' cores).

        ``behavior``/``critic_params`` default to the state's own train
        params (the phase-locked path).  The pipelined executor passes them
        explicitly: its collector state carries no learner subtree, and the
        snapshot must stay a non-donated program input so the learner's
        published params outlive the donated collector state
        (training/pipeline.py).
        """
        cfg = self.config
        if behavior is None:
            behavior = self._behavior_params(state)
        if critic_params is None:
            critic_params = self.agent.behavior_critic_params(state.train)
        sigmas = self._local_sigmas()
        rng, scan_key = jax.random.split(state.rng)
        scan_key = self._fold_axis(scan_key)

        def step(carry, key):
            env_state, obs, reset, a_carry, c_carry, noise_st, ep_ret = carry
            pre_carries = self._stored_carries(a_carry, c_carry)

            k_noise, k_env = jax.random.split(key)
            action, a_carry, c_carry, noise_st = self._policy_step(
                behavior, critic_params, obs, reset, a_carry, c_carry,
                noise_st, sigmas, k_noise,
            )

            if getattr(self.env, "batched", False):
                env_state, ts = self.env.step(env_state, action, k_env)
            else:
                env_keys = jax.random.split(k_env, cfg.num_envs)
                env_state, ts = jax.vmap(self.env.step)(
                    env_state, action, env_keys
                )

            record = StepRecord(
                obs=obs,
                action=action,
                reward=ts.reward,
                discount=ts.discount,
                reset=reset,
                carries=pre_carries,
            )
            ep_ret = ep_ret + ts.reward
            done = ts.reset > 0
            completed = (jnp.where(done, ep_ret, 0.0).sum(), done.sum())
            ep_ret = jnp.where(done, 0.0, ep_ret)
            carry = (env_state, ts.obs, ts.reset, a_carry, c_carry, noise_st, ep_ret)
            return carry, (record, completed)

        init = (
            state.env_state,
            state.obs,
            state.reset,
            state.actor_carry,
            state.critic_carry,
            state.noise_state,
            state.episode_return,
        )
        keys = jax.random.split(scan_key, cfg.stride)
        (env_state, obs, reset, a_carry, c_carry, noise_st, ep_ret), (
            records,
            (comp_sum, comp_cnt),
        ) = lax.scan(step, init, keys)

        state = dataclasses.replace(
            state,
            env_state=env_state,
            obs=obs,
            reset=reset,
            actor_carry=a_carry,
            critic_carry=c_carry,
            noise_state=noise_st,
            rng=rng,
            env_steps=state.env_steps + cfg.stride * self.global_envs,
            episode_return=ep_ret,
            completed_return_sum=state.completed_return_sum
            + self._psum(comp_sum.sum()),
            completed_count=state.completed_count + self._psum(comp_cnt.sum()),
            window=shift_in(state.window, records),
            phase_idx=state.phase_idx + 1,
        )
        return state

    def _initial_priorities(self, train, arena, seq) -> jnp.ndarray:
        """Entry priority for B fresh sequences (SURVEY §2.2 initial priority).

        Factored out of ``_emit_and_add`` so the pipelined executor's drain
        program — which holds only the learner subtree, not a full
        TrainerState — computes the same ranking the phase-locked path does."""
        if self.config.initial_priority == "td" and self.config.prioritized:
            return self.agent.initial_priority(train, seq)
        if self.config.prioritized:
            return jnp.full(
                (self.config.num_envs,),
                jnp.maximum(arena.priority.max(), 1.0),
            )
        return jnp.ones((self.config.num_envs,))

    def _emit_and_add(self, state: TrainerState) -> TrainerState:
        """Emit the window as one sequence per env and add with priority."""
        seq = emit(state.window)
        prios = self._initial_priorities(state.train, state.arena, seq)
        seq, prios = self._reshard_add(seq, prios)
        # In-process provenance (--actors 0): the LIVE nets collected this
        # window, so both meta columns carry the current learner step —
        # behavior version and entry stamp coincide (lag ~0 by
        # construction, replay age honest; obs/quality.py).
        meta = jnp.broadcast_to(
            state.train.step.astype(jnp.int32)[None, None],
            (prios.shape[0], 2),
        )
        arena = self.arena.add(state.arena, seq, prios, meta=meta)
        return dataclasses.replace(state, arena=arena)

    def _update_step(self, train, arena, res, key):
        """The update half of one learner step: IS weights -> gradient
        update -> priority write-back, on an already-sampled ``res``.
        Split from ``_learn_step`` so the prefetched learn path can draw
        batch k+1 before this step's write-back lands."""
        cfg = self.config
        # fold_in (not split) for the smoothing key: sampling keeps consuming
        # the substep key directly, so knobs-off runs draw the exact same
        # batch sequence as round 2 at a fixed seed (the folded key is DCE'd
        # from the graph when target_policy_sigma == 0).
        kl = jax.random.fold_in(key, 1)
        if cfg.prioritized:
            beta = anneal_beta(train.step, beta0=cfg.beta0, steps=cfg.beta_steps)
            w = importance_weights(res.probs, self.arena.size(arena), beta=beta)
        else:
            w = jnp.ones((cfg.batch_size,))
        train, prios, metrics = self.agent.learner_step(
            train, self._reshard_batch(res.batch), w, key=kl
        )
        if cfg.prioritized:
            with scope("priority_update"):
                arena = self.arena.update_priorities(
                    arena, res.indices, prios
                )
        # Experience-quality gauges (obs/quality.py) from values ALREADY
        # in the graph — they ride the metrics dict to the log cadence's
        # batched fetch, never a device sync of their own.  ESS/B uses
        # w'=1/p (the constant cancels); saturation counts weights at the
        # max-normalized ceiling; replay age reads the arena's entry
        # stamp (learner-step units), masked where provenance is absent.
        # Under the scope ``diagnostics`` (utils/profiling.py::SIDE_STAGES),
        # with the learner step's own counters.
        with scope("diagnostics"):
            inv = 1.0 / jnp.maximum(res.probs, 1e-12)
            metrics = dict(metrics)
            metrics["quality_ess_frac"] = (inv.sum() ** 2) / (
                res.probs.shape[0] * jnp.square(inv).sum()
            )
            metrics["quality_is_saturation"] = (w >= 1.0 - 1e-9).mean()
            entry = arena.meta[res.indices, 1]
            armed = entry >= 0
            age = jnp.where(
                armed, jnp.maximum(train.step.astype(jnp.int32) - entry, 0), 0
            )
            metrics["quality_replay_age"] = age.sum() / jnp.maximum(
                armed.sum(), 1
            )
        return train, arena, metrics

    def _learn_step(self, train, arena, key):
        """ONE prioritized learner update: sample -> IS weights -> update ->
        priority write-back.  Shared by the in-graph scan (``_learn``) and
        the hybrid trainer's interleaved substep jit, so sampling/anneal/
        write-back semantics cannot drift between the two paths."""
        with scope("replay_sample"):
            res = self.arena.sample(arena, key, self.config.batch_size)
        return self._update_step(train, arena, res, key)

    def _learn_many(
        self, train, arena, key, *, prefetch: bool = False
    ) -> Tuple[TrainState, ArenaState, Dict[str, jnp.ndarray]]:
        """K learner updates on a bare (train, arena) pair.

        The phase-locked ``_learn`` and the pipelined drain program
        (training/pipeline.py) share this body so sampling/anneal/write-back
        semantics cannot drift between the two schedules.

        ``prefetch=True`` double-buffers the batch: batch k+1 is sampled
        BEFORE update k's priority write-back lands, breaking the
        sample->write-back->sample dependency chain so the gather for the
        next batch overlaps the current update's compute.  Sampling then
        sees priorities one update stale — pipelined mode only; the
        phase-locked path keeps the exact sequential chain.
        """
        cfg = self.config
        keys = jax.random.split(key, cfg.learner_steps)
        if not prefetch:

            def one(carry, key):
                train, arena, metrics = self._learn_step(*carry, key)
                return (train, arena), metrics

            (train, arena), metrics = lax.scan(one, (train, arena), keys)
        else:
            # Batch k keeps its phase-locked sample key (keys[k]); only the
            # priorities it is drawn against are one write-back stale.
            with scope("replay_sample"):
                res0 = self.arena.sample(arena, keys[0], cfg.batch_size)
            next_keys = jnp.roll(keys, -1, axis=0)  # keys[k+1]; last unused

            def one_prefetch(carry, ks):
                train, arena, res = carry
                key, next_key = ks
                with scope("replay_sample"):
                    next_res = self.arena.sample(
                        arena, next_key, cfg.batch_size
                    )
                train, arena, metrics = self._update_step(train, arena, res, key)
                return (train, arena, next_res), metrics

            (train, arena, _), metrics = lax.scan(
                one_prefetch, (train, arena, res0), (keys, next_keys)
            )
        # The mean over the call's updates, a table along its own axes; a
        # count (an integer: the sdar core's ``moe/tokens_per_expert``) is
        # not averaged, it keeps one entry for each update.
        metrics = jax.tree_util.tree_map(
            lambda m: m if jnp.issubdtype(m.dtype, jnp.integer)
            else self._pmean(m.mean(axis=0)),
            metrics,
        )
        return train, arena, metrics

    def _learn(self, state: TrainerState) -> Tuple[TrainerState, Dict[str, jnp.ndarray]]:
        """K learner updates: sample -> update -> priority write-back."""
        rng, key = jax.random.split(state.rng)
        key = self._fold_axis(key)
        train, arena, metrics = self._learn_many(state.train, state.arena, key)
        state = dataclasses.replace(state, train=train, arena=arena, rng=rng)
        return state, metrics

    # -------------------------------------------------------------- phases
    def _collect_phase(self, state: TrainerState) -> TrainerState:
        return self._collect(state)

    def _fill_phase(self, state: TrainerState) -> TrainerState:
        return self._emit_and_add(self._collect(state))

    def _train_phase(
        self, state: TrainerState
    ) -> Tuple[TrainerState, Dict[str, jnp.ndarray]]:
        # scope(): HLO-metadata names so the profiler timeline shows the
        # collect/emit/learn stages of the fused phase; the learner's own
        # stages (utils/profiling.py::LEARN_STAGES) nest under ``learn``.
        if self.config.param_sync_every > 0:
            # Persist the snapshot *before* collecting (phase_idx is still
            # this phase's index), so the params _collect acts with are
            # exactly the ones carried forward until the next sync phase.
            state = dataclasses.replace(
                state, behavior_params=self._behavior_params(state)
            )
        with scope("collect"):
            state = self._collect(state)
        with scope("emit_add"):
            state = self._emit_and_add(state)
        with scope("learn"):
            return self._learn(state)

    # ------------------------------------------------------------ schedule
    @property
    def window_fill_phases(self) -> int:
        """Phases needed before the window holds seq_len real steps."""
        return -(-self.seq_len // self.config.stride)  # ceil div

    @property
    def replay_fill_phases(self) -> int:
        """Additional phases to reach min_replay sequences."""
        return -(-self.config.min_replay // self.config.num_envs)

    def pop_episode_metrics(
        self, state: TrainerState
    ) -> Tuple[TrainerState, Dict[str, float]]:
        """Host-side: drain the completed-episode accumulators (L6 logging).

        ONE batched ``jax.device_get`` for all scalars — separate
        ``float(...)`` casts were that many blocking host syncs per log
        call.  Callers invoke this only on the log cadence.  The arena's
        telemetry scalars (occupancy, priority-sum) ride the same fetch;
        multi-process fleets skip them (the replicated arena is not fully
        addressable from one process, and eager reductions on it would
        deadlock the SPMD schedule)."""
        refs = [state.completed_count, state.completed_return_sum, state.env_steps]
        single_proc = jax.process_count() == 1
        extra = []
        if single_proc:
            refs += [
                self.arena.size(state.arena),
                state.arena.priority.sum(),
                state.arena.total_added,
            ]
            extra = self._log_extra_refs(state.arena)
            refs += extra
        fetched = jax.device_get(tuple(refs))
        count, ret_sum, env_steps = fetched[:3]
        count = float(count)
        metrics = {
            "episode_return_mean": float(ret_sum) / max(count, 1.0),
            "episodes": count,
            "env_steps": float(env_steps),
        }
        if single_proc:
            occ, psum, added = fetched[3:6]
            self.arena.observe_state_scalars(
                float(occ), float(psum), float(added)
            )
            if extra:
                self._log_extra_publish(fetched[6:])
        self._obs_publish(metrics)
        state = dataclasses.replace(
            state,
            completed_return_sum=jnp.zeros(()),
            completed_count=jnp.zeros(()),
        )
        return state, metrics

    def _obs_publish(self, metrics: Dict[str, float]) -> None:
        """Fold one log cadence's host-side scalars onto the obs registry
        (shared by the phase-locked and pipelined log paths)."""
        if "env_steps" in metrics:
            self._obs_env_steps.set(metrics["env_steps"])
        if "episode_return_mean" in metrics:
            self._obs_return.set(metrics["episode_return_mean"])
        if "learner_steps" in metrics:
            self._obs_learner_steps.set(metrics["learner_steps"])
        if metrics.get("episodes"):
            self._obs_episodes.inc(metrics["episodes"])
        if any(k.startswith("quality_") for k in metrics):
            # The in-graph quality scalars' host fold (obs/quality.py):
            # the values rode this cadence's existing batched fetch.
            from r2d2dpg_tpu.obs.quality import get_quality_plane

            get_quality_plane().publish_scalars(
                ess_frac=metrics.get("quality_ess_frac"),
                is_saturation=metrics.get("quality_is_saturation"),
                replay_age_mean=metrics.get("quality_replay_age"),
            )
        # Device-plane gauges (HBM in-use/peak) refresh on
        # the same cadence — host-side allocator reads, no device syncs.
        self._device.publish()

    # ----------------------------------------------------------- main loop
    def run(
        self,
        num_phases: int,
        state: Optional[TrainerState] = None,
        log_every: int = 50,
        log_fn=print,
    ) -> TrainerState:
        """Drive the static phase schedule (warm-up -> fill -> train)."""
        state = self.init() if state is None else state
        warm, fill = self.window_fill_phases, self.replay_fill_phases
        last_metrics: Dict[str, jnp.ndarray] = {}
        mon = self._device
        mon.begin_run()
        train_done = 0
        try:
            for phase in range(num_phases):
                # annotate(): host-side trace regions around each phase
                # dispatch so the TB profiler timeline separates the
                # schedule stages.
                if phase < warm:
                    with annotate("trainer/collect_phase"):
                        state = self.collect_phase(state)
                elif phase < warm + fill:
                    with annotate("trainer/fill_phase"):
                        state = self.fill_phase(state)
                else:
                    mon.on_phase(train_done + 1)
                    with annotate("trainer/train_phase"), mon.program(
                        "train_phase"
                    ):
                        state, last_metrics = self.train_phase(state)
                    train_done += 1
                    if train_done == 1:
                        # The fused phase program is warm: any later
                        # compile outside a declared window is an
                        # aval-re-key alarm (docs/OBSERVABILITY.md
                        # "Device plane").
                        mon.mark_steady()
                if log_every and (phase + 1) % log_every == 0:
                    # The log fetch builds small eager reductions on
                    # first use — declared, never an alarm.
                    with mon.expected("log_fetch"):
                        state, ep = self.pop_episode_metrics(state)
                        # One batched fetch for the learn metrics too (a
                        # float() per metric would be N more blocking
                        # host syncs).
                        scalars = host_scalars(jax.device_get(last_metrics))
                    log_fn(
                        f"phase {phase + 1}/{num_phases} "
                        f"env_steps {int(ep['env_steps'])} "
                        f"return {ep['episode_return_mean']:.1f} "
                        f"({int(ep['episodes'])} eps) "
                        + " ".join(
                            f"{k} {v:.3g}" for k, v in scalars.items()
                        )
                    )
        finally:
            mon.end_run()
        return state

