"""R2D2-DPG learner: burn-in + n-step DDPG update as one jittable function.

Reference parity: SURVEY.md §2.4 / §3.3 — the reference learner's hot loop is
  sample -> host->device -> no-grad LSTM burn-in (all 4 nets) -> n-step
  targets -> IS-weighted critic Huber loss -> actor loss -Q(s, mu(s)) ->
  Adam steps -> Polyak soft target update -> priority write-back.
Here the whole pipeline is a single pure function (`learner_step`) traced
once under jit (BASELINE north star: "the LSTM actor-critic burn-in+unroll
and n-step TD update become a single jit-compiled XLA graph") — there is no
host->device boundary because the batch is gathered from the HBM arena
in-graph.

Algorithmic details the build reproduces [ALGO]:
- burn-in from *stored* recurrent state, no gradient through the burn-in
  (carries are stop_gradient'ed before the training unroll);
- critic target ``y = sum gamma^k r + gamma^n Q_tgt(s', mu_tgt(s'))``;
- actor loss ``-Q(s, mu(s))`` through the (frozen) online critic;
- sequence priority ``eta*max|td| + (1-eta)*mean|td|`` written back;
- soft target updates each step.

Distributed (SURVEY §2.8): ``axis_name`` switches on gradient ``pmean`` over
the device mesh — under ``shard_map`` each device computes grads on its local
shard of the batch and syncs over ICI.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import optax
from jax import lax

from r2d2dpg_tpu.models.sequence import _member, sequence_runner, window
from r2d2dpg_tpu.ops import (
    huber,
    n_step_targets,
    polyak_update,
    sequence_priority,
    td_errors,
)
from r2d2dpg_tpu.replay.arena import SequenceBatch
from r2d2dpg_tpu.utils.profiling import scope


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class TrainState:
    """All learner-owned mutable state (a pytree; device-resident)."""

    actor_params: Any
    critic_params: Any
    target_actor_params: Any
    target_critic_params: Any
    actor_opt_state: Any
    critic_opt_state: Any
    step: jnp.ndarray


@dataclasses.dataclass(frozen=True)
class AgentConfig:
    """Static hyperparameters (SURVEY §2.5 'Hyperparameters' row)."""

    burnin: int = 20
    unroll: int = 20
    n_step: int = 5
    gamma: float = 0.99
    tau: float = 5e-3
    eta: float = 0.9
    actor_lr: float = 1e-4
    critic_lr: float = 1e-3
    use_huber: bool = True
    grad_clip: Optional[float] = 40.0
    axis_name: Optional[str] = None  # mesh axis for gradient sync (SPMD)
    # --- overestimation mitigations (round-3; the config-#5 CPU evidence
    # run collapsed from textbook DDPG critic overestimation — q_mean rose
    # 0.15 -> 0.95 while eval return fell).  Both default
    # OFF so the baseline DDPG semantics (SURVEY §2.4) are unchanged.
    #
    # twin_critic: clipped double-Q (TD3) — two critics as a vmapped
    # ensemble (leading [2] axis on every critic leaf; TrainState structure
    # is unchanged), targets bootstrap from min(Q1', Q2'), the actor
    # ascends Q1.  The ensemble runs as ONE batched unroll on the MXU, so
    # the twin costs ~one extra critic-sized matmul batch, not a second
    # sequential scan.
    twin_critic: bool = False
    # target_policy_sigma/clip: TD3 target-policy smoothing — the target
    # action gets clip(N(0, sigma), +-clip) noise before bootstrapping, so
    # the critic target is a local average instead of a point the actor can
    # exploit.  sigma 0 disables (and then no RNG key is required).
    target_policy_sigma: float = 0.0
    target_policy_clip: float = 0.5

    @property
    def seq_len(self) -> int:
        """Stored sequence length: burn-in + unroll + n-step bootstrap tail."""
        return self.burnin + self.unroll + self.n_step


def _tm(x: jnp.ndarray) -> jnp.ndarray:
    return jnp.swapaxes(x, 0, 1)


class R2D2DPG:
    """Agent: networks + optimizers + the learner step (pure functions)."""

    def __init__(self, actor, critic, config: AgentConfig):
        self.actor = actor
        self.critic = critic
        self.config = config
        # How the nets' core is run over a stored sequence (burn-in, the
        # unrolls, what they leave behind): models/sequence.py decides it
        # from the nets; nothing below asks what the core is.
        self.seq = sequence_runner(actor, critic, config)

        def tx(lr: float) -> optax.GradientTransformation:
            if config.grad_clip is not None:
                return optax.chain(
                    optax.clip_by_global_norm(config.grad_clip), optax.adam(lr)
                )
            return optax.adam(lr)

        self.actor_tx = tx(config.actor_lr)
        self.critic_tx = tx(config.critic_lr)

    # ------------------------------------------------------------------ init
    def init(
        self, key: jax.Array, example_obs: jnp.ndarray, example_action: jnp.ndarray
    ) -> TrainState:
        """Initialize params/opt-states from example [B, ...] obs/action."""
        ka, kc = jax.random.split(key)
        b = example_obs.shape[0]
        reset = jnp.zeros((b,))
        actor_params = self.actor.init(
            ka, example_obs, self.actor.initial_carry(b), reset
        )
        init_critic = lambda k: self.critic.init(  # noqa: E731
            k, example_obs, example_action, self.critic.initial_carry(b), reset
        )
        if self.config.twin_critic:
            # Independent inits stacked on a leading [2] ensemble axis; every
            # critic consumer vmaps over it (TrainState structure unchanged).
            critic_params = jax.tree_util.tree_map(
                lambda a, b_: jnp.stack([a, b_]),
                *(init_critic(k) for k in jax.random.split(kc)),
            )
        else:
            critic_params = init_critic(kc)
        # Targets start as *copies* — aliased buffers would break donation
        # of the TrainState pytree in the trainer's jitted phases.
        copy = lambda t: jax.tree_util.tree_map(jnp.copy, t)  # noqa: E731
        return TrainState(
            actor_params=actor_params,
            critic_params=critic_params,
            target_actor_params=copy(actor_params),
            target_critic_params=copy(critic_params),
            actor_opt_state=self.actor_tx.init(actor_params),
            critic_opt_state=self.critic_tx.init(critic_params),
            step=jnp.zeros((), jnp.int32),
        )

    def behavior_critic_params(self, state: TrainState):
        """Critic params for the collection-time carry advance: member 0 in
        twin mode (the stored carry seeds both members at burn-in, so one
        member's carry trace is what gets stored)."""
        if self.config.twin_critic:
            return _member(state.critic_params, 0)
        return state.critic_params

    def _target_q(self, state, ca_tg, cc_tg, obs_tm, reset_tm, eps_tm):
        """Bootstrap Q through the target nets, time-major ``[T, B]``."""
        return self._target_unroll(state, ca_tg, cc_tg, obs_tm, reset_tm, eps_tm)[0]

    def _target_unroll(self, state, ca_tg, cc_tg, obs_tm, reset_tm, eps_tm):
        """``_target_q``'s worker: returns it with what the unroll left behind.

        Plain DDPG (twin off, sigma 0) is the fused pi+Q unroll unchanged;
        otherwise the per-step action is smoothed with the pre-drawn clipped
        noise ``eps_tm`` (TD3 target-policy smoothing) and/or Q is the min
        over the target-critic ensemble (clipped double-Q).
        """
        return self.seq.unroll_pi_q(
            state.target_actor_params,
            state.target_critic_params,
            ca_tg,
            cc_tg,
            obs_tm,
            reset_tm,
            eps_tm=eps_tm,
            q_min=self.config.twin_critic,
        )[1:]

    # ---------------------------------------------------------- learner step
    def learner_step(
        self,
        state: TrainState,
        batch: SequenceBatch,
        is_weights: jnp.ndarray,
        key: Optional[jax.Array] = None,
    ) -> Tuple[TrainState, jnp.ndarray, Dict[str, jnp.ndarray]]:
        """One optimization step on a batch of sequences.

        Args:
          state: current TrainState.
          batch: ``[B, L, ...]`` sequences, ``L == config.seq_len``.
          is_weights: ``[B]`` importance-sampling weights (ones when uniform).
          key: RNG for target-policy smoothing; required iff
            ``config.target_policy_sigma > 0``.

        Returns:
          (new_state, new_priorities ``[B]``, metrics).
        """
        cfg = self.config
        U = cfg.unroll

        # scope(): the stages of utils/profiling.py::LEARN_STAGES, which
        # obs/stages.py reads back from the chip's trace.  ``forward`` wraps
        # the gradient call(s), so that the operations JAX names
        # ``transpose(...)`` under it read as ``backward``.  ``frames``
        # (``SIDE_STAGES``) is what the nets want done once to the whole
        # batch's observations (pixels: scaled and re-laid for the conv
        # torso; flat: nothing); every pass below cuts its window out of
        # that one result.
        with scope("frames"):
            batch = self.seq.prepare(batch)

        with scope("burn_in"):
            ca_on, ca_tg, cc_on, cc_tg = self.seq.burn_in(state, batch)

        with scope("forward"):
            # Training window: [burnin, burnin+U+n) — time-major for the scans.
            w = slice(cfg.burnin, cfg.seq_len)
            obs_w = window(batch.obs, w.start, w.stop)
            act_w = _tm(batch.action[:, w])
            reset_w = _tm(batch.reset[:, w])
            rew_w = batch.reward[:, w]  # batch-major [B, U+n]
            disc_w = batch.discount[:, w]

            # --- n-step targets through the target nets (no gradient); plain
            # DDPG fuses the policy and Q unrolls into one scan, the mitigation
            # knobs (ensemble min / smoothing noise) reshape it in _target_q.
            eps_w = None
            if cfg.target_policy_sigma > 0:
                if key is None:
                    raise ValueError(
                        "AgentConfig.target_policy_sigma > 0 requires "
                        "learner_step(..., key=...)"
                    )
                eps_w = jnp.clip(
                    cfg.target_policy_sigma
                    * jax.random.normal(key, act_w.shape, act_w.dtype),
                    -cfg.target_policy_clip,
                    cfg.target_policy_clip,
                )
            q_tg_tm, left_tg = self._target_unroll(
                state, ca_tg, cc_tg, obs_w, reset_w, eps_w
            )
            y = lax.stop_gradient(
                n_step_targets(
                    rew_w,
                    disc_w,
                    batch.reset[:, w],
                    _tm(q_tg_tm),
                    n=cfg.n_step,
                    gamma=cfg.gamma,
                )
            )  # [B, U]

            # Online unrolls only need the U training steps (the n-step tail is
            # exclusively for target bootstraps) — saves ~n/(U+n) hot-loop LSTM
            # forward+backward compute.
            obs_u, act_u, reset_u = obs_w[:U], act_w[:U], reset_w[:U]

            # --- the losses.  Critic: IS-weighted (SURVEY §2.4 "weighted by IS
            # weights"); twin mode trains both members against the same
            # min-bootstrapped y (TD3), td/q metrics and priorities come from
            # member 0.  Actor: -Q(s, mu(s)) through the FROZEN online critic
            # (member 0 in twin mode, the TD3 convention).
            def critic_loss_of(q_tm, left):
                if cfg.twin_critic:
                    q2 = jnp.swapaxes(q_tm, 1, 2)  # [2, B, U]
                    td2 = jax.vmap(td_errors, in_axes=(0, None))(q2, y)
                    per_step = huber(td2) if cfg.use_huber else 0.5 * td2**2
                    # SUM over members (TD3's L = L1 + L2): each member's
                    # gradient matches what it would get as the single critic —
                    # a mean would silently halve the effective critic LR.
                    loss = (is_weights[:, None] * per_step.sum(axis=0)).mean()
                    spread = jnp.abs(q2[0] - q2[1]).mean()
                    return loss, (td2[0], q2[0], spread, left)
                q = _tm(q_tm)  # [B, U]
                td = td_errors(q, y)
                per_step = huber(td) if cfg.use_huber else 0.5 * td**2
                loss = (is_weights[:, None] * per_step).mean()
                return loss, (td, q, None, left)

            if self.seq.joint_online:
                # Both passes in ONE scan under ONE gradient call
                # (``Stepped.unroll_online``): the critic's loss pass and the
                # actor's do not depend on each other, and a step of either
                # costs its chain of operations, not their work.  The actor's
                # pass reads the critic through ``stop_gradient``, so the
                # critic's gradient is the critic loss's alone and the
                # actor's the actor loss's, as two calls gave them.
                def loss_fn(params):
                    q_tm, q_pi_tm, (left_c, left_a) = self.seq.unroll_online(
                        *params, ca_on, cc_on, obs_u, act_u, reset_u,
                        ensemble=cfg.twin_critic,
                    )
                    c_loss, aux = critic_loss_of(q_tm, left_c)
                    a_loss = -q_pi_tm.mean()
                    return c_loss + a_loss, (c_loss, a_loss, aux, left_a)

                (_, (critic_loss, actor_loss, (td, q_pred, q_spread, left_q), left_pi)), (
                    actor_grads, critic_grads
                ) = jax.value_and_grad(loss_fn, has_aux=True)(
                    (state.actor_params, state.critic_params)
                )
            else:
                # Two passes under two gradient calls (``Whole``: a
                # whole-sequence pass is no chain of small steps, and its
                # learner call sits at the compiler's memory limit).
                def critic_loss_fn(critic_params):
                    if cfg.twin_critic:
                        q_tm, left = jax.vmap(
                            lambda p, c: self.seq.unroll_critic(
                                p, c, obs_u, act_u, reset_u
                            )
                        )(critic_params, cc_on)
                    else:
                        q_tm, left = self.seq.unroll_critic(
                            critic_params, cc_on, obs_u, act_u, reset_u
                        )
                    return critic_loss_of(q_tm, left)

                (critic_loss, (td, q_pred, q_spread, left_q)), critic_grads = (
                    jax.value_and_grad(critic_loss_fn, has_aux=True)(
                        state.critic_params
                    )
                )
                cp_pi = (
                    _member(state.critic_params, 0) if cfg.twin_critic
                    else state.critic_params
                )
                cc_on_pi = _member(cc_on, 0) if cfg.twin_critic else cc_on

                def actor_loss_fn(actor_params):
                    _, q_pi_tm, left = self.seq.unroll_pi_q(
                        actor_params, cp_pi, ca_on, cc_on_pi, obs_u, reset_u
                    )
                    return -q_pi_tm.mean(), left

                (actor_loss, left_pi), actor_grads = jax.value_and_grad(
                    actor_loss_fn, has_aux=True
                )(state.actor_params)

        # --- gradient sync over the mesh (SURVEY §2.8: psum over ICI).
        if cfg.axis_name is not None:
            critic_grads = lax.pmean(critic_grads, cfg.axis_name)
            actor_grads = lax.pmean(actor_grads, cfg.axis_name)

        with scope("optimizer"):
            critic_updates, critic_opt_state = self.critic_tx.update(
                critic_grads, state.critic_opt_state, state.critic_params
            )
            critic_params = optax.apply_updates(state.critic_params, critic_updates)
            actor_updates, actor_opt_state = self.actor_tx.update(
                actor_grads, state.actor_opt_state, state.actor_params
            )
            actor_params = optax.apply_updates(state.actor_params, actor_updates)

            new_state = TrainState(
                actor_params=actor_params,
                critic_params=critic_params,
                target_actor_params=polyak_update(
                    actor_params, state.target_actor_params, cfg.tau
                ),
                target_critic_params=polyak_update(
                    critic_params, state.target_critic_params, cfg.tau
                ),
                actor_opt_state=actor_opt_state,
                critic_opt_state=critic_opt_state,
                step=state.step + 1,
            )
        priorities = sequence_priority(td, eta=cfg.eta)
        # The update's in-graph counters, under a scope of their own
        # (``SIDE_STAGES``): what they cost on the device is read back as
        # ``scopes["diagnostics"]`` of the stage table.
        with scope("diagnostics"):
            metrics = {
                "critic_loss": critic_loss,
                "actor_loss": actor_loss,
                "q_mean": q_pred.mean(),
                "td_abs_mean": jnp.abs(td).mean(),
                "target_mean": y.mean(),
                # Divergence-watchdog inputs (obs/watchdog.py): global norms of
                # this step's gradients and the updated params, computed
                # in-graph and fetched with the SAME batched device_get as the
                # losses on the log cadence — no extra host syncs.
                "grad_norm": optax.global_norm((actor_grads, critic_grads)),
                "param_norm": optax.global_norm((actor_params, critic_params)),
            }
            if cfg.twin_critic:
                metrics["q_spread"] = q_spread  # |Q1-Q2|: overestimation proxy
            # What the passes left behind (a core's counters; nothing for a scan).
            metrics.update(self.seq.metrics(
                burn=(ca_on, ca_tg, cc_on, cc_tg),
                target=left_tg, critic=left_q, pi=left_pi,
            ))
        return new_state, priorities, metrics

    # ------------------------------------------------------- initial priority
    def initial_priority(
        self, state: TrainState, batch: SequenceBatch
    ) -> jnp.ndarray:
        """TD-error priority for fresh sequences at collection time.

        SURVEY §2.2 "Initial priority" [ALGO, Ape-X §3]: actors compute the
        TD error locally so sequences enter replay with a meaningful
        priority.  In the Anakin layout this runs on-device right after the
        actor phase, with the current online/target nets.
        """
        cfg = self.config
        with scope("burn_in"):
            ca_on, ca_tg, cc_on, cc_tg = self.seq.burn_in(state, batch)
        w = slice(cfg.burnin, cfg.seq_len)
        obs_w = _tm(batch.obs[:, w])
        act_w = _tm(batch.action[:, w])
        reset_w = _tm(batch.reset[:, w])

        # Same bootstrap as the learner (ensemble min in twin mode) so fresh
        # sequences are ranked on the distribution they will be trained
        # under; no smoothing noise here — priorities stay deterministic.
        q_tg_tm = self._target_q(state, ca_tg, cc_tg, obs_w, reset_w, None)
        y = n_step_targets(
            batch.reward[:, w],
            batch.discount[:, w],
            batch.reset[:, w],
            _tm(q_tg_tm),
            n=cfg.n_step,
            gamma=cfg.gamma,
        )
        q_tm, _ = self.seq.unroll_critic(
            _member(state.critic_params, 0) if cfg.twin_critic
            else state.critic_params,
            _member(cc_on, 0) if cfg.twin_critic else cc_on,
            obs_w[: cfg.unroll],
            act_w[: cfg.unroll],
            reset_w[: cfg.unroll],
        )
        td = td_errors(_tm(q_tm), y)
        return sequence_priority(td, eta=cfg.eta)
