"""Building the program's own objects from a configuration file: the
experiment config with the file's numbers applied, the trainer, and a learner
state that holds the seed's weights.  Shared by the drivers."""

from __future__ import annotations

import dataclasses
import types
from typing import Any, Dict

import jax
import jax.numpy as jnp

from chipbench import plants, reference, traffic
from chipbench.harness import Context


def build_experiment(ctx: Context):
    """The program's own experiment config with every number of the
    configuration file applied to it."""
    from r2d2dpg_tpu.configs import get_config

    cfg = ctx.config
    exp = get_config(cfg["program_config"])

    def apply(obj):
        fields = {f.name for f in dataclasses.fields(obj)}
        return dataclasses.replace(obj, **{k: cfg[k] for k in fields if k in cfg})

    exp = dataclasses.replace(
        exp,
        agent=apply(exp.agent),
        trainer=dataclasses.replace(
            apply(exp.trainer), seed=int(ctx.seed) & 0x7FFFFFFF
        ),
        **{k: cfg[k] for k in ("hidden", "use_lstm", "pixels", "compute_dtype")},
    )
    return plants.on_experiment(exp, ctx.plant)


def hyperparameters(exp) -> Dict[str, Any]:
    a = exp.agent
    return {
        k: getattr(a, k)
        for k in ("burnin", "unroll", "n_step", "gamma", "tau", "eta",
                  "actor_lr", "critic_lr", "grad_clip")
    }


def build_trainer(ctx: Context, exp, env=None):
    """The program's ``Trainer``.  Without ``env`` it gets a stand-in that
    holds only the shapes: the learner never touches the environment."""
    from r2d2dpg_tpu.training.trainer import Trainer

    cfg = ctx.config
    if env is None:
        env = types.SimpleNamespace(
            spec=types.SimpleNamespace(
                action_dim=int(cfg["action_dim"]), obs_shape=tuple(cfg["obs_shape"])
            )
        )
    trainer = Trainer(env, exp.build_agent(env), exp.trainer)
    plants.on_trainer(trainer, ctx.plant)
    return trainer


def program_weight_shapes(trainer, spec):
    """The shapes of both nets' weights, as the program's init lays them out
    (shapes only: nothing is computed)."""
    obs = jnp.zeros((1,) + spec["obs_shape"], jnp.dtype(spec["obs_dtype"]))
    act = jnp.zeros((1, spec["action_dim"]), jnp.float32)
    st = jax.eval_shape(
        lambda k: trainer.agent.init(k, obs, act), jax.random.PRNGKey(0)
    )
    return st.actor_params, st.critic_params


def make_train_state(trainer, spec, config, seed: int):
    """The program's ``TrainState`` around the seed's weights.  The weights
    are made on the reference's own tree of shapes (the configuration file's
    numbers alone); the program's tree has to be that tree."""
    from r2d2dpg_tpu.agents.ddpg import TrainState

    shapes = reference.weight_shapes(config)
    def laid_out(tree):
        return [(jax.tree_util.keystr(path), tuple(s.shape), jnp.dtype(s.dtype))
                for path, s in jax.tree_util.tree_flatten_with_path(tree)[0]]

    if laid_out(shapes) != laid_out(program_weight_shapes(trainer, spec)):
        raise ValueError("the program's weights are not laid out as the "
                         "reference's: chipbench/reference.py::weight_shapes")
    actor, critic = traffic.make_weights(
        traffic.seed_key(seed, traffic.STREAM_WEIGHTS), shapes
    )
    copy = lambda t: jax.tree_util.tree_map(jnp.copy, t)  # noqa: E731
    return TrainState(
        actor_params=actor,
        critic_params=critic,
        target_actor_params=copy(actor),
        target_critic_params=copy(critic),
        actor_opt_state=trainer.agent.actor_tx.init(actor),
        critic_opt_state=trainer.agent.critic_tx.init(critic),
        step=jnp.zeros((), jnp.int32),
    )


def to_batch(rows: Dict[str, Any]):
    from r2d2dpg_tpu.replay.arena import SequenceBatch

    return SequenceBatch(**rows)
