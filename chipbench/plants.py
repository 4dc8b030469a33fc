"""Controls and faults, planted under the timed path from the benchmark's
side.  The benchmark's own runs plant nothing; ``readings.py`` and the tests
under ``tests/chipbench`` do, to show that ``correct`` comes out false.

Controls (one precision below what the configuration states):

- ``bf16``         the program's own ``compute_dtype="bfloat16"`` path;
- ``sample_bf16``  ``ReplayArena.sample`` with its CDF computed in bfloat16
                   (the program has no such path, so this is the plain
                   inverse-CDF draw, put in its place, one precision down).

Faults:

- ``frozen``       the timed call returns its state unchanged;
- ``half_batch``   the learner step leaves out half of the batch and takes
                   its mean over the rest.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

PLANTS = ("bf16", "sample_bf16", "frozen", "half_batch")


def on_experiment(exp, plant: Optional[str]):
    if plant is not None and plant not in PLANTS:
        raise ValueError(f"unknown plant {plant!r}; have {PLANTS}")
    if plant == "bf16":
        return dataclasses.replace(exp, compute_dtype="bfloat16")
    return exp


def on_trainer(trainer, plant: Optional[str]) -> None:
    import jax
    import jax.numpy as jnp

    if plant == "frozen":
        inner = trainer._learn_many

        def frozen(train, arena, key, **kw):
            _, _, metrics = inner(train, arena, key, **kw)
            return train, arena, metrics

        trainer._learn_many = frozen
    elif plant == "half_batch":
        agent = trainer.agent
        inner_step = agent.learner_step

        def half(state, batch, is_weights, key=None):
            h = is_weights.shape[0] // 2
            part = jax.tree_util.tree_map(lambda x: x[:h], batch)
            state, prios, metrics = inner_step(state, part, is_weights[:h], key=key)
            return state, jnp.concatenate([prios, prios]), metrics

        agent.learner_step = half
    elif plant == "sample_bf16":
        from r2d2dpg_tpu.replay.arena import SampleResult

        arena = trainer.arena

        def sample(state, key, batch_size):
            bf = jnp.bfloat16
            p = state.priority.astype(bf)
            scaled = jnp.where(p > 0, p ** jnp.asarray(arena.alpha, bf), 0).astype(bf)
            cdf = jnp.cumsum(scaled, dtype=bf)
            total = cdf[-1]
            u = jax.random.uniform(key, (batch_size,)).astype(bf) * total
            idx = jnp.clip(jnp.searchsorted(cdf, u, side="right"), 0,
                           arena.capacity - 1)
            probs = (scaled[idx] / jnp.maximum(total, 1e-12)).astype(jnp.float32)
            batch = jax.tree_util.tree_map(lambda buf: buf[idx], state.data)
            return SampleResult(batch=batch, indices=idx, probs=probs)

        arena.sample = sample
