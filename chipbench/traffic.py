"""The one generator of a training cell's inputs: weights and replay rows.

Everything is a function of ``--seed``.  A replay row is a function of
(seed, slot index) alone, so the fill makes rows ``start .. start + chunk``
on the device and the reference makes the few rows a learner step sampled,
by the same function, without keeping a second copy of the arena.

The parameters come from the cell's workload file (``traffic``):

- ``in_flight_calls``: how many timed calls the ``learn`` driver queues ahead
  of the device (about a third of a second of its work);
- ``fill_chunk_rows``: rows the arena is filled with at a time;
- ``reset_prob``: chance that a stored step begins an episode (DM-Control
  episodes are 1,000 physics steps and never terminate, so ``discount`` is 1
  and a boundary is a truncation);
- ``priority_log_sigma``, ``priority_scale``: stored priorities are
  log-normal, so that ``p^alpha`` spreads over slots as TD errors do;
- ``carry_scale``: spread of the stored LSTM carries;
- ``reward_max``: rewards are uniform in ``[0, reward_max]``.

Weights are fan-in uniform kernels and small uniform biases in float32, the
type the learner holds them in, made in one jitted call.
"""

from __future__ import annotations

import math
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

# Sub-streams of the seed, so that a new consumer never shifts an old one.
STREAM_WEIGHTS, STREAM_ROWS, STREAM_RUN = 1, 2, 3


def seed_key(seed: int, stream: int) -> jax.Array:
    """A key for one sub-stream of ``--seed`` (any whole number: the seed is
    folded in 31 bits at a time, since a PRNG key takes a signed 32-bit)."""
    seed = int(seed)
    if seed < 0:
        raise ValueError("seed must be >= 0")
    key = jax.random.PRNGKey(seed & 0x7FFFFFFF)
    key = jax.random.fold_in(key, (seed >> 31) & 0x7FFFFFFF)
    return jax.random.fold_in(key, stream)


# ------------------------------------------------------------------ weights
def _leaf_name(path) -> str:
    return str(getattr(path[-1], "key", path[-1]))


def make_weights(key: jax.Array, shapes: Any) -> Any:
    """Fill a tree of ``ShapeDtypeStruct`` leaves from ``key`` in one jitted
    call: kernels ``U(-1/sqrt(fan_in), 1/sqrt(fan_in))`` (fan-in is every
    axis but the last), biases ``U(-0.05, 0.05)``."""
    paths, treedef = jax.tree_util.tree_flatten_with_path(shapes)

    def build(key):
        leaves = []
        for i, (path, s) in enumerate(paths):
            k = jax.random.fold_in(key, i)
            if _leaf_name(path) == "kernel":
                bound = 1.0 / math.sqrt(max(int(np.prod(s.shape[:-1])), 1))
            else:
                bound = 0.05
            leaves.append(
                jax.random.uniform(k, s.shape, s.dtype, -bound, bound)
            )
        return jax.tree_util.tree_unflatten(treedef, leaves)

    return jax.jit(build)(key)


# --------------------------------------------------------------- replay rows
def row_spec(config: Dict[str, Any], seq_len: int, hidden: int) -> Dict[str, Any]:
    return {
        "seq_len": int(seq_len),
        "obs_shape": tuple(config["obs_shape"]),
        "obs_dtype": str(config["obs_dtype"]),
        "action_dim": int(config["action_dim"]),
        "hidden": int(hidden),
    }


def _one_row(key, spec, params):
    L, A, H = spec["seq_len"], spec["action_dim"], spec["hidden"]
    ks = jax.random.split(key, 10)
    obs_shape = (L,) + spec["obs_shape"]
    if spec["obs_dtype"] == "uint8":
        obs = jax.random.bits(ks[0], obs_shape, jnp.uint8)
    else:
        obs = jax.random.normal(ks[0], obs_shape, jnp.dtype(spec["obs_dtype"]))
    scale = params["carry_scale"]

    def carry(k):
        kc, kh = jax.random.split(k)
        return (
            scale * jax.random.normal(kc, (H,), jnp.float32),
            jnp.tanh(scale * jax.random.normal(kh, (H,), jnp.float32)),
        )

    row = {
        "obs": obs,
        "action": jax.random.uniform(ks[1], (L, A), jnp.float32, -1.0, 1.0),
        "reward": params["reward_max"]
        * jax.random.uniform(ks[2], (L,), jnp.float32),
        "discount": jnp.ones((L,), jnp.float32),
        "reset": jax.random.bernoulli(ks[3], params["reset_prob"], (L,)).astype(
            jnp.float32
        ),
        "carries": {"actor": carry(ks[4]), "critic": carry(ks[5])},
    }
    priority = params["priority_scale"] * jnp.exp(
        params["priority_log_sigma"] * jax.random.normal(ks[6], (), jnp.float32)
    )
    return row, priority


def make_rows(
    key: jax.Array, indices: jnp.ndarray, spec: Dict[str, Any], params: Dict[str, float]
) -> Tuple[Dict[str, Any], jnp.ndarray]:
    """Rows and stored priorities of the slots ``indices`` (``[N]`` int32):
    leaves ``[N, L, ...]``, carries ``[N, H]``.  Traceable."""
    keys = jax.vmap(lambda i: jax.random.fold_in(key, i))(indices)
    return jax.vmap(lambda k: _one_row(k, spec, params))(keys)
