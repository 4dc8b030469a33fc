"""Following the timed path's learner calls with the plain reference.

Shared by the drivers whose timed call ends in ``Trainer._learn_many``: the
reference keeps its own learner state and its own priority vector.  The timed
call hands back no indices, so which rows each update drew is worked out from
the priority vector the call drew against and the slots whose priority it
changed; on those rows the reference runs its own updates, weighs them by its
own probabilities and writes its own priorities back.
"""

from __future__ import annotations

import itertools
from typing import Any, Callable, Dict, List, Optional, Sequence

import jax
import numpy as np

from chipbench import compare, reference


def call_keys(rng, K: int):
    """The keys one timed call draws its ``K`` batches with, and the run key
    it leaves behind, as ``Trainer._learn`` and ``_learn_many`` split them."""
    rng, key = jax.random.split(rng)
    return rng, jax.random.split(key, K)


def learner_call(
    ref: Dict[str, Any],
    ref_prio: np.ndarray,
    before: np.ndarray,
    after: np.ndarray,
    changed: np.ndarray,
    keys,
    rows_of: Callable[[np.ndarray], Dict[str, Any]],
    size: int,
    replay: Dict[str, Any],
    update: Callable,
    near: float,
    must_cover=None,
    open_slots=None,
    metrics: Optional[Dict[str, float]] = None,
) -> Dict[str, Any]:
    """One timed call of ``K = len(keys)`` updates, followed.

    ``before`` is the priority vector the call drew against and ``after``
    the program's after the call (only the slots drawn are decided from
    them), ``changed`` the slots whose priority the call changed,
    ``must_cover`` and
    ``open_slots`` as ``compare.assign_draws`` takes them; ``replay`` holds
    ``batch_size``, ``alpha``, ``beta0``, ``beta_steps``.  Where a draw of an
    open first update lies within rounding of two slots, the call is followed
    with either and the one whose losses lie nearer the program's
    (``metrics``) is kept: float32 cannot tell the two apart, and both are
    sound draws.  Returns the reference's state and priority vector after the
    call, the mean losses of its updates, the first update's gradients, the
    slots drawn and the sampling gap.
    """
    import jax.numpy as jnp

    B = int(replay["batch_size"])
    u01 = [np.asarray(jax.random.uniform(k, (B,))) for k in keys]
    a = compare.assign_draws(before, after, changed, u01, replay["alpha"], near,
                             must_cover, open_slots)

    def run(slots):
        state, prio = ref, np.array(ref_prio, np.float32, copy=True)
        losses: Dict[str, List[float]] = {
            "critic_loss": [], "actor_loss": [], "q_abs_mean": []}
        grads = None
        for k in range(len(keys)):
            mass = reference.scaled_mass(prio, replay["alpha"])
            probs = mass[slots[k]] / max(float(mass.sum()), 1e-12)
            w = reference.is_weights(probs, size, int(state["step"]),
                                     replay["beta0"], replay["beta_steps"])
            state, prios, ls = update(state, rows_of(slots[k]), jnp.asarray(w))
            prio = reference.write_priorities(prio, slots[k], prios)
            for name in losses:
                losses[name].append(float(ls[name]))
            grads = ls["grads"] if k == 0 else grads
        return {"ref": state, "ref_prio": prio, "first_grads": grads, "slots": slots,
                "losses": {k: float(np.mean(v)) for k, v in losses.items()}}

    best = run(a["slots"])
    alts = a["alternatives"][:4] if metrics is not None else []
    for combo in itertools.product((False, True), repeat=len(alts)):
        if not any(combo):
            continue
        slots = a["slots"].copy()
        for take, (k, j, slot) in zip(combo, alts):
            if take:
                slots[k, j] = slot
        other = run(slots)
        if loss_gap(metrics, other["losses"]) < loss_gap(metrics, best["losses"]):
            best = other
    best["sample_gap"] = a["gap"]
    best["draws_unplaced"] = a["draws_unplaced"]
    return best


def loss_gap(metrics: Dict[str, float], ref_losses: Dict[str, float]) -> float:
    """The wider of the two losses' gaps; the actor's loss is a mean of Q
    values that may be near nought, so it is held to the mean ``|Q|``."""
    return max(
        compare.rel_gap(metrics["critic_loss"], ref_losses["critic_loss"]),
        compare.rel_gap(metrics["actor_loss"], ref_losses["actor_loss"],
                        ref_losses["q_abs_mean"]),
    )


def priority_gap(after: np.ndarray, ref_prio: np.ndarray, slots: Sequence[int]) -> float:
    """The widest relative gap between the program's and the reference's
    priorities over ``slots``."""
    slots = np.asarray(slots, np.int64)
    if slots.size == 0:
        return 0.0
    got, want = np.asarray(after)[slots], np.asarray(ref_prio)[slots]
    return float(np.max(np.abs(got - want) / np.maximum(np.abs(want), 1e-30)))


def adam_mu(opt_state) -> Any:
    """Adam's first moment out of an ``optax.chain(clip, adam)`` state."""
    for part in jax.tree_util.tree_leaves(
        opt_state, is_leaf=lambda x: hasattr(x, "mu")
    ):
        if hasattr(part, "mu"):
            return part.mu
    raise ValueError("no Adam state found in the optimizer state")


def grad_gap(opt_states, ref) -> tuple:
    """Adam's first moment after the first call, the program's against the
    reference's, by the worst leaf: the gradients the optimizer was given,
    mixed by fixed weights.  ``opt_states`` is (actor's, critic's)."""
    mu_prog = compare.leaf_norms(
        {"actor": adam_mu(opt_states[0]), "critic": adam_mu(opt_states[1])})
    mu_ref = compare.leaf_norms(jax.device_get(
        {"actor": ref["actor_opt"]["mu"], "critic": ref["critic_opt"]["mu"]}))
    return compare.worst_leaf_gap(mu_prog, mu_ref)


NETS = ("actor", "critic")
TARGETS = ("target_actor", "target_critic")


def train_params(train) -> Dict[str, Any]:
    """The four nets' weights of a program ``TrainState``, on the host."""
    return jax.device_get({
        "actor": train.actor_params,
        "critic": train.critic_params,
        "target_actor": train.target_actor_params,
        "target_critic": train.target_critic_params,
    })


def change_gaps(params, ref, p0, first_grads) -> Dict[str, Any]:
    """The norm of the weights' change since ``p0``, the program's against
    the reference's, by the worst leaf; leaves whose reference gradient is
    nought to rounding are left out (``compare.dead_leaves``)."""
    dead = compare.dead_leaves(compare.leaf_norms(jax.device_get(first_grads)))
    ref_p = jax.device_get({k: ref[k] for k in NETS + TARGETS})

    def change(p, names):
        return compare.leaf_norms({n: compare.tree_sub(p[n], p0[n]) for n in names})

    update, leaf_u = compare.worst_leaf_gap(
        change(params, NETS), change(ref_p, NETS), skip=dead)
    target, leaf_t = compare.worst_leaf_gap(
        change(params, TARGETS), change(ref_p, TARGETS),
        skip=["target_" + d for d in dead])
    return {"update_gap": update, "target_gap": target,
            "where": f"update {leaf_u}; target {leaf_t}; left out {dead}"}
