"""The saturated learner: ``Trainer._learn_many`` on an arena filled to its
capacity, dispatched back to back.

One timed call is the body every training schedule shares (``learner_steps``
updates: sample -> IS weights -> ``learner_step`` -> priority write-back),
jitted with the state donated, the run's key threaded through it as
``Trainer._learn`` threads it.  Set-up builds that one compiled call with
its state, drives it from the seed through its first three calls (which the
reference follows afterwards), and hands the same object to the window.
"""

from __future__ import annotations

import collections
import dataclasses
import time
from typing import Any, Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import follow, reference, traffic
from chipbench.program import (build_experiment, build_trainer, hyperparameters,
                               make_train_state, to_batch)
from chipbench.harness import Compared, Context

FIRST_CALLS = 3  # the calls the reference follows
PRECISION = "default"  # of the reference: what the configurations run at


@dataclasses.dataclass
class Session:
    trainer: Any
    call: Any  # the compiled timed call
    state: Any  # (train, arena, rng), donated into every call
    hp: Dict[str, Any]
    spec: Dict[str, Any]
    first: List[Dict[str, Any]]  # what the first calls produced
    rng0: Any  # the run key the first call was given
    in_flight: int  # timed calls dispatched ahead of the one waited for


def fill_arena(ctx: Context, trainer, spec):
    """The arena at capacity, filled on the device from the seed in donated
    chunks written in place.

    The state is what ``init_state`` and then ``add`` of every row would
    leave (priorities floored at the epsilon, the cursor back at 0, no
    provenance stamps; a CPU test holds the two equal).  It is not made
    through ``add``: its scatter re-lays the whole observation buffer out,
    which for the pixel arena is a second copy twice its size and does not
    fit the chip, and is 15 times slower than the in-place write besides.
    """
    capacity = trainer.arena.capacity
    chunk = int(ctx.cell["traffic"]["fill_chunk_rows"])
    if capacity % chunk:
        raise ValueError(f"capacity {capacity} is not a multiple of {chunk}")
    params = ctx.cell["traffic"]
    key = traffic.seed_key(ctx.seed, traffic.STREAM_ROWS)

    def put(buf, new, start):
        return jax.lax.dynamic_update_slice_in_dim(buf, new.astype(buf.dtype), start, 0)

    def one(state, key, start):
        idx = start + jnp.arange(chunk, dtype=jnp.int32)
        rows, prios = traffic.make_rows(key, idx, spec, params)
        data = jax.tree_util.tree_map(
            lambda buf, new: put(buf, new, start), state.data, to_batch(rows))
        prios = jnp.maximum(prios, reference.PRIORITY_EPS)
        return dataclasses.replace(
            state, data=data, priority=put(state.priority, prios, start))

    def empty(key):
        example, _ = traffic.make_rows(key, jnp.zeros((1,), jnp.int32), spec, params)
        return trainer.arena.init_state(to_batch(example))

    state = jax.jit(empty)(key)
    fill = jax.jit(one, donate_argnums=0)
    for start in range(0, capacity, chunk):
        state = fill(state, key, jnp.int32(start))
    return dataclasses.replace(
        state, total_added=jnp.asarray(capacity, state.total_added.dtype))


def setup(ctx: Context) -> Session:
    exp = build_experiment(ctx)
    trainer = build_trainer(ctx, exp)
    cfg = ctx.config
    spec = traffic.row_spec(cfg, exp.agent.seq_len, exp.hidden)

    ctx.log("program built")
    train = make_train_state(trainer, spec, cfg, ctx.seed)
    jax.block_until_ready(train)
    ctx.log("weights made")
    arena = fill_arena(ctx, trainer, spec)
    rng0 = traffic.seed_key(ctx.seed, traffic.STREAM_RUN)
    jax.block_until_ready(arena.priority)
    ctx.log(f"arena filled: {trainer.arena.capacity} sequences")

    def timed(train, arena, rng):
        rng, key = jax.random.split(rng)
        train, arena, metrics = trainer._learn_many(train, arena, key)
        return train, arena, rng, metrics

    call = jax.jit(timed, donate_argnums=(0, 1))
    s = Session(trainer=trainer, call=call, state=(train, arena, rng0),
                hp=hyperparameters(exp), spec=spec, first=[], rng0=rng0,
                in_flight=int(ctx.cell["traffic"]["in_flight_calls"]))

    # The first calls, from the seed, through the window's own compiled call.
    prio = np.array(arena.priority, copy=True)
    for c in range(FIRST_CALLS):
        train, arena, rng, metrics = s.call(*s.state)
        s.state = (train, arena, rng)
        after = np.array(arena.priority, copy=True)
        rec = {
            "metrics": {k: float(v) for k, v in jax.device_get(metrics).items()},
            "priority_before": prio,
            "priority_after": after,
        }
        if c == 0:
            rec["opt"] = jax.device_get(
                (train.actor_opt_state, train.critic_opt_state))
        if c == FIRST_CALLS - 1:
            rec["params"] = follow.train_params(train)
            rec["step"] = int(train.step)
        s.first.append(rec)
        prio = after
        ctx.log(f"first call {c + 1} done")
    return s


def _dispatch(s: Session):
    train, arena, rng, metrics = s.call(*s.state)
    s.state = (train, arena, rng)
    return metrics["critic_loss"]


def window(s: Session, seconds: float) -> Dict[str, Any]:
    """Dispatch the timed call back to back for ``seconds``; at most
    ``in_flight_calls`` (the workload file's) are queued ahead of the device,
    as the training loop queues its phases: deep enough that a stall of the
    host thread of a tenth of a second does not starve the chip."""
    K = s.trainer.config.learner_steps
    jax.block_until_ready(s.state)
    pending = collections.deque()
    calls = 0
    t0 = time.perf_counter()
    with jax.profiler.TraceAnnotation("chipbench/window"):
        while time.perf_counter() - t0 < seconds:
            pending.append(_dispatch(s))
            calls += 1
            if len(pending) > s.in_flight:
                pending.popleft().block_until_ready()
        jax.block_until_ready(s.state)
    elapsed = time.perf_counter() - t0
    return {
        "elapsed_s": elapsed,
        "calls": calls,
        "attempted": calls * K,
        "failed": 0,
        "metrics": {"learner_steps_per_s": calls * K / elapsed},
    }


def probes(s: Session) -> Dict[str, Any]:
    """The two replay operations alone, through the arena's public entries,
    each a jitted program of its own so that the trace finds it by name, on
    the cell's full arena and batch."""
    trainer = s.trainer
    B = trainer.config.batch_size
    reps = 32
    train, arena, rng = s.state

    def replay_sample(arena, key):
        with jax.named_scope("chipbench/replay_sample"):
            return trainer.arena.sample(arena, key, B)

    def replay_update(arena, idx, vals):
        with jax.named_scope("chipbench/replay_update"):
            return trainer.arena.update_priorities(arena, idx, vals)

    sample = jax.jit(replay_sample)
    update = jax.jit(replay_update, donate_argnums=0)
    keys = jax.random.split(jax.random.PRNGKey(0), reps + 1)
    res = sample(arena, keys[reps])  # warm-up, outside the capture's count
    arena = update(arena, res.indices, res.probs + 1.0)
    jax.block_until_ready(arena.priority)
    with jax.profiler.TraceAnnotation("chipbench/probes"):
        for i in range(reps):
            res = sample(arena, keys[i])
            arena = update(arena, res.indices, res.probs + 1.0)
        jax.block_until_ready(arena.priority)
    s.state = (train, arena, rng)
    return {"reps": reps, "sample_program": "jit_replay_sample",
            "update_program": "jit_replay_update"}


def check(ctx: Context, s: Session) -> List[Compared]:
    """Free the program's state, then follow the first calls with the plain
    reference at JAX's default matmul precision, which is what the
    configuration runs at (``reference.PRECISIONS``)."""
    s.state = None  # the arena goes before the reference comes
    out = _follow(ctx, s)
    limits = ctx.cell["limits"]
    return [Compared(name, out[name], limits[name]) for name in sorted(out)]


def _follow(ctx: Context, s: Session) -> Dict[str, float]:
    tcfg = s.trainer.config
    K, capacity = tcfg.learner_steps, s.trainer.arena.capacity
    replay = {"batch_size": tcfg.batch_size, "alpha": tcfg.priority_alpha,
              "beta0": tcfg.beta0, "beta_steps": tcfg.beta_steps}
    first, rng, spec, hp = s.first, s.rng0, s.spec, s.hp
    params = ctx.cell["traffic"]

    actor, critic = traffic.make_weights(
        traffic.seed_key(ctx.seed, traffic.STREAM_WEIGHTS),
        reference.weight_shapes(ctx.config))
    p0 = jax.device_get({"actor": actor, "critic": critic,
                         "target_actor": actor, "target_critic": critic})
    ref = reference.init_state(actor, critic)
    update = reference.at(
        PRECISION, lambda st, rows, w: reference.learner_update(st, rows, w, hp))
    row_key = traffic.seed_key(ctx.seed, traffic.STREAM_ROWS)
    make = jax.jit(lambda key, idx: traffic.make_rows(key, idx, spec, params))

    def rows_of(slots):
        return make(row_key, jnp.asarray(slots, jnp.int32))[0]

    # The reference's own priority vector: the seed's, floored as stored.
    ref_prio = np.maximum(
        np.asarray(jax.jit(lambda key: make(key, jnp.arange(capacity, dtype=jnp.int32))[1])(row_key)),
        np.float32(reference.PRIORITY_EPS))
    out: Dict[str, float] = {
        "loss_gap": 0.0, "priority_gap": follow.priority_gap(
            first[0]["priority_before"], ref_prio, np.arange(capacity)),
        "sample_gap": 0.0, "slots_unmatched": 0.0,
    }
    first_grads = None
    for c, rec in enumerate(first):
        rng, keys = follow.call_keys(rng, K)
        changed = np.flatnonzero(rec["priority_before"] != rec["priority_after"])
        f = follow.learner_call(ref, ref_prio, rec["priority_before"],
                                rec["priority_after"], changed, keys,
                                rows_of, capacity, replay, update,
                                near=float(ctx.cell["near_slot_widths"]))
        ref, ref_prio = f["ref"], f["ref_prio"]
        first_grads = f["first_grads"] if c == 0 else first_grads
        out["sample_gap"] = max(out["sample_gap"], f["sample_gap"])
        out["slots_unmatched"] += f["draws_unplaced"] + len(
            np.setxor1d(changed, np.unique(f["slots"])))
        out["loss_gap"] = max(out["loss_gap"], follow.loss_gap(rec["metrics"], f["losses"]))
        out["priority_gap"] = max(
            out["priority_gap"],
            follow.priority_gap(rec["priority_after"], ref_prio, changed))
        if c == 0:
            out["grad_gap"], leaf = follow.grad_gap(rec["opt"], ref)
            ctx.log(f"grad_gap worst leaf: {leaf}")
    last = first[-1]
    gaps = follow.change_gaps(last["params"], ref, p0, first_grads)
    ctx.log(f"worst leaves: {gaps.pop('where')}")
    out.update(gaps)
    out["steps_gap"] = abs(last["step"] - int(ref["step"]))
    return out
