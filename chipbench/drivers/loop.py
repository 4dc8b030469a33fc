"""The whole training loop: ``Trainer.train_phase`` called for the window.

What ``train --config <name>`` does every phase: ``stride`` environment
steps of ``num_envs`` environments through the host pool (ordered
``io_callback``), the window emitted into the arena with its TD priority,
``learner_steps`` updates.  Set-up runs the schedule's own collect and fill
phases to ``min_replay``, then the first three train phases, which the
reference follows afterwards, and hands the same compiled phase and state to
the window.

The pool is the edge of the program: a recorder of the benchmark's stands
between the program and the pool, keeps what went in (actions) and what came
out (observations, rewards, discounts, resets) during set-up and the first
train phases, and keeps the host clock of every pool step throughout.  The
reference recomputes from that record what the program should have sent,
stored, ranked and learned.
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
from chipbench.harness import Compared, Context
from chipbench.program import (build_experiment, build_trainer, hyperparameters,
                               make_train_state)

FIRST_PHASES = 3  # the train phases the reference follows
PRECISION = "default"  # of the reference: what the configuration runs at
IN_FLIGHT = 2  # phases dispatched ahead of the one waited for


class Recorder:
    """Stands between the program and its environment pool."""

    def __init__(self, pool):
        self.pool = pool
        self.recording = True
        self.reset_out = None
        self.steps: List[tuple] = []
        self.step_seconds = 0.0
        self.step_calls = 0
        self._reset_all, self._step_all = pool.reset_all, pool.step_all
        pool.reset_all, pool.step_all = self.reset_all, self.step_all

    def reset_all(self, seeds):
        out = self._reset_all(seeds)
        self.reset_out = tuple(np.array(x, copy=True) for x in out)
        return out

    def step_all(self, actions, repeat: int = 1):
        t0 = time.perf_counter()
        out = self._step_all(actions, repeat=repeat)
        self.step_seconds += time.perf_counter() - t0
        self.step_calls += 1
        if self.recording:
            self.steps.append(
                (np.array(actions, np.float32, copy=True),)
                + tuple(np.array(x, copy=True) for x in out)
            )
        return out

    def close(self):
        self.pool.reset_all, self.pool.step_all = self._reset_all, self._step_all
        if hasattr(self.pool, "close"):
            self.pool.close()


@dataclasses.dataclass
class Session:
    trainer: Any
    state: Any  # the TrainerState, donated into every phase
    recorder: Recorder
    hp: Dict[str, Any]
    spec: Dict[str, Any]
    first: List[Dict[str, Any]]
    rng0: Any
    phases_before: int  # collect and fill phases run in set-up
    warm: int


def setup(ctx: Context) -> Session:
    exp = build_experiment(ctx)
    env = exp.env_factory()
    recorder = Recorder(env._pool)
    trainer = build_trainer(ctx, exp, env)
    cfg, tcfg = ctx.config, trainer.config
    if tuple(env.spec.obs_shape) != tuple(cfg["obs_shape"]) or (
        env.spec.action_dim != cfg["action_dim"]
    ):
        raise ValueError("the configuration file's shapes are not the environment's")
    spec = traffic.row_spec(cfg, exp.agent.seq_len, exp.hidden)
    ctx.log("program built")

    key = traffic.seed_key(ctx.seed, traffic.STREAM_RUN)
    state = trainer.init(key)
    train = make_train_state(trainer, spec, cfg, ctx.seed)
    state = dataclasses.replace(
        state, train=train,
        behavior_params=jax.tree_util.tree_map(jnp.copy, train.actor_params))
    rng0 = jax.random.split(key, 3)[2]  # the run key ``init`` keeps
    warm, fill = trainer.window_fill_phases, trainer.replay_fill_phases
    for _ in range(warm):
        state = trainer.collect_phase(state)
    for _ in range(fill):
        state = trainer.fill_phase(state)
    jax.block_until_ready(state.arena.priority)
    ctx.log(f"{warm} collect and {fill} fill phases done")

    s = Session(trainer=trainer, state=state, recorder=recorder,
                hp=hyperparameters(exp), spec=spec, first=[], rng0=rng0,
                phases_before=warm + fill, warm=warm)
    E = tcfg.num_envs
    prio = np.array(state.arena.priority, copy=True)
    for i in range(FIRST_PHASES):
        s.state, metrics = trainer.train_phase(s.state)
        st = s.state
        after = np.array(st.arena.priority, copy=True)
        slots = (fill + i) * E + np.arange(E)
        rec = {
            "metrics": {k: float(v) for k, v in jax.device_get(metrics).items()},
            "priority_before": prio,
            "priority_after": after,
            "slots": slots,
            "rows": jax.device_get(jax.tree_util.tree_map(
                lambda buf: buf[slots], st.arena.data)),
        }
        if i == 0:
            rec["opt"] = jax.device_get(
                (st.train.actor_opt_state, st.train.critic_opt_state))
        if i == FIRST_PHASES - 1:
            rec["params"] = follow.train_params(st.train)
            rec["step"] = int(st.train.step)
            rec["total_added"] = int(st.arena.total_added)
        s.first.append(rec)
        prio = after
        ctx.log(f"first train phase {i + 1} done")
    recorder.recording = False
    return s


def window(s: Session, seconds: float) -> Dict[str, Any]:
    """Call the fused train phase for ``seconds``."""
    from r2d2dpg_tpu.utils.profiling import annotate

    tcfg = s.trainer.config
    jax.block_until_ready(s.state)
    pending = collections.deque()
    phases, pool0 = 0, s.recorder.step_seconds
    t0 = time.perf_counter()
    with jax.profiler.TraceAnnotation("chipbench/window"):
        while time.perf_counter() - t0 < seconds:
            with annotate("trainer/train_phase"):
                s.state, metrics = s.trainer.train_phase(s.state)
            pending.append(metrics["critic_loss"])
            phases += 1
            if len(pending) > IN_FLIGHT:
                pending.popleft().block_until_ready()
        jax.block_until_ready(s.state)
    elapsed = time.perf_counter() - t0
    steps = phases * tcfg.num_envs * tcfg.stride
    return {
        "elapsed_s": elapsed,
        "calls": phases,
        "attempted": steps,
        "failed": 0,
        "pool_seconds": s.recorder.step_seconds - pool0,
        "metrics": {"agent_steps_per_s": steps / elapsed},
    }


def _stream(rec: Recorder):
    """The record as arrays over time: ``obs[t]`` and ``reset[t]`` are what
    the policy saw at step ``t``; ``sent[t]``, ``reward[t]``, ``discount[t]``
    what that step gave to and got from the pool."""
    obs = np.stack([rec.reset_out[0]] + [st[1] for st in rec.steps])
    reset = np.stack([rec.reset_out[3]] + [st[4] for st in rec.steps])
    return {
        "obs": obs, "reset": reset,
        "sent": np.stack([st[0] for st in rec.steps]),
        "reward": np.stack([st[2] for st in rec.steps]),
        "discount": np.stack([st[3] for st in rec.steps]),
    }


def check(ctx: Context, s: Session) -> List[Compared]:
    """Free the program's state, then follow the record with the reference:
    every policy step from the reset on, every emitted window with its
    carries and its TD priority, and the learner calls of the first three
    train phases."""
    stream = _stream(s.recorder)
    due = (s.phases_before + FIRST_PHASES) * s.trainer.config.stride
    if len(s.recorder.steps) != due:
        raise ValueError(f"the record holds {len(s.recorder.steps)} pool steps, "
                         f"{due} were due")
    s.recorder.close()
    s.state = None  # the arena goes before the reference comes
    out = _follow(ctx, s, stream)
    limits = ctx.cell["limits"]
    return [Compared(name, out[name], limits[name]) for name in sorted(out)]


def _follow(ctx: Context, s: Session, stream) -> Dict[str, float]:
    tcfg = s.trainer.config
    n_phases = s.phases_before + FIRST_PHASES
    capacity = s.trainer.arena.capacity
    E, S, K, L = tcfg.num_envs, tcfg.stride, tcfg.learner_steps, s.spec["seq_len"]
    replay = {"batch_size": tcfg.batch_size, "alpha": tcfg.priority_alpha,
              "beta0": tcfg.beta0, "beta_steps": tcfg.beta_steps}
    first, hp, rng = s.first, s.hp, s.rng0

    actor, critic = traffic.make_weights(
        traffic.seed_key(ctx.seed, traffic.STREAM_WEIGHTS),
        reference.weight_shapes(ctx.config))
    p0 = jax.device_get({"actor": actor, "critic": critic,
                         "target_actor": actor, "target_critic": critic})
    ref = reference.init_state(actor, critic)
    update = reference.at(
        PRECISION, lambda st, rows, w: reference.learner_update(st, rows, w, hp))
    rank = reference.at(
        PRECISION, lambda st, rows: reference.initial_priority(st, rows, hp))
    collect = reference.at(PRECISION, reference.collect_steps)
    sigmas = reference.sigma_ladder(E, tcfg.sigma_max, tcfg.ladder_alpha)
    H = s.spec["hidden"]
    zeros = (jnp.zeros((E, H), jnp.float32), jnp.zeros((E, H), jnp.float32))
    ca, cc = zeros, zeros
    before: Dict[int, Any] = {}  # step -> both nets' carries before it
    emitted: List[Dict[str, Any]] = []  # one batch of E rows a fill/train phase
    ref_prio = np.zeros((capacity,), np.float32)

    def rows_of(slots):
        parts = [jax.tree_util.tree_map(lambda x: x[sl % E], emitted[sl // E])
                 for sl in np.asarray(slots)]
        return jax.tree_util.tree_map(lambda *xs: np.stack(xs), *parts)

    out = {"action_gap": 0.0, "row_gap": 0.0, "carry_gap": 0.0, "rank_gap": 0.0,
           "loss_gap": 0.0, "priority_gap": 0.0, "sample_gap": 0.0,
           "slots_unmatched": 0.0}
    first_grads = None
    for p in range(n_phases):
        t0 = p * S
        rng, scan_key = jax.random.split(rng)
        keys = jax.random.split(scan_key, S)
        acts, carries, last = collect(
            ref["actor"], ref["critic"], stream["obs"][t0:t0 + S],
            stream["reset"][t0:t0 + S], stream["sent"][t0:t0 + S], ca, cc, keys, sigmas)
        ca, cc = last["actor"], last["critic"]
        carries = jax.device_get(carries)
        for t in range(S):
            before[t0 + t] = jax.tree_util.tree_map(lambda x: x[t], carries)
        for old in [k for k in before if k < t0 + S - L - S]:
            del before[old]
        i = p - s.phases_before  # index among the followed train phases
        if i >= 0:
            out["action_gap"] = max(out["action_gap"], float(np.max(np.abs(
                np.asarray(acts) - stream["sent"][t0:t0 + S]))))
        if p < s.warm:
            continue
        # The window after this phase, one row an environment.
        start = t0 + S - L
        tm = lambda x: np.swapaxes(x[start:start + L], 0, 1)  # noqa: E731
        rows = {"obs": tm(stream["obs"]), "action": tm(stream["sent"]),
                "reward": tm(stream["reward"]), "discount": tm(stream["discount"]),
                "reset": tm(stream["reset"]), "carries": before[start]}
        j = len(emitted)
        emitted.append(rows)
        slots = j * E + np.arange(E)
        ref_prio[slots] = np.maximum(np.asarray(rank(ref, rows)),
                                     np.float32(reference.PRIORITY_EPS))
        if i < 0:
            continue
        rec = first[i]
        if i == 0:  # what the fill phases ranked, before any update
            out["rank_gap"] = follow.priority_gap(
                rec["priority_before"], ref_prio, np.arange(j * E))
        got = rec["rows"]
        for name in ("obs", "action", "reward", "discount", "reset"):
            out["row_gap"] = max(out["row_gap"], float(np.max(np.abs(
                np.asarray(getattr(got, name), np.float64) - rows[name]))))
        for net in ("actor", "critic"):
            for a, b in zip(got.carries[net], rows["carries"][net]):
                out["carry_gap"] = max(out["carry_gap"],
                                       float(np.max(np.abs(np.asarray(a) - b))))
        rng, keys = follow.call_keys(rng, K)
        changed = np.flatnonzero(rec["priority_before"] != rec["priority_after"])
        # The vector the phase's updates drew against: the program's before
        # the phase, and in the slots the phase itself filled (which it may
        # have drawn and overwritten since) the reference's own ranking.
        drawn_from = np.array(rec["priority_before"], np.float32, copy=True)
        drawn_from[slots] = ref_prio[slots]
        f = follow.learner_call(
            ref, ref_prio, drawn_from, rec["priority_after"], changed, keys, rows_of,
            (j + 1) * E, replay, update, near=float(ctx.cell["near_slot_widths"]),
            must_cover=np.setdiff1d(changed, slots),
            # No update has moved the weights since the fill phases ranked
            # their rows: the first update writes back what a slot held.
            open_slots=np.arange((j + 1) * E) if i == 0 else None,
            metrics=rec["metrics"])
        ref, ref_prio = f["ref"], f["ref_prio"]
        first_grads = f["first_grads"] if i == 0 else first_grads
        out["sample_gap"] = max(out["sample_gap"], f["sample_gap"])
        drawn = np.unique(f["slots"][1:] if i == 0 else f["slots"])
        out["slots_unmatched"] += (
            f["draws_unplaced"]
            + len(np.setdiff1d(changed, np.union1d(np.unique(f["slots"]), slots)))
            + len(np.setdiff1d(drawn, changed)))
        out["loss_gap"] = max(out["loss_gap"], follow.loss_gap(rec["metrics"], f["losses"]))
        out["priority_gap"] = max(out["priority_gap"], follow.priority_gap(
            rec["priority_after"], ref_prio, changed))
        if i == 0:
            out["grad_gap"], leaf = follow.grad_gap(rec["opt"], ref)
            ctx.log(f"grad_gap worst leaf: {leaf}")
    last_rec = first[-1]
    gaps = follow.change_gaps(last_rec["params"], ref, p0, first_grads)
    ctx.log(f"worst leaves: {gaps.pop('where')}")
    out.update(gaps)
    out["steps_gap"] = abs(last_rec["step"] - int(ref["step"])) + abs(
        last_rec["total_added"] - len(emitted) * E)
    return out
