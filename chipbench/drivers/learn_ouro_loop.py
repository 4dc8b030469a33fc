"""The saturated learner over Ouro-2.6B's looped stack: ``drivers/learn.py``'s
timed call (``Trainer._learn_many`` jitted, state donated, dispatched back to
back on an arena filled to capacity from the seed) for the configuration
whose core is 4 layers run 4 times with one set of weights.

The recipe is the sdar cell's (Adam 1e-3 on a 210 M-parameter critic, no
warm-up: two sound trajectories part within calls), so the scheme of
``drivers/learn_sdar_moe.py`` is kept and its rows, weights, arena fill, leaf
norms and slot inference are imported from there: each of the first three
calls is compared **from the program's own state at its start** by the
priorities its first update wrote back, and the first call is followed whole
from the seed for ``grad_gap``, ``update_gap``, ``target_gap``,
``sample_gap``, ``slots_unmatched`` and ``steps_gap``.

What is read otherwise than there, each for what 45 chip runs of this cell
showed (my chip runs, PR 31; PERF.md section 2).  The float32 program (one
bfloat16 pass of the MXU a product) and its bfloat16 control lie a factor of
two apart here, not ten, and a state four updates old may be one where Q has
grown from 0.4 to 17 and every gap is five times the usual:

- ``priority_gap`` is the gap that NINE TENTHS of the first call's
  first-update slots lie within.  That update's state is the seed's alone,
  as well conditioned in every run: 1.24e-3 to 1.78e-3 on 26 sound runs,
  2.64e-3 to 3.45e-3 on 14 under bfloat16, which spreads the upper slots
  more than the median one (5.2e-4 to 8.9e-4 against 9.6e-4 to 1.46e-3).
  It is the number the control has to fail.
- ``later_priority_gap`` is the wider of the later calls' medians, each from
  the program's own nets before that call, a slot's gap held to its priority
  or to the update's mean ``|Q|``, whichever is larger (a priority is a TD
  error, the difference of two Q values; the nets' rounding is Q's).  It
  reads 1e-4 to 2.7e-3 sound and 1e-3 to 1.1e-2 under bfloat16: it holds a
  later state to the reference, not a precision.
- ``grad_gap``, ``update_gap`` and ``target_gap``, over the first timed call's
  four updates, are the gap that HALF of the 34 leaves lie within (a block's
  leaves are stacked over the layers: few and large).  After four updates
  of this recipe every quantile still parts the faults from a sound run's
  chaos, the median with the most room (gradient: 4.9 times; the third
  quartile 2.0, the worst leaf 2.7), and the worst leaf of a sound run is
  ``critic/head/bias`` on 11 seeds of 25 and a norm's scale on 6: sums over
  all rows that nearly cancel.  They hold the chain of four updates; the
  worst leaf is held where it is well conditioned, after one.
- ``loss_gap`` and ``single_grad_gap`` are read from ONE update alone: after
  the window the program's own ``_learn_many`` with ``learner_steps`` 1 (the
  timed call's twin, same experiment, same plants) is run once from the
  seed's state, and the reference follows that update.  Its two losses are
  that update's, not a mean over four, and Adam's first moment after it is a
  tenth of the clipped gradient: ``single_grad_gap`` holds it by the WORST of
  the 34 leaves.  The timed call's own mean losses over four updates are the
  recipe's chaos (0.0007 to 0.49 on 13 sound seeds, 0.088 to 1.26 under the
  faults: logged, not compared), and after four updates a sound run's worst
  leaf reads 0.003 to 0.2 where a fault's reads 0.55 to 1.45 (PERF.md
  section 2 has every quantile's readings with the leaves named).  The
  weights' change after ONE update is not compared: Adam's first step is
  ``lr * sign(g)``, the same norm whatever the gradient.
- The slots of an update are inferred against the program's own end-of-call
  priority where it is the reference's to 5 % (a slot not drawn again, by a
  sound program), and against the reference's elsewhere: the written-back
  priorities are a tenth of the vector's mass after two updates.  A heavy
  slot drawn again still moves a draw by up to 2.8 slot widths on one sound
  seed in ten, and two of a call's 256 draws then land beside their slot:
  ``slots_unmatched`` has a limit of 3 here, between that and a fault's 6.

What is this file's own: the experiment builder (the stack's sizes come from
the published keys of the configuration file), the reference
(``reference_ouro_loop.py``: jitted pieces chained in Python, so "the
reference's update" is an object's method, not one jitted program), the
window (which averages the ``loop/`` counters over its calls) and one more
compared number, ``loop_change_gap``: the program's counter
``loop/last_step_rel_change`` over the first call against the reference's
own ``|h^(R) - h^(R-1)| / |h^(R-1)|`` over the same four updates: what a
stack run once too few reads differently whatever its outputs do.

Controls and faults beside ``plants.py``'s (``--plant``): ``loop_steps_short``
(the stack run ``total_ut_steps - 1`` times) and ``last_use_gradient`` (the
shared weights' gradient taken from the last loop step's use alone:
``stop_gradient`` on the weights the steps before it read).
"""

from __future__ import annotations

import collections
import dataclasses
import json
import os
import time
from typing import Any, Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import compare, follow, harness, plants, reference, reference_ouro_loop as ref_ouro, traffic
from chipbench.drivers import learn, learn_sdar_moe as sdar
from chipbench.harness import Compared, Context
from chipbench.program import build_trainer, hyperparameters

FIRST_CALLS = 3  # the calls whose first update is followed; the first one whole
PRECISION = learn.PRECISION
OWN_PLANTS = ("loop_steps_short", "last_use_gradient")
COUNTER = "loop/last_step_rel_change"
HALF = 0.5  # of the leaves: "the gap that half of them lie within"
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# --------------------------------------------------------------- experiment
def build_experiment(ctx: Context):
    """The program's own experiment config with the configuration file's
    numbers applied: agent and trainer fields under their own names, the
    stack's sizes from the published keys."""
    from r2d2dpg_tpu.configs import get_config

    cfg = ctx.config
    exp = get_config(cfg["program_config"])
    z = ref_ouro.sizes(cfg)

    def apply(obj):
        fields = {f.name for f in dataclasses.fields(obj)}
        return dataclasses.replace(obj, **{k: cfg[k] for k in fields if k in cfg})

    ouro = dataclasses.replace(
        exp.ouro, hidden=z["H"], layers=z["L"], heads=z["heads"], head_dim=z["D"],
        mlp_width=z["W"], rope_theta=z["theta"], rms_eps=z["eps"],
        loop_steps=z["R"] - (ctx.plant == "loop_steps_short"),
    )
    exp = dataclasses.replace(
        exp, agent=apply(exp.agent), hidden=z["H"], ouro=ouro,
        compute_dtype=cfg["compute_dtype"],
        trainer=dataclasses.replace(apply(exp.trainer),
                                    seed=int(ctx.seed) & 0x7FFFFFFF),
    )
    if ctx.plant in OWN_PLANTS:
        if ctx.plant == "last_use_gradient":
            _last_use_gradient()
        return exp
    return plants.on_experiment(exp, ctx.plant)


def _last_use_gradient() -> None:
    """Put the fault under the core's seam ``models/ouro_loop.py::loop`` (a
    module function looked up when the call is traced): the loop steps before
    the last read the weights under ``stop_gradient``."""
    from r2d2dpg_tpu.models import ouro_loop

    inner = ouro_loop.loop

    def loop(stack, weights, x, past, steps):
        if steps < 2:
            return inner(stack, weights, x, past, steps)
        take = lambda a, b: jax.tree_util.tree_map(lambda m: m[a:b], past)  # noqa: E731
        x, left, _ = inner(stack, jax.lax.stop_gradient(weights), x,
                           take(0, steps - 1), steps - 1)
        x, last, moved = inner(stack, weights, x, take(steps - 1, steps), 1)
        return x, jax.tree_util.tree_map(
            lambda a, b: jnp.concatenate([a, b]), left, last), moved

    ouro_loop.loop = loop


# ------------------------------------------------------- weights and state
def seeded_weights(seed: int, cfg):
    """Both nets' weights from the seed (``learn_sdar_moe.make_weights``: a
    stack of layers is a stack of kernels, each by its own fan-in)."""
    return sdar.make_weights(traffic.seed_key(seed, traffic.STREAM_WEIGHTS),
                             ref_ouro.weight_shapes(cfg))


def make_train_state(trainer, spec, config, seed: int):
    """The program's ``TrainState`` around the seed's weights, made on the
    reference's own tree of shapes, which the program's tree has to be."""
    from r2d2dpg_tpu.agents.ddpg import TrainState

    obs = jnp.zeros((1,) + spec["obs_shape"], jnp.float32)
    act = jnp.zeros((1, spec["action_dim"]), jnp.float32)
    st = jax.eval_shape(lambda k: trainer.agent.init(k, obs, act), jax.random.PRNGKey(0))

    def laid_out(tree):
        return [(jax.tree_util.keystr(path), tuple(s.shape), jnp.dtype(s.dtype))
                for path, s in jax.tree_util.tree_flatten_with_path(tree)[0]]

    if laid_out(ref_ouro.weight_shapes(config)) != laid_out(
            (st.actor_params, st.critic_params)):
        raise ValueError("the program's weights are not laid out as the "
                         "reference's: chipbench/reference_ouro_loop.py::weight_shapes")
    actor, critic = seeded_weights(seed, config)
    copy = lambda t: jax.tree_util.tree_map(jnp.copy, t)  # noqa: E731
    return TrainState(
        actor_params=actor, critic_params=critic,
        target_actor_params=copy(actor), target_critic_params=copy(critic),
        actor_opt_state=trainer.agent.actor_tx.init(actor),
        critic_opt_state=trainer.agent.critic_tx.init(critic),
        step=jnp.zeros((), jnp.int32),
    )


# ------------------------------------------------------------------- phases
@dataclasses.dataclass
class Session(learn.Session):
    single: Any = None  # the timed call's twin of ONE update a call


def program(ctx: Context):
    """What of a run does not depend on its seed: the trainer, the compiled
    timed call, the hyperparameters, the rows' shapes, and the timed call's
    twin with ``learner_steps`` 1 (the same experiment under the same plant,
    compiled when ``check`` first calls it: after the window)."""
    exp = build_experiment(ctx)
    trainer = build_trainer(ctx, exp)
    cfg = ctx.config
    spec = {"seq_len": exp.agent.seq_len, "obs_shape": tuple(cfg["obs_shape"]),
            "action_dim": int(cfg["action_dim"])}

    def timed_call(trainer):
        def timed(train, arena, rng):
            rng, key = jax.random.split(rng)
            train, arena, metrics = trainer._learn_many(train, arena, key)
            return train, arena, rng, metrics

        return jax.jit(timed, donate_argnums=(0, 1))

    one = dataclasses.replace(exp, trainer=dataclasses.replace(exp.trainer, learner_steps=1))
    return (trainer, timed_call(trainer), hyperparameters(exp), spec,
            timed_call(build_trainer(ctx, one)))


def setup(ctx: Context) -> Session:
    trainer, call, hp, spec, single = program(ctx)
    ctx.log("program built")
    train = make_train_state(trainer, spec, ctx.config, ctx.seed)
    jax.block_until_ready(train)
    ctx.log("weights made")
    arena = sdar.fill_arena(ctx, trainer, spec)
    rng0 = traffic.seed_key(ctx.seed, traffic.STREAM_RUN)
    jax.block_until_ready(arena.priority)
    ctx.log(f"arena filled: {trainer.arena.capacity} sequences")
    s = Session(
        trainer=trainer, call=call, state=(train, arena, rng0), hp=hp, spec=spec,
        first=[], rng0=rng0, in_flight=int(ctx.cell["traffic"]["in_flight_calls"]),
        single=single)

    # The first calls, from the seed, through the window's own compiled call.
    # What the follow needs of the program's state goes to the host: the four
    # nets as they stand between the calls and Adam's state after the first.
    prio = np.array(arena.priority, copy=True)
    nets = None
    for c in range(FIRST_CALLS):
        rec = {"priority_before": prio, "nets_before": nets}
        train, arena, rng, metrics = s.call(*s.state)
        s.state = (train, arena, rng)
        rec["metrics"] = {k: np.asarray(v) for k, v in jax.device_get(metrics).items()}
        rec["priority_after"] = prio = np.array(arena.priority, copy=True)
        if c == 0:
            rec["opt"] = jax.device_get((train.actor_opt_state, train.critic_opt_state))
            rec["step"] = int(train.step)
        if c < FIRST_CALLS - 1:
            rec["nets_after"] = nets = follow.train_params(train)
        s.first.append(rec)
        ctx.log(f"first call {c + 1} done")
    return s


def window(s: Session, seconds: float) -> Dict[str, Any]:
    """``learn.window``, which also keeps each call's ``loop/`` counters: their
    mean over the window's calls is what the per-layer metrics read."""
    K = s.trainer.config.learner_steps
    jax.block_until_ready(s.state)
    pending = collections.deque()
    loop: Dict[str, float] = {}
    calls = 0

    def finish(metrics):
        metrics["critic_loss"].block_until_ready()
        for k, v in metrics.items():
            if k.startswith("loop/"):
                loop[k] = loop.get(k, 0.0) + float(v)

    t0 = time.perf_counter()
    with jax.profiler.TraceAnnotation("chipbench/window"):
        while time.perf_counter() - t0 < seconds:
            train, arena, rng, metrics = s.call(*s.state)
            s.state = (train, arena, rng)
            pending.append(metrics)
            calls += 1
            if len(pending) > s.in_flight:
                finish(pending.popleft())
        jax.block_until_ready(s.state)
    elapsed = time.perf_counter() - t0
    while pending:
        finish(pending.popleft())
    return {
        "elapsed_s": elapsed, "calls": calls, "attempted": calls * K, "failed": 0,
        "metrics": {"learner_steps_per_s": calls * K / elapsed},
        "loop": {k: v / max(calls, 1) for k, v in loop.items()},
    }


# ------------------------------------------------------------------ correct
def learner_call(ref, ref_prio, before, after, changed, keys, rows_of, size, replay, update):
    """``len(keys)`` updates of one timed call, followed from ``ref``:
    ``learn_sdar_moe.learner_call``'s inference of the slots (the changed
    slots nearest the reference's own uniforms in the float64 CDF of the
    priorities as the updates before left them), with this core's counter
    kept an update where the sdar core keeps a routing table, and with what
    is laid over the program's vector ``before`` after an update: the
    program's own priority ``after`` the call where that is the reference's
    to 5 %, the reference's elsewhere (a slot drawn again holds its last
    value, not this update's; a faulty program's values tell nothing)."""
    B, alpha = int(replay["batch_size"]), replay["alpha"]
    current = np.array(before, np.float32, copy=True)
    out: Dict[str, Any] = {"slots": [], "prios": [], "moved": [], "losses": [],
                           "sample_gap": 0.0}
    for k, key in enumerate(keys):
        mass = reference.scaled_mass(current, alpha)
        cdf = np.cumsum(mass)
        width = cdf[-1] / max(int(np.count_nonzero(mass)), 1)
        u = np.asarray(jax.random.uniform(key, (B,)), np.float64) * cdf[-1]
        gaps = compare.mass_gaps(cdf, width, u, changed)
        pick = np.argmin(gaps, axis=1)
        drawn = changed[pick]
        out["sample_gap"] = max(out["sample_gap"], float(gaps[np.arange(B), pick].max()))
        own = reference.scaled_mass(ref_prio, alpha)
        w = reference.is_weights(own[drawn] / max(float(own.sum()), 1e-12), size,
                                 int(ref["step"]), replay["beta0"], replay["beta_steps"])
        ref, prios, ls = update(ref, rows_of(drawn), jnp.asarray(w))
        prios = np.maximum(np.asarray(prios, np.float32), np.float32(reference.PRIORITY_EPS))
        ref_prio = reference.write_priorities(ref_prio, drawn, prios)
        mine = np.asarray(after, np.float32)[drawn]
        current = reference.write_priorities(
            current, drawn, np.where(np.abs(mine - prios) <= 0.05 * prios, mine, prios))
        if k == 0:
            out["first_grads"] = {n: float(v) for n, v in
                                  sdar.by_path(jax.device_get(ls["grads"])).items()}
        out["slots"].append(drawn)
        out["prios"].append(prios)
        out["moved"].append(float(ls["last_step_rel_change"]))
        out["losses"].append({n: float(ls[n]) for n in ("critic_loss", "actor_loss", "q_abs_mean")})
    return dict(out, ref=ref, ref_prio=ref_prio)


def check(ctx: Context, s: Session) -> List[Compared]:
    """With ``--trace 1`` the stage table first, while the program still holds
    its state; then the program's single update from the seed's state; then
    that state goes (the learner's 6.6 GB before the reference's come) and
    the single update and the first calls are followed with the plain
    reference at JAX's default matmul precision, which is what the
    configuration runs at."""
    if ctx.trace:
        harness.load_module("reducers", "core_stage_ms", ROOT).capture(
            ctx, lambda seconds: window(s, seconds))
    single = single_update(ctx, s)
    out = _follow(ctx, s, single)
    limits = ctx.cell["limits"]
    return [Compared(name, out[name], limits[name]) for name in sorted(out)]


def single_update(ctx: Context, s: Session) -> Dict[str, Any]:
    """One update of the program alone, from the seed's weights and the
    seed's priorities over the arena the window left (its rows are the
    seed's still): what it wrote back, its losses, and Adam's first moment
    after it, leaf by leaf as norms."""
    _, arena, _ = s.state
    s.state = None  # the window's learner state goes before the seed's comes
    seeded = s.first[0]["priority_before"]
    arena = dataclasses.replace(arena, priority=jnp.asarray(seeded))
    train = make_train_state(s.trainer, s.spec, ctx.config, ctx.seed)
    train, arena, _, metrics = s.single(train, arena, s.rng0)
    rec = {
        "priority_before": seeded,
        "priority_after": np.array(arena.priority, copy=True),
        "metrics": {k: float(v) for k, v in jax.device_get(metrics).items() if np.size(v) == 1},
        "mu": sdar.leaf_norms(dict(zip(follow.NETS, (
            follow.adam_mu(train.actor_opt_state), follow.adam_mu(train.critic_opt_state))))),
    }
    ctx.log("single update done")
    return rec


def _follow(ctx: Context, s: Session, single: Dict[str, Any]) -> Dict[str, float]:
    tcfg = s.trainer.config
    K, capacity = tcfg.learner_steps, s.trainer.arena.capacity
    replay = {"batch_size": tcfg.batch_size, "alpha": tcfg.priority_alpha,
              "beta0": tcfg.beta0, "beta_steps": tcfg.beta_steps}
    first, rng, spec, cfg = s.first, s.rng0, s.spec, ctx.config
    params = ctx.cell["traffic"]
    update = ref_ouro.Reference(s.hp, cfg, precision=PRECISION, grads="norms").update
    row_key = traffic.seed_key(ctx.seed, traffic.STREAM_ROWS)
    make = jax.jit(lambda key, idx: sdar.make_rows(key, idx, spec, params))

    def rows_of(slots):
        rows = make(row_key, jnp.asarray(slots, jnp.int32))[0]
        return {k: v for k, v in rows.items() if k != "carries"}

    def seed_state():
        actor, critic = seeded_weights(ctx.seed, cfg)
        copy = lambda t: jax.tree_util.tree_map(jnp.copy, t)  # noqa: E731
        return dict(reference.init_state(actor, critic),
                    target_actor=copy(actor), target_critic=copy(critic))

    def state_of(nets, step):
        """The reference's state around four nets; Adam's moments zero."""
        st = {n: jax.device_put(nets[n]) for n in follow.NETS + follow.TARGETS}
        return dict(st, actor_opt=reference.adam_init(st["actor"]),
                    critic_opt=reference.adam_init(st["critic"]),
                    step=jnp.asarray(step, jnp.int32))

    seeded = np.maximum(
        np.asarray(jax.jit(lambda key: make(key, jnp.arange(capacity, dtype=jnp.int32))[1])(row_key)),
        np.float32(reference.PRIORITY_EPS))
    out: Dict[str, float] = {
        "priority_gap": follow.priority_gap(first[0]["priority_before"], seeded,
                                            np.arange(capacity)),
        "sample_gap": 0.0,
    }
    # The single update: one key, every slot it wrote back, from the seed.
    before, after = single["priority_before"], single["priority_after"]
    changed = np.flatnonzero(before != after)
    if changed.size == 0:
        return {name: float("inf") for name in ctx.cell["limits"]}
    f = learner_call(seed_state(), seeded, before, after, changed,
                     follow.call_keys(rng, 1)[1], rows_of, capacity, replay, update)
    out.update(_single_update(ctx, single, f), sample_gap=f["sample_gap"])
    del f

    medians = [0.0]  # of the later calls' first updates
    for c, rec in enumerate(first):
        rng, keys = follow.call_keys(rng, K)
        before, after = rec["priority_before"], rec["priority_after"]
        changed = np.flatnonzero(before != after)
        if changed.size == 0:  # the call wrote no priority back: nothing to follow
            return {name: float("inf") for name in ctx.cell["limits"]}
        if c == 0:  # from the seed, every update
            st, own = seed_state(), seeded
        else:  # from the program's own nets before the call, its first update
            st, own, keys = state_of(rec["nets_before"], c * K), before, keys[:1]
        f = learner_call(st, own, before, after, changed, keys, rows_of, capacity, replay,
                         update)
        del st

        # The call's first update: what the state before the call decides.
        drawn = f["slots"][0]
        later = np.concatenate(f["slots"][1:]) if len(keys) > 1 else np.zeros(0, np.int64)
        once = ~np.isin(drawn, later)  # not drawn again before the vector was read
        # A priority is a TD error, the difference of two Q values: its gap is
        # held to the priority or to the mean |Q| of the update, whichever is
        # larger (where Q has grown to 17 and a TD error is 2, the nets'
        # rounding is 17's; ``follow.loss_gap`` holds the actor's loss so).
        q_abs = f["losses"][0]["q_abs_mean"]
        gap = np.abs(after[drawn] - f["prios"][0])
        rel = gap / np.maximum(f["prios"][0], q_abs)
        by_call = {"priority": sdar.spread(rel[once]), "q_abs_mean": q_abs,
                   "priority_unscaled": sdar.spread((gap / np.maximum(f["prios"][0], 1e-30))[once]),
                   "priority_median": float(np.median(f["prios"][0])),
                   "sample_gap": f["sample_gap"],
                   "drawn_once": int(once.sum()), "moved": f["moved"],
                   "program_moved": float(rec["metrics"][COUNTER])}
        if c == 0:
            out["priority_gap"] = max(out["priority_gap"], by_call["priority"]["q90"])
        else:
            medians.append(by_call["priority"]["q50"])
        out["sample_gap"] = max(out["sample_gap"], f["sample_gap"])
        if c == 0:
            out.update(_whole_call(ctx, rec, f, changed))
        del f
        ctx.log(f"call {c + 1}: {json.dumps(by_call)}")
    out["later_priority_gap"] = max(medians)
    return out


def _single_update(ctx: Context, rec, f) -> Dict[str, float]:
    """The program's one update against the reference's: both losses, and
    Adam's first moment (a tenth of the clipped gradient) by the worst leaf."""
    gaps = sdar.leaf_gaps(rec["mu"], sdar.leaf_norms(
        {"actor": f["ref"]["actor_opt"]["mu"], "critic": f["ref"]["critic_opt"]["mu"]}))
    drawn, prios = f["slots"][0], f["prios"][0]
    out = {"loss_gap": follow.loss_gap(rec["metrics"], f["losses"][0]),
           "single_grad_gap": float(np.max(list(gaps.values())))}
    worst = max(gaps, key=lambda k: gaps[k] if gaps[k] == gaps[k] else np.inf)
    ctx.log(f"single update: {json.dumps(out)}; leaves {json.dumps(sdar.spread(gaps.values()))}; "
            f"worst leaf {worst}; losses {json.dumps(f['losses'][0])} against the program's "
            f"{json.dumps({n: rec['metrics'][n] for n in ('critic_loss', 'actor_loss')})}; priorities "
            f"{json.dumps(sdar.spread(np.abs(rec['priority_after'][drawn] - prios) / prios))}; "
            f"sample gap {f['sample_gap']!r}")
    return out


def _whole_call(ctx: Context, rec, f, changed) -> Dict[str, float]:
    """The first call through all its updates from the seed: the counter,
    the slots, Adam's first moment, the weights' and the targets' change (the
    mean losses are logged, not compared: ``loss_gap`` is the single update's)."""
    ref = f["ref"]
    scalars = {k: float(v) for k, v in rec["metrics"].items() if np.size(v) == 1}
    losses = {n: float(np.mean([ls[n] for ls in f["losses"]])) for n in f["losses"][0]}
    out = {
        "loop_change_gap": compare.rel_gap(scalars[COUNTER], float(np.mean(f["moved"]))),
        # The slots whose priority changed are the slots drawn, no others.
        "slots_unmatched": float(len(np.setxor1d(changed, np.unique(np.concatenate(f["slots"]))))),
        "steps_gap": float(abs(rec["step"] - int(ref["step"]))),
    }
    mu = lambda nets: sdar.leaf_norms(dict(zip(follow.NETS, nets)))  # noqa: E731
    dead = compare.dead_leaves(f["first_grads"])
    seeds = seeded_weights(ctx.seed, ctx.config)
    p0 = dict(zip(follow.NETS + follow.TARGETS, seeds + seeds))
    change = lambda p, nets: sdar.leaf_norms({n: p[n] for n in nets}, p0)  # noqa: E731
    for name, gaps in (
        ("grad_gap", sdar.leaf_gaps(mu([follow.adam_mu(o) for o in rec["opt"]]),
                                    mu([ref["actor_opt"]["mu"], ref["critic_opt"]["mu"]]))),
        ("update_gap", sdar.leaf_gaps(change(rec["nets_after"], follow.NETS),
                                      change(ref, follow.NETS), skip=dead)),
        ("target_gap", sdar.leaf_gaps(change(rec["nets_after"], follow.TARGETS),
                                      change(ref, follow.TARGETS),
                                      skip=["target_" + d for d in dead])),
    ):
        out[name] = float(np.quantile(list(gaps.values()), HALF))
        worst = max(gaps, key=lambda k: gaps[k] if gaps[k] == gaps[k] else np.inf)
        ctx.log(f"{name}: {json.dumps(sdar.spread(gaps.values()))}; worst leaf {worst}; "
                f"left out {dead if name != 'grad_gap' else []}")
    ctx.log(f"call 1 whole: {json.dumps(out)}; loss gap {follow.loss_gap(scalars, losses)!r}; "
            f"losses by update {json.dumps(f['losses'])}")
    return out
