"""The saturated learner over SDAR-30B-A3B-Chat's block: ``drivers/learn.py``'s
timed call (``Trainer._learn_many`` jitted, state donated, dispatched back to
back on an arena filled to capacity from the seed) for the configuration
whose core is a stack of sparse-expert attention blocks.

What differs from ``learn`` and so is this file's own: the experiment builder
(the block's sizes come from the published keys of the configuration file),
the rows (no stored carries: this core's replay stores none), the weights
(norm scales are drawn about 1, kernels by their own fan-in; the routers are
what the seed gives them), the window (which also averages the routing
counters over its calls), the reference (``reference_sdar_moe.py``) and how
the first calls are followed.

**The follow.**  Under this configuration's recipe (Adam 1e-3 on a 232
M-parameter critic, no warm-up) an update moves Q by tens and every router's
logits by about one, so two sound trajectories part within a call and what
is compared after several updates is their chaos, not a precision (PERF.md
section 2).  So each followed call is compared **from the program's own
state at the call's start** and its first update, which that state alone
decides, carries the numbers a precision has to fail:

- ``priority_gap``: the priorities the first update of each followed call
  wrote back, against the reference's for the same rows from the same
  weights (the seed's for the first call, the program's own nets as they
  stood before the call for the later ones): the relative gap that half of
  the update's slots lie within, the widest of the calls.  A top-8 set that
  flips on rounding moves one sequence by percents in a sound run too, and a
  slot drawn again later in a call shows its last priority, not its first;
  the median sees neither and sees what moves every row.
- ``expert_load_gap``: the tokens each held expert received in each layer
  of each pass of that first update, as the program's counter
  ``moe/tokens_per_expert`` reports them, against the reference's own
  routing: the tokens counted differently, summed over experts, layers,
  passes and calls, over the pairs an even router would send here in those
  updates.  (The worst single expert over its pass's tokens, which ISSUE 27
  asked for, reads one or two tokens in a sound run and in a bfloat16
  router alike; the sum counts every flipped choice.)

The first call is then followed through all its updates from the seed, as
``learn`` follows its calls, for what only a trajectory shows: ``loss_gap``
(the call's mean losses), ``grad_gap`` (Adam's first moment after the call),
``update_gap`` and ``target_gap`` (the weights' and the targets' change), each
of the last three the gap that three quarters of the leaves lie within (the
worst leaf is a router's or an expert's, whose gradient hangs on which
tokens flipped), ``sample_gap``, ``slots_unmatched`` and ``steps_gap``.  These
carry four updates of the recipe's chaos; their limits are set against the
faults.

The timed call hands back no indices.  The slots an update drew are the
changed slots nearest its draws (the reference's own uniforms) in the float64
CDF of the priorities as the updates before left them: the program's vector
before the call with the REFERENCE's written-back priorities laid over it
(``compare.assign_draws`` lays the program's end-of-call value over a slot,
which is far from what a slot drawn twice held between its draws: 7 against
22 here).

Controls and faults beside ``plants.py``'s (``--plant``): ``router_bf16``
(the router's logits from bfloat16 operands) and ``expert_unapplied`` (the
fullest held expert of a layer is routed to and counted but its part is
never added).
"""

from __future__ import annotations

import collections
import dataclasses
import json
import math
import os
import time
from typing import Any, Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import compare, follow, harness, plants, reference, reference_sdar_moe as ref_moe, traffic
from chipbench.drivers import learn
from chipbench.harness import Compared, Context
from chipbench.program import build_trainer, hyperparameters, to_batch

FIRST_CALLS = 3  # the calls whose first update is followed; the first one whole
PRECISION = learn.PRECISION
OWN_PLANTS = ("router_bf16", "expert_unapplied")
TABLE = "moe/tokens_per_expert"
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# --------------------------------------------------------------- experiment
def build_experiment(ctx: Context):
    """The program's own experiment config with the configuration file's
    numbers applied: agent and trainer fields under their own names, the
    block's sizes from the published keys."""
    from r2d2dpg_tpu.configs import get_config

    cfg = ctx.config
    exp = get_config(cfg["program_config"])
    z = ref_moe.sizes(cfg)

    def apply(obj):
        fields = {f.name for f in dataclasses.fields(obj)}
        return dataclasses.replace(obj, **{k: cfg[k] for k in fields if k in cfg})

    sdar = dataclasses.replace(
        exp.sdar, hidden=z["H"], layers=z["L"], heads=z["heads"], kv_heads=z["kv"],
        head_dim=z["D"], rope_theta=z["theta"], rms_eps=z["eps"],
        router_experts=z["R"], experts_per_token=z["k"], expert_width=z["W"],
        expert_shards=int(cfg["expert_shards"]), expert_shard=int(cfg["expert_shard"]),
    )
    if sdar.experts_held != z["E"]:
        raise ValueError(f"{z['R']} experts over {cfg['expert_shards']} shards "
                         f"are not the {z['E']} the file says are held")
    exp = dataclasses.replace(
        exp, agent=apply(exp.agent), hidden=z["H"], sdar=sdar,
        compute_dtype=cfg["compute_dtype"],
        trainer=dataclasses.replace(apply(exp.trainer),
                                    seed=int(ctx.seed) & 0x7FFFFFFF),
    )
    if ctx.plant in OWN_PLANTS:
        _plant(ctx.plant)
        return exp
    return plants.on_experiment(exp, ctx.plant)


def _plant(plant: str) -> None:
    """Put a control or a fault under the core's seams (module functions of
    ``models/sdar_moe.py`` that are looked up when the call is traced)."""
    from r2d2dpg_tpu.models import sdar_moe

    if plant == "router_bf16":
        def router_probs(h2, w_router):
            bf = jnp.bfloat16
            logits = jnp.matmul(h2.astype(bf), w_router.astype(bf),
                                preferred_element_type=jnp.float32)
            return jax.nn.softmax(logits, axis=-1)

        sdar_moe.router_probs = router_probs
    elif plant == "expert_unapplied":
        inner = sdar_moe.held_ffn

        def held_ffn(h2, w_gate, w_up, w_down, gates):
            fullest = jnp.argmax(jnp.sum(gates > 0, axis=0))
            kept = jnp.arange(gates.shape[1]) != fullest
            return inner(h2, w_gate, w_up, w_down, gates * kept)

        sdar_moe.held_ffn = held_ffn


# ------------------------------------------------------- weights and rows
def make_weights(key: jax.Array, shapes: Any) -> Any:
    """Fill a tree of ``ShapeDtypeStruct`` leaves from ``key`` in one jitted
    call, by the leaf's name: norm scales ``1 + U(-0.05, 0.05)``, biases
    ``U(-0.05, 0.05)``, every other leaf a kernel ``U(+-1/sqrt(fan_in))`` with
    the fan-in its last axis but one (a stack of experts is a stack of
    kernels)."""
    paths, treedef = jax.tree_util.tree_flatten_with_path(shapes)

    def draw(key):
        leaves = []
        for i, (path, s) in enumerate(paths):
            name = str(getattr(path[-1], "key", path[-1]))
            k = jax.random.fold_in(key, i)
            if "norm" in name:
                leaf = 1.0 + jax.random.uniform(k, s.shape, s.dtype, -0.05, 0.05)
            elif name == "bias":
                leaf = jax.random.uniform(k, s.shape, s.dtype, -0.05, 0.05)
            else:
                bound = 1.0 / math.sqrt(s.shape[-2])
                leaf = jax.random.uniform(k, s.shape, s.dtype, -bound, bound)
            leaves.append(leaf)
        return jax.tree_util.tree_unflatten(treedef, leaves)

    return jax.jit(draw)(key)


def seeded_weights(seed: int, cfg):
    """Both nets' weights from the seed, the routers as it gives them."""
    return make_weights(traffic.seed_key(seed, traffic.STREAM_WEIGHTS),
                        ref_moe.weight_shapes(cfg))


def _one_row(key, spec, params):
    L, A = spec["seq_len"], spec["action_dim"]
    ks = jax.random.split(key, 5)
    row = {
        "obs": jax.random.normal(ks[0], (L,) + spec["obs_shape"], jnp.float32),
        "action": jax.random.uniform(ks[1], (L, A), jnp.float32, -1.0, 1.0),
        "reward": params["reward_max"] * jax.random.uniform(ks[2], (L,), jnp.float32),
        "discount": jnp.ones((L,), jnp.float32),
        "reset": jax.random.bernoulli(ks[3], params["reset_prob"], (L,)).astype(
            jnp.float32),
        "carries": {"actor": (), "critic": ()},
    }
    priority = params["priority_scale"] * jnp.exp(
        params["priority_log_sigma"] * jax.random.normal(ks[4], (), jnp.float32))
    return row, priority


def make_rows(key, indices, spec, params):
    """Rows and stored priorities of the slots ``indices``: a function of
    (seed, slot) alone, as ``traffic.make_rows``.  Traceable."""
    keys = jax.vmap(lambda i: jax.random.fold_in(key, i))(indices)
    return jax.vmap(lambda k: _one_row(k, spec, params))(keys)


def fill_arena(ctx: Context, trainer, spec):
    """The arena at capacity, filled on the device from the seed in donated
    chunks written in place (``learn.fill_arena`` with this file's rows)."""
    capacity = trainer.arena.capacity
    params = ctx.cell["traffic"]
    chunk = int(params["fill_chunk_rows"])
    if capacity % chunk:
        raise ValueError(f"capacity {capacity} is not a multiple of {chunk}")
    key = traffic.seed_key(ctx.seed, traffic.STREAM_ROWS)

    def put(buf, new, start):
        return jax.lax.dynamic_update_slice_in_dim(buf, new.astype(buf.dtype), start, 0)

    def one(state, key, start):
        idx = start + jnp.arange(chunk, dtype=jnp.int32)
        rows, prios = make_rows(key, idx, spec, params)
        data = jax.tree_util.tree_map(
            lambda buf, new: put(buf, new, start), state.data, to_batch(rows))
        prios = jnp.maximum(prios, reference.PRIORITY_EPS)
        return dataclasses.replace(
            state, data=data, priority=put(state.priority, prios, start))

    def empty(key):
        example, _ = make_rows(key, jnp.zeros((1,), jnp.int32), spec, params)
        return trainer.arena.init_state(to_batch(example))

    state = jax.jit(empty)(key)
    fill = jax.jit(one, donate_argnums=0)
    for start in range(0, capacity, chunk):
        state = fill(state, key, jnp.int32(start))
    return dataclasses.replace(
        state, total_added=jnp.asarray(capacity, state.total_added.dtype))


def make_train_state(trainer, spec, config, seed: int):
    """The program's ``TrainState`` around the seed's weights, made on the
    reference's own tree of shapes, which the program's tree has to be."""
    from r2d2dpg_tpu.agents.ddpg import TrainState

    shapes = ref_moe.weight_shapes(config)
    obs = jnp.zeros((1,) + spec["obs_shape"], jnp.float32)
    act = jnp.zeros((1, spec["action_dim"]), jnp.float32)
    st = jax.eval_shape(lambda k: trainer.agent.init(k, obs, act), jax.random.PRNGKey(0))

    def laid_out(tree):
        return [(jax.tree_util.keystr(path), tuple(s.shape), jnp.dtype(s.dtype))
                for path, s in jax.tree_util.tree_flatten_with_path(tree)[0]]

    if laid_out(shapes) != laid_out((st.actor_params, st.critic_params)):
        raise ValueError("the program's weights are not laid out as the "
                         "reference's: chipbench/reference_sdar_moe.py::weight_shapes")
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
def program(ctx: Context):
    """What of a run does not depend on its seed: the trainer, the compiled
    timed call, the hyperparameters and the rows' shapes."""
    exp = build_experiment(ctx)
    trainer = build_trainer(ctx, exp)
    cfg = ctx.config
    spec = {"seq_len": exp.agent.seq_len, "obs_shape": tuple(cfg["obs_shape"]),
            "action_dim": int(cfg["action_dim"])}

    def timed(train, arena, rng):
        rng, key = jax.random.split(rng)
        train, arena, metrics = trainer._learn_many(train, arena, key)
        return train, arena, rng, metrics

    return trainer, jax.jit(timed, donate_argnums=(0, 1)), hyperparameters(exp), spec


def setup(ctx: Context) -> learn.Session:
    trainer, call, hp, spec = program(ctx)
    ctx.log("program built")
    train = make_train_state(trainer, spec, ctx.config, ctx.seed)
    jax.block_until_ready(train)
    ctx.log("weights made")
    arena = fill_arena(ctx, trainer, spec)
    rng0 = traffic.seed_key(ctx.seed, traffic.STREAM_RUN)
    jax.block_until_ready(arena.priority)
    ctx.log(f"arena filled: {trainer.arena.capacity} sequences")
    s = learn.Session(
        trainer=trainer, call=call, state=(train, arena, rng0), hp=hp, spec=spec,
        first=[], rng0=rng0, in_flight=int(ctx.cell["traffic"]["in_flight_calls"]))

    # The first calls, from the seed, through the window's own compiled call.
    # What the follow needs of the program's state goes to the host: the four
    # nets as they stand between the calls (what the later calls' first
    # update is followed from, and the first call's weights' change) and
    # Adam's state after the first call.
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


def window(s: learn.Session, seconds: float) -> Dict[str, Any]:
    """``learn.window``, which also keeps each call's routing counters: their
    mean over the window's calls is what the per-layer metrics read."""
    K = s.trainer.config.learner_steps
    jax.block_until_ready(s.state)
    pending = collections.deque()
    moe: Dict[str, Any] = {}
    calls = 0

    def finish(metrics):
        metrics["critic_loss"].block_until_ready()
        for k, v in metrics.items():
            if k.startswith("moe/"):  # the table comes one an update: their mean
                v = np.asarray(v, np.float64)
                moe[k] = moe.get(k, 0.0) + (v.mean(axis=0) if k == TABLE else v)

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
        "moe": {k: (v / max(calls, 1)).tolist() for k, v in moe.items()},
    }


# ------------------------------------------------------------------ correct
_DISTANCE = jax.jit(lambda a, b: jnp.sqrt(jnp.sum(jnp.square(a - b))))
QUARTER = 0.75  # of the leaves: "the gap that three quarters of them lie within"


def by_path(tree) -> Dict[str, Any]:
    """The leaves of a nested dict by their path, ``compare.leaf_norms``' keys."""
    out: Dict[str, Any] = {}

    def walk(node, path):
        if isinstance(node, dict):
            for k in sorted(node):
                walk(node[k], path + (str(k),))
        else:
            out["/".join(path)] = node

    walk(tree, ())
    return out


def leaf_norms(tree, base=None) -> Dict[str, float]:
    """``compare.leaf_norms`` (the same keys) of ``tree``, or of ``tree -
    base``, taken on the device leaf by leaf: float64 copies of 460 M
    parameters on the host take over a minute a tree."""
    other = None if base is None else by_path(base)
    zero = jnp.zeros((), jnp.float32)
    return {k: float(_DISTANCE(v, zero if other is None else other[k]))
            for k, v in by_path(tree).items()}


def leaf_gaps(program: Dict[str, float], ref: Dict[str, float], skip=()) -> Dict[str, float]:
    """Every leaf's gap as ``compare.worst_leaf_gap`` scales it: against the
    reference's norm of that leaf or of the median leaf, whichever is larger."""
    if set(program) != set(ref):
        raise ValueError("program and reference trees differ in their leaves")
    keys = [k for k in ref if k not in skip]
    median = float(np.median([ref[k] for k in keys]))
    return {k: abs(program[k] - ref[k]) / max(ref[k], median, 1e-30) for k in keys}


def spread(values) -> Dict[str, float]:
    """A set of gaps in four numbers, for the log; NaN stays NaN."""
    v = np.asarray(list(values), np.float64)
    return {"q50": float(np.quantile(v, 0.5)), "q75": float(np.quantile(v, QUARTER)),
            "q90": float(np.quantile(v, 0.9)), "max": float(v.max())}


def reference_update(hp, cfg):
    """The reference's update, jitted at the precision the configuration runs
    at.  Its state is donated (a second copy of its 7.4 GB would not fit
    beside the first) and its gradients leave the call as their leaves'
    norms (kept whole they are another 1.8 GB)."""

    def traced(st, rows, w):
        with jax.default_matmul_precision(PRECISION):
            st, prios, ls = ref_moe.learner_update(st, rows, w, hp, cfg)
        norm = lambda g: jnp.sqrt(jnp.sum(jnp.square(g)))  # noqa: E731
        return st, prios, dict(ls, grads=jax.tree_util.tree_map(norm, ls["grads"]))

    return jax.jit(traced, donate_argnums=0)


def learner_call(ref, ref_prio, before, changed, keys, rows_of, size, replay, update):
    """``len(keys)`` updates of one timed call, followed from ``ref``.

    Update ``k`` drew the changed slots nearest its draws (the reference's own
    uniforms) in the float64 CDF of the priorities as the updates before left
    them: ``before`` (the program's own vector before the call) with the
    reference's written-back priorities laid over it.  The reference weighs
    its rows by its own vector ``ref_prio``.  Returns, an update each: the
    slots, the reference's priorities for them, its routing table and its
    losses; the first update's gradient norms; the widest distance of a draw
    from its slot, in mean slot widths; the reference's state and vector."""
    B, alpha = int(replay["batch_size"]), replay["alpha"]
    current = np.array(before, np.float32, copy=True)
    out: Dict[str, Any] = {"slots": [], "prios": [], "loads": [], "losses": [], "sample_gap": 0.0}
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
        current = reference.write_priorities(current, drawn, prios)
        if k == 0:
            out["first_grads"] = {n: float(v) for n, v in by_path(jax.device_get(ls["grads"])).items()}
        out["slots"].append(drawn)
        out["prios"].append(prios)
        out["loads"].append(np.asarray(ls["loads"], np.float64))
        out["losses"].append({n: float(ls[n]) for n in ("critic_loss", "actor_loss", "q_abs_mean")})
    return dict(out, ref=ref, ref_prio=ref_prio)


def check(ctx: Context, s: learn.Session) -> List[Compared]:
    """With ``--trace 1`` the stage table first, while the program still holds
    its state; then that state goes (the learner's 7 GB before the
    reference's come) and the first calls are followed with the plain
    reference at JAX's default matmul precision, which is what the
    configuration runs at."""
    if ctx.trace:
        harness.load_module("reducers", "core_stage_ms", ROOT).capture(
            ctx, lambda seconds: window(s, seconds))
    s.state = None
    out = _follow(ctx, s)
    limits = ctx.cell["limits"]
    return [Compared(name, out[name], limits[name]) for name in sorted(out)]


def _follow(ctx: Context, s: learn.Session) -> Dict[str, float]:
    tcfg = s.trainer.config
    K, capacity = tcfg.learner_steps, s.trainer.arena.capacity
    replay = {"batch_size": tcfg.batch_size, "alpha": tcfg.priority_alpha,
              "beta0": tcfg.beta0, "beta_steps": tcfg.beta_steps}
    first, rng, spec, hp, cfg = s.first, s.rng0, s.spec, s.hp, ctx.config
    params = ctx.cell["traffic"]
    update = reference_update(hp, cfg)
    row_key = traffic.seed_key(ctx.seed, traffic.STREAM_ROWS)
    make = jax.jit(lambda key, idx: make_rows(key, idx, spec, params))

    def rows_of(slots):
        rows = make(row_key, jnp.asarray(slots, jnp.int32))[0]
        return {k: v for k, v in rows.items() if k != "carries"}

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
        "sample_gap": 0.0, "expert_load_gap": 0.0,
    }
    # The tokens of each pass (``ref_moe.PASSES``' order) and the pairs an even
    # router would send to the experts held here in one update: the burn-in
    # passes stop before their last layer's experts.
    z = ref_moe.sizes(cfg)
    tokens = np.array([hp["burnin"]] * 4 + [hp["unroll"] + hp["n_step"]] * 2
                      + [hp["unroll"]] * 3, np.float64) * tcfg.batch_size
    layers = np.array([z["L"] - 1] * 4 + [z["L"]] * 5, np.float64)
    even = float((tokens * layers).sum()) * z["k"] * z["E"] / z["R"]
    off = 0.0  # tokens the program and the reference count differently
    for c, rec in enumerate(first):
        rng, keys = follow.call_keys(rng, K)
        before, after = rec["priority_before"], rec["priority_after"]
        changed = np.flatnonzero(before != after)
        if changed.size == 0:  # the call wrote no priority back: nothing to follow
            return {name: float("inf") for name in ctx.cell["limits"]}
        if c == 0:  # from the seed, every update
            actor, critic = seeded_weights(ctx.seed, cfg)
            copy = lambda t: jax.tree_util.tree_map(jnp.copy, t)  # noqa: E731
            st = dict(reference.init_state(actor, critic),
                      target_actor=copy(actor), target_critic=copy(critic))
            del actor, critic
            own = seeded
        else:  # from the program's own nets before the call, its first update
            st, own, keys = state_of(rec["nets_before"], c * K), before, keys[:1]
        f = learner_call(st, own, before, changed, keys, rows_of, capacity, replay, update)
        del st

        # The call's first update: what the state before the call decides.
        drawn = f["slots"][0]
        later = np.concatenate(f["slots"][1:]) if len(keys) > 1 else np.zeros(0, np.int64)
        once = ~np.isin(drawn, later)  # not drawn again before the vector was read
        rel = np.abs(after[drawn] - f["prios"][0]) / np.maximum(f["prios"][0], 1e-30)
        got = np.asarray(rec["metrics"][TABLE], np.float64)
        if got.shape[1:] != f["loads"][0].shape:
            raise ValueError(f"{TABLE} is {got.shape}; the reference counts {f['loads'][0].shape}")
        miscounted = np.abs(got[0] - f["loads"][0])
        off += float(miscounted.sum())
        by_call = {"held_share_by_layer": [round(float(x), 3) for x in
                                           got[0].sum(axis=(0, 2)) * z["L"] / even],
                   "priority": spread(rel[once]), "tokens_off": float(miscounted.sum()),
                   "worst_expert": float((miscounted / tokens[:, None, None]).max()),
                   "sample_gap": f["sample_gap"], "drawn_once": int(once.sum())}
        out["priority_gap"] = max(out["priority_gap"], by_call["priority"]["q50"])
        out["expert_load_gap"] = off / (len(first) * even)
        out["sample_gap"] = max(out["sample_gap"], f["sample_gap"])
        if c == 0:
            out.update(_whole_call(ctx, rec, f, changed))
        del f
        ctx.log(f"call {c + 1}: {json.dumps(by_call)}")
    return out


def _whole_call(ctx: Context, rec, f, changed) -> Dict[str, float]:
    """The first call through all its updates from the seed: the losses, the
    slots, Adam's first moment, the weights' and the targets' change."""
    ref = f["ref"]
    scalars = {k: float(v) for k, v in rec["metrics"].items() if np.size(v) == 1}
    losses = {n: float(np.mean([ls[n] for ls in f["losses"]])) for n in f["losses"][0]}
    out = {
        "loss_gap": follow.loss_gap(scalars, losses),
        # The slots whose priority changed are the slots drawn, no others.
        "slots_unmatched": float(len(np.setxor1d(changed, np.unique(np.concatenate(f["slots"]))))),
        "steps_gap": float(abs(rec["step"] - int(ref["step"]))),
    }
    mu = lambda nets: leaf_norms(dict(zip(follow.NETS, nets)))  # noqa: E731
    dead = compare.dead_leaves(f["first_grads"])
    seeds = seeded_weights(ctx.seed, ctx.config)
    p0 = dict(zip(follow.NETS + follow.TARGETS, seeds + seeds))
    change = lambda p, nets: leaf_norms({n: p[n] for n in nets}, p0)  # noqa: E731
    for name, gaps in (
        ("grad_gap", leaf_gaps(mu([follow.adam_mu(o) for o in rec["opt"]]),
                               mu([ref["actor_opt"]["mu"], ref["critic_opt"]["mu"]]))),
        ("update_gap", leaf_gaps(change(rec["nets_after"], follow.NETS),
                                 change(ref, follow.NETS), skip=dead)),
        ("target_gap", leaf_gaps(change(rec["nets_after"], follow.TARGETS),
                                 change(ref, follow.TARGETS),
                                 skip=["target_" + d for d in dead])),
    ):
        out[name] = float(np.quantile(list(gaps.values()), QUARTER))
        worst = max(gaps, key=lambda k: gaps[k] if gaps[k] == gaps[k] else np.inf)
        ctx.log(f"{name}: {json.dumps(spread(gaps.values()))}; worst leaf {worst}; "
                f"left out {dead if name != 'grad_gap' else []}")
    ctx.log(f"call 1 whole: {json.dumps(out)}; losses by update {json.dumps(f['losses'])}")
    return out
