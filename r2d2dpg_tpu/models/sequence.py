"""How a core is run over a stored sequence: the learner's one seam to its nets.

R2D2's learner warms its nets over a burn-in prefix and unrolls them over the
training window (SURVEY.md §3.3).  HOW depends on the core, and it is decided
here and nowhere else: ``agents/ddpg.py`` holds one of these objects
(``sequence_runner`` picks it from the nets it is given) and calls its
operations —

- ``prepare``: what the nets want done ONCE an update to the observations of
  the whole sampled batch before any pass reads a window of them (a conv
  torso: scaled and re-laid frames, ``models/torsos.py``; else nothing);
  the module's ``window(obs, start, stop)`` then cuts the steps of a pass,
  time-major, out of whichever it is handed;
- ``unroll_actor`` / ``unroll_critic``: one net over time-major inputs from a
  carry; back come the outputs ``[T, B, ...]`` and what the pass left behind;
- ``unroll_pi_q``: the actor, then the critic on the actor's actions;
- ``unroll_online`` (where the runner says ``joint_online``): the critic's
  loss pass and the actor's in one scan, the critic frozen in the second;
- ``burn_in``: the four nets' carries (online and target actor, online and
  target critic) into the window, from a batch's prefix, no gradient;
- ``metrics``: what the update reports from what its passes left behind.

``Stepped`` (LSTM, Dense, any net whose ``apply`` is one step) scans single
steps from the carry the replay stored; what a pass leaves is its last carry.
What of a step does not depend on the carry (a net's ``encode``: the torso, the
LSTM's input projection) it computes once over the whole time-major input
before the scan, and the scan keeps what needs the carry (PR 30: the conv
torso saw 32 frames a step, now 640-800 a pass).  ``Whole`` (a whole-sequence
core: ``models/sdar_moe.py``, ``models/ouro_loop.py``) hands the net whole
sequences; its carry is the memory the prefix left, and what a pass leaves
and what the update reports from it are the core's own (expert loads; how
far the last loop step moved the state).  A new stepped core, whatever its
carry's shape, needs nothing here (``apply`` alone is scanned whole); a new
whole-sequence core needs nothing here either; a new kind is one more class
with these operations.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from r2d2dpg_tpu.models.actor_critic import Carry, time_major, unroll

# The passes of one learner update through a net's core, in the order
# ``Whole.metrics`` names them to the core: the four burn-in prefixes (absent
# at burn-in 0), the two target passes over the window, the critic's and the
# actor's of the losses, the critic's on the policy's actions.
PASSES = (
    "burn_actor", "burn_target_actor", "burn_critic", "burn_target_critic",
    "target_actor", "target_critic", "critic", "actor", "critic_pi",
)
_MEMORY = ("k", "v", "seg")  # of a call's aux: what the next call attends to


def _stack_n(tree: Any, n: int) -> Any:
    """Tile a pytree along a new leading ensemble axis of size ``n``."""
    return jax.tree_util.tree_map(lambda x: jnp.stack([x] * n), tree)


def _stack2(a: Any, b: Any) -> Any:
    """Stack two same-structure pytrees along a new leading axis of size 2."""
    return jax.tree_util.tree_map(lambda x, y: jnp.stack([x, y]), a, b)


def _append(members: Any, one: Any) -> Any:
    """``members`` (a leading member axis) with ``one`` as a further member."""
    return jax.tree_util.tree_map(
        lambda x, y: jnp.concatenate([x, y[None]]), members, one)


def _member(tree: Any, i: int) -> Any:
    """Member ``i`` of an ensemble-stacked pytree."""
    return jax.tree_util.tree_map(lambda x: x[i], tree)


def _unstack2(t: Any) -> Tuple[Any, Any]:
    return (
        jax.tree_util.tree_map(lambda x: x[0], t),
        jax.tree_util.tree_map(lambda x: x[1], t),
    )


def window(obs, start: int, stop: int):
    """Steps ``[start, stop)`` of a sampled batch's observations, time-major:
    of the batch-major array the replay handed over, or of what a net
    prepared of it, which is no array and is cut by steps
    (``prepared[start:stop]``)."""
    if hasattr(obs, "shape"):
        return time_major(obs[:, start:stop])
    return obs[start:stop]


def _prepare(actor, critic):
    """``obs [B, L, ...] -> `` what both nets' passes cut their windows from:
    the nets' own ``prepare`` (one preparation serves both, so they have to
    make the same of it), the identity for nets that offer none."""
    if not (hasattr(actor, "prepare") and hasattr(critic, "prepare")):
        return lambda obs: obs

    def prepare(obs):
        shape = jax.ShapeDtypeStruct(obs.shape, obs.dtype)
        a, c = (
            jax.eval_shape(lambda o: net.apply({}, o, method="prepare"), shape)
            for net in (actor, critic)
        )
        if a != c:
            raise ValueError(
                f"actor and critic prepare a batch differently: {a} and {c}")
        return actor.apply({}, obs, method="prepare")

    return prepare


def _parts(net):
    """``net`` as ``(encode, with_action, step, readout)``, each taking the
    parameters first.

    A net that says what of its step does not depend on the carry (``encode``,
    with ``step`` on the encoded rows and ``readout`` on the core's outputs:
    ``ActorNet``, ``CriticNet``) is taken apart there; a critic's
    ``with_action`` makes of rows encoded without an action the rows
    ``encode`` gives with one (``mix`` and the input projection), for an
    action made in the same step.  A net that offers ``apply`` alone is all
    step: its "encoded" rows are its inputs as they are, an action joins
    them, and its outputs are final.
    """
    if hasattr(net, "encode"):
        return (
            lambda p, *xs: net.apply(p, *xs, method="encode"),
            lambda p, z, a: net.apply(p, z, a, method="with_action"),
            lambda p, z, c, r: net.apply(p, z, c, r, method="step"),
            lambda p, y: net.apply(p, y, method="readout"),
        )
    return (
        lambda p, *xs: xs,
        lambda p, xs, a: xs + (a,),
        lambda p, xs, c, r: net.apply(p, *xs, c, r),
        lambda p, y: y,
    )


class Stepped:
    """``lax.scan`` of single steps from the stored carry, with what does not
    depend on the carry taken out of the scan.

    A step of a net is ``readout(step(encode(inputs), carry, reset))``
    (``_parts``).  ``encode`` (torso; for the LSTM core its input projection;
    for the critic on the replay's actions ``mix`` too) runs ONCE over the
    whole time-major ``[T, B, ...]`` input, so its matmuls and convolutions
    see T·B rows; the scan body keeps the reset, ``h·W_h + b``, the gates and,
    where the critic follows the actor, the actor's head and the critic's
    ``mix`` (``with_action``); ``readout`` runs once over the stacked outputs.
    Same products, same precision: only the order of the sums over rows
    changes.  A net offering ``apply`` alone (``actor.apply(params, obs,
    carry, reset)``, ``critic.apply(params, obs, action, carry, reset)``, each
    one step returning its output and the new carry) is scanned whole; a net
    that has nothing to warm is one whose stored carry has no leaves.

    A step costs the chain of its operations, not their work: at 64 rows one
    ``h·W_h`` is a fraction of a microsecond of the MXU (PERF.md section 5).
    So where passes do not depend on each other they run as members of ONE
    scan, whose batched operations replace one chain of each: burn-in stacks
    online and target, and the learner's two online passes (the critic's
    loss and the actor's, both from the burnt-in state over the same window)
    are ``unroll_online``, one scan under one gradient call
    (``joint_online``): 40 steps an update fewer at unroll 20, forward and
    transposed, and two loops fewer to enter.
    """

    # ``agents/ddpg.py::learner_step`` asks the runner for both online passes
    # in one ``unroll_online`` under one gradient call.
    joint_online = True

    def __init__(self, actor, critic, config):
        self.actor, self.critic, self.config = actor, critic, config
        self._actor, self._critic = _parts(actor), _parts(critic)
        self._prepare = _prepare(actor, critic)

    def prepare(self, batch):
        """``batch`` with its observations as the nets prepared them, all
        stored steps at once: ``burn_in`` and the learner's passes cut their
        windows out of the one result (``window``).  A flat observation comes
        back as it is."""
        return dataclasses.replace(batch, obs=self._prepare(batch.obs))

    @staticmethod
    def _unroll(parts, params, carry, reset_tm, *inputs_tm):
        encode, _, step, readout = parts
        y_tm, carry = unroll(
            lambda c, z, r: step(params, z, c, r),
            carry,
            encode(params, *inputs_tm),
            reset_tm,
        )
        return readout(params, y_tm), carry

    def unroll_actor(self, params, carry, obs_tm, reset_tm):
        return self._unroll(self._actor, params, carry, reset_tm, obs_tm)

    def unroll_critic(self, params, carry, obs_tm, act_tm, reset_tm):
        return self._unroll(self._critic, params, carry, reset_tm, obs_tm, act_tm)

    def unroll_pi_q(
        self, actor_params, critic_params, ca, cc, obs_tm, reset_tm,
        eps_tm=None, q_min=False,
    ):
        """Actor and critic advanced in ONE scan: a_t = mu(o_t), q_t = Q(o_t, a_t).

        Halves the sequential-scan count of the two places that unroll the
        policy and then re-unroll the critic over its actions (the n-step
        target pass and the actor loss) — per-step math is identical to the
        two-scan version, the cells just step together.  The critic's
        ``encode`` is its torso alone here: the action is made in the step,
        so ``mix`` stays there.  The TD3 knobs of the target pass: ``eps_tm``
        is added to each step's action (clipped back into range) before the
        critic sees it; ``q_min`` takes ``critic_params`` and ``cc`` with a
        leading ensemble axis and returns the least member's Q.
        """
        a_encode, _, a_step, a_readout = self._actor
        c_encode, c_with_action, c_step, c_readout = self._critic

        def q_step(p, z, c, r, a):
            return c_step(p, c_with_action(p, z, a), c, r)

        def step(carry, za, zc, r, *e):
            ca, cc = carry
            y, ca = a_step(actor_params, za, ca, r)
            a = a_readout(actor_params, y)
            if e:
                a = jnp.clip(a + e[0], -1.0, 1.0)
            if q_min:
                yq, cc = jax.vmap(lambda p, z, c: q_step(p, z, c, r, a))(
                    critic_params, zc, cc
                )
            else:
                yq, cc = q_step(critic_params, zc, cc, r, a)
            return (a, yq), (ca, cc)

        if q_min:  # members on axis 1 of the encoded rows: the scan runs over axis 0
            zc_tm = jax.vmap(lambda p: c_encode(p, obs_tm), out_axes=1)(critic_params)
        else:
            zc_tm = c_encode(critic_params, obs_tm)
        xs = (a_encode(actor_params, obs_tm), zc_tm, reset_tm)
        xs += () if eps_tm is None else (eps_tm,)
        (a_tm, yq_tm), carry = unroll(step, (ca, cc), *xs)
        if q_min:
            q_tm = jax.vmap(c_readout, in_axes=(0, 1))(critic_params, yq_tm).min(axis=0)
        else:
            q_tm = c_readout(critic_params, yq_tm)
        return a_tm, q_tm, carry

    def unroll_online(
        self, actor_params, critic_params, ca, cc, obs_tm, act_tm, reset_tm,
        ensemble=False,
    ):
        """The critic's loss pass and the actor's loss pass in ONE scan:
        ``unroll_critic(critic_params, cc, obs, act)`` and ``unroll_pi_q(
        actor_params, critic_params, ca, cc, obs)``, from the same carries
        over the same window, which do not depend on each other.

        A step advances the actor (cell, head, the critic's ``mix`` and
        ``W_i`` over the action it just made, as ``unroll_pi_q``'s step does)
        and then the critic's cell as members of one vmapped step, as
        ``burn_in`` stacks online and target: the first on the replay's
        actions (``encode`` with ``mix`` ran outside the scan), the last on
        the policy's.  ``h·W_h + b`` and the gates of all members are one
        batched dot and one gate fusion a step.

        The last member, and everything the actor's pass reads of the critic
        (its torso, ``mix``, ``W_i``, readout), is ``stop_gradient`` of the
        critic: the actor's loss goes through the FROZEN online critic, so a
        gradient call over both nets' parameters gives the critic the
        critic loss's gradient alone and the actor the actor loss's, as two
        calls did.  With ``ensemble``, ``critic_params`` and ``cc`` have a
        leading member axis (twin critics), every member runs on the replay's
        actions and the policy is judged by a frozen copy of member 0 (the
        TD3 convention).

        Returns ``(q [T, B] ([n, T, B] with ensemble), q of the policy's
        actions [T, B], (what the critic's pass left, what the actor's
        left))``.
        """
        a_encode, _, a_step, a_readout = self._actor
        c_encode, c_with_action, c_step, c_readout = self._critic

        # Outside the scan: the critic's torso ONCE over the window (the
        # frozen member reads member 0's features through ``stop_gradient``),
        # then ``mix`` and ``W_i`` over the replay's actions.
        def encode(p):
            x = c_encode(p, obs_tm)
            return x, c_with_action(p, x, act_tm)

        if ensemble:  # members on axis 1 of the encoded rows
            x_tm, zq_tm = jax.vmap(encode, out_axes=1)(critic_params)
            x_tm = jax.tree_util.tree_map(lambda v: v[:, 0], x_tm)
            trained = critic_params
        else:
            x_tm, zq_tm = encode(critic_params)
            zq_tm = jax.tree_util.tree_map(lambda v: v[:, None], zq_tm)
            trained, cc = _stack_n(critic_params, 1), _stack_n(cc, 1)
        n = jax.tree_util.tree_leaves(trained)[0].shape[0]
        frozen = lax.stop_gradient(_member(trained, 0))

        # The members' parameters are stacked inside the step (the compiler
        # hoists the stack): outside, the stack would be one input of the
        # scan with a gradient, and the transposed scan would carry a weight
        # gradient for the frozen member too.
        def step(carry, za, zq, x_pi, r):
            ca, cc = carry
            y, ca = a_step(actor_params, za, ca, r)
            z_pi = c_with_action(frozen, x_pi, a_readout(actor_params, y))
            yq, cc = jax.vmap(lambda p, z, c: c_step(p, z, c, r))(
                _append(trained, frozen), _append(zq, z_pi), cc
            )
            return yq, (ca, cc)

        xs = (a_encode(actor_params, obs_tm), zq_tm, lax.stop_gradient(x_tm), reset_tm)
        yq_tm, (ca, cc) = unroll(step, (ca, _append(cc, _member(cc, 0))), *xs)
        left_q = jax.tree_util.tree_map(lambda x: x[:n], cc)
        if ensemble:
            q_tm = jax.vmap(c_readout, in_axes=(0, 1))(critic_params, yq_tm[:, :n])
        else:
            q_tm, left_q = c_readout(critic_params, yq_tm[:, 0]), _member(left_q, 0)
        q_pi_tm = c_readout(frozen, yq_tm[:, n])
        return q_tm, q_pi_tm, (left_q, (ca, _member(cc, n)))

    def burn_in(self, state, batch) -> Tuple[Carry, Carry, Carry, Carry]:
        """SURVEY §3.3 hot loop: `no_grad: (h,c) = burn_in(seq[:B_len])` — online
        and target nets each burn in from the *stored* initial state.

        One scan per net: online+target param ensembles concatenated on the
        leading axis ([2] plain, [4] twin), ``encode`` vmapped over that axis
        once over the whole prefix and the step vmapped over it inside the
        scan (the matmuls of a step become one batched dot on the MXU); only
        the final carry is kept.
        """
        cfg = self.config
        nq = 2 if cfg.twin_critic else 1
        ca0, cc0 = batch.carries["actor"], batch.carries["critic"]
        # With twin critics the stored carry seeds BOTH members (collection
        # tracks one critic carry; each member warms its own state from it
        # during burn-in because its params differ).
        cc0e = _stack_n(cc0, nq) if cfg.twin_critic else cc0
        if cfg.burnin == 0:
            return ca0, ca0, cc0e, cc0e
        obs_b = window(batch.obs, 0, cfg.burnin)
        act_b = time_major(batch.action[:, : cfg.burnin])
        reset_b = time_major(batch.reset[:, : cfg.burnin])
        ca_on = ca_tg = ca0
        cc_on = cc_tg = cc0e

        def fused(parts, p_all, c0_single, n_all, *inputs_tm):
            encode, _, step, _ = parts
            z_tm = jax.vmap(lambda p: encode(p, *inputs_tm), out_axes=1)(p_all)
            v = jax.vmap(lambda p, z, c, r: step(p, z, c, r)[1], in_axes=(0, 0, 0, None))
            cN, _ = lax.scan(
                lambda c, inp: (v(p_all, inp[0], c, inp[1]), ()),
                _stack_n(c0_single, n_all),
                (z_tm, reset_b),
            )
            return cN

        if jax.tree_util.tree_leaves(ca0):
            c2 = fused(
                self._actor,
                _stack2(state.actor_params, state.target_actor_params),
                ca0,
                2,
                obs_b,
            )
            ca_on, ca_tg = _unstack2(c2)
        if jax.tree_util.tree_leaves(cc0):
            cat = lambda on, tg: jax.tree_util.tree_map(  # noqa: E731
                lambda x, y: jnp.concatenate([x, y]), on, tg
            )
            p_all = (
                cat(state.critic_params, state.target_critic_params)
                if cfg.twin_critic
                else _stack2(state.critic_params, state.target_critic_params)
            )
            cN = fused(self._critic, p_all, cc0, 2 * nq, obs_b, act_b)
            if cfg.twin_critic:
                cc_on = jax.tree_util.tree_map(lambda x: x[:nq], cN)
                cc_tg = jax.tree_util.tree_map(lambda x: x[nq:], cN)
            else:
                cc_on, cc_tg = _unstack2(cN)
        return lax.stop_gradient((ca_on, ca_tg, cc_on, cc_tg))

    def metrics(self, burn, target, critic, pi) -> Dict[str, jnp.ndarray]:
        """Last carries say nothing worth a log line."""
        return {}


class Whole:
    """Whole sequences through the nets' ``sequence`` method, no scan over time.

    Asks the nets for ``apply(..., method="sequence")`` (``ActorNet.sequence``,
    ``CriticNet.sequence``): batch-major inputs and a memory in, the outputs
    and the call's aux out: its own memory (``k``, ``v``, ``seg``) and, under
    the core's own names, what the pass leaves to report.  The replay stores
    no carry for such a core; what the update reports from what its passes
    left is the core's to say (``pass_metrics``).

    The critic's loss pass and the actor's stay two passes under two gradient
    calls (no ``unroll_online``): a whole-sequence pass is not a chain of
    small steps, so running them together saves no latency, and it would keep
    both passes' activations alive at once in a learner call that sits at the
    compiler's memory limit (``humanoid_sdar_moe``; PERF.md section 6).
    """

    joint_online = False

    def __init__(self, actor, critic, config):
        if config.twin_critic or config.target_policy_sigma > 0:
            raise ValueError(
                "twin_critic and target_policy_sigma are not wired for a "
                "whole-sequence core"
            )
        self.actor, self.critic, self.config = actor, critic, config

    def prepare(self, batch):
        """Nothing: the nets take whole batch-major sequences as sampled."""
        return batch

    @staticmethod
    def _left(aux):
        return {k: v for k, v in aux.items() if k not in _MEMORY}

    def unroll_actor(self, params, memory, obs_tm, reset_tm):
        a, aux = self.actor.apply(
            params, time_major(obs_tm), time_major(reset_tm), memory,
            method="sequence",
        )
        return time_major(a), self._left(aux)

    def unroll_critic(self, params, memory, obs_tm, act_tm, reset_tm):
        q, aux = self.critic.apply(
            params, time_major(obs_tm), time_major(act_tm), time_major(reset_tm),
            memory, method="sequence",
        )
        return time_major(q), self._left(aux)

    def unroll_pi_q(
        self, actor_params, critic_params, ma, mc, obs_tm, reset_tm,
        eps_tm=None, q_min=False,
    ):
        """``eps_tm`` and ``q_min`` are what ``__init__`` refuses."""
        a_tm, left_a = self.unroll_actor(actor_params, ma, obs_tm, reset_tm)
        q_tm, left_c = self.unroll_critic(critic_params, mc, obs_tm, a_tm, reset_tm)
        return a_tm, q_tm, (left_a, left_c)

    def burn_in(self, state, batch) -> Tuple[Carry, Carry, Carry, Carry]:
        """R2D2's burn-in in attention's terms: the prefix's keys and values
        in every layer, recomputed with today's weights, are the memory the
        window attends to.  All four memories are made before the first
        window pass reads one (``agents/ddpg.py::learner_step``'s order), so
        the four are alive at once."""
        n = self.config.burnin
        if n == 0:
            return (), (), (), ()
        obs, act, reset = batch.obs[:, :n], batch.action[:, :n], batch.reset[:, :n]

        def mem_a(p):
            return self.actor.apply(
                p, obs, reset, memory_only=True, method="sequence")[1]

        def mem_c(p):
            return self.critic.apply(
                p, obs, act, reset, memory_only=True, method="sequence")[1]

        return lax.stop_gradient((
            mem_a(state.actor_params), mem_a(state.target_actor_params),
            mem_c(state.critic_params), mem_c(state.target_critic_params),
        ))

    def metrics(self, burn, target, critic, pi) -> Dict[str, jnp.ndarray]:
        """The core's own counters from what each pass left, by ``PASSES``'
        names: a burn-in pass leaves its memory whole."""
        left = dict(zip(PASSES, tuple(burn) + tuple(target) + (critic,) + tuple(pi)))
        return self.actor.pass_metrics({n: v for n, v in left.items() if v})


def sequence_runner(actor, critic, config):
    """The runner for a pair of nets, from what the nets are: ``Whole`` for
    nets that say they take whole sequences (``whole_sequence``), else
    ``Stepped``."""
    whole = [bool(getattr(net, "whole_sequence", False)) for net in (actor, critic)]
    if whole[0] != whole[1]:
        raise ValueError(
            "actor and critic must both have a whole-sequence core, or neither")
    return (Whole if whole[0] else Stepped)(actor, critic, config)
