"""Plain reference for the R2D2-DPG learner over Ouro-2.6B's looped stack.

The whole learner update (both losses, n-step targets, priorities, clipping,
Adam, Polyak) in float32 ``jax.numpy``: Python loops over the loop steps,
the layers and (in the driver) the updates; no scan, no ``jax.checkpoint``,
no kernel.  Nothing of ``r2d2dpg_tpu`` is imported; the update's arithmetic
that does not depend on the core (targets, Huber, Adam, Polyak, priorities)
is ``chipbench/reference.py``'s, the norm, RoPE and the episode mask
``chipbench/reference_sdar_moe.py``'s.

The layer, as published (``config.json`` of ByteDance/Ouro-2.6B, ``model_type``
``ouro``; the four norms of a block and the final norm after every pass are
the source's modeling file's, ``assumed`` in the configuration file)::

    q, k, v = RMSNorm_1(x) {Wq, Wk, Wv}                (no bias, no q/k norm)
    a  = softmax_f32(rope(q) rope(k)^T / sqrt(d) + mask) v Wo
    x' = x + RMSNorm_2(a)
    m  = (silu(RMSNorm_3(x') Wg) * (RMSNorm_3(x') Wu)) Wd
    y  = x' + RMSNorm_4(m)
    h^(r) = RMSNorm_f(block_{L-1}(... block_0(h^(r-1)))),  r = 1..R, ONE set of
    weights every r; the nets read h^(R)

``mask``: step t sees step s iff s <= t and no reset lies in (s, t].  Keys
and values are made anew in every (loop step, layer) pair.  Burn-in is R2D2's
in attention's terms, as the sdar reference's: each net's keys and values
over the steps ``< burnin`` are made first, no gradient, and the steps after
attend to them.

**Computed in blocks.**  An application of a block keeps about 0.3 GB for
its backward pass at the published widths, and one differentiated pass has
``R * L = 16`` of them beside 6.6 GB of learner state: a single
differentiated program of the whole update does not fit the chip without
recomputation, and the reference has none.  So the update is a Python
sequence of small jitted pieces (one block forward; one block's
vector-Jacobian product from the block's kept input; the norm, the torso,
the heads, the losses), chained by hand: the forward passes keep every
application's input in a Python list, the backward passes walk the list from
its end and add the shared weights' gradients up over the ``R`` uses.  The
pieces compile once a shape (seconds), where the 144 applications of an
update written out in one program would compile for ten minutes.  The CPU
tests hold the chained gradients to the program's own, which are
``jax.grad``'s (``tests/test_ouro_loop.py``).

It runs at the precision the configuration states: float32 tensors at JAX's
default matmul precision (``precision="default"``).

``cfg`` is ``chipbench/configs/humanoid_ouro_loop.json`` as a dict.
"""

from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp

from chipbench import reference as ref
from chipbench.reference_sdar_moe import _rms, _rope, episode_mask

BLOCK_LEAVES = ("norm1", "norm2", "norm3", "norm4", "wq", "wk", "wv", "wo",
                "w_gate", "w_up", "w_down")


def sizes(cfg: Dict[str, Any]) -> Dict[str, Any]:
    """The stack's sizes under short names, from the published keys."""
    return {
        "H": int(cfg["hidden_size"]), "L": int(cfg["layers"]),
        "R": int(cfg["total_ut_steps"]), "heads": int(cfg["num_attention_heads"]),
        "kv": int(cfg["num_key_value_heads"]), "D": int(cfg["head_dim"]),
        "W": int(cfg["intermediate_size"]), "theta": float(cfg["rope_theta"]),
        "eps": float(cfg["rms_norm_eps"]),
    }


# ------------------------------------------------------------------- shapes
def weight_shapes(cfg: Dict[str, Any]):
    """Both nets' weights as the program's init lays them out: a block's
    leaves stacked over the layers."""
    z = sizes(cfg)
    H, L, W, A = z["H"], z["L"], z["W"], z["heads"] * z["D"]
    if z["kv"] != z["heads"]:
        raise ValueError("the ouro block has as many key-value heads as heads")
    acts, O = int(cfg["action_dim"]), int(cfg["obs_shape"][0])
    f32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32)  # noqa: E731

    def dense(n_in, n_out):
        return {"bias": f32(n_out), "kernel": f32(n_in, n_out)}

    def core():
        kernels = {"wq": (H, A), "wk": (H, A), "wv": (H, A), "wo": (A, H),
                   "w_gate": (H, W), "w_up": (H, W), "w_down": (W, H)}
        return {"ouro": {
            "final_norm": f32(H),
            **{n: f32(L, *kernels.get(n, (H,))) for n in BLOCK_LEAVES},
        }}

    torso = {"Dense_0": dense(O, H)}
    actor = {"params": {"torso": torso, "core": core(), "head": dense(H, acts)}}
    critic = {"params": {"torso": torso, "mix": dense(H + acts, H), "core": core(),
                         "head": dense(H, 1)}}
    return actor, critic


# ------------------------------------------------------------------- layer
def block(p, x, mask, z, past=None):
    """One application of a block (``p``: one layer's leaves) over ``x [B, T,
    H]``; ``past``: the keys and values ``(k, v)`` of the ``M`` steps before
    (``mask`` is then ``[B, T, M + T]``).  Returns ``y`` and this call's own
    ``(k, v)``."""
    B, T, _ = x.shape
    M = 0 if past is None else past[0].shape[1]
    h = _rms(x, p["norm1"], z["eps"])
    heads = lambda y: y.reshape(B, T, z["heads"], z["D"])  # noqa: E731
    q = _rope(heads(jnp.matmul(h, p["wq"])), z["theta"], M)
    k = _rope(heads(jnp.matmul(h, p["wk"])), z["theta"], M)
    v = heads(jnp.matmul(h, p["wv"]))
    own = (k, v)
    if past is not None:
        k = jnp.concatenate([past[0], k], axis=1)
        v = jnp.concatenate([past[1], v], axis=1)
    s = jnp.einsum("bthd,bshd->bhts", q, k) / jnp.sqrt(jnp.float32(z["D"]))
    s = jnp.where(mask[:, None], s, -jnp.inf)
    a = jnp.einsum("bhts,bshd->bthd", jax.nn.softmax(s, axis=-1), v)
    x = x + _rms(jnp.matmul(a.reshape(B, T, -1), p["wo"]), p["norm2"], z["eps"])
    h = _rms(x, p["norm3"], z["eps"])
    m = jnp.matmul(jax.nn.silu(jnp.matmul(h, p["w_gate"])) * jnp.matmul(h, p["w_up"]),
                   p["w_down"])
    return x + _rms(m, p["norm4"], z["eps"]), own


def actor_inputs(p, obs):
    return ref.torso(p["torso"], obs)


def critic_inputs(p, obs, act):
    x = jnp.concatenate([ref.torso(p["torso"], obs), act], axis=-1)
    return jax.nn.relu(ref._dense(p["mix"], x))


def actor_outputs(p, y):
    return jnp.tanh(ref._dense(p["head"], y))


def critic_outputs(p, y):
    return ref._dense(p["head"], y)[..., 0]


# ------------------------------------------------------------------ update
def _add(a, b):
    return jax.tree_util.tree_map(jnp.add, a, b)


class Reference:
    """The update of one configuration, as jitted pieces chained in Python.

    ``update(state, batch, w)`` is ``reference.learner_update``'s contract:
    (new state, priorities ``[B]``, losses).  Among the losses ``grads``, the
    clipped gradients both optimizers were given (``grads="norms"``: each
    leaf's norm in its place, for a caller that cannot keep 1.6 GB more),
    and ``last_step_rel_change``, the mean over the five window passes."""

    def __init__(self, hp: Dict[str, Any], cfg: Dict[str, Any],
                 precision: str = "default", grads: str = "whole"):
        self.hp, self.z = dict(hp), sizes(cfg)
        z, jit = self.z, lambda fn: ref.at(precision, fn)  # noqa: E731

        def vjp(fn):
            """Jitted ``(g, *args) -> `` the cotangent of every argument."""
            return jit(lambda g, *args: jax.vjp(fn, *args)[1](g))

        self.block = jit(lambda p, x, mask, past: block(p, x, mask, z, past))
        out = lambda p, x, mask, past: block(p, x, mask, z, past)[0]  # noqa: E731
        self.block_vjp = jit(lambda g, p, x, mask, past: jax.vjp(
            lambda p, x: out(p, x, mask, past), p, x)[1](g))
        self.block_vjp_x = jit(lambda g, p, x, mask, past: jax.vjp(
            lambda x: out(p, x, mask, past), x)[1](g)[0])
        norm = lambda s, x: _rms(x, s, z["eps"])  # noqa: E731
        self.norm, self.norm_vjp = jit(norm), vjp(norm)
        self.inputs = {"actor": jit(actor_inputs), "critic": jit(critic_inputs)}
        self.inputs_vjp = {"actor": vjp(actor_inputs), "critic": vjp(critic_inputs)}
        self.outputs = {"actor": jit(actor_outputs), "critic": jit(critic_outputs)}
        self.outputs_vjp = {"actor": vjp(actor_outputs), "critic": vjp(critic_outputs)}
        self.mask = jit(episode_mask)
        self.moved = jit(lambda x, before: jnp.sqrt(
            jnp.sum(jnp.square(x - before)) / jnp.sum(jnp.square(before))))

        def targets(rew, disc, reset, q):
            return ref.n_step_targets(rew, disc, reset, q, hp["n_step"], hp["gamma"])

        def critic_loss(q, y, w):
            loss = lambda q: jnp.mean(w[:, None] * ref.huber(y - q))  # noqa: E731
            value, g = jax.value_and_grad(loss)(q)
            return value, g, ref.sequence_priority(y - q, hp["eta"])

        self.targets, self.critic_loss = jit(targets), jit(critic_loss)

        def finish(state, g_actor, g_critic):
            critic, critic_opt, g_critic = ref.adam_update(
                state["critic"], g_critic, state["critic_opt"], state["step"],
                hp["critic_lr"], hp["grad_clip"])
            actor, actor_opt, g_actor = ref.adam_update(
                state["actor"], g_actor, state["actor_opt"], state["step"],
                hp["actor_lr"], hp["grad_clip"])
            tau = hp["tau"]
            polyak = lambda on, tg: jax.tree_util.tree_map(  # noqa: E731
                lambda o, t: tau * o + (1.0 - tau) * t, on, tg)
            given = {"actor": g_actor, "critic": g_critic}
            if grads == "norms":
                given = jax.tree_util.tree_map(
                    lambda g: jnp.sqrt(jnp.sum(jnp.square(g))), given)
            return {
                "actor": actor, "critic": critic,
                "target_actor": polyak(actor, state["target_actor"]),
                "target_critic": polyak(critic, state["target_critic"]),
                "actor_opt": actor_opt, "critic_opt": critic_opt,
                "step": state["step"] + 1,
            }, given

        # Gradients that leave as norms have no output to lend their buffers to.
        self.finish = jax.jit(
            finish, donate_argnums=(0,) if grads == "norms" else (0, 1, 2))

    # ---------------------------------------------------------- one net
    def _core(self, pc, x, mask, memory):
        """The looped stack, a block at a time.  Returns ``h^(R)``, the tape
        (each application's input, each loop step's state before the final
        norm, each layer's leaves), every application's own keys and values,
        and how far the last loop step moved the state."""
        z = self.z
        layers = [{n: pc[n][i] for n in BLOCK_LEAVES} for i in range(z["L"])]
        ins, ends, kv = [], [], []
        for r in range(z["R"]):
            before = x
            for i in range(z["L"]):
                ins.append(x)
                x, own = self.block(layers[i], x, mask,
                                    None if memory is None else memory[len(kv)])
                kv.append(own)
            ends.append(x)
            x = self.norm(pc["final_norm"], x)
        return x, (ins, ends, layers), kv, self.moved(x, before)

    def _core_back(self, pc, tape, mask, memory, g, weights: bool):
        """The cotangent of the core's input from that of ``h^(R)``, and,
        with ``weights``, the core's gradient: each layer's summed over the
        loop steps that used it."""
        z = self.z
        ins, ends, layers = tape
        g_layers = [None] * z["L"]
        g_final = None
        for r in reversed(range(z["R"])):
            g_scale, g = self.norm_vjp(g, pc["final_norm"], ends[r])
            g_final = g_scale if g_final is None else g_final + g_scale
            for i in reversed(range(z["L"])):
                n = r * z["L"] + i
                past = None if memory is None else memory[n]
                if weights:
                    g_p, g = self.block_vjp(g, layers[i], ins[n], mask, past)
                    g_layers[i] = g_p if g_layers[i] is None else _add(g_layers[i], g_p)
                else:
                    g = self.block_vjp_x(g, layers[i], ins[n], mask, past)
        if not weights:
            return g, None
        g_core = {n: jnp.stack([g_layers[i][n] for i in range(z["L"])])
                  for n in BLOCK_LEAVES}
        return g, dict(g_core, final_norm=g_final)

    @staticmethod
    def _outer(p):
        return {k: v for k, v in p.items() if k != "core"}

    def _forward(self, kind, net, mask, memory, *inputs):
        """One window pass of a net -> (its outputs, what its backward pass
        needs, how far the last loop step moved the state)."""
        p = net["params"]
        x0 = self.inputs[kind](self._outer(p), *inputs)
        y, tape, _, moved = self._core(p["core"]["ouro"], x0, mask, memory)
        return self.outputs[kind](self._outer(p), y), (inputs, y, tape), moved

    def _backward(self, kind, net, mask, memory, kept, g, weights: bool):
        """From the cotangent of a net's outputs: that of its last input (the
        critic's actions) and, with ``weights``, the net's gradient."""
        p = net["params"]
        inputs, y, tape = kept
        g_post, g = self.outputs_vjp[kind](g, self._outer(p), y)
        g, g_core = self._core_back(p["core"]["ouro"], tape, mask, memory, g, weights)
        g_pre, *g_in = self.inputs_vjp[kind](g, self._outer(p), *inputs)
        if not weights:
            return g_in[-1], None
        return g_in[-1], {"params": dict(_add(g_post, g_pre), core={"ouro": g_core})}

    def _memory(self, kind, net, mask, *inputs):
        p = net["params"]
        x0 = self.inputs[kind](self._outer(p), *inputs)
        return self._core(p["core"]["ouro"], x0, mask, None)[2]

    # ------------------------------------------------------- the update
    def update(self, state, batch, w):
        hp = self.hp
        Bn, U, n = hp["burnin"], hp["unroll"], hp["n_step"]
        obs, act, reset = batch["obs"], batch["action"], batch["reset"]
        if obs.shape[1] != Bn + U + n:
            raise ValueError(f"sequence length {obs.shape[1]} != {Bn + U + n}")
        pre, on, win, unr = slice(0, Bn), slice(0, Bn + U), slice(Bn, None), slice(Bn, Bn + U)
        seen = self.mask(reset)
        m_pre, m_win, m_on = seen[:, pre, pre], seen[:, win], seen[:, unr, on]
        moved = []

        def memory_of(name):
            if Bn == 0:
                return None
            ins = (obs[:, pre],) if "actor" in name else (obs[:, pre], act[:, pre])
            return self._memory(name.replace("target_", ""), state[name], m_pre, *ins)

        # The targets: both target nets over the window and the n-step tail.
        mem = memory_of("target_actor")
        a_tg, _, mv = self._forward("actor", state["target_actor"], m_win, mem, obs[:, win])
        moved.append(mv)
        mem = memory_of("target_critic")
        q_tg, _, mv = self._forward("critic", state["target_critic"], m_win, mem,
                                    obs[:, win], a_tg)
        moved.append(mv)
        y = self.targets(batch["reward"][:, win], batch["discount"][:, win],
                         reset[:, win], q_tg)
        del mem, a_tg, q_tg

        # The critic's loss.
        mem_c = memory_of("critic")
        q, kept, mv = self._forward("critic", state["critic"], m_on, mem_c,
                                    obs[:, unr], act[:, unr])
        moved.append(mv)
        critic_loss, g_q, priorities = self.critic_loss(q, y, w)
        _, g_critic = self._backward("critic", state["critic"], m_on, mem_c, kept, g_q, True)
        del kept

        # The actor's loss: -Q(s, mu(s)) through the online critic, no
        # gradient to the critic's weights.
        mem_a = memory_of("actor")
        a, kept_a, mv = self._forward("actor", state["actor"], m_on, mem_a, obs[:, unr])
        moved.append(mv)
        q_pi, kept_c, mv = self._forward("critic", state["critic"], m_on, mem_c,
                                         obs[:, unr], a)
        moved.append(mv)
        actor_loss, q_abs = -jnp.mean(q_pi), jnp.mean(jnp.abs(q_pi))
        g_a, _ = self._backward("critic", state["critic"], m_on, mem_c, kept_c,
                                jnp.full_like(q_pi, -1.0 / q_pi.size), False)
        del kept_c, mem_c
        _, g_actor = self._backward("actor", state["actor"], m_on, mem_a, kept_a, g_a, True)
        del kept_a, mem_a

        state, given = self.finish(state, g_actor, g_critic)
        losses = {
            "critic_loss": critic_loss, "actor_loss": actor_loss, "q_abs_mean": q_abs,
            "grads": given, "last_step_rel_change": jnp.mean(jnp.stack(moved)),
        }
        return state, priorities, losses


def learner_update(state, batch, w, hp, cfg):
    """One learner update; ``batch`` leaves are ``[B, L, ...]`` (no carries).
    ``reference.learner_update``'s contract (``Reference.update``)."""
    return Reference(hp, cfg).update(state, batch, w)
