"""Plain reference for the R2D2-DPG learner over SDAR-30B-A3B-Chat's block.

The whole learner update (both losses, n-step targets, priorities, clipping,
Adam, Polyak) in float32 ``jax.numpy``: a Python loop over layers and over
the held experts with masks, no scan, no kernel.  Nothing of
``r2d2dpg_tpu`` is imported; the update's arithmetic
that does not depend on the core (targets, Huber, Adam, Polyak, priorities)
is ``chipbench/reference.py``'s.

The layer, as published (``config.json`` of JetLM/SDAR-30B-A3B-Chat,
``model_type`` ``sdar_moe``; the q/k norm is the Qwen3-MoE lineage's)::

    h1 = RMSNorm(x);  q = h1 Wq,  k = h1 Wk,  v = h1 Wv            (no bias)
    q, k <- RMSNorm over each head's dims, learned scale
    q, k <- RoPE(theta, all head dims, position = the step's index)
    a = softmax_f32(q k^T / sqrt(d) + mask) v;   x' = x + a Wo
    h2 = RMSNorm(x');  p = softmax_f32(h2 Wr) over all experts;  S = top-k of p
    y = x' + sum_{e in S, held here} (p_e / sum_S p) (silu(h2 Wg_e) * (h2 Wu_e)) Wd_e

``mask``: step t sees step s iff s <= t and no reset lies in (s, t].  After
the last block the final RMSNorm, then the head.  The share: experts
``shard * held .. shard * held + held - 1`` of the router's ``router_experts``
are here; the others' part of ``y`` is left out.  The router's logits are
taken at precision ``highest`` whatever the ambient precision.

Burn-in is R2D2's in attention's terms: each net's keys and values over the
steps ``< burnin`` are made first, under ``stop_gradient`` (``burn_in``), and
the steps after attend to them (``core``): the same numbers as one pass over
the whole sequence with its prefix under ``stop_gradient``, which is how
this file was first written; on the chip the two orders round differently
at the MXU's bf16 pass (0.013 of the largest Q apart on one batch, my chip
run, PR 27), so the reference evaluates in the program's order and the CPU
tests hold that order to the whole-sequence one
(``tests/test_sdar_moe.py``).  The target nets run to ``burnin + unroll +
n_step``, the online nets to ``burnin + unroll``; the critic of the actor's
loss sees the stored actions before ``burnin`` and the policy's after, over
the online critic's own memory.

``cfg`` is ``chipbench/configs/humanoid_sdar_moe.json`` as a dict.
"""

from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp

from chipbench import reference as ref

# The passes whose routing the program counts, in its order
# (``models/sdar_moe.py::MOE_PASSES``): the burn-in prefixes of the four
# nets, then the windows.
PASSES = (
    "burn_actor", "burn_target_actor", "burn_critic", "burn_target_critic",
    "target_actor", "target_critic", "critic", "actor", "critic_pi",
)


def sizes(cfg: Dict[str, Any]) -> Dict[str, int]:
    """The block's sizes under short names, from the published keys."""
    held = int(cfg["num_experts"])
    return {
        "H": int(cfg["hidden_size"]), "L": int(cfg["layers"]),
        "heads": int(cfg["num_attention_heads"]),
        "kv": int(cfg["num_key_value_heads"]), "D": int(cfg["head_dim"]),
        "theta": float(cfg["rope_theta"]), "eps": float(cfg["rms_norm_eps"]),
        "R": int(cfg["published"]["num_experts"]), "k": int(cfg["num_experts_per_tok"]),
        "W": int(cfg["moe_intermediate_size"]), "E": held,
        "first": int(cfg["expert_shard"]) * held,
    }


# ------------------------------------------------------------------- shapes
def weight_shapes(cfg: Dict[str, Any]):
    """Both nets' weights as the program's init lays them out."""
    z = sizes(cfg)
    H, D, E, W = z["H"], z["D"], z["E"], z["W"]
    A, O = int(cfg["action_dim"]), int(cfg["obs_shape"][0])
    f32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32)  # noqa: E731

    def dense(n_in, n_out):
        return {"bias": f32(n_out), "kernel": f32(n_in, n_out)}

    def core():
        out = {"final_norm": f32(H)}
        for i in range(z["L"]):
            for name, shape in (
                ("norm1", (H,)), ("norm2", (H,)), ("q_norm", (D,)), ("k_norm", (D,)),
                ("wq", (H, z["heads"] * D)), ("wk", (H, z["kv"] * D)),
                ("wv", (H, z["kv"] * D)), ("wo", (z["heads"] * D, H)),
                ("router", (H, z["R"])),
                ("w_gate", (E, H, W)), ("w_up", (E, H, W)), ("w_down", (E, W, H)),
            ):
                out[f"block_{i}_{name}"] = f32(*shape)
        return {"sdar": out}

    torso = {"Dense_0": dense(O, H)}
    actor = {"params": {"torso": torso, "core": core(), "head": dense(H, A)}}
    critic = {"params": {"torso": torso, "mix": dense(H + A, H), "core": core(),
                         "head": dense(H, 1)}}
    return actor, critic


# ------------------------------------------------------------------- layer
def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _rope(x, theta, start=0):
    """``x [B, T, heads, D]`` rotated to positions ``start .. start + T - 1``."""
    T, D = x.shape[1], x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D)
    ang = (start + jnp.arange(T, dtype=jnp.float32))[:, None] * inv[None, :]
    cos = jnp.tile(jnp.cos(ang), (1, 2))[None, :, None, :]
    sin = jnp.tile(jnp.sin(ang), (1, 2))[None, :, None, :]
    half = D // 2
    turned = jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)
    return x * cos + turned * sin


def episode_mask(reset):
    """``[B, T, T]``: t sees s iff s <= t and no reset lies in (s, t]."""
    T = reset.shape[1]
    t = jnp.arange(T)[:, None, None]
    s = jnp.arange(T)[None, :, None]
    u = jnp.arange(T)[None, None, :]
    inside = ((s < u) & (u <= t)).astype(jnp.float32)  # u lies in (s, t]
    resets = jnp.einsum("bu,tsu->bts", (reset > 0).astype(jnp.float32), inside,
                        precision=jax.lax.Precision.HIGHEST)
    return (resets == 0) & (s <= t)[None, :, :, 0]


def router(h2, w_router, z):
    """Gates ``[N, R]`` (0 off the chosen k) and the chosen ids."""
    logits = jnp.matmul(h2, w_router, precision=jax.lax.Precision.HIGHEST)
    p = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    top_p, top_e = jax.lax.top_k(p, z["k"])
    chosen = jnp.sum(jax.nn.one_hot(top_e, z["R"], dtype=p.dtype), axis=1)
    return p * chosen / jnp.sum(top_p, axis=-1, keepdims=True), chosen


def keys_values(p, i, x, z, start=0):
    """Rotated queries and keys, and values, of block ``i`` for ``x [B, T, H]``
    at positions ``start ..``."""
    g = lambda name: p[f"block_{i}_{name}"]  # noqa: E731
    B, T, _ = x.shape
    h1 = _rms(x, g("norm1"), z["eps"])
    q = jnp.matmul(h1, g("wq")).reshape(B, T, z["heads"], z["D"])
    k = jnp.matmul(h1, g("wk")).reshape(B, T, z["kv"], z["D"])
    v = jnp.matmul(h1, g("wv")).reshape(B, T, z["kv"], z["D"])
    q = _rope(_rms(q, g("q_norm"), z["eps"]), z["theta"], start)
    k = _rope(_rms(k, g("k_norm"), z["eps"]), z["theta"], start)
    return q, k, v


def attention_part(p, i, x, mask, z, memory=None):
    """``x' = x + attention(RMSNorm(x)) Wo`` of block ``i`` over ``x [B, T, H]``;
    ``memory``: the keys and values ``(k, v)`` of the ``M`` steps before them
    (``mask`` is then ``[B, T, M + T]``).  Returns ``x'`` and this call's own
    ``(k, v)``."""
    B, T, _ = x.shape
    M = 0 if memory is None else memory[0].shape[1]
    q, k, v = keys_values(p, i, x, z, M)
    own = (k, v)
    if memory is not None:
        k = jnp.concatenate([memory[0], k], axis=1)
        v = jnp.concatenate([memory[1], v], axis=1)
    per = z["heads"] // z["kv"]  # each kv head serves this many query heads
    k, v = jnp.repeat(k, per, axis=2), jnp.repeat(v, per, axis=2)
    s = jnp.einsum("bthd,bshd->bhts", q, k) / jnp.sqrt(jnp.float32(z["D"]))
    s = jnp.where(mask[:, None], s, -jnp.inf)
    a = jnp.einsum("bhts,bshd->bthd", jax.nn.softmax(s, axis=-1), v)
    return x + jnp.matmul(a.reshape(B, T, -1), p[f"block_{i}_wo"]), own


def router_input(p, i, x, z):
    """``RMSNorm(x')`` of block ``i`` as tokens ``[B * T, H]``."""
    return _rms(x, p[f"block_{i}_norm2"], z["eps"]).reshape(-1, x.shape[-1])


def experts_part(p, i, x, z):
    """``x' + `` the held experts' part, with the tokens routed to each held
    expert by position ``[B, T, E]``."""
    g = lambda name: p[f"block_{i}_{name}"]  # noqa: E731
    B, T, H = x.shape
    h2 = router_input(p, i, x, z)
    gates, chosen = router(h2, g("router"), z)
    y = jnp.zeros_like(h2)
    for j in range(z["E"]):
        e = z["first"] + j
        out = jnp.matmul(
            jax.nn.silu(jnp.matmul(h2, g("w_gate")[j])) * jnp.matmul(h2, g("w_up")[j]),
            g("w_down")[j])
        y = y + gates[:, e : e + 1] * out
    routed = chosen[:, z["first"] : z["first"] + z["E"]].reshape(B, T, z["E"])
    return x + y.reshape(B, T, H), jax.lax.stop_gradient(routed)


def core(p, x, reset, z, burnin, memory=None):
    """The stack over steps ``burnin ..`` of ``x [B, T, H]`` (``reset [B, T]``
    covers all ``T`` steps), with the final norm -> (``y``, routed ``[L, B, T
    - burnin, E]``).  ``memory``: what ``burn_in`` made of the steps before."""
    mask = episode_mask(reset)[:, burnin:, : reset.shape[1]]
    x = x[:, burnin:]
    routed = []
    for i in range(z["L"]):
        # Recomputed in the backward pass (the same mathematics): a layer's
        # masked products over all tokens for every held expert, kept for
        # three differentiated passes, do not fit the chip beside the state.
        def layer(p, x, mask, kv, i=i):
            x, _ = attention_part(p, i, x, mask, z, kv)
            return experts_part(p, i, x, z)

        x, r = jax.checkpoint(layer)(p, x, mask, None if memory is None else memory[i])
        routed.append(r)
    return _rms(x, p["final_norm"], z["eps"]), jnp.stack(routed)


def burn_in(p, x, reset, z):
    """R2D2's burn-in in attention's terms: the keys and values of every layer
    over the prefix ``x [B, M, H]``, no gradient: the memory ``core`` attends
    to.  The last layer stops at its keys and values (nothing reads its
    output); returns the memory and the routed tokens of the layers before
    it ``[L, B, M, E]`` (the last layer's are none)."""
    mask = episode_mask(reset)
    memory, routed = [], []
    for i in range(z["L"]):
        if i == z["L"] - 1:
            _, k, v = keys_values(p, i, x, z)
            memory.append((k, v))
            routed.append(jnp.zeros_like(routed[-1]))
            break
        x, kv = attention_part(p, i, x, mask, z)
        memory.append(kv)
        x, r = experts_part(p, i, x, z)
        routed.append(r)
    return jax.lax.stop_gradient((memory, jnp.stack(routed)))


def actor_inputs(p, obs):
    return ref.torso(p["torso"], obs)


def critic_inputs(p, obs, act):
    x = jnp.concatenate([ref.torso(p["torso"], obs), act], axis=-1)
    return jax.nn.relu(ref._dense(p["mix"], x))


def actor_over(p, obs, reset, z, burnin, memory=None):
    """-> (actions ``[B, T - burnin, A]``, routed)."""
    y, routed = core(p["core"]["sdar"], actor_inputs(p, obs), reset, z, burnin, memory)
    return jnp.tanh(ref._dense(p["head"], y)), routed


def critic_over(p, obs, act, reset, z, burnin, memory=None):
    """-> (q ``[B, T - burnin]``, routed)."""
    y, routed = core(p["core"]["sdar"], critic_inputs(p, obs, act), reset, z, burnin, memory)
    return ref._dense(p["head"], y)[..., 0], routed


# ------------------------------------------------------------------ update
def learner_update(state, batch, w, hp, cfg):
    """One learner update; ``batch`` leaves are ``[B, L, ...]`` (no carries).
    Returns (state, priorities ``[B]``, losses) as ``reference.learner_update``
    does, with ``loads`` among the losses: the tokens each held expert
    received, ``[len(PASSES), layers, E]``."""
    z = sizes(cfg)
    Bn, U, n = hp["burnin"], hp["unroll"], hp["n_step"]
    obs, act, reset = batch["obs"], batch["action"], batch["reset"]
    if obs.shape[1] != Bn + U + n:
        raise ValueError(f"sequence length {obs.shape[1]} != {Bn + U + n}")
    pre, on, win = slice(0, Bn), slice(0, Bn + U), slice(Bn, None)
    sg = jax.lax.stop_gradient
    routed = {}

    def memory_of(name, inputs):
        net = state[name]["params"]
        memory, routed["burn_" + name] = burn_in(
            net["core"]["sdar"], inputs(net), reset[:, pre], z)
        return memory

    in_a = lambda net: actor_inputs(net, obs[:, pre])  # noqa: E731
    in_c = lambda net: critic_inputs(net, obs[:, pre], act[:, pre])  # noqa: E731
    mem = {"actor": memory_of("actor", in_a), "target_actor": memory_of("target_actor", in_a),
           "critic": memory_of("critic", in_c), "target_critic": memory_of("target_critic", in_c)}

    def after_burn_in(actions, upto):
        """The stored actions before the burn-in's end, the policy's after."""
        return jnp.concatenate([act[:, pre], actions], axis=1)[:, :upto]

    a_tg, routed["target_actor"] = actor_over(
        state["target_actor"]["params"], obs, reset, z, Bn, mem["target_actor"])
    q_tg, routed["target_critic"] = critic_over(
        state["target_critic"]["params"], obs, after_burn_in(a_tg, None), reset, z, Bn,
        mem["target_critic"])
    y = sg(ref.n_step_targets(batch["reward"][:, win], batch["discount"][:, win],
                              reset[:, win], q_tg, n, hp["gamma"]))

    def critic_loss_fn(p):
        q, r = critic_over(p["params"], obs[:, on], act[:, on], reset[:, on], z, Bn,
                           mem["critic"])
        td = y - q
        return jnp.mean(w[:, None] * ref.huber(td)), (td, r)

    def actor_loss_fn(p):
        a, ra = actor_over(p["params"], obs[:, on], reset[:, on], z, Bn, mem["actor"])
        q, rc = critic_over(state["critic"]["params"], obs[:, on],
                            after_burn_in(a, Bn + U), reset[:, on], z, Bn, mem["critic"])
        return -jnp.mean(q), (jnp.mean(jnp.abs(q)), ra, rc)

    (critic_loss, (td, routed["critic"])), g_critic = jax.value_and_grad(
        critic_loss_fn, has_aux=True)(state["critic"])
    (actor_loss, (q_abs, routed["actor"], routed["critic_pi"])), g_actor = (
        jax.value_and_grad(actor_loss_fn, has_aux=True)(state["actor"]))

    critic, critic_opt, g_critic = ref.adam_update(
        state["critic"], g_critic, state["critic_opt"], state["step"],
        hp["critic_lr"], hp["grad_clip"])
    actor, actor_opt, g_actor = ref.adam_update(
        state["actor"], g_actor, state["actor_opt"], state["step"],
        hp["actor_lr"], hp["grad_clip"])
    tau = hp["tau"]

    def polyak(online, target):
        return jax.tree_util.tree_map(
            lambda o, t: tau * o + (1.0 - tau) * t, online, target)

    new_state = {
        "actor": actor, "critic": critic,
        "target_actor": polyak(actor, state["target_actor"]),
        "target_critic": polyak(critic, state["target_critic"]),
        "actor_opt": actor_opt, "critic_opt": critic_opt,
        "step": state["step"] + 1,
    }
    losses = {
        "critic_loss": critic_loss, "actor_loss": actor_loss, "q_abs_mean": q_abs,
        "grads": {"actor": g_actor, "critic": g_critic},
        "loads": jnp.stack([routed[name].sum(axis=(1, 2)) for name in PASSES]),
    }
    return new_state, ref.sequence_priority(td, hp["eta"]), losses
