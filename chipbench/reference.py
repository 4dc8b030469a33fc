"""Plain reference for the R2D2-DPG learner and the replay operations.

Straightforward ``jax.numpy`` in float32 with every matmul and convolution
at ``highest`` precision (or, named so, at JAX's default: see
``PRECISIONS``), time steps as a Python loop (no ``lax.scan``), no
kernel, no batching tricks.  It imports nothing of ``r2d2dpg_tpu`` and takes
nothing the program made: weights, replay rows and priorities come from
``chipbench.traffic`` (made from ``--seed``), optimizer state starts here.

The equations (Kapturowski et al. 2019 for the replay recipe, Lillicrap et
al. 2015 for DDPG, the LSTM as flax's ``OptimizedLSTMCell`` lays it out):

- actor ``a = tanh(W_head h)``, ``h`` from ``LSTM(torso(o))``;
  critic ``q = w_head h``, ``h`` from ``LSTM(relu(W_mix [torso(o), a]))``;
- the carry is zeroed where ``reset`` is set before the cell runs;
- burn-in from the *stored* carries over the first ``burnin`` steps for
  the four nets, no gradient;
- ``y_t = sum_k gamma^k r_{t+k} + gamma^n Q'(s_{t+n}, mu'(s_{t+n}))`` with
  the horizon cut at an episode boundary (termination: reward counts,
  nothing after; truncation: bootstrap at the last stored state);
- critic loss ``mean(w_i * huber(y - q))``, actor loss ``-mean Q(s, mu(s))``;
- Adam (b1 .9, b2 .999, eps 1e-8) after a clip of each net's gradient to a
  global norm; Polyak targets from the *new* online weights;
- priority ``eta max|td| + (1 - eta) mean|td| + 1e-6``;
- proportional sampling by inverse CDF over ``p^alpha`` (in float64 here),
  IS weights ``(N P(i))^-beta / max``.
"""

from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

PRIORITY_EPS = 1e-6
_ADAM_B1, _ADAM_B2, _ADAM_EPS = 0.9, 0.999, 1e-8

# The precision of every matmul and convolution below is JAX's ambient
# ``default_matmul_precision``; ``at(name, fn)`` sets it while ``fn`` is
# traced.  ``highest`` is the reference proper.  ``default`` is the same
# mathematics at JAX's default matmul precision, which is what a float32
# configuration that names no precision runs at: on a TPU one bf16 pass of
# the MXU (operands rounded to bfloat16, products summed in float32), on the
# CPU plain float32.
PRECISIONS = ("highest", "default")


def at(name: str, fn):
    """``fn`` jitted, its matmuls and convolutions at precision ``name``."""
    if name not in PRECISIONS:
        raise ValueError(f"unknown precision {name!r}; have {PRECISIONS}")

    def traced(*args):
        with jax.default_matmul_precision(name):
            return fn(*args)

    return jax.jit(traced)


# ------------------------------------------------------------------- shapes
CONV_STACK = ((32, 8, 4), (64, 4, 2), (64, 3, 1))  # features, kernel, stride


def weight_shapes(cfg: Dict[str, Any]):
    """The shapes of the actor's and the critic's weights, from the
    configuration file alone (``hidden``, ``obs_shape``, ``action_dim``):
    the tree ``traffic.make_weights`` fills from the seed.  A driver holds
    the program's own tree equal to this one before it hands the weights
    over."""
    H, A = int(cfg["hidden"]), int(cfg["action_dim"])
    f32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32)  # noqa: E731

    def dense(n_in, n_out):
        return {"bias": f32(n_out), "kernel": f32(n_in, n_out)}

    def torso_shapes():
        shape = tuple(cfg["obs_shape"])
        if len(shape) == 1:
            return {"Dense_0": dense(shape[0], H)}
        h, w, c = shape
        out = {}
        for i, (features, k, s) in enumerate(CONV_STACK):
            out[f"Conv_{i}"] = {"bias": f32(features), "kernel": f32(k, k, c, features)}
            h, w, c = (h - k) // s + 1, (w - k) // s + 1, features
        out["Dense_0"] = dense(h * w * c, H)
        return out

    def core():
        cell = {"i" + g: {"kernel": f32(H, H)} for g in "ifgo"}
        cell.update({"h" + g: dense(H, H) for g in "ifgo"})
        return {"OptimizedLSTMCell_0": cell}

    actor = {"params": {"torso": torso_shapes(), "core": core(), "head": dense(H, A)}}
    critic = {"params": {"torso": torso_shapes(), "mix": dense(H + A, H),
                         "core": core(), "head": dense(H, 1)}}
    return actor, critic


# ------------------------------------------------------------------ networks
def _dense(p, x):
    return jnp.matmul(x.astype(jnp.float32), p["kernel"]) + p["bias"]


def _conv(p, x, stride):
    y = jax.lax.conv_general_dilated(
        x,
        p["kernel"],
        window_strides=(stride, stride),
        padding="VALID",
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
    )
    return y + p["bias"]


def torso(p, obs):
    """Observation encoder on ``[..., *obs_shape]``: one ReLU dense layer for
    flat observations, the Nature-DQN conv stack for pixels."""
    if "Conv_0" not in p:
        return jax.nn.relu(_dense(p["Dense_0"], obs))
    lead = obs.shape[:-3]
    x = obs.reshape((-1,) + obs.shape[-3:])
    scale = 255.0 if obs.dtype == jnp.uint8 else 1.0
    x = x.astype(jnp.float32) / scale
    for name, stride in (("Conv_0", 4), ("Conv_1", 2), ("Conv_2", 1)):
        x = jax.nn.relu(_conv(p[name], x, stride))
    x = x.reshape(x.shape[0], -1)
    x = jax.nn.relu(_dense(p["Dense_0"], x))
    return x.reshape(lead + x.shape[-1:])


def lstm(p, x, carry, reset):
    """One LSTM step, gates (i, f, g, o); the carry is float32 ``(c, h)``."""
    c, h = carry
    keep = (1.0 - reset.astype(jnp.float32))[:, None]
    c, h = c * keep, h * keep

    def gate(g):
        zi = jnp.matmul(x, p["i" + g]["kernel"])
        zh = jnp.matmul(h, p["h" + g]["kernel"])
        return zi + zh + p["h" + g]["bias"]

    i, f, g, o = (gate(k) for k in "ifgo")
    c = jax.nn.sigmoid(f) * c + jax.nn.sigmoid(i) * jnp.tanh(g)
    h = jax.nn.sigmoid(o) * jnp.tanh(c)
    return (c, h)


def actor_core(p, feat, carry, reset):
    """Actor after its torso: ``feat [B, H]`` -> (action ``[B, A]``, carry)."""
    carry = lstm(p["core"]["OptimizedLSTMCell_0"], feat, carry, reset)
    a = jnp.tanh(_dense(p["head"], carry[1]))
    return a, carry


def critic_core(p, feat, action, carry, reset):
    """Critic after its torso -> (q ``[B]``, carry); the action joins after
    the first layer."""
    x = jnp.concatenate([feat, action], axis=-1)
    x = jax.nn.relu(_dense(p["mix"], x))
    carry = lstm(p["core"]["OptimizedLSTMCell_0"], x, carry, reset)
    q = _dense(p["head"], carry[1])
    return q[:, 0], carry


# ------------------------------------------------------------- update maths
def n_step_targets(rew, disc, reset, q, n, gamma):
    """``[B, U+n]`` inputs -> ``[B, U]`` targets, horizon cut at boundaries."""
    U = rew.shape[1] - n
    y = q[:, :U]
    acc = jnp.zeros_like(y)
    cont = jnp.ones_like(y)
    live = jnp.ones_like(y)
    for k in range(n):
        d = disc[:, k : k + U]
        crossed = reset[:, k + 1 : k + 1 + U]
        truncated = crossed * (d > 0.0)
        go = (live * (1.0 - truncated)) > 0
        acc_k = acc + gamma**k * cont * rew[:, k : k + U]
        cont_k = cont * d
        y_k = acc_k + gamma ** (k + 1) * cont_k * q[:, k + 1 : k + 1 + U]
        y = jnp.where(go, y_k, y)
        acc = jnp.where(go, acc_k, acc)
        cont = jnp.where(go, cont_k, cont)
        live = live * (1.0 - crossed)
    return y


def huber(x):
    a = jnp.abs(x)
    quad = jnp.minimum(a, 1.0)
    return 0.5 * quad**2 + (a - quad)


def global_norm(tree):
    return jnp.sqrt(
        sum(jnp.sum(jnp.square(x)) for x in jax.tree_util.tree_leaves(tree))
    )


def adam_init(params):
    z = jax.tree_util.tree_map(jnp.zeros_like, params)
    return {"mu": z, "nu": jax.tree_util.tree_map(jnp.zeros_like, params)}


def adam_update(params, grads, opt, count, lr, clip):
    """Clip to a global norm, then one Adam step; ``count`` is the number of
    steps taken before this one.  Returns the clipped gradient too (it is
    what the optimizer was given)."""
    if clip is not None:
        g_norm = global_norm(grads)
        factor = jnp.where(g_norm < clip, 1.0, clip / g_norm)
        grads = jax.tree_util.tree_map(lambda g: g * factor, grads)
    t = count + 1
    mu = jax.tree_util.tree_map(
        lambda m, g: _ADAM_B1 * m + (1 - _ADAM_B1) * g, opt["mu"], grads
    )
    nu = jax.tree_util.tree_map(
        lambda v, g: _ADAM_B2 * v + (1 - _ADAM_B2) * g * g, opt["nu"], grads
    )
    c1 = 1 - _ADAM_B1 ** t.astype(jnp.float32)
    c2 = 1 - _ADAM_B2 ** t.astype(jnp.float32)
    params = jax.tree_util.tree_map(
        lambda p, m, v: p - lr * (m / c1) / (jnp.sqrt(v / c2) + _ADAM_EPS),
        params,
        mu,
        nu,
    )
    return params, {"mu": mu, "nu": nu}, grads


def init_state(actor_params, critic_params) -> Dict[str, Any]:
    """The learner's state from the seed's weights: targets are copies,
    Adam's moments zero, step 0."""
    return {
        "actor": actor_params,
        "critic": critic_params,
        "target_actor": actor_params,
        "target_critic": critic_params,
        "actor_opt": adam_init(actor_params),
        "critic_opt": adam_init(critic_params),
        "step": jnp.zeros((), jnp.int32),
    }


def critic_over(p, feats, acts, resets, carry):
    """Critic over ``[B, T, ...]`` inputs -> (q ``[B, T]``, final carry)."""
    qs = []
    for t in range(feats.shape[1]):
        q, carry = critic_core(p, feats[:, t], acts[:, t], carry,
                               resets[:, t])
        qs.append(q)
    return jnp.stack(qs, axis=1), carry


def actor_over(p, feats, resets, carry):
    """Actor over ``[B, T, H]`` -> (actions ``[B, T, A]``, final carry)."""
    acts = []
    for t in range(feats.shape[1]):
        a, carry = actor_core(p, feats[:, t], carry, resets[:, t])
        acts.append(a)
    return jnp.stack(acts, axis=1), carry


def pi_q_over(pa, pc, fa, fc, resets, ca, cc):
    """Policy and critic stepped together: ``q_t = Q(o_t, mu(o_t))``."""
    qs = []
    for t in range(fa.shape[1]):
        a, ca = actor_core(pa, fa[:, t], ca, resets[:, t])
        q, cc = critic_core(pc, fc[:, t], a, cc, resets[:, t])
        qs.append(q)
    return jnp.stack(qs, axis=1)


def _burn_in_and_targets(state, batch, hp):
    """What the loss and the ranking of fresh sequences share: the torso
    features, the four nets' carries after the burn-in (no gradient) and the
    n-step targets ``y [B, U]`` through the target nets."""
    Bn, U, n = hp["burnin"], hp["unroll"], hp["n_step"]
    obs, act, reset = batch["obs"], batch["action"], batch["reset"]
    if obs.shape[1] != Bn + U + n:
        raise ValueError(f"sequence length {obs.shape[1]} != {Bn + U + n}")
    ca0, cc0 = batch["carries"]["actor"], batch["carries"]["critic"]
    burn, win = slice(0, Bn), slice(Bn, None)
    pa, pc = state["actor"]["params"], state["critic"]["params"]
    pa_t = state["target_actor"]["params"]
    pc_t = state["target_critic"]["params"]

    # Torsos see every frame once per net; they carry no state.
    fa_on = torso(pa["torso"], obs)
    fc_on = torso(pc["torso"], obs)
    fa_tg = torso(pa_t["torso"], obs)
    fc_tg = torso(pc_t["torso"], obs)

    _, ca_on = actor_over(pa, fa_on[:, burn], reset[:, burn], ca0)
    _, ca_tg = actor_over(pa_t, fa_tg[:, burn], reset[:, burn], ca0)
    _, cc_on = critic_over(pc, fc_on[:, burn], act[:, burn], reset[:, burn],
                           cc0)
    _, cc_tg = critic_over(pc_t, fc_tg[:, burn], act[:, burn], reset[:, burn],
                           cc0)
    sg = jax.lax.stop_gradient
    ca_on, ca_tg, cc_on, cc_tg = sg((ca_on, ca_tg, cc_on, cc_tg))

    q_tg = pi_q_over(pa_t, pc_t, fa_tg[:, win], fc_tg[:, win], reset[:, win],
                     ca_tg, cc_tg)
    y = sg(n_step_targets(batch["reward"][:, win], batch["discount"][:, win],
                          reset[:, win], q_tg, n, hp["gamma"]))
    return sg(fc_on), ca_on, cc_on, y


def sequence_priority(td, eta):
    a_td = jnp.abs(td)
    return eta * a_td.max(axis=1) + (1 - eta) * a_td.mean(axis=1) + PRIORITY_EPS


def initial_priority(state, batch, hp):
    """The priority fresh sequences enter the replay with: the TD error of
    the current nets over the training window, no update."""
    Bn, U = hp["burnin"], hp["unroll"]
    unr = slice(Bn, Bn + U)
    fc_on, _, cc_on, y = _burn_in_and_targets(state, batch, hp)
    q, _ = critic_over(state["critic"]["params"], fc_on[:, unr],
                       batch["action"][:, unr], batch["reset"][:, unr], cc_on)
    return sequence_priority(y - q, hp["eta"])


def learner_update(state, batch, w, hp):
    """One learner update.  ``batch`` leaves are ``[B, L, ...]`` with stored
    carries ``{"actor": (c, h), "critic": (c, h)}``; ``hp`` holds burnin,
    unroll, n_step, gamma, tau, eta, actor_lr, critic_lr, grad_clip.

    Returns (state, priorities ``[B]``, losses).
    """
    Bn, U = hp["burnin"], hp["unroll"]
    obs, act, reset = batch["obs"], batch["action"], batch["reset"]
    unr = slice(Bn, Bn + U)
    pc = state["critic"]["params"]
    fc_on, ca_on, cc_on, y = _burn_in_and_targets(state, batch, hp)

    def critic_loss_fn(p):
        p = p["params"]
        feats = torso(p["torso"], obs[:, unr])
        q, _ = critic_over(p, feats, act[:, unr], reset[:, unr], cc_on)
        td = y - q
        return jnp.mean(w[:, None] * huber(td)), td

    def actor_loss_fn(p):
        p = p["params"]
        feats = torso(p["torso"], obs[:, unr])
        q = pi_q_over(p, pc, feats, fc_on[:, unr], reset[:, unr], ca_on, cc_on)
        return -jnp.mean(q), jnp.mean(jnp.abs(q))

    (critic_loss, td), g_critic = jax.value_and_grad(
        critic_loss_fn, has_aux=True)(state["critic"])
    (actor_loss, q_abs), g_actor = jax.value_and_grad(
        actor_loss_fn, has_aux=True)(state["actor"])

    critic, critic_opt, g_critic = adam_update(
        state["critic"], g_critic, state["critic_opt"], state["step"],
        hp["critic_lr"], hp["grad_clip"])
    actor, actor_opt, g_actor = adam_update(
        state["actor"], g_actor, state["actor_opt"], state["step"],
        hp["actor_lr"], hp["grad_clip"])
    tau = hp["tau"]

    def polyak(online, target):
        return jax.tree_util.tree_map(
            lambda o, t: tau * o + (1.0 - tau) * t, online, target)

    new_state = {
        "actor": actor,
        "critic": critic,
        "target_actor": polyak(actor, state["target_actor"]),
        "target_critic": polyak(critic, state["target_critic"]),
        "actor_opt": actor_opt,
        "critic_opt": critic_opt,
        "step": state["step"] + 1,
    }
    prios = sequence_priority(td, hp["eta"])
    losses = {
        "critic_loss": critic_loss,
        "actor_loss": actor_loss,
        "q_abs_mean": q_abs,
        "grads": {"actor": g_actor, "critic": g_critic},
    }
    return new_state, prios, losses


# ------------------------------------------------------------------ collect
def sigma_ladder(num_actors: int, sigma_max: float, alpha: float):
    """Per-actor exploration scales, geometric as in Ape-X:
    ``sigma_i = sigma_max ** (1 + alpha * i / (N - 1))``."""
    i = jnp.arange(num_actors, dtype=jnp.float32)
    return sigma_max ** (1.0 + alpha * i / max(num_actors - 1, 1))


def policy_step(actor, obs, reset, ca, noise):
    """One collect step for ``[E, ...]`` environments: the action the policy
    sends (Gaussian noise added, clipped to [-1, 1]) and its carry after."""
    a, ca = actor_core(actor["params"], torso(actor["params"]["torso"], obs),
                       ca, reset)
    return jnp.clip(a + noise, -1.0, 1.0), ca


def collect_steps(actor, critic, obs, reset, sent, ca, cc, keys, sigmas):
    """``T`` collect steps over recorded observations ``obs [T, E, ...]``.

    Returns the actions the reference would have sent ``[T, E, A]``, the
    carries before every step (what a sequence that starts there stores) and
    the carries after the last.  The critic's carry advances on ``sent``, the
    action the environment was in fact given, so that the reference's state
    stays on the recorded trajectory."""
    actions, before = [], []
    for t in range(obs.shape[0]):
        before.append({"actor": ca, "critic": cc})
        k_noise, _ = jax.random.split(keys[t])
        noise = sigmas[:, None] * jax.random.normal(k_noise, sent[t].shape)
        a, ca = policy_step(actor, obs[t], reset[t], ca, noise)
        actions.append(a)
        fc = torso(critic["params"]["torso"], obs[t])
        _, cc = critic_core(critic["params"], fc, sent[t], cc, reset[t])
    stack = lambda *xs: jnp.stack(xs)  # noqa: E731
    return (jnp.stack(actions), jax.tree_util.tree_map(stack, *before),
            {"actor": ca, "critic": cc})


# ------------------------------------------------------------------- replay
def scaled_mass(priority: np.ndarray, alpha: float) -> np.ndarray:
    """``p^alpha`` in float64; empty slots (priority 0) carry no mass."""
    p = np.asarray(priority, np.float64)
    return np.where(p > 0.0, p**alpha, 0.0)


def sample_indices(priority: np.ndarray, u01: np.ndarray, alpha: float):
    """Inverse-CDF draw in float64: ``u01 [B]`` in [0, 1) -> (indices, probs,
    cdf, total).  ``cdf[i]`` is the mass of slots ``0..i``."""
    mass = scaled_mass(priority, alpha)
    cdf = np.cumsum(mass)
    total = cdf[-1]
    idx = np.searchsorted(cdf, np.asarray(u01, np.float64) * total, side="right")
    idx = np.clip(idx, 0, len(cdf) - 1)
    return idx, mass[idx] / max(total, 1e-12), cdf, total


def is_weights(probs, size, step, beta0, beta_steps):
    beta = beta0 + (1.0 - beta0) * np.clip(step / max(beta_steps, 1), 0.0, 1.0)
    w = (max(float(size), 1.0) * np.maximum(probs, 1e-12)) ** (-beta)
    return (w / max(w.max(), 1e-12)).astype(np.float32)


def write_priorities(priority: np.ndarray, idx, values) -> np.ndarray:
    """Sequential last-write-wins write-back, floored at the epsilon."""
    out = np.array(priority, np.float32, copy=True)
    for i, v in zip(np.asarray(idx), np.asarray(values, np.float32)):
        out[int(i)] = max(float(v), PRIORITY_EPS)
    return out
