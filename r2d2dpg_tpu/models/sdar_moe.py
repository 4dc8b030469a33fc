"""SDAR-30B-A3B-Chat's decoder block as a sequence core of the actor-critic.

The block is the published ``sdar_moe`` layer (JetLM/SDAR-30B-A3B-Chat,
``config.json``; Qwen3-MoE lineage), a step's torso output standing where a
token's embedding would::

    h1 = RMSNorm(x);  q = h1 Wq,  k = h1 Wk,  v = h1 Wv            (no bias)
    q, k <- RMSNorm over each head's dims, learned scale
    q, k <- RoPE(theta, all head dims, position = the step's index)
    a = softmax_f32(q k^T / sqrt(d) + mask) v;   x' = x + a Wo
    h2 = RMSNorm(x');  p = softmax_f32(h2 Wr) over ALL experts
    S = top-k of p;  w_e = p_e / sum_S p
    y = x' + sum_{e in S, held here} w_e (silu(h2 Wg_e) * (h2 Wu_e)) Wd_e

and after the last block the model's final RMSNorm.  ``mask``: step t sees
step s iff s <= t and no ``reset`` lies in (s, t] — an episode never attends
across its boundary.  Left out, here and in the benchmark's reference: the
vocabulary (observations are continuous) and generation by diffusion over
blocks (a policy emits one action a step from the past only, which is
SDAR's block-causal mask at block length 1).

**The expert layer is told which experts it holds** (``expert_shard`` of
``expert_shards``: an expert-parallel deployment's share of a layer).  It
routes over all ``router_experts``, normalises over the chosen k wherever
they live, and computes its own experts' part; what the absent experts would
add is left out and that partial result goes on.

**The held experts' products are dense, not grouped** (``held_ffn``): every
held expert runs over every token, and a gate that is 0 for the tokens that
did not choose it weighs its output.  No token can be dropped at any
imbalance, and the products cost ``experts_held`` times the FLOPs of the
pairs routed here at an even load (16 times at the published sizes): the
sparse mechanism decides WHAT is computed, it does not yet save the time.
Grouped products over the pairs held here (``jax.lax.ragged_dot``, sorted
pairs, masked tails) were built and measured first (PERF.md section 6, PR
27): their time follows the load, an untrained router's load of the share
held here lies anywhere between 0 and several times its expectation, and at
those loads they were slower than the dense products on the chip; the TPU
lowering of ``ragged_dot`` also left the rows past its groups unwritten.

**The backward pass keeps the up product** (``moe``): of the three
``[E, N, W]`` values of ``held_ffn``, ``u = h2 Wu`` is saved by name (float32,
62.9 MB a layer and differentiated pass at the published sizes: N = 64 x 40
tokens; 755 MB an update over 4 layers and the 3 passes an update
differentiates), and the backward pass runs ``g = h2 Wg`` again and
``silu(g) * u * gates`` element-wise.  Under a plain ``jax.checkpoint`` it ran
both products again, in every layer of every differentiated pass.  Keeping g
as well does not pay: the learner call is at the compiler's memory limit, and
the compiler then clones products and attention's scores back to fit, which
on the chip cost more than the second product saves (PERF.md section 6).

**Three ways in**, all one set of weights:

- ``sequence``: ``[B, T, H]`` whole, no scan over time.  ``memory`` is the
  keys and values a prefix of the same sequence left in every layer (what
  ``sequence`` itself hands back): R2D2's burn-in in attention's terms, a
  Transformer-XL memory recomputed with today's weights.
- ``memory_only``: the prefix pass that only makes that memory; the last
  layer stops at its keys and values.
- ``step``: one step through the acting carry, a per-layer ring of the last
  ``ring`` steps' rotated keys and values with a step counter and validity,
  cleared by ``zeros_where_reset``.  From a cleared ring, step t equals
  position t of ``sequence``.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name

from r2d2dpg_tpu.utils.profiling import scope

Memory = Any  # {"k", "v": [B, L, M, KV, D], "seg": [B, M]} or () for none


@dataclasses.dataclass(frozen=True)
class SdarMoeConfig:
    """The block's sizes, named as this repo names them (the published keys
    are in ``chipbench/configs/humanoid_sdar_moe.json``)."""

    hidden: int = 2048
    layers: int = 4  # published 48
    heads: int = 32
    kv_heads: int = 4
    head_dim: int = 128
    rope_theta: float = 1e6
    rms_eps: float = 1e-6
    router_experts: int = 128  # the router's width: experts of a whole layer
    experts_per_token: int = 8
    expert_width: int = 768
    expert_shards: int = 16  # chips that share each layer
    expert_shard: int = 0  # which of them this is
    ring: int = 84  # steps the acting carry holds: seq_len - 1

    @property
    def experts_held(self) -> int:
        return self.router_experts // self.expert_shards

    def build(self, dtype) -> nn.Module:
        return SdarMoeCore(self, dtype=dtype, name="sdar")

    def acting_carry(self, batch_size: int) -> Dict[str, Any]:
        return cleared_ring(batch_size, self.layers, self.ring, self.kv_heads,
                            self.head_dim)

    @staticmethod
    def pass_metrics(passes: Dict[str, Any]) -> Dict[str, jnp.ndarray]:
        """What one learner update reports from what its passes left (by pass
        name): the routing counters."""
        return moe_metrics({name: left["load"] for name, left in passes.items()})


# ------------------------------------------------------------------ pieces
def rms_norm(x, scale, eps):
    x32 = x.astype(jnp.float32)
    y = x32 * lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (y * scale).astype(x.dtype)


def rope(x, pos, theta):
    """Rotate ``x [..., heads, D]`` to position ``pos`` (one entry per row of
    the axis before ``heads``), all ``D`` dims, half-split pairing."""
    d = x.shape[-1]
    inv_freq = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = pos.astype(jnp.float32)[..., None] * inv_freq
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[..., None, :]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[..., None, :]
    x32 = x.astype(jnp.float32)
    rot = jnp.concatenate([-x32[..., d // 2:], x32[..., : d // 2]], -1)
    return (x32 * cos + rot * sin).astype(x.dtype)


def attend(q, k, v, mask):
    """``q [B, T, Hq, D]`` over ``k, v [B, S, KV, D]`` where ``mask [B, T, S]``
    allows; each kv head serves ``Hq / KV`` query heads; softmax in float32."""
    B, T, Hq, D = q.shape
    KV = k.shape[2]
    q = q.reshape(B, T, KV, Hq // KV, D)
    s = jnp.einsum("btkgd,bskd->bkgts", q, k,
                   preferred_element_type=jnp.float32) * (D ** -0.5)
    s = jnp.where(mask[:, None, None], s, jnp.finfo(jnp.float32).min)
    p = jax.nn.softmax(s, axis=-1).astype(v.dtype)
    o = jnp.einsum("bkgts,bskd->btkgd", p, v)
    return o.reshape(B, T, Hq * D)


def sequence_mask(reset, memory=()):
    """Who sees whom over ``reset [B, T]`` after the steps a ``memory`` holds
    (its ``seg [B, M]``): step t sees step s iff s <= t and no reset lies in
    (s, t].  Returns the episode index of every step ``seg [B, T]``, counted
    on from the memory's, and ``mask [B, T, M + T]``."""
    T = reset.shape[1]
    seg = jnp.cumsum(reset.astype(jnp.int32), axis=1)
    mask = (seg[:, :, None] == seg[:, None, :]) & jnp.tril(jnp.ones((T, T), bool))
    if memory:
        seg = seg + memory["seg"][:, -1:]
        mask = jnp.concatenate(
            [seg[:, :, None] == memory["seg"][:, None, :], mask], axis=-1)
    return seg, mask


def cleared_ring(batch_size: int, stacks: int, size: int, kv_heads: int,
                 head_dim: int) -> Dict[str, Any]:
    """A cleared acting carry, a ring of the last ``size`` steps' rotated keys
    and values for each of ``stacks`` attention layers: all zeros, which is
    also what ``zeros_where_reset`` leaves of it."""
    kv = (batch_size, stacks, size, kv_heads, head_dim)
    return {
        "k": jnp.zeros(kv, jnp.float32),
        "v": jnp.zeros(kv, jnp.float32),
        "valid": jnp.zeros((batch_size, size), jnp.float32),
        "count": jnp.zeros((batch_size,), jnp.int32),
    }


def ring_slot(ring, size: int):
    """Where a step lands in an acting ring of ``size`` steps (``slot [B,
    size]``, one-hot) and what it sees: the valid steps and itself, ``mask
    [B, 1, size + 1]``."""
    slot = (ring["count"] % size)[:, None] == jnp.arange(size)
    mask = jnp.concatenate(
        [ring["valid"] > 0, jnp.ones_like(slot[:, :1])], axis=1)[:, None]
    return slot, mask


def router_probs(h2, w_router):
    """Softmax over all experts in float32 from float32 operands at precision
    ``highest`` (2048 x 128: nothing in time), so that a top-k set does not
    flip on rounding."""
    logits = jnp.matmul(h2.astype(jnp.float32), w_router.astype(jnp.float32),
                        precision=lax.Precision.HIGHEST)
    return jax.nn.softmax(logits, axis=-1)


def held_ffn(h2, w_gate, w_up, w_down, gates):
    """The held experts' gated feed-forward over EVERY token ``h2 [N, H]``,
    each expert's output weighed by ``gates [N, E]`` (0 where the token did
    not choose it) and summed: dense products, ``E`` times the FLOPs of the
    pairs routed here at an even load (the module's note says why).  The up
    product carries the name ``HELD_KEPT`` saves."""
    g = jnp.einsum("nh,ehw->enw", h2, w_gate.astype(h2.dtype))
    u = checkpoint_name(jnp.einsum("nh,ehw->enw", h2, w_up.astype(h2.dtype)),
                        "held_up")
    a = jax.nn.silu(g) * u * gates.T.astype(h2.dtype)[:, :, None]
    return jnp.einsum("enw,ewh->nh", a, w_down.astype(h2.dtype))


# What the backward pass of ``held_ffn`` keeps of its forward pass: the up
# product, by the name ``held_ffn`` gives it.
HELD_KEPT = jax.checkpoint_policies.save_only_these_names("held_up")


def moe(cfg: SdarMoeConfig, p: Dict[str, Any], h2) -> Tuple[Any, Any]:
    """The held experts' part of the layer for tokens ``h2 [N, H]``; returns
    it with the tokens each held expert received ``[E]``."""
    k, E = cfg.experts_per_token, cfg.experts_held
    with scope("moe_route"):
        top_p, top_e = lax.top_k(router_probs(h2, p["router"]), k)
        gate = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
        held = cfg.expert_shard * E + jnp.arange(E)
        mine = top_e[:, :, None] == held  # [N, k, E]: the pairs that live here
        gates = jnp.sum(jnp.where(mine, gate[:, :, None], 0.0), axis=1)
        sizes = jnp.sum(jnp.any(mine, axis=1), axis=0, dtype=jnp.int32)
    with scope("moe_experts"):
        # The backward pass reads u as the forward pass wrote it, one
        # [E, N, W] float32 value a layer and differentiated pass (755 MB an
        # update at the published sizes), and runs g = h2 Wg again with
        # a = silu(g) u gates.  ``held_ffn`` is looked up when ``moe`` is
        # traced.
        out = jax.checkpoint(held_ffn, policy=HELD_KEPT)(
            h2, p["w_gate"], p["w_up"], p["w_down"], gates
        )
    return out, sizes


def fan_in_normal(key, shape, dtype=jnp.float32):
    return jax.random.normal(key, shape, dtype) * shape[-2] ** -0.5


# -------------------------------------------------------------------- core
class SdarMoeCore(nn.Module):
    """``cfg.layers`` blocks and the final norm."""

    cfg: SdarMoeConfig
    dtype: Any = jnp.float32

    def setup(self):
        c = self.cfg
        H, D, E, W = c.hidden, c.head_dim, c.experts_held, c.expert_width
        ones, kernel = nn.initializers.ones_init(), fan_in_normal
        shapes = {
            "norm1": (ones, (H,)), "norm2": (ones, (H,)),
            "q_norm": (ones, (D,)), "k_norm": (ones, (D,)),
            "wq": (kernel, (H, c.heads * D)), "wk": (kernel, (H, c.kv_heads * D)),
            "wv": (kernel, (H, c.kv_heads * D)), "wo": (kernel, (c.heads * D, H)),
            "router": (kernel, (H, c.router_experts)),
            "w_gate": (kernel, (E, H, W)), "w_up": (kernel, (E, H, W)),
            "w_down": (kernel, (E, W, H)),
        }
        self.blocks = [
            {name: self.param(f"block_{i}_{name}", init, shape)
             for name, (init, shape) in shapes.items()}
            for i in range(c.layers)
        ]
        self.final_norm = self.param("final_norm", ones, (H,))

    def _qkv(self, p, x, pos):
        """Rotated queries and keys, and values, of ``x [B, T, H]`` at
        positions ``pos`` (``[T]``, or ``[B, 1]`` a row)."""
        c, dt = self.cfg, self.dtype
        h1 = rms_norm(x, p["norm1"], c.rms_eps)
        heads = lambda y, n: y.reshape(y.shape[:2] + (n, c.head_dim))  # noqa: E731
        q = heads(jnp.matmul(h1, p["wq"].astype(dt)), c.heads)
        k = heads(jnp.matmul(h1, p["wk"].astype(dt)), c.kv_heads)
        v = heads(jnp.matmul(h1, p["wv"].astype(dt)), c.kv_heads)
        q = rope(rms_norm(q, p["q_norm"], c.rms_eps), pos, c.rope_theta)
        k = rope(rms_norm(k, p["k_norm"], c.rms_eps), pos, c.rope_theta)
        return q, k, v

    def _rest(self, p, x, q, k, v, mask):
        """The block after its keys and values: attention, then the experts."""
        c = self.cfg
        B, T, H = x.shape
        with scope("core_attention"):
            x = x + jnp.matmul(attend(q, k, v, mask), p["wo"].astype(self.dtype))
        h2 = rms_norm(x, p["norm2"], c.rms_eps)
        y, load = moe(c, p, h2.reshape(B * T, H))
        return x + y.reshape(B, T, H), load

    def sequence(self, x, reset, memory: Memory = (), memory_only: bool = False):
        """``x [B, T, H]``, ``reset [B, T]`` -> (``y [B, T, H]``, aux) with
        aux = this call's own memory (``k``, ``v``, ``seg``) and ``load``,
        the tokens each held expert received ``[L, E]``."""
        c = self.cfg
        x = x.astype(self.dtype)
        M = memory["seg"].shape[1] if memory else 0
        seg, mask = sequence_mask(reset, memory)
        pos = M + jnp.arange(x.shape[1])
        ks, vs, loads = [], [], []
        for i, p in enumerate(self.blocks):
            with scope("core_attention"):
                q, k, v = self._qkv(p, x, pos)
                ks.append(k)
                vs.append(v)
                if M:
                    k = jnp.concatenate([memory["k"][:, i].astype(k.dtype), k], 1)
                    v = jnp.concatenate([memory["v"][:, i].astype(v.dtype), v], 1)
            if memory_only and i == c.layers - 1:
                loads.append(jnp.zeros((c.experts_held,), jnp.int32))
                break  # the memory is complete: nothing reads this layer's output
            x, load = self._rest(p, x, q, k, v, mask)
            loads.append(load)
        y = rms_norm(x, self.final_norm, c.rms_eps)
        aux = {"k": jnp.stack(ks, 1), "v": jnp.stack(vs, 1), "seg": seg,
               "load": jnp.stack(loads)}
        return y, aux

    def step(self, x, ring):
        """One step ``x [B, H]`` through the acting carry ``ring`` (already
        cleared where the step begins an episode).  A net that acts with no
        carry (the critic) sees the step alone."""
        c = self.cfg
        if not ring:
            y, _ = self.sequence(x[:, None], jnp.zeros(x.shape[:1] + (1,)))
            return y[:, 0], ring
        x = x.astype(self.dtype)[:, None]
        pos = ring["count"][:, None]
        slot, mask = ring_slot(ring, c.ring)
        ks, vs = [], []
        for i, p in enumerate(self.blocks):
            with scope("core_attention"):
                q, k, v = self._qkv(p, x, pos)
                old_k, old_v = ring["k"][:, i], ring["v"][:, i]
                put = slot[:, :, None, None]
                ks.append(jnp.where(put, k.astype(old_k.dtype), old_k))
                vs.append(jnp.where(put, v.astype(old_v.dtype), old_v))
                k = jnp.concatenate([old_k.astype(k.dtype), k], axis=1)
                v = jnp.concatenate([old_v.astype(v.dtype), v], axis=1)
            x, _ = self._rest(p, x, q, k, v, mask)
        ring = {
            "k": jnp.stack(ks, 1), "v": jnp.stack(vs, 1),
            "valid": jnp.where(slot, 1.0, ring["valid"]),
            "count": ring["count"] + 1,
        }
        return rms_norm(x[:, 0], self.final_norm, c.rms_eps), ring

    def __call__(self, x, carry, reset, *, sequence: bool = False,
                 memory_only: bool = False):
        if sequence:
            return self.sequence(x, reset, carry, memory_only)
        return self.step(x, carry)


def moe_metrics(loads: Dict[str, Any]) -> Dict[str, jnp.ndarray]:
    """The routing counters of one learner update from each pass's ``load``
    (``[L, E]``, by pass name): every pass's table stacked in the order the
    learner hands them (``models/sequence.py::PASSES``; counts: integers,
    which a caller that averages metrics over updates leaves one an update),
    the pairs routed here, and the fullest held expert over the mean one,
    averaged over the layers that ran experts."""
    counts = jnp.stack(list(loads.values()))
    table = counts.astype(jnp.float32)  # [P, L, E]
    total = table.sum(axis=-1)
    ran = total > 0
    ratio = table.max(axis=-1) / jnp.maximum(table.mean(axis=-1), 1e-9)
    return {
        "moe/tokens_per_expert": counts.astype(jnp.int32),
        "moe/pairs_here": total.sum(),
        "moe/load_max_over_mean": jnp.where(ran, ratio, 0.0).sum()
        / jnp.maximum(ran.sum(), 1),
    }
