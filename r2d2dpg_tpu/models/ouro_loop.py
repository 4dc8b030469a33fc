"""Ouro-2.6B's looped decoder stack as a sequence core of the actor-critic.

The stack is the published ``ouro`` model's (ByteDance/Ouro-2.6B,
``config.json``; "Scaling Latent Reasoning via Looped Language Models"), a
step's torso output standing where a token's embedding would.  One block,
``x [B, T, H]``::

    q, k, v = RMSNorm_1(x) {Wq, Wk, Wv}                       (no bias, no q/k norm)
    a  = attend(rope(q), rope(k), v, mask) Wo       (RoPE over all head dims,
                                                     position = the step's index)
    x' = x + RMSNorm_2(a)
    m  = (silu(RMSNorm_3(x') Wg) * (RMSNorm_3(x') Wu)) Wd
    y  = x' + RMSNorm_4(m)

``stack`` is ``layers`` such blocks one after the other, and the model is the
stack applied ``loop_steps`` times to its own output **with one set of
weights**, the final norm after every pass::

    h^(r) = RMSNorm_f(stack(h^(r-1))),  r = 1..loop_steps;  output h^(loop_steps)

``mask`` is ``models/sdar_moe.py``'s: step t sees step s iff s <= t and no
``reset`` lies in (s, t].  Keys and values are made anew in every
(loop step, layer) pair from that pass's input, at the same positions.  Left
out, here and in the benchmark's reference: the vocabulary and the exit gate
(a head a loop step that decides where a language model emits its token;
``early_exit_threshold`` 1 as published runs every step).

**The stack is rolled and rematerialised.**  The blocks' weights are stacked
``[layers, ...]``; ``lax.scan`` over the layers runs inside ``lax.scan`` over
the loop steps (``loop``), so the compiled program holds ONE copy of a block
however many times it is applied, and the block is under ``jax.checkpoint``:
the backward pass keeps each application's input and makes the rest again
(an application keeps about 0.3 GB at 2,880 rows of the published widths, 16
applications of three differentiated passes do not fit a chip).  The shared
weights' gradient is the sum over the loop steps' uses, which the transposed
scan gives.

**Three ways in**, as the sdar core's: ``sequence`` (whole ``[B, T, H]``,
optionally after the memory a prefix left), ``memory_only`` (the prefix pass:
the rolled stack runs its last application whole and its output is dropped),
``step`` (one step through the acting ring).  The memory and the ring hold
keys and values of every (loop step, layer) pair, ``[B, loop_steps * layers,
M, heads, D]``, loop step major.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax

from r2d2dpg_tpu.models.sdar_moe import (
    fan_in_normal,
    attend,
    cleared_ring,
    ring_slot,
    rms_norm,
    rope,
    sequence_mask,
)
from r2d2dpg_tpu.utils.profiling import scope


@dataclasses.dataclass(frozen=True)
class OuroLoopConfig:
    """The stack's sizes, named as this repo names them (the published keys
    are in ``chipbench/configs/humanoid_ouro_loop.json``)."""

    hidden: int = 2048
    layers: int = 4  # published 48
    heads: int = 16  # key-value heads too: no grouped queries
    head_dim: int = 128
    mlp_width: int = 5632
    rope_theta: float = 1e6
    rms_eps: float = 1e-6
    loop_steps: int = 4  # total_ut_steps
    ring: int = 84  # steps the acting carry holds: seq_len - 1

    def build(self, dtype) -> nn.Module:
        return OuroLoopCore(self, dtype=dtype, name="ouro")

    def acting_carry(self, batch_size: int) -> Dict[str, Any]:
        """A ring for every (loop step, layer) pair."""
        return cleared_ring(batch_size, self.loop_steps * self.layers, self.ring,
                            self.heads, self.head_dim)

    @staticmethod
    def pass_metrics(passes: Dict[str, Any]) -> Dict[str, jnp.ndarray]:
        """What one learner update reports from what its passes left (by pass
        name, ``models/sequence.py::PASSES``; a burn-in pass leaves its memory
        whole, a window pass what is not memory): how far the last run of the
        stack still moved the state, ``|h^(R) - h^(R-1)| / |h^(R-1)|``
        (Frobenius), mean over the window passes: what an exit gate would act
        on; and the bytes of keys and values the burn-in passes left."""
        window = [p["last_step_rel_change"] for p in passes.values() if "k" not in p]
        held = sum(p[n].size * p[n].dtype.itemsize
                   for p in passes.values() if "k" in p for n in ("k", "v"))
        return {
            "loop/last_step_rel_change": jnp.mean(jnp.stack(window)),
            "loop/memory_bytes": jnp.asarray(held, jnp.float32),
        }


# ------------------------------------------------------------------ pieces
def attention(cfg: OuroLoopConfig, p, x, pos, mask, past, dtype):
    """``x + RMSNorm_2(attend(...) Wo)`` of one block, with the keys and
    values ``x`` itself made; ``past``: ``(k, v)`` of the steps before, which
    ``mask``'s leading columns cover, or ``()``."""
    with scope("core_attention"):
        h = rms_norm(x, p["norm1"], cfg.rms_eps)
        heads = lambda y: y.reshape(y.shape[:2] + (cfg.heads, cfg.head_dim))  # noqa: E731
        q = rope(heads(jnp.matmul(h, p["wq"].astype(dtype))), pos, cfg.rope_theta)
        k = rope(heads(jnp.matmul(h, p["wk"].astype(dtype))), pos, cfg.rope_theta)
        v = heads(jnp.matmul(h, p["wv"].astype(dtype)))
        ks, vs = k, v
        if past:
            ks = jnp.concatenate([past[0].astype(k.dtype), k], axis=1)
            vs = jnp.concatenate([past[1].astype(v.dtype), v], axis=1)
        a = jnp.matmul(attend(q, ks, vs, mask), p["wo"].astype(dtype))
        return x + rms_norm(a, p["norm2"], cfg.rms_eps), k, v


def mlp(cfg: OuroLoopConfig, p, x, dtype):
    """``x + RMSNorm_4(SwiGLU(RMSNorm_3(x)))``."""
    with scope("core_mlp"):
        h = rms_norm(x, p["norm3"], cfg.rms_eps)
        gate = jnp.matmul(h, p["w_gate"].astype(dtype))
        up = jnp.matmul(h, p["w_up"].astype(dtype))
        m = jnp.matmul(jax.nn.silu(gate) * up, p["w_down"].astype(dtype))
        return x + rms_norm(m, p["norm4"], cfg.rms_eps)


def block(cfg: OuroLoopConfig, p, x, pos, mask, past, dtype):
    """One block application -> (``y``, its own keys, its own values)."""
    x, k, v = attention(cfg, p, x, pos, mask, past, dtype)
    return mlp(cfg, p, x, dtype), k, v


def loop(stack, weights, x, past, steps: int):
    """``h^(r) = stack(weights, h^(r-1), past[r])`` for ``r < steps``, ONE
    ``weights`` every step; ``past`` has the loop steps leading.  Returns
    ``h^(steps)``, what every step left (stacked) and the squared norms
    ``|h^(steps) - h^(steps-1)|^2`` and ``|h^(steps-1)|^2``."""

    def one(x, past_r):
        y, left = stack(weights, x, past_r)
        y32, x32 = y.astype(jnp.float32), x.astype(jnp.float32)
        moved = jnp.stack([jnp.sum(jnp.square(y32 - x32)), jnp.sum(jnp.square(x32))])
        return y, (left, lax.stop_gradient(moved))

    x, (left, moved) = lax.scan(one, x, past, length=steps)
    return x, left, moved[-1]


# -------------------------------------------------------------------- core
class OuroLoopCore(nn.Module):
    """``cfg.layers`` blocks, stacked, and the final norm; applied
    ``cfg.loop_steps`` times."""

    cfg: OuroLoopConfig
    dtype: Any = jnp.float32

    def setup(self):
        c = self.cfg
        L, H, A, W = c.layers, c.hidden, c.heads * c.head_dim, c.mlp_width
        ones, kernel = nn.initializers.ones_init(), fan_in_normal
        shapes = {
            "norm1": (ones, (L, H)), "norm2": (ones, (L, H)),
            "norm3": (ones, (L, H)), "norm4": (ones, (L, H)),
            "wq": (kernel, (L, H, A)), "wk": (kernel, (L, H, A)),
            "wv": (kernel, (L, H, A)), "wo": (kernel, (L, A, H)),
            "w_gate": (kernel, (L, H, W)), "w_up": (kernel, (L, H, W)),
            "w_down": (kernel, (L, W, H)),
        }
        self.blocks = {name: self.param(name, init, shape)
                       for name, (init, shape) in shapes.items()}
        self.final_norm = self.param("final_norm", ones, (H,))

    def _run(self, x, pos, mask, past):
        """The looped stack over ``x [B, T, H]``.  ``past``: keys and values
        of the steps before, ``(k, v)`` each ``[B, R * L, M, heads, D]``, or
        ``()``.  Returns ``h^(R)``, this call's own ``k`` and ``v`` in the
        same layout, and ``loop``'s two squared norms."""
        c, dt = self.cfg, self.dtype
        R, L = c.loop_steps, c.layers

        def split(m):  # [B, R * L, ...] -> [R, L, B, ...]
            return jnp.moveaxis(m.reshape(m.shape[:1] + (R, L) + m.shape[2:]), 0, 2)

        def merge(m):  # [R, L, B, ...] -> [B, R * L, ...]
            m = jnp.moveaxis(m, 2, 0)
            return m.reshape(m.shape[:1] + (R * L,) + m.shape[3:])

        def one_block(x, per_layer):
            p, past_l = per_layer
            y, k, v = jax.checkpoint(
                lambda p, x, past_l: block(c, p, x, pos, mask, past_l, dt)
            )(p, x, past_l)
            return y, (k, v)

        def stack(weights, x, past_r):
            x, kv = lax.scan(one_block, x, (weights["blocks"], past_r))
            return rms_norm(x, weights["final_norm"], c.rms_eps), kv

        weights = {"blocks": self.blocks, "final_norm": self.final_norm}
        past = tuple(split(m) for m in past)
        y, (k, v), moved = loop(stack, weights, x.astype(dt), past, R)
        return y, merge(k), merge(v), moved

    def sequence(self, x, reset, memory=()):
        """``x [B, T, H]``, ``reset [B, T]`` -> (``y [B, T, H]``, aux) with
        aux = this call's own memory (``k``, ``v``, ``seg``) and
        ``last_step_rel_change``."""
        M = memory["seg"].shape[1] if memory else 0
        seg, mask = sequence_mask(reset, memory)
        past = (memory["k"], memory["v"]) if M else ()
        y, k, v, moved = self._run(x, M + jnp.arange(x.shape[1]), mask, past)
        rel = jnp.sqrt(moved[0] / jnp.maximum(moved[1], 1e-30))
        return y, {"k": k, "v": v, "seg": seg, "last_step_rel_change": rel}

    def step(self, x, ring):
        """One step ``x [B, H]`` through the acting carry ``ring`` (already
        cleared where the step begins an episode).  A net that acts with no
        carry (the critic) sees the step alone."""
        if not ring:
            y, _ = self.sequence(x[:, None], jnp.zeros(x.shape[:1] + (1,)))
            return y[:, 0], ring
        slot, mask = ring_slot(ring, self.cfg.ring)
        y, k, v, _ = self._run(
            x[:, None], ring["count"][:, None], mask, (ring["k"], ring["v"]))
        put = slot[:, None, :, None, None]
        ring = {
            "k": jnp.where(put, k.astype(ring["k"].dtype), ring["k"]),
            "v": jnp.where(put, v.astype(ring["v"].dtype), ring["v"]),
            "valid": jnp.where(slot, 1.0, ring["valid"]),
            "count": ring["count"] + 1,
        }
        return y[:, 0], ring

    def __call__(self, x, carry, reset, *, sequence: bool = False,
                 memory_only: bool = False):
        """The nets' one way in.  ``memory_only`` (a prefix pass, whose output
        the caller drops) changes nothing here: a rolled stack has no cheaper
        last application."""
        if sequence:
            return self.sequence(x, reset, carry)
        return self.step(x, carry)
