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

TPU notes: the single-step call (``__call__``) is what the actor phase vmaps
over envs.  It is ``readout(step(encode(obs), carry, reset))``: ``encode`` is
what does not depend on the carry (torso; the LSTM's input projection; the
critic's ``mix`` where the action is the replay's) and takes any leading
dimensions, so the learner runs it once over a whole ``[T, B, ...]`` input,
scans ``step`` alone with ``lax.scan`` over time (SURVEY §2.9 — burn-in+unroll
as jitted scans instead of cuDNN LSTM calls) and reads the stacked outputs out
once (``models/sequence.py::Stepped``).  The hoisted matmuls and convolutions
see T·B rows, the step's [B, hidden] x [hidden, 4*hidden];
``dtype=bfloat16`` is supported throughout with float32 params.
"""

from __future__ import annotations

from typing import Any, Callable, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax

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

    features: int
    use_bias: bool
    kernel_init: Any

    @nn.compact
    def __call__(self, in_features: int):
        kernel = self.param("kernel", self.kernel_init, (in_features, self.features))
        bias = (
            self.param("bias", nn.initializers.zeros_init(), (self.features,))
            if self.use_bias
            else None
        )
        return kernel, bias


class MixedPrecisionLSTMCell(nn.Module):
    """The LSTM cell of every net: gate matmuls in ``dtype``, FLOAT32 state
    arithmetic, and its step split where the carry comes in.

    ``project`` is the input half of the gates, ``x·[W_ii|W_if|W_ig|W_io]``:
    it depends on nothing the recurrence carries and takes any leading
    dimensions, so the learner computes it ONCE over a whole ``[T, B, ...]``
    input before its scan (``models/sequence.py::Stepped``).  ``step`` is what
    needs the carry: ``h·W_h + b``, the gates, the state update.  ``__call__``
    is ``step(carry, project(x))``, the single step that acting uses.  flax's
    stock ``OptimizedLSTMCell`` offers no such split, so float32 runs through
    this cell too (PR 30): the same products at the same (JAX default)
    precision, summed in flax's order, ``(zh + b) + zx`` (until PR 30 this
    cell summed ``zx + zh + b``: float32 rounding of one add apart).

    Semantics AND param tree mirror flax's OptimizedLSTMCell exactly —
    gate order (i, f, g, o), zero-init recurrent biases with NO extra
    forget offset, lecun input kernels ``ii/if/ig/io`` (no bias), per-gate
    orthogonal recurrent kernels ``hi/hf/hg/ho`` (with bias) — declared as
    per-gate ``_GateParams`` leaves and fused into one [in, 4H] / [H, 4H]
    matmul pair at apply time (loop-invariant: XLA hoists the concat out
    of the unroll scan).  ``_Core`` pins the cell to the name the stock cell
    got by auto-naming (``OptimizedLSTMCell_0``).  A bf16-vs-fp32 comparison
    therefore measures precision alone, and a checkpoint written under
    either dtype, or by the stock cell, restores under the other.

    Why the state stays float32 under ``dtype=bfloat16`` (round-3 dtype A/B):
    with flax's cell at ``dtype=bfloat16`` the carry itself is returned in
    bf16, so the cell state ``c`` accumulates rounding across every unroll
    step — walker learning fell ~3x behind fp32 while short-horizon pendulum
    masked it.  Here the two gate projections (the MXU work, >95% of the
    FLOPs) run in ``dtype`` while the state update ``c' = f*c + i*g`` and the
    carry stay float32.

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

    def setup(self):
        lecun = nn.initializers.lecun_normal()
        orth = nn.initializers.orthogonal()
        for g in "ifgo":
            setattr(self, f"i{g}", _GateParams(self.hidden, False, lecun))
            setattr(self, f"h{g}", _GateParams(self.hidden, True, orth))

    def _dot(self, x: jnp.ndarray, kernels) -> jnp.ndarray:
        # Operands stream in ``dtype`` (the HBM/MXU win) but the dot
        # ACCUMULATES in fp32 via preferred_element_type — free on TPU,
        # whose MXU natively accumulates bf16 products into fp32; without
        # it XLA truncates the accumulator to bf16 at every step of the
        # recurrence, which the round-5 A/B implicates as the remaining
        # compounding-error path.
        return jnp.matmul(
            x.astype(self.dtype),
            jnp.concatenate(kernels, axis=1).astype(self.dtype),
            preferred_element_type=jnp.float32,
        )

    def project(self, x: jnp.ndarray) -> jnp.ndarray:
        """``x [..., in] -> zx [..., 4H]`` float32: the gates' input half."""
        return self._dot(
            x, [getattr(self, f"i{g}")(x.shape[-1])[0] for g in "ifgo"]
        )

    def step(self, carry: Carry, zx: jnp.ndarray):
        """``(c, h), zx [B, 4H] -> ((c, h), y [B, H])``: the recurrence."""
        c, h = carry  # float32 by contract (lstm_initial_carry)
        wh, bh = zip(*(getattr(self, f"h{g}")(self.hidden) for g in "ifgo"))
        # Gate math + state update in fp32 (bias join included), summed as
        # flax's cell sums them: ``(zh + b) + zx``, the second join gate by
        # gate on slices.  Joined whole (``zx + zh + b``) XLA's CPU backend
        # folds ``zx +`` into the matmul of a one-row batch as its
        # accumulator's start, and a served session's actions then depend on
        # the bucket it was batched into (tests/test_serving.py); joined on
        # slices throughout, the chip loses the bias as the matmul's epilogue
        # (walker 1,313 / 1,350 / 1,369 steps/s: slices, this, whole; PERF.md
        # PR 30).
        zh = self._dot(h, wh) + jnp.concatenate(bh, axis=0)
        i, f, g, o = (
            h_ + x_
            for x_, h_ in zip(jnp.split(zx, 4, axis=-1), jnp.split(zh, 4, axis=-1))
        )
        c = nn.sigmoid(f) * c + nn.sigmoid(i) * jnp.tanh(g)
        h = nn.sigmoid(o) * jnp.tanh(c)
        return (c, h), h.astype(self.dtype)

    def __call__(self, carry: Carry, x: jnp.ndarray):
        return self.step(carry, self.project(x))


class _Core(nn.Module):
    """A stepped core: an LSTM cell when ``use_lstm``, else Dense.  The
    learner scans it over time (``models/sequence.py::Stepped``).

    A step is ``step(project(x), carry, reset)``.  ``project`` is what of it
    does not depend on the carry and takes any leading dimensions (the LSTM's
    input projection; all of the Dense core, which carries nothing), so that
    the learner can run it once over a whole sequence and scan ``step`` alone.
    """

    hidden: int
    use_lstm: bool
    dtype: Any = jnp.float32

    whole_sequence = False

    def setup(self):
        # The names are the tree paths of every checkpoint and of
        # chipbench/reference.py::init_state: ``OptimizedLSTMCell_0`` and
        # ``Dense_0`` are what auto-naming called flax's stock modules here.
        if self.use_lstm:
            self.cell = MixedPrecisionLSTMCell(
                self.hidden, dtype=self.dtype, name="OptimizedLSTMCell_0"
            )
        else:
            self.dense = nn.Dense(
                self.hidden, kernel_init=fan_in_uniform(), dtype=self.dtype,
                name="Dense_0",
            )

    def acting_carry(self, batch_size: int, reads_past: bool) -> Carry:
        """The carry a net ACTS with."""
        return lstm_initial_carry(batch_size, self.hidden, self.use_lstm)

    def stored_carry(self, carry: Carry) -> Carry:
        """What of an acting carry a sequence is stored with: all of it."""
        return carry

    def project(self, x: jnp.ndarray) -> jnp.ndarray:
        if self.use_lstm:
            return self.cell.project(x)
        return nn.relu(self.dense(x))

    def step(self, z: jnp.ndarray, carry: Carry, reset: jnp.ndarray):
        if self.use_lstm:
            carry, y = self.cell.step(zeros_where_reset(carry, reset), z)
            return y, carry
        return z, carry

    def __call__(self, x: jnp.ndarray, carry: Carry, reset: jnp.ndarray):
        return self.step(self.project(x), carry, reset)


class _WholeCore(nn.Module):
    """A whole-sequence core: the learner gives it ``x [B, T, H]`` whole
    (``sequence=True``: ``carry`` is then the memory a prefix left, ``()`` for
    none, and what comes back beside ``y`` is that call's own memory and what
    the pass leaves to report; ``models/sequence.py::Whole``); it is stepped
    only when acting, through a carry of its own.

    Which core is the business of ``config`` (``models/sdar_moe.py``,
    ``models/ouro_loop.py``), which answers for it: ``build(dtype)`` is the
    module, ``acting_carry(batch_size)`` a cleared acting carry, and
    ``pass_metrics(passes)`` what a learner update reports from what its
    passes left.  Nothing of a step is independent of the carry.
    """

    config: Any
    dtype: Any = jnp.float32

    whole_sequence = True

    def setup(self):
        self.block = self.config.build(self.dtype)

    def acting_carry(self, batch_size: int, reads_past: bool) -> Carry:
        """The carry a net ACTS with: none for a net whose past nothing reads
        while acting (the critic: the replay stores no carry for this core)."""
        return self.config.acting_carry(batch_size) if reads_past else ()

    def stored_carry(self, carry: Carry) -> Carry:
        """The memory is recomputed from the burn-in prefix: nothing."""
        return ()

    def pass_metrics(self, passes) -> Any:
        return self.config.pass_metrics(passes)

    def project(self, x: jnp.ndarray) -> jnp.ndarray:
        return x

    def step(self, z: jnp.ndarray, carry: Carry, reset: jnp.ndarray, **seq):
        if not seq.get("sequence"):
            carry = zeros_where_reset(carry, reset)
        return self.block(z, carry, reset, **seq)

    def __call__(self, x: jnp.ndarray, carry: Carry, reset: jnp.ndarray, **seq):
        return self.step(x, carry, reset, **seq)


def _core_of(net: nn.Module, **kwargs) -> nn.Module:
    """``net``'s core from its fields: the one place that asks which kind it
    is.  With ``parent=None`` it is a description to ask outside ``apply``
    (``net.core`` exists only inside)."""
    if net.sequence_core is not None:
        return _WholeCore(net.sequence_core, net.dtype, **kwargs)
    return _Core(net.hidden, net.use_lstm, net.dtype, **kwargs)


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
    # A whole-sequence core's sizes (``_WholeCore``); then ``hidden`` is its
    # width and ``use_lstm`` is not read.
    sequence_core: Optional[Any] = None

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
        y, carry = self.step(self.encode(obs), carry, reset)
        return self.readout(y), carry

    def prepare(self, obs: jnp.ndarray):
        """What the torso wants done ONCE to the observations of a whole
        sampled batch ``[B, L, *obs_shape]`` before any pass cuts a window
        (``models/torsos.py``); it reads no parameter.  ``encode`` takes the
        windows of what comes back."""
        return self.torso.prepare(obs)

    def encode(self, obs: jnp.ndarray) -> jnp.ndarray:
        """What of a step does not depend on the carry: the torso and the
        core's input projection.  ``obs [..., *obs_shape]``, any leading
        dimensions (or a window of what ``prepare`` made): the learner runs
        it once over ``[T, B]``."""
        return self.core.project(self.torso(obs))

    def step(self, z: jnp.ndarray, carry: Carry, reset: jnp.ndarray):
        """What needs the carry: ``encode``'s rows of one step ``[B, ...]`` ->
        (the core's output ``y [B, H]``, new carry)."""
        return self.core.step(z, carry, reset)

    def readout(self, y: jnp.ndarray) -> jnp.ndarray:
        """``y [..., H]`` -> action ``[..., A]``."""
        return jnp.tanh(self.head(y)).astype(jnp.float32) * self.action_scale

    def sequence(self, obs, reset, memory=(), memory_only: bool = False):
        """A whole-sequence core over obs ``[B, T, ...]``, reset ``[B, T]`` ->
        (actions ``[B, T, A]``, the call's memory and what the pass leaves)."""
        y, aux = self.core(
            self.torso(obs), memory, reset, sequence=True, memory_only=memory_only
        )
        return self.readout(y), aux

    @property
    def whole_sequence(self) -> bool:
        return _core_of(self, parent=None).whole_sequence

    def initial_carry(self, batch_size: int) -> Carry:
        """The carry the net ACTS with."""
        return _core_of(self, parent=None).acting_carry(batch_size, reads_past=True)

    def stored_carry(self, carry: Carry) -> Carry:
        """What of an acting carry a sequence is stored with."""
        return _core_of(self, parent=None).stored_carry(carry)

    def pass_metrics(self, passes) -> Any:
        """What a learner update reports from what its passes through a
        whole-sequence core left (both nets' passes: the cores are one kind)."""
        return _core_of(self, parent=None).pass_metrics(passes)


class CriticNet(nn.Module):
    """Q(obs, action) with optional LSTM core; action concatenated after layer 1."""

    hidden: int = 256
    use_lstm: bool = True
    pixels: bool = False
    dtype: Any = jnp.float32
    # A whole-sequence core's sizes (``_WholeCore``); then ``hidden`` is its
    # width and ``use_lstm`` is not read.
    sequence_core: Optional[Any] = None

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
        y, carry = self.step(self.encode(obs, action), carry, reset)
        return self.readout(y), carry

    def _mixed(self, x: jnp.ndarray, action: jnp.ndarray) -> jnp.ndarray:
        return nn.relu(self.mix(jnp.concatenate([x, action.astype(x.dtype)], axis=-1)))

    def prepare(self, obs: jnp.ndarray):
        """``ActorNet.prepare``: the torsos are one kind."""
        return self.torso.prepare(obs)

    def encode(
        self, obs: jnp.ndarray, action: Optional[jnp.ndarray] = None
    ) -> jnp.ndarray:
        """What of a step does not depend on the carry, over any leading
        dimensions.  With the action (the replay's): torso, ``mix`` and the
        core's input projection.  Without (the action is a policy's, made in
        the same step): the torso's features alone, and ``step`` takes the
        action."""
        x = self.torso(obs)
        return x if action is None else self.core.project(self._mixed(x, action))

    def step(
        self,
        z: jnp.ndarray,
        carry: Carry,
        reset: jnp.ndarray,
        action: Optional[jnp.ndarray] = None,
    ):
        """What needs the carry: ``encode``'s rows of one step -> (the core's
        output ``y [B, H]``, new carry).  ``action`` goes with rows encoded
        without one; ``mix`` then runs whole here (its matmul is not split by
        columns: that would reorder a reduction)."""
        if action is not None:
            z = self.core.project(self._mixed(z, action))
        return self.core.step(z, carry, reset)

    def readout(self, y: jnp.ndarray) -> jnp.ndarray:
        """``y [..., H]`` -> q ``[...]``."""
        return jnp.squeeze(self.head(y).astype(jnp.float32), axis=-1)

    def sequence(self, obs, action, reset, memory=(), memory_only: bool = False):
        """A whole-sequence core over whole sequences -> (q ``[B, T]``, the
        call's memory and what the pass leaves)."""
        x = self._mixed(self.torso(obs), action)
        y, aux = self.core(x, memory, reset, sequence=True, memory_only=memory_only)
        return self.readout(y), aux

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
