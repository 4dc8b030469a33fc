"""Observation torsos: MLP encoder and CNN (pixels) encoder.

Reference parity: SURVEY.md §2.1 — MLP encoder feeding the LSTM for state
observations; a Conv2d stack -> flatten -> LSTM for the from-pixels config
(BASELINE config #5).  Weight init follows the DDPG convention (fan-in
uniform; SURVEY §2.1 "Weight init" row).

TPU notes: convs and the big dense layers run on the MXU; ``dtype`` lets the
whole torso compute in bfloat16 while keeping parameters in float32.

A torso also says what it wants done ONCE to the observations of a whole
sampled batch before any pass reads a window of them (``prepare``): nothing
for flat observations; for pixels the conversion to ``dtype`` and the one
re-lay into the order the first convolution reads (``Frames``).  The learner
prepares once an update and every pass cuts its window out of the result
(``models/sequence.py``); acting, serving and ``initial_priority`` hand the
torso raw frames as before.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Sequence

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental.layout import Layout, with_layout_constraint


def fan_in_uniform():
    """U(-1/sqrt(fan_in), 1/sqrt(fan_in)) — the canonical DDPG hidden init."""
    return nn.initializers.variance_scaling(1.0 / 3.0, "fan_in", "uniform")


def symmetric_uniform(scale: float):
    """U(-scale, scale) — the canonical DDPG final-layer init (3e-3)."""

    def init(key, shape, dtype=jnp.float32):
        return nn.initializers.uniform(2.0 * scale)(key, shape, dtype) - scale

    return init


class MLPTorso(nn.Module):
    """ReLU MLP over flat observations."""

    layer_sizes: Sequence[int] = (256,)
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, obs: jnp.ndarray) -> jnp.ndarray:
        x = obs.astype(self.dtype)
        for size in self.layer_sizes:
            x = nn.relu(
                nn.Dense(size, kernel_init=fan_in_uniform(), dtype=self.dtype)(x)
            )
        return x

    def prepare(self, obs: jnp.ndarray) -> jnp.ndarray:
        """Flat observations go to every pass as they are sampled."""
        return obs


# The lanes of a tile of the device's memory: the replay stores a frame's
# bytes as whole tiles (``replay/arena.py::_storage_shape``), and a row of one
# is what the frames are transposed by.
_LANES = 128


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class Frames:
    """The frames of a whole sampled batch, prepared for a conv torso: scaled
    and in ``dtype`` already, ``[H, W, C, T·B]`` with the flattened
    time-major frame index ``t·B + b`` minor-most, which is the order the
    TPU compiler gives the first convolution's input (its three channels
    would fill 3 of 128 lanes).  ``frames[a:b]`` is the steps ``[a, b)`` of
    every sequence: a range of the last axis, which with 32 sequences and
    windows that start at multiples of four steps is a run of whole tiles
    that a convolution reads in place."""

    pixels: jnp.ndarray
    batch: int = dataclasses.field(metadata=dict(static=True))

    def __getitem__(self, steps: slice) -> "Frames":
        start, stop, _ = steps.indices(self.pixels.shape[-1] // self.batch)
        return Frames(
            self.pixels[..., start * self.batch : stop * self.batch], self.batch
        )


def _scaled(obs: jnp.ndarray, dtype: Any) -> jnp.ndarray:
    """Pixels as the convolutions take them: ``dtype``, bytes over 255."""
    x = obs.astype(dtype)
    return x / 255.0 if obs.dtype == jnp.uint8 else x


class ConvTorso(nn.Module):
    """Nature-DQN-style CNN for pixel observations: ``[..., H, W, C]``, uint8
    or float, or the ``Frames`` that ``prepare`` made of a sampled batch."""

    out_size: int = 256
    dtype: Any = jnp.float32

    def prepare(self, obs: jnp.ndarray) -> Frames:
        """``obs [B, L, H, W, C]``, a sampled batch as the replay hands it,
        as ``Frames`` of all ``L`` steps: what ``__call__`` does to raw frames
        before its first convolution (``astype(dtype) / 255``), and the
        re-lay the compiler would otherwise make for every pass of every
        window (eleven copies and slices of the same 17.7 MB an update of
        ``cheetah_pixels``, a third of it; PERF.md PR 35).

        Two values are pinned by an ``optimization_barrier``, since only a
        value the compiler cannot fold away keeps a layout.  The sampled
        bytes time-major, as rows of 128 (the tiles they are stored in, so
        nothing moves to see them so): the gather's loop then writes each
        sequence's steps where they belong.  And the prepared frames, their
        order stated, so that one transposition of bytes and one pass that
        pads the channels make them for every window; left to itself the
        compiler goes through an order with the batch padded to 128 lanes.
        The values are ``__call__``'s, bit for bit; on the CPU both pins are
        the identity."""
        batch, length, *frame = obs.shape
        rows = (-1, _LANES) if math.prod(frame) % _LANES == 0 else (-1,)
        x = jnp.swapaxes(obs.reshape(batch, length, *rows), 0, 1)
        x = lax.optimization_barrier(x.reshape(length * batch, *rows))
        x = jnp.moveaxis(x, 0, -1).reshape(*frame, length * batch)
        x = with_layout_constraint(
            _scaled(x, self.dtype), Layout(major_to_minor=tuple(range(x.ndim)))
        )
        return Frames(lax.optimization_barrier(x), batch)

    @nn.compact
    def __call__(self, obs) -> jnp.ndarray:
        if isinstance(obs, Frames):  # [H, W, C, T·B] -> [T·B, H, W, C]
            x = jnp.moveaxis(obs.pixels, -1, 0)
        else:
            x = _scaled(obs, self.dtype)
        for features, kernel, stride in ((32, 8, 4), (64, 4, 2), (64, 3, 1)):
            x = nn.relu(
                nn.Conv(
                    features,
                    (kernel, kernel),
                    strides=(stride, stride),
                    padding="VALID",
                    dtype=self.dtype,
                )(x)
            )
        x = x.reshape(x.shape[:-3] + (-1,))
        x = nn.relu(
            nn.Dense(self.out_size, kernel_init=fan_in_uniform(), dtype=self.dtype)(x)
        )
        if isinstance(obs, Frames):  # [T·B, out] -> [T, B, out]
            x = x.reshape(-1, obs.batch, x.shape[-1])
        return x
