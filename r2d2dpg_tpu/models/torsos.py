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
re-lay into the order the first convolution reads (``Frames``: cut into
blocks of its stride, so that it runs as a stride-1 convolution).  The
learner prepares once an update and every pass cuts its window out of the
result (``models/sequence.py``); acting, serving and ``initial_priority``
hand the torso raw frames as before.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Sequence

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
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
# bytes as whole tiles (``replay/arena.py::_storage_parts``), and a row of one
# is what the frames are transposed by.
_LANES = 128


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class Frames:
    """The frames of a whole sampled batch, prepared for a conv torso: scaled
    and in ``dtype`` already, cut into ``block x block`` squares of pixels
    (the first convolution's stride), ``[H/block, W/block, block·block·C,
    T·B]``: channel ``(a·block + b)·C + c`` of position ``(i, j)`` is
    channel ``c`` of pixel ``(i·block + a, j·block + b)``.  The flattened
    time-major frame index ``t·B + b`` is minor-most, which is the order the
    TPU compiler gives the first convolution's input; 16·C channels (48)
    fill their tiles, where C would be padded to four and fill C of the
    MXU's rows.
    ``frames[a:b]`` is the steps ``[a, b)`` of every sequence: a range of the
    last axis, which with 32 sequences and windows that start at multiples of
    four steps is a run of whole tiles that a convolution reads in place."""

    pixels: jnp.ndarray
    batch: int = dataclasses.field(metadata=dict(static=True))
    block: int = dataclasses.field(metadata=dict(static=True))

    def __getitem__(self, steps: slice) -> "Frames":
        start, stop, _ = steps.indices(self.pixels.shape[-1] // self.batch)
        return Frames(
            self.pixels[..., start * self.batch : stop * self.batch],
            self.batch,
            self.block,
        )


def _scaled(obs: jnp.ndarray, dtype: Any) -> jnp.ndarray:
    """Pixels as the convolutions take them: ``dtype``, bytes over 255."""
    x = obs.astype(dtype)
    return x / 255.0 if obs.dtype == jnp.uint8 else x


# The torso's convolutions: (features, kernel, stride), all VALID.
_CONVS = ((32, 8, 4), (64, 4, 2), (64, 3, 1))


def _blocks(height: int, width: int, channels: int):
    """``(order, shape, block)``: a frame ``[H, W, C]``, flattened, cut into
    ``block x block`` squares of pixels, ``block`` the first convolution's
    stride: element ``order[k]`` of the frame is element ``k`` of the blocks
    ``shape = [H'/block, W'/block, block·block·C]`` (``Frames``).  Rows and
    columns that convolution never reads are left out: it reads ``(out -
    1)·stride + kernel`` of them, ``H'`` and ``W'``, a multiple of the
    stride."""
    _, kernel, block = _CONVS[0]
    read = [(n - kernel) // block * block + kernel for n in (height, width)]
    pixel = np.arange(height * width * channels).reshape(height, width, channels)
    pixel = pixel[: read[0], : read[1]].reshape(
        read[0] // block, block, read[1] // block, block, channels
    )
    shape = (read[0] // block, read[1] // block, block * block * channels)
    return pixel.transpose(0, 2, 1, 3, 4).reshape(-1), shape, block


def _pinned(x: jnp.ndarray) -> jnp.ndarray:
    """``x`` in row-major order, behind an ``optimization_barrier`` so that
    the compiler keeps that layout."""
    layout = Layout(major_to_minor=tuple(range(x.ndim)))
    return lax.optimization_barrier(with_layout_constraint(x, layout))


def _conv_on_blocks(conv: nn.Conv, x: jnp.ndarray, block: int) -> jnp.ndarray:
    """``conv`` (its stride ``block``, its kernel ``k x k`` with ``k`` a
    multiple of it) over frames cut into blocks (``Frames``; ``x [N, H/block,
    W/block, block·block·C]``): a stride-1 convolution with the kernel cut
    the same way, ``[k/block, k/block, block·block·C, F]``.  The same
    products, each output the same sum in another order, under ``conv``'s own
    name; the parameters are ``conv``'s own ``[k, k, C, F]``, made where the
    torso is initialised from raw frames."""
    k = conv.kernel_size[0]
    assert conv.strides == (block, block) and k % block == 0, (conv, block)
    assert conv.padding == "VALID" and conv.use_bias, conv
    assert conv.feature_group_count == 1, conv
    assert conv.input_dilation in (None, 1) and conv.kernel_dilation in (None, 1), conv
    params = conv.variables["params"]
    x, kernel, bias = nn.dtypes.promote_dtype(
        x, params["kernel"], params["bias"], dtype=conv.dtype
    )
    *_, channels, features = kernel.shape
    kernel = kernel.reshape(k // block, block, k // block, block, channels, features)
    kernel = jnp.swapaxes(kernel, 1, 2).reshape(
        k // block, k // block, block * block * channels, features
    )
    with jax.named_scope(conv.name):
        y = lax.conv_general_dilated(
            x,
            kernel,
            (1, 1),
            "VALID",
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
            precision=conv.precision,
        )
        return y + bias


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
        ``cheetah_pixels``, a third of it; PERF.md PR 35), into blocks of the
        first convolution's stride (``_blocks``).

        Three values are pinned by an ``optimization_barrier``, since only a
        value the compiler cannot fold away keeps a layout.  The sampled
        bytes time-major, as rows of 128 (the tiles they are stored in, so
        nothing moves to see them so): the gather's loop then writes each
        sequence's steps where they belong.  The same bytes transposed, a
        row of ``L·B`` bytes for each byte of a frame, their order stated:
        the blocks are then a gather of whole rows, one pass over bytes.  Cut
        into blocks by reshapes and transposes instead, the compiler pads
        each block's rows of 12 to 16 and makes four passes over the frames
        as bfloat16, 164 MB for 38 (PERF.md PR 39).  And the prepared frames,
        their order stated; left to itself the compiler goes through an order
        with the batch padded to 128 lanes.  The values are ``__call__``'s,
        bit for bit; on the CPU the pins are the identity."""
        batch, length, *frame = obs.shape
        rows = (-1, _LANES) if math.prod(frame) % _LANES == 0 else (-1,)
        x = jnp.swapaxes(obs.reshape(batch, length, *rows), 0, 1)
        x = lax.optimization_barrier(x.reshape(length * batch, *rows))
        x = _pinned(jnp.moveaxis(x, 0, -1).reshape(-1, length * batch))
        order, blocks, block = _blocks(*frame)
        x = jnp.take(x, order, axis=0).reshape(*blocks, length * batch)
        return Frames(_pinned(_scaled(x, self.dtype)), batch, block)

    @nn.compact
    def __call__(self, obs) -> jnp.ndarray:
        convs = [
            nn.Conv(
                features,
                (kernel, kernel),
                strides=(stride, stride),
                padding="VALID",
                dtype=self.dtype,
            )
            for features, kernel, stride in _CONVS
        ]
        if isinstance(obs, Frames):  # [h, w, c, T·B] -> [T·B, h, w, c]
            if self.is_initializing():
                raise ValueError("initialise ConvTorso from raw frames, not Frames")
            x = jnp.moveaxis(obs.pixels, -1, 0)
            x = nn.relu(_conv_on_blocks(convs[0], x, obs.block))
            convs = convs[1:]
        else:
            x = _scaled(obs, self.dtype)
        for conv in convs:
            x = nn.relu(conv(x))
        x = x.reshape(x.shape[:-3] + (-1,))
        x = nn.relu(
            nn.Dense(self.out_size, kernel_init=fan_in_uniform(), dtype=self.dtype)(x)
        )
        if isinstance(obs, Frames):  # [T·B, out] -> [T, B, out]
            x = x.reshape(-1, obs.batch, x.shape[-1])
        return x
