"""Pallas TPU kernel: priority scatter write-back for the replay arena.

BASELINE north star: "the prioritized sequence replay buffer lives in HBM
with Pallas scatter for priority updates".  The learner writes ``B`` fresh
sequence priorities into a ``[capacity]`` priority vector each step
(SURVEY.md §2.4 "priority write-back").

TPU-native formulation: Mosaic cannot prove alignment for dynamic single-lane
stores into a 1-D VMEM vector, so a write is expressed the VPU way — the
priority vector is viewed as ``[rows, 128]`` lanes, and an update is a
full-width masked select against a global-index iota
(``where(gid == idx_i, val_i, acc)``).  What the selects run over is the
``B`` lane-rows the written slots land in, not the vector: the vector stays
where it is (``memory_space=pl.ANY``, aliased to the output, so a length that
is a multiple of 128 is neither padded nor copied), row ``idx_i // 128`` is
copied into row ``i`` of a ``[B, 128]`` VMEM scratch, all ``B`` copies in
flight at once, every update is applied in order to the whole scratch, and
the ``B`` rows are copied back.  The work follows ``B`` (``B`` passes over
``B / 8`` vregs and two DMA round trips), whatever the capacity: 524,288
slots at batch 64 cost what 12,288 do, and nothing in VMEM grows with the
vector, so 2**20 slots and more compile.  Until PR 37 the whole vector sat
in VMEM and every update was a pass over all of it: ``B x capacity`` work,
0.067 ms of walker's 0.63 ms update (PERF.md, PR 37).

Duplicate indices resolve last-write-wins (sequential semantics): two slots
of one lane-row arrive as two copies of that row, every copy receives every
update in order, so the copies are equal when they are written back and the
order in which the write-backs land does not matter.  An index outside the
vector writes nothing: the row a copy fetches is clamped to the vector, the
comparison is on the index itself.

On non-TPU backends (CPU tests) the same kernel runs under the Pallas
interpreter when ``R2D2DPG_PALLAS_INTERPRET=1`` (so the kernel logic itself
is exercised in CI); otherwise we fall back to XLA scatter.
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_LANES = 128


def _scatter_kernel(idx_ref, val_ref, prio_ref, out_ref, rows_ref, sem):
    del prio_ref  # aliased to ``out_ref``: the vector is updated in place
    # Python loops: B is static and small, and written out the scalar core
    # issues the copies back to back (rolled, 6.5 us at batch 64 on a v5e;
    # written out, 2.8: PERF.md, PR 37).
    batch = range(idx_ref.shape[0])
    last = out_ref.shape[0] - 1
    row = [jnp.clip(idx_ref[i] // _LANES, 0, last) for i in batch]
    fetch = [
        pltpu.make_async_copy(
            out_ref.at[pl.ds(row[i], 1)], rows_ref.at[pl.ds(i, 1)], sem)
        for i in batch
    ]
    store = [
        pltpu.make_async_copy(
            rows_ref.at[pl.ds(i, 1)], out_ref.at[pl.ds(row[i], 1)], sem)
        for i in batch
    ]
    for copy in fetch:
        copy.start()
    # While the rows travel: the slot each lane of the scratch stands for.
    sub = lax.broadcasted_iota(jnp.int32, rows_ref.shape, 0)
    gid = lax.broadcasted_iota(jnp.int32, rows_ref.shape, 1)
    for i in batch:
        gid = jnp.where(sub == i, gid + row[i] * _LANES, gid)
    for copy in fetch:
        copy.wait()
    acc = rows_ref[:]
    for j in batch:
        acc = jnp.where(gid == idx_ref[j], val_ref[j], acc)
    rows_ref[:] = acc
    for copy in store:
        copy.start()
    for copy in store:
        copy.wait()


@functools.partial(jax.jit, static_argnames=("interpret",))
def _pallas_scatter(
    priority: jnp.ndarray,
    indices: jnp.ndarray,
    values: jnp.ndarray,
    interpret: bool = False,
) -> jnp.ndarray:
    (n,) = priority.shape
    rows = (n + _LANES - 1) // _LANES
    padded = jnp.pad(priority, (0, rows * _LANES - n)).reshape(rows, _LANES)
    out = pl.pallas_call(
        _scatter_kernel,
        out_shape=jax.ShapeDtypeStruct(padded.shape, padded.dtype),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        scratch_shapes=[
            pltpu.VMEM((indices.shape[0], _LANES), padded.dtype),
            pltpu.SemaphoreType.DMA(()),
        ],
        input_output_aliases={2: 0},
        interpret=interpret,
    )(indices.astype(jnp.int32), values, padded)
    return out.reshape(-1)[:n]


def priority_scatter(
    priority: jnp.ndarray, indices: jnp.ndarray, values: jnp.ndarray
) -> jnp.ndarray:
    """``priority.at[indices].set(values)`` via a Pallas kernel on TPU.

    Dispatch is static (backend known at trace time): Pallas on TPU, Pallas
    interpreter when ``R2D2DPG_PALLAS_INTERPRET=1`` (CPU tests), XLA scatter
    otherwise.
    """
    backend = jax.default_backend()
    if backend == "tpu":
        return _pallas_scatter(priority, indices, values)
    if os.environ.get("R2D2DPG_PALLAS_INTERPRET") == "1":
        return _pallas_scatter(priority, indices, values, interpret=True)
    return priority.at[indices].set(values)
