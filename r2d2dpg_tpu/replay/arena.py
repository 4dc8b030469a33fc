"""HBM-resident prioritized sequence replay arena.

Reference parity: SURVEY.md §2.2 — the reference keeps a CPU-side ring buffer
of fixed-length sequences with proportional prioritization (sum-tree or flat
``np.random.choice``), IS weights, and learner priority write-back, fed by
actor processes over a queue.

TPU-native design (BASELINE north star "prioritized sequence replay buffer
lives in HBM"): the arena is a struct-of-arrays pytree of preallocated device
buffers with ring semantics.  ``add`` / ``sample`` / ``update_priorities`` are
pure functions that live *inside* the outer jitted training program, so no
host round-trip ever touches the replay path:

- ``add``: batched scatter of B sequences at the ring cursor.
- ``sample``: proportional sampling by inverse CDF of ``p^alpha`` in two
  levels (``_draw_proportional``: one pass over the vector for the sums of
  blocks of 128 slots, a CDF over those, a running sum inside the B drawn
  blocks alone; no state beside ``priority``, no sum-tree) or uniform over
  the valid prefix.
- ``update_priorities``: scatter write-back (Pallas kernel on TPU — see
  ``ops/pallas/scatter.py`` — with an XLA ``.at[].set`` fallback).

Sequence layout (SURVEY §2.2 "sequence format"): each slot stores a
fixed-length window of ``burnin + unroll + n_step`` steps plus the initial
recurrent carries of actor and critic nets captured at window start.

Storage: every field ``[capacity, ...]``, each row's own bytes and no more,
shaped so that the device lays the slot axis major-most where it can
(``_storage_parts``): a small row as its whole 128-lane rows flat and the
rest beside them, a large one (pixels) as whole tiles, either kept as a
``StoredRows`` that knows the rows' own shape.  Rows go in and come out in
their own shapes whatever the storage: ``add`` / ``write_contiguous`` write
them, ``gather`` reads them, ``sample`` ends in the same gather, and nothing
else may assume how a field of ``ArenaState.data`` is stored.

The sampled batch is a boundary: ``sample`` hands its B rows back in the
arena's own dtypes, behind ``_pin_storage_dtypes``.  Without it the TPU
compiler rounds the whole arena once a call instead of the rows: the rows are
operands of default-precision matmuls, so its bfloat16 propagation retypes
the gather and puts the ``convert`` on the gather's ``[capacity, ...]``
operand, outside the update loop.  At 524,288 walker sequences that was 3.2 GB
read and 1.6 GB written in every call of 4 updates, 1.88 of 2.79 ms an update
(PERF.md, PR 23 and PR 25), and no CPU test can see it:
``chip_smoke.py``'s train leg guards it (``obs/hlo.py``).  The boundary also
states where the compiler would choose badly: the device layout of large
gathered rows is ``sample``'s, batch and time major-most (``_gather_rows``:
time first for a row stored as tiles, which is how the learner's conv torso
takes it), not the arena's slot-minor order carried over to the batch.
"""

from __future__ import annotations

import dataclasses
import functools
import math
import threading
from typing import Any, Dict, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental.layout import Layout, with_layout_constraint

from r2d2dpg_tpu.obs.quality import PROVENANCE_ABSENT
from r2d2dpg_tpu.ops.priority import PRIORITY_EPS


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class SequenceBatch:
    """A batch of stored sequences, batch-major ``[B, L, ...]``.

    ``carries`` holds the *initial* recurrent state (window start) per net:
    ``{"actor": carry, "critic": carry}`` with leaves ``[B, ...]`` (empty
    pytrees for feedforward nets).
    """

    obs: jnp.ndarray
    action: jnp.ndarray
    reward: jnp.ndarray
    discount: jnp.ndarray
    reset: jnp.ndarray
    carries: Dict[str, Any]


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class ArenaState:
    """Device-resident replay storage (a pytree of preallocated buffers)."""

    # A field's rows [capacity, ...] in their own shape and dtype, or as a
    # ``StoredRows`` (``_storage_parts``): read through ``gather``.
    data: SequenceBatch
    priority: jnp.ndarray  # [capacity] raw priorities; 0 marks empty slots
    cursor: jnp.ndarray  # next write position
    total_added: jnp.ndarray  # monotone count of sequences ever added
    # Experience-quality slot metadata (ISSUE 18): [capacity, 2] int32 —
    # column 0 the sequence's behavior param version (staged provenance),
    # column 1 the learner-step stamp at arena entry (the in-graph
    # replay-age clock).  PROVENANCE_ABSENT (-1) where unknown; survives
    # exactly as long as its slot (the ring scatter overwrites both).
    meta: jnp.ndarray


@functools.partial(
    jax.tree_util.register_dataclass, data_fields=["parts"], meta_fields=["row"]
)
@dataclasses.dataclass(frozen=True)
class StoredRows:
    """A field of ``ArenaState.data`` stored in other shapes than its rows'
    (``_storage_parts``): ``parts`` are ``[capacity, ...]`` leaves in the
    row's dtype that hold each row's elements in order, part after part.
    ``row``, the rows' own shape, is static, part of the tree's structure
    like a field's name: any arena reads the rows of a state another made,
    and a checkpoint is restored into it."""

    parts: Tuple[jnp.ndarray, ...]
    row: Tuple[int, ...]


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class SampleResult:
    batch: SequenceBatch
    indices: jnp.ndarray  # [B] slot indices, for priority write-back
    probs: jnp.ndarray  # [B] sampling probabilities (1/N for uniform)


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class StagedSequences:
    """B emitted sequences in flight from a collector to the learner.

    The pipelined executor's staging-queue payload (training/pipeline.py):
    one pytree so a whole collect phase's emission crosses the queue as a
    single object and enters the learner's drain program as one argument.
    ``priorities`` is ``None`` when the learner computes the initial
    priority at drain time (the default — it ranks fresh sequences with
    its CURRENT nets, the same staleness class as the phase-locked path);
    a collector that computes priorities locally (Ape-X style, with its
    stale behavior nets) fills it instead.

    ``behavior_version``/``collect_id`` are the experience-quality
    provenance (ISSUE 18): per-sequence int64 arrays stamping which
    behavior param version collected each sequence and the collector's
    monotone phase clock at staging.  ``None`` (the default, and the only
    value on pre-plane frames) means "unknown" — every downstream fold
    disarms rather than refuses (obs/quality.py), and the wire codec
    emits the provenance-free schema so provenance-absent frames stay
    byte-identical to the pre-plane layout.
    """

    seq: SequenceBatch  # leaves [B, L, ...] / carries [B, ...]
    priorities: Any  # [B] float32, or None (learner-computed at drain)
    behavior_version: Any = None  # [B] int64 behavior param version, or None
    collect_id: Any = None  # [B] int64 collector phase clock, or None


def staged_nbytes(staged: StagedSequences) -> int:
    """Total leaf bytes of a staged batch (numpy views or device arrays).

    The experience-path trace's size attribution (obs/trace.py): an
    ``arena_add`` span carrying its batch's byte count makes a slow
    host->device staging transfer diagnosable from trace.json alone."""
    return int(
        sum(
            int(getattr(leaf, "nbytes", 0))
            for leaf in jax.tree_util.tree_leaves(staged)
        )
    )


def stack_staged(batches: Sequence[StagedSequences]) -> StagedSequences:
    """Concatenate staged batches along B — the coalesced-drain payload.

    Host-side (numpy): the fleet learner stacks queue-backlogged actor
    batches BEFORE the compiled drain call so one ``add_staged`` dispatch
    amortizes the whole backlog (fleet/ingest.py ``drain_coalesce``).  A
    single batch passes through untouched (no copy — wire-decoded views go
    to the device as-is); mixing resolved and unresolved priorities is a
    caller bug (one fleet ranks one way) and refused loudly."""
    if not batches:
        raise ValueError("stack_staged needs at least one batch")
    if len(batches) == 1:
        return batches[0]
    resolved = [b.priorities is not None for b in batches]
    if any(resolved) != all(resolved):
        raise ValueError(
            "stack_staged: cannot mix resolved and unresolved priorities"
        )
    seq = jax.tree_util.tree_map(
        lambda *xs: np.concatenate([np.asarray(x) for x in xs], axis=0),
        *[b.seq for b in batches],
    )
    priorities = (
        np.concatenate([np.asarray(b.priorities) for b in batches])
        if all(resolved)
        else None
    )

    def _cat_provenance(parts):
        # Mixed presence DROPS the provenance (disarms the quality folds)
        # instead of refusing: an old-schema frame coalesced with stamped
        # ones is a tolerated interop case, unlike mixed priorities which
        # would silently change ranking semantics.
        if all(p is not None for p in parts):
            return np.concatenate([np.asarray(p) for p in parts])
        return None

    return StagedSequences(
        seq=seq,
        priorities=priorities,
        behavior_version=_cat_provenance(
            [b.behavior_version for b in batches]
        ),
        collect_id=_cat_provenance([b.collect_id for b in batches]),
    )


class _StagedWriterClaim:
    """``with arena.staged_writer():`` — loud refusal on overlap."""

    def __init__(self, lock):
        self._lock = lock

    def __enter__(self):
        if not self._lock.acquire(blocking=False):
            raise RuntimeError(
                "ReplayArena.add_staged is single-writer: another thread is "
                "mid-add on this arena.  Route producers through a staging "
                "queue drained by one thread (docs/FLEET.md)"
            )
        return self

    def __exit__(self, *exc):
        self._lock.release()


def _pin_storage_dtypes(batch: SequenceBatch) -> SequenceBatch:
    """The identity on values, and the place where storage precision ends.

    The float rows ``sample`` gathered are materialised here in the arena's
    own dtypes, so no consumer's dtype wish can travel back through the
    gather onto its ``[capacity, ...]`` operand (module docstring, "The
    sampled batch is a boundary").  A bare ``optimization_barrier`` does not
    hold: the TPU compiler's bfloat16 propagation retypes the barrier with
    the gather behind it.  So a float leaf crosses the barrier as its bit
    pattern, an unsigned integer of its own width, which no float type can
    be propagated into; B rows are cast, never the arena (casting the arena
    before the gather is hoisted out of the update loop and materialised: a
    second copy of the replay).  Integer leaves (pixels) cannot be rounded
    and pass untouched: their gather's output layout is ``_gather_rows``'s
    to state, and a barrier here would only stand between that statement
    and the consumer."""

    def pin(x):
        if not jnp.issubdtype(x.dtype, jnp.floating):
            return x
        bits = lax.bitcast_convert_type(
            x, jnp.dtype(f"uint{8 * x.dtype.itemsize}")
        )
        return lax.bitcast_convert_type(
            lax.optimization_barrier(bits), x.dtype
        )

    return jax.tree_util.tree_map(pin, batch)


# Rows of fewer elements the TPU compiler gathers in one fusion that reads
# each row where it lies; the loop it expands ``buf[indices]`` into was seen
# from 105,840 elements a row up (compiles for a described v5e).  One fusion
# is not one cost: stored in its own shape such a row lies slot minor-most and
# the fusion reads one lane of every tile the row touches, or the compiler
# re-lays the whole leaf once a call first.  So these rows are stored as their
# whole lane-rows and a rest, and the larger ones as tiles
# (``_storage_parts``).
_LOOPED_GATHER_ROW_ELEMENTS = 1 << 16

# A tile of the device's memory: 128 lanes by as many sublanes as hold 32
# bytes a lane (8 float32, 16 bfloat16, 32 uint8).
_LANES = 128
_SUBLANE_BYTES = 32
# The compiler's looped gather of a large row reads it in one piece while its
# stored dimensions stay at or under this; a longer one it first cuts out of
# the WHOLE leaf (a ``[capacity, 128, ...]`` slice an update, seen for the
# first dimension behind the slots and for the sublanes; compiles for a
# described v5e).  The one-fusion gather of a small row has no such limit: a
# flat stretch of 1,024 lanes (walker's observations) is read in place.
_GATHER_DIMENSION = 128


def _storage_parts(row_shape: Tuple[int, ...], dtype) -> Tuple[Tuple[int, ...], ...]:
    """The shapes, behind the slot axis, of the leaves a row is stored in;
    ``(row_shape,)`` where it is stored in its own shape.

    The device layout of a buffer follows from its shape: the compiler lays
    a leaf out with the least padding, which for a row in its own shape puts
    the slot axis minor-most, and the B rows of a batch are read a lane of
    every tile they touch.  For ``cheetah_pixels``' ``[capacity, L, H, W,
    C]`` pixel leaf each was a 70.8 MB padded slice (9.4 of 11.5 ms an
    update, PERF.md PR 34); for walker's ``f32[524288,43,24]`` observations
    528 KB of tiles for a row of 4 KB, where a smaller leaf
    (``f32[12288,45,6]``) was re-laid whole once a call instead (PERF.md PR
    40).  A leaf whose minor dimension is whole lane-rows, a multiple of 128,
    lies slot major-most with nothing to pad, and the compiler lays it so.

    A small row (under ``_LOOPED_GATHER_ROW_ELEMENTS``) of n elements is
    stored as two leaves: its first ``128 · floor(n / 128)`` elements flat,
    slot major-most, read in place; the other ``n mod 128`` flat beside
    them, a leaf that lies slot minor-most with each row in one column of a
    few tiles (walker's observations: 1,024 and 8).  A row that is whole
    lane-rows is only flattened (a carry of 256 keeps its shape), and one
    under 128 elements keeps its own shape: no shape of its own bytes lies
    slot major-most.  Padding each row to whole lane-rows would keep one
    leaf, at 1 GB more of walker's arena: the arena holds the rows' bytes.

    A large row is stored as whole tiles, nothing to pad and the slot axis
    major-most: the shortest run of its trailing dimensions that is a whole
    number of tiles becomes ``[n_tiles, sublanes, 128]``, the dimensions
    before it stay (pixels: a step's frame, ``[L, 3, 32, 128]`` for ``[L, 64,
    64, 3]``; keeping the time axis makes the way back to the frames a re-lay
    of the three minor dimensions alone, 0.36 ms an update less than from
    ``[135, 32, 128]``).  A large row that is no whole number of tiles, or
    would need a dimension over ``_GATHER_DIMENSION``, keeps its own shape.

    The parts hold the row's elements in order, in the row's dtype:
    ``concatenate([p.reshape(capacity, -1) for p in parts], 1)`` is the
    rows."""
    row_shape = tuple(row_shape)
    elements = math.prod(row_shape)
    if elements < _LOOPED_GATHER_ROW_ELEMENTS:
        whole, rest = divmod(elements, _LANES)
        if not whole or (row_shape == (elements,) and not rest):
            return (row_shape,)
        return ((whole * _LANES,), (rest,)) if rest else ((elements,),)
    sublanes = max(_SUBLANE_BYTES // jnp.dtype(dtype).itemsize, 1)
    tile = sublanes * _LANES
    for k in reversed(range(len(row_shape))):
        inner = math.prod(row_shape[k:])
        stored = row_shape[:k] + (inner // tile, sublanes, _LANES)
        if inner % tile == 0 and max(stored) <= _GATHER_DIMENSION:
            return (stored,)
    return (row_shape,)


def _as_stored(stored: Any, rows: jnp.ndarray) -> Any:
    """``rows`` (``[n, ...]`` in their own shape) as ``stored`` (a field of
    ``ArenaState.data``, or its shapes) holds them: in its dtype, and cut
    into its parts where it is a ``StoredRows``."""
    n = rows.shape[0]
    if not isinstance(stored, StoredRows):
        return rows.astype(stored.dtype).reshape((n,) + stored.shape[1:])
    flat = rows.astype(stored.parts[0].dtype).reshape(n, -1)
    parts, at = [], 0
    for part in stored.parts:
        width = math.prod(part.shape[1:])
        parts.append(flat[:, at : at + width].reshape((n,) + part.shape[1:]))
        at += width
    return dataclasses.replace(stored, parts=tuple(parts))


def _gather_rows(stored: Any, indices: jnp.ndarray) -> jnp.ndarray:
    """The rows of ``indices`` of one field of ``ArenaState.data`` in their
    own shape, with the device layout of large rows stated here: batch and
    time major-most, in the order their consumer wants them, so that a
    sequence is written once into stretches of its own.

    The TPU compiler expands the gather of a large row into a loop of B
    iterations over an accumulator, and left to itself hands the consumer a
    batch minor-most in the batch: B of 128 lanes used, and, gathered from a
    leaf in the rows' own shape, every iteration rewrote the whole buffer to
    fill one lane of each tile (``dynamic-update-slice``, 59 of
    ``cheetah_pixels``' 70 ms an update; PERF.md, PR 28).  A row stored as
    tiles behind its time axis (``_storage_parts``) is stated as it is
    stored, TIME major-most: the loop copies a sequence as L runs of a step's
    tiles, and the learner takes the steps of all sequences as rows of one
    time-major array without another pass (``models/torsos.py::ConvTorso
    .prepare``; stated on the rows' own shape, batch or time major-most, the
    loop writes batch-major and that is one more copy of the batch, 0.016 ms
    an update: PERF.md, PR 35); a consumer that takes the rows' own shape
    pays the one re-lay it paid before.  A large row kept in its own shape
    is stated batch major-most, then time, the two longest of the other
    dimensions minor-most (least padding).  A small row's parts are gathered
    each where it lies and joined: B rows, no layout to state.  The values
    are the stored rows', bit for bit; on the CPU the constraint is the
    identity.  ``chip_smoke.py``'s train leg holds the compiled learner call
    to all of it (``obs/hlo.py::batch_minor_writes``, ``arena_reads``,
    ``arena_relays``, ``frame_relays``)."""
    if isinstance(stored, StoredRows):
        parts, row_shape = stored.parts, stored.row
    else:
        parts, row_shape = (stored,), tuple(stored.shape[1:])
    own = indices.shape[:1] + row_shape
    if math.prod(row_shape) < _LOOPED_GATHER_ROW_ELEMENTS:
        flat = [part[indices].reshape(indices.shape[:1] + (-1,)) for part in parts]
        return jnp.concatenate(flat, axis=1).reshape(own)
    rows = parts[0][indices]
    if rows.shape != own and rows.shape[1] == row_shape[0]:
        stored = with_layout_constraint(
            rows, Layout(major_to_minor=(1, 0, *range(2, rows.ndim)))
        )
        return stored.reshape(own)
    rows = rows.reshape(own)
    by_length = sorted(range(2, rows.ndim), key=lambda d: rows.shape[d])
    return with_layout_constraint(
        rows, Layout(major_to_minor=(0, 1, *by_length))
    )


def _is_field(x: Any) -> bool:
    return isinstance(x, StoredRows)


def _stored_like(data: Any, batch: SequenceBatch) -> Any:
    """``batch``'s rows as ``data`` (an ``ArenaState.data``) stores them,
    field by field (``_as_stored``): a tree of ``data``'s structure."""
    return jax.tree_util.tree_map(
        lambda rows, stored: _as_stored(stored, rows), batch, data
    )


def _gather(data: Any, indices: jnp.ndarray) -> Any:
    """The stored rows of ``indices`` for every field of ``data`` (an
    ``ArenaState.data``, or any part of one: an empty tree gives an empty
    batch), each in its own shape."""
    return jax.tree_util.tree_map(
        lambda stored: _gather_rows(stored, indices), data, is_leaf=_is_field
    )


# Slots to a block of the two-level draw: the lane width, so that the vector
# viewed as ``[blocks, 128]`` keeps its order in memory.
_DRAW_BLOCK = 128


def _first_above(cdf: jnp.ndarray, u: jnp.ndarray) -> jnp.ndarray:
    """For each ``u`` (not below nought), the first entry of ``cdf`` (from
    nought up, non-decreasing along its last axis) above it: ``searchsorted(cdf, u, side="right")``, so an entry
    that adds no mass is never the answer.  Held to the entry at which
    ``cdf`` reaches its last value: a ``u`` that rounding put at or past the
    total mass gets the last entry WITH mass, not whatever lies at the end."""
    return jnp.minimum(
        (cdf <= u[..., None]).sum(axis=-1, dtype=jnp.int32),
        (cdf < cdf[..., -1:]).sum(axis=-1, dtype=jnp.int32),
    )


def _draw_proportional(
    scaled: jnp.ndarray, key: jax.Array, batch_size: int
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """``batch_size`` slots drawn in proportion to ``scaled`` (``[capacity]``,
    non-negative), and the total mass they were drawn against.

    The inverse CDF in two levels, because B draws need B entries of the CDF
    and not ``capacity``: a running sum over the whole vector compiles on the
    TPU to a ``reduce-window`` of 128 adds an element (13 % of walker's
    update at 524,288 slots, PERF.md PR 32).  The block of a draw comes from
    the CDF over the sums of blocks of ``_DRAW_BLOCK`` slots, the slot from
    the running sum inside that block alone.  The same distribution and, for
    the same uniforms, the slot a flat CDF gives up to the rounding of a
    float32 partial sum; the number of blocks follows from the shape."""
    capacity = scaled.shape[0]
    blocks = -(-capacity // _DRAW_BLOCK)
    rows = jnp.pad(scaled, (0, blocks * _DRAW_BLOCK - capacity)).reshape(
        blocks, _DRAW_BLOCK
    )
    block_sum = rows.sum(axis=1)
    block_cdf = jnp.cumsum(block_sum)
    total = block_cdf[-1]
    u = jax.random.uniform(key, (batch_size,)) * total
    block = _first_above(block_cdf, u)
    # The mass before the block, then the running sum inside it; ``u`` is
    # held to the block's own range where the two sums round differently.
    before = block_cdf[block] - block_sum[block]
    lane = _first_above(
        jnp.cumsum(rows[block], axis=1), jnp.maximum(u - before, 0.0)
    )
    indices = jnp.clip(block * _DRAW_BLOCK + lane, 0, capacity - 1)
    return indices, total


class ReplayArena:
    """Static replay configuration + pure state-transition functions.

    The instance holds only static metadata (capacity, prioritization flag),
    so it can be closed over by jitted functions; all mutable storage lives in
    the ``ArenaState`` pytree threaded through ``add``/``sample``/``update``.
    """

    def __init__(
        self,
        capacity: int,
        *,
        prioritized: bool = True,
        alpha: float = 0.6,
        use_pallas: bool = True,
    ):
        if capacity < 1:
            raise ValueError("capacity must be >= 1")
        self.capacity = capacity
        self.prioritized = prioritized
        self.alpha = alpha
        # Pallas needs single-device refs; trainers whose arena buffers carry
        # an explicit mesh sharding (parallel.hybrid) use the XLA scatter.
        self.use_pallas = use_pallas
        # Telemetry (obs/): the arena itself is pure device code, so the
        # host-side instruments are fed by whoever fetches the state —
        # trainer/pipeline log paths call ``observe_state_scalars`` with
        # values that rode the log cadence's existing batched device_get.
        from r2d2dpg_tpu.obs import get_registry

        reg = get_registry()
        self._obs_capacity = reg.gauge(
            "r2d2dpg_replay_capacity", "arena slot capacity (static)"
        )
        self._obs_capacity.set(float(capacity))
        self._obs_occupancy = reg.gauge(
            "r2d2dpg_replay_occupancy", "filled arena slots (min(added, cap))"
        )
        self._obs_priority_sum = reg.gauge(
            "r2d2dpg_replay_priority_sum",
            "sum of raw slot priorities (0 while empty)",
        )
        self._obs_added = reg.gauge(
            "r2d2dpg_replay_sequences_added",
            "monotone count of sequences ever added",
        )
        # Single-writer guard for the staged path (see staged_writer /
        # add_staged).  Reentrant: the drain loops hold it around their
        # jitted call while add_staged re-acquires inside the trace.
        self._staged_writer_lock = threading.RLock()

    def observe_state_scalars(
        self, occupancy: float, priority_sum: float, total_added: float
    ) -> None:
        """Publish host-fetched arena scalars onto the obs registry.

        Called on the log cadence with values from the SAME batched
        ``jax.device_get`` that drains the episode accumulators — the
        telemetry layer adds no host syncs of its own."""
        self._obs_occupancy.set(occupancy)
        self._obs_priority_sum.set(priority_sum)
        self._obs_added.set(total_added)

    # ------------------------------------------------------------------ init
    def init_state(self, example: SequenceBatch) -> ArenaState:
        """Preallocate buffers from one example sequence batch (leading dim B).

        Every field in the row's dtype and ``_storage_parts``' shapes: a
        row kept in its own shape as one ``[capacity, ...]`` leaf, another
        as a ``StoredRows`` of its parts."""

        def alloc(x):
            row = tuple(x.shape[1:])
            parts = _storage_parts(row, x.dtype)
            if parts == (row,):
                return jnp.zeros((self.capacity,) + row, x.dtype)
            return StoredRows(
                parts=tuple(
                    jnp.zeros((self.capacity,) + part, x.dtype) for part in parts
                ),
                row=row,
            )

        return ArenaState(
            data=jax.tree_util.tree_map(alloc, example),
            priority=jnp.zeros((self.capacity,), jnp.float32),
            cursor=jnp.zeros((), jnp.int32),
            total_added=jnp.zeros((), jnp.int32),
            meta=jnp.full((self.capacity, 2), PROVENANCE_ABSENT, jnp.int32),
        )

    # ------------------------------------------------------------------- add
    def add(
        self,
        state: ArenaState,
        batch: SequenceBatch,
        priorities: jnp.ndarray,
        meta: Any = None,
    ) -> ArenaState:
        """Scatter B new sequences at the ring cursor (FIFO overwrite).

        ``meta`` is the quality plane's per-slot stamp (``[B, 2]`` int32:
        behavior version, entry step — see ``ArenaState.meta``); ``None``
        writes ``PROVENANCE_ABSENT`` so an unstamped add disarms the
        downstream age/lag folds instead of inheriting the evicted
        slot's stale metadata."""
        b = priorities.shape[0]
        idx = (state.cursor + jnp.arange(b, dtype=jnp.int32)) % self.capacity

        data = jax.tree_util.tree_map(
            lambda buf, new: buf.at[idx].set(new),
            state.data,
            _stored_like(state.data, batch),
        )
        priority = state.priority.at[idx].set(
            jnp.maximum(priorities, PRIORITY_EPS)
        )
        if meta is None:
            meta = jnp.full((b, 2), PROVENANCE_ABSENT, jnp.int32)
        else:
            meta = jnp.asarray(meta).astype(jnp.int32)
        return ArenaState(
            data=data,
            priority=priority,
            cursor=(state.cursor + b) % self.capacity,
            total_added=state.total_added + b,
            meta=state.meta.at[idx].set(meta),
        )

    def write_contiguous(
        self, state: ArenaState, batch: SequenceBatch, priorities: jnp.ndarray
    ) -> ArenaState:
        """``add`` for ``n`` rows that do not wrap: slots ``[cursor, cursor +
        n)``, the caller's to ensure.  One ``dynamic_update_slice`` a storage
        leaf, so under ``jit(..., donate_argnums=0)`` nothing is copied (a
        scatter, as ``add``'s, re-laid a whole pixel buffer out: a second
        copy that does not fit the chip, PERF.md PR 33); the state is
        ``add``'s of the same rows without stamps, to the last bit."""
        n = priorities.shape[0]

        def put(buf, new):
            return lax.dynamic_update_slice_in_dim(
                buf, _as_stored(buf, new), state.cursor, 0
            )

        return ArenaState(
            data=jax.tree_util.tree_map(put, state.data, _stored_like(state.data, batch)),
            priority=put(state.priority, jnp.maximum(priorities, PRIORITY_EPS)),
            cursor=(state.cursor + n) % self.capacity,
            total_added=state.total_added + n,
            meta=put(state.meta, jnp.full((n, 2), PROVENANCE_ABSENT, jnp.int32)),
        )

    def staged_meta(self, staged: StagedSequences, stamp: Any = None) -> Any:
        """Build the ``add`` meta stamp for a staged batch: column 0 from
        the staged behavior-version provenance (absent -> sentinel),
        column 1 from ``stamp`` — the OWNING learner's step clock at
        absorption, so in-graph replay age is always measured against one
        process's clock (the actor's ``collect_id`` phase clock serves the
        host-side shard path instead).  Returns ``None`` (a pure sentinel
        fill) when neither is known."""
        if staged.behavior_version is None and stamp is None:
            return None
        b = staged.seq.reward.shape[0]

        def col(x):
            if x is None:
                return jnp.full((b,), PROVENANCE_ABSENT, jnp.int32)
            x = jnp.asarray(x).astype(jnp.int32)
            return jnp.broadcast_to(x, (b,)) if x.ndim == 0 else x

        return jnp.stack(
            [col(staged.behavior_version), col(stamp)], axis=1
        )

    def add_staged(
        self,
        state: ArenaState,
        staged: StagedSequences,
        stamp: Any = None,
    ) -> ArenaState:
        """Absorb a staged batch (the pipelined executor's drain path).

        ``staged.priorities`` must be resolved by the caller (the drain
        program fills ``None`` via ``Trainer._initial_priorities`` before
        calling) — the arena itself has no nets to rank with.

        SINGLE-WRITER contract: ``add`` is a pure state transition, so two
        threads calling it concurrently on the same ``ArenaState`` (e.g. a
        fleet ingest handler racing a local collector) would each produce a
        new state from the SAME input and one side's sequences would be
        silently lost when the caller threads the wrong result forward.
        Producers must route through a staging queue drained by ONE thread
        (training/pipeline.py, fleet/ingest.py; docs/FLEET.md "Single
        writer").  The ``staged_writer`` guard turns a violated contract
        into a loud error instead of silent data loss — but note it only
        fires HERE for eager callers: inside a jitted drain program this
        body runs at trace time, so drain loops must hold ``staged_writer``
        around the compiled call itself (fleet/ingest.py does)."""
        if staged.priorities is None:
            raise ValueError(
                "add_staged needs resolved priorities; compute them "
                "(e.g. Trainer._initial_priorities) before absorbing"
            )
        if isinstance(state.cursor, jax.core.Tracer):
            # Under a jit trace the claim is meaningless (this body runs at
            # trace time, not execution time — see the contract above), and
            # taking it would falsely collide with a drain thread holding
            # the writer claim around its compiled call while ANOTHER
            # thread traces a new drain width (the fleet learner's
            # background coalesce-width precompile, fleet/ingest.py).
            return self.add(
                state,
                staged.seq,
                staged.priorities,
                meta=self.staged_meta(staged, stamp),
            )
        with self.staged_writer():
            return self.add(
                state,
                staged.seq,
                staged.priorities,
                meta=self.staged_meta(staged, stamp),
            )

    def staged_writer(self):
        """Non-blocking claim of the single staged-writer slot (a context
        manager).  Overlapping claims from another thread are exactly the
        lost-update race, so they raise loudly; the lock is reentrant so a
        drain loop can hold it around its jitted call while ``add_staged``
        re-claims inside the trace."""
        return _StagedWriterClaim(self._staged_writer_lock)

    # ------------------------------------------------------------------ size
    def size(self, state: ArenaState) -> jnp.ndarray:
        return jnp.minimum(state.total_added, self.capacity)

    def per_shard_occupancy(
        self, state: ArenaState, num_shards: int
    ) -> jnp.ndarray:
        """``[num_shards]`` filled-slot counts by contiguous capacity block.

        The dp-sharded arena's per-shard occupancy (parallel/dp_learner.py):
        ``NamedSharding(P(DP_AXIS))`` splits axis 0 into equal CONTIGUOUS
        blocks, so block ``i`` of this reshape is exactly shard ``i``'s
        slots.  Pure device code — callers fold the result into the obs
        registry off the log cadence's existing batched ``device_get``."""
        if self.capacity % num_shards:
            raise ValueError(
                f"capacity {self.capacity} not divisible by {num_shards} shards"
            )
        return (state.priority.reshape(num_shards, -1) > 0.0).sum(axis=1)

    # ---------------------------------------------------------------- sample
    def sample(
        self, state: ArenaState, key: jax.Array, batch_size: int
    ) -> SampleResult:
        """Draw ``batch_size`` sequences (proportional-prioritized or uniform).

        Caller must ensure the arena is non-empty (the training loop gates on
        a warm-up size; SURVEY §2.5 "Lifecycle" row).

        The batch comes back through ``_pin_storage_dtypes``: the identity on
        values, and not a no-op.  It keeps the chip's compiler from rounding
        the whole arena to bfloat16 once a call (two thirds of walker's
        learner time before it: 357.6 updates/s at 524,288 slots, PR 23).
        """
        size = self.size(state)
        if self.prioritized:
            # p^alpha over valid slots (empty slots have priority 0).
            scaled = jnp.where(
                state.priority > 0.0, state.priority**self.alpha, 0.0
            )
            indices, total = _draw_proportional(scaled, key, batch_size)
            probs = scaled[indices] / jnp.maximum(total, 1e-12)
        else:
            indices = jax.random.randint(
                key, (batch_size,), 0, jnp.maximum(size, 1)
            )
            probs = jnp.full(
                (batch_size,), 1.0 / jnp.maximum(size.astype(jnp.float32), 1.0)
            )

        # The same gather as ``gather``, reached as a function of
        # ``state.data`` (a subclass that keeps its rows elsewhere hands
        # ``sample`` an empty ``data`` and gets an empty batch).
        batch = _gather(state.data, indices)
        return SampleResult(
            batch=_pin_storage_dtypes(batch), indices=indices, probs=probs
        )

    def gather(self, state: ArenaState, indices: jnp.ndarray) -> SequenceBatch:
        """The stored rows of the slots ``indices`` in the rows' own shapes
        and dtypes, the layout of large rows stated (``_gather_rows``):
        what ``sample`` ends with, before ``_pin_storage_dtypes``."""
        return _gather(state.data, indices)

    # ------------------------------------------------------- priority update
    def update_priorities(
        self, state: ArenaState, indices: jnp.ndarray, priorities: jnp.ndarray
    ) -> ArenaState:
        """Learner write-back of fresh sequence priorities (SURVEY §2.4)."""
        values = jnp.maximum(priorities, PRIORITY_EPS)
        if self.use_pallas:
            from r2d2dpg_tpu.ops.pallas import priority_scatter

            new_priority = priority_scatter(state.priority, indices, values)
        else:
            new_priority = state.priority.at[indices].set(values)
        return dataclasses.replace(state, priority=new_priority)
