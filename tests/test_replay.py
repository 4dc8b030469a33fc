"""Replay arena: ring overwrite, prioritized sampling distribution, priority
write-back via the Pallas kernel (interpret mode) — SURVEY.md §4.1/§4.5."""

import dataclasses
import math
import os

os.environ["R2D2DPG_PALLAS_INTERPRET"] = "1"  # exercise the kernel on CPU

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chip_smoke import SCATTER_CAPACITIES, scatter_case
from r2d2dpg_tpu.replay import ReplayArena, SequenceBatch
from r2d2dpg_tpu.replay.arena import StoredRows

L, OBS, ACT, HID = 4, 3, 2, 8


def make_batch(b, value=0.0):
    zeros = jnp.zeros((b, HID))
    return SequenceBatch(
        obs=jnp.full((b, L, OBS), value),
        action=jnp.zeros((b, L, ACT)),
        reward=jnp.arange(b, dtype=jnp.float32)[:, None] * jnp.ones((b, L)),
        discount=jnp.ones((b, L)),
        reset=jnp.zeros((b, L)),
        carries={"actor": (zeros, zeros), "critic": (zeros, zeros)},
    )


def test_add_and_size():
    arena = ReplayArena(capacity=10)
    state = arena.init_state(make_batch(2))
    assert int(arena.size(state)) == 0
    state = arena.add(state, make_batch(2), jnp.ones(2))
    assert int(arena.size(state)) == 2
    state = arena.add(state, make_batch(3), jnp.ones(3))
    assert int(arena.size(state)) == 5
    assert int(state.cursor) == 5


def test_ring_overwrite_fifo():
    arena = ReplayArena(capacity=4)
    state = arena.init_state(make_batch(1))
    for i in range(6):  # 6 adds into capacity 4 -> slots hold adds 2..5
        b = make_batch(1, value=float(i))
        state = arena.add(state, b, jnp.ones(1))
    obs_vals = np.asarray(arena.gather(state, jnp.arange(4)).obs)[:, 0, 0]
    # slot k holds add k for k in 4,5 (wrapped to 0,1) and 2,3 at slots 2,3
    np.testing.assert_allclose(sorted(obs_vals), [2.0, 3.0, 4.0, 5.0])
    assert int(arena.size(state)) == 4


def test_prioritized_sampling_distribution():
    """chi^2-style check: empirical sampling freq tracks p^alpha (SURVEY §4.1)."""
    arena = ReplayArena(capacity=4, alpha=1.0)
    state = arena.init_state(make_batch(4))
    prios = jnp.array([1.0, 2.0, 3.0, 6.0])
    state = arena.add(state, make_batch(4), prios)

    n_draws, bsz = 200, 64
    keys = jax.random.split(jax.random.PRNGKey(0), n_draws)
    sample = jax.jit(lambda s, k: arena.sample(s, k, bsz).indices)
    counts = np.zeros(4)
    for k in keys:
        idx, c = np.unique(np.asarray(sample(state, k)), return_counts=True)
        counts[idx] += c
    freq = counts / counts.sum()
    want = np.asarray(prios) / float(prios.sum())
    np.testing.assert_allclose(freq, want, atol=0.02)


def test_sample_probs_match_distribution():
    arena = ReplayArena(capacity=8, alpha=0.7)
    state = arena.init_state(make_batch(4))
    prios = jnp.array([0.5, 1.0, 2.0, 4.0])
    state = arena.add(state, make_batch(4), prios)
    res = arena.sample(state, jax.random.PRNGKey(1), 16)
    scaled = np.asarray(prios) ** 0.7
    want = scaled / scaled.sum()
    np.testing.assert_allclose(
        np.asarray(res.probs), want[np.asarray(res.indices)], rtol=1e-5
    )


def test_empty_slots_never_sampled():
    arena = ReplayArena(capacity=100)
    state = arena.init_state(make_batch(3))
    state = arena.add(state, make_batch(3), jnp.ones(3))
    res = arena.sample(state, jax.random.PRNGKey(2), 256)
    assert np.asarray(res.indices).max() < 3


def test_uniform_sampling():
    arena = ReplayArena(capacity=50, prioritized=False)
    state = arena.init_state(make_batch(10))
    state = arena.add(state, make_batch(10), jnp.ones(10))
    res = arena.sample(state, jax.random.PRNGKey(3), 512)
    idx = np.asarray(res.indices)
    assert idx.min() >= 0 and idx.max() < 10
    np.testing.assert_allclose(np.asarray(res.probs), 0.1, rtol=1e-6)


def _priorities(pattern, capacity):
    """A ``[capacity]`` float32 priority vector for one of the draw's cases."""
    rng = np.random.default_rng(capacity)
    p = rng.uniform(0.1, 2.0, capacity).astype(np.float32)
    if pattern == "tenth_empty":
        p[rng.random(capacity) < 0.1] = 0.0
    elif pattern == "all_mass_in_last_filled":
        p[:] = 0.0
        p[capacity // 2] = 3.0
    elif pattern == "empty_tail":
        p[capacity // 2:] = 0.0
    elif pattern == "six_decades":
        p = (10.0 ** rng.uniform(-3.0, 3.0, capacity)).astype(np.float32)
    return p


@pytest.mark.parametrize(
    "pattern",
    ["tenth_empty", "all_mass_in_last_filled", "empty_tail", "six_decades"],
)
@pytest.mark.parametrize("capacity", [100, 128, 12_288, 50_000, 524_288])
def test_prioritized_draw_is_the_float64_inverse_cdf(capacity, pattern):
    """For the uniforms its key gives, ``sample`` draws the slot a float64
    inverse CDF over the same ``p^alpha`` draws, or a neighbour WITH mass that
    the rounding of a float32 partial sum puts the uniform in: the uniform
    lies within 4 float32 roundings of the total of the drawn slot's own
    stretch of the CDF.  Never a slot of no mass, at any capacity (one block,
    a ragged last block, the cells' 12,288 / 50,000 / 524,288).  ``probs`` is
    the slot's mass over the total."""
    batch = 64
    arena = ReplayArena(capacity=capacity)
    priority = _priorities(pattern, capacity)
    one = jnp.zeros((1, 1))
    state = dataclasses.replace(
        arena.init_state(SequenceBatch(
            obs=one, action=one, reward=one, discount=one, reset=one, carries={})),
        priority=jnp.asarray(priority),
        total_added=jnp.asarray(capacity, jnp.int32),
    )
    key = jax.random.PRNGKey(capacity)
    res = jax.jit(arena.sample, static_argnums=2)(state, key, batch)
    indices, probs = np.asarray(res.indices), np.asarray(res.probs)

    # The mass as the device raised it (float32), summed in float64.
    scaled = np.asarray(
        jnp.where(state.priority > 0.0, state.priority**arena.alpha, 0.0)
    ).astype(np.float64)
    cdf = np.cumsum(scaled)
    total = cdf[-1]
    u = np.asarray(jax.random.uniform(key, (batch,))).astype(np.float64) * total
    want = np.searchsorted(cdf, u, side="right")

    assert (scaled[indices] > 0.0).all()
    start = np.concatenate([[0.0], cdf[:-1]])[indices]
    beside = np.maximum(np.maximum(start - u, u - cdf[indices]), 0.0)
    assert beside.max() <= 4 * np.finfo(np.float32).eps * total
    assert (indices == want).mean() >= 0.9
    np.testing.assert_allclose(probs, scaled[indices] / total, rtol=1e-5)


@pytest.mark.parametrize(
    "u, want",
    [
        ([0.0, 0.5, 1.0, 2.9], [1, 1, 3, 3]),  # side="right": past entries of no mass
        ([3.0, 5.0], [3, 3]),  # at or past the total: the last entry with mass
    ],
    ids=["inside", "past_the_total"],
)
def test_first_above_never_answers_an_entry_of_no_mass(u, want):
    """Entries 0, 2 and 4 of this CDF add nothing, the last among them."""
    from r2d2dpg_tpu.replay.arena import _first_above

    cdf = jnp.array([0.0, 1.0, 1.0, 3.0, 3.0])
    np.testing.assert_array_equal(_first_above(cdf, jnp.array(u)), want)
    rows = jnp.broadcast_to(cdf, (len(u), 5))  # a CDF a draw, as inside the blocks
    np.testing.assert_array_equal(_first_above(rows, jnp.array(u)), want)


def test_priority_update_pallas_kernel():
    """update_priorities runs the Pallas kernel (interpret mode on CPU)."""
    arena = ReplayArena(capacity=8)
    state = arena.init_state(make_batch(4))
    state = arena.add(state, make_batch(4), jnp.ones(4))
    state = arena.update_priorities(
        state, jnp.array([0, 2]), jnp.array([5.0, 7.0])
    )
    np.testing.assert_allclose(
        np.asarray(state.priority)[:4], [5.0, 1.0, 7.0, 1.0], rtol=1e-5
    )


@pytest.mark.parametrize("capacity", SCATTER_CAPACITIES)
def test_priority_scatter_at_config_capacities(capacity):
    """The interpreted kernel on the very cases chip_smoke.py's kernel leg
    compiles on the chip: every capacity a config uses (256, 8,000, 50,000,
    100,000 — all but the first short of a whole 8x128 float32 tile), the
    walker cell's 524,288 and twice that, at the learner batch of 64, with a
    slot written four times: last write wins."""
    from r2d2dpg_tpu.ops.pallas.scatter import priority_scatter

    priority, indices, values, want = scatter_case(capacity)
    got = np.asarray(jax.jit(priority_scatter)(priority, indices, values))
    np.testing.assert_array_equal(got, want)


def _written_slots(capacity):
    """The slots the write-back cases plant among a batch's random ones, by
    what each is there to show."""
    row = capacity // 2 // 128 * 128
    return {
        "a_slot_written_four_times": [capacity // 3] * 4,
        "two_slots_of_one_lane_row": [row + 3, row + 77],
        "the_first_and_the_last_row": [
            5, 127, (capacity - 1) // 128 * 128, capacity - 1],
        "the_last_slot": [capacity - 1],
        "an_index_equal_to_the_capacity": [capacity],
    }


def _write_back_cases():
    """``(capacity, batch, what)``: every capacity a config or a cell uses
    (the cells' 524,288 and 12,288 are multiples of 128, the others end
    inside a lane-row) at both learner batches with all of the planted slots
    at once, then each kind of planted slot alone at a short ragged vector
    and at walker's."""
    kinds = list(_written_slots(256))
    for capacity in (256, 8_000, 12_288, 50_000, 100_000, 524_288):
        for batch in (64, 32):
            yield capacity, batch, "all"
    for capacity in (8_000, 524_288):
        for kind in kinds:
            yield capacity, 64, kind


@pytest.mark.parametrize(
    "capacity, batch, what", list(_write_back_cases()), ids=str)
def test_priority_scatter_is_the_sequential_write_back(capacity, batch, what):
    """The interpreted kernel against a loop that writes one slot after
    another (the last write wins; an index outside the vector writes
    nothing), bit for bit: the B lane-rows travel as B copies, so two slots
    of one row, a slot written four times, both ends of the vector and the
    last slot of a length that is no multiple of 128 are the cases a copy
    written back late, or a row fetched from beyond the vector, would
    break."""
    from r2d2dpg_tpu.ops.pallas.scatter import priority_scatter

    planted = _written_slots(capacity)
    slots = sum(planted.values(), []) if what == "all" else planted[what]
    rng = np.random.default_rng([capacity, batch, len(slots)])
    priority = rng.random(capacity, dtype=np.float32)
    indices = rng.integers(0, capacity, batch).astype(np.int32)
    indices[rng.choice(batch, len(slots), replace=False)] = slots
    values = rng.random(batch, dtype=np.float32) + 1.0
    want = priority.copy()
    for i, v in zip(indices, values):
        if i < capacity:
            want[i] = v
    got = np.asarray(jax.jit(priority_scatter)(priority, indices, values))
    np.testing.assert_array_equal(got, want)
    assert (want != priority).sum() == len(set(indices[indices < capacity]))


def test_priority_update_inside_jit():
    arena = ReplayArena(capacity=8)
    state = arena.init_state(make_batch(4))
    state = arena.add(state, make_batch(4), jnp.ones(4))

    @jax.jit
    def upd(s):
        return arena.update_priorities(s, jnp.array([1, 3]), jnp.array([9.0, 2.0]))

    s2 = upd(state)
    np.testing.assert_allclose(
        np.asarray(s2.priority)[:4], [1.0, 9.0, 1.0, 2.0], rtol=1e-5
    )


def _dp_arena_state(arena, batch, prios, mesh):
    """Place a fresh ArenaState on ``mesh`` with the dp-learner layout
    (data/priority capacity-sharded, cursor/total_added replicated) and
    add ``batch`` through the jitted staged path."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from r2d2dpg_tpu.parallel.mesh import DP_AXIS
    from r2d2dpg_tpu.replay.arena import ArenaState, StagedSequences

    dp = NamedSharding(mesh, P(DP_AXIS))
    rep = NamedSharding(mesh, P())
    state = jax.device_put(
        arena.init_state(batch),
        ArenaState(
            data=dp, priority=dp, cursor=rep, total_added=rep, meta=dp
        ),
    )
    add = jax.jit(arena.add_staged)
    return add(state, StagedSequences(seq=batch, priorities=prios))


def test_dp_sharded_add_staged_and_sample_match_dp1():
    """ISSUE 9: add_staged + sample on a dp=2 capacity-sharded arena give
    the SAME indices/probs/priorities as the dp=1 layout at the same seed
    — sharding is layout, never semantics.  Priorities are small integers
    so every cumsum association is exact."""
    from r2d2dpg_tpu.parallel import make_mesh

    arena = ReplayArena(capacity=16, alpha=1.0, use_pallas=False)
    prios = jnp.array([1.0, 2.0, 3.0, 6.0])
    key = jax.random.PRNGKey(9)
    results = {}
    for d in (1, 2):
        state = _dp_arena_state(arena, make_batch(4), prios, make_mesh(d))
        res = jax.jit(arena.sample, static_argnums=2)(state, key, 32)
        results[d] = jax.device_get(
            (res.indices, res.probs, state.priority, state.cursor)
        )
    for a, b in zip(results[1], results[2]):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_dp_sharded_arena_layout_and_per_shard_occupancy():
    """The dp=2 arena's storage really is capacity-sharded, and
    per_shard_occupancy counts each contiguous capacity block (= shard)."""
    from jax.sharding import PartitionSpec as P

    from r2d2dpg_tpu.parallel import make_mesh
    from r2d2dpg_tpu.parallel.mesh import DP_AXIS

    arena = ReplayArena(capacity=8, use_pallas=False)
    mesh = make_mesh(2)
    state = _dp_arena_state(arena, make_batch(3), jnp.ones(3), mesh)
    assert state.priority.sharding.spec == P(DP_AXIS)
    assert state.data.obs.sharding.spec == P(DP_AXIS)
    # 3 adds at cursor 0 -> all in shard 0's block (slots 0..3).
    np.testing.assert_array_equal(
        np.asarray(arena.per_shard_occupancy(state, 2)), [3, 0]
    )
    with pytest.raises(ValueError, match="divisible"):
        arena.per_shard_occupancy(state, 3)


# ------------------------------------------- sharded replay (ISSUE 10)
def _np_batch(b, start=0.0):
    return SequenceBatch(
        obs=np.zeros((b, L, OBS), np.float32),
        action=np.zeros((b, L, ACT), np.float32),
        reward=(start + np.arange(b, dtype=np.float32))[:, None]
        * np.ones((b, L), np.float32),
        discount=np.ones((b, L), np.float32),
        reset=np.zeros((b, L), np.float32),
        carries={},
    )


def test_two_level_sharded_sampling_matches_central_distribution():
    """ISSUE 10 fidelity anchor: exact-integer priorities spread over 2
    shards, alpha=1 — the two-level draw (shards ∝ Σp, within-shard
    proportional) and the central ``ReplayArena.sample`` converge to the
    SAME p/Σp distribution over many draws, and the combined two-level
    probabilities equal the central per-draw probabilities exactly."""
    from r2d2dpg_tpu.replay.sharded import (
        ReplayShard,
        combine_probs,
        shard_quotas,
    )

    prios = np.array([1.0, 2.0, 3.0, 6.0], np.float64)
    # Central reference: empirical frequency from the device arena.
    arena = ReplayArena(capacity=4, alpha=1.0)
    state = arena.init_state(make_batch(4))
    state = arena.add(state, make_batch(4), jnp.asarray(prios))
    n_draws, bsz = 200, 64
    sample = jax.jit(lambda s, k: arena.sample(s, k, bsz).indices)
    central = np.zeros(4)
    for k in jax.random.split(jax.random.PRNGKey(0), n_draws):
        idx, c = np.unique(np.asarray(sample(state, k)), return_counts=True)
        central[idx] += c
    central /= central.sum()

    # Sharded: priorities 1,2 on shard 0 and 3,6 on shard 1; reward row
    # value identifies the slot globally.
    shards = [ReplayShard(4, alpha=1.0, shard_id=i) for i in range(2)]
    shards[0].add(_np_batch(2, start=0.0), prios[:2])
    shards[1].add(_np_batch(2, start=2.0), prios[2:])
    rng = np.random.default_rng(1)
    sums = np.array([s.scaled_sum() for s in shards])
    total = float(sums.sum())
    counts = np.zeros(4)
    for _ in range(n_draws):
        quotas = shard_quotas(sums, bsz, rng)
        for sid, q in enumerate(quotas):
            if q == 0:
                continue
            s = shards[sid].sample(int(q), rng)
            keys = s.seq.reward[:, 0].astype(int)
            np.testing.assert_allclose(  # combined == central p/Σ, exact
                combine_probs(s.probs, float(sums[sid]), total),
                prios[keys] / prios.sum(),
                rtol=1e-12,
            )
            np.add.at(counts, keys, 1)
    sharded = counts / counts.sum()
    want = prios / prios.sum()
    np.testing.assert_allclose(sharded, want, atol=0.02)
    np.testing.assert_allclose(central, want, atol=0.02)
    np.testing.assert_allclose(sharded, central, atol=0.03)


def test_shard_priority_write_back_roundtrip_and_stale_version_ignored():
    """Write-back is keyed (slot, generation): a verdict about a
    sequence the ring has since evicted must NOT clobber the newer
    occupant's priority — stale versions are ignored, like param
    regressions (docs/REPLAY.md 'Write-back versioning')."""
    from r2d2dpg_tpu.replay.sharded import ReplayShard

    s = ReplayShard(4, alpha=1.0)
    s.add(_np_batch(4), np.array([1.0, 1.0, 1.0, 1.0]))
    rng = np.random.default_rng(0)
    sam = s.sample(4, rng)
    # Fresh handles: every entry applies; the sum moves accordingly.
    applied = s.update_priorities(
        sam.slots, sam.gens, np.full(4, 3.0)
    )
    assert applied == 4
    hit = np.unique(sam.slots)
    assert s.priority_sum() == 3.0 * len(hit) + 1.0 * (4 - len(hit))
    # Overwrite two slots (ring wrap bumps their generations) …
    before = s.sample(4, rng)  # handles from the OLD generation
    s.add(_np_batch(2, start=10.0), np.array([2.0, 2.0]))
    psum = s.priority_sum()
    # … a stale write-back touches only the un-overwritten slots.
    stale_mask = np.isin(before.slots, [0, 1])
    applied = s.update_priorities(
        before.slots, before.gens, np.full(4, 100.0)
    )
    assert applied == int((~stale_mask).sum())
    # The overwritten slots' fresh 2.0 priorities survived untouched.
    assert s._priority[0] == 2.0 and s._priority[1] == 2.0
    if stale_mask.all():
        assert s.priority_sum() == psum


def test_shard_ring_eviction_semantics():
    """The shard ring is FIFO over capacity: occupancy caps, the oldest
    rows are the evicted ones, generations bump per overwrite, and
    total_added stays monotone (the 'a dead shard loses only
    re-collectable experience' accounting base)."""
    from r2d2dpg_tpu.replay.sharded import ReplayShard

    s = ReplayShard(4, alpha=1.0)
    for i in range(6):  # 6 adds into capacity 4 -> rows 2..5 survive
        s.add(_np_batch(1, start=float(i)), np.array([1.0]))
    assert s.occupancy() == 4 and s.total_added == 6
    rows = sorted(s._data.reward[:, 0].tolist())
    assert rows == [2.0, 3.0, 4.0, 5.0]
    # Slots 0,1 were written twice (generation 2), 2,3 once.
    np.testing.assert_array_equal(s._generation, [2, 2, 1, 1])
    # None priorities enter at the shard max (the central "max" entry
    # semantics) with floor 1.0.
    s.update_priorities(np.array([2]), np.array([1]), np.array([7.0]))
    s.add(_np_batch(1, start=9.0), None)
    assert s._priority[2] == 7.0  # untouched slot keeps its rank
    assert s._priority[s._cursor - 1] == 7.0  # new row entered at max
    # An empty shard refuses to sample (quotas never route draws there).
    import pytest as _pytest

    empty = ReplayShard(2, alpha=1.0)
    with _pytest.raises(ValueError, match="empty"):
        empty.sample(1, np.random.default_rng(0))


def test_degraded_two_level_sampling_over_surviving_subset():
    """ISSUE 12 satellite: the degraded-sampling math.  With a dead
    shard advertising Σp^α = 0 (or simply absent), ``shard_quotas`` over
    the SURVIVING subset is still a valid distribution (non-negative,
    sums to n, zero draws for the dead shard), and the two-level draw
    restricted to survivors matches central proportional sampling
    restricted to the surviving slots — on exact-integer priorities, the
    combined probabilities are exactly ``p / Σ_survivors``."""
    from r2d2dpg_tpu.replay.sharded import (
        ReplayShard,
        combine_probs,
        shard_quotas,
    )

    prios = np.array([1.0, 2.0, 4.0, 8.0, 5.0, 3.0], np.float64)
    shards = [ReplayShard(4, alpha=1.0, shard_id=i) for i in range(3)]
    shards[0].add(_np_batch(2, start=0.0), prios[:2])
    shards[1].add(_np_batch(2, start=2.0), prios[2:4])  # the dead one
    shards[2].add(_np_batch(2, start=4.0), prios[4:])
    # Shard 1 dies: its advertised weight is ZERO (exactly what
    # RemoteShardSet.scaled_sums reports for a dead shard).
    sums = np.array(
        [shards[0].scaled_sum(), 0.0, shards[2].scaled_sum()], np.float64
    )
    total = float(sums.sum())
    surviving = np.array([1.0, 2.0, 5.0, 3.0])  # shards 0 and 2's slots
    rng = np.random.default_rng(5)
    counts: dict = {}
    n_rounds, per_round = 250, 32
    for _ in range(n_rounds):
        quotas = shard_quotas(sums, per_round, rng)
        assert quotas.sum() == per_round and (quotas >= 0).all()
        assert quotas[1] == 0  # a dead shard NEVER receives draws
        for sid, q in enumerate(quotas):
            if q == 0:
                continue
            s = shards[sid].sample(int(q), rng)
            keys = s.seq.reward[:, 0].astype(int)
            # Combined probability == central proportional RESTRICTED to
            # the surviving slots, exactly (integer priorities).
            np.testing.assert_allclose(
                combine_probs(s.probs, float(sums[sid]), total),
                prios[keys] / surviving.sum(),
                rtol=1e-12,
            )
            for k in keys:
                counts[int(k)] = counts.get(int(k), 0) + 1
    assert set(counts) <= {0, 1, 4, 5}  # no draw from the dead shard
    freq = np.array(
        [counts.get(k, 0) for k in (0, 1, 4, 5)], np.float64
    ) / (n_rounds * per_round)
    np.testing.assert_allclose(freq, surviving / surviving.sum(), atol=0.02)
    # An all-dead tier is a caller error, loudly (the sampler WAITS on
    # this instead of fabricating draws).
    import pytest as _pytest

    with _pytest.raises(ValueError, match="empty"):
        shard_quotas([0.0, 0.0, 0.0], 4, np.random.default_rng(0))


def test_ring_wrap_eviction_counter_counts():
    """ISSUE 12 satellite: FIFO eviction (which replaced shedding in
    PR 10) leaves a trace — ``evictions_total`` counts exactly the
    FILLED slots the ring overwrote, and the ``evict_cb`` hook (the obs
    counter's rider) sees the same numbers under the same add lock."""
    from r2d2dpg_tpu.replay.sharded import ReplayShard

    seen = []
    s = ReplayShard(4, alpha=1.0, evict_cb=seen.append)
    s.add(_np_batch(3), np.ones(3))
    assert s.evictions_total == 0 and seen == []  # filling, not evicting
    # Wrap: slots 3,0,1 — slot 3 was still EMPTY, 0 and 1 were filled.
    s.add(_np_batch(3, start=10.0), np.ones(3))
    assert s.evictions_total == 2 and seen == [2]
    # Full ring: every further add evicts its whole width.
    s.add(_np_batch(4, start=20.0), np.ones(4))
    assert s.evictions_total == 6 and seen == [2, 4]
    assert s.occupancy() == 4 and s.total_added == 10


def test_sampled_batch_contents_roundtrip():
    arena = ReplayArena(capacity=16)
    state = arena.init_state(make_batch(4))
    state = arena.add(state, make_batch(4), jnp.array([1e9, 1e-6, 1e-6, 1e-6]))
    res = arena.sample(state, jax.random.PRNGKey(0), 8)
    # Overwhelming priority on slot 0 -> nearly all samples are slot 0 with reward row 0.
    assert (np.asarray(res.indices) == 0).mean() > 0.9
    row0 = np.asarray(res.batch.reward)[np.asarray(res.indices) == 0]
    np.testing.assert_allclose(row0, 0.0)


def _mixed_rows(n=12, frame=(2, 2, 3)):
    """Rows whose leaves are float32 and uint8 (pixel observations), with
    bit patterns a rounding or a float compare would lose: a NaN payload,
    -0.0, a subnormal, and mantissas bfloat16 cannot hold."""
    rng = np.random.default_rng(0)
    action = rng.standard_normal((n, L, ACT)).astype(np.float32)
    action[0, 0] = np.array([0x7FC00123, 0x80000000], np.uint32).view(np.float32)
    action[1, 0, 0] = np.float32(1e-45)

    def carry():
        return jnp.asarray(rng.standard_normal((n, HID)).astype(np.float32))

    batch = SequenceBatch(
        obs=jnp.asarray(rng.integers(0, 256, (n, L) + frame, dtype=np.uint8)),
        action=jnp.asarray(action),
        reward=jnp.asarray(rng.random((n, L), dtype=np.float32)),
        discount=jnp.ones((n, L)),
        reset=jnp.zeros((n, L)),
        carries={"actor": (carry(), carry()), "critic": (carry(), carry())},
    )
    return batch, jnp.asarray(rng.random(n) + 0.1, jnp.float32)


def _mixed_state(arena, n=12, frame=(2, 2, 3)):
    """An arena holding ``_mixed_rows``, slot ``i`` the row ``i``."""
    batch, priorities = _mixed_rows(n, frame)
    return arena.add(arena.init_state(batch), batch, priorities)


def _bits(x):
    x = np.asarray(x)
    return x.view(f"uint{8 * x.dtype.itemsize}")


@pytest.mark.parametrize("mode", ["eager", "jit", "scan", "vmap"])
@pytest.mark.parametrize("prioritized", [False, True], ids=["uniform", "prioritized"])
@pytest.mark.parametrize("frame", [(2, 2, 3), (64, 96, 3)], ids=["frame2x2", "frame64x96"])
def test_sample_is_the_plain_gather_in_the_arenas_own_dtypes(
    frame, prioritized, mode, monkeypatch
):
    """What ``sample`` does to the rows it gathers (their device layout
    stated, the float ones pinned to the arena's dtypes) is the identity on
    bits: batch, indices and probs are what the sample without the pin
    returns, and the batch is the rows added, leaf by leaf in their own shapes
    and dtypes, for a rank-5 pixel leaf beside the float and carry leaves:
    small frames, stored as they are and gathered in one piece, and frames
    of unequal sides (a transposed row would show) large enough to be stored
    as tiles and gathered with a stated layout."""
    from r2d2dpg_tpu.replay import arena as arena_mod

    B = 5
    arena = ReplayArena(capacity=16, prioritized=prioritized)
    state = _mixed_state(arena, frame=frame)
    stated = L * np.prod(frame) >= arena_mod._LOOPED_GATHER_ROW_ELEMENTS
    assert stated == (frame != (2, 2, 3))
    keys = jax.random.split(jax.random.PRNGKey(3), 2)

    def draw():
        def sample(s, k):
            return arena.sample(s, k, B)

        if mode == "eager":
            return sample(state, keys[0])
        if mode == "jit":
            return jax.jit(sample)(state, keys[0])
        if mode == "scan":
            return jax.jit(
                lambda s, ks: jax.lax.scan(lambda c, k: (c, sample(c, k)), s, ks)[1]
            )(state, keys)
        return jax.vmap(lambda k: sample(state, k))(keys)

    got = draw()
    monkeypatch.setattr(arena_mod, "_pin_storage_dtypes", lambda batch: batch)
    plain = draw()

    rows, _ = _mixed_rows(frame=frame)
    want = jax.tree_util.tree_map(lambda x: x[got.indices], rows)
    lead = (2, B) if mode in ("scan", "vmap") else (B,)
    for g, w, row in zip(
        jax.tree_util.tree_leaves(got.batch),
        jax.tree_util.tree_leaves(want),
        jax.tree_util.tree_leaves(rows),
    ):
        assert g.dtype == row.dtype
        assert g.shape == lead + row.shape[1:]
        np.testing.assert_array_equal(_bits(g), _bits(w))
    assert {str(x.dtype) for x in jax.tree_util.tree_leaves(got.batch)} == {
        "float32", "uint8"}
    for g, p in zip(jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(plain)):
        assert g.dtype == p.dtype
        np.testing.assert_array_equal(_bits(g), _bits(p))


@pytest.mark.parametrize("how", ["named_sharding", "shard_map"])
def test_sample_states_its_rows_layout_under_a_sharded_arena(how):
    """The mesh trainers' two ways of holding the arena (``parallel/hybrid``
    and ``dp_learner``: an explicit ``NamedSharding`` over the slot axis;
    ``parallel/spmd``: each device's own slots under ``shard_map``) take the
    stated layout of a large pixel row: the draw is still ``buf[indices]``."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    devices = jax.devices()[:4]
    if len(devices) < 4:
        pytest.skip("needs four devices")
    n, B = 8, 3
    per = n // len(devices)
    arena = ReplayArena(capacity=n, prioritized=True, use_pallas=False)
    state = _mixed_state(arena, n=n, frame=(64, 96, 3))
    pixels = np.asarray(_mixed_rows(n, (64, 96, 3))[0].obs)
    mesh = Mesh(np.array(devices), ("dp",))

    def slots(x):
        return x.ndim > 0 and x.shape[0] == n

    key = jax.random.PRNGKey(5)
    if how == "named_sharding":
        sharded = jax.device_put(state, jax.tree_util.tree_map(
            lambda x: NamedSharding(mesh, P("dp") if slots(x) else P()), state))
        res = jax.jit(lambda s, k: arena.sample(s, k, B))(sharded, key)
        np.testing.assert_array_equal(
            np.asarray(res.batch.obs), pixels[np.asarray(res.indices)])
        return

    # Each device's arena is the one that initialised the state, at a
    # device's share of the capacity (``SPMDTrainer.init``).
    local = arena
    local.capacity = per

    def draw(s, k):
        s = dataclasses.replace(s, total_added=jnp.minimum(s.total_added, per))
        res = local.sample(s, k[0], B)
        return res.batch.obs, res.indices

    specs = jax.tree_util.tree_map(lambda x: P("dp") if slots(x) else P(), state)
    obs, idx = jax.jit(jax.shard_map(
        draw, mesh=mesh, in_specs=(specs, P("dp")), out_specs=(P("dp"), P("dp")),
        check_vma=False))(state, jax.random.split(key, len(devices)))
    obs, idx = np.asarray(obs).reshape(len(devices), B, *pixels.shape[1:]), np.asarray(idx)
    for d in range(len(devices)):
        np.testing.assert_array_equal(obs[d], pixels[d * per + idx[d * B:(d + 1) * B]])


# ------------------------------------ contiguous storage of large rows (PR 34)
FRAMES = [(2, 2, 3), (64, 96, 3)]  # stored in its own shape; stored as tiles
FRAME_IDS = ["small_row", "large_row"]


def _rows_equal(got, want):
    assert jax.tree_util.tree_structure(got) == jax.tree_util.tree_structure(want)
    for g, w in zip(jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)):
        assert g.shape == w.shape and g.dtype == w.dtype
        np.testing.assert_array_equal(_bits(g), _bits(w))


@pytest.mark.parametrize("frame", FRAMES, ids=FRAME_IDS)
def test_gather_after_a_wrapped_ring_equals_the_rows_added(frame):
    """Ten rows into six slots, four at a time: slot ``k`` holds the latest
    row whose number is ``k`` modulo six, in the rows' own shapes and dtypes."""
    rows, priorities = _mixed_rows(12, frame)
    arena = ReplayArena(capacity=6)
    state = arena.init_state(rows)
    for start in (0, 4, 8):
        part = jax.tree_util.tree_map(lambda x: x[start:start + 4], rows)
        state = arena.add(state, part, priorities[start:start + 4])
    assert int(state.cursor) == 0 and int(state.total_added) == 12
    held = jnp.asarray([6, 7, 8, 9, 10, 11])  # slots 0..5
    order = jnp.asarray([5, 0, 3, 3, 1])
    _rows_equal(arena.gather(state, order),
                jax.tree_util.tree_map(lambda x: x[held[order]], rows))


@pytest.mark.parametrize("frame", FRAMES, ids=FRAME_IDS)
def test_write_contiguous_at_a_cursor_equals_add(frame):
    """The in-place write of rows that do not wrap is ``add``'s of the same
    rows: priorities floored at the epsilon, stamps cleared, cursor and count
    advanced; under ``jit`` with the state donated."""
    from r2d2dpg_tpu.obs.quality import PROVENANCE_ABSENT
    from r2d2dpg_tpu.ops.priority import PRIORITY_EPS

    rows, priorities = _mixed_rows(12, frame)
    arena = ReplayArena(capacity=16)
    first = jax.tree_util.tree_map(lambda x: x[:5], rows)
    partly = arena.add(arena.init_state(rows), first, priorities[:5],
                       meta=jnp.full((5, 2), 7))
    partly = dataclasses.replace(partly, meta=jnp.full_like(partly.meta, 7))
    nxt = jax.tree_util.tree_map(lambda x: x[5:12], rows)
    low = priorities[5:12].at[2].set(0.0)
    added = arena.add(partly, nxt, low)
    written = jax.jit(arena.write_contiguous, donate_argnums=0)(partly, nxt, low)
    _rows_equal(written, added)
    assert int(written.cursor) == 12 and int(written.total_added) == 12
    assert float(written.priority[7]) == np.float32(PRIORITY_EPS)
    np.testing.assert_array_equal(np.asarray(written.meta[5:12]), PROVENANCE_ABSENT)
    np.testing.assert_array_equal(np.asarray(written.meta[:5]), 7)
    _rows_equal(arena.gather(written, jnp.arange(12)), rows)


@pytest.mark.parametrize(
    "frame, dtype, stored",
    [
        ((64, 96, 3), "uint8", (18, 32, 128)),  # 73,728 B: 18 tiles of 32 x 128
        ((64, 64, 4), "float32", (L, 16, 8, 128)),  # a step is 16 tiles of 8 x 128
        ((64, 64, 4), "bfloat16", (L, 8, 16, 128)),
        ((50, 111, 3), "uint8", (L, 50, 111, 3)),  # no whole number of tiles
        ((2, 2, 3), "uint8", (L, 2, 2, 3)),  # a small row of 48 elements
    ],
    ids=["uint8_row_tiles", "float32_step_tiles", "bfloat16_step_tiles",
         "no_whole_tiles", "small_row"],
)
def test_a_large_rows_storage_is_the_rows_elements_in_order(frame, dtype, stored):
    """A large row's storage leaf has another shape behind the slot axis and
    nothing else: the row's dtype, its element count, its elements in order,
    so ``buf.reshape(capacity, -1)`` is the rows, kept as a ``StoredRows``
    that knows the rows' own shape.  The shortest run of trailing dimensions
    that is whole tiles is stored as tiles; a row that has none, or is small
    and under one lane-row, keeps its own shape."""
    n, capacity = 3, 4
    rng = np.random.default_rng(1)
    obs = jnp.asarray(rng.integers(0, 200, (n, L) + frame)).astype(dtype)
    rows = dataclasses.replace(make_batch(n), obs=obs)
    arena = ReplayArena(capacity=capacity)
    state = arena.add(arena.init_state(rows), rows, jnp.ones(n))
    buf = _one_leaf(state.data.obs, (L,) + frame)
    assert buf.shape == (capacity,) + stored
    assert buf.dtype == obs.dtype
    np.testing.assert_array_equal(
        _bits(buf.reshape(capacity, -1)[:n]), _bits(obs.reshape(n, -1)))
    # Every other leaf is small here, under one lane-row, and keeps the
    # row's shape.
    assert state.data.action.shape == (capacity, L, ACT)
    _rows_equal(arena.gather(state, jnp.arange(n)), rows)


def _one_leaf(field, row):
    """The one leaf a field is stored in: the ``StoredRows``' single part,
    which knows the rows' shape, or the field itself in the rows' shape."""
    if isinstance(field, StoredRows):
        assert field.row == row and len(field.parts) == 1
        return field.parts[0]
    assert field.shape[1:] == row
    return field


def test_sample_under_jit_with_donated_state_returns_rows_in_their_own_shapes():
    """The learner call's pattern: the state donated into a jitted program
    that samples and hands the state back."""
    B = 4
    rows, _ = _mixed_rows(12, (64, 96, 3))
    arena = ReplayArena(capacity=16)
    state = _mixed_state(arena, frame=(64, 96, 3))

    def program(s, k):
        return s, arena.sample(s, k, B)

    state, res = jax.jit(program, donate_argnums=0)(state, jax.random.PRNGKey(2))
    assert res.batch.obs.shape == (B, L, 64, 96, 3) and res.batch.obs.dtype == jnp.uint8
    _rows_equal(res.batch, jax.tree_util.tree_map(lambda x: x[res.indices], rows))
    _rows_equal(res.batch, arena.gather(state, res.indices))


def test_dp_sharded_arena_shards_a_large_rows_storage_over_its_slots():
    """``P(DP_AXIS)`` on axis 0 shards the tiled leaf as it shards the others,
    and the capacity-sharded arena samples the rows added."""
    from jax.sharding import PartitionSpec as P

    from r2d2dpg_tpu.parallel import make_mesh
    from r2d2dpg_tpu.parallel.mesh import DP_AXIS

    rows, priorities = _mixed_rows(12, (64, 96, 3))
    arena = ReplayArena(capacity=16, use_pallas=False)
    state = _dp_arena_state(arena, rows, priorities, make_mesh(2))
    tiles = _one_leaf(state.data.obs, (L, 64, 96, 3))
    assert tiles.shape == (16, 18, 32, 128)
    assert tiles.sharding.spec == P(DP_AXIS)
    assert {s.data.shape for s in tiles.addressable_shards} == {(8, 18, 32, 128)}
    res = jax.jit(arena.sample, static_argnums=2)(state, jax.random.PRNGKey(4), 6)
    want = jax.tree_util.tree_map(lambda x: x[res.indices], rows)
    np.testing.assert_array_equal(np.asarray(res.batch.obs), np.asarray(want.obs))
    # The float leaves come back through the partitioner's sum over shards,
    # which keeps values and not every bit pattern (a subnormal goes to 0).
    for g, w in zip(jax.tree_util.tree_leaves(res.batch), jax.tree_util.tree_leaves(want)):
        assert g.shape == w.shape and g.dtype == w.dtype
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), atol=1e-30)


# ------------------ small rows as their whole lane-rows and the rest (PR 40)
# Row shapes of the benchmark's configurations: walker's observation and
# action (43 x 24, 43 x 6) and its [43] leaves, the whole-sequence cells'
# (85 x 67, 85 x 21), and cheetah's pixels beside its actions (a large row,
# stored as tiles).  A carry of 256 is whole lane-rows already.
ROW_SETS = {
    "walker_rows": (43, (24,), "float32", 6),
    "whole_sequence_rows": (85, (67,), "float32", 21),
    "pixel_rows": (45, (64, 64, 3), "uint8", 6),
}


def _odd_rows(n, steps, frame, frame_dtype, act):
    """``n`` rows at these shapes, the float leaves with bit patterns a
    rounding would lose, ``reset`` a ``bool`` leaf."""
    rng = np.random.default_rng(7)

    def floats(*shape):
        x = rng.standard_normal(shape).astype(np.float32)
        x.flat[0] = np.array(0x7FC00123, np.uint32).view(np.float32)  # NaN payload
        x.flat[-1] = np.float32(-0.0)
        return jnp.asarray(x)

    obs = (jnp.asarray(rng.integers(0, 256, (n, steps) + frame, dtype=np.uint8))
           if frame_dtype == "uint8" else floats(n, steps, *frame))
    batch = SequenceBatch(
        obs=obs, action=floats(n, steps, act), reward=floats(n, steps),
        discount=floats(n, steps),
        reset=jnp.asarray(rng.random((n, steps)) < 0.3),
        carries={"actor": (floats(n, 256), floats(n, 256)),
                 "critic": (floats(n, 256), floats(n, 256))},
    )
    return batch, jnp.asarray(rng.random(n) + 0.1, jnp.float32)


@pytest.mark.parametrize("entry", ["add", "add_staged", "write_contiguous"])
@pytest.mark.parametrize("rows_at", list(ROW_SETS), ids=list(ROW_SETS))
def test_small_rows_go_in_and_come_out_bit_for_bit(rows_at, entry):
    """A small row of n elements is stored as its first ``128 * (n // 128)``
    elements flat and the other ``n % 128`` beside them, in its own dtype
    and in order; one of whole lane-rows is only flattened (a carry of 256
    keeps its shape), one under 128 elements keeps its shape, a large one is
    tiles.  ``gather`` and ``sample`` give every row back bit for bit in its
    own shape and dtype, whichever entry wrote it."""
    from r2d2dpg_tpu.replay import StagedSequences

    steps, frame, frame_dtype, act = ROW_SETS[rows_at]
    n, capacity = 5, 8
    rows, priorities = _odd_rows(n, steps, frame, frame_dtype, act)
    arena = ReplayArena(capacity=capacity)
    state = arena.init_state(rows)
    if entry == "add":
        state = arena.add(state, rows, priorities)
    elif entry == "add_staged":
        state = arena.add_staged(state, StagedSequences(seq=rows, priorities=priorities))
    else:
        state = jax.jit(arena.write_contiguous, donate_argnums=0)(state, rows, priorities)

    fields = jax.tree_util.tree_leaves(state.data, is_leaf=lambda x: isinstance(x, StoredRows))
    for field, row in zip(fields, jax.tree_util.tree_leaves(rows)):
        elements = math.prod(row.shape[1:])
        whole, rest = divmod(elements, 128)
        parts = field.parts if isinstance(field, StoredRows) else (field,)
        if isinstance(field, StoredRows):
            assert field.row == row.shape[1:]
        if elements >= 1 << 16:  # the pixels: a step's frame as 3 tiles
            assert [p.shape for p in parts] == [(capacity, steps, 3, 32, 128)]
        elif not whole or row.ndim == 2 and not rest:
            assert parts == (field,) and field.shape == (capacity,) + row.shape[1:]
        else:
            want = [(capacity, 128 * whole)] + [(capacity, rest)] * bool(rest)
            assert [p.shape for p in parts] == want
        assert all(p.dtype == row.dtype for p in parts)
        flat = jnp.concatenate([p.reshape(capacity, -1) for p in parts], axis=1)
        np.testing.assert_array_equal(_bits(flat[:n]), _bits(row.reshape(n, -1)))

    order = jnp.asarray([4, 0, 3, 3, 1])
    _rows_equal(arena.gather(state, order),
                jax.tree_util.tree_map(lambda x: x[order], rows))
    res = jax.jit(lambda s, k: arena.sample(s, k, 6))(state, jax.random.PRNGKey(9))
    assert int(np.asarray(res.indices).max()) < n
    _rows_equal(res.batch, jax.tree_util.tree_map(lambda x: x[res.indices], rows))


@pytest.mark.parametrize("rows_at", list(ROW_SETS), ids=list(ROW_SETS))
def test_the_arena_stores_each_rows_bytes_and_no_more(rows_at):
    """A slot's leaves hold its row's bytes, whatever shapes they are
    stored in: walker's rows are 9,772 B a slot, and the arena adds its
    float32 priority and two int32 stamps."""
    steps, frame, frame_dtype, act = ROW_SETS[rows_at]
    rows, _ = _odd_rows(1, steps, frame, frame_dtype, act)
    capacity = 16
    state = jax.eval_shape(ReplayArena(capacity=capacity).init_state, rows)
    stored = sum(x.size * x.dtype.itemsize for x in jax.tree_util.tree_leaves(state)
                 if x.shape[:1] == (capacity,))
    row = sum(x.size * x.dtype.itemsize for x in jax.tree_util.tree_leaves(rows))
    assert stored == capacity * (row + 4 + 8)
    if rows_at == "walker_rows":
        assert row == 43 * (24 + 6 + 2) * 4 + 43 + 4 * 256 * 4 == 9772 - 3 * 43


def test_an_arena_reads_the_rows_of_a_state_another_arena_made():
    """The rows' own shapes are part of the state's structure
    (``StoredRows.row``, static): an arena that never ran ``init_state``
    gathers and samples the state another arena made, each row in its own
    shape."""
    rows, priorities = _odd_rows(4, 37, (10,), "float32", 5)
    first = ReplayArena(capacity=8)
    state = first.add(first.init_state(rows), rows, priorities)
    assert isinstance(state.data.obs, StoredRows) and state.data.obs.row == (37, 10)
    twin = ReplayArena(capacity=8)
    _rows_equal(twin.gather(state, jnp.arange(4)), rows)
    res = jax.jit(lambda s, k: twin.sample(s, k, 6))(state, jax.random.PRNGKey(3))
    _rows_equal(res.batch, jax.tree_util.tree_map(lambda x: x[res.indices], rows))
