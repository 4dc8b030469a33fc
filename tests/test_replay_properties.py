"""Property tests for replay invariants under load (SURVEY.md §4.5).

Hypothesis drives random op sequences (add batches of varying size, priority
write-backs at random indices) against a small arena and checks the ring /
priority-mass invariants a CPU sum-tree implementation would keep:

- size == min(total_added, capacity), cursor == total_added % capacity;
- the set of resident sequences is exactly the last `capacity` adds (FIFO);
- every resident slot's priority is the max(eps, value) of the *latest* write
  touching it; empty slots stay at exactly 0 (so they can never be sampled);
- sampled indices always land on resident slots.
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

# The property tests are hypothesis-driven; on boxes without it the module
# must still COLLECT cleanly (skip, not error) so tier-1's collection pass
# stays green.  pip-installing into the serving image is not an option.
hypothesis = pytest.importorskip(
    "hypothesis", reason="property tests need the hypothesis package"
)
from hypothesis import given, settings, strategies as st  # noqa: E402

from r2d2dpg_tpu.ops.priority import PRIORITY_EPS
from r2d2dpg_tpu.replay import ReplayArena, SequenceBatch

CAPACITY = 7
L = 2


def make_batch(values):
    b = len(values)
    v = jnp.asarray(values, jnp.float32)
    return SequenceBatch(
        obs=jnp.broadcast_to(v[:, None, None], (b, L, 1)),
        action=jnp.zeros((b, L, 1)),
        reward=jnp.zeros((b, L)),
        discount=jnp.ones((b, L)),
        reset=jnp.zeros((b, L)),
        carries={"actor": (), "critic": ()},
    )


ops = st.lists(
    st.one_of(
        st.tuples(
            st.just("add"),
            st.lists(
                st.floats(0.01, 10.0), min_size=1, max_size=CAPACITY - 1
            ),
        ),
        st.tuples(
            st.just("update"),
            st.lists(
                st.tuples(
                    st.integers(0, CAPACITY - 1), st.floats(0.0, 10.0)
                ),
                min_size=1,
                max_size=4,
            ),
        ),
    ),
    min_size=1,
    max_size=12,
)


@settings(max_examples=40, deadline=None)
@given(ops=ops, seed=st.integers(0, 2**31 - 1))
def test_ring_and_priority_invariants(ops, seed):
    arena = ReplayArena(capacity=CAPACITY, alpha=1.0)
    state = arena.init_state(make_batch([0.0]))

    # Host-side model: list of (add_id, latest_priority) per slot.
    model = {}  # slot -> (add_id, prio)
    next_id = 0

    for kind, payload in ops:
        if kind == "add":
            prios = payload
            vals = [float(next_id + i) for i in range(len(prios))]
            state = arena.add(state, make_batch(vals), jnp.asarray(prios))
            for i, p in enumerate(prios):
                slot = (next_id + i) % CAPACITY
                model[slot] = (next_id + i, max(p, PRIORITY_EPS))
            next_id += len(prios)
        else:
            # Priority write-back only touches resident slots (the learner
            # writes back indices it sampled, which are always resident).
            # Dedupe to one write per slot — with duplicate indices the
            # scatter's winner is implementation-defined.
            pairs = list({s: (s, p) for s, p in payload if s in model}.values())
            if not pairs:
                continue
            idx = jnp.asarray([s for s, _ in pairs], jnp.int32)
            pr = jnp.asarray([p for _, p in pairs], jnp.float32)
            state = arena.update_priorities(state, idx, pr)
            for s, p in pairs:
                model[s] = (model[s][0], max(p, PRIORITY_EPS))

    # --- ring bookkeeping.
    assert int(state.total_added) == next_id
    assert int(arena.size(state)) == min(next_id, CAPACITY)
    assert int(state.cursor) == next_id % CAPACITY

    # --- FIFO residency: slot k holds the latest add whose id % C == k.
    prio = np.asarray(state.priority)
    obs = np.asarray(arena.gather(state, jnp.arange(CAPACITY)).obs)[:, 0, 0]
    for slot in range(CAPACITY):
        if slot in model:
            add_id, want_prio = model[slot]
            assert obs[slot] == float(add_id)
            np.testing.assert_allclose(prio[slot], want_prio, rtol=1e-5)
        else:
            assert prio[slot] == 0.0  # empty slots stay exactly 0

    # --- priority mass: total == sum over the model's resident slots.
    want_mass = sum(p for _, p in model.values())
    np.testing.assert_allclose(prio.sum(), want_mass, rtol=1e-4)

    # --- sampling never touches empty slots.
    if model:
        res = arena.sample(state, jax.random.PRNGKey(seed), 64)
        assert all(int(i) in model for i in np.asarray(res.indices))
