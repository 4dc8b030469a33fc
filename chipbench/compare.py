"""The arithmetic of ``correct``: gaps between what the timed path produced
and what the plain reference gives for the same inputs.

Nothing here knows a limit: a driver hands the numbers computed here to the
harness beside the limits of its cell's workload file.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from chipbench import reference


def leaf_norms(tree: Any) -> Dict[str, float]:
    """L2 norm of every leaf of a nested dict/tuple tree, keyed by its path."""
    out: Dict[str, float] = {}

    def walk(node, path):
        if isinstance(node, dict):
            for k in sorted(node):
                walk(node[k], path + (str(k),))
        elif isinstance(node, (tuple, list)):
            for i, v in enumerate(node):
                walk(v, path + (str(i),))
        else:
            a = np.asarray(node, np.float64)
            out["/".join(path)] = float(np.sqrt(np.sum(a * a)))

    walk(tree, ())
    return out


def tree_sub(a: Any, b: Any) -> Any:
    if isinstance(a, dict):
        return {k: tree_sub(a[k], b[k]) for k in a}
    if isinstance(a, (tuple, list)):
        return [tree_sub(x, y) for x, y in zip(a, b)]
    return np.asarray(a, np.float64) - np.asarray(b, np.float64)


def worst_leaf_gap(
    program: Dict[str, float], ref: Dict[str, float], skip: Sequence[str] = ()
) -> Tuple[float, str]:
    """The widest gap between the program's norm of a leaf and the
    reference's, against the reference's norm of that leaf or of the median
    leaf, whichever is larger.  Returns (gap, leaf)."""
    keys = [k for k in ref if k not in skip]
    if set(program) != set(ref):
        raise ValueError("program and reference trees differ in their leaves")
    median = float(np.median([ref[k] for k in keys])) if keys else 0.0
    worst, where = 0.0, ""
    for k in keys:
        scale = max(ref[k], median, 1e-30)
        gap = abs(program[k] - ref[k]) / scale
        if not gap <= worst:  # NaN wins
            worst, where = gap, k
    return float(worst), where


def dead_leaves(ref_grad_norms: Dict[str, float]) -> List[str]:
    """Leaves whose reference gradient is under a thousandth of the median
    leaf's: under Adam they move by round-off alone and are left out of the
    parameters' change."""
    median = float(np.median(list(ref_grad_norms.values())))
    return [k for k, v in ref_grad_norms.items() if v < 1e-3 * median]


def rel_gap(program: float, ref: float, floor: float = 0.0) -> float:
    return abs(float(program) - float(ref)) / max(abs(float(ref)), floor, 1e-30)


# ----------------------------------------------------------------- sampling
def mass_gaps(cdf: np.ndarray, width: float, u: np.ndarray, slots: np.ndarray):
    """``[len(u), len(slots)]``: how far draw ``u`` (in mass units) lies
    outside slot ``s``'s interval ``[cdf[s-1], cdf[s])``, as a number of
    ``width``s (the mean width of the slots that carry mass); 0 where it
    lies inside."""
    hi = cdf[slots]
    lo = np.where(slots > 0, cdf[np.maximum(slots - 1, 0)], 0.0)
    u = np.asarray(u, np.float64)[:, None]
    out = np.maximum(lo[None, :] - u, 0.0) + np.maximum(u - hi[None, :], 0.0)
    return out / width


def assign_draws(
    base: np.ndarray,
    after: np.ndarray,
    changed: np.ndarray,
    u01: Sequence[np.ndarray],
    alpha: float,
    near: float,
    must_cover: Optional[np.ndarray] = None,
    open_slots: Optional[np.ndarray] = None,
) -> Dict[str, Any]:
    """Which slot each draw of one timed call took.

    The call made ``len(u01)`` updates; update ``k`` drew ``u01[k]`` against
    the priorities as updates ``< k`` left them.  The timed program hands
    back only the priority vector, so the slots it sampled are among
    ``changed``, the slots whose priority the call changed.  ``base`` is the
    priority vector the call drew against (the program's own before the
    call: the reference's differs from it by rounding in every slot, which
    over thousands of slots shifts the CDF by more than a draw's distance
    from its slot's edge): the float64 CDF is built from it, with the
    program's new value (``after``) laid over each slot once an earlier
    update of the call has been given it.  Only the slots are decided here;
    the probabilities the reference weighs its rows with come from its own
    priority vector (``follow.learner_call``).  Each draw is given
    the changed slot that its inverse-CDF point lies in or nearest to; a
    changed slot left without a draw takes the nearest draw of a slot that
    holds two, if that draw lies within ``near`` slot widths of it (two draws
    in neighbouring slots, one of them shifted by float32 rounding).
    ``must_cover`` names the changed slots that only a draw can explain (all
    of them, unless the call also wrote slots for another reason).

    ``open_slots``: where the call's first update may write back the very
    priority a slot already held (the weights have not moved since the slot
    was ranked), a sampled slot does not show as changed; the first update's
    draws then choose among these slots too, and a draw whose second choice
    lies within ``near`` is listed under ``alternatives`` as
    ``(k, j, slot)``, the nearest first, for the caller to settle.

    Returns ``slots [K, B]``, ``gap`` (the widest distance of a draw from
    its slot, in mean widths of the slots that carry mass) and
    ``draws_unplaced`` (draws for which no changed slot exists at all).
    """
    base = np.asarray(base, np.float32)
    after = np.asarray(after, np.float32)
    changed = np.asarray(changed, np.int64)
    K, B = len(u01), len(u01[0])
    if changed.size == 0:
        return {
            "slots": np.zeros((K, B), np.int64),
            "gap": float("inf"),
            "draws_unplaced": K * B,
            "alternatives": [],
        }
    current = base.copy()
    slots, gaps, tables, alternatives = [], [], [], []
    for k in range(K):
        mass = reference.scaled_mass(current, alpha)
        cdf = np.cumsum(mass)
        total = float(cdf[-1])
        cand = changed
        if k == 0 and open_slots is not None:
            cand = np.union1d(changed, np.asarray(open_slots, np.int64))
        width = total / max(int(np.count_nonzero(mass)), 1)
        g = mass_gaps(cdf, width, np.asarray(u01[k], np.float64) * total, cand)
        pick = np.argmin(g, axis=1)
        slots.append(cand[pick])
        gaps.append(g[np.arange(B), pick])
        if cand is not changed:
            for j in range(B):
                second = np.argsort(g[j])[1] if len(cand) > 1 else pick[j]
                if second != pick[j] and g[j, second] <= near:
                    alternatives.append((float(g[j, second]), k, j, int(cand[second])))
            g = g[:, np.searchsorted(cand, changed)]
        tables.append(g)
        current[slots[-1]] = after[slots[-1]]
    slots = np.stack(slots)
    gaps = np.stack(gaps)
    # Repair: a changed slot without a draw takes a near draw of a crowded slot.
    must_cover = changed if must_cover is None else np.asarray(must_cover, np.int64)
    for t in np.setdiff1d(must_cover, slots.ravel()):
        col = int(np.searchsorted(changed, t))
        counts = {s: int(c) for s, c in zip(*np.unique(slots, return_counts=True))}
        best = None
        for k in range(K):
            g = tables[k]
            for j in range(B):
                if counts[slots[k, j]] >= 2 and g[j, col] <= near:
                    if best is None or g[j, col] < best[0]:
                        best = (g[j, col], k, j)
        if best is not None:
            gap, k, j = best
            slots[k, j], gaps[k, j] = t, gap
    return {"slots": slots, "gap": float(gaps.max()), "draws_unplaced": 0,
            "alternatives": [a[1:] for a in sorted(alternatives)]}
