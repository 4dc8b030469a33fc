"""Summarize a training run's metrics.csv as markdown tables.

Usage: python scripts/summarize_run.py <logdir> [--every N]

Prints:
- a curve table (wall min, env steps, eval return) from the deterministic
  eval rows (falls back to noisy actor returns when no evals were logged);
- the run's final throughput (env/learner steps/sec) and totals.

Pure stdlib — safe to run next to a live training process (no JAX import).
"""

from __future__ import annotations

import argparse
import csv
import os
import sys


def load(logdir: str) -> list:
    path = os.path.join(logdir, "metrics.csv")
    with open(path, newline="") as f:
        return [r for r in csv.DictReader(f)]


def fget(row: dict, key: str):
    v = row.get(key, "")
    try:
        return float(v)
    except (TypeError, ValueError):
        return None


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("logdir")
    ap.add_argument("--every", type=int, default=1, help="keep every Nth curve row")
    args = ap.parse_args()
    args.every = max(1, args.every)

    rows = load(args.logdir)
    if not rows:
        sys.exit(f"no rows in {args.logdir}/metrics.csv")

    # Eval rows from runs predating train.py's env_steps stamp carry only
    # eval_* scalars; fill env_steps forward from the most recent training
    # row, marking filled values "~N" so approximations are visible in the
    # table (ADVICE r2 #4).
    last_steps = 0.0
    filled = set()
    for i, r in enumerate(rows):
        v = fget(r, "env_steps")
        if v is not None:
            last_steps = v
        else:
            r["env_steps"] = last_steps
            filled.add(i)

    ret_key = "eval_return_mean"
    curve = [r for r in rows if fget(r, ret_key) is not None]
    if not curve:
        ret_key = "episode_return_mean"
        curve = [
            r
            for r in rows
            if fget(r, ret_key) is not None and (fget(r, "episodes") or 0) > 0
        ]
    label = (
        "eval return (deterministic)"
        if ret_key == "eval_return_mean"
        else "actor return (noisy)"
    )

    kept = curve[:: args.every]
    if curve and curve[-1] is not kept[-1]:
        kept.append(curve[-1])

    idx = {id(r): i for i, r in enumerate(rows)}
    print(f"### {args.logdir} — {len(rows)} log rows\n")
    print(f"| wall min | env steps | {label} |")
    print("|---|---|---|")
    for r in kept:
        mins = (fget(r, "wall_seconds") or 0) / 60
        steps = fget(r, "env_steps") or 0
        approx = "~" if idx[id(r)] in filled else ""
        print(f"| {mins:.0f} | {approx}{steps:,.0f} | {fget(r, ret_key):.1f} |")
    if any(idx[id(r)] in filled for r in kept):
        print(
            "\n(~N = env steps forward-filled from the last training row — "
            "pre-stamp run)"
        )

    if curve:
        # curve rows are pre-filtered to numeric returns — no None guard.
        best = max(curve, key=lambda r: fget(r, ret_key))
        print(
            f"\nbest: {fget(best, ret_key):.1f} at "
            f"{(fget(best, 'wall_seconds') or 0) / 60:.0f} min / "
            f"{fget(best, 'env_steps') or 0:,.0f} steps"
        )

    last = rows[-1]
    bits = []
    for k in ("env_steps_per_sec", "learner_steps_per_sec"):
        vals = [fget(r, k) for r in rows if fget(r, k) is not None]
        if vals:
            tail = vals[-5:]
            bits.append(f"{k} (last-5 mean) {sum(tail) / len(tail):,.1f}")
    total_min = (fget(last, "wall_seconds") or 0) / 60
    print(
        f"\nfinal: {total_min:.0f} min, {fget(last, 'env_steps') or 0:,.0f} env "
        f"steps, phase {last.get('step')}" + ("; " + "; ".join(bits) if bits else "")
    )


if __name__ == "__main__":
    main()
