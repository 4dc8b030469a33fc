"""Full-phase throughput for the host-pool (DMC) training path.

VERDICT r1 weak #4: the learner-only bench overstates the system — the
north star is won or lost in the env pool.  This measures what actually
bounds wall-clock: complete ``train_phase`` rate (collect + emit + learner
updates) at walker_r2d2 shapes, in three modes:

1. ``collect``     — env stepping only (the pool ceiling).
2. ``sequential``  — classic phase: collect, then emit+learn at the end.
3. ``overlap``     — learner substeps interleaved between env steps
                     (TrainerConfig.overlap_learner): on a real TPU the
                     updates hide under the MuJoCo C step.

Prints one JSON line per row: the three modes above, plus one extra
``overlap_ls<K>`` row per requested extra density (4th argv) — on-chip
the learner is nearly free, so the question the extra rows answer is how
many interleaved updates per phase the rate sustains.  Every row is a
device rate, so the script exits non-zero unless JAX resolves a TPU, and
each row names the device it ran on.

Usage:
  python benchmarks/phase_throughput.py [num_envs] [phases] [learner_steps] \
      [extra_overlap_densities_csv]     # e.g. 64 12 48 192
"""

from __future__ import annotations

import dataclasses
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def build(num_envs: int, learner_steps: int, overlap: bool):
    import jax

    from r2d2dpg_tpu.configs import WALKER_R2D2
    from r2d2dpg_tpu.parallel import make_mesh

    cfg = dataclasses.replace(
        WALKER_R2D2,
        trainer=dataclasses.replace(
            WALKER_R2D2.trainer,
            num_envs=num_envs,
            min_replay=num_envs * 2,
            learner_steps=learner_steps,
            overlap_learner=overlap,
        ),
    )
    return cfg.build_spmd(make_mesh(len(jax.devices())))


def measure(trainer, phases: int, mode: str, device: dict) -> dict:
    import jax

    state = trainer.init()
    for _ in range(trainer.window_fill_phases):
        state = trainer.collect_phase(state)
    for _ in range(trainer.replay_fill_phases):
        state = trainer.fill_phase(state)

    step = (
        trainer.collect_phase
        if mode == "collect"
        else lambda s: trainer.train_phase(s)[0]
    )
    state = step(state)  # compile / warm
    jax.block_until_ready(state.obs)
    t0 = time.perf_counter()
    for _ in range(phases):
        state = step(state)
    jax.block_until_ready(state.train.step)
    dt = time.perf_counter() - t0

    cfg = trainer.config
    return {
        "metric": f"walker_phase_throughput_{mode}",
        "phases_per_sec": round(phases / dt, 3),
        "agent_steps_per_sec": round(phases * cfg.stride * cfg.num_envs / dt, 1),
        "learner_steps_per_sec": round(
            0 if mode == "collect" else phases * cfg.learner_steps / dt, 2
        ),
        "num_envs": cfg.num_envs,
        "stride": cfg.stride,
        "learner_steps_per_phase": cfg.learner_steps,
        "device": device,
    }


def main() -> None:
    from r2d2dpg_tpu.utils.startup import enable_compile_cache, require_tpu

    enable_compile_cache()
    device = require_tpu()
    num_envs = int(sys.argv[1]) if len(sys.argv) > 1 else 64
    phases = int(sys.argv[2]) if len(sys.argv) > 2 else 20
    learner_steps = int(sys.argv[3]) if len(sys.argv) > 3 else 4
    # Optional comma-separated EXTRA overlap densities (e.g. "192"): how
    # many interleaved updates the phase rate sustains — each extra density
    # adds one overlap row named overlap_ls<K>.
    extra_overlap = (
        [int(x) for x in sys.argv[4].split(",") if x]
        if len(sys.argv) > 4
        else []
    )

    t = build(num_envs, learner_steps, overlap=False)
    print(json.dumps(measure(t, phases, "collect", device)), flush=True)
    print(json.dumps(measure(t, phases, "sequential", device)), flush=True)
    t = build(num_envs, learner_steps, overlap=True)
    print(json.dumps(measure(t, phases, "overlap", device)), flush=True)
    for k in extra_overlap:
        t = build(num_envs, k, overlap=True)
        row = measure(t, phases, "overlap", device)
        row["metric"] = f"walker_phase_throughput_overlap_ls{k}"
        print(json.dumps(row), flush=True)


if __name__ == "__main__":
    main()
