"""Environment-fleet throughput benchmarks.

Measures the two collection paths (SURVEY.md §3.5: actor-side time goes to
env stepping + policy forwards):

1. ``pendulum``: the fully on-device path — vmapped pure-JAX Pendulum fleet
   stepped with the LSTM policy inside one jitted ``lax.scan`` (the Anakin
   hot loop).  Reports agent steps/sec (num_envs x scan steps / wall).
2. ``walker`` / ``humanoid``: the native C++ MuJoCo pool stepped host-side
   (the hybrid / io_callback path's host half), with action repeat 2 —
   whole-pool throughput; see ``bench_native_pool`` for the per-core
   reading.
3. ``pixels``: config-#5 collection — cheetah-run with 64x64 EGL renders on
   the pinned render-thread pool, action repeat 4.

Usage: python benchmarks/env_throughput.py [num_envs] [steps] [modes]
``modes`` is a comma-separated subset of pendulum,walker,humanoid,pixels
(default: pendulum,walker,pixels).  Prints one JSON line per benchmark.
"""

from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def bench_pendulum(num_envs: int, steps: int) -> dict:
    import jax
    import jax.numpy as jnp

    from r2d2dpg_tpu.envs import Pendulum
    from r2d2dpg_tpu.models import ActorNet

    env = Pendulum()
    actor = ActorNet(action_dim=1, hidden=256, use_lstm=True)
    key = jax.random.PRNGKey(0)
    env_keys = jax.random.split(key, num_envs)
    state, ts = jax.vmap(env.reset)(env_keys)
    carry = actor.initial_carry(num_envs)
    params = actor.init(key, ts.obs, carry, ts.reset)

    @jax.jit
    def rollout(params, state, obs, reset, carry, key):
        def step(c, k):
            state, obs, reset, carry = c
            action, carry = actor.apply(params, obs, carry, reset)
            ks = jax.random.split(k, num_envs)
            state, ts = jax.vmap(env.step)(state, action, ks)
            return (state, ts.obs, ts.reset, carry), ts.reward.mean()

        c, rews = jax.lax.scan(
            step, (state, obs, reset, carry), jax.random.split(key, steps)
        )
        return c, rews.mean()

    c, _ = rollout(params, state, ts.obs, ts.reset, carry, key)  # compile
    jax.block_until_ready(c[1])
    t0 = time.perf_counter()
    c, out = rollout(params, c[0], c[1], c[2], c[3], jax.random.fold_in(key, 1))
    jax.block_until_ready(out)
    dt = time.perf_counter() - t0
    return {
        "metric": "pendulum_env_steps_per_sec",
        "value": round(num_envs * steps / dt, 1),
        "unit": "agent steps/s",
        "num_envs": num_envs,
        # Device path: a reader must be able to tell an on-chip number
        # from a CPU one.
        "backend": jax.default_backend(),
    }


def bench_native_pool(domain: str, task: str, num_envs: int, steps: int) -> dict:
    """Whole-POOL physics throughput for a native-pool task (walker and
    humanoid supported).  The pool threads over min(cores, num_envs)
    workers, so this equals the per-core ceiling only on a 1-core host;
    divide by the reported ``threads`` for per-core (the number a
    scaling estimate multiplies by host cores)."""
    import numpy as np

    from r2d2dpg_tpu.envs import native_pool

    pool = native_pool.NativeEnvPool(domain, task)
    pool.reset_all(np.arange(num_envs))
    a = np.zeros((num_envs, pool.action_dim), np.float32)
    pool.step_all(a, repeat=2)  # warm
    t0 = time.perf_counter()
    for _ in range(steps):
        pool.step_all(a, repeat=2)
    dt = time.perf_counter() - t0
    return {
        "metric": f"{domain}_native_pool_steps_per_sec",
        "value": round(num_envs * steps / dt, 1),
        "unit": "agent steps/s (repeat 2)",
        "num_envs": num_envs,
        "threads": pool.num_threads,  # resolved by the pool itself
    }


def bench_cheetah_pixels(num_envs: int, steps: int) -> dict:
    """Config-#5 collection path: threaded physics + pinned-thread renders."""
    import numpy as np

    from r2d2dpg_tpu.envs.dmc_host import DMCHostEnv, _HostPool

    env = DMCHostEnv("cheetah", "run", pixels=True, action_repeat=4)
    import jax

    _, ts = env.reset(jax.random.PRNGKey(0), num_envs)
    a = np.zeros((num_envs, env.spec.action_dim), np.float32)
    env.host_step(a)  # warm (EGL context creation per render thread)
    t0 = time.perf_counter()
    for _ in range(steps):
        env.host_step(a)
    dt = time.perf_counter() - t0
    return {
        "metric": "cheetah_pixels_env_steps_per_sec",
        "value": round(num_envs * steps / dt, 1),
        "unit": "agent steps/s (repeat 4, 64x64 render)",
        "num_envs": num_envs,
        "render_threads": min(_HostPool.RENDER_THREADS, num_envs),
    }


def main() -> None:
    num_envs = int(sys.argv[1]) if len(sys.argv) > 1 else 64
    steps = int(sys.argv[2]) if len(sys.argv) > 2 else 200
    modes = sys.argv[3].split(",") if len(sys.argv) > 3 else [
        "pendulum", "walker", "pixels"
    ]
    unknown = set(modes) - {"pendulum", "walker", "humanoid", "pixels"}
    if unknown:
        raise SystemExit(
            f"unknown mode(s) {sorted(unknown)}; pick from "
            "pendulum,walker,humanoid,pixels"
        )
    if "pendulum" in modes:
        print(json.dumps(bench_pendulum(num_envs, steps)), flush=True)
    if "walker" in modes:
        print(
            json.dumps(
                bench_native_pool("walker", "walk", num_envs, min(steps, 100))
            ),
            flush=True,
        )
    if "humanoid" in modes:
        print(
            json.dumps(
                bench_native_pool("humanoid", "run", num_envs, min(steps, 100))
            ),
            flush=True,
        )
    if "pixels" in modes:
        print(
            json.dumps(bench_cheetah_pixels(num_envs, min(steps, 50))),
            flush=True,
        )


if __name__ == "__main__":
    main()
