"""DM-Control environments as a host-callback pool (SURVEY.md §7 step 5b).

No MJX ships in this image, so MuJoCo physics cannot run on-device; the
TPU-native compromise keeps *everything else* in the jitted program and
crosses to host only for the physics step: a pool of ``dm_control`` envs
steps in a thread pool (MuJoCo releases the GIL during ``mj_step``), exposed
to JAX through an **ordered ``io_callback``** so the whole actor phase stays
inside ``lax.scan`` (SURVEY §3.2's hot loop, with the env.step row replaced
by one batched host call).

This is the moral equivalent of the reference's N actor processes stepping
gym/dm_control on CPU (SURVEY §2.3) — except the policy forward, noise,
sequence assembly, replay and learner never leave the device, and the host
boundary moves exactly one obs/action batch per step.

Contract notes:
- Batched: implements the ``batched = True`` env API (``reset(key, n)``,
  ``step(state, actions, key)`` over ``[E, ...]``); the trainer skips vmap.
- Ordering: the callback is ``ordered=True`` — host env state is mutable, so
  calls must execute in program order.  This is incompatible with vmap /
  shard_map; the SPMD trainer rejects batched host envs (multi-chip scaling
  of host-backed envs needs one pool per host — a later milestone, tracked
  in docs/PARITY.md).
- Auto-reset: on ``dm_ts.last()`` the pool resets that env and returns the
  fresh obs with ``reset=1``; ``discount`` keeps dm_control's semantics
  (0 only on true termination, 1 on time-limit truncation), which is
  exactly what ``ops.returns.n_step_targets`` expects.
- Pixels (BASELINE config #5): 64x64x3 uint8 via MuJoCo's EGL headless
  renderer (``MUJOCO_GL=egl`` — set automatically; osmesa/glfw are broken in
  this image).  Physics steps run in threads; renders run concurrently on a
  pool of render threads with each env pinned to one thread (EGL contexts
  are one-thread-at-a-time; pinning keeps them from migrating).
"""

from __future__ import annotations

import atexit
import dataclasses
import functools
import threading
import time
import warnings
from concurrent.futures import ThreadPoolExecutor
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import io_callback

from r2d2dpg_tpu.envs.core import EnvSpec, TimeStep
from r2d2dpg_tpu.envs.native_pool import PoolObsMixin
from r2d2dpg_tpu.obs import flight_event

_PIXEL_HW = 64


def _load_dmc(domain: str, task: str, seed: int):
    from dm_control import suite

    return suite.load(domain, task, task_kwargs={"random": seed})


def _flatten_obs(obs_dict) -> np.ndarray:
    parts = [np.asarray(v, np.float32).reshape(-1) for v in obs_dict.values()]
    return np.concatenate(parts) if parts else np.zeros((0,), np.float32)


class _HostPool(PoolObsMixin):
    """The host-side fleet: E dm_control envs + a thread pool."""

    # Render thread-pool width.  Each env is PINNED to one render thread
    # (env i -> thread i mod K) so its EGL context never migrates threads —
    # contexts are current-on-one-thread-at-a-time, and dm_control creates
    # them lazily on first render.  K renders proceed concurrently (MuJoCo
    # releases the GIL during mjr render calls), so pixel throughput scales
    # with host cores instead of serializing on one thread (VERDICT r1 weak
    # #5); on a 1-core host this degrades gracefully to the serial rate.
    RENDER_THREADS = 8

    def __init__(self, domain: str, task: str, pixels: bool, camera_id: int):
        self.domain, self.task = domain, task
        self.pixels = pixels
        self.camera_id = camera_id
        self.envs: list = []
        self.executor: Optional[ThreadPoolExecutor] = None
        self.render_threads: list = []
        self._atexit_registered = False
        # Host env state is mutable: with the pipelined executor the pool is
        # driven from a collector thread (directly, or via the io_callback
        # thread the collect program's ordered callback runs on) while other
        # code may still reach it — serialize whole-fleet transitions.
        self._step_lock = threading.Lock()
        self._init_pool_obs()  # lazy role-labelled instruments (PoolObsMixin)

    def ensure(self, seeds: np.ndarray):
        """Create or re-seed the fleet to match the per-env ``seeds``."""
        num_envs = len(seeds)
        if len(self.envs) != num_envs:
            if self.envs and self.pixels:
                # Resize: free the outgoing fleet's EGL contexts on their
                # pinned threads and shut those executors down before the
                # new fleet replaces them (otherwise both leak, and exit-time
                # cleanup would double-free).
                self._free_render_contexts()
                for t in self.render_threads:
                    t.shutdown(wait=False)
            if self.executor is not None:
                self.executor.shutdown(wait=False)
            self.envs = [
                _load_dmc(self.domain, self.task, int(s)) for s in seeds
            ]
            self.executor = ThreadPoolExecutor(
                max_workers=min(32, max(1, num_envs))
            )
            if self.pixels:
                self.render_threads = [
                    ThreadPoolExecutor(max_workers=1)
                    for _ in range(min(self.RENDER_THREADS, num_envs))
                ]
                # Free EGL contexts from the thread they are current on;
                # dm_control's own atexit hook would EGL_BAD_ACCESS otherwise.
                if not self._atexit_registered:
                    atexit.register(self._free_render_contexts)
                    self._atexit_registered = True
        else:
            # Explicit re-reset: honor the new seeds on the existing fleet.
            for env, s in zip(self.envs, seeds):
                env.task._random = np.random.RandomState(int(s))

    def _free_render_contexts(self, total_timeout: float = 10.0):
        import time as _time

        def _free(lo):
            for i in range(lo, len(self.envs), len(self.render_threads)):
                try:
                    self.envs[i].physics.free()
                except Exception:
                    pass

        deadline = _time.monotonic() + total_timeout  # bound across ALL threads
        futs = []
        for k, t in enumerate(self.render_threads):
            try:
                # At atexit time CPython has already joined executor threads;
                # submit() then raises — swallow it (same as the old code)
                # rather than aborting the whole cleanup loop.
                futs.append(t.submit(_free, k))
            except Exception:
                pass
        for f in futs:
            try:
                f.result(timeout=max(0.0, deadline - _time.monotonic()))
            except Exception:
                pass

    def _render_all(self) -> np.ndarray:
        """Render every env, each on its pinned thread, concurrently."""
        futs = [
            self.render_threads[i % len(self.render_threads)].submit(
                env.physics.render,
                height=_PIXEL_HW,
                width=_PIXEL_HW,
                camera_id=self.camera_id,
            )
            for i, env in enumerate(self.envs)
        ]
        return np.stack([f.result() for f in futs])

    def _obs_all(self, dm_steps) -> np.ndarray:
        if self.pixels:
            return self._render_all()
        return np.stack([_flatten_obs(ts.observation) for ts in dm_steps])

    def reset_all(self, seeds: np.ndarray):
        with self._step_lock:
            self.ensure(seeds)
            dm_steps = [env.reset() for env in self.envs]
            obs = self._obs_all(dm_steps)
            e = len(self.envs)
            return (
                obs,
                np.zeros((e,), np.float32),
                np.ones((e,), np.float32),
                np.ones((e,), np.float32),
            )

    def step_all(self, actions: np.ndarray, repeat: int = 1):
        if repeat < 1:
            raise ValueError(f"repeat must be >= 1, got {repeat}")
        t_lock = time.monotonic()
        if self._obs_step is None:
            self._bind_pool_obs()
        with self._step_lock:
            t0 = time.monotonic()
            self._obs_lock_wait.add(t0 - t_lock)
            out = self._step_all_locked(actions, repeat)
            self._obs_step.add(time.monotonic() - t0)
            self._obs_resets.inc(float(out[3].sum()))
            return out

    def _step_all_locked(self, actions: np.ndarray, repeat: int):

        def step_one(i):
            env = self.envs[i]
            # Action repeat: same control for `repeat` dm steps, rewards
            # summed, stopping at the episode boundary (wrapper convention —
            # keeps the suite's 0..1000 episode-return scale).
            reward = np.float32(0.0)
            discount = np.float32(1.0)
            for _ in range(repeat):
                dm_ts = env.step(actions[i])
                reward += np.float32(dm_ts.reward or 0.0)
                discount *= np.float32(
                    1.0 if dm_ts.discount is None else dm_ts.discount
                )
                if dm_ts.last():
                    fresh = env.reset()
                    return fresh, reward, discount, np.float32(1.0)
            return dm_ts, reward, discount, np.float32(0.0)

        results = list(self.executor.map(step_one, range(len(self.envs))))
        # Renders (pixels): concurrent across the pinned render threads.
        obs = self._obs_all([r[0] for r in results])
        reward = np.stack([r[1] for r in results])
        discount = np.stack([r[2] for r in results])
        reset = np.stack([r[3] for r in results])
        return obs, reward, discount, reset


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class DMCState:
    """Device-side token; the host pool owns the real state.  The token is
    threaded through every callback to give XLA a data dependency chain."""

    token: jnp.ndarray


class DMCHostEnv:
    """Batched functional facade over a host dm_control pool."""

    batched = True

    # action/obs specs per (domain, task) we ship configs for; measured once
    # at construction from a probe env.
    def __init__(
        self,
        domain: str,
        task: str,
        *,
        pixels: bool = False,
        camera_id: int = 0,
        native: Optional[bool] = None,
        action_repeat: int = 1,
    ):
        """``native``: use the C++ batched pool (native/envpool) when the
        task supports it — True forces it, False forces the Python pool,
        None (default) auto-selects.  State obs only; pixels always use the
        Python pool (rendering needs dm_control's EGL path).

        ``action_repeat``: apply each policy action for this many control
        steps (rewards summed, boundary-safe) — the standard DM-Control
        benchmark wrapper.  On TPU it also divides the host-callback count
        per collected agent step by the repeat factor."""
        if action_repeat < 1:
            raise ValueError(f"action_repeat must be >= 1, got {action_repeat}")
        self.action_repeat = action_repeat
        # MUJOCO_GL=egl is pinned in r2d2dpg_tpu.envs.__init__ (dm_control
        # picks its GL backend at first import, which any entry point may
        # trigger before a pixels env exists).
        probe = _load_dmc(domain, task, 0)
        action_spec = probe.action_spec()
        self._act_min = np.asarray(action_spec.minimum, np.float32)
        self._act_max = np.asarray(action_spec.maximum, np.float32)
        ts0 = probe.reset()
        if pixels:
            obs_shape: Tuple[int, ...] = (_PIXEL_HW, _PIXEL_HW, 3)
            self._obs_dtype = jnp.uint8
        else:
            obs_shape = _flatten_obs(ts0.observation).shape
            self._obs_dtype = jnp.float32
        limit = getattr(probe, "_step_limit", 1000)
        limit = int(limit) if np.isfinite(limit) else 1000
        self.spec = EnvSpec(
            name=f"{domain}-{task}" + ("-pixels" if pixels else ""),
            obs_shape=obs_shape,
            action_dim=int(np.prod(action_spec.shape)),
            action_min=float(self._act_min.min()),
            action_max=float(self._act_max.max()),
            # Agent-visible horizon: control steps / action_repeat.
            episode_length=-(-limit // action_repeat),
            pixels=pixels,
        )
        probe.close()
        from r2d2dpg_tpu.envs import native_pool

        use_native = (
            native_pool.is_supported(domain, task, pixels)
            if native is None
            else native
        )
        if use_native:
            if not native_pool.is_supported(domain, task, pixels):
                raise ValueError(
                    f"native pool does not support {domain}-{task}"
                    f"{' (pixels)' if pixels else ''}"
                )
            try:
                self._pool = native_pool.NativeEnvPool(domain, task)
            except (OSError, RuntimeError, AttributeError) as e:
                # make/g++ missing or failing, or a library that will not load.
                if native:  # explicitly requested: surface the build error
                    raise
                # Auto-select carries on with the Python pool, which is
                # several times slower: never silently (warnings print a
                # given message once; the flight ring keeps every one).
                reason = f"{type(e).__name__}: {e}"
                warnings.warn(
                    f"native env pool unavailable for {domain}-{task}; "
                    f"using the slower Python pool: {reason}",
                    RuntimeWarning,
                    stacklevel=2,
                )
                flight_event(
                    "env_native_fallback",
                    env=f"{domain}-{task}",
                    error=reason[-2000:],
                )
                use_native = False
                self._pool = _HostPool(domain, task, pixels, camera_id)
        else:
            self._pool = _HostPool(domain, task, pixels, camera_id)
        self.native = use_native

    def set_role(self, role: str) -> None:
        """Label this env's pool metrics by purpose (train|eval|actor)."""
        self._pool.set_role(role)

    # ------------------------------------------------------------- callbacks
    def _result_shapes(self, e: int):
        return (
            jax.ShapeDtypeStruct((e,) + self.spec.obs_shape, self._obs_dtype),
            jax.ShapeDtypeStruct((e,), jnp.float32),
            jax.ShapeDtypeStruct((e,), jnp.float32),
            jax.ShapeDtypeStruct((e,), jnp.float32),
        )

    def reset(self, key: jax.Array, num_envs: int) -> Tuple[DMCState, TimeStep]:
        seeds = jax.random.randint(key, (num_envs,), 0, 2**31 - 1)
        obs, reward, discount, reset = io_callback(
            self._pool.reset_all,
            self._result_shapes(num_envs),
            seeds,
            ordered=True,
        )
        ts = TimeStep(obs=obs, reward=reward, discount=discount, reset=reset)
        return DMCState(token=jnp.zeros((), jnp.int32)), ts

    def step(
        self, state: DMCState, actions: jnp.ndarray, key: jax.Array
    ) -> Tuple[DMCState, TimeStep]:
        del key  # host envs own their randomness (seeded at creation)
        lo, hi = jnp.asarray(self._act_min), jnp.asarray(self._act_max)
        scaled = lo + (jnp.clip(actions, -1.0, 1.0) + 1.0) * 0.5 * (hi - lo)
        # The token rides along so successive steps form a dependency chain.
        scaled = scaled + 0.0 * state.token.astype(scaled.dtype)
        e = actions.shape[0]
        obs, reward, discount, reset = io_callback(
            functools.partial(self._pool.step_all, repeat=self.action_repeat),
            self._result_shapes(e),
            scaled,
            ordered=True,
        )
        ts = TimeStep(obs=obs, reward=reward, discount=discount, reset=reset)
        return DMCState(token=state.token + 1), ts

    # ------------------------------------------------- host-level API (SPMD)
    # The hybrid multi-chip trainer steps the pool from Python between jitted
    # device calls (ordered io_callback cannot run inside shard_map/pjit-
    # sharded graphs); resets still go through ``reset`` above (eager
    # io_callback outside jit), so only the step needs a numpy twin.
    def host_step(self, actions: np.ndarray):
        """numpy step: canonical [-1,1] actions -> (obs, reward, discount, reset)."""
        lo, hi = self._act_min, self._act_max
        scaled = lo + (np.clip(actions, -1.0, 1.0) + 1.0) * 0.5 * (hi - lo)
        return self._pool.step_all(
            scaled.astype(np.float32), repeat=self.action_repeat
        )
