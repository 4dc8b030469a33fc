"""Data-parallel multi-chip learner: dp-sharded replay + batch, fed by ingest.

ISSUE 9 tentpole / ROADMAP "Break the learner ceiling": a fleet's
single-chip learner is the stage every actor count waits on (seen on one
shared CPU core only; no chip number).  This trainer scales the learner side over the existing ``parallel/`` dp mesh in the
pjit layout style (annotate shardings, let GSPMD place the collectives —
the same recipe as ``HostSPMDTrainer``), while collection stays wherever
it already lives (fleet actor subprocesses under ``--actors N``, or the
in-graph collect under ``--actors 0``):

- **replay arena dp-sharded over capacity** — ``ArenaState.data`` /
  ``priority`` carry ``P(DP_AXIS)`` on axis 0, so replay capacity grows
  past one chip's HBM and the sample gather's bandwidth scales with the
  mesh (each shard gathers its rows; Accelerated Methods, PAPERS.md
  1803.02811, large-batch data parallelism).
- **learner batch dp-sharded, params replicated** — ``_reshard_batch``
  lays the sampled batch over dp, so the K-update ``lax.scan`` inside the
  one compiled drain dispatch (``Trainer._learn_many`` via
  ``training/pipeline.py::drain_staged``) splits its compute across the
  mesh and XLA psums the gradients.  K updates still cost ONE dispatch.
- **staged payloads mesh-placed before the drain** — ``_put_staged``
  mirrors the hybrid trainer's ``_put_fleet``: host numpy batches are
  laid over dp (``jax.make_array_from_process_local_data`` when
  multi-process), and ``_reshard_add`` replicates the B fresh rows only
  for the capacity-sharded ring scatter (B is small next to the arena).
- **everything else replicated** — train/optimizer/RNG/counters, and the
  env-side fields: with ``--actors 0`` the in-graph collect runs as the
  single logical stream the determinism anchor pins (a 1-device mesh is
  bit-identical to the base ``Trainer``; tests/test_dp_learner.py).

``SPMDTrainer`` (shard_map) remains the whole-loop-on-mesh design for
pure-JAX collect; this class is the LEARNER-side half that composes with
the fleet's host-visible drain boundary (``FleetLearner`` rejects
shard_map trainers).  docs/FLEET.md "Multi-chip learner" has the layout
table and the refused knob combos.
"""

from __future__ import annotations

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from r2d2dpg_tpu.agents.ddpg import R2D2DPG
from r2d2dpg_tpu.envs.core import Environment
from r2d2dpg_tpu.parallel.mesh import (
    DP_AXIS,
    constrain_batch_sharded,
    constrain_replicated,
)
from r2d2dpg_tpu.replay.arena import ArenaState
from r2d2dpg_tpu.training.trainer import Trainer, TrainerConfig, TrainerState


class DPLearnerTrainer(Trainer):
    """dp-sharded replay + data-parallel learner in the pjit layout style.

    ``config`` is global (total capacity, global batch size); jitted
    programs see global shapes and XLA splits the work across the mesh
    from the array shardings.  ``axis`` stays ``None``: no named axis, no
    explicit collectives — replicated params + dp-sharded batch make
    GSPMD insert the gradient psum (the HostSPMDTrainer recipe, minus the
    host env pool: this trainer's envs are pure-JAX or fleet-remote).
    """

    axis = None  # pjit style: XLA inserts the gradient collectives

    def __init__(
        self,
        env: Environment,
        agent: R2D2DPG,
        config: TrainerConfig,
        mesh: Mesh,
    ):
        if agent.config.axis_name is not None:
            raise ValueError(
                "DPLearnerTrainer uses pjit-style gradient sync; build the "
                "agent with axis_name=None (got "
                f"{agent.config.axis_name!r})"
            )
        d = mesh.shape[DP_AXIS]
        # capacity: the arena shards over it; batch_size: the learner
        # splits over it; num_envs: staged batches arrive in multiples of
        # it, so the dp1 staged layout stays divisible at every coalesce
        # width (widths are num_envs multiples — replay/arena.stack_staged).
        for field in ("capacity", "batch_size", "num_envs"):
            if getattr(config, field) % d:
                raise ValueError(
                    f"TrainerConfig.{field}={getattr(config, field)} must "
                    f"be divisible by the mesh size {d}"
                )
        self.mesh = mesh
        self.num_devices = d
        self._nproc = jax.process_count()
        super().__init__(env, agent, config)
        # Arena buffers carry explicit mesh shardings -> XLA scatter path
        # (Pallas needs single-device refs; replay/arena.py).
        self.arena.use_pallas = False
        from r2d2dpg_tpu.obs import get_registry

        reg = get_registry()
        # ISSUE 9 obs satellite: per-shard arena occupancy (a skewed shard
        # = a skewed ring/scatter) and the per-shard rows of the most
        # recent staged drain dispatch.  Occupancy rides the log cadence's
        # batched device_get (_log_extra_refs); the width is host-known at
        # _put_staged time — neither adds a fetch to the hot path.
        self._obs_shard_occ = reg.gauge(
            "r2d2dpg_dp_shard_occupancy",
            "filled replay slots in this dp shard's capacity block",
            labelnames=("shard",),
        )
        self._obs_learn_width = reg.gauge(
            "r2d2dpg_dp_shard_learn_width",
            "staged sequences per dp shard in the most recent drain "
            "dispatch (global staged B / mesh size)",
        )

    # --------------------------------------------------------------- builds
    def _build_phases(self):
        mesh = self.mesh
        dp = P(DP_AXIS)
        # Layout: ONLY the learner side is sharded.  The arena shards over
        # capacity (axis 0 of data/priority — replay grows with the mesh);
        # train/behavior/RNG/counters replicate (GSPMD psums the grads);
        # the env-side fields replicate too — under --actors N this
        # process never collects, and under --actors 0 the in-graph
        # collect must stay the single logical stream the determinism
        # anchor pins (sharding it would change nothing numerically but
        # waste layout churn on a path the dp learner exists to starve).
        spec = TrainerState(
            env_state=P(),
            obs=P(),
            reset=P(),
            actor_carry=P(),
            critic_carry=P(),
            noise_state=P(),
            window=P(),
            arena=ArenaState(
                data=dp, priority=dp, cursor=P(), total_added=P(), meta=dp
            ),
            train=P(),
            behavior_params=P(),
            rng=P(),
            phase_idx=P(),
            env_steps=P(),
            episode_return=P(),
            completed_return_sum=P(),
            completed_count=P(),
        )
        self._shardings = jax.tree_util.tree_map(
            lambda s: NamedSharding(mesh, s),
            spec,
            is_leaf=lambda x: isinstance(x, P),
        )
        self._replicated = NamedSharding(mesh, P())
        self._dp_arena = NamedSharding(mesh, dp)
        super()._build_phases()

    def lstate_shardings(self):
        """The drain programs' output-sharding contract
        (``training/pipeline.py::LearnerState``): pinning the outputs to
        the init layout keeps the donated drain chain's avals STABLE, so
        the fleet learner's jit cache (and its AOT-precompiled coalesce
        widths) never re-keys mid-run on a GSPMD layout drift."""
        from r2d2dpg_tpu.training.pipeline import LearnerState

        return LearnerState(
            train=self._replicated,
            arena=ArenaState(
                data=self._dp_arena,
                priority=self._dp_arena,
                cursor=self._replicated,
                total_added=self._replicated,
                meta=self._dp_arena,
            ),
            rng=self._replicated,
        )

    # ----------------------------------------------------------------- init
    def init(self, key=None) -> TrainerState:
        state = super().init(key)
        return jax.device_put(state, self._shardings)

    # ------------------------------------------------------------- reshards
    def _reshard_add(self, seq, prios):
        """Replicate the B fresh rows for the capacity-sharded ring
        scatter — AFTER the initial-priority forward ran in the staged
        (dp-over-B) layout.  B (one emit / one staged drain) is small next
        to the arena, and a replicated operand keeps each capacity shard's
        ``.at[idx].set`` local instead of routing rows between shards.
        ``with_sharding_constraint`` (not device_put): these hooks run
        INSIDE the jitted phase/drain programs."""
        return constrain_replicated((seq, prios), self.mesh)

    def _reshard_batch(self, batch):
        """Shard the sampled batch over dp so the learner step's compute
        splits and XLA psums the gradients (params replicated + batch
        sharded — the pjit/GSPMD recipe)."""
        return constrain_batch_sharded(batch, self.mesh)

    # ---------------------------------------------------------- fleet hooks
    def _put_staged(self, staged, axis: int = 0):
        """Lay a host batch tree over the dp mesh (the hybrid trainer's
        ``_put_fleet`` idiom): batch axis over dp, global assembly via
        ``jax.make_array_from_process_local_data`` when multi-process.  A
        width that does not divide the mesh (foreign actor shapes — a
        defensive case, ``structural_argv`` pins num_envs fleet-wide)
        replicates instead: correctness over bandwidth.

        ``axis=0`` is the staged fleet layout (leaves ``[B, ...]``);
        ``axis=1`` is the sampler learner's pulled layout (leaves
        ``[K, B, ...]``): each dp slice receives its ``B/D`` rows at
        placement time, so the composed sampler+dp run's learn program
        sees a batch already in the ``_reshard_batch`` layout — no
        central reshard hop (docs/TOPOLOGY.md)."""
        b = int(
            np.shape(jax.tree_util.tree_leaves(staged)[0])[axis]
        )
        # Divisibility is a GLOBAL property: each process contributes b
        # local rows, and the assembled array's batch dim is b * nproc.
        sharded = (b * self._nproc) % self.num_devices == 0
        if not sharded and self._nproc > 1:
            # The defensive replicate fallback is single-process-only:
            # device_put of process-LOCAL data against a replicated
            # global sharding would build per-process-inconsistent
            # arrays.  Multi-process widths must divide the mesh.
            raise ValueError(
                f"multi-process staged width {b} x {self._nproc} "
                f"processes does not divide the {self.num_devices}-device "
                f"mesh"
            )
        if axis != 0 and self._nproc > 1:
            # Only the staged axis-0 path is multi-process-shaped today
            # (the sampler learner is single-process; its multi-HOST pull
            # is a ROADMAP open item).
            raise ValueError(
                "batch-axis placement (axis != 0) is single-process only"
            )

        def put(x):
            x = np.asarray(x)
            if not sharded:
                return jax.device_put(x, self._replicated)
            spec = [None] * x.ndim
            spec[axis] = DP_AXIS
            sh = NamedSharding(self.mesh, P(*spec))
            if self._nproc == 1:
                return jax.device_put(x, sh)
            return jax.make_array_from_process_local_data(
                sh, x, (x.shape[0] * self._nproc,) + x.shape[1:]
            )

        return jax.tree_util.tree_map(put, staged)

    # ------------------------------------------------------------------ obs
    def dp_set_learn_width(self, b: int) -> None:
        """Record the per-shard rows of a REAL drain-learn dispatch
        (called by the fleet drain loop at the dispatch site — not from
        ``_put_staged``, which also places warm-precompile dummies and
        absorb batches that never learn)."""
        sharded = b % self.num_devices == 0
        self._obs_learn_width.set(float(b // self.num_devices if sharded else b))

    def _log_extra_refs(self, arena_state) -> list:
        return [self.arena.per_shard_occupancy(arena_state, self.num_devices)]

    def _log_extra_publish(self, fetched) -> None:
        for i, v in enumerate(np.asarray(fetched[0])):
            self._obs_shard_occ.labels(shard=str(i)).set(float(v))
