"""Pipelined collect/learn executor: overlap env stepping with learner compute.

The phase-locked ``Trainer.run`` serializes collect -> emit -> K learner
updates inside one jit per phase: on dm_control configs the chip idles
during every MuJoCo host step and the host env pool idles during every
learner update.  Ape-X (Horgan et al. 2018, PAPERS.md 1803.00933) and
Podracer (Hessel et al. 2021, PAPERS.md 2104.06272) get distributed-RL
throughput from decoupling exactly this:

::

    phase-locked            pipelined (this module)
    ------------            -----------------------
    C0 E0 L0 C1 E1 L1 ...   collector thread: C0 E0 | C1 E1 | C2 E2 | ...
                                                 \\      \\      \\
                                              [bounded staging queue]
                                                   \\      \\      \\
                            learner thread:         A0 L0 | A1 L1 | ...

    C = collect stride env steps   E = emit window    (collector program)
    A = add staged seqs to arena   L = K learner updates  (drain program)

Contracts (docs/PIPELINE.md has the long form):

- **Schedule parity** — one drain phase per collect phase, in order: the
  data-to-update ratio is identical to the phase-locked schedule; only the
  *interleaving* changes.  ``PipelineConfig(enabled=False)`` routes train
  phases through the trainer's own fused ``train_phase`` — the phase-locked
  schedule itself, bit-identical to ``Trainer.run`` at a fixed seed
  (tests/test_pipeline.py pins this).
- **Staleness** — the collector acts with a snapshot of the learner's
  params, refreshed from the newest *published* learner state every
  ``max(param_sync_every, 1)`` collect phases.  The bounded queue
  (``queue_depth``) caps how far collection runs ahead of learning, so
  behavior-param staleness is at most ``param_sync_every + queue_depth + 1``
  phases — the same knob/contract as the phase-locked trainer, widened by
  the queue bound.  (``param_sync_every == 0``, phase-locked "always
  fresh", means "freshest published" here: refreshed every phase.)
- **Backpressure** — ``queue.put`` blocks the collector when the learner
  falls ``queue_depth`` phases behind; ``queue.get`` blocks the learner
  when collection is the bottleneck.  Both waits feed ``PercentileWindow``s
  (``stats()``: p50/p99 + totals + overlap fraction).
- **RNG** — pipelined mode forks the state's stream (collector/learner get
  independent ``fold_in`` branches); a pipelined run is a *different* —
  equally valid — random trajectory than the phase-locked schedule.
  Determinism claims attach to ``enabled=False`` only.
- **Donation safety** — both device programs donate their state argument,
  so the behavior snapshot crosses as a separate non-donated input and the
  learner publishes ``jnp.copy``'d param trees: the next drain's donation
  must never invalidate buffers the collector still reads.
"""

from __future__ import annotations

import dataclasses
import queue
import threading
import time
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from r2d2dpg_tpu.obs import flight_event, get_registry
from r2d2dpg_tpu.obs import trace as obs_trace
from r2d2dpg_tpu.obs.device import get_device_monitor
from r2d2dpg_tpu.replay.arena import StagedSequences
from r2d2dpg_tpu.training.assembler import emit
from r2d2dpg_tpu.training.trainer import Trainer, TrainerState
from r2d2dpg_tpu.utils.metrics import host_scalars
from r2d2dpg_tpu.utils.profiling import annotate, scope

# A single queue wait this long is operator-worthy: it lands in the flight
# recorder as a ``queue_stall`` event (the percentile windows keep the full
# distribution either way).
_STALL_EVENT_S = 1.0


@dataclasses.dataclass(frozen=True)
class PipelineConfig:
    """Static executor knobs (the trainer's own config governs the rest)."""

    enabled: bool = True  # False = phase-locked control schedule
    queue_depth: int = 2  # staging-queue capacity, in collect phases
    prefetch: bool = True  # double-buffered batch sampling in the drain
    # Experience-path trace sampling (obs/trace.py; --trace-sample).  The
    # in-process path records the hops that exist without a wire: collect,
    # enqueue, arena_add, learn.  0 = off — no span, no extra
    # block_until_ready, the schedule untouched.
    trace_sample: float = 0.0


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class CollectorState:
    """The collector thread's slice of ``TrainerState`` (no learner subtree).

    Field names deliberately match ``TrainerState`` so ``Trainer._collect``
    and ``HostSPMDTrainer._absorb`` run on either pytree unchanged
    (``dataclasses.replace`` and attribute reads resolve the same way)."""

    env_state: Any
    obs: jnp.ndarray
    reset: jnp.ndarray
    actor_carry: Any
    critic_carry: Any
    noise_state: jnp.ndarray
    window: Any
    rng: jax.Array
    phase_idx: jnp.ndarray
    env_steps: jnp.ndarray
    episode_return: jnp.ndarray
    completed_return_sum: jnp.ndarray
    completed_count: jnp.ndarray


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class LearnerState:
    """The learner thread's slice of ``TrainerState``."""

    train: Any
    arena: Any
    rng: jax.Array


_COLLECT_FIELDS = tuple(f.name for f in dataclasses.fields(CollectorState))


def drain_staged(
    trainer: Trainer,
    lstate: LearnerState,
    staged: StagedSequences,
    *,
    learn: bool = True,
    prefetch: bool = True,
) -> Tuple[LearnerState, Dict[str, jnp.ndarray]]:
    """The learner-side drain body: resolve priorities -> arena add -> K
    updates (double-buffered sampling when ``prefetch``).

    Shared by the in-process pipelined executor (``_drain_learn_impl``) and
    the fleet learner (fleet/ingest.py) so the two staging-queue consumers
    cannot drift: an out-of-process actor's batch enters the arena through
    the exact code path a local collector's does.  ``staged.priorities`` may
    be pre-resolved (fleet actors rank locally with their stale nets, the
    Ape-X contract) or ``None`` (ranked here with the learner's current
    nets).  ``learn=False`` absorbs without updating — the fleet's
    replay-fill mode before ``min_replay`` sequences are resident."""
    t = trainer
    rng, key = jax.random.split(lstate.rng)
    key = t._fold_axis(key)
    with scope("pipeline_add"):
        prios = staged.priorities
        if prios is None:
            prios = t._initial_priorities(lstate.train, lstate.arena, staged.seq)
        seq, prios = t._reshard_add(staged.seq, prios)
        # Provenance rides through untouched (same [B] layout as prios);
        # the entry stamp is the OWNING learner's step clock, so replay
        # age is measured on one clock per arena (obs/quality.py).
        arena = t.arena.add_staged(
            lstate.arena,
            StagedSequences(
                seq=seq,
                priorities=prios,
                behavior_version=staged.behavior_version,
                collect_id=staged.collect_id,
            ),
            stamp=lstate.train.step,
        )
    if not learn:
        return LearnerState(train=lstate.train, arena=arena, rng=rng), {}
    with scope("pipeline_learn"):
        train, arena, metrics = t._learn_many(
            lstate.train, arena, key, prefetch=prefetch
        )
    return LearnerState(train=train, arena=arena, rng=rng), metrics


def bucket_width(available: int, limit: int) -> int:
    """Power-of-two coalesce bucket: the largest 2^k <= min(available,
    limit).

    A coalesced drain's compiled program is shaped by its batch width, so
    arbitrary widths would compile up to ``limit`` distinct programs —
    and the bench showed those mid-run compiles eating the very dispatch
    savings coalescing buys.  Bucketing to powers of two caps the program
    count at log2(limit)+1 while still absorbing any backlog within a
    factor of two of its size."""
    n = max(1, min(available, limit))
    return 1 << (n.bit_length() - 1)


def coalesce_from_queue(q: "queue.Queue", first: Any, limit: int) -> list:
    """``first`` (already blocking-got) plus queue-resident items up to
    the power-of-two bucket of ``limit`` — never blocks, never waits for
    stragglers.

    The coalesced-drain pull schedule (fleet/ingest.py): when the learner
    falls behind, the backlog is drained in one compiled call instead of
    one XLA dispatch per actor batch; when it keeps up, every pull returns
    width 1 and the schedule is byte-identical to the uncoalesced drain.
    Widths are bucketed (``bucket_width``) so a run compiles a bounded
    set of drain programs.  Callers whose queue carries a termination
    sentinel must coalesce with ``limit=1`` or filter it themselves (the
    fleet queue never does)."""
    width = bucket_width(1 + q.qsize(), limit)
    items = [first]
    while len(items) < width:
        try:
            items.append(q.get_nowait())
        except queue.Empty:
            break  # qsize raced low: a rare narrower pull, never a stall
    return items


def split_state(state: TrainerState) -> Tuple[CollectorState, LearnerState]:
    """Partition a ``TrainerState`` into the two threads' disjoint slices.

    The RNG stream forks (independent ``fold_in`` branches per side) — see
    the module contract: pipelined mode is a different random trajectory."""
    fields = {f: getattr(state, f) for f in _COLLECT_FIELDS if f != "rng"}
    return (
        CollectorState(rng=jax.random.fold_in(state.rng, 0), **fields),
        LearnerState(
            train=state.train,
            arena=state.arena,
            rng=jax.random.fold_in(state.rng, 1),
        ),
    )


def merge_state(
    state: TrainerState,
    cstate: CollectorState,
    lstate: LearnerState,
    behavior_params: Any = None,
) -> TrainerState:
    """Reassemble a full ``TrainerState`` after a pipelined section.

    Every leaf comes from the two slices (plus the final behavior snapshot),
    so ``state`` — whose buffers the first donating program call consumed —
    contributes only pytree structure."""
    return dataclasses.replace(
        state,
        train=lstate.train,
        arena=lstate.arena,
        behavior_params=(
            behavior_params
            if behavior_params is not None
            else jax.tree_util.tree_map(jnp.copy, lstate.train.actor_params)
        ),
        **{f: getattr(cstate, f) for f in _COLLECT_FIELDS},
    )


class _ParamBox:
    """Latest learner-published behavior params, swapped under a lock.

    Holds ``jnp.copy``'d trees (the learner copies before publishing): the
    drain program donates its ``LearnerState`` input, so raw ``train``
    references would be invalidated one phase after publication while the
    collector may hold its snapshot for ``param_sync_every`` phases."""

    def __init__(self, actor, critic):
        self._lock = threading.Lock()
        self._params = (actor, critic)

    def publish(self, actor, critic) -> None:
        with self._lock:
            self._params = (actor, critic)

    def snapshot(self):
        with self._lock:
            return self._params


class PipelineExecutor:
    """Drives a trainer's phase schedule with collect and learn overlapped.

    Works with the base ``Trainer`` (in-graph collect; for ``DMCHostEnv``
    the ordered ``io_callback`` physics steps block the collector thread
    while the learner thread's updates run — the host/device overlap the
    phase-locked schedule cannot express) and with ``HostSPMDTrainer``
    (host-driven collect loop on the collector thread).  ``SPMDTrainer``
    is rejected: its phases are fused ``shard_map`` programs with no
    host-visible collect/learn boundary to pipeline across.

    Warm-up and replay-fill phases always run phase-locked on the calling
    thread — the learner has nothing to do until replay holds
    ``min_replay`` sequences, so there is nothing to overlap.
    """

    def __init__(
        self, trainer: Trainer, config: PipelineConfig = PipelineConfig()
    ):
        if trainer.axis is not None:
            raise ValueError(
                "PipelineExecutor needs a host-visible collect/learn "
                "boundary; shard_map trainers (SPMDTrainer) fuse whole "
                "phases — use the base Trainer or HostSPMDTrainer"
            )
        if config.queue_depth < 1:
            raise ValueError("queue_depth must be >= 1")
        self.trainer = trainer
        self.config = config
        self._host_driven = hasattr(trainer, "_host_collect")
        if self._host_driven:
            # Host-driven collect: the stride loop runs in Python on the
            # collector thread (parallel/hybrid.py's layout); only the
            # per-phase RNG split and the window emission are device
            # programs here — act/absorb reuse the trainer's own jits.
            self._setup_prog = jax.jit(self._setup_impl)
            self._emit_prog = jax.jit(emit)
        else:
            self._collect_prog = jax.jit(
                self._collect_emit_impl, donate_argnums=(0,)
            )
        self._drain_prog = jax.jit(self._drain_learn_impl, donate_argnums=(0,))
        self._reset_stats()

    # --------------------------------------------------------- device parts
    def _collect_emit_impl(
        self, cstate: CollectorState, behavior, critic_params
    ) -> Tuple[CollectorState, StagedSequences]:
        """The collector's program: stride env steps + window shift + emit.

        ``behavior``/``critic_params`` are explicit non-donated inputs (see
        module docstring: the donated collector state must not swallow the
        published snapshot)."""
        with scope("pipeline_collect"):
            cstate = self.trainer._collect(
                cstate, behavior=behavior, critic_params=critic_params
            )
        with scope("pipeline_emit"):
            staged = StagedSequences(seq=emit(cstate.window), priorities=None)
        return cstate, staged

    def _setup_impl(self, rng: jax.Array):
        """Host-driven collect prep: advance the stream, make stride keys.

        Takes ONLY the key — jitting the whole CollectorState through here
        would materialize fresh buffers for every pass-through leaf each
        phase (no donation); the eager ``dataclasses.replace`` at the call
        site aliases the unchanged leaves for free."""
        rng, sk = jax.random.split(rng)
        keys = jax.random.split(sk, self.trainer.config.stride)
        return rng, keys

    def _drain_learn_impl(
        self, lstate: LearnerState, staged: StagedSequences
    ) -> Tuple[LearnerState, Dict[str, jnp.ndarray]]:
        """The learner's program: the shared ``drain_staged`` body at this
        executor's prefetch setting."""
        return drain_staged(
            self.trainer, lstate, staged, prefetch=self.config.prefetch
        )

    # ------------------------------------------------------- host-side parts
    def _collect_phase_pipelined(
        self, cstate: CollectorState, behavior, critic_params
    ) -> Tuple[CollectorState, StagedSequences]:
        """One collect phase on the collector thread, either layout."""
        if not self._host_driven:
            return self._collect_prog(cstate, behavior, critic_params)
        # Host-driven: the hybrid trainer's shared stride loop
        # (parallel/hybrid.py ``_stride_loop``) on the CollectorState — no
        # learner-substep hook (the learner THREAD is the overlap here).
        rng, keys = self._setup_prog(cstate.rng)
        cstate = self.trainer._stride_loop(
            cstate, behavior, critic_params, keys, rng
        )
        return cstate, StagedSequences(
            seq=self._emit_prog(cstate.window), priorities=None
        )

    def _publish(
        self, box: _ParamBox, train, phase: int = -1, record: bool = True
    ) -> Any:
        """Copy + publish the learner's behavior params (donation safety).

        Published EVERY drain phase even when the collector reads only
        every ``param_sync_every``-th: a lazily-copied raw ref would be
        invalidated by the next drain's donation before the collector
        copies it, and publishing on the collector's cadence would add a
        publication-age term to the documented staleness bound.  The cost
        is two small param-tree copies next to K full learner updates.

        ``record=False`` skips the flight event: a per-drain-phase event
        would flood the bounded ring at tens of phases per second and
        evict the rare events (checkpoint saves, stalls, sheds) a
        post-mortem actually needs — the caller records on the log
        cadence instead."""
        cp = lambda tree: jax.tree_util.tree_map(jnp.copy, tree)  # noqa: E731
        actor = cp(train.actor_params)
        box.publish(actor, cp(self.trainer.agent.behavior_critic_params(train)))
        if record:
            flight_event("param_publish", phase=phase)
        return actor

    # ------------------------------------------------------------------ runs
    def _reset_stats(self) -> None:
        # Registry histograms (obs/): same PercentileWindow backend the bare
        # windows used, but scrapeable via /metrics while a section runs.
        # Reset at each section start so stats() stays per-section.
        reg = get_registry()
        self.learner_wait = reg.histogram(
            "r2d2dpg_pipeline_learner_wait_seconds",
            "learner thread blocked on the staging queue (starvation)",
        )
        self.collect_wait = reg.histogram(
            "r2d2dpg_pipeline_collect_wait_seconds",
            "collector thread blocked on the staging queue (backpressure)",
        )
        self._obs_queue_depth = reg.gauge(
            "r2d2dpg_pipeline_staging_queue_depth",
            "staged collect phases awaiting drain",
        )
        self.learner_wait.reset()
        self.collect_wait.reset()
        self._stats: Dict[str, float] = {}

    def stats(self) -> Dict[str, float]:
        """Instrumentation from the most recent pipelined section.

        ``overlap_fraction`` = 1 - learner_wait_total / wall: the fraction
        of the pipelined wall-clock during which the learner had staged
        data available (1.0 = never starved — collection fully hidden;
        0.0 = the schedule degenerated to phase-locked)."""
        return dict(self._stats)

    def run(
        self,
        num_phases: int,
        state: Optional[TrainerState] = None,
        log_every: int = 50,
        log_fn=print,
        metrics_fn: Optional[Callable[[int, Dict[str, float]], None]] = None,
        minutes: Optional[float] = None,
    ) -> TrainerState:
        """Drive the full schedule (warm-up -> fill -> train) for
        ``num_phases`` phases, mirroring ``Trainer.run``'s schedule and log
        cadence exactly; train phases run pipelined when enabled.

        ``metrics_fn(phase, scalars)``, when given, receives the raw log
        scalars instead of ``log_fn`` receiving a formatted line (the
        train.py wiring).  ``minutes`` bounds wall-clock: the schedule
        stops starting new phases once the budget is spent."""
        t = self.trainer
        state = t.init() if state is None else state
        deadline = time.monotonic() + minutes * 60 if minutes is not None else None
        warm, fill = t.window_fill_phases, t.replay_fill_phases
        locked_until = min(num_phases, warm + fill)

        def emit_log(phase: int, ep: Dict[str, float], scalars: Dict[str, float]):
            if metrics_fn is not None:
                metrics_fn(phase, {**ep, **scalars})
                return
            log_fn(
                f"phase {phase}/{num_phases} "
                f"env_steps {int(ep['env_steps'])} "
                f"return {ep['episode_return_mean']:.1f} "
                f"({int(ep['episodes'])} eps) "
                + " ".join(f"{k} {v:.3g}" for k, v in scalars.items())
            )

        phase = 0
        while phase < locked_until:
            if deadline is not None and time.monotonic() >= deadline:
                break
            if phase < warm:
                with annotate("pipeline/warmup_phase"):
                    state = t.collect_phase(state)
            else:
                with annotate("pipeline/fill_phase"):
                    state = t.fill_phase(state)
            phase += 1
            if log_every and phase % log_every == 0:
                state, ep = t.pop_episode_metrics(state)
                emit_log(phase, ep, {})

        if phase < num_phases and (
            deadline is None or time.monotonic() < deadline
        ):
            if not self.config.enabled:
                state = self._run_locked(
                    state, phase, num_phases, log_every, emit_log, deadline
                )
            else:
                state = self._run_pipelined(
                    state, phase, num_phases, log_every, emit_log, deadline
                )
        return state

    def run_train_phases(
        self,
        state: TrainerState,
        n: int,
        log_every: int = 0,
        log_fn=print,
    ) -> TrainerState:
        """Run exactly ``n`` TRAIN phases from ``state`` — pipelined when
        enabled, phase-locked otherwise.  No warm-up/fill bookkeeping: the
        replay arena must already hold ``min_replay`` sequences.  The
        measurement/test entry point (the overlap smoke test); ``run``
        drives the full schedule."""

        def emit_log(phase, ep, scalars):
            log_fn(f"train phase {phase}/{n} " + " ".join(
                f"{k} {v:.3g}" for k, v in {**ep, **scalars}.items()
            ))

        if self.config.enabled:
            return self._run_pipelined(state, 0, n, log_every, emit_log, None)
        return self._run_locked(state, 0, n, log_every, emit_log, None)

    def _run_locked(
        self, state, phase, num_phases, log_every, emit_log, deadline
    ) -> TrainerState:
        """The phase-locked control schedule: the trainer's own fused
        ``train_phase``, driven with ``Trainer.run``'s exact cadence — the
        bit-identity anchor the determinism test pins."""
        t = self.trainer
        last_metrics: Dict[str, jnp.ndarray] = {}
        while phase < num_phases:
            if deadline is not None and time.monotonic() >= deadline:
                break
            with annotate("trainer/train_phase"):
                state, last_metrics = t.train_phase(state)
            phase += 1
            if log_every and phase % log_every == 0:
                state, ep = t.pop_episode_metrics(state)
                scalars = host_scalars(jax.device_get(last_metrics))
                emit_log(phase, ep, scalars)
        return state

    def _run_pipelined(
        self, state, phase0, num_phases, log_every, emit_log, deadline
    ) -> TrainerState:
        t = self.trainer
        cfg = self.config
        n_train = num_phases - phase0
        self._reset_stats()
        # Device plane (ISSUE 14): the learner thread owns the run window
        # — steady arms once the first drain executed, the profiler
        # window ticks on drain phases, and the collector thread's
        # compiles carry their own label.
        mon = get_device_monitor().install()
        mon.begin_run()
        cstate, lstate = split_state(state)
        box = _ParamBox(None, None)
        self._publish(box, lstate.train, phase0)
        q: queue.Queue = queue.Queue(maxsize=cfg.queue_depth)
        # Live depth at scrape time (set_fn: evaluated per snapshot).  The
        # queue outlives the section only as an empty object, so a late
        # scrape correctly reads 0.
        self._obs_queue_depth.set_fn(q.qsize)
        stop = threading.Event()
        collector_err: list = []
        result: Dict[str, Any] = {}
        sync_every = max(t.config.param_sync_every, 1)

        def collector() -> None:
            cs = cstate
            mon.label_thread("pipeline_collect")
            try:
                behavior, critic = box.snapshot()
                for k in range(n_train):
                    if stop.is_set():
                        break
                    if deadline is not None and time.monotonic() >= deadline:
                        break
                    if k and k % sync_every == 0:
                        behavior, critic = box.snapshot()
                    tr = obs_trace.maybe_start(cfg.trace_sample)
                    with annotate("pipeline/collect"):
                        cs, staged = self._collect_phase_pipelined(
                            cs, behavior, critic
                        )
                    if tr is not None:
                        # The collect hop ends when the staged batch is
                        # actually materialized (async dispatch otherwise
                        # returns immediately); sampled phases only.
                        jax.block_until_ready(staged)
                        tr.t_collect_end = time.time()
                        obs_trace.record_hop(
                            "collect", tr.t_collect_start, tr.t_collect_end,
                            tr.trace_id,
                        )
                    gphase = phase0 + k + 1
                    ep_refs = None
                    if log_every and gphase % log_every == 0:
                        # Drain the episode accumulators HERE (collector
                        # owns them); the refs ride the queue and join the
                        # learner's single batched device_get at log time.
                        # env_steps is COPIED: the original stays in cs and
                        # gets donated by the next collect call, possibly
                        # before the learner's fetch runs (the drained
                        # accumulators leave cs, so their refs are safe).
                        ep_refs = (
                            jnp.copy(cs.env_steps),
                            cs.completed_return_sum,
                            cs.completed_count,
                        )
                        # Two DISTINCT zero arrays: one shared buffer for
                        # both fields would be a double-donation on the
                        # next collect call.
                        cs = dataclasses.replace(
                            cs,
                            completed_return_sum=jnp.zeros(()),
                            completed_count=jnp.zeros(()),
                        )
                    item = (gphase, staged, ep_refs, tr)
                    t_wait = time.monotonic()
                    while not stop.is_set():
                        try:
                            q.put(item, timeout=0.2)
                            break
                        except queue.Full:
                            continue
                    waited = time.monotonic() - t_wait
                    self.collect_wait.add(waited)
                    if waited >= _STALL_EVENT_S:
                        flight_event(
                            "queue_stall", side="collector",
                            phase=gphase, seconds=round(waited, 3),
                        )
            except BaseException as e:  # surfaced on the learner thread
                collector_err.append(e)
            finally:
                result["cstate"] = cs
                q.put(None)

        thread = threading.Thread(
            target=collector, name="pipeline-collector", daemon=True
        )
        t0 = time.monotonic()
        thread.start()
        ls = lstate
        behavior_final = None
        drained = 0
        try:
            while True:
                t_wait = time.monotonic()
                item = q.get()
                waited = time.monotonic() - t_wait
                self.learner_wait.add(waited)
                if waited >= _STALL_EVENT_S:
                    flight_event(
                        "queue_stall", side="learner",
                        phase=phase0 + drained + 1, seconds=round(waited, 3),
                    )
                if item is None:
                    break
                gphase, staged, ep_refs, tr = item
                t_dequeue = time.time()
                mon.on_phase(drained + 1)
                with annotate("pipeline/learn"), mon.program(
                    "pipeline_drain"
                ):
                    ls, metrics = self._drain_prog(ls, staged)
                if tr is not None:
                    # Sampled batch: enqueue = staging-queue residency,
                    # arena_add = the drain call's dispatch window, learn =
                    # device execution (block_until_ready — sampled phases
                    # only, the unsampled schedule stays fully async).
                    t_dispatch_end = time.time()
                    obs_trace.record_hop(
                        "enqueue", tr.t_collect_end, t_dequeue, tr.trace_id
                    )
                    obs_trace.record_hop(
                        "arena_add", t_dequeue, t_dispatch_end, tr.trace_id
                    )
                    jax.block_until_ready(ls.train.step)
                    obs_trace.record_hop(
                        "learn", t_dispatch_end, time.time(), tr.trace_id
                    )
                behavior_final = self._publish(
                    box, ls.train, gphase, record=ep_refs is not None
                )
                drained += 1
                if drained == 1:
                    # Drain + collect + publish programs are all warm
                    # (the publish's eager copies compiled at the
                    # pre-loop publish): the sentinel arms.
                    mon.mark_steady()
                if ep_refs is not None:
                    # ONE batched fetch per log cadence: episode stats,
                    # learner step counter, the phase's learn metrics, and
                    # the arena telemetry scalars (obs/ rides this fetch —
                    # no host syncs of its own).  Same guard as
                    # pop_episode_metrics: a multi-process fleet's arena is
                    # not fully addressable per process, so eager
                    # reductions on it are skipped.
                    with mon.expected("log_fetch"):
                        refs = [*ep_refs, ls.train.step, metrics]
                        single_proc = jax.process_count() == 1
                        if single_proc:
                            refs += [
                                t.arena.size(ls.arena),
                                ls.arena.priority.sum(),
                                ls.arena.total_added,
                            ]
                        fetched = jax.device_get(tuple(refs))
                    env_steps, ret_sum, count, lstep, m = fetched[:5]
                    count = float(count)
                    ep = {
                        "episode_return_mean": float(ret_sum) / max(count, 1.0),
                        "episodes": count,
                        "env_steps": float(env_steps),
                        "learner_steps": float(lstep),
                    }
                    if single_proc:
                        occ, psum, added = fetched[5:]
                        t.arena.observe_state_scalars(
                            float(occ), float(psum), float(added)
                        )
                    t._obs_publish(ep)
                    emit_log(gphase, ep, host_scalars(m))
        finally:
            stop.set()
            # Unblock a collector mid-put, then collect its state.
            while thread.is_alive():
                try:
                    q.get_nowait()
                except queue.Empty:
                    thread.join(timeout=0.2)
            thread.join()
            # Rebind the depth gauge to a literal 0: the section is over,
            # and the set_fn closure would otherwise (a) report leftover
            # sentinel/staged items as live depth after an abort and
            # (b) pin the queue's device-resident payloads until the next
            # section rebinds it.
            self._obs_queue_depth.set(0.0)
            # Disarm the sentinel (and close any open profiler capture):
            # whatever compiles after this section is a new window.
            mon.end_run()
        if collector_err:
            raise collector_err[0]
        jax.block_until_ready(ls.train.step)
        wall = max(time.monotonic() - t0, 1e-9)
        # One consistent (count, total, p50, p99) per window — a single
        # locked read each, not three (PercentileWindow.snapshot).
        _, lw_total, lw_p50, lw_p99 = self.learner_wait.snapshot()
        _, cw_total, cw_p50, cw_p99 = self.collect_wait.snapshot()
        self._stats = {
            "train_phases": float(drained),
            "wall_s": wall,
            "learner_steps_per_sec": drained * t.config.learner_steps / wall,
            "learner_wait_p50_ms": lw_p50 * 1e3,
            "learner_wait_p99_ms": lw_p99 * 1e3,
            "learner_wait_total_s": lw_total,
            "collect_wait_p50_ms": cw_p50 * 1e3,
            "collect_wait_p99_ms": cw_p99 * 1e3,
            "collect_wait_total_s": cw_total,
            "overlap_fraction": float(
                np.clip(1.0 - lw_total / wall, 0.0, 1.0)
            ),
            # Device plane (ISSUE 14): this section's compile ledger +
            # peak HBM — the bench/evidence columns.
            **mon.run_stats(),
        }
        return merge_state(state, result["cstate"], ls, behavior_final)
