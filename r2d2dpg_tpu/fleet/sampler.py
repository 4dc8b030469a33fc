"""In-network experience sampling: sharded replay, learner-pulled batches.

The central-drain fleet (``fleet/ingest.py``) funnels EVERY collected
sequence through one staging queue into one device arena behind one drain
thread — the wire and the drain both carry experience that may never be
sampled.  This module inverts the topology (ISSUE 10; In-Network
Experience Sampling, PAPERS.md 2110.13506; Ape-X distributed replay,
1803.00933):

::

    actor 0 ── SEQS ──▶ handler ──▶ [shard 0]  priority structure + ring
    actor 1 ── SEQS ──▶ handler ──▶ [shard 1]      (replay/sharded.py)
    actor … ── SEQS ──▶ handler ──▶ [shard h(actor) mod N]
                                        ▲ │
                     SAMPLE_REQ {quota} ─┘ │ BATCH {seqs, slots/gens,
                     PRIO {slot, gen, p}◀──┘        probs, Σp^α}
                                      sampler learner:
                                      quotas ∝ Σp^α → K·B draws →
                                      learn program → TD write-back

- **Adds are concurrent**: each ingest handler writes straight into its
  actor's shard (consistent-hash ``shard_for_actor`` routing assigned at
  HELLO) under that shard's own lock — the central drain thread stops
  being a serialization point, and replay capacity is a per-shard slice
  (horizontal, not one device ring).
- **The learner pulls**: each train phase draws per-shard quotas from a
  multinomial over the shards' advertised ``Σ p^alpha``
  (``replay.sharded.shard_quotas``), samples within-shard
  proportionally, and learns on the assembled ``[K, B]`` batch with
  importance weights computed from the COMBINED two-level probabilities —
  exactly the central proportional distribution
  (tests/test_replay.py pins this on exact-integer priorities).
- **Priority write-back rides the versioned path in reverse**: PRIO
  frames keyed ``(shard, slot, generation)``; a slot the ring has
  evicted since the sample ignores the stale verdict, the same posture
  as the actors' param-version regression guard.
- **Backpressure becomes ring eviction**: shards never shed — a full ring
  FIFO-overwrites its oldest (re-collectable) sequences, so actor acks
  are always ``OK`` and a stalled learner never sheds or reaps a healthy
  fleet (the ``stall_sampler`` chaos drill pins this).

**Deployment shape**: the shards run as in-learner handlers behind
``--replay-shards N`` today, but every sample/write-back crosses the REAL
``SAMPLE_REQ``/``BATCH``/``PRIO`` frame codecs (``fleet/wire.py``
``pack_sample_req``/``pack_shard_batch``/``pack_prio_update``, on the
fleet's negotiated lane) through an in-process loopback — the byte
accounting is the honest cross-process cost, and moving a shard out of
the learner process is a listening socket away, not a format change
(docs/REPLAY.md "Topology").  The headline this buys: only SAMPLED
sequences cross the sampling boundary into training
(``bytes_per_trained_seq``).

``--replay-shards 1 --actors 0`` routes the untouched phase-locked loop
(nothing to shard without a fleet) and is pinned bit-identical to
``Trainer.run`` through the CLI — ``scripts/lib_gate.sh sampler_gate``
refuses to bless ``--replay-shards N`` evidence without that anchor plus
the sampling-equivalence test.

**Composes with ``--learner-dp`` since ISSUE 11** (docs/TOPOLOGY.md):
with a ``DPLearnerTrainer``, the pulled ``[K, B]`` batch is placed
through ``Trainer._put_staged(..., axis=1)`` so each dp slice receives
its ``B/D`` rows at device_put time — the compiled K-update scan runs
dp-sharded with no central reshard hop, and the learn program's outputs
stay pinned to the replicated layout (stable donated avals).
"""

from __future__ import annotations

import dataclasses
import queue
import threading
import time
import zlib
from typing import Any, Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from r2d2dpg_tpu.fleet import transport, wire
from r2d2dpg_tpu.fleet.ingest import (
    FleetConfig,
    IngestServer,
    prune_fleet_counters,
    save_fleet_counters,
    snapshot_params,
)
from r2d2dpg_tpu.obs import flight_event, get_registry
from r2d2dpg_tpu.obs import trace as obs_trace
from r2d2dpg_tpu.obs.device import get_device_monitor
from r2d2dpg_tpu.obs.quality import (
    PROVENANCE_ABSENT,
    get_quality_plane,
    policy_lags,
    quality_stats_columns,
    replay_ages,
)
from r2d2dpg_tpu.ops import anneal_beta, importance_weights
from r2d2dpg_tpu.replay.arena import SequenceBatch, StagedSequences
from r2d2dpg_tpu.replay.sharded import (
    ReplayShard,
    actor_code,
    combine_probs,
    shard_quotas,
)
from r2d2dpg_tpu.training.pipeline import merge_state, split_state
from r2d2dpg_tpu.training.trainer import Trainer, TrainerState


def _resp_provenance(resp: Dict[str, Any]) -> tuple:
    """(behavior, collect, actors) of one BATCH response, sentinel-filled
    when the frame carried no provenance (old shard procs) — the quality
    folds disarm on the sentinel instead of refusing the batch."""
    n = int(np.shape(resp["slots"])[0])

    def get(k: str) -> np.ndarray:
        v = resp.get(k)
        if v is None:
            return np.full((n,), PROVENANCE_ABSENT, np.int64)
        return np.asarray(v, np.int64)

    return (get("behavior"), get("collect"), get("actors"))


def shard_for_actor(actor_id: Any, num_shards: int) -> int:
    """Consistent actor→shard routing, assigned at HELLO.

    A pure function of the actor id (not the connection), so a
    supervised restart or an in-process reconnect lands the SAME actor
    back on the SAME shard — its slice of replay keeps one feed across
    incarnations, and every process (ingest, tests, a future cross-host
    spawner) computes the route identically with no coordination.
    Integer ids (the supervisor's 0..N-1) route round-robin by modulo —
    perfect balance at fleet sizes where a generic hash would collide —
    and any other id falls back to a crc32 consistent hash."""
    s = str(actor_id)
    if s.lstrip("-").isdigit():
        return int(s) % max(num_shards, 1)
    return zlib.crc32(s.encode()) % max(num_shards, 1)


class ShardSet:
    """N replay shards + routing + the fleet-side accounting bank.

    Owned by the sampler learner, written by the ingest handler threads
    (``add`` routes each actor's SEQS batch into its shard under that
    shard's lock).  Episode/step accounting deltas ride the same bank the
    central path uses for shed stats: the experience goes to a shard, the
    ACCOUNTING goes to the learner (popped once per train phase), so the
    fleet-wide sums stay monotone whatever the sampler is doing."""

    def __init__(
        self,
        num_shards: int,
        shard_capacity: int,
        *,
        alpha: float = 0.6,
        prioritized: bool = True,
    ):
        if num_shards < 1:
            raise ValueError("num_shards must be >= 1")
        # Eviction visibility (ISSUE 12 satellite): ring FIFO-overwrites
        # replaced shedding in PR 10 but previously left no trace — the
        # labeled counter (bumped under the shard's own add lock via
        # evict_cb) plus the sampler stats' ``evictions`` column make
        # silent experience recycling a first-class signal.
        evict = get_registry().counter(
            "r2d2dpg_replay_shard_evictions_total",
            "filled replay-shard slots FIFO-overwritten by the ring "
            "(re-collectable experience recycled before it was sampled)",
            labelnames=("shard",),
        )
        # Quality plane (ISSUE 18): evicted-before-ever-sampled churn per
        # shard — reported from inside the shard's add lock, where the
        # verdict is exact.
        qplane = get_quality_plane()
        self.shards = [
            ReplayShard(
                shard_capacity,
                alpha=alpha,
                prioritized=prioritized,
                shard_id=i,
                evict_cb=evict.labels(shard=str(i)).inc,
                evict_unsampled_cb=(
                    lambda evicted, unsampled, _i=i: qplane.note_evictions(
                        _i, evicted, unsampled
                    )
                ),
            )
            for i in range(num_shards)
        ]
        self._stats_lock = threading.Lock()
        self._stats = {
            "env_steps_delta": 0.0, "ep_return_sum": 0.0, "ep_count": 0.0,
        }
        # Per-shard gauges (ISSUE 10 obs satellite): the shards are
        # host-side, so the values are lock-guarded floats — set_fn
        # closures evaluated at scrape/log time, NO device fetch rides
        # anywhere (cheaper than the central arena's gauges, which need
        # the log cadence's batched device_get).
        reg = get_registry()
        psum = reg.gauge(
            "r2d2dpg_replay_shard_priority_sum",
            "raw priority sum of one replay shard (the quota weight is "
            "sum p^alpha — ReplayShard.scaled_sum)",
            labelnames=("shard",),
        )
        occ = reg.gauge(
            "r2d2dpg_replay_shard_occupancy",
            "filled slots of one replay shard",
            labelnames=("shard",),
        )
        for i, s in enumerate(self.shards):
            psum.labels(shard=str(i)).set_fn(s.priority_sum)
            occ.labels(shard=str(i)).set_fn(s.occupancy)

    @property
    def num_shards(self) -> int:
        return len(self.shards)

    def route(self, actor_id: Any) -> int:
        return shard_for_actor(actor_id, len(self.shards))

    def add(self, shard_id: int, msg: Dict[str, Any]) -> int:
        """One SEQS message into its shard (handler-thread side): the
        staged sequences enter the ring (None priorities resolve to the
        shard's max — the central "max" entry semantics), the accounting
        deltas enter the bank.  Never sheds: a full ring FIFO-evicts."""
        staged: StagedSequences = msg["staged"]
        # msg["actor_id"] is the HELLO-authenticated identity — the
        # ingest handler overwrites any payload-carried claim before the
        # message reaches this fold (the PR 6 TELEM posture), so the
        # slot's actor code can never be spoofed from a SEQS body.
        actor = msg.get("actor_id")
        n = self.shards[shard_id].add(
            staged.seq,
            staged.priorities,
            behavior=staged.behavior_version,
            collect=staged.collect_id,
            actor=None if actor is None else actor_code(actor),
        )
        self.bank_stats(msg)
        return n

    def bank_stats(self, msg: Dict[str, Any]) -> None:
        """Bank one message's accounting deltas (the K_STATS control
        frame's landing spot on the split-plane wire, ISSUE 17 — same
        bank ``add`` feeds on the forwarded path)."""
        with self._stats_lock:
            for k in self._stats:
                self._stats[k] += float(msg.get(k, 0.0))

    def pop_stats(self) -> Dict[str, float]:
        with self._stats_lock:
            out = dict(self._stats)
            for k in self._stats:
                self._stats[k] = 0.0
        return out

    def occupancy_total(self) -> int:
        return sum(s.occupancy() for s in self.shards)

    def scaled_sums(self) -> np.ndarray:
        return np.asarray([s.scaled_sum() for s in self.shards], np.float64)

    def evictions_total(self) -> int:
        return sum(s.evictions_total for s in self.shards)


class _PrefetchPull:
    """One background pull (``--shard-prefetch 1``): phase ``p+1``'s
    two-level draw/encode/transit overlaps phase ``p``'s compiled learn
    step, the way the pipelined executor overlaps collect.  Exactly one
    pull is ever in flight (kicked only after the previous completed),
    so the learner's np_rng stays a sequentially-consumed stream — same
    draws as the unprefetched schedule.  Daemon thread: a pull stuck on
    a dead tier must never pin process exit."""

    def __init__(self, fn: Callable[[], Any]):
        self._result: Any = None
        self._error: Optional[BaseException] = None
        self._done = threading.Event()
        self._thread = threading.Thread(
            target=self._run, args=(fn,), name="sampler-prefetch", daemon=True
        )
        self._thread.start()

    def _run(self, fn) -> None:
        try:
            self._result = fn()
        except BaseException as e:  # noqa: BLE001 - re-raised at result()
            self._error = e
        finally:
            self._done.set()

    def result(self) -> Any:
        self._done.wait()
        if self._error is not None:
            raise self._error
        return self._result


class SamplerLearner:
    """The learner side of in-network sampling (``--replay-shards N``).

    Mirrors ``FleetLearner``'s lifecycle (start/run/close, counters,
    checkpoint sidecar, param publication, chaos ``phase_fn`` hook) but
    replaces the drain loop with a PULL loop: no staging queue, no device
    arena on the hot path — each train phase assembles ``K`` batches of
    ``batch_size`` from the shards through the SAMPLE_REQ/BATCH loopback
    codecs and runs one compiled K-update program on them, then writes
    TD priorities back through PRIO frames.

    The learner free-runs at its own pace (the Ape-X relation): phases
    are not arrival-paced, so the data-to-update ratio floats with the
    collection/consumption balance — a *different, equally valid*
    trajectory class than the phase-locked schedule, like the fleet
    itself (docs/REPLAY.md "Pacing").
    """

    def __init__(
        self,
        trainer: Trainer,
        config: FleetConfig,
        *,
        num_shards: int,
        total_capacity: Optional[int] = None,
        shard_set=None,
    ):
        if trainer.axis is not None:
            raise ValueError(
                "SamplerLearner needs a host-visible learn boundary; "
                "shard_map trainers fuse whole phases — use the base "
                "Trainer"
            )
        if config.num_actors < 1:
            raise ValueError(
                "SamplerLearner requires num_actors >= 1 (replay shards "
                "are fed by actor SEQS traffic)"
            )
        if num_shards < 1:
            raise ValueError("num_shards must be >= 1")
        if config.drain_coalesce != 1:
            raise ValueError(
                "--drain-coalesce shapes the central drain the sampler "
                "path replaces; it does not compose with --replay-shards"
            )
        # The shards own the REPLAY capacity; train.py shrinks the
        # trainer's (unused) device arena in sampler mode and passes the
        # experiment's real capacity here instead.
        cap = (
            int(total_capacity)
            if total_capacity is not None
            else trainer.config.capacity
        )
        if cap % num_shards:
            raise ValueError(
                f"replay capacity {cap} not divisible by {num_shards} "
                f"shards (each shard owns an equal slice)"
            )
        config.wire.validate()
        self.trainer = trainer
        self.config = config
        self.num_shards = num_shards
        # Where replay LIVES is deployment, not semantics (ISSUE 12): the
        # default is the in-learner loopback ShardSet (PR 10's path,
        # pinned bit-identical through the CLI); a ``shard_set`` — the
        # standalone tier's RemoteShardSet (fleet/shard.py, behind
        # train.py --shard-procs N) — swaps every shard interaction onto
        # real sockets while this class's lifecycle stays identical.
        self._remote = shard_set is not None
        if self._remote:
            if shard_set.num_shards != num_shards:
                raise ValueError(
                    f"shard_set has {shard_set.num_shards} shards, "
                    f"expected {num_shards}"
                )
            self.shards = shard_set
        else:
            self.shards = ShardSet(
                num_shards,
                cap // num_shards,
                alpha=trainer.config.priority_alpha,
                prioritized=trainer.config.prioritized,
            )
        # Direct data plane (ISSUE 17): with a standalone tier, the
        # ingest acks advertise each actor's shard assignment + address
        # so actors ship SEQS straight to their shard; in-learner shards
        # have no dialable address — the fn stays None and actors keep
        # forwarding (the documented fallback).
        assignment_fn = None
        if config.shard_direct and self._remote:
            assignment_fn = self.shards.assignment_for
        # Sampling-boundary concurrency (ISSUE 17): N pullers over M
        # shards, one in-flight SAMPLE_REQ per live shard per quota
        # round.  0 = auto (min(shards, 8)); 1 = the serial control leg.
        if config.shard_pullers < 0:
            raise ValueError("shard_pullers must be >= 0")
        self._pullers = (
            int(config.shard_pullers)
            if config.shard_pullers > 0
            else min(num_shards, 8)
        )
        # The ingest server routes SEQS straight into the shards; its
        # staging queue exists only structurally (nothing ever enqueues,
        # so nothing can shed — ring eviction is the backpressure).
        self.queue: "queue.Queue" = queue.Queue(maxsize=config.queue_depth)
        self.server = IngestServer(
            self.queue,
            address=config.address,
            shed_after_s=config.shed_after_s,
            startup_shed_grace_s=config.startup_shed_grace_s,
            max_frame_bytes=config.max_frame_bytes,
            wire_config=config.wire,
            read_deadline_s=config.heartbeat_s,
            warmup_deadline_s=config.warmup_deadline_s,
            auth_token=config.auth_token,
            shards=self.shards,
            expected_actors=config.num_actors,
            shard_assignment_fn=assignment_fn,
        )
        # Loopback frame codecs, one packer/unpacker pair per direction
        # (the sampler loop is the only caller — single-threaded).  The
        # negotiated fleet lane applies, so the counted bytes are exactly
        # what a cross-process shard would put on a real socket; on the
        # default f32/none lane the roundtrip is bit-exact.
        self._req_packer = wire.TreePacker(
            config.wire, max_frame_bytes=config.max_frame_bytes
        )
        self._req_unpacker = wire.TreeUnpacker(
            max_frame_bytes=config.max_frame_bytes
        )
        self._batch_packer = wire.TreePacker(
            config.wire, max_frame_bytes=config.max_frame_bytes
        )
        self._batch_unpacker = wire.TreeUnpacker(
            max_frame_bytes=config.max_frame_bytes
        )
        # dp-mesh composition (ISSUE 11, docs/TOPOLOGY.md): a
        # DPLearnerTrainer replicates train and shards the pulled batch
        # over dp via _put_staged(axis=1) below.  Pinning the outputs to
        # the replicated layout keeps the donated chain's avals stable
        # (the FleetLearner drain's out_shardings discipline); None for
        # single-device trainers.
        self._replicated = getattr(trainer, "_replicated", None)
        learn_kwargs: Dict[str, Any] = {"donate_argnums": (0,)}
        if self._replicated is not None:
            learn_kwargs["out_shardings"] = (
                self._replicated, self._replicated, self._replicated
            )
        self._learn_prog = jax.jit(self._learn_impl, **learn_kwargs)
        self._req_id = 0
        self._phase_stall_s = 0.0  # per-pull dead-tier wait side channel
        self.sample_bytes_total = 0  # SAMPLE_REQ + BATCH + PRIO, with headers
        self.trained_seqs_total = 0
        # Quality-fold context (ISSUE 18): (published param version,
        # drained phases) as of the last run-loop iteration — the pull
        # fold reads it to turn provenance into lag/age without touching
        # the device (beta is reconstructed from the phase clock, K
        # updates per phase, exactly the annealed schedule).
        self._quality_ctx = (0, 0)
        reg = get_registry()
        # Two DISTINCT waits, two histograms: the one-off cold-start /
        # resume absorb (expected to take tens of seconds — compile +
        # actor spawn) and mid-run pull stalls (a live-but-empty or dead
        # shard tier).  Folding the absorb into the wait histogram made
        # its p99 equal the absorb duration for the whole run, so the
        # /health learner_starving rule read every sampler run as
        # permanently starving off its single cold-start sample.
        self.sampler_wait = reg.histogram(
            "r2d2dpg_sampler_wait_seconds",
            "seconds the pull loop stalled waiting for a live non-empty "
            "shard, one sample PER PHASE (zeros included, so a past "
            "outage decays out of the p99 — the /health learner_starving "
            "input; cold-start absorb is r2d2dpg_sampler_absorb_seconds)",
        )
        self.sampler_absorb = reg.histogram(
            "r2d2dpg_sampler_absorb_seconds",
            "absorb-to-min_replay wait, one sample per incarnation "
            "(cold start and --resume re-entry)",
        )
        self.sample_assemble = reg.histogram(
            "r2d2dpg_sampler_sample_seconds",
            "one phase's SAMPLE_REQ -> stacked-batch assembly (pack, "
            "shard draws, decode, stack)",
        )
        self.puller_wait = reg.histogram(
            "r2d2dpg_sampler_puller_wait_seconds",
            "one puller's SAMPLE_REQ -> BATCH exchange wall time, one "
            "sample per per-shard draw (N concurrent pullers overlap "
            "these; the serial control leg sums them)",
        )
        self._obs_trained = reg.counter(
            "r2d2dpg_sampler_trained_seqs_total",
            "sequences pulled across the sampling boundary into training",
        )
        self._obs_bytes = reg.counter(
            "r2d2dpg_sampler_bytes_total",
            "bytes crossing the sampling boundary (SAMPLE_REQ + BATCH + "
            "PRIO frames, headers included)",
        )
        if self._remote:
            # The honest sampling-boundary byte count now includes real
            # socket traffic (REQ/BATCH/PRIO + their acks + HELLOs).
            self.shards.bind_sample_bytes(self._obs_bytes.inc)
        self._stats: Dict[str, float] = {}
        self._counters: Dict[str, float] = {}

    # ------------------------------------------------------------- lifecycle
    def start(self) -> str:
        self.server.start()
        return self.server.connect_address

    def close(self) -> None:
        self.server.stop()

    def stats(self) -> Dict[str, float]:
        return dict(self._stats)

    def counters(self) -> Dict[str, float]:
        return dict(self._counters)

    # ------------------------------------------------------- device program
    def _learn_impl(self, train, seqs: SequenceBatch, probs, size, key):
        """K importance-weighted updates on pulled batches.

        ``seqs`` leaves are ``[K, B, ...]``, ``probs`` the COMBINED
        two-level probabilities ``[K, B]``, ``size`` the fleet-wide
        occupancy (the N of the IS correction).  Same anneal / weight /
        smoothing-key semantics as ``Trainer._update_step`` — only the
        sample source moved; there is no arena scatter because priorities
        ride back to the shards host-side."""
        t = self.trainer
        cfg = t.config
        keys = jax.random.split(key, cfg.learner_steps)

        def one(train, inp):
            batch, p, k = inp
            kl = jax.random.fold_in(k, 1)
            if cfg.prioritized:
                beta = anneal_beta(
                    train.step, beta0=cfg.beta0, steps=cfg.beta_steps
                )
                w = importance_weights(p, size, beta=beta)
            else:
                w = jnp.ones((cfg.batch_size,))
            train, prios, metrics = t.agent.learner_step(
                train, t._reshard_batch(batch), w, key=kl
            )
            return train, (prios, metrics)

        train, (prios, metrics) = lax.scan(one, train, (seqs, probs, keys))
        metrics = jax.tree_util.tree_map(lambda m: m.mean(), metrics)
        return train, prios, metrics

    # ------------------------------------------------------- sample assembly
    def _roundtrip(self, unpacker, parts) -> Any:
        """One loopback frame (already packed ``parts``): count its
        honest wire bytes (header included), decode through the real
        unpacker.  This IS the cross-process hot path minus the
        socket."""
        payload = b"".join(bytes(p) for p in parts)
        n = transport.HEADER_BYTES + len(payload)
        self.sample_bytes_total += n
        self._obs_bytes.inc(n)
        return unpacker.unpack(payload)

    def _fold_quality(
        self, behavior, collect, actors, probs, occupancy
    ) -> None:
        """Quality-plane fold at the batch-assembly site (ISSUE 18).

        Everything here is host numpy the pull already holds — zero new
        device fetches.  Lag/age disarm on absent provenance (the -1
        sentinel masks out inside ``policy_lags``/``replay_ages``); beta
        is reconstructed from the phase clock (exactly K updates per
        drained phase, so ``step = phase * K`` matches the in-graph
        anneal bit-for-bit as a float schedule)."""
        plane = get_quality_plane()
        version, phase = self._quality_ctx
        if behavior is not None:
            plane.observe_lags(policy_lags(version, behavior))
        if collect is not None:
            plane.observe_ages(replay_ages(phase, collect))
        cfg = self.trainer.config
        if cfg.prioritized:
            step = phase * cfg.learner_steps
            frac = min(step / max(cfg.beta_steps, 1), 1.0)
            beta = cfg.beta0 + (1.0 - cfg.beta0) * frac
        else:
            beta = 0.0
        plane.observe_probs(probs, occupancy, beta)
        if actors is not None:
            a = np.asarray(actors, np.int64).ravel()
            a = a[a != PROVENANCE_ABSENT]
            if a.size:
                codes, counts = np.unique(a, return_counts=True)
                for c, n in zip(codes, counts):
                    plane.note_trained(str(int(c)), int(n))

    def _pull_phase_batches(
        self, n_draws: int, rng: np.random.Generator, tr=None
    ):
        """One phase's two-level pull: quotas ∝ advertised Σp^α, one
        SAMPLE_REQ/BATCH exchange per non-empty shard, PRIO handles and
        combined probabilities assembled for the learn program.

        Returns ``(seq [n,...], probs [n], handles, occupancy_total)``
        with the concatenated draws PERMUTED (seeded) before the caller
        reshapes to ``[K, B]`` — quota counts are per shard, and without
        the shuffle update k would correlate with shard identity.

        ``tr`` is the phase's sampled trace (ISSUE 13): on the remote
        path its id rides each SAMPLE_REQ's 32B sidecar so the shard
        procs stamp their own hops into the same trace; the loopback has
        no process boundary to trace (the sampler chain covers it).

        Side channel: ``self._phase_stall_s`` accumulates any dead-tier
        wait this pull spent (remote path only; the loopback cannot
        stall).  The caller observes it into ``sampler_wait`` ONCE PER
        PHASE, zeros included — a rare 30s outage sample would otherwise
        sit at the window's p99 indefinitely and keep /health reading a
        long-recovered incident as starving-now."""
        self._phase_stall_s = 0.0
        if self._remote:
            return self._pull_phase_batches_remote(n_draws, rng, tr)
        sums = self.shards.scaled_sums()
        quotas = shard_quotas(sums, n_draws, rng)
        total = float(sums.sum())
        seqs: List[SequenceBatch] = []
        probs: List[np.ndarray] = []
        handles: List[tuple] = []  # (shard, slots, gens) per response
        prov: List[tuple] = []  # (behavior, collect, actors) per response
        for shard_id, quota in enumerate(quotas):
            if quota == 0:
                continue
            self._req_id += 1
            req = wire.unpack_sample_req(
                self._roundtrip(
                    self._req_unpacker,
                    wire.pack_sample_req(
                        self._req_packer,
                        req_id=self._req_id,
                        shard=shard_id,
                        quota=int(quota),
                    ),
                )
            )
            shard = self.shards.shards[req["shard"]]
            s = shard.sample(req["quota"], rng)
            resp = wire.unpack_shard_batch(
                self._roundtrip(
                    self._batch_unpacker,
                    wire.pack_shard_batch(
                        self._batch_packer,
                        req_id=req["req_id"],
                        shard=req["shard"],
                        staged=StagedSequences(seq=s.seq, priorities=None),
                        slots=s.slots,
                        gens=s.gens,
                        probs=s.probs,
                        priority_sum=shard.scaled_sum(),
                        occupancy=shard.occupancy(),
                        behavior=s.behavior,
                        collect=s.collect,
                        actors=s.actors,
                    ),
                )
            )
            seqs.append(resp["staged"].seq)
            probs.append(
                combine_probs(resp["probs"], float(sums[shard_id]), total)
            )
            handles.append((req["shard"], resp["slots"], resp["gens"]))
            prov.append(_resp_provenance(resp))
        seq = jax.tree_util.tree_map(
            lambda *xs: np.concatenate([np.asarray(x) for x in xs], axis=0),
            *seqs,
        )
        prob = np.concatenate(probs)
        shard_of = np.concatenate(
            [np.full(len(h[1]), h[0], np.int64) for h in handles]
        )
        slots = np.concatenate([h[1] for h in handles])
        gens = np.concatenate([h[2] for h in handles])
        perm = rng.permutation(n_draws)
        seq = jax.tree_util.tree_map(lambda x: x[perm], seq)
        occ_total = self.shards.occupancy_total()
        # Quality fold AT the assembly site (permutation-invariant): the
        # combined probs + provenance arrays are already on the host.
        self._fold_quality(
            np.concatenate([p[0] for p in prov]),
            np.concatenate([p[1] for p in prov]),
            np.concatenate([p[2] for p in prov]),
            prob,
            occ_total,
        )
        return (
            seq,
            prob[perm],
            (shard_of[perm], slots[perm], gens[perm]),
            occ_total,
        )

    def _pull_phase_batches_remote(
        self, n_draws: int, rng: np.random.Generator, tr=None
    ):
        """The ``--shard-procs`` pull: same two-level math, real sockets,
        plus the graceful-degradation contract — a shard whose exchange
        fails mid-phase is marked dead, its quota redistributed over the
        SURVIVORS' advertised Σp^α within this very phase (the
        renormalization acceptance), and a fully-dead tier is waited out
        (bounded by ``idle_timeout_s``) while the supervisor restarts it.
        Handles carry each batch's shard EPOCH so the write-back can
        fence a restart that happens between sample and verdict."""
        from r2d2dpg_tpu.fleet.shard import ShardUnavailableError

        shards = self.shards
        shards.maybe_rejoin()
        seqs: List[SequenceBatch] = []
        probs: List[np.ndarray] = []
        shard_of: List[np.ndarray] = []
        slots: List[np.ndarray] = []
        gens: List[np.ndarray] = []
        epochs: List[np.ndarray] = []
        prov: List[tuple] = []  # (behavior, collect, actors) per response
        remaining = int(n_draws)
        deadline = time.monotonic() + self.config.idle_timeout_s
        stall_t0: Optional[float] = None
        while remaining > 0:
            sums = shards.scaled_sums()
            total = float(sums.sum())
            if total <= 0.0:
                # Every shard dead or freshly-rejoined-empty: degrade by
                # WAITING (sampling stalls, training pauses, actors keep
                # streaming into re-routed/absorbing shards) — never by
                # fabricating draws.
                if stall_t0 is None:
                    stall_t0 = time.monotonic()
                if time.monotonic() >= deadline:
                    raise RuntimeError(
                        "sampler starved: no live non-empty replay shard "
                        "to draw from (shard tier down past "
                        f"{self.config.idle_timeout_s:.0f}s — check "
                        "flight.jsonl for shard_dead/shard_crash events)"
                    )
                shards.maybe_rejoin()
                time.sleep(0.1)
                continue
            if stall_t0 is not None:
                # Banked into this PHASE's wait sample (see the caller):
                # the mid-run learner-starving signal /health judges.
                self._phase_stall_s += time.monotonic() - stall_t0
                stall_t0 = None
            quotas = shard_quotas(sums, remaining, rng)
            remaining = 0
            # Concurrent pullers (ISSUE 17): one quota round = one job
            # per non-empty shard, req_ids assigned in SHARD-ID ORDER
            # BEFORE any exchange dispatches and results processed in
            # shard-id order after the join — the learner rng is consumed
            # only by shard_quotas above and the final permutation, so
            # arrival order cannot reach any seeded draw (the puller
            # determinism pin, tests/test_shard_direct.py).
            jobs: List[tuple] = []  # (shard_id, quota, req_id, req_tr)
            for shard_id, quota in enumerate(quotas):
                if quota == 0:
                    continue
                self._req_id += 1
                req_tr = None
                if tr is not None:
                    # A fresh stamp per REQ, sharing the phase's trace id:
                    # the sidecar's collect-start slot carries the REQ's
                    # birth time, and the packer stamps encode-end — the
                    # shard's req_receive hop starts where that stamp
                    # ends (obs/trace.py SHARD_HOPS).
                    req_tr = obs_trace.TraceStamp(
                        trace_id=tr.trace_id, t_collect_start=time.time()
                    )
                jobs.append((shard_id, int(quota), self._req_id, req_tr))
            for (shard_id, quota, _, _), outcome in zip(
                jobs, self._exchange_jobs(shards, jobs)
            ):
                if isinstance(outcome, ShardUnavailableError):
                    # The mid-phase degradation moment: the dead shard's
                    # draws go back into the pool; the NEXT loop
                    # iteration's quota draw sees its weight zeroed
                    # (``_mark_dead`` records the renormalization) — the
                    # phase still delivers its full n_draws, from the
                    # survivors.
                    shards._mark_dead(shard_id, str(outcome))
                    flight_event(
                        "shard_draws_redistributed",
                        shard=shard_id,
                        redistributed_draws=int(quota),
                    )
                    remaining += int(quota)
                    continue
                resp = outcome
                if resp is None:
                    # LIVE but empty (a stale quota weight met a freshly
                    # restarted ring): not a death — the ack's advert
                    # zeroed its weight, so the re-draw below lands on
                    # shards that actually hold data.
                    remaining += int(quota)
                    continue
                seqs.append(resp["staged"].seq)
                probs.append(
                    combine_probs(resp["probs"], float(sums[shard_id]), total)
                )
                n_got = int(resp["slots"].shape[0])
                shard_of.append(np.full(n_got, shard_id, np.int64))
                slots.append(np.asarray(resp["slots"], np.int64))
                gens.append(np.asarray(resp["gens"], np.int64))
                epochs.append(np.full(n_got, int(resp["epoch"]), np.int64))
                prov.append(_resp_provenance(resp))
        seq = jax.tree_util.tree_map(
            lambda *xs: np.concatenate([np.asarray(x) for x in xs], axis=0),
            *seqs,
        )
        perm = rng.permutation(n_draws)
        seq = jax.tree_util.tree_map(lambda x: x[perm], seq)
        prob = np.concatenate(probs)
        occ_total = self.shards.occupancy_total()
        self._fold_quality(
            np.concatenate([p[0] for p in prov]),
            np.concatenate([p[1] for p in prov]),
            np.concatenate([p[2] for p in prov]),
            prob,
            occ_total,
        )
        return (
            seq,
            prob[perm],
            (
                np.concatenate(shard_of)[perm],
                np.concatenate(slots)[perm],
                np.concatenate(gens)[perm],
                np.concatenate(epochs)[perm],
            ),
            occ_total,
        )

    def _exchange_jobs(self, shards, jobs: List[tuple]) -> List[Any]:
        """Run one quota round's SAMPLE_REQ/BATCH exchanges — results in
        JOB ORDER regardless of arrival order.

        ``--shard-pullers 1`` (the serial control leg) runs them inline,
        exactly the pre-ISSUE-17 loop; otherwise up to ``self._pullers``
        exchanges are in flight at once, one per shard (each RemoteShard
        owns its own socket + leg lock, so per-shard exchanges never
        contend).  A dead shard's ``ShardUnavailableError`` is an OUTCOME
        (the caller redistributes its quota); anything else re-raises on
        the caller's thread.  Every exchange lands one sample in the
        puller-wait histogram — the overlap this buys is the gap between
        its sum and the phase's assemble time."""
        from r2d2dpg_tpu.fleet.shard import ShardUnavailableError

        def one(shard_id: int, quota: int, req_id: int, req_tr) -> Any:
            t0 = time.monotonic()
            try:
                return shards.shards[shard_id].sample(
                    quota, req_id, trace=req_tr
                )
            except ShardUnavailableError as e:
                return e
            finally:
                self.puller_wait.add(time.monotonic() - t0)

        if self._pullers <= 1 or len(jobs) <= 1:
            return [one(*job) for job in jobs]
        results: List[Any] = [None] * len(jobs)
        errors: List[BaseException] = []
        sem = threading.BoundedSemaphore(self._pullers)

        def work(i: int, job: tuple) -> None:
            with sem:
                try:
                    results[i] = one(*job)
                except BaseException as e:  # noqa: BLE001 - re-raised below
                    errors.append(e)

        threads = [
            threading.Thread(
                target=work,
                args=(i, job),
                name=f"sampler-puller-{job[0]}",
                daemon=True,
            )
            for i, job in enumerate(jobs)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        if errors:
            raise errors[0]
        return results

    def _write_back_remote(self, handles, prios: np.ndarray) -> None:
        """TD write-back to standalone shards, grouped per (shard, epoch):
        a shard that died since the sample drops its verdict loudly
        (re-collectable, like the experience itself), and handles whose
        epoch no longer matches the shard's live incarnation are fenced
        LEARNER-side before a byte crosses — the shard's own epoch check
        (``ShardServer``) remains the authoritative backstop."""
        from r2d2dpg_tpu.fleet.shard import ShardUnavailableError

        shard_of, slots, gens, epochs = handles
        prios = np.asarray(prios, np.float32).reshape(-1)
        for shard_id in np.unique(shard_of):
            sh = self.shards.shards[int(shard_id)]
            m_shard = shard_of == shard_id
            for ep in np.unique(epochs[m_shard]):
                m = m_shard & (epochs == ep)
                if not sh.alive:
                    flight_event(
                        "prio_dropped_shard_dead",
                        shard=int(shard_id),
                        entries=int(m.sum()),
                    )
                    continue
                if sh.epoch != int(ep):
                    flight_event(
                        "stale_epoch_prio_dropped",
                        shard=int(shard_id),
                        got_epoch=int(ep),
                        epoch=sh.epoch,
                        entries=int(m.sum()),
                    )
                    continue
                # Coalesced write-back (ISSUE 17): with-replacement draws
                # repeat (slot, gen) keys within a phase — dedupe to the
                # LAST write (sequential application is last-write-wins)
                # so one (shard, epoch) PRIO frame carries each key once.
                c_slots, c_gens, c_prios = wire.coalesce_prio_update(
                    slots[m], gens[m], prios[m]
                )
                try:
                    sh.write_back(
                        c_slots, c_gens, c_prios, epoch=int(ep)
                    )
                except ShardUnavailableError as e:
                    self.shards._mark_dead(int(shard_id), str(e))
                    flight_event(
                        "prio_dropped_shard_dead",
                        shard=int(shard_id),
                        entries=int(m.sum()),
                    )

    def _write_back(self, handles, prios: np.ndarray) -> None:
        """TD write-back through PRIO frames, grouped per shard; stale
        generations (ring-evicted slots) are ignored shard-side."""
        if self._remote:
            return self._write_back_remote(handles, prios)
        shard_of, slots, gens = handles
        prios = np.asarray(prios, np.float32).reshape(-1)
        for shard_id in np.unique(shard_of):
            m = shard_of == shard_id
            # Same coalesce as the remote path: one PRIO frame per shard
            # per phase, each (slot, gen) key once (last write wins).
            c_slots, c_gens, c_prios = wire.coalesce_prio_update(
                slots[m], gens[m], prios[m]
            )
            upd = wire.unpack_prio_update(
                self._roundtrip(
                    self._req_unpacker,
                    wire.pack_prio_update(
                        self._req_packer,
                        shard=int(shard_id),
                        slots=c_slots,
                        gens=c_gens,
                        priorities=c_prios,
                    ),
                )
            )
            if upd["shard"] >= self.num_shards:
                # The codec checks >= 0; the upper bound is deployment
                # state only this side knows.  Unreachable via the
                # loopback (we packed it), load-bearing the day a remote
                # shard speaks these frames.
                raise wire.WireFormatError(
                    f"PRIO shard {upd['shard']} outside fleet of "
                    f"{self.num_shards}"
                )
            self.shards.shards[upd["shard"]].update_priorities(
                upd["slots"], upd["gens"], upd["priorities"]
            )

    # ------------------------------------------------------------------- run
    def run(
        self,
        num_train_phases: int,
        state: Optional[TrainerState] = None,
        log_every: int = 50,
        log_fn=print,
        metrics_fn: Optional[Callable[[int, Dict[str, float]], None]] = None,
        minutes: Optional[float] = None,
        ckpt=None,
        checkpoint_every: int = 0,
        resume_from: Optional[Dict[str, float]] = None,
        phase_fn: Optional[Callable[[int], None]] = None,
        trace_sample: float = 0.0,
    ) -> TrainerState:
        """Wait for ``min_replay`` resident sequences across the shards,
        then run ``num_train_phases`` pull-learn phases (K·B two-level
        draws + K compiled updates + PRIO write-back each).  Same
        checkpoint/resume/counter contract as ``FleetLearner.run`` (the
        shards, like the central arena, are never checkpointed: a
        resumed learner re-fills them from live actors)."""
        if self.server.address is None:
            raise RuntimeError("call start() before run()")
        t = self.trainer
        cfg = t.config
        # Device plane (ISSUE 14): the pull loop owns the run window.
        mon = get_device_monitor().install()
        mon.begin_run()
        state = t.init() if state is None else state
        cstate, lstate = split_state(state)
        train = lstate.train
        rng = lstate.rng
        np_rng = np.random.default_rng(cfg.seed)
        deadline = (
            time.monotonic() + minutes * 60 if minutes is not None else None
        )
        self.sampler_wait.reset()
        self.sampler_absorb.reset()
        self.sample_assemble.reset()
        self.puller_wait.reset()
        resume_from = resume_from or {}
        version = int(resume_from.get("param_version", 0)) + 1
        self.server.publish_params(version, self._snapshot_params(train))

        n_draws = cfg.learner_steps * cfg.batch_size
        drained = int(resume_from.get("drained", 0))
        drained_at_start = drained
        last_metrics: Dict[str, Any] = {}
        ep_ret_sum = float(resume_from.get("ep_return_sum", 0.0))
        ep_count = float(resume_from.get("ep_count", 0.0))
        env_steps_total = float(resume_from.get("env_steps_total", 0.0))
        episodes_total = float(resume_from.get("episodes_total", 0.0))
        t0 = time.monotonic()
        train_t0: Optional[float] = None
        marked_steady = False

        def emit_log(phase: int, scalars: Dict[str, float]) -> None:
            if metrics_fn is not None:
                metrics_fn(phase, scalars)
                return
            log_fn(
                f"sampler phase {phase}/{num_train_phases} "
                + " ".join(f"{k} {v:.3g}" for k, v in scalars.items())
            )

        def fold_stats() -> None:
            nonlocal env_steps_total, ep_ret_sum, ep_count, episodes_total
            s = self.shards.pop_stats()
            env_steps_total += s["env_steps_delta"]
            ep_ret_sum += s["ep_return_sum"]
            ep_count += s["ep_count"]
            episodes_total += s["ep_count"]

        try:
            # ------------------------------------------------ absorb phase
            # The recovery contract's re-entry point too: a resumed
            # learner waits here while reconnecting actors refill shards.
            last_growth = time.monotonic()
            last_occ = -1
            t_wait = time.monotonic()
            # Direct data plane (ISSUE 17): SEQS bypass the learner, so
            # no forward ack refreshes the occupancy view — poke the
            # shards' adverts over the sampler leg or the gate would
            # starve against a tier the actors are actively filling.
            poke_adverts = (
                bool(self.config.shard_direct)
                and self._remote
                and hasattr(self.shards, "refresh_adverts")
            )
            last_poke = 0.0
            while self.shards.occupancy_total() < cfg.min_replay:
                if deadline is not None and time.monotonic() >= deadline:
                    break
                if poke_adverts and time.monotonic() - last_poke >= 0.25:
                    self.shards.refresh_adverts()
                    last_poke = time.monotonic()
                occ = self.shards.occupancy_total()
                if occ != last_occ:
                    last_occ = occ
                    last_growth = time.monotonic()
                # Cold start pays actor spawn + jax import + collect
                # compile — double the steady bound, like the drain loop.
                bound = self.config.idle_timeout_s * (2.0 if occ == 0 else 1.0)
                if time.monotonic() - last_growth > bound:
                    raise RuntimeError(
                        f"sampler starved: shard occupancy stuck at {occ} "
                        f"for {bound:.0f}s — are the actors alive? "
                        f"(check flight.jsonl)"
                    )
                time.sleep(0.05)
            self.sampler_absorb.add(time.monotonic() - t_wait)

            # Batch prefetch (ISSUE 17, --shard-prefetch 1): pull phase
            # p+1 on a background thread while phase p learns.  The
            # np_rng stays sequential (one pull in flight, ever) so the
            # DRAWS are anchor-identical; what moves by one phase is the
            # write-back visibility — phase p+1 samples against
            # priorities that do not yet reflect phase p's TD verdict
            # (stale-by-one, the documented overlap tradeoff, docs/
            # REPLAY.md "Direct data plane").  0 (default) keeps the
            # strict pull->learn->write-back interleave.
            prefetch_on = bool(self.config.shard_prefetch) and self._remote
            pending: Optional[_PrefetchPull] = None

            def pull_once() -> Dict[str, Any]:
                tr = obs_trace.maybe_start(trace_sample)
                t_req = time.time()
                t_assemble = time.monotonic()
                out = self._pull_phase_batches(n_draws, np_rng, tr)
                return {
                    "out": out,
                    "tr": tr,
                    "t_req": t_req,
                    "assemble_s": time.monotonic() - t_assemble,
                    "stall_s": self._phase_stall_s,
                }

            while drained < num_train_phases:
                if deadline is not None and time.monotonic() >= deadline:
                    break
                fold_stats()
                mon.on_phase(drained + 1)
                # The pull fold's clock view (published version + phase);
                # a prefetched pull reads the previous iteration's pair —
                # one phase of skew, same as the sample it describes.
                self._quality_ctx = (version, drained)
                if pending is not None:
                    pulled, pending = pending.result(), None
                else:
                    pulled = pull_once()
                if (
                    prefetch_on
                    and drained + 1 < num_train_phases
                    and (deadline is None or time.monotonic() < deadline)
                ):
                    pending = _PrefetchPull(pull_once)
                tr = pulled["tr"]
                t_req = pulled["t_req"]
                seq_np, probs_np, handles, occ = pulled["out"]
                t_batches = time.time()
                self.sample_assemble.add(pulled["assemble_s"])
                # One wait sample per PHASE, zeros included (see the
                # _pull_phase_batches docstring): stall-free phases
                # dilute and eventually evict a past outage's sample, so
                # the /health p99 answers "starving NOW", not "ever".
                self.sampler_wait.add(pulled["stall_s"])
                # [n] -> [K, B] for the compiled K-update scan, then
                # mesh placement through the _put_staged hook on the
                # BATCH axis (axis=1): under --learner-dp each dp slice
                # receives its B/D rows here, at device_put time, so the
                # learn program's _reshard_batch constraint is already
                # satisfied — the BATCH frames from M shards land
                # per-dp-slice with no central reshard hop (identity for
                # single-device trainers; docs/TOPOLOGY.md).
                seqs = t._put_staged(
                    jax.tree_util.tree_map(
                        lambda x: np.reshape(
                            x,
                            (cfg.learner_steps, cfg.batch_size)
                            + x.shape[1:],
                        ),
                        seq_np,
                    ),
                    axis=1,
                )
                probs = t._put_staged(
                    np.reshape(
                        probs_np.astype(np.float32),
                        (cfg.learner_steps, cfg.batch_size),
                    ),
                    axis=1,
                )
                size = np.float32(occ)
                if self._replicated is not None:
                    # Scalars replicate explicitly so every learn input
                    # shares the mesh's device set (uncommitted host
                    # scalars would otherwise default single-device).
                    size = jax.device_put(size, self._replicated)
                rng, key = jax.random.split(rng)
                with mon.program("sampler_learn"):
                    train, prios_dev, last_metrics = self._learn_prog(
                        train, seqs, probs, size, key
                    )
                t_dispatch = time.time()
                # ONE host fetch per phase: the write-back priorities
                # must come back to the host-side shards (there is no
                # in-graph arena scatter on this path).  The blocking
                # fetch also makes the learn hop honest for free.
                prios = jax.device_get(prios_dev)
                t_learn_done = time.time()
                self._write_back(handles, prios)
                self.trained_seqs_total += n_draws
                self._obs_trained.inc(n_draws)
                if tr is not None:
                    # The sampler-path trace chain (obs/trace.py): the
                    # two new hops + learn, recorded together
                    # (all-or-nothing, like the 8-hop wire chain).
                    obs_trace.record_hop(
                        "sample_req", t_req, t_batches, tr.trace_id,
                        draws=n_draws,
                    )
                    obs_trace.record_hop(
                        "batch_return", t_batches, t_dispatch,
                        tr.trace_id, seqs=n_draws,
                    )
                    obs_trace.record_hop(
                        "learn", t_dispatch, t_learn_done, tr.trace_id
                    )
                drained += 1
                if train_t0 is None:
                    jax.block_until_ready(train.step)
                    train_t0 = time.monotonic()
                if not marked_steady:
                    self.server.mark_steady()
                    # The pull-learn program is warm: the compile
                    # sentinel arms (obs/device.py).
                    mon.mark_steady()
                    marked_steady = True
                if phase_fn is not None:
                    phase_fn(drained)
                if (
                    ckpt is not None
                    and checkpoint_every > 0
                    and drained % checkpoint_every == 0
                ):
                    self._save_checkpoint(
                        ckpt, drained, state, cstate, train, rng, lstate,
                        {
                            "drained": drained,
                            "env_steps_total": env_steps_total,
                            "ep_return_sum": ep_ret_sum,
                            "ep_count": ep_count,
                            "episodes_total": episodes_total,
                            "param_version": version,
                        },
                    )
                if drained % max(self.config.publish_every, 1) == 0:
                    version += 1
                    self.server.publish_params(
                        version, self._snapshot_params(train)
                    )
                    if log_every and drained % log_every == 0:
                        flight_event("param_publish", version=version)
                if log_every and drained % log_every == 0:
                    with mon.expected("log_fetch"):
                        lstep, m = jax.device_get(
                            (train.step, last_metrics)
                        )
                    scalars = {
                        "episode_return_mean": ep_ret_sum / max(ep_count, 1.0),
                        "episodes": ep_count,
                        "env_steps": env_steps_total,
                        "learner_steps": float(lstep),
                        "replay_occupancy": float(occ),
                        **{k: float(v) for k, v in m.items()},
                    }
                    ep_ret_sum = 0.0
                    ep_count = 0.0
                    t._obs_publish(scalars)
                    emit_log(drained, scalars)
        finally:
            jax.block_until_ready(train.step)
            # Sentinel disarmed + any open profiler capture closed before
            # teardown's own device work runs.
            mon.end_run()
            t_end = time.monotonic()
            fold_stats()
            wall = max(t_end - t0, 1e-9)
            _, sw_total, sw_p50, sw_p99 = self.sampler_wait.snapshot()
            _, sa_total, _, _ = self.sampler_absorb.snapshot()
            _, pw_total, _, pw_p99 = self.puller_wait.snapshot()
            srv = self.server
            drained_here = drained - drained_at_start
            trained = drained_here * n_draws
            if self._remote:
                # Real-socket accounting: the shard set counted every
                # sampler-leg byte (REQ/BATCH/PRIO + acks + HELLOs).
                self.sample_bytes_total = self.shards.sample_bytes_total
            self._counters = {
                "drained": float(drained),
                "env_steps_total": env_steps_total,
                "ep_return_sum": ep_ret_sum,
                "ep_count": ep_count,
                "episodes_total": episodes_total,
                "param_version": float(version),
            }
            self._stats = {
                "train_phases": float(drained_here),
                "train_phases_total": float(drained),
                "trained_seqs": float(trained),
                "wall_s": wall,
                "learner_steps_per_sec": (
                    drained_here * cfg.learner_steps / wall
                ),
                # The headline boundary: only SAMPLED sequences cross
                # into training (compare the central drain's
                # bytes_per_trained_seq, fleet/ingest.py).
                "sample_bytes_total": float(self.sample_bytes_total),
                "bytes_per_trained_seq": (
                    self.sample_bytes_total / max(trained, 1)
                ),
                # The actor wire, for honesty: collection traffic still
                # lands on the (in-learner) shards today.
                "seqs_bytes_total": float(srv.seqs_bytes_total),
                "collected_seqs": float(srv.seqs_received_total),
                "sheds": float(srv.shed_total),  # structurally 0
                # Eviction visibility (ISSUE 12 satellite): ring FIFO
                # overwrites of filled slots — the quantity shedding
                # turned into in PR 10, now first-class in the stats row.
                "evictions": float(self.shards.evictions_total()),
                "replay_occupancy": float(self.shards.occupancy_total()),
                "sampler_wait_p50_ms": sw_p50 * 1e3,
                "sampler_wait_p99_ms": sw_p99 * 1e3,
                "sampler_wait_total_s": sw_total,
                "sampler_absorb_s": sa_total,
                # Puller concurrency (ISSUE 17): per-exchange wall times;
                # with N pullers the phase pays ~the max, the serial
                # control leg pays the sum.
                "shard_pullers": float(self._pullers if self._remote else 1),
                "puller_wait_p99_ms": pw_p99 * 1e3,
                "puller_wait_total_s": pw_total,
                # The pipelined executor's overlap instrumentation,
                # riding the composed loop (ISSUE 11): fraction of the
                # wall during which the learner had sample data available
                # (1.0 = collection fully hidden behind learning — same
                # definition as PipelineExecutor.stats / FleetLearner).
                # Absorb counts as un-overlapped wait here even though it
                # lives in its own histogram for /health.
                "overlap_fraction": max(
                    0.0, 1.0 - (sw_total + sa_total) / wall
                ),
                # Experience-quality columns (obs/quality.py; -1 =
                # signal never armed this run).
                **quality_stats_columns(),
                # Device plane (ISSUE 14): compile ledger + peak HBM.
                **mon.run_stats(),
            }
            if self._remote:
                # The standalone tier's robustness ledger (ISSUE 12).
                self._stats.update(
                    {
                        "shard_deaths": float(self.shards.deaths_total),
                        "shard_rejoins": float(self.shards.rejoins_total),
                        "shard_forward_bytes_total": float(
                            self.shards.forward_bytes_total
                        ),
                        # Observability riders, apart from the sampling
                        # boundary's wire-cost contract.
                        "telem_bytes_total": float(
                            self.shards.telem_bytes_total
                        ),
                    }
                )
            if train_t0 is not None:
                train_wall = max(t_end - train_t0, 1e-9)
                self._stats["train_wall_s"] = train_wall
                self._stats["train_learner_steps_per_sec"] = (
                    max(drained_here - 1, 0) * cfg.learner_steps / train_wall
                )
        lstate = dataclasses.replace(lstate, train=train, rng=rng)
        return dataclasses.replace(
            merge_state(state, cstate, lstate),
            phase_idx=cstate.phase_idx + drained,
        )

    def _save_checkpoint(
        self, ckpt, step: int, state, cstate, train, rng, lstate, counters
    ) -> None:
        # The ADVANCED per-phase rng, not lstate's run-start key: a light
        # checkpoint persists only the train subtree today, but the saved
        # state must never claim a key stream the run already consumed.
        lstate = dataclasses.replace(lstate, train=train, rng=rng)
        ckpt.save(step, merge_state(state, cstate, lstate))
        save_fleet_counters(ckpt.directory, step, counters)
        prune_fleet_counters(ckpt.directory, ckpt.all_steps())

    def _snapshot_params(self, train) -> Any:
        """The shared published-snapshot contract (ingest.snapshot_params):
        all four net cores + step, one definition for both learners."""
        return snapshot_params(train)
