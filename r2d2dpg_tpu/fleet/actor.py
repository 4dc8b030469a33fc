"""Fleet actor: an out-of-process collector streaming experience upstream.

One actor subprocess owns its env pool (``--num-envs`` lanes of the
vmapped batch, or a host dm_control pool) and a stale copy of the
learner's nets, runs the R2D2-DPG rollout, computes initial priorities
locally with those stale nets (Ape-X §3: sequences enter replay already
ranked), and streams one ``replay.StagedSequences`` batch per collect
phase to the learner's ingest server — applying versioned param updates
between phases and ignoring regressions (a delayed PARAMS frame must
never roll the policy backwards).

Exploration: Ape-X gives actor ``i`` of ``N`` its own epsilon
(1803.00933 §D); the DPG analogue is this repo's sigma ladder
(``ops/noise.py``).  In-process the "actors" are env lanes, so the ladder
spans ``num_envs``; in a fleet it spans the GLOBAL ``num_actors *
num_envs`` lanes and each actor slices its contiguous block —
``FleetActorTrainer._local_sigmas`` below, the same slicing contract as
``SPMDTrainer``'s per-device shards.  A 3-actor pendulum fleet explores
exactly like one 3x-wider in-process batch.

CLI (spawned by ``fleet/supervisor.py``; runnable by hand for debugging):

    python -m r2d2dpg_tpu.fleet.actor --config pendulum_tiny \\
        --connect 127.0.0.1:7450 --actor-id 0 --num-actors 3 --seed 0
"""

from __future__ import annotations

import argparse
import dataclasses
import socket as socket_mod
import sys
import threading
import time
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from r2d2dpg_tpu.configs import CONFIGS, ExperimentConfig, get_config
from r2d2dpg_tpu.fleet import chaos as fleet_chaos
from r2d2dpg_tpu.fleet import wire
from r2d2dpg_tpu.fleet.transport import (
    HEADER_BYTES,
    MAX_FRAME_BYTES,
    READ_DEADLINE_S,
    K_ACK,
    K_BYE,
    K_HELLO,
    K_PARAMS,
    K_SEQS,
    K_STATS,
    K_TELEM,
    FrameError,
    PeerDeadError,
    connect,
    hello_auth_proof,
    pack_hello,
    pack_obj,
    recv_frame_heartbeat,
    send_frame,
    send_frame_parts,
    unpack_obj,
)
from r2d2dpg_tpu.obs import flight_event, get_registry, set_flight_identity
from r2d2dpg_tpu.obs import trace as obs_trace
from r2d2dpg_tpu.ops import sigma_ladder
from r2d2dpg_tpu.replay.arena import StagedSequences
from r2d2dpg_tpu.training.assembler import emit
from r2d2dpg_tpu.training.pipeline import CollectorState, split_state
from r2d2dpg_tpu.training.trainer import Trainer, TrainerConfig
from r2d2dpg_tpu.utils.codes import (
    EXIT_AUTH_REFUSED,
    EXIT_WIRE_REFUSED,
    OK,
    REFUSED_AUTH,
    REFUSED_WIRE,
    SHED_INGEST,
)


class FleetActorTrainer(Trainer):
    """A ``Trainer`` whose noise ladder is one actor's slice of the fleet's.

    Everything else (collect scan, window assembler, episode accounting)
    is the base trainer verbatim — the actor IS a collector, just living
    in its own process with ``num_envs`` local lanes of a
    ``num_actors * num_envs``-lane fleet."""

    def __init__(
        self,
        env,
        agent,
        config: TrainerConfig,
        *,
        actor_index: int,
        num_actors: int,
    ):
        if not 0 <= actor_index < num_actors:
            raise ValueError(
                f"actor_index {actor_index} outside fleet of {num_actors}"
            )
        self.actor_index = actor_index
        self.num_actors = num_actors
        super().__init__(env, agent, config)

    def _local_sigmas(self) -> jnp.ndarray:
        sigmas = sigma_ladder(
            self.num_actors * self.config.num_envs,
            sigma_max=self.config.sigma_max,
            alpha=self.config.ladder_alpha,
            kind=self.config.ladder_kind,
        )
        lo = self.actor_index * self.config.num_envs
        return sigmas[lo : lo + self.config.num_envs]


def build_actor_trainer(
    exp: ExperimentConfig, *, actor_index: int, num_actors: int
) -> FleetActorTrainer:
    """The actor's trainer: full net/agent recipe, TINY arena (the actor
    never samples — replay lives learner-side; allocating the config's
    full capacity here would burn host RAM per actor for buffers that
    only ever hold ``init_state`` zeros)."""
    env = exp.env_factory()
    agent = exp.build_agent(env)
    tcfg = dataclasses.replace(
        exp.trainer, capacity=max(exp.trainer.num_envs, 1), min_replay=1
    )
    return FleetActorTrainer(
        env, agent, tcfg, actor_index=actor_index, num_actors=num_actors
    )


class FleetActor:
    """The worker loop: collect -> rank -> stream -> apply params."""

    def __init__(
        self,
        exp: ExperimentConfig,
        *,
        actor_id: int,
        num_actors: int,
        address: str,
        seed: Optional[int] = None,
        wire_config: Optional[wire.WireConfig] = None,
        max_frame_bytes: int = MAX_FRAME_BYTES,
        telem_every: float = 0.0,
        trace_sample: float = 0.0,
        read_deadline_s: float = READ_DEADLINE_S,
        warmup_deadline_s: float = 120.0,
        auth_token: Optional[str] = None,
        shard_direct: bool = False,
        chaos_spec: Optional[str] = None,
        reconnect_tries: int = 4,
        reconnect_base_s: float = 0.5,
        reconnect_max_s: float = 10.0,
    ):
        self.actor_id = actor_id
        self.address = address
        # Liveness bound on this end of the wire (transport.py): no ack
        # wait or backpressured send ever hangs past the deadline; a
        # silent learner is PINGed once, then treated as dead (reconnect
        # attempts below, then a retryable exit for the supervisor).
        # Until a session's FIRST ack the LARGER of the two deadlines
        # applies — the learner's first drain-learn compile legitimately
        # parks the handler in a queue-full wait (not reading, so no PONG
        # either), and a dialed-down heartbeat must not read that warmup
        # as a dead learner and churn the whole fleet through restarts.
        # The ingest server holds the mirror-image warmup window.
        self.read_deadline_s = read_deadline_s
        self.warmup_deadline_s = max(warmup_deadline_s, read_deadline_s)
        self.auth_token = auth_token
        # Reconnect-with-backoff (docs/FLEET.md "Failure modes"): a torn
        # connection — ingest restart, reaped stall, dropped conn — is
        # retried in-process with a fresh socket + HELLO + param snapshot
        # before the actor gives the incarnation up to the supervisor.  A
        # session that delivered at least one acked batch resets the
        # ladder (the same healthy-uptime contract as the supervisor's).
        self.reconnect_tries = reconnect_tries
        self.reconnect_base_s = reconnect_base_s
        self.reconnect_max_s = reconnect_max_s
        # Fleet observability plane (ISSUE 6): TELEM snapshot cadence in
        # seconds (0 = off; train.py --obs-fleet spawns actors at 1 Hz)
        # and the experience-path trace sampling rate (0 = off).
        self.telem_every = float(telem_every)
        self.trace_sample = float(trace_sample)
        self._telem_last = 0.0
        # The wire fast lane (fleet/wire.py): must MIRROR the learner's
        # --fleet-wire/--fleet-compress — the ingest server refuses a
        # mismatched HELLO (one fleet, one wire format).
        self.wire_config = (wire_config or wire.WireConfig()).validate()
        # Frame ceiling: must mirror the learner's FleetConfig value too
        # (the spawner forwards it) — a packer pinned to the default would
        # FrameTooLarge-crash-loop a fleet configured for larger frames,
        # and a larger actor ceiling would emit frames the server refuses.
        self.max_frame_bytes = max_frame_bytes
        self.trainer = build_actor_trainer(
            exp, actor_index=actor_id, num_actors=num_actors
        )
        t = self.trainer
        # Host-pool envs label their r2d2dpg_envpool_* series per ROLE so a
        # fleet's actor pools never interleave with a learner-side pool.
        if hasattr(t.env, "set_role"):
            t.env.set_role("actor")
        seed = t.config.seed if seed is None else seed
        # Distinct stream per actor: same base seed, folded actor index —
        # a fleet at seed S is a different (equally valid) trajectory per
        # actor, never N copies of one rollout.
        key = jax.random.fold_in(jax.random.PRNGKey(seed), actor_id)
        state = t.init(key)
        self._cstate, lstate = split_state(state)
        # The stale learner-net copy: acts AND ranks until the first
        # PARAMS frame lands (version 0 = own init).
        self._train = lstate.train
        self._param_version = 0
        self._sheds = 0
        self._phase = 0
        self._batches = 0  # emitted (post-warmup) batches: the chaos clock
        # Orderly drain (ISSUE 16 scale-down): once set — SIGUSR1 from
        # the supervisor's retire_slot, or request_drain() in-process —
        # the session loop exits after the CURRENT phase's ack lands, so
        # the banked accounting is folded, then falls through to BYE and
        # a zero exit.  Scale-down loses no steps and looks nothing like
        # a crash.
        self._drain = threading.Event()
        self._last_env_steps = 0.0  # for per-phase deltas (see run)
        # At-least-once stats accounting: the per-phase episode/step
        # DELTAS ride the SEQS message and are cleared only once an ack
        # proves the server owns them (OK folds them; SHED banks them
        # server-side).  A connection lost before the ack re-banks them
        # into the NEXT send, so a drill's dropped frame loses experience
        # (droppable by contract) but never loses accounting.  The rare
        # double-count window — server queued the batch but its OK ack
        # died on the wire — is the price of never silently losing steps.
        self._pending_stats = {
            "env_steps_delta": 0.0, "ep_return_sum": 0.0, "ep_count": 0.0,
        }
        # Direct data plane (ISSUE 17): when the learner advertises a shard
        # assignment on an ack, dial the shard and ship SEQS to it directly
        # — the control connection then carries only a tiny K_STATS frame
        # per phase (params/telem/accounting), shedding the ingest forward
        # hop from the experience path.  Any data-leg failure falls back
        # LOUDLY to the learner-forwarded path; _pending_stats is cleared
        # only on a control-plane ack, so accounting is plane-independent.
        self.shard_direct = bool(shard_direct)
        self._assignment: Optional[dict] = None  # last dialed advert
        self._failed_assignment: Optional[dict] = None  # don't re-hammer
        self._data_sock = None  # live => ship SEQS direct
        self._data_packer: Optional[wire.TreePacker] = None
        self._data_epoch = -1  # epoch the data HELLO ack pinned
        # Actor-side chaos faults (fleet/chaos.py): the forwarded
        # --chaos-spec's stall/corrupt drills that target THIS actor.
        self.chaos: Optional[fleet_chaos.ActorChaos] = None
        if chaos_spec:
            # ``seed`` is already resolved above (config default or
            # override) — the same value the learner's engine hashes, so
            # both sides agree on every fault's target actor.
            self.chaos = fleet_chaos.ActorChaos(
                fleet_chaos.parse_chaos_spec(chaos_spec),
                seed=seed,
                num_actors=num_actors,
                actor_id=actor_id,
            )
        self._warm_prog = jax.jit(
            lambda cs, behavior, critic: t._collect(
                cs, behavior=behavior, critic_params=critic
            ),
            donate_argnums=(0,),
        )
        self._collect_prog = jax.jit(self._collect_emit, donate_argnums=(0,))
        self._local_priorities = (
            t.config.prioritized and t.config.initial_priority == "td"
        )
        if self._local_priorities:
            self._prio_prog = jax.jit(t.agent.initial_priority)
        reg = get_registry()
        self._obs_phases = reg.counter(
            "r2d2dpg_actor_phases_total", "collect phases completed"
        )
        self._obs_shed = reg.counter(
            "r2d2dpg_actor_shed_total", "batches the ingest server shed"
        )
        self._obs_version = reg.gauge(
            "r2d2dpg_actor_param_version", "last applied param version"
        )
        self._obs_bytes_out = reg.counter(
            "r2d2dpg_actor_bytes_out_total",
            "bytes this actor put on the fleet wire (frames + headers)",
        )
        self._obs_bytes_in = reg.counter(
            "r2d2dpg_actor_bytes_in_total",
            "bytes this actor received off the fleet wire (acks + params)",
        )
        self._obs_telem = reg.counter(
            "r2d2dpg_actor_telem_sent_total",
            "TELEM registry snapshots pushed to the learner's ingest",
        )
        self._obs_reconnects = reg.counter(
            "r2d2dpg_actor_reconnects_total",
            "successful in-process reconnects after a torn connection "
            "(fresh socket + HELLO + param snapshot, same incarnation)",
        )
        # Per-plane byte accounting (ISSUE 17 satellite): the data leg's
        # bytes land here and ONLY here — never in the actor/control
        # totals above — so control-vs-data traffic stays separable.  The
        # r2d2dpg_fleet_ prefix keeps these out of the shard TELEM echo.
        self._obs_data_out = reg.counter(
            "r2d2dpg_fleet_data_bytes_out_total",
            "bytes sent on the direct actor->shard data plane",
            labelnames=("plane",),
        ).labels(plane="data")
        self._obs_data_in = reg.counter(
            "r2d2dpg_fleet_data_bytes_in_total",
            "bytes received on the direct actor->shard data plane",
            labelnames=("plane",),
        ).labels(plane="data")
        self._obs_fallback = reg.counter(
            "r2d2dpg_actor_data_fallback_total",
            "direct data-plane failures that fell back to the "
            "learner-forwarded path (dial refused, torn leg, partition)",
        )
        self._session_delivered = False

    # ---------------------------------------------------------- device parts
    def _collect_emit(self, cstate: CollectorState, behavior, critic):
        cstate = self.trainer._collect(
            cstate, behavior=behavior, critic_params=critic
        )
        return cstate, emit(cstate.window)

    # -------------------------------------------------------------- params
    def maybe_apply_params(self, msg: Any) -> bool:
        """Apply a versioned snapshot; IGNORE stale or replayed versions.

        The regression guard: acks/pushes can interleave across a
        reconnect, and a policy must only ever move forward — an actor
        that applied version 7 then saw a delayed 5 would collect with
        nets the learner has already trained past twice over."""
        version = int(msg["version"])
        if version <= self._param_version:
            flight_event(
                "param_regression_ignored",
                got=version,
                have=self._param_version,
            )
            return False
        # device_put ONCE at apply time: leaving numpy leaves in _train
        # would re-upload the whole param set on every jitted collect call.
        p = jax.device_put(msg["params"])
        self._train = dataclasses.replace(
            self._train,
            actor_params=p["actor_params"],
            critic_params=p["critic_params"],
            target_actor_params=p["target_actor_params"],
            target_critic_params=p["target_critic_params"],
        )
        self._param_version = version
        self._obs_version.set(float(version))
        return True

    # ------------------------------------------------------------ one phase
    def collect_phase(self) -> Optional[StagedSequences]:
        """One stride of env steps; returns the emitted batch (None during
        window warm-up, when the window still contains init padding)."""
        behavior = self._train.actor_params
        critic = self.trainer.agent.behavior_critic_params(self._train)
        if self._phase < self.trainer.window_fill_phases:
            self._cstate = self._warm_prog(self._cstate, behavior, critic)
            self._phase += 1
            self._obs_phases.inc()
            return None
        self._cstate, seq = self._collect_prog(self._cstate, behavior, critic)
        self._phase += 1
        self._obs_phases.inc()
        prios = (
            self._prio_prog(self._train, seq)
            if self._local_priorities
            else None
        )
        return StagedSequences(seq=seq, priorities=prios)

    def _pop_episode_stats(self):
        """Drain the device accumulators (refs leave ``_cstate`` before the
        next donating collect call — the pipeline collector's discipline)."""
        cs = self._cstate
        refs = (jnp.copy(cs.env_steps), cs.completed_return_sum, cs.completed_count)
        self._cstate = dataclasses.replace(
            cs,
            completed_return_sum=jnp.zeros(()),
            completed_count=jnp.zeros(()),
        )
        return refs

    # ------------------------------------------------------------------ run
    def request_drain(self) -> None:
        """Ask the actor to leave the fleet cleanly: finish the current
        phase (its ack folds the pending accounting), send BYE, return.
        Signal-safe and idempotent — the supervisor's retire path routes
        SIGUSR1 here, and the autoscaler's scale-down rides on it."""
        if not self._drain.is_set():
            self._drain.set()
            flight_event("actor_drain", phase=self._phase)

    def run(self, max_phases: Optional[int] = None) -> None:
        """Stream until the server goes away (orderly end) or an
        unrecoverable error surfaces (crash — nonzero exit, the supervisor
        restarts).

        A torn connection — ingest restart, a heartbeat reap after a
        stall, a chaos conn-drop — is retried IN-process first: fresh
        socket, fresh HELLO (the server re-pushes its current param
        snapshot ahead of the hello ack), fresh wire schema cache, with
        exponential backoff between attempts.  Collection state (window,
        env pool, phase count, pending accounting deltas) survives the
        reconnect, so a recovered actor resumes streaming where it left
        off instead of re-paying its warm-up.  Only after
        ``reconnect_tries`` consecutive failed sessions does the error
        propagate (nonzero exit; the supervisor's backoff restart takes
        over)."""
        attempts = 0
        backoff = self.reconnect_base_s
        while True:
            self._session_delivered = False
            try:
                self._run_session(max_phases, reconnected=attempts > 0)
                return
            except (_OrderlyShutdown, _WireRefused, _AuthRefused):
                raise  # deterministic verdicts: never retried here
            except (FrameError, OSError) as e:
                if isinstance(e, PeerDeadError):
                    # Mirror of the ingest handler's reap: the learner
                    # answered neither frames nor our PING.
                    flight_event(
                        "peer_dead",
                        phase=self._phase,
                        deadline_s=self.read_deadline_s,
                        error=str(e),
                    )
                if self._session_delivered:
                    # A healthy session resets the ladder (the supervisor's
                    # healthy-uptime contract): only CONSECUTIVE failures
                    # walk toward giving the incarnation up.
                    attempts = 0
                    backoff = self.reconnect_base_s
                attempts += 1
                if attempts > self.reconnect_tries:
                    raise
                err = f"{type(e).__name__}: {e}"
                flight_event(
                    "actor_reconnect_wait",
                    phase=self._phase,
                    attempt=attempts,
                    backoff_s=round(backoff, 3),
                    error=err,
                )
                time.sleep(backoff)
                backoff = min(backoff * 2, self.reconnect_max_s)

    def _run_session(
        self, max_phases: Optional[int], *, reconnected: bool = False
    ) -> None:
        """One connection's lifetime: HELLO -> stream -> BYE."""
        # Warmup window until the first SEQS ack (see __init__): the
        # learner's first compile parks its handler, which is legitimate
        # silence — the steady-state deadline arms after the ack.
        sock = connect(self.address, read_deadline_s=self.warmup_deadline_s)
        # Wire state lives and dies with the socket: a reconnect gets a
        # fresh packer whose first SEQS frame re-inlines its schema.
        packer = wire.TreePacker(
            self.wire_config, max_frame_bytes=self.max_frame_bytes
        )
        self._unpacker = wire.TreeUnpacker(
            max_frame_bytes=self.max_frame_bytes
        )
        try:
            hello = {
                "actor_id": self.actor_id,
                "num_envs": self.trainer.config.num_envs,
                **wire.negotiation_fields(self.wire_config),
            }
            if self.auth_token is not None:
                hello["auth"] = hello_auth_proof(self.auth_token)
            self._obs_bytes_out.inc(
                send_frame(
                    sock,
                    K_HELLO,
                    pack_hello(hello),  # JSON: parsed pre-auth on the far end
                    max_frame_bytes=self.max_frame_bytes,
                )
            )
            hello_ack = self._await_ack(sock)
            if hello_ack.get("code") == REFUSED_WIRE:
                raise _WireRefused(
                    f"ingest refused wire negotiation "
                    f"({hello_ack.get('reason')}); launch this actor with "
                    f"the learner's --fleet-wire/--fleet-compress "
                    f"(server expects {hello_ack.get('expect')})"
                )
            if hello_ack.get("code") == REFUSED_AUTH:
                raise _AuthRefused(
                    "ingest refused HELLO authentication; launch this "
                    "actor with the learner's --fleet-token"
                )
            if reconnected:
                flight_event("actor_reconnect", phase=self._phase)
                self._obs_reconnects.inc()
            # The HELLO ack may carry the shard assignment advert
            # (ingest._assignment waits for the tier at HELLO time): dial
            # the data plane before the first phase so the forward hop is
            # shed from batch one, not batch two.
            self._maybe_update_assignment(hello_ack)
            self._maybe_send_telem(sock, force=True)
            while (
                max_phases is None or self._phase < max_phases
            ) and not self._drain.is_set():
                if self.chaos is not None:
                    # The stall drill: stop reading AND sending mid-loop,
                    # exactly what a wedged env or GC pause looks like on
                    # the wire — the ingest handler's heartbeat reaps us.
                    self.chaos.maybe_stall(self._batches + 1)
                    if self.chaos.partition_data_plane(self._batches + 1):
                        # The partition drill: sever the data leg under
                        # our feet (shutdown, reference kept) so the next
                        # direct send hits a dead socket and the LOUD
                        # fallback path runs — the control plane keeps
                        # the accounting whole throughout.
                        self._partition_data_plane()
                # Trace sampling decided at collection time (obs/trace.py):
                # rate 0 allocates nothing and the frame is byte-identical
                # to an untraced wire.
                tr = obs_trace.maybe_start(self.trace_sample)
                staged = self.collect_phase()
                if staged is None:
                    # Warm-up: window not yet real.  The TELEM cadence must
                    # still tick — warm-up phases (the first carries the
                    # JIT compile, tens of seconds) would otherwise read as
                    # a wedged actor on the staleness gauge after every
                    # supervised restart.
                    self._maybe_send_telem(sock)
                    continue
                self._batches += 1
                # ONE batched device fetch per phase (episode stats + the
                # staged pytree + priorities) — the pop_episode_metrics
                # lesson; separate fetches would be three host syncs on
                # every actor's critical path.  None priorities pass
                # through device_get as an empty subtree.
                (env_steps, ret_sum, count), seq_host, prios_host = (
                    jax.device_get(
                        (
                            self._pop_episode_stats(),
                            staged.seq,
                            staged.priorities,
                        )
                    )
                )
                if tr is not None:
                    # Collection "ends" when the host holds the batch: the
                    # fetch above is part of the collect hop.
                    tr.t_collect_end = time.time()
                # DELTAS, not cumulative: a supervised restart resets this
                # process, and the learner's fleet-wide sums must stay
                # monotone across incarnations (ingest just accumulates).
                # Folded into _pending_stats, which is cleared only on an
                # ack — a frame lost to a torn connection re-banks its
                # accounting into the next send (at-least-once; __init__).
                steps_delta = float(env_steps) - self._last_env_steps
                self._last_env_steps = float(env_steps)
                self._pending_stats["env_steps_delta"] += steps_delta
                self._pending_stats["ep_return_sum"] += float(ret_sum)
                self._pending_stats["ep_count"] += float(count)
                # Provenance stamps ride the already-fetched host batch:
                # the behavior version these sequences were collected
                # under and this actor's monotone phase clock.  The
                # learner folds lag/age from them without any extra
                # device traffic on either side.
                seq_b = jax.tree_util.tree_leaves(seq_host)[0].shape[0]
                staged_host = StagedSequences(
                    seq=seq_host,
                    priorities=prios_host,
                    behavior_version=np.full(
                        (seq_b,), self._param_version, np.int64
                    ),
                    collect_id=np.full((seq_b,), self._phase, np.int64),
                )
                sent_direct = self._data_sock is not None and (
                    self._send_direct(staged_host)
                )
                if sent_direct:
                    # Experience is shard-owned; only the accounting
                    # deltas ride the control connection now — a tiny
                    # pickled K_STATS frame, acked like SEQS so the
                    # at-least-once clear below is plane-independent.
                    self._obs_bytes_out.inc(
                        send_frame(
                            sock,
                            K_STATS,
                            pack_obj(  # wire-lint: control
                                {
                                    "phase": self._phase,
                                    "param_version": self._param_version,
                                    **self._pending_stats,
                                }
                            ),
                            max_frame_bytes=self.max_frame_bytes,
                        )
                    )
                else:
                    # The learner-forwarded path: steady state when
                    # --shard-direct is off, the LOUD fallback when the
                    # data leg just died (the staged batch that failed
                    # mid-push retries here — nothing is dropped).
                    # Schema-cached binary frames (fleet/wire.py), tensor
                    # bytes streamed without an intermediate payload join
                    # (send_frame_parts).
                    parts = packer.pack(
                        {
                            "phase": self._phase,
                            "param_version": self._param_version,
                            **self._pending_stats,
                            "staged": staged_host,
                        },
                        trace=tr,
                    )
                    if self.chaos is not None and (
                        self.chaos.corrupt_next_frame(self._batches)
                    ):
                        # The corrupt-frame drill: pristine CRC over
                        # flipped bytes — the server MUST reject it
                        # (FrameCRCError) and kill the connection; we
                        # reconnect and re-bank.
                        self._obs_bytes_out.inc(
                            fleet_chaos.send_corrupt_frame(
                                sock, K_SEQS, parts
                            )
                        )
                    else:
                        self._obs_bytes_out.inc(
                            send_frame_parts(
                                sock,
                                K_SEQS,
                                parts,
                                max_frame_bytes=self.max_frame_bytes,
                            )
                        )
                ack = self._await_ack(sock)
                # Acked (OK or shed): the server owns the accounting now —
                # OK folds it with the batch, a shed banks it server-side.
                for k in self._pending_stats:
                    self._pending_stats[k] = 0.0
                if not self._session_delivered:
                    # First ack of the session: warmup is over, arm the
                    # steady-state heartbeat deadline (mirror of the
                    # ingest handler tightening on its first SEQS).
                    sock.settimeout(self.read_deadline_s)
                self._session_delivered = True
                if ack["code"] == SHED_INGEST:
                    self._sheds += 1
                    self._obs_shed.inc()
                # Every control ack may carry a (re-)advert: the first
                # one after an epoch-bumped shard rejoin re-dials the new
                # incarnation; an unchanged advert on a live leg is a
                # no-op.
                self._maybe_update_assignment(ack)
                self._maybe_send_telem(sock)
            try:
                send_frame(sock, K_BYE, b"")  # wire-lint: control
            except OSError:
                pass
        finally:
            # The data leg lives and dies with the control session: a
            # reconnect re-dials from the fresh HELLO ack's advert.
            self._drop_data_plane(reason=None)
            try:
                sock.close()
            except OSError:
                pass

    def _maybe_send_telem(self, sock, force: bool = False) -> None:
        """The ~1 Hz TELEM cadence rider (ISSUE 6 leg 1): push this
        process's registry snapshot so the learner's exporter is the
        fleet's single scrape point.  Fire-and-forget control frame — no
        ack (the next SEQS ack already paces the connection); rides the
        collect loop, so a wedged actor's silence is itself the signal
        (the ingest side's per-actor staleness gauge keeps counting)."""
        if self.telem_every <= 0.0:
            return
        now = time.monotonic()
        if not force and now - self._telem_last < self.telem_every:
            return
        self._telem_last = now
        self._obs_telem.inc()
        self._obs_bytes_out.inc(
            send_frame(
                sock,
                K_TELEM,
                pack_obj(  # wire-lint: control
                    {
                        "actor_id": self.actor_id,
                        "host": socket_mod.gethostname(),
                        "t_wall": time.time(),
                        "snapshot": get_registry().snapshot(),
                    }
                ),
                max_frame_bytes=self.max_frame_bytes,
            )
        )

    # ------------------------------------------------- direct data plane
    def _maybe_update_assignment(self, ack: Any) -> None:
        """Track the learner's shard-assignment advert; (re)dial the data
        plane when it changes.

        The advert rides control acks (HELLO/SEQS/STATS), so this runs at
        most once per phase — natural rate limiting on re-dials.  An
        advert identical to the last FAILED one is skipped (no hammering
        a refusing shard every phase); the learner re-adverts with a
        bumped epoch once the shard rejoins, which unsticks us."""
        if not self.shard_direct or not isinstance(ack, dict):
            return
        advert = ack.get("shard_assignment")
        if not isinstance(advert, dict):
            return
        if advert == self._failed_assignment:
            return
        if (
            self._data_sock is not None
            and self._assignment is not None
            and advert.get("address") == self._assignment.get("address")
            and int(advert.get("epoch", -1)) == self._data_epoch
        ):
            return  # same shard incarnation, leg already live
        self._dial_data_plane(advert)

    def _dial_data_plane(self, advert: dict) -> bool:
        """Dial the advertised shard: connect + plane="data" HELLO (same
        token as the control HELLO) + OK ack.  A refusal or dead address
        is LOUD but non-fatal — the learner-forwarded path keeps the
        experience flowing."""
        address = str(advert.get("address") or "")
        if not address:
            return False
        self._drop_data_plane(reason=None)  # replace any previous leg
        sock = None
        try:
            sock = connect(address, read_deadline_s=self.read_deadline_s)
            hello = {
                "actor_id": self.actor_id,
                "plane": "data",
                **wire.negotiation_fields(self.wire_config),
            }
            if self.auth_token is not None:
                hello["auth"] = hello_auth_proof(self.auth_token)
            self._obs_data_out.inc(
                send_frame(
                    sock,
                    K_HELLO,
                    pack_hello(hello),
                    max_frame_bytes=self.max_frame_bytes,
                )
            )
            hello_ack = self._await_data_ack(sock)
            if hello_ack.get("code") != OK:
                raise FrameError(
                    f"shard refused data-plane HELLO: "
                    f"code={hello_ack.get('code')} "
                    f"reason={hello_ack.get('reason')}"
                )
        except (FrameError, OSError) as e:
            if sock is not None:
                try:
                    sock.close()
                except OSError:
                    pass
            self._failed_assignment = dict(advert)
            self._obs_fallback.inc()
            flight_event(
                "data_plane_dial_failed",
                phase=self._phase,
                shard=advert.get("shard"),
                address=address,
                error=f"{type(e).__name__}: {e}",
            )
            return False
        self._data_sock = sock
        # Fresh packer per leg: its first SEQS frame re-inlines the
        # schema, exactly like a control reconnect.
        self._data_packer = wire.TreePacker(
            self.wire_config, max_frame_bytes=self.max_frame_bytes
        )
        self._data_epoch = int(advert.get("epoch", -1))
        self._assignment = dict(advert)
        self._failed_assignment = None
        flight_event(
            "data_plane_dialed",
            phase=self._phase,
            shard=advert.get("shard"),
            address=address,
            epoch=self._data_epoch,
        )
        return True

    def _send_direct(self, staged: StagedSequences) -> bool:
        """Ship one staged batch straight to the shard; True only once
        its ack lands.  ANY failure tears the leg down loudly and returns
        False — the caller then sends the SAME batch on the control
        connection, so a mid-push shard death drops nothing."""
        try:
            parts = self._data_packer.pack({"staged": staged})
            self._obs_data_out.inc(
                send_frame_parts(
                    self._data_sock,
                    K_SEQS,
                    parts,
                    max_frame_bytes=self.max_frame_bytes,
                )
            )
            ack = self._await_data_ack(self._data_sock)
            if ack.get("code") != OK:
                raise FrameError(
                    f"shard data-plane ack code {ack.get('code')}"
                )
            return True
        except (FrameError, OSError) as e:
            self._drop_data_plane(reason=f"{type(e).__name__}: {e}")
            return False

    def _await_data_ack(self, sock) -> Any:
        """Read to the shard's next ACK on the data leg.  The shard rides
        TELEM pushes on any authenticated connection — the learner is
        their consumer, so here they are counted and dropped."""
        while True:
            kind, payload = recv_frame_heartbeat(
                sock,
                max_frame_bytes=self.max_frame_bytes,
                bytes_in=self._obs_data_in.inc,
                bytes_out=self._obs_data_out.inc,
            )
            self._obs_data_in.inc(HEADER_BYTES + len(payload))
            if kind == K_TELEM:
                continue
            if kind == K_ACK:
                return unpack_obj(payload)  # wire-lint: control
            raise FrameError(f"unexpected data-plane frame kind {kind}")

    def _drop_data_plane(self, reason: Optional[str]) -> None:
        """Tear down the data leg.  A non-None reason is a FAILURE — loud
        flight event + fallback counter; None is lifecycle (session end,
        re-dial replacing the leg)."""
        sock, self._data_sock = self._data_sock, None
        self._data_packer = None
        self._assignment = None
        self._data_epoch = -1
        if sock is not None:
            try:
                sock.close()
            except OSError:
                pass
        if reason is not None:
            self._obs_fallback.inc()
            flight_event(
                "data_plane_fallback",
                phase=self._phase,
                error=reason,
            )

    def _partition_data_plane(self) -> None:
        """Chaos partition_data_plane: sever the leg at the transport
        (shutdown both directions) but KEEP the reference, so the next
        direct send surfaces the failure exactly like a real network
        partition would — mid-send, not at dial time."""
        if self._data_sock is None:
            return
        try:
            self._data_sock.shutdown(socket_mod.SHUT_RDWR)
        except OSError:
            pass

    def _await_ack(self, sock) -> Any:
        """Read to the next ACK, applying any PARAMS pushed ahead of it
        (the server orders PARAMS-then-ACK so a fresh snapshot is live
        before the next collect phase).

        Deadline-aware (transport.recv_frame_heartbeat): a learner silent
        past the read deadline is PINGed once and declared dead on a
        second silence — this wait was the fleet's last unbounded read."""
        while True:
            kind, payload = recv_frame_heartbeat(
                sock,
                max_frame_bytes=self.max_frame_bytes,
                bytes_in=self._obs_bytes_in.inc,
                bytes_out=self._obs_bytes_out.inc,
            )
            self._obs_bytes_in.inc(HEADER_BYTES + len(payload))
            if kind == K_PARAMS:
                self.maybe_apply_params(self._unpacker.unpack(payload))
                continue
            if kind == K_ACK:
                return unpack_obj(payload)  # wire-lint: control
            if kind == K_BYE:
                raise _OrderlyShutdown()
            raise FrameError(f"unexpected frame kind {kind}")


class _OrderlyShutdown(Exception):
    """Server said BYE mid-stream: exit 0, nothing crashed."""


class _WireRefused(FrameError):
    """HELLO refused: deterministic config mismatch, not a transient crash.

    Exits with ``EXIT_WIRE_REFUSED`` so the supervisor gives the slot up
    instead of crash-restarting a misconfigured actor forever (every
    incarnation would be refused again within milliseconds)."""


class _AuthRefused(FrameError):
    """HELLO refused on the --fleet-token proof: deterministic
    misconfiguration, same terminal contract as ``_WireRefused`` (exits
    ``EXIT_AUTH_REFUSED``; the supervisor gives the slot up)."""


# ---------------------------------------------------------------------- CLI
def structural_argv(exp: ExperimentConfig):
    """The actor flags that must MIRROR the learner's resolved config —
    net/param-tree structure (a mismatched tree crash-loops every actor)
    and the exploration ladder.  THE single source for the spawner
    (train.py forwards exactly this); a new structural knob is added here
    plus the parser/_apply_overrides below, never hand-copied into
    spawners."""
    return [
        "--num-envs", str(exp.trainer.num_envs),
        "--n-step", str(exp.agent.n_step),
        "--twin-critic", "1" if exp.agent.twin_critic else "0",
        "--sigma-max", str(exp.trainer.sigma_max),
        "--ladder-alpha", str(exp.trainer.ladder_alpha),
        "--compute-dtype", exp.compute_dtype,
    ]


def parse_args(argv=None) -> argparse.Namespace:
    p = argparse.ArgumentParser(
        prog="python -m r2d2dpg_tpu.fleet.actor", description=__doc__
    )
    p.add_argument("--config", required=True, choices=sorted(CONFIGS))
    p.add_argument("--connect", required=True, help="ingest server address")
    p.add_argument("--actor-id", type=int, required=True)
    p.add_argument("--num-actors", type=int, required=True)
    p.add_argument("--seed", type=int, default=None)
    p.add_argument("--phases", type=int, default=None,
                   help="stop after this many collect phases (default: "
                   "stream until the server disconnects)")
    # Structural/exploration overrides — must match the learner's so the
    # published param trees fit the actor's nets (train.py forwards them).
    p.add_argument("--num-envs", type=int, default=None)
    p.add_argument("--n-step", type=int, default=None)
    p.add_argument("--twin-critic", type=int, default=None, choices=[0, 1])
    p.add_argument("--sigma-max", type=float, default=None)
    p.add_argument("--ladder-alpha", type=float, default=None)
    p.add_argument("--compute-dtype", default=None,
                   choices=["float32", "bfloat16"])
    # Wire fast lane — must mirror the learner's --fleet-wire/
    # --fleet-compress (the ingest server refuses a mismatched HELLO).
    p.add_argument("--wire", default="f32", choices=list(wire.ENCODINGS),
                   help="payload precision on the wire (bf16: observations/"
                   "carries/params downcast; rewards/priorities stay f32)")
    p.add_argument("--compress", default="none",
                   choices=list(wire.COMPRESSIONS),
                   help="frame compression (zstd only where the zstandard "
                   "module is installed)")
    p.add_argument("--max-frame-bytes", type=int, default=MAX_FRAME_BYTES,
                   help="frame ceiling — must mirror the learner's "
                   "FleetConfig.max_frame_bytes (the spawner forwards it)")
    p.add_argument("--flight-path", default=None,
                   help="dump this actor's flight ring here on exit")
    # Fleet observability plane (ISSUE 6; train.py --obs-fleet/
    # --trace-sample forward these).
    p.add_argument("--telem-every", type=float, default=0.0,
                   help="seconds between TELEM registry-snapshot pushes to "
                   "the learner's ingest (0 = off; --obs-fleet spawns 1.0)")
    p.add_argument("--trace-sample", type=float, default=0.0,
                   help="experience-path trace sampling rate in [0, 1] "
                   "(0 = off: no trace sidecar, byte-identical wire)")
    # Fault tolerance (ISSUE 7; docs/FLEET.md "Failure modes & recovery").
    p.add_argument("--read-deadline", type=float, default=READ_DEADLINE_S,
                   help="seconds a blocking wire read may wait before the "
                   "PING-then-reap liveness protocol runs — must mirror "
                   "the learner's --fleet-heartbeat (the spawner forwards "
                   "it)")
    p.add_argument("--fleet-token", default=None,
                   help="shared HELLO-authentication secret; defaults to "
                   "$R2D2DPG_FLEET_TOKEN (the spawner passes the secret "
                   "via the environment so it never shows in ps)")
    # Direct data plane (ISSUE 17; train.py --shard-direct forwards it).
    p.add_argument("--shard-direct", type=int, default=0, choices=[0, 1],
                   help="1: dial the learner-advertised replay shard and "
                   "ship SEQS to it directly (control connection carries "
                   "params/telem/accounting only); falls back loudly to "
                   "the learner-forwarded path on any data-leg failure")
    p.add_argument("--chaos-spec", default=None,
                   help="seeded chaos schedule (fleet/chaos.py grammar); "
                   "this actor fires the stall/corrupt faults that target "
                   "its id (the learner's engine fires the rest)")
    return p.parse_args(argv)


def _apply_overrides(exp: ExperimentConfig, args) -> ExperimentConfig:
    t = {
        k: getattr(args, k)
        for k in ("num_envs", "sigma_max", "ladder_alpha", "seed")
        if getattr(args, k) is not None
    }
    if t:
        exp = dataclasses.replace(
            exp, trainer=dataclasses.replace(exp.trainer, **t)
        )
    a = {}
    if args.n_step is not None:
        a["n_step"] = args.n_step
    if args.twin_critic is not None:
        a["twin_critic"] = bool(args.twin_critic)
    if a:
        exp = dataclasses.replace(
            exp, agent=dataclasses.replace(exp.agent, **a)
        )
    if args.compute_dtype is not None:
        exp = dataclasses.replace(exp, compute_dtype=args.compute_dtype)
    return exp


def main(argv=None) -> None:
    args = parse_args(argv)
    set_flight_identity(actor=args.actor_id)
    if args.flight_path:
        import os
        import signal

        from r2d2dpg_tpu.obs import get_flight_recorder

        flight_path = args.flight_path
        if os.path.exists(flight_path):
            # A predecessor incarnation (supervised restart) already
            # dumped here — its ring is post-mortem EVIDENCE (possibly a
            # chaos injection flushed moments before its SIGKILL), and an
            # overwrite would destroy it.  Dump beside it instead; the
            # fleet timeline merge globs flight*.jsonl, so both
            # incarnations stay attributable.
            root, ext = os.path.splitext(flight_path)
            flight_path = f"{root}.pid{os.getpid()}{ext}"
        get_flight_recorder().install(flight_path)
        # The supervisor's orderly teardown is a SIGTERM, whose default
        # disposition skips atexit — and with it the flight dump this
        # flag just armed.  Convert it to a clean SystemExit so every
        # incarnation leaves its flight_actor<i>.jsonl for the fleet
        # timeline merge (obs/flight.py).
        signal.signal(signal.SIGTERM, lambda *_: sys.exit(0))
    exp = _apply_overrides(get_config(args.config), args)
    try:
        wire_config = wire.WireConfig(
            encoding=args.wire, compress=args.compress
        ).validate()
    except ValueError as e:
        raise SystemExit(f"fleet actor {args.actor_id}: --compress: {e}")
    if not 0.0 <= args.trace_sample <= 1.0:
        raise SystemExit(
            f"fleet actor {args.actor_id}: --trace-sample must be in [0, 1]"
        )
    auth_token = args.fleet_token
    if auth_token is None:
        import os

        auth_token = os.environ.get("R2D2DPG_FLEET_TOKEN") or None
    try:
        actor = FleetActor(
            exp,
            actor_id=args.actor_id,
            num_actors=args.num_actors,
            address=args.connect,
            seed=args.seed,
            wire_config=wire_config,
            max_frame_bytes=args.max_frame_bytes,
            telem_every=args.telem_every,
            trace_sample=args.trace_sample,
            read_deadline_s=args.read_deadline,
            auth_token=auth_token,
            shard_direct=bool(args.shard_direct),
            chaos_spec=args.chaos_spec,
        )
    except ValueError as e:
        # e.g. a malformed --chaos-spec: deterministic misconfiguration,
        # refused at startup rather than as a crash-looping fleet.
        raise SystemExit(f"fleet actor {args.actor_id}: {e}")
    # The supervisor's retire_slot speaks SIGUSR1 (ISSUE 16 scale-down):
    # finish the phase, fold the accounting via its ack, BYE, exit 0.
    # PEP 475 restarts the interrupted socket call, so a drain never
    # tears a frame — it lands at the next loop check.
    import signal

    signal.signal(signal.SIGUSR1, lambda *_: actor.request_drain())
    # The backend is stamped so a post-mortem can confirm the actor stayed
    # off the learner's chip (the supervisor pins JAX_PLATFORMS=cpu).
    flight_event(
        "actor_start",
        phase=0,
        address=args.connect,
        backend=jax.default_backend(),
    )
    try:
        actor.run(max_phases=args.phases)
    except _OrderlyShutdown:
        # The server said BYE: the learner is done — exit 0, nothing broke.
        flight_event("actor_disconnect", phase=actor._phase)
    except (_WireRefused, _AuthRefused) as e:
        # Deterministic misconfiguration — a restart would be refused
        # again within milliseconds.  Exit with the dedicated code so the
        # supervisor gives this slot up instead of crash-looping it.
        err = f"{type(e).__name__}: {e}"
        auth = isinstance(e, _AuthRefused)
        flight_event(
            "actor_auth_refused" if auth else "actor_wire_refused",
            phase=actor._phase,
            error=err,
        )
        print(  # obs-lint: allow — CLI entrypoint, routed to the actor log
            f"fleet actor {args.actor_id}: {err}",
            file=sys.stderr,
            flush=True,
        )
        raise SystemExit(EXIT_AUTH_REFUSED if auth else EXIT_WIRE_REFUSED)
    except (FrameError, OSError) as e:
        # Anything else — refused connect, CRC violation, torn stream — is
        # a CRASH per this module's contract: record the actual error
        # (flight ring + stderr, which the supervisor routes to the
        # per-actor log) and exit nonzero so the supervisor restarts us.
        err = f"{type(e).__name__}: {e}"
        flight_event("actor_conn_lost", phase=actor._phase, error=err)
        raise SystemExit(
            f"fleet actor {args.actor_id}: connection lost at phase "
            f"{actor._phase}: {err}"
        )
    flight_event("actor_exit", phase=actor._phase, sheds=actor._sheds)


if __name__ == "__main__":
    main()
