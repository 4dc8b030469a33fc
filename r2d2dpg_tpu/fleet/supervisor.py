"""Actor supervision: spawn, watch, restart with exponential backoff.

The reference repo's ``main.py`` spawns actor processes and forgets them;
a crashed actor silently thins the fleet forever.  Here the supervisor is
the fleet's process-lifecycle owner: it spawns each actor as a
subprocess, polls liveness on a monitor thread, and restarts any actor
that exits while the fleet is live — after an exponential backoff (a
crash-looping actor must not fork-bomb the host), reset once an
incarnation survives ``healthy_after_s`` (a crash after an hour is bad
luck, not a loop).  Every crash lands in the flight recorder
(``actor_crash`` with actor id, returncode, restart count), so a fleet
post-mortem's first question — "who died, when, how often" — reads
straight out of ``flight.jsonl``.

Actors are forced onto CPU (``JAX_PLATFORMS=cpu``): env stepping is host
work, and a chip belongs to one process — an actor subprocess reaching for
the learner's accelerator would fail or hang.
"""

from __future__ import annotations

import dataclasses
import os
import signal
import subprocess
import sys
import threading
import time
from typing import Callable, Dict, List, Optional

from r2d2dpg_tpu.obs import flight_event, get_registry
from r2d2dpg_tpu.utils.codes import TERMINAL_ACTOR_EXITS


@dataclasses.dataclass(frozen=True)
class SupervisorConfig:
    backoff_base_s: float = 0.5  # first restart delay; doubles per crash
    backoff_max_s: float = 30.0
    healthy_after_s: float = 60.0  # uptime that resets the backoff ladder
    max_restarts: Optional[int] = None  # per actor; None = never give up
    poll_s: float = 0.2
    # Who owns a crashed slot's respawn (ISSUE 16): "backoff" is the
    # reflexive ladder above; "policy" records the crash and leaves the
    # slot DOWN for an external policy engine (fleet/autoscaler.py) to
    # replace via spawn_slot — the autoscaled fleet's recovery is a
    # decision, not a reflex.  Terminal exits give the slot up either way.
    restart: str = "backoff"
    # retire_slot drain window: seconds a retiring worker gets to finish
    # its phase and send BYE before the monitor escalates SIGTERM (and,
    # one more window later, SIGKILL).
    retire_grace_s: float = 10.0


@dataclasses.dataclass
class _ActorSlot:
    proc: Optional[subprocess.Popen] = None
    started_at: float = 0.0
    restarts: int = 0
    consecutive_crashes: int = 0
    restart_at: Optional[float] = None  # backoff deadline when dead
    gave_up: bool = False
    # Runtime-resize state (ISSUE 16): a retired slot is DRAINING out of
    # the fleet (SIGUSR1 -> finish phase -> BYE -> exit 0) — the monitor
    # must never read its exit as a crash to restart (that churn is the
    # exact bug the retire path exists to avoid).  ``retire_at`` is the
    # escalation deadline; ``term_sent`` marks SIGTERM already escalated.
    retired: bool = False
    retire_at: Optional[float] = None
    term_sent: bool = False


class ActorSupervisor:
    """Owns ``num_actors`` worker subprocesses for the life of a fleet run.

    ``argv_fn(actor_id)`` builds each worker's command line (train.py wires
    ``python -m r2d2dpg_tpu.fleet.actor ...`` with the ingest address);
    ``log_path_fn(actor_id)``, when given, routes the worker's
    stdout/stderr to a per-worker file for post-mortems.

    ``role`` names the supervised process class: ``"actor"`` (default,
    the historical metric/event names) or ``"shard"`` (the standalone
    replay-shard tier, ISSUE 12 — ``r2d2dpg_shard_alive`` /
    ``r2d2dpg_shard_restarts_total`` gauges, ``shard_crash`` /
    ``shard_restart`` / ``shard_gave_up`` flight events).  The whole
    backoff/give-up/terminal-exit ladder is role-agnostic — one
    supervision contract for every fleet process class.
    """

    def __init__(
        self,
        argv_fn: Callable[[int], List[str]],
        num_actors: int,
        *,
        config: SupervisorConfig = SupervisorConfig(),
        env: Optional[Dict[str, str]] = None,
        log_path_fn: Optional[Callable[[int], str]] = None,
        clock: Callable[[], float] = time.monotonic,
        role: str = "actor",
        id_field: Optional[str] = None,
    ):
        if num_actors < 1:
            raise ValueError("num_actors must be >= 1")
        self.argv_fn = argv_fn
        self.num_actors = num_actors
        self.config = config
        self.log_path_fn = log_path_fn
        self.role = role
        # The flight-event key carrying the supervised slot index.  The
        # shard tier names it "shard_proc": its slot is a PROCESS hosting
        # M/N shards, and reusing "shard" would collide with the shard-ID
        # unit the learner's shard_dead/shard_rejoin events carry — a
        # flight-merge post-mortem must never conflate the two.
        self.id_field = id_field or role
        # Injectable clock: the backoff/give-up timing contract is tested
        # against a FAKE clock (tests drive _poll_once directly), so the
        # healthy-uptime reset and restart_at deadlines are pinned without
        # real sleeps.
        self._clock = clock
        self._env = dict(os.environ if env is None else env)
        # CPU discipline (module docstring): the learner owns the chip.
        self._env["JAX_PLATFORMS"] = "cpu"
        self._env.setdefault("R2D2DPG_PALLAS_INTERPRET", "1")
        self._slots: Dict[int, _ActorSlot] = {
            i: _ActorSlot() for i in range(num_actors)
        }
        # The runtime population target (ISSUE 16): starts at the spawn
        # count; set_target moves it while the fleet is live.  num_actors
        # stays the STARTUP value — chaos fault hashing and the sigma
        # ladder width are fixed at spawn time and must not drift with it.
        self._target = num_actors
        self._lock = threading.Lock()
        self._stopping = threading.Event()
        self._monitor: Optional[threading.Thread] = None
        # Fleet health at scrape time (ISSUE 6): the central process-health
        # view Ape-X-scale fleets live on — live process count (set_fn:
        # evaluated per scrape) and cumulative restarts.  Metric names are
        # per-ROLE so an actor fleet and a shard tier in one learner never
        # share (or clobber) a series.
        reg = get_registry()
        if role == "actor":
            alive_name = "r2d2dpg_fleet_actors_alive"
            restarts_name = "r2d2dpg_fleet_actor_restarts_total"
        else:
            alive_name = f"r2d2dpg_{role}_alive"
            restarts_name = f"r2d2dpg_{role}_restarts_total"
        self._obs_alive = reg.gauge(
            alive_name,
            f"live supervised {role} subprocesses",
        )
        self._obs_alive.set_fn(lambda: float(self.alive_count()))
        self._obs_restarts = reg.counter(
            restarts_name,
            f"supervised {role} restarts (crash -> backoff -> respawn)",
        )

    # ------------------------------------------------------------- lifecycle
    def start(self) -> "ActorSupervisor":
        if self._monitor is not None:
            raise RuntimeError("supervisor already started")
        for i in range(self.num_actors):
            self._spawn(i)
        self._monitor = threading.Thread(
            target=self._monitor_loop, name="fleet-supervisor", daemon=True
        )
        self._monitor.start()
        return self

    def stop(self, timeout: float = 10.0) -> None:
        """Orderly teardown: no restarts from here on, SIGTERM the fleet,
        SIGKILL stragglers.  Call BEFORE stopping the ingest server so a
        connection reset never masquerades as a crash."""
        self._stopping.set()
        if self._monitor is not None:
            self._monitor.join(timeout=5)
        with self._lock:
            procs = [s.proc for s in self._slots.values() if s.proc is not None]
        for p in procs:
            if p.poll() is None:
                p.terminate()
        deadline = time.monotonic() + timeout
        for p in procs:
            if p.poll() is None:
                try:
                    p.wait(timeout=max(deadline - time.monotonic(), 0.1))
                except subprocess.TimeoutExpired:
                    p.kill()
                    p.wait()

    # ------------------------------------------------------------ inspection
    def alive_count(self) -> int:
        with self._lock:
            return sum(
                1
                for s in self._slots.values()
                if s.proc is not None and s.proc.poll() is None
            )

    @property
    def restarts_total(self) -> int:
        with self._lock:
            return sum(s.restarts for s in self._slots.values())

    def kill_actor(self, actor_id: int) -> bool:
        """Test/drill hook: hard-kill one actor (the supervisor sees a
        crash and walks the restart path — the soak test's lever).
        Returns True when a kill was actually delivered — False for a slot
        that is already a corpse or mid-backoff, so a chaos drill can tell
        a real injection from a no-op (fleet/chaos.py keeps no-ops
        pending instead of recording a drill that never ran)."""
        with self._lock:
            proc = self._slots[actor_id].proc
        if proc is not None and proc.poll() is None:
            proc.kill()
            return True
        return False

    # ------------------------------------------------- runtime resize (16)
    @property
    def target(self) -> int:
        """The current population target (set_target moves it)."""
        with self._lock:
            return self._target

    def slot_states(self) -> Dict[int, str]:
        """Each slot's lifecycle state, for policy decisions and tests:
        ``live`` / ``backoff`` (ladder owns a pending respawn) / ``down``
        (dead, nobody owns a respawn — a policy-mode corpse) /
        ``retired`` / ``gave_up``."""
        out: Dict[int, str] = {}
        with self._lock:
            for i, s in self._slots.items():
                if s.gave_up:
                    out[i] = "gave_up"
                elif s.retired:
                    out[i] = "retired"
                elif s.proc is not None and s.proc.poll() is None:
                    out[i] = "live"
                elif s.restart_at is not None:
                    out[i] = "backoff"
                else:
                    out[i] = "down"
        return out

    def spawn_slot(self, actor_id: int, *, origin: str = "resize") -> bool:
        """Explicitly (re)spawn one slot at runtime — the policy engine's
        replace/scale-up actuator.

        Pending-until-landed contract (the PR 12 chaos convention): the
        spawn returns False — caller keeps it pending and retries — when
        the slot's process is still alive, or when the backoff ladder
        already owns a pending respawn (``restart_at`` armed): landing it
        anyway would put TWO processes in one ladder lane.  A gave-up
        terminal slot IS spawnable here — this explicit call is the
        "unless explicitly re-targeted" escape hatch scale-up never takes.
        """
        with self._lock:
            if self._stopping.is_set():
                return False
            slot = self._slots.get(actor_id)
            if slot is None:
                slot = self._slots[actor_id] = _ActorSlot()
            if slot.proc is not None and slot.proc.poll() is None:
                return False  # still alive (or still draining a retire)
            if slot.restart_at is not None and not slot.gave_up:
                return False  # mid-backoff: the monitor owns this respawn
            resurrected = slot.gave_up
            slot.gave_up = False
            slot.retired = False
            slot.retire_at = None
            slot.term_sent = False
            slot.consecutive_crashes = 0
            try:
                self._spawn(actor_id)
            except Exception as e:  # noqa: BLE001 — same contract as the
                # monitor's respawn: a failed exec is an event, never an
                # exception into the policy loop.
                flight_event(
                    f"{self.role}_spawn_failed",
                    **{self.id_field: actor_id},
                    error=f"{type(e).__name__}: {e}",
                )
                return False
            flight_event(
                f"{self.role}_spawn",
                **{self.id_field: actor_id},
                origin=origin,
                resurrected=resurrected,
            )
            return True

    def retire_slot(self, actor_id: int, *, origin: str = "resize") -> bool:
        """Drain one slot out of the fleet — the scale-down actuator.

        The slot is marked retired FIRST (the monitor skips it, so its
        exit can never read as a crash to restart), then the worker gets
        SIGUSR1: fleet/actor.py finishes its current phase, sends BYE
        (banked accounting already folded by the last ack) and exits 0.
        A worker that ignores the drain past ``retire_grace_s`` is
        escalated SIGTERM, then SIGKILL one grace later (_poll_once).
        Returns False for a slot that is already retired/gave-up/absent
        (no-op; pending-until-landed callers may retry elsewhere)."""
        with self._lock:
            slot = self._slots.get(actor_id)
            if slot is None or slot.retired or slot.gave_up:
                return False
            slot.retired = True
            slot.restart_at = None
            slot.retire_at = self._clock() + self.config.retire_grace_s
            slot.term_sent = False
            proc = slot.proc
            draining = proc is not None and proc.poll() is None
            if draining:
                try:
                    proc.send_signal(signal.SIGUSR1)
                except (OSError, ValueError):
                    draining = False
            flight_event(
                f"{self.role}_retire",
                **{self.id_field: actor_id},
                origin=origin,
                draining=draining,
            )
            return True

    def set_target(self, n: int, *, lane_limit: Optional[int] = None) -> Dict[str, List[int]]:
        """Resize the live population to ``n`` slots.

        Scale-down retires the HIGHEST-indexed active slots (the newest
        sigma-ladder lanes drain first; lane 0 is the greediest explorer
        and the last to go).  Scale-up re-fills the LOWEST free lane —
        where "free" never includes a gave-up terminal slot (resurrection
        needs an explicit spawn_slot) or a lane whose old process is
        still draining.  ``lane_limit`` caps mintable lane ids (the
        autoscaler passes its --autoscale-max so a new actor always fits
        the global sigma ladder).  Returns the slot ids spawned and
        retiring; a spawn that cannot land (mid-backoff lane) stops the
        walk — callers retry on their own cadence."""
        if n < 0:
            raise ValueError("set_target: n must be >= 0")
        with self._lock:
            previous, self._target = self._target, n
        if n != previous:
            flight_event(
                f"{self.role}_set_target", target=n, previous=previous
            )
        spawned: List[int] = []
        retiring: List[int] = []
        while True:
            with self._lock:
                active = sorted(
                    i
                    for i, s in self._slots.items()
                    if not s.retired and not s.gave_up
                )
            if len(active) <= n:
                break
            if not self.retire_slot(active[-1], origin="resize"):
                break
            retiring.append(active[-1])
        while True:
            with self._lock:
                active = {
                    i
                    for i, s in self._slots.items()
                    if not s.retired and not s.gave_up
                }
                if len(active) >= n:
                    break
                lane = 0
                while True:
                    s = self._slots.get(lane)
                    if lane not in active and (
                        s is None
                        or (
                            not s.gave_up
                            and (s.proc is None or s.proc.poll() is not None)
                        )
                    ):
                        break
                    lane += 1
                if lane_limit is not None and lane >= lane_limit:
                    lane = None
            if lane is None or not self.spawn_slot(lane, origin="resize"):
                break
            spawned.append(lane)
        return {"spawned": spawned, "retiring": retiring}

    # -------------------------------------------------------------- internal
    def _spawn(self, actor_id: int) -> None:
        slot = self._slots[actor_id]
        stdout = subprocess.DEVNULL
        if self.log_path_fn is not None:
            stdout = open(self.log_path_fn(actor_id), "ab")
        try:
            slot.proc = subprocess.Popen(
                self.argv_fn(actor_id),
                env=self._env,
                stdout=stdout,
                stderr=subprocess.STDOUT,
                stdin=subprocess.DEVNULL,
            )
        finally:
            if stdout is not subprocess.DEVNULL:
                stdout.close()  # child holds its own fd
        slot.started_at = self._clock()
        slot.restart_at = None

    def _monitor_loop(self) -> None:
        while not self._stopping.is_set():
            self._poll_once(self._clock())
            self._stopping.wait(self.config.poll_s)

    def _poll_once(self, now: float) -> None:
        """One supervision pass at time ``now`` — the whole timing contract
        (healthy-uptime ladder reset, backoff arming, restart_at deadline,
        give-up paths) in one directly-testable step (the fake-clock tests
        call this; the monitor thread calls it on ``poll_s``)."""
        cfg = self.config
        with self._lock:
            for actor_id, slot in self._slots.items():
                if slot.gave_up:
                    continue
                if slot.retired:
                    # Draining out (retire_slot): the exit here is ASKED
                    # FOR — reap it as a drain, never as a crash, and
                    # never arm the backoff ladder (an autoscale kill
                    # must not trigger crash-restart churn).
                    proc = slot.proc
                    if proc is None:
                        continue  # already reaped
                    if proc.poll() is not None:
                        flight_event(
                            f"{self.role}_drained",
                            **{self.id_field: actor_id},
                            returncode=proc.returncode,
                        )
                        slot.proc = None
                        slot.retire_at = None
                        continue
                    if slot.retire_at is not None and now >= slot.retire_at:
                        # Ignored the SIGUSR1 drain: escalate SIGTERM,
                        # then SIGKILL one more grace window later.
                        if not slot.term_sent:
                            proc.terminate()
                            slot.term_sent = True
                            slot.retire_at = now + cfg.retire_grace_s
                        else:
                            proc.kill()
                            slot.retire_at = None  # next poll reaps
                    continue
                if slot.proc is not None and slot.proc.poll() is None:
                    # Healthy uptime resets the backoff ladder.
                    if (
                        slot.consecutive_crashes
                        and now - slot.started_at > cfg.healthy_after_s
                    ):
                        slot.consecutive_crashes = 0
                    continue
                if slot.proc is not None and slot.restart_at is None:
                    # Fresh corpse: record, arm the backoff.
                    rc = slot.proc.returncode
                    slot.consecutive_crashes += 1
                    backoff = min(
                        cfg.backoff_base_s
                        * (2 ** (slot.consecutive_crashes - 1)),
                        cfg.backoff_max_s,
                    )
                    flight_event(
                        f"{self.role}_crash",
                        **{self.id_field: actor_id},
                        returncode=rc,
                        restarts=slot.restarts,
                        backoff_s=round(backoff, 3),
                    )
                    if rc in TERMINAL_ACTOR_EXITS:
                        # Deterministic HELLO refusal (wire mismatch or
                        # auth failure): every restart would be refused
                        # again within milliseconds (healthy_after_s never
                        # resets the ladder) — give the slot up NOW with a
                        # terminal event instead of churning forever.
                        slot.gave_up = True
                        flight_event(
                            f"{self.role}_gave_up",
                            **{self.id_field: actor_id},
                            restarts=slot.restarts,
                            reason=TERMINAL_ACTOR_EXITS[rc],
                        )
                        continue
                    if (
                        cfg.max_restarts is not None
                        and slot.restarts >= cfg.max_restarts
                    ):
                        slot.gave_up = True
                        flight_event(
                            f"{self.role}_gave_up",
                            **{self.id_field: actor_id},
                            restarts=slot.restarts,
                        )
                        continue
                    if cfg.restart == "policy":
                        # Policy-owned recovery (ISSUE 16): leave the
                        # slot DOWN — no restart_at, no reflexive
                        # respawn.  The autoscaler reads actors_down and
                        # decides; its spawn_slot is the only way back.
                        slot.proc = None
                        continue
                    slot.restart_at = now + backoff
                if (
                    slot.restart_at is not None
                    and now >= slot.restart_at
                ):
                    # A failed spawn (logdir vanished, ENOSPC, exec
                    # error) must not kill THIS thread — supervision
                    # is the subsystem's headline feature.  Note it
                    # and retry on the max backoff.
                    try:
                        self._spawn(actor_id)
                    except Exception as e:  # noqa: BLE001
                        flight_event(
                            f"{self.role}_spawn_failed",
                            **{self.id_field: actor_id},
                            error=f"{type(e).__name__}: {e}",
                        )
                        slot.restart_at = now + cfg.backoff_max_s
                        continue
                    slot.restarts += 1
                    self._obs_restarts.inc()
                    flight_event(
                        f"{self.role}_restart",
                        **{self.id_field: actor_id},
                        restarts=slot.restarts,
                    )


def default_actor_argv(
    actor_id: int,
    *,
    config_name: str,
    address: str,
    num_actors: int,
    seed: Optional[int] = None,
    extra: Optional[List[str]] = None,
) -> List[str]:
    """The standard actor command line (train.py's spawner)."""
    argv = [
        sys.executable,
        "-m",
        "r2d2dpg_tpu.fleet.actor",
        "--config",
        config_name,
        "--connect",
        address,
        "--actor-id",
        str(actor_id),
        "--num-actors",
        str(num_actors),
    ]
    if seed is not None:
        argv += ["--seed", str(seed)]
    if extra:
        argv += list(extra)
    return argv
