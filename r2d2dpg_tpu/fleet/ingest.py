"""Learner-side experience ingest: N actor connections -> one staging queue.

The Ape-X topology's center (PAPERS.md 1803.00933): out-of-process actors
stream ``replay.StagedSequences`` batches over the fleet wire protocol
(``fleet/transport.py``); this server reassembles them onto the SAME
bounded staging queue / ``ReplayArena.add_staged`` path the in-process
pipelined executor uses (``training/pipeline.py``), so fleet experience
enters the arena through the exact drain program local experience does.

Per-connection protocol (one handler thread per actor; that thread is the
connection's ONLY writer, so acks and param pushes never interleave):

    actor                          ingest handler
    -----                          --------------
    HELLO {actor_id, wire...} ->        (wire mismatch: ACK refused_wire
                                         + close — fleet/wire.py)
                              <-   [PARAMS {version, params}]   (if any)
                              <-   ACK {code: ok, param_version}
    SEQS {staged, stats}      ->   staging_queue.put (bounded wait)
                              <-   [PARAMS]     (actor's version is stale)
                              <-   ACK {code: ok | shed_ingest_queue_full}
    TELEM {snapshot}          ->   fold into the obs RemoteMirror under
                                   actor=/host= labels (no ack; malformed
                                   frames drop with a flight event) — the
                                   learner's /metrics is the fleet's ONE
                                   scrape point (ISSUE 6)
    ...
    BYE                       ->   (or either side just closes)

Backpressure/shed contract: the actor blocks on the ACK, so it has at most
one unacknowledged batch in flight; the handler waits ``shed_after_s`` for
queue room and then **sheds loudly** — ``SHED_INGEST`` ack (the actor
counts and keeps collecting), a ``shed`` flight-recorder event, and the
per-actor shed counter.  Experience is the one payload that may be dropped
under pressure: fresher experience is already behind it.

The drain side (``FleetLearner``) runs on the caller's thread and is the
staging queue's single consumer — the single-writer contract
``ReplayArena.add_staged`` enforces (docs/FLEET.md "Single writer").
"""

from __future__ import annotations

import dataclasses
import hmac
import json
import os
import queue
import socket
import threading
import time
from typing import Any, Callable, Dict, List, Optional

import jax
import numpy as np

from r2d2dpg_tpu.fleet import transport, wire
from r2d2dpg_tpu.fleet.transport import (
    HEADER_BYTES,
    K_ACK,
    K_BYE,
    K_HELLO,
    K_PARAMS,
    K_SEQS,
    K_STATS,
    K_TELEM,
    FrameError,
    PeerDeadError,
    pack_obj,
    recv_frame,
    recv_frame_heartbeat,
    send_frame,
    to_host,
    unpack_obj,
)
from r2d2dpg_tpu.obs import flight_event, get_registry, get_remote_mirror
from r2d2dpg_tpu.obs import trace as obs_trace
from r2d2dpg_tpu.obs.device import get_device_monitor
from r2d2dpg_tpu.obs.quality import (
    get_quality_plane,
    policy_lags,
    quality_stats_columns,
)
from r2d2dpg_tpu.replay.arena import stack_staged, staged_nbytes
from r2d2dpg_tpu.training.pipeline import (
    LearnerState,
    coalesce_from_queue,
    drain_staged,
    merge_state,
    split_state,
)
from r2d2dpg_tpu.training.trainer import Trainer, TrainerState
from r2d2dpg_tpu.utils.codes import (
    OK,
    REFUSED_AUTH,
    REFUSED_WIRE,
    SHED_INGEST,
)


@dataclasses.dataclass(frozen=True)
class FleetConfig:
    """Static fleet knobs (the trainer's own config governs the rest)."""

    num_actors: int
    address: str = "127.0.0.1:0"  # "host:port" (0 = ephemeral) | "unix:/path"
    queue_depth: int = 4  # staging-queue capacity, in staged batches
    publish_every: int = 1  # drain phases between param publications
    prefetch: bool = True  # double-buffered sampling in the drain program
    shed_after_s: float = 1.0  # handler waits this long before shedding
    # Sheds are suppressed (handlers wait this long instead) until the
    # first drain-learn has EXECUTED: the drain program's one-time compile
    # takes tens of seconds on a small host, long enough that every
    # actor's pending put used to time out exactly once — the historical
    # "sheds == num_actors" startup artifact (docs/FLEET.md).
    startup_shed_grace_s: float = 120.0
    idle_timeout_s: float = 300.0  # no batch for this long = starved, abort
    max_frame_bytes: int = transport.MAX_FRAME_BYTES
    # The wire fast lane (fleet/wire.py): one encoding/compression per
    # fleet, negotiated at HELLO; actors with a different lane are refused.
    wire: wire.WireConfig = wire.WireConfig()
    # Max queued staged batches stacked into ONE compiled drain call (the
    # arena-add dispatch amortization); 1 = today's one-call-per-batch.
    drain_coalesce: int = 1
    # Liveness (docs/FLEET.md "Failure modes"): per-connection read
    # deadline in seconds — a peer silent past it is PINGed once and
    # reaped on a second silence (transport.recv_frame_heartbeat).  The
    # window between HELLO and the first SEQS frame uses the LARGER of
    # this and ``warmup_deadline_s`` (a fresh actor legitimately goes
    # silent for its collect-program compile).
    heartbeat_s: float = transport.READ_DEADLINE_S
    warmup_deadline_s: float = 120.0
    # Shared-secret HELLO authentication (hmac.compare_digest); None = no
    # auth.  REQUIRED before binding a routable (non-loopback) address on
    # anything but a trusted network.
    auth_token: Optional[str] = None
    # Split-plane wire (ISSUE 17): actors dial their shard DIRECTLY for
    # SEQS (the ingest ack carries the assignment + dialable address),
    # keeping the learner connection as a control plane for HELLO/params/
    # TELEM/accounting.  Requires the standalone shard tier; the actor
    # falls back LOUDLY to learner-forwarded SEQS when the direct dial is
    # refused, partitioned, or the tier is in-learner.
    shard_direct: bool = False
    # Sampling-boundary concurrency (ISSUE 17): N concurrent pullers over
    # M shards (0 = auto: min(shards, 8); 1 = serial, the control leg) and
    # one phase of batch prefetch overlapping the compiled learn step
    # (0 = off — the determinism-anchor default).
    shard_pullers: int = 0
    shard_prefetch: int = 0


class IngestServer:
    """Accepts actor connections and feeds the learner's staging queue."""

    def __init__(
        self,
        staging_queue: "queue.Queue",
        *,
        address: str = "127.0.0.1:0",
        shed_after_s: float = 1.0,
        startup_shed_grace_s: float = 120.0,
        max_frame_bytes: int = transport.MAX_FRAME_BYTES,
        wire_config: Optional[wire.WireConfig] = None,
        read_deadline_s: float = transport.READ_DEADLINE_S,
        warmup_deadline_s: float = 120.0,
        auth_token: Optional[str] = None,
        shards=None,
        expected_actors: Optional[int] = None,
        shard_assignment_fn: Optional[Callable[[str], Any]] = None,
    ):
        self.queue = staging_queue
        # In-network sampling (fleet/sampler.py, ISSUE 10): when a
        # ``ShardSet`` is given, SEQS batches bypass the staging queue —
        # each handler writes straight into its actor's replay shard
        # (consistent-hash routing assigned at HELLO) under that shard's
        # own lock, so N handlers add concurrently and NOTHING sheds
        # (a full shard ring FIFO-evicts re-collectable experience).
        # The standalone tier (fleet/shard.py ``RemoteShardSet``,
        # ISSUE 12) plugs in through the same two-call contract —
        # ``route(actor)`` at HELLO, ``add(shard_id, msg)`` per frame —
        # with ``add`` forwarding the experience over the shard's socket
        # (re-routing to survivors on shard death; the accounting deltas
        # bank learner-side inside ``add`` either way, so a dead shard
        # can never lose step/episode sums).  This handler is agnostic
        # to where replay lives.
        self.shards = shards
        # Direct data plane (ISSUE 17): when set, every ack on the control
        # connection carries {"shard", "address", "epoch"} for the actor's
        # home shard (``assignment_for`` on the RemoteShardSet) so the
        # actor can dial its shard directly for SEQS; epoch-bumped rejoins
        # re-advertise through the same ack field.  None (or a fn that
        # returns None — tier in-learner, shard down, address file not yet
        # published) means: keep forwarding through this server.
        self.shard_assignment_fn = shard_assignment_fn
        self._request_address = address
        self.shed_after_s = shed_after_s
        self.startup_shed_grace_s = startup_shed_grace_s
        self.max_frame_bytes = max_frame_bytes
        self.wire_config = (wire_config or wire.WireConfig()).validate()
        # Liveness: per-connection read deadline (the heartbeat bound).
        # Between HELLO and the first SEQS the LARGER of the two applies —
        # a fresh actor's collect compile is legitimate silence, and a
        # spurious reap per actor startup would drown the real signal.
        self.read_deadline_s = read_deadline_s
        self.warmup_deadline_s = max(warmup_deadline_s, read_deadline_s)
        self.auth_token = auth_token
        self.stop_join_s = 5.0  # handler join bound before leak reporting
        # Param snapshots are packed once per version and broadcast to all
        # handlers, so every frame inlines its schema — a freshly
        # reconnected (restarted) actor must decode it standalone.
        self._params_packer = wire.TreePacker(
            self.wire_config,
            always_inline=True,
            max_frame_bytes=max_frame_bytes,
        )
        # Until the first drain-learn executes (mark_steady), handlers
        # wait out the learner's compile instead of shedding (FleetConfig.
        # startup_shed_grace_s — the sheds==num_actors warmup artifact).
        # The grace also SELF-EXPIRES startup_shed_grace_s after the first
        # successful queue hand-off, so an embedder that consumes the
        # queue itself (IngestServer is public) and never calls
        # mark_steady still gets its configured shed_after_s back.
        self._steady = threading.Event()
        self._first_put_at: Optional[float] = None
        self.address: Optional[str] = None  # resolved at start()
        # What actors should DIAL: equals ``address`` except for wildcard
        # binds (0.0.0.0), where locally-spawned actors get loopback.
        self.connect_address: Optional[str] = None
        self._unix_path: Optional[str] = None
        self._listener: Optional[socket.socket] = None
        self._accept_thread: Optional[threading.Thread] = None
        self._handlers: List[threading.Thread] = []
        self._conns: Dict[int, socket.socket] = {}  # ident -> live socket
        self._conn_actors: Dict[int, str] = {}  # ident -> actor id (HELLO'd)
        self._conn_seq = 0
        self._lock = threading.Lock()
        self._stop = threading.Event()
        # Latest published params: raw host trees swapped in by the drain
        # thread (cheap), packed ONCE per version — in the negotiated wire
        # encoding — on the first handler push (_params_snapshot); neither
        # the drain thread nor later pushes pay the pack.
        self._params_obj: Optional[Any] = None
        self._params_frame: Optional[bytes] = None
        self._param_version = 0
        self.shed_total = 0
        self.seqs_total = 0
        # Wire accounting (all SEQS frames, shed or not; under _lock):
        # bytes as received vs their declared decompressed size — the
        # bench probe's bytes-on-wire and compression-ratio columns.
        self.seqs_received_total = 0
        self.seqs_bytes_total = 0
        self.seqs_raw_bytes_total = 0
        # Scalar stats riding a shed SEQS message: the EXPERIENCE may be
        # dropped under pressure, but the episode/step accounting must not
        # be (the actor already drained its accumulators) — banked here,
        # folded back in by the learner (pop_shed_stats).
        self._shed_stats = {
            "env_steps_delta": 0.0, "ep_return_sum": 0.0, "ep_count": 0.0,
        }
        # Telemetry (obs/): per-actor label sets on shared instruments.
        reg = get_registry()
        self._obs_frames = reg.counter(
            "r2d2dpg_fleet_frames_total",
            "experience frames received from actors",
            labelnames=("actor",),
        )
        self._obs_seqs = reg.counter(
            "r2d2dpg_fleet_sequences_total",
            "sequences received from actors (pre-shed)",
            labelnames=("actor",),
        )
        self._obs_shed = reg.counter(
            "r2d2dpg_fleet_shed_total",
            "staged batches shed on a full staging queue",
            labelnames=("actor",),
        )
        self._obs_staleness = reg.gauge(
            "r2d2dpg_fleet_param_staleness_versions",
            "published param version minus the actor's last-applied version",
            labelnames=("actor",),
        )
        self._obs_connected = reg.gauge(
            "r2d2dpg_fleet_actors_connected", "live actor connections"
        )
        self._obs_connected.set_fn(lambda: float(len(self._conns)))
        if expected_actors:
            # The spawn TARGET on the scrape itself (ISSUE 13): the
            # /health actors_down rule compares the supervisor's
            # r2d2dpg_fleet_actors_alive against this, so the verdict
            # needs no out-of-band config to know what "all actors up"
            # means.  Kept as an attribute so autoscale resizes
            # (set_expected_actors) move the SAME series the health rule
            # reads — the verdict tracks the moving target, not the
            # startup value.
            self._obs_expected = reg.gauge(
                "r2d2dpg_fleet_actors_expected",
                "the fleet's actor spawn target (--actors N)",
            )
            self._obs_expected.set(float(expected_actors))
        else:
            self._obs_expected = None
        self._obs_peer_dead = reg.counter(
            "r2d2dpg_fleet_peer_dead_total",
            "connections reaped after a silent heartbeat deadline (the "
            "peer answered neither frames nor the PING probe)",
            labelnames=("actor",),
        )
        self._obs_bytes_in = reg.counter(
            "r2d2dpg_fleet_bytes_in_total",
            "bytes received off the fleet wire (frames + headers)",
            labelnames=("actor",),
        )
        self._obs_bytes_out = reg.counter(
            "r2d2dpg_fleet_bytes_out_total",
            "bytes sent on the fleet wire (acks + param pushes)",
            labelnames=("actor",),
        )
        self._obs_ratio = reg.gauge(
            "r2d2dpg_fleet_compress_ratio",
            "declared decompressed size over received payload size of the "
            "last SEQS frame (1.0 = uncompressed wire)",
        )
        # Fleet observability plane (ISSUE 6 leg 1): TELEM snapshots fold
        # into the process RemoteMirror (the exporter merges it into ONE
        # /metrics page), and each actor gets a live staleness gauge so a
        # wedged actor reads as STALE, never as silently frozen series.
        self._mirror = get_remote_mirror()
        self._telem_last: Dict[str, float] = {}
        self._obs_telem = reg.counter(
            "r2d2dpg_fleet_telem_frames_total",
            "TELEM registry snapshots received from actors",
            labelnames=("actor",),
        )
        self._obs_telem_staleness = reg.gauge(
            "r2d2dpg_fleet_telem_staleness_seconds",
            "seconds since this actor's last TELEM snapshot (a wedged or "
            "dead actor goes visibly stale)",
            labelnames=("actor",),
        )

    # ------------------------------------------------------------- lifecycle
    def start(self) -> "IngestServer":
        if self._listener is not None:
            raise RuntimeError("ingest server already started")
        family, target = transport.parse_address(self._request_address)
        sock = socket.socket(family, socket.SOCK_STREAM)
        if family == socket.AF_INET:
            sock.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        else:
            # A previous run's STALE socket file would fail the bind — but
            # only unlink if nothing answers: blindly unlinking would let a
            # second run silently steal a live run's ingest address (and
            # its restarting actors).
            import os

            if os.path.exists(target):
                probe = socket.socket(family, socket.SOCK_STREAM)
                probe.settimeout(0.5)
                try:
                    probe.connect(target)
                except OSError:
                    os.unlink(target)  # stale: nothing listening
                else:
                    raise RuntimeError(
                        f"ingest address unix:{target} already has a live "
                        f"server — is another fleet run using it?"
                    )
                finally:
                    probe.close()
        sock.bind(target)
        sock.listen(64)
        if family == socket.AF_INET:
            host, port = sock.getsockname()[:2]
            self.address = f"{host}:{port}"
            # A wildcard bind listens everywhere but is not DIALABLE as
            # written; locally-spawned actors get loopback (remote actors
            # are pointed at a routable interface by the operator).
            dial_host = "127.0.0.1" if host in ("0.0.0.0", "::", "") else host
            self.connect_address = f"{dial_host}:{port}"
        else:
            self.address = f"unix:{target}"
            self.connect_address = self.address
            self._unix_path = target
        self._listener = sock
        self._accept_thread = threading.Thread(
            target=self._accept_loop, name="fleet-ingest-accept", daemon=True
        )
        self._accept_thread.start()
        return self

    def stop(self) -> None:
        self._stop.set()
        if self._listener is not None:
            # A bare close does not wake a thread blocked in accept(2) —
            # the in-flight syscall pins the open file description and the
            # socket stays LISTENING in the kernel (still accepting
            # connects!), so the join below would eat its full timeout.
            # TCP: shutdown() tears the listen state down and wakes the
            # acceptor.  AF_UNIX: shutdown is a no-op on listeners, so
            # poke it awake with a throwaway connect (the accept loop
            # closes post-stop connections immediately).
            try:
                self._listener.shutdown(socket.SHUT_RDWR)
            except OSError:
                pass
            if self._unix_path is not None:
                try:
                    poke = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
                    poke.settimeout(0.5)
                    poke.connect(self._unix_path)
                    poke.close()
                except OSError:
                    pass
            try:
                self._listener.close()
            except OSError:
                pass
            if self._unix_path is not None:
                try:
                    import os

                    os.unlink(self._unix_path)
                except OSError:
                    pass
        with self._lock:
            conns = list(self._conns.values())
        for conn in conns:
            try:
                conn.close()
            except OSError:
                pass
        if self._accept_thread is not None:
            self._accept_thread.join(timeout=5)
        for t in list(self._handlers):
            t.join(timeout=self.stop_join_s)
            if t.is_alive():
                # A handler that outlives its join window is WEDGED (its
                # socket is closed and _stop is set, so every legitimate
                # path exits in a slice) — report it instead of silently
                # leaking the thread, so a post-mortem sees the wedge.
                print(  # obs-lint: allow — teardown diagnostic
                    f"fleet ingest: handler thread {t.name} still alive "
                    f"{self.stop_join_s:.0f}s after stop — leaked (wedged "
                    f"handler; see flight.jsonl)",
                    flush=True,
                )
                flight_event("ingest_handler_leaked", thread=t.name)

    def mark_steady(self) -> None:
        """Startup is over (the drain loop's first compiled drain-learn
        has executed): from here on, queue-full waits shed after
        ``shed_after_s`` instead of the startup grace."""
        self._steady.set()

    @property
    def is_steady(self) -> bool:
        """Whether the warm-up grace has ended (mark_steady ran) — the
        autoscaler's warm-up exemption gate: load-based scale decisions
        are deferred until the loop is past its first compiled phase."""
        return self._steady.is_set()

    def set_expected_actors(self, n: int) -> None:
        """Move the fleet's actor population target (ISSUE 16): a landed
        autoscale resize updates ``r2d2dpg_fleet_actors_expected`` so the
        /health ``actors_down`` rule — and every scrape — judges against
        the CURRENT target, not the spawn-time ``--actors``.  A no-op
        when the server was built without an expected count (embedders
        that never declared a target don't grow one mid-run)."""
        if self._obs_expected is not None:
            self._obs_expected.set(float(n))

    # ---------------------------------------------------------------- params
    def publish_params(self, version: int, params: Any) -> None:
        """Swap in a new versioned param snapshot (numpy trees; callers use
        ``transport.to_host`` — the device fetch MUST happen caller-side,
        before donation invalidates the source buffers).  Handlers push it
        to each actor ahead of that actor's next ack."""
        with self._lock:
            self._param_version = int(version)
            self._params_obj = params
            self._params_frame = None

    def _params_snapshot(self):
        """Lazy pack on the FIRST push (a handler thread), once per
        version, in the negotiated wire encoding (fleet/wire.py — bf16
        params cross at half the bytes); the pack itself runs OUTSIDE the
        server lock so other handlers' acks and the drain thread's
        publishes never stall on it.  The packed payload is one bytes
        object broadcast to every handler thread."""
        with self._lock:
            version = self._param_version
            frame, obj = self._params_frame, self._params_obj
        if frame is None and obj is not None:
            frame = b"".join(
                self._params_packer.pack({"version": version, "params": obj})
            )
            with self._lock:
                if self._param_version == version and self._params_frame is None:
                    self._params_frame = frame
                # else a newer publish raced in: later pushes pack the new
                # version; THIS push still sends the frame it packed.
        return version, frame

    def _fold_telem(self, actor: str, telem: Any) -> None:
        """Fold one actor's TELEM snapshot into the remote mirror under
        ``actor=<id>`` (+ ``host=``) labels.

        Keyed by actor id, so a reconnecting (supervised-restarted) actor
        UPDATES its slot — label re-registration is idempotent and the
        scrape never grows duplicate sources.  The actor id comes from the
        connection's HELLO, never from the TELEM payload: a confused frame
        cannot relabel another actor's series.  Raises on malformed
        payloads (the handler drops them with a flight event)."""
        if not isinstance(telem, dict):
            raise ValueError("TELEM payload is not a dict")
        snapshot = telem.get("snapshot")
        if not isinstance(snapshot, dict):
            raise ValueError("TELEM snapshot is not a dict")
        labels = {"actor": actor}
        host = telem.get("host")
        if host:
            labels["host"] = str(host)
        self._mirror.update(f"actor:{actor}", labels, snapshot)
        with self._lock:
            self._telem_last[actor] = time.monotonic()
        self._arm_telem_staleness(actor)
        self._obs_telem.labels(actor=actor).inc()

    def _arm_telem_staleness(self, actor: str) -> None:
        """Install the actor's live staleness gauge (idempotent).

        Armed at HELLO — counting from connection time — so an actor that
        connects but never delivers a well-formed TELEM still shows a
        GROWING staleness series instead of being silently absent (the
        exact failure the staleness design exists to surface); each fold
        re-arms it, which just overwrites the same closure.  The
        ``.get(a, 0.0)`` default is the sentinel a fold always overwrites,
        so the closure never KeyErrors even if an operator clears state
        mid-scrape."""
        with self._lock:
            self._telem_last.setdefault(actor, time.monotonic())
        self._obs_telem_staleness.labels(actor=actor).set_fn(
            lambda a=actor: time.monotonic() - self._telem_last.get(a, 0.0)
        )

    def pop_shed_stats(self) -> Dict[str, float]:
        """Drain the scalar stats banked off shed messages (learner-side,
        on its log cadence)."""
        with self._lock:
            out = dict(self._shed_stats)
            for k in self._shed_stats:
                self._shed_stats[k] = 0.0
        return out

    def drop_connection(self, actor: Optional[str] = None) -> Optional[str]:
        """Abruptly close one live actor connection — the ``kill_ingest_conn``
        chaos boundary (fleet/chaos.py), equivalent to a mid-run network
        reset.  ``actor`` picks by HELLO'd id; ``None`` (or an id with no
        live connection) drops the oldest live connection instead, so a
        scheduled drill always drills SOMETHING when any peer is up.
        Returns the dropped actor id (or ``None`` when no connection is
        live).  The handler sees its blocking read fail and walks the
        normal torn-stream path; the actor reconnects with backoff."""
        with self._lock:
            ident = None
            if actor is not None:
                for i, a in self._conn_actors.items():
                    if a == str(actor) and i in self._conns:
                        ident = i
                        break
            if ident is None and self._conns:
                ident = next(iter(self._conns))
            if ident is None:
                return None
            conn = self._conns[ident]
            dropped = self._conn_actors.get(ident, "?")
        try:
            # SHUT_RDWR first: close() alone does not wake a handler whose
            # recv holds a reference to the open file description.
            conn.shutdown(socket.SHUT_RDWR)
        except OSError:
            pass
        try:
            conn.close()
        except OSError:
            pass
        return dropped

    # ------------------------------------------------------------ connection
    def _accept_loop(self) -> None:
        while not self._stop.is_set():
            try:
                conn, _ = self._listener.accept()
            except OSError:
                return  # listener closed
            if self._stop.is_set():
                # stop()'s wake-up poke (or a raced late dial): drop it.
                try:
                    conn.close()
                except OSError:
                    pass
                return
            transport.configure_socket(conn)
            # Warmup deadline until the first SEQS frame: a fresh actor's
            # collect compile is legitimate silence; the handler tightens
            # to read_deadline_s once the connection is streaming.
            conn.settimeout(self.warmup_deadline_s)
            with self._lock:
                self._conn_seq += 1
                ident = self._conn_seq
                self._conns[ident] = conn
            # Prune finished handlers (only this thread mutates the list):
            # supervised restarts reconnect indefinitely, and the history
            # of dead Thread objects must not grow with them.
            self._handlers = [t for t in self._handlers if t.is_alive()]
            t = threading.Thread(
                target=self._handle,
                args=(ident, conn),
                name=f"fleet-ingest-conn{ident}",
                daemon=True,
            )
            self._handlers.append(t)
            t.start()

    def _push_params_if_stale(
        self, conn: socket.socket, sent_version: int, bytes_out
    ) -> int:
        version, frame = self._params_snapshot()
        if frame is not None and version > sent_version:
            bytes_out.inc(
                send_frame(
                    conn,
                    K_PARAMS,
                    frame,
                    max_frame_bytes=self.max_frame_bytes,
                )
            )
            return version
        return sent_version

    def _assignment(self, actor: str, wait_s: float = 0.0):
        """The actor's current shard assignment (or None — keep forwarding).

        Guarded: an assignment fn that raises must never cost the control
        connection.  ``wait_s`` bounds a HELLO-time poll for the shard
        tier's address file — a fresh fleet races actor HELLOs against
        the tier's atomic address publish, and waiting ~a second here
        means the actor's FIRST staged batch already rides the data plane
        (the bench leg's shard_forward_bytes == 0 depends on it).
        Steady-state refreshes (SEQS/STATS acks) pass 0: never block the
        experience path on an address lookup."""
        if self.shard_assignment_fn is None:
            return None
        deadline = time.monotonic() + wait_s
        while True:
            try:
                assignment = self.shard_assignment_fn(actor)
            except Exception as e:  # noqa: BLE001 - advisory, never fatal
                flight_event(
                    "assignment_error",
                    actor=actor,
                    error=f"{type(e).__name__}: {e}",
                )
                return None
            if assignment is not None or time.monotonic() >= deadline:
                return assignment
            if self._stop.is_set():
                return None
            time.sleep(0.1)

    def _put_or_shed(self, msg) -> bool:
        """Bounded-wait enqueue: True = queued, False = shed.

        The bound is ``shed_after_s`` once the drain loop marks steady —
        or once the grace window has elapsed since the FIRST hand-off
        (the self-expiry for embedders that never mark) — and the
        startup grace before that (the first drain-learn's compile must
        not cost every actor one shed).  The wait runs in short slices
        so a stopping server (learner aborted mid-compile) reclaims its
        handlers in ~a slice, not after a monolithic 120 s ``queue.put``
        that ignores ``_stop``."""
        now = time.monotonic()
        in_grace = not self._steady.is_set() and (
            self._first_put_at is None
            or now - self._first_put_at < self.startup_shed_grace_s
        )
        if in_grace:
            # Anchor the deadline at the END of the grace window (first
            # hand-off + grace), not now + grace: a wait that begins just
            # inside the window must not stretch the window to ~2x; it
            # gets its shed_after_s past the expiry and no more.
            anchor = now if self._first_put_at is None else self._first_put_at
            deadline = max(
                now + self.shed_after_s,
                anchor + self.startup_shed_grace_s,
            )
        else:
            deadline = now + self.shed_after_s
        while not self._stop.is_set():
            try:
                self.queue.put(
                    msg,
                    timeout=min(0.25, max(deadline - time.monotonic(), 0.0)),
                )
                if self._first_put_at is None:
                    self._first_put_at = time.monotonic()
                return True
            except queue.Full:
                if time.monotonic() >= deadline:
                    return False
        return False  # stopping: drop silently, the run is over

    def _handle(self, ident: int, conn: socket.socket) -> None:
        actor = "?"
        # Per-connection wire state: the peer's packer lives on its side
        # of this socket, so the schema cache must die with it too.
        unpacker = wire.TreeUnpacker(max_frame_bytes=self.max_frame_bytes)
        try:
            kind, payload = recv_frame(
                conn, max_frame_bytes=self.max_frame_bytes
            )
            if kind != K_HELLO:
                raise FrameError(f"expected HELLO, got kind {kind}")
            # JSON, never pickle: this parse runs BEFORE the auth check
            # below (the proof is inside the payload), on bytes from a
            # peer nothing has vouched for — transport.pack_hello.
            hello = transport.unpack_hello(payload)
            actor = str(hello.get("actor_id", "?"))
            if self.auth_token is not None:
                # Constant-time comparison of the HELLO proof against the
                # shared secret's (ROADMAP cross-host prerequisite): a
                # mismatch — or a missing proof — is refused at the door,
                # before wire negotiation or any tensor decode.  Also
                # before ANY per-actor state: the claimed actor_id is
                # attacker-controlled on exactly the routable binds auth
                # exists for, and registering labeled metric series or a
                # _conn_actors entry per unauthenticated HELLO would let a
                # port scanner grow the registry (and the /metrics page)
                # without bound.  The bounded flight ring may name it.
                want = transport.hello_auth_proof(self.auth_token)
                got = str(hello.get("auth", ""))
                if not hmac.compare_digest(want, got):
                    flight_event("auth_refused", actor=actor)
                    send_frame(
                        conn,
                        K_ACK,
                        pack_obj(  # wire-lint: control
                            {"code": REFUSED_AUTH, "param_version": 0}
                        ),
                    )
                    return
            with self._lock:
                self._conn_actors[ident] = actor
            bytes_in = self._obs_bytes_in.labels(actor=actor)
            bytes_out = self._obs_bytes_out.labels(actor=actor)
            bytes_in.inc(HEADER_BYTES + len(payload))
            mismatch = wire.check_negotiation(hello, self.wire_config)
            if mismatch is not None:
                # One fleet, one wire format: a mismatched actor would
                # poison every SEQS decode — refuse at the door, loudly.
                flight_event("wire_refused", actor=actor, reason=mismatch)
                bytes_out.inc(
                    send_frame(
                        conn,
                        K_ACK,
                        pack_obj(  # wire-lint: control
                            {
                                "code": REFUSED_WIRE,
                                "param_version": 0,
                                "reason": mismatch,
                                "expect": wire.negotiation_fields(
                                    self.wire_config
                                ),
                            }
                        ),
                    )
                )
                return
            # Accepted actor: staleness is visible from THIS moment, not
            # from its first well-formed TELEM (which may never come).
            self._arm_telem_staleness(actor)
            sent_version = self._push_params_if_stale(conn, 0, bytes_out)
            # Direct data plane (ISSUE 17): the HELLO ack advertises the
            # actor's shard assignment + dialable address.  Bounded poll:
            # a fresh tier publishes its address file a beat after the
            # first HELLOs land, and shipping the assignment NOW means no
            # forwarded warmup batches.
            hello_assignment = self._assignment(actor, wait_s=10.0)
            ack = {"code": OK, "param_version": sent_version}
            if hello_assignment is not None:
                ack["shard_assignment"] = hello_assignment
            bytes_out.inc(
                send_frame(
                    conn,
                    K_ACK,
                    pack_obj(ack),  # wire-lint: control
                )
            )
            streaming = False  # first SEQS tightens the read deadline
            while not self._stop.is_set():
                kind, payload = recv_frame_heartbeat(
                    conn,
                    max_frame_bytes=self.max_frame_bytes,
                    bytes_in=bytes_in.inc,
                    bytes_out=bytes_out.inc,
                )
                t_recv = time.time()
                bytes_in.inc(HEADER_BYTES + len(payload))
                if kind == K_BYE:
                    return
                if kind == K_TELEM:
                    # Fire-and-forget metric aggregation: fold or drop —
                    # a malformed snapshot must cost ONE flight event, not
                    # the connection (the experience path is unaffected).
                    try:
                        self._fold_telem(
                            actor, unpack_obj(payload)  # wire-lint: control
                        )
                    except Exception as e:  # noqa: BLE001 - quarantine
                        flight_event(
                            "telem_malformed",
                            actor=actor,
                            error=f"{type(e).__name__}: {e}",
                        )
                    continue
                if kind == K_STATS:
                    # Split-plane accounting (ISSUE 17): the staged batch
                    # went straight to the actor's shard on the data
                    # plane; this tiny control frame carries ONLY the
                    # accounting deltas, banked into the same sums the
                    # forwarded path's ``add`` banks — the actor clears
                    # its accumulators on THIS ack, so at-least-once
                    # accounting is plane-independent.
                    if not streaming:
                        conn.settimeout(self.read_deadline_s)
                        streaming = True
                    stats_msg = unpack_obj(payload)  # wire-lint: control
                    if self.shards is not None:
                        self.shards.bank_stats(stats_msg)
                    self._obs_staleness.labels(actor=actor).set(
                        self._param_version
                        - int(stats_msg.get("param_version", 0))
                    )
                    sent_version = self._push_params_if_stale(
                        conn, sent_version, bytes_out
                    )
                    ack = {"code": OK, "param_version": sent_version}
                    assignment = self._assignment(actor)
                    if assignment is not None:
                        ack["shard_assignment"] = assignment
                    bytes_out.inc(
                        send_frame(
                            conn,
                            K_ACK,
                            pack_obj(ack),  # wire-lint: control
                        )
                    )
                    continue
                if kind != K_SEQS:
                    raise FrameError(f"expected SEQS/BYE, got kind {kind}")
                if not streaming:
                    # The connection is streaming: from here on the peer's
                    # longest legitimate silence is one collect phase, and
                    # the heartbeat deadline bounds it.
                    conn.settimeout(self.read_deadline_s)
                    streaming = True
                msg = unpacker.unpack(payload)
                t_decode_end = time.time()
                tr = unpacker.last_trace
                if tr is not None and self.shards is not None:
                    # Sharded mode: the SEQS sidecar's hop chain has no
                    # completing drain to record it (the sampler path
                    # traces sample_req -> batch_return -> learn
                    # instead) — drop it rather than leave a partial
                    # chain (the all-or-nothing contract, obs/trace.py).
                    tr = None
                if tr is not None:
                    # The sampled batch's actor-side hops (off the wire
                    # sidecar) + this handler's transit/decode timestamps
                    # ride the queue message; NOTHING is recorded here.
                    # The drain loop records all 8 hops together for the
                    # batches it actually traces through learn, so every
                    # hop histogram shares ONE sample population — an
                    # absorb-phase or shed batch contributes no partial
                    # 4-hop chain ("absorb batches are untraced").
                    msg["trace"] = {
                        "id": tr.trace_id,
                        "actor": actor,
                        "t_collect_start": tr.t_collect_start,
                        "t_collect_end": tr.t_collect_end,
                        "t_encode_end": tr.t_encode_end,
                        "t_recv": t_recv,
                        "t_enqueue_start": t_decode_end,
                    }
                msg["actor_id"] = actor
                n_seqs = int(
                    np.shape(msg["staged"].seq.reward)[0]
                )
                self._obs_frames.labels(actor=actor).inc()
                self._obs_seqs.labels(actor=actor).inc(n_seqs)
                self._obs_staleness.labels(actor=actor).set(
                    self._param_version - int(msg.get("param_version", 0))
                )
                self._obs_ratio.set(
                    unpacker.last_raw_len
                    / max(unpacker.last_payload_len, 1)
                )
                with self._lock:
                    self.seqs_received_total += n_seqs
                    self.seqs_bytes_total += HEADER_BYTES + len(payload)
                    self.seqs_raw_bytes_total += unpacker.last_raw_len
                if self.shards is not None:
                    # In-network sampling: straight into this actor's
                    # shard — concurrent across handlers, never sheds
                    # (ring eviction is the backpressure), accounting
                    # deltas banked for the sampler learner's sums.
                    # Routed per FRAME, not per connection: the route is
                    # a pure actor-id hash on the loopback (identical
                    # every call), and liveness-aware on the standalone
                    # tier — an actor whose home shard was down at HELLO
                    # lands back home the moment it rejoins, instead of
                    # feeding a neighbor for the connection's lifetime.
                    self.shards.add(self.shards.route(actor), msg)
                    code = OK
                    with self._lock:
                        self.seqs_total += n_seqs
                elif self._put_or_shed(msg):
                    code = OK
                    with self._lock:  # N handler threads share these sums
                        self.seqs_total += n_seqs
                else:
                    if self._stop.is_set():
                        return
                    code = SHED_INGEST
                    with self._lock:
                        self.shed_total += 1
                        for k in self._shed_stats:
                            self._shed_stats[k] += float(msg.get(k, 0.0))
                    self._obs_shed.labels(actor=actor).inc()
                    flight_event(
                        "shed", code=code, actor=actor,
                        phase=int(msg.get("phase", -1)),
                    )
                sent_version = self._push_params_if_stale(
                    conn, sent_version, bytes_out
                )
                ack = {"code": code, "param_version": sent_version}
                # Assignment refresh on every ack (non-blocking): a
                # fallen-back actor re-learns its shard's address the
                # moment an epoch-bumped rejoin re-publishes it.
                assignment = self._assignment(actor)
                if assignment is not None:
                    ack["shard_assignment"] = assignment
                bytes_out.inc(
                    send_frame(
                        conn,
                        K_ACK,
                        pack_obj(ack),  # wire-lint: control
                    )
                )
        except PeerDeadError as e:
            if not self._stop.is_set():
                # The liveness verdict (docs/FLEET.md "Failure modes"): a
                # peer that answered neither frames nor the PING probe is
                # REAPED — connection closed, loudly attributed.  The
                # supervisor restarts a wedged actor when its stall
                # eventually crashes or exits it; a merely-slow actor
                # reconnects by itself.
                flight_event(
                    "peer_dead",
                    actor=actor,
                    deadline_s=self.read_deadline_s,
                    error=str(e),
                )
                self._obs_peer_dead.labels(actor=actor).inc()
        except (FrameError, OSError) as e:
            if not self._stop.is_set():
                # A crashed actor's torn stream: note it and drop the
                # connection — the supervisor owns the restart.
                flight_event(
                    "ingest_conn_error",
                    actor=actor,
                    error=f"{type(e).__name__}: {e}",
                )
        finally:
            with self._lock:
                self._conns.pop(ident, None)
                self._conn_actors.pop(ident, None)
            try:
                conn.close()
            except OSError:
                pass


def aval_tree(tree):
    """ShapeDtypeStruct tree of ``tree``'s leaves, shardings preserved —
    the aval capture shared by the drain loop and the coalesce-width
    precompile (one definition, so the warm-compiled avals can never
    silently diverge from what the drain loop passes)."""
    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(
            np.shape(x), x.dtype, sharding=getattr(x, "sharding", None)
        ),
        tree,
    )


# -------------------------------------------------------- fleet checkpoints
# The learner-recovery contract (docs/FLEET.md "Failure modes & recovery"):
# a fleet checkpoint is the LEARNER subtree (params + targets + optimizer
# states + step; utils/checkpoint.py light layout) plus this sidecar of
# host-side monotone counters (env steps, episode sums, drained-phase
# count, param version).  The replay arena is deliberately NOT
# checkpointed — it is GBs of re-collectable experience — so a resumed run
# re-enters the absorb-to-min_replay phase with fresh actor experience
# before drain-learn phases continue, and every counter continues monotone
# from where the checkpoint left it.
def fleet_counters_path(directory: str, step: int) -> str:
    return os.path.join(
        os.path.abspath(directory), f"fleet_counters_{int(step)}.json"
    )


def save_fleet_counters(directory: str, step: int, counters: Dict) -> str:
    """Atomically write the monotone-counter sidecar next to the orbax
    step (tmp + rename: a torn write never masquerades as a counter
    state).  Returns the path."""
    path = fleet_counters_path(directory, step)
    tmp = f"{path}.tmp.{os.getpid()}"
    with open(tmp, "w") as f:
        json.dump({k: float(v) for k, v in counters.items()}, f)
    os.replace(tmp, path)
    return path


def load_fleet_counters(directory: str, step: int) -> Dict[str, float]:
    """Read the sidecar for ``step``; missing file -> empty dict (callers
    warn loudly — counters would restart at zero, losing monotonicity
    against the previous incarnation's logs)."""
    path = fleet_counters_path(directory, step)
    if not os.path.exists(path):
        return {}
    with open(path) as f:
        return {k: float(v) for k, v in json.load(f).items()}


def prune_fleet_counters(directory: str, keep_steps) -> None:
    """Drop sidecars whose orbax step was garbage-collected (max_to_keep),
    so the two never drift apart on disk."""
    keep = {int(s) for s in keep_steps}
    directory = os.path.abspath(directory)
    try:
        names = os.listdir(directory)
    except OSError:
        return
    for name in names:
        if not (name.startswith("fleet_counters_") and name.endswith(".json")):
            continue
        try:
            step = int(name[len("fleet_counters_"):-len(".json")])
        except ValueError:
            continue
        if step not in keep:
            try:
                os.unlink(os.path.join(directory, name))
            except OSError:
                pass


class FleetLearner:
    """The staging queue's single consumer: drain -> arena add -> K updates.

    Owns the ingest server and the drain/absorb device programs; runs on
    the calling thread.  ``fleet=off`` (``--actors 0``) never constructs
    this class — the phase-locked ``Trainer.run`` path is untouched, and
    tests/test_fleet.py pins that bit-identically.
    """

    def __init__(self, trainer: Trainer, config: FleetConfig):
        if trainer.axis is not None:
            raise ValueError(
                "FleetLearner needs a host-visible drain boundary; "
                "shard_map trainers (SPMDTrainer) fuse whole phases — use "
                "the base Trainer or HostSPMDTrainer"
            )
        if config.num_actors < 1:
            raise ValueError(
                "FleetLearner requires num_actors >= 1 (fleet=off runs "
                "Trainer.run directly; there is nothing to ingest)"
            )
        if config.queue_depth < 1:
            raise ValueError("queue_depth must be >= 1")
        if config.drain_coalesce < 1:
            raise ValueError("drain_coalesce must be >= 1")
        config.wire.validate()
        self.trainer = trainer
        self.config = config
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
            expected_actors=config.num_actors,
        )
        drain_kwargs: Dict[str, Any] = {"donate_argnums": (0,)}
        ls_sh = getattr(trainer, "lstate_shardings", None)
        if ls_sh is not None:
            # dp learner (parallel/dp_learner.py): pin the drain outputs
            # to the init layout so the donated chain's avals stay stable
            # — neither the jit cache nor the AOT-precompiled coalesce
            # widths below may re-key mid-run on a GSPMD layout drift.
            drain_kwargs["out_shardings"] = (ls_sh(), trainer._replicated)
        self._drain_prog = jax.jit(
            lambda ls, st: drain_staged(
                trainer, ls, st, learn=True, prefetch=config.prefetch
            ),
            **drain_kwargs,
        )
        self._absorb_prog = jax.jit(
            lambda ls, st: drain_staged(trainer, ls, st, learn=False),
            **drain_kwargs,
        )
        # Coalesce-width precompile (ISSUE 9 satellite — the coalesce
        # regression): every power-of-two bucket width is a
        # distinct drain program, and compiling one MID-RUN stalls the
        # drain for tens of seconds — long enough to fill the queue and
        # shed.  A background thread AOT-compiles the widths during the
        # absorb phase (_warm_drain_widths); until a width's program is
        # READY the pull limit is clamped to the widths that are
        # (_coalesce_ready), so the drain never blocks on a width compile.
        self._drain_exec: Dict[int, Any] = {}  # total staged B -> compiled
        self._coalesce_ready = 1
        self._warm_thread: Optional[threading.Thread] = None
        # Set when the run is over: the warm thread checks it between
        # width compiles, and run()'s finally JOINS the thread — a
        # daemon mid-XLA-compile at interpreter teardown aborts the
        # whole process (std::terminate), turning a finished short run
        # into rc=134.
        self._warm_stop = threading.Event()
        reg = get_registry()
        self._obs_queue_depth = reg.gauge(
            "r2d2dpg_fleet_staging_queue_depth",
            "staged batches awaiting drain",
        )
        self._obs_queue_depth.set_fn(self.queue.qsize)
        # Same split as the sampler's wait/absorb pair: absorb-phase
        # queue waits are EXPECTED (actor spawn + jax import + collect
        # compile — each Empty timeout lands a ~0.5s sample, right at the
        # /health learner_starving threshold) and would read a clean
        # warm-up as starving until ~window-size later waits flush them.
        self.learner_wait = reg.histogram(
            "r2d2dpg_fleet_learner_wait_seconds",
            "learner thread blocked on the fleet staging queue AFTER "
            "absorb (starvation — the /health learner_starving input)",
        )
        self.absorb_wait = reg.histogram(
            "r2d2dpg_fleet_absorb_wait_seconds",
            "learner thread blocked on the staging queue during the "
            "absorb-to-min_replay phase (cold start and --resume re-entry)",
        )
        self._obs_coalesce = reg.gauge(
            "r2d2dpg_fleet_drain_coalesce_width",
            "staged batches stacked into the most recent compiled drain",
        )
        self._stats: Dict[str, float] = {}
        self._counters: Dict[str, float] = {}

    # ------------------------------------------------------------- lifecycle
    def start(self) -> str:
        """Bind + start the ingest server; returns the resolved DIALABLE
        address the supervisor hands to actor subprocesses (loopback for a
        wildcard bind — ``IngestServer.connect_address``)."""
        self.server.start()
        return self.server.connect_address

    def close(self) -> None:
        """Stop the ingest server.  Callers stop the SUPERVISOR first: an
        actor that loses its connection while unsupervised exits cleanly,
        but one mid-send sees a reset — the supervisor must already be in
        its stopping state so that exit is not treated as a crash."""
        self.server.stop()
        self._obs_queue_depth.set(0.0)

    def stats(self) -> Dict[str, float]:
        """Instrumentation from the most recent ``run`` (throughput +
        shed/starvation accounting; ``arena_add_seqs_per_sec`` is the
        bench probe's headline)."""
        return dict(self._stats)

    def counters(self) -> Dict[str, float]:
        """The monotone counters as of the most recent ``run``'s end — the
        values the FINAL checkpoint's sidecar must record so a later
        ``--resume`` continues them (train.py writes it next to
        ``save_final``)."""
        return dict(self._counters)

    def _save_checkpoint(
        self, ckpt, step: int, state, cstate, lstate, counters: Dict
    ) -> None:
        """One periodic learner checkpoint: the merged state (a LIGHT
        manager persists only the ``train`` subtree — params, targets,
        optimizer, step) plus the monotone-counter sidecar, pruned in
        lockstep with orbax's ``max_to_keep``.  Runs on the drain thread
        between phases; the synchronous save completes before the next
        drain call donates ``lstate``'s buffers."""
        ckpt.save(step, merge_state(state, cstate, lstate))
        save_fleet_counters(ckpt.directory, step, counters)
        prune_fleet_counters(ckpt.directory, ckpt.all_steps())

    def _warm_drain_widths(self, ls_avals, staged_example) -> None:
        """Background AOT precompile of the power-of-two coalesce widths.

        Runs on a daemon thread started when the FIRST staged batch
        arrives (its shapes parameterize every width): for each width
        ``2^k <= drain_coalesce`` the drain-learn program is lowered and
        compiled against the width's avals — leading-dim-scaled from ONE
        ``trainer._put_staged`` placement of the example, so the
        compiled input layout matches what the drain loop will actually
        pass — and published to ``_drain_exec`` keyed by TOTAL staged B.
        ``_coalesce_ready`` rises as widths land, in order, so the pull
        clamp only ever admits a backlog width whose program exists; the
        drain thread keeps absorbing (tracing is thread-safe; the arena's
        staged-writer claim is skipped under trace — replay/arena.py).
        Any failure leaves the clamp at the widths already published
        (a ``drain_warm_failed`` flight event names it): narrower drains,
        never a wrong or stalling one.

        Device-plane attribution (ISSUE 14 satellite): this thread's
        compiles are DECLARED (an ``expected`` window — warm-window
        compiles may legitimately land after the first drain-learn
        marked steady in a future ordering) and labelled
        ``fleet_drain_warm``, so the compile histograms attribute them
        instead of leaving them invisible; each ``drain_width_ready``
        event carries the measured wall seconds of its width's
        lower+compile."""
        t = self.trainer
        mon = get_device_monitor()
        try:
            b0 = int(np.shape(staged_example.seq.reward)[0])
            # ONE width-1 placement yields the layout (dtype + sharding
            # per leaf — NamedShardings are shape-agnostic); each width's
            # avals just scale the leading dim.  No per-width dummy
            # stacks or device transfers competing with the absorb
            # phase's real traffic.  A width whose divisibility would
            # flip the placement decision (b0 not mesh-divisible but
            # w*b0 is) compiles against the width-1 layout and falls
            # back through the drain loop's exec_ guard — structural
            # argv pins b0 divisible fleet-wide, so that is theoretical.
            base_avals = aval_tree(t._put_staged(staged_example))
            # w starts at 1: when the FIRST learn pull is coalesced (a
            # backlog at the absorb->learn crossing dispatches through
            # the AOT object), the jit wrapper's width-1 cache entry is
            # never populated — a later width-1 pull would then compile
            # inline POST-steady, the exact stall this thread removes.
            w = 1
            while w <= self.config.drain_coalesce:
                if self._warm_stop.is_set():
                    return  # run over: don't start another width compile
                staged_avals = jax.tree_util.tree_map(
                    lambda a, _w=w: jax.ShapeDtypeStruct(
                        (_w * a.shape[0],) + tuple(a.shape[1:]),
                        a.dtype,
                        sharding=a.sharding,
                    ),
                    base_avals,
                )
                t_compile = time.monotonic()
                with mon.expected("drain_warm"), mon.program(
                    "fleet_drain_warm"
                ):
                    compiled = self._drain_prog.lower(
                        ls_avals, staged_avals
                    ).compile()
                compile_s = time.monotonic() - t_compile
                self._drain_exec[w * b0] = compiled
                self._coalesce_ready = w
                flight_event(
                    "drain_width_ready",
                    width=w,
                    seqs=w * b0,
                    seconds=round(compile_s, 3),
                )
                w *= 2
        except Exception as e:  # noqa: BLE001 — degrade, never crash the run
            flight_event(
                "drain_warm_failed", error=f"{type(e).__name__}: {e}"
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
    ) -> TrainerState:
        """Absorb staged batches until ``min_replay`` sequences are
        resident, then run ``num_train_phases`` drain-learn phases (one
        staged batch + K updates each — the phase-locked data-to-update
        ratio, fed from the fleet).  The server must already be started;
        the caller owns actor lifecycle (supervisor).

        ``ckpt`` (a LIGHT ``utils.CheckpointManager``) + ``checkpoint_every``
        arm periodic learner checkpoints: the learner subtree is saved
        every N drain phases with the monotone-counter sidecar
        (``save_fleet_counters``) — the recovery contract's durable half.
        ``resume_from`` (``load_fleet_counters`` of the restored step)
        continues counters, phase numbering and param versions where the
        previous incarnation left them; ``num_train_phases`` stays the
        TOTAL target across incarnations.  ``phase_fn(drained)`` runs
        after every drain-learn phase — the chaos engine's injection hook
        (fleet/chaos.py)."""
        if self.server.address is None:
            raise RuntimeError("call start() before run()")
        t = self.trainer
        # Device plane (ISSUE 14): the drain loop owns the run window —
        # steady arms at the existing mark_steady boundary (first
        # drain-learn executed AND warm-width compiles done).
        mon = get_device_monitor().install()
        mon.begin_run()
        state = t.init() if state is None else state
        cstate, lstate = split_state(state)
        deadline = (
            time.monotonic() + minutes * 60 if minutes is not None else None
        )
        self.learner_wait.reset()
        self.absorb_wait.reset()
        resume_from = resume_from or {}
        version = int(resume_from.get("param_version", 0)) + 1
        self.server.publish_params(version, self._snapshot_params(lstate))

        min_seqs = t.config.min_replay
        absorbed = 0
        # Monotone across learner incarnations: a resumed run continues
        # the drained-phase count and the host-side sums exactly where the
        # checkpoint's sidecar left them (the recovery contract).
        drained = int(resume_from.get("drained", 0))
        drained_at_start = drained
        last_metrics: Dict[str, Any] = {}
        # Host-side episode accounting: actors drain their device
        # accumulators each phase and ship DELTAS as plain floats, so the
        # sums here stay monotone across supervised actor restarts.
        ep_ret_sum = float(resume_from.get("ep_return_sum", 0.0))
        ep_count = float(resume_from.get("ep_count", 0.0))
        env_steps_total = float(resume_from.get("env_steps_total", 0.0))
        episodes_total = float(resume_from.get("episodes_total", 0.0))
        last_batch_t = time.monotonic()
        t0 = time.monotonic()
        # Steady-state window for throughput claims: everything before the
        # first drain-learn completes (actor subprocess spawn, jax imports,
        # program compiles, replay fill) is startup, not sustained rate.
        train_t0: Optional[float] = None
        seqs_at_train_t0 = 0
        marked_steady = False

        def emit_log(phase: int, scalars: Dict[str, float]) -> None:
            if metrics_fn is not None:
                metrics_fn(phase, scalars)
                return
            log_fn(
                f"fleet phase {phase}/{num_train_phases} "
                + " ".join(f"{k} {v:.3g}" for k, v in scalars.items())
            )

        coalesce_sum = 0
        coalesce_n = 0
        try:
            while drained < num_train_phases:
                if deadline is not None and time.monotonic() >= deadline:
                    break
                t_wait = time.monotonic()
                # Absorb-phase waits go to their own histogram (see the
                # registration comment): the learn-phase boundary is the
                # same absorbed>min_seqs crossing the drain programs use.
                wait_hist = (
                    self.learner_wait
                    if absorbed > min_seqs
                    else self.absorb_wait
                )
                try:
                    first = self.queue.get(timeout=0.5)
                except queue.Empty:
                    wait_hist.add(time.monotonic() - t_wait)
                    # Cold-start grace: the FIRST batch pays actor
                    # subprocess spawn + jax import + collect compile +
                    # window fill — give it double the steady-state bound.
                    bound = self.config.idle_timeout_s * (
                        2.0 if absorbed == 0 else 1.0
                    )
                    if time.monotonic() - last_batch_t > bound:
                        raise RuntimeError(
                            f"fleet starved: no staged batch in "
                            f"{self.config.idle_timeout_s:.0f}s — are the "
                            f"actors alive? (supervisor restarts crashed "
                            f"ones; check flight.jsonl)"
                        )
                    continue
                wait_hist.add(time.monotonic() - t_wait)
                last_batch_t = time.monotonic()
                t_dequeue = time.time()
                # Coalesced drain (drain_coalesce): the blocking-got batch
                # plus whatever backlog the queue ALREADY holds, stacked
                # into ONE compiled call — the arena-add dispatch is paid
                # once per backlog instead of once per actor batch.  A
                # keeping-up learner sees width 1 and the uncoalesced
                # schedule exactly.  The pull is clamped to the widths
                # whose drain program is READY (precompiled by the warm
                # thread below): a mid-run width compile stalls the drain
                # long enough to fill the queue and shed — the
                # coalesce regression this clamp removes.
                # Absorb-phase pulls clamp to 1 outright: only the
                # drain-LEARN widths are warmed, and a wide pull there
                # would compile an absorb program used for seconds and
                # never again — the same inline stall in another coat.
                limit = (
                    1
                    if absorbed < min_seqs
                    else min(self.config.drain_coalesce, self._coalesce_ready)
                )
                msgs = coalesce_from_queue(self.queue, first, limit)
                if self.config.drain_coalesce > 1 and self._warm_thread is None:
                    # First batch ever: its shapes parameterize every
                    # coalesce width.  Capture the lstate avals NOW (the
                    # next drain call donates these buffers) and compile
                    # the widths in the background while absorb proceeds.
                    ls_avals = aval_tree(lstate)
                    self._warm_thread = threading.Thread(
                        target=self._warm_drain_widths,
                        args=(ls_avals, msgs[0]["staged"]),
                        name="fleet-drain-warm",
                        daemon=True,
                    )
                    self._warm_thread.start()
                coalesce_sum += len(msgs)
                coalesce_n += 1
                self._obs_coalesce.set(float(len(msgs)))
                # Fold shed-banked accounting EVERY iteration (a cheap
                # locked dict swap): only the experience of a shed message
                # was droppable, and the sums must be right whenever read
                # (log cadence, log_every=0 probes, end-of-run stats).
                shed_stats = self.server.pop_shed_stats()
                env_steps_total += shed_stats["env_steps_delta"]
                ep_ret_sum += shed_stats["ep_return_sum"]
                ep_count += shed_stats["ep_count"]
                episodes_total += shed_stats["ep_count"]
                staged = stack_staged([m["staged"] for m in msgs])
                t_stack_end = time.time()
                # Sampled batches' hops (obs/trace.py): absorb phases are
                # untraced (their "learn" would be a lie), so ALL 8 hops —
                # including the actor-side stamps riding the message — are
                # recorded only once the run is draining for real.
                traces = [m["trace"] for m in msgs if m.get("trace")]
                n_seqs = int(np.shape(staged.seq.reward)[0])
                for msg in msgs:
                    ep_ret_sum += float(msg.get("ep_return_sum", 0.0))
                    ep_count += float(msg.get("ep_count", 0.0))
                    episodes_total += float(msg.get("ep_count", 0.0))
                    env_steps_total += float(msg.get("env_steps_delta", 0.0))
                absorbed += n_seqs
                # Mesh placement BEFORE the compiled call (the dp
                # learner's _put_staged lays the batch over its dp axis —
                # jax.make_array_from_process_local_data when
                # multi-process; identity for single-chip trainers).
                placed = t._put_staged(staged)
                # staged_writer around the COMPILED call: inside the jit
                # the arena's own guard only runs at trace time, so the
                # single-writer claim must wrap the execution (replay/
                # arena.py "SINGLE-WRITER contract").
                if absorbed <= min_seqs:
                    with t.arena.staged_writer():
                        lstate, _ = self._absorb_prog(lstate, placed)
                    continue
                # Experience-quality fold (obs/quality.py), host numpy on
                # the already-decoded batch — zero device traffic.  Under
                # the central drain EVERY absorbed sequence crosses into
                # the training arena, so the per-actor counters attribute
                # train-visible experience by the HELLO-authenticated id
                # the handler stamped (never the payload's claim), and
                # the lag distribution is the published-version distance
                # at the moment the batch enters training.
                qplane = get_quality_plane()
                if staged.behavior_version is not None:
                    qplane.observe_lags(
                        policy_lags(version, staged.behavior_version)
                    )
                for m_q in msgs:
                    qplane.note_trained(
                        m_q["actor_id"],
                        int(np.shape(m_q["staged"].seq.reward)[0]),
                    )
                exec_ = self._drain_exec.get(n_seqs)
                note_width = getattr(t, "dp_set_learn_width", None)
                if note_width is not None:
                    # The dp learner's dispatch-width gauge, set at the
                    # REAL drain site (host-known B — no fetch).
                    note_width(n_seqs)
                mon.on_phase(drained + 1)
                with t.arena.staged_writer(), mon.program("fleet_drain"):
                    if exec_ is not None:
                        # AOT-precompiled width (the warm thread's
                        # contract): dispatch through the compiled object
                        # — the jit wrapper's cache never saw this width
                        # and would recompile on it.  An aval mismatch
                        # (foreign batch structure) raises BEFORE any
                        # donation, so falling back to the jit path is
                        # safe — it pays the compile this width's AOT
                        # object existed to avoid, once, loudly.
                        try:
                            lstate, last_metrics = exec_(lstate, placed)
                        except (TypeError, ValueError) as e:
                            flight_event(
                                "drain_exec_fallback",
                                seqs=n_seqs,
                                error=f"{type(e).__name__}: {e}",
                            )
                            self._drain_exec.pop(n_seqs, None)
                            lstate, last_metrics = self._drain_prog(
                                lstate, placed
                            )
                    else:
                        lstate, last_metrics = self._drain_prog(
                            lstate, placed
                        )
                t_dispatch_end = time.time()
                if traces:
                    # One block_until_ready per SAMPLED drain is what makes
                    # the learn hop honest (async dispatch otherwise
                    # returns immediately); unsampled drains pay nothing.
                    jax.block_until_ready(lstate.train.step)
                    t_done = time.time()
                    nbytes = staged_nbytes(staged)
                    for tr in traces:
                        tid, act = tr["id"], tr.get("actor")
                        obs_trace.record_hop(
                            "collect", tr["t_collect_start"],
                            tr["t_collect_end"], tid, actor=act,
                        )
                        obs_trace.record_hop(
                            "encode", tr["t_collect_end"],
                            tr["t_encode_end"], tid, actor=act,
                        )
                        obs_trace.record_hop(
                            "transit", tr["t_encode_end"], tr["t_recv"],
                            tid, actor=act,
                        )
                        obs_trace.record_hop(
                            "decode", tr["t_recv"], tr["t_enqueue_start"],
                            tid, actor=act,
                        )
                        obs_trace.record_hop(
                            "enqueue", tr["t_enqueue_start"], t_dequeue,
                            tid, actor=act,
                        )
                        obs_trace.record_hop(
                            "coalesce", t_dequeue, t_stack_end,
                            tid, actor=act, width=len(msgs),
                        )
                        obs_trace.record_hop(
                            "arena_add", t_stack_end, t_dispatch_end,
                            tid, actor=act, bytes=nbytes, seqs=n_seqs,
                        )
                        obs_trace.record_hop(
                            "learn", t_dispatch_end, t_done,
                            tid, actor=act,
                        )
                drained += 1
                if train_t0 is None:
                    # The first drain carries the compile; the sustained
                    # window starts once it has actually executed.
                    jax.block_until_ready(lstate.train.step)
                    train_t0 = time.monotonic()
                    seqs_at_train_t0 = absorbed
                if not marked_steady and (
                    self._warm_thread is None
                    or not self._warm_thread.is_alive()
                ):
                    # Startup is over: the first drain-learn has executed
                    # AND the background coalesce-width compiles (which
                    # contend for the same cores and would slow the drain
                    # into queue-full sheds) are done — handlers now shed
                    # on the real shed_after_s bound instead of the
                    # compile grace.
                    self.server.mark_steady()
                    # The compile sentinel arms at the SAME boundary: the
                    # drain programs (jit width-1 + every warm width) are
                    # materialized — any later compile outside a declared
                    # window is an aval-re-key alarm.
                    mon.mark_steady()
                    marked_steady = True
                if phase_fn is not None:
                    # The chaos engine's drain-clock hook (fleet/chaos.py):
                    # learner-boundary faults fire here, between phases.
                    phase_fn(drained)
                if (
                    ckpt is not None
                    and checkpoint_every > 0
                    and drained % checkpoint_every == 0
                ):
                    self._save_checkpoint(
                        ckpt, drained, state, cstate, lstate,
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
                        version, self._snapshot_params(lstate)
                    )
                    # Flight-ring discipline (training/pipeline.py
                    # _publish): record on the log cadence only, so
                    # publishes don't evict the rare events.
                    if log_every and drained % log_every == 0:
                        flight_event("param_publish", version=version)
                if log_every and drained % log_every == 0:
                    # The dp learner's per-shard gauges ride THIS batched
                    # fetch (Trainer._log_extra_refs — no fetches of
                    # their own on the hot path; ISSUE 9 obs satellite).
                    # expected(): the extra refs build small eager
                    # reductions on first use — declared, not an alarm.
                    with mon.expected("log_fetch"):
                        extra = t._log_extra_refs(lstate.arena)
                        lstep, m, *extra_vals = jax.device_get(
                            (lstate.train.step, last_metrics, *extra)
                        )
                    if extra:
                        t._log_extra_publish(extra_vals)
                    scalars = {
                        "episode_return_mean": ep_ret_sum / max(ep_count, 1.0),
                        "episodes": ep_count,
                        "env_steps": env_steps_total,
                        "learner_steps": float(lstep),
                        **{k: float(v) for k, v in m.items()},
                    }
                    ep_ret_sum = 0.0
                    ep_count = 0.0
                    t._obs_publish(scalars)
                    emit_log(drained, scalars)
        finally:
            jax.block_until_ready(lstate.train.step)
            # Disarm the sentinel + close any open profiler capture:
            # teardown/checkpoint compiles are a new window's business.
            mon.end_run()
            # The run's honest end — BEFORE reaping the warm thread, so
            # a pending width compile can't inflate the measured walls.
            t_end = time.monotonic()
            # Reap the width-precompile thread BEFORE teardown: a daemon
            # still inside an XLA compile when the interpreter exits
            # std::terminates the process (observed rc=134 on short
            # runs).  The stop flag caps the wait at the in-flight
            # compile; the join itself is unbounded because the thread
            # always terminates (compile returns or raises).
            self._warm_stop.set()
            if self._warm_thread is not None:
                self._warm_thread.join()
            wall = max(t_end - t0, 1e-9)
            _, lw_total, lw_p50, lw_p99 = self.learner_wait.snapshot()
            _, aw_total, _, _ = self.absorb_wait.snapshot()
            srv = self.server
            # Rates are per-INCARNATION (phases this process ran over this
            # process's wall clock); the monotone totals live in counters().
            drained_here = drained - drained_at_start
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
                "absorbed_seqs": float(absorbed),
                "wall_s": wall,
                "learner_steps_per_sec": (
                    drained_here * t.config.learner_steps / wall
                ),
                "arena_add_seqs_per_sec": absorbed / wall,
                "sheds": float(self.server.shed_total),
                "learner_wait_p50_ms": lw_p50 * 1e3,
                "learner_wait_p99_ms": lw_p99 * 1e3,
                "learner_wait_total_s": lw_total,
                "absorb_wait_s": aw_total,
                # The pipelined executor's overlap instrumentation on the
                # fleet schedule (ISSUE 11): fraction of the wall during
                # which the learner had staged data available — same
                # definition as PipelineExecutor.stats (1 - wait / wall).
                # Absorb waits still count as un-overlapped here even
                # though /health judges only the post-absorb histogram.
                "overlap_fraction": max(
                    0.0, 1.0 - (lw_total + aw_total) / wall
                ),
                # Wire accounting (docs/FLEET.md "Wire format"): frame
                # bytes as received vs the declared decompressed size.
                "bytes_in_total": float(srv.seqs_bytes_total),
                "bytes_per_seq": (
                    srv.seqs_bytes_total / max(srv.seqs_received_total, 1)
                ),
                # Bytes crossing into the TRAINING path per trained
                # sequence: under the central drain, EVERY collected
                # sequence crosses the wire into the arena whether or not
                # it is ever sampled — the in-network sampler's headline
                # comparison (docs/REPLAY.md).
                "bytes_per_trained_seq": (
                    srv.seqs_bytes_total
                    / max(
                        drained_here
                        * t.config.learner_steps
                        * t.config.batch_size,
                        1,
                    )
                ),
                "wire_ratio": (
                    srv.seqs_raw_bytes_total / max(srv.seqs_bytes_total, 1)
                ),
                "drain_coalesce_width_mean": (
                    coalesce_sum / max(coalesce_n, 1)
                ),
                # Experience-quality columns (obs/quality.py; the bench
                # fleet leg's algorithm-health read — -1 means the
                # signal never armed this run).
                **quality_stats_columns(),
                # Device plane (ISSUE 14): this run's compile ledger +
                # peak HBM — the bench columns, and what an evidence
                # gate reads off the printed stats line.
                **mon.run_stats(),
            }
            if train_t0 is not None:
                # Steady-state window rates (the bench probe's keys): the
                # plain *_per_sec above span the WHOLE run, startup
                # included — honest for operations, wrong for throughput
                # comparisons.
                train_wall = max(t_end - train_t0, 1e-9)
                self._stats["train_wall_s"] = train_wall
                self._stats["train_arena_add_seqs_per_sec"] = (
                    absorbed - seqs_at_train_t0
                ) / train_wall
                self._stats["train_learner_steps_per_sec"] = (
                    max(drained_here - 1, 0)
                    * t.config.learner_steps
                    / train_wall
                )
        # phase_idx is a collector-slice field the fleet learner never
        # advances; stamp the drained-phase count so the final checkpoint
        # step (and any tooling keyed on it) reflects the trained run.
        return dataclasses.replace(
            merge_state(state, cstate, lstate),
            phase_idx=cstate.phase_idx + drained,
        )

    def _snapshot_params(self, lstate: LearnerState) -> Any:
        return snapshot_params(lstate.train)


def snapshot_params(train) -> Any:
    """The published snapshot: everything an actor needs to act AND to
    rank fresh sequences locally (``agent.initial_priority`` burns in
    online + target nets of both cores — Ape-X actors rank with their
    stale copies of all four).  ONE definition for both learners (the
    central ``FleetLearner`` and the sampler's ``SamplerLearner``): a
    published field added here reaches every fleet flavor."""
    return to_host(
        {
            "actor_params": train.actor_params,
            "critic_params": train.critic_params,
            "target_actor_params": train.target_actor_params,
            "target_critic_params": train.target_critic_params,
            "step": train.step,
        }
    )
