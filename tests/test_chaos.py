"""Fault-tolerance layer (ISSUE 7): heartbeat liveness, HELLO auth,
chaos-injection drills, and the actor reconnect/learner resume paths.

The socket-level heartbeat tests pin the acceptance contract directly:
no blocking read on either wire end ever hangs past the configured
deadline — a silent peer is PINGed once and reaped (``peer_dead``) on a
second silence.  The in-process e2e drives a seeded multi-fault
``--chaos-spec`` through a real 2-actor fleet (thread actors for the
wire drills + a supervised subprocess for the SIGKILL drill) and asserts
every injected fault is paired with its documented recovery event.

``scripts/lib_gate.sh chaos_gate`` refuses to bless ``--actors N``
evidence dirs unless the non-slow tests here pass.
"""

import json
import queue
import socket
import sys
import threading
import time
import zlib

import pytest

from r2d2dpg_tpu.configs import PENDULUM_TINY
from r2d2dpg_tpu.fleet import (
    ActorSupervisor,
    ChaosEngine,
    FleetConfig,
    FleetLearner,
    IngestServer,
    SupervisorConfig,
    parse_chaos_spec,
    transport,
    wire,
)
from r2d2dpg_tpu.fleet import chaos as fleet_chaos
from r2d2dpg_tpu.fleet.chaos import fault_target, send_corrupt_frame
from r2d2dpg_tpu.fleet.transport import (
    K_ACK,
    K_HELLO,
    K_PING,
    K_PONG,
    K_SEQS,
    FrameCRCError,
    PeerDeadError,
    pack_hello,
    pack_obj,
    recv_frame,
    recv_frame_heartbeat,
    send_frame,
    send_frame_parts,
    unpack_obj,
)
from r2d2dpg_tpu.obs import get_flight_recorder
from r2d2dpg_tpu.utils.codes import OK, REFUSED_AUTH

pytestmark = pytest.mark.chaos


def _events(kind=None):
    evs = get_flight_recorder().events()
    return [e for e in evs if kind is None or e["kind"] == kind]


def _hello(sock, actor_id=0, **extra):
    send_frame(
        sock,
        K_HELLO,
        pack_hello(
            {
                "actor_id": actor_id,
                **wire.negotiation_fields(wire.WireConfig()),
                **extra,
            }
        ),
    )


def _np_staged(b=2, l=3):
    import numpy as np

    from r2d2dpg_tpu.replay.arena import SequenceBatch, StagedSequences

    rng = np.random.default_rng(1)
    return StagedSequences(
        seq=SequenceBatch(
            obs=rng.normal(size=(b, l, 3)).astype(np.float32),
            action=rng.normal(size=(b, l, 1)).astype(np.float32),
            reward=rng.normal(size=(b, l)).astype(np.float32),
            discount=np.ones((b, l), np.float32),
            reset=np.zeros((b, l), np.float32),
            carries={},
        ),
        priorities=np.ones((b,), np.float32),
    )


def _seqs_parts(packer, phase=1):
    return packer.pack(
        {
            "phase": phase,
            "param_version": 0,
            "env_steps_delta": 1.0,
            "ep_return_sum": 0.0,
            "ep_count": 0.0,
            "staged": _np_staged(),
        }
    )


# ------------------------------------------------------------- spec parsing
def test_parse_chaos_spec_grammar():
    faults = parse_chaos_spec(
        "kill_actor@p3, stall_actor@p5:4s,corrupt_frame@p7,kill_ingest_conn@p9"
    )
    assert [f.kind for f in faults] == [
        "kill_actor", "stall_actor", "corrupt_frame", "kill_ingest_conn",
    ]
    assert [f.phase for f in faults] == [3, 5, 7, 9]
    assert faults[1].duration_s == 4.0
    assert [f.index for f in faults] == [0, 1, 2, 3]


def test_parse_chaos_spec_sampler_faults():
    """The sampler peer class (ISSUE 10): kill_sampler_conn (no
    duration) and stall_sampler (duration required) parse as
    learner-side faults."""
    from r2d2dpg_tpu.fleet.chaos import LEARNER_FAULTS

    faults = parse_chaos_spec("kill_sampler_conn@p2,stall_sampler@p3:1s")
    assert [f.kind for f in faults] == ["kill_sampler_conn", "stall_sampler"]
    assert faults[1].duration_s == 1.0
    assert {"kill_sampler_conn", "stall_sampler"} <= LEARNER_FAULTS


def test_parse_chaos_spec_shard_faults():
    """The standalone shard tier class (ISSUE 12): kill_shard and
    partition_shard fire learner-side (supervisor SIGKILL / both-legs
    conn drop), stall_shard (duration required) fires inside the target
    shard process."""
    from r2d2dpg_tpu.fleet.chaos import (
        LEARNER_FAULTS,
        SHARD_FAULTS,
        SHARD_PROC_FAULTS,
    )

    faults = parse_chaos_spec(
        "kill_shard@p2,stall_shard@p3:2s,partition_shard@p4"
    )
    assert [f.kind for f in faults] == [
        "kill_shard", "stall_shard", "partition_shard",
    ]
    assert faults[1].duration_s == 2.0
    assert {"kill_shard", "partition_shard"} <= LEARNER_FAULTS
    assert SHARD_PROC_FAULTS == {"stall_shard"}
    assert SHARD_FAULTS == {"kill_shard", "stall_shard", "partition_shard"}


@pytest.mark.parametrize(
    "bad",
    [
        "",
        "kill_actor",
        "kill_actor@3",
        "unknown_fault@p2",
        "kill_actor@p0",
        "kill_actor@p2:3s",  # duration on a non-stall fault
        "stall_actor@p2",  # stall without a duration
        "kill_sampler_conn@p2:3s",  # duration on a non-stall fault
        "stall_sampler@p2",  # stall without a duration
        "kill_shard@p2:3s",  # duration on a non-stall fault
        "stall_shard@p2",  # stall without a duration
        "kill_actor@p1,,kill_actor@p2",
    ],
)
def test_parse_chaos_spec_rejects(bad):
    with pytest.raises(ValueError):
        parse_chaos_spec(bad)


def test_fault_target_deterministic_and_in_range():
    faults = parse_chaos_spec("kill_actor@p1,stall_actor@p2:1s,kill_actor@p3")
    for n in (1, 2, 3, 7):
        targets = [fault_target(f, seed=42, num_actors=n) for f in faults]
        assert targets == [
            fault_target(f, seed=42, num_actors=n) for f in faults
        ]
        assert all(0 <= t < n for t in targets)
    # Distinct spec positions may hit distinct actors (seeded spread, not
    # everything piled on actor 0): over a few seeds SOME pair differs.
    spread = {
        tuple(fault_target(f, seed=s, num_actors=4) for f in faults)
        for s in range(8)
    }
    assert len(spread) > 1


# ------------------------------------------------------- heartbeat liveness
def test_actor_faults_unfired_reads_dump_evidence(tmp_path):
    """Actor-boundary drills leave their evidence in flight_actor*.jsonl
    (record_injection flushes at injection time); a scheduled fault with
    no such line — matched on (kind, phase, target actor), so duplicate
    spec entries hashing to different actors need their own lines — is
    reported so it cannot read as a drill that passed.  Learner-side
    faults are out of scope (ChaosEngine.unfired covers them); garbage
    lines and missing dumps are tolerated."""
    seed, n = 0, 2
    faults = parse_chaos_spec(
        "corrupt_frame@p2,stall_actor@p5:1s,kill_actor@p3"
    )
    targets = {f.kind: fault_target(f, seed, n) for f in faults}
    unfired = lambda: fleet_chaos.actor_faults_unfired(  # noqa: E731
        faults, str(tmp_path), seed=seed, num_actors=n
    )
    # No dumps at all: both actor-side faults are unfired.
    assert {(f.kind, f.phase) for f in unfired()} == {
        ("corrupt_frame", 2), ("stall_actor", 5),
    }
    # Evidence for one of them (+ a garbage line): only the other remains.
    # A line for the WRONG actor is not evidence (a duplicate-entry spec
    # hashes the same kind to different actors).
    with open(tmp_path / "flight_actor1.jsonl", "w") as fh:
        fh.write("not json\n")
        fh.write(
            json.dumps(
                {"kind": "chaos_inject", "fault": "corrupt_frame",
                 "phase": 2, "actor": 1 - targets["corrupt_frame"]}
            ) + "\n"
        )
        fh.write(
            json.dumps(
                {"kind": "chaos_inject", "fault": "corrupt_frame",
                 "phase": 2, "actor": targets["corrupt_frame"]}
            ) + "\n"
        )
    assert [(f.kind, f.phase) for f in unfired()] == [("stall_actor", 5)]
    # A restarted incarnation's pid-suffixed dump counts as evidence too.
    with open(tmp_path / "flight_actor0.pid123.jsonl", "w") as fh:
        fh.write(
            json.dumps(
                {"kind": "chaos_inject", "fault": "stall_actor",
                 "phase": 5, "actor": targets["stall_actor"]}
            ) + "\n"
        )
    assert unfired() == ()


def test_recv_frame_deadline_never_hangs():
    """THE acceptance pin: a blocking read on a deadlined socket raises
    within the deadline — never hangs."""
    a, b = socket.socketpair()
    try:
        a.settimeout(0.2)
        t0 = time.monotonic()
        with pytest.raises(transport.FrameDeadline):
            recv_frame(a)
        assert time.monotonic() - t0 < 2.0
    finally:
        a.close()
        b.close()


def test_recv_frame_heartbeat_pings_then_reaps():
    """Silent peer: one PING after the first deadline, PeerDeadError after
    the second — the whole verdict bounded by ~2x the deadline."""
    a, b = socket.socketpair()
    try:
        a.settimeout(0.3)
        b.settimeout(5)
        t0 = time.monotonic()
        with pytest.raises(PeerDeadError):
            recv_frame_heartbeat(a)
        assert time.monotonic() - t0 < 3.0
        kind, payload = recv_frame(b)  # the probe reached the peer
        assert kind == K_PING and payload == b""
    finally:
        a.close()
        b.close()


def test_recv_frame_heartbeat_mid_frame_stall_is_peer_dead():
    """A peer that stalls MID-frame past the deadline is reaped directly:
    the partial frame's bytes are already consumed, so the stream can
    never resynchronize — a PING-then-retry would misparse the leftover
    payload as a header (FrameBadMagic) and misattribute the liveness
    failure as a protocol violation."""
    a, b = socket.socketpair()
    try:
        a.settimeout(0.3)
        # Header promising 64 payload bytes, then only half of them.
        payload = bytes(64)
        header = transport._HEADER.pack(
            transport.MAGIC, K_SEQS, len(payload), zlib.crc32(payload)
        )
        b.sendall(header + payload[:32])
        t0 = time.monotonic()
        with pytest.raises(PeerDeadError, match="mid-frame"):
            recv_frame_heartbeat(a)
        assert time.monotonic() - t0 < 2.0
    finally:
        a.close()
        b.close()


def test_recv_frame_heartbeat_pong_proves_liveness():
    """A peer that answers the PING is alive: the reader keeps waiting
    (re-probing), and a real frame ends the exchange normally."""
    a, b = socket.socketpair()
    try:
        a.settimeout(0.3)
        b.settimeout(5)

        def peer():
            # Answer two probes, then send a real frame.
            for _ in range(2):
                kind, _ = recv_frame(b)
                assert kind == K_PING
                send_frame(b, K_PONG, b"")
            send_frame(b, K_ACK, pack_obj({"code": OK}))

        t = threading.Thread(target=peer, daemon=True)
        t.start()
        kind, payload = recv_frame_heartbeat(a)
        assert kind == K_ACK and unpack_obj(payload) == {"code": OK}
        t.join(timeout=5)
    finally:
        a.close()
        b.close()


def test_ingest_reaps_silent_peer_with_peer_dead_event():
    """Server side of the contract: a connection that HELLOs, streams one
    batch, then goes silent is PINGed and reaped within the heartbeat
    deadline — ``peer_dead`` flight event + obs counter, connection
    closed."""
    q: queue.Queue = queue.Queue(maxsize=4)
    srv = IngestServer(
        q, address="127.0.0.1:0", read_deadline_s=0.3, warmup_deadline_s=0.3
    )
    srv.start()
    sock = transport.connect(srv.address, read_deadline_s=None)
    sock.settimeout(10)
    try:
        _hello(sock, actor_id=7)
        recv_frame(sock)  # hello ack
        packer = wire.TreePacker(wire.WireConfig())
        send_frame_parts(sock, K_SEQS, _seqs_parts(packer))
        kind, payload = recv_frame(sock)
        assert kind == K_ACK and unpack_obj(payload)["code"] == OK
        # Go silent.  The handler pings once, then reaps.
        t0 = time.monotonic()
        kind, _ = recv_frame(sock)
        assert kind == K_PING
        with pytest.raises(transport.FrameError):
            while True:  # drain to the reap (a second PING may precede it)
                recv_frame(sock)
        assert time.monotonic() - t0 < 5.0
        deadline = time.monotonic() + 5
        while time.monotonic() < deadline and not _events("peer_dead"):
            time.sleep(0.05)
        reaps = [e for e in _events("peer_dead") if e.get("actor") == "7"]
        assert reaps and reaps[-1]["deadline_s"] == 0.3
    finally:
        sock.close()
        srv.stop()


# ---------------------------------------------------------------- HELLO auth
def test_hello_is_json_never_pickle():
    """HELLO is the ONE frame parsed before authentication (the token
    proof rides inside it), so its decoder must be data-only: a pickled
    HELLO — which would execute attacker bytes on a routable bind — is
    refused as malformed and the connection dropped, auth never
    consulted."""
    assert transport.unpack_hello(
        transport.pack_hello({"actor_id": 3, "auth": "ab" * 32})
    ) == {"actor_id": 3, "auth": "ab" * 32}
    for bad in (pack_obj({"actor_id": 3}), b"\xff\xfe", b"[1, 2]"):
        with pytest.raises(transport.FrameError, match="malformed HELLO"):
            transport.unpack_hello(bad)
    # End to end: a pickle HELLO at the door is dropped, never parsed.
    q: queue.Queue = queue.Queue(maxsize=1)
    srv = IngestServer(q, address="127.0.0.1:0", auth_token="s3cret")
    srv.start()
    try:
        sock = transport.connect(srv.address, read_deadline_s=None)
        sock.settimeout(10)
        send_frame(sock, K_HELLO, pack_obj({"actor_id": 3}))
        with pytest.raises(transport.FrameTruncated):
            recv_frame(sock)  # connection dropped without any ack
        sock.close()
    finally:
        srv.stop()


def test_is_loopback_address_hostnames_are_not_loopback():
    """Only literal loopback IPs (and unix:/localhost) are provably
    local: a HOSTNAME merely starting with '127.' could resolve anywhere
    and must not suppress the unauthenticated-routable-bind warning."""
    assert transport.is_loopback_address("127.0.0.1:7000")
    assert transport.is_loopback_address("127.9.8.7:7000")
    assert transport.is_loopback_address("localhost:7000")
    assert transport.is_loopback_address("unix:/tmp/x.sock")
    assert not transport.is_loopback_address("0.0.0.0:7000")
    assert not transport.is_loopback_address("10.1.2.3:7000")
    assert not transport.is_loopback_address("127-compat.example:7000")
    assert not transport.is_loopback_address("127.evil.example:7000")


def test_ingest_auth_refuses_missing_and_bad_token():
    q: queue.Queue = queue.Queue(maxsize=1)
    srv = IngestServer(q, address="127.0.0.1:0", auth_token="s3cret")
    srv.start()
    try:
        for extra in ({}, {"auth": "not-the-proof"}):
            sock = transport.connect(srv.address, read_deadline_s=None)
            sock.settimeout(10)
            _hello(sock, actor_id="intruder-99", **extra)
            kind, payload = recv_frame(sock)
            ack = unpack_obj(payload)
            assert kind == K_ACK and ack["code"] == REFUSED_AUTH
            with pytest.raises(transport.FrameTruncated):
                recv_frame(sock)  # server dropped the connection
            sock.close()
        assert q.qsize() == 0
        assert _events("auth_refused")
        # No per-actor state for an UNAUTHENTICATED claim: the actor_id is
        # attacker-controlled on routable binds, and labeled series (or a
        # _conn_actors entry) per refused HELLO would grow the registry
        # without bound under a port scanner.
        assert "intruder-99" not in srv._conn_actors.values()
        from r2d2dpg_tpu.obs import get_registry

        snap = get_registry().snapshot()["r2d2dpg_fleet_bytes_in_total"]
        assert not any(
            s["labels"].get("actor") == "intruder-99"
            for s in snap["samples"]
        )

        # The right proof is accepted and the stream works.
        sock = transport.connect(srv.address, read_deadline_s=None)
        sock.settimeout(10)
        _hello(sock, actor_id=2, auth=transport.hello_auth_proof("s3cret"))
        kind, payload = recv_frame(sock)
        assert kind == K_ACK and unpack_obj(payload)["code"] == OK
        sock.close()
    finally:
        srv.stop()


def test_actor_exits_terminal_on_auth_refusal():
    """A wrong-token actor must exit EXIT_AUTH_REFUSED (terminal — the
    supervisor gives the slot up, no crash-restart churn)."""
    from r2d2dpg_tpu.fleet.actor import FleetActor, _AuthRefused

    q: queue.Queue = queue.Queue(maxsize=1)
    srv = IngestServer(q, address="127.0.0.1:0", auth_token="right")
    srv.start()
    try:
        actor = FleetActor(
            PENDULUM_TINY,
            actor_id=0,
            num_actors=1,
            address=srv.address,
            seed=0,
            auth_token="wrong",
            reconnect_tries=0,
        )
        with pytest.raises(_AuthRefused):
            actor.run(max_phases=1)
    finally:
        srv.stop()


def test_supervisor_gives_up_on_auth_refused_exit():
    from r2d2dpg_tpu.utils.codes import EXIT_AUTH_REFUSED

    sup = ActorSupervisor(
        lambda i: [sys.executable, "-c", f"exit({EXIT_AUTH_REFUSED})"],
        1,
        config=SupervisorConfig(backoff_base_s=0.02, poll_s=0.02),
    )
    sup.start()
    try:
        deadline = time.monotonic() + 20
        while time.monotonic() < deadline:
            if any(
                e.get("reason") == "auth_refused"
                for e in _events("actor_gave_up")
            ):
                break
            time.sleep(0.05)
    finally:
        sup.stop()
    assert sup.restarts_total == 0
    assert any(
        e.get("reason") == "auth_refused" for e in _events("actor_gave_up")
    )


# ------------------------------------------------------------ frame corruption
def test_send_corrupt_frame_is_crc_rejected():
    """The corrupt_frame boundary: pristine CRC over flipped bytes — the
    receiver MUST reject (never silently decode)."""
    a, b = socket.socketpair()
    try:
        b.settimeout(5)
        payload = b"x" * 64
        send_corrupt_frame(a, K_SEQS, [payload])
        with pytest.raises(FrameCRCError):
            recv_frame(b)
    finally:
        a.close()
        b.close()


def test_ingest_rejects_corrupt_frame_and_drops_connection():
    q: queue.Queue = queue.Queue(maxsize=4)
    srv = IngestServer(q, address="127.0.0.1:0")
    srv.start()
    sock = transport.connect(srv.address, read_deadline_s=None)
    sock.settimeout(10)
    try:
        _hello(sock, actor_id=4)
        recv_frame(sock)  # hello ack
        packer = wire.TreePacker(wire.WireConfig())
        send_corrupt_frame(sock, K_SEQS, _seqs_parts(packer))
        with pytest.raises(transport.FrameError):
            recv_frame(sock)  # connection killed, no ack
        deadline = time.monotonic() + 5
        while time.monotonic() < deadline:
            if any(
                "FrameCRCError" in str(e.get("error", ""))
                for e in _events("ingest_conn_error")
            ):
                break
            time.sleep(0.05)
        assert any(
            "FrameCRCError" in str(e.get("error", ""))
            for e in _events("ingest_conn_error")
        )
        assert q.qsize() == 0  # the corrupt batch never crossed
    finally:
        sock.close()
        srv.stop()


# --------------------------------------------------------- leaked handlers
def test_ingest_stop_reports_leaked_handler_threads():
    """stop() must NAME a handler that outlives its join window (a wedged
    handler was previously leaked silently — ISSUE 7 satellite)."""
    q: queue.Queue = queue.Queue(maxsize=1)
    srv = IngestServer(q, address="127.0.0.1:0")
    srv.start()
    srv.stop_join_s = 0.1
    release = threading.Event()
    wedged = threading.Thread(
        target=release.wait, name="fleet-ingest-conn99-wedged", daemon=True
    )
    wedged.start()
    srv._handlers.append(wedged)
    try:
        srv.stop()
        leaks = _events("ingest_handler_leaked")
        assert any("conn99-wedged" in e.get("thread", "") for e in leaks)
    finally:
        release.set()


# --------------------------------------------------- in-process chaos e2e
def test_chaos_multi_fault_drill_in_process_e2e(tmp_path):
    """The non-slow acceptance drill: a seeded spec covering
    kill/stall/corrupt/conn-drop against a live 2-actor fleet.

    Thread actors carry the experience stream (stall/corrupt/conn-drop
    drills hit their REAL wire boundaries); the SIGKILL drill hits a real
    supervised subprocess (a stand-in sleeper — jax-free, so the drill
    costs milliseconds, while the kill -> crash -> backoff-restart path
    is the genuine supervisor code).  Asserts: the run completes its full
    phase schedule, env-step counters are monotone, accounting is not
    lost, sheds stay 0, and every injected fault is paired with its
    recovery event in the flight ring (all sides share this process's
    recorder, so the pairing is checked in ONE place — a subprocess fleet
    checks the same via `obs.flight merge`, tests/test_chaos.py soak)."""
    from r2d2dpg_tpu.fleet.actor import FleetActor

    seed = 0
    num_actors = 2
    spec = "corrupt_frame@p2,stall_actor@p3:2s,kill_actor@p2,kill_ingest_conn@p5"
    faults = parse_chaos_spec(spec)
    trainer = PENDULUM_TINY.build()
    learner = FleetLearner(
        trainer,
        FleetConfig(
            num_actors=num_actors,
            # Deep queue: handlers never park in a queue-full wait, so
            # acks stay prompt, the short heartbeat below only ever fires
            # on REAL silence, and a parked handler can never miss the
            # stall drill's reap window.  Sized ~3x past what the actors
            # can produce over the whole run on a slow 1-core box
            # (~120 tiny batches/s for ~45 s), so zero sheds holds by
            # construction; the actors' effectively-unbounded max_phases
            # below keeps them connected (and the conn-kill drill
            # targetable) until the learner's schedule completes.
            queue_depth=16384,
            idle_timeout_s=120,
            heartbeat_s=0.75,
            warmup_deadline_s=60,
        ),
    )
    address = learner.start()
    actors = [
        FleetActor(
            PENDULUM_TINY,
            actor_id=i,
            num_actors=num_actors,
            address=address,
            seed=seed,
            chaos_spec=spec,
            read_deadline_s=30,
            reconnect_tries=8,
            reconnect_base_s=0.1,
            reconnect_max_s=0.5,
        )
        for i in range(num_actors)
    ]

    def actor_loop(a):
        try:
            a.run(max_phases=1_000_000)  # outlive the learner's schedule
        except Exception:  # noqa: BLE001 — server teardown cuts the socket
            pass

    threads = [
        threading.Thread(target=actor_loop, args=(a,), daemon=True)
        for a in actors
    ]
    # The SIGKILL drill's victims: supervised jax-free sleepers (spawn in
    # milliseconds), one slot per fleet actor id so any seeded target is
    # coverable.  The kill -> actor_crash -> backoff -> actor_restart path
    # is the real supervisor.
    sup = ActorSupervisor(
        lambda i: [sys.executable, "-c", "import time; time.sleep(600)"],
        num_actors,
        config=SupervisorConfig(backoff_base_s=0.1, poll_s=0.05),
    )
    engine = ChaosEngine(
        faults,
        seed=seed,
        num_actors=num_actors,
        supervisor=sup,
        server=learner.server,
    )
    n_train = 8
    rows = []
    # The flight ring is global across tests (other drills leave their
    # own chaos_inject lines behind): only events from OUR run count.
    n0 = len(get_flight_recorder().events())
    for t in threads:
        t.start()
    try:
        sup.start()
        state = learner.run(
            n_train,
            log_every=2,
            metrics_fn=lambda p, s: rows.append((p, dict(s))),
            phase_fn=engine.on_phase,
        )
        # The queue backlog lets the learner burn its remaining phases in
        # milliseconds after the SIGKILL drill, so on a fast box the run
        # can end BEFORE the ~0.1 s backoff restart lands — and teardown
        # stops the supervisor, erasing the recovery this test asserts.
        # Hold the fleet up until the restart is observable.
        deadline = time.monotonic() + 15
        while time.monotonic() < deadline and sup.restarts_total < 1:
            time.sleep(0.05)
        time.sleep(0.1)  # let the restart's flight event land too
    finally:
        sup.stop()
        learner.close()
        for t in threads:
            t.join(timeout=30)

    # 1. The run completed its exact schedule despite every fault.
    assert int(state.train.step) == n_train * trainer.config.learner_steps
    stats = learner.stats()
    assert stats["train_phases"] == n_train
    assert not engine.unfired()

    # 2. Monotone env-step counters, no lost accounting, sheds == 0.
    env_steps = [s["env_steps"] for _, s in rows]
    assert env_steps == sorted(env_steps) and env_steps[-1] > 0
    assert stats["sheds"] == 0

    # 3. Every injected fault paired with its documented recovery.
    events = get_flight_recorder().events()[n0:]
    injected = {
        (e["fault"], e["actor"])
        for e in events
        if e["kind"] == "chaos_inject"
    }
    assert {f for f, _ in injected} == {
        "kill_actor", "stall_actor", "corrupt_frame", "kill_ingest_conn",
    }
    kinds = {e["kind"] for e in events}
    # corrupt_frame -> CRC reject killed the connection…
    assert any(
        "FrameCRCError" in str(e.get("error", ""))
        for e in events
        if e["kind"] == "ingest_conn_error"
    )
    # stall_actor -> heartbeat reap…
    assert "peer_dead" in kinds
    # …and both recovered via in-process reconnect (fresh HELLO).
    assert "actor_reconnect" in kinds
    # kill_actor -> supervised crash + backoff restart.
    kill_target = next(a for f, a in injected if f == "kill_actor")
    assert any(
        e["kind"] == "actor_crash" and e.get("actor") == kill_target
        for e in events
    )
    assert any(
        e["kind"] == "actor_restart" and e.get("actor") == kill_target
        for e in events
    )
    # kill_ingest_conn named who it dropped.
    drop = next(
        e for e in events
        if e["kind"] == "chaos_inject" and e["fault"] == "kill_ingest_conn"
    )
    assert drop.get("dropped") is not None

    # 4. The drill counter counted every fired fault.
    from r2d2dpg_tpu.obs import get_registry

    snap = get_registry().snapshot()["r2d2dpg_fleet_chaos_drills_total"]
    fired = {
        s["labels"]["fault"]: s["value"] for s in snap["samples"]
    }
    for kind in ("kill_actor", "stall_actor", "corrupt_frame",
                 "kill_ingest_conn"):
        assert fired.get(kind, 0) >= 1


def test_chaos_sampler_drills_in_process_e2e():
    """The sampler peer class's drills (ISSUE 10): a live 2-actor
    2-shard sampler fleet under ``stall_sampler`` + ``kill_sampler_conn``.

    What the drills pin (docs/REPLAY.md "Recovery contract"):

    - ``stall_sampler`` — the pull loop sleeps, and NOTHING downstream
      degrades: shards keep absorbing under their own locks (no central
      drain to back up), so actors neither shed nor get reaped — the
      run completes with sheds == 0 and zero peer_dead events.
    - ``kill_sampler_conn`` — the connection FEEDING a shard dies; the
      actor reconnects (fresh HELLO) onto the SAME consistent-hash
      shard, whose data survives, and the at-least-once accounting
      re-banks the in-flight deltas: env-step counters stay monotone,
      so a dead shard feed loses only re-collectable experience.
    """
    from r2d2dpg_tpu.fleet import FleetConfig, SamplerLearner
    from r2d2dpg_tpu.fleet.actor import FleetActor
    from r2d2dpg_tpu.configs import PENDULUM_TINY

    seed = 0
    num_actors = 2
    spec = "stall_sampler@p2:1s,kill_sampler_conn@p3"
    faults = parse_chaos_spec(spec)
    trainer = PENDULUM_TINY.build()
    learner = SamplerLearner(
        trainer,
        FleetConfig(num_actors=num_actors, idle_timeout_s=120),
        num_shards=2,
    )
    address = learner.start()
    actors = [
        FleetActor(
            PENDULUM_TINY,
            actor_id=i,
            num_actors=num_actors,
            address=address,
            seed=seed,
            reconnect_tries=8,
            reconnect_base_s=0.1,
            reconnect_max_s=0.5,
        )
        for i in range(num_actors)
    ]

    def actor_loop(a):
        try:
            # Unpaced on purpose: sampler-mode acks never block (ring
            # eviction replaces backpressure), so a phase-capped actor
            # would sprint through its budget during the learner's
            # compile and exit before the drills fire — stream until the
            # server teardown cuts the socket.
            a.run()
        except Exception:  # noqa: BLE001 — server teardown cuts the socket
            pass

    threads = [
        threading.Thread(target=actor_loop, args=(a,), daemon=True)
        for a in actors
    ]
    engine = ChaosEngine(
        faults,
        seed=seed,
        num_actors=num_actors,
        server=learner.server,
    )
    n0 = len(get_flight_recorder().events())
    n_train = 6
    rows = []
    for t in threads:
        t.start()
    try:
        state = learner.run(
            n_train,
            log_every=1,
            metrics_fn=lambda p, s: rows.append((p, dict(s))),
            phase_fn=engine.on_phase,
        )
        # The free-running sampler finishes its phases in milliseconds;
        # hold the server open until the dropped actor's reconnect (its
        # backoff is ~0.1 s) lands, so the recovery is observable.
        deadline = time.monotonic() + 15
        while time.monotonic() < deadline and not any(
            e["kind"] == "actor_reconnect"
            for e in get_flight_recorder().events()[n0:]
        ):
            time.sleep(0.05)
    finally:
        learner.close()
        for t in threads:
            t.join(timeout=30)

    # The run completed its exact schedule despite both faults.
    assert int(state.train.step) == n_train * trainer.config.learner_steps
    stats = learner.stats()
    assert stats["train_phases"] == n_train
    assert not engine.unfired()
    # Monotone accounting, structurally zero sheds.
    env_steps = [s["env_steps"] for _, s in rows]
    assert env_steps == sorted(env_steps) and env_steps[-1] > 0
    assert stats["sheds"] == 0
    events = get_flight_recorder().events()[n0:]
    injected = {
        e["fault"] for e in events if e["kind"] == "chaos_inject"
    }
    assert injected == {"stall_sampler", "kill_sampler_conn"}
    # The stall recorded its duration and reaped NOBODY (ring eviction,
    # not queue backpressure, absorbs a stalled sampler).
    stall = next(
        e for e in events
        if e["kind"] == "chaos_inject" and e["fault"] == "stall_sampler"
    )
    assert stall.get("duration_s") == 1.0
    assert not [e for e in events if e["kind"] == "peer_dead"]
    # The conn drop named its victim and the actor reconnected; the
    # victim's shard kept its data (occupancy never collapsed to the
    # other shard alone — the run finished sampling from BOTH whenever
    # both advertise, which monotone env steps + completion imply).
    drop = next(
        e for e in events
        if e["kind"] == "chaos_inject" and e["fault"] == "kill_sampler_conn"
    )
    assert drop.get("dropped") is not None
    assert any(e["kind"] == "actor_reconnect" for e in events)


# ------------------------------------------------------------- slow soaks
@pytest.mark.slow
def test_chaos_subprocess_fleet_soak(tmp_path):
    """The full-fidelity drill: real actor SUBPROCESSES via the train.py
    CLI with a seeded --chaos-spec covering all four faults — completes
    training, and the merged learner+actor flight timeline pairs every
    injection with its recovery."""
    from r2d2dpg_tpu import train
    from r2d2dpg_tpu.obs.flight import expand_flight_paths, merge_flight_files

    logdir = tmp_path / "run"
    final = train.run(
        train.parse_args(
            [
                "--config", "pendulum_tiny",
                "--actors", "2",
                # Enough drain phases to OUTLAST the queue backlog: the
                # deep queue (below) fills completely during the drain
                # compile, and those 32 batches burn in well under a
                # second — the supervisor's backoff restart (~0.5s) can
                # only be witnessed by phases fed from LIVE collection
                # after the burn, so the schedule must extend past it.
                "--phases", "50",
                "--log-every", "10",
                "--logdir", str(logdir),
                "--fleet-queue-depth", "32",
                "--fleet-heartbeat", "2",
                "--fleet-idle-timeout", "600",
                "--chaos-spec",
                "kill_actor@p2,corrupt_frame@p3,stall_actor@p4:5s,"
                "kill_ingest_conn@p6",
                "--watchdog", "0",
            ]
        )
    )
    assert final["fleet_train_phases"] == 50
    # Merge the learner's ring (still in memory — dump it) + actor dumps.
    get_flight_recorder().dump(str(logdir / "flight.jsonl"))
    events, skipped = merge_flight_files(
        expand_flight_paths([str(logdir)])
    )
    assert skipped == 0
    injected = {e["fault"] for e in events if e["kind"] == "chaos_inject"}
    assert injected == {
        "kill_actor", "stall_actor", "corrupt_frame", "kill_ingest_conn",
    }
    kinds = {e["kind"] for e in events}
    assert "actor_crash" in kinds and "actor_restart" in kinds
    assert "peer_dead" in kinds or "ingest_conn_error" in kinds
    assert "actor_reconnect" in kinds


@pytest.mark.slow
def test_learner_kill_and_resume_e2e(tmp_path):
    """Learner recovery, full fidelity: a fleet train.py run is SIGKILLed
    mid-phase, then resumed from its periodic checkpoint — the resumed
    run completes the TOTAL phase target, counters stay monotone, and the
    actors of the new incarnation connect without supervisor give-up."""
    import os
    import signal
    import subprocess

    from r2d2dpg_tpu import train
    from r2d2dpg_tpu.fleet.ingest import load_fleet_counters

    logdir = tmp_path / "run"
    ckpt_dir = logdir / "ckpt"
    env = dict(os.environ)
    env.update(JAX_PLATFORMS="cpu", R2D2DPG_PALLAS_INTERPRET="1")
    argv = [
        sys.executable, "-m", "r2d2dpg_tpu.train",
        "--config", "pendulum_tiny",
        "--actors", "2",
        "--phases", "12",
        "--log-every", "2",
        "--logdir", str(logdir),
        "--checkpoint-dir", str(ckpt_dir),
        "--checkpoint-every", "2",
        "--fleet-queue-depth", "32",
        "--fleet-idle-timeout", "600",
        "--watchdog", "0",
    ]
    proc = subprocess.Popen(
        argv, env=env,
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
    )
    # Wait for a periodic checkpoint (sidecar + orbax step), then KILL the
    # learner mid-run — hour-10 crash, miniature.
    deadline = time.monotonic() + 600
    step = None
    try:
        while time.monotonic() < deadline:
            if proc.poll() is not None:
                out = proc.stdout.read().decode(errors="replace")
                raise AssertionError(
                    f"learner exited rc={proc.returncode} before the kill:"
                    f"\n{out[-4000:]}"
                )
            steps = [
                int(n[len("fleet_counters_"):-len(".json")])
                for n in (
                    os.listdir(ckpt_dir) if ckpt_dir.exists() else []
                )
                if n.startswith("fleet_counters_") and n.endswith(".json")
            ]
            if steps:
                step = max(steps)
                break
            time.sleep(0.5)
        assert step is not None, "no periodic checkpoint before the deadline"
        proc.send_signal(signal.SIGKILL)
    finally:
        proc.wait(timeout=30)
    counters_before = load_fleet_counters(str(ckpt_dir), step)
    assert counters_before.get("drained", 0) >= 2
    gave_up_before = len(_events("actor_gave_up"))

    # Resume IN-process (same CLI path) and run to the total target.
    final = train.run(
        train.parse_args(
            [
                "--config", "pendulum_tiny",
                "--actors", "2",
                "--phases", "12",
                "--log-every", "2",
                "--logdir", str(logdir),
                "--checkpoint-dir", str(ckpt_dir),
                "--checkpoint-every", "2",
                "--resume",
                "--fleet-queue-depth", "32",
                "--fleet-idle-timeout", "600",
                "--watchdog", "0",
            ]
        )
    )
    assert final["fleet_train_phases_total"] == 12
    assert final["env_steps"] >= counters_before["env_steps_total"]
    assert final["learner_steps"] == 12 * PENDULUM_TINY.trainer.learner_steps
    # The new incarnation's supervisor never gave an actor up.
    assert len(_events("actor_gave_up")) == gave_up_before
    # And a further resume would see the final counters.
    latest = max(
        int(p.name[len("fleet_counters_"):-len(".json")])
        for p in ckpt_dir.iterdir()
        if p.name.startswith("fleet_counters_")
        and p.name.endswith(".json")
    )
    counters_after = load_fleet_counters(str(ckpt_dir), latest)
    assert counters_after["drained"] == 12
    assert counters_after["env_steps_total"] >= counters_before[
        "env_steps_total"
    ]
