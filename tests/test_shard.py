"""Standalone crash-tolerant replay shard tier (ISSUE 12): supervised
shard processes, quota renormalization on shard loss, epoch-fenced
rejoin (fleet/shard.py).

Anchors ``scripts/lib_gate.sh shard_gate`` enforces before blessing
``--shard-procs N`` evidence dirs:

- **determinism** — the loopback-vs-out-of-process boundary is layout,
  never semantics: a BATCH through a REAL socket decodes bit-identically
  to the in-learner loopback roundtrip on the f32 lane (plus the
  ``--shard-procs 0`` off-setting riding the sampler CLI anchor in
  tests/test_sampler.py).
- **kill_shard** — the non-slow chaos e2e: 2 actors x 2 shard procs,
  ``kill_shard`` mid-run -> the run completes, counters stay monotone,
  quotas renormalize to the surviving shard, the restarted shard rejoins
  under a bumped epoch and serves traffic, and stale-epoch PRIO frames
  are ignored with a flight event; ``stall_shard`` pins zero sheds and
  zero false reaps through the stall.
"""

import glob
import json
import re
import socket
import threading
import time

import numpy as np
import pytest

from r2d2dpg_tpu import obs
from r2d2dpg_tpu.configs import PENDULUM_TINY
from r2d2dpg_tpu.fleet import chaos as fleet_chaos
from r2d2dpg_tpu.fleet import transport, wire
from r2d2dpg_tpu.fleet.shard import (
    RemoteShard,
    RemoteShardSet,
    ShardProcTier,
    ShardServer,
    ShardUnavailableError,
)
from r2d2dpg_tpu.fleet.supervisor import SupervisorConfig
from r2d2dpg_tpu.obs import get_flight_recorder
from r2d2dpg_tpu.obs import registry as obs_registry
from r2d2dpg_tpu.obs.trace import SHARD_HOPS
from r2d2dpg_tpu.replay.arena import SequenceBatch, StagedSequences
from r2d2dpg_tpu.replay.sharded import ReplayShard

pytestmark = pytest.mark.shard


def _np_staged(b=3, l=3, prios=(1.0, 2.0, 3.0), seed=1):
    rng = np.random.default_rng(seed)
    return StagedSequences(
        seq=SequenceBatch(
            obs=rng.normal(size=(b, l, 3)).astype(np.float32),
            action=rng.normal(size=(b, l, 1)).astype(np.float32),
            reward=rng.normal(size=(b, l)).astype(np.float32),
            discount=np.ones((b, l), np.float32),
            reset=np.zeros((b, l), np.float32),
            carries={},
        ),
        priorities=(
            None if prios is None else np.asarray(prios, np.float64)
        ),
    )


def _server(shard_id=0, epoch=1, capacity=8, auth=None, chaos=None):
    return ShardServer(
        ReplayShard(capacity, alpha=1.0, shard_id=shard_id),
        epoch=epoch,
        seed=0,
        auth_token=auth,
        chaos=chaos,
    ).start()


def _client(srv, auth=None, **kw):
    return RemoteShard(
        srv.shard.shard_id,
        lambda: srv.address,
        wire_config=wire.WireConfig(),
        auth_token=auth,
        max_frame_bytes=transport.MAX_FRAME_BYTES,
        read_deadline_s=30.0,
        **kw,
    )


# ------------------------------------------------------- determinism anchor
def test_socket_vs_loopback_batch_determinism_bitwise():
    """The shard_gate anchor: the SAME ShardSample through (a) the
    in-learner loopback pack/unpack and (b) a REAL ShardServer socket
    exchange decodes bit-identically on the f32 lane — moving a shard
    out of process is layout, never semantics."""
    staged = _np_staged(b=4, prios=(1.0, 2.0, 3.0, 4.0))
    srv = _server(capacity=8)
    client = _client(srv)
    try:
        # Seed the remote shard, then mirror its exact ring state locally.
        client.forward_seqs(staged)
        local = ReplayShard(8, alpha=1.0, shard_id=0)
        local.add(staged.seq, staged.priorities)
        # Remote draw (real socket), then replay the identical draw
        # locally: the shard process seeds its rng (seed, shard, epoch).
        resp = client.sample(5, req_id=1)
        rng = np.random.default_rng((0, 0, 1))
        s = local.sample(5, rng)
        packer = wire.TreePacker(wire.WireConfig())
        unpacker = wire.TreeUnpacker()
        loop = wire.unpack_shard_batch(
            unpacker.unpack(
                b"".join(
                    bytes(p)
                    for p in wire.pack_shard_batch(
                        packer,
                        req_id=1,
                        shard=0,
                        staged=StagedSequences(seq=s.seq, priorities=None),
                        slots=s.slots,
                        gens=s.gens,
                        probs=s.probs,
                        priority_sum=local.scaled_sum(),
                        occupancy=local.occupancy(),
                        epoch=1,
                    )
                )
            )
        )
        np.testing.assert_array_equal(resp["slots"], loop["slots"])
        np.testing.assert_array_equal(resp["gens"], loop["gens"])
        np.testing.assert_array_equal(resp["probs"], loop["probs"])
        for a, b in zip(
            [resp["staged"].seq.obs, resp["staged"].seq.reward],
            [loop["staged"].seq.obs, loop["staged"].seq.reward],
        ):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        assert resp["epoch"] == loop["epoch"] == 1
        assert resp["priority_sum"] == loop["priority_sum"]
    finally:
        client.close()
        srv.stop()


# ----------------------------------------------------------- shard protocol
def test_shard_server_auth_epoch_and_stale_prio_fence():
    """Protocol + fences on one in-process server: HELLO auth refusal,
    the SEQS ack advertisement, BATCH epoch stamping, and the
    authoritative shard-side stale-epoch PRIO ignore (applied=0 + flight
    event + counter) that protects a restarted ring from its
    predecessor's verdicts."""
    srv = _server(shard_id=3, epoch=7, auth="sekrit")
    n0 = len(get_flight_recorder().events())
    try:
        # Wrong token: refused at the door.
        bad = _client(srv, auth="wrong")
        with pytest.raises(RuntimeError, match="refused"):
            bad.forward_seqs(_np_staged())
        bad.close()
        client = _client(srv, auth="sekrit")
        ack = client.forward_seqs(_np_staged(prios=(1.0, 2.0, 4.0)))
        assert ack["code"] == "ok" and ack["epoch"] == 7
        assert ack["occupancy"] == 3 and ack["scaled_sum"] == 7.0
        assert ack["priority_sum"] == 7.0 and ack["evictions"] == 0
        assert client.epoch == 7 and client.occupancy == 3
        resp = client.sample(2, req_id=5)
        assert resp["epoch"] == 7 and resp["req_id"] == 5
        # Fresh-epoch write-back applies; stale-epoch is IGNORED loudly.
        ok = client.write_back(
            resp["slots"], resp["gens"],
            np.full(2, 9.0, np.float32), epoch=7,
        )
        assert ok["applied"] == 2 and not ok["stale"]
        stale = client.write_back(
            resp["slots"], resp["gens"],
            np.full(2, 1.0, np.float32), epoch=6,
        )
        assert stale["applied"] == 0 and stale["stale"]
        # A SAMPLE_REQ at a live-but-EMPTY shard answers with an
        # empty-marked advert ack (None here), never a torn connection —
        # a stale quota weight meeting a fresh ring must not read as a
        # dead process (the connection stays usable).
        empty_srv = _server(shard_id=9, epoch=1)
        empty_client = _client(empty_srv)
        try:
            assert empty_client.sample(3, req_id=1) is None
            assert empty_client.scaled_sum == 0.0
            empty_client.forward_seqs(_np_staged())
            # The SAMPLE leg survived the empty answer: the very same
            # connection now serves a real BATCH.
            assert empty_client.sample(2, req_id=2) is not None
        finally:
            empty_client.close()
            empty_srv.stop()
        evs = [
            e for e in get_flight_recorder().events()[n0:]
            if e["kind"] == "stale_epoch_prio_ignored"
        ]
        assert evs and evs[-1]["got_epoch"] == 6 and evs[-1]["epoch"] == 7
        client.close()
    finally:
        srv.stop()


def test_remote_set_reroute_renorm_and_epoch_fenced_rejoin():
    """The degradation half without processes: kill server 0 (stop =
    dial refused), the set marks it dead — quota weights zero, routing
    falls to the survivor in ring order, accounting banks regardless —
    then a NEW incarnation (bumped epoch) rejoins: routing returns home,
    the stale advert is zeroed (an empty restarted ring must not inherit
    the dead ring's sums), and the learner-side epoch fence drops
    write-backs against the old incarnation."""
    addrs = {}
    srv0 = _server(shard_id=0, epoch=1)
    srv1 = _server(shard_id=1, epoch=1)
    addrs[0], addrs[1] = srv0.address, srv1.address
    ss = RemoteShardSet(
        2,
        lambda sid: addrs[sid],
        wire_config=wire.WireConfig(),
        rejoin_interval_s=0.0,
    )
    n0 = len(get_flight_recorder().events())
    try:
        for sid in (0, 1):
            ss.add(sid, {"staged": _np_staged(), "env_steps_delta": 9.0})
        assert ss.occupancy_total() == 6
        np.testing.assert_allclose(ss.scaled_sums(), [6.0, 6.0])
        resp = ss.shards[0].sample(2, req_id=1)
        handles_epoch = resp["epoch"]
        # --- death: server 0 gone, dial refused.
        srv0.stop()
        with pytest.raises(ShardUnavailableError):
            ss.shards[0].sample(1, req_id=2)
        ss._mark_dead(0, "drill")
        np.testing.assert_allclose(ss.scaled_sums(), [0.0, 6.0])
        assert ss.route(0) == 1  # home shard dead -> survivor, in ring order
        # adds (home 0) re-route; the accounting banks either way.
        ss.add(0, {"staged": _np_staged(), "env_steps_delta": 9.0,
                   "actor_id": 0})
        assert ss.shards[1].occupancy == 6  # ring of 8 holds both adds
        assert ss.pop_stats()["env_steps_delta"] == 27.0
        # --- rejoin: new incarnation, bumped epoch, empty ring.
        srv0b = _server(shard_id=0, epoch=2, capacity=8)
        addrs[0] = srv0b.address
        ss.maybe_rejoin()
        assert ss.shards[0].alive and ss.shards[0].epoch == 2
        assert ss.route(0) == 0  # traffic lands back home
        # The rejoined ring is EMPTY: its weight stays 0 (the dead ring's
        # sums are never inherited); the survivor holds both adds' sums.
        np.testing.assert_allclose(ss.scaled_sums(), [0.0, 12.0])
        kinds = [e["kind"] for e in get_flight_recorder().events()[n0:]]
        assert "shard_dead" in kinds and "shard_rejoin" in kinds
        # Learner-side epoch fence: handles from incarnation 1 never even
        # cross the wire (fleet/sampler.py groups per (shard, epoch)).
        assert handles_epoch == 1 != ss.shards[0].epoch
        srv0b.stop()
    finally:
        ss.close()
        srv1.stop()


def test_shard_chaos_stall_gate_arms_and_waits():
    fs = fleet_chaos.parse_chaos_spec("stall_shard@p2:0.3s")
    target = fleet_chaos.fault_target(fs[0], seed=0, num_actors=2)
    chaos = fleet_chaos.ShardChaos(
        fs, seed=0, num_shard_procs=2, proc_index=target
    )
    chaos.on_seqs_frame()
    t0 = time.monotonic()
    chaos.gate()
    assert time.monotonic() - t0 < 0.05  # phase 1: not due yet
    chaos.on_seqs_frame()  # phase 2: arms the stall
    t0 = time.monotonic()
    chaos.gate()
    assert time.monotonic() - t0 >= 0.25
    other = fleet_chaos.ShardChaos(
        fs, seed=0, num_shard_procs=2, proc_index=1 - target
    )
    other.on_seqs_frame()
    other.on_seqs_frame()
    t0 = time.monotonic()
    other.gate()
    assert time.monotonic() - t0 < 0.05  # not its fault


# ----------------------------------------------------- shard TELEM (ISSUE 13)
@pytest.fixture
def fresh_obs(monkeypatch):
    """A fresh process registry + remote mirror for the duration of one
    test: the TELEM fold and the /health rules read process singletons,
    and an earlier test's armed staleness set_fn (its server long closed)
    would otherwise fire the telem_stale rule forever."""
    monkeypatch.setattr(obs_registry, "_REGISTRY", obs_registry.Registry())
    monkeypatch.setattr(obs_registry, "_MIRROR", obs_registry.RemoteMirror())
    return obs_registry.get_registry(), obs_registry.get_remote_mirror()


def test_shard_telem_folds_with_staleness_and_epoch_rearm(fresh_obs):
    """Leg 1 of the health plane: a shard proc's TELEM push lands in the
    learner's RemoteMirror under shard=/host= labels (idempotently keyed
    — a respawned incarnation UPDATES its slot), the per-shard staleness
    gauge grows while the shard is silent, and an epoch-bumped rejoin
    RESTARTS the clock so a fresh incarnation's absorb phase never reads
    as wedged (the actor warm-up cadence fix, carried to the shard
    tier)."""
    reg, mirror = fresh_obs
    srv = ShardServer(
        ReplayShard(8, alpha=1.0, shard_id=0),
        epoch=1, seed=0, telem_every=0.01,
    ).start()
    addrs = {0: srv.address}
    ss = RemoteShardSet(
        1, lambda sid: addrs[sid],
        wire_config=wire.WireConfig(), rejoin_interval_s=0.0,
    )
    try:
        # First exchange: HELLO arms the staleness clock, and the forced
        # post-HELLO TELEM push folds on this exchange's reply read.
        ss.add(0, {"staged": _np_staged()})
        sources = mirror.sources()
        assert len(sources) == 1
        key, labels, snap = sources[0]
        assert key == "shard:0"
        assert labels["shard"] == "0" and labels["host"]
        # The pushes ride AFTER replies, so a snapshot folds on the NEXT
        # exchange's read — and the cadence gate makes WHICH rider
        # carries a given snapshot scheduling-dependent: poll adds until
        # a fold with real occupancy lands instead of assuming the
        # schedule (a descheduled handler shifts it by one exchange).
        occ = []
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline:
            time.sleep(0.03)  # past the 0.01 s cadence: the rider is due
            ss.add(0, {"staged": _np_staged()})
            snap = mirror.sources()[0][2]
            occ = snap.get("r2d2dpg_replay_shard_occupancy", {}).get(
                "samples", []
            )
            if occ and occ[0]["value"] >= 3.0:
                break
        assert occ and occ[0]["value"] >= 3.0
        assert occ[0]["labels"]["shard"] == "0"
        # The fold's own accounting must NOT ride the push (echo
        # suppression): the learner's staleness gauge stays live-only.
        assert "r2d2dpg_shard_telem_staleness_seconds" not in snap
        # Same echo class, whole learner-owned FAMILIES: with a shared
        # registry (this very test) the proc-wide slice would push a
        # frozen copy of e.g. the learner's wait histogram back under
        # shard= attribution — and /health's learner_starving would keep
        # judging the dead mirrored sample after the live one recovered.
        reg.histogram("r2d2dpg_sampler_wait_seconds").observe(99.0)
        reg.gauge("r2d2dpg_health_status").set(1.0)
        deadline = time.monotonic() + 5
        while time.monotonic() < deadline:
            time.sleep(0.03)
            ss.add(0, {"staged": _np_staged()})
            snap = mirror.sources()[0][2]
            if "r2d2dpg_replay_shard_occupancy" in snap:
                break
        assert "r2d2dpg_sampler_wait_seconds" not in snap
        assert "r2d2dpg_health_status" not in snap
        stale = reg.get("r2d2dpg_shard_telem_staleness_seconds").labels(
            shard="0"
        )
        # Silence makes the gauge GROW — a wedged shard is visibly
        # stale, never a silently flat mirrored series.
        s0 = stale.value
        time.sleep(0.3)
        assert stale.value >= s0 + 0.25
        # --- respawn under a bumped epoch: new server, same shard id.
        srv2 = ShardServer(
            ReplayShard(8, alpha=1.0, shard_id=0),
            epoch=2, seed=0, telem_every=0.01,
        ).start()
        addrs[0] = srv2.address
        srv.stop()
        ss.add(0, {"staged": _np_staged()})  # torn conn -> re-dial -> HELLO
        assert ss.shards[0].epoch == 2
        # The incarnation's HELLO re-armed the clock: staleness restarted
        # well below the dead incarnation's accumulated silence.
        assert stale.value < 0.25
        assert len(mirror.sources()) == 1  # same key, updated in place
        srv2.stop()
    finally:
        ss.close()
        srv.stop()


def test_shard_telem_malformed_dropped_without_connection_loss(fresh_obs):
    """A malformed TELEM frame on a shard leg costs one flight event,
    never the connection: the tolerant reply read keeps the exchange
    alive, and a payload that contradicts its connection's shard id is
    malformed by definition (identity comes from the socket, so a
    confused frame cannot relabel another shard's series)."""
    reg, mirror = fresh_obs
    ss = RemoteShardSet(
        1, lambda sid: None, wire_config=wire.WireConfig()
    )
    rs = ss.shards[0]
    a, b = socket.socketpair()
    n0 = len(get_flight_recorder().events())
    try:
        a.settimeout(10)
        # Three TELEM pushes ahead of the real reply: garbage, a wrong
        # shard claim, then a WELL-FORMED one; the ACK follows.
        transport.send_frame(
            b, transport.K_TELEM, transport.pack_obj(["not", "a", "dict"])
        )
        transport.send_frame(
            b,
            transport.K_TELEM,
            transport.pack_obj({"shard": 5, "snapshot": {}}),
        )
        transport.send_frame(
            b,
            transport.K_TELEM,
            transport.pack_obj(
                {"shard": 0, "epoch": 1, "host": "h", "snapshot": {}}
            ),
        )
        transport.send_frame(
            b, transport.K_ACK, transport.pack_obj({"code": "ok"})
        )
        kind, _payload = rs._recv("ingest", a)
        assert kind == transport.K_ACK  # the reply survived all three
        drops = [
            e
            for e in get_flight_recorder().events()[n0:]
            if e["kind"] == "shard_telem_malformed"
        ]
        assert len(drops) == 2  # one per malformed frame, none for the good
        assert [s[0] for s in mirror.sources()] == ["shard:0"]
    finally:
        a.close()
        b.close()
        ss.close()


def test_stall_shard_staleness_health_degraded_then_ok(fresh_obs):
    """The stall drill as the /health fixture: mid-``stall_shard`` the
    shard answers nothing, so its TELEM staleness crosses the threshold
    and ``GET /health`` reads ``degraded`` with a ``telem_stale`` finding
    naming the shard; once the gate lifts and the next exchange folds the
    buffered push, the verdict recovers to ``ok`` — and both transitions
    are durable flight events."""
    reg, mirror = fresh_obs
    faults = fleet_chaos.parse_chaos_spec("stall_shard@p2:1.2s")
    chaos = fleet_chaos.ShardChaos(
        faults, seed=0, num_shard_procs=1, proc_index=0
    )
    srv = ShardServer(
        ReplayShard(16, alpha=1.0, shard_id=0),
        epoch=1, seed=0, chaos=chaos, telem_every=0.01,
    ).start()
    addrs = {0: srv.address}
    ss = RemoteShardSet(
        1, lambda sid: addrs[sid],
        wire_config=wire.WireConfig(), rejoin_interval_s=0.0,
    )
    engine = obs.HealthEngine(
        obs.HealthConfig(
            telem_stale_after_s=0.3, learner_wait_p99_s=1e9,
            eviction_churn_per_s=1e18,
        ),
        registry=reg,
        mirror=mirror,
    )
    n0 = len(get_flight_recorder().events())
    try:
        ss.add(0, {"staged": _np_staged()})  # frame 1: TELEM armed + folded
        assert engine.evaluate()["verdict"] == "ok"
        # Frame 2 arms the stall: the gated ack parks this add for the
        # stall's duration, during which the shard pushes nothing.
        blocked = threading.Thread(
            target=lambda: ss.add(0, {"staged": _np_staged()}), daemon=True
        )
        t_stall = time.monotonic()
        blocked.start()
        time.sleep(0.7)  # mid-stall, well past the 0.3 s threshold
        res = engine.evaluate()
        stale = [f for f in res["findings"] if f["rule"] == "telem_stale"]
        assert res["verdict"] == "degraded"
        assert stale and "shard 0" in stale[0]["detail"]
        blocked.join(timeout=10)
        assert time.monotonic() - t_stall >= 1.0  # the gate really held
        # Recovery: the post-stall ack's TELEM rider folds on the next
        # exchange, resetting the staleness clock.
        ss.add(0, {"staged": _np_staged()})
        res = engine.evaluate()
        assert res["verdict"] == "ok"
        verdicts = [
            (e.get("previous"), e["verdict"])
            for e in get_flight_recorder().events()[n0:]
            if e["kind"] == "health_verdict"
        ]
        assert (None, "ok") in verdicts  # armed
        assert ("ok", "degraded") in verdicts  # degraded during the stall
        assert ("degraded", "ok") in verdicts  # recovered after it
        assert reg.get("r2d2dpg_health_status").value == 0.0
    finally:
        ss.close()
        srv.stop()


# --------------------------------------------------------------- chaos e2e
@pytest.mark.chaos
def test_chaos_kill_shard_stall_and_partition_e2e(tmp_path, fresh_obs):
    """The acceptance drill (non-slow, 2 actors x 2 REAL shard procs):
    ``stall_shard`` + ``partition_shard`` + ``kill_shard`` in one run —
    the run completes its full phase schedule, counters stay monotone,
    zero sheds and zero false reaps through the stall, the dead shard's
    quota renormalizes to the survivor, and after the supervisor's
    backoff restart the shard rejoins EMPTY under a bumped epoch, serves
    traffic on both legs, and fences stale-epoch write-backs.

    The ISSUE 13 health-plane half rides the same run: every shard's
    ring series + staleness gauge in ONE merged scrape (shard-proc TELEM
    at 0.05 s cadence), ``/health`` degraded with a ``shards_down``
    finding during the kill window and ``ok`` after the rejoin, and the
    trace plane (rate 1.0) yielding complete learner->shard->learner
    chains fused into one timeline by ``obs.flight merge --trace-out``."""
    import queue as _q

    from r2d2dpg_tpu.fleet import FleetConfig, SamplerLearner
    from r2d2dpg_tpu.fleet.transport import (
        K_ACK,
        K_HELLO,
        K_SEQS,
        pack_hello,
        recv_frame,
        send_frame,
        send_frame_parts,
    )
    from r2d2dpg_tpu.training.pipeline import split_state

    SEED = 2  # pinned: stall->proc0, partition->shard1, kill->proc0
    N_TRAIN = 6
    spec = "stall_shard@p1:0.6s,partition_shard@p1,kill_shard@p2"
    faults = fleet_chaos.parse_chaos_spec(spec)
    assert fleet_chaos.fault_target(faults[2], SEED, 2) == 0  # kill proc 0
    assert fleet_chaos.fault_target(faults[1], SEED, 2) == 1  # partition 1

    import dataclasses as dc

    import jax

    trainer = PENDULUM_TINY.build()
    state = trainer.init()
    _, lstate = split_state(state)
    # A stored row IS the staged-batch template (leaves [1, L, ...]):
    # synthetic actors emit exactly the structure the learn program
    # expects, without paying a collect-program compile this drill does
    # not test.
    template = jax.device_get(trainer.arena.gather(lstate.arena, np.zeros(1, np.int32)))

    def synth_staged(rng, b=4):
        data = jax.tree_util.tree_map(
            lambda buf: (
                rng.normal(size=(b,) + np.shape(buf)[1:]).astype(buf.dtype)
                if buf.dtype.kind == "f"
                else np.zeros((b,) + np.shape(buf)[1:], buf.dtype)
            ),
            template,
        )
        data = dc.replace(
            data,
            discount=np.ones_like(data.discount),
            reset=np.zeros_like(data.reset),
        )
        return StagedSequences(
            seq=data, priorities=rng.uniform(0.5, 4.0, size=b)
        )

    tier = ShardProcTier(
        num_shards=2,
        num_procs=2,
        capacity_per_shard=128,
        alpha=trainer.config.priority_alpha,
        prioritized=True,
        dirpath=str(tmp_path / "shards"),
        seed=SEED,
        wire_config=wire.WireConfig(),
        chaos_spec=spec,
        flight_dir=str(tmp_path),
        telem_every=0.05,
        supervisor_config=SupervisorConfig(
            backoff_base_s=0.2, poll_s=0.05
        ),
    )
    learner = SamplerLearner(
        trainer,
        FleetConfig(num_actors=2, idle_timeout_s=60),
        num_shards=2,
        shard_set=tier.shard_set,
    )
    engine = fleet_chaos.ChaosEngine(
        faults, seed=SEED, num_actors=2, server=learner.server,
        shard_tier=tier,
    )
    # The /health verdict engine over the run's registry+mirror: every
    # rule but shards_down disarmed (generous thresholds) so the ONE
    # deterministic degraded window — the kill -> backoff-restart gap —
    # is what the verdict sequence pins.
    health = obs.HealthEngine(
        obs.HealthConfig(
            learner_wait_p99_s=1e9,
            telem_stale_after_s=1e9,
            eviction_churn_per_s=1e18,
            occupancy_skew_min_mean=1e18,
            # ISSUE 18 quality rules disarmed too: this drill churns a
            # tiny ring far faster than its starved learner samples, so
            # untrained_churn would (correctly) stay degraded past the
            # rejoin and blur the one shards_down window under test.
            quality_min_lag_count=1e18,
            quality_ess_floor=0.0,
            quality_churn_min_evictions=1e18,
            quality_actor_skew_min_mean=1e18,
            expected_shard_procs=2,
        ),
        registry=fresh_obs[0],
        mirror=fresh_obs[1],
    )
    health_findings = []

    def phase_hook(p):
        engine.on_phase(p)
        if p == 2:
            # kill_shard just landed on proc 0: the supervisor's backoff
            # (0.2 s) guarantees a down window — catch the shards_down
            # verdict inside it.
            deadline = time.monotonic() + 5
            while time.monotonic() < deadline:
                res = health.evaluate()
                down = [
                    f for f in res["findings"]
                    if f["rule"] == "shards_down"
                ]
                if down:
                    health_findings.append((res["verdict"], down[0]))
                    break
                time.sleep(0.01)

    tier.start()
    address = learner.start()
    stop = threading.Event()

    def actor_loop(actor_id):
        # A wire-real synthetic actor: HELLO + streamed SEQS frames (the
        # collect compile is not what this drill tests); param pushes are
        # read and discarded.
        rng = np.random.default_rng(100 + actor_id)
        try:
            sock = transport.connect(address, read_deadline_s=60)
            packer = wire.TreePacker(wire.WireConfig())
            send_frame(
                sock,
                K_HELLO,
                pack_hello(
                    {
                        "actor_id": actor_id,
                        **wire.negotiation_fields(wire.WireConfig()),
                    }
                ),
            )
            while recv_frame(sock)[0] != K_ACK:
                pass
            phase = 0
            while not stop.is_set():
                send_frame_parts(
                    sock,
                    K_SEQS,
                    packer.pack(
                        {
                            "phase": phase,
                            "param_version": 0,
                            "env_steps_delta": 16.0,
                            "ep_return_sum": -1.0,
                            "ep_count": 1.0,
                            "staged": synth_staged(rng),
                        }
                    ),
                )
                while recv_frame(sock)[0] != K_ACK:
                    pass
                phase += 1
            sock.close()
        except Exception:  # noqa: BLE001 — teardown cuts the socket
            pass

    threads = [
        threading.Thread(target=actor_loop, args=(i,), daemon=True)
        for i in range(2)
    ]
    logged = []
    n0 = len(get_flight_recorder().events())
    s0 = len(get_flight_recorder().spans())
    try:
        for t in threads:
            t.start()
        state = learner.run(
            N_TRAIN,
            state=state,
            log_every=2,
            metrics_fn=lambda p, s: logged.append((p, dict(s))),
            phase_fn=phase_hook,
            trace_sample=1.0,
        )
    finally:
        stop.set()
        learner.close()
        for t in threads:
            t.join(timeout=10)

    # Run completed its exact schedule despite a shard dying mid-run.
    assert int(state.train.step) == N_TRAIN * trainer.config.learner_steps
    stats = learner.stats()
    assert stats["train_phases"] == N_TRAIN
    assert stats["sheds"] == 0  # zero sheds through the stall
    assert stats["shard_deaths"] >= 1
    assert engine.unfired() == ()  # kill + partition both landed
    # Monotone counters through stall, partition, death, re-route.
    env_steps = [s["env_steps"] for _, s in logged]
    assert env_steps == sorted(env_steps) and env_steps[-1] > 0
    evs = get_flight_recorder().events()[n0:]
    kinds = [e["kind"] for e in evs]
    assert "shard_dead" in kinds
    assert "shard_quota_renorm" in kinds  # survivors re-quota'd on death
    # Zero false reaps: nothing declared an actor or shard peer dead.
    assert "peer_dead" not in kinds
    # --- epoch-fenced rejoin: the killed proc's shard comes back under a
    # bumped epoch and serves BOTH legs.
    ss = tier.shard_set
    deadline = time.monotonic() + 20
    while time.monotonic() < deadline and not ss.shards[0].alive:
        ss.maybe_rejoin()
        time.sleep(0.05)
    try:
        assert ss.shards[0].alive and ss.shards[0].epoch == 2
        occ_before = ss.shards[0].occupancy
        rng = np.random.default_rng(0)
        ss.add(0, {"staged": synth_staged(rng), "actor_id": 0})
        # Restarted shard serves the ingest leg (occupancy grew by B
        # relative to whatever it re-absorbed since rejoin)...
        assert ss.shards[0].occupancy == occ_before + 4
        # ...and the sampler leg.
        resp = ss.shards[0].sample(2, req_id=99)
        assert resp["epoch"] == 2
        # Stale-epoch PRIO against the new incarnation: ignored loudly.
        stale = ss.shards[0].write_back(
            resp["slots"], resp["gens"], np.ones(2, np.float32), epoch=1
        )
        assert stale["applied"] == 0 and stale["stale"]
        # --- health plane (ISSUE 13): degraded with a shards_down
        # finding during the kill window, ok after the rejoin.
        assert health_findings, "no shards_down verdict in the kill window"
        verdict, finding = health_findings[0]
        assert verdict == "degraded" and finding["value"] == 1.0
        assert health.evaluate()["verdict"] == "ok"
        # --- ONE merged scrape carries every shard's ring series (from
        # the shard procs' TELEM pushes) AND both staleness gauges.
        reg, mirror = fresh_obs
        assert {k for k, _, _ in mirror.sources()} >= {"shard:0", "shard:1"}
        text = obs.render_prometheus(
            obs.merge_remote(reg.snapshot(), mirror.sources())
        )
        for sid in ("0", "1"):
            for metric in (
                "r2d2dpg_replay_shard_occupancy",
                "r2d2dpg_replay_shard_priority_sum",
                "r2d2dpg_replay_shard_evictions_total",
                "r2d2dpg_shard_telem_staleness_seconds",
            ):
                assert re.search(
                    metric + r'\{[^}]*shard="' + sid + '"', text
                ), f"{metric}{{shard={sid}}} missing from the merged scrape"
    finally:
        tier.stop()
    # The shard-side stall drill left durable evidence in its dump, and
    # every scheduled shard-proc fault fired (the unfired contract).
    assert (
        fleet_chaos.shard_faults_unfired(
            faults, str(tmp_path), seed=SEED, num_shard_procs=2
        )
        == ()
    )
    restarts = tier.restarts_total
    assert restarts >= 1  # the supervisor's ladder did the rejoin
    # --- cross-boundary tracing (ISSUE 13 leg 2): every phase was
    # sampled (rate 1.0); the learner chain's contiguous hops sum to its
    # end-to-end within 10%, and the shard procs stamped their own
    # contiguous req_receive -> shard_draw -> batch_encode chains into
    # the SAME trace ids, dumped as trace_shard<i>.jsonl at SIGTERM.
    spans = get_flight_recorder().spans()[s0:]
    by_id = {}
    for s in spans:
        by_id.setdefault(s["trace_id"], {})[s["hop"]] = s
    chains = {
        tid: h
        for tid, h in by_id.items()
        if {"sample_req", "batch_return", "learn"} <= set(h)
    }
    assert len(chains) == N_TRAIN
    for h in chains.values():
        end_to_end = (
            h["learn"]["t_wall"] + h["learn"]["dur_s"]
            - h["sample_req"]["t_wall"]
        )
        total = sum(
            h[k]["dur_s"] for k in ("sample_req", "batch_return", "learn")
        )
        assert abs(total - end_to_end) <= 0.1 * end_to_end
    shard_spans = []
    for path in glob.glob(str(tmp_path / "trace_shard*.jsonl")):
        with open(path) as f:
            for line in f:
                s = json.loads(line)
                s["file"] = path.rsplit("/", 1)[-1]
                shard_spans.append(s)
    shard_chains = {}
    for s in shard_spans:
        shard_chains.setdefault(
            (s["file"], s["trace_id"]), {}
        )[s["hop"]] = s
    complete = {
        k: h
        for k, h in shard_chains.items()
        if set(SHARD_HOPS) <= set(h) and k[1] in chains
    }
    assert complete, "no complete shard-side chain matched a learner trace"
    for (_, tid), h in complete.items():
        # Contiguous by construction, nested inside the learner's
        # sample_req window (both clocks are this host's wall clock).
        assert (
            h["req_receive"]["t_wall"] + h["req_receive"]["dur_s"]
            == h["shard_draw"]["t_wall"]
        )
        assert (
            h["shard_draw"]["t_wall"] + h["shard_draw"]["dur_s"]
            == h["batch_encode"]["t_wall"]
        )
        shard_total = sum(h[k]["dur_s"] for k in SHARD_HOPS)
        assert shard_total <= chains[tid]["sample_req"]["dur_s"] + 0.05
    # --- one fused Perfetto timeline: learner spans (trace.json) +
    # shard-proc span rings, merged by the run-dir CLI.
    from r2d2dpg_tpu.obs.flight import main as flight_main

    get_flight_recorder().dump_trace(str(tmp_path / "trace.json"))
    flight_main(
        ["merge", str(tmp_path), "--trace-out", str(tmp_path / "fused.json")]
    )
    with open(tmp_path / "fused.json") as f:
        fused = json.load(f)
    names = {e["name"] for e in fused["traceEvents"]}
    assert {"sample_req", "batch_return", "learn"} <= names
    assert set(SHARD_HOPS) <= names
    stamped = {
        e["args"].get("file")
        for e in fused["traceEvents"]
        if e["name"] in SHARD_HOPS
    }
    assert all(s and s.startswith("trace_shard") for s in stamped)
