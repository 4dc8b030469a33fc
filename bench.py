"""Headline benchmark: learner steps/sec/chip (BASELINE.json `metric`).

Measures the sustained rate of the full R2D2-DPG learner step — prioritized
sample from the HBM arena, LSTM burn-in of all four nets, n-step targets,
IS-weighted critic + actor updates, Polyak, Pallas priority write-back — at
config-#3 (walker) shapes: batch 64, obs 24, act 6, hidden 256, with the
sequence recipe taken live from ``WALKER_R2D2.agent`` (currently burn-in 20
+ unroll 20 + n-step 3 -> seq 43; a recorded recipe flip moves this
measurement with it).

``python bench.py`` is ONE process that measures on the chip and prints ONE
JSON line: {"metric", "value", "unit", "device": {"platform", "kind",
"count"}, "pipeline"}.  It starts and stops no other process.  Where JAX
resolves anything but a TPU it exits non-zero and prints no record: a CPU
rate under a device metric's name is worse than no number.  The ``fleet`` /
``serve`` subcommands are host-side probes (actor ingest, wire bytes,
router structure) that pin their subprocess legs to the CPU and say so in
their records.

Usage:
    python bench.py                # measure at the flagship config's dtype
                                   # (WALKER_R2D2.compute_dtype)
    python bench.py bfloat16       # explicit activation-dtype override
    python bench.py float32
    python bench.py fleet          # actor-fleet ingest probe (CPU, local):
                                   # actor-count vs arena-add throughput
                                   # vs the single-process collector
"""

from __future__ import annotations

import json
import os
import signal
import subprocess
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))
METRIC = "learner_steps_per_sec_per_chip"


def _drain_group(proc) -> None:
    """SIGTERM-first teardown of a whole process GROUP (legs started with
    ``start_new_session=True``).  The fleet legs' train.py spawns actor
    and standalone shard subprocesses; signalling the leader alone
    orphans them on the timeout path (a SIGTERMed leader never runs its
    finally-block supervisor teardown, and a shard proc has no
    learner-death exit of its own — it would keep listening on its
    socket and stealing CPU from every later contention-sensitive leg).
    The group signal reaches each member directly: shard procs dump
    their flight ring on SIGTERM, actors just exit."""
    try:
        os.killpg(proc.pid, signal.SIGTERM)
    except OSError:
        proc.terminate()
    try:
        proc.wait(timeout=20)
    except subprocess.TimeoutExpired:
        try:
            os.killpg(proc.pid, signal.SIGKILL)
        except OSError:
            proc.kill()
        proc.wait()


def _run_leg_cmd(cmd, env):
    """subprocess.run(capture_output, timeout=900) equivalent for fleet
    legs, with process-GROUP teardown on timeout (the spawned train.py
    forks actor/shard subprocesses — see _drain_group).  Output spools
    to temp FILES, not pipes: a pipe would deadlock a chatty child
    (64 KiB buffer), and worse, a leader that dies abnormally leaves its
    orphans holding the pipe open, so communicate() would block on a
    DEAD leader until the full timeout.  Returns (returncode, stdout,
    stderr); returncode None means the 900s budget expired and the whole
    group was reaped."""
    with tempfile.TemporaryFile(mode="w+") as out_f, tempfile.TemporaryFile(
        mode="w+"
    ) as err_f:
        proc = subprocess.Popen(
            cmd, env=env, cwd=HERE, stdout=out_f, stderr=err_f,
            text=True, start_new_session=True,
        )
        timed_out = False
        try:
            proc.wait(timeout=900)
        except subprocess.TimeoutExpired:
            _drain_group(proc)
            timed_out = True
        if not timed_out and proc.returncode != 0:
            # A leader that died WITHOUT running its finally-block
            # teardown (SIGKILL/OOM/segfault) leaves its actor/shard
            # subprocesses alive in the session; sweep the group
            # best-effort.  Clean exits (rc 0) ran their own teardown —
            # and their reaped pgid could already be recycled, so don't
            # signal it.
            try:
                os.killpg(proc.pid, signal.SIGTERM)
            except OSError:
                pass
        out_f.seek(0)
        err_f.seek(0)
        stdout = out_f.read()
        stderr = err_f.read()
    return (None if timed_out else proc.returncode), stdout, stderr


def _pipeline_probe(backend: str) -> dict:
    """Pipelined vs phase-locked executor throughput on the host-env config.

    Walker-walk through the host pool (the config whose MuJoCo steps the
    pipelined executor hides under learner compute), at reduced probe
    shapes so the probe stays ~1 min on CPU: E=8 envs, stride 10, K=2
    updates/phase, batch 32, hidden 128, seq 11.  Reports learner steps/s
    under both schedules plus the executor's overlap fraction and
    learner-wait p50/p99.  Never raises: on any failure (e.g. dm_control
    cannot construct — broken EGL) it falls back to the pure-JAX pendulum
    env so the schedule comparison still lands, and stamps the error.
    """
    import jax

    def measure(env_factory, env_name: str) -> dict:
        from r2d2dpg_tpu.agents.ddpg import AgentConfig, R2D2DPG
        from r2d2dpg_tpu.models import ActorNet, CriticNet
        from r2d2dpg_tpu.training.pipeline import (
            PipelineConfig,
            PipelineExecutor,
        )
        from r2d2dpg_tpu.training.trainer import Trainer, TrainerConfig

        tcfg = TrainerConfig(
            num_envs=8,
            stride=10,
            learner_steps=2,
            batch_size=32,
            capacity=4096,
            min_replay=32,
            prioritized=True,
        )

        def prep():
            # A FRESH env + trainer per schedule leg: host pools are
            # stateful, so reusing one env would leave the second leg's
            # device state desynchronized from physics the first leg
            # advanced.  Same seeds -> identical starting states.
            env = env_factory()
            acfg = AgentConfig(burnin=5, unroll=5, n_step=1)
            actor = ActorNet(
                action_dim=env.spec.action_dim, hidden=128, use_lstm=True
            )
            critic = CriticNet(hidden=128, use_lstm=True)
            trainer = Trainer(env, R2D2DPG(actor, critic, acfg), tcfg)
            state = trainer.init()
            for _ in range(trainer.window_fill_phases):
                state = trainer.collect_phase(state)
            for _ in range(trainer.replay_fill_phases):
                state = trainer.fill_phase(state)
            return trainer, state

        n = 6
        trainer, state = prep()
        ex_off = PipelineExecutor(trainer, PipelineConfig(enabled=False))
        state = ex_off.run_train_phases(state, 1)  # compile, untimed
        jax.block_until_ready(state.train.step)
        t0 = time.perf_counter()
        state = ex_off.run_train_phases(state, n)
        jax.block_until_ready(state.train.step)
        dt_off = time.perf_counter() - t0

        trainer_on, state_on = prep()
        ex_on = PipelineExecutor(trainer_on, PipelineConfig(enabled=True))
        state_on = ex_on.run_train_phases(state_on, 1)  # compile, untimed
        jax.block_until_ready(state_on.train.step)
        state_on = ex_on.run_train_phases(state_on, n)
        stats = ex_on.stats()

        locked = n * tcfg.learner_steps / dt_off
        piped = stats["learner_steps_per_sec"]
        return {
            "config": f"{env_name} E8 stride10 K2 b32 h128 seq11",
            "backend": backend,
            "phase_locked_learner_steps_per_sec": round(locked, 2),
            "pipelined_learner_steps_per_sec": round(piped, 2),
            "speedup": round(piped / max(locked, 1e-9), 3),
            "overlap_fraction": round(stats["overlap_fraction"], 3),
            "learner_wait_p50_ms": round(stats["learner_wait_p50_ms"], 2),
            "learner_wait_p99_ms": round(stats["learner_wait_p99_ms"], 2),
            "collect_wait_p50_ms": round(stats["collect_wait_p50_ms"], 2),
            "collect_wait_p99_ms": round(stats["collect_wait_p99_ms"], 2),
        }

    out: dict = {}
    try:
        # The fallback wraps the WHOLE measurement, not just env
        # construction: dm_control failures can first surface inside the
        # pool's first reset (trainer.init) or mid-step.
        try:
            from r2d2dpg_tpu.envs.dmc_host import DMCHostEnv

            out.update(
                measure(
                    lambda: DMCHostEnv("walker", "walk", action_repeat=2),
                    "walker-walk(host-pool)",
                )
            )
        except Exception as e:
            from r2d2dpg_tpu.envs.pendulum import Pendulum

            out["env_fallback"] = f"{type(e).__name__}: {e}"[-200:]
            out.update(measure(Pendulum, "pendulum(fallback)"))
    except Exception as e:
        out["error"] = f"{type(e).__name__}: {e}"[-300:]
    return out


def _fleet_probe(actor_counts=(1, 2, 3), phases: int = 12) -> None:
    """``python bench.py fleet`` — actor-count vs arena-add throughput +
    bytes-on-wire, on the negotiated fast lane (ISSUE 5).

    Runs entirely on THIS host's CPU: the question is whether supervised
    out-of-process actors
    (fleet/) can feed the learner's arena at least as fast as the
    single-process phase-locked collector does, per docs/FLEET.md's
    acceptance bar.  Config: ``pendulum_r2d2`` widened to 32 envs/actor
    (``--num-envs`` is a structural flag, so learner and actors stay
    matched) — per-phase collect work heavy enough that serializing it
    after the learner update (the phase-locked schedule) is a real tax;
    at the stock 4 envs the probe mostly measures learner-side XLA core
    contention on this 2-core box, not ingest capacity.

    Wire: the fleet legs run the byte fast lane (bf16 + zlib frames —
    ``fleet/wire.py``) at drain_coalesce=1, and a 3-actor
    ``fleet_f32_control`` leg runs f32/none — behaviorally the PR 4
    pickle wire (bit-exact payloads) — as the bytes-per-sequence
    denominator for ``bytes_reduction_vs_f32``.  On this 2-core box the
    learner STARVES at every fleet size (actor collection is the
    bottleneck: learner_wait_p99 ~0.5 s), so the headline claim is the
    second acceptance clause — fewer bytes per sequence at equal seqs/s —
    not a seqs/s multiple.  A separate ``fleet_coalesce`` leg runs
    drain_coalesce=4 to record the coalesced schedule's behavior
    (power-of-two width buckets; each bucket's one-time drain compile is
    a real mid-run cost at this box's 12-phase scale, which is why
    coalescing is not in the headline lane here).

    Rates are STEADY-STATE: both legs exclude compile (first phase
    untimed); the fleet leg additionally excludes actor subprocess spawn
    and replay fill (``FleetLearner`` stats' train window, which opens
    once the first drain-learn has executed).  Sheds, if any, are real
    steady-state sheds: the ingest server suppresses the historical
    one-shed-per-actor startup artifact (every actor's pending put used
    to time out while the first drain-learn compiled) by holding
    queue-full waits to ``startup_shed_grace_s`` until that compile has
    executed (docs/FLEET.md "Startup grace").  Prints ONE JSON line;
    ``vs_baseline`` is the 3-actor sustained rate over the
    single-process collector's.
    """
    import jax

    jax.config.update("jax_platforms", "cpu")
    os.environ.setdefault("R2D2DPG_PALLAS_INTERPRET", "1")

    from r2d2dpg_tpu.configs import get_config
    from r2d2dpg_tpu.fleet import (
        ActorSupervisor,
        FleetConfig,
        FleetLearner,
        WireConfig,
        default_actor_argv,
    )

    import dataclasses

    cfg_name = "pendulum_r2d2"
    n_envs = 64
    cfg = get_config(cfg_name)
    cfg = dataclasses.replace(
        cfg, trainer=dataclasses.replace(cfg.trainer, num_envs=n_envs)
    )
    fast_wire = WireConfig(encoding="bf16", compress="zlib")

    def baseline_leg() -> float:
        trainer = cfg.build()
        state = trainer.init()
        for _ in range(trainer.window_fill_phases):
            state = trainer.collect_phase(state)
        for _ in range(trainer.replay_fill_phases):
            state = trainer.fill_phase(state)
        state, _ = trainer.train_phase(state)  # compile, untimed
        jax.block_until_ready(state.train.step)
        t0 = time.perf_counter()
        for _ in range(phases):
            state, _ = trainer.train_phase(state)
        jax.block_until_ready(state.train.step)
        return phases * n_envs / (time.perf_counter() - t0)

    def fleet_leg(
        num_actors: int, wire_cfg: "WireConfig", coalesce: int
    ) -> dict:
        trainer = cfg.build()
        # Throughput posture, not liveness posture: a long shed_after_s
        # parks surplus actors on backpressure (blocked in the ack wait)
        # instead of shedding — on a core-starved box, shed batches are
        # re-collected and that wasted collect work steals cycles from the
        # very drain being measured.  publish_every>1 similarly keeps the
        # per-phase param device_get off the measured drain cadence.
        learner = FleetLearner(
            trainer,
            FleetConfig(
                num_actors=num_actors,
                queue_depth=4,
                shed_after_s=5.0,
                publish_every=4,
                wire=wire_cfg,
                drain_coalesce=coalesce,
            ),
        )
        address = learner.start()
        supervisor = ActorSupervisor(
            lambda i: default_actor_argv(
                i,
                config_name=cfg_name,
                address=address,
                num_actors=num_actors,
                seed=cfg.trainer.seed,
                extra=[
                    "--num-envs", str(n_envs),
                    "--wire", wire_cfg.encoding,
                    "--compress", wire_cfg.compress,
                ],
            ),
            num_actors,
        )
        try:
            supervisor.start()
            learner.run(phases, log_every=0)
        finally:
            supervisor.stop()
            learner.close()
        s = learner.stats()
        return {
            # train_* keys: the steady-state window (startup excluded) —
            # the full-wall rates would understate a short run.
            "arena_add_seqs_per_sec": round(
                s.get("train_arena_add_seqs_per_sec", 0.0), 2
            ),
            "learner_steps_per_sec": round(
                s.get("train_learner_steps_per_sec", 0.0), 2
            ),
            "sheds": s["sheds"],
            "learner_wait_p99_ms": round(s["learner_wait_p99_ms"], 1),
            "bytes_per_seq": round(s["bytes_per_seq"], 1),
            # Bytes crossing into the TRAINING path per trained sequence
            # — the central-drain side of the fleet_sampler comparison
            # (every collected sequence crosses, sampled or not).
            "bytes_per_trained_seq": round(s["bytes_per_trained_seq"], 1),
            "wire_ratio": round(s["wire_ratio"], 3),
            "coalesce_width_mean": round(s["drain_coalesce_width_mean"], 2),
            **_device_cols(s),
            **_quality_cols(s),
        }

    def sampler_leg(
        num_actors: int, num_shards: int, wire_cfg: "WireConfig"
    ) -> dict:
        """One in-network-sampling leg (ISSUE 10, docs/REPLAY.md): same
        fleet, same wire lane, but replay sharded at the ingest edge and
        the learner PULLING batches — only sampled sequences cross the
        sampling boundary into training, so bytes_per_trained_seq is the
        REQ+BATCH+PRIO cost of exactly the trained draws, not the whole
        collected stream."""
        from r2d2dpg_tpu.fleet import SamplerLearner

        trainer = cfg.build()
        learner = SamplerLearner(
            trainer,
            FleetConfig(
                num_actors=num_actors,
                publish_every=4,
                wire=wire_cfg,
            ),
            num_shards=num_shards,
        )
        address = learner.start()
        supervisor = ActorSupervisor(
            lambda i: default_actor_argv(
                i,
                config_name=cfg_name,
                address=address,
                num_actors=num_actors,
                seed=cfg.trainer.seed,
                extra=[
                    "--num-envs", str(n_envs),
                    "--wire", wire_cfg.encoding,
                    "--compress", wire_cfg.compress,
                ],
            ),
            num_actors,
        )
        try:
            supervisor.start()
            learner.run(phases, log_every=0)
        finally:
            supervisor.stop()
            learner.close()
        s = learner.stats()
        return {
            "learner_steps_per_sec": round(
                s.get("train_learner_steps_per_sec", 0.0), 2
            ),
            "sheds": s["sheds"],  # structurally 0: ring eviction, no queue
            "trained_seqs": s["trained_seqs"],
            "collected_seqs": s["collected_seqs"],
            "bytes_per_trained_seq": round(s["bytes_per_trained_seq"], 1),
            "sample_bytes_total": round(s["sample_bytes_total"], 0),
            "replay_occupancy": s["replay_occupancy"],
            "sampler_wait_p99_ms": round(s["sampler_wait_p99_ms"], 1),
            **_device_cols(s),
            **_quality_cols(s),
        }

    rec = {
        "metric": "fleet_arena_add_seqs_per_sec",
        "unit": "seqs/s",
        "config": f"{cfg_name} E{n_envs} K{cfg.trainer.learner_steps} "
        f"x{phases} phases",
        "backend": "cpu",
        "wire": {
            "encoding": fast_wire.encoding,
            "compress": fast_wire.compress,
            "drain_coalesce": 1,
        },
    }
    try:
        baseline = baseline_leg()
        rec["baseline_single_process"] = round(baseline, 2)
        rec["fleet"] = {
            str(n): fleet_leg(n, fast_wire, 1) for n in actor_counts
        }
        # The PR 4-equivalent wire (f32/none, one drain call per batch) at
        # the top actor count: the bytes-reduction denominator AND the
        # seqs/s control for the "at equal seqs/s" clause.
        rec["fleet_f32_control"] = fleet_leg(
            actor_counts[-1], WireConfig(), 1
        )
        # Coalesced schedule probe (drain_coalesce=4, 3 actors): the
        # power-of-two widths are AOT-precompiled by a background thread
        # during absorb and the pull clamp only admits READY widths
        # (fleet/ingest.py), so this leg must record sheds=0 — the
        # ISSUE 9 fix for the mid-run width-compile stalls that shed.
        rec["fleet_coalesce"] = fleet_leg(actor_counts[-1], fast_wire, 4)
        # In-network sampling probe (ISSUE 10): same 3-actor fleet and
        # fast lane, replay sharded at the ingest edge (2 shards: the
        # config's capacity must split evenly; 3 would be refused on
        # indivisibility), learner-pulled batches.  The
        # headline is bytes_per_trained_seq vs the central-drain leg —
        # only sampled sequences cross the sampling boundary — at
        # sheds=0 on BOTH sides (the sampler's are structural).
        rec["fleet_sampler"] = sampler_leg(actor_counts[-1], 2, fast_wire)
        rec["sampler_bytes_reduction_vs_central"] = round(
            rec["fleet"][str(actor_counts[-1])]["bytes_per_trained_seq"]
            / max(rec["fleet_sampler"]["bytes_per_trained_seq"], 1e-9),
            2,
        )
        # Standalone shard tier probe (ISSUE 12): same fleet shape, the
        # 2 shards hosted OUT of process with a kill_shard drill mid-run
        # — bytes/trained-seq across real sockets vs the loopback leg
        # above, plus the kill->requota recovery latency.
        rec["fleet_shard_procs"] = _shard_procs_leg(phases)
        if "bytes_per_trained_seq" in rec["fleet_shard_procs"]:
            rec["shard_procs_bytes_vs_loopback"] = round(
                rec["fleet_shard_procs"]["bytes_per_trained_seq"]
                / max(rec["fleet_sampler"]["bytes_per_trained_seq"], 1e-9),
                2,
            )
        # Policy-driven recovery probe (ISSUE 16): the same 3-actor fleet
        # with --autoscale 1 and a kill_actor drill — the health loop
        # (not the backoff ladder) restores the population, and the leg
        # records the closed loop's kill->spawn latency.
        rec["fleet_autoscale"] = _autoscale_leg(phases)
        # Multi-chip learner probe (ISSUE 9): --learner-dp over a forced
        # 2-virtual-device CPU mesh (subprocess legs), dp=1 vs dp=2 at
        # equal fleet size, through the full train.py CLI wiring.
        rec["fleet_learner_dp"] = {
            "1": _learner_dp_leg(1, phases),
            "2": _learner_dp_leg(2, phases),
        }
        # Full-topology probe (ISSUE 11): actors x shards x dp composed
        # through the CLI, with the lr/batch co-scaling note stamped —
        # see _composed_leg's honesty docstring (single-core contention).
        rec["fleet_composed"] = _composed_leg(phases)
        top_leg = rec["fleet"][str(actor_counts[-1])]
        top = top_leg["arena_add_seqs_per_sec"]
        rec["value"] = top
        rec["vs_baseline"] = round(top / max(baseline, 1e-9), 3)
        rec["vs_f32_wire_seqs"] = round(
            top
            / max(rec["fleet_f32_control"]["arena_add_seqs_per_sec"], 1e-9),
            3,
        )
        rec["bytes_reduction_vs_f32"] = round(
            rec["fleet_f32_control"]["bytes_per_seq"]
            / max(top_leg["bytes_per_seq"], 1e-9),
            2,
        )
        rec["vs_baseline_note"] = (
            "wire change (ISSUE 5): pickle SEQS/PARAMS replaced by "
            "zero-copy schema-cached frames (fleet/wire.py); headline "
            "fleet legs on bf16+zlib at drain_coalesce=1 — the "
            "acceptance claim is bytes_reduction_vs_f32 at equal seqs/s "
            "(vs_f32_wire_seqs), since the learner starves (actor-bound "
            "box), not a seqs/s multiple; fleet_f32_control is the PR 4-"
            "equivalent lane; fleet_coalesce records the drain_coalesce=4 "
            "schedule (ISSUE 9: widths AOT-precompiled during absorb + "
            "ready-width pull clamp, so mid-run width compiles can no "
            "longer stall the drain into sheds — NB with the stalls "
            "gone this starved-learner box forms no queue backlog, so "
            "coalesce_width_mean ~1 means width>1 never engaged here; "
            "the width>1 AOT path's correctness evidence is the bitwise "
            "AOT-vs-jit pin in tests/test_dp_learner.py, and the old "
            "leg's width_mean 3.62 was itself an artifact of the "
            "compile stalls creating the backlog); fleet_learner_dp runs "
            "dp=1 vs dp=2 on 2 FORCED host devices time-slicing this "
            "container's SINGLE CPU core with 3 actor processes — a "
            "dp=2 virtual 'chip' adds zero compute here, so dp=2 BELOW "
            "dp=1 is the expected contention artifact, not a regression; "
            "the dp speedup claim needs real chips (TPU mesh, or a "
            "multi-core box via XLA_FLAGS forced devices) and "
            "learner_dp_gate stamps learner_dp.txt into any such "
            "evidence dir; vs_baseline is container-relative — PR 5's "
            "1.1 was recorded on a 2-core box where actor processes "
            "added real cores, while a single-core container time-slices "
            "the whole fleet against the one-process baseline, so "
            "vs_baseline<1 here is the box, not a fleet regression; "
            "startup shed grace removes the old sheds==num_actors "
            "warmup artifact; fleet_sampler (ISSUE 10) runs the same "
            "3-actor fleet with --replay-shards 2 in-network sampling — "
            "its bytes_per_trained_seq counts the SAMPLE_REQ/BATCH/PRIO "
            "frames of exactly the trained draws (the central leg's "
            "counts every collected+absorbed sequence, fill included), "
            "sampler_bytes_reduction_vs_central is the headline 'only "
            "sampled sequences cross' ratio, and its learner free-runs "
            "(pull-paced, not arrival-paced) so steps/s is not "
            "comparable to the drain legs' arrival-paced rate; every "
            "fleet leg records the device-plane ledger (ISSUE 14: "
            "compile_count / steady_recompiles / peak_hbm_bytes from "
            "obs/device.py), and fleet_composed REFUSES to read as a "
            "clean run unless steady_recompiles == 0 — the aval-"
            "stability claim the PR 9/11 out_shardings pins make, now "
            "measured instead of assumed"
        )
    except Exception as e:  # noqa: BLE001 — the JSON line is the contract
        rec["value"] = 0.0
        rec["error"] = f"{type(e).__name__}: {e}"[-400:]
    print(json.dumps(rec))


def _device_cols(stats: dict) -> dict:
    """The device-plane columns every fleet leg records (ISSUE 14): the
    run's compile ledger and peak HBM, straight off the learner's stats
    (in-process legs) or the parsed ``fleet:`` stats line (subprocess
    legs).  ``steady_recompiles`` is the headline: a nonzero value means
    a learn/drain program's avals re-keyed mid-run — the silent-stall
    bug class the sentinel exists for — and the composed leg refuses to
    record it as a clean run."""
    return {
        "compile_count": stats.get("compile_count", -1.0),
        "steady_recompiles": stats.get("steady_recompiles", -1.0),
        "peak_hbm_bytes": stats.get("peak_hbm_bytes", 0.0),
    }


def _quality_cols(stats: dict) -> dict:
    """The experience-quality columns every fleet leg records (ISSUE 18),
    straight off the learner's stats or the parsed ``fleet:`` line: how
    STALE (policy lag in param versions), how OLD (replay age in phases
    or learner steps), and how DIVERSE (ESS/B of the drawn priorities)
    the experience the run actually trained on was.  -1.0 = the plane
    never armed on that axis (e.g. lag on an --actors 0 run, where no
    wire provenance exists)."""
    return {
        "quality_lag_mean": stats.get("quality_lag_mean", -1.0),
        "quality_lag_p99": stats.get("quality_lag_p99", -1.0),
        "quality_replay_age_mean": stats.get(
            "quality_replay_age_mean", -1.0
        ),
        "quality_ess_frac": stats.get("quality_ess_frac", -1.0),
        "quality_is_saturation": stats.get("quality_is_saturation", -1.0),
    }


def _parse_fleet_stats(stdout: str) -> dict:
    """Parse the end-of-run ``fleet: <k v ...>`` stats line out of a train
    CLI subprocess's stdout — "fleet: ingest on HOST:PORT" and
    "fleet: WARNING ..." share the prefix but not the keys, so only the
    line carrying ``train_phases`` counts.  ONE definition for every
    subprocess bench leg (learner-dp / composed / shard-procs): a stats-
    line format change is a one-site fix."""
    stats = {}
    for line in stdout.splitlines():
        if not line.startswith("fleet: ") or "train_phases" not in line:
            continue
        toks = line[len("fleet: "):].split()
        try:
            stats = {
                toks[i]: float(toks[i + 1])
                for i in range(0, len(toks) - 1, 2)
            }
        except ValueError:
            continue
    return stats


def _learner_dp_leg(dp: int, phases: int) -> dict:
    """One ``--learner-dp`` leg of the fleet probe (ISSUE 9), in a
    SUBPROCESS: the dp mesh needs ``XLA_FLAGS=
    --xla_force_host_platform_device_count=2`` set before jax initializes,
    and forcing virtual devices on the in-process legs would change THEIR
    XLA runtime mid-comparison.  Both dp legs run under the same forced
    2-device env (dp=1 on the degenerate mesh), so the dp=2/dp=1 ratio is
    apples to apples; the probe exercises the real CLI wiring end to end
    (``--actors 3`` feeding a dp-mesh learner) and parses the end-of-run
    ``fleet:`` stats line."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.setdefault("R2D2DPG_PALLAS_INTERPRET", "1")
    flags = env.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        env["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=2"
        ).strip()
    cmd = [
        sys.executable, "-m", "r2d2dpg_tpu.train",
        "--config", "pendulum_r2d2", "--num-envs", "64",
        "--actors", "3", "--learner-dp", str(dp),
        # The in-process legs' throughput posture (see fleet_leg): park
        # surplus actors on backpressure rather than shedding and
        # re-collecting, keep the param device_get off the drain cadence.
        "--fleet-shed-after", "5", "--fleet-publish-every", "4",
        "--phases", str(phases), "--log-every", "0",
    ]
    rc, stdout, stderr = _run_leg_cmd(cmd, env)
    if rc is None:
        return {"error": f"learner-dp leg exceeded 900s: {stderr[-300:]}"}
    stats = _parse_fleet_stats(stdout)
    if not stats:
        return {"error": f"rc={rc}: {stderr[-300:]}"}
    leg = {
        "learner_steps_per_sec": round(
            stats.get("train_learner_steps_per_sec", 0.0), 2
        ),
        "arena_add_seqs_per_sec": round(
            stats.get("train_arena_add_seqs_per_sec", 0.0), 2
        ),
        "sheds": stats.get("sheds", -1.0),
        "learner_wait_p99_ms": round(
            stats.get("learner_wait_p99_ms", 0.0), 1
        ),
        **_device_cols(stats),
    }
    if rc != 0:
        # The stats line printed but the child died in teardown (final
        # save, logger close): numbers are real, the run was NOT clean —
        # the record must say so, not mask it.
        leg["error"] = f"rc={rc}: {stderr[-300:]}"
    return leg


def _composed_leg(phases: int = 12) -> dict:
    """``python bench.py fleet_composed`` — the full-topology run
    (ISSUE 11): ``--actors 2 --replay-shards 2 --learner-dp 2`` through
    the real train.py CLI in a SUBPROCESS (the dp mesh needs
    ``XLA_FLAGS=--xla_force_host_platform_device_count=2`` before jax
    initializes, same discipline as ``_learner_dp_leg``).  Fleet actors
    feed 2 ingest-edge shards and the dp=2 sampler learner pulls
    mesh-sharded batches — the first run where all three scaling axes
    run together.

    The leg also exercises the lr/batch co-scaling recipe the composed
    sampling bandwidth exists for (PAPERS.md 1803.02811): batch doubled
    to 128 with ``--lr-scale-batch 1``, and the resulting scale note is
    stamped into the record.

    HONESTY (carried over from fleet_learner_dp): on this container the
    2 forced host devices time-slice a SINGLE CPU core with 2 actor
    processes, so throughput here is a contention artifact, not a dp
    speedup — the claim this leg records is *the composition runs end to
    end with sheds=0 and monotone counters*; the speedup evidence path
    is a real mesh (learner_dp_gate + topology_gate stamp any such
    evidence dir)."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.setdefault("R2D2DPG_PALLAS_INTERPRET", "1")
    flags = env.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        env["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=2"
        ).strip()
    cmd = [
        sys.executable, "-m", "r2d2dpg_tpu.train",
        "--config", "pendulum_r2d2", "--num-envs", "64",
        "--actors", "2", "--replay-shards", "2", "--learner-dp", "2",
        "--batch-size", "128", "--lr-scale-batch", "1",
        "--fleet-publish-every", "4",
        "--phases", str(phases), "--log-every", "0",
    ]
    rc, stdout, stderr = _run_leg_cmd(cmd, env)
    if rc is None:
        return {"error": f"composed leg exceeded 900s: {stderr[-300:]}"}
    stats = _parse_fleet_stats(stdout)
    lr_note = topo_note = ""
    for line in stdout.splitlines():
        if line.startswith("lr-scale-batch: "):
            lr_note = line[len("lr-scale-batch: "):]
        if line.startswith("topology: "):
            topo_note = line[len("topology: "):]
    if not stats:
        return {"error": f"rc={rc}: {stderr[-300:]}"}
    leg = {
        "topology": topo_note,
        "lr_scale_batch": lr_note,  # the 1803.02811 co-scaling note
        "learner_steps_per_sec": round(
            stats.get("train_learner_steps_per_sec", 0.0), 2
        ),
        "trained_seqs_per_sec": round(
            stats.get("trained_seqs", 0.0) / max(stats.get("wall_s", 0.0), 1e-9),
            2,
        ),
        "trained_seqs": stats.get("trained_seqs", 0.0),
        "bytes_per_trained_seq": round(
            stats.get("bytes_per_trained_seq", 0.0), 1
        ),
        "sheds": stats.get("sheds", -1.0),
        "replay_occupancy": stats.get("replay_occupancy", 0.0),
        "overlap_fraction": round(stats.get("overlap_fraction", 0.0), 3),
        **_device_cols(stats),
    }
    if leg["steady_recompiles"] > 0.0:
        # The composed run is exactly the topology whose donated-chain
        # avals the PR 9/11 out_shardings pins keep stable: ANY steady
        # recompile here is the re-key bug class live, and the record
        # must refuse to read as a clean composition (ISSUE 14).
        leg["error"] = (
            f"steady_recompiles={leg['steady_recompiles']:g} — a "
            "learn/drain program re-keyed mid-run (see steady_recompile "
            "flight events); the composition did not run aval-stable"
        )
    if rc != 0:
        leg["error"] = f"rc={rc}: {stderr[-300:]}"
    return leg


def _shard_procs_leg(phases: int = 12) -> dict:
    """``python bench.py fleet_shard_procs`` — the standalone shard tier
    (ISSUE 12): ``--actors 3 --replay-shards 2 --shard-procs 2`` through
    the real train.py CLI in a subprocess, with a ``kill_shard`` chaos
    drill injected mid-run so the leg records the tier's RECOVERY
    latency, not just its throughput.

    Records ``bytes_per_trained_seq`` across REAL shard sockets (the
    loopback leg ``fleet_sampler`` is the comparison denominator:
    identical frames, so the delta is socket/ack overhead plus the
    HELLO/advert traffic), ``shard_forward_bytes_total`` (the
    ingest->shard SEQS hop the loopback doesn't pay — the honest cost of
    the extra localhost hop; ROADMAP names shedding it via direct
    actor->shard dials as the elasticity seam), and
    ``time_to_requota_s``: the gap between the kill_shard injection and
    the ``shard_dead``/``shard_quota_renorm`` verdict (both stamped
    ``t_mono`` in flight.jsonl) — how long a dead replay node degrades
    sampling before quotas renormalize to the survivors.

    HONESTY (carried from the other fleet legs): this single-core
    container time-slices the learner, 3 actor processes and 2 shard
    processes, so rates are contention artifacts; the claims this leg
    records are sheds=0, run completion THROUGH a shard kill, and the
    recovery latency.

    ISSUE 13 additions: the run carries the full health plane
    (``--obs-fleet`` TELEM from actors AND shard procs, ``--obs-port 0``
    exporter) and the leg records the SCRAPE PATH's cost — /metrics GET
    latency p50/p99 sampled ~5 Hz while every fleet process reports into
    the one page — plus the end-of-run ``/health`` verdict
    (health_final.json, stamped by train.py's fleet teardown).  On this
    contended container a ``degraded``/``learner_starving`` verdict is an
    HONEST answer (the wait p99 really is over threshold here), exactly
    the signal the ROADMAP autoscaler would act on."""
    import json as _json
    import tempfile
    import urllib.request

    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.setdefault("R2D2DPG_PALLAS_INTERPRET", "1")
    logdir = tempfile.mkdtemp(prefix="bench_shard_procs_")
    cmd = [
        sys.executable, "-m", "r2d2dpg_tpu.train",
        "--config", "pendulum_r2d2", "--num-envs", "64",
        "--actors", "3", "--replay-shards", "2", "--shard-procs", "2",
        "--fleet-publish-every", "4",
        # The probe's fast lane (bf16+zlib), so bytes_per_trained_seq is
        # lane-matched against the recorded loopback leg fleet_sampler —
        # the delta is then socket/ack/advert overhead, not encoding.
        "--fleet-wire", "bf16", "--fleet-compress", "zlib",
        "--chaos-spec", f"kill_shard@p{max(phases // 2, 1)}",
        "--obs-fleet", "1", "--obs-port", "0", "--obs-host", "127.0.0.1",
        "--phases", str(phases), "--log-every", "0",
        "--logdir", logdir,
    ]
    # Pipes would deadlock a chatty child (64 KiB buffer); spool to files
    # so the scrape loop below can run while the child trains.
    out_path = os.path.join(logdir, "bench_stdout.log")
    err_path = os.path.join(logdir, "bench_stderr.log")
    scrape_lat = []
    with open(out_path, "w") as out_f, open(err_path, "w") as err_f:
        proc = subprocess.Popen(
            cmd, env=env, cwd=HERE, stdout=out_f, stderr=err_f, text=True,
            start_new_session=True,
        )
        try:
            deadline = time.monotonic() + 900
            port = None
            port_path = os.path.join(logdir, "obs_port.txt")
            while proc.poll() is None and time.monotonic() < deadline:
                if port is None:
                    try:
                        port = int(open(port_path).read().strip())
                    except (OSError, ValueError):
                        time.sleep(0.5)
                        continue
                t0 = time.monotonic()
                try:
                    urllib.request.urlopen(
                        f"http://127.0.0.1:{port}/metrics", timeout=5
                    ).read()
                    scrape_lat.append(time.monotonic() - t0)
                except Exception:  # noqa: BLE001 — e.g. BadStatusLine on
                    pass  # a teardown race; a failed scrape never counts
                time.sleep(0.2)
            if proc.poll() is None:
                _drain_group(proc)
                return {"error": "shard-procs leg exceeded 900s"}
        finally:
            # Whatever escapes the loop must not orphan the training
            # child (and its actor/shard subprocesses); an abnormal exit
            # (rc != 0: the leader's finally-block teardown may not have
            # run) gets a best-effort group sweep too.
            if proc.poll() is None:
                _drain_group(proc)
            elif proc.returncode != 0:
                try:
                    os.killpg(proc.pid, signal.SIGTERM)
                except OSError:
                    pass
    rc = proc.returncode
    stdout = open(out_path).read()
    stderr = open(err_path).read()
    stats = _parse_fleet_stats(stdout)
    if not stats:
        return {"error": f"rc={rc}: {stderr[-300:]}"}
    # Recovery latency off the flight timeline: kill injection ->
    # shard_dead (+ the quota renorm recorded in the same breath).
    t_kill = t_dead = None
    try:
        with open(os.path.join(logdir, "flight.jsonl")) as fh:
            for line in fh:
                try:
                    e = _json.loads(line)
                except ValueError:
                    continue
                if (
                    e.get("kind") == "chaos_inject"
                    and e.get("fault") == "kill_shard"
                ):
                    t_kill = e.get("t_mono")
                if e.get("kind") == "shard_dead" and t_dead is None:
                    t_dead = e.get("t_mono")
    except OSError:
        pass
    leg = {
        "trained_seqs": stats.get("trained_seqs", 0.0),
        "sheds": stats.get("sheds", -1.0),
        "bytes_per_trained_seq": round(
            stats.get("bytes_per_trained_seq", 0.0), 1
        ),
        "sample_bytes_total": stats.get("sample_bytes_total", 0.0),
        "shard_forward_bytes_total": stats.get(
            "shard_forward_bytes_total", 0.0
        ),
        "shard_deaths": stats.get("shard_deaths", 0.0),
        "shard_rejoins": stats.get("shard_rejoins", 0.0),
        "evictions": stats.get("evictions", 0.0),
        "learner_steps_per_sec": round(
            stats.get("train_learner_steps_per_sec", 0.0), 2
        ),
        "time_to_requota_s": (
            round(t_dead - t_kill, 3)
            if t_kill is not None and t_dead is not None and t_dead >= t_kill
            else None
        ),
        **_device_cols(stats),
    }
    # Scrape-path overhead (ISSUE 13): /metrics latency with 3 actors +
    # 2 shard procs all reporting into the one merged page.
    if scrape_lat:
        lat = sorted(scrape_lat)
        leg["scrapes"] = len(lat)
        leg["scrape_p50_ms"] = round(lat[len(lat) // 2] * 1e3, 2)
        leg["scrape_p99_ms"] = round(lat[int(0.99 * (len(lat) - 1))] * 1e3, 2)
    # End-of-run /health verdict: the autoscaler's input, stamped as
    # bench evidence (train.py's fleet teardown writes the file).
    try:
        with open(os.path.join(logdir, "health_final.json")) as fh:
            health = _json.load(fh)
        leg["health_verdict"] = health.get("verdict")
        leg["health_rules"] = sorted(
            {f.get("rule") for f in health.get("findings", ())}
        )
    except (OSError, ValueError):
        leg["health_verdict"] = None
    if rc != 0:
        leg["error"] = f"rc={rc}: {stderr[-300:]}"
    return leg


def _shard_direct_leg(phases: int = 12) -> dict:
    """``python bench.py fleet_shard_direct`` — the direct actor->shard
    data plane (ISSUE 17): two lane-matched sub-runs of ``--actors 3
    --replay-shards 2 --shard-procs 2`` through the real train.py CLI,
    one with ``--shard-direct 1`` (+ concurrent pullers and one phase of
    batch prefetch), one on the learner-forwarded path with the SERIAL
    pull loop (``--shard-direct 0 --shard-pullers 1`` — the pre-ISSUE-17
    control).

    The claims the direct leg records: ``shard_forward_bytes == 0``
    (every staged batch bypassed the learner's ingest->shard hop — the
    seam the ROADMAP named after ISSUE 12), ``learner_seqs_bytes``
    collapsed to K_STATS control frames (recorded per trained sequence
    against the control leg's full forwarded stream), sheds == 0,
    steady_recompiles == 0, and ``sampler_wait_p99_ms`` at or under the
    serial control leg's (N pullers pay ~the max per-shard exchange,
    the serial loop pays the sum).

    HONESTY (the standing fleet-leg caveat): this container time-slices
    the learner, 3 actors and 2 shard procs on shared cores, so
    wait/throughput columns are contention-noisy — the byte counters
    and the zero/nonzero structural claims are the stable evidence;
    treat the p99 comparison as directional on this box."""

    def sub_run(tag: str, extra_args: list) -> dict:
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        env.setdefault("R2D2DPG_PALLAS_INTERPRET", "1")
        cmd = [
            sys.executable, "-m", "r2d2dpg_tpu.train",
            "--config", "pendulum_r2d2", "--num-envs", "64",
            "--actors", "3", "--replay-shards", "2", "--shard-procs", "2",
            "--fleet-publish-every", "4",
            # Lane-matched to fleet_sampler/fleet_shard_procs so byte
            # columns compare across legs, not across encodings.
            "--fleet-wire", "bf16", "--fleet-compress", "zlib",
            "--phases", str(phases), "--log-every", "0",
        ] + extra_args
        rc, stdout, stderr = _run_leg_cmd(cmd, env)
        if rc is None:
            return {"error": f"shard-direct {tag} leg exceeded 900s"}
        stats = _parse_fleet_stats(stdout)
        if not stats:
            return {"error": f"rc={rc}: {stderr[-300:]}"}
        trained = max(stats.get("trained_seqs", 0.0), 1.0)
        leg = {
            "trained_seqs": stats.get("trained_seqs", 0.0),
            "sheds": stats.get("sheds", -1.0),
            # The shed hop, as a counter: ingest->shard SEQS bytes the
            # learner forwarded (0 on the direct leg is the tentpole).
            "shard_forward_bytes": stats.get(
                "shard_forward_bytes_total", -1.0
            ),
            # The actor->learner wire per trained sequence: K_STATS-only
            # on the direct leg vs the full forwarded stream.
            "learner_seqs_bytes": stats.get("seqs_bytes_total", 0.0),
            "learner_wire_bytes_per_trained_seq": round(
                stats.get("seqs_bytes_total", 0.0) / trained, 1
            ),
            "sample_bytes_total": stats.get("sample_bytes_total", 0.0),
            "bytes_per_trained_seq": round(
                stats.get("bytes_per_trained_seq", 0.0), 1
            ),
            "shard_pullers": stats.get("shard_pullers", 0.0),
            # Starvation signal, one sample per phase zeros included:
            # 0.0 IS the healthy reading (see sampler.py's
            # _pull_phase_batches docstring), so the cross-leg claim is
            # "no worse", not a ratio.
            "sampler_wait_p99_ms": round(
                stats.get("sampler_wait_p99_ms", 0.0), 3
            ),
            "sampler_wait_total_s": round(
                stats.get("sampler_wait_total_s", 0.0), 3
            ),
            # Per-exchange SAMPLE_REQ/BATCH latency: the serial leg
            # pays the SUM of these per phase, K pullers pay ~the max
            # per round — on this time-sliced box the per-exchange p99
            # rises under concurrency while phase wall clock drops, so
            # both the p99 and the total are recorded.
            "puller_wait_p99_ms": round(
                stats.get("puller_wait_p99_ms", 0.0), 3
            ),
            "puller_wait_total_s": round(
                stats.get("puller_wait_total_s", 0.0), 3
            ),
            "learner_steps_per_sec": round(
                stats.get("train_learner_steps_per_sec", 0.0), 2
            ),
            "evictions": stats.get("evictions", 0.0),
            **_device_cols(stats),
        }
        if rc != 0:
            leg["error"] = f"rc={rc}: {stderr[-300:]}"
        return leg

    direct = sub_run(
        "direct",
        ["--shard-direct", "1", "--shard-prefetch", "1"],
    )
    control = sub_run(
        "forwarded-serial",
        ["--shard-direct", "0", "--shard-pullers", "1"],
    )
    leg = {"direct": direct, "forwarded_serial": control}
    if "error" not in direct and "error" not in control:
        leg["forward_bytes_shed"] = control["shard_forward_bytes"]
        leg["sampler_wait_p99_le_serial"] = bool(
            direct["sampler_wait_p99_ms"]
            <= control["sampler_wait_p99_ms"]
        )
    return leg


def _autoscale_leg(phases: int = 12) -> dict:
    """``python bench.py fleet_autoscale`` — the policy-driven recovery
    probe (ISSUE 16): a 3-actor fleet through the real train.py CLI with
    ``--autoscale 1`` and a ``kill_actor@p3`` drill.  Under autoscale the
    supervisor runs restart="policy" — the crash leaves the slot down and
    the HEALTH loop (actors_down finding -> hysteresis gate -> spawn)
    restores the population, so ``time_to_restore_s`` is the closed
    loop's latency (chaos_inject -> the landed autoscale_action, both
    stamped ``t_mono`` in flight.jsonl), not the backoff ladder's.

    The claims this leg records: run completion THROUGH the kill with
    sheds=0 and steady_recompiles=0, ``autoscale_actions`` >= 1 (the
    recovery was a decision, not a reflex — restarts stay 0 in policy
    mode), and the recovery latency.  Rates stay contention artifacts on
    this single-core container (the standing fleet-leg honesty note)."""
    import json as _json
    import tempfile

    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.setdefault("R2D2DPG_PALLAS_INTERPRET", "1")
    logdir = tempfile.mkdtemp(prefix="bench_autoscale_")
    cmd = [
        sys.executable, "-m", "r2d2dpg_tpu.train",
        "--config", "pendulum_r2d2", "--num-envs", "64",
        "--actors", "3", "--fleet-publish-every", "4",
        "--fleet-wire", "bf16", "--fleet-compress", "zlib",
        "--chaos-spec", "kill_actor@p3",
        "--autoscale", "1",
        # Fast policy cadence so the recovery fits inside the short run:
        # 2 consecutive findings at 0.5 s evals, 2 s between actions —
        # the hysteresis MATH is pinned by tests/test_autoscaler.py; the
        # leg measures the closed loop's end-to-end latency.
        "--autoscale-fire", "2", "--autoscale-every", "0.5",
        "--autoscale-cooldown", "2",
        "--phases", str(phases), "--log-every", "0",
        "--logdir", logdir,
    ]
    out_path = os.path.join(logdir, "bench_stdout.log")
    err_path = os.path.join(logdir, "bench_stderr.log")
    with open(out_path, "w") as out_f, open(err_path, "w") as err_f:
        proc = subprocess.Popen(
            cmd, env=env, cwd=HERE, stdout=out_f, stderr=err_f, text=True,
            start_new_session=True,
        )
        try:
            proc.wait(timeout=900)
        except subprocess.TimeoutExpired:
            _drain_group(proc)
            return {"error": "autoscale leg exceeded 900s"}
        finally:
            if proc.poll() is None:
                _drain_group(proc)
            elif proc.returncode != 0:
                try:
                    os.killpg(proc.pid, signal.SIGTERM)
                except OSError:
                    pass
    rc = proc.returncode
    stdout = open(out_path).read()
    stderr = open(err_path).read()
    stats = _parse_fleet_stats(stdout)
    if not stats:
        return {"error": f"rc={rc}: {stderr[-300:]}"}
    # Recovery latency off the flight timeline: the kill injection -> the
    # LANDED autoscale action that restored the population (the paired
    # origin="autoscale" actor_spawn rides the same tick).
    t_kill = t_restore = None
    try:
        with open(os.path.join(logdir, "flight.jsonl")) as fh:
            for line in fh:
                try:
                    e = _json.loads(line)
                except ValueError:
                    continue
                if (
                    e.get("kind") == "chaos_inject"
                    and e.get("fault") == "kill_actor"
                ):
                    t_kill = e.get("t_mono")
                if (
                    e.get("kind") == "autoscale_action"
                    and t_kill is not None
                    and t_restore is None
                    and e.get("t_mono", 0.0) >= t_kill
                ):
                    t_restore = e.get("t_mono")
    except OSError:
        pass
    leg = {
        # Central-drain topology: absorbed_seqs is this leg's volume
        # column (trained_seqs is the sampler legs').
        "absorbed_seqs": stats.get("absorbed_seqs", 0.0),
        "sheds": stats.get("sheds", -1.0),
        "autoscale_actions": stats.get("autoscale_actions", 0.0),
        "autoscale_decisions": stats.get("autoscale_decisions", 0.0),
        "autoscale_target": stats.get("autoscale_target", 0.0),
        # Policy mode: the ladder never restarts — a nonzero value here
        # means the crash-restart path fired alongside the policy loop,
        # exactly the double-owner bug the mode exists to preclude.
        "actor_restarts": stats.get("actor_restarts", -1.0),
        "learner_steps_per_sec": round(
            stats.get("train_learner_steps_per_sec", 0.0), 2
        ),
        "time_to_restore_s": (
            round(t_restore - t_kill, 3)
            if t_kill is not None and t_restore is not None
            else None
        ),
        **_device_cols(stats),
    }
    if rc != 0:
        leg["error"] = f"rc={rc}: {stderr[-300:]}"
    return leg


def _serve_leg(workers: int) -> dict:
    """One ``python bench.py serve`` leg in a SUBPROCESS: the N-worker
    router needs ``XLA_FLAGS=--xla_force_host_platform_device_count=2``
    set before jax initializes (one forced host device per worker), and
    each leg must see a FRESH process anyway so its compile ledger and
    registry start clean.  The child prints ONE JSON line
    (``_serve_leg_worker``); rc/stderr failures come back as an error
    record instead of raising — the BENCH_SERVE.json line is the
    contract."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.setdefault("R2D2DPG_PALLAS_INTERPRET", "1")
    flags = env.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        env["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=2"
        ).strip()
    env["R2D2DPG_BENCH_SERVE_LEG"] = str(workers)
    rc, stdout, stderr = _run_leg_cmd(
        [sys.executable, os.path.abspath(__file__)], env
    )
    if rc is None:
        return {"error": f"serve leg workers={workers} exceeded 900s"}
    for line in reversed(stdout.strip().splitlines()):
        try:
            rec = json.loads(line)
        except json.JSONDecodeError:
            continue
        if rec.get("workers") == workers:
            if rc != 0:
                rec["error"] = f"rc={rc}: {stderr[-300:]}"
            return rec
    return {"error": f"rc={rc} with no leg record: {stderr[-300:]}"}


def _serve_leg_worker(workers: int) -> None:
    """Traffic-harness body (child process): open-loop arrival of
    ``SESSIONS`` concurrent recurrent sessions against a ``workers``-wide
    router, p50/p99 from each request's INTENDED arrival time.

    Open loop: requests are issued on a fixed schedule regardless of
    completions (a closed loop would slow its offered load to whatever
    the service sustains and hide queueing — coordinated omission), so
    latency for request k is measured from its scheduled arrival
    ``t0 + k/RATE``, not from whenever the generator got around to it:
    lat = (enqueued_at - t_sched) + req.latency_s, all on the service's
    own monotonic clock.

    Steady-state discipline: ``start(warmup=True)`` precompiles every
    bucket on every worker and ``mark_steady()`` arms the device
    sentinel BEFORE traffic — ``steady_recompiles`` in the record is the
    pad-to-bucket claim, measured.  Sheds and affinity violations ride
    the router's own health aggregate; both must read 0 on the blessed
    config.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    jax.config.update("jax_platforms", "cpu")

    from r2d2dpg_tpu.models import ActorNet
    from r2d2dpg_tpu.obs.device import get_device_monitor
    from r2d2dpg_tpu.obs.registry import Registry
    from r2d2dpg_tpu.serving import OK, build_router

    SESSIONS = 2048
    STEPS = 3  # recurrent: step 0 resets, 1-2 ride the slab carry
    RATE = 800.0  # offered req/s, open loop
    OBS = (12,)
    # action_dim >= 3: single-column heads hit XLA:CPU's batch-size-
    # dependent gemv reduction order (docs/SERVING.md "Determinism").
    actor = ActorNet(action_dim=3, hidden=32, use_lstm=True)
    params = actor.init(
        jax.random.PRNGKey(0),
        jnp.zeros((1,) + OBS),
        actor.initial_carry(1),
        jnp.zeros((1,)),
    )
    rng = np.random.default_rng(7)
    sids = [f"sess-{i}" for i in range(SESSIONS)]
    obs = rng.standard_normal((SESSIONS,) + OBS).astype(np.float32)

    mon = get_device_monitor().install()
    mon.begin_run()
    router = build_router(
        actor,
        num_workers=workers,
        params=params,
        obs_shape=OBS,
        max_sessions=SESSIONS,  # per worker: holds the 1-worker leg too
        max_queue=4096,
        bucket_sizes=(1, 2, 4, 8, 16, 32, 64),
        flush_ms=2.0,
        registry=Registry(),
        params_step=0,
    )
    with router:
        mon.mark_steady()  # warmup compiled every bucket on every worker
        total = SESSIONS * STEPS
        pending = []
        t0 = time.monotonic()
        for k in range(total):
            t_sched = t0 + k / RATE
            now = time.monotonic()
            if t_sched > now:
                time.sleep(t_sched - now)
            step, i = divmod(k, SESSIONS)
            req = router.act_async(sids[i], obs[i], reset=(step == 0))
            pending.append((t_sched, req))
        lat_ms, ok, shed = [], 0, 0
        for t_sched, req in pending:
            assert req.wait(120.0), "request never completed"
            if req.code == OK:
                ok += 1
                lat_ms.append(
                    ((req.enqueued_at - t_sched) + req.latency_s) * 1e3
                )
            else:
                shed += 1
        wall = time.monotonic() - t0
        health = router.health()
    stats = mon.run_stats()
    mon.end_run()
    lat = np.sort(np.asarray(lat_ms)) if lat_ms else np.zeros((1,))
    rec = {
        "workers": workers,
        "sessions": SESSIONS,
        "steps_per_session": STEPS,
        "offered_rps": RATE,
        "requests": total,
        "ok": ok,
        "sheds": shed,
        "affinity_violations": health["affinity_violations"],
        "sessions_active": health["sessions_active"],
        "worker_errors": health["worker_errors"],
        "throughput_rps": round(ok / max(wall, 1e-9), 1),
        "latency_p50_ms": round(float(np.percentile(lat, 50)), 2),
        "latency_p99_ms": round(float(np.percentile(lat, 99)), 2),
        "wall_s": round(wall, 2),
        "per_worker_requests": {
            w: snap["requests_ok"]
            for w, snap in health["per_worker"].items()
        },
        "compile_count": stats.get("compile_count", -1.0),
        "steady_recompiles": stats.get("steady_recompiles", -1.0),
    }
    print(json.dumps(rec))


def _serve_probe() -> None:
    """``python bench.py serve`` — the scale-out traffic harness
    (ISSUE 20): 1-worker vs 2-worker router legs under identical open-
    loop load, written to BENCH_SERVE.json beside the headline benches.

    HONESTY (the standing single-core caveat, same as BENCH_FLEET.json's
    dp legs): the 2 forced host devices time-slice this container's
    single CPU core, so the 2-worker leg pays contention the 1-worker
    leg doesn't — a p50/p99 regression at N=2 here is the box, not the
    router; the claims this harness records are the STRUCTURAL ones
    (affinity_violations == 0, sheds == 0 at steady state,
    steady_recompiles == 0, per-worker residency matching the hash
    split).  The latency-scaling claim needs real chips; serve_gate
    stamps serve_workers.txt into any such evidence dir.
    """
    rec = {
        "metric": "serve_p99_latency_ms",
        "unit": "ms",
        "config": "2048 recurrent sessions x3 steps, open loop 800 req/s, "
        "ActorNet h32 act3, buckets 1..64, forced 2 host devices",
        "backend": "cpu",
        "legs": {str(n): _serve_leg(n) for n in (1, 2)},
        "vs_baseline_note": (
            "single-core container: 2 forced host devices time-slice one "
            "CPU core, so cross-leg latency deltas are contention "
            "artifacts; the recorded claims are affinity_violations=0, "
            "sheds=0 at steady state, steady_recompiles=0 per leg"
        ),
    }
    leg = rec["legs"].get("2", {})
    rec["value"] = leg.get("latency_p99_ms", 0.0)
    if "error" in rec["legs"].get("1", {}) or "error" in leg:
        rec["error"] = "; ".join(
            f"workers={n}: {rec['legs'][str(n)]['error']}"
            for n in (1, 2)
            if "error" in rec["legs"][str(n)]
        )[-400:]
    with open(os.path.join(HERE, "BENCH_SERVE.json"), "w") as f:
        json.dump(rec, f, indent=2, sort_keys=True)
        f.write("\n")
    print(json.dumps(rec))


def main() -> None:
    """The measurement: one process, on the chip or not at all."""
    from r2d2dpg_tpu.utils.startup import enable_compile_cache, require_tpu

    enable_compile_cache()
    device = require_tpu()

    import jax
    import jax.numpy as jnp

    from r2d2dpg_tpu.agents import R2D2DPG
    from r2d2dpg_tpu.configs import WALKER_R2D2
    from r2d2dpg_tpu.models import ActorNet, CriticNet
    from r2d2dpg_tpu.replay import ReplayArena, SequenceBatch

    # No explicit dtype argument -> measure at the flagship config's
    # compute dtype, so flipping WALKER_R2D2's default (pending the bf16
    # learning-parity evidence) flips the headline number with it.
    dtype = jnp.dtype(
        sys.argv[1] if len(sys.argv) > 1 else WALKER_R2D2.compute_dtype
    )

    # Config-#3 (walker_r2d2) learner shapes; the agent recipe (burn-in,
    # unroll, n-step, lrs) comes from the flagship config itself so a
    # recorded default flip (e.g. round 3's n-step 5 -> 3) moves the
    # headline measurement with it, same as compute_dtype above.
    batch, obs_dim, act_dim, hidden = 64, 24, 6, 256
    cfg = WALKER_R2D2.agent
    seq_len = cfg.seq_len
    capacity = 100_000

    actor = ActorNet(action_dim=act_dim, hidden=hidden, use_lstm=True, dtype=dtype)
    critic = CriticNet(hidden=hidden, use_lstm=True, dtype=dtype)
    agent = R2D2DPG(actor, critic, cfg)

    key = jax.random.PRNGKey(0)
    ks = jax.random.split(key, 8)
    fill = 4096  # sequences resident for realistic sampling
    seqs = SequenceBatch(
        obs=jax.random.normal(ks[0], (fill, seq_len, obs_dim)),
        action=jax.random.uniform(ks[1], (fill, seq_len, act_dim), minval=-1, maxval=1),
        reward=jax.random.normal(ks[2], (fill, seq_len)),
        discount=jnp.ones((fill, seq_len)),
        reset=jnp.zeros((fill, seq_len)),
        carries={
            "actor": actor.initial_carry(fill),
            "critic": critic.initial_carry(fill),
        },
    )
    arena = ReplayArena(capacity, prioritized=True)
    arena_state = arena.init_state(seqs)
    arena_state = arena.add(
        arena_state, seqs, jax.random.uniform(ks[3], (fill,)) + 0.5
    )
    train = agent.init(ks[4], seqs.obs[:batch, 0], seqs.action[:batch, 0])

    def one_step(carry, key):
        train, arena_state = carry
        res = arena.sample(arena_state, key, batch)
        w = jnp.ones((batch,))
        train, prios, _ = agent.learner_step(train, res.batch, w)
        arena_state = arena.update_priorities(arena_state, res.indices, prios)
        return (train, arena_state), prios.mean()

    CHUNK = 50

    # Donate (train, arena) like the production jits do (trainer.py /
    # parallel/hybrid.py donate_argnums=(0,)): without donation XLA must
    # materialize fresh output buffers for the threaded-through arena
    # (hundreds of MB at capacity 100k) on every chunk boundary — a copy
    # the real learner loop never pays, which understates steps/s on the
    # HBM-bandwidth-limited chip.
    def _run_chunk(train, arena_state, key):
        keys = jax.random.split(key, CHUNK)
        (train, arena_state), out = jax.lax.scan(
            one_step, (train, arena_state), keys
        )
        return train, arena_state, out.mean()

    run_chunk = jax.jit(_run_chunk, donate_argnums=(0, 1))

    # Warm-up / compile.
    train, arena_state, _ = run_chunk(train, arena_state, ks[5])
    jax.block_until_ready(train.step)

    n_chunks = 6
    t0 = time.perf_counter()
    for i in range(n_chunks):
        train, arena_state, out = run_chunk(
            train, arena_state, jax.random.fold_in(ks[6], i)
        )
    jax.block_until_ready(out)
    dt = time.perf_counter() - t0
    steps_per_sec = n_chunks * CHUNK / dt

    rec = {
        "metric": METRIC,
        "value": round(steps_per_sec, 2),
        "unit": "steps/s",
        "device": device,
    }
    # Pipelined-executor probe (ISSUE 2): rides in the same record under
    # the "pipeline" key so the one-JSON-line contract holds.
    # R2D2DPG_BENCH_PIPELINE=0 skips it.
    if os.environ.get("R2D2DPG_BENCH_PIPELINE", "1") != "0":
        rec["pipeline"] = _pipeline_probe(device["platform"])
    print(json.dumps(rec))


if __name__ == "__main__":
    if os.environ.get("R2D2DPG_BENCH_SERVE_LEG"):
        _serve_leg_worker(int(os.environ["R2D2DPG_BENCH_SERVE_LEG"]))
    elif len(sys.argv) > 1 and sys.argv[1] == "fleet":
        _fleet_probe()  # local CPU probe

    elif len(sys.argv) > 1 and sys.argv[1] == "fleet_composed":
        # Just the composed-topology leg (subprocess; CPU-local): prints
        # ONE JSON object — merge it into BENCH_FLEET.json's
        # "fleet_composed" key beside the single-axis legs.
        print(json.dumps({"fleet_composed": _composed_leg()}))
    elif len(sys.argv) > 1 and sys.argv[1] == "fleet_shard_procs":
        # Just the standalone-shard-tier leg (ISSUE 12; subprocess,
        # CPU-local, kill_shard drill included): ONE JSON object — merge
        # into BENCH_FLEET.json's "fleet_shard_procs" key.
        print(json.dumps({"fleet_shard_procs": _shard_procs_leg()}))
    elif len(sys.argv) > 1 and sys.argv[1] == "fleet_autoscale":
        # Just the policy-driven recovery leg (ISSUE 16; subprocess,
        # CPU-local, kill_actor drill under --autoscale 1): ONE JSON
        # object — merge into BENCH_FLEET.json's "fleet_autoscale" key.
        print(json.dumps({"fleet_autoscale": _autoscale_leg()}))
    elif len(sys.argv) > 1 and sys.argv[1] == "serve":
        # Serving scale-out traffic harness (ISSUE 20; two subprocess
        # legs, CPU-local on forced host devices): prints ONE JSON object
        # AND writes it to BENCH_SERVE.json.
        _serve_probe()
    elif len(sys.argv) > 1 and sys.argv[1] == "fleet_shard_direct":
        # Just the direct-data-plane leg (ISSUE 17; two subprocess
        # sub-runs, direct vs forwarded-serial, CPU-local): ONE JSON
        # object — merge into BENCH_FLEET.json's "fleet_shard_direct".
        print(json.dumps({"fleet_shard_direct": _shard_direct_leg()}))
    else:
        main()
