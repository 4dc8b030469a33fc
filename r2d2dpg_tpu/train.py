"""Training entry point: ``python -m r2d2dpg_tpu.train --config walker_r2d2``.

Reference parity: SURVEY.md §2.5 — the reference's ``main.py`` parses flags,
spawns N actor processes + a learner and runs forever.  Here the same entry
drives the Anakin phase schedule (warm-up -> replay-fill -> train) on one
device or an SPMD mesh, wired to the aux subsystems of SURVEY §5:
checkpoint/resume (orbax), metrics (CSV + TensorBoard, return@wall-clock,
SPS), deterministic evaluation, profiler traces, NaN-debug mode.

Stop conditions: ``--phases N`` (exact phase count) and/or ``--minutes M``
(wall-clock budget — the BASELINE metric is return @ 30 min, so
``--minutes 30`` reproduces the north-star measurement).
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import time
from typing import Optional

from r2d2dpg_tpu import topology
from r2d2dpg_tpu.configs import CONFIGS, ExperimentConfig, get_config


def parse_args(argv=None) -> argparse.Namespace:
    p = argparse.ArgumentParser(
        prog="python -m r2d2dpg_tpu.train", description=__doc__
    )
    p.add_argument("--config", required=True, choices=sorted(CONFIGS))
    p.add_argument("--phases", type=int, default=None, help="train phases to run")
    p.add_argument(
        "--minutes", type=float, default=None, help="wall-clock budget (stops at whichever of --phases/--minutes hits first)"
    )
    p.add_argument("--logdir", default=None, help="metrics/TB/profile output dir")
    p.add_argument("--log-every", type=int, default=50, help="phases between logs")
    p.add_argument("--seed", type=int, default=None)
    # Orchestration scale overrides (SURVEY §2.5 hyperparameter flags).
    p.add_argument("--num-envs", type=int, default=None)
    p.add_argument("--batch-size", type=int, default=None)
    p.add_argument(
        "--lr-scale-batch", type=int, default=0, choices=[0, 1],
        help="scale actor/critic learning rates linearly with the batch "
        "size (Accelerated Methods, PAPERS.md 1803.02811): the resolved "
        "lrs are multiplied by batch_size / <config default batch> — the "
        "large-batch recipe the composed topology's sampling bandwidth "
        "(--actors x --replay-shards x --learner-dp) makes reachable.  "
        "Applied to the RESOLVED lrs (after --actor-lr/--critic-lr "
        "overrides); a no-op scale of 1.0 is printed, never silent"
    )
    p.add_argument("--learner-steps", type=int, default=None)
    p.add_argument("--min-replay", type=int, default=None)
    p.add_argument(
        "--param-sync-every", type=int, default=None,
        help="refresh behavior params every K phases (0 = always fresh)"
    )
    p.add_argument(
        "--overlap-learner", type=int, default=None, choices=[0, 1],
        help="host-pool trainers: interleave learner updates between env "
        "steps so they hide under the MuJoCo step (1 = on)"
    )
    p.add_argument(
        "--pipeline", type=int, default=0, choices=[0, 1],
        help="run train phases through the pipelined collect/learn "
        "executor (training/pipeline.py): collection and learning overlap "
        "in two threads over a bounded staging queue (1 = on)"
    )
    p.add_argument(
        "--pipeline-depth", type=int, default=2,
        help="staging-queue capacity in collect phases (backpressure bound)"
    )
    # Fleet mode (docs/FLEET.md): supervised out-of-process actors.
    p.add_argument(
        "--actors", type=int, default=0, metavar="N",
        help="spawn N supervised actor subprocesses streaming experience "
        "to a learner-side ingest server (0 = off: the in-process "
        "schedules, untouched)"
    )
    p.add_argument(
        "--fleet-address", default="127.0.0.1:0",
        help="ingest server bind: 'host:port' (port 0 = ephemeral) or "
        "'unix:/path'"
    )
    p.add_argument(
        "--fleet-queue-depth", type=int, default=4,
        help="staging-queue capacity in staged batches (past it the "
        "ingest server sheds loudly)"
    )
    p.add_argument(
        "--fleet-publish-every", type=int, default=1,
        help="drain phases between versioned param publications to actors"
    )
    p.add_argument(
        "--fleet-idle-timeout", type=float, default=300.0,
        help="seconds without a staged batch before the learner aborts as "
        "starved (the first batch gets double: actor spawn + compile)"
    )
    p.add_argument(
        "--fleet-shed-after", type=float, default=None, metavar="S",
        help="seconds a queue-full ingest handler waits before shedding a "
        "staged batch (past the startup grace; default 1.0).  Larger = "
        "backpressure posture: surplus actors park in the ack wait "
        "instead of re-collecting shed experience (the bench probes' "
        "throughput setting); smaller = freshness posture"
    )
    # Fleet wire fast lane (docs/FLEET.md "Wire format"): one negotiated
    # encoding per fleet; actors are spawned with matching flags.
    p.add_argument(
        "--fleet-wire", default="f32", choices=["f32", "bf16"],
        help="payload precision on the fleet wire: f32 = bit-exact "
        "(default), bf16 = observations/carries/params at half the bytes "
        "(rewards/priorities stay f32; restored to f32 learner-side)"
    )
    p.add_argument(
        "--fleet-compress", default="none", choices=["none", "zlib", "zstd"],
        help="fleet frame compression (zstd refused where the zstandard "
        "module is absent; the decompressed-size ceiling is enforced "
        "before allocation)"
    )
    p.add_argument(
        "--drain-coalesce", type=int, default=1, metavar="K",
        help="stack up to K queue-backlogged staged batches into one "
        "compiled arena-add drain call (1 = one call per batch; widths "
        "are bucketed to powers of two <= K to bound drain-program "
        "compiles)"
    )
    # In-network experience sampling (docs/REPLAY.md): replay sharded at
    # the ingest edge, learner pulls training-ready batches.
    p.add_argument(
        "--replay-shards", type=int, default=0, metavar="N",
        help="shard prioritized replay across N ingest-edge shards "
        "(fleet/sampler.py): each actor's SEQS traffic feeds its "
        "consistent-hash shard directly (no central drain thread), and "
        "the learner PULLS batches via SAMPLE_REQ/BATCH frames with "
        "quotas proportional to each shard's priority sum — two-level "
        "sampling that preserves the central proportional distribution; "
        "TD priorities ride back as versioned PRIO frames.  Requires "
        "--actors N (with --actors 0 only --replay-shards 1 is accepted "
        "and routes the untouched phase-locked loop — the determinism "
        "anchor).  0 = off (central drain)"
    )
    p.add_argument(
        "--shard-procs", type=int, default=0, metavar="N",
        help="host the --replay-shards M replay shards in N supervised "
        "STANDALONE shard processes (fleet/shard.py; M %% N == 0, one "
        "listening socket per shard, HELLO-auth'd frames on the "
        "negotiated wire lane): the replay tier becomes its own failure "
        "domain — a dead shard degrades sampling (quotas renormalize "
        "over survivors within a phase, handlers re-route), never "
        "training, and the supervisor's backoff restart rejoins it EMPTY "
        "under a bumped epoch that fences stale BATCH/PRIO traffic.  "
        "0 = in-learner loopback (PR 10's path, pinned bit-identical)"
    )
    # Direct data plane + concurrent pullers (ISSUE 17; docs/REPLAY.md
    # "Direct data plane").
    p.add_argument(
        "--shard-direct", type=int, default=0, choices=[0, 1],
        help="1: the ingest ack advertises each actor's shard assignment "
        "(consistent-hash shard + its dialable address + epoch) and the "
        "actor ships SEQS straight to the shard — the learner wire "
        "carries only params/telem/accounting (a tiny K_STATS frame per "
        "phase), shedding the ingest forward hop from the experience "
        "path.  Requires --actors N --replay-shards M; with "
        "--shard-procs 0 there is no dialable tier, so actors stay on "
        "the learner-forwarded path (the documented fallback, also "
        "taken loudly on any data-leg failure).  0 = learner-forwarded "
        "(pinned bit-identical)"
    )
    p.add_argument(
        "--shard-pullers", type=int, default=0, metavar="N",
        help="concurrent SAMPLE_REQ pullers over the replay shards "
        "(fleet/sampler.py): each quota round keeps one in-flight "
        "request per live shard, up to N at once — draw quotas and "
        "req-id assignment stay in shard-id order, so the pulled batch "
        "is bit-identical to the serial loop regardless of arrival "
        "order.  0 = one puller per shard, capped at 8; 1 = the serial "
        "loop"
    )
    p.add_argument(
        "--shard-prefetch", type=int, default=0, choices=[0, 1],
        help="1: overlap one phase of batch prefetch with training — the "
        "next phase's pull starts while the current batch trains "
        "(priorities it samples under are stale by exactly the one "
        "phase in flight, the documented Reverb-style tradeoff).  "
        "0 = off (pull inline; pinned bit-identical)"
    )
    # Fleet fault tolerance (docs/FLEET.md "Failure modes & recovery").
    p.add_argument(
        "--fleet-heartbeat", type=float, default=None, metavar="S",
        help="liveness read deadline on both fleet wire ends (default "
        "300): a peer silent past it is PINGed once and reaped on a "
        "second silence (peer_dead flight event; the actor exits "
        "retryably and the supervisor restarts it)"
    )
    p.add_argument(
        "--fleet-token", default=None,
        help="shared HELLO-authentication secret (hmac.compare_digest at "
        "the ingest door; mismatched actors are refused with "
        "REFUSED_AUTH).  REQUIRED practice for non-loopback "
        "--fleet-address binds; defaults to $R2D2DPG_FLEET_TOKEN — "
        "PREFER the env var, an argv secret is readable in ps — and is "
        "passed to spawned actors via the environment, never their "
        "command line"
    )
    p.add_argument(
        "--chaos-spec", default=None, metavar="SPEC",
        help="seeded fault-injection schedule (fleet/chaos.py), e.g. "
        "'kill_actor@p3,stall_actor@p5:4s,corrupt_frame@p7,"
        "kill_ingest_conn@p9' — each fault fires once at its drain/actor "
        "phase, at a real boundary (SIGKILL, sleep, byte flip, socket "
        "close), and must recover through the documented path; every "
        "injection lands in flight.jsonl + "
        "r2d2dpg_fleet_chaos_drills_total"
    )
    # Autoscaler (docs/FLEET.md "Autoscaling", ISSUE 16): the
    # health→actuation policy loop over the fleet supervisor.
    p.add_argument(
        "--autoscale", type=int, default=0, choices=[0, 1],
        help="close the health→actuation loop (fleet/autoscaler.py): a "
        "policy thread evaluates the in-process health engine and maps "
        "findings to hysteresis-gated spawn/kill/replace actions through "
        "the supervisor's runtime resize API; crashed actors are "
        "replaced by POLICY (SupervisorConfig restart='policy') instead "
        "of the reflexive backoff ladder.  0 = off (structurally inert; "
        "default)"
    )
    p.add_argument(
        "--autoscale-dry-run", type=int, default=0, choices=[0, 1],
        help="walk the full decision path — streaks, cooldown, window "
        "budget — logging autoscale_decision events, but never actuate "
        "(the supervisor keeps its reflexive ladder)"
    )
    p.add_argument(
        "--autoscale-min", type=int, default=1, metavar="N",
        help="scale-down floor on the actor population (default 1)"
    )
    p.add_argument(
        "--autoscale-max", type=int, default=0, metavar="N",
        help="scale-up ceiling on the actor population; also the GLOBAL "
        "sigma-ladder width (actors spawn with --num-actors max so every "
        "mintable lane has its own exploration sigma).  0 = pinned to "
        "--actors (no scale-up; default)"
    )
    p.add_argument(
        "--autoscale-cooldown", type=float, default=30.0, metavar="S",
        help="minimum seconds between landed autoscale actions (default "
        "30)"
    )
    p.add_argument(
        "--autoscale-every", type=float, default=2.0, metavar="S",
        help="health-evaluation cadence of the policy loop (default 2)"
    )
    p.add_argument(
        "--autoscale-fire", type=int, default=3, metavar="K",
        help="consecutive evaluations a health rule must fire before it "
        "may act (hysteresis; default 3)"
    )
    # Agent/exploration hyperparameter overrides (VERDICT r2 weak #3: probe
    # whether the walker plateau is data-bound or hparam-capped).
    p.add_argument("--sigma-max", type=float, default=None,
                   help="exploration noise ladder max sigma")
    p.add_argument("--ladder-alpha", type=float, default=None,
                   help="noise ladder spread exponent")
    p.add_argument("--n-step", type=int, default=None, help="n-step TD horizon")
    p.add_argument("--actor-lr", type=float, default=None)
    p.add_argument("--critic-lr", type=float, default=None)
    # Overestimation mitigations (agents/ddpg.py AgentConfig; default off).
    p.add_argument(
        "--twin-critic", type=int, default=None, choices=[0, 1],
        help="TD3 clipped double-Q: train a 2-critic ensemble, bootstrap "
        "from min(Q1',Q2') (eval needs the same flag to restore)"
    )
    p.add_argument(
        "--target-policy-sigma", type=float, default=None,
        help="TD3 target-policy smoothing noise scale (0 = off)"
    )
    p.add_argument(
        "--compute-dtype", default=None, choices=["float32", "bfloat16"],
        help="net activation dtype (params/optimizer stay float32)"
    )
    # SPMD.
    p.add_argument(
        "--spmd", type=int, default=0, metavar="D",
        help="run under shard_map on a D-device dp mesh (0 = single device)"
    )
    p.add_argument(
        "--learner-dp", type=int, default=0, metavar="D",
        help="data-parallel LEARNER over a D-device dp mesh "
        "(parallel/dp_learner.py): replay arena capacity-sharded, learner "
        "batch dp-sharded, params replicated.  Composes with --actors N "
        "(the fleet feeds a multi-chip learner — docs/FLEET.md "
        "'Multi-chip learner') and with --actors 0 (pure-JAX env configs "
        "only; --learner-dp 1 is pinned bit-identical to the plain "
        "schedule).  On CPU use XLA_FLAGS="
        "--xla_force_host_platform_device_count=D.  0 = off"
    )
    # Checkpointing.
    p.add_argument("--checkpoint-dir", default=None)
    p.add_argument(
        "--checkpoint-every", type=int, default=500,
        help="phases between checkpoints (0 = off entirely; -1 = final-"
        "save-only, e.g. for measurement runs where periodic saves would "
        "drag the GB-scale replay arena device->host mid-run)"
    )
    p.add_argument(
        "--checkpoint-light", action="store_true",
        help="save only the learner subtree (params/targets/opt/step): MBs "
        "instead of GBs, eval-compatible; resume restarts replay fresh"
    )
    p.add_argument("--resume", action="store_true", help="resume from the latest checkpoint in --checkpoint-dir")
    # Evaluation.
    p.add_argument("--eval-every", type=int, default=0, help="train phases between deterministic evals (0 = off)")
    p.add_argument("--eval-envs", type=int, default=10)
    # Debug / profiling.
    p.add_argument("--profile-phases", type=int, default=0, help="trace this many train phases into --logdir/profile")
    p.add_argument(
        "--profile-window", default=None, metavar="P:N",
        help="device-plane profiler capture (obs/device.py): run "
        "jax.profiler for N train/drain phases starting at phase P into "
        "<logdir>/profile_window, on WHICHEVER learner loop the run "
        "resolves to (phase-locked, pipelined, fleet drain, sampler "
        "pull).  profile_start/profile_stop flight events bracket the "
        "capture, and 'obs.flight merge --trace-out' stamps it as a "
        "labelled profile_window span in the fused Perfetto timeline; "
        "the capture's device time by stage of the learner call lands in "
        "<logdir>/profile_window/stages.json and in profile_stop (its entry "
        "'scopes' has every scope by pass, 'programs' the executions the "
        "capture holds, 'truncated' whether it lost the tail of its device "
        "events: docs/OBSERVABILITY.md).  "
        "Mutually exclusive with --profile-phases (one jax profiler "
        "session per process); requires --logdir"
    )
    p.add_argument("--nan-debug", action="store_true")
    # Observability (docs/OBSERVABILITY.md).
    p.add_argument(
        "--obs-port", type=int, default=None, metavar="PORT",
        help="serve the telemetry registry over HTTP: /metrics (Prometheus "
        "text) + /metrics.json (JSON snapshot); 0 binds an ephemeral port "
        "(resolved port printed and written to <logdir>/obs_port.txt)"
    )
    p.add_argument(
        "--obs-host", default="0.0.0.0",
        help="interface the --obs-port exporter binds (127.0.0.1 = "
        "loopback-only on shared hosts)"
    )
    p.add_argument(
        "--flight-path", default=None,
        help="flight-recorder dump path (default <logdir>/flight.jsonl, "
        "or ./flight.jsonl without --logdir); sampled trace spans dump "
        "to trace.json next to it"
    )
    p.add_argument(
        "--obs-fleet", type=int, default=0, choices=[0, 1],
        help="fleet-wide metric aggregation: with --actors N, actors push "
        "~1 Hz TELEM registry snapshots that fold into this process's "
        "/metrics under actor=/host= labels (one scrape point per fleet, "
        "with per-actor staleness gauges); with --shard-procs N the "
        "standalone shard processes push the same TELEM over their "
        "authenticated learner legs (shard=/host= labels, per-shard "
        "staleness armed at HELLO and reset on epoch-bumped rejoin); on "
        "a multi-process SPMD run, registry scalars process_allgather "
        "into process 0's exporter"
    )
    # /health verdict thresholds (obs/health.py; the endpoint rides
    # --obs-port's exporter — docs/OBSERVABILITY.md "/health verdicts").
    p.add_argument(
        "--health-wait-p99", type=float, default=0.5, metavar="S",
        help="/health 'learner_starving' threshold: learner/sampler wait "
        "p99 above this reads as the fleet failing to feed the learner"
    )
    p.add_argument(
        "--health-stale-after", type=float, default=10.0, metavar="S",
        help="/health 'telem_stale' threshold: an actor's or shard's "
        "TELEM staleness above this reads as wedged/partitioned/dead"
    )
    p.add_argument(
        "--quality-max-lag", type=float, default=100.0, metavar="N",
        help="/health 'stale_experience' threshold: policy-lag p99 "
        "(learner param version minus the behavior version stamped on "
        "trained sequences, obs/quality.py) above this reads as the "
        "learner training on stale experience"
    )
    p.add_argument(
        "--trace-sample", type=float, default=0.0, metavar="RATE",
        help="experience-path tracing: sample this fraction of staged "
        "batches and record per-hop spans (collect -> encode -> transit "
        "-> decode -> enqueue -> coalesce -> arena_add -> learn) into "
        "r2d2dpg_trace_*_seconds histograms and a Chrome-trace/Perfetto "
        "trace.json next to flight.jsonl (0 = off: no per-sequence "
        "overhead, wire bytes unchanged)"
    )
    p.add_argument(
        "--watchdog", type=int, default=1, choices=[0, 1],
        help="divergence watchdog on the log cadence: NaN/Inf or norm "
        "blow-up in learner outputs aborts loudly with a flight-recorder "
        "dump and a last-good-checkpoint pointer (1 = on)"
    )
    p.add_argument("--watchdog-grad-norm", type=float, default=1e6,
                   help="watchdog trip threshold for grad_norm")
    p.add_argument("--watchdog-param-norm", type=float, default=1e7,
                   help="watchdog trip threshold for param_norm")
    p.add_argument(
        "--nan-inject-phase", type=int, default=None, metavar="K",
        help="FAULT INJECTION (tests/drills): poison the actor params with "
        "NaN after the K-th train phase, so the next learner update "
        "produces non-finite outputs and the watchdog path is exercised "
        "end to end"
    )
    return p.parse_args(argv)


def _apply_overrides(cfg: ExperimentConfig, args) -> ExperimentConfig:
    t = {}
    for flag, field in (
        ("num_envs", "num_envs"),
        ("batch_size", "batch_size"),
        ("learner_steps", "learner_steps"),
        ("min_replay", "min_replay"),
        ("param_sync_every", "param_sync_every"),
        ("overlap_learner", "overlap_learner"),
        ("seed", "seed"),
        ("sigma_max", "sigma_max"),
        ("ladder_alpha", "ladder_alpha"),
    ):
        v = getattr(args, flag)
        if v is not None:
            t[field] = bool(v) if field == "overlap_learner" else v
    if t:
        cfg = dataclasses.replace(
            cfg, trainer=dataclasses.replace(cfg.trainer, **t)
        )
    a = {}
    for flag in ("n_step", "actor_lr", "critic_lr", "target_policy_sigma"):
        v = getattr(args, flag)
        if v is not None:
            a[flag] = v
    if args.twin_critic is not None:
        a["twin_critic"] = bool(args.twin_critic)
    if a:
        cfg = dataclasses.replace(
            cfg, agent=dataclasses.replace(cfg.agent, **a)
        )
    if args.compute_dtype is not None:
        cfg = dataclasses.replace(cfg, compute_dtype=args.compute_dtype)
    return cfg


def _health_config(args) -> "obs.HealthConfig":
    """The run's resolved /health thresholds + expected process counts.

    One builder for BOTH consumers — the exporter's armed engine and the
    fleet teardown's health_final.json fallback — so evidence stamped by
    a run without a live exporter still judges against the real spawn
    targets (a default HealthConfig has expected_actors=0 and
    expected_shard_procs=0, which disarms actors_down/shards_down and
    would stamp a dead shard tier as 'ok')."""
    from r2d2dpg_tpu import obs

    return obs.HealthConfig(
        learner_wait_p99_s=args.health_wait_p99,
        telem_stale_after_s=args.health_stale_after,
        expected_actors=args.actors or 0,
        expected_shard_procs=args.shard_procs or 0,
        # Staleness clocks arm at HELLO regardless, but TELEM pushes only
        # ride --obs-fleet — without it a growing clock is configuration,
        # not a wedged peer.
        telem_expected=bool(getattr(args, "obs_fleet", 0)),
        quality_max_lag=args.quality_max_lag,
    )


def run(args) -> dict:
    """Drive one experiment; returns the final metrics dict."""
    import jax

    from r2d2dpg_tpu import obs
    from r2d2dpg_tpu.training.evaluator import Evaluator
    from r2d2dpg_tpu.utils import (
        CheckpointManager,
        MetricLogger,
        nan_debug,
        profile_trace,
    )
    from r2d2dpg_tpu.utils.checkpoint import resume_state
    from r2d2dpg_tpu.utils.metrics import host_scalars

    if args.nan_debug:
        nan_debug(True)

    # ONE validation authority (ISSUE 11): every still-refused knob
    # pairing lives in topology.REFUSALS with its documented reason —
    # there are no ad-hoc refusal branches here.  The resolved Topology
    # names the four stages (collect/ingest/sample/learn) this run
    # assembles below (docs/TOPOLOGY.md has the composition matrix).
    topo = topology.validate(args, process_count=jax.process_count())
    if args.actors and args.replay_shards > args.actors:
        # Integer actor ids route round-robin, so only
        # min(actors, shards) shards ever get a feed: the surplus
        # shards stay empty forever and effective replay capacity
        # silently shrinks to that fraction — never silently.
        print(
            f"replay-shards: WARNING — {args.replay_shards} shards "
            f"but only {args.actors} actors: "
            f"{args.replay_shards - args.actors} shards will never "
            f"receive traffic and effective replay capacity is "
            f"{args.actors}/{args.replay_shards} of the configured "
            f"capacity (docs/REPLAY.md 'Topology')",
            flush=True,
        )

    cfg = _apply_overrides(get_config(args.config), args)
    if args.lr_scale_batch:
        # Linear lr/batch co-scaling (PAPERS.md 1803.02811): lr follows
        # batch relative to the config's recorded recipe.  Applied to the
        # RESOLVED values so explicit --actor-lr/--critic-lr overrides
        # scale too; a scale of 1.0 is printed, never silent.
        base_batch = get_config(args.config).trainer.batch_size
        scale = cfg.trainer.batch_size / base_batch
        cfg = dataclasses.replace(
            cfg,
            agent=dataclasses.replace(
                cfg.agent,
                actor_lr=cfg.agent.actor_lr * scale,
                critic_lr=cfg.agent.critic_lr * scale,
            ),
        )
        print(
            f"lr-scale-batch: linear rule (1803.02811) batch "
            f"{base_batch} -> {cfg.trainer.batch_size}, scale {scale:g} "
            f"(actor_lr {cfg.agent.actor_lr:g}, critic_lr "
            f"{cfg.agent.critic_lr:g})",
            flush=True,
        )

    if args.replay_shards and not args.actors:
        print(
            "replay-shards: no fleet (--actors 0) — replay stays in the "
            "central device arena and the phase-locked schedule runs "
            "unchanged (the determinism anchor, docs/REPLAY.md)",
            flush=True,
        )
    replay_capacity = cfg.trainer.capacity
    if args.replay_shards and args.actors:
        reachable = (replay_capacity // args.replay_shards) * min(
            args.actors, args.replay_shards
        )
        if cfg.trainer.min_replay > reachable:
            # The absorb gate waits for min_replay resident sequences,
            # but only min(actors, shards) shards ever receive traffic:
            # an unreachable gate would die after idle_timeout with a
            # misleading "starved" error against a healthy fleet.
            raise SystemExit(
                f"--replay-shards: min_replay {cfg.trainer.min_replay} "
                f"exceeds the reachable shard occupancy {reachable} "
                f"({args.actors} actors feed min(actors, shards) of "
                f"{args.replay_shards} shards x "
                f"{replay_capacity // args.replay_shards} slots) — "
                f"lower --min-replay or --replay-shards"
            )
        # Sampler mode: replay lives in the host-side ingest shards
        # (which get ``replay_capacity``, captured above), so the
        # trainer's device arena is structural only — shrink it to a
        # token allocation instead of reserving the config's full
        # capacity in HBM for buffers that stay init-zeros.  min_replay
        # is untouched (it gates the sampler's absorb phase).
        import dataclasses as _dc

        cfg = _dc.replace(
            cfg,
            trainer=_dc.replace(
                cfg.trainer,
                capacity=max(cfg.trainer.num_envs, cfg.trainer.batch_size),
            ),
        )

    trainer = topology.build_trainer(topo, cfg)

    # Stamp the resolved backend where automation can gate on it: a run
    # that resolved to the CPU must not be mistaken for an on-chip result
    # (chip_smoke.py refuses on it; the entry point itself carries on, since
    # tests drive it on the CPU).
    backend = jax.default_backend()
    print(f"backend: {backend}", flush=True)
    print(f"topology: {topo.describe()}", flush=True)
    if args.logdir:
        os.makedirs(args.logdir, exist_ok=True)
        with open(os.path.join(args.logdir, "backend.txt"), "w") as f:
            f.write(backend + "\n")
        with open(os.path.join(args.logdir, "topology.txt"), "w") as f:
            f.write(topo.describe() + "\n")

    # ------------------------------------------------------------ telemetry
    # Flight recorder is ALWAYS armed (an in-memory ring is ~free; the dump
    # is exit-time); the exporter and the CSV-bridge fold are --obs-port
    # opt-in; the watchdog is on by default (--watchdog 0 to drop it).
    registry = obs.get_registry()
    flight = obs.get_flight_recorder()
    # Device plane (ISSUE 14, docs/OBSERVABILITY.md "Device plane"):
    # compile sentinel + HBM gauges are always armed (the listener is
    # ~free; gauges ride the log cadence); the profiler window is opt-in.
    device_mon = obs.get_device_monitor().install()
    if args.profile_window is not None:
        if args.profile_phases:
            raise SystemExit(
                "--profile-window and --profile-phases both drive the one "
                "jax profiler session this process has — pick one "
                "(--profile-window works on every learner loop and is "
                "the superset)"
            )
        if not args.logdir:
            raise SystemExit("--profile-window requires --logdir")
        try:
            pw_phase, pw_steps = device_mon.arm_profile(
                args.profile_window,
                os.path.join(args.logdir, "profile_window"),
            )
        except ValueError as e:
            raise SystemExit(f"--profile-window: {e}")
        print(
            f"obs: profiler capture armed for phases "
            f"{pw_phase}..{pw_phase + pw_steps - 1} -> "
            f"{args.logdir}/profile_window",
            flush=True,
        )
    # Identity stamp (docs/FLEET.md post-mortems): every event this process
    # records says which host of a multi-process fleet it came from, so
    # interleaved flight.jsonl dumps stay attributable.
    obs.set_flight_identity(process_index=jax.process_index())
    flight_path = args.flight_path or (
        os.path.join(args.logdir, "flight.jsonl")
        if args.logdir
        else "flight.jsonl"
    )
    if args.logdir or args.flight_path:
        # Exit-time dump armed only when the operator named a destination
        # (no surprise ./flight.jsonl litter from bare smoke runs); the
        # watchdog abort path dumps explicitly either way.
        flight.install(flight_path)
    exporter = None
    if args.obs_port is not None:
        exporter = obs.start_exporter(args.obs_port, registry, args.obs_host)
        # The /health verdict engine (ISSUE 13 leg 3), armed with this
        # run's RESOLVED topology so actors_down/shards_down compare
        # against the real spawn targets — the autoscaler's input
        # contract, live from the first scrape.  arm_health(): the server
        # is already answering GETs, and the handler's lazy default must
        # never outrace this configured engine.
        exporter.arm_health(
            obs.HealthEngine(
                _health_config(args),
                registry=registry,
                mirror=obs.get_remote_mirror(),
            )
        )
        print(
            f"obs: /metrics + /metrics.json + /health on port "
            f"{exporter.port}",
            flush=True,
        )
        if args.logdir:
            with open(os.path.join(args.logdir, "obs_port.txt"), "w") as f:
                f.write(f"{exporter.port}\n")
    watchdog = (
        obs.DivergenceWatchdog(
            obs.WatchdogConfig(
                grad_norm_max=args.watchdog_grad_norm,
                param_norm_max=args.watchdog_param_norm,
            )
        )
        if args.watchdog
        else None
    )

    ckpt: Optional[CheckpointManager] = None
    if args.checkpoint_dir:
        light = args.checkpoint_light
        if args.actors and not light:
            # The fleet recovery contract (docs/FLEET.md): a fleet
            # checkpoint is the learner subtree + counter sidecar — the
            # replay arena is NEVER checkpointed (GBs of re-collectable
            # experience; resume re-enters absorb-to-min_replay).
            print(
                "fleet: checkpoints under --actors N are always light "
                "(learner subtree + counters; the arena is re-absorbed "
                "on resume — docs/FLEET.md)",
                flush=True,
            )
            light = True
        ckpt = CheckpointManager(
            args.checkpoint_dir,
            save_every=args.checkpoint_every,
            light=light,
        )

    evaluator: Optional[Evaluator] = None
    if args.eval_every:
        evaluator = Evaluator(
            cfg.env_factory(), trainer.agent.actor, num_envs=args.eval_envs
        )

    logger = MetricLogger(
        args.logdir, registry=registry if exporter is not None else None
    )
    deadline = (
        time.monotonic() + args.minutes * 60 if args.minutes is not None else None
    )

    if args.resume and ckpt is None:
        raise SystemExit("--resume requires --checkpoint-dir")
    if args.resume and not args.actors:
        state = resume_state(trainer, ckpt)
        if hasattr(trainer, "_shardings"):
            # dp-mesh trainers: restored leaves land single-device; put
            # them back on the mesh layout or the next jit call sees
            # inputs spanning mismatched device sets.
            state = jax.device_put(state, trainer._shardings)
        print(f"resumed from phase {int(state.phase_idx)}", flush=True)
    else:
        # Fleet resume is handled inside _run_fleet: the learner never
        # collects, so the generic resume_state's window-refill collect
        # phases would compile a program this process never runs.
        state = trainer.init()

    if args.pipeline:
        return _run_pipelined(
            trainer, state, logger, ckpt, args, watchdog, flight, flight_path
        )
    if args.actors:
        return _run_fleet(
            trainer, cfg, state, logger, ckpt, args, watchdog, flight,
            flight_path, replay_capacity=replay_capacity, topo=topo,
        )

    warm = trainer.window_fill_phases
    fill = warm + trainer.replay_fill_phases
    eval_key = jax.random.PRNGKey(cfg.trainer.seed + 1)
    last_learn = {}
    final = {}
    train_phases_done = 0
    diverged = False
    phase = start = int(state.phase_idx)
    # --phases counts *train* phases for this invocation: a fresh run stops
    # after fill + N, a resumed one after N more from wherever it restarted.
    stop_at = (
        max(start, fill) + args.phases if args.phases is not None else None
    )
    profile_until = None
    profiler_cm = None
    device_mon.begin_run()

    try:
        while True:
            if stop_at is not None and phase >= stop_at:
                break
            if deadline is not None and time.monotonic() >= deadline:
                break
            if stop_at is None and deadline is None and phase >= fill + 1:
                break  # nothing requested: run a single train phase (smoke)

            if phase < warm:
                state = trainer.collect_phase(state)
            elif phase < fill:
                state = trainer.fill_phase(state)
            else:
                if (
                    args.profile_phases
                    and args.logdir
                    and profile_until is None
                ):
                    profile_until = phase + args.profile_phases
                    profiler_cm = profile_trace(f"{args.logdir}/profile")
                    profiler_cm.__enter__()
                device_mon.on_phase(train_phases_done + 1)
                with device_mon.program("train_phase"):
                    state, last_learn = trainer.train_phase(state)
                train_phases_done += 1
                if train_phases_done == 1:
                    # The fused phase program is warm: the compile
                    # sentinel arms — a post-steady compile outside a
                    # declared window (log fetch, eval, drills) is the
                    # aval-re-key alarm (docs/OBSERVABILITY.md).
                    device_mon.mark_steady()
                if train_phases_done == args.nan_inject_phase:
                    with device_mon.expected("nan_inject"):
                        state = _poison_actor_params(state)
                if profiler_cm is not None and phase + 1 >= profile_until:
                    jax.block_until_ready(state.train.step)
                    profiler_cm.__exit__(None, None, None)
                    profiler_cm = None
            phase += 1

            if args.log_every and phase % args.log_every == 0:
                # expected(): the log fetch builds small eager reductions
                # on first use — declared, never a sentinel alarm.
                with device_mon.expected("log_fetch"):
                    state, ep = trainer.pop_episode_metrics(state)
                    scalars = dict(ep)
                    # ONE batched fetch for learn metrics + the step
                    # counter (per-scalar float() casts were N+1 blocking
                    # host syncs).
                    learn_np, lstep = jax.device_get(
                        (last_learn, state.train.step)
                    )
                scalars.update(host_scalars(learn_np))
                trainer._obs_publish({"learner_steps": float(lstep)})
                watch_scalars = dict(scalars)
                scalars.update(
                    logger.rates(
                        env_steps=ep["env_steps"],
                        learner_steps=float(lstep),
                    )
                )
                logger.log(phase, scalars)
                final = scalars
                if args.obs_fleet and jax.process_count() > 1:
                    # Multi-process leg of the fleet observability plane:
                    # COLLECTIVE (every process logs on the same cadence),
                    # folds rank >0 registries into process 0's exporter.
                    obs.allgather_into_mirror()
                if watchdog is not None:
                    # Rides the fetch above — no extra host syncs; checked
                    # AFTER the log call so the poisoned row is on disk as
                    # forensic evidence when the run aborts.
                    watchdog.check(phase, watch_scalars)

            if ckpt is not None and ckpt.save_every:
                ckpt.maybe_save(phase, state)

            if (
                evaluator is not None
                and phase > fill
                and (phase - fill) % args.eval_every == 0
            ):
                eval_key, k = jax.random.split(eval_key)
                # Eval compiles its own programs on first use: a declared
                # window, not an aval re-key of the training chain.
                with device_mon.expected("eval"):
                    ev = evaluator.run(state.train.actor_params, k)
                # Stamp the monotone env-step counter so eval-vs-steps
                # curves read directly off the CSV/TB row.
                ev["env_steps"] = float(state.env_steps)
                logger.log(phase, ev)
                final.update(ev)
    except obs.DivergenceError as e:
        diverged = True
        _abort_on_divergence(e, flight, flight_path, ckpt)
    finally:
        # Sentinel disarmed FIRST: the final save / logger close below
        # belong to teardown, not the steady window.
        device_mon.end_run()
        if profiler_cm is not None:
            profiler_cm.__exit__(None, None, None)
        if ckpt is not None:
            if ckpt.save_every and not diverged:
                # A diverged state must NOT become the "final" checkpoint —
                # it would shadow the last good one the abort points at.
                ckpt.save_final(phase, state)
            ckpt.wait()
            ckpt.close()
        logger.close()
    return final


def _poison_actor_params(state):
    """--nan-inject-phase fault injection: NaN every actor-param leaf, so
    the next learner update's outputs (losses, norms) go non-finite through
    the REAL divergence propagation path."""
    import jax
    import jax.numpy as jnp

    poisoned = jax.tree_util.tree_map(
        lambda x: jnp.full_like(x, jnp.nan), state.train.actor_params
    )
    return dataclasses.replace(
        state, train=dataclasses.replace(state.train, actor_params=poisoned)
    )


def _abort_on_divergence(e, flight, flight_path, ckpt) -> None:
    """Watchdog trip: dump the flight ring, point at the last good
    checkpoint, exit non-zero (SystemExit(2))."""
    import sys

    flight.record("abort", reason=str(e), step=e.step)
    dumped = flight.dump(flight_path)
    if ckpt is not None and ckpt.latest_step is not None:
        # Honesty about the detection window: the watchdog sees learner
        # outputs once per log cadence, so a checkpoint cadence FINER than
        # the log cadence can have saved an already-poisoned state before
        # the trip.  The abort never overwrites anything (final save is
        # skipped); the operator verifies before resuming.
        pointer = (
            f"{ckpt.directory} step {ckpt.latest_step} (verify before "
            f"resuming: a save inside the last log cadence may already "
            f"carry the divergence)"
        )
    else:
        pointer = "none on disk"
    print(
        f"watchdog: DIVERGENCE at step {e.step}: {e.reason}\n"
        f"watchdog: flight recorder dumped to {dumped}\n"
        f"watchdog: last-good checkpoint: {pointer}",
        file=sys.stderr,
        flush=True,
    )
    raise SystemExit(2)


def _make_executor_metrics_fn(logger, watchdog, final):
    """The log-cadence hook shared by the executors that own their phase
    loop (--pipeline 1, --actors N): fold rates in, log, keep the final
    row, and give the watchdog the raw (pre-rates) scalars."""

    def metrics_fn(phase: int, scalars) -> None:
        scalars = dict(scalars)
        watch_scalars = dict(scalars)
        scalars.update(
            logger.rates(
                env_steps=scalars.get("env_steps", 0.0),
                learner_steps=scalars.get("learner_steps", 0.0),
            )
        )
        logger.log(phase, scalars)
        final.clear()
        final.update(scalars)
        if watchdog is not None:
            watchdog.check(phase, watch_scalars)

    return metrics_fn


def _fold_executor_stats(prefix: str, stats: dict, final: dict) -> None:
    """Print an executor's end-of-run stats line and fold the values into
    the final metrics dict under ``<prefix>_`` keys."""
    if stats:
        print(
            f"{prefix}: "
            + " ".join(f"{k} {v:.4g}" for k, v in sorted(stats.items())),
            flush=True,
        )
        final.update({f"{prefix}_{k}": v for k, v in stats.items()})


def _run_pipelined(
    trainer, state, logger, ckpt, args, watchdog, flight, flight_path
) -> dict:
    """Drive the run through the pipelined executor (--pipeline 1).

    The executor owns the warm-up -> fill -> train schedule and the log
    cadence; metrics land in the same MetricLogger (CSV/TB) rows as the
    phase-locked loop, and a final checkpoint is saved when a checkpoint
    dir is configured."""
    from r2d2dpg_tpu.obs import DivergenceError
    from r2d2dpg_tpu.training.pipeline import PipelineConfig, PipelineExecutor

    executor = PipelineExecutor(
        trainer,
        PipelineConfig(
            enabled=True,
            queue_depth=args.pipeline_depth,
            trace_sample=args.trace_sample,
        ),
    )
    if ckpt is not None and ckpt.save_every and ckpt.save_every > 0:
        # The state is split across two threads mid-run, so periodic saves
        # aren't composed with the executor yet — degrade LOUDLY to the
        # --checkpoint-every -1 (final-save-only) semantics.
        print(
            "pipeline: periodic checkpoints not supported with --pipeline 1; "
            "saving the final checkpoint only (--checkpoint-every -1 "
            "semantics)",
            flush=True,
        )
    fill = trainer.window_fill_phases + trainer.replay_fill_phases
    if args.phases is not None:
        num_phases = fill + args.phases
    elif args.minutes is not None:
        num_phases = 10**9  # the wall-clock budget is the stop condition
    else:
        num_phases = fill + 1  # nothing requested: single-train-phase smoke

    final: dict = {}
    # On a watchdog trip metrics_fn raises DivergenceError through the
    # executor's learner loop, whose finally-block stops and joins the
    # collector thread.
    metrics_fn = _make_executor_metrics_fn(logger, watchdog, final)

    try:
        state = executor.run(
            num_phases,
            state=state,
            log_every=args.log_every,
            metrics_fn=metrics_fn,
            minutes=args.minutes,
        )
        _fold_executor_stats("pipeline", executor.stats(), final)
        if ckpt is not None and ckpt.save_every:
            ckpt.save_final(int(state.phase_idx), state)
    except DivergenceError as e:
        _abort_on_divergence(e, flight, flight_path, ckpt)
    finally:
        # Sampled spans -> trace.json next to flight.jsonl (no-op when
        # tracing is off or no dump path is armed).
        flight.dump_trace()
        if ckpt is not None:
            ckpt.wait()
            ckpt.close()
        logger.close()
    return final


def _run_fleet(
    trainer, cfg, state, logger, ckpt, args, watchdog, flight, flight_path,
    replay_capacity=None, topo=None,
) -> dict:
    """Drive the run through the actor fleet (--actors N, docs/FLEET.md).

    This process becomes the learner: an ingest server feeds the staging
    queue, a supervisor owns N actor subprocesses (spawn/monitor/restart
    with backoff), and the drain loop runs here.  ``--phases`` counts
    drain-learn phases; metrics land in the same MetricLogger rows as the
    other schedules."""
    from r2d2dpg_tpu.fleet import (
        ActorSupervisor,
        FleetConfig,
        SupervisorConfig,
        WireConfig,
        default_actor_argv,
    )
    from r2d2dpg_tpu import obs
    from r2d2dpg_tpu.fleet import chaos as fleet_chaos
    from r2d2dpg_tpu.fleet import transport as fleet_transport
    from r2d2dpg_tpu.fleet.ingest import load_fleet_counters
    from r2d2dpg_tpu.obs import DivergenceError, flight_event

    try:
        wire_config = WireConfig(
            encoding=args.fleet_wire, compress=args.fleet_compress
        ).validate()
    except ValueError as e:
        # e.g. zstd on a box without the zstandard module: refuse loudly
        # at startup, not with a crash-looping actor fleet.
        raise SystemExit(f"--fleet-compress: {e}")
    # run() already validated the grammar (fail before the trainer build);
    # this parse only materializes the Fault tuple.
    chaos_faults = (
        fleet_chaos.parse_chaos_spec(args.chaos_spec)
        if args.chaos_spec
        else ()
    )
    # $R2D2DPG_FLEET_TOKEN fallback, same as fleet/actor.py: a secret on
    # the learner's own command line would sit in /proc/<pid>/cmdline for
    # the run's whole lifetime — the exact exposure the env-var hand-off
    # to actors avoids.  Resolved here (fleet-only path), so an exported
    # token never trips the fleet-knobs-without---actors refusal.
    fleet_token = (
        args.fleet_token or os.environ.get("R2D2DPG_FLEET_TOKEN") or None
    )
    if not fleet_transport.is_loopback_address(
        args.fleet_address
    ) and not fleet_token:
        # Routable bind without authentication: anyone who can reach the
        # port can feed the learner experience (the frame parser is safe
        # on untrusted bytes, but the TRAINING DATA would be attacker-
        # chosen).  Allowed — trusted private networks exist — but never
        # silently.
        print(
            f"fleet: WARNING — binding routable address "
            f"{args.fleet_address!r} WITHOUT --fleet-token: any host that "
            f"can reach this port can stream experience into training. "
            f"Set --fleet-token (docs/FLEET.md 'Authentication').",
            flush=True,
        )
        flight_event("fleet_unauthenticated_bind", address=args.fleet_address)
    heartbeat_s = (
        args.fleet_heartbeat
        if args.fleet_heartbeat is not None
        else fleet_transport.READ_DEADLINE_S
    )
    fleet_config = FleetConfig(
        num_actors=args.actors,
        address=args.fleet_address,
        queue_depth=args.fleet_queue_depth,
        publish_every=args.fleet_publish_every,
        idle_timeout_s=args.fleet_idle_timeout,
        shed_after_s=(
            args.fleet_shed_after
            if args.fleet_shed_after is not None
            else 1.0
        ),
        wire=wire_config,
        drain_coalesce=args.drain_coalesce,
        heartbeat_s=heartbeat_s,
        auth_token=fleet_token,
        shard_direct=bool(args.shard_direct),
        shard_pullers=args.shard_pullers,
        shard_prefetch=args.shard_prefetch,
    )
    # The ingest+sample+learn assembly comes from the validated Topology
    # (docs/TOPOLOGY.md): sharded rings + two-level sampling ->
    # SamplerLearner (composes with a dp-mesh trainer since ISSUE 11 —
    # the pulled [K, B] batch lands mesh-sharded via _put_staged);
    # central drain -> FleetLearner.  In sampler mode the shards own the
    # experiment's REAL replay capacity — captured by run() BEFORE it
    # shrank the trainer's unused device arena (one config resolution,
    # no chance to desynchronize).
    if topo is None:
        topo = topology.resolve(args)
    # Standalone shard tier (ISSUE 12, --shard-procs N): spawn the shard
    # processes FIRST (their address files appear asynchronously; every
    # learner-side dial waits them out), hand the RemoteShardSet to the
    # sampler learner in place of the in-learner loopback.
    shard_tier = None
    if args.shard_procs:
        from r2d2dpg_tpu.fleet.shard import ShardProcTier

        if args.logdir:
            shard_dir = os.path.join(args.logdir, "shards")
        else:
            import tempfile

            shard_dir = tempfile.mkdtemp(prefix="r2d2dpg_shards_")
        shard_tier = ShardProcTier(
            num_shards=args.replay_shards,
            num_procs=args.shard_procs,
            capacity_per_shard=replay_capacity // args.replay_shards,
            alpha=cfg.trainer.priority_alpha,
            prioritized=cfg.trainer.prioritized,
            dirpath=shard_dir,
            seed=cfg.trainer.seed,
            wire_config=wire_config,
            auth_token=fleet_token,
            max_frame_bytes=fleet_config.max_frame_bytes,
            heartbeat_s=heartbeat_s,
            chaos_spec=args.chaos_spec,
            flight_dir=args.logdir,
            # The shard tier joins the --obs-fleet plane at the actors'
            # cadence: every shard proc's registry lands in THIS
            # process's /metrics under shard=/host= labels (ISSUE 13).
            telem_every=1.0 if args.obs_fleet else 0.0,
        )
    learner = topology.build_fleet_learner(
        topo, trainer, fleet_config, replay_capacity=replay_capacity,
        shard_set=shard_tier.shard_set if shard_tier is not None else None,
    )
    # NB the tier's processes are SPAWNED inside the try below (beside the
    # actor supervisor): anything that can SystemExit before then — a
    # --resume with no checkpoint, a bind failure — must not orphan
    # shard processes whose only exit is the supervisor's stop.
    address = learner.start()
    print(
        f"fleet: ingest on {address}; spawning {args.actors} actors"
        + (
            f"; {args.replay_shards} replay shards (learner-pulled "
            f"sampling"
            + (
                f", {args.shard_procs} standalone shard procs"
                if args.shard_procs
                else ""
            )
            + ")"
            if args.replay_shards
            else ""
        ),
        flush=True,
    )
    # Learner recovery (docs/FLEET.md "Failure modes"): resume restores
    # the learner subtree into a fresh state and continues the monotone
    # counters from the checkpoint's sidecar; the arena is re-absorbed.
    resume_from = None
    if args.resume:
        step = ckpt.latest_step
        if step is None:
            raise SystemExit(
                f"--resume: no checkpoint found under {args.checkpoint_dir}"
            )
        state = dataclasses.replace(state, train=ckpt.restore(state))
        if hasattr(trainer, "_shardings"):
            # dp-mesh learner: the restored train subtree lands
            # single-device; re-place the state on the mesh layout so the
            # drain programs' inputs keep one device set (--learner-dp).
            import jax

            state = jax.device_put(state, trainer._shardings)
        resume_from = load_fleet_counters(args.checkpoint_dir, step)
        if not resume_from:
            print(
                f"fleet: WARNING — checkpoint step {step} has no counter "
                f"sidecar (pre-ISSUE-7 layout?); counters restart at 0",
                flush=True,
            )
        print(
            f"fleet: resumed learner from step {step} "
            f"(drained {int(resume_from.get('drained', 0))} phases, "
            f"env_steps {resume_from.get('env_steps_total', 0.0):.0f})",
            flush=True,
        )
    # Forward the RESOLVED config values (not the raw flags): the actors'
    # net/param-tree structure and exploration ladder must match the
    # learner's exactly, whichever side of an override they came from.
    # fleet/actor.py owns the flag list (one source, not hand-synced).
    from r2d2dpg_tpu.fleet.actor import structural_argv

    extra = structural_argv(cfg)
    # The wire lane mirrors --fleet-wire/--fleet-compress exactly: the
    # ingest server refuses a mismatched HELLO, so the spawner forwards
    # the negotiated values rather than trusting actor defaults.
    extra += [
        "--wire", args.fleet_wire,
        "--compress", args.fleet_compress,
        # Both ends of the lane enforce ONE frame ceiling: an actor packer
        # pinned to a different default would either FrameTooLarge-crash
        # on frames the server accepts or emit frames the server refuses.
        "--max-frame-bytes", str(learner.config.max_frame_bytes),
    ]
    if args.obs_fleet:
        # The ~1 Hz TELEM cadence: every actor's registry lands in THIS
        # process's /metrics under actor=/host= labels (ISSUE 6).
        extra += ["--telem-every", "1.0"]
    if args.trace_sample and not args.replay_shards:
        # Sharded ingest drops every SEQS trace sidecar (the sampler
        # records its own sample_req -> batch_return -> learn chain via
        # run_kwargs below), so forwarding the rate to actors there
        # would buy 32 wasted wire bytes per sampled frame and nothing.
        extra += ["--trace-sample", str(args.trace_sample)]
    # Liveness: one deadline per fleet, both wire ends (docs/FLEET.md).
    extra += ["--read-deadline", str(heartbeat_s)]
    if args.shard_direct:
        # The direct data plane (ISSUE 17): actors dial the shard the
        # ingest ack advertises and ship SEQS to it directly.
        extra += ["--shard-direct", "1"]
    if args.chaos_spec:
        # Actors fire the stall/corrupt faults that target their id; the
        # learner's engine fires the rest — same seeded schedule.
        extra += ["--chaos-spec", args.chaos_spec]
    spawn_env = None
    if fleet_token:
        # Via the environment, NOT argv: a command-line token would be
        # visible to every user on the host in ps/procfs.
        spawn_env = dict(os.environ)
        spawn_env["R2D2DPG_FLEET_TOKEN"] = fleet_token

    # The GLOBAL sigma-ladder width (ISSUE 16): every lane the autoscaler
    # may ever mint needs its own exploration sigma, so actors spawn with
    # --num-actors max(--actors, --autoscale-max) and slice that ladder.
    # Chaos fault hashing rides the same value on BOTH wire ends (the
    # learner's engine and each actor's ActorChaos must agree on every
    # fault's target).  With --autoscale 0 this is exactly --actors — the
    # structural-inertness anchor.
    ladder_n = max(args.actors, args.autoscale_max if args.autoscale else 0)

    def argv_fn(i: int):
        argv = default_actor_argv(
            i,
            config_name=args.config,
            address=address,
            num_actors=ladder_n,
            seed=cfg.trainer.seed,
            extra=extra,
        )
        if args.logdir:
            argv += [
                "--flight-path",
                os.path.join(args.logdir, f"flight_actor{i}.jsonl"),
            ]
        return argv

    sup_config = SupervisorConfig()
    if args.autoscale and not args.autoscale_dry_run:
        # Crash recovery becomes a DECISION: the ladder records the crash
        # and leaves the slot down for the policy loop's spawn_slot (a
        # dry run keeps the reflexive ladder — observe, don't own).
        sup_config = dataclasses.replace(sup_config, restart="policy")
    supervisor = ActorSupervisor(
        argv_fn,
        args.actors,
        config=sup_config,
        env=spawn_env,
        log_path_fn=(
            (lambda i: os.path.join(args.logdir, f"actor{i}.log"))
            if args.logdir
            else None
        ),
    )
    engine = None
    if chaos_faults:
        engine = fleet_chaos.ChaosEngine(
            chaos_faults,
            seed=cfg.trainer.seed,
            num_actors=ladder_n,
            supervisor=supervisor,
            server=learner.server,
            shard_tier=shard_tier,
        )
    autoscaler = None
    if args.autoscale:
        from r2d2dpg_tpu.fleet.autoscaler import AutoscaleConfig, Autoscaler

        # Reuse the exporter's armed engine when --obs-port is up (the
        # health plane was built re-entrant for exactly this: the policy
        # loop racing an operator's curl); arm a private one otherwise.
        health = getattr(obs.current_exporter(), "health", None)
        if health is None:
            health = obs.HealthEngine(
                _health_config(args),
                registry=obs.get_registry(),
                mirror=obs.get_remote_mirror(),
            )
        autoscaler = Autoscaler(
            health,
            supervisor,
            shard_tier=shard_tier,
            config=AutoscaleConfig(
                min_actors=args.autoscale_min,
                max_actors=args.autoscale_max or args.actors,
                cooldown_s=args.autoscale_cooldown,
                eval_every_s=args.autoscale_every,
                fire_threshold=args.autoscale_fire,
                dry_run=bool(args.autoscale_dry_run),
            ),
            ready_fn=lambda: learner.server.is_steady,
            expected_fn=learner.server.set_expected_actors,
        )

    if args.phases is not None:
        num_phases = args.phases
    elif args.minutes is not None:
        num_phases = 10**9  # the wall-clock budget is the stop condition
    else:
        num_phases = 1  # nothing requested: single-train-phase smoke

    final: dict = {}
    metrics_fn = _make_executor_metrics_fn(logger, watchdog, final)

    run_kwargs = {}
    if args.replay_shards:
        # The sampler learner records its own trace hops (sample_req ->
        # batch_return -> learn); the central drain's hops ride the SEQS
        # sidecar instead, so only the sampler takes the rate directly.
        run_kwargs["trace_sample"] = args.trace_sample
    try:
        if shard_tier is not None:
            shard_tier.start()
        supervisor.start()
        if autoscaler is not None:
            autoscaler.start()
        state = learner.run(
            num_phases,
            state=state,
            log_every=args.log_every,
            metrics_fn=metrics_fn,
            minutes=args.minutes,
            ckpt=ckpt,
            checkpoint_every=args.checkpoint_every,
            resume_from=resume_from,
            phase_fn=engine.on_phase if engine is not None else None,
            **run_kwargs,
        )
        # Supervisor/policy/tier counters join the learner's stats BEFORE
        # the fold so they ride the printed ``fleet:`` line too — the
        # subprocess bench legs parse that line, not the metrics dict.
        fstats = dict(learner.stats())
        fstats["actor_restarts"] = float(supervisor.restarts_total)
        if autoscaler is not None:
            a_stats = autoscaler.stats()
            fstats["autoscale_actions"] = float(
                sum(a_stats["autoscale_actions"].values())
            )
            fstats["autoscale_decisions"] = float(
                a_stats["autoscale_decisions"]
            )
            fstats["autoscale_target"] = float(a_stats["autoscale_target"])
        if shard_tier is not None:
            fstats["shard_restarts"] = float(shard_tier.restarts_total)
        _fold_executor_stats("fleet", fstats, final)
        if engine is not None and engine.unfired():
            # A drill that never got its phase must not read as one that
            # passed: name it loudly in the log and the flight ring.
            names = [f"{f.kind}@p{f.phase}" for f in engine.unfired()]
            print(
                f"fleet: WARNING — chaos faults never fired (run too "
                f"short?): {', '.join(names)}",
                flush=True,
            )
            flight_event("chaos_unfired", faults=names)
        if ckpt is not None and ckpt.save_every:
            from r2d2dpg_tpu.fleet.ingest import (
                prune_fleet_counters,
                save_fleet_counters,
            )

            step = int(state.phase_idx)
            ckpt.save_final(step, state)
            # The final counters sidecar: what a later --resume continues.
            save_fleet_counters(ckpt.directory, step, learner.counters())
            # The final save may have pushed an old orbax step past
            # max_to_keep: prune its sidecar too, or the two drift on disk.
            ckpt.wait()
            prune_fleet_counters(ckpt.directory, ckpt.all_steps())
    except DivergenceError as e:
        _abort_on_divergence(e, flight, flight_path, ckpt)
    finally:
        if args.logdir:
            # The run's FINAL merged scrape + /health verdict as durable
            # evidence (ISSUE 13): lib_gate.sh shard_gate refuses
            # --shard-procs evidence whose scrape lacks a live shard's
            # labelled series, and bench stamps the end-of-run verdict —
            # both read these files, no live exporter needed post-run.
            # Written BEFORE the supervisors stop: the verdict must
            # describe the RUN's end state, not the teardown's (stopped
            # supervisors read alive=0, which would stamp every clean
            # exit as critical/shards_down).
            try:
                snap = obs.get_registry().snapshot()
                sources = obs.get_remote_mirror().sources()
                if sources:
                    snap = obs.merge_remote(snap, sources)
                with open(
                    os.path.join(args.logdir, "metrics_final.prom"), "w"
                ) as f:
                    f.write(obs.render_prometheus(snap))
                engine = getattr(obs.current_exporter(), "health", None)
                if engine is None:
                    # No armed exporter engine (e.g. no --obs-port):
                    # judge with the run's resolved config anyway —
                    # defaults would disarm actors_down/shards_down.
                    engine = obs.HealthEngine(
                        _health_config(args),
                        registry=obs.get_registry(),
                        mirror=obs.get_remote_mirror(),
                    )
                with open(
                    os.path.join(args.logdir, "health_final.json"), "w"
                ) as f:
                    json.dump(engine.evaluate(), f, default=str)
                # The experience-quality plane's end-of-run state (ISSUE
                # 18): lag/age distributions, ESS/saturation, per-actor
                # trained counts, per-shard untrained-eviction fractions.
                # lib_gate.sh quality_gate reads this beside
                # health_final.json.
                with open(
                    os.path.join(args.logdir, "quality_final.json"), "w"
                ) as f:
                    json.dump(
                        obs.get_quality_plane().snapshot_final(),
                        f,
                        default=str,
                    )
            except Exception as e:  # noqa: BLE001 — evidence is optional,
                # the teardown below it is NOT: an exception escaping this
                # finally block would skip supervisor/shard-tier/learner
                # teardown (orphaning their process groups) and mask the
                # run's own error.  Loud note, never a raise.
                print(f"obs: final evidence stamp failed: {e!r}", flush=True)
        # Autoscaler FIRST of all: a policy tick racing the teardown
        # would read stopped supervisors as a fleet to repopulate.
        if autoscaler is not None:
            autoscaler.stop()
        # Supervisor FIRST (its stopping flag makes the actors' connection
        # loss an orderly exit, not a crash to restart), then the SHARD
        # TIER (its stop flag releases any ingest handler parked in the
        # tier-down wait inside RemoteShardSet.add — closing the ingest
        # server first would eat a join timeout per wedged handler and
        # log false handler leaks), then the ingest server.
        supervisor.stop()
        if shard_tier is not None:
            shard_tier.stop()
        learner.close()
        # Sampled spans -> trace.json next to flight.jsonl (no-op when
        # tracing is off or no dump path is armed).
        flight.dump_trace()
        if ckpt is not None:
            ckpt.wait()
            ckpt.close()
        logger.close()
    if chaos_faults and args.logdir:
        # Actor-boundary drills fire in the ACTOR processes; their
        # evidence is the chaos_inject lines in the flight_actor*.jsonl
        # dumps the teardown above just flushed.  A fault with no such
        # line never fired (run too short, target crashed first) and must
        # not read as a drill that passed — same contract as
        # ChaosEngine.unfired() for the learner-side faults.
        missing = fleet_chaos.actor_faults_unfired(
            chaos_faults,
            args.logdir,
            seed=cfg.trainer.seed,
            num_actors=ladder_n,
        )
        if args.shard_procs:
            # Shard-process-boundary drills (stall_shard) fire in the
            # SHARD processes; the same no-evidence-means-unfired
            # contract applies to their flight_shard*.jsonl dumps.
            missing += fleet_chaos.shard_faults_unfired(
                chaos_faults,
                args.logdir,
                seed=cfg.trainer.seed,
                num_shard_procs=args.shard_procs,
            )
        if missing:
            names = [f"{f.kind}@p{f.phase}" for f in missing]
            print(
                f"fleet: WARNING — actor/shard-side chaos faults left no "
                f"injection evidence in {args.logdir!r} (run too short? "
                f"target kept crashing?): {', '.join(names)}",
                flush=True,
            )
            flight_event("chaos_unfired", faults=names)
    return final


def main(argv=None):
    args = parse_args(argv)
    from r2d2dpg_tpu.utils.startup import enable_compile_cache

    enable_compile_cache()
    run(args)


if __name__ == "__main__":
    main()
