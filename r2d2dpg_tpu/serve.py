"""Serving entry point: ``python -m r2d2dpg_tpu serve --config ... --checkpoint-dir ...``

Stands up a ``PolicyService`` (serving/) over the latest checkpoint of a
training run and speaks newline-delimited JSON on stdio — dependency-free,
scriptable, and enough to drive the service from any language or a shell
pipe while the learner keeps writing new checkpoints into the same dir:

    {"session": "u1", "obs": [..], "reset": true}
        -> {"code": "ok", "action": [..], "params_step": 1500, "latency_ms": 1.9}
    {"cmd": "health"}        -> the HealthSnapshot as JSON
    {"cmd": "end_session", "session": "u1"}   -> {"code": "ok", "released": true}
    {"cmd": "quit"}          -> exits after draining

``--selftest N`` instead drives N synthetic requests through the full
stack (sessions x buckets x hot-reload poll), prints the final health
snapshot and exits non-zero unless every request was answered ``ok`` — a
one-command smoke of the serving path on any box.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import sys

from r2d2dpg_tpu.configs import CONFIGS, get_config
from r2d2dpg_tpu.utils.codes import OK


def parse_args(argv=None) -> argparse.Namespace:
    p = argparse.ArgumentParser(
        prog="python -m r2d2dpg_tpu serve", description=__doc__
    )
    p.add_argument("--config", required=True, choices=sorted(CONFIGS))
    p.add_argument(
        "--checkpoint-dir", required=True,
        help="training run's checkpoint dir; also watched for hot-reload"
    )
    p.add_argument(
        "--compute-dtype", default=None, choices=["float32", "bfloat16"],
        help="must match the checkpoint's train-time setting (the LSTM "
        "cell's param tree is dtype-specific)"
    )
    # Batching / latency knobs (docs/SERVING.md "Knobs").
    p.add_argument(
        "--bucket-sizes", default="1,2,4,8,16,32",
        help="comma-separated pad-to-bucket sizes (one compile each)"
    )
    p.add_argument(
        "--flush-ms", type=float, default=5.0,
        help="max time the batcher waits for stragglers before launching"
    )
    p.add_argument(
        "--max-queue", type=int, default=256,
        help="admission bound; beyond it requests shed with shed_queue_full"
    )
    # Scale-out (docs/SERVING.md "Scale-out").
    p.add_argument(
        "--serve-workers", type=int, default=1, metavar="N",
        help="worker services behind the session-affine router, one device "
        "each (forced host devices on CPU, one chip each on a real mesh); "
        "1 = the single-worker PolicyService path, no router (the "
        "off-setting determinism anchor)"
    )
    # Sessions.
    p.add_argument(
        "--max-sessions", type=int, default=1024,
        help="session-slab capacity PER WORKER"
    )
    p.add_argument(
        "--session-ttl", type=float, default=300.0,
        help="seconds of idleness before a session's slot is reclaimed"
    )
    # Hot-reload / observability.
    p.add_argument(
        "--poll-every", type=float, default=2.0,
        help="seconds between checkpoint-dir polls for new params"
    )
    p.add_argument("--logdir", default=None, help="health metrics CSV/TB dir")
    p.add_argument(
        "--log-every-s", type=float, default=10.0,
        help="seconds between health rows written to --logdir"
    )
    p.add_argument(
        "--obs-port", type=int, default=None, metavar="PORT",
        help="serve the telemetry registry over HTTP: /metrics (Prometheus "
        "text, incl. the r2d2dpg_serving_* health gauges) + /metrics.json; "
        "0 binds an ephemeral port (printed to stderr)"
    )
    p.add_argument(
        "--obs-host", default="0.0.0.0",
        help="interface the --obs-port exporter binds (127.0.0.1 = "
        "loopback-only on shared hosts)"
    )
    p.add_argument(
        "--flight-path", default=None,
        help="flight-recorder dump path (default <logdir>/flight.jsonl, "
        "or ./flight.jsonl without --logdir)"
    )
    p.add_argument(
        "--selftest", type=int, default=0, metavar="N",
        help="drive N synthetic requests through the service and exit"
    )
    return p.parse_args(argv)


def build_service(args):
    """Construct the serving front end from CLI flags.

    ``--serve-workers 1`` (the default) builds the single-worker
    ``PolicyService`` exactly as PR 1 did — no router in the path, which is
    what the off-setting determinism anchor pins.  ``--serve-workers N``
    replicates the service N times (one device, slab, batcher, and compiled
    step each) behind the session-affine ``ServiceRouter``.
    """
    from r2d2dpg_tpu.serving import (
        CheckpointHotReloader,
        PolicyService,
        build_router,
    )
    from r2d2dpg_tpu.serving.reload import actor_params_template
    from r2d2dpg_tpu.utils import MetricLogger

    cfg = get_config(args.config)
    if args.compute_dtype is not None:
        cfg = dataclasses.replace(cfg, compute_dtype=args.compute_dtype)
    env = cfg.env_factory()
    actor = cfg.build_agent(env).actor
    obs_shape = tuple(env.spec.obs_shape)

    reloader = CheckpointHotReloader(
        args.checkpoint_dir,
        actor_params_template(actor, obs_shape),
        poll_every_s=args.poll_every,
    )
    logger = MetricLogger(args.logdir) if args.logdir else None
    workers = int(getattr(args, "serve_workers", 1) or 1)
    if workers < 1:
        raise SystemExit(f"--serve-workers must be >= 1, got {workers}")
    if workers > 1:
        # No CSV MetricLogger in routed mode: N workers would interleave
        # rows in one file.  The labelled r2d2dpg_serve_* registry family
        # (scrape via --obs-port) and the flight recorder carry per-worker
        # telemetry instead.
        service = build_router(
            actor,
            num_workers=workers,
            obs_shape=obs_shape,
            reloader=reloader,
            bucket_sizes=[int(b) for b in args.bucket_sizes.split(",")],
            max_queue=args.max_queue,
            flush_ms=args.flush_ms,
            max_sessions=args.max_sessions,
            session_ttl_s=args.session_ttl,
        )
        return service, env
    service = PolicyService(
        actor,
        obs_shape=obs_shape,
        bucket_sizes=[int(b) for b in args.bucket_sizes.split(",")],
        max_queue=args.max_queue,
        flush_ms=args.flush_ms,
        max_sessions=args.max_sessions,
        session_ttl_s=args.session_ttl,
        reloader=reloader,
        logger=logger,
        log_every_s=args.log_every_s,
    )
    return service, env


def _health_dict(service) -> dict:
    """JSON-ready health: a PolicyService returns a dataclass snapshot, a
    ServiceRouter an aggregate dict (with per_worker snapshots) already."""
    snap = service.health()
    return snap if isinstance(snap, dict) else dataclasses.asdict(snap)


def _serve_stdio(service) -> None:
    """The JSONL request loop (one line in, one line out, order-preserving)."""
    for line in sys.stdin:
        line = line.strip()
        if not line:
            continue
        try:
            msg = json.loads(line)
        except json.JSONDecodeError as e:
            print(json.dumps({"code": "bad_request", "error": str(e)}), flush=True)
            continue
        if not isinstance(msg, dict):
            print(json.dumps({"code": "bad_request",
                              "error": "request must be a JSON object"}),
                  flush=True)
            continue
        cmd = msg.get("cmd")
        if cmd == "quit":
            break
        if cmd == "health":
            print(json.dumps(_health_dict(service)), flush=True)
            continue
        if cmd == "end_session":
            released = service.end_session(str(msg.get("session", "")))
            print(json.dumps({"code": "ok", "released": released}), flush=True)
            continue
        try:
            res = service.act(
                str(msg.get("session", "")),
                msg.get("obs", []),
                reset=bool(msg.get("reset", False)),
            )
            out = {"code": res.code, "params_step": res.params_step,
                   "latency_ms": round(res.latency_s * 1e3, 3)}
            if res.action is not None:
                out["action"] = [float(a) for a in res.action]
        except Exception as e:  # noqa: BLE001 — one bad payload (e.g.
            # non-numeric obs failing np.asarray) must answer THIS client,
            # not take the server and every live session down.
            out = {"code": "bad_request", "error": f"{type(e).__name__}: {e}"}
        print(json.dumps(out), flush=True)


def _selftest(service, obs_shape, n: int) -> bool:
    """Drive n synthetic requests (8 interleaved sessions) and print health.
    True only when every request came back ``ok``."""
    import numpy as np

    rng = np.random.default_rng(0)
    pending = []
    for i in range(n):
        sid = f"selftest-{i % 8}"
        pending.append(
            service.act_async(
                sid, rng.standard_normal(obs_shape).astype(np.float32),
                reset=(i < 8),
            )
        )
    codes: dict = {}
    for req in pending:
        req.wait(60.0)
        codes[req.code] = codes.get(req.code, 0) + 1
    print(json.dumps({"selftest": n, "codes": codes,
                      **_health_dict(service)}), flush=True)
    return codes == {OK: n}


def main(argv=None) -> None:
    args = parse_args(argv)
    import os

    import jax

    from r2d2dpg_tpu import obs
    from r2d2dpg_tpu.utils.startup import enable_compile_cache

    enable_compile_cache()
    flight_path = args.flight_path or (
        os.path.join(args.logdir, "flight.jsonl")
        if args.logdir
        else "flight.jsonl"
    )
    if args.logdir or args.flight_path:
        # Same gating as train.py: arm the exit-time dump only when the
        # operator named a destination.
        obs.get_flight_recorder().install(flight_path)
    if args.obs_port is not None:
        exporter = obs.start_exporter(args.obs_port, host=args.obs_host)
        # A serving process has no actor fleet: arm /health without the
        # fleet-telemetry expectation so the serve_* rules judge it alone.
        exporter.arm_health(
            obs.HealthEngine(obs.HealthConfig(telem_expected=False))
        )
        print(
            f"obs: /metrics + /metrics.json + /health on port {exporter.port}",
            file=sys.stderr,
            flush=True,
        )

    service, env = build_service(args)
    # Same backend stamp train.py prints — automation gates on it.
    print(f"backend: {jax.default_backend()}", file=sys.stderr, flush=True)
    passed = True
    with service:
        if args.selftest:
            passed = _selftest(
                service, tuple(env.spec.obs_shape), args.selftest
            )
        else:
            _serve_stdio(service)
    if not passed:
        raise SystemExit("selftest: not every request was answered ok")


if __name__ == "__main__":
    main()
