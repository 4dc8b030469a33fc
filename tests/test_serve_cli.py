"""serve CLI: flag plumbing (fast) and the stdio/selftest loops (slow,
subprocess — covers the ``python -m r2d2dpg_tpu serve`` dispatch too)."""

import json
import os
import subprocess
import sys

import pytest

from r2d2dpg_tpu.serve import build_service, parse_args

pytestmark = pytest.mark.serving

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_parse_args_plumbing():
    args = parse_args(
        [
            "--config", "pendulum_tiny", "--checkpoint-dir", "ck",
            "--bucket-sizes", "2,8", "--flush-ms", "1.5", "--max-queue", "7",
            "--max-sessions", "3", "--session-ttl", "9", "--poll-every", "0.5",
        ]
    )
    assert args.config == "pendulum_tiny" and args.checkpoint_dir == "ck"
    assert args.bucket_sizes == "2,8" and args.flush_ms == 1.5
    assert (args.max_queue, args.max_sessions) == (7, 3)
    assert (args.session_ttl, args.poll_every) == (9.0, 0.5)
    assert args.serve_workers == 1  # scale-out is opt-in
    assert parse_args(
        ["--config", "pendulum_tiny", "--checkpoint-dir", "ck",
         "--serve-workers", "4"]
    ).serve_workers == 4


def _cli_args(ckpt_dir, *extra):
    return parse_args(
        ["--config", "pendulum_tiny", "--checkpoint-dir", ckpt_dir,
         "--bucket-sizes", "1,2", "--flush-ms", "1", *extra]
    )


def test_build_service_workers_flag_selects_plain_service_or_router(ckpt_dir):
    """Structural half of the off-setting anchor: ``--serve-workers 1``
    (default or explicit) builds the PR-1 single-worker PolicyService with
    NO router and NO worker label in the path; ``--serve-workers N``
    builds the session-affine router over N labelled per-device workers
    sharing one fanout reloader."""
    from r2d2dpg_tpu.serving import PolicyService, ServiceRouter
    from r2d2dpg_tpu.serving.router import FanoutReloader

    for argv_extra in ((), ("--serve-workers", "1")):
        svc, _env = build_service(_cli_args(ckpt_dir, *argv_extra))
        assert type(svc) is PolicyService
        assert svc.worker_label is None and svc.device is None

    router, _env = build_service(_cli_args(ckpt_dir, "--serve-workers", "2"))
    assert type(router) is ServiceRouter and router.num_workers == 2
    fanouts = set()
    for w, svc in enumerate(router.services):
        assert svc.worker_label == str(w)
        assert svc.device is not None
        fanouts.add(id(svc.reloader._fanout))
        assert isinstance(svc.reloader._fanout, FanoutReloader)
    assert len(fanouts) == 1, "workers must share ONE checkpoint poller"


def test_serve_workers_1_bit_identical_to_pr1_path(ckpt_dir):
    """Determinism half of the anchor: the CLI-built ``--serve-workers 1``
    service serves the exact bits a directly-constructed PR-1
    PolicyService serves for the same traffic."""
    import numpy as np

    from r2d2dpg_tpu.configs import get_config
    from r2d2dpg_tpu.serving import CheckpointHotReloader, PolicyService
    from r2d2dpg_tpu.serving.reload import actor_params_template

    cfg = get_config("pendulum_tiny")
    env = cfg.env_factory()
    actor = cfg.build_agent(env).actor
    obs_shape = tuple(env.spec.obs_shape)
    rng = np.random.default_rng(5)
    sids = ["a", "b", "c"]
    obs = {
        s: rng.standard_normal((3,) + obs_shape).astype(np.float32)
        for s in sids
    }

    def drive(service):
        got = {s: [] for s in sids}
        with service:
            for t in range(3):
                pending = [
                    (s, service.act_async(s, obs[s][t], reset=(t == 0)))
                    for s in sids
                ]
                for s, req in pending:
                    assert req.wait(30.0) and req.code == "ok", req.code
                    got[s].append(req.action)
        return got

    via_cli, _ = build_service(_cli_args(ckpt_dir, "--serve-workers", "1"))
    pr1 = PolicyService(
        actor,
        obs_shape=obs_shape,
        bucket_sizes=(1, 2),
        flush_ms=1.0,
        reloader=CheckpointHotReloader(
            ckpt_dir, actor_params_template(actor, obs_shape),
            poll_every_s=2.0,
        ),
    )
    got_cli, got_pr1 = drive(via_cli), drive(pr1)
    for s in sids:
        for t in range(3):
            np.testing.assert_array_equal(got_cli[s][t], got_pr1[s][t])


@pytest.fixture(scope="module")
def ckpt_dir(tmp_path_factory):
    """A real pendulum_tiny light checkpoint for the subprocess to serve."""
    from r2d2dpg_tpu.configs import get_config
    from r2d2dpg_tpu.utils.checkpoint import CheckpointManager

    cfg = get_config("pendulum_tiny")
    state = cfg.build().init()
    d = str(tmp_path_factory.mktemp("serve") / "ckpt")
    mgr = CheckpointManager(d, save_every=1, light=True)
    mgr.save(5, state)
    mgr.wait()
    mgr.close()
    return d


def _serve_env():
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.setdefault("R2D2DPG_PALLAS_INTERPRET", "1")
    return env


@pytest.mark.slow
def test_serve_stdio_loop_end_to_end(ckpt_dir):
    lines = "\n".join(
        [
            json.dumps({"session": "u1", "obs": [0.1, 0.2, 0.3], "reset": True}),
            json.dumps({"session": "u1", "obs": [0.2, 0.3, 0.4]}),
            json.dumps({"cmd": "health"}),
            json.dumps({"cmd": "end_session", "session": "u1"}),
            "not json",
            # Valid JSON, poisonous payloads: each must answer THIS client
            # with a code, not crash the server (np.asarray raises on
            # strings; a non-object line has no .get).
            json.dumps({"session": "u9", "obs": ["boom"]}),
            json.dumps([1, 2, 3]),
            json.dumps({"cmd": "quit"}),
        ]
    )
    proc = subprocess.run(
        [sys.executable, "-m", "r2d2dpg_tpu", "serve",
         "--config", "pendulum_tiny", "--checkpoint-dir", ckpt_dir,
         "--flush-ms", "1", "--selftest", "0"],
        input=lines, capture_output=True, text=True, cwd=HERE,
        env=_serve_env(), timeout=300,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    out = [json.loads(l) for l in proc.stdout.strip().splitlines()]
    assert len(out) == 7
    act1, act2, health, ended, bad_json, bad_obs, bad_type = out
    assert act1["code"] == "ok" and len(act1["action"]) == 1
    assert act1["params_step"] == 5 and act2["code"] == "ok"
    assert health["params_step"] == 5 and health["requests_ok"] == 2
    assert ended == {"code": "ok", "released": True}
    assert bad_json["code"] == "bad_request"
    assert bad_obs["code"] == "bad_request" and "ValueError" in bad_obs["error"]
    assert bad_type["code"] == "bad_request"


@pytest.mark.slow
def test_serve_selftest_smoke(ckpt_dir):
    proc = subprocess.run(
        [sys.executable, "-m", "r2d2dpg_tpu", "serve",
         "--config", "pendulum_tiny", "--checkpoint-dir", ckpt_dir,
         "--flush-ms", "1", "--selftest", "24"],
        capture_output=True, text=True, cwd=HERE, env=_serve_env(),
        timeout=300,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    rec = json.loads(proc.stdout.strip().splitlines()[-1])
    assert rec["selftest"] == 24
    assert rec["codes"] == {"ok": 24}
    assert rec["params_step"] == 5 and rec["sessions_active"] == 8
