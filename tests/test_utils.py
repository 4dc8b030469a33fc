"""Aux-subsystem tests (SURVEY.md §5): metrics, checkpoint/resume, profiling,
evaluator, and the CLI entry."""

import csv
import dataclasses
import glob
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from r2d2dpg_tpu.configs import PENDULUM_TINY, get_config
from r2d2dpg_tpu.training.evaluator import Evaluator
from r2d2dpg_tpu.utils import CheckpointManager, MetricLogger, profile_trace
from r2d2dpg_tpu.utils.checkpoint import resume_state


# --------------------------------------------------------------------- metrics
def test_metric_logger_csv_and_rates(tmp_path):
    logdir = str(tmp_path / "run")
    with MetricLogger(logdir, stdout=False, tensorboard=False) as log:
        log.log(1, {"a": 1.0})
        r = log.rates(env_steps=0.0)
        assert r == {}  # first call: no previous sample
        r = log.rates(env_steps=100.0)
        assert r["env_steps_per_sec"] > 0
        # New key appears later: header must grow without losing old rows.
        log.log(2, {"a": 2.0, "b": 7.0})
    with open(os.path.join(logdir, "metrics.csv")) as f:
        rows = list(csv.DictReader(f))
    assert len(rows) == 2
    assert rows[0]["a"] == "1.0" and rows[0]["b"] == ""
    assert rows[1]["b"] == "7.0"
    assert float(rows[1]["wall_seconds"]) >= float(rows[0]["wall_seconds"])


def test_metric_logger_resume_appends_and_continues_wallclock(tmp_path):
    logdir = str(tmp_path / "run")
    with MetricLogger(logdir, stdout=False, tensorboard=False) as log:
        log.log(1, {"a": 1.0})
    with MetricLogger(logdir, stdout=False, tensorboard=False) as log:
        log.log(2, {"a": 2.0})
    with open(os.path.join(logdir, "metrics.csv")) as f:
        rows = list(csv.DictReader(f))
    assert [r["step"] for r in rows] == ["1", "2"]
    # Wall clock continues monotonically across the restart.
    assert float(rows[1]["wall_seconds"]) >= float(rows[0]["wall_seconds"])


def test_metric_logger_no_logdir_is_stdout_only(capsys):
    log = MetricLogger(None)
    log.log(5, {"x": 1.5})
    assert "[5]" in capsys.readouterr().out
    log.close()


# ------------------------------------------------------------------- profiling
def test_profile_trace_writes_trace(tmp_path):
    logdir = str(tmp_path / "prof")
    with profile_trace(logdir):
        jnp.ones((8, 8)).sum().block_until_ready()
    assert glob.glob(os.path.join(logdir, "**", "*.xplane.pb"), recursive=True)


def test_profile_trace_disabled_is_noop(tmp_path):
    with profile_trace(None):
        pass
    with profile_trace(str(tmp_path / "x"), enabled=False):
        pass
    assert not (tmp_path / "x").exists()


# ------------------------------------------------------------------ checkpoint
def _tree_allclose(a, b):
    flat_a = jax.tree_util.tree_leaves(a)
    flat_b = jax.tree_util.tree_leaves(b)
    assert len(flat_a) == len(flat_b)
    for x, y in zip(flat_a, flat_b):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y))


def test_checkpoint_roundtrip_and_resume(tmp_path):
    trainer = PENDULUM_TINY.build()
    state = trainer.init()
    for _ in range(trainer.window_fill_phases):
        state = trainer.collect_phase(state)
    state = trainer.fill_phase(state)

    ckpt = CheckpointManager(str(tmp_path / "ckpt"), save_every=2)
    assert not ckpt.maybe_save(3, state)  # off-cadence
    assert ckpt.maybe_save(4, state)
    ckpt.wait()
    assert ckpt.latest_step == 4

    restored = resume_state(trainer, ckpt)
    _tree_allclose(state, restored)

    # Bit-exact resume: both copies advance identically (pure-JAX env).
    s1, m1 = trainer.train_phase(state)
    s2, m2 = trainer.train_phase(restored)
    _tree_allclose(m1, m2)
    _tree_allclose(s1.train.actor_params, s2.train.actor_params)
    ckpt.close()


@pytest.mark.parametrize("saved", ["rows_own_shape", "another_row"])
def test_restore_converts_an_arena_saved_in_the_rows_own_shape(tmp_path, saved):
    """A checkpoint from before the arena stored large rows as tiles holds
    the pixel leaf as ``[capacity, L, H, W, C]``: restored into today's
    arena it is the same rows, reshaped; a replay leaf of another row size
    is refused by name."""
    import jax.numpy as jnp

    from r2d2dpg_tpu.replay.arena import ReplayArena, SequenceBatch

    n, L, frame = 3, 4, (64, 96, 3)
    rng = np.random.default_rng(0)
    rows = SequenceBatch(
        obs=jnp.asarray(rng.integers(0, 256, (n, L) + frame, dtype=np.uint8)),
        action=jnp.asarray(rng.standard_normal((n, L, 2)), jnp.float32),
        reward=jnp.zeros((n, L)), discount=jnp.ones((n, L)),
        reset=jnp.zeros((n, L)), carries={})
    arena = ReplayArena(capacity=4)
    state = {"arena": arena.add(arena.init_state(rows), rows, jnp.ones(n))}
    assert state["arena"].data.obs.parts[0].shape == (4, 18, 32, 128)
    old_shape = (4, L) + (frame if saved == "rows_own_shape" else (64, 96, 4))
    old_obs = jnp.zeros(old_shape, jnp.uint8).at[:n].set(
        rows.obs if saved == "rows_own_shape" else 0)
    old = {"arena": dataclasses.replace(
        state["arena"], data=dataclasses.replace(state["arena"].data, obs=old_obs))}

    ckpt = CheckpointManager(str(tmp_path / "ckpt"), save_every=1)
    ckpt.save(1, old)
    ckpt.wait()
    if saved == "another_row":
        with pytest.raises(ValueError, match=r"data\.obs.*older storage shape"):
            ckpt.restore(state)
    else:
        restored = ckpt.restore(state)
        _tree_allclose(restored, state)
        assert restored["arena"].data.obs.parts[0].shape == (4, 18, 32, 128)
        got = arena.gather(restored["arena"], jnp.arange(n))
        np.testing.assert_array_equal(np.asarray(got.obs), np.asarray(rows.obs))
    ckpt.close()


@pytest.mark.parametrize(
    "saved", ["rows_own_shape", "another_row", "another_dtype", "another_capacity"])
def test_restore_splits_a_small_leaf_saved_in_the_rows_own_shape(tmp_path, saved):
    """A checkpoint from before the arena stored a small row as its whole
    lane-rows and the rest holds walker's observations as ``[capacity, 43,
    24]``: restored into today's ``[capacity, 1024]`` and ``[capacity, 8]``
    parts it is the same rows, in the same dtype at the same capacity.  A
    leaf of another row (``[43, 25]``), of another dtype or of another
    capacity is refused by name."""
    import jax.numpy as jnp

    from r2d2dpg_tpu.replay.arena import ReplayArena, SequenceBatch

    n, L, capacity = 3, 43, 4
    rng = np.random.default_rng(2)
    rows = SequenceBatch(
        obs=jnp.asarray(rng.standard_normal((n, L, 24)), jnp.float32),
        action=jnp.asarray(rng.standard_normal((n, L, 6)), jnp.float32),
        reward=jnp.zeros((n, L)), discount=jnp.ones((n, L)),
        reset=jnp.zeros((n, L)), carries={})
    arena = ReplayArena(capacity=capacity)
    state = {"arena": arena.add(arena.init_state(rows), rows, jnp.ones(n))}
    assert [p.shape for p in state["arena"].data.obs.parts] == [
        (capacity, 1024), (capacity, 8)]
    old_obs = {
        "rows_own_shape": jnp.zeros((capacity, L, 24)).at[:n].set(rows.obs),
        "another_row": jnp.zeros((capacity, L, 25)),
        "another_dtype": jnp.zeros((capacity, L, 24), jnp.bfloat16),
        "another_capacity": jnp.zeros((2 * capacity, L, 24)),
    }[saved]
    old = {"arena": dataclasses.replace(
        state["arena"], data=dataclasses.replace(state["arena"].data, obs=old_obs))}

    ckpt = CheckpointManager(str(tmp_path / "ckpt"), save_every=1)
    ckpt.save(1, old)
    ckpt.wait()
    if saved != "rows_own_shape":
        with pytest.raises(ValueError, match=r"data\.obs.*older storage shape"):
            ckpt.restore(state)
    else:
        restored = ckpt.restore(state)
        _tree_allclose(restored, state)
        parts = restored["arena"].data.obs.parts
        assert [(p.shape, p.dtype) for p in parts] == [
            ((capacity, 1024), jnp.float32), ((capacity, 8), jnp.float32)]
        np.testing.assert_array_equal(
            np.concatenate([np.asarray(p) for p in parts], axis=1),
            np.asarray(old_obs).reshape(capacity, -1))
        got = arena.gather(restored["arena"], jnp.arange(n))
        np.testing.assert_array_equal(np.asarray(got.obs), np.asarray(rows.obs))
    ckpt.close()


def test_light_checkpoint_roundtrip_resume_and_eval(tmp_path):
    """Light mode stores only the learner subtree: resume_state grafts it
    onto a fresh state (replay/schedule restart), and eval's
    _restore_learner reads it exactly like a full checkpoint."""
    from r2d2dpg_tpu.eval import _restore_learner

    trainer = PENDULUM_TINY.build()
    state = trainer.init()
    for _ in range(trainer.window_fill_phases):
        state = trainer.collect_phase(state)
    state = trainer.fill_phase(state)
    state, _ = trainer.train_phase(state)

    ckpt = CheckpointManager(
        str(tmp_path / "light"), save_every=1, light=True
    )
    ckpt.save(1, state)
    ckpt.wait()

    resumed = resume_state(trainer, ckpt)
    _tree_allclose(resumed.train, state.train)  # learner restored...
    assert int(resumed.phase_idx) == 0  # ...schedule/replay fresh
    assert int(trainer.arena.size(resumed.arena)) == 0
    ckpt.close()

    train = _restore_learner(trainer, str(tmp_path / "light"))
    _tree_allclose(train, state.train)


def test_checkpoint_same_step_overwrite_final_skip_and_layout_guards(tmp_path):
    """save() overwrites a same-step checkpoint (light-resume runs restart
    phase numbering); save_final() no-ops on an already-saved step instead
    of letting orbax StepAlreadyExistsError fail a finished run; light/full
    layout mismatches raise a clear error, not an orbax tree mismatch."""
    trainer = PENDULUM_TINY.build()
    state = trainer.init()

    d = str(tmp_path / "full")
    ck = CheckpointManager(d, save_every=1)
    ck.save(2, state)
    ck.save_final(2, state)  # cadence already saved step 2: must no-op
    ck.save(2, state)  # same-step overwrite: must not raise
    ck.wait()
    assert ck.latest_step == 2
    ck.close()

    with pytest.raises(ValueError, match="FULL"):
        lt = CheckpointManager(d, save_every=1, light=True)
        lt.save(3, state)

    d2 = str(tmp_path / "light")
    l2 = CheckpointManager(d2, save_every=1, light=True)
    l2.save(1, state)
    l2.wait()
    l2.close()
    with pytest.raises(ValueError, match="LIGHT"):
        CheckpointManager(d2, save_every=1).restore(state)


@pytest.mark.parametrize("twin_critic", [False, True])
def test_restore_learner_roundtrip(tmp_path, twin_critic):
    """_restore_learner's partial restore must return the saved learner
    subtree bit-for-bit (ADVICE r1: pin the orbax dict/dataclass key
    matching so an orbax upgrade breaking it is caught here, not in eval).
    Parametrized over twin_critic: the ensemble axis changes the critic
    tree, and post-hoc eval of a --twin-critic run depends on this path."""
    import dataclasses

    from r2d2dpg_tpu.eval import _restore_learner

    cfg = dataclasses.replace(
        PENDULUM_TINY,
        agent=dataclasses.replace(
            PENDULUM_TINY.agent, twin_critic=twin_critic
        ),
    )
    trainer = cfg.build()
    state = trainer.init()
    ckpt = CheckpointManager(str(tmp_path / "ck"), save_every=1)
    ckpt.save(1, state)
    ckpt.wait()
    ckpt.close()
    train = _restore_learner(trainer, str(tmp_path / "ck"))
    _tree_allclose(train, state.train)


@pytest.mark.slow
def test_checkpoint_survives_sigkill(tmp_path):
    """Kill a training run mid-flight; --resume must restore from a
    FINALIZED checkpoint (VERDICT r1: the round-1 long run left only
    *.orbax-checkpoint-tmp dirs and nothing restorable)."""
    import signal
    import subprocess
    import sys
    import time

    ckdir = str(tmp_path / "ck")
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.setdefault("R2D2DPG_PALLAS_INTERPRET", "1")
    proc = subprocess.Popen(
        [
            sys.executable, "-m", "r2d2dpg_tpu.train",
            "--config", "pendulum_tiny",
            "--phases", "100000",
            "--log-every", "0",
            "--checkpoint-dir", ckdir,
            "--checkpoint-every", "5",
        ],
        env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT,
        text=True,
    )
    try:
        # Wait for at least one finalized checkpoint to exist, then SIGKILL
        # (no cleanup handlers run — the crash case).
        deadline = time.time() + 240
        seen = None
        while time.time() < deadline:
            finalized = [
                d for d in (os.listdir(ckdir) if os.path.isdir(ckdir) else [])
                if d.isdigit()
            ]
            if finalized:
                seen = max(int(d) for d in finalized)
                break
            if proc.poll() is not None:
                pytest.fail(f"train died early:\n{proc.stdout.read()[-2000:]}")
            time.sleep(1.0)
        assert seen is not None, "no finalized checkpoint within 240s"
    finally:
        if proc.poll() is None:
            proc.send_signal(signal.SIGKILL)
        proc.wait()
        proc.stdout.close()

    # The manager must see a finalized step and restore it bit-for-bit.
    ckpt = CheckpointManager(ckdir)
    assert ckpt.latest_step is not None and ckpt.latest_step >= seen
    trainer = PENDULUM_TINY.build()
    restored = resume_state(trainer, ckpt)
    assert int(restored.phase_idx) >= seen
    ckpt.close()

    # And a full --resume run continues from it.
    from r2d2dpg_tpu.train import main as train_main

    train_main(
        [
            "--config", "pendulum_tiny",
            "--phases", "1",
            "--log-every", "0",
            "--checkpoint-dir", ckdir,
            "--checkpoint-every", "1000",
            "--resume",
        ]
    )


def test_checkpoint_restore_missing_raises(tmp_path):
    ckpt = CheckpointManager(str(tmp_path / "empty"))
    with pytest.raises(FileNotFoundError):
        ckpt.restore(template={})
    ckpt.close()


# ------------------------------------------------------------------- evaluator
def test_evaluator_deterministic_and_finite():
    cfg = PENDULUM_TINY
    trainer = cfg.build()
    state = trainer.init()
    ev = Evaluator(cfg.env_factory(), trainer.agent.actor, num_envs=3)
    key = jax.random.PRNGKey(0)
    out1 = ev.run(state.train.actor_params, key)
    out2 = ev.run(state.train.actor_params, key)
    assert out1 == out2  # same key, no noise -> identical
    # Pendulum returns are negative costs bounded by ~-17 per step.
    T = cfg.env_factory().spec.episode_length
    assert -17.0 * T <= out1["eval_return_mean"] <= 0.0
    assert out1["eval_return_min"] <= out1["eval_return_mean"] <= out1["eval_return_max"]


# ------------------------------------------------------------------------ CLI
def test_cli_end_to_end_with_checkpoint_resume(tmp_path):
    from r2d2dpg_tpu.train import parse_args, run

    logdir = str(tmp_path / "log")
    ckdir = str(tmp_path / "ck")
    args = parse_args(
        [
            "--config", "pendulum_tiny",
            "--phases", "3",
            "--log-every", "2",
            "--logdir", logdir,
            "--checkpoint-dir", ckdir,
            "--checkpoint-every", "2",
            "--eval-every", "2",
            "--eval-envs", "2",
        ]
    )
    final = run(args)
    assert os.path.exists(os.path.join(logdir, "metrics.csv"))
    assert "eval_return_mean" in final

    # Resume picks up from the saved phase and runs N *more* train phases.
    args2 = parse_args(
        [
            "--config", "pendulum_tiny",
            "--phases", "2",
            "--log-every", "100",
            "--checkpoint-dir", ckdir,
            "--checkpoint-every", "1000",  # off-cadence; final save still fires
            "--resume",
        ]
    )
    run(args2)
    ck = CheckpointManager(ckdir)
    trainer = get_config("pendulum_tiny").build()
    resumed = ck.restore(trainer.init())
    # First run: window_fill + replay_fill + 3 train phases; second adds 2.
    fill = trainer.window_fill_phases + trainer.replay_fill_phases
    assert int(resumed.phase_idx) == fill + 3 + 2
    assert int(resumed.train.step) > 0
    ck.close()


def test_cli_rejects_unknown_config():
    from r2d2dpg_tpu.train import parse_args

    with pytest.raises(SystemExit):
        parse_args(["--config", "nope"])


def test_eval_cli_from_checkpoint(tiny_cli_checkpoint):
    """python -m r2d2dpg_tpu.eval: restore a checkpoint, score it.  The
    checkpoint is the shared read-only session fixture
    (tests/conftest.py) — this test only restores from it."""
    from r2d2dpg_tpu.eval import main as eval_main

    ckdir = tiny_cli_checkpoint
    out = eval_main(
        [
            "--config", "pendulum_tiny",
            "--checkpoint-dir", ckdir,
            "--episodes", "3",
            "--rounds", "2",
        ]
    )
    assert out["learner_step"] > 0
    T = 200  # pendulum episode length
    assert -17.0 * T <= out["eval_return_mean"] <= 0.0
    # Same checkpoint scores under bf16 activations (params are fp32 in the
    # checkpoint regardless of train-time compute dtype, so the restore
    # template matches under both).
    out_bf16 = eval_main(
        [
            "--config", "pendulum_tiny",
            "--checkpoint-dir", ckdir,
            "--episodes", "3",
            "--rounds", "1",
            "--compute-dtype", "bfloat16",
        ]
    )
    assert out_bf16["learner_step"] == out["learner_step"]
    assert -17.0 * T <= out_bf16["eval_return_mean"] <= 0.0
    # A WRONG shape-affecting flag must fail loudly at restore time: orbax
    # silently returns the checkpoint's arrays on a shape mismatch (twin
    # template vs single-critic checkpoint), so the guard in
    # _restore_learner is the only thing standing between a wrong flag and
    # a confusing downstream error.
    with pytest.raises(ValueError, match="does not match"):
        eval_main(
            [
                "--config", "pendulum_tiny",
                "--checkpoint-dir", ckdir,
                "--episodes", "1",
                "--rounds", "1",
                "--twin-critic", "1",
            ]
        )


def test_eval_cli_bf16_checkpoint_restores_fp32(tmp_path):
    """The reverse interchange direction (VERDICT r4 weak #2b): a checkpoint
    written by a --compute-dtype bfloat16 train (mixed cell) must restore
    and score under the default fp32 eval (stock cell) — the mixed cell's
    docstring promises both directions; test_eval_cli_from_checkpoint
    covers fp32-train -> bf16-eval."""
    from r2d2dpg_tpu.eval import main as eval_main
    from r2d2dpg_tpu.train import main as train_main

    ckdir = str(tmp_path / "ck")
    train_main(
        [
            "--config", "pendulum_tiny",
            "--compute-dtype", "bfloat16",
            "--phases", "2",
            "--log-every", "0",
            "--checkpoint-dir", ckdir,
            "--checkpoint-every", "1",
        ]
    )
    out = eval_main(
        [
            "--config", "pendulum_tiny",
            "--checkpoint-dir", ckdir,
            "--episodes", "3",
            "--rounds", "1",
        ]
    )
    assert out["learner_step"] > 0
    T = 200  # pendulum episode length
    assert -17.0 * T <= out["eval_return_mean"] <= 0.0


def test_restore_learner_raises_on_missing_leaves(tmp_path):
    """A restore template whose tree has leaves the checkpoint lacks must
    fail LOUDLY naming the missing keys, not hand back silent abstract
    leaves that explode later inside the jitted evaluator (VERDICT r4 weak
    #2c — exactly how the round-3 mixed-cell tree mismatch surfaced).
    Feedforward checkpoint + LSTM template = guaranteed-missing cell leaves."""
    import dataclasses

    from r2d2dpg_tpu.eval import _restore_learner

    ff_cfg = dataclasses.replace(PENDULUM_TINY, use_lstm=False)
    state = ff_cfg.build().init()
    ckpt = CheckpointManager(str(tmp_path / "ck"), save_every=1)
    ckpt.save(1, state)
    ckpt.wait()
    ckpt.close()
    with pytest.raises((ValueError, KeyError), match="missing|unrestored"):
        _restore_learner(PENDULUM_TINY.build(), str(tmp_path / "ck"))


def test_eval_cli_relative_checkpoint_dir(
    tmp_path, monkeypatch, tiny_cli_checkpoint
):
    """orbax requires absolute paths; the eval CLI must absolutize

    (regression: a relative --checkpoint-dir raised ValueError from orbax
    while training with the same relative path worked).  The checkpoint's
    provenance is irrelevant to the path-handling under test, so the
    shared session checkpoint is COPIED under a relative name instead of
    training a fresh identical one."""
    import shutil

    from r2d2dpg_tpu.eval import main as eval_main

    monkeypatch.chdir(tmp_path)
    shutil.copytree(tiny_cli_checkpoint, tmp_path / "ck")
    out = eval_main(
        ["--config", "pendulum_tiny", "--checkpoint-dir", "ck",
         "--episodes", "2", "--rounds", "1"]
    )
    assert out["learner_step"] > 0
