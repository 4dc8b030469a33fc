"""Test configuration: run on a virtual 8-device CPU mesh (SURVEY.md §4.4).

Multi-chip TPU hardware is unavailable in CI; all sharding/collective code
paths execute on 8 virtual CPU devices via
``--xla_force_host_platform_device_count``.

Tests run on the CPU mesh whatever the machine holds: the platform is
pinned here, unconditionally, before the first backend touch.
"""

import os

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    flags = (flags + " --xla_force_host_platform_device_count=8").strip()
# Compile time dominates the suite on a small CPU host (tiny shapes,
# hundreds of jit programs) and XLA:CPU's backend optimization pipeline
# is most of each compile: level 0 cuts ~30% of suite wall-clock
# (ROADMAP.md's 870 s tier-1 budget).  Execution of the tiny test shapes
# is not measurably slower, and numerics stay self-consistent — every
# trainer-side bit-identity anchor and its subject run under the SAME
# flags (subprocess legs inherit this env), while the serving plane is
# flag-INDEPENDENT by design: PolicyService and the serving tests'
# references compile through ``serving.compile_pinned``, which pins the
# backend level per-executable (level 0 would otherwise pick per-bucket
# reduction strategies and break the cross-bucket row-identity contract).
# Real-chip runs never see this: it applies only when conftest is in the
# process.
if "xla_backend_optimization_level" not in flags:
    flags = (flags + " --xla_backend_optimization_level=0").strip()
os.environ["XLA_FLAGS"] = flags
# Exercise Pallas kernels via the interpreter on CPU (SURVEY §4: the kernel
# logic itself is under test; the Mosaic-compiled path runs on real TPU).
os.environ.setdefault("R2D2DPG_PALLAS_INTERPRET", "1")

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_default_matmul_precision", "highest")

assert jax.default_backend() == "cpu", (
    "tests must run on the virtual CPU mesh, got " + jax.default_backend()
)
assert len(jax.devices()) == 8


# --------------------------------------------------- shared anchor references
# The determinism anchors (fleet/pipeline/dp-learner/sampler/topology
# gates) all pin their subsystem's off-setting BIT-IDENTICAL to the same
# quantity: the phase-locked ``Trainer.run`` of PENDULUM_TINY over
# warm + fill + N train phases at a fixed log cadence (the cadence is part
# of the state — pop_episode_metrics drains device accumulators).  Each
# anchor used to recompute that identical reference (~12 s of jit compiles
# apiece); these session fixtures compute each (N, cadence) flavor ONCE
# and every anchor compares against the shared copy.  Coverage is
# unchanged — the schedule UNDER TEST still runs fresh inside each anchor;
# only the never-mutated reference state is shared (tests read leaves,
# nothing donates them).  The tier-1 wall-clock budget is the point
# (ROADMAP.md's 870 s timeout).

import pytest  # noqa: E402


def _phase_locked_reference(n_train: int, log_every: int):
    from r2d2dpg_tpu.configs import PENDULUM_TINY

    t = PENDULUM_TINY.build()
    warm, fill = t.window_fill_phases, t.replay_fill_phases
    return t.run(
        warm + fill + n_train, log_every=log_every, log_fn=lambda *_: None
    )


@pytest.fixture(scope="session")
def phase_locked_reference_k10():
    """PENDULUM_TINY warm+fill+10 train phases at log_every=3 (the
    fleet / pipeline / dp-learner anchors' reference)."""
    return _phase_locked_reference(10, 3)


@pytest.fixture(scope="session")
def phase_locked_reference_k6():
    """PENDULUM_TINY warm+fill+6 train phases at log_every=2 (the
    sampler / topology anchors' reference)."""
    return _phase_locked_reference(6, 2)


@pytest.fixture(scope="session")
def tiny_cli_checkpoint(tmp_path_factory):
    """A 2-phase pendulum_tiny training checkpoint written through the
    real train CLI (checkpoint-every 1) — shared by the eval-CLI tests
    that only READ a checkpoint (each used to train its own identical
    one; same tier-1 budget rationale as the anchor references above).
    Consumers that need a different flavor (bf16 train) or mutate the
    directory keep training their own."""
    from r2d2dpg_tpu.train import main as train_main

    ckdir = str(tmp_path_factory.mktemp("shared_ck") / "ck")
    train_main(
        [
            "--config", "pendulum_tiny",
            "--phases", "2",
            "--log-every", "0",
            "--checkpoint-dir", ckdir,
            "--checkpoint-every", "1",
        ]
    )
    return ckdir
