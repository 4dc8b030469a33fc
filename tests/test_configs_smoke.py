"""Every BASELINE DMC config drives end-to-end through the real CLI.

Tiny overrides keep each run to a couple of train phases; the point is that
each config's full path — env pool (native / Python / pixels+EGL), action
repeat, CNN/LSTM nets, prioritized replay, learner updates — executes and
produces finite metrics (SURVEY.md §4.3's integration matrix, configs #3-#5;
the pendulum configs #1-#2 are covered by test_trainer / test_utils).
"""

import numpy as np
import pytest

from r2d2dpg_tpu.train import parse_args, run

pytestmark = pytest.mark.slow


@pytest.mark.parametrize(
    "config", ["walker_r2d2", "humanoid_r2d2", "cheetah_pixels", "sdar_tiny", "ouro_tiny"]
)
def test_config_cli_smoke(config, tmp_path):
    args = parse_args(
        [
            "--config", config,
            "--num-envs", "4",
            "--batch-size", "4",
            "--min-replay", "8",
            "--phases", "2",
            "--log-every", "1",
            "--logdir", str(tmp_path / config),
        ]
    )
    final = run(args)
    assert final["env_steps"] > 0
    for key in ("critic_loss", "actor_loss", "q_mean"):
        assert np.isfinite(final[key]), (key, final)
