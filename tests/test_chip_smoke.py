"""chip_smoke.py refuses to pass without a chip, and the compile-cache
helper puts the cache where the outside says or at one fixed path.

Both run in child processes: the smoke's parent must stay off JAX, and the
helper edits process-wide JAX config.
"""

import json
import os
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_chip_smoke_refuses_on_cpu():
    t0 = time.monotonic()
    proc = subprocess.run(
        [sys.executable, os.path.join(HERE, "chip_smoke.py")],
        capture_output=True, text=True, cwd=HERE, timeout=120,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert proc.returncode != 0
    assert "platform 'cpu'" in proc.stderr, proc.stderr[-2000:]
    assert proc.stdout.strip() == ""  # no result line, not even a false one
    assert time.monotonic() - t0 < 60  # refused before any training


def _cache_config(cwd, cache_env):
    """[what the helper returned, the directory and the keep-threshold JAX
    ended up configured with] in a fresh process started from ``cwd``."""
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(cache_env, JAX_PLATFORMS="cpu", PYTHONPATH=HERE)
    code = (
        "import json, jax\n"
        "from r2d2dpg_tpu.utils.startup import enable_compile_cache\n"
        "print(json.dumps([enable_compile_cache(),"
        " jax.config.jax_compilation_cache_dir,"
        " jax.config.jax_persistent_cache_min_compile_time_secs]))"
    )
    proc = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        cwd=cwd, env=env, timeout=120,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_compile_cache_follows_the_environment(tmp_path):
    outside = str(tmp_path / "cache_from_outside")
    # JAX read the variable itself; the helper overrode nothing.
    assert _cache_config(HERE, {"JAX_COMPILATION_CACHE_DIR": outside}) == [
        outside, outside, 1.0
    ]


def test_compile_cache_defaults_to_one_path_in_the_checkout(tmp_path):
    fixed = os.path.join(HERE, ".jax_cache")
    assert _cache_config(HERE, {}) == [fixed, fixed, 0.0]
    assert _cache_config(str(tmp_path), {}) == [fixed, fixed, 0.0]
