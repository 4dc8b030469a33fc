"""What an entry point settles before its first compile: where JAX's
persistent compilation cache lives, and (for measurements) that the
process really holds a TPU.
"""

from __future__ import annotations

import os
from typing import Dict

_CHECKOUT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)
DEFAULT_CACHE_DIR = os.path.join(_CHECKOUT, ".jax_cache")


def enable_compile_cache() -> str:
    """Point JAX at the compile cache; returns the directory in use.

    Called first thing by ``train``, ``serve``, ``eval``,
    ``chipbench/run.py`` and ``chip_smoke.py``'s legs, so consecutive processes of one checkout share
    compiled programs.  The directory is part of the cache key, so it must
    not move between processes: it is either the one
    ``JAX_COMPILATION_CACHE_DIR`` names (JAX reads that variable itself;
    nothing is set in code then) or the fixed ``<checkout>/.jax_cache`` —
    never a tempdir, a pid or a clock.

    JAX never caches a program that embeds a host callback, so the fused
    phases of the DM-Control configs (``io_callback`` into the env pool)
    compile afresh in every process; everything else is kept.
    """
    from_env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if from_env:
        return from_env
    import jax

    jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    # JAX's default keeps only programs that took a second to compile; a
    # short run compiles dozens that take less and add up.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return DEFAULT_CACHE_DIR


def require_tpu() -> Dict[str, object]:
    """The device this process runs on, as JAX reports it — or exit.

    A device rate taken on the CPU backend measures nothing a user pays
    for, so every measurement path calls this before measuring and stamps
    the returned record into what it prints.  The entry points themselves
    do not: tests drive them on the CPU.
    """
    import jax

    devices = jax.devices()
    record = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }
    if record["platform"] != "tpu":
        raise SystemExit(
            f"no TPU: JAX resolved platform {record['platform']!r} "
            f"({record['kind']}, {record['count']} devices); this "
            "measurement runs on the chip or not at all"
        )
    return record
