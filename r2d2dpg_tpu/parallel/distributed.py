"""Multi-host runtime initialization (SURVEY.md §5.8, DCN scale-out).

Reference parity: the reference's communication backend is single-host
``multiprocessing`` — it has no multi-node story at all (SURVEY §0, §5.8).
The build's backend is XLA collectives: inside one host/slice they ride
**ICI**; across hosts/slices they ride **DCN**.  Nothing in the program
changes between the two — the same ``shard_map`` specs compile to whichever
fabric connects the devices — so "multi-host support" reduces to bringing up
the JAX distributed runtime and building a mesh over *all* processes'
devices.

Usage (same program on every host):

    from r2d2dpg_tpu.parallel import distributed
    distributed.initialize()            # no-op single-host; auto-detect on TPU pods
    mesh = distributed.global_mesh()    # dp mesh over every chip in the job
    trainer = cfg.build_spmd(mesh)

Sharding guidance (why dp-over-everything is the right layout here): the
models are tiny (≤ a few M params), so parameters/optimizer state replicate
and only the gradient ``pmean`` crosses chips — one small all-reduce per
learner step, which DCN handles fine.  The bandwidth-heavy state (env fleet,
replay arena, sequence windows) is sharded and **never moves**.  This is the
layout the scaling-book recipe picks for pure data parallelism: shard the
batch axis, replicate params, let XLA place the collective.
"""

from __future__ import annotations

import os
from typing import Optional

import jax

from r2d2dpg_tpu.parallel.mesh import DP_AXIS


def initialize(
    coordinator_address: Optional[str] = None,
    num_processes: Optional[int] = None,
    process_id: Optional[int] = None,
) -> None:
    """Bring up the JAX distributed runtime (idempotent; single-host no-op).

    - On TPU pods (JAX sees the libtpu cluster env) every argument
      auto-detects: ``initialize()`` is all that's needed.
    - On CPU/GPU clusters, pass coordinator ``host:port``, world size and
      this process's rank — or export ``JAX_COORDINATOR_ADDRESS``,
      ``JAX_NUM_PROCESSES``, ``JAX_PROCESS_ID``.
    - With no cluster configuration at all this is a no-op, so single-host
      runs need no special-casing at call sites.
    """
    coordinator_address = coordinator_address or os.environ.get(
        "JAX_COORDINATOR_ADDRESS"
    )
    if num_processes is None and "JAX_NUM_PROCESSES" in os.environ:
        num_processes = int(os.environ["JAX_NUM_PROCESSES"])
    if process_id is None and "JAX_PROCESS_ID" in os.environ:
        process_id = int(os.environ["JAX_PROCESS_ID"])

    # IMPORTANT: jax.distributed.initialize() must run before anything
    # touches the local XLA backend, so cluster detection here reads only
    # environment variables — never jax.default_backend()/process_count().
    # TPU_WORKER_HOSTNAMES is set even on single-host boxes (e.g.
    # 'localhost'); only >1 comma-separated workers means a pod.
    workers = os.environ.get("TPU_WORKER_HOSTNAMES", "")
    on_tpu_pod = (
        len([w for w in workers.split(",") if w.strip()]) > 1
        or "MEGASCALE_COORDINATOR_ADDRESS" in os.environ
    )
    if coordinator_address is None and not on_tpu_pod:
        return  # single-host: nothing to bring up

    if jax.distributed.is_initialized():
        return
    jax.distributed.initialize(
        coordinator_address=coordinator_address,
        num_processes=num_processes,
        process_id=process_id,
    )


def global_mesh() -> jax.sharding.Mesh:
    """A 1-D ``dp`` mesh over every device in the job (all processes).

    ``jax.devices()`` already enumerates the global device set once the
    distributed runtime is up; locally it degrades to the local mesh.
    """
    from r2d2dpg_tpu.parallel.mesh import make_mesh

    return make_mesh()


def is_primary() -> bool:
    """True on the process that should own logging/checkpoint side effects."""
    return jax.process_index() == 0
