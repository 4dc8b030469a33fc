"""Device-mesh helpers (SURVEY.md §2.8, BASELINE north star).

The rebuild's scaling axis is ``dp`` — Ape-X actor parallelism *and* learner
data parallelism collapse onto one mesh axis: each device owns a shard of
the env fleet, of the window assembler, and of the replay arena, and the
learner syncs gradients with ``pmean`` over ICI (SURVEY §2.8's table:
"batch sharded across chips", "replay lives in HBM, sharded").

On the 1-chip dev box the mesh is degenerate; on CPU CI it is 8 virtual
devices (``--xla_force_host_platform_device_count``); on a v4-8 it is the
real ICI ring.  Multi-host (DCN) uses the same specs — ``jax.make_mesh``
over all processes' devices; XLA routes the collectives.
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P

DP_AXIS = "dp"


def make_mesh(
    n_devices: Optional[int] = None, devices: Optional[Sequence[jax.Device]] = None
) -> Mesh:
    """A 1-D ``dp`` mesh over the first ``n_devices`` local devices.

    The axis is *Auto*: all three mesh trainers are GSPMD-style (sharding
    annotations via ``NamedSharding``/``with_sharding_constraint``, XLA
    places the collectives), which an Explicit axis — ``jax.make_mesh``'s
    default — rejects at trace time.
    """
    if devices is None:
        devices = jax.devices()
    if n_devices is not None:
        if n_devices > len(devices):
            raise ValueError(
                f"requested {n_devices} devices, have {len(devices)}"
            )
        devices = devices[:n_devices]
    return jax.make_mesh(
        (len(devices),),
        (DP_AXIS,),
        axis_types=(AxisType.Auto,),
        devices=list(devices),
    )


def sharded(mesh: Mesh) -> NamedSharding:
    """Leading-axis sharding over ``dp`` (works for any rank >= 1)."""
    return NamedSharding(mesh, P(DP_AXIS))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def constrain_replicated(tree, mesh: Mesh):
    """Inside a jitted program: every leaf of ``tree`` replicated over
    ``mesh`` (``with_sharding_constraint``, not ``device_put``)."""
    rep = replicated(mesh)
    return jax.tree_util.tree_map(
        lambda x: jax.lax.with_sharding_constraint(x, rep), tree
    )


def constrain_batch_sharded(tree, mesh: Mesh):
    """Inside a jitted program: every leaf's leading (batch) axis laid over
    ``dp``, the rest unsharded — with replicated params this is what makes
    XLA split the learner's compute and psum the gradients."""
    return jax.tree_util.tree_map(
        lambda x: jax.lax.with_sharding_constraint(
            x, NamedSharding(mesh, P(DP_AXIS, *([None] * (x.ndim - 1))))
        ),
        tree,
    )
