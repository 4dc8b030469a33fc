"""Operations and bytes of the learner step and the replay operations, from
shapes alone.

These count what the mathematics needs, whichever kernel or schedule the
program uses: multiply-adds of the matmuls and convolutions of the forward
pass, and for the backward pass the weight gradient of every trained layer
plus the input gradient of every layer whose input depends on a trained
weight or a policy action.  Element-wise work (gates, ReLU, Adam, Polyak) is
left out: it is under 1 % at these widths and would only flatter the share.
Nothing recomputed is counted: the critic's torso features that the actor
loss reuses from the critic loss count once.

``cfg`` is a configuration file of ``chipbench/configs`` as a dict.
"""

from __future__ import annotations

import json
import os
from typing import Any, Dict, List, Tuple

import numpy as np

from chipbench.reference import CONV_STACK  # (features, kernel, stride), VALID

_HERE = os.path.dirname(os.path.abspath(__file__))



def load_peaks(device_kind: str) -> Dict[str, Any]:
    """The published peaks of ``device_kind``; an unknown device is an error."""
    with open(os.path.join(_HERE, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table or device_kind.startswith("_"):
        raise KeyError(
            f"device_kind {device_kind!r} is not in chipbench/peaks.json; "
            "add its published peaks with their source"
        )
    return table[device_kind]


def torso_macs(cfg: Dict[str, Any]) -> List[int]:
    """Multiply-adds per frame of each torso layer, first layer first."""
    shape, hidden = tuple(cfg["obs_shape"]), int(cfg["hidden"])
    if len(shape) == 1:
        return [shape[0] * hidden]
    h, w, c = shape
    macs = []
    for features, k, s in CONV_STACK:
        h, w = (h - k) // s + 1, (w - k) // s + 1
        macs.append(h * w * features * k * k * c)
        c = features
    macs.append(h * w * c * hidden)
    return macs


def _net_macs(cfg: Dict[str, Any]) -> Dict[str, Any]:
    H, A = int(cfg["hidden"]), int(cfg["action_dim"])
    return {
        "torso": torso_macs(cfg),
        "lstm": 2 * H * 4 * H,  # input and recurrent projections
        "actor_head": H * A,
        "mix_obs": H * H,
        "mix_act": A * H,
        "critic_head": H,
    }


def learner_step_flops(cfg: Dict[str, Any], rows: int | None = None) -> float:
    """FLOPs one learner update needs (forward and backward), 2 per
    multiply-add, for a batch of ``rows`` sequences (default: the batch)."""
    m = _net_macs(cfg)
    B = int(cfg["batch_size"]) if rows is None else int(rows)
    Bn, U, n = int(cfg["burnin"]), int(cfg["unroll"]), int(cfg["n_step"])
    torso_all = sum(m["torso"])
    torso_tail = sum(m["torso"][1:])  # layers whose input gradient is needed
    actor_body = torso_all + m["lstm"]
    critic_body = torso_all + m["mix_obs"] + m["mix_act"] + m["lstm"]
    actor_full = actor_body + m["actor_head"]
    critic_full = critic_body + m["critic_head"]

    macs = 0
    # Burn-in: four nets, carries only (no head).
    macs += Bn * 2 * (actor_body + critic_body)
    # Target pass over the window.
    macs += (U + n) * (actor_full + critic_full)
    # Critic loss: forward; backward = weight gradients of every layer and
    # input gradients of all but the first torso layer and the action columns.
    macs += U * critic_full
    macs += U * critic_full  # dW
    macs += U * (torso_tail + m["mix_obs"] + m["lstm"] + m["critic_head"])
    # Actor loss: actor forward, critic forward on the policy's actions (its
    # torso features are those of the critic loss: not counted again);
    # backward through the frozen critic to the action, then the actor.
    macs += U * (actor_full + critic_full - torso_all)
    macs += U * (m["critic_head"] + m["lstm"] + m["mix_act"])  # critic dX
    macs += U * actor_full  # actor dW
    macs += U * (torso_tail + m["lstm"] + m["actor_head"])  # actor dX
    return 2.0 * macs * B


def learn_call_flops(cfg: Dict[str, Any]) -> float:
    """FLOPs one timed call of the ``learn`` driver needs: ``learner_steps``
    updates."""
    return int(cfg["learner_steps"]) * learner_step_flops(cfg)


def initial_priority_flops(cfg: Dict[str, Any], rows: int) -> float:
    """FLOPs of ranking ``rows`` fresh sequences: burn-in of the four nets,
    the target pass and the online critic's forward unroll."""
    m = _net_macs(cfg)
    Bn, U, n = int(cfg["burnin"]), int(cfg["unroll"]), int(cfg["n_step"])
    torso_all = sum(m["torso"])
    actor_body = torso_all + m["lstm"]
    critic_body = torso_all + m["mix_obs"] + m["mix_act"] + m["lstm"]
    macs = Bn * 2 * (actor_body + critic_body)
    macs += (U + n) * (actor_body + m["actor_head"] + critic_body + m["critic_head"])
    macs += U * (critic_body + m["critic_head"])
    return 2.0 * macs * rows


def policy_step_flops(cfg: Dict[str, Any], rows: int) -> float:
    """FLOPs of one collect step: the actor's forward pass and the critic's
    carry advance (no Q head) for ``rows`` environments."""
    m = _net_macs(cfg)
    torso_all = sum(m["torso"])
    macs = torso_all + m["lstm"] + m["actor_head"]
    macs += torso_all + m["mix_obs"] + m["mix_act"] + m["lstm"]
    return 2.0 * macs * rows


def train_phase_flops(cfg: Dict[str, Any]) -> float:
    """FLOPs one fused train phase needs: ``stride`` policy steps of
    ``num_envs`` rows, the ranking of the emitted sequences, and
    ``learner_steps`` updates."""
    E = int(cfg["num_envs"])
    return (
        int(cfg["stride"]) * policy_step_flops(cfg, E)
        + initial_priority_flops(cfg, E)
        + int(cfg["learner_steps"]) * learner_step_flops(cfg)
    )


def seq_len(cfg: Dict[str, Any]) -> int:
    return int(cfg["burnin"]) + int(cfg["unroll"]) + int(cfg["n_step"])


def row_data_bytes(cfg: Dict[str, Any]) -> int:
    """Bytes of one stored sequence's data leaves (what ``sample`` gathers)."""
    L, A, H = seq_len(cfg), int(cfg["action_dim"]), int(cfg["hidden"])
    obs = L * int(np.prod(cfg["obs_shape"])) * np.dtype(cfg["obs_dtype"]).itemsize
    return obs + 4 * (L * A + 3 * L + 4 * H)


def arena_row_bytes(cfg: Dict[str, Any]) -> int:
    """Bytes one slot takes: data, float32 priority, two int32 stamps."""
    return row_data_bytes(cfg) + 4 + 8


def arena_bytes(cfg: Dict[str, Any]) -> int:
    return int(cfg["capacity"]) * arena_row_bytes(cfg)


def sample_bytes(cfg: Dict[str, Any]) -> int:
    """Bytes ``ReplayArena.sample`` has to move whatever implements it: the
    sampled sequences read once and written once.  The priority vector is not
    counted (a sum-tree would not read it whole)."""
    return 2 * int(cfg["batch_size"]) * row_data_bytes(cfg)


def update_bytes(cfg: Dict[str, Any]) -> int:
    """Bytes ``update_priorities`` has to move: B indices and B values read,
    B floats written."""
    return 3 * 4 * int(cfg["batch_size"])


def least_seconds(flops: float, nbytes: float, peaks: Dict[str, Any]) -> Tuple[float, str]:
    """The least time the chip could take, and which peak bounds it."""
    t_flops = flops / peaks["flops_per_s"]
    t_bytes = nbytes / peaks["hbm_bytes_per_s"]
    return (t_flops, "flops") if t_flops >= t_bytes else (t_bytes, "bytes")
