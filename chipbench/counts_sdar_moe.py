"""Operations and bytes of the learner step over SDAR-30B-A3B-Chat's block,
from shapes alone: what the mathematics needs, whichever kernel or schedule
the program uses.  Multiply-adds of the matmuls (2 FLOPs each); norms,
softmax, RoPE, gates, Adam and Polyak are left out.  Nothing recomputed is
counted.  The experts' work is counted at the expected load: each token
sends ``num_experts_per_tok`` pairs over the router's width, of which the
share held here (``num_experts`` of ``published.num_experts``) is computed.

A net goes through its core in passes (``reference_sdar_moe.PASSES``): the
burn-in prefix of each of the four nets (forward only; the last layer stops
at its keys and values), the two target nets over ``unroll + n_step``
positions (forward only), and over ``unroll`` positions the critic of its
loss and the actor of its loss (forward, weight and input gradients) and the
critic on the policy's actions (forward and input gradients).

``cfg`` is ``chipbench/configs/humanoid_sdar_moe.json`` as a dict.
"""

from __future__ import annotations

from typing import Any, Dict

from chipbench.reference_sdar_moe import sizes


def _layer_macs(cfg: Dict[str, Any]) -> Dict[str, float]:
    """Multiply-adds per token of one layer's parts (attention's scores and
    values apart: they depend on how many keys a position sees)."""
    z = sizes(cfg)
    H, D = z["H"], z["D"]
    return {
        "kv": 2 * H * z["kv"] * D,
        "q_o": 2 * H * z["heads"] * D,
        "router": H * z["R"],
        "experts": z["k"] * z["E"] / z["R"] * 3 * H * z["W"],  # expected pairs
        "per_key": 2 * z["heads"] * D,  # q.k and p.v for one key
    }


def _keys_seen(tokens: int, memory: int) -> float:
    """Keys all ``tokens`` positions of a pass see together: the memory and,
    causally, themselves."""
    return tokens * memory + tokens * (tokens + 1) / 2


def learner_step_flops(cfg: Dict[str, Any]) -> float:
    """FLOPs one learner update needs, forward and backward."""
    z, m = sizes(cfg), _layer_macs(cfg)
    B, A, O = int(cfg["batch_size"]), int(cfg["action_dim"]), int(cfg["obs_shape"][0])
    Bn, U, n = int(cfg["burnin"]), int(cfg["unroll"]), int(cfg["n_step"])
    H, L = z["H"], z["L"]
    dense = m["kv"] + m["q_o"] + m["router"] + m["experts"]
    outer = {"actor": O * H + H * A, "critic": O * H + (H + A) * H + H}

    def forward(net, tokens, memory):
        return (tokens * (L * dense + outer[net])
                + L * m["per_key"] * _keys_seen(tokens, memory))

    macs = 0.0
    for net in ("actor", "critic"):
        # Burn-in, online and target: whole layers but the last, of which the
        # memory needs the keys and values alone; no head.
        macs += 2 * (Bn * ((L - 1) * dense + m["kv"] + outer[net] - (A * H if net == "actor" else H))
                     + (L - 1) * m["per_key"] * _keys_seen(Bn, 0))
        macs += forward(net, U + n, Bn)  # the target net over the window
    # The losses' own passes over the unroll: forward, then weight and input
    # gradients (each a forward's worth; attention's backward is twice its
    # forward over the window's keys and once over the memory's, which take
    # no gradient; the first torso layer needs no input gradient).
    attn_back = L * m["per_key"] * (U * Bn + 2 * U * (U + 1) / 2)
    for net in ("critic", "actor"):
        macs += forward(net, U, Bn)
        macs += 2 * U * (L * dense + outer[net]) - U * O * H + attn_back
    # The critic on the policy's actions: forward and input gradients only
    # (through the core, the action columns of the mix, the head).
    macs += forward("critic", U, Bn) - U * O * H  # its torso features are the loss's
    macs += U * (L * dense + A * H + H) + attn_back
    return 2.0 * macs * B


def learn_call_flops(cfg: Dict[str, Any]) -> float:
    """FLOPs one timed call needs: ``learner_steps`` updates."""
    return int(cfg["learner_steps"]) * learner_step_flops(cfg)


# ------------------------------------------------------- the experts alone
# How each pass differentiates its experts: (weight gradients, input gradients).
BACKWARD = {"critic": (True, True), "actor": (True, True), "critic_pi": (False, True)}


def experts_work(cfg: Dict[str, Any], table, passes) -> Dict[str, float]:
    """FLOPs and bytes the grouped products of one update need for the pairs
    ``table [P, L, E]`` counts (the program's ``moe/tokens_per_expert``, rows
    named by ``passes``): 6 H W FLOPs a pair forward, as much again for each
    of its two gradients; the held experts' weights read once by every layer
    of a pass that ran them, once more where a gradient is taken, and their
    gradient written once."""
    z = sizes(cfg)
    pair = 6.0 * z["H"] * z["W"]
    weights = 4.0 * z["E"] * 3 * z["H"] * z["W"]
    flops = nbytes = 0.0
    for name, rows in zip(passes, table):
        d_w, d_x = BACKWARD.get(name, (False, False))
        for row in rows:
            pairs = float(sum(row))
            if pairs <= 0:
                continue  # the layer ran no experts in this pass
            flops += pairs * pair * (1 + d_w + d_x)
            nbytes += weights * (1 + (d_w or d_x) + d_w)
    return {"flops": flops, "bytes": nbytes}
