"""Operations and bytes of the learner step over Ouro-2.6B's looped stack,
from shapes alone: what the mathematics needs, whichever kernel or schedule
the program uses.  Multiply-adds of the matmuls (2 FLOPs each); norms,
softmax, RoPE, gates, Adam and Polyak are left out.  Nothing recomputed is
counted: the program's rematerialised blocks show as lost share.

**FLOPs go by applications, bytes by parameters.**  A block's weights are
``layers`` sets of parameters and ``total_ut_steps * layers`` applications: a
pass through a net's core multiplies every token by every layer's weights
``total_ut_steps`` times, and reads (or, for a gradient, writes) a layer's
parameters as many times as it is applied (read) or once (written: the
gradient of a shared weight is one sum over its uses).

A net goes through its core in passes (``pass_tokens``): the
burn-in prefix of each of the four nets (forward only; of the last
application the memory needs the keys and values alone), the two target nets
over ``unroll + n_step`` positions (forward only), and over ``unroll``
positions the critic of its loss and the actor of its loss (forward, weight
and input gradients) and the critic on the policy's actions (forward and
input gradients).

``cfg`` is ``chipbench/configs/humanoid_ouro_loop.json`` as a dict.
"""

from __future__ import annotations

from typing import Any, Dict

from chipbench.counts_sdar_moe import BACKWARD, _keys_seen  # the passes are the same
from chipbench.reference_ouro_loop import sizes


def _application_macs(cfg: Dict[str, Any]) -> Dict[str, float]:
    """Multiply-adds per token of one block application's parts (attention's
    scores and values apart: they depend on how many keys a position sees)."""
    z = sizes(cfg)
    H, A = z["H"], z["heads"] * z["D"]
    return {
        "kv": 2 * H * A,
        "q_o": 2 * H * A,
        "mlp": 3 * H * z["W"],
        "per_key": 2 * A,  # q.k and p.v for one key
    }


def pass_tokens(cfg: Dict[str, Any]) -> Dict[str, int]:
    """Positions a sequence has in each pass."""
    Bn, U, n = int(cfg["burnin"]), int(cfg["unroll"]), int(cfg["n_step"])
    return {
        "burn_actor": Bn, "burn_target_actor": Bn, "burn_critic": Bn,
        "burn_target_critic": Bn, "target_actor": U + n, "target_critic": U + n,
        "critic": U, "actor": U, "critic_pi": U,
    }


def learner_step_flops(cfg: Dict[str, Any]) -> float:
    """FLOPs one learner update needs, forward and backward."""
    z, m = sizes(cfg), _application_macs(cfg)
    B, A, O = int(cfg["batch_size"]), int(cfg["action_dim"]), int(cfg["obs_shape"][0])
    Bn, U, n = int(cfg["burnin"]), int(cfg["unroll"]), int(cfg["n_step"])
    H, apps = z["H"], z["R"] * z["L"]
    dense = m["kv"] + m["q_o"] + m["mlp"]
    outer = {"actor": O * H + H * A, "critic": O * H + (H + A) * H + H}

    def forward(net, tokens, memory):
        return (tokens * (apps * dense + outer[net])
                + apps * m["per_key"] * _keys_seen(tokens, memory))

    macs = 0.0
    for net in ("actor", "critic"):
        # Burn-in, online and target: whole applications but the last, of
        # which the memory needs the keys and values alone; no head.
        macs += 2 * (Bn * ((apps - 1) * dense + m["kv"] + outer[net]
                           - (A * H if net == "actor" else H))
                     + (apps - 1) * m["per_key"] * _keys_seen(Bn, 0))
        macs += forward(net, U + n, Bn)  # the target net over the window
    # The losses' own passes over the unroll: forward, then weight and input
    # gradients (each a forward's worth; attention's backward is twice its
    # forward over the window's keys and once over the memory's, which take
    # no gradient; the first torso layer needs no input gradient).
    attn_back = apps * m["per_key"] * (U * Bn + 2 * U * (U + 1) / 2)
    for net in ("critic", "actor"):
        macs += forward(net, U, Bn)
        macs += 2 * U * (apps * dense + outer[net]) - U * O * H + attn_back
    # The critic on the policy's actions: forward and input gradients only
    # (through the core, the action columns of the mix, the head).
    macs += forward("critic", U, Bn) - U * O * H  # its torso features are the loss's
    macs += U * (apps * dense + A * H + H) + attn_back
    return 2.0 * macs * B


def learn_call_flops(cfg: Dict[str, Any]) -> float:
    """FLOPs one timed call needs: ``learner_steps`` updates."""
    return int(cfg["learner_steps"]) * learner_step_flops(cfg)


# ---------------------------------------------------------- the MLP alone
def mlp_work(cfg: Dict[str, Any]) -> Dict[str, float]:
    """FLOPs and bytes the dense MLPs of one update need (the scope
    ``core_mlp``): 6 H W FLOPs a token and application forward, as much again
    for each of its two gradients; a layer's three kernels read once by every
    application of a pass, once more by every application where a gradient
    is taken, and their gradient written once a layer.  A burn-in pass needs
    no MLP in its last application."""
    z = sizes(cfg)
    B = int(cfg["batch_size"])
    per_token = 6.0 * z["H"] * z["W"]
    kernels = 4.0 * 3 * z["H"] * z["W"]  # one layer's, float32
    flops = nbytes = 0.0
    for name, tokens in pass_tokens(cfg).items():
        apps = z["R"] * z["L"] - name.startswith("burn_")
        d_w, d_x = BACKWARD.get(name, (False, False))
        flops += B * tokens * apps * per_token * (1 + d_w + d_x)
        nbytes += kernels * (apps * (1 + (d_w or d_x)) + z["L"] * d_w)
    return {"flops": flops, "bytes": nbytes}
