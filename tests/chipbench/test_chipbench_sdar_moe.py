"""The ``humanoid_sdar_moe.learn`` cell's driver, reference, counts and
reducers on the CPU at ``sdar_tiny``: the tiny cell through ``run_cell`` sound,
and not ``correct`` under each control and fault."""

import json
import os
import time

import numpy as np
import pytest

from chipbench import counts_sdar_moe, harness, reference_sdar_moe as ref_moe, run

from chipbench_fixtures import DATA, FAKE_TPU, REPO, bench_with, tiny_copy

TINY = "sdar_tiny.learn"
CELL = "humanoid_sdar_moe.learn"


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    return tiny_copy(str(tmp_path_factory.mktemp("cb_sdar")))


@pytest.fixture(scope="module")
def tiny_bench():
    return bench_with(TINY, "sdar_tiny", "learn", like=CELL)


def _run(root, bench, trace=False, plant=None, seed=2**31 + 7):
    return run.run_cell(
        TINY, seed, 0.3, trace, plant=plant, t_start=time.time(), root=root,
        bench=bench, device=dict(FAKE_TPU) if trace else None)


@pytest.fixture(scope="module")
def sound(tiny_root, tiny_bench):
    return _run(tiny_root, tiny_bench)


def test_sound_run_is_correct_with_every_number_of_the_cell(sound):
    assert sound["correct"] is True, sound["compared"]
    assert set(sound["metrics"]) == {"learner_steps_per_s", "setup_s"}
    assert sound["attempted"] > 0 and sound["failed"] == 0
    with open(os.path.join(REPO, "chipbench", "workloads", CELL + ".json")) as f:
        limits = json.load(f)["limits"]
    assert set(sound["compared"]) == set(limits) | {"compiles_in_window"}
    # The routing is exact at float32: every expert's count is the reference's.
    assert sound["compared"]["expert_load_gap"]["value"] == 0.0


@pytest.mark.parametrize("plant, expect", [
    ("bf16", {"loss_gap", "grad_gap", "priority_gap", "expert_load_gap"}),
    ("router_bf16", {"expert_load_gap", "grad_gap"}),
    ("half_batch", {"loss_gap", "grad_gap", "expert_load_gap"}),
    ("expert_unapplied", {"loss_gap", "grad_gap", "priority_gap", "expert_load_gap"}),
    ("frozen", {"loss_gap", "update_gap", "steps_gap", "slots_unmatched"}),
])
def test_planted_control_or_fault_is_not_correct(tiny_root, tiny_bench, plant, expect):
    """One precision down in the whole net and in the router alone, half the
    batch left out, an expert that is held and counted but never applied, and
    a call that hands its state back unchanged."""
    from r2d2dpg_tpu.models import sdar_moe

    seams = (sdar_moe.router_probs, sdar_moe.held_ffn)
    try:
        r = _run(tiny_root, tiny_bench, plant=plant)
    finally:
        sdar_moe.router_probs, sdar_moe.held_ffn = seams
    assert r["correct"] is False
    failed = {k for k, c in r["compared"].items() if not c["ok"]}
    assert expect <= failed, (plant, failed)


def _driver():
    return harness.load_module("drivers", "learn_sdar_moe")


def test_a_slot_drawn_twice_is_placed_by_what_the_reference_wrote_between():
    """The follow infers each update's slots from the priorities as the
    updates before left them.  A slot the first update drew and raised
    hundredfold draws most of the second update's mass; laying the program's
    end-of-call value over it (far lower here) would send those draws to
    other slots."""
    import jax

    drv = _driver()
    n, B = 64, 8
    before = np.full(n, 0.1, np.float32)
    keys = jax.random.split(jax.random.PRNGKey(0), 2)
    replay = {"batch_size": B, "alpha": 1.0, "beta0": 0.4, "beta_steps": 100}
    written = [np.full(B, 50.0, np.float32), np.full(B, 0.2, np.float32)]

    def truth():
        cur, slots = before.astype(np.float64), []
        for k, key in enumerate(keys):
            cdf = np.cumsum(cur)
            u = np.asarray(jax.random.uniform(key, (B,)), np.float64) * cdf[-1]
            slots.append(np.minimum(np.searchsorted(cdf, u, side="right"), n - 1))
            cur[slots[-1]] = written[k]
        return slots

    slots = truth()
    assert np.isin(slots[1], slots[0]).sum() >= B // 2  # the raised slots are drawn again
    after = before.copy()
    for k in range(2):
        after[slots[k]] = written[k]
    changed = np.flatnonzero(before != after)
    calls = iter(written)

    def update(ref, rows, w):
        return ref, next(calls), {"grads": {}, "loads": np.zeros((1, 1, 1)),
                                  "critic_loss": 0.0, "actor_loss": 0.0, "q_abs_mean": 1.0}

    f = drv.learner_call({"step": 0}, before.copy(), before, changed, keys,
                         lambda s: s, n, replay, update)
    for k in range(2):
        np.testing.assert_array_equal(f["slots"][k], slots[k])
    assert f["sample_gap"] == 0.0
    np.testing.assert_allclose(f["ref_prio"], after)


def test_leaf_gaps_scale_by_the_leaf_or_the_median_leaf_and_the_quartile_ignores_a_few():
    drv = _driver()
    ref = {f"leaf{i}": 1.0 for i in range(8)} | {"tiny": 1e-6}
    prog = dict(ref, leaf0=1.5, tiny=3e-6)
    gaps = drv.leaf_gaps(prog, ref)
    assert gaps["leaf0"] == pytest.approx(0.5) and gaps["tiny"] == pytest.approx(2e-6)
    assert drv.spread(gaps.values())["q75"] < 1e-5 < drv.spread(gaps.values())["max"]
    assert "leaf3" not in drv.leaf_gaps(prog, ref, skip=["leaf3"])
    with pytest.raises(ValueError):
        drv.leaf_gaps({"a": 1.0}, {"b": 1.0})


def test_routers_are_what_the_seed_gives_them(cfg):
    """No balancing pass: every kernel, the routers among them, is one uniform
    draw by its fan-in."""
    import jax

    drv = _driver()
    assert "router_balance" not in cfg["assumed"] and not hasattr(drv, "balance_routers")
    shapes = {"block_0_router": jax.ShapeDtypeStruct((64, 8), np.float32),
              "norm2": jax.ShapeDtypeStruct((64,), np.float32)}
    w = drv.make_weights(jax.random.PRNGKey(1), shapes)
    k = jax.random.fold_in(jax.random.PRNGKey(1), 0)  # leaves in sorted order
    np.testing.assert_array_equal(
        w["block_0_router"], jax.random.uniform(k, (64, 8), np.float32, -0.125, 0.125))
    assert np.abs(np.asarray(w["norm2"]) - 1.0).max() <= 0.05


def test_traced_run_reads_the_counters_and_leaves_out_the_device_metrics(
    tiny_root, tiny_bench, capfd
):
    r = _run(tiny_root, tiny_bench, trace=True)
    # One set-up a run: the stage table is captured on the live session.
    assert capfd.readouterr().err.count("program built") == 1
    # No device plane in a CPU capture: the stage times and the roofline find
    # nothing to read and are left out, never reported as 0.
    assert set(r["metrics"]) == {"learn_mfu_moe", "moe_load_max_over_mean"}
    assert r["metrics"]["moe_load_max_over_mean"]["value"] >= 1.0
    assert r["correct"] is True


def test_new_metric_readers_return_nothing_on_a_program_without_the_core():
    """The parent commit has neither the counters nor ``CORE_STAGES``: on its
    cells' windows the readers find nothing and do not raise."""
    ctx = harness.Context(cell_name="x", cell={"driver": "learn"}, config={}, seed=0,
                          seconds=1.0, trace=True)
    ctx.window = {"elapsed_s": 1.0, "calls": 3, "metrics": {}}
    ctx.steady_trace = None
    for reducer, args in (("window_counter", {"group": "moe", "name": "moe/load_max_over_mean"}),
                          ("core_stage_ms", {"stage": "moe_experts"}),
                          ("moe_roofline", {"stage": "moe_experts"})):
        assert harness.load_module("reducers", reducer).read(ctx, **args) is None


@pytest.fixture(scope="module")
def cfg():
    return harness.load_json("configs", "humanoid_sdar_moe")


def test_configuration_file_holds_the_published_config_and_names_its_cuts(cfg):
    published = {
        "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128,
        "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
        "max_position_embeddings": 32768, "max_window_layers": 48, "mlp_only_layers": [],
        "model_type": "sdar_moe", "moe_intermediate_size": 768, "norm_topk_prob": True,
        "num_attention_heads": 32, "num_experts": 128, "num_experts_per_tok": 8,
        "num_hidden_layers": 48, "num_key_value_heads": 4, "rms_norm_eps": 1e-06,
        "rope_scaling": None, "rope_theta": 1000000, "sliding_window": None,
        "tie_word_embeddings": False, "use_sliding_window": False, "vocab_size": 151936,
    }
    differs = {k for k, v in published.items() if cfg.get(k, "absent") != v}
    assert differs == {"num_experts"} and cfg["num_experts"] == 8
    assert set(cfg["changed"]) == {"layers", "num_experts"} and cfg["layers"] == 4
    assert cfg["published"] == {"num_experts": 128, "layers": 48}
    assert cfg["expert_shards"] * cfg["num_experts"] == cfg["published"]["num_experts"]
    assert {"vocabulary", "block_diffusion", "mask"} <= set(cfg["departures"])
    assert {"qk_norm", "router_precision", "weights"} <= set(cfg["assumed"])
    assert "16" in cfg["deployment"]


def test_parameter_count_is_the_issues_arithmetic(cfg):
    actor, critic = ref_moe.weight_shapes(cfg)
    import jax

    count = lambda t: sum(int(np.prod(s.shape)) for s in jax.tree_util.tree_leaves(t))  # noqa: E731
    core = count(actor["params"]["core"])
    assert core == 4 * 56_889_600 + 2048  # four layers and the final norm
    assert count(actor) == core + 67 * 2048 + 2048 + 2048 * 21 + 21
    assert count(critic) == core + 67 * 2048 + 2048 + (2048 + 21) * 2048 + 2048 + 2048 + 1
    assert 459e6 < count(actor) + count(critic) < 460.5e6


def test_learner_flops_scale_as_the_passes_do(cfg):
    whole = counts_sdar_moe.learner_step_flops(cfg)
    assert counts_sdar_moe.learn_call_flops(cfg) == 4 * whole
    # Per token and layer the dense part is 18.9 M + 0.26 M + 2.36 M multiply-adds;
    # (4 x 40 + 2 x 45) forward tokens and 3 x 40 differentiated ones a sequence.
    per = 18_874_368 + 262_144 + 0.5 * 4_718_592
    floor = 2.0 * 64 * 4 * per * (3 * 40 + 2 * 45 + 40 * (3 + 3 + 2))
    assert floor < whole < 1.25 * floor
    assert counts_sdar_moe.learner_step_flops(dict(cfg, batch_size=32)) == pytest.approx(whole / 2)


def test_experts_work_counts_pairs_and_weight_traffic(cfg):
    table = np.zeros((9, 4, 8))
    table[6, 0] = 10  # the critic's loss pass, first layer: 80 pairs, both gradients
    table[4, 1] = 5  # a target pass, second layer: 40 pairs, forward only
    w = counts_sdar_moe.experts_work(cfg, table, ref_moe.PASSES)
    pair, weights = 6 * 2048 * 768, 4 * 8 * 3 * 2048 * 768
    assert w["flops"] == 80 * pair * 3 + 40 * pair
    assert w["bytes"] == weights * 3 + weights
    table[8, 3] = 1  # the critic on the policy's actions: input gradients only
    w2 = counts_sdar_moe.experts_work(cfg, table, ref_moe.PASSES)
    assert w2["flops"] - w["flops"] == 8 * pair * 2 and w2["bytes"] - w["bytes"] == weights * 2


def test_tiny_data_files_are_the_cells_own_shape():
    with open(os.path.join(DATA, "workloads", TINY + ".json")) as f:
        tiny = json.load(f)
    with open(os.path.join(REPO, "chipbench", "workloads", CELL + ".json")) as f:
        cell = json.load(f)
    assert set(tiny) == set(cell) and set(tiny["limits"]) == set(cell["limits"])
    assert set(tiny["traffic"]) == set(cell["traffic"]) and tiny["driver"] == cell["driver"]
    assert cell["traffic"]["in_flight_calls"] == 4 and len(cell["why"]) <= 200
