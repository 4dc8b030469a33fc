"""The ``humanoid_ouro_loop.learn`` cell's driver, reference, counts and
reducers on the CPU at ``ouro_tiny``: the tiny cell through ``run_cell`` sound,
not ``correct`` under each control and fault, the counts against XLA's own of
the unrolled call, and the configuration file against the catalog's row."""

import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import counts_ouro_loop, harness, reference_ouro_loop as ref_ouro, run

from chipbench_fixtures import DATA, FAKE_TPU, REPO, bench_with, tiny_copy

TINY = "ouro_tiny.learn"
CELL = "humanoid_ouro_loop.learn"
STAGES = {"sample": "replay_sample", "burn_in": "burn_in", "forward": "forward",
          "backward": "backward", "optimizer": "optimizer", "unscoped": "unscoped"}
PER_LAYER = {"learn_mfu_looped", "loop_stage_ms.attention", "loop_stage_ms.mlp",
             "loop_mlp_roofline", "loop_last_step_rel_change",
             "device_idle.learn_looped"} | {"loop_stage_ms." + s for s in STAGES}


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    return tiny_copy(str(tmp_path_factory.mktemp("cb_ouro")))


@pytest.fixture(scope="module")
def tiny_bench():
    return bench_with(TINY, "ouro_tiny", "learn", like=CELL)


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
    # At float32 the counter is the reference's own to rounding.
    assert sound["compared"]["loop_change_gap"]["value"] < 1e-5


@pytest.mark.parametrize("plant, expect", [
    ("bf16", {"grad_gap", "priority_gap", "loop_change_gap", "loss_gap", "single_grad_gap"}),
    ("half_batch", {"grad_gap", "priority_gap", "update_gap", "loss_gap", "single_grad_gap"}),
    ("loop_steps_short", {"grad_gap", "priority_gap", "loop_change_gap", "loss_gap",
                          "single_grad_gap"}),
    ("last_use_gradient", {"grad_gap", "update_gap", "target_gap", "single_grad_gap"}),
    ("frozen", {"grad_gap", "update_gap", "steps_gap", "slots_unmatched", "loss_gap",
                "single_grad_gap"}),
])
def test_planted_control_or_fault_is_not_correct(tiny_root, tiny_bench, plant, expect):
    """One precision down, half the batch left out, the stack run once too
    few, the shared weights' gradient from the last loop step's use alone,
    and a call that hands its state back unchanged."""
    from r2d2dpg_tpu.models import ouro_loop

    seam = ouro_loop.loop
    try:
        r = _run(tiny_root, tiny_bench, plant=plant)
    finally:
        ouro_loop.loop = seam
    assert r["correct"] is False
    failed = {k for k, c in r["compared"].items() if not c["ok"]}
    assert expect <= failed, (plant, failed)
    if plant == "last_use_gradient":  # the forward pass is sound: only gradients tell
        assert not {"priority_gap", "loss_gap"} & failed


def test_slots_are_inferred_against_the_programs_own_priorities_where_they_are_sound():
    """The follow infers each update's slots from the priorities as the
    updates before left them.  What a sound program wrote is a little off the
    reference's on every slot, and the written-back priorities are most of the
    vector's mass: laid over at the reference's values the second update's
    draws land in other slots; at the program's own they land where the
    program drew them.  A slot drawn twice holds its LAST value after the
    call, far from what it held between its draws: there the reference's."""
    drv = harness.load_module("drivers", "learn_ouro_loop")
    n, B = 64, 8
    before = np.full(n, 0.1, np.float32)
    keys = jax.random.split(jax.random.PRNGKey(0), 2)
    replay = {"batch_size": B, "alpha": 1.0, "beta0": 0.4, "beta_steps": 100}
    ref_wrote = [np.full(B, 50.0, np.float32), np.full(B, 0.2, np.float32)]
    program_wrote = [w * np.float32(1.02) for w in ref_wrote]  # sound, 2 % off everywhere

    cur, slots = before.astype(np.float64), []
    for k, key in enumerate(keys):  # what the program drew, against its own vector
        cdf = np.cumsum(cur)
        u = np.asarray(jax.random.uniform(key, (B,)), np.float64) * cdf[-1]
        slots.append(np.minimum(np.searchsorted(cdf, u, side="right"), n - 1))
        cur[slots[-1]] = program_wrote[k]
    assert np.isin(slots[1], slots[0]).sum() >= B // 2  # the raised slots are drawn again
    after = cur.astype(np.float32)
    changed = np.flatnonzero(before != after)
    calls = iter(ref_wrote)

    def update(ref, rows, w):
        return ref, next(calls), {"grads": {}, "last_step_rel_change": 0.5,
                                  "critic_loss": 0.0, "actor_loss": 0.0, "q_abs_mean": 1.0}

    f = drv.learner_call({"step": 0}, before.copy(), before, after, changed, keys,
                         lambda s: s, n, replay, update)
    for k in range(2):
        np.testing.assert_array_equal(f["slots"][k], slots[k])
    assert f["sample_gap"] < 0.5 and f["moved"] == [0.5, 0.5]
    # The reference's own vector keeps the reference's own values.
    assert set(np.unique(f["ref_prio"])) <= {np.float32(0.1), np.float32(0.2), np.float32(50.0)}


def test_traced_run_reads_the_counters_and_leaves_out_the_device_metrics(
    tiny_root, tiny_bench, capfd
):
    r = _run(tiny_root, tiny_bench, trace=True)
    # One set-up a run: the stage table is captured on the live session.
    assert capfd.readouterr().err.count("program built") == 1
    # No device plane in a CPU capture: the stage times and the roofline find
    # nothing to read and are left out, never reported as 0.
    assert set(r["metrics"]) == {"learn_mfu_looped", "loop_last_step_rel_change"}
    assert 0.0 < r["metrics"]["loop_last_step_rel_change"]["value"] < 2.0
    assert r["correct"] is True


def test_new_metric_readers_return_nothing_on_a_program_without_the_core():
    """The parent commit has neither the counters nor the scope ``core_mlp``:
    on another cell's window the readers find nothing and do not raise."""
    ctx = harness.Context(cell_name="x", cell={"driver": "learn"}, config={}, seed=0,
                          seconds=1.0, trace=True)
    ctx.window = {"elapsed_s": 1.0, "calls": 3, "metrics": {}}
    ctx.steady_trace = None
    for reducer, args in (
            ("window_counter", {"group": "loop", "name": "loop/last_step_rel_change"}),
            ("core_stage_ms", {"stage": "core_mlp"}),
            ("core_stage_ms", {"stage": "backward"}),
            ("device_idle", {}),
            ("loop_roofline", {"stage": "core_mlp", "work": "mlp_work"})):
        assert harness.load_module("reducers", reducer).read(ctx, **args) is None
    # A table without the scope (the parent's CORE_STAGES): nothing, not a KeyError.
    ctx.core_stage_table = {"updates": 4, "core_attention": 0.1}
    assert harness.load_module("reducers", "loop_roofline").read(
        ctx, stage="core_mlp", work="mlp_work") is None
    assert harness.load_module("reducers", "core_stage_ms").read(ctx, stage="core_mlp") is None


def test_benchmark_lists_the_cell_its_configuration_and_its_metrics():
    bench = harness.load_benchmark()
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert cell == dict(cell, config="humanoid_ouro_loop", traffic="learn", chips=1)
    assert len(cell["why"]) <= 200 and "95 %" in cell["why"]
    config = next(c for c in bench["configs"] if c["name"] == "humanoid_ouro_loop")
    assert config["reduced"] == ["layers"] and "ByteDance/Ouro-2.6B" in config["source"]
    mine = {m["name"]: m for m in bench["per_layer"] if CELL in m.get("workloads", ())}
    assert set(mine) == PER_LAYER
    for name, m in mine.items():
        assert m["workloads"] == [CELL] and m["moves"] == "learner_steps_per_s"
        spec = harness.load_json("metrics", name)
        assert {k: spec[k] for k in m} == m  # the metric file mirrors the entry
    # Every stage of the learner call has a metric here: what the table
    # holds under LEARN_STAGES + CORE_STAGES but ``rest`` and the write-back.
    for short, stage in STAGES.items():
        spec = harness.load_json("metrics", "loop_stage_ms." + short)
        assert (spec["reducer"], spec["args"]) == ("core_stage_ms", {"stage": stage})
    assert CELL in next(m for m in bench["end_to_end"]
                        if m["name"] == "learner_steps_per_s")["workloads"]


@pytest.fixture(scope="module")
def cfg():
    return harness.load_json("configs", "humanoid_ouro_loop")


def test_configuration_file_holds_the_published_config_and_names_its_cut(cfg):
    published = {
        "head_dim": 128, "hidden_act": "silu", "hidden_size": 2048,
        "intermediate_size": 5632, "layer_types": ["full_attention"] * 48,
        "max_position_embeddings": 65536, "max_window_layers": 48, "model_type": "ouro",
        "num_attention_heads": 16, "num_hidden_layers": 48, "num_key_value_heads": 16,
        "rms_norm_eps": 1e-06, "rope_scaling": None, "rope_theta": 1000000,
        "sliding_window": None, "tie_word_embeddings": False, "total_ut_steps": 4,
        "early_exit_threshold": 1, "use_sliding_window": False, "vocab_size": 49152,
    }
    assert {k for k, v in published.items() if cfg.get(k, "absent") != v} == set()
    assert set(cfg["changed"]) == {"layers"} and cfg["layers"] == 4
    assert cfg["changed"]["layers"]["from"] == 48 and cfg["published"] == {"layers": 48}
    assert {"vocabulary", "exit_gate", "mask"} <= set(cfg["departures"])
    assert {"sandwich_norms", "final_norm_every_pass", "matmul_precision", "weights",
            "replay_contents", "task"} <= set(cfg["assumed"])
    assert "12" in cfg["deployment"] and "pipeline" in cfg["deployment"]
    # The task is the sdar configuration's to the key: the two cells differ by the core.
    sdar = harness.load_json("configs", "humanoid_sdar_moe")
    task = ("obs_shape", "action_dim", "burnin", "unroll", "n_step", "batch_size",
            "learner_steps", "capacity", "actor_lr", "critic_lr", "tau", "grad_clip",
            "compute_dtype", "priority_alpha", "beta0")
    assert {k: cfg[k] for k in task} == {k: sdar[k] for k in task}


def test_parameter_count_is_the_issues_arithmetic(cfg):
    actor, critic = ref_ouro.weight_shapes(cfg)
    count = lambda t: sum(int(np.prod(s.shape)) for s in jax.tree_util.tree_leaves(t))  # noqa: E731
    layer = 4 * 2048 * 2048 + 3 * 2048 * 5632
    assert layer == 51_380_224
    core = count(actor["params"]["core"])
    assert core == 4 * (layer + 4 * 2048) + 2048  # four layers, their norms, the final norm
    assert count(actor) == core + 67 * 2048 + 2048 + 2048 * 21 + 21
    assert count(critic) == core + 67 * 2048 + 2048 + (2048 + 21) * 2048 + 2048 + 2048 + 1
    assert 415e6 < count(actor) + count(critic) < 416e6


def test_learner_flops_go_by_applications_and_mlp_bytes_by_parameters(cfg):
    whole = counts_ouro_loop.learner_step_flops(cfg)
    assert counts_ouro_loop.learn_call_flops(cfg) == 4 * whole
    # Per token and APPLICATION the dense part is a layer's 51.38 M multiply-adds;
    # 16 applications; (4 x 40 + 2 x 45) forward tokens and 3 x 40 differentiated
    # ones a sequence, of which the critic on the policy's actions takes no weight gradient.
    floor = 2.0 * 64 * 16 * 51_380_224 * (4 * 40 + 2 * 45 + 3 * 40 + 40 * (2 + 2 + 1)) * 15 / 16
    assert floor < whole < 1.1 * floor
    assert counts_ouro_loop.learner_step_flops(dict(cfg, batch_size=32)) == pytest.approx(whole / 2)
    # One more loop step is one more stack of applications, and no more parameters.
    five = dict(cfg, total_ut_steps=5)
    assert counts_ouro_loop.learner_step_flops(five) == pytest.approx(whole * 5 / 4, rel=0.02)
    assert ref_ouro.weight_shapes(five)[0] == ref_ouro.weight_shapes(cfg)[0]
    w, w5 = counts_ouro_loop.mlp_work(cfg), counts_ouro_loop.mlp_work(five)
    kernels = 4 * 3 * 2048 * 5632
    tokens = 64 * (4 * 40 * 15 + 2 * 45 * 16 + 40 * 16 * (3 + 3 + 2))
    assert w["flops"] == 6.0 * 2048 * 5632 * tokens
    # Reads go by applications (15 a burn-in pass, 16 a target pass, 32 where
    # a gradient is taken), the written gradient by layers (4, twice).
    assert w["bytes"] == kernels * (4 * 15 + 2 * 16 + 3 * 32 + 2 * 4)
    assert w5["bytes"] - w["bytes"] == kernels * 4 * (4 + 2 + 3 * 2)


def test_counts_match_xla_on_the_unrolled_unrematerialised_tiny_call(monkeypatch):
    """XLA's own count of the tiny learner step with every scan unrolled and
    no ``jax.checkpoint`` (CPU counts, no device number): what the
    mathematics needs, which is what ``counts_ouro_loop`` counts.  Rolled,
    XLA counts a scan's body once."""
    from chipbench.drivers import learn_sdar_moe as sdar
    from chipbench.program import to_batch

    drv = harness.load_module("drivers", "learn_ouro_loop")
    tiny = dict(harness.load_json("configs", "ouro_tiny", os.path.join(DATA)),
                batch_size=4, burnin=3, unroll=4, n_step=2)
    cell = harness.load_json("workloads", TINY, DATA)

    def step_flops(unrolled):
        if unrolled:
            scan = jax.lax.scan
            monkeypatch.setattr(
                jax.lax, "scan",
                lambda f, init, xs=None, length=None, **kw: scan(
                    f, init, xs, length=length, unroll=True))
            monkeypatch.setattr(jax, "checkpoint", lambda f, **kw: f)
        ctx = harness.Context(cell_name=TINY, cell=cell, config=tiny, seed=3,
                              seconds=0.0, trace=False)
        trainer, _, _, spec, _ = drv.program(ctx)
        rows, _ = sdar.make_rows(jax.random.PRNGKey(0), jnp.arange(4), spec, cell["traffic"])
        train = drv.make_train_state(trainer, spec, tiny, 3)
        step = jax.jit(lambda t, b, w: trainer.agent.learner_step(t, b, w))
        cost = step.lower(train, to_batch(rows), jnp.ones((4,))).compile().cost_analysis()
        cost = cost[0] if isinstance(cost, (list, tuple)) else cost
        return float(cost["flops"])

    mine = counts_ouro_loop.learner_step_flops(tiny)
    rolled, unrolled = step_flops(False), step_flops(True)
    # XLA also counts element-wise work and what the program computes beyond
    # need (the burn-in's last application whole): mine is the floor.
    assert 0.6 * unrolled <= mine <= 1.02 * unrolled, (mine, unrolled)
    assert rolled < 0.5 * mine, (rolled, mine)


def test_tiny_data_files_are_the_cells_own_shape():
    with open(os.path.join(DATA, "workloads", TINY + ".json")) as f:
        tiny = json.load(f)
    with open(os.path.join(REPO, "chipbench", "workloads", CELL + ".json")) as f:
        cell = json.load(f)
    assert set(tiny) == set(cell) and set(tiny["limits"]) == set(cell["limits"])
    assert set(tiny["traffic"]) == set(cell["traffic"]) and tiny["driver"] == cell["driver"]
    assert cell["traffic"]["in_flight_calls"] == 2 and len(cell["why"]) <= 200
    sdar = harness.load_json("workloads", "humanoid_sdar_moe.learn")["traffic"]
    assert {k: v for k, v in cell["traffic"].items() if k != "in_flight_calls"} == {
        k: v for k, v in sdar.items() if k != "in_flight_calls"}
