"""The stage scopes of the learner call (``utils/profiling.py::LEARN_STAGES``)
reach the compiled program's ``op_name`` metadata, and the rule that reads a
stage off such a path (``obs/stages.py::stage_of``)."""

import dataclasses
import os
import re

import jax
import pytest

from r2d2dpg_tpu.obs.stages import (
    ALL_SCOPES, BACKWARD, LOOPS, PASSES, RECOMPUTED, REST, SCOPE_ROWS, UNSCOPED,
    pass_of, scope_of, stage_of, table_keys)
from r2d2dpg_tpu.utils.profiling import CORE_STAGES, LEARN_STAGES, SIDE_STAGES

# The scopes beside the five stages (``frames``, ``diagnostics``): keys of a
# table read with them, rows of every table's ``scopes``.
WITH_SIDE = LEARN_STAGES + SIDE_STAGES
CAPTURE = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                       "chipbench", "testdata", "tiny.xplane.pb")


@pytest.fixture(scope="module")
def op_names():
    """``op_name`` paths of ``Trainer._learn_many`` compiled at the tiny test
    configuration, by branch.  Two updates a call: with one, the prefetched
    branch's sample inside the loop feeds nothing and is compiled away."""
    from r2d2dpg_tpu.configs import PENDULUM_TINY

    t = dataclasses.replace(
        PENDULUM_TINY,
        trainer=dataclasses.replace(PENDULUM_TINY.trainer, learner_steps=2),
    ).build()
    s = t.init()
    key = jax.random.PRNGKey(0)
    out = {}
    for prefetch in (False, True):
        text = jax.jit(
            lambda tr, ar, k: t._learn_many(tr, ar, k, prefetch=prefetch)
        ).lower(s.train, s.arena, key).compile().as_text()
        out[prefetch] = set(re.findall(r'op_name="([^"]*)"', text))
    return out


@pytest.mark.parametrize("prefetch", (False, True))
def test_every_stage_is_on_a_path_of_the_compiled_learner_call(op_names, prefetch):
    found = {stage_of(p) for p in op_names[prefetch]}
    assert set(LEARN_STAGES) | {BACKWARD} <= found
    # ``frames`` holds nothing here: for a flat observation the preparation
    # is the identity (the pixel case is below).
    assert "frames" not in {stage_of(p, WITH_SIDE) for p in op_names[prefetch]}
    # The prefetched branch samples before the loop and inside it.
    sample = [p for p in op_names[prefetch] if stage_of(p) == "replay_sample"]
    assert any("/while/body/" in p.split("replay_sample")[0] for p in sample)
    if prefetch:
        assert any("/while/" not in p.split("replay_sample")[0] for p in sample)


def test_forward_is_differentiated_and_burn_in_is_not(op_names):
    paths = op_names[False]
    forward = [p for p in paths if "/forward/" in p]
    assert any("transpose(" in p for p in forward)
    assert any("transpose(" not in p for p in forward)
    assert {stage_of(p) for p in forward} == {"forward", BACKWARD}
    burn_in = [p for p in paths if "/burn_in/" in p]
    assert burn_in and not any("transpose(" in p for p in burn_in)
    # ``transpose`` the operation (no parenthesis) is not the wrapper.
    assert any(p.endswith("/burn_in/transpose") for p in burn_in)


@pytest.mark.parametrize("prefetch", (False, True))
def test_diagnostics_is_a_scope_of_the_compiled_learner_call_and_of_no_stage(
        op_names, prefetch):
    """The update's in-graph counters (the norm walks of ``grad_norm`` and
    ``param_norm``, the quality gauges' gather out of ``arena.meta``) carry
    ``diagnostics``, and none of them lies under one of the five stages."""
    mine = [p for p in op_names[prefetch] if "diagnostics" in p.split("/")]
    assert mine and {scope_of(p) for p in mine} == {"diagnostics"}
    assert {stage_of(p) for p in mine} == {REST}
    assert {stage_of(p, WITH_SIDE) for p in mine} == {"diagnostics"}
    inside = [p for p in mine if "/while/body/" in p]
    for op in ("reduce_sum", "sqrt", "gather", "ge"):
        assert any(p.endswith("/diagnostics/" + op) for p in inside), op
    # Every stage still has its operations: the scope took none of theirs.
    assert set(LEARN_STAGES) <= {scope_of(p) for p in op_names[prefetch]}


def test_frames_is_a_stage_of_a_pixel_update_and_holds_the_preparation():
    """With a conv torso ``learner_step`` prepares the sampled frames once,
    under ``frames``: the conversion and the re-lay are on its paths, and no
    pass converts pixels again under ``burn_in`` or ``forward``."""
    import jax.numpy as jnp

    from r2d2dpg_tpu.agents.ddpg import AgentConfig, R2D2DPG
    from r2d2dpg_tpu.models.actor_critic import ActorNet, CriticNet
    from r2d2dpg_tpu.replay.arena import SequenceBatch

    agent = R2D2DPG(
        ActorNet(action_dim=2, hidden=8, pixels=True),
        CriticNet(hidden=8, pixels=True),
        AgentConfig(burnin=2, unroll=2, n_step=1),
    )
    B, L = 2, agent.config.seq_len
    obs = jnp.zeros((B, L, 36, 36, 3), jnp.uint8)
    carry = (jnp.zeros((B, 8)), jnp.zeros((B, 8)))
    batch = SequenceBatch(
        obs=obs, action=jnp.zeros((B, L, 2)), reward=jnp.zeros((B, L)),
        discount=jnp.ones((B, L)), reset=jnp.zeros((B, L)),
        carries={"actor": carry, "critic": carry})
    state = agent.init(jax.random.PRNGKey(0), obs[:, 0], batch.action[:, 0])
    text = jax.jit(agent.learner_step).lower(
        state, batch, jnp.ones(B)).compile().as_text()
    paths = set(re.findall(r'op_name="([^"]*)"', text))
    frames = [p for p in paths if stage_of(p, WITH_SIDE) == "frames"]
    assert any(p.endswith("/div") for p in frames)
    assert any(p.endswith("/transpose") for p in frames)
    assert not [p for p in paths if p.endswith("/div") and "/torso/" in p]
    assert {"burn_in", "forward", BACKWARD, "optimizer"} <= {
        stage_of(p, WITH_SIDE) for p in paths}
    # Read with the learner's five alone, the preparation is ``rest``.
    assert {stage_of(p) for p in frames} == {REST}


@pytest.mark.parametrize("path, stage", [
    ("jit(timed)/while/body/closed_call/frames/div", REST),
    ("jit(timed)/while/body/closed_call/frames/optimization_barrier", REST),
    ("jit(timed)/while/body/closed_call/forward/jvp()/while/body/dot_general", "forward"),
    ("jit(timed)/while/body/closed_call/forward/transpose(jvp())/while/body/dot_general", BACKWARD),
    ("jit(f)/learn/transpose(jvp(forward))/while/body/closed_call/mul", BACKWARD),
    ("jit(f)/learn/jvp(forward)/while/body/closed_call/dot_general", "forward"),
    ("jit(f)/vmap(burn_in)/while/body/tanh", "burn_in"),
    ("jit(timed)/replay_sample/jit(cumsum)/reduce_window_sum", "replay_sample"),
    ("jit(timed)/replay_sample/forward/add", "forward"),  # the innermost wins
    ("jit(timed)/burn_in/transpose", "burn_in"),  # the operation, not a wrapper
    ("jit(forward)/add", REST),  # jit(...) names a function, not a scope
    ("jit(timed)/jit(_threefry_split)/slice", REST),
    ("jit(timed)/optimizer/jit(_where)/select_n", "optimizer"),
    ("jit(timed)/while/body/closed_call/priority_update/jit(_pallas_scatter)", "priority_update"),
    ("", UNSCOPED),
    (None, UNSCOPED),
])
def test_stage_of_a_path(path, stage):
    assert stage_of(path) == stage


# A ``grad`` through ``jax.checkpoint`` under a scope (jax 0.9.0), and the
# cases of ``test_stage_of_a_path``: path, row of ``scopes``, pass.
@pytest.mark.parametrize("path, row, pass_", [
    ("jit(f)/jvp(forward)/core_mlp/tanh", "core_mlp", "forward"),
    ("jit(f)/jvp(forward)/core_mlp/dot_general", "core_mlp", "forward"),
    ("jit(f)/jvp(forward)/mul", "forward", "forward"),
    ("jit(f)/transpose(jvp(forward))/mul", "forward", BACKWARD),
    ("jit(f)/transpose(jvp(forward))/jvp(forward)/checkpoint/core_mlp/mul", "core_mlp", BACKWARD),
    ("jit(f)/transpose(jvp(forward))/jvp(forward)/checkpoint/rematted_computation/core_mlp/dot_general",
     "core_mlp", RECOMPUTED),
    ("jit(f)/transpose(jvp(forward))/jvp(forward)/checkpoint/rematted_computation/core_mlp/tanh",
     "core_mlp", RECOMPUTED),
    ("jit(timed)/while/body/closed_call/frames/div", "frames", "forward"),
    ("jit(timed)/while/body/closed_call/frames/optimization_barrier", "frames", "forward"),
    ("jit(timed)/while/body/closed_call/forward/jvp()/while/body/dot_general", "forward", "forward"),
    ("jit(timed)/while/body/closed_call/forward/transpose(jvp())/while/body/dot_general", "forward", BACKWARD),
    ("jit(f)/learn/transpose(jvp(forward))/while/body/closed_call/mul", "forward", BACKWARD),
    ("jit(f)/learn/jvp(forward)/while/body/closed_call/dot_general", "forward", "forward"),
    ("jit(f)/vmap(burn_in)/while/body/tanh", "burn_in", "forward"),
    ("jit(timed)/replay_sample/jit(cumsum)/reduce_window_sum", "replay_sample", "forward"),
    ("jit(timed)/replay_sample/forward/add", "forward", "forward"),
    ("jit(timed)/burn_in/transpose", "burn_in", "forward"),  # the operation, not a wrapper
    ("jit(forward)/add", REST, "forward"),
    ("jit(timed)/jit(_threefry_split)/slice", REST, "forward"),
    # Named by a control-flow construct under no scope: the loop's own.
    ("jit(timed)/while", LOOPS, "forward"),
    ("jit(timed)/while/body/closed_call", LOOPS, "forward"),
    ("jit(f)/transpose(jvp(while))/body/closed_call", LOOPS, BACKWARD),
    ("jit(timed)/while/body/dynamic_update_slice", REST, "forward"),
    ("jit(timed)/while/body/closed_call/forward/jvp()/while", "forward", "forward"),
    ("jit(timed)/optimizer/jit(_where)/select_n", "optimizer", "forward"),
    ("jit(timed)/while/body/closed_call/priority_update/jit(_pallas_scatter)", "priority_update", "forward"),
    ("", UNSCOPED, "forward"),
    (None, UNSCOPED, "forward"),
])
def test_scope_and_pass_of_a_path(path, row, pass_):
    assert scope_of(path) == row and pass_of(path) == pass_
    assert row in SCOPE_ROWS and pass_ in PASSES
    # The old key of a path is its row, but ``forward`` transposed (and a
    # key knows no ``loops``: that is ``rest`` there).
    old = stage_of(path, ALL_SCOPES)
    assert old == (BACKWARD if row == "forward" and pass_ != "forward"
                   else REST if row == LOOPS else row)


def test_a_clone_the_compilers_rematerialisation_made_goes_by_its_path(tmp_path):
    """``fusion.1.remat2`` on a backward path is ``backward``: the name says
    the compiler cloned it, not that the original runs as well (sdar's 104
    listed clones: the original ran too for 38, PERF.md PR 36)."""
    from r2d2dpg_tpu.obs.stages import stage_table

    tf_op = _msg((5, _msg((1, 1), (2, _msg((1, 1), (2, b"tf_op"))))))
    path = b"jit(f)/forward/transpose(jvp(core))/moe_experts/checkpoint/dot_general:dot"
    clone = _msg((4, _msg((1, 1), (2, _msg(
        (1, 1), (2, b"%fusion.1.remat2 = f32[8]{0} fusion(%p)"),
        (5, _msg((1, 1), (5, path))))))))
    line = _msg((2, b"XLA Ops"), (4, _msg((1, 1), (2, 0), (3, 700_000))))
    file = tmp_path / "clone.xplane.pb"
    file.write_bytes(_msg((1, _msg((2, b"/device:TPU:0"), (3, line)) + tf_op + clone)))
    t = stage_table(str(file))
    assert t["scopes"]["moe_experts"] == {
        "forward": 0.0, RECOMPUTED: 0.0, BACKWARD: pytest.approx(700e-9),
        "all": pytest.approx(700e-9)}
    assert t["scope_ops"]["moe_experts"] == {BACKWARD: [["fusion.1.remat2", pytest.approx(700e-9)]]}
    assert t[BACKWARD] == pytest.approx(700e-9)  # the key: ``forward`` transposed


def test_jax_writes_the_three_passes_into_the_paths_of_a_checkpointed_scope():
    """The rule reads what this image's JAX writes: under a scope, a ``grad``
    through ``jax.checkpoint`` has operations of all three passes."""
    import jax.numpy as jnp

    from r2d2dpg_tpu.utils.profiling import scope

    def core(w, x):
        with scope("core_mlp"):
            return jnp.tanh(x @ w) * x

    def loss(w, x):
        with scope("forward"):
            return (jax.checkpoint(core)(w, x) ** 2).sum()

    text = jax.jit(jax.grad(loss)).lower(
        jnp.ones((8, 8)), jnp.ones((4, 8))).as_text(debug_info=True)
    paths = set(re.findall(r'loc\("(jit\(loss\)[^"]*)"', text))
    inner = {pass_of(p) for p in paths if scope_of(p) == "core_mlp"}
    assert inner == set(PASSES)
    assert {pass_of(p) for p in paths if scope_of(p) == "forward"} == {"forward", BACKWARD}
    assert "jit(loss)/jvp(forward)/core_mlp/tanh" in paths
    assert any(p.endswith("/checkpoint/rematted_computation/core_mlp/dot_general") for p in paths)


def test_table_keys_are_the_stages_and_what_is_derived():
    assert table_keys() == LEARN_STAGES + (BACKWARD, UNSCOPED, REST)
    # The scopes beside the stages (PR 35's once-an-update preparation, PR
    # 36's diagnostics) are keys of the wider table only, and rows of
    # ``scopes`` always.
    assert not set(SIDE_STAGES) & set(table_keys()) and table_keys(WITH_SIDE) == (
        WITH_SIDE + (BACKWARD, UNSCOPED, REST))
    assert SIDE_STAGES == ("frames", "diagnostics")
    assert SCOPE_ROWS == LEARN_STAGES + SIDE_STAGES + CORE_STAGES + (LOOPS, UNSCOPED, REST)
    assert stage_of("jit(timed)/while/body/closed_call/frames/div", WITH_SIDE) == "frames"
    assert table_keys(("learn", "alpha")) == ("learn", "alpha", UNSCOPED, REST)
    assert stage_of("jit(f)/transpose(jvp(learn))/mul", ("learn",)) == "learn"


# ------------------------------------------- the reader on a synthetic capture
def _varint(n):
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def _msg(*fields):
    """Protobuf wire format: (number, int) a varint, (number, bytes) a
    length-delimited field."""
    out = bytearray()
    for number, value in fields:
        if isinstance(value, int):
            out += _varint(number << 3) + _varint(value)
        else:
            out += _varint((number << 3) | 2) + _varint(len(value)) + value
    return bytes(out)


def test_reader_on_a_capture_with_a_host_wait_inside_a_loop(tmp_path):
    """A ``while`` of 1000 ns that holds a fusion of ``forward`` (300 ns), a
    ``recv-done`` (500 ns) and a copy the compiler left without a name
    (60 ns): the wait counts nowhere, the loop keeps its own 140 ns under the
    path its program's Hlo Proto gives it, the unnamed copy inside it is named
    by the loop, and a top-level copy without a path is unscoped."""
    from r2d2dpg_tpu.obs.stages import stage_table

    TF_OP, PROGRAM, HLO = 1, 2, 3  # stat metadata ids
    stat_names = [_msg((5, _msg((1, i), (2, _msg((1, i), (2, name)))))) for i, name in
                  ((TF_OP, b"tf_op"), (PROGRAM, b"program_id"), (HLO, b"Hlo Proto"))]

    def op(mid, text, tf_op=None):
        stats = [(5, _msg((1, PROGRAM), (3, 77)))]
        if tf_op:
            stats.append((5, _msg((1, TF_OP), (5, tf_op))))
        return _msg((4, _msg((1, mid), (2, _msg((1, mid), (2, text), *stats)))))

    def event(mid, start_ns, dur_ns):
        return (4, _msg((1, mid), (2, start_ns * 1000), (3, dur_ns * 1000)))

    line = _msg((2, b"XLA Ops"), event(1, 0, 1000), event(2, 100, 300),
                event(3, 400, 500), event(5, 920, 60), event(4, 1000, 50))
    device = _msg((2, b"/device:TPU:0"), (3, line)) + b"".join(stat_names) + b"".join([
        op(1, b"%while.7 = (s32[]) while(%t), body=%b"),
        op(2, b"%fusion.1 = f32[8]{0} fusion(%p)", b"jit(f)/learn/forward/while/body/add:add"),
        op(3, b"%recv-done.2 = (f32[8]{0}, token[]) recv-done(%recv.2)",
           b"jit(f)/learn/forward/while/body/io_callback:"),
        op(4, b"%copy.3 = f32[8]{0} copy(%q)"),
        op(5, b"%copy.9 = f32[8]{0} copy(%r)"),
    ])
    instr = _msg((1, b"while.7"), (2, b"while"), (7, _msg((2, b"jit(f)/learn/forward/while"))))
    hlo = _msg((1, _msg((1, b"jit_f"), (3, _msg((1, b"main"), (2, instr))))))
    metadata = _msg((2, b"/host:metadata")) + stat_names[2] + _msg(
        (4, _msg((1, 77), (2, _msg((1, 77), (2, b"jit_f(77)"),
                                   (5, _msg((1, HLO), (6, hlo))))))))
    path = tmp_path / "synthetic.xplane.pb"
    path.write_bytes(_msg((1, device), (1, metadata)))

    t = stage_table(str(path))
    assert t["devices"] == 1
    # The fusion, the loop's own and the copy inside the loop.
    assert t["forward"] == pytest.approx(500e-9)
    assert t["unscoped"] == pytest.approx(50e-9)
    assert [name for name, _ in t["unscoped_ops"]] == ["copy.3"]
    assert t["rest"] == 0.0 and t["backward"] == 0.0
    assert t["busy"] == pytest.approx(550e-9)  # 1050 ns of intervals, 500 waiting


# ------------------------------------------------ the capture recorded on the chip
# What the reader of PR 35 (this PR's parent) gives on
# ``chipbench/testdata/tiny.xplane.pb`` with the learner's five stages: the
# keys a table had before ``scopes``, to the last digit.
PARENT_TABLE = {
    "replay_sample": 2.779e-06, "burn_in": 0.0, "forward": 0.0, "optimizer": 0.0,
    "priority_update": 0.0, "backward": 0.0, "unscoped": 4.7760000000000005e-06,
    "rest": 3.79e-06, "busy": 1.1345e-05, "devices": 1,
    "rest_paths": [
        ["jit(tiny_step)/learn/while/body/closed_call/dot_general", 2.6880000000000004e-06],
        ["jit(tiny_step)/chipbench/alpha/dot_general", 6.650000000000001e-07],
        ["jit(tiny_step)/learn/while", 4.37e-07]],
    "unscoped_ops": [
        ["convert.1", 2.1740000000000003e-06], ["copy-done.1", 1.5670000000000002e-06],
        ["copy-done.2", 8.49e-07], ["copy-done", 7.800000000000001e-08],
        ["copy-start", 6.600000000000001e-08]],
}


@pytest.fixture(scope="module")
def recorded():
    from r2d2dpg_tpu.obs.stages import stage_table

    return stage_table(CAPTURE)


def test_old_keys_of_the_recorded_capture_are_the_parent_readers(recorded):
    assert {k: recorded[k] for k in PARENT_TABLE} == PARENT_TABLE
    assert set(recorded) == set(PARENT_TABLE) | {
        "scopes", "scope_ops", "programs", "truncated", "host_after_ops"}


def test_rows_of_scopes_add_up_to_busy_on_the_recorded_capture(recorded):
    scopes = recorded["scopes"]
    assert tuple(scopes) == SCOPE_ROWS
    for row in scopes.values():
        assert set(row) == set(PASSES) | {"all"}
        assert row["all"] == pytest.approx(sum(row[p] for p in PASSES), rel=1e-12)
    assert sum(r["all"] for r in scopes.values()) == pytest.approx(recorded["busy"], rel=1e-9)
    # The probe's cumsum is the one scope of the learner's here; the loop
    # over the scan's steps has its own time (184 ns) and the copy the
    # compiler put inside it under the loop's name (253 ns): the whole of
    # the path ``jit(tiny_step)/learn/while``; the matmuls are ``rest``.
    assert scopes["replay_sample"]["all"] == recorded["replay_sample"]
    assert scopes[UNSCOPED]["all"] == recorded["unscoped"]
    assert scopes[LOOPS]["all"] + scopes[REST]["all"] == pytest.approx(recorded["rest"])
    assert scopes[LOOPS]["all"] == pytest.approx(4.37e-07)
    assert [name for name, _ in recorded["scope_ops"][LOOPS]["forward"]] == ["copy.9", "while"]
    assert {name for name, _ in recorded["scope_ops"][REST]["forward"]} == {
        "convolution_tanh_fusion.3", "convolution_tanh_fusion.1"}
    assert recorded["scope_ops"]["replay_sample"]["forward"] == [
        ["reduce_window_sum.1", recorded["replay_sample"]]]
    for row, by_pass in recorded["scope_ops"].items():
        for pass_, ops in by_pass.items():
            assert 1 <= len(ops) <= 5 and scopes[row][pass_] >= ops[0][1] > 0.0


def test_programs_of_the_recorded_capture_are_counted_from_the_device(recorded):
    assert [(p["name"], p["executions"]) for p in recorded["programs"]] == [
        ("jit_tiny_step", 5), ("jit_tiny_probe", 3)]
    assert recorded["programs"][0]["seconds"] == pytest.approx(8.656e-06)
    assert recorded["truncated"] is False
    # The device line ends with the last probe, half a microsecond AFTER the
    # span ``chipbench/window`` closed on the host's clock.
    assert recorded["host_after_ops"] == pytest.approx(-5.39e-07)


def _cut_copy(path, drop_share):
    """The recorded capture with the last ``drop_share`` of the events of
    every device plane's line ``XLA Ops`` left out; the file itself is read
    only."""
    from r2d2dpg_tpu.obs.stages import DEVICE_PLANE, OPS_LINE, _fields, _first

    def start(event):
        return _first(event, 2, 0)

    out = []
    with open(CAPTURE, "rb") as f:
        space = f.read()
    for number, plane in _fields(space):
        if number == 1 and DEVICE_PLANE.match(_first(plane, 2, b"").decode()):
            fields = []
            for n, v in _fields(plane):
                if n == 3 and _first(v, 2) == OPS_LINE.encode():
                    line = list(_fields(v))
                    events = sorted((x for m, x in line if m == 4), key=start)
                    kept = set(events[:len(events) - int(len(events) * drop_share)])
                    v = _msg(*[(m, x) for m, x in line if m != 4 or x in kept])
                fields.append((n, v))
            plane = _msg(*fields)
        out.append((number, plane))
    path.write_bytes(_msg(*out))
    return str(path)


def test_a_capture_cut_short_reads_truncated_and_fewer_executions(tmp_path, recorded):
    from r2d2dpg_tpu.obs.stages import stage_table

    whole = stage_table(_cut_copy(tmp_path / "whole.xplane.pb", 0.0))
    assert whole == recorded  # the copy's wire format is the file's
    cut = stage_table(_cut_copy(tmp_path / "cut.xplane.pb", 1.0 / 3.0))
    assert cut["truncated"] is True
    # 59 of 88 operations are left: three whole executions of the step (17
    # operations each) and the head of the fourth; no probe.
    assert [(p["name"], p["executions"]) for p in cut["programs"]] == [("jit_tiny_step", 3)]
    assert cut["host_after_ops"] > 1e-3  # the span went on for 2 ms more
    assert 0.0 < cut["busy"] < recorded["busy"]
    assert sum(r["all"] for r in cut["scopes"].values()) == pytest.approx(cut["busy"], rel=1e-9)


@pytest.mark.parametrize("span, span_end_ns, truncated", [
    # The device line ends inside a third execution, 300 ns after the span:
    # lost under the benchmark's window, which closes on a drained device;
    # expected under a train phase, which is dispatched ahead of the device.
    ("chipbench/window", 2_200, True),
    ("trainer/train_phase", 2_200, False),
    # The host's span goes on for 20 ms after the device line: lost, whatever
    # the span.
    ("chipbench/window", 20_002_500, True),
    ("trainer/train_phase", 20_002_500, True),
    ("chipbench/probes", 20_002_500, False),  # not a span a capture is taken over
])
def test_truncated_is_told_from_the_hosts_span_and_the_last_whole_execution(
        tmp_path, span, span_end_ns, truncated):
    from r2d2dpg_tpu.obs.stages import stage_table

    def event(mid, start_ns, dur_ns):
        return (4, _msg((1, mid), (2, start_ns * 1000), (3, dur_ns * 1000)))

    def named(mid, text):
        return _msg((4, _msg((1, mid), (2, _msg((1, mid), (2, text))))))

    ops = _msg((2, b"XLA Ops"), event(1, 0, 1000), event(1, 1000, 1000), event(1, 2000, 500))
    modules = _msg((2, b"XLA Modules"), event(2, 0, 1000), event(2, 1000, 1000))
    device = _msg((2, b"/device:TPU:0"), (3, ops), (3, modules)) + named(
        1, b"%fusion.1 = f32[8]{0} fusion(%p)") + named(2, b"jit_step(77)")
    host = _msg((2, b"/host:CPU"), (3, _msg((2, b"python"), (3, 100), event(3, 0, span_end_ns - 100)))
                ) + named(3, span.encode())
    path = tmp_path / "spans.xplane.pb"
    path.write_bytes(_msg((1, device), (1, host)))
    t = stage_table(str(path))
    assert t["programs"] == [{"name": "jit_step", "executions": 2, "seconds": pytest.approx(2e-6)}]
    assert t["truncated"] is truncated
    if span in ("chipbench/window", "trainer/train_phase"):
        assert t["host_after_ops"] == pytest.approx((span_end_ns - 2500) * 1e-9)
    else:
        assert t["host_after_ops"] is None
    assert t["scopes"]["unscoped"]["all"] == pytest.approx(t["busy"]) == pytest.approx(2.5e-6)
