"""The stage scopes of the learner call (``utils/profiling.py::LEARN_STAGES``)
reach the compiled program's ``op_name`` metadata, and the rule that reads a
stage off such a path (``obs/stages.py::stage_of``)."""

import dataclasses
import re

import jax
import pytest

from r2d2dpg_tpu.obs.stages import BACKWARD, REST, UNSCOPED, stage_of, table_keys
from r2d2dpg_tpu.utils.profiling import LEARN_STAGES, PREPARE_STAGES

WITH_FRAMES = LEARN_STAGES + PREPARE_STAGES


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
    assert "frames" not in {stage_of(p, WITH_FRAMES) for p in op_names[prefetch]}
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
    frames = [p for p in paths if stage_of(p, WITH_FRAMES) == "frames"]
    assert any(p.endswith("/div") for p in frames)
    assert any(p.endswith("/transpose") for p in frames)
    assert not [p for p in paths if p.endswith("/div") and "/torso/" in p]
    assert {"burn_in", "forward", BACKWARD, "optimizer"} <= {
        stage_of(p, WITH_FRAMES) for p in paths}
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


def test_table_keys_are_the_stages_and_what_is_derived():
    assert table_keys() == LEARN_STAGES + (BACKWARD, UNSCOPED, REST)
    # The once-an-update preparation (PR 35) is a key of the wider table only.
    assert "frames" not in table_keys() and table_keys(WITH_FRAMES) == (
        WITH_FRAMES + (BACKWARD, UNSCOPED, REST))
    assert stage_of("jit(timed)/while/body/closed_call/frames/div", WITH_FRAMES) == "frames"
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
