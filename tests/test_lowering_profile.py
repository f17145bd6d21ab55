"""Inside `executor.lower` (PR 52): the phases of a trace as spans under the
executor's span, the lowering's Python by (phase, op type) as SELF time on that
span's `by_op`, and nothing of either while the monitor is off.  Tiny programs
on the CPU: (a) a `backward` and an optimizer, (b) an `is_sparse` table, (c) a
`recompute_scope` segment, (d) a `layers.Repeat` body, (e) no `backward` at
all, (f) an op with a `custom_vjp` of the program's own."""
import re

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import layers, monitor
from paddle_tpu.core import lowering
from paddle_tpu.monitor import MONITOR, NULL_SPAN

FIVE = ("plan_kept", "sparse_probe", "forward", "transpose", "update")
RNG = np.random.RandomState(0)
X, Y = RNG.randn(4, 8).astype("f4"), RNG.randn(4, 1).astype("f4")


@pytest.fixture(autouse=True)
def _clean_monitor():
    monitor.disable()
    monitor.reset()
    yield
    monitor.disable()
    monitor.reset()


def _loss(h, y):
    return layers.mean(layers.square_error_cost(layers.fc(h, 1), y))


def _dense(main_only=False):
    x, y = layers.data("x", [8]), layers.data("y", [1])
    loss = _loss(layers.fc(x, 16, act="relu"), y)
    if not main_only:
        fluid.optimizer.Adam(0.01).minimize(loss)
    return loss, {"x": X, "y": Y}


def _sparse():
    ids, y = layers.data("ids", [4], dtype="int64"), layers.data("y", [1])
    emb = layers.embedding(ids, size=[50, 8], is_sparse=True)
    loss = _loss(layers.reshape(emb, [-1, 32]), y)
    fluid.optimizer.SGD(0.1).minimize(loss)
    return loss, {"ids": RNG.randint(0, 50, (4, 4)).astype("int64"), "y": Y}


def _segment():
    x, y = layers.data("x", [8]), layers.data("y", [1])
    h = layers.fc(x, 16, act="relu")
    with fluid.recompute_scope():
        h = layers.fc(layers.fc(h, 16, act="relu"), 16, act="relu")
    loss = _loss(h, y)
    fluid.optimizer.SGD(0.1).minimize(loss)
    return loss, {"x": X, "y": Y}


def _repeat():
    x, y = layers.data("x", [8]), layers.data("y", [1])
    loop = layers.Repeat(3)
    with loop.block():
        h = loop.carry(x)
        loop.update(h, layers.fc(h, 8, act="tanh"))
    loss = _loss(loop.final(h), y)
    fluid.optimizer.SGD(0.1).minimize(loss)
    return loss, {"x": X, "y": Y}


def _custom_rule():
    x, y = layers.data("x", [6, 8]), layers.data("y", [1])
    h = layers.short_conv(x, kernel_size=3)
    loss = _loss(layers.reduce_mean(h, dim=1), y)
    fluid.optimizer.SGD(0.1).minimize(loss)
    return loss, {"x": RNG.randn(4, 6, 8).astype("f4"), "y": Y}


CASES = {
    "backward_and_optimizer": (_dense, {"plan_kept", "forward", "transpose", "update"}),
    "sparse_table": (_sparse, {"plan_kept", "sparse_probe", "forward", "transpose", "update"}),
    "recomputed_segment": (_segment, {"plan_kept", "forward", "transpose", "update"}),
    "repeat_body": (_repeat, {"plan_kept", "forward", "transpose", "update"}),
    "no_backward": (lambda: _dense(main_only=True), {"plan_kept", "forward"}),
    "custom_vjp": (_custom_rule, {"plan_kept", "forward", "transpose", "update"}),
}


def _run(build):
    """One step of the program `build` makes; (the main program, its step)."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        loss, feed = build()
    scope, exe = fluid.Scope(), fluid.Executor(fluid.TPUPlace(0))
    exe.run(startup, scope=scope)
    exe.run(main, feed=feed, fetch_list=[loss], scope=scope)
    return main, next(step for step in exe._cache.values() if step.module.split("_")[0] != "startup")


def _events():
    keys = ("name", "ts", "dur", "tid", "depth", "args", "id", "parent")
    return [dict(zip(keys, e)) for e in MONITOR.events()]


def _lowering_of(main):
    """(the `executor.lower` event of `main`, {phase: its events under it}, every event by id)."""
    events = _events()
    [lower] = [e for e in events if e["name"] == "executor.lower" and e["args"]["program"] == main._uuid[:8]]
    by_id = {e["id"]: e for e in events}
    phases = {}
    for e in events:
        above = e
        while above["parent"] in by_id and above["id"] != lower["id"]:
            above = by_id[above["parent"]]
        if e["name"].startswith("lowering.") and above["id"] == lower["id"]:
            phases.setdefault(e["name"][len("lowering."):], []).append(e)
    return lower, phases, by_id


@pytest.mark.parametrize("case", CASES)
def test_the_phases_are_children_of_the_executors_span_and_carry_its_program(case):
    build, expected = CASES[case]
    monitor.enable()
    main, step = _run(build)
    lower, phases, by_id = _lowering_of(main)
    assert set(phases) == expected | {"trace", "to_hlo"}
    for name, found in phases.items():
        for e in found:
            assert e["args"]["program"] == lower["args"]["program"] == main._uuid[:8]
            assert e["args"]["module"] == lower["args"]["module"] == step.module
            above = "executor.lower" if name in ("trace", "to_hlo") else "lowering.trace"
            assert by_id[e["parent"]]["name"] == above
            assert len(found) == 1
    # the trace and its way to StableHLO are the executor's span but for the glue round them
    inside = phases["trace"][0]["dur"] + phases["to_hlo"][0]["dur"]
    assert inside <= lower["dur"] and lower["dur"] - inside < 0.05
    assert sum(phases[n][0]["dur"] for n in expected) <= phases["trace"][0]["dur"]
    size = phases["to_hlo"][0]["args"]
    assert size["jaxpr_eqns"] > 0 and size["pallas_calls"] == 0


@pytest.mark.parametrize("case", CASES)
def test_the_tables_seconds_are_self_time_and_no_more_than_their_phase(case):
    build, expected = CASES[case]
    monitor.enable()
    main, _ = _run(build)
    lower, phases, _ = _lowering_of(main)
    table = lower["args"]["by_op"]
    other = table.pop("other")                     # what the twelve dearest rows leave: a sparse program's has some
    assert len(table) <= lowering.BY_OP_ROWS and (other == [0, 0] or len(table) == lowering.BY_OP_ROWS)
    assert table and all(seconds >= 0 and calls > 0 for seconds, calls in table.values())
    by_phase = {}
    for key, (seconds, _) in table.items():
        at, op_type = key.split(":")
        assert at in FIVE and op_type
        by_phase[at] = by_phase.get(at, 0.0) + seconds
    for at, seconds in by_phase.items():
        [span] = phases[at]
        assert seconds <= span["args"]["ops_s"] + 1e-9 and span["args"]["ops_s"] <= span["dur"]
    # the table and `other` hold what the phases' rows held, no more
    held = sum(phases[at][0]["args"]["ops_s"] for at in expected)
    assert sum(by_phase.values()) + other[0] == pytest.approx(held)
    # every op of the block is lowered once in the forward (and once more by a sparse table's probe)
    forward = sum(calls for key, (_, calls) in table.items() if key.startswith("forward:"))
    assert forward >= lower["args"]["ops_total"] / (3 if "sparse_probe" in expected else 2)


def test_an_op_lowered_inside_a_repeat_or_a_segment_is_counted_once():
    monitor.enable()
    main, _ = _run(_repeat)
    table = _lowering_of(main)[0]["args"]["by_op"]
    # the body is traced ONCE whatever the passes; its ops have rows of their own beside the loop's
    assert table["forward:repeat"][1] == 1
    body = main.blocks[1].ops
    assert table["forward:tanh"][1] == sum(op.type == "tanh" for op in body) == 1
    assert table["forward:mul"][1] == 1 + 1     # the body's product and the head's

    monitor.reset()
    main, _ = _run(_segment)
    lower, phases, _ = _lowering_of(main)
    table = lower["args"]["by_op"]
    assert table["forward:" + lowering.SEGMENT][1] == 1
    assert table["forward:mul"][1] == 4          # two in the segment, two outside: each once
    assert sum(seconds for key, (seconds, _) in table.items() if key.startswith("forward:")) <= phases["forward"][0]["dur"]


def test_self_time_takes_the_inner_calls_out_of_the_outer_row():
    clock = iter([0.0, 1.0, 4.0, 10.0])
    profile = lowering.TraceProfile(program="p")
    profile.phases.append("forward")
    real, lowering.time.perf_counter = lowering.time.perf_counter, lambda: next(clock)
    try:
        with profile.timed("repeat"):            # 0 .. 10
            with profile.timed("mul"):           # 1 .. 4
                pass
    finally:
        lowering.time.perf_counter = real
    assert profile.rows == {("forward", "repeat"): [7.0, 1], ("forward", "mul"): [3.0, 1]}
    assert profile.seconds_in("forward") == 10.0 and profile.seconds_in("update") == 0
    profile.rows.update({("update", f"op{i}"): [float(i), 1] for i in range(1, 14)})
    table = profile.by_op()
    assert len(table) == lowering.BY_OP_ROWS + 1 and list(table)[0] == "update:op13"
    assert table["other"] == [1.0 + 2.0 + 3.0, 3] and "forward:repeat" in table


def test_a_custom_vjps_backward_rule_is_counted_under_its_op_in_the_transpose():
    monitor.enable()
    main, _ = _run(_custom_rule)
    lower, phases, _ = _lowering_of(main)
    table = lower["args"]["by_op"]
    seconds, calls = table["transpose:short_conv"]
    assert calls == 1 and 0 < seconds <= phases["transpose"][0]["dur"]
    # the op's lowering and, inside it, the forward rule JAX calls to differentiate it: one row
    assert table["forward:short_conv"][1] == 2


def test_with_the_monitor_off_no_table_is_built_and_the_spans_are_the_null_span():
    with lowering.profiled(program="p", module="m") as profile:
        assert profile is None and lowering.open_profile() is None
        with lowering.phase("forward") as span:
            assert span is NULL_SPAN
    main, step = _run(_dense)
    assert MONITOR.events() == [] and lowering.open_profile() is None
    monitor.enable()
    with lowering.profiled(program="p", module="m") as profile:
        assert lowering.open_profile() is profile
    assert lowering.open_profile() is None


@pytest.mark.parametrize("case", ["backward_and_optimizer", "recomputed_segment", "repeat_body", "custom_vjp"])
def test_the_steps_compiled_text_is_the_same_with_the_monitor_on_and_off(case):
    build, _ = CASES[case]
    texts = []
    for on in (False, True):
        monitor.enable() if on else monitor.disable()
        _, step = _run(build)
        # the program, not where a line of Python stands: the monitor's branch of `counted_rules`' wrapper is another line
        texts.append(re.sub(r" line=\d+ end_line=\d+ column=\d+ end_column=\d+", "", step._exec.as_text()))
    assert texts[0] == texts[1]


def test_every_lowering_counter_that_moved_rides_on_the_executors_span():
    monitor.enable()
    main, _ = _run(_segment)
    args = _lowering_of(main)[0]["args"]
    assert args["fenced"] == 8 and "fenced_grads" not in args      # four products and their biases
    assert args["ops_total"] > 0 and args["recomputed_segments"] == 1
    # a counter that did not move is left out, as before
    assert "attention_flash" not in args and "moe_layers" not in args
