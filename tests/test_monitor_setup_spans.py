"""What set-up reports into the monitor (PR 36): the front end's `program.*`
spans, the executor's `executor.prepare`, the JAX compiles the monitor
observes, and the stamp `enable()` leaves.  With the monitor off none of them
records anything or allocates a span."""
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import monitor
from paddle_tpu.monitor import MONITOR, NULL_SPAN
from paddle_tpu.monitor.core import JAX_DURATIONS

FEED = {"x": np.ones((4, 8), "f4"), "y": np.ones((4, 1), "f4")}


@pytest.fixture(autouse=True)
def _clean_monitor():
    monitor.disable()
    monitor.reset()
    yield
    monitor.disable()
    monitor.reset()


def _model(width=16):
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data("x", [8], dtype="float32")
        y = fluid.layers.data("y", [1], dtype="float32")
        h = fluid.layers.fc(x, width, act="relu")
        pred = fluid.layers.fc(h, 1)
        loss = fluid.layers.mean(fluid.layers.square_error_cost(pred, y))
        fluid.optimizer.SGD(0.1).minimize(loss)
    return main, startup, loss


def _events(name=None):
    """The monitor's events as dicts, by name if one is given."""
    keys = ("name", "ts", "dur", "tid", "depth", "args", "id", "parent")
    found = [dict(zip(keys, e)) for e in MONITOR.events()]
    return [e for e in found if e["name"] == name] if name else found


def _fresh_jit(scale):
    """A function no earlier test has compiled: the constant is in its HLO."""
    return jax.jit(lambda a: a * scale + 1.0)


def test_building_a_program_leaves_the_front_ends_spans_with_their_parents():
    monitor.enable()
    main, _, _ = _model()
    clone = main.clone(for_test=True)
    [build], [optimize] = _events("program.build"), _events("program.optimize")
    [backward], [cloned] = _events("program.backward"), _events("program.clone")
    assert build["parent"] == 0 and cloned["parent"] == 0
    assert optimize["parent"] == build["id"]
    assert backward["parent"] == optimize["id"]
    u8 = main._uuid[:8]
    assert build["args"]["program"] == optimize["args"]["program"] == u8
    assert backward["args"]["program"] == u8
    # every op of the program was appended under the guard
    assert build["args"]["ops"] == sum(len(b.ops) for b in main.blocks) > 0
    assert cloned["args"] == {"source": u8, "for_test": True,
                              "program": clone._uuid[:8]}
    assert clone._uuid != main._uuid


def test_a_nested_guard_is_a_child_and_append_backward_alone_is_a_root():
    monitor.enable()
    main, startup, inner = fluid.Program(), fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data("x", [8], dtype="float32")
        with fluid.program_guard(inner):
            fluid.layers.data("z", [2], dtype="float32")
        loss = fluid.layers.mean(fluid.layers.fc(x, 1))
    fluid.append_backward(loss)
    outer, nested = sorted(_events("program.build"), key=lambda e: e["depth"])
    assert nested["parent"] == outer["id"]
    assert nested["args"]["program"] == inner._uuid[:8]
    assert outer["args"]["program"] == main._uuid[:8]
    [backward] = _events("program.backward")
    assert backward["parent"] == 0


def test_with_the_monitor_off_set_up_records_nothing_and_allocates_no_span():
    assert not monitor.is_enabled()
    assert monitor.span("program.build") is NULL_SPAN
    hits0 = MONITOR.jax_cache_hits()  # this thread's, never reset
    main, startup, loss = _model()
    main.clone(for_test=True)
    exe, scope = fluid.Executor(fluid.CPUPlace()), fluid.Scope()
    exe.run(startup, scope=scope)
    exe.run(main, feed=FEED, fetch_list=[loss], scope=scope)
    _fresh_jit(7.25)(jnp.ones(3)).block_until_ready()
    assert MONITOR.events() == [] and MONITOR.span_stats() == {}
    assert MONITOR.jax_cache_hits() == hits0
    assert MONITOR.enabled_at is None
    # the listeners that an earlier enable() left behind stay silent too
    monitor.enable()
    monitor.disable()
    monitor.reset()
    _fresh_jit(7.5)(jnp.ones(3)).block_until_ready()
    MONITOR._on_jax_event("/jax/compilation_cache/cache_hits")
    assert MONITOR.events() == [] and MONITOR.jax_cache_hits() == hits0


def test_enable_stamps_both_clocks_once():
    before = (time.time(), time.perf_counter())
    monitor.enable()
    stamp = MONITOR.enabled_at
    after = (time.time(), time.perf_counter())
    assert before[0] <= stamp[0] <= after[0] and before[1] <= stamp[1] <= after[1]
    monitor.disable()
    monitor.enable()
    assert MONITOR.enabled_at == stamp, "a second enable() keeps the first stamp"
    monitor.reset()  # a reset with the monitor on starts a new run: a new stamp
    assert MONITOR.enabled_at[1] >= after[1]
    monitor.disable()
    monitor.reset()
    assert MONITOR.enabled_at is None


def test_a_compile_outside_the_executor_is_observed_with_no_parent():
    monitor.enable()
    t0 = time.time()
    _fresh_jit(3.125)(jnp.ones(5)).block_until_ready()
    t1 = time.time()
    mine = [e for e in _events()
            if e["name"].startswith("jax.") and "lambda" in str(e["args"])]
    kinds = {e["name"] for e in mine}
    assert {"jax.trace", "jax.lower", "jax.backend_compile"} <= kinds
    for e in mine:
        assert e["parent"] == 0 and e["depth"] == 0
        # back-dated: the event lies where the work ran
        assert t0 <= e["ts"] and e["ts"] + e["dur"] <= t1 + 1e-3
    # under a span of the caller's the same compile is that span's child
    with monitor.span("caller") as caller:
        _fresh_jit(3.375)(jnp.ones(5)).block_until_ready()
    compiles = [e for e in _events("jax.backend_compile") if e["parent"] == caller.id]
    assert len(compiles) == 1


def test_the_executors_compile_is_a_child_of_executor_compile():
    main, startup, loss = _model(width=24)
    exe, scope = fluid.Executor(fluid.CPUPlace()), fluid.Scope()
    monitor.enable()
    exe.run(startup, scope=scope)
    exe.run(main, feed=FEED, fetch_list=[loss], scope=scope)
    by_id = {e["id"]: e for e in _events()}
    compiles = _events("executor.compile")
    assert len(compiles) == 2  # the start-up program and the step
    for span in compiles:
        inside = [e for e in _events("jax.backend_compile") if e["parent"] == span["id"]]
        assert len(inside) == 1
        assert span["ts"] <= inside[0]["ts"]
        assert inside[0]["dur"] <= span["dur"]
    for span in _events("executor.lower"):
        # the trace and its way to StableHLO are spans of their own since PR 52; what JAX reports lies under them
        below = {e["name"]: e for e in _events() if e["parent"] == span["id"]}
        assert set(below) == {"lowering.trace", "lowering.to_hlo"}
        assert {e["name"] for e in _events() if e["parent"] == below["lowering.trace"]["id"]} >= {"jax.trace"}
        # (a function JAX traces only when it lowers it reports a `jax.trace` there too)
        assert {e["name"] for e in _events() if e["parent"] == below["lowering.to_hlo"]["id"]} >= {"jax.lower"}
    # the miss path is one span, parent of the three that were there
    prepares = _events("executor.prepare")
    assert [p["args"]["program"] for p in prepares] == [startup._uuid[:8], main._uuid[:8]]
    for name in ("analysis.verify", "analysis.plan", "executor.build"):
        assert [by_id[e["parent"]]["name"] for e in _events(name)] == ["executor.prepare"] * 2
    # a warm call opens none
    exe.run(main, feed=FEED, fetch_list=[loss], scope=scope)
    assert len(_events("executor.prepare")) == 2


def test_cache_hit_and_miss_still_count_one_a_compile():
    main, startup, loss = _model(width=40)
    exe, scope = fluid.Executor(fluid.CPUPlace()), fluid.Scope()
    monitor.enable()
    exe.run(startup, scope=scope)
    exe.run(main, feed=FEED, fetch_list=[loss], scope=scope)
    counters = MONITOR.counter_values()
    hits = counters.get("executor.compile_cache_hit", 0)
    misses = counters.get("executor.compile_cache_miss", 0)
    assert hits + misses == counters["executor.recompile"] == 2
    assert sum(bool(e["args"]["cache_hit"]) for e in _events("executor.compile")) == hits
    assert "executor.feed_bytes" not in counters
    assert monitor.step_records()[-1]["feed_bytes"] == sum(v.nbytes for v in FEED.values())


def test_a_cache_load_is_the_child_of_the_backend_compile_that_wraps_it():
    """The persistent cache's retrieval fires BEFORE the backend-compile
    event that encloses it: the listener draws the compile's id early."""
    monitor.enable()
    hits0 = MONITOR.jax_cache_hits()
    jax_event = {v: k for k, v in JAX_DURATIONS.items()}
    load, compile_ = jax_event["jax.cache_load"], jax_event["jax.backend_compile"]
    with monitor.span("executor.compile") as span:
        time.sleep(0.02)
        MONITOR._on_jax_event("/jax/compilation_cache/cache_hits")
        MONITOR._on_jax_duration(load, 0.01)
        MONITOR._on_jax_duration(compile_, 0.02, fun_name="jit(step)")
    [loaded], [compiled] = _events("jax.cache_load"), _events("jax.backend_compile")
    assert loaded["parent"] == compiled["id"] and compiled["parent"] == span.id
    assert loaded["depth"] == compiled["depth"] + 1
    assert MONITOR.jax_cache_hits() == hits0 + 1
    # a compile that no load preceded takes an id of its own
    time.sleep(0.03)
    MONITOR._on_jax_duration(compile_, 0.02, fun_name="jit(other)")
    later = _events("jax.backend_compile")[-1]
    assert later["id"] != compiled["id"] and later["parent"] == 0
    assert all(e["parent"] != later["id"] for e in _events("jax.cache_load"))
    # an event the monitor has no name for is dropped
    n = len(MONITOR.events())
    MONITOR._on_jax_duration("/jax/some/other_duration", 1.0)
    assert len(MONITOR.events()) == n
