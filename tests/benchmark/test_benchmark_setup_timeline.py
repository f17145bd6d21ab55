"""benchmark/setup_timeline.py: the one partition of set-up on monitor events
built by hand, the five per-layer metrics that read it in the manifest, and one
train cell rehearsed tiny on the CPU with the five on its line."""
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmark import manifest as mf, program_trace as pt, setup_timeline as st  # noqa: E402
from tests.benchmark.test_benchmark_rehearsal import check_line, run_cell, tiny_root  # noqa: E402,F401

LOOP, PRODUCER = 11, 22
METRICS = {
    "setup_program_build_s": ("s", "front end: program construction "
                              "(core/program.py, core/autodiff.py, optimizer.py)"),
    "setup_lower_s": ("s", "executor (core/executor.py)"),
    "setup_run_s": ("s", "executor (core/executor.py)"),
    "setup_foreign_compile_s": ("s", "executor (core/executor.py)"),
    "setup_unattributed_share": ("%", "executor (core/executor.py)"),
}
# the process started at 100 s on the events' clock (perf_counter 40) and the
# monitor was switched on 3 s later
T_PROCESS, ENABLED_AT = 40.0, (103.0, 43.0)


def event(name, start, dur, sid, parent=0, tid=LOOP, **args):
    """One event as the program's monitor keeps it."""
    return (name, float(start), float(dur), tid, 0, args or None, sid, parent)


def a_small_set_up():
    """Process start at 100, the window's first pull at 150."""
    return [
        # 103-105: nothing; 105-109 the model is built
        event("program.build", 105, 4, 1, program="aaaa"),
        event("program.optimize", 106, 2, 2, parent=1, program="aaaa"),
        event("program.backward", 106.5, 1, 3, parent=2, program="aaaa"),
        # 110-121 the start-up program: prepare, then a run that lowers,
        # compiles (a cache load inside the backend's compile) and fetches
        event("executor.prepare", 110, 1, 4, program="bbbb"),
        event("executor.build", 110.25, 0.5, 5, parent=4, program="bbbb"),
        event("executor.run", 111, 10, 6, program="bbbb"),
        event("executor.execute", 111, 9, 7, parent=6, program="bbbb"),
        event("executor.enqueue", 111.5, 8, 8, parent=7, program="bbbb"),
        event("executor.lower", 112, 2, 9, parent=8, program="bbbb"),
        event("jax.trace", 112, 1.5, 10, parent=9, fun_name="startup"),
        event("jax.trace", 112.5, 0.25, 11, parent=9, fun_name="add"),
        event("jax.lower", 113.5, 0.5, 12, parent=9, fun_name="jit(startup)"),
        event("executor.compile", 114, 4, 13, parent=8, program="bbbb"),
        event("jax.backend_compile", 114.5, 3, 15, parent=13, fun_name="jit(startup)"),
        event("jax.cache_load", 115, 2, 14, parent=15),
        event("executor.fetch", 120, 1, 16, parent=6, program="bbbb"),
        # an eager jnp call under the fetch: foreign, wherever it fell
        event("jax.backend_compile", 120.25, 0.5, 17, parent=16, fun_name="jit(convert)"),
        # 121-123 the clone; 123-133 a gap with the caller's own jit in it
        event("program.clone", 121, 2, 18, program="cccc", source="aaaa", for_test=True),
        event("jax.trace", 124, 1, 19, fun_name="reference"),
        event("jax.lower", 125, 1, 20, fun_name="jit(reference)"),
        event("jax.backend_compile", 126, 4, 21, fun_name="jit(reference)"),
        # 133-149 two warm-up steps; the producer's thread is nobody's here
        event("pipeline.next_batch", 133, 1, 22, step=0),
        event("pipeline.dispatch", 134, 6, 23, step=0),
        event("reader.stage", 134, 20, 24, tid=PRODUCER, batch=1),
        event("pipeline.next_batch", 140, 1, 25, step=1),
        event("pipeline.dispatch", 141, 8, 26, step=1),
        # the window opens at 150 with step 2's pull
        event("pipeline.next_batch", 150, 1, 27, step=2),
        event("pipeline.dispatch", 151, 5, 28, step=2),
    ]


def test_the_partition_gives_every_instant_to_one_part():
    found = st.setup_pieces(a_small_set_up(), 2, ENABLED_AT, T_PROCESS)
    pieces, observed, lo, hi = found
    assert (lo, hi) == (100.0, 150.0)
    # in order, touching, covering the interval once
    assert pieces[0][0] == lo and pieces[-1][1] == hi
    assert all(a[1] == b[0] for a, b in zip(pieces, pieces[1:]))
    parts = st.parts_of(pieces)
    assert parts[st.BUILD] == pytest.approx(4 + 2)
    # prepare 1 and lower 2, the traces inside lower included
    assert parts[st.LOWER] == pytest.approx(1 + 2)
    assert parts[st.COMPILE] == pytest.approx(4)
    # run 10 less lower, compile and the foreign half second; the warm-up 16
    assert parts[st.RUN] == pytest.approx(10 - 2 - 4 - 0.5 + 16)
    # the eager call under the fetch and the caller's jit (the load counted
    # inside its compile, not beside it)
    assert parts[st.FOREIGN] == pytest.approx(0.5 + 6)
    # 100-105, 109-110, 123-124, 130-133, 149-150
    assert parts[st.UNATTRIBUTED] == pytest.approx(5 + 1 + 1 + 3 + 1)
    assert sum(parts.values()) == pytest.approx(hi - lo)
    assert {e.name for e in observed} == {"jax.trace", "jax.lower",
                                          "jax.backend_compile", "jax.cache_load"}


def test_the_timeline_lists_root_spans_and_the_gaps_with_what_jax_did_in_them():
    pieces, observed, lo, _ = st.setup_pieces(a_small_set_up(), 2, ENABLED_AT, T_PROCESS)
    rows = st.timeline_of(pieces, observed, lo, ENABLED_AT[0])
    assert [r[0] for r in rows] == [
        "gap:before_enable", "gap", "program.build", "gap", "executor.prepare",
        "executor.run", "program.clone", "gap", "pipeline.next_batch",
        "pipeline.dispatch", "pipeline.next_batch", "pipeline.dispatch", "gap"]
    assert [r[1] for r in rows][:3] == [0.0, 3.0, 5.0]
    assert sum(r[2] for r in rows) == pytest.approx(50.0)
    run = rows[5]
    assert run[3]["program"] == "bbbb"
    assert run[3]["parts"] == pytest.approx({st.RUN: 3.5, st.LOWER: 2, st.COMPILE: 4,
                                            st.FOREIGN: 0.5})
    gap = rows[7]
    assert gap[1:3] == [23.0, 10.0]
    assert gap[3]["seconds"] == pytest.approx({"jax.trace": 1, "jax.lower": 1,
                                              "jax.backend_compile": 4})
    assert gap[3]["longest"][0] == ["jax.backend_compile", "jit(reference)", 4.0]
    assert rows[0][3] == {"seconds": {}, "longest": []}
    assert st.by_program(pieces)["bbbb"] == pytest.approx(
        {st.LOWER: 3, st.COMPILE: 4, st.RUN: 3.5, st.FOREIGN: 0.5})
    inside = st.inside_executor(pieces, observed)
    # the nested trace once; the load inside the compile that wraps it
    assert inside["executor.lower"] == pytest.approx({"jax.trace": 1.5, "jax.lower": 0.5})
    assert inside["executor.compile"] == pytest.approx({"jax.backend_compile": 3,
                                                       "jax.cache_load": 2})


def test_clock_rounding_and_overlapping_children_cannot_count_an_instant_twice():
    events = [
        event("executor.run", 10, 5, 1),
        # a child that overhangs its parent, and a sibling that overlaps it
        event("executor.execute", 9.5, 4, 2, parent=1),
        event("executor.fetch", 13, 3, 3, parent=1),
        # a span whose parent is on another thread is a root here
        event("executor.dispatch", 16, 1, 4, parent=99),
        event("pipeline.next_batch", 20, 1, 5, step=0),
    ]
    pieces, _, lo, hi = st.setup_pieces(events, 0, (8.0, 8.0), 8.0)
    assert sum(e - s for s, e, *_ in pieces) == pytest.approx(hi - lo) == 12
    assert st.parts_of(pieces)[st.RUN] == pytest.approx(5 + 1)


def test_an_event_before_enable_is_placed_against_process_start():
    """The listener may have been on before this run's `enable()` (a reset in
    between): the event still lies where it ran, and what precedes process
    start is cut off."""
    events = [
        event("jax.backend_compile", 99, 2.5, 1, fun_name="jit(early)"),
        event("program.build", 104, 1, 2, program="aaaa"),
        event("pipeline.next_batch", 110, 1, 3, step=0),
    ]
    pieces, observed, lo, hi = st.setup_pieces(events, 0, ENABLED_AT, T_PROCESS)
    assert (lo, hi) == (100.0, 110.0)
    parts = st.parts_of(pieces)
    assert parts[st.FOREIGN] == pytest.approx(1.5)
    assert parts[st.UNATTRIBUTED] == pytest.approx(10 - 1.5 - 1)
    rows = st.timeline_of(pieces, observed, lo, ENABLED_AT[0])
    assert rows[0][0] == "gap:before_enable" and rows[0][2] == pytest.approx(3.0)
    assert rows[0][3]["seconds"] == pytest.approx({"jax.backend_compile": 1.5})


def test_without_the_windows_first_pull_or_the_stamp_there_is_nothing_to_read(monkeypatch):
    assert st.setup_pieces(a_small_set_up(), 7, ENABLED_AT, T_PROCESS) is None

    class Parent:  # the monitor of a program that has no stamp
        def events(self):
            return a_small_set_up()

    monkeypatch.setattr(pt, "program_monitor", lambda: Parent())
    ctx = {"traffic": {"warmup_steps": 2}, "end_to_end": {"setup_s": 50.0}}
    for name in METRICS:
        assert mf.reader_module(name).read(ctx) is None
    Parent.enabled_at = ENABLED_AT
    monkeypatch.setattr(st, "process_start", lambda: T_PROCESS)
    got = {name: mf.reader_module(name).read(ctx) for name in METRICS}
    assert got["setup_program_build_s"] == pytest.approx(6)
    assert got["setup_unattributed_share"] == pytest.approx(100 * 11 / 50)
    # a serve cell's traffic has no warm-up steps: nothing to read there
    assert mf.reader_module("setup_run_s").read({**ctx, "traffic": {}}) is None


def test_the_manifest_has_the_five_metrics_and_their_readers():
    manifest = mf.load()
    assert mf.problems(manifest) == []
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    assert [m["name"] for m in manifest["per_layer"]][-5:] == list(METRICS)
    for name, (unit, layer) in METRICS.items():
        entry = by_name[name]
        assert entry == {"name": name, "unit": unit, "better": "lower",
                         "source": "program_span", "layer": layer, "moves": "setup_s"}
        reader = mf.reader_module(name)
        assert (reader.UNIT, reader.BETTER, reader.SOURCE, reader.LAYER, reader.MOVES) == (
            unit, "lower", "program_span", layer, "setup_s")
    # every cell reports them, as it does `setup_compile_s`
    for cell in manifest["workloads"]:
        mine = {m["name"] for m in mf.metrics_of(manifest, cell["name"], "per_layer")}
        assert set(METRICS) <= mine


def test_a_train_cell_rehearsed_tiny_has_the_five_and_they_add_up(tiny_root, monkeypatch):  # noqa: F811
    seen, real = {}, st.read_metric

    def spy(ctx, name):  # a traced run's line does not carry `setup_s`
        seen["setup_s"] = ctx["end_to_end"]["setup_s"]
        return real(ctx, name)

    monkeypatch.setattr(st, "read_metric", spy)
    cell = "bert-base.pretrain-s128"
    result = run_cell(tiny_root, cell, 1, 2)
    check_line(result, cell, 1)
    got = {name: result["metrics"][name]["value"] for name in METRICS}
    traffic = mf.read_json(mf.traffic_path("pretrain-s128"), tiny_root)
    found = st.report(traffic)
    parts, rows = found["parts"], found["timeline"]
    assert got["setup_program_build_s"] == pytest.approx(parts[st.BUILD]) and parts[st.BUILD] > 0
    assert got["setup_lower_s"] == pytest.approx(parts[st.LOWER]) and parts[st.LOWER] > 0
    assert got["setup_run_s"] == pytest.approx(parts[st.RUN]) and parts[st.RUN] > 0
    # the reference and the probe are jits of the benchmark's own
    assert got["setup_foreign_compile_s"] == pytest.approx(parts[st.FOREIGN])
    assert parts[st.FOREIGN] > 0 and parts[st.COMPILE] > 0
    # `setup_s` is counted from this test process's import of benchmark.run;
    # the timeline's interval ends one pull from the loader earlier
    setup_s = seen["setup_s"]
    assert found["interval_s"] == pytest.approx(setup_s, rel=0.02)
    held = sum(got[n] for n in METRICS if n != "setup_unattributed_share")
    total = held + parts[st.COMPILE] + got["setup_unattributed_share"] * setup_s / 100.0
    assert total == pytest.approx(setup_s, rel=0.02)
    assert sum(parts.values()) == pytest.approx(found["interval_s"])
    names = [r[0] for r in rows]
    for name in ("program.build", "program.clone", "executor.prepare", "executor.run",
                 "pipeline.dispatch", "gap"):
        assert name in names, name
    # the three programs the executor prepared each have a row of their own
    assert len(found["by_program"]) >= 3
