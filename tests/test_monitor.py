"""Monitor subsystem: spans, counters/gauges, executor step breakdown,
exporters, the profiler facade, and the perf_report CLI gate."""
import json
import os
import subprocess
import sys
import threading

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import monitor
from paddle_tpu.monitor import MONITOR, MonitorLogger, NULL_SPAN


@pytest.fixture(autouse=True)
def _clean_monitor():
    monitor.disable()
    monitor.reset()
    yield
    monitor.disable()
    monitor.reset()


def _model():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data("x", [8], dtype="float32")
        y = fluid.layers.data("y", [1], dtype="float32")
        h = fluid.layers.fc(x, 16, act="relu")
        pred = fluid.layers.fc(h, 1)
        loss = fluid.layers.mean(fluid.layers.square_error_cost(pred, y))
        fluid.optimizer.SGD(0.1).minimize(loss)
    return main, startup, loss


FEED = {"x": np.ones((4, 8), "f4"), "y": np.ones((4, 1), "f4")}


# --- core: spans ------------------------------------------------------------

def test_span_nesting_and_aggregates():
    monitor.enable()
    with monitor.span("outer"):
        with monitor.span("inner", tag="a"):
            pass
        with monitor.span("inner", tag="b"):
            pass
    stats = MONITOR.span_stats()
    assert stats["outer"]["calls"] == 1
    assert stats["inner"]["calls"] == 2
    assert stats["outer"]["total_s"] >= stats["inner"]["total_s"]
    # nesting depth landed in the event buffer (inner below outer)
    events = MONITOR.events()
    depths = {e[0]: e[4] for e in events}
    assert depths["outer"] == 0 and depths["inner"] == 1
    # every span has an id of its own and names its parent's (0: none)
    ids = {e[0]: e[6] for e in events}
    parents = {e[6]: e[7] for e in events}
    assert len(parents) == 3 and parents[ids["outer"]] == 0
    assert [e[7] for e in events if e[0] == "inner"] == [ids["outer"]] * 2


def test_disabled_mode_is_allocation_free():
    assert not monitor.is_enabled()
    # span() returns the one shared null singleton: nothing allocated
    assert monitor.span("a") is NULL_SPAN
    assert monitor.span("a") is monitor.span("b")
    with monitor.span("x", program="p"):
        pass
    monitor.counter("c").inc(5)
    monitor.gauge("g").set(3.0)
    assert MONITOR.span_stats() == {}
    assert MONITOR.events() == []
    assert monitor.counter("c").value == 0
    assert monitor.gauge("g").value == 0.0


def test_spans_threadsafe():
    monitor.enable()

    def work():
        for _ in range(50):
            with monitor.span("t"):
                monitor.counter("n").inc()

    ts = [threading.Thread(target=work) for _ in range(4)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert MONITOR.span_stats()["t"]["calls"] == 200
    assert monitor.counter("n").value == 200


# --- exporters: round trips -------------------------------------------------

def test_prometheus_and_json_round_trip(tmp_path):
    monitor.enable()
    monitor.counter("executor.cache_miss").inc(3)
    monitor.gauge("reader.queue_depth").set(7)
    with monitor.span("compile", program="abcd"):
        pass
    text = monitor.export_prometheus()
    assert "# TYPE paddle_tpu_executor_cache_miss counter" in text
    assert "paddle_tpu_executor_cache_miss 3" in text
    assert "paddle_tpu_reader_queue_depth 7" in text
    assert "paddle_tpu_compile_seconds_count 1" in text
    # every sample line parses as "name value"
    for ln in text.splitlines():
        if ln and not ln.startswith("#"):
            name, val = ln.rsplit(" ", 1)
            assert name.startswith("paddle_tpu_")
            float(val)  # NaN parses too

    p = tmp_path / "snap.json"
    monitor.export_json(str(p))
    snap = json.load(open(p))
    assert snap["counters"]["executor.cache_miss"] == 3
    assert snap["gauges"]["reader.queue_depth"] == 7
    assert snap["spans"]["compile"]["calls"] == 1
    assert "memory.live_array_bytes" in snap["gauges"]


def test_prometheus_hostile_names_golden():
    """Exporter hardening (ISSUE 8): hostile metric names sanitize to the
    exposition grammar, label values escape, TYPE lines never repeat, and
    sanitization collisions disambiguate with a raw= label instead of
    emitting an invalid duplicate series."""
    import re

    monitor.enable()
    monitor.counter("analysis.verify").inc(4)
    monitor.counter('hostile "name"\n{x}').inc(1)
    monitor.counter("a.b").inc(2)
    monitor.counter("a_b").inc(3)          # collides with a.b post-sanitize
    monitor.gauge("0starts.with digit").set(1.5)
    with monitor.span('span "quoted"\nname'):
        pass
    text = monitor.export_prometheus(
        labels={"rank": 0, 'bad"key': 'v"\n\\', "0zone": "a"})

    name_re = re.compile(r"^[a-zA-Z_:][a-zA-Z0-9_:]*$")
    seen_types = set()
    for ln in text.splitlines():
        if not ln:
            continue
        if ln.startswith("# TYPE "):
            fam = ln.split()[2]
            assert name_re.match(fam), ln
            assert fam not in seen_types, f"duplicate TYPE: {ln}"
            seen_types.add(fam)
            continue
        # every sample: name{labels} value, name legal, labels escaped,
        # label KEYS legal too (leading digit gets a _ prefix)
        name = ln.split("{")[0].split(" ")[0]
        assert name_re.match(name), ln
        assert "\n" not in ln
        if "{" in ln:
            for kv in ln[ln.index("{") + 1:ln.rindex("}")].split('",'):
                key = kv.split("=")[0]
                assert re.match(r"^[a-zA-Z_][a-zA-Z0-9_]*$", key), ln
        val = ln.rsplit(" ", 1)[1]
        float(val)  # parses (NaN included)
    assert "paddle_tpu_analysis_verify" in text
    assert "paddle_tpu_hostile__name___x_" in text
    assert "paddle_tpu_0starts_with_digit" in text  # prefix keeps it legal
    # escaped label values: backslash, quote, newline per the format
    assert 'bad_key="v\\"\\n\\\\"' in text
    # digit-leading label key gets a _ prefix (no PROM_PREFIX on labels)
    assert '_0zone="a"' in text and "{0zone" not in text
    # collision: one family, second series disambiguated by raw label
    assert text.count("# TYPE paddle_tpu_a_b counter") == 1
    assert ('paddle_tpu_a_b{_0zone="a",bad_key="v\\"\\n\\\\",rank="0"} 2'
            in text)
    assert ',raw="a_b"} 3' in text
    # hostile span name: the summary family is sanitized too
    assert "# TYPE paddle_tpu_span__quoted__name_seconds summary" in text


def test_monitor_logger_jsonl(tmp_path):
    monitor.enable()
    path = str(tmp_path / "metrics.jsonl")
    lg = monitor.attach_logger(MonitorLogger(path))
    try:
        MONITOR.record_step({"t_total_s": 0.1})
        MONITOR.record_step({"t_total_s": 0.2})
        lg.write_snapshot()
    finally:
        monitor.detach_logger(lg)
    lines = [json.loads(ln) for ln in open(path)]
    kinds = [ln["kind"] for ln in lines]
    assert kinds == ["step", "step", "snapshot"]
    assert lines[1]["step"] == 1


# --- the executor step breakdown (ISSUE acceptance criterion) ---------------

def test_executor_step_breakdown_and_disabled_fast_path():
    main, startup, loss = _model()
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    exe.run(startup, scope=scope)

    monitor.enable()
    exe.run(main, feed=FEED, fetch_list=[loss], scope=scope)
    recs = monitor.step_records()
    # startup run happened before enable(): exactly one record, a cold one
    [rec] = [r for r in recs if r["program"] == main._uuid[:8]]
    assert rec["cache_hit"] is False and rec["recompiled"] is True
    # distinct per-phase timings, all really measured
    assert rec["t_lower_s"] > 0 and rec["t_compile_s"] > 0
    assert rec["t_execute_s"] > 0 and rec["t_fetch_s"] >= 0
    assert rec["t_total_s"] >= rec["t_execute_s"]
    # cache-hit + recompile counters present and coherent
    assert rec["cache_misses_total"] == 1
    assert rec["recompiles_total"] == 1
    # the phases also landed as named spans with per-program attribution
    stats = MONITOR.span_stats()
    for name in ("executor.lower", "executor.compile", "executor.execute",
                 "executor.fetch", "executor.build"):
        assert stats[name]["calls"] >= 1, name
    # the op census from core/lowering.py (trace-time, one counter)
    assert monitor.counter("lowering.ops_total").value > 0
    assert not any(n.startswith("lowering.op.") for n in MONITOR.counter_values())

    # warm second run: cache hit, no recompile, still a full record
    exe.run(main, feed=FEED, fetch_list=[loss], scope=scope)
    rec2 = monitor.step_records()[-1]
    assert rec2["cache_hit"] is True and rec2["recompiled"] is False
    assert rec2["t_lower_s"] == 0.0 and rec2["t_compile_s"] == 0.0
    assert rec2["recompiles_total"] == 1  # flat — steady state

    # disabled: the fast path records nothing and allocates no spans
    monitor.disable()
    n_events = len(MONITOR.events())
    n_steps = len(monitor.step_records())
    assert monitor.span("executor.run") is NULL_SPAN
    exe.run(main, feed=FEED, fetch_list=[loss], scope=scope)
    assert len(MONITOR.events()) == n_events
    assert len(monitor.step_records()) == n_steps


def test_recompile_counter_fires_on_shape_change():
    main, startup, loss = _model()
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    exe.run(startup, scope=scope)
    monitor.enable()
    exe.run(main, feed=FEED, fetch_list=[loss], scope=scope)
    base = monitor.counter("executor.recompile").value
    # new batch size -> new executor cache entry -> fresh XLA compile
    feed2 = {"x": np.ones((8, 8), "f4"), "y": np.ones((8, 1), "f4")}
    exe.run(main, feed=feed2, fetch_list=[loss], scope=scope)
    assert monitor.counter("executor.recompile").value == base + 1
    rec = monitor.step_records()[-1]
    assert rec["cache_hit"] is False and rec["recompiled"] is True


# --- facade + trace export --------------------------------------------------

def test_chrome_trace_via_facade(tmp_path):
    from paddle_tpu import profiler

    main, startup, loss = _model()
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    exe.run(startup, scope=scope)
    profiler.start_profiler()
    exe.run(main, feed=FEED, fetch_list=[loss], scope=scope)
    profiler.stop_profiler(profile_path=str(tmp_path / "tbl.txt"))
    trace = str(tmp_path / "trace.json")
    n = profiler.export_chrome_trace(trace)
    assert n > 0
    doc = json.load(open(trace))
    names = {e["name"] for e in doc["traceEvents"]}
    assert "executor.execute" in names
    # one span name for every program: the program is an argument
    assert "executor.run" in names
    assert not any(name.startswith("executor.run[") for name in names)
    # valid trace JSON: X events carry ts+dur, metadata row present
    assert all("ts" in e and "dur" in e
               for e in doc["traceEvents"] if e.get("ph") == "X")
    assert any(e.get("ph") == "M" for e in doc["traceEvents"])
    # table written by stop_profiler
    assert "executor.run" in open(tmp_path / "tbl.txt").read()


def test_reader_metrics():
    monitor.enable()
    x = fluid.layers.data("x", [4], dtype="float32")
    loader = fluid.DataLoader([x], capacity=2)
    loader.set_batch_generator(
        lambda: iter([{"x": np.ones((2, 4), "f4")} for _ in range(3)]))
    batches = list(loader)
    assert len(batches) == 3
    assert monitor.counter("reader.batches").value == 3
    assert monitor.counter("reader.bytes_staged").value == 3 * 2 * 4 * 4
    # 3 batch waits + the END-sentinel wait
    assert MONITOR.span_stats()["reader.wait"]["calls"] == 4


# --- perf_report CLI --------------------------------------------------------

def _run_perf_report(*args):
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    return subprocess.run(
        [sys.executable, os.path.join(root, "tools", "perf_report.py"), *args],
        capture_output=True, text=True,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})


def test_perf_report_render_and_check(tmp_path):
    monitor.enable()
    main, startup, loss = _model()
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    exe.run(startup, scope=scope)
    path = str(tmp_path / "metrics.jsonl")
    lg = monitor.attach_logger(MonitorLogger(path))
    try:
        for _ in range(4):
            exe.run(main, feed=FEED, fetch_list=[loss], scope=scope)
    finally:
        monitor.detach_logger(lg)
    snap = str(tmp_path / "snap.json")
    monitor.export_json(snap)

    r = _run_perf_report(snap)
    assert r.returncode == 0, r.stderr
    assert "step breakdown" in r.stdout and "executor.execute" in r.stdout

    r = _run_perf_report("--diff", snap, snap)
    assert r.returncode == 0, r.stderr

    # healthy steady state: recompile count flat
    r = _run_perf_report("--check", path)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "flat" in r.stdout

    # corrupt the steady state: a rising recompile count must fail the gate
    with open(path, "a") as f:
        f.write(json.dumps({"kind": "step", "recompiles_total": 99}) + "\n")
    r = _run_perf_report("--check", path)
    assert r.returncode == 1
    assert "recompile count moved" in r.stdout

    r = _run_perf_report("--check", str(tmp_path / "missing.jsonl"))
    assert r.returncode == 1


def test_perf_report_reads_streams_and_no_bench_round():
    """The tool gates a run's metrics stream and nothing else: speed is the
    benchmark's to state (`python3 -m benchmark.run`, PERF.md, the ledger),
    so a bench-round gate is refused, and every gate that
    tests/test_gate_zero_evidence.py audits is an option of the CLI."""
    from test_gate_zero_evidence import GATES

    r = _run_perf_report("--check-bench", "round.json")
    assert r.returncode == 2 and "unrecognized arguments" in r.stderr
    r = _run_perf_report("--help")
    assert r.returncode == 0, r.stderr
    for name, *_ in GATES:
        assert "--" + name.replace("_", "-") in r.stdout, name
    for gone in ("--max-spread-pct", "--min-roofline-frac",
                 "--require-overlap"):
        assert gone not in r.stdout, gone
