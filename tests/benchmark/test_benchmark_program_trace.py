"""benchmark/program_trace.py on monitor events and profiler planes built by
hand: self time, the window cut by step, the producer thread, idle gaps going
to the innermost span, the step's phases, and a stale trace ignored."""
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmark import manifest as mf, program_trace as pt  # noqa: E402

LOOP, PRODUCER = 11, 22
FUSION = "%fusion.{0} = bf16[8,128]{{1,0}} fusion(bf16[8,128]{{1,0}} %p.1), kind=kLoop, calls=%fused.{0}"


def event(name, start, dur, tid=LOOP, sid=0, parent=0, **args):
    """One event as the program's monitor keeps it."""
    return (name, float(start), float(dur), tid, 0, args or None, sid, parent)


def test_self_time_is_the_duration_less_what_the_children_cover():
    spans = pt.spans_of([
        event("pipeline.dispatch", 0, 10, sid=1, step=3),
        event("executor.dispatch", 1, 8, sid=2, parent=1, step=3),
        event("executor.feed_place", 1, 2, sid=3, parent=2, step=3),
        event("executor.enqueue", 4, 5, sid=4, parent=2, step=3),
        # overlapping children are counted once, one that overhangs is cut
        event("a", 20, 10, sid=5), event("b", 21, 4, sid=6, parent=5),
        event("c", 23, 4, sid=7, parent=5), event("d", 28, 9, sid=8, parent=5),
    ])
    own = pt.self_times(spans)
    assert own[1] == pytest.approx(2) and own[2] == pytest.approx(1)
    assert own[3] == pytest.approx(2) and own[4] == pytest.approx(5)
    assert own[5] == pytest.approx(10 - 6 - 2)


def steps(first, last, step_s=1.0, wait=0.01, dispatch=0.02):
    """A loop that pulls, dispatches and then waits out the rest of each step."""
    out = []
    for i in range(first, last):
        t = i * step_s
        out += [event("pipeline.next_batch", t, wait, step=i),
                event("pipeline.dispatch", t + wait, dispatch, step=i),
                event("pipeline.host_blocked", t + wait + dispatch,
                      step_s - wait - dispatch, step=i - 2)]
    return out


def test_the_window_is_cut_by_step_and_the_producer_by_the_windows_start():
    events = steps(0, 10)
    # warm-up pulls are slow and must not count; nor must a batch staged
    # before the window opened, nor the part of one that outlasts the loop
    events[0] = event("pipeline.next_batch", 0, 0.9, step=0)
    events += [event("reader.stage", 3.5, 0.3, tid=PRODUCER, batch=5, bytes=9),
               event("reader.stage", 4.0, 0.25, tid=PRODUCER, batch=6, bytes=9),
               event("reader.stage", 5.0, 0.25, tid=PRODUCER, batch=7, bytes=9),
               event("reader.stage", 9.9, 0.5, tid=PRODUCER, batch=8, bytes=9)]
    got = pt.loop_metrics(events, first_step=4)
    # the loop's time in the window: 4.0 (step 4's pull) to 10.0 (the last wait)
    assert pt.loop_window(pt.spans_of(events), 4) == (4.0, pytest.approx(10.0))
    assert got["next_batch_wait_share"] == pytest.approx(100 * 6 * 0.01 / 6)
    assert got["dispatch_ms_per_step"] == pytest.approx(20.0)
    assert got["reader_stage_share"] == pytest.approx(100 * (0.25 + 0.25 + 0.1) / 6)
    # a loop fed from memory has no producer thread: the metric is left out
    assert "reader_stage_share" not in pt.loop_metrics(steps(0, 10), 4)


def test_a_program_without_the_spans_reads_as_nothing():
    # the parent's events: six fields, no pipeline.next_batch, no dispatch span
    old = [("pipeline.host_blocked", 1.0, 0.5, LOOP, 0, {"step": 7, "logged": False}),
           ("executor.dispatch", 0.9, 0.01, LOOP, 0, {"program": "ab12cd34"})]
    assert pt.loop_metrics(old, 4) == {}
    assert [(s.id, s.parent) for s in pt.spans_of(old)] == [(0, 0), (0, 0)]
    assert pt.slow_step_share([{"kind": "step", "t_total_s": 1.0}], 4) is None
    assert pt.idle_attribution([("/device:TPU:0", [("XLA Ops", [(FUSION.format(1), 0.0, 5.0, {})])]),
                                ("/host:CPU", [("python3", [("bench.traced_window", 0.0, 10.0, {})])])]) is None
    assert pt.phases_from_hlo_text(
        '  %fusion.2 = f32[8]{0} fusion(%p), kind=kLoop, calls=%fc, '
        'metadata={op_name="jit(step)/transpose(jvp(op12:mul))/dot_general"}') == {}


def test_slow_steps_are_counted_against_the_median_of_the_window():
    def rec(i, wall):
        return {"kind": "pipeline_step", "pipeline_step": i, "t_step_wall_s": wall}

    records = [rec(0, 30.0), rec(1, 0.1)] + [rec(i, 0.1) for i in range(2, 22)]
    records[10] = rec(10, 0.205)          # a step that took two
    records[15] = rec(15, 0.149)          # slower, under 1.5 x the median
    records.append({"kind": "step", "t_total_s": 9.0})
    assert pt.slow_step_share(records, first_step=2) == pytest.approx(100 / 20)
    assert pt.slow_step_share(records, first_step=11) == 0.0


def host(*lines):
    return ("/host:CPU", [(f"python3/{i}", list(events)) for i, events in enumerate(lines)])


def device(n, ops, modules=()):
    return (f"/device:TPU:{n}", [("XLA Ops", [(name, s, d, {}) for name, s, d in ops]),
                                 ("XLA Modules", [(name, s, d, {}) for name, s, d in modules])])


def test_an_idle_gap_goes_to_the_innermost_span_over_it_the_loops_thread_first():
    producer = [("reader.stage", 0.0, 100.0, {"batch": 4})]
    loop = [("bench.traced_window", 0.0, 100.0, {}),
            ("pipeline.next_batch", 0.0, 10.0, {"step": 7}),
            ("reader.wait", 2.0, 6.0, {"step": 7}),
            ("pipeline.dispatch", 12.0, 18.0, {"step": 7}),
            ("executor.dispatch", 14.0, 14.0, {"step": 7}),
            ("executor.enqueue", 20.0, 6.0, {"step": 7}),
            ("pipeline.host_blocked", 40.0, 50.0, {"step": 5}),
            ("executor.fetch", 52.0, 4.0, {"step": 5})]
    # the device is idle 0..5, 10..12, 22..30, 50..60 and 90..100
    ops = [(FUSION.format(1), 5.0, 5.0), (FUSION.format(2), 12.0, 10.0),
           (FUSION.format(3), 30.0, 20.0), (FUSION.format(4), 60.0, 30.0)]
    planes = [host(producer, loop), device(0, ops)]
    lines = pt.host_lines(planes)
    assert [ln[0][0] for ln in lines] == ["pipeline.next_batch", "reader.stage"]
    gaps = pt.idle_gaps(planes[1][1][0][1], (0.0, 100.0))
    assert gaps == [(0.0, 5.0), (10.0, 12.0), (22.0, 30.0), (50.0, 60.0), (90.0, 100.0)]
    by_span = pt.attribute_gaps(gaps, lines)
    assert by_span == {
        "pipeline.next_batch": pytest.approx(2.0),    # 0..2
        "reader.wait": pytest.approx(3.0),            # 2..5, inside next_batch
        # 10..12 and 90..100: only the producer is in a span
        "reader.stage": pytest.approx(12.0),
        "executor.enqueue": pytest.approx(4.0),       # 22..26, innermost of three
        "executor.dispatch": pytest.approx(2.0),      # 26..28
        "pipeline.dispatch": pytest.approx(2.0),      # 28..30
        # 50..60 is the loop's, not the producer's: the fetch of a logged
        # step is the innermost span of its part
        "pipeline.host_blocked": pytest.approx(6.0),
        "executor.fetch": pytest.approx(4.0),
        }
    by_span_without_producer = pt.attribute_gaps(gaps, lines[:1])
    assert by_span_without_producer[pt.UNATTRIBUTED] == pytest.approx(2.0 + 10.0)
    # the window is cut to where the loop's thread has spans, 0..90: the
    # benchmark's annotation closes after the loop has returned, and the
    # idle tail 90..100 is nobody's
    got = pt.idle_attribution(planes)
    assert got["window_s"] == pytest.approx(90e-9)
    # idle 25 of 90, 10 of it inside host_blocked (the fetch under it included)
    assert got["idle_host_active_share"] == pytest.approx(100 * 15 / 90)
    assert got["idle_unattributed_share"] == 0.0
    assert got["by_span_s"]["reader.stage"] == pytest.approx(2e-9)
    assert pt.idle_attribution([host(loop), device(0, ops)])["idle_unattributed_share"] \
        == pytest.approx(100 * 2 / 25)
    # the median device of three
    planes += [device(1, [(FUSION.format(1), 0.0, 100.0)]),
               device(2, [(FUSION.format(1), 0.0, 38.0), (FUSION.format(1), 40.0, 60.0)])]
    assert pt.idle_attribution(planes)["idle_host_active_share"] == pytest.approx(100 * 2 / 90)
    # a trace with no dispatching thread (a server's) keeps the whole window
    serving = [("serving.batch", 10.0, 20.0, {"batch": 3})]
    got = pt.idle_attribution([host([("bench.traced_window", 0.0, 100.0, {})], serving), device(0, ops)])
    assert got["window_s"] == pytest.approx(100e-9)
    assert got["by_span_s"] == {"serving.batch": pytest.approx(10e-9), pt.UNATTRIBUTED: pytest.approx(25e-9)}


HLO = "\n".join([
    "HloModule jit_train_ab12cd34, is_scheduled=true",
    '  %fusion.1 = f32[8]{0} fusion(%p), kind=kLoop, calls=%fc, metadata={op_name="jit(train_ab12cd34)/jvp(fwd)/op3:mul/dot_general" stack_frame_id=4}',
    '  %fusion.2 = f32[8]{0} fusion(%p), kind=kLoop, calls=%fc, metadata={op_name="jit(train_ab12cd34)/transpose(jvp(fwd))/op3:mul/dot_general"}',
    '  %fusion.3 = f32[8]{0} fusion(%p), kind=kLoop, calls=%fc, metadata={op_name="jit(train_ab12cd34)/update/op40:adam/mul"}',
    '  ROOT %fusion.4 = f32[8]{0} fusion(%p), kind=kLoop, calls=%fc, metadata={op_name="jit(train_ab12cd34)/transpose(jvp(fwd))/op2:relu/select_n;jit(train_ab12cd34)/update/op41:adam/mul"}',
    '  %copy.5 = f32[8]{0} copy(%p), metadata={op_name="jit(train_ab12cd34)/broadcast_in_dim"}',
    '  %copy.6 = f32[8]{0} copy(%p)',
])


def test_the_steps_phases_come_from_the_compiled_text():
    assert pt.phase_of("jit(infer_1)/fwd/op0:mul/dot_general") == "fwd"
    assert pt.phase_of("jit(t)/transpose(jvp(checkpoint))/rematted_computation/fwd/op1:relu/max") == "bwd"
    assert pt.phase_of("jit(t)/op3:fwd_helper/add") is None
    # a fusion carries one scope: a gradient fusion that holds the update too
    # counts as backward
    assert pt.phases_from_hlo_text(HLO) == {
        "fusion.1": "fwd", "fusion.2": "bwd", "fusion.3": "update", "fusion.4": "bwd"}


def test_phase_time_a_step_is_over_the_main_modules_runs_in_the_window():
    phases = pt.phases_from_hlo_text(HLO)
    window = host([("bench.traced_window", 0.0, 4e6, {})])

    def ops(scale):
        return [(FUSION.format(1), 0.0, 1e6 * scale), (FUSION.format(2), 1e6, 2e6 * scale),
                (FUSION.format(3), 3.2e6, 0.5e6 * scale), ("%copy.5 = f32[8]{0} copy(%p)", 3.8e6, 1e5),
                # the next run's forward, cut by the window's end
                (FUSION.format(1), 3.9e6, 1e6 * scale)]

    # the step ran 1.5 times in the window; a probe ran too, less
    modules = [("jit_train_ab12cd34(7)", 0.0, 3.9e6), ("jit_train_ab12cd34(7)", 3.9e6, 0.2e6),
               ("jit_sums(9)", 3.95e6, 1e4)]
    got = pt.phase_ms_per_step([window, device(0, ops(1.0), modules)], phases)
    runs = 1.0 + 0.5
    assert got == {"fwd": pytest.approx(1.1 / runs), "bwd": pytest.approx(2.0 / runs),
                   "update": pytest.approx(0.5 / runs)}
    # the median device
    three = [window, device(0, ops(1.0), modules), device(1, ops(0.5), modules),
             device(2, ops(0.8), modules)]
    assert pt.phase_ms_per_step(three, phases)["bwd"] == pytest.approx(1.6 / runs)
    assert pt.phase_ms_per_step([window, device(0, ops(1.0), modules)], {}) is None
    assert pt.phase_ms_per_step([window], phases) is None


XSPACE_TEXT = """
planes { id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 2000000 } }
  lines { id: 2 name: "Steps" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 2000000 } }
  event_metadata { key: 1 value { id: 1 name: "%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop, calls=%fc" } } }
planes { id: 2 name: "/host:CPU"
  lines { id: 7 name: "python3" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 5000000 }
    events { metadata_id: 2 offset_ps: 2000000 duration_ps: 1000000
             stats { metadata_id: 1 int64_value: 41 } }
    events { metadata_id: 3 offset_ps: 2000000 duration_ps: 1000000 } }
  event_metadata { key: 1 value { id: 1 name: "bench.traced_window" } }
  event_metadata { key: 2 value { id: 2 name: "pipeline.dispatch" } }
  event_metadata { key: 3 value { id: 3 name: "ThunkExecutor::Execute" } }
  stat_metadata { key: 1 value { id: 1 name: "step" } } }
"""


def test_the_programs_spans_are_read_from_the_profilers_own_format_with_their_stats():
    import jax

    PD = jax.profiler.ProfileData
    data = PD.from_serialized_xspace(PD.text_proto_to_serialized_xspace(XSPACE_TEXT))
    planes = pt.planes_of(data, frozenset({"pipeline.dispatch", "bench.traced_window"}))
    assert planes == [
        ("/device:TPU:0", [("XLA Ops", [(FUSION.format(1).replace("bf16[8,128]{1,0}", "f32[8]{0}")
                                         .replace("%p.1", "%p").replace("%fused.1", "%fc"), 1000.0, 2000.0, {})])]),
        ("/host:CPU", [("python3", [("bench.traced_window", 1000.0, 5000.0, {}),
                                    ("pipeline.dispatch", 3000.0, 1000.0, {"step": 41})])])]
    assert pt.traced_window(planes) == (1000.0, 6000.0)
    assert pt.host_lines(planes) == [[("pipeline.dispatch", 3000.0, 4000.0, {"step": 41})]]


def test_a_trace_written_before_this_runs_first_dispatch_is_not_used(tmp_path, monkeypatch):
    run = tmp_path / ".bench_trace" / "some.cell" / "plugins" / "profile" / "2026_01_01"
    run.mkdir(parents=True)
    assert pt.find_trace(str(tmp_path / ".bench_trace" / "some.cell"), 0.0) is None
    pb = run / "host.xplane.pb"
    pb.write_bytes(b"")
    os.utime(pb, (1000.0, 1000.0))
    assert pt.find_trace(str(tmp_path / ".bench_trace" / "some.cell"), 999.0) == str(pb)
    assert pt.find_trace(str(tmp_path / ".bench_trace" / "some.cell"), 1000.5) is None

    class Monitor:
        def events(self):
            return [event("pipeline.dispatch", 1000.5, 0.01, step=0),
                    event("pipeline.dispatch", 1001.5, 0.01, step=1)]

    monkeypatch.setattr(pt, "program_monitor", lambda: Monitor())
    monkeypatch.setattr(mf, "ROOT", str(tmp_path))
    ctx = {"cell": {"name": "some.cell"}, "executables": [object()], "traffic": {}}
    assert pt.traced_planes(ctx) is None
    assert pt.read_idle_metric(ctx, "idle_host_active_share") is None
    assert pt.read_phase_metric(ctx, "fwd") is None


def test_the_timeline_report_splits_dispatch_and_reads_set_up_per_program():
    from benchmark import timeline_report as report

    events = [
        event("executor.build", 0, 0.5, sid=1, program="p1", module="train_ab12cd34"),
        event("executor.lower", 1, 2.0, sid=2, program="p1", module="train_ab12cd34", step=0),
        event("executor.compile", 3, 30.0, sid=3, program="p1", module="train_ab12cd34",
              step=0, cache_hit=False),
        event("executor.compile", 40, 1.5, sid=4, program="p2", module="infer_0000aaaa",
              cache_hit=True),
    ]
    sid = 10
    for i in (4, 5):
        t = 100.0 + i
        events += [event("pipeline.next_batch", t, 0.001, sid=sid, step=i),
                   event("pipeline.dispatch", t + 0.001, 0.004, sid=sid + 1, step=i),
                   event("executor.dispatch", t + 0.002, 0.003, sid=sid + 2, parent=sid + 1, step=i),
                   event("executor.enqueue", t + 0.003, 0.002, sid=sid + 3, parent=sid + 2, step=i),
                   event("reader.stage", t + 0.0005, 0.002, tid=PRODUCER, sid=sid + 4, batch=i, bytes=77)]
        sid += 10
    assert report.setup_by_module(events) == {
        "train_ab12cd34": {"build_s": 0.5, "lower_s": 2.0, "compile_s": 30.0, "cache_hit": False},
        "infer_0000aaaa": {"compile_s": 1.5, "cache_hit": True}}
    per_step = report.host_ms_per_step(events, first_step=4)
    assert per_step["pipeline.dispatch"] == [pytest.approx(4.0), pytest.approx(1.0)]
    assert per_step["executor.dispatch"] == [pytest.approx(3.0), pytest.approx(1.0)]
    assert per_step["executor.enqueue"] == [pytest.approx(2.0), pytest.approx(2.0)]
    assert "executor.compile" not in per_step          # step 0 is set-up
    assert report.host_ms_per_step(events, first_step=9) == {}
    staged = report.reader_stage(events, first_step=4)
    assert staged["batches"] == 2 and staged["bytes"] == 77 and staged["ms_p50"] == pytest.approx(2.0)
    records = [{"kind": "pipeline_step", "pipeline_step": i, "t_step_wall_s": 0.1,
                "t_next_batch_s": 0.001, "t_dispatch_s": 0.004, "t_host_blocked_s": 0.09,
                "inflight": 1, "logged": False} for i in range(12)]
    records[7] = dict(records[7], t_step_wall_s=0.2, t_host_blocked_s=0.19)
    [slow] = report.slow_steps(records, first_step=4)
    assert slow["pipeline_step"] == 7 and slow["t_host_blocked_s"] == 0.19
    assert slow["t_next_step_wall_s"] == 0.1
    assert report.slow_steps(records, first_step=8) == [] == report.slow_steps([], 0)
