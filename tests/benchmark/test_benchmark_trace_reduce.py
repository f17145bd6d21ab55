"""The reduction from a profiler trace to busy, idle, per-label, collective
and idle-gap seconds, on traces built by hand: one as the text form of the
profiler's own file format (so that loading is covered), the rest as the
plain tuples the reduction works on."""
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmark import trace_reduce as tr  # noqa: E402

FUSION = "%fusion.{} = bf16[8,128]{{1,0:T(8,128)(2,1)}} fusion(bf16[8,128]{{1,0:T(8,128)(2,1)}} %p.1), kind=kLoop, calls=%fused.{}"
CONV = "%convolution.5 = bf16[8,64]{1,0} convolution(bf16[8,3]{1,0} %a, bf16[3,64]{1,0} %b), window={size=1}"
ALLREDUCE = "%all-reduce.7 = f32[768,768]{1,0:T(8,128)} all-reduce(f32[768,768]{1,0:T(8,128)} %fusion.3), replica_groups={{0,1,2,3}}, to_apply=%add"
AR_START = "%all-reduce-start.9 = f32[64]{0} all-reduce-start(f32[64]{0} %fusion.3), replica_groups={{0,1}}"
AR_DONE = "%all-reduce-done.9 = f32[64]{0} all-reduce-done(f32[64]{0} %all-reduce-start.9)"

XSPACE_TEXT = """
planes { id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 2000000 }
    events { metadata_id: 2 offset_ps: 3000000 duration_ps: 1000000 } }
  lines { id: 2 name: "XLA Modules" timestamp_ns: 1000
    events { metadata_id: 3 offset_ps: 0 duration_ps: 4000000 } }
  event_metadata { key: 1 value { id: 1 name: "%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop, calls=%fc" } }
  event_metadata { key: 2 value { id: 2 name: "%all-reduce.2 = f32[8]{0} all-reduce(f32[8]{0} %fusion.1), replica_groups={}" } }
  event_metadata { key: 3 value { id: 3 name: "jit_step(123)" } } }
planes { id: 2 name: "/host:CPU"
  lines { id: 7 name: "python3" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 5000000 }
    events { metadata_id: 2 offset_ps: 2000000 duration_ps: 1000000 } }
  event_metadata { key: 1 value { id: 1 name: "bench.traced_window" } }
  event_metadata { key: 2 value { id: 2 name: "bench.next_loader" } } }
"""


def test_names_are_read_from_the_instruction_text():
    assert tr.opcode_of(FUSION.format(1, 1)) == "fusion"
    assert tr.instruction_of(FUSION.format(12, 3)) == "fusion.12"
    assert tr.opcode_of(CONV) == "convolution"
    assert tr.opcode_of(ALLREDUCE) == "all-reduce" and tr.is_collective("all-reduce")
    assert tr.opcode_of(AR_START) == "all-reduce-start" and tr.is_collective("all-reduce-start")
    assert tr.opcode_of("%t = (f32[8]{0:T(8)S(1)}, u32[]{:S(2)}) copy-start(f32[8]{0} %x)") == "copy-start"
    assert not tr.is_collective("copy-start")
    assert tr.opcode_of("jit_step(123)") == "jit_step(123)"  # not HLO text


def test_interval_arithmetic():
    u = tr.union([(0, 2), (1, 3), (5, 6), (6, 7), (9, 9)])
    assert u == [(0, 3), (5, 7)]
    assert tr.total(u) == 5
    assert tr.clip(u, 2, 6) == [(2, 3), (5, 6)]
    assert tr.subtract([(0, 10)], u) == [(3, 5), (7, 10)]
    assert tr.subtract(u, [(0, 10)]) == []
    assert tr.subtract([(0, 4), (6, 8)], [(1, 2), (3, 7)]) == [(0, 1), (2, 3), (7, 8)]


def test_scopes_come_from_the_compiled_text():
    text = "\n".join([
        'HloModule jit_step',
        '  %fusion.1 = f32[8]{0} fusion(%p), kind=kLoop, calls=%fc, metadata={op_name="jit(step)/op12:mul/dot_general" stack_frame_id=4}',
        '  %fusion.2 = f32[8]{0} fusion(%p), kind=kLoop, calls=%fc, metadata={op_name="jit(step)/transpose(jvp(op12:mul))/dot_general"}',
        '  ROOT %fusion.3 = f32[8]{0} fusion(%p), kind=kLoop, calls=%fc, metadata={op_name="jit(step)/op40:adam/mul"}',
        '  %copy.4 = f32[8]{0} copy(%p), metadata={op_name="jit(step)/broadcast_in_dim"}',
        '  %copy.5 = f32[8]{0} copy(%p)',
    ])
    assert tr.scopes_from_hlo_text(text) == {
        "fusion.1": "mul.fwd", "fusion.2": "mul.bwd", "fusion.3": "adam.fwd"}


def test_a_trace_in_the_profilers_own_format():
    import jax

    PD = jax.profiler.ProfileData
    planes = tr.planes_of(PD.from_serialized_xspace(PD.text_proto_to_serialized_xspace(XSPACE_TEXT)))
    r = tr.reduce_trace(planes, {"fusion.1": "mul.fwd"})
    assert r["window_s"] == pytest.approx(5e-6)
    assert r["busy_s"] == pytest.approx(3e-6)
    assert r["idle_share"] == pytest.approx(0.4)
    assert r["scoped_share"] == pytest.approx(2 / 3)
    assert r["collective_share"] == pytest.approx(0.2)
    assert r["collective_exposed_share"] == pytest.approx(0.2)
    assert r["by_label"] == {"mul.fwd": pytest.approx(2e-6),
                             "all-reduce:all-reduce": pytest.approx(1e-6)}
    # the gap 2..3 us lies under bench.next_loader, the gap 4..5 us under nothing
    assert r["idle_gaps"] == {"bench.next_loader": pytest.approx(1e-6),
                              "host:unannotated": pytest.approx(1e-6)}
    assert r["main_module"] == "jit_step(123)" and r["main_module_runs"] == pytest.approx(1.0)
    b = tr.breakdown(r)
    assert b["device_ops"][0] == ["mul.fwd", pytest.approx(2e-6)]
    assert len(b["idle_gaps"]) == 2


def device(n, ops, in_flight=(), modules=()):
    return (f"/device:TPU:{n}", [("XLA Ops", list(ops)), ("Async XLA Ops", list(in_flight)),
                                 ("XLA Modules", list(modules))])


def test_collectives_hidden_and_exposed_and_the_median_device():
    window = ("/host:CPU", [("python3", [("bench.traced_window", 0.0, 100.0)])])
    # device 0: an async all-reduce in flight 10..40, hidden behind work
    # until 30, then waited for (its -done op) until 40; work again 50..90
    d0 = device(0, [(FUSION.format(1, 1), 0.0, 30.0), (AR_START, 10.0, 1.0),
                    (AR_DONE, 30.0, 10.0), (CONV, 50.0, 40.0)],
                in_flight=[(AR_START, 10.0, 30.0)],
                modules=[("jit_step(1)", 0.0, 90.0), ("jit_step(1)", 90.0, 20.0)])
    # device 1: a synchronous all-reduce 20..40, nothing else then
    d1 = device(1, [(FUSION.format(1, 1), 0.0, 20.0), (ALLREDUCE, 20.0, 20.0),
                    (CONV, 40.0, 40.0)])
    # device 2: no collective at all, busy throughout
    d2 = device(2, [(CONV, 0.0, 100.0)])
    r = tr.reduce_trace([d0, d1, d2, window])
    by = {d["name"]: d for d in r["devices"]}
    a = by["/device:TPU:0"]
    assert a["busy_s"] == pytest.approx(80e-9) and a["window_s"] == pytest.approx(100e-9)
    assert a["collective_s"] == pytest.approx(30e-9)          # 10..40
    assert a["collective_exposed_s"] == pytest.approx(10e-9)  # 30..40: only the wait ran
    assert a["module_runs"]["jit_step(1)"] == pytest.approx(1.5)  # the second run is cut in half
    b = by["/device:TPU:1"]
    assert b["collective_s"] == b["collective_exposed_s"] == pytest.approx(20e-9)
    # shares are those of the median device; seconds are means over devices
    assert r["collective_exposed_share"] == pytest.approx(0.1)
    assert r["collective_share"] == pytest.approx(0.2)
    assert r["idle_share"] == pytest.approx(0.2)
    assert r["busy_s"] == pytest.approx((80 + 80 + 100) / 3 * 1e-9)


def test_events_are_cut_to_the_window_and_a_trace_without_a_device_reduces_to_nothing():
    window = ("/host:CPU", [("python3", [("bench.traced_window", 10.0, 10.0),
                                           ("bench.submit", 12.0, 2.0),
                                           ("other", 0.0, 50.0)])])
    r = tr.reduce_trace([device(0, [(CONV, 0.0, 12.0), (CONV, 14.0, 2.0), (CONV, 18.0, 30.0)]), window])
    assert r["window_s"] == pytest.approx(10e-9)
    assert r["busy_s"] == pytest.approx(6e-9)      # 10..12, 14..16, 18..20
    assert r["idle_gaps"] == {"bench.submit": pytest.approx(2e-9),
                              "host:unannotated": pytest.approx(2e-9)}
    assert r["n_annotations"] == 2                 # "other" is not the benchmark's
    empty = tr.reduce_trace([window, ("/host:metadata", [])])
    assert empty["devices"] == [] and "busy_s" not in empty
    assert tr.breakdown(empty) == {"device_ops": [], "idle_gaps": []}
    # without the window annotation the window is the extent of the device's events
    bare = tr.reduce_trace([device(0, [(CONV, 5.0, 5.0), (CONV, 15.0, 5.0)])])
    assert bare["window_s"] == pytest.approx(15e-9) and bare["idle_share"] == pytest.approx(1 / 3)
