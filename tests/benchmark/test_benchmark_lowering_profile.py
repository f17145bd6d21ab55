"""benchmark/lowering_profile.py: the six readers of the spans inside
`executor.lower` on monitor events built by hand, their entries in the
manifest, and one train cell rehearsed tiny on the CPU through the command,
with the six on its line and a line a program."""
import json
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmark import lowering_profile as lp, manifest as mf, program_trace as pt, setup_timeline as st  # noqa: E402
from tests.benchmark.test_benchmark_rehearsal import tiny_root  # noqa: E402,F401
from tests.benchmark.test_benchmark_setup_timeline import ENABLED_AT, LOOP, PRODUCER, T_PROCESS, event  # noqa: E402

LAYER = "lowering (core/lowering.py)"
METRICS = {
    "setup_trace_forward_s": "s", "setup_trace_transpose_s": "s", "setup_trace_update_s": "s",
    "setup_trace_probe_s": "s", "setup_to_hlo_s": "s", "setup_lower_unattributed_share": "%",
}
STEP_TABLE = {"forward:mul": [3.0, 12], "transpose:fused_attention": [1.5, 2], "update:adam": [1.0, 6],
              "trace:assign": [0.25, 1], "other": [0.5, 9]}


def lowered(start, sid, program, module, table, phases, parent=0, tid=LOOP):
    """An `executor.lower` of 1 s of its own round a `lowering.trace` (half a
    second of its own before `phases`, each (name, seconds, extra arguments))
    and a `lowering.to_hlo` of 2 s."""
    what = dict(program=program, module=module, tid=tid)
    inside = sum(seconds for _, seconds, _ in phases)
    events = [event("executor.lower", start, 0.5 + 0.5 + inside + 2 + 0.5, sid, parent=parent, by_op=table, fenced=0, **what),
              event("lowering.trace", start + 0.5, 0.5 + inside, sid + 1, parent=sid, ops_s=0, **what)]
    at = start + 1.0
    for i, (name, seconds, extra) in enumerate(phases):
        events.append(event("lowering." + name, at, seconds, sid + 2 + i, parent=sid + 1, **what, **extra))
        at += seconds
    return events + [
        event("lowering.to_hlo", at, 2, sid + 9, parent=sid, jaxpr_eqns=400, pallas_calls=3, ops_s=0, **what),
        # what JAX reports lies under the phases and takes nothing from them
        event("jax.trace", start + 1.0, 0.5, sid + 10, parent=sid + 2, tid=tid, fun_name="inner"),
        event("jax.lower", at, 1.5, sid + 11, parent=sid + 9, tid=tid, fun_name="jit(step)"),
    ]


def a_set_up_with_two_programs():
    """Process start at 100; the start-up program lowers at 110, the step at
    130 under the first warm-up step's dispatch; the window opens at 160."""
    return [
        event("executor.prepare", 108, 1, 1, program="bbbb"),
        event("executor.run", 109, 12, 2, program="bbbb"),
        *lowered(110, 10, "bbbb", "startup_11", {"forward:fill_constant": [2.0, 30], "other": [0, 0]},
                 [("plan_kept", 0.25, {"ops_s": 0}), ("forward", 3, {"ops_s": 2.0})], parent=2),
        event("pipeline.next_batch", 128, 1, 3, step=0),
        event("pipeline.dispatch", 129, 25, 4, step=0),
        *lowered(130, 30, "aaaa", "train_22", STEP_TABLE,
                 [("plan_kept", 0.5, {"ops_s": 0}), ("sparse_probe", 1, {"ops_s": 0.75}),
                  ("forward", 6, {"ops_s": 3.25}), ("transpose", 4, {"ops_s": 1.5}), ("update", 2, {"ops_s": 1.0})],
                 parent=4),
        # a lowering on another thread is nobody's here, nor is one inside the window
        *lowered(131, 50, "cccc", "infer_33", {"other": [0, 0]}, [("forward", 1, {"ops_s": 0})], tid=PRODUCER),
        event("pipeline.next_batch", 160, 1, 5, step=1),
        event("pipeline.dispatch", 161, 20, 6, step=1),
        *lowered(162, 70, "dddd", "infer_44", {"other": [0, 0]}, [("forward", 1, {"ops_s": 0})], parent=6),
    ]


def pieces_of(events, first_step=1):
    return st.setup_pieces(events, first_step, ENABLED_AT, T_PROCESS)[0], pt.spans_of(events)


def test_the_parts_are_self_seconds_and_add_up_to_the_executors_spans():
    pieces, spans = pieces_of(a_set_up_with_two_programs())
    parts = lp.parts_of(pieces)
    assert parts == pytest.approx({lp.FORWARD: 3 + 6, lp.TRANSPOSE: 4, lp.UPDATE: 2, lp.PROBE: 0.25 + 0.5 + 1,
                                   lp.HLO: 2 + 2, lp.UNATTRIBUTED: 2 * (0.5 + 0.5 + 0.5)})
    lowers = [s for s in spans if s.name == "executor.lower" and s.tid == LOOP and s.start < 160]
    assert sum(parts.values()) == pytest.approx(sum(s.end - s.start for s in lowers))
    # `setup_lower_s` holds the same seconds and `executor.prepare`
    assert st.parts_of(pieces)[st.LOWER] == pytest.approx(sum(parts.values()) + 1)
    table = lp.merged(s.args["by_op"] for s in lp.lowering_spans(spans, pieces) if s.name == lp.LOWER)
    assert table[0] == ["forward", "mul", 3.0, 12] and table[-1] == ["other", "", 0.5, 9]
    assert ["forward", "fill_constant", 2.0, 30] in table
    # three seconds lie in no part; a quarter of one is a row's outside the five phases
    assert lp.unattributed_share(parts, table) == pytest.approx(100 * (3 - 0.25) / sum(parts.values()))


def test_a_line_a_program_with_its_phases_its_size_and_its_table():
    pieces, spans = pieces_of(a_set_up_with_two_programs())
    startup, step = lp.by_program(lp.lowering_spans(spans, pieces), pieces)
    assert (startup["kind"], startup["module"], step["kind"], step["program"]) == ("startup", "startup_11", "train", "aaaa")
    assert step["lower_s"] == pytest.approx(17) and startup["lower_s"] == pytest.approx(6.75)
    assert step["phases"] == pytest.approx({"plan_kept": 0.5, "sparse_probe": 1, "forward": 6, "transpose": 4,
                                            "update": 2, "to_hlo": 2, "unattributed": 1.5})
    # what is left of a phase beside its rows is JAX's own: the linearisation, `backward_pass`
    assert step["jax_own_s"] == pytest.approx({"sparse_probe": 0.25, "forward": 2.75, "transpose": 2.5, "update": 1.0})
    assert (step["jaxpr_eqns"], step["pallas_calls"]) == (400, 3)
    assert step["by_op"][0] == ["forward", "mul", 3.0, 12]


def test_without_a_lowering_before_the_window_or_without_the_spans_there_is_nothing_to_read(monkeypatch):
    events = a_set_up_with_two_programs()
    parent = [e for e in events if not e[0].startswith("lowering.")]       # the parent of PR 52 opens none
    assert lp.parts_of(pieces_of(parent)[0]) is None
    late = [e for e in events if (e[5] or {}).get("program") in (None, "dddd")]
    assert lp.parts_of(pieces_of(late)[0]) is None

    class Program:
        enabled_at = ENABLED_AT
        seen = parent

        def events(self):
            return self.seen

    monkeypatch.setattr(pt, "program_monitor", lambda: Program())
    monkeypatch.setattr(st, "process_start", lambda: T_PROCESS)
    ctx = {"traffic": {"warmup_steps": 1}, "end_to_end": {"setup_s": 60.0}}
    assert all(mf.reader_module(name).read(ctx) is None for name in METRICS)
    assert all(mf.reader_module(name).read({**ctx, "traffic": {}}) is None for name in METRICS)
    Program.seen = events
    got = {name: mf.reader_module(name).read(ctx) for name in METRICS}
    assert got["setup_trace_forward_s"] == pytest.approx(9) and got["setup_to_hlo_s"] == pytest.approx(4)
    assert got["setup_trace_probe_s"] == pytest.approx(1.75)
    assert got["setup_lower_unattributed_share"] == pytest.approx(100 * 2.75 / 23.75)


def test_the_manifest_has_the_six_metrics_and_their_readers():
    manifest = mf.load()
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    for name, unit in METRICS.items():
        assert by_name[name] == {"name": name, "unit": unit, "better": "lower", "source": "program_span",
                                 "layer": LAYER, "moves": "setup_s"}
        reader = mf.reader_module(name)
        assert (reader.UNIT, reader.BETTER, reader.SOURCE, reader.LAYER, reader.MOVES) == (
            unit, "lower", "program_span", LAYER, "setup_s")
    # every cell reports them, as it does `setup_lower_s`
    for cell in manifest["workloads"]:
        assert set(METRICS) <= {m["name"] for m in mf.metrics_of(manifest, cell["name"], "per_layer")}


def test_the_command_rehearsed_tiny_prints_the_six_and_a_line_a_program(tiny_root, capsys):  # noqa: F811
    found = lp.main(["--workload", "bert-base.pretrain-s128", "--seconds", "2"], root=tiny_root)
    lines = [json.loads(line) for line in capsys.readouterr().out.splitlines() if line.startswith("{")]
    [result] = [line for line in lines if "metrics" in line]
    got = {name: result["metrics"][name]["value"] for name in METRICS}
    assert all(got[name] > 0 for name in ("setup_trace_forward_s", "setup_trace_transpose_s", "setup_trace_update_s",
                                          "setup_to_hlo_s"))
    parts = found["parts"]
    assert got["setup_trace_forward_s"] == pytest.approx(parts[lp.FORWARD])
    assert got["setup_trace_probe_s"] == pytest.approx(parts[lp.PROBE])
    # the five parts and the unattributed seconds are the `executor.lower` spans', which with `executor.prepare`
    # are what `setup_lower_s` reads
    seconds = sum(v for k, v in got.items() if k.endswith("_s"))
    assert seconds + got["setup_lower_unattributed_share"] / 100 * found["lower_s"] == pytest.approx(found["lower_s"])
    assert found["lower_s"] + found["prepare_s"] == pytest.approx(result["metrics"]["setup_lower_s"]["value"])
    assert 0 <= got["setup_lower_unattributed_share"] < 50
    programs = [line for line in lines if line.get("info") == "lowering_program"]
    assert [p["kind"] for p in programs] == ["startup", "infer", "train"] or sorted(p["kind"] for p in programs) == [
        "infer", "startup", "train"]
    step = next(p for p in programs if p["kind"] == "train")
    assert step["jaxpr_eqns"] > 0 and step["pallas_calls"] == 0
    assert {"forward", "transpose", "update", "to_hlo", "plan_kept"} <= set(step["phases"])
    assert step["by_op"][0][2] > 0 and {row[0] for row in step["by_op"][:-1]} <= {"forward", "transpose", "update"}
    sums = [line for line in lines if line.get("info") == "lowering_profile"]
    assert sums and sums[-1]["by_op"][-1][0] == "other" and "programs" not in sums[-1]
