"""PR 67's three readers of the multi-chip layer on planes built by hand, and
their entries in the manifest.  The readers are told by the PROGRAM which
instructions carry a collective (its `kind="collectives"` step record), so a
fusion that holds a gather counts though its name says nothing, and a fusion
that computes while a gather goes on does not.  Entries of `BENCHMARK.json` are
found by name, never by position or count: a later PR appends after them."""
import copy
import json
import types

import pytest

from benchmark import manifest as mf
from benchmark import program_trace
from benchmark.metrics import (collective_bytes_per_step, collective_in_flight_hidden_share,
                               collective_own_time_share)

NEW = ("collective_bytes_per_step", "collective_own_time_share", "collective_in_flight_hidden_share")
FOUR_CHIP_CELLS = ["bert-base.pretrain-s128-dp4", "ai21-jamba2-3b.train-ssm-fsdp4",
                   "nemotron-3-super-120b-a12b.train-ssd-fsdp4"]

#: what the program says of the step below: a synchronous reduce, a gather whose start and done are fusions with a
#: fusion between them that multiplies meanwhile, a fusion that IS a reduce, a permute inside a `while`
RECORD = {
    "kind": "collectives", "program": "01234567", "module": "train_x", "devices": 4, "mesh": {"dp": 4},
    "ops": 4, "bytes": 1000 + 4000 + 2000 + 500, "in_while": 1,
    "by_kind": {"all-reduce": [2, 3000], "all-gather": [1, 4000], "collective-permute": [1, 500]},
    "by_op": {"fwd:mul": [1, 4000], "bwd:matmul": [1, 2000], "bwd:mul": [1, 1000], "other": [1, 500]},
    "instructions": {
        "all-reduce.1": ["all-reduce", "sync", "bwd:mul", 0],
        "async-collective-start": ["all-gather", "start", "fwd:mul", 1],
        "fusion.20": ["all-gather", "overlap", "fwd:mul", 1],
        "async-collective-done": ["all-gather", "done", "fwd:mul", 1],
        "fusion.4": ["all-reduce", "fused", "bwd:matmul", 2],
        "collective-permute-start": ["collective-permute", "start", "partitioner", 3],
        "collective-permute-done": ["collective-permute", "done", "partitioner", 3],
    },
}
#: another module's record of the same process (the `for_test` clone's): never the step's
OTHER = dict(RECORD, module="infer_y", bytes=7, ops=1)

HLO = '''
ENTRY %main {
  %fusion.10 = bf16[8]{0} fusion(%a), kind=kLoop, calls=%f10, metadata={op_name="jit(train_x)/jvp(fwd)/op1:mul/dot_general"}
}
'''


def op(name, start_ms, ms):
    return (f"%{name} = bf16[1]{{0}} fusion(%a), kind=kLoop", start_ms * 1e6, ms * 1e6, {})


#: one device, a window of 100 ms holding ONE run of the step
OPS = [
    op("fusion.10", 0, 10),                      # work
    op("all-reduce.1", 10, 4),                   # a synchronous reduce: 4 ms that nothing hides
    op("async-collective-start", 14, 1),         # the gather leaves
    op("fusion.20", 15, 8),                      # a product runs meanwhile (and carries the gather: `overlap`)
    op("fusion.11", 23, 2),                      # so does other work
    op("async-collective-done", 25, 3),          # 3 ms of waiting
    op("fusion.4", 30, 5),                       # a fusion that IS a reduce
    op("while.3", 40, 20),                       # a loop of two passes, each a permute round 3 ms of work
    op("collective-permute-start", 41, 1), op("fusion.12", 42, 3), op("collective-permute-done", 45, 2),
    op("collective-permute-start", 51, 1), op("fusion.12", 52, 3), op("collective-permute-done", 55, 2),
    op("fusion.13", 70, 10),
]
PLANES = [("/host:CPU", [("main", [("bench.traced_window", 0.0, 100e6, {})])]),
          ("/device:TPU:0", [("XLA Ops", OPS), ("XLA Modules", [("jit_train_x(1)", 0.0, 100e6, {})])])]
TRACE = {"devices": [{}], "window_s": 0.1, "main_module": "jit_train_x(1)", "main_module_runs": 1.0}


@pytest.fixture
def run(monkeypatch):
    """A traced run's `ctx` whose program wrote RECORD and whose trace is PLANES."""
    records = [OTHER, RECORD]
    monkeypatch.setattr(program_trace, "program_monitor", lambda: types.SimpleNamespace(step_records=lambda: records))
    monkeypatch.setattr(program_trace, "traced_planes", lambda ctx: PLANES)
    compiled = type("Compiled", (), {"as_text": lambda self: HLO})()
    return {"trace": dict(TRACE), "executables": [compiled], "cell": {"name": "x"}}, records


def test_the_bytes_are_the_records_of_the_module_that_ran(run):
    ctx, records = run
    assert collective_bytes_per_step.read(ctx) == pytest.approx(7500 / 1e9)
    # joined on `module`, not on `program`: the other module's record is not the step's, whichever came last
    records.reverse()
    assert collective_bytes_per_step.step_record(ctx) is RECORD
    # a rehearsal on the CPU has no device trace: the newest module that trains
    assert collective_bytes_per_step.step_record({"trace": {}}) is RECORD
    # a program that wrote no record (one chip, or a parent without the walk): nothing
    records.clear()
    for reader in (collective_bytes_per_step, collective_own_time_share, collective_in_flight_hidden_share):
        assert reader.read(ctx) is None


def test_the_own_time_is_the_cores_time_in_a_collective_or_waiting_for_one(run, capsys):
    ctx, _ = run
    # the reduce 4, the gather's start 1 and done 3 (NOT the 8 ms product that carries it), the fused reduce 5, the
    # permute's two ends in two passes 2 x (1 + 2); the `while` itself is no collective, whatever it encloses
    own = 4 + (1 + 3) + 5 + 2 * (1 + 2)
    assert collective_own_time_share.read(ctx) == pytest.approx(100.0 * own / 100.0)
    [line] = [json.loads(l) for l in capsys.readouterr().out.splitlines() if '"collectives"' in l]
    assert line["info"] == "collectives" and line["module"] == "train_x" and line["step_ms"] == pytest.approx(100.0)
    assert line["own_ms"] == pytest.approx(own)
    assert line["by_kind"] == {"all-reduce": [2, 3000, pytest.approx(9.0)], "all-gather": [1, 4000, pytest.approx(4.0)],
                               "collective-permute": [1, 500, pytest.approx(6.0)]}
    # a row the record summed under `other` (the permute's `partitioner`) stays there, and is shown
    assert line["by_op"] == {"fwd:mul": [1, 4000, pytest.approx(4.0)], "bwd:matmul": [1, 2000, pytest.approx(5.0)],
                             "bwd:mul": [1, 1000, pytest.approx(4.0)], "other": [1, 500, pytest.approx(6.0)]}
    assert sum(row[2] for row in line["by_op"].values()) == pytest.approx(line["own_ms"])
    assert line["by_role"] == {"sync": pytest.approx(4.0), "start": pytest.approx(3.0), "overlap": pytest.approx(8.0),
                               "done": pytest.approx(7.0), "fused": pytest.approx(5.0)}
    # the own-time table is a cell's one table: made once, kept where Jamba's readers keep theirs
    table = ctx["ssm_own_ms"]
    assert table[0]["while.3"] == pytest.approx(20 - 2 * 6)
    collective_own_time_share.read(ctx)
    assert ctx["ssm_own_ms"] is table
    capsys.readouterr()


def test_a_table_another_reader_of_the_run_made_is_used_as_it_is(run, capsys):
    ctx, _ = run
    ctx["ssm_own_ms"] = ({"all-reduce.1": 2.5, "fusion.20": 50.0}, {})
    assert collective_own_time_share.read(ctx) == pytest.approx(2.5)
    ctx["ssm_own_ms"] = None          # that reader found no trace
    assert collective_own_time_share.read(ctx) is None
    capsys.readouterr()


def test_the_hidden_share_is_the_time_in_flight_that_something_else_covers(run):
    ctx, _ = run
    # in flight: the reduce 4 and the fused reduce 5 (their own events: nothing hides them), the gather from its
    # start at 14 to its done's end at 28, the permute 41-47 and 51-57; something else ran 15-25 under the gather
    # (the product that carries it counts: its time is the product's) and 3 ms in each pass under the permute
    flown = 4 + 5 + 14 + 6 + 6
    hidden = 10 + 3 + 3
    assert collective_in_flight_hidden_share.read(ctx) == pytest.approx(100.0 * hidden / flown)
    flights, elsewhere = collective_in_flight_hidden_share.in_flight(OPS, RECORD["instructions"], (0.0, 100e6))
    assert sorted(flights) == [(10e6, 14e6), (14e6, 28e6), (30e6, 35e6), (41e6, 47e6), (51e6, 57e6)]
    # the `while` encloses its body's collectives: their time is taken out of what counts as something else
    assert (40e6, 41e6) in elsewhere and (42e6, 45e6) in elsewhere and not [i for i in elsewhere if i[0] < 46e6 < i[1]]
    # every collective synchronous (the dp4 cell under GSPMD): nothing is hidden
    sync_only = {"all-reduce.1": RECORD["instructions"]["all-reduce.1"]}
    flights, elsewhere = collective_in_flight_hidden_share.in_flight(OPS, sync_only, (0.0, 100e6))
    assert collective_in_flight_hidden_share.covered(flights, elsewhere) == 0.0 and flights == [(10e6, 14e6)]
    # a start whose done fell outside the window is no flight
    cut = collective_in_flight_hidden_share.in_flight(OPS, RECORD["instructions"], (0.0, 26e6))[0]
    assert sorted(cut) == [(10e6, 14e6)]


def test_covered_time_is_counted_a_flight_though_flights_overlap():
    merged = [(0.0, 10.0), (20.0, 30.0)]
    assert collective_in_flight_hidden_share.covered([(5.0, 25.0), (8.0, 22.0), (12.0, 18.0), (40.0, 50.0)], merged) == 10.0 + 4.0
    assert collective_in_flight_hidden_share.covered([(0.0, 30.0)], []) == 0.0


def test_the_manifest_holds_the_three_readers_for_the_three_four_chip_cells():
    m = mf.load()
    cells = {w["name"]: w for w in m["workloads"]}
    assert all(cells[c]["chips"] == 4 for c in FOUR_CHIP_CELLS)
    for name in NEW:
        entry = next(x for x in m["per_layer"] if x["name"] == name)
        reader = mf.reader_module(name)
        assert entry["workloads"] == FOUR_CHIP_CELLS
        assert (entry["layer"], entry["moves"]) == ("multi-chip (parallel/*)", "train_samples_per_s")
        assert (entry["layer"], entry["unit"], entry["better"], entry["source"], entry["moves"]) == (
            reader.LAYER, reader.UNIT, reader.BETTER, reader.SOURCE, reader.MOVES)
    # no one-chip cell reports one of them
    for w in m["workloads"]:
        mine = {x["name"] for x in mf.metrics_of(m, w["name"], "per_layer")}
        assert (set(NEW) <= mine) == (w["chips"] == 4) and (w["chips"] == 4 or not mine & set(NEW))
    # the two readers that were there stay, for a `benchmark` PR to retire or repoint
    assert {"collective_time_share", "collective_exposed_share"} <= {x["name"] for x in m["per_layer"]}
    # and the checks report nothing they did not report of the manifest without the three entries
    without = copy.deepcopy(m)
    without["per_layer"] = [x for x in m["per_layer"] if x["name"] not in NEW]
    assert mf.problems(m) == mf.problems(without)
