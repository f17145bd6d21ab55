"""What a compiled step communicates (ISSUE 67): the walk over a mesh
program's compiled text (`paddle_tpu/parallel/collectives.py`), the record, the
span and the two counters it publishes while the monitor is on, and nothing of
it with the monitor off or on one chip.  The CPU's compiler makes synchronous
collectives only; what the TPU's makes of Jamba's ZeRO-3 step (collectives
inside fusions, a start and a done fusion round the fusions that compute
meanwhile) is walked from an excerpt of that step's compiled text under
`tests/data/`."""
import gzip
import os
import re
import types

import jax
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import monitor
from paddle_tpu.monitor import MONITOR
from paddle_tpu.parallel import collectives

HERE = os.path.dirname(os.path.abspath(__file__))
FEED = {"x": np.ones((8, 64), "f4"), "y": np.ones((8, 1), "f4")}
#: fc_0 64 x 128 + 128, fc_1 128 x 1 + 1: the parameters a step's gradients are summed for
PARAMETER_BYTES = 4 * (64 * 128 + 128 + 128 + 1)


@pytest.fixture(autouse=True)
def _clean_monitor():
    monitor.disable()
    monitor.reset()
    yield
    monitor.disable()
    monitor.reset()


def _mesh(shape=(4,), names=("dp",)):
    return fluid.parallel.make_mesh(shape, names, jax.devices()[:int(np.prod(shape))])


def _model(hints=None, mesh=None):
    main, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):      # the same names, so the same module, a build
        x = fluid.layers.data("x", [64], dtype="float32")
        y = fluid.layers.data("y", [1], dtype="float32")
        h = fluid.layers.fc(x, 128, act="relu")
        loss = fluid.layers.mean(fluid.layers.square_error_cost(fluid.layers.fc(h, 1), y))
        if hints:
            for program in (main, startup):
                assert fluid.parallel.shard_parameters(program, hints, mesh=mesh, batch_axis="dp") == len(hints)
        fluid.optimizer.SGD(0.01).minimize(loss)
    return main, startup, loss


def _run(mesh=None, hints=None):
    """One step of the model on `mesh` (one chip without); (the executor, the train program)."""
    main, startup, loss = _model(hints, mesh if hints else None)
    exe, scope = fluid.Executor(fluid.CPUPlace()), fluid.Scope()
    exe.run(startup, scope=scope)
    program = fluid.CompiledProgram(main).with_mesh(mesh, batch_axis="dp") if mesh is not None else main
    exe.run(program, feed=FEED, fetch_list=[loss], scope=scope)
    return exe, main


def _records(main=None):
    found = [r for r in monitor.step_records() if r["kind"] == "collectives"]
    return [r for r in found if main is None or r["program"] == main._uuid[:8]]


def _step_text(exe, main):
    [step] = [s for s in exe._cache.values() if s.program_uuid == main._uuid[:8]]
    return step._exec.as_text()


def test_a_dp_programs_record_holds_the_gradients_reduces_with_the_parameters_bytes():
    monitor.enable()
    before = dict(MONITOR.counter_values())
    _, main = _run(_mesh())
    [record] = _records(main)
    assert record["module"].startswith("train_") and record["devices"] == 4 and record["mesh"] == {"dp": 4}
    reduces, nbytes = record["by_kind"]["all-reduce"]
    # the gradients of every parameter once, and the loss's mean with them (XLA sums them in one or several reduces)
    assert reduces >= 1 and PARAMETER_BYTES <= nbytes <= PARAMETER_BYTES + 64
    assert set(record["by_kind"]) == {"all-reduce"} and record["by_axis"] == {"dp": [reduces, nbytes]}
    assert record["by_dtype"] == {"f32": [reduces, nbytes]} and record["in_while"] == 0
    assert record["ops"] == reduces and record["bytes"] == nbytes
    assert sum(row[1] for row in record["by_op"].values()) == nbytes and "other" in record["by_op"]
    # every instruction the device's line will show, with its kind, its role, its op's row and its collective's number
    assert record["instructions"] and all(re.fullmatch(r"all-reduce[.\d]*", name) and row[:2] == ["all-reduce", "sync"]
                                          for name, row in record["instructions"].items())
    assert {row[2] for row in record["instructions"].values()} <= set(record["by_op"])
    # the two counters: a step of every mesh module compiled, summed
    moved = {k: v - before.get(k, 0) for k, v in MONITOR.counter_values().items() if k.startswith("executor.collective_")}
    assert moved == {"executor.collective_ops": reduces, "executor.collective_bytes": nbytes}
    # the walk is a span of its own, a sibling after the compile's under the same parent
    events = MONITOR.events()
    [walk] = [e for e in events if e[0] == "executor.collectives" and e[5]["program"] == main._uuid[:8]]
    [compiling] = [e for e in events if e[0] == "executor.compile" and e[5]["program"] == main._uuid[:8]]
    assert walk[7] == compiling[7] and walk[1] >= compiling[1] + compiling[2]
    assert walk[5]["module"] == record["module"] and walk[5]["ops"] == reduces and walk[5]["bytes"] == nbytes


def test_a_hinted_matrix_is_gathered_a_pass_and_the_row_names_the_product():
    mesh = _mesh()
    monitor.enable()
    _, main = _run(mesh, hints={r"fc_0\.w_0": ("dp", None)})
    [record] = _records(main)
    gathers, nbytes = record["by_kind"]["all-gather"]
    # the whole 64 x 128 matrix on one chip after each gather
    assert gathers >= 1 and nbytes == gathers * 64 * 128 * 4
    assert record["by_op"]["fwd:mul"][0] >= 1 and record["by_op"]["fwd:mul"][1] % (64 * 128 * 4) == 0
    assert any(row[0] == "all-gather" and row[2] == "fwd:mul" for row in record["instructions"].values())
    assert record["by_axis"].keys() == {"dp"}


def test_a_one_chip_program_opens_no_span_and_writes_no_record(monkeypatch):
    monkeypatch.setattr(jax.stages.Compiled, "as_text", lambda self, *a, **k: pytest.fail("as_text was called"))
    monitor.enable()
    _run()
    assert not _records() and not [e for e in MONITOR.events() if e[0] == "executor.collectives"]
    # (`monitor.reset()` keeps the names an earlier test counted under: the counters did not MOVE)
    assert not [k for k, v in MONITOR.counter_values().items() if k.startswith("executor.collective_") and v]


def test_with_the_monitor_off_the_text_is_never_asked_for_and_the_step_is_the_same(monkeypatch):
    mesh = _mesh()
    asked = []
    as_text = jax.stages.Compiled.as_text
    monkeypatch.setattr(jax.stages.Compiled, "as_text", lambda self, *a, **k: asked.append(1) or as_text(self, *a, **k))
    exe_off, main_off = _run(mesh)
    assert not asked and not _records()
    monitor.enable()
    exe_on, main_on = _run(mesh)
    assert asked and _records(main_on)
    monitor.disable()
    texts = [re.sub(r" line=\d+ end_line=\d+ column=\d+ end_column=\d+", "", _step_text(exe, main))
             for exe, main in ((exe_off, main_off), (exe_on, main_on))]
    assert texts[0] == texts[1]


# -- the walk itself, on text ----------------------------------------------------

def _fake_mesh(shape, names):
    return types.SimpleNamespace(axis_names=names, devices=np.arange(int(np.prod(shape))).reshape(shape),
                                 shape=dict(zip(names, shape)), size=int(np.prod(shape)))


@pytest.mark.parametrize("op_name,want", [
    ("jit(train_1)/jvp(fwd)/mamba/op3:mul/dot_general", "fwd:mul"),
    ("jit(train_1)/transpose(jvp(fwd))/op168:mul/dot_general", "bwd:mul"),
    ("jit(train_1)/transpose(jvp(fwd))/rematted_computation/op9:rms_norm/mul", "again:rms_norm"),
    ("jit(train_1)/update/op431:adam/add", "update:adam"),
    ("jit(train_1)/jvp(fwd)/op2:recompute_scope/op7:matmul/dot_general", "fwd:matmul"),
    ("jit(train_1)/reduce_precision", "partitioner"),
    ("", "partitioner"),
])
def test_the_op_a_collective_serves_is_read_from_its_op_name(op_name, want):
    assert collectives.op_of(op_name) == want


def test_shapes_are_counted_by_element_type_and_a_tuple_is_summed():
    assert collectives.shape_bytes("bf16[3072,768]{1,0:T(8,128)(2,1)S(1)} ") == {"bf16": 3072 * 768 * 2}
    held = collectives.shape_bytes("(f32[]{:T(128)}, f32[768]{0:T(1024)S(1)}, /*index=2*/bf16[768,768]{1,0}, u32[]{:S(2)}) ")
    assert held == {"f32": 4 + 768 * 4, "bf16": 768 * 768 * 2, "u32": 4}
    assert collectives.shape_bytes("(bf16[120,8,128]{2,1,0}, bf16[120,8,128]{2,1,0}, u32[], u32[]) ", largest=True) == {"bf16": 120 * 8 * 128 * 2}
    assert collectives.shape_bytes("token[] ") == {}


@pytest.mark.parametrize("attribute,pairs,want", [
    ("replica_groups=[1,4]<=[4]", False, (4, "dp,tp")),
    ("replica_groups={{0,1,2,3}}", False, (4, "dp,tp")),
    ("replica_groups={}", False, (4, "dp,tp")),
    ("replica_groups=[2,2]<=[4]", False, (2, "tp")),                 # {0,1},{2,3}: the minor axis
    ("replica_groups=[2,2]<=[2,2]T(1,0)", False, (2, "dp")),          # {0,2},{1,3}: the major axis
    ("replica_groups={{0,2},{1,3}}", False, (2, "dp")),
    ("replica_groups={{0,3},{1,2}}", False, (2, "?")),                # no axis's groups
    ("source_target_pairs={{0,1},{2,3}}", True, (4, "tp")),
    ("source_target_pairs={{0,2}}", True, (2, "dp")),
])
def test_replica_groups_are_named_by_the_mesh_axes_whose_groups_they_are(attribute, pairs, want):
    axes = collectives._Axes(_fake_mesh((2, 2), ("dp", "tp")))
    assert axes.name(collectives._groups(f"all-reduce(%x), {attribute}, to_apply=%add", 4), pairs) == want


TEXT = """HloModule jit_train_0123abcd, is_scheduled=true

%add (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %sum = f32[] add(%a, %b)
}

%dead_clone (p: f32[64]) -> f32[256] {
  %p = f32[64]{0} parameter(0)
  ROOT %all-gather.77 = f32[256]{0} all-gather(%p), channel_id=77, replica_groups=[1,4]<=[4], dimensions={0}
}

%wrapped_gather (p.1: f32[64]) -> f32[256] {
  %p.1 = f32[64]{0} parameter(0)
  ROOT %all-gather.9 = f32[256]{0} all-gather(%p.1), channel_id=9, replica_groups=[1,4]<=[4], dimensions={0}, metadata={op_name="jit(train)/jvp(fwd)/op5:matmul/dot_general"}
}

%body (state: (s32[], f32[128])) -> (s32[], f32[128]) {
  %state = (s32[], f32[128]{0}) parameter(0)
  %i = s32[] get-tuple-element(%state), index=0
  %v = f32[128]{0} get-tuple-element(%state), index=1
  %reduce-scatter.3 = f32[32]{0} reduce-scatter(%v), channel_id=3, replica_groups={{0,1,2,3}}, dimensions={0}, to_apply=%add, metadata={op_name="jit(train)/transpose(jvp(fwd))/op7:repeat/while/body/op2:mul/dot_general"}
  %all-gather.4 = f32[128]{0} all-gather(%reduce-scatter.3), channel_id=4, replica_groups={{0,1,2,3}}, dimensions={0}
  ROOT %next = (s32[], f32[128]{0}) tuple(%i, %all-gather.4)
}

%condition (state.1: (s32[], f32[128])) -> pred[] {
  %state.1 = (s32[], f32[128]{0}) parameter(0)
  ROOT %go = pred[] constant(true)
}

ENTRY %main.1_spmd (x: f32[64], w: bf16[16,8]) -> f32[256] {
  %x = f32[64]{0} parameter(0)
  %w = bf16[16,8]{1,0} parameter(1)
  %all-reduce-start.1 = bf16[16,8]{1,0} all-reduce-start(%w), channel_id=1, replica_groups=[1,4]<=[4], to_apply=%add, metadata={op_name="jit(train)/transpose(jvp(fwd))/op3:mul/dot_general"}
  %permute-start = (f32[64]{0}, f32[64]{0}, u32[], u32[]) collective-permute-start(%x), channel_id=2, source_target_pairs={{0,1},{1,2},{2,3}}
  %busy = f32[64]{0} multiply(%x, %x), metadata={op_name="jit(train)/jvp(fwd)/op1:elementwise_mul/mul"}
  %permute-done = f32[64]{0} collective-permute-done(%permute-start)
  %all-reduce-done.1 = bf16[16,8]{1,0} all-reduce-done(%all-reduce-start.1)
  %start = ((f32[64]{0}), f32[256]{0}, u32[]) async-start(%x), calls=%wrapped_gather
  %done = f32[256]{0} async-done(%start)
  %zero = s32[] constant(0)
  %v0 = f32[128]{0} constant({...})
  %init = (s32[], f32[128]{0}) tuple(%zero, %v0)
  %loop = (s32[], f32[128]{0}) while(%init), condition=%condition, body=%body, backend_config={"known_trip_count":{"n":"5"}}
  ROOT %out = f32[256]{0} copy(%done)
}
"""


def test_the_walk_pairs_a_start_with_its_done_follows_calls_and_counts_a_loops_passes():
    found = collectives.collectives_of(TEXT, _fake_mesh((4,), ("dp",)))
    by_kind = {(one["kind"], one["op"]): one for one in found}
    assert len(found) == len(by_kind) == 5              # the clone no instruction calls is not the step's
    reduce = by_kind["all-reduce", "bwd:mul"]
    assert reduce["instructions"] == {"all-reduce-start.1": "start", "all-reduce-done.1": "done"}     # ONE collective
    assert (reduce["bytes"], reduce["dtype"], reduce["participants"], reduce["axis"]) == (16 * 8 * 2, "bf16", 4, "dp")
    permute = by_kind["collective-permute", "partitioner"]
    assert permute["instructions"] == {"permute-start": "start", "permute-done": "done"}
    assert (permute["bytes"], permute["participants"]) == (64 * 4, 4)      # the done's shape: the array, not the start's tuple
    wrapped = by_kind["all-gather", "fwd:matmul"]          # its own instruction's op_name, through the call
    assert wrapped["instructions"] == {"start": "start", "done": "done"} and wrapped["bytes"] == 256 * 4
    scattered = by_kind["reduce-scatter", "bwd:mul"]
    # before the reduce: the result times the participants; a pass of the body times the loop's five
    assert (scattered["bytes"], scattered["in_while"], scattered["passes"]) == (32 * 4 * 4, True, 5)
    assert scattered["instructions"] == {"reduce-scatter.3": "sync"}
    assert by_kind["all-gather", "partitioner"]["passes"] == 5
    record = collectives.record_of(found, _fake_mesh((4,), ("dp",)), "0123abcd", "train_0123abcd")
    assert record["by_kind"] == {"all-reduce": [1, 256], "collective-permute": [1, 256], "all-gather": [6, 1024 + 5 * 512],
                                 "reduce-scatter": [5, 5 * 512]}
    assert record["ops"] == 13 and record["bytes"] == 256 + 256 + 1024 + 5 * 512 + 5 * 512 and record["in_while"] == 2
    assert record["instructions"]["permute-done"] == ["collective-permute", "done", "partitioner", found.index(permute)]


def test_the_tables_keep_the_twelve_dearest_rows_and_sum_the_rest():
    rows = {f"fwd:op{i}": [1, 100 - i] for i in range(20)}
    table = collectives._table(rows)
    assert list(table)[:12] == [f"fwd:op{i}" for i in range(12)] and len(table) == 13
    assert table["other"] == [8, sum(100 - i for i in range(12, 20))]


@pytest.fixture(scope="module")
def jamba_excerpt():
    with gzip.open(os.path.join(HERE, "data", "jamba_step_collectives.hlo.txt.gz"), "rt") as f:
        return f.read()


def test_the_tpus_fused_collectives_are_found_in_an_excerpt_of_jambas_compiled_step(jamba_excerpt):
    """The chip's own `compiled.as_text()` of `ai21-jamba2-3b.train-ssm-fsdp4`'s
    step (my chip run, PR 67), cut to a few of its collectives with everything
    they call: a gather whose three fusions (`async-collective-start`, an
    `async_collective_fusion` that multiplies meanwhile, `async-collective-done`)
    carry clones of ONE `all-gather` of one channel; an `all-reduce-scatter`
    fusion, which IS its collective; a permute's start and done; and a
    synchronous gather and reduce."""
    found = collectives.collectives_of(jamba_excerpt, _fake_mesh((4,), ("dp",)))
    shapes = sorted((one["kind"], tuple(sorted(set(one["instructions"].values())))) for one in found)
    assert ("all-gather", ("done", "overlap", "start")) in shapes
    assert ("all-reduce", ("fused",)) in shapes
    assert ("collective-permute", ("done", "start")) in shapes
    assert ("all-gather", ("sync",)) in shapes
    assert all(one["axis"] == "dp" and one["participants"] == 4 and not one["in_while"] for one in found)
    # a clone of a collective stands in each fusion that carries it: one channel, ONE collective
    in_text = len(re.findall(r" all-gather\(", jamba_excerpt))
    gathers = [one for one in found if one["kind"] == "all-gather"]
    assert len(gathers) < in_text
    fused = next(one for one in gathers if "overlap" in one["instructions"].values())
    assert [name for name, role in fused["instructions"].items() if role == "start"][0].startswith("async-collective-start")
    assert [name for name, role in fused["instructions"].items() if role == "done"][0].startswith("async-collective-done")
    assert re.fullmatch(r"(fwd|bwd|again):\w+", fused["op"]) and fused["dtype"] in ("bf16", "f32")
    record = collectives.record_of(found, _fake_mesh((4,), ("dp",)), "p", "train_2a9cba2d")
    assert record["bytes"] == sum(one["bytes"] for one in found) > 0
    assert {row[1] for row in record["instructions"].values()} == {"sync", "start", "done", "fused", "overlap"}
