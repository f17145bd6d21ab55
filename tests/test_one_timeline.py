"""One timeline (ISSUE 23): the monitor's spans are also events of a
`jax.profiler` trace, the train path's boundaries are spans that carry their
step, and the compiled step says which phase an instruction belongs to and
what the program is."""
import glob
import itertools
import os
import re
import sys

import jax
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import monitor
from paddle_tpu.monitor import MONITOR, NULL_SPAN
from paddle_tpu.monitor import core as monitor_core

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from benchmark import trace_reduce  # noqa: E402

# table B of the issue: the spans of one training step, and the thread of each
LOOP_SPANS = ("pipeline.next_batch", "pipeline.dispatch", "executor.dispatch",
              "executor.feed_place", "executor.enqueue", "pipeline.host_blocked")
STEPS = 6


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
        loss = fluid.layers.mean(fluid.layers.square_error_cost(fluid.layers.fc(h, 1), y))
        fluid.optimizer.Adam(0.01).minimize(loss)
    return main, startup, (x, y), loss


FEED = {"x": np.ones((4, 8), "f4"), "y": np.ones((4, 1), "f4")}


def _train(steps=STEPS, before_loop=lambda: None, uuid8=None):
    main, startup, feeds, loss = _model()
    if uuid8 is not None:
        main._uuid = uuid8 + main._uuid[8:]
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    exe.run(startup, scope=scope)
    loader = fluid.DataLoader.from_generator(list(feeds), capacity=2)
    loader.set_batch_generator(lambda: itertools.repeat(FEED, steps))
    before_loop()
    stats = fluid.train_loop(exe, main, loader, [loss], scope=scope,
                             max_inflight=2, log_period=2)
    assert stats.steps == steps
    return exe, main


def same_program(stat, uuid8: str) -> bool:
    """Is a trace event's `program=` stat the program whose `_uuid[:8]` is
    `uuid8`?  By this rule: the profiler hands a stat that READS as a number
    back as that number, and eight hexadecimal characters do so about once in
    twenty-seven draws: all digits ((10/16)**8, one in forty-three: an int, its
    leading zeros lost, so `len(stat)` is a TypeError) or digits round one `e`
    (`12e45678`: a float, and `inf` at that, which nothing turns back into the
    id).  So a stat that is text is compared as text, and one that is a number
    with the id read the same way.  `module=` starts with a word and is the key
    to join a trace event to a span or a record on (docs/observability.md, "One
    timeline")."""
    if isinstance(stat, str):
        return stat == uuid8
    try:
        return float(uuid8) == stat
    except ValueError:
        return False


@pytest.mark.parametrize("uuid8", [None, "01234567", "12e45678", "0123abcd"],
                         ids=["as_drawn", "all_digits", "digits_round_an_e", "hexadecimal"])
def test_every_span_of_a_training_step_is_in_the_profiler_trace(tmp_path, uuid8):
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0

    def start():
        monitor.enable()
        jax.profiler.start_trace(str(tmp_path), profiler_options=options)

    try:
        _, main = _train(before_loop=start, uuid8=uuid8)
    finally:
        jax.profiler.stop_trace()
    [pb] = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    [host] = [p for p in jax.profiler.ProfileData.from_file(pb).planes
              if p.name == "/host:CPU"]
    wanted = set(LOOP_SPANS) | {"reader.stage", "reader.wait", "executor.fetch"}
    by_thread = {}
    for i, line in enumerate(host.lines):
        found = [(e.name, dict(e.stats)) for e in line.events if e.name in wanted]
        if found:
            by_thread[i] = found
    # two threads: the loop's and the loader's producer
    assert len(by_thread) == 2
    loop, producer = sorted(by_thread.values(), key=len, reverse=True)
    # the loop's thread: every span of table B under its bare name, once a
    # step, each with the step its work belongs to (children take it from
    # their parent)
    for name in LOOP_SPANS:
        steps = sorted(st["step"] for n, st in loop if n == name)
        # the pull after the last step finds the loader exhausted
        pulls = name == "pipeline.next_batch"
        assert steps == list(range(STEPS + pulls)), (name, steps)
    assert {n for n, _ in loop} >= {"reader.wait", "executor.fetch"}
    assert all("step" in st for _, st in loop)
    # keyword arguments come back as the event's stats
    [enq] = [st for n, st in loop if n == "executor.enqueue" and st["step"] == 1]
    assert re.fullmatch(r"train_[0-9a-f]{8}", enq["module"]) and same_program(enq["program"], main._uuid[:8])
    if uuid8 == "01234567":     # the case the rule is for: the id came back as a number, its leading zero gone
        assert enq["program"] == 1234567 and not isinstance(enq["program"], str)
    if uuid8 == "12e45678":     # and worse: a float too large to hold, whatever followed the `e`
        assert enq["program"] == float("inf")
    [placed] = [st for n, st in loop if n == "executor.feed_place" and st["step"] == 1]
    assert placed["bytes"] == sum(v.nbytes for v in FEED.values())
    # the producer's thread: one `reader.stage` a batch, numbered
    assert {n for n, _ in producer} == {"reader.stage"}
    assert sorted(st["batch"] for _, st in producer) == list(range(STEPS))


def test_spans_of_a_step_carry_ids_parents_and_the_step():
    _train(before_loop=monitor.enable)
    events = MONITOR.events()
    by_id = {e[6]: e for e in events}
    assert len(by_id) == len(events) and 0 not in by_id

    def parent_name(e):
        return by_id[e[7]][0] if e[7] else None

    step3 = [e for e in events if (e[5] or {}).get("step") == 3]
    names = {e[0] for e in step3}
    assert names >= set(LOOP_SPANS)
    for e in step3:
        want = {"pipeline.next_batch": None, "pipeline.dispatch": None,
                "pipeline.host_blocked": None, "reader.wait": "pipeline.next_batch",
                "executor.dispatch": "pipeline.dispatch",
                "executor.feed_place": "executor.dispatch",
                "executor.enqueue": "executor.dispatch"}
        if e[0] in want:
            assert parent_name(e) == want[e[0]], e
    # the producer's spans carry their batch, their bytes and no step
    staged = [e for e in events if e[0] == "reader.stage"]
    assert [e[5]["batch"] for e in staged] == list(range(STEPS))
    assert all(e[5]["bytes"] == sum(v.nbytes for v in FEED.values()) and "step" not in e[5]
               for e in staged)
    loop_tids = {e[3] for e in events if e[0] == "pipeline.dispatch"}
    assert len(loop_tids) == 1 and {e[3] for e in staged}.isdisjoint(loop_tids)
    # one record a step says what the host did in it
    recs = [r for r in monitor.step_records() if r["kind"] == "pipeline_step"]
    assert [r["pipeline_step"] for r in recs] == list(range(STEPS))
    for r in recs:
        assert r["t_next_batch_s"] > 0 and r["t_dispatch_s"] > 0
        assert r["t_step_wall_s"] > 0 and r["t_host_blocked_s"] >= 0
    # the executor's records name the module they ran
    assert {r["module"] for r in monitor.step_records() if r["kind"] == "step"} \
        == {by_id[e[6]][5]["module"] for e in step3 if e[0] == "executor.enqueue"}


def test_with_the_monitor_off_no_span_and_no_annotation_is_built(monkeypatch):
    built = []

    class Counting(monitor_core.TraceAnnotation):
        def __init__(self, *a, **kw):
            built.append(a)
            super().__init__(*a, **kw)

    monkeypatch.setattr(monitor_core, "TraceAnnotation", Counting)
    assert monitor.span("pipeline.dispatch", step=1) is NULL_SPAN
    _train()
    assert built == [] and MONITOR.events() == [] and monitor.step_records() == []
    # the same loop with the monitor on builds one annotation a span
    _train(before_loop=monitor.enable)
    assert len(built) == len(MONITOR.events()) > 0


def test_observe_records_an_event_and_enters_no_trace(monkeypatch):
    built = []
    monkeypatch.setattr(monitor_core, "TraceAnnotation",
                        lambda *a, **kw: built.append(a))
    monitor.enable()
    monitor.observe("profiler.record_run", 0.25, tag="t")
    [e] = MONITOR.events()
    assert e[0] == "profiler.record_run" and e[2] == 0.25 and e[6] > 0 and e[7] == 0
    assert built == []


def _compiled_texts(exe):
    return {step.module: [e.as_text() for e in step._exec_by_sig.values()]
            for step in exe._cache.values()}


def test_the_step_says_its_phases_and_its_name():
    main, startup, _, loss = _model()
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    exe.run(startup, scope=scope)
    exe.run(main, feed=FEED, fetch_list=[loss], scope=scope)
    exe.run(main.clone(for_test=True), feed=FEED, fetch_list=[loss], scope=scope)
    texts = _compiled_texts(exe)
    kinds = sorted(name.split("_")[0] for name in texts)
    assert kinds == ["infer", "startup", "train"]
    assert all(re.fullmatch(r"(train|startup|infer)_[0-9a-f]{8}", n) for n in texts)
    [train] = [n for n in texts if n.startswith("train_")]
    [text] = texts[train]
    # the jitted function, and so the module XLA runs, is named from the program
    assert text.splitlines()[0].startswith(f"HloModule jit_{train},")
    op_names = set(re.findall(r'op_name="([^"]*)"', text))
    scoped = [n for n in op_names if re.search(r"op\d+:", n)]
    fwd = {n for n in scoped if "/jvp(fwd)/" in n}
    bwd = {n for n in scoped if "/transpose(jvp(fwd))/" in n}
    update = {n for n in scoped if "/update/" in n}
    assert fwd and bwd and update and fwd | bwd | update == set(scoped)
    # an index names one op of the block: the update continues the forward's
    # numbering (7 forward ops, then one adam per parameter)
    ops = {(int(i), t) for n in scoped for i, t in re.findall(r"op(\d+):([\w.]+)", n)}
    assert len({i for i, _ in ops}) == len(ops)
    assert {t for i, t in ops if i >= 7} == {"adam"} and "adam" not in {t for i, t in ops if i < 7}
    # the innermost scope is what trace_reduce reads: same labels as before
    labels = set(trace_reduce.scopes_from_hlo_text(text).values())
    assert labels >= {"mul.fwd", "mul.bwd", "elementwise_add.fwd",
                      "elementwise_add.bwd", "relu.bwd", "adam.fwd"}
    assert not any(lab.startswith(("fwd", "update")) for lab in labels)
    # a program without a backward op carries `fwd` only
    [infer] = [t for n, ts in texts.items() if n.startswith("infer_") for t in ts]
    infer_names = [n for n in re.findall(r'op_name="([^"]*)"', infer) if re.search(r"op\d+:", n)]
    assert infer_names and all("/fwd/op" in n for n in infer_names)


def test_two_programs_built_alike_share_the_name():
    """The name is part of JAX's persistent-cache key: it comes from the
    program's structure, never from its per-process uuid."""
    from paddle_tpu.core import unique_name

    names = []
    for _ in range(2):
        with unique_name.guard():
            main, startup, _, loss = _model()
        exe = fluid.Executor(fluid.CPUPlace())
        scope = fluid.Scope()
        exe.run(startup, scope=scope)
        exe.run(main, feed=FEED, fetch_list=[loss], scope=scope)
        names.append((main._uuid, sorted(s.module for s in exe._cache.values())))
    (uuid_a, a), (uuid_b, b) = names
    assert uuid_a != uuid_b and a == b and len(a) == 2
    # another structure, another name
    with unique_name.guard():
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup):
            x = fluid.layers.data("x", [8], dtype="float32")
            out = fluid.layers.fc(x, 3)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    exe.run(main, feed={"x": FEED["x"]}, fetch_list=[out])
    assert not set(s.module for s in exe._cache.values()) & set(a)


def test_the_servers_worker_thread_is_on_the_timeline(tmp_path):
    from paddle_tpu import serving

    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data("x", [8], dtype="float32")
        out = fluid.layers.fc(x, 3)
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    exe.run(startup, scope=scope)
    model_dir = str(tmp_path / "m")
    with fluid.scope_guard(scope):
        fluid.io.save_inference_model(model_dir, ["x"], [out], exe, main_program=main)
    registry = serving.ModelRegistry(place=fluid.CPUPlace())
    with serving.Server(registry, buckets=(1, 4)) as srv:
        srv.load_model("m", model_dir)
        monitor.enable()
        srv.infer("m", {"x": np.ones((3, 8), "f4")})
    events = MONITOR.events()
    phases = {e[0]: e for e in events
              if e[0] in ("serving.batch_build", "serving.batch", "serving.split")}
    assert set(phases) == {"serving.batch_build", "serving.batch", "serving.split"}
    batch = {e[5]["batch"] for e in phases.values()}
    assert len(batch) == 1 and len({e[3] for e in phases.values()}) == 1
    for e in phases.values():
        assert (e[5]["bucket"], e[5]["rows"], e[5]["pad_rows"]) == (4, 3, 1)
    # what the predictor ran under `serving.batch` is a span of that batch
    run = [e for e in events if e[0] == "executor.run"]
    assert run and all(e[5]["batch"] in batch for e in run)
    assert all(e[5]["module"].startswith("infer_") for e in run)
