"""Static resource planner (paddle_tpu/core/resource_plan.py): liveness
peak-HBM + op cost model, and its three consumers.

Acceptance contract (ISSUE 12):
  * planted-defect tests per planner class — leaked live range,
    double-counted donated buffer, sub-block peak escaping to parent,
    persistable misclassified as temp — each asserting the WATERMARK names
    the offending op (same style as tests/test_analysis.py);
  * plan peak within the stated tolerance of measured truth on all 5 zoo
    programs (tools/resource_plan.py --check, the tier-1 calibration gate;
    the [CALIBRATION_RATIO_LO, CALIBRATION_RATIO_HI] band is the ratchet);
  * an over-budget program raises classified ResourceError naming the
    watermark ops BEFORE any XLA compile/allocate.
"""
import contextlib
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import layers, monitor
from paddle_tpu.core import resource_plan as rp
from paddle_tpu.core.program import Operator
from paddle_tpu.errors import ResourceError, classify

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)

F4 = 4  # float32 bytes


@contextlib.contextmanager
def _flag(name, value):
    old = fluid.get_flags([name])[name]
    fluid.set_flags({name: value})
    try:
        yield
    finally:
        fluid.set_flags({name: old})


def _watermark_vars(plan):
    return [w["var"] for w in plan.watermark]


# --------------------------------------------------------------------------
# planner semantics: planted defects, each naming the op
# --------------------------------------------------------------------------

def test_leaked_live_range_names_consumer_and_def_op():
    """A late reader of an early temp stretches its interval to itself —
    the watermark at the (now later) peak must name the leaked var AND its
    def op."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = layers.data("x", [256, 256], dtype="float32")
        y = layers.relu(x)    # big temp
        z = layers.relu(y)
        w = layers.relu(z)
    feed = {"x": (4, 256, 256)}
    base = rp.plan_program(main, feed, [w.name])
    # baseline: y dies after z's read; with a chain of equal-size temps the
    # peak holds ~2 temps + the fetched one
    blk = main.global_block()
    blk.ops.append(Operator(blk, "elementwise_add",
                            {"X": [w.name], "Y": [y.name]},
                            {"Out": [blk.create_var(
                                name="leak_out", shape=[-1, 256, 256],
                                dtype="float32").name]}))
    leaked = rp.plan_program(main, feed, ["leak_out"])
    assert leaked.peak_bytes > base.peak_bytes, \
        "a leaked live range must raise the planned peak"
    assert y.name in _watermark_vars(leaked)
    ent = next(w_ for w_ in leaked.watermark if w_["var"] == y.name)
    assert ent["def_op_type"] == "relu" and ent["def_op_idx"] == 0


def test_donated_inplace_update_counted_once():
    """An in-place persistable update (read + written, the executor's
    donation set) costs its buffer ONCE — the donation audit's `donated`
    class."""
    main = fluid.Program()
    blk = main.global_block()
    blk.create_parameter("w", shape=[512, 512], dtype="float32")
    blk.ops.append(Operator(blk, "scale", {"X": ["w"]}, {"Out": ["w"]},
                            {"scale": 1.1}))
    plan = rp.plan_program(main)
    W = 512 * 512 * F4
    assert plan.persistable_bytes == W
    assert plan.peak_bytes == W, \
        f"donated in-place update double-counted: {plan.peak_bytes} != {W}"
    assert plan.peak_temp_bytes == 0


def test_written_not_read_persistable_pays_double_buffer_and_names_op():
    """A persistable written but never read (donation audit's
    `copied_not_read`) CANNOT be aliased by XLA: its writer pays a
    transient second buffer and the watermark names that op."""
    main = fluid.Program()
    blk = main.global_block()
    blk.create_parameter("w", shape=[512, 512], dtype="float32")
    blk.create_parameter("w2", shape=[512, 512], dtype="float32")
    blk.ops.append(Operator(blk, "scale", {"X": ["w"]}, {"Out": ["w"]},
                            {"scale": 1.1}))
    blk.ops.append(Operator(blk, "assign", {"X": ["w"]}, {"Out": ["w2"]}))
    plan = rp.plan_program(main)
    W = 512 * 512 * F4
    assert plan.persistable_bytes == 2 * W
    assert plan.peak_bytes == 3 * W, \
        "copied_not_read persistable must cost a transient double buffer"
    assert plan.peak_op_type == "assign"
    assert "w2" in _watermark_vars(plan)


def test_sub_block_peak_charged_to_owner_and_does_not_escape():
    """Sub-block temps peak INSIDE the owning op (charged to it, named by
    it) and die at loop exit — an op after the loop must not carry them."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = layers.data("x", [16], dtype="float32")
        y = layers.relu(x)
    sub = main.create_block()
    sub.create_var(name="sub_big", shape=[1024, 1024], dtype="float32")
    sub.create_var(name="sub_out", shape=[1024, 1024], dtype="float32")
    sub.ops.append(Operator(sub, "fill_constant", {}, {"Out": ["sub_big"]},
                            {"shape": [1024, 1024], "value": 0.0,
                             "dtype": "float32"}))
    sub.ops.append(Operator(sub, "relu", {"X": ["sub_big"]},
                            {"Out": ["sub_out"]}))
    main.rollback()
    blk = main.global_block()
    blk.create_var(name="loop_out", shape=[-1, 16], dtype="float32")
    blk.ops.append(Operator(blk, "while", {"X": [y.name]},
                            {"Out": ["loop_out"]}, {"sub_block": sub.idx}))
    blk.ops.append(Operator(blk, "relu", {"X": [y.name]},
                            {"Out": [blk.create_var(
                                name="after", shape=[-1, 16],
                                dtype="float32").name]}))
    plan = rp.plan_program(main, {"x": (4, 16)}, ["after"])
    MB4 = 1024 * 1024 * F4
    assert plan.peak_op_type == "while", \
        "the sub-block peak must be charged to (and named by) the owner op"
    assert plan.peak_temp_bytes >= 2 * MB4  # sub_big + sub_out live together
    assert "sub_big" in _watermark_vars(plan)
    # the op AFTER the loop must not still carry the sub-block temps
    after_row = [r for r in plan.rows if r.op_type == "relu"][-1]
    assert after_row.live_bytes < MB4, \
        f"sub-block temps escaped to the parent: {after_row.live_bytes}"


def test_persistable_written_late_is_resident_not_a_temp():
    """A persistable written mid/late-block (BN stats, metric accumulators)
    is scope state resident for the WHOLE program — not an interval that
    starts at its writer."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = layers.data("x", [8], dtype="float32")
        y = layers.relu(x)
    blk = main.global_block()
    blk.create_parameter("acc", shape=[1024, 256], dtype="float32")
    blk.ops.append(Operator(blk, "scale", {"X": ["acc"]}, {"Out": ["acc"]},
                            {"scale": 0.9}))
    plan = rp.plan_program(main, {"x": (4, 8)}, [y.name])
    ACC = 1024 * 256 * F4
    assert plan.persistable_bytes == ACC
    assert plan.peak_bytes >= ACC + plan.feed_bytes
    # resident state, not a live-range temp: it must not appear in the
    # temp watermark and the first op already pays for it via the base
    assert "acc" not in _watermark_vars(plan)
    assert all(r.live_bytes < ACC for r in plan.rows), \
        "persistable misclassified as a def/last-use temp"


def test_backward_extends_activations_and_defines_grads():
    """Ahead of a `backward` op every forward temp is potentially saved
    for the VJP (live until the backward), and the grad buffers its attrs
    name are defined there — the training-peak shape the zoo plans show."""
    from paddle_tpu import optimizer as opt

    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = layers.data("x", [64], dtype="float32")
        h = layers.fc(x, 64, act="relu")
        loss = layers.mean(layers.fc(h, 1))
        opt.SGD(learning_rate=0.1).minimize(loss)
    plan = rp.plan_program(main, {"x": (8, 64)}, [loss.name])
    assert plan.peak_op_type == "backward"
    assert any(v.endswith("@GRAD") for v in _watermark_vars(plan))


# --------------------------------------------------------------------------
# cost model
# --------------------------------------------------------------------------

def test_matmul_cost_is_2mkn_and_coverage_complete():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = layers.data("x", [32, 64], dtype="float32")
        y = layers.fc(x, 128)  # mul + elementwise_add
    plan = rp.plan_program(main, {"x": (4, 32, 64)}, [y.name])
    mul = next(r for r in plan.rows if r.op_type == "mul")
    # fc flattens to [4*32, 64] @ [64, 128]
    assert mul.flops == 2 * (4 * 32) * 64 * 128
    assert plan.cost_coverage_frac == 1.0
    assert all(r.cost_covered for r in plan.rows)


def test_sub_block_body_rows_inherit_owner_grad_factor():
    """A sub-block executing ahead of a parent-block `backward` is
    differentiated too: its body rows must carry the owner's 3x factor
    (the planner once costed bodies at 1x — body-local liveness saw no
    backward)."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = layers.data("x", [16], dtype="float32")
        y = layers.relu(x)
    sub = main.create_block()
    sub.create_var(name="body_out", shape=[-1, 16], dtype="float32")
    sub.ops.append(Operator(sub, "relu", {"X": [y.name]},
                            {"Out": ["body_out"]}))
    main.rollback()
    blk = main.global_block()
    blk.create_var(name="loop_out", shape=[-1, 16], dtype="float32")
    blk.ops.append(Operator(blk, "while", {"X": [y.name]},
                            {"Out": ["loop_out"]}, {"sub_block": sub.idx}))
    blk.create_var(name="loss", shape=[1], dtype="float32")
    blk.ops.append(Operator(blk, "mean", {"X": ["loop_out"]},
                            {"Out": ["loss"]}))
    blk.ops.append(Operator(blk, "backward", {"Loss": ["loss"]},
                            {"Grads": []},
                            {"loss_name": "loss", "param_names": [],
                             "grad_names": []}))
    plan = rp.plan_program(main, {"x": (4, 16)}, ["loss"])
    relu_rows = [r for r in plan.rows if r.op_type == "relu"]
    assert len(relu_rows) == 2  # parent x->y AND the body relu
    assert all(r.grad_factor == 3 for r in relu_rows), \
        "sub-block body ahead of backward must inherit the 3x factor"
    owner = next(r for r in plan.rows if r.op_type == "while")
    assert owner.grad_factor == 3


def test_grad_factor_3x_ahead_of_backward():
    from paddle_tpu import optimizer as opt

    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = layers.data("x", [16], dtype="float32")
        loss = layers.mean(layers.fc(x, 4))
        opt.SGD(learning_rate=0.1).minimize(loss)
    plan = rp.plan_program(main, {"x": (2, 16)}, [loss.name])
    mul = next(r for r in plan.rows if r.op_type == "mul")
    sgd = next(r for r in plan.rows if r.op_type == "sgd")
    assert mul.grad_factor == 3   # fwd + 2x bwd
    assert sgd.grad_factor == 1   # the update itself runs once


# --------------------------------------------------------------------------
# consumer 1: the executor's OOM pre-check
# --------------------------------------------------------------------------

def test_over_budget_raises_resource_error_before_any_compile():
    """The acceptance bar: classified ResourceError (phase=build) naming
    the watermark ops, with ZERO compile-cache misses / recompiles — i.e.
    before any XLA work."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = layers.data("x", [256], dtype="float32")
        y = layers.fc(x, 256, act="relu")
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    with _flag("FLAGS_resource_precheck", "off"):
        exe.run(startup, scope=scope)
    miss0 = monitor.counter("executor.cache_miss").value
    rec0 = monitor.counter("executor.recompile").value
    with _flag("FLAGS_resource_hbm_limit_mb", 0.01):  # 10 KB: nothing fits
        with pytest.raises(ResourceError) as ei:
            exe.run(main, feed={"x": np.ones((4, 256), "f4")},
                    fetch_list=[y.name], scope=scope)
    e = ei.value
    assert e.phase == "build"
    assert e.watermark_ops, "the error must name the watermark ops"
    assert e.needed_bytes > e.limit_bytes
    assert classify(e) is e  # already classified; never re-wrapped
    assert monitor.counter("executor.cache_miss").value == miss0
    assert monitor.counter("executor.recompile").value == rec0, \
        "ResourceError must fire BEFORE any XLA compile"


def test_precheck_passes_and_program_runs_under_honest_limit():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = layers.data("x", [8], dtype="float32")
        y = layers.relu(x)
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    exe.run(startup, scope=scope)
    with _flag("FLAGS_resource_hbm_limit_mb", 64.0):
        out = exe.run(main, feed={"x": np.ones((2, 8), "f4")},
                      fetch_list=[y.name], scope=scope)
    assert np.allclose(out[0], 1.0)


def test_precheck_off_flag_skips_the_check():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = layers.data("x", [8], dtype="float32")
        y = layers.relu(x)
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    exe.run(startup, scope=scope)
    with _flag("FLAGS_resource_precheck", "off"), \
            _flag("FLAGS_resource_hbm_limit_mb", 0.0001):
        out = exe.run(main, feed={"x": np.ones((2, 8), "f4")},
                      fetch_list=[y.name], scope=scope)
    assert np.allclose(out[0], 1.0)


# --------------------------------------------------------------------------
# consumer 2: serving budgets on plan bytes (weights + activations)
# --------------------------------------------------------------------------

def _save_serving_model(dirname, d_in=64, d_out=64):
    from paddle_tpu.core import unique_name

    with unique_name.guard():
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup):
            x = layers.data("x", [d_in], dtype="float32")
            out = layers.fc(x, d_out, act="relu")
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    exe.run(startup, scope=scope)
    fluid.io.save_inference_model(dirname, ["x"], [out], exe, main, scope)
    return dirname


def test_plan_model_bytes_counts_activations_past_manifest(tmp_path):
    from paddle_tpu import serving

    d = _save_serving_model(str(tmp_path / "m"))
    manifest = serving.manifest_weight_bytes(d)
    plan64 = serving.plan_model_bytes(d, 64)
    assert manifest > 0
    assert plan64 > manifest, \
        "the plan must see activations + feeds the manifest cannot"
    assert serving.plan_model_bytes(d, 256) > plan64  # scales with bucket


def test_serving_budget_refuses_on_plan_bytes_with_warm_buckets(tmp_path):
    """Budget sized between manifest weight bytes and the plan at the warm
    bucket: the manifest-only estimator would admit the load; the plan
    refuses it up front."""
    from paddle_tpu import serving
    from paddle_tpu.errors import ServingError

    d = _save_serving_model(str(tmp_path / "m"))
    manifest = serving.manifest_weight_bytes(d)
    plan = serving.plan_model_bytes(d, 64)
    budget_mb = (manifest + (plan - manifest) * 0.5) / 1e6
    reg = serving.ModelRegistry(place=fluid.CPUPlace(),
                                hbm_budget_mb=budget_mb)
    with pytest.raises(ServingError) as ei:
        reg.load("m", d, warm_buckets=(64,))
    assert ei.value.reason == "hbm_budget"
    # without warm buckets the documented fallback (manifest) admits it
    reg2 = serving.ModelRegistry(place=fluid.CPUPlace(),
                                 hbm_budget_mb=budget_mb)
    reg2.load("m", d)
    assert sorted(reg2.models()) == ["m"]


def test_unbudgeted_load_is_counted_and_evented(tmp_path):
    """The silent HBM-budget bypass, made loud: a model whose pre-load
    estimate is zero (empty/absent manifest, unplannable program) loads
    past FLAGS_serving_hbm_budget_mb unchecked — the registry counts it
    and records the event (fallback order: plan -> manifest -> post-load
    re-check only)."""
    from paddle_tpu import serving

    monitor.reset()
    monitor.enable()
    try:
        d = _save_serving_model(str(tmp_path / "m"))
        # blind both estimators: empty manifest vars + no plannable program
        with open(os.path.join(d, fluid.io.MANIFEST)) as f:
            man = json.load(f)
        man["vars"] = []
        with open(os.path.join(d, fluid.io.MANIFEST), "w") as f:
            json.dump(man, f)
        reg = serving.ModelRegistry(place=fluid.CPUPlace(), hbm_budget_mb=1.0)
        before = monitor.counter("serving.unbudgeted_loads").value
        reg.load("m", d)  # no warm_buckets: plan path not consulted
        assert monitor.counter("serving.unbudgeted_loads").value == before + 1
        evs = [r for r in monitor.step_records()
               if r.get("kind") == "serving_event"
               and r.get("action") == "unbudgeted_load"]
        assert evs and evs[-1]["model"] == "m"
    finally:
        monitor.disable()
        monitor.reset()


# --------------------------------------------------------------------------
# consumer 3: CLI gate (tier-1 wiring)
# --------------------------------------------------------------------------

def _run_cli(*args, timeout=780):
    # single-device env like a standalone CLI run: conftest's 8-virtual-
    # device XLA_FLAGS would change XLA's buffer assignment (the
    # calibration truth) under the multi-device allocator
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["XLA_FLAGS"] = " ".join(
        f for f in env.get("XLA_FLAGS", "").split()
        if "xla_force_host_platform_device_count" not in f)
    return subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "resource_plan.py"),
         *args],
        capture_output=True, text=True, env=env, cwd=REPO, timeout=timeout)


def test_cli_check_zoo_plans_calibrate_within_tolerance():
    """THE acceptance gate: all 5 zoo programs plan cleanly, cost-rule
    coverage holds the floor, and plan peak stays inside the stated
    tolerance band of measured truth (XLA buffer assignment on CPU)."""
    r = _run_cli("--check")
    assert r.returncode == 0, r.stdout + r.stderr
    assert "CHECK OK" in r.stdout
    assert "calibration inside" in r.stdout


def test_cli_coverage_gate_trips_when_floor_unreachable():
    r = _run_cli("--check", "--program", "mnist", "--min-coverage", "1.01",
                 timeout=300)
    assert r.returncode == 1, r.stdout + r.stderr
    assert "coverage" in r.stdout


def test_cli_json_is_one_document_of_plans_and_coverage():
    """`--json` is what another tool reads: one JSON document on stdout, a
    plan a program and the coverage of the cost rules; and the CLI plans
    programs, it reads no bench round and ranks nothing."""
    r = _run_cli("--json", "--program", "mnist", timeout=300)
    assert r.returncode == 0, r.stdout + r.stderr
    doc = json.loads(r.stdout)
    assert sorted(doc) == ["_coverage", "mnist"]
    plan = doc["mnist"]["plan"]
    assert plan["peak_bytes"] >= plan["persistable_bytes"] > 0
    assert plan["roofline_step_s"] > 0 and plan["watermark"]
    assert doc["_coverage"]["frac"] == 1.0 and not doc["_coverage"]["missing_types"]
    for gone in ("--gap-rank", "--bench"):
        r = _run_cli(gone, "x.json", timeout=120)
        assert r.returncode == 2 and "unrecognized arguments" in r.stderr, gone


def test_the_planner_and_the_benchmark_hold_one_chip_model():
    """The library may not import the benchmark, so the planner keeps a
    second copy of the chip's peaks; this holds it to the benchmark's row,
    so that a roofline and a `model_flops_util` are shares of one chip."""
    from benchmark.peaks import PEAKS

    row = PEAKS["TPU v5 lite"]
    assert rp.CHIP_PEAK_FLOPS == row["bf16_flops_per_s"]
    assert rp.CHIP_HBM_BANDWIDTH == row["hbm_bytes_per_s"]
    assert rp.CHIP_HBM_BYTES == row["hbm_bytes"]


# --------------------------------------------------------------------------
# misc: serialized programs, plan dict round-trip
# --------------------------------------------------------------------------

def test_plan_serialized_program_roundtrip():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = layers.data("x", [4], dtype="float32")
        y = layers.relu(x)
    clone = fluid.Program.parse_from_string(main.to_string())
    plan = rp.plan_program(clone, {"x": (2, 4)}, [y.name])
    d = plan.to_dict()
    assert d["peak_bytes"] == plan.peak_bytes
    json.dumps(d)  # JSON-serializable for the CLI --json path
