"""PTQ/QAT int8 inference pipeline (r4 review #8).

Reference chain being mirrored: slim QAT (fake-quant instrumentation) ->
QuantizationFreezePass -> mkldnn_quantizer-style deployable int8 model ->
AnalysisConfig/AnalysisPredictor serving with ZeroCopyTensor handles.
"""
import os

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import layers, optimizer
from paddle_tpu.contrib import slim
from paddle_tpu.contrib.slim.quantization import convert_quant_model
from paddle_tpu.core.program import Program, program_guard
from paddle_tpu.inference import AnalysisConfig, Predictor, create_predictor


def _build_net():
    main, startup = Program(), Program()
    with program_guard(main, startup):
        x = layers.data("x", [3, 8, 8], dtype="float32")
        c = layers.conv2d(x, num_filters=8, filter_size=3, padding=1, act="relu")
        p = layers.pool2d(c, pool_size=2, pool_stride=2)
        flat = layers.reshape(p, [-1, 8 * 4 * 4])
        out = layers.fc(flat, 10, act="softmax")
    return main, startup, x, out


def _train_and_save(tmpdir, quantized, qat=False):
    main, startup, x, out = _build_net()
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    startup.random_seed = 3
    exe.run(startup, scope=scope)
    if qat:
        # weight-only QAT: the deployed model drops activation fake-quants,
        # so only weight quantization survives into serving — instrument
        # what deployment keeps and the parity check below can be tight
        n = slim.quant_aware(main, weight_bits=8, quantize_activations=False)
        assert n > 0
    xv = np.random.RandomState(0).rand(4, 3, 8, 8).astype("f4")
    (ref,) = exe.run(main, feed={"x": xv}, fetch_list=[out], scope=scope)
    d = os.path.join(tmpdir, "q" if quantized else "f")
    if quantized:
        fluid.io.save_quantized_inference_model(d, ["x"], [out], exe, main, scope)
    else:
        fluid.io.save_inference_model(d, ["x"], [out], exe, main, scope)
    return d, xv, np.asarray(ref)


def test_convert_strips_fake_quant_and_snaps_weights():
    main, startup, x, out = _build_net()
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    exe.run(startup, scope=scope)
    n = slim.quant_aware(main, weight_bits=8)
    assert n > 0
    types_before = [op.type for op in main.global_block().ops]
    assert any(t.startswith("fake_quantize") for t in types_before)
    manifest = convert_quant_model(main, scope, weight_bits=8)
    types_after = [op.type for op in main.global_block().ops]
    assert not any(t.startswith("fake_quantize") for t in types_after)
    assert manifest["weights"]
    # snapped weights sit exactly on the int8 grid (per-tensor scales here)
    for wname, rec in manifest["weights"].items():
        if rec["axis"] is not None:
            continue
        w = np.asarray(scope.find_var(wname))
        q = w / np.float32(rec["scale"]) * 127
        assert np.allclose(q, np.round(q), atol=1e-3)


def test_quantized_model_roundtrip_parity(tmp_path):
    d, xv, ref = _train_and_save(str(tmp_path), quantized=True)
    # int8 payloads on disk
    import json
    qman = json.load(open(os.path.join(d, "__quant__.json")))
    assert qman["weights"]
    for wname in qman["weights"]:
        arr = np.load(os.path.join(d, wname.replace("/", "%2F") + ".npy"))
        assert arr.dtype == np.int8
    cfg = AnalysisConfig(d, place=fluid.CPUPlace())
    pred = create_predictor(cfg)
    (got,) = pred.run({"x": xv})
    # documented tolerance: int8 weight grid on a small conv net
    assert np.allclose(got, ref, atol=0.05), np.abs(got - ref).max()
    # probabilities still sum to 1
    np.testing.assert_allclose(np.asarray(got).sum(-1), 1.0, rtol=1e-4)


def test_quantized_vs_float_predictor_close(tmp_path):
    df, xv, ref_f = _train_and_save(str(tmp_path), quantized=False)
    dq, _, _ = _train_and_save(str(tmp_path), quantized=True)
    pf = Predictor(AnalysisConfig(df, place=fluid.CPUPlace()))
    pq = Predictor(AnalysisConfig(dq, place=fluid.CPUPlace()))
    (a,) = pf.run({"x": xv})
    (b,) = pq.run({"x": xv})
    assert np.allclose(a, b, atol=0.05), np.abs(np.asarray(a) - np.asarray(b)).max()


def test_qat_to_deployed_int8(tmp_path):
    d, xv, ref = _train_and_save(str(tmp_path), quantized=True, qat=True)
    pred = Predictor(AnalysisConfig(d, place=fluid.CPUPlace()))
    (got,) = pred.run({"x": xv})
    # the QAT forward already saw the quantization error, so deploy matches
    # the instrumented program tightly
    assert np.allclose(got, ref, atol=1e-3), np.abs(got - ref).max()


def test_zero_copy_handles(tmp_path):
    d, xv, ref = _train_and_save(str(tmp_path), quantized=True)
    pred = Predictor(AnalysisConfig(d, place=fluid.CPUPlace()))
    assert pred.get_input_names() == ["x"]
    pred.get_input_handle("x").copy_from_cpu(xv)
    assert pred.run_zero_copy()
    out_h = pred.get_output_handle(pred.get_output_names()[0])
    got = out_h.copy_to_cpu()
    assert np.allclose(got, ref, atol=0.05)
    # device-resident pass-through: share a jax array, no host copy
    import jax.numpy as jnp
    pred.get_input_handle("x").share_external_data(jnp.asarray(xv))
    assert pred.run_zero_copy()
    got2 = out_h.copy_to_cpu()
    np.testing.assert_allclose(got, got2, rtol=1e-5)


def test_analysis_config_surface(tmp_path):
    d, _, _ = _train_and_save(str(tmp_path), quantized=False)
    cfg = (AnalysisConfig(d).disable_tpu().switch_ir_optim(False)
           .enable_memory_optim().set_cpu_math_library_num_threads(4)
           .enable_quantize())
    s = cfg.summary()
    assert s["place"] == "CPUPlace" and s["threads"] == 4
    p = Predictor(cfg)
    c = p.clone()
    assert c.scope is p.scope  # shared weights


def test_channel_wise_square_weight_axis(tmp_path):
    """Regression (r5 review): a SQUARE matmul weight with channel-wise
    scales must carry its quant_axis through save/load explicitly —
    shape-matching inference would pick the wrong axis and wrap int8."""
    main, startup = Program(), Program()
    with program_guard(main, startup):
        x = layers.data("x", [16], dtype="float32")
        h = layers.fc(x, 16, param_attr=fluid.ParamAttr(name="sq.w"),
                      bias_attr=False)  # 16x16 square weight
        out = layers.fc(h, 4, act="softmax")
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    startup.random_seed = 9
    exe.run(startup, scope=scope)
    # make per-column magnitudes very different so a wrong axis is loud
    w = np.asarray(scope.find_var("sq.w")).copy()
    w *= np.geomspace(0.01, 10.0, 16)[None, :]
    scope.set_var("sq.w", w.astype("f4"))
    from paddle_tpu.contrib.slim import quant_aware
    quant_aware(main, weight_bits=8, quantize_activations=False,
                weight_quantize_type="channel_wise_abs_max")
    xv = np.random.RandomState(1).rand(8, 16).astype("f4")
    (ref,) = exe.run(main, feed={"x": xv}, fetch_list=[out], scope=scope)
    d = str(tmp_path / "sq")
    fluid.io.save_quantized_inference_model(d, ["x"], [out], exe, main, scope)
    pred = Predictor(AnalysisConfig(d, place=fluid.CPUPlace()))
    (got,) = pred.run({"x": xv})
    assert np.allclose(got, np.asarray(ref), atol=1e-3), \
        np.abs(np.asarray(got) - np.asarray(ref)).max()


def test_quant_save_leaves_training_scope_bit_identical(tmp_path):
    """Regression (ISSUE 19 satellite, fix from r17): the quant passes
    snap weights to the int8 grid via scope.set_var while SAVING; the
    live training scope must be restored bit-identically afterwards —
    an online-learning loop keeps training this scope between publishes,
    so a silent int8 snap would poison every step after the first save."""
    main, startup, x, out = _build_net()
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    startup.random_seed = 11
    exe.run(startup, scope=scope)
    xv = np.random.RandomState(2).rand(4, 3, 8, 8).astype("f4")
    (ref,) = exe.run(main, feed={"x": xv}, fetch_list=[out], scope=scope)
    before = {n: np.asarray(scope.find_var(n)).copy()
              for n in scope.local_var_names()}
    fluid.io.save_quantized_inference_model(
        str(tmp_path / "q"), ["x"], [out], exe, main, scope)
    after_names = set(scope.local_var_names())
    assert after_names == set(before), \
        f"quant save changed the scope's var set: {after_names ^ set(before)}"
    for n, b in before.items():
        a = np.asarray(scope.find_var(n))
        assert a.dtype == b.dtype, n
        np.testing.assert_array_equal(a, b, err_msg=f"var {n!r} mutated")
    # and the float forward pass still reproduces bit-identically
    (again,) = exe.run(main, feed={"x": xv}, fetch_list=[out], scope=scope)
    np.testing.assert_array_equal(np.asarray(again), np.asarray(ref))
