"""Mixed-precision master-weight + optimizer-state regression tests.

r5 chip round find: bf16 models created bf16 parameters, whose
bf16 Adam beta-pow accumulators rounded 0.999 -> 1.0, making the bias-
corrected lr identically zero — bf16+Adam parameters silently never
trained (the r4 BERT bench trained only its f32 embedding/LN params).
Reference contract being pinned: mixed-precision training keeps f32 master
weights + f32 optimizer state (contrib/mixed_precision/decorator.py role).
"""
import numpy as np

import paddle_tpu as fluid
from paddle_tpu import layers, optimizer
from paddle_tpu.core.program import Program, program_guard


def _tiny_bf16_net():
    main, startup = Program(), Program()
    with program_guard(main, startup):
        x = layers.data("x", [8], dtype="float32")
        y = layers.data("y", [1], dtype="float32")
        xb = layers.cast(x, "bfloat16")
        h = layers.fc(xb, 16, act="relu", param_attr=fluid.ParamAttr(name="w1"))
        o = layers.fc(h, 1, param_attr=fluid.ParamAttr(name="w2"))
        loss = layers.mean(layers.square_error_cost(layers.cast(o, "float32"), y))
        optimizer.Adam(learning_rate=1e-2).minimize(loss)
    return main, startup, loss


def test_bf16_params_are_f32_masters():
    main, _, _ = _tiny_bf16_net()
    block = main.global_block()
    assert str(block.var("w1").dtype) in ("float32", "fp32")
    assert str(block.var("w2").dtype) in ("float32", "fp32")


def test_bf16_adam_actually_trains():
    main, startup, loss = _tiny_bf16_net()
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    exe.run(startup, scope=scope)
    rng = np.random.RandomState(0)
    xv = rng.rand(64, 8).astype("f4")
    yv = (xv.sum(1, keepdims=True) > 4).astype("f4")
    w0 = np.asarray(scope.find_var("w1")).copy()
    losses = []
    for _ in range(50):
        (lv,) = exe.run(main, feed={"x": xv, "y": yv}, fetch_list=[loss], scope=scope)
        losses.append(float(np.asarray(lv).reshape(-1)[0]))
    w1 = np.asarray(scope.find_var("w1"))
    assert np.abs(w1 - w0).max() > 1e-4, "params froze (the r4 bf16+Adam bug)"
    assert losses[-1] < losses[0] * 0.6, (losses[0], losses[-1])


def test_beta_pow_accumulators_are_f32():
    main, startup, _ = _tiny_bf16_net()
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    exe.run(startup, scope=scope)
    names = [n for n in scope.var_names() if "beta1_pow" in n or "beta2_pow" in n]
    assert names, "no beta pow accumulators found"
    for n in names:
        v = np.asarray(scope.find_var(n))
        assert v.dtype == np.float32, (n, v.dtype)
        # the fatal symptom: bf16(0.999) == 1.0 exactly
        assert 0.0 < float(v.reshape(-1)[0]) < 1.0


def test_dygraph_params_are_f32_masters():
    import paddle_tpu.dygraph as dg

    with dg.guard():
        fc = dg.nn.Linear(4, 4, dtype="bfloat16")
        for p in fc.parameters():
            assert str(np.asarray(p.numpy()).dtype) == "float32", p.name
