"""Core executor tests: feed/fetch, startup init, persistable state."""
import numpy as np

import paddle_tpu as fluid


def test_feed_fetch_arithmetic():
    x = fluid.layers.data("x", [3], dtype="float32")
    y = fluid.layers.data("y", [3], dtype="float32")
    z = fluid.layers.elementwise_add(x, y)
    exe = fluid.Executor(fluid.CPUPlace())
    xv = np.random.rand(4, 3).astype("float32")
    yv = np.random.rand(4, 3).astype("float32")
    (out,) = exe.run(feed={"x": xv, "y": yv}, fetch_list=[z])
    np.testing.assert_allclose(out, xv + yv, rtol=1e-6)


def test_scalar_sugar():
    x = fluid.layers.data("x", [2], dtype="float32")
    y = (x * 2.0 + 1.0) / 2.0
    exe = fluid.Executor(fluid.CPUPlace())
    xv = np.ones((1, 2), dtype="float32")
    (out,) = exe.run(feed={"x": xv}, fetch_list=[y])
    np.testing.assert_allclose(out, (xv * 2 + 1) / 2)


def test_startup_initialization_persists():
    main = fluid.Program()
    startup = fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data("x", [4], dtype="float32")
        out = fluid.layers.fc(x, size=8, bias_attr=fluid.ParamAttr(
            initializer=fluid.initializer.Constant(0.5)))
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    scope = fluid.global_scope()
    params = [p.name for p in main.all_parameters()]
    assert len(params) == 2
    for p in params:
        assert scope.has_var(p)
    bias = [p for p in main.all_parameters() if p.shape == (8,)][0]
    np.testing.assert_allclose(scope.to_numpy(bias.name), np.full((8,), 0.5), rtol=1e-6)
    (out_v,) = exe.run(main, feed={"x": np.zeros((2, 4), dtype="float32")}, fetch_list=[out])
    np.testing.assert_allclose(out_v, np.full((2, 8), 0.5), rtol=1e-6)


def test_fetch_multiple_and_cache():
    x = fluid.layers.data("x", [2], dtype="float32")
    a = fluid.layers.relu(x)
    b = fluid.layers.reduce_sum(x)
    exe = fluid.Executor(fluid.CPUPlace())
    xv = np.array([[-1.0, 2.0]], dtype="float32")
    outs = exe.run(feed={"x": xv}, fetch_list=[a, b])
    np.testing.assert_allclose(outs[0], [[0.0, 2.0]])
    np.testing.assert_allclose(outs[1], 1.0)
    # second run hits the executable cache
    outs2 = exe.run(feed={"x": xv}, fetch_list=[a, b])
    np.testing.assert_allclose(outs2[0], outs[0])


def test_program_serialization_roundtrip():
    main = fluid.Program()
    startup = fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data("x", [4], dtype="float32")
        y = fluid.layers.fc(x, size=3, act="relu")
    d = main.to_dict()
    import json

    restored = fluid.Program.from_dict(json.loads(json.dumps(d)))
    assert [op.type for op in restored.global_block().ops] == [
        op.type for op in main.global_block().ops
    ]
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    xv = np.random.rand(2, 4).astype("float32")
    (a,) = exe.run(main, feed={"x": xv}, fetch_list=[y])
    (b,) = exe.run(restored, feed={"x": xv}, fetch_list=[y.name])
    np.testing.assert_allclose(a, b, rtol=1e-6)


def test_rng_advances_between_runs():
    out = fluid.layers.data("x", [2], dtype="float32")
    d = fluid.layers.dropout(out, dropout_prob=0.5)
    exe = fluid.Executor(fluid.CPUPlace())
    xv = np.ones((100, 2), dtype="float32")
    (a,) = exe.run(feed={"x": xv}, fetch_list=[d])
    (b,) = exe.run(feed={"x": xv}, fetch_list=[d])
    assert not np.array_equal(a, b)


def test_calc_gradient_multi_target():
    """Review weak-item regression: calc_gradient over several targets
    (gradient of the summed targets, reference backward.py:672)."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data("x", [3], dtype="float32")
        t1 = fluid.layers.scale(x, scale=2.0)     # d sum(t1)/dx = 2
        t2 = fluid.layers.scale(x, scale=5.0)     # d sum(t2)/dx = 5
        grads = fluid.calc_gradient([t1, t2], [x])
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    exe.run(startup, scope=scope)
    xv = np.ones((2, 3), "float32")
    (g,) = exe.run(main, feed={"x": xv}, fetch_list=[grads[0]], scope=scope)
    np.testing.assert_allclose(g, np.full((2, 3), 7.0), atol=1e-6)


def test_calc_gradient_mixed_none_target_gradients():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data("x", [3], dtype="float32")
        t1 = fluid.layers.scale(x, scale=2.0)
        t2 = fluid.layers.scale(x, scale=5.0)
        tg = fluid.layers.fill_constant([3], "float32", 3.0)
        grads = fluid.calc_gradient([t1, t2], [x], target_gradients=[tg, None])
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    exe.run(startup, scope=scope)
    (g,) = exe.run(main, feed={"x": np.ones((2, 3), "float32")},
                   fetch_list=[grads[0]], scope=scope)
    np.testing.assert_allclose(g, np.full((2, 3), 2 * 3 + 5.0), atol=1e-6)
