"""Dygraph layer fill-in (r3 review #10): GroupNorm / SpectralNorm / NCE /
BilinearTensorProduct / Conv3D / Conv3DTranspose — forward+backward smoke and
static-vs-dygraph parity where a static op exists."""
import numpy as np

import paddle_tpu as fluid
from paddle_tpu import dygraph


def test_group_norm_static_parity():
    rng = np.random.RandomState(0)
    x = rng.randn(2, 8, 4, 4).astype("f4")

    with dygraph.guard():
        gn = dygraph.GroupNorm(8, groups=4)
        dy = gn(dygraph.to_variable(x)).numpy()

    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        xv = fluid.layers.data("x", [8, 4, 4], dtype="float32")
        out = fluid.layers.group_norm(xv, groups=4)
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    exe.run(startup, scope=scope)
    (st,) = exe.run(main, feed={"x": x}, fetch_list=[out], scope=scope)
    np.testing.assert_allclose(dy, np.asarray(st), rtol=1e-4, atol=1e-5)


def test_spectral_norm_normalizes():
    rng = np.random.RandomState(1)
    w = (rng.randn(6, 10) * 3).astype("f4")
    with dygraph.guard():
        sn = dygraph.SpectralNorm([6, 10], power_iters=20)
        out = sn(dygraph.to_variable(w)).numpy()
    # spectral norm of the output ~ 1
    s = np.linalg.svd(out, compute_uv=False)[0]
    np.testing.assert_allclose(s, 1.0, rtol=5e-2)


def test_nce_trains():
    rng = np.random.RandomState(2)
    with dygraph.guard():
        nce = dygraph.NCE(num_total_classes=50, dim=8, num_neg_samples=5)
        opt = fluid.optimizer.SGD(0.1)
        x = dygraph.to_variable(rng.randn(16, 8).astype("f4"))
        lab = dygraph.to_variable(rng.randint(0, 50, (16, 1)).astype("int64"))
        losses = []
        for _ in range(30):
            cost = fluid.layers.mean(nce(x, lab))
            cost.backward()
            opt.minimize(cost, parameter_list=nce.parameters())
            nce.clear_gradients()
            losses.append(float(cost.numpy().reshape(-1)[0]))
        assert losses[-1] < losses[0], (losses[0], losses[-1])


def test_bilinear_tensor_product_parity():
    rng = np.random.RandomState(3)
    x = rng.randn(4, 3).astype("f4")
    y = rng.randn(4, 5).astype("f4")
    with dygraph.guard():
        btp = dygraph.BilinearTensorProduct(3, 5, 7)
        out = btp(dygraph.to_variable(x), dygraph.to_variable(y))
        w = np.asarray(btp.weight.value)
        b = np.asarray(btp.bias.value)
        got = out.numpy()
    ref = np.einsum("nd,kde,ne->nk", x, w, y) + b
    np.testing.assert_allclose(got, ref, rtol=1e-4, atol=1e-5)


def test_conv3d_layers_forward_backward():
    rng = np.random.RandomState(4)
    with dygraph.guard():
        c3 = dygraph.Conv3D(2, 4, 3, stride=1, padding=1)
        x = dygraph.to_variable(rng.rand(1, 2, 5, 5, 5).astype("f4"))
        y = c3(x)
        assert y.numpy().shape == (1, 4, 5, 5, 5)
        ct3 = dygraph.Conv3DTranspose(4, 2, 3, stride=2, padding=1)
        z = ct3(y)
        assert z.numpy().shape == (1, 2, 9, 9, 9)
        loss = fluid.layers.mean(z)
        loss.backward()
        assert np.isfinite(c3.parameters()[0].gradient()).all()


def test_conv3d_transpose_static_matches_dygraph():
    rng = np.random.RandomState(5)
    x = rng.rand(2, 3, 4, 4, 4).astype("f4")

    with dygraph.guard():
        ct = dygraph.Conv3DTranspose(3, 5, 3, stride=2, padding=1)
        w = np.asarray(ct.weight.value)
        dy = ct(dygraph.to_variable(x)).numpy()

    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        xv = fluid.layers.data("x", [3, 4, 4, 4], dtype="float32")
        out = fluid.layers.conv3d_transpose(
            xv, 5, filter_size=3, stride=2, padding=1,
            param_attr=fluid.ParamAttr(name="ct_w"), bias_attr=False)
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    exe.run(startup, scope=scope)
    scope.set_var("ct_w", w)
    (st,) = exe.run(main, feed={"x": x}, fetch_list=[out], scope=scope)
    np.testing.assert_allclose(dy, np.asarray(st), rtol=1e-4, atol=1e-5)
