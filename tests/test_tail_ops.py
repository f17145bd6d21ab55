"""API-tail batch goldens (audit, r3 review #6): numpy transcriptions of the
reference kernels (activation_op.h functors, smooth_l1_loss_op.h,
teacher_student_sigmoid_loss_op.h:26, pixel_shuffle_op.h, shuffle_channel_op.h,
temporal_shift_op.h, fsp_op.h, unfold_op.h, pool_op adaptive path, cvm_op.h,
add_position_encoding_op.h, bilinear_tensor_product_op.h, data_norm_op.cc)."""
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import LoDTensor


def _run1(build, feed, n_fetch=1):
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        fetches = build()
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    exe.run(startup, scope=scope)
    outs = exe.run(main, feed=feed, fetch_list=list(fetches), scope=scope)
    return [np.asarray(o) for o in outs]


RNG = np.random.RandomState(0)
X = (RNG.randn(4, 6) * 3).astype("f4")


@pytest.mark.parametrize("fn,kw,ref", [
    ("brelu", {"t_min": -1.0, "t_max": 2.0}, lambda x: np.clip(x, -1, 2)),
    ("soft_relu", {"threshold": 3.0},
     lambda x: np.log1p(np.exp(np.clip(x, -3, 3)))),
    ("thresholded_relu", {"threshold": 0.5}, lambda x: np.where(x > 0.5, x, 0)),
    ("elu", {"alpha": 0.7},
     lambda x: np.where(x > 0, x, 0.7 * (np.exp(x) - 1))),
    ("hard_sigmoid", {"slope": 0.3, "offset": 0.4},
     lambda x: np.clip(0.3 * x + 0.4, 0, 1)),
    ("stanh", {"scale_a": 0.5, "scale_b": 2.0},
     lambda x: 2.0 * np.tanh(0.5 * x)),
    ("swish", {"beta": 1.5}, lambda x: x / (1 + np.exp(-1.5 * x))),
    ("hard_shrink", {"threshold": 1.0}, lambda x: np.where(np.abs(x) > 1, x, 0)),
    ("softshrink", {},
     lambda x: np.where(x > 0.5, x - 0.5, np.where(x < -0.5, x + 0.5, 0))),
])
def test_unary_goldens(fn, kw, ref):
    def build():
        xv = fluid.layers.data("x", [6], dtype="float32")
        return [getattr(fluid.layers, fn)(xv, **kw)]

    (got,) = _run1(build, {"x": X})
    np.testing.assert_allclose(got, ref(X.astype("f8")), rtol=1e-5, atol=1e-5)


def test_rsqrt_sign_acos_family():
    xp = np.abs(X) + 0.5
    xu = np.clip(X / 10, -0.99, 0.99)

    def build():
        a = fluid.layers.data("a", [6], dtype="float32")
        u = fluid.layers.data("u", [6], dtype="float32")
        return [fluid.layers.rsqrt(a), fluid.layers.sign(a),
                fluid.layers.acos(u), fluid.layers.asin(u),
                fluid.layers.atan(u), fluid.layers.tanh_shrink(a)]

    rs, sg, ac, as_, at, ts = _run1(build, {"a": xp, "u": xu})
    np.testing.assert_allclose(rs, 1 / np.sqrt(xp), rtol=1e-5)
    np.testing.assert_allclose(sg, np.sign(xp), rtol=1e-6)
    np.testing.assert_allclose(ac, np.arccos(xu), rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(as_, np.arcsin(xu), rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(at, np.arctan(xu), rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(ts, xp - np.tanh(xp), rtol=1e-4, atol=1e-5)


def test_logic_and_probes():
    a = np.array([[1, 2], [3, 4]], "f4")
    b = np.array([[1, 3], [3, 3]], "f4")
    bad = np.array([1.0, np.inf, np.nan], "f4")

    def build():
        av = fluid.layers.data("a", [2], dtype="float32")
        bv = fluid.layers.data("b", [2], dtype="float32")
        cv = fluid.layers.data("c", [], dtype="float32")
        xb = fluid.layers.cast(av, "bool")
        yb = fluid.layers.cast(bv - 1.0, "bool")
        return [fluid.layers.less_equal(av, bv),
                fluid.layers.greater_equal(av, bv),
                fluid.layers.not_equal(av, bv),
                fluid.layers.logical_xor(xb, yb),
                fluid.layers.has_inf(cv), fluid.layers.has_nan(cv),
                fluid.layers.isfinite(cv),
                fluid.layers.reduce_all(fluid.layers.cast(av, "bool")),
                fluid.layers.reduce_any(fluid.layers.cast(av - 1.0, "bool"), dim=1)]

    le, ge, ne, lx, hi, hn, isf, ra, ry = _run1(
        build, {"a": a, "b": b, "c": bad})
    assert (le == (a <= b)).all() and (ge == (a >= b)).all()
    assert (ne == (a != b)).all()
    assert (lx == np.logical_xor(a != 0, (b - 1) != 0)).all()
    assert hi[0] and hn[0] and not isf[0]
    assert ra[()] == True  # noqa: E712
    assert (ry == np.any(a - 1 != 0, axis=1)).all()


def test_cos_sim_smooth_l1():
    x = RNG.randn(5, 8).astype("f4")
    y = RNG.randn(5, 8).astype("f4")

    def build():
        xv = fluid.layers.data("x", [8], dtype="float32")
        yv = fluid.layers.data("y", [8], dtype="float32")
        return [fluid.layers.cos_sim(xv, yv),
                fluid.layers.smooth_l1(xv, yv, sigma=2.0)]

    cs, sl = _run1(build, {"x": x, "y": y})
    ref_cs = (x * y).sum(1) / (np.linalg.norm(x, axis=1) * np.linalg.norm(y, axis=1))
    np.testing.assert_allclose(cs.reshape(-1), ref_cs, rtol=1e-4, atol=1e-5)
    s2 = 4.0
    d = (x - y).astype("f8")
    el = np.where(np.abs(d) < 1 / s2, 0.5 * d * d * s2, np.abs(d) - 0.5 / s2)
    np.testing.assert_allclose(sl.reshape(-1), el.sum(1), rtol=1e-4)


def test_teacher_student_sigmoid_loss_golden():
    x = np.array([0.5, -1.2, 2.0, -0.3], "f4").reshape(-1, 1)
    z = np.array([-2.0, -0.5, 0.7, 1.4], "f4").reshape(-1, 1)

    def np_ref(x, z):
        x = x.astype("f8")
        base = np.maximum(x, 0) + np.log1p(np.exp(-np.abs(x)))
        out = np.where(z < -1, base,
                       np.where(z < 0, base - x,
                                np.where(z < 1, 2 * base - x * z,
                                         2 * base - x - x * (z - 1))))
        return out

    def build():
        xv = fluid.layers.data("x", [1], dtype="float32")
        zv = fluid.layers.data("z", [1], dtype="float32")
        return [fluid.layers.teacher_student_sigmoid_loss(xv, zv)]

    (got,) = _run1(build, {"x": x, "z": z})
    np.testing.assert_allclose(got, np_ref(x, z), rtol=1e-5, atol=1e-6)


def test_pixel_shuffle_and_shuffle_channel_and_temporal_shift():
    x = RNG.randn(2, 8, 3, 3).astype("f4")  # r=2 -> [2, 2, 6, 6]
    xt = RNG.randn(6, 8, 2, 2).astype("f4")  # N=3 segs of T=2

    def build():
        xv = fluid.layers.data("x", [8, 3, 3], dtype="float32")
        tv = fluid.layers.data("t", [8, 2, 2], dtype="float32")
        return [fluid.layers.pixel_shuffle(xv, 2),
                fluid.layers.shuffle_channel(xv, 4),
                fluid.layers.temporal_shift(tv, 2, 0.25)]

    ps, sc, tsh = _run1(build, {"x": x, "t": xt})
    ref_ps = x.reshape(2, 2, 2, 2, 3, 3).transpose(0, 1, 4, 2, 5, 3).reshape(2, 2, 6, 6)
    np.testing.assert_allclose(ps, ref_ps)
    ref_sc = x.reshape(2, 4, 2, 3, 3).transpose(0, 2, 1, 3, 4).reshape(2, 8, 3, 3)
    np.testing.assert_allclose(sc, ref_sc)
    v = xt.reshape(3, 2, 8, 2, 2)
    ref_t = np.zeros_like(v)
    ref_t[:, :-1, :2] = v[:, 1:, :2]      # backward shift
    ref_t[:, 1:, 2:4] = v[:, :-1, 2:4]    # forward shift
    ref_t[:, :, 4:] = v[:, :, 4:]
    np.testing.assert_allclose(tsh, ref_t.reshape(6, 8, 2, 2))


def test_fsp_and_unfold():
    x = RNG.randn(2, 3, 4, 5).astype("f4")
    y = RNG.randn(2, 6, 4, 5).astype("f4")

    def build():
        xv = fluid.layers.data("x", [3, 4, 5], dtype="float32")
        yv = fluid.layers.data("y", [6, 4, 5], dtype="float32")
        return [fluid.layers.fsp_matrix(xv, yv),
                fluid.layers.unfold(xv, [3, 3], strides=1, paddings=1)]

    fsp, unf = _run1(build, {"x": x, "y": y})
    ref = np.einsum("bchw,bdhw->bcd", x, y) / 20.0
    np.testing.assert_allclose(fsp, ref, rtol=1e-4, atol=1e-5)
    # im2col reference: [N, C*kh*kw, oh*ow], (c, kh, kw)-major
    xp = np.pad(x, ((0, 0), (0, 0), (1, 1), (1, 1)))
    cols = np.zeros((2, 3, 3, 3, 4, 5), "f4")
    for i in range(3):
        for j in range(3):
            cols[:, :, i, j] = xp[:, :, i:i + 4, j:j + 5]
    np.testing.assert_allclose(unf, cols.reshape(2, 27, 20), rtol=1e-6)


def test_adaptive_pools():
    x = RNG.randn(2, 3, 7, 5).astype("f4")

    def np_adaptive(x, oh, ow, op):
        out = np.zeros(x.shape[:2] + (oh, ow), "f8")
        for i in range(oh):
            for j in range(ow):
                hs, he = (i * 7) // oh, -(-((i + 1) * 7) // oh)
                ws, we = (j * 5) // ow, -(-((j + 1) * 5) // ow)
                blk = x[:, :, hs:he, ws:we]
                out[:, :, i, j] = blk.max((2, 3)) if op == "max" else blk.mean((2, 3))
        return out

    def build():
        xv = fluid.layers.data("x", [3, 7, 5], dtype="float32")
        return [fluid.layers.adaptive_pool2d(xv, [3, 2], "max"),
                fluid.layers.adaptive_pool2d(xv, [3, 2], "avg")]

    mx, av = _run1(build, {"x": x})
    np.testing.assert_allclose(mx, np_adaptive(x, 3, 2, "max"), rtol=1e-5)
    np.testing.assert_allclose(av, np_adaptive(x, 3, 2, "avg"), rtol=1e-5, atol=1e-6)


def test_batch_size_like_and_random_fillers():
    ref = np.zeros((5, 3), "f4")

    def build():
        rv = fluid.layers.data("r", [3], dtype="float32")
        fc = fluid.layers.fill_constant_batch_size_like(rv, [1, 7], "float32", 2.5)
        ur = fluid.layers.uniform_random_batch_size_like(rv, [1, 4], min=0.0, max=1.0)
        gr = fluid.layers.gaussian_random_batch_size_like(rv, [1, 4], mean=5.0, std=0.1)
        u = fluid.layers.uniform_random([6, 2], min=-2.0, max=-1.0)
        g = fluid.layers.gaussian_random([6, 2], mean=3.0, std=0.01)
        s = fluid.layers.sampling_id(fluid.layers.softmax(rv))
        return [fc, ur, gr, u, g, s]

    fc, ur, gr, u, g, s = _run1(build, {"r": ref})
    assert fc.shape == (5, 7) and (fc == 2.5).all()
    assert ur.shape == (5, 4) and (ur >= 0).all() and (ur <= 1).all()
    assert gr.shape == (5, 4) and abs(gr.mean() - 5.0) < 0.5
    assert (u >= -2).all() and (u <= -1).all()
    assert abs(g.mean() - 3.0) < 0.1
    assert s.shape == (5,) and (s >= 0).all() and (s < 3).all()


def test_shape_rank_sum_pad_unstack_range_is_empty():
    a = RNG.randn(3, 4).astype("f4")
    b = RNG.randn(3, 4).astype("f4")

    def build():
        av = fluid.layers.data("a", [4], dtype="float32")
        bv = fluid.layers.data("b", [4], dtype="float32")
        parts = fluid.layers.unstack(av, axis=1)
        return [fluid.layers.shape(av), fluid.layers.rank(av),
                fluid.layers.sum([av, bv]),
                fluid.layers.pad(av, [0, 1, 2, 0], pad_value=9.0),
                parts[1],
                fluid.layers.range(0, 10, 2, "int32"),
                fluid.layers.is_empty(av),
                fluid.layers.pad_constant_like(
                    fluid.layers.data("big", [6], dtype="float32"), av, 7.0)]

    sh, rk, sm, pd, p1, rg, ie, pcl = _run1(
        build, {"a": a, "b": b, "big": np.zeros((4, 6), "f4")})
    assert sh.tolist() == [3, 4] and rk[0] == 2
    np.testing.assert_allclose(sm, a + b, rtol=1e-6)
    assert pd.shape == (4, 6) and (pd[3] == 9.0).all() and (pd[:, :2] == 9.0).all()
    np.testing.assert_allclose(pd[:3, 2:], a, rtol=1e-6)
    np.testing.assert_allclose(p1, a[:, 1], rtol=1e-6)
    assert rg.tolist() == [0, 2, 4, 6, 8]
    assert not ie[0]
    # batch dim is dynamic (-1) at trace time -> unpadded; cols pad to 6
    assert pcl.shape == (3, 6)
    np.testing.assert_allclose(pcl[:, :4], a, rtol=1e-6)
    assert (pcl[:, 4:] == 7.0).all()


def test_add_position_encoding_and_bilinear_and_cvm():
    x = RNG.randn(2, 5, 8).astype("f4")
    cvm_x = np.abs(RNG.randn(4, 6)).astype("f4")
    cvm_sc = np.ones((4, 2), "f4")

    def build():
        xv = fluid.layers.data("x", [5, 8], dtype="float32")
        a = fluid.layers.data("a", [3], dtype="float32")
        b = fluid.layers.data("b", [4], dtype="float32")
        cx = fluid.layers.data("cx", [6], dtype="float32")
        cs = fluid.layers.data("cs", [2], dtype="float32")
        return [fluid.layers.add_position_encoding(xv, 0.5, 2.0),
                fluid.layers.bilinear_tensor_product(a, b, 7),
                fluid.layers.continuous_value_model(cx, cs, True),
                fluid.layers.continuous_value_model(cx, cs, False)]

    feed = {"x": x, "a": RNG.randn(2, 3).astype("f4"),
            "b": RNG.randn(2, 4).astype("f4"), "cx": cvm_x, "cs": cvm_sc}
    pe, btp, cvm1, cvm0 = _run1(build, feed)
    half = 4
    pos = np.arange(5, dtype="f8")[:, None]
    i = np.arange(half, dtype="f8")[None, :]
    ang = pos / np.power(10000.0, i / half)
    enc = np.concatenate([np.sin(ang), np.cos(ang)], axis=1)
    np.testing.assert_allclose(pe, 0.5 * x + 2.0 * enc[None], rtol=1e-4, atol=1e-5)
    assert btp.shape == (2, 7)
    show = np.log(cvm_x[:, 0:1] + 1)
    clk = np.log(cvm_x[:, 1:2] + 1) - show
    np.testing.assert_allclose(cvm1, np.concatenate([show, clk, cvm_x[:, 2:]], 1),
                               rtol=1e-5)
    np.testing.assert_allclose(cvm0, cvm_x[:, 2:], rtol=1e-6)


def test_sequence_reshape_golden():
    rows = [RNG.randn(2, 6).astype("f4"), RNG.randn(3, 6).astype("f4")]

    def build():
        xv = fluid.layers.data("x", [6], dtype="float32", lod_level=1)
        out = fluid.layers.sequence_reshape(xv, 3)
        pooled = fluid.layers.sequence_pool(out, "sum")
        return [out, pooled]

    out, pooled = _run1(build, {"x": LoDTensor(rows)})
    # row 0: 2 tokens * 6 = 12 values -> 4 tokens of 3
    np.testing.assert_allclose(out[0, :4], rows[0].reshape(4, 3), rtol=1e-6)
    np.testing.assert_allclose(out[1, :6], rows[1].reshape(6, 3), rtol=1e-6)
    np.testing.assert_allclose(pooled[0], rows[0].reshape(4, 3).sum(0), rtol=1e-5)


def test_data_norm_trains_stats():
    x = (RNG.randn(32, 5) * 2 + 3).astype("f4")

    def build():
        xv = fluid.layers.data("x", [5], dtype="float32")
        return [fluid.layers.data_norm(xv)]

    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        (y,) = build()
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    exe.run(startup, scope=scope)
    # initial accumulators: size 1e4, sum 0, sqsum 1e4 -> mean 0, scale ~1
    (y1,) = exe.run(main, feed={"x": x}, fetch_list=[y], scope=scope)
    np.testing.assert_allclose(np.asarray(y1), x, rtol=1e-3, atol=1e-3)
    # after many repeats of the same batch the stats converge to the batch's
    for _ in range(3000):
        exe.run(main, feed={"x": x}, fetch_list=[y], scope=scope)
    (y2,) = exe.run(main, feed={"x": x}, fetch_list=[y], scope=scope)
    got = np.asarray(y2)
    np.testing.assert_allclose(got.mean(0), 0.0, atol=0.35)
    np.testing.assert_allclose(got.std(0), 1.0, atol=0.35)


def test_dice_and_npair_losses_composition():
    p = np.abs(RNG.rand(4, 10)).astype("f4")
    lab = (RNG.rand(4, 10) > 0.5).astype("f4")

    def build():
        pv = fluid.layers.data("p", [10], dtype="float32")
        lv = fluid.layers.data("l", [10], dtype="float32")
        anchor = fluid.layers.data("anc", [6], dtype="float32")
        pos = fluid.layers.data("pos", [6], dtype="float32")
        ids = fluid.layers.data("ids", [1], dtype="int64")
        return [fluid.layers.dice_loss(pv, lv),
                fluid.layers.npair_loss(anchor, pos, ids)]

    feed = {"p": p, "l": lab, "anc": RNG.randn(4, 6).astype("f4"),
            "pos": RNG.randn(4, 6).astype("f4"),
            "ids": np.arange(4, dtype="int64").reshape(4, 1)}
    dl, nl = _run1(build, feed)
    inse = (p * lab).sum(1)
    denom = p.sum(1) + lab.sum(1)
    ref = (1 - 2 * inse / (denom + 1e-5)).mean()
    np.testing.assert_allclose(float(dl), ref, rtol=1e-4)
    assert np.isfinite(nl).all()
