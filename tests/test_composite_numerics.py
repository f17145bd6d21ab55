"""The one lowering each of five ops has, held to float64 numpy directly (ISSUE 58).

Until PR 58 `layer_norm`, `batch_norm`, `softmax_with_cross_entropy`, `adam` and
`elementwise_add` each had a second lowering behind a flag a user set, and the
parity tests held the KERNELS to the composites.  The kernels went; what those
tests pinned down about the numbers is asked of the path that stays: each op run
through `Executor` on a small program, forward and gradient, float32 and
bfloat16, against a reference written here in numpy at float64 (on the inputs as
the op's dtype holds them).  What an older test already holds is left to it:
`tests/test_ops_golden.py` has float32 `layer_norm` forward (with its statistics)
and by finite differences, float32 `softmax_with_cross_entropy` without ignored
rows and one float32 `adam` step; `tests/test_lowering_one_path.py` has
`batch_norm`'s statistics by dtype.
"""
import math

import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import layers

EPS = 1e-5
#: dtype -> the tolerance, relative to the reference's largest magnitude: float32 is a few ulps of the sums; bfloat16
#: is what two bf16 formulations of one chain may differ by (chip_smoke.py's BF16_TOL)
TOL = {"float32": 1e-5, "bfloat16": 5e-2}


def _held(x, dtype):
    """`x` as `dtype` holds it, in float64."""
    return np.asarray(jnp.asarray(x, jnp.float32).astype(dtype), np.float64)


def _close(got, want, dtype, what):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    assert got.shape == want.shape, (what, got.shape, want.shape)
    tol = TOL[dtype] * max(np.abs(want).max(), 1.0)
    assert np.abs(got - want).max() <= tol, (what, np.abs(got - want).max(), tol)


class _Program:
    """A program of ops appended by hand over fed variables: `data` declares a float32 (or int64) feed and hands it on
    in `dtype`; `op` appends one op and returns its outputs; `run` fetches `outs` and the gradients of
    sum(cast(loss_of, float32) * weights) by `wrt`."""

    def __init__(self):
        self.main, self.startup, self.feed = fluid.Program(), fluid.Program(), {}
        self.guard = fluid.program_guard(self.main, self.startup)
        self.guard.__enter__()
        self.block = self.main.global_block()

    def data(self, name, value, dtype="float32"):
        value = np.asarray(value)
        self.feed[name] = value
        v = self.block.create_var(name, shape=value.shape, dtype=str(value.dtype), is_data=True)
        return v if dtype == str(value.dtype) else layers.cast(v, dtype)

    def op(self, op_type, inputs, outputs, attrs=None, dtype="float32"):
        made = {slot: self.block.create_var(fluid.unique_name.generate(f"{op_type}_{slot}"), dtype=dtype)
                for slot in outputs}
        self.block.append_op(op_type, inputs={k: [v.name] for k, v in inputs.items()},
                             outputs={k: [v.name] for k, v in made.items()}, attrs=attrs or {})
        return [made[slot] for slot in outputs]

    def run(self, outs, loss_of=None, weights=None, wrt=()):
        grads = []
        if loss_of is not None:
            loss = layers.reduce_sum(layers.cast(loss_of, "float32") * self.data("weights", weights))
            grads = fluid.calc_gradient(loss, [self.block.var(n) for n in wrt])
        self.guard.__exit__(None, None, None)
        exe, scope = fluid.Executor(fluid.CPUPlace()), fluid.Scope()
        exe.run(self.startup, scope=scope)
        got = exe.run(self.main, feed=self.feed, fetch_list=list(outs) + list(grads), scope=scope)
        return [np.asarray(a, np.float64) for a in got]


# --- layer_norm --------------------------------------------------------------------------------------------------

def _ln_reference(x, scale, bias, dy=None):
    mean, var = x.mean(-1, keepdims=True), x.var(-1, keepdims=True)
    rstd = 1.0 / np.sqrt(var + EPS)
    xhat = (x - mean) * rstd
    y = xhat * scale + bias
    if dy is None:
        return y, mean[..., 0], var[..., 0]
    g = dy * scale
    dx = rstd * (g - g.mean(-1, keepdims=True) - xhat * (g * xhat).mean(-1, keepdims=True))
    lead = tuple(range(x.ndim - 1))
    return dx, (dy * xhat).sum(lead), dy.sum(lead)


def _layer_norm(dtype, gradient, offset=0.0, shape=(6, 10, 64)):
    r = np.random.RandomState(58)
    x = (r.randn(*shape) + offset).astype("float32")
    scale, bias, w = (r.rand(shape[-1]) + 0.5).astype("float32"), (0.1 * r.randn(shape[-1])).astype("float32"), r.randn(*shape).astype("float32")
    p = _Program()
    y, mean, var = p.op("layer_norm", {"X": p.data("x", x, dtype), "Scale": p.data("scale", scale), "Bias": p.data("bias", bias)},
                        ("Y", "Mean", "Variance"), {"epsilon": EPS, "begin_norm_axis": len(shape) - 1}, dtype)
    seen = _held(x, dtype)
    # the lowering multiplies the normalised rows in their own dtype: scale and bias as that dtype holds them
    seen_scale, seen_bias = _held(scale, dtype), _held(bias, dtype)
    if gradient:
        got = p.run([], y, w, ("x", "scale", "bias"))
        for g, want, what in zip(got, _ln_reference(seen, seen_scale, seen_bias, w.astype(np.float64)), ("dX", "dScale", "dBias")):
            _close(g, want, dtype, what)
        return
    got_y, got_mean, got_var = p.run([y, mean, var])
    want_y, want_mean, want_var = _ln_reference(seen, seen_scale, seen_bias)
    _close(got_y, want_y, dtype, "Y")
    # the statistics are float32 sums whatever the rows' dtype: an offset of 300, where bf16 is 2 apart, does not
    # show in them (a mean taken in bf16 would be 300 or 302 and the rows off by a whole deviation)
    assert got_mean.shape == shape[:-1] and np.abs(got_mean - want_mean).max() <= 1e-4 * max(abs(offset), 1.0)
    assert np.abs(got_var - want_var).max() <= 1e-3 * want_var.max()


# --- batch_norm, then relu ---------------------------------------------------------------------------------------

def _bn_relu_reference(x, scale, bias, dy=None, live=None):
    axes, per = (0, 2, 3), (1, -1, 1, 1)
    mean, var = x.mean(axes, keepdims=True), x.var(axes, keepdims=True)
    rstd = 1.0 / np.sqrt(var + EPS)
    xhat = (x - mean) * rstd
    y = np.maximum(xhat * scale.reshape(per) + bias.reshape(per), 0.0)
    if dy is None:
        return y
    dz = dy * live      # where the program's own output is over zero: a row at the kink may round to either side
    dx = scale.reshape(per) * rstd * (dz - dz.mean(axes, keepdims=True) - xhat * (dz * xhat).mean(axes, keepdims=True))
    return dx, (dz * xhat).sum(axes), dz.sum(axes)


def _batch_norm_relu(dtype, gradient):
    r = np.random.RandomState(59)
    shape = (8, 16, 6, 6)
    x, w = r.randn(*shape).astype("float32"), r.randn(*shape).astype("float32")
    scale, bias = (r.rand(16) + 0.5).astype("float32"), (0.3 * r.randn(16)).astype("float32")
    p = _Program()
    ins = {"X": p.data("x", x, dtype), "Scale": p.data("scale", scale), "Bias": p.data("bias", bias),
           "Mean": p.data("mean", np.zeros(16, "float32")), "Variance": p.data("variance", np.ones(16, "float32"))}
    (z,) = p.op("batch_norm", ins, ("Y", "MeanOut", "VarianceOut", "SavedMean", "SavedVariance"),
                {"epsilon": EPS, "momentum": 0.9, "is_test": False, "data_layout": "NCHW"}, dtype)[:1]
    (y,) = p.op("relu", {"X": z}, ("Out",), dtype=dtype)
    seen = _held(x, dtype)
    if gradient:
        out, *got = p.run([y], y, w, ("x", "scale", "bias"))
        for g, want, what in zip(got, _bn_relu_reference(seen, scale, bias, w.astype(np.float64), out > 0), ("dX", "dScale", "dBias")):
            _close(g, want, dtype, what)
        return
    _close(p.run([y])[0], _bn_relu_reference(seen, scale.astype(np.float64), bias.astype(np.float64)), dtype, "relu(Y)")


# --- softmax_with_cross_entropy with ignored rows ----------------------------------------------------------------

def _cross_entropy(dtype, gradient):
    r = np.random.RandomState(60)
    rows, vocab = 48, 96
    logits = (2.0 * r.randn(rows, vocab)).astype("float32")
    label = r.randint(0, vocab, (rows, 1)).astype("int64")
    ignored = np.arange(rows) % 5 == 2
    label[ignored] = -100
    w = (r.rand(rows, 1) + 0.5).astype("float32")
    p = _Program()
    (loss,) = p.op("softmax_with_cross_entropy", {"Logits": p.data("logits", logits, dtype), "Label": p.data("label", label, "int64")},
                   ("Loss", "Softmax"), {"ignore_index": -100}, dtype)[:1]
    seen = _held(logits, dtype)
    e = np.exp(seen - seen.max(-1, keepdims=True))
    softmax = e / e.sum(-1, keepdims=True)
    picked = np.where(ignored, 0, label[:, 0])
    want = np.where(ignored, 0.0, -np.log(softmax[np.arange(rows), picked]))[:, None]
    if gradient:
        (got,) = p.run([], loss, w, ("logits",))
        onehot = np.arange(vocab)[None, :] == label
        _close(got, np.where(ignored[:, None], 0.0, (softmax - onehot) * w), dtype, "dLogits")
        assert not got[ignored].any()       # exactly nothing flows back through a row that is ignored
        return
    (got,) = p.run([loss])
    _close(got, want, "float32", "Loss")     # float32 sums of the rows' own values: the loss is float32 for bf16 logits too
    assert not got[ignored].any()


# --- adam over three steps ---------------------------------------------------------------------------------------

def _adam(dtype, gradient):
    r = np.random.RandomState(61)
    w0 = r.randn(7, 33).astype("float32")
    targets = [r.randn(7, 33).astype("float32") for _ in range(3)]
    lr, b1, b2, eps = 0.05, 0.9, 0.95, 1e-8
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        w = layers.create_parameter([7, 33], "float32", name="w", default_initializer=fluid.initializer.NumpyArrayInitializer(w0))
        t = layers.data("t", [7, 33], append_batch_size=False)
        loss = layers.reduce_sum(layers.square(w - t)) * 0.5
        fluid.optimizer.Adam(lr, beta1=b1, beta2=b2, epsilon=eps).minimize(loss)
    exe, scope = fluid.Executor(fluid.CPUPlace()), fluid.Scope()
    exe.run(startup, scope=scope)
    want, m, v = w0.astype(np.float64), 0.0, 0.0
    for step, target in enumerate(targets, 1):
        exe.run(main, feed={"t": target}, fetch_list=[loss], scope=scope)
        g = want - target
        m, v = b1 * m + (1 - b1) * g, b2 * v + (1 - b2) * g * g
        want = want - lr * np.sqrt(1 - b2 ** step) / (1 - b1 ** step) * m / (np.sqrt(v) + eps)
        _close(scope.find_var("w"), want, "float32", f"w after step {step}")


# --- elementwise_add, then an activation -------------------------------------------------------------------------

_erf = np.vectorize(math.erf)


def _bias_act(act):
    def case(dtype, gradient):
        r = np.random.RandomState(62)
        x, b, w = r.randn(40, 72).astype("float32"), (0.1 * r.randn(72)).astype("float32"), r.randn(40, 72).astype("float32")
        p = _Program()
        (z,) = p.op("elementwise_add", {"X": p.data("x", x, dtype), "Y": p.data("b", b)}, ("Out",), {"axis": -1}, dtype)
        (y,) = p.op(act, {"X": z}, ("Out",), dtype=dtype)
        s = _held(x, dtype) + _held(b, dtype)       # the bias is cast to the rows' dtype before the add
        if act == "relu":
            want, slope = np.maximum(s, 0.0), (s > 0).astype(np.float64)
        else:
            cdf = 0.5 * (1.0 + _erf(s / np.sqrt(2.0)))
            want, slope = s * cdf, cdf + s * np.exp(-0.5 * s * s) / np.sqrt(2.0 * np.pi)
        if gradient:
            dx, db = p.run([], y, w, ("x", "b"))
            _close(dx, w * slope, dtype, "dX")
            _close(db, (w * slope).sum(0), dtype, "dY")
            return
        _close(p.run([y])[0], want, dtype, "Out")
    return case


CASES = {
    "layer_norm-bfloat16-forward": (_layer_norm, "bfloat16", False),
    "layer_norm-bfloat16-rows_of_mean_300-forward": (lambda dtype, gradient: _layer_norm(dtype, gradient, offset=300.0), "bfloat16", False),
    "layer_norm-float32-last_axis_of_three-forward": (_layer_norm, "float32", False),
    "layer_norm-float32-gradient": (_layer_norm, "float32", True),
    "layer_norm-bfloat16-gradient": (_layer_norm, "bfloat16", True),
    "batch_norm_relu-float32-forward": (_batch_norm_relu, "float32", False),
    "batch_norm_relu-bfloat16-forward": (_batch_norm_relu, "bfloat16", False),
    "batch_norm_relu-float32-gradient": (_batch_norm_relu, "float32", True),
    "batch_norm_relu-bfloat16-gradient": (_batch_norm_relu, "bfloat16", True),
    "softmax_with_cross_entropy-ignored_rows-float32-forward": (_cross_entropy, "float32", False),
    "softmax_with_cross_entropy-ignored_rows-bfloat16-forward": (_cross_entropy, "bfloat16", False),
    "softmax_with_cross_entropy-ignored_rows-float32-gradient": (_cross_entropy, "float32", True),
    "softmax_with_cross_entropy-ignored_rows-bfloat16-gradient": (_cross_entropy, "bfloat16", True),
    "adam-float32-three_steps": (_adam, "float32", False),
    "elementwise_add_gelu-float32-forward": (_bias_act("gelu"), "float32", False),
    "elementwise_add_gelu-bfloat16-forward": (_bias_act("gelu"), "bfloat16", False),
    "elementwise_add_gelu-float32-gradient": (_bias_act("gelu"), "float32", True),
    "elementwise_add_relu-float32-forward": (_bias_act("relu"), "float32", False),
    "elementwise_add_relu-bfloat16-forward": (_bias_act("relu"), "bfloat16", False),
    "elementwise_add_relu-float32-gradient": (_bias_act("relu"), "float32", True),
}


@pytest.mark.parametrize("case", list(CASES))
def test_the_one_lowering_agrees_with_float64_numpy(case):
    run, dtype, gradient = CASES[case]
    with fluid.unique_name.guard():
        run(dtype, gradient)
