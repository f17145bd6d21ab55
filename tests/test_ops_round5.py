"""Round-5 op tests: BN fused single-pass stats (bf16 path) numerics.

Reference numerics: batch_norm_op.cc training mode (mean/var over N,H,W).
The bf16 activation path now computes E[x]/E[x^2] in one fused pass with
f32 accumulators (r5 chip round); these tests pin its accuracy against
float64 numpy at bf16-appropriate tolerances, including a shifted-mean case
where naive cancellation would show up first.
"""
import numpy as np

import paddle_tpu as fluid
from paddle_tpu import layers
from paddle_tpu.core.program import Program, program_guard


def _run_bn_bf16(x_np, scale, bias):
    main, startup = Program(), Program()
    with program_guard(main, startup):
        x = layers.data("x", list(x_np.shape[1:]), dtype="float32")
        xb = layers.cast(x, "bfloat16")
        y = layers.batch_norm(xb, is_test=False,
                              param_attr=fluid.ParamAttr(
                                  initializer=fluid.initializer.NumpyArrayInitializer(scale)),
                              bias_attr=fluid.ParamAttr(
                                  initializer=fluid.initializer.NumpyArrayInitializer(bias)))
        yf = layers.cast(y, "float32")
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    exe.run(startup, scope=scope)
    (out,) = exe.run(main, feed={"x": x_np}, fetch_list=[yf], scope=scope)
    return np.asarray(out)


def _ref_bn(x_np, scale, bias, eps=1e-5):
    x64 = x_np.astype(np.float64)
    m = x64.mean(axis=(0, 2, 3), keepdims=True)
    v = x64.var(axis=(0, 2, 3), keepdims=True)
    return ((x64 - m) / np.sqrt(v + eps) * scale.reshape(1, -1, 1, 1)
            + bias.reshape(1, -1, 1, 1))


def test_bn_bf16_fused_pass_centered():
    rng = np.random.RandomState(0)
    x = rng.randn(8, 4, 6, 6).astype("float32")
    scale = rng.uniform(0.5, 1.5, 4).astype("float32")
    bias = rng.uniform(-0.5, 0.5, 4).astype("float32")
    got = _run_bn_bf16(x, scale, bias)
    want = _ref_bn(x, scale, bias)
    # bf16 activations: ~2^-8 relative representation error dominates
    assert np.allclose(got, want, atol=5e-2, rtol=5e-2), np.abs(got - want).max()


def test_bn_bf16_fused_pass_shifted_mean():
    # |mean|/std = 10: cancellation in E[x^2]-mean^2 must stay below the
    # bf16 representation error of the input itself
    rng = np.random.RandomState(1)
    x = (rng.randn(8, 4, 6, 6) * 1.0 + 10.0).astype("float32")
    scale = np.ones(4, "float32")
    bias = np.zeros(4, "float32")
    got = _run_bn_bf16(x, scale, bias)
    want = _ref_bn(x, scale, bias)
    # shifted input quantized to bf16 loses ~10*2^-8 absolute on (x-mean);
    # the normalized output tolerance reflects that input-level error
    assert np.allclose(got, want, atol=0.15, rtol=0.1), np.abs(got - want).max()


def test_bn_f32_stays_two_pass_exact():
    # float32 takes jnp.mean / jnp.var: exact vs the two-pass numpy reference
    rng = np.random.RandomState(2)
    x = rng.randn(4, 3, 5, 5).astype("float32")
    main, startup = Program(), Program()
    with program_guard(main, startup):
        xv = layers.data("x", [3, 5, 5], dtype="float32")
        y = layers.batch_norm(xv, is_test=False)
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    exe.run(startup, scope=scope)
    (out,) = exe.run(main, feed={"x": x}, fetch_list=[y], scope=scope)
    m = x.mean(axis=(0, 2, 3), keepdims=True)
    v = x.var(axis=(0, 2, 3), keepdims=True)
    want = (x - m) / np.sqrt(v + 1e-5)
    assert np.allclose(np.asarray(out), want, atol=1e-4, rtol=1e-4)
