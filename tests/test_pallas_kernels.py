"""Pallas <-> XLA parity matrix for the ISSUE-7 fused kernels
(ops/pallas_kernels.py): every registered kernel against its composite
fallback over fp32 + bf16 at per-kernel tolerances, gradients included,
plus the routing contract — `FLAGS_use_pallas` off or a platform without
Pallas support must exercise the composite path bit-for-bit.

Kernels run in interpret mode here (the tests are on the virtual CPU
mesh); the device A/B lives in tools/opbench.py --fused."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.ops import pallas_kernels as pk

KERNELS = pk.registered_fused_kernels()
DTYPES = ("float32", "bfloat16")


def _flat(out):
    leaves = out if isinstance(out, (list, tuple)) else [out]
    return [np.asarray(l.astype(jnp.float32)) for l in leaves]


def _max_err(got, want):
    return max((float(np.max(np.abs(g - w))) if g.size else 0.0)
               for g, w in zip(_flat(got), _flat(want)))


# --------------------------------------------------------------------------
# forward parity matrix
# --------------------------------------------------------------------------


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("kernel", KERNELS)
def test_forward_parity(kernel, dtype):
    spec = pk.FUSED_KERNELS[kernel]
    args = spec["example"](jnp.dtype(dtype))
    got = spec["fused"](args, interpret=True)
    want = spec["reference"](args)
    err = _max_err(got, want)
    assert err <= spec["tol"][dtype], (
        f"{kernel} ({dtype}): fused kernel diverged from composite, "
        f"max|d|={err:.3e} > tol={spec['tol'][dtype]:.0e}")


@pytest.mark.parametrize("kernel",
                         [k for k in KERNELS
                          if pk.FUSED_KERNELS[k]["grad_argnums"]])
def test_grad_parity_fp32(kernel):
    """Custom-VJP backward (stats recomputed flash-style) against jax.grad
    through the composite."""
    spec = pk.FUSED_KERNELS[kernel]
    args = spec["example"](jnp.float32)
    live = list(args)
    # differentiate only grad_argnums (ORIGINAL positions — e.g. the
    # softmax_xent labels are integral and excluded by the registry)
    argnums = tuple(i for i in spec["grad_argnums"] if args[i] is not None)

    def loss(fn):
        def wrapped(*a):
            out = fn(a)
            return jnp.sum(jnp.square(out.astype(jnp.float32)))
        return wrapped

    gf = jax.grad(loss(lambda a: spec["fused"](a, interpret=True)),
                  argnums=argnums)(*live)
    gr = jax.grad(loss(spec["reference"]), argnums=argnums)(*live)
    for i, (a, b) in enumerate(zip(gf, gr)):
        err = float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                    - b.astype(jnp.float32))))
        # scale-aware: reduced grads (dscale/dmul sum over rows) carry
        # accumulation-order noise proportional to their magnitude
        tol = 1e-4 * (1.0 + float(jnp.max(jnp.abs(b.astype(jnp.float32)))))
        assert err <= tol, f"{kernel} d(arg{i}): max|d|={err:.3e} > {tol:.1e}"


@pytest.mark.parametrize("kernel",
                         [k for k in KERNELS
                          if pk.FUSED_KERNELS[k]["grad_argnums"]])
def test_grad_parity_multi_slab(kernel, monkeypatch):
    """Same grad parity with the VMEM budget shrunk so the row grid has
    MANY steps (grid > 1).  Pins the per-slab output contract: dm/da in the
    epilogue backward are per-row on disjoint blocks (plain store per
    step), while ln's dscale/dbias share one block across steps (genuine
    accumulation).  Interpret mode zero-fills outputs, so this can't
    reproduce an uninitialized-accumulator read — it guards the index-map
    and store/accumulate split, the device-visible half of that class."""
    # 256 KB: every example still gets >= 8 grid steps of whole
    # (8|16, 128) tiles, the smallest slab the chip's compiler takes
    monkeypatch.setattr(pk, "_VMEM_BUDGET", 256 * 1024)
    spec = pk.FUSED_KERNELS[kernel]
    args = spec["example"](jnp.float32)
    live = list(args)
    argnums = tuple(i for i in spec["grad_argnums"] if args[i] is not None)

    def loss(fn):
        def wrapped(*a):
            out = fn(a)
            return jnp.sum(jnp.square(out.astype(jnp.float32)))
        return wrapped

    gf = jax.grad(loss(lambda a: spec["fused"](a, interpret=True)),
                  argnums=argnums)(*live)
    gr = jax.grad(loss(spec["reference"]), argnums=argnums)(*live)
    for i, (a, b) in enumerate(zip(gf, gr)):
        err = float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                    - b.astype(jnp.float32))))
        tol = 1e-4 * (1.0 + float(jnp.max(jnp.abs(b.astype(jnp.float32)))))
        assert err <= tol, f"{kernel} d(arg{i}): max|d|={err:.3e} > {tol:.1e}"


def test_ln_without_residual():
    """res=None is the plain-LN shape the composite lowering also hits."""
    x, _, scale, bias = pk.FUSED_KERNELS["ln_residual"]["example"](jnp.float32)
    got = pk.fused_ln_residual(x, None, scale, bias, 1e-5, True)
    want = pk._ln_reference(x, None, scale, bias)
    assert _max_err(got, want) <= 2e-5


def test_adam_shape_contract():
    """Non-lane-multiple element counts must fall back (no padding): the
    lowering guards on adam_shape_ok before routing."""
    assert pk.adam_shape_ok((512, 256))
    assert pk.adam_shape_ok((pk._ADAM_LANE,))
    assert not pk.adam_shape_ok((3, 5))
    assert not pk.adam_shape_ok(())


def test_adam_matches_composite_sequence():
    """Two chained fused steps track the composite recurrence (m/v carry)."""
    p, g, m, v = pk.FUSED_KERNELS["adam_slab"]["example"](jnp.float32)
    p1, m1, v1 = pk.fused_adam(p, g, m, v, 1e-3, 0.9, 0.999, 1e-8,
                               interpret=True)
    p2, m2, v2 = pk.fused_adam(p1, g, m1, v1, 1e-3, 0.9, 0.999, 1e-8,
                               interpret=True)
    rp, rm, rv = pk._adam_reference(p, g, m, v)
    rp2, rm2, rv2 = pk._adam_reference(rp, g, rm, rv)
    assert _max_err((p2, m2, v2), (rp2, rm2, rv2)) <= 1e-5


# --------------------------------------------------------------------------
# routing: flag off / unsupported platform -> the composite, bit-for-bit
# --------------------------------------------------------------------------


def test_use_pallas_requires_tpu_platform():
    import paddle_tpu as fluid

    class Ctx:
        platform = "cpu"

    class TpuCtx:
        platform = "tpu"

    fluid.set_flags({"FLAGS_use_pallas": True})
    try:
        assert not pk.use_pallas(Ctx())          # capability gate
        assert pk.use_pallas(TpuCtx())
    finally:
        fluid.set_flags({"FLAGS_use_pallas": False})
    assert not pk.use_pallas(TpuCtx())           # opt-in gate


def _ln_program():
    import paddle_tpu as fluid

    main, startup = fluid.Program(), fluid.Program()
    startup.random_seed = main.random_seed = 3
    with fluid.program_guard(main, startup):
        x = fluid.layers.data("x", [8, 64], dtype="float32")
        y = fluid.layers.layer_norm(x, begin_norm_axis=2)
        h = fluid.layers.batch_norm(
            fluid.layers.conv2d(
                fluid.layers.reshape(y, [-1, 4, 16, 8]), 4, 3, padding=1))
        out = fluid.layers.mean(h) + fluid.layers.mean(y)
        fluid.optimizer.Adam(1e-3).minimize(out)
    return main, startup, out


def test_fallback_exercised_when_flag_on_but_platform_unsupported():
    """On the CPU test backend the composite must run even with
    FLAGS_use_pallas=1 (pallas_supported gates on platform), producing
    bit-identical results to the flag-off run — proof the fallback path is
    the one executing."""
    import paddle_tpu as fluid

    def run(flag):
        fluid.set_flags({"FLAGS_use_pallas": flag})
        try:
            main, startup, out = _ln_program()
            scope = fluid.Scope()
            exe = fluid.Executor(fluid.CPUPlace())
            exe.run(startup, scope=scope)
            feed = {"x": np.random.RandomState(0).rand(2, 8, 64).astype("f4")}
            (lv,) = exe.run(main, feed=feed, fetch_list=[out], scope=scope)
            return np.asarray(lv)
        finally:
            fluid.set_flags({"FLAGS_use_pallas": False})

    a, b = run(False), run(True)
    np.testing.assert_array_equal(a, b)


def test_flag_participates_in_compile_cache_key():
    """Toggling FLAGS_use_pallas must recompile (stale executables from the
    other routing would silently keep the old kernels)."""
    import paddle_tpu as fluid

    main, startup, out = _ln_program()
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup, scope=scope)
    feed = {"x": np.random.RandomState(0).rand(2, 8, 64).astype("f4")}
    exe.run(main, feed=feed, fetch_list=[out], scope=scope)
    n0 = len(exe._cache)
    fluid.set_flags({"FLAGS_use_pallas": True})
    try:
        exe.run(main, feed=feed, fetch_list=[out], scope=scope)
    finally:
        fluid.set_flags({"FLAGS_use_pallas": False})
    assert len(exe._cache) == n0 + 1, (
        "toggling FLAGS_use_pallas reused a cached executable")


# --------------------------------------------------------------------------
# program passes that feed the kernels
# --------------------------------------------------------------------------


def _run(prog, startup, feed, fetch, seed=5):
    import paddle_tpu as fluid

    startup.random_seed = prog.random_seed = seed
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup, scope=scope)
    (out,) = exe.run(prog, feed=feed, fetch_list=[fetch], scope=scope)
    return np.asarray(out)


def test_fuse_ln_residual_pass_parity():
    import paddle_tpu as fluid
    from paddle_tpu.core.passes import apply_pass

    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data("x", [8, 32], dtype="float32")
        h = fluid.layers.scale(x, scale=0.5)
        s = fluid.layers.elementwise_add(h, x)
        y = fluid.layers.layer_norm(s, begin_norm_axis=2)
        out = fluid.layers.mean(y)
    feed = {"x": np.random.RandomState(0).rand(4, 8, 32).astype("f4")}
    base = _run(main, startup, feed, out.name)
    apply_pass(main, "fuse_ln_residual", keep=[out.name])
    ln = [op for op in main.global_block().ops if op.type == "layer_norm"][0]
    assert ln.inputs.get("Residual") == ["x"], "residual not folded in"
    assert not any(op.type == "elementwise_add"
                   for op in main.global_block().ops)
    np.testing.assert_array_equal(base, _run(main, startup, feed, out.name))


def test_fuse_ln_residual_pass_skips_multi_reader():
    """An add whose output has a second reader must NOT fuse (the other
    reader still needs the pre-norm sum)."""
    import paddle_tpu as fluid
    from paddle_tpu.core.passes import apply_pass

    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data("x", [8, 32], dtype="float32")
        s = fluid.layers.elementwise_add(fluid.layers.scale(x, scale=0.5), x)
        y = fluid.layers.layer_norm(s, begin_norm_axis=2)
        out = fluid.layers.mean(y) + fluid.layers.mean(s)  # second reader
    apply_pass(main, "fuse_ln_residual", keep=[out.name])
    ln = [op for op in main.global_block().ops if op.type == "layer_norm"][0]
    assert not ln.inputs.get("Residual")
    assert any(op.type == "elementwise_add" and "tmp" in op.output("Out")[0]
               for op in main.global_block().ops)


def test_fuse_ln_residual_pass_skips_intervening_write():
    """Fusing moves the reads of the add's inputs down to the layer_norm's
    position — an op between that mutates an input (here increment on the
    add's X) would make the fused LN observe the mutation.  Must skip."""
    import paddle_tpu as fluid
    from paddle_tpu.core.passes import apply_pass

    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data("x", [8, 32], dtype="float32")
        h = fluid.layers.scale(x, scale=0.5)
        s = fluid.layers.elementwise_add(h, x)
        fluid.layers.increment(h)  # writes h between the add and the LN
        y = fluid.layers.layer_norm(s, begin_norm_axis=2)
        out = fluid.layers.mean(y)
    apply_pass(main, "fuse_ln_residual", keep=[out.name])
    ln = [op for op in main.global_block().ops if op.type == "layer_norm"][0]
    assert not ln.inputs.get("Residual")
    assert any(op.type == "elementwise_add"
               for op in main.global_block().ops)


def test_fuse_ln_residual_pass_skips_later_writer():
    """adds keeps the LAST elementwise_add writing each Out name; when that
    add executes AFTER the layer_norm (the name is written twice), pairing
    with it would normalize the wrong sum.  Must skip."""
    import paddle_tpu as fluid
    from paddle_tpu.core.passes import apply_pass

    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data("x", [8, 32], dtype="float32")
        t = fluid.layers.elementwise_add(fluid.layers.scale(x, scale=0.5), x)
        y = fluid.layers.layer_norm(t, begin_norm_axis=2)
        out = fluid.layers.mean(y)
        t2 = fluid.layers.elementwise_add(fluid.layers.scale(x, scale=2.0), x)
    # rewrite the second add to clobber t AFTER the LN consumed it
    add2 = main.global_block().ops[-1]
    assert add2.type == "elementwise_add"
    add2.outputs["Out"] = [t.name]
    apply_pass(main, "fuse_ln_residual", keep=[out.name])
    ln = [op for op in main.global_block().ops if op.type == "layer_norm"][0]
    assert not ln.inputs.get("Residual")
    assert sum(op.type == "elementwise_add"
               for op in main.global_block().ops) == 2


def test_fuse_bn_relu_pass_skips_later_writer():
    """by_out keeps the LAST batch_norm writing each Y name; when that BN
    executes AFTER the relu (the name is written twice), fusing would pair
    a backwards def-use and miscompile.  Must skip."""
    import paddle_tpu as fluid
    from paddle_tpu.core.passes import apply_pass

    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        img = fluid.layers.data("img", [4, 8, 8], dtype="float32")
        c = fluid.layers.conv2d(img, 8, 3, padding=1)
        b1 = fluid.layers.batch_norm(c)
        r = fluid.layers.relu(b1)
        out = fluid.layers.mean(r)
        fluid.layers.batch_norm(r)
    # rewrite the second BN to clobber b1's Y AFTER the relu consumed it
    bn2 = [op for op in main.global_block().ops
           if op.type == "batch_norm"][-1]
    bn2.outputs["Y"] = [b1.name]
    apply_pass(main, "fuse_bn_relu", keep=[out.name])
    assert any(op.type == "relu" for op in main.global_block().ops)
    assert not any(op.attrs.get("fuse_relu")
                   for op in main.global_block().ops
                   if op.type == "batch_norm")


def test_fuse_bn_relu_pass_parity():
    import paddle_tpu as fluid
    from paddle_tpu.core.passes import apply_pass

    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        img = fluid.layers.data("img", [4, 8, 8], dtype="float32")
        c = fluid.layers.conv2d(img, 8, 3, padding=1)
        r = fluid.layers.relu(fluid.layers.batch_norm(c))
        out = fluid.layers.mean(r)
    feed = {"img": np.random.RandomState(0).rand(2, 4, 8, 8).astype("f4")}
    base = _run(main, startup, feed, out.name)
    apply_pass(main, "fuse_bn_relu", keep=[out.name])
    bn = [op for op in main.global_block().ops if op.type == "batch_norm"][0]
    assert bn.attrs.get("fuse_relu") is True
    assert not any(op.type == "relu" for op in main.global_block().ops)
    np.testing.assert_array_equal(base, _run(main, startup, feed, out.name))


def test_fuse_bn_relu_pass_skips_fetched_bn_out():
    """A BN output that is itself a fetch target must stay written."""
    import paddle_tpu as fluid
    from paddle_tpu.core.passes import apply_pass

    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        img = fluid.layers.data("img", [4, 8, 8], dtype="float32")
        b = fluid.layers.batch_norm(fluid.layers.conv2d(img, 8, 3, padding=1))
        fluid.layers.relu(b)
    apply_pass(main, "fuse_bn_relu", keep=[b.name])
    assert any(op.type == "relu" for op in main.global_block().ops)


def test_fuse_bn_relu_pass_skips_intervening_write():
    """An op between the BN and the relu that overwrites the BN's Y means
    the relu never saw the BN's value — fusing would resurrect it.  The
    single-reader count alone misses this (assign reads its own input, not
    Y), so the positional hazard check must catch it."""
    import paddle_tpu as fluid
    from paddle_tpu.core.passes import apply_pass

    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        img = fluid.layers.data("img", [4, 8, 8], dtype="float32")
        c = fluid.layers.conv2d(img, 8, 3, padding=1)
        b = fluid.layers.batch_norm(c)
        fluid.layers.assign(fluid.layers.scale(c, scale=2.0), output=b)
        r = fluid.layers.relu(b)
        out = fluid.layers.mean(r)
    apply_pass(main, "fuse_bn_relu", keep=[out.name])
    bn = [op for op in main.global_block().ops if op.type == "batch_norm"][0]
    assert not bn.attrs.get("fuse_relu")
    assert any(op.type == "relu" for op in main.global_block().ops)


def test_fuse_bias_act_pass_parity():
    """ISSUE 17: elementwise_add -> relu folds into one add(fuse_act) op
    with identical numerics (the bias-act epilogue's graph-side half)."""
    import paddle_tpu as fluid
    from paddle_tpu.core.passes import apply_pass

    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data("x", [32], dtype="float32")
        h = fluid.layers.scale(x, scale=0.5)
        s = fluid.layers.elementwise_add(h, x)
        r = fluid.layers.relu(s)
        out = fluid.layers.mean(r)
    feed = {"x": np.random.RandomState(0).randn(4, 32).astype("f4")}
    base = _run(main, startup, feed, out.name)
    apply_pass(main, "fuse_bias_act", keep=[out.name])
    add = [op for op in main.global_block().ops
           if op.type == "elementwise_add"][0]
    assert add.attrs.get("fuse_act") == "relu"
    assert not any(op.type == "relu" for op in main.global_block().ops)
    np.testing.assert_array_equal(base, _run(main, startup, feed, out.name))


def test_fuse_bias_act_pass_gelu_parity():
    import paddle_tpu as fluid
    from paddle_tpu.core.passes import apply_pass

    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data("x", [32], dtype="float32")
        s = fluid.layers.elementwise_add(fluid.layers.scale(x, scale=0.5), x)
        out = fluid.layers.mean(fluid.layers.gelu(s))
    feed = {"x": np.random.RandomState(1).randn(4, 32).astype("f4")}
    base = _run(main, startup, feed, out.name)
    apply_pass(main, "fuse_bias_act", keep=[out.name])
    add = [op for op in main.global_block().ops
           if op.type == "elementwise_add"][0]
    assert add.attrs.get("fuse_act") == "gelu"
    assert not any(op.type == "gelu" for op in main.global_block().ops)
    np.testing.assert_array_equal(base, _run(main, startup, feed, out.name))


def test_fuse_bias_act_pass_skips_multi_reader():
    """An add whose output has a second reader must NOT fuse — the other
    reader still needs the pre-activation value."""
    import paddle_tpu as fluid
    from paddle_tpu.core.passes import apply_pass

    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data("x", [32], dtype="float32")
        s = fluid.layers.elementwise_add(fluid.layers.scale(x, scale=0.5), x)
        r = fluid.layers.relu(s)
        out = fluid.layers.mean(r) + fluid.layers.mean(s)  # second reader
    apply_pass(main, "fuse_bias_act", keep=[out.name])
    assert any(op.type == "relu" for op in main.global_block().ops)
    assert not any(op.attrs.get("fuse_act")
                   for op in main.global_block().ops
                   if op.type == "elementwise_add")


def test_fuse_bias_act_pass_skips_fetched_add_out():
    """A pre-activation sum that is itself a fetch target must stay
    written."""
    import paddle_tpu as fluid
    from paddle_tpu.core.passes import apply_pass

    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data("x", [32], dtype="float32")
        s = fluid.layers.elementwise_add(fluid.layers.scale(x, scale=0.5), x)
        fluid.layers.relu(s)
    apply_pass(main, "fuse_bias_act", keep=[s.name])
    assert any(op.type == "relu" for op in main.global_block().ops)


def test_fuse_bias_act_pass_skips_intervening_write():
    """An op between the add and the activation that overwrites the add's
    Out means the activation never saw the add's value — fusing would
    resurrect it."""
    import paddle_tpu as fluid
    from paddle_tpu.core.passes import apply_pass

    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data("x", [32], dtype="float32")
        h = fluid.layers.scale(x, scale=0.5)
        s = fluid.layers.elementwise_add(h, x)
        fluid.layers.assign(fluid.layers.scale(x, scale=2.0), output=s)
        r = fluid.layers.relu(s)
        out = fluid.layers.mean(r)
    apply_pass(main, "fuse_bias_act", keep=[out.name])
    assert any(op.type == "relu" for op in main.global_block().ops)
    assert not any(op.attrs.get("fuse_act")
                   for op in main.global_block().ops
                   if op.type == "elementwise_add")


# --------------------------------------------------------------------------
# opbench --fused smoke (the tier-1 wiring for the ISSUE-7 CI satellite)
# --------------------------------------------------------------------------


@pytest.mark.parametrize("dtype", DTYPES)
def test_opbench_fused_smoke(dtype):
    """Every registered fused kernel compiles through the opbench A/B
    harness and holds parity at the registry tolerance (the harness raises
    on divergence before timing)."""
    from tools.opbench import run_fused_ab

    recs = run_fused_ab(dtypes=(dtype,), interpret=True, rounds=1, iters=1)
    assert sorted(r["kernel"] for r in recs) == KERNELS
    for rec in recs:
        assert rec["pallas"]["best_ms"] > 0 and rec["xla"]["best_ms"] > 0
