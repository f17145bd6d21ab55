"""fused_attention op: parity vs the unfused matmul/softmax/matmul program
path, causal masking, bias, grad flow, and the bf16 BERT builder.

Reference role: operators/fused/ attention fusion ambitions; here the TPU
lowering is the Pallas flash kernel (paddle_tpu/ops/nn_ops.py) and these
CPU tests exercise the identical-math fallback plus the program plumbing.
"""
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import layers
from paddle_tpu.core.program import Program, program_guard


def _run(build_fn, feeds, fetch):
    main, startup = Program(), Program()
    with program_guard(main, startup):
        out = build_fn()
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    exe.run(startup, scope=scope)
    (val,) = exe.run(main, feed=feeds, fetch_list=[out], scope=scope)
    return val


def _plain_attention(q, k, v, bias=None, causal=False):
    d = q.shape[-1]
    scores = layers.matmul(q, k, transpose_y=True, alpha=1.0 / np.sqrt(d))
    if bias is not None:
        scores = layers.elementwise_add(scores, bias)
    if causal:
        L = q.shape[2]
        mask_np = np.triu(np.full((L, L), -1e30, np.float32), k=1).reshape(1, 1, L, L)
        mask = layers.assign(mask_np)
        scores = layers.elementwise_add(scores, mask)
    attn = layers.softmax(scores)
    return layers.matmul(attn, v)


@pytest.mark.parametrize("causal", [False, True])
def test_fused_matches_plain(causal):
    rng = np.random.RandomState(0)
    B, H, L, D = 2, 3, 16, 8
    q = rng.randn(B, H, L, D).astype(np.float32)
    k = rng.randn(B, H, L, D).astype(np.float32)
    v = rng.randn(B, H, L, D).astype(np.float32)

    def build_fused():
        qv = layers.data("q", [H, L, D])
        kv = layers.data("k", [H, L, D])
        vv = layers.data("v", [H, L, D])
        return layers.fused_attention(qv, kv, vv, causal=causal)

    def build_plain():
        qv = layers.data("q", [H, L, D])
        kv = layers.data("k", [H, L, D])
        vv = layers.data("v", [H, L, D])
        return _plain_attention(qv, kv, vv, causal=causal)

    feeds = {"q": q, "k": k, "v": v}
    fused = _run(build_fused, feeds, "out")
    plain = _run(build_plain, feeds, "out")
    np.testing.assert_allclose(fused, plain, rtol=1e-5, atol=1e-5)


def test_fused_with_bias_broadcasts_heads():
    rng = np.random.RandomState(1)
    B, H, L, D = 2, 4, 8, 8
    q = rng.randn(B, H, L, D).astype(np.float32)
    k = rng.randn(B, H, L, D).astype(np.float32)
    v = rng.randn(B, H, L, D).astype(np.float32)
    bias = np.where(rng.rand(B, 1, L, L) < 0.2, -1e30, 0.0).astype(np.float32)

    def build(fused):
        qv = layers.data("q", [H, L, D])
        kv = layers.data("k", [H, L, D])
        vv = layers.data("v", [H, L, D])
        bv = layers.data("bias", [1, L, L])
        if fused:
            return layers.fused_attention(qv, kv, vv, bias=bv)
        return _plain_attention(qv, kv, vv, bias=bv)

    feeds = {"q": q, "k": k, "v": v, "bias": bias}
    np.testing.assert_allclose(
        _run(lambda: build(True), feeds, "out"),
        _run(lambda: build(False), feeds, "out"),
        rtol=1e-5, atol=1e-5)


def test_fused_attention_grad_flows():
    """Gradients through fused_attention match the unfused composition."""
    rng = np.random.RandomState(2)
    B, H, L, D = 2, 2, 8, 4
    x_np = rng.randn(B, H, L, D).astype(np.float32)

    def losses(fused):
        main, startup = Program(), Program()
        with program_guard(main, startup):
            x = layers.data("x", [H, L, D])
            q = layers.fc(x, D, num_flatten_dims=3)
            out = (layers.fused_attention(q, x, x)
                   if fused else _plain_attention(q, x, x))
            loss = layers.mean(out)
            fluid.optimizer.SGD(learning_rate=0.5).minimize(loss)
        exe = fluid.Executor(fluid.CPUPlace())
        scope = fluid.Scope()
        startup.random_seed = 3
        exe.run(startup, scope=scope)
        vals = []
        for _ in range(3):
            (lv,) = exe.run(main, feed={"x": x_np}, fetch_list=[loss], scope=scope)
            vals.append(float(np.asarray(lv).reshape(-1)[0]))
        return vals

    np.testing.assert_allclose(losses(True), losses(False), rtol=1e-5, atol=1e-6)


def test_bert_bf16_fused_builds_and_trains():
    from paddle_tpu.models import transformer

    main, startup, feeds, fetches = transformer.build_bert(
        vocab_size=100, seq_len=16, d_model=32, n_layers=2, n_heads=2, d_ff=64,
        dropout_prob=0.0, use_fused_attention=True, dtype="bfloat16")
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    exe.run(startup, scope=scope)
    batch = transformer.make_fake_batch(4, 16, 100)
    losses = []
    for _ in range(5):
        (lv,) = exe.run(main, feed=batch, fetch_list=[fetches["loss"]], scope=scope)
        losses.append(float(np.asarray(lv).reshape(-1)[0]))
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0]  # memorizes the tiny fake batch


def test_the_lower_span_says_which_attention_the_program_took():
    """`lowering.attention_*` count one a `fused_attention` op where the
    lowering decides; the `executor.lower` span of the program carries the
    ones that moved.  Off the TPU every op takes XLA's attention (the TPU's
    choices, shape by shape: tests/test_lowering_one_path.py)."""
    import paddle_tpu as fluid
    from paddle_tpu import layers, monitor
    from paddle_tpu.core.program import Program, program_guard

    main, startup = Program(), Program()
    with program_guard(main, startup):
        q = layers.data("q", [2, 8, 4], dtype="float32")
        out = layers.fused_attention(layers.fused_attention(q, q, q), q, q, causal=True)
    monitor.enable()
    try:
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(main, feed={"q": np.ones((3, 2, 8, 4), "f4")}, fetch_list=[out])
        seen = monitor.get_monitor().counter_values()
        assert seen["lowering.attention_xla"] == 2
        assert not seen.get("lowering.attention_row_kernel") and not seen.get("lowering.attention_flash")
        lowered = [e[5] for e in monitor.get_monitor().events() if e[0] == "executor.lower"][-1]
        assert lowered["attention_xla"] == 2 and "attention_row_kernel" not in lowered
    finally:
        monitor.disable()
        monitor.reset()


#: query heads, key/value heads, length, head width -> blocks of the 128-grid visited, cut.  128-wide heads as
#: OLMoE's, 64-wide heads on grouped key/value heads as LFM2's (32 on 8 scaled down), a length of three blocks
CAUSAL_KERNEL_CASES = {(4, 4, 256, 128): (3, 2), (8, 2, 256, 64): (3, 2), (4, 1, 384, 64): (6, 3), (2, 2, 384, 128): (6, 3)}


@pytest.mark.parametrize("hq,hkv,length,dh", list(CAUSAL_KERNEL_CASES))
def test_the_causal_plans_kernels_agree_with_xlas_attention_forward_and_backward(hq, hkv, length, dh):
    """What a TPU runs for a causal mask at long keys (`_attention_path`:
    `block_causal`), here interpreted and in blocks of 128: the stock splash
    kernels under the causal rule against the op's XLA attention, the output
    and the three gradients, key/value heads read as they are (nothing
    repeated, `dk` and `dv` of a key/value head summed over its query heads
    by the kernel), within the tolerances of tests/test_sdar.py's kernel
    cases; and the block map skips what the rule empties."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops import masked_attention

    rng = np.random.RandomState(37)
    q = rng.randn(2, hq, length, dh).astype("f4")
    k, v = (rng.randn(2, hkv, length, dh).astype("f4") for _ in range(2))
    weight = rng.randn(*q.shape).astype("f4")
    plan = masked_attention.causal_plan(length, hq)
    assert (plan.block, plan.first_key, plan.rule) == (128, 0, "causal")
    blocks = masked_attention.block_maps(plan)[0].block_mask
    assert (np.count_nonzero(blocks), np.count_nonzero(blocks == 1)) == CAUSAL_KERNEL_CASES[hq, hkv, length, dh]

    def kernel(q, k, v):
        return masked_attention.causal_attention(q, k, v, dh ** -0.5, interpret=True)

    def xla(q, k, v):
        with jax.default_matmul_precision("highest"):
            return _lower_attention("cpu", "bhld", causal=True)(q, k, v)

    def agree(got, want, tol):
        got, want = np.asarray(got, "f8"), np.asarray(want, "f8")
        assert got.shape == want.shape and np.isfinite(got).all()
        assert np.abs(got - want).max() <= tol * np.abs(want).max(), (np.abs(got - want).max(), np.abs(want).max())

    agree(kernel(q, k, v), xla(q, k, v), tol=1e-5)
    got = jax.grad(lambda *a: jnp.sum(kernel(*a) * weight), (0, 1, 2))(q, k, v)
    want = jax.grad(lambda *a: jnp.sum(xla(*a) * weight), (0, 1, 2))(q, k, v)
    for g, w in zip(got, want):
        agree(g, w, tol=2e-5)


def backward_agrees_with_dense_float32(plan, allowed, hq, hkv, widths):
    """The ONE backward kernel of `ops/attention_backward_kernels.py` as
    `attention_under` reaches it under `plan`, INTERPRETED, against dense
    float32 attention under `allowed(q_ids, kv_ids)`: dq, dk and dv within the
    kernel cases' tolerance, `dk` and `dv` of a key/value head summed over its
    query heads by the kernel (tests/test_sambay.py has the window rule's cases)."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops import masked_attention

    length, (width, v_width) = plan.positions, widths
    rng = np.random.RandomState(64)
    q, k = rng.randn(2, hq, length, width).astype("f4"), rng.randn(2, hkv, length, width).astype("f4")
    v, weight = rng.randn(2, hkv, length, v_width).astype("f4"), rng.randn(2, hq, length, v_width).astype("f4")
    assert plan.backward == "onchip_dq"

    def dense(q, k, v):
        k, v = (jnp.repeat(t, hq // hkv, axis=1) for t in (k, v))
        at = jnp.arange(length)
        s = jnp.einsum("bhqd,bhkd->bhqk", q, k, precision="highest") * width ** -0.5
        p = jax.nn.softmax(jnp.where(allowed(at[:, None], at[None, :]), s, -jnp.inf), -1)
        return jnp.einsum("bhqk,bhkd->bhqd", p, v, precision="highest")

    got = jax.grad(lambda *a: jnp.sum(masked_attention.attention_under(plan, *a, width ** -0.5) * weight), (0, 1, 2))(q, k, v)
    want = jax.grad(lambda *a: jnp.sum(dense(*a) * weight), (0, 1, 2))(q, k, v)
    for g, w in zip(got, want):
        g, w = np.asarray(g, "f8"), np.asarray(w, "f8")
        assert g.shape == w.shape and np.isfinite(g).all()
        assert np.abs(g - w).max() <= 2e-5 * np.abs(w).max(), (np.abs(g - w).max(), np.abs(w).max())


#: query heads, key/value heads, blocks of 128 in the length, widths of (queries and keys, values): every head
#: grouping with every width and both lengths once (a group of 7 on 1 as SmallThinker's 28 on 4, 192 | 128 as Kanana-2's)
ONCHIP_DQ_CASES = [(4, 4, 4, (64, 64)), (4, 4, 8, (128, 128)), (8, 2, 4, (128, 128)), (8, 2, 8, (192, 128)),
                   (7, 1, 4, (192, 128)), (7, 1, 8, (64, 64)), (4, 4, 4, (192, 128)), (8, 2, 4, (64, 64)), (7, 1, 4, (128, 128))]


@pytest.mark.parametrize("hq,hkv,blocks,widths", ONCHIP_DQ_CASES)
def test_the_one_backward_kernel_agrees_with_dense_float32_under_the_causal_rule(hq, hkv, blocks, widths):
    from paddle_tpu.ops import masked_attention

    plan = masked_attention.causal_plan(128 * blocks, hq, True, widths)._replace(block=128)
    steps = masked_attention._steps(plan)
    assert steps.q_block.size == blocks * (blocks + 1) // 2 and (steps.q_block >= steps.kv_block).all()   # the triangle alone
    backward_agrees_with_dense_float32(plan, masked_attention.causal_allowed, hq, hkv, widths)


#: the rule, query heads, key/value heads, a group's dk and dv summed in VMEM (`kv_rows`; None: every query head its own
#: key/value head), the causal rule laid over the picks: the one backward kernel under a STORED mask (ISSUE 68)
STORED_CASES = [("block_diffusion", 4, 2, True, None), ("block_diffusion", 4, 2, False, None), ("block_diffusion", 7, 1, True, None),
                ("block_diffusion", 2, 2, None, None), ("selected", 4, 2, True, True), ("selected", 4, 2, False, True),
                ("selected", 6, 2, True, False), ("selected", 2, 2, None, True)]


@pytest.mark.parametrize("rule,hq,hkv,kv_rows,causal", STORED_CASES)
def test_the_one_backward_kernel_agrees_with_dense_float32_under_a_stored_mask(rule, hq, hkv, kv_rows, causal):
    """The kernel INTERPRETED, in blocks of 128, as the two ops reach it, dq, dk
    and dv against float32 dense attention under the rule.  Block diffusion's:
    the far term a RECTANGLE, 512 queries against the 256 clean keys (two
    distinct cut blocks and the block of ones), under the joined log-sum-exp,
    the first noised block's rows with no far key.  The selected rule's: two
    rows with different picks, a pair of blocks no query chose (its state 0:
    the step computes nothing), the log-sum-exp an output with a cotangent of
    its own, the causal rule's triangle of steps or the whole square."""
    from unittest import mock

    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops import attention_backward_kernels as onchip
    from paddle_tpu.ops import masked_attention
    from paddle_tpu.ops.sparse_index_ops import pack_bits

    rng = np.random.RandomState(68)
    width, length = 128, 512 if rule == "block_diffusion" else 384
    q, weight = (rng.randn(2, hq, length, width).astype("f4") for _ in range(2))
    k, v = (rng.randn(2, hkv, length, width).astype("f4") for _ in range(2))
    lse_weight = rng.randn(2, hq, length).astype("f4")
    at = np.arange(length)
    if rule == "block_diffusion":
        allowed = np.broadcast_to(masked_attention.block_diffusion_allowed(at[:, None], at[None, :], length // 2, 4), (2, length, length))
        plan = masked_attention.plan_of(length, hq, 4, True)
        assert (plan.block, plan.first_key) == (128, 256) and masked_attention._stored_blocks(plan)[0].shape == (3, 128, 128)
        assert masked_attention._steps(plan).q_block.size == 6 and sorted(masked_attention._stored_blocks(plan)[1]) == [0, 0, 1, 1, 2, 2]

        def kernel(q, k, v):
            return jnp.sum(masked_attention.attention_under(plan, q, k, v, width ** -0.5) * weight)
    else:
        allowed = (rng.rand(2, length, length) < 0.3) | np.eye(length, dtype=bool)
        allowed[:, 256:, :128] = False                   # no query of the third block holds a key of the first
        allowed[1, 128:256, :128] = False                # ... and in the second row none of the second block either
        picks = pack_bits(jnp.asarray(allowed))
        allowed = allowed & (at[:, None] >= at[None, :]) if causal else allowed
        plan = masked_attention.selected_plan(length, hq, causal, True)
        assert plan.block == 128 and masked_attention._steps(plan).q_block.size == (6 if causal else 9)

        def kernel(q, k, v):
            out, lse = masked_attention.selected_attention(q, k, v, picks, width ** -0.5, causal, interpret=True)
            return jnp.sum(out * weight) + jnp.sum(lse * lse_weight)
    assert plan.backward == "onchip_dq"

    def dense(q, k, v):
        k, v = (jnp.repeat(t, hq // hkv, axis=1) for t in (k, v))
        s = jnp.where(allowed[:, None], jnp.einsum("bhqd,bhkd->bhqk", q, k, precision="highest") * width ** -0.5, -jnp.inf)
        out = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1), v, precision="highest")
        return jnp.sum(out * weight) + (jnp.sum(jax.nn.logsumexp(s, -1) * lse_weight) if rule == "selected" else 0.0)

    onchip.backward.clear_cache()            # the kernel's call is a `jax.jit` of its own: a trace under another patch is not this one's
    with mock.patch.object(onchip, "kv_rows_fit", lambda *a: bool(kv_rows)):
        got = jax.grad(kernel, (0, 1, 2))(q, k, v)
    onchip.backward.clear_cache()
    want = jax.grad(dense, (0, 1, 2))(q, k, v)
    for g, w in zip(got, want):
        g, w = np.asarray(g, "f8"), np.asarray(w, "f8")
        assert g.shape == w.shape and np.isfinite(g).all()
        assert np.abs(g - w).max() <= 2e-5 * np.abs(w).max(), (np.abs(g - w).max(), np.abs(w).max())
    if rule == "block_diffusion":            # the rows with no far key: the own-block term's gradient alone, the far term's exactly 0
        assert np.abs(np.asarray(got[0])[:, :, :4] - np.asarray(want[0])[:, :, :4]).max() <= 2e-5 * np.abs(np.asarray(want[0])).max()


def _backward_jaxpr(plan, q_shape, kv_heads, v_width=None, picks=False):
    """Backward alone under `plan` as a TPU would run it, traced and not run:
    the jaxpr of the op's backward rule on its residuals' shapes."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops import masked_attention

    q = jax.ShapeDtypeStruct(q_shape, jnp.bfloat16)
    k = jax.ShapeDtypeStruct((q_shape[0], kv_heads) + q_shape[2:], jnp.bfloat16)
    v = jax.ShapeDtypeStruct(k.shape[:3] + (v_width or q_shape[3],), jnp.bfloat16)
    out = jax.ShapeDtypeStruct(q_shape[:3] + v.shape[3:], jnp.bfloat16)
    lse = jax.ShapeDtypeStruct(q_shape[:3], jnp.float32)
    if picks:
        chosen = jax.ShapeDtypeStruct((q_shape[0], q_shape[2], q_shape[2] // 32), jnp.int32)
        return str(jax.make_jaxpr(lambda *a: masked_attention._selected_bwd(plan, None, a[:6], a[6:]))(q, k, v, chosen, out, lse, out, lse))
    return str(jax.make_jaxpr(lambda *a: masked_attention._attention_bwd(plan, None, a[:5], a[5]))(q, k, v, out, lse, out))


def test_the_causal_plans_backward_is_one_kernel_and_holds_no_partial_dq():
    """ONE `pallas_call`, the kernel that sums dq in VMEM, and no array a block
    of keys by the queries' shape (`[L / block, Hq, L, dh]`: the stock fused
    kernel's partials); the op's counter says so at trace time."""
    import re

    import jax
    import jax.numpy as jnp

    from paddle_tpu import monitor
    from paddle_tpu.ops import masked_attention

    plan = masked_attention.causal_plan(4096, 8, widths=(64, 64))
    assert (plan.block, plan.backward) == (1024, "onchip_dq")
    text = _backward_jaxpr(plan, (1, 8, 4096, 64), 2)
    assert text.count("pallas_call[") == 1 and "name=attention_dq_dk_dv" in text and "splash_mha" not in text
    assert not re.findall(r"\[4,(?:1,)?8,4096,64\]", text)
    monitor.reset()
    monitor.enable()
    try:
        q = jax.ShapeDtypeStruct((1, 8, 4096, 64), jnp.bfloat16)
        jax.eval_shape(lambda q: masked_attention.causal_attention(q, q[:, :2], q[:, :2], 0.125), q)
        seen = monitor.get_monitor().counter_values()
    finally:
        monitor.disable()
        monitor.reset()
    assert seen["lowering.attention_backward_onchip_dq"] == 1 and not seen.get("lowering.attention_dq_partials_bytes")


# --------------------------------------------------------------------------
# The op's `layout` (ISSUE 39): "blhd" hands Q, K, V over as (B, L, H, dh), the
# projections' own layout, and takes Out back so.  One mathematics: every path
# gives what it gives heads-major on the transposed operands.
# --------------------------------------------------------------------------


def _lower_attention(platform, layout, causal=False, mask=None, mask_block=None, mesh=None):
    """f(q, k, v, *bias) -> Out: the op's registered lowering for `platform`, as the executor calls it."""
    import jax
    from types import SimpleNamespace

    from paddle_tpu.core.lowering import LoweringContext
    from paddle_tpu.core.registry import get_op_def

    attrs = {"causal": causal, "layout": layout, "mask": mask, "mask_block": mask_block}
    op = SimpleNamespace(type="fused_attention", attr=lambda name, default=None: attrs.get(name, default))
    ctx = LoweringContext(jax.random.PRNGKey(0), platform=platform, mesh=mesh)
    return lambda q, k, v, *bias: get_op_def("fused_attention").lower(ctx, op, {"Q": [q], "K": [k], "V": [v], "Bias": list(bias)})["Out"]


#: query heads, key/value heads, causal, bias, structured mask
LAYOUT_CASES = {"plain": (4, 4, False, False, False), "causal": (4, 4, True, False, False), "bias": (4, 4, False, True, False),
                "causal-bias": (4, 4, True, True, False), "grouped": (4, 2, False, False, False),
                "grouped-causal": (6, 2, True, False, False), "block-diffusion": (4, 4, False, False, True),
                "block-diffusion-grouped": (4, 1, False, False, True)}


@pytest.mark.parametrize("case", list(LAYOUT_CASES))
def test_the_op_over_the_projections_layout_is_the_op_over_heads_major_on_transposed_operands(case):
    """XLA's attention (every platform but the TPU, and the TPU's short and
    unpriced lengths): the output and the three gradients, to the bit: the
    lowering transposes at its own edge and runs the same einsums."""
    import jax
    import jax.numpy as jnp

    hq, hkv, causal, biased, masked = LAYOUT_CASES[case]
    rng = np.random.RandomState(39)
    q = rng.randn(2, hq, 16, 8).astype("f4")
    k, v = (rng.randn(2, hkv, 16, 8).astype("f4") for _ in range(2))
    w = rng.randn(*q.shape).astype("f4")
    bias = (rng.randn(2, 1, 16, 16).astype("f4"),) if biased else ()
    mask = dict(mask="block_diffusion", mask_block=4) if masked else {}
    swap = lambda t: jnp.swapaxes(t, 1, 2)  # noqa: E731

    def results(f, w, *operands):
        out, vjp = jax.vjp(lambda *a: f(*a, *bias), *operands)
        return (out,) + vjp(w)

    want = results(_lower_attention("cpu", "bhld", causal, **mask), w, q, k, v)
    got = results(_lower_attention("cpu", "blhd", causal, **mask), swap(w), swap(q), swap(k), swap(v))
    for g, t in zip(got, want):
        assert g.shape == swap(t).shape
        np.testing.assert_array_equal(np.asarray(swap(g)), np.asarray(t))


#: (queries, keys), causal, structured mask -> the attention a TPU takes, the kernels in its jaxpr.  One choice
#: whatever the layout, the row kernel's lower end too: one bound, `_ROW_KERNEL_MIN_SEQ`, 256 keys
TPU_LAYOUT_CASES = {
    ((128, 128), False, False): ("xla", set()), ((256, 128), False, False): ("xla", set()),
    ((256, 256), False, False): ("row_kernel", {"fused_sdpa_fwd", "fused_sdpa_bwd"}),
    ((512, 256), False, False): ("row_kernel", {"fused_sdpa_fwd", "fused_sdpa_bwd"}),
    ((384, 384), False, False): ("row_kernel", {"fused_sdpa_fwd", "fused_sdpa_bwd"}),
    ((512, 512), True, False): ("row_kernel", {"fused_sdpa_fwd", "fused_sdpa_bwd"}),
    ((512, 384), False, False): ("row_kernel", {"fused_sdpa_fwd", "fused_sdpa_bwd"}),
    ((1, 512), False, False): ("xla", set()), ((1024, 1024), False, False): ("xla", set()),
    ((2048, 2048), False, False): ("flash", {"flash_attention"}),
    ((2048, 2048), True, False): ("block_causal", {"splash_mha_fwd", "attention_dq_dk_dv"}),   # ONE backward kernel, of our own
    ((2048, 2048), False, True): ("block_sparse", {"splash_mha_fwd", "attention_dq_dk_dv"}),   # the same kernel on stored blocks (PR 68)
}


@pytest.mark.parametrize("lengths,causal,masked", list(TPU_LAYOUT_CASES),
                         ids=[f"{n[0]}x{n[1]}{'-causal' * c}{'-block-diffusion' * m}" for n, c, m in TPU_LAYOUT_CASES])
def test_on_the_tpu_the_layout_changes_no_choice_and_only_the_row_kernel_reads_it_as_it_is(lengths, causal, masked):
    """`_attention_path` reads the lengths by the layout: the same attention
    for (B, L, H, dh) as for (B, H, L, dh).  The row kernel's jaxpr holds no
    transpose under either layout; every other path's holds, under "blhd",
    the same kernels as heads-major and the transposes at its own edge
    (four forward: q, k, v and the result); the two counters say which."""
    import re

    import jax
    import jax.numpy as jnp

    from paddle_tpu import monitor
    from paddle_tpu.ops.nn_ops import _attention_path

    (lq, lk), head = lengths, 128 if masked else 64
    mask = dict(mask="block_diffusion", mask_block=4) if masked else {}
    case = (lengths, causal, masked)
    shapes = {"bhld": [(2, 4, n, head) for n in (lq, lk, lk)], "blhd": [(2, n, 4, head) for n in (lq, lk, lk)]}
    found = {}
    monitor.enable()
    try:
        for layout, operands in shapes.items():
            path, kernels = TPU_LAYOUT_CASES[case]
            args = [jax.ShapeDtypeStruct(s, jnp.bfloat16) for s in operands]
            assert _attention_path("tpu", None, args[0], args[1], ("block_diffusion", 4) if masked else None, causal, False,
                                   layout) == path
            f = _lower_attention("tpu", layout, causal, **mask)
            before = monitor.get_monitor().counter_values()
            text = str(jax.make_jaxpr(jax.grad(lambda *a: f(*a).astype(jnp.float32).sum(), argnums=(0, 1, 2)))(*args))
            moved = {n[len("lowering.attention_"):]: c - before.get(n, 0) for n, c in monitor.get_monitor().counter_values().items()
                     if n.startswith("lowering.attention_") and c != before.get(n, 0)}
            assert set(re.findall(r"name=(fused_sdpa_fwd|fused_sdpa_bwd|flash_attention|splash_mha_fwd|splash_mha_dq|splash_mha_dkv|attention_dq_dk_dv)\w*\b",
                                  text)) == kernels
            native = layout == "bhld" or path == "row_kernel"
            assert moved.pop(path) == 1 and moved.pop("layout_native" if native else "layout_transposed") == 1
            assert not {n for n in moved if n.startswith("layout_") or n in ("xla", "flash", "row_kernel", "block_causal", "block_sparse")}
            found[layout] = len(re.findall(r"\btranspose\[", text))
    finally:
        monitor.disable()
        monitor.reset()
    if TPU_LAYOUT_CASES[case][0] == "row_kernel":
        assert found["blhd"] == found["bhld"] == 0
    else:
        assert found["blhd"] >= found["bhld"] + 4


def test_the_infer_rule_reads_heads_and_lengths_by_the_layout():
    """Out is Q's shape in either layout; K's heads must divide Q's along the
    layout's heads axis; the rule refuses a layout it does not know when the op is appended."""
    def attention(layout, q_shape, kv_shape):
        main, startup = Program(), Program()
        with program_guard(main, startup):
            q = layers.data("q", q_shape)
            k, v = layers.data("k", kv_shape), layers.data("v", kv_shape)
            return layers.fused_attention(q, k, v, layout=layout), main

    out, main = attention("blhd", [16, 6, 8], [24, 2, 8])  # 16 queries, 24 keys, six heads on two
    assert tuple(out.shape)[1:] == (16, 6, 8)
    assert [op.attrs["layout"] for op in main.global_block().ops if op.type == "fused_attention"] == ["blhd"]
    out, main = attention("bhld", [6, 16, 8], [2, 24, 8])
    assert tuple(out.shape)[1:] == (6, 16, 8)
    assert ["layout" in op.attrs for op in main.global_block().ops if op.type == "fused_attention"] == [False]
    with pytest.raises(Exception, match="heads"):
        attention("blhd", [6, 16, 8], [2, 24, 8])  # heads-major shapes under the other layout's name: 16 heads on 24
    with pytest.raises(Exception, match="layout 'bldh'"):
        attention("bldh", [16, 6, 8], [24, 2, 8])


def test_the_planners_row_counts_the_same_products_under_either_layout():
    from paddle_tpu.core import resource_plan

    def flops(layout, q_shape, kv_shape):
        main, startup = Program(), Program()
        with program_guard(main, startup):
            q = layers.data("q", q_shape)
            k, v = layers.data("k", kv_shape), layers.data("v", kv_shape)
            layers.fused_attention(q, k, v, layout=layout)
        feeds = {"q": (2, *q_shape), "k": (2, *kv_shape), "v": (2, *kv_shape)}
        return [row.flops for row in resource_plan.plan_program(main, feeds).rows if row.op_type == "fused_attention"]

    assert flops("blhd", [16, 6, 8], [24, 6, 8]) == flops("bhld", [6, 16, 8], [6, 24, 8]) == [4.0 * 2 * 6 * 8 * 16 * 24]


@pytest.mark.parametrize("layout", ["bhld", "blhd"])
def test_a_program_runs_the_op_under_either_layout(layout):
    """Through `layers.fused_attention` and the executor, against the unfused program."""
    rng = np.random.RandomState(5)
    B, H, L, D = 2, 3, 16, 8
    q, k, v = (rng.randn(B, H, L, D).astype(np.float32) for _ in range(3))
    bias = np.where(rng.rand(B, 1, L, L) < 0.2, -1e30, 0.0).astype(np.float32)
    at = (lambda t: t) if layout == "bhld" else (lambda t: np.ascontiguousarray(t.transpose(0, 2, 1, 3)))

    def build(fused):
        shape = [H, L, D] if layout == "bhld" or not fused else [L, H, D]
        qv, kv, vv = (layers.data(n, shape) for n in "qkv")
        bv = layers.data("bias", [1, L, L])
        if fused:
            return layers.fused_attention(qv, kv, vv, bias=bv, layout=layout)
        return _plain_attention(qv, kv, vv, bias=bv)

    fused = _run(lambda: build(True), {"q": at(q), "k": at(k), "v": at(v), "bias": bias}, "out")
    plain = _run(lambda: build(False), {"q": q, "k": k, "v": v, "bias": bias}, "out")
    np.testing.assert_allclose(fused, at(plain), rtol=1e-5, atol=1e-5)


#: builder -> `transpose2` ops in the program it builds, and the layout of its `fused_attention` ops
def _bert(**kw):
    from paddle_tpu.models import transformer

    return transformer.build_bert(vocab_size=100, seq_len=16, d_model=32, n_layers=2, n_heads=2, d_ff=64, **kw)[0]


def _decoder(**kw):
    from paddle_tpu.models import transformer

    return transformer.build_causal_lm(vocab_size=64, seq_len=16, d_model=32, n_layers=2, n_heads=2, head_dim=16, expert_width=32,
                                       num_experts=4, top_k=2, **kw)[0]


TRANSPOSES = {
    "bert-fused": (lambda: _bert(use_fused_attention=True), 0, ["blhd", "blhd"]),
    "bert-fused-for-test": (lambda: _bert(use_fused_attention=True).clone(for_test=True), 0, ["blhd", "blhd"]),
    "bert-unfused": (lambda: _bert(use_fused_attention=False), 8, []),
    "rotary-decoder": (_decoder, 8, [None, None]),
}


@pytest.mark.parametrize("case", list(TRANSPOSES))
def test_the_builder_transposes_only_where_something_needs_heads_major(case):
    """BERT's builder (no rotary embedding, no per-head norm) hands the fused
    attention the projections' own layout and its program holds no
    `transpose2`; the unfused attention and a rotary decoder keep the four a
    layer they had."""
    build, transposes, layouts = TRANSPOSES[case]
    ops = build().global_block().ops
    assert sum(op.type == "transpose2" for op in ops) == transposes
    assert [op.attrs.get("layout") for op in ops if op.type == "fused_attention"] == layouts
