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
    from types import SimpleNamespace

    from paddle_tpu.core.lowering import LoweringContext
    from paddle_tpu.core.registry import get_op_def
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
        op = SimpleNamespace(type="fused_attention", attr=lambda name, default=None: {"causal": True}.get(name, default))
        ctx = LoweringContext(jax.random.PRNGKey(0), platform="cpu")
        with jax.default_matmul_precision("highest"):
            return get_op_def("fused_attention").lower(ctx, op, {"Q": [q], "K": [k], "V": [v]})["Out"]

    def agree(got, want, tol):
        got, want = np.asarray(got, "f8"), np.asarray(want, "f8")
        assert got.shape == want.shape and np.isfinite(got).all()
        assert np.abs(got - want).max() <= tol * np.abs(want).max(), (np.abs(got - want).max(), np.abs(want).max())

    agree(kernel(q, k, v), xla(q, k, v), tol=1e-5)
    got = jax.grad(lambda *a: jnp.sum(kernel(*a) * weight), (0, 1, 2))(q, k, v)
    want = jax.grad(lambda *a: jnp.sum(xla(*a) * weight), (0, 1, 2))(q, k, v)
    for g, w in zip(got, want):
        agree(g, w, tol=2e-5)
