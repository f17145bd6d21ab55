"""ISSUE 50: a decoder whose later layers read what earlier ones made (SambaY:
Phi-4-mini-flash-reasoning's self-decoder, cross-decoder and Gated Memory
Units) and the sliding-window rule of `fused_attention`.

(a) the window rule: `window_allowed` against a dense mask, the pairs it
    allows, its plan and block maps (a window as long as the sequence IS the
    causal rule, block maps included), the stock kernels under it INTERPRETED
    against XLA's attention under the same rule, forward and backward, and
    `_attention_path`'s choice;
(b) the op `memory_gate`: its lowering, infer rule, planner row and record;
(c) the builder: refusals of a misplaced reader, the plain Mamba mixer against
    Jamba's reference without its inner norms;
(d) the model at a small size (hidden 64, 4 heads on 2 of 16, window 8 over 64
    positions, the six layers of the five kinds, vocabulary 128) against
    benchmark/models/phi4flash.py's plain reference: in float32 the loss, the
    logits, every stage and every parameter's gradient, with and without
    `recompute_layers`; the gradients that reach a parameter THROUGH a kept
    tensor alone; in bf16 within the benchmark's tolerances; and each fault of
    `phi4flash.FAULTS` refused by a limit.
"""
import os
import sys
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import paddle_tpu as fluid  # noqa: E402
from benchmark import manifest as mf  # noqa: E402
from benchmark.models import jamba, phi4flash  # noqa: E402
from paddle_tpu import layers, monitor  # noqa: E402
from paddle_tpu.core.lowering import LoweringContext  # noqa: E402
from paddle_tpu.core.registry import get_op_def  # noqa: E402
from paddle_tpu.models import transformer  # noqa: E402
from paddle_tpu.ops import masked_attention, nn_ops  # noqa: E402
from test_fused_attention import ONCHIP_DQ_CASES, _backward_jaxpr, backward_agrees_with_dense_float32  # noqa: E402


def agree(got, want, tol=1e-5, floor=1e-12):
    got, want = np.asarray(got, "f8"), np.asarray(want, "f8")
    assert got.shape == want.shape, (got.shape, want.shape)
    assert np.isfinite(got).all()
    assert np.abs(got - want).max() <= tol * max(np.abs(want).max(), floor), \
        (np.abs(got - want).max(), np.abs(want).max())


def lower_attention(platform, mask=None, mask_block=None, causal=False, layout="bhld", mesh=None, kept_kv=False):
    attrs = {"causal": causal, "layout": layout, "mask": mask, "mask_block": mask_block, "kept_kv": kept_kv}
    op = SimpleNamespace(type="fused_attention", attr=lambda name, default=None: attrs.get(name, default))
    ctx = LoweringContext(jax.random.PRNGKey(0), platform=platform, mesh=mesh)
    return lambda q, k, v: get_op_def("fused_attention").lower(ctx, op, {"Q": [q], "K": [k], "V": [v], "Bias": []})["Out"]


# -- (a) the window rule ---------------------------------------------------------------------

@pytest.mark.parametrize("window", [1, 8, 64, 96, 200])
def test_window_allowed_is_the_dense_band_and_its_pairs_are_counted(window):
    length = 96
    dense = np.zeros((length, length), bool)
    for i in range(length):
        dense[i, max(0, i - window + 1):i + 1] = True
    at = np.arange(length)
    mine = masked_attention.window_allowed(at[:, None], at[None, :], window)
    assert (mine == dense).all() and masked_attention.window_pairs(length, window) == dense.sum()
    assert (np.asarray(masked_attention.window_allowed(jnp.asarray(at)[:, None], jnp.asarray(at)[None, :], window)) == dense).all()
    if window >= length:   # the causal rule, nothing less
        assert (mine == masked_attention.causal_allowed(at[:, None], at[None, :])).all()
        assert masked_attention.window_pairs(length, window) == length * (length + 1) // 2


@pytest.mark.parametrize("window", [1, 8, 64, 130, 512, 1000])
def test_the_windows_block_maps_hold_the_bands_blocks_and_a_whole_window_is_the_causal_plan(window):
    length, heads = 512, 4
    plan = masked_attention.window_plan(length, heads, window)
    if window >= length:
        causal = masked_attention.causal_plan(length, heads)
        assert plan == causal and plan.rule == "causal"
        for mine, theirs in zip(masked_attention.block_maps(plan), masked_attention.block_maps(causal)):
            assert (mine is None and theirs is None) or all(
                (a is None and b is None) or np.array_equal(a, b) for a, b in zip(mine, theirs))
        return
    assert plan.rule == "sliding_window" and plan.mask_block == window and plan.backward == "onchip_dq"
    assert plan.block == {1: 128, 8: 128, 64: 128, 130: 256}[window]             # the smallest block that holds a window
    forward, dq, dkv = masked_attention.block_maps(plan)
    assert dq is None                                                          # one backward kernel, which walks the dkv map
    at = np.arange(length)
    dense = masked_attention.window_allowed(at[:, None], at[None, :], window)
    n = length // plan.block
    by_block = dense.reshape(n, plan.block, n, plan.block).transpose(0, 2, 1, 3)
    touched, whole = by_block.any((2, 3)), by_block.all((2, 3))
    state = forward.block_mask[0]                                              # 0 empty, 1 cut, 2 whole
    if state.shape == (n, n):
        assert ((state > 0) == touched).all() and ((state == 2) == whole).all()
    assert np.count_nonzero(state) == touched.sum() <= 2 * n - 1                 # the diagonal and one block before it
    assert np.count_nonzero(dkv.block_mask) == np.count_nonzero(state)
    steps = masked_attention._steps(plan)                                      # ... a step a touched block, a key block after the other
    assert steps.q_block.size == touched.sum() and (np.diff(steps.kv_block) >= 0).all()
    assert touched[steps.q_block, steps.kv_block].all()
    first = np.r_[True, np.diff(steps.kv_block) > 0]                          # marked: a key block's first and last step
    assert ((steps.marks & 1 > 0) == first).all() and ((steps.marks & 2 > 0) == np.r_[first[1:], True]).all()
    assert len(set(zip(steps.q_block.tolist(), steps.kv_block.tolist()))) == steps.q_block.size


WINDOW_KERNEL_CASES = [(4, 2, 384, 64, 130), (2, 2, 256, 128, 64), (4, 1, 256, 64, 1)]


@pytest.mark.parametrize("hq,hkv,length,dh,window", WINDOW_KERNEL_CASES)
def test_the_window_plans_kernels_agree_with_xlas_attention_forward_and_backward(hq, hkv, length, dh, window):
    """What a TPU runs under the window rule, here interpreted and in blocks of
    128: the stock splash kernels with the rule computed on the cut blocks
    against the op's XLA attention under the same rule, the output and the
    three gradients, grouped key/value heads read as they are; the trace-time
    counters count the op, the visited and the allowed pairs."""
    rng = np.random.RandomState(50)
    q = rng.randn(2, hq, length, dh).astype("f4")
    k, v = (rng.randn(2, hkv, length, dh).astype("f4") for _ in range(2))
    weight = rng.randn(*q.shape).astype("f4")

    def kernel(q, k, v):
        return masked_attention.window_attention(q, k, v, window, dh ** -0.5, interpret=True)

    def xla(q, k, v):
        with jax.default_matmul_precision("highest"):
            return lower_attention("cpu", "sliding_window", window)(q, k, v)

    monitor.reset()
    monitor.enable()
    try:
        agree(kernel(q, k, v), xla(q, k, v), tol=1e-5)
        seen = monitor.get_monitor().counter_values()
    finally:
        monitor.disable()
        monitor.reset()
    plan = masked_attention.window_plan(length, hq, window)
    blocks = np.count_nonzero(masked_attention.block_maps(plan)[0].block_mask[0])
    assert seen["lowering.window_attention_ops"] == seen["lowering.attention_backward_onchip_dq"] == 1
    assert seen["lowering.window_pairs_allowed"] == 2 * hq * masked_attention.window_pairs(length, window)
    assert seen["lowering.window_pairs_visited"] == 2 * hq * blocks * plan.block ** 2 >= seen["lowering.window_pairs_allowed"]
    assert seen["lowering.attention_blocks_visited"] >= blocks
    got = jax.grad(lambda *a: jnp.sum(kernel(*a) * weight), (0, 1, 2))(q, k, v)
    want = jax.grad(lambda *a: jnp.sum(xla(*a) * weight), (0, 1, 2))(q, k, v)
    for g, w in zip(got, want):
        agree(g, w, tol=2e-5, floor=1e-1)      # under a window of 1 the queries' and keys' gradients are exactly 0


#: the causal rule's cases (tests/test_fused_attention.py) under a window SHORTER than the kernels' block of 128, of a
#: block and of FOUR blocks: a key block then meets up to five query blocks, the first and the last of them cut
ONCHIP_DQ_WINDOW_CASES = [(hq, hkv, blocks, widths, window)
                          for (hq, hkv, blocks, widths), window in zip(ONCHIP_DQ_CASES, (100, 512, 128, 512, 100, 128, 128, 100, 128))]


@pytest.mark.parametrize("hq,hkv,blocks,widths,window", ONCHIP_DQ_WINDOW_CASES)
def test_the_one_backward_kernel_agrees_with_dense_float32_under_the_window_rule(hq, hkv, blocks, widths, window):
    length = 128 * blocks
    plan = masked_attention.window_plan(length, hq, window, True, widths)._replace(block=128)
    assert plan.rule == "sliding_window"
    steps = masked_attention._steps(plan)
    reach = 1 + -(-(window - 1) // 128)                        # query blocks a key block meets where the sequence does not end
    assert steps.q_block.size == sum(min(reach, blocks - j) for j in range(blocks)) < blocks * (blocks + 1) // 2
    backward_agrees_with_dense_float32(plan, masked_attention._window_rule(window), hq, hkv, widths)


def test_the_window_plans_backward_is_one_kernel_and_holds_no_partial_dq():
    import re

    plan = masked_attention.window_plan(8192, 8, 2048)
    assert (plan.block, plan.backward) == (1024, "onchip_dq")
    text = _backward_jaxpr(plan, (1, 8, 8192, 128), 2)
    assert text.count("pallas_call[") == 1 and "name=attention_dq_dk_dv" in text and "splash_mha" not in text
    assert not re.findall(r"\[8,(?:1,)?8,8192,128\]", text)


@pytest.mark.parametrize("rule", ["block_diffusion", "selected", "selected_too_long"])
def test_a_stored_masks_plan_still_lowers_to_the_stock_pair(rule):
    """No longer (ISSUE 68, the issue of their own that ISSUE 64 left them to):
    under either rule whose cut blocks are STORED the backward is ONE
    `pallas_call`, the kernel that reads a stored block of the mask a step and
    keeps dq on the chip, no stock dq or dkv kernel, and under the selected
    rule no loop over the rows.  The stock pair is still what a length takes
    whose accumulators overrun the kernel's VMEM: 65536 positions of 128-wide
    heads are 64 MiB of dq alone."""
    if rule == "selected_too_long":
        plan = masked_attention.selected_plan(65536, 4)
        assert plan.backward == "stock_pair"
        text = _backward_jaxpr(plan, (1, 4, 65536, 128), 2, picks=True)
        assert "name=splash_mha_dq" in text and "name=splash_mha_dkv" in text and "attention_dq_dk_dv" not in text
        return
    plan = masked_attention.selected_plan(1024, 4) if rule == "selected" else masked_attention.plan_of(1024, 4, 4)
    assert plan.backward == "onchip_dq" and plan.stored
    text = _backward_jaxpr(plan, (1, 4, 1024, 128), 2, picks=rule == "selected")
    assert text.count("name=attention_dq_dk_dv") == 1 and "splash_mha_dq" not in text and "splash_mha_dkv" not in text
    # the own-block term's kernel beside it under block diffusion's rule, and nothing else
    assert text.count("pallas_call[") == (1 if rule == "selected" else 2) and ("own_block_backward" in text) == (rule != "selected")
    assert "while[" not in text


def test_a_window_as_long_as_the_sequence_is_the_causal_attention():
    rng = np.random.RandomState(51)
    q, k, v = (rng.randn(2, 2, 24, 8).astype("f4") for _ in range(3))
    with jax.default_matmul_precision("highest"):
        causal = lower_attention("cpu", causal=True)(q, k, v)
        for window in (24, 100):
            agree(lower_attention("cpu", "sliding_window", window)(q, k, v), causal, tol=1e-6)
        narrow = lower_attention("cpu", "sliding_window", 3)(q, k, v)
        assert np.abs(np.asarray(narrow) - np.asarray(causal))[:, :, 3:].max() > 1e-2
        agree(np.asarray(narrow)[:, :, :3], np.asarray(causal)[:, :, :3], tol=1e-6)   # the first rows see the start
        blhd = lower_attention("cpu", "sliding_window", 3, layout="blhd")(*(t.transpose(0, 2, 1, 3) for t in (q, k, v)))
        agree(np.asarray(blhd).transpose(0, 2, 1, 3), narrow, tol=1e-6)


@pytest.mark.parametrize("platform,mask,shape,dtype,mesh,path", [
    ("tpu", ("sliding_window", 512), (1, 40, 8192, 64), "bfloat16", None, "block_sparse"),      # the cell's window layer
    ("tpu", ("sliding_window", 512), (1, 40, 8192, 128), "bfloat16", None, "block_sparse"),
    ("tpu", ("sliding_window", 512), (1, 40, 8192, 64), "float32", None, "xla"),                # unpriced: XLA's
    ("tpu", ("sliding_window", 512), (1, 40, 8192, 32), "bfloat16", None, "xla"),
    ("tpu", ("sliding_window", 512), (1, 40, 8200, 64), "bfloat16", None, "xla"),               # no whole number of blocks
    ("tpu", ("sliding_window", 512), (4, 40, 8192, 64), "bfloat16", ("tp",), "xla"),            # a mesh that splits heads
    ("tpu", ("sliding_window", 512), (4, 40, 8192, 64), "bfloat16", ("dp",), "block_sparse"),   # ... the rows alone
    ("tpu", ("block_diffusion", 4), (2, 32, 8192, 64), "bfloat16", None, "xla"),                # its own-block term: 128s
    ("tpu", ("block_diffusion", 4), (2, 32, 8192, 128), "bfloat16", None, "block_sparse"),
    ("cpu", ("sliding_window", 512), (1, 40, 8192, 64), "bfloat16", None, "xla"),
])
def test_the_attentions_rule_sends_a_window_to_the_splash_kernels_at_64_wide_heads(platform, mask, shape, dtype, mesh, path):
    q = jax.ShapeDtypeStruct(shape, jnp.dtype(dtype))
    k = jax.ShapeDtypeStruct((shape[0], shape[1] // 2) + shape[2:], jnp.dtype(dtype))
    device_mesh = mesh and jax.sharding.Mesh(np.asarray(jax.devices()[:4]), mesh)
    assert nn_ops._attention_path(platform, device_mesh, q, k, mask, batch_axis="dp") == path


def test_the_ops_mask_attribute_is_checked_where_it_is_lowered():
    q = np.ones((1, 2, 8, 4), "f4")
    with pytest.raises(ValueError, match="known masks"):
        lower_attention("cpu", "sliding", 4)(q, q, q)
    with pytest.raises(ValueError, match="known masks"):
        lower_attention("cpu", "sliding_window", 0)(q, q, q)
    with pytest.raises(ValueError, match="as many keys as queries"):
        lower_attention("cpu", "sliding_window", 4)(q, q[:, :, :4], q[:, :, :4])
    with pytest.raises(ValueError, match="2L positions"):
        lower_attention("cpu", "block_diffusion", 3)(q, q, q)


# -- (b) the op `memory_gate` ------------------------------------------------------------------

def test_memory_gate_is_silu_of_the_gate_times_the_memory_and_says_how_both_are():
    rng = np.random.RandomState(52)
    gate, memory = rng.randn(2, 5, 8).astype("f4"), rng.randn(2, 5, 8).astype("f4")
    op = SimpleNamespace(type="memory_gate", attr=lambda n, d=None: d)
    monitor.reset()
    monitor.enable()
    try:
        out = get_op_def("memory_gate").lower(LoweringContext(jax.random.PRNGKey(0)), op,
                                              {"Gate": [jnp.asarray(gate)], "Memory": [jnp.asarray(memory)]})
        assert monitor.get_monitor().counter_values()["lowering.kept_tensor_readers"] == 1
    finally:
        monitor.disable()
        monitor.reset()
    silu = gate / (1 + np.exp(-gate))
    agree(out["Out"], silu * memory, tol=1e-6)
    agree(out["Stats"], [np.abs(memory).mean(), silu.mean(), 1.0], tol=1e-6)
    bad = get_op_def("memory_gate").lower(LoweringContext(jax.random.PRNGKey(0)), op,
                                          {"Gate": [jnp.asarray(gate)], "Memory": [jnp.asarray(memory).at[0, 0, 0].set(jnp.inf)]})
    assert float(bad["Stats"][2]) == 0.0
    low = get_op_def("memory_gate").lower(LoweringContext(jax.random.PRNGKey(0)), op,
                                          {"Gate": [jnp.asarray(gate, jnp.bfloat16)], "Memory": [jnp.asarray(memory, jnp.bfloat16)]})
    assert low["Out"].dtype == jnp.bfloat16 and low["Stats"].dtype == jnp.float32


def test_the_new_op_has_an_infer_rule_a_planner_row_and_a_record():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        gate, memory = layers.data("gate", [5, 8]), layers.data("memory", [5, 8])
        out = layers.memory_gate(gate, memory)
    op = main.global_block().ops[-1]
    assert op.type == "memory_gate" and tuple(out.shape)[1:] == (5, 8)
    assert tuple(main.global_block().var(op.outputs["Stats"][0]).shape) == (3,)
    got, = fluid.Executor(fluid.CPUPlace()).run(main, feed={"gate": np.zeros((2, 5, 8), "f4"), "memory": np.ones((2, 5, 8), "f4")},
                                                fetch_list=[out])
    assert np.asarray(got).shape == (2, 5, 8) and not np.asarray(got).any()      # silu(0) = 0
    with pytest.raises(Exception, match="one shape"):
        with fluid.program_guard(fluid.Program(), fluid.Program()):
            layers.memory_gate(layers.data("g", [5, 8]), layers.data("m", [5, 4]))


# -- (c) the builder ---------------------------------------------------------------------------

KINDS = ["mamba", "sliding_attention", "mamba", "full_attention", "gmu", "cross_attention"]
SMALL = dict(vocab_size=16, seq_len=8, d_model=16, n_heads=2, n_kv_heads=1, qk_norm=None, rotary=False, norm="layer",
             proj_bias=True, conv_kernel=4, mamba=dict(expand=2, state=4, dt_rank=2, inner_norms=False), num_dense_layers=6,
             dense_width=24, tie_embedding=True, with_optimizer=False)


@pytest.mark.parametrize("kinds,over,message", [
    (["gmu", "mamba"] + KINDS[2:], dict(memory_layer=1, kv_layer=3), "layer 0 is a gmu layer and reads what layer memory_layer=1"),
    (KINDS, dict(memory_layer=None, kv_layer=3), "layer 4 is a gmu layer and reads what layer memory_layer=None"),
    (KINDS, dict(memory_layer=2, kv_layer=None), "layer 5 is a cross_attention layer and reads what layer kv_layer=None"),
    (KINDS, dict(memory_layer=2, kv_layer=5), "kv_layer=5 names no layer of kind full_attention / sliding_attention"),
    (KINDS[:3] + ["cross_attention", "gmu", "full_attention"], dict(memory_layer=2, kv_layer=5),
     "layer 3 is a cross_attention layer and reads what layer kv_layer=5"),
    (KINDS, dict(memory_layer=1, kv_layer=3), "memory_layer=1 names no layer of kind mamba"),
    (KINDS, dict(memory_layer=2, kv_layer=3, sliding_window=None), "needs sliding_window="),
    (KINDS, dict(memory_layer=2, kv_layer=3, norm="batch"), "norm='batch'"),
    (KINDS, dict(memory_layer=2, kv_layer=3, loop=2), "a kept tensor does not leave a pass"),
    (KINDS[:4] + ["scan"], dict(memory_layer=2, kv_layer=3), "sliding_attention, gmu or cross_attention"),
])
def test_the_builder_refuses_a_reader_before_what_it_reads(kinds, over, message):
    args = dict(SMALL, layer_types=kinds, sliding_window=4)
    args.update(over)
    with pytest.raises(ValueError, match=message):
        transformer.build_causal_lm(**args)


def test_kept_keys_and_values_are_the_fused_attentions_layout():
    with fluid.program_guard(fluid.Program(), fluid.Program()):
        x = layers.data("x", [8, 16])
        with pytest.raises(ValueError, match="no q/k-norm and no rotary positions"):
            transformer.multi_head_attention(x, 8, 16, 2, "a", dropout_prob=0.0, keep={})
        kept = {}
        transformer.multi_head_attention(x, 8, 16, 2, "a", dropout_prob=0.0, use_fused_attention=True, n_kv_heads=1, keep=kept)
        k, v = kept["kv"]
        assert tuple(k.shape)[1:] == tuple(v.shape)[1:] == (8, 1, 8)
        before = len(fluid.default_main_program().all_parameters())
        transformer.multi_head_attention(x, 8, 16, 2, "b", dropout_prob=0.0, use_fused_attention=True, n_kv_heads=1, kept_kv=kept["kv"])
        names = [p.name for p in fluid.default_main_program().all_parameters()][before:]
        assert sorted(names) == ["b.out.b", "b.out.w", "b.q.b", "b.q.w"]       # no key or value weights


def test_the_plain_mamba_mixer_is_jambas_reference_without_its_inner_norms():
    from paddle_tpu.core import unique_name

    cfg = dict(mf.read_json("benchmark/configs/ai21-jamba2-3b.json"), compute_dtype="float32", hidden_size=64,
               intermediate_size=96, mamba_dt_rank=4, num_attention_heads=4, vocab_size=96, num_hidden_layers=3,
               layer_types=["mamba", "full_attention", "mamba"])
    with pytest.MonkeyPatch.context() as patch, jax.default_matmul_precision("highest"), unique_name.guard():
        from benchmark.models import lfm2

        patch.setattr(lfm2, "LOGIT_SAMPLE", 44)
        patch.setattr(lfm2, "ATTENTION_SAMPLE", 44)
        patch.setattr(jamba, "STAGE_CHANNELS", 64)
        main, startup, feeds, fetches = transformer.build_causal_lm(
            vocab_size=96, seq_len=44, d_model=64, n_heads=4, n_kv_heads=1, qk_norm=None, rotary=False, norm_eps=1e-6,
            layer_types=cfg["layer_types"], conv_kernel=4, mamba=dict(expand=2, state=16, dt_rank=4, inner_norms=False),
            num_dense_layers=3, dense_width=96, tie_embedding=True, with_optimizer=False)
        main.random_seed = startup.random_seed = 5
        scope, exe = fluid.Scope(), fluid.Executor(fluid.TPUPlace(0))
        exe.run(startup, scope=scope)
        params = {p.name: np.asarray(scope.find_var(p.name)) for p in main.all_parameters()}
        assert not [n for n in params if "_norm" in n and ".mamba." in n] and "lm.l0.mamba.x.w" in params
        rows = jamba.make_batch(np.random.RandomState(5), cfg, dict(seq_len=44), 3)
        loss, logits = exe.run(main.clone(for_test=True), feed=rows, fetch_list=[fetches["loss"], fetches["logits"]], scope=scope)
        want = jax.jit(lambda p, b: jamba.reference(p, b, cfg, inner_norms=False)[:2])(params, rows)
        agree(np.asarray(loss).reshape(()), want[0], tol=1e-5)
        agree(logits, want[1], tol=2e-5)
        normed = np.asarray(jax.jit(lambda p, b: jamba.reference(
            dict(p, **{f"lm.l{i}.mamba.{n}_norm.w": np.ones(w, "f4") for i in (0, 2) for n, w in (("dt", 4), ("b", 16), ("c", 16))}),
            b, cfg)[1])(params, rows))
        assert np.abs(normed - np.asarray(want[1])).max() > 1e-4 * np.abs(normed).max()     # the norms are not nothing (20x the agreement)


# -- (d) the model against the reference ---------------------------------------------------------

TINY = dict(hidden_size=64, intermediate_size=96, mamba_dt_rank=4, num_attention_heads=4, num_key_value_heads=2,
            vocab_size=128, sliding_window=8)
JOB = dict(seq_len=64, batch_per_chip=1)


@pytest.fixture(scope="module", autouse=True)
def every_position_is_sampled():
    from benchmark.models import lfm2

    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(lfm2, "LOGIT_SAMPLE", 64)
        patch.setattr(lfm2, "ATTENTION_SAMPLE", 64)
        patch.setattr(jamba, "STAGE_CHANNELS", 64)
        patch.setattr(phi4flash, "STAGE_CHANNELS", 64)
        yield


def tiny_model(dtype, recompute=True, seed=3, lively=False):
    """`lively` scales the Mamba layers' x and dt projections by 10 after the start-up program: at this width B and C are
    0.09 in size (0.46 at the published 5120 channels) and the state's part of a scan's output is under 1% of the skip's
    D x, so that the gradients of everything that only feeds the state (x.w, dt.w, dt.b, A_log) lie near float32's
    noise; the float32 comparisons are made where the state carries weight."""
    from paddle_tpu.core import unique_name

    cfg = dict(mf.read_json("benchmark/configs/phi-4-mini-flash-reasoning.json"), compute_dtype=dtype, **TINY)
    job = dict(mf.read_json("benchmark/traffic/train-sambay-s8192.json"), recompute_layers=recompute, **JOB)
    with unique_name.guard():
        main, startup, feeds, loss, names = phi4flash.build(cfg, job)
    main.random_seed = startup.random_seed = seed
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.TPUPlace(0))
    exe.run(startup, scope=scope)
    for i in (0, 2) if lively else ():
        for name in (f"lm.l{i}.mamba.x.w", f"lm.l{i}.mamba.dt.w"):
            scope.set_var(name, jnp.asarray(np.asarray(scope.find_var(name)) * 10.0))
    return cfg, job, main, loss, names, scope, exe


def params_of(main, scope):
    return {p.name: np.asarray(scope.find_var(p.name)) for p in main.all_parameters()}


def reference_of(cfg, params, rows, **kw):
    return [np.asarray(w) for w in jax.jit(lambda p, b: phi4flash.reference(p, b, cfg, **kw))(params, rows)]


def one_step(main, loss, scope, exe, batch):
    """(loss, every parameter's gradient as Adam's first moment / (1 - beta1), the parameters after) of one step."""
    step_loss, = exe.run(main, feed=batch, fetch_list=[loss], scope=scope)
    grads = {p.name: np.asarray(scope.find_var(p.name + "_moment1_0")) / (1 - 0.9) for p in main.all_parameters()}
    return float(np.asarray(step_loss).reshape(-1)[0]), grads, params_of(main, scope)


@pytest.fixture(scope="module", params=[True, False], ids=["recomputed", "kept"])
def float32_run(request):
    with jax.default_matmul_precision("highest"):
        cfg, job, main, loss, names, scope, exe = tiny_model("float32", recompute=request.param, lively=True)
        rows = phi4flash.make_batch(np.random.RandomState(3), cfg, job, 8)
        got = exe.run(main.clone(for_test=True), feed=rows, fetch_list=list(names), scope=scope)
        before = params_of(main, scope)
        want = reference_of(cfg, before, rows)
        batch = phi4flash.make_batch(np.random.RandomState(4), cfg, job, 4)
        ref_loss, ref_grads = jax.jit(jax.value_and_grad(lambda p: phi4flash.reference(p, batch, cfg)[0]))(before)
        step_loss, grads, after = one_step(main, loss, scope, exe, batch)
        ops = [op.type for op in main.global_block().ops]
        segments = {op.attrs.get("recompute_segment") for op in main.global_block().ops} - {None}
    return SimpleNamespace(cfg=cfg, job=job, got=got, want=want, ops=ops, before=before, after=after, grads=grads, rows=rows,
                           batch=batch, ref_loss=float(ref_loss), step_loss=step_loss, segments=segments,
                           recompute=request.param, ref_grads={k: np.asarray(v) for k, v in ref_grads.items()})


def test_float32_loss_logits_and_every_stage_agree_with_the_reference(float32_run):
    r = float32_run
    found = phi4flash.compare(r.got, r.want)
    assert found["loss_error"] < 1e-5 and found["logit_error"] < 2e-5, found
    assert max(found[key] for key, _ in phi4flash.LIMITS) < 2e-5, found
    assert phi4flash.failed_limits(found) == [] and phi4flash.reference_error(r.got, r.want) < 2e-5
    assert abs(r.step_loss - r.ref_loss) < 1e-5 * r.ref_loss
    assert len(r.segments) == (6 if r.recompute else 0)
    assert np.asarray(r.got[1]).shape == (64, 8, 128)
    assert np.asarray(r.got[16]).shape == (phi4flash.STAGE_ROWS, 64, 4, 16)      # the window layer's q, as handed
    assert np.asarray(r.got[21]).shape == (phi4flash.STAGE_ROWS, 64, 2, 16)      # the K the cross layer read
    assert np.asarray(r.got[25]).shape == (phi4flash.STAGE_ROWS, 64, 64)         # the memory the GMU read


MAMBA_PARAMS = ("in.w", "conv.w", "conv.b", "x.w", "dt.w", "dt.b", "a_log", "d", "out.w")
PARAMS = sorted(
    ["lm.tok_emb", "lm.final_norm.w", "lm.final_norm.b"]
    + [f"lm.l{i}.{n}" for i in range(6) for n in ("ln1.w", "ln1.b", "ln2.w", "ln2.b", "ffn.gate.w", "ffn.up.w", "ffn.down.w")]
    + [f"lm.l{i}.mamba.{n}" for i in (0, 2) for n in MAMBA_PARAMS]
    + [f"lm.l{i}.attn.{n}.{p}" for i in (1, 3) for n in ("q", "k", "v", "out") for p in "wb"]
    + ["lm.l4.gmu.in.w", "lm.l4.gmu.out.w"]
    + [f"lm.l5.attn.{n}.{p}" for n in ("q", "out") for p in "wb"])


def test_the_tiny_model_has_these_layers_parameters_and_no_other(float32_run):
    r = float32_run
    assert sorted(r.before) == PARAMS
    assert sum(v.size for v in r.before.values()) == phi4flash.parameters(r.cfg)
    assert (r.ops.count("selective_scan"), r.ops.count("short_conv"), r.ops.count("fused_attention"), r.ops.count("memory_gate"),
            r.ops.count("rms_norm"), r.ops.count("rotary_embedding")) == (2, 2, 3, 1, 0, 0)
    assert (r.before["lm.l0.ln1.w"] == 1).all() and not r.before["lm.l0.ln1.b"].any()      # a decoder's norms start at 1 and 0
    assert r.before["lm.l1.attn.q.b"].std() > 0.005                                          # the biases are drawn
    shapes = {n: r.before[n].shape for n in ("lm.l1.attn.k.w", "lm.l1.attn.k.b", "lm.l4.gmu.in.w", "lm.l4.gmu.out.w",
                                            "lm.l5.attn.q.w", "lm.l2.mamba.x.w")}
    assert shapes == {"lm.l1.attn.k.w": (64, 32), "lm.l1.attn.k.b": (32,), "lm.l4.gmu.in.w": (64, 128),
                      "lm.l4.gmu.out.w": (128, 64), "lm.l5.attn.q.w": (64, 64), "lm.l2.mamba.x.w": (128, 36)}


@pytest.mark.parametrize("name", PARAMS)
def test_float32_gradient_and_adam_step_agree_with_the_reference(float32_run, name):
    """Adam's first moment after one step is 0.1 x the gradient; the kept
    tensors' gradients are sums over their readers, across the segments'
    boundaries where the layers are made again."""
    r = float32_run
    agree(r.grads[name], r.ref_grads[name], tol=2e-4, floor=1e-5)
    moved = np.abs(r.after[name] - r.before[name]).max()
    # the warm-up's first rate, 1e-6, where the gradient stands over Adam's epsilon; a bias on the KEYS adds one number to
    # all of a query's scores, which a softmax does not see: its gradient is zero but for rounding
    if name.endswith("attn.k.b"):
        assert np.abs(r.ref_grads[name]).max() < 1e-8 and moved < 1e-7
    else:
        assert 0.3e-6 < moved < 4e-6, moved


#: read through a kept tensor: the full layer's key and value weights through the cross layer, the boundary Mamba
#: layer's through the Gated Memory Unit
THROUGH_KEPT = (["lm.l3.attn.k.w", "lm.l3.attn.v.w", "lm.l3.attn.v.b"]
                + [f"lm.l2.mamba.{n}" for n in ("in.w", "conv.w", "conv.b", "x.w", "dt.w", "dt.b", "a_log", "d")])


@pytest.fixture(scope="module")
def through_the_readers_alone(float32_run):
    """One step from the run's parameters with the two KEEPING layers' out
    projections at zero: nothing of a loss's gradient reaches their operators'
    other parameters but through what they handed on."""
    r = float32_run
    closed = dict(r.before, **{n: np.zeros_like(r.before[n]) for n in ("lm.l2.mamba.out.w", "lm.l3.attn.out.w", "lm.l3.attn.out.b")})
    with jax.default_matmul_precision("highest"):
        cfg, job, main, loss, names, scope, exe = tiny_model("float32", recompute=r.recompute, lively=True)
        for name, value in closed.items():
            scope.set_var(name, jnp.asarray(value))
        _, grads, _ = one_step(main, loss, scope, exe, r.batch)
        want = jax.jit(jax.grad(lambda p: phi4flash.reference(p, r.batch, r.cfg)[0]))(closed)
    return grads, {k: np.asarray(v) for k, v in want.items()}


@pytest.mark.parametrize("name", THROUGH_KEPT + ["lm.l3.attn.q.w", "lm.l2.mamba.out.w"])
def test_the_gradient_through_a_kept_tensor_alone_is_the_references(through_the_readers_alone, float32_run, name):
    grads, want = through_the_readers_alone
    if name == "lm.l3.attn.q.w":       # the keeping layer's queries are read by no other layer
        assert not grads[name].any() and not want[name].any()
        return
    assert np.abs(want[name]).max() > 1e-8, "the reader's gradient reaches it"     # (float32's noise here is ~1e-12)
    agree(grads[name], want[name], tol=1e-3, floor=1e-8)
    if name in THROUGH_KEPT:           # ... and is a part of the whole gradient, not all of it
        whole = float32_run.ref_grads[name]
        assert np.abs(whole - want[name]).max() > 1e-3 * np.abs(whole).max()


def test_bfloat16_agrees_within_the_benchmarks_tolerances():
    cfg, job, main, loss, names, scope, exe = tiny_model("bfloat16")
    rows = phi4flash.make_batch(np.random.RandomState(3), cfg, job, 8)
    got = exe.run(main.clone(for_test=True), feed=rows, fetch_list=list(names), scope=scope)
    want = reference_of(cfg, params_of(main, scope), rows)
    found = phi4flash.compare(got, want)
    assert 1e-4 < found["logit_error"] < phi4flash.REFERENCE_RTOL and found["loss_error"] < 1e-3, found
    assert found["conv_error"] < phi4flash.CONV_RTOL < found["conv_error_bf16"]
    assert found["gmu_error"] < phi4flash.GMU_RTOL < found["gmu_error_bf16"], found
    assert phi4flash.failed_limits(found) == [], found
    assert phi4flash.reference_error(got, want) == max(found["loss_error"], found["logit_error"])


@pytest.mark.parametrize("fault,limit", [("window_as_causal", "WINDOW_RTOL"), ("cross_own_kv", "KEPT_KV_RTOL"),
                                         ("gmu_gated_memory", "MEMORY_RTOL")])
def test_a_fault_in_the_reference_is_refused_by_a_limit(fault, limit, float32_run):
    r = float32_run
    want = reference_of(r.cfg, r.before, r.rows, fault=fault)
    missed = phi4flash.failed_limits(phi4flash.compare(r.got, want, fault=fault))
    assert limit in missed, missed


def test_a_step_counts_the_two_readers_and_publishes_the_memory_record():
    from benchmark import program_trace

    monitor.reset()
    monitor.enable()
    try:
        cfg, job, main, loss, names, scope, exe = tiny_model("float32")
        feeds = [main.global_block().var(n) for n in phi4flash.FEEDS]
        loader = fluid.DataLoader.from_generator(feeds, capacity=2)
        batches = [phi4flash.make_batch(np.random.RandomState(i), cfg, job, 2) for i in range(4)]
        loader.set_batch_generator(lambda: iter(batches))
        fluid.train_loop(exe, main, loader, [loss], scope=scope, max_inflight=1, log_period=2)
        seen = monitor.get_monitor().counter_values()
        assert seen["lowering.kept_tensor_readers"] == 2          # the GMU's gate and the cross layer's attention
        assert seen["lowering.attention_xla"] == 3 and not seen.get("lowering.window_attention_ops")   # off the TPU: XLA's
        lowered = [e[5] for e in monitor.get_monitor().events() if e[0] == "executor.lower"][-1]
        assert lowered["kept_tensor_readers"] == 2
        records = [x for x in program_trace.program_monitor().step_records() if x.get("kind") == "gmu_memory"]
        assert records and all(x["finite"] and len(x["memory_abs_mean"]) == len(x["gate_mean"]) == 1 for x in records)
        assert all(x["memory_abs_mean"][0] > 0 for x in records)
        assert [x for x in program_trace.program_monitor().step_records() if x.get("kind") == "ssm_state"]
    finally:
        monitor.disable()
        monitor.reset()
