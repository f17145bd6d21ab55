"""LFM2-8B-A1B's parts and the whole, tiny on the CPU (ISSUE 34).

(a) `short_conv`, the gated short convolution, against a golden written tap
    by tap: forward and the gradients of its three streams and of the filter,
    K = 3 and another K, and a sequence shorter than K;
(b) `moe_router` with sigmoid scores and a bias that enters the choice alone:
    a bias that flips a choice leaves the weights those of the unbiased
    scores; the 1e-6; the scaling factor; the count of moved choices; and the
    defaults, which are the 2024 router;
(c) a tiny `build_causal_lm` (a dense short-convolution layer, an attention
    layer and three more convolution layers, all four sparse, a tied head) in
    float32 against the benchmark's reference (benchmark/models/lfm2.py) on
    seeded weights: loss, logits, routing, every parameter's gradient, the tied
    table's equal to the sum of its two uses';
(d) the same in bf16 within the benchmark's tolerances, and the stage readings
    a precision lower over their limits;
(e) the four shares of 8 experts, added up, give what the uncut layer of 32
    gives, for THIS router;
(f) steps through `train_loop` publish the share of the choices the bias moved.
"""
import functools
import json
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
from benchmark.models import lfm2  # noqa: E402
from paddle_tpu import layers, monitor  # noqa: E402
from paddle_tpu.core.lowering import LoweringContext  # noqa: E402
from paddle_tpu.core.registry import get_op_def  # noqa: E402
from paddle_tpu.models import transformer  # noqa: E402


def lower(op_type, ins, attrs=None):
    """One op's lowering called as the interpreter calls it."""
    attrs = attrs or {}
    op = SimpleNamespace(type=op_type, attr=lambda n, d=None: attrs.get(n, d))
    ctx = LoweringContext(jax.random.PRNGKey(0))
    return get_op_def(op_type).lower(ctx, op, {k: [jnp.asarray(v)] for k, v in ins.items()})


def agree(got, want, tol=1e-5):
    got, want = np.asarray(got, "f8"), np.asarray(want, "f8")
    assert got.shape == want.shape
    assert np.abs(got - want).max() <= tol * max(np.abs(want).max(), 1e-12), \
        (np.abs(got - want).max(), np.abs(want).max())


# -- (a) the gated short convolution ------------------------------------------------

def conv_golden(x3, w):
    """out[b, t, c] = C . sum_j w[c, j] . (B u)[b, t - (K - 1) + j, c], a term
    at a time, nothing before the sequence's start."""
    d, taps = w.shape
    gate_in, gate_out, u = x3[..., :d], x3[..., d:2 * d], x3[..., 2 * d:]
    z = gate_in * u
    out = jnp.zeros_like(z)
    for t in range(x3.shape[1]):
        for j in range(taps):
            at = t - (taps - 1) + j
            if at >= 0:
                out = out.at[:, t].add(w[:, j] * z[:, at])
    return gate_out * out


@pytest.mark.parametrize("taps,length", [(3, 9), (4, 7), (3, 2), (4, 1), (1, 5)])
def test_short_conv_golden_forward_and_gradients(taps, length):
    rng = np.random.RandomState(taps * 10 + length)
    x3 = rng.randn(2, length, 3 * 5).astype("f4")
    w = rng.randn(5, taps).astype("f4")
    weigh = rng.randn(2, length, 5).astype("f4")   # a loss that weighs every output element apart

    def through_the_op(x3, w):
        return jnp.sum(lower("short_conv", {"X": x3, "Filter": w})["Out"] * weigh)

    agree(lower("short_conv", {"X": x3, "Filter": w})["Out"], conv_golden(jnp.asarray(x3), jnp.asarray(w)), tol=1e-6)
    got = jax.grad(through_the_op, argnums=(0, 1))(jnp.asarray(x3), jnp.asarray(w))
    want = jax.grad(lambda a, b: jnp.sum(conv_golden(a, b) * weigh), argnums=(0, 1))(jnp.asarray(x3), jnp.asarray(w))
    for stream in range(3):  # B, C and u: the op's three gradients, and the filter's
        agree(got[0][..., stream * 5:(stream + 1) * 5], want[0][..., stream * 5:(stream + 1) * 5], tol=1e-5)
    agree(got[1], want[1], tol=1e-5)
    # the numpy form the benchmark's stage check uses is the same function
    agree(lfm2._conv(x3, w), conv_golden(jnp.asarray(x3), jnp.asarray(w)), tol=1e-6)


def test_short_conv_is_causal_and_rounds_once_from_float32():
    rng = np.random.RandomState(5)
    x3 = rng.randn(1, 12, 3 * 4).astype("f4")
    w = rng.randn(4, 3).astype("f4")
    whole = np.asarray(lower("short_conv", {"X": x3, "Filter": w})["Out"])
    later = x3.copy()
    later[:, 7:] += 1.0   # nothing before position 7 may change
    assert np.array_equal(np.asarray(lower("short_conv", {"X": later, "Filter": w})["Out"])[:, :7], whole[:, :7])
    # a bf16 activation: computed in float32 from the bf16 values and rounded once
    low = jnp.asarray(x3).astype(jnp.bfloat16)
    out = lower("short_conv", {"X": low, "Filter": w})["Out"]
    assert out.dtype == jnp.bfloat16 and out.shape == (1, 12, 4)
    want = lfm2._conv(np.asarray(low.astype(jnp.float32)), w)
    assert np.array_equal(np.asarray(out.astype(jnp.float32)), lfm2._bf16(want))


def test_layers_short_conv_declares_two_projections_and_a_filter_and_refuses_other_shapes():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = layers.data("x", [6, 8], dtype="float32")
        y = layers.short_conv(x, kernel_size=3, in_attr="c.in", filter_attr="c.filter", out_attr="c.out")
    assert tuple(y.shape)[1:] == (6, 8)
    assert {p.name: tuple(p.shape) for p in main.all_parameters()} == {"c.in": (8, 24), "c.filter": (8, 3), "c.out": (8, 8)}
    assert [op.type for op in main.global_block().ops] == ["mul", "short_conv", "mul"]
    with pytest.raises(Exception, match=r"X must be \(b, T, 3d\)"):
        with fluid.program_guard(fluid.Program(), fluid.Program()):
            x = layers.data("x", [6, 8], dtype="float32")
            helper = fluid.core.layer_helper.LayerHelper("bad")
            w = helper.create_parameter("w", [8, 3], "float32")
            helper.append_op("short_conv", inputs={"X": [x.name], "Filter": [w.name]},
                             outputs={"Out": [helper.create_variable_for_type_inference("float32").name]})


def test_the_cost_row_counts_the_taps_and_the_ops_own_bytes():
    from paddle_tpu.core import resource_plan

    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = layers.data("x", [6, 8], dtype="float32")
        layers.short_conv(x, kernel_size=3)
    plan = resource_plan.plan_program(main, feed_shapes={"x": (2, 6, 8)})
    row = next(r for r in plan.rows if r.op_type == "short_conv")
    assert row.flops == (2 + 2 * 3) * 2 * 6 * 8
    assert row.traffic_bytes == 4 * (2 * 6 * 24 + 8 * 3 + 2 * 6 * 8)


# -- (b) the router ------------------------------------------------------------------

def sigmoid(x):
    return 1.0 / (1.0 + np.exp(-np.asarray(x, "f8")))


def test_a_bias_that_flips_a_choice_leaves_the_weights_those_of_the_unbiased_scores():
    rng = np.random.RandomState(7)
    x = rng.randn(6, 16).astype("f4")
    w = rng.randn(16, 8).astype("f4") / 4
    scores = sigmoid(x.astype("f8") @ w.astype("f8"))
    order = np.argsort(-scores, -1)
    # lift every token's THIRD expert over its second: the bias of that one expert, for token 0
    bias = np.zeros(8, "f4")
    bias[order[0, 2]] = 1.0
    attrs = {"top_k": 2, "norm_topk_prob": True, "scoring": "sigmoid", "norm_eps": 1e-6}
    out = lower("moe_router", {"X": x, "W": w, "Bias": bias}, attrs)
    plain = lower("moe_router", {"X": x, "W": w}, attrs)
    assert "BiasMoved" not in plain and sorted(np.asarray(plain["TopKIndex"])[0]) == sorted(order[0, :2])
    chosen = np.asarray(out["TopKIndex"])
    assert order[0, 2] in chosen[0] and order[0, 1] not in chosen[0]     # the bias picked
    want_choice = np.argsort(-(scores + bias), -1)[:, :2]
    assert np.array_equal(np.sort(chosen, -1), np.sort(want_choice, -1))
    mine = np.take_along_axis(scores, chosen.astype("int64"), -1)          # ... and did not weigh
    agree(out["TopKProb"], mine / (mine.sum(-1, keepdims=True) + 1e-6), tol=1e-6)
    moved = sum(len(set(want_choice[t]) - set(order[t, :2])) for t in range(6))
    assert int(np.asarray(out["BiasMoved"])[0]) == moved >= 1
    assert np.array_equal(np.asarray(out["Load"]), np.bincount(chosen.reshape(-1), minlength=8))
    # a zero bias moves nothing and chooses as no bias does
    zero = lower("moe_router", {"X": x, "W": w, "Bias": np.zeros(8, "f4")}, attrs)
    assert int(np.asarray(zero["BiasMoved"])[0]) == 0
    assert np.array_equal(np.asarray(zero["TopKIndex"]), np.asarray(plain["TopKIndex"]))
    agree(zero["TopKProb"], plain["TopKProb"], tol=1e-7)


@pytest.mark.parametrize("eps,scaling", [(0.0, 1.0), (1e-6, 1.0), (0.5, 2.5)])
def test_the_renormalisations_epsilon_and_the_scaling_factor(eps, scaling):
    rng = np.random.RandomState(9)
    x, w = rng.randn(5, 8).astype("f4"), rng.randn(8, 6).astype("f4")
    attrs = {"top_k": 3, "norm_topk_prob": True, "scoring": "sigmoid", "norm_eps": eps,
             "routed_scaling_factor": scaling}
    out = lower("moe_router", {"X": x, "W": w}, attrs)
    top = -np.sort(-sigmoid(x.astype("f8") @ w.astype("f8")), -1)[:, :3]
    agree(out["TopKProb"], top / (top.sum(-1, keepdims=True) + eps) * scaling, tol=1e-6)
    if eps == 0.5:  # the weights no longer sum to the scaling factor: the epsilon is in the sum
        assert np.all(np.asarray(out["TopKProb"]).sum(-1) < scaling * 0.9)


def test_the_routers_defaults_are_the_softmax_router_and_layers_moe_writes_no_new_attribute():
    rng = np.random.RandomState(11)
    x, w = rng.randn(7, 8).astype("f4"), rng.randn(8, 6).astype("f4")
    out = lower("moe_router", {"X": x, "W": w}, {"top_k": 2, "norm_topk_prob": True})
    logits = x.astype("f8") @ w.astype("f8")
    probs = np.exp(logits - logits.max(-1, keepdims=True))
    probs /= probs.sum(-1, keepdims=True)
    top = -np.sort(-probs, -1)[:, :2]
    agree(out["TopKProb"], top / top.sum(-1, keepdims=True), tol=1e-6)
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        layers.moe(layers.data("x", [4, 8], dtype="float32"), 6, 4, 2, norm_topk_prob=True)
    router = next(op for op in main.global_block().ops if op.type == "moe_router")
    assert sorted(router.attrs) == ["norm_topk_prob", "top_k"] and sorted(router.inputs) == ["W", "X"]
    assert "BiasMoved" not in router.outputs


def test_layers_moe_with_a_bias_holds_a_buffer_that_is_no_parameter():
    from paddle_tpu.core.initializer import NormalInitializer
    from paddle_tpu.core.param_attr import ParamAttr

    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = layers.data("x", [4, 8], dtype="float32")
        out, _, _ = layers.moe(x, 6, 4, 2, norm_topk_prob=True, scoring="sigmoid", norm_eps=1e-6,
                               bias_attr=ParamAttr(name="r.bias", initializer=NormalInitializer(0.0, 0.5, 17)))
        fluid.optimizer.Adam(1e-3).minimize(layers.mean(out))
    assert "r.bias" not in {p.name for p in main.all_parameters()}
    router = next(op for op in main.global_block().ops if op.type == "moe_router")
    assert router.inputs["Bias"] == ["r.bias"] and router.attrs["scoring"] == "sigmoid"
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup, scope=scope)
    names = set(scope.var_names())
    assert "r.bias" in names and not any(n.startswith("r.bias_") for n in names)   # no moment of its own
    before = np.asarray(scope.find_var("r.bias")).copy()
    agree(before, 0.5 * jax.random.normal(jax.random.PRNGKey(17), (6,), jnp.float32), tol=1e-7)
    exe.run(main, feed={"x": np.random.RandomState(0).randn(3, 4, 8).astype("f4")}, scope=scope)
    assert np.array_equal(np.asarray(scope.find_var("r.bias")), before)   # no step writes it
    with pytest.raises(Exception, match="neither softmax nor sigmoid"):
        with fluid.program_guard(fluid.Program(), fluid.Program()):
            layers.moe(layers.data("x", [4, 8], dtype="float32"), 6, 4, 2, scoring="tanh")


# -- (c), (d) the whole model against the benchmark's reference --------------------------

TINY = dict(hidden_size=64, num_attention_heads=4, num_key_value_heads=2, head_dim=16, intermediate_size=96,
            moe_intermediate_size=32, num_experts=4, num_routed_experts=16, experts_held_first=4,
            num_experts_per_tok=2, vocab_size=96, expert_bias_std=0.05)
JOB = dict(seq_len=32, batch_per_chip=4)


@pytest.fixture(scope="module", autouse=True)
def every_position_is_sampled():
    """At 32 positions the logits of all are compared (the hash is a bijection
    modulo a power of two) and every query's attention is checked."""
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(lfm2, "LOGIT_SAMPLE", 32)
        patch.setattr(lfm2, "ATTENTION_SAMPLE", 32)
        yield


def tiny_model(dtype, sizes=TINY, job=JOB):
    cfg = dict(mf.read_json("benchmark/configs/lfm2-8b-a1b.json"), compute_dtype=dtype, **sizes)
    job = dict(mf.read_json("benchmark/traffic/train-s8192.json"), **job)
    main, startup, feeds, loss, names = lfm2.build(cfg, job)
    main.random_seed = startup.random_seed = 3
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.TPUPlace(0))
    exe.run(startup, scope=scope)
    return cfg, job, main, loss, names, scope, exe


def params_of(main, scope):
    return {p.name: np.asarray(scope.find_var(p.name)) for p in main.all_parameters()}


def reference_of(cfg, params, rows):
    return [np.asarray(w) for w in jax.jit(lambda p, b: lfm2.reference(p, b, cfg))(params, rows)]


@pytest.fixture(scope="module")
def float32_run():
    """The tiny float32 model: the for_test clone's fetches on 8 rows, the
    reference's, the reference's gradients on 4 rows, and the program's state
    after one training step on those 4."""
    from paddle_tpu.core import unique_name

    with unique_name.guard():
        cfg, job, main, loss, names, scope, exe = tiny_model("float32")
        rows = lfm2.make_batch(np.random.RandomState(3), cfg, job, 8)
        got = exe.run(main.clone(for_test=True), feed=rows, fetch_list=list(names), scope=scope)
        before = params_of(main, scope)
        want = reference_of(cfg, before, rows)
        batch = lfm2.make_batch(np.random.RandomState(4), cfg, job, 4)
        ref_loss, ref_grads = jax.jit(jax.value_and_grad(
            lambda p: lfm2.reference(p, batch, cfg)[0]))(before)

        def one_use(lookup, head):  # the table's two uses told apart: the same values as an untied head
            untied = dict(before, **{"lm.tok_emb": lookup, "lm.head.w": head.T})
            return lfm2.reference(untied, batch, dict(cfg, tie_word_embeddings=False))[0]

        by_use = jax.jit(jax.grad(one_use, argnums=(0, 1)))(before["lm.tok_emb"], before["lm.tok_emb"])
        step_loss, = exe.run(main, feed=batch, fetch_list=[loss], scope=scope)
        after = params_of(main, scope)
        moments = {n: np.asarray(scope.find_var(n + "_moment1_0")) for n in before}
        ops = [op.type for op in main.global_block().ops]
    return SimpleNamespace(cfg=cfg, job=job, got=got, want=want, rows=rows, names=names, ops=ops,
                           before=before, after=after, moments=moments, by_use=[np.asarray(g) for g in by_use],
                           ref_loss=float(ref_loss), step_loss=float(np.asarray(step_loss).reshape(-1)[0]),
                           ref_grads={k: np.asarray(v) for k, v in ref_grads.items()})


def test_float32_loss_logits_and_routing_agree_with_the_reference(float32_run):
    found = lfm2.compare(float32_run.got, float32_run.want)
    assert found["left_out"] == found["routed_differently"] == found["routed_differently_above_margin"] == 0
    assert found["loss_error"] < 1e-5 and found["logit_error"] < 1e-5, found
    assert max(found["router_prob_error"], found["experts_error"], found["conv_error"],
               found["attention_error"], found["qk_error"]) < 1e-5, found
    assert found["biases_differ"] == 0 and found["bias_moved"] > 0     # the bias is no zero added
    assert lfm2.reference_error(float32_run.got, float32_run.want) < 1e-5
    assert abs(float32_run.step_loss - float32_run.ref_loss) < 1e-5 * float32_run.ref_loss
    assert np.asarray(float32_run.got[1]).shape == (32, 8, 96)   # the sampled positions, every row, the slice


PARAMS = sorted(
    ["lm.tok_emb", "lm.final_norm.w", "lm.l0.ffn.gate.w", "lm.l0.ffn.up.w", "lm.l0.ffn.down.w"]
    + [f"lm.l{i}.{n}" for i in range(5) for n in ("ln1.w", "ln2.w")]
    + [f"lm.l{i}.conv.{n}.w" for i in (0, 2, 3, 4) for n in ("in", "filter", "out")]
    + [f"lm.l1.attn.{n}" for n in ("q.w", "k.w", "v.w", "out.w", "q_norm.w", "k_norm.w")]
    + [f"lm.l{i}.moe.{n}.w" for i in range(1, 5) for n in ("router", "gate", "up", "down")])


def test_the_tiny_model_has_these_layers_parameters_and_no_other(float32_run):
    """One builder, the kinds from the configuration: a dense short-convolution
    layer, a sparse attention layer, three sparse short-convolution layers, the
    head a matmul over the embedding's own table."""
    r = float32_run
    assert sorted(r.before) == PARAMS                       # no lm.head.w, no router bias
    assert r.ops.count("short_conv") == 4 and r.ops.count("fused_attention") == 1
    assert r.ops.count("moe_router") == r.ops.count("moe_experts") == 4 and r.ops.count("swish") == 1
    assert r.ops.count("lookup_table") + r.ops.count("lookup_table_v2") == 1 and r.ops.count("matmul") == 1
    shapes = {n: r.before[n].shape for n in ("lm.l0.conv.in.w", "lm.l0.conv.filter.w", "lm.l0.conv.out.w",
                                            "lm.l0.ffn.gate.w", "lm.l0.ffn.down.w", "lm.l1.attn.k.w",
                                            "lm.l1.attn.q_norm.w", "lm.l2.moe.router.w", "lm.l2.moe.gate.w")}
    assert shapes == {"lm.l0.conv.in.w": (64, 192), "lm.l0.conv.filter.w": (64, 3), "lm.l0.conv.out.w": (64, 64),
                      "lm.l0.ffn.gate.w": (64, 96), "lm.l0.ffn.down.w": (96, 64), "lm.l1.attn.k.w": (64, 32),
                      "lm.l1.attn.q_norm.w": (16,), "lm.l2.moe.router.w": (64, 16), "lm.l2.moe.gate.w": (4, 64, 32)}
    with pytest.raises(ValueError, match="full_attention or conv"):
        transformer.build_causal_lm(vocab_size=8, seq_len=4, d_model=8, n_heads=2, layer_types=["conv", "scan"])


@pytest.mark.parametrize("n_layers,ok", [(None, True), (2, True), (16, False), (3, False)])
def test_the_depth_is_stated_once(n_layers, ok):
    """`layer_types` states the depth; an `n_layers` beside it that says another is an error, not ignored."""
    build = functools.partial(transformer.build_causal_lm, vocab_size=8, seq_len=4, d_model=8, n_heads=2, expert_width=8,
                              num_experts=2, top_k=1, with_optimizer=False, layer_types=["conv", "full_attention"])
    if ok:
        ops = [op.type for op in build(n_layers=n_layers)[0].global_block().ops]
        assert ops.count("short_conv") == ops.count("fused_attention") == 1
    else:
        with pytest.raises(ValueError, match="layer_types alone states the depth"):
            build(n_layers=n_layers)


@pytest.mark.parametrize("name", PARAMS)
def test_float32_gradient_and_adam_step_agree_with_the_reference(float32_run, name):
    """Adam's first moment after one step is 0.1 x the gradient (to 1e-4 of its
    largest element); the parameter moves by the warm-up's first rate, 1e-6,
    times sign(g) where |g| is far above eps."""
    r = float32_run
    g = r.ref_grads[name]
    agree(r.moments[name] / (1 - 0.9), g, tol=1e-4)
    moved = np.abs(r.after[name] - r.before[name]).max()
    assert 0.5e-6 < moved < 4e-6, moved   # lr_t = 1e-6 x sqrt(1 - 0.95) / (1 - 0.9) = 2.2e-6


def test_the_tied_tables_gradient_is_the_sum_of_its_two_uses(float32_run):
    r = float32_run
    lookup, head = r.by_use
    assert np.abs(lookup).max() > 0 and np.abs(head).max() > 0
    agree(r.ref_grads["lm.tok_emb"], lookup + head, tol=1e-5)
    agree(r.moments["lm.tok_emb"] / (1 - 0.9), lookup + head, tol=1e-4)    # one gradient, summed once
    assert np.abs(lookup + head - head).max() > 1e-3 * np.abs(head).max()  # neither use alone


def test_bfloat16_agrees_within_the_benchmarks_tolerances(capsys):
    cfg, job, main, loss, names, scope, exe = tiny_model("bfloat16")
    rows = lfm2.make_batch(np.random.RandomState(3), cfg, job, 8)
    got = exe.run(main.clone(for_test=True), feed=rows, fetch_list=list(names), scope=scope)
    want = reference_of(cfg, params_of(main, scope), rows)
    found = lfm2.compare(got, want)
    assert found["tokens"] == 8 * 32 and found["routed_differently_above_margin"] == 0
    assert found["left_out"] <= found["routed_differently"] <= 0.2 * found["tokens"]   # 16 outputs of 64 features: near ties
    assert 1e-4 < found["logit_error"] < lfm2.REFERENCE_RTOL and found["loss_error"] < 1e-3
    assert found["router_prob_error"] < lfm2.ROUTER_RTOL and found["experts_error"] < lfm2.EXPERTS_RTOL
    assert found["conv_error"] < lfm2.CONV_RTOL < found["conv_error_bf16"]
    assert found["router_prob_error_bf16_logits"] > lfm2.ROUTER_RTOL
    assert found["attention_error"] < lfm2.ATTENTION_RTOL and found["qk_error"] < lfm2.QK_RTOL
    assert lfm2.reference_error(got, want) == max(found["loss_error"], found["logit_error"])
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["info"] == "reference_routing" and line["left_out"] == found["left_out"]


FAULTS = ["filter_reversed", "conv_looks_ahead", "bias_weighs", "no_epsilon_where_it_counts", "conv_in_bf16"]


@pytest.mark.parametrize("fault", FAULTS)
def test_the_reference_check_fails_on(fault, monkeypatch):
    """A program that computes something else under the same names is not
    correct: the short convolution with its taps reversed or looking ahead, a
    bias that also weighs, and a convolution computed in bf16 throughout."""
    from paddle_tpu.ops import moe_ops

    dtype = "float32"
    if fault == "filter_reversed":
        real = moe_ops._gated_short_conv
        monkeypatch.setattr(moe_ops, "_gated_short_conv", lambda x, w: real(x, w[:, ::-1]))
    elif fault == "conv_looks_ahead":
        real = moe_ops._gated_short_conv
        monkeypatch.setattr(moe_ops, "_gated_short_conv", lambda x, w: real(x[:, ::-1], w)[:, ::-1])
    elif fault == "conv_in_bf16":
        dtype = "bfloat16"

        def rounded(x, w):
            b = lambda t: t.astype(jnp.bfloat16)  # noqa: E731
            gate_in, gate_out, u = jnp.split(x, 3, axis=-1)
            z, acc = b(gate_in * u), None
            for j in range(w.shape[1]):
                back = w.shape[1] - 1 - j
                term = b(jnp.pad(z, ((0, 0), (back, 0), (0, 0)))[:, :z.shape[1]] * b(w[:, j]))
                acc = term if acc is None else b(acc + term)
            return b(gate_out * acc)

        monkeypatch.setattr(moe_ops, "_gated_short_conv", rounded)
    elif fault in ("bias_weighs", "no_epsilon_where_it_counts"):
        real = get_op_def("moe_router").lower

        def wrong(ctx, op, ins):
            outs = dict(real(ctx, op, ins))
            if fault == "bias_weighs":  # the weights from the BIASED scores
                x = ins["X"][0].reshape(-1, ins["X"][0].shape[-1]).astype(jnp.float32)
                biased = jax.nn.sigmoid(x @ ins["W"][0]) + ins["Bias"][0]
                top = jnp.take_along_axis(biased, outs["TopKIndex"].reshape(-1, outs["TopKIndex"].shape[-1]), -1)
                outs["TopKProb"] = (top / (top.sum(-1, keepdims=True) + 1e-6)).reshape(outs["TopKProb"].shape)
            else:  # an epsilon a thousand times the published one
                outs["TopKProb"] = outs["TopKProb"] * (1.0 / (1.0 + 1e-3))
            return outs

        monkeypatch.setattr(get_op_def("moe_router"), "lower", wrong)
    cfg, job, main, loss, names, scope, exe = tiny_model(dtype)
    rows = lfm2.make_batch(np.random.RandomState(3), cfg, job, 8)
    got = exe.run(main.clone(for_test=True), feed=rows, fetch_list=list(names), scope=scope)
    want = reference_of(cfg, params_of(main, scope), rows)
    found = lfm2.compare(got, want)
    stage = {"filter_reversed": "conv_error", "conv_looks_ahead": "conv_error", "conv_in_bf16": "conv_error",
             "bias_weighs": "router_prob_error", "no_epsilon_where_it_counts": "router_prob_error"}[fault]
    limit = {"conv_error": lfm2.CONV_RTOL, "router_prob_error": lfm2.ROUTER_RTOL}[stage]
    assert found[stage] > limit, found
    assert lfm2.reference_error(got, want) == float("inf")


def test_a_bias_that_is_not_the_configurations_fails_the_check(float32_run):
    got = list(float32_run.got)
    got[6] = np.asarray(got[6]) + 1e-3   # the first sparse layer's bias as the program fetched it
    assert lfm2.compare(got, float32_run.want)["biases_differ"] > 0
    assert lfm2.reference_error(got, float32_run.want) == float("inf")


def test_make_batch_shifts_the_labels_and_draws_from_the_slice():
    cfg, job = dict(vocab_size=50), dict(seq_len=12)
    batch = lfm2.make_batch(np.random.RandomState(1), cfg, job, 5)
    assert batch["ids"].shape == batch["labels"].shape == batch["pos_ids"].shape == (5, 12)
    assert np.array_equal(batch["ids"][:, 1:], batch["labels"][:, :-1])
    assert 0 <= batch["ids"].min() and batch["labels"].max() < 50
    assert np.array_equal(batch["pos_ids"][3], np.arange(12))


# -- (e) the shares add up -----------------------------------------------------------

def test_the_four_shares_of_a_layer_add_up_to_the_layer():
    """Four chips hold 8 of 32 experts each behind THIS router: sigmoid scores,
    the choice by score + bias, the four unbiased scores renormalised over all
    four chosen with the 1e-6.  The shares' outputs, summed, are the uncut
    layer's, and the plain reference's with all 32 held."""
    rng = np.random.RandomState(34)
    tokens, experts, k, d, f = 48, 32, 4, 16, 8
    x = rng.randn(tokens, d).astype("f4")
    router = rng.randn(d, experts).astype("f4") / 2
    bias = (rng.randn(experts) * 0.1).astype("f4")
    gate, up = (rng.randn(experts, d, f).astype("f4") / 4 for _ in range(2))
    down = rng.randn(experts, f, d).astype("f4") / 4
    routed = lower("moe_router", {"X": x, "W": router, "Bias": bias},
                   {"top_k": k, "norm_topk_prob": True, "scoring": "sigmoid", "norm_eps": 1e-6})
    assert int(np.asarray(routed["BiasMoved"])[0]) > 0

    def share(first, count):
        ins = {"X": x, "TopKProb": routed["TopKProb"], "TopKIndex": routed["TopKIndex"], "Load": routed["Load"],
               "WGate": gate[first:first + count], "WUp": up[first:first + count], "WDown": down[first:first + count]}
        return lower("moe_experts", ins, {"held": [first, count]} if count < experts else {})

    shares = [share(first, 8) for first in range(0, experts, 8)]
    assert sum(int(np.asarray(s["Held"])[0]) for s in shares) == tokens * k
    assert all(int(np.asarray(s["Dropped"])[0]) == 0 for s in shares)
    whole = share(0, experts)["Out"]
    agree(sum(s["Out"] for s in shares), whole, tol=2e-6)
    # the plain reference's layer with every expert held: the same sum, written expert by expert
    scores = sigmoid(x.astype("f8") @ router.astype("f8"))
    chosen = np.argsort(-(scores + bias), -1)[:, :k]
    weights = np.take_along_axis(scores, chosen, -1)
    weights /= weights.sum(-1, keepdims=True) + 1e-6
    want = np.zeros((tokens, d))
    for t in range(tokens):
        for e, g_e in zip(chosen[t], weights[t]):
            h = x[t].astype("f8") @ gate[e]
            want[t] += g_e * ((h * sigmoid(h) * (x[t].astype("f8") @ up[e])) @ down[e])
    agree(whole, want, tol=1e-5)
    # one share alone is its own experts' part, not a rescaled whole
    assert np.abs(np.asarray(shares[1]["Out"]) - np.asarray(whole) / 4).max() > 1e-3


# -- (f) the step record ---------------------------------------------------------------

def test_steps_through_train_loop_publish_the_share_of_the_choices_the_bias_moved():
    from benchmark.metrics import router_bias_moved_share

    monitor.reset()
    monitor.enable()
    try:
        cfg, job, main, loss, names, scope, exe = tiny_model("float32")
        rng = np.random.RandomState(5)
        batches = [lfm2.make_batch(rng, cfg, job, 4) for _ in range(4)]
        fluid.train_loop(exe, main, iter(batches), [loss], scope=scope, log_period=2)
        records = [r for r in monitor.get_monitor().step_records() if r.get("kind") == "moe_routing"]
        counters = monitor.get_monitor().counter_values()
    finally:
        monitor.disable()
        monitor.reset()
    assert len(records) == 2
    for r in records:
        assert len(r["bias_moved_share"]) == len(r["held_rows_share"]) == 4 and r["dropped_tokens"] == 0
        assert all(0.0 < s < 1.0 for s in r["bias_moved_share"])
    assert router_bias_moved_share.bias_moved_share(records, 0) == pytest.approx(
        100.0 * np.median([max(r["bias_moved_share"]) for r in records]))
    assert counters["lowering.short_conv_layers"] >= 4 and counters["lowering.moe_router_sigmoid"] >= 4


# -- (g) the block-diffusion cell's program is the parent's but for the held experts' rare path ------

def test_sdars_step_lowers_to_the_parents_program_but_for_the_rare_path():
    """`tests/test_lowering_one_path.py` pins BERT's, ResNet-50's and OLMoE's
    lowered steps and not SDAR's, which shares the router, the held experts,
    the block builder and `train_loop`'s step statistics with this PR's
    changes: its op listing and its fetches (the loss and the statistics the
    loop adds) are what the parent `ef94f98` gives, recorded there by this
    test's own code.  Its StableHLO for the chip (the kernels' serialised
    bodies stripped) was the parent's too (sha256 1a79d422...) until
    `_held_experts`' rare path was rewritten (2048 rows a pass, its transpose
    written out: ops/moe_ops.py; sha256 1f8e552e...), and that one's until PR 35
    gave the held path's gathers and scatter-adds the live rows' passes only
    (`_over_the_live_rows`: a conditional with one branch a count of passes,
    four of 8192 rows at the most, where one instruction over the bound
    stood, and `live` among the chunk's values); the op listing, the fetches, the router, the block
    builder, the sort, the kernels and the rare path's conditional lower as
    before, and the hash below pins the whole again.  That one stood (sha256
    ce85b78d...) until PR 53 sent the common pass's `_add_to_tokens` to the
    `token_sum` kernel with the slots no held expert owns left out: two kernel
    calls a layer and one more sort of scalars where two five-branch
    conditionals of scatter-adds stood; the rare path keeps XLA's form.  That
    one stood (sha256 aff8883d...) until PR 68 gave block diffusion's attention
    the one backward kernel (`ops/attention_backward_kernels.py` on the rule's
    stored blocks: one call a layer where the stock dq and dkv calls stood);
    the op listing is what it was."""
    import hashlib
    import re

    from benchmark.models import sdar
    from paddle_tpu import pipeline
    from paddle_tpu.core import executor as ex

    # an inner `jax.jit` that an earlier test of this worker traced is found again in JAX's caches, and functions that
    # share one traced object lower to one private function: the text's numbering must not hang on the worker's history
    jax.clear_caches()
    cfg = mf.read_json("benchmark/configs/sdar-30b-a3b-chat.json")
    job = mf.read_json("benchmark/traffic/train-blockdiff-s4096.json")
    with fluid.unique_name.guard():
        main, startup, _, loss, _ = sdar.build(cfg, job)
    main.random_seed = startup.random_seed = 3
    listing = json.dumps([[op.type, op.inputs, op.outputs, {k: repr(v) for k, v in sorted(op.attrs.items())}]
                          for op in main.global_block().ops], sort_keys=True)
    scope = fluid.Scope()
    for v in startup.global_block().vars.values():
        if v.persistable:
            scope.set_var(v.name, jax.ShapeDtypeStruct(tuple(v.shape), v.dtype))
    fetch = [loss.name] + [n for _, names, _ in pipeline._step_stats(main) for slot in names.values() for n in slot]
    assert len(fetch) == 13   # the loss, and Load, Dropped and Held of four layers
    b, length = job["batch_per_chip"], job["seq_len"]
    feeds = {"ids": jax.ShapeDtypeStruct((b, 2 * length), np.int32), "labels": jax.ShapeDtypeStruct((b, length), np.int32),
             "pos_ids": jax.ShapeDtypeStruct((b, 2 * length), np.int32),
             "loss_weight": jax.ShapeDtypeStruct((b, length), np.float32)}
    step = ex._CompiledStep(main, list(feeds), fetch, scope, platform="tpu",
                            feed_shapes={n: s.shape for n, s in feeds.items()})
    as_shape = lambda v: jax.ShapeDtypeStruct(v.shape, v.dtype)  # noqa: E731
    traced = step.jfn.trace({n: as_shape(scope.find_var(n)) for n in step.rw_names},
                            {n: as_shape(scope.find_var(n)) for n in step.ro_names},
                            feeds, as_shape(jax.random.PRNGKey(0)))
    text = re.sub(r'(\\22body\\22: \\22)[A-Za-z0-9+/=]+', r"\1", traced.lower(lowering_platforms=("tpu",)).as_text())
    found = (step.module, hashlib.sha256(listing.encode()).hexdigest(), hashlib.sha256(text.encode()).hexdigest())
    print(found)
    assert found == ("train_6de7c714", "bc7cad00c44ec7d55d9ad0458b439478849ada1e810ed87a3ceddfbb31a7734b",
                     "8b781bff58902adaaf891f9faa2151bc267a227fef0c86edae1ef4145babed1a")
