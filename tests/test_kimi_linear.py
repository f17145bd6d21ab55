"""Kimi-Linear-48B-A3B's parts and the whole, tiny on the CPU (ISSUE 42).

(a) the op `kda`: its `jax.numpy` form against the token-by-token recurrence,
    its stage's faults, `kda_gate`, `infer=`, the planner rows and
    `analysis.verify` stand in `tests/test_kda_op.py`, its Pallas kernels
    (`ops/kda_kernels.py`, interpreted) in `tests/test_kda_kernels.py`; this file
    keeps `lower`, `agree` and the float32 products they take;
(b) `short_conv`'s plain mode against four shifted multiply-adds, forward and
    gradients, and the gated mode's lowered text unchanged;
(c) `fused_attention` with values of another width than queries and keys
    against the dense reference at (192, 128), and the shapes that run today
    choosing what they choose today;
(d) the shared expert beside the routed ones; the 32 shares of 8 experts, the
    shared expert counted once, add up to the uncut layer;
(e) a tiny `build_causal_lm` (kda dense, kda, kda, latent_attention, kda) in
    float32 against the benchmark's reference (benchmark/models/kimi_linear.py)
    on seeded weights: loss, logits, routing, every stage, every parameter's
    gradient; in bf16 within the benchmark's tolerances;
(f) steps through `train_loop` publish the `kda_state` record and the counters.
"""
import hashlib
import os
import re
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
from benchmark.models import kimi_linear  # noqa: E402
from paddle_tpu import layers, monitor  # noqa: E402
from paddle_tpu.core.lowering import LoweringContext  # noqa: E402
from paddle_tpu.core.registry import get_op_def  # noqa: E402
from paddle_tpu.models import transformer  # noqa: E402
from paddle_tpu.ops import linear_attention_ops as lao  # noqa: E402
from paddle_tpu.ops import nn_ops  # noqa: E402


def lower(op_type, ins, attrs=None):
    """One op's lowering called as the interpreter calls it."""
    attrs = attrs or {}
    op = SimpleNamespace(type=op_type, attr=lambda n, d=None: attrs.get(n, d))
    ctx = LoweringContext(jax.random.PRNGKey(0))
    return get_op_def(op_type).lower(ctx, op, {k: [jnp.asarray(v)] for k, v in ins.items()})


def agree(got, want, tol=1e-5, floor=1e-12):
    got, want = np.asarray(got, "f8"), np.asarray(want, "f8")
    assert got.shape == want.shape
    assert np.abs(got - want).max() <= tol * max(np.abs(want).max(), floor), \
        (np.abs(got - want).max(), np.abs(want).max())


@pytest.fixture(autouse=True)
def float32_products():
    with jax.default_matmul_precision("highest"):
        yield


# -- (b) the plain short convolution ---------------------------------------------------

def plain_conv_golden(x, w):
    taps, out = w.shape[1], jnp.zeros_like(x)
    for t in range(x.shape[1]):
        for j in range(taps):
            if t - (taps - 1) + j >= 0:
                out = out.at[:, t].add(w[:, j] * x[:, t - (taps - 1) + j])
    return jax.nn.silu(out)


@pytest.mark.parametrize("taps,length", [(4, 9), (4, 3), (3, 7), (1, 5), (4, 1)])
def test_the_plain_short_convolution_is_four_shifted_multiply_adds_and_a_silu(taps, length):
    rng = np.random.RandomState(taps * 10 + length)
    x, w = rng.randn(2, length, 5).astype("f4"), rng.randn(5, taps).astype("f4")
    weigh = rng.randn(2, length, 5).astype("f4")
    attrs = {"gated": False, "activation": "silu"}
    agree(lower("short_conv", {"X": x, "Filter": w}, attrs)["Out"], plain_conv_golden(jnp.asarray(x), jnp.asarray(w)), tol=1e-6)
    got = jax.grad(lambda a, b: jnp.sum(lower("short_conv", {"X": a, "Filter": b}, attrs)["Out"] * weigh),
                   argnums=(0, 1))(jnp.asarray(x), jnp.asarray(w))
    want = jax.grad(lambda a, b: jnp.sum(plain_conv_golden(a, b) * weigh), argnums=(0, 1))(jnp.asarray(x), jnp.asarray(w))
    agree(got[0], want[0], tol=1e-5)
    agree(got[1], want[1], tol=1e-5)
    agree(kimi_linear._plain_conv(x, w), plain_conv_golden(jnp.asarray(x), jnp.asarray(w)), tol=1e-6)   # the stage check's numpy form
    low = jnp.asarray(x).astype(jnp.bfloat16)                 # computed in float32 from bf16 and rounded once
    out = lower("short_conv", {"X": low, "Filter": w}, attrs)["Out"]
    assert out.dtype == jnp.bfloat16
    exact = np.asarray(plain_conv_golden(low.astype(jnp.float32), jnp.asarray(w)))
    assert np.abs(np.asarray(out.astype(jnp.float32)) - exact).max() <= 2.0 ** -8 * np.abs(exact).max()


def test_the_plain_mode_is_an_attribute_of_the_one_op_and_the_gated_modes_text_is_unchanged():
    """`layers.short_conv(gated=False, activation="silu")` appends the same op
    type with two attributes and no projection; the gated layer's op carries
    no new attribute, and its lowered text is the parent's (recorded from
    commit 32f0c9c by this test's own code)."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = layers.data("x", [6, 8], dtype="float32")
        layers.short_conv(x, kernel_size=4, filter_attr="c.taps", gated=False, activation="silu")
        layers.short_conv(x, kernel_size=3)
    plain, gated = [op for op in main.global_block().ops if op.type == "short_conv"]
    assert plain.attrs == {"gated": False, "activation": "silu"} and not gated.attrs
    assert [op.type for op in main.global_block().ops] == ["short_conv", "mul", "short_conv", "mul"]
    with pytest.raises(Exception, match="the gated form has none"):
        with fluid.program_guard(fluid.Program(), fluid.Program()):
            from paddle_tpu.core import analysis
            layers.short_conv(layers.data("x", [6, 8], dtype="float32"), gated=False, activation="tanh")
            problems = [d for d in analysis.verify_program(fluid.default_main_program(), level="full") if d.severity == "error"]
            assert not problems, f"the gated form has none: {problems}"

    def gated_step(x, w, g):
        out, pull = jax.vjp(lambda x, w: lower("short_conv", {"X": x, "Filter": w})["Out"], x, w)
        return (out,) + pull(g)

    text = jax.jit(gated_step).lower(jax.ShapeDtypeStruct((2, 16, 24), jnp.bfloat16), jax.ShapeDtypeStruct((8, 3), jnp.float32),
                                     jax.ShapeDtypeStruct((2, 16, 8), jnp.bfloat16)).as_text()
    text = re.sub(r"loc\(.*?\)|#loc.*", "", text)
    assert hashlib.sha256(text.encode()).hexdigest() == GATED_SHORT_CONV_TEXT


#: sha256 of the gated short convolution's lowered step above, at the parent commit (32f0c9c)
GATED_SHORT_CONV_TEXT = "2edaa7ab09cef1a3325b93f2237d717383031e080df284aa2410b522b18eedca"


# -- (c) values of another width --------------------------------------------------------

def dense_attention(q, k, v, scale):
    """softmax(q k^T scale, causal) v over (B, L, H, d), float64."""
    q, k, v = (np.asarray(t, "f8").transpose(0, 2, 1, 3) for t in (q, k, v))
    s = np.einsum("bhqd,bhkd->bhqk", q, k) * scale
    s = np.where(np.tril(np.ones(s.shape[-2:], bool)), s, -np.inf)
    p = np.exp(s - s.max(-1, keepdims=True))
    return np.einsum("bhqk,bhkd->bhqd", p / p.sum(-1, keepdims=True), v).transpose(0, 2, 1, 3)


def test_attention_takes_values_of_another_width_than_queries_and_keys():
    rng = np.random.RandomState(4)
    q, k = (rng.randn(2, 24, 3, 192).astype("f4") / 4 for _ in range(2))
    v = rng.randn(2, 24, 3, 128).astype("f4")
    out = lower("fused_attention", {"Q": q, "K": k, "V": v}, {"causal": True, "layout": "blhd"})["Out"]
    assert out.shape == (2, 24, 3, 128)
    agree(out, dense_attention(q, k, v, 192 ** -0.5), tol=1e-5)
    heads_major = lower("fused_attention", {n: t.transpose(0, 2, 1, 3) for n, t in (("Q", q), ("K", k), ("V", v))},
                        {"causal": True})["Out"]
    agree(heads_major.transpose(0, 2, 1, 3), out, tol=1e-6)
    grads = jax.grad(lambda q, k, v: jnp.sum(jnp.square(lower(
        "fused_attention", {"Q": q, "K": k, "V": v}, {"causal": True, "layout": "blhd"})["Out"])), argnums=(0, 1, 2))(q, k, v)
    assert [g.shape for g in grads] == [q.shape, k.shape, v.shape]


def test_the_attentions_rule_reads_the_values_width_and_leaves_todays_shapes_where_they_were():
    bf16 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.bfloat16)  # noqa: E731
    path = lambda q, k, v=None, **kw: nn_ops._attention_path("tpu", None, q, k, v_width=v, **kw)  # noqa: E731
    # latent attention at the cell's shape: the splash kernels, the widths as they are
    assert path(bf16(1, 4096, 32, 192), bf16(1, 4096, 32, 192), 128, causal=True, layout="blhd") == "block_causal"
    # what no kernel here was ever given: a bias or no causal mask at two widths, short rows at two widths, a structured mask
    assert path(bf16(1, 32, 4096, 192), bf16(1, 32, 4096, 192), 128, causal=True, biased=True) == "xla"
    assert path(bf16(1, 32, 4096, 192), bf16(1, 32, 4096, 192), 128) == "xla"
    assert path(bf16(1, 12, 512, 64), bf16(1, 12, 512, 64), 128) == "xla"
    assert path(bf16(1, 32, 8192, 128), bf16(1, 32, 8192, 128), 64, mask=("block_diffusion", 4)) == "xla"
    # one width: what they chose at the parent
    for v_width in (None, 128):
        assert path(bf16(4, 16, 4096, 128), bf16(4, 16, 4096, 128), v_width, causal=True) == "block_causal"
        assert path(bf16(4, 16, 4096, 128), bf16(4, 16, 4096, 128), v_width, causal=True, biased=True) == "flash"
        assert path(bf16(2, 32, 8192, 128), bf16(2, 4, 8192, 128), v_width, mask=("block_diffusion", 4)) == "block_sparse"
    assert path(bf16(32, 512, 12, 64), bf16(32, 512, 12, 64), 64, layout="blhd") == "row_kernel"
    assert path(bf16(256, 128, 12, 64), bf16(256, 128, 12, 64), 64, layout="blhd") == "xla"


# -- (d) the shared expert, and the shares ---------------------------------------------------

def sigmoid(x):
    return 1.0 / (1.0 + np.exp(-x))


def test_the_shared_expert_is_added_once_beside_the_routed_sum_and_outside_the_held_path():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = layers.data("x", [6, 16], dtype="float32")
        out, _, _ = layers.moe(x, 8, 4, 2, norm_topk_prob=True, held=(2, 2), scoring="sigmoid", shared_experts=1,
                               shared_attrs=("s.gate", "s.up", "s.down"), gate_attr="e.gate")
    ops = main.global_block().ops
    assert [op.type for op in ops] == ["moe_router", "moe_experts", "mul", "swish", "mul", "elementwise_mul", "mul",
                                       "elementwise_add"]
    assert ops[1].attrs["shared_experts"] == 1 and ops[1].attrs["held"] == [2, 2]
    shapes = {p.name: tuple(p.shape) for p in main.all_parameters()}
    assert shapes["s.gate"] == shapes["s.up"] == (16, 4) and shapes["s.down"] == (4, 16) and shapes["e.gate"] == (2, 16, 4)
    scope, exe = fluid.Scope(), fluid.Executor(fluid.TPUPlace(0))
    exe.run(startup, scope=scope)
    rows = np.random.RandomState(1).randn(3, 6, 16).astype("f4")
    routed = ops[1].outputs["Out"][0]
    got, got_routed = exe.run(main, feed={"x": rows}, fetch_list=[out.name, routed], scope=scope)
    gate, up, down = (np.asarray(scope.find_var(n), "f8") for n in ("s.gate", "s.up", "s.down"))
    h = rows.astype("f8") @ gate
    agree(np.asarray(got) - np.asarray(got_routed), (h * sigmoid(h) * (rows @ up)) @ down, tol=1e-5)
    # a layer without one is the parent's: no attribute, no op
    with fluid.program_guard(fluid.Program(), fluid.Program()):
        layers.moe(layers.data("x", [6, 16], dtype="float32"), 8, 4, 2)
        assert [op.type for op in fluid.default_main_program().global_block().ops] == ["moe_router", "moe_experts"]
        assert "shared_experts" not in fluid.default_main_program().global_block().ops[1].attrs


def test_the_32_shares_of_a_layer_and_the_shared_expert_once_add_up_to_the_layer():
    """32 chips hold 8 of 256 experts each behind THIS router (sigmoid scores,
    the choice by score + bias, the eight unbiased scores renormalised over all
    eight with the 1e-20, times 2.446) and each computes the shared expert
    alike.  The 32 routed parts, summed, and the shared expert's output ONCE
    are the uncut layer's output as the plain reference writes it."""
    rng = np.random.RandomState(42)
    tokens, experts, k, d, f, scaling = 64, 256, 8, 16, 8, 2.446
    x = rng.randn(tokens, d).astype("f4")
    router = rng.randn(d, experts).astype("f4") / 2
    bias = (rng.randn(experts) * 0.1).astype("f4")
    gate, up = (rng.randn(experts, d, f).astype("f4") / 4 for _ in range(2))
    down = rng.randn(experts, f, d).astype("f4") / 4
    s_gate, s_up, s_down = rng.randn(d, f).astype("f4") / 4, rng.randn(d, f).astype("f4") / 4, rng.randn(f, d).astype("f4") / 4
    routed = lower("moe_router", {"X": x, "W": router, "Bias": bias},
                   {"top_k": k, "norm_topk_prob": True, "scoring": "sigmoid", "norm_eps": 1e-20,
                    "routed_scaling_factor": scaling})

    def share(first, count):
        ins = {"X": x, "TopKProb": routed["TopKProb"], "TopKIndex": routed["TopKIndex"], "Load": routed["Load"],
               "WGate": gate[first:first + count], "WUp": up[first:first + count], "WDown": down[first:first + count]}
        return lower("moe_experts", ins, {"held": [first, count], "shared_experts": 1})

    shares = [share(first, 8) for first in range(0, experts, 8)]
    assert sum(int(np.asarray(s["Held"])[0]) for s in shares) == tokens * k
    assert all(int(np.asarray(s["Dropped"])[0]) == 0 for s in shares)
    h = x.astype("f8") @ s_gate
    shared = (h * sigmoid(h) * (x.astype("f8") @ s_up)) @ s_down          # what every chip computes alike: counted once
    scores = sigmoid(x.astype("f8") @ router.astype("f8"))
    chosen = np.argsort(-(scores + bias), -1)[:, :k]
    weights = np.take_along_axis(scores, chosen, -1)
    weights = weights / (weights.sum(-1, keepdims=True) + 1e-20) * scaling
    want = shared.copy()
    for t in range(tokens):
        for e, g_e in zip(chosen[t], weights[t]):
            h = x[t].astype("f8") @ gate[e]
            want[t] += g_e * ((h * sigmoid(h) * (x[t].astype("f8") @ up[e])) @ down[e])
    agree(sum(np.asarray(s["Out"], "f8") for s in shares) + shared, want, tol=1e-5)
    # 32 times the shared expert would be another layer
    assert np.abs(31 * shared).max() > 1e-2 * np.abs(want).max()


# -- (e) the whole model against the benchmark's reference ------------------------------------

TINY = dict(hidden_size=48, num_attention_heads=2, intermediate_size=96, moe_intermediate_size=16,
            num_experts=4, num_routed_experts=32, experts_held_first=4, num_experts_per_token=4, vocab_size=96,
            kv_lora_rank=24, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16, expert_bias_std=0.05,
            linear_attn_config=dict(num_heads=2, head_dim=16, short_conv_kernel_size=4, kda_layers=[1, 2, 3, 5],
                                    full_attn_layers=[4]))
JOB = dict(seq_len=128, batch_per_chip=4)


@pytest.fixture(scope="module", autouse=True)
def every_position_is_sampled():
    from benchmark.models import lfm2

    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(lfm2, "LOGIT_SAMPLE", 128)
        patch.setattr(lfm2, "ATTENTION_SAMPLE", 128)
        yield


def tiny_model(dtype):
    cfg = dict(mf.read_json("benchmark/configs/kimi-linear-48b-a3b.json"), compute_dtype=dtype, **TINY)
    job = dict(mf.read_json("benchmark/traffic/train-kda-s4096.json"), **JOB)
    main, startup, feeds, loss, names = kimi_linear.build(cfg, job)
    main.random_seed = startup.random_seed = 3
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.TPUPlace(0))
    exe.run(startup, scope=scope)
    return cfg, job, main, loss, names, scope, exe


def params_of(main, scope):
    return {p.name: np.asarray(scope.find_var(p.name)) for p in main.all_parameters()}


def reference_of(cfg, params, rows):
    return [np.asarray(w) for w in jax.jit(lambda p, b: kimi_linear.reference(p, b, cfg))(params, rows)]


@pytest.fixture(scope="module")
def float32_run():
    from paddle_tpu.core import unique_name

    with jax.default_matmul_precision("highest"), unique_name.guard():
        cfg, job, main, loss, names, scope, exe = tiny_model("float32")
        rows = kimi_linear.make_batch(np.random.RandomState(3), cfg, job, 8)
        got = exe.run(main.clone(for_test=True), feed=rows, fetch_list=list(names), scope=scope)
        before = params_of(main, scope)
        want = reference_of(cfg, before, rows)
        batch = kimi_linear.make_batch(np.random.RandomState(4), cfg, job, 4)
        ref_loss, ref_grads = jax.jit(jax.value_and_grad(lambda p: kimi_linear.reference(p, batch, cfg)[0]))(before)
        step_loss, = exe.run(main, feed=batch, fetch_list=[loss], scope=scope)
        after = params_of(main, scope)
        moments = {n: np.asarray(scope.find_var(n + "_moment1_0")) for n in before}
        ops = [op.type for op in main.global_block().ops]
    return SimpleNamespace(cfg=cfg, job=job, got=got, want=want, ops=ops, before=before, after=after, moments=moments,
                           ref_loss=float(ref_loss), step_loss=float(np.asarray(step_loss).reshape(-1)[0]),
                           ref_grads={k: np.asarray(v) for k, v in ref_grads.items()})


def test_float32_loss_logits_routing_and_every_stage_agree_with_the_reference(float32_run):
    found = kimi_linear.compare(float32_run.got, float32_run.want)
    assert found["left_out"] == found["routed_differently"] == found["routed_differently_above_margin"] == 0
    assert found["loss_error"] < 1e-5 and found["logit_error"] < 2e-5, found
    assert max(found["router_prob_error"], found["experts_error"], found["shared_error"], found["conv_error"],
               found["kda_error"], found["attention_error"], found["qk_error"]) < 2e-5, found
    assert found["biases_differ"] == 0 and found["bias_moved"] > 0
    assert found["kda_error_bf16_state"] > 1e-3                        # what the stage has to refuse
    assert all(0.2 < decay < 1.0 for decay in found["kda_decay_mean"])
    assert kimi_linear.reference_error(float32_run.got, float32_run.want) < 2e-5
    assert abs(float32_run.step_loss - float32_run.ref_loss) < 1e-5 * float32_run.ref_loss
    assert np.asarray(float32_run.got[1]).shape == (128, 8, 96)
    assert np.asarray(float32_run.got[-1]).shape == (kimi_linear.STAGE_ROWS, 128, 2, 16)     # the stage rows only


KDA_PARAMS = ("q.w", "q_conv.w", "k.w", "k_conv.w", "v.w", "v_conv.w", "f_a.w", "f_b.w", "a_log", "dt_bias", "b.w",
              "o_norm.w", "g_a.w", "g_b.w", "g_b.b", "out.w")
PARAMS = sorted(
    ["lm.tok_emb", "lm.head.w", "lm.final_norm.w", "lm.l0.ffn.gate.w", "lm.l0.ffn.up.w", "lm.l0.ffn.down.w"]
    + [f"lm.l{i}.{n}" for i in range(5) for n in ("ln1.w", "ln2.w")]
    + [f"lm.l{i}.kda.{n}" for i in (0, 1, 2, 4) for n in KDA_PARAMS]
    + [f"lm.l3.attn.{n}.w" for n in ("q", "kv_a", "kv_norm", "kv_b", "out")]
    + [f"lm.l{i}.moe.{n}.w" for i in range(1, 5)
       for n in ("router", "gate", "up", "down", "shared.gate", "shared.up", "shared.down")])


def test_the_tiny_model_has_these_layers_parameters_and_no_other(float32_run):
    r = float32_run
    assert sorted(r.before) == PARAMS
    assert r.ops.count("kda") == r.ops.count("kda_gate") == 4 and r.ops.count("short_conv") == 12
    assert r.ops.count("fused_attention") == 1 and r.ops.count("rotary_embedding") == 0 and r.ops.count("transpose2") == 1
    assert r.ops.count("moe_router") == r.ops.count("moe_experts") == 4
    shapes = {n: r.before[n].shape for n in ("lm.l0.kda.q.w", "lm.l0.kda.q_conv.w", "lm.l0.kda.f_a.w", "lm.l0.kda.f_b.w",
                                            "lm.l0.kda.a_log", "lm.l0.kda.dt_bias", "lm.l0.kda.b.w", "lm.l0.kda.o_norm.w",
                                            "lm.l0.kda.g_b.b", "lm.l3.attn.q.w", "lm.l3.attn.kv_a.w", "lm.l3.attn.kv_norm.w",
                                            "lm.l3.attn.kv_b.w", "lm.l3.attn.out.w", "lm.l1.moe.router.w", "lm.l1.moe.gate.w",
                                            "lm.l1.moe.shared.down.w")}
    assert shapes == {"lm.l0.kda.q.w": (48, 32), "lm.l0.kda.q_conv.w": (32, 4), "lm.l0.kda.f_a.w": (48, 16),
                      "lm.l0.kda.f_b.w": (16, 32), "lm.l0.kda.a_log": (2,), "lm.l0.kda.dt_bias": (32,),
                      "lm.l0.kda.b.w": (48, 2), "lm.l0.kda.o_norm.w": (16,), "lm.l0.kda.g_b.b": (32,),
                      "lm.l3.attn.q.w": (48, 48), "lm.l3.attn.kv_a.w": (48, 32), "lm.l3.attn.kv_norm.w": (24,),
                      "lm.l3.attn.kv_b.w": (24, 64), "lm.l3.attn.out.w": (32, 48), "lm.l1.moe.router.w": (48, 32),
                      "lm.l1.moe.gate.w": (4, 48, 16), "lm.l1.moe.shared.down.w": (16, 48)}
    with pytest.raises(ValueError, match="kda or latent_attention"):
        transformer.build_causal_lm(vocab_size=8, seq_len=4, d_model=8, n_heads=2, layer_types=["conv", "scan"])
    with pytest.raises(ValueError, match="kda_heads and kda_head_dim"):
        transformer.build_causal_lm(vocab_size=8, seq_len=4, d_model=8, n_heads=2, layer_types=["kda"])


@pytest.mark.parametrize("name", PARAMS)
def test_float32_gradient_and_adam_step_agree_with_the_reference(float32_run, name):
    """Adam's first moment after one step is 0.1 x the gradient (to 2e-4 of its
    largest element: the reference differentiates the token-by-token
    recurrence, the program its chunked form by hand); the parameter moves by
    the warm-up's first rate."""
    r = float32_run
    agree(r.moments[name] / (1 - 0.9), r.ref_grads[name], tol=2e-4)
    moved = np.abs(r.after[name] - r.before[name]).max()
    assert 0.5e-6 < moved < 4e-6, moved


def test_bfloat16_agrees_within_the_benchmarks_tolerances():
    cfg, job, main, loss, names, scope, exe = tiny_model("bfloat16")
    rows = kimi_linear.make_batch(np.random.RandomState(3), cfg, job, 8)
    got = exe.run(main.clone(for_test=True), feed=rows, fetch_list=list(names), scope=scope)
    want = reference_of(cfg, params_of(main, scope), rows)
    found = kimi_linear.compare(got, want)
    assert found["tokens"] == 8 * 128 and found["routed_differently_above_margin"] == 0
    assert found["left_out"] <= found["routed_differently"] <= 0.35 * found["tokens"]   # 32 outputs of 48 features: near ties
    assert 1e-4 < found["logit_error"] < kimi_linear.REFERENCE_RTOL and found["loss_error"] < 1e-3
    assert found["router_prob_error"] < kimi_linear.ROUTER_RTOL and found["experts_error"] < kimi_linear.EXPERTS_RTOL
    assert found["shared_error"] < kimi_linear.SHARED_RTOL
    assert found["conv_error"] < kimi_linear.CONV_RTOL < found["conv_error_bf16"]
    assert found["kda_error"] < kimi_linear.KDA_RTOL < found["kda_error_bf16_state"]
    assert found["attention_error"] < kimi_linear.ATTENTION_RTOL and found["qk_error"] < kimi_linear.QK_RTOL
    assert kimi_linear.reference_error(got, want) in (max(found["loss_error"], found["logit_error"]), float("inf"))


@pytest.mark.parametrize("fault", ["taps_reversed", "no_decay", "attention_not_causal", "shared_expert_twice"])
def test_the_reference_check_fails_on(fault, monkeypatch):
    """A program that computes something else under the same names is not
    correct: a convolution with its taps reversed, a scan without Diag(alpha),
    latent attention that sees the keys after a query, a shared expert added twice."""
    from paddle_tpu.ops import moe_ops

    if fault == "taps_reversed":
        real = moe_ops._plain_short_conv
        monkeypatch.setattr(moe_ops, "_plain_short_conv", lambda x, w: real(x, w[:, ::-1]))
    elif fault == "no_decay":
        real = lao.chunked_kda
        monkeypatch.setattr(lao, "chunked_kda", lambda q, k, v, g, *rest: real(q, k, v, 0 * g, *rest))
    elif fault == "attention_not_causal":
        real = get_op_def("fused_attention").lower

        def wrong(ctx, op, ins):
            attrs = {"causal": False}
            faulty = SimpleNamespace(type=op.type, attr=lambda n, d=None: attrs.get(n, op.attr(n, d)))
            return real(ctx, faulty, ins)

        monkeypatch.setattr(get_op_def("fused_attention"), "lower", wrong)
    else:
        real = get_op_def("elementwise_add").lower

        def twice(ctx, op, ins):
            outs = dict(real(ctx, op, ins))
            if "shared_expert" in getattr(op, "namescope", "") or "moe" in op.inputs["X"][0]:
                outs["Out"] = outs["Out"] + ins["Y"][0]
            return outs

        monkeypatch.setattr(get_op_def("elementwise_add"), "lower", twice)
    cfg, job, main, loss, names, scope, exe = tiny_model("float32")
    rows = kimi_linear.make_batch(np.random.RandomState(3), cfg, job, 8)
    got = exe.run(main.clone(for_test=True), feed=rows, fetch_list=list(names), scope=scope)
    want = reference_of(cfg, params_of(main, scope), rows)
    found = kimi_linear.compare(got, want)
    stage, limit = {"taps_reversed": ("conv_error", kimi_linear.CONV_RTOL), "no_decay": ("kda_error", kimi_linear.KDA_RTOL),
                    "attention_not_causal": ("attention_error", kimi_linear.ATTENTION_RTOL),
                    "shared_expert_twice": ("logit_error", kimi_linear.REFERENCE_RTOL)}[fault]
    assert found[stage] > limit, found
    assert not kimi_linear.reference_error(got, want) <= kimi_linear.REFERENCE_RTOL


# -- (f) the step record and the counters ---------------------------------------------------

def test_steps_through_train_loop_publish_the_kda_state_and_the_counters_count_the_layers():
    from benchmark.metrics import kda_state_decay_mean

    monitor.reset()
    monitor.enable()
    try:
        cfg, job, main, loss, names, scope, exe = tiny_model("float32")
        rng = np.random.RandomState(5)
        batches = [kimi_linear.make_batch(rng, cfg, job, 4) for _ in range(4)]
        fluid.train_loop(exe, main, iter(batches), [loss], scope=scope, log_period=2)
        records = monitor.get_monitor().step_records()
        counters = monitor.get_monitor().counter_values()
    finally:
        monitor.disable()
        monitor.reset()
    states = [r for r in records if r.get("kind") == "kda_state"]
    assert len(states) == 2 and len([r for r in records if r.get("kind") == "moe_routing"]) == 2
    for r in states:
        assert len(r["decay_mean"]) == len(r["beta_mean"]) == len(r["state_abs_max"]) == 4
        assert all(0.2 < d < 1.0 for d in r["decay_mean"]) and all(0.4 < b < 0.6 for b in r["beta_mean"])
        assert all(0 < s < 100 for s in r["state_abs_max"]) and 0 <= r["worst_layer"] < 4
    assert kda_state_decay_mean.decay_mean(records, 0) == pytest.approx(
        np.median([np.mean(r["decay_mean"]) for r in states]))
    assert kda_state_decay_mean.decay_mean([], 0) is None
    assert counters["lowering.kda_layers"] >= 4 and counters["lowering.kda_chunks"] >= 4 * 2
    assert counters["lowering.short_conv_plain_layers"] >= 12 and counters["lowering.latent_attention_layers"] >= 1
    assert counters["lowering.shared_expert_layers"] >= 4 and not counters.get("lowering.short_conv_layers")
