"""OLMoE-1B-7B's parts and the whole, tiny on the CPU (ISSUE 26).

(a) each new op against a plain jnp golden, forward and gradient;
(b) a tiny `build_causal_lm` in float32 against the benchmark's reference
    (benchmark/models/olmoe.py) on seeded weights: loss, logits, every
    gradient and, after one Adam step, every parameter;
(c) the same in bf16 within the benchmark's stated tolerance, the tokens left
    out of the logit comparison counted;
(d) no dropped token under a skewed router;
(e) three steps through `train_loop`, no recompile after the first;
and `build_bert`, which shares the builders, still lowers to the parent
commit's program at the bert-base sizes.
"""
import hashlib
import itertools
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
from benchmark.models import olmoe  # noqa: E402
from paddle_tpu import layers, monitor  # noqa: E402
from paddle_tpu.core import executor as ex  # noqa: E402
from paddle_tpu.core.lowering import LoweringContext  # noqa: E402
from paddle_tpu.core.registry import get_op_def  # noqa: E402
from paddle_tpu.models import transformer  # noqa: E402
from paddle_tpu.ops import moe_ops  # noqa: E402

RNG = np.random.RandomState(26)


def lower(op_type, ins, attrs=None, platform=None):
    """One op's lowering called as the interpreter calls it."""
    attrs = attrs or {}
    op = SimpleNamespace(type=op_type, attr=lambda n, d=None: attrs.get(n, d))
    ctx = LoweringContext(jax.random.PRNGKey(0), platform=platform)
    return get_op_def(op_type).lower(ctx, op, {k: [jnp.asarray(v)] for k, v in ins.items()})


def agree(got, want, tol=1e-5):
    got, want = np.asarray(got, "f8"), np.asarray(want, "f8")
    assert got.shape == want.shape
    assert np.abs(got - want).max() <= tol * max(np.abs(want).max(), 1e-12), \
        (np.abs(got - want).max(), np.abs(want).max())


# -- (a) the ops ---------------------------------------------------------------

def test_rms_norm_golden_forward_and_gradient():
    x = RNG.randn(3, 5, 16).astype("f4")
    g = RNG.rand(16).astype("f4") + 0.5

    def ours(x, g):
        return lower("rms_norm", {"X": x, "Scale": g}, {"begin_norm_axis": 2, "epsilon": 1e-5})["Y"]

    def golden(x, g):
        return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-5) * g

    agree(ours(x, g), golden(x, g))
    w = RNG.randn(3, 5, 16).astype("f4")
    for ours_g, golden_g in zip(jax.grad(lambda *a: (ours(*a) * w).sum(), (0, 1))(x, g),
                                jax.grad(lambda *a: (golden(*a) * w).sum(), (0, 1))(x, g)):
        agree(ours_g, golden_g)
    # bf16 activations: float32 statistics, the activation's dtype out
    y = ours(jnp.asarray(x, jnp.bfloat16), g)
    assert y.dtype == jnp.bfloat16
    agree(y.astype(jnp.float32), golden(x, g), tol=2e-2)


def test_rotary_embedding_golden_forward_and_gradient():
    x = RNG.randn(2, 3, 7, 8).astype("f4")
    pos = np.stack([np.arange(7), np.arange(7) + 100]).astype("i4")

    def ours(x):
        return lower("rotary_embedding", {"X": x, "Positions": pos}, {"theta": 10000.0})["Out"]

    def golden(x):  # complex rotation of the pairs (i, i + dh/2)
        half = x.shape[-1] // 2
        angle = pos[:, None, :, None] * 10000.0 ** (-np.arange(half) / half)
        z = (x[..., :half] + 1j * x[..., half:]) * jnp.exp(1j * angle)
        return jnp.concatenate([z.real, z.imag], -1)

    agree(ours(x), golden(x))
    w = RNG.randn(*x.shape).astype("f4")
    agree(jax.grad(lambda x: (ours(x) * w).sum())(x),
          jax.grad(lambda x: (golden(x) * w).sum().real)(x))
    # a rotation keeps norms, position 0 is the identity, and scores depend
    # on the distance alone
    agree(jnp.linalg.norm(ours(x), axis=-1), np.linalg.norm(x, axis=-1))
    agree(ours(x)[0, :, 0], x[0, :, 0])
    q = ours(np.broadcast_to(x[:1, :, :1], (2, 3, 7, 8)))  # one vector at every position
    agree(jnp.einsum("hd,hd->h", q[0, :, 2], q[0, :, 5]), jnp.einsum("hd,hd->h", q[1, :, 2], q[1, :, 5]))


def test_moe_router_golden_forward_and_gradient():
    tokens, d, experts, k = 24, 16, 8, 3
    x = RNG.randn(4, 6, d).astype("f4")
    w = (RNG.randn(d, experts) * 0.5).astype("f4")

    def ours(x, w):
        return lower("moe_router", {"X": x, "W": w}, {"top_k": k})

    def golden(x, w):
        logits = x.reshape(tokens, d) @ w
        probs = jax.nn.softmax(logits, -1)
        order = jnp.argsort(-probs, -1)[:, :k]
        load = jnp.zeros(experts).at[order.reshape(-1)].add(1.0)
        balance = experts * jnp.sum(load / (tokens * k) * probs.mean(0))
        z = jnp.mean(jax.nn.logsumexp(logits, -1) ** 2)
        return jnp.take_along_axis(probs, order, 1), order, load, balance, z

    got, (top_p, top_i, load, balance, z) = ours(x, w), golden(x, w)
    assert got["TopKProb"].dtype == jnp.float32 and got["TopKIndex"].dtype == jnp.int32
    agree(got["TopKProb"].reshape(tokens, k), top_p)
    assert np.array_equal(np.asarray(got["TopKIndex"]).reshape(tokens, k), np.asarray(top_i))
    assert np.array_equal(np.asarray(got["Load"]), np.asarray(load)) and int(load.sum()) == tokens * k
    agree(got["LoadBalanceLoss"], jnp.reshape(balance, (1,)))
    agree(got["ZLoss"], jnp.reshape(z, (1,)))
    r = RNG.randn(tokens, k).astype("f4")

    def scalar(f):
        def loss(x, w):
            o = f(x, w)
            o = (o["TopKProb"], o["LoadBalanceLoss"], o["ZLoss"]) if isinstance(o, dict) else (o[0], o[3], o[4])
            return (o[0].reshape(tokens, k) * r).sum() + 3.0 * jnp.sum(o[1]) + 0.5 * jnp.sum(o[2])
        return jax.grad(loss, (0, 1))

    for a, b in zip(scalar(ours)(x, w), scalar(golden)(x, w)):
        agree(a, b, tol=1e-4)
    # bf16 activations: the router is float32 all the same
    assert ours(jnp.asarray(x, jnp.bfloat16), w)["TopKProb"].dtype == jnp.float32
    # renormalised over the chosen ones when asked
    agree(lower("moe_router", {"X": x, "W": w}, {"top_k": k, "norm_topk_prob": True})["TopKProb"].sum(-1),
          np.ones((4, 6)))


def experts_golden(x, top_p, top_i, w_gate, w_up, w_down):
    """Every expert applied to every token, masked by the choice."""
    out = jnp.zeros_like(x)
    for e in range(w_gate.shape[0]):
        weight = jnp.sum(jnp.where(top_i == e, top_p, 0.0), -1)
        out = out + (jax.nn.silu(x @ w_gate[e]) * (x @ w_up[e])) @ w_down[e] * weight[:, None]
    return out


def routing_case(name, tokens, experts, k):
    if name == "every_expert_hit":
        top_i = np.stack([RNG.permutation(experts)[:k] for _ in range(tokens)])
        top_i[:experts, 0] = np.arange(experts)
        for t in range(experts):  # keep a token's experts distinct
            top_i[t, 1:] = [(top_i[t, 0] + j) % experts for j in range(1, k)]
    elif name == "one_expert_empty":
        top_i = np.stack([RNG.permutation(experts - 1)[:k] + 1 for _ in range(tokens)])
    elif name == "all_tokens_to_one_expert":  # the same k experts, slot 0 always expert 5
        top_i = np.tile((5 + np.arange(k)) % experts, (tokens, 1))
    elif name == "over_half_the_rows_to_one_expert":  # three tokens of four choose expert 2
        top_i = np.where(np.arange(tokens) % 4 < 3, 2, RNG.randint(experts, size=tokens)).reshape(tokens, 1)
    else:
        top_i = np.stack([RNG.permutation(experts)[:k] for _ in range(tokens)])
    return top_i.astype("i4")


#: name -> (tokens, k); 80 rows pad to one row tile of 128, 600 to two of 512
ROUTING_CASES = {"every_expert_hit": (40, 2), "one_expert_empty": (40, 2), "all_tokens_to_one_expert": (40, 2),
                 "top_k_1": (40, 1), "over_half_the_rows_to_one_expert": (40, 1),
                 "rows_no_multiple_of_the_row_tile": (300, 2)}


@pytest.mark.parametrize("case", list(ROUTING_CASES))
def test_moe_experts_golden_forward_and_gradient(case):
    """Against every expert applied to every token in float32: the output and
    the gradients of X, TopKProb (through the hidden rows: the weights are
    applied in expert order), WGate, WUp and WDown."""
    (tokens, k), d, f, experts = ROUTING_CASES[case], 16, 12, 8
    x = RNG.randn(tokens, d).astype("f4")
    top_i = routing_case(case, tokens, experts, k)
    top_p = RNG.rand(tokens, k).astype("f4") * 0.3 + 0.05
    load = np.bincount(top_i.reshape(-1), minlength=experts).astype("i4")
    assert {"every_expert_hit": load.min() > 0, "one_expert_empty": load[0] == 0,
            "all_tokens_to_one_expert": load[5] == tokens, "top_k_1": load.sum() == tokens,
            "over_half_the_rows_to_one_expert": 2 * load[2] > tokens * k,
            "rows_no_multiple_of_the_row_tile": (tokens * k) % moe_ops._GMM_TILE[0] != 0
            and tokens * k > moe_ops._GMM_TILE[0]}[case]
    weights = [(RNG.randn(experts, d, f) * 0.3).astype("f4"), (RNG.randn(experts, d, f) * 0.3).astype("f4"),
               (RNG.randn(experts, f, d) * 0.3).astype("f4")]

    def ours(x, top_p, *w):
        return lower("moe_experts", {"X": x, "TopKProb": top_p, "TopKIndex": top_i, "Load": load,
                                     "WGate": w[0], "WUp": w[1], "WDown": w[2]})

    got = ours(x, top_p, *weights)
    assert int(got["Dropped"][0]) == 0
    agree(got["Out"], experts_golden(x, top_p, top_i, *weights), tol=2e-5)
    r = RNG.randn(tokens, d).astype("f4")
    ours_g = jax.grad(lambda *a: (ours(*a)["Out"] * r).sum(), (0, 1, 2, 3, 4))(x, top_p, *weights)
    golden_g = jax.grad(lambda x, p, *w: (experts_golden(x, p, top_i, *w) * r).sum(),
                        (0, 1, 2, 3, 4))(x, top_p, *weights)
    for a, b in zip(ours_g, golden_g):
        agree(a, b, tol=1e-4)
    if case == "one_expert_empty":  # an expert nobody chose learns nothing
        assert all(float(jnp.abs(g[0]).max()) == 0.0 for g in ours_g[2:])


def route_of(top_i):
    """`moe_experts`' route of a [T, k] choice: (order, inverse, expert)."""
    order = np.argsort(top_i.reshape(-1), kind="stable").astype("i4")
    return jnp.asarray(order), jnp.asarray(np.argsort(order).astype("i4")), jnp.asarray(top_i, jnp.int32)


@pytest.mark.parametrize("tokens,k,d,kernel", [(12, 1, 5, None), (10, 3, 5, None), (128, 1, 128, (4, True)), (256, 3, 128, (4, True))],
                         ids=["xla-12-1", "xla-10-3", "kernel-128-1", "kernel-256-3"])
def test_rows_by_expert_and_sum_by_token_are_each_others_transpose(tokens, k, d, kernel):
    """The op's two row operations against the transposes jax derives from
    the plain gathers (scatter-adds): each one's values are the other's
    derived transpose, and so is each one's hand-written VJP; the way back
    as XLA's gather and sum, and as the kernel (interpreted)."""
    route = order, inverse, _ = route_of(RNG.randint(4, size=(tokens, k)))
    order, inverse = np.asarray(order), np.asarray(inverse)
    x = jnp.asarray(RNG.randn(tokens, d), jnp.float32)
    rows = jnp.asarray(RNG.randn(tokens * k, d), jnp.float32)
    to_rows = lambda x: moe_ops._rows_by_expert(x, route, k, kernel)  # noqa: E731
    to_tokens = lambda rows: moe_ops._sum_by_token(rows, route, k, kernel)  # noqa: E731
    agree(to_rows(x), np.asarray(x)[order // k], tol=0)
    scatter_add, = jax.linear_transpose(lambda x: x[order // k], x)(rows)
    agree(to_tokens(rows), scatter_add)
    agree(jax.vjp(to_rows, x)[1](rows)[0], scatter_add)
    spread, = jax.linear_transpose(lambda rows: rows[inverse].reshape(tokens, k, d).sum(1), rows)(x)
    agree(to_rows(x), spread, tol=0)
    agree(jax.vjp(to_tokens, rows)[1](x)[0], spread, tol=0)
    agree(jnp.vdot(to_rows(x), rows), jnp.vdot(x, to_tokens(rows)), tol=1e-4)  # <A x, r> = <x, A' r>, two float32 sums of all


def token_sum_routing(name, tokens, experts, k):
    if name == "a_quarter_of_all_rows_to_one_expert":   # of four slots, the first is always expert 3
        rest = np.stack([RNG.permutation(np.delete(np.arange(experts), 3))[:k - 1] for _ in range(tokens)])
        return np.concatenate([np.full((tokens, 1), 3), rest], axis=1).astype("i4")
    return routing_case(name, tokens, experts, k)


#: name -> (tokens, experts a token, hidden, experts, dtype, router): one block of the kernel's tokens and several, one
#: buffer chunk and several, fewer experts than tiles and more
TOKEN_SUM_CASES = {
    "k1_f32": (128, 1, 128, 8, "float32", "uniform"),
    "k1_bf16_three_blocks": (384, 1, 256, 4, "bfloat16", "uniform"),
    "k8_f32": (128, 8, 128, 16, "float32", "uniform"),
    "k8_bf16_64_experts": (256, 8, 256, 64, "bfloat16", "uniform"),
    "k2_bf16_an_expert_with_no_rows": (256, 2, 128, 8, "bfloat16", "one_expert_empty"),
    "k2_f32_an_expert_with_no_rows": (128, 2, 384, 8, "float32", "one_expert_empty"),
    "k4_bf16_a_quarter_to_one_expert": (256, 4, 128, 16, "bfloat16", "a_quarter_of_all_rows_to_one_expert"),
    "k4_f32_a_quarter_to_one_expert": (256, 4, 128, 16, "float32", "a_quarter_of_all_rows_to_one_expert"),
    "k2_bf16_all_tokens_to_one_expert": (256, 2, 128, 8, "bfloat16", "all_tokens_to_one_expert"),
}


@pytest.mark.parametrize("case", list(TOKEN_SUM_CASES))
def test_the_token_sum_kernel_is_the_gather_and_the_float32_sum(case):
    """`moe_kernels.token_sum`, interpreted, against `_sum_by_token`'s
    `jax.numpy` form on the same rows: float32 rows to 1e-6 of the largest
    sum, bf16 rows to one bf16 ulp (the same float32 sums in another order,
    rounded once)."""
    from paddle_tpu.ops import moe_kernels

    tokens, k, d, experts, dtype, router = TOKEN_SUM_CASES[case]
    assert moe_kernels.fits(tokens, d, k, dtype, experts)
    top_i = token_sum_routing(router, tokens, experts, k)
    load = np.bincount(top_i.reshape(-1), minlength=experts)
    assert {"uniform": lambda: True, "one_expert_empty": lambda: load[0] == 0, "all_tokens_to_one_expert": lambda: load[5] == tokens,
            "a_quarter_of_all_rows_to_one_expert": lambda: 4 * load[3] == tokens * k}[router]()
    route = route_of(top_i)
    rows = jnp.asarray(RNG.randn(tokens * k, d), dtype)
    want = np.asarray(moe_ops._sum_by_token(rows, route, k), "f8")
    got = moe_ops._sum_by_token(rows, route, k, (experts, True))
    assert got.dtype == rows.dtype and got.shape == (tokens, d)
    off = np.abs(np.asarray(got, "f8") - want)
    if dtype == "float32":
        assert off.max() <= 1e-6 * np.abs(want).max(), off.max()
    else:
        assert (off <= 2.0 ** -7 * np.maximum(np.abs(want), 1e-3)).all(), off.max()


@pytest.mark.parametrize("platform,devices,tokens,d,dtype,experts,path", [
    ("tpu", None, 16384, 2048, "bfloat16", 64, "kernel"), ("tpu", 1, 128, 128, "float32", 8, "kernel"),
    ("tpu", 1, 16384, 2048, "bfloat16", 128, "kernel"),
    ("cpu", None, 16384, 2048, "bfloat16", 64, "xla"), (None, None, 16384, 2048, "bfloat16", 64, "xla"),
    ("tpu", 4, 16384, 2048, "bfloat16", 64, "xla"), ("tpu", None, 16384, 2000, "bfloat16", 64, "xla"),
    ("tpu", None, 16320, 2048, "bfloat16", 64, "xla"), ("tpu", None, 16384, 2048, "float16", 64, "xla"),
    ("tpu", None, 16384, 8192, "float32", 384, "xla")])
def test_the_token_sum_kernel_is_taken_on_one_tpu_at_whole_tiles_and_blocks_and_nowhere_else(platform, devices, tokens, d, dtype,
                                                                                             experts, path):
    """`_token_sum_path` reads the platform, the mesh, the tokens' shape and
    dtype, the rows a token and the number of experts, and nothing else: no flag, environment
    variable or attribute.  The last case: two buffers that do not fit."""
    import inspect
    import re

    k = 8
    mesh = None if devices is None else SimpleNamespace(size=devices)
    assert moe_ops._token_sum_path(platform, mesh, jax.ShapeDtypeStruct((tokens, d), dtype), k, experts) == path
    assert not re.search(r"environ|getenv|FLAGS|\.attr\(", inspect.getsource(moe_ops._token_sum_path))


def _count_token_sum_kernel_calls(trace):
    monitor.reset()
    monitor.enable()
    try:
        traced = trace()
        return monitor.get_monitor().counter_values().get("lowering.token_sum_kernel_calls", 0), str(traced)
    finally:
        monitor.disable()
        monitor.reset()


@pytest.mark.parametrize("platform,devices,d,calls", [("tpu", None, 128, 2), ("cpu", None, 128, 0), ("tpu", None, 120, 0),
                                                      ("tpu", 4, 128, 0)])
def test_the_counter_says_which_layers_took_the_token_sum_kernel(platform, devices, d, calls):
    """`lowering.token_sum_kernel_calls` counts, at trace time, the calls of
    `_sum_by_token` that took the kernel: two a differentiated layer on one
    TPU device (forward's, and the transpose of `_rows_by_expert`), one a plain
    call; on a mesh, on the CPU and at a hidden size that is no whole number of
    lane tiles the layer keeps XLA's form and the counter stays 0."""
    tokens, f, experts, k = 128, 16, 8, 2
    top_i = routing_case("uniform", tokens, experts, k)
    ins = {"X": RNG.randn(tokens, d).astype("f4"), "TopKProb": RNG.rand(tokens, k).astype("f4"), "TopKIndex": top_i,
           "Load": np.bincount(top_i.reshape(-1), minlength=experts).astype("i4"),
           "WGate": RNG.randn(experts, d, f).astype("f4"), "WUp": RNG.randn(experts, d, f).astype("f4"),
           "WDown": RNG.randn(experts, f, d).astype("f4")}
    op = SimpleNamespace(type="moe_experts", attr=lambda n, default=None: default)
    # the mesh splits more than the rows (under one that splits the rows alone the op runs on a chip's own rows: tests/test_nemotron_h.py)
    mesh = None if devices is None else SimpleNamespace(size=devices, shape={"dp": 2, "tp": devices // 2})
    ctx = LoweringContext(jax.random.PRNGKey(0), platform=platform, mesh=mesh)

    def layer(x):
        return jnp.sum(get_op_def("moe_experts").lower(ctx, op, {n: [jnp.asarray(v)] for n, v in {**ins, "X": x}.items()})["Out"])

    found, text = _count_token_sum_kernel_calls(lambda: jax.make_jaxpr(jax.grad(layer))(ins["X"]))   # traced, not run
    assert found == calls and text.count("name=token_sum") == 2 * calls  # each one `jax.jit` and the kernel's call inside it
    found, _ = _count_token_sum_kernel_calls(lambda: jax.make_jaxpr(layer)(ins["X"]))
    assert found == calls // 2


def test_permute_scalars_is_the_gather_and_its_transpose_the_inverse_gather():
    n = 37
    perm = RNG.permutation(n).astype("i4")
    inverse = np.argsort(perm).astype("i4")
    v, g = (jnp.asarray(RNG.randn(n), jnp.float32) for _ in range(2))
    agree(moe_ops._permute_scalars(v, perm, inverse), np.asarray(v)[perm], tol=0)
    scatter, = jax.linear_transpose(lambda v: v[perm], v)(g)
    agree(jax.vjp(lambda v: moe_ops._permute_scalars(v, perm, inverse), v)[1](g)[0], scatter, tol=0)
    agree(scatter, np.asarray(g)[inverse], tol=0)


def test_moe_experts_cost_row_counts_the_lowerings_passes_over_its_rows():
    from paddle_tpu.core import resource_plan

    tokens, d, f, experts, k = 40, 16, 12, 8, 2
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        out = layers.moe(layers.data("x", [d]), experts, f, k)[0]
    plan = resource_plan.plan_program(main, {"x": (tokens, d)}, [out.name])
    row = next(r for r in plan.rows if r.op_type == "moe_experts")
    assert row.cost_covered and row.flops == 3 * 2 * tokens * k * d * f
    # X and Out, TopKProb and TopKIndex, Load, the three matrices, Dropped: once each
    once = 2 * tokens * d + 2 * tokens * k + experts + 3 * experts * d * f + 1
    # a hidden size of 16 is no whole lane tile: the way back is XLA's gather and sum, seven passes over [rows, hidden]
    assert moe_ops._ROW_PASSES == {"hidden": 5, "hidden_xla": 7, "width": 6}
    assert row.traffic_bytes == 4 * (once + tokens * k * (7 * d + 6 * f))


@pytest.mark.parametrize("rows,groups", [(256, [256, 0, 0, 0]), (200, [13, 0, 100, 87]), (640, [1, 638, 0, 1])])
def test_grouped_matmul_golden_forward_and_gradients(rows, groups):
    """`grouped_matmul` (off the chip: the kernel the chip compiles, in
    interpret mode) against every row times its own group's matrix: forward
    and both gradients, rows that do not fill the row tile, empty groups."""
    k, n = 128, 256
    x = jnp.asarray(RNG.randn(rows, k), jnp.float32)
    w = jnp.asarray(RNG.randn(len(groups), k, n) * 0.1, jnp.float32)
    sizes = jnp.asarray(groups, jnp.int32)
    r = jnp.asarray(RNG.randn(rows, n), jnp.float32)
    group_of_row = np.repeat(np.arange(len(groups)), groups)
    ours = lambda x, w: moe_ops.grouped_matmul(x, w, sizes, platform="cpu")  # noqa: E731
    golden = lambda x, w: jnp.einsum(  # noqa: E731
        "mk,mkn->mn", x, w[group_of_row], precision=jax.lax.Precision.HIGHEST)
    agree(ours(x, w), golden(x, w), tol=1e-5)
    for a, b in zip(jax.grad(lambda x, w: (ours(x, w) * r).sum(), (0, 1))(x, w),
                    jax.grad(lambda x, w: (golden(x, w) * r).sum(), (0, 1))(x, w)):
        agree(a, b, tol=1e-5)


def test_grouped_matmul_gradient_for_a_float32_master_is_the_float32_accumulator():
    """bf16 rows over a float32 master: the product runs in bf16, and the
    master's gradient is `tgmm`'s float32 sum of exact bf16 x bf16 products,
    so it agrees with a float32 per-group reference to float32 rounding.  At
    the parent of PR 28 it was rounded to bf16 on its way (and widened again)."""
    k, n, groups = 128, 256, [70, 0, 300, 142]
    rows = sum(groups)
    x = jnp.asarray(RNG.randn(rows, k), BF16)
    master = jnp.asarray(RNG.randn(len(groups), k, n) * 0.1, jnp.float32)
    sizes = jnp.asarray(groups, jnp.int32)
    r = jnp.asarray(RNG.randn(rows, n), BF16)
    out, vjp = jax.vjp(lambda x, w: moe_ops.grouped_matmul(x, w, sizes, platform="cpu"), x, master)
    d_x, d_master = vjp(r)
    assert out.dtype == d_x.dtype == BF16 and d_master.dtype == jnp.float32
    ends = np.cumsum(groups)
    want = jnp.stack([jnp.dot(x[e - g:e].astype(jnp.float32).T, r[e - g:e].astype(jnp.float32),
                              precision=jax.lax.Precision.HIGHEST) for g, e in zip(groups, ends)])
    agree(d_master, want, tol=2e-6)
    assert float(jnp.abs(_round_to_bfloat16(want) - want).max() / jnp.abs(want).max()) > 1e-3
    # the rows' side multiplies the master as the forward does: rounded to the rows' dtype
    group_of_row = np.repeat(np.arange(len(groups)), groups)
    w16 = master.astype(BF16).astype(jnp.float32)
    agree(out, jnp.einsum("mk,mkn->mn", x.astype(jnp.float32), w16[group_of_row]), tol=4e-3)
    agree(d_x, jnp.einsum("mn,mkn->mk", r.astype(jnp.float32), w16[group_of_row]), tol=4e-3)


# -- (b), (c) the whole model against the benchmark's reference ----------------

TINY = dict(hidden_size=64, num_hidden_layers=2, num_attention_heads=4, intermediate_size=32,
            num_experts=8, num_experts_per_tok=2, vocab_size=96)
JOB = dict(seq_len=32, batch_per_chip=4)


def tiny_model(dtype, sizes=TINY, job=JOB):
    cfg = dict(mf.read_json("benchmark/configs/olmoe-1b-7b.json"), compute_dtype=dtype, **sizes)
    job = dict(mf.read_json("benchmark/traffic/train-s4096.json"), **job)
    main, startup, feeds, loss, names = olmoe.build(cfg, job)
    main.random_seed = startup.random_seed = 3
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.TPUPlace(0))
    exe.run(startup, scope=scope)
    return cfg, job, main, loss, names, scope, exe


def params_of(main, scope):
    return {p.name: np.asarray(scope.find_var(p.name)) for p in main.all_parameters()}


@pytest.fixture(scope="module")
def float32_run():
    """The tiny float32 model: the for_test clone's (loss, logits, choices)
    on 8 rows, the reference's, the reference's gradients on 4 rows, and the
    program's state after one training step on those 4."""
    from paddle_tpu.core import unique_name

    with unique_name.guard():
        cfg, job, main, loss, names, scope, exe = tiny_model("float32")
        rows = olmoe.make_batch(np.random.RandomState(3), cfg, job, 8)
        got = exe.run(main.clone(for_test=True), feed=rows, fetch_list=list(names), scope=scope)
        before = params_of(main, scope)
        want = jax.jit(lambda p, b: olmoe.reference(p, b, cfg))(before, rows)
        batch = olmoe.make_batch(np.random.RandomState(4), cfg, job, 4)
        ref_loss, ref_grads = jax.jit(jax.value_and_grad(
            lambda p: olmoe.reference(p, batch, cfg)[0]))(before)
        step_loss, = exe.run(main, feed=batch, fetch_list=[loss], scope=scope)
        after = params_of(main, scope)
        moments = {n: np.asarray(scope.find_var(n + "_moment1_0")) for n in before}
    return SimpleNamespace(cfg=cfg, job=job, got=got, want=[np.asarray(w) for w in want],
                           before=before, after=after, moments=moments,
                           ref_loss=float(ref_loss), step_loss=float(np.asarray(step_loss).reshape(-1)[0]),
                           ref_grads={k: np.asarray(v) for k, v in ref_grads.items()})


def test_float32_loss_logits_and_routing_agree_with_the_reference(float32_run):
    found = olmoe.compare(float32_run.got, float32_run.want)
    assert found["left_out"] == 0 and found["routed_differently_above_margin"] == 0
    assert found["loss_error"] < 1e-5 and found["logit_error"] < 1e-5, found
    assert olmoe.reference_error(float32_run.got, float32_run.want) < 1e-5
    assert abs(float32_run.step_loss - float32_run.ref_loss) < 1e-5 * float32_run.ref_loss


PARAMS = sorted(
    ["lm.tok_emb", "lm.head.w", "lm.final_norm.w"]
    + [f"lm.l{i}.{n}" for i in range(2) for n in (
        "ln1.w", "ln2.w", "attn.q.w", "attn.k.w", "attn.v.w", "attn.out.w", "attn.q_norm.w",
        "attn.k_norm.w", "moe.router.w", "moe.gate.w", "moe.up.w", "moe.down.w")])


def test_the_tiny_model_has_these_parameters_and_no_other(float32_run):
    assert sorted(float32_run.before) == PARAMS
    assert float32_run.before["lm.l0.moe.gate.w"].shape == (8, 64, 32)
    assert float32_run.before["lm.l0.moe.down.w"].shape == (8, 32, 64)
    assert np.all(float32_run.before["lm.l1.attn.q_norm.w"] == 1.0)


@pytest.mark.parametrize("name", PARAMS)
def test_float32_gradient_and_adam_step_agree_with_the_reference(float32_run, name):
    """Adam's first moment after one step is 0.1 x the gradient; the
    parameter moves by lr_t m / (sqrt(v) + eps), which is lr . sign(g) where
    |g| is far above eps: the step is compared to 2% of the learning rate,
    the gradient to 1e-4 of its largest element."""
    r = float32_run
    lr, b1, b2, eps = 4e-4, 0.9, 0.95, 1e-8
    g = r.ref_grads[name]
    agree(r.moments[name] / (1 - b1), g, tol=1e-4)
    lr_t = lr * np.sqrt(1 - b2) / (1 - b1)
    want = r.before[name] - lr_t * (1 - b1) * g / (np.sqrt((1 - b2) * g * g) + eps)
    assert np.abs(r.after[name] - want).max() <= 0.02 * lr
    assert np.abs(r.after[name] - r.before[name]).max() > 0.5 * lr  # it moved


def test_bfloat16_agrees_within_the_benchmarks_tolerance_with_the_left_out_counted(capsys):
    cfg, job, main, loss, names, scope, exe = tiny_model("bfloat16")
    rows = olmoe.make_batch(np.random.RandomState(3), cfg, job, 8)
    got = exe.run(main.clone(for_test=True), feed=rows, fetch_list=list(names), scope=scope)
    want = [np.asarray(w) for w in
            jax.jit(lambda p, b: olmoe.reference(p, b, cfg))(params_of(main, scope), rows)]
    found = olmoe.compare(got, want)
    assert found["tokens"] == 8 * 32 and found["routed_differently_above_margin"] == 0
    # at hidden 64 a router logit has std 0.16, so near ties are common: a
    # few tokens of 256 route differently, each under the margin
    assert 0 < found["left_out"] <= olmoe.LEFT_OUT_MAX * found["tokens"]
    assert 1e-4 < found["logit_error"] < olmoe.REFERENCE_RTOL
    assert olmoe.reference_error(got, want) == max(found["loss_error"], found["logit_error"])
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["info"] == "reference_routing" and line["left_out"] == found["left_out"]


@pytest.mark.parametrize("fault", ["routes_elsewhere_above_the_margin", "too_many_left_out",
                                   "logits_off", "loss_off"])
def test_the_reference_check_fails_on(fault, float32_run):
    got = [np.array(g) for g in float32_run.got]
    want = [np.array(w) for w in float32_run.want]
    if fault == "routes_elsewhere_above_the_margin":
        token = np.unravel_index(np.argmax(want[2]), want[2].shape)  # the clearest choice of all
        got[2][token] = (got[2][token] + 1) % 8
    elif fault == "too_many_left_out":
        want[2][:] = 0.0                       # every gap "under the margin"...
        got[2][:, :8] = (got[2][:, :8] + 1) % 8  # ...and a quarter of the tokens route elsewhere
    elif fault == "logits_off":
        got[1] = got[1] + 0.02 * np.abs(want[1]).max() * (np.arange(got[1].size).reshape(got[1].shape) == 77)
    else:
        got[0] = got[0] * 1.03
    assert not olmoe.reference_error(got, want) <= olmoe.REFERENCE_RTOL


# OLMoE's own router (64 experts, 8 a token, heads of 128) over a narrower
# stream: 1024 tokens, enough for the shares the tolerances are about
MIDDLE = dict(hidden_size=512, num_hidden_layers=1, num_attention_heads=4, intermediate_size=256,
              vocab_size=256)
BF16 = jnp.bfloat16


def _round_to_bfloat16(x):
    """float32 holding bf16 values; a pair of casts XLA may drop
    (`xla_allow_excess_precision`)."""
    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


def _router_weights_in_bfloat16(shipped):
    return lambda ctx, op, ins: shipped(ctx, op, dict(ins, W=[ins["W"][0].astype(BF16)]))


def _router_logits_in_bfloat16(shipped):
    """The router's product takes bf16 operands and rounds its output to
    bf16; softmax, top-k and the losses stay float32."""
    dot = jnp.dot

    def lower(ctx, op, ins):
        with pytest.MonkeyPatch.context() as patch:
            patch.setattr(jnp, "dot", lambda a, b, **kw: _round_to_bfloat16(dot(
                a.astype(BF16), b.astype(BF16), preferred_element_type=jnp.float32)))
            return shipped(ctx, op, ins)
    return lower


def _grouped_matmul_accumulating_in_bfloat16(rows, weights, sizes, platform=None):
    """The running sum of a product held in bf16, eight terms at a time."""
    def add_eight(i, acc):
        part = jax.lax.ragged_dot(jax.lax.dynamic_slice_in_dim(rows, 8 * i, 8, axis=1),
                                  jax.lax.dynamic_slice_in_dim(weights, 8 * i, 8, axis=1).astype(rows.dtype),
                                  sizes, preferred_element_type=jnp.float32)
        return _round_to_bfloat16(acc + part)

    acc = jnp.zeros((rows.shape[0], weights.shape[2]), jnp.float32)
    return jax.lax.fori_loop(0, rows.shape[1] // 8, add_eight, acc).astype(rows.dtype)


@pytest.fixture(scope="module")
def middle_reference():
    cfg, job, main, loss, names, scope, exe = tiny_model("bfloat16", MIDDLE, dict(seq_len=128))
    rows = olmoe.make_batch(np.random.RandomState(3), cfg, job, 8)
    want = jax.jit(lambda p, b: olmoe.reference(p, b, cfg))(params_of(main, scope), rows)
    return rows, [np.asarray(w) for w in want]


@pytest.mark.parametrize("fault,caught_by", [
    ("as_shipped", None),
    ("router_weights_in_bfloat16", "router_prob_error"),
    ("router_logits_in_bfloat16", "router_prob_error"),
    ("experts_accumulate_in_bfloat16", "experts_error")])
def test_the_reference_check_at_bfloat16_passes_as_shipped_and_fails_on(
        fault, caught_by, middle_reference, monkeypatch):
    """What `olmoe.REFERENCE_RTOL` says the check is tight enough for, run:
    the same seeded bf16 model with one stage's precision lowered.  End to
    end none of the faults shows (logits and left-out share stay where the
    shipped program has them); the stage on the program's own input does."""
    router = get_op_def("moe_router")
    if fault == "router_weights_in_bfloat16":
        monkeypatch.setattr(router, "lower", _router_weights_in_bfloat16(router.lower))
    elif fault == "router_logits_in_bfloat16":
        monkeypatch.setattr(router, "lower", _router_logits_in_bfloat16(router.lower))
    elif fault == "experts_accumulate_in_bfloat16":
        monkeypatch.setattr(moe_ops, "grouped_matmul", _grouped_matmul_accumulating_in_bfloat16)
    rows, want = middle_reference
    cfg, job, main, loss, names, scope, exe = tiny_model("bfloat16", MIDDLE, dict(seq_len=128))
    got = exe.run(main.clone(for_test=True), feed=rows, fetch_list=list(names), scope=scope)
    found = olmoe.compare(got, want)
    assert found["tokens"] == 1024 and found["routed_differently_above_margin"] == 0
    assert found["logit_error"] < olmoe.REFERENCE_RTOL and found["loss_error"] < 1e-4
    assert 0.02 * 1024 < found["left_out"] < olmoe.LEFT_OUT_MAX * 1024
    assert found["logit_error_left_out"] < olmoe.LEFT_OUT_LOGIT_MAX
    limits = {"router_prob_error": olmoe.ROUTER_RTOL, "experts_error": olmoe.EXPERTS_RTOL}
    for stage, limit in limits.items():
        if stage == caught_by:
            assert found[stage] > 1.5 * limit, found
        else:
            assert found[stage] < 0.75 * limit, found
    if fault.startswith("router"):
        assert found["router_choice_differs"] > 0
    else:
        assert found["router_choice_differs"] == 0
    error = olmoe.reference_error(got, want)
    assert (error == found["logit_error"]) if caught_by is None else (error == float("inf"))


# -- (d) a skewed router drops nothing -----------------------------------------

def test_no_token_is_dropped_under_a_skewed_router():
    """Positive inputs and a router whose first two columns dominate: every
    token sends both its slots to experts 0 and 1, 4x the mean load, and
    every one of them is computed."""
    tokens, d, f, experts, k = 48, 16, 8, 8, 2
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = layers.data("x", [d])
        out, balance, z = layers.moe(x, experts, f, k, router_attr=fluid.ParamAttr(name="r"),
                                     gate_attr=fluid.ParamAttr(name="g"), up_attr=fluid.ParamAttr(name="u"),
                                     down_attr=fluid.ParamAttr(name="dn"))
    ops = {op.type: op for op in main.global_block().ops}
    load, dropped = ops["moe_router"].outputs["Load"][0], ops["moe_experts"].outputs["Dropped"][0]
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.TPUPlace(0))
    exe.run(startup, scope=scope)
    router = (RNG.randn(d, experts) * 0.01).astype("f4")
    router[:, 0], router[:, 1] = 1.0, 0.9
    scope.set_var("r", jnp.asarray(router))
    xv = (np.abs(RNG.randn(tokens, d)) + 0.5).astype("f4")
    got, load_v, dropped_v, balance_v = exe.run(main, feed={"x": xv}, fetch_list=[out, load, dropped, balance],
                                                scope=scope)
    assert load_v.tolist() == [tokens, tokens] + [0] * 6 and int(dropped_v[0]) == 0
    assert load_v.max() / load_v.mean() == 4.0 and float(balance_v[0]) > 3.5
    probs = jax.nn.softmax(xv @ router, -1)
    top_p, top_i = jax.lax.top_k(probs, k)
    agree(got, experts_golden(xv, top_p, top_i, *(np.asarray(scope.find_var(n)) for n in "gu") ,
                              np.asarray(scope.find_var("dn"))), tol=2e-5)


# -- (e) through train_loop ------------------------------------------------------

def test_three_steps_through_train_loop_compile_once_and_publish_the_routing():
    monitor.reset()
    monitor.enable()
    try:
        cfg, job, main, loss, names, scope, exe = tiny_model("float32")
        ring = [olmoe.make_batch(np.random.RandomState(i), cfg, job, 4) for i in range(3)]
        recompiles = monitor.counter("executor.recompile")
        logged, seen = [], []

        def on_dispatch(step, feed):
            seen.append(recompiles.value)

        stats = fluid.train_loop(exe, main, itertools.islice(itertools.cycle(ring), 4), [loss],
                                 scope=scope, max_inflight=2, log_period=2, on_dispatch=on_dispatch,
                                 on_logged=lambda i, vals: logged.append((i, vals)))
        assert stats.steps == 4
        # one compile, at the first dispatch; none at the second, third, fourth
        assert seen[1] == seen[2] == seen[3] == recompiles.value == seen[0] + 1
        # the user's fetch list comes back as it went in
        assert [i for i, _ in logged] == [0, 2] and all(len(v) == 1 for _, v in logged)
        assert all(np.isfinite(v[0]).all() for _, v in logged)
        gauges = monitor.get_monitor().gauge_values()
        assert gauges["moe.dropped_tokens"] == 0
        assert gauges["moe.load_max_over_mean"] >= 1.0 >= gauges["moe.load_min_over_mean"] >= 0.0
        records = [r for r in monitor.step_records() if r.get("kind") == "moe_routing"]
        assert [r["pipeline_step"] for r in records] == [0, 2]
        assert all(len(r["load_max_over_mean"]) == 2 and r["dropped_tokens"] == 0 for r in records)
        assert monitor.get_monitor().counter_values()["lowering.moe_layers"] == 2
        lowered = [e for e in monitor.get_monitor().events() if e[0] == "executor.lower"][-1]
        assert lowered[5]["moe_layers"] == 2
    finally:
        monitor.disable()
        monitor.reset()


def test_a_program_without_experts_fetches_nothing_more():
    from paddle_tpu import pipeline

    main, _, _, fetches = transformer.build_bert(vocab_size=64, seq_len=8, d_model=32, n_layers=1,
                                                 n_heads=2, d_ff=64, with_optimizer=False)
    assert pipeline._step_stats(main) == []
    lm = transformer.build_causal_lm(vocab_size=64, seq_len=8, d_model=32, n_layers=3, n_heads=2,
                                     expert_width=16, num_experts=4, top_k=2, with_optimizer=False)[0]
    (publish, names, attrs), = pipeline._step_stats(fluid.CompiledProgram(lm))
    assert publish is moe_ops._publish_routing and attrs == {}   # no layer holds a share: no `held` to read against
    assert list(names) == ["Load", "Dropped"] and len(names["Load"]) == len(names["Dropped"]) == 3


# -- the shared builder still builds BERT ---------------------------------------

#: sha256 of `build_bert`'s op list and of its lowered StableHLO at the
#: bert-base sizes of `bert-base.pretrain-s128` (256 x 128 tokens, bf16, fused
#: attention, Adam), taken at the parent commit 863309a BEFORE the builders
#: gained their arguments.  A later change that means to alter BERT's program
#: re-records them (this test prints both) and says so in CHANGES.md.
#: PR 39 did: the four `transpose2` ops a layer round the attention are gone
#: (546 -> 498 ops; eight a layer with backward's) and `fused_attention`
#: carries `layout="blhd"`, so the listing, the text and the module's name moved.
BERT_OPS_SHA = "ad604c402ea6916dc1d33a8b1ffffa1099b7f41e51e8f94b14007955a5978ded"
BERT_TEXT_SHA = "0a22ade36687defbab16d2d7aefcbd8952f2a23b7713bb567c0863cad4efa10c"


def test_build_bert_at_bert_base_sizes_lowers_to_the_parents_program():
    main, startup, feeds, fetches = transformer.build_bert(
        vocab_size=30522, seq_len=128, d_model=768, n_layers=12, n_heads=12, d_ff=3072,
        dropout_prob=0.1, learning_rate=1e-4, with_optimizer=True, dtype="bfloat16",
        use_fused_attention=True)
    main.random_seed = startup.random_seed = 3
    ops = main.global_block().ops
    listing = json.dumps([[op.type, op.inputs, op.outputs,
                           {k: repr(v) for k, v in sorted(op.attrs.items())}] for op in ops],
                         sort_keys=True)
    assert len(ops) == 498 and not [op for op in ops if op.type == "transpose2"]
    assert not {"rms_norm", "rotary_embedding", "moe_router", "moe_experts"} & {op.type for op in ops}
    # the state the start-up program would make, as shapes: nothing runs
    scope = fluid.Scope()
    for v in startup.global_block().vars.values():
        if v.persistable:
            scope.set_var(v.name, jax.ShapeDtypeStruct(tuple(v.shape), v.dtype))
    shapes = {n: (256, 128) for n in ("ids", "labels", "pos_ids")}
    step = ex._CompiledStep(main, list(shapes), [fetches["loss"].name], scope,
                            feed_shapes=shapes, platform="tpu")
    as_shape = lambda v: jax.ShapeDtypeStruct(v.shape, v.dtype)  # noqa: E731
    text = step.jfn.trace({n: as_shape(scope.find_var(n)) for n in step.rw_names},
                          {n: as_shape(scope.find_var(n)) for n in step.ro_names},
                          {n: jax.ShapeDtypeStruct(s, np.int32) for n, s in shapes.items()},
                          as_shape(jax.random.PRNGKey(0))).lower().as_text()
    found = (hashlib.sha256(listing.encode()).hexdigest(), hashlib.sha256(text.encode()).hexdigest())
    print("build_bert: ops", found[0], "text", found[1], "module", step.module)
    assert step.module == "train_44acd386"  # the name the chip's compile cache knows (`train_e9476d18` until PR 39)
    assert found == (BERT_OPS_SHA, BERT_TEXT_SHA)
