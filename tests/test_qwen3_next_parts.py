"""Qwen3-Next's parts, tiny on the CPU (ISSUE 69); the whole model stands in
tests/test_qwen3_next.py, a file of its own so that another worker has it
(`docs/tier1_durations.md`).

(a) the op `kda` with a decay of ONE number a head and fewer key heads than
    value heads: it equals the op with that decay written out over the channels
    and the keys repeated, forward and every gradient (the decay's summed over
    the channels, a key head's over its value heads), in the `jax.numpy` form
    (the kernels' cases stand in tests/test_kda_kernels.py); a decay a channel
    is handed on untouched; the chunked form against the token-by-token
    recurrence at a decay of 0.2 a token over 256 tokens; beta 0 leaves the state
    at zero; the shapes are checked where the program is built;
(b) the gate a FEATURE: its columns at zero halve the ungated attention; the
    form a head is what it was; the rotation of a quarter of a 256-wide head
    passes features 64 to 255 bit for bit; the router's ten weights sum to 1;
    the shared expert's gate at w_s = 0 halves the shared expert; the 32 shares
    of one sparse layer, the gated shared expert counted once, add up to the
    uncut layer.
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
from paddle_tpu import layers, monitor  # noqa: E402
from paddle_tpu.core import unique_name  # noqa: E402
from paddle_tpu.core.lowering import LoweringContext  # noqa: E402
from paddle_tpu.core.registry import get_op_def  # noqa: E402
from paddle_tpu.models import transformer  # noqa: E402
from paddle_tpu.ops import linear_attention_ops as lao  # noqa: E402



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


# -- (a) the op with a decay a head -------------------------------------------------------------------

def scan_operands(T=128, key_heads=2, value_heads=4, width=8, decay=(0.001, 1.6), seed=0):
    rng = np.random.default_rng(seed)

    def unit(t):
        return t / np.sqrt((t * t).sum(-1, keepdims=True) + 1e-6)

    q = unit(rng.normal(size=(2, T, key_heads, width))) * width ** -0.5
    k = unit(rng.normal(size=(2, T, key_heads, width)))
    v = rng.normal(size=(2, T, value_heads, width))
    g = -rng.uniform(*decay, size=(2, T, value_heads))
    beta = rng.uniform(0.1, 0.9, size=(2, T, value_heads, 1))
    return tuple(jnp.asarray(t, jnp.float32) for t in (q, k, v, g, beta))


def written_out(q, k, v, g):
    share = v.shape[2] // k.shape[2]
    return jnp.repeat(q, share, 2), jnp.repeat(k, share, 2), jnp.broadcast_to(g[..., None], v.shape[:3] + (k.shape[-1],))


def test_a_decay_a_head_on_fewer_key_heads_is_the_decay_written_out_on_repeated_keys_forward_and_every_gradient():
    q, k, v, g, beta = scan_operands()
    w = jnp.asarray(np.random.default_rng(1).normal(size=v.shape), jnp.float32)

    def loss(handed):
        def f(q, k, v, g, beta):
            o, final = lao.chunked_kda(*(written_out(q, k, v, g)[:2] if not handed else (q, k)), v,
                                       g if handed else written_out(q, k, v, g)[2], beta, 64, 16, None)
            return jnp.sum(o * w), (o, final)
        return jax.value_and_grad(f, argnums=(0, 1, 2, 3, 4), has_aux=True)(q, k, v, g, beta)

    (mine, (o, final)), grads = loss(True)
    (theirs, (want_o, want_final)), want_grads = loss(False)       # autodiff sums the repeats and the broadcast
    np.testing.assert_array_equal(np.asarray(o), np.asarray(want_o))
    np.testing.assert_array_equal(np.asarray(final), np.asarray(want_final))
    assert [t.shape for t in grads] == [t.shape for t in (q, k, v, g, beta)]
    for got, want in zip(grads, want_grads):
        agree(got, want, tol=2e-6)


def test_a_decay_a_channel_on_a_key_head_a_value_head_is_handed_on_untouched():
    """`kda` with a decay a channel lowers as it did: nothing is repeated, spread or summed for it."""
    q, k, v, g, beta = scan_operands(key_heads=4)
    wide = jnp.broadcast_to(g[..., None], k.shape)
    assert all(a is b for a, b in zip(lao._per_channel(q, k, v, wide), (q, k, wide)))
    assert all(a is b for a, b in zip(lao._as_handed(q, k, wide, k, wide), (q, k, wide)))
    traced = jax.make_jaxpr(lambda *a: lao._chunked_kda(*a, 64, 16, None))(q, k, v, wide, beta)
    assert [eqn.primitive.name for eqn in traced.jaxpr.eqns] == ["scan"]     # the rows' map alone: nothing repeated or spread before it


def recurrence(q, k, v, g, beta):
    """The delta rule a token at a time in float64 numpy, a key head a value head, rows [T, H, .]."""
    T, H, K = k.shape
    S, out = np.zeros((H, K, v.shape[-1])), np.zeros(v.shape)
    for t in range(T):
        S = S * np.exp(g[t])[:, None, None]
        S = S + (beta[t][:, None] * k[t])[:, :, None] * (v[t] - np.einsum("hkv,hk->hv", S, k[t]))[:, None, :]
        out[t] = np.einsum("hkv,hk->hv", S, q[t])
    return out, S


@pytest.mark.parametrize("decay", [np.log(0.2), -20.0], ids=["0.2_a_token", "e-20_a_token"])
def test_the_chunked_form_is_the_recurrence_at_a_strong_decay_over_256_tokens(decay):
    """A decay of 0.2 a token is exp(-103) over a chunk: every exponent of the chunked form is a difference that is
    <= 0, so nothing overflows and nothing is NaN."""
    q, k, v, _, beta = scan_operands(T=256, decay=(-decay, -decay))
    g = jnp.full(v.shape[:3], decay, jnp.float32)
    o, final = lao.chunked_kda(q, k, v, g, beta, 64, 16, None)
    assert np.isfinite(np.asarray(o)).all() and np.isfinite(np.asarray(final)).all()
    wide_q, wide_k, _ = written_out(q, k, v, g)
    want, state = recurrence(*(np.asarray(t[0], "f8") for t in (wide_q, wide_k, v, g, beta[..., 0])))
    agree(o[0], want, tol=2e-5)
    agree(final[0], state, tol=2e-5)


def test_beta_at_zero_leaves_the_state_at_zero_and_the_output():
    q, k, v, g, beta = scan_operands()
    out = lower("kda", {"Q": q, "K": k, "V": v, "G": g, "Beta": jnp.zeros_like(beta[..., 0])})
    assert not np.asarray(out["Out"]).any() and float(np.asarray(out["Stats"])[2]) == 0.0        # the largest |S|: nothing was written


def test_the_ops_shapes_are_checked_where_the_program_is_built():
    with unique_name.guard(), fluid.program_guard(fluid.Program(), fluid.Program()):
        q, k = (layers.data(n, [64, 2, 8], dtype="float32") for n in "qk")
        v = layers.data("v", [64, 4, 8], dtype="float32")
        beta = layers.data("beta", [64, 4], dtype="float32")
        assert tuple(layers.kda(q, k, v, layers.data("g", [64, 4], dtype="float32"), beta).shape[1:]) == (64, 4, 8)
        assert tuple(layers.kda(q, k, v, layers.data("gc", [64, 4, 8], dtype="float32"), beta).shape[1:]) == (64, 4, 8)
        for wrong in (layers.data("g2", [64, 2], dtype="float32"), layers.data("g3", [64, 4, 4], dtype="float32")):
            with pytest.raises(Exception, match="log decay"):
                layers.kda(q, k, v, wrong, beta)
        with pytest.raises(Exception, match="divisor"):
            layers.kda(layers.data("q3", [64, 3, 8], dtype="float32"), layers.data("k3", [64, 3, 8], dtype="float32"), v,
                       layers.data("g4", [64, 4], dtype="float32"), beta)


# -- (b) the gates, the rotation, the router ---------------------------------------------------------

def attention_layer(head_gate):
    with unique_name.guard():
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup):
            x = layers.data("x", [24, 32], dtype="float32")
            pos = layers.data("pos", [24], dtype="int64")
            out = transformer.multi_head_attention(x, 24, 32, 4, "a", dropout_prob=0.0, causal=True, use_fused_attention=True,
                                                   proj_bias=False, qk_norm_eps=1e-6, qk_norm_per_head=True, positions=pos,
                                                   rope_theta=dict(theta=1e7, rotary_dim=4), n_kv_heads=2, head_dim=16,
                                                   head_gate=head_gate)
    main.random_seed = startup.random_seed = 3
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.TPUPlace(0))
    exe.run(startup, scope=scope)
    return main, scope, exe, out


def test_the_gates_columns_at_zero_halve_the_ungated_attention_and_the_form_a_head_is_what_it_was():
    feed = {"x": np.random.RandomState(2).randn(2, 24, 32).astype("f4"), "pos": np.tile(np.arange(24), (2, 1))}
    plain_main, plain_scope, plain_exe, plain_out = attention_layer(False)
    main, scope, exe, out = attention_layer("feature")
    wq = np.asarray(plain_scope.find_var("a.q.w")).reshape(32, 4, 16)
    assert np.asarray(scope.find_var("a.q.w")).shape == (32, 4 * 32) and "a.gate.w" not in scope.var_names()
    for name in ("a.k.w", "a.v.w", "a.out.w", "a.q_norm.w", "a.k_norm.w"):
        scope.set_var(name, plain_scope.find_var(name))
    scope.set_var("a.q.w", jnp.asarray(np.concatenate([wq, np.zeros_like(wq)], -1).reshape(32, 128)))   # a head's q, then its gate
    want = np.asarray(plain_exe.run(plain_main, feed=feed, fetch_list=[plain_out], scope=plain_scope)[0])
    agree(2.0 * np.asarray(exe.run(main, feed=feed, fetch_list=[out], scope=scope)[0]), want, tol=1e-6)
    gate_ops = [op.type for op in main.global_block().ops if "attention_gate" in (op.attrs.get("op_namescope") or "")]
    assert gate_ops == ["slice", "cast", "sigmoid", "cast", "elementwise_mul", "cast"]
    def listing(m):     # (sibling scopes of one name are numbered by the process's table)
        return [(op.type, sorted((k, v) for k, v in op.attrs.items() if k != "op_namescope")) for op in m.global_block().ops]

    assert listing(attention_layer(True)[0]) == listing(attention_layer("head")[0])
    with pytest.raises(ValueError, match="head_gate"):
        attention_layer("channel")


def test_a_quarter_of_a_256_wide_head_turns_and_features_64_to_255_pass_bit_for_bit():
    rng = np.random.RandomState(4)
    x = jnp.asarray(rng.randn(1, 2, 16, 256), jnp.bfloat16)
    pos = jnp.asarray(rng.randint(0, 16384, (1, 16)), jnp.int32)
    out = np.asarray(lower("rotary_embedding", {"X": x, "Positions": pos}, {"theta": 1e7, "rotary_dim": 64})["Out"], "f4")
    np.testing.assert_array_equal(out[..., 64:], np.asarray(x, "f4")[..., 64:])
    angle = np.asarray(pos, "f8")[0][:, None] * 1e7 ** (-np.arange(32) / 32)
    a, b = np.asarray(x, "f8")[0, :, :, :32], np.asarray(x, "f8")[0, :, :, 32:64]
    want = np.concatenate([a * np.cos(angle) - b * np.sin(angle), b * np.cos(angle) + a * np.sin(angle)], -1)
    agree(out[0, :, :, :64], want, tol=1e-2)       # bf16 out; the float32 angle at position 16383 is itself 3e-4 off float64's


def routed(x, w, top_k=10):
    return lower("moe_router", {"X": x, "W": w}, {"top_k": top_k, "norm_topk_prob": True})


def softmax(t):
    e = np.exp(t - t.max(-1, keepdims=True))
    return e / e.sum(-1, keepdims=True)


def test_the_routers_ten_weights_are_the_renormalised_softmax_and_sum_to_1():
    rng = np.random.RandomState(8)
    x, w = rng.randn(256, 32).astype("f4"), rng.randn(32, 512).astype("f4") / 4
    out = routed(x, w)
    weights, chosen = np.asarray(out["TopKProb"], "f8"), np.asarray(out["TopKIndex"])
    assert weights.shape == (256, 10)
    agree(weights.sum(-1), np.ones(256), tol=1e-6)
    scores = softmax(x.astype("f8") @ w.astype("f8"))
    np.testing.assert_array_equal(np.sort(chosen, -1), np.sort(np.argsort(-scores, -1)[:, :10], -1))
    mine = np.take_along_axis(scores, chosen, -1)
    agree(weights, mine / mine.sum(-1, keepdims=True), tol=1e-5)


def silu(t):
    return t / (1.0 + np.exp(-t))


def sparse_layer(shared_gate):
    with unique_name.guard():
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup):
            x = layers.data("x", [24, 32], dtype="float32")
            attrs = {n: transformer._attr(f"m.{n}") for n in ("router", "gate", "up", "down", "s_gate", "s_up", "s_down", "w_s")}
            out, _, _ = layers.moe(x, 16, 8, 4, norm_topk_prob=True, router_attr=attrs["router"], gate_attr=attrs["gate"],
                                   up_attr=attrs["up"], down_attr=attrs["down"], shared_experts=1, shared_width=8,
                                   shared_attrs=(attrs["s_gate"], attrs["s_up"], attrs["s_down"]),
                                   shared_gate_attr=attrs["w_s"] if shared_gate else None)
    main.random_seed = startup.random_seed = 7
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.TPUPlace(0))
    exe.run(startup, scope=scope)
    return main, scope, exe, out


def test_the_shared_gate_at_zero_halves_the_shared_expert_and_it_stands_in_its_scope():
    feed = {"x": np.random.RandomState(3).randn(2, 24, 32).astype("f4")}
    main, scope, exe, out = sparse_layer(True)
    plain_main, plain_scope, plain_exe, plain_out = sparse_layer(False)
    for name in plain_scope.var_names():
        plain_scope.set_var(name, scope.find_var(name))
    assert np.asarray(scope.find_var("m.w_s")).shape == (32, 1) and "m.w_s" not in plain_scope.var_names()
    x8 = feed["x"].astype("f8")
    p = {n: np.asarray(scope.find_var(f"m.{n}"), "f8") for n in ("s_gate", "s_up", "s_down", "w_s")}
    shared = (silu(x8 @ p["s_gate"]) * (x8 @ p["s_up"])) @ p["s_down"]
    gated = np.asarray(exe.run(main, feed=feed, fetch_list=[out], scope=scope)[0], "f8")
    ungated = np.asarray(plain_exe.run(plain_main, feed=feed, fetch_list=[plain_out], scope=plain_scope)[0], "f8")
    agree(ungated - gated, shared * (1.0 - 1.0 / (1.0 + np.exp(-(x8 @ p["w_s"])))), tol=1e-5)
    scope.set_var("m.w_s", jnp.zeros((32, 1), jnp.float32))
    halved = np.asarray(exe.run(main, feed=feed, fetch_list=[out], scope=scope)[0], "f8")
    agree(ungated - halved, 0.5 * shared, tol=1e-5)
    scopes = [op.attrs.get("op_namescope") for op in main.global_block().ops if "moe_shared_gate" in (op.attrs.get("op_namescope") or "")]
    assert len(scopes) == 6 and all(s.startswith("shared_expert") for s in scopes)


def test_the_32_shares_of_a_layer_with_the_gated_shared_expert_counted_once_add_up_to_the_uncut_layer():
    """Thirty-two chips hold 16 of 512 experts each behind THIS router (a
    softmax over 512, the top 10, renormalised).  Every chip computes the shared
    expert and its gate alike: counted once, the 32 routed parts and it are the
    uncut layer's output as the plain equations write it."""
    rng = np.random.RandomState(69)
    tokens, experts, k, d, f = 64, 512, 10, 16, 8
    m = rng.randn(tokens, d).astype("f4")
    router = rng.randn(d, experts).astype("f4") / 2
    gate, up = (rng.randn(experts, d, f).astype("f4") / 4 for _ in range(2))
    down = rng.randn(experts, f, d).astype("f4") / 4
    shared = [rng.randn(d, f).astype("f4") / 4, rng.randn(d, f).astype("f4") / 4, rng.randn(f, d).astype("f4") / 4]
    w_s = rng.randn(d, 1).astype("f4")
    out = routed(m, router)

    def share(first, count):
        ins = {"X": m, "TopKProb": out["TopKProb"], "TopKIndex": out["TopKIndex"], "Load": out["Load"],
               "WGate": gate[first:first + count], "WUp": up[first:first + count], "WDown": down[first:first + count]}
        return lower("moe_experts", ins, {"held": [first, count]})

    shares = [share(first, 16) for first in range(0, experts, 16)]
    assert len(shares) == 32 and sum(int(np.asarray(s["Held"])[0]) for s in shares) == tokens * k
    assert all(int(np.asarray(s["Dropped"])[0]) == 0 for s in shares)
    m8 = m.astype("f8")
    scores = softmax(m8 @ router.astype("f8"))
    chosen = np.argsort(-scores, -1)[:, :k]
    weights = np.take_along_axis(scores, chosen, -1)
    weights = weights / weights.sum(-1, keepdims=True)
    once = (silu(m8 @ shared[0]) * (m8 @ shared[1])) @ shared[2] / (1.0 + np.exp(-(m8 @ w_s)))
    want = once.copy()
    for t in range(tokens):
        for e, w_e in zip(chosen[t], weights[t]):
            want[t] += w_e * ((silu(m8[t] @ gate[e]) * (m8[t] @ up[e])) @ down[e])
    agree(sum(np.asarray(s["Out"], "f8") for s in shares) + once, want, tol=1e-5)
    assert np.abs(sum(np.asarray(s["Out"], "f8") for s in shares) + 32 * once - want).max() > 1e-2    # 32 times is another layer
