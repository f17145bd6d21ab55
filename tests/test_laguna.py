"""Laguna-XS.2's parts and the whole, tiny on the CPU (ISSUE 65).

(a) `rotary_embedding`'s three new attributes: none of them is today's op, text
    for text; `rotary_dim` passes the rest of a head bit for bit; `inv_freq`
    takes theta's place; `scale` lengthens the turned features alone; both
    kinds' embeddings are relative; YaRN's table at the published keys;
(b) heads, the rotary description and the gate a layer kind in
    `build_causal_lm`: the q, out and gate matrices' widths by kind, YaRN's
    `factor` moves the full layers and no window layer, a gate forced to 1 is
    the ungated layer and forced to 0 the residual alone, and the counters;
(c) the window of 512: a query at position 5000 does not see key 4488 and sees
    key 4489; the router's eight weights sum to 2.5; the sixteen shares of one
    sparse layer, the shared expert counted once, add up to the uncut layer;
(d) a toy of the same pattern (layer 0 dense and full, then two periods; 6 and
    8 query heads on 2 key/value heads; window 8 of 64 positions; 16 experts
    top 4 with one shared, every expert held) in float32 against the
    benchmark's reference (benchmark/models/laguna.py) on seeded weights: loss,
    logits, routing, every stage, every parameter's gradient, with and without
    `recompute_layers` to the last bit; in bf16 within the benchmark's
    tolerances; and the faults the comparison refuses.

One compiled tiny model serves (d): `float32_run`.
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
from benchmark.models import laguna  # noqa: E402
from paddle_tpu import layers, monitor  # noqa: E402
from paddle_tpu.core import unique_name  # noqa: E402
from paddle_tpu.core.lowering import LoweringContext, count_layer_forms  # noqa: E402
from paddle_tpu.core.registry import get_op_def  # noqa: E402
from paddle_tpu.models import transformer  # noqa: E402
from paddle_tpu.ops import masked_attention  # noqa: E402

CFG = mf.read_json("benchmark/configs/laguna-xs.2.json")
FULL, WINDOW = CFG["rope_parameters"]["full_attention"], CFG["rope_parameters"]["sliding_attention"]


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


# -- (a) the rotary op's three attributes ------------------------------------------------------------

def rotary_op(**kw):
    with unique_name.guard():
        main = fluid.Program()
        with fluid.program_guard(main, fluid.Program()):
            x = layers.data("x", [4, 16, 128], dtype="float32")
            layers.rotary_embedding(x, layers.data("pos", [16], dtype="int64"), **kw)
    return main.global_block().ops[-1]


def test_a_default_is_no_attribute_and_the_op_without_them_lowers_to_todays_text():
    """`rotary_dim` the head's width, `inv_freq` None and `scale` 1 are no
    attributes of the op, so the programs that stood keep their text; and the
    lowering without them traces to the jaxpr of the rotation as it was written
    before ISSUE 65 (`then`, below), equation for equation."""
    assert rotary_op(theta=5e5).attrs == {"theta": 5e5}
    assert rotary_op(theta=1e4, rotary_dim=128, inv_freq=None, scale=1.0).attrs == {"theta": 1e4}
    stated = rotary_op(theta=5e5, rotary_dim=64, inv_freq=[0.5] * 32, scale=1.5).attrs
    assert stated == {"theta": 5e5, "rotary_dim": 64, "inv_freq": (0.5,) * 32, "scale": 1.5}
    for wrong, said in ((dict(rotary_dim=130), "rotary_dim=130"), (dict(rotary_dim=63), "rotary_dim=63"),
                        (dict(rotary_dim=64, inv_freq=[1.0] * 64), "64 frequencies for 32 pairs")):
        with pytest.raises(ValueError, match=said):
            rotary_op(**wrong)

    def then(x, pos):   # ops/moe_ops.py at the parent commit, rotate-half
        half = x.shape[-1] // 2
        inv_freq = 1e4 ** (-np.arange(half, dtype=np.float32) / half)
        angle = pos.astype(jnp.float32)[:, None, :, None] * inv_freq
        cos, sin = jnp.cos(angle), jnp.sin(angle)
        x1, x2 = x[..., :half].astype(jnp.float32), x[..., half:].astype(jnp.float32)
        return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1).astype(x.dtype)

    x, pos = jnp.zeros((2, 4, 16, 128), jnp.bfloat16), jnp.zeros((2, 16), jnp.int32)
    op, ctx = SimpleNamespace(type="rotary_embedding", attr=lambda n, d=None: {"theta": 1e4}.get(n, d)), LoweringContext(jax.random.PRNGKey(0))
    now = jax.make_jaxpr(lambda x, pos: get_op_def("rotary_embedding").lower(ctx, op, {"X": [x], "Positions": [pos]})["Out"])(x, pos)
    assert str(now) == str(jax.make_jaxpr(then)(x, pos))


def rotated(x, pos, stated, layout="bhld"):
    table, turned, factor = laguna.rotary_table(stated, x.shape[-1])
    attrs = {"theta": float(stated["rope_theta"]), "rotary_dim": turned, "scale": factor, "layout": layout}
    if stated["rope_type"] == "yarn":
        attrs["inv_freq"] = tuple(table)
    return np.asarray(lower("rotary_embedding", {"X": x, "Positions": pos}, attrs)["Out"])


def test_a_partial_rotation_passes_the_rest_of_the_head_bit_for_bit_and_agrees_with_the_reference():
    rng = np.random.RandomState(0)
    x = rng.randn(2, 3, 16, 128).astype("f4")
    pos = np.tile(np.arange(16) * 97 + 5, (2, 1))
    out = rotated(x, pos, FULL)
    np.testing.assert_array_equal(out[..., 64:], x[..., 64:])                      # features 64 to 127 pass
    assert np.abs(out[..., :64] - x[..., :64]).max() > 0.1
    table = laguna.rotary_table(FULL, 128)
    for b in range(2):      # the reference's own rotation, [L, H, dh], float64
        want = laguna.rotate(x[b].transpose(1, 0, 2).astype("f8"), pos[b].astype("f8"), table, np)
        agree(out[b].transpose(1, 0, 2), want, tol=2e-4)                            # a float32 angle at position 1460
    whole = rotated(x, pos, WINDOW)
    want = laguna.rotate(x[0].transpose(1, 0, 2).astype("f8"), pos[0].astype("f8"), laguna.rotary_table(WINDOW, 128), np)
    agree(whole[0].transpose(1, 0, 2), want, tol=2e-4)
    by_position = rotated(x.transpose(0, 2, 1, 3), pos, FULL, layout="blhd")       # (B, L, H, dh): the same numbers
    np.testing.assert_array_equal(by_position.transpose(0, 2, 1, 3), out)
    bf16 = np.asarray(lower("rotary_embedding", {"X": jnp.asarray(x, jnp.bfloat16), "Positions": pos},
                            {"theta": 5e5, "rotary_dim": 64})["Out"])
    np.testing.assert_array_equal(bf16[..., 64:], np.asarray(jnp.asarray(x, jnp.bfloat16))[..., 64:])


def test_the_scale_lengthens_the_turned_half_by_the_attention_factor_and_the_passed_half_by_nothing():
    x = np.random.RandomState(1).randn(1, 2, 8, 128).astype("f4")
    pos = np.tile(np.arange(8) * 2000, (1, 1))
    plain = np.asarray(lower("rotary_embedding", {"X": x, "Positions": pos}, {"theta": 5e5, "rotary_dim": 64})["Out"], "f8")
    scaled = np.asarray(lower("rotary_embedding", {"X": x, "Positions": pos},
                              {"theta": 5e5, "rotary_dim": 64, "scale": FULL["attention_factor"]})["Out"], "f8")
    turned, passed = np.linalg.norm(scaled[..., :64], axis=-1), np.linalg.norm(scaled[..., 64:], axis=-1)
    agree(turned, np.linalg.norm(x[..., :64].astype("f8"), axis=-1) * 1.4158883, tol=1e-5)
    agree(passed, np.linalg.norm(x[..., 64:].astype("f8"), axis=-1), tol=1e-7)
    agree(scaled[..., :64], plain[..., :64] * 1.4158883, tol=1e-6)


@pytest.mark.parametrize("stated", [FULL, WINDOW], ids=["yarn_half", "plain_whole"])
def test_both_kinds_embeddings_are_relative(stated):
    """Shifting EVERY position by one constant leaves q . k of every pair as it was, to rounding."""
    rng = np.random.RandomState(2)
    q, k = rng.randn(1, 1, 12, 128).astype("f4"), rng.randn(1, 1, 12, 128).astype("f4")
    pos = np.arange(12)[None] * 37

    def scores(shift):
        return np.einsum("bhqd,bhkd->bhqk", rotated(q, pos + shift, stated).astype("f8"), rotated(k, pos + shift, stated).astype("f8"))

    agree(scores(1000), scores(0), tol=2e-4)
    assert np.abs(scores(0) - np.einsum("bhqd,bhkd->bhqk", q.astype("f8"), k.astype("f8"))).max() > 0.1


def test_yarns_table_at_the_published_keys():
    """low 5 and high 16: pairs 0 to 4 turn as theta says, pairs 16 to 31 a
    sixty-fourth as fast, a linear ramp between; `attention_factor` is 0.1 ln 64
    + 1; the framework's table and the reference's are one table, each by its
    own lines."""
    own = 5e5 ** (-np.arange(32) / 32.0)
    table, turned, factor = laguna.rotary_table(FULL, CFG["head_dim"])
    assert turned == 64 and factor == FULL["attention_factor"] == pytest.approx(0.1 * np.log(64) + 1, abs=1e-7)

    def pair_of(turns):
        return 64 * np.log(4096 / (turns * 2 * np.pi)) / (2 * np.log(5e5))

    assert (pair_of(64), pair_of(1)) == (pytest.approx(5.66, abs=0.01), pytest.approx(15.80, abs=0.01))
    assert (np.floor(pair_of(64)), np.ceil(pair_of(1))) == (5, 16)
    np.testing.assert_allclose(table[:6], own[:6], rtol=1e-12)                       # the ramp starts AFTER pair 5
    np.testing.assert_allclose(table[16:], own[16:] / 64, rtol=1e-12)
    ramp = (np.arange(6, 16) - 5) / 11.0
    np.testing.assert_allclose(table[6:16], own[6:16] * (1 - ramp) + own[6:16] / 64 * ramp, rtol=1e-12)
    mine = transformer.yarn_frequencies(5e5, 64, 64, 4096, 64, 1)
    assert len(mine) == 32
    np.testing.assert_allclose(mine, table, rtol=1e-12)
    plain, turned, factor = laguna.rotary_table(WINDOW, CFG["head_dim"])
    assert turned == 128 and factor == 1.0
    np.testing.assert_allclose(plain, 1e4 ** (-np.arange(64) / 64.0), rtol=1e-12)
    assert laguna._rotary(CFG, "sliding_attention") == {"theta": 1e4, "rotary_dim": 128}
    assert set(laguna._rotary(CFG, "full_attention")) == {"theta", "rotary_dim", "inv_freq", "scale"}


# -- (b) a statement a layer kind -------------------------------------------------------------------------

KINDS = ["full_attention", "sliding_attention", "sliding_attention", "sliding_attention", "full_attention"]


def small(factor=64, gate=True, **kw):
    rope = {"full_attention": dict(theta=5e5, rotary_dim=8, inv_freq=transformer.yarn_frequencies(5e5, 8, factor, 16, 4, 1),
                                   scale=1.4158883), "sliding_attention": 1e4}
    with unique_name.guard():
        main, startup, feeds, fetches = transformer.build_causal_lm(
            vocab_size=32, seq_len=24, d_model=32, n_heads={"full_attention": 6, "sliding_attention": 8}, n_kv_heads=2,
            head_dim=16, qk_norm=None, rope_theta=rope, layer_types=KINDS, sliding_window=8, attention_gate=gate,
            num_dense_layers=5, dense_width=32, load_balance_coef=0.0, router_z_coef=0.0, with_optimizer=False, **kw)
    main.random_seed = startup.random_seed = 11
    return main, startup, feeds, fetches


def layer_outputs(main, startup, fetches, feed):
    """Every layer's output (the residual sum that closes it), then the logits."""
    ops = main.global_block().ops
    closes = [op.outputs["Out"][0] for op in ops if op.type == "elementwise_add"][1::2]
    scope, exe = fluid.Scope(), fluid.Executor(fluid.TPUPlace(0))
    exe.run(startup, scope=scope)
    return [np.asarray(t) for t in exe.run(main.clone(for_test=True), feed=feed, fetch_list=closes + [fetches["logits"].name],
                                           scope=scope)]


FEED = {"ids": np.random.RandomState(5).randint(0, 32, (2, 24)).astype("int64")}
FEED = dict(FEED, labels=FEED["ids"], pos_ids=np.tile(np.arange(24), (2, 1)))


def test_heads_the_rotary_description_and_the_gate_are_stated_a_layer_kind():
    main, startup, _, fetches = small()
    shapes = {p.name: tuple(p.shape) for p in main.all_parameters()}
    for i, heads in enumerate([6, 8, 8, 8, 6]):
        assert shapes[f"lm.l{i}.attn.q.w"] == (32, heads * 16) and shapes[f"lm.l{i}.attn.out.w"] == (heads * 16, 32)
        assert shapes[f"lm.l{i}.attn.gate.w"] == (32, heads) and shapes[f"lm.l{i}.attn.k.w"] == (32, 32)
    ops = main.global_block().ops
    turns = [{k: v for k, v in op.attrs.items() if k != "op_namescope"} for op in ops if op.type == "rotary_embedding"]
    assert len(turns) == 10 and [sorted(a) for a in turns[:2]] == [["inv_freq", "rotary_dim", "scale", "theta"]] * 2
    assert turns[2] == turns[3] == {"theta": 1e4} and turns[8] == turns[0]
    gates = [op for op in ops if op.type == "sigmoid"]
    assert len(gates) == 5 and all("attention_gate" in op.attrs["op_namescope"] for op in gates)
    assert not [p for p in small(gate=False)[0].all_parameters() if ".attn.gate." in p.name]
    # one number for the stack is today's program; a kind a dict does not name takes the default
    with unique_name.guard():
        plain = transformer.build_causal_lm(vocab_size=32, seq_len=24, d_model=32, n_layers=2, n_heads={"conv": 3}, expert_width=8,
                                            num_experts=4, top_k=2, with_optimizer=False)[0]
    assert {p.name: tuple(p.shape) for p in plain.all_parameters()}["lm.l0.attn.q.w"] == (32, 32)       # 16 heads of 2
    with pytest.raises(ValueError, match="turns by theta alone"):
        transformer.build_causal_lm(layer_types=["latent_attention"], latent=dict(rank=8, nope_dim=8, rope_dim=8, v_dim=8, rope=True),
                                    rope_theta={"latent_attention": dict(theta=1e4, rotary_dim=4)}, d_model=32, n_heads=2)


def test_yarns_factor_moves_the_full_layers_output_and_no_window_layers():
    """The first layer is full: its output moves with the factor.  A window
    layer's OWN part (what it adds to its input) reads no table but its own."""
    a, b = (layer_outputs(*small(factor)[::1][:2], small(factor)[3], FEED) for factor in (64, 8))
    assert np.abs(a[0] - b[0]).max() > 1e-6
    # the window layers alone: the same stack with the full layers' kind renamed away from the dict
    def windows_only(factor):
        main, startup, _, fetches = small(factor)
        ops = main.global_block().ops
        turned = [op for op in ops if op.type == "rotary_embedding"]
        return [{k: v for k, v in op.attrs.items() if k != "op_namescope"} for op in turned[2:8]]
    assert windows_only(64) == windows_only(8) == [{"theta": 1e4}] * 6
    full = lambda factor: [dict(op.attrs) for op in small(factor)[0].global_block().ops if op.type == "rotary_embedding"][0]  # noqa: E731
    assert full(64)["inv_freq"] != full(8)["inv_freq"]


def test_shifting_every_position_by_a_constant_leaves_the_logits_as_they_were():
    main, startup, _, fetches = small()
    moved = dict(FEED, pos_ids=FEED["pos_ids"] + 3000)
    a, b = layer_outputs(main, startup, fetches, FEED), layer_outputs(main, startup, fetches, moved)
    for x, y in zip(a, b):
        agree(y, x, tol=2e-4)
    far = layer_outputs(main, startup, fetches, dict(FEED, pos_ids=FEED["pos_ids"] * 7))
    assert np.abs(far[-1] - a[-1]).max() > 1e-5


@pytest.mark.parametrize("forced,what", [(1.0, "the ungated layer"), (0.0, "the residual alone")])
def test_a_gate_forced_to(forced, what, monkeypatch):
    """The gate's sigmoid replaced by a constant: at 1 every layer's output is
    the ungated program's (the same seed: the gate's matrices are drawn after
    the layer's others... so the parameters are handed over by name); at 0 the
    attention adds nothing and a layer is x + ffn(rms(x))."""
    gated = small()
    ungated = small(gate=False)
    scope, exe = fluid.Scope(), fluid.Executor(fluid.TPUPlace(0))
    exe.run(gated[1], scope=scope)
    names = [op.outputs["Out"][0] for op in ungated[0].global_block().ops if op.type == "elementwise_add"]
    want = exe.run(ungated[0].clone(for_test=True), feed=FEED, fetch_list=names, scope=scope)     # the gated program's parameters
    monkeypatch.setattr(layers, "sigmoid", lambda t: layers.scale(t, scale=0.0, bias=forced))
    forced_program = small()
    got = exe.run(forced_program[0].clone(for_test=True), feed=FEED, scope=scope,
                  fetch_list=[op.outputs["Out"][0] for op in forced_program[0].global_block().ops if op.type == "elementwise_add"])
    if forced == 1.0:
        for g, w in zip(got, want):
            agree(g, w, tol=1e-6)
    else:   # h = x + 0: the first sum of a layer is its input
        ops = forced_program[0].global_block().ops
        sums = [op for op in ops if op.type == "elementwise_add"]
        inputs = exe.run(forced_program[0].clone(for_test=True), feed=FEED, scope=scope, fetch_list=[op.inputs["X"][0] for op in sums[0::2]])
        for h, x in zip(got[0::2], inputs):
            np.testing.assert_array_equal(np.asarray(h), np.asarray(x))
        assert np.abs(np.asarray(got[1]) - np.asarray(want[1])).max() > 1e-6


def test_the_three_counters_are_counted_once_a_trace_of_a_program_with_a_backward_pass():
    main = small()[0]
    with unique_name.guard():
        trained = transformer.build_causal_lm(
            vocab_size=32, seq_len=24, d_model=32, n_heads={"full_attention": 6, "sliding_attention": 8}, n_kv_heads=2, head_dim=16,
            qk_norm=None, rope_theta={"full_attention": dict(theta=5e5, rotary_dim=8, scale=1.4), "sliding_attention": 1e4},
            layer_types=KINDS, sliding_window=8, attention_gate=True, num_dense_layers=5, dense_width=32)[0]
    monitor.reset()
    monitor.enable()
    try:
        count_layer_forms(main.global_block().ops)                   # no backward: nothing is counted
        assert not monitor.get_monitor().counter_values().get("lowering.gated_attention_layers")
        count_layer_forms(trained.global_block().ops)
        counted = monitor.get_monitor().counter_values()
        assert counted["lowering.gated_attention_layers"] == 5 and counted["lowering.rotary_tables"] == 2
        assert [counted[f"lowering.query_heads_by_layer.{i}"] for i in range(5)] == [6, 8, 8, 8, 6]
    finally:
        monitor.disable()
        monitor.reset()


# -- (c) the window's edge, the router's weights, the shares ---------------------------------------------------

def test_a_query_at_5000_does_not_see_key_4488_and_sees_key_4489():
    """i - 512 < j <= i: the rule as the kernels compute it, as the reference
    writes it, in the block maps at the cell's shape, and through the op."""
    for rule in (lambda q, k: masked_attention.window_allowed(q, k, 512), lambda q, k: laguna.allowed(q, k, 512)):
        assert not rule(np.int64(5000), np.int64(4488)) and rule(np.int64(5000), np.int64(4489))
        assert rule(np.int64(5000), np.int64(5000)) and not rule(np.int64(5000), np.int64(5001))
    assert laguna.allowed(np.int64(5000), np.int64(0), None) and not laguna.allowed(np.int64(5), np.int64(6), None)
    assert masked_attention.window_block(16384, 512) == 512 and masked_attention.window_block(8192, 512) == 512
    plan = masked_attention.window_plan(16384, 64, 512)
    visited = masked_attention.block_maps(plan)[0]
    pairs = masked_attention.window_pairs(16384, 512)
    assert pairs == laguna._pairs(16384, 512) == 512 * 513 // 2 + (16384 - 512) * 512
    assert np.count_nonzero(visited.block_mask[0]) * 512 * 512 / pairs == pytest.approx(2.0, abs=0.07)   # two blocks a block of queries
    rng = np.random.RandomState(0)
    q, k, v = (rng.randn(1, 1, 5120, 8).astype("f4") for _ in range(3))
    attrs = {"causal": False, "mask": "sliding_window", "mask_block": 512}

    def at_5000(values):
        return np.asarray(lower("fused_attention", {"Q": q, "K": k, "V": values}, attrs)["Out"])[0, 0, 5000]

    base = at_5000(v)
    for key, seen in ((4488, False), (4489, True)):
        moved = v.copy()
        moved[0, 0, key] += 100.0
        assert (np.abs(at_5000(moved) - base).max() > 1e-3) == seen, key


def routed(x, w, top_k=8):
    return lower("moe_router", {"X": x, "W": w}, {"top_k": top_k, "norm_topk_prob": True, "scoring": "sigmoid",
                                                  "routed_scaling_factor": 2.5})


def test_the_routers_eight_weights_are_the_renormalised_sigmoids_and_sum_to_2_5():
    rng = np.random.RandomState(8)
    x, w = rng.randn(256, 32).astype("f4"), rng.randn(32, 256).astype("f4") / 4
    out = routed(x, w)
    weights, chosen = np.asarray(out["TopKProb"], "f8"), np.asarray(out["TopKIndex"])
    agree(weights.sum(-1), np.full(256, 2.5), tol=1e-6)
    scores = 1.0 / (1.0 + np.exp(-(x.astype("f8") @ w.astype("f8"))))
    np.testing.assert_array_equal(np.sort(chosen, -1), np.sort(np.argsort(-scores, -1)[:, :8], -1))
    mine = np.take_along_axis(scores, chosen, -1)
    agree(weights, 2.5 * mine / mine.sum(-1, keepdims=True), tol=1e-5)


def test_the_sixteen_shares_of_a_layer_with_the_shared_expert_counted_once_add_up_to_the_uncut_layer():
    """Sixteen chips hold 16 of 256 experts each behind THIS router (256
    sigmoids, the top 8, renormalised, times 2.5).  Every chip computes the
    shared expert alike: counted once, the sixteen routed parts and it are the
    uncut layer's output as the plain equations write it."""
    rng = np.random.RandomState(65)
    tokens, experts, k, d, f = 64, 256, 8, 16, 8
    m = rng.randn(tokens, d).astype("f4")
    router = rng.randn(d, experts).astype("f4") / 2
    gate, up = (rng.randn(experts, d, f).astype("f4") / 4 for _ in range(2))
    down = rng.randn(experts, f, d).astype("f4") / 4
    shared = [rng.randn(d, f).astype("f4") / 4, rng.randn(d, f).astype("f4") / 4, rng.randn(f, d).astype("f4") / 4]
    out = routed(m, router)

    def share(first, count):
        ins = {"X": m, "TopKProb": out["TopKProb"], "TopKIndex": out["TopKIndex"], "Load": out["Load"],
               "WGate": gate[first:first + count], "WUp": up[first:first + count], "WDown": down[first:first + count]}
        return lower("moe_experts", ins, {"held": [first, count]})

    shares = [share(first, 16) for first in range(0, experts, 16)]
    assert len(shares) == 16 and sum(int(np.asarray(s["Held"])[0]) for s in shares) == tokens * k
    assert all(int(np.asarray(s["Dropped"])[0]) == 0 for s in shares)

    def silu(t):
        return t / (1.0 + np.exp(-t))

    m8 = m.astype("f8")
    scores = 1.0 / (1.0 + np.exp(-(m8 @ router.astype("f8"))))
    chosen = np.argsort(-scores, -1)[:, :k]
    weights = np.take_along_axis(scores, chosen, -1)
    weights = 2.5 * weights / weights.sum(-1, keepdims=True)
    once = (silu(m8 @ shared[0]) * (m8 @ shared[1])) @ shared[2]
    want = once.copy()
    for t in range(tokens):
        for e, w_e in zip(chosen[t], weights[t]):
            want[t] += w_e * ((silu(m8[t] @ gate[e]) * (m8[t] @ up[e])) @ down[e])
    agree(sum(np.asarray(s["Out"], "f8") for s in shares) + once, want, tol=1e-5)
    assert np.abs(sum(np.asarray(s["Out"], "f8") for s in shares) + 16 * once - want).max() > 1e-2    # sixteen times is another layer


# -- (d) the whole model --------------------------------------------------------------------------------

TINY = dict(hidden_size=32, num_key_value_heads=2, head_dim=16, intermediate_size=64, moe_intermediate_size=16,
            shared_expert_intermediate_size=16, num_experts=16, num_routed_experts=16, experts_held_first=0, num_experts_per_tok=4,
            vocab_size=96, sliding_window=8, num_hidden_layers=9, layer_types=KINDS + KINDS[1:],
            mlp_layer_types=["dense"] + ["sparse"] * 8, num_attention_heads_per_layer=[6, 8, 8, 8, 6, 8, 8, 8, 6])
JOB = dict(seq_len=64, batch_per_chip=4)


@pytest.fixture(scope="module", autouse=True)
def every_position_is_sampled():
    from benchmark.models import lfm2

    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(lfm2, "LOGIT_SAMPLE", 64)
        patch.setattr(laguna, "ATTENTION_SAMPLE", 192)      # three runs of 64: every position of the toy
        yield


def tiny_model(dtype, cfg_over=None, **job):
    cfg = dict(CFG, compute_dtype=dtype, **{**TINY, **(cfg_over or {})})
    job = dict(mf.read_json("benchmark/traffic/train-gated-swa-s16384.json"), **JOB, **job)
    with unique_name.guard():
        main, startup, feeds, loss, names = laguna.build(cfg, job)
    main.random_seed = startup.random_seed = 3
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.TPUPlace(0))
    exe.run(startup, scope=scope)
    return cfg, job, main, loss, names, scope, exe


def params_of(main, scope):
    return {p.name: np.asarray(scope.find_var(p.name)) for p in main.all_parameters()}


def reference_of(cfg, params, rows, **kw):
    return [np.asarray(w) for w in jax.jit(lambda p, b: laguna.reference(p, b, cfg, **kw))(params, rows)]


def one_step(main, loss, scope, exe, batch):
    """(the step's loss, Adam's first moments, the trace's `lowering.` counters) of one step through `train_loop`."""
    losses = []
    monitor.reset()
    monitor.enable()
    try:
        before = {k: v for k, v in monitor.get_monitor().counter_values().items() if k.startswith("lowering.")}
        fluid.train_loop(exe, main, iter([batch]), [loss], scope=scope, log_period=1,
                         on_logged=lambda i, vals: losses.append(float(np.asarray(vals[0]).reshape(-1)[0])))
        counters = {k: v - before.get(k, 0) for k, v in monitor.get_monitor().counter_values().items()
                    if k.startswith("lowering.")}
    finally:
        monitor.disable()
        monitor.reset()
    moments = {p.name: np.asarray(scope.find_var(p.name + "_moment1_0")) for p in main.all_parameters()}
    return losses.pop(), moments, counters


@pytest.fixture(scope="module")
def float32_run():
    """The toy built twice from the same seed, every layer a `recompute_scope`
    (as the cell builds it) and none, one step each on the same batch; the
    recomputed one's `for_test` clone against the reference."""
    with jax.default_matmul_precision("highest"):
        cfg, job, main, loss, names, scope, exe = tiny_model("float32")
        rows = laguna.make_batch(np.random.RandomState(3), cfg, job, 8)
        got = exe.run(main.clone(for_test=True), feed=rows, fetch_list=list(names), scope=scope)
        before = params_of(main, scope)
        want = reference_of(cfg, before, rows)
        batch = laguna.make_batch(np.random.RandomState(4), cfg, job, 4)
        ref_loss, ref_grads = jax.jit(jax.value_and_grad(lambda p: laguna.reference(p, batch, cfg)[0]))(before)
        step_loss, moments, counters = one_step(main, loss, scope, exe, batch)
        _, _, plain_main, plain_loss, _, plain_scope, plain_exe = tiny_model("float32", recompute_layers=False)
        plain = one_step(plain_main, plain_loss, plain_scope, plain_exe, batch)
    return SimpleNamespace(cfg=cfg, job=job, main=main, got=got, want=want, before=before, rows=rows, names=names,
                           moments=moments, counters=counters, plain=plain, scope=scope,
                           ref_loss=float(ref_loss), step_loss=step_loss,
                           ref_grads={k: np.asarray(v) for k, v in ref_grads.items()})


def test_float32_loss_logits_routing_and_every_stage_agree_with_the_reference(float32_run):
    found = laguna.compare(float32_run.got, float32_run.want)
    assert found["left_out"] == found["routed_differently"] == found["routed_differently_above_margin"] == 0
    assert found["router_choice_differs"] == 0
    assert found["loss_error"] < 1e-5 and found["logit_error"] < 2e-5, found
    assert max(found["router_prob_error"], found["experts_error"], found["shared_error"], found["attention_error"],
               found["qk_error"], found["gate_error"], found["gated_error"]) < 2e-5, found
    assert found["attention_error_other_grouping"] > 0.1 and found["gated_error_no_gate"] > 0.1      # what they refuse
    assert found["gate_error_bf16"] > 1e-3 and found["gated_error_next_head"] > 1e-2
    assert abs(found["window_edge_missing"]) < 1e-4 and abs(found["window_edge_extra"]) < 1e-4    # the rule as stated
    assert found["reference_self_error"] < 1e-5 and laguna.failed_limits(found) == []
    assert laguna.reference_error(float32_run.got, float32_run.want) < 2e-5
    assert abs(float32_run.step_loss - float32_run.ref_loss) < 1e-5 * float32_run.ref_loss
    assert found["held_rows_share"] == [1.0] * 8                                      # every expert held
    assert np.asarray(float32_run.got[1]).shape == (64, 8, 96)
    assert np.asarray(float32_run.got[2]).shape == (8, 64, 4)                                     # the choice: every row
    assert np.asarray(float32_run.got[3]).shape == (laguna.STAGE_ROWS, 64, 32)                    # the router's input: the stage rows
    assert np.asarray(float32_run.got[-2]).shape == (laguna.STAGE_ROWS, 64, 8, 16)                # layer 1's gated output
    assert np.asarray(float32_run.got[-1]).shape == (32, 8)                                       # and its gate's matrix


PARAMS = sorted(["lm.tok_emb", "lm.head.w", "lm.final_norm.w"]
                + [f"lm.l{i}.{n}" for i in range(9) for n in ("ln1.w", "ln2.w")]
                + [f"lm.l{i}.attn.{n}.w" for i in range(9) for n in ("q", "k", "v", "out", "gate")]
                + [f"lm.l0.ffn.{n}.w" for n in ("gate", "up", "down")]
                + [f"lm.l{i}.moe.{n}.w" for i in range(1, 9)
                   for n in ("router", "gate", "up", "down", "shared.gate", "shared.up", "shared.down")])


def test_the_toy_has_these_layers_parameters_and_no_other(float32_run):
    r = float32_run
    assert sorted(r.before) == PARAMS
    ops = r.main.global_block().ops
    kinds = [op.type for op in ops]
    assert kinds.count("fused_attention") == 9 and kinds.count("rotary_embedding") == 18            # q and k a layer
    assert kinds.count("moe_router") == kinds.count("moe_experts") == 8
    windows = [op.attr("mask_block", None) for op in ops if op.type == "fused_attention"]
    assert windows == [None, 8, 8, 8, None, 8, 8, 8, None]
    assert r.before["lm.l0.attn.q.w"].shape == (32, 96) and r.before["lm.l1.attn.q.w"].shape == (32, 128)
    assert r.before["lm.l0.attn.gate.w"].shape == (32, 6) and r.before["lm.l1.attn.gate.w"].shape == (32, 8)
    assert r.before["lm.l1.moe.router.w"].shape == (32, 16) and r.before["lm.l1.moe.shared.gate.w"].shape == (32, 16)
    assert r.counters["lowering.gated_attention_layers"] == 9 and r.counters["lowering.rotary_tables"] == 2
    assert [r.counters[f"lowering.query_heads_by_layer.{i}"] for i in range(9)] == [6, 8, 8, 8, 6, 8, 8, 8, 6]


@pytest.mark.parametrize("name", PARAMS)
def test_float32_gradient_agrees_with_the_reference(float32_run, name):
    """Adam's first moment after one step is 0.1 x the gradient.  The program
    differentiated here makes every layer again in backward, its routing with it."""
    agree(float32_run.moments[name] / (1 - 0.9), float32_run.ref_grads[name], tol=2e-4, floor=1e-7)


@pytest.mark.parametrize("name", PARAMS)
def test_a_recomputed_layers_gradient_is_the_plain_layers_to_the_last_bit(float32_run, name):
    plain_loss, plain_moments, _ = float32_run.plain
    assert plain_loss == float32_run.step_loss
    np.testing.assert_array_equal(float32_run.moments[name], plain_moments[name])


def test_the_counted_parameters_of_the_cells_program_are_490_3_million():
    """The program as the cell builds it, at the published widths (built, not
    lowered): 490.3 M parameters, what the configuration file states."""
    job = mf.read_json("benchmark/traffic/train-gated-swa-s16384.json")
    with unique_name.guard():
        main = laguna.build(CFG, job)[0]
    shapes = {p.name: tuple(p.shape) for p in main.all_parameters()}
    counted = sum(int(np.prod(s)) for s in shapes.values())
    assert counted == CFG["parameters"] == 490297344

    def of(prefix):
        return sum(int(np.prod(s)) for n, s in shapes.items() if n.startswith(prefix))

    assert of("lm.l0.attn.") == 29458432 and of("lm.l1.attn.") == 37879808          # 29.46 M full, 37.88 M under a window
    assert shapes["lm.l0.attn.q.w"] == (2048, 6144) and shapes["lm.l1.attn.q.w"] == (2048, 8192)
    assert shapes["lm.l0.attn.gate.w"] == (2048, 48) and shapes["lm.l1.attn.gate.w"] == (2048, 64)
    assert shapes["lm.l0.attn.k.w"] == shapes["lm.l1.attn.v.w"] == (2048, 1024)
    assert of("lm.l0.ffn.") == 50331648 and of("lm.l1.moe.") == 524288 + 3145728 + 16 * 3145728
    assert shapes["lm.l1.moe.router.w"] == (2048, 256) and shapes["lm.l4.moe.gate.w"] == (16, 2048, 512)
    assert shapes["lm.tok_emb"] == shapes["lm.head.w"][::-1] == (12544, 2048)
    ops = main.global_block().ops
    windows = [op.attr("mask_block", None) for op in ops if op.type == "fused_attention"]
    assert windows == [None, 512, 512, 512, None]
    assert laguna.flops_per_sample(CFG, job) > 0


def test_the_counted_operations_are_the_issues():
    """3.30 TFLOP of attention forward a full layer (48 heads over the
    triangle), 0.27 a window layer over the pairs the rule allows; the functions
    count the allowed pairs at each kind's own heads."""
    job = mf.read_json("benchmark/traffic/train-gated-swa-s16384.json")
    full, band = laguna._pairs(16384, None), laguna._pairs(16384, 512)
    assert full == 16384 * 16385 // 2 and band == masked_attention.window_pairs(16384, 512)
    assert band / full == pytest.approx(0.0615, abs=1e-3)
    assert laguna.causal_attention_flops(CFG, job) == 2 * 6 * 2.0 * 48 * 128 * full
    assert laguna.window_attention_flops(CFG, job) == 3 * 6 * 2.0 * 64 * 128 * band
    assert laguna.causal_attention_flops(CFG, job) / 6 == pytest.approx(3.30e12, rel=5e-3)        # forward, a layer
    assert laguna.window_attention_flops(CFG, job) / 9 == pytest.approx(0.27e12, rel=2e-2)
    assert laguna.causal_attention_bytes(CFG, job) == 2 * 2 * 2 * (2 * 48 + 16) * 128 * 16384
    assert laguna.window_attention_bytes(CFG, job) == 3 * 2 * 2 * (2 * 64 + 16) * 128 * 16384
    assert laguna.flops_per_sample(CFG, job) == pytest.approx(3 * 16.3e12, rel=3e-2)


@pytest.mark.parametrize("limit,sound,faulty", [
    ("REFERENCE_RTOL", 1.46e-2, 0.102),            # the factor 2.5 left out, the least fault to the stream
    ("LEFT_OUT_MAX", 0.0562, 0.286),               # half a head turned in layer 1 (the shared expert left out 0.511)
    ("ROUTER_RTOL", 1.0e-6, 1.08e-3),              # the router's matrix in bf16
    ("EXPERTS_RTOL", 4.75e-3, 3.05e-2),            # the running sums in bf16 (numpy)
    ("SHARED_RTOL", 4.26e-3, 3.05e-2),             # the held experts' limit and its faulty reading
    ("ATTENTION_RTOL", 3.63e-3, 2.33e-2),          # a window of 513 (the other grouping of the heads 1.46)
    ("WINDOW_EDGE_MAX", 3.1e-3, 1.0),              # a window of 511 | 513; the most under another control
    ("QK_RTOL", 1.32e-2, 0.293),                   # `attention_factor` left out, layer 0
    ("GATE_RTOL", 4.44e-6, 3.82e-3),               # the float64 gate rounded to bf16 (in the program 8.8e-3)
    ("GATED_RTOL", 3.31e-3, 0.617),                # the gate of head j on head j + 1
    ("REFERENCE_SELF_RTOL", 1.3e-6, 4.39e-3),      # the reference's attention at the chip's default precision
])
def test_every_limit_lies_between_the_readings_the_chip_gave(limit, sound, faulty):
    """My chip runs, PR 65 (PERF.md section 6): the most any of nine sound runs
    read, and the least a fault this limit has to refuse read, with room on both
    sides."""
    value = getattr(laguna, limit)
    assert 1.7 * sound < value < faulty / 1.7, (limit, sound, value, faulty)


def test_bfloat16_agrees_within_the_benchmarks_tolerances():
    cfg, job, main, _, names, scope, exe = tiny_model("bfloat16")
    rows = laguna.make_batch(np.random.RandomState(3), cfg, job, 8)
    got = exe.run(main.clone(for_test=True), feed=rows, fetch_list=list(names), scope=scope)
    found = laguna.compare(got, reference_of(cfg, params_of(main, scope), rows))
    assert found["routed_differently_above_margin"] == 0 and found["router_choice_differs"] == 0
    assert found["router_prob_error"] <= laguna.ROUTER_RTOL and found["experts_error"] <= laguna.EXPERTS_RTOL
    assert found["gate_error"] <= laguna.GATE_RTOL and found["gated_error"] <= laguna.GATED_RTOL
    assert found["attention_error"] <= laguna.ATTENTION_RTOL and found["reference_self_error"] <= laguna.REFERENCE_SELF_RTOL
    assert max(found["loss_error"], found["logit_error"]) <= laguna.REFERENCE_RTOL, found


def rope_over(kind, **over):
    stated = CFG["rope_parameters"]
    return dict(rope_parameters={**stated, kind: {**stated[kind], **over}})


FAULTS = {
    "the_attention_factor_left_out": (rope_over("full_attention", attention_factor=1.0), "QK_RTOL"),
    "the_whole_head_turned_in_layer_0": (rope_over("full_attention", partial_rotary_factor=1), "QK_RTOL"),
    "half_the_head_turned_in_layer_1": (rope_over("sliding_attention", partial_rotary_factor=0.5), "QK_RTOL"),
    "yarns_factor_8": (rope_over("full_attention", factor=8), "QK_RTOL"),
    "a_window_of_7": (dict(sliding_window=7), "WINDOW_EDGE_MAX"),
    "a_window_of_9": (dict(sliding_window=9), "WINDOW_EDGE_MAX"),
    "a_softmax_for_the_sigmoid_in_the_router": (dict(scoring_func="softmax"), "ROUTER_RTOL"),
    "the_factor_2_5_left_out": (dict(moe_routed_scaling_factor=1.0), "ROUTER_RTOL"),
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_the_comparison_refuses_a_program_with(fault, float32_run):
    """A program built with the fault on the sound program's parameters (the
    names are the same) against the sound reference."""
    over, limit = FAULTS[fault]
    _, _, main, _, names, _, exe = tiny_model("float32", over)
    got = exe.run(main.clone(for_test=True), feed=float32_run.rows, fetch_list=list(names), scope=float32_run.scope)
    found = laguna.compare(got, float32_run.want)
    refused = laguna.failed_limits(found)
    assert limit in refused, (refused, found)
    if limit == "WINDOW_EDGE_MAX":   # the stage's rule is the configuration's 8 keys: the program's own lacks one or has one more
        edge = found["window_edge_missing"] if over["sliding_window"] == 7 else found["window_edge_extra"]
        assert edge == pytest.approx(1.0, abs=1e-3), found


def test_the_reference_at_default_precision_in_its_attention_is_what_it_says():
    """Off the chip "default" is float32 too: the argument reaches the two
    products and changes nothing here; on the chip it is bf16 operands, which
    `REFERENCE_SELF_RTOL` refuses (tools/chip_laguna_controls.py)."""
    cfg, job, main, _, _, scope, _ = tiny_model("float32")
    rows = laguna.make_batch(np.random.RandomState(3), cfg, job, 2)
    a = reference_of(cfg, params_of(main, scope), rows)
    b = reference_of(cfg, params_of(main, scope), rows, attention_precision="default")
    agree(a[1], b[1], tol=1e-5)
