"""Kanana-2-30B-A3B's parts and the whole, tiny on the CPU (ISSUE 54).

(a) `rotary_embedding` over (B, L, H, dh) and over interleaved pairs: against
    float64 numpy at position 16383 and theta 1e6; the pairs equal the
    de-interleave-then-halves form of the family's public code, as rotations
    and as attention scores; the old layout's lowered text is what it was; the
    `infer=` rule, the planner row, `analysis.verify`;
(b) `latent_attention` with positions: the ops it appends, under which scopes,
    the one k_r rotated before it is spread, and the path without positions
    unchanged;
(c) the eight shares of 16 experts, the shared experts counted once, add up to
    the uncut layer;
(d) a sparse layer inside a `recompute_scope`: gradients bit-equal with and
    without it, the same `moe_routing` records, what `plan_kept` is offered and
    what it keeps where the chip is full;
(e) a tiny `build_causal_lm` (one dense and two sparse latent layers) in
    float32 against the benchmark's reference (benchmark/models/kanana.py) on
    seeded weights: loss, logits, routing, every stage, every parameter's
    gradient; in bf16 within the benchmark's tolerances; and the faults the
    comparison has to refuse.

One compiled tiny model serves (d) and (e): `float32_run`.
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
from benchmark.models import kanana  # noqa: E402
from paddle_tpu import layers, monitor  # noqa: E402
from paddle_tpu.core import lowering  # noqa: E402
from paddle_tpu.core.lowering import LoweringContext  # noqa: E402
from paddle_tpu.core.registry import get_op_def  # noqa: E402
from paddle_tpu.models import transformer  # noqa: E402


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


# -- (a) the rotation ---------------------------------------------------------------------

def rotate_float64(x, positions, theta, interleave):
    """x (B, L, H, dh) rotated in float64 numpy: feature 2i with 2i + 1, or i with i + dh/2."""
    x = np.asarray(x, "f8")
    half = x.shape[-1] // 2
    angle = np.asarray(positions, "f8")[:, :, None, None] * theta ** (-np.arange(half, dtype="f8") / half)
    cos, sin = np.cos(angle), np.sin(angle)
    if interleave:
        a, b = x[..., 0::2], x[..., 1::2]
        return np.stack([a * cos - b * sin, b * cos + a * sin], -1).reshape(x.shape)
    a, b = x[..., :half], x[..., half:]
    return np.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


@pytest.mark.parametrize("interleave", [False, True], ids=["halves", "pairs"])
@pytest.mark.parametrize("heads", [1, 4], ids=["one-shared-head", "four-heads"])
def test_the_rotation_over_positions_major_operands_is_float64s_to_float32(interleave, heads):
    """(B, L, H, dh) as the latent path hands it, positions up to 16383 at theta
    1e6: the float32 angle of the fastest pair is ~1.6e4 radians, whose own
    rounding (2^-24 of it, 1e-3 radians) is all that separates the op from
    float64; a bf16 angle there is off by whole turns."""
    r = np.random.RandomState(heads)
    positions = np.stack([np.array([0, 1, 255, 4096, 16382, 16383]), np.array([16383, 8191, 3, 2, 1, 0])])
    x = r.randn(2, 6, heads, 64).astype("f4")
    attrs = {"theta": 1e6, "layout": "blhd", **({"interleave": True} if interleave else {})}
    got = lower("rotary_embedding", {"X": x, "Positions": positions}, attrs)["Out"]
    want = rotate_float64(x, positions, 1e6, interleave)
    assert got.dtype == jnp.float32 and got.shape == x.shape
    agree(got, want, tol=2e-3)
    # position 0 turns nothing; a rotation keeps every pair's length
    np.testing.assert_array_equal(np.asarray(got)[0, 0], x[0, 0])
    agree(np.square(np.asarray(got, "f8")).sum(-1), np.square(x.astype("f8")).sum(-1), tol=1e-5)
    # the angle in bf16 is another rotation altogether at these positions
    half = 32
    low = np.asarray(jnp.asarray(positions[:, :, None, None] * 1e6 ** (-np.arange(half) / half), jnp.bfloat16), "f8")
    assert np.abs(np.cos(low) - np.cos(positions[:, :, None, None] * 1e6 ** (-np.arange(half) / half))).max() > 0.5
    # bf16 operands come back bf16, rotated in float32 and rounded once
    low_x = jnp.asarray(x, jnp.bfloat16)
    out = lower("rotary_embedding", {"X": low_x, "Positions": positions}, attrs)["Out"]
    assert out.dtype == jnp.bfloat16
    agree(out, rotate_float64(np.asarray(low_x, "f4"), positions, 1e6, interleave), tol=6e-3)


def test_heads_major_and_positions_major_are_one_rotation():
    r = np.random.RandomState(0)
    x = r.randn(2, 3, 10, 16).astype("f4")                       # (B, H, L, dh)
    positions = np.tile(np.arange(10) * 7, (2, 1))
    for interleave in (False, True):
        extra = {"interleave": True} if interleave else {}
        a = lower("rotary_embedding", {"X": x, "Positions": positions}, {"theta": 1e4, **extra})["Out"]
        b = lower("rotary_embedding", {"X": x.transpose(0, 2, 1, 3), "Positions": positions},
                  {"theta": 1e4, "layout": "blhd", **extra})["Out"]
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b).transpose(0, 2, 1, 3))


def deinterleave(t):
    """The family's public code: t[..., (0, 2, 4, ..., 1, 3, 5, ...)], then rotate halves."""
    return np.concatenate([t[..., 0::2], t[..., 1::2]], -1)


def test_interleaved_pairs_are_the_deinterleaved_halves_as_rotations_and_as_scores():
    """Rotating pairs (2i, 2i + 1) and then de-interleaving is rotating the
    de-interleaved halves: the same numbers in another order, for q_r and k_r
    alike, so q_r . k_r is the same score either way.  De-interleaving ONE of
    the two is another score."""
    r = np.random.RandomState(1)
    q, k = r.randn(1, 12, 4, 16).astype("f4"), r.randn(1, 12, 1, 16).astype("f4")
    positions = np.arange(12)[None] * 1000
    pairs = {n: np.asarray(lower("rotary_embedding", {"X": t, "Positions": positions},
                                 {"theta": 1e6, "layout": "blhd", "interleave": True})["Out"]) for n, t in (("q", q), ("k", k))}
    halves = {n: np.asarray(lower("rotary_embedding", {"X": deinterleave(t), "Positions": positions},
                                  {"theta": 1e6, "layout": "blhd"})["Out"]) for n, t in (("q", q), ("k", k))}
    for n in "qk":
        agree(deinterleave(pairs[n]), halves[n], tol=1e-6)

    def scores(a, b):
        return np.einsum("bqhd,bkd->bhqk", a.astype("f8"), b[:, :, 0].astype("f8"))

    agree(scores(pairs["q"], pairs["k"]), scores(halves["q"], halves["k"]), tol=1e-6)
    assert np.abs(scores(pairs["q"], halves["k"]) - scores(pairs["q"], pairs["k"])).max() > 0.1
    # the benchmark's reference writes the pairs too, on its own
    mine = kanana.rotate_pairs(jnp.asarray(q[0]), jnp.arange(12) * 1000, 1e6)
    agree(mine, pairs["q"][0], tol=1e-5)


def rotary_program(**kw):
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = layers.data("x", [4, 8, 16], dtype="float32")
        positions = layers.data("positions", [4 if kw.get("layout") == "blhd" else 8], dtype="int64")
        out = layers.rotary_embedding(x, positions, theta=1e6, **kw)
    return main, out


def test_a_default_is_no_attribute_and_the_new_ones_are_attributes_of_the_one_op():
    main, _ = rotary_program()
    assert main.global_block().ops[0].attrs["theta"] == 1e6
    assert not {"layout", "interleave"} & set(main.global_block().ops[0].attrs)
    main, out = rotary_program(layout="blhd", interleave=True)
    op = main.global_block().ops[0]
    assert (op.type, op.attrs["layout"], op.attrs["interleave"]) == ("rotary_embedding", "blhd", True)
    assert tuple(out.shape) == (-1, 4, 8, 16)
    with pytest.raises(ValueError, match="layout="):
        rotary_program(layout="lbhd")
    # the heads-major lowering is the expression it was: the same jaxpr text as the parent's form written out here
    def parent(x, pos):
        half = x.shape[-1] // 2
        inv_freq = 1e4 ** (-np.arange(half, dtype=np.float32) / half)
        angle = pos.astype(jnp.float32)[:, None, :, None] * inv_freq
        cos, sin = jnp.cos(angle), jnp.sin(angle)
        x1, x2 = x[..., :half].astype(jnp.float32), x[..., half:].astype(jnp.float32)
        return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1).astype(x.dtype)

    x, pos = jnp.ones((2, 3, 5, 8), jnp.bfloat16), jnp.ones((2, 5), jnp.int32)
    ctx, op = LoweringContext(jax.random.PRNGKey(0)), SimpleNamespace(type="rotary_embedding", attr=lambda n, d=None: {"theta": 1e4}.get(n, d))
    mine = jax.make_jaxpr(lambda x, pos: get_op_def("rotary_embedding").lower(ctx, op, {"X": [x], "Positions": [pos]})["Out"])(x, pos)
    assert hashlib.sha256(str(mine).encode()).hexdigest() == hashlib.sha256(str(jax.make_jaxpr(parent)(x, pos)).encode()).hexdigest()


def test_the_rotation_has_an_infer_rule_a_planner_row_and_passes_verify():
    from paddle_tpu.core import analysis, resource_plan

    main, out = rotary_program(layout="blhd", interleave=True)
    assert [d for d in analysis.verify_program(main, level="full") if d.severity == "error"] == []
    cost = resource_plan.op_cost(main.global_block().ops[0], main.global_block(),
                                 resource_plan.ShapeEnv(main, {"x": (2, 4, 8, 16), "positions": (2, 4)}))
    assert cost[0] == 6.0 * 2 * 4 * 8 * 16
    with fluid.program_guard(fluid.Program(), fluid.Program()):
        x = layers.data("x", [4, 8, 16], dtype="float32")
        wrong = layers.data("positions", [8], dtype="int64")        # the heads' axis taken for the positions'
        with pytest.raises(Exception, match=r"Positions must be \(B, L\) with L = 4"):
            layers.rotary_embedding(x, wrong, layout="blhd")


# -- (b) the layer -----------------------------------------------------------------------

def latent_program(**kw):
    from paddle_tpu.core import unique_name

    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), unique_name.guard():
        x = layers.data("x", [12, 32], dtype="float32")
        positions = layers.data("pos", [12], dtype="int64")
        out = transformer.latent_attention(x, 32, 4, "l", rank=16, nope_dim=8, rope_dim=4, v_dim=8,
                                           positions=positions if kw.pop("rope", False) else None, **kw)
    return main, startup, out


def test_latent_attention_rotates_q_r_and_the_one_k_r_before_it_is_spread():
    main, _, _ = latent_program(rope=True, rope_theta=1e6, rope_interleave=True)
    ops = main.global_block().ops
    # sibling scopes of one name are numbered over the process: latent_attention, latent_attention_1, ...
    scoped = [(op.type, re.sub(r"^latent_attention_\d+", "latent_attention", op.attrs.get("op_namescope"))) for op in ops]
    rotary = [op for op, scope in scoped if scope == "latent_attention/rotary"]
    assert rotary == ["slice", "slice", "rotary_embedding", "concat", "rotary_embedding", "expand"]
    q_rot, k_rot = [op for op in ops if op.type == "rotary_embedding"]
    block = main.global_block()
    assert tuple(block.var(q_rot.inputs["X"][0]).shape) == (-1, 12, 4, 4)
    assert tuple(block.var(k_rot.inputs["X"][0]).shape) == (-1, 12, 1, 4)          # ONE head a token
    assert all(op.attrs["layout"] == "blhd" and op.attrs["interleave"] and op.attrs["theta"] == 1e6 for op in (q_rot, k_rot))
    expand = next(op for op in ops if op.type == "expand")
    assert expand.inputs["X"] == k_rot.outputs["Out"]                                # spread AFTER its rotation
    # without positions: the layer it was, op for op, and nothing under a rotary scope
    plain, _, _ = latent_program()
    assert [op.type for op in plain.global_block().ops] == [t for t, scope in scoped if scope != "latent_attention/rotary"
                                                             or t == "expand"]
    assert all(re.fullmatch(r"latent_attention(_\d+)?", op.attrs.get("op_namescope")) for op in plain.global_block().ops)


def test_the_layer_is_the_equations_with_the_rotation_in():
    """One layer against the equations in float64 numpy: pairs rotated, the
    shared key part one head, scale 192^-0.5 of the widths here."""
    main, startup, out = latent_program(rope=True, rope_theta=1e6, rope_interleave=True)
    scope, exe = fluid.Scope(), fluid.Executor(fluid.TPUPlace(0))
    main.random_seed = startup.random_seed = 11
    exe.run(startup, scope=scope)
    for p in main.all_parameters():        # N(0, 0.02) keeps every score near 0: draw them larger
        if p.name != "l.kv_norm.w":
            scope.set_var(p.name, jnp.asarray(np.random.RandomState(len(p.name)).randn(*p.shape).astype("f4") * 0.3))
    x = np.random.RandomState(2).randn(2, 12, 32).astype("f4")
    positions = np.stack([np.arange(12), np.arange(12) * 1489 + 5])
    got, = exe.run(main, feed={"x": x, "pos": positions}, fetch_list=[out], scope=scope)
    w = {p.name: np.asarray(scope.find_var(p.name), "f8") for p in main.all_parameters()}
    want = np.zeros((2, 12, 32))
    for b in range(2):
        a = x[b].astype("f8")
        q = (a @ w["l.q.w"]).reshape(12, 4, 12)
        down = a @ w["l.kv_a.w"]
        c = down[:, :16]
        c = c / np.sqrt(np.mean(c * c, -1, keepdims=True) + 1e-5) * w["l.kv_norm.w"]
        up = (c @ w["l.kv_b.w"]).reshape(12, 4, 16)
        q_r = rotate_float64(q[None, ..., 8:], positions[b:b + 1], 1e6, True)[0]
        k_r = rotate_float64(down[None, :, None, 16:], positions[b:b + 1], 1e6, True)[0]
        scores = (np.einsum("qhd,khd->hqk", q[..., :8], up[..., :8]) + np.einsum("qhd,kd->hqk", q_r, k_r[:, 0])) / np.sqrt(12)
        scores = np.where(np.arange(12)[None, :] <= np.arange(12)[:, None], scores, -np.inf)
        p = np.exp(scores - scores.max(-1, keepdims=True))
        ctx = np.einsum("hqk,khd->qhd", p / p.sum(-1, keepdims=True), up[..., 8:])
        want[b] = ctx.reshape(12, 32) @ w["l.out.w"]
    agree(got, want, tol=2e-5)


def test_build_causal_lm_names_the_keyword_it_refuses():
    with pytest.raises(ValueError, match="latent="):
        transformer.build_causal_lm(vocab_size=8, seq_len=4, d_model=8, n_heads=2, layer_types=["latent_attention"])
    with pytest.raises(ValueError, match="rotary=False"):
        transformer.build_causal_lm(vocab_size=8, seq_len=4, d_model=8, n_heads=2, layer_types=["latent_attention"],
                                    latent=dict(rank=4, nope_dim=4, rope_dim=2, v_dim=4, rope=True), rotary=False,
                                    num_dense_layers=1, dense_width=8)
    with pytest.raises(ValueError, match="layer_types"):
        transformer.build_causal_lm(vocab_size=8, seq_len=4, d_model=8, n_heads=2, layer_types=["conv", "scan"])


# -- (c) the shares -----------------------------------------------------------------------

def sigmoid(x):
    return 1.0 / (1.0 + np.exp(-x))


def test_the_eight_shares_of_a_layer_and_the_shared_experts_once_add_up_to_the_layer():
    """Eight chips hold 16 of 128 experts each behind THIS router (sigmoid
    scores, the choice by score + bias, the six unbiased scores renormalised
    over all six with the 1e-20, times 2.448) and each computes the two shared
    experts alike.  The eight routed parts, summed, and the shared experts'
    output ONCE are the uncut layer's output as the plain equations write it."""
    rng = np.random.RandomState(54)
    tokens, experts, k, d, f, scaling = 64, 128, 6, 16, 8, 2.448
    x = rng.randn(tokens, d).astype("f4")
    router = rng.randn(d, experts).astype("f4") / 2
    bias = (rng.randn(experts) * 0.1).astype("f4")
    gate, up = (rng.randn(experts, d, f).astype("f4") / 4 for _ in range(2))
    down = rng.randn(experts, f, d).astype("f4") / 4
    s_gate, s_up, s_down = rng.randn(d, 2 * f).astype("f4") / 4, rng.randn(d, 2 * f).astype("f4") / 4, rng.randn(2 * f, d).astype("f4") / 4
    routed = lower("moe_router", {"X": x, "W": router, "Bias": bias},
                   {"top_k": k, "norm_topk_prob": True, "scoring": "sigmoid", "norm_eps": 1e-20,
                    "routed_scaling_factor": scaling})

    def share(first, count):
        ins = {"X": x, "TopKProb": routed["TopKProb"], "TopKIndex": routed["TopKIndex"], "Load": routed["Load"],
               "WGate": gate[first:first + count], "WUp": up[first:first + count], "WDown": down[first:first + count]}
        return lower("moe_experts", ins, {"held": [first, count], "shared_experts": 2})

    shares = [share(first, 16) for first in range(0, experts, 16)]
    assert len(shares) == 8 and sum(int(np.asarray(s["Held"])[0]) for s in shares) == tokens * k
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
    assert np.abs(7 * shared).max() > 1e-2 * np.abs(want).max()           # eight times the shared experts: another layer


# -- (d), (e) the whole model ---------------------------------------------------------------

TINY = dict(hidden_size=48, num_attention_heads=2, intermediate_size=96, moe_intermediate_size=16,
            n_routed_experts=4, num_routed_experts=32, experts_held_first=4, num_experts_per_tok=4, vocab_size=96,
            kv_lora_rank=24, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16, expert_bias_std=0.05,
            num_hidden_layers=3, layer_types=["latent_attention"] * 3)
JOB = dict(seq_len=128, batch_per_chip=4)


@pytest.fixture(scope="module", autouse=True)
def every_position_is_sampled():
    from benchmark.models import lfm2

    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(lfm2, "LOGIT_SAMPLE", 128)
        patch.setattr(lfm2, "ATTENTION_SAMPLE", 128)
        yield


def tiny_model(dtype, **job):
    from paddle_tpu.core import unique_name

    cfg = dict(mf.read_json("benchmark/configs/kanana-2-30b-a3b.json"), compute_dtype=dtype, **TINY)
    job = dict(mf.read_json("benchmark/traffic/train-mla-s16384.json"), **JOB, **job)
    with unique_name.guard():
        main, startup, feeds, loss, names = kanana.build(cfg, job)
    main.random_seed = startup.random_seed = 3
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.TPUPlace(0))
    exe.run(startup, scope=scope)
    return cfg, job, main, loss, names, scope, exe


def params_of(main, scope):
    return {p.name: np.asarray(scope.find_var(p.name)) for p in main.all_parameters()}


def reference_of(cfg, params, rows):
    return [np.asarray(w) for w in jax.jit(lambda p, b: kanana.reference(p, b, cfg))(params, rows)]


def one_step(main, loss, scope, exe, batch):
    """(the step's loss, Adam's first moments, the logged step's `moe_routing`
    record, the trace's `lowering.` counters) of one step through `train_loop`."""
    losses = []
    monitor.reset()
    monitor.enable()
    try:
        fluid.train_loop(exe, main, iter([batch]), [loss], scope=scope, log_period=1,
                         on_logged=lambda i, vals: losses.append(float(np.asarray(vals[0]).reshape(-1)[0])))
        records = [r for r in monitor.get_monitor().step_records() if r.get("kind") == "moe_routing"]
        counters = {k: v for k, v in monitor.get_monitor().counter_values().items() if k.startswith("lowering.")}
    finally:
        monitor.disable()
        monitor.reset()
    moments = {p.name: np.asarray(scope.find_var(p.name + "_moment1_0")) for p in main.all_parameters()}
    return losses.pop(), moments, records, counters


@pytest.fixture(scope="module")
def float32_run():
    """The tiny model built twice from the same seed, every layer a
    `recompute_scope` (as the cell builds it) and none, one step each on the
    same batch; the recomputed one's `for_test` clone against the reference."""
    with jax.default_matmul_precision("highest"):
        cfg, job, main, loss, names, scope, exe = tiny_model("float32")
        rows = kanana.make_batch(np.random.RandomState(3), cfg, job, 8)
        got = exe.run(main.clone(for_test=True), feed=rows, fetch_list=list(names), scope=scope)
        before = params_of(main, scope)
        want = reference_of(cfg, before, rows)
        batch = kanana.make_batch(np.random.RandomState(4), cfg, job, 4)
        ref_loss, ref_grads = jax.jit(jax.value_and_grad(lambda p: kanana.reference(p, batch, cfg)[0]))(before)
        step_loss, moments, records, counters = one_step(main, loss, scope, exe, batch)
        after = params_of(main, scope)
        _, _, plain_main, plain_loss, _, plain_scope, plain_exe = tiny_model("float32", recompute_layers=False)
        plain = one_step(plain_main, plain_loss, plain_scope, plain_exe, batch)
        ops = [op.type for op in main.global_block().ops]
    return SimpleNamespace(cfg=cfg, job=job, main=main, got=got, want=want, ops=ops, before=before, after=after,
                           moments=moments, records=records, counters=counters, plain=plain,
                           plain_segments=[op.attrs.get("recompute_segment") for op in plain_main.global_block().ops],
                           ref_loss=float(ref_loss), step_loss=step_loss,
                           ref_grads={k: np.asarray(v) for k, v in ref_grads.items()})


def test_float32_loss_logits_routing_and_every_stage_agree_with_the_reference(float32_run):
    found = kanana.compare(float32_run.got, float32_run.want)
    assert found["left_out"] == found["routed_differently"] == found["routed_differently_above_margin"] == 0
    assert found["loss_error"] < 1e-5 and found["logit_error"] < 2e-5, found
    assert max(found["router_prob_error"], found["experts_error"], found["shared_error"], found["rotary_error"],
               found["attention_error"], found["qk_error"]) < 2e-5, found
    assert found["biases_differ"] == 0 and found["bias_moved"] > 0
    assert found["rotary_error_bf16_angles"] > 1e-3                        # what the rotary stage has to refuse
    assert found["reference_self_error"] < 1e-5 and kanana.failed_limits(found) == []
    assert kanana.reference_error(float32_run.got, float32_run.want) < 2e-5
    assert abs(float32_run.step_loss - float32_run.ref_loss) < 1e-5 * float32_run.ref_loss
    assert np.asarray(float32_run.got[1]).shape == (128, 8, 96)
    assert np.asarray(float32_run.got[2]).shape == (8, 128, 4)                                  # the choice: every row
    assert np.asarray(float32_run.got[3]).shape == (kanana.STAGE_ROWS, 128, 48)                 # the rest: the stage rows
    assert np.asarray(float32_run.got[-1]).shape == (kanana.STAGE_ROWS, 128, 2, 16)             # the sampled queries' outputs
    assert np.asarray(float32_run.got[-3]).shape == (kanana.STAGE_ROWS, 128, 2, 24)             # every key
    assert np.asarray(float32_run.got[-9]).shape == (kanana.STAGE_ROWS, 128, 1, 8)              # the one rotated k_r


PARAMS = sorted(
    ["lm.tok_emb", "lm.head.w", "lm.final_norm.w", "lm.l0.ffn.gate.w", "lm.l0.ffn.up.w", "lm.l0.ffn.down.w"]
    + [f"lm.l{i}.{n}" for i in range(3) for n in ("ln1.w", "ln2.w")]
    + [f"lm.l{i}.attn.{n}.w" for i in range(3) for n in ("q", "kv_a", "kv_norm", "kv_b", "out")]
    + [f"lm.l{i}.moe.{n}.w" for i in (1, 2)
       for n in ("router", "gate", "up", "down", "shared.gate", "shared.up", "shared.down")])


def test_the_tiny_model_has_these_layers_parameters_and_no_other(float32_run):
    r = float32_run
    assert sorted(r.before) == PARAMS
    assert r.ops.count("fused_attention") == 3 and r.ops.count("rotary_embedding") == 6 and r.ops.count("transpose2") == 0
    assert r.ops.count("moe_router") == r.ops.count("moe_experts") == 2
    shapes = {n: r.before[n].shape for n in ("lm.l0.attn.q.w", "lm.l0.attn.kv_a.w", "lm.l0.attn.kv_norm.w",
                                            "lm.l0.attn.kv_b.w", "lm.l0.attn.out.w", "lm.l1.moe.router.w",
                                            "lm.l1.moe.gate.w", "lm.l1.moe.shared.gate.w", "lm.l1.moe.shared.down.w")}
    assert shapes == {"lm.l0.attn.q.w": (48, 48), "lm.l0.attn.kv_a.w": (48, 32), "lm.l0.attn.kv_norm.w": (24,),
                      "lm.l0.attn.kv_b.w": (24, 64), "lm.l0.attn.out.w": (32, 48), "lm.l1.moe.router.w": (48, 32),
                      "lm.l1.moe.gate.w": (4, 48, 16), "lm.l1.moe.shared.gate.w": (48, 32),       # two shared experts: 2 x 16
                      "lm.l1.moe.shared.down.w": (32, 48)}
    segments = [op.attrs.get("recompute_segment") for op in r.main.global_block().ops]
    assert sorted(set(segments) - {None}) == [1, 2, 3] and set(r.plain_segments) == {None}


@pytest.mark.parametrize("name", PARAMS)
def test_float32_gradient_and_adam_step_agree_with_the_reference(float32_run, name):
    """Adam's first moment after one step is 0.1 x the gradient; the parameter
    moves by the warm-up's first rate.  The program differentiated here makes
    every layer again in backward."""
    r = float32_run
    agree(r.moments[name] / (1 - 0.9), r.ref_grads[name], tol=2e-4)
    moved = np.abs(r.after[name] - r.before[name]).max()
    assert 0.5e-6 < moved < 4e-6, moved


@pytest.mark.parametrize("name", PARAMS)
def test_a_recomputed_layers_gradient_is_the_plain_layers_to_the_last_bit(float32_run, name):
    """The same arithmetic either way: with every layer a `recompute_scope`
    (the sparse ones too: router, the held path's conditional, the `token_sum`
    way back) and with none, Adam's first moments are equal bit for bit."""
    np.testing.assert_array_equal(float32_run.moments[name], float32_run.plain[1][name])


def test_a_recomputed_sparse_segment_publishes_what_the_plain_layer_publishes(float32_run):
    r = float32_run
    plain_loss, _, plain_records, plain_counters = r.plain
    assert r.step_loss == plain_loss
    assert len(r.records) == len(plain_records) == 1

    def said(record):
        return {k: v for k, v in record.items() if k not in ("ts", "step", "lane")}

    assert said(r.records[0]) == said(plain_records[0])
    record = r.records[0]
    assert record["dropped_tokens"] == 0 and len(record["held_rows_share"]) == len(record["bias_moved_share"]) == 2
    assert r.counters["lowering.recomputed_segments"] == 3 and r.counters["lowering.recomputed_sparse_segments"] == 2
    assert r.counters["lowering.latent_rotary_ops"] == 6                      # two a layer, counted once a trace
    assert not plain_counters.get("lowering.recomputed_segments") and plain_counters["lowering.latent_rotary_ops"] == 6
    # the CPU reports no memory limit, so the chip model's stands in and everything offered is kept
    assert r.counters["lowering.recomputed_kept_bytes"] == r.counters["lowering.recomputed_candidates_bytes"] > 0


def test_plan_kept_is_offered_the_experts_products_and_the_routers_logits(float32_run):
    """What a sparse segment may keep: the gate and the up product's outputs
    over the held path's bound of rows (under one name, the op's residuals),
    priced by the op's cost rule, and the router's float32 logits; a chip that
    is full keeps none of them and the program is the plain `jax.checkpoint`'s."""
    from paddle_tpu.core import resource_plan
    from paddle_tpu.ops import moe_ops

    block = float32_run.main.global_block()
    ops = [op for op in block.ops if op.type not in ("feed", "fetch")]
    shapes = resource_plan.ShapeEnv(float32_run.main, {n: (4, 128) for n in kanana.FEEDS})
    ctx = LoweringContext(jax.random.PRNGKey(0))
    offered = lowering.kept_candidates(ctx, ops, shapes)
    by_type = {}
    for value in offered:
        maker = next(op for op in ops if value.name.split("@")[0] in op.output_arg_names)
        by_type.setdefault(maker.type, []).append(value)
    assert {"moe_experts", "moe_router", "mul"} <= set(by_type)     # (the CPU's attention is XLA's: it names nothing)
    experts, routers = by_type["moe_experts"], by_type["moe_router"]
    assert len(experts) == len(routers) == 2 and {v.segment for v in experts} == {2, 3}
    rows = moe_ops._held_rows_bound(4 * 128 * 4, 4, 32)                           # the bound: twice the uniform share
    assert all(v.nbytes == 2 * rows * 16 * 4 and v.name.endswith("@residuals") for v in experts)
    assert all(v.flops == pytest.approx(resource_plan.op_cost(
        next(op for op in ops if op.type == "moe_experts"), block, shapes)[0]) for v in experts)
    assert all(v.nbytes == 4 * 4 * 128 * 32 and v.name.endswith("@logits") for v in routers)
    # a budget that holds nothing keeps nothing; one that holds everything, everything, in program order
    assert lowering.choose_kept(offered, 0) == []
    assert lowering.choose_kept(offered, sum(v.nbytes for v in offered)) == offered


def primitives_of(jaxpr, counted=None):
    """{primitive: how often it stands in `jaxpr`, its sub-computations included}."""
    counted = {} if counted is None else counted
    for eqn in jaxpr.eqns:
        counted[eqn.primitive.name] = counted.get(eqn.primitive.name, 0) + 1
        for value in eqn.params.values():
            for sub in (value if isinstance(value, (list, tuple)) else [value]):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    primitives_of(inner, counted)
    return counted


@pytest.mark.parametrize("share,spared", [(0.5, True), (0.0, False)], ids=["room-for-all", "a-full-chip"])
def test_what_a_sparse_segment_keeps_it_does_not_make_again(share, spared, monkeypatch):
    """The traced step holds a grouped product (a `pallas_call`, interpreted
    here) for each of the held path's gate and up products forward, for their
    transposes, and once more where backward makes them again: with room for
    every candidate the second forward's four calls (two sparse layers) and the
    routers' two logits products are not in the trace; with no room they are."""
    from paddle_tpu.core import executor as ex

    monkeypatch.setattr(lowering, "KEPT_SHARE", share)
    cfg, job, main, loss, names, scope, exe = tiny_model("float32")
    feeds = {n: jax.ShapeDtypeStruct((4, 128), np.int32) for n in kanana.FEEDS}
    step = ex._CompiledStep(main, list(feeds), [loss.name], scope, feed_shapes={n: s.shape for n, s in feeds.items()})

    def as_shape(v):
        return jax.ShapeDtypeStruct(v.shape, v.dtype)

    traced = step.jfn.trace({n: as_shape(scope.find_var(n)) for n in step.rw_names},
                            {n: as_shape(scope.find_var(n)) for n in step.ro_names}, feeds, as_shape(jax.random.PRNGKey(0)))
    counted = primitives_of(traced.jaxpr.jaxpr)
    assert counted["pallas_call"] == (46 if spared else 50), counted["pallas_call"]


SOUND = dict(routed_differently_above_margin=0, left_out=30, tokens=1000, logit_error_left_out=0.2, router_choice_differs=0,
             biases_differ=0, router_prob_error=1e-6, experts_error=5e-3, shared_error=4e-3, rotary_error=4e-3,
             attention_error=4e-3, qk_error=1.4e-2, loss_error=1e-5, logit_error=1.5e-2, reference_self_error=1e-6)


@pytest.mark.parametrize("reading,limit", [
    (dict(routed_differently_above_margin=1), "ROUTING_MARGIN"), (dict(left_out=340), "LEFT_OUT_MAX"),
    (dict(logit_error_left_out=0.54), "LEFT_OUT_LOGIT_MAX"), (dict(router_choice_differs=532), "ROUTER_TIE"),
    (dict(biases_differ=1), "router_bias"), (dict(router_prob_error=1.3e-3), "ROUTER_RTOL"),
    (dict(experts_error=3.1e-2), "EXPERTS_RTOL"), (dict(shared_error=1.0), "SHARED_RTOL"),
    (dict(rotary_error=1.4), "ROTARY_RTOL"), (dict(attention_error=0.059), "ATTENTION_RTOL"),
    (dict(qk_error=0.18), "QK_RTOL"), (dict(logit_error=0.178), "REFERENCE_RTOL"), (dict(loss_error=float("nan")), "REFERENCE_RTOL"),
    (dict(reference_self_error=2e-3), "REFERENCE_SELF_RTOL")])
def test_every_limit_refuses_the_least_faulty_reading_the_chip_gave(reading, limit):
    """`failed_limits` on the chip's sound readings (my chip runs, PR 54) names
    nothing, and with the least reading a fault gave there (tools/
    chip_kanana_controls.py) the limit that reading belongs to."""
    assert kanana.failed_limits(SOUND) == []
    assert kanana.failed_limits({**SOUND, **reading}) == [limit]


def test_bfloat16_agrees_within_the_benchmarks_tolerances():
    cfg, job, main, loss, names, scope, exe = tiny_model("bfloat16")
    rows = kanana.make_batch(np.random.RandomState(3), cfg, job, 8)
    got = exe.run(main.clone(for_test=True), feed=rows, fetch_list=list(names), scope=scope)
    want = reference_of(cfg, params_of(main, scope), rows)
    found = kanana.compare(got, want)
    assert found["tokens"] == 8 * 128 and found["routed_differently_above_margin"] == 0
    assert found["left_out"] <= found["routed_differently"] <= 0.35 * found["tokens"]   # 32 outputs of 48 features: near ties
    assert 1e-4 < found["logit_error"] < kanana.REFERENCE_RTOL and found["loss_error"] < 1e-3
    assert found["router_prob_error"] < kanana.ROUTER_RTOL and found["experts_error"] < kanana.EXPERTS_RTOL
    assert found["shared_error"] < kanana.SHARED_RTOL
    assert found["rotary_error"] < kanana.ROTARY_RTOL and found["attention_error"] < kanana.ATTENTION_RTOL
    assert found["qk_error"] < kanana.QK_RTOL and found["reference_self_error"] < kanana.REFERENCE_SELF_RTOL
    assert kanana.reference_error(got, want) in (max(found["loss_error"], found["logit_error"]), float("inf"))


def faulty_rotation(fault):
    """The registered lowering of `rotary_embedding` with `fault` put in."""
    real = get_op_def("rotary_embedding").lower

    def wrong(ctx, op, ins):
        one_head = ins["X"][0].shape[2] == 1
        if fault == "no_rotation_of_k_r" and one_head:
            return {"Out": ins["X"][0]}
        if fault == "q_r_deinterleaved_k_r_not" and not one_head:
            x = ins["X"][0]
            return real(ctx, op, {**ins, "X": [jnp.concatenate([x[..., 0::2], x[..., 1::2]], -1)]})
        if fault == "halves_for_pairs":
            attrs = {"interleave": False}
            return real(ctx, SimpleNamespace(type=op.type, attr=lambda n, d=None: attrs.get(n, op.attr(n, d))), ins)
        return real(ctx, op, ins)

    return wrong


@pytest.mark.parametrize("fault", ["no_rotation_of_k_r", "q_r_deinterleaved_k_r_not", "halves_for_pairs",
                                   "attention_not_causal", "scale_of_the_nope_width", "shared_experts_twice"])
def test_the_reference_check_fails_on(fault, monkeypatch):
    """A program that computes something else under the same names is not
    correct: a k_r that is not rotated, a q_r de-interleaved where k_r is not,
    halves rotated where the source pairs, attention that sees the keys after a
    query or scales by 128^-0.5, shared experts added twice."""
    stage, limit = {"no_rotation_of_k_r": ("rotary_error", kanana.ROTARY_RTOL),
                    "q_r_deinterleaved_k_r_not": ("rotary_error", kanana.ROTARY_RTOL),
                    "halves_for_pairs": ("rotary_error", kanana.ROTARY_RTOL),
                    "attention_not_causal": ("attention_error", kanana.ATTENTION_RTOL),
                    "scale_of_the_nope_width": ("attention_error", kanana.ATTENTION_RTOL),
                    "shared_experts_twice": ("logit_error", kanana.REFERENCE_RTOL)}[fault]
    if stage == "rotary_error":
        monkeypatch.setattr(get_op_def("rotary_embedding"), "lower", faulty_rotation(fault))
    elif stage == "attention_error":
        real = get_op_def("fused_attention").lower
        attrs = {"causal": False} if fault == "attention_not_causal" else {"scale": 16 ** -0.5}

        def wrong(ctx, op, ins):
            return real(ctx, SimpleNamespace(type=op.type, attr=lambda n, d=None: attrs.get(n, op.attr(n, d)),
                                             input=op.input, output=op.output), ins)

        monkeypatch.setattr(get_op_def("fused_attention"), "lower", wrong)
    else:
        real = get_op_def("elementwise_add").lower

        def twice(ctx, op, ins):
            outs = dict(real(ctx, op, ins))
            if "moe" in op.inputs["X"][0]:
                outs["Out"] = outs["Out"] + ins["Y"][0]
            return outs

        monkeypatch.setattr(get_op_def("elementwise_add"), "lower", twice)
    cfg, job, main, loss, names, scope, exe = tiny_model("float32")
    for p in main.all_parameters():        # N(0, 0.02) keeps every score near 0 and the softmax flat: draw q, k larger
        if p.name.endswith((".attn.q.w", ".attn.kv_a.w", ".attn.kv_b.w")):
            scope.set_var(p.name, jnp.asarray(np.asarray(scope.find_var(p.name)) * 10.0))
        if p.name.endswith(".moe.shared.down.w"):   # ... and the shared experts' output, 16 hidden features wide here, too
            scope.set_var(p.name, jnp.asarray(np.asarray(scope.find_var(p.name)) * 4.0))
    rows = kanana.make_batch(np.random.RandomState(3), cfg, job, 8)
    got = exe.run(main.clone(for_test=True), feed=rows, fetch_list=list(names), scope=scope)
    want = reference_of(cfg, params_of(main, scope), rows)
    found = kanana.compare(got, want)
    assert found[stage] > limit, found
    assert not kanana.reference_error(got, want) <= kanana.REFERENCE_RTOL
