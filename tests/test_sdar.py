"""SDAR-30B-A3B-Chat's parts and the whole, tiny on the CPU (ISSUE 32).

(a) `fused_attention` under the block-diffusion mask and with grouped
    key/value heads: XLA's attention against a plain numpy golden, and the
    block-sparse kernel (interpret mode) against the dense rule, forward and
    gradient; which attention a shape takes;
(b) `moe_experts` holding a share of the experts against a plain golden,
    forward and gradient, however the router is skewed, and the eight shares
    of a layer adding up to the layer;
(c) a tiny `build_causal_lm` in float32 against the benchmark's reference
    (benchmark/models/sdar.py) on seeded weights: loss, the noised half's
    logits, routing, every gradient and, after one Adam step, every parameter;
(d) the same in bf16 within the benchmark's tolerances;
(e) the reference check fails on each of the seven faults ISSUE 32 lists, and
    on a router or experts a precision lower;
(f) steps through `train_loop` publish the share of the rows that were held.
"""
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
from benchmark.models import sdar  # noqa: E402
from paddle_tpu import monitor  # noqa: E402
from paddle_tpu.core.lowering import LoweringContext  # noqa: E402
from paddle_tpu.core.registry import get_op_def  # noqa: E402
from paddle_tpu.models import transformer  # noqa: E402
from paddle_tpu.ops import masked_attention, moe_ops, nn_ops  # noqa: E402

RNG = np.random.RandomState(32)
BF16 = jnp.bfloat16


def lower(op_type, ins, attrs=None, platform=None, mesh=None):
    """One op's lowering called as the interpreter calls it."""
    attrs = attrs or {}
    op = SimpleNamespace(type=op_type, attr=lambda n, d=None: attrs.get(n, d))
    ctx = LoweringContext(jax.random.PRNGKey(0), platform=platform, mesh=mesh)
    return get_op_def(op_type).lower(ctx, op, {k: [jnp.asarray(v)] for k, v in ins.items()})


def agree(got, want, tol=1e-5):
    got, want = np.asarray(got, "f8"), np.asarray(want, "f8")
    assert got.shape == want.shape
    assert np.abs(got - want).max() <= tol * max(np.abs(want).max(), 1e-12), \
        (np.abs(got - want).max(), np.abs(want).max())


# -- (a) the attention ----------------------------------------------------------

def dense_mask(positions, block):
    """M of the reference's docstring, written out pair by pair."""
    seq = positions // 2
    m = np.zeros((positions, positions), bool)
    for i in range(positions):
        for j in range(positions):
            bi, bj = (i % seq) // block, (j % seq) // block
            m[i, j] = ((i < seq and j < seq and bj == bi) or (i < seq and j >= seq and bj < bi)
                       or (i >= seq and j >= seq and bj <= bi))
    return m


def attention_golden(q, k, v, mask):
    """softmax(q k^T / sqrt(dh) under `mask`) v with query head j on key/value
    head j div (Hq / Hkv), by einsum over explicitly repeated heads."""
    group = q.shape[1] // k.shape[1]
    k, v = (jnp.stack([t[:, j // group] for j in range(q.shape[1])], 1) for t in (k, v))
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k, precision="highest") / np.sqrt(q.shape[-1])
    s = jnp.where(mask, s, -jnp.inf)
    return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1), v, precision="highest")


def test_the_rule_is_the_docstrings_mask_and_allows_a_quarter_of_the_square():
    for positions, block in ((16, 2), (24, 4), (64, 4), (32, 16)):
        at = np.arange(positions)
        rule = masked_attention.block_diffusion_allowed(at[:, None], at[None, :], positions // 2, block)
        assert (rule == dense_mask(positions, block)).all()
        assert rule.sum() == masked_attention.allowed_pairs(positions, block) == sdar.allowed_pairs(positions // 2, block)
        assert rule.any(-1).all()  # no query without a key: no row of the softmax is empty
    n = 4096 // 4
    assert masked_attention.allowed_pairs(8192, 4) / 8192 ** 2 == 0.25 + 0.25 / n


ATTENTION_CASES = {"grouped-mask": (4, 2, 32, 4), "grouped-causal": (4, 1, 16, None), "mask-equal-heads": (2, 2, 32, 8),
                   "one-kv-head-mask": (4, 1, 64, 4)}


@pytest.mark.parametrize("case", list(ATTENTION_CASES))
def test_fused_attention_golden_forward_and_gradient_under_the_mask_and_with_grouped_heads(case):
    hq, hkv, positions, block = ATTENTION_CASES[case]
    q = RNG.randn(2, hq, positions, 16).astype("f4")
    k, v = (RNG.randn(2, hkv, positions, 16).astype("f4") for _ in range(2))
    weight = RNG.randn(*q.shape).astype("f4")
    mask = dense_mask(positions, block) if block else np.tril(np.ones((positions, positions), bool))
    attrs = {"mask": "block_diffusion", "mask_block": block} if block else {"causal": True}

    def program(q, k, v):
        return lower("fused_attention", {"Q": q, "K": k, "V": v}, attrs)["Out"]

    agree(program(q, k, v), attention_golden(q, k, v, mask), tol=2e-6)
    got = jax.grad(lambda *a: jnp.sum(program(*a) * weight), (0, 1, 2))(q, k, v)
    want = jax.grad(lambda *a: jnp.sum(attention_golden(*a, mask) * weight), (0, 1, 2))(q, k, v)
    for g, w in zip(got, want):
        agree(g, w, tol=1e-5)


#: heads, key/value heads, the rule's block, positions -> is the own-block term split off the kernels (rule's block < kernels' 128)
KERNEL_CASES = {(4, 2, 4, 256): True, (2, 2, 8, 256): True, (8, 1, 4, 256): True, (4, 4, 64, 256): True,
                (2, 2, 4, 512): True, (4, 2, 128, 768): False, (2, 2, 128, 768): False, (4, 1, 384, 768): False}


@pytest.mark.parametrize("hq,hkv,block,positions", list(KERNEL_CASES))
def test_the_block_sparse_kernel_agrees_with_the_dense_rule_forward_and_backward(hq, hkv, block, positions):
    """The stock kernels, interpreted: what the chip runs but for Mosaic
    (tests/test_chip_compile.py compiles it).  Blocks of 128: blocks the rule
    empties, fills and cuts; the own-block term joined by its log-sum-exp
    where the rule's block is smaller than the kernels', one call over the
    whole square where it is not; the first noised block's rows, which have
    no far key, finite and the dense rule's in the output and every gradient."""
    dh = 128
    q = RNG.randn(2, hq, positions, dh).astype("f4")
    k, v = (RNG.randn(2, hkv, positions, dh).astype("f4") for _ in range(2))
    weight = RNG.randn(*q.shape).astype("f4")
    mask = dense_mask(positions, block)
    plan = masked_attention.plan_of(positions, hq, block)
    assert (plan.first_key == positions // 2) is KERNEL_CASES[hq, hkv, block, positions] and plan.block == 128

    def kernel(q, k, v):
        return masked_attention.block_sparse_attention(q, k, v, block, dh ** -0.5, interpret=True)

    out, golden = kernel(q, k, v), attention_golden(q, k, v, mask)
    agree(out, golden, tol=1e-5)
    got = jax.grad(lambda *a: jnp.sum(kernel(*a) * weight), (0, 1, 2))(q, k, v)
    want = jax.grad(lambda *a: jnp.sum(attention_golden(*a, mask) * weight), (0, 1, 2))(q, k, v)
    for g, w in zip(got, want):
        agree(g, w, tol=2e-5)
    first = slice(0, block)  # the first noised block: queries with no far key, keys no clean query sees
    assert all(np.isfinite(np.asarray(t)).all() for t in (out, *got))
    for g, w in zip((out, *got), (golden, *want)):
        agree(g[:, :, first], w[:, :, first], tol=2e-5)


def test_the_kernels_block_map_skips_what_the_rule_empties():
    """At the cell's 8192 positions the kernels see the 4096 clean keys only:
    20 of the rectangle's 32 1024-blocks are visited, 8 of them cut (a clean
    block's diagonal, as the noised rows and as the clean rows see it: two
    distinct blocks are all of the mask that reaches the device), a row of the
    grid holds 4 at the most and all heads share one map; no dq map is made
    (PR 68: backward is the one kernel that walks the dkv map, 20 steps a head,
    8 of them reading one of the two stored blocks and 12 the block of ones).
    The whole square in blocks of 512, which is what a
    rule's block as large as the kernels' still takes: 80 of 256 visited, 56
    of them whole, three distinct cut blocks (a diagonal block of each
    quadrant)."""
    from jax.experimental.pallas.ops.tpu.splash_attention import splash_attention_kernel as splash
    from jax.experimental.pallas.ops.tpu.splash_attention import splash_attention_mask as mask_lib

    plan = masked_attention.plan_of(8192, 32, 4)
    assert (plan.block, plan.first_key) == (1024, 4096)
    forward, dq, dkv = masked_attention.block_maps(plan)
    blocks = np.asarray(forward.block_mask)
    assert blocks.shape == (1, 8, 4) and forward.q_sequence is None
    assert ((blocks > 0).sum(), (blocks == 1).sum(), (blocks == 2).sum()) == (20, 8, 12)
    assert np.asarray(forward.data_next).max() < 4096 // 1024  # no block over a noised key: there are none to fetch
    assert np.asarray(forward.partial_mask_blocks).shape == (2, 1024, 1024)
    assert dq is None and plan.backward == "onchip_dq"
    assert np.asarray(dkv.block_mask).shape == (1, 8, 4) and (np.asarray(dkv.block_mask) > 0).sum() == 20
    steps, (stored, mask_of) = masked_attention._steps(plan), masked_attention._stored_blocks(plan)
    assert steps.q_block.size == mask_of.size == 20 and stored.shape == (3, 1024, 1024) and stored.dtype == np.int8
    assert (mask_of < 2).sum() == 8 and stored[2].all() and not stored[:2].all(axis=(1, 2)).any()
    at = np.arange(1024)
    for q_block, kv_block, named in zip(steps.q_block, steps.kv_block, mask_of):      # each step's block IS the rule's, keys by queries
        rule = masked_attention.block_diffusion_allowed((1024 * q_block + at)[None, :], (4096 + 1024 * kv_block + at)[:, None], 4096, 4)
        assert (stored[named].astype(bool) == rule).all()
    whole = masked_attention.block_maps(plan._replace(first_key=0))[0].block_mask
    assert ((whole > 0).sum(), (whole == 1).sum()) == (24, 12)  # what the split took off: the noised quadrant's diagonal

    sizes = splash.BlockSizes(block_q=512, block_kv=512)
    kernel = splash.make_splash_mha(mask_lib.MultiHeadMask([masked_attention._rule_mask(8192, 0, 4)] * 32),
                                    block_sizes=sizes, head_shards=1, q_seq_shards=1)
    info = kernel.fwd_mask_info
    blocks = np.asarray(info.block_mask)
    assert blocks.shape == (1, 16, 9) and info.q_sequence is None
    assert ((blocks == 1).sum(), (blocks == 2).sum()) == (24, 56)
    cut = np.asarray(info.partial_mask_blocks)
    assert cut.shape == (3, 512, 512)
    at = np.arange(512)
    diagonals = {tuple(map(int, masked_attention.block_diffusion_allowed(
        (q0 + at)[:, None], (k0 + at)[None, :], 4096, 4).sum(-1))) for q0, k0 in ((0, 0), (0, 4096), (4096, 4096))}
    assert {tuple(map(int, c.sum(-1))) for c in cut} == diagonals


@pytest.mark.parametrize("block,counted", [(4, (20, 8, 1)), (1024, (20, 0, 0))])
def test_the_lowering_counts_the_blocks_the_kernels_visit_and_the_own_block_terms(block, counted):
    """An attention at the cell's shape: 20 blocks visited, 8 of them cut and
    one own-block term; with the rule's block as large as the kernels' the
    whole square holds 20 whole blocks, none cut, and no such term."""
    monitor.reset()
    monitor.enable()
    try:
        args = [jax.ShapeDtypeStruct((2, 32, 8192, 128), BF16)] + [jax.ShapeDtypeStruct((2, 4, 8192, 128), BF16)] * 2
        jax.eval_shape(lambda q, k, v: lower("fused_attention", {"Q": q, "K": k, "V": v},
                                             {"mask": "block_diffusion", "mask_block": block}, platform="tpu")["Out"], *args)
        got = monitor.MONITOR.counter_values()
        assert tuple(got[f"lowering.attention_{name}"] for name in ("blocks_visited", "blocks_cut", "own_block_terms")) == counted
        assert got["lowering.attention_block_sparse"] == 1
    finally:
        monitor.disable()
        monitor.reset()


def test_a_mask_or_fewer_key_heads_that_make_no_sense_are_refused():
    q = jnp.zeros((1, 4, 32, 16))
    with pytest.raises(ValueError, match="mask 'windowed'"):
        lower("fused_attention", {"Q": q, "K": q, "V": q}, {"mask": "windowed", "mask_block": 4})
    with pytest.raises(ValueError, match="mask_block 5"):
        lower("fused_attention", {"Q": q, "K": q, "V": q}, {"mask": "block_diffusion", "mask_block": 5})
    with pytest.raises(ValueError, match="fused_attention's"):
        with fluid.program_guard(fluid.Program(), fluid.Program()):
            x = fluid.layers.data("x", [32, 64])
            transformer.multi_head_attention(x, 32, 64, 4, "a", n_kv_heads=2)


MASKED_PATHS = {  # (queries = keys, head width, on a mesh) -> the attention a TPU takes under the mask
    (8192, 128, False): "block_sparse", (512, 128, False): "block_sparse", (256, 128, False): "block_sparse",
    (384, 128, False): "block_sparse", (8192, 128, True): "xla", (320, 128, False): "xla", (8192, 64, False): "xla",
}


@pytest.mark.parametrize("case", list(MASKED_PATHS), ids=lambda c: f"{c[0]}x{c[1]}{'-mesh' if c[2] else ''}")
def test_a_structured_mask_takes_the_block_sparse_kernel_or_xlas_attention(case):
    positions, dh, on_a_mesh = case
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:4]), ("dp",)) if on_a_mesh else None
    q = jax.ShapeDtypeStruct((2, 32, positions, dh), BF16)
    k = jax.ShapeDtypeStruct((2, 4, positions, dh), BF16)
    mask = ("block_diffusion", 4)
    assert nn_ops._attention_path("tpu", mesh, q, k, mask) == MASKED_PATHS[case]
    assert nn_ops._attention_path("cpu", None, q, k, mask) == "xla"
    # without a mask the shape's own choice, whatever the heads
    assert nn_ops._attention_path("tpu", None, q, k) == ("flash" if positions >= 2048 else "xla")


def test_the_lowering_counts_the_block_sparse_attention_and_names_its_kernels():
    import re

    monitor.reset()
    monitor.enable()
    try:
        args = [jax.ShapeDtypeStruct((1, 4, 256, 128), BF16)] + [jax.ShapeDtypeStruct((1, 2, 256, 128), BF16)] * 2

        def attention(q, k, v):
            out = lower("fused_attention", {"Q": q, "K": k, "V": v},
                        {"mask": "block_diffusion", "mask_block": 4}, platform="tpu")["Out"]
            return out.astype(jnp.float32).sum()

        text = str(jax.make_jaxpr(jax.grad(attention, (0, 1, 2)))(*args))
        counted = monitor.MONITOR.counter_values()
        assert counted["lowering.attention_block_sparse"] == 1 and not counted.get("lowering.attention_xla")
        # the stock forward kernel and the ONE backward kernel that reads a stored block of the mask (PR 68)
        assert "splash_mha_fwd" in text and "name=attention_dq_dk_dv" in text and "splash_mha_dq" not in text and "splash_mha_dkv" not in text
        assert counted["lowering.attention_backward_onchip_dq"] == 1 and counted["lowering.attention_backward_stored_steps"] == 2   # both of the far term's blocks are cut
        assert "own_block_join" in text and "own_block_backward" in text  # and the own-block term's two
        assert not re.findall(r"\[(?:\d+,)*256,256\]", text)  # no array of the whole square
    finally:
        monitor.disable()
        monitor.reset()


def test_the_cost_row_counts_the_allowed_pairs():
    from paddle_tpu.core import resource_plan

    def planned(**kw):
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup):
            q = fluid.layers.data("q", [4, 64, 16])
            k = fluid.layers.data("k", [2, 64, 16])
            out = fluid.layers.fused_attention(q, k, k, **kw)
        plan = resource_plan.plan_program(main, {"q": (2, 4, 64, 16), "k": (2, 2, 64, 16)}, [out.name])
        return next(r for r in plan.rows if r.op_type == "fused_attention").flops

    full = 4.0 * 2 * 4 * 64 * 64 * 16
    assert planned() == full
    assert planned(mask="block_diffusion", mask_block=4) == full * masked_attention.allowed_pairs(64, 4) / 64 ** 2


# -- (b) a layer that holds a share of its experts --------------------------------

def held_golden(x, top_p, top_i, w_gate, w_up, w_down, first):
    """sum over a token's chosen experts THAT ARE HELD of p . down(silu(gate x) * up x)."""
    out = jnp.zeros_like(x)
    for e in range(w_gate.shape[0]):
        weight = jnp.sum(jnp.where(top_i == first + e, top_p, 0.0), -1, keepdims=True)
        hidden = jax.nn.silu(jnp.dot(x, w_gate[e], precision="highest")) * jnp.dot(x, w_up[e], precision="highest")
        out = out + jnp.dot(hidden, w_down[e], precision="highest") * weight
    return out


def held_case(name, tokens=1024, experts=16, k=2, first=4, count=2):
    """Routings a held layer has to get right, 2048 assignments of which the
    bound covers 512: a uniform router (an eighth of the rows held), one that
    sends every token to held experts (four times the bound: the rare path
    runs every chunk), one that sends none, one expert taking all, a bound
    that is met to the row or passed by one, and held rows that end inside a
    pass of 128 of the two row operations and on its edge."""
    rng = np.random.RandomState(len(name))
    if name == "uniform":
        top_i = np.stack([rng.permutation(experts)[:k] for _ in range(tokens)])
    elif name == "all-held":
        top_i = np.stack([first + rng.permutation(count)[:k] for _ in range(tokens)])
    elif name == "none-held":
        top_i = np.stack([rng.permutation(first)[:k] for _ in range(tokens)])
    elif name == "one-expert-takes-all":
        top_i = np.tile([first + 1, 0], (tokens, 1))
    else:
        held_rows = {"bound-met": 512, "bound-passed-by-one": 513, "ends-inside-a-pass": 200, "ends-on-a-passes-edge": 256}[name]
        top_i = np.tile([0, 1], (tokens, 1))
        top_i.reshape(-1)[rng.permutation(tokens * k)[:held_rows]] = first + np.arange(held_rows) % count
    top_i = top_i.astype("int32")
    load = np.bincount(top_i.reshape(-1), minlength=experts).astype("int32")
    d, f = 16, 8
    return SimpleNamespace(
        x=rng.randn(tokens, d).astype("f4"), top_p=rng.rand(tokens, k).astype("f4"), top_i=top_i,
        load=load, w_gate=rng.randn(count, d, f).astype("f4") / 4, w_up=rng.randn(count, d, f).astype("f4") / 4,
        w_down=rng.randn(count, f, d).astype("f4") / 4, first=first, count=count)


def held_lowering(c, x, top_p, w_gate, w_up, w_down, first=None, count=None):
    ins = {"X": x, "TopKProb": top_p, "TopKIndex": c.top_i, "Load": c.load,
           "WGate": w_gate, "WUp": w_up, "WDown": w_down}
    return lower("moe_experts", ins, {"held": [c.first if first is None else first, count or c.count]})


HELD_CASES = ["uniform", "all-held", "none-held", "one-expert-takes-all", "bound-met", "bound-passed-by-one"]


#: the cases past the bound with the rare path's pass cut to 128 rows: twelve passes, not three of the bound's 512
SMALL_PASSES = ["all-held", "one-expert-takes-all", "bound-passed-by-one"]
#: the common path's row operations go over the bound's 512 rows in passes of 128 (`_pass_rows`, PR 35): in the
#: cases above the held rows end before the first pass (none-held: no pass runs), inside the second (uniform)
#: and with the fourth (bound-met: the bound, to the row); in these inside the second pass and on its edge
ROW_PASSES = ["ends-inside-a-pass", "ends-on-a-passes-edge"]
#: ... and in passes of 384: the bound is no whole number of them, and the second pass ends with it
RAGGED_PASSES = ["bound-met", "ends-inside-a-pass"]


@pytest.mark.parametrize("case,rest_rows,pass_rows", [(c, None, None) for c in HELD_CASES + ROW_PASSES]
                         + [(c, 128, None) for c in SMALL_PASSES] + [(c, None, 384) for c in RAGGED_PASSES])
def test_held_experts_golden_forward_and_gradient_and_nothing_dropped(case, rest_rows, pass_rows, monkeypatch):
    if rest_rows:
        monkeypatch.setattr(moe_ops, "_HELD_REST_ROWS", rest_rows)
    if pass_rows:
        monkeypatch.setattr(moe_ops, "_pass_rows", lambda n: min(n, pass_rows))
    assert pass_rows or moe_ops._pass_rows(512) == 128
    c = held_case(case)
    out = held_lowering(c, c.x, c.top_p, c.w_gate, c.w_up, c.w_down)
    held_rows = int(c.load[c.first:c.first + c.count].sum())
    assert int(out["Held"][0]) == held_rows and int(out["Dropped"][0]) == 0
    bound = moe_ops._held_rows_bound(c.top_i.size, c.count, c.load.size)
    assert bound == 512 and c.top_i.size == 4 * bound
    rare = ("all-held", "one-expert-takes-all", "bound-passed-by-one")
    assert (held_rows > bound) == (case in rare)  # the rare path is run, and is not
    agree(out["Out"], held_golden(c.x, c.top_p, c.top_i, c.w_gate, c.w_up, c.w_down, c.first), tol=2e-6)
    weight = np.random.RandomState(1).randn(*c.x.shape).astype("f4")
    args = (c.x, c.top_p, c.w_gate, c.w_up, c.w_down)
    got = jax.grad(lambda *a: jnp.sum(held_lowering(c, *a)["Out"] * weight), range(5))(*args)
    want = jax.grad(lambda x, p, *w: jnp.sum(held_golden(x, p, c.top_i, *w, c.first) * weight), range(5))(*args)
    for g, w in zip(got, want):
        agree(g, w, tol=1e-5)
    if case == "none-held":   # no token chose a held expert: no pass runs, and nothing is anything but zero
        assert not np.asarray(out["Out"]).any() and not any(np.asarray(g).any() for g in got)


@pytest.mark.parametrize("rows_a_pass", [8, 5, 24, 64])
def test_the_two_row_operations_are_each_others_transpose_whatever_is_live(rows_a_pass, monkeypatch):
    """`_rows_of_tokens` is R x and `_add_to_tokens` R^T rows for the [C, T]
    one-hot R of the chunk's LIVE rows, for every count of them from none to
    the chunk's 24, with passes of 8 rows, of 5 (the last ends with the
    chunk), of the chunk's own 24 and of more: forward and `jax.vjp`, float32.
    Rows past the live ones belong to no token: R has no row for them, a
    gather leaves zeros past its last pass, and what it fetches between the
    last live row and that pass's end is masked where it is used (`keep`)."""
    monkeypatch.setattr(moe_ops, "_pass_rows", lambda n: min(n, rows_a_pass))
    rng = np.random.RandomState(rows_a_pass)
    chunk, tokens, d = 24, 10, 4
    token = rng.randint(0, tokens, chunk).astype("int32")
    x, rows = rng.randn(tokens, d).astype("f4"), rng.randn(chunk, d).astype("f4")
    g_rows, g_tokens = rng.randn(chunk, d).astype("f4"), rng.randn(tokens, d).astype("f4")

    @jax.jit
    def both(live):
        target = jnp.where(jnp.arange(chunk) < live, token, tokens)
        gathered, pull_x = jax.vjp(lambda x: moe_ops._rows_of_tokens(x, token, target, live, tokens), x)
        added, pull_rows = jax.vjp(lambda r: moe_ops._add_to_tokens(r, token, target, live, tokens), rows)
        return gathered, pull_x(g_rows)[0], added, pull_rows(g_tokens)[0]

    for live in range(chunk + 1):
        one_hot = (np.arange(chunk)[:, None] < live) * (token[:, None] == np.arange(tokens)[None, :]).astype("f4")
        gathered, d_x, added, d_rows = both(jnp.int32(live))
        is_live = np.arange(chunk)[:, None] < live
        passed = min(-(-live // min(rows_a_pass, chunk)) * min(rows_a_pass, chunk), chunk)
        agree(np.where(is_live, gathered, 0), one_hot @ x)
        assert not np.asarray(gathered[passed:]).any() and not np.asarray(d_rows[passed:]).any()   # zeros past the last pass
        agree(d_x, one_hot.T @ g_rows)
        agree(added, one_hot.T @ rows)
        agree(np.where(is_live, d_rows, 0), one_hot @ g_tokens)


def test_the_eight_shares_of_a_layer_add_up_to_the_layer():
    """Eight chips hold 4 of 32 experts each; the router's weights are
    renormalised over all the chosen, so the shares' outputs, summed, are the
    uncut layer's: today's `moe_experts` over all 32, and the plain golden."""
    rng = np.random.RandomState(8)
    tokens, experts, k, d, f = 40, 32, 8, 16, 8
    top_i = np.stack([rng.permutation(experts)[:k] for _ in range(tokens)]).astype("int32")
    top_p = rng.rand(tokens, k).astype("f4")
    top_p /= top_p.sum(-1, keepdims=True)
    x = rng.randn(tokens, d).astype("f4")
    gate, up = (rng.randn(experts, d, f).astype("f4") / 4 for _ in range(2))
    down = rng.randn(experts, f, d).astype("f4") / 4
    c = SimpleNamespace(top_i=top_i, load=np.bincount(top_i.reshape(-1), minlength=experts).astype("int32"))
    shares = [held_lowering(c, x, top_p, gate[s:s + 4], up[s:s + 4], down[s:s + 4], first=s, count=4)
              for s in range(0, experts, 4)]
    assert sum(int(s["Held"][0]) for s in shares) == tokens * k
    whole = lower("moe_experts", {"X": x, "TopKProb": top_p, "TopKIndex": top_i, "Load": c.load,
                                  "WGate": gate, "WUp": up, "WDown": down})["Out"]
    agree(sum(s["Out"] for s in shares), whole, tol=2e-6)
    agree(whole, held_golden(x, top_p, top_i, gate, up, down, 0), tol=2e-6)
    # ... and one share alone is its own experts' part, not a rescaled whole
    agree(shares[2]["Out"], held_golden(x, top_p, top_i, gate[8:12], up[8:12], down[8:12], 8), tol=2e-6)


def test_the_held_rows_bound_is_twice_the_uniform_share_in_whole_tiles():
    assert moe_ops._held_rows_bound(16384 * 8, 16, 128) == 32768
    assert moe_ops._held_rows_bound(16384 * 8, 128, 128) == 131072   # all held: one pass over all
    assert moe_ops._held_rows_bound(96, 4, 16) == 128                 # under a tile: one small tile
    assert moe_ops._held_rows_bound(1000, 1, 128) == 512


def test_the_sort_by_key_is_a_stable_argsort_and_its_transpose_sorts_back():
    key = np.array([3, 1, 3, 0, 1, 2], "int32")
    values = np.arange(6, dtype="f4") + 10
    order, ordered = moe_ops._sort_by_key(key, values)
    assert order.tolist() == [3, 1, 4, 5, 0, 2] and ordered.tolist() == values[np.asarray(order)].tolist()
    weight = np.array([1., 2., 3., 4., 5., 6.], "f4")
    grad = jax.grad(lambda v: jnp.sum(moe_ops._sort_by_key(key, v)[1] * weight))(values)
    want = np.zeros(6, "f4")
    want[np.asarray(order)] = weight
    assert grad.tolist() == want.tolist()


def test_layers_moe_with_a_share_held_declares_its_parameters_and_refuses_no_range():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data("x", [8, 16])
        fluid.layers.moe(x, 32, 8, 4, held=(8, 4))
        with pytest.raises(ValueError, match="no range"):
            fluid.layers.moe(x, 32, 8, 4, held=(30, 4))
    shapes = sorted(tuple(p.shape) for p in main.all_parameters())
    assert shapes == [(4, 8, 16), (4, 16, 8), (4, 16, 8), (16, 32)]
    experts = next(op for op in main.global_block().ops if op.type == "moe_experts")
    assert experts.attrs["held"] == [8, 4] and "Held" in experts.outputs
    # and a layer that holds them all is today's op: no attribute, no output more
    main2, startup2 = fluid.Program(), fluid.Program()
    with fluid.program_guard(main2, startup2):
        fluid.layers.moe(fluid.layers.data("x", [8, 16]), 32, 8, 4)
    whole = next(op for op in main2.global_block().ops if op.type == "moe_experts")
    assert "held" not in whole.attrs and sorted(whole.outputs) == ["Dropped", "Out"]


def test_the_cost_row_charges_the_held_rows_and_the_bounds_passes():
    """The traffic is an upper bound since PR 35: the row operations go over
    the bound in passes and stop after the last that holds a live row, a count
    the step's routing gives and the plan cannot know, so the row stays the bound's."""
    from paddle_tpu.core import resource_plan

    tokens, d, f, experts, k = 256, 16, 8, 32, 8

    def planned(held):
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup):
            out = fluid.layers.moe(fluid.layers.data("x", [d]), experts, f, k, held=held)[0]
        plan = resource_plan.plan_program(main, {"x": (tokens, d)}, [out.name])
        return next(r for r in plan.rows if r.op_type == "moe_experts")

    whole, share = planned(None), planned((0, 4))
    rows = tokens * k
    assert whole.flops == 6.0 * rows * d * f and share.flops == whole.flops / 8
    bound = moe_ops._held_rows_bound(rows, 4, experts)
    assert bound == 512  # twice the share: a quarter of the rows
    once = 2 * tokens * d + 2 * tokens * k + experts + 3 * 4 * d * f + 2
    assert share.traffic_bytes == 4 * (once + bound * (7 * d + 6 * f))


# -- (c), (d) the whole model against the benchmark's reference --------------------

TINY = dict(hidden_size=64, num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2, head_dim=32,
            moe_intermediate_size=32, num_experts=4, num_routed_experts=16, experts_held_first=4,
            num_experts_per_tok=2, vocab_size=96, routing_seed=0)  # every weight from the test's seed
JOB = dict(seq_len=32, batch_per_chip=4)


def tiny_model(dtype, sizes=TINY, job=JOB):
    cfg = dict(mf.read_json("benchmark/configs/sdar-30b-a3b-chat.json"), compute_dtype=dtype, **sizes)
    job = dict(mf.read_json("benchmark/traffic/train-blockdiff-s4096.json"), **job)
    main, startup, feeds, loss, names = sdar.build(cfg, job)
    main.random_seed = startup.random_seed = 3
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.TPUPlace(0))
    exe.run(startup, scope=scope)
    return cfg, job, main, loss, names, scope, exe


def params_of(main, scope):
    return {p.name: np.asarray(scope.find_var(p.name)) for p in main.all_parameters()}


def reference_of(cfg, params, rows):
    return [np.asarray(w) for w in jax.jit(lambda p, b: sdar.reference(p, b, cfg))(params, rows)]


@pytest.fixture(scope="module")
def float32_run():
    """The tiny float32 model: the for_test clone's fetches on 8 rows, the
    reference's, the reference's gradients on 4 rows, and the program's state
    after one training step on those 4."""
    from paddle_tpu.core import unique_name

    with unique_name.guard():
        cfg, job, main, loss, names, scope, exe = tiny_model("float32")
        rows = sdar.make_batch(np.random.RandomState(3), cfg, job, 8)
        test_program = main.clone(for_test=True)
        got = exe.run(test_program, feed=rows, fetch_list=list(names), scope=scope)
        before = params_of(main, scope)
        want = reference_of(cfg, before, rows)
        batch = sdar.make_batch(np.random.RandomState(4), cfg, job, 4)
        ref_loss, ref_grads = jax.jit(jax.value_and_grad(
            lambda p: sdar.reference(p, batch, cfg)[0]))(before)
        step_loss, = exe.run(main, feed=batch, fetch_list=[loss], scope=scope)
        after = params_of(main, scope)
        moments = {n: np.asarray(scope.find_var(n + "_moment1_0")) for n in before}
    return SimpleNamespace(cfg=cfg, job=job, got=got, want=want, rows=rows, names=names,
                           before=before, after=after, moments=moments,
                           ref_loss=float(ref_loss), step_loss=float(np.asarray(step_loss).reshape(-1)[0]),
                           ref_grads={k: np.asarray(v) for k, v in ref_grads.items()})


def test_float32_loss_logits_and_routing_agree_with_the_reference(float32_run):
    found = sdar.compare(float32_run.got, float32_run.want)
    assert found["left_out"] == found["routed_differently"] == found["routed_differently_above_margin"] == 0
    assert found["loss_error"] < 1e-5 and found["logit_error"] < 1e-5, found
    assert max(found["router_prob_error"], found["experts_error"], found["attention_error"],
               found["qk_error"]) < 1e-5, found
    assert sdar.reference_error(float32_run.got, float32_run.want) < 1e-5
    assert abs(float32_run.step_loss - float32_run.ref_loss) < 1e-5 * float32_run.ref_loss
    assert np.asarray(float32_run.got[1]).shape == (8, 32, 96)  # the noised half's logits and no other


PARAMS = sorted(
    ["lm.tok_emb", "lm.head.w", "lm.final_norm.w"]
    + [f"lm.l{i}.{n}" for i in range(2) for n in (
        "ln1.w", "ln2.w", "attn.q.w", "attn.k.w", "attn.v.w", "attn.out.w", "attn.q_norm.w",
        "attn.k_norm.w", "moe.router.w", "moe.gate.w", "moe.up.w", "moe.down.w")])


def test_the_tiny_model_has_these_parameters_and_no_other(float32_run):
    assert sorted(float32_run.before) == PARAMS
    shapes = {n: float32_run.before[f"lm.l0.{n}"].shape for n in (
        "attn.q.w", "attn.k.w", "attn.v.w", "attn.out.w", "attn.q_norm.w", "attn.k_norm.w",
        "moe.router.w", "moe.gate.w", "moe.down.w")}
    assert shapes == {"attn.q.w": (64, 128), "attn.k.w": (64, 64), "attn.v.w": (64, 64), "attn.out.w": (128, 64),
                      "attn.q_norm.w": (32,), "attn.k_norm.w": (32,), "moe.router.w": (64, 16),
                      "moe.gate.w": (4, 64, 32), "moe.down.w": (4, 32, 64)}


@pytest.mark.parametrize("name", PARAMS)
def test_float32_gradient_and_adam_step_agree_with_the_reference(float32_run, name):
    """Adam's first moment after one step is 0.1 x the gradient; the parameter
    moves by lr_t m / (sqrt(v) + eps), which is lr . sign(g) where |g| is far
    above eps: the step is compared to 2% of the learning rate, the gradient
    to 1e-4 of its largest element.  (The token the mask stands on, and the
    embedding's rows no id drew, have no gradient: they stay.)"""
    r = float32_run
    lr, b1, b2, eps = 1e-4, 0.9, 0.95, 1e-8
    g = r.ref_grads[name]
    agree(r.moments[name] / (1 - b1), g, tol=1e-4)
    lr_t = lr * np.sqrt(1 - b2) / (1 - b1)
    want = r.before[name] - lr_t * (1 - b1) * g / (np.sqrt((1 - b2) * g * g) + eps)
    assert np.abs(r.after[name] - want).max() <= 0.02 * lr
    assert np.abs(r.after[name] - r.before[name]).max() > 0.5 * lr  # it moved


def test_bfloat16_agrees_within_the_benchmarks_tolerances(capsys):
    cfg, job, main, loss, names, scope, exe = tiny_model("bfloat16")
    rows = sdar.make_batch(np.random.RandomState(3), cfg, job, 8)
    got = exe.run(main.clone(for_test=True), feed=rows, fetch_list=list(names), scope=scope)
    want = reference_of(cfg, params_of(main, scope), rows)
    found = sdar.compare(got, want)
    assert found["tokens"] == 8 * 64 and found["routed_differently_above_margin"] == 0
    assert found["left_out"] <= found["routed_differently"] <= sdar.LEFT_OUT_MAX * found["tokens"]
    assert 1e-4 < found["logit_error"] < sdar.REFERENCE_RTOL and found["loss_error"] < 1e-3
    assert found["router_prob_error"] < sdar.ROUTER_RTOL and found["experts_error"] < sdar.EXPERTS_RTOL
    assert found["attention_error"] < sdar.ATTENTION_RTOL and found["qk_error"] < sdar.QK_RTOL
    assert sdar.reference_error(got, want) == max(found["loss_error"], found["logit_error"])
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["info"] == "reference_routing" and line["left_out"] == found["left_out"]


def test_make_batch_is_the_noise_the_docstring_says():
    cfg = dict(mf.read_json("benchmark/configs/sdar-30b-a3b-chat.json"), vocab_size=96)
    job = {"seq_len": 512}
    b = sdar.make_batch(np.random.RandomState(5), cfg, job, 6)
    assert b["ids"].shape == b["pos_ids"].shape == (6, 1024) and b["labels"].shape == b["loss_weight"].shape == (6, 512)
    noised, clean = b["ids"][:, :512], b["ids"][:, 512:]
    assert (clean == b["labels"]).all() and clean.max() < 95 and (b["pos_ids"] == np.tile(np.arange(512), (6, 2))).all()
    masked = noised == 95
    assert ((noised == clean) | masked).all() and ((b["loss_weight"] > 0) == masked).all()
    weight = b["loss_weight"].reshape(6, 128, 4)
    # one noise level a block: the block's masked positions share 1/t, t in (1e-3, 1]
    assert all(len(set(w[w > 0])) <= 1 for w in weight.reshape(-1, 4)) and weight[weight > 0].min() >= 1.0
    assert 0.4 < masked.mean() < 0.6 and 0.8 < b["loss_weight"].mean() < 1.2  # E[masked] = 1/2, E[weight] = 1
    again = sdar.make_batch(np.random.RandomState(5), cfg, job, 6)
    assert all((again[k] == b[k]).all() for k in b)


# -- (e) what the check has to catch -----------------------------------------------

def _with_lowering(monkeypatch, op_type, wrap):
    op_def = get_op_def(op_type)
    monkeypatch.setattr(op_def, "lower", wrap(op_def.lower))


def _faulty_rule(noised_sees_its_own_clean_copy=False, clean_sees_noised=False):
    """The mask's rule with one thing wrong: `<=` for `<` where a noised query
    looks at the clean copy (the answer leaks), or a clean query that sees
    the noised copy of its own block."""
    def allowed(q_ids, kv_ids, seq, block):
        q_clean, kv_clean = q_ids >= seq, kv_ids >= seq
        q_blk, kv_blk = (q_ids % seq) // block, (kv_ids % seq) // block
        before = (kv_blk <= q_blk) if noised_sees_its_own_clean_copy else (kv_blk < q_blk)
        rule = ((~q_clean & ~kv_clean & (kv_blk == q_blk)) | (~q_clean & kv_clean & before)
                | (q_clean & kv_clean & (kv_blk <= q_blk)))
        return rule | (q_clean & ~kv_clean & (kv_blk == q_blk)) if clean_sees_noised else rule
    return allowed


def _renormalised_over_the_held(first, count):
    def wrap(shipped):
        def lower_(ctx, op, ins):
            top_p, top_i = ins["TopKProb"][0], ins["TopKIndex"][0]
            mine = jnp.where((top_i >= first) & (top_i < first + count), top_p, 0.0)
            mine = mine / jnp.maximum(jnp.sum(mine, -1, keepdims=True), 1e-9)
            return shipped(ctx, op, dict(ins, TopKProb=[mine]))
        return lower_
    return wrap


def _absent_experts_computed(first, count):
    """An absent expert e is run as held expert e mod count."""
    def wrap(shipped):
        def lower_(ctx, op, ins):
            top_i = first + ins["TopKIndex"][0] % count
            load = jnp.sum(top_i.reshape(-1)[:, None] == jnp.arange(ins["Load"][0].shape[0]), 0, dtype=jnp.int32)
            return shipped(ctx, op, dict(ins, TopKIndex=[top_i], Load=[load]))
        return lower_
    return wrap


FAULTS = ["noised_block_sees_its_own_clean_copy", "clean_query_sees_noised_keys", "key_head_j_mod_4_for_j_div_8",
          "renormalised_over_the_held_experts_only", "an_absent_expert_computed", "weight_1_for_1_over_t",
          "qk_norm_over_the_whole_width"]
CAUGHT_BY = {"noised_block_sees_its_own_clean_copy": "attention_error", "clean_query_sees_noised_keys": "attention_error",
             "key_head_j_mod_4_for_j_div_8": "attention_error", "renormalised_over_the_held_experts_only": "router_prob_error",
             "an_absent_expert_computed": "experts_error", "weight_1_for_1_over_t": "loss_error",
             "qk_norm_over_the_whole_width": "qk_error"}
LIMITS = {"attention_error": sdar.ATTENTION_RTOL, "router_prob_error": sdar.ROUTER_RTOL,
          "experts_error": sdar.EXPERTS_RTOL, "loss_error": sdar.REFERENCE_RTOL, "qk_error": sdar.QK_RTOL}


@pytest.mark.parametrize("fault", FAULTS)
def test_the_reference_check_fails_on(fault, float32_run, monkeypatch):
    """The seeded float32 model with one thing wrong in the PROGRAM, against
    the reference computed before: the check says no, and which of its parts
    says it."""
    from paddle_tpu.core import unique_name

    r = float32_run
    rows, first, count = dict(r.rows), r.cfg["experts_held_first"], r.cfg["num_experts"]
    build_over = {}
    if fault == "noised_block_sees_its_own_clean_copy":
        monkeypatch.setattr(masked_attention, "block_diffusion_allowed",
                            _faulty_rule(noised_sees_its_own_clean_copy=True))
    elif fault == "clean_query_sees_noised_keys":
        monkeypatch.setattr(masked_attention, "block_diffusion_allowed", _faulty_rule(clean_sees_noised=True))
    elif fault == "key_head_j_mod_4_for_j_div_8":
        monkeypatch.setattr(jnp, "repeat", lambda t, n, axis: jnp.concatenate([t] * n, axis=axis))
    elif fault == "renormalised_over_the_held_experts_only":
        _with_lowering(monkeypatch, "moe_experts", _renormalised_over_the_held(first, count))
    elif fault == "an_absent_expert_computed":
        _with_lowering(monkeypatch, "moe_experts", _absent_experts_computed(first, count))
    elif fault == "weight_1_for_1_over_t":
        rows["loss_weight"] = (rows["loss_weight"] > 0).astype("float32")
    else:
        build_over = {"qk_norm": "width"}
    with unique_name.guard():
        if build_over:
            shipped_build = transformer.build_causal_lm
            monkeypatch.setattr(transformer, "build_causal_lm",
                                lambda **kw: shipped_build(**dict(kw, **build_over)))
        cfg, job, main, loss, names, scope, exe = tiny_model("float32")
        for name, value in r.before.items():   # the seeded weights the reference was given
            if tuple(np.shape(scope.find_var(name))) == value.shape:
                scope.set_var(name, jnp.asarray(value))
        got = exe.run(main.clone(for_test=True), feed=rows, fetch_list=list(names), scope=scope)
    found = sdar.compare(got, r.want)
    part = CAUGHT_BY[fault]
    if fault == "renormalised_over_the_held_experts_only":
        # the router's own output is right; what the experts were GIVEN is not:
        # their output against the router's weights
        part = "experts_error"
    assert found[part] > 2 * LIMITS[part], (part, found)
    assert not sdar.reference_error(got, r.want) <= sdar.REFERENCE_RTOL


@pytest.mark.parametrize("fault", ["left_out_too_many", "routes_elsewhere_above_the_margin", "logits_off"])
def test_the_reference_check_fails_on_a_wrong_account(fault, float32_run):
    got = [np.array(g) for g in float32_run.got]
    want = [np.array(w) for w in float32_run.want]
    first = float32_run.cfg["experts_held_first"]
    if fault == "routes_elsewhere_above_the_margin":
        token = np.unravel_index(np.argmax(want[2]), want[2].shape)  # the clearest choice of all
        got[2][token] = (got[2][token] + 1) % 16
    elif fault == "left_out_too_many":
        want[2][:] = 0.0                 # every gap "under the margin"...
        got[2][:, :16] = first            # ...and a quarter of the positions' held choice is another
        got[6][:, :16] = first
    else:
        got[1] = got[1] + 0.05 * np.abs(want[1]).max() * (np.arange(got[1].size).reshape(got[1].shape) == 77)
    assert not sdar.reference_error(got, want) <= sdar.REFERENCE_RTOL


def test_the_stage_readings_a_precision_lower_lie_over_their_limits():
    """What each run prints beside its own stage errors: the same float32
    stage with bf16 router logits or bf16 running sums in the experts, on a
    router of the published widths (128 outputs, 8 a token, hidden 2048)."""
    rng = np.random.RandomState(7)
    tokens, d, f, experts, k, held = 512, 2048, 128, 128, 8, 16   # the contraction's published length: the sums' error grows with it
    m = sdar._bf16(rng.randn(tokens, d).astype("f4"))
    router = (rng.randn(d, experts) * 0.02).astype("f4")
    gate, up = ((rng.randn(held, d, f) * 0.02).astype("f4") for _ in range(2))
    down = (rng.randn(held, f, d) * 0.02).astype("f4")
    logits = (m @ router).astype("f8")
    probs = np.exp(logits - logits.max(-1, keepdims=True))
    probs /= probs.sum(-1, keepdims=True)
    choice = np.argsort(-probs, -1)[:, :k]
    top_p = np.take_along_axis(probs, choice, -1)
    top_p = (top_p / top_p.sum(-1, keepdims=True)).astype("f4")
    out = np.zeros((tokens, d), "f4")
    for e in range(held):
        row, slot = np.nonzero(choice == e)
        g = m[row] @ gate[e]
        out[row] += ((g / (1 + np.exp(-g)) * (m[row] @ up[e])) @ down[e]) * top_p[row, slot][:, None]
    found = sdar.stage_errors(choice, m, top_p, out, router, gate, up, down, 0)
    assert found["router_choice_differs"] == 0 and found["router_prob_error"] < 1e-5 and found["experts_error"] < 1e-5
    assert found["router_prob_error_bf16_logits"] > 2 * sdar.ROUTER_RTOL
    assert found["experts_error_bf16_sums"] > 1.5 * sdar.EXPERTS_RTOL


# -- (f) through train_loop -----------------------------------------------------------

def _logged_routing(steps=6):
    """The `moe_routing` records and the monitor's counters of `steps` steps of the tiny bf16 model through `train_loop`."""
    import itertools

    from paddle_tpu.core import unique_name

    monitor.reset()
    monitor.enable()
    try:
        with unique_name.guard():
            cfg, job, main, loss, names, scope, exe = tiny_model("bfloat16")
        rng = np.random.RandomState(9)
        ring = [sdar.make_batch(rng, cfg, job, 4) for _ in range(3)]
        compiled = monitor.MONITOR.counter_values().get("executor.recompile", 0)
        stats = fluid.train_loop(exe, main, itertools.cycle(ring), [loss], scope=scope,
                                 max_inflight=2, log_period=2, max_steps=steps)
        assert stats.steps == steps and monitor.MONITOR.counter_values()["executor.recompile"] == compiled + 1
        records = [r for r in monitor.MONITOR.step_records() if r.get("kind") == "moe_routing"]
        gauges = {name: monitor.MONITOR.gauge(name).value for name in ("moe.held_rows_share", "moe.held_rows_passed_share")}
        return records, monitor.MONITOR.counter_values(), gauges
    finally:
        monitor.disable()
        monitor.reset()


def test_steps_through_train_loop_publish_the_share_of_the_rows_that_were_held():
    records, counted, gauges = _logged_routing()
    assert [r["pipeline_step"] for r in records] == [0, 2, 4]
    for r in records:
        assert r["dropped_tokens"] == 0 and len(r["held_rows_share"]) == 2
        assert all(0.0 < s < 1.0 for s in r["held_rows_share"])
    assert gauges["moe.held_rows_share"] == max(records[-1]["held_rows_share"])
    assert counted["lowering.attention_xla"] >= 2 and not counted.get("lowering.attention_block_sparse")


def test_steps_through_train_loop_publish_the_share_of_the_bound_that_the_row_passes_went_over():
    """Beside `held_rows_share` the record holds, per layer, the rows the two
    row operations' passes went over as a share of the bound's:
    ceil(Held / P) P / bound, from the `Held` the step fetched already and the
    op's own `held` attribute.  The tiny model has 512 (token, slot)
    assignments a layer and a bound of 512 rows, so P is 128 and the share a
    whole number of quarters, the first that covers the held rows."""
    records, counted, gauges = _logged_routing(steps=4)
    assignments = JOB["batch_per_chip"] * 2 * JOB["seq_len"] * TINY["num_experts_per_tok"]
    bound = moe_ops._held_rows_bound(assignments, TINY["num_experts"], TINY["num_routed_experts"])
    assert (assignments, bound, moe_ops._pass_rows(bound)) == (512, 512, 128) and len(records) == 2
    for r in records:
        held = [round(share * assignments) for share in r["held_rows_share"]]
        assert all(0 < h < bound for h in held)
        assert r["held_rows_passed_share"] == [-(-h // 128) * 128 / bound for h in held]
        assert all(passed >= h / bound for passed, h in zip(r["held_rows_passed_share"], held))
    assert gauges["moe.held_rows_passed_share"] == max(records[-1]["held_rows_passed_share"])
    # two layers, traced once for the step: each bound makes four passes of 128 at the most
    assert counted["lowering.held_row_passes"] % 8 == 0 and counted["lowering.held_row_passes"] >= 8
