"""Keye-VL-2.0-30B-A3B's parts and the whole, tiny on the CPU (ISSUE 56).

(a) `sparse_index`: every query holds min(topk, t + 1) keys, none after itself,
    equals broken by the lower index, over one chunk and over several chunks
    and bands; the threshold found by counting (`ops/sparse_index_kernels.py`,
    plain and the kernel interpreted) against a stable sort, the op's picks
    against a `lax.top_k` oracle's, word for word, and which form the platform
    and the shape choose; the picks as bits; the `infer=` rules, the planner
    rows, `analysis.verify`;
(b) `fused_attention(picks=)`: with topk >= the length the selected attention
    IS the causal one, to the last bit; its gradient is dense attention's under
    the same fixed mask; the splash kernels on block maps made from the picks
    (interpreted here) against the dense form, output and gradients, a block
    with no chosen pair skipped;
(c) `index_alignment` against the reference's function, value and gradients,
    over several chunks; nothing of it reaches the attention's operands; its
    gradients, made from dI with the products made again (ISSUE 59:
    `ops/index_alignment_kernels.py`, plain and the kernel interpreted),
    against `jax.grad` through `index_scores`; which form the platform and the
    shape choose; backward holds three small arrays and scales them; its
    TARGET's kernel (ISSUE 62: `ops/alignment_target_kernels.py`) interpreted
    against the plain form `attention_target`, where it fits, and which of the
    two the platform and the shape choose;
(d) the sectioned rotation (`mrope_section`) equals the plain one for equal
    streams, and does not for unequal ones;
(e) the held shares of this block's router add up to the uncut layer;
(f) a tiny `build_causal_lm` of two sparse-attention layers in float32 against
    the benchmark's reference (benchmark/models/keye.py) on seeded weights: both
    loss terms, logits, every stage, every parameter's gradient (the main
    weights' from L_LM alone, the indexer's from L_I alone); gradients with and
    without `recompute_scope` equal to the last bit, the choice KEPT and never
    made again; in bf16 within the benchmark's tolerances; and the faults the
    comparison has to refuse.

One compiled tiny model serves (f): `float32_run`.
"""
import functools
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
from benchmark.models import keye  # noqa: E402
from paddle_tpu import layers, monitor  # noqa: E402
from paddle_tpu.core import lowering  # noqa: E402
from paddle_tpu.core.lowering import LoweringContext  # noqa: E402
from paddle_tpu.core.registry import get_op_def  # noqa: E402
from paddle_tpu.models import transformer  # noqa: E402
from paddle_tpu.ops import alignment_target_kernels as atk  # noqa: E402
from paddle_tpu.ops import index_alignment_kernels as iak  # noqa: E402
from paddle_tpu.ops import sparse_index_kernels as sik  # noqa: E402
from paddle_tpu.ops import sparse_index_ops as sio  # noqa: E402


def lower(op_type, ins, attrs=None):
    """One op's lowering called as the interpreter calls it."""
    attrs = attrs or {}
    op = SimpleNamespace(type=op_type, attr=lambda n, d=None: attrs.get(n, d), input=lambda s: [], output=lambda s: [])
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


def indexer_operands(rng, rows, length, heads, width):
    return (rng.randn(rows, length, heads, width).astype("f4"), rng.randn(rows, length, 1, width).astype("f4"),
            rng.randn(rows, length, heads).astype("f4"))


def scores_float64(qi, ki, w):
    """I [rows, L, L] of the module's docstring in float64 numpy, every pair."""
    qi, ki, w = (np.asarray(t, "f8") for t in (qi, ki, w))
    products = np.einsum("bthd,bsd->bths", qi, ki[:, :, 0])
    return np.einsum("bths,bth->bts", np.maximum(products, 0.0), w) * qi.shape[2] ** -0.5 * qi.shape[3] ** -0.5


def top_keys(scores, topk):
    """bool [rows, L, L]: float64's choice, the lower index first among equals."""
    length = scores.shape[-1]
    causal = np.tril(np.ones((length, length), bool))
    order = np.argsort(-np.where(causal, scores, -np.inf), -1, kind="stable")
    want = np.zeros(scores.shape, bool)
    np.put_along_axis(want, order[..., :topk], True, -1)
    return want & causal


# -- (a) the choice --------------------------------------------------------------------------

@pytest.mark.parametrize("length,topk,heads,width", [(32, 8, 4, 16), (1024, 48, 2, 8), (4096, 96, 2, 8)],
                         ids=["one-chunk", "two-chunks", "two-bands-of-four-chunks"])
def test_every_query_holds_its_top_keys_none_after_itself(length, topk, heads, width):
    rng = np.random.RandomState(56)
    qi, ki, w = indexer_operands(rng, 2, length, heads, width)
    out = lower("sparse_index", {"QI": qi, "KI": ki, "W": w}, {"topk": topk})
    picks = keye.unpack(np.asarray(out["Picks"]), length)
    assert np.asarray(out["Picks"]).dtype == np.int32 and np.asarray(out["Picks"]).shape == (2, length, length // 32)
    held = np.minimum(topk, np.arange(length) + 1)
    assert (picks.sum(-1) == held).all()                                     # min(topk, t + 1) keys a query
    assert not (picks & ~np.tril(np.ones((length, length), bool))).any()     # none after itself
    want = top_keys(scores_float64(qi, ki, w), topk)
    differ = (picks != want).sum() / 2 / want.sum()
    assert differ < 2e-4, differ                                             # float32 against float64 at the threshold
    chunk, bands = sio.chunking(length)
    assert (chunk, len(bands)) == {32: (32, 1), 1024: (512, 1), 4096: (512, 2)}[length]
    stats = np.asarray(out["Stats"])
    assert stats[0] == 2 * held.sum() and stats[3] == 2 * length and stats[4] == 2 * sio.chunk_pairs(length)
    recent = sum((picks[r] & (np.arange(length)[None, :] > np.arange(length)[:, None] - topk)).sum() for r in range(2))
    assert stats[1] == recent and 0 < stats[2] <= stats[4]


def test_equal_scores_are_broken_by_the_lower_index():
    """A key's scores all zero (every ReLU shut) tie at 0: of the equals the
    lowest indices are held, as `lax.top_k` orders them."""
    length, topk = 32, 8
    qi = np.ones((1, length, 2, 4), "f4")
    ki = -np.ones((1, length, 1, 4), "f4")        # every product negative: I = 0 everywhere
    ki[0, 20:24] = 1.0                            # but four keys, which every later query holds first
    w = np.ones((1, length, 2), "f4")
    picks = keye.unpack(np.asarray(lower("sparse_index", {"QI": qi, "KI": ki, "W": w}, {"topk": topk})["Picks"]), length)[0]
    assert picks[31].nonzero()[0].tolist() == [0, 1, 2, 3, 20, 21, 22, 23]
    assert picks[21].nonzero()[0].tolist() == [0, 1, 2, 3, 4, 5, 20, 21]
    assert picks[12].nonzero()[0].tolist() == list(range(8)) and picks[5].nonzero()[0].tolist() == list(range(6))


def _rows_of(case, rng, rows, keys, topk):
    """float32 [rows, keys] scores of one kind of trouble."""
    x = rng.randn(rows, keys).astype("f4")
    if case == "a-whole-row-tied":
        x[:] = 0.0
        x[1] = -3.5
    elif case == "ties-straddle-the-threshold":     # a row's threshold value stands topk / 2 times before it and as often after
        for row in x:
            at = np.argsort(-row, kind="stable")
            row[at[topk // 2:topk + topk // 2]] = row[at[topk - 1]]
        x[0, rng.permutation(keys)[:keys // 2]] = x[0].max() + 1.0        # ... and above it, where nothing is counted
    elif case == "both-zeros":                      # -0.0 and +0.0 are one value: the lower INDEX is first, whichever zero
        x = np.where(rng.rand(rows, keys) < 0.5, np.float32(-0.0), np.float32(0.0))
        x[::2, ::5] = rng.randn(*x[::2, ::5].shape).astype("f4")
    elif case == "beyond-the-causal-edge":          # row r sees keys 0 .. 3 r + 2: fewer than topk in the first rows
        x = np.where(np.arange(keys)[None, :] <= 3 * np.arange(rows)[:, None] + 2, x, -np.inf).astype("f4")
    return x


@pytest.mark.parametrize("form", ["plain", "kernel"])
@pytest.mark.parametrize("rows,keys,topk", [(8, 256, 48), (16, 128, 127), (24, 1024, 200)],
                         ids=["48-of-256", "all-but-one-of-128", "200-of-1024"])
@pytest.mark.parametrize("case", ["no-ties", "a-whole-row-tied", "ties-straddle-the-threshold", "both-zeros",
                                  "beyond-the-causal-edge"])
def test_the_threshold_by_counting_is_a_stable_sorts(case, rows, keys, topk, form):
    """Both forms against numpy's stable sort of the row, descending: the value
    at place `topk` and that place's index (the kernel interpreted here)."""
    x = _rows_of(case, np.random.RandomState(57), rows, keys, topk)
    place = np.argsort(-x, -1, kind="stable")[:, topk - 1:topk]       # -x keeps -0.0 and +0.0 equal, as `>` and `==` do
    assert sik.fits(rows, keys)
    select = jax.jit(sik.kth_and_last, static_argnums=1) if form == "plain" else functools.partial(sik.select, interpret=True)
    kth, last = (np.asarray(t) for t in select(jnp.asarray(x), topk))
    assert kth.dtype == np.float32 and last.dtype == np.int32 and kth.shape == last.shape == (rows, 1)
    np.testing.assert_array_equal(last, place)
    np.testing.assert_array_equal(kth, np.take_along_axis(x, place, -1))     # equal as VALUES: either zero is the zero


def test_the_plain_form_takes_any_shape_and_the_kernel_whole_tiles():
    x = np.random.RandomState(3).randn(5, 37).astype("f4")
    kth, last = sik.kth_and_last(jnp.asarray(x), 36)
    place = np.argsort(-x, -1, kind="stable")[:, 35:36]
    np.testing.assert_array_equal(np.asarray(last), place)
    np.testing.assert_array_equal(np.asarray(kth), np.take_along_axis(x, place, -1))
    assert not sik.fits(5, 128) and not sik.fits(8, 37) and sik.fits(512, 16384) and not sik.fits(8, 2 ** 17)
    assert [sik._rows(512, keys) for keys in (4096, 8192, 16384, 65536)] == [128, 64, 32, 8]


def test_the_keys_are_whole_numbers_in_the_scores_order():
    x = np.array([-np.inf, -3.0e38, -1.5, -1e-30, -0.0, 0.0, 1e-30, 2.5, 3.0e38, np.inf], "f4")
    keys = np.asarray(sik.ordered(jnp.asarray(x)))
    assert keys.dtype == np.int32 and (np.diff(keys.astype("i8")) > 0).sum() == len(x) - 2 and keys[4] == keys[5]
    back = np.asarray(sik.score_of(jnp.asarray(keys)))
    np.testing.assert_array_equal(back, x)                 # -0.0 comes back as +0.0: equal as values
    assert not np.signbit(back[4])


def lowering_counters(lowered):
    """(what `lowered()` returns, the `lowering.` counters it moved)."""
    monitor.reset()
    monitor.enable()
    try:
        return lowered(), {k: v for k, v in monitor.get_monitor().counter_values().items() if k.startswith("lowering.")}
    finally:
        monitor.disable()
        monitor.reset()


def _top_k_oracle(masked, topk):
    """What `choose` read before it counted: the last column of `lax.top_k`."""
    values, indices = jax.lax.top_k(masked, topk)
    return values[:, -1:], indices[:, -1:]


@pytest.mark.parametrize("length,topk,heads,width", [(32, 8, 4, 16), (1024, 48, 2, 8), (4096, 96, 2, 8)],
                         ids=["one-chunk", "two-chunks", "two-bands-of-four-chunks"])
def test_the_picks_are_a_top_k_oracles_word_for_word(length, topk, heads, width, monkeypatch):
    rng = np.random.RandomState(57)
    qi, ki, w = indexer_operands(rng, 2, length, heads, width)
    ki[0, length // 2:length // 2 + 40] = ki[0, 3]          # equal keys score equal: ties at every query after them
    qi[1, :, :, :] = np.abs(qi[1])
    ki[1, ::3] = -np.abs(ki[1, ::3])                        # a third of a row's scores exactly 0
    mine, counted = lowering_counters(lambda: lower("sparse_index", {"QI": qi, "KI": ki, "W": w}, {"topk": topk}))
    assert counted["lowering.sparse_index_ops"] == 1 and counted["lowering.index_select_kernel_calls"] == 0    # the CPU
    monkeypatch.setattr(sik, "kth_and_last", _top_k_oracle)
    theirs = lower("sparse_index", {"QI": qi, "KI": ki, "W": w}, {"topk": topk})
    np.testing.assert_array_equal(np.asarray(mine["Picks"]), np.asarray(theirs["Picks"]))
    np.testing.assert_array_equal(np.asarray(mine["Stats"]), np.asarray(theirs["Stats"]))


def test_on_the_tpu_the_select_goes_to_the_kernel_where_the_chunks_are_whole_tiles(monkeypatch):
    """What `_sparse_index` hands `_select_row`, by the platform and the shape
    alone, and what `lowering.index_select_kernel_calls` counts."""
    def seen(qi, ki, w, topk, select):
        chosen.append(select)
        return jnp.zeros((qi.shape[0], qi.shape[0] // 32), jnp.int32), jnp.zeros((5,), jnp.int32)

    def selects(platform, length, topk):
        op = SimpleNamespace(type="sparse_index", attr=lambda n, d=None: {"topk": topk}.get(n, d))
        ctx = LoweringContext(jax.random.PRNGKey(0), platform=platform)
        ins = {k: [jnp.asarray(v)] for k, v in zip(("QI", "KI", "W"), indexer_operands(np.random.RandomState(0), 1, length, 2, 8))}
        _, counted = lowering_counters(lambda: get_op_def("sparse_index").lower(ctx, op, ins))
        return chosen[-1], counted["lowering.index_select_kernel_calls"]

    chosen = []
    monkeypatch.setattr(sio, "_select_row", seen)
    assert selects("tpu", 4096, 96) == (sik.select, 1)
    assert selects("tpu", 1024, 48) == (sik.select, 1)               # one band, two chunks of 512 x 1024 keys
    assert selects("cpu", 4096, 96) == (sik.kth_and_last, 0)
    assert selects("tpu", 96, 8) == (sik.kth_and_last, 0)            # one chunk of 96 keys: no whole tile
    assert selects("tpu", 1024, 1024) == (sik.kth_and_last, 0)       # nothing to select: every causal key is held


def test_the_picks_are_words_of_32_keys_bits():
    rng = np.random.RandomState(1)
    chosen = rng.rand(3, 5, 96) < 0.3
    packed = sio.pack_bits(jnp.asarray(chosen))
    assert packed.dtype == jnp.int32 and packed.shape == (3, 5, 3)
    np.testing.assert_array_equal(np.asarray(sio.unpack_bits(packed, 96)), chosen)
    np.testing.assert_array_equal(keye.unpack(np.asarray(packed), 96), chosen)
    word = np.asarray(packed).view(np.uint32)[0, 0, 1]
    assert [bool(word >> j & 1) for j in range(32)] == chosen[0, 0, 32:64].tolist()      # bit j of word w is key 32 w + j


def test_the_ops_have_infer_rules_planner_rows_and_pass_verify():
    from paddle_tpu.core import analysis, resource_plan

    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        qi, ki = layers.data("qi", [64, 2, 8]), layers.data("ki", [64, 1, 8])
        w, q, k = layers.data("w", [64, 2]), layers.data("q", [4, 64, 16]), layers.data("k", [2, 64, 16])
        picks = layers.sparse_index(qi, ki, w, topk=16)
        handed = {}
        out = layers.fused_attention(q, k, k, causal=True, picks=picks, picks_topk=16, keep=handed)
        term = layers.index_alignment(qi, ki, w, picks, q, k, handed["lse"])
        still = layers.stop_gradient(out)
        with pytest.raises(analysis.ShapeInferenceError, match="ONE key a token"):
            layers.sparse_index(qi, layers.data("ki2", [64, 2, 8]), w, topk=16)
        with pytest.raises(analysis.ShapeInferenceError, match="words of 32 keys"):
            layers.sparse_index(layers.data("qi3", [48, 2, 8]), layers.data("ki3", [48, 1, 8]), layers.data("w3", [48, 2]), 4)
        with pytest.raises(analysis.ShapeInferenceError, match="Picks must be"):
            layers.fused_attention(q, k, k, picks=layers.data("p4", [64, 3], dtype="int32"))
    assert tuple(picks.shape) == (-1, 64, 2) and picks.dtype == "int32"
    assert tuple(out.shape) == (-1, 4, 64, 16) and tuple(term.shape) == (1,) and tuple(still.shape) == tuple(out.shape)
    assert tuple(handed["lse"].shape) == (-1, 4, 64) and handed["lse"].dtype == "float32"
    block = main.global_block()
    env = resource_plan.ShapeEnv(main, {n: (3,) + tuple(block.var(n).shape[1:]) for n in ("qi", "ki", "w", "q", "k")})
    cost = {}
    for op in block.ops:       # the first of each type: the refused ones stand after them
        cost.setdefault(op.type, resource_plan.op_cost(op, block, env))
    triangle = 3 * 64 * 65 / 2
    assert cost["sparse_index"][0] == triangle * 2 * (2 * 8 + 3)
    assert cost["fused_attention"][0] == 2.0 * 3 * 4 * (16 + 16) * (16 * 17 // 2 + 48 * 16)       # the CHOSEN pairs
    assert cost["index_alignment"][0] == triangle * (4 * 2 * (2 * 8 + 3) + 4 * (2 * 16 + 4))      # the index scores four times
    assert cost["stop_gradient"][0] == 0.0


# -- (b) the selected attention ---------------------------------------------------------------

def attention_operands(rng, rows, heads, kv_heads, length, width, dtype="f4"):
    return tuple(jnp.asarray(rng.randn(rows, h, length, width), dtype) for h in (heads, kv_heads, kv_heads))


def dense_attention(q, k, v, allowed):
    group = q.shape[1] // k.shape[1]
    k, v = jnp.repeat(k, group, 1), jnp.repeat(v, group, 1)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k, preferred_element_type=jnp.float32) * q.shape[-1] ** -0.5
    p = jax.nn.softmax(jnp.where(allowed[:, None], s, -1e30), -1)
    return jnp.einsum("bhqk,bhkd->bhqd", p.astype(q.dtype), v, preferred_element_type=jnp.float32).astype(q.dtype)


def test_with_topk_at_least_the_length_the_selected_attention_is_the_causal_one_to_the_last_bit():
    rng = np.random.RandomState(2)
    length = 64
    qi, ki, w = indexer_operands(rng, 2, length, 2, 8)
    q, k, v = attention_operands(rng, 2, 4, 2, length, 16)
    for topk in (length, 4 * length):
        picks = lower("sparse_index", {"QI": qi, "KI": ki, "W": w}, {"topk": topk})["Picks"]
        np.testing.assert_array_equal(keye.unpack(np.asarray(picks), length),
                                      np.broadcast_to(np.tril(np.ones((length, length), bool)), (2, length, length)))
        selected = lower("fused_attention", {"Q": q, "K": k, "V": v, "Picks": picks}, {"causal": True})["Out"]
        causal = lower("fused_attention", {"Q": q, "K": k, "V": v}, {"causal": True})["Out"]
        np.testing.assert_array_equal(np.asarray(selected), np.asarray(causal))
        unmarked = lower("fused_attention", {"Q": q, "K": k, "V": v, "Picks": picks}, {})["Out"]     # the picks ARE the mask
        np.testing.assert_array_equal(np.asarray(unmarked), np.asarray(causal))


def test_the_selected_attentions_gradient_is_dense_attentions_under_the_same_fixed_mask():
    rng = np.random.RandomState(3)
    length = 64
    qi, ki, w = indexer_operands(rng, 2, length, 2, 8)
    q, k, v = attention_operands(rng, 2, 4, 2, length, 16)
    picks = lower("sparse_index", {"QI": qi, "KI": ki, "W": w}, {"topk": 8})["Picks"]
    allowed = jnp.asarray(keye.unpack(np.asarray(picks), length))
    weight = jnp.asarray(rng.randn(2, 4, length, 16), "f4")

    def selected(q, k, v):
        return jnp.sum(lower("fused_attention", {"Q": q, "K": k, "V": v, "Picks": picks}, {"causal": True})["Out"] * weight)

    def dense(q, k, v):
        return jnp.sum(dense_attention(q, k, v, allowed) * weight)

    agree(selected(q, k, v), dense(q, k, v), 1e-6)
    for mine, theirs in zip(jax.grad(selected, (0, 1, 2))(q, k, v), jax.grad(dense, (0, 1, 2))(q, k, v)):
        agree(mine, theirs, 1e-5)
    # a key that no query holds has no weight and takes no gradient: rows 48 on hold keys among 0..47 and their own
    outside = ~np.asarray(allowed).any(axis=(0, 1))
    if outside.any():
        assert not np.asarray(jax.grad(selected, 1)(q, k, v))[:, :, outside].any()


def test_the_kernels_under_block_maps_made_from_the_picks_are_the_dense_form():
    """The TPU's path (`ops/masked_attention.py: selected_attention`), its
    kernels interpreted: output and the three gradients against the dense form
    in bf16, with a block of the grid that holds no chosen pair (skipped: its
    `block_mask` is 0) and the causal rule laid over picks that break it."""
    from paddle_tpu.ops import masked_attention as ma

    rng = np.random.RandomState(4)
    rows, heads, kv_heads, length, width = 1, 4, 2, 512, 128
    q, k, v = attention_operands(rng, rows, heads, kv_heads, length, width, jnp.bfloat16)
    allowed = (rng.rand(rows, length, length) < 0.3) | np.eye(length, dtype=bool)
    allowed[:, 256:, :128] = False                                   # a [256, 128] block of the grid with no pair
    picks = sio.pack_bits(jnp.asarray(allowed))                      # ... and pairs ABOVE the diagonal, which `causal` cuts
    causal = allowed & np.tril(np.ones((length, length), bool))
    weight = jnp.asarray(rng.randn(rows, heads, length, width), "f4")
    assert ma.selected_block(length) == 512 and ma.selected_block(16384) == 1024 and ma.selected_block(100) is None

    def kernels(q, k, v):
        out, lse = ma.selected_attention(q, k, v, picks, width ** -0.5, causal=True, interpret=True)
        return jnp.sum(out.astype(jnp.float32) * weight) + jnp.sum(lse * lse_weight), (out, lse)

    def dense(q, k, v):
        out = dense_attention(q, k, v, jnp.asarray(causal))
        s = jnp.einsum("bhqd,bhkd->bhqk", q, jnp.repeat(k, heads // kv_heads, 1), preferred_element_type=jnp.float32)
        lse = jax.nn.logsumexp(jnp.where(jnp.asarray(causal)[:, None], s * width ** -0.5, -jnp.inf), -1)
        return jnp.sum(out.astype(jnp.float32) * weight) + jnp.sum(lse * lse_weight), (out, lse)

    lse_weight = jnp.asarray(rng.randn(rows, heads, length), "f4")       # the log-sum-exp is an output: its cotangent counts
    (_, (mine, mine_lse)), grads = jax.value_and_grad(kernels, (0, 1, 2), has_aux=True)(q, k, v)
    (_, (theirs, their_lse)), want = jax.value_and_grad(dense, (0, 1, 2), has_aux=True)(q, k, v)
    agree(mine.astype(jnp.float32), theirs.astype(jnp.float32), 2e-2)
    agree(mine_lse, their_lse, 5e-3)
    for g, t in zip(grads, want):
        agree(g.astype(jnp.float32), t.astype(jnp.float32), 2e-2)
    unruled, _ = ma.selected_attention(q, k, v, picks, width ** -0.5, causal=False, interpret=True)
    agree(unruled.astype(jnp.float32), dense_attention(q, k, v, jnp.asarray(allowed)).astype(jnp.float32), 2e-2)


def test_the_path_is_the_kernels_where_their_conditions_hold_and_xlas_elsewhere():
    from paddle_tpu.ops.nn_ops import _attention_path

    def path(length=16384, dtype=jnp.bfloat16, width=128, platform="tpu", **more):
        q = jax.ShapeDtypeStruct((1, 32, length, width), dtype)
        k = jax.ShapeDtypeStruct((1, 4, more.pop("keys", length), width), dtype)
        return _attention_path(platform, None, q, k, None, True, False, "bhld", width, None, **more)

    assert path(picked=True) == "selected" and path() == "block_causal"
    assert path(picked=True, platform="cpu") == path(picked=True, dtype=jnp.float32) == "xla"
    assert path(picked=True, length=16384 + 32) == path(picked=True, keys=8192) == path(picked=True, width=96) == "xla"


# -- (c) the alignment term --------------------------------------------------------------------

@pytest.mark.parametrize("length,topk", [(32, 8), (1024, 48)], ids=["one-chunk", "two-chunks"])
def test_the_alignment_term_and_its_gradients_are_the_references(length, topk):
    rng = np.random.RandomState(5)
    rows, heads, kv_heads, width = 2, 4, 2, 16
    qi, ki, w = indexer_operands(rng, rows, length, 2, 8)
    q, k, v = attention_operands(rng, rows, heads, kv_heads, length, width)
    picks = lower("sparse_index", {"QI": qi, "KI": ki, "W": w}, {"topk": topk})["Picks"]
    allowed = jnp.asarray(keye.unpack(np.asarray(picks), length))
    lse = lower("fused_attention", {"Q": q, "K": k, "V": v, "Picks": picks}, {"causal": True})["Lse"]
    assert lse.shape == (rows, heads, length) and lse.dtype == jnp.float32

    def mine(qi, ki, w, q, k, lse=lse):
        out = lower("index_alignment", {"QI": qi, "KI": ki, "W": w, "Picks": picks, "Q": q, "K": k, "Lse": lse})
        return out["Out"][0], out["Rows"]

    def theirs(qi, ki, w, q, k):
        terms = [keye.sparse_attention(q[r], k[r], jnp.zeros_like(k[r]), qi[r].transpose(1, 0, 2), ki[r, :, 0],
                                       w[r] * 2 ** -0.5 * 8 ** -0.5, topk, picks=allowed[r])[1] / length for r in range(rows)]
        return jnp.mean(jnp.stack(terms)), jnp.stack(terms)

    operands = tuple(jnp.asarray(t) for t in (qi, ki, w)) + (q, k)
    (value, each), grads = jax.value_and_grad(mine, (0, 1, 2, 3, 4), has_aux=True)(*operands)
    (want, want_each), want_grads = jax.value_and_grad(theirs, (0, 1, 2, 3, 4), has_aux=True)(*operands)
    agree(value, want, 2e-5)
    agree(each, want_each, 2e-5)
    assert float(value) > 0
    for g, t in zip(grads[:3], want_grads[:3]):
        agree(g, t, 5e-5)
    assert not np.asarray(grads[3]).any() and not np.asarray(grads[4]).any()        # the target is a constant
    assert not np.asarray(want_grads[3]).any() and not np.asarray(want_grads[4]).any()
    # the forward pass alone (a `for_test` clone) is the same number; the log-sum-exp only steadies the op's own softmax:
    # handed another (every row's off by 3), the term is the same
    agree(jax.jit(lambda *o: mine(*o)[0])(*operands), value, 1e-6)
    agree(mine(*operands, lse=lse + 3.0)[0], value, 1e-5)


def by_autodiff(qi, ki, w, q, k, lse, picks, scale):
    """A row's mean term as the op chunks it, every chunk `chunk_divergence`
    on a constant target, for `jax.grad`: what `_alignment_row` took `jax.vjp`
    of until PR 59."""
    length = qi.shape[0]
    chunk, bands = sio.chunking(length)
    total = 0.0
    for lo, hi in bands:
        for start in range(lo, hi, chunk):
            allowed = sio.unpack_bits(picks[start:start + chunk, :hi // 32], hi)
            target = jax.lax.stop_gradient(sio.attention_target(q[:, start:start + chunk], k[:, :hi], lse[:, start:start + chunk],
                                                                allowed, scale))
            total = total + sio.chunk_divergence(qi[start:start + chunk], ki[:hi], w[start:start + chunk], target, allowed)
    return total / length


@pytest.mark.parametrize("length,topk", [(32, 8), (1024, 48), (4096, 96), (96, 16)],
                         ids=["one-chunk", "two-chunks", "two-bands-of-four-chunks", "a-band-of-no-whole-tiles"])
def test_the_ops_gradients_made_from_d_scores_are_jax_grads_of_the_plain_term(length, topk):
    """The rule that makes the products again (`index_alignment_kernels.
    gradients_plain` on the CPU) against `jax.grad` through `index_scores`."""
    rng = np.random.RandomState(59)
    heads, kv_heads, width, index_heads, index_width = 4, 2, 16, 2, 8
    qi, ki, w = (jnp.asarray(t[0]) for t in indexer_operands(rng, 1, length, index_heads, index_width))
    q, k, v = attention_operands(rng, 1, heads, kv_heads, length, width)
    picks = lower("sparse_index", {"QI": qi[None], "KI": ki[None], "W": w[None]}, {"topk": topk})["Picks"]
    lse = lower("fused_attention", {"Q": q, "K": k, "V": v, "Picks": picks}, {"causal": True})["Lse"]

    def mine(qi, ki, w):
        return lower("index_alignment", {"QI": qi[None], "KI": ki[None], "W": w[None], "Picks": picks, "Q": q, "K": k, "Lse": lse})["Out"][0]

    def theirs(qi, ki, w):
        scaled = sio.scaled_weights(w, index_heads, index_width)
        return by_autodiff(qi, ki[:, 0], scaled, q[0], k[0], lse[0], picks[0], width ** -0.5)

    value, grads = jax.value_and_grad(mine, (0, 1, 2))(qi, ki, w)
    want, want_grads = jax.jit(jax.value_and_grad(theirs, (0, 1, 2)))(qi, ki, w)
    agree(value, want, 1e-6)
    for g, t in zip(grads, want_grads):
        assert np.abs(np.asarray(t)).max() > 0
        agree(g, t, 5e-5)


def chunk_operands(rng, chunk, keys, heads=2, width=8):
    qi, ki, w = rng.randn(chunk, heads, width), rng.randn(keys, width), rng.randn(chunk, heads) * heads ** -0.5 * width ** -0.5
    allowed = np.arange(keys) <= keys - chunk + np.arange(chunk)[:, None]
    target = np.where(allowed, rng.exponential(size=(chunk, keys)), 0.0)
    return [jnp.asarray(t, "f4") for t in (qi, ki, w)], target, allowed


@pytest.mark.parametrize("case", ["every-causal-key-held", "no-held-key-but-the-diagonal", "a-target-of-zero-on-allowed-keys",
                                  "keys-of-no-whole-tile"])
def test_a_chunks_term_and_gradients_are_jax_value_and_grads(case):
    rng = np.random.RandomState(60)
    chunk, keys = (24, 72) if case == "keys-of-no-whole-tile" else (32, 96)
    operands, target, allowed = chunk_operands(rng, chunk, keys)
    if case == "no-held-key-but-the-diagonal":        # half the rows: the target is the diagonal's alone, others are allowed
        own = np.arange(keys) == keys - chunk + np.arange(chunk)[:, None]
        target = np.where((np.arange(chunk) % 2 == 0)[:, None], own * 1.0, target)
        allowed = allowed & ((np.arange(chunk) % 4 != 0)[:, None] | own)      # ... and a quarter allow the diagonal alone
    if case == "a-target-of-zero-on-allowed-keys":
        target = np.where(rng.rand(chunk, keys) < 0.4, 0.0, target)
        target[np.arange(chunk), keys - chunk + np.arange(chunk)] += 0.1
    target = jnp.asarray(target / target.sum(-1, keepdims=True), "f4")
    allowed = jnp.asarray(allowed)
    value, d_qi, d_ki, d_w = jax.jit(sio.chunk_divergence_and_gradients)(*operands, target, allowed)
    want, (want_qi, want_ki, want_w) = jax.jit(jax.value_and_grad(
        lambda *o: sio.chunk_divergence(*o, target, allowed), (0, 1, 2)))(*operands)
    assert float(want) > 0 and all(np.isfinite(np.asarray(g)).all() for g in (d_qi, d_ki, d_w))
    agree(value, want, 1e-6)
    agree(d_w, want_w, 1e-6)
    agree(d_qi, want_qi, 5e-5)
    agree(d_ki, want_ki, 5e-5)
    if case == "no-held-key-but-the-diagonal":        # r is 1 where one key is allowed and the target is 1 there: no gradient
        alone = np.arange(chunk) % 4 == 0
        assert not np.asarray(d_qi)[alone].any() and not np.asarray(d_w)[alone].any()


@pytest.mark.parametrize("dtype,tol", [("float32", 2e-5), ("bfloat16", 4e-3)])    # the kernel rounds G to bf16, as the chip does
@pytest.mark.parametrize("keys", [2048, 4096])
def test_the_gradients_kernel_interpreted_is_the_plain_form(keys, dtype, tol):
    """`index_alignment_kernels.gradients` at a chunk of the cell's shape, 512
    queries of 16 heads of 64 against two and four blocks of keys, with a dI
    that is zero on most pairs as the loss's is."""
    rng = np.random.RandomState(61)
    chunk, heads, width = 512, 16, 64
    assert iak.fits(chunk, keys, heads, width) and iak._block(keys) == 1024
    qi, ki = jnp.asarray(rng.randn(chunk, heads, width), dtype), jnp.asarray(rng.randn(keys, width), dtype)
    w = jnp.asarray(rng.randn(chunk, heads) / 32, "f4")
    d_scores = jnp.asarray(rng.randn(chunk, keys) * (rng.rand(chunk, keys) < 0.25), "f4")
    got = iak.gradients(qi, ki, w, d_scores, interpret=True)
    want = jax.jit(iak.gradients_plain)(qi, ki, w, d_scores)
    for g, t in zip(got, want):
        assert g.shape == t.shape and g.dtype == t.dtype == jnp.float32
        agree(g, t, tol)


def test_the_gradients_kernel_takes_whole_tiles_of_no_more_rows_than_a_tile_holds():
    assert iak.fits(512, 2048, 16, 64) and iak.fits(512, 16384, 16, 64) and iak.fits(128, 128, 2, 64) and iak.fits(32, 256, 16, 8)
    assert not iak.fits(96, 96, 16, 8)             # keys that are no whole tile
    assert not iak.fits(1024, 1024, 16, 8)         # more rows than a head's tile in VMEM holds
    assert not iak.fits(512, 2048, 3, 64)          # half of 128 lanes without a head
    assert not iak.fits(512, 2048, 4, 48)          # a head across two tiles of lanes
    assert [iak._block(keys) for keys in (2048, 4096, 1536, 384)] == [1024, 1024, 512, 128]


def forms_handed_to_a_row(monkeypatch, platform, length, index_heads=2, index_width=8, heads=2, kv_heads=1, width=8):
    """((gradients, target_kernel) that `_index_alignment` hands `_alignment_row` where the op is differentiated, the
    `lowering.` counters that lowering moved)."""
    def seen(qi, ki, w, q, k, lse, picks, scale, gradients, target_kernel=None):
        chosen.append((gradients, target_kernel))
        return (jnp.zeros((), jnp.float32),) + ((jnp.zeros_like(qi), jnp.zeros_like(ki), jnp.zeros_like(w)) if gradients else ())

    chosen = []
    monkeypatch.setattr(sio, "_alignment_row", seen)
    rng = np.random.RandomState(0)
    qi, ki, w = indexer_operands(rng, 1, length, index_heads, index_width)
    q, k, _ = attention_operands(rng, 1, heads, kv_heads, length, width)
    ins = {"QI": qi, "KI": ki, "W": w, "Q": q, "K": k, "Lse": np.zeros((1, heads, length), "f4"),
           "Picks": np.zeros((1, length, length // 32), "i4")}
    op = SimpleNamespace(type="index_alignment", attr=lambda n, d=None: d)
    ctx = LoweringContext(jax.random.PRNGKey(0), platform=platform)

    def run(*operands):
        return get_op_def("index_alignment").lower(ctx, op, {**{n: [jnp.asarray(v)] for n, v in ins.items()},
                                                             **dict(zip(("QI", "KI", "W"), ([o] for o in operands)))})["Out"][0]

    _, counted = lowering_counters(lambda: jax.grad(run, (0, 1, 2))(*(jnp.asarray(t) for t in (qi, ki, w))))
    assert counted["lowering.index_alignment_ops"] == 1
    return chosen[-1], counted


def test_on_the_tpu_the_gradients_go_to_the_kernel_where_the_chunks_are_whole_tiles(monkeypatch):
    """What `_index_alignment` hands `_alignment_row`, by the platform and the
    shape alone, and what `lowering.index_alignment_kernel_calls` counts."""
    def forms(platform, length, index_heads, index_width):
        (gradients, _), counted = forms_handed_to_a_row(monkeypatch, platform, length, index_heads, index_width)
        return gradients, counted["lowering.index_alignment_kernel_calls"]

    assert forms("tpu", 4096, 2, 64) == (iak.gradients, 1)
    assert forms("tpu", 1024, 16, 8) == (iak.gradients, 1)              # one band, two chunks of 512 x 1024 keys
    assert forms("cpu", 4096, 2, 64) == (iak.gradients_plain, 0)
    assert forms("tpu", 96, 2, 64) == (iak.gradients_plain, 0)          # one chunk of 96 keys: no whole tile
    assert forms("tpu", 1280, 2, 64) == (iak.gradients_plain, 0)        # one chunk of 1280 queries: over a tile's rows
    assert forms("tpu", 1024, 2, 8) == (iak.gradients_plain, 0)         # 16 of 128 lanes: no whole tile of qI


def target_operands(rng, heads, kv_heads, chunk, keys, first_query, dtype, held=None):
    """A chunk's (q, k, lse, allowed) of `attention_target`: the queries `first_query` on see keys up to themselves;
    with `held`, each holds that many of them at random (its own among them), else all.  `lse` is the allowed scores'
    log-sum-exp moved by a third, as another kernel's rounding moves it."""
    q, k = jnp.asarray(rng.randn(heads, chunk, 128), dtype), jnp.asarray(rng.randn(kv_heads, keys, 128), dtype)
    at = first_query + np.arange(chunk)[:, None]
    allowed = np.arange(keys) <= at
    if held is not None:
        drawn = np.where(allowed, rng.rand(chunk, keys), 2.0)
        allowed = (drawn < np.sort(drawn, axis=-1)[:, held - 1:held]) | (np.arange(keys) == at)
    s = jnp.einsum("ghcd,gkd->ghck", q.reshape(kv_heads, -1, chunk, 128), k, preferred_element_type=jnp.float32) * 128 ** -0.5
    lse = jax.nn.logsumexp(jnp.where(allowed, s, -jnp.inf), axis=-1).reshape(heads, chunk) + 1 / 3
    return q, k, lse, jnp.asarray(allowed)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("case,keys,heads,kv_heads,first_query,held", [
    ("rows-that-hold-one-key", 2048, 8, 1, 1536, 1),
    ("every-key-up-to-the-diagonal", 2048, 8, 1, 1536, None),
    ("a-band-wider-than-its-diagonal", 4096, 8, 1, 2048, 200),
    ("two-groups-of-eight", 2048, 16, 2, 0, 64)])
def test_the_target_kernel_interpreted_is_the_plain_form(case, keys, heads, kv_heads, first_query, held, dtype):
    """`alignment_target_kernels.target` at a chunk of the cell's shape, 512
    queries of groups of 8 heads of 128 against two and four thousand keys: rows
    that hold ONE key (the target is 1 there), rows that hold every key up to
    the diagonal, a band's first chunk (its last blocks of keys hold no allowed
    pair: the kernel's sums pass zeros) and two key/value groups.  Float32
    differences at rounding's size, each row's target summing to 1."""
    q, k, lse, allowed = target_operands(np.random.RandomState(62), heads, kv_heads, 512, keys, first_query, dtype, held)
    assert atk.fits(512, keys, heads, kv_heads, 128)
    got = atk.target(q, k, lse, allowed, 128 ** -0.5, interpret=True)
    want = jax.jit(sio.attention_target, static_argnums=4)(q, k, lse, allowed, 128 ** -0.5)
    assert got.shape == want.shape == (512, keys) and got.dtype == want.dtype == jnp.float32
    agree(got, want, 1e-6)
    assert not np.asarray(got)[~np.asarray(allowed)].any()
    np.testing.assert_allclose(np.asarray(got).sum(-1), 1.0, atol=2e-6)
    if held == 1:
        assert np.array_equal(np.asarray(got)[np.asarray(allowed)], np.ones(512, "f4"))
    # the seam hands the kernel what it was given and nothing else
    through = sio.attention_target(q, k, lse, allowed, 128 ** -0.5, functools.partial(atk.target, interpret=True))
    assert np.array_equal(np.asarray(through), np.asarray(got))


@pytest.mark.parametrize("shape,fits", [
    *(((512, keys, 32, 4, 128), True) for keys in range(2048, 16385, 2048)),       # the cell's eight bands
    ((128, 256, 8, 8, 128), True), ((512, 2048, 1, 1, 128), True),                   # groups of one head (the one-head control's)
    ((96, 96, 8, 2, 128), False),          # a chunk that is no whole tile of the mask's bytes, keys that are no whole tile
    ((1024, 1024, 8, 2, 128), False),      # more rows than a call takes
    ((512, 2048, 8, 2, 64), False),        # a head of half a tile's lanes
    ((512, 2048, 64, 4, 128), False)],     # twice the heads whose chunk of q the kernel holds in VMEM
    ids=lambda v: "x".join(map(str, v)) if isinstance(v, tuple) else str(v))
def test_the_target_kernel_takes_whole_tiles_of_heads_128_lanes_wide(shape, fits):
    assert atk.fits(*shape) is fits


@pytest.mark.parametrize("platform,length,heads,kv_heads,width,kernel", [
    ("tpu", 4096, 8, 2, 128, True),
    ("tpu", 1024, 2, 2, 128, True),         # one band, two chunks of 512 x 1024 keys, groups of one head
    ("cpu", 4096, 8, 2, 128, False),
    ("tpu", 96, 8, 2, 128, False),          # one chunk of 96 keys: no whole tile
    ("tpu", 1280, 8, 2, 128, False),        # one chunk of 1280 queries: over a call's rows
    ("tpu", 4096, 8, 2, 64, False)])        # heads of half a tile's lanes
def test_on_the_tpu_the_target_goes_to_the_kernel_where_the_chunks_are_whole_tiles(platform, length, heads, kv_heads, width, kernel,
                                                                                    monkeypatch):
    """What `_index_alignment` hands `_alignment_row` for the target, by the
    platform and the attention's shapes alone (the indexer's decide the
    gradients' form, not this one), and what
    `lowering.alignment_target_kernel_calls` counts."""
    (gradients, target_kernel), counted = forms_handed_to_a_row(monkeypatch, platform, length, heads=heads, kv_heads=kv_heads, width=width)
    assert gradients is iak.gradients_plain and counted["lowering.index_alignment_kernel_calls"] == 0     # 8 of 128 lanes of qI
    assert counted["lowering.alignment_target_kernel_calls"] == (1 if kernel else 0)
    assert target_kernel is (atk.target if kernel else None)


def test_backward_holds_three_small_arrays_and_only_scales_them():
    """The op's forward rule makes the three gradients with the value; what
    backward holds is those three, of qI's, kI's and w's shapes, and no array
    of a query and a key; `_divergence_bwd` is a multiplication by the row's
    cotangent and nothing else."""
    rng = np.random.RandomState(62)
    rows, length = 2, 64
    qi, ki, w = (jnp.asarray(t) for t in indexer_operands(rng, rows, length, 2, 8))
    q, k, v = attention_operands(rng, rows, 4, 2, length, 16)
    picks = lower("sparse_index", {"QI": qi, "KI": ki, "W": w}, {"topk": 16})["Picks"]
    lse = lower("fused_attention", {"Q": q, "K": k, "V": v, "Picks": picks}, {"causal": True})["Lse"]

    def term(qi, ki, w):
        return sio._divergence(qi, ki, w, q, k, lse, picks, 0.25, iak.gradients_plain)

    ki = ki[:, :, 0]
    _, pull = jax.vjp(term, qi, ki, w)
    backward = jax.make_jaxpr(pull)(jnp.ones((rows,), jnp.float32))
    assert sorted(c.shape for c in backward.consts) == sorted([qi.shape, ki.shape, w.shape])
    assert set(primitives_of(backward.jaxpr)) <= {"mul", "reshape", "broadcast_in_dim", "convert_element_type", "pjit", "jit"}
    shapes = [v.aval.shape for eqn in backward.jaxpr.eqns for v in eqn.outvars]
    assert not [s for s in shapes if sum(d == length for d in s) > 1]


# -- (d) the sectioned rotation ------------------------------------------------------------------

def test_the_sectioned_rotation_is_the_plain_one_for_equal_streams_and_not_for_unequal_ones():
    rng = np.random.RandomState(6)
    length, theta, sections = 96, 1e7, [16, 24, 24]
    x = rng.randn(2, 3, length, 128).astype("f4")                               # (B, H, L, dh)
    positions = np.stack([np.arange(length), rng.randint(0, 16384, length)])
    plain = np.asarray(lower("rotary_embedding", {"X": x, "Positions": positions}, {"theta": theta})["Out"])
    # (a float32 angle at position 16383 is itself 1e-3 of a turn from float64's, in the op and in the reference alike)
    for row, tol in ((0, 1e-5), (1, 1e-3)):
        streams = jnp.asarray(np.stack([positions[row]] * 3))
        agree(keye.rotate_sections(jnp.asarray(x[row]), streams, theta, sections), plain[row], tol)
        agree(keye.rotate_sections(jnp.asarray(x[row]), streams, theta), plain[row], tol)            # one stream: the indexer's
    # an image token's streams differ (temporal, height, width): another rotation, in the sections' angles alone
    streams = np.stack([positions[0], positions[0] + 3, positions[0] + 7])
    other = np.asarray(keye.rotate_sections(jnp.asarray(x[0]), jnp.asarray(streams), theta, sections))
    same = np.isclose(other, plain[0], atol=1e-5).all(axis=(0, 1))
    assert same[:16].all() and same[64:80].all()                                # the temporal section's features, both halves
    assert not same[16:64].all() and not same[80:].all()
    assert sum(sections) == 64 and mf.read_json("benchmark/configs/keye-vl-2.0-30b-a3b.json")["rope_scaling"]["mrope_section"] == sections


# -- (e) the held shares ---------------------------------------------------------------------------

def test_the_eight_shares_of_a_layer_add_up_to_the_uncut_layer():
    """Eight chips hold 16 of 128 experts each behind THIS block's router
    (softmax over 128, top 8 renormalised over all eight, no shared expert):
    their parts, summed, are the uncut layer's output as the equations write it."""
    rng = np.random.RandomState(56)
    tokens, experts, k, d, f = 64, 128, 8, 16, 8
    x = rng.randn(tokens, d).astype("f4")
    router = rng.randn(d, experts).astype("f4") / 2
    gate, up = (rng.randn(experts, d, f).astype("f4") / 4 for _ in range(2))
    down = rng.randn(experts, f, d).astype("f4") / 4
    routed = lower("moe_router", {"X": x, "W": router}, {"top_k": k, "norm_topk_prob": True})

    def share(first, count):
        ins = {"X": x, "TopKProb": routed["TopKProb"], "TopKIndex": routed["TopKIndex"], "Load": routed["Load"],
               "WGate": gate[first:first + count], "WUp": up[first:first + count], "WDown": down[first:first + count]}
        return lower("moe_experts", ins, {"held": [first, count]})

    shares = [share(first, 16) for first in range(0, experts, 16)]
    assert sum(int(np.asarray(s["Held"])[0]) for s in shares) == tokens * k
    assert all(int(np.asarray(s["Dropped"])[0]) == 0 for s in shares)
    logits = x.astype("f8") @ router.astype("f8")
    probs = np.exp(logits - logits.max(-1, keepdims=True))
    probs /= probs.sum(-1, keepdims=True)
    chosen = np.argsort(-probs, -1)[:, :k]
    weights = np.take_along_axis(probs, chosen, -1)
    weights /= weights.sum(-1, keepdims=True)
    want = np.zeros((tokens, d))
    for t in range(tokens):
        for e, g_e in zip(chosen[t], weights[t]):
            h = x[t].astype("f8") @ gate[e]
            want[t] += g_e * ((h / (1 + np.exp(-h)) * (x[t].astype("f8") @ up[e])) @ down[e])
    agree(sum(np.asarray(s["Out"], "f8") for s in shares), want, tol=1e-5)
    assert np.abs(np.asarray(shares[0]["Out"], "f8") - want).max() > 0.1 * np.abs(want).max()     # one share is not the layer


# -- (f) the whole model ---------------------------------------------------------------------------

TINY = dict(hidden_size=64, num_attention_heads=4, num_key_value_heads=2, head_dim=16, moe_intermediate_size=32,
            num_experts=2, num_routed_experts=8, experts_held_first=2, num_experts_per_tok=2, vocab_size=96,
            num_hidden_layers=2, rope_scaling=dict(mrope_section=[2, 3, 3]),
            sa_config=dict(indexer_head_dim=16, indexer_num_heads=4, indexer_num_kv_heads=1, kv_chunk_size=512,
                           q_chunk_size=512, topk=8))
JOB = dict(seq_len=32, batch_per_chip=4)


@pytest.fixture(scope="module", autouse=True)
def every_position_is_sampled():
    from benchmark.models import lfm2

    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(lfm2, "LOGIT_SAMPLE", 32)
        patch.setattr(lfm2, "ATTENTION_SAMPLE", 32)
        yield


def tiny_model(dtype, **job):
    from paddle_tpu.core import unique_name

    cfg = dict(mf.read_json("benchmark/configs/keye-vl-2.0-30b-a3b.json"), compute_dtype=dtype, **TINY)
    job = dict(mf.read_json("benchmark/traffic/train-dsa-s16384.json"), **JOB, **job)
    with unique_name.guard():
        main, startup, feeds, loss, names = keye.build(cfg, job)
    main.random_seed = startup.random_seed = 3
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.TPUPlace(0))
    exe.run(startup, scope=scope)
    return cfg, job, main, loss, names, scope, exe


def params_of(main, scope):
    return {p.name: np.asarray(scope.find_var(p.name)) for p in main.all_parameters()}


def reference_of(cfg, params, rows):
    return [np.asarray(w) for w in jax.jit(lambda p, b: keye.reference(p, b, cfg))(params, rows)]


def one_step(main, loss, scope, exe, batch):
    """(the step's loss, Adam's first moments, the logged step's `sparse_index`
    record, the trace's `lowering.` counters) of one step through `train_loop`."""
    losses = []
    monitor.reset()
    monitor.enable()
    try:
        fluid.train_loop(exe, main, iter([batch]), [loss], scope=scope, log_period=1,
                         on_logged=lambda i, vals: losses.append(float(np.asarray(vals[0]).reshape(-1)[0])))
        records = [r for r in monitor.get_monitor().step_records() if r.get("kind") == "sparse_index"]
        counters = {k: v for k, v in monitor.get_monitor().counter_values().items() if k.startswith("lowering.") and v}
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
        rows = keye.make_batch(np.random.RandomState(3), cfg, job, 8)
        got = exe.run(main.clone(for_test=True), feed=rows, fetch_list=list(names), scope=scope)
        before = params_of(main, scope)
        want = reference_of(cfg, before, rows)
        batch = keye.make_batch(np.random.RandomState(4), cfg, job, 4)

        def term(which):
            return jax.jit(jax.value_and_grad(lambda p: keye.reference(p, batch, cfg)[which]))(before)

        (ref_loss, ref_grads), (_, lm_grads), (_, index_grads) = term(0), term(2), term(3)
        step_loss, moments, records, counters = one_step(main, loss, scope, exe, batch)
        after = params_of(main, scope)
        _, _, plain_main, plain_loss, _, plain_scope, plain_exe = tiny_model("float32", recompute_layers=False)
        plain = one_step(plain_main, plain_loss, plain_scope, plain_exe, batch)
        ops = [op.type for op in main.global_block().ops]
    as_numpy = lambda grads: {k: np.asarray(v) for k, v in grads.items()}   # noqa: E731
    return SimpleNamespace(cfg=cfg, job=job, main=main, loss=loss, got=got, want=want, ops=ops, before=before, after=after,
                           moments=moments, records=records, counters=counters, plain=plain,
                           plain_segments=[op.attrs.get("recompute_segment") for op in plain_main.global_block().ops],
                           ref_loss=float(ref_loss), step_loss=step_loss, ref_grads=as_numpy(ref_grads),
                           lm_grads=as_numpy(lm_grads), index_grads=as_numpy(index_grads))


def test_float32_both_loss_terms_logits_and_every_stage_agree_with_the_reference(float32_run):
    found = keye.compare(float32_run.got, float32_run.want)
    assert found["left_out"] == found["routed_differently"] == found["routed_differently_above_margin"] == 0
    assert found["loss_error"] < 1e-5 and found["ce_error"] < 1e-5 and found["index_kl_error"] < 1e-5, found
    assert found["logit_error"] < 2e-5 and found["index_kl"] > 1e-3, found
    assert found["picks_differ"] == found["picks_gap"] == found["picks_miscounted"] == found["picks_after_query"] == 0
    assert max(found["router_prob_error"], found["experts_error"], found["attention_error"], found["alignment_error"],
               found["qk_error"]) < 2e-5, found
    assert found["attention_error_dense"] > 0.1                               # what the attention stage has to refuse
    assert found["reference_self_error"] < 1e-5 and keye.failed_limits(found) == []
    assert keye.reference_error(float32_run.got, float32_run.want) < 2e-5
    assert abs(float32_run.step_loss - float32_run.ref_loss) < 1e-5 * float32_run.ref_loss
    got = float32_run.got
    assert np.asarray(got[1]).shape == (32, 8, 96)                                             # the sampled logits
    assert np.asarray(got[4]).shape == (8, 32, 2) and np.asarray(got[5]).shape == (keye.STAGE_ROWS, 32, 64)
    staged = got[4 + 4 * 2:]
    assert [np.asarray(t).shape for t in staged[:9]] == [(1, 32, 4, 16), (1, 32, 1, 16), (1, 32, 4), (1, 32, 1),
                                                          (1, 4, 32, 16), (1, 2, 32, 16), (1, 2, 32, 16), (1, 4, 32, 16), (8,)]
    assert len(staged) == 18                                                                     # the first and the last layer


PARAMS = sorted(
    ["lm.tok_emb", "lm.head.w", "lm.final_norm.w"]
    + [f"lm.l{i}.{n}" for i in range(2) for n in ("ln1.w", "ln2.w")]
    + [f"lm.l{i}.attn.{n}.w" for i in range(2) for n in ("q", "k", "v", "out", "q_norm", "k_norm")]
    + [f"lm.l{i}.moe.{n}.w" for i in range(2) for n in ("router", "gate", "up", "down")])
INDEXER = sorted(f"lm.l{i}.attn.index.{n}" for i in range(2) for n in ("q.w", "k.w", "w.w", "k_norm.w", "k_norm.b"))


def test_the_tiny_model_has_these_layers_parameters_and_no_other(float32_run):
    r = float32_run
    assert sorted(r.before) == sorted(PARAMS + INDEXER)
    assert r.ops.count("fused_attention") == r.ops.count("sparse_index") == r.ops.count("index_alignment") == 2
    assert r.ops.count("stop_gradient") == 2 and r.ops.count("rotary_embedding") == 8 and r.ops.count("moe_experts") == 2
    shapes = {n: r.before[n].shape for n in ("lm.l0.attn.q.w", "lm.l0.attn.k.w", "lm.l0.attn.index.q.w",
                                            "lm.l0.attn.index.k.w", "lm.l0.attn.index.w.w", "lm.l0.attn.index.k_norm.b",
                                            "lm.l1.moe.gate.w", "lm.l1.moe.router.w")}
    assert shapes == {"lm.l0.attn.q.w": (64, 64), "lm.l0.attn.k.w": (64, 32), "lm.l0.attn.index.q.w": (64, 64),
                      "lm.l0.attn.index.k.w": (64, 16), "lm.l0.attn.index.w.w": (64, 4), "lm.l0.attn.index.k_norm.b": (16,),
                      "lm.l1.moe.gate.w": (2, 64, 32), "lm.l1.moe.router.w": (64, 8)}
    block = r.main.global_block()
    segments = [op.attrs.get("recompute_segment") for op in block.ops]
    assert sorted(set(segments) - {None}) == [1, 2] and set(r.plain_segments) == {None}
    # the alignment op stands AFTER its layer's segment: its forward pass makes its gradients too, once
    after = [(op.type, op.attrs.get("recompute_segment")) for op in block.ops if op.type in ("index_alignment", "sparse_index")]
    assert after == [("sparse_index", 1), ("index_alignment", None), ("sparse_index", 2), ("index_alignment", None)]
    # the indexer stands in the scope `sparse_index`, its three ops read the DETACHED input, and the attention takes the picks
    scoped = [op.type for op in block.ops if "sparse_index" in (op.attrs.get("op_namescope") or "")]
    assert scoped.count("sparse_index") == scoped.count("index_alignment") == scoped.count("stop_gradient") == 2
    assert scoped.count("layer_norm") == 2 and scoped.count("rotary_embedding") == 4 and "fused_attention" not in scoped
    first = next(op for op in block.ops if op.type == "fused_attention")
    choice = next(op for op in block.ops if op.type == "sparse_index")
    assert first.inputs["Picks"] == choice.outputs["Picks"] and first.attrs["picks_topk"] == 8 and first.attrs["causal"]


@pytest.mark.parametrize("name", PARAMS + INDEXER)
def test_float32_gradient_and_adam_step_agree_with_the_reference(float32_run, name):
    """Adam's first moment after one step is 0.1 x the gradient; the parameter
    moves by the warm-up's first rate.  The main weights' gradient is the
    language-model term's alone and the indexer's the alignment term's alone:
    the top-k passes none, the indexer reads its input detached and the target
    is a constant."""
    r = float32_run
    agree(r.moments[name] / (1 - 0.9), r.ref_grads[name], tol=2e-4)
    mine, other = (r.index_grads, r.lm_grads) if name in INDEXER else (r.lm_grads, r.index_grads)
    agree(r.moments[name] / (1 - 0.9), mine[name], tol=2e-4)
    assert not other[name].any()
    moved = np.abs(r.after[name] - r.before[name]).max()
    assert 0.5e-6 < moved < 4e-6, moved


@pytest.mark.parametrize("name", PARAMS + INDEXER)
def test_a_recomputed_layers_gradient_is_the_plain_layers_to_the_last_bit(float32_run, name):
    np.testing.assert_array_equal(float32_run.moments[name], float32_run.plain[1][name])


def test_a_recomputed_segment_publishes_what_the_plain_layer_publishes_and_counts_what_it_lowered(float32_run):
    r = float32_run
    plain_loss, _, plain_records, plain_counters = r.plain
    assert r.step_loss == plain_loss and len(r.records) == len(plain_records) == 1

    def said(record):
        return {k: v for k, v in record.items() if k not in ("ts", "step", "lane")}

    assert said(r.records[0]) == said(plain_records[0])
    record = r.records[0]
    held = int(np.minimum(8, np.arange(32) + 1).sum())
    assert record["picks"] == [4 * held] * 2 and record["queries"] == [4 * 32] * 2
    assert record["picks_per_query"] == [held / 32] * 2 and record["chunk_pairs_touched_share"] == [1.0, 1.0]
    assert all(0.3 < share <= 1.0 for share in record["recent_share"]) and all(kl > 0 for kl in record["index_kl"])
    assert r.counters["lowering.sparse_index_ops"] == r.counters["lowering.selected_attention_ops"] == 2
    assert r.counters["lowering.attention_xla"] == 2 and r.counters["lowering.recomputed_segments"] == 2
    assert plain_counters["lowering.sparse_index_ops"] == 2 and not plain_counters.get("lowering.recomputed_segments")
    # the CPU reports no memory limit, so the chip model's stands in and everything offered is kept: the picks among it
    assert r.counters["lowering.recomputed_kept_bytes"] == r.counters["lowering.recomputed_candidates_bytes"] > 0


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


def traced_step(run):
    from paddle_tpu.core import executor as ex

    scope = fluid.Scope()
    for v in run.main.global_block().vars.values():
        if v.persistable:
            scope.set_var(v.name, jax.ShapeDtypeStruct(tuple(v.shape), v.dtype))
    feeds = {n: jax.ShapeDtypeStruct((4, 32), np.int32) for n in keye.FEEDS}
    step = ex._CompiledStep(run.main, list(feeds), [run.loss.name], scope, feed_shapes={n: s.shape for n, s in feeds.items()})

    def as_shape(v):
        return jax.ShapeDtypeStruct(v.shape, v.dtype)

    return step.jfn.trace({n: as_shape(scope.find_var(n)) for n in step.rw_names},
                          {n: as_shape(scope.find_var(n)) for n in step.ro_names}, feeds, as_shape(jax.random.PRNGKey(0)))


def test_the_choice_is_offered_as_one_that_must_be_kept_and_is_kept_on_a_full_chip(float32_run):
    from paddle_tpu.core import resource_plan

    block = float32_run.main.global_block()
    ops = [op for op in block.ops if op.type not in ("feed", "fetch")]
    shapes = resource_plan.ShapeEnv(float32_run.main, {n: (4, 32) for n in keye.FEEDS})
    offered = lowering.kept_candidates(LoweringContext(jax.random.PRNGKey(0)), ops, shapes)
    picks = [v for v in offered if v.must]
    names = [op.outputs["Picks"][0] for op in ops if op.type == "sparse_index"]
    assert [v.name for v in picks] == names and all(v.nbytes == 4 * 32 * 1 * 4 for v in picks)
    # a budget that holds nothing keeps the choice and nothing else; one that holds everything, everything
    assert lowering.choose_kept(offered, 0) == picks
    assert lowering.choose_kept(offered, sum(v.nbytes for v in offered)) == offered
    assert not any(v.must for v in offered if v.name not in names)


@pytest.mark.parametrize("share", [0.5, 0.0], ids=["room-for-all", "a-full-chip"])
def test_the_forward_made_again_reads_the_kept_choice_and_never_chooses_again(float32_run, share, monkeypatch):
    """The traced step holds ONE select of the indexer a layer (its marker is
    the `cond` by which `sparse_index_kernels._search` asks whether a row's
    equals are all held; the chip's kernel holds it too): the second forward that
    backward makes has none, whether the chip has room for the other candidates
    or is full.  Without the rule that keeps the choice it would hold two a
    layer: the test's own control.  The router's `top_k`s (forward and again)
    are as many either way, and the indexer has none."""
    monkeypatch.setattr(lowering, "KEPT_SHARE", share)
    kept = primitives_of(traced_step(float32_run).jaxpr.jaxpr)
    monkeypatch.setattr(get_op_def("sparse_index"), "kept", None)
    again = primitives_of(traced_step(float32_run).jaxpr.jaxpr)
    assert again["cond"] - kept["cond"] == 2, (kept["cond"], again["cond"])
    assert again["top_k"] == kept["top_k"] == 4, (kept["top_k"], again["top_k"])       # two sparse layers' routers, twice


SOUND = dict(routed_differently_above_margin=0, left_out=58, tokens=1000, logit_error_left_out=0.037, router_choice_differs=0,
             router_prob_error=5.5e-6, experts_error=4.8e-3, index_kl_error=3e-5, picks_miscounted=0, picks_after_query=0,
             picks_differ=0.0, picks_gap=0.0, attention_error=4.1e-3, alignment_error=2e-7, qk_error=1.21e-2, ce_error=2.2e-6,
             logit_error=1.21e-2, reference_self_error=2e-7)


@pytest.mark.parametrize("reading,limit", [
    (dict(routed_differently_above_margin=1), "ROUTING_MARGIN"), (dict(left_out=265), "LEFT_OUT_MAX"),
    (dict(logit_error_left_out=float("nan")), "LEFT_OUT_LOGIT_MAX"), (dict(router_choice_differs=5), "ROUTER_TIE"),
    (dict(router_prob_error=1.3e-3), "ROUTER_RTOL"), (dict(experts_error=3.1e-2), "EXPERTS_RTOL"),
    (dict(index_kl_error=0.165), "INDEX_KL_RTOL"), (dict(picks_miscounted=1), "picks_count"),
    (dict(picks_after_query=1), "picks_count"), (dict(picks_differ=8.7e-4), "PICKS_DIFFER_MAX"),
    (dict(picks_gap=1.5e-3), "PICKS_GAP_MAX"), (dict(attention_error=5.8e-2), "ATTENTION_RTOL"),
    (dict(alignment_error=7.4e-6), "ALIGNMENT_RTOL"), (dict(qk_error=7.06e-2), "QK_RTOL"),
    (dict(logit_error=7.25e-2), "REFERENCE_RTOL"), (dict(ce_error=float("nan")), "REFERENCE_RTOL"),
    (dict(reference_self_error=2.17e-3), "REFERENCE_SELF_RTOL")])
def test_every_limit_refuses_the_least_faulty_reading_the_chip_gave(reading, limit):
    """`failed_limits` on the chip's sound readings (my chip runs, PR 56: the
    most seen of each) names nothing, and with the least reading a fault gave
    there (tools/chip_keye_controls.py) the limit that reading belongs to."""
    assert keye.failed_limits(SOUND) == []
    assert keye.failed_limits({**SOUND, **reading}) == [limit]


def test_bfloat16_agrees_within_the_benchmarks_tolerances():
    cfg, job, main, loss, names, scope, exe = tiny_model("bfloat16")
    rows = keye.make_batch(np.random.RandomState(3), cfg, job, 8)
    got = exe.run(main.clone(for_test=True), feed=rows, fetch_list=list(names), scope=scope)
    want = reference_of(cfg, params_of(main, scope), rows)
    found = keye.compare(got, want)
    assert found["tokens"] == 8 * 32 and found["routed_differently_above_margin"] == 0
    assert 1e-4 < found["logit_error"] < keye.REFERENCE_RTOL and found["ce_error"] < 1e-3
    assert found["router_prob_error"] < keye.ROUTER_RTOL and found["experts_error"] < keye.EXPERTS_RTOL
    assert found["picks_miscounted"] == found["picks_after_query"] == 0 and found["picks_gap"] <= keye.PICKS_GAP_MAX
    assert found["picks_differ"] <= keye.PICKS_DIFFER_MAX                       # on its OWN bf16 operands the choice is float64's
    assert found["attention_error"] < keye.ATTENTION_RTOL < found["attention_error_dense"]
    assert found["alignment_error"] < keye.ALIGNMENT_RTOL and found["qk_error"] < keye.QK_RTOL
    assert found["reference_self_error"] < keye.REFERENCE_SELF_RTOL


def patched_attr(op, attrs):
    return SimpleNamespace(type=op.type, attr=lambda n, d=None: attrs.get(n, op.attr(n, d)), input=op.input, output=op.output,
                           inputs=op.inputs, outputs=op.outputs, attrs=op.attrs)


@pytest.mark.parametrize("fault", ["half_the_picks", "a_key_after_the_query", "no_relu", "no_weights", "threshold_from_16_bits",
                                   "dense_attention", "target_of_one_head", "plain_rotation_of_other_streams"])
def test_the_reference_check_fails_on(fault, monkeypatch):
    """A program that computes something else under the same names is not
    correct: half the picks, a key after the query chosen, index scores without
    the ReLU or without the weights, a threshold from the scores' upper 16 bits,
    dense causal attention where the selected one belongs, an alignment target
    of one head, another rotation."""
    stage, limit = {"half_the_picks": ("picks_miscounted", 0), "a_key_after_the_query": ("picks_after_query", 0),
                    "no_relu": ("picks_differ", keye.PICKS_DIFFER_MAX), "no_weights": ("picks_differ", keye.PICKS_DIFFER_MAX),
                    "threshold_from_16_bits": ("picks_differ", keye.PICKS_DIFFER_MAX),
                    "dense_attention": ("attention_error", keye.ATTENTION_RTOL),
                    "target_of_one_head": ("alignment_error", keye.ALIGNMENT_RTOL),
                    "plain_rotation_of_other_streams": ("qk_error", keye.QK_RTOL)}[fault]
    if fault == "half_the_picks":
        real = get_op_def("sparse_index").lower
        monkeypatch.setattr(get_op_def("sparse_index"), "lower",
                            lambda ctx, op, ins: real(ctx, patched_attr(op, {"topk": 4}), ins))
    elif fault == "a_key_after_the_query":
        real = sio.choose

        def wrong(scores, first_query, topk, select):
            return real(scores, first_query, topk, select).at[:, -1].set(True)       # every query holds the last key

        monkeypatch.setattr(sio, "choose", wrong)
    elif fault == "threshold_from_16_bits":
        real = sio.choose

        def upper_half(masked, topk):       # bf16's order of the scores: the float32 threshold's place among them is lost
            bits = jax.lax.bitcast_convert_type(masked, jnp.int32) & jnp.int32(-65536)
            return sik.kth_and_last(jax.lax.bitcast_convert_type(bits, jnp.float32), topk)

        monkeypatch.setattr(sio, "choose", lambda scores, first_query, topk, select: real(scores, first_query, topk, upper_half))
    elif fault in ("no_relu", "no_weights"):
        def wrong(qi, ki, w):
            products = jnp.einsum("chd,kd->hck", qi, ki, preferred_element_type=jnp.float32)
            products = products if fault == "no_relu" else jax.nn.relu(products)
            return jnp.sum(products * (jnp.transpose(w)[:, :, None] if fault == "no_relu" else 1.0), axis=0)

        real_select = sio._select_row       # the choice alone is faulty: the alignment term keeps the sound scores
        monkeypatch.setattr(sio, "_select_row", lambda *a: _with(sio, "index_scores", wrong, real_select, *a))
    elif fault == "dense_attention":
        real = get_op_def("fused_attention").lower
        monkeypatch.setattr(get_op_def("fused_attention"), "lower", lambda ctx, op, ins: {
            **real(ctx, op, ins), "Out": real(ctx, op, {k: v for k, v in ins.items() if k != "Picks"})["Out"]})
    elif fault == "target_of_one_head":
        real = sio.attention_target
        monkeypatch.setattr(sio, "attention_target",
                            lambda q, k, lse, allowed, scale, *kernel: real(q[:1], k[:1], lse[:1], allowed, scale, *kernel))
    else:
        real = get_op_def("rotary_embedding").lower

        def wrong(ctx, op, ins):       # the main attention's queries and keys turn by twice the position
            if op.attr("layout", "bhld") == "bhld":
                return real(ctx, op, {**ins, "Positions": [2 * ins["Positions"][0]]})
            return real(ctx, op, ins)

        monkeypatch.setattr(get_op_def("rotary_embedding"), "lower", wrong)
    cfg, job, main, loss, names, scope, exe = tiny_model("float32")
    for p in main.all_parameters():        # N(0, 0.02) keeps every score near 0 and the softmax flat: draw q, k larger
        if p.name.endswith((".attn.q.w", ".attn.k.w", ".index.q.w", ".index.k.w", ".index.w.w")):
            scope.set_var(p.name, jnp.asarray(np.asarray(scope.find_var(p.name)) * 10.0))
    rows = keye.make_batch(np.random.RandomState(3), cfg, job, 8)
    got = exe.run(main.clone(for_test=True), feed=rows, fetch_list=list(names), scope=scope)
    want = reference_of(cfg, params_of(main, scope), rows)
    found = keye.compare(got, want)
    assert found[stage] > limit, found
    assert not keye.reference_error(got, want) <= keye.REFERENCE_RTOL


def _with(module, name, replacement, function, *args):
    """`function(*args)` with `module.name` replaced while it is traced."""
    real = getattr(module, name)
    setattr(module, name, replacement)
    try:
        return function(*args)
    finally:
        setattr(module, name, real)


def test_build_causal_lm_names_what_a_sparse_attention_layer_needs():
    with pytest.raises(ValueError, match="sparse_index=dict"):
        transformer.build_causal_lm(layer_types=["sparse_attention"], with_optimizer=False)
    with pytest.raises(ValueError, match="sparse_attention"):
        transformer.build_causal_lm(layer_types=["sparse_attentoin"], with_optimizer=False)
    with pytest.raises(ValueError, match="the chosen keys are the layer's mask"):
        transformer.build_causal_lm(layer_types=["sparse_attention"], sparse_index=dict(heads=2, head_dim=8, topk=4),
                                    attention_mask=("block_diffusion", 4), with_optimizer=False)
    with fluid.program_guard(fluid.Program(), fluid.Program()):
        with pytest.raises(ValueError, match="heads-major"):      # no per-head norm and no positions: the projections' layout
            transformer.multi_head_attention(layers.data("x", [32, 64]), 32, 64, 4, "a", use_fused_attention=True,
                                             sparse_index=dict(heads=2, head_dim=8, topk=4))
