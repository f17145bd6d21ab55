"""The held experts' way back to token order through `moe_kernels.token_sum`
with the slots no held expert owns left out (ISSUE 53), tiny and interpreted on
the CPU:

(a) the kernel's masked form against `sum_j rows[index[t, j]]` over the owned
    slots, and the unmasked call against what the parent `14276a2` lowered to;
(b) `_add_to_tokens` by the kernel against XLA's scatter-add, forward and both
    written transposes, with NaN in every row past the live ones;
(c) `_held_experts` whole, the rare branch taken and not, by either way back;
(d) the counter `lowering.held_token_sum_calls` and the rule that chooses.

tests/test_chip_compile.py compiles the same for a described v5e.
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

from paddle_tpu import monitor  # noqa: E402
from paddle_tpu.core.lowering import LoweringContext  # noqa: E402
from paddle_tpu.core.registry import get_op_def  # noqa: E402
from paddle_tpu.ops import moe_kernels, moe_ops  # noqa: E402


def held_routing(name, tokens, experts, k, held, seed=0):
    """TopKIndex [T, k] over `experts` of which 0 .. held - 1 are held: a uniform
    router, one under which held expert 1 has no row, one that sends every slot
    of every fourth token to held experts and no slot of the others, one that
    sends every slot of every token to held experts, one that sends 513 slots
    there, one that sends none."""
    rng = np.random.RandomState(seed)
    if name == "uniform":
        top_i = np.stack([rng.permutation(experts)[:k] for _ in range(tokens)])
    elif name == "an_empty_held_expert":
        top_i = np.stack([rng.permutation(np.delete(np.arange(experts), 1))[:k] for _ in range(tokens)])
    elif name == "k_owned_or_none":
        top_i = np.stack([rng.permutation(held)[:k] if t % 4 == 0 else held + rng.permutation(experts - held)[:k] for t in range(tokens)])
    elif name == "all_held":
        top_i = np.stack([rng.permutation(held)[:k] for _ in range(tokens)])
    elif name == "513_rows_held":
        top_i = held_routing("none_held", tokens, experts, k, held)
        top_i.reshape(-1)[rng.permutation(tokens * k)[:513]] = np.arange(513) % held
    else:
        assert name == "none_held"
        top_i = np.stack([held + rng.permutation(experts - held)[:k] for _ in range(tokens)])
    return top_i.astype("i4")


def the_way_back_of(top_i, held, bound):
    """What `_held_experts`' common pass hands `_add_to_tokens` for a choice
    `top_i` [T, k] with experts 0 .. held - 1 held and `bound` rows a pass, in
    numpy: (token [bound], XLA's target [bound], the kernel's (index, group)
    [T, k], live)."""
    tokens, k = top_i.shape
    local = np.where(top_i.reshape(-1) < held, top_i.reshape(-1), held)
    order = np.argsort(local, kind="stable")
    place = np.argsort(order).reshape(tokens, k)
    live = min(int((local < held).sum()), bound)
    order = np.concatenate([order, np.full(max(bound - order.size, 0), tokens * k)])[:bound]
    token = np.minimum(order // k, tokens - 1).astype("i4")
    owned = place < live
    return (jnp.asarray(token), jnp.asarray(np.where(np.arange(bound) < live, token, tokens).astype("i4")),
            (jnp.asarray(np.where(owned, place, -1).astype("i4")), jnp.asarray(np.where(owned, local.reshape(tokens, k), held).astype("i4"))),
            jnp.int32(live))


def close(got, want, dtype):
    """float32 rows to 1e-6 of the largest sum; bf16 rows to one bf16 ulp of the
    same float32 sum rounded once."""
    got, want = np.asarray(got, "f8"), np.asarray(want, "f8")
    assert got.shape == want.shape and np.isfinite(got).all()
    off = np.abs(got - want)
    if jnp.dtype(dtype) == jnp.float32:
        assert off.max() <= 1e-6 * max(np.abs(want).max(), 1e-12), off.max()
    else:
        assert (off <= 2.0 ** -7 * np.maximum(np.abs(want), 1e-3)).all(), off.max()


# -- (a) the kernel ---------------------------------------------------------------

#: name -> (tokens, slots a token, hidden, experts, held, rows given, router)
MASKED = {
    "uniform_k4": (256, 4, 128, 16, 4, 512, "uniform"),
    "uniform_k8_three_blocks": (384, 8, 256, 32, 8, 1536, "uniform"),
    "an_empty_held_expert": (256, 2, 128, 8, 4, 512, "an_empty_held_expert"),
    "k_owned_or_none": (256, 4, 128, 16, 4, 512, "k_owned_or_none"),
    "every_slot_owned_and_rows_to_spare": (128, 2, 128, 4, 4, 384, "all_held"),
    "no_slot_owned": (128, 2, 128, 8, 2, 128, "none_held"),
}


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("case", list(MASKED))
def test_the_masked_token_sum_is_the_sum_over_the_owned_slots(case, dtype):
    """`token_sum` interpreted, with slots marked unowned (`group == groups`, a
    negative `index`), against sum_j rows[index[t, j]] over the owned slots in
    float64: tokens with no owned row read zeros, a held expert with no row
    is no run, and the rows past the live ones' last tile, NaN here, are never
    copied."""
    tokens, k, d, experts, held, given, router = MASKED[case]
    assert moe_kernels.fits(tokens, d, k, dtype, held)
    top_i = held_routing(router, tokens, experts, k, held)
    _, _, (index, group), live = the_way_back_of(top_i, held, given)
    live = int(live)
    owned = np.asarray(index) >= 0
    per_token = owned.sum(1)
    assert {"uniform": 0 < owned.sum() < owned.size and per_token.min() == 0, "an_empty_held_expert": not (np.asarray(group) == 1).any(),
            "k_owned_or_none": set(per_token) == {0, k}, "all_held": owned.all(), "none_held": not owned.any()}[router]
    rng = np.random.RandomState(1)
    rows = rng.randn(given, d).astype("f4")
    rows[-(-live // moe_kernels.GRANULE) * moe_kernels.GRANULE:] = np.nan   # past the tile that holds the last live row
    rows[live:-(-live // moe_kernels.GRANULE) * moe_kernels.GRANULE] = 0    # the caller's part (`_zeros_from`)
    rows = jnp.asarray(rows, dtype)
    want = np.where(owned[:, :, None], np.asarray(rows, "f8")[np.maximum(np.asarray(index), 0)], 0).sum(1)
    got = moe_kernels.token_sum(rows, index, group, held, True)
    assert got.dtype == rows.dtype
    close(got, want, dtype)
    assert not np.asarray(got, "f8")[per_token == 0].any()


def test_a_marked_slot_is_in_no_run_and_its_place_meets_no_column():
    """`plan` on a choice with unowned slots: the runs are those of the owned
    slots alone (what `plan` gives for them with the others cut out), and every
    unowned slot's place is negative."""
    tokens, k, held = 128, 4, 4
    top_i = held_routing("uniform", tokens, 16, k, held)
    _, _, (index, group), live = the_way_back_of(top_i, held, 256)
    runs, place = moe_kernels.plan(index, group, held)
    owned = np.asarray(index) >= 0
    assert (np.asarray(place)[~owned] < 0).all() and (np.asarray(place)[owned] >= 0).all()
    first, tiles, used = np.asarray(runs)[0, 0, :held], np.asarray(runs)[0, 0, held:2 * held], int(np.asarray(runs)[0, 0, 2 * held])
    for e in range(held):
        mine = np.asarray(index)[np.asarray(group) == e]
        assert (first[e], tiles[e]) == (mine.min() // 8, (mine.max() // 8) - (mine.min() // 8) + 1)
    assert used == tiles.sum() and sorted(np.asarray(place)[owned]) == sorted(set(np.asarray(place)[owned]))   # a buffer row a slot
    assert int(live) == owned.sum()


#: sha256 of `token_sum`'s StableHLO for the TPU (the kernel's serialised body, which names this checkout's files,
#: stripped) with every slot owning a row, recorded at the parent `14276a2` by this test's own code: OLMoE's call, and
#: a small float32 one.  The masked form changed nothing an unmasked call lowers to, the counted bytes among it.
PARENTS_TOKEN_SUM = {
    ((131072, 2048), 16384, 8, 64, "bfloat16"): "a290ac007577bb4e7ddabaf86b3996032264aa3c04d63387e9cfa3ce60bb342a",
    ((1024, 128), 256, 4, 8, "float32"): "72375f8bfdab088df03c071d8303d2c02a4493d6969062da294f54921004f1a2",
}


@pytest.mark.parametrize("case", list(PARENTS_TOKEN_SUM), ids=["olmoe", "small_float32"])
def test_the_unmasked_token_sum_lowers_to_the_parents_text(case):
    rows, tokens, k, groups, dtype = case
    shape = jax.ShapeDtypeStruct
    lowered = jax.jit(lambda r, i, g: moe_kernels.token_sum(r, i, g, groups)).trace(
        shape(rows, dtype), shape((tokens, k), jnp.int32), shape((tokens, k), jnp.int32)).lower(lowering_platforms=("tpu",))
    text = re.sub(r'(\\22body\\22: \\22)[A-Za-z0-9+/=]+', r"\1", lowered.as_text())
    assert hashlib.sha256(text.encode()).hexdigest() == PARENTS_TOKEN_SUM[case]


@pytest.mark.parametrize("given,rows_counted", [(131072, 131072), (32768, 32768)])
def test_the_kernels_counted_bytes_are_the_rows_it_is_given_and_the_tokens_written(given, rows_counted):
    """`cost_estimate`: (R + T) d values, not T k + T: the held path gives a
    quarter of T k rows and the step's counted bytes do not grow by rows nobody
    reads; with every slot owning a row R is T k, OLMoE's count as it was."""
    tokens, k, d = 16384, 8, 2048
    shape = jax.ShapeDtypeStruct
    text = str(jax.make_jaxpr(lambda r, i, g: moe_kernels.token_sum(r, i, g, 16))(
        shape((given, d), jnp.bfloat16), shape((tokens, k), jnp.int32), shape((tokens, k), jnp.int32)))
    assert f"bytes_accessed={(rows_counted + tokens) * d * 2}" in text


# -- (b) the row operation ----------------------------------------------------------

@pytest.mark.parametrize("live,rows", [(0, 16), (1, 16), (5, 16), (8, 16), (13, 16), (16, 16), (40, 16), (3, 8)])
def test_zeros_from_zeroes_the_dead_rows_of_the_live_rows_last_tile_and_no_other(live, rows):
    x = np.arange(1, rows * 4 + 1, dtype="f4").reshape(rows, 4)
    got = np.asarray(jax.jit(moe_ops._zeros_from)(jnp.asarray(x), jnp.int32(live)))
    tile = moe_kernels.GRANULE
    end = min(-(-max(live, 1) // tile) * tile if live % tile else live + tile, rows)   # the tile that holds row `live`
    want = x.copy()
    want[min(live, rows):end] = 0
    assert (got == want).all()
    assert (got[:min(live, rows)] == x[:min(live, rows)]).all()


#: name -> (tokens, slots a token, hidden, experts, held, the pass's rows, router)
WAYS_BACK = {
    "uniform": (256, 4, 128, 16, 4, 512, "uniform"),
    "ends_on_a_tiles_edge_or_not_k8": (256, 8, 128, 32, 8, 1024, "uniform"),
    "the_bound_is_met": (128, 2, 128, 4, 4, 256, "all_held"),
    "past_the_bound": (256, 2, 128, 4, 4, 256, "all_held"),
    "none_held": (128, 2, 128, 8, 2, 128, "none_held"),
}


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("case", list(WAYS_BACK))
def test_add_to_tokens_by_the_kernel_is_xlas_scatter_add_and_so_are_both_transposes(case, dtype):
    """`_add_to_tokens` with the kernel (interpreted) against XLA's form on the
    same rows, NaN in every row past the live ones as the grouped kernels may
    leave them: the values, its written transpose (`_rows_of_tokens`, XLA's
    gather either way) through `jax.vjp`, and the transpose of
    `_rows_of_tokens`, which is `_add_to_tokens` again, by either form."""
    tokens, k, d, experts, held, bound, router = WAYS_BACK[case]
    top_i = held_routing(router, tokens, experts, k, held)
    token, xla_target, kernel_target, live = the_way_back_of(top_i, held, bound)
    assert {"the_bound_is_met": int(live) == bound, "past_the_bound": int(live) == bound < tokens * k,
            "none_held": int(live) == 0}.get(case, 0 < int(live) < bound)
    rng = np.random.RandomState(2)
    is_live = (np.arange(bound) < int(live))[:, None]
    rows = jnp.asarray(np.where(is_live, rng.randn(bound, d), np.nan), dtype)
    g_tokens, x = (jnp.asarray(rng.randn(tokens, d), dtype) for _ in range(2))
    g_rows = jnp.asarray(np.where(is_live, rng.randn(bound, d), np.nan), dtype)
    kernel = (held, True)

    def both(target, kernel):
        added, pull_rows = jax.vjp(lambda r: moe_ops._add_to_tokens(r, token, target, live, tokens, kernel), rows)
        gathered, pull_x = jax.vjp(lambda x: moe_ops._rows_of_tokens(x, token, target, live, tokens, kernel), x)
        return added, pull_rows(g_tokens)[0], gathered, pull_x(g_rows)[0]

    wanted, found = both(xla_target, None), both(kernel_target, kernel)
    exact = np.asarray(jnp.zeros((tokens + 1, d), jnp.float32).at[xla_target].add(jnp.where(is_live, rows, 0).astype(jnp.float32)))[:tokens]
    close(found[0], exact, dtype)
    close(found[3], np.asarray(jnp.zeros((tokens + 1, d), jnp.float32).at[xla_target].add(
        jnp.where(is_live, g_rows, 0).astype(jnp.float32)))[:tokens], dtype)
    for i in (1, 2):   # the gathers are the same instruction by either form
        assert (np.asarray(found[i], "f4") == np.asarray(wanted[i], "f4")).all()
    if dtype == "float32":   # XLA's form sums in the rows' dtype: in float32 the two forms are the same sum
        close(found[0], wanted[0], dtype)
        close(found[3], wanted[3], dtype)


# -- (c) the layer --------------------------------------------------------------------

def held_layer(top_i, load, held, platform=None, mesh=None):
    """The op's lowering as the interpreter calls it, experts 0 .. held - 1 held: every output by slot."""
    op = SimpleNamespace(type="moe_experts", attr=lambda n, default=None: {"held": [0, held]}.get(n, default))
    ctx = LoweringContext(jax.random.PRNGKey(0), platform=platform, mesh=mesh)

    def layer(x, top_p, w_gate, w_up, w_down):
        ins = {"X": [x], "TopKProb": [top_p], "TopKIndex": [jnp.asarray(top_i)], "Load": [jnp.asarray(load)],
               "WGate": [w_gate], "WUp": [w_up], "WDown": [w_down]}
        return get_op_def("moe_experts").lower(ctx, op, ins)
    return layer


def layer_operands(tokens, k, d, f, held, seed=3):
    rng = np.random.RandomState(seed)
    return (rng.randn(tokens, d).astype("f4"), rng.rand(tokens, k).astype("f4"), rng.randn(held, d, f).astype("f4") / 4,
            rng.randn(held, d, f).astype("f4") / 4, rng.randn(held, f, d).astype("f4") / 4)


@pytest.mark.parametrize("router,rare", [("uniform", False), ("an_empty_held_expert", False), ("none_held", False),
                                         ("k_owned_or_none", False), ("513_rows_held", True), ("all_held", True)])
def test_held_experts_by_the_kernel_is_held_experts_by_xla_with_the_rare_branch_taken_and_not(router, rare, monkeypatch):
    """One held layer in float32, 4 of 16 experts held at 256 tokens of 4 slots
    (a bound of 512 of 1024 assignments), output, `Held`, `Dropped` and the five
    gradients: the common pass's way back through the kernel (interpreted)
    against XLA's scatter-add (which tests/test_sdar.py holds to the plain
    golden).  Where more rows are held than the bound the rare branch adds the
    rest, by XLA's form under either."""
    tokens, experts, k, d, f, held = 256, 16, 4, 128, 16, 4
    top_i = held_routing(router, tokens, experts, k, held)
    load = np.bincount(top_i.reshape(-1), minlength=experts).astype("i4")
    assert moe_ops._held_rows_bound(tokens * k, held, experts) == 512
    assert (load[:held].sum() > 512) == rare
    args = layer_operands(tokens, k, d, f, held)
    weight = np.random.RandomState(4).randn(tokens, d).astype("f4")
    layer = held_layer(top_i, load, held)

    def out_and_gradients():
        out = layer(*args)
        return (out["Out"], out["Held"], out["Dropped"]) + jax.grad(lambda *a: jnp.sum(layer(*a)["Out"] * weight), range(5))(*args)

    wanted = out_and_gradients()
    monkeypatch.setattr(moe_ops, "_token_sum_path", lambda *a: "interpret")
    monitor.reset()
    monitor.enable()
    try:
        found = out_and_gradients()
        assert monitor.get_monitor().counter_values().get("lowering.held_token_sum_calls", 0) == 3   # a plain call, a differentiated one
    finally:
        monitor.disable()
        monitor.reset()
    assert int(found[1][0]) == load[:held].sum() and int(found[2][0]) == 0
    for got, want in zip(found, wanted):
        got, want = np.asarray(got, "f8"), np.asarray(want, "f8")
        assert np.abs(got - want).max() <= 1e-5 * max(np.abs(want).max(), 1e-12), (np.abs(got - want).max(), np.abs(want).max())


# -- (d) the counter and the rule -----------------------------------------------------

@pytest.mark.parametrize("platform,devices,d,dtype,calls", [
    ("tpu", None, 128, "float32", 2), ("tpu", 1, 256, "bfloat16", 2), ("cpu", None, 128, "float32", 0), (None, None, 128, "float32", 0),
    ("tpu", 4, 128, "float32", 0), ("tpu", None, 120, "float32", 0)])
def test_the_counter_says_which_held_layers_took_the_kernel(platform, devices, d, dtype, calls):
    """`lowering.held_token_sum_calls` counts, at trace time, the calls of
    `_add_to_tokens` that took the kernel: two a differentiated held layer on
    one TPU device (the common pass's, and the transpose of its gather; the
    rare path's keep XLA's form), one a plain call; none on a mesh, on the CPU
    or at a hidden size that is no whole number of lane tiles."""
    tokens, experts, k, f, held = 256, 16, 4, 16, 4
    top_i = held_routing("uniform", tokens, experts, k, held)
    load = np.bincount(top_i.reshape(-1), minlength=experts).astype("i4")
    lowered = held_layer(top_i, load, held, platform, None if devices is None else SimpleNamespace(size=devices, shape={"dp": 2, "tp": devices // 2}))
    x, *rest = layer_operands(tokens, k, d, f, held)
    x = jnp.asarray(x, dtype)

    def layer(x):
        return jnp.sum(lowered(x, *(jnp.asarray(a) for a in rest))["Out"].astype(jnp.float32))

    def counted(trace):
        monitor.reset()
        monitor.enable()
        try:
            text = str(trace())
            return monitor.get_monitor().counter_values().get("lowering.held_token_sum_calls", 0), text
        finally:
            monitor.disable()
            monitor.reset()

    found, text = counted(lambda: jax.make_jaxpr(jax.grad(layer))(x))   # traced, not run
    assert found == calls and (text.count("name=token_sum") > 0) == (calls > 0)
    assert counted(lambda: jax.make_jaxpr(layer)(x))[0] == calls // 2
    assert "lowering.held_token_sum_calls" in open(os.path.join(REPO, "docs", "observability.md")).read()


@pytest.mark.parametrize("cell,tokens,d,k,held", [("sdar", 16384, 2048, 8, 16), ("lfm2", 16384, 2048, 4, 8), ("kimi-linear", 4096, 2304, 8, 8),
                                                  ("sdars_clone", 8 * 8192, 2048, 8, 16)])
def test_the_three_held_cells_shapes_take_the_kernel_on_one_tpu(cell, tokens, d, k, held):
    """The rule is `_token_sum_path` over the HELD experts: what the three
    cells' steps (and SDAR's eight-row reference check) hand it fits, on one
    TPU device and nowhere else."""
    x = jax.ShapeDtypeStruct((tokens, d), jnp.bfloat16)
    assert moe_ops._token_sum_path("tpu", None, x, k, held) == "kernel"
    assert moe_ops._token_sum_path("tpu", SimpleNamespace(size=4), x, k, held) == "xla"
    assert moe_ops._token_sum_path("cpu", None, x, k, held) == "xla"
    assert moe_ops._token_sum_path("tpu", None, jax.ShapeDtypeStruct((tokens, d), jnp.float16), k, held) == "xla"
    ctx = SimpleNamespace(platform="tpu", mesh=None)
    assert moe_ops._token_sum_kernel(ctx, x, k, held) == (held, False)
    assert moe_ops._token_sum_kernel(SimpleNamespace(platform="cpu", mesh=None), x, k, held) is None
