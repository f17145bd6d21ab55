"""The latent attention's operands as one unit (`ops/latent_operands.py`):
the chain of ops between the projections and the attention lowered with it,
against the op-by-op lowering of the SAME program (which the program takes
whenever an intermediate of the chain is fetched), values and gradients; what
falls back and how it is counted; what a recomputed segment keeps either way;
and the chip's kernels (`ops/latent_kernels.py`), interpreted, against the
plain `jax.numpy` passes."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import monitor
from paddle_tpu.core import executor as ex
from paddle_tpu.core.lowering import LoweringContext
from paddle_tpu.models import transformer
from paddle_tpu.ops import latent_operands as lo

D, HEADS, RANK, NOPE, ROPE, V, L = 32, 4, 16, 8, 4, 8, 16
WEIGHTS = ("q.w", "kv_a.w", "kv_b.w", "out.w", "kv_norm.w")
CASES = {"pairs": dict(interleave=True), "halves": dict(interleave=False), "no_positions": dict(positions=False),
         "two_layers": dict(interleave=True, layers=2)}


def build(dtype="float32", interleave=False, positions=True, layers=1, recompute=False, extra_reader=False,
          heads=HEADS, nope=NOPE, rope=ROPE, v=V, length=L, d=D, rank=RANK):
    main, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        x = fluid.layers.data("x", [length, d], dtype=dtype)
        at = fluid.layers.data("pos", [length], dtype="int32")
        x.stop_gradient = False
        h = x
        for i in range(layers):
            with fluid.recompute_scope() if recompute else fluid.name_scope(None):
                h = h + transformer.latent_attention(h, d, heads, f"l{i}", rank, nope, rope, v,
                                                     positions=at if positions else None, rope_theta=1e4,
                                                     rope_interleave=interleave)
        loss = fluid.layers.mean(fluid.layers.square(fluid.layers.cast(h, "float32")))
        if extra_reader:    # a second reader of the first attention's K, as the benchmark's stage ops are
            k = main.global_block().var(attentions(main)[0].input("K")[0])
            loss = loss + fluid.layers.mean(fluid.layers.cast(k, "float32"))
        fluid.optimizer.SGD(0.0).minimize(loss)
    main.random_seed = startup.random_seed = 7
    return main, startup, loss, h


def attentions(main):
    return [op for op in main.global_block().ops if op.type == "fused_attention"]


def counted(prefix="lowering.latent_operands"):
    return {k[len("lowering."):]: v for k, v in monitor.MONITOR.counter_values().items() if k.startswith(prefix) and v}


@pytest.fixture
def counters():
    monitor.reset()
    monitor.enable()
    yield counted
    monitor.disable()
    monitor.reset()


def both_ways(dtype, **case):
    """{name: (the unit's, the op-by-op lowering's)} of the output and the
    five weights' gradients of every layer, one program and one scope."""
    layers = case.get("layers", 1)
    main, startup, loss, out = build(dtype, **case)
    names = [out.name] + [f"l{i}.{w}@GRAD" for i in range(layers) for w in WEIGHTS]
    exe, scope = fluid.Executor(fluid.TPUPlace(0)), fluid.Scope()
    rng = np.random.RandomState(0)
    feed = {"x": (3.0 * rng.randn(2, L, D)).astype("f4").astype(dtype if dtype != "bfloat16" else jnp.bfloat16),
            "pos": np.stack([np.arange(L), np.arange(L) * 37 % 101]).astype("int32")}
    monitor.reset()
    monitor.enable()
    try:
        with fluid.scope_guard(scope):
            exe.run(startup)
            unit = exe.run(main, feed=feed, fetch_list=names)
            said = counted()
            per_op = exe.run(main, feed=feed, fetch_list=names + [attentions(main)[0].input("K")[0]] +
                             [a.input("V")[0] for a in attentions(main)[1:]])
            said_per_op = counted()
    finally:
        monitor.disable()
        monitor.reset()
    assert said == {"latent_operands_assembled": layers}, said
    assert said_per_op["latent_operands_fallback"] == said_per_op["latent_operands_fallback_fetched"] == layers
    return {n: (np.asarray(a, "f4"), np.asarray(b, "f4")) for n, a, b in zip(names, unit, per_op)}


@pytest.fixture(scope="module")
def runs():
    made = {}

    def of(dtype, case):
        if (dtype, case) not in made:
            made[dtype, case] = both_ways(dtype, **CASES[case])
        return made[dtype, case]

    return of


@pytest.mark.parametrize("what", ["out"] + list(WEIGHTS))
@pytest.mark.parametrize("case", sorted(CASES))
def test_float32_the_unit_is_the_op_by_op_lowering_to_1e_6(runs, case, what):
    """The same arithmetic in float32 but for where the scale is applied (the
    queries here, the scores there) and the order of a sum over the heads."""
    found = runs("float32", case)
    for name, (unit, per_op) in found.items():
        if name.endswith(what + "@GRAD") or (what == "out" and "@GRAD" not in name):
            assert np.abs(per_op).max() > 0
            assert np.abs(unit - per_op).max() <= 1e-6 * np.abs(per_op).max(), name


@pytest.mark.parametrize("what", ["out"] + list(WEIGHTS))
@pytest.mark.parametrize("case", sorted(CASES))
def test_bfloat16_the_unit_is_within_one_rounding_of_the_op_by_op_lowering(runs, case, what):
    """In bf16 the unit rounds the rotated, scaled queries ONCE where the ops
    round the rotation and then the scaling: the two differ by a rounding of
    the operands, and neither lies further from float32 than the other by more
    than that."""
    found, exact = runs("bfloat16", case), runs("float32", case)
    for name, (unit, per_op) in found.items():
        if name.endswith(what + "@GRAD") or (what == "out" and "@GRAD" not in name):
            size = np.abs(exact[name][1]).max()
            assert np.abs(unit - per_op).max() <= 2.0 ** -6 * size, name
            assert np.abs(unit - exact[name][1]).max() <= np.abs(per_op - exact[name][1]).max() + 2.0 ** -7 * size, name


def planned(main, fetch=(), kept=None, mesh=None, platform="cpu"):
    """(`ctx.latent_units` as `plan` leaves it for the program's ops, the counters)."""
    ctx = LoweringContext(jax.random.PRNGKey(0), mesh=mesh, platform=platform)
    ctx.fetch_names = tuple(fetch)
    ctx.kept_by_segment = kept or {}
    lo.plan(ctx, list(main.global_block().ops))
    return ctx.latent_units, counted()


def test_a_latent_attention_and_its_chain_are_one_unit(counters):
    main = build(interleave=True)[0]
    units, said = planned(main)
    unit = units[id(attentions(main)[0])]
    assert said == {"latent_operands_assembled": 1}
    assert [op.type for op in unit.chain] == ["slice", "slice", "rotary_embedding", "concat", "reshape2", "rotary_embedding",
                                              "expand", "slice", "slice", "concat"]
    assert set(units) == {id(op) for op in unit.chain} | {id(unit.attention)}
    assert (unit.nope, unit.rope, unit.interleave, unit.positions) == (NOPE, ROPE, True, "pos")
    assert unit.q_pass.type == unit.shared_pass.type == "rotary_embedding" and unit.q_pass is not unit.shared_pass
    assert unit.k_pass.type == "concat"


def test_without_positions_the_queries_are_the_projection_and_nothing_rotates(counters):
    main = build(positions=False)[0]
    units, said = planned(main)
    unit = units[id(attentions(main)[0])]
    assert said == {"latent_operands_assembled": 1}
    assert [op.type for op in unit.chain] == ["reshape2", "expand", "slice", "slice", "concat"]
    assert unit.positions is None and unit.q == unit.attention.input("Q")[0] and unit.k_pass.type == "concat"


def _an_intermediate(main, slot="K"):
    return attentions(main)[0].input(slot)[0]


@pytest.mark.parametrize("reason", lo.REASONS)
def test_what_cannot_be_one_unit_falls_back_and_the_counter_says_why(counters, reason):
    """Fetched, read by a second op, kept by its segment, another chain, a mesh."""
    main = build(recompute=True, extra_reader=reason == "shared_reader",
                 **(dict(nope=128, rope=64, v=128, d=64, heads=3, length=64, dtype="bfloat16") if reason == "shape" else {}))[0]
    how = {"fetched": dict(fetch=[_an_intermediate(main, "V")]), "shared_reader": {}, "kept": dict(kept={1: {_an_intermediate(main, "Q")}}),
           "shape": dict(platform="tpu"),      # three heads: the chip's kernels take pairs of them
           "mesh": dict(mesh=fluid.parallel.make_mesh((2,), ("dp",)))}[reason]
    units, said = planned(main, **how)
    assert not units
    assert said == {"latent_operands_fallback": 1, f"latent_operands_fallback_{reason}": 1}


def test_a_chain_of_another_shape_is_left_to_its_ops(counters):
    """Narrower values, but the keys are no concat of a slice and a spread part."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        x = fluid.layers.data("x", [L, D], dtype="float32")
        q, k = (fluid.layers.reshape(fluid.layers.fc(x, 48, num_flatten_dims=2), [0, 0, 4, 12]) for _ in range(2))
        v = fluid.layers.reshape(fluid.layers.fc(x, 32, num_flatten_dims=2), [0, 0, 4, 8])
        fluid.layers.fused_attention(q, k, v, causal=True, layout="blhd")
    units, said = planned(main)
    assert not units and said == {"latent_operands_fallback": 1, "latent_operands_fallback_shape": 1}


def test_a_program_with_no_latent_attention_plans_nothing_and_counts_nothing(counters):
    main, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        x = fluid.layers.data("x", [L, D], dtype="float32")
        q, k, v = (fluid.layers.reshape(fluid.layers.fc(x, 32, num_flatten_dims=2), [0, 0, 4, 8]) for _ in range(3))
        fluid.layers.fused_attention(q, k, v, causal=True, layout="blhd")
    assert planned(main) == ({}, {})


def test_the_clone_whose_stage_ops_read_a_layers_operands_takes_the_unit_in_the_other_layers(counters):
    """Two layers, the first one's K read by a second op: 1 assembled, 1 fallen
    back; the step that prunes the reader (its fetch does not reach it) takes both."""
    main, startup, loss, _ = build(layers=2, extra_reader=True)
    _, said = planned(main)
    assert said == {"latent_operands_assembled": 1, "latent_operands_fallback": 1, "latent_operands_fallback_shared_reader": 1}


def _names_kept(main, loss, fetch, length, d):
    """The names a `jax.checkpoint` of the traced step saves, and the kept counters."""
    scope = fluid.Scope()
    for var in main.global_block().vars.values():
        if var.persistable:
            scope.set_var(var.name, jax.ShapeDtypeStruct(tuple(var.shape), var.dtype))
    feeds = {"x": jax.ShapeDtypeStruct((1, length, d), jnp.bfloat16), "pos": jax.ShapeDtypeStruct((1, length), jnp.int32)}
    step = ex._CompiledStep(main, list(feeds), fetch, scope, platform="tpu", feed_shapes={n: s.shape for n, s in feeds.items()})
    as_shape = lambda v: jax.ShapeDtypeStruct(v.shape, v.dtype)
    monitor.reset()
    traced = step.jfn.trace({n: as_shape(scope.find_var(n)) for n in step.rw_names},
                            {n: as_shape(scope.find_var(n)) for n in step.ro_names}, feeds, as_shape(jax.random.PRNGKey(0)))
    names = set()

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "name":
                names.add(eqn.params["name"])
            for value in eqn.params.values():
                for sub in value if isinstance(value, (tuple, list)) else (value,):
                    if hasattr(sub, "eqns") or hasattr(getattr(sub, "jaxpr", None), "eqns"):
                        walk(getattr(sub, "jaxpr", sub))

    walk(traced.jaxpr.jaxpr)
    return names, counted("lowering.")


def test_inside_a_recomputed_segment_the_kept_names_are_the_op_by_op_lowerings(counters):
    """At the chip's path (splash kernels from 2048 keys on; traced for the
    TPU, nothing compiled): the products' outputs and the attention's `out`
    and `lse` under the op's residuals name, with the unit as without it."""
    main, startup, loss, _ = build("bfloat16", interleave=True, recompute=True, heads=2, nope=128, rope=64, v=128, length=2048,
                                   d=64, rank=32)
    unit, said = _names_kept(main, loss, [loss.name], 2048, 64)
    per_op, said_per_op = _names_kept(main, loss, [loss.name, _an_intermediate(main, "V")], 2048, 64)
    assert said["latent_operands_assembled"] == 1 and said_per_op["latent_operands_fallback_fetched"] == 1
    assert said["attention_block_causal"] == said_per_op["attention_block_causal"] == 1
    assert said["attention_backward_onchip_dq"] == said_per_op["attention_backward_onchip_dq"] == 1   # 192 | 128 widths take the one backward kernel
    assert unit == per_op and any(n.endswith("@residuals") for n in unit) and len(unit) == 4   # q, kv_a, kv_b; out, lse
    assert said["recomputed_kept_values"] == said_per_op["recomputed_kept_values"] == len(unit)
    assert said["recomputed_kept_bytes"] == said_per_op["recomputed_kept_bytes"]
    assert said["latent_rotary_ops"] == said_per_op["latent_rotary_ops"] == 2


KERNEL_CASES = {"pairs": (True, True), "halves": (True, False), "not_rotated": (False, False)}
MADE = ("q_hm", "k_hm", "v_hm", "dq", "d_up", "d_shared")


@pytest.fixture(scope="module")
def passes_both_ways():
    """{case: {what: (plain `jax.numpy`, the kernels interpreted)}} at two tiles
    of own part a head (the second head's tiles are made of three neighbours')."""
    batch, length, heads, nope, v = 2, 32, 4, 256, 128
    rng = np.random.RandomState(1)
    q, up, shared = (jnp.asarray(rng.randn(batch, length, *tail), jnp.bfloat16)
                     for tail in ((heads, nope + 64), (heads, nope + v), (64,)))
    pos = jnp.asarray(np.stack([np.arange(length), np.arange(length) * 997 % 16384]), jnp.int32)
    cotangents = tuple(jnp.asarray(rng.randn(batch, heads, length, w), jnp.bfloat16) for w in (nope + 64, nope + 64, v))
    found = {}
    for case, (rotated, interleave) in KERNEL_CASES.items():
        made = []
        for kernels in (False, True):
            p = lo.Passes(nope, 64, (nope + 64) ** -0.5, rotated, 1e6, interleave, "q", "k", "s", kernels, True)
            out, back = jax.vjp(lambda q, up, shared: lo.assemble(p, q, up, shared, pos if rotated else None), q, up, shared)
            made.append([np.asarray(t, "f4") for t in (*out, *back(cotangents))])
        found[case] = dict(zip(MADE, zip(*made)))
    return found


@pytest.mark.parametrize("what", MADE)
@pytest.mark.parametrize("case", sorted(KERNEL_CASES))
def test_the_chips_kernels_interpreted_are_the_plain_passes(passes_both_ways, case, what):
    """The same float32 arithmetic and the one rounding: equal but for a
    multiply-add contracted one way or the other (half a bf16 ulp at most)."""
    plain, kernels = passes_both_ways[case][what]
    assert plain.shape == kernels.shape and np.abs(plain).max() > 0
    assert np.abs(plain - kernels).max() <= 2.0 ** -8 * np.abs(plain).max()
    assert np.mean(plain != kernels) < 1e-3


def test_the_kernels_take_whole_tiles_and_pairs_of_heads_only():
    from paddle_tpu.ops import latent_kernels as lk

    assert lk.fits(32, 16384, 128, 64, 128, jnp.bfloat16) and lk.fits(2, 16, 256, 64, 128, jnp.bfloat16)
    for other in ((3, 16384, 128, 64, 128, jnp.bfloat16), (32, 16384, 96, 64, 128, jnp.bfloat16),
                  (32, 16384, 128, 32, 128, jnp.bfloat16), (32, 16384, 128, 64, 64, jnp.bfloat16),
                  (32, 16384, 128, 64, 128, jnp.float32), (32, 1000, 128, 64, 128, jnp.bfloat16)):
        assert not lk.fits(*other), other
