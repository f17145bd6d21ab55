"""What a `recompute_scope` segment keeps for backward (ISSUE 51): products'
outputs and kernels' residuals, chosen once a trace by `lowering.plan_kept` from
the program's shapes and the room the chip has left.

(a) the chooser alone, a pure function of (candidates, budget);
(b) a Mamba and an attention layer, each a segment, the kernels interpreted:
    loss and every gradient are what they are with nothing kept and with no
    segment at all, bitwise where XLA:CPU makes the same fusions;
(c) the differentiated step's jaxpr: what is kept is not made again;
(d) under a (4,) batch mesh the bytes are a chip's, and the names hold inside
    the `shard_map` the kernels run in;
(e) a program without a segment, and a segment that keeps nothing, hold no name:
    `tests/test_lowering_one_path.py` pins the cells' lowered text.
"""
import os
import sys

import jax
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import paddle_tpu as fluid  # noqa: E402
from paddle_tpu import monitor  # noqa: E402
from paddle_tpu.core import executor as ex  # noqa: E402
from paddle_tpu.core import lowering  # noqa: E402
from paddle_tpu.core.autodiff import append_backward  # noqa: E402
from paddle_tpu.core.lowering import Kept, choose_kept  # noqa: E402
from paddle_tpu.models import transformer  # noqa: E402
from paddle_tpu.monitor import memstats  # noqa: E402
from paddle_tpu.ops import masked_attention, nn_ops, ssm_ops  # noqa: E402

# -- (a) the chooser -------------------------------------------------------------------------------

#: four candidates of two segments, in program order: operations a byte 10, 40, 40, 2
FOUR = [Kept(1, "a", 100, 1000.0), Kept(1, "b", 50, 2000.0), Kept(2, "c", 50, 2000.0), Kept(2, "d", 200, 400.0)]


@pytest.mark.parametrize("budget,names", [
    (0, []),                                # a chip that is full keeps nothing: the plain `jax.checkpoint`
    (49, []),                               # nothing fits
    (50, ["b"]),                            # the dearest a byte; of two alike, the first in program order
    (100, ["b", "c"]),
    (199, ["b", "c"]),                      # b and c first; a (100) does not fit in the 99 left, nor does d behind it
    (200, ["a", "b", "c"]),
    (399, ["a", "b", "c"]),                 # d (200) does not fit in the 199 left
    (400, ["a", "b", "c", "d"]),
    (1e18, ["a", "b", "c", "d"]),
])
def test_the_chooser_takes_the_dearest_a_byte_first_and_never_passes_the_budget(budget, names):
    chosen = choose_kept(FOUR, budget)
    assert [c.name for c in chosen] == names
    assert sum(c.nbytes for c in chosen) <= budget
    assert chosen == choose_kept(list(FOUR), budget)                       # the same set for the same question
    assert chosen == sorted(chosen, key=FOUR.index)                        # handed back in program order


def test_the_chooser_goes_on_past_a_candidate_that_does_not_fit():
    """Greedy over the whole order: a large value that the budget cannot hold
    does not stop a cheaper one behind it from being kept."""
    large, small = Kept(1, "large", 1000, 1e6), Kept(1, "small", 10, 10.0)
    assert choose_kept([large, small], 500) == [small]
    assert choose_kept([large, small], 1010) == [large, small]


# -- the two-layer model ---------------------------------------------------------------------------

SEQ, ROWS = 128, 4


def build(recompute=True):
    """A Mamba layer and an attention layer, each with a gated MLP, float32:
    (program, loss, gradient names); with `recompute` each layer is a
    `recompute_scope`.  The same names whatever the process built before."""
    with fluid.unique_name.guard():
        main, startup, _, fetches = transformer.build_causal_lm(
            vocab_size=64, seq_len=SEQ, d_model=128, n_heads=2, n_kv_heads=1, qk_norm=None, rotary=False,
            layer_types=["mamba", "full_attention"], conv_kernel=4, mamba=dict(expand=2, state=8, dt_rank=8),
            num_dense_layers=2, dense_width=192, tie_embedding=True, recompute_layers=recompute, with_optimizer=False,
            dtype="float32")
        with fluid.program_guard(main, startup):
            grads = [g.name for _, g in append_backward(fetches["loss"])]
    main.random_seed = startup.random_seed = 3
    return main, startup, fetches["loss"].name, grads


@pytest.fixture
def kernels_interpreted(monkeypatch):
    """What a TPU lowers the two ops to, here interpreted: the scan's kernels
    and the splash kernels under the causal rule."""
    causal_plan = masked_attention.causal_plan
    monkeypatch.setattr(ssm_ops, "_scan_path", lambda *a, **k: "interpret")
    monkeypatch.setattr(nn_ops, "_attention_path", lambda *a, **k: "block_causal")
    monkeypatch.setattr(masked_attention, "causal_plan",
                        lambda length, heads, interpret=False, widths=(128, 128): causal_plan(length, heads, True, widths))


def batch(seed=5):
    ids = np.random.RandomState(seed).randint(0, 64, size=(ROWS, SEQ + 1)).astype("int64")
    return {"ids": ids[:, :-1], "labels": ids[:, 1:]}


def state_bytes(main):
    return sum(int(np.prod(p.shape)) * 4 for p in main.all_parameters())


def counted(name):
    return monitor.counter("lowering.recomputed_" + name).value


def run(main, scope, loss, grads, limit, monkeypatch):
    """One step on a new executor (a compiled step is kept by executor) with the
    device's memory said to be `limit` bytes: (loss and gradients, the values
    kept, their bytes, the candidates' bytes)."""
    monkeypatch.setattr(memstats, "device_bytes_limit", lambda *a: limit)
    before = [counted(n) for n in ("kept_values", "kept_bytes", "candidates_bytes")]
    got = fluid.Executor(fluid.TPUPlace(0)).run(main, feed=batch(), fetch_list=[loss] + grads, scope=scope)
    return [np.asarray(g) for g in got], *(counted(n) - b for n, b in zip(("kept_values", "kept_bytes", "candidates_bytes"), before))


@pytest.fixture
def monitor_on():
    monitor.reset()
    monitor.enable()
    yield
    monitor.disable()
    monitor.reset()


# -- (b) the same numbers ---------------------------------------------------------------------------

def test_loss_and_gradients_are_bitwise_the_same_whatever_is_kept(kernels_interpreted, monitor_on, monkeypatch):
    main, startup, loss, grads = build()
    scope = fluid.Scope()
    fluid.Executor(fluid.TPUPlace(0)).run(startup, scope=scope)
    held = state_bytes(main)
    nothing, n0, b0, candidates = run(main, scope, loss, grads, held, monkeypatch)          # no room: the plain checkpoint
    assert (n0, b0) == (0, 0) and candidates > 0
    everything, n_all, b_all, _ = run(main, scope, loss, grads, held + 2 * candidates, monkeypatch)
    assert b_all == candidates and n_all >= 12              # 7 + 7 products less the two only sums read, and two kernels
    half, n_half, b_half, _ = run(main, scope, loss, grads, held + candidates, monkeypatch)   # half the candidates' bytes
    assert 0 < n_half < n_all and 0.3 * candidates < b_half <= 0.5 * candidates
    plain = main.clone()                                   # the same program with no segment: nothing is made again
    for op in plain.global_block().ops:
        op.attrs.pop("recompute_segment", None)
    unsegmented, n_plain, _, none = run(plain, scope, loss, grads, held + 2 * candidates, monkeypatch)
    assert (n_plain, none) == (0, 0)
    assert np.isfinite(nothing[0]).all() and all(np.abs(g).max() > 0 for g in nothing[1:])
    for name, a, b, c, d in zip([loss] + grads, nothing, half, everything, unsegmented):
        if name == loss or ".l1." in name or "final_norm" in name:
            # the loss, and every gradient that backward has before it reaches the Mamba layer: the same bits, the
            # attention kernels' kept output and log-sum-exp among what they were made from
            assert np.array_equal(a, b) and np.array_equal(a, c), name
        # Behind the Mamba layer the last bits are XLA:CPU's to choose: a chain of elementwise ops made again inside
        # backward's fusions is contracted otherwise than the one that wrote its value out (the program WITHOUT any
        # segment differs from the plain checkpoint's by as much, 7.3e-7 of the largest value: nothing this PR adds)
        for other in (b, c, d):
            assert np.abs(a - other).max() <= 2e-6 * np.abs(a).max(), name


# -- (c) what backward makes again ------------------------------------------------------------------

def walk(jaxpr, inside=False):
    """(primitive name, inside a differentiated checkpoint's jaxpr?) of every
    equation, sub-jaxprs and all: JAX calls the computation that backward runs
    again `remat2` with `differentiated=True`."""
    for eqn in jaxpr.eqns:
        again = inside or (eqn.primitive.name == "remat2" and eqn.params.get("differentiated", False))
        yield eqn.primitive.name, inside
        if eqn.primitive.name == "pallas_call":
            continue                                   # a kernel is one call: its body's products are its own
        for value in eqn.params.values():
            for sub in value if isinstance(value, (list, tuple)) else [value]:
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from walk(sub, again)


def traced(main, loss, grads, limit, monkeypatch, mesh=None):
    """[(primitive, inside a differentiated checkpoint?)] of the program's step
    traced for shapes alone, the device's memory said to be `limit` bytes."""
    monkeypatch.setattr(memstats, "device_bytes_limit", lambda *a: limit)
    scope = fluid.Scope()
    for v in main.global_block().vars.values():
        if v.persistable:
            scope.set_var(v.name, jax.ShapeDtypeStruct(tuple(v.shape), v.dtype))
    feeds = {n: jax.ShapeDtypeStruct(v.shape, np.int32) for n, v in batch().items()}
    step = ex._CompiledStep(main, list(feeds), [loss] + grads, scope, mesh=mesh, feed_shapes={n: s.shape for n, s in feeds.items()})
    jaxpr = step.jfn.trace({n: scope.find_var(n) for n in step.rw_names}, {n: scope.find_var(n) for n in step.ro_names},
                           feeds, jax.ShapeDtypeStruct((2,), np.uint32)).jaxpr.jaxpr
    return list(walk(jaxpr))


def in_backward(*args, **kw):
    """(products, kernel calls) inside the step's differentiated checkpoints,
    which hold a segment's transposes and whatever of its forward they make
    again."""
    names = [name for name, again in traced(*args, **kw) if again]
    return np.array([names.count("dot_general"), names.count("pallas_call")])


#: Of the two layers' fourteen products backward makes twelve again (each layer's last is read by nothing in its
#: segment), and both forward kernels.
AGAIN = (12, 2)


def test_what_is_kept_is_not_made_again(kernels_interpreted, monitor_on, monkeypatch):
    """With everything kept the differentiated checkpoints hold the transposes
    alone: two products a product, the scan's transposed kernel and the
    attention's one backward kernel, and no forward kernel.  With nothing kept
    they hold `AGAIN` more, with half the bytes something between."""
    main, _, loss, grads = build()
    held = state_bytes(main)
    least = in_backward(main, loss, grads, 1e12, monkeypatch)
    assert least[1] == 2 and least[0] >= 2 * 14
    assert tuple(in_backward(main, loss, grads, held, monkeypatch) - least) == AGAIN
    some = in_backward(main, loss, grads, held + counted("candidates_bytes") // 2, monkeypatch) - least   # two traces' candidates
    assert 0 < some[0] < AGAIN[0] and 0 <= some[1] <= AGAIN[1]


# -- (d) on a mesh ----------------------------------------------------------------------------------

def test_under_a_batch_mesh_the_bytes_are_a_chips_and_the_kernels_names_hold_inside_the_shard_map(kernels_interpreted, monitor_on, monkeypatch):
    main, _, loss, grads = build()
    least = in_backward(main, loss, grads, 1e12, monkeypatch)
    alone = counted("kept_bytes")
    mesh = fluid.parallel.make_mesh((4,), ("dp",))
    before = monitor.counter("lowering.kernels_under_shard_map").value
    assert tuple(in_backward(main, loss, grads, 1e12, monkeypatch, mesh=mesh)) == tuple(least)   # no forward kernel again
    assert monitor.counter("lowering.kernels_under_shard_map").value - before >= 2   # both ran inside a `shard_map`
    assert counted("kept_bytes") - alone == alone // 4                               # four rows, one a chip
    assert tuple(in_backward(main, loss, grads, 0, monkeypatch, mesh=mesh) - least) == AGAIN


# -- (e) no name where nothing is kept --------------------------------------------------------------

def test_a_program_that_keeps_nothing_holds_no_name(kernels_interpreted, monitor_on, monkeypatch):
    """`checkpoint_name` is a primitive: its lowering takes a symbol of the
    StableHLO module, so that a name anywhere renumbers the private functions
    behind it.  A step without a segment, and one whose chip has no room, hold
    none, and every cell's program but the two that keep something lowers to the
    text it lowered to (`tests/test_lowering_one_path.py`, `tests/test_lfm2.py`)."""
    main, _, loss, grads = build()
    plain = main.clone()
    for op in plain.global_block().ops:
        op.attrs.pop("recompute_segment", None)

    def names_in(program, limit):
        return [name for name, _ in traced(program, loss, grads, limit, monkeypatch)].count("name")

    assert names_in(plain, 1e12) == 0
    assert names_in(main, 0) == 0
    assert names_in(main, 1e12) > 0


def test_a_for_test_clone_chooses_nothing(monitor_on, monkeypatch):
    main, startup, loss, _ = build()
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.TPUPlace(0))
    exe.run(startup, scope=scope)
    monkeypatch.setattr(memstats, "device_bytes_limit", lambda *a: int(1e12))
    exe.run(main.clone(for_test=True), feed=batch(), fetch_list=[loss], scope=scope)
    assert counted("candidates_bytes") == 0 and counted("kept_values") == 0


def test_the_budget_is_the_devices_memory_less_the_state_times_the_share(monkeypatch):
    """`plan_kept` asks the device (`memstats.device_bytes_limit`), falls back
    to the chip model where the backend reports nothing (the CPU), and hands the
    chooser `KEPT_SHARE` of what the state leaves."""
    from paddle_tpu.core import resource_plan

    main, _, loss, grads = build()
    ops = ex._runnable_ops(main.global_block())
    seen = []
    monkeypatch.setattr(lowering, "choose_kept", lambda candidates, budget: seen.append(budget) or [])
    ctx = lowering.LoweringContext(None)
    feed_shapes = {n: v.shape for n, v in batch().items()}
    assert memstats.device_bytes_limit() is None                      # XLA:CPU reports no memory_stats
    lowering.plan_kept(ctx, ops, feed_shapes, 1000)
    monkeypatch.setattr(memstats, "device_bytes_limit", lambda *a: 5000)
    lowering.plan_kept(ctx, ops, feed_shapes, 1000)
    lowering.plan_kept(ctx, ops, feed_shapes, 9000)                   # a state past the limit: no room, not a negative one
    assert seen == [lowering.KEPT_SHARE * (resource_plan.CHIP_HBM_BYTES - 1000), lowering.KEPT_SHARE * 4000, 0]
    assert ctx.kept_by_segment == {}
