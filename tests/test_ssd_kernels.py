"""`ops/ssd_kernels.py` (ISSUE 61), tiny and interpreted on the CPU: the two
Pallas kernels behind `ssd_ops.kernel_ssd_scan` against the plain chunked form
(`chunked_ssd_scan`, what the CPU runs) and the token-by-token recurrence:

    the output, the state after the last token and the statistics; all seven
    gradients against `jax.vjp` of the chunked form; heads that read their own
    group's B and C; a row that is no whole number of chunks; the start states
    forward keeps; `_scan_path`'s rule; the three counters; the op under a
    rows-only mesh; a layer inside a `recompute_scope` whose plan keeps the
    kernels' residuals.

Interpreted kernels show the arithmetic; what Mosaic refuses shows in
`tests/test_chip_compile.py`, and what the chip rounds in the cell's scan stage.
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
from benchmark.models import nemotron_h  # noqa: E402
from paddle_tpu import layers, monitor  # noqa: E402
from paddle_tpu.core import unique_name  # noqa: E402
from paddle_tpu.core.lowering import LoweringContext  # noqa: E402
from paddle_tpu.models import transformer  # noqa: E402
from paddle_tpu.ops import ssd_kernels, ssd_ops  # noqa: E402


def agree(got, want, tol=1e-5, floor=1e-12):
    got, want = np.asarray(got, "f8"), np.asarray(want, "f8")
    assert got.shape == want.shape, (got.shape, want.shape)
    assert np.abs(got - want).max() <= tol * max(np.abs(want).max(), floor), \
        (np.abs(got - want).max(), np.abs(want).max())


def scan_inputs(seed, rows, length, heads=8, width=8, groups=2, state=16, dtype="float32"):
    """x, dt, ALog, B, C, D, DtBias as the op takes them; A = 1 .. 16 over the heads."""
    r = np.random.RandomState(seed)
    arrays = (r.randn(rows, length, heads * width), r.randn(rows, length, heads), np.log(np.linspace(1.0, 16.0, heads)),
              r.randn(rows, length, groups * state), r.randn(rows, length, groups * state), r.randn(heads), r.randn(heads) - 2)
    dtypes = (dtype, dtype, "float32", dtype, dtype, "float32", "float32")
    return tuple(jnp.asarray(t, d) for t, d in zip(arrays, dtypes))


def plain(groups, chunk):
    return lambda *a: ssd_ops.chunked_ssd_scan(*a, groups, chunk)


def kernels(groups, chunk):
    return lambda *a: ssd_ops.kernel_ssd_scan(*a, groups, chunk, "interpret")


#: (tokens, chunk, heads, channels a head, groups, dtype): two heads of 8 channels a slab's stand-in (a group of four is
#: one slab here, `ssd_kernels._operands`), a row with a padded tail, one shorter than a chunk, heads of a whole lane
#: tile (a head a slab), one group, bf16 activations
CASES = [(64, 16, 8, 8, 2, "float32"), (50, 16, 8, 8, 2, "float32"), (7, 16, 8, 8, 2, "float32"), (40, 8, 4, 128, 2, "float32"),
         (48, 16, 32, 8, 2, "float32"), (33, 128, 8, 8, 1, "float32"), (64, 32, 8, 8, 1, "bfloat16"), (45, 16, 8, 8, 4, "bfloat16")]


@pytest.mark.parametrize("length,chunk,heads,width,groups,dtype", CASES)
def test_the_kernels_are_the_chunked_form_and_the_recurrence_forward(length, chunk, heads, width, groups, dtype):
    """y, `State` and the two means of `Stats` from the interpreted kernels: the
    plain form's to float32's rounding (the same roundings to the activations'
    dtype), and the token-by-token recurrence's."""
    args = scan_inputs(length, 2, length, heads, width, groups, dtype=dtype)
    y, final, means = kernels(groups, chunk)(*args)
    want_y, want_final, want_means = plain(groups, chunk)(*args)
    assert y.dtype == jnp.dtype(dtype) and y.shape == want_y.shape and final.dtype == jnp.float32 and final.shape == want_final.shape
    agree(y, want_y, tol=2e-6 if dtype == "float32" else 1e-2)        # a bf16 output may round the other way: one step of it
    agree(final, want_final, tol=2e-6)
    for mine, theirs in zip(means, want_means):
        agree(mine, theirs, tol=2e-6)
    x, dt, a_log, b, c, d, bias = (np.asarray(t, "f4") for t in args)
    plain_y, plain_final = nemotron_h.scan_recurrence(x, dt, b, c, a_log, d, bias, groups, with_state=True)
    agree(y, plain_y, tol=2e-5 if dtype == "float32" else 1e-2)
    agree(final, plain_final, tol=2e-5)


GRADIENTS = ("x", "dt", "a_log", "b", "c", "d", "dt_bias")


@pytest.fixture(scope="module")
def gradients():
    """{case: (the kernels' seven gradients, `jax.vjp` of the chunked form's)} of sum(y . w), made once a case."""
    made = {}

    def of(case):
        if case not in made:
            length, chunk, heads, width, groups, dtype = case
            args = scan_inputs(length, 2, length, heads, width, groups, dtype=dtype)
            weigh = jnp.asarray(np.random.RandomState(1).randn(2, length, heads * width), dtype)
            made[case] = tuple(jax.vjp(lambda *a: form(groups, chunk)(*a)[0], *args)[1](weigh) for form in (kernels, plain))
        return made[case]
    return of


@pytest.mark.parametrize("which", range(7), ids=GRADIENTS)
@pytest.mark.parametrize("case", CASES[:5] + CASES[6:7], ids=lambda c: "-".join(str(v) for v in c))
def test_the_transposed_kernel_gives_the_chunked_forms_gradient(gradients, case, which):
    """Each of the seven gradients from the transposed kernel (and the plain
    lines XLA transposes round it) against `jax.vjp` of the chunked form: the
    same dtype and shape, and float32's rounding apart (ALog's is the sum of
    the cumulative decay's cotangent along a chunk, whose terms cancel: float32's
    rounding of THEM is a few more bits of it, in either form, by which way they
    happened to round); a bf16 gradient a step of it."""
    mine, theirs = (grads[which] for grads in gradients(case))
    assert mine.dtype == theirs.dtype and mine.shape == theirs.shape and np.isfinite(np.asarray(mine, "f4")).all()
    tol = 5e-4 if GRADIENTS[which] == "a_log" else 1e-4 if GRADIENTS[which] == "dt_bias" else 2e-5
    agree(mine, theirs, tol=tol if mine.dtype == jnp.float32 else 2e-2)


def test_a_head_of_the_kernels_reads_its_own_groups_b_and_c():
    """Four groups of four heads: with the third group's B zeroed its heads see
    no input (y = D x there, and no gradient reaches its C), the other groups'
    heads and gradients are what they were."""
    args = scan_inputs(5, 1, 40, heads=16, width=8, groups=4)
    x, dt, a_log, b, c, d, bias = args
    cut_b = b.at[..., 32:48].set(0.0)
    through = lambda *a: jax.vjp(lambda *o: kernels(4, 16)(*o)[0], *a)                                     # noqa: E731
    (whole, back), (cut, back_cut) = through(*args), through(x, dt, a_log, cut_b, c, d, bias)
    theirs = slice(64, 96)                                                # the third group's heads' channels
    agree(cut[..., theirs], (jnp.repeat(d, 8) * x)[..., theirs], tol=1e-6)
    ours = np.r_[0:64, 96:128]
    agree(np.asarray(cut)[..., ours], np.asarray(whole)[..., ours], tol=1e-6)
    weigh = jnp.asarray(np.random.RandomState(3).randn(1, 40, 128), jnp.float32)
    grads, grads_cut = back(weigh), back_cut(weigh)
    assert not np.asarray(grads_cut[4][..., 32:48]).any() and np.asarray(grads[4][..., 32:48]).any()      # dC of the cut group
    others = np.r_[0:32, 48:64]
    for which in (3, 4):
        agree(np.asarray(grads_cut[which])[..., others], np.asarray(grads[which])[..., others], tol=1e-6)


def test_a_padded_tail_steps_by_exactly_zero_and_its_gradients_are_exactly_zero():
    """50 tokens in chunks of 16: the kernels see 64, the tail's step is exactly
    0 (the state after the last token is the one after token 50) and, of the
    transposed kernel's own outputs on the padded row, the tail's dX, dB, dC and
    the step's own cotangent are exactly 0."""
    args = scan_inputs(7, 2, 50)
    x, dt, a_log, b, c, d, bias = args
    (xs, bs, cs), decays, Q, pad = ssd_ops._kernel_operands(x, b, c, 16)
    assert (Q, pad, xs.shape[1]) == (16, 14, 64)
    step, cum, log_decay = decays(dt, a_log, bias)
    assert not np.asarray(step[:, 50:]).any() and not np.asarray(log_decay[:, 50:]).any()
    assert np.array_equal(np.asarray(cum[:, 50:]), np.broadcast_to(np.asarray(cum[:, 49:50]), (2, 14, 8)))
    y, final, starts = ssd_kernels.scan(xs, bs, cs, step, cum, d, 16, 2, True, True)
    agree(ssd_kernels.heads_first(final, 8), plain(2, 16)(*args)[1], tol=2e-6)
    d_y = jnp.pad(jnp.asarray(np.random.RandomState(2).randn(2, 50, 64), jnp.float32), ((0, 0), (0, 14), (0, 0)))
    dx, db, dc, dstep, dcum, dskip = ssd_kernels.scan_transposed(xs, bs, cs, step, cum, d, d_y, starts, 16, 2, True)
    for name, t in (("dx", dx), ("db", db), ("dc", dc), ("dstep", dstep)):
        assert np.asarray(t[:, :50]).any() and not np.asarray(t[:, 50:]).any(), name
    grads = jax.grad(lambda *a: jnp.sum(jnp.sin(kernels(2, 16)(*a)[0])), argnums=(0, 1))(*args)
    assert all(g.shape == a.shape and np.isfinite(np.asarray(g)).all() for g, a in zip(grads, args))


def test_forward_keeps_the_state_every_chunk_starts_from():
    """What `_kernel_scan_fwd` keeps beside the seven inputs: the plain form's
    state at every chunk boundary (its final state on the tokens before it), in
    the kernels' own tiles [chunks, rows, slabs, N, heads P]."""
    args = scan_inputs(4, 2, 50)
    x, dt, a_log, b, c, d, bias = args
    (y, final, _), (inputs, (starts,)) = ssd_ops._kernel_scan_fwd(*args, 2, 16, "interpret", None)
    assert starts.shape == (4, 2, 2, 16, 32) and all(kept is given for kept, given in zip(inputs, args))
    starts = ssd_kernels.heads_first(starts, 8)
    assert not np.asarray(starts[0]).any()
    for k in (1, 2, 3):
        agree(starts[k], plain(2, 16)(x[:, :16 * k], dt[:, :16 * k], a_log, b[:, :16 * k], c[:, :16 * k], d, bias)[1], tol=2e-6)
    agree(y, kernels(2, 16)(*args)[0], tol=0)


def test_a_step_of_sixty_nats_a_token_overflows_nothing_in_the_kernels():
    """A strong step (dt ~ 60, A down to -16: the decay underflows to 0) is
    finite forward and backward: no exponent in the kernels is positive."""
    x, dt, a_log, b, c, d, bias = scan_inputs(2, 1, 40)
    args = (x, dt, a_log, b, c, d, bias + 62.0)
    y, final, _ = kernels(2, 16)(*args)
    grads = jax.grad(lambda *a: kernels(2, 16)(*a)[0].sum(), argnums=tuple(range(7)))(*args)
    assert all(np.isfinite(np.asarray(t)).all() for t in (y, final, *grads))
    agree(y, plain(2, 16)(*args)[0], tol=2e-6)


MESH4 = SimpleNamespace(size=4, shape={"dp": 4})
MESH22 = SimpleNamespace(size=4, shape={"dp": 2, "tp": 2})


@pytest.mark.parametrize("platform,mesh,axis,rows,tokens,heads,width,groups,state,chunk,path", [
    ("tpu", None, None, 1, 8192, 128, 64, 8, 128, 128, "kernels"),          # a chip's row in Nemotron-3-Super's cell
    ("tpu", MESH4, "dp", 4, 8192, 128, 64, 8, 128, 128, "kernels"),         # the rows split four ways and nothing else
    ("tpu", SimpleNamespace(size=1, shape={"dp": 1}), "dp", 2, 4096, 32, 128, 4, 256, 256, "kernels"),
    ("tpu", None, None, 1, 8100, 128, 64, 8, 128, 128, "kernels"),          # the op pads the tail itself
    ("tpu", None, None, 1, 128, 16, 8, 1, 128, 256, "kernels"),             # a row of one short chunk, sixteen heads a slab
    ("cpu", None, None, 1, 8192, 128, 64, 8, 128, 128, "xla"),
    ("tpu", None, None, 1, 8192, 128, 64, 8, 64, 128, "xla"),               # a state that is no whole lane tile
    ("tpu", None, None, 1, 8192, 128, 48, 8, 128, 128, "xla"),              # channels that fill no lane tile
    ("tpu", None, None, 1, 8192, 128, 4, 8, 128, 128, "xla"),               # ... that are no whole sublane tile
    ("tpu", None, None, 1, 8192, 24, 64, 8, 128, 128, "xla"),               # a group of three heads: no whole slabs of two
    ("tpu", None, None, 1, 8192, 128, 64, 8, 128, 64, "xla"),               # a chunk that is no whole lane tile
    ("tpu", None, None, 1, 100, 128, 64, 8, 128, 128, "xla"),               # ... a row shorter than a chunk that is none
    ("tpu", MESH22, "dp", 4, 8192, 128, 64, 8, 128, 128, "xla"),            # the heads may be split too: GSPMD's form
    ("tpu", MESH4, None, 4, 8192, 128, 64, 8, 128, 128, "xla"),             # no batch axis known
    ("tpu", MESH4, "dp", 6, 8192, 128, 64, 8, 128, 128, "xla"),             # rows that 4 does not divide
])
def test_the_scans_rule_reads_the_platform_the_mesh_and_the_shapes(platform, mesh, axis, rows, tokens, heads, width, groups, state, chunk, path):
    x = jax.ShapeDtypeStruct((rows, tokens, heads * width), jnp.bfloat16)
    a_log, b_t = jax.ShapeDtypeStruct((heads,), jnp.float32), jax.ShapeDtypeStruct((rows, tokens, groups * state), jnp.bfloat16)
    assert ssd_ops._scan_path(platform, mesh, x, a_log, b_t, groups, chunk, axis) == path


KERNEL_COUNTERS = ("lowering.ssd_scan_ops",) + tuple(f"lowering.ssd_scan_{n}" for n in ("kernel_calls", "kernel_transposed_calls", "starts_kept"))
SLOTS = ("X", "Dt", "ALog", "B", "C", "D", "DtBias")


def scan_op(ctx, rows):
    """(Out, State, Stats; the gradients of sum(sin(Out)) by ALog, D and DtBias)
    of the op lowered under `ctx` on `rows` rows of 24 tokens, chunks of 8."""
    inputs = scan_inputs(6, rows, 24)
    attrs = {"groups": 2, "chunk": 8}
    op = SimpleNamespace(type="ssd_scan", attr=lambda n, d=None: attrs.get(n, d))

    def outs(*arrays):
        return ssd_ops._ssd_scan(ctx, op, {k: [v] for k, v in zip(SLOTS, arrays)})

    forward = jax.jit(lambda *a: tuple(map(outs(*a).get, ("Out", "State", "Stats"))))(*inputs)
    return forward, jax.jit(jax.grad(lambda *a: jnp.sum(jnp.sin(outs(*a)["Out"])), argnums=(2, 5, 6)))(*inputs)


def test_the_counters_read_what_ran_and_under_a_rows_only_mesh_the_op_is_the_same_rows_on_one_device(monkeypatch):
    """The CPU's path counts the op and none of the kernels' three; with the
    kernels taken (interpreted) `ssd_scan_kernel_calls` keeps step with
    `ssd_scan_ops`, and the `custom_vjp` stands inside the `shard_map`: under
    the four-device batch mesh Out, State and Stats are one device's on the
    same rows, and ALog's, D's and DtBias' gradients each chip's share summed
    over the chips once."""
    mesh = fluid.parallel.make_mesh((4,), ("dp",))
    monitor.reset()
    monitor.enable()
    try:
        moved = lambda: [monitor.counter(n).value for n in KERNEL_COUNTERS]                                # noqa: E731
        xla = scan_op(LoweringContext(jax.random.PRNGKey(0)), 4)
        assert moved() == [2, 0, 0, 0]                                                 # lowered twice: the outputs, and Out's gradients
        monkeypatch.setattr(ssd_ops, "_scan_path", lambda *a, **k: "interpret")
        alone = scan_op(LoweringContext(jax.random.PRNGKey(0)), 4)
        assert moved() == [4, 2, 1, 1]
        under = monitor.counter("lowering.kernels_under_shard_map").value
        split = scan_op(LoweringContext(jax.random.PRNGKey(0), mesh=mesh, platform="cpu", batch_axis="dp"), 4)
        assert moved() == [6, 4, 2, 2]
        assert monitor.counter("lowering.kernels_under_shard_map").value == under + 2
    finally:
        monitor.disable()
        monitor.reset()
    for mine, one_device, plain_form in zip(split[0] + split[1], alone[0] + alone[1], xla[0] + xla[1]):
        agree(mine, one_device, tol=1e-5)
        agree(mine, plain_form, tol=1e-4)


def mixer_step(feed, mesh=None):
    """(the loss, the mixer's parameters after one SGD step) of a Mamba-2 mixer
    inside a `recompute_scope` over 4 rows of 32 tokens."""
    with unique_name.guard():
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup):
            x = layers.data("x", [32, 32])
            with fluid.recompute_scope():
                out = transformer.mamba2_mixer(x, 32, "m", heads=8, head_dim=8, state=16, groups=2, chunk=16)
            loss = layers.mean(layers.square(out))
            fluid.optimizer.SGD(0.5).minimize(loss)
    main.random_seed = startup.random_seed = 5
    scope, exe = fluid.Scope(), fluid.Executor(fluid.TPUPlace(0))
    exe.run(startup, scope=scope)
    program = main if mesh is None else fluid.CompiledProgram(main).with_mesh(mesh, batch_axis="dp")
    got = exe.run(program, feed={"x": feed}, fetch_list=[loss], scope=scope)
    return np.asarray(got[0]), {p.name: np.asarray(scope.find_var(p.name)) for p in main.all_parameters()}


def test_a_mixer_through_the_kernels_under_a_rows_only_mesh_is_the_same_rows_on_one_device_and_the_plain_forms_step(monkeypatch):
    """The twin of `test_on_a_mesh_that_splits_the_rows...` for the mixer: one
    training step of a Mamba-2 mixer in a `recompute_scope`, the scan through
    the interpreted kernels on one device and under the four-device batch mesh,
    against the plain form's: the loss and every parameter after the step."""
    feed = np.random.RandomState(4).randn(4, 32, 32).astype("f4")
    with jax.default_matmul_precision("highest"):
        plain_loss, plain_params = mixer_step(feed)
        monkeypatch.setattr(ssd_ops, "_scan_path", lambda *a, **k: "interpret")
        loss, params = mixer_step(feed)
        split_loss, split_params = mixer_step(feed, fluid.parallel.make_mesh((4,), ("dp",)))
    agree(loss, plain_loss, tol=1e-5)
    agree(split_loss, loss, tol=1e-5)
    assert set(params) == set(plain_params) == set(split_params) and {"m.a_log", "m.d", "m.dt_bias"} <= set(params)
    for name in params:
        agree(params[name], plain_params[name], tol=1e-4)
        agree(split_params[name], params[name], tol=1e-5)
