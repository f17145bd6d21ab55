"""`ops/kda_kernels.py` (ISSUE 44, ISSUE 45), tiny and interpreted on the CPU: the
Pallas kernels behind `linear_attention_ops.chunked_kda` against the `jax.numpy`
form (what the CPU runs) and the token-by-token recurrence, the rule that takes
them, the chunks' start states the differentiated forward keeps, the two kernel
calls of a gradient, and the counters that say so.  The op's `jax.numpy` form
and its stage's faults stand in `tests/test_kda_op.py`, whose inputs and
recurrence these cases take, the whole model in `tests/test_kimi_linear.py`: this
file is the kernels' own so that another worker has them (ISSUE 66;
`docs/tier1_durations.md`).

Interpreted kernels show the arithmetic; what Mosaic refuses shows in
`tests/test_chip_compile.py`.
"""
import re
from types import SimpleNamespace

from test_kda_op import recurrence_with_state, scan_inputs
from test_kimi_linear import agree, float32_products  # noqa: F401  (the fixture by name)

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.models import kimi_linear
from paddle_tpu import monitor
from paddle_tpu.core.lowering import LoweringContext
from paddle_tpu.core.registry import get_op_def
from paddle_tpu.ops import kda_kernels
from paddle_tpu.ops import linear_attention_ops as lao


def dying_channel_inputs():
    """Two heads, two chunks, and a channel that one token of chunk 0 forgets outright."""
    q, k, v, g, beta = scan_inputs(7, 1, 128, 2, 8, 8, 0.05)
    return q, k, v, g.at[:, 5, :, 3].set(-100.0).at[:, 37, 0, :2].set(-60.0), beta


def test_no_exponent_is_positive_in_the_kernels_either():
    """The same dying channel through the kernels, which take the block's own
    pairs from their differences for the (heads, chunk) that hold it and the
    carried-back products for the others: output, state and every gradient
    finite, and the `jax.numpy` form's and the recurrence's."""
    q, k, v, g, beta = dying_channel_inputs()                             # chunk 1 is mild in both heads
    weigh = jnp.asarray(np.random.RandomState(1).randn(*v.shape).astype("f4"))

    def through(kernels):
        op = lambda *a: lao.chunked_kda(*a[:4], a[4][..., None], 64, 16, kernels)
        return op(q, k, v, g, beta), jax.grad(lambda *a: jnp.sum(op(*a)[0] * weigh), argnums=(0, 1, 2, 3, 4))(q, k, v, g, beta)

    (out, state), grads = through("interpret")
    (plain, plain_state), plain_grads = through(None)
    want, want_state = recurrence_with_state(q, k, v, g, beta)
    for mine, theirs in ((out, want), (state, want_state), (out, plain), (state, plain_state)) + tuple(zip(grads, plain_grads)):
        assert np.isfinite(np.asarray(mine)).all()
        agree(mine, theirs, tol=3e-5)


KERNEL_CASES = [  # rows, length, heads, dtype, decay, beta
    (1, 64, 2, "float32", 0.1, None), (2, 128, 3, "float32", 1.0, None), (1, 256, 4, "float32", 0.02, None),
    (2, 64, 3, "bfloat16", 0.3, None), (1, 128, 2, "bfloat16", 0.05, None), (1, 128, 2, "float32", 20.0, None),
    (1, 64, 3, "float32", 0.5, 0.0), (2, 128, 2, "float32", 0.2, 0.999), (1, 256, 8, "bfloat16", 0.1, None)]


@pytest.mark.parametrize("rows,length,heads,dtype,decay,beta", KERNEL_CASES)
def test_the_kernels_are_the_jax_numpy_form_and_the_recurrence(rows, length, heads, dtype, decay, beta):
    """`ops/kda_kernels.py`, interpreted: a step's terms (Phi, B, Qe, P U) are
    `_chunk_terms`'; the op through the kernels (one, two, four heads a grid
    step; the state carried in scratch; the chunks in reverse for backward)
    gives the `jax.numpy` form's output, final state and five gradients and
    the token-by-token recurrence's (`benchmark/models/kimi_linear.py:
    kda_recurrence`), at float32, from float32 and from bf16 inputs, at a mild
    decay and at g = -20 a token (every output finite), beta drawn, 0 and near 1."""
    q, k, v, g, b = scan_inputs(length + heads, rows, length, heads, 8, 8, decay)
    b = b if beta is None else jnp.full_like(b, beta)
    q, k, v = (t.astype(dtype) for t in (q, k, v))
    # (a) the terms, as the kernels make them in VMEM (plain jax.numpy outside a kernel), chunk 0 of row 0
    chunks = kda_kernels._Chunks([tuple(t[0, :64, h].astype(jnp.float32) for t in (q, k, v, g)) + (b[0, :64, h, None],)
                                  for h in range(heads)], 16, lao._KDA_SAFE, lao._kernel_seams())
    want = lao._chunk_terms(q[0, :64], k[0, :64], v[0, :64], g[0, :64], b[0, :64, :, None], 64, 16)
    for mine, theirs in zip((chunks.phi, chunks.B, chunks.q_eff, chunks.own_out), want):
        # exp of two float32 sums of 64 terms, each summed in its own order; a term that cancels to 1e-6 (beta near 1) by its parts' size
        agree(jnp.stack(mine), theirs[0], tol=1e-4, floor=1e-3)

    # (b) the op
    def through(kernels):
        return lambda *a: lao.chunked_kda(*a[:4], a[4][..., None], 64, 16, kernels)

    out, state = through("interpret")(q, k, v, g, b)
    plain, plain_state = through(None)(q, k, v, g, b)
    floats = tuple(t.astype(jnp.float32) for t in (q, k, v)) + (g, b)
    recurred = kimi_linear.kda_recurrence(*floats)
    assert out.dtype == v.dtype and state.dtype == jnp.float32
    assert np.isfinite(np.asarray(out, "f4")).all() and np.isfinite(np.asarray(state)).all()
    agree(state, plain_state, tol=5e-5)
    agree(state, recurrence_with_state(*floats)[1], tol=5e-5)
    agree(out, plain, tol=5e-5 if dtype == "float32" else 8e-3)      # a bf16 output rounds once, either way
    agree(out, recurred, tol=5e-5 if dtype == "float32" else 8e-3)
    # (c) the gradients, of float32 inputs (a bf16 cotangent rounds each form's sum at another place)
    weigh = jnp.asarray(np.random.RandomState(1).randn(*out.shape).astype("f4"))
    grads = [jax.grad(lambda *a: jnp.sum(fn(*a)[0] * weigh), argnums=(0, 1, 2, 3, 4))(*floats)
             for fn in (through("interpret"), through(None), recurrence_with_state)]
    for name, mine, theirs, recurrences in zip("q k v g beta".split(), *grads):
        assert np.isfinite(np.asarray(mine)).all(), name
        # as above: lost to underflow on either side; the kernels' x . dx - k . dk leaves float32's rounding of two O(1) terms
        floor = 1e-2 if decay >= 20 and name == "g" else 1e-12
        agree(mine, theirs, tol=5e-5, floor=floor)
        agree(mine, recurrences, tol=5e-5, floor=floor)


@pytest.mark.parametrize("platform,devices,width,v_width,length,path", [
    ("tpu", 1, 128, 128, 4096, "kernels"), ("tpu", None, 128, 128, 64, "kernels"), ("tpu", 1, 256, 128, 128, "kernels"),
    ("cpu", 1, 128, 128, 4096, "xla"), (None, None, 128, 128, 4096, "xla"), ("tpu", 1, 64, 64, 4096, "xla"),
    ("tpu", 1, 128, 64, 4096, "xla"), ("tpu", 1, 128, 128, 32, "xla"), ("tpu", 4, 128, 128, 4096, "xla")])
def test_the_rule_takes_the_kernels_on_one_tpu_at_whole_lane_tiles_and_nowhere_else(platform, devices, width, v_width, length, path):
    """`_kda_path` reads the platform, the mesh, the two head widths and the
    chunk, and nothing else: no flag, environment variable or attribute."""
    q, v = jax.ShapeDtypeStruct((1, length, 2, width), jnp.bfloat16), jax.ShapeDtypeStruct((1, length, 2, v_width), jnp.bfloat16)
    mesh = None if devices is None else SimpleNamespace(size=devices)
    assert lao._kda_path(platform, mesh, q, v, min(lao._KDA_CHUNK, length)) == path
    import inspect
    assert not re.search(r"environ|getenv|FLAGS|\.attr\(", inspect.getsource(lao._kda_path) + inspect.getsource(lao._kda))


@pytest.mark.parametrize("key_heads,heads,scalar", [(2, 4, True), (4, 4, True), (2, 4, False), (1, 2, True)],
                         ids=["16_on_32s_kind", "a_decay_a_head_alone", "fewer_key_heads_alone", "two_value_heads_a_key_head"])
def test_a_decay_a_head_and_fewer_key_heads_through_the_kernels_are_the_jax_numpy_form(key_heads, heads, scalar):
    """ISSUE 69: g [b, T, H] (one decay a head: the kernels take it out of the
    chunk's Grams and read and write it as a head's row) and q, k of fewer heads
    than v (a group's key heads come through the index map; a key head's
    gradient is its value heads' summed in the kernel): output, state and every
    gradient against the `jax.numpy` form, which writes both out, and the
    gradients in the shapes the op was handed."""
    q, k, v, g, beta = scan_inputs(11, 2, 128, heads, 8, 8, 0.3)
    q, k = q[:, :, :key_heads], k[:, :, :key_heads]
    g = g[..., 0] if scalar else g
    weigh = jnp.asarray(np.random.RandomState(1).randn(*v.shape).astype("f4"))

    def through(kernels):
        op = lambda *a: lao.chunked_kda(*a[:4], a[4][..., None], 64, 16, kernels)
        return op(q, k, v, g, beta), jax.grad(lambda *a: jnp.sum(op(*a)[0] * weigh), argnums=(0, 1, 2, 3, 4))(q, k, v, g, beta)

    (out, state), grads = through("interpret")
    (plain, plain_state), plain_grads = through(None)
    assert [t.shape for t in grads] == [t.shape for t in (q, k, v, g, beta)]
    for mine, theirs in ((out, plain), (state, plain_state)) + tuple(zip(grads, plain_grads)):
        assert np.isfinite(np.asarray(mine)).all()
        agree(mine, theirs, tol=3e-5)


def test_the_rule_takes_the_kernels_where_a_grid_steps_value_heads_are_whole_key_heads():
    """16 key heads feeding 32 value heads: four value heads a grid step read two key heads.  Eight value heads on one
    key head leave no step of (4, 2, 1) heads whole key heads: the `jax.numpy` form."""
    shape = lambda heads: jax.ShapeDtypeStruct((1, 4096, heads, 128), jnp.bfloat16)     # noqa: E731
    assert lao._kda_path("tpu", None, shape(16), shape(32), 64) == "kernels" and kda_kernels.heads_a_step(32, 2) == 4
    assert lao._kda_path("tpu", None, shape(8), shape(32), 64) == "kernels" and kda_kernels.heads_a_step(32, 4) == 4
    assert lao._kda_path("tpu", None, shape(4), shape(32), 64) == "xla" and kda_kernels.heads_a_step(32, 8) is None
    assert kda_kernels.heads_a_step(6, 2) == 2 and kda_kernels.heads_a_step(3, 1) == 1


KEPT_CASES = [KERNEL_CASES[1], KERNEL_CASES[3], KERNEL_CASES[5], KERNEL_CASES[7], KERNEL_CASES[8], "a_channel_dies"]


@pytest.mark.parametrize("case", KEPT_CASES, ids=lambda c: c if isinstance(c, str) else "-".join(map(str, c)))
def test_the_differentiated_forward_keeps_the_chunks_start_states_and_is_the_plain_call(case):
    """Under `jax.vjp` the kernels' forward (`_chunked_kda_fwd`: ONE `kda_scan`
    call with two more outputs) gives the plain call's o and final state to
    the bit, and what it keeps beside the five inputs is the state every chunk
    starts from, [n, b, H, K, V] float32: zero, then Phi_c S_c + B_c of
    `_chunk_terms` chunk after chunk up to the final state, the `jax.numpy`
    form's `_states`; and T, unit lower triangular, the inverse of I + beta M,
    laid out [n, b, H, C / 2, 2 C] (its upper rows beside its lower: a whole
    lane tile wide).  Backward reads them and makes neither."""
    if case == "a_channel_dies":
        q, k, v, g, b = dying_channel_inputs()
    else:
        rows, length, heads, dtype, decay, beta = case
        q, k, v, g, b = scan_inputs(length + heads, rows, length, heads, 8, 8, decay)
        b = b if beta is None else jnp.full_like(b, beta)
        q, k, v = (t.astype(dtype) for t in (q, k, v))
    op = lambda *a: lao.chunked_kda(*a[:4], a[4][..., None], 64, 16, "interpret")  # noqa: E731
    out, final = op(q, k, v, g, b)
    (under_vjp, final_under_vjp), _ = jax.vjp(op, q, k, v, g, b)
    (kept_out, kept_final), (inputs, (starts, inverses)) = lao._chunked_kda_fwd(q, k, v, g, b[..., None], 64, 16, "interpret")
    for mine, plain in ((under_vjp, out), (final_under_vjp, final), (kept_out, out), (kept_final, final)):
        assert mine.dtype == plain.dtype and (np.asarray(mine, "f4") == np.asarray(plain, "f4")).all()
    assert len(inputs) == 5 and all(kept is given for kept, given in zip(inputs[:4], (q, k, v, g)))
    (rows, length, heads, width), n = k.shape, k.shape[1] // 64
    assert starts.shape == (n, rows, heads, width, v.shape[-1]) and starts.dtype == jnp.float32
    assert inverses.shape == (n, rows, heads, 32, 128) and inverses.dtype == jnp.float32
    assert np.isfinite(np.asarray(starts)).all() and not np.asarray(starts[0]).any()
    for row in range(rows):
        phi, B, _, _ = lao._chunk_terms(q[row], k[row], v[row], g[row], b[row, :, :, None], 64, 16)
        follows = jnp.concatenate([starts[1:, row], final[None, row]])          # what each chunk hands on
        scale = max(float(jnp.abs(follows).max()), 1e-12)
        for c in range(n):
            assert float(jnp.abs(lao._mm("hkj,hjv->hkv", phi[c], starts[c, row]) + B[c] - follows[c]).max()) <= 5e-5 * scale
        plain_starts, plain_final = lao._states(phi, B)
        agree(starts[:, row], plain_starts, tol=5e-5, floor=1e-6)
        agree(final[row], plain_final, tol=5e-5)
        for c in range(n):
            at = slice(64 * c, 64 * (c + 1))
            terms = kda_kernels._Chunks([tuple(t[row, at, h].astype(jnp.float32) for t in (q, k, v, g)) + (b[row, at, h, None],)
                                         for h in range(heads)], 16, lao._KDA_SAFE, lao._kernel_seams())
            for h in range(heads):
                T = np.asarray(kda_kernels._halves_stacked(inverses[c, row, h]), "f8")
                assert (np.asarray(kda_kernels._halves_side_by_side(T)) == np.asarray(inverses[c, row, h])).all()
                assert (np.triu(T, 1) == 0).all() and (np.diag(T) == 1).all()
                agree((np.eye(64) + np.asarray(terms.beta[h] * terms.M[h], "f8")) @ T, np.eye(64), tol=2e-5)
    # the xla path keeps the five inputs alone
    assert lao._chunked_kda_fwd(q, k, v, g, b[..., None], 64, 16, None)[1][1] == ()


def pallas_calls(jaxpr):
    """(name, outputs' shapes) of every `pallas_call` of a jaxpr, its sub-jaxprs' too."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            found.append((eqn.params["name"], [v.aval.shape for v in eqn.outvars]))
        for inner in jax.core.jaxprs_in_params(eqn.params):
            found += pallas_calls(inner)
    return found


@pytest.mark.parametrize("rows,length,heads", [(1, 128, 2), (2, 256, 4), (1, 64, 3)])
def test_the_gradient_is_two_kernel_calls_and_the_plain_op_one_with_two_outputs(rows, length, heads):
    """What is traced for the TPU: the op's gradient holds ONE `kda_scan` (o,
    the final state, the chunks' start states and T) and ONE
    `kda_scan_transposed`, and no call that makes the start states again; the
    plain op ONE `kda_scan` that writes o and the final state and keeps nothing."""
    q, k, v, g, b = scan_inputs(3, rows, length, heads, 128, 128, 0.1)
    op = lambda *a: lao.chunked_kda(*a[:4], a[4][..., None], 64, 16, "tpu")  # noqa: E731
    n, o, final = length // 64, (rows, length, heads * 128), (rows, heads, 128, 128)
    assert pallas_calls(jax.make_jaxpr(op)(q, k, v, g, b).jaxpr) == [("kda_scan", [o, final])]
    grad = jax.make_jaxpr(jax.grad(lambda *a: jnp.sum(op(*a)[0]), argnums=(0, 1, 2, 3, 4)))(q, k, v, g, b)
    calls = pallas_calls(grad.jaxpr)
    assert [name for name, _ in calls] == ["kda_scan", "kda_scan_transposed"] and "kda_scan_starts" not in str(grad)
    assert calls[0][1] == [o, final, (n, rows, heads, 128, 128), (n, rows, heads, 32, 128)]
    assert calls[1][1] == [o, o, o, o, (rows, n, -(-heads // kda_kernels.heads_a_step(heads)), kda_kernels.heads_a_step(heads), 64)]


def kda_lowered(platform):
    """The op `kda`'s lowering for `platform`, as a function of its inputs."""
    op = SimpleNamespace(type="kda", attr=lambda n, d=None: d)
    ctx = LoweringContext(jax.random.PRNGKey(0), platform=platform)
    return lambda ins: get_op_def("kda").lower(ctx, op, ins)["Out"]


@pytest.mark.parametrize("platform,differentiated,kept", [("tpu", True, 1), ("tpu", False, 0), ("cpu", True, 0), ("cpu", False, 0)])
def test_the_counter_says_whose_forward_kept_its_start_states(platform, differentiated, kept):
    """`lowering.kda_starts_kept` counts, at trace time, the `kda` ops whose
    forward wrote the chunks' start states for backward: the kernels' where the
    op is differentiated (`custom_vjp`'s forward rule), and no other: not a
    plain call (the `for_test` clone, inference), not the `jax.numpy` form."""
    q, k, v, g, beta = scan_inputs(5, 1, 64, 2, 128, 128, 0.1)
    ins = {n: [jnp.asarray(t)] for n, t in zip(("Q", "K", "V", "G", "Beta"), (q, k, v, g, beta))}
    fn = lambda ins: jnp.sum(kda_lowered(platform)(ins))  # noqa: E731
    monitor.reset()
    monitor.enable()
    try:
        jax.make_jaxpr(jax.grad(fn) if differentiated else fn)(ins)                # traced for the platform, not run
        counters = monitor.get_monitor().counter_values()
    finally:
        monitor.disable()
        monitor.reset()
    assert counters.get("lowering.kda_starts_kept", 0) == kept
    assert counters.get("lowering.kda_kernel_calls", 0) == (platform == "tpu")
    assert counters.get("lowering.kda_kernel_transposed_calls", 0) == (platform == "tpu" and differentiated)
    assert counters["lowering.kda_layers"] == 1


def test_the_counter_says_which_kda_ops_took_the_kernels():
    """`lowering.kda_kernel_calls` counts, at trace time, the `kda` ops whose
    lowering took the kernels (`lowering.kda_kernel_transposed_calls` their
    backward, `lowering.kda_starts_kept` the forwards that kept the chunks'
    start states for it): one on the TPU at 128-wide heads, none off it, where
    the op's numbers are the `jax.numpy` form's to the bit."""
    q, k, v, g, beta = scan_inputs(5, 1, 64, 2, 128, 128, 0.1)
    ins = {n: [jnp.asarray(t)] for n, t in zip(("Q", "K", "V", "G", "Beta"), (q, k, v, g, beta))}
    lowered = kda_lowered
    counter = lambda name: monitor.get_monitor().counter_values().get(name, 0)
    monitor.reset()
    monitor.enable()
    try:
        traced = jax.make_jaxpr(jax.grad(lambda ins: jnp.sum(lowered("tpu")(ins))))(ins)       # traced for the TPU, not run
        assert counter("lowering.kda_kernel_calls") == 1 and counter("lowering.kda_kernel_transposed_calls") == 1
        assert counter("lowering.kda_layers") == 1 and counter("lowering.kda_starts_kept") == 1
        assert str(traced).count("pallas_call") == 2 and "kda_scan_transposed" in str(traced)      # o with what is kept, the transpose
        out = lowered("cpu")(ins)
        assert counter("lowering.kda_kernel_calls") == 1 and counter("lowering.kda_layers") == 2
        assert counter("lowering.kda_starts_kept") == 1
    finally:
        monitor.disable()
        monitor.reset()
    assert (np.asarray(out) == np.asarray(lao.chunked_kda(q, k, v, g, beta[..., None])[0])).all()
