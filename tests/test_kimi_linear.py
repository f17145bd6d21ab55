"""Kimi-Linear-48B-A3B's parts and the whole, tiny on the CPU (ISSUE 42).

(a) the op `kda`, chunk by chunk, against the token-by-token recurrence:
    forward, the final state and the hand-written backward against `jax.grad`
    of the recurrence, at several chunk counts, at mild and at strong decay
    (g = -20 a token: finite everywhere), and the faults the benchmark's stage
    has to refuse; `kda_gate`; `infer=`, the planner rows, `analysis.verify`;
    since ISSUE 44 the Pallas kernels of `ops/kda_kernels.py`, interpreted,
    against both, the rule that takes them and the counter that says so;
    since ISSUE 45 the chunks' start states that the differentiated forward
    keeps for backward, the two kernel calls of a gradient and their counter;
(b) `short_conv`'s plain mode against four shifted multiply-adds, forward and
    gradients, and the gated mode's lowered text unchanged;
(c) `fused_attention` with values of another width than queries and keys
    against the dense reference at (192, 128), and the shapes that run today
    choosing what they choose today;
(d) the shared expert beside the routed ones; the 32 shares of 8 experts, the
    shared expert counted once, add up to the uncut layer;
(e) a tiny `build_causal_lm` (kda dense, kda, kda, latent_attention, kda) in
    float32 against the benchmark's reference (benchmark/models/kimi_linear.py)
    on seeded weights: loss, logits, routing, every stage, every parameter's
    gradient; in bf16 within the benchmark's tolerances;
(f) steps through `train_loop` publish the `kda_state` record and the counters.
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
from benchmark.models import kimi_linear  # noqa: E402
from paddle_tpu import layers, monitor  # noqa: E402
from paddle_tpu.core.lowering import LoweringContext  # noqa: E402
from paddle_tpu.core.registry import get_op_def  # noqa: E402
from paddle_tpu.models import transformer  # noqa: E402
from paddle_tpu.ops import kda_kernels  # noqa: E402
from paddle_tpu.ops import linear_attention_ops as lao  # noqa: E402
from paddle_tpu.ops import nn_ops  # noqa: E402


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


# -- (a) the chunked recurrence ---------------------------------------------------------

def scan_inputs(seed, rows, length, heads, width, v_width, decay):
    """q, k unit a head, v, a log decay of `decay` x |N(0, 1)| a channel (or
    exactly -`decay` a token where `decay` >= 20) and beta in (0, 1)."""
    r = np.random.RandomState(seed)
    q, k = (r.randn(rows, length, heads, width).astype("f4") for _ in range(2))
    q, k = (t / np.linalg.norm(t, axis=-1, keepdims=True) for t in (q, k))
    v = r.randn(rows, length, heads, v_width).astype("f4")
    g = -decay * (np.ones_like(q) if decay >= 20 else np.abs(r.randn(rows, length, heads, width))).astype("f4")
    beta = (1 / (1 + np.exp(-r.randn(rows, length, heads)))).astype("f4")
    return tuple(jnp.asarray(t) for t in (q, k, v, g, beta))


def recurrence_with_state(q, k, v, g, beta):
    """(o, the state after the last token) of the recurrence, a token at a time."""
    def step(S, token):
        q_t, k_t, v_t, g_t, beta_t = token
        S = S * jnp.exp(g_t)[..., None]
        S = S + (beta_t[..., None] * k_t)[..., None] * (v_t - jnp.einsum("rhkv,rhk->rhv", S, k_t))[..., None, :]
        return S, jnp.einsum("rhkv,rhk->rhv", S, q_t)

    tokens = tuple(t.swapaxes(0, 1) for t in (q, k, v, g, beta))
    S, o = jax.lax.scan(step, jnp.zeros(k.shape[:1] + k.shape[2:] + v.shape[-1:]), tokens)
    return o.swapaxes(0, 1), S


CASES = [  # rows, length, chunk, decay
    (2, 64, 64, 0.1), (1, 128, 32, 1.0), (2, 256, 64, 0.02), (1, 64, 16, 20.0), (1, 128, 64, 20.0),
    (1, 48, 24, 3.0), (1, 1024, 64, 0.3), (2, 16, 16, 0.5), (1, 4, 4, 0.5)]


@pytest.mark.parametrize("rows,length,chunk,decay", CASES)
def test_the_chunked_recurrence_is_the_recurrence_forward_and_backward(rows, length, chunk, decay):
    """The chunked form, whatever the chunk (one chunk, many, a group of chunks
    at a time from 16 chunks on, blocks of 16, 4 and 1 or fewer levels), gives
    the token-by-token recurrence's output and final state, and its hand-written
    backward `jax.grad` of the recurrence, for all five inputs.  At g = -20 a
    token (alpha = 2e-9) everything is finite and still the recurrence."""
    args = scan_inputs(length + chunk, rows, length, 3, 8, 5, decay)
    blocks = lao._blocks_of(chunk)

    def op(*a):
        return lao.chunked_kda(*a[:4], a[4][..., None], chunk, blocks)

    out, state = op(*args)
    want, want_state = recurrence_with_state(*args)
    assert np.isfinite(np.asarray(out)).all() and np.isfinite(np.asarray(state)).all()
    agree(out, want, tol=2e-5)
    agree(state, want_state, tol=2e-5)
    weigh = jnp.asarray(np.random.RandomState(1).randn(*out.shape).astype("f4"))
    got = jax.grad(lambda *a: jnp.sum(op(*a)[0] * weigh), argnums=(0, 1, 2, 3, 4))(*args)
    ref = jax.grad(lambda *a: jnp.sum(recurrence_with_state(*a)[0] * weigh), argnums=(0, 1, 2, 3, 4))(*args)
    for name, mine, theirs in zip("q k v g beta".split(), got, ref):
        assert np.isfinite(np.asarray(mine)).all(), name
        # at alpha = 2e-9 the decay's own gradient is of the order of 1e-9 and lost to underflow on either side
        agree(mine, theirs, tol=5e-5, floor=1e-3 if decay >= 20 and name == "g" else 1e-12)


def test_no_exponent_is_positive_in_a_channel_that_dies_in_one_token():
    """One token forgets a channel outright (g = -100 there) between mild
    decays: the pairs on either side of it are still exact, where a Gram
    factored about the chunk's start would meet exp(100)."""
    q, k, v, g, beta = scan_inputs(7, 1, 64, 2, 8, 8, 0.05)
    g = g.at[:, 5, :, 3].set(-100.0).at[:, 37, :, :2].set(-60.0)
    out, state = lao.chunked_kda(q, k, v, g, beta[..., None], 64, lao._blocks_of(64))
    want, want_state = recurrence_with_state(q, k, v, g, beta)
    agree(out, want, tol=2e-5)
    agree(state, want_state, tol=2e-5)


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
    assert calls[1][1] == [o, o, o, o, (rows, n, -(-heads // kda_kernels._heads_a_step(heads)), kda_kernels._heads_a_step(heads), 64)]


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


def test_the_benchmarks_recurrence_is_the_same_and_a_bf16_state_is_not():
    args = scan_inputs(3, 2, 96, 2, 8, 8, 0.2)
    want, _ = recurrence_with_state(*args)
    agree(kimi_linear.kda_recurrence(*args), want, tol=1e-6)
    low = kimi_linear.kda_recurrence(*args, bf16_state=True)
    assert np.abs(np.asarray(low) - np.asarray(want)).max() > 1e-3 * np.abs(np.asarray(want)).max()


@pytest.mark.parametrize("fault", ["bf16_state", "bf16_cumulative_decay", "no_decay", "bf16_output_only"])
def test_the_kda_stage_tells_the_faults_apart(fault, monkeypatch):
    """The benchmark's KDA stage (`kimi_linear.kda_errors`: the op's output
    against the recurrence on the op's own inputs) reads the sound op at its
    output's rounding and each fault above it: the state kept in bf16 from
    chunk to chunk, the cumulative decay rounded to bf16, Diag(alpha) dropped.
    (tools/chip_kimi_controls.py shows the same at the published widths against
    the limit `KDA_RTOL`, which two chip readings set.)"""
    q, k, v, g, beta = scan_inputs(11, 2, 512, 2, 16, 16, 0.3)
    q, k, v = (t.astype(jnp.bfloat16) for t in (q, k, v))
    if fault == "bf16_state":
        def rounded(phi, B):
            def step(S, term):
                return jax.lax.reduce_precision(lao._mm("hkj,hjv->hkv", term[0], S) + term[1], 8, 7), S
            final, starts = jax.lax.scan(step, jnp.zeros(B.shape[1:], jnp.float32), (phi, B))
            return starts, final
        monkeypatch.setattr(lao, "_states", rounded)
    elif fault == "bf16_cumulative_decay":
        real = lao._cumulative
        monkeypatch.setattr(lao, "_cumulative", lambda g: jax.lax.reduce_precision(real(g), 8, 7))
    out = lower("kda", {"Q": q, "K": k, "V": v, "G": 0 * g if fault == "no_decay" else g, "Beta": beta})["Out"]
    assert out.dtype == jnp.bfloat16
    found = kimi_linear.kda_errors([(q, k, v, g, beta, out)])
    if fault == "bf16_output_only":
        # against the float32 recurrence the output's own rounding is all there is to see (2^-9 / sqrt(3) and more);
        # against the recurrence rounded alike, only the elements whose last float32 bits cross a rounding boundary
        assert 0.2 * 2.0 ** -9 < found["kda_error_unrounded"] < 2.0 ** -9, found
        assert found["kda_error"] < 1e-4 < 3e-4 < found["kda_error_bf16_state"], found
    else:
        assert found["kda_error"] > 3e-4, found


def test_kda_publishes_its_state_and_kda_gate_is_the_published_decay():
    q, k, v, g, beta = scan_inputs(5, 1, 32, 2, 8, 8, 0.1)
    outs = lower("kda", {"Q": q, "K": k, "V": v, "G": g, "Beta": beta})
    _, state = recurrence_with_state(q, k, v, g, beta)
    agree(outs["Stats"], [np.exp(np.asarray(g)).mean(), np.asarray(beta).mean(), np.abs(np.asarray(state)).max()], tol=1e-5)
    x = np.random.RandomState(2).randn(2, 6, 3 * 4).astype("f4")
    a_log, dt_bias = np.log([1.0, 4.0, 16.0]).astype("f4"), np.random.RandomState(3).randn(12).astype("f4")
    got = lower("kda_gate", {"X": jnp.asarray(x).astype(jnp.bfloat16), "ALog": a_log, "DtBias": dt_bias})["Out"]
    assert got.dtype == jnp.float32 and got.shape == (2, 6, 3, 4)
    rounded = np.asarray(jnp.asarray(x).astype(jnp.bfloat16).astype(jnp.float32))
    agree(got, -np.exp(a_log)[:, None] * np.log1p(np.exp(rounded + dt_bias)).reshape(2, 6, 3, 4), tol=1e-5)
    assert (np.asarray(got) < 0).all()


def test_the_new_ops_have_infer_rules_planner_rows_and_pass_verify():
    from paddle_tpu.core import analysis, resource_plan

    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = layers.data("x", [128, 24], dtype="float32")
        y = transformer.kimi_delta_attention(x, 24, n_heads=2, head_dim=8, prefix="t.kda")
        z = transformer.latent_attention(y, 24, 2, "t.attn", rank=12, nope_dim=8, rope_dim=4, v_dim=8)
    assert tuple(y.shape)[1:] == (128, 24) and tuple(z.shape)[1:] == (128, 24)
    assert [d for d in analysis.verify_program(main, level="full") if d.severity == "error"] == []
    shapes = {op.type: tuple(main.global_block().var(op.outputs["Out"][0]).shape)[1:]
              for op in main.global_block().ops if op.type in ("kda", "kda_gate", "short_conv", "fused_attention")}
    assert shapes == {"kda": (128, 2, 8), "kda_gate": (128, 2, 8), "short_conv": (128, 16), "fused_attention": (128, 2, 8)}
    plan = resource_plan.plan_program(main, feed_shapes={"x": (2, 128, 24)})
    rows = {r.op_type: r for r in plan.rows}
    assert rows["kda"].flops == lao.kda_chunk_flops(2 * 128, 2, 8, 8) == kimi_linear._chunk_flops(2 * 128, 2, 8, 8)
    assert rows["kda"].traffic_bytes == 4 * (4 * 2 * 128 * 16 + 2 * 128 * 2 + 2 * 128 * 16 + 3)
    assert rows["fused_attention"].flops == 2.0 * 2 * 2 * (12 + 8) * 128 * 128   # QK^T over 12, PV over 8
    assert rows["short_conv"].flops == (4 + 2 * 4) * 2 * 128 * 16                 # the SiLU and four taps
    # shapes the rules refuse
    for bad in (dict(G=(2, 128, 2, 4)), dict(Beta=(2, 128, 1)), dict(K=(2, 128, 2, 4))):
        with pytest.raises(Exception, match="kda|Beta|log decay|Q and K"):
            with fluid.program_guard(fluid.Program(), fluid.Program()):
                shapes = {**dict(Q=(2, 128, 2, 8), K=(2, 128, 2, 8), V=(2, 128, 2, 8), G=(2, 128, 2, 8), Beta=(2, 128, 2)), **bad}
                ins = {n: layers.data(n, list(s[1:]), dtype="float32") for n, s in shapes.items()}
                layers.kda(*(ins[n] for n in ("Q", "K", "V", "G", "Beta")))
                problems = [d for d in analysis.verify_program(fluid.default_main_program(), level="full") if d.severity == "error"]
                assert not problems, f"kda: {problems}"


# -- (b) the plain short convolution ---------------------------------------------------

def plain_conv_golden(x, w):
    taps, out = w.shape[1], jnp.zeros_like(x)
    for t in range(x.shape[1]):
        for j in range(taps):
            if t - (taps - 1) + j >= 0:
                out = out.at[:, t].add(w[:, j] * x[:, t - (taps - 1) + j])
    return jax.nn.silu(out)


@pytest.mark.parametrize("taps,length", [(4, 9), (4, 3), (3, 7), (1, 5), (4, 1)])
def test_the_plain_short_convolution_is_four_shifted_multiply_adds_and_a_silu(taps, length):
    rng = np.random.RandomState(taps * 10 + length)
    x, w = rng.randn(2, length, 5).astype("f4"), rng.randn(5, taps).astype("f4")
    weigh = rng.randn(2, length, 5).astype("f4")
    attrs = {"gated": False, "activation": "silu"}
    agree(lower("short_conv", {"X": x, "Filter": w}, attrs)["Out"], plain_conv_golden(jnp.asarray(x), jnp.asarray(w)), tol=1e-6)
    got = jax.grad(lambda a, b: jnp.sum(lower("short_conv", {"X": a, "Filter": b}, attrs)["Out"] * weigh),
                   argnums=(0, 1))(jnp.asarray(x), jnp.asarray(w))
    want = jax.grad(lambda a, b: jnp.sum(plain_conv_golden(a, b) * weigh), argnums=(0, 1))(jnp.asarray(x), jnp.asarray(w))
    agree(got[0], want[0], tol=1e-5)
    agree(got[1], want[1], tol=1e-5)
    agree(kimi_linear._plain_conv(x, w), plain_conv_golden(jnp.asarray(x), jnp.asarray(w)), tol=1e-6)   # the stage check's numpy form
    low = jnp.asarray(x).astype(jnp.bfloat16)                 # computed in float32 from bf16 and rounded once
    out = lower("short_conv", {"X": low, "Filter": w}, attrs)["Out"]
    assert out.dtype == jnp.bfloat16
    exact = np.asarray(plain_conv_golden(low.astype(jnp.float32), jnp.asarray(w)))
    assert np.abs(np.asarray(out.astype(jnp.float32)) - exact).max() <= 2.0 ** -8 * np.abs(exact).max()


def test_the_plain_mode_is_an_attribute_of_the_one_op_and_the_gated_modes_text_is_unchanged():
    """`layers.short_conv(gated=False, activation="silu")` appends the same op
    type with two attributes and no projection; the gated layer's op carries
    no new attribute, and its lowered text is the parent's (recorded from
    commit 32f0c9c by this test's own code)."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = layers.data("x", [6, 8], dtype="float32")
        layers.short_conv(x, kernel_size=4, filter_attr="c.taps", gated=False, activation="silu")
        layers.short_conv(x, kernel_size=3)
    plain, gated = [op for op in main.global_block().ops if op.type == "short_conv"]
    assert plain.attrs == {"gated": False, "activation": "silu"} and not gated.attrs
    assert [op.type for op in main.global_block().ops] == ["short_conv", "mul", "short_conv", "mul"]
    with pytest.raises(Exception, match="the gated form has none"):
        with fluid.program_guard(fluid.Program(), fluid.Program()):
            from paddle_tpu.core import analysis
            layers.short_conv(layers.data("x", [6, 8], dtype="float32"), gated=False, activation="tanh")
            problems = [d for d in analysis.verify_program(fluid.default_main_program(), level="full") if d.severity == "error"]
            assert not problems, f"the gated form has none: {problems}"

    def gated_step(x, w, g):
        out, pull = jax.vjp(lambda x, w: lower("short_conv", {"X": x, "Filter": w})["Out"], x, w)
        return (out,) + pull(g)

    text = jax.jit(gated_step).lower(jax.ShapeDtypeStruct((2, 16, 24), jnp.bfloat16), jax.ShapeDtypeStruct((8, 3), jnp.float32),
                                     jax.ShapeDtypeStruct((2, 16, 8), jnp.bfloat16)).as_text()
    text = re.sub(r"loc\(.*?\)|#loc.*", "", text)
    assert hashlib.sha256(text.encode()).hexdigest() == GATED_SHORT_CONV_TEXT


#: sha256 of the gated short convolution's lowered step above, at the parent commit (32f0c9c)
GATED_SHORT_CONV_TEXT = "2edaa7ab09cef1a3325b93f2237d717383031e080df284aa2410b522b18eedca"


# -- (c) values of another width --------------------------------------------------------

def dense_attention(q, k, v, scale):
    """softmax(q k^T scale, causal) v over (B, L, H, d), float64."""
    q, k, v = (np.asarray(t, "f8").transpose(0, 2, 1, 3) for t in (q, k, v))
    s = np.einsum("bhqd,bhkd->bhqk", q, k) * scale
    s = np.where(np.tril(np.ones(s.shape[-2:], bool)), s, -np.inf)
    p = np.exp(s - s.max(-1, keepdims=True))
    return np.einsum("bhqk,bhkd->bhqd", p / p.sum(-1, keepdims=True), v).transpose(0, 2, 1, 3)


def test_attention_takes_values_of_another_width_than_queries_and_keys():
    rng = np.random.RandomState(4)
    q, k = (rng.randn(2, 24, 3, 192).astype("f4") / 4 for _ in range(2))
    v = rng.randn(2, 24, 3, 128).astype("f4")
    out = lower("fused_attention", {"Q": q, "K": k, "V": v}, {"causal": True, "layout": "blhd"})["Out"]
    assert out.shape == (2, 24, 3, 128)
    agree(out, dense_attention(q, k, v, 192 ** -0.5), tol=1e-5)
    heads_major = lower("fused_attention", {n: t.transpose(0, 2, 1, 3) for n, t in (("Q", q), ("K", k), ("V", v))},
                        {"causal": True})["Out"]
    agree(heads_major.transpose(0, 2, 1, 3), out, tol=1e-6)
    grads = jax.grad(lambda q, k, v: jnp.sum(jnp.square(lower(
        "fused_attention", {"Q": q, "K": k, "V": v}, {"causal": True, "layout": "blhd"})["Out"])), argnums=(0, 1, 2))(q, k, v)
    assert [g.shape for g in grads] == [q.shape, k.shape, v.shape]


def test_the_attentions_rule_reads_the_values_width_and_leaves_todays_shapes_where_they_were():
    bf16 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.bfloat16)  # noqa: E731
    path = lambda q, k, v=None, **kw: nn_ops._attention_path("tpu", None, q, k, v_width=v, **kw)  # noqa: E731
    # latent attention at the cell's shape: the splash kernels, the widths as they are
    assert path(bf16(1, 4096, 32, 192), bf16(1, 4096, 32, 192), 128, causal=True, layout="blhd") == "block_causal"
    # what no kernel here was ever given: a bias or no causal mask at two widths, short rows at two widths, a structured mask
    assert path(bf16(1, 32, 4096, 192), bf16(1, 32, 4096, 192), 128, causal=True, biased=True) == "xla"
    assert path(bf16(1, 32, 4096, 192), bf16(1, 32, 4096, 192), 128) == "xla"
    assert path(bf16(1, 12, 512, 64), bf16(1, 12, 512, 64), 128) == "xla"
    assert path(bf16(1, 32, 8192, 128), bf16(1, 32, 8192, 128), 64, mask=("block_diffusion", 4)) == "xla"
    # one width: what they chose at the parent
    for v_width in (None, 128):
        assert path(bf16(4, 16, 4096, 128), bf16(4, 16, 4096, 128), v_width, causal=True) == "block_causal"
        assert path(bf16(4, 16, 4096, 128), bf16(4, 16, 4096, 128), v_width, causal=True, biased=True) == "flash"
        assert path(bf16(2, 32, 8192, 128), bf16(2, 4, 8192, 128), v_width, mask=("block_diffusion", 4)) == "block_sparse"
    assert path(bf16(32, 512, 12, 64), bf16(32, 512, 12, 64), 64, layout="blhd") == "row_kernel"
    assert path(bf16(256, 128, 12, 64), bf16(256, 128, 12, 64), 64, layout="blhd") == "xla"


# -- (d) the shared expert, and the shares ---------------------------------------------------

def sigmoid(x):
    return 1.0 / (1.0 + np.exp(-x))


def test_the_shared_expert_is_added_once_beside_the_routed_sum_and_outside_the_held_path():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = layers.data("x", [6, 16], dtype="float32")
        out, _, _ = layers.moe(x, 8, 4, 2, norm_topk_prob=True, held=(2, 2), scoring="sigmoid", shared_experts=1,
                               shared_attrs=("s.gate", "s.up", "s.down"), gate_attr="e.gate")
    ops = main.global_block().ops
    assert [op.type for op in ops] == ["moe_router", "moe_experts", "mul", "swish", "mul", "elementwise_mul", "mul",
                                       "elementwise_add"]
    assert ops[1].attrs["shared_experts"] == 1 and ops[1].attrs["held"] == [2, 2]
    shapes = {p.name: tuple(p.shape) for p in main.all_parameters()}
    assert shapes["s.gate"] == shapes["s.up"] == (16, 4) and shapes["s.down"] == (4, 16) and shapes["e.gate"] == (2, 16, 4)
    scope, exe = fluid.Scope(), fluid.Executor(fluid.TPUPlace(0))
    exe.run(startup, scope=scope)
    rows = np.random.RandomState(1).randn(3, 6, 16).astype("f4")
    routed = ops[1].outputs["Out"][0]
    got, got_routed = exe.run(main, feed={"x": rows}, fetch_list=[out.name, routed], scope=scope)
    gate, up, down = (np.asarray(scope.find_var(n), "f8") for n in ("s.gate", "s.up", "s.down"))
    h = rows.astype("f8") @ gate
    agree(np.asarray(got) - np.asarray(got_routed), (h * sigmoid(h) * (rows @ up)) @ down, tol=1e-5)
    # a layer without one is the parent's: no attribute, no op
    with fluid.program_guard(fluid.Program(), fluid.Program()):
        layers.moe(layers.data("x", [6, 16], dtype="float32"), 8, 4, 2)
        assert [op.type for op in fluid.default_main_program().global_block().ops] == ["moe_router", "moe_experts"]
        assert "shared_experts" not in fluid.default_main_program().global_block().ops[1].attrs


def test_the_32_shares_of_a_layer_and_the_shared_expert_once_add_up_to_the_layer():
    """32 chips hold 8 of 256 experts each behind THIS router (sigmoid scores,
    the choice by score + bias, the eight unbiased scores renormalised over all
    eight with the 1e-20, times 2.446) and each computes the shared expert
    alike.  The 32 routed parts, summed, and the shared expert's output ONCE
    are the uncut layer's output as the plain reference writes it."""
    rng = np.random.RandomState(42)
    tokens, experts, k, d, f, scaling = 64, 256, 8, 16, 8, 2.446
    x = rng.randn(tokens, d).astype("f4")
    router = rng.randn(d, experts).astype("f4") / 2
    bias = (rng.randn(experts) * 0.1).astype("f4")
    gate, up = (rng.randn(experts, d, f).astype("f4") / 4 for _ in range(2))
    down = rng.randn(experts, f, d).astype("f4") / 4
    s_gate, s_up, s_down = rng.randn(d, f).astype("f4") / 4, rng.randn(d, f).astype("f4") / 4, rng.randn(f, d).astype("f4") / 4
    routed = lower("moe_router", {"X": x, "W": router, "Bias": bias},
                   {"top_k": k, "norm_topk_prob": True, "scoring": "sigmoid", "norm_eps": 1e-20,
                    "routed_scaling_factor": scaling})

    def share(first, count):
        ins = {"X": x, "TopKProb": routed["TopKProb"], "TopKIndex": routed["TopKIndex"], "Load": routed["Load"],
               "WGate": gate[first:first + count], "WUp": up[first:first + count], "WDown": down[first:first + count]}
        return lower("moe_experts", ins, {"held": [first, count], "shared_experts": 1})

    shares = [share(first, 8) for first in range(0, experts, 8)]
    assert sum(int(np.asarray(s["Held"])[0]) for s in shares) == tokens * k
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
    # 32 times the shared expert would be another layer
    assert np.abs(31 * shared).max() > 1e-2 * np.abs(want).max()


# -- (e) the whole model against the benchmark's reference ------------------------------------

TINY = dict(hidden_size=48, num_attention_heads=2, intermediate_size=96, moe_intermediate_size=16,
            num_experts=4, num_routed_experts=32, experts_held_first=4, num_experts_per_token=4, vocab_size=96,
            kv_lora_rank=24, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16, expert_bias_std=0.05,
            linear_attn_config=dict(num_heads=2, head_dim=16, short_conv_kernel_size=4, kda_layers=[1, 2, 3, 5],
                                    full_attn_layers=[4]))
JOB = dict(seq_len=128, batch_per_chip=4)


@pytest.fixture(scope="module", autouse=True)
def every_position_is_sampled():
    from benchmark.models import lfm2

    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(lfm2, "LOGIT_SAMPLE", 128)
        patch.setattr(lfm2, "ATTENTION_SAMPLE", 128)
        yield


def tiny_model(dtype):
    cfg = dict(mf.read_json("benchmark/configs/kimi-linear-48b-a3b.json"), compute_dtype=dtype, **TINY)
    job = dict(mf.read_json("benchmark/traffic/train-kda-s4096.json"), **JOB)
    main, startup, feeds, loss, names = kimi_linear.build(cfg, job)
    main.random_seed = startup.random_seed = 3
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.TPUPlace(0))
    exe.run(startup, scope=scope)
    return cfg, job, main, loss, names, scope, exe


def params_of(main, scope):
    return {p.name: np.asarray(scope.find_var(p.name)) for p in main.all_parameters()}


def reference_of(cfg, params, rows):
    return [np.asarray(w) for w in jax.jit(lambda p, b: kimi_linear.reference(p, b, cfg))(params, rows)]


@pytest.fixture(scope="module")
def float32_run():
    from paddle_tpu.core import unique_name

    with jax.default_matmul_precision("highest"), unique_name.guard():
        cfg, job, main, loss, names, scope, exe = tiny_model("float32")
        rows = kimi_linear.make_batch(np.random.RandomState(3), cfg, job, 8)
        got = exe.run(main.clone(for_test=True), feed=rows, fetch_list=list(names), scope=scope)
        before = params_of(main, scope)
        want = reference_of(cfg, before, rows)
        batch = kimi_linear.make_batch(np.random.RandomState(4), cfg, job, 4)
        ref_loss, ref_grads = jax.jit(jax.value_and_grad(lambda p: kimi_linear.reference(p, batch, cfg)[0]))(before)
        step_loss, = exe.run(main, feed=batch, fetch_list=[loss], scope=scope)
        after = params_of(main, scope)
        moments = {n: np.asarray(scope.find_var(n + "_moment1_0")) for n in before}
        ops = [op.type for op in main.global_block().ops]
    return SimpleNamespace(cfg=cfg, job=job, got=got, want=want, ops=ops, before=before, after=after, moments=moments,
                           ref_loss=float(ref_loss), step_loss=float(np.asarray(step_loss).reshape(-1)[0]),
                           ref_grads={k: np.asarray(v) for k, v in ref_grads.items()})


def test_float32_loss_logits_routing_and_every_stage_agree_with_the_reference(float32_run):
    found = kimi_linear.compare(float32_run.got, float32_run.want)
    assert found["left_out"] == found["routed_differently"] == found["routed_differently_above_margin"] == 0
    assert found["loss_error"] < 1e-5 and found["logit_error"] < 2e-5, found
    assert max(found["router_prob_error"], found["experts_error"], found["shared_error"], found["conv_error"],
               found["kda_error"], found["attention_error"], found["qk_error"]) < 2e-5, found
    assert found["biases_differ"] == 0 and found["bias_moved"] > 0
    assert found["kda_error_bf16_state"] > 1e-3                        # what the stage has to refuse
    assert all(0.2 < decay < 1.0 for decay in found["kda_decay_mean"])
    assert kimi_linear.reference_error(float32_run.got, float32_run.want) < 2e-5
    assert abs(float32_run.step_loss - float32_run.ref_loss) < 1e-5 * float32_run.ref_loss
    assert np.asarray(float32_run.got[1]).shape == (128, 8, 96)
    assert np.asarray(float32_run.got[-1]).shape == (kimi_linear.STAGE_ROWS, 128, 2, 16)     # the stage rows only


KDA_PARAMS = ("q.w", "q_conv.w", "k.w", "k_conv.w", "v.w", "v_conv.w", "f_a.w", "f_b.w", "a_log", "dt_bias", "b.w",
              "o_norm.w", "g_a.w", "g_b.w", "g_b.b", "out.w")
PARAMS = sorted(
    ["lm.tok_emb", "lm.head.w", "lm.final_norm.w", "lm.l0.ffn.gate.w", "lm.l0.ffn.up.w", "lm.l0.ffn.down.w"]
    + [f"lm.l{i}.{n}" for i in range(5) for n in ("ln1.w", "ln2.w")]
    + [f"lm.l{i}.kda.{n}" for i in (0, 1, 2, 4) for n in KDA_PARAMS]
    + [f"lm.l3.attn.{n}.w" for n in ("q", "kv_a", "kv_norm", "kv_b", "out")]
    + [f"lm.l{i}.moe.{n}.w" for i in range(1, 5)
       for n in ("router", "gate", "up", "down", "shared.gate", "shared.up", "shared.down")])


def test_the_tiny_model_has_these_layers_parameters_and_no_other(float32_run):
    r = float32_run
    assert sorted(r.before) == PARAMS
    assert r.ops.count("kda") == r.ops.count("kda_gate") == 4 and r.ops.count("short_conv") == 12
    assert r.ops.count("fused_attention") == 1 and r.ops.count("rotary_embedding") == 0 and r.ops.count("transpose2") == 1
    assert r.ops.count("moe_router") == r.ops.count("moe_experts") == 4
    shapes = {n: r.before[n].shape for n in ("lm.l0.kda.q.w", "lm.l0.kda.q_conv.w", "lm.l0.kda.f_a.w", "lm.l0.kda.f_b.w",
                                            "lm.l0.kda.a_log", "lm.l0.kda.dt_bias", "lm.l0.kda.b.w", "lm.l0.kda.o_norm.w",
                                            "lm.l0.kda.g_b.b", "lm.l3.attn.q.w", "lm.l3.attn.kv_a.w", "lm.l3.attn.kv_norm.w",
                                            "lm.l3.attn.kv_b.w", "lm.l3.attn.out.w", "lm.l1.moe.router.w", "lm.l1.moe.gate.w",
                                            "lm.l1.moe.shared.down.w")}
    assert shapes == {"lm.l0.kda.q.w": (48, 32), "lm.l0.kda.q_conv.w": (32, 4), "lm.l0.kda.f_a.w": (48, 16),
                      "lm.l0.kda.f_b.w": (16, 32), "lm.l0.kda.a_log": (2,), "lm.l0.kda.dt_bias": (32,),
                      "lm.l0.kda.b.w": (48, 2), "lm.l0.kda.o_norm.w": (16,), "lm.l0.kda.g_b.b": (32,),
                      "lm.l3.attn.q.w": (48, 48), "lm.l3.attn.kv_a.w": (48, 32), "lm.l3.attn.kv_norm.w": (24,),
                      "lm.l3.attn.kv_b.w": (24, 64), "lm.l3.attn.out.w": (32, 48), "lm.l1.moe.router.w": (48, 32),
                      "lm.l1.moe.gate.w": (4, 48, 16), "lm.l1.moe.shared.down.w": (16, 48)}
    with pytest.raises(ValueError, match="kda or latent_attention"):
        transformer.build_causal_lm(vocab_size=8, seq_len=4, d_model=8, n_heads=2, layer_types=["conv", "scan"])
    with pytest.raises(ValueError, match="kda_heads and kda_head_dim"):
        transformer.build_causal_lm(vocab_size=8, seq_len=4, d_model=8, n_heads=2, layer_types=["kda"])


@pytest.mark.parametrize("name", PARAMS)
def test_float32_gradient_and_adam_step_agree_with_the_reference(float32_run, name):
    """Adam's first moment after one step is 0.1 x the gradient (to 2e-4 of its
    largest element: the reference differentiates the token-by-token
    recurrence, the program its chunked form by hand); the parameter moves by
    the warm-up's first rate."""
    r = float32_run
    agree(r.moments[name] / (1 - 0.9), r.ref_grads[name], tol=2e-4)
    moved = np.abs(r.after[name] - r.before[name]).max()
    assert 0.5e-6 < moved < 4e-6, moved


def test_bfloat16_agrees_within_the_benchmarks_tolerances():
    cfg, job, main, loss, names, scope, exe = tiny_model("bfloat16")
    rows = kimi_linear.make_batch(np.random.RandomState(3), cfg, job, 8)
    got = exe.run(main.clone(for_test=True), feed=rows, fetch_list=list(names), scope=scope)
    want = reference_of(cfg, params_of(main, scope), rows)
    found = kimi_linear.compare(got, want)
    assert found["tokens"] == 8 * 128 and found["routed_differently_above_margin"] == 0
    assert found["left_out"] <= found["routed_differently"] <= 0.35 * found["tokens"]   # 32 outputs of 48 features: near ties
    assert 1e-4 < found["logit_error"] < kimi_linear.REFERENCE_RTOL and found["loss_error"] < 1e-3
    assert found["router_prob_error"] < kimi_linear.ROUTER_RTOL and found["experts_error"] < kimi_linear.EXPERTS_RTOL
    assert found["shared_error"] < kimi_linear.SHARED_RTOL
    assert found["conv_error"] < kimi_linear.CONV_RTOL < found["conv_error_bf16"]
    assert found["kda_error"] < kimi_linear.KDA_RTOL < found["kda_error_bf16_state"]
    assert found["attention_error"] < kimi_linear.ATTENTION_RTOL and found["qk_error"] < kimi_linear.QK_RTOL
    assert kimi_linear.reference_error(got, want) in (max(found["loss_error"], found["logit_error"]), float("inf"))


@pytest.mark.parametrize("fault", ["taps_reversed", "no_decay", "attention_not_causal", "shared_expert_twice"])
def test_the_reference_check_fails_on(fault, monkeypatch):
    """A program that computes something else under the same names is not
    correct: a convolution with its taps reversed, a scan without Diag(alpha),
    latent attention that sees the keys after a query, a shared expert added twice."""
    from paddle_tpu.ops import moe_ops

    if fault == "taps_reversed":
        real = moe_ops._plain_short_conv
        monkeypatch.setattr(moe_ops, "_plain_short_conv", lambda x, w: real(x, w[:, ::-1]))
    elif fault == "no_decay":
        real = lao.chunked_kda
        monkeypatch.setattr(lao, "chunked_kda", lambda q, k, v, g, *rest: real(q, k, v, 0 * g, *rest))
    elif fault == "attention_not_causal":
        real = get_op_def("fused_attention").lower

        def wrong(ctx, op, ins):
            attrs = {"causal": False}
            faulty = SimpleNamespace(type=op.type, attr=lambda n, d=None: attrs.get(n, op.attr(n, d)))
            return real(ctx, faulty, ins)

        monkeypatch.setattr(get_op_def("fused_attention"), "lower", wrong)
    else:
        real = get_op_def("elementwise_add").lower

        def twice(ctx, op, ins):
            outs = dict(real(ctx, op, ins))
            if "shared_expert" in getattr(op, "namescope", "") or "moe" in op.inputs["X"][0]:
                outs["Out"] = outs["Out"] + ins["Y"][0]
            return outs

        monkeypatch.setattr(get_op_def("elementwise_add"), "lower", twice)
    cfg, job, main, loss, names, scope, exe = tiny_model("float32")
    rows = kimi_linear.make_batch(np.random.RandomState(3), cfg, job, 8)
    got = exe.run(main.clone(for_test=True), feed=rows, fetch_list=list(names), scope=scope)
    want = reference_of(cfg, params_of(main, scope), rows)
    found = kimi_linear.compare(got, want)
    stage, limit = {"taps_reversed": ("conv_error", kimi_linear.CONV_RTOL), "no_decay": ("kda_error", kimi_linear.KDA_RTOL),
                    "attention_not_causal": ("attention_error", kimi_linear.ATTENTION_RTOL),
                    "shared_expert_twice": ("logit_error", kimi_linear.REFERENCE_RTOL)}[fault]
    assert found[stage] > limit, found
    assert not kimi_linear.reference_error(got, want) <= kimi_linear.REFERENCE_RTOL


# -- (f) the step record and the counters ---------------------------------------------------

def test_steps_through_train_loop_publish_the_kda_state_and_the_counters_count_the_layers():
    from benchmark.metrics import kda_state_decay_mean

    monitor.reset()
    monitor.enable()
    try:
        cfg, job, main, loss, names, scope, exe = tiny_model("float32")
        rng = np.random.RandomState(5)
        batches = [kimi_linear.make_batch(rng, cfg, job, 4) for _ in range(4)]
        fluid.train_loop(exe, main, iter(batches), [loss], scope=scope, log_period=2)
        records = monitor.get_monitor().step_records()
        counters = monitor.get_monitor().counter_values()
    finally:
        monitor.disable()
        monitor.reset()
    states = [r for r in records if r.get("kind") == "kda_state"]
    assert len(states) == 2 and len([r for r in records if r.get("kind") == "moe_routing"]) == 2
    for r in states:
        assert len(r["decay_mean"]) == len(r["beta_mean"]) == len(r["state_abs_max"]) == 4
        assert all(0.2 < d < 1.0 for d in r["decay_mean"]) and all(0.4 < b < 0.6 for b in r["beta_mean"])
        assert all(0 < s < 100 for s in r["state_abs_max"]) and 0 <= r["worst_layer"] < 4
    assert kda_state_decay_mean.decay_mean(records, 0) == pytest.approx(
        np.median([np.mean(r["decay_mean"]) for r in states]))
    assert kda_state_decay_mean.decay_mean([], 0) is None
    assert counters["lowering.kda_layers"] >= 4 and counters["lowering.kda_chunks"] >= 4 * 2
    assert counters["lowering.short_conv_plain_layers"] >= 12 and counters["lowering.latent_attention_layers"] >= 1
    assert counters["lowering.shared_expert_layers"] >= 4 and not counters.get("lowering.short_conv_layers")
