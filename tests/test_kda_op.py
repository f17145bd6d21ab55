"""The op `kda` (ISSUE 42) in its `jax.numpy` form, chunk by chunk, tiny on the CPU,
against the token-by-token recurrence: forward, the final state and the
hand-written backward against `jax.grad` of the recurrence, at several chunk
counts, at mild and at strong decay (g = -20 a token: finite everywhere), and the
faults the benchmark's stage has to refuse; `kda_gate`; `infer=`, the planner
rows, `analysis.verify`.  Its Pallas kernels stand in `tests/test_kda_kernels.py`
(which takes `scan_inputs` and the recurrence from here), the model's other parts
and the whole in `tests/test_kimi_linear.py` (whose `lower`, `agree` and float32
products these cases take): three files so that three workers share what was ten
minutes of one (ISSUE 66; `docs/tier1_durations.md`).
"""
from test_kimi_linear import agree, float32_products, lower  # noqa: F401  (the fixture by name)

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as fluid
from benchmark.models import kimi_linear
from paddle_tpu import layers
from paddle_tpu.models import transformer
from paddle_tpu.ops import linear_attention_ops as lao


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
