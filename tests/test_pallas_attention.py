"""fused_sdpa Pallas kernel goldens (interpret mode on the CPU mesh), and
the error of each of `fused_attention`'s attentions against float32 on
inputs that make attention count (ISSUE 30).

Reference semantics: scaled-dot-product attention as in the unfused
matmul/softmax stack (layers/nn.py multi-head attention) — the kernel must
match the jnp fallback in ops/nn_ops.py _fused_attention bit-for-bit-ish in
f32 (both compute f32 scores + f32 softmax).  Grads via the custom VJP's
recompute backward kernel vs jax.grad of the reference.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops.pallas_attention import fused_sdpa


def _ref(q, k, v, bias, causal, scale):
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    if bias is not None:
        s = s + bias.astype(jnp.float32)
    if causal:
        Lq, Lk = s.shape[-2], s.shape[-1]
        s = jnp.where(jnp.tril(jnp.ones((Lq, Lk), bool), k=Lk - Lq), s, -1e30)
    p = jax.nn.softmax(s, -1)
    return jnp.einsum("bhqk,bhkd->bhqd", p.astype(q.dtype), v,
                      preferred_element_type=jnp.float32).astype(q.dtype)


@pytest.mark.parametrize("bias_kind,causal", [
    (None, False), ("bcast", False), ("per_head", True), (None, True),
])
def test_fused_sdpa_fwd_and_grad(bias_kind, causal):
    B, H, L, dh = 2, 4, 16, 8
    rng = np.random.RandomState(0)
    q = jnp.asarray(rng.randn(B, H, L, dh), jnp.float32)
    k = jnp.asarray(rng.randn(B, H, L, dh), jnp.float32)
    v = jnp.asarray(rng.randn(B, H, L, dh), jnp.float32)
    bias = None
    if bias_kind == "bcast":
        bias = jnp.asarray(rng.randn(B, 1, L, L) * 2, jnp.float32)
    elif bias_kind == "per_head":
        bias = jnp.asarray(rng.randn(B, H, L, L) * 2, jnp.float32)
    scale = 1.0 / np.sqrt(dh)

    out = fused_sdpa(q, k, v, bias, causal, scale, True)
    want = _ref(q, k, v, bias, causal, scale)
    assert np.allclose(out, want, atol=1e-5), np.abs(out - want).max()

    def f(q, k, v):
        return jnp.sum(jnp.sin(fused_sdpa(q, k, v, bias, causal, scale, True)))

    def g(q, k, v):
        return jnp.sum(jnp.sin(_ref(q, k, v, bias, causal, scale)))

    got = jax.grad(f, argnums=(0, 1, 2))(q, k, v)
    ref = jax.grad(g, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(got, ref):
        assert np.allclose(a, b, atol=1e-4), np.abs(a - b).max()


def test_fused_sdpa_cross_attention_lengths():
    # Lq != Lk (cross attention): kernel block specs carry distinct lengths
    B, H, Lq, Lk, dh = 1, 2, 8, 24, 8
    rng = np.random.RandomState(1)
    q = jnp.asarray(rng.randn(B, H, Lq, dh), jnp.float32)
    k = jnp.asarray(rng.randn(B, H, Lk, dh), jnp.float32)
    v = jnp.asarray(rng.randn(B, H, Lk, dh), jnp.float32)
    out = fused_sdpa(q, k, v, None, False, 0.5, True)
    want = _ref(q, k, v, None, False, 0.5)
    assert np.allclose(out, want, atol=1e-5)


# --------------------------------------------------------------------------
# A numerics check that can see attention.  The benchmark's reference check
# cannot: the zoo's N(0, 0.02) weights make the scores ~1e-4 and every
# softmax row uniform, so float32 scores, bf16 scores and this kernel read
# the same logit error to ten digits (PERF.md section 7, defect 3).  Here q
# and k have unit variance: at 64-wide heads the scores have unit variance
# too, a row of 512 is peaked (its largest probability ~15x the mean), and
# rounding the scores, the probabilities or dS shows.
# --------------------------------------------------------------------------


def attention_errors(shape, attentions, seed=0, causal=False, kv_heads=None):
    """{attention: {"out" | "dq" | "dk" | "dv": error}} for bf16 q, k, v of
    `shape` = (B, H, L, dh) drawn N(0, 1) and a N(0, 1) cotangent: root mean
    square of (result - reference) over root mean square of the reference,
    the reference float32 throughout on the same (bf16-rounded) inputs.
    `attentions` maps a name to f(q, k, v) -> out.  `tools/chip_attention_errors.py`
    runs this on the TPU at (32, 12, 512, 64), the stock flash kernel beside
    the two (PERF.md, PR 30), and under a causal mask with k and v on
    `kv_heads` heads, which the reference repeats (PR 37)."""
    rng = np.random.RandomState(seed)
    kv_shape = (shape[0], kv_heads or shape[1]) + tuple(shape[2:])
    q, k, v, w = (jnp.asarray(rng.randn(*s), jnp.bfloat16) for s in (shape, kv_shape, kv_shape, shape))
    scale = shape[-1] ** -0.5
    group = shape[1] // kv_shape[1]

    def results(f, *operands):
        out, vjp = jax.vjp(f, *operands)
        return (out,) + vjp(w.astype(out.dtype))

    with jax.default_matmul_precision("highest"):
        want = results(lambda q, k, v: _ref(q, jnp.repeat(k, group, 1), jnp.repeat(v, group, 1), None, causal, scale),
                       *(t.astype(jnp.float32) for t in (q, k, v)))
    want = [np.asarray(t, np.float64) for t in want]
    errors = {}
    for name, f in attentions.items():
        got = [np.asarray(t.astype(jnp.float32), np.float64) for t in jax.jit(lambda *a: results(f, *a))(q, k, v)]
        errors[name] = {part: float(np.sqrt(np.mean(np.square(g - r)) / np.mean(np.square(r))))
                        for part, g, r in zip(("out", "dq", "dk", "dv"), got, want)}
    return errors


def xla_attention(q, k, v):
    """`fused_attention` as it lowers off the TPU and for the shapes that keep XLA's attention."""
    from types import SimpleNamespace

    from paddle_tpu.core.lowering import LoweringContext
    from paddle_tpu.core.registry import get_op_def

    op = SimpleNamespace(type="fused_attention", attr=lambda name, default=None: default)
    ctx = LoweringContext(jax.random.PRNGKey(0), platform="cpu")
    return get_op_def("fused_attention").lower(ctx, op, {"Q": [q], "K": [k], "V": [v]})["Out"]


def flash_causal(q, k, v):
    """The stock flash kernel under a causal mask as `fused_attention` calls
    it where its rule still takes it: k and v repeated at its edge (the tools'
    yardstick for the causal rule's splash kernels, PR 37)."""
    from paddle_tpu.ops.nn_ops import _flash_attention_tpu

    k, v = (jnp.repeat(t, q.shape[1] // t.shape[1], axis=1) for t in (k, v))
    return _flash_attention_tpu(q, k, v, None, True, q.shape[-1] ** -0.5)


def _bf16_scores_attention(q, k, v):
    """The precision below the one the configuration states: the scores
    rounded to bf16 before the softmax (`score_dtype="bfloat16"`, deleted in PR 30)."""
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k, preferred_element_type=jnp.float32) * q.shape[-1] ** -0.5
    s = s.astype(jnp.bfloat16)
    e = jnp.exp((s - jnp.max(s, axis=-1, keepdims=True)).astype(jnp.float32))
    p = e / jnp.sum(e, axis=-1, keepdims=True)
    return jnp.einsum("bhqk,bhkd->bhqd", p.astype(q.dtype), v,
                      preferred_element_type=jnp.float32).astype(q.dtype)


def test_the_row_kernel_is_as_close_to_float32_as_xlas_attention_on_peaked_rows():
    """BERT-base's heads at 512 keys, two sequences: bf16 operands, float32
    scores and softmax in both; the kernel's output and its three gradients
    (dS rounded to bf16 before dQ and dK, as the stock flash kernel rounds
    it) are within 1.5x of the XLA path's distance from float32.  The check
    can see what it is for: bf16 scores are 1.8x (output, dV) to 3.8x (dQ,
    dK) XLA's distance, and fail it."""
    shape = (2, 12, 512, 64)
    errors = attention_errors(shape, {
        "xla": xla_attention,
        "row_kernel": lambda q, k, v: fused_sdpa(q, k, v, None, False, shape[-1] ** -0.5, True),
        "bf16_scores": _bf16_scores_attention})
    print("attention_errors", shape, errors)
    for part, of_xla in errors["xla"].items():
        assert errors["row_kernel"][part] <= 1.5 * of_xla, (part, errors)
        assert errors["bf16_scores"][part] > 1.5 * of_xla, (part, errors)
        assert of_xla < 1e-2, (part, errors)


# --------------------------------------------------------------------------
# The projections' own layout (ISSUE 39): q, k, v and the result (B, L, H, dh),
# read and written by the kernel as [B, L, H*dh] with `g` heads side by side on
# the lanes of a block and each head's [L, dh] tile a static slice of them.
# --------------------------------------------------------------------------


def _blhd(t):
    return jnp.swapaxes(t, 1, 2)


def _layout_operands(lq, lk, bias_kind, seed):
    rng = np.random.RandomState(seed)
    q = jnp.asarray(rng.randn(1, 12, lq, 64), jnp.float32)
    k, v = (jnp.asarray(rng.randn(1, 12, lk, 64), jnp.float32) for _ in range(2))
    w = jnp.asarray(rng.randn(1, 12, lq, 64), jnp.float32)
    bias = {None: None, "bcast": (1, 1, lq, lk), "per_head": (1, 12, lq, lk)}[bias_kind]
    if bias is not None:
        bias = jnp.asarray(rng.randn(*bias) * 2, jnp.float32)
    return q, k, v, w, bias


#: (queries, keys), causal, bias: self-attention at the kernel's two lengths under every mask, and queries against more keys
LAYOUT_CASES = [(n, causal, bias) for n in ((384, 384), (512, 512)) for causal, bias in
                ((False, None), (True, None), (False, "bcast"), (True, "per_head"))] + [((384, 512), False, None), ((384, 512), False, "bcast")]


@pytest.mark.parametrize("lengths,causal,bias_kind", LAYOUT_CASES,
                         ids=[f"{n[0]}x{n[1]}{'-causal' * c}{'-bias-' + b if b else ''}" for n, c, b in LAYOUT_CASES])
@pytest.mark.parametrize("g", [2, 6, 12])
def test_the_kernel_over_the_projections_layout_agrees_with_heads_major_and_with_xla(g, lengths, causal, bias_kind,
                                                                                     monkeypatch):
    """BERT-base's twelve 64-wide heads, one sequence: the kernel over
    (B, L, H, dh) at `g` heads a grid step against today's heads-major call
    (the same `_sdpa_tile` and `_sdpa_tile_bwd` a head: to rounding's last
    place) and against XLA's attention, the output and dq, dk, dv."""
    from paddle_tpu.ops import pallas_attention

    lq, lk = lengths
    q, k, v, w, bias = _layout_operands(lq, lk, bias_kind, seed=g + lq)
    scale = 0.125

    def results(f, *operands):
        out, vjp = jax.vjp(f, *operands)
        return (out,) + vjp(w if out.shape == w.shape else _blhd(w))

    heads_major = results(lambda q, k, v: fused_sdpa(q, k, v, bias, causal, scale, True), q, k, v)
    with jax.default_matmul_precision("highest"):
        xla = results(lambda q, k, v: _ref(q, k, v, bias, causal, scale), q, k, v)
    monkeypatch.setattr(pallas_attention, "_pick_heads", lambda *a: g)
    native = results(lambda q, k, v: fused_sdpa(q, k, v, bias, causal, scale, True, "blhd"), _blhd(q), _blhd(k), _blhd(v))
    for got, same, want in zip(native, heads_major, xla):
        got = np.asarray(_blhd(got))
        assert got.shape == want.shape and np.isfinite(got).all()
        np.testing.assert_allclose(got, same, rtol=0, atol=1e-6 * float(np.abs(same).max()))
        assert np.abs(got - want).max() <= 2e-4 * np.abs(want).max(), (np.abs(got - want).max(), np.abs(want).max())


@pytest.mark.parametrize("layout,heads,length,want", [
    ("bhld", 12, 512, (3, 2)), ("bhld", 12, 384, (4, 3)), ("bhld", 12, 128, (12, 12)), ("bhld", 5, 512, (1, 1)),
    ("blhd", 12, 512, (12, 6)), ("blhd", 12, 384, (12, 12)), ("blhd", 12, 256, (12, 12)), ("blhd", 5, 512, (5, 5)),
    ("blhd", 3, 128, (3, 3)),
])
def test_the_heads_a_grid_step_fit_the_budget_and_tile_the_lanes(layout, heads, length, want):
    """`_pick_heads`: heads-major any divisor of H that fits (PR 30's 3 and 2
    pairs at 512 keys, 4 and 3 at 384); in the projections' layout the score
    buffers count once and a block's g*dh lanes are whole 128-lane tiles or
    the whole row (five 64-wide heads: the whole row, whatever the budget);
    a float32 bias of its own a head brings twelve heads at 512 keys down to
    two."""
    from paddle_tpu.ops.pallas_attention import _pick_heads

    got = tuple(_pick_heads(heads, length, 64, 2, bufs, layout) for bufs in ((6, 2), (10, 3)))
    assert got == want
    for g in got:
        assert heads % g == 0 and (layout == "bhld" or g == heads or (g * 64) % 128 == 0)
    if (layout, heads, length) == ("blhd", 12, 512):
        assert _pick_heads(heads, length, 64, 2, (6, 2), layout, head_bias=512 * 512 * 4) == 2


@pytest.mark.parametrize("layout", ["bhld", "blhd"])
def test_layers_of_one_signature_share_one_lowered_kernel_a_direction(layout):
    """Six layers' attentions and their gradients, lowered for the TPU here:
    the module holds TWO Mosaic calls, one a direction, that every layer
    calls (the calls are `jax.jit`s of their own: a bare `pallas_call` is
    traced and lowered layer by layer, which was `bert-base.pretrain-s512`'s
    `setup_lower_s`, PR 39); a second signature (causal) adds its own two."""
    shape = (2, 12, 512, 64) if layout == "bhld" else (2, 512, 12, 64)
    x = jax.ShapeDtypeStruct(shape, jnp.bfloat16)

    def layers(causal_too):
        def loss(q, k, v):
            out = fused_sdpa(q, k, v, None, True, 0.125, False, layout) if causal_too else q
            for _ in range(6):
                out = fused_sdpa(out, k, v, None, False, 0.125, False, layout)
            return out.astype(jnp.float32).sum()
        return jax.jit(jax.grad(loss, argnums=(0, 1, 2))).trace(x, x, x).lower(lowering_platforms=("tpu",)).as_text()

    assert layers(False).count("@tpu_custom_call") == 2
    assert layers(True).count("@tpu_custom_call") == 4
