"""Kimi Delta Attention (KDA): a linear-attention recurrence over the sequence,
a gated delta rule with a decay for every key channel (Kimi Linear,
arXiv:2510.26692) or ONE a head (Gated DeltaNet, arXiv:2412.06464), computed
chunk by chunk.

A head keeps a float32 state S in R^{K x V} (keys x values), S_0 = 0:

    S_t = (I - beta_t k_t k_t^T) Diag(alpha_t) S_{t-1} + beta_t k_t v_t^T,    o_t = S_t^T q_t

with alpha_t = exp(g_t) in (0, 1)^K the channels' decay and beta_t in (0, 1)
the step.  `kda` takes q, k, v [b, T, H, K|V], the LOG decay g (float32), a
decay a channel [b, T, H, K] or a head [b, T, H] (alpha_t the same number in
every channel: G's rank says which, no attribute does), and beta [b, T, H];
q and k may have FEWER heads than v, a divisor of them: value head h reads key
head h div (H / key heads), as grouped attention's query heads read theirs.
The convolutions, norms, gates and projections round it are ops of the program.
`kda_gate` makes g from the decay's projection: g = -exp(A_log[h]) . softplus(x
+ dt_bias), float32.

A decay a head factors out of the decayed Grams below, M = (k k^T) . exp(G[r] -
G[i]): the kernels compute it so (`kda_kernels._Chunks._scalar_grams`: one
product a KEY head, a [C, C] matrix of exponentials a value head, no block and
no `lax.cond`) and read g and write its gradient as [b, T, H], and a group's key
heads come through the index map: neither g's 128 copies nor q's and k's
repeats are in HBM.  The `jax.numpy` form, the CPU's, writes both out
(`_per_channel`) and runs the one form below; backward sums them back.

The chunked form (`_KDA_CHUNK` tokens a chunk, G the cumulative log decay
inside the chunk, inclusive):

    M[r, i] = sum_c k[r, c] k[i, c] exp(G[r, c] - G[i, c])   r > i        (the keys' decayed Gram)
    P[r, i] = sum_c q[r, c] k[i, c] exp(G[r, c] - G[i, c])   r >= i
    T = (I + Diag(beta) M)^-1                                              (unit lower triangular)
    W = T (beta . k exp(G)),  U = T (beta . v)        so that  beta_r u_r = U - W S  for the chunk's start state S
    S' = Phi S + B,    Phi = Diag(exp(G_last)) - Kend^T W,   B = Kend^T U,   Kend_i = k_i exp(G_last - G_i)
    O  = Qe S + P U,   Qe = q exp(G) - P W

Everything but the two lines that read S is computed for all chunks at once;
the state goes through ONE `lax.scan` whose body is one product a head.

Every exponent's argument is a difference of cumulative log decays that is
<= 0, so no exp overflows however strong the decay (g = -20 a token is tested):
a chunk's rows are cut into blocks of `_KDA_SUB`; a block's rows meet the keys
of the blocks before it as one product, the rows decayed from the block's
FIRST row and the keys up to it (both differences of the right sign), and the
keys of their own block either carried back to that row, over at most
`_KDA_SAFE` nats, or by the block's [sub, sub, K] differences themselves
(`_decayed_grams`).  G, the state, T and every product that reads them are
float32 at `_KDA_PRECISION`.

Backward is written out (`jax.custom_vjp`): it makes the chunks' terms again,
runs the state's recurrence TRANSPOSED (lambda_c = Phi_c^T lambda_{c+1} +
Qe_c^T dO_c, one reverse scan), and hands the cotangents of (Phi, B, Qe, P U)
to the transpose of the chunks' own terms, which no scan is part of.  What
forward keeps for it is the op's five inputs and, on the kernels' path, two
things a (head, chunk) that the forward kernel has in VMEM anyway and writes
out where the op is differentiated (`_chunked_kda_fwd`; a plain call writes
neither): the state the chunk STARTS from ([n, b, H, K, V] float32, 134 MB a
layer at the cell's shape; backward made it again with a second forward call
until PR 45) and T ([C, C], 33.5 MB a layer: ten dependent products that were
a sixth of the transposed kernel's time).  So backward is the transposed
kernel and nothing else.  The `jax.numpy` form keeps the five inputs only and
makes the boundary states again.  Nothing of [T, T] and no [chunks, C, C, K]
array is kept from forward to backward.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from ..core import analysis as _A
from ..core import resource_plan as _RP
from jax.ad_checkpoint import checkpoint_name

from ..core.registry import register_op, set_kept, set_step_stats
from ..monitor import MONITOR as _MON
from . import kda_kernels
from .common import counted_rules, first, kept_residuals, operand_of, residuals_name

#: Tokens a chunk (the published kernels') and a block of its rows for the
#: decayed Grams (`_decayed_grams`), and the largest decay inside a block, in
#: nats a channel, that the keys are carried back over: exp(80) and exp(-80)
#: are float32 numbers with 20 bits to spare either way, and the published
#: parametrisation (g = -exp(A_log) softplus(.), A up to 16) stays far under 5
#: nats a token.  Past it the block's differences are taken directly: [16, 16,
#: K] a block is 1 GB a layer at the cell's shape and wrote 13 GB a forward pass
#: where it was the only form; cutting the block into 4s and single rows
#: instead left arrays whose last axes are 4 or 1 wide, which the chip tiles to
#: (8, 128): 51 ms a layer forward and backward, a third of it copies (my chip
#: runs, PR 42; PERF.md section 6).
_KDA_CHUNK = 64
_KDA_SUB = 16
_KDA_SAFE = 80.0


def _blocks_of(chunk):
    """The block of a chunk of `chunk` rows: `_KDA_SUB` where it cuts the chunk."""
    return _KDA_SUB if chunk % _KDA_SUB == 0 else chunk


#: Of every product that reads the float32 state, the cumulative decay's
#: exponentials or T: the arithmetic of the scan is ~1% of a layer's projections,
#: so six bf16 passes cost little and keep the op a rounding of the recurrence.
_KDA_PRECISION = jax.lax.Precision.HIGHEST


def _mm(spec, *operands):
    return jnp.einsum(spec, *operands, precision=_KDA_PRECISION, preferred_element_type=jnp.float32)


def _decayed_grams(rows, keys, G, sub):
    """[..., X, C, C], lower triangular with its diagonal: entry [x, r, i] = sum_c
    rows[x, r, c] keys[i, c] exp(G[r, c] - G[i, c]) for r >= i, of rows [..., X,
    C, K] and keys, G [..., C, K] (float32).  The C rows are cut into blocks of
    `sub`.  A block's rows meet the keys of the blocks BEFORE it as one
    product, both sides decayed to the block's first row (the rows from it,
    the keys up to it: no exponent is positive).  They meet the keys of their
    OWN block as a product too, the keys carried BACK to the block's first row
    (an exponent of at most `_KDA_SAFE`), where no channel decays by more than
    that inside a block; where one does, from the [sub, sub, K] differences
    themselves, none of them positive: a rarer lowering of the same numbers in
    the same program (`lax.cond`), a group of chunks at a time."""
    *lead, X, C, K = rows.shape
    n = C // sub
    rows = rows.reshape(*lead, X, n, sub, K)
    keys, G = keys.reshape(*lead, n, sub, K), G.reshape(*lead, n, sub, K)
    first = G[..., :, :1, :]                                                 # G at a block's first row
    near = rows * jnp.exp(G - first)[..., None, :, :, :]
    lower = np.tril(np.ones((sub, sub), bool))

    def carried_back(near, keys, G):
        return _mm("...xark,...aik->...axri", near, keys * jnp.exp(first - G))

    def by_differences(near, keys, G):
        decay = jnp.where(lower[..., None], jnp.exp(jnp.where(lower[..., None], G[..., :, None, :] - G[..., None, :, :], 0.0)), 0.0)
        weighed = keys[..., None, :, :] * decay                               # [..., a, r, i, K]
        return jnp.sum(rows.swapaxes(-4, -3)[..., :, :, None, :] * weighed[..., None, :, :, :], axis=-1)

    own = jax.lax.cond(jnp.max(first - G[..., :, -1:, :]) > _KDA_SAFE, by_differences, carried_back, near, keys, G)
    own = jnp.where(lower, own, 0.0)                                         # [..., n, X, sub, sub]
    strips = []
    for a in range(n):
        parts = [own[..., a, :, :, :]]
        if a:
            far = (keys[..., :a, :, :] * jnp.exp(first[..., a:a + 1, :, :] - G[..., :a, :, :])).reshape(*lead, a * sub, K)
            parts.insert(0, _mm("...xrk,...ik->...xri", near[..., a, :, :], far))
        if a < n - 1:
            parts.append(jnp.zeros((*lead, X, sub, (n - 1 - a) * sub), jnp.float32))
        strips.append(jnp.concatenate(parts, axis=-1))
    return jnp.concatenate(strips, axis=-2)


def _unit_lower_inverse(a):
    """(I + a)^-1 for a strictly lower triangular [..., C, C]: with N = -a,
    nilpotent, (I - N)^-1 = (I + N)(I + N^2)(I + N^4) ... : products only."""
    C = a.shape[-1]
    eye = jnp.eye(C, dtype=a.dtype)
    power, inverse = -a, eye - a
    for _ in range(int(np.ceil(np.log2(C))) - 1):
        power = _mm("...ij,...jk->...ik", power, power)
        inverse = _mm("...ij,...jk->...ik", inverse, eye + power)
    return inverse


def _cumulative(g):
    """The log decay summed from a chunk's first token to each of its tokens,
    inclusive: float32, whatever else is not (a function of its own so that the
    controls can round it: tests/test_kimi_linear.py, tools/chip_kimi_controls.py)."""
    return jnp.cumsum(g, axis=-2)


def _chunk_terms(q, k, v, g, beta, chunk, sub):
    """What a chunk contributes whatever state it starts from, all chunks of ONE
    row at once: (Phi [n, H, K, K], B [n, H, K, V], Qe [n, H, C, K], the chunk's
    own output P U [n, H, C, V]), float32, of q, k, v, g [T, H, .] and beta
    [T, H, 1]."""
    T, H, K = k.shape
    n = T // chunk

    def chunks(t):   # [T, H, .] -> [n, H, C, .]
        return t.astype(jnp.float32).reshape(n, chunk, H, -1).swapaxes(1, 2)

    q, k, v, g, beta = chunks(q), chunks(k), chunks(v), chunks(g), chunks(beta)
    G = _cumulative(g)
    grams = _decayed_grams(jnp.stack([k, q], axis=-3), k, G, sub)
    M, P = jnp.where(np.tril(np.ones((chunk, chunk), bool), -1), grams[..., 0, :, :], 0.0), grams[..., 1, :, :]
    T_inv = _unit_lower_inverse(beta * M)
    from_start = jnp.exp(G)
    W = _mm("...ri,...ik->...rk", T_inv, beta * k * from_start)
    U = _mm("...ri,...iv->...rv", T_inv, beta * v)
    last = G[..., -1:, :]
    k_end = k * jnp.exp(last - G)
    phi = jnp.exp(last)[..., 0, :, None] * jnp.eye(K, dtype=jnp.float32) - _mm("...ik,...ij->...kj", k_end, W)
    B = _mm("...ik,...iv->...kv", k_end, U)
    q_eff = q * from_start - _mm("...ri,...ik->...rk", P, W)
    return phi, B, q_eff, _mm("...ri,...iv->...rv", P, U)


def _states(phi, B):
    """The state each chunk STARTS from [n, H, K, V] and the last chunk's end
    state, from S' = Phi S + B and S_0 = 0."""
    def step(S, term):
        phi_c, b_c = term
        return _mm("hkj,hjv->hkv", phi_c, S) + b_c, S

    final, starts = jax.lax.scan(step, jnp.zeros(B.shape[1:], jnp.float32), (phi, B))
    return starts, final


#: Chunks whose terms are made at once.  The terms of a chunk need ~30 float32
#: arrays of its [C, H, K] shape at once where they are differentiated (the
#: decayed copies of rows and keys); a row of 4096 tokens taken whole planned
#: 3.8 GB of them, an eighth at a time a few hundred MB (the op compiled for the
#: described v5e, PR 42).  The state's scan runs over all.
_KDA_GROUP = 8


def _in_groups(fn, chunks, *arrays):
    """`fn` of arrays whose leading axis is tokens or chunks, `_KDA_GROUP` of the
    `chunks` chunks at a time, its outputs (leading axis: chunks or tokens)
    joined again."""
    groups = max(chunks // _KDA_GROUP, 1)
    if groups == 1 or chunks % groups:
        return fn(*arrays)
    out = jax.lax.map(lambda group: fn(*group),
                      tuple(t.reshape(groups, t.shape[0] // groups, *t.shape[1:]) for t in arrays))
    return jax.tree.map(lambda t: t.reshape(t.shape[0] * t.shape[1], *t.shape[2:]), out)


def _row_forward(q, k, v, g, beta, chunk, sub):
    n = q.shape[0] // chunk
    phi, B, q_eff, own = _in_groups(lambda *a: _chunk_terms(*a, chunk, sub), n, q, k, v, g, beta)
    starts, final = _states(phi, B)
    o = own + _mm("nhrk,nhkv->nhrv", q_eff, starts)                          # [n, H, C, V]
    return o.swapaxes(1, 2).reshape(v.shape).astype(v.dtype), final


def _row_backward(q, k, v, g, beta, d_o, chunk, sub):
    T, H, V = d_o.shape
    n = T // chunk
    phi, B, q_eff, _ = _in_groups(lambda *a: _chunk_terms(*a, chunk, sub), n, q, k, v, g, beta)
    starts, _ = _states(phi, B)
    d_own = d_o.astype(jnp.float32).reshape(n, chunk, H, V).swapaxes(1, 2)
    # the recurrence transposed: lambda_c, the cotangent of the state chunk c STARTS from
    reads = _mm("nhrk,nhrv->nhkv", q_eff, d_own)

    def step(after, term):      # `after`: the cotangent of the state chunk c ENDS in
        phi_c, reads_c = term
        return _mm("hjk,hjv->hkv", phi_c, after) + reads_c, after

    _, ends = jax.lax.scan(step, jnp.zeros_like(reads[0]), (phi, reads), reverse=True)
    d_phi, d_q_eff = _mm("nhkv,nhjv->nhkj", ends, starts), _mm("nhrv,nhkv->nhrk", d_own, starts)

    def pull(q, k, v, g, beta, *cotangents):
        """The transpose of a group's own terms, which are made again here: what
        their transpose reads is alive a group at a time."""
        return jax.vjp(lambda *a: _chunk_terms(*a, chunk, sub), q, k, v, g, beta)[1](cotangents)

    return _in_groups(pull, n, q, k, v, g, beta, d_phi, ends, d_q_eff, d_own)


def _over_rows(fn, *rows):
    """`fn` of each row of the batch in turn: what a row's chunks need at once
    (`_KDA_GROUP`) is needed a row at a time, whatever the batch."""
    if rows[0].shape[0] == 1:
        return jax.tree.map(lambda t: t[None], fn(*(t[0] for t in rows)))
    return jax.lax.map(lambda row: fn(*row), rows)


def _kernel_seams():
    """What the kernels take from this module and their own where the
    `jax.numpy` form reads its globals: the products' precision, the cumulative
    decay's function and the carried state's (static arguments of the kernels'
    `jax.jit`s, so a control that patches one is traced anew:
    tools/chip_kimi_controls.py)."""
    return _KDA_PRECISION, kda_kernels.cumulative, kda_kernels.carried


def _per_channel(q, k, v, g):
    """(q, k, g) as the `jax.numpy` form computes them: q and k repeated to v's
    heads (value head h reads key head h div (H / key heads)) and a decay of
    one number a head written out over the key's channels.  Nothing where the
    op was handed a head of keys a head of values and a decay a channel."""
    shared = v.shape[2] // k.shape[2]
    if shared > 1:
        q, k = jnp.repeat(q, shared, axis=2), jnp.repeat(k, shared, axis=2)
    return q, k, jnp.broadcast_to(g[..., None], k.shape) if g.ndim == 3 else g


def _as_handed(d_q, d_k, d_g, k, g):
    """`_per_channel`'s transpose: a key head's gradient summed over the value
    heads that read it, a head's decay's over the channels (float32 sums)."""
    if d_k.shape != k.shape:
        b, T, key_heads, K = k.shape
        d_q, d_k = (jnp.sum(t.astype(jnp.float32).reshape(b, T, key_heads, -1, K), axis=3).astype(t.dtype) for t in (d_q, d_k))
    return d_q, d_k, jnp.sum(d_g, axis=-1) if g.ndim == 3 else d_g


def _kda_path(platform, mesh, q, v, chunk):
    """How the op is lowered: "kernels" (`ops/kda_kernels.py`: a group of heads'
    chunk in VMEM, the state carried there, forward and transposed) on the TPU
    where a head of keys and of values is a whole number of lane tiles, a chunk
    is the published 64 tokens and the program runs on one device (a
    `pallas_call` cannot be partitioned: `nn_ops._attention_path`'s rule), else
    "xla", this module's `jax.numpy` form: the CPU's path and what the tests
    hold the kernels to.  TPU v5e, (1, 4096, 32, 128), forward | backward of the
    op alone: PERF.md, PR 44.  A 64-wide head stays "xla" until someone prices a
    kernel for it.  A decay a head and fewer key heads than value heads take
    the kernels too where a grid step's value heads are whole key heads
    (`kda_kernels.heads_a_step`: 16 feeding 32 are two key heads a step of
    four): TPU v5e, (1, 16384, 32, 128) with 16 key heads, forward | backward of
    the op alone 14.19 | 19.08 ms against 16.45 | 25.67 with the decay written
    out over the channels and the keys repeated (`kda_kernels._HEADS`' comment;
    PERF.md, PR 69); eight value heads on one key head take "xla"."""
    one_device = mesh is None or mesh.size == 1
    whole = q.shape[-1] % 128 == 0 and v.shape[-1] % 128 == 0 and chunk == _KDA_CHUNK and q.shape[1] % chunk == 0
    grouped = kda_kernels.heads_a_step(v.shape[2], v.shape[2] // q.shape[2]) is not None
    return "kernels" if platform == "tpu" and one_device and whole and grouped else "xla"


def _chunked_kda(q, k, v, g, beta, chunk, sub, kernels, keep=False):
    """(o, the final state) and, of the kernels with `keep`, what backward
    reads after them: the chunks' start states and T."""
    with jax.named_scope("kda_chunk_scan"):
        if kernels:
            return kda_kernels.scan(q, k, v, g, beta[..., 0], chunk, sub, _KDA_SAFE, _kernel_seams(), keep,
                                    kernels == "interpret")
        q, k, g = _per_channel(q, k, v, g)
        return _over_rows(functools.partial(_row_forward, chunk=chunk, sub=sub), q, k, v, g, beta)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8))
def chunked_kda(q, k, v, g, beta, chunk=_KDA_CHUNK, sub=_KDA_SUB, kernels=None, keep=None):
    """(o [b, T, H, V] in v's dtype, the state after the last token [b, H, K, V]
    float32) of the recurrence above over q, k [b, T, H or a divisor of it, K],
    v [b, T, H, V], the log decay g [b, T, H, K] a channel or [b, T, H] a head
    and beta [b, T, H, 1], `chunk` tokens at a time,
    a chunk's rows in blocks of `sub` (`_blocks_of`).  `kernels`: None for the
    `jax.numpy` form, "tpu" for the Pallas kernels of `ops/kda_kernels.py`
    (`_kda_path` says when), "interpret" for those interpreted (the tests').
    The final state is for statistics: backward takes no cotangent for it.
    `keep` (the kernels' path) names what only the forward kernel makes and
    backward reads, the output, the chunks' start states and T: a
    `jax.checkpoint` round the op whose policy saves the name
    (`core/lowering.py: plan_kept`, a layer's `recompute_scope`) then runs no
    second forward kernel."""
    return _chunked_kda(q, k, v, g, beta, chunk, sub, kernels)


def _chunked_kda_fwd(q, k, v, g, beta, chunk, sub, kernels, keep=None):
    """The op where it is differentiated: the kernels keep the chunks' start
    states and T beside the five inputs (the `jax.numpy` form the inputs alone)."""
    inputs = (q, k, v, g, beta)
    if not kernels:
        return _chunked_kda(*inputs, chunk, sub, None), (inputs, ())
    _MON.counter("lowering.kda_starts_kept").inc()
    o, final, *kept = _chunked_kda(*inputs, chunk, sub, kernels, keep=True)
    if keep:
        o, kept = checkpoint_name(o, keep), [checkpoint_name(t, keep) for t in kept]
    return (o, final), (inputs, tuple(kept))


def _chunked_kda_bwd(chunk, sub, kernels, keep, residuals, cotangents):
    inputs, kept = residuals
    with jax.named_scope("kda_chunk_scan"):
        if not kernels:
            q, k, v, g, beta = inputs
            wide_q, wide_k, wide_g = _per_channel(q, k, v, g)
            d_q, d_k, d_v, d_g, d_beta = _over_rows(functools.partial(_row_backward, chunk=chunk, sub=sub),
                                                    wide_q, wide_k, v, wide_g, beta, cotangents[0])
            d_q, d_k, d_g = _as_handed(d_q, d_k, d_g, k, g)
            return d_q, d_k, d_v, d_g, d_beta
        # the chunks in reverse, each from the start state and with the T that forward kept
        _MON.counter("lowering.kda_kernel_transposed_calls").inc()
        *d_inputs, d_beta = kda_kernels.scan_transposed(*inputs[:4], inputs[4][..., 0], cotangents[0], *kept, chunk, sub,
                                                        _KDA_SAFE, _kernel_seams(), kernels == "interpret")
        return (*d_inputs, d_beta[..., None].astype(inputs[4].dtype))


chunked_kda.defvjp(*counted_rules("kda", _chunked_kda_fwd, _chunked_kda_bwd))


@register_op("kda")
def _kda(ctx, op, ins):
    """The chunked recurrence over Q, K [b, T, H or a divisor of it, K] (value
    head h reads key head h div (H / key heads)), V [b, T, H, V], G (the float32
    log decay, [b, T, H, K] a channel or [b, T, H] a head) and Beta [b, T, H].
    `lowering.scalar_decay_scans` (core/lowering.py: `count_layer_forms`) counts a step's ops whose decay is a head's.
    `Stats` [3] is the
    step's health, read on logged steps: the mean decay exp(G), the mean
    step beta and the largest |S| of the state after the last token."""
    q, k, v, g, beta = (first(ins, s) for s in ("Q", "K", "V", "G", "Beta"))
    T = q.shape[1]
    chunk = min(_KDA_CHUNK, T)
    if T % chunk:
        raise ValueError(f"kda: {T} positions are no whole number of chunks of {chunk} tokens")
    kernels = "tpu" if _kda_path(ctx.platform, ctx.mesh, q, v, chunk) == "kernels" else None
    _MON.counter("lowering.kda_layers").inc()
    _MON.counter("lowering.kda_chunks").inc(T // chunk)
    _MON.counter("lowering.kda_kernel_calls").inc(1 if kernels else 0)
    g = g.astype(jnp.float32)
    if kernels:
        # The kernels read the VARIABLES.  Without the barrier XLA hands a custom call whose operands it has to lay out
        # anew ([b, T, H . 128]) a second run of their producers' fusion, which rounds at other places than the one
        # that made the variables (excess precision): the first KDA layer of Kimi Linear's clone then read 4.71e-3
        # against the recurrence on its FETCHED q, k, v, g, beta where the same kernels on those arrays read 5.9e-4
        # (my chip runs, PR 44: `correct` false by `KDA_RTOL` for arithmetic that was sound).
        q, k, v, g, beta = jax.lax.optimization_barrier((q, k, v, g, beta))
    out, final = chunked_kda(q, k, v, g, beta[..., None], chunk, _blocks_of(chunk), kernels,
                             kept_residuals(ctx, op) if kernels else None)
    stats = jnp.stack([jnp.mean(jnp.exp(g)), jnp.mean(beta.astype(jnp.float32)), jnp.max(jnp.abs(final))])
    return {"Out": out, "Stats": jax.lax.stop_gradient(stats)}


@register_op("kda_gate")
def _kda_gate(ctx, op, ins):
    """The channels' log decay from its projection X [b, T, H . K]:
    g = -exp(ALog[h]) . softplus(X + DtBias), float32 [b, T, H, K]."""
    x, a_log, dt_bias = first(ins, "X"), first(ins, "ALog"), first(ins, "DtBias")
    heads = a_log.shape[0]
    step = jax.nn.softplus(x.astype(jnp.float32) + dt_bias.astype(jnp.float32))
    step = step.reshape(x.shape[:-1] + (heads, x.shape[-1] // heads))
    return {"Out": -jnp.exp(a_log.astype(jnp.float32))[:, None] * step}


def _publish_kda_state(step, values):
    """One logged step's `kda_state` record: per layer the mean decay, the mean
    step and the largest |S| after the last token; the worst layer's as gauges.
    A health check (a decay at 1 forgets nothing and the state grows; at 0 the
    layer reads one token): no lever on the step's time."""
    stats = np.stack([np.asarray(s, "f8").reshape(3) for s in values["Stats"]])
    record = {"kind": "kda_state", "pipeline_step": step, "decay_mean": stats[:, 0].tolist(),
              "beta_mean": stats[:, 1].tolist(), "state_abs_max": stats[:, 2].tolist(),
              "worst_layer": int(np.argmax(stats[:, 2]))}
    _MON.gauge("kda.decay_mean").set(float(stats[:, 0].mean()))
    _MON.gauge("kda.state_abs_max").set(float(stats[:, 2].max()))
    _MON.record_step(record)


set_step_stats("kda", ("Stats",), _publish_kda_state)


def _infer_kda(ctx):
    q, k, v, g, beta = (ctx.in_shape(s) for s in ("Q", "K", "V", "G", "Beta"))
    if q is None or k is None or v is None:
        return
    if (len(q) != 4 or tuple(k) != tuple(q) or len(v) != 4 or tuple(v[:2]) != tuple(q[:2])
            or (_A.DYN not in (v[2], q[2]) and v[2] % q[2])):
        ctx.fail(f"Q and K must be (b, T, H or a divisor of it, K) and V (b, T, H, V), got {q}, {k}, {v}")
    if g is not None and tuple(g) not in (tuple(v[:3]) + (k[3],), tuple(v[:3])):
        ctx.fail(f"G holds one log decay for each of the {v[2]} heads' {k[3]} channels or for each head, got {g}")
    if beta is not None and tuple(beta) != tuple(v[:3]):
        ctx.fail(f"Beta must be (b, T, H) = {tuple(v[:3])}, got {beta}")
    T = q[1]
    if T != _A.DYN and T > _KDA_CHUNK and T % _KDA_CHUNK:
        ctx.fail(f"{T} positions are no whole number of chunks of {_KDA_CHUNK}")
    ctx.set_out("Out", v, ctx.in_dtype("V"))
    ctx.set_out("Stats", (3,), "float32")


def _infer_kda_gate(ctx):
    xs, a_log, dt_bias = ctx.in_shape("X"), ctx.in_shape("ALog"), ctx.in_shape("DtBias")
    if xs is None or a_log is None:
        return
    if len(a_log) != 1 or xs[-1] % a_log[0] or (dt_bias is not None and tuple(dt_bias) != (xs[-1],)):
        ctx.fail(f"X (..., H . K) with ALog (H,) and DtBias (H . K,), got {xs}, {a_log}, {dt_bias}")
    ctx.set_out("Out", tuple(xs[:-1]) + (a_log[0], xs[-1] // a_log[0]), "float32")


def _kept_kda(ctx, op, shapes):
    """Where the op takes the kernels: its output, the float32 state every chunk
    starts from [chunks, b, H, K, V] and T [chunks, b, H, C / 2, 2 C] (what only
    the forward kernel makes and the transposed one reads: 0.8 GB a layer at
    (1, 16384, 32, 128), where making them again is a second forward kernel,
    13 ms a layer of Qwen3-Next's step: PERF.md, PR 69).  The `jax.numpy` form
    offers nothing: it keeps its inputs alone and makes the rest again."""
    q, v = (operand_of(shapes, op.input(slot)[0]) for slot in ("Q", "V"))
    chunk = min(_KDA_CHUNK, q.shape[1])
    if _kda_path(ctx.platform, ctx.mesh, q, v, chunk) != "kernels":
        return None
    batch, tokens, heads, width = v.shape
    chunks = tokens // chunk
    return residuals_name(op), shapes.nbytes(op.output("Out")[0]) + 4 * chunks * batch * heads * (q.shape[-1] * width + chunk * chunk)


set_kept("kda", _kept_kda)
_A.register_rule(["kda"], _infer_kda)
_A.register_rule(["kda_gate"], _infer_kda_gate)


def kda_chunk_flops(tokens, heads, k_width, v_width, chunk=_KDA_CHUNK):
    """Multiply-adds x 2 of the chunked recurrence's forward over `tokens`
    positions of `heads` heads, a chunk's triangles counted as triangles: the
    two Grams, the triangular solve of [C, K + V] right-hand sides, Phi, B, Qe,
    the chunk's own output, and the state's two products."""
    C, K, V = chunk, k_width, v_width
    a_chunk = (2 * C * C * K          # M and P, half a square each
               + C * C * (K + V)      # T applied to [beta k exp(G) | beta v]: forward substitution
               + 2 * C * K * K + 2 * C * K * V            # Phi, B
               + C * C * K + C * C * V                    # P W, P U (lower triangles)
               + 2 * K * K * V + 2 * C * K * V)           # Phi S, Qe S
    return float(a_chunk) * heads * tokens / C


def _cost_kda(ctx):
    q, v = ctx.in_shape("Q"), ctx.in_shape("V")
    if q is None or v is None or len(q) != 4:
        return float(ctx.out_elems_total()), ctx.io_bytes()
    return kda_chunk_flops(v[0] * v[1], v[2], q[3], v[3], min(_KDA_CHUNK, q[1])), ctx.io_bytes()


_RP.register_cost(["kda"], _cost_kda)
_RP.register_elementwise_cost("kda_gate", flops_per_elem=8.0)
