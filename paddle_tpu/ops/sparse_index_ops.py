"""A learned sparse selection of keys: the indexer of DeepSeek Sparse Attention
(DeepSeek-V3.2-Exp's report, section 2) as two ops beside `fused_attention`.

`sparse_index` SCORES and CHOOSES.  From the indexer's own small queries qI
(B, L, Hi, Di), its ONE key a token kI (B, L, 1, Di) and a weight a query and
index head w (B, L, Hi) float32,

    I[t, s] = sum_j w[t, j] Hi^-0.5 Di^-0.5 relu(qI[t, j] . kI[s]),   s <= t,

products in the operands' dtype into float32, the ReLU, the weights and the sum
over the index heads in float32; query t then holds S_t, the min(topk, t + 1)
keys of the largest I[t, .], the lower index first among equals (a stable
sort's order).  What leaves the op is ONE tensor, `Picks` int32 (B, L, L / 32): bit j
of word w of query t is set where t holds key 32 w + j (`pack_bits`; 33.5 MB a
row at 16384 tokens, where the picks as indices (B, L, 2048) int32 would be
134 MB and a [L, L] byte mask 268 MB).  `fused_attention` takes it as its input
`Picks` and gives weight to no other pair; `index_alignment` reads it too.  The
choice is whole numbers: no gradient passes it.

The scores are made by query chunk (`CHUNK` queries against the keys up to
their band's end, `BAND` queries a band, so that the work follows the causal
triangle in steps): no [L, L] float32 array of a whole layer is in HBM.  The
chosen keys of a row are those above its `topk`-th largest score and, of those
equal to it, the ones up to the index `last`.  Both are found by COUNTING, not
by sorting the row (`ops/sparse_index_kernels.py`): the scores as whole numbers
in the scores' order, the threshold a bit at a time by 32 passes that compare
and count, then its equals: exact, ties included.  On the TPU, where a chunk is
whole (8, 128) tiles, a Pallas kernel makes every pass on a block of rows held
in VMEM (`select`); anywhere else the same function is plain `jax.numpy`
(`kth_and_last`).  The platform and the shape choose, nothing else can.

A `recompute_scope` round the layer KEEPS `Picks` (`registry.set_kept`, marked
as one that must be kept whatever the room): the forward that backward makes
again READS the choice and never chooses again, for a top-k made on scores that
another fusion rounded otherwise is another mask, and the gradient would be of
an attention that never ran.

`index_alignment` is the loss that trains the indexer (the report's sparse
training stage): mean over rows and queries of KL(p_t || softmax_{S_t}(I[t, .])),
p_t[s] = (1 / Hq) sum_h P[t, h, s] the main attention's probabilities over S_t
summed over its heads, a CONSTANT (the op stops the gradient of the attention's
queries and keys itself).  The target is made under the scope
`selected_attention` (it is that attention's scores once more, for the chosen
pairs), by query chunk as the scores are, with a softmax of its own so that it
sums to 1 whatever kernel made the attention's output; that attention's
log-sum-exp (`fused_attention`'s output `Lse`) only steadies the exponentials.
On the TPU, where a chunk and its band are whole tiles and the heads 128 lanes
wide, one Pallas kernel a chunk makes it (`ops/alignment_target_kernels.py`: a
head's scores and exponentials live for a block of keys in VMEM); anywhere else
`attention_target`'s plain form, whose [group, C, K] float32 exponentials pass
through HBM; the platform and the shape choose, nothing else can.
Its gradient reaches
qI, kI and w and nothing else; it is computed WITH the value, chunk by chunk
(the value is linear in its cotangent), so that backward holds three small
arrays and no [L, L] one; a builder therefore puts the op AFTER its layer's
`recompute_scope` (`build_causal_lm` does), where it is made once.

A chunk's value is `index_scores` as `sparse_index` runs it (XLA fuses the ReLU,
the weights and the heads' sum into the product: I [C, K] leaves and no
per-head array), the softmax over the allowed keys and the term.  Its gradients
are NOT `jax.vjp`'s through `index_scores`, which kept the per-head products
[Hi, C, K] float32 for backward and handed two more einsums a d_products of
that shape: the term's gradient to the scores is written out, dI = r T - target
over the held pairs (T the row's sum of the held target), and the three
gradients are made from dI with the products made AGAIN where they are used
(`ops/index_alignment_kernels.py`: on the TPU, where a chunk and its band are
whole tiles, one Pallas kernel a chunk that holds a head's products for a block
of keys in VMEM; anywhere else the same sums in `jax.numpy`; the platform and
the shape choose, nothing else can).  So the index scores are computed four
times a pair, once for the value, once more for the ReLU's mask and d_w, and
the two gradient products, and nothing [Hi, C, K] reaches HBM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from ..core import analysis as _A
from ..core import resource_plan as _RP
from ..core.registry import register_op, set_kept, set_step_stats
from ..monitor import MONITOR as _MON
from . import alignment_target_kernels, index_alignment_kernels, sparse_index_kernels
from .common import counted_rules, first

#: Queries a chunk of the scores (`sa_config.q_chunk_size`, read as tiling) and queries a band: a band's chunks see the
#: keys up to the band's end, so eight bands at 16384 tokens compute 56% of the square for the triangle's 50%.
CHUNK = 512
BAND = 2048


@register_op("stop_gradient")
def _stop_gradient(ctx, op, ins):
    """The identity whose gradient is zero: what reads `Out` trains nothing
    that made `X`."""
    return {"Out": jax.lax.stop_gradient(first(ins, "X"))}


def pack_bits(chosen):
    """int32 [..., K / 32] of bool [..., K]: bit j of word w is element 32 w + j."""
    words = chosen.reshape(chosen.shape[:-1] + (chosen.shape[-1] // 32, 32)).astype(jnp.uint32)
    packed = jnp.sum(words << jnp.arange(32, dtype=jnp.uint32), axis=-1, dtype=jnp.uint32)
    return jax.lax.bitcast_convert_type(packed, jnp.int32)


def unpack_bits(picks, length: int):
    """bool [..., `length`] of `pack_bits`' words."""
    words = jax.lax.bitcast_convert_type(picks, jnp.uint32)
    bits = (words[..., None] >> jnp.arange(32, dtype=jnp.uint32)) & jnp.uint32(1)
    return bits.reshape(picks.shape[:-1] + (-1,))[..., :length].astype(jnp.bool_)


def chunking(length: int):
    """(queries a chunk, [(first query, end) of each band]): `CHUNK` and `BAND`
    where they divide the length, else the whole length as one."""
    chunk = CHUNK if length % CHUNK == 0 else length
    band = BAND if length % BAND == 0 and BAND % chunk == 0 else length
    return chunk, [(lo, lo + band) for lo in range(0, length, band)]


def chunk_pairs(length: int) -> int:
    """(chunk of queries, chunk of keys) pairs on or under the diagonal."""
    n = length // chunking(length)[0]
    return n * (n + 1) // 2


def index_scores(qi, ki, w):
    """I [C, K] float32 of a chunk's qI [C, Hi, Di], the keys' kI [K, Di] and
    the chunk's weights w [C, Hi] float32, the two scales already in w."""
    products = jnp.einsum("chd,kd->hck", qi, ki, preferred_element_type=jnp.float32)
    # the weights and the sum over the heads off the matrix unit, which would round the float32 products to its operands'
    return jnp.sum(jax.nn.relu(products) * jnp.transpose(w)[:, :, None], axis=0)


def scaled_weights(w, heads: int, width: int):
    return w.astype(jnp.float32) * np.float32(heads ** -0.5 * width ** -0.5)


def choose(scores, first_query, topk: int, select=sparse_index_kernels.kth_and_last):
    """bool [C, K]: the keys each of a chunk's queries (`first_query` on)
    holds, from its float32 scores against keys 0 to K - 1; `select` finds a
    row's threshold (one of `sparse_index_kernels`' two forms)."""
    chunk, keys = scores.shape
    at = first_query + jax.lax.broadcasted_iota(jnp.int32, (chunk, keys), 0)
    key = jax.lax.broadcasted_iota(jnp.int32, (chunk, keys), 1)
    causal = key <= at
    if keys <= topk:
        return causal
    masked = jnp.where(causal, scores, -jnp.inf)
    kth, last = select(masked, topk)
    return causal & ((masked > kth) | ((masked == kth) & (key <= last)))


def _by_chunk(length: int, body):
    """`body(first query, keys)` for every chunk, a band at a time: its
    results, each concatenated along the chunks' axis (a `lax.map` a band)."""
    chunk, bands = chunking(length)
    found = []
    for lo, hi in bands:
        starts = lo + chunk * jnp.arange((hi - lo) // chunk, dtype=jnp.int32)
        found.append(jax.lax.map(functools.partial(body, keys=hi), starts))
    return jax.tree.map(lambda *parts: jnp.concatenate(parts, axis=0), *found)


def _select_row(qi, ki, w, topk: int, select=sparse_index_kernels.kth_and_last):
    """One row: (picks int32 [L, L / 32], `Stats`' five counts of the row)."""
    length = qi.shape[0]
    chunk = chunking(length)[0]

    def body(start, keys):
        scores = index_scores(jax.lax.dynamic_slice_in_dim(qi, start, chunk, 0), ki[:keys],
                              jax.lax.dynamic_slice_in_dim(w, start, chunk, 0))
        chosen = choose(scores, start, topk, select)
        at = start + jax.lax.broadcasted_iota(jnp.int32, chosen.shape, 0)
        recent = chosen & (jax.lax.broadcasted_iota(jnp.int32, chosen.shape, 1) > at - topk)
        touched = jnp.any(chosen.reshape(chunk, keys // chunk, chunk), axis=(0, 2))
        stats = jnp.stack([jnp.sum(chosen, dtype=jnp.int32), jnp.sum(recent, dtype=jnp.int32),
                           jnp.sum(touched, dtype=jnp.int32)])
        return jnp.pad(pack_bits(chosen), ((0, 0), (0, (length - keys) // 32))), stats

    picks, stats = _by_chunk(length, body)
    whole = jnp.asarray([length, chunk_pairs(length)], jnp.int32)
    return picks.reshape(length, length // 32), jnp.concatenate([jnp.sum(stats.reshape(-1, 3), axis=0), whole])


@register_op("sparse_index")
def _sparse_index(ctx, op, ins):
    """See the module's docstring.  `Picks` int32 (B, L, L / 32); `Stats`
    int32 [5], summed over the rows: the (query, key) pairs chosen, those of
    them among the query's nearest `topk` keys, the (chunk of queries, chunk of
    keys) pairs on or under the diagonal that hold a chosen pair, the queries
    and all such chunk pairs: what `train_loop` publishes a logged step
    (`_publish_sparse_index`)."""
    qi, ki, w = first(ins, "QI"), first(ins, "KI"), first(ins, "W")
    ki = ki.reshape(ki.shape[0], ki.shape[1], ki.shape[-1])
    w = scaled_weights(jax.lax.stop_gradient(w), qi.shape[2], qi.shape[3])
    topk = op.attr("topk")
    chunk, bands = chunking(qi.shape[1])
    selecting = [keys for _, keys in bands if keys > topk]       # the bands whose chunks have a threshold to find
    kernel = ctx.platform == "tpu" and bool(selecting) and all(sparse_index_kernels.fits(chunk, keys) for keys in selecting)
    _MON.counter("lowering.sparse_index_ops").inc()
    _MON.counter("lowering.index_select_kernel_calls").inc(1 if kernel else 0)
    select = sparse_index_kernels.select if kernel else sparse_index_kernels.kth_and_last
    with jax.named_scope("index_select"):
        picks, stats = jax.lax.map(lambda row: _select_row(*row, topk, select),
                                   (jax.lax.stop_gradient(qi), jax.lax.stop_gradient(ki), w))
    return {"Picks": picks, "Stats": jnp.sum(stats, axis=0)}


def _whole(reduced):
    """A row's statistic made WHOLE before it is spread over the row again.
    XLA's TPU compiler otherwise writes `x - max(x, keepdims)` as ONE
    `reduce-window` over the whole row (window 2 K - 1, padded K - 1 either
    side): 188 ms a step for each such pass over [8, 512, 8192] where a reduce
    and a pass take 20 (my chip run, PR 56)."""
    return jax.lax.optimization_barrier(reduced)


def attention_target(q, k, lse, allowed, scale: float, kernel=None):
    """p [C, K] float32: the main attention's probabilities of a chunk's
    queries q [Hq, C, dh] over the keys k [Hkv, K, dh] that `allowed` holds,
    each head's softmax over those keys, summed over the heads and divided by
    their number.  The softmax is this function's own (it sums to 1 whatever
    made the attention's output); `lse` [Hq, C], that attention's log-sum-exp,
    only steadies the exponentials, so no pass looks for a row's largest score.
    A key/value head's group of query heads at a time.  Both forms pass here:
    `kernel` is `alignment_target_kernels.target` where `_index_alignment`
    found the platform and the shapes fit (a group's scores live for a block
    of keys in VMEM only); without it the plain form below, whose [group, C, K]
    float32 exponentials are whole in HBM before the row's sum divides them."""
    if kernel is not None:
        return kernel(q, k, lse, allowed, scale)
    heads, kv_heads = q.shape[0], k.shape[0]
    group = heads // kv_heads

    def of_group(operands):
        qg, kg, steady = operands
        s = jnp.einsum("hcd,kd->hck", qg, kg, preferred_element_type=jnp.float32) * scale
        e = jnp.where(allowed, jnp.exp(s - steady[:, :, None]), 0.0)
        return jnp.sum(e * (1.0 / _whole(jnp.sum(e, axis=-1)))[:, :, None], axis=0)

    grouped = (q.reshape(kv_heads, group, *q.shape[1:]), k, lse.reshape(kv_heads, group, lse.shape[-1]))
    return jnp.sum(jax.lax.map(of_group, grouped), axis=0) / heads


def _divergence_of(scores, target, allowed):
    """(the chunk's term, which pairs it holds, log r [C, K]) of the index
    scores I [C, K]: r the softmax of I over the allowed keys."""
    scores = jnp.where(allowed, scores, -jnp.inf)
    top = _whole(jax.lax.stop_gradient(jnp.max(scores, axis=-1)))
    log_r = scores - (top + jnp.log(_whole(jnp.sum(jnp.exp(scores - top[:, None]), axis=-1))))[:, None]
    held = allowed & (target > 0)
    term = jnp.sum(jnp.where(held, target * (jnp.log(jnp.where(held, target, 1.0)) - jnp.where(held, log_r, 0.0)), 0.0))
    return term, held, log_r


def chunk_divergence(qi, ki, w, target, allowed):
    """sum over a chunk's queries of KL(target_t || softmax over the allowed
    keys of I[t, .]), float32; `target` is a constant."""
    return _divergence_of(index_scores(qi, ki, w), target, allowed)[0]


def chunk_divergence_and_gradients(qi, ki, w, target, allowed, gradients=index_alignment_kernels.gradients_plain):
    """(`chunk_divergence`, its gradients to qI [C, Hi, Di], kI [K, Di] and w
    [C, Hi], float32).  The value is `chunk_divergence`'s own expressions; its
    gradient to the scores is written out, dI = r T - target over the held
    pairs (T the row's sum of the held target; zero where a key is not
    allowed), and `gradients` (one of `index_alignment_kernels`' two forms)
    makes the three from it with the per-head products made again: nothing
    [Hi, C, K] is kept of the forward pass."""
    term, held, log_r = _divergence_of(index_scores(qi, ki, w), target, allowed)
    target = jnp.where(held, target, 0.0)
    d_scores = jnp.exp(log_r) * _whole(jnp.sum(target, axis=-1))[:, None] - target
    return (term,) + tuple(gradients(qi, ki, w, d_scores))


def _alignment_row(qi, ki, w, q, k, lse, picks, scale: float, gradients, target_kernel=None):
    """One row's summed divergence and, with `gradients` (one of
    `index_alignment_kernels`' two forms; None for the value alone), its
    gradients to qI [L, Hi, Di], kI [L, Di] and w [L, Hi] (the SCALED weights');
    `target_kernel` is `attention_target`'s, None for its plain form."""
    length = qi.shape[0]
    chunk = chunking(length)[0]

    def body(start, keys):
        def rows(t):
            return jax.lax.dynamic_slice_in_dim(t, start, chunk, 0)

        allowed = unpack_bits(rows(picks)[:, :keys // 32], keys)
        with jax.named_scope("selected_attention"):
            target = attention_target(jax.lax.dynamic_slice_in_dim(q, start, chunk, 1), k[:, :keys],
                                      jax.lax.dynamic_slice_in_dim(lse, start, chunk, 1), allowed, scale, target_kernel)
        operands = (rows(qi), ki[:keys], rows(w))
        if gradients is None:
            return (chunk_divergence(*operands, target, allowed),)
        value, d_qi, d_ki, d_w = chunk_divergence_and_gradients(*operands, target, allowed, gradients)
        # qI's gradient in qI's dtype here, as `jax.vjp` gave it: the chunks stack up to half the bytes
        return value, d_qi.astype(qi.dtype), jnp.pad(d_ki, ((0, length - keys), (0, 0))), d_w

    found = _by_chunk(length, body)
    if gradients is None:
        return (jnp.sum(found[0]),)
    value, d_qi, d_ki, d_w = found
    return jnp.sum(value), d_qi.reshape(qi.shape), jnp.sum(d_ki, axis=0), d_w.reshape(w.shape)


def _alignment(operands, scale: float, gradients, target_kernel):
    """The rows one at a time: (each row's mean divergence a query [B], then
    that mean's gradients a row)."""
    qi = operands[0]
    found = jax.lax.map(lambda row: _alignment_row(*row, scale, gradients, target_kernel), operands)
    return tuple(t / qi.shape[1] for t in found)


@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8, 9))
def _divergence(qi, ki, w, q, k, lse, picks, scale, gradients, target_kernel=None):
    return _alignment((qi, ki, w, q, k, lse, picks), scale, None, target_kernel)[0]


def _divergence_fwd(qi, ki, w, q, k, lse, picks, scale, gradients, target_kernel):
    rows, *made = _alignment((qi, ki, w, q, k, lse, picks), scale, gradients, target_kernel)
    return rows, tuple(g.astype(t.dtype) for g, t in zip(made, (qi, ki, w)))


def _divergence_bwd(scale, form, target_kernel, gradients, cotangent):
    """A row's term is linear in its cotangent: the gradients made with it, times that."""
    d_qi, d_ki, d_w = (cotangent.reshape((-1,) + (1,) * (g.ndim - 1)).astype(g.dtype) * g for g in gradients)
    return d_qi, d_ki, d_w, None, None, None, None


_divergence.defvjp(*counted_rules("index_alignment", _divergence_fwd, _divergence_bwd))


@register_op("index_alignment")
def _index_alignment(ctx, op, ins):
    """See the module's docstring.  Q (B, Hq, L, dh) and K (B, Hkv, L, dh) are
    the main attention's operands as it reads them (heads-major), `scale` its
    scores' scale and `Lse` (B, Hq, L) float32 its log-sum-exp a query; `Rows`
    (B,) float32 is each row's mean over its queries and `Out` [1] their mean."""
    qi, ki, w = first(ins, "QI"), first(ins, "KI"), first(ins, "W")
    q, k, lse = (jax.lax.stop_gradient(first(ins, slot)) for slot in ("Q", "K", "Lse"))
    ki = ki.reshape(ki.shape[0], ki.shape[1], ki.shape[-1])
    scale = op.attr("scale", None)
    scale = float(q.shape[-1]) ** -0.5 if scale is None else float(scale)
    chunk, bands = chunking(qi.shape[1])
    kernel = ctx.platform == "tpu" and all(index_alignment_kernels.fits(chunk, keys, *qi.shape[2:]) for _, keys in bands)
    _MON.counter("lowering.index_alignment_ops").inc()
    _MON.counter("lowering.index_alignment_kernel_calls").inc(1 if kernel else 0)
    gradients = index_alignment_kernels.gradients if kernel else index_alignment_kernels.gradients_plain
    target_fits = ctx.platform == "tpu" and all(alignment_target_kernels.fits(chunk, keys, q.shape[1], k.shape[1], q.shape[3])
                                                for _, keys in bands)
    _MON.counter("lowering.alignment_target_kernel_calls").inc(1 if target_fits else 0)
    rows = _divergence(qi, ki, scaled_weights(w, qi.shape[2], qi.shape[3]), q, k, lse.astype(jnp.float32),
                       first(ins, "Picks"), scale, gradients, alignment_target_kernels.target if target_fits else None)
    return {"Out": jnp.mean(rows).reshape(1), "Rows": rows}


def _publish_sparse_index(step, values):
    """One logged step's `sparse_index` record, a value a layer: the picks a
    query, the share of them among the query's nearest `topk` keys, the share
    of the chunk pairs under the diagonal that hold a chosen pair (what a
    kernel that skips the others could leave out), and the alignment term."""
    stats = [np.asarray(v, "i8").reshape(5) for v in values["Stats"]]
    record = {"kind": "sparse_index", "pipeline_step": step,
              "picks": [int(s[0]) for s in stats], "queries": [int(s[3]) for s in stats],
              "picks_per_query": [float(s[0]) / max(int(s[3]), 1) for s in stats],
              "recent_share": [float(s[1]) / max(int(s[0]), 1) for s in stats],
              "chunk_pairs_touched_share": [float(s[2]) / max(int(s[4]), 1) for s in stats]}
    if values.get("Out"):
        record["index_kl"] = [float(np.asarray(v, "f8").reshape(-1)[0]) for v in values["Out"]]
        _MON.gauge("sparse_index.index_kl").set(max(record["index_kl"]))
    _MON.gauge("sparse_index.chunk_pairs_touched_share").set(max(record["chunk_pairs_touched_share"]))
    _MON.record_step(record)


set_step_stats("sparse_index", ("Stats",), _publish_sparse_index)
set_step_stats("index_alignment", ("Out",), _publish_sparse_index)


# -- build-time shape and dtype rules -----------------------------------------

def _indexer_shapes(ctx):
    """(B, L, Hi, Di) of QI, once KI and W agree with it."""
    qi, ki, w = ctx.in_shape("QI"), ctx.in_shape("KI"), ctx.in_shape("W")
    if qi is None:
        return None
    if len(qi) != 4:
        ctx.fail(f"QI must be (B, L, index heads, width), got {qi}")
    if ki is not None and (tuple(ki) not in ((qi[0], qi[1], 1, qi[3]), (qi[0], qi[1], qi[3]))):
        ctx.fail(f"KI must be ONE key a token, (B, L, 1, {qi[3]}), got {ki} beside QI {qi}")
    if w is not None and tuple(w) != tuple(qi[:3]):
        ctx.fail(f"W must be a weight a query and index head, {tuple(qi[:3])}, got {w}")
    if qi[1] % 32:
        ctx.fail(f"{qi[1]} positions: the picks are words of 32 keys, so a whole number of them")
    return qi


def _infer_sparse_index(ctx):
    qi = _indexer_shapes(ctx)
    if qi is None:
        return
    if ctx.op.attr("topk", 0) < 1:
        ctx.fail(f"topk {ctx.op.attr('topk', None)}: a query holds one key at the least, its own")
    ctx.set_out("Picks", (qi[0], qi[1], qi[1] // 32), "int32")
    ctx.set_out("Stats", (5,), "int32")


def _infer_index_alignment(ctx):
    qi = _indexer_shapes(ctx)
    q, k, picks, lse = ctx.in_shape("Q"), ctx.in_shape("K"), ctx.in_shape("Picks"), ctx.in_shape("Lse")
    if qi is None:
        return
    if lse is not None and q is not None and tuple(lse[1:]) != tuple(q[1:3]):
        ctx.fail(f"Lse must be a log-sum-exp a query and head of Q, (B, {q[1]}, {q[2]}), got {lse}")
    if picks is not None and tuple(picks) != (qi[0], qi[1], qi[1] // 32):
        ctx.fail(f"Picks must be {(qi[0], qi[1], qi[1] // 32)} (sparse_index's), got {picks}")
    for name, shape in (("Q", q), ("K", k)):
        if shape is not None and (len(shape) != 4 or shape[2] != qi[1]):
            ctx.fail(f"{name} must be the attention's heads-major operand (B, H, {qi[1]}, dh), got {shape}")
    if q is not None and k is not None and (q[-1] != k[-1] or q[1] % k[1]):
        ctx.fail(f"Q {q} and K {k}: one head width, and K's heads a divisor of Q's")
    ctx.set_out("Out", (1,), "float32")
    ctx.set_out("Rows", (qi[0],), "float32")


def _infer_stop_gradient(ctx):
    if ctx.in_shape("X") is not None:
        ctx.set_out("Out", ctx.in_shape("X"), ctx.in_dtype("X"))


_A.register_rule(["sparse_index"], _infer_sparse_index)
_A.register_rule(["index_alignment"], _infer_index_alignment)
_A.register_rule(["stop_gradient"], _infer_stop_gradient)


# -- cost rows (core/resource_plan.py) -----------------------------------------

def _triangle(length: int) -> float:
    return length * (length + 1) / 2.0


def _cost_sparse_index(ctx):
    """The triangle's scores, Hi heads of Di, 2 per multiply-add, and a ReLU,
    a weight and a sum a head and pair.  The choosing is compares over the same
    pairs, 34 a pair where no scores tie (`sparse_index_kernels`: 32 counting
    passes for the threshold's bits, two for its equals): 1.6% of the scores'
    2096 operations a pair at 16 heads of 64, which the row leaves out.
    Traffic: the op's own operands and the picks."""
    qi = ctx.in_shape("QI")
    if qi is None:
        return float(ctx.out_elems_total()), ctx.io_bytes()
    return qi[0] * _triangle(qi[1]) * qi[2] * (2.0 * qi[3] + 3.0), ctx.io_bytes()


def _cost_index_alignment(ctx):
    """The index scores FOUR times (once more for the value, once again for the
    ReLU's mask and the weights' gradient where the gradients are made, and the
    two products of the gradients to qI and kI:
    `index_alignment_kernels`), and the main attention's scores over the
    triangle for the target."""
    qi, q = ctx.in_shape("QI"), ctx.in_shape("Q")
    if qi is None or q is None:
        return float(ctx.out_elems_total()), ctx.io_bytes()
    pairs = qi[0] * _triangle(qi[1])
    return pairs * (4.0 * qi[2] * (2.0 * qi[3] + 3.0) + q[1] * (2.0 * q[3] + 4.0)), ctx.io_bytes()


_RP.register_cost(["sparse_index"], _cost_sparse_index)
_RP.register_cost(["index_alignment"], _cost_index_alignment)
_RP.register_elementwise_cost("stop_gradient", flops_per_elem=0.0)


# -- what a `recompute_scope` round the layer keeps (core/lowering.py: plan_kept) ----------

def _kept_picks(ctx, op, shapes):
    """The choice itself, and it MUST be kept (the third value): the forward
    made again reads it."""
    name = op.output("Picks")[0]
    return name, shapes.nbytes(name), True


set_kept("sparse_index", _kept_picks)
