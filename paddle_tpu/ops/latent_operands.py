"""The latent attention's kernel operands, each written once.

`models/transformer.py: latent_attention` emits, between its three projections
and its `fused_attention(layout="blhd")`, a chain of ops that moves bytes and
computes next to nothing: Q = concat(slice, rotary_embedding(slice)) of the
query projection; K = concat(slice of the up-projection, expand(
rotary_embedding(reshape2 of the one shared part a token))); V = slice of the
up-projection (without `positions` no rotation, and Q as projected: Kimi
Linear's global layer).  Lowered op by op, every slice, rotation, spreading,
concat, scaling and move to heads-major is an array of the operands' size in
HBM, and JAX's transposes of them (pad, `add_any`, split) are as many again.

Here the chain and its attention are ONE differentiable unit, found in the
program at lowering (`plan`; nothing marks it, no flag and no attribute):

    forward   q_hm [B, H, L, nope + rope] = heads-major of [q_n ; rot(q_r)] . scale
              k_hm [B, H, L, nope + rope] = [k_own ; rot(k_r) for every head]
              v_hm [B, H, L, v]
    backward  d(q projection) from dq_hm: scale, inverse rotation, position-major
              d(up projection) [B, L, H, nope + v] from dk_hm[..., :nope] and dv_hm
              d(k_r) = inverse rotation of the sum over heads of dk_hm[..., nope:]

a `jax.custom_vjp` (`assemble`) whose two directions are written by hand: two
passes each way (the queries'; the keys' and values', from ONE read of the
up-projection), every pass reads what a projection or a kernel wrote and
writes what a kernel or a projection reads.  On the chip the passes are the
Pallas kernels of `ops/latent_kernels.py`; anywhere else plain `jax.numpy` of
the same arithmetic (NOT on the chip: XLA holds every slice of a `jnp.roll` as
an array of its own, and the plain form wrote more than the ops it replaces).
The rotation inside a pass is a rotation of the lanes and a select on the lane
(no product with a constant matrix), float32 angles from `Positions` exactly
as `rotary_embedding` makes them, float32 arithmetic and ONE rounding to the
operands' dtype at the end: one fewer than rotate, round, scale, round.  The
unit keeps nothing for backward but the positions: q_hm and k_hm are the
attention's residuals, made again by a `recompute_scope` segment in one pass
each.

The attention itself is `nn_ops.attention` on operands that are already
heads-major and carry the scale (`assembled`): the same path rule, kernels,
residual names and counters as the op alone.

The passes stand under the scopes of the ops they stand for, as `run_ops`
would have opened them: those that rotate (the queries' pass and its transpose,
the shared row's rotation and its way back) under the chain's own
`rotary_embedding` ops (`.../latent_attention/rotary/opN:rotary_embedding`),
the keys' and values' pass, which rotates nothing, under the keys' `concat`,
the attention under `fused_attention`.

What falls back to the op-by-op lowering, counted by reason
(`lowering.latent_operands_fallback_<reason>`, and `lowering.latent_operands_fallback`
over all of them; `lowering.latent_operands_assembled` counts the units taken):
an intermediate of the chain that is fetched or persistable (`fetched`), read
by an op outside the chain among the ops the step lowers (`shared_reader`: the
`for_test` clone whose stage ops read a layer's Q, K, V) or named among what
its recomputed segment keeps (`kept`); a chain of another shape than the one
above (`shape`); a mesh that splits heads or positions (`mesh`: where
`_attention_path` leaves the kernels, the unit steps aside too)."""
from __future__ import annotations

from functools import partial
from typing import Any, Dict, List, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..monitor import MONITOR as _MON
from . import latent_kernels
from .common import canon_dtype, counted_rules, rotary_angles

#: Why a latent attention was not assembled: `lowering.latent_operands_fallback_<reason>`.
REASONS = ("fetched", "shared_reader", "kept", "shape", "mesh")


class Passes(NamedTuple):
    """What `assemble` needs beside its operands, all static: the widths, the
    rotation (none where `rotated` is False), the scope each direction's passes
    stand under and whether they are the chip's kernels."""
    nope: int           # a head's own (unrotated) part of a query and a key
    rope: int           # the rotated part, which the heads share in a key
    scale: float        # the scores', carried by the queries
    rotated: bool
    theta: float
    interleave: bool    # feature 2i turns with 2i + 1, and not i with i + rope / 2
    q_scope: str        # the queries' pass and its transpose: the passes that rotate
    k_scope: str        # the keys' and the values', and theirs: they move what is rotated already
    shared_scope: str   # the rotation of the one shared row a token, and its way back
    kernels: bool       # `ops/latent_kernels.py`'s passes (the chip), else plain `jax.numpy` (anywhere)
    interpret: bool = False

    @property
    def shift(self) -> Optional[int]:
        """How many lanes off a pair's other member lies; None without a rotation."""
        return None if not self.rotated else 1 if self.interleave else self.rope // 2


class Unit(NamedTuple):
    """One latent attention of the program as `plan` found it."""
    attention: Any              # the `fused_attention` op
    chain: tuple                # the ops lowered with it, none of them on its own
    q: str                      # the query projection (B, L, H, nope + rope)
    up: str                     # the up-projection (B, L, H, nope + v)
    shared: str                 # the part of a key that the heads share (B, L, rope)
    positions: Optional[str]
    nope: int
    rope: int
    theta: float
    interleave: bool
    q_pass: Any                 # the ops whose scopes the passes stand under: the queries' (their rotation, else
    k_pass: Any                 # the attention), the keys' and values' (the keys' concat), the shared row's rotation
    shared_pass: Any


# -- the passes ---------------------------------------------------------------------------------------------------

def _pairs(pos, p: Passes):
    """cos, sin (B, L, 1, rope) float32, a pair's two members alike."""
    cos, sin = rotary_angles(pos, p.rope // 2, p.theta, True)
    if p.interleave:
        return jnp.repeat(cos, 2, axis=-1), jnp.repeat(sin, 2, axis=-1)
    return jnp.concatenate([cos, cos], axis=-1), jnp.concatenate([sin, sin], axis=-1)


def _tables(pairs):
    """The kernels' tables: cos, sin (B, L, 2 rope) float32, `_pairs`' lanes twice; (None, None) without a rotation."""
    return (None, None) if pairs is None else tuple(jnp.concatenate([t[:, :, 0], t[:, :, 0]], axis=-1) for t in pairs)


def _turned(x, cos, sin, p: Passes):
    """Float32 x (..., rope) turned by the angles: x cos + other sin, `other`
    the pair's other member, signed ((-x[2i+1], x[2i]), or (-x[i + rope/2],
    x[i])), by a rotation of the lanes either way and a select on the lane.
    Turned back with `-sin`: a rotation's transpose is its inverse."""
    lane = jax.lax.broadcasted_iota(jnp.int32, (p.rope,), 0)
    other = jnp.where(lane % (2 * p.shift) < p.shift, -jnp.roll(x, -p.shift, axis=-1), jnp.roll(x, p.shift, axis=-1))
    return x * cos + other * sin


def _queries(q, pairs, p: Passes):
    with jax.named_scope(p.q_scope):
        if p.kernels:
            return latent_kernels.queries(q.reshape(q.shape[:2] + (-1,)), *_tables(pairs), heads=q.shape[2], nope=p.nope,
                                          scale=p.scale, shift=p.shift, interpret=p.interpret)
        x = q.astype(jnp.float32)
        if p.rotated:
            x = jnp.concatenate([x[..., :p.nope], _turned(x[..., p.nope:], *pairs, p)], axis=-1)
        return jnp.swapaxes(x * p.scale, 1, 2).astype(q.dtype)


def _keys_values(up, shared, pairs, p: Passes):
    part = shared[:, :, None, :]
    if p.rotated:    # ONE row a token: XLA's on the chip too
        with jax.named_scope(p.shared_scope):
            part = _turned(part.astype(jnp.float32), *pairs, p).astype(up.dtype)
    with jax.named_scope(p.k_scope):
        if p.kernels:
            return latent_kernels.keys_values(up.reshape(up.shape[:2] + (-1,)), part[:, :, 0], heads=up.shape[2],
                                              nope=p.nope, interpret=p.interpret)
        part = jnp.broadcast_to(part, up.shape[:3] + (p.rope,))
        return (jnp.swapaxes(jnp.concatenate([up[..., :p.nope], part], axis=-1), 1, 2),
                jnp.swapaxes(up[..., p.nope:], 1, 2))


@partial(jax.custom_vjp, nondiff_argnums=(0,))
def assemble(p: Passes, q, up, shared, pos):
    """(q_hm, k_hm, v_hm) of the module's docstring from the query projection
    (B, L, H, nope + rope), the up-projection (B, L, H, nope + v), the shared
    part (B, L, rope) and the positions ((B, L) integers, or None)."""
    pairs = _pairs(pos, p) if p.rotated else None
    return (_queries(q, pairs, p), *_keys_values(up, shared, pairs, p))


def _assemble_fwd(p: Passes, q, up, shared, pos):
    return assemble(p, q, up, shared, pos), pos


def _assemble_bwd(p: Passes, pos, cotangents):
    dq_hm, dk_hm, dv_hm = cotangents
    batch, heads, positions, _ = dq_hm.shape
    back = None
    if p.rotated:       # turned back: a rotation's transpose is its inverse
        cos, sin = _pairs(pos, p)
        back = (cos, -sin)
    with jax.named_scope(p.q_scope):
        if p.kernels:
            dq = latent_kernels.queries_back(dq_hm, *_tables(back), heads=heads, nope=p.nope, scale=p.scale,
                                             shift=p.shift, interpret=p.interpret)
        else:
            g = jnp.swapaxes(dq_hm, 1, 2).astype(jnp.float32) * p.scale
            if p.rotated:
                g = jnp.concatenate([g[..., :p.nope], _turned(g[..., p.nope:], *back, p)], axis=-1)
            dq = g.astype(dq_hm.dtype)
        dq = dq.reshape(batch, positions, heads, -1)
    with jax.named_scope(p.k_scope):
        if p.kernels:
            dup, part = latent_kernels.up_back(dk_hm, dv_hm, interpret=p.interpret)
        else:
            dup = jnp.concatenate([jnp.swapaxes(dk_hm[..., :p.nope], 1, 2), jnp.swapaxes(dv_hm, 1, 2)], axis=-1)
            part = jnp.sum(dk_hm[..., p.nope:].astype(jnp.float32), axis=1)     # over the heads
        dup = dup.reshape(batch, positions, heads, -1)
    if p.rotated:
        with jax.named_scope(p.shared_scope):
            part = _turned(part[:, :, None], *back, p)[:, :, 0]
    dshared = part.astype(dk_hm.dtype)
    return dq, dup, dshared, None if pos is None else np.zeros(pos.shape, jax.dtypes.float0)


assemble.defvjp(*counted_rules("latent_operands", _assemble_fwd, _assemble_bwd))


# -- the unit in the program ----------------------------------------------------------------------------------------

class _Other(Exception):
    """The ops round an attention are not the chain `plan` knows."""


def _reads(op) -> List[str]:
    """The names an op reads, its sub-block's ops' included."""
    names = list(op.input_arg_names)
    sub = op.attrs.get("sub_block")
    if sub is not None and op.type in ("while", "conditional_block", "dynamic_rnn", "repeat"):
        for inner in op.block.program.blocks[sub].ops:
            names += _reads(inner)
    return names


def _dims(op, name):
    var = op.block._find_var_recursive(name)
    return None if var is None or var.shape is None else tuple(var.shape)


def is_latent(op) -> bool:
    """A `fused_attention` whose values are narrower than its queries."""
    q, v = (_dims(op, op.input(slot)[0]) for slot in ("Q", "V"))
    return bool(q and v and 0 < v[-1] < q[-1])


def _match(attention, producer) -> Unit:
    """The chain of the module's docstring behind `attention`'s Q, K and V, or `_Other`."""
    def need(ok):
        if not ok:
            raise _Other

    def made(name, kind):
        op = producer.get(name)
        need(op is not None and op.type == kind)
        return op

    def sliced(name, lo, hi):    # (the `slice` that makes `name` as [..., lo:hi], what it slices)
        op = made(name, "slice")
        need((op.attr("axes"), op.attr("starts"), op.attr("ends")) == ([3], [lo], [hi]))
        return op, op.input("Input")[0]

    def joined(name):            # (the `concat` along the features that makes `name`, its two parts)
        op = made(name, "concat")
        need(op.attr("axis", 0) in (3, -1) and len(op.input("X")) == 2)
        return op, op.input("X")

    need(attention.attr("layout", "bhld") == "blhd" and attention.attr("causal", False)
         and not attention.input("Bias") and attention.attr("mask", None) is None)
    q_name, k_name, v_name = (attention.input(slot)[0] for slot in ("Q", "K", "V"))
    k_cat, (own, spread) = joined(k_name)
    own_op = made(own, "slice")
    need(own_op.attr("axes") == [3] and own_op.attr("starts") == [0])
    nope, up = own_op.attr("ends")[0], own_op.input("Input")[0]
    wide, narrow, up_dims = _dims(attention, q_name), _dims(attention, v_name), _dims(attention, up)
    need(wide and narrow and up_dims and len(up_dims) == 4 and up_dims[-1] == nope + narrow[-1] and 0 < nope < wide[-1])
    rope = wide[-1] - nope
    v_op, v_from = sliced(v_name, nope, nope + narrow[-1])
    need(v_from == up)
    spread_op = made(spread, "expand")
    need(list(spread_op.attr("expand_times")) == [1, 1, up_dims[2], 1])
    turned_k = producer.get(spread_op.input("X")[0])
    positions = None
    if turned_k is not None and turned_k.type == "rotary_embedding":
        need(turned_k.attr("layout", "bhld") == "blhd" and rope % 2 == 0)
        positions, shaped = turned_k.input("Positions")[0], made(turned_k.input("X")[0], "reshape2")
    else:
        turned_k, shaped = None, made(spread_op.input("X")[0], "reshape2")
    need(list(shaped.attr("shape")) == [0, 0, 1, rope])
    shared = shaped.input("X")[0]
    shared_dims = _dims(attention, shared)
    need(shared_dims and len(shared_dims) == 3 and shared_dims[-1] == rope)
    if turned_k is None:
        need(_dims(attention, q_name)[-1] == nope + rope)
        return Unit(attention, (shaped, spread_op, own_op, v_op, k_cat), q_name, up, shared, None, nope, rope, 0.0, False,
                    attention, k_cat, k_cat)
    q_cat, (plain, turned) = joined(q_name)
    plain_op, q_from = sliced(plain, 0, nope)
    turned_q = made(turned, "rotary_embedding")
    how = [(op.attr("theta", 10000.0), op.attr("interleave", False), op.attr("layout", "bhld"), op.input("Positions"))
           for op in (turned_q, turned_k)]
    need(how[0] == how[1])
    part_op, part_from = sliced(turned_q.input("X")[0], nope, nope + rope)
    need(part_from == q_from and (_dims(attention, q_from) or (0,))[-1] == nope + rope)
    chain = (plain_op, part_op, turned_q, q_cat, shaped, turned_k, spread_op, own_op, v_op, k_cat)
    return Unit(attention, chain, q_from, up, shared, positions, nope, rope, float(how[0][0]), bool(how[0][1]),
                turned_q, k_cat, turned_k)


def _refused(ctx, unit: Unit, ops, at: Dict[int, int], readers) -> Optional[str]:
    """Why the unit cannot be lowered whole (one of `REASONS`), or None."""
    block = unit.attention.block
    members = {id(op) for op in unit.chain} | {id(unit.attention)}
    made = [n for op in unit.chain for n in op.output_arg_names]
    segment = unit.attention.attrs.get("recompute_segment")
    if any(_dims(unit.attention, n) is None or block._find_var_recursive(n).persistable for n in made) \
            or set(made) & set(ctx.fetch_names):
        return "fetched"
    if any(id(reader) not in members for n in made for reader in readers.get(n, ())):
        return "shared_reader"
    if set(made) & ctx.kept_by_segment.get(segment, set()):
        return "kept"
    # one run of ops, in one segment or in none, with nothing between the chain's first op and the attention that
    # ends the forward or writes what the passes read
    sources = {unit.q, unit.up, unit.shared, unit.positions}
    between = ops[min(at[id(op)] for op in unit.chain):at[id(unit.attention)]]
    if any(op.attrs.get("recompute_segment") != segment or op.type == "backward"
           or (id(op) not in members and sources & set(op.output_arg_names)) for op in between):
        return "shape"
    if ctx.mesh is not None and ctx.mesh.size > 1:
        return "mesh"
    _, positions, heads, width = _dims(unit.attention, unit.up)
    if ctx.platform == "tpu" and not latent_kernels.fits(heads, positions, unit.nope, unit.rope, width - unit.nope,
                                                         canon_dtype(block._find_var_recursive(unit.up).dtype)):
        return "shape"     # off the chip plain `jax.numpy` takes any widths; on it XLA holds that form's every slice
    return None


# This is where a chain of ops is fused, and how the next fused edge is to be built (PR 58 settled it: the
# program-level fusion passes went with the kernels nothing priced): at LOWERING time, over the ops the step really
# lowers, where `ctx.fetch_names`, the kept values and the mesh are visible and a refusal falls back op by op.
# `core/passes.py` holds only rewrites of the program that mean the same on every backend.
def plan(ctx, ops) -> None:
    """Find, once a trace and before any op of it is lowered, the latent
    attentions among `ops` (the ops the step lowers: the executor's, after its
    pruning) that are lowered as one unit with their chain: `ctx.latent_units`,
    the unit by the `id` of each of its ops.  Every latent attention is counted
    once, as assembled or as fallen back with the reason."""
    ctx.latent_units = {}
    attentions = [op for op in ops if op.type == "fused_attention" and is_latent(op)]
    if not attentions:
        return
    producer, readers, at = {}, {}, {}
    for i, op in enumerate(ops):
        at[id(op)] = i
        for n in _reads(op):
            readers.setdefault(n, []).append(op)
        for n in op.output_arg_names:
            producer[n] = op

    for attention in attentions:
        try:
            unit = _match(attention, producer)
            reason = _refused(ctx, unit, ops, at, readers)
        except _Other:
            reason = "shape"
        if reason is None:
            ctx.latent_units.update({id(op): unit for op in unit.chain + (attention,)})
            _MON.counter("lowering.latent_operands_assembled").inc()
        else:
            _MON.counter("lowering.latent_operands_fallback").inc()
            _MON.counter(f"lowering.latent_operands_fallback_{reason}").inc()


def lower(ctx, unit: Unit, env: Dict[str, Any], scope_of) -> None:
    """The unit's chain and attention into `env`: `assemble`, the attention on
    what it made, the output back to (B, L, H, v).  `scope_of(op)` is the path
    of scopes `run_ops` would have opened round `op`."""
    from .nn_ops import attention, attention_scale

    op = unit.attention
    q, up, shared = (env[n] for n in (unit.q, unit.up, unit.shared))
    pos = env[unit.positions] if unit.positions else None
    kernels = ctx.platform == "tpu" and latent_kernels.fits(up.shape[2], up.shape[1], unit.nope, unit.rope,
                                                            up.shape[3] - unit.nope, up.dtype)
    passes = Passes(unit.nope, unit.rope, attention_scale(op, q.shape[-1]), pos is not None, unit.theta, unit.interleave,
                    scope_of(unit.q_pass), scope_of(unit.k_pass), scope_of(unit.shared_pass), kernels)
    if pos is not None:
        _MON.counter("lowering.latent_rotary_ops").inc(2)    # the two the chain holds, as their own lowering counts them
    q_hm, k_hm, v_hm = assemble(passes, q, up, shared, pos)
    with jax.named_scope(scope_of(op)):
        out = attention(ctx, op, q_hm, k_hm, v_hm, assembled=True)
        env[op.output("Out")[0]] = jnp.swapaxes(out, 1, 2)
