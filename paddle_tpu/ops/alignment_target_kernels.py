"""The alignment target of a chunk with the per-head scores held in VMEM
(`ops/sparse_index_ops.py: attention_target` is the seam both forms pass and
the plain form; `_alignment_row` calls it a chunk):

    (q [Hq, C, dh], k [Hkv, K, dh], lse [Hq, C] float32, allowed [C, K], scale) -> p [C, K] float32

    s_h = (q_h . k_g(h)^T) scale           the operands' dtype into float32
    e_h = exp(s_h - lse_h) where allowed, else 0
    l_h = sum_k e_h                        the target's OWN row sum
    p   = (1 / Hq) sum_h e_h (1 / l_h)

The plain form makes e for a key/value group's heads, [group, C, K] float32,
and an `optimization_barrier` stands between the row's sum and its use, so e
is whole in HBM before it is divided: 2.3 float32 passes of 77 GB a step in
Keye-VL-2.0's cell for 25 ms of products (PERF.md, section 6, PR 62).  l_h is
known only after the last key, so `target` makes the scores TWICE and keeps
nothing [group, C, K] anywhere: ONE `pallas_call` a chunk, its grid (sweep,
block of `_block(K)` keys, key/value group), the group innermost.  q's chunk
[Hkv, group, C, dh], lse and the sums l stay in VMEM, k's block and the
mask's block stream.  Sweep 0: a head's s and e for the block, e's row sums
into l [Hkv, C, group].  Sweep 1 (its first step of a group turns l into
1 / l): s and e again, sum_h e_h / l_h over the group's heads into the output's
[C, block] tile, which stays in VMEM while the groups pass and leaves once,
divided by Hq (sweep 0 parks the output's window on the first tile, which
sweep 1 writes first).  Every product is the operands' dtype into float32;
the scale, the difference from lse, `exp`, the sums, the reciprocal and the
division are float32, as in the plain form; no copy of e outlives a step.

The other honest shape, ONE sweep with e_h [rows, K] of a tile of rows kept in
a VMEM scratch until l_h is known, was priced beside this one and deleted: as
fast alone at its best tile, 33% slower in the cell's step, and at 16384 keys
it takes 38 MB of VMEM at 128 rows (PERF.md, section 6, PR 62).

Chosen by the platform and the shape (`sparse_index_ops._index_alignment`),
never by a flag: `fits`, else the plain form.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

F32 = jnp.float32
LANES = 128
#: The most queries a call takes and the most keys a grid step: a head's s and e are [ROWS, BLOCK] float32 tiles in VMEM,
#: 2 MB each, beside q's chunk twice (the pipeline's two buffers, 8 MB at 32 heads of 128 in bf16) and the output's tile twice.
ROWS = 512
BLOCK = 1024
_VMEM_LIMIT = 64 * 2 ** 20


def _block(keys: int) -> int:
    """Keys a grid step: the most of 128, 256, 512 and `BLOCK` that divide `keys`."""
    return max(b for b in (128, 256, 512, BLOCK) if keys % b == 0)


def fits(chunk: int, keys: int, heads: int, kv_heads: int, width: int) -> bool:
    """Whether `target` takes a chunk of `chunk` queries of `heads` heads
    `width` wide over `kv_heads` key/value heads against `keys` keys: whole
    tiles of rows (32 of the mask's bytes) and of keys, heads of whole tiles of
    lanes, whole groups, and no more of q than a chunk of 32 heads of 128 holds
    in VMEM."""
    return (chunk % 32 == 0 and chunk <= ROWS and keys % LANES == 0 and width % LANES == 0 and heads % kv_heads == 0
            and heads * width <= 32 * LANES)


def _exponentials(q, k, steady, allowed, scale):
    """e_h [C, block] of one head's q [C, dh] and a block of its group's keys [block, dh]."""
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())), preferred_element_type=F32) * scale
    return jnp.where(allowed, jnp.exp(s - steady), 0.0)


def _target_kernel(q_ref, k_ref, lse_ref, allowed_ref, out_ref, l_ref, *, scale: float, heads: int):
    """q_ref [Hkv, group, C, dh] and lse_ref, l_ref [Hkv, C, group] whole; k_ref [1, block, dh] the group's block of
    keys; allowed_ref (bytes) and out_ref [C, block].  The body is the group's heads unrolled."""
    sweep, j, g = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    group = q_ref.shape[1]
    allowed = allowed_ref[...] != 0
    k = k_ref[0]
    steady = lse_ref[g]

    @pl.when(sweep == 0)
    def _():
        @pl.when(j == 0)
        def _():
            l_ref[g] = jnp.zeros_like(steady)

        head_of = jax.lax.broadcasted_iota(jnp.int32, steady.shape, 1)
        sums = jnp.zeros_like(steady)
        for h in range(group):
            e = _exponentials(q_ref[g, h], k, steady[:, h:h + 1], allowed, scale)
            sums = jnp.where(head_of == h, jnp.sum(e, axis=1, keepdims=True), sums)
        l_ref[g] += sums

    @pl.when(sweep == 1)
    def _():
        @pl.when(j == 0)
        def _():
            l_ref[g] = 1.0 / l_ref[g]

        inverse = l_ref[g]
        part = _exponentials(q_ref[g, 0], k, steady[:, :1], allowed, scale) * inverse[:, :1]
        for h in range(1, group):
            part = part + _exponentials(q_ref[g, h], k, steady[:, h:h + 1], allowed, scale) * inverse[:, h:h + 1]

        @pl.when(g == 0)
        def _():
            out_ref[...] = part

        @pl.when(g != 0)
        def _():
            out_ref[...] += part

        @pl.when(g == pl.num_programs(2) - 1)
        def _():
            out_ref[...] = out_ref[...] / heads


@functools.partial(jax.jit, static_argnames=("scale", "block", "interpret"))
def target(q, k, lse, allowed, scale: float, block: int | None = None, interpret: bool = False):
    """The kernel's form: see the module's docstring.  `block` (keys a grid
    step, `_block(K)` unless given) is `tools/chip_alignment_target.py`'s,
    `interpret` the tests'."""
    heads, chunk, width = q.shape
    kv_heads, keys, _ = k.shape
    group = heads // kv_heads
    block = block or _block(keys)
    return pl.pallas_call(
        functools.partial(_target_kernel, scale=scale, heads=heads),
        grid=(2, keys // block, kv_heads),
        in_specs=[pl.BlockSpec((kv_heads, group, chunk, width), lambda s, j, g: (0, 0, 0, 0)),
                  pl.BlockSpec((1, block, width), lambda s, j, g: (g, j, 0)),
                  pl.BlockSpec((kv_heads, chunk, group), lambda s, j, g: (0, 0, 0)),
                  pl.BlockSpec((chunk, block), lambda s, j, g: (0, j))],
        out_specs=pl.BlockSpec((chunk, block), lambda s, j, g: (0, j * s)),
        out_shape=jax.ShapeDtypeStruct((chunk, keys), F32),
        scratch_shapes=[pltpu.VMEM((kv_heads, chunk, group), F32)],
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",) * 3, vmem_limit_bytes=_VMEM_LIMIT),
        name="alignment_target",
        interpret=interpret,
    )(q.reshape(kv_heads, group, chunk, width), k, jnp.swapaxes(lse.reshape(kv_heads, group, chunk), 1, 2),
      allowed.astype(jnp.int8))
