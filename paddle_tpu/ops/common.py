"""Shared helpers for op lowerings."""
from __future__ import annotations

import functools

import jax.numpy as jnp
import numpy as np

from ..core.dtypes import as_np_dtype
from ..core.lowering import open_profile


def first(ins, slot, default=None):
    vals = ins.get(slot)
    if not vals:
        return default
    return vals[0]


def batch_shards(mesh, batch_axis, batch) -> int:
    """How `mesh` (a lowering context's) splits an op whose operands lead with
    a batch axis of `batch` rows: 1 on one device; n where `batch_axis` (the
    executor's, `ctx.batch_axis`) is its ONLY axis of more than one device and cuts
    the rows n ways evenly: the op's operands are then split over the batch
    alone, and a chip's share of the op is the whole op on its own rows
    (`over_batch_shards`); 0 under any other mesh, where a lowering keeps the
    forms GSPMD can partition by itself."""
    if mesh is None or mesh.size == 1:
        return 1
    n = dict(mesh.shape).get(batch_axis, 1)
    return n if n == mesh.size and batch is not None and batch > 0 and batch % n == 0 else 0


def over_batch_shards(ctx, fn, batched, whole=()):
    """`fn(*batched, *whole)` run by every chip on its own rows: a `shard_map`
    over the mesh's batch axis, `batched` (and every output) split along the
    leading axis, `whole` handed to every chip entire.  What a `pallas_call`
    needs under a mesh (a custom call that GSPMD cannot partition and would
    run on all the rows on every chip), and what keeps a `lax.scan` over the
    sequence from being split any other way.  `fn` sees per-chip shapes; a
    reduction over the batch inside it names the axis `ctx.batch_axis`."""
    import jax
    from jax.sharding import PartitionSpec as P

    from ..monitor import MONITOR

    MONITOR.counter("lowering.kernels_under_shard_map").inc()
    rows = P(ctx.batch_axis)
    return jax.shard_map(lambda b, w: fn(*b, *w), mesh=ctx.mesh, in_specs=(rows, P()), out_specs=rows,
                         check_vma=False)(tuple(batched), tuple(whole))


def counted_rules(op_type, *rules):
    """An op's `jax.custom_vjp` rules (`f.defvjp(*counted_rules("kda", fwd,
    bwd))`), each counted in the lowering's profile under `op_type` and the
    phase of the trace that is open when JAX calls it
    (`core.lowering.TraceProfile`): a backward rule during `lowering.transpose`,
    a forward rule wherever JAX differentiates the op, inside its own lowering
    or, for an op of a recomputed segment, after it.  With no trace being
    profiled (the monitor off) a rule runs as it is."""
    def counted(rule):
        @functools.wraps(rule)
        def timed(*args):
            profile = open_profile()
            if profile is None:
                return rule(*args)
            with profile.timed(op_type):
                return rule(*args)
        return timed

    return tuple(counted(rule) for rule in rules)


def operand_of(shapes, name):
    """The variable `name` as a lowering will see it, shape and dtype alone
    (`shapes`: a `resource_plan.ShapeEnv`): what a `registry.set_kept` rule
    hands the op's own path rule before anything is traced."""
    import jax

    return jax.ShapeDtypeStruct(shapes.shape(name), canon_dtype(shapes.dtype(name)))


def residuals_name(op):
    """What an op whose kernel has residuals that only its forward makes calls
    them, for its `registry.set_kept` rule and for its lowering alike."""
    return op.output("Out")[0] + "@residuals"


def kept_residuals(ctx, op):
    """`residuals_name(op)` where the recomputed segment being lowered keeps
    them (`ctx.keep`), else None: the name the op's kernel gives them.  Where
    nothing is kept the op is not asked for its names (the tests' stand-ins
    have none)."""
    return residuals_name(op) if ctx.keep and residuals_name(op) in ctx.keep else None


def rotary_angles(pos, half: int, theta: float, by_position: bool, inv_freq=None, scale: float = 1.0):
    """(cos, sin) float32 of `half` angles a position, pos . theta^(-i / half)
    (or pos . `inv_freq`[i], a table of `half` frequencies), each times
    `scale`, over (B, L, 1, half) where the heads follow the positions
    (`by_position`) or (B, 1, L, half): at position 16383 a bf16 angle is off
    by whole turns."""
    if inv_freq is None:
        inv_freq = theta ** (-np.arange(half, dtype=np.float32) / half)
    else:
        inv_freq = np.asarray(inv_freq, np.float32)
    pos = pos.astype(jnp.float32)
    angle = (pos[:, :, None, None] if by_position else pos[:, None, :, None]) * inv_freq
    if scale != 1.0:
        return jnp.cos(angle) * np.float32(scale), jnp.sin(angle) * np.float32(scale)
    return jnp.cos(angle), jnp.sin(angle)


def bcast_y_to_x(x, y, axis: int):
    """Fluid elementwise broadcasting (reference: operators/elementwise/
    elementwise_op_function.h): Y's dims align to X starting at `axis`
    (axis=-1 => align trailing, i.e. plain numpy broadcasting)."""
    if axis == -1 or x.ndim == y.ndim:
        return y
    pad_right = x.ndim - axis - y.ndim
    if pad_right < 0:
        return y
    return jnp.reshape(y, (1,) * axis + y.shape + (1,) * pad_right)


def np_dtype(attr_dtype):
    return as_np_dtype(attr_dtype)


def canon_dtype(dtype):
    """x32-canonicalized dtype for in-program casts: int64/float64 requests
    become int32/float32 unless jax_enable_x64 is set (avoids the per-trace
    jnp truncation warning while keeping declared var dtypes intact)."""
    import jax

    if isinstance(dtype, str):
        d = np.dtype(as_np_dtype(dtype))
    else:
        d = np.dtype(dtype)  # accept any numpy dtype (incl. uint32/uint64)
    if not jax.config.jax_enable_x64:
        if d == np.int64:
            return np.int32
        if d == np.uint64:
            return np.uint32
        if d == np.float64:
            return np.float32
    return d


def match_dtype(x, y):
    """Harmonize a parameter/second operand to the activation dtype for
    mixed precision: when both are floats of different width, y follows x
    (so bf16 activations keep convs/matmuls on the MXU in bf16 while master
    weights stay fp32)."""
    if (
        x.dtype != y.dtype
        and jnp.issubdtype(x.dtype, jnp.floating)
        and jnp.issubdtype(y.dtype, jnp.floating)
    ):
        return y.astype(x.dtype)
    return y


def normalize_axes(dim, ndim):
    if dim is None:
        return tuple(range(ndim))
    if isinstance(dim, (int, np.integer)):
        dim = [dim]
    return tuple(sorted(d % ndim for d in dim))


def flatten_lookup_ids(ids):
    """Fluid lookup_table ids carry a trailing dim of 1 (lookup_table_op.cc);
    strip it when present.  Shared by the lookup lowering and the sparse-grad
    assembler (core/lowering.py) so SelectedRows rows/values stay aligned."""
    return ids.reshape(ids.shape[:-1]) if ids.shape and ids.shape[-1] == 1 else ids
