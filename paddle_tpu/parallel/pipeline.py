"""Pipeline parallelism: microbatch pipelining over a `pp` mesh axis.

Reference: PipelineTrainer/SectionWorker (SURVEY.md §2a #17) — program cut
into sections with scope queues between stages and NCCL param sync.

TPU-first redesign: all stages are ONE SPMD program under shard_map.  Each
device holds its stage's parameters (stacked pytree, leading axis sharded
over `pp`); activations hop to the next stage with `collective_permute`
each tick while microbatches stream in — a GPipe schedule with the classic
(S-1)-tick bubble.  Backward comes from jax autodiff through the loop
(vjp of ppermute is the reverse permute), so no hand-written 1F1B engine
is needed for correctness; an interleaved schedule is a later optimization.
"""
from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def _pipeline_local(params, xs, stage_id, fn: Callable, axis_name: str, S: int):
    """Per-device body: params = this stage's params (leading axis 1),
    xs = all microbatches (M, mb, ...) — only stage 0 reads them.

    The stage index arrives as a P(axis_name)-sharded iota INPUT rather
    than `jax.lax.axis_index`: under a partially-manual shard_map (extra
    mesh axes left to GSPMD, e.g. pp inside a dp×pp×mp step) axis_index
    lowers to a PartitionId instruction SPMD partitioning rejects."""
    idx = stage_id[0]
    params = jax.tree.map(lambda p: p[0], params)  # drop stage axis
    M = xs.shape[0]
    T = M + S - 1
    perm = [(j, (j + 1) % S) for j in range(S - 1)]  # no wraparound send

    mb_shape = xs.shape[1:]
    ys = jnp.zeros((M,) + mb_shape, dtype=xs.dtype)

    def body(t, carry):
        carry_in, ys = carry
        # stage 0 ingests microbatch t (clamped); others use received value
        x0 = jax.lax.dynamic_index_in_dim(xs, jnp.minimum(t, M - 1), 0, keepdims=False)
        x = jnp.where(idx == 0, x0, carry_in)
        y = fn(params, x)
        # last stage records microbatch (t - S + 1) once it's valid
        # (a where-select, not lax.cond: replication checking cannot unify
        # cond branches whose rep types differ, and the update is cheap)
        out_slot = t - (S - 1)
        valid = jnp.logical_and(idx == S - 1, out_slot >= 0)
        upd = jax.lax.dynamic_update_index_in_dim(
            ys, y, jnp.maximum(out_slot, 0), 0)
        ys = jnp.where(valid, upd, ys)
        nxt = jax.lax.ppermute(y, axis_name, perm)
        return nxt, ys

    _, ys = jax.lax.fori_loop(0, T, body, (jnp.zeros(mb_shape, xs.dtype), ys))
    # only the last stage's ys is meaningful; a masked psum broadcasts it
    # to the ring AND is provably replicated over axis_name, which lets
    # replication checking verify out_specs=P() where
    # all_gather-then-index defeated the inference
    return jax.lax.psum(
        jnp.where(idx == S - 1, ys, jnp.zeros_like(ys)), axis_name)


def gpipe(
    fn: Callable,
    stacked_params,
    microbatches,
    mesh: Mesh,
    axis_name: str = "pp",
):
    """Run `y = fn(stage_params, x)` through S pipeline stages.

    stacked_params: pytree whose leaves have leading dim S (one slice per
    stage), sharded over `axis_name`.
    microbatches: (M, mb, ...) array of stage-0 inputs; M >= S for good
    bubble amortization.
    Returns (M, mb, ...) outputs of the last stage, replicated.
    """
    S = mesh.shape[axis_name]
    param_specs = jax.tree.map(lambda _: P(axis_name), stacked_params)
    shard = jax.shard_map(
        functools.partial(_pipeline_local, fn=fn, axis_name=axis_name, S=S),
        mesh=mesh,
        in_specs=(param_specs, P(), P(axis_name)),
        out_specs=P(),
        check_vma=False,
    )
    return shard(stacked_params, microbatches, jnp.arange(S, dtype=jnp.int32))
