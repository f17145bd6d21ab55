"""LocalSGD: k unsynchronized local steps per worker, then parameter
averaging (reference: transpiler/collective.py:249 LocalSGD — snapshot
vars + allreduce of param deltas every k steps).

TPU-first redesign: workers are mesh devices.  Parameters carry a leading
per-worker axis sharded over `dp`, so each device trains its own replica
inside a shard_map; an inner lax.scan runs the k communication-free local
steps, then one pmean averages the replicas — the collective executes
exactly once per round instead of once per step, which is the entire point
of the method (trades ICI/DCN traffic for staleness).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def _stack_params(params, n):
    return jax.tree_util.tree_map(
        lambda p: jnp.broadcast_to(p[None], (n,) + p.shape), params)


def local_sgd_train(step_fn, params, batches, mesh: Mesh, axis_name: str = "dp",
                    sync_every: int = 4):
    """Train with LocalSGD over the `axis_name` mesh axis.

    step_fn(params, batch) -> (new_params, loss) — one worker-local step.
    params: replicated pytree.
    batches: pytree of [n_workers, rounds, sync_every, ...] arrays (each
      worker sees its own slice; rounds*sync_every total steps per worker).
    Returns (averaged params replicated, losses [n_workers, rounds, k]).
    """
    n = mesh.shape[axis_name]
    stacked = _stack_params(params, n)

    def worker(pstack, bshard):
        p = jax.tree_util.tree_map(lambda a: jnp.squeeze(a, 0), pstack)
        bs = jax.tree_util.tree_map(lambda a: jnp.squeeze(a, 0), bshard)

        def round_body(p, round_batches):
            def local_step(p, bt):
                p2, loss = step_fn(p, bt)
                return p2, loss

            p, losses = jax.lax.scan(local_step, p, round_batches)
            # the ONE collective per round: average replicas
            p = jax.tree_util.tree_map(
                functools.partial(jax.lax.pmean, axis_name=axis_name), p)
            return p, losses

        p, losses = jax.lax.scan(round_body, p, bs)
        pstack_out = jax.tree_util.tree_map(lambda a: a[None], p)
        return pstack_out, losses[None]

    shard = jax.shard_map(
        worker, mesh=mesh,
        in_specs=(P(axis_name), P(axis_name)),
        out_specs=(P(axis_name), P(axis_name)),
        check_vma=False,
    )
    pstack, losses = shard(stacked, batches)
    # replicas are identical after the final pmean; take worker 0's copy
    final = jax.tree_util.tree_map(lambda a: a[0], pstack)
    return final, losses
