"""Deep Gradient Compression (reference: DGCMomentumOptimizer
optimizer.py:786 + dgc_op.cc + sparse_all_reduce_op_handle.cc — top-k
sparsified, momentum-corrected gradient exchange with error feedback).

TPU-first: ICI bandwidth makes DGC rarely necessary (SURVEY §2c ranks it
low), but the capability maps cleanly: each worker keeps momentum (u) and
error-feedback (v) buffers, selects its local top-k of |v|, and the sparse
slabs exchange via all_gather of fixed-size (values, indices) pairs — the
static-shape analogue of the reference's sparse allgather.  Everything
lives in one shard_map, so it composes with the executor's mesh path.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def dgc_allreduce(grads, u, v, mesh: Mesh, axis_name: str = "dp",
                  sparsity: float = 0.99, momentum: float = 0.9):
    """One DGC round for a flat gradient vector.

    grads: [n_workers, D] per-worker local gradients (sharded over dp).
    u, v:  [n_workers, D] momentum / error-feedback state (sharded).
    Returns (dense_update [n_workers, D] — every worker's identical summed
    sparse update, replicated row-wise — u_new, v_new).
    """
    D = grads.shape[-1]
    k = max(1, int(D * (1.0 - sparsity)))

    def worker(g, u_, v_):
        g = g[0]
        u_ = u_[0]
        v_ = v_[0]
        # momentum correction + error feedback (dgc_op.cc)
        u_new = momentum * u_ + g
        v_acc = v_ + u_new
        _, idx = jax.lax.top_k(jnp.abs(v_acc), k)
        sel_vals = v_acc[idx]
        # reference dgc_op.cc clears BOTH buffers at the selected indices
        # (momentum factor masking): a sent coordinate restarts its momentum
        mask = jnp.zeros((D,), bool).at[idx].set(True)
        v_res = jnp.where(mask, 0.0, v_acc)
        u_new = jnp.where(mask, 0.0, u_new)
        # exchange fixed-size sparse slabs
        all_vals = jax.lax.all_gather(sel_vals, axis_name)   # [W, k]
        all_idx = jax.lax.all_gather(idx, axis_name)         # [W, k]
        dense = jnp.zeros((D,), v_acc.dtype)
        dense = dense.at[all_idx.reshape(-1)].add(all_vals.reshape(-1))
        return dense[None], u_new[None], v_res[None]

    shard = jax.shard_map(
        worker, mesh=mesh,
        in_specs=(P(axis_name), P(axis_name), P(axis_name)),
        out_specs=(P(axis_name), P(axis_name), P(axis_name)),
        check_vma=False,
    )
    return shard(grads, u, v)
