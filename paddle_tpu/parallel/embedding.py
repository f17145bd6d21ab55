"""Embedding parallelism: row-sharded tables (distributed lookup_table).

Reference: the pserver-sharded lookup table + remote prefetch
(SURVEY.md §2c "Distributed lookup table": ids split over pservers,
`parameter_prefetch.cc`).  TPU-first: the table is row-sharded over a mesh
axis in HBM; lookup = local gather of in-range rows + `psum` combine over
the axis (XLA emits the same all-to-all-ish traffic NCCL/pserver RPC
carried).  Gradients scatter-add back into the local shard via autodiff.

Two ways to use it:
  * declarative: `shard_parameters(program, {"emb_table": ("ep", None)})` —
    GSPMD partitions the plain lookup_table gather automatically;
  * explicit: `sharded_lookup` below inside shard_map when you need the
    collective pattern pinned (e.g. out-of-HBM staging, later rounds).

`TieredEmbedding` (ISSUE 19) closes ROADMAP item 3's loop: the HOT head
of the vocabulary (the rows every batch touches) lives in memory/HBM and
trains locally, while the COLD tail — the part that does not fit — lives
on the supervised parameter server behind `HostTableEmbedding`.  The
tier inherits the host tier's fault story: a down pserver degrades the
cold tail (zero rows, dropped pushes, `sparse.host_lag_steps` bounded by
FLAGS_max_host_lag_steps) while hot-row training continues untouched.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P


def _lookup_local(ids, table_local, axis_name: str):
    n = jax.lax.psum(1, axis_name)
    my = jax.lax.axis_index(axis_name)
    rows = table_local.shape[0]
    lo = my * rows
    local_ids = ids - lo
    in_range = jnp.logical_and(local_ids >= 0, local_ids < rows)
    safe = jnp.clip(local_ids, 0, rows - 1)
    vals = jnp.take(table_local, safe, axis=0)
    vals = jnp.where(in_range[..., None], vals, 0)
    return jax.lax.psum(vals, axis_name)


def sharded_lookup(ids, table, mesh: Mesh, axis_name: str = "ep"):
    """ids: int (...,) replicated; table: (V, D) row-sharded over axis_name.
    Returns (..., D) replicated embeddings."""
    fn = functools.partial(_lookup_local, axis_name=axis_name)
    shard = jax.shard_map(
        fn,
        mesh=mesh,
        in_specs=(P(), P(axis_name, None)),
        out_specs=P(),
        check_vma=False,
    )
    return shard(ids, table)


class TieredEmbedding:
    """HBM-hot head + host-tiered cold tail for one logical (V, D) table.

    Rows [0, hot_rows) are the hot shard: held locally (feedable to the
    device program / `sharded_lookup`), updated in place with SGD.  Rows
    [hot_rows, vocab_size) are the cold tail on the parameter server via
    `HostTableEmbedding` — pulled per batch, row-gradients pushed back
    with the client's exactly-once sequenced pushes.

    While the pserver tier is down (supervisor mid-restart or out of
    budget) and `degraded_ok=True`, steps keep running HOT-SHARD-ONLY:
    cold lookups return zeros, cold pushes are dropped (counted), and
    `host_lag_steps` / the `sparse.host_lag_steps` gauge track the
    outage — terminal past FLAGS_max_host_lag_steps.  That is the
    bounded degraded mode of the ISSUE-19 contract: a dead host tier
    costs cold-tail freshness, never the run."""

    def __init__(self, client, name: str, vocab_size: int, dim: int,
                 hot_rows: int, lr: float = 0.1, degraded_ok: bool = True,
                 seed: int = 0, scale: float = 0.01, create: bool = True):
        from ..param_server import HostTableEmbedding

        if not 0 < hot_rows <= vocab_size:
            raise ValueError(f"hot_rows={hot_rows} must be in "
                             f"(0, vocab_size={vocab_size}]")
        self.name = name
        self.vocab_size, self.dim, self.hot_rows = vocab_size, dim, hot_rows
        self.lr = lr
        rng = np.random.RandomState(seed)
        self.hot = (rng.randn(hot_rows, dim) * scale).astype(np.float32)
        self.host = HostTableEmbedding(client, name, dim,
                                       degraded_ok=degraded_ok)
        if create and vocab_size > hot_rows:
            client.create(name, (rng.randn(vocab_size - hot_rows, dim)
                                 * scale).astype(np.float32))

    @property
    def host_lag_steps(self) -> int:
        return self.host.host_lag_steps

    def lookup(self, ids: np.ndarray) -> np.ndarray:
        """ids (...,) int -> (..., D) float32 rows across both tiers."""
        ids = np.asarray(ids, np.int64)
        flat = ids.reshape(-1)
        out = np.zeros((flat.size, self.dim), np.float32)
        hot_mask = flat < self.hot_rows
        if hot_mask.any():
            out[hot_mask] = self.hot[flat[hot_mask]]
        cold = flat[~hot_mask] - self.hot_rows
        if cold.size:
            uniq, local, rows = self.host.prepare_batch(cold)
            out[~hot_mask] = rows[local]
        return out.reshape(ids.shape + (self.dim,))

    def apply_grad(self, ids: np.ndarray, grad_rows: np.ndarray):
        """SGD on the hot shard in place; sequenced push for the cold
        tail (dropped, counted, while the tier is degraded)."""
        flat = np.asarray(ids, np.int64).reshape(-1)
        grads = np.asarray(grad_rows, np.float32).reshape(-1, self.dim)
        hot_mask = flat < self.hot_rows
        if hot_mask.any():
            np.add.at(self.hot, flat[hot_mask], -self.lr * grads[hot_mask])
        cold = flat[~hot_mask] - self.hot_rows
        if cold.size:
            uniq, inv = np.unique(cold, return_inverse=True)
            merged = np.zeros((uniq.size, self.dim), np.float32)
            np.add.at(merged, inv, grads[~hot_mask])
            self.host.push_grad(uniq, merged)

    def export_selected_rows(self):
        """Materialize the FULL logical table as one SelectedRows (hot
        head locally + cold tail fetched from the pserver) — the payload
        an online run snapshots and publishes into serving.  Raises the
        client's classified ParamServerError when the tier is down past
        its retry budget: a publish must never silently ship a
        zeros-for-cold-tail snapshot."""
        from ..core.selected_rows import SelectedRows

        parts = [self.hot]
        if self.vocab_size > self.hot_rows:
            parts.append(np.asarray(self.host.client.fetch_table(self.name),
                                    np.float32))
        values = np.concatenate(parts, axis=0)
        return SelectedRows(np.arange(self.vocab_size, dtype=np.int64),
                            values, height=self.vocab_size)
