"""Multi-process / multi-host bootstrap.

Reference: the NCCL2 transpile mode — `gen_nccl_id_op.cc:31` RPC-broadcasts
an ncclUniqueId keyed by trainer_id/endpoints set via
PADDLE_TRAINER_ID / PADDLE_TRAINER_ENDPOINTS / PADDLE_CURRENT_ENDPOINT
(`distribute_transpiler.py:261`).

TPU-first: the bootstrap maps to JAX's coordination service
(`jax.distributed.initialize`) — endpoint 0 is the coordinator, the rest
dial in — after which `jax.devices()` is the GLOBAL device list and every
in-program collective (GSPMD or shard_map) spans processes over ICI/DCN
exactly where the reference spanned nodes with NCCL rings."""
from __future__ import annotations

import os
from typing import Optional, Sequence


def trainer_env():
    """Read the reference's trainer env-var contract."""
    tid = os.environ.get("PADDLE_TRAINER_ID")
    eps = os.environ.get("PADDLE_TRAINER_ENDPOINTS")
    cur = os.environ.get("PADDLE_CURRENT_ENDPOINT")
    return (
        int(tid) if tid is not None else None,
        eps.split(",") if eps else None,
        cur,
    )


_initialized = False


def is_initialized() -> bool:
    return _initialized


def init_distributed(trainer_id: Optional[int] = None,
                     trainer_endpoints: Optional[Sequence[str]] = None,
                     current_endpoint: Optional[str] = None):
    """Bring up the cross-process runtime.  Arguments default to the
    PADDLE_* env vars (same contract the transpiler's NCCL2 mode used).
    Endpoint 0's host:port doubles as the coordinator address (the
    gen_nccl_id role)."""
    global _initialized
    import jax

    if _initialized:
        return  # idempotent: the runtime is already bootstrapped

    env_tid, env_eps, env_cur = trainer_env()
    trainer_id = trainer_id if trainer_id is not None else env_tid
    trainer_endpoints = list(trainer_endpoints or env_eps or [])
    current_endpoint = current_endpoint or env_cur
    if trainer_id is None or not trainer_endpoints:
        raise ValueError(
            "init_distributed: need trainer_id + trainer_endpoints (args or "
            "PADDLE_TRAINER_ID / PADDLE_TRAINER_ENDPOINTS)")
    if current_endpoint and current_endpoint != trainer_endpoints[trainer_id]:
        raise ValueError(
            f"init_distributed: current_endpoint {current_endpoint!r} does not "
            f"match trainer_endpoints[{trainer_id}] = "
            f"{trainer_endpoints[trainer_id]!r}")
    if len(trainer_endpoints) == 1:
        _initialized = True
        return  # single process: nothing to bootstrap
    if os.environ.get("JAX_PLATFORMS", "").startswith("cpu"):
        # cross-process collectives on the CPU backend need gloo
        jax.config.update("jax_cpu_collectives_implementation", "gloo")
    # The persistent compilation cache (FLAGS_compile_cache_dir) corrupts
    # the heap when a cross-process executable round-trips through it on
    # this jaxlib (observed deterministically: malloc corruption / SIGSEGV
    # in gang workers at the first cached multi-process compile).  The
    # cold-start win is a single-process feature; force it off before the
    # runtime goes multi-process.
    if jax.config.jax_compilation_cache_dir:
        import logging

        logging.getLogger("paddle_tpu.distributed").warning(
            "init_distributed: disabling the persistent compilation cache "
            "(%s) for this multi-process run — cached cross-process "
            "executables are unsafe on this backend",
            jax.config.jax_compilation_cache_dir)
        jax.config.update("jax_compilation_cache_dir", None)

    # The bootstrap is the first gang-wide rendezvous, so it is also the
    # first place a dead/never-started worker wedges everyone else.  Run
    # it under a bounded deadline (FLAGS_dist_bootstrap_timeout_s) on a
    # worker thread: expiry raises a classified CollectiveTimeoutError in
    # this frame instead of blocking forever (the jax-level
    # initialization_timeout is kept slightly wider as a backstop for the
    # abandoned thread).
    from ..dist_resilience import CollectiveWatchdog, active_heartbeat
    from ..flags import flag as _flag

    boot_timeout = float(_flag("FLAGS_dist_bootstrap_timeout_s"))

    def _boot():
        jax.distributed.initialize(
            coordinator_address=trainer_endpoints[0],
            num_processes=len(trainer_endpoints),
            process_id=trainer_id,
            initialization_timeout=max(int(boot_timeout) + 10, 15),
        )

    CollectiveWatchdog(heartbeat=active_heartbeat(),
                       timeout_s=boot_timeout, rank=trainer_id).run(
        _boot, what="jax.distributed.initialize")
    _initialized = True


def global_mesh(axes=None):
    """Mesh over the GLOBAL device list (all processes).  axes defaults to
    one data-parallel axis spanning everything."""
    import jax
    from .mesh import make_mesh

    devs = jax.devices()
    if axes is None:
        return make_mesh((len(devs),), ("dp",), devices=devs)
    shape = tuple(n for n, _ in axes)
    names = tuple(a for _, a in axes)
    return make_mesh(shape, names, devices=devs)


# --------------------------------------------------------------------------
# backward-overlapped gradient all-reduce (DDP-style bucketing)
# --------------------------------------------------------------------------

def plan_buckets(named_sizes, cap_bytes):
    """Group (name, nbytes) pairs into size-capped buckets, preserving
    order: a bucket closes when adding the next grad would exceed
    `cap_bytes` (a single over-cap grad gets its own bucket).  Callers pass
    grads in REVERSE-topological order — the order backward produces them —
    so early buckets complete while later grads are still being computed
    (the PyTorch-DDP bucketing strategy)."""
    buckets, cur, cur_bytes = [], [], 0
    for name, nbytes in named_sizes:
        if cur and cur_bytes + nbytes > cap_bytes:
            buckets.append(cur)
            cur, cur_bytes = [], 0
        cur.append(name)
        cur_bytes += nbytes
    if cur:
        buckets.append(cur)
    return buckets


def _bucket_psum(vals, axis_name, scale=None):
    """All-reduce one bucket as a single flat collective: grads are
    flattened and concatenated (f32 comm dtype keeps the sum exact across
    mixed-precision params), one psum covers the bucket, then the segments
    are split back out.  `scale` (the 1/n mean factor) is applied to the
    f32 sum BEFORE the downcast to each grad's native dtype — dividing
    after the cast would round twice at bf16 precision."""
    import jax
    import jax.numpy as jnp

    flat = [jnp.ravel(v).astype(jnp.float32) for v in vals]
    sizes = [f.shape[0] for f in flat]
    summed = jax.lax.psum(jnp.concatenate(flat), axis_name)
    if scale is not None:
        summed = summed * scale
    out, off = [], 0
    for v, n in zip(vals, sizes):
        seg = jax.lax.dynamic_slice_in_dim(summed, off, n)
        out.append(seg.reshape(v.shape).astype(v.dtype))
        off += n
    return out


def make_grad_sync(axis_name: str, bucket_bytes: int, mode: str = "bucketed"):
    """Build the grad-sync callable installed on the LoweringContext
    (core/lowering.py) when `CompiledProgram.with_grad_overlap` is active.

    Receives [(grad_name, value)] in forward-parameter order, returns
    {grad_name: synced_value}.  Dense grads are MEAN-reduced over the dp
    axis (sync-SGD; each worker computed grads of its LOCAL-batch mean
    loss).  SelectedRows grads (is_sparse embeddings) are synced by
    all-gathering rows+values — the concatenated slab is the global sparse
    gradient and the optimizer's MergeAdd sums duplicates, so no dense
    V x D cotangent ever crosses the interconnect.

    mode="bucketed": dense grads are processed in REVERSE order (the order
    backward produces them) and grouped into `bucket_bytes`-capped buckets,
    one psum per bucket — XLA's latency-hiding scheduler overlaps each
    bucket's collective with the still-running earlier parts of the
    backward pass.  mode="serial": the A/B baseline — ONE flat psum over
    every dense grad, issuable only once the entire backward has finished
    (the fetch-barrier-at-optimizer-boundary shape DDP replaced).  Both
    modes are element-wise identical: bucketing never changes what each
    element is summed with, so the A/B isolates scheduling."""
    import jax
    import jax.numpy as jnp

    from ..core.selected_rows import SelectedRows

    if mode not in ("bucketed", "serial"):
        raise ValueError(f"make_grad_sync: unknown mode {mode!r}")

    def sync(named_grads):
        n = jax.lax.psum(1, axis_name)
        inv_n = 1.0 / n
        out = {}
        dense = []
        for name, g in named_grads:
            if isinstance(g, SelectedRows):
                rows = jax.lax.all_gather(g.rows, axis_name).reshape(-1)
                vals = jax.lax.all_gather(g.values, axis_name)
                vals = (vals.astype(jnp.float32) * inv_n).astype(g.values.dtype)
                vals = vals.reshape((-1,) + g.values.shape[1:])
                out[name] = SelectedRows(rows, vals, g.height)
            else:
                dense.append((name, g))
        if not dense:
            return out
        dense = dense[::-1]  # reverse-topological: backward-production order
        if mode == "serial":
            buckets = [[nm for nm, _ in dense]]
        else:
            buckets = plan_buckets(
                [(nm, g.size * 4) for nm, g in dense], bucket_bytes)
        by_name = dict(dense)
        for bucket in buckets:
            vals = _bucket_psum([by_name[nm] for nm in bucket], axis_name,
                                scale=inv_n)
            for nm, v in zip(bucket, vals):
                out[nm] = v
        return out

    sync.axis_name = axis_name
    sync.mode = mode
    return sync


def trainer_id() -> int:
    import jax

    return jax.process_index()


def num_trainers() -> int:
    import jax

    return jax.process_count()
