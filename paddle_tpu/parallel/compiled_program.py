"""CompiledProgram: the data-parallel façade.

Reference: python/paddle/fluid/compiler.py (CompiledProgram:48,
with_data_parallel:116) — there it builds the per-device SSA graph with
NCCL allreduce nodes; here it just records a mesh + sharding choice and the
executor jits ONE SPMD program.  BuildStrategy/ExecutionStrategy are kept
as accepted-and-mostly-ignored config carriers: their reference knobs
(fuse_all_reduce, num_threads, ...) are XLA's job now.
"""
from __future__ import annotations

from typing import Optional

from .mesh import make_mesh


class ExecutionStrategy:
    """reference: framework/details/execution_strategy.h"""

    def __init__(self):
        self.num_threads = 0
        self.num_iteration_per_drop_scope = 1
        self.use_experimental_executor = False


class BuildStrategy:
    """reference: framework/details/build_strategy.h:36 — knobs map to XLA:
    fuse_all_reduce_ops ≈ allreduce combining (automatic), reduce_strategy
    kReduce ≈ ZeRO-style sharded update (future), memory_optimize ≈ XLA
    buffer assignment."""

    class ReduceStrategy:
        AllReduce = 0
        Reduce = 1

    def __init__(self):
        self.reduce_strategy = BuildStrategy.ReduceStrategy.AllReduce
        self.fuse_all_reduce_ops = False
        self.memory_optimize = False
        self.enable_inplace = False


class CompiledProgram:
    def __init__(self, program, build_strategy: Optional[BuildStrategy] = None):
        self.program = program
        self.build_strategy = build_strategy or BuildStrategy()
        self.mesh = None
        self.loss_name = None
        self.batch_axis = "dp"
        self.local_sgd_every = 0
        self.grad_overlap_mode = None  # None | "bucketed" | "serial"
        self.grad_overlap_bucket_mb = 0.0

    def with_data_parallel(
        self,
        loss_name: Optional[str] = None,
        build_strategy: Optional[BuildStrategy] = None,
        exec_strategy: Optional[ExecutionStrategy] = None,
        share_vars_from=None,
        places=None,
    ) -> "CompiledProgram":
        """Mark for SPMD data-parallel execution over all (or `places`)
        devices.  Batch-dim-0 feeds are sharded over the `dp` axis;
        gradients allreduce automatically under GSPMD."""
        if build_strategy is not None:
            self.build_strategy = build_strategy
        self.loss_name = loss_name
        n = len(places) if places is not None else None
        import jax

        devices = jax.devices()
        if n is not None:
            devices = devices[:n]
        self.mesh = make_mesh((len(devices),), ("dp",), devices)
        return self

    def with_mesh(self, mesh, batch_axis: str = "dp") -> "CompiledProgram":
        """Explicit-mesh variant (new capability: dp x tp x ... meshes).
        Parameter placement comes from program.sharding_hints."""
        self.mesh = mesh
        self.batch_axis = batch_axis
        return self

    def with_grad_overlap(self, bucket_mb: Optional[float] = None,
                          mode: str = "bucketed") -> "CompiledProgram":
        """Backward-overlapped data-parallel gradient all-reduce (the
        PyTorch-DDP bucketing strategy, TPU-native): instead of GSPMD's
        derived collectives, the step runs as a manual per-shard region in
        which gradients are MEAN-all-reduced in size-capped buckets, issued
        in reverse-topological order as backward produces them — XLA's
        latency-hiding scheduler overlaps each bucket's collective with the
        rest of the backward pass; the only barrier left at the optimizer
        boundary is the final (smallest) bucket.

        mode="serial" keeps ONE flat all-reduce after the whole backward —
        the baseline tests/test_grad_overlap.py compares with; both modes
        are element-wise identical (bucketing never changes what each grad
        element is summed with), so final params stay bit-identical.

        DDP semantics ride along: dropout masks and BN batch stats are
        per-shard (the reference's multi-device behavior), unlike GSPMD's
        global-batch semantics.  bucket_mb defaults to FLAGS_dp_bucket_mb.
        Requires with_data_parallel/with_mesh first; composes with
        steps>1 scans, not with with_local_sgd (no per-step grads to sync
        in a LocalSGD round)."""
        if mode not in ("bucketed", "serial"):
            raise ValueError(f"with_grad_overlap: unknown mode {mode!r}")
        if self.local_sgd_every:
            raise ValueError(
                "with_grad_overlap does not compose with with_local_sgd: "
                "LocalSGD rounds deliberately run collective-free steps")
        if bucket_mb is None:
            from ..flags import flag

            bucket_mb = float(flag("FLAGS_dp_bucket_mb"))
        if bucket_mb <= 0:
            raise ValueError(f"with_grad_overlap: bucket_mb must be > 0, "
                             f"got {bucket_mb}")
        self.grad_overlap_mode = mode
        self.grad_overlap_bucket_mb = float(bucket_mb)
        return self

    def with_local_sgd(self, sync_every: int = 4) -> "CompiledProgram":
        """LocalSGD mode (reference transpiler/collective.py:249 +
        DistributedStrategy.use_local_sgd): each dp worker runs `sync_every`
        communication-free local steps on its own diverging state, then one
        pmean re-syncs — one executor dispatch per round with feeds stacked
        [sync_every, ...].  Requires a single-controller mesh
        (with_data_parallel/with_mesh first).  Fetches come back as the
        dp-mean of per-worker values: exact for scalar losses/metrics; for
        per-sample outputs run a separate (non-LocalSGD) eval dispatch."""
        if sync_every < 1:
            raise ValueError(f"with_local_sgd: sync_every must be >= 1, got {sync_every}")
        if self.grad_overlap_mode:
            raise ValueError(
                "with_local_sgd does not compose with with_grad_overlap: "
                "LocalSGD rounds deliberately run collective-free steps")
        self.local_sgd_every = int(sync_every)
        return self



class ParallelExecutor:
    """reference parallel_executor.py ParallelExecutor: compat shim over
    CompiledProgram.with_data_parallel + Executor (the SSA-graph executor
    it wrapped is subsumed by XLA/GSPMD)."""

    def __init__(self, use_cuda=True, loss_name=None, main_program=None,
                 share_vars_from=None, exec_strategy=None, build_strategy=None,
                 num_trainers=1, trainer_id=0, scope=None):
        from ..core.executor import Executor, TPUPlace, CPUPlace
        from ..core.program import default_main_program
        from ..core.scope import global_scope

        self._program = main_program or default_main_program()
        self._compiled = CompiledProgram(
            self._program, build_strategy=build_strategy
        ).with_data_parallel(loss_name=loss_name)
        self._exe = Executor(TPUPlace(0) if use_cuda else CPUPlace())
        self._scope = scope or global_scope()

    def run(self, fetch_list, feed=None, feed_dict=None, return_numpy=True):
        return self._exe.run(self._compiled, feed=feed or feed_dict,
                             fetch_list=fetch_list, scope=self._scope,
                             return_numpy=return_numpy)

    def drop_local_exe_scopes(self):
        """reference: drop per-device scopes; no residue (single scope)."""
        return None
