"""Fleet: the user-facing cluster training API.

Reference: incubate/fleet/base/fleet_base.py:37 (Fleet) +
role_maker.py (PaddleCloudRoleMaker reads PADDLE_* env) +
transpiler/distribute_transpiler.py collective/NCCL2 modes.

TPU-first: one implementation path — the coordination-service bootstrap
(parallel/distributed.py) plus a global dp mesh; `distributed_optimizer`
wraps any Optimizer so `minimize()` compiles the program for the global
mesh.  The pserver mode has no TPU equivalent for dense params (allreduce
won, SURVEY §2c); sparse tables ride the SelectedRows/ep path instead.
"""
from __future__ import annotations

import os
from typing import Optional

import numpy as np

from .monitor import MONITOR as _MON


class UserDefinedRoleMaker:
    """reference role_maker.UserDefinedRoleMaker (collective flavor)."""

    def __init__(self, current_id: int = 0, worker_num: int = 1,
                 worker_endpoints=None):
        self._id = current_id
        self._num = worker_num
        self._endpoints = list(worker_endpoints or [])

    def worker_index(self) -> int:
        return self._id

    def worker_num(self) -> int:
        return self._num

    def get_trainer_endpoints(self):
        return list(self._endpoints)

    def is_first_worker(self) -> bool:
        return self._id == 0


class PaddleCloudRoleMaker(UserDefinedRoleMaker):
    """reference role_maker.PaddleCloudRoleMaker: everything from PADDLE_*
    env vars (one parser: parallel.distributed.trainer_env)."""

    def __init__(self, is_collective: bool = True):
        from .parallel.distributed import trainer_env

        tid, endpoints, _ = trainer_env()
        endpoints = endpoints or []
        if len(endpoints) > 1 and tid is None:
            # defaulting to rank 0 here would give every process the same id
            # and corrupt the bootstrap — fail fast like the reference
            raise ValueError(
                "PaddleCloudRoleMaker: PADDLE_TRAINER_ENDPOINTS lists "
                f"{len(endpoints)} workers but PADDLE_TRAINER_ID is unset")
        super().__init__(
            current_id=tid if tid is not None else 0,
            worker_num=len(endpoints) or int(os.environ.get("PADDLE_TRAINERS_NUM", "1")),
            worker_endpoints=endpoints,
        )


class DistributedStrategy:
    """reference DistributedStrategy carrier: the knobs that still mean
    something map onto BuildStrategy/mesh choices."""

    def __init__(self):
        self.use_local_sgd = False
        self.local_sgd_steps = 4
        self.memory_optimize = False  # -> remat
        self.nccl_comm_num = 1        # accepted no-op: ICI is one fabric


class Fleet:
    def __init__(self):
        self._role = None
        self._strategy = DistributedStrategy()
        self._mesh = None

    # -- lifecycle ---------------------------------------------------------
    def init(self, role_maker=None):
        """Bootstrap the cross-process runtime when endpoints say so.

        Multi-worker gangs also get the distributed health layer (ISSUE
        4): the heartbeat starts BEFORE the coordination-service
        bootstrap — a peer that dies while everyone else is still dialing
        in must already be detectable — and the collective watchdog it
        arms guards every blocking executor wait from then on
        (core/executor.py routes them through
        dist_resilience.guard_blocking)."""
        self._role = role_maker or PaddleCloudRoleMaker()
        eps = self._role.get_trainer_endpoints()
        # each trainer gets its own monitor lane so merged Chrome traces
        # (monitor.merge_chrome_traces) show one row per worker
        _MON.set_lane(self._role.worker_index(),
                      f"trainer{self._role.worker_index()}")
        # telemetry plane (ISSUE 8): when the gang supervisor assigned a
        # rank-shared telemetry dir (PADDLE_TELEMETRY_DIR), stream this
        # worker's rank-stamped metrics there and arm the flight recorder;
        # a no-op outside a telemetry-armed gang
        from .monitor import init_worker_telemetry as _init_tel

        _init_tel(rank=self._role.worker_index())
        _MON.gauge("fleet.worker_num").set(self._role.worker_num())
        if len(eps) > 1:
            from . import dist_resilience as _dres
            from .parallel import distributed as dist

            self._watchdog = _dres.init_health(
                rank=self._role.worker_index(),
                world=self._role.worker_num(), endpoints=eps)
            with _MON.span("fleet.init", workers=len(eps)):
                dist.init_distributed(
                    trainer_id=self._role.worker_index(),
                    trainer_endpoints=eps,
                )
                # Establish the cross-process collective context NOW, while
                # every worker sits at the same point (right after the
                # bootstrap, before model build/compile skews them apart):
                # gloo's context handshake carries its own short internal
                # deadline, and deferring it to the first training
                # collective makes compile-time skew look like a collective
                # failure.  A straggler surfaces here instead, classified,
                # under the bootstrap deadline.
                from .flags import flag as _flag

                self._watchdog.run(
                    self._collective_warmup, what="fleet.init.barrier",
                    timeout_s=float(_flag("FLAGS_dist_bootstrap_timeout_s")))
        return self

    @staticmethod
    def _collective_warmup():
        from jax.experimental import multihost_utils

        multihost_utils.sync_global_devices("paddle_tpu.fleet.init")

    @property
    def watchdog(self):
        """The gang's CollectiveWatchdog (None for single-worker runs)."""
        return getattr(self, "_watchdog", None)

    @property
    def heartbeat(self):
        from .dist_resilience import active_heartbeat

        return active_heartbeat() if getattr(self, "_watchdog", None) else None

    def is_first_worker(self) -> bool:
        return self._role is None or self._role.is_first_worker()

    def worker_index(self) -> int:
        return 0 if self._role is None else self._role.worker_index()

    def worker_num(self) -> int:
        return 1 if self._role is None else self._role.worker_num()

    @property
    def mesh(self):
        if self._mesh is None:
            from .parallel.distributed import global_mesh

            self._mesh = global_mesh()
        return self._mesh

    # -- the training surface ---------------------------------------------
    def distributed_optimizer(self, optimizer, strategy: Optional[DistributedStrategy] = None):
        if strategy is not None:
            self._strategy = strategy
        return _DistributedOptimizer(self, optimizer)

    def main_program(self, program):
        """Compile a program for the fleet's global mesh (what the
        transpiler's NCCL2 mode produced as `trainer_program`)."""
        from .parallel.compiled_program import BuildStrategy, CompiledProgram

        bs = BuildStrategy()
        bs.memory_optimize = self._strategy.memory_optimize
        cp = CompiledProgram(program, build_strategy=bs).with_mesh(self.mesh)
        if self._strategy.use_local_sgd:
            # DistributedStrategy.use_local_sgd (reference collective.py
            # LocalSGD mode): k communication-free local steps per worker,
            # one pmean per round — executor runs one round per dispatch
            cp = cp.with_local_sgd(self._strategy.local_sgd_steps)
        return cp

    def save_inference_model(self, executor, dirname, feeded_var_names,
                             target_vars, main_program=None, scope=None):
        from . import io as _io

        if self.is_first_worker():
            return _io.save_inference_model(dirname, feeded_var_names,
                                            target_vars, executor,
                                            main_program=main_program, scope=scope)

    def save_persistables(self, executor, dirname, main_program=None, scope=None):
        from . import io as _io

        if self.is_first_worker():
            return _io.save_persistables(executor, dirname,
                                         main_program=main_program, scope=scope)


class _DistributedOptimizer:
    """reference fleet_base.DistributedOptimizer: minimize() keeps the
    reference's 2-tuple return; the mesh-compiled program is available as
    `.compiled_program` afterwards (or via fleet.main_program)."""

    def __init__(self, fleet: Fleet, inner):
        self._fleet = fleet
        self._inner = inner
        self.compiled_program = None

    def __getattr__(self, item):
        return getattr(self._inner, item)

    def minimize(self, loss, startup_program=None, parameter_list=None,
                 no_grad_set=None):
        with _MON.span("fleet.minimize"):
            ops, pg = self._inner.minimize(loss, startup_program,
                                           parameter_list, no_grad_set)
            self.compiled_program = self._fleet.main_program(loss.block.program)
        # the per-round gradient allreduce GSPMD will insert moves
        # sum(param bytes) over the dp axis; record the per-sync volume so
        # bench tooling can compare measured step time against it
        if _MON.enabled:
            from .core.dtypes import as_np_dtype

            nbytes = 0
            for p in loss.block.program.all_parameters():
                if not (p.shape and all(isinstance(d, int) and d > 0 for d in p.shape)):
                    continue
                dt = as_np_dtype(p.dtype)
                nbytes += int(np.prod(p.shape)) * (np.dtype(dt).itemsize if dt else 4)
            _MON.counter("collective.sync_bytes").inc(nbytes)
        return ops, pg


fleet = Fleet()  # the module-level singleton the reference exposes
