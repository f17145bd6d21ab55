"""Executor: compile-and-run of whole programs.

Reference counterparts: `python/paddle/fluid/executor.py` (Executor:292,
run:564) and `framework/executor.cc:150` (per-op interpreter).

TPU-first redesign: `run()` does NOT interpret ops.  It lowers the program's
global block to ONE jax function (forward + vjp backward + optimizer update),
jit-compiles it, caches the executable keyed by (program version, feed
signature, state signature, fetch names) — the role the reference's
`use_program_cache` played — and executes it.  Persistent state (parameters,
optimizer accumulators, RNG key) lives in a Scope as device arrays and is
donated to the executable each step, so parameter updates are in-place in HBM.
"""
from __future__ import annotations

import hashlib
import time
from typing import Dict, List, Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np

from ..dist_resilience import guard_blocking as _guard_blocking
from ..monitor import MONITOR as _MON
from . import locks
from .dtypes import as_np_dtype
from .lowering import (LoweringContext, count_layer_forms, jaxpr_size, phase, plan_kept, plan_latent_operands, profiled,
                       run_block_with_backward)
from .program import Program, Variable, default_main_program
from .scope import RNG_STATE_VAR, Scope, global_scope


class Place:
    pass


class TPUPlace(Place):
    """Device handle (reference: platform/place.h CUDAPlace:37)."""

    def __init__(self, device_id: int = 0):
        self.device_id = device_id

    def __repr__(self):
        return f"TPUPlace({self.device_id})"

    def jax_device(self):
        """Device `device_id` of the backend the environment selected (the
        TPU on a chip host; the CPU under JAX_PLATFORMS=cpu, where the
        tests' virtual mesh lives).  Entry points that need the chip check
        the platform themselves (chip_smoke.py)."""
        # local_devices: under multi-process, jax.devices() lists the global
        # topology but only local ones can receive single-device work
        devs = jax.local_devices()
        if not 0 <= self.device_id < len(devs):
            raise ValueError(
                f"{self!r}: the {devs[0].platform} backend has "
                f"{len(devs)} local device(s)")
        return devs[self.device_id]


class CPUPlace(Place):
    def __init__(self):
        self.device_id = 0

    def __repr__(self):
        return "CPUPlace()"

    def jax_device(self):
        try:
            return jax.local_devices(backend="cpu")[0]
        except RuntimeError:
            return jax.local_devices()[0]


# CUDAPlace alias keeps reference-era scripts importable; it is a TPU device.
CUDAPlace = TPUPlace


class CUDAPinnedPlace(Place):
    """reference CUDAPinnedPlace: host-pinned staging memory.  PJRT manages
    transfer staging itself, so this is the host (CPU) place."""

    def __init__(self):
        self.device_id = 0

    def jax_device(self):
        return CPUPlace().jax_device()


def cpu_places(device_count=None):
    """reference fluid.cpu_places."""
    import os

    n = device_count or int(os.environ.get("CPU_NUM", 1))
    return [CPUPlace() for _ in range(n)]


def cuda_places(device_ids=None):
    """reference fluid.cuda_places: accelerator places (TPU chips here)."""
    if device_ids is None:
        device_ids = range(len(jax.local_devices()))
    return [TPUPlace(i) for i in device_ids]


def cuda_pinned_places(device_count=None):
    import os

    n = device_count or int(os.environ.get("CPU_NUM", 1))
    return [CUDAPinnedPlace() for _ in range(n)]


def _runnable_ops(block):
    return [op for op in block.ops if op.type not in ("feed", "fetch")]


def _structure_digest(program: Program, *more) -> str:
    """Eight hex digits of the program's STRUCTURE (op types and argument
    names, every block) and whatever else the caller adds.  The same in
    every process and on every rank that built the same program; never from
    per-process identities like `program._uuid`."""
    structure = tuple(
        (op.type, tuple(op.input_arg_names), tuple(op.output_arg_names))
        for blk in program.blocks for op in blk.ops)
    return hashlib.sha1(repr((structure,) + more).encode()).hexdigest()[:8]


class _CompiledStep:
    """One jitted executable for (program, feed sig, fetch names, state sig)."""

    def __init__(self, program: Program, feed_names: Sequence[str], fetch_names: Sequence[str], scope: Scope,
                 mesh=None, batch_axis: str = "dp", feed_shapes: Optional[Dict[str, tuple]] = None,
                 n_steps: int = 1, remat: bool = False, platform: Optional[str] = None,
                 local_sgd: bool = False, grad_overlap=None):
        self.mesh = mesh
        self.platform = platform
        self.batch_axis = batch_axis
        self.n_steps = n_steps
        self.remat = remat
        self.multiprocess = mesh is not None and any(
            d.process_index != jax.process_index() for d in mesh.devices.flat
        )
        # AOT executable state: trace/lower and XLA-compile are split out of
        # dispatch (jax.jit's .trace().lower().compile()) so the monitor can
        # time each phase; re-built on state-aval change like jit's retrace.
        # _exec_by_sig keeps previously built executables so programs whose
        # state avals alternate don't recompile on every flip (the multi-
        # entry cache jit provided); the signature is only computed on the
        # miss path, never in steady state.
        self.program_uuid = program._uuid[:8]
        # cross-rank correlation key (ISSUE 8): every rank compiling this
        # (program, mesh) pair derives the same digest, so
        # tools/trace_merge.py can line up "the same collective-bearing
        # step" across per-rank telemetry streams by (csig, step number).
        # RANK-INVARIANT by construction: built from the program's
        # structure (op types + arg names — identical when every rank
        # built the same program, which the collective-order lint already
        # demands), its static collective_signature, and the mesh shape —
        # never from per-process identities like program._uuid.  None
        # off-mesh: nothing to correlate.
        self.csig = None
        if mesh is not None:
            try:
                from .analysis import collective_signature

                self.csig = _structure_digest(
                    program, collective_signature(program),
                    tuple(sorted(dict(mesh.shape).items())))
            except Exception:
                self.csig = None
        self._exec = None
        self._exec_by_sig: Dict[tuple, object] = {}
        # serving clones share _CompiledStep instances across threads: two
        # threads cold-starting the same signature must build ONE
        # executable (a double trace+compile would double-count the
        # recompile gate and waste the compile lane)
        self._build_lock = locks.named_lock("executor.build", rank=26)
        self.last_lower_s = 0.0
        self.last_compile_s = 0.0
        self.last_recompiled = False
        feed_shapes = feed_shapes or {}
        block = program.global_block()
        ops = _runnable_ops(block)

        persistable = {
            v.name for v in program.list_vars() if v.persistable
        }
        ops = self._prune(ops, fetch_names, persistable)
        # What the step IS, as the name of the jitted function (the `XLA
        # Modules` line of a device trace shows `jit_<module>`), of the
        # executor's spans (`module=`) and of its step records, so that a
        # device event joins to the span that built and dispatched it.
        # The name is part of JAX's persistent-cache key: it is made of
        # the program's structure, which every process that builds the
        # same program shares, so a warm set-up stays warm.
        kind = ("train" if any(op.type == "backward" for op in ops)
                else "startup" if not feed_names else "infer")
        self.moe_layers = sum(op.type == "moe_experts" for op in ops)
        self.module = f"{kind}_{_structure_digest(program)}"
        read_names = set()
        written = []
        written_set = set()
        for op in ops:
            # _effective_io folds in sub-block reads/writes (while / cond /
            # dynamic_rnn / repeat bodies read parameters the top-level op may not list)
            reads, outs = self._effective_io(op)
            read_names.update(reads)
            if op.type == "backward":
                read_names.update(op.attrs.get("param_names", []))
            for n in outs:
                if n in persistable and n not in written_set:
                    written_set.add(n)
                    written.append(n)
        # grads of params: backward writes grad vars which may be persistable? no.
        needed = (read_names | set(fetch_names)) & persistable
        self.state_in_names = sorted(n for n in needed if scope.has_var(n))
        self.written_names = written
        self.fetch_names = list(fetch_names)
        self.feed_names = list(feed_names)

        # Donate only buffers the step overwrites (params/accumulators under
        # an optimizer); read-only state is passed undonated.
        self.rw_names = [n for n in self.state_in_names if n in written_set]
        self.ro_names = [n for n in self.state_in_names if n not in written_set]

        # Backward-overlapped dp gradient all-reduce (CompiledProgram.
        # with_grad_overlap): the step runs inside a manual shard_map region
        # and grads are bucket-psum'd via the LoweringContext hook.
        self._grad_sync = None
        if grad_overlap is not None:
            overlap_mode, bucket_bytes = grad_overlap
            if mesh is None or not dict(mesh.shape).get(batch_axis):
                raise ValueError(
                    "with_grad_overlap needs a mesh with a batch axis "
                    "(CompiledProgram.with_data_parallel / with_mesh first)")
            if program.sharding_hints:
                raise NotImplementedError(
                    "with_grad_overlap is a pure-dp path (replicated "
                    "state); programs with sharding_hints keep the GSPMD "
                    "collectives")
            from ..parallel.distributed import make_grad_sync

            self._grad_sync = make_grad_sync(batch_axis, bucket_bytes,
                                             mode=overlap_mode)

        def step(state_rw: Dict[str, jnp.ndarray], state_ro: Dict[str, jnp.ndarray],
                 feeds: Dict[str, jnp.ndarray], key):
            # LocalSGD and the overlapped all-reduce trace the step INSIDE a shard_map of their own: no batch axis is
            # left for an op to split over
            manual = local_sgd or grad_overlap is not None
            ctx = LoweringContext(key, mesh=mesh, platform=self.platform, batch_axis=None if manual else batch_axis)
            ctx.remat = self.remat
            ctx.grad_sync = self._grad_sync
            ctx.fetch_names = tuple(self.fetch_names)
            env = dict(state_ro)
            env.update(state_rw)
            # what the recomputed segments keep for backward, from the shapes and the room the state leaves on a chip
            with phase("plan_kept"):
                plan_kept(ctx, ops, {n: v.shape for n, v in feeds.items()}, self._held_bytes(env, whole=manual))
                plan_latent_operands(ctx, ops)
                count_layer_forms(ops)
            env.update(feeds)
            env = run_block_with_backward(ctx, ops, env)
            new_state = {n: env[n] for n in written if n in env}
            fetches = [env[n] for n in self.fetch_names]
            return fetches, new_state, ctx.key

        # dp geometry shared by every feed-sharding consumer below (LocalSGD
        # and overlap shard_map in_specs, jit-level in_shardings).
        # feed_shapes are the caller's LOCAL per-process shapes; when the
        # batch axis spans processes each feed's global batch is
        # local * dp_procs, so divisibility checks must use the per-process
        # dp share, not the global dp size.
        if mesh is not None:
            n_dp = dict(mesh.shape).get(batch_axis, 0)  # 0: no data axis (e.g. pure pp mesh)
            dp_spans = False
            dp_procs = 1
            if self.multiprocess and n_dp:
                ax = list(mesh.axis_names).index(batch_axis)
                line = np.moveaxis(mesh.devices, ax, 0).reshape(n_dp, -1)[:, 0]
                procs = {d.process_index for d in line}
                dp_spans = len(procs) > 1
                dp_procs = max(len(procs), 1)
            n_dp_local = max(n_dp // dp_procs, 1) if dp_spans else n_dp

            def _feed_pspec(n):
                # CONTRACT (cross-process dp): every feed with a batch dim
                # is this process's slice of the global batch, sharded over
                # the dp axis exactly when the local batch divides this
                # process's dp share; replicated non-scalar data must be
                # passed as a pre-placed jax.Array.  The ONE copy of this
                # rule feeds the LocalSGD and overlap shard_map in_specs
                # and the jit in_shardings — if two of them disagreed,
                # shard_map would all-gather the batch and every worker
                # would compute the full global batch (dp silently gone).
                from jax.sharding import PartitionSpec as P

                shape = feed_shapes.get(n, ())
                bdim = 1 if n_steps > 1 else 0  # steps>1: axis 0 is scan
                if (n_dp and len(shape) > bdim
                        and shape[bdim] % n_dp_local == 0):
                    return P(*([None] * bdim + [batch_axis]))
                if dp_spans and len(shape) > bdim and shape[bdim] > 1:
                    # replicating per-process data that differs across
                    # processes silently breaks sync-SGD; refuse instead
                    raise ValueError(
                        f"multiprocess feed {n!r}: local batch "
                        f"{shape[bdim]} is not divisible by this process's "
                        f"dp share ({n_dp_local}); pad the local batch or "
                        f"adjust the mesh")
                return P()

        if n_steps > 1:
            # Multi-step dispatch: lax.scan the whole train step over feeds
            # stacked on a leading [n_steps] axis.  One host->device dispatch
            # drives K optimizer steps — the TPU answer to the reference's
            # dataset-driven trainer hot loop (`hogwild_worker.cc:137`:
            # `for op in ops: op->Run()` per batch, no Python between steps).
            # Requires every written persistable to round-trip through the
            # carry, i.e. written ⊆ read state (true for params/accumulators).
            missing = [n for n in written if n not in set(self.rw_names)]
            if missing:
                raise ValueError(
                    f"steps>1 needs write-back state to be read by the program "
                    f"too; write-only persistables: {missing}"
                )
            inner = step

            if local_sgd:
                # LocalSGD round (reference transpiler/collective.py:249
                # LocalSGD: snapshot + allreduce param deltas every k steps).
                # TPU-native: each dp worker runs the k scanned steps on ITS
                # OWN diverging copy of the state inside a shard_map — no
                # collective between steps — then one pmean re-syncs.  One
                # dispatch = one round; the scope's single logical copy means
                # optimizer accumulators are averaged at the sync too (the
                # reference keeps them worker-local; recorded deviation).
                if mesh is None or not dict(mesh.shape).get(batch_axis):
                    raise ValueError(
                        "local_sgd needs a mesh with a batch axis "
                        "(CompiledProgram.with_local_sgd on a dp mesh)")
                if self.multiprocess:
                    # the shard_map in_specs below assume single-controller
                    # global batches; per-process slice assembly is not wired
                    raise NotImplementedError(
                        "with_local_sgd on a multi-process mesh is not "
                        "supported yet; use a single-controller dp mesh")
                from jax.sharding import PartitionSpec as P

                ls_in_feeds = {n: _feed_pspec(n) for n in self.feed_names}
                rw_repl = {n: P() for n in self.rw_names}
                ro_repl = {n: P() for n in self.ro_names}
                out_state_spec = {n: P() for n in written}

                def worker(state_rw, state_ro, feeds, key):
                    wk = jax.random.fold_in(key, jax.lax.axis_index(batch_axis))

                    def body(carry, feed_t):
                        srw, k = carry
                        fetches_t, new_state, k2 = inner(srw, state_ro, feed_t, k)
                        return (new_state, k2), fetches_t

                    (srw, _), stacked = jax.lax.scan(body, (state_rw, wk), feeds)
                    srw = jax.tree_util.tree_map(
                        lambda a: jax.lax.pmean(a, batch_axis), srw)
                    # fetch semantics under LocalSGD: the dp-MEAN of each
                    # worker's value (right for scalar losses/metrics; for
                    # per-sample outputs run a separate eval dispatch)
                    stacked = jax.tree_util.tree_map(
                        lambda a: jax.lax.pmean(a, batch_axis), stacked)
                    return stacked, srw

                smapped = jax.shard_map(
                    worker, mesh=mesh,
                    in_specs=(rw_repl, ro_repl, ls_in_feeds, P()),
                    out_specs=([P()] * len(self.fetch_names), out_state_spec),
                    check_vma=False,
                )

                def step(state_rw, state_ro, feeds, key):
                    stacked, srw = smapped(state_rw, state_ro, feeds, key)
                    return stacked, srw, jax.random.fold_in(key, n_steps)
            else:
                def step(state_rw, state_ro, feeds, key):
                    def body(carry, feed_t):
                        srw, k = carry
                        fetches_t, new_state, k2 = inner(srw, state_ro, feed_t, k)
                        return (new_state, k2), fetches_t

                    (srw, key2), stacked = jax.lax.scan(body, (state_rw, key), feeds)
                    return stacked, srw, key2

        if self._grad_sync is not None:
            # Manual dp region around the (possibly scanned) step: each dp
            # worker traces the program over ITS batch shard; the grad_sync
            # hook mean-reduces gradients in buckets inside the backward, so
            # parameter updates are identical across workers and the state
            # stays replicated.  DDP semantics: dropout masks and BN batch
            # stats are per-shard (each worker folds the step key with its
            # dp index); fetches come back as the dp-mean (exact for the
            # scalar losses/metrics training fetches).
            from jax.sharding import PartitionSpec as P

            ov_in_feeds = {n: _feed_pspec(n) for n in self.feed_names}
            rw_repl = {n: P() for n in self.rw_names}
            ro_repl = {n: P() for n in self.ro_names}
            out_state_spec = {n: P() for n in written}
            inner_step = step
            n_fetch = len(self.fetch_names)
            # Written state whose update is NOT grad-derived needs its own
            # sync: each worker folds ITS shard's statistics, so without
            # one the P() out_spec would claim replication over genuinely
            # divergent per-device buffers (rank-divergent checkpoints,
            # undefined eval stats).  Two classes, two reductions:
            #   - BN running mean/var: dp-MEAN — exact for the running
            #     mean, the standard shard-mean approximation for the
            #     running variance; normalization itself stays per-shard
            #     (DDP semantics).
            #   - additive accumulators (auc StatPos/StatNeg histograms):
            #     delta-PSUM — new = old + psum(new - old), so the global
            #     histogram counts every shard's samples exactly (integer
            #     math, bit-identical across serial/bucketed arms).
            bn_stat_names = set()
            acc_stat_names = set()
            # walk every block, not just the compiled op list — a BN inside
            # a while/conditional sub-block still writes persistable stats
            # into `written` and needs the same sync
            for blk in program.blocks:
                for op_ in blk.ops:
                    if (op_.type in ("batch_norm", "sync_batch_norm")
                            and not op_.attrs.get("is_test")
                            and not op_.attrs.get("use_global_stats")):
                        for slot in ("MeanOut", "VarianceOut"):
                            bn_stat_names.update(op_.outputs.get(slot, ()))
                    elif op_.type == "auc":
                        for slot in ("StatPosOut", "StatNegOut"):
                            acc_stat_names.update(op_.outputs.get(slot, ()))
            bn_stat_names &= set(written)
            acc_stat_names &= set(written)

            def worker(state_rw, state_ro, feeds, key):
                wk = jax.random.fold_in(key, jax.lax.axis_index(batch_axis))
                fetches, new_state, _ = inner_step(state_rw, state_ro, feeds, wk)
                # the dp-mean below is only meaningful for scalar losses/
                # metrics (per step); a per-sample fetch would come back as
                # the element-wise average of DIFFERENT samples across
                # shards at 1/n_dp the batch — garbage with no error.
                # Refuse at trace time instead.  (A fetch whose PER-SHARD
                # size is 1 is indistinguishable from a scalar metric here
                # and passes — shapes are shard-local inside shard_map.)
                for fname, f in zip(self.fetch_names, fetches):
                    if getattr(f, "size", 1) > max(n_steps, 1):
                        raise ValueError(
                            f"with_grad_overlap: fetch {fname!r} has shape "
                            f"{f.shape} — overlap fetches are dp-MEANed "
                            f"across workers, which is only exact for "
                            f"scalar losses/metrics; fetch a reduced "
                            f"scalar, or run evaluation through a program "
                            f"compiled without grad overlap")
                fetches = jax.tree_util.tree_map(
                    lambda a: jax.lax.pmean(a, batch_axis), fetches)
                if bn_stat_names or acc_stat_names:
                    def _sync_stat(n, v):
                        if n in bn_stat_names:
                            return jax.lax.pmean(v, batch_axis)
                        if n in acc_stat_names:
                            # additive accumulator: every shard starts from
                            # the same replicated base and adds its shard's
                            # delta — psum the delta, not the state, or the
                            # base would be counted n_dp times
                            return state_rw[n] + jax.lax.psum(
                                v - state_rw[n], batch_axis)
                        return v
                    new_state = {n: _sync_stat(n, v)
                                 for n, v in new_state.items()}
                return fetches, new_state

            smapped = jax.shard_map(
                worker, mesh=mesh,
                in_specs=(rw_repl, ro_repl, ov_in_feeds, P()),
                out_specs=([P()] * n_fetch, out_state_spec),
                check_vma=False,
            )

            def step(state_rw, state_ro, feeds, key):
                fetches, new_state = smapped(state_rw, state_ro, feeds, key)
                return fetches, new_state, jax.random.fold_in(key, max(n_steps, 1))

        step.__name__ = step.__qualname__ = self.module
        if mesh is None:
            self.jfn = jax.jit(step, donate_argnums=(0,))
            self.feed_specs = None
        else:
            # SPMD: feeds batch-sharded on dim 0, state placed per program
            # sharding hints (default replicated) — GSPMD inserts the
            # gradient all-reduces the reference emitted as NCCL op handles.
            from jax.sharding import NamedSharding, PartitionSpec as P

            hints = dict(program.sharding_hints)

            def state_spec(n):
                return NamedSharding(mesh, P(*hints[n]) if n in hints else P())

            repl = NamedSharding(mesh, P())

            def feed_spec(n):
                # the dp feed-sharding contract lives in _feed_pspec (shared
                # with the overlap shard_map in_specs); this just places it
                return NamedSharding(mesh, _feed_pspec(n))

            rw_specs = {n: state_spec(n) for n in self.rw_names}
            ro_specs = {n: state_spec(n) for n in self.ro_names}
            feed_specs = {n: feed_spec(n) for n in self.feed_names}
            self.feed_specs = feed_specs
            self.state_specs = {**rw_specs, **ro_specs}
            self.key_spec = repl
            out_specs = (
                [repl] * len(self.fetch_names),
                {n: state_spec(n) for n in written},
                repl,
            )
            self.jfn = jax.jit(
                step,
                donate_argnums=(0,),
                in_shardings=(rw_specs, ro_specs, feed_specs, repl),
                out_shardings=out_specs,
            )

    @staticmethod
    def _effective_io(op):
        """(reads, writes) including sub-block effects for control flow."""
        reads = list(op.input_arg_names)
        writes = list(op.output_arg_names)
        if op.type in ("while", "conditional_block", "dynamic_rnn", "repeat"):
            idx = op.attrs.get("sub_block")
            if idx is not None:
                sub = op.block.program.blocks[idx]
                for sop in sub.ops:
                    r, w = _CompiledStep._effective_io(sop)
                    reads.extend(r)
                    writes.extend(w)
        return reads, writes

    @staticmethod
    def _prune(ops, fetch_names, persistable):
        """Fetch-driven dead-op elimination (the reference prunes programs to
        feed/fetch targets at io.py save_inference_model:915; here it runs on
        every compile so eval programs don't demand training-only feeds).
        Ops are kept if they (transitively) contribute to a fetch or write a
        persistable var.  Control-flow ops count their sub-block reads and
        writes."""
        needed = set(fetch_names)
        kept = []
        for op in reversed(ops):
            reads, outs = _CompiledStep._effective_io(op)
            writes_state = any(o in persistable for o in outs)
            if writes_state or any(o in needed for o in outs):
                kept.append(op)
                needed.update(reads)
                if op.type == "backward":
                    needed.add(op.attrs["loss_name"])
                    needed.update(op.attrs.get("param_names", []))
        kept.reverse()
        return kept

    def _place(self, v, spec):
        """Host/local array -> mesh placement.  Multi-process meshes can't
        jax.device_put a local array onto non-addressable devices; each
        process instead materializes its own shards from the (replicated)
        host value via make_array_from_callback."""
        if self.multiprocess:
            host = np.asarray(v)
            return jax.make_array_from_callback(host.shape, spec, lambda idx: host[idx])
        return jax.device_put(v, spec)

    @property
    def last_build_s(self) -> float:
        """Seconds the last call spent lowering and compiling; 0 when it
        ran an executable it already had."""
        return (self.last_lower_s + self.last_compile_s
                if self.last_recompiled else 0.0)

    @staticmethod
    def _state_sig(state_rw, state_ro):
        return (
            tuple((n, v.shape, str(v.dtype)) for n, v in sorted(state_rw.items())),
            tuple((n, v.shape, str(v.dtype)) for n, v in sorted(state_ro.items())),
        )

    def _dispatch(self, state_rw, state_ro, feeds, key):
        """Run the step through the AOT executable, building it on first
        use (and after a state-aval change) with the block->jaxpr lowering
        and the XLA compile timed as separate monitor spans."""
        self.last_recompiled = False
        exec_ = self._exec
        if exec_ is not None:
            try:
                return exec_(state_rw, state_ro, feeds, key)
            except TypeError:
                # state avals changed (dtype promotion, resharding): the
                # aval check fires before execution, so donated buffers are
                # untouched.  Try an executable built for this signature
                # before recompiling (jit's multi-entry cache role).
                cached = self._exec_by_sig.get(self._state_sig(state_rw, state_ro))
                if cached is not None and cached is not exec_:
                    try:
                        out = cached(state_rw, state_ro, feeds, key)
                        self._exec = cached
                        return out
                    except TypeError:
                        pass
                self._exec = None
        with self._build_lock:  # lock-ok: one XLA trace+compile per executable signature IS the lock's purpose; a hit path never reaches here and the cache lock stays free throughout
            # a concurrent thread (serving clones share this step) may
            # have built the executable while we waited for the lock:
            # serve from its entry instead of compiling a duplicate
            sig = self._state_sig(state_rw, state_ro)
            cached = self._exec_by_sig.get(sig)
            if cached is not None:
                try:
                    out = cached(state_rw, state_ro, feeds, key)
                    self._exec = cached
                    return out
                except TypeError:
                    pass
            mon_on = _MON.enabled
            what = dict(program=self.program_uuid, module=self.module)
            t0 = time.perf_counter()
            counted0 = _MON.counter_values() if mon_on else {}
            with _MON.span("executor.lower", **what) as lowering, profiled(**what) as profile:
                # the trace's phases are spans of their own under this one (`lowering.TraceProfile`)
                with phase("trace"):
                    traced = self.jfn.trace(state_rw, state_ro, feeds, key)
                with phase("to_hlo") as to_hlo:
                    if profile is not None:
                        to_hlo.annotate(**jaxpr_size(traced.jaxpr))
                    lowered = traced.lower()
                if profile is not None:
                    if self.moe_layers:
                        _MON.counter("lowering.moe_layers").inc(self.moe_layers)
                    # every `lowering.` counter that moved during this span (which attention each fused_attention op of
                    # this program took, what its `repeat` ops lowered, what its recomputed segments keep, ...), the
                    # gradients fenced under the name they have had, and where the trace's Python went
                    moved = {name[len("lowering."):]: n - counted0.get(name, 0)
                             for name, n in _MON.counter_values().items()
                             if name.startswith("lowering.") and n != counted0.get(name, 0)}
                    lowering.annotate(fenced=moved.pop("fenced_grads", 0), by_op=profile.by_op(), **moved)
            t1 = time.perf_counter()
            with _MON.span("executor.compile", **what) as compiling:
                # did JAX's persistent cache serve it?  The monitor hears
                # the one event JAX fires on a hit, counted per thread
                hits0 = _MON.jax_cache_hits()
                built = lowered.compile()
                if mon_on:
                    hit = _MON.jax_cache_hits() > hits0
                    compiling.annotate(cache_hit=hit)
                    _MON.counter("executor.compile_cache_hit" if hit
                                 else "executor.compile_cache_miss").inc()
            t2 = time.perf_counter()
            if mon_on and self.mesh is not None and self.mesh.size > 1:
                # what the step communicates: a walk over the compiled text, a span of its own beside the compile's
                from ..parallel import collectives
                collectives.publish(built, self.mesh, **what)
            self._exec = built
            self._exec_by_sig[sig] = built
            if len(self._exec_by_sig) > 8:
                self._exec_by_sig.pop(next(iter(self._exec_by_sig)))
            self.last_lower_s = t1 - t0
            self.last_compile_s = t2 - t1
            self.last_recompiled = True
        _MON.counter("executor.recompile").inc()
        return built(state_rw, state_ro, feeds, key)

    def __call__(self, scope: Scope, feeds: Dict[str, jnp.ndarray], key):
        if self.mesh is not None:
            # Reshard state committed elsewhere (e.g. by a single-device
            # startup run) onto the mesh layout the step expects.
            for n, spec in self.state_specs.items():
                v = scope.find_var(n)
                if getattr(v, "sharding", None) != spec:
                    scope.set_var(n, self._place(v, spec))
            if getattr(key, "sharding", None) != self.key_spec:
                key = self._place(key, self.key_spec)
        state_rw = {n: scope.find_var(n) for n in self.rw_names}
        state_ro = {n: scope.find_var(n) for n in self.ro_names}
        fetches, new_state, new_key = self._dispatch(state_rw, state_ro, feeds, key)
        for n, v in new_state.items():
            scope.set_var(n, v)
        if self.mesh is not None and self.last_recompiled:
            self._count_state({**state_ro, **state_rw, **new_state})
        return fetches, new_key

    def _held_bytes(self, state, whole=False) -> int:
        """Bytes of the step's state (traced values by name) that ONE chip
        holds: a value's own where there is no mesh or the step is traced inside
        a `shard_map` of its own (`whole`), else its shard's under the
        program's hints."""
        specs = {} if whole or self.mesh is None else self.state_specs
        return sum(int(np.prod(specs[n].shard_shape(v.shape) if n in specs else v.shape)) * v.dtype.itemsize
                   for n, v in state.items())

    def _count_state(self, state):
        """At placement (a program's first run on its mesh): the bytes of its
        persistables that a device holds, those split by a hint and those held
        whole, as two gauges of the program's last placement and in the
        `executor.state_placed` step record."""
        sharded = replicated = 0
        for v in state.values():
            shards = getattr(v, "addressable_shards", None)
            if not shards:
                continue
            held = int(shards[0].data.nbytes)
            if held < v.nbytes:
                sharded += held
            else:
                replicated += held
        _MON.gauge("executor.state_bytes_sharded").set(sharded)
        _MON.gauge("executor.state_bytes_replicated").set(replicated)
        if _MON.enabled:
            _MON.record_step({"kind": "state_placed", "program": self.program_uuid, "module": self.module,
                              "devices": int(self.mesh.size), "bytes_sharded_per_device": sharded,
                              "bytes_replicated_per_device": replicated})


class _PendingFetches:
    """Shared state behind the FetchHandles of one `run_async` dispatch.

    Holds the still-in-flight output `jax.Array`s (plus the new RNG key, so
    `wait()` exerts backpressure even for fetch-less programs) and the
    deferred NaN/Inf check.  Resolution happens at most once; an error
    raised during resolution is sticky so every handle of the dispatch
    reports the same failure."""

    __slots__ = ("fetch_names", "fetches", "key", "program_u8", "_np",
                 "_exc", "_done")

    def __init__(self, fetch_names, fetches, key, program_u8):
        self.fetch_names = list(fetch_names)
        self.fetches = list(fetches)
        self.key = key
        self.program_u8 = program_u8
        self._np = None
        self._exc = None
        self._done = False

    def wait(self):
        """Block until the dispatched step has executed on the device —
        no device->host copy.  The bounded-depth knob:
        train_loop calls this on non-logging steps.  Routed through the
        collective watchdog: on a cross-process mesh this wait sits inside
        the step's allreduce, which never completes once a peer is dead."""
        def _block():
            jax.block_until_ready(self.fetches)
            if self.key is not None:
                jax.block_until_ready(self.key)

        _guard_blocking(_block, what="executor.wait")

    def ready(self) -> bool:
        """Non-blocking readiness probe."""
        outs = self.fetches if self.fetches else ([self.key] if self.key is not None else [])
        return all(a.is_ready() for a in outs)

    def resolve(self):
        """Materialize fetches to numpy (first call only), finishing the
        deferred NaN/Inf check.  In-flight errors —
        a poisoned value caught by FLAGS_check_nan_inf, an XLA runtime
        failure surfacing at the blocking copy — raise HERE, not at
        dispatch; the scope already holds the step's output buffers, so a
        resolution failure does not corrupt persistent state."""
        if self._done:
            if self._exc is not None:
                raise self._exc
            return self._np
        try:
            # the device->host copy (the NaN guard's np.asarray included)
            # is where an in-flight collective's block manifests;
            # watchdog-guarded so a dead peer raises (classified below)
            # instead of hanging the resolver
            def _materialize():
                Executor._check_nan_inf(self.fetch_names, self.fetches)
                return [np.asarray(v) for v in self.fetches]

            with _MON.span("executor.fetch", program=self.program_u8):
                self._np = _guard_blocking(_materialize,
                                           what="executor.resolve")
        except BaseException as e:
            # route the in-flight failure through the taxonomy
            # (paddle_tpu/errors.py): an XLA RESOURCE_EXHAUSTED /
            # UNAVAILABLE surfacing at the blocking copy becomes a
            # TransientDeviceError the resilient loop can retry; anything
            # unmapped stays itself.  The classified error is the sticky
            # one — every handle of the dispatch reports the same failure.
            from ..errors import classify

            ce = classify(e)
            self._exc = ce
            if ce is e:
                raise
            raise ce from e
        finally:
            # resolution is one-shot either way: drop the device buffers
            # and the key so retained handles don't pin a step's outputs
            # in memory past their numpy copies
            self._done = True
            self.fetches = []
            self.key = None
        return self._np


class FetchHandle:
    """Lazy fetch result from `Executor.run_async`.

    Wraps one output of a still-in-flight dispatch: JAX's async dispatch
    keeps the device busy while Python runs ahead, and the device->host
    copy (plus the deferred NaN check) happens only on first
    access — `numpy()`, `np.asarray(handle)`, or `float(handle)`."""

    __slots__ = ("_pending", "_idx", "name")

    def __init__(self, pending: _PendingFetches, idx: int, name: str):
        self._pending = pending
        self._idx = idx
        self.name = name

    def numpy(self) -> np.ndarray:
        return self._pending.resolve()[self._idx]

    def wait(self):
        """Block until device execution finished, WITHOUT copying to host."""
        self._pending.wait()
        return self

    # jax-style alias so generic `jax.block_until_ready`-ish call sites work
    def block_until_ready(self):
        return self.wait()

    def is_ready(self) -> bool:
        return self._pending._done or self._pending.ready()

    def __array__(self, dtype=None):
        arr = self.numpy()
        return arr.astype(dtype) if dtype is not None else arr

    def __float__(self):
        return float(np.asarray(self.numpy()).reshape(-1)[0])

    def __repr__(self):
        state = "resolved" if self._pending._done else "in-flight"
        return f"FetchHandle({self.name!r}, {state})"


class Executor:
    """Reference: executor.py:292.  `run` signature kept source-compatible."""

    def __init__(self, place: Optional[Place] = None):
        self.place = place if place is not None else TPUPlace(0)
        self._cache: Dict[tuple, _CompiledStep] = {}
        # compile-cache bookkeeping lock: the LRU pop/re-insert pair and
        # the miss-path build/insert must be atomic — two serving threads
        # racing the same key would otherwise each count a miss and build
        # a duplicate _CompiledStep (the serving cache-share contract is
        # one compiled entry per (program, bucket shape) signature)
        self._cache_lock = locks.named_lock("executor.cache", rank=24)

    def close(self):
        self._cache.clear()

    # -- main entry ------------------------------------------------------
    def run(
        self,
        program: Optional[Program] = None,
        feed: Optional[Dict[str, np.ndarray]] = None,
        fetch_list: Optional[Sequence[Union[str, Variable]]] = None,
        scope: Optional[Scope] = None,
        return_numpy: bool = True,
        use_program_cache: bool = True,  # parity arg; caching is always on
        steps: int = 1,
    ):
        """steps > 1 runs K optimizer steps in ONE device dispatch: every
        feed must carry a leading [steps] axis and fetches come back stacked
        [steps, ...].  Amortizes host dispatch overhead the way the
        reference's dataset trainers amortize the Python boundary."""
        return self._run_impl(program, feed, fetch_list, scope, return_numpy,
                              steps, async_mode=False)

    def run_async(
        self,
        program: Optional[Program] = None,
        feed: Optional[Dict[str, np.ndarray]] = None,
        fetch_list: Optional[Sequence[Union[str, Variable]]] = None,
        scope: Optional[Scope] = None,
        use_program_cache: bool = True,
        steps: int = 1,
    ) -> List["FetchHandle"]:
        """`run`, minus the blocking tail: returns one `FetchHandle` per
        fetch as soon as the step is ENQUEUED on the device.  The scope is
        updated immediately with the step's (in-flight) output buffers and
        the advanced RNG key, so the next `run`/`run_async` over the same
        scope chains correctly — values, optimizer accumulators, and RNG
        advance exactly as under the synchronous path.  Device->host
        copies and the FLAGS_check_nan_inf guard
        run at handle resolution (`handle.numpy()`); an in-flight
        error therefore surfaces on resolution, not dispatch.  See
        paddle_tpu/pipeline.py:train_loop for the bounded-depth driver."""
        return self._run_impl(program, feed, fetch_list, scope, True,
                              steps, async_mode=True)

    def _run_impl(
        self,
        program: Optional[Program],
        feed: Optional[Dict[str, np.ndarray]],
        fetch_list: Optional[Sequence[Union[str, Variable]]],
        scope: Optional[Scope],
        return_numpy: bool,
        steps: int,
        async_mode: bool,
    ):
        program = program if program is not None else default_main_program()
        mesh = None
        batch_axis = "dp"
        remat = False
        local_sgd_every = 0
        grad_overlap = None
        if hasattr(program, "program") and hasattr(program, "mesh"):  # CompiledProgram
            mesh = program.mesh
            batch_axis = getattr(program, "batch_axis", "dp")
            bs = getattr(program, "build_strategy", None)
            # BuildStrategy.memory_optimize -> rematerialized backward
            # (the XLA-native descendant of the reference's
            # memory_optimize_pass: trade FLOPs for activation memory)
            remat = bool(getattr(bs, "memory_optimize", False))
            local_sgd_every = int(getattr(program, "local_sgd_every", 0) or 0)
            ov_mode = getattr(program, "grad_overlap_mode", None)
            if ov_mode:
                bucket_mb = float(getattr(program, "grad_overlap_bucket_mb", 0.0))
                grad_overlap = (ov_mode, int(bucket_mb * 1e6))
            program = program.program
            hinted = getattr(program, "sharding_mesh", None)
            if mesh is not None and hinted is not None and (mesh != hinted or batch_axis != program.sharding_batch_axis):
                # the start-up program placed the state over `hinted`; a step over other devices, another order or
                # another batch axis would read every persistable through a reshard, silently
                raise ValueError(
                    f"CompiledProgram.with_mesh({mesh.axis_names}, batch_axis={batch_axis!r}) is not the mesh the "
                    f"program's sharding hints were given ({hinted.axis_names}, batch_axis="
                    f"{program.sharding_batch_axis!r}: `parallel.shard_parameters(mesh=)`): the same devices in "
                    f"the same order, and the same batch axis")
        elif getattr(program, "sharding_hints", None) and getattr(program, "sharding_mesh", None) is not None:
            # state that is born sharded: a program that carries hints AND the mesh they name (`parallel.
            # shard_parameters(mesh=)`) is placed by them whoever runs it: the start-up program's draws come out split
            # (`out_shardings`), a `for_test` clone reads them where they lie.  A program without both is placed as ever
            mesh, batch_axis = program.sharding_mesh, program.sharding_batch_axis
        if local_sgd_every:
            if steps == 1:
                steps = local_sgd_every  # one dispatch = one LocalSGD round
            elif steps != local_sgd_every:
                raise ValueError(
                    f"with_local_sgd(sync_every={local_sgd_every}): each "
                    f"dispatch runs exactly one round; pass steps="
                    f"{local_sgd_every} (got {steps}) with feeds stacked "
                    f"[sync_every, ...]")
        scope = scope if scope is not None else global_scope()
        feed = feed or {}
        fetch_names = [f.name if isinstance(f, Variable) else str(f) for f in (fetch_list or [])]

        device = self.place.jax_device()
        block = program.global_block()

        # Convert feeds to host arrays with the declared var dtype.
        # Ragged feeds (LoDTensor / list of per-sequence arrays) expand into
        # the padded carrier + `<name>@LOD` lengths pair (paddle_tpu/lod.py).
        from ..lod import LoDTensor, lod_var_name

        expanded = {}
        for name, value in feed.items():
            declared_ragged = block.has_var(name) and block.var(name).lod_level >= 1
            is_ragged_feed = isinstance(value, LoDTensor) or (
                declared_ragged
                and isinstance(value, (list, tuple))
                and len(value) > 0
                and all(isinstance(s, np.ndarray) for s in value)
            )
            if steps > 1 and is_ragged_feed:
                raise ValueError(
                    f"steps>1 does not support ragged/LoDTensor feeds (got one for "
                    f"'{name}'): the padded expansion has no [steps] axis. Stack "
                    f"pre-padded dense arrays [steps, b, T, ...] plus the lengths "
                    f"companion instead, or run with steps=1."
                )
            if is_ragged_feed:
                lt = value if isinstance(value, LoDTensor) else LoDTensor(value)
                padded, lens = lt.padded(bucket=True)
                expanded[name] = padded
                expanded[lod_var_name(name)] = lens
            else:
                expanded[name] = value
        feed = expanded

        from ..ops.common import canon_dtype

        jfeeds = {}
        for name, value in feed.items():
            if isinstance(value, jax.Array):
                # device-resident feed: trust caller's placement (a
                # DataLoader prefetched it, or fake-data benchmarking)
                jfeeds[name] = value
                continue
            dtype = None
            if block.has_var(name):
                dtype = as_np_dtype(block.var(name).dtype)
            arr = np.asarray(value)
            if dtype is not None and arr.dtype != dtype:
                arr = arr.astype(dtype)
            # x32 canonicalization at the feed boundary (silences jax's
            # per-call int64-truncation warning)
            canon = canon_dtype(arr.dtype)
            if arr.dtype != canon:
                arr = arr.astype(canon)
            jfeeds[name] = arr

        if steps > 1:
            for name, value in jfeeds.items():
                shape = np.shape(value)
                if len(shape) == 0 or shape[0] != steps:
                    raise ValueError(
                        f"steps={steps} requires every feed to carry a leading "
                        f"[steps] axis; feed '{name}' has shape {shape}. Stack K "
                        f"batches along axis 0 (fetches come back stacked the "
                        f"same way)."
                    )

        key = scope.find_var(RNG_STATE_VAR)
        if key is None:
            seed = program.random_seed if program.random_seed is not None else 0
            key = jax.random.PRNGKey(seed)
        if mesh is None:
            key = jax.device_put(key, device)
        # (mesh path: _CompiledStep reshards the key onto the mesh itself)

        # NOTE: state shapes/dtypes are deliberately NOT in the key — the
        # inner jax.jit retraces on aval changes anyway; keying on them
        # would cost a walk over every persistable per step.
        cache_key = (
            program._uuid,
            program.version,
            tuple(sorted((n, v.shape, str(v.dtype)) for n, v in jfeeds.items())),
            tuple(fetch_names),
            scope._uuid,
            (tuple(mesh.shape.items()), batch_axis) if mesh is not None else None,
            steps,
            remat,
            local_sgd_every,
            grad_overlap,
        )
        # the bookkeeping lock covers only the dict operations: a HIT (the
        # serving steady state) never waits behind a concurrent miss's
        # verify/build, which a hot reload's staged warm would otherwise
        # stretch into a traffic stall
        with self._cache_lock:
            compiled = self._cache.pop(cache_key, None)
            if compiled is not None:
                self._cache[cache_key] = compiled  # re-insert: true LRU order
                _MON.counter("executor.cache_hit").inc()
        cache_hit = compiled is not None
        if compiled is None:
            # the whole miss path is one span: what a cold call pays
            # before its first dispatch (verify, plan, the step builder
            # and the bookkeeping between them)
            with _MON.span("executor.prepare", program=program._uuid[:8]):
                mesh_platform = (
                    mesh.devices.flat[0].platform if mesh is not None else device.platform
                )
                # Static analysis ahead of lowering (FLAGS_verify_program):
                # once per compile-cache miss, so steady state pays nothing.
                # A malformed program raises a classified error naming the
                # op/var/block here instead of dying inside JAX tracing.
                from ..flags import flag as _flagv

                verify_level = _flagv("FLAGS_verify_program")
                if verify_level not in ("", "off"):
                    from .analysis import check_program

                    with _MON.span("analysis.verify", program=program._uuid[:8]):
                        check_program(program, level=verify_level,
                                      feed_names=list(jfeeds),
                                      fetch_names=fetch_names)
                if mesh is None:
                    # Static OOM pre-check (FLAGS_resource_precheck): the
                    # liveness plan predicts peak HBM for THIS (program, feed
                    # shapes) pair and raises classified ResourceError naming
                    # the watermark ops when it exceeds
                    # FLAGS_resource_hbm_limit_mb — before the trace/compile
                    # below allocates anything.  Mesh runs skip it: per-device
                    # residency depends on sharding, which the single-device
                    # plan would overstate.
                    from .resource_plan import precheck_program

                    with _MON.span("analysis.plan", program=program._uuid[:8]):
                        precheck_program(
                            program,
                            {n: np.shape(v) for n, v in jfeeds.items()},
                            fetch_names, steps=steps)
                with _MON.span("executor.build", program=program._uuid[:8]) as building:
                    compiled = _CompiledStep(
                        program, list(jfeeds), fetch_names, scope,
                        mesh=mesh, batch_axis=batch_axis,
                        feed_shapes={n: v.shape for n, v in jfeeds.items()},
                        n_steps=steps, remat=remat, platform=mesh_platform,
                        local_sgd=bool(local_sgd_every),
                        grad_overlap=grad_overlap,
                    )
                    building.annotate(module=compiled.module)
                with self._cache_lock:
                    existing = self._cache.get(cache_key)
                    if existing is not None:
                        # a racing thread built this signature while we did:
                        # adopt its entry so the signature keeps ONE
                        # _CompiledStep (its _build_lock then keeps XLA
                        # compiles single too); our duplicate build was cheap
                        # (no trace/compile happens until _dispatch)
                        compiled = existing
                        cache_hit = True
                        _MON.counter("executor.cache_hit").inc()
                    else:
                        _MON.counter("executor.cache_miss").inc()
                        self._cache[cache_key] = compiled
                        if len(self._cache) > _flagv("FLAGS_executor_cache_capacity"):  # LRU evict
                            self._cache.pop(next(iter(self._cache)))

        # one tail for both modes; mon_on guards only the records and the
        # block to completion, so the disabled fast path stays branch-only
        # (a few NULL_SPAN entries, no blocking, no records) while the
        # monitored per-phase breakdown cannot diverge from it.
        # Monitored, the spans of one synchronous run nest as
        #   executor.run > executor.execute > executor.dispatch
        #                                     > executor.feed_place
        #                                     > executor.enqueue
        #                                       > executor.lower, .compile
        #                > executor.fetch
        # and `run_async` opens `executor.dispatch` alone.
        mon_on = _MON.enabled
        u8 = program._uuid[:8]
        what = dict(program=u8, module=compiled.module)
        feed_bytes = 0
        if mon_on:
            feed_bytes = int(sum(getattr(v, "nbytes", 0) for v in jfeeds.values()))
            # dispatch-attempt census BEFORE the (possibly collective-
            # blocking) dispatch: the heartbeat's beat payload reads this,
            # and it is what makes a slow-but-alive rank's lag visible
            # while its peers sit blocked inside the collective
            _MON.counter("executor.steps_started").inc()
            ts_dispatch = time.time()
            t_run0 = time.perf_counter()

        def dispatch():
            with _MON.span("executor.dispatch", **what):
                with _MON.span("executor.feed_place", program=u8, bytes=feed_bytes):
                    placed = self._place_feeds(compiled, jfeeds, scope, device)
                # dispatch is watchdog-guarded: on backends whose dispatch
                # blocks (CPU/gloo cross-process collectives), a dead peer
                # wedges the enqueue itself — the guard turns that into
                # PeerFailureError.  With the health layer off (every
                # single-process run) this is a direct call behind one
                # None-check.
                with _MON.span("executor.enqueue", **what):
                    fetches, new_key = _guard_blocking(
                        lambda: compiled(scope, placed, key), what="executor.dispatch")
                scope.set_var(RNG_STATE_VAR, new_key)
            return fetches, new_key

        def record(**phases):
            # dispatch = what `run_async` pays on the critical path: feed
            # placement and the enqueue, less any build
            rec = {
                "program": u8,
                "module": compiled.module,
                "steps": steps,
                "cache_hit": cache_hit,
                "recompiled": compiled.last_recompiled,
                "cache_hits_total": _MON.counter("executor.cache_hit").value,
                "cache_misses_total": _MON.counter("executor.cache_miss").value,
                "recompiles_total": _MON.counter("executor.recompile").value,
                "t_lower_s": compiled.last_lower_s if compiled.last_recompiled else 0.0,
                "t_compile_s": compiled.last_compile_s if compiled.last_recompiled else 0.0,
                **phases,
                "ts_dispatch": ts_dispatch,
                "feed_bytes": feed_bytes,
            }
            if compiled.csig is not None:
                rec["csig"] = compiled.csig
            _MON.record_step(rec)

        if async_mode:
            fetches, new_key = dispatch()
            if mon_on:
                record(**{"async": True,
                          "t_dispatch_s": time.perf_counter() - t_run0 - compiled.last_build_s})
            pending = _PendingFetches(fetch_names, fetches, new_key, u8)
            return [FetchHandle(pending, i, n)
                    for i, n in enumerate(fetch_names)]

        def _fetch_out():
            # the NaN guard's np.asarray is itself the blocking copy, so
            # it lives inside the watchdog guard with the fetch
            self._check_nan_inf(fetch_names, fetches)
            return ([np.asarray(f) for f in fetches] if return_numpy
                    else list(fetches))

        with _MON.span("executor.run", **what):
            with _MON.span("executor.execute", **what):
                fetches, _ = dispatch()
                if mon_on:
                    t_dispatch = time.perf_counter() - t_run0 - compiled.last_build_s
                    # execute additionally blocks to completion so device
                    # compute isn't attributed to the fetch copy
                    _guard_blocking(lambda: jax.block_until_ready(fetches),
                                    what="executor.execute")
                    t_execute = time.perf_counter() - t_run0 - compiled.last_build_s
            if mon_on:
                t_f0 = time.perf_counter()
            with _MON.span("executor.fetch", program=u8):
                out = _guard_blocking(_fetch_out, what="executor.fetch")
        if mon_on:
            t_end = time.perf_counter()
            _MON.gauge("executor.last_step_s").set(t_execute)
            record(t_dispatch_s=t_dispatch, t_execute_s=t_execute,
                   t_fetch_s=t_end - t_f0, t_total_s=t_end - t_run0)
        return out

    def _place_feeds(self, compiled: _CompiledStep, jfeeds: dict, scope: Scope,
                     device) -> dict:
        """The feeds where the step wants them, and host-resident state
        moved to the device: the `executor.feed_place` span."""
        if compiled.mesh is None:
            # Single-device: pin feeds and any host-resident state.
            jfeeds = {
                n: v if isinstance(v, jax.Array) else jax.device_put(jnp.asarray(v), device)
                for n, v in jfeeds.items()
            }
            for n in compiled.state_in_names:
                v = scope.find_var(n)
                if not isinstance(v, jax.Array):
                    # owned copy, NOT device_put: on CPU, device_put can
                    # alias the numpy buffer zero-copy, and rw state is
                    # DONATED — XLA reusing/freeing memory the caller
                    # (checkpoint snapshot, resilience restore) still
                    # references corrupts it in place
                    with jax.default_device(device):
                        scope.set_var(n, jnp.array(v, copy=True))
            return jfeeds
        if compiled.multiprocess:
            # Cross-process mesh: every process contributes its LOCAL slice
            # of batch-sharded feeds (reference: per-trainer data shards in
            # NCCL2 mode); replicated feeds pass the full array everywhere.
            return {
                n: v if isinstance(v, jax.Array)
                else jax.make_array_from_process_local_data(
                    compiled.feed_specs[n], np.asarray(v))
                for n, v in jfeeds.items()
            }
        # SPMD: shard feeds up front; jit's in_shardings places state.
        return {
            n: v if isinstance(v, jax.Array) and v.sharding == compiled.feed_specs[n]
            else jax.device_put(v, compiled.feed_specs[n])
            for n, v in jfeeds.items()
        }

    @staticmethod
    def _check_nan_inf(fetch_names, fetches):
        from ..errors import NumericError
        from ..flags import flag as _flag

        if not _flag("FLAGS_check_nan_inf"):
            return
        for name, val in zip(fetch_names, fetches):
            arr = np.asarray(val)
            if np.issubdtype(arr.dtype, np.floating) and not np.isfinite(arr).all():
                # NumericError subclasses RuntimeError, so legacy callers
                # catching the guard's historical type keep working
                raise NumericError(
                    f"FLAGS_check_nan_inf: fetch {name!r} contains "
                    f"NaN/Inf (reference CheckTensorNANOrInf)")

    def train_from_dataset(self, program=None, dataset=None, scope=None,
                           thread=0, debug=False, fetch_list=None,
                           fetch_info=None, print_period=100):
        """reference executor.py:892 train_from_dataset — file-list-driven
        training loop over a Dataset (paddle_tpu/dataset.py)."""
        from ..dataset import train_from_dataset as _tfd

        return _tfd(self, program if program is not None else default_main_program(),
                    dataset, scope=scope, fetch_list=fetch_list,
                    fetch_info=fetch_info, print_period=print_period)

    def infer_from_dataset(self, program=None, dataset=None, scope=None, **kw):
        return self.train_from_dataset(program, dataset, scope, **kw)
