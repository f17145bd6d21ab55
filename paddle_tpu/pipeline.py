"""Overlapped training driver: feed staging, device compute, and fetch
run concurrently, K steps deep.

Reference counterparts: `operators/reader/buffered_reader.cc` (the async
`cudaMemcpyAsync` double-buffer) hid H2D latency, and the
ParallelExecutor's dependency-driven op scheduling overlapped compute
with transfer.  The TPU-native equivalent composes three existing
pieces:

  * `DataLoader` stages batches onto the device in its producer thread
    (H2D off the critical path, `capacity` batches deep);
  * `Executor.run_async` enqueues a step and returns lazy `FetchHandle`s
    immediately — JAX's async dispatch keeps the device busy while
    Python prepares and dispatches the NEXT step;
  * `train_loop` below bounds how many dispatched-but-unresolved steps
    may be in flight (donated-buffer pressure on HBM grows with depth)
    and only materializes fetches on logging steps — non-logging steps
    `wait()` for execution without paying the device->host copy.

Monitor integration: `pipeline.inflight` gauge; the loop's three
boundaries as spans that carry their `step`: `pipeline.next_batch` (the
pull from the loader), `pipeline.dispatch` (`exe.run_async`; the
`on_dispatch` hook stays outside it) and `pipeline.host_blocked` (time
the host spent waiting on the device — the overlap-win metric); and one
`kind="pipeline_step"` record per drained step with that step's wall
time and what the host spent in each of the three, which
`tools/perf_report.py` turns into a host-blocked fraction (and can gate
on via `--check --max-host-blocked-frac`).  An op may declare statistics of
the step (`core.registry.set_step_stats`): their variables ride along as
fetches of the step and are read on LOGGED steps only, when the loss is (no
sync of their own), and the op publishes them.  `moe_experts` does
(ops/moe_ops.py): every layer's tokens per expert and dropped-token count
become the gauges `moe.load_max_over_mean`, `moe.load_min_over_mean`,
`moe.dropped_tokens` and one `kind="moe_routing"` record per logged step.
"""
from __future__ import annotations

import time
from collections import deque
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, Iterable, List, Optional, Sequence, Tuple

import numpy as np

from . import errors as _errors
from .core.registry import get_op_def_or_none
from .monitor import MONITOR as _MON


@dataclass
class PipelineStats:
    """What `train_loop` hands back: per-logged-step fetch values plus the
    overlap accounting tools/perf_report.py's gates read."""

    steps: int = 0
    logged: List[Tuple[int, List[np.ndarray]]] = field(default_factory=list)
    wall_s: float = 0.0
    host_blocked_s: float = 0.0
    max_inflight_seen: int = 0

    @property
    def host_blocked_frac(self) -> float:
        """Fraction of wall time the host spent blocked on the device
        (resolving or waiting on handles).  A serial exe.run loop sits
        near 1.0 whenever the device step dominates; the pipelined loop's
        win is exactly how far below that it lands."""
        return self.host_blocked_s / self.wall_s if self.wall_s > 0 else 0.0


def _step_stats(program):
    """[(publish, {slot: [variable name per op]}, {attribute: [value per op]})]
    for every op type of the program's main block that declares step
    statistics (`core.registry.set_step_stats`); empty for most programs."""
    program = getattr(program, "program", program)  # a CompiledProgram wraps one
    found, constants = {}, {}
    for op in program.global_block().ops:
        op_def = get_op_def_or_none(op.type)
        if op_def is None or op_def.step_stats is None:
            continue
        slots, publish, attrs = op_def.step_stats
        # op types that share a publish (a layer's router and its experts) make one record
        names = found.setdefault(publish, {})
        for slot in slots:
            name = op.inputs.get(slot) or op.outputs.get(slot)
            if name:
                names.setdefault(slot, []).append(name[0])
        for attr in attrs:
            if attr in op.attrs:
                constants.setdefault(publish, {}).setdefault(attr, []).append(op.attrs[attr])
    # a slot no op has (a layer that holds every expert has no `Held`) is not fetched
    return [(publish, names, constants.get(publish, {})) for publish, names in found.items() if names]


def train_loop(
    exe,
    program,
    loader: Iterable,
    fetch_list: Sequence,
    scope=None,
    max_inflight: int = 2,
    log_period: int = 1,
    on_logged: Optional[Callable[[int, List[np.ndarray]], Any]] = None,
    max_steps: Optional[int] = None,
    step_offset: int = 0,
    on_dispatch: Optional[Callable[[int, Dict], Any]] = None,
    resolve_all: bool = False,
) -> PipelineStats:
    """Drive a training program over `loader` with up to `max_inflight`
    steps dispatched ahead of resolution.

        loader = fluid.DataLoader.from_generator([x, y], capacity=4) \\
                      .set_batch_generator(gen)
        stats = train_loop(exe, main, loader, [loss], scope=scope,
                           max_inflight=3, log_period=10)

    `loader` yields feed dicts (a `DataLoader` places them on device in
    its producer thread; plain numpy dicts also work).  Step N+1 is
    dispatched BEFORE step N's handles resolve; state write-back and RNG
    threading stay correct because the scope holds each step's output
    buffers, not the handles.  Every `log_period`-th step (step 0, then
    log_period, ...) is resolved to numpy and collected in
    `stats.logged` (or passed to `on_logged(step, values)`); other steps
    only `wait()` for device completion, skipping the host copy
    entirely.  `max_inflight` bounds donated-buffer pressure so deep
    pipelines cannot OOM HBM.

    Note the skip trade-off: the FLAGS_check_nan_inf guard runs at
    resolution, so non-logged steps are not NaN-checked (a NaN in the
    params still surfaces at the next logged step's loss).  Passing
    `resolve_all=True` closes that window — every step pays the host
    copy + guard, which is what the resilience layer's NaN modes need to
    attribute a NaN to the exact step that produced it.

    Resilience hooks: `step_offset` shifts step numbering (logging phase,
    records, error context) so a restarted segment keeps GLOBAL step
    indices; `on_dispatch(step, feed)` runs just before each dispatch
    (snapshot/checkpoint/fault-injection point — an exception it raises
    aborts the loop like any other).  Whenever the loop exits abnormally,
    still-in-flight steps are waited on and discarded before the error
    propagates, so abandoned handles never keep device buffers pinned;
    errors raised while draining carry their step index
    (`errors.get_context`)."""
    if not fetch_list:
        raise ValueError("train_loop needs a non-empty fetch_list (the "
                         "handles are also the pipeline's backpressure)")
    if max_inflight < 1:
        raise ValueError(f"max_inflight must be >= 1, got {max_inflight}")
    if log_period < 1:
        raise ValueError(f"log_period must be >= 1, got {log_period}")

    stats = PipelineStats()
    n_user = len(fetch_list)
    step_stats = _step_stats(program)
    fetch_list = list(fetch_list) + [name for _, names, _ in step_stats
                                     for slot in names.values() for name in slot]
    # (step index, [FetchHandle, ...], seconds in next(it), in run_async)
    inflight: deque = deque()
    gauge = _MON.gauge("pipeline.inflight")
    t_wall0 = time.perf_counter()
    last_drain_t = t_wall0

    def drain_one():
        nonlocal last_drain_t
        step_i, handles, t_next_batch, t_dispatch = inflight.popleft()
        gauge.set(len(inflight))
        want_log = step_i % log_period == 0
        must_resolve = want_log or resolve_all
        t_b0 = time.perf_counter()
        with _MON.span("pipeline.host_blocked", step=step_i, logged=want_log):
            try:
                if must_resolve:
                    vals = [h.numpy() for h in handles]
                else:
                    handles[0].wait()  # all handles share one pending dispatch
            except BaseException as e:
                # a resolution failure (sticky NaN guard, XLA runtime
                # error) belongs to THIS step; recovery rewinds to it
                raise _errors.attach_context(e, step=step_i)
        now = time.perf_counter()
        stats.host_blocked_s += now - t_b0
        if _MON.enabled:
            # per-step wall gauge: what the heartbeat's telemetry payload
            # reports as this rank's step time when the async path (no
            # executor.execute timing) is driving
            _MON.gauge("pipeline.last_step_wall_s").set(now - last_drain_t)
            _MON.record_step({
                "kind": "pipeline_step",
                "pipeline_step": step_i,
                "t_next_batch_s": t_next_batch,
                "t_dispatch_s": t_dispatch,
                "t_host_blocked_s": now - t_b0,
                "t_step_wall_s": now - last_drain_t,
                "inflight": len(inflight),
                "logged": want_log,
            })
        last_drain_t = now
        if must_resolve and step_stats:
            if want_log and _MON.enabled:
                extra = iter(vals[n_user:])
                for publish, names, constants in step_stats:
                    publish(step_i, {**constants, **{slot: [next(extra) for _ in slot_names]
                                                     for slot, slot_names in names.items()}})
            vals = vals[:n_user]
        if want_log:
            if on_logged is not None:
                on_logged(step_i, vals)
            else:
                stats.logged.append((step_i, vals))

    it = iter(loader)
    try:
        while max_steps is None or stats.steps < max_steps:
            # bound checked BEFORE pulling: a shared/resumable loader must
            # not lose a batch the loop will never dispatch
            step_i = step_offset + stats.steps
            t_n0 = time.perf_counter()
            try:
                with _MON.span("pipeline.next_batch", step=step_i):
                    feed = next(it)
            except StopIteration:
                break
            t_next_batch = time.perf_counter() - t_n0
            while len(inflight) >= max_inflight:
                drain_one()
            try:
                if on_dispatch is not None:
                    on_dispatch(step_i, feed)
                t_d0 = time.perf_counter()
                with _MON.span("pipeline.dispatch", step=step_i):
                    handles = exe.run_async(program, feed=feed,
                                            fetch_list=fetch_list, scope=scope)
                t_dispatch = time.perf_counter() - t_d0
            except BaseException as e:
                # a synchronous dispatch failure (hook, compile/enqueue
                # path) belongs to this step — but OLDER steps still in
                # flight have unresolved guards (the sticky NaN
                # check).  Drain them FIRST: if one fails,
                # ITS error propagates and supersedes this one, because
                # recovery must rewind to the OLDEST failure — keying
                # recovery on the newer step would restore a snapshot
                # that already embeds the older step's unguarded update
                # and silently commit it.
                err = _errors.attach_context(e, step=step_i)
                while inflight:
                    drain_one()
                raise err
            inflight.append((step_i, handles, t_next_batch, t_dispatch))
            stats.steps += 1
            stats.max_inflight_seen = max(stats.max_inflight_seen,
                                          len(inflight))
            gauge.set(len(inflight))
        while inflight:
            drain_one()
    finally:
        # abnormal exit: the remaining in-flight handles would otherwise
        # be abandoned still pinning device buffers (donated inputs + a
        # whole batch each).  wait() for execution and discard — values
        # already landed in the scope at dispatch; resolution errors here
        # are secondary to the one propagating.
        while inflight:
            handles = inflight.popleft()[1]
            try:
                handles[0].wait()
            except Exception:
                pass
        gauge.set(0)
    stats.wall_s = time.perf_counter() - t_wall0
    return stats
