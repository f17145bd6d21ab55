"""Monitor core: span tracer + counter/gauge registry + step records.

Reference lineage: the C++ profiler's RecordEvent/EventList
(platform/profiler.cc) was a *profiling mode* — pay-when-on, nothing when
off, nothing queryable in between.  This subsystem is the always-available
replacement the perf rounds asked for (r5 review): every layer of the
framework reports spans and counters into one process-global `Monitor`,
and exporters (exporters.py) render the same state as a Prometheus text
page, a JSON snapshot, a Chrome trace, or an appended JSONL stream.

Disabled-mode contract (the hot-path budget): `span()` is one attribute
load + branch returning a shared singleton (no allocation), `Counter.inc`
/ `Gauge.set` are one branch.  Tests pin this (tests/test_monitor.py).

Flight recorder (ISSUE 8): alongside the capped buffers, the monitor
keeps a small bounded ring of the most RECENT step records and span
events.  `arm_flight_recorder(path, rank)` names a `BLACKBOX.p<rank>.json`
destination; `dump_blackbox(reason)` writes the ring plus the live
counter/gauge state there atomically (tmp + fsync + rename, so a SIGKILL
half-write can never pass for a black box).  The first dump wins — a
watchdog expiry that cascades into a crash keeps the watchdog's
attribution.  Ring appends ride the locks the buffers already take, so
the always-on recorder adds two deque appends to the hot path
(tests/test_telemetry_plane.py bounds the cost).
"""
from __future__ import annotations

import itertools
import json
import os
import threading
import time
from collections import deque
from typing import Any, Callable, Dict, List, Optional

import jax.monitoring
from jax.profiler import TraceAnnotation

from ..core.locks import named_lock

# Cap on buffered trace events / step records so an always-on monitor in a
# long-running trainer cannot grow without bound (same role as the old
# profiler's _EVENT_CAP).
EVENT_CAP = 200_000
STEP_CAP = 50_000
# Flight-recorder ring depth: the "last N steps before it died" a crash
# black box carries (per record class: step records and span events).
FLIGHT_RECORDER_CAP = 256
# Request-flight trace ring (ISSUE 16): the newest N closed per-request
# span trees (serving/tracing.py) kept live for the Chrome-trace request
# lanes and `tools/serve_trace.py` — same bounded-ring discipline as the
# flight recorder, appends riding the registry lock.
TRACE_RING_CAP = 1024
# Slow/bad-request exemplar ring: full traces of deadline misses, sheds,
# and errors, retained past the trace ring's churn so a post-mortem black
# box still carries the episodes that actually burned the SLO.
EXEMPLAR_CAP = 64
# The identifiers the spans of one piece of work share (choosing-metrics
# guide, section 4): a span that does not set one takes its parent's, so
# `executor.enqueue` under `pipeline.dispatch(step=7)` is a span of step 7
# and a reader cuts a window by step without walking the tree.
SHARED_IDS = ("step", "batch", "trace_id")
# What the monitor hears from `jax.monitoring` (this JAX: 0.9.0), each a
# duration JAX reports when the work ENDS, recorded back-dated under the
# span open on that thread: tracing a function to a jaxpr, lowering the
# jaxpr to StableHLO, the backend's compile (XLA, or a load from the
# persistent cache) and, inside that, the load from the cache alone.
JAX_DURATIONS = {
    "/jax/core/compile/jaxpr_trace_duration": "jax.trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "jax.lower",
    "/jax/core/compile/backend_compile_duration": "jax.backend_compile",
    "/jax/compilation_cache/cache_retrieval_time_sec": "jax.cache_load",
}
# The one event JAX fires when its persistent cache served a compile.
# (`/jax/compilation_cache/cache_misses` fires only when an entry is
# WRITTEN, which the minimum-compile-time rule suppresses: a compile
# without the hit event is the miss.)
JAX_CACHE_HIT = "/jax/compilation_cache/cache_hits"


class _NullSpan:
    """Shared do-nothing span returned while the monitor is disabled."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def annotate(self, **kw):
        return self


NULL_SPAN = _NullSpan()


class Span:
    """Timed region, on two timelines at once.  Its event in the monitor's
    buffer carries an id and its parent's id (the per-thread stack of open
    spans; 0 is "no parent"), the tid and the nesting depth, so the
    Chrome-trace exporter renders children inside their parents and a
    reader computes self time.  While it is open it is also a
    `jax.profiler.TraceAnnotation` of the same name and arguments: an event
    on the `/host:CPU` plane of a profiler trace, on the thread that did
    the work and on the clock of the device's `XLA Ops`.  With no profiler
    session on, the annotation is a no-op in C++."""

    __slots__ = ("mon", "name", "args", "t0", "ts", "id", "parent", "_note")

    def __init__(self, mon: "Monitor", name: str, args: Optional[dict]):
        self.mon = mon
        self.name = name
        self.args = args
        self.t0 = 0.0
        self.ts = 0.0
        self.id = 0
        self.parent = 0
        self._note = None

    def annotate(self, **kw):
        """Arguments learned while the span is open land in the monitor's
        event; the profiler's annotation keeps those it was opened with."""
        if self.args is None:
            self.args = dict(kw)
        else:
            self.args.update(kw)
        return self

    def __enter__(self):
        stack = self.mon._stack()
        if stack:
            above = stack[-1]
            self.parent = above.id
            if above.args:
                shared = {k: above.args[k] for k in SHARED_IDS
                          if k in above.args
                          and not (self.args and k in self.args)}
                if shared:
                    self.annotate(**shared)
        self.id = next(self.mon._span_ids)
        stack.append(self)
        self._note = TraceAnnotation(self.name, **(self.args or {}))
        self.ts = time.time()
        self.t0 = time.perf_counter()
        self._note.__enter__()
        return self

    def __exit__(self, *exc):
        self._note.__exit__(*exc)
        dur = time.perf_counter() - self.t0
        stack = self.mon._stack()
        stack.pop()
        self.mon._record(self.name, self.ts, dur, len(stack), self.args,
                         self.id, self.parent)
        return False


class Counter:
    """Monotonic counter.  `inc` is one branch when disabled; enabled it
    takes a per-counter lock — `value += n` alone is a LOAD/STORE pair a
    GIL switch can split, losing increments under concurrent producers."""

    __slots__ = ("mon", "name", "value", "_lock")

    def __init__(self, mon: "Monitor", name: str):
        self.mon = mon
        self.name = name
        self.value = 0
        # telemetry=False on every monitor-internal lock: lock telemetry
        # records through Counter.inc, so instrumenting the lock inc
        # itself takes would recurse/deadlock
        self._lock = named_lock("monitor.counter", rank=68, telemetry=False)

    def inc(self, n: int = 1):
        if self.mon.enabled:
            with self._lock:
                self.value += n
        return self


class Gauge:
    """Point-in-time value: either `set()` explicitly or `set_fn()` a
    callable evaluated lazily at read/export time (how the HBM/live-array
    gauges avoid walking `jax.live_arrays()` on the hot path)."""

    __slots__ = ("mon", "name", "value", "fn")

    def __init__(self, mon: "Monitor", name: str):
        self.mon = mon
        self.name = name
        self.value = 0.0
        self.fn: Optional[Callable[[], float]] = None

    def set(self, v: float):
        if self.mon.enabled:
            self.value = v
        return self

    def set_fn(self, fn: Callable[[], float]):
        self.fn = fn
        return self

    def read(self) -> float:
        if self.fn is not None:
            try:
                return float(self.fn())
            except Exception:
                return float("nan")
        return float(self.value)


class Monitor:
    """Process-global telemetry sink (one instance per process; see
    monitor/__init__.py for the singleton + module-level API)."""

    def __init__(self):
        self.enabled = False
        # (time.time(), time.perf_counter()) at the first enable() since
        # the last reset(): the two clocks a Span reads, so that a reader
        # places the events (time.time) against a perf_counter stamp of
        # its own without guessing the offset
        self.enabled_at: Optional[tuple] = None
        self._jax_listening = False
        self._lock = named_lock("monitor.registry", rank=64, telemetry=False)
        self._tls = threading.local()
        self._span_ids = itertools.count(1)  # next() is atomic under the GIL
        # span aggregates: name -> [calls, total_s, max_s, min_s]
        self._agg: Dict[str, list] = {}
        # raw events for trace export:
        # (name, ts_s, dur_s, tid, depth, args, span id, parent's span id)
        self._events: List[tuple] = []
        self._counters: Dict[str, Counter] = {}
        self._gauges: Dict[str, Gauge] = {}
        self._steps: List[dict] = []
        self._loggers: List[Any] = []
        # flight recorder: bounded rings of the NEWEST records (the capped
        # buffers above keep the oldest), dumped as a black box on crash
        self._bb_steps: deque = deque(maxlen=FLIGHT_RECORDER_CAP)
        self._bb_events: deque = deque(maxlen=FLIGHT_RECORDER_CAP)
        # request-flight traces (ISSUE 16): newest-N closed span trees,
        # plus the slow/bad exemplars the black box keeps past ring churn
        self._traces: deque = deque(maxlen=TRACE_RING_CAP)
        self._exemplars: deque = deque(maxlen=EXEMPLAR_CAP)
        self._bb_path: Optional[str] = None
        self._bb_rank = 0
        self._bb_dumped: Optional[str] = None
        # dump latch lock — NOT self._lock: blackbox_snapshot takes that
        # one, and the latch must stay held across snapshot + write
        self._bb_dump_lock = named_lock("monitor.blackbox", rank=60,
                                        telemetry=False)
        # per-device/trainer lane for merged multi-process traces
        self.lane = 0
        self.lane_name = "paddle_tpu"
        # steps/sec EMA state has its own lock: record_step also needs the
        # registry lock, and nesting the two would invite deadlock
        self._rate_lock = named_lock("monitor.rate", rank=62, telemetry=False)
        self._last_step_t: Optional[float] = None
        self._steps_per_sec_ema = 0.0

    # -- lifecycle ---------------------------------------------------------
    def enable(self):
        if self.enabled_at is None:
            self.enabled_at = (time.time(), time.perf_counter())
        if not self._jax_listening:
            # JAX offers no unregister: the listeners stay for the life of
            # the process and are one comparison an event while disabled
            self._jax_listening = True
            jax.monitoring.register_event_listener(self._on_jax_event)
            jax.monitoring.register_event_duration_secs_listener(
                self._on_jax_duration)
        self.enabled = True
        return self

    def disable(self):
        self.enabled = False
        return self

    def reset(self):
        with self._lock:
            self._agg.clear()
            self._events.clear()
            self._steps.clear()
            self._bb_steps.clear()
            self._bb_events.clear()
            self._traces.clear()
            self._exemplars.clear()
            # a reset starts a fresh run: the one-shot dump latch re-opens
            # (the armed path survives — re-arm to change it)
            self._bb_dumped = None
            self.enabled_at = ((time.time(), time.perf_counter())
                               if self.enabled else None)
            for c in self._counters.values():
                c.value = 0
            for g in self._gauges.values():
                if g.fn is None:
                    g.value = 0.0
            self._last_step_t = None
            self._steps_per_sec_ema = 0.0
        return self

    def set_lane(self, lane: int, name: Optional[str] = None):
        """Assign this process a trace lane (pid in Chrome-trace terms) so
        merged multi-trainer traces show one lane per device/worker."""
        self.lane = int(lane)
        if name is not None:
            self.lane_name = str(name)
        return self

    # -- spans -------------------------------------------------------------
    def span(self, name: str, **args):
        if not self.enabled:
            return NULL_SPAN
        return Span(self, name, args or None)

    def _stack(self) -> list:
        """This thread's open spans, outermost first."""
        tls = self._tls
        try:
            return tls.stack
        except AttributeError:
            tls.stack = []
            return tls.stack

    def observe(self, name: str, seconds: float, ts: Optional[float] = None,
                **args):
        """Record a duration that was measured elsewhere (the profiler
        facade's record_run, a bucket's occupancy): an event in the
        monitor's buffer, back-dated, under the span open on this thread.
        It was not open while the work ran, so it does not enter a
        profiler trace; a region of this program's own is a `span()`."""
        if not self.enabled:
            return
        stack = self._stack()
        self._record(name, ts if ts is not None else time.time() - seconds,
                     seconds, len(stack), args or None, next(self._span_ids),
                     stack[-1].id if stack else 0)

    # -- what JAX reports ----------------------------------------------------
    def _on_jax_event(self, event: str, **_):
        if not self.enabled:
            return
        if event == JAX_CACHE_HIT:
            tls = self._tls
            tls.jax_cache_hits = getattr(tls, "jax_cache_hits", 0) + 1

    def _on_jax_duration(self, event: str, seconds: float, **kw):
        if not self.enabled:
            return
        name = JAX_DURATIONS.get(event)
        if name is None:
            return
        tls, stack = self._tls, self._stack()
        now = time.time()
        sid = next(self._span_ids)
        parent = stack[-1].id if stack else 0
        if name == "jax.cache_load":
            # the backend-compile event that wraps this load fires after
            # it: its id is drawn now, so that the load is its child
            tls.jax_compile_id = parent = next(self._span_ids)
            tls.jax_load_at = now
        elif name == "jax.backend_compile":
            if now - seconds <= getattr(tls, "jax_load_at", -1.0):
                sid = tls.jax_compile_id
            tls.jax_load_at = -1.0
        # the work is over, so in a profiler trace the event can only be a
        # marker where it ENDED (its seconds among the stats); the monitor's
        # own event is back-dated to where it ran
        with TraceAnnotation(name, seconds=seconds, **kw):
            pass
        self._record(name, now - seconds, seconds,
                     len(stack) + (name == "jax.cache_load"), kw or None,
                     sid, parent)

    def jax_cache_hits(self) -> int:
        """Compiles on THIS thread that JAX's persistent cache served while
        the monitor was on (a compile on another thread is not taken for
        this one's)."""
        return getattr(self._tls, "jax_cache_hits", 0)

    def _record(self, name, ts, dur, depth, args, sid, parent):
        tid = threading.get_ident() & 0xFFFF
        with self._lock:
            a = self._agg.get(name)
            if a is None:
                self._agg[name] = [1, dur, dur, dur]
            else:
                a[0] += 1
                a[1] += dur
                if dur > a[2]:
                    a[2] = dur
                if dur < a[3]:
                    a[3] = dur
            event = (name, ts, dur, tid, depth, args, sid, parent)
            if len(self._events) < EVENT_CAP:
                self._events.append(event)
            self._bb_events.append(event)

    def span_stats(self) -> Dict[str, dict]:
        with self._lock:
            return {n: {"calls": a[0], "total_s": a[1], "max_s": a[2],
                        "min_s": a[3]}
                    for n, a in self._agg.items()}

    def events(self) -> List[tuple]:
        with self._lock:
            return list(self._events)

    # -- counters / gauges -------------------------------------------------
    def counter(self, name: str) -> Counter:
        c = self._counters.get(name)
        if c is None:
            with self._lock:
                c = self._counters.setdefault(name, Counter(self, name))
        return c

    def gauge(self, name: str) -> Gauge:
        g = self._gauges.get(name)
        if g is None:
            with self._lock:
                g = self._gauges.setdefault(name, Gauge(self, name))
        return g

    def counter_values(self) -> Dict[str, int]:
        return {n: c.value for n, c in sorted(self._counters.items())}

    def gauge_values(self) -> Dict[str, float]:
        return {n: g.read() for n, g in sorted(self._gauges.items())}

    # -- step records ------------------------------------------------------
    def record_step(self, record: dict):
        """Append one per-`run()` record (executor step breakdown) and fan
        it out to attached loggers.  Only `kind="step"` records (the
        executor's own) advance the executor.steps counter and steps/sec
        EMA — auxiliary kinds (pipeline_step, ...) describe the SAME
        training step from another layer and must not double-count it."""
        if not self.enabled:
            return
        record = dict(record)
        record.setdefault("kind", "step")
        record.setdefault("ts", time.time())
        is_exec_step = record["kind"] == "step"
        steps_counter = self.counter("executor.steps")  # before _lock: counter() locks too
        if is_exec_step:
            rate_gauge = self.gauge("executor.steps_per_sec_ema")
            now = time.perf_counter()
            with self._rate_lock:
                if self._last_step_t is not None:
                    dt = now - self._last_step_t
                    if dt > 0:
                        inst = 1.0 / dt
                        ema = self._steps_per_sec_ema
                        self._steps_per_sec_ema = inst if ema == 0.0 else 0.9 * ema + 0.1 * inst
                        rate_gauge.set(self._steps_per_sec_ema)
                self._last_step_t = now
        record.setdefault("lane", self.lane)
        record["step"] = steps_counter.value
        with self._lock:
            if len(self._steps) < STEP_CAP:
                self._steps.append(record)
            self._bb_steps.append(record)
        if is_exec_step:
            steps_counter.inc()
        for lg in list(self._loggers):
            try:
                lg.on_step(record)
            except Exception:
                pass

    def step_records(self) -> List[dict]:
        with self._lock:
            return list(self._steps)

    # -- request-flight traces (ISSUE 16) ----------------------------------
    def record_trace(self, record: dict):
        """Append one CLOSED per-request span tree (a `serving_trace`
        record from serving/tracing.py) to the bounded trace ring, and
        fan it through `record_step` so it rides the JSONL stream, the
        step buffer, and the flight-recorder ring like every other
        record kind.  One branch when disabled."""
        if not self.enabled:
            return
        record = dict(record)
        record.setdefault("kind", "serving_trace")
        with self._lock:
            self._traces.append(record)
        self.record_step(record)

    def request_traces(self) -> List[dict]:
        """The newest TRACE_RING_CAP closed request traces (exporters
        render them as Chrome-trace request lanes)."""
        with self._lock:
            return list(self._traces)

    def record_exemplar(self, record: dict):
        """Retain a slow/bad-request trace (deadline miss, shed, error,
        rejected publish) in the exemplar ring the black box carries —
        these must survive the trace ring's churn so a post-mortem still
        shows the episodes that burned the SLO."""
        if not self.enabled:
            return
        with self._lock:
            self._exemplars.append(dict(record))

    def exemplars(self) -> List[dict]:
        with self._lock:
            return list(self._exemplars)

    # -- flight recorder ---------------------------------------------------
    def arm_flight_recorder(self, path: str, rank: int = 0) -> "Monitor":
        """Name the black-box destination (`BLACKBOX.p<rank>.json` under a
        gang's telemetry dir).  Arming does not enable the monitor — the
        telemetry plane (exporters.init_worker_telemetry) does both."""
        self._bb_path = str(path)
        self._bb_rank = int(rank)
        return self

    def flight_recorder_path(self) -> Optional[str]:
        return self._bb_path

    def blackbox_snapshot(self, reason: str = "manual") -> dict:
        """The flight-recorder ring rendered as one JSON-able document:
        the last FLIGHT_RECORDER_CAP step records and span events plus the
        live counter/gauge state — what the gang was doing right before it
        died."""
        with self._lock:
            steps = list(self._bb_steps)
            exemplars = list(self._exemplars)
            events = [
                {"name": n, "ts": ts, "dur_s": dur, "tid": tid,
                 "depth": depth, "id": sid, "parent": parent,
                 "args": ({k: str(v) for k, v in args.items()}
                          if args else None)}
                for (n, ts, dur, tid, depth, args, sid, parent)
                in self._bb_events
            ]
        try:
            gauges = self.gauge_values()
        except Exception:
            gauges = {}
        return {"kind": "blackbox", "reason": str(reason),
                "rank": self._bb_rank, "pid": os.getpid(),
                "ts": time.time(), "lane": self.lane,
                "lane_name": self.lane_name, "steps": steps,
                "events": events, "exemplars": exemplars,
                "counters": self.counter_values(),
                "gauges": gauges}

    def dump_blackbox(self, reason: str = "manual",
                      path: Optional[str] = None) -> Optional[str]:
        """Write the black box atomically (tmp + fsync + rename) and return
        its path; no-op (None) when unarmed.  The FIRST dump wins: a
        watchdog expiry that cascades into a crash/exit keeps the
        watchdog's attribution instead of being overwritten by the
        secondary failure.  The latch is lock-held across snapshot+write:
        a watchdog-thread dump racing a crash-hook dump must not both
        pass the check and overwrite each other.  Never raises — this
        runs on crash paths."""
        with self._bb_dump_lock:  # lock-ok: one-shot crash latch — the first-dump-wins guarantee REQUIRES holding it across snapshot+write; contention only exists while the process is already dying
            if self._bb_dumped is not None:
                return self._bb_dumped
            p = path or self._bb_path
            if p is None:
                return None
            try:
                snap = self.blackbox_snapshot(reason)
                tmp = f"{p}.tmp{os.getpid()}"
                with open(tmp, "w") as f:
                    json.dump(snap, f, default=str)
                    f.flush()
                    os.fsync(f.fileno())  # to disk before a SIGKILL lands
                os.replace(tmp, p)
                self._bb_dumped = p
                return p
            except Exception:
                return None

    # -- loggers -----------------------------------------------------------
    def attach_logger(self, logger):
        self._loggers.append(logger)
        return logger

    def detach_logger(self, logger):
        if logger in self._loggers:
            self._loggers.remove(logger)
        close = getattr(logger, "close", None)
        if callable(close):
            close()
