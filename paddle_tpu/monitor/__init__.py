"""paddle_tpu.monitor — the framework-wide observability subsystem.

Every layer reports into one process-global `Monitor`:

    from paddle_tpu import monitor

    monitor.enable()
    with monitor.span("compile", program=uuid):      # nested, thread-safe
        ...
    monitor.counter("executor.cache_miss").inc()
    monitor.gauge("reader.queue_depth").set(3)

    print(monitor.export_prometheus())               # text exposition
    monitor.export_json("snapshot.json")             # perf_report input
    monitor.export_chrome_trace("trace.json")        # chrome://tracing
    log = monitor.attach_logger(monitor.MonitorLogger("metrics.jsonl"))

Disabled (the default) every entry point is a branch: `span()` returns a
shared null singleton, `inc`/`set` are no-ops.  `paddle_tpu.profiler` is a
compatibility facade over this module.

Instrumented out of the box: `core/program.py`, `core/autodiff.py`,
`optimizer.py` (where a program is built: program.build / backward /
optimize / clone spans), `core/executor.py` (per-run step breakdown —
prepare / build / lower / compile / dispatch / execute / fetch spans,
cache-hit + recompile counters, steps/sec EMA), every JAX trace, lowering,
compile and cache load in the process (observed `jax.*` events under the
span they ran in), `pipeline.py` (the loop's next_batch /
dispatch / host_blocked spans, each with its step), `core/lowering.py`
(the op census), `reader.py` (the producer's stage span, queue depth /
wait), `serving/server.py` (the worker's batch_build / batch / split),
`fleet.py` + `dygraph/parallel.py` (worker lanes, collective bytes),
memstats gauges (live HBM bytes).  While enabled, every span is also a
`jax.profiler.TraceAnnotation`: inside a profiler session the program's
spans and the device's operations are one timeline.
See docs/observability.md.
"""
from __future__ import annotations

import time

from .core import (Counter, EXEMPLAR_CAP, FLIGHT_RECORDER_CAP,  # noqa: F401
                   Gauge, Monitor, NULL_SPAN, Span, TRACE_RING_CAP)
from . import exporters as _exp
from .exporters import (MonitorLogger, escape_label_value,  # noqa: F401
                        prometheus_text, summary_table)
from .memstats import register_memory_gauges

__all__ = [
    "Counter", "Gauge", "Monitor", "MonitorLogger", "Span", "NULL_SPAN",
    "FLIGHT_RECORDER_CAP", "TRACE_RING_CAP", "EXEMPLAR_CAP", "MONITOR",
    "get_monitor", "enable", "disable",
    "is_enabled", "reset", "span", "observe", "counter", "gauge",
    "record_step", "step_records", "record_trace", "record_fleet_event",
    "request_traces",
    "record_exemplar", "exemplars", "set_lane", "attach_logger",
    "detach_logger", "export_prometheus", "export_json", "json_snapshot",
    "export_chrome_trace", "merge_chrome_traces", "summary",
    "prometheus_text", "escape_label_value", "arm_flight_recorder",
    "dump_blackbox", "blackbox_snapshot", "init_worker_telemetry",
    "telemetry_dir", "register_memory_gauges",
]

MONITOR = Monitor()
register_memory_gauges(MONITOR)


def get_monitor() -> Monitor:
    return MONITOR


def enable():
    return MONITOR.enable()


def disable():
    return MONITOR.disable()


def is_enabled() -> bool:
    return MONITOR.enabled


def reset():
    return MONITOR.reset()


def span(name: str, **args):
    return MONITOR.span(name, **args)


def observe(name: str, seconds: float, **args):
    return MONITOR.observe(name, seconds, **args)


def counter(name: str) -> Counter:
    return MONITOR.counter(name)


def gauge(name: str) -> Gauge:
    return MONITOR.gauge(name)


def record_step(record: dict):
    return MONITOR.record_step(record)


def step_records():
    return MONITOR.step_records()


def record_trace(record: dict):
    """Append a closed per-request span tree (serving/tracing.py) to the
    bounded trace ring + the step/JSONL streams (ISSUE 16)."""
    return MONITOR.record_trace(record)


def record_fleet_event(action: str, **fields):
    """One serving-fleet lifecycle transition (replica_dead /
    replica_restarted / roll_started / roll_halted / roll_converged /
    ...) as a `kind="fleet_event"` step record plus a per-action
    counter — the stream `serve_trace --fleet` renders as roll episodes
    and `perf_report --check` gates for roll convergence (ISSUE 18)."""
    rec = {"kind": "fleet_event", "action": action, "ts": time.time(),
           **fields}
    MONITOR.counter(f"serving.fleet.events[{action}]").inc()
    MONITOR.record_step(rec)
    return rec


def request_traces():
    return MONITOR.request_traces()


def record_exemplar(record: dict):
    """Retain a slow/bad-request trace in the black box's exemplar ring."""
    return MONITOR.record_exemplar(record)


def exemplars():
    return MONITOR.exemplars()


def set_lane(lane: int, name=None):
    return MONITOR.set_lane(lane, name)


def attach_logger(logger):
    if isinstance(logger, MonitorLogger):
        logger.bind(MONITOR)
    return MONITOR.attach_logger(logger)


def detach_logger(logger):
    return MONITOR.detach_logger(logger)


def arm_flight_recorder(path: str, rank: int = 0) -> Monitor:
    """Name this process's black-box file (`BLACKBOX.p<rank>.json`); the
    bounded last-N ring of steps/spans is dumped there on crash, watchdog
    expiry, SIGTERM drain, and injected kills."""
    return MONITOR.arm_flight_recorder(path, rank)


def dump_blackbox(reason: str = "manual", path=None):
    """Atomically write the flight-recorder black box (first dump wins);
    returns its path, or None when unarmed."""
    return MONITOR.dump_blackbox(reason, path)


def blackbox_snapshot(reason: str = "manual") -> dict:
    return MONITOR.blackbox_snapshot(reason)


def init_worker_telemetry(telemetry_dir=None, rank=None, every: int = 1):
    """Arm this worker's end of the gang telemetry plane (rank-stamped
    JSONL stream + flight recorder + crash hook + exit-time Chrome trace);
    no-op outside a telemetry-armed gang.  See exporters.py."""
    return _exp.init_worker_telemetry(telemetry_dir, rank, MONITOR, every)


def telemetry_dir():
    return _exp.telemetry_dir()


def export_prometheus(labels=None) -> str:
    return prometheus_text(MONITOR, labels=labels)


def export_json(path: str, include_steps: bool = True) -> str:
    return _exp.export_json(MONITOR, path, include_steps)


def json_snapshot(include_steps: bool = True) -> dict:
    return _exp.json_snapshot(MONITOR, include_steps)


def export_chrome_trace(path: str, pid=None, process_name=None) -> int:
    return _exp.export_chrome_trace(MONITOR, pid=pid, path=path,
                                    process_name=process_name)


def merge_chrome_traces(named_paths, out_path: str) -> str:
    return _exp.merge_chrome_traces(named_paths, out_path)


def summary(sorted_key: str = "total") -> str:
    return summary_table(MONITOR, sorted_key)
