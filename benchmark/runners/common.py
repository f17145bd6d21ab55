"""What the runners share: the traced part of a window, what is read from
the program's monitor and executor, and the parameter probe."""
from __future__ import annotations

import os
import shutil
import time

import numpy as np

from benchmark import trace_reduce

MONITOR_SPANS = ("executor.build", "executor.lower", "executor.compile",
                 "pipeline.host_blocked")
MONITOR_COUNTERS = ("executor.recompile",)

now = time.perf_counter  # the one clock of every time stamp the runners take


class TracedPart:
    """A `jax.profiler` trace over the end of the window, with the
    `bench.traced_window` annotation round it.  Started from the thread that
    drives the load once `due()`; stopped after the window, so that writing
    the trace out costs the window nothing.  The Python tracer is off: it
    slows the host that the trace is there to watch."""

    def __init__(self, enabled: bool, trace_dir: str, t_start: float):
        self.enabled, self.dir, self.t_start = enabled, trace_dir, t_start
        self.on = False
        self._note = None

    def due(self, now: float) -> bool:
        return self.enabled and not self.on and now >= self.t_start

    def start(self) -> None:
        import jax

        shutil.rmtree(self.dir, ignore_errors=True)
        os.makedirs(self.dir, exist_ok=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 2
        jax.profiler.start_trace(self.dir, profiler_options=options)
        self._note = jax.profiler.TraceAnnotation(trace_reduce.WINDOW_ANNOTATION)
        self._note.__enter__()
        self.on = True

    def stop(self) -> None:
        import jax

        if self.on:
            self._note.__exit__(None, None, None)
            jax.profiler.stop_trace()
            self.on = False


def annotate(name: str):
    """A host span in the profiler's own trace (a no-op while no trace is
    on), so that the device's idle gaps can be put down to what the host
    was doing."""
    import jax

    return jax.profiler.TraceAnnotation(name)


def monitor_snapshot() -> dict:
    """The program's spans and counters that the per-layer metrics read;
    zeros while the monitor is off."""
    from paddle_tpu import monitor

    spans = monitor.json_snapshot(include_steps=False).get("spans", {})
    out = {k: float(spans.get(k, {}).get("total_s", 0.0)) for k in MONITOR_SPANS}
    out.update({k: float(monitor.counter(k).value) for k in MONITOR_COUNTERS})
    return out


def delta(after: dict, before: dict) -> dict:
    return {k: after[k] - before.get(k, 0.0) for k in after}


def executables_of(exe, programs) -> list:
    """The compiled programs the executor holds for `programs`: the
    benchmark reads their memory and cost analysis and their text, as
    chip_smoke.py does.  Empty where the executor's cache is not what it
    was: the readers then return nothing."""
    wanted = {p._uuid[:8] for p in programs}
    found = []
    for step in getattr(exe, "_cache", {}).values():
        if getattr(step, "program_uuid", None) in wanted:
            found += list(getattr(step, "_exec_by_sig", {}).values())
    return found


def scopes_of(executables) -> dict:
    scope_of = {}
    for e in executables:
        scope_of.update(trace_reduce.scopes_from_hlo_text(e.as_text()))
    return scope_of


class ParamProbe:
    """Did training touch every parameter?  Per parameter, on the device,
    (sum, sum of squares, max |x|): a parameter moved if its sums changed,
    and one whose update rounded away below f32 resolution still has a
    first moment that is not all zero.  The rule of
    tools/bench_kit.attach_param_probe without the host copies (880 MB for
    BERT-base as f8)."""

    MOMENTS = ("_moment1_0", "_moment_0", "_velocity_0", "_momentum_0")

    def __init__(self, program, scope):
        import jax
        import jax.numpy as jnp

        self.scope = scope
        self.params = [p.name for p in program.all_parameters()
                       if scope.find_var(p.name) is not None]

        def sums(arrays):
            return {n: jnp.stack([a.astype(jnp.float32).sum(),
                                  jnp.square(a.astype(jnp.float32)).sum(),
                                  jnp.abs(a.astype(jnp.float32)).max()])
                    for n, a in arrays.items()}

        self._sums = jax.jit(sums)

    def _read(self, names) -> dict:
        arrays = {n: self.scope.find_var(n) for n in names}
        return {n: np.asarray(v) for n, v in self._sums(arrays).items()}

    def before(self) -> None:
        self._before = self._read(self.params)

    def after(self) -> dict:
        after = self._read(self.params)
        still = [n for n in self.params
                 if np.array_equal(after[n][:2], self._before[n][:2])]
        names = set(self.scope.var_names())
        moments = {n: next((n + s for s in self.MOMENTS if n + s in names), None)
                   for n in still}
        live = self._read([m for m in moments.values() if m])
        dead = [n for n, m in moments.items() if not (m and live[m][2] > 0)]
        return {"params": len(self.params), "still": still, "dead": dead}

