"""Traffic of kind `serve`: an open loop against `serving.Server`.

The model is built, saved with `io.save_inference_model` and loaded into a
`serving.Server` with its default `workers` and `max_queue` and the traffic
file's `buckets`; loading warms every bucket, which is the cell's compile.

The load is open: every request has a time at which it is DUE, fixed by the
seed before the run, and is sent then whether or not earlier ones have come
back.  One thread (the main one) sends; one thread collects, waiting on the
futures in the order sent (the server's one worker completes them in that
order) and stamping each completion.  A request's latency runs from its due
time, so a stall of the server or of the generator counts against every
request it delays, and the generator's own lateness is reported beside it.

The amount of work is fixed, not drawn: a window of `seconds` at
`rate_per_s` holds exactly round(rate x seconds) requests, arriving at
sorted uniform times (a Poisson process given its count), and each class of
`rows_mix` gets its exact share of them with its row counts dealt out
evenly; the seed shuffles which request gets which.  So every seed offers
the same rows, and `serve_rows_per_s` moves only when the server does.
`warm_seconds` of the same traffic run before the window.
"""
from __future__ import annotations

import queue
import shutil
import tempfile
import threading
import time

import numpy as np

from benchmark import arith
from benchmark.runners import common

SAMPLED = 32
RESULT_TIMEOUT_S = 60.0


def schedule(seed: int, job: dict, rate_per_s: float, seconds: float):
    """(due_s, rows, offset) of `warm_seconds` of warming traffic followed by
    a window of `seconds`, both at `rate_per_s`, and the mask of the
    requests due inside the window."""
    warm = job["warm_seconds"]
    segments = [arith.fixed_work_schedule(seed, rate_per_s, warm,
                                          job["rows_mix"], job["pool_rows"]),
                arith.fixed_work_schedule(seed + 1, rate_per_s, seconds,
                                          job["rows_mix"], job["pool_rows"], t_from=warm)]
    due, rows, offset = (np.concatenate(x) for x in zip(*segments))
    return due, rows, offset, due >= warm


class Served:
    """The model saved, loaded and warm behind a server; `close()` stops
    the server and removes the saved model."""

    def __init__(self, run):
        import paddle_tpu as fluid
        from paddle_tpu import io, serving

        cfg, job, model = run.config, run.traffic, run.model
        main, startup, feed_names, logits = model.build_inference(cfg, job)
        main.random_seed = startup.random_seed = run.seed
        self.main, self.scope = main, fluid.Scope()
        exe = fluid.Executor(fluid.TPUPlace(0))
        exe.run(startup, scope=self.scope)
        self.feed = feed_names[0]
        self.model_dir = tempfile.mkdtemp(prefix="benchmark_model_")
        io.save_inference_model(self.model_dir, feed_names, [logits], exe,
                                main_program=main, scope=self.scope)
        exe.close()
        self.registry = serving.ModelRegistry(place=fluid.TPUPlace(0))
        self.server = serving.Server(self.registry, buckets=tuple(job["buckets"]))
        self.version = self.server.load_model("m", self.model_dir)
        rng = np.random.RandomState(run.seed)
        size = cfg["image_size"]
        self.pool = rng.random_sample(
            (job["pool_rows"], 3, size, size)).astype("float32")

    def executables(self):
        return common.executables_of(self.registry.executor, [self.version.program])

    def close(self):
        self.server.stop()
        shutil.rmtree(self.model_dir, ignore_errors=True)


def drive(served: Served, due, rows, offset, tracer=None, keep=()):
    """Send the schedule, collect every answer.  Returns per request the
    due, send and completion times (perf_counter; NaN where it failed) and
    whether the answer had the request's rows, plus the kept outputs."""
    from paddle_tpu.errors import ServingError

    n = len(due)
    t_send, t_done = np.full(n, np.nan), np.full(n, np.nan)
    ok = np.zeros(n, bool)
    kept = {}
    sent: "queue.Queue" = queue.Queue()

    def collect():
        while True:
            item = sent.get()
            if item is None:
                return
            i, fut = item
            try:
                out = fut.result(RESULT_TIMEOUT_S)
            except (ServingError, TimeoutError):
                continue
            t_done[i] = common.now()
            ok[i] = len(out) == 1 and out[0].shape[0] == rows[i]
            if i in keep:
                kept[i] = np.asarray(out[0][0])

    collector = threading.Thread(target=collect, name="bench-collector", daemon=True)
    collector.start()
    server, pool, feed = served.server, served.pool, served.feed
    t_start = common.now() + 0.05
    t_due = t_start + due
    for i in range(n):
        with common.annotate("bench.wait_due"):
            while True:
                left = t_due[i] - common.now()
                if left <= 0:
                    break
                if left > 0.0004:
                    time.sleep(left - 0.0003)
        if tracer is not None and tracer.due(common.now()):
            tracer.start()
        t_send[i] = common.now()
        try:
            with common.annotate("bench.submit"):
                fut = server.submit("m", {feed: pool[offset[i]:offset[i] + rows[i]]})
        except ServingError:
            continue  # shed at the door: failed
        sent.put((i, fut))
    sent.put(None)
    collector.join(RESULT_TIMEOUT_S + 5.0)
    if collector.is_alive():
        raise RuntimeError("the collector did not finish: a request never completed")
    return {"t_due": t_due, "t_send": t_send, "t_done": t_done, "ok": ok,
            "kept": kept, "t_end": common.now()}


def summarise(res: dict, rows, seconds: float, picked) -> dict:
    """The end-to-end numbers of the requests `picked` (a boolean mask: those
    due inside the window)."""
    done = picked & res["ok"] & np.isfinite(res["t_done"])
    lat_ms = (res["t_done"][done] - res["t_due"][done]) * 1e3
    lag_ms = (res["t_send"][picked] - res["t_due"][picked]) * 1e3
    return {"attempted": int(picked.sum()), "failed": int(picked.sum() - done.sum()),
            "request_p50_ms": arith.percentile(lat_ms, 50),
            "request_p99_ms": arith.percentile(lat_ms, 99),
            "serve_rows_per_s": float(rows[done].sum() / seconds),
            "generator_lag_p99_ms": arith.percentile(lag_ms, 99),
            "request_p90_ms": float(np.percentile(lat_ms, 90)),
            "request_p95_ms": float(np.percentile(lat_ms, 95)),
            "request_p98_ms": float(np.percentile(lat_ms, 98)),
            "request_mean_ms": float(lat_ms.mean()),
            "request_max_ms": float(lat_ms.max()),
            "offered_rows_per_s": float(rows[picked].sum() / seconds)}


def host_phases_by_bucket() -> dict:
    """Mean milliseconds a batch spent building (concatenate, pad), in the
    predictor call (copy in, execute, copy out: a host clock, not device
    time) and splitting results, by bucket, from the program's
    `serving_batch` records; empty while the monitor is off."""
    from paddle_tpu import monitor

    by_bucket: dict = {}
    for r in monitor.step_records():
        if r.get("kind") == "serving_batch":
            b = by_bucket.setdefault(r["bucket"], {"batches": 0, "build": 0.0,
                                                   "infer": 0.0, "fetch": 0.0})
            b["batches"] += 1
            for k in ("build", "infer", "fetch"):
                b[k] += r[f"t_{k}_s"] * 1e3
    return {b: {k: (v if k == "batches" else v / d["batches"]) for k, v in d.items()}
            for b, d in sorted(by_bucket.items())}


def run(run):
    import jax

    from benchmark.run import Outcome, info

    cfg, job, model = run.config, run.traffic, run.model
    served = Served(run)
    try:
        warm_s = job["warm_seconds"]
        due, rows, offset, picked = schedule(run.seed, job, job["rate_per_s"], run.seconds)
        rng = np.random.RandomState(run.seed)
        keep = set(rng.choice(np.flatnonzero(picked), SAMPLED, replace=False).tolist())
        stats0 = served.server.stats()
        mon_setup = common.monitor_snapshot()
        # the warming traffic is the same traffic and counts as set-up:
        # the window opens warm_s after its first request is due
        tracer = common.TracedPart(run.trace, run.trace_dir, float("inf"))
        t_before = common.now()
        tracer.t_start = t_before + 0.05 + warm_s + run.seconds - job["trace_seconds"]
        res = drive(served, due, rows, offset, tracer, keep)
        tracer.stop()
        mon1 = common.monitor_snapshot()
        stats1 = served.server.stats()
        ledger = served.server.ledger()
        qwf = served.server.queue_wait_frac()
        t0 = res["t_due"][picked][0] - (due[picked][0] - warm_s)
        e2e = summarise(res, rows, run.seconds, picked)

        reasons = []
        if e2e["failed"]:
            reasons.append(f"{e2e['failed']} of {e2e['attempted']} requests "
                           f"failed, were shed or came back with wrong rows")
        if not ledger["balanced"]:
            reasons.append(f"the server's ledger does not reconcile: {ledger}")
        # a seeded sample of 32 responses, first row each, against the
        # plain float32 forward (one fixed shape, so it compiles once)
        idx = sorted(res["kept"])
        if len(idx) == SAMPLED:
            imgs = np.stack([served.pool[offset[i]] for i in idx])
            got = np.stack([res["kept"][i] for i in idx])
            params = {p.name: served.scope.find_var(p.name)
                      for p in served.main.all_parameters()}
            want = jax.jit(lambda p, b: model.reference(p, b, cfg, served.main))(
                params, {"img": imgs})
            err = model.reference_error(got, np.asarray(want[0]))
            if not err <= model.REFERENCE_RTOL:
                reasons.append(f"reference: error {err:.3e} > {model.REFERENCE_RTOL}")
            info("reference", error=err, tolerance=model.REFERENCE_RTOL, rows=SAMPLED)
        else:
            reasons.append(f"only {len(idx)} of {SAMPLED} sampled responses came back")
        executables = served.executables()
        d = {k: stats1[k] - stats0[k] for k in ("batches", "rows", "padded_rows",
                                                 "completed", "shed", "requests")}
        info("serve", rate_per_s=job["rate_per_s"], **e2e, **{f"server_{k}": v for k, v in d.items()},
             mean_batch_rows=d["rows"] / max(d["batches"], 1),
             queue_depth_end=stats1["queue_depth"], ledger=ledger,
             buckets=served.server.bucket_attribution(),
             host_ms_by_bucket=host_phases_by_bucket())
        return Outcome(
            correct=not reasons, attempted=e2e["attempted"], failed=e2e["failed"],
            end_to_end={"request_p50_ms": e2e["request_p50_ms"],
                        "request_p99_ms": e2e["request_p99_ms"],
                        "serve_rows_per_s": e2e["serve_rows_per_s"],
                        "setup_s": t0 - run.t_process},
            stats={"window_s": run.seconds, "queue_wait_frac": qwf,
                   "rows": d["rows"], "padded_rows": d["padded_rows"],
                   "generator_lag_p99_ms": e2e["generator_lag_p99_ms"], **d},
            window=(t0, t0 + run.seconds), executables=executables,
            scope_of=common.scopes_of(executables) if run.trace else {},
            monitor_delta={"setup": mon_setup, "window": common.delta(mon1, mon_setup)},
            reasons=reasons)
    finally:
        served.close()
