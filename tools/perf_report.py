#!/usr/bin/env python
"""Render / diff / CI-gate monitor output (paddle_tpu.monitor).

    python tools/perf_report.py snapshot.json
        Render span, counter, gauge, and step-breakdown tables from a
        monitor.export_json() snapshot.

    python tools/perf_report.py --diff before.json after.json
        Per-span total/avg deltas and counter deltas between two snapshots
        (the A/B view the perf rounds kept rebuilding by hand).

    python tools/perf_report.py --check metrics.jsonl [--steady-after N]
        CI/bench gate: assert the JSONL metrics file (MonitorLogger output)
        exists, contains step records, and that the recompile count stayed
        FLAT across steady-state steps (index >= N, default 2).  A rising
        recompile count in steady state is the compile-cache-thrash
        signature behind NMT-style run-to-run variance; exit 1 names the
        offending steps.

    python tools/perf_report.py --check metrics.jsonl --max-host-blocked-frac 0.5
        Additionally gate the pipelined loop's steady-state host-blocked
        fraction (from paddle_tpu.pipeline.train_loop's pipeline_step
        records): above the threshold, the host is back to waiting on the
        device — an overlap regression.

    python tools/perf_report.py --check metrics.jsonl --max-retry-frac 0.1
        Additionally gate recovery events per executed step (skip-batch /
        skip-step / retry / rollback resilience_event records from
        paddle_tpu.resilience.resilient_train_loop): a healthy run sits
        near 0; above the threshold the run is burning its budget
        re-doing work (flaky data source, NaN-prone config, sick device).

    python tools/perf_report.py --check metrics.jsonl --max-heartbeat-miss-frac 0.02
        Gate the distributed health layer (paddle_tpu.dist_resilience):
        heartbeat-miss transitions over beats sent, read from the newest
        counter snapshot in the file (MonitorLogger.write_snapshot).  A
        creeping fraction means peers keep falling past the liveness
        deadline — flaky network, GC pauses, or a host about to die.

    python tools/perf_report.py --check metrics.jsonl --max-step-skew-frac 0.5
        Gate the per-step cross-rank skew metric (ISSUE 8): the live
        straggler detector's `straggler` dist_event records (falling back
        to the dist.step_skew_frac gauge in the newest counter snapshot
        — counters-only files work, same as the dist gates below).  Each
        unit is one full step of sustained lag behind the gang: a rank
        was slow-but-alive and everyone else waited for it.

    python tools/perf_report.py --postmortem TELEMETRY_DIR
        Render a merged gang post-mortem from the flight-recorder black
        boxes (BLACKBOX.p<rank>.json) and supervisor INCIDENT files a
        paddle_tpu.launch gang left in its telemetry root: names the
        dead rank(s) and folds every rank's last-N step records into one
        timeline.  See also tools/trace_merge.py for the merged Chrome
        trace + straggler attribution over the same directory.

    python tools/perf_report.py --check metrics.jsonl --max-gang-restarts 1
        Gate gang restarts (paddle_tpu.launch run_gang dist_event records
        / dist.gang_restarts counter): each one is a full
        rollback-and-relaunch, so a chaos budget above the expected
        schedule means workers are dying for reasons the fault spec does
        not explain.

    python tools/perf_report.py --check metrics.jsonl --max-data-corrupt-frac 0.01
        Gate the data layer (paddle_tpu.recordio): corrupt chunks dropped
        per chunk scanned, from the newest counter snapshot.  The corrupt
        budget keeps a run alive through isolated rot; this gate notices
        when the rot rate itself is the problem.

    python tools/perf_report.py --check metrics.jsonl --max-replay-batches 0
        Gate the resume cost: batches replayed just to fast-forward a
        stateless data source (replay_fast_forward resilience events).
        0 asserts every source resumed via the O(1) stream-state seek.

    python tools/perf_report.py --check metrics.jsonl --max-shed-frac 0.05
        Gate the serving runtime's admission control (paddle_tpu.serving):
        requests shed over requests offered, from the newest counter
        snapshot (serving.shed / serving.requests; serving_event records
        as fallback — counters-only files work).  Shedding is the DESIGNED
        overload response, so the budget is "how much overload the round
        was allowed to see", not "is shedding broken".

    python tools/perf_report.py --check metrics.jsonl --max-p99-ms 50
        Gate the serving tail: p99 request latency from the newest
        snapshot's serving.p99_ms gauge (lat_ms_max over serving_batch
        records as fallback).  The SLO number a server over capacity
        must hold WITH shedding active — bounded-queue admission is what
        keeps it flat while load climbs.

    python tools/perf_report.py --check metrics.jsonl --require-quant-parity
        Gate a quantized-serving run (a save_quantized_inference_model
        directory published through serving.publish): the file must
        carry at least one `quant_parity` serving_event — the publish
        ladder's accuracy gate over a quantized snapshot
        (FLAGS_serving_quant_atol vs the fp32 parent's outputs) — with
        max_abs_diff within its recorded atol, and no quant-parity
        publish rejection.  A file with no quant evidence FAILS (zero
        evidence must not gate green).

    python tools/perf_report.py --check metrics.jsonl --max-lock-wait-frac 0.2
        Gate named-lock contention (paddle_tpu/core/locks.py, recorded
        when the run sets FLAGS_lock_telemetry=1): of all time threads
        spent holding-or-waiting-on named locks, the share spent WAITING
        (sum lock.*.wait_us / (wait_us + hold_us), newest counter
        snapshot).  A file with no lock.* counters FAILS the gate — zero
        evidence must not gate green (the PR 8/10 convention).  The
        failure message names the worst locks so the fix starts at the
        right critical section.

    python tools/perf_report.py --check metrics.jsonl --max-integrity-mismatches 0
        Gate silent-corruption detections (paddle_tpu/integrity.py):
        live cross-rank digest divergences + at-rest file digest
        mismatches (integrity_event records, integrity.* counter
        fallback).  Walk-back ckpt_rejected events are the downstream
        consequence of a detection that already counted — rendered, not
        double-billed.  0 asserts the run saw NO corruption at all; a
        chaos round budgets exactly its injected count.  A file with no
        integrity evidence FAILS the gate — zero evidence must not gate
        green.

    python tools/perf_report.py --check metrics.jsonl --max-chaos-violations 0
        Gate the chaos campaign's verdict (paddle_tpu/chaos.py, ISSUE
        20): invariant violations recorded by seeded multi-fault
        schedules (chaos.invariant_violations counter, failed-schedule
        chaos_event records as the floor).  0 asserts every schedule the
        campaign drew left the cross-subsystem invariants intact; any
        failure's minimal repro lives in the campaign's
        CHAOS_REPRO.json.  A file with no chaos evidence at all FAILS
        the gate — zero evidence must not gate green.
"""
from __future__ import annotations

import argparse
import json
import os
import sys


def _load_snapshot(path):
    with open(path) as f:
        return json.load(f)


def _fmt_table(rows, headers):
    widths = [max(len(str(r[i])) for r in rows + [headers])
              for i in range(len(headers))]
    out = ["  ".join(str(h).ljust(w) for h, w in zip(headers, widths))]
    for r in rows:
        out.append("  ".join(str(c).ljust(w) for c, w in zip(r, widths)))
    return "\n".join(out)


def render(path: str) -> str:
    snap = _load_snapshot(path)
    parts = [f"# monitor snapshot  lane={snap.get('lane_name', '?')}  "
             f"ts={snap.get('ts', 0):.3f}"]

    spans = snap.get("spans", {})
    if spans:
        rows = [(n, s["calls"], f"{s['total_s']*1e3:.3f}",
                 f"{s['total_s']/max(s['calls'],1)*1e3:.3f}",
                 f"{s['max_s']*1e3:.3f}")
                for n, s in sorted(spans.items(),
                                   key=lambda kv: -kv[1]["total_s"])]
        parts.append("\n## spans\n" + _fmt_table(
            rows, ["name", "calls", "total_ms", "avg_ms", "max_ms"]))

    counters = snap.get("counters", {})
    if counters:
        rows = [(n, v) for n, v in counters.items()]
        parts.append("\n## counters\n" + _fmt_table(rows, ["name", "value"]))

    gauges = snap.get("gauges", {})
    if gauges:
        rows = [(n, v) for n, v in gauges.items()]
        parts.append("\n## gauges\n" + _fmt_table(rows, ["name", "value"]))

    records = snap.get("steps", [])
    steps = [s for s in records if s.get("kind", "step") == "step"]
    if steps:
        phases = ("t_lower_s", "t_compile_s", "t_dispatch_s", "t_execute_s",
                  "t_fetch_s", "t_total_s")
        rows = []
        for ph in phases:
            # average only over records that carry the phase: async-dispatch
            # records have no execute/fetch/total, and zero-filling them
            # would report device time as near-free
            vals = [s[ph] for s in steps if ph in s]
            if not vals:
                continue
            rows.append((ph[2:-2], f"{sum(vals)*1e3:.3f}",
                         f"{sum(vals)/len(vals)*1e3:.3f}",
                         f"{max(vals)*1e3:.3f}",
                         len(vals)))
        parts.append(f"\n## step breakdown ({len(steps)} steps)\n"
                     + _fmt_table(rows, ["phase", "total_ms", "avg_ms",
                                         "max_ms", "records"]))
        hits = sum(1 for s in steps if s.get("cache_hit"))
        rec = sum(1 for s in steps if s.get("recompiled"))
        parts.append(f"cache hits {hits}/{len(steps)}, recompiles {rec}")

    psteps = [s for s in records if s.get("kind") == "pipeline_step"]
    if psteps:
        blocked, wall, frac = host_blocked_fraction(psteps)
        depths = [s.get("inflight", 0) for s in psteps]
        logged = sum(1 for s in psteps if s.get("logged"))
        parts.append(
            f"\n## pipeline ({len(psteps)} steps, {logged} logged)\n"
            f"host-blocked {blocked*1e3:.3f} ms of {wall*1e3:.3f} ms wall "
            f"-> fraction {frac:.3f}\n"
            f"inflight depth avg {sum(depths)/len(depths):.2f} "
            f"max {max(depths)}")

    devents = [s for s in records if s.get("kind") == "dist_event"]
    counters = snap.get("counters", {})
    if devents or any(n.startswith("dist.") for n in counters):
        rows = [(r.get("action", "?"),
                 r.get("rank", r.get("incarnation", "")),
                 r.get("peers", r.get("peer", r.get("what",
                       r.get("after_death_of", "")))))
                for r in devents]
        hb = heartbeat_miss_fraction([snap] if counters else [])
        parts.append(f"\n## distributed ({len(devents)} events, "
                     f"heartbeat-miss fraction {hb:.4f}, "
                     f"gang restarts {counters.get('dist.gang_restarts', 0)})\n"
                     + (_fmt_table(rows, ["action", "rank/inc", "detail"])
                        if rows else "(counters only)"))

    sbatches = [s for s in records if s.get("kind") == "serving_batch"]
    sevents = [s for s in records if s.get("kind") == "serving_event"]
    straces = [s for s in records if s.get("kind") == "serving_trace"]
    if sbatches or sevents or straces:
        lines = records + [snap]  # snap's counters/gauges = newest state
        occ = [s.get("occupancy", 0.0) for s in sbatches]
        parts.append(
            f"\n## serving ({len(sbatches)} batches, {len(sevents)} "
            f"events, shed frac {shed_fraction(lines):.4f}, "
            f"p99 {serving_p99_ms(lines):.1f} ms"
            + (f", mean occupancy {sum(occ)/len(occ):.3f}" if occ else "")
            + (f", queue-wait frac {queue_wait_fraction(lines):.4f}"
               if _has_queue_wait_evidence(lines) else "")
            + (f", pad frac {pad_fraction(lines):.4f}"
               if _has_pad_evidence(lines) else "")
            + (f", {len(straces)} request traces — tools/serve_trace.py "
               f"renders them" if straces else "")
            + ")")
        rows = [(r.get("action", "?"), r.get("model", ""),
                 r.get("reason", r.get("detail", r.get("rows", ""))))
                for r in sevents]
        if rows:
            parts.append(_fmt_table(rows, ["action", "model", "detail"]))

    ievents = [s for s in records if s.get("kind") == "integrity_event"]
    icounters = {n: v for n, v in snap.get("counters", {}).items()
                 if n.startswith("integrity.")}
    if ievents or icounters:
        rows = [(r.get("action", "?"),
                 r.get("corrupt_ranks", r.get("rank", "")),
                 r.get("safe_step", r.get("step", "")),
                 r.get("file", r.get("dir", r.get("digests", ""))))
                for r in ievents]
        parts.append(
            f"\n## integrity ({len(ievents)} events, "
            f"digest epochs {icounters.get('integrity.digests', 0)}, "
            f"files verified "
            f"{icounters.get('integrity.files_verified', 0)}, "
            f"mismatches {icounters.get('integrity.file_mismatches', 0)}"
            f"+{icounters.get('integrity.divergences', 0)} div, "
            f"rollbacks {icounters.get('integrity.rollbacks', 0)})\n"
            + (_fmt_table(rows, ["action", "ranks", "step", "detail"])
               if rows else "(counters only)"))

    revents = [s for s in records if s.get("kind") == "resilience_event"]
    if revents:
        rows = [(r.get("action", "?"), r.get("class", "?"),
                 r.get("at_step", r.get("at_batch", "")),
                 r.get("code", r.get("restored_step",
                                     r.get("max_inflight", ""))))
                for r in revents]
        frac = retry_fraction(records)
        parts.append(f"\n## resilience ({len(revents)} events, "
                     f"recovery fraction {frac:.3f})\n"
                     + _fmt_table(rows, ["action", "class", "at", "detail"]))

    sevs = [s for s in records if s.get("kind") == "resilience_event"
            and s.get("action") in STORAGE_ACTIONS]
    scnt = {n: v for n, v in snap.get("counters", {}).items()
            if n.startswith("checkpoint.")
            or n.startswith("resilience.ckpt")
            or n in ("resilience.storage_degraded",
                     "serving.publish_retries")}
    if sevs or any(scnt.values()):
        g = snap.get("gauges", {})
        parts.append(
            f"\n## storage ({len(sevs)} events, "
            f"saves {scnt.get('checkpoint.saves', 0)}, "
            f"save retries {scnt.get('resilience.ckpt_save_retries', 0)}, "
            f"degraded entries "
            f"{scnt.get('resilience.storage_degraded', 0)}, "
            f"recoveries {scnt.get('resilience.ckpt_recovered', 0)}, "
            f"fallback saves "
            f"{scnt.get('resilience.ckpt_fallback_saves', 0)}, "
            f"publish retries {scnt.get('serving.publish_retries', 0)}, "
            f"ckpt lag {g.get('resilience.ckpt_lag_steps', 0)} steps)"
            + ("\n" + _fmt_table(
                [(r.get("action", "?"), r.get("at_step", ""),
                  r.get("lag_steps", ""), r.get("cause", r.get("dir", "")))
                 for r in sevs],
                ["action", "at_step", "lag", "detail"]) if sevs else ""))

    # sparse host tier + publish cadence (ISSUE 19)
    spevs = [s for s in records if s.get("kind") == "sparse_event"]
    pubevs = [s for s in records if s.get("kind") == "resilience_event"
              and s.get("action") in ("publish", "publish_failed")]
    pscnt = {n: v for n, v in snap.get("counters", {}).items()
             if n.startswith("ps.") or n.startswith("sparse.")
             or n in ("serving.publishes", "serving.publish_errors")}
    if spevs or pubevs or any(pscnt.values()):
        g = snap.get("gauges", {})
        parts.append(
            f"\n## sparse tier ({len(spevs)} host-tier events, "
            f"publishes {pscnt.get('serving.publishes', 0)}, "
            f"publish errors {pscnt.get('serving.publish_errors', 0)}, "
            f"pserver retries {pscnt.get('ps.retries', 0)}, "
            f"push dedups {pscnt.get('ps.push_dedup', 0)}, "
            f"degraded steps {pscnt.get('sparse.degraded_steps', 0)}, "
            f"host lag {g.get('sparse.host_lag_steps', 0)} steps, "
            f"publish staleness "
            f"{g.get('serving.publish_staleness_steps', 0)} steps)"
            + ("\n" + _fmt_table(
                [(r.get("action", "?"),
                  r.get("at_step", r.get("step", "")),
                  r.get("lag_steps", r.get("staleness", "")),
                  str(r.get("detail", r.get("table", "")))[:60])
                 for r in (spevs + pubevs)[:40]],
                ["action", "at_step", "lag", "detail"])
               if spevs or pubevs else ""))

    # chaos campaign (ISSUE 20)
    cevs = [s for s in records if s.get("kind") == "chaos_event"]
    ccnt = {n: v for n, v in snap.get("counters", {}).items()
            if n.startswith("chaos.")}
    if cevs or any(ccnt.values()):
        lines = records + [snap]
        parts.append(
            f"\n## chaos campaign ({len(cevs)} events, "
            f"schedules {ccnt.get('chaos.schedules_run', 0)}, "
            f"invariant checks {ccnt.get('chaos.invariants_checked', 0)}, "
            f"violations {chaos_violation_count(lines)})"
            + ("\n" + _fmt_table(
                [(r.get("event", "?"), r.get("scenario", ""),
                  r.get("verdict", ""),
                  str(r.get("shrunk_spec", r.get("spec", "")))[:50])
                 for r in cevs[:40]],
                ["event", "scenario", "verdict", "spec"]) if cevs else ""))
    return "\n".join(parts)


RECOVERY_ACTIONS = ("skip_batch", "skip_step", "retry", "rollback")

# storage-resilience events (ISSUE 15, paddle_tpu/checkpoint_manager.py):
# each degraded/skipped round carries the lag it left training unprotected
# for — the number --max-ckpt-lag-steps gates
STORAGE_ACTIONS = ("storage_degraded", "ckpt_round_skipped",
                   "storage_recovered", "ckpt_fallback")


def _has_storage_evidence(lines):
    """True when the file carries ANY checkpoint-storage signal: storage
    resilience_event records, checkpoint.* counters, or the
    resilience.ckpt_lag_steps gauge in a snapshot.  The lag gate fails on
    a file with none — a run that never checkpointed (or never logged)
    must not gate green (the zero-evidence-fails convention, PR 8/10/13)."""
    if any(r.get("kind") == "resilience_event"
           and r.get("action") in STORAGE_ACTIONS for r in lines):
        return True
    if _latest_counters(lines, "checkpoint."):
        return True
    g = _latest_gauges(lines, "resilience.")
    return "resilience.ckpt_lag_steps" in g


def ckpt_lag_steps(lines):
    """The worst checkpoint lag the run saw: max lag_steps over
    storage_degraded / ckpt_round_skipped resilience events, falling back
    to the resilience.ckpt_lag_steps gauge in the newest snapshot (which
    reads 0 after recovery — the events are the durable evidence).  0 on
    healthy storage: every save committed, no step ran unprotected."""
    lags = [float(r.get("lag_steps", 0) or 0) for r in lines
            if r.get("kind") == "resilience_event"
            and r.get("action") in ("storage_degraded", "ckpt_round_skipped")]
    if lags:
        return max(lags)
    g = _latest_gauges(lines, "resilience.")
    try:
        return float(g.get("resilience.ckpt_lag_steps", 0.0) or 0.0)
    except (TypeError, ValueError):
        return 0.0


def _has_publish_evidence(lines):
    """True when the file carries ANY publish-cadence signal: publish /
    publish_failed resilience events, serving.publishes/publish_errors
    counters, or the serving.publish_staleness_steps gauge in a
    snapshot.  The staleness gate fails on a file with none — a run
    whose publish hook never armed (or never logged) must not gate
    green (zero-evidence-fails, PR 8/10)."""
    if any(r.get("kind") == "resilience_event"
           and r.get("action") in ("publish", "publish_failed")
           for r in lines):
        return True
    c = _latest_counters(lines, "serving.")
    if c.get("serving.publishes") or c.get("serving.publish_errors"):
        return True
    g = _latest_gauges(lines, "serving.")
    return "serving.publish_staleness_steps" in g


def publish_staleness_steps(lines):
    """The worst publish-to-serving staleness the run saw: max staleness
    over publish_failed resilience events (each failed period stamps how
    far training ran past the last served snapshot), with the newest
    serving.publish_staleness_steps gauge as the end-of-run floor (it
    reads the gap at the final dispatch, catching a cadence that stalled
    silently at the tail)."""
    vals = [float(r.get("staleness", 0) or 0) for r in lines
            if r.get("kind") == "resilience_event"
            and r.get("action") == "publish_failed"]
    g = _latest_gauges(lines, "serving.")
    try:
        vals.append(float(g.get("serving.publish_staleness_steps", 0.0)
                          or 0.0))
    except (TypeError, ValueError):
        pass
    return max(vals) if vals else 0.0


def _has_chaos_evidence(lines):
    """True when the file carries ANY chaos-campaign signal: chaos_event
    records (one per schedule run, plus one per shrink) or chaos.*
    counters in a snapshot.  The --max-chaos-violations gate fails on a
    file with none — a campaign that never ran (or ran with the monitor
    muted) must not gate green (zero-evidence-fails, PR 8/10)."""
    if any(r.get("kind") == "chaos_event" for r in lines):
        return True
    return bool(_latest_counters(lines, "chaos."))


def chaos_violation_count(lines):
    """Invariant violations the chaos campaign saw: the newest
    chaos.invariant_violations counter, with a recount of failed
    schedule chaos_event records as the floor (the events survive even
    when no final counter snapshot was written)."""
    n_events = sum(1 for r in lines if r.get("kind") == "chaos_event"
                   and r.get("event") == "schedule"
                   and r.get("verdict") == "fail")
    c = _latest_counters(lines, "chaos.")
    try:
        n_counter = int(c.get("chaos.invariant_violations", 0) or 0)
    except (TypeError, ValueError):
        n_counter = 0
    return max(n_events, n_counter)


def _has_sparse_evidence(lines):
    """True when the file carries ANY host-tier signal: sparse_event
    records (host_tier_degraded/recovered, pserver recovery/journal
    events), sparse.* or ps.* counters, or the sparse.host_lag_steps
    gauge.  The host-lag gate fails on a file with none."""
    if any(r.get("kind") == "sparse_event" for r in lines):
        return True
    if _latest_counters(lines, "sparse.") or _latest_counters(lines, "ps."):
        return True
    g = _latest_gauges(lines, "sparse.")
    return "sparse.host_lag_steps" in g


def host_lag_steps(lines):
    """The worst host-tier outage the run saw, in consecutive degraded
    steps: max lag_steps over host_tier_degraded sparse events, falling
    back to the newest sparse.host_lag_steps gauge (which reads 0 after
    the tier recovers — the events are the durable evidence)."""
    lags = [float(r.get("lag_steps", 0) or 0) for r in lines
            if r.get("kind") == "sparse_event"
            and r.get("action") == "host_tier_degraded"]
    if lags:
        return max(lags)
    g = _latest_gauges(lines, "sparse.")
    try:
        return float(g.get("sparse.host_lag_steps", 0.0) or 0.0)
    except (TypeError, ValueError):
        return 0.0


def retry_fraction(records):
    """Recovery events per executed step — the resilience-health number a
    chaos bench / CI run gates on.  A fraction creeping up means the run
    is spending its life re-doing work (flaky data, NaN-prone config,
    sick device) even if it technically still converges."""
    steps = sum(1 for r in records if r.get("kind", "step") == "step")
    rec = sum(1 for r in records if r.get("kind") == "resilience_event"
              and r.get("action") in RECOVERY_ACTIONS)
    return rec / steps if steps else 0.0


def _latest_counters(lines, prefix):
    """`prefix`-named counters from the NEWEST record carrying a counter
    map (a MonitorLogger.write_snapshot line, or a rendered snapshot
    dict)."""
    for rec in reversed(lines):
        counters = rec.get("counters")
        if isinstance(counters, dict):
            return {n: v for n, v in counters.items() if n.startswith(prefix)}
    return {}


def _latest_gauges(lines, prefix):
    for rec in reversed(lines):
        gauges = rec.get("gauges")
        if isinstance(gauges, dict):
            return {n: v for n, v in gauges.items() if n.startswith(prefix)}
    return {}


def step_skew_frac(lines):
    """The per-step cross-rank skew metric (ISSUE 8): the maximum skew
    fraction over the live straggler detector's `straggler` dist_event
    records, falling back to the `dist.step_skew_frac` gauge in the
    newest snapshot (counters/gauges-only files, same as the PR-4 dist
    gates).  ~0 on a healthy lock-step gang; each unit is one full step
    of sustained lag behind the gang."""
    fracs = [float(r.get("skew_frac", r.get("lag_steps", 0)) or 0)
             for r in lines if r.get("kind") == "dist_event"
             and r.get("action") == "straggler"]
    if fracs:
        return max(fracs)
    g = _latest_gauges(lines, "dist.")
    try:
        return float(g.get("dist.step_skew_frac", 0.0) or 0.0)
    except (TypeError, ValueError):
        return 0.0


def _latest_dist_counters(lines):
    return _latest_counters(lines, "dist.")


def heartbeat_miss_fraction(lines):
    """Missed-liveness transitions per beat sent, from the newest counter
    snapshot in a metrics stream.  The distributed-health number: ~0 on a
    healthy gang; each unit of the numerator is one peer observed falling
    past the deadline (paddle_tpu.dist_resilience heartbeat)."""
    c = _latest_dist_counters(lines)
    sent = c.get("dist.heartbeat.sent", 0)
    missed = c.get("dist.heartbeat.missed", 0)
    return missed / sent if sent else 0.0


def gang_restart_count(lines):
    """Gang restarts: the launcher's dist_event records, falling back to
    the dist.gang_restarts counter snapshot when the event lines were
    rotated away."""
    n = sum(1 for r in lines if r.get("kind") == "dist_event"
            and r.get("action") == "gang_restart")
    if n:
        return n
    return int(_latest_dist_counters(lines).get("dist.gang_restarts", 0))


def gang_resize_count(lines):
    """Elastic world-size changes (paddle_tpu.launch `gang_resize`
    dist_events; dist.gang_resizes counter fallback).  Each shrink is a
    worker's capacity genuinely lost, each grow an interruption of the
    shrunk gang — both legitimate under chaos, both worth a budget."""
    n = sum(1 for r in lines if r.get("kind") == "dist_event"
            and r.get("action") == "gang_resize")
    if n:
        return n
    return int(_latest_dist_counters(lines).get("dist.gang_resizes", 0))


def data_corrupt_fraction(lines):
    """Corrupt RecordIO chunks dropped per chunk scanned, from the newest
    counter snapshot (`data.corrupt_chunks` / `data.chunks_scanned`,
    paddle_tpu.recordio).  ~0 on healthy storage; a creeping fraction
    means the dataset files are rotting (torn writes, bad disks) even
    while the corrupt budget keeps the run alive."""
    c = _latest_counters(lines, "data.")
    scanned = c.get("data.chunks_scanned", 0)
    corrupt = c.get("data.corrupt_chunks", 0)
    return corrupt / scanned if scanned else 0.0


def replayed_batches(lines):
    """Batches pulled-and-discarded to fast-forward a stateless data
    source on resume (`replay_fast_forward` resilience events, counter
    fallback).  The resume-cost number: 0 when every source speaks the
    stream-state protocol (O(1) seek); anything else is an O(dataset)
    resume eating the recovery budget."""
    n = sum(int(r.get("batches", 0)) for r in lines
            if r.get("kind") == "resilience_event"
            and r.get("action") == "replay_fast_forward")
    if n:
        return n
    return int(_latest_counters(lines, "resilience.")
               .get("resilience.replayed_batches", 0))


def _has_serving_evidence(lines):
    """True when the file carries ANY serving signal (records, counters,
    or gauges).  The serving gates fail on a file with none — a typo'd
    path or a run that silently logged nothing must not gate green
    (the trace_merge zero-evidence class, PR 8)."""
    if any(r.get("kind") in ("serving_batch", "serving_event")
           for r in lines):
        return True
    return bool(_latest_counters(lines, "serving.")
                or _latest_gauges(lines, "serving."))


def _has_fleet_evidence(lines):
    """True when the file carries ANY serving-fleet signal (fleet_event
    records, serving.fleet.* counters or gauges) — the ISSUE-18 fleet
    gates fail without one (zero evidence must not gate green)."""
    if any(r.get("kind") == "fleet_event" for r in lines):
        return True
    return bool(_latest_counters(lines, "serving.fleet.")
                or _latest_gauges(lines, "serving.fleet."))


def fleet_healthy_replicas(lines):
    """Newest `serving.fleet.healthy_replicas` gauge, or None when no
    snapshot in the file carries it."""
    return _latest_gauges(lines, "serving.fleet.").get(
        "serving.fleet.healthy_replicas")


def roll_convergence_failures(lines):
    """Rolling publishes that HALTED without converging.  Exact from
    fleet_event records (per roll ctl id: a `roll_halted` with no
    `roll_rolled_back`/`roll_converged` after it); counters-only files
    fall back to the events[*] counter balance."""
    events = [r for r in lines if r.get("kind") == "fleet_event"]
    if events:
        rolls = {}
        for e in events:
            if e.get("ctl"):
                rolls.setdefault(e["ctl"], []).append(e.get("action"))
        return [ctl for ctl, actions in rolls.items()
                if "roll_halted" in actions
                and "roll_rolled_back" not in actions
                and "roll_converged" not in actions]
    c = _latest_counters(lines, "serving.fleet.")
    halted = c.get("serving.fleet.events[roll_halted]", 0)
    settled = (c.get("serving.fleet.events[roll_rolled_back]", 0)
               + c.get("serving.fleet.events[roll_converged]", 0))
    if halted > settled:
        return [f"{halted:g} roll_halted vs {settled:g} "
                f"rolled_back+converged (counters)"]
    return []


def shed_fraction(lines):
    """Requests shed by serving admission control per request offered
    (paddle_tpu.serving.Server), from the newest counter snapshot
    (serving.shed / serving.requests), falling back to counting shed
    serving_event records against completed+shed when the file carries
    records but no snapshot.  ~0 on an unloaded server; each unit of the
    numerator is one client told 'no' in O(1) instead of 'yes' late."""
    c = _latest_counters(lines, "serving.")
    req = c.get("serving.requests", 0)
    if req:
        return c.get("serving.shed", 0) / req
    shed = sum(1 for r in lines if r.get("kind") == "serving_event"
               and r.get("action") == "shed")
    done = sum(int(r.get("requests", 0)) for r in lines
               if r.get("kind") == "serving_batch")
    total = shed + done
    return shed / total if total else 0.0


def serving_p99_ms(lines):
    """p99 request latency (ms) from the newest snapshot's
    serving.p99_ms gauge (the server keeps a sliding latency window),
    falling back to the p99 of lat_ms_max over serving_batch records.
    0.0 when the file carries no serving evidence."""
    g = _latest_gauges(lines, "serving.")
    try:
        v = float(g.get("serving.p99_ms", 0.0) or 0.0)
    except (TypeError, ValueError):
        v = 0.0
    if v:
        return v
    lats = [float(r.get("lat_ms_max", 0.0) or 0.0) for r in lines
            if r.get("kind") == "serving_batch"]
    lats = [x for x in lats if x > 0]
    if not lats:
        return 0.0
    lats.sort()
    return lats[min(int(0.99 * len(lats)), len(lats) - 1)]


def _has_queue_wait_evidence(lines):
    """True when the file carries ANY queue-wait attribution signal:
    serving_trace records (span trees carry the queue phase),
    serving_batch records stamped with queue_wait_frac, or the
    serving.queue_wait_frac gauge in a snapshot.  The queue-wait gate
    fails on a file with none (zero-evidence-fails convention)."""
    if any(r.get("kind") == "serving_trace" for r in lines):
        return True
    if any(r.get("kind") == "serving_batch" and "queue_wait_frac" in r
           for r in lines):
        return True
    return "serving.queue_wait_frac" in _latest_gauges(lines, "serving.")


def queue_wait_fraction(lines):
    """Of all the wall time completed requests spent in the server, the
    fraction spent QUEUED (waiting for a batch) rather than being built,
    on device, or split — the latency-attribution number ISSUE 16's
    tracing exists to produce.  High under overload by design; high at
    modest load means batches are too slow or workers too few.
    Preference order: serving_trace span trees (exact, per-request) ->
    the serving.queue_wait_frac windowed gauge -> request-weighted
    per-batch queue_wait_frac stamps on serving_batch records."""
    q = tot = 0.0
    for r in lines:
        if r.get("kind") != "serving_trace" \
                or r.get("outcome") != "completed":
            continue
        tot += float(r.get("total_ms", 0.0) or 0.0)
        q += sum(float(s.get("dur_ms", 0.0) or 0.0)
                 for s in r.get("spans", ()) if s.get("name") == "queue")
    if tot > 0:
        return q / tot
    g = _latest_gauges(lines, "serving.")
    try:
        v = float(g.get("serving.queue_wait_frac", 0.0) or 0.0)
    except (TypeError, ValueError):
        v = 0.0
    if v:
        return v
    pairs = [(float(r.get("queue_wait_frac", 0.0) or 0.0),
              int(r.get("requests", 1) or 1))
             for r in lines if r.get("kind") == "serving_batch"
             and "queue_wait_frac" in r]
    n = sum(w for _, w in pairs)
    return sum(f * w for f, w in pairs) / n if n else 0.0


def _has_pad_evidence(lines):
    """True when the file carries ANY pad-waste signal: serving.pad_rows
    / serving.padded_rows counters in a snapshot, or serving_batch
    records (bucket + rows reconstruct the pad even on pre-ISSUE-16
    files)."""
    c = _latest_counters(lines, "serving.")
    if "serving.pad_rows" in c or "serving.padded_rows" in c:
        return True
    return any(r.get("kind") == "serving_batch" for r in lines)


def pad_fraction(lines):
    """Pad rows per padded-batch row: the fraction of serving device
    compute spent on rows no client asked for (pad-to-bucket waste).
    From the newest counter snapshot (serving.pad_rows /
    (serving.rows + serving.pad_rows)), falling back to summing
    serving_batch records — where pre-ISSUE-16 files reconstruct
    pad_rows as bucket - rows."""
    c = _latest_counters(lines, "serving.")
    pad = c.get("serving.pad_rows", c.get("serving.padded_rows", 0))
    rows = c.get("serving.rows", 0)
    if rows + pad:
        return pad / (rows + pad)
    pad = rows = 0
    for r in lines:
        if r.get("kind") != "serving_batch":
            continue
        b = int(r.get("bucket", 0) or 0)
        rw = int(r.get("rows", 0) or 0)
        pad += int(r.get("pad_rows", max(b - rw, 0)))
        rows += rw
    return pad / (rows + pad) if rows + pad else 0.0


def quant_parity_events(lines):
    """The publisher's `quant_parity` serving_event records: one per
    quantized snapshot that PASSED the accuracy-parity gate
    (FLAGS_serving_quant_atol vs the serving fp32 parent's outputs,
    paddle_tpu/serving/publisher.py).  A drifted snapshot never emits
    one — it rejects with a publish_rejected event whose detail names
    'quant parity' instead."""
    return [r for r in lines if r.get("kind") == "serving_event"
            and r.get("action") == "quant_parity"]


def _has_integrity_evidence(lines):
    """True when the file carries ANY integrity signal: integrity_event
    records or integrity.* counters/gauges in a snapshot.  The integrity
    gate fails on a file with none — a run that never armed the sentinel
    (FLAGS_integrity_check_period=0, no digested manifests touched) must
    not gate green (the zero-evidence-fails convention)."""
    if any(r.get("kind") == "integrity_event" for r in lines):
        return True
    return bool(_latest_counters(lines, "integrity.")
                or _latest_gauges(lines, "integrity."))


# PRIMARY detections only: a walk-back ckpt_rejected is the downstream
# CONSEQUENCE of a file mismatch (its event already counted) or of a
# divergence's quarantine markers — counting it too would double-bill
# one injected rot (one rotted checkpoint = one file_mismatch event AND
# one ckpt_rejected event); it still renders in the integrity section.
INTEGRITY_MISMATCH_ACTIONS = ("divergence", "file_mismatch")


def integrity_mismatches(lines):
    """Silent-corruption detections: integrity_event records (live
    digest divergences + at-rest file digest mismatches), falling back
    to the integrity.* counter snapshot when the event lines were
    rotated away.  0 on healthy hardware + storage; anything else is
    real rot the sentinel caught — budget it explicitly (a chaos round
    expects exactly its injected count)."""
    n = sum(1 for r in lines if r.get("kind") == "integrity_event"
            and r.get("action") in INTEGRITY_MISMATCH_ACTIONS)
    if n:
        return n
    c = _latest_counters(lines, "integrity.")
    return int(c.get("integrity.divergences", 0)
               + c.get("integrity.file_mismatches", 0))


def _has_lock_evidence(lines):
    """True when the file carries named-lock telemetry (lock.* counters
    from FLAGS_lock_telemetry, paddle_tpu/core/locks.py).  The lock gate
    fails on a file with none — gating a run that never measured its
    locks green would be the zero-evidence class again."""
    return bool(_latest_counters(lines, "lock."))


def lock_wait_fraction(lines):
    """(fraction, per_lock) — of all time threads spent in named-lock
    critical sections plus the queues in front of them, the share spent
    WAITING: sum(lock.*.wait_us) / (sum wait_us + sum hold_us), from the
    newest counter snapshot.  0 on an uncontended process; creeping up
    means a hot lock is serializing threads (the contention ledger names
    which — per_lock maps name -> (wait_us, hold_us, contended)).
    Thread-count independent, which is what makes it gateable: it does
    not change just because the run got longer or wider."""
    c = _latest_counters(lines, "lock.")
    per_lock = {}
    for k, v in c.items():
        if k == "lock.order_inversions":
            continue
        base, _, leaf = k.rpartition(".")
        name = base[len("lock."):]
        if leaf in ("wait_us", "hold_us", "contended"):
            slot = per_lock.setdefault(name, {"wait_us": 0, "hold_us": 0,
                                              "contended": 0})
            slot[leaf] = v
    wait = sum(s["wait_us"] for s in per_lock.values())
    hold = sum(s["hold_us"] for s in per_lock.values())
    frac = wait / (wait + hold) if (wait + hold) else 0.0
    return frac, per_lock


def host_blocked_fraction(pipeline_steps):
    """(blocked_s, wall_s, fraction) over `kind="pipeline_step"` records.
    The overlap-health number: a serial loop sits near 1.0 whenever the
    device step dominates; the pipelined loop's win is how far below
    that it lands."""
    blocked = sum(s.get("t_host_blocked_s", 0.0) for s in pipeline_steps)
    wall = sum(s.get("t_step_wall_s", 0.0) for s in pipeline_steps)
    return blocked, wall, (blocked / wall if wall > 0 else 0.0)


def diff(path_a: str, path_b: str) -> str:
    a, b = _load_snapshot(path_a), _load_snapshot(path_b)
    parts = [f"# monitor diff  A={path_a}  B={path_b}"]
    sa, sb = a.get("spans", {}), b.get("spans", {})
    rows = []
    for n in sorted(set(sa) | set(sb)):
        ta = sa.get(n, {}).get("total_s", 0.0)
        tb = sb.get(n, {}).get("total_s", 0.0)
        ca = sa.get(n, {}).get("calls", 0)
        cb = sb.get(n, {}).get("calls", 0)
        aa = ta / max(ca, 1)
        ab = tb / max(cb, 1)
        pct = (ab - aa) / aa * 100 if aa else float("inf") if ab else 0.0
        rows.append((n, f"{aa*1e3:.3f}", f"{ab*1e3:.3f}", f"{pct:+.1f}%"))
    if rows:
        parts.append("\n## span avg_ms A -> B\n"
                     + _fmt_table(rows, ["name", "A", "B", "delta"]))
    ca, cb = a.get("counters", {}), b.get("counters", {})
    rows = [(n, ca.get(n, 0), cb.get(n, 0), cb.get(n, 0) - ca.get(n, 0))
            for n in sorted(set(ca) | set(cb))
            if ca.get(n, 0) != cb.get(n, 0)]
    if rows:
        parts.append("\n## counter deltas\n"
                     + _fmt_table(rows, ["name", "A", "B", "delta"]))
    return "\n".join(parts)


def check(path: str, steady_after: int = 2,
          max_host_blocked_frac: float = None,
          max_retry_frac: float = None,
          max_heartbeat_miss_frac: float = None,
          max_gang_restarts: int = None,
          max_data_corrupt_frac: float = None,
          max_replay_batches: int = None,
          max_step_skew_frac: float = None,
          max_gang_resizes: int = None,
          max_shed_frac: float = None,
          max_p99_ms: float = None,
          max_lock_wait_frac: float = None,
          max_integrity_mismatches: int = None,
          max_ckpt_lag_steps: float = None,
          max_publish_staleness_steps: float = None,
          max_host_lag_steps: float = None,
          max_queue_wait_frac: float = None,
          max_pad_frac: float = None,
          require_quant_parity: bool = False,
          min_healthy_replicas: float = None,
          check_roll_convergence: bool = False,
          max_chaos_violations: int = None) -> int:
    """Return 0 when the metrics file is healthy, 1 otherwise (printed
    diagnosis either way).  Made for CI/bench scripts:

        python tools/perf_report.py --check metrics.jsonl || exit 1

    Two gates: recompile count must stay FLAT across steady-state steps,
    and — when --max-host-blocked-frac is given — the pipeline's
    steady-state host-blocked fraction must not exceed it (an overlap
    regression: the host is back to waiting on the device)."""
    try:
        with open(path) as f:
            lines = [json.loads(ln) for ln in f if ln.strip()]
    except FileNotFoundError:
        print(f"perf_report --check: {path} does not exist "
              f"(was a MonitorLogger attached?)")
        return 1
    except json.JSONDecodeError as e:
        print(f"perf_report --check: {path} is not valid JSONL: {e}")
        return 1
    steps = [r for r in lines if r.get("kind") == "step"]
    # a launcher- or loader-side metrics file (gang restarts, dist events,
    # data-layer counters) carries no executor step records; those gates
    # must still be checkable on it
    dist_gates_only = (max_heartbeat_miss_frac is not None
                       or max_gang_restarts is not None
                       or max_data_corrupt_frac is not None
                       or max_replay_batches is not None
                       or max_step_skew_frac is not None
                       or max_gang_resizes is not None
                       or max_shed_frac is not None
                       or max_p99_ms is not None
                       or max_lock_wait_frac is not None
                       or max_integrity_mismatches is not None
                       or max_ckpt_lag_steps is not None
                       or max_publish_staleness_steps is not None
                       or max_host_lag_steps is not None
                       or max_queue_wait_frac is not None
                       or max_pad_frac is not None
                       or require_quant_parity
                       or min_healthy_replicas is not None
                       or check_roll_convergence
                       or max_chaos_violations is not None) \
        and max_host_blocked_frac is None and max_retry_frac is None
    if not steps and not dist_gates_only:
        print(f"perf_report --check: {path} contains no step records "
              f"({len(lines)} lines)")
        return 1
    failures = []
    steady = steps[steady_after:]
    if not steps:
        pass  # dist-gates-only file: no recompile gate to run
    elif not steady:
        print(f"perf_report --check: only {len(steps)} steps, fewer than "
              f"--steady-after={steady_after}; recompile gate skipped")
    else:
        base = steady[0].get("recompiles_total", 0)
        bad = [(i + steady_after, s.get("recompiles_total", 0))
               for i, s in enumerate(steady)
               if s.get("recompiles_total", 0) != base]
        if bad:
            failures.append(
                f"recompile count moved in steady state (started at {base}): "
                f"steps {bad[:10]} — the executor is re-tracing; check feed "
                f"shape/dtype churn")
        else:
            print(f"perf_report --check: recompile count flat at {base} "
                  f"across {len(steady)} steady-state steps")
    if max_host_blocked_frac is not None:
        psteps = [r for r in lines if r.get("kind") == "pipeline_step"]
        steady_p = psteps[steady_after:]
        if not steady_p:
            failures.append(
                f"--max-host-blocked-frac given but no steady-state "
                f"pipeline_step records in {path} (found {len(psteps)} "
                f"total) — was train_loop run with the monitor enabled?")
        else:
            blocked, wall, frac = host_blocked_fraction(steady_p)
            if frac > max_host_blocked_frac:
                failures.append(
                    f"host-blocked fraction {frac:.3f} exceeds the "
                    f"--max-host-blocked-frac={max_host_blocked_frac} gate "
                    f"over {len(steady_p)} steady-state pipeline steps "
                    f"({blocked*1e3:.1f} ms blocked of {wall*1e3:.1f} ms) — "
                    f"overlap regression: raise max_inflight / log_period, "
                    f"or look for a new sync point in the step")
            else:
                print(f"perf_report --check: host-blocked fraction "
                      f"{frac:.3f} <= {max_host_blocked_frac} across "
                      f"{len(steady_p)} steady-state pipeline steps")
    if max_retry_frac is not None:
        frac = retry_fraction(lines)
        if frac > max_retry_frac:
            n_ev = sum(1 for r in lines
                       if r.get("kind") == "resilience_event"
                       and r.get("action") in RECOVERY_ACTIONS)
            failures.append(
                f"recovery fraction {frac:.3f} ({n_ev} skip/retry/rollback "
                f"events over {len(steps)} steps) exceeds the "
                f"--max-retry-frac={max_retry_frac} gate — the run is "
                f"spending its budget re-doing work; check the data "
                f"source, NaN guard hits, and device health")
        else:
            print(f"perf_report --check: recovery fraction {frac:.3f} <= "
                  f"{max_retry_frac}")
    if max_heartbeat_miss_frac is not None:
        frac = heartbeat_miss_fraction(lines)
        if frac > max_heartbeat_miss_frac:
            failures.append(
                f"heartbeat-miss fraction {frac:.4f} exceeds the "
                f"--max-heartbeat-miss-frac={max_heartbeat_miss_frac} gate "
                f"— peers keep falling past the liveness deadline "
                f"(flaky network, long GC/compile pauses, or a host on "
                f"its way out); check dist.heartbeat.* counters and the "
                f"stack dumps in worker stderr")
        else:
            print(f"perf_report --check: heartbeat-miss fraction "
                  f"{frac:.4f} <= {max_heartbeat_miss_frac}")
    if max_gang_restarts is not None:
        n = gang_restart_count(lines)
        if n > max_gang_restarts:
            failures.append(
                f"{n} gang restart(s) exceed the "
                f"--max-gang-restarts={max_gang_restarts} gate — each one "
                f"is a full rollback to the last coordinated checkpoint; "
                f"workers are dying beyond what the fault schedule "
                f"explains (see worker_death dist_event records)")
        else:
            print(f"perf_report --check: gang restarts {n} <= "
                  f"{max_gang_restarts}")
    if max_gang_resizes is not None:
        n = gang_resize_count(lines)
        if n > max_gang_resizes:
            shrinks = sum(1 for r in lines if r.get("kind") == "dist_event"
                          and r.get("action") == "gang_resize"
                          and r.get("direction") == "shrink")
            failures.append(
                f"{n} gang resize(s) ({shrinks} shrink(s)) exceed the "
                f"--max-gang-resizes={max_gang_resizes} gate — the gang's "
                f"world size is churning beyond what the fault schedule "
                f"explains (each shrink is lost capacity, each grow an "
                f"interruption of the shrunk gang; see gang_resize "
                f"dist_event records)")
        else:
            print(f"perf_report --check: gang resizes {n} <= "
                  f"{max_gang_resizes}")
    if max_data_corrupt_frac is not None:
        frac = data_corrupt_fraction(lines)
        if frac > max_data_corrupt_frac:
            failures.append(
                f"data-corrupt fraction {frac:.4f} exceeds the "
                f"--max-data-corrupt-frac={max_data_corrupt_frac} gate — "
                f"the dataset files are rotting faster than the corrupt "
                f"budget should have to cover (torn writes, bad disks, a "
                f"broken producer); check data.corrupt_chunks vs "
                f"data.chunks_scanned and regenerate the files")
        else:
            print(f"perf_report --check: data-corrupt fraction {frac:.4f} "
                  f"<= {max_data_corrupt_frac}")
    if max_step_skew_frac is not None:
        frac = step_skew_frac(lines)
        if frac > max_step_skew_frac:
            stragglers = sorted({r.get("rank") for r in lines
                                 if r.get("kind") == "dist_event"
                                 and r.get("action") == "straggler"})
            failures.append(
                f"per-step cross-rank skew fraction {frac} exceeds the "
                f"--max-step-skew-frac={max_step_skew_frac} gate — a rank "
                f"is holding the gang back "
                f"(straggler suspect(s): {stragglers or 'see gauge'}); "
                f"check dist.straggler_* counters, the offender's "
                f"telemetry in the straggler dist_events, and "
                f"tools/trace_merge.py over the gang's telemetry dir")
        else:
            print(f"perf_report --check: step skew fraction {frac} <= "
                  f"{max_step_skew_frac}")
    if (max_shed_frac is not None or max_p99_ms is not None) \
            and not _has_serving_evidence(lines):
        failures.append(
            f"serving gates given but {path} carries no serving evidence "
            f"(no serving_batch/serving_event records and no serving.* "
            f"counters/gauges in any snapshot) — was the monitor enabled "
            f"and a MonitorLogger attached to the serving run?")
        max_shed_frac = max_p99_ms = None  # no data to gate meaningfully
    if max_shed_frac is not None:
        frac = shed_fraction(lines)
        if frac > max_shed_frac:
            failures.append(
                f"serving shed fraction {frac:.4f} exceeds the "
                f"--max-shed-frac={max_shed_frac} gate — the server is "
                f"shedding more of its offered load than the round "
                f"budgeted; either traffic genuinely exceeds capacity "
                f"(scale out, widen buckets, raise the queue bound) or "
                f"batches got slower (check serving_batch t_infer_s and "
                f"the recompile gate above)")
        else:
            print(f"perf_report --check: serving shed fraction "
                  f"{frac:.4f} <= {max_shed_frac}")
    if max_p99_ms is not None:
        p99 = serving_p99_ms(lines)
        if p99 > max_p99_ms:
            failures.append(
                f"serving p99 latency {p99:.1f} ms exceeds the "
                f"--max-p99-ms={max_p99_ms} gate — the tail SLO broke; "
                f"with admission control on, suspects are batch execution "
                f"time (serving_batch t_infer_s), an inline recompile "
                f"(recompile gate above), or a queue bound sized past the "
                f"latency budget (max_queue x batch time is the worst-"
                f"case wait)")
        else:
            print(f"perf_report --check: serving p99 {p99:.1f} ms <= "
                  f"{max_p99_ms}")
    if max_queue_wait_frac is not None:
        if not _has_queue_wait_evidence(lines):
            failures.append(
                f"--max-queue-wait-frac given but {path} carries no "
                f"queue-wait evidence (no serving_trace records, no "
                f"queue_wait_frac-stamped serving_batch records, no "
                f"serving.queue_wait_frac gauge in any snapshot) — was "
                f"the monitor enabled on the serving run?  (zero "
                f"evidence must not gate green)")
        else:
            frac = queue_wait_fraction(lines)
            if frac > max_queue_wait_frac:
                failures.append(
                    f"serving queue-wait fraction {frac:.4f} exceeds the "
                    f"--max-queue-wait-frac={max_queue_wait_frac} gate — "
                    f"completed requests spent most of their latency "
                    f"budget QUEUED, not computing; either offered load "
                    f"sits past capacity (scale out, or let admission "
                    f"control shed it) or batches got slower (check "
                    f"serving_batch t_infer_s and serve_trace --top's "
                    f"per-bucket queue column)")
            else:
                print(f"perf_report --check: serving queue-wait fraction "
                      f"{frac:.4f} <= {max_queue_wait_frac}")
    if max_pad_frac is not None:
        if not _has_pad_evidence(lines):
            failures.append(
                f"--max-pad-frac given but {path} carries no pad-waste "
                f"evidence (no serving_batch records and no "
                f"serving.pad_rows/padded_rows counters in any snapshot) "
                f"— was the monitor enabled on the serving run?  (zero "
                f"evidence must not gate green)")
        else:
            frac = pad_fraction(lines)
            if frac > max_pad_frac:
                failures.append(
                    f"serving pad fraction {frac:.4f} exceeds the "
                    f"--max-pad-frac={max_pad_frac} gate — too much of "
                    f"the device compute is pad rows no client asked "
                    f"for; the bucket ladder is too coarse for the "
                    f"traffic's batch-size mix (add intermediate "
                    f"FLAGS_serving_buckets rungs; serve_trace --top "
                    f"names the wasteful buckets)")
            else:
                print(f"perf_report --check: serving pad fraction "
                      f"{frac:.4f} <= {max_pad_frac}")
    if require_quant_parity:
        qevs = quant_parity_events(lines)
        qrej = [r for r in lines if r.get("kind") == "serving_event"
                and r.get("action") == "publish_rejected"
                and "quant parity" in str(r.get("detail", ""))]
        if qrej:
            failures.append(
                f"{len(qrej)} quantized publish(es) REJECTED on the "
                f"accuracy-parity gate "
                f"({qrej[0].get('detail', '')!r}) — the int8 snapshot "
                f"drifted past FLAGS_serving_quant_atol from its fp32 "
                f"parent; re-quantize (check the scales) rather than "
                f"raising the tolerance")
        elif not qevs:
            failures.append(
                f"--require-quant-parity given but {path} carries no "
                f"quant_parity serving_event — no quantized snapshot "
                f"went through the publish ladder's parity gate (did "
                f"the run quantize a model, with the monitor "
                f"enabled?); zero evidence must not gate green")
        else:
            worst = max(float(r.get("max_abs_diff", 0.0) or 0.0)
                        for r in qevs)
            drifted = [r for r in qevs
                       if float(r.get("max_abs_diff", 0.0) or 0.0)
                       > float(r.get("atol", 0.0) or 0.0)]
            if drifted:
                failures.append(
                    f"quant parity event carries max_abs_diff "
                    f"{drifted[0].get('max_abs_diff')} past its own atol "
                    f"{drifted[0].get('atol')} — the gate recorded drift "
                    f"it should have rejected; the publisher's parity "
                    f"rung is broken")
            else:
                print(f"perf_report --check: quant parity held across "
                      f"{len(qevs)} quantized publish(es) "
                      f"(worst max|diff| {worst:.3e})")
    if min_healthy_replicas is not None:
        if not _has_fleet_evidence(lines):
            failures.append(
                f"--min-healthy-replicas given but {path} carries no "
                f"serving-fleet evidence (no fleet_event records and no "
                f"serving.fleet.* counters/gauges in any snapshot) — was "
                f"this a fleet router.jsonl (ServingFleet telemetry)?  "
                f"(zero evidence must not gate green)")
        else:
            n = fleet_healthy_replicas(lines)
            if n is None:
                failures.append(
                    f"--min-healthy-replicas given but no snapshot in "
                    f"{path} carries the serving.fleet.healthy_replicas "
                    f"gauge — the fleet supervisor's snapshot loop never "
                    f"wrote one (zero evidence must not gate green)")
            elif n < min_healthy_replicas:
                failures.append(
                    f"fleet ended with {n:g} healthy replica(s), below "
                    f"the --min-healthy-replicas={min_healthy_replicas:g} "
                    f"gate — replicas died past their restart budget or "
                    f"never came up; see the replica_dead / "
                    f"replica_abandoned fleet_events and the replica "
                    f"stderr spools in the fleet's logs/ dir")
            else:
                print(f"perf_report --check: healthy replicas {n:g} >= "
                      f"{min_healthy_replicas:g}")
    if check_roll_convergence:
        if not _has_fleet_evidence(lines):
            failures.append(
                f"--check-roll-convergence given but {path} carries no "
                f"serving-fleet evidence (no fleet_event records and no "
                f"serving.fleet.* counters/gauges in any snapshot) — "
                f"(zero evidence must not gate green)")
        else:
            unconverged = roll_convergence_failures(lines)
            if unconverged:
                failures.append(
                    f"{len(unconverged)} rolling publish(es) halted "
                    f"WITHOUT converging ({unconverged[:3]}) — no "
                    f"roll_rolled_back/roll_converged followed the "
                    f"roll_halted, so replicas may be split between "
                    f"versions; `serve_trace --fleet` renders the "
                    f"episode, and ROLL.json in the fleet root holds "
                    f"the persisted state to resume_roll() from")
            else:
                n_rolls = sum(1 for r in lines
                              if r.get("kind") == "fleet_event"
                              and r.get("action") == "roll_started")
                print(f"perf_report --check: roll convergence holds "
                      f"({n_rolls} roll(s) on record)")
    if max_lock_wait_frac is not None:
        if not _has_lock_evidence(lines):
            failures.append(
                f"--max-lock-wait-frac given but {path} carries no lock.* "
                f"counters in any snapshot — was the run launched with "
                f"FLAGS_lock_telemetry=1 and a MonitorLogger snapshot "
                f"written?  (zero evidence must not gate green)")
        else:
            frac, per_lock = lock_wait_fraction(lines)
            if frac > max_lock_wait_frac:
                worst = sorted(per_lock.items(),
                               key=lambda kv: -kv[1]["wait_us"])[:3]
                worst_s = ", ".join(
                    f"{n} (wait {s['wait_us']/1e3:.1f} ms / hold "
                    f"{s['hold_us']/1e3:.1f} ms, {s['contended']} "
                    f"contended)" for n, s in worst)
                failures.append(
                    f"lock wait fraction {frac:.4f} exceeds the "
                    f"--max-lock-wait-frac={max_lock_wait_frac} gate — "
                    f"threads are queueing on named locks instead of "
                    f"working; worst: {worst_s}.  Shrink the critical "
                    f"section (the concurrency lint's blocking-under-lock "
                    f"registry is the usual culprit list) or split the "
                    f"lock")
            else:
                print(f"perf_report --check: lock wait fraction "
                      f"{frac:.4f} <= {max_lock_wait_frac}")
    if max_integrity_mismatches is not None:
        if not _has_integrity_evidence(lines):
            failures.append(
                f"--max-integrity-mismatches given but {path} carries no "
                f"integrity evidence (no integrity_event records and no "
                f"integrity.* counters/gauges in any snapshot) — was the "
                f"sentinel armed (FLAGS_integrity_check_period > 0) and "
                f"a snapshot written?  (zero evidence must not gate "
                f"green)")
        else:
            n = integrity_mismatches(lines)
            if n > max_integrity_mismatches:
                where = sorted({r.get("action") for r in lines
                                if r.get("kind") == "integrity_event"
                                and r.get("action")
                                in INTEGRITY_MISMATCH_ACTIONS})
                failures.append(
                    f"{n} integrity mismatch(es) exceed the "
                    f"--max-integrity-mismatches="
                    f"{max_integrity_mismatches} gate "
                    f"({where or 'counters only'}) — the sentinel caught "
                    f"real silent corruption beyond what the fault "
                    f"schedule explains; scrub the checkpoint tree "
                    f"(tools/scrub.py) and check the host's memory/disk "
                    f"health")
            else:
                print(f"perf_report --check: integrity mismatches {n} "
                      f"<= {max_integrity_mismatches}")
    if max_ckpt_lag_steps is not None:
        if not _has_storage_evidence(lines):
            failures.append(
                f"--max-ckpt-lag-steps given but {path} carries no "
                f"checkpoint-storage evidence (no storage resilience "
                f"events, no checkpoint.* counters, no "
                f"resilience.ckpt_lag_steps gauge in any snapshot) — was "
                f"a CheckpointManager attached and a snapshot written?  "
                f"(zero evidence must not gate green)")
        else:
            lag = ckpt_lag_steps(lines)
            if lag > max_ckpt_lag_steps:
                rounds = sum(1 for r in lines
                             if r.get("kind") == "resilience_event"
                             and r.get("action") in ("storage_degraded",
                                                     "ckpt_round_skipped"))
                failures.append(
                    f"checkpoint lag of {lag:g} step(s) exceeds the "
                    f"--max-ckpt-lag-steps={max_ckpt_lag_steps} gate "
                    f"({rounds} degraded/skipped save round(s)) — "
                    f"training ran unprotected past the budget while "
                    f"storage failed; check resilience.ckpt_storage_"
                    f"errors, the storage_degraded events' causes, and "
                    f"the store itself (full disk, read-only mount, "
                    f"flaky NFS)")
            else:
                print(f"perf_report --check: checkpoint lag {lag:g} <= "
                      f"{max_ckpt_lag_steps} steps")
    if max_publish_staleness_steps is not None:
        if not _has_publish_evidence(lines):
            failures.append(
                f"--max-publish-staleness-steps given but {path} carries "
                f"no publish-cadence evidence (no publish/publish_failed "
                f"resilience events, no serving.publishes counter, no "
                f"serving.publish_staleness_steps gauge in any snapshot) "
                f"— was resilient_train_loop's publish_hook armed with "
                f"FLAGS_publish_period_steps > 0?  (zero evidence must "
                f"not gate green)")
        else:
            st = publish_staleness_steps(lines)
            if st > max_publish_staleness_steps:
                fails = sum(1 for r in lines
                            if r.get("kind") == "resilience_event"
                            and r.get("action") == "publish_failed")
                failures.append(
                    f"publish-to-serving staleness of {st:g} step(s) "
                    f"exceeds the --max-publish-staleness-steps="
                    f"{max_publish_staleness_steps} gate ({fails} failed "
                    f"publish period(s)) — the serving fleet ran on a "
                    f"snapshot further behind training than the cadence "
                    f"SLO allows; check serving.publish_errors, the "
                    f"publish_failed events' details, and the store / "
                    f"publish ladder they name")
            else:
                print(f"perf_report --check: publish staleness {st:g} <= "
                      f"{max_publish_staleness_steps} steps")
    if max_host_lag_steps is not None:
        if not _has_sparse_evidence(lines):
            failures.append(
                f"--max-host-lag-steps given but {path} carries no "
                f"host-tier evidence (no sparse_event records, no "
                f"sparse.*/ps.* counters, no sparse.host_lag_steps gauge "
                f"in any snapshot) — did the run use HostTableEmbedding "
                f"/ TieredEmbedding at all?  (zero evidence must not "
                f"gate green)")
        else:
            lag = host_lag_steps(lines)
            if lag > max_host_lag_steps:
                n = sum(1 for r in lines
                        if r.get("kind") == "sparse_event"
                        and r.get("action") == "host_tier_degraded")
                failures.append(
                    f"host-tier lag of {lag:g} consecutive degraded "
                    f"step(s) exceeds the --max-host-lag-steps="
                    f"{max_host_lag_steps} gate ({n} degraded step "
                    f"record(s)) — the cold embedding tail trained "
                    f"hot-shard-only longer than the budget allows; "
                    f"check the pserver supervisor's restart budget "
                    f"(pserver_give_up fleet events) and ps.retries")
            else:
                print(f"perf_report --check: host-tier lag {lag:g} <= "
                      f"{max_host_lag_steps} steps")
    if max_replay_batches is not None:
        n = replayed_batches(lines)
        if n > max_replay_batches:
            failures.append(
                f"{n} batch(es) replayed to fast-forward on resume exceed "
                f"the --max-replay-batches={max_replay_batches} gate — the "
                f"data source is stateless, so every resume is O(dataset); "
                f"give the loop a checkpointable reader (stream-state "
                f"protocol) to make resume an O(1) seek")
        else:
            print(f"perf_report --check: replayed batches {n} <= "
                  f"{max_replay_batches}")
    if max_chaos_violations is not None:
        if not _has_chaos_evidence(lines):
            failures.append(
                f"--max-chaos-violations given but {path} carries no "
                f"chaos-campaign evidence (no chaos_event records, no "
                f"chaos.* counters in any snapshot) — was "
                f"tools/chaos_campaign.py run with --metrics pointed at "
                f"this file?  (zero evidence must not gate green)")
        else:
            n = chaos_violation_count(lines)
            if n > max_chaos_violations:
                sched = sum(1 for r in lines
                            if r.get("kind") == "chaos_event"
                            and r.get("event") == "schedule")
                failures.append(
                    f"{n} chaos invariant violation(s) over {sched} "
                    f"schedule(s) exceed the --max-chaos-violations="
                    f"{max_chaos_violations} gate — a seeded multi-fault "
                    f"schedule broke a cross-subsystem invariant; the "
                    f"failing chaos_event records name the spec, and the "
                    f"campaign's CHAOS_REPRO.json carries the shrunk "
                    f"minimal repro (replay it with tools/"
                    f"chaos_campaign.py --replay)")
            else:
                print(f"perf_report --check: chaos violations {n} <= "
                      f"{max_chaos_violations}")
    if failures:
        for f_ in failures:
            print(f"perf_report --check: {f_}")
        return 1
    print(f"perf_report --check: OK — {len(steps)} steps")
    return 0


def postmortem(root: str, last_n: int = 30) -> int:
    """Render a merged post-mortem from a gang's harvested telemetry
    (`perf_report --postmortem <telemetry_root>`): every rank's
    BLACKBOX.p<rank>.json flight-recorder dump plus the supervisor's
    INCIDENT.i<k>.json files, folded into one last-N-steps timeline that
    names the dead rank(s).  Returns 0 when at least one black box was
    found, 1 otherwise."""
    import glob as _glob

    boxes = []
    for p in sorted(_glob.glob(os.path.join(root, "**", "BLACKBOX.p*.json"),
                               recursive=True)):
        try:
            with open(p) as f:
                doc = json.load(f)
            doc["_path"] = p
            boxes.append(doc)
        except (OSError, json.JSONDecodeError):
            continue
    incidents = []
    for p in sorted(_glob.glob(os.path.join(root, "**", "INCIDENT*.json"),
                               recursive=True)):
        try:
            with open(p) as f:
                incidents.append(json.load(f))
        except (OSError, json.JSONDecodeError):
            continue
    if not boxes and not incidents:
        print(f"perf_report --postmortem: no BLACKBOX.p*.json or "
              f"INCIDENT*.json under {root} — was the gang telemetry "
              f"plane armed (run_gang exports PADDLE_TELEMETRY_DIR)?")
        return 1

    print(f"# gang post-mortem  {root}")
    # who died: the supervisor's incident ledger is authoritative.  Exit
    # 43 (EXIT_PEER_FAILURE) is a survivor REACTING to someone else's
    # death — list it separately so "dead rank(s)" names the rank that
    # actually went down, not everyone its death took with it.
    details = {d["rank"]: d for inc in incidents for d in inc.get("dead", [])}
    reacting = sorted(r for r, d in details.items()
                      if d.get("returncode") == 43)
    dead = sorted(r for r in details if r not in set(reacting)) or reacting
    if details:
        print(f"dead rank(s): {dead} — " + "; ".join(
            f"rank {r}: returncode {details[r]['returncode']}"
            + (" (signaled)" if details[r].get("signaled") else "")
            + (" [classified]" if details[r].get("classified") else "")
            for r in dead))
        if reacting and reacting != dead:
            print(f"peer-failure reactions (exit 43): {reacting}")
    elif boxes:
        suspects = sorted({b.get("rank") for b in boxes
                           if not str(b.get("reason", "")).startswith(
                               ("peer_failure", "sigterm"))})
        if suspects:
            print(f"dead rank suspect(s) from black-box reasons: {suspects}")

    print(f"\n## black boxes ({len(boxes)})")
    rows = [("rank", "reason", "last_step", "records", "path")]
    for b in sorted(boxes, key=lambda b: (b.get("rank", -1), b["_path"])):
        steps = b.get("steps", [])
        last = max((s.get("step", 0) for s in steps
                    if isinstance(s.get("step"), int)), default="-")
        rows.append((b.get("rank", "?"), b.get("reason", "?"), last,
                     len(steps), os.path.relpath(b["_path"], root)))
    print(_fmt_table(rows[1:], list(rows[0])))

    # merged last-N timeline: every rank's ring, one stream, by wall time
    merged = []
    for b in boxes:
        for s in b.get("steps", []):
            if isinstance(s, dict) and s.get("ts") is not None:
                merged.append((float(s["ts"]),
                               s.get("lane", b.get("rank", "?")), s))
    merged.sort(key=lambda t: t[0])
    tail = merged[-last_n:]
    if tail:
        t0 = tail[0][0]
        print(f"\n## merged timeline (last {len(tail)} records across "
              f"ranks; t=0 at {t0:.3f})")
        rows = []
        for ts, rank, s in tail:
            kind = s.get("kind", "step")
            detail = ""
            if kind == "step":
                detail = (f"step {s.get('step')} "
                          f"exec {s.get('t_execute_s', s.get('t_dispatch_s', 0)) * 1e3:.1f}ms")
            elif kind == "dist_event":
                detail = f"{s.get('action')} {s.get('peers', s.get('rank', ''))}"
            elif kind == "pipeline_step":
                detail = f"pstep {s.get('pipeline_step')}"
            else:
                detail = str({k: v for k, v in s.items()
                              if k not in ("kind", "ts", "lane")})[:60]
            rows.append((f"{ts - t0:+8.3f}s", f"r{rank}", kind, detail))
        print(_fmt_table(rows, ["t", "rank", "kind", "detail"]))
    for b in boxes:
        c = b.get("counters", {})
        dist = {k: v for k, v in c.items() if k.startswith("dist.") and v}
        if dist:
            print(f"\nrank {b.get('rank')} dist counters: {dist}")
    return 0


def main(argv=None):
    ap = argparse.ArgumentParser(
        description=__doc__, formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("paths", nargs="*", help="snapshot.json (render mode)")
    ap.add_argument("--diff", nargs=2, metavar=("A", "B"),
                    help="diff two snapshots")
    ap.add_argument("--check", metavar="METRICS_JSONL",
                    help="CI gate over a MonitorLogger JSONL file")
    ap.add_argument("--postmortem", metavar="TELEMETRY_DIR",
                    help="render a merged gang post-mortem from harvested "
                         "BLACKBOX.p<rank>.json flight-recorder dumps + "
                         "INCIDENT files (paddle_tpu.launch telemetry "
                         "root), naming the dead rank(s) and the last-N-"
                         "steps timeline across ranks")
    ap.add_argument("--postmortem-last-n", type=int, default=30,
                    metavar="N",
                    help="--postmortem: merged-timeline depth (default 30)")
    ap.add_argument("--steady-after", type=int, default=2,
                    help="steps to skip before the recompile-flat gate "
                         "(default 2: startup + first real step)")
    ap.add_argument("--max-host-blocked-frac", type=float, default=None,
                    metavar="FRAC",
                    help="additionally gate the pipeline's steady-state "
                         "host-blocked fraction (pipeline_step records from "
                         "paddle_tpu.pipeline.train_loop) at <= FRAC")
    ap.add_argument("--max-retry-frac", type=float, default=None,
                    metavar="FRAC",
                    help="additionally gate recovery events per step "
                         "(resilience_event records from paddle_tpu."
                         "resilience.resilient_train_loop) at <= FRAC")
    ap.add_argument("--max-heartbeat-miss-frac", type=float, default=None,
                    metavar="FRAC",
                    help="gate heartbeat-miss transitions per beat sent "
                         "(dist.heartbeat.* counters from paddle_tpu."
                         "dist_resilience, newest snapshot in the file) "
                         "at <= FRAC")
    ap.add_argument("--max-gang-restarts", type=int, default=None,
                    metavar="N",
                    help="gate gang restarts (paddle_tpu.launch "
                         "gang_restart dist_event records / "
                         "dist.gang_restarts counter) at <= N")
    ap.add_argument("--max-gang-resizes", type=int, default=None,
                    metavar="N",
                    help="gate elastic world-size changes "
                         "(paddle_tpu.launch gang_resize dist_event "
                         "records / dist.gang_resizes counter) at <= N — "
                         "each shrink is capacity lost, each grow an "
                         "interruption of the shrunk gang")
    ap.add_argument("--max-data-corrupt-frac", type=float, default=None,
                    metavar="FRAC",
                    help="gate corrupt RecordIO chunks per chunk scanned "
                         "(data.corrupt_chunks / data.chunks_scanned "
                         "counters, newest snapshot) at <= FRAC")
    ap.add_argument("--max-replay-batches", type=int, default=None,
                    metavar="N",
                    help="gate the resume cost: batches replayed to "
                         "fast-forward a stateless data source "
                         "(replay_fast_forward resilience events) at <= N "
                         "— 0 asserts every source resumes via the O(1) "
                         "stream-state seek")
    ap.add_argument("--max-shed-frac", type=float, default=None,
                    metavar="FRAC",
                    help="gate serving admission-control sheds per "
                         "request offered (serving.shed / "
                         "serving.requests counters, shed serving_event "
                         "records as fallback) at <= FRAC — the overload "
                         "budget a serving round may spend")
    ap.add_argument("--max-p99-ms", type=float, default=None,
                    metavar="MS",
                    help="gate serving p99 request latency "
                         "(serving.p99_ms gauge, serving_batch "
                         "lat_ms_max fallback) at <= MS — the tail SLO "
                         "shedding must hold under overload")
    ap.add_argument("--require-quant-parity", action="store_true",
                    help="require the file to carry at least one "
                         "quant_parity serving_event (the publish "
                         "ladder's accuracy gate over a quantized "
                         "snapshot, paddle_tpu/serving/publisher.py) "
                         "with max_abs_diff within its atol, and no "
                         "quant-parity publish rejection — a "
                         "quantized-serving run's metrics "
                         "gate.  Fails on a file with no quant evidence "
                         "at all (zero evidence must not gate green)")
    ap.add_argument("--max-lock-wait-frac", type=float, default=None,
                    metavar="FRAC",
                    help="gate named-lock contention at <= FRAC: "
                         "wait/(wait+hold) over the lock.* counters "
                         "FLAGS_lock_telemetry records "
                         "(paddle_tpu/core/locks.py).  Fails on a file "
                         "with no lock telemetry at all — zero evidence "
                         "must not gate green")
    ap.add_argument("--max-integrity-mismatches", type=int, default=None,
                    metavar="N",
                    help="gate silent-corruption detections at <= N: "
                         "integrity_event records (live digest "
                         "divergences + at-rest file mismatches; "
                         "walk-back ckpt_rejected echoes render but "
                         "don't double-bill) with integrity.* counter "
                         "fallback (paddle_tpu/integrity.py).  Fails on "
                         "a file with no integrity evidence at all — "
                         "zero evidence must not gate green")
    ap.add_argument("--max-ckpt-lag-steps", type=float, default=None,
                    metavar="N",
                    help="gate the worst checkpoint lag — steps training "
                         "ran past its last committed checkpoint while "
                         "storage failed (storage_degraded / "
                         "ckpt_round_skipped resilience events, "
                         "resilience.ckpt_lag_steps gauge fallback; "
                         "paddle_tpu/checkpoint_manager.py degraded "
                         "mode) — at <= N.  0 asserts every save round "
                         "committed.  Fails on a file with no "
                         "checkpoint-storage evidence at all — zero "
                         "evidence must not gate green")
    ap.add_argument("--max-publish-staleness-steps", type=float,
                    default=None, metavar="N",
                    help="gate the worst publish-to-serving staleness — "
                         "steps training ran past the last snapshot the "
                         "serving tier had (publish_failed resilience "
                         "events' staleness, "
                         "serving.publish_staleness_steps gauge "
                         "fallback; resilient_train_loop's publish hook, "
                         "ISSUE 19) — at <= N.  Fails on a file with no "
                         "publish-cadence evidence at all — zero "
                         "evidence must not gate green")
    ap.add_argument("--max-host-lag-steps", type=float, default=None,
                    metavar="N",
                    help="gate the worst host-tier outage — consecutive "
                         "steps the sparse cold tail trained degraded "
                         "(hot-shard-only) while the parameter server "
                         "was down (host_tier_degraded sparse events, "
                         "sparse.host_lag_steps gauge fallback; "
                         "paddle_tpu/param_server.py degraded mode) — "
                         "at <= N.  Fails on a file with no host-tier "
                         "evidence at all — zero evidence must not gate "
                         "green")
    ap.add_argument("--max-queue-wait-frac", type=float, default=None,
                    metavar="FRAC",
                    help="gate serving latency attribution: the fraction "
                         "of completed requests' wall time spent QUEUED "
                         "(serving_trace span trees from the ISSUE-16 "
                         "request tracing; serving.queue_wait_frac gauge "
                         "and queue_wait_frac-stamped serving_batch "
                         "records as fallbacks) at <= FRAC.  Fails on a "
                         "file with no queue-wait evidence at all — zero "
                         "evidence must not gate green")
    ap.add_argument("--max-pad-frac", type=float, default=None,
                    metavar="FRAC",
                    help="gate pad-to-bucket waste: pad rows per "
                         "padded-batch row (serving.pad_rows / "
                         "(serving.rows + serving.pad_rows) counters, "
                         "serving_batch bucket-vs-rows fallback) at <= "
                         "FRAC — the device compute a serving round may "
                         "spend on rows no client asked for.  Fails on a "
                         "file with no pad evidence at all — zero "
                         "evidence must not gate green")
    ap.add_argument("--min-healthy-replicas", type=float, default=None,
                    metavar="N",
                    help="gate the serving fleet's final health: the "
                         "newest serving.fleet.healthy_replicas gauge "
                         "(ServingFleet router.jsonl snapshots) must be "
                         ">= N.  Fails on a file with no fleet evidence "
                         "at all — zero evidence must not gate green")
    ap.add_argument("--check-roll-convergence", action="store_true",
                    help="require every halted rolling publish to have "
                         "converged: a roll_halted fleet_event with no "
                         "matching roll_rolled_back/roll_converged "
                         "fails (per roll ctl id; counters-only files "
                         "fall back to the events[*] counter balance).  "
                         "Fails on a file with no fleet evidence at all")
    ap.add_argument("--max-step-skew-frac", type=float, default=None,
                    metavar="FRAC",
                    help="gate the MAX sustained straggler lag, in step "
                         "units (straggler dist_event records from the "
                         "live detector, dist.step_skew_frac gauge "
                         "fallback), at <= FRAC.  The live detector only "
                         "emits episodes at lag >= "
                         "FLAGS_dist_straggler_lag_steps (default 1.0), "
                         "so a gate under 1.0 means 'no straggler "
                         "episode at all'; tools/trace_merge.py --check "
                         "shares the flag name but gates the MEAN "
                         "arrival skew per correlated step instead")
    ap.add_argument("--max-chaos-violations", type=int, default=None,
                    metavar="N",
                    help="gate the chaos campaign's invariant violations "
                         "(chaos.invariant_violations counter, failed "
                         "schedule chaos_event records) at <= N.  Fails "
                         "on a file with no chaos evidence at all — zero "
                         "evidence must not gate green")
    args = ap.parse_args(argv)
    if args.postmortem:
        return postmortem(args.postmortem, last_n=args.postmortem_last_n)
    if args.check:
        return check(args.check, args.steady_after,
                     args.max_host_blocked_frac, args.max_retry_frac,
                     args.max_heartbeat_miss_frac, args.max_gang_restarts,
                     args.max_data_corrupt_frac, args.max_replay_batches,
                     args.max_step_skew_frac, args.max_gang_resizes,
                     args.max_shed_frac, args.max_p99_ms,
                     args.max_lock_wait_frac,
                     args.max_integrity_mismatches,
                     args.max_ckpt_lag_steps,
                     max_publish_staleness_steps=(
                         args.max_publish_staleness_steps),
                     max_host_lag_steps=args.max_host_lag_steps,
                     max_queue_wait_frac=args.max_queue_wait_frac,
                     max_pad_frac=args.max_pad_frac,
                     require_quant_parity=args.require_quant_parity,
                     min_healthy_replicas=args.min_healthy_replicas,
                     check_roll_convergence=args.check_roll_convergence,
                     max_chaos_violations=args.max_chaos_violations)
    if args.diff:
        print(diff(*args.diff))
        return 0
    if not args.paths:
        ap.print_help()
        return 2
    for p in args.paths:
        print(render(p))
    return 0


if __name__ == "__main__":
    sys.exit(main())
