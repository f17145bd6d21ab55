#!/usr/bin/env python
"""Merge per-rank gang telemetry into one timeline + straggler attribution.

The gang telemetry plane (paddle_tpu.launch run_gang exports
PADDLE_TELEMETRY_DIR; fleet.init arms each worker via
monitor.init_worker_telemetry) leaves one directory per incarnation:

    <telemetry_root>/i<k>/metrics.p<rank>.jsonl   rank-tagged step records
    <telemetry_root>/i<k>/trace.p<rank>.json      per-rank Chrome trace
    <telemetry_root>/i<k>/BLACKBOX.p<rank>.json   flight-recorder dumps

This tool turns N disjoint per-rank files into answers:

    python tools/trace_merge.py DIR --out merged.json
        Merge every rank's Chrome trace into ONE timeline with one pid
        lane per rank (perfetto/chrome://tracing renders one row per
        worker, collectives and steps aligned).

    python tools/trace_merge.py DIR [--report skew.json]
        Correlate collective-bearing steps across ranks by
        (collective_signature digest, step number) from the per-rank
        step-record streams, and print per-collective SKEW ATTRIBUTION:
        which rank arrived last at each correlated step's dispatch, by
        how much, and which rank is the gang's straggler overall.

    python tools/trace_merge.py DIR --check --max-step-skew-frac 0.5
        CI gate: fail when the mean per-step cross-rank skew exceeds the
        given fraction of the MEDIAN step time (median, not mean: a
        periodic slow step — checkpoint flush, re-compile — must not
        inflate the denominator and hide real skew).

Arrival time is the record's `ts_dispatch` (wall clock when the step
entered dispatch, BEFORE the blocking collective) — the rank that arrives
last is the rank everyone else waited for.  Single-host gangs share one
clock; across hosts the numbers inherit NTP skew, so treat sub-millisecond
attribution there with suspicion.
"""
from __future__ import annotations

import argparse
import glob
import json
import os
import re
import sys
from typing import Dict, List, Optional

_METRICS_RE = re.compile(r"metrics\.p(\d+)\.jsonl$")
_TRACE_RE = re.compile(r"trace\.p(\d+)\.json$")
_INC_RE = re.compile(r"^i(\d+)$")


def _incarnation_of(path: str) -> int:
    """The i<k> incarnation a telemetry file belongs to (0 for files that
    sit directly in a single-incarnation dir)."""
    m = _INC_RE.match(os.path.basename(os.path.dirname(path)))
    return int(m.group(1)) if m else 0


def find_rank_files(root: str) -> Dict[str, Dict[int, str]]:
    """Walk `root` (a telemetry dir, or a telemetry root holding i<k>
    incarnation dirs) and collect per-rank metrics/trace files.  When the
    same rank appears in several incarnation dirs, the newest (highest
    NUMERIC incarnation — i10 sorts after i9, not between i1 and i2) wins
    for traces; metrics files are all kept per rank, incarnation order,
    so a restarted gang's history stays whole."""
    metrics: Dict[int, List[str]] = {}
    traces: Dict[int, str] = {}
    paths = sorted(glob.glob(os.path.join(root, "**", "*"), recursive=True),
                   key=lambda p: (_incarnation_of(p), p))
    for path in paths:
        base = os.path.basename(path)
        m = _METRICS_RE.match(base)
        if m:
            metrics.setdefault(int(m.group(1)), []).append(path)
            continue
        m = _TRACE_RE.match(base)
        if m:
            traces[int(m.group(1))] = path
    return {"metrics": metrics, "traces": traces}


def load_records(paths) -> List[dict]:
    """All JSONL records from one rank's metrics file(s), in file order;
    unparseable lines are skipped (a SIGKILL can tear the last line).
    Each record is stamped with its source file's incarnation (`_inc`) so
    cross-rank correlation never pairs step N of incarnation 0 with step
    N of incarnation 1 — global step numbering restarts with the gang,
    and conflating them reads the restart gap as skew."""
    if isinstance(paths, str):
        paths = [paths]
    out = []
    for p in paths:
        inc = _incarnation_of(p)
        try:
            with open(p) as f:
                for ln in f:
                    ln = ln.strip()
                    if not ln:
                        continue
                    try:
                        rec = json.loads(ln)
                    except json.JSONDecodeError:
                        continue
                    if isinstance(rec, dict):
                        rec.setdefault("_inc", inc)
                        out.append(rec)
        except OSError:
            continue
    return out


def merge_traces(traces: Dict[int, str], out_path: str) -> int:
    """Merge per-rank Chrome traces into one timeline, pid = rank; returns
    the number of span events written."""
    merged = []
    n = 0
    for rank in sorted(traces):
        try:
            with open(traces[rank]) as f:
                doc = json.load(f)
        except (OSError, json.JSONDecodeError):
            continue
        merged.append({"name": "process_name", "ph": "M", "pid": rank,
                       "args": {"name": f"rank{rank}"}})
        for ev in doc.get("traceEvents", []):
            if ev.get("ph") == "M":
                continue  # one fresh metadata row per rank, above
            ev = dict(ev)
            ev["pid"] = rank
            merged.append(ev)
            n += 1
    with open(out_path, "w") as f:
        json.dump({"traceEvents": merged}, f)
    return n


def _arrival(rec: dict) -> Optional[float]:
    """A step record's dispatch-entry wall time (ts_dispatch; records
    predating the field fall back to the record timestamp)."""
    ts = rec.get("ts_dispatch", rec.get("ts"))
    try:
        return float(ts)
    except (TypeError, ValueError):
        return None


def correlate(per_rank: Dict[int, List[dict]], steady_after: int = 2) -> dict:
    """Cross-rank skew attribution over per-rank step-record streams.

    Steps are correlated by (incarnation, csig, step number): csig is the
    digest of the program's static collective signature (identical on
    every rank by construction — the build-time lint guarantees the
    order), so a key names ONE gang-wide collective-bearing step; the
    incarnation component keeps a restarted gang's replayed step numbers
    from pairing across incarnations (the restart gap is downtime, not
    skew).  For each key observed on >= 2 ranks: skew_s = last arrival -
    first arrival, and the last rank is the one the collective waited
    for.

    The first `steady_after` correlated steps of each csig are marked
    warm-in and excluded from the aggregate skew/straggler stats (same
    convention as perf_report --steady-after): ranks pay compile at
    different moments, and that startup skew would otherwise drown the
    steady-state signal the gates care about.  Per-step entries keep the
    warm-in rows, flagged."""
    arrivals: Dict[tuple, Dict[int, float]] = {}
    step_times: Dict[int, List[float]] = {}
    for rank, recs in per_rank.items():
        prev_ts = prev_inc = None
        for r in recs:
            if r.get("kind", "step") != "step":
                continue
            ts = _arrival(r)
            if ts is None:
                continue
            inc = r.get("_inc", 0)
            if inc != prev_inc:
                prev_ts = None  # restart gap is downtime, not a step time
                prev_inc = inc
            if prev_ts is not None and ts > prev_ts:
                step_times.setdefault(rank, []).append(ts - prev_ts)
            prev_ts = ts
            csig = r.get("csig")
            if csig is None:
                continue  # no collectives: nothing to correlate
            arrivals.setdefault(
                (r.get("_inc", 0), csig, r.get("step")), {})[rank] = ts

    def _median(v):
        s = sorted(v)
        return s[len(s) // 2] if s else 0.0

    median_step_s = _median([t for ts in step_times.values() for t in ts])
    entries = []
    seen_per_csig: Dict[tuple, int] = {}
    for (inc, csig, step), by_rank in sorted(
            arrivals.items(), key=lambda kv: min(kv[1].values())):
        if len(by_rank) < 2:
            continue
        first = min(by_rank, key=by_rank.get)
        last = max(by_rank, key=by_rank.get)
        skew_s = by_rank[last] - by_rank[first]
        idx = seen_per_csig.get((inc, csig), 0)
        seen_per_csig[(inc, csig)] = idx + 1
        e = {
            "csig": csig, "step": step, "incarnation": inc,
            "skew_s": round(skew_s, 6),
            "skew_frac": (round(skew_s / median_step_s, 4)
                          if median_step_s else None),
            "first_rank": first, "last_rank": last,
            "arrivals": {str(r): ts for r, ts in sorted(by_rank.items())},
        }
        if idx < steady_after:
            e["warmup"] = True
        entries.append(e)
    # NO fallback to warm-in rows when nothing steady survives: compile
    # skew is exactly what the exclusion exists to keep out of the
    # aggregates, and a gate fed warm-in data would name a healthy rank
    # straggler.  Too-short runs report entries only; the --check gate
    # treats missing aggregates as missing evidence (fail), not as clean.
    steady = [e for e in entries if not e.get("warmup")]
    last_counts: Dict[int, int] = {}
    for e in steady:
        last_counts[e["last_rank"]] = last_counts.get(e["last_rank"], 0) + 1
    report = {
        "kind": "skew_report",
        "ranks": sorted(per_rank),
        "steps_correlated": len(entries),
        "steady_steps": len(steady),
        "median_step_s": round(median_step_s, 6),
        "entries": entries,
        "last_arrival_counts": {str(r): c
                                for r, c in sorted(last_counts.items())},
    }
    if steady:
        skews = [e["skew_s"] for e in steady]
        report["max_skew_s"] = round(max(skews), 6)
        report["mean_skew_s"] = round(sum(skews) / len(skews), 6)
        if median_step_s:
            report["max_skew_frac"] = round(max(skews) / median_step_s, 4)
            report["mean_skew_frac"] = round(
                sum(skews) / len(skews) / median_step_s, 4)
        # the straggler: the rank the gang waited for most often — only
        # attributed when it was last for a clear majority of the
        # correlated steps (50/50 on two ranks is noise, not a straggler)
        # AND the waiting was material (>10% of a step when it was last;
        # on a healthy gang SOMEONE is always technically last, by µs)
        straggler, n_last = max(last_counts.items(), key=lambda kv: kv[1])
        frac_last = n_last / len(steady)
        skew_when_last = sum(e["skew_s"] for e in steady
                             if e["last_rank"] == straggler) / n_last
        # no step-time baseline (a single correlated step) means no way
        # to judge materiality — never attribute from that little data
        if (frac_last > 0.5 and median_step_s
                and skew_when_last > 0.1 * median_step_s):
            report["straggler"] = {
                "rank": straggler, "last_frac": round(frac_last, 4),
                "mean_skew_s_when_last": round(skew_when_last, 6),
            }
    return report


def skew_from_dir(root: str) -> Optional[dict]:
    """Skew report over every rank's metrics stream under `root`; None when
    fewer than two ranks left telemetry."""
    files = find_rank_files(root)
    if len(files["metrics"]) < 2:
        return None
    per_rank = {r: load_records(ps) for r, ps in files["metrics"].items()}
    return correlate(per_rank)


def render(report: dict) -> str:
    parts = [f"# gang skew report  ranks={report['ranks']}  "
             f"{report['steps_correlated']} correlated steps  "
             f"({report.get('steady_steps', 0)} steady)  "
             f"median step {report['median_step_s'] * 1e3:.3f} ms"]
    if report.get("entries") and report.get("mean_skew_s") is None:
        parts.append("all correlated steps are warm-in (compile skew): "
                     "no steady-state aggregates — run longer to gate")
    if report.get("mean_skew_s") is not None:
        parts.append(
            f"skew: mean {report['mean_skew_s'] * 1e3:.3f} ms "
            f"(frac {report.get('mean_skew_frac')}), "
            f"max {report['max_skew_s'] * 1e3:.3f} ms "
            f"(frac {report.get('max_skew_frac')})")
        parts.append("last-arrival counts: " + ", ".join(
            f"rank{r}={c}" for r, c in report["last_arrival_counts"].items()))
        st = report.get("straggler")
        if st:
            parts.append(
                f"STRAGGLER: rank {st['rank']} arrived last on "
                f"{st['last_frac'] * 100:.0f}% of correlated steps, "
                f"mean skew {st['mean_skew_s_when_last'] * 1e3:.3f} ms "
                f"when last")
        else:
            parts.append("no dominant straggler (last arrivals balanced)")
        head = report["entries"][:20]
        parts.append("per-step (first 20):")
        for e in head:
            parts.append(
                f"  step {e['step']} csig {e['csig']}: rank "
                f"{e['last_rank']} last by {e['skew_s'] * 1e3:.3f} ms "
                f"(frac {e['skew_frac']})")
    elif not report.get("entries"):
        parts.append("no cross-rank correlated steps (need csig-stamped "
                     "step records from >= 2 ranks)")
    return "\n".join(parts)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("dir", help="telemetry dir (or telemetry root with "
                                "i<k> incarnation dirs)")
    ap.add_argument("--out", default=None, metavar="MERGED_JSON",
                    help="write the merged per-rank-lane Chrome trace here")
    ap.add_argument("--report", default=None, metavar="SKEW_JSON",
                    help="write the skew report JSON here")
    ap.add_argument("--check", action="store_true",
                    help="gate mode: exit 1 when the skew gate fails")
    ap.add_argument("--max-step-skew-frac", type=float, default=0.5,
                    metavar="FRAC",
                    help="--check: ceiling on MEAN per-step skew as a "
                         "fraction of the MEDIAN step time (default 0.5)")
    args = ap.parse_args(argv)

    files = find_rank_files(args.dir)
    if args.out:
        n = merge_traces(files["traces"], args.out)
        print(f"trace_merge: wrote {n} events from "
              f"{len(files['traces'])} rank trace(s) to {args.out}")
    if not files["metrics"]:
        print(f"trace_merge: no metrics.p<rank>.jsonl under {args.dir}")
        if args.check:
            # a gate with zero evidence must not pass green
            return 1
        return 0 if args.out else 2
    per_rank = {r: load_records(ps) for r, ps in files["metrics"].items()}
    report = correlate(per_rank)
    print(render(report))
    if args.report:
        with open(args.report, "w") as f:
            json.dump(report, f, indent=1)
    if args.check:
        frac = report.get("mean_skew_frac")
        if frac is None:
            print("trace_merge --check: no correlated steps to gate on")
            return 1
        if frac > args.max_step_skew_frac:
            st = report.get("straggler", {})
            print(f"trace_merge --check: mean step skew fraction {frac} "
                  f"exceeds --max-step-skew-frac={args.max_step_skew_frac}"
                  + (f" — rank {st['rank']} is the straggler" if st else ""))
            return 1
        print(f"trace_merge --check: mean step skew fraction {frac} <= "
              f"{args.max_step_skew_frac}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
