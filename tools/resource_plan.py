#!/usr/bin/env python
"""Render / CI-gate static resource plans (paddle_tpu/core/resource_plan.py).

    python tools/resource_plan.py
        Plan every model-zoo program (mnist, resnet50, bert, nmt, deepfm —
        the donation-audit zoo) at CI-size configs: per-program peak-HBM
        estimate with the watermark ops at the peak, FLOPs/traffic roll-up,
        analytic roofline step time, and predicted MFU.

    python tools/resource_plan.py --calibrate
        Additionally compile each zoo step (CPU XLA) and compare the plan's
        peak against measured truth: the executable's own buffer assignment
        (memory_analysis: arguments + outputs + temps - aliased) — or, when
        the attached device exposes allocator stats (TPU), the memstats
        `device_bytes_in_use` high-water around a real run.

    python tools/resource_plan.py --check [--min-coverage F]
        CI gate (tier-1 via tests/test_resource_plan.py): exit 1 when
          * any zoo program fails to plan, or
          * cost-rule coverage over the zoo drops below the floor
            (ratchet: raise, never lower), or
          * calibration drifts outside [CALIBRATION_RATIO_LO,
            CALIBRATION_RATIO_HI] on any zoo program (the stated-tolerance
            contract from docs/static_analysis.md — also a ratchet).

Exit codes: 0 clean, 1 gate failure.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

if __name__ == "__main__":
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# Cost-rule coverage floor over the zoo's op types (the ratchet: landed
# coverage is 1.0; never lower).
COST_COVERAGE_FLOOR = 1.0

# Calibration contract: plan peak / measured peak must stay inside this
# band on every zoo program (measured r12: 0.89..1.41 on CPU XLA buffer
# assignment).  The band is the ratchet — tighten as the model improves,
# never widen.
CALIBRATION_RATIO_LO = 0.6
CALIBRATION_RATIO_HI = 2.0


def _fmt_table(rows, headers):
    widths = [max(len(str(r[i])) for r in rows + [headers])
              for i in range(len(headers))]
    out = ["  ".join(str(h).ljust(w) for h, w in zip(headers, widths))]
    for r in rows:
        out.append("  ".join(str(c).ljust(w) for c, w in zip(r, widths)))
    return "\n".join(out)


def zoo_plans(tiny=True, only=None):
    """[(name, program, plan)] over the donation-audit zoo (main programs
    at their example feed shapes)."""
    from tools.donation_audit import build_zoo

    from paddle_tpu.core import resource_plan as rp

    out = []
    for name, main, startup, feed, fetches in build_zoo(tiny=tiny, only=only):
        feed_shapes = {n: tuple(v.shape) for n, v in feed.items()}
        plan = rp.plan_program(main, feed_shapes, fetches)
        out.append((name, main, plan))
    return out


def measured_peak_bytes(name, tiny=True):
    """Measured truth for one zoo program's step: prefer the live
    allocator high-water (device_bytes_in_use around a real run) when the
    backend exposes it; else the compiled executable's XLA buffer
    assignment (arguments + outputs + temps - aliased)."""
    import math

    import jax
    import numpy as np

    import paddle_tpu as fluid
    from paddle_tpu.core.dtypes import as_np_dtype
    from paddle_tpu.core.executor import _CompiledStep
    from paddle_tpu.core.scope import RNG_STATE_VAR
    from paddle_tpu.monitor import memstats
    from paddle_tpu.ops.common import canon_dtype
    from tools.donation_audit import build_zoo

    (_, main, startup, feed, fetches), = build_zoo(tiny=tiny, only=name)
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup, scope=scope)
    block = main.global_block()
    jfeed = {}
    for n, v in feed.items():
        arr = np.asarray(v)
        if block.has_var(n):
            want = as_np_dtype(block.var(n).dtype)
            if want is not None and arr.dtype != want:
                arr = arr.astype(want)
        c = canon_dtype(arr.dtype)
        if arr.dtype != c:
            arr = arr.astype(c)
        jfeed[n] = arr
    compiled = _CompiledStep(main, list(jfeed), list(fetches), scope,
                             platform="cpu",
                             feed_shapes={n: v.shape for n, v in jfeed.items()})
    srw = {n: scope.find_var(n) for n in compiled.rw_names}
    sro = {n: scope.find_var(n) for n in compiled.ro_names}
    key = scope.find_var(RNG_STATE_VAR)
    if key is None:
        key = jax.random.PRNGKey(main.random_seed or 0)
    built = compiled.jfn.trace(srw, sro, jfeed, key).lower().compile()
    live = memstats.device_bytes_in_use()
    if not math.isnan(live):
        base = live
        out = built(dict(srw), sro, jfeed, key)
        jax.block_until_ready(out)
        high = memstats.device_bytes_in_use()
        if not math.isnan(high) and high > base:
            return int(high), "device_bytes_in_use"
    ma = built.memory_analysis()
    measured = (ma.argument_size_in_bytes + ma.output_size_in_bytes
                + ma.temp_size_in_bytes - ma.alias_size_in_bytes)
    return int(measured), "xla_buffer_assignment"


def render(tiny=True, only=None, calibrate=False):
    """(text, results) — results: {name: {plan..., ratio?...}}."""
    from paddle_tpu.core import resource_plan as rp

    plans = zoo_plans(tiny=tiny, only=only)
    rows = []
    results = {}
    for name, _, plan in plans:
        rows.append((name, f"{plan.peak_bytes / 1e6:.2f}",
                     f"{plan.persistable_bytes / 1e6:.2f}",
                     f"{plan.feed_bytes / 1e6:.2f}",
                     f"{plan.peak_temp_bytes / 1e6:.2f}",
                     f"#{plan.peak_op_idx}({plan.peak_op_type})",
                     f"{plan.roofline_step_s * 1e3:.3f}",
                     f"{plan.predicted_mfu:.3f}"))
        results[name] = {"plan": plan.to_dict()}
    parts = ["# resource plans  (zoo, %s configs)" % ("tiny" if tiny else "full"),
             "", _fmt_table(rows, ["program", "peak_MB", "persistable_MB",
                                   "feed_MB", "live_temp_MB", "peak_op",
                                   "roofline_ms", "pred_MFU"])]
    parts.append("\n## peak attribution (watermark ops)")
    for name, _, plan in plans:
        parts.append(f"- {name}: " + "; ".join(plan.watermark_ops()[:4]))
    cov = rp.cost_coverage([p for _, p, _ in plans])
    parts.append(f"\n## cost-rule coverage\nop types covered: "
                 f"{len(cov['covered_types'])} / "
                 f"{len(cov['covered_types']) + len(cov['missing_types'])} "
                 f"(frac {cov['frac']:.3f})")
    if cov["missing_types"]:
        parts.append("missing cost rules (default 1-flop/elem model used): "
                     + ", ".join(cov["missing_types"]))
    results["_coverage"] = cov
    if calibrate:
        parts.append("\n## calibration (plan peak vs measured)")
        crows = []
        for name, _, plan in plans:
            measured, how = measured_peak_bytes(name, tiny=tiny)
            ratio = plan.peak_bytes / measured if measured else float("inf")
            ok = CALIBRATION_RATIO_LO <= ratio <= CALIBRATION_RATIO_HI
            crows.append((name, f"{plan.peak_bytes / 1e6:.2f}",
                          f"{measured / 1e6:.2f}", f"{ratio:.3f}",
                          how, "OK" if ok else "DRIFT"))
            results[name]["measured_bytes"] = measured
            results[name]["ratio"] = ratio
            results[name]["calibration_ok"] = ok
        parts.append(_fmt_table(crows, ["program", "plan_MB", "measured_MB",
                                        "ratio", "truth", "verdict"]))
        parts.append(f"tolerance band: [{CALIBRATION_RATIO_LO}, "
                     f"{CALIBRATION_RATIO_HI}] (the ratchet)")
    return "\n".join(parts), results


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__,
                                 formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--check", action="store_true",
                    help="CI gate: plans build, coverage >= floor, "
                         "calibration inside the tolerance band")
    ap.add_argument("--calibrate", action="store_true",
                    help="compare plan peaks against measured truth")
    ap.add_argument("--full", action="store_true",
                    help="full-size model configs (default: CI-size tiny)")
    ap.add_argument("--program", default=None,
                    help="plan one zoo program (mnist|resnet50|bert|nmt|deepfm)")
    ap.add_argument("--min-coverage", type=float, default=COST_COVERAGE_FLOOR,
                    help=f"cost-rule coverage floor for --check "
                         f"(default {COST_COVERAGE_FLOOR})")
    ap.add_argument("--json", action="store_true", help="machine output")
    args = ap.parse_args(argv)

    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    # NOTE: no persistent XLA compile cache here, deliberately — a
    # cache-deserialized executable's memory_analysis() loses alias_size
    # (donation), which silently inflates the calibration's "measured"
    # side (found when a cached run drifted nmt to ratio 0.57)

    try:
        text, results = render(tiny=not args.full, only=args.program,
                               calibrate=args.calibrate or args.check)
    except Exception as e:
        print(f"resource_plan: planning the zoo FAILED: {type(e).__name__}: {e}")
        return 1
    if args.json:
        print(json.dumps(results, default=str))
    else:
        print(text)

    if args.check:
        failed = False
        cov = results["_coverage"]
        if cov["frac"] < args.min_coverage:
            print(f"\nCHECK FAILED: cost-rule coverage {cov['frac']:.3f} < "
                  f"floor {args.min_coverage} (missing: "
                  f"{cov['missing_types']})")
            failed = True
        for name, r in results.items():
            if name.startswith("_"):
                continue
            if "calibration_ok" in r and not r["calibration_ok"]:
                print(f"\nCHECK FAILED: {name} plan/measured ratio "
                      f"{r['ratio']:.3f} outside "
                      f"[{CALIBRATION_RATIO_LO}, {CALIBRATION_RATIO_HI}] — "
                      f"the planner's liveness or cost model drifted from "
                      f"XLA's buffer assignment")
                failed = True
        if failed:
            return 1
        print(f"\nCHECK OK: {len([k for k in results if not k.startswith('_')])} "
              f"zoo plans clean, coverage {cov['frac']:.3f} >= "
              f"{args.min_coverage}, calibration inside "
              f"[{CALIBRATION_RATIO_LO}, {CALIBRATION_RATIO_HI}]")
    return 0


if __name__ == "__main__":
    sys.exit(main())
