"""Benchmark driver covering every BASELINE.md target (reference harness:
benchmark/fluid/fluid_benchmark.py — one driver, many models).

Default invocation prints ONE JSON line: the flagship ResNet-50 metric with
every other model's result embedded under extra.models.  `--per-model`
prints one JSON line per model instead (mnist parity gate, resnet50,
transformer NMT ragged path, BERT-base, DeepFM CTR).  `--pipeline` runs
the serial-vs-overlapped loop A/B (paddle_tpu.pipeline.train_loop +
Executor.run_async) and prints its own JSON line with both rates and
host-blocked fractions.  `--chaos` runs the resilient loop under a fixed
injected fault schedule (paddle_tpu.faults) and reports throughput plus
the recovery ledger — the robustness overhead as a number; a storage
spec (enospc@S / ro_fs@S / eio@N / slow_io@N:MS) routes to the
storage-fault A/B, reporting the degraded-window length, recovery
overhead, and the bit-identical-parity bit.  With a
distributed spec (kill_worker@S:RANK), `--elastic` adds the ISSUE-9 arm:
the same kill under elastic supervision (shrink to N-1, grow back),
reporting resize overhead and post-resize throughput next to the
fixed-size restart baseline.  `--serve` runs the closed-loop serving
load generator (paddle_tpu.serving): throughput vs p50/p99 tail latency
through the continuous-batching server plus an overload arm proving
admission-control shedding keeps p99 bounded — its JSONL metrics stream
is gated by `perf_report --check --max-shed-frac/--max-p99-ms`.
`--serve --quant` runs the fp32-vs-quantized serving A/B instead
(ISSUE 17): the int8/bf16 snapshot goes live through the full publish
ladder (accuracy-parity gate included) and the record carries both
arms' rps/p99, the HBM narrowing, and the parity ledger — gated by
`perf_report --check --require-quant-parity`.  `--chaos-campaign`
(ISSUE 20) runs the seeded multi-fault campaign engine
(paddle_tpu/chaos.py) over the train / online / serving scenarios —
pseudo-random compound schedules judged by the cross-subsystem
invariant registry, failures shrunk to minimal repro specs — and the
record carries the campaign ledger plus the `perf_report --check
--max-chaos-violations 0` verdict on its own metrics stream.

vs_baseline: the reference published no numbers (BASELINE.md), so the
absolute series is tracked across rounds; vs_baseline = this round's
imgs/s over round-1's 2295.

MFU numbers are computed from analytic FLOPs; labeled `*_analytic`.

A model that fails fails the run: the exception propagates and the exit
code is non-zero.  The persistent compile cache follows
paddle_tpu.flags.apply_compile_cache (JAX_COMPILATION_CACHE_DIR if set,
else .jax_cache/ in the checkout).
"""
from __future__ import annotations

import json
import sys
import time as _time

import numpy as np

from tools.bench_kit import (make_bert_dispatch, make_resnet_dispatch,
                             spread_pct as _spread, timed_steps as _timed_steps)
# ONE spread ceiling, shared with the --check-bench gate: ratcheting it in
# perf_report ratchets the warm-until-stable target here in lockstep
from tools.perf_report import MAX_SPREAD_PCT

ROUND1_IMGS_PER_SEC = 2295.0  # the r1 chip record
V5E_BF16_PEAK = 197e12


def _predicted_roofline(dispatch):
    """The program's OWN static roofline MFU (core/resource_plan.py) for
    the EXACT program + feed shapes this dispatch measured (bench_kit
    attaches them) — the denominator perf_report --check-bench prints
    measured MFU against, so a number far under roofline is named instead
    of averaged away.  None when planning fails (a plan bug must never
    block a bench round)."""
    try:
        from paddle_tpu.core.resource_plan import plan_program

        plan = plan_program(dispatch.main_program, dispatch.feed_shapes,
                            [dispatch.loss_name], steps=dispatch.steps)
        return round(plan.predicted_mfu, 4)
    except Exception as e:  # pragma: no cover - defensive
        print(f"bench: roofline prediction failed: {e!r}", file=sys.stderr)
        return None


def _params_moved(dispatch, before, max_frozen_frac=0.25):
    """Bench-level optimizer-liveness gate (the r5 bf16+Adam freeze shipped
    two rounds of plausible-looking BERT numbers with ~96% of params frozen
    while the f32 embeddings moved — loss finiteness cannot catch that).

    ISSUE-7 resolution of the r5 chip record's "18/198 BERT params frozen": the
    donation audit (tools/donation_audit.py) proves every zoo param is
    donated and updated in place, so a zero param delta with a LIVE
    first-order moment means the optimizer ran and the update rounded away
    below the param dtype's resolution — exactly the bf16 q/k stall at
    symmetric init (score grads cancel below bf16 ulp for the first steps;
    measured in the r5 chip round).  Those now count as `subresolution`,
    not `frozen`; a param whose MOMENT is also dead is a genuinely dropped
    update, and any such param fails the bench outright
    (tests/test_donation_audit.py pins both classes).

    Known ambiguity, strict on purpose: a param whose gradient is EXACTLY
    zero for the whole window (dead ReLU unit) also shows a dead moment and
    trips the hard fail.  After the r5 silent-freeze history we prefer the
    loud false positive: if tools/donation_audit.py --check is green, the
    param is a genuinely zero-gradient unit — re-bench with a different
    seed/batch rather than raising the tolerance here."""
    after = dispatch.probe_param()
    moments = (dispatch.probe_moments()
               if hasattr(dispatch, "probe_moments") else {})
    frozen, subres = [], []
    min_moved = float("inf")
    for name, b in before.items():
        d = float(np.abs(after[name] - b).max())
        if d == 0.0:
            m = moments.get(name)
            if m is None:
                # no first-order accumulator to consult (SGD-class
                # optimizers keep none): a zero delta here is
                # indistinguishable from a legitimately-zero gradient, so
                # it counts against the bounded budget, not the hard fail
                subres.append(name)
            elif float(np.abs(m).max()) > 0.0:
                subres.append(name)  # optimizer live, update < dtype ulp
            else:
                frozen.append(name)
        else:
            min_moved = min(min_moved, d)
    assert not frozen, (
        f"{len(frozen)}/{len(before)} params have DEAD optimizer state "
        f"(dropped-update class bug — see tools/donation_audit.py): "
        f"{sorted(frozen)[:5]}")
    assert len(subres) <= max_frozen_frac * len(before), (
        f"{len(subres)}/{len(before)} params sat below update resolution "
        f"(or have no optimizer accumulator to consult) during the bench "
        f"window: {sorted(subres)[:5]}")
    assert min_moved < float("inf"), "no param moved at all"
    return {"frozen": len(frozen), "subresolution": len(subres),
            "total": len(before), "min_moved_delta": min_moved}


def _gang_results(res):
    """Every RESULT-line JSON record printed by a gang's workers (the
    worker output protocol shared by the overlap and chaos A/Bs)."""
    recs = []
    for code, out, err in res.workers:
        for line in (out or "").splitlines():
            if line.startswith("RESULT "):
                recs.append(json.loads(line[len("RESULT "):]))
    return recs


def _gang_skew(res):
    """Embed the gang's cross-rank skew record (ISSUE 8): the workers
    streamed rank-tagged step records into run_gang's telemetry dir, so
    tools/trace_merge.py can correlate them and name the round's
    straggler.  {} when fewer than two ranks left telemetry (e.g. a rank
    died before its first step) — `perf_report --check-bench` gates the
    fields only when present."""
    try:
        from tools.trace_merge import skew_from_dir

        rep = skew_from_dir(res.telemetry_dir) if res.telemetry_dir else None
    except Exception:
        rep = None
    if not rep or not rep.get("steps_correlated"):
        return {}
    out = {"step_skew_frac": rep.get("mean_skew_frac"),
           "max_step_skew_frac": rep.get("max_skew_frac"),
           "skew_steps_correlated": rep.get("steps_correlated"),
           "straggler_rank": rep.get("straggler", {}).get("rank")}
    return {k: v for k, v in out.items() if v is not None}


def bench_resnet50(batch_size=128, K=16, iters=4):
    # bs128/K=16 interleaved-A/B'd vs bs256/K8 and bs64/K32: 2573 vs 2445
    # vs 2351 imgs/s — the r4 "bs256 wins" result predates the single-pass
    # BN stats; with less stats traffic the smaller batch's better
    # cache/VMEM behavior wins (r5 chip round)
    dispatch, _ = make_resnet_dispatch(batch_size=batch_size, K=K)
    before = dispatch.probe_param()
    dt, out, ws = _timed_steps(dispatch, K=K, iters=iters, windows=3,
                               spread_target=MAX_SPREAD_PCT)
    lossN = float(np.asarray(out[0]).reshape(-1)[-1])
    assert np.isfinite(lossN), f"non-finite resnet loss {lossN}"
    moved = _params_moved(dispatch, before)
    imgs = batch_size / dt
    mfu = imgs * 3 * 4.089e9 / V5E_BF16_PEAK
    print(f"resnet50: {dt*1e3:.1f} ms  {imgs:.0f} imgs/s  mfu {mfu:.3f}", file=sys.stderr)
    pred = _predicted_roofline(dispatch)
    return {"metric": "resnet50_train_imgs_per_sec_per_chip", "value": round(imgs, 2),
            "unit": "imgs/sec", "mfu_bf16_analytic": round(mfu, 4),
            "mfu_predicted_roofline": pred,
            "batch_size": batch_size, "steps_per_dispatch": K,
            "params_moved": moved,
            "windows_ms": ws, "spread_pct": _spread(ws)}


def bench_mnist(batch_size=128, steps=40, K=20, iters=3):
    """Loss-parity gate (BASELINE: 'loss parity vs CPU ref'): the same
    seeded program must converge on the chip and match a rerun bit-for-bit
    modulo accelerator numerics (rtol 1e-3 on the loss curve).  Throughput
    is a separate steps=K scan with device-resident feeds — the per-step
    host loop below measures the parity curve, not the chip."""
    import jax
    import jax.numpy as jnp

    import paddle_tpu as fluid
    from paddle_tpu.models import mnist

    rng = np.random.RandomState(0)
    # strongly learnable synthetic task (random labels would floor the CE
    # at ln10): each class k brightens the image by 0.06*k, so class is
    # linearly decodable from mean brightness and the net leaves the prior
    # floor within a few dozen steps
    labels = rng.randint(0, 10, (steps, batch_size)).astype("int64")
    imgs = (rng.rand(steps, batch_size, 1, 28, 28) * 0.4
            + labels[..., None, None, None] * 0.06).astype("float32")
    labels = labels[..., None]

    def run(place):
        main, startup, feeds, fetches = mnist.build(learning_rate=1e-3)
        startup.random_seed = 7
        scope = fluid.Scope()
        exe = fluid.Executor(place)
        exe.run(startup, scope=scope)
        losses = []
        for i in range(steps):
            (lv,) = exe.run(main, feed={"img": imgs[i], "label": labels[i]},
                            fetch_list=[fetches["loss"]], scope=scope)
            losses.append(float(np.asarray(lv).reshape(-1)[0]))
        return losses

    tpu_losses = run(fluid.TPUPlace(0))
    cpu_losses = run(fluid.CPUPlace())
    parity = bool(np.allclose(tpu_losses, cpu_losses, rtol=5e-2, atol=1e-3))
    converged = tpu_losses[-1] < tpu_losses[0] * 0.7

    # steady-state throughput: K optimizer steps per dispatch
    main, startup, feeds, fetches = mnist.build(learning_rate=1e-3)
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.TPUPlace(0))
    exe.run(startup, scope=scope)
    dev = fluid.TPUPlace(0).jax_device()
    feed = {
        "img": jax.device_put(jnp.asarray(imgs[:K]), dev),
        "label": jax.device_put(jnp.asarray(labels[:K], jnp.int32), dev),
    }
    loss_name = fetches["loss"].name

    def dispatch():
        return exe.run(main, feed=feed, fetch_list=[loss_name], scope=scope,
                       steps=K, return_numpy=False)

    dt, out, ws = _timed_steps(dispatch, K=K, iters=iters, windows=3,
                               spread_target=MAX_SPREAD_PCT)
    imgs_per_sec = batch_size / dt
    print(f"mnist: parity={parity} converged={converged} "
          f"loss {tpu_losses[0]:.3f}->{tpu_losses[-1]:.3f}  "
          f"{imgs_per_sec:.0f} imgs/s", file=sys.stderr)
    return {"metric": "mnist_loss_parity", "value": round(imgs_per_sec, 2),
            "unit": "imgs/sec", "parity_vs_cpu": parity, "converged": bool(converged),
            "first_loss": round(tpu_losses[0], 4), "last_loss": round(tpu_losses[-1], 4),
            "steps_per_dispatch": K, "windows_ms": ws, "spread_pct": _spread(ws)}


def bench_nmt(K=8, iters=3, b=32):
    """Transformer-base NMT on the ragged/LoD path: seqs/sec with
    variable-length batches (BASELINE: 'no CUDA ops in executed program' —
    trivially true: every op lowers to XLA).

    Measurement (r5): K steps per dispatch with device-resident pre-padded
    feeds + `<name>@LOD` lengths companions (tools.bench_kit.
    make_nmt_dispatch) — the executed program is the SAME ragged program,
    but the harness no longer measures one host dispatch per step, which
    is what capped r3/r4 at ~250 seqs/s."""
    from tools.bench_kit import make_nmt_dispatch

    dispatch, _, mean_tokens = make_nmt_dispatch(K=K, b=b)
    before = dispatch.probe_param()
    # warmup-until-stable windowing (ISSUE 7): the r5 chip record's 26.3% NMT spread
    # was the first window still carrying warm-in (30.3 -> 22.8 ms); windows
    # now extend until the trailing 3 agree to 5%, so kernel A/Bs on this
    # config compare steady state against steady state.  spread_ok is the
    # self-check the record carries (and perf_report's bench gate can read).
    dt, out, ws = _timed_steps(dispatch, K=K, iters=iters, windows=3,
                               spread_target=MAX_SPREAD_PCT)
    lv = float(np.asarray(out[0]).reshape(-1)[-1])
    assert np.isfinite(lv)
    moved = _params_moved(dispatch, before)
    seqs = b / dt
    toks = mean_tokens * seqs
    print(f"nmt: {dt*1e3:.1f} ms  {seqs:.0f} seqs/s  loss {lv:.3f}", file=sys.stderr)
    return {"metric": "transformer_nmt_train_seqs_per_sec_per_chip",
            "value": round(seqs, 2), "unit": "seqs/sec", "batch_size": b,
            "config": "base-6L-512d ragged", "tokens_per_sec": round(toks, 1),
            "params_moved": moved,
            "steps_per_dispatch": K, "windows_ms": ws,
            "spread_pct": _spread(ws), "spread_ok": _spread(ws) <= MAX_SPREAD_PCT}


def bench_bert(batch_size=256, seq_len=128, K=2, iters=4):
    dispatch, _ = make_bert_dispatch(batch_size=batch_size, seq_len=seq_len, K=K)
    before = dispatch.probe_param()
    dt, out, ws = _timed_steps(dispatch, K=K, iters=iters, windows=2,
                               spread_target=MAX_SPREAD_PCT)
    lossN = float(np.asarray(out[0]).reshape(-1)[-1])
    assert np.isfinite(lossN)
    moved = _params_moved(dispatch, before)
    seqs = batch_size / dt
    # analytic train FLOPs/seq for BERT-base @128: ~6 * 110e6 params * 128 tokens
    flops_per_seq = 6 * 110e6 * seq_len
    mfu = seqs * flops_per_seq / V5E_BF16_PEAK
    print(f"bert: {dt*1e3:.1f} ms  {seqs:.0f} seqs/s  mfu {mfu:.3f}", file=sys.stderr)
    pred = _predicted_roofline(dispatch)
    return {"metric": "bert_base_train_seqs_per_sec_per_chip", "value": round(seqs, 2),
            "unit": "seqs/sec", "mfu_bf16_analytic": round(mfu, 4),
            "mfu_predicted_roofline": pred,
            "batch_size": batch_size, "seq_len": seq_len,
            "config": "fused-attention (output-dropout substitution)",
            "params_moved": moved,
            "steps_per_dispatch": K, "windows_ms": ws, "spread_pct": _spread(ws)}


def bench_deepfm(batch_size=4096, K=16, iters=3):
    """DeepFM CTR with sparse LookupTable grads.  r5: K steps per dispatch +
    device-resident feeds + windows/spread — the r4 harness (one exe.run per
    step, host feeds, no windows) was dominated by host dispatch and swung
    90k..165k ex/s run-to-run on identical code."""
    import jax
    import jax.numpy as jnp

    import paddle_tpu as fluid
    from paddle_tpu.core import lowering
    from paddle_tpu.models import deepfm

    main, startup, feeds, fetches = deepfm.build(
        num_fields=26, vocab_size=200000, embed_dim=16, mlp_dims=(400, 400, 400),
        learning_rate=0.05)
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.TPUPlace(0))
    exe.run(startup, scope=scope)
    rng = np.random.RandomState(0)
    dev = fluid.TPUPlace(0).jax_device()
    feed = {
        "feat_ids": jax.device_put(
            jnp.asarray(rng.randint(0, 200000, (K, batch_size, 26)), jnp.int32), dev),
        "label": jax.device_put(
            jnp.asarray((rng.rand(K, batch_size, 1) < 0.3), jnp.float32), dev),
    }

    def dispatch():
        return exe.run(main, feed=feed, fetch_list=[fetches["loss"]], scope=scope,
                       steps=K, return_numpy=False)

    from tools.bench_kit import attach_param_probe

    attach_param_probe(dispatch, main, scope)
    dispatch()  # compile before the probe so 'before' is post-init state
    before = dispatch.probe_param()
    dt, out, ws = _timed_steps(dispatch, K=K, iters=iters, windows=3,
                               spread_target=MAX_SPREAD_PCT)
    lossN = float(np.asarray(out[0]).reshape(-1)[-1])
    assert np.isfinite(lossN)
    moved = _params_moved(dispatch, before)
    sparse = sorted(lowering.LAST_TRACE_REPORT.get("sparse_grad_params", []))
    ex = batch_size / dt
    print(f"deepfm: {dt*1e3:.2f} ms  {ex:.0f} ex/s  sparse={sparse}", file=sys.stderr)
    return {"metric": "deepfm_ctr_train_examples_per_sec_per_chip",
            "value": round(ex, 2), "unit": "examples/sec",
            "batch_size": batch_size, "vocab": 200000,
            "sparse_grad_params": sparse, "steps_per_dispatch": K,
            "params_moved": moved,
            "windows_ms": ws, "spread_pct": _spread(ws)}


def bench_pipeline(batch_size=128, steps=24, max_inflight=4, log_period=8,
                   n_distinct_batches=4):
    """Serial `exe.run` loop vs `pipeline.train_loop` A/B over identical
    DataLoader-staged ResNet-50 batches (the ISSUE-2 overlap win).

    Both arms pull device-resident feeds from the same DataLoader config
    (H2D in the producer thread), so the A/B isolates the dispatch/fetch
    overlap: the serial arm resolves every step's fetch before dispatching
    the next, the pipelined arm keeps `max_inflight` steps in flight and
    resolves only every `log_period`-th.  Reports both rates plus each
    arm's host-blocked fraction — the pipelined one must sit strictly
    below the serial one (and does, or this bench is the regression
    alarm)."""
    import jax
    import jax.numpy as jnp

    import paddle_tpu as fluid
    from paddle_tpu import monitor, pipeline
    from paddle_tpu.models import resnet

    main_p, startup, feeds, fetches = resnet.build(
        dtype="bfloat16", class_dim=1000, learning_rate=0.1,
        with_optimizer=True, stem="space_to_depth")
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.TPUPlace(0))
    exe.run(startup, scope=scope)
    loss_name = fetches["loss"].name
    dev = fluid.TPUPlace(0).jax_device()
    rng = np.random.RandomState(0)
    batches = [
        {"img": rng.rand(batch_size, 3, 224, 224).astype("float32"),
         "label": rng.randint(0, 1000, (batch_size, 1)).astype("int64")}
        for _ in range(n_distinct_batches)
    ]

    def make_loader():
        def gen():
            for i in range(steps):
                yield batches[i % n_distinct_batches]

        return fluid.DataLoader.from_generator(
            [feeds["img"], feeds["label"]], capacity=max_inflight + 2,
            device=dev).set_batch_generator(gen)

    # warmup/compile outside both timing windows (same executable serves
    # both arms: same program, feed signature, and scope)
    exe.run(main_p, feed=batches[0], fetch_list=[loss_name], scope=scope)

    monitor.reset()
    monitor.enable()
    t0 = _time.perf_counter()
    last = None
    for feed in make_loader():
        (last,) = exe.run(main_p, feed=feed, fetch_list=[loss_name],
                          scope=scope)
    serial_wall = _time.perf_counter() - t0
    spans = monitor.get_monitor().span_stats()
    serial_blocked = (spans.get("executor.execute", {}).get("total_s", 0.0)
                      + spans.get("executor.fetch", {}).get("total_s", 0.0))
    serial_frac = serial_blocked / serial_wall if serial_wall else 0.0
    assert np.isfinite(float(np.asarray(last).reshape(-1)[0]))

    monitor.reset()
    stats = pipeline.train_loop(exe, main_p, make_loader(), [loss_name],
                                scope=scope, max_inflight=max_inflight,
                                log_period=log_period)
    monitor.disable()
    for _, vals in stats.logged:
        assert np.isfinite(float(np.asarray(vals[0]).reshape(-1)[0]))

    serial_imgs = steps * batch_size / serial_wall
    piped_imgs = steps * batch_size / stats.wall_s
    print(f"pipeline: serial {serial_imgs:.0f} imgs/s (host-blocked "
          f"{serial_frac:.3f})  pipelined {piped_imgs:.0f} imgs/s "
          f"(host-blocked {stats.host_blocked_frac:.3f})", file=sys.stderr)
    return {"metric": "resnet50_pipeline_overlap",
            "value": round(piped_imgs, 2), "unit": "imgs/sec",
            "serial_imgs_per_sec": round(serial_imgs, 2),
            "pipelined_imgs_per_sec": round(piped_imgs, 2),
            "speedup": round(piped_imgs / serial_imgs, 4) if serial_imgs else 0.0,
            "host_blocked_frac_serial": round(serial_frac, 4),
            "host_blocked_frac_pipelined": round(stats.host_blocked_frac, 4),
            "overlap_confirmed": bool(stats.host_blocked_frac < serial_frac),
            "batch_size": batch_size, "steps": steps,
            "max_inflight": max_inflight, "log_period": log_period}


def _serve_roofline(model_dir, rows):
    """The saved serving program's own static roofline at the `rows`-row
    bucket (core/resource_plan.py over the inference graph): the
    predicted-MFU denominator the serve record stamps
    (`mfu_predicted_roofline`, same meaning as the train records') plus
    the analytic per-row forward FLOPs the measured serving MFU is
    computed from.  {} when planning fails — a plan bug must never block
    a serve round."""
    import os

    try:
        from paddle_tpu.core.program import Program
        from paddle_tpu.core.resource_plan import plan_program
        from paddle_tpu.serving.registry import synthetic_feed_shapes

        with open(os.path.join(model_dir, "__model__.json")) as f:
            doc = json.load(f)
        program = Program.from_dict(doc)
        shapes = synthetic_feed_shapes(program, doc.get("feed_names", []),
                                       rows)
        plan = plan_program(program, shapes, doc.get("fetch_names", []))
        return {"mfu_predicted_roofline": round(plan.predicted_mfu, 4),
                "flops_per_row_analytic": plan.flops_total / max(rows, 1)}
    except Exception as e:  # pragma: no cover - defensive
        print(f"bench: serve roofline prediction failed: {e!r}",
              file=sys.stderr)
        return {}


# The serve bench's timed window must dwarf a CPython gen2 GC pause: at
# the old default of 400 requests the window was ~0.15 s, ONE collection
# landing inside it (steered by import order, nothing else) read as a
# ~20% rps regression and burned a PR-12 bisect.  Gen2 is frozen around
# the windows below AND the window length is asserted, so the bench
# physically cannot report a pause as a regression again.
MIN_SERVE_WINDOW_S = 1.0


class _gc_quiesced:
    """Freeze the current heap out of gen2's reach and disable automatic
    collection for the duration of a timed window; one explicit collect
    on entry starts the window clean."""

    def __enter__(self):
        import gc

        gc.collect()
        gc.freeze()
        gc.disable()
        return self

    def __exit__(self, *exc):
        import gc

        gc.enable()
        gc.unfreeze()


def bench_serve(requests=4000, clients=6, buckets=(1, 2, 4, 8),
                max_queue=64, overload_clients=12, overload_queue=4,
                overload_burst=6, overload_bursts=8, p99_gate_ms=2000.0,
                metrics_path=None, min_window_s=MIN_SERVE_WINDOW_S):
    """Closed-loop serving load generator (ISSUE 11): throughput vs tail
    latency through `paddle_tpu.serving.Server`, plus an OVERLOAD arm
    proving admission control keeps p99 bounded by shedding.

    Baseline arm: `clients` closed-loop threads (one outstanding request
    each, random 1..4-row batches — novel sizes on purpose) drive
    `requests` total requests; the record carries rps, p50/p99, and the
    steady-state recompile delta, which MUST be zero (every size serves
    from a warmed pad-to-bucket executable — the no-inline-recompile
    acceptance).

    Overload arm: a second server over the SAME registry (warm cache)
    with a tiny queue bound; `overload_clients` threads submit bursts so
    offered load exceeds capacity.  Shed requests are the designed
    response — the record reports the exact shed ledger and the p99 the
    survivors saw, gated against `p99_gate_ms` (unbounded queueing is
    what this arm would catch).

    Timed-window hardening (ISSUE 14 satellite): both arms run with gen2
    GC frozen+disabled (`_gc_quiesced`) and the baseline window must
    clear `MIN_SERVE_WINDOW_S` — the PR-12 false ~20% regression was ONE
    gen2 pause inside a ~0.15 s window at the old requests=400 default.

    Each arm gets its OWN metrics stream (`metrics_path` for baseline,
    `<metrics_path>.overload.jsonl` for the flood): the overload arm's
    mass shedding is designed, and folding it into the baseline stream
    would make the documented tight gate
    (`perf_report --check --max-shed-frac 0.05`) unusable on the bench's
    own output.  Gate the baseline file tight on sheds and the overload
    file loose on sheds / tight on p99."""
    import os
    import tempfile
    import threading

    import paddle_tpu as fluid
    from paddle_tpu import layers, monitor, serving
    from paddle_tpu.monitor import MonitorLogger

    rng = np.random.RandomState(0)
    main_p, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main_p, startup):
        x = layers.data("x", [64], dtype="float32")
        h = layers.fc(x, 128, act="relu")
        out = layers.fc(h, 10, act="softmax")
    startup.random_seed = 7
    exe = fluid.Executor(fluid.TPUPlace(0))
    scope = fluid.Scope()
    exe.run(startup, scope=scope)
    model_dir = tempfile.mkdtemp(prefix="pt-serve-bench-")
    fluid.io.save_inference_model(model_dir, ["x"], [out], exe, main_p, scope)

    if metrics_path is None:
        metrics_path = os.path.join(model_dir, "serve_metrics.jsonl")
    monitor.reset()
    monitor.enable()

    registry = serving.ModelRegistry(place=fluid.TPUPlace(0))
    srv = serving.Server(registry, buckets=buckets, max_queue=max_queue)
    srv.load_model("m", model_dir)  # warms every bucket
    rec0 = monitor.counter("executor.recompile").value
    miss0 = monitor.counter("executor.cache_miss").value
    # the metrics stream starts AFTER the load-time compile lane: steady
    # state is what the recompile-flat gate (and this bench's own zero-
    # recompile assert) holds to — warm compiles are the paid-once cost
    logger = monitor.attach_logger(MonitorLogger(metrics_path))

    served = [0]
    lock = threading.Lock()

    def client(seed):
        r = np.random.RandomState(seed)
        while True:
            with lock:
                if served[0] >= requests:
                    return
                served[0] += 1
            rows = int(r.randint(1, 5))
            srv.infer("m", {"x": r.rand(rows, 64).astype("f4")})

    threads = [threading.Thread(target=client, args=(i,)) for i in range(clients)]
    with _gc_quiesced():
        t0 = _time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        wall = _time.perf_counter() - t0
    # min_window_s=0 is for tier-1 SMOKES only (they test plumbing, not
    # throughput); any measured round keeps the floor
    assert wall >= min_window_s, (
        f"serve bench timed window {wall*1e3:.0f} ms is shorter than the "
        f"{min_window_s:.1f} s floor — a window this size is "
        f"GC-pause-sized and its rps is noise; raise `requests` "
        f"(currently {requests}) until the window clears the floor")
    lat = srv.latency_ms()
    base_stats = srv.stats()
    # per-bucket queue/pad/compute attribution (ISSUE 16): read before
    # stop() like the stats — the record embeds where the latency went
    base_attr = srv.bucket_attribution()
    recompiles = monitor.counter("executor.recompile").value - rec0
    misses = monitor.counter("executor.cache_miss").value - miss0
    # snapshot BEFORE stop(): stop releases the server's lazy p50/p99
    # gauges, and the baseline file's gate reads them from the snapshot
    logger.write_snapshot()
    monitor.detach_logger(logger)
    srv.stop()
    assert recompiles == 0 and misses == 0, (
        f"steady-state serving compiled inline ({recompiles} recompiles, "
        f"{misses} cache misses) — the pad-to-bucket policy broke")

    # -- overload arm (its own stream: designed sheds must not pollute
    # the baseline file's gate) -------------------------------------------
    ov_metrics = metrics_path + ".overload.jsonl"
    ov_logger = monitor.attach_logger(MonitorLogger(ov_metrics))
    ov = serving.Server(registry, buckets=buckets, max_queue=overload_queue)
    offered = [0]
    shed = [0]

    def flood(seed):
        r = np.random.RandomState(1000 + seed)
        for _ in range(overload_bursts):
            futs = []
            for _ in range(overload_burst):
                with lock:
                    offered[0] += 1
                try:
                    futs.append(ov.submit(
                        "m", {"x": r.rand(int(r.randint(1, 5)), 64).astype("f4")}))
                except fluid.errors.ServingError as e:
                    assert e.reason == "overload", e
                    with lock:
                        shed[0] += 1
            for f in futs:
                f.result(timeout=60)

    threads = [threading.Thread(target=flood, args=(i,))
               for i in range(overload_clients)]
    with _gc_quiesced():
        t0 = _time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        ov_wall = _time.perf_counter() - t0
    ov_lat = ov.latency_ms()
    ov_stats = ov.stats()
    ov_attr = ov.bucket_attribution()
    ov_logger.write_snapshot()  # before stop: gauges still armed
    monitor.detach_logger(ov_logger)
    ov.stop()
    assert ov_stats["shed"] == shed[0], "shed ledger drifted from clients'"
    monitor.disable()

    rps = requests / wall
    shed_frac = shed[0] / offered[0] if offered[0] else 0.0
    print(f"serve: {rps:.0f} req/s p50 {lat['p50']:.1f} ms p99 "
          f"{lat['p99']:.1f} ms (recompiles {recompiles}); overload: "
          f"{ov_stats['completed']}/{offered[0]} served, {shed[0]} shed "
          f"({shed_frac:.2%}), p99 {ov_lat['p99']:.1f} ms", file=sys.stderr)
    # measured-vs-predicted MFU stamps (ISSUE 17 satellite): the serving
    # program's own static roofline is the denominator perf_report
    # --check-bench prints measured MFU against — same contract as the
    # train records, so serving gaps are named, not averaged away
    import jax as _jax

    roof = _serve_roofline(model_dir, max(buckets))
    rows_per_sec = base_stats["rows"] / wall
    mfu = (rows_per_sec * roof["flops_per_row_analytic"] / V5E_BF16_PEAK
           if roof.get("flops_per_row_analytic") else None)
    return {"metric": "serving_closed_loop_rps", "value": round(rps, 2),
            "unit": "req/sec",
            "device": _jax.default_backend(),
            "mfu_bf16_analytic": round(mfu, 6) if mfu is not None else None,
            "mfu_predicted_roofline": roof.get("mfu_predicted_roofline"),
            "window_s": round(wall, 3), "min_window_s": min_window_s,
            "gc_frozen": True,
            "requests": requests, "clients": clients,
            "buckets": list(buckets), "max_queue": max_queue,
            "p50_ms": lat["p50"], "p99_ms": lat["p99"],
            "rows_per_sec": round(base_stats["rows"] / wall, 1),
            "batches": base_stats["batches"],
            "mean_batch_occupancy": round(
                base_stats["rows"] / max(
                    base_stats["rows"] + base_stats["padded_rows"], 1), 4),
            "recompiles_steady": recompiles,
            "cache_misses_steady": misses,
            # latency/pad attribution + SLO burn (ISSUE 16): queue-wait
            # share of completed requests' wall time, per-bucket ledger
            # (JSON keys are strings), and the windowed SLO accounting
            "queue_wait_frac": base_stats["queue_wait_frac"],
            "slo": base_stats["slo"],
            "bucket_attribution": {str(b): a for b, a in base_attr.items()},
            "overload": {
                "offered": offered[0], "completed": ov_stats["completed"],
                "shed": shed[0], "shed_frac": round(shed_frac, 4),
                "p99_ms": ov_lat["p99"],
                "p99_bounded": bool(ov_lat["p99"] <= p99_gate_ms),
                "p99_gate_ms": p99_gate_ms, "queue_bound": overload_queue,
                "req_per_sec": round((offered[0] - shed[0]) / ov_wall, 2),
                "queue_wait_frac": ov_stats["queue_wait_frac"],
                "slo": ov_stats["slo"],
                "bucket_attribution": {str(b): a
                                       for b, a in ov_attr.items()},
                "metrics_path": ov_metrics,
            },
            "metrics_path": metrics_path}


def bench_serve_quant(requests=4000, clients=4, buckets=(1, 2, 4, 8),
                      max_queue=64, serve_dtype="bfloat16", weight_bits=8,
                      metrics_path=None, min_window_s=MIN_SERVE_WINDOW_S):
    """fp32-vs-quantized serving A/B (ISSUE 17): the same model served
    twice through the bucketed server — once from its fp32
    save_inference_model dir, once from the int8
    save_quantized_inference_model dir whose weights dequantize into
    `serve_dtype` (bf16: half the resident weight HBM, int8-grid
    numerics).  The quant arm goes live through the FULL publish ladder,
    so the round exercises the accuracy-parity gate
    (FLAGS_serving_quant_atol vs the fp32 parent's outputs) for real —
    the record embeds the gate's own `quant_parity` event next to a
    direct fp32-vs-quant output comparison (the parity ledger).

    Honesty contract: rps/p99 are chip numbers ONLY on TPU.  Off-device
    the record still lands — parity ledger, HBM narrowing, and precision
    plumbing are platform-independent — but `throughput_claim` says
    `parity_only_off_device` and no floor may ratchet from it.

    The metrics stream starts AFTER the fp32 arm, so one file carries the
    quant publish lane (its warm compiles are the paid-once head of the
    stream), the `quant_parity` gate event, and the quant arm's
    steady-state serving steps.  Gate it with BOTH serving gates::

        python tools/perf_report.py --check <metrics_path> \\
            --steady-after <gate_steady_after> --require-quant-parity

    where `gate_steady_after` is embedded in the record (the measured
    publish-lane step count plus margin): past it the recompile-flat
    gate holds over the quant arm, which this bench also asserts
    directly (`recompiles_steady` must be 0)."""
    import os
    import tempfile
    import threading

    import jax as _jax

    import paddle_tpu as fluid
    from paddle_tpu import layers, monitor, serving
    from paddle_tpu.monitor import MonitorLogger

    main_p, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main_p, startup):
        x = layers.data("x", [64], dtype="float32")
        h = layers.fc(x, 128, act="relu")
        out = layers.fc(h, 10, act="softmax")
    startup.random_seed = 7
    exe = fluid.Executor(fluid.TPUPlace(0))
    scope = fluid.Scope()
    exe.run(startup, scope=scope)
    root = tempfile.mkdtemp(prefix="pt-serve-quant-")
    fp32_dir = os.path.join(root, "fp32")
    quant_dir = os.path.join(root, "quant")
    fluid.io.save_inference_model(fp32_dir, ["x"], [out], exe, main_p, scope)
    fluid.io.save_quantized_inference_model(
        quant_dir, ["x"], [out], exe, main_p, scope,
        weight_bits=weight_bits, serve_dtype=serve_dtype)

    if metrics_path is None:
        metrics_path = os.path.join(root, "serve_quant_metrics.jsonl")
    monitor.reset()
    monitor.enable()
    registry = serving.ModelRegistry(place=fluid.TPUPlace(0))

    lock = threading.Lock()

    def window(srv):
        served = [0]

        def client(seed):
            r = np.random.RandomState(seed)
            while True:
                with lock:
                    if served[0] >= requests:
                        return
                    served[0] += 1
                rows = int(r.randint(1, 5))
                srv.infer("m", {"x": r.rand(rows, 64).astype("f4")})

        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(clients)]
        with _gc_quiesced():
            t0 = _time.perf_counter()
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            wall = _time.perf_counter() - t0
        assert wall >= min_window_s, (
            f"quant A/B timed window {wall*1e3:.0f} ms is shorter than "
            f"the {min_window_s:.1f} s floor — GC-pause-sized windows "
            f"are noise; raise `requests` (currently {requests})")
        lat = srv.latency_ms()
        return {"rps": round(requests / wall, 2), "window_s": round(wall, 3),
                "p50_ms": lat["p50"], "p99_ms": lat["p99"]}

    # -- fp32 arm ----------------------------------------------------------
    srv = serving.Server(registry, buckets=buckets, max_queue=max_queue)
    srv.load_model("m", fp32_dir)  # warms every bucket
    fp32_info = registry.models()["m"]
    # the parity ledger's reference outputs: a fixed feed through the
    # fp32 version, re-run after the quant publish for the direct diff
    ref_feed = {"x": np.random.RandomState(7).rand(4, 64).astype("f4")}
    ref_out = np.asarray(registry.acquire("m").run(ref_feed)[0], np.float64)
    fp32_arm = window(srv)
    srv.stop()

    # metrics stream starts here: publish compile lane + parity event +
    # quant steady state, one file gateable per the docstring recipe
    logger = monitor.attach_logger(MonitorLogger(metrics_path))
    steps0 = monitor.counter("executor.steps").value

    # -- quant publish: the verification ladder INCLUDING the parity gate --
    atol = float(fluid.flags.flag("FLAGS_serving_quant_atol") or 0.0)
    serving.publish(registry, "m", quant_dir, warm_buckets=buckets)
    quant_info = registry.models()["m"]
    gate_ev = [r for r in monitor.step_records()
               if r.get("kind") == "serving_event"
               and r.get("action") == "quant_parity"]
    quant_out = np.asarray(registry.acquire("m").run(ref_feed)[0], np.float64)
    max_diff = float(np.max(np.abs(quant_out - ref_out)))
    # every step record before this point is publish-lane (warm compiles,
    # golden smoke, the parity gate's reference run): the recompile-flat
    # gate must start past them
    publish_lane_steps = monitor.counter("executor.steps").value - steps0
    rec0 = monitor.counter("executor.recompile").value

    # -- quant arm (same registry: warm executable cache, same buckets) ----
    srv = serving.Server(registry, buckets=buckets, max_queue=max_queue)
    quant_arm = window(srv)
    quant_recompiles = monitor.counter("executor.recompile").value - rec0
    assert quant_recompiles == 0, (
        f"quant arm compiled inline ({quant_recompiles} recompiles) — the "
        f"publish ladder's pre-swap warm lane must leave every bucket "
        f"shape compiled before the swap")
    logger.write_snapshot()
    monitor.detach_logger(logger)
    srv.stop()
    monitor.disable()

    device = _jax.default_backend()
    on_tpu = device == "tpu"
    speedup = (quant_arm["rps"] / fp32_arm["rps"]
               if fp32_arm["rps"] else 0.0)
    hbm_sav = (1.0 - quant_info["bytes"] / fp32_info["bytes"]
               if fp32_info["bytes"] else 0.0)
    roof = _serve_roofline(fp32_dir, max(buckets))
    parity = {
        "max_abs_diff": max_diff, "atol": atol,
        "within_atol": bool(max_diff <= atol),
        "gate_event_recorded": bool(gate_ev),
        "gate_max_abs_diff": gate_ev[-1]["max_abs_diff"] if gate_ev else None,
    }
    print(f"serve-quant: fp32 {fp32_arm['rps']:.0f} req/s p99 "
          f"{fp32_arm['p99_ms']:.1f} ms ({fp32_info['bytes']/1e3:.1f} KB) "
          f"-> {quant_info['precision']} {quant_arm['rps']:.0f} req/s p99 "
          f"{quant_arm['p99_ms']:.1f} ms ({quant_info['bytes']/1e3:.1f} KB, "
          f"x{speedup:.3f}); parity max|diff| {max_diff:.2e} <= atol "
          f"{atol:g}: {parity['within_atol']} [device={device}]",
          file=sys.stderr)
    return {"metric": "serving_quant_ab_rps", "value": quant_arm["rps"],
            "unit": "req/sec", "device": device,
            "throughput_claim": ("measured_on_device" if on_tpu
                                 else "parity_only_off_device"),
            "quant_speedup": round(speedup, 4),
            "quant_throughput_ge_fp32": bool(speedup >= 1.0),
            "fp32": {**fp32_arm, "hbm_bytes": fp32_info["bytes"],
                     "precision": fp32_info["precision"]},
            "quant": {**quant_arm, "hbm_bytes": quant_info["bytes"],
                      "precision": quant_info["precision"],
                      "serve_dtype": serve_dtype,
                      "weight_bits": weight_bits},
            "hbm_savings_frac": round(hbm_sav, 4),
            "parity": parity,
            "mfu_predicted_roofline": roof.get("mfu_predicted_roofline"),
            "recompiles_steady": quant_recompiles,
            "publish_lane_steps": publish_lane_steps,
            "gate_steady_after": publish_lane_steps + 2,
            "requests": requests, "clients": clients,
            "buckets": list(buckets), "max_queue": max_queue,
            "metrics_path": metrics_path}


def bench_serve_fleet(requests=1000, clients=6, replica_counts=(1, 2, 4),
                      buckets=(1, 2, 4, 8), hb_interval_s=0.2):
    """Fleet serving bench (ISSUE 18): closed-loop rps/p99 through the
    health-aware router at 1/2/4 replicas, plus a CHAOS arm that
    SIGKILLs a replica mid-window at n=2 and prices the failover.

    Each arm spawns a REAL multi-process fleet (replica Server processes
    under the supervisor, per-request TCP through the router), drives
    `requests` closed-loop requests from `clients` threads, and records
    client-observed rps/p50/p99 — the wire + routing overhead is the
    point, so latency is measured at the caller, not inside the replica.

    The chaos arm re-runs the n=2 shape, kills rank 0 a third of the way
    in, and reports survivor-carried rps, the exact shed ledger (every
    loss must be a classified `replica_down` — the router's exactly-once
    accounting is part of what's priced), and the post-run
    `serve_trace --fleet --check` / `perf_report --check-roll-convergence`
    verdicts over the fleet's own telemetry.

    On a CPU container the absolute rps is plumbing evidence only
    (`throughput_claim="parity_only_off_device"`, same contract as
    BENCH_r06's serving round); the replica-scaling ratios and the
    chaos-arm loss bound are platform-independent."""
    import os
    import signal
    import subprocess
    import tempfile
    import threading

    import jax as _jax
    import paddle_tpu as fluid
    from paddle_tpu import layers
    from paddle_tpu.errors import ServingError
    from paddle_tpu.serving import ServingFleet

    main_p, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main_p, startup):
        x = layers.data("x", [64], dtype="float32")
        h = layers.fc(x, 128, act="relu")
        out = layers.fc(h, 10, act="softmax")
    startup.random_seed = 7
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    exe.run(startup, scope=scope)
    work = tempfile.mkdtemp(prefix="pt-serve-fleet-bench-")
    model_dir = os.path.join(work, "model")
    fluid.io.save_inference_model(model_dir, ["x"], [out], exe, main_p,
                                  scope)
    device = _jax.default_backend()

    def run_arm(n, chaos=False):
        root = os.path.join(work, f"fleet{n}{'.chaos' if chaos else ''}")
        fleet = ServingFleet({"m": model_dir}, n_replicas=n, root=root,
                             buckets=buckets, hb_interval_s=hb_interval_s)
        lat_ms, errs, lock = [], [], threading.Lock()
        issued = [0]
        try:
            fleet.wait_healthy(timeout=180)

            def client(seed):
                r = np.random.RandomState(seed)
                while True:
                    with lock:
                        if issued[0] >= requests:
                            return
                        issued[0] += 1
                    rows = int(r.randint(1, 5))
                    feeds = {"x": r.rand(rows, 64).astype("f4")}
                    t0 = _time.perf_counter()
                    try:
                        fleet.infer("m", feeds)
                        ms = (_time.perf_counter() - t0) * 1e3
                        with lock:
                            lat_ms.append(ms)
                    except ServingError as e:
                        with lock:
                            errs.append(e.reason)

            threads = [threading.Thread(target=client, args=(i,))
                       for i in range(clients)]
            with _gc_quiesced():
                t0 = _time.perf_counter()
                for t in threads:
                    t.start()
                if chaos:
                    # let ~1/3 of the window elapse, then kill rank 0;
                    # the supervisor restarts it inside the window
                    while True:
                        with lock:
                            if issued[0] >= requests // 3:
                                break
                        _time.sleep(0.005)
                    with fleet._lock:
                        fleet._replicas[0]["proc"].send_signal(
                            signal.SIGKILL)
                for t in threads:
                    t.join()
                wall = _time.perf_counter() - t0
            ledger = fleet.stats()
            if chaos:
                # the arm also prices recovery: the supervisor must
                # restore full capacity before the fleet shuts down (the
                # --min-healthy-replicas gate below reads the final
                # snapshot)
                fleet.wait_healthy(timeout=180)
        finally:
            fleet.stop()
        arr = np.asarray(lat_ms) if lat_ms else np.asarray([0.0])
        rec = {"replicas": n, "rps": round(len(lat_ms) / wall, 1),
               "wall_s": round(wall, 3),
               "p50_ms": round(float(np.percentile(arr, 50)), 2),
               "p99_ms": round(float(np.percentile(arr, 99)), 2),
               "completed": len(lat_ms), "lost": len(errs),
               "loss_reasons": sorted(set(errs)),
               "ledger_exact": bool(
                   ledger["requests"] == ledger["completed"]
                   + ledger["errors"])}
        if chaos:
            # every loss classified, bounded by one replica's in-flight
            rec["losses_all_classified"] = all(
                r == "replica_down" for r in errs)
            rec["loss_bound"] = fleet.router.inflight_cap + 1
            tools = os.path.join(os.path.dirname(
                os.path.abspath(__file__)), "tools")
            rec["fleet_check_rc"] = subprocess.call(
                [sys.executable, os.path.join(tools, "serve_trace.py"),
                 "--fleet", "--check", root],
                stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL)
            rec["perf_gate_rc"] = subprocess.call(
                [sys.executable, os.path.join(tools, "perf_report.py"),
                 "--check", os.path.join(root, "telemetry",
                                         "router.jsonl"),
                 "--min-healthy-replicas", str(n),
                 "--check-roll-convergence"],
                stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL)
        return rec

    arms = {n: run_arm(n) for n in replica_counts}
    chaos = run_arm(2, chaos=True)
    base2 = arms.get(2, arms[max(arms)])
    overhead = (round(1.0 - chaos["rps"] / base2["rps"], 4)
                if base2["rps"] else None)
    for n, a in sorted(arms.items()):
        print(f"serve-fleet n={n}: {a['rps']} req/s p50 {a['p50_ms']} ms "
              f"p99 {a['p99_ms']} ms (lost {a['lost']})", file=sys.stderr)
    print(f"serve-fleet chaos n=2 (SIGKILL rank0 mid-window): "
          f"{chaos['rps']} req/s, lost {chaos['lost']} "
          f"(all classified: {chaos['losses_all_classified']}, "
          f"bound {chaos['loss_bound']}), rps overhead "
          f"{overhead if overhead is not None else 'n/a'}; "
          f"fleet_check rc={chaos['fleet_check_rc']} "
          f"perf_gate rc={chaos['perf_gate_rc']}", file=sys.stderr)
    return {"metric": "serve_fleet_rps", "value": base2["rps"],
            "unit": "req/sec", "device": device,
            "throughput_claim": ("measured" if device == "tpu"
                                 else "parity_only_off_device"),
            "replica_curve": {str(n): a for n, a in sorted(arms.items())},
            "chaos_arm": chaos, "chaos_rps_overhead_frac": overhead,
            "scaling_note": (
                "single-host replicas contend for the same cores, so the "
                "off-device replica curve prices wire+routing overhead "
                "and failover correctness, NOT horizontal scaling"
                if device != "tpu" else "per-chip replicas"),
            "requests_per_arm": requests, "clients": clients,
            "buckets": list(buckets)}


def bench_chaos(steps=48, batch_size=256, max_inflight=3,
                fault_spec="bad_batch@5;nan@13;device@21:UNAVAILABLE;"
                           "device@29:RESOURCE_EXHAUSTED"):
    """Throughput under a fixed fault schedule: the same seeded MLP run
    twice through `resilient_train_loop` — once clean, once with the
    fault injector delivering one of each recoverable class — reporting
    both rates, the recovery ledger, and the end-state parity check that
    the chaos run's params match what the surviving batches should
    produce.  The resilience overhead (snapshots + per-step resolution
    under skip_step) is the metric: it is the price of not dying."""
    import paddle_tpu as fluid
    from paddle_tpu import monitor
    from tools.perf_report import retry_fraction

    main_p, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main_p, startup):
        x = fluid.layers.data("x", [64], dtype="float32")
        y = fluid.layers.data("y", [1], dtype="float32")
        h = fluid.layers.fc(x, 256, act="relu")
        h = fluid.layers.fc(h, 256, act="relu")
        loss = fluid.layers.mean(
            fluid.layers.square_error_cost(fluid.layers.fc(h, 1), y))
        fluid.optimizer.Adam(1e-3).minimize(loss)
    startup.random_seed = main_p.random_seed = 7
    rng = np.random.RandomState(0)
    feeds = []
    for _ in range(steps):
        xv = rng.rand(batch_size, 64).astype("f4")
        feeds.append({"x": xv, "y": xv.sum(1, keepdims=True)})

    def run(injector, nan_mode):
        exe = fluid.Executor(fluid.TPUPlace(0))
        scope = fluid.Scope()
        exe.run(startup, scope=scope)
        t0 = _time.perf_counter()
        stats = fluid.resilient_train_loop(
            exe, main_p, lambda: list(feeds), [loss], scope=scope,
            injector=injector, nan_mode=nan_mode,
            policy=fluid.RetryPolicy(backoff_base_s=0.0),
            max_inflight=max_inflight, log_period=8)
        return stats, _time.perf_counter() - t0

    run(None, "raise")  # warmup/compile outside both timing windows
    monitor.enable()
    clean_stats, clean_wall = run(None, "raise")
    monitor.reset()  # recovery_frac must count the chaos run's steps only
    chaos_stats, chaos_wall = run(fluid.FaultInjector(fault_spec),
                                  "skip_step")
    frac = retry_fraction(monitor.step_records())
    monitor.disable()
    clean_sps = clean_stats.steps / clean_wall
    chaos_sps = chaos_stats.steps / chaos_wall
    # expected committed steps: each bad batch and each skip_step'd NaN
    # drops exactly one batch from the schedule; retries drop none
    from paddle_tpu.faults import parse_fault_spec

    dropped = sum(1 for f in parse_fault_spec(fault_spec)
                  if f.kind in ("bad_batch", "nan"))
    survived = bool(chaos_stats.steps == steps - dropped)
    print(f"chaos: clean {clean_sps:.1f} steps/s, faulted {chaos_sps:.1f} "
          f"steps/s (skipped {chaos_stats.skipped_batches} batches, "
          f"{chaos_stats.skipped_steps} steps, {chaos_stats.retries} "
          f"retries)", file=sys.stderr)
    return {"metric": "chaos_train_steps_per_sec", "value": round(chaos_sps, 2),
            "unit": "steps/sec", "clean_steps_per_sec": round(clean_sps, 2),
            "chaos_overhead": round(1.0 - chaos_sps / clean_sps, 4)
            if clean_sps else 0.0,
            "fault_spec": fault_spec, "steps": chaos_stats.steps,
            "survived": survived,
            "skipped_batches": chaos_stats.skipped_batches,
            "skipped_steps": chaos_stats.skipped_steps,
            "retries": chaos_stats.retries,
            "degraded_inflight": chaos_stats.degraded_inflight,
            "final_max_inflight": chaos_stats.final_max_inflight,
            "recovery_frac": round(frac, 4),
            "batch_size": batch_size, "max_inflight": max_inflight}


def bench_chaos_data(fault_spec="corrupt_chunk@2", steps=32, batch_size=64,
                     budget=4, chunk_records=64):
    """Data-corruption A/B (ISSUE 5): the same seeded MLP trained from a
    RecordIO-backed checkpointable reader pipeline twice — once over
    pristine files, once after the fault injector mutates chunks ON DISK
    (`corrupt_chunk@N` / `truncated_file@N` via `on_files`) with a corrupt
    budget armed.  Reports both rates, the corrupt-chunk ledger
    (`data.corrupt_chunks` / `data.chunks_scanned`), and how many batches
    survived — the cost of tolerating rotting storage as a number."""
    import os
    import shutil
    import tempfile

    import paddle_tpu as fluid
    from paddle_tpu import monitor, recordio
    from paddle_tpu import reader as rd
    from paddle_tpu.faults import FaultInjector

    main_p, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main_p, startup):
        x = fluid.layers.data("x", [16], dtype="float32")
        y = fluid.layers.data("y", [1], dtype="float32")
        h = fluid.layers.fc(x, 64, act="relu")
        loss = fluid.layers.mean(
            fluid.layers.square_error_cost(fluid.layers.fc(h, 1), y))
        fluid.optimizer.Adam(1e-3).minimize(loss)
    startup.random_seed = main_p.random_seed = 7

    root = tempfile.mkdtemp(prefix="pt-chaos-data-")
    rng = np.random.RandomState(0)
    path = os.path.join(root, "train.rio")
    recordio.write_arrays(
        path,
        [(rng.rand(16).astype("f4"),) for _ in range(steps * batch_size)],
        max_chunk_records=chunk_records)

    def make_factory(p):
        def to_feed(samples):
            xv = np.stack([s[0] for s in samples])
            return {"x": xv, "y": xv.sum(1, keepdims=True)}

        def factory():
            return rd.map_readers(
                to_feed, rd.batch(recordio.reader_creator(p), batch_size,
                                  drop_last=True))

        return factory

    def run(p):
        recordio.reset_corrupt_spent()
        exe = fluid.Executor(fluid.TPUPlace(0))
        scope = fluid.Scope()
        exe.run(startup, scope=scope)
        t0 = _time.perf_counter()
        stats = fluid.resilient_train_loop(
            exe, main_p, make_factory(p), [loss], scope=scope,
            policy=fluid.RetryPolicy(backoff_base_s=0.0),
            max_inflight=3, log_period=8)
        return stats, _time.perf_counter() - t0

    run(path)  # warmup/compile outside both timing windows
    monitor.enable()
    clean_stats, clean_wall = run(path)
    corrupt_path = os.path.join(root, "train_corrupt.rio")
    shutil.copyfile(path, corrupt_path)
    injector = FaultInjector(fault_spec)
    injector.on_files([corrupt_path])
    monitor.reset()
    fluid.set_flags({"FLAGS_data_corrupt_budget": budget})
    try:
        chaos_stats, chaos_wall = run(corrupt_path)
    finally:
        fluid.set_flags({"FLAGS_data_corrupt_budget": 0})
    counters = monitor.get_monitor().counter_values()
    monitor.disable()
    clean_sps = clean_stats.steps / clean_wall
    chaos_sps = chaos_stats.steps / chaos_wall if chaos_wall else 0.0
    corrupt = int(counters.get("data.corrupt_chunks", 0))
    scanned = int(counters.get("data.chunks_scanned", 0))
    print(f"chaos-data: clean {clean_sps:.1f} steps/s, corrupted "
          f"{chaos_sps:.1f} steps/s ({corrupt}/{scanned} chunks dropped, "
          f"{clean_stats.steps - chaos_stats.steps} batch(es) lost)",
          file=sys.stderr)
    return {"metric": "chaos_data_train_steps_per_sec",
            "value": round(chaos_sps, 2), "unit": "steps/sec",
            "clean_steps_per_sec": round(clean_sps, 2),
            "corrupt_overhead": round(1.0 - chaos_sps / clean_sps, 4)
            if clean_sps else 0.0,
            "fault_spec": fault_spec, "budget": budget,
            "corrupt_chunks": corrupt, "chunks_scanned": scanned,
            "data_corrupt_frac": round(corrupt / scanned, 5) if scanned else 0.0,
            "clean_steps": clean_stats.steps, "chaos_steps": chaos_stats.steps,
            "batches_lost": clean_stats.steps - chaos_stats.steps,
            "survived": bool(chaos_stats.steps > 0),
            "batch_size": batch_size, "chunk_records": chunk_records}


def bench_chaos_storage(fault_spec="enospc@12", steps=36, batch_size=256,
                        save_every=6, max_inflight=3):
    """Storage-fault A/B (ISSUE 15): the same seeded MLP trained under
    `resilient_train_loop` with periodic checkpoints twice — once on
    healthy storage, once with the fault injector failing the io.py choke
    point (`enospc@S` / `ro_fs@S` / `eio@N` / `slow_io@N:MS`).  Reports
    both rates, the DEGRADED WINDOW (steps training ran past its last
    committed checkpoint while the store failed), the recovery overhead
    (retries + skipped save rounds as wall-clock), and the parity bit:
    storage faults drop no batches, so the chaos run's end-state params
    must be BIT-IDENTICAL to the clean run's — surviving the store is
    free of training-semantics cost by construction, and this proves it."""
    import tempfile

    import paddle_tpu as fluid
    from paddle_tpu import monitor
    from paddle_tpu.checkpoint_manager import CheckpointManager
    # parity via the integrity module's full-state content digest — ONE
    # digest definition shared with the sentinel, not another hand-rolled
    # scope hash that could silently drift from it
    from paddle_tpu.integrity import state_digest as digest

    main_p, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main_p, startup):
        x = fluid.layers.data("x", [64], dtype="float32")
        y = fluid.layers.data("y", [1], dtype="float32")
        h = fluid.layers.fc(x, 256, act="relu")
        loss = fluid.layers.mean(
            fluid.layers.square_error_cost(fluid.layers.fc(h, 1), y))
        fluid.optimizer.Adam(1e-3).minimize(loss)
    startup.random_seed = main_p.random_seed = 7
    rng = np.random.RandomState(0)
    feeds = []
    for _ in range(steps):
        xv = rng.rand(batch_size, 64).astype("f4")
        feeds.append({"x": xv, "y": xv.sum(1, keepdims=True)})

    def run(spec):
        exe = fluid.Executor(fluid.TPUPlace(0))
        scope = fluid.Scope()
        exe.run(startup, scope=scope)
        cm = CheckpointManager(tempfile.mkdtemp(prefix="pt-chaos-storage-"),
                               program=main_p, scope=scope,
                               save_every_steps=save_every)
        t0 = _time.perf_counter()
        stats = fluid.resilient_train_loop(
            exe, main_p, lambda: list(feeds), [loss], scope=scope,
            injector=fluid.FaultInjector(spec) if spec else None,
            checkpoint_manager=cm,
            policy=fluid.RetryPolicy(backoff_base_s=0.0),
            max_inflight=max_inflight, log_period=8)
        return stats, _time.perf_counter() - t0, cm, digest(scope)

    run(None)  # warmup/compile outside both timing windows
    monitor.enable()
    clean_stats, clean_wall, _, clean_sha = run(None)
    monitor.reset()  # the storage ledger must count the chaos run only
    chaos_stats, chaos_wall, cm, chaos_sha = run(fault_spec)
    counters = monitor.get_monitor().counter_values()
    degraded = [r for r in monitor.step_records()
                if r.get("kind") == "resilience_event"
                and r.get("action") in ("storage_degraded",
                                        "ckpt_round_skipped")]
    recovered = [r for r in monitor.step_records()
                 if r.get("kind") == "resilience_event"
                 and r.get("action") == "storage_recovered"]
    monitor.disable()
    clean_sps = clean_stats.steps / clean_wall
    chaos_sps = chaos_stats.steps / chaos_wall if chaos_wall else 0.0
    # degraded window: first failed save round -> the recovering commit
    # (steps of training that ran with no durable checkpoint behind them)
    window = 0
    if degraded:
        end = recovered[0]["at_step"] if recovered \
            else chaos_stats.steps
        window = int(end - degraded[0]["at_step"]
                     + degraded[0].get("lag_steps", 0))
    parity = bool(chaos_sha == clean_sha)
    print(f"chaos-storage: clean {clean_sps:.1f} steps/s, faulted "
          f"{chaos_sps:.1f} steps/s ({len(degraded)} degraded round(s), "
          f"window {window} steps, recovered={bool(recovered)}, "
          f"parity={parity})", file=sys.stderr)
    return {"metric": "chaos_storage_train_steps_per_sec",
            "value": round(chaos_sps, 2), "unit": "steps/sec",
            "clean_steps_per_sec": round(clean_sps, 2),
            "storage_overhead": round(1.0 - chaos_sps / clean_sps, 4)
            if clean_sps else 0.0,
            "fault_spec": fault_spec, "steps": chaos_stats.steps,
            "survived": bool(chaos_stats.steps == steps),
            "degraded_rounds": len(degraded),
            "degraded_window_steps": window,
            "recovered": bool(recovered),
            "save_retries": int(counters.get(
                "resilience.ckpt_save_retries", 0)),
            "storage_errors": int(counters.get(
                "resilience.ckpt_storage_errors", 0)),
            "committed_saves": int(counters.get("checkpoint.saves", 0)),
            "parity": parity,
            "batch_size": batch_size, "save_every": save_every,
            "max_inflight": max_inflight}


def bench_overlap(steps=16, n_procs=2, bucket_mb=4.0, batch_size=256,
                  width=1024, depth=4):
    """2-process backward-overlapped gradient all-reduce A/B (ISSUE 7):
    the same seeded MLP trained through real multi-process gangs
    (paddle_tpu.launch.run_gang) under three grad-sync arms —

      serial    one flat all-reduce after the whole backward (the
                fetch-barrier baseline)
      bucketed  size-capped buckets issued as grads become ready,
                reverse-topological order (CompiledProgram.
                with_grad_overlap; FLAGS_dp_bucket_mb-shaped)
      gspmd     the pre-ISSUE-7 GSPMD-derived collectives, for reference

    Reports each arm's gang rate plus the acceptance checks: the bucketed
    arm must beat the serial baseline and the two must end bit-identical
    (bucketing never changes what each grad element is summed with).  The
    micro-version of this A/B (no process overhead, production bucketing
    code) is tools/collective_bench.py --overlap."""
    import os

    from paddle_tpu.launch import run_gang

    worker = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "tests", "dist_worker_overlap.py")

    def one(mode):
        res = run_gang(
            [sys.executable, worker], n_procs,
            extra_env={"GRAD_SYNC_MODE": mode, "RUN_STEPS": str(steps),
                       "BUCKET_MB": str(bucket_mb),
                       "BATCH_SIZE": str(batch_size),
                       "MODEL_WIDTH": str(width),
                       "MODEL_DEPTH": str(depth)},
            max_restarts=0, timeout=540)
        assert res.ok, f"{mode} overlap gang failed: {res.workers}"
        recs = _gang_results(res)
        assert len(recs) == n_procs, f"{mode}: got {len(recs)} RESULT lines"
        shas = {r["params_sha"] for r in recs}
        assert len(shas) == 1, f"{mode}: ranks diverged: {shas}"
        # gang rate: the slowest worker's window is the gang's window
        wall = max(r["wall_s"] for r in recs)
        return {"steps_per_sec": round(steps / wall, 3),
                "wall_s": round(wall, 4), "params_sha": shas.pop(),
                "last_loss": recs[0]["last_loss"],
                "skew": _gang_skew(res)}

    arms = {m: one(m) for m in ("serial", "bucketed", "gspmd")}
    parity = arms["serial"]["params_sha"] == arms["bucketed"]["params_sha"]
    speedup = (arms["bucketed"]["steps_per_sec"]
               / arms["serial"]["steps_per_sec"])
    print(f"overlap: serial {arms['serial']['steps_per_sec']:.2f} steps/s, "
          f"bucketed {arms['bucketed']['steps_per_sec']:.2f} steps/s "
          f"(x{speedup:.3f}), gspmd {arms['gspmd']['steps_per_sec']:.2f} "
          f"steps/s, bit-parity={parity}", file=sys.stderr)
    return {"metric": "dp_grad_overlap_ab_steps_per_sec",
            "value": arms["bucketed"]["steps_per_sec"], "unit": "steps/sec",
            "serial_steps_per_sec": arms["serial"]["steps_per_sec"],
            "bucketed_steps_per_sec": arms["bucketed"]["steps_per_sec"],
            "gspmd_steps_per_sec": arms["gspmd"]["steps_per_sec"],
            "speedup_vs_serial": round(speedup, 4),
            "overlap_confirmed": bool(speedup > 1.0),
            "bit_parity_serial_vs_bucketed": bool(parity),
            "last_loss": arms["bucketed"]["last_loss"],
            # the bucketed arm's cross-rank skew record (trace_merge over
            # the gang's telemetry) — perf_report --check-bench gates it
            **arms["bucketed"].get("skew", {}),
            "n_procs": n_procs, "steps": steps, "bucket_mb": bucket_mb,
            "batch_size": batch_size}


def bench_chaos_dist(fault_spec, steps=12, n_procs=2, save_every=3,
                     max_restarts=2, elastic=False):
    """Multi-worker chaos benchmark: the same 2-worker sync-SGD gang run
    uninterrupted and under a distributed fault schedule
    (kill_worker@S:RANK / stall_worker@S:RANK:SECS), both through
    `paddle_tpu.launch.run_gang` + the resilient gang worker.  Reports
    both gang rates, the restart ledger, and the end-state parity check —
    gang-restart overhead (detection + rollback + relaunch + replay) as a
    number, the multi-worker analogue of the single-process chaos bench
    above.

    `elastic=True` (ISSUE 9) switches every arm to the elastic worker
    (checkpointable sharded streams, elastic CheckpointManager) and adds
    a THIRD arm: the same kill under `run_gang(elastic=True)` — the gang
    shrinks to N-1, keeps training, and grows back when capacity
    returns.  The record reports resize overhead and the post-resize
    (final grown incarnation) throughput next to the fixed-size restart
    baseline.  Elastic parity is allclose-grade, not bit-grade: a
    different world size reassociates the dp mean (docs/robustness.md)."""
    import os
    import tempfile

    from paddle_tpu.launch import run_gang

    worker = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "tests",
                          "dist_worker_elastic.py" if elastic
                          else "dist_worker_resilient.py")
    env = {"RUN_STEPS": str(steps), "SAVE_EVERY": str(save_every),
           "FLAGS_dist_heartbeat_interval_s": "0.25",
           "FLAGS_dist_heartbeat_miss_factor": "12",
           "FLAGS_dist_watchdog_timeout_s": "60"}
    if elastic:
        # the grow decision needs the shrunk gang to live long enough to
        # observe its commit; a tiny per-step sleep keeps the window open
        env["PT_STEP_SLEEP"] = "0.05"

    def one(spec, restarts, run_elastic=False):
        root = tempfile.mkdtemp(prefix="pt-chaos-dist-")
        e = dict(env)
        if spec:
            e["FLAGS_fault_spec"] = spec
        t0 = _time.perf_counter()
        res = run_gang([sys.executable, worker], n_procs,
                       checkpoint_root=root, extra_env=e,
                       max_restarts=restarts, timeout=540,
                       elastic=run_elastic, min_procs=1)
        wall = _time.perf_counter() - t0
        shas = [r["params_sha"] for r in _gang_results(res)]
        return res, wall, shas

    clean_res, clean_wall, clean_shas = one(None, 0)
    assert clean_res.ok, "clean gang run failed; chaos numbers meaningless"
    chaos_res, chaos_wall, chaos_shas = one(fault_spec, max_restarts)
    parity = bool(chaos_res.ok and clean_shas and chaos_shas
                  and len(set(clean_shas + chaos_shas)) == 1)
    clean_sps = steps / clean_wall
    chaos_sps = steps / chaos_wall if chaos_res.ok else 0.0
    print(f"chaos-dist: clean {clean_sps:.2f} steps/s, faulted "
          f"{chaos_sps:.2f} steps/s ({chaos_res.restarts} gang restart(s), "
          f"parity={parity})", file=sys.stderr)
    rec = {"metric": "chaos_dist_train_steps_per_sec",
           "value": round(chaos_sps, 3), "unit": "steps/sec",
           "clean_steps_per_sec": round(clean_sps, 3),
           "gang_restart_overhead": round(1.0 - chaos_sps / clean_sps, 4)
           if clean_sps and chaos_sps else None,
           "fault_spec": fault_spec, "n_procs": n_procs, "steps": steps,
           "survived": bool(chaos_res.ok),
           "gang_restarts": chaos_res.restarts,
           "incarnations": chaos_res.incarnations,
           "worker_deaths": [d for i in chaos_res.incidents
                             for d in i.get("dead", [])],
           # cross-rank skew over the CLEAN gang's telemetry (the chaos
           # arm's skew measures the injected fault, not the gang)
           **_gang_skew(clean_res),
           "telemetry_dir": chaos_res.telemetry_dir,
           "bit_parity_vs_clean": parity}
    if not elastic:
        return rec
    el_res, el_wall, el_shas = one(fault_spec, max_restarts,
                                   run_elastic=True)
    el_sps = steps / el_wall if el_res.ok else 0.0
    # post-resize throughput: the final (grown-back) incarnation's own
    # rate, from its RESULT line — what the gang sustains once capacity
    # is back, with the resize machinery out of the hot path
    post_sps = None
    final = _gang_results(el_res)
    if el_res.ok and final:
        r0 = final[0]
        if r0.get("steps_run") and r0.get("wall_s"):
            post_sps = round(r0["steps_run"] / r0["wall_s"], 3)
    print(f"chaos-dist --elastic: {el_sps:.2f} steps/s end-to-end "
          f"({el_res.resizes} resize(s), sizes {el_res.size_history}), "
          f"post-resize {post_sps} steps/s vs fixed-restart "
          f"{chaos_sps:.2f}", file=sys.stderr)
    rec["elastic"] = {
        "steps_per_sec": round(el_sps, 3),
        "post_resize_steps_per_sec": post_sps,
        "resize_overhead": round(1.0 - el_sps / clean_sps, 4)
        if clean_sps and el_sps else None,
        "fixed_restart_steps_per_sec": round(chaos_sps, 3),
        "survived": bool(el_res.ok),
        "resizes": el_res.resizes,
        "size_history": el_res.size_history,
        "resize_events": el_res.resize_events,
        "incarnations": el_res.incarnations,
        "ranks_agree": bool(el_res.ok and len(set(el_shas)) == 1),
    }
    return rec


def bench_chaos_integrity(fault_spec="rot_shard@1", steps=24, save_every=4,
                          batch_size=64, n_procs=2, max_restarts=2):
    """Silent-corruption chaos A/B (ISSUE 14).

    rot_shard specs run single-process: train with periodic commits while
    the injector flips a byte of the Nth COMMITTED checkpoint post-COMMIT,
    then a fresh process resumes — the at-rest digests must reject the
    rotted snapshot (`integrity.ckpt_rejected`), the walk-back lands one
    earlier, and the resumed run must end bit-identical to a resume from
    a pristine tree.  The record reports the walk-back ledger and the
    resume-time overhead of paying one extra restore.

    flip_bit specs route to a 2-process gang on the integrity worker
    (FLAGS_integrity_check_period armed): the live digests must diverge,
    the vote must name the flipped rank, the gang restarts from the
    newest quarantine-clean checkpoint, and the final params must be
    bit-identical to an uninterrupted gang — detection + restart + replay
    overhead as a number."""
    import os
    import tempfile

    import paddle_tpu as fluid
    from paddle_tpu import monitor
    from paddle_tpu.faults import FaultInjector, parse_fault_spec

    kinds = {f.kind for f in parse_fault_spec(fault_spec)}
    if "flip_bit" in kinds:
        from paddle_tpu.launch import run_gang

        worker = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                              "tests", "dist_worker_integrity.py")
        env = {"RUN_STEPS": str(steps), "SAVE_EVERY": str(save_every),
               "INTEGRITY_PERIOD": "2", "PT_STEP_SLEEP": "0.02",
               "FLAGS_dist_heartbeat_interval_s": "0.1",
               "FLAGS_dist_heartbeat_miss_factor": "30",
               "FLAGS_dist_watchdog_timeout_s": "60"}

        def one(spec, restarts):
            root = tempfile.mkdtemp(prefix="pt-chaos-integrity-")
            e = dict(env)
            if spec:
                e["FLAGS_fault_spec"] = spec
            t0 = _time.perf_counter()
            res = run_gang([sys.executable, worker], n_procs,
                           checkpoint_root=root, extra_env=e,
                           max_restarts=restarts, timeout=540)
            return res, _time.perf_counter() - t0

        clean_res, clean_wall = one(None, 0)
        assert clean_res.ok, "clean gang run failed; chaos numbers " \
                             "meaningless"
        chaos_res, chaos_wall = one(fault_spec, max_restarts)
        clean_shas = [r["params_sha"] for r in _gang_results(clean_res)]
        chaos_shas = [r["params_sha"] for r in _gang_results(chaos_res)]
        # the verdict is printed by the DETECTING incarnation, whose
        # workers exit classified without a RESULT line — harvest it
        # from the full per-incarnation stderr history
        import re as _re

        named = set()
        for inc in chaos_res.history:
            for _code, _out, err in inc:
                for m in _re.finditer(
                        r"INTEGRITY_FAILURE corrupt_ranks=\[([\d, ]*)\]",
                        err or ""):
                    named.update(int(x) for x in m.group(1).split(",")
                                 if x.strip())
        named = sorted(named)
        parity = bool(chaos_res.ok and clean_shas and chaos_shas
                      and len(set(clean_shas + chaos_shas)) == 1)
        print(f"chaos-integrity: flip_bit detected "
              f"(corrupt rank(s) {named}), {chaos_res.restarts} gang "
              f"restart(s), parity={parity}", file=sys.stderr)
        return {"metric": "chaos_integrity_flip_bit",
                "value": round(chaos_wall - clean_wall, 3),
                "unit": "sec_recovery_overhead",
                "fault_spec": fault_spec, "corrupt_ranks_named": named,
                "gang_restarts": chaos_res.restarts,
                "bit_parity": parity, "steps": steps}

    # rot_shard: single-process commit-rot-resume A/B
    main_p, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main_p, startup):
        x = fluid.layers.data("x", [32], dtype="float32")
        y = fluid.layers.data("y", [1], dtype="float32")
        h = fluid.layers.fc(x, 64, act="relu")
        loss = fluid.layers.mean(
            fluid.layers.square_error_cost(fluid.layers.fc(h, 1), y))
        fluid.optimizer.Adam(1e-3).minimize(loss)
    startup.random_seed = main_p.random_seed = 7
    rng = np.random.RandomState(0)
    feeds = []
    for _ in range(steps):
        xv = rng.rand(batch_size, 32).astype("f4")
        feeds.append({"x": xv, "y": xv.sum(1, keepdims=True)})

    def train(root, injector, resume, n):
        exe = fluid.Executor(fluid.TPUPlace(0))
        scope = fluid.Scope()
        if not resume:
            exe.run(startup, scope=scope)
        cm = fluid.CheckpointManager(root, program=main_p, scope=scope,
                                     save_every_steps=save_every)
        t0 = _time.perf_counter()
        stats = fluid.resilient_train_loop(
            exe, main_p, lambda: list(feeds), [loss], scope=scope,
            checkpoint_manager=cm, resume=resume, injector=injector,
            max_inflight=1, max_steps=n)
        from paddle_tpu import integrity as _integ

        return stats, _time.perf_counter() - t0, _integ.state_digest(scope)

    half = steps // 2
    monitor.enable()
    root_a = tempfile.mkdtemp(prefix="pt-rot-clean-")
    root_b = tempfile.mkdtemp(prefix="pt-rot-chaos-")
    train(root_a, None, False, half)
    train(root_b, FaultInjector(fault_spec), False, half)
    rej0 = monitor.counter("integrity.ckpt_rejected").value
    _, clean_wall, clean_sha = train(root_a, None, True, steps)
    _, chaos_wall, chaos_sha = train(root_b, None, True, steps)
    rejected = monitor.counter("integrity.ckpt_rejected").value - rej0
    monitor.disable()
    parity = bool(clean_sha == chaos_sha)
    print(f"chaos-integrity: rot_shard rejected {rejected} checkpoint(s) "
          f"on resume, walk-back overhead "
          f"{chaos_wall - clean_wall:+.3f}s, parity={parity}",
          file=sys.stderr)
    return {"metric": "chaos_integrity_rot_shard",
            "value": round(chaos_wall - clean_wall, 3),
            "unit": "sec_walkback_overhead",
            "fault_spec": fault_spec, "ckpt_rejected": int(rejected),
            "bit_parity": parity, "steps": steps,
            "survived": bool(rejected >= 1 and parity)}


def bench_online(steps=48, publish_every=8, batch_size=512, feat=8,
                 dim=16, base_vocab=4096, table_scales=(1, 4),
                 chaos_spec="kill_pserver@18", staleness_bound_steps=None):
    """Online-learning round (ISSUE 19): a CTR model whose embedding
    table lives HOST-TIERED (hot head in process, cold tail on a
    supervised parameter-server child) trains under
    `resilient_train_loop` while the publish hook streams verified
    sparse snapshots into a serving `ModelRegistry` every
    `publish_every` steps.

    Arms: one clean run per table scale (1x / 4x an HBM-equivalent base
    table — on this container "HBM-equivalent" prices BYTES MOVED
    through the host tier, not a real device budget), plus a chaos arm
    that SIGKILLs the pserver child mid-run (`kill_pserver@S` via the
    fault injector).  Each arm reports examples/sec and the
    publish-to-serving staleness ledger (max trained-step minus
    last-published-step, from the `serving.publish_staleness_steps`
    gauge the loop maintains); the chaos arm additionally requires
    bit-identical table recovery (server digest before kill == after
    restart-and-replay at the same op count is the unit-tested
    invariant; here the END-TO-END check is that every published
    snapshot passed the ladder, cadence held, and the staleness bound
    declared in this record was never exceeded), and the arm's own
    metrics stream must pass `perf_report --check
    --max-publish-staleness-steps` (gate rc embedded in the record)."""
    import os
    import subprocess
    import tempfile

    import paddle_tpu as fluid
    from paddle_tpu import io, layers, monitor
    from paddle_tpu.core.selected_rows import SelectedRows
    from paddle_tpu.faults import FaultInjector
    from paddle_tpu.monitor import MonitorLogger
    from paddle_tpu.parallel.embedding import TieredEmbedding
    from paddle_tpu.param_server import KVClient, PServerSupervisor
    from paddle_tpu.serving import ModelRegistry, publish

    bound = (2 * publish_every if staleness_bound_steps is None
             else int(staleness_bound_steps))
    # a pserver kill costs at most the client-retry window in degraded
    # steps; one publish period is the declared recovery budget
    lag_bound = publish_every

    # training program: the embedding block arrives as a FEED (pulled
    # from the tiered table per batch); calc_gradient taps the grad to
    # push back — the host-table pattern of tests/test_param_server.py
    main_p, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main_p, startup):
        emb = layers.data("emb", [feat * dim], dtype="float32")
        label = layers.data("label", [1], dtype="float32")
        h = layers.fc(emb, 64, act="relu",
                      param_attr=fluid.ParamAttr(name="ol_h"),
                      bias_attr=fluid.ParamAttr(name="ol_hb"))
        pred = layers.fc(h, 1, param_attr=fluid.ParamAttr(name="ol_p"),
                         bias_attr=False)
        loss = fluid.layers.mean(
            fluid.layers.square_error_cost(pred, label))
        (emb_grad,) = fluid.calc_gradient(loss, [emb])
        fluid.optimizer.SGD(0.1).minimize(
            loss, parameter_list=["ol_h", "ol_hb", "ol_p"])
    startup.random_seed = main_p.random_seed = 7

    def serving_program(vocab):
        sp, st = fluid.Program(), fluid.Program()
        with fluid.program_guard(sp, st):
            ids = layers.data("ids", [feat], dtype="int64")
            e = layers.embedding(ids, size=[vocab, dim], is_sparse=True,
                                 param_attr=fluid.ParamAttr(name="ol_tbl"))
            h = layers.fc(layers.reshape(e, [-1, feat * dim]), 64,
                          act="relu",
                          param_attr=fluid.ParamAttr(name="ol_h"),
                          bias_attr=fluid.ParamAttr(name="ol_hb"))
            pr = layers.fc(h, 1, param_attr=fluid.ParamAttr(name="ol_p"),
                           bias_attr=False)
        st.random_seed = 7
        return sp, st, pr

    def run_arm(scale, chaos=False):
        vocab = base_vocab * scale
        root = tempfile.mkdtemp(prefix=f"pt-online-x{scale}-")
        metrics = os.path.join(root, "metrics.jsonl")
        monitor.enable()
        logger = monitor.attach_logger(MonitorLogger(metrics))
        sup = PServerSupervisor(os.path.join(root, "ps"),
                                optimizer="sgd", lr=0.1,
                                snapshot_every_ops=64).start()
        sup.wait_ready()
        client = KVClient(sup.endpoint)
        tiered = TieredEmbedding(client, "ol_tbl", vocab, dim,
                                 hot_rows=vocab // 4, lr=0.1, seed=3)
        exe = fluid.Executor(fluid.CPUPlace())
        scope = fluid.Scope()
        exe.run(startup, scope=scope)

        # serving side: boot the registry on the step-0 table
        sprog, sstart, spred = serving_program(vocab)
        sscope = fluid.Scope()
        exe.run(sstart, scope=sscope)
        reg = ModelRegistry(place=fluid.CPUPlace())
        snames = [v.name for v in io._persistables(sprog)]

        def snapshot_dir(step):
            d = os.path.join(root, f"snap-{step:06d}")
            pub = fluid.Scope()
            pub.set_var("ol_tbl", tiered.export_selected_rows())
            for n in snames:
                if n != "ol_tbl":
                    v = scope.find_var(n)
                    assert v is not None, f"dense var {n!r} not trained"
                    pub.set_var(n, np.asarray(v))
            io.save_sharded(d, snames, pub, program=sprog,
                            process_index=0)
            return d

        d0 = os.path.join(root, "model-0")
        sscope.set_var("ol_tbl",
                       np.asarray(tiered.export_selected_rows()))
        for n in snames:
            if n != "ol_tbl":
                v = scope.find_var(n)
                assert v is not None, f"dense var {n!r} not in train scope"
                sscope.set_var(n, np.asarray(v))
        io.save_inference_model(d0, ["ids"], [spred], exe, sprog, sscope)
        reg.load("ctr", d0)

        rng = np.random.RandomState(scale)
        ids_stream = [rng.randint(0, vocab, size=(batch_size, feat))
                      for _ in range(steps)]
        w_true = rng.rand(feat * dim, 1).astype("f4")

        def loader():
            for ids in ids_stream:
                e = tiered.lookup(ids).reshape(batch_size, feat * dim)
                yield {"emb": e, "label": e @ w_true}

        step_ids = {"i": 0}

        def on_logged(step, vals):
            ids = ids_stream[step_ids["i"] % steps]
            step_ids["i"] += 1
            g = np.asarray(vals[1]).reshape(-1, dim)
            tiered.apply_grad(ids.reshape(-1), g)

        published = []

        def publish_hook(step):
            d = snapshot_dir(step)
            if injector is not None:
                injector.on_commit(d)
            published.append(step)
            publish(reg, "ctr", d)

        injector = None
        if chaos:
            injector = FaultInjector(chaos_spec).set_pserver(sup)
        t0 = _time.perf_counter()
        stats = fluid.resilient_train_loop(
            exe, main_p, loader, [loss, emb_grad], scope=scope,
            injector=injector, max_inflight=1, log_period=1,
            on_logged=on_logged, publish_hook=publish_hook,
            publish_period_steps=publish_every,
            policy=fluid.RetryPolicy(backoff_base_s=0.0))
        wall = _time.perf_counter() - t0
        from tools.perf_report import publish_staleness_steps as _stale

        logger.write_snapshot()  # final counter/gauge state for the gates
        monitor.detach_logger(logger)
        counters = monitor.get_monitor().counter_values()
        with open(metrics) as f:
            lines = [json.loads(ln) for ln in f if ln.strip()]
        staleness = _stale(lines)
        monitor.disable()
        monitor.reset()
        tools = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                             "tools")
        # --steady-after past the run: every publish stages a FRESH
        # scope, so the ladder's verification compile moves the global
        # recompile counter each period by design — the steady-state
        # recompile gate is about the TRAINING loop's cache and is
        # skipped here, while the staleness/host-lag gates (the round's
        # contract) run against the declared bounds
        gate_rc = subprocess.call(
            [sys.executable, os.path.join(tools, "perf_report.py"),
             "--check", metrics, "--steady-after", str(steps + 2),
             "--max-publish-staleness-steps", str(bound),
             "--max-host-lag-steps", str(lag_bound)],
            stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL)
        table_bytes = vocab * dim * 4
        client.close()
        sup.stop()
        exs = round(stats.steps * batch_size / wall, 1) if wall else 0.0
        rec = {"scale": scale, "vocab": vocab,
               "table_mb": round(table_bytes / 1e6, 2),
               "examples_per_sec": exs, "steps": stats.steps,
               "publishes": stats.publishes,
               "publish_failures": stats.publish_failures,
               "max_staleness_steps": int(staleness or 0),
               "staleness_bound_steps": bound,
               "staleness_bound_ok": bool((staleness or 0) <= bound),
               "host_lag_steps": tiered.host_lag_steps,
               "host_lag_bound_steps": lag_bound,
               "perf_gate_rc": gate_rc}
        if chaos:
            rec.update({
                "fault_spec": chaos_spec,
                "pserver_restarts": sup.restarts,
                "push_retries": int(counters.get("ps.retries", 0)),
                "push_dedup": int(counters.get("ps.push_dedup", 0)),
                "degraded_steps": int(
                    counters.get("sparse.degraded_steps", 0)),
                "survived": bool(stats.steps == steps
                                 and not sup.failed)})
        return rec

    arms = {s: run_arm(s) for s in table_scales}
    chaos = run_arm(min(table_scales), chaos=True)
    for s, a in sorted(arms.items()):
        print(f"online x{s} ({a['table_mb']} MB table): "
              f"{a['examples_per_sec']} ex/s, {a['publishes']} publishes, "
              f"max staleness {a['max_staleness_steps']} steps "
              f"(bound {a['staleness_bound_steps']}, gate "
              f"rc={a['perf_gate_rc']})", file=sys.stderr)
    print(f"online chaos ({chaos['fault_spec']}): "
          f"{chaos['examples_per_sec']} ex/s, survived="
          f"{chaos['survived']} with {chaos['pserver_restarts']} pserver "
          f"restart(s), {chaos['push_retries']} client retries, "
          f"{chaos['degraded_steps']} degraded step(s), max staleness "
          f"{chaos['max_staleness_steps']} steps (bound "
          f"{chaos['staleness_bound_steps']}, gate "
          f"rc={chaos['perf_gate_rc']})", file=sys.stderr)
    import jax as _jax

    base = arms[min(table_scales)]
    device = _jax.default_backend()
    return {"metric": "online_learning_examples_per_sec",
            "value": base["examples_per_sec"], "unit": "examples/sec",
            "device": device,
            "throughput_claim": ("measured" if device == "tpu"
                                 else "parity_only_off_device"),
            "publish_every_steps": publish_every,
            "staleness_bound_steps": bound,
            "table_curve": {str(s): a for s, a in sorted(arms.items())},
            "chaos": chaos,
            "batch_size": batch_size, "steps": steps}


def bench_chaos_campaign(seed=7, per_scenario=3, max_faults=3):
    """Chaos-campaign round (ISSUE 20): seeded multi-fault schedules
    drawn over the train / online-learning / serving scenarios
    (paddle_tpu/chaos.py), every run judged by the cross-subsystem
    invariant registry, failures shrunk to minimal repro specs.  The
    record carries the campaign ledger (schedules run, invariant checks,
    violations — 0 is the pass bar), schedules/sec as the round's
    number, and the `perf_report --check --max-chaos-violations 0`
    verdict on the campaign's own metrics stream, so the gate gates the
    gate."""
    import os
    import subprocess
    import tempfile

    from paddle_tpu import chaos

    out = tempfile.mkdtemp(prefix="pt-bench-chaos-campaign-")
    metrics = os.path.join(out, "chaos_metrics.jsonl")
    t0 = _time.perf_counter()
    res = chaos.run_campaign(scenarios=("train", "online", "serving"),
                             seed=seed, per_scenario=per_scenario,
                             out_dir=out, metrics_path=metrics,
                             max_faults=max_faults)
    wall = _time.perf_counter() - t0
    tools = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                         "tools")
    gate_rc = subprocess.call(
        [sys.executable, os.path.join(tools, "perf_report.py"),
         "--check", metrics, "--max-chaos-violations", "0"],
        stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL)
    print(f"chaos-campaign: {res.schedules_run} schedule(s), "
          f"{res.invariants_checked} invariant check(s), "
          f"{len(res.violations)} violation(s) in {wall:.1f}s "
          f"(gate rc={gate_rc})", file=sys.stderr)
    for v in res.violations:
        print(f"  VIOLATION {v['invariant']} [{v['class']}] on "
              f"{v['scenario']} {v['spec']!r} -> "
              f"{v.get('shrunk_spec', '(unshrunk)')}", file=sys.stderr)
    return {"metric": "chaos_campaign_schedules_per_sec",
            "value": round(res.schedules_run / wall, 3),
            "unit": "schedules/sec", "seed": seed,
            "schedules_run": res.schedules_run,
            "invariants_checked": res.invariants_checked,
            "violations": len(res.violations),
            "repro_specs": [v.get("shrunk_spec", v["spec"])
                            for v in res.violations],
            "perf_gate_rc": gate_rc, "wall_s": round(wall, 1),
            "survived": bool(not res.violations and gate_rc == 0)}


_DIST_FAULT_KINDS = ("kill_worker", "stall_worker")
_DATA_FAULT_KINDS = ("corrupt_chunk", "truncated_file")
_INTEGRITY_FAULT_KINDS = ("flip_bit", "rot_shard")
_STORAGE_FAULT_KINDS = ("enospc", "eio@", "slow_io", "ro_fs")
_PSERVER_FAULT_KINDS = ("kill_pserver", "stall_pserver", "rot_row")


def main():
    from paddle_tpu.flags import CHECKOUT_CACHE_DIR, apply_compile_cache

    apply_compile_cache(CHECKOUT_CACHE_DIR)
    per_model = "--per-model" in sys.argv
    fault_spec = None
    for i, a in enumerate(sys.argv):
        if a == "--fault-spec" and i + 1 < len(sys.argv):
            fault_spec = sys.argv[i + 1]
        elif a.startswith("--fault-spec="):
            fault_spec = a.split("=", 1)[1]
    if "--online" in sys.argv:
        if fault_spec:
            print(json.dumps(bench_online(chaos_spec=fault_spec)))
        else:
            print(json.dumps(bench_online()))
        return
    if "--pipeline" in sys.argv:
        print(json.dumps(bench_pipeline()))
        return
    if "--overlap" in sys.argv:
        print(json.dumps(bench_overlap()))
        return
    if "--serve-fleet" in sys.argv:
        print(json.dumps(bench_serve_fleet()))
        return
    if "--serve" in sys.argv:
        if "--quant" in sys.argv:
            print(json.dumps(bench_serve_quant()))
        else:
            print(json.dumps(bench_serve()))
        return
    if "--chaos-campaign" in sys.argv:
        seed = 7
        for i, a in enumerate(sys.argv):
            if a == "--seed" and i + 1 < len(sys.argv):
                seed = int(sys.argv[i + 1])
            elif a.startswith("--seed="):
                seed = int(a.split("=", 1)[1])
        print(json.dumps(bench_chaos_campaign(seed=seed)))
        return
    if "--chaos" in sys.argv:
        # distributed entries route to the multi-worker gang bench, data
        # entries to the RecordIO corruption A/B; plain specs keep the
        # single-process resilient-loop bench
        if fault_spec and any(k in fault_spec for k in _PSERVER_FAULT_KINDS):
            # host-tier chaos rides the online-learning bench (the only
            # arm with a pserver child + sparse publish cadence to hurt)
            print(json.dumps(bench_online(chaos_spec=fault_spec)))
        elif fault_spec and any(k in fault_spec for k in _DIST_FAULT_KINDS):
            print(json.dumps(bench_chaos_dist(
                fault_spec, elastic="--elastic" in sys.argv)))
        elif fault_spec and any(k in fault_spec
                                for k in _INTEGRITY_FAULT_KINDS):
            print(json.dumps(bench_chaos_integrity(fault_spec)))
        elif fault_spec and any(k in fault_spec for k in _DATA_FAULT_KINDS):
            print(json.dumps(bench_chaos_data(fault_spec)))
        elif fault_spec and any(k in fault_spec
                                for k in _STORAGE_FAULT_KINDS):
            print(json.dumps(bench_chaos_storage(fault_spec)))
        elif fault_spec:
            print(json.dumps(bench_chaos(fault_spec=fault_spec)))
        else:
            print(json.dumps(bench_chaos()))
        return
    only = None
    for a in sys.argv[1:]:
        if not a.startswith("-"):
            only = a
    # The MFU campaign's kernels are opt-in (FLAGS_use_pallas); the model
    # arms measure them by default — platform-gated, so this is a no-op
    # off-TPU, and `--no-pallas` A/Bs the composite baseline.
    if "--no-pallas" not in sys.argv:
        import paddle_tpu as fluid

        fluid.set_flags({"FLAGS_use_pallas": True})
    results = {}
    benches = [("mnist", bench_mnist), ("nmt", bench_nmt), ("bert", bench_bert),
               ("deepfm", bench_deepfm), ("resnet50", bench_resnet50)]
    for name, fn in benches:
        if only and name != only:
            continue
        results[name] = fn()

    if per_model or only:
        for name, r in results.items():
            print(json.dumps(r))
        return

    flag = results.get("resnet50", {})
    imgs = flag.get("value", 0.0)
    print(json.dumps({
        "metric": "resnet50_train_imgs_per_sec_per_chip",
        "value": imgs,
        "unit": "imgs/sec",
        "vs_baseline": round(imgs / ROUND1_IMGS_PER_SEC, 4) if imgs else 0.0,
        "extra": {
            "mfu_bf16_analytic": flag.get("mfu_bf16_analytic"),
            "spread_pct": flag.get("spread_pct"),
            "windows_ms": flag.get("windows_ms"),
            "batch_size": flag.get("batch_size"),
            "steps_per_dispatch": flag.get("steps_per_dispatch"),
            # params_moved must ride the wrapper or check_bench's
            # dead-optimizer-state gate can never fire for the flagship
            "params_moved": flag.get("params_moved"),
            "vs_baseline_is": "this_round_imgs_per_sec / round1_imgs_per_sec",
            "models": {k: v for k, v in results.items() if k != "resnet50"},
        },
    }))


if __name__ == "__main__":
    main()
