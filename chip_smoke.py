#!/usr/bin/env python3
"""The quickest proof that the trainer and the server still start on the chip.

    python chip_smoke.py             # one TPU chip, every phase below
    python chip_smoke.py --chips 4   # the 2x2 mesh phase and nothing else

One process, through the entry points a user calls (`fluid.Program` ->
`fluid.Executor(fluid.TPUPlace(0))`, `serving.Server`), at published widths:

  trainer        BERT-base (vocab 30522, d_model 768, 12 layers, 12 heads,
                 d_ff 3072, seq 128), bf16, fused attention, Adam, batch 256
                 and steps=2 per dispatch.  Loss finite and lower at the
                 end, parameters moved, state on the chip, int64 feeds
                 narrowed without a warning.
  server         ResNet-50 (bf16, 1000 classes, 224x224) saved with
                 io.save_inference_model and served by serving.Server over
                 buckets (1, 8): sizes that hit and that pad to a bucket
                 agree with a direct Executor run, no compile after warm.
  host callback  a small TPUPlace program with a py_func op in mid-graph:
                 jax.pure_callback works on this runtime.
  --chips 4      BERT-base widths on a dp=2 x tp=2 mesh
                 (CompiledProgram.with_mesh + transformer.tp_rules()) against
                 the same Program, batch and initial state on one chip.

There is no fallback: without a TPU (JAX_PLATFORMS=cpu, no accelerator) the
script exits non-zero before it runs anything, a phase that fails raises, and
only a run in which every phase passed prints the last line,
`{"ok": true, "device": {...}}`.  tests/test_chip_smoke.py rehearses the
phases at tiny sizes on the CPU by overriding PLATFORM and the size arguments
from the test; the script itself has no such option.
"""
from __future__ import annotations

import argparse
import gc
import json
import re
import shutil
import statistics
import tempfile
import time
import warnings

import numpy as np

PLATFORM = "tpu"
SEED = 21
# what two bf16 formulations of the same step may differ by (a bucket's
# padding, one chip against the 2x2 mesh)
BF16_TOL = 5e-2

BERT_BASE = dict(vocab_size=30522, seq_len=128, d_model=768, n_layers=12,
                 n_heads=12, d_ff=3072)
BERT_BASE_2L = dict(BERT_BASE, n_layers=2)


def say(msg: str) -> None:
    print(f"chip_smoke: {msg}", flush=True)


def require_chips(n_chips: int):
    """The devices of the backend the environment selected, or exit: the
    smoke never runs on anything but the chip."""
    import jax

    devs = jax.devices()
    if devs[0].platform != PLATFORM or len(devs) < n_chips:
        raise SystemExit(
            f"chip_smoke: needs {n_chips} {PLATFORM} chip(s); JAX found "
            f"{len(devs)} {devs[0].platform!r} device(s) "
            f"({devs[0].device_kind}).  Nothing was run: there is no "
            f"fallback to another backend.")
    return devs


def on_chip(what: str, arrays) -> None:
    """Every array lives on PLATFORM devices — a fetch or a piece of state
    that sits on the host CPU backend means a fallback hid the device."""
    for name, a in arrays:
        platforms = {d.platform for d in a.devices()}
        if platforms != {PLATFORM}:
            raise AssertionError(
                f"{what}: {name} is on {sorted(platforms)}, not {PLATFORM}")


def kernels_in(exe) -> dict:
    """Mosaic kernels in the executables `exe` compiled, by kernel name
    (`tpu_custom_call` in the compiled text, named by pallas_call's
    `name=`): what ran, not what a flag asked for."""
    found: dict = {}
    for step in exe._cache.values():
        for built in step._exec_by_sig.values():
            for line in built.as_text().splitlines():
                if 'custom_call_target="tpu_custom_call"' not in line:
                    continue
                m = re.search(r'op_name="[^"]*?([\w.]+)/pallas_call', line)
                name = m.group(1) if m else "unnamed"
                found[name] = found.get(name, 0) + 1
    return found


def compile_seconds() -> float:
    from paddle_tpu import monitor

    spans = monitor.json_snapshot()["spans"]
    return sum(spans.get(k, {}).get("total_s", 0.0)
               for k in ("executor.lower", "executor.compile"))


def recompiles() -> int:
    from paddle_tpu import monitor

    return monitor.counter("executor.recompile").value


def host_copy(scope) -> dict:
    """The scope as host arrays: taken after `startup`, restored before a
    second arm, so both arms start from the same state (two builds in one
    process do not initialise alike; see the verify skill)."""
    return {n: np.array(scope.find_var(n)) for n in scope.var_names()}


def restore(scope, snapshot: dict) -> None:
    for n, v in snapshot.items():
        scope.set_var(n, v)


def bert_feed(bert: dict, batch: int, k: int) -> dict:
    """K seeded batches stacked [K, batch, seq], int64 as a reader yields
    them (the executor narrows to int32 under the x32 default)."""
    from paddle_tpu.models import transformer

    batches = [transformer.make_fake_batch(
        batch, bert["seq_len"], bert["vocab_size"],
        rng=np.random.RandomState(SEED + i)) for i in range(k)]
    feed = {n: np.stack([b[n] for b in batches]).astype("int64")
            for n in batches[0]}
    return feed if k > 1 else {n: v[0] for n, v in feed.items()}


def start_bert(bert: dict, dropout: float):
    """(main, loss, scope, exe) of a seeded BERT train program after
    `startup`: bf16 compute on f32 master weights, fused attention, Adam."""
    import paddle_tpu as fluid
    from paddle_tpu.models import transformer

    main, startup, _, fetches = transformer.build_bert(
        **bert, dropout_prob=dropout, with_optimizer=True, dtype="bfloat16",
        use_fused_attention=True)
    main.random_seed = startup.random_seed = SEED
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.TPUPlace(0))
    exe.run(startup, scope=scope)
    return main, fetches["loss"], scope, exe


# First-order optimizer accumulators per param ({param}_moment1_0 /
# _moment_0 / _velocity_0 ...: optimizer.py _add_accumulator naming).
# _mean_grad_0 LAST: rmsprop only updates it under centered=True, so
# _momentum_0 is the live accumulator there and must win the tie.
_MOMENT_SUFFIXES = ("_moment1_0", "_moment_0", "_velocity_0", "_momentum_0",
                    "_avg_squared_grad_0", "_squared_0", "_mean_grad_0")


def _params(main, scope) -> dict:
    """{param: f8 snapshot} of EVERY trainable parameter, so that a partial
    optimizer freeze (bf16 + Adam once froze every encoder parameter while
    the f32 embeddings kept moving) cannot pass by luck of program order."""
    found = ((p.name, scope.find_var(p.name)) for p in main.all_parameters())
    snap = {n: np.asarray(v).astype("f8") for n, v in found if v is not None}
    if not snap:
        raise RuntimeError("no parameters in scope")
    return snap


def _first_moments(main, scope) -> dict:
    """{param: f8 snapshot of its first moment}: the tie-breaker when a
    parameter's snapshot does not move.  A LIVE moment means the optimizer
    ran and the update rounded away below the parameter's resolution; a dead
    moment beside a dead parameter is a dropped update, the class
    tools/donation_audit.py pins statically."""
    names = set(scope.var_names())
    snap = {}
    for p in main.all_parameters():
        for suffix in _MOMENT_SUFFIXES:
            if p.name + suffix in names:
                snap[p.name] = np.asarray(
                    scope.find_var(p.name + suffix)).astype("f8")
                break
    return snap


def run_steps(exe, program, feed, loss, scope, k, dispatches):
    """`dispatches` x exe.run(steps=k); returns ([dispatches, k] losses,
    per-step seconds of each dispatch after the first)."""
    losses, secs = [], []
    for i in range(dispatches):
        t0 = time.perf_counter()
        (out,) = exe.run(program, feed=feed, fetch_list=[loss], scope=scope,
                         steps=k, return_numpy=False)
        out.block_until_ready()
        if i:
            secs.append((time.perf_counter() - t0) / k)
        on_chip("fetch", [(loss.name, out)])
        losses.append(np.asarray(out, "f8").reshape(k))
    return np.stack(losses), secs


# --------------------------------------------------------------------------
# trainer
# --------------------------------------------------------------------------


def phase_trainer(bert: dict = BERT_BASE, batch: int = 256, k: int = 2,
                  dispatches: int = 4) -> None:
    import jax

    cut = ("no cut"
           if (bert, batch, k) == (BERT_BASE, 256, 2) else
           "CUT from BERT-base at batch 256, steps=2")
    say(f"trainer: BERT {bert}, bf16, fused attention, Adam, batch {batch}, "
        f"steps={k} per dispatch, {dispatches} dispatches ({cut})")
    main, loss, scope, exe = start_bert(bert, dropout=0.1)
    before = _params(main, scope)
    feed = bert_feed(bert, batch, k)

    c0 = compile_seconds()
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        losses, secs = run_steps(exe, main, feed, loss, scope, k,
                                 dispatches)
    narrowing = [str(w.message) for w in caught if "int64" in str(w.message)]
    if narrowing:
        raise AssertionError(
            f"int64 feeds were truncated with a warning: {narrowing[:2]}")
    say("trainer: int64 ids/labels/pos_ids fed from the host, narrowed to "
        "int32 at the feed boundary, no truncation warning")

    if not np.isfinite(losses).all():
        raise AssertionError(f"trainer: non-finite loss {losses.tolist()}")
    # the K batches of a dispatch differ, so compare batch by batch: the
    # last dispatch against the first
    if not (losses[-1] < losses[0]).all():
        raise AssertionError(
            f"trainer: loss did not fall: first dispatch "
            f"{losses[0].tolist()}, last {losses[-1].tolist()}")
    say(f"trainer: loss per step {np.round(losses.reshape(-1), 4).tolist()}")

    after = _params(main, scope)
    moments = _first_moments(main, scope)
    still = [n for n in before if not np.abs(after[n] - before[n]).max() > 0]
    dead = [n for n in still
            if not np.abs(moments.get(n, np.zeros(1))).max() > 0]
    if dead or len(still) > 0.25 * len(before):
        raise AssertionError(
            f"trainer: {len(still)}/{len(before)} parameters did not move, "
            f"{len(dead)} of them with a dead first moment: {dead[:5]}")
    say(f"trainer: {len(before) - len(still)}/{len(before)} parameters "
        f"moved; {len(still)} below f32 resolution with a live moment "
        f"{still[:3]}")

    state = [(n, scope.find_var(n)) for n in scope.var_names()]
    on_chip("trainer state", state)
    say(f"trainer: {len(state)} state arrays on {PLATFORM}; compile "
        f"{compile_seconds() - c0:.1f} s, step "
        f"{statistics.median(secs) * 1e3:.1f} ms (median of "
        f"{len(secs)} dispatches), kernels in the compiled step: "
        f"{kernels_in(exe) or 'none (XLA composites)'}")
    stats = jax.devices()[0].memory_stats() or {}
    say(f"trainer: memory_stats peak_bytes_in_use "
        f"{stats.get('peak_bytes_in_use')} of bytes_limit "
        f"{stats.get('bytes_limit')}")
    exe.close()


# --------------------------------------------------------------------------
# server
# --------------------------------------------------------------------------


def phase_server(depth: int = 50, image: int = 224, class_dim: int = 1000,
                 buckets=(1, 8), sizes=(1, 8, 3, 5)) -> None:
    import paddle_tpu as fluid
    from paddle_tpu import io, serving
    from paddle_tpu.models import resnet

    say(f"server: ResNet-{depth} bf16 is_test, {class_dim} classes, "
        f"{image}x{image}, buckets {tuple(buckets)}, requests of "
        f"{tuple(sizes)} rows")
    main, startup, _, fetches = resnet.build(
        depth=depth, class_dim=class_dim, image_shape=(3, image, image),
        with_optimizer=False, is_test=True, dtype="bfloat16")
    main.random_seed = startup.random_seed = SEED
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.TPUPlace(0))
    exe.run(startup, scope=scope)
    logits = fetches["logits"]
    rng = np.random.RandomState(SEED)
    imgs = rng.rand(max(sizes), 3, image, image).astype("float32")

    model_dir = tempfile.mkdtemp(prefix="chip_smoke_resnet_")
    try:
        io.save_inference_model(model_dir, ["img"], [logits], exe,
                                main_program=main, scope=scope)
        registry = serving.ModelRegistry(place=fluid.TPUPlace(0))
        c0 = compile_seconds()
        with serving.Server(registry, buckets=tuple(buckets)) as srv:
            version = srv.load_model("resnet", model_dir)  # warms each bucket
            on_chip("served weights", [
                (n, version.scope.find_var(n))
                for n in version.scope.var_names()])
            say(f"server: loaded and warmed {len(buckets)} buckets, "
                f"compile {compile_seconds() - c0:.1f} s")
            warm = recompiles()
            secs = {}
            outs = {}
            for n in sizes:
                t0 = time.perf_counter()
                (out,) = srv.infer("resnet", {"img": imgs[:n]})
                secs[n] = time.perf_counter() - t0
                outs[n] = np.asarray(out)
                if outs[n].shape != (n, class_dim):
                    raise AssertionError(
                        f"server: {n} rows gave shape {outs[n].shape}")
                if not np.isfinite(outs[n]).all():
                    raise AssertionError(f"server: {n} rows: non-finite")
            if recompiles() != warm:
                raise AssertionError(
                    f"server: {recompiles() - warm} compile(s) after warm")
            stats = srv.stats()
    finally:
        shutil.rmtree(model_dir, ignore_errors=True)

    # the reference: the saved program's source, run directly at the
    # largest request size (a compile the server never sees)
    (want,) = exe.run(main, feed={"img": imgs}, fetch_list=[logits],
                      scope=scope)
    scale = max(float(np.abs(want).max()), 1.0)
    errs = {n: float(np.abs(got - want[:n]).max()) / scale
            for n, got in outs.items()}
    if not max(errs.values()) <= BF16_TOL:
        raise AssertionError(
            f"server: rows differ from the direct run by {errs} of the "
            f"largest logit ({scale:.3e}), tolerance {BF16_TOL}")
    say(f"server: {len(sizes)} requests served, shapes right, values within "
        f"{max(errs.values()):.2e} of the largest logit of a direct "
        f"Executor run (tolerance {BF16_TOL}), no compile after warm; "
        f"request ms "
        f"{ {n: round(s * 1e3, 2) for n, s in secs.items()} }, "
        f"{stats['padded_rows']} padded rows in {stats['batches']} batches")
    exe.close()


# --------------------------------------------------------------------------
# host callback
# --------------------------------------------------------------------------


def phase_host_callback() -> None:
    import paddle_tpu as fluid

    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data("x", [4], dtype="float32")
        y = fluid.layers.scale(x, scale=2.0)            # device, upstream
        mid = main.current_block().create_var("cb_out", shape=(3, 1),
                                              dtype="float32")
        fluid.layers.py_func(
            lambda a: np.asarray(a).sum(axis=1, keepdims=True).astype("f4"),
            y, mid)
        out = fluid.layers.scale(mid, scale=0.5)        # device, downstream
    exe = fluid.Executor(fluid.TPUPlace(0))
    scope = fluid.Scope()
    exe.run(startup, scope=scope)
    xv = np.arange(12, dtype="f4").reshape(3, 4)
    (got,) = exe.run(main, feed={"x": xv}, fetch_list=[out], scope=scope,
                     return_numpy=False)
    on_chip("host callback fetch", [(out.name, got)])
    np.testing.assert_allclose(np.asarray(got), xv.sum(1, keepdims=True),
                               rtol=1e-6)
    say("host callback: py_func between two device ops ran through "
        "jax.pure_callback inside the compiled program, value correct")
    exe.close()


# --------------------------------------------------------------------------
# four chips: dp=2 x tp=2 against one chip
# --------------------------------------------------------------------------


def phase_mesh(bert: dict = BERT_BASE_2L, batch: int = 32, steps: int = 4,
               dp: int = 2, tp: int = 2) -> None:
    import jax
    from jax.sharding import PartitionSpec as P

    import paddle_tpu as fluid
    from paddle_tpu.models import transformer

    devices = jax.devices()[:dp * tp]
    say(f"mesh: BERT {bert} (depth CUT to {bert['n_layers']} layers: four "
        f"chips are charged four times and the sharding rules do not depend "
        f"on depth), bf16, dropout 0, batch {batch}, {steps} steps, "
        f"dp={dp} x tp={tp} over {[d.id for d in devices]}")
    main, loss, scope, exe = start_bert(bert, dropout=0.0)
    n_hints = fluid.parallel.shard_parameters(main, transformer.tp_rules())
    start = host_copy(scope)
    feed = bert_feed(bert, batch, 1)

    one, _ = run_steps(exe, main, feed, loss, scope, 1, steps)
    say(f"mesh: one chip   loss {np.round(one.reshape(-1), 4).tolist()}")

    restore(scope, start)
    mesh = fluid.parallel.make_mesh((dp, tp), ("dp", "tp"), devices)
    compiled = fluid.CompiledProgram(main).with_mesh(mesh)
    c0 = compile_seconds()
    many, secs = run_steps(exe, compiled, feed, loss, scope, 1, steps)
    say(f"mesh: {dp}x{tp} mesh  loss {np.round(many.reshape(-1), 4).tolist()}"
        f", compile {compile_seconds() - c0:.1f} s, step "
        f"{statistics.median(secs) * 1e3:.1f} ms")
    err = float(np.abs(many - one).max())
    if not (np.isfinite(many).all() and err <= BF16_TOL):
        raise AssertionError(
            f"mesh: losses differ from one chip by {err:.3e} > {BF16_TOL}")

    # the state really is spread
    w_name = "bert.l0.ffn1.w"
    w = scope.find_var(w_name)
    shard = w.addressable_shards[0].data
    if len(w.sharding.device_set) != dp * tp or shard.nbytes * tp != w.nbytes:
        raise AssertionError(
            f"mesh: {w_name} is on {len(w.sharding.device_set)} devices "
            f"with shards of {shard.nbytes} of {w.nbytes} bytes")
    step = next(s for s in exe._cache.values() if s.mesh is not None)
    ids_spec = step.feed_specs["ids"]
    ids_shard = ids_spec.shard_shape(feed["ids"].shape)
    if ids_spec.spec != P("dp") or ids_shard[0] * dp != batch:
        raise AssertionError(
            f"mesh: ids fed as {ids_spec.spec} in shards of {ids_shard}")
    in_use = [(d.memory_stats() or {}).get("bytes_in_use") for d in devices]
    if PLATFORM == "tpu" and not all(in_use[1:]):  # the CPU reports none
        raise AssertionError(f"mesh: bytes_in_use by device {in_use}")
    on_chip("mesh state", [(n, scope.find_var(n)) for n in scope.var_names()])
    say(f"mesh: losses agree to {err:.2e} (tolerance {BF16_TOL}); "
        f"{n_hints} parameters carry tp hints; {w_name} {tuple(w.shape)} is "
        f"on {len(w.sharding.device_set)} devices in shards of "
        f"{tuple(shard.shape)} ({shard.nbytes} of {w.nbytes} bytes); ids "
        f"{feed['ids'].shape} fed as {ids_spec.spec} in shards of "
        f"{ids_shard}; bytes_in_use by device {in_use}")
    exe.close()


# --------------------------------------------------------------------------


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: the dp=2 x tp=2 mesh phase and what it is "
                         "compared with, no other phase")
    args = ap.parse_args(argv)

    devs = require_chips(args.chips)

    from importlib.metadata import version

    import jax

    from paddle_tpu import monitor
    from paddle_tpu.flags import CHECKOUT_CACHE_DIR, apply_compile_cache

    cache = apply_compile_cache(CHECKOUT_CACHE_DIR)
    monitor.enable()  # the recompile counter and the compile spans
    say(f"jax {jax.__version__}, jaxlib {version('jaxlib')}, libtpu "
        f"{version('libtpu')}; {len(devs)} x {devs[0].device_kind}; "
        f"compile cache {cache}")

    t0 = time.perf_counter()
    phases = ([phase_mesh] if args.chips == 4 else
              [phase_trainer, phase_server, phase_host_callback])
    for phase in phases:
        t1 = time.perf_counter()
        phase()
        gc.collect()
        say(f"{phase.__name__} passed in {time.perf_counter() - t1:.1f} s")
    say(f"all phases passed in {time.perf_counter() - t0:.1f} s, "
        f"{compile_seconds():.1f} s of them compiling")
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}), flush=True)


if __name__ == "__main__":
    main()
