"""bench.py's dispatch builders (reference role:
benchmark/fluid/fluid_benchmark.py model setup helpers): the single copy of
"build model -> Executor -> device-resident feeds -> steps=K scan closure".

Import as `from tools.bench_kit import ...` from the repo root.
"""
from __future__ import annotations

import statistics
import time

import numpy as np


def timed_steps(dispatch, K=1, n_warm=2, iters=3, windows=1,
                spread_target=None, max_windows=12, clock=None):
    """Best-of-N timing windows, per-OPTIMIZER-step results.

    The r5 machine showed ~±20% run-to-run throughput variance, so the
    minimum window was taken as the compute time; all windows are returned
    so results report spread.  K = optimizer steps per dispatch (the scan
    length): returned dt and windows are divided by it exactly once.

    spread_target (percent): warmup-until-stable windowing — keep timing
    windows (up to `max_windows` total) until the LAST `windows` of them
    agree to within spread_target%, then report exactly those.  The fix for
    the r5 chip record's NMT entry, whose first window still carried compile/cache
    warm-in and swung the reported spread to 26% (30.3 -> 22.8 ms): the
    early windows are treated as extended warmup instead of evidence.  When
    the budget runs out before stabilizing, the trailing windows are
    returned as-is — callers see the honest spread and their own gate
    decides (`spread_pct(ws)`); `clock` injects a fake timer for tests.
    """
    clock = clock or time.perf_counter
    out = None
    for _ in range(n_warm):
        out = dispatch()
    np.asarray(out[0])
    ws = []

    def one_window():
        nonlocal out
        t0 = clock()
        for _ in range(iters):
            out = dispatch()
        np.asarray(out[0])
        ws.append((clock() - t0) / iters / K)

    for _ in range(windows):
        one_window()
    if spread_target is not None:
        while (spread_pct([w * 1e3 for w in ws[-windows:]]) > spread_target
               and len(ws) < max_windows):
            one_window()
        ws = ws[-windows:]
    return min(ws), out, [round(w * 1e3, 3) for w in ws]


def spread_pct(windows_ms):
    """(max-min)/median over windows, %; same stat as tools/opbench.py."""
    if len(windows_ms) < 2:
        return 0.0
    return round((max(windows_ms) - min(windows_ms))
                 / statistics.median(windows_ms) * 100, 1)




def attach_param_probe(dispatch, main, scope):
    """Attach `dispatch.probe_param()` returning {param: f8 snapshot} of
    EVERY trainable param — the bench-level liveness gate.  All params (not
    just the first) so a partial optimizer freeze — the r5 bf16+Adam bug
    froze every encoder param while the f32 embeddings kept moving — cannot
    pass by luck of program order."""
    def _probe_param():
        snap = {}
        for p in main.all_parameters():
            v = scope.find_var(p.name)
            if v is not None:
                snap[p.name] = np.asarray(v).astype("f8")
        if not snap:
            raise RuntimeError("no parameters in scope")
        return snap

    # First-order optimizer accumulators per param ({param}_moment1_0 /
    # _moment_0 / _velocity_0 ... — optimizer.py _add_accumulator naming).
    # The moment is the tie-breaker when a param snapshot doesn't move: a
    # LIVE moment means the optimizer ran and the update rounded away below
    # the param dtype's resolution (bf16 q/k early-training stalls), while
    # a dead moment alongside a dead param is a genuinely dropped update —
    # the class tools/donation_audit.py pins statically.
    # _mean_grad_0 LAST: rmsprop only updates it under centered=True (the
    # non-default), so probing it first would misreport every non-centered
    # RMSProp param as dropped-update; _momentum_0 is the live accumulator
    # there and must win the tie
    _MOMENT_SUFFIXES = ("_moment1_0", "_moment_0", "_velocity_0",
                        "_momentum_0", "_avg_squared_grad_0", "_squared_0",
                        "_mean_grad_0")

    def _probe_moments():
        snap = {}
        names = set(scope.var_names())
        for p in main.all_parameters():
            for suf in _MOMENT_SUFFIXES:
                n = p.name + suf
                if n in names:
                    snap[p.name] = np.asarray(scope.find_var(n)).astype("f8")
                    break
        return snap

    dispatch.probe_param = _probe_param
    dispatch.probe_moments = _probe_moments
    return dispatch

def make_resnet_dispatch(batch_size=256, K=4, stem="space_to_depth",
                         data_format="NCHW", dtype="bfloat16"):
    """ResNet-50 train-step closure: returns (dispatch, loss_name)."""
    import jax
    import jax.numpy as jnp

    import paddle_tpu as fluid
    from paddle_tpu.models import resnet

    main, startup, feeds, fetches = resnet.build(
        dtype=dtype, class_dim=1000, learning_rate=0.1, with_optimizer=True,
        stem=stem, data_format=data_format)
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.TPUPlace(0))
    exe.run(startup, scope=scope)
    rng = np.random.RandomState(0)
    dev = fluid.TPUPlace(0).jax_device()
    shape = ((K, batch_size, 3, 224, 224) if data_format == "NCHW"
             else (K, batch_size, 224, 224, 3))
    feed = {
        "img": jax.device_put(jnp.asarray(rng.rand(*shape), jnp.float32), dev),
        "label": jax.device_put(
            jnp.asarray(rng.randint(0, 1000, (K, batch_size, 1)), jnp.int32), dev),
    }
    loss_name = fetches["loss"].name

    def dispatch():
        return exe.run(main, feed=feed, fetch_list=[loss_name], scope=scope,
                       steps=K, return_numpy=False)

    # compile now (under whatever lowering flags the caller has set) and
    # fail fast on a broken model
    out = dispatch()
    assert np.isfinite(float(np.asarray(out[0]).reshape(-1)[-1]))
    attach_param_probe(dispatch, main, scope)
    _attach_plan_inputs(dispatch, main, feed, loss_name, K)
    return dispatch, loss_name


def _attach_plan_inputs(dispatch, main, feed, loss_name, K):
    """Expose the EXACT program + feed shapes this dispatch measures, so
    bench.py's static-roofline prediction (core/resource_plan.py) plans
    the same computation instead of rebuilding from a copied config."""
    dispatch.main_program = main
    dispatch.feed_shapes = {n: tuple(np.shape(v)) for n, v in feed.items()}
    dispatch.loss_name = loss_name
    dispatch.steps = K
    return dispatch


def make_bert_dispatch(batch_size=256, seq_len=128, K=2, dtype="bfloat16",
                       use_fused_attention=True):
    """BERT-base train-step closure: returns (dispatch, loss_name).

    Default fused attention: one op for scale/bias/softmax/context (mixed-
    precision XLA formulation; attention-prob dropout becomes output
    dropout — the substitution documented in models/transformer.py).
    r5 A/B: 255.1 vs 273.8 ms/step vs the unfused op stack."""
    import jax
    import jax.numpy as jnp

    import paddle_tpu as fluid
    from paddle_tpu.models import transformer

    main, startup, feeds, fetches = transformer.build_bert(
        vocab_size=30522, seq_len=seq_len, d_model=768, n_layers=12,
        n_heads=12, d_ff=3072, dropout_prob=0.1, with_optimizer=True,
        dtype=dtype, use_fused_attention=use_fused_attention)
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.TPUPlace(0))
    exe.run(startup, scope=scope)
    batches = [transformer.make_fake_batch(batch_size, seq_len, 30522,
                                           rng=np.random.RandomState(k))
               for k in range(K)]
    dev = fluid.TPUPlace(0).jax_device()
    feed = {k: jax.device_put(jnp.asarray(np.stack([b[k] for b in batches])), dev)
            for k in batches[0]}
    loss_name = fetches["loss"].name

    def dispatch():
        return exe.run(main, feed=feed, fetch_list=[loss_name], scope=scope,
                       steps=K, return_numpy=False)

    out = dispatch()
    assert np.isfinite(float(np.asarray(out[0]).reshape(-1)[-1]))
    attach_param_probe(dispatch, main, scope)
    _attach_plan_inputs(dispatch, main, feed, loss_name, K)
    return dispatch, loss_name


def make_nmt_dispatch(K=8, b=32, T=64, dtype="float32"):
    """Transformer-NMT ragged train-step closure: returns (dispatch, loss_name).

    Pre-padded [K,b,T,1] id feeds + `@LOD` lengths companions — the executed
    program is the same ragged program the LoDTensor path runs; only the
    harness avoids per-step host dispatch."""
    import jax
    import jax.numpy as jnp

    import paddle_tpu as fluid
    from paddle_tpu.lod import lod_var_name
    from paddle_tpu.models import nmt

    main, startup, feeds, fetches = nmt.build_transformer_nmt(
        src_vocab=8000, tgt_vocab=8000, d_model=512, n_layers=6, n_heads=8,
        d_ff=2048, dropout=0.1, learning_rate=2.0, dtype=dtype)
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.TPUPlace(0))
    exe.run(startup, scope=scope)
    rng = np.random.RandomState(0)
    dev = fluid.TPUPlace(0).jax_device()
    feed = {}
    lens = {}
    for name in ("src_word", "trg_word", "lbl_word"):
        side = "src" if name == "src_word" else "tgt"
        if side not in lens:
            lens[side] = rng.randint(20, T, size=(K, b)).astype("int32")
        ids = rng.randint(1, 8000, size=(K, b, T, 1)).astype("int32")
        # zero the padding region so the padded carrier matches what the
        # LoDTensor expansion would produce
        mask = np.arange(T)[None, None, :] < lens[side][..., None]
        ids = ids * mask[..., None]
        feed[name] = jax.device_put(jnp.asarray(ids), dev)
        feed[lod_var_name(name)] = jax.device_put(jnp.asarray(lens[side]), dev)
    loss_name = fetches["loss"].name

    def dispatch():
        return exe.run(main, feed=feed, fetch_list=[loss_name], scope=scope,
                       steps=K, return_numpy=False)

    out = dispatch()
    assert np.isfinite(float(np.asarray(out[0]).reshape(-1)[-1]))
    attach_param_probe(dispatch, main, scope)
    mean_tokens = float(lens["src"].mean() + lens["tgt"].mean())
    return dispatch, loss_name, mean_tokens
