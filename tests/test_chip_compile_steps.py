"""The cells' whole train steps compiled for the described v5e, one chip or the
2x2 host (`tests/test_chip_compile.py` has the chip and the kernels alone): the
step `benchmark/models/<cell>.py` builds at its configuration's and traffic's
own sizes, through `_CompiledStep` with what `plan_kept` chooses at the chip's
memory limit, so that a plan is read before a chip call is spent on it.  One
such compile is tens of seconds on every core; the ones of a minute and more
stand behind `-m slow` with their seconds beside them and are run by name.
"""
import re

from test_chip_compile import I32, _no_persistent_cache, chip, host  # noqa: F401  (the fixtures by name)

import jax
import pytest
from jax.sharding import SingleDeviceSharding


@pytest.mark.slow   # one compile of ~45 s on eight free cores, 112 s beside five other workers: run by name (`-m slow`); docs/tier1_durations.md (PR 66)
def test_lfm2s_step_compiles_for_the_chip_and_its_planned_peak_leaves_room(chip):
    """The cell's whole train step (benchmark/models/lfm2.py: build, at the
    configuration's and the traffic's own sizes: two sequences) compiles for
    the described v5e, and XLA plans it under the 15.5 GB the cell allows
    itself and over the 12 GB it promises to fill (PERF.md, PR 34, has the
    three planned peaks: a third sequence plans 15.60).  What `cost_analysis()`
    counts for the step stays under what the HBM moves in 280 ms, the step's
    time on the chip: the whole-step roofline share the cell reports reads
    under 100% (it read 121.6% while the held experts' never-run branch was
    a bound's rows a pass)."""
    import paddle_tpu as fluid
    from benchmark import manifest as mf
    from benchmark.models import lfm2
    from paddle_tpu.core import executor as ex

    cfg = mf.read_json("benchmark/configs/lfm2-8b-a1b.json")
    job = mf.read_json("benchmark/traffic/train-s8192.json")
    with fluid.unique_name.guard():
        main, startup, _, loss, _ = lfm2.build(cfg, job)
    main.random_seed = startup.random_seed = 3
    scope = fluid.Scope()
    for v in startup.global_block().vars.values():
        if v.persistable:
            scope.set_var(v.name, jax.ShapeDtypeStruct(tuple(v.shape), v.dtype))
    feeds = {n: jax.ShapeDtypeStruct((job["batch_per_chip"], job["seq_len"]), I32) for n in lfm2.FEEDS}
    step = ex._CompiledStep(main, list(feeds), [loss.name], scope, platform="tpu",
                            feed_shapes={n: s.shape for n, s in feeds.items()})

    def on_chip(v):
        return jax.ShapeDtypeStruct(v.shape, v.dtype, sharding=chip)

    compiled = step.jfn.lower({n: on_chip(scope.find_var(n)) for n in step.rw_names},
                              {n: on_chip(scope.find_var(n)) for n in step.ro_names},
                              {n: on_chip(s) for n, s in feeds.items()},
                              on_chip(jax.random.PRNGKey(0))).compile()
    m = compiled.memory_analysis()
    peak = m.argument_size_in_bytes + m.output_size_in_bytes - m.alias_size_in_bytes + m.temp_size_in_bytes
    assert 12e9 <= peak <= 15.5e9, peak
    cost = compiled.cost_analysis()
    cost = cost[0] if isinstance(cost, (list, tuple)) else cost
    assert max(cost["bytes accessed"] / 819e9, cost["flops"] / 197e12) < 0.280
    text = compiled.as_text()
    # the one attention layer took the splash kernels under the causal rule (PR 37; the flash kernel until then)
    assert "splash_mha_fwd" in text and "attention_dq_dk_dv" in text and "splash_mha_d" not in text and "flash_mha" not in text
    assert text.count("/gated_short_conv/") > 0 and text.count("/expert_gemm/") > 0


def test_ouros_step_compiles_for_the_chip_as_one_loop_and_its_planned_peak_leaves_room(chip):
    """The looped cell's whole train step (benchmark/models/ouro.py: build, at
    the configuration's and the traffic's own sizes: one sequence of 4096
    through four passes of eight layers) compiles for the described v5e as a
    forward and a backward `while` (the `repeat` op's scan and its transpose),
    the pass's forward computed again inside the backward one, the splash
    kernels inside both, and XLA plans it under the 15.5 GB the cell allows
    itself and over the 25% of the chip a cell has to fill (PERF.md, PR 38:
    12.7 GB).  `cost_analysis()` counts a loop's body once, so what it counts
    stays far under what the chip does in the step's ~0.6 s: the whole-step
    roofline share the cell reports reads LOW, never over 100%."""
    import paddle_tpu as fluid
    from benchmark import manifest as mf
    from benchmark.models import ouro
    from paddle_tpu.core import executor as ex

    cfg = mf.read_json("benchmark/configs/ouro-2.6b.json")
    job = mf.read_json("benchmark/traffic/train-ut4-s4096.json")
    with fluid.unique_name.guard():
        main, startup, _, loss, _ = ouro.build(cfg, job)
    main.random_seed = startup.random_seed = 3
    scope = fluid.Scope()
    for v in startup.global_block().vars.values():
        if v.persistable:
            scope.set_var(v.name, jax.ShapeDtypeStruct(tuple(v.shape), v.dtype))
    feeds = {n: jax.ShapeDtypeStruct((job["batch_per_chip"], job["seq_len"]), I32) for n in ouro.FEEDS}
    step = ex._CompiledStep(main, list(feeds), [loss.name], scope, platform="tpu",
                            feed_shapes={n: s.shape for n, s in feeds.items()})

    def on_chip(v):
        return jax.ShapeDtypeStruct(v.shape, v.dtype, sharding=chip)

    compiled = step.jfn.lower({n: on_chip(scope.find_var(n)) for n in step.rw_names},
                              {n: on_chip(scope.find_var(n)) for n in step.ro_names},
                              {n: on_chip(s) for n, s in feeds.items()},
                              on_chip(jax.random.PRNGKey(0))).compile()
    m = compiled.memory_analysis()
    peak = m.argument_size_in_bytes + m.output_size_in_bytes - m.alias_size_in_bytes + m.temp_size_in_bytes
    assert 0.25 * 16.9e9 <= peak <= 15.5e9, peak
    assert m.argument_size_in_bytes == pytest.approx(3 * 4 * 461.4e6, rel=1e-3)      # masters and Adam's two moments
    cost = compiled.cost_analysis()
    cost = cost[0] if isinstance(cost, (list, tuple)) else cost
    assert max(cost["bytes accessed"] / 819e9, cost["flops"] / 197e12) < 0.5
    text = compiled.as_text()
    assert len(re.findall(r" while\(", text)) == 2
    assert "splash_mha_fwd" in text and "attention_dq_dk_dv" in text and "splash_mha_d" not in text and "flash_mha" not in text
    # the scopes the cell's readers find: the recomputed forward, the exits, the body's ops under the construct's
    assert text.count("/rematted_computation/") > 0
    # numbered where this process built a looped model before: sibling `name_scope`s of one name are
    assert re.search(r"/exit_head(_\d+)?/", text) and re.search(r"/exit_loss(_\d+)?/", text)
    assert re.search(r':repeat/[^"]*loop_pass/op\d+:fused_attention', text) and not re.search(r'loop_pass/[^"]*exit_head', text)
    assert re.search(r'transpose\([^"]*:repeat/[^"]*rematted_computation/[^"]*op\d+:mul', text)


@pytest.mark.slow   # 3 to 4.5 minutes of one compile on every core: run by name (`-m slow`), PERF.md PR 42 has its readings
def test_kimi_linears_step_compiles_for_the_chip_and_its_planned_peak_leaves_room(chip):
    """Kimi Linear's cell's whole train step (benchmark/models/kimi_linear.py:
    build, at the configuration's and the traffic's own sizes: one sequence of
    4096 through four KDA layers and a latent attention) compiles for the
    described v5e, and XLA plans it under the 15.5 GB the cell allows itself
    and over the 25% of the chip a cell has to fill (PERF.md, PR 42, has the
    planned peaks that chose the batch).  The latent attention took the splash
    kernels with its two widths as they are; the scans are the kernels of
    `ops/kda_kernels.py` since PR 44, two calls a layer since PR 45 (forward,
    which in the step writes the chunks' start states and T beside o, 0.17 GB
    a layer kept until backward, and the transpose, which reads them: no call
    makes the states again; four heads a grid step: they fit their VMEM inside the
    step, not only alone) under the scope their roofline share reads, forward
    and backward; the state and Adam's moments are 12 bytes of the 16 a
    parameter.  PERF.md, PR 45, has the planned peak."""
    import paddle_tpu as fluid
    from benchmark import manifest as mf
    from benchmark.models import kimi_linear
    from paddle_tpu.core import executor as ex

    cfg = mf.read_json("benchmark/configs/kimi-linear-48b-a3b.json")
    job = mf.read_json("benchmark/traffic/train-kda-s4096.json")
    with fluid.unique_name.guard():
        main, startup, _, loss, _ = kimi_linear.build(cfg, job)
    main.random_seed = startup.random_seed = 3
    scope = fluid.Scope()
    for v in startup.global_block().vars.values():
        if v.persistable:
            scope.set_var(v.name, jax.ShapeDtypeStruct(tuple(v.shape), v.dtype))
    feeds = {n: jax.ShapeDtypeStruct((job["batch_per_chip"], job["seq_len"]), I32) for n in kimi_linear.FEEDS}
    step = ex._CompiledStep(main, list(feeds), [loss.name], scope, platform="tpu",
                            feed_shapes={n: s.shape for n, s in feeds.items()})

    def on_chip(v):
        return jax.ShapeDtypeStruct(v.shape, v.dtype, sharding=chip)

    compiled = step.jfn.lower({n: on_chip(scope.find_var(n)) for n in step.rw_names},
                              {n: on_chip(scope.find_var(n)) for n in step.ro_names},
                              {n: on_chip(s) for n, s in feeds.items()},
                              on_chip(jax.random.PRNGKey(0))).compile()
    m = compiled.memory_analysis()
    peak = m.argument_size_in_bytes + m.output_size_in_bytes - m.alias_size_in_bytes + m.temp_size_in_bytes
    assert 0.25 * 16.9e9 <= peak <= 15.5e9, peak
    assert m.argument_size_in_bytes == pytest.approx(3 * 4 * cfg["parameters"], rel=1e-3)    # masters and Adam's two moments
    text = compiled.as_text()
    assert "splash_mha_fwd" in text and "attention_dq_dk_dv" in text and "splash_mha_d" not in text and "flash_mha" not in text
    scans = re.findall(r'op_name="([^"]*/kda_chunk_scan/[^"]*)"', text)
    assert any("transpose(" in name for name in scans) and any("transpose(" not in name for name in scans)
    assert all(any(name.endswith(f"/{kernel}/pallas_call") for name in scans) for kernel in ("kda_scan", "kda_scan_transposed"))
    assert "kda_scan_starts" not in text
    print(f"planned peak {peak / 1e9:.3f} GB, temporaries {m.temp_size_in_bytes / 1e9:.3f} GB")     # shown by `-s`
    assert not re.search(r"kda_chunk_scan/[^\"]*while", text)          # no `lax.scan` is left in the op
    assert re.search(r"/kda(_\d+)?/op\d+:kda/kda_chunk_scan/", text) and re.search(r"/latent_attention(_\d+)?/op\d+:fused_attention", text)
    assert re.search(r"/shared_expert(_\d+)?/op\d+:mul", text) and text.count("/plain_short_conv/") > 0


#: What a v5e reports as `memory_stats()["bytes_limit"]` (my chip run, PR 51, call 1): the limit `plan_kept` reads on
#: the chip, given to it here, where the CPU reports none, so that the step compiled here is the step the chip compiles.
V5E_BYTES_LIMIT = 16_909_336_064


@pytest.mark.parametrize("limit,made_again", [(0, 3), (V5E_BYTES_LIMIT, 0)], ids=["a-full-chip", "a-v5es-room"])
def test_the_benchmarks_readers_find_the_selective_scans_kernels_forward_recomputed_and_backward(chip, monkeypatch, limit, made_again):
    """A small Jamba (the configuration's period cut to four layers, 512 wide:
    1024 channels a mixer and a state of 16, which `_scan_path` sends to the
    kernels on the TPU; 64 tokens) trained one step, compiled for the described
    v5e: every call of the two kernels of `ops/ssm_kernels.py`, forward, made
    again under the layer's `recompute_scope` and transposed (the one in the
    `custom_vjp`'s backward), carries an `op_name` that the benchmark's readers
    `ssm_scan_roofline_share` and `ssm_ms_per_step` match (their own `SCOPE`s,
    imported), the recomputed ones `recompute_ms_per_step`'s too; no `while` is
    left under the op's scope.  The forward kernel is made again where the chip
    has no room for what `plan_kept` would keep (the cell's own thirteen at its
    size), and not at all where it has (this small model on a v5e: PR 51)."""
    import paddle_tpu as fluid
    from benchmark import manifest as mf
    from benchmark.metrics import recompute_ms_per_step, ssm_ms_per_step, ssm_scan_roofline_share
    from benchmark.models import jamba
    from paddle_tpu.core import executor as ex
    from paddle_tpu.monitor import memstats

    monkeypatch.setattr(memstats, "device_bytes_limit", lambda *a: limit)
    cfg = dict(mf.read_json("benchmark/configs/ai21-jamba2-3b.json"), hidden_size=512, intermediate_size=96, mamba_dt_rank=4,
               num_attention_heads=4, num_key_value_heads=1, vocab_size=96, num_hidden_layers=4, attn_layer_period=4,
               attn_layer_offset=2)
    cfg["layer_types"] = jamba.layer_types(cfg)
    job = dict(mf.read_json("benchmark/traffic/train-ssm-fsdp4.json"), seq_len=64, batch_per_chip=1)
    del job["mesh_shape"], job["mesh_axes"]
    with fluid.unique_name.guard():
        main, startup, _, loss, _ = jamba.build(cfg, job)
    scope = fluid.Scope()
    for v in startup.global_block().vars.values():
        if v.persistable:
            scope.set_var(v.name, jax.ShapeDtypeStruct(tuple(v.shape), v.dtype))
    feeds = {n: jax.ShapeDtypeStruct((1, 64), I32) for n in jamba.FEEDS}
    step = ex._CompiledStep(main, list(feeds), [loss.name], scope, platform="tpu", feed_shapes={n: s.shape for n, s in feeds.items()})

    def on_chip(v):
        return jax.ShapeDtypeStruct(v.shape, v.dtype, sharding=chip)

    text = step.jfn.lower({n: on_chip(scope.find_var(n)) for n in step.rw_names}, {n: on_chip(scope.find_var(n)) for n in step.ro_names},
                          {n: on_chip(s) for n, s in feeds.items()}, on_chip(jax.random.PRNGKey(0))).compile().as_text()
    kernels = sorted({name for name in recompute_ms_per_step.op_names(text).values()      # a call's operands' copies carry its name too
                      if name.endswith(("/selective_scan/pallas_call", "/selective_scan_transposed/pallas_call"))})
    assert all(ssm_scan_roofline_share.SCOPE.search(name) and ssm_ms_per_step.SCOPE.search(name) for name in kernels), kernels
    transposed = [name for name in kernels if name.endswith("/selective_scan_transposed/pallas_call")]
    again = [name for name in kernels if recompute_ms_per_step.SCOPE in name]
    forward = [name for name in kernels if name not in transposed and name not in again]
    assert len(forward) == len(transposed) == 3 and len(again) == made_again, kernels   # the three Mamba layers, each way
    assert all("transpose(" in name for name in transposed) and not any("transpose(" in name for name in forward)
    assert all(name.endswith("/selective_scan/pallas_call") for name in again)
    assert not re.search(r'op_name="[^"]*op\d+:selective_scan/[^"]*while', text)


def _kept_step(module, config, traffic, devices, monkeypatch, check_rows=None):
    """(the compiled train step of a cell at its configuration's and traffic's
    own sizes, for the described chip or mesh, with what `plan_kept` chose at
    the chip's own memory limit; the `lowering.recomputed_*` counters of its
    trace).  `check_rows`: the cell's `for_test` clone on that many rows with
    the variables its reference check fetches, instead of the step."""
    import importlib

    import paddle_tpu as fluid
    from benchmark import manifest as mf
    from paddle_tpu import monitor
    from paddle_tpu.core import executor as ex
    from paddle_tpu.monitor import memstats

    model = importlib.import_module(f"benchmark.models.{module}")
    cfg, job = mf.read_json(f"benchmark/configs/{config}.json"), mf.read_json(f"benchmark/traffic/{traffic}.json")
    monkeypatch.setattr(memstats, "device_bytes_limit", lambda *a: V5E_BYTES_LIMIT)
    mesh, make_mesh = None, fluid.parallel.make_mesh
    if "mesh_shape" in job:    # the builder's mesh over the described devices, not the CPU's
        monkeypatch.setattr(fluid.parallel, "make_mesh", lambda sizes, names, _=None: make_mesh(sizes, names, list(devices)))
        mesh = fluid.parallel.make_mesh(tuple(job["mesh_shape"]), tuple(job["mesh_axes"]))
    with fluid.unique_name.guard():
        main, startup, _, loss, compared = model.build(cfg, job)
    main.random_seed = startup.random_seed = 3
    program, fetched = (main, [loss.name]) if check_rows is None else (main.clone(for_test=True), list(compared))
    scope = fluid.Scope()
    for v in startup.global_block().vars.values():
        if v.persistable:
            scope.set_var(v.name, jax.ShapeDtypeStruct(tuple(v.shape), v.dtype))
    rows = check_rows or job["batch_per_chip"] * (mesh.size if mesh is not None else 1)
    feeds = {n: jax.ShapeDtypeStruct((rows, job["seq_len"]), I32) for n in model.FEEDS}
    step = ex._CompiledStep(program, list(feeds), fetched, scope, mesh=mesh, batch_axis=job.get("mesh_axes", ["dp"])[0],
                            platform="tpu", feed_shapes={n: s.shape for n, s in feeds.items()})
    one = SingleDeviceSharding(devices[0])

    def placed(v, sharding):
        return jax.ShapeDtypeStruct(v.shape, v.dtype, sharding=one if mesh is None else sharding)

    monitor.reset()
    monitor.enable()
    try:
        lowered = step.jfn.lower(
            {n: placed(scope.find_var(n), mesh and step.state_specs[n]) for n in step.rw_names},
            {n: placed(scope.find_var(n), mesh and step.state_specs[n]) for n in step.ro_names},
            {n: placed(s, mesh and step.feed_specs[n]) for n, s in feeds.items()},
            placed(jax.random.PRNGKey(0), mesh and step.key_spec))
        # (the counters that moved: `monitor.reset()` keeps the names an earlier test of this process counted under)
        counted = {k[len("lowering.recomputed_"):]: v for k, v in monitor.MONITOR.counter_values().items()
                   if k.startswith("lowering.recomputed_") and v}
    finally:
        monitor.disable()
        monitor.reset()
    return lowered.compile(), counted


def _planned_peak(compiled):
    m = compiled.memory_analysis()
    return m.argument_size_in_bytes + m.output_size_in_bytes - m.alias_size_in_bytes + m.temp_size_in_bytes


def _made_again(text):
    """The names of the instructions of the compiled step that stand in a
    rematerialised computation, as `recompute_ms_per_step` finds them."""
    from benchmark.metrics import recompute_ms_per_step

    return [name for name in recompute_ms_per_step.op_names(text).values() if recompute_ms_per_step.SCOPE in name]


def test_phi4_mini_flashs_step_keeps_every_product_and_kernel_residual_and_its_planned_peak_leaves_room(host, monkeypatch):
    """`phi-4-mini-flash-reasoning.train-sambay-s8192`'s whole step at the
    published widths, compiled for the described v5e with what `plan_kept`
    chooses at the chip's memory limit: all 37 candidates of the six segments
    (3.45 GB of the 4.27 the state leaves the kept values), planned under the
    14.5 GB the issue allows and over the parent's 10.5; in the rematerialised
    computations no product and no kernel call is left (ISSUE 51)."""
    compiled, counted = _kept_step("phi4flash", "phi-4-mini-flash-reasoning", "train-sambay-s8192", host.devices, monkeypatch)
    assert counted == {"segments": 6, "kept_values": 37, "kept_bytes": 3449552896, "candidates_bytes": 3449552896}
    peak = _planned_peak(compiled)
    print(f"planned peak {peak / 1e9:.3f} GB")     # shown by `-s`
    assert 12.5e9 <= peak <= 14.5e9, peak
    again = _made_again(compiled.as_text())
    assert again and not [name for name in again if name.endswith(("/dot_general", "/pallas_call"))]


@pytest.mark.slow   # one compile of ~57 s on eight free cores, 79 s beside five other workers: run by name (`-m slow`); docs/tier1_durations.md (PR 66)
def test_smallthinkers_step_compiles_for_the_chip_with_its_routers_ahead_and_a_window_of_4096(host, monkeypatch):
    """`smallthinker-21b-a3b.train-nope-swa-s16384`'s whole step at the published
    widths and 16384 tokens, compiled for the described v5e with what
    `plan_kept` chooses at the chip's memory limit: all 28 candidates of the
    four sparse segments (every product's output, the kernels' residuals, the
    expert products' outputs and the routers' logits: 1.74 GB), planned over the
    25% of the chip a cell has to fill and under 9 GB; the full layer took the
    causal splash kernels and the three window layers the window rule's, in
    blocks of 1024 at (28 on 4, 128) inside the scoped VMEM; every router's
    scope stands AHEAD of its layer's attention; and in the rematerialised
    computations no product and no attention kernel is left (ISSUE 63)."""
    compiled, counted = _kept_step("smallthinker", "smallthinker-21b-a3b", "train-nope-swa-s16384", host.devices, monkeypatch)
    assert counted == {"segments": 4, "sparse_segments": 4, "kept_values": 28, "kept_bytes": 1735393280,
                       "candidates_bytes": 1735393280}
    peak = _planned_peak(compiled)
    print(f"planned peak {peak / 1e9:.3f} GB")     # shown by `-s`
    assert 0.25 * 16.9e9 <= peak <= 9.0e9, peak
    text = compiled.as_text()
    assert "splash_mha_fwd" in text and "splash_mha_d" not in text and "flash_mha" not in text
    # ONE backward kernel an attention layer (ISSUE 64), and no partial dq a block of keys
    assert len(re.findall(r'custom_call_target="tpu_custom_call"[^\n]*/attention_dq_dk_dv["/]', text)) == 4
    assert not re.findall(r"\[16,28,16384,128\]", text)
    names = re.findall(r'op_name="([^"]*)"', text)
    window = {re.search(r"/(sliding_attention(?:_\d+)?)/", n).group(1) for n in names if "/window_attention/" in n}
    assert len(window) == 3                        # the three rotary layers, each under its own numbered scope
    assert any(re.search(r"/op\d+:fused_attention/block_sparse_attention/", n) for n in names)      # the full layer: no window scope
    routers = sorted({int(i) for n in names for i in re.findall(r"/op(\d+):moe_router", n)})
    attentions = sorted({int(i) for n in names for i in re.findall(r"/op(\d+):fused_attention", n)})
    assert len(routers) == len(attentions) == 4 and all(r < a for r, a in zip(routers, attentions))
    assert all(a < r for a, r in zip(attentions, routers[1:]))          # router, attention, router, attention, ...
    again = [name for name in _made_again(text) if "/cond/branch_" not in name]
    assert again and not [name for name in again if name.endswith("/dot_general") or "splash_mha" in name or "attention_dq_dk_dv" in name or "/expert_gemm/" in name]


@pytest.mark.slow   # one compile of ~63 s on eight free cores, 122 s beside five other workers: run by name (`-m slow`); docs/tier1_durations.md (PR 66)
def test_lagunas_step_compiles_for_the_chip_with_its_gates_its_two_head_counts_and_a_window_of_512(host, monkeypatch):
    """`laguna-xs.2.train-gated-swa-s16384`'s whole step at the published widths
    and 16384 tokens, compiled for the described v5e with what `plan_kept`
    chooses at the chip's memory limit: all 48 candidates of the five segments
    (4.0 GB), planned over the 25% of the chip a cell has to fill and under
    12.5 GB (11.77 with 16 experts held; 32 held planned 14.76 and its 8-row
    clone did not fit beside the moments: the configuration's `deployment`); the
    two full layers took the causal splash kernels at 48 heads on 8 and the
    three window layers the window rule's at 64 on 8, blocks of 512, ONE
    backward kernel a layer; five `attention_gate` scopes, each under its
    layer's; and in the rematerialised computations no product and no attention
    kernel is left (ISSUE 65)."""
    compiled, counted = _kept_step("laguna", "laguna-xs.2", "train-gated-swa-s16384", host.devices, monkeypatch)
    assert counted == {"segments": 5, "sparse_segments": 4, "kept_values": 48, "kept_bytes": 3997171712,
                       "candidates_bytes": 3997171712}
    peak = _planned_peak(compiled)
    print(f"planned peak {peak / 1e9:.3f} GB")     # shown by `-s`
    assert 0.25 * 16.9e9 <= peak <= 12.5e9, peak
    text = compiled.as_text()
    assert "splash_mha_fwd" in text and "splash_mha_d" not in text and "flash_mha" not in text
    assert len(re.findall(r'custom_call_target="tpu_custom_call"[^\n]*/attention_dq_dk_dv["/]', text)) == 5
    assert re.findall(r"bf16\[1,48,16384,128\]", text) and re.findall(r"bf16\[1,64,16384,128\]", text)
    names = re.findall(r'op_name="([^"]*)"', text)
    window = {re.search(r"/(sliding_attention(?:_\d+)?)/", n).group(1) for n in names if "/window_attention/" in n}
    assert len(window) == 3                        # the three window layers, each under its own numbered scope
    assert any(re.search(r"/op\d+:fused_attention/block_sparse_attention/", n) for n in names)      # the full layers: no window scope
    gates = {m.group(1) for n in names for m in [re.search(r"/((?:sliding_attention(?:_\d+)?/)?attention_gate(?:_\d+)?)/", n)] if m}
    assert len(gates) == 5 and sum(g.startswith("sliding_attention") for g in gates) == 3, gates
    again = [name for name in _made_again(text) if "/cond/branch_" not in name]
    assert again and not [name for name in again if name.endswith("/dot_general") or "splash_mha" in name or "attention_dq_dk_dv" in name or "/expert_gemm/" in name]


def test_one_latent_attention_layer_writes_each_kernel_operand_once(host):
    """ONE latent attention layer at Kanana-2's widths (H 32, 192 / 128) over
    2048 positions, forward and backward through `_CompiledStep`, compiled for
    the described v5e (ISSUE 55): the chain of ops between the projections and
    the attention went into the unit's four kernels (`ops/latent_kernels.py`),
    which write the arrays the attention's kernels and the projections' backward
    read and nothing else: q, k, v forward and again, dq and d_up backward.
    Beside them no instruction under the layer's scope that is no product, no
    kernel call and not the partials' sum writes 30 MB x (2048 / 16384) or more
    but the kept output's copy in its two layouts and the output's way back to
    (B, L, H, 128), forward and again; no `dot_general` stands under `/rotary/`
    (the rotation is a rotation of lanes inside a pass, not a product with a
    0/+-1 matrix of its own)."""
    from tools import chip_latent_edges as edge

    positions = 2048
    compiled, counted = edge.one_layer_step(host.devices, positions)
    assert counted["lowering.latent_operands_assembled"] == 1 and not counted.get("lowering.latent_operands_fallback")
    assert counted["lowering.attention_block_causal"] == counted["lowering.attention_backward_onchip_dq"] == 1 and counted["lowering.latent_rotary_ops"] == 2
    text = compiled.as_text()
    found = edge.edges(text, floor=edge.FLOOR * positions / 16384)
    mb = 2 * positions * 32 / 1e6       # of a (B, L, H, 1) slab in bf16
    kernels = sorted((way, kind, round(size / mb)) for way, kind, size, _, _ in found if kind.startswith("kernel:"))
    assert kernels == sorted([("forward", "kernel:latent_queries", 192), ("forward", "kernel:latent_keys_values", 192 + 128),
                              ("again", "kernel:latent_queries", 192), ("again", "kernel:latent_keys_values", 192 + 128),
                              ("backward", "kernel:latent_queries_back", 192), ("backward", "kernel:latent_up_back", 256 + 4)]), kernels   # + the float32 sum over the heads
    # (a projection's own cast of its weights, 16.8 MB whatever the positions, is no array of the edge's)
    rest = sorted((way, kind, round(size / mb)) for way, kind, size, _, name in found
                  if not kind.startswith("kernel:") and ":mul/" not in name)
    assert rest == [("again", "transpose", 128), ("forward", "reduce_precision", 256), ("forward", "transpose", 128)], rest
    names = re.findall(r'op_name="([^"]*)"', text)
    assert any("/rotary/" in name for name in names)
    assert not [name for name in names if "/rotary/" in name and name.endswith("/dot_general")]
    assert "splash_mha_fwd" in text and "attention_dq_dk_dv" in text and "splash_mha_d" not in text


@pytest.mark.slow   # one compile of ~70 s on every core: run by name (`-m slow`); PERF.md, PR 54, has its readings
def test_kanana2s_step_keeps_every_candidate_of_its_sparse_segments_and_its_planned_peak_leaves_room(host, monkeypatch):
    """`kanana-2-30b-a3b.train-mla-s16384`'s whole step at the published widths
    and 16384 tokens, compiled for the described v5e with what `plan_kept`
    chooses at the chip's memory limit: every candidate of the five segments,
    four of them sparse (the expert products' outputs and the routers' logits
    among them), planned under the 15.5 GB a cell allows itself and over 25% of
    the chip; the latent attention took the splash kernels at (192, 128) over
    16384 keys, the ten rotations stand under `latent_attention/rotary`, and in
    the rematerialised computations no product, no attention kernel and no
    grouped product of the held path's COMMON pass is left (ISSUE 54)."""
    compiled, counted = _kept_step("kanana", "kanana-2-30b-a3b", "train-mla-s16384", host.devices, monkeypatch)
    assert counted["segments"] == 5 and counted["sparse_segments"] == 4
    assert counted["kept_bytes"] == counted["candidates_bytes"] > 4e9
    peak = _planned_peak(compiled)
    print(f"planned peak {peak / 1e9:.3f} GB")     # shown by `-s`
    assert 0.25 * 16.9e9 <= peak <= 14.9e9, peak
    text = compiled.as_text()
    assert "splash_mha_fwd" in text and "attention_dq_dk_dv" in text and "splash_mha_d" not in text and "flash_mha" not in text
    assert len(set(re.findall(r"/(latent_attention(?:_\d+)?)/rotary/op\d+:rotary_embedding", text))) == 5
    # the edge of a sparse layer's latent attention, the unit's own kernels with it: 2.6 GB written or less, from 4.1
    # before the chain was lowered as one unit (ISSUE 55; `tools/chip_latent_edges.py` prints the table)
    from tools import chip_latent_edges as edge

    written = sum(size for _, _, size, _, _ in edge.edges(text, edge.LAYER))
    print(f"a sparse layer's edge writes {written / 1e3:.3f} GB")
    assert 1.5e3 <= written <= 2.6e3, written
    # (the rare path makes its own again, and a rotation's pair swap is a product with a constant, no kept matrix's)
    again = [name for name in _made_again(text) if "/cond/branch_" not in name and ":rotary_embedding/" not in name]
    assert again and not [name for name in again if name.endswith("/dot_general") or "splash_mha" in name or "attention_dq_dk_dv" in name or "/expert_gemm/" in name]


@pytest.mark.slow   # two compiles, ~115 and ~80 s on every core: run by name (`-m slow`); PERF.md, PR 56, has their readings
def test_keye_vl_2s_step_and_its_eight_row_clone_plan_under_the_chips_memory(host, monkeypatch):
    """`keye-vl-2.0-30b-a3b.train-dsa-s16384`'s whole step at the published
    widths and 16384 tokens, compiled for the described v5e with what
    `plan_kept` chooses at the chip's memory limit: every candidate of the four
    segments, the four layers' picks among them (33.5 MB each, kept whatever the
    room), planned over 25% of the chip and under the 15.5 GB a cell allows
    itself; the attention took the splash kernels under the stored mask, no
    `reduce-window` spans a row of keys, and in the rematerialised computations
    no `top_k` of the indexer, no attention kernel and no product is left.  The 8-row
    `for_test` clone of the reference check, the tightest program of a
    16384-token cell (PERF.md, PR 54), plans with the optimizer's two moments
    beside it under the 16.9 GB the chip's runtime gives (ISSUE 56)."""
    compiled, counted = _kept_step("keye", "keye-vl-2.0-30b-a3b", "train-dsa-s16384", host.devices, monkeypatch)
    assert counted["segments"] == counted["sparse_segments"] == 4
    assert counted["kept_bytes"] == counted["candidates_bytes"] > 2e9
    peak = _planned_peak(compiled)
    print(f"the step's planned peak {peak / 1e9:.3f} GB")     # shown by `-s`
    assert 0.25 * 16.9e9 <= peak <= 15.5e9, f"the step plans {peak / 1e9:.3f} GB"
    text = compiled.as_text()
    # the stock forward kernel on block maps made from the picks, and the ONE backward kernel on the row's byte mask (PR 68)
    assert "splash_mha_fwd" in text and "attention_dq_dk_dv" in text and "flash_mha" not in text
    assert "splash_mha_dq" not in text and "splash_mha_dkv" not in text
    assert len(set(re.findall(r"/(sparse_index(?:_\d+)?)/op\d+:sparse_index/index_select/", text))) == 4
    assert len(set(re.findall(r"/(sparse_index(?:_\d+)?)/op\d+:index_alignment/", text))) == 4
    # the alignment's gradients are the kernel's in every layer (PR 59), and its target's (PR 62): a call a chunk loop's body,
    # and no float32 array of a group's scores or exponentials under the op
    assert len(set(re.findall(r"/(sparse_index(?:_\d+)?)/op\d+:index_alignment/[^\"]*index_alignment_gradients", text))) == 4
    targets = re.findall(r"custom_call_target=\"tpu_custom_call\"[^\n]*/(sparse_index(?:_\d+)?)/op\d+:index_alignment/[^\"\n]*selected_attention/[^\"\n]*alignment_target", text)
    assert len(set(targets)) == 4 and len(targets) == 4 * 8, (len(set(targets)), len(targets))          # eight bands a layer, a call a chunk
    widths = "|".join(str(keys) for keys in range(2048, 16385, 2048))
    assert not [line for line in text.splitlines() if "index_alignment" in line and re.search(rf"f32\[(4,)?8,512,({widths})\]", line)]
    windows = [int(n) for n in re.findall(r"reduce-window\([^\n]*window=\{size=[0-9x]*?x?(\d+) pad", text)]
    assert max(windows, default=0) < 2048, max(windows)      # no row's statistic is spread as one window over the row
    again = [name for name in _made_again(text) if "/cond/branch_" not in name]
    # (the router's own top-8 is made again with its layer, the same choice bit for bit: ISSUE 54; the INDEXER's never)
    assert again and not [name for name in again if name.endswith("/dot_general") or "splash_mha" in name or "attention_dq_dk_dv" in name
                          or (name.endswith("/top_k") and "moe_router" not in name)
                          or "index_select" in name or "index_alignment" in name]
    clone, _ = _kept_step("keye", "keye-vl-2.0-30b-a3b", "train-dsa-s16384", host.devices, monkeypatch, check_rows=8)
    moments = 2 * 4 * 465_391_104
    beside = _planned_peak(clone) + moments
    print(f"the 8-row clone's planned peak {_planned_peak(clone) / 1e9:.3f} GB, {beside / 1e9:.3f} with the moments")
    assert beside <= 16.9e9, f"the clone plans {_planned_peak(clone) / 1e9:.3f} GB beside {moments / 1e9:.3f} GB of moments"


@pytest.mark.slow   # one compile for four devices, ~3 minutes here: run by name (`-m slow`); PERF.md, PR 51, has its readings
def test_jamba2s_step_on_the_2x2_host_keeps_what_a_chips_room_holds_and_its_planned_peak_leaves_room(host, monkeypatch):
    """`ai21-jamba2-3b.train-ssm-fsdp4`'s whole step at the published widths
    on the described 2x2 host, ZeRO-3 over `dp`: of 98 candidates (9.38 GB a
    chip) the budget (half of what 4.80 GB of state leave of the chip) holds
    68, 6.04 GB: the attention's residuals and every product but the 13 step
    projections and the last layer's `up`; the 13 scans' forward kernels are
    still made again (their output and start states come last by operations a
    byte).  Planned under 14.5 GB a chip."""
    compiled, counted = _kept_step("jamba", "ai21-jamba2-3b", "train-ssm-fsdp4", host.devices, monkeypatch)
    assert counted == {"segments": 14, "kept_values": 68, "kept_bytes": 6035210240, "candidates_bytes": 9382264832}
    peak = _planned_peak(compiled)
    print(f"planned peak {peak / 1e9:.3f} GB a chip")
    assert 11e9 <= peak <= 14.5e9, peak
    again = _made_again(compiled.as_text())
    assert sum(name.endswith("/selective_scan/pallas_call") for name in again) >= 13
    assert not [name for name in again if name.endswith("/pallas_call") and "selective_scan" not in name]   # the attention's is kept


@pytest.mark.slow   # two compiles for four devices, ~6 minutes here: run by name (`-m slow -k nemotron3`); PERF.md, PR 60, has its readings
def test_nemotron3_supers_step_and_its_check_rows_on_the_2x2_host_leave_room(host, monkeypatch):
    """`nemotron-3-super-120b-a12b.train-ssd-fsdp4`'s whole step at the published
    widths on the described 2x2 host, ZeRO-3 over `dp`, 32 experts held a layer
    (7.49 GB a chip of state), and the 8-row `for_test` clone its reference
    check runs beside that state: both planned under the chip's 16.9 GB.  Since
    PR 61 the five scans are kernels whose residuals (the output and the
    chunks' start states, 0.40 GB a layer) `plan_kept` holds with every other
    candidate: 34 values, 4.89 GB a chip (29 and 2.88 with the plain form, which
    offered nothing), planned 14.47 GB (13.46), and no scan is made again."""
    compiled, counted = _kept_step("nemotron_h", "nemotron-3-super-120b-a12b", "train-ssd-fsdp4", host.devices, monkeypatch)
    peak = _planned_peak(compiled)
    print(f"step: planned peak {peak / 1e9:.3f} GB a chip, kept {counted}")
    assert counted == {"segments": 11, "sparse_segments": 5, "kept_values": 34, "kept_bytes": 4891082752, "candidates_bytes": 4891082752}
    assert 14.2e9 <= peak <= 14.8e9, peak
    text = compiled.as_text()
    assert text.count("all-gather") and "tpu_custom_call" in text
    assert not [name for name in _made_again(text) if "ssd_scan" in name and name.endswith("/pallas_call")]
    clone, _ = _kept_step("nemotron_h", "nemotron-3-super-120b-a12b", "train-ssd-fsdp4", host.devices, monkeypatch, check_rows=8)
    moments = 2 * 4 * 1871531904 / 4     # Adam's two float32 moments lie beside the clone's own arguments, split four ways
    beside = _planned_peak(clone) + moments
    print(f"the 8-row clone's planned peak {_planned_peak(clone) / 1e9:.3f} GB, {beside / 1e9:.3f} with the moments")
    assert beside <= 16.9e9, beside


@pytest.mark.slow   # two compiles, ~90 and ~75 s on every core: run by name (`-m slow -k qwen3_next`); PERF.md, PR 69, has their readings
def test_qwen3_nexts_step_and_its_eight_row_clone_plan_under_the_chips_memory(host, monkeypatch):
    """`qwen3-next-80b-a3b-instruct.train-gdn-s16384`'s whole step at the
    published widths and 16384 tokens, compiled for the described v5e with what
    `plan_kept` chooses at the chip's memory limit: every candidate of the four
    segments (41 values, 4.96 GB: the three scans' outputs, chunks' start states
    and T among them, 0.8 GB a layer, so that no scan is made again), planned
    over 25% of the chip and under 12.5 GB (11.94; 9.70 with the scans made
    again); the three Gated DeltaNet layers took the scan's kernels, forward
    and transposed, at a decay [1, 16384, 32] float32 (no [.., 128]
    copy of it) and 16 key heads; the full layer the causal splash forward and
    the ONE backward kernel at 16 heads of 256 on 2; the scopes
    `gated_delta_net` (three, numbered), `attention_gate` and `moe_shared_gate`
    stand in the compiled step.  The 8-row `for_test` clone of the reference
    check plans with the optimizer's two moments beside it under the 16.9 GB the
    chip's runtime gives (12.54 + 3.39: the gated norm's product in the
    projections' own layout took 2.4 GB off it; ISSUE 69)."""
    compiled, counted = _kept_step("qwen3_next", "qwen3-next-80b-a3b-instruct", "train-gdn-s16384", host.devices, monkeypatch)
    assert counted["segments"] == counted["sparse_segments"] == 4 and counted["kept_values"] == 41
    assert counted["kept_bytes"] == counted["candidates_bytes"] > 4.9e9
    peak = _planned_peak(compiled)
    print(f"the step's planned peak {peak / 1e9:.3f} GB")     # shown by `-s`
    assert 0.25 * 16.9e9 <= peak <= 12.5e9, f"the step plans {peak / 1e9:.3f} GB"
    text = compiled.as_text()
    names = re.findall(r'op_name="([^"]*)"', text)
    scans = [n for n in names if n.endswith("/kda_scan/pallas_call") or n.endswith("/kda_scan_transposed/pallas_call")]
    assert {re.search(r"/(gated_delta_net(?:_\d+)?)/", n).group(1) for n in scans} == {"gated_delta_net", "gated_delta_net_1", "gated_delta_net_2"}
    assert len({re.search(r"/(gated_delta_net(?:_\d+)?)/", n).group(1) for n in scans if "kda_scan_transposed" in n}) == 3
    assert not [n for n in scans if "rematted_computation" in n]                  # no scan is made again: its residuals are kept
    assert re.findall(r"f32\[1,16384,32\]", text)            # the decay as the kernels read it: a number a head a token
    assert re.findall(r"bf16\[1,16384,2048\]", text)          # q and k of 16 key heads: never repeated to 32
    assert "splash_mha_fwd" in text and "flash_mha" not in text
    assert len(re.findall(r'custom_call_target="tpu_custom_call"[^\n]*/attention_dq_dk_dv["/]', text)) == 1
    assert re.findall(r"bf16\[1,16,16384,256\]", text)
    assert any("/attention_gate" in n for n in names) and any("/moe_shared_gate/" in n for n in names)
    again = [name for name in _made_again(text) if "/cond/branch_" not in name]
    assert again and not [name for name in again if "splash_mha" in name or "attention_dq_dk_dv" in name or "/expert_gemm/" in name
                          or "kda_scan" in name]
    clone, _ = _kept_step("qwen3_next", "qwen3-next-80b-a3b-instruct", "train-gdn-s16384", host.devices, monkeypatch, check_rows=8)
    moments = 2 * 4 * 424_340_544
    beside = _planned_peak(clone) + moments
    print(f"the 8-row clone's planned peak {_planned_peak(clone) / 1e9:.3f} GB, {beside / 1e9:.3f} with the moments")
    assert beside <= 16.9e9, f"the clone plans {_planned_peak(clone) / 1e9:.3f} GB beside {moments / 1e9:.3f} GB of moments"
