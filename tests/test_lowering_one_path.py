"""One lowering per op (ISSUEs 29 and 30): conv2d, pool2d, batch_norm and
fused_attention choose by what the op can observe (platform, dtype, shapes,
the context's mesh, the program's own layout attributes) and by nothing a
process or a program can set.

(a) the programs the cells and the zoo build lower, for the chip, to the text
    they lowered to at the parent commit `7602313`, where six module globals
    and three attention attributes still stood at their defaults: the deletion
    changed no generated program (lowered, never compiled: nothing runs);
(b) batch_norm's one choice, its statistics by dtype, against numpy;
(c) the compile-cache key holds no global of an ops module;
(d) `fused_attention`'s choice among its three attentions, shape by shape,
    and the counters that say which a program took (ISSUE 30).
"""
import glob
import hashlib
import inspect
import json
import os
import re
import sys

import jax
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import paddle_tpu as fluid  # noqa: E402
from paddle_tpu import layers  # noqa: E402
from paddle_tpu.core import executor as ex  # noqa: E402
from paddle_tpu.models import resnet, transformer  # noqa: E402


def _resnet50(batch, for_test=False, **kw):
    """ResNet-50 at the benchmark's widths (benchmark/models/resnet.py: _build)."""
    main, startup, _, fetches = resnet.build(
        depth=50, class_dim=1000, learning_rate=0.1, momentum=0.9, with_optimizer=True, **kw)
    shape = (224, 224, 3) if kw.get("data_format") == "NHWC" else (3, 224, 224)
    feeds = {"img": jax.ShapeDtypeStruct((batch,) + shape, np.float32)}
    if for_test:  # the clone the benchmark's reference check runs
        return main.clone(for_test=True), startup, feeds, fetches["logits"].name
    feeds["label"] = jax.ShapeDtypeStruct((batch, 1), np.int32)
    return main, startup, feeds, fetches["loss"].name


def _bert(batch, seq):
    """A BERT cell's program (benchmark/models/bert.py: build)."""
    main, startup, _, fetches = transformer.build_bert(
        vocab_size=30522, seq_len=seq, d_model=768, n_layers=12, n_heads=12, d_ff=3072,
        dropout_prob=0.1, learning_rate=1e-4, with_optimizer=True, dtype="bfloat16",
        use_fused_attention=True)
    feeds = {n: jax.ShapeDtypeStruct((batch, seq), np.int32) for n in ("ids", "labels", "pos_ids")}
    return main, startup, feeds, fetches["loss"].name


def _olmoe_s4096():
    """`olmoe-1b-7b.train-s4096`'s program (benchmark/models/olmoe.py: build):
    one layer at the published widths, 12576 vocabulary rows."""
    main, startup, _, fetches = transformer.build_causal_lm(
        vocab_size=12576, seq_len=4096, n_layers=1, with_optimizer=True, dtype="bfloat16")
    feeds = {n: jax.ShapeDtypeStruct((4, 4096), np.int32) for n in ("ids", "labels", "pos_ids")}
    return main, startup, feeds, fetches["loss"].name


def _cell(model, config, traffic):
    """A decoder cell's program as the benchmark builds it (benchmark/models/<model>.py: build) at the
    configuration's and the traffic's own sizes, fetching the loss."""
    import importlib

    from benchmark import manifest as mf

    module = importlib.import_module(f"benchmark.models.{model}")
    cfg, job = mf.read_json(f"benchmark/configs/{config}.json"), mf.read_json(f"benchmark/traffic/{traffic}.json")
    main, startup, _, loss, _ = module.build(cfg, job)
    feeds = {n: jax.ShapeDtypeStruct((job["batch_per_chip"], job["seq_len"]), np.int32) for n in module.FEEDS}
    return main, startup, feeds, loss.name


def _fp16_batch_norm():
    """The one dtype no cell runs: a convolution and a training batch norm in float16."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = layers.data("x", [8, 16, 16], dtype="float32")
        h = layers.conv2d(layers.cast(x, "float16"), 16, 3, padding=1, bias_attr=False)
        y = layers.pool2d(layers.batch_norm(h, act="relu"), pool_size=2, pool_stride=2)
        loss = layers.mean(layers.cast(y, "float32"))
        fluid.optimizer.SGD(0.1).minimize(loss)
    return main, startup, {"x": jax.ShapeDtypeStruct((4, 8, 16, 16), np.float32)}, loss.name


#: case -> (builder, module name, sha256 of the op listing, sha256 of the
#: lowered StableHLO, the program's `fused_attention` ops by the attention the
#: lowering took: row kernel, flash, XLA's), recorded at the parent commit
#: `7602313` by this test (it prints what it finds).  A PR that means to
#: change a lowering re-records the case and says so in CHANGES.md.  PR 30
#: re-recorded `bert-base-s512-fused` (its twelve attentions are the whole-row
#: kernel now) and added the two cells beside it, both the parent's programs:
#: `bert-base-s128-fused` is `tests/test_olmoe.py`'s `BERT_TEXT_SHA`, OLMoE's
#: is what `bb78123` lowers to.  A program that holds a TPU kernel is lowered
#: FOR the TPU (a CPU host cannot lower a Mosaic call for itself) and hashed
#: without the kernels' serialised bodies.  PR 39 re-recorded the two BERT
#: cases and only those: the four `transpose2` ops a layer round the attention
#: are gone from the program (546 -> 498 ops; eight a layer with their
#: transposes in backward) and each `fused_attention` op carries
#: `layout="blhd"`; at 512 keys the kernel reads (B, L, H*dh) blocks, each
#: direction's call ONE function of the module that the twelve layers call
#: (two `@tpu_custom_call`s in the text for the parent's 24), at 128
#: XLA's attention transposes at the lowering's own edge.  The six other
#: programs, OLMoE's among them, lower to what they lowered to.  So do LFM2's
#: and Ouro's cells (rotary positions, a per-head norm: `layout="bhld"`, the
#: attribute not written), whose two cases PR 39 added: recorded by this test
#: in a `git archive` of the parent `5f26992` and found again in the tree
#: (SDAR's cell is pinned by `tests/test_lfm2.py`).  PR 49 re-recorded
#: `olmoe-1b-7b-s4096` and only that: its text holds two calls of the
#: `token_sum` kernel (`ops/moe_kernels.py`: the experts' rows back to token
#: order) where it held two gathers and two sums over k; the op listing's hash
#: is what it was, and the nine other programs lower to what they lowered to.
#: PR 53 re-recorded `lfm2-8b-a1b-s8192` and only that (sha256 516ab54d... at
#: the parent `14276a2`): its four sparse layers' common pass comes back to
#: token order through two calls of the same kernel with the slots no held
#: expert owns left out, where two five-branch conditionals of scatter-adds
#: stood; the op listing's hash is what it was, and OLMoE's text with the
#: unmasked call is the parent's to the byte.
PARENTS_PROGRAMS = {
    "resnet50-train-bf16-nchw": (lambda: _resnet50(256, dtype="bfloat16"), "train_cc732a46",
        "b40b8617a8e43d947e7dcdd6c6b5ea2136abe6018b84b409dacbf82eadaa1b35",
        "e437a7597c251c9f4488b4bd25aa0782721bda89de6bbeb1823bd9a16933274c", (0, 0, 0, 0)),
    "resnet50-for_test-bf16-nchw": (lambda: _resnet50(8, for_test=True, dtype="bfloat16"), "infer_87838cf4",
        "851aa4de6eed0242f77d2494362eb97c788109f448d2d11d66f5d487cd3c2762",
        "87d011020c6b861070f55ffb8b357c61d33dc3628d9666aebadc404c686f38e6", (0, 0, 0, 0)),
    "resnet50-train-f32-nchw": (lambda: _resnet50(64, dtype="float32"), "train_e29c5d91",
        "189f0d192c4800bf280ba303e8b8e09013d5c26a596e1cd944287c15d9807a8b",
        "697ddeb2e7bf3498a9bf54f8be1c456a19dec700b811e7967298709305a8a14f", (0, 0, 0, 0)),
    "resnet50-train-bf16-nhwc": (lambda: _resnet50(256, dtype="bfloat16", data_format="NHWC"), "train_cc732a46",
        "03f8097c8e4f2816fa57d4b2b45496da8d8a7e2a23de6657256a26d17ddcc3e2",
        "73cfc133487bbb2c7e57a9846ed7f785ccd85c0c7514ed0bd6519ab192285f13", (0, 0, 0, 0)),
    "bert-base-s512-fused": (lambda: _bert(32, 512), "train_44acd386",
        "ad604c402ea6916dc1d33a8b1ffffa1099b7f41e51e8f94b14007955a5978ded",
        "e1ffe2a03c46eb78509fa1147debdd84990ffebe26a43b14af53b00d326296f4", (12, 0, 0, 0)),
    "bert-base-s128-fused": (lambda: _bert(256, 128), "train_44acd386",
        "ad604c402ea6916dc1d33a8b1ffffa1099b7f41e51e8f94b14007955a5978ded",
        "0a22ade36687defbab16d2d7aefcbd8952f2a23b7713bb567c0863cad4efa10c", (0, 0, 12, 0)),
    # OLMoE's, LFM2's and Ouro's text re-recorded by PR 64 (the causal rule's backward: `ops/attention_backward_kernels.py`);
    # their listings of ops are the parent's
    "olmoe-1b-7b-s4096": (_olmoe_s4096, "train_cbb6bbe7",
        "822e9f203b8780a8ce13ae8c050fe4480b215eb43e3063bc89b21090c48146d1",
        "66ffe85c3d6308031ea0876d0aa1567725e5e6ce3e0d925ab9c191c69a99c016", (0, 0, 0, 1)),
    "lfm2-8b-a1b-s8192": (lambda: _cell("lfm2", "lfm2-8b-a1b", "train-s8192"), "train_b5740440",
        "94c5da17ec8b9b45b8a6e3c57b80081513f0cdc288a7212598cece8733215c98",
        "2013734ce6ab07a3ee242fadf3fd2d9709f15094f554a0d302e78a4669fb7f17", (0, 0, 0, 1)),
    "ouro-2.6b-ut4-s4096": (lambda: _cell("ouro", "ouro-2.6b", "train-ut4-s4096"), "train_3a3d8d40",
        "de4588fb1ac19708384a3c0cc4e3b98109602dd8aa2103815510ee3782ecdf3a",
        "33533526eb79b0e32a69b24490454067c8362c3d670e9a1941477eb339d1c5b7", (0, 0, 0, 8)),
    "batch_norm-train-fp16": (_fp16_batch_norm, "train_97080cb5",
        "97158b65993029a94935d7280f793136a5fe07aa0458a7b0de8d003fb41fbd33",
        "c51ed89d771c7584243bbd025643a313dbcaafe3ab0d33c7761134fc04f57984", (0, 0, 0, 0)),
}


#: cell -> (module name, sha256 of the step as lowered for the TPU, the kernels' bodies stripped): the five one-chip
#: cells `PARENTS_PROGRAMS` lacks, through `tools/lowered_hash.py`'s function (the recipe every `perf_opt` since PR 51
#: rests "the other cells cannot move" on), recorded at the parent commit `41636dc` of PR 58 by that tool in a
#: `git archive` of it.  A PR that means to change one of these programs re-records its line and says so.
PARENTS_CELLS = {
    # Kimi Linear's, Phi-4-mini-flash's and Kanana-2's lines re-recorded by PR 64, which means to change these programs:
    # the causal and the window rule's backward is one kernel of `ops/attention_backward_kernels.py`, not the stock
    # fused kernel or the stock pair (the parent's lines were 2c60a2d6...dd4eb, 5d27410c...84a7a, c11c6801...6c118);
    # SDAR's (block diffusion's rule) and Keye-VL-2.0's (the selected rule) re-recorded by PR 68, which means to change these
    # two programs and no other: their backward is that kernel too, reading a stored block of the mask a step (the parent's
    # lines were 3a0761cf...b8195 and 89cf2a57...2ea4e; the eleven other one-chip cells' are the parent's, `tools/lowered_hash.py`)
    "sdar-30b-a3b-chat.train-blockdiff-s4096": ("train_6de7c714",
        "b81ea9561a73e8504db346466d90b2b3e94301dce33ddd20e7ea20f1886700d7"),
    "kimi-linear-48b-a3b.train-kda-s4096": ("train_f85463d4",
        "f90a348c6f9460dfea90bb984d9dd4d084abf11d37de553a04e55d0bbe4d49a2"),
    "phi-4-mini-flash-reasoning.train-sambay-s8192": ("train_d4522e44",
        "6b601724f5a394da300cf9409359a264175e2f75310435adb903a6eb3e5c3b7b"),
    "kanana-2-30b-a3b.train-mla-s16384": ("train_9752db24",
        "9d96882e36a8fbccc6318cac72f400183a5635f2f44212ed1b17e268251d1a0f"),
    # re-recorded by PR 62, which means to change this program (`index_alignment`'s target: `ops/alignment_target_kernels.py`),
    # as PR 59 did for the op's gradients; and by PR 68 (the selected rule's backward: above)
    "keye-vl-2.0-30b-a3b.train-dsa-s16384": ("train_21d207fd",
        "f105ab5b53b0a0c46b14e368c929da67450df3d99cdfdb4ba98f51125af47184"),
}


def _attention_counters():
    from paddle_tpu.monitor import MONITOR

    seen = MONITOR.counter_values()
    return tuple(seen.get(f"lowering.attention_{path}", 0) for path in ("row_kernel", "flash", "xla", "block_causal"))


@pytest.fixture
def monitor_on():
    from paddle_tpu import monitor

    monitor.reset()  # what an earlier test of this process counted is not this one's
    monitor.enable()
    yield
    monitor.disable()
    monitor.reset()


@pytest.mark.parametrize("case", list(PARENTS_PROGRAMS) + list(PARENTS_CELLS))
def test_the_step_lowers_to_the_parents_program(case, monitor_on, monkeypatch):
    from collections import defaultdict

    # a `name_scope` met a second time in a process is numbered ("exit_head_1") and the ops carry it: a table of this
    # test's own, so that the listing does not depend on what was built before and nothing built after sees this
    monkeypatch.setattr(fluid.unique_name, "_scope_children", defaultdict(lambda: defaultdict(int)))
    # nor on what it traced: an inner `jax.jit` found again in JAX's caches shares one traced object with its other
    # callers, which lower to one private function, and the text's numbering moves
    jax.clear_caches()
    if case in PARENTS_CELLS:
        from tools import lowered_hash

        found = lowered_hash.lowered(case)
        print(f'    "{case}": {found},')
        assert found == PARENTS_CELLS[case]
        return
    build, module, ops_sha, text_sha, attentions = PARENTS_PROGRAMS[case]
    with fluid.unique_name.guard():  # parameter names come from process-wide counters
        main, startup, feeds, fetch = build()
    main.random_seed = startup.random_seed = 3
    listing = json.dumps([[op.type, op.inputs, op.outputs,
                           {k: repr(v) for k, v in sorted(op.attrs.items())}]
                          for op in main.global_block().ops], sort_keys=True)
    # the state the start-up program would make, as shapes: nothing runs
    scope = fluid.Scope()
    for v in startup.global_block().vars.values():
        if v.persistable:
            scope.set_var(v.name, jax.ShapeDtypeStruct(tuple(v.shape), v.dtype))
    step = ex._CompiledStep(main, list(feeds), [fetch], scope, platform="tpu",
                            feed_shapes={n: s.shape for n, s in feeds.items()})
    as_shape = lambda v: jax.ShapeDtypeStruct(v.shape, v.dtype)  # noqa: E731
    traced = step.jfn.trace({n: as_shape(scope.find_var(n)) for n in step.rw_names},
                            {n: as_shape(scope.find_var(n)) for n in step.ro_names},
                            feeds, as_shape(jax.random.PRNGKey(0)))
    # one count an op, where the lowering decided
    assert _attention_counters() == attentions
    if attentions[0] or attentions[1] or attentions[3]:
        # a Mosaic kernel's serialised body names the files and lines of its
        # call stack (this checkout's path among them): the pin is the program
        # round the kernels and each call's name, cost and layout, not its body
        text = re.sub(r'(\\22body\\22: \\22)[A-Za-z0-9+/=]+', r"\1",
                      traced.lower(lowering_platforms=("tpu",)).as_text())
        assert text.count('\\22body\\22: \\22\\22') == text.count("@tpu_custom_call") > 0
    else:
        text = traced.lower().as_text()
    found = (step.module, hashlib.sha256(listing.encode()).hexdigest(),
             hashlib.sha256(text.encode()).hexdigest())
    print(f'    "{case}": {found},')
    assert found == (module, ops_sha, text_sha)


#: dtype -> (offset of the input, tolerance on the saved statistics, on the
#: normalised output).  float32: two-pass numpy, exact.  bfloat16: the one
#: read of x, within tests/test_ops_round5.py's tolerance.  float16 at
#: |x| = 300: x * x is 90000, past fp16's 65504, so the one-read form would
#: give inf; the centred form holds (fp16 is 0.25 apart at 300: the input's
#: own rounding adds 0.25**2 / 12 to a variance of 1, and the output,
#: x * mul + add in fp16, is as coarse as x: half a step either way).
STATISTICS = {"float32": (0.0, 1e-4, 1e-4), "bfloat16": (0.0, 5e-2, 5e-2),
              "float16": (300.0, 5e-2, 0.26)}


@pytest.mark.parametrize("dtype", list(STATISTICS))
def test_batch_norm_chooses_its_statistics_by_dtype(dtype):
    offset, tol, out_tol = STATISTICS[dtype]
    x = (np.random.RandomState(29).randn(8, 4, 6, 6) + offset).astype("float32")
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        xv = layers.data("x", [4, 6, 6], dtype="float32")
        y = layers.cast(layers.batch_norm(layers.cast(xv, dtype), is_test=False), "float32")
    bn = next(op for op in main.global_block().ops if op.type == "batch_norm")
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    exe.run(startup, scope=scope)
    out, mean, var = (np.asarray(a, np.float64) for a in exe.run(
        main, feed={"x": x}, scope=scope,
        fetch_list=[y.name, bn.outputs["SavedMean"][0], bn.outputs["SavedVariance"][0]]))
    assert np.isfinite(out).all() and np.isfinite(var).all()
    # what the op was given: x as its dtype holds it
    seen = np.asarray(jax.numpy.asarray(x).astype(dtype), np.float64)
    m = seen.mean(axis=(0, 2, 3), keepdims=True)
    v = seen.var(axis=(0, 2, 3), keepdims=True)
    assert np.allclose(mean, m.ravel(), rtol=tol, atol=tol), np.abs(mean - m.ravel()).max()
    assert np.allclose(var, v.ravel(), rtol=tol, atol=tol), np.abs(var - v.ravel()).max()
    assert np.allclose(out, (seen - m) / np.sqrt(v + 1e-5), rtol=out_tol, atol=out_tol)


def test_the_compile_cache_key_names_no_ops_module_global():
    from paddle_tpu.ops import nn_ops

    # the key has no process-global element (the last, the flag between two lowerings of five ops, went with PR 58):
    # every element of the tuple is a name the run was called with or made from them
    elements = re.search(r"cache_key = \((.*?)\n        \)", inspect.getsource(ex.Executor._run_impl), re.S).group(1)
    assert [e.strip().rstrip(",") for e in elements.strip().splitlines()] == [
        "program._uuid", "program.version", "tuple(sorted((n, v.shape, str(v.dtype)) for n, v in jfeeds.items()))",
        "tuple(fetch_names)", "scope._uuid", "(tuple(mesh.shape.items()), batch_axis) if mesh is not None else None",
        "steps", "remat", "local_sgd_every", "grad_overlap"]
    assert "nn_ops" not in inspect.getsource(ex)
    # and nothing under `paddle_tpu/ops/` nor the interpreter asks for a flag: a lowering is chosen from what the op
    # can observe (platform, dtype, shape, the context's mesh), never from what a process can set
    sources = sorted(glob.glob(os.path.join(REPO, "paddle_tpu", "ops", "*.py"))) + [os.path.join(REPO, "paddle_tpu", "core", "lowering.py")]
    assert len(sources) > 20
    asking = [os.path.relpath(path, REPO) for path in sources if re.search(r"\bflag\(|\bflags\b", open(path).read())]
    assert not asking, asking
    # and the lowerings have nothing of the kind to name: what is left are
    # thresholds on a shape, each with the chip runs that set it beside it and
    # cells (or the crossing's runs) on both sides
    switches = [n for n, v in vars(nn_ops).items() if isinstance(v, (bool, int, float, str))
                and n.startswith("_") and n.isupper()]
    assert switches == ["_FLASH_MIN_SEQ", "_FLASH_MIN_QUERIES", "_ROW_KERNEL_MAX_SEQ", "_ROW_KERNEL_MIN_SEQ",
                        "_ROW_KERNEL_HEAD_DIM", "_ROW_KERNEL_SEQ_MULTIPLE"]
    assert not [n for n in vars(nn_ops) if n.startswith(("enable_", "set_"))]  # nor a setter for one
    # and no attribute of the op selects an attention: `causal`, `scale` and (PR 39) `layout`, which says where the
    # heads lie in the operands it was handed, are its mathematics and its signature; `kept_kv` (PR 50) says whose keys
    # and values the operands are and is read by a counter alone (`lowering.kept_tensor_readers`), outside the rule
    # (`attention` is the op's lowering on its operands, which the latent attention's unit shares: PR 55)
    source = "".join(inspect.getsource(f) for f in (nn_ops._fused_attention, nn_ops.attention, nn_ops.attention_scale,
                                                    nn_ops._attention_path))
    assert sorted(set(re.findall(r"op\.attr\(\"(\w+)\"", source))) == ["causal", "kept_kv", "layout", "scale"]
    assert "kept_kv" not in inspect.getsource(nn_ops._attention_path)


def test_a_process_that_still_exports_the_deleted_flag_runs_and_setting_it_raises():
    """An operator's shell may still carry `FLAGS_use_pallas=1` (PR 58 deleted the flag): the environment is read for
    the registered flags alone, so such a process imports the package and runs a step; `set_flags` of the name fails
    as any unknown flag does and lists the known ones."""
    import subprocess

    script = (
        "import numpy as np, paddle_tpu as fluid\n"
        "main, startup = fluid.Program(), fluid.Program()\n"
        "with fluid.program_guard(main, startup):\n"
        "    x = fluid.layers.data('x', [4])\n"
        "    loss = fluid.layers.mean(fluid.layers.fc(fluid.layers.layer_norm(x), 1))\n"
        "    fluid.optimizer.Adam(0.1).minimize(loss)\n"
        "exe = fluid.Executor(fluid.CPUPlace())\n"
        "exe.run(startup)\n"
        "print('loss', float(exe.run(main, feed={'x': np.ones((2, 4), 'float32')}, fetch_list=[loss])[0]))\n"
        "try:\n"
        "    fluid.set_flags({'FLAGS_use_pallas': True})\n"
        "except Exception as e:\n"
        "    print('refused', type(e).__name__, e)\n")
    out = subprocess.run([sys.executable, "-c", script], cwd=REPO, capture_output=True, text=True, timeout=300,
                         env=dict(os.environ, JAX_PLATFORMS="cpu", FLAGS_use_pallas="1"))
    assert out.returncode == 0, out.stderr[-2000:]
    assert "loss " in out.stdout
    refused = next(line for line in out.stdout.splitlines() if line.startswith("refused"))
    assert "FLAGS_use_pallas" in refused and "FLAGS_dp_bucket_mb" in refused, refused    # the known flags are listed


#: (queries, keys) -> the attention a bf16 `fused_attention` over 64-wide
#: heads takes on one TPU chip.  The row kernel from `_ROW_KERNEL_MIN_SEQ` to
#: `_ROW_KERNEL_MAX_SEQ` queries AND keys, the flash kernel from
#: `_FLASH_MIN_SEQ` keys (and `_FLASH_MIN_QUERIES` queries), XLA's attention
#: elsewhere: 128 (the kernel loses by 0.26% to 11%), a decoding step's one
#: query, the lengths no run has priced.  These operands are heads-major,
#: (B, H, L, dh); the rule reads the lengths by the op's layout and is the same
#: for the projections' own (tests/test_fused_attention.py has that table): it
#: starts at 256 since PR 39 (+9.1% there in BERT's program, 384 until then).
#: Where the flash kernel would be taken, a CAUSAL
#: mask without a bias, over as many keys as queries in whole blocks of the
#: splash kernels, on one device, takes those (`block_causal`, PR 37):
#: `LONG_CAUSAL` below.
ATTENTION_BY_LENGTHS = {
    (128, 128): "xla", (256, 256): "row_kernel", (384, 384): "row_kernel", (512, 512): "row_kernel",
    (512, 384): "row_kernel", (512, 256): "row_kernel", (256, 128): "xla", (1, 512): "xla", (128, 512): "xla", (320, 320): "xla",
    (640, 640): "xla", (1024, 1024): "xla", (2048, 2048): "flash", (1, 2048): "xla", (8192, 8192): "flash",
    (2048, 4096): "flash", (2176, 2176): "flash",
}
#: the lengths of `ATTENTION_BY_LENGTHS` that the causal rule's kernels take (2176 in their 128-blocks)
LONG_CAUSAL = {(2048, 2048), (8192, 8192), (2176, 2176)}
#: what else the op can see: (dtype, head width) -> may the row kernel be taken
ATTENTION_OPERANDS = {("bfloat16", 64): True, ("float32", 64): False, ("bfloat16", 128): False}


def _trace_attention(lengths, dtype="bfloat16", head=64, bias=False, causal=False, mesh=None):
    """The jaxpr of one `fused_attention` op as the interpreter lowers it for
    a TPU (nothing is lowered further, nothing runs), forward and backward,
    and the three counters' movement."""
    from types import SimpleNamespace

    from paddle_tpu.core.lowering import LoweringContext
    from paddle_tpu.core.registry import get_op_def

    lq, lk = lengths
    op = SimpleNamespace(type="fused_attention", attr=lambda name, default=None: {"causal": causal}.get(name, default))
    ctx = LoweringContext(jax.random.PRNGKey(0), platform="tpu", mesh=mesh)

    def attention(q, k, v, *b):
        ins = {"Q": [q], "K": [k], "V": [v], "Bias": list(b)}
        return get_op_def("fused_attention").lower(ctx, op, ins)["Out"].astype(np.float32).sum()

    args = [jax.ShapeDtypeStruct((2, 4, n, head), dtype) for n in (lq, lk, lk)]
    if bias:
        args.append(jax.ShapeDtypeStruct((2, 1, lq, lk), np.float32))
    before = _attention_counters()
    text = str(jax.make_jaxpr(jax.grad(attention, argnums=(0, 1, 2)))(*args))
    moved = tuple(b - a for a, b in zip(before, _attention_counters()))
    kernels = set(re.findall(r"name=(fused_sdpa_fwd|fused_sdpa_bwd|flash_attention|splash_mha_fwd|splash_mha_dq|splash_mha_dkv|attention_dq_dk_dv)\w*\b", text))
    return kernels, moved


KERNELS_OF = {"row_kernel": {"fused_sdpa_fwd", "fused_sdpa_bwd"}, "flash": {"flash_attention"}, "xla": set(),
              "block_causal": {"splash_mha_fwd", "attention_dq_dk_dv"}}  # one backward kernel, dq summed on the chip
COUNTED_AS = {"row_kernel": (1, 0, 0, 0), "flash": (0, 1, 0, 0), "xla": (0, 0, 1, 0), "block_causal": (0, 0, 0, 1)}


@pytest.mark.parametrize("on_a_mesh", [False, True], ids=["one-chip", "mesh"])
@pytest.mark.parametrize("bias,causal", [(False, False), (True, False), (False, True), (True, True)],
                         ids=["plain", "bias", "causal", "bias-causal"])
@pytest.mark.parametrize("lengths", list(ATTENTION_BY_LENGTHS), ids=lambda ls: f"{ls[0]}x{ls[1]}")
def test_fused_attention_takes_its_attention_from_the_shape(lengths, bias, causal, on_a_mesh, monitor_on):
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:4]), ("dp",)) if on_a_mesh else None
    want = ATTENTION_BY_LENGTHS[lengths]
    if on_a_mesh and want == "row_kernel":
        want = "xla"  # a custom call GSPMD cannot partition: the lowering's docstring
    if want == "flash" and causal and not bias and not on_a_mesh and lengths in LONG_CAUSAL:
        want = "block_causal"
    kernels, moved = _trace_attention(lengths, bias=bias, causal=causal, mesh=mesh)
    assert (kernels, moved) == (KERNELS_OF[want], COUNTED_AS[want])


@pytest.mark.parametrize("dtype,head", list(ATTENTION_OPERANDS), ids=lambda v: str(v))
def test_the_row_kernel_is_taken_only_for_operands_it_was_run_with(dtype, head, monitor_on):
    want = "row_kernel" if ATTENTION_OPERANDS[(dtype, head)] else "xla"
    kernels, moved = _trace_attention((512, 512), dtype=dtype, head=head)
    assert (kernels, moved) == (KERNELS_OF[want], COUNTED_AS[want])
    # off the TPU there is one attention whatever the shape
    from paddle_tpu.ops.nn_ops import _attention_path
    q = jax.ShapeDtypeStruct((2, 4, 512, head), dtype)
    assert _attention_path("cpu", None, q, q) == "xla"
