"""ISSUE 60: the scalar-decay state-space scan (`ssd_scan`), experts of two
matrices in a latent, layers of one part, routed experts on a mesh that splits
the rows alone, and the Nemotron-H-class model built from them.

(a) `ssd_scan`: the chunked form against the token-by-token recurrence, forward
    and both transposes (the inputs' gradients and the parameters'), at chunk
    lengths that do and do not divide the row, with groups; the controls' copy of
    it is the op and its faults bite; its
    `infer=` rule, planner row and statistics;
(b) layers of one part in `encoder_layer`;
(c) the latent expert layer: the 16 shares' partial results, the shared expert
    and the up-projection's part counted once, add up to the uncut layer; on a
    virtual (4,) mesh the layer is the same rows on one device, statistics
    included;
(d) the tiny model in float32 against `benchmark.models.nemotron_h.reference`:
    loss, logits, every stage, every parameter's gradient; in bf16 within the
    benchmark's tolerances; the faults the stages have to refuse.
"""
import os
import sys
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import paddle_tpu as fluid  # noqa: E402
from benchmark import manifest as mf  # noqa: E402
from benchmark.models import nemotron_h  # noqa: E402
from paddle_tpu import layers, monitor  # noqa: E402
from paddle_tpu.core import unique_name  # noqa: E402
from paddle_tpu.core.lowering import LoweringContext  # noqa: E402
from paddle_tpu.core.registry import get_op_def  # noqa: E402
from paddle_tpu.models import transformer  # noqa: E402
from paddle_tpu.ops import moe_ops, ssd_ops  # noqa: E402


def agree(got, want, tol=1e-5, floor=1e-12):
    got, want = np.asarray(got, "f8"), np.asarray(want, "f8")
    assert got.shape == want.shape, (got.shape, want.shape)
    assert np.abs(got - want).max() <= tol * max(np.abs(want).max(), floor), \
        (np.abs(got - want).max(), np.abs(want).max())


# -- (a) the op ---------------------------------------------------------------------------

def scan_inputs(seed, rows, length, heads=8, width=4, groups=2, state=6):
    r = np.random.RandomState(seed)
    return (r.randn(rows, length, heads * width).astype("f4"), r.randn(rows, length, heads).astype("f4"),
            np.log(np.linspace(1.0, 16.0, heads)).astype("f4"), r.randn(rows, length, groups * state).astype("f4"),
            r.randn(rows, length, groups * state).astype("f4"), r.randn(heads).astype("f4"), (r.randn(heads) - 2).astype("f4"))


def recurrence(x, dt, a_log, b, c, d, bias, groups):
    return nemotron_h.scan_recurrence(x, dt, b, c, a_log, d, bias, groups)


@pytest.mark.parametrize("length,chunk", [(64, 16), (50, 16), (7, 16), (33, 128), (40, 8)])
def test_the_chunked_scan_is_the_recurrence_forward_and_both_transposes(length, chunk):
    groups = 2
    args = tuple(jnp.asarray(t) for t in scan_inputs(length, 2, length, groups=groups))
    weigh = jnp.asarray(np.random.RandomState(1).randn(2, length, 32), jnp.float32)

    def chunked(*a):
        return ssd_ops.chunked_ssd_scan(*a, groups, chunk)[0]

    def plain(*a):
        return recurrence(*a, groups)

    agree(chunked(*args), plain(*args), tol=2e-5)
    mine = jax.grad(lambda *a: jnp.sum(chunked(*a) * weigh), argnums=tuple(range(7)))(*args)
    theirs = jax.grad(lambda *a: jnp.sum(plain(*a) * weigh), argnums=tuple(range(7)))(*args)
    for got, want in zip(mine, theirs):   # x, dt, B, C (the inputs' transposes) and A_log, D, dt_bias (the parameters')
        agree(got, want, tol=5e-5)
    # the state after the last token and the means are the recurrence's own
    _, final, (decay, step) = ssd_ops.chunked_ssd_scan(*args, groups, chunk)
    dt_f = np.log1p(np.exp(np.asarray(args[1]) + np.asarray(args[6])))
    agree(decay, np.exp(-dt_f * np.exp(np.asarray(args[2]))).mean(), tol=1e-5)
    agree(step, dt_f.mean(), tol=1e-5)
    assert final.shape == (2, 8, 4, 6) and 0.0 < float(decay) < 1.0
    x, dt, a_log, b, c, d, bias = args
    agree(final, nemotron_h.scan_recurrence(x, dt, b, c, a_log, d, bias, groups, with_state=True)[1], tol=2e-5)


def test_a_head_reads_its_own_groups_b_and_c():
    """Head h reads group h // (H / G): with B of the second group zeroed the
    first group's heads are what they were and the second group's see no input."""
    x, dt, a_log, b, c, d, bias = scan_inputs(5, 1, 24)
    whole = np.asarray(ssd_ops.chunked_ssd_scan(x, dt, a_log, b, c, d, bias, 2, 8)[0])
    b[..., 6:] = 0.0
    cut = np.asarray(ssd_ops.chunked_ssd_scan(x, dt, a_log, b, c, d, bias, 2, 8)[0])
    agree(cut[..., :16], whole[..., :16], tol=1e-6)
    agree(cut[..., 16:], (np.repeat(d, 4) * x)[..., 16:], tol=1e-6)


@pytest.mark.parametrize("fault", ["bf16_state", "bf16_cumulative", "wrong_group", "default_precision"])
def test_the_controls_copy_of_the_scan_is_the_op_and_its_fault_bites(fault):
    """tools/chip_nemotron_controls.py puts its faults into a copy of the chunked
    form: with none the copy is the op bit for bit, output and last state."""
    from tools import chip_nemotron_controls as controls

    x, dt, a_log, b, c, d, bias = scan_inputs(9, 1, 48)
    sound, last, _ = ssd_ops.chunked_ssd_scan(x, dt, a_log, b, c, d, bias, 2, 16)
    copied, copied_last = controls.faulty_ssd_scan(x, dt, a_log, b, c, d, bias, 2, 16)
    assert np.array_equal(np.asarray(copied), np.asarray(sound)) and np.array_equal(np.asarray(copied_last), np.asarray(last))
    faulty, faulty_last = controls.faulty_ssd_scan(x, dt, a_log, b, c, d, bias, 2, 16, fault)
    off = np.abs(np.asarray(faulty) - np.asarray(sound)).max() / np.abs(np.asarray(sound)).max()
    off_last = np.abs(np.asarray(faulty_last) - np.asarray(last)).max() / np.abs(np.asarray(last)).max()
    if fault == "default_precision":   # the CPU's default precision IS float32
        assert off == off_last == 0.0
    else:
        assert off > 1e-4 and off_last > 1e-4


def test_the_op_publishes_its_state_has_an_infer_rule_and_a_planner_row():
    with unique_name.guard():
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup):
            x, dt = layers.data("x", [20, 32]), layers.data("dt", [20, 8])
            b, c = layers.data("b", [20, 12]), layers.data("c", [20, 12])
            y = layers.ssd_scan(x, dt, b, c, heads=8, groups=2, chunk=8)
            loss = layers.mean(y)
            fluid.optimizer.SGD(0.1).minimize(loss)
    assert tuple(y.shape)[1:] == (20, 32)
    op = next(op for op in main.global_block().ops if op.type == "ssd_scan")
    assert (op.attr("groups"), op.attr("chunk")) == (2, 8)
    assert get_op_def("ssd_scan").step_stats[0] == ("Stats",) and get_op_def("ssd_scan").kept is not None
    from paddle_tpu.core import analysis, resource_plan
    assert [d for d in analysis.verify_program(main, level="full") if d.severity == "error"] == []
    feed_shapes = {"x": (2, 20, 32), "dt": (2, 20, 8), "b": (2, 20, 12), "c": (2, 20, 12)}
    row = next(r for r in resource_plan.plan_program(main, feed_shapes=feed_shapes).rows if r.op_type == "ssd_scan")
    # a token: C . B against the chunk's 8 keys a group, the decayed scores by x, the state's update and its read
    assert row.flops == ssd_ops.ssd_scan_flops(40, 8, 4, 6, 2, 8) == 2 * 40 * (2 * 8 * 6 + 8 * (8 * 4 + 2 * 4 * 6))
    exe, scope = fluid.Executor(fluid.TPUPlace(0)), fluid.Scope()
    exe.run(startup, scope=scope)
    r = np.random.RandomState(0)
    feed = {"x": r.randn(2, 20, 32).astype("f4"), "dt": r.randn(2, 20, 8).astype("f4"),
            "b": r.randn(2, 20, 12).astype("f4"), "c": r.randn(2, 20, 12).astype("f4")}
    out, stats = exe.run(main.clone(for_test=True), feed=feed, fetch_list=[y, op.outputs["Stats"][0]], scope=scope)
    want = recurrence(feed["x"], feed["dt"], *(np.asarray(scope.find_var(n)) for n in (op.inputs["ALog"][0],)), feed["b"],
                      feed["c"], np.asarray(scope.find_var(op.inputs["D"][0])), np.asarray(scope.find_var(op.inputs["DtBias"][0])), 2)
    agree(out, want, tol=2e-5)
    assert 0.0 < stats[0] < 1.0 and stats[1] > 0 and np.isfinite(stats[2])
    with pytest.raises(Exception, match="groups 3 does not divide"):
        with unique_name.guard(), fluid.program_guard(fluid.Program(), fluid.Program()):
            layers.ssd_scan(layers.data("x", [20, 32]), layers.data("dt", [20, 8]), layers.data("b", [20, 12]),
                            layers.data("c", [20, 12]), heads=8, groups=3)
            problems = [d for d in analysis.verify_program(fluid.default_main_program(), level="full") if d.severity == "error"]
            assert not problems, f"ssd_scan: {problems}"


# -- (b) layers of one part -------------------------------------------------------------------

def test_a_layer_of_one_part_is_its_operator_or_its_feed_forward_part_alone():
    def build(**kw):
        with unique_name.guard():
            main, startup = fluid.Program(), fluid.Program()
            with fluid.program_guard(main, startup):
                x = layers.data("x", [6, 16])
                y = transformer.encoder_layer(x, 6, 16, 2, 24, "l", dropout_prob=0.0, causal=True, norm="rms",
                                              proj_bias=False, unit_norms=True, **{"pre_norm": True, **kw})
        return main, sorted(p.name for p in main.all_parameters()), [op.type for op in main.global_block().ops], y

    _, names, ops, _ = build(ffn=None)
    assert names == ["l.attn.k.w", "l.attn.out.w", "l.attn.q.w", "l.attn.v.w", "l.ln1.w"] and ops.count("rms_norm") == 1
    assert ops.count("elementwise_add") == 1
    _, names, ops, _ = build(operator=None, ffn="gated_silu")
    assert names == ["l.ffn.down.w", "l.ffn.gate.w", "l.ffn.up.w", "l.ln2.w"] and ops.count("rms_norm") == 1
    assert "fused_attention" not in ops and "softmax" not in ops and ops.count("elementwise_add") == 1
    _, names, _, _ = build(ffn="gated_silu")
    assert len(names) == 9      # both parts, as ever
    with pytest.raises(ValueError, match="no part"):
        build(operator=None, ffn=None)
    with pytest.raises(ValueError, match="pre-norm"):
        build(ffn=None, pre_norm=False)
    with pytest.raises(ValueError, match="mamba2 layer mamba2="):
        transformer.build_causal_lm(vocab_size=8, seq_len=4, d_model=8, n_heads=2, layer_types=["mamba2"])
    with pytest.raises(ValueError, match="or feed_forward"):
        transformer.build_causal_lm(vocab_size=8, seq_len=4, d_model=8, n_heads=2, layer_types=["experts"])


# -- (c) the latent expert layer ----------------------------------------------------------------

def latent_layer(held, seed=11, experts=16):   # every weight's seed is its own: the same values whatever `held`
    """(the layer's output, its statistics, the scope's parameters) of one latent expert layer over 2 x 24 tokens."""
    with unique_name.guard():
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup):
            x = layers.data("x", [24, 32])
            attr = transformer._attr
            out, _, _ = layers.moe(
                x, experts, 12, 3, norm_topk_prob=True, held=held, router_attr=attr("m.router.w", seed=5),
                up_attr=attr("m.up.w", 0.3, seed=6), down_attr=attr("m.down.w", 0.3, seed=7), scoring="sigmoid",
                routed_scaling_factor=5.0, norm_eps=1e-20, bias_attr=attr("m.bias", 0.02, 9), shared_experts=1,
                shared_attrs=(None, attr("m.shared.up.w", seed=8), attr("m.shared.down.w", seed=10)), activation="relu2",
                gated=False, latent_size=8, latent_attrs=(attr("m.in.w", 0.3, seed=3), attr("m.out.w", 0.3, seed=4)),
                shared_width=20)
    main.random_seed = startup.random_seed = seed
    return main, startup, out


def run_layer(held, feed, mesh=None):
    main, startup, out = latent_layer(held)
    scope, exe = fluid.Scope(), fluid.Executor(fluid.TPUPlace(0))
    exe.run(startup, scope=scope)
    ops = main.global_block().ops
    experts_op = next(op for op in ops if op.type == "moe_experts")
    router_op = next(op for op in ops if op.type == "moe_router")
    names = [out.name, experts_op.outputs["Out"][0], experts_op.outputs["Dropped"][0], router_op.outputs["Load"][0],
             router_op.outputs["BiasMoved"][0]] + ([experts_op.outputs["Held"][0]] if held else [])
    program = main if mesh is None else fluid.CompiledProgram(main).with_mesh(mesh, batch_axis="dp")
    got = exe.run(program, feed={"x": feed}, fetch_list=names, scope=scope)
    return got, {p.name: np.asarray(scope.find_var(p.name)) for p in main.all_parameters()}, scope, main


def test_the_sixteen_shares_add_up_to_the_uncut_layer():
    """The guide's shares test: with every share's matrices the uncut layer's
    own rows, the sum of the shares' routed parts IN THE LATENT is the uncut
    layer's, so the shares' outputs, the shared expert and the up-projection's
    part counted once, add up to the uncut 16-expert layer."""
    feed = np.random.RandomState(2).randn(4, 24, 32).astype("f4")
    with jax.default_matmul_precision("highest"):
        main, startup, out = latent_layer(None)
        scope, exe = fluid.Scope(), fluid.Executor(fluid.TPUPlace(0))
        exe.run(startup, scope=scope)
        experts_op = next(op for op in main.global_block().ops if op.type == "moe_experts")
        whole, routed = exe.run(main, feed={"x": feed}, fetch_list=[out.name, experts_op.outputs["Out"][0]], scope=scope)
        full = {n: np.asarray(scope.find_var(n)) for n in ("m.up.w", "m.down.w", "m.out.w")}
        latent_sum, held_rows, outputs = 0.0, 0, []
        for share in range(4):
            held = (4 * share, 4)
            main_s, startup_s, out_s = latent_layer(held)
            scope_s = fluid.Scope()
            exe.run(startup_s, scope=scope_s)
            for n in ("m.up.w", "m.down.w"):   # this share's experts ARE the uncut layer's rows
                scope_s.set_var(n, jnp.asarray(full[n][held[0]:held[0] + 4]))
            op_s = next(op for op in main_s.global_block().ops if op.type == "moe_experts")
            part, o, n_held, dropped = exe.run(main_s, feed={"x": feed}, scope=scope_s,
                                               fetch_list=[op_s.outputs["Out"][0], out_s.name, op_s.outputs["Held"][0],
                                                           op_s.outputs["Dropped"][0]])
            latent_sum, held_rows = latent_sum + np.asarray(part, "f8"), held_rows + int(n_held[0])
            outputs.append(np.asarray(o, "f8"))
            assert int(dropped[0]) == 0
    assert held_rows == 4 * 24 * 3                      # every assignment fell on exactly one share
    agree(latent_sum, routed, tol=1e-5)
    shared_and_nothing_routed = np.asarray(whole, "f8") - np.asarray(routed, "f8") @ full["m.out.w"]
    agree(sum(o - shared_and_nothing_routed for o in outputs) + shared_and_nothing_routed, whole, tol=1e-5)


def test_on_a_mesh_that_splits_the_rows_the_layer_is_the_same_rows_on_one_device_statistics_included():
    feed = np.random.RandomState(4).randn(4, 24, 32).astype("f4")
    mesh = fluid.parallel.make_mesh((4,), ("dp",))
    monitor.enable()
    try:
        before = monitor.MONITOR.counter("lowering.moe_experts_under_shard_map").value
        with jax.default_matmul_precision("highest"):
            alone, params, _, _ = run_layer((4, 8), feed)
            split, params_split, _, main = run_layer((4, 8), feed, mesh)
        assert monitor.MONITOR.counter("lowering.moe_experts_under_shard_map").value == before + 1
    finally:
        monitor.disable()
    for name in params:
        agree(params_split[name], params[name], tol=0)
    agree(split[0], alone[0], tol=1e-5)                  # the layer's output
    agree(split[1], alone[1], tol=1e-5)                  # the routed part in the latent
    for mine, theirs in zip(split[2:], alone[2:]):       # Dropped, Load, BiasMoved, Held: whole over the mesh
        assert np.array_equal(np.asarray(mine), np.asarray(theirs))
    assert int(alone[2][0]) == 0 and int(alone[5][0]) > 0 and int(np.asarray(alone[3]).sum()) == 4 * 24 * 3


def test_the_token_sum_kernel_is_taken_on_a_chips_own_rows_under_a_rows_only_mesh():
    x = jax.ShapeDtypeStruct((8192, 1024), jnp.bfloat16)
    rows_only, both = SimpleNamespace(size=4, shape={"dp": 4}), SimpleNamespace(size=4, shape={"dp": 2, "tp": 2})
    assert moe_ops._token_sum_path("tpu", rows_only, x, 22, 32) == "xla"               # GSPMD's own partitioning of the plain form
    assert moe_ops._token_sum_path("tpu", rows_only, x, 22, 32, True) == moe_ops._token_sum_path("tpu", None, x, 22, 32)
    assert moe_ops._token_sum_path("cpu", rows_only, x, 22, 32, True) == "xla"
    ctx = SimpleNamespace(platform="tpu", mesh=both)
    assert moe_ops._token_sum_kernel(ctx, x, 22, 32) is None


# -- (d) the tiny model against the reference ----------------------------------------------------

TINY = dict(hidden_size=64, num_attention_heads=4, num_key_value_heads=2, head_dim=16, vocab_size=96,
            mamba_num_heads=8, mamba_head_dim=8, ssm_state_size=16, n_groups=2, chunk_size=16,
            moe_latent_size=32, moe_intermediate_size=24, moe_shared_expert_intermediate_size=48,
            num_routed_experts=32, n_routed_experts=8, num_experts_per_tok=4,
            num_hidden_layers=5, hybrid_override_pattern="MEM*E", conv_taps_bound=4.0)
#: 44 tokens: two whole chunks of 16 and a padded tail
JOB = dict(seq_len=44, batch_per_chip=1)


@pytest.fixture(scope="module", autouse=True)
def every_position_is_sampled():
    from benchmark.models import lfm2

    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(lfm2, "LOGIT_SAMPLE", 44)
        patch.setattr(lfm2, "ATTENTION_SAMPLE", 44)
        patch.setattr(nemotron_h, "STAGE_CHANNELS", 64)
        patch.setattr(nemotron_h, "STAGE_HEADS", 8)
        patch.setattr(nemotron_h, "STAGE_TOKENS", 44)
        patch.setattr(nemotron_h, "EXPERTS_SAMPLE", 88)
        yield


def tiny_cfg(dtype):
    cfg = dict(mf.read_json("benchmark/configs/nemotron-3-super-120b-a12b.json"), compute_dtype=dtype, **TINY)
    cfg["layer_types"] = nemotron_h.layer_types(cfg)
    job = dict(mf.read_json("benchmark/traffic/train-ssd-fsdp4.json"), **JOB)
    del job["mesh_shape"], job["mesh_axes"]
    return cfg, job


def tiny_model(dtype, seed=3):
    cfg, job = tiny_cfg(dtype)
    with unique_name.guard():
        main, startup, feeds, loss, names = nemotron_h.build(cfg, job)
    main.random_seed = startup.random_seed = seed
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.TPUPlace(0))
    exe.run(startup, scope=scope)
    return cfg, job, main, loss, names, scope, exe


def params_of(main, scope):
    return {p.name: np.asarray(scope.find_var(p.name)) for p in main.all_parameters()}


def reference_of(cfg, params, rows, **kw):
    return [np.asarray(w) for w in jax.jit(lambda p, b: nemotron_h.reference(p, b, cfg, **kw))(params, rows)]


@pytest.fixture(scope="module")
def float32_run():
    with jax.default_matmul_precision("highest"):
        cfg, job, main, loss, names, scope, exe = tiny_model("float32")
        rows = nemotron_h.make_batch(np.random.RandomState(3), cfg, job, 8)
        got = exe.run(main.clone(for_test=True), feed=rows, fetch_list=list(names), scope=scope)
        before = params_of(main, scope)
        want = reference_of(cfg, before, rows)
        batch = nemotron_h.make_batch(np.random.RandomState(4), cfg, job, 4)
        ref_loss, ref_grads = jax.jit(jax.value_and_grad(lambda p: nemotron_h.reference(p, batch, cfg)[0]))(before)
        step_loss, = exe.run(main, feed=batch, fetch_list=[loss], scope=scope)
        moments = {n: np.asarray(scope.find_var(n + "_moment1_0")) for n in before}
        ops = [op.type for op in main.global_block().ops]
    return SimpleNamespace(cfg=cfg, job=job, got=got, want=want, ops=ops, before=before, moments=moments, rows=rows,
                           ref_loss=float(ref_loss), step_loss=float(np.asarray(step_loss).reshape(-1)[0]),
                           ref_grads={k: np.asarray(v) for k, v in ref_grads.items()})


def test_float32_loss_logits_and_every_stage_agree_with_the_reference(float32_run):
    found = nemotron_h.compare(float32_run.got, float32_run.want)
    assert found["loss_error"] < 1e-5 and found["logit_error"] < 5e-5, found
    assert found["other_choice"] == found["routed_differently"] == found["router_choice_differs"] == found["biases_differ"] == 0
    assert found["logit_error_other_choice"] == found["qk_error_other_choice"] == found["other_choice_share"] == 0.0
    assert max(found["conv_error"], found["scan_error"], found["scan_error_deep"], found["attention_error"], found["qk_error"],
               found["experts_error"], found["scan_state_error"]) < 5e-5, found
    assert found["router_prob_error"] < 1e-5 and found["bias_moved"] > 0
    assert found["scan_error_bf16_state"] > 5e-4 and found["experts_error_relu"] > 0.1      # what the stages have to refuse
    assert found["scan_state_error_bf16_state"] > 1e-3 > nemotron_h.SCAN_STATE_RTOL
    assert all(0.0 < decay < 1.0 for decay in found["scan_decay_mean"])
    assert nemotron_h.reference_error(float32_run.got, float32_run.want) < 5e-5
    assert abs(float32_run.step_loss - float32_run.ref_loss) < 1e-5 * float32_run.ref_loss


MAMBA_PARAMS = ("in.w", "conv.w", "conv.b", "a_log", "d", "dt_bias", "norm.w", "out.w")
MOE_PARAMS = ("router.w", "latent_in.w", "up.w", "down.w", "latent_out.w", "shared.up.w", "shared.down.w")
PARAMS = sorted(
    ["lm.tok_emb", "lm.head.w", "lm.final_norm.w"]
    + [f"lm.l{i}.ln1.w" for i in (0, 2, 3)] + [f"lm.l{i}.ln2.w" for i in (1, 4)]
    + [f"lm.l{i}.mamba2.{n}" for i in (0, 2) for n in MAMBA_PARAMS]
    + [f"lm.l{i}.moe.{n}" for i in (1, 4) for n in MOE_PARAMS]
    + [f"lm.l3.attn.{n}.w" for n in ("q", "k", "v", "out")])


def test_the_tiny_model_has_these_layers_parameters_and_no_other(float32_run):
    r = float32_run
    assert sorted(r.before) == PARAMS
    assert sum(v.size for v in r.before.values()) == nemotron_h.parameters(r.cfg)
    assert r.ops.count("ssd_scan") == 2 and r.ops.count("short_conv") == 2 and r.ops.count("fused_attention") == 1
    assert r.ops.count("moe_router") == r.ops.count("moe_experts") == 2
    assert r.ops.count("rotary_embedding") == r.ops.count("selective_scan") == 0
    shapes = {n: r.before[n].shape for n in ("lm.l0.mamba2.in.w", "lm.l0.mamba2.conv.w", "lm.l0.mamba2.a_log",
                                            "lm.l0.mamba2.norm.w", "lm.l1.moe.router.w", "lm.l1.moe.up.w",
                                            "lm.l1.moe.down.w", "lm.l1.moe.latent_in.w", "lm.l1.moe.shared.up.w",
                                            "lm.l3.attn.k.w")}
    assert shapes == {"lm.l0.mamba2.in.w": (64, 64 + 64 + 64 + 8), "lm.l0.mamba2.conv.w": (128, 4), "lm.l0.mamba2.a_log": (8,),
                      "lm.l0.mamba2.norm.w": (64,), "lm.l1.moe.router.w": (64, 32), "lm.l1.moe.up.w": (8, 32, 24),
                      "lm.l1.moe.down.w": (8, 24, 32), "lm.l1.moe.latent_in.w": (64, 32), "lm.l1.moe.shared.up.w": (64, 48),
                      "lm.l3.attn.k.w": (64, 32)}


@pytest.mark.parametrize("name", PARAMS)
def test_float32_gradient_agrees_with_the_reference(float32_run, name):
    """Adam's first moment after one step is 0.1 x the gradient `append_backward`
    made (every layer a recomputed segment) against `jax.grad` of the reference,
    which differentiates the token-by-token recurrence and the masked loop over
    the held experts."""
    r = float32_run
    agree(r.moments[name] / (1 - 0.9), r.ref_grads[name], tol=3e-4)


def test_bfloat16_agrees_within_the_benchmarks_tolerances(monkeypatch):
    monkeypatch.setattr(nemotron_h, "OTHER_CHOICE_MAX", 0.5)    # a quarter of the experts held, 64 wide
    cfg, job, main, loss, names, scope, exe = tiny_model("bfloat16")
    rows = nemotron_h.make_batch(np.random.RandomState(3), cfg, job, 8)
    got = exe.run(main.clone(for_test=True), feed=rows, fetch_list=list(names), scope=scope)
    want = reference_of(cfg, params_of(main, scope), rows)
    found = nemotron_h.compare(got, want)
    assert 1e-4 < found["logit_error"] < nemotron_h.REFERENCE_RTOL and found["loss_error"] < 2e-3
    assert found["conv_error"] < nemotron_h.CONV_RTOL < found["conv_error_bf16"]
    assert found["scan_error"] < nemotron_h.SCAN_RTOL and found["scan_error_deep"] < nemotron_h.SCAN_DEEP_RTOL
    assert max(found["scan_error"], found["scan_error_deep"]) < 0.2 * found["scan_error_bf16_state"]
    assert found["scan_state_error"] < nemotron_h.SCAN_STATE_RTOL < 0.2 * found["scan_state_error_bf16_state"]
    assert found["logit_error_other_choice"] < nemotron_h.OTHER_CHOICE_RTOL
    assert found["qk_error_other_choice"] < nemotron_h.OTHER_CHOICE_RTOL
    assert found["attention_error"] < nemotron_h.ATTENTION_RTOL and found["qk_error"] < nemotron_h.QK_RTOL
    assert found["experts_error"] < nemotron_h.EXPERTS_RTOL < found["experts_error_relu"]
    assert found["router_prob_error"] < nemotron_h.ROUTER_RTOL and found["router_choice_differs"] == 0
    assert nemotron_h.reference_error(got, want) == max(found["loss_error"], found["logit_error"])


@pytest.mark.parametrize("fault", ["relu_for_relu2", "norm_before_gate", "bf16_state", "bf16_cumulative", "wrong_group"])
def test_the_reference_check_fails_on(fault, monkeypatch, float32_run):
    r = float32_run
    if fault in ("relu_for_relu2", "norm_before_gate"):      # into the reference
        kw = {"relu_for_relu2": dict(activation="relu"), "norm_before_gate": dict(gate_first=False)}[fault]
        with jax.default_matmul_precision("highest"):
            want = reference_of(r.cfg, r.before, r.rows, **kw)
        assert nemotron_h.reference_error(r.got, want) == float("inf") or \
            nemotron_h.reference_error(r.got, want) > nemotron_h.REFERENCE_RTOL
        return

    from tools import chip_nemotron_controls as controls

    mambas = [i for i, kind in enumerate(r.cfg["layer_types"]) if kind == "mamba2"]
    parameters = [tuple(r.before[f"lm.l{i}.mamba2.{n}"] for n in ("a_log", "d", "dt_bias")) for i in (mambas[0], mambas[-1])]
    got = controls.scan_stage(r.got, parameters, r.cfg["n_groups"], r.cfg["chunk_size"], fault)   # into the stage
    found, sound = nemotron_h.compare(got, r.want), nemotron_h.compare(r.got, r.want)
    # in float32 nothing rounds the output alike: the fault shows as itself, twenty times what the sound program reads
    # (on the chip, in bf16, each has its reading beside its limit: PERF.md section 6, PR 60)
    for layer in ("scan_error", "scan_error_deep", "scan_state_error"):     # the first and the last Mamba-2 layer, their states
        assert found[layer] > 20 * sound[layer] and found[layer] > 2e-5, (layer, found[layer], sound[layer])
    assert found["scan_state_error"] > nemotron_h.SCAN_STATE_RTOL
    assert nemotron_h.reference_error(got, r.want) == float("inf")
    if fault == "wrong_group":
        assert found["scan_error"] > nemotron_h.SCAN_RTOL and found["scan_error_deep"] > nemotron_h.SCAN_DEEP_RTOL


@pytest.mark.parametrize("fault", ["mask_shifted", "wrong_kv_head"])
def test_the_attention_stage_refuses(fault, float32_run):
    """A query that also sees the key after it, and a query head that reads the
    other key/value head: what `ATTENTION_RTOL` stands under (the controls put
    them into what the program fetched of the stage)."""
    from tools import chip_nemotron_controls as controls

    r = float32_run
    found = nemotron_h.compare(controls.attention_stage(r.got, fault), r.want)
    assert found["attention_error"] > 5 * nemotron_h.ATTENTION_RTOL, found["attention_error"]
    assert nemotron_h.reference_error(controls.attention_stage(r.got, fault), r.want) == float("inf")
