"""Qwen3-Next's whole model, tiny on the CPU (ISSUE 69); its parts (the op with
a decay a head, the gates, the rotation, the router, the 32 shares) stand in
tests/test_qwen3_next_parts.py.

A toy of the same pattern (two periods; 2 key and 4 value heads of 8 in the
linear layers; 4 query heads of 16 on 2, a quarter rotated; 16 experts top 4
with the gated shared one, every expert held) in float32 against the
benchmark's reference (benchmark/models/qwen3_next.py) on seeded weights: loss,
logits, routing, every stage and every parameter's gradient at 128 positions
(two chunks) and the stages at 48 (no whole chunk), with and without
`recompute_layers` to the last bit; the counters and the `kda_state` records;
the counted parameters of the cell's program; and the faults the comparison
refuses.

One compiled tiny model serves it: `float32_run`.
"""
import os
import sys
from types import SimpleNamespace

import jax
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import paddle_tpu as fluid  # noqa: E402
from benchmark import manifest as mf  # noqa: E402
from benchmark.models import qwen3_next  # noqa: E402
from paddle_tpu import monitor  # noqa: E402
from paddle_tpu.core import unique_name  # noqa: E402

CFG = mf.read_json("benchmark/configs/qwen3-next-80b-a3b-instruct.json")
TRAFFIC = "benchmark/traffic/train-gdn-s16384.json"

from test_qwen3_next_parts import agree, float32_products  # noqa: E402,F401  (the fixture by name)


# -- the whole model ------------------------------------------------------------------------------------

KINDS = ["gated_delta_net", "gated_delta_net", "gated_delta_net", "full_attention"]
TINY = dict(hidden_size=32, vocab_size=96, head_dim=16, num_attention_heads=4, num_key_value_heads=2, linear_num_key_heads=2,
            linear_num_value_heads=4, linear_key_head_dim=8, linear_value_head_dim=8, moe_intermediate_size=16,
            shared_expert_intermediate_size=16, num_routed_experts=16, num_experts=16, num_experts_per_tok=4,
            num_hidden_layers=8, layer_types=KINDS + KINDS)
JOB = dict(seq_len=128, batch_per_chip=2)


@pytest.fixture(scope="module", autouse=True)
def every_position_is_sampled():
    from benchmark.models import lfm2

    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(lfm2, "LOGIT_SAMPLE", 128)
        yield


def tiny_model(dtype, cfg_over=None, **job):
    cfg = dict(CFG, compute_dtype=dtype, **{**TINY, **(cfg_over or {})})
    job = dict(mf.read_json(TRAFFIC), **{**JOB, **job})
    with unique_name.guard():
        main, startup, feeds, loss, names = qwen3_next.build(cfg, job)
    main.random_seed = startup.random_seed = 3
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.TPUPlace(0))
    exe.run(startup, scope=scope)
    return cfg, job, main, loss, names, scope, exe


def params_of(main, scope):
    return {p.name: np.asarray(scope.find_var(p.name)) for p in main.all_parameters()}


def reference_of(cfg, params, rows, **kw):
    return [np.asarray(w) for w in jax.jit(lambda p, b: qwen3_next.reference(p, b, cfg, **kw))(params, rows)]


def one_step(main, loss, scope, exe, batch):
    """(the step's loss, Adam's first moments, the trace's `lowering.` counters) of one step through `train_loop`."""
    losses = []
    monitor.reset()
    monitor.enable()
    try:
        before = {k: v for k, v in monitor.get_monitor().counter_values().items() if k.startswith("lowering.")}
        fluid.train_loop(exe, main, iter([batch]), [loss], scope=scope, log_period=1,
                         on_logged=lambda i, vals: losses.append(float(np.asarray(vals[0]).reshape(-1)[0])))
        counters = {k: v - before.get(k, 0) for k, v in monitor.get_monitor().counter_values().items()
                    if k.startswith("lowering.")}
        records = [r for r in monitor.get_monitor().step_records() if r.get("kind") == "kda_state"]
    finally:
        monitor.disable()
        monitor.reset()
    moments = {p.name: np.asarray(scope.find_var(p.name + "_moment1_0")) for p in main.all_parameters()}
    return losses.pop(), moments, counters, records


@pytest.fixture(scope="module")
def float32_run():
    """The toy built twice from the same seed, every layer a `recompute_scope`
    (as the cell builds it) and none, one step each on the same batch; the
    recomputed one's `for_test` clone against the reference."""
    with jax.default_matmul_precision("highest"):
        cfg, job, main, loss, names, scope, exe = tiny_model("float32")
        rows = qwen3_next.make_batch(np.random.RandomState(3), cfg, job, 8)
        got = exe.run(main.clone(for_test=True), feed=rows, fetch_list=list(names), scope=scope)
        before = params_of(main, scope)
        want = reference_of(cfg, before, rows)
        batch = qwen3_next.make_batch(np.random.RandomState(4), cfg, job, 2)
        ref_loss, ref_grads = jax.jit(jax.value_and_grad(lambda p: qwen3_next.reference(p, batch, cfg)[0]))(before)
        step_loss, moments, counters, records = one_step(main, loss, scope, exe, batch)
        _, _, plain_main, plain_loss, _, plain_scope, plain_exe = tiny_model("float32", recompute_layers=False)
        plain = one_step(plain_main, plain_loss, plain_scope, plain_exe, batch)
    return SimpleNamespace(cfg=cfg, job=job, main=main, got=got, want=want, before=before, rows=rows, names=names,
                           moments=moments, counters=counters, records=records, plain=plain, scope=scope,
                           ref_loss=float(ref_loss), step_loss=step_loss,
                           ref_grads={k: np.asarray(v) for k, v in ref_grads.items()})


def test_float32_loss_logits_routing_and_every_stage_agree_with_the_reference(float32_run):
    found = qwen3_next.compare(float32_run.got, float32_run.want)
    assert found["left_out"] == found["routed_differently"] == found["routed_differently_above_margin"] == 0
    assert found["router_choice_differs"] == 0
    assert found["loss_error"] < 1e-5 and found["logit_error"] < 2e-5, found
    assert max(found[n] for n in ("router_prob_error", "experts_error", "shared_error", "shared_gate_error", "conv_error",
                                  "unit_error", "decay_error", "scan_error", "gated_norm_error", "attention_error", "qk_error",
                                  "gated_error")) < 2e-5, found
    # what the limits refuse, read beside the sound stages
    assert found["attention_error_other_grouping"] > 0.1 and found["gated_error_no_gate"] > 0.1 and found["gated_error_a_head"] > 1e-2
    assert found["conv_error_three_taps"] > 0.1 and found["conv_error_bf16"] > qwen3_next.CONV_RTOL
    assert min(found["unit_error_no_scale"], found["unit_error_no_norm"], found["decay_error_next_head"],
               found["decay_error_over_channels"], found["decay_error_no_beta"], found["gated_norm_error_sigmoid"],
               found["shared_error_no_gate"], found["router_prob_error_no_renormalisation"]) > 0.1
    assert found["scan_error_bf16_state"] > qwen3_next.SCAN_RTOL and found["router_prob_error_sigmoid"] > qwen3_next.ROUTER_RTOL
    assert qwen3_next.failed_limits(found) == []
    assert qwen3_next.reference_error(float32_run.got, float32_run.want) < 2e-5
    assert abs(float32_run.step_loss - float32_run.ref_loss) < 1e-5 * float32_run.ref_loss
    assert found["held_rows_share"] == [1.0] * 8                                      # every expert held
    assert np.asarray(float32_run.got[1]).shape == (128, 8, 96)
    assert np.asarray(float32_run.got[2]).shape == (8, 128, 4)                                    # the choice: every row
    assert np.asarray(float32_run.got[3]).shape == (qwen3_next.STAGE_ROWS, 128, 32)               # the router's input: the stage rows
    assert np.asarray(float32_run.got[-10]).shape == (qwen3_next.STAGE_ROWS, 128, 4, 16)          # the full layer's gated output
    assert [np.asarray(t).shape for t in float32_run.got[-9:]] == [(128, 32)] * 9                 # the stream entering 8 layers and the final norm
    assert max(found["stream_errors_worst"]) < 2e-5 and len(found["stream_errors_median"]) == 9


def test_a_sequence_that_is_no_whole_number_of_chunks_agrees_with_the_reference_too():
    """48 positions: one chunk of 48 tokens, three blocks of 16."""
    with jax.default_matmul_precision("highest"):
        cfg, job, main, loss, names, scope, exe = tiny_model("float32", dict(num_hidden_layers=4, layer_types=KINDS), seq_len=48)
        rows = qwen3_next.make_batch(np.random.RandomState(5), cfg, job, 8)
        got = exe.run(main.clone(for_test=True), feed=rows, fetch_list=list(names), scope=scope)
        found = qwen3_next.compare(got, reference_of(cfg, params_of(main, scope), rows))
    assert found["loss_error"] < 1e-5 and found["logit_error"] < 2e-5 and found["scan_error"] < 2e-5, found
    assert qwen3_next.failed_limits(found) == []


PARAMS = sorted(["lm.tok_emb", "lm.head.w", "lm.final_norm.w"]
                + [f"lm.l{i}.{n}" for i in range(8) for n in ("ln1.w", "ln2.w")]
                + [f"lm.l{i}.gdn.{n}" for i in (0, 1, 2, 4, 5, 6) for n in ("qkvz.w", "ba.w", "qkv_conv.w", "a_log", "dt_bias", "o_norm.w", "out.w")]
                + [f"lm.l{i}.attn.{n}.w" for i in (3, 7) for n in ("q", "k", "v", "out", "q_norm", "k_norm")]
                + [f"lm.l{i}.moe.{n}.w" for i in range(8)
                   for n in ("router", "gate", "up", "down", "shared.gate", "shared.up", "shared.down", "shared_gate")])


def test_the_toy_has_these_layers_parameters_counters_and_records_and_no_other(float32_run):
    r = float32_run
    assert sorted(r.before) == PARAMS
    kinds = [op.type for op in r.main.global_block().ops]
    assert kinds.count("kda") == 6 and kinds.count("short_conv") == 6 and kinds.count("kda_gate") == 6
    assert kinds.count("fused_attention") == 2 and kinds.count("rotary_embedding") == 4
    assert kinds.count("moe_router") == kinds.count("moe_experts") == 8
    assert r.before["lm.l0.gdn.qkvz.w"].shape == (32, 2 * 16 + 2 * 32) and r.before["lm.l0.gdn.ba.w"].shape == (32, 8)
    assert r.before["lm.l0.gdn.qkv_conv.w"].shape == (64, 4) and r.before["lm.l0.gdn.a_log"].shape == (4,)
    assert r.before["lm.l3.attn.q.w"].shape == (32, 4 * 32) and r.before["lm.l3.attn.k.w"].shape == (32, 32)
    assert r.before["lm.l0.moe.shared_gate.w"].shape == (32, 1)
    assert r.counters["lowering.scalar_decay_scans"] == 6 and r.counters["lowering.gated_attention_layers"] == 2
    assert r.counters["lowering.rotary_tables"] == 1 and r.counters["lowering.kda_layers"] == 6
    assert [r.counters[f"lowering.query_heads_by_layer.{i}"] for i in range(2)] == [4, 4]
    scopes = {op.attrs.get("op_namescope") for op in r.main.global_block().ops if op.type == "kda"}
    assert scopes == {"gated_delta_net"} | {f"gated_delta_net_{i}" for i in range(1, 6)}
    assert r.records and all(len(rec["decay_mean"]) == 6 and 0.2 < min(rec["decay_mean"]) and max(rec["decay_mean"]) < 1.0
                             for rec in r.records)


@pytest.mark.parametrize("name", PARAMS)
def test_float32_gradient_agrees_with_the_reference(float32_run, name):
    """Adam's first moment after one step is 0.1 x the gradient.  The program
    differentiated here makes every layer again in backward, its routing and its scan with it."""
    agree(float32_run.moments[name] / (1 - 0.9), float32_run.ref_grads[name], tol=2e-4, floor=1e-7)


@pytest.mark.parametrize("name", PARAMS)
def test_a_recomputed_layers_gradient_is_the_plain_layers_to_the_last_bit(float32_run, name):
    plain_loss, plain_moments, _, _ = float32_run.plain
    assert plain_loss == float32_run.step_loss
    np.testing.assert_array_equal(float32_run.moments[name], plain_moments[name])


def test_the_counted_parameters_of_the_cells_program_are_424_3_million():
    """The program as the cell builds it, at the published widths (built, not
    lowered): 424.3 M parameters, what the configuration file states."""
    job = mf.read_json(TRAFFIC)
    with unique_name.guard():
        main = qwen3_next.build(CFG, job)[0]
    shapes = {p.name: tuple(p.shape) for p in main.all_parameters()}
    counted = sum(int(np.prod(s)) for s in shapes.values())
    assert counted == CFG["parameters"] and round(counted / 1e6, 1) == 424.3

    def of(prefix):
        return sum(int(np.prod(s)) for n, s in shapes.items() if n.startswith(prefix))

    assert of("lm.l0.gdn.") == 2048 * 12288 + 2048 * 64 + 8192 * 4 + 4096 * 2048 + 32 + 32 + 128      # 33.72 M
    assert of("lm.l3.attn.") == 2048 * 8192 + 2 * 2048 * 512 + 4096 * 2048 + 2 * 256                   # 27.26 M
    assert of("lm.l0.moe.") == 2048 * 512 + 3 * 2048 * 512 + 2048 + 16 * 3 * 2048 * 512                # 54.53 M
    assert shapes["lm.l0.gdn.qkvz.w"] == (2048, 12288) and shapes["lm.l3.attn.q.w"] == (2048, 8192)
    assert shapes["lm.l2.moe.router.w"] == (2048, 512) and shapes["lm.l3.moe.gate.w"] == (16, 2048, 512)
    assert shapes["lm.tok_emb"] == shapes["lm.head.w"][::-1] == (18992, 2048)
    ops = main.global_block().ops
    rotary = [op for op in ops if op.type == "rotary_embedding"]
    assert len(rotary) == 2 and all((op.attr("theta"), op.attr("rotary_dim")) == (1e7, 64) for op in rotary)
    scans = [op for op in ops if op.type == "kda"]
    assert [tuple(main.global_block().var(op.inputs[s][0]).shape[1:]) for op in scans[:1] for s in ("Q", "V", "G")] == \
        [(16384, 16, 128), (16384, 32, 128), (16384, 32)]
    assert qwen3_next.flops_per_sample(CFG, job) > 0


REFUSED = {"no_decay", "decay_over_channels", "no_delta_correction", "key_head_by_modulo", "three_taps", "whole_head_turned",
           "no_query_scale", "top_8", "no_shared_gate"}


@pytest.mark.parametrize("fault", sorted(REFUSED))
def test_the_comparison_refuses_a_reference_with(fault, float32_run):
    """Nine of the controls' faults, those the toy's sizes can show (logits a
    thousandth wide and 128 positions cannot show a sigmoid router, theta 1e6 or
    a gate a head); tools/chip_qwen3_next_controls.py reads all of them on the
    chip (PERF.md section 6), and `DRY=1` of it here (tests/test_chip_controls.py)."""
    r = float32_run
    with jax.default_matmul_precision("highest"):
        found = qwen3_next.compare(r.got, reference_of(r.cfg, r.before, r.rows, faults=(fault,)))
    assert qwen3_next.failed_limits(found), fault
