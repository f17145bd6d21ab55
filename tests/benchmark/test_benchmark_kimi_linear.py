"""PR 42's cell rehearsed tiny on the CPU, its configuration against the
catalog row, its arithmetic against hand counts, and its four per-layer
readers on hand-built inputs.

The rehearsal builds on `tiny_root` of test_benchmark_rehearsal.py: the
cell's configuration and traffic files are written, cut down, into the same
scratch root.  As there, no number of a CPU run means anything.
"""
import json
import os
import re

import pytest

from benchmark import manifest as mf
from benchmark.metrics import (attention_roofline_share, kda_ms_per_step, kda_scan_roofline_share,
                               kda_state_decay_mean, latent_attention_ms_per_step)
from benchmark.models import kimi_linear, lfm2

from test_benchmark_rehearsal import check_line, run_cell, tiny_root  # noqa: F401

CELL = "kimi-linear-48b-a3b.train-kda-s4096"
CONFIG = "benchmark/configs/kimi-linear-48b-a3b.json"
TRAFFIC = "benchmark/traffic/train-kda-s4096.json"
#: the per-layer metrics this cell brought: each lists it, none is pinned to it
OWN_METRICS = ("kda_ms_per_step", "kda_scan_roofline_share", "latent_attention_ms_per_step", "kda_state_decay_mean")
TINY_NEW = {
    CONFIG: dict(hidden_size=48, num_attention_heads=2, intermediate_size=96, moe_intermediate_size=16, num_experts=4,
                 num_routed_experts=32, num_experts_per_token=4, vocab_size=96, kv_lora_rank=24, qk_nope_head_dim=16,
                 qk_rope_head_dim=8, v_head_dim=16,
                 linear_attn_config=dict(num_heads=2, head_dim=16, short_conv_kernel_size=4, kda_layers=[1, 2, 3, 5],
                                         full_attn_layers=[4])),
    TRAFFIC: dict(seq_len=128, batch_per_chip=2, ring=4, trace_seconds=0.8),
}


@pytest.fixture
def tiny_root_with_the_cell(tiny_root, monkeypatch):  # noqa: F811
    for path, over in TINY_NEW.items():
        data = mf.read_json(path)
        data.update(over)
        os.makedirs(os.path.dirname(os.path.join(tiny_root, path)), exist_ok=True)
        with open(os.path.join(tiny_root, path), "w") as f:
            json.dump(data, f)
    monkeypatch.setattr(lfm2, "LOGIT_SAMPLE", 8)
    monkeypatch.setattr(lfm2, "ATTENTION_SAMPLE", 8)
    return tiny_root


@pytest.mark.parametrize("trace", [0, 1])
def test_the_cell_rehearsed_tiny_on_the_cpu(tiny_root_with_the_cell, trace, capsys):
    result = run_cell(tiny_root_with_the_cell, CELL, trace, 2)
    check_line(result, CELL, trace)
    lines = [json.loads(line) for line in capsys.readouterr().out.splitlines() if line.startswith('{"info"')]
    routing = [line for line in lines if line["info"] == "reference_routing"]
    assert len(routing) == 1 and routing[0]["routed_differently_above_margin"] == 0
    assert routing[0]["biases_differ"] == 0 and routing[0]["bias_moved"] > 0
    assert routing[0]["kda_error"] <= kimi_linear.KDA_RTOL < routing[0]["kda_error_bf16_state"]
    assert routing[0]["conv_error"] <= kimi_linear.CONV_RTOL and routing[0]["shared_error"] <= kimi_linear.SHARED_RTOL
    assert routing[0]["attention_error"] <= kimi_linear.ATTENTION_RTOL and routing[0]["qk_error"] <= kimi_linear.QK_RTOL
    if trace:  # the program's record, no device needed
        assert 0.2 < result["metrics"]["kda_state_decay_mean"]["value"] < 1.0
        assert result["metrics"]["recompiles_in_window"]["value"] == 0


def test_the_manifest_holds_the_configuration_and_the_cell_and_nothing_is_wrong_with_it():
    m = mf.load()
    assert mf.problems(m) == []
    cell = mf.cell(m, CELL)      # membership, never position: a later PR appends after it
    assert cell["chips"] == 1 and (cell["config"], cell["traffic"]) == ("kimi-linear-48b-a3b", "train-kda-s4096")
    config = next(x for x in m["configs"] if x["name"] == cell["config"])
    for text in (cell["why"], config["why"], config["source"]):
        assert 1 <= len(text) <= 200 and text.isprintable()
    assert "1/32" in cell["why"] and "32x" in cell["why"]          # what a held expert sees, said in the cell
    for name in OWN_METRICS:
        metric = next(x for x in m["per_layer"] if x["name"] == name)
        reader = mf.reader_module(name)
        assert CELL in metric["workloads"]
        assert (metric["unit"], metric["better"], metric["source"], metric["layer"], metric["moves"]) == \
            (reader.UNIT, reader.BETTER, reader.SOURCE, reader.LAYER, reader.MOVES)
        assert reader.read({}) is None  # an empty context (a parent without the scopes): nothing, and no error
    reported = {x["name"] for x in mf.metrics_of(m, CELL, "per_layer")}
    assert set(OWN_METRICS) | {"model_flops_util", "peak_hbm_gb", "update_ms_per_step", "device_idle_share",
                               "dispatch_ms_per_step", "recompiles_in_window", "router_bias_moved_share"} <= reported
    # the generic readers that misread a step with `while`s and three-line splash calls in it (PERF.md, defects 4a, 13b),
    # and the readers two other cells' tests pin to their one cell (13a)
    assert not reported & {"fwd_ms_per_step", "bwd_ms_per_step", "scoped_time_share", "device_roofline_share",
                           "moe_ms_per_step", "attention_ms_per_step", "expert_gemm_roofline_share"}
    assert {x["name"] for x in mf.metrics_of(m, CELL, "end_to_end")} == {"train_samples_per_s", "setup_s"}


def test_the_configuration_keeps_every_published_number_but_the_four_it_says():
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog beside the model-configs guide here")
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "Kimi-Linear-48B-A3B-Instruct")
    cfg = mf.read_json(CONFIG)
    differs = sorted(k for k, v in row["config"].items() if cfg.get(k, "absent") != v)
    assert differs == sorted(cfg["reduced"]) == ["linear_attn_config", "num_experts", "num_hidden_layers", "vocab_size"]
    assert cfg["reduced_from"] == {k: row["config"][k] for k in cfg["reduced"]}
    # the widths, by name: none is cut, in the nested group neither
    assert (cfg["hidden_size"], cfg["intermediate_size"], cfg["moe_intermediate_size"], cfg["num_attention_heads"],
            cfg["kv_lora_rank"], cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"],
            cfg["num_experts_per_token"], cfg["num_shared_experts"], cfg["routed_scaling_factor"],
            cfg["num_routed_experts"]) == (2304, 9216, 1024, 32, 512, 128, 64, 128, 8, 1, 2.446, row["config"]["num_experts"])
    published = row["config"]["linear_attn_config"]
    mine = cfg["linear_attn_config"]
    assert {k: mine[k] for k in ("head_dim", "num_heads", "short_conv_kernel_size")} == \
        {k: published[k] for k in ("head_dim", "num_heads", "short_conv_kernel_size")} == \
        {"head_dim": 128, "num_heads": 32, "short_conv_kernel_size": 4}
    # the floors: the leading dense layer once and a whole period of four sparse layers at the published three to one,
    # 8 experts, an eighth of the rows
    depth = cfg["num_hidden_layers"]
    assert depth == 5 == len(cfg["layer_types"]) and cfg["first_k_dense_replace"] == 1
    assert mine["kda_layers"] == [i for i in published["kda_layers"] if i <= depth] == [1, 2, 3, 5]
    assert mine["full_attn_layers"] == [i for i in published["full_attn_layers"] if i <= depth] == [4]
    assert cfg["layer_types"] == ["latent_attention" if i + 1 in mine["full_attn_layers"] else "kda" for i in range(depth)]
    assert kimi_linear.held(cfg) == (0, 8) and cfg["vocab_size"] * 8 == row["config"]["vocab_size"]
    assert cfg["num_experts"] * 32 == row["config"]["num_experts"]
    entry = next(c for c in mf.load()["configs"] if c["name"] == "kimi-linear-48b-a3b")
    assert entry["source"] == row["source_url"] == cfg["source"] and entry["reduced"] == cfg["reduced"]
    assert "32 chips share each layer" in cfg["deployment"] and "9.64 GB" in cfg["deployment"]
    assert {"kda_short_conv", "kda_qk_l2norm", "kda_decay", "kda_beta", "kda_output_gate", "kda_state", "mla_no_rope",
            "router", "norm_topk_eps", "expert_bias", "routing_seed", "shared_expert", "optimizer", "weights", "data",
            "aux_losses", "head_dim"} <= set(cfg["assumed"])


def test_the_parameter_sum_is_the_files_and_the_issues():
    """The program built from the file has 602.5 M parameters (the issue's sum),
    counted from its own shapes; the file states the same number."""
    import numpy as np

    import paddle_tpu as fluid

    cfg, job = cfg_and_job()
    with fluid.unique_name.guard():
        main = kimi_linear.build(cfg, dict(job, seq_len=64))[0]
    sizes = {p.name: int(np.prod(p.shape)) for p in main.all_parameters()}
    total = sum(sizes.values())
    assert total == cfg["parameters"] and abs(total - 602.5e6) < 0.1e6
    assert abs(16 * total / 1e9 - 9.64) < 0.01                          # 16 bytes a parameter
    kda = sum(n for name, n in sizes.items() if name.startswith("lm.l1.kda."))
    latent = sum(n for name, n in sizes.items() if name.startswith("lm.l3.attn."))
    sparse = sum(n for name, n in sizes.items() if name.startswith("lm.l1.moe."))
    assert (round(kda / 1e6, 2), round(latent / 1e6, 2), round(sparse / 1e6, 2)) == (39.52, 29.11, 64.29)
    assert sizes["lm.l1.moe.gate.w"] == 8 * 2304 * 1024 and sizes["lm.l1.moe.router.w"] == 2304 * 256
    assert sizes["lm.tok_emb"] == sizes["lm.head.w"] == 20480 * 2304


def test_the_departures_are_the_docstrings_word_for_word():
    listed = kimi_linear.__doc__.split("word for word):")[1]
    items = [re.sub(r"\s+", " ", d.strip().rstrip(";.")) for d in listed.split("  * ")[1:]]
    assert items == mf.read_json(CONFIG)["departures"]
    assert len(items) == 7


def test_the_traffic_is_the_issues():
    job = mf.read_json(TRAFFIC)
    assert (job["kind"], job["seq_len"], job["learning_rate"], job["lr_warmup_steps"], job["lr_warmup_start"]) == \
        ("train", 4096, 1e-4, 200, 1e-6)
    assert (job["adam_beta1"], job["adam_beta2"], job["adam_epsilon"]) == (0.9, 0.95, 1e-8)
    assert (job["ring"], job["loader_capacity"], job["max_inflight"], job["log_period"], job["warmup_steps"],
            job["trace_seconds"]) == (64, 2, 2, 8, 4, 2.5)
    assert job["batch_per_chip"] in (1, 2)


# -- the arithmetic kept with the benchmark ------------------------------------

def cfg_and_job():
    return mf.read_json(CONFIG), mf.read_json(TRAFFIC)


def scan_flops_by_hand(C, K, V):
    """A chunk of C tokens, one head, forward: the two decayed Grams' triangles
    (C^2 K multiply-adds together), the triangular solve of K + V right-hand
    sides (C^2 / 2 each), Phi and B (C K^2, C K V), the two corrections' triangles
    (C^2 K / 2, C^2 V / 2), the state's two products (K^2 V, C K V); 2 a multiply-add."""
    return 2 * (C * C * K + C * C * (K + V) / 2 + C * K * K + C * K * V + C * C * K / 2 + C * C * V / 2
                + K * K * V + C * K * V)


def test_kda_scan_flops_and_bytes_by_hand():
    tiny = dict(linear_attn_config=dict(num_heads=3, head_dim=8), layer_types=["kda", "latent_attention", "kda"])
    job = dict(seq_len=128, batch_per_chip=5)
    chunks = 5 * 128 // 64
    assert kimi_linear.kda_scan_flops(tiny, job) == 3 * 2 * 3 * chunks * scan_flops_by_hand(64, 8, 8)
    # a head's token: q, k, v, o in bf16, g and beta in float32, and their gradients once
    assert kimi_linear.kda_scan_bytes(tiny, job) == 2 * (4 * 8 * 2 + 8 * 4 + 4) * 3 * 5 * 128 * 2
    cfg, job = cfg_and_job()
    flops, moved = kimi_linear.kda_scan_flops(cfg, job), kimi_linear.kda_scan_bytes(cfg, job)
    tokens = job["batch_per_chip"] * 4096
    assert flops == 3 * 4 * 32 * (tokens // 64) * scan_flops_by_hand(64, 128, 128)
    assert moved == 2 * 1540 * 32 * tokens * 4
    assert abs(flops / tokens / 1e6 - 81.8) < 0.1          # ~27 MFLOP a token a layer forward and backward x 3
    # at one sequence the least time is the bytes', 0.49 ms a layer beside 0.42 of arithmetic: a share over 100% would
    # need the four scans under 2.0 ms a step
    least = attention_roofline_share.least_seconds(flops, moved, {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9})
    assert least == pytest.approx(moved / 819e9) and 1.9e-3 < least < 2.0e-3


def test_flops_per_sample_at_the_published_sizes():
    cfg, job = cfg_and_job()
    d, seq = 2304, 4096
    kda = 4 * 2 * d * 4096 + 2 * (2 * d * 128 + 2 * 128 * 4096) + 2 * d * 32 + 32 * scan_flops_by_hand(64, 128, 128) / 64
    latent = 2 * d * 32 * 192 + 2 * d * 576 + 2 * 512 * 32 * 256 + 2 * 4096 * d + 2 * 32 * (192 + 128) * (seq + 1) / 2
    dense = 3 * 2 * d * 9216
    sparse = 2 * d * 256 + (1 + 0.25) * 3 * 2 * d * 1024    # the router, the shared expert, a quarter of a held one
    per_position = (kda + dense) + 2 * (kda + sparse) + (latent + sparse) + (kda + sparse) + 2 * d * 20480
    assert kimi_linear.flops_per_sample(cfg, job) == pytest.approx(3.0 * seq * per_position, rel=1e-12)
    # 2.2 GFLOP a token (the issue reckoned 2.0 without the scans' products and the low-rank pairs)
    assert abs(kimi_linear.flops_per_sample(cfg, job) / seq - 2.22e9) < 0.01e9
    assert 0.42 < 4 * kda / per_position < 0.50                                       # the four KDA operators
    assert 32 * scan_flops_by_hand(64, 128, 128) / 64 / kda < 0.10                    # the scan's products in one


# -- the readers -----------------------------------------------------------------

def test_kda_state_decay_mean_reads_the_windows_logged_steps():
    def record(step, decay, largest=(1.0, 2.0)):
        return {"kind": "kda_state", "pipeline_step": step, "decay_mean": list(decay), "beta_mean": [0.5, 0.5],
                "state_abs_max": list(largest), "worst_layer": 1}

    records = [record(0, [0.1, 0.1]), {"kind": "moe_routing", "pipeline_step": 8},
               record(8, [0.8, 0.6]), record(16, [0.9, 0.7]), record(24, [0.7, 0.7])]
    assert kda_state_decay_mean.decay_mean(records, 4) == pytest.approx(0.7)     # step 0 is warm-up; means 0.7, 0.8, 0.7
    assert kda_state_decay_mean.decay_mean([], 4) is None
    assert kda_state_decay_mean.decay_mean([{"kind": "moe_routing", "pipeline_step": 8}], 4) is None
    with pytest.raises(AssertionError, match="largest"):
        kda_state_decay_mean.decay_mean(records + [record(32, [0.5, 0.5], largest=(1.0, float("nan")))], 4)
    assert kda_state_decay_mean.read({"traffic": {}}) is None


HLO = '''
  %fusion.1 = bf16[1,4096,4096]{2,1,0} fusion(%a, %w), kind=kOutput, calls=%f1, metadata={op_name="jit(train_x)/jvp(fwd)/kda/op9:mul/dot_general"}
  %fusion.2 = f32[64,32,64,64]{3,2,1,0} fusion(%q, %k), kind=kOutput, calls=%f2, metadata={op_name="jit(train_x)/jvp(fwd)/kda_2/op30:kda/kda_chunk_scan/dot_general"}
  %while.3 = (s32[], f32[32,128,128]{2,1,0}) while(%t), condition=%c3, body=%b3, metadata={op_name="jit(train_x)/transpose(jvp(fwd))/kda_2/op30:kda/kda_chunk_scan/while"}
  %fusion.4 = f32[32,128,128]{2,1,0} fusion(%s, %p), kind=kOutput, calls=%f4, metadata={op_name="jit(train_x)/transpose(jvp(fwd))/kda_2/op30:kda/kda_chunk_scan/while/body/dot_general"}
  %fusion.5 = bf16[1,4096,32,192]{3,2,1,0} fusion(%c), kind=kLoop, calls=%f5, metadata={op_name="jit(train_x)/jvp(fwd)/latent_attention/op50:concat/concatenate"}
  %splash.6 = bf16[1,32,4096,128]{3,2,1,0} custom-call(%q, %k, %v), custom_call_target="tpu_custom_call", frontend_attributes={kernel_metadata="{
   }"},
   metadata={op_name="jit(train_x)/jvp(fwd)/latent_attention/op51:fused_attention/block_sparse_attention/splash_mha_fwd"}
  %fusion.7 = bf16[1,4096,2304]{2,1,0} fusion(%c), kind=kLoop, calls=%f7, metadata={op_name="jit(train_x)/jvp(fwd)/op60:mul/dot_general"}
  %fusion.8 = bf16[1,4096,2304]{2,1,0} fusion(%c), kind=kLoop, calls=%f8, metadata={op_name="jit(train_x)/jvp(fwd)/kda_like/op61:mul/dot_general"}
'''


class _Compiled:
    def as_text(self):
        return HLO


def test_the_three_device_time_readers_by_hand(monkeypatch):
    """Own time by instruction: the `while` of the state's scan encloses its
    body's product on the `XLA Ops` line and is counted by what is left of it;
    sibling scopes are numbered (kda, kda_2); a scope that only begins alike
    is left out; the splash call's three-line instruction is found."""
    from benchmark import program_trace

    def op(name, start_ms, ms):
        return (f"%{name} = bf16[1]{{0}} fusion(%a)", start_ms * 1e6, ms * 1e6, {})

    planes = [("/host:CPU", [("main", [("bench.traced_window", 0.0, 100e6, {})])]),
              ("/device:TPU:0", [
                  ("XLA Ops", [op("fusion.1", 1, 3), op("fusion.2", 5, 2), op("while.3", 10, 20), op("fusion.4", 11, 4),
                               op("fusion.4", 16, 4), op("fusion.5", 40, 1), op("splash.6", 42, 6), op("fusion.7", 50, 9),
                               op("fusion.8", 60, 5)]),
                  ("XLA Modules", [("jit_train_x(1)", 0.0, 100e6, {})]),
              ])]
    monkeypatch.setattr(program_trace, "traced_planes", lambda ctx: planes)
    cfg, job = cfg_and_job()
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    ctx = {"executables": [_Compiled()], "model": kimi_linear, "config": cfg, "traffic": job, "peaks": peaks}
    assert kda_ms_per_step.read(ctx) == pytest.approx(3 + 2 + (20 - 8) + 8)
    assert latent_attention_ms_per_step.read(ctx) == pytest.approx(1 + 6)
    scan_ms = 2 + (20 - 8) + 8
    least = attention_roofline_share.least_seconds(kimi_linear.kda_scan_flops(cfg, job), kimi_linear.kda_scan_bytes(cfg, job), peaks)
    assert kda_scan_roofline_share.read(ctx) == pytest.approx(100.0 * least / (scan_ms / 1e3))
    assert kda_scan_roofline_share.read(ctx) < 100.0
    # a run without executables, a trace or the scope, or a model without the arithmetic: nothing
    assert kda_scan_roofline_share.read(dict(ctx, model=object())) is None
    for reader in (kda_ms_per_step, kda_scan_roofline_share, latent_attention_ms_per_step):
        assert reader.read(dict(ctx, executables=[])) is None
    monkeypatch.setattr(program_trace, "traced_planes", lambda ctx: None)
    for reader in (kda_ms_per_step, kda_scan_roofline_share, latent_attention_ms_per_step):
        assert reader.read(ctx) is None
