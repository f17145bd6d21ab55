"""PR 50's cell rehearsed tiny on the CPU, its configuration against the catalog
row, its parameter table and arithmetic against hand counts, and its four
per-layer readers on hand-built inputs.

The rehearsal builds on `tiny_root` of test_benchmark_rehearsal.py: the cell's
configuration and traffic files are written, cut down, into the same scratch
root.  As there, no number of a CPU run means anything.  Entries of
`BENCHMARK.json` are found by name, never by position or count: a later PR
appends after them.
"""
import json
import os
import re

import pytest

from benchmark import manifest as mf
from benchmark.metrics import (attention_roofline_share, gmu_ms_per_step, kept_kv_attention_ms_per_step,
                               window_attention_roofline_share, window_pairs_visited_over_allowed)
from benchmark.models import jamba, lfm2, phi4flash

from test_benchmark_rehearsal import check_line, run_cell, tiny_root  # noqa: F401

CELL = "phi-4-mini-flash-reasoning.train-sambay-s8192"
CONFIG = "benchmark/configs/phi-4-mini-flash-reasoning.json"
TRAFFIC = "benchmark/traffic/train-sambay-s8192.json"
#: the per-layer metrics this cell brought: each lists it
OWN_METRICS = ("gmu_ms_per_step", "kept_kv_attention_ms_per_step", "window_attention_roofline_share",
               "window_pairs_visited_over_allowed")
TINY_NEW = {
    CONFIG: dict(hidden_size=64, intermediate_size=96, mamba_dt_rank=4, num_attention_heads=4, num_key_value_heads=2,
                 vocab_size=96, sliding_window=8),
    TRAFFIC: dict(seq_len=48, batch_per_chip=1, ring=4, trace_seconds=0.8),
}


@pytest.fixture
def tiny_root_with_the_cell(tiny_root, monkeypatch):  # noqa: F811
    for path, over in TINY_NEW.items():
        data = mf.read_json(path)
        data.update(over)
        os.makedirs(os.path.dirname(os.path.join(tiny_root, path)), exist_ok=True)
        with open(os.path.join(tiny_root, path), "w") as f:
            json.dump(data, f)
    manifest = mf.load()   # `tiny_root` wrote the manifest with the planned cells; this cell is in the accepted one
    assert CELL in [w["name"] for w in manifest["workloads"]]
    monkeypatch.setattr(lfm2, "LOGIT_SAMPLE", 8)
    monkeypatch.setattr(lfm2, "ATTENTION_SAMPLE", 8)
    monkeypatch.setattr(jamba, "STAGE_CHANNELS", 64)
    monkeypatch.setattr(phi4flash, "STAGE_CHANNELS", 64)
    return tiny_root


@pytest.mark.parametrize("trace", [0, 1])
def test_the_cell_rehearsed_tiny_on_the_cpu(tiny_root_with_the_cell, trace, capsys):
    result = run_cell(tiny_root_with_the_cell, CELL, trace, 2)
    check_line(result, CELL, trace)
    assert result["device"]["count"] == 1
    lines = [json.loads(line) for line in capsys.readouterr().out.splitlines() if line.startswith('{"info"')]
    stages = [line for line in lines if line["info"] == "reference_stages"]
    assert len(stages) == 1
    assert phi4flash.failed_limits(stages[0]) == [], stages[0]
    # (no inner norms: at N(0, 0.02) the state's part of the scan's output lies under the output's own bf16 step, so a
    # bf16 state reads like a sound one here; PERF.md section 6, PR 50)
    assert stages[0]["gmu_error"] < 0.2 * stages[0]["gmu_error_bf16"], stages[0]
    if trace:  # the program's records, no device needed
        assert 0.2 < result["metrics"]["ssm_state_decay_mean"]["value"] < 1.0
        assert result["metrics"]["recompiles_in_window"]["value"] == 0
        from benchmark import program_trace

        records = [r for r in program_trace.program_monitor().step_records() if r.get("kind") == "gmu_memory"]
        assert records and all(r["finite"] and len(r["memory_abs_mean"]) == 1 for r in records)


def test_the_manifest_holds_the_configuration_the_cell_and_its_metrics_by_membership():
    m = mf.load()
    assert mf.problems(m) == []
    cell = mf.cell(m, CELL)
    assert cell["chips"] == 1 and (cell["config"], cell["traffic"]) == ("phi-4-mini-flash-reasoning", "train-sambay-s8192")
    config = next(x for x in m["configs"] if x["name"] == cell["config"])
    for text in (cell["why"], config["why"], config["source"]):
        assert 1 <= len(text) <= 200 and text.isprintable()
    assert "6 of 32 layers" in cell["why"] and "MLPs" in cell["why"]      # what the step is, said in the cell
    four = [c["name"] for c in m["workloads"] if c["chips"] == 4]
    assert CELL not in four and len(four) <= max(1, len(m["workloads"]) // 4)
    for name in OWN_METRICS:
        metric = next(x for x in m["per_layer"] if x["name"] == name)
        reader = mf.reader_module(name)
        assert CELL in metric["workloads"]
        assert (metric["unit"], metric["better"], metric["source"], metric["layer"], metric["moves"]) == \
            (reader.UNIT, reader.BETTER, reader.SOURCE, reader.LAYER, reader.MOVES)
        assert reader.read({}) is None  # an empty context (a parent without the scopes): nothing, and no error
    reported = {x["name"] for x in mf.metrics_of(m, CELL, "per_layer")}
    jamba_cell = {x["name"] for x in mf.metrics_of(m, "ai21-jamba2-3b.train-ssm-fsdp4", "per_layer")}
    # every per-layer metric Jamba's cell is on but the two of its collectives
    assert {n for n in jamba_cell if not n.startswith("collective_")} <= reported
    assert not reported & {"collective_time_share", "collective_exposed_share"}
    assert set(OWN_METRICS) | {"ssm_ms_per_step", "ssm_scan_roofline_share", "ssm_state_decay_mean", "recompute_ms_per_step",
                               "flash_attention_ms_per_step", "model_flops_util", "peak_hbm_gb"} <= reported
    assert {x["name"] for x in mf.metrics_of(m, CELL, "end_to_end")} == {"train_samples_per_s", "setup_s"}


def _catalog_row():
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog beside the model-configs guide here")
    with open(catalog) as f:
        row = next((r for r in map(json.loads, f) if r["name"] == "Phi-4-mini-flash-reasoning"), None)
    if row is None:
        pytest.skip("the catalog here has no row for this model")
    return row


def test_the_configuration_keeps_every_published_number_but_the_depth_and_the_vocabulary():
    row = _catalog_row()
    cfg = mf.read_json(CONFIG)
    differs = sorted(k for k, v in row["config"].items() if cfg.get(k, "absent") != v)
    assert differs == cfg["reduced"] == ["num_hidden_layers", "vocab_size"]
    assert cfg["reduced_from"] == {k: row["config"][k] for k in differs} == {"num_hidden_layers": 32, "vocab_size": 200064}
    # the widths, by name: none is cut
    assert (cfg["hidden_size"], cfg["intermediate_size"], cfg["num_attention_heads"], cfg["num_key_value_heads"],
            cfg["sliding_window"], cfg["mamba_expand"], cfg["mamba_d_state"], cfg["mamba_dt_rank"], cfg["mamba_d_conv"]) == \
        (2560, 10240, 40, 20, 512, 2, 16, 160, 4)
    assert cfg["vocab_size"] * 8 == 200064 and cfg["num_hidden_layers"] == len(cfg["layer_types"]) == 6
    assert cfg["layer_types"] == phi4flash.layer_types(cfg) == ["mamba", "sliding_attention", "mamba", "full_attention",
                                                               "gmu", "cross_attention"]
    assert cfg["published_layers"] == [0, 1, 16, 17, 18, 19]
    assert cfg["layer_types"][cfg["memory_layer"]] == "mamba" and cfg["layer_types"][cfg["kv_layer"]] == "full_attention"
    entry = next(c for c in mf.load()["configs"] if c["name"] == "phi-4-mini-flash-reasoning")
    assert entry["source"] == row["source_url"] == cfg["source"] and entry["reduced"] == cfg["reduced"]
    assert "eight v5e chips" in cfg["deployment"] and "11.15 GB" in cfg["deployment"] and "over-weights" in cfg["deployment"]
    assert {"layer_types", "mamba", "head_dim", "biases", "positions", "gmu", "cross_attention", "norms", "initialisation",
            "compute_dtype"} <= set(cfg["assumed"])


def test_the_whole_models_layout_sums_to_the_published_size():
    cfg = mf.read_json(CONFIG)
    whole = dict(cfg, vocab_size=200064, published_layers=list(range(32)))
    kinds = whole["layer_types"] = phi4flash.layer_types(whole)
    assert [kinds.count(k) for k in ("mamba", "sliding_attention", "full_attention", "gmu", "cross_attention")] == [9, 8, 1, 7, 7]
    assert kinds[16] == "mamba" and kinds[17] == "full_attention" and kinds[18:20] == ["gmu", "cross_attention"]
    assert phi4flash.parameters(whole) == cfg["parameters_whole_model"] and abs(cfg["parameters_whole_model"] - 3852.6e6) < 0.1e6


def test_the_parameter_sum_is_the_files_and_the_issues():
    """The program built from the file has 697 M parameters (the issue's sum),
    counted from its own shapes; the file states the same number, and so does
    the module's formula; each kind's part is the issue's table's."""
    import numpy as np

    import paddle_tpu as fluid

    cfg, job = cfg_and_job()
    with fluid.unique_name.guard():
        main = phi4flash.build(cfg, dict(job, seq_len=64))[0]
    sizes = {p.name: int(np.prod(p.shape)) for p in main.all_parameters()}
    total = sum(sizes.values())
    assert total == cfg["parameters"] == phi4flash.parameters(cfg) and abs(total - 697.1e6) < 0.1e6
    assert abs(16 * total / 1e9 - 11.15) < 0.01                           # 16 bytes a parameter

    def part(prefix):
        return round(sum(n for name, n in sizes.items() if name.startswith(prefix)) / 1e6, 2)

    assert (part("lm.l0.mamba."), part("lm.l1.attn."), part("lm.l3.attn."), part("lm.l4.gmu."), part("lm.l5.attn."),
            part("lm.l0.ffn.")) == (41.24, 19.67, 19.67, 26.21, 13.11, 78.64)
    assert part("lm.l2.mamba.") == 41.24 and not any(".mamba." in name and "_norm" in name for name in sizes)   # no inner norms
    assert sizes["lm.tok_emb"] == 25008 * 2560 and "lm.head.w" not in sizes                   # tied
    assert "lm.l5.attn.k.w" not in sizes and "lm.l5.attn.v.w" not in sizes                    # the cross layer has none
    assert sizes["lm.l1.attn.k.w"] == 2560 * 20 * 64 and sizes["lm.l1.attn.k.b"] == 20 * 64
    assert sizes["lm.final_norm.w"] == sizes["lm.final_norm.b"] == sizes["lm.l0.ln1.b"] == 2560


def test_the_departures_are_the_docstrings_word_for_word():
    listed = phi4flash.__doc__.split("word for word):")[1]
    items = [re.sub(r"\s+", " ", d.strip().rstrip(";.")) for d in listed.split("  * ")[1:]]
    assert items == mf.read_json(CONFIG)["departures"]
    assert len(items) == 5 and "Differential Attention" in items[1]


def test_the_traffic_is_the_issues():
    job = mf.read_json(TRAFFIC)
    assert (job["kind"], job["seq_len"], job["batch_per_chip"], job["learning_rate"], job["lr_warmup_steps"],
            job["lr_warmup_start"]) == ("train", 8192, 1, 3e-4, 200, 1e-6)
    assert (job["adam_beta1"], job["adam_beta2"], job["adam_epsilon"]) == (0.9, 0.95, 1e-8)
    assert (job["ring"], job["loader_capacity"], job["max_inflight"], job["log_period"], job["warmup_steps"],
            job["trace_seconds"]) == (64, 2, 2, 8, 4, 2.5)
    assert "mesh_shape" not in job


# -- the arithmetic kept with the benchmark ------------------------------------

def cfg_and_job():
    return mf.read_json(CONFIG), mf.read_json(TRAFFIC)


def test_window_flops_and_bytes_by_hand():
    tiny = dict(hidden_size=32, num_attention_heads=4, num_key_value_heads=2, sliding_window=4,
                layer_types=["mamba", "sliding_attention", "full_attention", "sliding_attention"])
    job = dict(seq_len=16, batch_per_chip=3)
    pairs = (1 + 2 + 3) + 13 * 4                                           # the first three queries see fewer keys
    # six products (two forward, four backward) of 2 operations a pair and feature: 4 heads of 8
    assert phi4flash.window_attention_flops(tiny, job) == 6 * 2 * 4 * 8 * pairs * 3 * 2
    # q and the output (4 heads) and k, v (2 heads) of 8, 16 positions, bf16, once forward and their gradients once
    assert phi4flash.window_attention_bytes(tiny, job) == 2 * (2 * 4 + 2 * 2) * 8 * 16 * 2 * 3 * 2
    cfg, job = cfg_and_job()
    band = 512 * 513 // 2 + (8192 - 512) * 512
    assert phi4flash.window_attention_flops(cfg, job) == 12 * 2560 * band
    assert phi4flash.window_attention_bytes(cfg, job) == 4 * 120 * 64 * 8192
    assert 0.12 < band / (8192 * 8193 // 2) < 0.125                       # the band is an eighth of the causal triangle
    least = attention_roofline_share.least_seconds(phi4flash.window_attention_flops(cfg, job),
                                                   phi4flash.window_attention_bytes(cfg, job),
                                                   {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9})
    assert 0.6e-3 < least < 0.7e-3                                         # the products decide: 0.63 ms a step
    # the scan's arithmetic is Jamba's module's, for two layers on one chip
    assert phi4flash.selective_scan_flops(cfg, job) == 3 * 2 * 8192 * 5120 * 118
    assert phi4flash.selective_scan_bytes(cfg, job) == 3 * 30784 * 8192 * 2


def test_flops_per_sample_at_the_published_sizes():
    cfg, job = cfg_and_job()
    d, seq = 2560, job["seq_len"]
    mamba = 2 * d * 10240 + 2 * 5120 * 192 + 2 * 160 * 5120 + 2 * 5120 * d
    projections = 2 * 2 * d * 2560 + 2 * 2 * d * 1280
    mlp = 3 * 2 * d * 10240
    triangle, band = seq * (seq + 1) // 2, 512 * 513 // 2 + (seq - 512) * 512
    matrices = 2 * mamba + 2 * projections + 2 * 2 * d * 5120 + 2 * 2 * d * 2560 + 6 * mlp + 2 * d * 25008
    want = 3.0 * (seq * matrices + 2 * 2 * 2560 * (band + 2 * triangle))
    assert phi4flash.flops_per_sample(cfg, job) == pytest.approx(want, rel=1e-12)
    # 6 x the matrices' parameters a token, plus the attentions' products over the allowed pairs
    assert abs(phi4flash.flops_per_sample(cfg, job) / seq / (6 * 697.1e6) - 1.0) < 0.08
    assert 0.62 < 6 * mlp / matrices < 0.70 and 0.08 < 2 * d * 25008 / matrices < 0.10      # the MLPs ~2/3, the head ~9%
    attention = 3.0 * 2 * 2 * 2560 * (band + 2 * triangle)
    assert 0.05 < attention / want < 0.09 and 30e12 < want < 40e12                            # ~7% of ~37 TFLOP a sample


# -- the readers -----------------------------------------------------------------

HLO = '''
  %fusion.1 = bf16[1,8192,5120]{2,1,0} fusion(%a, %w), kind=kOutput, calls=%f1, metadata={op_name="jit(train_x)/jvp(fwd)/checkpoint/gmu/op70:mul/dot_general"}
  %fusion.2 = bf16[1,8192,5120]{2,1,0} fusion(%g, %m), kind=kLoop, calls=%f2, metadata={op_name="jit(train_x)/transpose(jvp(fwd))/checkpoint/rematted_computation/gmu/op72:memory_gate/mul"}
  %fusion.3 = bf16[1,8192,2560]{2,1,0} fusion(%a, %w), kind=kOutput, calls=%f3, metadata={op_name="jit(train_x)/jvp(fwd)/checkpoint/cross_attention/op80:mul/dot_general"}
  %splash.4 = bf16[40,8192,64]{2,1,0} custom-call(%q, %k, %v), custom_call_target="tpu_custom_call", metadata={op_name="jit(train_x)/jvp(fwd)/checkpoint/cross_attention/op84:fused_attention/block_sparse_attention/vmap(splash_mha_fwd_residuals)/pallas_call"}
  %splash.5 = bf16[40,8192,64]{2,1,0} custom-call(%q, %k, %v), custom_call_target="tpu_custom_call", metadata={op_name="jit(train_x)/jvp(fwd)/checkpoint/sliding_attention/op20:fused_attention/window_attention/block_sparse_attention/vmap(splash_mha_fwd_residuals)/pallas_call"}
  %dkv.6 = bf16[40,8192,64]{2,1,0} custom-call(%q, %k, %v), custom_call_target="tpu_custom_call", metadata={op_name="jit(train_x)/transpose(jvp(fwd))/checkpoint/sliding_attention/op20:fused_attention/window_attention/block_sparse_attention/vmap(splash_mha_dkv_no_residuals)/pallas_call"}
  %fusion.7 = bf16[1,8192,2560]{2,1,0} fusion(%c), kind=kLoop, calls=%f7, metadata={op_name="jit(train_x)/jvp(fwd)/checkpoint/sliding_attention/op22:mul/dot_general"}
  %fusion.8 = bf16[1,8192,2560]{2,1,0} fusion(%c), kind=kLoop, calls=%f8, metadata={op_name="jit(train_x)/jvp(fwd)/checkpoint/gmu_like/op61:mul/dot_general"}
'''


def test_the_three_device_time_readers_by_hand(monkeypatch):
    """Own time by instruction under each scope: the GMU's products and its gate
    (forward and made again), the cross layer's projection and kernel, and for
    the roofline share the window rule's kernels alone (its inner scope), not
    the window layer's projections; a scope that only begins alike is left out."""
    from benchmark import program_trace

    def op(name, start_ms, ms):
        return (f"%{name} = bf16[1]{{0}} fusion(%a)", start_ms * 1e6, ms * 1e6, {})

    planes = [("/host:CPU", [("main", [("bench.traced_window", 0.0, 100e6, {})])]),
              ("/device:TPU:0", [
                  ("XLA Ops", [op("fusion.1", 1, 3), op("fusion.2", 5, 2), op("fusion.3", 10, 4), op("splash.4", 15, 6),
                               op("splash.5", 22, 1), op("dkv.6", 24, 3), op("fusion.7", 30, 9), op("fusion.8", 40, 5)]),
                  ("XLA Modules", [("jit_train_x(1)", 0.0, 100e6, {})]),
              ])]
    loads = []
    monkeypatch.setattr(program_trace, "traced_planes", lambda ctx: loads.append(1) or planes)
    compiled = type("Compiled", (), {"as_text": lambda self: HLO})()
    cfg, job = cfg_and_job()
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    run = {"executables": [compiled], "model": phi4flash, "config": cfg, "traffic": job, "peaks": peaks}
    ctx = dict(run)
    assert gmu_ms_per_step.read(ctx) == pytest.approx(3 + 2)
    assert kept_kv_attention_ms_per_step.read(ctx) == pytest.approx(4 + 6)
    least = attention_roofline_share.least_seconds(phi4flash.window_attention_flops(cfg, job),
                                                   phi4flash.window_attention_bytes(cfg, job), peaks)
    assert window_attention_roofline_share.read(ctx) == pytest.approx(100.0 * least / 4e-3)
    assert 0 < window_attention_roofline_share.read(ctx) < 100.0
    assert loads == [1], "a run's readers share `ctx`: one table of the trace"
    assert window_attention_roofline_share.read(dict(run, model=object())) is None
    for reader in (gmu_ms_per_step, kept_kv_attention_ms_per_step, window_attention_roofline_share):
        assert reader.read(dict(run, executables=[])) is None
    monkeypatch.setattr(program_trace, "traced_planes", lambda ctx: None)
    for reader in (gmu_ms_per_step, kept_kv_attention_ms_per_step, window_attention_roofline_share):
        assert reader.read(dict(run)) is None


def test_the_pairs_ratio_reads_the_programs_counters(monkeypatch):
    from benchmark import program_trace

    class Monitor:
        def __init__(self, counted):
            self.counted = counted

        def counter_values(self):
            return self.counted

    monkeypatch.setattr(program_trace, "program_monitor", lambda: Monitor(
        {"lowering.window_pairs_visited": 40 * 31 * 512 * 512 * 2, "lowering.window_pairs_allowed": 40 * 4063488 * 2}))
    assert window_pairs_visited_over_allowed.read({"traffic": {}}) == pytest.approx(31 * 512 * 512 / 4063488)
    monkeypatch.setattr(program_trace, "program_monitor", lambda: Monitor({}))
    assert window_pairs_visited_over_allowed.read({"traffic": {}}) is None     # a parent without the counters
    assert window_pairs_visited_over_allowed.read({}) is None
