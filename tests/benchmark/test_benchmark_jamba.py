"""PR 47's cell rehearsed tiny on the CPU's virtual mesh, its configuration
against the catalog row, its arithmetic against hand counts, and its three
per-layer readers on hand-built inputs.

The rehearsal builds on `tiny_root` of test_benchmark_rehearsal.py: the
cell's configuration and traffic files are written, cut down, into the same
scratch root.  As there, no number of a CPU run means anything.  Entries of
`BENCHMARK.json` are found by name, never by position: a later PR appends after
them.
"""
import json
import os
import re

import pytest

from benchmark import manifest as mf
from benchmark.metrics import (attention_roofline_share, ssm_ms_per_step, ssm_scan_roofline_share,
                               ssm_state_decay_mean)
from benchmark.models import jamba, lfm2

from test_benchmark_rehearsal import check_line, run_cell, tiny_root  # noqa: F401

CELL = "ai21-jamba2-3b.train-ssm-fsdp4"
CONFIG = "benchmark/configs/ai21-jamba2-3b.json"
TRAFFIC = "benchmark/traffic/train-ssm-fsdp4.json"
#: the per-layer metrics this cell brought: each lists it, none is pinned to it
OWN_METRICS = ("ssm_ms_per_step", "ssm_scan_roofline_share", "ssm_state_decay_mean")
TINY_NEW = {
    CONFIG: dict(hidden_size=64, intermediate_size=96, mamba_dt_rank=4, num_attention_heads=4, vocab_size=96,
                 num_hidden_layers=4, attn_layer_period=4, attn_layer_offset=2,
                 layer_types=["mamba", "mamba", "full_attention", "mamba"]),
    TRAFFIC: dict(seq_len=44, batch_per_chip=1, ring=4, trace_seconds=0.8),
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
    monkeypatch.setattr(jamba, "STAGE_CHANNELS", 64)
    return tiny_root


@pytest.mark.parametrize("trace", [0, 1])
def test_the_cell_rehearsed_tiny_on_the_virtual_mesh(tiny_root_with_the_cell, trace, capsys):
    result = run_cell(tiny_root_with_the_cell, CELL, trace, 2)
    check_line(result, CELL, trace)
    assert result["device"]["count"] == 4
    lines = [json.loads(line) for line in capsys.readouterr().out.splitlines() if line.startswith('{"info"')]
    stages = [line for line in lines if line["info"] == "reference_stages"]
    assert len(stages) == 1
    assert stages[0]["scan_error"] <= jamba.SCAN_RTOL and stages[0]["scan_error"] < 0.2 * stages[0]["scan_error_bf16_state"]
    assert stages[0]["conv_error"] <= jamba.CONV_RTOL and stages[0]["attention_error"] <= jamba.ATTENTION_RTOL
    assert stages[0]["qk_error"] <= jamba.QK_RTOL and stages[0]["inner_error"] <= jamba.INNER_RTOL
    if trace:  # the program's records, no device needed
        assert 0.2 < result["metrics"]["ssm_state_decay_mean"]["value"] < 1.0
        assert result["metrics"]["recompiles_in_window"]["value"] == 0


def test_the_manifest_holds_the_configuration_and_the_cell_and_nothing_is_wrong_with_it():
    m = mf.load()
    assert mf.problems(m) == []
    cell = mf.cell(m, CELL)
    assert cell["chips"] == 4 and (cell["config"], cell["traffic"]) == ("ai21-jamba2-3b", "train-ssm-fsdp4")
    config = next(x for x in m["configs"] if x["name"] == cell["config"])
    for text in (cell["why"], config["why"], config["source"]):
        assert 1 <= len(text) <= 200 and text.isprintable()
    assert "ZeRO-3" in cell["why"] and "depth 14" in cell["why"]      # the cut's cost, said in the cell
    four = [c["name"] for c in m["workloads"] if c["chips"] == 4]
    assert CELL in four and len(four) <= max(1, len(m["workloads"]) // 4)
    for name in OWN_METRICS:
        metric = next(x for x in m["per_layer"] if x["name"] == name)
        reader = mf.reader_module(name)
        assert CELL in metric["workloads"]
        assert (metric["unit"], metric["better"], metric["source"], metric["layer"], metric["moves"]) == \
            (reader.UNIT, reader.BETTER, reader.SOURCE, reader.LAYER, reader.MOVES)
        assert reader.read({}) is None  # an empty context (a parent without the scopes): nothing, and no error
    reported = {x["name"] for x in mf.metrics_of(m, CELL, "per_layer")}
    assert set(OWN_METRICS) | {"model_flops_util", "peak_hbm_gb", "update_ms_per_step", "device_idle_share",
                               "dispatch_ms_per_step", "recompiles_in_window", "collective_time_share",
                               "collective_exposed_share",
                               # accepted readers whose scopes this step holds: the one attention (not inside a
                               # `while`) and what every layer's `recompute_scope` makes again
                               "flash_attention_ms_per_step", "recompute_ms_per_step"} <= reported
    # the generic readers that misread a step with `while`s in it (PERF.md, defect 4a)
    assert not reported & {"fwd_ms_per_step", "bwd_ms_per_step", "scoped_time_share", "device_roofline_share"}
    assert {x["name"] for x in mf.metrics_of(m, CELL, "end_to_end")} == {"train_samples_per_s", "setup_s"}


def test_the_configuration_keeps_every_published_number_but_the_depth():
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog beside the model-configs guide here")
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "AI21-Jamba2-3B")
    cfg = mf.read_json(CONFIG)
    differs = sorted(k for k, v in row["config"].items() if cfg.get(k, "absent") != v)
    assert differs == cfg["reduced"] == ["num_hidden_layers"]
    assert cfg["reduced_from"] == {"num_hidden_layers": row["config"]["num_hidden_layers"]} == {"num_hidden_layers": 28}
    # the widths, by name: none is cut
    assert (cfg["hidden_size"], cfg["intermediate_size"], cfg["mamba_expand"], cfg["mamba_d_state"], cfg["mamba_dt_rank"],
            cfg["mamba_d_conv"], cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["vocab_size"],
            cfg["num_experts"], cfg["num_experts_per_tok"]) == (2560, 8192, 2, 16, 160, 4, 20, 1, 65536, 1, 1)
    # one whole period: 13 Mamba layers and the attention layer where the published rule puts it
    assert cfg["num_hidden_layers"] == cfg["attn_layer_period"] == len(cfg["layer_types"]) == 14
    assert cfg["layer_types"] == jamba.layer_types(cfg)
    assert [i for i, kind in enumerate(cfg["layer_types"]) if kind == "full_attention"] == [cfg["attn_layer_offset"]] == [7]
    entry = next(c for c in mf.load()["configs"] if c["name"] == "ai21-jamba2-3b")
    assert entry["source"] == row["source_url"] == cfg["source"] and entry["reduced"] == cfg["reduced"]
    assert "4-way ZeRO-3 over the data axis" in cfg["deployment"] and "25.6 GB" in cfg["deployment"]
    assert {"layer_types", "head_dim", "mlp_every_layer", "attention_positions", "mamba_inner_norms", "mamba_biases",
            "initialisation", "mamba_state", "compute_dtype"} <= set(cfg["assumed"])


def test_the_parameter_sum_is_the_files_and_the_issues():
    """The program built from the file has 1599 M parameters (the issue's sum),
    counted from its own shapes; the file states the same number, and so does
    the module's formula."""
    import numpy as np

    import paddle_tpu as fluid

    cfg, job = cfg_and_job()
    job = {k: v for k, v in job.items() if not k.startswith("mesh_")}
    with fluid.unique_name.guard():
        main = jamba.build(cfg, dict(job, seq_len=64))[0]
    sizes = {p.name: int(np.prod(p.shape)) for p in main.all_parameters()}
    total = sum(sizes.values())
    assert total == cfg["parameters"] == jamba.parameters(cfg) and abs(total - 1598.6e6) < 0.1e6
    assert abs(16 * total / 1e9 - 25.6) < 0.05                           # 16 bytes a parameter
    mamba = sum(n for name, n in sizes.items() if name.startswith("lm.l0.mamba."))
    attention = sum(n for name, n in sizes.items() if name.startswith("lm.l7.attn."))
    mlp = sum(n for name, n in sizes.items() if name.startswith("lm.l0.ffn."))
    assert (round(mamba / 1e6, 2), round(attention / 1e6, 2), round(mlp / 1e6, 2)) == (41.24, 13.76, 62.91)
    assert sizes["lm.tok_emb"] == 65536 * 2560 and "lm.head.w" not in sizes           # tied
    assert sizes["lm.l0.mamba.in.w"] == 2560 * 10240 and sizes["lm.l0.mamba.a_log"] == 5120 * 16
    assert sizes["lm.l7.attn.k.w"] == sizes["lm.l7.attn.v.w"] == 2560 * 128            # one key/value head


def test_the_departures_are_the_docstrings_word_for_word():
    listed = jamba.__doc__.split("word for word):")[1]
    items = [re.sub(r"\s+", " ", d.strip().rstrip(";.")) for d in listed.split("  * ")[1:]]
    assert items == mf.read_json(CONFIG)["departures"]
    assert len(items) == 4


def test_the_traffic_is_the_issues():
    job = mf.read_json(TRAFFIC)
    assert (job["kind"], job["mesh_shape"], job["mesh_axes"], job["learning_rate"], job["lr_warmup_steps"],
            job["lr_warmup_start"]) == ("train", [4], ["dp"], 3e-4, 200, 1e-6)
    assert (job["adam_beta1"], job["adam_beta2"], job["adam_epsilon"]) == (0.9, 0.95, 1e-8)
    assert (job["ring"], job["loader_capacity"], job["max_inflight"], job["log_period"], job["warmup_steps"],
            job["trace_seconds"]) == (64, 2, 2, 8, 4, 2.5)
    assert (job["seq_len"], job["batch_per_chip"]) in ((8192, 1), (4096, 2))     # 32768 tokens a step either way
    assert "8192" in job["what"] and "4096" in job["what"]                      # both plans are written down


# -- the arithmetic kept with the benchmark ------------------------------------

def cfg_and_job():
    return mf.read_json(CONFIG), mf.read_json(TRAFFIC)


def test_selective_scan_flops_and_bytes_by_hand():
    tiny = dict(hidden_size=8, mamba_expand=2, mamba_d_state=4, mamba_dt_rank=2,
                layer_types=["mamba", "full_attention", "mamba"])
    job = dict(seq_len=128, batch_per_chip=5)
    # a token of a layer: 16 channels x (7 operations x 4 state elements + 6), forward; backward twice that
    assert jamba.selective_scan_flops(tiny, job) == 3 * 2 * 5 * 128 * 16 * (7 * 4 + 6)
    # a token: xs, dt and y in bf16 (16 channels each), B and C in bf16 (4 each); forward, and twice more backward
    assert jamba.selective_scan_bytes(tiny, job) == 3 * (3 * 16 * 2 + 2 * 4 * 2) * 5 * 128 * 2
    cfg, job = cfg_and_job()
    tokens = job["batch_per_chip"] * job["seq_len"]
    flops, moved = jamba.selective_scan_flops(cfg, job), jamba.selective_scan_bytes(cfg, job)
    assert flops == 3 * 13 * tokens * 5120 * 118 and moved == 3 * 30784 * tokens * 13
    assert abs(moved / 3 / 13 / tokens / 1e3 - 30.8) < 0.1               # ~31 KB a token a layer forward
    # the bytes decide against the matrix unit's peak, which is not the vector unit's: 12.0 ms a step against 0.98
    least = attention_roofline_share.least_seconds(flops, moved, {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9})
    assert least == pytest.approx(moved / 819e9) and 11e-3 < least < 13e-3


def test_flops_per_sample_at_the_published_sizes():
    cfg, job = cfg_and_job()
    d, seq = 2560, job["seq_len"]
    mamba = 2 * d * 10240 + 2 * 5120 * 192 + 2 * 160 * 5120 + 2 * 5120 * d
    attention = 2 * d * 2560 + 2 * 2 * d * 128 + 2 * 2560 * d + 2 * 20 * 2 * 128 * (seq + 1) / 2
    mlp = 3 * 2 * d * 8192
    per_position = 13 * (mamba + mlp) + (attention + mlp) + 2 * d * 65536
    assert jamba.flops_per_sample(cfg, job) == pytest.approx(3.0 * seq * per_position, rel=1e-12)
    # 6 x 1599 M parameters a token, less the scan's own and plus the attention's products over the causal pairs
    assert abs(jamba.flops_per_sample(cfg, job) / seq / (6 * 1598.6e6) - 1.0) < 0.02
    assert 0.09 < 2 * d * 65536 / per_position < 0.11                     # the head: 10% at depth 14 (5.5% at 28)


# -- the readers -----------------------------------------------------------------

def test_ssm_state_decay_mean_reads_the_windows_logged_steps():
    def record(step, decay, largest=(1.0, 2.0)):
        return {"kind": "ssm_state", "pipeline_step": step, "decay_mean": list(decay), "dt_mean": [0.05, 0.05],
                "state_abs_max": list(largest), "worst_layer": 1}

    records = [record(0, [0.1, 0.1]), {"kind": "kda_state", "pipeline_step": 8, "decay_mean": [0.0]},
               record(8, [0.8, 0.6]), record(16, [0.9, 0.7]), record(24, [0.7, 0.7])]
    assert ssm_state_decay_mean.decay_mean(records, 4) == pytest.approx(0.7)     # step 0 is warm-up; means 0.7, 0.8, 0.7
    assert ssm_state_decay_mean.decay_mean([], 4) is None
    assert ssm_state_decay_mean.decay_mean([{"kind": "kda_state", "pipeline_step": 8}], 4) is None
    with pytest.raises(AssertionError, match="largest"):
        ssm_state_decay_mean.decay_mean(records + [record(32, [0.5, 0.5], largest=(1.0, float("inf")))], 4)
    assert ssm_state_decay_mean.read({"traffic": {}}) is None


HLO = '''
  %fusion.1 = bf16[1,8192,10240]{2,1,0} fusion(%a, %w), kind=kOutput, calls=%f1, metadata={op_name="jit(train_x)/jvp(fwd)/checkpoint/mamba/op9:mul/dot_general"}
  %fusion.2 = f32[1,128,16,5120]{3,2,1,0} fusion(%q, %k), kind=kLoop, calls=%f2, metadata={op_name="jit(train_x)/jvp(fwd)/checkpoint/mamba_2/selective_scan/op30:selective_scan/shard_map/selective_scan/exp"}
  %while.3 = (s32[], f32[1,16,5120]{2,1,0}) while(%t), condition=%c3, body=%b3, metadata={op_name="jit(train_x)/transpose(jvp(fwd))/checkpoint/rematted_computation/mamba_2/selective_scan/op30:selective_scan/shard_map/selective_scan/while"}
  %fusion.4 = f32[1,128,16,5120]{3,2,1,0} fusion(%s, %p), kind=kLoop, calls=%f4, metadata={op_name="jit(train_x)/transpose(jvp(fwd))/checkpoint/rematted_computation/mamba_2/selective_scan/op30:selective_scan/shard_map/selective_scan/while/body/checkpoint/mul"}
  %fusion.5 = bf16[1,8192,16]{2,1,0} fusion(%c), kind=kLoop, calls=%f5, metadata={op_name="jit(train_x)/jvp(fwd)/checkpoint/mamba_2/op28:rms_norm/mul"}
  %fusion.6 = bf16[1,8192,5120]{2,1,0} fusion(%c), kind=kLoop, calls=%f6, metadata={op_name="jit(train_x)/jvp(fwd)/checkpoint/mamba_2/selective_scan/op30:selective_scan/shard_map/convert_element_type"}
  %fusion.7 = bf16[1,8192,2560]{2,1,0} fusion(%c), kind=kLoop, calls=%f7, metadata={op_name="jit(train_x)/jvp(fwd)/checkpoint/op60:mul/dot_general"}
  %fusion.8 = bf16[1,8192,2560]{2,1,0} fusion(%c), kind=kLoop, calls=%f8, metadata={op_name="jit(train_x)/jvp(fwd)/checkpoint/mamba_like/op61:mul/dot_general"}
'''


class _Compiled:
    def as_text(self):
        return HLO


def test_the_two_device_time_readers_by_hand(monkeypatch):
    """Own time by instruction: the `while` of the chunks' scan encloses its
    body's fusion on the `XLA Ops` line and is counted by what is left of it;
    sibling scopes are numbered (mamba, mamba_2); a scope that only begins alike
    is left out; the roofline share reads the scope INSIDE the op, not the
    program's scope of the same name round it (the cast at the op's edge is the
    mixer's time, not the scan's)."""
    from benchmark import program_trace

    def op(name, start_ms, ms):
        return (f"%{name} = bf16[1]{{0}} fusion(%a)", start_ms * 1e6, ms * 1e6, {})

    planes = [("/host:CPU", [("main", [("bench.traced_window", 0.0, 100e6, {})])]),
              ("/device:TPU:0", [
                  ("XLA Ops", [op("fusion.1", 1, 3), op("fusion.2", 5, 2), op("while.3", 10, 20), op("fusion.4", 11, 4),
                               op("fusion.4", 16, 4), op("fusion.5", 40, 1), op("fusion.6", 42, 6), op("fusion.7", 50, 9),
                               op("fusion.8", 60, 5)]),
                  ("XLA Modules", [("jit_train_x(1)", 0.0, 100e6, {})]),
              ])]
    monkeypatch.setattr(program_trace, "traced_planes", lambda ctx: planes)
    cfg, job = cfg_and_job()
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    run = {"executables": [_Compiled()], "model": jamba, "config": cfg, "traffic": job, "peaks": peaks}
    ctx, loads = dict(run), []
    monkeypatch.setattr(program_trace, "traced_planes", lambda ctx: loads.append(1) or planes)
    assert ssm_ms_per_step.read(ctx) == pytest.approx(3 + 2 + (20 - 8) + 8 + 1 + 6)
    scan_ms = 2 + (20 - 8) + 8
    least = attention_roofline_share.least_seconds(jamba.selective_scan_flops(cfg, job), jamba.selective_scan_bytes(cfg, job), peaks)
    assert ssm_scan_roofline_share.read(ctx) == pytest.approx(100.0 * least / (scan_ms / 1e3))
    assert ssm_scan_roofline_share.read(ctx) < 100.0
    assert loads == [1], "a run's readers share `ctx`, and the second reads the table the first made of the trace"
    # a run without executables, a trace or the scope, or a model without the arithmetic: nothing
    assert ssm_scan_roofline_share.read(dict(run, model=object())) is None
    for reader in (ssm_ms_per_step, ssm_scan_roofline_share):
        assert reader.read(dict(run, executables=[])) is None
    monkeypatch.setattr(program_trace, "traced_planes", lambda ctx: None)
    for reader in (ssm_ms_per_step, ssm_scan_roofline_share):
        assert reader.read(dict(run)) is None


HLO_JOINED = HLO + '''
  %splash.9 = bf16[20,8192,128]{2,1,0} custom-call(%q, %k, %v), custom_call_target="tpu_custom_call", metadata={op_name="jit(train_x)/jvp(fwd)/checkpoint/op184:fused_attention/shard_map/block_sparse_attention/vmap(splash_mha_fwd_residuals)/splash_mha_fwd_residuals/pallas_call"}
  %splash.10 = bf16[20,8192,128]{2,1,0} custom-call(%q, %k, %v), custom_call_target="tpu_custom_call", metadata={op_name="jit(train_x)/transpose(jvp(fwd))/jvp(fwd)/checkpoint/rematted_computation/op184:fused_attention/shard_map/block_sparse_attention/vmap(splash_mha_fwd_residuals)/splash_mha_fwd_residuals/pallas_call"}
  %dkv.11 = bf16[1,8192,128]{2,1,0} custom-call(%q, %k, %v), custom_call_target="tpu_custom_call", metadata={op_name="jit(train_x)/transpose(jvp(fwd))/jvp(fwd)/checkpoint/op184:fused_attention/shard_map/block_sparse_attention/vmap(splash_mha_dkv_no_residuals)/splash_mha_dkv_no_residuals/pallas_call"}
'''


@pytest.mark.parametrize("metric,want_ms", [
    ("flash_attention_ms_per_step", 2 + 2 + 5),          # the kernel forward, made again, and backward: every event
    ("recompute_ms_per_step", (20 - 8) + 8 + 2),         # own time of what `rematted_computation` holds: the scan's `while` and the kernel
])
def test_the_two_accepted_readers_the_cell_joined_read_its_step(metric, want_ms, monkeypatch, capsys):
    """The cell lists two readers earlier cells brought: the attention's scope
    stands under a `shard_map` here and is in no `while`; every layer is a
    `recompute_scope`, whose `jax.checkpoint` names what backward makes again."""
    from benchmark import program_trace

    def op(name, start_ms, ms):
        return (f"%{name} = bf16[1]{{0}} fusion(%a)", start_ms * 1e6, ms * 1e6, {})

    planes = [("/host:CPU", [("main", [("bench.traced_window", 0.0, 100e6, {})])]),
              ("/device:TPU:0", [
                  ("XLA Ops", [op("fusion.1", 1, 3), op("splash.9", 5, 2), op("while.3", 10, 20), op("fusion.4", 11, 4),
                               op("fusion.4", 16, 4), op("splash.10", 40, 2), op("dkv.11", 43, 5), op("fusion.7", 50, 9)]),
                  ("XLA Modules", [("jit_train_x(1)", 0.0, 100e6, {})]),
              ])]
    monkeypatch.setattr(program_trace, "traced_planes", lambda ctx: planes)
    compiled = type("Compiled", (), {"as_text": lambda self: HLO_JOINED})()
    m = mf.load()
    assert CELL in next(x for x in m["per_layer"] if x["name"] == metric)["workloads"]
    assert mf.reader_module(metric).read({"executables": [compiled]}) == pytest.approx(want_ms)
    capsys.readouterr()   # `recompute_ms_per_step` prints its `loop_device_time` line
