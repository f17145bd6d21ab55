"""PR 60's cell rehearsed tiny on the CPU's virtual mesh, its configuration
against the catalog row, its arithmetic against hand counts, and its five
per-layer readers on hand-built inputs.

The rehearsal builds on `tiny_root` of test_benchmark_rehearsal.py: the cell's
configuration and traffic files are written, cut down, into the same scratch
root.  As there, no number of a CPU run means anything.  Entries of
`BENCHMARK.json` are found by name, never by position: a later PR appends after
them.
"""
import json
import os
import re

import pytest

from benchmark import manifest as mf, peaks
from benchmark.metrics import (latent_expert_gemm_roofline_share, latent_experts_ms_per_step, ssd_ms_per_step,
                               ssd_scan_roofline_share, ssd_state_decay_mean)
from benchmark.models import lfm2, nemotron_h

from test_benchmark_rehearsal import check_line, run_cell, tiny_root  # noqa: F401

CELL = "nemotron-3-super-120b-a12b.train-ssd-fsdp4"
CONFIG = "benchmark/configs/nemotron-3-super-120b-a12b.json"
TRAFFIC = "benchmark/traffic/train-ssd-fsdp4.json"
REDUCED = ["num_hidden_layers", "hybrid_override_pattern", "n_routed_experts", "vocab_size", "num_nextn_predict_layers"]
#: the per-layer metrics this cell brought: each lists it alone
OWN_METRICS = ("ssd_ms_per_step", "ssd_scan_roofline_share", "latent_experts_ms_per_step",
               "latent_expert_gemm_roofline_share", "ssd_state_decay_mean")
TINY_NEW = {
    CONFIG: dict(hidden_size=64, num_attention_heads=4, num_key_value_heads=2, head_dim=16, vocab_size=96,
                 mamba_num_heads=8, mamba_head_dim=8, ssm_state_size=16, n_groups=2, chunk_size=16,
                 moe_latent_size=32, moe_intermediate_size=24, moe_shared_expert_intermediate_size=48,
                 num_routed_experts=32, n_routed_experts=8, num_experts_per_tok=4,
                 num_hidden_layers=5, hybrid_override_pattern="MEM*E", conv_taps_bound=4.0,
                 layer_types=["mamba2", "feed_forward", "mamba2", "full_attention", "feed_forward"]),
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
    monkeypatch.setattr(nemotron_h, "STAGE_CHANNELS", 64)
    monkeypatch.setattr(nemotron_h, "STAGE_HEADS", 8)
    monkeypatch.setattr(nemotron_h, "STAGE_TOKENS", 32)
    monkeypatch.setattr(nemotron_h, "EXPERTS_SAMPLE", 64)
    # a quarter of the experts held and four layers of near-tied scores at 64 wide: a flip meets a held expert often
    monkeypatch.setattr(nemotron_h, "OTHER_CHOICE_MAX", 0.5)
    return tiny_root


@pytest.mark.parametrize("trace", [0, 1])
def test_the_cell_rehearsed_tiny_on_the_virtual_mesh(tiny_root_with_the_cell, trace, capsys):
    result = run_cell(tiny_root_with_the_cell, CELL, trace, 6)   # a step through the interpreted grouped kernels is ~0.7 s here
    check_line(result, CELL, trace)
    assert result["device"]["count"] == 4
    lines = [json.loads(line) for line in capsys.readouterr().out.splitlines() if line.startswith('{"info"')]
    stages = [line for line in lines if line["info"] == "reference_routing"]
    assert len(stages) == 1
    found = stages[0]
    assert found["scan_error"] <= nemotron_h.SCAN_RTOL and found["scan_error_deep"] <= nemotron_h.SCAN_DEEP_RTOL
    assert max(found["scan_error"], found["scan_error_deep"]) < 0.2 * found["scan_error_bf16_state"]
    assert found["scan_state_error"] <= nemotron_h.SCAN_STATE_RTOL < 0.2 * found["scan_state_error_bf16_state"]
    assert found["conv_error"] <= nemotron_h.CONV_RTOL and found["attention_error"] <= nemotron_h.ATTENTION_RTOL
    assert found["experts_error"] <= nemotron_h.EXPERTS_RTOL < found["experts_error_relu"]
    assert found["router_choice_differs"] == found["biases_differ"] == 0 and found["bias_moved"] > 0
    if trace:  # the program's records, no device needed
        assert 0.0 < result["metrics"]["ssd_state_decay_mean"]["value"] < 1.0
        assert result["metrics"]["recompiles_in_window"]["value"] == 0
        assert result["metrics"]["router_bias_moved_share"]["value"] > 0
        assert result["metrics"]["recompute_kept_bytes_share"]["value"] == 100.0      # the CPU reports no limit: all is kept


def test_the_manifest_holds_the_configuration_and_the_cell_and_nothing_is_wrong_with_it():
    m = mf.load()
    assert mf.problems(m) == []
    cell = mf.cell(m, CELL)
    assert cell["chips"] == 4 and (cell["config"], cell["traffic"]) == ("nemotron-3-super-120b-a12b", "train-ssd-fsdp4")
    config = next(x for x in m["configs"] if x["name"] == cell["config"])
    for text in (cell["why"], config["why"], config["source"]):
        assert 1 <= len(text) <= 200 and text.isprintable()
    assert "ZeRO-3" in cell["why"] and "1/64" in cell["why"]      # the cut's cost, said in the cell
    four = [c["name"] for c in m["workloads"] if c["chips"] == 4]
    assert CELL in four and len(four) == 3 <= max(1, len(m["workloads"]) // 4) and len(m["workloads"]) == 14
    for name in OWN_METRICS:
        metric = next(x for x in m["per_layer"] if x["name"] == name)
        reader = mf.reader_module(name)
        assert metric["workloads"] == [CELL]
        assert (metric["unit"], metric["better"], metric["source"], metric["layer"], metric["moves"]) == \
            (reader.UNIT, reader.BETTER, reader.SOURCE, reader.LAYER, reader.MOVES)
        assert reader.read({}) is None  # an empty context (a parent without the scopes): nothing, and no error
    reported = {x["name"] for x in mf.metrics_of(m, CELL, "per_layer")}
    assert set(OWN_METRICS) | {"model_flops_util", "peak_hbm_gb", "update_ms_per_step", "device_idle_share",
                               "dispatch_ms_per_step", "recompiles_in_window", "collective_time_share",
                               "collective_exposed_share", "router_bias_moved_share",
                               "flash_attention_ms_per_step", "recompute_ms_per_step"} <= reported
    # the readers pinned to their first cell by a test (PERF.md, defect 13a), and the generic ones that misread a
    # step with `while`s in it (defect 4a)
    assert not reported & {"moe_ms_per_step", "expert_gemm_roofline_share", "expert_load_max_over_mean",
                           "attention_ms_per_step", "attention_roofline_share", "held_expert_rows_share",
                           "fwd_ms_per_step", "bwd_ms_per_step", "scoped_time_share", "device_roofline_share"}
    assert {x["name"] for x in mf.metrics_of(m, CELL, "end_to_end")} == {"train_samples_per_s", "setup_s"}


def test_the_configuration_keeps_every_published_number_but_the_five_reduced():
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog beside the model-configs guide here")
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "NVIDIA-Nemotron-3-Super-120B-A12B-BF16")
    cfg = mf.read_json(CONFIG)
    differs = sorted(k for k, v in row["config"].items() if cfg.get(k, "absent") != v)
    assert differs == sorted(cfg["reduced"]) == sorted(REDUCED)
    assert cfg["reduced_from"] == {k: row["config"][k] for k in REDUCED}
    # the widths, by name: none is cut
    assert (cfg["hidden_size"], cfg["mamba_num_heads"], cfg["mamba_head_dim"], cfg["ssm_state_size"], cfg["n_groups"],
            cfg["conv_kernel"], cfg["chunk_size"], cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"],
            cfg["moe_latent_size"], cfg["moe_intermediate_size"], cfg["moe_shared_expert_intermediate_size"],
            cfg["num_experts_per_tok"], cfg["norm_eps"]) == (4096, 128, 64, 128, 8, 4, 128, 32, 2, 128, 1024, 2688, 5376, 22, 1e-5)
    # one whole period at the published 5 : 5 : 1, the published layers 31 to 41
    assert cfg["hybrid_override_pattern"] == row["config"]["hybrid_override_pattern"][31:42] == "MEMEM*EMEME"
    assert cfg["num_hidden_layers"] == len(cfg["layer_types"]) == 11 and cfg["layer_types"] == nemotron_h.layer_types(cfg)
    assert (cfg["n_routed_experts"], cfg["num_routed_experts"], cfg["experts_held_first"]) == (32, 512, 0)
    entry = next(c for c in mf.load()["configs"] if c["name"] == "nemotron-3-super-120b-a12b")
    assert entry["source"] == row["source_url"] == cfg["source"] and entry["reduced"] == cfg["reduced"] == REDUCED
    for said in ("16 four-chip v5e hosts share each layer", "ZeRO-3", "eight ways by row", "77 layers lie on further hosts"):
        assert said in cfg["deployment"]
    assert {"layer_types", "attention_positions", "gated_norm", "router", "expert_bias", "latent_experts", "shared_expert",
            "initialisation", "num_nextn_predict_layers", "mamba2_state"} <= set(cfg["assumed"])
    # the departures are the module's, word for word
    doc = nemotron_h.__doc__.split("Departures of the program")[1]
    assert cfg["departures"] == [d.rstrip(";.") for d in re.findall(r"^  \* (.*)$", doc, re.M)]


def test_the_parameters_are_the_hand_count_and_the_published_model_counts_120b_a12b():
    cfg = mf.read_json(CONFIG)
    mamba = 4096 * (8192 + 10240 + 128) + 8192 * 4096 + 10240 * 5 + 3 * 128 + 8192 + 4096
    attention = 2 * 4096 * 4096 + 2 * 4096 * 256 + 4096
    beside = 4096 * 512 + 2 * 4096 * 1024 + 2 * 4096 * 5376 + 4096
    an_expert = 2 * 1024 * 2688
    ends = 2 * 16384 * 4096 + 4096
    by_hand = 5 * mamba + attention + 5 * (beside + 32 * an_expert) + ends
    assert nemotron_h.parameters(cfg) == cfg["parameters"] == by_hand and round(by_hand / 1e6, 1) == 1871.5
    assert (round(mamba / 1e6, 2), round(attention / 1e6, 2), round(beside / 1e6, 2), round(an_expert / 1e6, 3)) == \
        (109.64, 35.66, 54.53, 5.505)
    whole, active = nemotron_h.published_parameters(cfg)
    assert whole == 40 * mamba + 8 * attention + 40 * (beside + 512 * an_expert) + 2 * 131072 * 4096 + 4096
    assert (round(whole / 1e9, 1), round(active / 1e9, 1)) == (120.7, 12.2)


def test_the_work_functions_count_the_mathematics():
    cfg, job = mf.read_json(CONFIG), mf.read_json(TRAFFIC)
    # the recurrence: a token and head, 2 P N for the update and 2 P N for the read; x 3 for backward; five layers
    assert nemotron_h.ssd_recurrence_flops(cfg, job) == 3 * 5 * 8192 * 128 * (2 * 64 * 128 + 2 * 64 * 128)
    assert nemotron_h.ssd_recurrence_bytes(cfg, job) == 3 * 5 * 8192 * (2 * 8192 * 2 + 2 * 1024 * 2 + 128 * 2)
    # nothing of the chunk length in either: another chunk is the same work
    assert nemotron_h.ssd_recurrence_flops({**cfg, "chunk_size": 64}, job) == nemotron_h.ssd_recurrence_flops(cfg, job)
    rows = 8192 * 22 * 32 / 512
    assert nemotron_h.latent_expert_gemm_flops(cfg, job) == 3 * 5 * rows * 2 * 2 * 1024 * 2688
    assert nemotron_h.latent_expert_gemm_flops(cfg, job, 100.0) == 3 * 5 * 100.0 * 2 * 2 * 1024 * 2688
    assert nemotron_h.latent_expert_gemm_bytes(cfg, job, 0.0) == 5 * 32 * 2 * 1024 * 2688 * 8
    least = max(nemotron_h.ssd_recurrence_flops(cfg, job) / peaks.PEAKS["TPU v5 lite"]["bf16_flops_per_s"],
                nemotron_h.ssd_recurrence_bytes(cfg, job) / peaks.PEAKS["TPU v5 lite"]["hbm_bytes_per_s"])
    assert 4e-3 < least < 7e-3      # the bytes decide: 5.6 ms a step
    # the model's own operations a sequence: about 2.1 GFLOP a token forward by the chip's count
    assert 1.9e9 < nemotron_h.flops_per_sample(cfg, job) / 3 / 8192 < 2.3e9


def test_the_readers_on_a_recorded_small_trace():
    spent = {"fusion.1": 2.0, "fusion.2": 3.0, "gmm.3": 0.5, "fusion.4": 7.0, "while.5": 0.25}
    names = {"fusion.1": "jit(train)/lm/mamba2/op7:ssd_scan/ssd_scan/dot_general",
             "fusion.2": "jit(train)/transpose(jvp(lm))/mamba2_1/op31:ssd_scan/ssd_scan/mul",
             "while.5": "jit(train)/lm/mamba2/op7:ssd_scan/ssd_scan/while",
             "gmm.3": "jit(train)/lm/latent_experts_1/op40:moe_experts/shard_map/expert_gemm/pallas_call",
             "fusion.4": "jit(train)/lm/latent_experts/op12:mul/dot_general"}
    cfg, job = mf.read_json(CONFIG), mf.read_json(TRAFFIC)
    ctx = {"executables": ["stub"], "ssm_own_ms": (spent, names), "model": nemotron_h, "config": cfg, "traffic": job,
           "peaks": peaks.PEAKS["TPU v5 lite"]}
    assert ssd_ms_per_step.read(ctx) == 5.25
    assert latent_experts_ms_per_step.read(ctx) == 7.5
    share = ssd_scan_roofline_share.read(ctx)
    least = nemotron_h.ssd_recurrence_bytes(cfg, job) / peaks.PEAKS["TPU v5 lite"]["hbm_bytes_per_s"]
    assert share == pytest.approx(100.0 * least / 5.25e-3)
    assert latent_expert_gemm_roofline_share.read(ctx) > 0      # no step logged: the uniform router's share
    assert latent_expert_gemm_roofline_share.held_rows_a_layer(
        [{"kind": "moe_routing", "pipeline_step": 8, "held_rows_share": [0.05, 0.07]},
         {"kind": "moe_routing", "pipeline_step": 2, "held_rows_share": [0.5, 0.5]}], 4, 1000) == pytest.approx(60.0)
    records = [{"kind": "ssd_state", "pipeline_step": s, "decay_mean": [0.9, 0.8], "dt_mean": [0.01, 0.02],
                "state_abs_max": [1.0, 2.0], "worst_layer": 1} for s in (0, 8, 16)]
    assert ssd_state_decay_mean.decay_mean(records, 4) == pytest.approx(0.85)
    assert ssd_state_decay_mean.decay_mean([], 4) is None
    for reader in (ssd_ms_per_step, ssd_scan_roofline_share, latent_experts_ms_per_step, latent_expert_gemm_roofline_share):
        assert reader.read({"executables": ["stub"], "ssm_own_ms": None, "model": nemotron_h}) is None
