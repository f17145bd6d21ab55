"""PR 26's two cells rehearsed tiny on the CPU, and its three per-layer
readers and the grouped-GEMM arithmetic on hand-built inputs.

The rehearsal builds on `tiny_root` of test_benchmark_rehearsal.py: the new
cells' configuration and traffic files are written, cut down, into the same
scratch root.  As there, no number of a CPU run means anything.
"""
import json
import os
import re

import pytest

from benchmark import manifest as mf
from benchmark.metrics import (expert_gemm_roofline_share, expert_load_max_over_mean,
                               moe_ms_per_step)
from benchmark.models import olmoe

from test_benchmark_rehearsal import check_line, run_cell, tiny_root  # noqa: F401

TINY_NEW = {
    "benchmark/configs/olmoe-1b-7b.json": dict(
        hidden_size=64, num_hidden_layers=2, num_attention_heads=4, intermediate_size=32,
        num_experts=8, num_experts_per_tok=2, vocab_size=96),
    "benchmark/traffic/train-s4096.json": dict(seq_len=32, batch_per_chip=4, trace_seconds=0.8),
    "benchmark/traffic/pretrain-s512.json": dict(seq_len=32, batch_per_chip=8, trace_seconds=0.8),
}


@pytest.fixture
def tiny_root_with_new_cells(tiny_root):  # noqa: F811
    for path, over in TINY_NEW.items():
        data = mf.read_json(path)
        data.update(over)
        os.makedirs(os.path.dirname(os.path.join(tiny_root, path)), exist_ok=True)
        with open(os.path.join(tiny_root, path), "w") as f:
            json.dump(data, f)
    return tiny_root


@pytest.mark.parametrize("cell,trace", [
    ("olmoe-1b-7b.train-s4096", 0),
    ("olmoe-1b-7b.train-s4096", 1),
    ("bert-base.pretrain-s512", 0),
    ("bert-base.pretrain-s512", 1),
], ids=lambda v: str(v))
def test_new_cell_rehearsed_tiny_on_the_cpu(tiny_root_with_new_cells, cell, trace, capsys):
    result = run_cell(tiny_root_with_new_cells, cell, trace, 2)
    check_line(result, cell, trace)
    if cell.startswith("olmoe"):
        routing = [json.loads(line) for line in capsys.readouterr().out.splitlines()
                   if '"reference_routing"' in line]
        assert len(routing) == 1 and routing[0]["routed_differently_above_margin"] == 0
        assert routing[0]["left_out"] <= olmoe.LEFT_OUT_MAX * routing[0]["tokens"]
        if trace:  # the program's counter, no device needed
            assert result["metrics"]["expert_load_max_over_mean"]["value"] >= 1.0


def test_the_manifest_holds_the_new_cells_and_nothing_is_wrong_with_it():
    m = mf.load()
    assert mf.problems(m) == []
    cells = {w["name"]: w for w in m["workloads"]}
    assert cells["olmoe-1b-7b.train-s4096"]["chips"] == cells["bert-base.pretrain-s512"]["chips"] == 1
    for name in ("moe_ms_per_step", "expert_gemm_roofline_share", "expert_load_max_over_mean"):
        metric = next(x for x in m["per_layer"] if x["name"] == name)
        reader = mf.reader_module(name)
        assert metric["workloads"] == ["olmoe-1b-7b.train-s4096"]
        assert (metric["unit"], metric["better"], metric["source"], metric["layer"], metric["moves"]) == \
            (reader.UNIT, reader.BETTER, reader.SOURCE, reader.LAYER, reader.MOVES)


def test_the_configuration_keeps_every_published_number_but_the_two_it_says():
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog beside the model-configs guide here")
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "OLMoE-1B-7B-0125-Instruct")
    cfg = mf.read_json("benchmark/configs/olmoe-1b-7b.json")
    differs = sorted(k for k, v in row["config"].items() if cfg.get(k, "absent") != v)
    assert differs == sorted(cfg["reduced"]) == ["num_hidden_layers", "vocab_size"]
    assert cfg["reduced_from"] == {k: row["config"][k] for k in cfg["reduced"]}
    assert cfg["vocab_size"] * 4 == row["config"]["vocab_size"]
    entry = next(c for c in mf.load()["configs"] if c["name"] == "olmoe-1b-7b")
    assert entry["source"] == row["source_url"] and entry["reduced"] == cfg["reduced"]


def test_the_departures_are_the_docstrings_word_for_word():
    listed = olmoe.__doc__.split("word for word):")[1]
    items = [re.sub(r"\s+", " ", d.strip().rstrip(";.")) for d in listed.split("  * ")[1:]]
    assert items == mf.read_json("benchmark/configs/olmoe-1b-7b.json")["departures"]
    assert len(items) == 6


# -- the arithmetic kept with the benchmark ------------------------------------

CFG = dict(hidden_size=2048, intermediate_size=1024, num_experts=64, num_experts_per_tok=8,
           num_hidden_layers=1, num_attention_heads=16, vocab_size=12576)


def test_flops_per_sample_at_the_published_widths():
    # per token: projections 8 d^2, causal attention 2 seq d, router 2 d E,
    # 8 experts x 3 products x 2 d f, head 2 d V; x3 for backward, x seq
    per_token = (8 * 2048 ** 2 + 2 * 4096 * 2048 + 2 * 2048 * 64
                 + 8 * 6 * 2048 * 1024 + 2 * 2048 * 12576)
    assert olmoe.flops_per_sample(CFG, {"seq_len": 4096}) == 3.0 * per_token * 4096
    assert abs(olmoe.flops_per_sample(CFG, {"seq_len": 4096}) - 2.49e12) < 0.01e12


def test_expert_gemm_flops_and_bytes_by_hand():
    tiny = dict(hidden_size=4, intermediate_size=3, num_experts=2, num_experts_per_tok=2,
                num_hidden_layers=1)
    # 5 tokens x 2 slots = 10 rows; one product 2 x 10 x 4 x 3 = 240; three
    # forward products and two more behind each: x 9
    assert olmoe.expert_gemm_flops(tiny, 5) == 9 * 240
    # one product: rows in 10 x 4, matrices 2 x 4 x 3, rows out 10 x 3 = 94 elements
    assert olmoe.expert_gemm_bytes(tiny, 5, itemsize=2) == 9 * 94 * 2
    assert olmoe.expert_gemm_flops(dict(tiny, num_hidden_layers=3), 5) == 27 * 240
    # the published layer over 16384 tokens: compute-bound on a v5e
    flops, moved = olmoe.expert_gemm_flops(CFG, 16384), olmoe.expert_gemm_bytes(CFG, 16384)
    assert flops == 3 * 16384 * 8 * 3 * 2 * 2048 * 1024
    assert flops / 197e12 > 2 * moved / 819e9


# -- the readers -----------------------------------------------------------------

def test_moe_ms_per_step_sums_the_two_scopes_both_ways():
    trace = {"main_module_runs": 4.0,
             "by_label": {"moe_experts.fwd": 0.080, "moe_experts.bwd": 0.160,
                          "moe_router.fwd": 0.004, "moe_router.bwd": 0.002,
                          "mul.fwd": 0.5, "fused_attention.bwd": 0.3}}
    assert moe_ms_per_step.read({"trace": trace}) == pytest.approx(61.5)
    # a program without the scopes (the parent commit), or no trace: nothing
    assert moe_ms_per_step.read({"trace": {"main_module_runs": 4.0,
                                           "by_label": {"mul.fwd": 0.5}}}) is None
    assert moe_ms_per_step.read({"trace": {}}) is None


def test_expert_load_reads_the_windows_logged_steps():
    def record(step, most, dropped=0):
        return {"kind": "moe_routing", "pipeline_step": step, "load_max_over_mean": most,
                "load_min_over_mean": [0.9], "dropped_tokens": dropped}

    records = [record(0, [9.0]), {"kind": "pipeline_step", "pipeline_step": 8},
               record(8, [1.10, 1.30]), record(16, [1.20, 1.05]), record(24, [1.50, 1.00])]
    # step 0 is warm-up; per step the worst layer: 1.30, 1.20, 1.50
    assert expert_load_max_over_mean.load_max_over_mean(records, 4) == 1.30
    assert expert_load_max_over_mean.load_max_over_mean([], 4) is None
    assert expert_load_max_over_mean.load_max_over_mean(records[:2], 4) is None
    with pytest.raises(AssertionError, match="dropped_tokens"):
        expert_load_max_over_mean.load_max_over_mean(records + [record(32, [1.0], dropped=3)], 4)
    assert expert_load_max_over_mean.read({"traffic": {}}) is None


HLO = '''
  %gmm.3 = bf16[64,32]{1,0} custom-call(%a, %b), custom_call_target="tpu_custom_call", metadata={op_name="jit(train_x)/jvp(fwd)/op23:moe_experts/expert_gemm/jit(gmm)/pallas_call"}
  %tgmm.1 = bf16[8,16,32]{2,1,0} custom-call(%a, %b), custom_call_target="tpu_custom_call", metadata={op_name="jit(train_x)/transpose(jvp(fwd))/op23:moe_experts/expert_gemm/jit(tgmm)/pallas_call"}
  %fusion.9 = bf16[64,16]{1,0} fusion(%c), kind=kLoop, calls=%f, metadata={op_name="jit(train_x)/jvp(fwd)/op23:moe_experts/jit(_take)/gather"}
  %fusion.2 = f32[64,16]{1,0} fusion(%c), kind=kLoop, calls=%g, metadata={op_name="jit(train_x)/jvp(fwd)/op12:mul/dot_general"}
'''


def test_expert_gemm_roofline_share_by_hand():
    instructions = expert_gemm_roofline_share.instructions_under(HLO)
    assert instructions == {"gmm.3", "tgmm.1"}

    def op(name, start_ms, ms):
        return (f"%{name} = bf16[1]{{0}} custom-call(%a)", start_ms * 1e6, ms * 1e6, {})

    window = ("bench.traced_window", 0.0, 100e6, {})
    planes = [("/host:CPU", [("main", [window])]),
              ("/device:TPU:0", [
                  ("XLA Ops", [op("gmm.3", 1, 10), op("tgmm.1", 20, 20), op("fusion.9", 50, 7),
                               op("gmm.3", 95, 10)]),     # half of the last one is past the window
                  ("XLA Modules", [("jit_train_x(1)", 0.0, 50e6, {}), ("jit_train_x(1)", 50e6, 50e6, {})]),
              ])]
    spent = expert_gemm_roofline_share.seconds_per_run(planes, instructions)
    assert spent == pytest.approx((10 + 20 + 5) * 1e-3 / 2)
    assert expert_gemm_roofline_share.seconds_per_run(planes, set()) is None
    peaks = {"bf16_flops_per_s": 100e12, "hbm_bytes_per_s": 1e12}
    assert expert_gemm_roofline_share.least_seconds(1e12, 1e9, peaks) == pytest.approx(0.01)
    assert expert_gemm_roofline_share.least_seconds(1e12, 5e10, peaks) == pytest.approx(0.05)
    # a run without executables, or a model without the arithmetic: nothing
    ctx = {"executables": [], "model": olmoe, "config": CFG, "traffic": {}, "peaks": peaks}
    assert expert_gemm_roofline_share.read(ctx) is None
    assert expert_gemm_roofline_share.read(dict(ctx, executables=[object()], model=object())) is None
