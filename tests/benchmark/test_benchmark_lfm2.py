"""PR 34's cell rehearsed tiny on the CPU, its configuration against the
catalog row, its arithmetic against hand counts, and its five per-layer
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
from benchmark.metrics import (attention_roofline_share, router_bias_moved_share, short_conv_ms_per_step,
                               short_conv_roofline_share)
from benchmark.models import lfm2

from test_benchmark_rehearsal import check_line, run_cell, tiny_root  # noqa: F401

CELL = "lfm2-8b-a1b.train-s8192"
CONFIG = "benchmark/configs/lfm2-8b-a1b.json"
TRAFFIC = "benchmark/traffic/train-s8192.json"
#: the per-layer metrics this cell brought: each lists it, none is pinned to it
OWN_METRICS = ("short_conv_ms_per_step", "short_conv_roofline_share", "router_bias_moved_share",
               "held_experts_ms_per_step", "flash_attention_ms_per_step")
TINY_NEW = {
    CONFIG: dict(hidden_size=64, num_attention_heads=4, num_key_value_heads=2, head_dim=16, intermediate_size=96,
                 moe_intermediate_size=32, num_experts=4, num_routed_experts=16, num_experts_per_tok=2, vocab_size=96),
    TRAFFIC: dict(seq_len=32, batch_per_chip=4, ring=4, trace_seconds=0.8),
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
    assert routing[0]["conv_error"] <= lfm2.CONV_RTOL < routing[0]["conv_error_bf16"]
    assert routing[0]["attention_error"] <= lfm2.ATTENTION_RTOL and routing[0]["qk_error"] <= lfm2.QK_RTOL
    if trace:  # the program's counter, no device needed; the reader's own line carries the held share
        assert 0.0 < result["metrics"]["router_bias_moved_share"]["value"] < 100.0
        assert result["metrics"]["recompiles_in_window"]["value"] == 0
        held = [line for line in lines if line["info"] == "moe_routing"]
        assert len(held) == 1 and len(held[0]["held_rows_share"]) == 4


def test_the_manifest_holds_the_cell_and_nothing_is_wrong_with_it():
    m = mf.load()
    assert mf.problems(m) == []
    cell = mf.cell(m, CELL)
    assert cell["chips"] == 1 and (cell["config"], cell["traffic"]) == ("lfm2-8b-a1b", "train-s8192")
    config = next(x for x in m["configs"] if x["name"] == cell["config"])
    # the driver holds every `why` and `source` to 200 printable characters on one line
    for text in (cell["why"], config["why"], config["source"]):
        assert 1 <= len(text) <= 200 and text.isprintable()
    assert m["configs"][-1] is config and m["workloads"][-1] is cell      # new entries stand last
    for name in OWN_METRICS:
        metric = next(x for x in m["per_layer"] if x["name"] == name)
        reader = mf.reader_module(name)
        assert CELL in metric["workloads"]   # membership: a later cell may join (PERF.md, defect 13a)
        assert (metric["unit"], metric["better"], metric["source"], metric["layer"], metric["moves"]) == \
            (reader.UNIT, reader.BETTER, reader.SOURCE, reader.LAYER, reader.MOVES)
        assert reader.read({}) is None  # an empty context: nothing, and no error
    reported = {x["name"] for x in mf.metrics_of(m, CELL, "per_layer")}
    # its own five, what the train cells share, and the whole-step roofline share, which has all it reads here
    assert set(OWN_METRICS) | {"model_flops_util", "peak_hbm_gb", "fwd_ms_per_step", "scoped_time_share",
                               "device_idle_share", "device_roofline_share"} <= reported
    assert {x["name"] for x in mf.metrics_of(m, CELL, "end_to_end")} == {"train_samples_per_s", "setup_s"}


def test_the_configuration_keeps_every_published_number_but_the_five_it_says():
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog beside the model-configs guide here")
    with open(catalog) as f:
        row = next((r for r in map(json.loads, f) if r["name"] == "LFM2-8B-A1B"), None)
    if row is None:
        pytest.skip("the catalog here has no row LFM2-8B-A1B")
    cfg = mf.read_json(CONFIG)
    differs = sorted(k for k, v in row["config"].items() if cfg.get(k, "absent") != v)
    assert differs == sorted(cfg["reduced"]) == ["layer_types", "num_dense_layers", "num_experts",
                                                 "num_hidden_layers", "vocab_size"]
    assert cfg["reduced_from"] == {k: row["config"][k] for k in cfg["reduced"]}
    # the widths, by name: none is cut
    assert (cfg["hidden_size"], cfg["intermediate_size"], cfg["moe_intermediate_size"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["head_dim"], cfg["num_experts_per_tok"], cfg["conv_L_cache"],
            cfg["num_routed_experts"]) == (2048, 7168, 1792, 32, 8, 64, 4, 3, row["config"]["num_experts"])
    assert cfg["vocab_size"] * 4 == row["config"]["vocab_size"] and cfg["num_experts"] * 4 == 32
    # the floors: a whole period and four sparse layers after the leading dense one, 8 experts, an eighth of the rows
    published = row["config"]["layer_types"]
    assert cfg["layer_types"] == published[1:6] and len(cfg["layer_types"]) == cfg["num_hidden_layers"] == 5
    assert cfg["num_dense_layers"] == 1 and cfg["layer_types"][1:].count("full_attention") == 1
    assert lfm2.held(cfg) == (0, 8) and cfg["vocab_size"] * 8 >= row["config"]["vocab_size"]
    entry = next(c for c in mf.load()["configs"] if c["name"] == "lfm2-8b-a1b")
    assert entry["source"] == row["source_url"] == cfg["source"] and entry["reduced"] == cfg["reduced"]
    assert "four chips share each layer" in cfg["deployment"]
    assert {"tie_word_embeddings", "head_dim", "qk_norm", "norm_topk_eps", "expert_bias", "routing_seed",
            "optimizer", "weights", "data", "aux_losses"} <= set(cfg["assumed"])


def test_the_departures_are_the_docstrings_word_for_word():
    listed = lfm2.__doc__.split("word for word):")[1]
    items = [re.sub(r"\s+", " ", d.strip().rstrip(";.")) for d in listed.split("  * ")[1:]]
    assert items == mf.read_json(CONFIG)["departures"]
    assert len(items) == 7


def test_the_traffic_is_the_issues():
    job = mf.read_json(TRAFFIC)
    assert (job["kind"], job["seq_len"], job["learning_rate"], job["lr_warmup_steps"], job["lr_warmup_start"]) == \
        ("train", 8192, 1e-4, 200, 1e-6)
    assert (job["adam_beta1"], job["adam_beta2"], job["adam_epsilon"]) == (0.9, 0.95, 1e-8)
    assert (job["ring"], job["loader_capacity"], job["max_inflight"], job["log_period"], job["warmup_steps"],
            job["trace_seconds"]) == (64, 2, 2, 8, 4, 2.5)
    assert job["batch_per_chip"] in (1, 2, 3)


# -- the arithmetic kept with the benchmark ------------------------------------

def cfg_and_job():
    return mf.read_json(CONFIG), mf.read_json(TRAFFIC)


def test_flops_per_sample_at_the_published_sizes():
    cfg, job = cfg_and_job()
    d = 2048
    conv = 2 * d * 3 * d + 2 * d * d                       # in- and out-projection
    attention = 2 * d * (2 * 2048 + 2 * 512) + 4 * 2048 * (8192 + 1) / 2   # four projections, two causal products
    dense = 3 * 2 * d * 7168
    sparse = 2 * d * 32 + 1 * 3 * 2 * d * 1792             # the router, ONE held expert (4 x 8 / 32)
    per_position = (conv + dense) + (attention + sparse) + 3 * (conv + sparse) + 2 * d * 16384
    assert lfm2.flops_per_sample(cfg, job) == 3.0 * 8192 * per_position
    assert abs(lfm2.flops_per_sample(cfg, job) / 8192 - 1.30e9) < 0.01e9    # the issue's ~1.3 GFLOP a token
    # the four short-convolution layers are about two thirds of it, the attention layer and the head a sixth each
    share = (4 * conv + dense + 3 * sparse) / per_position
    assert 0.60 < share < 0.70


def test_short_conv_flops_and_bytes_by_hand():
    tiny = dict(hidden_size=16, conv_L_cache=3, layer_types=["conv", "full_attention", "conv"])
    job = dict(seq_len=10, batch_per_chip=5)
    elements = 5 * 10 * 16 * 2                             # tokens x d, two conv layers
    # forward 2 gates + 3 multiply-adds = 8 an element; backward dC, dc, 3 into dz, 3 into dw, dB, du = 16
    assert lfm2.short_conv_flops(tiny, job) == (8 + 16) * elements
    # forward reads 3 and writes 1; backward reads 1 + 3 and writes 3; bf16
    assert lfm2.short_conv_bytes(tiny, job, itemsize=2) == (4 + 7) * elements * 2
    cfg, job = cfg_and_job()
    flops, moved = lfm2.short_conv_flops(cfg, job), lfm2.short_conv_bytes(cfg, job)
    tokens = job["batch_per_chip"] * 8192
    assert moved == 22 * tokens * 2048 * 4 and flops == 24 * tokens * 2048 * 4
    assert moved / 819e9 > 100 * flops / 197e12            # a pass over memory: the bytes decide


# -- the readers -----------------------------------------------------------------

def test_router_bias_moved_share_reads_the_windows_logged_steps(capsys):
    def record(step, moved, dropped=0, held=(0.25, 0.26)):
        return {"kind": "moe_routing", "pipeline_step": step, "load_max_over_mean": [1.0],
                "load_min_over_mean": [0.9], "dropped_tokens": dropped, "bias_moved_share": moved,
                "held_rows_share": list(held)}

    records = [record(0, [0.9, 0.9]), {"kind": "pipeline_step", "pipeline_step": 8},
               record(8, [0.11, 0.13]), record(16, [0.12, 0.105]), record(24, [0.15, 0.10], held=(0.2, 0.3))]
    # step 0 is warm-up; per step the worst layer: 13, 12, 15 per cent
    assert router_bias_moved_share.bias_moved_share(records, 4) == pytest.approx(13.0)
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["info"] == "moe_routing" and line["logged_steps"] == 3
    assert line["held_rows_share"] == [0.25, 0.26] and line["held_rows_share_max"] == 0.3
    assert line["bias_moved_share"] == [0.12, 0.105]
    assert router_bias_moved_share.bias_moved_share([], 4) is None
    # a router without a bias publishes no share (OLMoE's and SDAR's records): nothing
    plain = [{k: v for k, v in r.items() if k != "bias_moved_share"} for r in records]
    assert router_bias_moved_share.bias_moved_share(plain, 4) is None
    with pytest.raises(AssertionError, match="dropped_tokens"):
        router_bias_moved_share.bias_moved_share(records + [record(32, [0.1], dropped=3)], 4)
    assert router_bias_moved_share.read({"traffic": {}}) is None


HLO = '''
  %fusion.7 = bf16[2,8192,2048]{2,1,0} fusion(%a, %w), kind=kLoop, calls=%f7, metadata={op_name="jit(train_x)/jvp(fwd)/op9:short_conv/gated_short_conv/mul"}
  %fusion.8 = (f32[2048]{0}, bf16[2,8192,2048]{2,1,0}) fusion(%g, %a), kind=kInput, calls=%f8, metadata={op_name="jit(train_x)/transpose(jvp(fwd))/op9:short_conv/gated_short_conv/reduce_sum"}
  %copy.3 = bf16[2,8192,6144]{2,1,0} copy(%a), metadata={op_name="jit(train_x)/jvp(fwd)/op9:short_conv/copy"}
  %fusion.9 = bf16[2,8192,6144]{2,1,0} fusion(%c), kind=kOutput, calls=%f9, metadata={op_name="jit(train_x)/jvp(fwd)/op8:mul/dot_general"}
  %fusion.2 = bf16[2,8192,2048]{2,1,0} fusion(%c), kind=kLoop, calls=%f2, metadata={op_name="jit(train_x)/jvp(fwd)/op90:short_conv_like/gated_short_conv_like/mul"}
'''


class _Compiled:
    def as_text(self):
        return HLO


def test_the_two_short_conv_readers_by_hand(monkeypatch):
    from benchmark import program_trace

    inner = attention_roofline_share.instructions_under(HLO, short_conv_roofline_share.SCOPE)
    assert inner == {"fusion.7", "fusion.8"}
    assert attention_roofline_share.instructions_under(HLO, short_conv_ms_per_step.SCOPE) == {"fusion.7", "fusion.8", "copy.3"}

    def op(name, start_ms, ms):
        return (f"%{name} = bf16[1]{{0}} fusion(%a)", start_ms * 1e6, ms * 1e6, {})

    window = ("bench.traced_window", 0.0, 100e6, {})
    planes = [("/host:CPU", [("main", [window])]),
              ("/device:TPU:0", [
                  ("XLA Ops", [op("fusion.7", 1, 2), op("fusion.8", 20, 4), op("fusion.9", 30, 7), op("copy.3", 40, 1),
                               op("fusion.7", 51, 2), op("fusion.8", 70, 4), op("copy.3", 90, 1)]),
                  ("XLA Modules", [("jit_train_x(1)", 0.0, 50e6, {}), ("jit_train_x(1)", 50e6, 50e6, {})]),
              ])]
    peaks = {"bf16_flops_per_s": 100e12, "hbm_bytes_per_s": 1e12}
    cfg, job = cfg_and_job()
    monkeypatch.setattr(program_trace, "traced_planes", lambda ctx: planes)
    ctx = {"executables": [_Compiled()], "model": lfm2, "config": cfg, "traffic": job, "peaks": peaks}
    assert short_conv_ms_per_step.read(ctx) == pytest.approx(2 + 4 + 1)
    least = attention_roofline_share.least_seconds(lfm2.short_conv_flops(cfg, job), lfm2.short_conv_bytes(cfg, job), peaks)
    assert least == pytest.approx(lfm2.short_conv_bytes(cfg, job) / 1e12)
    assert short_conv_roofline_share.read(ctx) == pytest.approx(100.0 * least / 6e-3)
    # a run without executables, a trace or the scope, or a model without the arithmetic: nothing
    assert short_conv_roofline_share.read(dict(ctx, executables=[])) is None
    assert short_conv_roofline_share.read(dict(ctx, model=object())) is None
    assert short_conv_ms_per_step.read(dict(ctx, executables=[])) is None
    monkeypatch.setattr(program_trace, "traced_planes", lambda ctx: None)
    assert short_conv_ms_per_step.read(ctx) is None and short_conv_roofline_share.read(ctx) is None


HLO_EXPERTS = HLO + '''
  %fusion.20 = f32[16384,32]{1,0} fusion(%m), kind=kLoop, calls=%f20, metadata={op_name="jit(train_x)/jvp(fwd)/op35:moe_router/top_k"}
  %gmm.1 = bf16[32768,1792]{1,0} custom-call(%rows, %w), custom_call_target="tpu_custom_call", metadata={op_name="jit(train_x)/jvp(fwd)/op36:moe_experts/checkpoint/expert_gemm/gmm"}
  %gather.4 = bf16[32768,2048]{1,0} fusion(%x), kind=kLoop, calls=%f4, metadata={op_name="jit(train_x)/transpose(jvp(fwd))/op36:moe_experts/rematted_computation/gather"}
  %flash.1 = bf16[2,32,8192,64]{3,2,1,0} custom-call(%q, %k, %v), custom_call_target="tpu_custom_call", metadata={op_name="jit(train_x)/jvp(fwd)/op20:fused_attention/flash_attention"}
  %fusion.30 = bf16[2,8192,2048]{2,1,0} fusion(%c), kind=kLoop, calls=%f30, metadata={op_name="jit(train_x)/jvp(fwd)/op37:moe_experts_like/mul"}
'''


@pytest.mark.parametrize("name,instructions,ms", [
    ("held_experts_ms_per_step", {"fusion.20", "gmm.1", "gather.4"}, 3 + 5 + 7),
    ("flash_attention_ms_per_step", {"flash.1"}, 11),
])
def test_the_expert_and_attention_time_readers_by_hand(monkeypatch, name, instructions, ms):
    """The cell's largest layer and its one attention are on its result line: the two scopes read by
    instruction, the router's with the experts', a scope that only begins alike left out."""
    from benchmark import program_trace
    from benchmark.metrics import attention_ms_per_step

    reader = mf.reader_module(name)
    scope = reader.SCOPE if name.startswith("held") else attention_ms_per_step.SCOPE
    assert attention_roofline_share.instructions_under(HLO_EXPERTS, scope) == instructions

    class Compiled:
        def as_text(self):
            return HLO_EXPERTS

    def op(name, start_ms, ms):
        return (f"%{name} = bf16[1]{{0}} fusion(%a)", start_ms * 1e6, ms * 1e6, {})

    planes = [("/host:CPU", [("main", [("bench.traced_window", 0.0, 100e6, {})])]),
              ("/device:TPU:0", [
                  ("XLA Ops", [op("fusion.20", 1, 3), op("gmm.1", 10, 5), op("gather.4", 20, 7), op("flash.1", 30, 11),
                               op("fusion.30", 45, 2), op("fusion.7", 48, 1)]),
                  ("XLA Modules", [("jit_train_x(1)", 0.0, 100e6, {})]),
              ])]
    monkeypatch.setattr(program_trace, "traced_planes", lambda ctx: planes)
    ctx = {"executables": [Compiled()]}
    assert reader.read(ctx) == pytest.approx(ms)
    assert reader.read(dict(ctx, executables=[])) is None
    monkeypatch.setattr(program_trace, "traced_planes", lambda ctx: None)
    assert reader.read(ctx) is None
