"""PR 65's cell rehearsed tiny on the CPU, its configuration against the
catalog row, its arithmetic against hand counts, and its two per-layer
readers on hand-built inputs.

The rehearsal builds on `tiny_root` of test_benchmark_rehearsal.py: the
cell's configuration and traffic files are written, cut down, into the same
scratch root.  As there, no number of a CPU run means anything.  Lists are
checked by MEMBERSHIP, never by position or equality: a later PR appends.
"""
import json
import os
import re

import pytest

from benchmark import manifest as mf
from benchmark.metrics import (attention_gate_ms_per_step, attention_roofline_share, causal_attention_roofline_share,
                               gated_attention_layers, window_attention_roofline_share)
from benchmark.models import laguna, lfm2

from test_benchmark_rehearsal import check_line, run_cell, tiny_root  # noqa: F401

CELL = "laguna-xs.2.train-gated-swa-s16384"
CONFIG = "benchmark/configs/laguna-xs.2.json"
TRAFFIC = "benchmark/traffic/train-gated-swa-s16384.json"
#: the per-layer metrics this cell brought: each lists it alone
OWN_METRICS = ("attention_gate_ms_per_step", "gated_attention_layers")
#: ... and the lists it joined
JOINED = ("window_attention_roofline_share", "window_pairs_visited_over_allowed", "causal_attention_roofline_share",
          "flash_attention_ms_per_step", "held_experts_ms_per_step", "recompute_ms_per_step", "recompute_kept_bytes_share",
          "model_flops_util", "peak_hbm_gb", "update_ms_per_step", "device_idle_share", "dispatch_ms_per_step",
          "recompiles_in_window", "loader_wait_share", "host_blocked_share", "next_batch_wait_share", "reader_stage_share",
          "slow_step_share", "idle_host_active_share", "idle_unattributed_share")
TINY_NEW = {
    CONFIG: dict(hidden_size=32, num_key_value_heads=2, head_dim=16, intermediate_size=64, moe_intermediate_size=16,
                 shared_expert_intermediate_size=16, num_experts=8, num_routed_experts=16, num_experts_per_tok=4, vocab_size=64,
                 sliding_window=16, num_attention_heads_per_layer=[6, 8, 8, 8, 6]),
    TRAFFIC: dict(seq_len=64, batch_per_chip=2, ring=4, trace_seconds=0.8),
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
    monkeypatch.setattr(laguna, "ATTENTION_SAMPLE", 48)
    return tiny_root


@pytest.mark.parametrize("trace", [0, 1])
def test_the_cell_rehearsed_tiny_on_the_cpu(tiny_root_with_the_cell, trace, capsys):
    result = run_cell(tiny_root_with_the_cell, CELL, trace, 2)
    check_line(result, CELL, trace)
    lines = [json.loads(line) for line in capsys.readouterr().out.splitlines() if line.startswith('{"info"')]
    routing = [line for line in lines if line["info"] == "reference_routing"]
    assert len(routing) == 1 and routing[0]["routed_differently_above_margin"] == 0
    assert routing[0]["router_choice_differs"] == 0 and routing[0]["router_prob_error"] <= laguna.ROUTER_RTOL
    assert routing[0]["attention_error"] <= laguna.ATTENTION_RTOL and routing[0]["qk_error"] <= laguna.QK_RTOL
    assert routing[0]["experts_error"] <= laguna.EXPERTS_RTOL and routing[0]["shared_error"] <= laguna.SHARED_RTOL
    assert routing[0]["gate_error"] <= laguna.GATE_RTOL < routing[0]["gate_error_bf16"]
    assert routing[0]["gated_error"] <= laguna.GATED_RTOL < routing[0]["gated_error_no_gate"]
    assert len(routing[0]["attention_errors"]) == len(routing[0]["qk_errors"]) == len(routing[0]["gate_errors"]) == 2
    assert len(routing[0]["held_rows_share"]) == 4                                    # the four sparse layers
    if trace:  # the program's counters, no device needed
        assert result["metrics"]["gated_attention_layers"]["value"] == 5.0
        assert result["metrics"]["recompute_kept_bytes_share"]["value"] == 100.0      # the CPU reports no limit: all is kept
        assert result["metrics"]["recompiles_in_window"]["value"] == 0
        assert "window_pairs_visited_over_allowed" not in result["metrics"]           # off the TPU the rule is XLA's attention
        forms = [line for line in lines if line["info"] == "attention_forms"]
        assert forms == [{"info": "attention_forms", "query_heads_by_layer": [6, 8, 8, 8, 6], "rotary_tables": 2}]
        moe = [line for line in lines if line["info"] == "moe_routing"]
        assert moe and len(moe[0]["held_rows_share"]) == 4          # published from inside the four recomputed segments


def test_the_manifest_holds_the_configuration_and_the_cell_and_nothing_is_wrong_with_it():
    m = mf.load()
    assert mf.problems(m) == []
    cell = mf.cell(m, CELL)      # membership, never position: a later PR appends after it
    assert cell["chips"] == 1 and (cell["config"], cell["traffic"]) == ("laguna-xs.2", "train-gated-swa-s16384")
    config = next(x for x in m["configs"] if x["name"] == cell["config"])
    for text in (cell["why"], config["why"], config["source"]):
        assert 1 <= len(text) <= 200 and text.isprintable()
    assert "16384" in cell["why"] and "16x" in cell["why"] and "512 rows" in cell["why"] and "2 full" in cell["why"]
    for name in OWN_METRICS:
        metric = next(x for x in m["per_layer"] if x["name"] == name)
        reader = mf.reader_module(name)
        assert metric["workloads"] == [CELL] or CELL in metric["workloads"]
        assert (metric["unit"], metric["better"], metric["source"], metric["layer"], metric["moves"]) == \
            (reader.UNIT, reader.BETTER, reader.SOURCE, reader.LAYER, reader.MOVES)
        assert reader.read({}) is None  # an empty context (a parent without the scopes or counters): nothing, no error
    reported = {x["name"] for x in mf.metrics_of(m, CELL, "per_layer")}
    assert set(OWN_METRICS) | set(JOINED) <= reported
    # the generic readers that misread a step with three-line splash calls in it (PERF.md, defects 4a, 13b), the readers
    # other cells' tests pin to their one cell (13a), and SmallThinker's own two
    assert not reported & {"fwd_ms_per_step", "bwd_ms_per_step", "scoped_time_share", "device_roofline_share",
                           "moe_ms_per_step", "attention_ms_per_step", "expert_gemm_roofline_share",
                           "held_expert_rows_share", "pre_router_ms_per_step", "routers_before_attention"}
    assert {x["name"] for x in mf.metrics_of(m, CELL, "end_to_end")} == {"train_samples_per_s", "setup_s"}
    assert CELL in next(x for x in m["end_to_end"] if x["name"] == "train_samples_per_s")["workloads"]
    assert sum(w["chips"] == 4 for w in m["workloads"]) == 3 and len(m["workloads"]) >= 16


def test_the_configuration_keeps_every_published_number_but_the_keys_it_says():
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog beside the model-configs guide here")
    with open(catalog) as f:
        row = next((r for r in map(json.loads, f) if r["name"] == "Laguna-XS.2"), None)
    if row is None:
        pytest.skip("the catalog here has no such row")
    cfg = mf.read_json(CONFIG)
    differs = sorted(k for k, v in row["config"].items() if cfg.get(k, "absent") != v)
    assert differs == sorted(cfg["reduced"]) == ["layer_types", "mlp_layer_types", "num_attention_heads_per_layer",
                                                 "num_experts", "num_hidden_layers", "vocab_size"]
    assert {k: cfg["reduced_from"][k] for k in ("num_experts", "vocab_size", "num_hidden_layers")} == \
        {k: row["config"][k] for k in ("num_experts", "vocab_size", "num_hidden_layers")}
    # the per-layer lists are cut with the layers to their first entries, and are what they were there
    for key, period in (("layer_types", ["full_attention"] + ["sliding_attention"] * 3), ("num_attention_heads_per_layer", [48, 64, 64, 64])):
        assert cfg[key] == row["config"][key][:cfg["num_hidden_layers"]] == period + period[:1]
        assert row["config"][key] == period * 10
    assert cfg["mlp_layer_types"] == row["config"]["mlp_layer_types"][:5] == ["dense"] + ["sparse"] * 4
    assert row["config"]["mlp_layer_types"] == ["dense"] + ["sparse"] * 39
    # the widths, by name: none is cut; both rotary descriptions whole
    assert (cfg["hidden_size"], cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"], cfg["intermediate_size"],
            cfg["moe_intermediate_size"], cfg["shared_expert_intermediate_size"], cfg["num_experts_per_tok"], cfg["sliding_window"],
            cfg["moe_routed_scaling_factor"], cfg["rms_norm_eps"], cfg["max_position_embeddings"], cfg["num_routed_experts"],
            cfg["partial_rotary_factor"], cfg["gating"]) == \
        (2048, 48, 8, 128, 8192, 512, 512, 8, 512, 2.5, 1e-6, 262144, row["config"]["num_experts"], 0.5, True)
    assert cfg["rope_parameters"] == row["config"]["rope_parameters"]
    assert cfg["rope_parameters"]["full_attention"]["factor"] == 64 and cfg["rope_parameters"]["sliding_attention"]["rope_theta"] == 10000
    # the floors: the leading dense layer and one whole period of four layers, at least 8 experts, an eighth of the rows
    assert cfg["num_hidden_layers"] == 5 == len(cfg["layer_types"])
    assert laguna.held(cfg) == (0, 16) and cfg["vocab_size"] * 8 == row["config"]["vocab_size"]
    assert laguna.heads_by_kind(cfg) == {"full_attention": 48, "sliding_attention": 64} and laguna._dense_layers(cfg) == 1
    entry = next(c for c in mf.load()["configs"] if c["name"] == "laguna-xs.2")
    assert entry["source"] == row["source_url"] == cfg["source"] and entry["reduced"] == cfg["reduced"]
    assert "SIXTEEN chips share each layer" in cfg["deployment"] and "5.88 GB" in cfg["deployment"] and "20.6 GB" in cfg["deployment"]
    assert {"gate", "router", "attention", "rotary", "yarn", "window", "hidden_act", "routing_seed", "aux_losses", "optimizer",
            "compute_dtype", "weights", "data"} <= set(cfg["assumed"])
    assert "33.4 B" in cfg["assumed"]["gate"] and "2505.06708" in cfg["assumed"]["gate"]


def test_the_departures_are_the_docstrings_word_for_word():
    listed = laguna.__doc__.split("word for word):")[1]
    items = [re.sub(r"\s+", " ", d.strip().rstrip(";.")) for d in listed.split("  * ")[1:]]
    assert items == mf.read_json(CONFIG)["departures"]
    assert len(items) == 7


def test_the_traffic_is_the_issues():
    job = mf.read_json(TRAFFIC)
    assert (job["kind"], job["seq_len"], job["batch_per_chip"], job["learning_rate"], job["lr_warmup_steps"],
            job["lr_warmup_start"]) == ("train", 16384, 1, 1e-4, 200, 1e-6)
    assert (job["adam_beta1"], job["adam_beta2"], job["adam_epsilon"]) == (0.9, 0.95, 1e-8)
    assert (job["ring"], job["loader_capacity"], job["max_inflight"], job["log_period"], job["warmup_steps"],
            job["trace_seconds"]) == (64, 2, 2, 8, 4, 2.5)
    stated = mf.read_json(CONFIG)["rope_parameters"]["full_attention"]
    assert job["seq_len"] == 4 * stated["original_max_position_embeddings"] == mf.read_json(CONFIG)["max_position_embeddings"] // 16


# -- the arithmetic kept with the benchmark ------------------------------------

def cfg_and_job():
    return mf.read_json(CONFIG), mf.read_json(TRAFFIC)


PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def test_the_attentions_flops_and_bytes_by_hand():
    """Each kind at its own count of query heads: 6 full, 10 under the window."""
    tiny = dict(num_key_value_heads=2, head_dim=8, sliding_window=4, layer_types=["full_attention", "sliding_attention", "sliding_attention"],
                num_attention_heads_per_layer=[6, 10, 10])
    job = dict(seq_len=16, batch_per_chip=5)
    triangle, band = 16 * 17 // 2, 4 * 5 // 2 + 12 * 4
    assert laguna.causal_attention_flops(tiny, job) == 6 * 2 * 6 * 8 * triangle * 5
    assert laguna.window_attention_flops(tiny, job) == 2 * 6 * 2 * 10 * 8 * band * 5
    assert laguna.causal_attention_bytes(tiny, job) == 2 * 2 * (2 * 6 + 2 * 2) * 8 * 16 * 5
    assert laguna.window_attention_bytes(tiny, job) == 2 * 2 * 2 * (2 * 10 + 2 * 2) * 8 * 16 * 5
    cfg, job = cfg_and_job()
    flops, moved = laguna.causal_attention_flops(cfg, job), laguna.causal_attention_bytes(cfg, job)
    assert abs(flops / 1e12 - 19.79) < 0.01 and abs(moved / 1e9 - 1.879) < 0.001
    # the arithmetic binds: 100.5 ms at the bf16 peak for the two full layers; a share over 100% would need their kernels under it
    least = attention_roofline_share.least_seconds(flops, moved, PEAKS)
    assert least == pytest.approx(flops / 197e12) and 0.1003 < least < 0.1006
    window = attention_roofline_share.least_seconds(laguna.window_attention_flops(cfg, job), laguna.window_attention_bytes(cfg, job), PEAKS)
    assert 0.0123 < window < 0.0126                                        # three layers of 4.1 ms over the ALLOWED pairs


def test_flops_per_sample_at_the_published_sizes():
    cfg, job = cfg_and_job()
    d, seq = 2048, 16384
    forward = seq * 2 * d * 12544
    for heads, window, dense in ((48, None, True), (64, 512, False), (64, 512, False), (64, 512, False), (48, None, False)):
        projections = 2 * (2 * d * heads * 128 + 2 * d * 8 * 128) + 2 * d * heads
        ffn = 3 * 2 * d * 8192 if dense else 2 * d * 256 + 3 * 2 * d * 512 + 0.5 * 3 * 2 * d * 512      # half a held expert
        pairs = seq * (seq + 1) // 2 if window is None else 512 * 513 // 2 + (seq - 512) * 512
        forward += seq * (projections + ffn) + 2 * 2 * heads * 128 * pairs
    assert laguna.flops_per_sample(cfg, job) == pytest.approx(3.0 * forward, rel=1e-12)
    attention = 2 * 2 * 128 * (2 * 48 * (seq * (seq + 1) // 2) + 3 * 64 * (512 * 513 // 2 + (seq - 512) * 512))
    assert 0.44 < attention / forward < 0.47                               # the kernels' pairs: 45% of a step's arithmetic
    assert 4 * 0.5 * 3 * 2 * d * 512 * seq / forward < 0.02                # the held experts: under 2%


# -- the readers -----------------------------------------------------------------

HLO = '''
  %fusion.1 = bf16[1,16384,8192]{2,1,0} fusion(%a, %w), kind=kOutput, calls=%f1, metadata={op_name="jit(train_x)/jvp(fwd)/checkpoint/sliding_attention/op9:mul/dot_general"}
  %fusion.2 = f32[1,16384,64]{2,1,0} fusion(%a, %wg), kind=kOutput, calls=%f2, metadata={op_name="jit(train_x)/jvp(fwd)/checkpoint/sliding_attention/attention_gate/op21:mul/dot_general"}
  %fusion.3 = bf16[1,64,16384,128]{3,2,1,0} fusion(%o, %g), kind=kLoop, calls=%f3, metadata={op_name="jit(train_x)/jvp(fwd)/checkpoint/sliding_attention/attention_gate/op25:elementwise_mul/mul"}
  %fusion.4 = bf16[1,48,16384,128]{3,2,1,0} fusion(%o, %g), kind=kLoop, calls=%f4, metadata={op_name="jit(train_x)/transpose(jvp(fwd))/checkpoint/rematted_computation/attention_gate_1/op160:elementwise_mul/mul"}
  %fusion.5 = f32[2048,48]{1,0} fusion(%a, %dg), kind=kOutput, calls=%f5, metadata={op_name="jit(train_x)/transpose(jvp(fwd))/checkpoint/attention_gate/op21:mul/transpose/dot_general"}
  %splash.6 = bf16[1,48,16384,128]{3,2,1,0} custom-call(%q, %k, %v), custom_call_target="tpu_custom_call", frontend_attributes={kernel_metadata="{
   }"},
   metadata={op_name="jit(train_x)/jvp(fwd)/checkpoint/op14:fused_attention/block_sparse_attention/splash_mha_fwd"}
  %splash.7 = bf16[1,64,16384,128]{3,2,1,0} custom-call(%q, %k, %v), custom_call_target="tpu_custom_call", frontend_attributes={kernel_metadata="{
   }"},
   metadata={op_name="jit(train_x)/jvp(fwd)/checkpoint/sliding_attention/op52:fused_attention/window_attention/block_sparse_attention/splash_mha_fwd"}
  %fusion.8 = bf16[8192,512]{1,0} fusion(%r, %w), kind=kOutput, calls=%f8, metadata={op_name="jit(train_x)/jvp(fwd)/checkpoint/op30:moe_experts/expert_gemm/gmm"}
'''


class _Compiled:
    def as_text(self):
        return HLO


class _Monitor:
    def __init__(self, **counted):
        self.counted = counted

    def counter_values(self):
        return self.counted

    def step_records(self):
        return [{"kind": "moe_routing", "pipeline_step": step, "dropped_tokens": 0, "held_rows_share": [0.0625, share]}
                for step, share in ((0, 0.5), (8, 0.06), (16, 0.07))]


COUNTED = {"lowering.gated_attention_layers": 5, "lowering.rotary_tables": 2,
           **{f"lowering.query_heads_by_layer.{i}": h for i, h in enumerate([48, 64, 64, 64, 48])}}


def test_the_gates_device_time_reader_by_hand(monkeypatch):
    """Own time by instruction: what stands under a scope `attention_gate`,
    numbered or not, forward, made again and backward, is the gate's; the q
    projection beside it, the kernels and an expert's grouped product are not."""
    from benchmark import program_trace

    def op(name, start_ms, ms):
        return (f"%{name} = bf16[1]{{0}} fusion(%a)", start_ms * 1e6, ms * 1e6, {})

    planes = [("/host:CPU", [("main", [("bench.traced_window", 0.0, 1000e6, {})])]),
              ("/device:TPU:0", [
                  ("XLA Ops", [op("fusion.1", 1, 3), op("fusion.2", 5, 0.5), op("fusion.3", 8, 1.25), op("fusion.4", 10, 1.0),
                               op("fusion.5", 20, 0.25), op("splash.6", 130, 31), op("splash.7", 400, 10), op("fusion.8", 700, 7)]),
                  ("XLA Modules", [("jit_train_x(1)", 0.0, 1000e6, {})]),
              ])]
    monkeypatch.setattr(program_trace, "traced_planes", lambda ctx: planes)
    monkeypatch.setattr(program_trace, "program_monitor", lambda: _Monitor(**COUNTED))
    cfg, job = cfg_and_job()

    def ctx():
        return {"executables": [_Compiled()], "model": laguna, "config": cfg, "traffic": job, "peaks": PEAKS}

    assert attention_gate_ms_per_step.read(ctx()) == pytest.approx(0.5 + 1.25 + 1.0 + 0.25)
    least = attention_roofline_share.least_seconds(laguna.causal_attention_flops(cfg, job), laguna.causal_attention_bytes(cfg, job), PEAKS)
    assert causal_attention_roofline_share.read(ctx()) == pytest.approx(100.0 * least / (31 / 1e3))
    least = attention_roofline_share.least_seconds(laguna.window_attention_flops(cfg, job), laguna.window_attention_bytes(cfg, job), PEAKS)
    assert window_attention_roofline_share.read(ctx()) == pytest.approx(100.0 * least / (10 / 1e3))
    assert attention_gate_ms_per_step.read(dict(ctx(), executables=[])) is None
    # no gated layer (a parent, another cell): nothing of its own to read, whatever the trace holds
    monkeypatch.setattr(program_trace, "program_monitor", lambda: _Monitor())
    assert attention_gate_ms_per_step.read(ctx()) is None
    monkeypatch.setattr(program_trace, "traced_planes", lambda ctx: None)
    monkeypatch.setattr(program_trace, "program_monitor", lambda: _Monitor(**COUNTED))
    assert attention_gate_ms_per_step.read(ctx()) is None


@pytest.mark.parametrize("counted,value", [(COUNTED, 5), ({"lowering.gated_attention_layers": 0}, None), ({}, None)])
def test_gated_attention_layers_reads_the_counter_and_prints_the_forms_and_what_the_routers_chose(monkeypatch, counted, value, capsys):
    from benchmark import program_trace

    monkeypatch.setattr(program_trace, "program_monitor", lambda: _Monitor(**counted))
    assert gated_attention_layers.read({"traffic": {"warmup_steps": 4}}) == value
    lines = [json.loads(line) for line in capsys.readouterr().out.splitlines()]
    if value:   # the window's logged steps (from step 4 on), layer by layer
        assert lines == [{"info": "attention_forms", "query_heads_by_layer": [48, 64, 64, 64, 48], "rotary_tables": 2},
                         {"info": "moe_routing", "logged_steps": 2, "held_rows_share": [0.0625, 0.065], "held_rows_share_max": 0.07}]
    else:
        assert lines == []
    assert gated_attention_layers.read({}) is None
