"""PR 63's cell rehearsed tiny on the CPU, its configuration against the
catalog row, its arithmetic against hand counts, and its three per-layer
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
from benchmark.metrics import (attention_roofline_share, causal_attention_roofline_share, pre_router_ms_per_step,
                               routers_before_attention, window_attention_roofline_share)
from benchmark.models import lfm2, smallthinker

from test_benchmark_rehearsal import check_line, run_cell, tiny_root  # noqa: F401

CELL = "smallthinker-21b-a3b.train-nope-swa-s16384"
CONFIG = "benchmark/configs/smallthinker-21b-a3b.json"
TRAFFIC = "benchmark/traffic/train-nope-swa-s16384.json"
#: the per-layer metrics this cell brought: each lists it alone
OWN_METRICS = ("causal_attention_roofline_share", "pre_router_ms_per_step", "routers_before_attention")
#: ... and the lists it joined
JOINED = ("window_attention_roofline_share", "window_pairs_visited_over_allowed", "flash_attention_ms_per_step",
          "held_experts_ms_per_step", "recompute_ms_per_step", "recompute_kept_bytes_share", "model_flops_util",
          "peak_hbm_gb", "update_ms_per_step", "device_idle_share", "dispatch_ms_per_step", "recompiles_in_window",
          "loader_wait_share", "host_blocked_share", "next_batch_wait_share", "reader_stage_share", "slow_step_share",
          "idle_host_active_share", "idle_unattributed_share")
TINY_NEW = {
    CONFIG: dict(hidden_size=32, num_attention_heads=4, num_key_value_heads=2, head_dim=16, moe_ffn_hidden_size=16,
                 moe_num_primary_experts=4, num_routed_experts=16, moe_num_active_primary_experts=4, vocab_size=64,
                 sliding_window_size=16),
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
    monkeypatch.setattr(lfm2, "ATTENTION_SAMPLE", 16)
    return tiny_root


@pytest.mark.parametrize("trace", [0, 1])
def test_the_cell_rehearsed_tiny_on_the_cpu(tiny_root_with_the_cell, trace, capsys):
    result = run_cell(tiny_root_with_the_cell, CELL, trace, 2)
    check_line(result, CELL, trace)
    lines = [json.loads(line) for line in capsys.readouterr().out.splitlines() if line.startswith('{"info"')]
    routing = [line for line in lines if line["info"] == "reference_routing"]
    assert len(routing) == 1 and routing[0]["routed_differently_above_margin"] == 0
    assert routing[0]["router_choice_differs"] == 0 and routing[0]["router_prob_error"] <= smallthinker.ROUTER_RTOL
    assert routing[0]["attention_error"] <= smallthinker.ATTENTION_RTOL and routing[0]["qk_error"] <= smallthinker.QK_RTOL
    assert routing[0]["experts_error"] <= smallthinker.EXPERTS_RTOL < routing[0]["experts_error_silu"]
    assert len(routing[0]["attention_errors"]) == len(routing[0]["qk_errors"]) == 2          # the full and the first window layer
    assert len(routing[0]["held_rows_share"]) == 4
    if trace:  # the program's counters, no device needed
        assert result["metrics"]["routers_before_attention"]["value"] == 4.0
        assert result["metrics"]["recompute_kept_bytes_share"]["value"] == 100.0      # the CPU reports no limit: all is kept
        assert result["metrics"]["recompiles_in_window"]["value"] == 0
        assert "window_pairs_visited_over_allowed" not in result["metrics"]           # off the TPU the rule is XLA's attention
        moe = [line for line in lines if line["info"] == "moe_routing"]
        assert moe and len(moe[0]["held_rows_share"]) == 4          # published from inside the four recomputed segments


def test_the_manifest_holds_the_configuration_and_the_cell_and_nothing_is_wrong_with_it():
    m = mf.load()
    assert mf.problems(m) == []
    cell = mf.cell(m, CELL)      # membership, never position: a later PR appends after it
    assert cell["chips"] == 1 and (cell["config"], cell["traffic"]) == ("smallthinker-21b-a3b", "train-nope-swa-s16384")
    config = next(x for x in m["configs"] if x["name"] == cell["config"])
    for text in (cell["why"], config["why"], config["source"]):
        assert 1 <= len(text) <= 200 and text.isprintable()
    assert "16384" in cell["why"] and "8x" in cell["why"] and "1536 rows" in cell["why"]
    for name in OWN_METRICS:
        metric = next(x for x in m["per_layer"] if x["name"] == name)
        reader = mf.reader_module(name)
        assert metric["workloads"] == [CELL] or CELL in metric["workloads"]
        assert (metric["unit"], metric["better"], metric["source"], metric["layer"], metric["moves"]) == \
            (reader.UNIT, reader.BETTER, reader.SOURCE, reader.LAYER, reader.MOVES)
        assert reader.read({}) is None  # an empty context (a parent without the scopes or counters): nothing, no error
    reported = {x["name"] for x in mf.metrics_of(m, CELL, "per_layer")}
    assert set(OWN_METRICS) | set(JOINED) <= reported
    # the generic readers that misread a step with three-line splash calls in it (PERF.md, defects 4a, 13b), and the
    # readers other cells' tests pin to their one cell (13a)
    assert not reported & {"fwd_ms_per_step", "bwd_ms_per_step", "scoped_time_share", "device_roofline_share",
                           "moe_ms_per_step", "attention_ms_per_step", "expert_gemm_roofline_share",
                           "held_expert_rows_share"}
    assert {x["name"] for x in mf.metrics_of(m, CELL, "end_to_end")} == {"train_samples_per_s", "setup_s"}
    assert CELL in next(x for x in m["end_to_end"] if x["name"] == "train_samples_per_s")["workloads"]
    assert sum(w["chips"] == 4 for w in m["workloads"]) == 3 and len(m["workloads"]) >= 15


def test_the_configuration_keeps_every_published_number_but_the_keys_it_says():
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog beside the model-configs guide here")
    with open(catalog) as f:
        row = next((r for r in map(json.loads, f) if r["name"] == "SmallThinker-21BA3B-Instruct"), None)
    if row is None:
        pytest.skip("the catalog here has no such row")
    cfg = mf.read_json(CONFIG)
    differs = sorted(k for k, v in row["config"].items() if cfg.get(k, "absent") != v)
    assert differs == sorted(cfg["reduced"]) == ["moe_num_primary_experts", "num_hidden_layers", "rope_layout",
                                                 "sliding_window_layout", "vocab_size"]
    assert {k: cfg["reduced_from"][k] for k in ("moe_num_primary_experts", "vocab_size", "num_hidden_layers")} == \
        {k: row["config"][k] for k in ("moe_num_primary_experts", "vocab_size", "num_hidden_layers")}
    # the per-layer lists are cut with the layers to their first entries, and are what they were there
    for key in ("rope_layout", "sliding_window_layout"):
        assert cfg[key] == row["config"][key][:cfg["num_hidden_layers"]] == [0, 1, 1, 1]
        assert row["config"][key] == [0, 1, 1, 1] * 13
    # the widths, by name: none is cut
    assert (cfg["hidden_size"], cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"],
            cfg["moe_ffn_hidden_size"], cfg["moe_num_active_primary_experts"], cfg["sliding_window_size"], cfg["rope_theta"],
            cfg["rms_norm_eps"], cfg["max_position_embeddings"], cfg["num_routed_experts"]) == \
        (2560, 28, 4, 128, 768, 6, 4096, 1500000, 1e-6, 16384, row["config"]["moe_num_primary_experts"])
    # the floors: one whole period of four layers, 8 experts, an eighth of the rows
    assert cfg["num_hidden_layers"] == 4 == len(cfg["layer_types"])
    assert cfg["layer_types"] == ["full_attention"] + ["sliding_attention"] * 3
    assert smallthinker.held(cfg) == (0, 8) and cfg["vocab_size"] * 8 == row["config"]["vocab_size"]
    entry = next(c for c in mf.load()["configs"] if c["name"] == "smallthinker-21b-a3b")
    assert entry["source"] == row["source_url"] == cfg["source"] and entry["reduced"] == cfg["reduced"]
    assert "EIGHT chips share each layer" in cfg["deployment"] and "5.93 GB" in cfg["deployment"]
    assert {"router", "attention", "rotary", "window", "hidden_act", "secondary_experts", "routing_seed", "embedding_std",
            "aux_losses", "optimizer", "compute_dtype", "weights", "data", "layer_types"} <= set(cfg["assumed"])


def test_the_departures_are_the_docstrings_word_for_word():
    listed = smallthinker.__doc__.split("word for word):")[1]
    items = [re.sub(r"\s+", " ", d.strip().rstrip(";.")) for d in listed.split("  * ")[1:]]
    assert items == mf.read_json(CONFIG)["departures"]
    assert len(items) == 8


def test_the_traffic_is_the_issues():
    job = mf.read_json(TRAFFIC)
    assert (job["kind"], job["seq_len"], job["batch_per_chip"], job["learning_rate"], job["lr_warmup_steps"],
            job["lr_warmup_start"]) == ("train", 16384, 1, 1e-4, 200, 1e-6)
    assert (job["adam_beta1"], job["adam_beta2"], job["adam_epsilon"]) == (0.9, 0.95, 1e-8)
    assert (job["ring"], job["loader_capacity"], job["max_inflight"], job["log_period"], job["warmup_steps"],
            job["trace_seconds"]) == (64, 2, 2, 8, 4, 2.5)
    assert job["seq_len"] == mf.read_json(CONFIG)["max_position_embeddings"]          # the model's whole context


# -- the arithmetic kept with the benchmark ------------------------------------

def cfg_and_job():
    return mf.read_json(CONFIG), mf.read_json(TRAFFIC)


PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def test_the_attentions_flops_and_bytes_by_hand():
    tiny = dict(num_attention_heads=6, num_key_value_heads=2, head_dim=8, sliding_window_size=4,
                layer_types=["full_attention", "sliding_attention", "sliding_attention"])
    job = dict(seq_len=16, batch_per_chip=5)
    triangle, band = 16 * 17 // 2, 4 * 5 // 2 + 12 * 4
    # two products forward and four backward over the allowed pairs, 2 a multiply-add, 6 heads of 8
    assert smallthinker.causal_attention_flops(tiny, job) == 6 * 2 * 6 * 8 * triangle * 5
    assert smallthinker.window_attention_flops(tiny, job) == 2 * 6 * 2 * 6 * 8 * band * 5
    # q, out and their gradients over 6 heads, k, v and theirs over 2: bf16, forward and backward
    assert smallthinker.causal_attention_bytes(tiny, job) == 2 * 2 * (2 * 6 + 2 * 2) * 8 * 16 * 5
    assert smallthinker.window_attention_bytes(tiny, job) == 2 * smallthinker.causal_attention_bytes(tiny, job)
    cfg, job = cfg_and_job()
    flops, moved = smallthinker.causal_attention_flops(cfg, job), smallthinker.causal_attention_bytes(cfg, job)
    assert abs(flops / 1e12 - 5.77) < 0.01 and abs(moved / 1e9 - 0.537) < 0.001
    # the arithmetic binds: 29.3 ms at the bf16 peak against 0.66 ms of bytes; a share over 100% would need the full
    # layer's kernels under 29.3 ms a step
    least = attention_roofline_share.least_seconds(flops, moved, PEAKS)
    assert least == pytest.approx(flops / 197e12) and 0.0292 < least < 0.0294
    window = attention_roofline_share.least_seconds(smallthinker.window_attention_flops(cfg, job),
                                                    smallthinker.window_attention_bytes(cfg, job), PEAKS)
    assert 0.0383 < window < 0.0386                                        # three layers of 12.8 ms


def test_flops_per_sample_at_the_published_sizes():
    cfg, job = cfg_and_job()
    d, seq = 2560, 16384
    projections = 2 * (2 * d * 28 * 128 + 2 * d * 4 * 128)
    sparse = 2 * d * 64 + 0.75 * 3 * 2 * d * 768           # the router, three quarters of a held expert
    pairs = 2 * 2 * 28 * 128 * (seq * (seq + 1) // 2 + 3 * (4096 * 4097 // 2 + (seq - 4096) * 4096))
    forward = seq * (4 * (projections + sparse) + 2 * d * 18992) + pairs
    assert smallthinker.flops_per_sample(cfg, job) == pytest.approx(3.0 * forward, rel=1e-12)
    assert 0.46 < pairs / forward < 0.49                                 # the attentions: nearly half of a step's arithmetic
    assert 4 * 0.75 * 3 * 2 * d * 768 * seq / forward < 0.07             # the held experts: under 7%


# -- the readers -----------------------------------------------------------------

HLO = '''
  %fusion.1 = bf16[1,16384,3584]{2,1,0} fusion(%a, %w), kind=kOutput, calls=%f1, metadata={op_name="jit(train_x)/jvp(fwd)/checkpoint/op9:mul/dot_general"}
  %fusion.2 = f32[16384,64]{1,0} fusion(%x, %wr), kind=kOutput, calls=%f2, metadata={op_name="jit(train_x)/jvp(fwd)/checkpoint/op5:moe_router/dot_general"}
  %fusion.3 = f32[16384,6]{1,0} fusion(%l), kind=kLoop, calls=%f3, metadata={op_name="jit(train_x)/transpose(jvp(fwd))/checkpoint/rematted_computation/op38:moe_router/top_k"}
  %fusion.4 = bf16[1,16384,28,128]{3,2,1,0} fusion(%q), kind=kLoop, calls=%f4, metadata={op_name="jit(train_x)/jvp(fwd)/checkpoint/op19:fused_attention/block_sparse_attention/mul"}
  %splash.5 = bf16[1,28,16384,128]{3,2,1,0} custom-call(%q, %k, %v), custom_call_target="tpu_custom_call", frontend_attributes={kernel_metadata="{
   }"},
   metadata={op_name="jit(train_x)/jvp(fwd)/checkpoint/op19:fused_attention/block_sparse_attention/splash_mha_fwd"}
  %splash.6 = bf16[16,28,16384,128]{3,2,1,0} custom-call(%q, %k, %v, %do), custom_call_target="tpu_custom_call", frontend_attributes={kernel_metadata="{
   }"},
   metadata={op_name="jit(train_x)/transpose(jvp(fwd))/checkpoint/op19:fused_attention/block_sparse_attention/splash_mha_dkv"}
  %splash.7 = bf16[1,28,16384,128]{3,2,1,0} custom-call(%q, %k, %v), custom_call_target="tpu_custom_call", frontend_attributes={kernel_metadata="{
   }"},
   metadata={op_name="jit(train_x)/jvp(fwd)/checkpoint/sliding_attention/op52:fused_attention/window_attention/block_sparse_attention/splash_mha_fwd"}
  %splash.8 = bf16[1,28,16384,128]{3,2,1,0} custom-call(%q, %k, %v, %do), custom_call_target="tpu_custom_call", frontend_attributes={kernel_metadata="{
   }"},
   metadata={op_name="jit(train_x)/transpose(jvp(fwd))/checkpoint/sliding_attention_2/op120:fused_attention/window_attention/block_sparse_attention/splash_mha_dq"}
  %fusion.9 = bf16[12288,768]{1,0} fusion(%r, %w), kind=kOutput, calls=%f9, metadata={op_name="jit(train_x)/jvp(fwd)/checkpoint/op30:moe_experts/expert_gemm/gmm"}
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
        return [{"kind": "moe_routing", "pipeline_step": step, "dropped_tokens": 0, "held_rows_share": [0.125, share]}
                for step, share in ((0, 0.5), (8, 0.12), (16, 0.13))]


def test_the_two_device_time_readers_by_hand(monkeypatch):
    """Own time by instruction.  The full layer's kernels and its queries'
    scaling, forward and backward, are the causal share's, and nothing under
    `/window_attention/` is, whichever numbered scope it stands in: that is the
    window share's.  The routers' instructions, forward and made again, are the
    router reader's, and an expert's grouped product is not; the splash calls'
    three-line instructions are found."""
    from benchmark import program_trace

    def op(name, start_ms, ms):
        return (f"%{name} = bf16[1]{{0}} fusion(%a)", start_ms * 1e6, ms * 1e6, {})

    planes = [("/host:CPU", [("main", [("bench.traced_window", 0.0, 1000e6, {})])]),
              ("/device:TPU:0", [
                  ("XLA Ops", [op("fusion.1", 1, 3), op("fusion.2", 5, 2), op("fusion.3", 8, 1), op("fusion.4", 10, 4),
                               op("splash.5", 20, 15), op("splash.6", 130, 31), op("splash.7", 400, 10),
                               op("splash.8", 600, 12), op("fusion.9", 700, 7)]),
                  ("XLA Modules", [("jit_train_x(1)", 0.0, 1000e6, {})]),
              ])]
    monkeypatch.setattr(program_trace, "traced_planes", lambda ctx: planes)
    monkeypatch.setattr(program_trace, "program_monitor", lambda: _Monitor(**{"lowering.routers_before_attention": 4}))
    cfg, job = cfg_and_job()

    def ctx():
        return {"executables": [_Compiled()], "model": smallthinker, "config": cfg, "traffic": job, "peaks": PEAKS}

    assert pre_router_ms_per_step.read(ctx()) == pytest.approx(2 + 1)
    causal_ms, window_ms = 4 + 15 + 31, 10 + 12
    least = attention_roofline_share.least_seconds(smallthinker.causal_attention_flops(cfg, job),
                                                   smallthinker.causal_attention_bytes(cfg, job), PEAKS)
    assert causal_attention_roofline_share.read(ctx()) == pytest.approx(100.0 * least / (causal_ms / 1e3))
    assert 50.0 < causal_attention_roofline_share.read(ctx()) < 100.0
    least = attention_roofline_share.least_seconds(smallthinker.window_attention_flops(cfg, job),
                                                   smallthinker.window_attention_bytes(cfg, job), PEAKS)
    assert window_attention_roofline_share.read(ctx()) == pytest.approx(100.0 * least / (window_ms / 1e3))
    # a run without executables, a trace or the scope, or a model without the arithmetic: nothing
    assert causal_attention_roofline_share.read(dict(ctx(), model=object())) is None
    for reader in (causal_attention_roofline_share, pre_router_ms_per_step):
        assert reader.read(dict(ctx(), executables=[])) is None
    # no router ahead of an attention (a parent, another cell): the router reader has nothing of its own to read
    monkeypatch.setattr(program_trace, "program_monitor", lambda: _Monitor())
    assert pre_router_ms_per_step.read(ctx()) is None
    monkeypatch.setattr(program_trace, "traced_planes", lambda ctx: None)
    monkeypatch.setattr(program_trace, "program_monitor", lambda: _Monitor(**{"lowering.routers_before_attention": 4}))
    for reader in (causal_attention_roofline_share, pre_router_ms_per_step):
        assert reader.read(ctx()) is None


@pytest.mark.parametrize("counted,value", [({"lowering.routers_before_attention": 4}, 4), ({"lowering.routers_before_attention": 0}, None),
                                           ({}, None)])
def test_routers_before_attention_reads_the_counter_and_prints_what_the_routers_chose(monkeypatch, counted, value, capsys):
    from benchmark import program_trace

    monkeypatch.setattr(program_trace, "program_monitor", lambda: _Monitor(**counted))
    assert routers_before_attention.read({"traffic": {"warmup_steps": 4}}) == value
    lines = [json.loads(line) for line in capsys.readouterr().out.splitlines()]
    if value:   # the window's logged steps (from step 4 on), layer by layer
        assert lines == [{"info": "moe_routing", "logged_steps": 2, "held_rows_share": [0.125, 0.125], "held_rows_share_max": 0.13}]
    else:
        assert lines == []
    assert routers_before_attention.read({}) is None
