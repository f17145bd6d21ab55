"""PR 69's cell rehearsed tiny on the CPU, its configuration against the
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
from benchmark.metrics import (attention_roofline_share, causal_attention_roofline_share, gdn_ms_per_step, kda_ms_per_step,
                               kda_scan_roofline_share, scalar_decay_scans)
from benchmark.models import lfm2, qwen3_next

from test_benchmark_rehearsal import check_line, run_cell, tiny_root  # noqa: F401

CELL = "qwen3-next-80b-a3b-instruct.train-gdn-s16384"
CONFIG = "benchmark/configs/qwen3-next-80b-a3b-instruct.json"
TRAFFIC = "benchmark/traffic/train-gdn-s16384.json"
#: the per-layer metrics this cell brought: each lists it alone
OWN_METRICS = ("gdn_ms_per_step", "scalar_decay_scans")
#: ... and the lists it joined
JOINED = ("kda_scan_roofline_share", "kda_state_decay_mean", "causal_attention_roofline_share", "flash_attention_ms_per_step",
          "attention_gate_ms_per_step", "gated_attention_layers", "held_experts_ms_per_step", "recompute_ms_per_step",
          "recompute_kept_bytes_share", "model_flops_util", "peak_hbm_gb", "update_ms_per_step", "device_idle_share",
          "dispatch_ms_per_step", "recompiles_in_window", "loader_wait_share", "host_blocked_share", "next_batch_wait_share",
          "reader_stage_share", "slow_step_share", "idle_host_active_share", "idle_unattributed_share")
TINY_NEW = {
    CONFIG: dict(hidden_size=32, vocab_size=64, head_dim=16, num_attention_heads=4, num_key_value_heads=2, linear_num_key_heads=2,
                 linear_num_value_heads=4, linear_key_head_dim=8, linear_value_head_dim=8, moe_intermediate_size=16,
                 shared_expert_intermediate_size=16, num_routed_experts=32, num_experts=8, num_experts_per_tok=10),
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
    monkeypatch.setattr(qwen3_next, "ATTENTION_SAMPLE", 48)
    return tiny_root


@pytest.mark.parametrize("trace", [0, 1])
def test_the_cell_rehearsed_tiny_on_the_cpu(tiny_root_with_the_cell, trace, capsys):
    result = run_cell(tiny_root_with_the_cell, CELL, trace, 2)
    check_line(result, CELL, trace)
    lines = [json.loads(line) for line in capsys.readouterr().out.splitlines() if line.startswith('{"info"')]
    routing = [line for line in lines if line["info"] == "reference_routing"]
    assert len(routing) == 1 and routing[0]["routed_differently_above_margin"] == 0 and routing[0]["failed_limits"] == []
    found = routing[0]
    assert found["router_choice_differs"] == 0 and found["router_prob_error"] <= qwen3_next.ROUTER_RTOL
    assert found["scan_error"] <= qwen3_next.SCAN_RTOL and found["conv_error"] <= qwen3_next.CONV_RTOL
    assert found["attention_error"] <= qwen3_next.ATTENTION_RTOL and found["qk_error"] <= qwen3_next.QK_RTOL
    assert found["gated_error"] <= qwen3_next.GATED_RTOL < found["gated_error_no_gate"]
    assert found["shared_error"] <= qwen3_next.SHARED_RTOL < found["shared_error_no_gate"]
    assert len(found["held_rows_share"]) == 4 and len(found["qk_errors"]) == 4          # the four sparse layers; q0, k0, q3, k3
    if trace:  # the program's counters, no device needed
        assert result["metrics"]["scalar_decay_scans"]["value"] == 3.0
        assert result["metrics"]["gated_attention_layers"]["value"] == 1.0
        assert result["metrics"]["recompute_kept_bytes_share"]["value"] == 100.0      # the CPU reports no limit: all is kept
        assert result["metrics"]["recompiles_in_window"]["value"] == 0
        assert 0.2 < result["metrics"]["kda_state_decay_mean"]["value"] < 1.0
        forms = [line for line in lines if line["info"] == "attention_forms"]
        # (`monitor.reset()` keeps the NAMES an earlier file of this worker counted under: its further layers read 0)
        assert len(forms) == 1 and forms[0]["rotary_tables"] == 1
        assert forms[0]["query_heads_by_layer"][0] == 4 and not any(forms[0]["query_heads_by_layer"][1:])
        moe = [line for line in lines if line["info"] == "moe_routing"]
        assert moe and len(moe[0]["held_rows_share"]) == 4          # published from inside the four recomputed segments


def test_the_manifest_holds_the_configuration_and_the_cell_and_gains_no_problem():
    m = mf.load()
    assert mf.problems(m) == []
    cell = mf.cell(m, CELL)      # membership, never position: a later PR appends after it
    assert cell["chips"] == 1 and (cell["config"], cell["traffic"]) == ("qwen3-next-80b-a3b-instruct", "train-gdn-s16384")
    config = next(x for x in m["configs"] if x["name"] == cell["config"])
    for text in (cell["why"], config["why"], config["source"]):
        assert 1 <= len(text) <= 200 and text.isprintable()
    assert "16384" in cell["why"] and "32x" in cell["why"] and "320 rows" in cell["why"] and "3 scalar-decay" in cell["why"]
    for name in OWN_METRICS:
        metric = next(x for x in m["per_layer"] if x["name"] == name)
        reader = mf.reader_module(name)
        assert metric["workloads"] == [CELL] or CELL in metric["workloads"]
        assert (metric["unit"], metric["better"], metric["source"], metric["layer"], metric["moves"]) == \
            (reader.UNIT, reader.BETTER, reader.SOURCE, reader.LAYER, reader.MOVES)
        assert reader.read({}) is None  # an empty context (a parent without the scopes or counters): nothing, no error
    reported = {x["name"] for x in mf.metrics_of(m, CELL, "per_layer")}
    assert set(OWN_METRICS) | set(JOINED) <= reported
    # Kimi Linear's operator's scope, the generic readers that misread a step with three-line splash calls in it (PERF.md,
    # defects 4a, 13b) and the readers other cells' tests pin to their one cell (13a)
    assert not reported & {"kda_ms_per_step", "fwd_ms_per_step", "bwd_ms_per_step", "scoped_time_share", "device_roofline_share",
                           "moe_ms_per_step", "attention_ms_per_step", "expert_gemm_roofline_share", "held_expert_rows_share"}
    assert {x["name"] for x in mf.metrics_of(m, CELL, "end_to_end")} == {"train_samples_per_s", "setup_s"}
    assert CELL in next(x for x in m["end_to_end"] if x["name"] == "train_samples_per_s")["workloads"]
    assert sum(w["chips"] == 4 for w in m["workloads"]) == 3 and len(m["workloads"]) >= 17


def test_the_configuration_keeps_every_published_number_but_the_keys_it_says():
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog beside the model-configs guide here")
    with open(catalog) as f:
        row = next((r for r in map(json.loads, f) if r["name"] == "Qwen3-Next-80B-A3B-Instruct"), None)
    if row is None:
        pytest.skip("the catalog here has no such row")
    cfg = mf.read_json(CONFIG)
    differs = sorted(k for k, v in row["config"].items() if cfg.get(k, "absent") != v)
    assert differs == sorted(cfg["reduced"]) == ["num_experts", "num_hidden_layers", "vocab_size"]
    assert cfg["reduced_from"] == {k: row["config"][k] for k in ("num_experts", "vocab_size", "num_hidden_layers")}
    # the widths, by name: none is cut
    assert (cfg["hidden_size"], cfg["linear_num_key_heads"], cfg["linear_num_value_heads"], cfg["linear_key_head_dim"],
            cfg["linear_value_head_dim"], cfg["linear_conv_kernel_dim"], cfg["num_attention_heads"], cfg["num_key_value_heads"],
            cfg["head_dim"], cfg["partial_rotary_factor"], cfg["rope_theta"], cfg["moe_intermediate_size"],
            cfg["shared_expert_intermediate_size"], cfg["num_experts_per_tok"], cfg["num_routed_experts"], cfg["intermediate_size"],
            cfg["rms_norm_eps"], cfg["max_position_embeddings"], cfg["full_attention_interval"], cfg["norm_topk_prob"]) == \
        (2048, 16, 32, 128, 128, 4, 16, 2, 256, 0.25, 10000000, 512, 512, 10, row["config"]["num_experts"], 5120, 1e-6, 262144, 4, True)
    # the floors: one whole period of four layers, at least 8 experts, an eighth of the rows
    assert cfg["num_hidden_layers"] == 4 == len(cfg["layer_types"]) == cfg["full_attention_interval"]
    assert cfg["layer_types"] == ["gated_delta_net" if (i + 1) % cfg["full_attention_interval"] else "full_attention" for i in range(4)]
    assert qwen3_next.held(cfg) == (0, 16) and cfg["vocab_size"] * 8 == row["config"]["vocab_size"]
    entry = next(c for c in mf.load()["configs"] if c["name"] == "qwen3-next-80b-a3b-instruct")
    assert entry["source"] == row["source_url"] == cfg["source"] and entry["reduced"] == cfg["reduced"]
    assert "THIRTY-TWO chips share each layer" in cfg["deployment"] and "5.09 GB" in cfg["deployment"] and "9.96 GB" in cfg["deployment"]
    assert {"norm_gains", "multi_token_prediction", "aux_losses", "column_layout", "decay", "linear_layers", "rotary", "attention",
            "router", "layer_types", "intermediate_size", "routing_seed", "optimizer", "compute_dtype", "weights", "data"} <= set(cfg["assumed"])
    assert cfg["parameters"] == 424340544


def test_the_departures_are_the_docstrings_word_for_word():
    listed = qwen3_next.__doc__.split("word for word):")[1]
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
    pattern = mf.read_json("benchmark/traffic/train-gated-swa-s16384.json")
    assert set(job) == set(pattern) and {k for k in job if job[k] != pattern[k]} == {"what"}


# -- the arithmetic kept with the benchmark ------------------------------------

def cfg_and_job():
    return mf.read_json(CONFIG), mf.read_json(TRAFFIC)


PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def test_the_scans_and_the_attentions_flops_and_bytes_by_hand():
    tiny = dict(layer_types=["gated_delta_net", "full_attention", "gated_delta_net"], linear_num_key_heads=2, linear_num_value_heads=6,
                linear_key_head_dim=8, linear_value_head_dim=8, linear_conv_kernel_dim=4, num_attention_heads=6, num_key_value_heads=2, head_dim=16)
    job = dict(seq_len=128, batch_per_chip=5)
    C, K = 64, 8
    a_key_head = 2 * C * C * K                                                    # the two Grams, a triangle each
    a_value_head = 2 * C * C * K + 4 * C * K * K + 2 * C * C * K + 2 * K ** 3 + 2 * C * K * K
    assert qwen3_next.kda_scan_flops(tiny, job) == 3.0 * 2 * (2 * a_key_head + 6 * a_value_head) * (5 * 128) / C
    assert qwen3_next.kda_scan_bytes(tiny, job) == 2 * (2 * 2 * 8 * 2 + 2 * 6 * 8 * 2 + 2 * 6 * 4) * 5 * 128 * 2
    assert qwen3_next.causal_attention_flops(tiny, job) == 6 * 2 * 6 * 16 * (128 * 129 // 2) * 5
    assert qwen3_next.causal_attention_bytes(tiny, job) == 2 * 2 * (2 * 6 + 2 * 2) * 16 * 128 * 5
    cfg, job = cfg_and_job()
    scan = attention_roofline_share.least_seconds(qwen3_next.kda_scan_flops(cfg, job), qwen3_next.kda_scan_bytes(cfg, job), PEAKS)
    assert 0.0048 < scan < 0.0050        # 0.97 TFLOP at the bf16 peak: 4.9 ms for the three layers, forward and backward
    full = attention_roofline_share.least_seconds(qwen3_next.causal_attention_flops(cfg, job), qwen3_next.causal_attention_bytes(cfg, job), PEAKS)
    assert 0.0334 < full < 0.0336        # 6.60 TFLOP: 33.5 ms for the one full layer
    # a decay written out over the channels or keys repeated in HBM is more bytes for the same NEEDED work: it reads lower
    wide = dict(cfg, linear_num_key_heads=32)
    assert qwen3_next.kda_scan_bytes(wide, job) > qwen3_next.kda_scan_bytes(cfg, job)


def test_flops_per_sample_at_the_published_sizes():
    cfg, job = cfg_and_job()
    d, seq = 2048, 16384
    sparse = 2 * d * 512 + 3 * 2 * d * 512 + 2 * d + (10 * 16 / 512) * 3 * 2 * d * 512
    linear = 2 * d * 12288 + 2 * d * 64 + 2 * 4096 * d + qwen3_next._chunk_flops(1, 16, 32, 128)
    full = 2 * d * 8192 + 2 * 2 * d * 512 + 2 * 4096 * d
    forward = seq * 2 * d * 18992 + 3 * seq * (linear + sparse) + seq * (full + sparse) + 2 * 2 * 16 * 256 * (seq * (seq + 1) // 2)
    assert qwen3_next.flops_per_sample(cfg, job) == pytest.approx(3.0 * forward, rel=1e-12)
    assert 25.9e12 < 3.0 * forward < 26.2e12
    assert 0.24 < 2 * 2 * 16 * 256 * (seq * (seq + 1) // 2) / forward < 0.27       # the full layer's scores: a quarter
    assert 0.36 < 3 * seq * (2 * d * 12288 + 2 * 4096 * d) / forward < 0.40        # the linear layers' projections
    assert 4 * (10 * 16 / 512) * 3 * 2 * d * 512 * seq / forward < 0.02            # the held experts: under 2%


# -- the readers -----------------------------------------------------------------

HLO = '''
  %fusion.1 = bf16[1,16384,12288]{2,1,0} fusion(%a, %w), kind=kOutput, calls=%f1, metadata={op_name="jit(train_x)/jvp(fwd)/checkpoint/gated_delta_net/op9:mul/dot_general"}
  %scan.2 = bf16[1,16384,4096]{2,1,0} custom-call(%q, %k, %v), custom_call_target="tpu_custom_call", metadata={op_name="jit(train_x)/jvp(fwd)/checkpoint/gated_delta_net/op25:kda/kda_chunk_scan/jit(scan)/kda_scan/pallas_call"}
  %scan.3 = bf16[1,16384,4096]{2,1,0} custom-call(%q, %k, %v), custom_call_target="tpu_custom_call", metadata={op_name="jit(train_x)/transpose(jvp(fwd))/checkpoint/rematted_computation/gated_delta_net_1/op71:kda/kda_chunk_scan/jit(scan)/kda_scan/pallas_call"}
  %scan.4 = bf16[1,16384,2048]{2,1,0} custom-call(%q, %k, %v), custom_call_target="tpu_custom_call", metadata={op_name="jit(train_x)/transpose(jvp(fwd))/checkpoint/gated_delta_net_2/op117:kda/kda_chunk_scan/jit(scan_transposed)/kda_scan_transposed/pallas_call"}
  %fusion.5 = bf16[1,16384,4096]{2,1,0} fusion(%o, %z), kind=kLoop, calls=%f5, metadata={op_name="jit(train_x)/jvp(fwd)/checkpoint/gated_delta_net/op33:elementwise_mul/mul"}
  %fusion.6 = bf16[1,16384,4096]{2,1,0} fusion(%o, %g), kind=kLoop, calls=%f6, metadata={op_name="jit(train_x)/jvp(fwd)/checkpoint/attention_gate_1/op160:elementwise_mul/mul"}
  %splash.7 = bf16[1,16,16384,256]{3,2,1,0} custom-call(%q, %k, %v), custom_call_target="tpu_custom_call", frontend_attributes={kernel_metadata="{
   }"},
   metadata={op_name="jit(train_x)/jvp(fwd)/checkpoint/op150:fused_attention/block_sparse_attention/splash_mha_fwd"}
  %fusion.8 = bf16[8192,512]{1,0} fusion(%r, %w), kind=kOutput, calls=%f8, metadata={op_name="jit(train_x)/jvp(fwd)/checkpoint/op30:moe_experts/expert_gemm/gmm"}
  %fusion.9 = bf16[1,4096,4096]{2,1,0} fusion(%a, %w), kind=kOutput, calls=%f9, metadata={op_name="jit(train_x)/jvp(fwd)/checkpoint/kda/op9:mul/dot_general"}
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
        return []


def test_the_operators_device_time_reader_and_the_joined_roofline_shares_by_hand(monkeypatch):
    """Own time by instruction: what stands under a scope `gated_delta_net`,
    numbered or not, forward, made again and backward, is the operator's: its
    projection, its scans, its gated norm's product; the attention's gate, the
    attention kernel, an expert's grouped product and Kimi Linear's `kda` scope
    are not."""
    from benchmark import program_trace

    def op(name, start_ms, ms):
        return (f"%{name} = bf16[1]{{0}} fusion(%a)", start_ms * 1e6, ms * 1e6, {})

    planes = [("/host:CPU", [("main", [("bench.traced_window", 0.0, 1000e6, {})])]),
              ("/device:TPU:0", [
                  ("XLA Ops", [op("fusion.1", 1, 3), op("scan.2", 5, 12), op("scan.3", 20, 12.5), op("scan.4", 40, 30), op("fusion.5", 80, 1.25),
                               op("fusion.6", 90, 1.0), op("splash.7", 130, 31), op("fusion.8", 700, 7), op("fusion.9", 800, 2)]),
                  ("XLA Modules", [("jit_train_x(1)", 0.0, 1000e6, {})]),
              ])]
    monkeypatch.setattr(program_trace, "traced_planes", lambda ctx: planes)
    monkeypatch.setattr(program_trace, "program_monitor", lambda: _Monitor(**{"lowering.scalar_decay_scans": 3}))
    cfg, job = cfg_and_job()

    def ctx():
        return {"executables": [_Compiled()], "model": qwen3_next, "config": cfg, "traffic": job, "peaks": PEAKS}

    assert gdn_ms_per_step.read(ctx()) == pytest.approx(3 + 12 + 12.5 + 30 + 1.25)
    assert kda_ms_per_step.read(ctx()) == pytest.approx(2.0)                       # Kimi Linear's operator's scope: not this cell's
    least = attention_roofline_share.least_seconds(qwen3_next.kda_scan_flops(cfg, job), qwen3_next.kda_scan_bytes(cfg, job), PEAKS)
    assert kda_scan_roofline_share.read(ctx()) == pytest.approx(100.0 * least / ((12 + 12.5 + 30) / 1e3))
    least = attention_roofline_share.least_seconds(qwen3_next.causal_attention_flops(cfg, job), qwen3_next.causal_attention_bytes(cfg, job), PEAKS)
    assert causal_attention_roofline_share.read(ctx()) == pytest.approx(100.0 * least / (31 / 1e3))
    assert gdn_ms_per_step.read(dict(ctx(), executables=[])) is None
    monkeypatch.setattr(program_trace, "traced_planes", lambda ctx: None)
    assert gdn_ms_per_step.read(ctx()) is None


@pytest.mark.parametrize("counted,value", [({"lowering.scalar_decay_scans": 3}, 3), ({"lowering.scalar_decay_scans": 0}, None), ({}, None)])
def test_scalar_decay_scans_reads_the_counter_and_nothing_where_it_is_absent_or_zero(monkeypatch, counted, value):
    from benchmark import program_trace

    monkeypatch.setattr(program_trace, "program_monitor", lambda: _Monitor(**counted))
    assert scalar_decay_scans.read({"traffic": {"warmup_steps": 4}}) == value
    assert scalar_decay_scans.read({}) is None
