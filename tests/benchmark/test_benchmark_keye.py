"""PR 56's cell rehearsed tiny on the CPU, its configuration against the catalog
row, its arithmetic against hand counts, and its four per-layer readers on
hand-built inputs.

The rehearsal builds on `tiny_root` of test_benchmark_rehearsal.py: the cell's
configuration and traffic files are written, cut down, into the same scratch
root.  As there, no number of a CPU run means anything.  Lists are checked by
MEMBERSHIP, never by position or equality: a later PR appends.
"""
import json
import os
import re

import pytest

from benchmark import manifest as mf
from benchmark.metrics import (attention_roofline_share, chunk_pairs_touched_share, selected_attention_roofline_share,
                               sparse_index_ms_per_step, sparse_index_roofline_share)
from benchmark.models import keye, lfm2

from test_benchmark_rehearsal import check_line, run_cell, tiny_root  # noqa: F401

CELL = "keye-vl-2.0-30b-a3b.train-dsa-s16384"
CONFIG = "benchmark/configs/keye-vl-2.0-30b-a3b.json"
TRAFFIC = "benchmark/traffic/train-dsa-s16384.json"
#: the per-layer metrics this cell brought: each lists it, none is pinned to it
OWN_METRICS = ("sparse_index_ms_per_step", "sparse_index_roofline_share", "selected_attention_roofline_share",
               "chunk_pairs_touched_share")
#: ... and the lists it joined
JOINED = ("flash_attention_ms_per_step", "recompute_ms_per_step", "recompute_kept_bytes_share", "model_flops_util",
          "peak_hbm_gb", "update_ms_per_step", "device_idle_share", "dispatch_ms_per_step", "recompiles_in_window",
          "loader_wait_share", "host_blocked_share", "next_batch_wait_share", "reader_stage_share", "slow_step_share",
          "idle_host_active_share", "idle_unattributed_share")
TINY_NEW = {
    CONFIG: dict(hidden_size=64, num_attention_heads=4, num_key_value_heads=2, head_dim=16, moe_intermediate_size=32,
                 num_experts=2, num_routed_experts=8, experts_held_first=2, num_experts_per_tok=2, vocab_size=96,
                 num_hidden_layers=2, rope_scaling=dict(mrope_section=[2, 3, 3]),
                 sa_config=dict(indexer_head_dim=16, indexer_num_heads=4, indexer_num_kv_heads=1, kv_chunk_size=512,
                                q_chunk_size=512, topk=8)),
    TRAFFIC: dict(seq_len=32, batch_per_chip=2, ring=4, trace_seconds=0.8),
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
    assert len(routing) == 1 and routing[0]["failed_limits"] == []
    assert routing[0]["picks_miscounted"] == routing[0]["picks_after_query"] == 0
    assert routing[0]["attention_error"] <= keye.ATTENTION_RTOL < routing[0]["attention_error_dense"]
    assert routing[0]["alignment_error"] <= keye.ALIGNMENT_RTOL and routing[0]["index_kl"] > 0
    assert len(routing[0]["attention_errors"]) == len(routing[0]["alignment_errors"]) == 2    # the first and the last layer
    if trace:  # the program's counters and records, no device needed
        assert result["metrics"]["recompute_kept_bytes_share"]["value"] == 100.0      # the CPU reports no limit: all is kept
        assert result["metrics"]["chunk_pairs_touched_share"]["value"] == 100.0       # 32 tokens are one chunk
        assert result["metrics"]["recompiles_in_window"]["value"] == 0
        chosen = [line for line in lines if line["info"] == "sparse_index"]
        # published from inside the two recomputed segments: 2 rows x (1 + ... + 8 + 24 x 8) picks a layer
        assert chosen and chosen[0]["picks"] == [2 * 228, 2 * 228] and len(chosen[0]["index_kl"]) == 2


def test_the_manifest_holds_the_configuration_and_the_cell_and_nothing_is_wrong_with_it():
    m = mf.load()
    assert mf.problems(m) == []
    cell = mf.cell(m, CELL)      # membership, never position: a later PR appends after it
    assert cell["chips"] == 1 and (cell["config"], cell["traffic"]) == ("keye-vl-2.0-30b-a3b", "train-dsa-s16384")
    config = next(x for x in m["configs"] if x["name"] == cell["config"])
    for text in (cell["why"], config["why"], config["source"]):
        assert 1 <= len(text) <= 200 and text.isprintable()
    assert "16384" in cell["why"] and "steps" in cell["why"] and "2048" in cell["why"]
    for name in OWN_METRICS:
        metric = next(x for x in m["per_layer"] if x["name"] == name)
        reader = mf.reader_module(name)
        assert CELL in metric["workloads"]
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


def test_the_configuration_keeps_every_published_number_but_the_three_it_says():
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog beside the model-configs guide here")
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "Keye-VL-2.0-30B-A3B")
    cfg = mf.read_json(CONFIG)
    differs = sorted(k for k, v in row["config"].items() if cfg.get(k, "absent") != v)
    assert differs == sorted(cfg["reduced"]) == ["num_experts", "num_hidden_layers", "vocab_size"]
    assert cfg["reduced_from"] == {k: row["config"][k] for k in cfg["reduced"]}
    # the widths, by name: none is cut, and `sa_config` is whole
    assert (cfg["hidden_size"], cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"],
            cfg["moe_intermediate_size"], cfg["intermediate_size"], cfg["num_experts_per_tok"], cfg["rope_theta"],
            cfg["rms_norm_eps"], cfg["num_routed_experts"], cfg["num_local_experts"]) == \
        (2048, 32, 4, 128, 768, 6144, 8, 10000000, 1e-6, row["config"]["num_experts"], 128)
    assert cfg["sa_config"] == row["config"]["sa_config"] == dict(
        indexer_head_dim=64, indexer_num_heads=16, indexer_num_kv_heads=1, kv_chunk_size=512, q_chunk_size=512, topk=2048)
    assert cfg["rope_scaling"] == row["config"]["rope_scaling"] and cfg["rope_scaling"]["mrope_section"] == [16, 24, 24]
    assert keye.indexer(cfg) == dict(heads=16, head_dim=64, topk=2048)
    # the floors: four layers (all alike: one is a period), 8 experts or more, an eighth of the rows
    assert cfg["num_hidden_layers"] == 4 and cfg["decoder_sparse_step"] == 1 and cfg["mlp_only_layers"] == []
    assert keye.held(cfg) == (0, 16) and cfg["vocab_size"] * 8 == row["config"]["vocab_size"]
    assert cfg["num_experts"] * 8 == row["config"]["num_experts"]
    entry = next(c for c in mf.load()["configs"] if c["name"] == "keye-vl-2.0-30b-a3b")
    assert entry["source"] == row["source_url"] == cfg["source"] and entry["reduced"] == cfg["reduced"]
    assert "eight chips share each layer" in cfg["deployment"] and "7.45 GB" in cfg["deployment"]
    assert any("vision tower" in d for d in cfg["departures"]) and any("text positions" in d for d in cfg["departures"])
    assert {"qk_norm", "rotary", "indexer_queries", "indexer_key", "indexer_weights", "indexer_rotary",
            "indexer_kernel_details", "chunk_sizes", "ties", "loss", "optimizer", "weights", "data", "aux_losses",
            "compute_dtype", "routing_seed"} <= set(cfg["assumed"])


def test_the_parameter_sum_is_the_files():
    """The program built from the file has 465.4 M parameters, counted from its
    own shapes; the file states the same number."""
    import numpy as np

    import paddle_tpu as fluid

    cfg, job = cfg_and_job()
    with fluid.unique_name.guard():
        main = keye.build(cfg, dict(job, seq_len=64))[0]
    sizes = {p.name: int(np.prod(p.shape)) for p in main.all_parameters()}
    total = sum(sizes.values())
    assert total == cfg["parameters"] and abs(total - 465.4e6) < 0.1e6
    assert abs(16 * total / 1e9 - 7.45) < 0.01 and abs(12 * total / 1e9 - 5.58) < 0.01     # 16 bytes a parameter, 12 persistent
    attention = sum(n for name, n in sizes.items() if name.startswith("lm.l3.attn.") and ".index." not in name)
    index = sum(n for name, n in sizes.items() if name.startswith("lm.l3.attn.index."))
    experts = sum(n for name, n in sizes.items() if name.startswith("lm.l1.moe.") and "router" not in name)
    assert (round(attention / 1e6, 2), round(index / 1e6, 2), round(experts / 1e6, 2)) == (18.87, 2.26, 75.50)
    assert sizes["lm.l1.moe.gate.w"] == 16 * 2048 * 768 and sizes["lm.l1.moe.router.w"] == 2048 * 128
    assert (sizes["lm.l0.attn.index.q.w"], sizes["lm.l0.attn.index.k.w"], sizes["lm.l0.attn.index.w.w"]) == \
        (2048 * 16 * 64, 2048 * 64, 2048 * 16)
    assert sizes["lm.l0.attn.index.k_norm.w"] == sizes["lm.l0.attn.index.k_norm.b"] == 64
    assert sizes["lm.tok_emb"] == sizes["lm.head.w"] == 18992 * 2048
    ops = [op.type for op in main.global_block().ops]
    assert ops.count("sparse_index") == ops.count("index_alignment") == ops.count("fused_attention") == 4
    assert ops.count("rotary_embedding") == 16 and ops.count("moe_experts") == 4 and ops.count("stop_gradient") == 4
    segments = {op.attrs.get("recompute_segment") for op in main.global_block().ops} - {None}
    assert segments == {1, 2, 3, 4}                                         # every layer a recompute_scope


def test_the_departures_are_the_docstrings_word_for_word():
    listed = keye.__doc__.split("word for word):")[1]
    items = [re.sub(r"\s+", " ", d.strip().rstrip(";.")) for d in listed.split("  * ")[1:]]
    assert items == mf.read_json(CONFIG)["departures"]
    assert len(items) == 10


def test_the_traffic_is_the_issues_and_kanana2s_value_for_value():
    job, other = mf.read_json(TRAFFIC), mf.read_json("benchmark/traffic/train-mla-s16384.json")
    assert (job["kind"], job["seq_len"], job["batch_per_chip"], job["learning_rate"], job["lr_warmup_steps"],
            job["lr_warmup_start"]) == ("train", 16384, 1, 1e-4, 200, 1e-6)
    assert (job["adam_beta1"], job["adam_beta2"], job["adam_epsilon"]) == (0.9, 0.95, 1e-8)
    assert (job["ring"], job["loader_capacity"], job["max_inflight"], job["log_period"], job["warmup_steps"],
            job["trace_seconds"]) == (64, 2, 2, 8, 4, 2.5)
    assert {k: v for k, v in job.items() if k != "what"} == {k: v for k, v in other.items() if k != "what"}


# -- the arithmetic kept with the benchmark ------------------------------------

def cfg_and_job():
    return mf.read_json(CONFIG), mf.read_json(TRAFFIC)


PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def test_the_chosen_pairs_and_the_four_counts_by_hand():
    assert keye.chosen_pairs(32, 8) == 36 + 24 * 8 and keye.chosen_pairs(6, 8) == 21
    cfg, job = cfg_and_job()
    pairs, triangle = keye.chosen_pairs(16384, 2048), 16384 * 16385 // 2
    assert pairs == 31_458_304 and abs(pairs / triangle - 0.234) < 0.001          # 31.46 M of 134.2 M
    assert keye.index_flops(cfg, job) == triangle * 16 * 2 * 64 * 4
    assert keye.index_bytes(cfg, job) == (17 * 64 * 2 + 4 * 16 + 2048) * 16384 * 4
    assert keye.selected_attention_flops(cfg, job) == 7 * 2 * 128 * pairs * 32 * 4
    assert keye.selected_attention_bytes(cfg, job) == ((72 + 144 + 36) * 128 * 2 + 3 * 2048) * 16384 * 4
    tiny = dict(num_attention_heads=2, num_key_value_heads=1, head_dim=8, num_hidden_layers=3,
                sa_config=dict(indexer_num_heads=2, indexer_head_dim=4, topk=4))
    few = dict(seq_len=8, batch_per_chip=5)
    assert keye.index_flops(tiny, few) == 36 * 2 * 2 * 4 * 3 * 5
    assert keye.selected_attention_flops(tiny, few) == 7 * 2 * 8 * (10 + 16) * 2 * 3 * 5
    # the arithmetic binds both: 5.6 ms of index scores and 36.6 ms of chosen pairs a step at the bf16 peak
    assert attention_roofline_share.least_seconds(keye.index_flops(cfg, job), keye.index_bytes(cfg, job), PEAKS) == \
        pytest.approx(keye.index_flops(cfg, job) / 197e12)
    least = attention_roofline_share.least_seconds(keye.selected_attention_flops(cfg, job),
                                                   keye.selected_attention_bytes(cfg, job), PEAKS)
    assert least == pytest.approx(keye.selected_attention_flops(cfg, job) / 197e12) and 0.036 < least < 0.037


def test_flops_per_sample_at_the_published_sizes():
    cfg, job = cfg_and_job()
    d, seq = 2048, 16384
    block = 2 * d * (2 * 4096 + 2 * 512) + 2 * d * 128 + 1.0 * 3 * 2 * d * 768        # ONE held expert a position
    index = 2 * d * (1024 + 64 + 16)
    pairs, triangle = keye.chosen_pairs(seq, 2048), seq * (seq + 1) // 2
    layer = seq * (3 * block + 2 * index) + pairs * 32 * 7 * 256 + (triangle + 2 * pairs) * 16 * 128
    assert keye.flops_per_sample(cfg, job) == pytest.approx(4 * layer + 3 * seq * 2 * d * 18992, rel=1e-12)
    # the mechanism (the chosen pairs' products, the target, the index scores) is two fifths of what a step REQUIRES
    # (8.8 of 22.6 TFLOP); a form that computes the whole triangle under the mask executes four times the chosen pairs'
    mechanism = 4 * (pairs * 32 * 7 * 256 + (triangle + 2 * pairs) * 16 * 128)
    assert 0.35 < mechanism / keye.flops_per_sample(cfg, job) < 0.45


# -- the readers -----------------------------------------------------------------

HLO = '''
  %fusion.1 = bf16[1,16384,1024]{2,1,0} fusion(%a, %w), kind=kOutput, calls=%f1, metadata={op_name="jit(train_x)/jvp(fwd)/checkpoint/sparse_index/op9:mul/dot_general"}
  %fusion.2 = f32[512,16384]{1,0} fusion(%q), kind=kLoop, calls=%f2, metadata={op_name="jit(train_x)/jvp(fwd)/checkpoint/sparse_index/op14:sparse_index/index_select/while/body/mul"}
  %sort.3 = f32[512,2048]{1,0} fusion(%k), kind=kLoop, calls=%f3, metadata={op_name="jit(train_x)/jvp(fwd)/checkpoint/sparse_index_2/op90:sparse_index/index_select/while/body/top_k"}
  %fusion.4 = bf16[1,32,16384,128]{3,2,1,0} fusion(%q), kind=kLoop, calls=%f4, metadata={op_name="jit(train_x)/jvp(fwd)/checkpoint/op19:fused_attention/selected_attention/mul"}
  %splash.5 = bf16[32,16384,128]{2,1,0} custom-call(%q, %k, %v), custom_call_target="tpu_custom_call", frontend_attributes={kernel_metadata="{
   }"},
   metadata={op_name="jit(train_x)/jvp(fwd)/checkpoint/op19:fused_attention/selected_attention/while/body/splash_mha_fwd"}
  %splash.6 = bf16[32,16384,128]{2,1,0} custom-call(%q, %k, %v, %do), custom_call_target="tpu_custom_call", frontend_attributes={kernel_metadata="{
   }"},
   metadata={op_name="jit(train_x)/transpose(jvp(fwd))/checkpoint/op95:fused_attention/selected_attention/while/body/splash_mha_dkv"}
  %fusion.7 = f32[512,16384]{1,0} fusion(%q), kind=kLoop, calls=%f7, metadata={op_name="jit(train_x)/jvp(fwd)/checkpoint/sparse_index_1/op21:index_alignment/while/body/selected_attention/exp"}
  %fusion.8 = f32[512,16384]{1,0} fusion(%q), kind=kLoop, calls=%f8, metadata={op_name="jit(train_x)/jvp(fwd)/checkpoint/sparse_index_1/op21:index_alignment/while/body/mul"}
  %splash.9 = bf16[1,32,8192,128]{3,2,1,0} custom-call(%q, %k, %v), custom_call_target="tpu_custom_call", frontend_attributes={kernel_metadata="{
   }"},
   metadata={op_name="jit(train_x)/jvp(fwd)/op60:fused_attention/block_sparse_attention/splash_mha_fwd"}
  %fusion.10 = bf16[1,16384,2048]{2,1,0} fusion(%c), kind=kLoop, calls=%f10, metadata={op_name="jit(train_x)/jvp(fwd)/sparse_index_like/op61:mul/dot_general"}
'''


class _Compiled:
    def as_text(self):
        return HLO


def test_the_three_device_time_readers_by_hand(monkeypatch):
    """Own time by instruction: everything under a `sparse_index` scope,
    whichever number it carries, is the indexer's (the projections, the choosing,
    the alignment op and the target inside it); the choosing alone is the
    index's roofline's; the kernels under the stored mask and the alignment's
    target are the selected attention's; an attention under a rule and a scope
    that only begins alike are nobody's; the splash calls' three-line
    instructions are found."""
    from benchmark import program_trace

    def op(name, start_ms, ms):
        return (f"%{name} = bf16[1]{{0}} fusion(%a)", start_ms * 1e6, ms * 1e6, {})

    planes = [("/host:CPU", [("main", [("bench.traced_window", 0.0, 1000e6, {})])]),
              ("/device:TPU:0", [
                  ("XLA Ops", [op("fusion.1", 1, 3), op("fusion.2", 5, 20), op("sort.3", 30, 40), op("fusion.4", 80, 4),
                               op("splash.5", 100, 100), op("splash.6", 230, 250), op("fusion.7", 500, 60),
                               op("fusion.8", 600, 30), op("splash.9", 700, 50), op("fusion.10", 800, 7)]),
                  ("XLA Modules", [("jit_train_x(1)", 0.0, 1000e6, {})]),
              ])]
    monkeypatch.setattr(program_trace, "traced_planes", lambda ctx: planes)
    cfg, job = cfg_and_job()
    ctx = {"executables": [_Compiled()], "model": keye, "config": cfg, "traffic": job, "peaks": PEAKS}
    assert sparse_index_ms_per_step.read(ctx) == pytest.approx(3 + 20 + 40 + 60 + 30)
    least = attention_roofline_share.least_seconds(keye.index_flops(cfg, job), keye.index_bytes(cfg, job), PEAKS)
    assert sparse_index_roofline_share.read(ctx) == pytest.approx(100.0 * least / ((20 + 40) / 1e3))
    least = attention_roofline_share.least_seconds(keye.selected_attention_flops(cfg, job),
                                                   keye.selected_attention_bytes(cfg, job), PEAKS)
    assert selected_attention_roofline_share.read(ctx) == pytest.approx(100.0 * least / ((4 + 100 + 250 + 60) / 1e3))
    assert 0.0 < selected_attention_roofline_share.read(ctx) < 100.0 and 0.0 < sparse_index_roofline_share.read(ctx) < 100.0
    # a run without executables, a trace or the scope, or a model without the arithmetic: nothing
    for reader in (sparse_index_roofline_share, selected_attention_roofline_share):
        assert reader.read(dict(ctx, model=object())) is None
    for reader in (sparse_index_ms_per_step, sparse_index_roofline_share, selected_attention_roofline_share):
        assert reader.read(dict(ctx, executables=[])) is None
    monkeypatch.setattr(program_trace, "traced_planes", lambda ctx: None)
    for reader in (sparse_index_ms_per_step, sparse_index_roofline_share, selected_attention_roofline_share):
        assert reader.read(ctx) is None


def record(step, picks, touched, kl=(0.1, 0.2)):
    return {"kind": "sparse_index", "pipeline_step": step, "picks": picks, "queries": [32] * len(picks),
            "picks_per_query": [p / 32 for p in picks], "recent_share": [1.0] * len(picks),
            "chunk_pairs_touched_share": touched, "index_kl": list(kl)}


def test_chunk_pairs_touched_share_reads_the_records_and_asserts_what_the_program_promises():
    full = 36 + 24 * 8
    records = [record(0, [full, full], [0.1, 0.2]), {"kind": "moe_routing", "pipeline_step": 4, "dropped_tokens": 0},
               record(4, [full, full], [0.5, 0.75]), record(12, [full, full], [0.6, 0.25]), record(20, [full, full], [1.0, 0.3])]
    # the worst layer's, the median over the logged steps from the window's first on (step 0 is the warm-up's)
    assert chunk_pairs_touched_share.touched_share(records, 4, 8, 32) == 75.0
    assert chunk_pairs_touched_share.touched_share([r for r in records if r["kind"] != "sparse_index"], 4, 8, 32) is None
    with pytest.raises(AssertionError, match="picks"):
        chunk_pairs_touched_share.touched_share(records + [record(28, [full, full - 1], [0.5, 0.5])], 4, 8, 32)
    with pytest.raises(AssertionError, match="index_kl"):
        chunk_pairs_touched_share.touched_share(records + [record(28, [full, full], [0.5, 0.5], (0.1, float("nan")))], 4, 8, 32)
    with pytest.raises(AssertionError, match="dropped_tokens"):
        chunk_pairs_touched_share.touched_share(records + [{"kind": "moe_routing", "pipeline_step": 12, "dropped_tokens": 3}],
                                                4, 8, 32)
    # two rows a step: twice the picks
    assert chunk_pairs_touched_share.touched_share(
        [dict(record(4, [2 * full], [0.5]), queries=[64])], 4, 8, 32) == 50.0
    assert chunk_pairs_touched_share.read({}) is None and chunk_pairs_touched_share.read({"traffic": {"warmup_steps": 4}}) is None
