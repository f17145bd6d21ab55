"""PR 32's cell rehearsed tiny on the CPU, its configuration against the
catalog row, its arithmetic against hand counts, and its three per-layer
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
from benchmark.metrics import attention_ms_per_step, attention_roofline_share, held_expert_rows_share
from benchmark.models import sdar

from test_benchmark_rehearsal import check_line, run_cell, tiny_root  # noqa: F401

CELL = "sdar-30b-a3b-chat.train-blockdiff-s4096"
CONFIG = "benchmark/configs/sdar-30b-a3b-chat.json"
TINY_NEW = {
    CONFIG: dict(hidden_size=64, num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2, head_dim=32,
                 moe_intermediate_size=32, num_experts=4, num_routed_experts=16, num_experts_per_tok=2,
                 vocab_size=96, routing_seed=0),  # the cell's was chosen for 128 routers' outputs over 2048 features
    "benchmark/traffic/train-blockdiff-s4096.json": dict(seq_len=32, batch_per_chip=4, ring=4, trace_seconds=0.8),
}


@pytest.fixture
def tiny_root_with_the_cell(tiny_root):  # noqa: F811
    for path, over in TINY_NEW.items():
        data = mf.read_json(path)
        data.update(over)
        os.makedirs(os.path.dirname(os.path.join(tiny_root, path)), exist_ok=True)
        with open(os.path.join(tiny_root, path), "w") as f:
            json.dump(data, f)
    return tiny_root


@pytest.mark.parametrize("trace", [0, 1])
def test_the_cell_rehearsed_tiny_on_the_cpu(tiny_root_with_the_cell, trace, capsys):
    result = run_cell(tiny_root_with_the_cell, CELL, trace, 2)
    check_line(result, CELL, trace)
    routing = [json.loads(line) for line in capsys.readouterr().out.splitlines()
               if '"reference_routing"' in line]
    assert len(routing) == 1 and routing[0]["routed_differently_above_margin"] == 0
    assert routing[0]["left_out"] <= sdar.LEFT_OUT_MAX * routing[0]["tokens"]
    assert routing[0]["attention_error"] <= sdar.ATTENTION_RTOL and routing[0]["qk_error"] <= sdar.QK_RTOL
    if trace:  # the program's counter, no device needed
        assert 0.0 < result["metrics"]["held_expert_rows_share"]["value"] < 100.0
        assert result["metrics"]["recompiles_in_window"]["value"] == 0


def test_the_manifest_holds_the_cell_and_nothing_is_wrong_with_it():
    m = mf.load()
    assert mf.problems(m) == []
    cell = mf.cell(m, CELL)
    assert cell["chips"] == 1 and (cell["config"], cell["traffic"]) == ("sdar-30b-a3b-chat", "train-blockdiff-s4096")
    assert len(cell["why"]) <= 200
    config = next(x for x in m["configs"] if x["name"] == cell["config"])
    # the driver holds a configuration's `why` and `source` to the same 200 characters on one line
    for text in (config["why"], config["source"]):
        assert 1 <= len(text) <= 200 and text.isprintable()
    for name in ("attention_ms_per_step", "attention_roofline_share", "held_expert_rows_share"):
        metric = next(x for x in m["per_layer"] if x["name"] == name)
        reader = mf.reader_module(name)
        assert metric["workloads"] == [CELL]
        assert (metric["unit"], metric["better"], metric["source"], metric["layer"], metric["moves"]) == \
            (reader.UNIT, reader.BETTER, reader.SOURCE, reader.LAYER, reader.MOVES)
        assert reader.read({}) is None  # an empty context: nothing, and no error
    reported = {x["name"] for x in mf.metrics_of(m, CELL, "per_layer")}
    # every per-layer metric the five train cells share, and none of the other cells' own
    assert {"model_flops_util", "peak_hbm_gb", "device_roofline_share", "fwd_ms_per_step"} <= reported
    assert not reported & {"moe_ms_per_step", "expert_gemm_roofline_share", "expert_load_max_over_mean",
                           "collective_time_share", "collective_exposed_share"}
    assert {x["name"] for x in mf.metrics_of(m, CELL, "end_to_end")} == {"train_samples_per_s", "setup_s"}


def test_the_configuration_keeps_every_published_number_but_the_three_it_says():
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog beside the model-configs guide here")
    with open(catalog) as f:
        row = next((r for r in map(json.loads, f) if r["name"] == "SDAR-30B-A3B-Chat"), None)
    if row is None:
        pytest.skip("the catalog here has no row SDAR-30B-A3B-Chat")
    cfg = mf.read_json(CONFIG)
    differs = sorted(k for k, v in row["config"].items() if cfg.get(k, "absent") != v)
    assert differs == sorted(cfg["reduced"]) == ["num_experts", "num_hidden_layers", "vocab_size"]
    assert cfg["reduced_from"] == {k: row["config"][k] for k in cfg["reduced"]}
    # the widths, by name: none is cut
    assert (cfg["hidden_size"], cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"],
            cfg["moe_intermediate_size"], cfg["num_experts_per_tok"], cfg["num_routed_experts"]) == \
        (2048, 32, 4, 128, 768, 8, row["config"]["num_experts"])
    assert cfg["vocab_size"] * 8 == row["config"]["vocab_size"] and cfg["num_experts"] * 8 == 128
    assert cfg["num_hidden_layers"] >= 4 and sdar.held(cfg) == (0, 16)
    entry = next(c for c in mf.load()["configs"] if c["name"] == "sdar-30b-a3b-chat")
    assert entry["source"] == row["source_url"] and entry["reduced"] == cfg["reduced"]
    assert "eight chips share each layer" in cfg["deployment"]
    assert {"block_length", "noise_schedule", "qk_norm", "optimizer"} <= set(cfg["assumed"])


def test_the_departures_are_the_docstrings_word_for_word():
    listed = sdar.__doc__.split("word for word):")[1]
    items = [re.sub(r"\s+", " ", d.strip().rstrip(";.")) for d in listed.split("  * ")[1:]]
    assert items == mf.read_json(CONFIG)["departures"]
    assert len(items) == 7


# -- the arithmetic kept with the benchmark ------------------------------------

def cfg_and_job():
    return mf.read_json(CONFIG), mf.read_json("benchmark/traffic/train-blockdiff-s4096.json")


def test_flops_per_sample_at_the_published_sizes():
    cfg, job = cfg_and_job()
    # per position and layer: q and out 2 x 2 x 2048 x 4096, k and v 2 x 2 x 2048 x 512, the router
    # 2 x 2048 x 128, ONE held expert (8 x 16 / 128) of 3 products 2 x 2048 x 768
    per_position = 4 * 2048 * 4096 + 4 * 2048 * 512 + 2 * 2048 * 128 + 6 * 2048 * 768
    # attention per layer: 32 heads x 128 x 2 products x 2 over the allowed pairs, 16 x 1024 x 1025
    pairs = 16 * 1024 * 1025
    attention = 32 * 128 * 4 * pairs
    forward = cfg["num_hidden_layers"] * (8192 * per_position + attention) + 4096 * 2 * 2048 * 18992
    assert sdar.allowed_pairs(4096, 4) == pairs
    assert sdar.flops_per_sample(cfg, job) == 3.0 * forward
    assert cfg["num_hidden_layers"] != 4 or abs(sdar.flops_per_sample(cfg, job) - 8.95e12) < 0.01e12


def test_attention_flops_and_bytes_by_hand():
    tiny = dict(num_attention_heads=4, num_key_value_heads=2, head_dim=8, num_hidden_layers=3, block_length=2)
    job = dict(seq_len=8, batch_per_chip=5)
    # 4 blocks of 2: pairs 4 x (4 + 6 + 10) = 80 of 256; one head: 2 x 80 x 8 = 1280 a product,
    # six products (two forward, four backward); 4 heads, 3 layers, 5 sequences
    assert sdar.allowed_pairs(8, 2) == 80
    assert sdar.attention_flops(tiny, job) == 6 * 1280 * 4 * 3 * 5
    # forward: q and out at 4 heads, k and v at 2: 12 x 16 positions x 8; backward: q, out, their two gradients at 4, k, v and theirs at 2: 24
    assert sdar.attention_bytes(tiny, job, itemsize=2) == (12 + 24) * 16 * 8 * 2 * 3 * 5
    cfg, job = cfg_and_job()
    flops, moved = sdar.attention_flops(cfg, job), sdar.attention_bytes(cfg, job)
    assert flops == 6 * 2 * 16 * 1024 * 1025 * 128 * 32 * cfg["num_hidden_layers"] * 2
    assert flops / 197e12 > 5 * moved / 819e9  # bound by arithmetic on a v5e: 33.5 ms against 4.4


# -- the readers -----------------------------------------------------------------

def test_held_expert_rows_share_reads_the_windows_logged_steps():
    def record(step, shares, dropped=0):
        return {"kind": "moe_routing", "pipeline_step": step, "load_max_over_mean": [1.0],
                "load_min_over_mean": [0.9], "dropped_tokens": dropped, "held_rows_share": shares}

    records = [record(0, [0.9]), {"kind": "pipeline_step", "pipeline_step": 8},
               record(8, [0.11, 0.13]), record(16, [0.12, 0.105]), record(24, [0.15, 0.10])]
    # step 0 is warm-up; per step the worst layer: 13, 12, 15 per cent
    assert held_expert_rows_share.held_rows_share(records, 4) == pytest.approx(13.0)
    assert held_expert_rows_share.held_rows_share([], 4) is None
    # a layer that holds every expert publishes no share (OLMoE's records): nothing
    whole = [{k: v for k, v in r.items() if k != "held_rows_share"} for r in records]
    assert held_expert_rows_share.held_rows_share(whole, 4) is None
    with pytest.raises(AssertionError, match="dropped_tokens"):
        held_expert_rows_share.held_rows_share(records + [record(32, [0.1], dropped=3)], 4)
    assert held_expert_rows_share.read({"traffic": {}}) is None


HLO = '''
  %splash.3 = (f32[2,512,128]{2,1,0}, bf16[2,32,8192,128]{3,2,1,0}) custom-call(%a, %b), custom_call_target="tpu_custom_call", frontend_attributes={kernel_metadata={
"xprof_metadata":"{\\"block_q\\": 1024, \\"block_kv\\": 1024}"
}}, metadata={op_name="jit(train_x)/jvp(fwd)/op14:fused_attention/block_sparse_attention/vmap(jit(_splash_attention))/splash_mha_fwd_residuals/pallas_call" stack_frame_id=61}, backend_config={}
  %dkv.1 = bf16[2,4,8192,128]{3,2,1,0} custom-call(%a, %b), custom_call_target="tpu_custom_call", frontend_attributes={kernel_metadata={
"xprof_metadata":"{}"
}}, metadata={op_name="jit(train_x)/transpose(jvp(fwd))/op14:fused_attention/block_sparse_attention/vmap(jit(_splash_attention))/splash_mha_dkv_no_residuals/pallas_call"}
  %repeat.4 = bf16[2,32,8192,128]{3,2,1,0} fusion(%c), kind=kLoop, calls=%r, metadata={op_name="jit(train_x)/jvp(fwd)/op14:fused_attention/broadcast_in_dim"}
  %fusion.9 = bf16[2,32,8192,128]{3,2,1,0} fusion(%c), kind=kLoop, calls=%f, metadata={op_name="jit(train_x)/jvp(fwd)/op13:rotary_embedding/mul"}
  %fusion.2 = bf16[2,32,8192,128]{3,2,1,0} fusion(%c), kind=kLoop, calls=%g, metadata={op_name="jit(train_x)/jvp(fwd)/op140:fused_attention_like/mul"}
  %gmm.2 = bf16[64,16]{1,0} custom-call(%c), custom_call_target="tpu_custom_call", metadata={op_name="jit(train_x)/jvp(fwd)/op23:moe_experts/expert_gemm/jit(gmm)/pallas_call"}
'''


class _Compiled:
    def as_text(self):
        return HLO


def test_the_two_attention_readers_by_hand(monkeypatch):
    """The kernel's calls run over three lines of the compiled text, their
    `op_name` on the last: both readers find them, `trace_reduce`'s one-line
    pattern does not."""
    from benchmark import program_trace, trace_reduce

    instructions = attention_roofline_share.instructions_under(HLO)
    assert instructions == {"splash.3", "dkv.1"}
    assert attention_roofline_share.instructions_under(HLO, attention_ms_per_step.SCOPE) == {"splash.3", "dkv.1", "repeat.4"}
    assert not {"splash.3", "dkv.1"} & set(trace_reduce.scopes_from_hlo_text(HLO))

    def op(name, start_ms, ms):
        return (f"%{name} = bf16[1]{{0}} custom-call(%a)", start_ms * 1e6, ms * 1e6, {})

    window = ("bench.traced_window", 0.0, 100e6, {})
    planes = [("/host:CPU", [("main", [window])]),
              ("/device:TPU:0", [
                  ("XLA Ops", [op("splash.3", 1, 10), op("dkv.1", 20, 20), op("fusion.9", 50, 7), op("repeat.4", 60, 4),
                               op("splash.3", 95, 10)]),     # half of the last one is past the window
                  ("XLA Modules", [("jit_train_x(1)", 0.0, 50e6, {}), ("jit_train_x(1)", 50e6, 50e6, {})]),
              ])]
    spent = attention_roofline_share.seconds_per_run(planes, instructions)
    assert spent == pytest.approx((10 + 20 + 5) * 1e-3 / 2)
    assert attention_roofline_share.seconds_per_run(planes, set()) is None
    peaks = {"bf16_flops_per_s": 100e12, "hbm_bytes_per_s": 1e12}
    assert attention_roofline_share.least_seconds(1e12, 1e9, peaks) == pytest.approx(0.01)
    assert attention_roofline_share.least_seconds(1e12, 5e10, peaks) == pytest.approx(0.05)
    # through `read`: the program's executables and the run's trace
    cfg, job = cfg_and_job()
    monkeypatch.setattr(program_trace, "traced_planes", lambda ctx: planes)
    ctx = {"executables": [_Compiled()], "model": sdar, "config": cfg, "traffic": job, "peaks": peaks}
    assert attention_ms_per_step.read(ctx) == pytest.approx((10 + 20 + 5 + 4) / 2)
    least = attention_roofline_share.least_seconds(sdar.attention_flops(cfg, job), sdar.attention_bytes(cfg, job), peaks)
    assert attention_roofline_share.read(ctx) == pytest.approx(100.0 * least / spent)
    # a run without executables, a trace or the scope, or a model without the arithmetic: nothing
    assert attention_roofline_share.read(dict(ctx, executables=[])) is None
    assert attention_roofline_share.read(dict(ctx, model=object())) is None
    assert attention_ms_per_step.read(dict(ctx, executables=[])) is None
    monkeypatch.setattr(program_trace, "traced_planes", lambda ctx: None)
    assert attention_ms_per_step.read(ctx) is None and attention_roofline_share.read(ctx) is None
