"""PR 38's cell rehearsed tiny on the CPU, its configuration against the
catalog row, its arithmetic against a hand count, and its three per-layer
readers on hand-built inputs, one of them a `while` that encloses its body.

The rehearsal builds on `tiny_root` of test_benchmark_rehearsal.py: the
cell's configuration and traffic files are written, cut down, into the same
scratch root.  As there, no number of a CPU run means anything.
"""
import json
import os
import re

import pytest

from benchmark import manifest as mf
from benchmark.metrics import (attention_roofline_share, exit_loss_ms_per_step, exit_mass_last_pass_share,
                               recompute_ms_per_step)
from benchmark.models import ouro

from test_benchmark_rehearsal import check_line, run_cell, tiny_root  # noqa: F401

CELL = "ouro-2.6b.train-ut4-s4096"
CONFIG = "benchmark/configs/ouro-2.6b.json"
TRAFFIC = "benchmark/traffic/train-ut4-s4096.json"
OWN_METRICS = ("recompute_ms_per_step", "exit_loss_ms_per_step", "exit_mass_last_pass_share")
TINY_NEW = {
    CONFIG: dict(hidden_size=64, num_attention_heads=4, num_key_value_heads=4, head_dim=16, intermediate_size=96,
                 vocab_size=96, layer_types=["full_attention"] * 2, num_hidden_layers=2),
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
    monkeypatch.setattr(ouro, "LOGIT_SAMPLE", 8)
    return tiny_root


@pytest.mark.parametrize("trace", [0, 1])
def test_the_cell_rehearsed_tiny_on_the_cpu(tiny_root_with_the_cell, trace, capsys):
    result = run_cell(tiny_root_with_the_cell, CELL, trace, 2)
    check_line(result, CELL, trace)
    lines = [json.loads(line) for line in capsys.readouterr().out.splitlines() if line.startswith('{"info"')]
    exits = [line for line in lines if line["info"] == "reference_exits"]
    assert len(exits) == 1 and len(exits[0]["logit_error_by_exit"]) == 4
    assert exits[0]["gate_error"] <= ouro.GATE_ATOL < exits[0]["gate_error_bf16"]
    assert exits[0]["pass2_error"] <= ouro.STAGE_RTOL and exits[0]["exit_p_error"] <= ouro.EXIT_P_ATOL
    if trace:  # the program's records, no device needed
        assert 5.0 < result["metrics"]["exit_mass_last_pass_share"]["value"] < 25.0    # a gate at its start: an eighth
        assert result["metrics"]["recompiles_in_window"]["value"] == 0


def test_the_manifest_holds_the_cell_and_nothing_is_wrong_with_it():
    m = mf.load()
    assert mf.problems(m) == []
    cell = mf.cell(m, CELL)
    assert cell["chips"] == 1 and (cell["config"], cell["traffic"]) == ("ouro-2.6b", "train-ut4-s4096")
    config = next(x for x in m["configs"] if x["name"] == cell["config"])
    for text in (cell["why"], config["why"], config["source"]):
        assert 1 <= len(text) <= 200 and text.isprintable()
    for name in OWN_METRICS:
        metric = next(x for x in m["per_layer"] if x["name"] == name)
        reader = mf.reader_module(name)
        assert CELL in metric["workloads"]   # membership: a later cell may join (PERF.md, defect 13a)
        assert (metric["unit"], metric["better"], metric["source"], metric["layer"], metric["moves"]) == \
            (reader.UNIT, reader.BETTER, reader.SOURCE, reader.LAYER, reader.MOVES)
        assert reader.read({}) is None  # an empty context: nothing, and no error
    reported = {x["name"] for x in mf.metrics_of(m, CELL, "per_layer")}
    assert set(OWN_METRICS) | {"model_flops_util", "peak_hbm_gb", "device_idle_share", "update_ms_per_step",
                               "recompiles_in_window", "setup_lower_s"} <= reported
    # readers that sum a `while` over its body, or count its body once, would read wrong here (PERF.md, defects 4a, 13b)
    assert not {"fwd_ms_per_step", "bwd_ms_per_step", "scoped_time_share", "device_roofline_share"} & reported
    assert {x["name"] for x in mf.metrics_of(m, CELL, "end_to_end")} == {"train_samples_per_s", "setup_s"}


def test_the_configuration_keeps_every_published_number_but_the_three_it_says():
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog beside the model-configs guide here")
    with open(catalog) as f:
        row = next((r for r in map(json.loads, f) if r["name"] == "Ouro-2.6B"), None)
    if row is None:
        pytest.skip("the catalog here has no row Ouro-2.6B")
    cfg = mf.read_json(CONFIG)
    differs = sorted(k for k, v in row["config"].items() if cfg.get(k, "absent") != v)
    assert differs == sorted(cfg["reduced"]) == ["layer_types", "num_hidden_layers", "vocab_size"]
    assert cfg["reduced_from"] == {k: row["config"][k] for k in cfg["reduced"]}
    # the widths and the trip count, by name: none is cut
    assert (cfg["hidden_size"], cfg["intermediate_size"], cfg["num_attention_heads"], cfg["num_key_value_heads"],
            cfg["head_dim"], cfg["total_ut_steps"], cfg["rms_norm_eps"], cfg["rope_theta"]) == \
        (2048, 5632, 16, 16, 128, 4, 1e-6, 1000000)
    # the floors: four layers at the least (all are alike), an eighth of the rows
    assert cfg["layer_types"] == row["config"]["layer_types"][:8] and cfg["num_hidden_layers"] == 8 >= 4
    assert cfg["vocab_size"] * 4 == row["config"]["vocab_size"]
    entry = next(c for c in mf.load()["configs"] if c["name"] == "ouro-2.6b")
    assert entry["source"] == row["source_url"] == cfg["source"] and entry["reduced"] == cfg["reduced"]
    assert "six stages of eight layers" in cfg["deployment"]
    assert {"sandwich_norms", "final_norm_place", "exit_gate", "objective", "exit_entropy_beta", "optimizer",
            "weights", "data"} <= set(cfg["assumed"])
    assert cfg["exit_entropy_beta"] == 0.05


def test_the_departures_are_the_docstrings_word_for_word():
    listed = ouro.__doc__.split("word for word):")[1].split("\n\nThe reference:")[0]
    items = [re.sub(r"\s+", " ", d.strip().rstrip(";.")) for d in listed.split("  * ")[1:]]
    assert items == mf.read_json(CONFIG)["departures"]
    assert len(items) == 7


def test_the_traffic_is_the_issues():
    job = mf.read_json(TRAFFIC)
    assert (job["kind"], job["seq_len"], job["batch_per_chip"], job["learning_rate"], job["lr_warmup_steps"],
            job["lr_warmup_start"]) == ("train", 4096, 1, 1e-4, 200, 1e-6)
    assert (job["adam_beta1"], job["adam_beta2"], job["adam_epsilon"]) == (0.9, 0.95, 1e-8)
    assert (job["ring"], job["loader_capacity"], job["max_inflight"], job["log_period"], job["warmup_steps"],
            job["trace_seconds"]) == (64, 2, 2, 8, 4, 4.0)


def test_flops_per_sample_at_the_published_sizes():
    cfg, job = mf.read_json(CONFIG), mf.read_json(TRAFFIC)
    d = 2048
    layer = 4 * 2 * d * d + 3 * 2 * d * 5632 + 4 * 2048 * (4096 + 1) / 2    # four projections, the feed-forward, two causal products
    per_pass = 8 * layer + 2 * d * 12288                                    # eight layers and the exit's head
    assert ouro.flops_per_sample(cfg, job) == 3.0 * 4096 * 4 * per_pass
    assert abs(ouro.flops_per_sample(cfg, job) / 4096 - 12.1e9) < 0.1e9     # the issue's ~12.1 GFLOP a token
    assert 0.045 < 3 * 4 * 2 * d * 12288 / (ouro.flops_per_sample(cfg, job) / 4096) < 0.055    # the four heads: 5%
    # a layer is 51.39 M parameters, the state 461.4 M
    assert abs((4 * d * d + 3 * d * 5632 + 4 * d) / 1e6 - 51.39) < 0.01


# -- the readers -----------------------------------------------------------------

def test_exit_mass_last_pass_share_reads_the_windows_logged_steps():
    def record(step, mass, ce=(9.4, 9.4, 9.4, 9.4)):
        return {"kind": "loop_exit", "pipeline_step": step, "exit_mass": list(mass), "entropy": 1.2, "exit_ce": list(ce)}

    records = [record(0, (0.1, 0.1, 0.1, 0.7)), {"kind": "pipeline_step", "pipeline_step": 8},
               record(8, (0.5, 0.25, 0.125, 0.125)), record(16, (0.5, 0.3, 0.1, 0.1)), record(24, (0.4, 0.3, 0.1, 0.2))]
    assert exit_mass_last_pass_share.last_pass_share(records, 4) == pytest.approx(12.5)     # step 0 is warm-up
    assert exit_mass_last_pass_share.last_pass_share([], 4) is None
    assert exit_mass_last_pass_share.last_pass_share([{"kind": "moe_routing", "pipeline_step": 8}], 4) is None
    with pytest.raises(AssertionError, match="sum to"):
        exit_mass_last_pass_share.last_pass_share(records + [record(32, (0.5, 0.3, 0.1, 0.2))], 4)
    with pytest.raises(AssertionError, match="cross entropies"):
        exit_mass_last_pass_share.last_pass_share(records + [record(32, (0.5, 0.3, 0.1, 0.1), ce=(9.4, float("nan"), 1, 1))], 4)
    assert exit_mass_last_pass_share.read({"traffic": {}}) is None


HLO = '''
  %while.1 = (s32[], bf16[1,4096,2048]{2,1,0}) while(%t), condition=%cond, body=%body, metadata={op_name="jit(train_x)/jvp(fwd)/op3:repeat/while"}
  %fusion.1 = bf16[4096,5632]{1,0} fusion(%a, %w), kind=kOutput, calls=%f1, metadata={op_name="jit(train_x)/jvp(fwd)/op3:repeat/while/body/checkpoint/loop_pass/op19:mul/dot_general"}
  %fusion.2 = bf16[16384,12288]{1,0} fusion(%h, %w), kind=kOutput, calls=%f2, metadata={op_name="jit(train_x)/jvp(fwd)/exit_head/op4:mul/dot_general"}
  %while.2 = (s32[], bf16[1,4096,2048]{2,1,0}) while(%t2), condition=%cond2, body=%body2, metadata={op_name="jit(train_x)/transpose(jvp(fwd))/op3:repeat/while"}
  %fusion.3 = bf16[4096,5632]{1,0} fusion(%a, %w), kind=kOutput, calls=%f3, metadata={op_name="jit(train_x)/transpose(jvp(fwd))/op3:repeat/while/body/checkpoint/rematted_computation/loop_pass/op19:mul/dot_general"}
  %fusion.4 = bf16[4096,2048]{1,0} fusion(%g, %w), kind=kOutput, calls=%f4, metadata={op_name="jit(train_x)/transpose(jvp(fwd))/op3:repeat/while/body/checkpoint/loop_pass/op19:mul/transpose[permutation=(1, 0)]"}
  %fusion.5 = f32[2048,12288]{1,0} fusion(%g, %h), kind=kOutput, calls=%f5, metadata={op_name="jit(train_x)/transpose(jvp(fwd))/exit_head/op4:mul/dot_general"}
  %fusion.6 = bf16[4096,2048]{1,0} fusion(%x, %g), kind=kInput, calls=%f6, metadata={op_name="jit(train_x)/transpose(jvp(fwd))/op3:repeat/while/body/checkpoint/rematted_computation/loop_pass/op51:rms_norm/mul"}
  %fusion.7 = f32[4,1,4096,1]{3,2,1,0} fusion(%z, %y), kind=kInput, calls=%f7, metadata={op_name="jit(train_x)/jvp(fwd)/exit_loss/op6:softmax_with_cross_entropy/reduce_sum"}
  %fusion.8 = f32[2048,2048]{1,0} fusion(%p, %g), kind=kLoop, calls=%f8, metadata={op_name="jit(train_x)/update/op40:adam/mul"}
  %fusion.9 = bf16[4096,2048]{1,0} fusion(%c), kind=kLoop, calls=%f9, metadata={op_name="jit(train_x)/jvp(fwd)/exit_headroom/op9:mul/dot_general"}
'''


class _Compiled:
    def as_text(self):
        return HLO


def test_the_two_time_readers_by_hand_with_a_while_that_encloses_its_body(monkeypatch, capsys):
    from benchmark import program_trace

    again = attention_roofline_share.instructions_under(HLO, recompute_ms_per_step.SCOPE)
    assert again == {"fusion.3", "fusion.6"}
    names = recompute_ms_per_step.op_names(HLO)
    assert len(names) == 11 and names["fusion.8"].endswith("op40:adam/mul")

    def op(name, start_ms, ms):
        return (f"%{name} = bf16[1]{{0}} fusion(%a)", start_ms * 1e6, ms * 1e6, {})

    # one run of the step: the forward `while` (14 ms) encloses four passes of fusion.1; the four exits' head and
    # cross entropies; the head's backward; the backward `while` (36 ms) encloses the recomputed forward and the
    # transposes; then Adam
    forward = [op("while.1", 0, 14)] + [op("fusion.1", 3.5 * t, 3) for t in range(4)]
    exits = [op("fusion.2", 15, 6), op("fusion.7", 22, 4), op("fusion.5", 27, 8)]
    backward = [op("while.2", 36, 36)] + [e for t in range(4) for e in (
        op("fusion.3", 36 + 9 * t, 3), op("fusion.6", 39 + 9 * t, 0.5), op("fusion.4", 40 + 9 * t, 5))]
    rest = [op("fusion.8", 85, 10), op("fusion.9", 96, 2)]
    window = ("bench.traced_window", 0.0, 100e6, {})
    planes = [("/host:CPU", [("main", [window])]),
              ("/device:TPU:0", [("XLA Ops", forward + exits + backward + rest),
                                 ("XLA Modules", [("jit_train_x(1)", 0.0, 100e6, {})])])]
    own = dict()
    for name, ns in recompute_ms_per_step.own_times(planes[1][1][0][1], (0.0, 100e6)):
        own[name] = own.get(name, 0.0) + ns / 1e6
    assert own["while.1"] == pytest.approx(14 - 4 * 3) and own["while.2"] == pytest.approx(36 - 4 * 8.5)
    assert sum(own.values()) == pytest.approx(14 + 18 + 36 + 10 + 2)          # the busy time, nothing counted twice
    monkeypatch.setattr(program_trace, "traced_planes", lambda ctx: planes)
    ctx = {"executables": [_Compiled()]}
    assert recompute_ms_per_step.read(ctx) == pytest.approx(4 * 3.5)
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["info"] == "loop_device_time" and line["own_ms_per_step"] == pytest.approx(80.0)
    assert line["by_scope"]["mul.again"] == pytest.approx(12.0) and line["by_scope"]["adam.fwd"] == pytest.approx(10.0)
    assert line["by_scope"]["rms_norm.again"] == pytest.approx(2.0)
    assert line["by_scope"]["repeat.fwd"] == pytest.approx(2.0) and line["by_scope"]["repeat.bwd"] == pytest.approx(2.0)
    # the exits: the head forward and backward and the cross entropies; not a scope that only begins alike
    assert exit_loss_ms_per_step.read(ctx) == pytest.approx(6 + 4 + 8)
    # a process's second looped model opens the scopes numbered; `exit_headroom` above is none of them
    assert exit_loss_ms_per_step.SCOPE.search("jit(t)/jvp(fwd)/exit_head_1/op4:mul/dot_general")
    # the two add up with the rest to the busy time: recomputed, exits, the whiles' own, forward, backward, Adam, the rest
    assert line["own_ms_per_step"] == pytest.approx(14 + 18 + (2 + 2) + 4 * 3 + 4 * 5 + 10 + 2)
    assert recompute_ms_per_step.read(dict(ctx, executables=[])) is None
    assert exit_loss_ms_per_step.read(dict(ctx, executables=[])) is None
    monkeypatch.setattr(program_trace, "traced_planes", lambda ctx: None)
    assert recompute_ms_per_step.read(ctx) is None and exit_loss_ms_per_step.read(ctx) is None


def test_a_program_that_recomputes_nothing_reports_no_recomputation(monkeypatch):
    from benchmark import program_trace

    class Plain:
        def as_text(self):
            return "\n".join(line for line in HLO.splitlines() if "rematted" not in line)

    planes = [("/host:CPU", [("main", [("bench.traced_window", 0.0, 100e6, {})])]),
              ("/device:TPU:0", [("XLA Ops", [("%fusion.8 = f32[1]{0} fusion(%a)", 1e6, 2e6, {})]),
                                 ("XLA Modules", [("jit_train_x(1)", 0.0, 100e6, {})])])]
    monkeypatch.setattr(program_trace, "traced_planes", lambda ctx: planes)
    assert recompute_ms_per_step.read({"executables": [Plain()]}) is None
    assert exit_loss_ms_per_step.read({"executables": [Plain()]}) is None           # no exit ran in this trace
