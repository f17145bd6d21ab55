"""Every cell of BENCHMARK.json rehearsed through `benchmark.run.main` at tiny
sizes on the virtual CPU mesh.

The command itself has no CPU mode (test_benchmark_arith.py checks that it
refuses a machine without the chip).  The sizes, the platform the runs are
held to and the peaks row for it are overridden HERE, by the test: the cells'
own configuration and traffic files are copied into a scratch root with a
few numbers made small, and `benchmark.run.PLATFORM` is patched.  What is
rehearsed is the control flow: build, start-up, reference check, warm-up,
window, trace, reduction, readers, the last line's shape.  No number of a
CPU run means anything, and none is asserted on beyond being finite.
"""
import json
import math
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmark import manifest as mf, peaks, run as bench_run  # noqa: E402
from benchmark.models import resnet as resnet_model  # noqa: E402

TINY = {
    "benchmark/configs/bert-base.json": dict(
        hidden_size=64, num_hidden_layers=2, num_attention_heads=4,
        intermediate_size=128, vocab_size=128),
    "benchmark/configs/resnet50.json": dict(image_size=32, num_classes=10),
    "benchmark/traffic/pretrain-s128.json": dict(
        seq_len=16, batch_per_chip=8, trace_seconds=0.8),
    "benchmark/traffic/pretrain-s128-dp4.json": dict(
        seq_len=16, batch_per_chip=8, trace_seconds=0.8),
    # lr 0.1 on 4 images of 32x32 diverges within a few steps
    "benchmark/traffic/train-b256.json": dict(batch_per_chip=4, learning_rate=0.001,
                                              trace_seconds=0.8),
    "benchmark/traffic/serve-steady.json": dict(
        # light enough that a CPU shared with five other test workers keeps
        # up: a p99 still wants its 1000 requests
        buckets=[1, 8, 64], rate_per_s=100.0, warm_seconds=0.5,
        rows_mix=[{"p": 0.9, "low": 1, "high": 1}, {"p": 0.1, "low": 2, "high": 2}],
        trace_seconds=0.8),
}


def manifest_with_planned_cells() -> dict:
    """The manifest with the planned cells' entries added, a bound put where
    theirs is still to be measured."""
    m = mf.load_with_planned()
    for e in m["end_to_end"]:
        if e["bound"] is None:
            e["bound"] = 0.1
    return m


def test_the_planned_cells_fit_the_manifest():
    m = manifest_with_planned_cells()
    assert mf.problems(m) == []
    assert len(m["workloads"]) > len(mf.load()["workloads"])


@pytest.fixture
def tiny_root(tmp_path, monkeypatch):
    """A scratch root holding the manifest and the cells' files cut down,
    the run held to the CPU, the program's monitor and the compile cache
    left as the other tests expect them."""
    import jax

    from paddle_tpu import monitor

    root = str(tmp_path)
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(manifest_with_planned_cells(), f)
    for path, over in TINY.items():
        data = mf.read_json(path)
        data.update(over)
        os.makedirs(os.path.dirname(os.path.join(root, path)), exist_ok=True)
        with open(os.path.join(root, path), "w") as f:
            json.dump(data, f)
    monkeypatch.setattr(bench_run, "PLATFORM", "cpu")
    # the tolerance is set for 224x224 and 1000 classes on the chip; at 32x32
    # and 10 classes the largest logit is small and the same bf16 roundings
    # are 1e-2 to 2e-2 of it
    monkeypatch.setattr(resnet_model, "REFERENCE_RTOL", 5e-2)
    monkeypatch.setitem(peaks.PEAKS, "cpu", dict(peaks.PEAKS["TPU v5 lite"]))
    # keep the cache where conftest.py put it: with the variable set, the
    # one rule (flags.apply_compile_cache) sets nothing in code
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR",
                       jax.config.jax_compilation_cache_dir or os.path.join(root, ".jax_cache"))
    yield root
    monitor.disable()
    monitor.reset()


def run_cell(root, cell, trace, seconds):
    return bench_run.main(["--workload", cell, "--seed", "5", "--seconds", str(seconds),
                           "--trace", str(trace)], root=root)


def check_line(result, cell, trace):
    m = manifest_with_planned_cells()
    assert set(result) >= {"correct", "attempted", "failed", "metrics", "device"}
    assert result["correct"] is True, result
    assert result["attempted"] > 0 and result["failed"] == 0
    assert result["device"]["platform"] == "cpu"
    assert result["device"]["count"] == mf.cell(m, cell)["chips"]
    assert result["device"]["memory_peak_bytes"] > 0
    group = "per_layer" if trace else "end_to_end"
    allowed = {x["name"]: x["unit"] for x in mf.metrics_of(m, cell, group)}
    assert result["metrics"], "no metric in the line"
    for name, v in result["metrics"].items():
        assert name in allowed and v["unit"] == allowed[name]
        assert math.isfinite(v["value"])
    if trace:
        assert set(result["breakdown"]) == {"device_ops", "idle_gaps"}
        assert "setup_compile_s" in result["metrics"]
    else:
        assert set(result["metrics"]) == set(allowed), "every end-to-end metric of the cell"
        assert result["metrics"]["setup_s"]["value"] > 0
    json.dumps(result)  # the last line is one JSON object


@pytest.mark.parametrize("cell,trace,seconds", [
    ("bert-base.pretrain-s128", 0, 2),
    ("bert-base.pretrain-s128", 1, 2),
    ("resnet50.train-b256", 1, 3),
    ("resnet50.serve-steady", 0, 10.5),
    ("resnet50.serve-steady", 1, 10.5),
    ("bert-base.pretrain-s128-dp4", 0, 2),
    ("bert-base.pretrain-s128-dp4", 1, 2),
], ids=lambda v: str(v))
def test_cell_rehearsed_tiny_on_the_cpu(tiny_root, cell, trace, seconds):
    check_line(run_cell(tiny_root, cell, trace, seconds), cell, trace)


def test_a_compile_inside_the_window_is_not_correct(tiny_root, monkeypatch):
    """The benchmark's own count of backend compiles, taken from
    jax.monitoring: one that falls in the window fails the run."""
    real = bench_run.CompileCounter.count_between
    monkeypatch.setattr(bench_run.CompileCounter, "count_between",
                        lambda self, t0, t1: real(self, t0, t1) + 1)
    result = run_cell(tiny_root, "bert-base.pretrain-s128", 0, 2)
    assert result["correct"] is False


def test_the_compile_counter_sees_a_backend_compile():
    import jax
    import jax.numpy as jnp

    from benchmark.runners import common

    counter = bench_run.CompileCounter()
    t0 = common.now()
    jax.jit(lambda x: x * 3 + 1)(jnp.arange(7)).block_until_ready()
    assert counter.count_between(t0, common.now()) >= 1
    assert counter.count_between(common.now(), common.now() + 1) == 0
