"""The benchmark's own arithmetic and manifest, checked without a device:
what every later PR is measured with must itself be right, and must stay
addable-to without edits (benchmark/manifest.py finds files by name)."""
import copy
import importlib
import os
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmark import arith, manifest as mf, peaks  # noqa: E402

MIX = [{"p": 0.70, "low": 1, "high": 1}, {"p": 0.25, "low": 2, "high": 8},
       {"p": 0.05, "low": 9, "high": 32}]


def test_schedule_is_a_pure_function_of_the_seed():
    a = arith.fixed_work_schedule(7, 250.0, 20.0, MIX, 64, t_from=2.0)
    b = arith.fixed_work_schedule(7, 250.0, 20.0, MIX, 64, t_from=2.0)
    c = arith.fixed_work_schedule(8, 250.0, 20.0, MIX, 64, t_from=2.0)
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x, y)
    assert not np.array_equal(a[0], c[0]) and not np.array_equal(a[1], c[1])


def test_schedule_offers_the_same_work_whatever_the_seed():
    due, rows, offset = arith.fixed_work_schedule(1, 250.0, 20.0, MIX, 64, t_from=2.0)
    due2, rows2, _ = arith.fixed_work_schedule(2, 250.0, 20.0, MIX, 64, t_from=2.0)
    assert len(due) == len(due2) == 5000
    assert rows.sum() == rows2.sum()
    assert sorted(rows) == sorted(rows2)
    assert (np.diff(due) >= 0).all() and due[0] >= 2.0 and due[-1] < 22.0
    assert rows.min() == 1 and rows.max() == 32
    assert ((offset >= 0) & (offset + rows <= 64)).all()
    # the classes' exact shares, and the mean the traffic file states
    assert (rows == 1).sum() == 3500 and (rows >= 9).sum() == 250
    assert abs(rows.mean() - 2.975) < 0.03


def test_schedule_refuses_a_request_larger_than_the_pool():
    with pytest.raises(ValueError, match="does not fit"):
        arith.fixed_work_schedule(1, 1000.0, 1.0, MIX, 16)


@pytest.mark.parametrize("n,q,ok", [(1000, 99, True), (999, 99, False),
                                    (20, 50, True), (19, 50, False),
                                    (200, 95, True), (150, 95, False)])
def test_percentile_refuses_fewer_than_ten_samples_beyond_it(n, q, ok):
    samples = np.arange(1, n + 1, dtype=float)
    if ok:
        got = arith.percentile(samples[::-1], q)
        assert got == np.ceil(q / 100 * n)  # nearest rank, order-free
    else:
        with pytest.raises(ValueError, match="ten are needed"):
            arith.percentile(samples, q)


def test_samples_per_s_on_a_synthetic_completion_series():
    # a step every 250 ms; the window [10, 20] cuts a step at either end
    t_done = 9.9 + 0.25 * np.arange(60)
    r = arith.samples_per_s(t_done, 256, 10.0, 20.0)
    assert r["samples_per_s"] == pytest.approx(1024.0)
    assert r["step_ms_p50"] == pytest.approx(250.0)
    assert r["n_steps"] == 40
    # one slow step lowers the rate by exactly its share
    slow = np.concatenate([t_done[:20], t_done[20:] + 0.5])
    r2 = arith.samples_per_s(slow, 256, 10.0, 20.0)
    assert r2["n_steps"] == 38
    assert r2["samples_per_s"] == pytest.approx(256 * 37 / (37 * 0.25 + 0.5))
    with pytest.raises(ValueError, match="too few"):
        arith.samples_per_s([10.1, 10.2], 256, 10.0, 20.0)


def test_peaks_table_raises_on_an_unknown_device_kind():
    assert peaks.peaks_for("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.peaks_for("TPU v9 imaginary")
    with pytest.raises(KeyError):
        peaks.peaks_for("cpu")


def test_manifest_passes_its_own_checks():
    m = mf.load()
    assert mf.problems(m) == []
    assert sum(w["chips"] == 4 for w in m["workloads"]) <= 1
    assert len(open(os.path.join(REPO, "BENCHMARK.json")).read()) < 64 * 1024


@pytest.mark.parametrize("break_it,expect", [
    (lambda m: m["workloads"][0].update(config="nope"), "unknown config"),
    (lambda m: m["workloads"][0].update(traffic="nope"), "no file benchmark/traffic/nope.json"),
    (lambda m: m["workloads"][1].update(chips=4), "cells ask for 4 chips"),
    (lambda m: m["end_to_end"][0].update(unit="tokens per second"), "unit"),
    (lambda m: m["per_layer"][0].update(name="has space"), "outside the allowed characters"),
    (lambda m: m["per_layer"][0].update(moves="nothing"), "moves unknown metric"),
    (lambda m: m["end_to_end"][0].update(bound=0.5), "bound"),
    (lambda m: m.update(run_seconds=52), "run_seconds"),
    (lambda m: m["end_to_end"].pop(), "no setup_s"),
], ids=["config", "traffic", "chips", "unit", "name", "moves", "bound",
        "run_seconds", "setup_s"])
def test_manifest_check_catches(break_it, expect):
    m = copy.deepcopy(mf.load())
    break_it(m)
    assert any(expect in p for p in mf.problems(m)), mf.problems(m)


def test_every_named_file_is_found_by_its_name():
    m = mf.load()
    for c in m["configs"]:
        cfg = mf.read_json(c["file"])
        model = mf.model_module(cfg)
        assert callable(model.reference) and callable(model.flops_per_sample)
        assert cfg["reduced"] == c["reduced"]
    for w in m["workloads"]:
        traffic = mf.read_json(mf.traffic_path(w["traffic"]))
        assert callable(mf.runner_module(traffic).run)
    for metric in m["per_layer"]:
        reader = mf.reader_module(metric["name"])
        declared = (reader.LAYER, reader.UNIT, reader.BETTER, reader.SOURCE, reader.MOVES)
        assert declared == tuple(metric[k] for k in ("layer", "unit", "better", "source", "moves"))
        # a reader that finds nothing to read returns nothing
        empty = {"trace": {}, "stats": {}, "monitor": {}, "end_to_end": {},
                 "executables": [], "config": {}, "traffic": {}, "cell": {},
                 "model": None, "peaks": {}}
        assert reader.read(empty) is None


def test_flops_per_sample_at_the_published_sizes():
    m = mf.load()
    by_name = {c["name"]: mf.read_json(c["file"]) for c in m["configs"]}
    bert = importlib.import_module("benchmark.models.bert")
    job = mf.read_json(mf.traffic_path("pretrain-s128"))
    # 12 x (2 x 7.08e6 weights + attention) + LM head, x3, x128 tokens
    assert bert.flops_per_sample(by_name["bert-base"], job) == pytest.approx(85.04e9, rel=1e-3)
    resnet = importlib.import_module("benchmark.models.resnet")
    # 4.09e9 multiply-adds forward (He et al.'s 3.8e9 is the v1 variant)
    assert resnet.flops_per_sample(by_name["resnet50"], {}) / 6 == pytest.approx(4.09e9, rel=1e-2)


def test_the_command_refuses_a_machine_without_the_chip():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, "-m", "benchmark.run", "--workload", "bert-base.pretrain-s128",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=300)
    assert out.returncode != 0
    assert "tpu chip" in out.stderr and "'cpu'" in out.stderr, out.stderr[-2000:]
    assert '"correct"' not in out.stdout, "printed a result without a chip"
