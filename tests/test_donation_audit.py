"""tools/donation_audit.py: the static buffer-donation audit over compiled
train steps, the planted-defect classes it must catch, and the bench-side
frozen-vs-subresolution param classification it informs (ISSUE 7's
resolution of the r5 chip record's '18/198 BERT params frozen').

Also covers the ratcheted bench-round gate (perf_report --check-bench) and
the warmup-until-stable bench windowing (tools/bench_kit.timed_steps),
which together make the MFU floors trustworthy."""
import json

import numpy as np
import pytest

from tools import donation_audit as da


# --------------------------------------------------------------------------
# the zoo donates everything (the ISSUE-7 acceptance gate, tier-1-wired)
# --------------------------------------------------------------------------


def test_zoo_donates_every_persistable_update():
    """Zero non-donated persistable updates across the model zoo — the
    static proof that the r5 chip record's 18 'frozen' BERT params were a probe
    artifact (sub-bf16-resolution updates), not a donation drop."""
    reports = da.audit_zoo(tiny=True)
    assert sorted(reports) == ["bert", "deepfm", "mnist", "nmt", "resnet50"]
    for name, r in reports.items():
        assert r["clean"], (name, r)
        assert r["donated"] == r["persistable_written"] > 0, (name, r)


def test_check_cli_exit_codes(capsys):
    assert da.main(["--check", "--tiny", "--program", "mnist"]) == 0
    out = capsys.readouterr()
    assert "OK" in out.err


# --------------------------------------------------------------------------
# planted defects: each non-donated class must be named
# --------------------------------------------------------------------------


def _mlp_program():
    import paddle_tpu as fluid

    main, startup = fluid.Program(), fluid.Program()
    startup.random_seed = main.random_seed = 3
    with fluid.program_guard(main, startup):
        x = fluid.layers.data("x", [8], dtype="float32")
        y = fluid.layers.data("y", [1], dtype="float32")
        loss = fluid.layers.mean(
            fluid.layers.square_error_cost(fluid.layers.fc(x, 1), y))
        fluid.optimizer.Adam(1e-3).minimize(loss)
    return main, startup, loss


def _feed():
    rng = np.random.RandomState(0)
    return {"x": rng.rand(4, 8).astype("f4"),
            "y": rng.rand(4, 1).astype("f4")}


def test_clean_mlp_baseline():
    main, startup, loss = _mlp_program()
    r = da.audit_program(main, startup, _feed(), [loss.name])
    assert not r["copied_not_read"] and not r["copied_aval_drift"]
    assert not r["never_updated"]
    assert r["donated"] == r["persistable_written"]


def test_written_but_never_read_is_flagged():
    """A persistable written without being read sits outside the donation
    set entirely — the silently-double-buffered class."""
    main, startup, loss = _mlp_program()
    block = main.global_block()
    v = block.create_var("aux_counter", shape=(1,), dtype="float32",
                         persistable=True)
    # write it from a fresh constant: written, never read
    c = block.create_var("aux_src")
    block.append_op("fill_constant", inputs={}, outputs={"Out": [c.name]},
                    attrs={"shape": [1], "dtype": "float32", "value": 1.0})
    block.append_op("assign", inputs={"X": [c.name]},
                    outputs={"Out": [v.name]}, attrs={})
    r = da.audit_program(main, startup, _feed(), [loss.name])
    assert "aux_counter" in r["copied_not_read"]
    assert not r["clean"] if "clean" in r else True


def test_aval_drift_is_flagged():
    """A read+written persistable whose written dtype differs from the
    resident buffer cannot be aliased by XLA — the r5 bf16+Adam freeze
    class (optimizer lowerings now pin their output dtypes, so the plant
    needs an explicit cast writing back over the var)."""
    main, startup, loss = _mlp_program()
    # startup initializes `drifter` as f32; the main block declares it f16
    # and cast-writes it in place, so the step reads f32 and writes f16
    startup.global_block().create_var("drifter", shape=(4,), dtype="float32",
                                      persistable=True)
    startup.global_block().append_op(
        "fill_constant", inputs={}, outputs={"Out": ["drifter"]},
        attrs={"shape": [4], "dtype": "float32", "value": 1.0})
    block = main.global_block()
    block.create_var("drifter", shape=(4,), dtype="float16",
                     persistable=True)
    block.append_op("cast", inputs={"X": ["drifter"]},
                    outputs={"Out": ["drifter"]},
                    attrs={"out_dtype": "float16", "in_dtype": "float16"})
    r = da.audit_program(main, startup, _feed(), [loss.name])
    assert "drifter" in r["copied_aval_drift"], r


def test_never_updated_param_is_flagged():
    """A trainable param the optimizer does not touch is genuinely frozen
    (vs. the bench probe's sub-resolution artifact)."""
    import paddle_tpu as fluid

    main, startup = fluid.Program(), fluid.Program()
    startup.random_seed = main.random_seed = 3
    with fluid.program_guard(main, startup):
        x = fluid.layers.data("x", [8], dtype="float32")
        y = fluid.layers.data("y", [1], dtype="float32")
        used = fluid.layers.fc(x, 1)
        fluid.layers.fc(x, 1)  # params exist, excluded from the update
        loss = fluid.layers.mean(fluid.layers.square_error_cost(used, y))
        fluid.optimizer.Adam(1e-3).minimize(
            loss, parameter_list=[p.name for p in main.all_parameters()
                                  if p.name.startswith("fc_0")])
    r = da.audit_program(main, startup, _feed(), [loss.name])
    assert r["never_updated"], r
    assert any(n.startswith("fc_1") for n in r["never_updated"])


# --------------------------------------------------------------------------
# bench-side classification: frozen (dead optimizer state) vs subresolution
# --------------------------------------------------------------------------


class _FakeDispatch:
    def __init__(self, after, moments):
        self._after, self._moments = after, moments

    def probe_param(self):
        return dict(self._after)

    def probe_moments(self):
        return dict(self._moments)


def test_params_moved_subresolution_vs_frozen():
    """A zero param delta with a LIVE first-order moment is a
    sub-resolution update (bf16 q/k stall), not a dropped update; a dead
    moment alongside a dead param fails the bench outright."""
    from bench import _params_moved

    before = {"a": np.zeros(4), "b": np.ones(4)}
    # a: moved; b: still but moment live -> subresolution
    ok = _params_moved(
        _FakeDispatch({"a": np.full(4, 0.1), "b": np.ones(4)},
                      {"a": np.full(4, 0.5), "b": np.full(4, 1e-3)}),
        before, max_frozen_frac=0.6)
    assert ok["frozen"] == 0 and ok["subresolution"] == 1

    # b still AND moment dead -> dropped-update class, hard failure
    with pytest.raises(AssertionError, match="DEAD optimizer state"):
        _params_moved(
            _FakeDispatch({"a": np.full(4, 0.1), "b": np.ones(4)},
                          {"a": np.full(4, 0.5), "b": np.zeros(4)}),
            before)


def test_params_moved_subresolution_budget():
    from bench import _params_moved

    before = {f"p{i}": np.ones(2) for i in range(4)}
    after = dict(before)          # nothing moved except p0
    after["p0"] = np.full(2, 2.0)
    moments = {n: np.full(2, 1e-4) for n in before}
    with pytest.raises(AssertionError, match="below update resolution"):
        _params_moved(_FakeDispatch(after, moments), before,
                      max_frozen_frac=0.25)


# --------------------------------------------------------------------------
# perf_report --check-bench: the ratcheted MFU floors
# --------------------------------------------------------------------------


def _round_doc(resnet_mfu=0.20, bert_mfu=0.45, nmt_spread=2.0, frozen=0,
               overlap=None):
    models = {
        "bert": {"metric": "bert_base_train_seqs_per_sec_per_chip",
                 "value": 1000.0, "mfu_bf16_analytic": bert_mfu,
                 "spread_pct": 0.5,
                 "params_moved": {"frozen": frozen, "subresolution": 18,
                                  "total": 198}},
        "nmt": {"metric": "transformer_nmt_train_seqs_per_sec_per_chip",
                "value": 1400.0, "spread_pct": nmt_spread},
    }
    if overlap is not None:
        models["overlap"] = overlap
    return {"metric": "resnet50_train_imgs_per_sec_per_chip", "value": 2800.0,
            "extra": {"mfu_bf16_analytic": resnet_mfu, "spread_pct": 0.4,
                      "models": models}}


def _check(tmp_path, doc, **kw):
    from tools.perf_report import check_bench

    p = tmp_path / "bench.json"
    p.write_text(json.dumps(doc))
    return check_bench(str(p), **kw)


def test_check_bench_passes_above_floors(tmp_path):
    assert _check(tmp_path, _round_doc()) == 0


def test_check_bench_fails_below_resnet_floor(tmp_path):
    # the floor is EXCLUSIVE: tying r05's 0.168 is not enough
    assert _check(tmp_path, _round_doc(resnet_mfu=0.168)) == 1
    assert _check(tmp_path, _round_doc(resnet_mfu=0.12)) == 1


def test_check_bench_fails_below_bert_floor(tmp_path):
    assert _check(tmp_path, _round_doc(bert_mfu=0.40)) == 1
    assert _check(tmp_path, _round_doc(bert_mfu=0.402)) == 0  # inclusive


def test_check_bench_fails_on_spread(tmp_path):
    assert _check(tmp_path, _round_doc(nmt_spread=26.3)) == 1
    assert _check(tmp_path, _round_doc(nmt_spread=26.3),
                  max_spread_pct=30.0) == 0


def test_check_bench_fails_on_frozen_params(tmp_path):
    assert _check(tmp_path, _round_doc(frozen=3)) == 1


def test_check_bench_fails_on_resnet_frozen_params(tmp_path):
    """The flagship's params_moved rides the round wrapper's extra (not
    extra.models), so the dead-optimizer-state gate must fire there too."""
    doc = _round_doc()
    doc["extra"]["params_moved"] = {"frozen": 2, "subresolution": 0,
                                    "total": 161}
    assert _check(tmp_path, doc) == 1


def test_check_bench_overlap_record(tmp_path):
    good = {"metric": "dp_grad_overlap_ab_steps_per_sec", "value": 6.3,
            "speedup_vs_serial": 1.07, "overlap_confirmed": True,
            "bit_parity_serial_vs_bucketed": True}
    assert _check(tmp_path, _round_doc(overlap=good)) == 0
    # unconfirmed overlap (the off-device parity-only record bench.py
    # produces on CPU gloo) passes by default — embedding the parity
    # evidence must not fail the round — but --require-overlap demands a
    # confirmed device record
    unconfirmed = dict(good, overlap_confirmed=False)
    assert _check(tmp_path, _round_doc(overlap=unconfirmed)) == 0
    assert _check(tmp_path, _round_doc(overlap=unconfirmed),
                  require_overlap=True) == 1
    # broken bit-parity fails unconditionally — bucketing changed numerics
    noparity = dict(good, bit_parity_serial_vs_bucketed=False)
    assert _check(tmp_path, _round_doc(overlap=noparity)) == 1


def _serving_round_doc(within_atol=True, gate_event=True):
    serve = {"metric": "serving_closed_loop_rps", "value": 2091.0,
             "device": "cpu", "mfu_bf16_analytic": 1e-06,
             "mfu_predicted_roofline": 0.0096}
    return {"metric": "serving_quant_ab_rps", "value": 2481.0,
            "device": "cpu",
            "throughput_claim": "parity_only_off_device",
            "parity": {"max_abs_diff": 7.8e-4, "atol": 0.05,
                       "within_atol": within_atol,
                       "gate_event_recorded": gate_event},
            "mfu_predicted_roofline": 0.0096,
            "extra": {"models": {"serving_closed_loop": serve}}}


def test_check_bench_serving_only_round(tmp_path, capsys):
    """A round with only serving_* records skips the training MFU floors
    (loudly) but still prints the measured-vs-predicted roofline line and
    the off-device honesty NOTE, and enforces the quant parity ledger."""
    assert _check(tmp_path, _serving_round_doc()) == 0
    out = capsys.readouterr().out
    assert "serving-only round" in out
    assert "MFU floors skipped" in out
    assert "no throughput or MFU floor may ratchet" in out
    assert "quant parity ledger clean" in out
    assert "vs static roofline" in out
    assert "no bench record to hold its MFU floor" not in out


def test_check_bench_serving_round_dirty_parity_fails(tmp_path, capsys):
    assert _check(tmp_path, _serving_round_doc(within_atol=False)) == 1
    assert "quant parity ledger DIRTY" in capsys.readouterr().out


def test_check_bench_serving_round_ungated_quant_fails(tmp_path, capsys):
    assert _check(tmp_path, _serving_round_doc(gate_event=False)) == 1
    assert "no quant_parity event" in capsys.readouterr().out


def test_check_bench_mixed_round_still_holds_floors(tmp_path):
    """A serving record riding a training round must NOT flip the round
    to serving-only — the training floors still hold (and still fail)."""
    doc = _round_doc(resnet_mfu=0.12)
    doc["extra"]["models"]["serving"] = _serving_round_doc()
    assert _check(tmp_path, doc) == 1


def test_bench_r06_serving_round_passes():
    """The committed BENCH_r06.json is a serving-only parity round: it
    must clear --check-bench as-is (floors skipped, ledger clean)."""
    import os

    from tools.perf_report import check_bench

    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert check_bench(os.path.join(here, "BENCH_r06.json")) == 0


def test_check_bench_reads_round_wrapper(tmp_path):
    doc = {"n": 9, "tail": "noise\n" + json.dumps(_round_doc()) + "\n"}
    assert _check(tmp_path, doc) == 0


# --------------------------------------------------------------------------
# warmup-until-stable bench windowing (tools/bench_kit.timed_steps)
# --------------------------------------------------------------------------


def _fake_clock(durations_ms):
    """Clock yielding windows of the given durations: timed_steps calls it
    twice per window (start, end)."""
    t = [0.0]
    seq = iter(durations_ms)
    state = {"open": False, "dur": None}

    def clock():
        if not state["open"]:
            state["open"] = True
            state["dur"] = next(seq)
            return t[0]
        state["open"] = False
        t[0] += state["dur"] / 1e3
        return t[0]

    return clock


def test_timed_steps_extends_past_warm_in():
    """The r5 chip record NMT shape: a slow first window (compile/cache warm-in)
    must be treated as extended warmup, not evidence — windows extend until
    the trailing 3 agree, and exactly those are reported."""
    from tools.bench_kit import timed_steps

    calls = [0]

    def dispatch():
        calls[0] += 1
        return [np.zeros(1)]

    dt, _, ws = timed_steps(dispatch, K=1, n_warm=1, iters=1, windows=3,
                            spread_target=5.0,
                            clock=_fake_clock([30.0, 23.0, 23.1, 23.0]))
    assert ws == [23.0, 23.1, 23.0]
    assert dt == pytest.approx(0.023)


def test_timed_steps_budget_returns_honest_trailing_windows():
    """When the budget runs out before stabilizing, the trailing windows
    come back as-is — the caller's spread gate sees the honest noise."""
    from tools.bench_kit import timed_steps

    durations = [10.0 + 5 * (i % 2) for i in range(12)]  # never stabilizes
    dt, _, ws = timed_steps(lambda: [np.zeros(1)], K=1, n_warm=1, iters=1,
                            windows=3, spread_target=5.0, max_windows=6,
                            clock=_fake_clock(durations))
    assert len(ws) == 3
    from tools.bench_kit import spread_pct

    assert spread_pct(ws) > 5.0


def test_timed_steps_no_target_keeps_fixed_windows():
    from tools.bench_kit import timed_steps

    dt, _, ws = timed_steps(lambda: [np.zeros(1)], K=1, n_warm=1, iters=1,
                            windows=2, clock=_fake_clock([9.0, 11.0]))
    assert ws == [9.0, 11.0]
