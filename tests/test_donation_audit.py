"""tools/donation_audit.py: the static buffer-donation audit over compiled
train steps, and the planted-defect classes it must catch: a persistable
written but never read, an update whose shape or dtype drifted from its
input, a parameter the optimizer never updates.  And the dynamic side of the
same question, as `chip_smoke.py` asks it on the chip: a parameter that did
not move is frozen only where its first moment is dead too."""
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


def _mlp_program(optimizer=lambda fluid: fluid.optimizer.Adam(1e-3)):
    import paddle_tpu as fluid

    main, startup = fluid.Program(), fluid.Program()
    startup.random_seed = main.random_seed = 3
    with fluid.program_guard(main, startup):
        x = fluid.layers.data("x", [8], dtype="float32")
        y = fluid.layers.data("y", [1], dtype="float32")
        loss = fluid.layers.mean(
            fluid.layers.square_error_cost(fluid.layers.fc(x, 1), y))
        optimizer(fluid).minimize(loss)
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
# the smoke's probe: which accumulator says that an update was not dropped
# --------------------------------------------------------------------------


@pytest.mark.parametrize("optimizer", [
    lambda fluid: fluid.optimizer.Adam(1e-3),
    lambda fluid: fluid.optimizer.Momentum(1e-2, momentum=0.9),
    lambda fluid: fluid.optimizer.RMSProp(1e-2),   # not centred: `_mean_grad_0` stays zero
    lambda fluid: fluid.optimizer.Adagrad(1e-2),
], ids=["adam", "momentum", "rmsprop", "adagrad"])
def test_the_smoke_finds_each_optimizers_live_first_moment(optimizer):
    """`chip_smoke.py` finds a parameter's first moment by optimizer.py's
    accumulator names.  Under a renamed accumulator, or the wrong one of
    RMSProp's two, every parameter below its dtype's resolution would read
    as a dropped update, on the chip and nowhere else."""
    import chip_smoke
    import paddle_tpu as fluid

    main, startup, loss = _mlp_program(optimizer)
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup, scope=scope)
    before = chip_smoke._params(main, scope)
    assert sorted(before) == sorted(p.name for p in main.all_parameters())
    exe.run(main, feed=_feed(), fetch_list=[loss], scope=scope)
    after = chip_smoke._params(main, scope)
    moments = chip_smoke._first_moments(main, scope)
    for name in before:
        assert np.abs(after[name] - before[name]).max() > 0, name
        assert np.abs(moments[name]).max() > 0, (name, "a dead moment")
