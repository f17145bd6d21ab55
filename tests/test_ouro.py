"""A looped decoder's parts and the whole, tiny on the CPU (ISSUE 38).

(a) `layers.Repeat`, the construct alone: once is the body, four times are four
    copies; carried and stacked variables; a parameter read in the body gets
    ONE gradient, the sum of its uses; recomputed or not, the same numbers; two
    constructs in a program; clones keep it; the verifier walks it and the
    planner counts its body `times` over; an embedding read in it stays dense;
(b) `layers.exit_loss` against the products as written, and its step record;
(c) a tiny `build_causal_lm(loop=4, post_norm=True, ...)` in float32 against the
    benchmark's reference (benchmark/models/ouro.py) on seeded weights: the four
    exits' logits, the exit distribution, the loss, every parameter's gradient,
    and a shared weight's gradient equal to the sum of the four passes' when the
    weight is given four names;
(d) the controls, each of which the comparison has to refuse;
(e) steps through `train_loop` publish the exit distribution.
"""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import paddle_tpu as fluid  # noqa: E402
from benchmark import manifest as mf  # noqa: E402
from benchmark.models import ouro  # noqa: E402
from paddle_tpu import layers, monitor  # noqa: E402
from paddle_tpu.core import analysis, resource_plan  # noqa: E402
from paddle_tpu.core.param_attr import ParamAttr  # noqa: E402
from paddle_tpu.models import transformer  # noqa: E402


def agree(got, want, tol=1e-5):
    got, want = np.asarray(got, "f8"), np.asarray(want, "f8")
    assert got.shape == want.shape, (got.shape, want.shape)
    assert np.abs(got - want).max() <= tol * max(np.abs(want).max(), 1e-12), \
        (np.abs(got - want).max(), np.abs(want).max())


# -- (a) the construct alone ------------------------------------------------------

def cell(h, name="w"):
    return layers.fc(h, 8, act="tanh", param_attr=ParamAttr(name=name), bias_attr=ParamAttr(name=name + ".b"))


def looped(times, recompute=False, copies=False, names=None):
    """loss = mean((h_T o - y)^2) + mean(the stacked passes): a program whose
    body is `cell`, as ONE `repeat` op or written out `times` over."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        x, y = layers.data("x", [8]), layers.data("y", [1])
        if copies:
            h, passes = x, []
            for t in range(times):
                h = cell(h, names[t] if names else "w")
                passes.append(h)
            stacked = layers.stack(passes, axis=0)
        else:
            loop = layers.Repeat(times, recompute=recompute)
            with loop.block():
                h = loop.carry(x)
                new = cell(h)
                loop.update(h, new)
                loop.output(new)
            stacked, h = loop(), loop.final(h)
        out = layers.fc(h, 1, param_attr=ParamAttr(name="o"), bias_attr=False)
        loss = layers.elementwise_add(layers.mean(layers.square_error_cost(out, y)), layers.mean(stacked))
        grads = fluid.backward.append_backward(loss)
    return main, startup, loss, stacked, grads


FEED = {"x": np.random.RandomState(0).randn(4, 8).astype("f4"), "y": np.random.RandomState(1).randn(4, 1).astype("f4")}
WEIGHTS = {"w": np.random.RandomState(2).randn(8, 8).astype("f4") * 0.5, "w.b": np.random.RandomState(3).randn(8).astype("f4"),
           "o": np.random.RandomState(4).randn(8, 1).astype("f4")}


def run(built, weights=WEIGHTS, feed=FEED):
    main, startup, loss, stacked, grads = built
    scope, exe = fluid.Scope(), fluid.Executor(fluid.TPUPlace(0))
    exe.run(startup, scope=scope)
    for name, value in weights.items():
        scope.set_var(name, jnp.asarray(value))
    out = exe.run(main, feed=feed, fetch_list=[loss, stacked] + [g for _, g in grads], scope=scope)
    return {"loss": out[0], "stacked": out[1], **{p.name: g for (p, _), g in zip(grads, out[2:])}}


def test_once_is_the_body_and_four_times_are_four_copies():
    for times in (1, 4):
        loop, copies = run(looped(times)), run(looped(times, copies=True))
        assert loop["stacked"].shape == (times, 4, 8)
        for name in copies:
            agree(loop[name], copies[name], tol=1e-6)
    main = looped(4)[0]
    assert [op.type for op in main.global_block().ops][:2] == ["repeat", "mul"]       # ONE op with a sub-block
    op = main.global_block().ops[0]
    assert op.attr("times") == 4 and op.attr("recompute") is False and op.attr("sub_block") == 1
    assert op.input("X") == ["w", "w.b"] and len(op.attr("carry_vars")) == 1        # the parameters are captured
    assert [o.type for o in main.blocks[1].ops] == ["mul", "elementwise_add", "tanh"]
    assert '"repeat"' in main.to_string() or "repeat" in repr(main)


def test_a_shared_weights_gradient_is_the_sum_of_its_four_uses():
    names = ["w0", "w1", "w2", "w3"]
    apart = run(looped(4, copies=True, names=names),
                weights={**{n: WEIGHTS["w"] for n in names}, **{n + ".b": WEIGHTS["w.b"] for n in names}, "o": WEIGHTS["o"]})
    shared = run(looped(4))
    agree(shared["w"], sum(apart[n] for n in names), tol=1e-5)
    agree(shared["w.b"], sum(apart[n + ".b"] for n in names), tol=1e-5)
    assert float(np.abs(apart["w0"] - apart["w3"]).max()) > 1e-3                       # the uses differ


def test_recomputed_or_not_the_same_loss_and_gradients():
    kept, again = run(looped(4)), run(looped(4, recompute=True))
    for name in kept:
        agree(again[name], kept[name], tol=1e-6)
    main, startup, loss = looped(4, recompute=True)[:3]
    assert main.global_block().ops[0].attr("recompute") is True                         # an attribute of the op
    scope, exe = fluid.Scope(), fluid.Executor(fluid.TPUPlace(0))
    monitor.reset()
    monitor.enable()
    try:
        exe.run(startup, scope=scope)
        exe.run(main, feed=FEED, fetch_list=[loss], scope=scope)
        counters = {n: monitor.counter(n).value for n in
                    ("lowering.loop_passes", "lowering.loop_body_ops", "lowering.recomputed_segments")}
    finally:
        monitor.disable()
    assert counters["lowering.loop_passes"] % 4 == 0 and counters["lowering.loop_passes"] > 0
    assert counters["lowering.loop_body_ops"] * 4 == counters["lowering.loop_passes"] * 3
    assert counters["lowering.recomputed_segments"] == counters["lowering.loop_passes"]


def test_a_dropout_in_a_recomputed_body_draws_the_same_mask_again():
    """The key rides in the carry and enters each pass as an argument, so the
    forward that backward computes again drops what the first one dropped, and
    a second call of the step runs (whole-forward recomputation does not:
    ROADMAP.md D10)."""
    def steps(recompute):
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup), fluid.unique_name.guard():
            x = layers.data("x", [8])
            loop = layers.Repeat(3, recompute=recompute)
            with loop.block():
                h = loop.carry(x)
                new = layers.dropout(cell(h), 0.3, dropout_implementation="upscale_in_train")
                loop.update(h, new)
                loop.output(new)
            loss = layers.mean(loop.final(h))
            fluid.optimizer.SGD(0.5).minimize(loss)
        main.random_seed = startup.random_seed = 9
        scope, exe = fluid.Scope(), fluid.Executor(fluid.TPUPlace(0))
        exe.run(startup, scope=scope)
        for name in ("w", "w.b"):
            scope.set_var(name, jnp.asarray(WEIGHTS[name]))
        return [exe.run(main, feed={"x": FEED["x"]}, fetch_list=[loss, loop()], scope=scope) for _ in range(3)]

    kept, again = steps(False), steps(True)
    for (loss_a, passes_a), (loss_b, passes_b) in zip(kept, again):
        agree(loss_b, loss_a, tol=1e-6)
        agree(passes_b, passes_a, tol=1e-6)
    dropped = [(np.asarray(p) == 0).mean(axis=(1, 2)) for _, p in again]
    assert all(0.05 < share < 0.6 for step in dropped for share in step)
    assert not np.array_equal(np.asarray(again[0][1][0] == 0), np.asarray(again[0][1][1] == 0))    # a mask a pass
    assert not np.array_equal(np.asarray(again[0][1][0] == 0), np.asarray(again[1][1][0] == 0))    # and a step
    assert float(again[2][0][0]) != float(again[0][0][0])                                          # the weights moved


def test_two_constructs_clones_and_the_verifier():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        x = layers.data("x", [8])
        finals = []
        for name, times in (("a", 2), ("b", 3)):
            loop = layers.Repeat(times, recompute=name == "b")
            with loop.block():
                h = loop.carry(x)
                loop.update(h, cell(h, name))
                loop.output(h)                       # the carried variable AS READ: x, then the passes' inputs
            finals.append((loop(), loop.final(h)))
            x = finals[-1][1]
        loss = layers.mean(x)
        fluid.optimizer.SGD(0.1).minimize(loss)
    assert [op.type for op in main.global_block().ops].count("repeat") == 2
    assert [d for d in analysis.verify_program(main) if d.severity == "error"] == []
    scope, exe = fluid.Scope(), fluid.Executor(fluid.TPUPlace(0))
    exe.run(startup, scope=scope)
    first = exe.run(main, feed={"x": FEED["x"]}, fetch_list=[loss, finals[0][0], finals[1][0]], scope=scope)
    assert first[1].shape == (2, 4, 8) and first[2].shape == (3, 4, 8)
    agree(first[1][0], FEED["x"], tol=0)                                               # pass 1 read the initial value
    agree(first[2][0], np.asarray(exe.run(main.clone(for_test=True), feed={"x": FEED["x"]},
                                           fetch_list=[finals[0][1]], scope=scope)[0]), tol=1.0)
    for clone in (main.clone(), main.clone(for_test=True)):
        ops = [op for op in clone.global_block().ops if op.type == "repeat"]
        assert [op.attr("times") for op in ops] == [2, 3] and [op.attr("recompute") for op in ops] == [False, True]
        assert len(clone.blocks) == 3
    test = main.clone(for_test=True)
    assert "backward" not in [op.type for op in test.global_block().ops]
    before = exe.run(test, feed={"x": FEED["x"]}, fetch_list=[loss], scope=scope)[0]
    for _ in range(3):
        exe.run(main, feed={"x": FEED["x"]}, fetch_list=[loss], scope=scope)
    assert float(exe.run(test, feed={"x": FEED["x"]}, fetch_list=[loss], scope=scope)[0]) < float(before)


def test_the_builder_and_the_shape_rule_refuse_what_cannot_loop():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = layers.data("x", [8])
        with pytest.raises(ValueError, match="once at least"):
            layers.Repeat(0)
        loop = layers.Repeat(2)
        with pytest.raises(RuntimeError, match="inside"):
            loop.carry(x)
        with pytest.raises(ValueError, match="never updated"):
            with loop.block():
                loop.carry(x)
        wide = layers.Repeat(2)
        with pytest.raises(Exception, match="keeps its shape and dtype"):
            with wide.block():
                h = wide.carry(x)
                wide.update(h, layers.fc(h, 5))


def test_the_planner_counts_the_body_times_over_and_a_recomputed_forward_once_more():
    def rows_of(times, recompute, backward=True):
        main, _, loss, _, _ = looped(times, recompute=recompute)
        if not backward:
            main = main.clone(for_test=True)
        plan = resource_plan.plan_program(main, {"x": (4, 8), "y": (4, 1)}, [loss.name])
        body = [r for r in plan.rows if r.op_type == "tanh"]
        assert len(body) == 1 and plan.cost_coverage_frac == 1.0
        return body[0].grad_factor, plan

    assert rows_of(1, False)[0] == 3 and rows_of(4, False)[0] == 12 and rows_of(4, True)[0] == 16
    assert rows_of(4, True, backward=False)[0] == 4                                     # nothing to differentiate: 4 forwards
    # a body that is not recomputed keeps every pass's temporaries until the backward
    assert rows_of(4, False)[1].peak_temp_bytes > rows_of(4, True)[1].peak_temp_bytes
    assert rows_of(4, True)[1].flops_total > rows_of(4, False)[1].flops_total > rows_of(1, False)[1].flops_total


def test_an_embedding_read_inside_the_construct_stays_on_the_dense_path():
    from paddle_tpu.core import lowering

    def build(inside):
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup), fluid.unique_name.guard():
            ids, x = layers.data("ids", [1], dtype="int64"), layers.data("x", [8])

            def lookup():
                return layers.embedding(ids, size=[16, 8], is_sparse=True, param_attr=ParamAttr(name="table"))

            if inside:
                loop = layers.Repeat(2)
                with loop.block():
                    h = loop.carry(x)
                    loop.update(h, layers.elementwise_add(h, lookup()))
                h = loop.final(h)
            else:
                h = layers.elementwise_add(x, lookup())
            loss = layers.mean(h)
            fluid.optimizer.SGD(0.1).minimize(loss)
        return main, startup, loss

    feed = {"ids": np.array([[1], [3], [3], [7]], "int64"), "x": FEED["x"]}
    for inside, sparse in ((False, ["table"]), (True, [])):
        main, startup, loss = build(inside)
        backward = next(op for op in main.global_block().ops if op.type == "backward")
        assert backward.attr("sparse_param_names") == sparse
        scope, exe = fluid.Scope(), fluid.Executor(fluid.TPUPlace(0))
        exe.run(startup, scope=scope)
        table = np.array(scope.find_var("table"))
        exe.run(main, feed=feed, fetch_list=[loss], scope=scope)
        assert lowering.LAST_TRACE_REPORT["sparse_grad_params"] == sparse
        moved = np.abs(np.array(scope.find_var("table")) - table).sum(1) > 0
        assert sorted(np.nonzero(moved)[0]) == [1, 3, 7]                                # either way the rows read


# -- (b) the exit-weighted loss ---------------------------------------------------------

def test_exit_loss_is_the_products_as_written_and_publishes_the_distribution():
    rng = np.random.RandomState(7)
    ce, gate = np.abs(rng.randn(4, 3, 5, 1)).astype("f4") + 1, rng.randn(4, 3, 5, 1).astype("f4") * 2
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        c = layers.data("ce", [12, 5, 1], append_batch_size=False)
        g = layers.data("gate", [12, 5, 1], append_batch_size=False)
        c = layers.reshape(c, [4, 3, 5, 1])
        g = layers.reshape(g, [4, 3, 5, 1])
        loss, p = layers.exit_loss(c, g, beta=0.05)
    exe = fluid.Executor(fluid.TPUPlace(0))
    op = next(o for o in main.global_block().ops if o.type == "exit_loss")
    got = exe.run(main, feed={"ce": ce.reshape(12, 5, 1), "gate": gate.reshape(12, 5, 1)},
                  fetch_list=[loss, p] + [op.output(s)[0] for s in ("ExitMass", "Entropy", "ExitCE")])
    want_p = ouro.exit_distribution(gate.astype("f8"))
    entropy = -(want_p * np.log(want_p)).sum(0)
    agree(got[1], want_p, tol=1e-6)
    agree(got[0], [((want_p * ce).sum(0) - 0.05 * entropy).mean()], tol=1e-6)
    agree(got[2], want_p.mean(axis=(1, 2, 3)), tol=1e-6)
    agree(got[3], [entropy.mean()], tol=1e-6)
    agree(got[4], ce.mean(axis=(1, 2, 3)), tol=1e-6)
    assert abs(float(got[2].sum()) - 1.0) < 1e-6
    # a saturated gate: tiny probabilities, no NaN, and gradients that are finite
    from test_lfm2 import lower

    def through(gate_logits):
        return lower("exit_loss", {"CE": ce, "Gate": gate_logits}, {"beta": 0.05})["Loss"][0]

    hard = np.where(gate > 0, 90.0, -90.0).astype("f4")
    assert np.isfinite(float(through(jnp.asarray(hard)))) and np.isfinite(np.asarray(jax.grad(through)(jnp.asarray(hard)))).all()


# -- (c) the whole model against the reference --------------------------------------------

TINY = dict(hidden_size=32, num_attention_heads=4, num_key_value_heads=4, head_dim=8, intermediate_size=48,
            vocab_size=64, layer_types=["full_attention"] * 2, num_hidden_layers=2)
SEQ, ROWS = 16, 3


def tiny_cfg(**over):
    cfg = mf.read_json("benchmark/configs/ouro-2.6b.json")
    cfg.update(TINY, compute_dtype="float32")
    cfg.update(over)
    return cfg


def tiny_job(**over):
    job = mf.read_json("benchmark/traffic/train-ut4-s4096.json")
    job.update(seq_len=SEQ, batch_per_chip=ROWS)
    job.update(over)
    return job


def seeded(main, startup, seed=11, as_drawn=False):
    """A scope after the start-up program, with gains, gate and bias drawn away
    from 1 and 0 so that a norm or a gate left out shows (`as_drawn`: the
    cell's own start)."""
    main.random_seed = startup.random_seed = seed
    scope, exe = fluid.Scope(), fluid.Executor(fluid.TPUPlace(0))
    exe.run(startup, scope=scope)
    rng = np.random.RandomState(seed)
    for p in () if as_drawn else main.all_parameters():
        if "ln" in p.name or "norm" in p.name or "exit_gate" in p.name:
            scope.set_var(p.name, jnp.asarray((1.0 + 0.3 * rng.randn(*p.shape)).astype("f4")))
        else:  # N(0, 0.02) weights make every sub-layer's output tiny: the post-norms then carry the model
            scope.set_var(p.name, jnp.asarray(np.asarray(scope.find_var(p.name)) * 10))
    return scope, exe


@pytest.fixture(scope="module")
def model(request):
    """(cfg, rows, params, got: the for_test clone's check outputs, grads: the
    train program's gradients by parameter name, loss) at float32."""
    cfg, job = tiny_cfg(), tiny_job()
    saved = ouro.LOGIT_SAMPLE
    ouro.LOGIT_SAMPLE = 6
    request.addfinalizer(lambda: setattr(ouro, "LOGIT_SAMPLE", saved))
    with fluid.unique_name.guard():
        main, startup, feeds, loss, names = ouro.build(cfg, job)
    scope, exe = seeded(main, startup)
    rows = ouro.make_batch(np.random.RandomState(5), cfg, job, ROWS)
    params = {p.name: np.array(scope.find_var(p.name)) for p in main.all_parameters()}
    got = exe.run(main.clone(for_test=True), feed=rows, fetch_list=list(names), scope=scope)
    backward = next(op for op in main.global_block().ops if op.type == "backward")
    out = exe.run(main, feed=rows, fetch_list=[loss.name] + list(backward.attr("grad_names")), scope=scope)
    grads = dict(zip(backward.attr("param_names"), out[1:]))
    return dict(cfg=cfg, job=job, rows=rows, params=params, got=got, grads=grads, loss=out[0], main=main)


def reference_of(model, params=None, cfg=None, **fault):
    want = ouro.reference(params or model["params"], model["rows"], cfg or model["cfg"], **fault)
    return [np.asarray(w) for w in want]


def test_the_program_is_one_loop_with_the_exits_read_off_what_it_wrote(model):
    main = model["main"]
    loops = [op for op in main.global_block().ops if op.type == "repeat"]
    assert len(loops) == 1 and loops[0].attr("times") == 4 and loops[0].attr("recompute") is True
    body = main.blocks[loops[0].attr("sub_block")].ops
    assert [op.type for op in body].count("fused_attention") == 2 and [op.type for op in body].count("rms_norm") == 9
    assert not any(op.attr("op_namescope") for op in body) and body[-1].type == "rms_norm"    # the norm closes the pass
    assert loops[0].attr("out_vars") == loops[0].attr("carry_updates")                        # h_t is what is stacked
    scoped = {scope: [op.type for op in main.global_block().ops if op.attr("op_namescope") == scope]
              for scope in ("exit_head", "exit_loss")}
    assert scoped["exit_head"] == ["mul", "cast", "elementwise_mul", "reduce_sum", "elementwise_add"]
    assert scoped["exit_loss"] == ["reshape2", "expand", "softmax_with_cross_entropy", "exit_loss"]
    names = sorted(p.name for p in main.all_parameters())
    assert len(names) == 2 * 11 + 5 and "lm.l1.post_ln2.w" in names and "lm.exit_gate.b" in names
    assert not any("moe" in n for n in names)
    # a decoder of dense layers alone, without a loop: the auxiliary terms are left out, not divided by zero
    _, _, _, fetches = transformer.build_causal_lm(
        vocab_size=64, seq_len=8, d_model=32, n_heads=4, layer_types=["full_attention"], num_dense_layers=1,
        dense_width=48, qk_norm=None, with_optimizer=False)
    assert sorted(fetches) == ["ce", "logits", "loss"]
    with pytest.raises(ValueError, match="stack of dense layers"):
        transformer.build_causal_lm(vocab_size=64, seq_len=8, d_model=32, n_heads=4, n_layers=1, num_experts=4,
                                    top_k=2, expert_width=16, loop=2, with_optimizer=False)


def test_logits_exit_distribution_and_loss_agree_with_the_reference(model):
    want = reference_of(model)
    found = ouro.compare(model["got"], want)
    assert found["loss_error"] < 1e-5 and found["logit_error"] < 1e-5 and found["exit_p_error"] < 1e-5
    assert found["gate_error"] < 1e-5 < found["gate_error_bf16"] and found["pass2_error"] < 1e-5
    assert found["exit_p_sum_error"] < 1e-6 and len(found["logit_error_by_exit"]) == 4
    agree(model["loss"], [want[0]], tol=1e-5)
    assert ouro.reference_error(model["got"], want) < 1e-5
    assert min(found["exit_mass"]) > 0.01                      # every exit carries weight in this test


def test_every_parameters_gradient_agrees_with_the_references(model):
    cfg, rows = model["cfg"], model["rows"]
    want = jax.grad(lambda p: ouro.reference(p, rows, cfg)[0])({k: jnp.asarray(v) for k, v in model["params"].items()})
    assert sorted(want) == sorted(model["grads"])
    for name, grad in want.items():
        agree(model["grads"][name], grad, tol=2e-4)
        assert float(np.abs(np.asarray(grad)).max()) > 0


def test_a_shared_weights_gradient_is_the_sum_of_the_four_passes(model):
    """By hand: the reference with the layers' weights given a name a pass."""
    cfg, rows, params = model["cfg"], model["rows"], model["params"]
    name = "lm.l1.ffn.up.w"

    def loss_of(four):
        def p(n):
            return jnp.asarray(params[n], jnp.float32)

        final_gain, w_exit, b_exit, head = p("lm.final_norm.w"), p("lm.exit_gate.w"), p("lm.exit_gate.b"), p("lm.head.w")
        total = 0.0
        for r in range(ROWS):
            ids, labels, pos = (jnp.asarray(rows[n][r], jnp.int32) for n in ouro.FEEDS)
            x, gates, ces = p("lm.tok_emb")[ids], [], []
            for t in range(4):
                weights = ouro.stacked_weights({**params, name: four[t]}, 2)
                x = ouro.one_pass(x, pos, weights, final_gain, cfg)
                ces.append(-jnp.take_along_axis(jax.nn.log_softmax(x @ head, -1), labels[:, None], 1)[:, 0])
                gates.append(x @ w_exit + b_exit[0])
            dist = ouro.exit_distribution(jnp.stack(gates), jnp)
            total += jnp.sum(jnp.sum(dist * jnp.stack(ces), 0) + cfg["exit_entropy_beta"] * jnp.sum(dist * jnp.log(dist), 0))
        return total / (ROWS * SEQ)

    with jax.default_matmul_precision("highest"):
        apart = jax.grad(loss_of)([jnp.asarray(params[name])] * 4)
    agree(model["grads"][name], sum(apart), tol=2e-4)
    assert float(np.abs(np.asarray(apart[0]) - np.asarray(apart[3])).max()) > 1e-3 * float(np.abs(np.asarray(apart[0])).max())


# -- (d) the controls -------------------------------------------------------------------

def second_set(params, seed=99):
    rng = np.random.RandomState(seed)
    return {k: (v if ".l" not in k else (v + 0.2 * np.abs(v).max() * rng.randn(*v.shape)).astype("f4")) for k, v in params.items()}


FAULTS = {
    "three passes for four": dict(passes=3),
    "the post-norms dropped": dict(post_norms=False),
    "the final norm after the last pass only": dict(final_norm_every_pass=False),
    "pass 2 reads a second set of weights": "second",
    "bf16 masters": "bf16",
    "the cross entropies, the exit weighting and the mean in bf16": dict(bf16_loss=True),
    "the entropy term left out": dict(cfg=tiny_cfg(exit_entropy_beta=0.0)),
}
LOSS_ALONE = tuple(FAULTS)[-2:]


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_reference_with_a_fault_in_it_is_refused(model, fault):
    how, params = FAULTS[fault], None
    if how == "second":
        how = dict(second_weights=second_set(model["params"]))
    elif how == "bf16":
        how, params = {}, {k: ouro._bf16(v) for k, v in model["params"].items()}
    error = ouro.reference_error(model["got"], reference_of(model, params, **how))
    assert not error <= 1e-4, fault                             # float32 against float32 reads under 1e-5
    # ... and by the cell's own limits at its own precision where the fault is one of structure
    if fault != "bf16 masters":
        assert not error <= ouro.REFERENCE_RTOL, fault
    if fault in LOSS_ALONE:  # the logits, the distribution and the stages are the same numbers: the loss's own limit tells
        found = ouro.compare(model["got"], reference_of(model, params, **how))
        assert found["loss_error"] > ouro.LOSS_RTOL and found["logit_error"] < 1e-4 and found["exit_p_error"] < 1e-4


def test_the_gates_products_in_bf16_are_refused_by_the_gates_own_limit(model):
    found = ouro.compare(model["got"], reference_of(model))
    assert found["gate_error"] <= ouro.GATE_ATOL < found["gate_error_bf16"]
    p = np.asarray(model["got"][2], "f4")
    rounded = list(model["got"])
    rounded[2] = ouro._bf16(p)                                   # a program whose distribution is bf16
    assert ouro.reference_error(rounded, reference_of(model)) == float("inf")


def test_norm_statistics_in_bf16_are_refused_by_the_norms_own_limit(model):
    from tools.chip_ouro_controls import norm_statistics_in_bf16
    assert ouro.compare(model["got"], reference_of(model))["norm_error"] < 1e-6
    with norm_statistics_in_bf16(), fluid.unique_name.guard():  # THE PROGRAM with the fault, against the sound reference
        main, startup, _, _, names = ouro.build(model["cfg"], model["job"])
        scope, exe = seeded(main, startup)
        got = exe.run(main.clone(for_test=True), feed=model["rows"], fetch_list=list(names), scope=scope)
    found = ouro.compare(got, reference_of(model))
    assert found["norm_error"] > ouro.NORM_RTOL
    assert ouro.reference_error(got, reference_of(model)) == float("inf")


def test_the_bf16_program_stays_inside_the_cells_limits():
    cfg, job = tiny_cfg(compute_dtype="bfloat16"), tiny_job()
    with fluid.unique_name.guard():
        main, startup, feeds, loss, names = ouro.build(cfg, job)
    scope, exe = seeded(main, startup, as_drawn=True)
    rows = ouro.make_batch(np.random.RandomState(5), cfg, job, ROWS)
    got = exe.run(main.clone(for_test=True), feed=rows, fetch_list=list(names), scope=scope)
    assert got[1].dtype == jnp.bfloat16 and got[2].dtype == np.float32 and got[3].dtype == jnp.bfloat16
    params = {p.name: np.array(scope.find_var(p.name)) for p in main.all_parameters()}
    want = [np.asarray(w) for w in ouro.reference(params, rows, cfg)]
    assert ouro.reference_error(got, want) <= ouro.REFERENCE_RTOL
    # the masters and their gradients are float32, one gradient a shared weight
    backward = next(op for op in main.global_block().ops if op.type == "backward")
    out = exe.run(main, feed=rows, fetch_list=list(backward.attr("grad_names")), scope=scope)
    assert all(g.dtype == np.float32 for g in out) and len(out) == len(params)


# -- (e) through train_loop -------------------------------------------------------------

def test_train_loop_publishes_the_exit_distribution_on_logged_steps():
    cfg, job = tiny_cfg(), tiny_job()
    with fluid.unique_name.guard():
        main, startup, feeds, loss, _ = ouro.build(cfg, job)
    main.random_seed = startup.random_seed = 3
    scope, exe = fluid.Scope(), fluid.Executor(fluid.TPUPlace(0))
    exe.run(startup, scope=scope)
    rng = np.random.RandomState(0)
    batches = [ouro.make_batch(rng, cfg, job, ROWS) for _ in range(6)]
    monitor.reset()
    monitor.enable()
    try:
        stats = fluid.train_loop(exe, main, iter(batches), [loss], scope=scope, max_inflight=2, log_period=2)
        records = [r for r in monitor.get_monitor().step_records() if r.get("kind") == "loop_exit"]
        last_pass = monitor.gauge("loop.exit_mass_last_pass").value
    finally:
        monitor.disable()
    assert stats.steps == 6 and [r["pipeline_step"] for r in records] == [0, 2, 4]
    for r in records:
        assert len(r["exit_mass"]) == len(r["exit_ce"]) == 4 and abs(sum(r["exit_mass"]) - 1) < 1e-5
        assert 0 < r["entropy"] <= np.log(4) + 1e-6 and all(np.isfinite(r["exit_ce"]))
        assert abs(r["exit_mass"][0] - 0.5) < 0.05 and abs(r["exit_mass"][3] - 0.125) < 0.05   # a gate at its start
    assert last_pass == records[-1]["exit_mass"][-1]
    assert [len(vals) for _, vals in stats.logged] == [1, 1, 1]                       # the caller's fetches alone
