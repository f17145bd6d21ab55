"""Slim depth (r3 review #9): structured pruning prune-retrain,
distillation (L2 / FSP / soft-label over the fsp op), channel-wise QAT.
Reference: contrib/slim/prune/pruner.py, distillation/distiller.py,
fake_quantize_op.cc fake_channel_wise_quantize_abs_max."""
import numpy as np

import paddle_tpu as fluid
from paddle_tpu.contrib import slim


def _mnist_scale_net():
    main, startup = fluid.Program(), fluid.Program()
    startup.random_seed = 9
    with fluid.program_guard(main, startup):
        x = fluid.layers.data("x", [16], dtype="float32")
        y = fluid.layers.data("y", [1], dtype="int64")
        h = fluid.layers.fc(x, 32, act="relu",
                            param_attr=fluid.ParamAttr(name="fc1_w"))
        logits = fluid.layers.fc(h, 4, param_attr=fluid.ParamAttr(name="fc2_w"))
        loss = fluid.layers.mean(
            fluid.layers.softmax_with_cross_entropy(logits, y))
        fluid.optimizer.Adam(0.01).minimize(loss)
    return main, startup, loss


def _data(rng, n=64):
    y = rng.randint(0, 4, (n, 1)).astype("int64")
    x = (rng.rand(n, 16) * 0.2).astype("f4")
    x[np.arange(n), y[:, 0] * 4] += 2.0  # class k lights up feature 4k
    return x, y


def test_structure_pruner_group_selection():
    pruner = slim.StructurePruner(pruning_axis={"*": 1}, criterions={"*": "l1_norm"})
    w = np.array([[1.0, 0.1, 5.0, 0.2]] * 3, "f4")  # col l1: 3, .3, 15, .6
    idx = pruner.cal_pruned_idx("w", w, 0.5, axis=1)
    assert sorted(idx.tolist()) == [1, 3]
    pruned = pruner.prune_tensor(w, idx, 1, lazy=True)
    assert (pruned[:, [1, 3]] == 0).all() and (pruned[:, [0, 2]] != 0).all()
    hard = pruner.prune_tensor(w, idx, 1, lazy=False)
    assert hard.shape == (3, 2)


def test_prune_retrain_keeps_structure_and_recovers():
    rng = np.random.RandomState(0)
    main, startup, loss = _mnist_scale_net()
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    exe.run(startup, scope=scope)
    x, y = _data(rng)
    for _ in range(40):
        exe.run(main, feed={"x": x, "y": y}, fetch_list=[loss], scope=scope)
    (base,) = exe.run(main, feed={"x": x, "y": y}, fetch_list=[loss], scope=scope)
    base = float(np.asarray(base).reshape(-1)[0])

    masks = slim.prune_parameters(main, scope, ["fc1_w"], [0.5])
    assert abs(slim.sparsity(scope, masks) - 0.5) < 0.05
    (pruned_loss,) = exe.run(main, feed={"x": x, "y": y}, fetch_list=[loss],
                             scope=scope)
    # retrain with masks re-applied each step
    for _ in range(60):
        exe.run(main, feed={"x": x, "y": y}, fetch_list=[loss], scope=scope)
        slim.apply_masks(scope, masks)
    eval_prog = main.clone(for_test=True)
    (rec,) = exe.run(eval_prog, feed={"x": x, "y": y}, fetch_list=[loss],
                     scope=scope)
    rec = float(np.asarray(rec).reshape(-1)[0])
    w = np.asarray(scope.find_var("fc1_w"))
    assert (w[masks["fc1_w"] == 0] == 0).all()  # structure preserved
    assert rec < float(np.asarray(pruned_loss).reshape(-1)[0])
    assert rec < base * 3  # recovers to the ballpark of the dense model


def test_distillation_student_learns_teacher():
    """student trained ONLY on distillation losses (L2 + FSP + soft label)
    matches the frozen teacher better than at init."""
    rng = np.random.RandomState(1)
    main, startup = fluid.Program(), fluid.Program()
    startup.random_seed = 4
    with fluid.program_guard(main, startup):
        x = fluid.layers.data("x", [1, 8, 8], dtype="float32")
        # frozen teacher
        t1 = fluid.layers.conv2d(x, 4, 3, padding=1, act="relu",
                                 param_attr=fluid.ParamAttr(name="t1w"))
        t2 = fluid.layers.conv2d(t1, 4, 3, padding=1,
                                 param_attr=fluid.ParamAttr(name="t2w"))
        t_logits = fluid.layers.fc(t2, 4, param_attr=fluid.ParamAttr(name="t3w"))
        # student
        s1 = fluid.layers.conv2d(x, 4, 3, padding=1, act="relu",
                                 param_attr=fluid.ParamAttr(name="s1w"))
        s2 = fluid.layers.conv2d(s1, 4, 3, padding=1,
                                 param_attr=fluid.ParamAttr(name="s2w"))
        s_logits = fluid.layers.fc(s2, 4, param_attr=fluid.ParamAttr(name="s3w"))

        l2 = slim.L2Distiller(s2, t2).distiller_loss()
        fsp = slim.FSPDistiller([(s1, s2)], [(t1, t2)]).distiller_loss()
        soft = slim.SoftLabelDistiller(
            s_logits, t_logits, student_temperature=1.0,
            teacher_temperature=2.0).distiller_loss()
        total = l2 + fsp + soft
        student_params = [main.global_block().var(n)
                          for n in ("s1w", "s2w", "s3w")]
        fluid.optimizer.Adam(0.01).minimize(total, parameter_list=student_params)
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    exe.run(startup, scope=scope)
    t_before = np.asarray(scope.find_var("t1w")).copy()
    xs = rng.rand(16, 1, 8, 8).astype("f4")
    totals, l2s = [], []
    for _ in range(50):
        lv, l2v = exe.run(main, feed={"x": xs}, fetch_list=[total, l2],
                          scope=scope)
        totals.append(float(np.asarray(lv).reshape(-1)[0]))
        l2s.append(float(np.asarray(l2v).reshape(-1)[0]))
    # the soft-label CE floors at the teacher's entropy; the feature-match
    # terms must collapse and the total must strictly improve
    assert totals[-1] < totals[0], (totals[0], totals[-1])
    assert l2s[-1] < l2s[0] * 0.3, (l2s[0], l2s[-1])
    # teacher stayed frozen
    np.testing.assert_array_equal(t_before, np.asarray(scope.find_var("t1w")))


def test_channel_wise_qat():
    rng = np.random.RandomState(2)
    main, startup = fluid.Program(), fluid.Program()
    startup.random_seed = 3
    with fluid.program_guard(main, startup):
        x = fluid.layers.data("x", [1, 8, 8], dtype="float32")
        y = fluid.layers.data("y", [1], dtype="int64")
        c = fluid.layers.conv2d(x, 8, 3, padding=1, act="relu",
                                param_attr=fluid.ParamAttr(name="qw"))
        logits = fluid.layers.fc(c, 4)
        loss = fluid.layers.mean(
            fluid.layers.softmax_with_cross_entropy(logits, y))
        fluid.optimizer.Adam(0.01).minimize(loss)
    n = slim.quant_aware(main, weight_quantize_type="channel_wise_abs_max")
    assert n >= 2
    ops = [o.type for o in main.global_block().ops]
    assert "fake_channel_wise_quantize_abs_max" in ops
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    exe.run(startup, scope=scope)
    xs = rng.rand(8, 1, 8, 8).astype("f4")
    ys = rng.randint(0, 4, (8, 1)).astype("int64")
    losses = []
    for _ in range(30):
        (lv,) = exe.run(main, feed={"x": xs, "y": ys}, fetch_list=[loss],
                        scope=scope)
        losses.append(float(np.asarray(lv).reshape(-1)[0]))
    assert np.isfinite(losses).all() and losses[-1] < losses[0]
    w = np.asarray(scope.find_var("qw"))
    assert w.shape[0] == 8
