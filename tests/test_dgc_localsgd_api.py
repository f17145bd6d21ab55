"""DGC + LocalSGD reachable from the fluid API (r3 review item 5):
fluid.optimizer.DGCMomentumOptimizer (reference optimizer.py:786) and
CompiledProgram.with_local_sgd / DistributedStrategy.use_local_sgd
(reference transpiler/collective.py:249)."""
import numpy as np

import paddle_tpu as fluid

D = 132  # 132*132 = 17424 >= the 16384 DGC eligibility threshold


def _build_reg(opt):
    main, startup = fluid.Program(), fluid.Program()
    startup.random_seed = 21
    with fluid.program_guard(main, startup):
        x = fluid.layers.data("x", [D], dtype="float32")
        y = fluid.layers.data("y", [D], dtype="float32")
        h = fluid.layers.fc(x, D, bias_attr=False)
        loss = fluid.layers.mean(fluid.layers.square_error_cost(h, y))
        opt.minimize(loss)
    return main, startup, loss


def _data(steps=1, batch=16, seed=0):
    rng = np.random.RandomState(seed)
    w = rng.randn(D, D).astype("f4") * 0.1
    xs = rng.rand(steps, batch, D).astype("f4")
    ys = xs @ w
    return xs, ys


def _train(main, startup, loss, xs, ys, scope=None):
    exe = fluid.Executor(fluid.CPUPlace())
    scope = scope or fluid.Scope()
    exe.run(startup, scope=scope)
    losses = []
    for i in range(xs.shape[0]):
        (lv,) = exe.run(main, feed={"x": xs[i], "y": ys[i]},
                        fetch_list=[loss], scope=scope)
        losses.append(float(np.asarray(lv).reshape(-1)[0]))
    return losses, scope


def test_dgc_before_rampup_matches_plain_momentum():
    """rampup_begin_step in the future -> bit-identical to Momentum."""
    xs, ys = _data(steps=5)
    m1, s1, l1 = _build_reg(fluid.optimizer.MomentumOptimizer(0.05, 0.9))
    m2, s2, l2 = _build_reg(fluid.optimizer.DGCMomentumOptimizer(
        0.05, 0.9, rampup_begin_step=1000, sparsity=[0.99]))
    r1, _ = _train(m1, s1, l1, xs, ys)
    r2, _ = _train(m2, s2, l2, xs, ys)
    np.testing.assert_allclose(r1, r2, rtol=1e-6, atol=1e-7)


def test_dgc_first_update_is_topk_sparse():
    """rampup_begin_step=0: the first param delta touches <= k coordinates."""
    sparsity = 0.99
    xs, ys = _data(steps=1)
    main, startup, loss = _build_reg(fluid.optimizer.DGCMomentumOptimizer(
        0.05, 0.9, rampup_begin_step=0, sparsity=[sparsity]))
    pname = [v.name for v in main.list_vars()
             if isinstance(v, fluid.core.program.Parameter)][0]
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    exe.run(startup, scope=scope)
    before = np.asarray(scope.find_var(pname)).copy()
    exe.run(main, feed={"x": xs[0], "y": ys[0]}, fetch_list=[loss], scope=scope)
    after = np.asarray(scope.find_var(pname))
    delta_nnz = int((np.abs(after - before) > 0).sum())
    k = max(1, int(D * D * (1 - sparsity)))
    assert 0 < delta_nnz <= k, (delta_nnz, k)
    # error-feedback buffer holds the unsent residual
    v_buf = np.asarray(scope.find_var(f"{pname}_dgc_v_0"))
    assert (np.abs(v_buf) > 0).sum() > 0


def test_dgc_converges_close_to_momentum():
    """convergence parity within tolerance.  Note the compounding: the dgc
    op's output is the top-k of the momentum-corrected V buffer (the
    reference feeds the decoded sparse V into the momentum op —
    dgc_op.h k_select over v_out), so the effective step is larger than
    plain momentum's at the same lr; a warmup-free small lr keeps both
    stable, matching how the reference is deployed (rampup warmup)."""
    xs, ys = _data(steps=80)
    lr = 0.002
    m1, s1, l1 = _build_reg(fluid.optimizer.MomentumOptimizer(lr, 0.9))
    m2, s2, l2 = _build_reg(fluid.optimizer.DGCMomentumOptimizer(
        lr, 0.9, rampup_begin_step=0, rampup_step=30,
        sparsity=[0.8, 0.9, 0.99]))
    r1, _ = _train(m1, s1, l1, xs, ys)
    r2, _ = _train(m2, s2, l2, xs, ys)
    assert r2[-1] < r2[0] * 0.5, (r2[0], r2[-1])
    assert r2[-1] < max(r1[-1] * 5.0, r1[0] * 0.5), (r1[-1], r2[-1])


def test_local_sgd_round_trains_and_tracks_sync_dp():
    """8-dev mesh: with_local_sgd(k) runs k diverging local steps + one
    pmean per dispatch; converges within tolerance of plain sync dp."""
    import jax

    if len(jax.devices()) < 8:
        import pytest
        pytest.skip("needs the 8-device virtual mesh")

    def build():
        main, startup = fluid.Program(), fluid.Program()
        startup.random_seed = 3
        with fluid.program_guard(main, startup):
            x = fluid.layers.data("x", [13], dtype="float32")
            y = fluid.layers.data("y", [1], dtype="float32")
            h = fluid.layers.fc(x, 1, bias_attr=False)
            loss = fluid.layers.mean(fluid.layers.square_error_cost(h, y))
            fluid.optimizer.SGD(0.1).minimize(loss)
        return main, startup, loss

    rng = np.random.RandomState(0)
    w = rng.randn(13, 1).astype("f4")
    k, rounds, B = 4, 10, 32  # B divisible by 8 devices

    def feeds():
        xs = rng.rand(rounds, k, B, 13).astype("f4")
        return xs, xs @ w

    xs, ys = feeds()

    # LocalSGD path
    main, startup, loss = build()
    cp = (fluid.CompiledProgram(main)
          .with_data_parallel(loss_name=loss.name)
          .with_local_sgd(sync_every=k))
    exe = fluid.Executor(fluid.TPUPlace(0))
    scope = fluid.Scope()
    exe.run(startup, scope=scope)
    ls_losses = []
    for r in range(rounds):
        (lv,) = exe.run(cp, feed={"x": xs[r], "y": ys[r]},
                        fetch_list=[loss], scope=scope)
        # fetches come back stacked [k]; track the round's last step
        ls_losses.append(float(np.asarray(lv).reshape(-1)[-1]))
    assert ls_losses[-1] < ls_losses[0] * 0.3, ls_losses

    # plain sync dp on the same data stream (steps=k per dispatch)
    main2, startup2, loss2 = build()
    cp2 = fluid.CompiledProgram(main2).with_data_parallel(loss_name=loss2.name)
    scope2 = fluid.Scope()
    exe.run(startup2, scope=scope2)
    dp_losses = []
    for r in range(rounds):
        (lv,) = exe.run(cp2, feed={"x": xs[r], "y": ys[r]},
                        fetch_list=[loss2], scope=scope2, steps=k)
        dp_losses.append(float(np.asarray(lv).reshape(-1)[-1]))
    # parity within tolerance: LocalSGD pays staleness, not divergence
    assert ls_losses[-1] < max(dp_losses[-1] * 5.0, dp_losses[0] * 0.3), (
        ls_losses[-1], dp_losses[-1])


def test_fleet_strategy_local_sgd_knob():
    from paddle_tpu.fleet import DistributedStrategy, Fleet

    f = Fleet()
    strat = DistributedStrategy()
    strat.use_local_sgd = True
    strat.local_sgd_steps = 6

    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data("x", [4], dtype="float32")
        loss = fluid.layers.mean(fluid.layers.fc(x, 1))
        opt = f.distributed_optimizer(fluid.optimizer.SGD(0.1), strat)
        opt.minimize(loss)
    cp = f.main_program(main)
    assert cp.local_sgd_every == 6
