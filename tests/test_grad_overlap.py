"""Backward-overlapped dp gradient all-reduce (ISSUE 7):
`parallel.distributed.make_grad_sync` bucketing + the
`CompiledProgram.with_grad_overlap` end-to-end path on the virtual CPU
mesh.  The micro A/B of the two modes is
tools/collective_bench.py --overlap."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

import paddle_tpu as fluid
from paddle_tpu.parallel import make_mesh
from paddle_tpu.parallel.distributed import make_grad_sync, plan_buckets


# --------------------------------------------------------------------------
# bucket planning
# --------------------------------------------------------------------------


def test_plan_buckets_caps_and_preserves_order():
    sizes = [("a", 3), ("b", 3), ("c", 3), ("d", 3)]
    assert plan_buckets(sizes, 6) == [["a", "b"], ["c", "d"]]
    assert plan_buckets(sizes, 7) == [["a", "b"], ["c", "d"]]
    assert plan_buckets(sizes, 100) == [["a", "b", "c", "d"]]
    assert plan_buckets(sizes, 1) == [["a"], ["b"], ["c"], ["d"]]


def test_plan_buckets_oversize_grad_gets_own_bucket():
    assert plan_buckets([("big", 50), ("s1", 2), ("s2", 2)], 10) == \
        [["big"], ["s1", "s2"]]
    assert plan_buckets([("s1", 2), ("big", 50), ("s2", 2)], 10) == \
        [["s1"], ["big"], ["s2"]]


def test_plan_buckets_empty():
    assert plan_buckets([], 10) == []


# --------------------------------------------------------------------------
# make_grad_sync: dense mean-reduce, bucketed == serial element-wise
# --------------------------------------------------------------------------


def _sync_under_shard_map(sync, grads, mesh):
    """Run `sync` over per-worker grads inside a shard_map dp region and
    return each output stacked over workers."""
    names = [n for n, _ in grads[0]]

    def worker(*stacked):
        per = [(n, g[0]) for n, g in zip(names, stacked)]
        out = sync(per)
        return tuple(out[n][None] for n in names)

    args = [jnp.stack([dict(g)[n] for g in grads]) for n in names]
    f = jax.shard_map(worker, mesh=mesh,
                      in_specs=tuple(P("dp") for _ in names),
                      out_specs=tuple(P("dp") for _ in names),
                      check_vma=False)
    return dict(zip(names, f(*args)))


@pytest.mark.parametrize("mode", ["serial", "bucketed"])
def test_grad_sync_mean_reduces(mode):
    mesh = make_mesh((4,), ("dp",))
    rng = np.random.RandomState(0)
    grads = [[("g0", jnp.asarray(rng.randn(8, 4), jnp.float32)),
              ("g1", jnp.asarray(rng.randn(16), jnp.float32))]
             for _ in range(4)]
    sync = make_grad_sync("dp", bucket_bytes=64, mode=mode)
    out = _sync_under_shard_map(sync, grads, mesh)
    for n in ("g0", "g1"):
        want = np.mean([np.asarray(dict(g)[n]) for g in grads], axis=0)
        # every worker must hold the same mean
        for w in range(4):
            np.testing.assert_allclose(np.asarray(out[n][w]), want,
                                       rtol=1e-6, atol=1e-6)


def test_grad_sync_bucketed_bitwise_matches_serial():
    """Bucketing never changes what each grad element is summed with, so
    the two modes must agree to the BIT — the property that makes the
    bench A/B isolate scheduling."""
    mesh = make_mesh((4,), ("dp",))
    rng = np.random.RandomState(1)
    grads = [[(f"g{i}", jnp.asarray(rng.randn(64), jnp.float32))
              for i in range(6)] for _ in range(4)]
    outs = {}
    for mode in ("serial", "bucketed"):
        sync = make_grad_sync("dp", bucket_bytes=64 * 4 * 2, mode=mode)
        outs[mode] = _sync_under_shard_map(sync, grads, mesh)
    for n in outs["serial"]:
        np.testing.assert_array_equal(np.asarray(outs["serial"][n]),
                                      np.asarray(outs["bucketed"][n]))


def test_grad_sync_unknown_mode_raises():
    with pytest.raises(ValueError, match="unknown mode"):
        make_grad_sync("dp", 1024, mode="pipelined")


# --------------------------------------------------------------------------
# end-to-end: CompiledProgram.with_grad_overlap
# --------------------------------------------------------------------------


def _mlp(seed=11):
    main, startup = fluid.Program(), fluid.Program()
    startup.random_seed = main.random_seed = seed
    with fluid.program_guard(main, startup):
        x = fluid.layers.data("x", [16], dtype="float32")
        y = fluid.layers.data("y", [1], dtype="float32")
        h = fluid.layers.fc(x, 32, act="relu")
        loss = fluid.layers.mean(
            fluid.layers.square_error_cost(fluid.layers.fc(h, 1), y))
        fluid.optimizer.Adam(1e-2).minimize(loss)
    return main, startup, loss


def _train(mode, steps=4, n_steps=1, bucket_mb=0.001, adam_state=False):
    main, startup, loss = _mlp()
    cp = fluid.CompiledProgram(main).with_data_parallel(loss_name=loss.name)
    if mode:
        cp = cp.with_grad_overlap(bucket_mb=bucket_mb, mode=mode)
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.TPUPlace(0))
    exe.run(startup, scope=scope)
    rng = np.random.RandomState(0)
    losses = []
    for _ in range(steps):
        if n_steps > 1:
            feed = {"x": rng.rand(n_steps, 8, 16).astype("f4"),
                    "y": rng.rand(n_steps, 8, 1).astype("f4")}
        else:
            feed = {"x": rng.rand(8, 16).astype("f4"),
                    "y": rng.rand(8, 1).astype("f4")}
        (lv,) = exe.run(cp, feed=feed, fetch_list=[loss], scope=scope,
                        steps=n_steps)
        losses.append(np.asarray(lv).reshape(-1))
    # keyed by build order, not name: each _mlp() call advances the
    # unique_name counter, so names differ across arms
    params = [np.asarray(scope.find_var(p.name)).copy()
              for p in sorted(main.all_parameters(), key=lambda p: p.name)]
    if adam_state:  # each parameter's moments and beta powers, by suffix
        params = [{n[len(p.name):]: np.asarray(scope.find_var(n)).copy()
                   for n in scope.var_names() if n.startswith(p.name)}
                  for p in sorted(main.all_parameters(), key=lambda p: p.name)]
    return np.concatenate(losses), params


def test_overlap_arms_bit_identical_to_gspmd():
    """serial == bucketed == GSPMD-derived collectives, to the bit: the
    overlap path changes scheduling, never numerics.  Held on what the sync
    hands on.  An overlap arm cannot fetch a gradient (its fetches are
    dp-means of scalars), but after the first step from zero moments Adam's
    Moment1Out IS the synced gradient, `fl(0.1 * g)` with nothing to round
    another way, so: the loss, every parameter and every moment after one
    step are the same bits in the three arms."""
    losses, state = {}, {}
    for mode in (None, "serial", "bucketed"):
        losses[mode], state[mode] = _train(mode, steps=1, adam_state=True)
    for mode in (None, "serial"):
        np.testing.assert_array_equal(losses[mode], losses["bucketed"])
        for a, b in zip(state[mode], state["bucketed"]):
            assert sorted(a) == sorted(b) and len(a) == 5  # param, 2 moments, 2 powers
            assert np.any(a["_moment1_0"] != 0)
            for suffix in a:
                np.testing.assert_array_equal(a[suffix], b[suffix], err_msg=suffix)


def test_overlap_arms_agree_over_four_steps():
    """Four steps of the three arms: losses equal to the bit everywhere, the
    two overlap arms' parameters too.  The GSPMD arm's are held to float32's
    last places since the gradients are a fusion boundary (ISSUE 25): from
    the second step on `beta * m + (1 - beta) * g` has two products, XLA's
    CPU backend contracts one of them into the add (an FMA rounds once), and
    which one hangs on how it ordered the fusion, not on the HLO.  That this
    is all the difference is:
    tests/test_grad_fence.py::test_gspmd_arm_differs_by_the_cpu_fma_alone."""
    losses = {}
    params = {}
    for mode in (None, "serial", "bucketed"):
        losses[mode], params[mode] = _train(mode)
    np.testing.assert_array_equal(losses["serial"], losses["bucketed"])
    np.testing.assert_array_equal(losses[None], losses["bucketed"])
    for a, b, c in zip(params[None], params["serial"], params["bucketed"]):
        np.testing.assert_array_equal(b, c)
        np.testing.assert_allclose(a, c, rtol=1e-6, atol=1e-7)


def test_overlap_composes_with_multi_step_scan():
    """steps>1 scanned dispatches run inside the manual dp region too."""
    l1, p1 = _train("bucketed", steps=2, n_steps=3)
    l2, p2 = _train("serial", steps=2, n_steps=3)
    np.testing.assert_array_equal(l1, l2)
    for a, b in zip(p1, p2):
        np.testing.assert_array_equal(a, b)


def test_overlap_syncs_bn_running_stats():
    """BN running mean/var updates are per-shard batch stats (not
    grad-derived), so the overlap worker must dp-mean them before claiming
    replication — serial and bucketed arms must agree to the bit on EVERY
    persistable, running stats included, and the stats must have moved."""
    def build(seed=13):
        main, startup = fluid.Program(), fluid.Program()
        startup.random_seed = main.random_seed = seed
        with fluid.program_guard(main, startup):
            img = fluid.layers.data("img", [3, 8, 8], dtype="float32")
            y = fluid.layers.data("y", [1], dtype="float32")
            c = fluid.layers.batch_norm(
                fluid.layers.conv2d(img, 4, 3, padding=1))
            loss = fluid.layers.mean(fluid.layers.square_error_cost(
                fluid.layers.fc(c, 1), y))
            fluid.optimizer.SGD(0.1).minimize(loss)
        return main, startup, loss

    state = {}
    for mode in ("serial", "bucketed"):
        main, startup, loss = build()
        cp = (fluid.CompiledProgram(main)
              .with_data_parallel(loss_name=loss.name)
              .with_grad_overlap(bucket_mb=0.001, mode=mode))
        scope = fluid.Scope()
        exe = fluid.Executor(fluid.TPUPlace(0))
        exe.run(startup, scope=scope)
        init = {n: np.asarray(scope.find_var(n)).copy()
                for n in scope.var_names()}
        rng = np.random.RandomState(0)
        for _ in range(3):
            feed = {"img": rng.rand(16, 3, 8, 8).astype("f4"),
                    "y": rng.rand(16, 1).astype("f4")}
            exe.run(cp, feed=feed, fetch_list=[loss], scope=scope)
        # keyed by build order (unique names differ across arms)
        state[mode] = ([init[n] for n in sorted(init)],
                       [np.asarray(scope.find_var(n)).copy()
                        for n in sorted(init)])
    for (ia, fa), (ib, fb) in [(state["serial"], state["bucketed"])]:
        for a, b in zip(fa, fb):
            np.testing.assert_array_equal(a, b)
        # the BN running stats moved off their init (the update ran)
        moved = [not np.array_equal(i, f) for i, f in zip(ia, fa)]
        assert any(moved)


def test_overlap_syncs_auc_accumulators():
    """auc's StatPos/StatNeg histograms are the OTHER non-grad-derived
    written state: additive accumulators.  Each dp shard buckets only ITS
    samples, so the overlap worker must psum the per-shard DELTA (not
    pmean, not raw psum — the replicated base would be counted n_dp
    times).  Integer histogram adds are order-invariant, so all three arms
    (GSPMD / serial / bucketed) must agree to the bit and equal the
    full-batch accumulation."""
    def build(seed=17):
        main, startup = fluid.Program(), fluid.Program()
        startup.random_seed = main.random_seed = seed
        with fluid.program_guard(main, startup):
            x = fluid.layers.data("x", [16], dtype="float32")
            y = fluid.layers.data("y", [1], dtype="float32")
            yl = fluid.layers.data("yl", [1], dtype="int64")
            pred = fluid.layers.sigmoid(fluid.layers.fc(x, 1))
            fluid.layers.auc(pred, yl, num_thresholds=255)
            loss = fluid.layers.mean(fluid.layers.square_error_cost(pred, y))
            fluid.optimizer.SGD(0.1).minimize(loss)
        return main, startup, loss

    state = {}
    for mode in (None, "serial", "bucketed"):
        main, startup, loss = build()
        cp = fluid.CompiledProgram(main).with_data_parallel(
            loss_name=loss.name)
        if mode:
            cp = cp.with_grad_overlap(bucket_mb=0.001, mode=mode)
        scope = fluid.Scope()
        exe = fluid.Executor(fluid.TPUPlace(0))
        exe.run(startup, scope=scope)
        stat_names = sorted(n for n in scope.var_names()
                            if ".stat_" in n)
        assert len(stat_names) == 2
        rng = np.random.RandomState(0)
        for _ in range(3):
            xv = rng.rand(16, 16).astype("f4")
            feed = {"x": xv,
                    "y": rng.rand(16, 1).astype("f4"),
                    "yl": (rng.rand(16, 1) > 0.5).astype("i8")}
            exe.run(cp, feed=feed, fetch_list=[loss], scope=scope)
        # keyed by build order (unique names differ across arms)
        state[mode] = [np.asarray(scope.find_var(n)).copy()
                       for n in stat_names]
    for arm in ("serial", "bucketed"):
        for a, b in zip(state[None], state[arm]):
            np.testing.assert_array_equal(a, b)
    # the histograms actually accumulated: 3 steps x 16 samples
    assert sum(int(s.sum()) for s in state["bucketed"]) == 3 * 16


def test_overlap_rejects_non_scalar_fetch():
    """Overlap fetches come back dp-MEANed — exact for scalar losses and
    metrics, garbage for per-sample outputs (the element-wise average of
    DIFFERENT samples at 1/n_dp the batch).  Must refuse at compile."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data("x", [16], dtype="float32")
        y = fluid.layers.data("y", [1], dtype="float32")
        pred = fluid.layers.fc(x, 1)
        loss = fluid.layers.mean(fluid.layers.square_error_cost(pred, y))
        fluid.optimizer.Adam(1e-2).minimize(loss)
    cp = (fluid.CompiledProgram(main)
          .with_data_parallel(loss_name=loss.name)
          .with_grad_overlap(bucket_mb=1.0))
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.TPUPlace(0))
    exe.run(startup, scope=scope)
    # batch 32 on the 8-device mesh: per-shard pred is (4, 1), so the
    # trace-time guard sees a genuinely non-scalar fetch (a per-shard
    # size-1 fetch is indistinguishable from a scalar metric and passes)
    feed = {"x": np.random.RandomState(0).rand(32, 16).astype("f4"),
            "y": np.random.RandomState(1).rand(32, 1).astype("f4")}
    with pytest.raises(ValueError, match="dp-MEAN"):
        exe.run(cp, feed=feed, fetch_list=[pred, loss], scope=scope)


def test_overlap_requires_mesh():
    main, startup, loss = _mlp()
    cp = fluid.CompiledProgram(main).with_grad_overlap(bucket_mb=1.0)
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.TPUPlace(0))
    exe.run(startup, scope=scope)
    with pytest.raises(ValueError, match="needs a mesh"):
        exe.run(cp, feed={"x": np.zeros((8, 16), "f4"),
                          "y": np.zeros((8, 1), "f4")},
                fetch_list=[loss], scope=scope)


def test_overlap_rejects_local_sgd_composition():
    main, _, loss = _mlp()
    with pytest.raises(ValueError, match="local_sgd"):
        fluid.CompiledProgram(main).with_local_sgd(2).with_grad_overlap()
    with pytest.raises(ValueError, match="local_sgd"):
        fluid.CompiledProgram(main).with_grad_overlap().with_local_sgd(2)


def test_overlap_rejects_bad_args():
    main, _, _ = _mlp()
    with pytest.raises(ValueError, match="unknown mode"):
        fluid.CompiledProgram(main).with_grad_overlap(mode="async")
    with pytest.raises(ValueError, match="must be > 0"):
        fluid.CompiledProgram(main).with_grad_overlap(bucket_mb=0.0)


def test_overlap_bucket_mb_defaults_to_flag():
    main, _, _ = _mlp()
    fluid.set_flags({"FLAGS_dp_bucket_mb": 7.5})
    try:
        cp = fluid.CompiledProgram(main).with_grad_overlap()
        assert cp.grad_overlap_bucket_mb == 7.5
    finally:
        fluid.set_flags({"FLAGS_dp_bucket_mb": 4.0})


def test_overlap_sparse_grads_match_gspmd():
    """SelectedRows (is_sparse embedding) grads ride the all-gather branch
    of make_grad_sync; losses and params must track the GSPMD arm."""

    def build():
        main, startup = fluid.Program(), fluid.Program()
        startup.random_seed = main.random_seed = 13
        with fluid.program_guard(main, startup):
            ids = fluid.layers.data("ids", [4], dtype="int64")
            y = fluid.layers.data("y", [1], dtype="float32")
            emb = fluid.layers.embedding(ids, size=(50, 8), is_sparse=True)
            h = fluid.layers.reduce_mean(emb, dim=1)
            loss = fluid.layers.mean(
                fluid.layers.square_error_cost(fluid.layers.fc(h, 1), y))
            fluid.optimizer.SGD(0.1).minimize(loss)
        return main, startup, loss

    def run(mode):
        main, startup, loss = build()
        cp = fluid.CompiledProgram(main).with_data_parallel(
            loss_name=loss.name)
        if mode:
            cp = cp.with_grad_overlap(bucket_mb=0.001, mode=mode)
        scope = fluid.Scope()
        exe = fluid.Executor(fluid.TPUPlace(0))
        exe.run(startup, scope=scope)
        rng = np.random.RandomState(0)
        out = []
        for _ in range(3):
            feed = {"ids": rng.randint(0, 50, (8, 4)).astype("i8"),
                    "y": rng.rand(8, 1).astype("f4")}
            (lv,) = exe.run(cp, feed=feed, fetch_list=[loss], scope=scope)
            out.append(float(np.asarray(lv).reshape(-1)[0]))
        emb_w = np.asarray(scope.find_var(
            [p.name for p in main.all_parameters()
             if "emb" in p.name.lower() or "embedding" in p.name][0])).copy()
        return out, emb_w

    l_g, w_g = run(None)
    l_b, w_b = run("bucketed")
    np.testing.assert_allclose(l_b, l_g, rtol=1e-6)
    np.testing.assert_allclose(w_b, w_g, rtol=1e-6, atol=1e-7)
