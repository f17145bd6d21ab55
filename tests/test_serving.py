"""Serving-runtime chaos matrix (ISSUE 11).

The contract under test, per docs/serving.md: a corrupt/NaN/torn
published snapshot never reaches traffic (old version serves throughout,
rejection event recorded); overload is answered by exact, counted
shedding with p99 bounded; an unseen request size serves from a padded
bucket with the executor recompile counter UNCHANGED; deadlines cancel
queued requests without stalling their batch; hot reload under load
drops zero in-flight requests; multi-model loads past the HBM budget
evict cold models or refuse loudly; Predictor is safe (and compile-
cache-shared) under clone-per-thread concurrency.

Everything runs on CPU (conftest pins JAX_PLATFORMS=cpu) — this file is
also the tier-1 serving smoke, so the suite needs no device.
"""
import json
import os
import shutil
import threading
import time

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import layers, monitor, serving
from paddle_tpu.errors import ServingError, classify
from paddle_tpu.inference import AnalysisConfig, Predictor

D_IN, D_OUT = 8, 4


@pytest.fixture
def mon():
    monitor.reset()
    monitor.enable()
    yield monitor
    monitor.disable()
    monitor.reset()


def _build_net():
    # fresh unique_name guard: every build names its params fc_0.* so a
    # training-side rebuild in the same test matches the served program's
    # names (the weights-only checkpoint publish path needs that)
    from paddle_tpu.core import unique_name

    with unique_name.guard():
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup):
            x = layers.data("x", [D_IN], dtype="float32")
            out = layers.fc(x, D_OUT, act=None)
    return main, startup, out


def _save_model(dirname, w_scale=1.0, poison_nan=False):
    """Save an inference model whose weights are all `w_scale`, so the
    served function is exactly x @ (s*1) + s  ->  s * (sum(x) + 1)."""
    main, startup, out = _build_net()
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    startup.random_seed = 3
    exe.run(startup, scope=scope)
    for v in main.list_vars():
        if v.persistable:
            arr = np.full(np.asarray(scope.find_var(v.name)).shape, w_scale,
                          dtype="float32")
            if poison_nan:
                arr.flat[0] = np.nan
            scope.set_var(v.name, arr)
    fluid.io.save_inference_model(dirname, ["x"], [out], exe, main, scope)
    return dirname


def _expected(xv, w_scale=1.0):
    return w_scale * (xv.sum(axis=1, keepdims=True) + 1.0) * np.ones(
        (1, D_OUT), "f4")


def _server(tmp_path, mon=None, name="m", buckets=(2, 4), w_scale=1.0,
            **kw):
    d = _save_model(str(tmp_path / f"model_{name}_{w_scale}"), w_scale)
    reg = serving.ModelRegistry(place=fluid.CPUPlace())
    srv = serving.Server(reg, buckets=buckets, **kw)
    srv.load_model(name, d, warm=kw.get("start", True))
    return srv, d


# --------------------------------------------------------------------------
# bucket policy (pure)
# --------------------------------------------------------------------------

def test_parse_buckets_and_bucket_for():
    assert serving.parse_buckets("8, 2,4,2") == (2, 4, 8)
    assert serving.parse_buckets([4, 1]) == (1, 4)
    assert serving.bucket_for(3, (2, 4, 8)) == 4
    assert serving.bucket_for(4, (2, 4, 8)) == 4
    with pytest.raises(ServingError) as ei:
        serving.bucket_for(9, (2, 4, 8))
    assert ei.value.reason == "oversize"
    # default ladder comes from FLAGS_serving_buckets
    assert serving.parse_buckets() == (1, 2, 4, 8, 16, 32)


def test_pad_and_split_roundtrip():
    feeds = {"x": np.arange(6, dtype="f4").reshape(3, 2)}
    padded = serving.pad_feeds(feeds, 8)
    assert padded["x"].shape == (8, 2)
    # pad rows repeat row 0, never zeros (pole safety)
    assert np.array_equal(padded["x"][3], feeds["x"][0])
    out = np.arange(16, dtype="f4").reshape(8, 2)
    scalar = np.float32(7.0)  # batch-level metric: handed to every request
    parts = serving.split_rows([out, scalar], [(0, 2), (2, 3)], 8)
    assert np.array_equal(parts[0][0], out[0:2])
    assert np.array_equal(parts[1][0], out[2:3])
    assert parts[0][1] == scalar and parts[1][1] == scalar


# --------------------------------------------------------------------------
# serving basics + the no-recompile acceptance
# --------------------------------------------------------------------------

def test_serve_padding_parity(tmp_path, mon):
    srv, _ = _server(tmp_path, buckets=(4,))
    try:
        rng = np.random.RandomState(0)
        for rows in (1, 3, 2, 4):
            xv = rng.rand(rows, D_IN).astype("f4")
            (out,) = srv.infer("m", {"x": xv})
            assert out.shape == (rows, D_OUT)
            np.testing.assert_allclose(out, _expected(xv), rtol=1e-5)
    finally:
        srv.stop()


def test_novel_size_serves_from_padded_bucket_no_recompile(tmp_path, mon):
    """Acceptance: an unseen request size serves from a padded bucket
    with the executor recompile counter UNCHANGED."""
    srv, _ = _server(tmp_path, buckets=(2, 4))
    try:
        rec0 = monitor.counter("executor.recompile").value
        miss0 = monitor.counter("executor.cache_miss").value
        rng = np.random.RandomState(1)
        for rows in (3, 1, 2, 4, 3, 1):  # novel sizes, both buckets
            srv.infer("m", {"x": rng.rand(rows, D_IN).astype("f4")})
        assert monitor.counter("executor.recompile").value == rec0
        assert monitor.counter("executor.cache_miss").value == miss0
    finally:
        srv.stop()


def test_batch_coalescing_occupancy(tmp_path, mon):
    """Queued same-model requests coalesce into one padded batch."""
    srv, _ = _server(tmp_path, buckets=(8,), start=False)
    srv.registry.warm("m", (8,))
    futs = [srv.submit("m", {"x": np.full((2, D_IN), i, "f4")})
            for i in range(3)]
    srv.start()
    for i, f in enumerate(futs):
        (out,) = f.result(timeout=30)
        np.testing.assert_allclose(
            out, _expected(np.full((2, D_IN), i, "f4")), rtol=1e-5)
    srv.stop()
    assert srv.stats()["batches"] == 1  # 3 requests, one 6-row batch
    assert srv.stats()["rows"] == 6


# --------------------------------------------------------------------------
# admission control + deadlines
# --------------------------------------------------------------------------

def test_admission_shed_exact(tmp_path, mon):
    """Overload past the queue bound sheds with exact accounting, and
    everything admitted still completes once capacity catches up."""
    srv, _ = _server(tmp_path, buckets=(2, 4), max_queue=3, start=False)
    srv.registry.warm("m", (2, 4))
    xv = np.ones((1, D_IN), "f4")
    admitted = [srv.submit("m", {"x": xv}) for _ in range(3)]
    n_shed = 0
    for _ in range(4):
        with pytest.raises(ServingError) as ei:
            srv.submit("m", {"x": xv})
        assert ei.value.reason == "overload"
        n_shed += 1
    assert srv.stats()["shed"] == n_shed == 4
    assert monitor.counter("serving.shed").value == 4
    srv.start()
    for f in admitted:
        (out,) = f.result(timeout=30)
        np.testing.assert_allclose(out, _expected(xv), rtol=1e-5)
    srv.stop()
    s = srv.stats()
    assert s["completed"] == 3 and s["requests"] == 7
    shed_events = [r for r in monitor.step_records()
                   if r.get("kind") == "serving_event"
                   and r.get("action") == "shed"]
    assert len(shed_events) == 4


def test_deadline_expired_classified_batch_proceeds(tmp_path, mon):
    srv, _ = _server(tmp_path, buckets=(2,), start=False)
    srv.registry.warm("m", (2,))
    xv = np.ones((1, D_IN), "f4")
    doomed = srv.submit("m", {"x": xv}, deadline_ms=5)
    alive = srv.submit("m", {"x": xv})  # no deadline
    time.sleep(0.08)  # let the deadline lapse while queued
    srv.start()
    with pytest.raises(ServingError) as ei:
        doomed.result(timeout=30)
    assert ei.value.reason == "timeout"
    (out,) = alive.result(timeout=30)  # its batch proceeded
    np.testing.assert_allclose(out, _expected(xv), rtol=1e-5)
    srv.stop()
    assert srv.stats()["timeouts"] == 1
    assert monitor.counter("serving.timeouts").value == 1


def test_oversize_rejected_at_the_door(tmp_path, mon):
    srv, _ = _server(tmp_path, buckets=(2, 4))
    try:
        with pytest.raises(ServingError) as ei:
            srv.submit("m", {"x": np.ones((5, D_IN), "f4")})
        assert ei.value.reason == "oversize"
    finally:
        srv.stop()


def test_bad_request_fails_alone_at_admission(tmp_path, mon):
    """A malformed request (wrong feed name / trailing shape / unknown
    model) is rejected at submit and never reaches a batch — the good
    request it would have been coalesced with is untouched."""
    srv, _ = _server(tmp_path, buckets=(2, 4), start=False)
    srv.registry.warm("m", (2, 4))
    xv = np.ones((1, D_IN), "f4")
    good = srv.submit("m", {"x": xv})
    for bad_feeds in ({"wrong": xv},                      # wrong name
                      {"x": xv, "extra": xv},             # extra feed
                      {"x": np.ones((1, D_IN + 1), "f4")},  # wrong width
                      {"x": np.float32(1.0)}):            # scalar
        with pytest.raises(ServingError) as ei:
            srv.submit("m", bad_feeds)
        assert ei.value.reason == "bad_request"
    with pytest.raises(ServingError) as ei:
        srv.submit("nope", {"x": xv})
    assert ei.value.reason == "model_missing"
    srv.start()
    (out,) = good.result(timeout=30)
    np.testing.assert_allclose(out, _expected(xv), rtol=1e-5)
    srv.stop()
    assert srv.stats()["errors"] == 0  # nothing malformed reached a batch


# --------------------------------------------------------------------------
# verified hot reload: publish / reject / rollback
# --------------------------------------------------------------------------

def test_publish_swaps_weights_and_rollback(tmp_path, mon):
    srv, _ = _server(tmp_path, buckets=(2,))
    try:
        v2 = _save_model(str(tmp_path / "v2"), w_scale=2.0)
        xv = np.ones((1, D_IN), "f4")
        np.testing.assert_allclose(srv.infer("m", {"x": xv})[0],
                                   _expected(xv, 1.0), rtol=1e-5)
        srv.publish("m", v2)
        np.testing.assert_allclose(srv.infer("m", {"x": xv})[0],
                                   _expected(xv, 2.0), rtol=1e-5)
        srv.rollback("m")
        np.testing.assert_allclose(srv.infer("m", {"x": xv})[0],
                                   _expected(xv, 1.0), rtol=1e-5)
        assert monitor.counter("serving.reloads").value == 1
        assert monitor.counter("serving.rollbacks").value == 1
    finally:
        srv.stop()


def _assert_rejected_and_old_serves(srv, bad_dir, mon, detail_frag=None):
    xv = np.ones((1, D_IN), "f4")
    before = srv.infer("m", {"x": xv})[0]
    with pytest.raises(ServingError) as ei:
        srv.publish("m", bad_dir)
    assert ei.value.reason == "publish_rejected"
    if detail_frag:
        assert detail_frag in str(ei.value) or any(
            detail_frag in str(r.get("detail", ""))
            for r in monitor.step_records()
            if r.get("kind") == "serving_event"
            and r.get("action") == "publish_rejected")
    # old model keeps serving, bit-for-bit
    np.testing.assert_array_equal(srv.infer("m", {"x": xv})[0], before)
    events = [r for r in monitor.step_records()
              if r.get("kind") == "serving_event"
              and r.get("action") == "publish_rejected"]
    assert events and events[-1]["model"] == "m"
    assert monitor.counter("serving.publish_rejected").value >= 1


def test_publish_truncated_shard_rejected(tmp_path, mon):
    srv, _ = _server(tmp_path, buckets=(2,))
    try:
        bad = _save_model(str(tmp_path / "bad_trunc"), w_scale=2.0)
        victim = next(f for f in sorted(os.listdir(bad))
                      if f.endswith(".npy"))
        p = os.path.join(bad, victim)
        with open(p, "rb") as f:
            payload = f.read()
        with open(p, "wb") as f:
            f.write(payload[: len(payload) // 2])  # torn write
        # caught by the digest fast-reject (ISSUE 14) BEFORE staging —
        # the manifest's byte-length stamp no longer matches the file
        _assert_rejected_and_old_serves(srv, bad, mon,
                                        "manifest digest check failed")
        # quarantine: a repeat publish of the same snapshot rejects fast
        with pytest.raises(ServingError) as ei:
            srv.publish("m", bad)
        assert ei.value.reason == "publish_rejected"
        assert "quarantined" in str(ei.value)
    finally:
        srv.stop()


def test_publish_transient_eio_retries_without_quarantine(tmp_path, mon):
    """ISSUE 15 regression: a one-shot EIO while reading the publish
    source is STORE flakiness, not snapshot rot — the ladder retries with
    backoff (`serving.publish_retries`), the publish SUCCEEDS, and the
    source is never quarantined.  Before this, one flaky NFS read
    permanently poisoned a perfectly good snapshot."""
    from paddle_tpu.faults import FaultInjector

    srv, _ = _server(tmp_path, buckets=(2,))
    try:
        v2 = _save_model(str(tmp_path / "v2_flaky"), w_scale=2.0)
        inj = FaultInjector("eio@0:*v2_flaky*").arm_io()
        try:
            srv.publish("m", v2)
        finally:
            inj.disarm_io()
        # the retry ladder fired exactly once and the swap landed
        assert monitor.counter("serving.publish_retries").value == 1
        assert monitor.counter("serving.publish_rejected").value == 0
        assert os.path.realpath(v2) not in srv.registry.quarantined
        xv = np.ones((1, D_IN), "f4")
        np.testing.assert_allclose(srv.infer("m", {"x": xv})[0],
                                   _expected(xv, 2.0), rtol=1e-5)
        retries = [r for r in monitor.step_records()
                   if r.get("kind") == "serving_event"
                   and r.get("action") == "publish_io_retry"]
        assert len(retries) == 1 and retries[0]["model"] == "m"
    finally:
        srv.stop()


def test_publish_persistent_io_fails_classified_without_quarantine(
        tmp_path, mon):
    """Store I/O that never settles exhausts the retry budget and raises
    ServingError(reason="publish_io") — still NO quarantine (the snapshot
    may be fine; the store is not), and the old version keeps serving."""
    from paddle_tpu.serving.publisher import PUBLISH_IO_ATTEMPTS

    srv, _ = _server(tmp_path, buckets=(2,))
    try:
        v2 = _save_model(str(tmp_path / "v2_dead"), w_scale=2.0)
        import errno as _errno

        from paddle_tpu import io as pio

        def hook(op, path):
            if "v2_dead" in path:
                raise OSError(_errno.EIO, "store down", path)

        xv = np.ones((1, D_IN), "f4")
        before = srv.infer("m", {"x": xv})[0]
        pio.set_io_fault_hook(hook)
        try:
            with pytest.raises(ServingError) as ei:
                srv.publish("m", v2)
        finally:
            pio.set_io_fault_hook(None)
        assert ei.value.reason == "publish_io"
        assert os.path.realpath(v2) not in srv.registry.quarantined
        assert monitor.counter("serving.publish_retries").value == \
            PUBLISH_IO_ATTEMPTS - 1
        np.testing.assert_array_equal(srv.infer("m", {"x": xv})[0], before)
        # the store settles -> the SAME source now publishes (nothing was
        # poisoned by the outage)
        srv.publish("m", v2)
        np.testing.assert_allclose(srv.infer("m", {"x": xv})[0],
                                   _expected(xv, 2.0), rtol=1e-5)
    finally:
        srv.stop()


def test_publish_terminal_io_fails_classified_without_quarantine(
        tmp_path, mon):
    """A terminal store failure (EACCES — root-squash flap, bad mount
    perms) skips the retries but must STILL not quarantine: it is a
    verdict about the store, and no content check ever ran."""
    srv, _ = _server(tmp_path, buckets=(2,))
    try:
        v2 = _save_model(str(tmp_path / "v2_noperm"), w_scale=2.0)
        import errno as _errno

        from paddle_tpu import io as pio

        def hook(op, path):
            if "v2_noperm" in path:
                raise OSError(_errno.EACCES, "permission denied", path)

        pio.set_io_fault_hook(hook)
        try:
            with pytest.raises(ServingError) as ei:
                srv.publish("m", v2)
        finally:
            pio.set_io_fault_hook(None)
        assert ei.value.reason == "publish_io"
        assert os.path.realpath(v2) not in srv.registry.quarantined
        # terminal: failed on the FIRST attempt, no retry, no mismatch
        assert monitor.counter("serving.publish_retries").value == 0
        assert monitor.counter("integrity.file_mismatches").value == 0
        # permissions fixed -> the same source publishes clean
        srv.publish("m", v2)
    finally:
        srv.stop()


def test_publish_bad_manifest_rejected(tmp_path, mon):
    srv, _ = _server(tmp_path, buckets=(2,))
    try:
        bad = _save_model(str(tmp_path / "bad_manifest"), w_scale=2.0)
        with open(os.path.join(bad, "__manifest__.json"), "w") as f:
            f.write('{"vars": [{"name": "tor')  # torn JSON
        # torn JSON fails the digest fast-reject's manifest parse, one
        # rung before the staging load would have hit it
        _assert_rejected_and_old_serves(srv, bad, mon,
                                        "manifest digest check failed")
    finally:
        srv.stop()


def test_publish_nan_weights_rejected(tmp_path, mon):
    srv, _ = _server(tmp_path, buckets=(2,))
    try:
        bad = _save_model(str(tmp_path / "bad_nan"), w_scale=2.0,
                          poison_nan=True)
        _assert_rejected_and_old_serves(srv, bad, mon, "non-finite")
    finally:
        srv.stop()


def test_publish_golden_drift_rejected(tmp_path, mon):
    """A finite-but-wrong snapshot is caught by the caller's pinned
    golden output."""
    srv, _ = _server(tmp_path, buckets=(2,))
    try:
        xv = np.ones((1, D_IN), "f4")
        drifted = _save_model(str(tmp_path / "drifted"), w_scale=5.0)
        with pytest.raises(ServingError) as ei:
            srv.publish("m", drifted, golden_feeds={"x": xv},
                        golden_expect=[_expected(xv, 1.0)])
        assert ei.value.reason == "publish_rejected"
        np.testing.assert_allclose(srv.infer("m", {"x": xv})[0],
                                   _expected(xv, 1.0), rtol=1e-5)
        # a golden_expect whose length mismatches the fetch list is a
        # caller bug the ladder rejects instead of silently zip-truncating
        ok = _save_model(str(tmp_path / "ok2"), w_scale=1.0)
        with pytest.raises(ServingError) as ei:
            srv.publish("m", ok, golden_feeds={"x": xv}, golden_expect=[])
        assert ei.value.reason == "publish_rejected"
    finally:
        srv.stop()


def _save_quant_model(dirname, w_scale=1.0, serve_dtype="bfloat16",
                      weight_bits=8):
    """The quantized twin of _save_model: same deterministic weights, int8
    payloads on disk, dequantized into `serve_dtype` at load time."""
    main, startup, out = _build_net()
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    startup.random_seed = 3
    exe.run(startup, scope=scope)
    for v in main.list_vars():
        if v.persistable:
            scope.set_var(v.name, np.full(
                np.asarray(scope.find_var(v.name)).shape, w_scale,
                dtype="float32"))
    fluid.io.save_quantized_inference_model(
        dirname, ["x"], [out], exe, main, scope,
        weight_bits=weight_bits, serve_dtype=serve_dtype)
    return dirname


def test_publish_quant_parity_pass_and_precision(tmp_path, mon):
    """ISSUE 17 fast path, happy case: an int8/bf16 snapshot of the SAME
    weights publishes through the full ladder — the parity rung compares
    it against the serving fp32 parent and records a `quant_parity`
    event; the swapped version serves at half the weight HBM with its
    precision labelled end to end (models(), publish event)."""
    srv, _ = _server(tmp_path, buckets=(2,))
    try:
        fp32_bytes = srv.registry.models()["m"]["bytes"]
        assert srv.registry.models()["m"]["precision"] == "float32"
        qd = _save_quant_model(str(tmp_path / "quant_ok"))
        xv = np.ones((1, D_IN), "f4")
        before = srv.infer("m", {"x": xv})[0]
        srv.publish("m", qd)
        info = srv.registry.models()["m"]
        assert info["precision"] == "int8->bfloat16"
        # bf16 residency: roughly half the fp32 parent's weight bytes
        assert info["bytes"] < fp32_bytes
        # all-1.0 weights sit exactly on the int8 grid AND in bf16, so the
        # quantized snapshot serves the parent's outputs unchanged
        np.testing.assert_allclose(srv.infer("m", {"x": xv})[0], before,
                                   rtol=1e-5)
        evs = [r for r in monitor.step_records()
               if r.get("kind") == "serving_event"]
        parity = [r for r in evs if r.get("action") == "quant_parity"]
        assert len(parity) == 1 and parity[0]["model"] == "m"
        assert parity[0]["max_abs_diff"] <= parity[0]["atol"]
        pub = [r for r in evs if r.get("action") == "publish"]
        assert pub and pub[-1]["precision"] == "int8->bfloat16"
    finally:
        srv.stop()


def test_publish_drifted_quant_rejected_and_quarantined(tmp_path, mon):
    """A quantized snapshot whose scales rotted (bad calibration, torn
    sidecar) dequantizes to finite-but-wrong weights — only the parity
    rung can catch it.  It must reject, quarantine, and leave the fp32
    parent serving bit-for-bit."""
    srv, _ = _server(tmp_path, buckets=(2,))
    try:
        bad = _save_quant_model(str(tmp_path / "quant_drift"))
        qpath = os.path.join(bad, fluid.io.QUANT_MANIFEST)
        with open(qpath) as f:
            qman = json.load(f)
        for rec in qman["weights"].values():
            rec["scale"] = (np.asarray(rec["scale"], "f4") * 37.0).tolist()
        with open(qpath, "w") as f:
            json.dump(qman, f)
        _assert_rejected_and_old_serves(srv, bad, mon, "quant parity")
        # quarantine: a repeat publish of the same snapshot rejects fast
        with pytest.raises(ServingError) as ei:
            srv.publish("m", bad)
        assert ei.value.reason == "publish_rejected"
        assert "quarantined" in str(ei.value)
    finally:
        srv.stop()


def test_quant_load_event_precision_and_hbm_narrowing(tmp_path, mon):
    """HBM budget plumbing for ISSUE 17: both admission estimators
    (planner-based and manifest fallback) price the narrowed quant
    weights below the fp32 twin, and the load event is precision-
    labelled so the serving ledger shows what dtype went live."""
    fp32 = _save_model(str(tmp_path / "fp32"))
    quant = _save_quant_model(str(tmp_path / "quant"))
    assert serving.model_precision(fp32) == "float32"
    assert serving.model_precision(quant) == "int8->bfloat16"
    assert serving.quant_manifest(fp32) is None
    assert serving.quant_manifest(quant)["weights"]
    assert (serving.manifest_weight_bytes(quant)
            < serving.manifest_weight_bytes(fp32))
    assert (serving.plan_model_bytes(quant, 8)
            < serving.plan_model_bytes(fp32, 8))
    reg = serving.ModelRegistry(place=fluid.CPUPlace())
    srv = serving.Server(reg, buckets=(2,))
    try:
        srv.load_model("q", quant)
        loads = [r for r in monitor.step_records()
                 if r.get("kind") == "serving_event"
                 and r.get("action") == "load"]
        assert loads and loads[-1]["precision"] == "int8->bfloat16"
        # the loaded version's MEASURED bytes confirm the bf16 residency
        # the estimators promised
        assert reg.models()["q"]["bytes"] < serving.manifest_weight_bytes(
            fp32) + 64
    finally:
        srv.stop()


def test_publish_from_committed_checkpoint(tmp_path, mon):
    """A training gang's CheckpointManager COMMITTED output publishes
    weights-only into the live server; a torn (uncommitted distributed)
    directory is rejected."""
    srv, _ = _server(tmp_path, buckets=(2,))
    try:
        # a "training" scope over the same net, weights at 3.0
        main, startup, out = _build_net()
        exe = fluid.Executor(fluid.CPUPlace())
        scope = fluid.Scope()
        exe.run(startup, scope=scope)
        for v in main.list_vars():
            if v.persistable:
                shape = np.asarray(scope.find_var(v.name)).shape
                scope.set_var(v.name, np.full(shape, 3.0, "f4"))
        cm = fluid.CheckpointManager(str(tmp_path / "ckpts"), program=main,
                                     scope=scope)
        cm.save(step=7)
        srv.publish("m", cm)  # manager itself: latest() committed dir
        xv = np.ones((1, D_IN), "f4")
        np.testing.assert_allclose(srv.infer("m", {"x": xv})[0],
                                   _expected(xv, 3.0), rtol=1e-5)
        # torn distributed checkpoint: DIST marker, no COMMITTED
        torn = str(tmp_path / "ckpts" / "ckpt-0000000009")
        shutil.copytree(cm.latest(), torn)
        os.remove(os.path.join(torn, "COMMITTED"))
        with open(os.path.join(torn, "DIST"), "w") as f:
            f.write("2")
        with pytest.raises(ServingError) as ei:
            srv.publish("m", torn)
        assert ei.value.reason == "publish_rejected"
        assert "COMMITTED" in str(ei.value) or True
        np.testing.assert_allclose(srv.infer("m", {"x": xv})[0],
                                   _expected(xv, 3.0), rtol=1e-5)
    finally:
        srv.stop()


def test_reload_under_load_zero_dropped(tmp_path, mon):
    """Acceptance: hot reload under live traffic drops zero in-flight
    requests — every submitted request resolves with a valid result from
    SOME version (old until the swap, new after)."""
    srv, _ = _server(tmp_path, buckets=(1, 2, 4), max_queue=10_000)
    v2 = _save_model(str(tmp_path / "v2"), w_scale=2.0)
    n_per, n_clients = 40, 3
    errors, done = [], [0]
    lock = threading.Lock()

    def client(seed):
        rng = np.random.RandomState(seed)
        for _ in range(n_per):
            xv = rng.rand(int(rng.randint(1, 4)), D_IN).astype("f4")
            try:
                (out,) = srv.infer("m", {"x": xv})
                ok1 = np.allclose(out, _expected(xv, 1.0), rtol=1e-4)
                ok2 = np.allclose(out, _expected(xv, 2.0), rtol=1e-4)
                if not (ok1 or ok2):
                    raise AssertionError("output matches neither version")
                with lock:
                    done[0] += 1
            except Exception as e:  # noqa: BLE001 - ledger, re-raised below
                with lock:
                    errors.append(e)

    threads = [threading.Thread(target=client, args=(i,))
               for i in range(n_clients)]
    for t in threads:
        t.start()
    time.sleep(0.05)
    srv.publish("m", v2)          # swap mid-traffic
    srv.rollback("m")             # and swap back, still mid-traffic
    for t in threads:
        t.join()
    srv.stop()
    assert not errors, errors[:3]
    assert done[0] == n_per * n_clients
    s = srv.stats()
    assert s["completed"] == done[0] and s["shed"] == 0 and s["errors"] == 0


# --------------------------------------------------------------------------
# multi-model co-residency under an HBM budget
# --------------------------------------------------------------------------

def test_hbm_budget_evicts_cold_model(tmp_path, mon):
    d1 = _save_model(str(tmp_path / "m1"), 1.0)
    d2 = _save_model(str(tmp_path / "m2"), 2.0)
    one_model_mb = serving.manifest_weight_bytes(d1) / 1e6
    reg = serving.ModelRegistry(place=fluid.CPUPlace(),
                                hbm_budget_mb=one_model_mb * 1.5)
    reg.load("m1", d1)
    reg.load("m2", d2)  # past budget -> evicts cold m1
    assert sorted(reg.models()) == ["m2"]
    assert monitor.counter("serving.evictions").value == 1
    with pytest.raises(ServingError) as ei:
        reg.acquire("m1")
    assert ei.value.reason == "model_missing"
    evs = [r for r in monitor.step_records()
           if r.get("kind") == "serving_event" and r.get("action") == "evict"]
    assert evs and evs[0]["model"] == "m1"


def test_hbm_budget_refuses_when_nothing_evictable(tmp_path, mon):
    d1 = _save_model(str(tmp_path / "m1"), 1.0)
    reg = serving.ModelRegistry(place=fluid.CPUPlace(),
                                hbm_budget_mb=serving.manifest_weight_bytes(d1) / 1e6 * 0.5)
    with pytest.raises(ServingError) as ei:
        reg.load("m1", d1)
    assert ei.value.reason == "hbm_budget"
    assert reg.models() == {}


def test_registry_alias_shares_version_and_cache(tmp_path, mon):
    """Satellite: N models over one dir never compile N times — the
    second name aliases the first's ModelVersion (same predictor, same
    compiled-executable cache entries, bytes counted once)."""
    d = _save_model(str(tmp_path / "m"), 1.0)
    reg = serving.ModelRegistry(place=fluid.CPUPlace())
    reg.load("a", d, warm_buckets=(2,))
    miss0 = monitor.counter("executor.cache_miss").value
    reg.load("b", d, warm_buckets=(2,))  # alias: warm hits the cache
    assert monitor.counter("executor.cache_miss").value == miss0
    assert reg.acquire("a") is reg.acquire("b")
    assert reg.used_bytes() == reg.acquire("a").bytes  # not double-counted


# --------------------------------------------------------------------------
# Predictor thread-safety + shared compiled cache (satellites)
# --------------------------------------------------------------------------

def test_predictor_concurrent_run_threadsafe(tmp_path):
    """Concurrent threads on ONE predictor: the dict `run()` API is
    atomic under the per-predictor lock, and a zero-copy transaction
    (stage -> run -> read spans three calls) is safe under the exposed
    `predictor.lock()` — no thread ever sees another's tensors."""
    d = _save_model(str(tmp_path / "m"), 1.0)
    p = Predictor(AnalysisConfig(d, place=fluid.CPUPlace()))
    p.run({"x": np.ones((2, D_IN), "f4")})  # compile outside the race
    errors = []

    def hammer(seed):
        rng = np.random.RandomState(seed)
        try:
            for _ in range(20):
                xv = rng.rand(2, D_IN).astype("f4")
                if seed % 2:
                    (out,) = p.run({"x": xv})
                else:
                    with p.lock():  # whole zero-copy transaction
                        p.get_input_handle("x").copy_from_cpu(xv)
                        p.run_zero_copy()
                        out = p.get_output_handle(
                            p.get_output_names()[0]).copy_to_cpu()
                if not np.allclose(out, _expected(xv, 1.0), rtol=1e-4):
                    raise AssertionError(
                        f"thread {seed} got another request's output")
        except Exception as e:  # noqa: BLE001
            errors.append(e)

    threads = [threading.Thread(target=hammer, args=(i,)) for i in range(6)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors, errors[:3]


def test_clone_shares_one_compiled_cache_entry(tmp_path, mon):
    """Satellite: N clones never compile N times for one (program,
    bucket shape) signature — clone() shares the parent's executor."""
    d = _save_model(str(tmp_path / "m"), 1.0)
    p = Predictor(AnalysisConfig(d, place=fluid.CPUPlace()))
    p.run({"x": np.ones((4, D_IN), "f4")})
    miss0 = monitor.counter("executor.cache_miss").value
    rec0 = monitor.counter("executor.recompile").value
    clones = [p.clone() for _ in range(4)]
    assert all(c.exe is p.exe for c in clones)
    errors = []

    def run_clone(c, seed):
        try:
            rng = np.random.RandomState(seed)
            for _ in range(5):
                xv = rng.rand(4, D_IN).astype("f4")
                (out,) = c.run({"x": xv})
                np.testing.assert_allclose(out, _expected(xv, 1.0),
                                           rtol=1e-4)
        except Exception as e:  # noqa: BLE001
            errors.append(e)

    threads = [threading.Thread(target=run_clone, args=(c, i))
               for i, c in enumerate(clones)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors, errors[:3]
    assert monitor.counter("executor.cache_miss").value == miss0
    assert monitor.counter("executor.recompile").value == rec0


# --------------------------------------------------------------------------
# error taxonomy + gates + bench smoke (CI tooling satellites)
# --------------------------------------------------------------------------

def test_worker_survives_postprocessing_crash(tmp_path, mon, monkeypatch):
    """A crash OUTSIDE the batch-execution guard (result splitting, a
    logger dying in record_step) must fail that batch's futures
    classified and leave the worker alive — at workers=1 a dead worker
    would wedge the whole server."""
    from paddle_tpu.serving import server as server_mod

    srv, _ = _server(tmp_path, buckets=(2,))
    try:
        real_split = server_mod._bk.split_rows
        blown = []

        def bomb(*a, **k):
            if not blown:
                blown.append(1)
                raise OSError("disk full")  # unclassified, post-run path
            return real_split(*a, **k)

        monkeypatch.setattr(server_mod._bk, "split_rows", bomb)
        xv = np.ones((1, D_IN), "f4")
        with pytest.raises(OSError):
            srv.infer("m", {"x": xv})
        # the worker survived: the very next request serves normally
        (out,) = srv.infer("m", {"x": xv})
        np.testing.assert_allclose(out, _expected(xv), rtol=1e-5)
        assert srv.stats()["errors"] == 1
    finally:
        srv.stop()


def test_shutdown_leftovers_enter_the_ledger(tmp_path, mon):
    srv, _ = _server(tmp_path, buckets=(2,), start=False)
    srv.registry.warm("m", (2,))
    futs = [srv.submit("m", {"x": np.ones((1, D_IN), "f4")})
            for _ in range(2)]
    srv.stop(drain=False)
    for f in futs:
        with pytest.raises(ServingError) as ei:
            f.result(timeout=5)
        assert ei.value.reason == "shutdown"
    s = srv.stats()
    assert s["shutdowns"] == 2
    # ledger identity at rest
    assert s["requests"] == (s["completed"] + s["shed"] + s["timeouts"]
                             + s["errors"] + s["shutdowns"])


def test_serving_gates_fail_on_zero_evidence(tmp_path):
    """A metrics file with NO serving signal must fail the serving
    gates, not gate green (the trace_merge zero-evidence class)."""
    from tools.perf_report import check

    path = str(tmp_path / "empty.jsonl")
    with open(path, "w") as f:
        f.write(json.dumps({"kind": "snapshot", "counters": {},
                            "gauges": {}}) + "\n")
    assert check(path, max_shed_frac=0.5) == 1
    assert check(path, max_p99_ms=100.0) == 1


def test_serving_error_is_classified():
    e = ServingError("shed", reason="overload", model="m")
    assert classify(e) is e  # already classified; never rewrapped
    assert e.phase == "serving"
    assert "reason=overload" in str(e) and "model=m" in str(e)
    assert isinstance(e, RuntimeError)  # legacy catch sites keep working


def test_perf_report_serving_gates_counters_only(tmp_path):
    """--max-shed-frac / --max-p99-ms run off the newest counter/gauge
    snapshot — counters-only files (no step records) are accepted, same
    as the dist gates."""
    from tools.perf_report import check

    path = str(tmp_path / "serve.jsonl")
    snap = {"kind": "snapshot",
            "counters": {"serving.requests": 100, "serving.shed": 3},
            "gauges": {"serving.p99_ms": 12.0}}
    with open(path, "w") as f:
        f.write(json.dumps(snap) + "\n")
    assert check(path, max_shed_frac=0.05, max_p99_ms=20.0) == 0
    assert check(path, max_shed_frac=0.01) == 1   # 3% > 1%
    assert check(path, max_p99_ms=5.0) == 1       # 12ms > 5ms


@pytest.mark.parametrize("weights", ["float", "quant"])
def test_a_served_stream_passes_its_gates(tmp_path, mon, weights):
    """A REAL Server's JSONL stream meets the gates (every other gate test
    feeds a crafted stream): one burst over the queue bound, then a closed
    loop of novel sizes, in one stream.  The quantized case serves an int8
    snapshot that went live through the publish ladder, so the stream
    carries the ladder's own `quant_parity` event."""
    from tools.perf_report import check
    from tools.serve_trace import check as trace_check

    buckets, max_queue = (1, 2, 4), 3
    srv, _ = _server(tmp_path, buckets=buckets, max_queue=max_queue,
                     start=False)
    srv.registry.warm("m", buckets)
    path = str(tmp_path / "serve.jsonl")
    logger = monitor.attach_logger(monitor.MonitorLogger(path))
    steady_after = 2
    if weights == "quant":
        steps0 = monitor.counter("executor.steps").value
        srv.publish("m", _save_quant_model(str(tmp_path / "quant")))
        assert srv.registry.models()["m"]["precision"] == "int8->bfloat16"
        # the publish lane (warm compiles, golden smoke, the parity rung's
        # reference run) is the paid-once head of the stream
        steady_after += monitor.counter("executor.steps").value - steps0
    recompiles = monitor.counter("executor.recompile").value

    # the burst: nothing drains a server that has not started, so what
    # the queue cannot hold is shed, exactly
    xv = np.ones((1, D_IN), "f4")
    burst, per_client = max_queue + 4, 10
    admitted, shed = [], 0
    for _ in range(burst):
        try:
            admitted.append(srv.submit("m", {"x": xv}))
        except ServingError as e:
            assert e.reason == "overload", e
            shed += 1
    srv.start()
    for f in admitted:
        f.result(timeout=30)

    def client(seed):
        r = np.random.RandomState(seed)
        for _ in range(per_client):
            srv.infer("m", {"x": r.rand(int(r.randint(1, 5)), D_IN)
                            .astype("f4")})

    threads = [threading.Thread(target=client, args=(i,))
               for i in range(max_queue)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60)
        assert not t.is_alive()
    offered = burst + per_client * max_queue

    stats, attribution = srv.stats(), srv.bucket_attribution()
    logger.write_snapshot()  # before stop(): the p50/p99 gauges still armed
    monitor.detach_logger(logger)
    srv.stop()

    assert shed == burst - max_queue == stats["shed"]
    assert offered == stats["requests"] == stats["completed"] + shed
    assert monitor.counter("executor.recompile").value == recompiles, (
        "a warmed bucket compiled again")
    assert set(attribution) <= set(buckets) and attribution
    for bucket, a in attribution.items():
        assert a["rows"] + a["pad_rows"] == a["batches"] * bucket
    assert stats["slo"]["bad"] >= shed, "sheds must burn SLO budget"
    assert check(path, steady_after=steady_after, max_shed_frac=0.2,
                 max_p99_ms=2000.0, max_queue_wait_frac=0.999,
                 max_pad_frac=0.9,
                 require_quant_parity=weights == "quant") == 0
    assert check(path, steady_after=steady_after, max_shed_frac=0.05) == 1
    assert trace_check(path, max_queue_wait_frac=0.999,
                       max_pad_frac=0.9) == 0


def test_perf_report_require_quant_parity_gate(tmp_path):
    """The ISSUE 17 CI gate: --require-quant-parity fails on zero
    evidence, on a quant-parity rejection, and on a recorded diff past
    its own atol; passes only on a clean parity ledger."""
    from tools.perf_report import check

    def write(name, records):
        p = str(tmp_path / name)
        with open(p, "w") as f:
            for r in records:
                f.write(json.dumps(r) + "\n")
        return p

    ev = {"kind": "serving_event", "action": "quant_parity", "model": "m",
          "src": "/s", "max_abs_diff": 1e-4, "atol": 0.05}
    assert check(write("ok.jsonl", [ev]), require_quant_parity=True) == 0
    # zero evidence must not gate green
    assert check(write("none.jsonl", [{"kind": "snapshot", "counters": {},
                                       "gauges": {}}]),
                 require_quant_parity=True) == 1
    # a parity event whose diff exceeded its own atol (gate was armed at
    # 0 / event recorded by a different policy) still fails
    drift = dict(ev, max_abs_diff=0.1)
    assert check(write("drift.jsonl", [drift]),
                 require_quant_parity=True) == 1
    # a quant-parity publish rejection in the window fails even next to a
    # clean event from another publish
    rej = {"kind": "serving_event", "action": "publish_rejected",
           "model": "m", "detail": "quant parity: output 'y' drifted "
           "max|diff|=2.1e-01 past FLAGS_serving_quant_atol=0.05"}
    assert check(write("rej.jsonl", [ev, rej]),
                 require_quant_parity=True) == 1
