"""Fault-hardened online learning (ISSUE 19): the host-tiered sparse
table + supervised pserver + publish-cadence contract, chaos-tested.

The invariants pinned here are the round's acceptance criteria:
  - SIGKILLing the pserver child mid-stream loses NOTHING: the journal
    replays to a BIT-IDENTICAL table (server-side content digest equal
    across the kill) and the client's reconnect-retry rides the restart
    out on the same endpoint.
  - A retried push is applied EXACTLY ONCE (per-client sequence numbers;
    the dedup is observable as ps.push_dedup).
  - A rotted SelectedRows values shard (rot_row) is REJECTED by the
    publish ladder and the last good snapshot keeps serving.
  - A dead host tier degrades boundedly: hot-shard-only steps with the
    sparse.host_lag_steps gauge rising, terminal past
    FLAGS_max_host_lag_steps.
  - The publish cadence survives storage faults: a failed publish is
    absorbed + counted, staleness is measured, and the perf_report
    --max-publish-staleness-steps / --max-host-lag-steps gates hold the
    declared bounds (zero evidence fails).
"""
import glob
import json
import os
import struct
import sys
import time

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import io, layers, monitor
from paddle_tpu.core.selected_rows import SelectedRows
from paddle_tpu.errors import ParamServerError
from paddle_tpu.faults import FaultInjector
from paddle_tpu.parallel.embedding import TieredEmbedding
from paddle_tpu.param_server import (KVClient, ParameterServer,
                                     PServerSupervisor)

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "tools"))
import perf_report  # noqa: E402


# --- exactly-once + durability (in-process server) --------------------------

def test_resent_push_applied_exactly_once():
    """The same sequenced frame delivered twice (a retry whose first
    reply was lost) must mutate the table once; the duplicate is counted
    on ps.push_dedup."""
    monitor.enable()
    srv = ParameterServer(optimizer="sgd", lr=1.0).start()
    try:
        c = KVClient(srv.endpoint)
        c.create("t", np.zeros((4, 2), "f4"))
        ids = np.array([1, 2], np.int64)
        grads = np.ones((2, 2), "f4")
        c.push("t", ids, grads)  # seq 1
        # replay the exact wire message (client_id, seq=1) — the retry
        # path after a lost reply re-sends precisely this
        c._call(b"S", "t", ids, grads,
                seq_prefix=struct.pack("<QQ", c.client_id, 1))
        after = c.fetch_table("t")
        exp = np.zeros((4, 2), "f4")
        exp[[1, 2]] -= 1.0  # ONE sgd application, not two
        np.testing.assert_allclose(after, exp)
        assert monitor.counter("ps.push_dedup").value >= 1
        c.close()
    finally:
        srv.stop()
        monitor.disable()
        monitor.reset()


def test_stale_sequence_push_ignored_fresh_applied():
    """Out-of-date sequence numbers from the same client stream are
    dropped; a NEW client object is a new stream and applies."""
    srv = ParameterServer(optimizer="sgd", lr=1.0).start()
    try:
        c = KVClient(srv.endpoint)
        c.create("t", np.zeros((3, 1), "f4"))
        c.push("t", np.array([0], np.int64), np.ones((1, 1), "f4"))
        c.push("t", np.array([1], np.int64), np.ones((1, 1), "f4"))
        # seq 1 again: stale, dropped
        c._call(b"S", "t", np.array([2], np.int64), np.ones((1, 1), "f4"),
                seq_prefix=struct.pack("<QQ", c.client_id, 1))
        c2 = KVClient(srv.endpoint)
        c2.push("t", np.array([2], np.int64), np.ones((1, 1), "f4"))
        np.testing.assert_allclose(c.fetch_table("t"),
                                   [[-1.0], [-1.0], [-1.0]])
        c.close()
        c2.close()
    finally:
        srv.stop()


def test_journal_recovery_bit_identical(tmp_path):
    """Snapshot + journal replay reconstructs the table bit-identically:
    a fresh server over the same snapshot_dir reports the same content
    digest the dying server held."""
    snap = str(tmp_path / "ps")
    srv = ParameterServer(optimizer="adagrad", lr=0.5, snapshot_dir=snap,
                          snapshot_every_ops=3).start()
    c = KVClient(srv.endpoint)
    rng = np.random.RandomState(0)
    c.create("t", rng.rand(16, 4).astype("f4"))
    for i in range(8):  # crosses a snapshot boundary; journal tail replays
        c.push("t", rng.randint(0, 16, 5).astype(np.int64),
               rng.rand(5, 4).astype("f4"))
    want_digest = c.table_digest("t")
    want_table = c.fetch_table("t")
    c.close()
    # simulate a CRASH: tear the sockets down without the graceful
    # stop()-time snapshot — recovery must come from snap + journal tail
    srv._srv.shutdown()
    srv._srv.server_close()

    srv2 = ParameterServer(optimizer="adagrad", lr=0.5, snapshot_dir=snap,
                           snapshot_every_ops=3).start()
    try:
        c2 = KVClient(srv2.endpoint)
        assert c2.table_digest("t") == want_digest
        np.testing.assert_array_equal(c2.fetch_table("t"), want_table)
        c2.close()
    finally:
        srv2.stop()


def test_frame_cap_rejects_oversized_terminal():
    """A frame past FLAGS_ps_max_frame_mb is a protocol violation:
    terminal ParamServerError (no retry storm), counted."""
    monitor.enable()
    fluid.set_flags({"FLAGS_ps_max_frame_mb": 1})
    srv = ParameterServer().start()
    try:
        c = KVClient(srv.endpoint, retries=3)
        with pytest.raises(ParamServerError) as ei:
            c.create("big", np.zeros((1024, 512), "f4"))  # 2 MB frame
        assert not ei.value.transient
        c.close()
    finally:
        fluid.set_flags({"FLAGS_ps_max_frame_mb": 256})
        srv.stop()
        monitor.disable()
        monitor.reset()


# --- supervised child process: SIGKILL recovery -----------------------------

def test_supervisor_sigkill_bit_identical_and_exactly_once(tmp_path):
    """The full tentpole invariant in one life: SIGKILL the pserver
    child mid-stream; the supervisor respawns it on the SAME endpoint,
    the journal replays bit-identically (digest equality across the
    kill), and the client's retried pushes land exactly once."""
    sup = PServerSupervisor(str(tmp_path / "ps"), optimizer="sgd", lr=0.1,
                            snapshot_every_ops=4, max_restarts=2).start()
    try:
        sup.wait_ready()
        c = KVClient(sup.endpoint, retries=8, backoff_base_s=0.2)
        rng = np.random.RandomState(1)
        c.create("t", rng.rand(32, 4).astype("f4"))
        for _ in range(6):
            c.push("t", rng.randint(0, 32, 4).astype(np.int64),
                   rng.rand(4, 4).astype("f4"))
        before = c.table_digest("t")
        sup.kill()
        # the client's retry loop must ride the restart out by itself
        after = c.table_digest("t")
        assert after == before, \
            "journal replay did not reconstruct the table bit-identically"
        # pushes against the RESTARTED incarnation still apply (the
        # client's sequence stream continues across the restart)
        t0 = c.fetch_table("t")
        c.push("t", np.array([0], np.int64), np.ones((1, 4), "f4"))
        t1 = c.fetch_table("t")
        np.testing.assert_allclose(t1[0], t0[0] - 0.1)
        np.testing.assert_array_equal(t1[1:], t0[1:])
        assert sup.restarts == 1 and not sup.failed
        c.close()
    finally:
        sup.stop()


# --- degraded mode ----------------------------------------------------------

def test_degraded_mode_bounded_then_terminal():
    """With the host tier dead and degraded_ok=True, lookups run
    hot-shard-only (cold rows zero) while host_lag_steps rises; past
    FLAGS_max_host_lag_steps the next failure is TERMINAL."""
    monitor.enable()
    srv = ParameterServer(optimizer="sgd", lr=0.1).start()
    c = KVClient(srv.endpoint, retries=1, timeout_s=2.0,
                 backoff_base_s=0.0)
    emb = TieredEmbedding(c, "tbl", vocab_size=16, dim=2, hot_rows=8,
                          degraded_ok=True, seed=0)
    ids = np.array([[1, 9]])  # one hot row, one cold row
    warm = emb.lookup(ids)
    assert np.abs(warm[0, 1]).sum() > 0  # cold row served while healthy
    srv.stop()  # host tier dies...
    c.close()   # ...and the next op must reconnect (and fail)
    fluid.set_flags({"FLAGS_max_host_lag_steps": 3})
    try:
        for k in (1, 2):
            out = emb.lookup(ids)
            np.testing.assert_array_equal(out[0, 1], np.zeros(2, "f4"))
            np.testing.assert_allclose(out[0, 0], warm[0, 0])  # hot intact
            assert emb.host_lag_steps == k
        # a push during the outage drops the COLD slab only, counted —
        # it is itself one degraded step against the budget (lag 3)
        emb.apply_grad(ids.reshape(-1), np.ones((2, 2), "f4"))
        assert monitor.counter("sparse.dropped_pushes").value >= 1
        assert emb.host_lag_steps == 3
        with pytest.raises(ParamServerError) as ei:
            emb.lookup(ids)  # lag 4 > bound: terminal
        assert not ei.value.transient
        assert "host_lag_steps" in str(ei.value) or "lag" in str(ei.value)
    finally:
        fluid.set_flags({"FLAGS_max_host_lag_steps": 0})
        c.close()
        monitor.disable()
        monitor.reset()


# --- sparse publish ladder: rot_row quarantine ------------------------------

def _sparse_serving_model(tmp_path, vocab=24, dim=4, feat=3):
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        ids = layers.data("ids", [feat], dtype="int64")
        e = layers.embedding(ids, size=[vocab, dim], is_sparse=True,
                             param_attr=fluid.ParamAttr(name="q_tbl"))
        pred = layers.fc(layers.reshape(e, [-1, feat * dim]), 1,
                         param_attr=fluid.ParamAttr(name="q_fc"),
                         bias_attr=False)
    startup.random_seed = 5
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    exe.run(startup, scope=scope)
    d0 = str(tmp_path / "model-0")
    io.save_inference_model(d0, ["ids"], [pred], exe, main, scope)
    return main, scope, d0


def _sparse_snapshot(tmp_path, name, main, scope, bump=0.0):
    vocab = 24
    table = np.asarray(scope.find_var("q_tbl")).copy() + bump
    s = fluid.Scope()
    s.set_var("q_tbl", SelectedRows(np.arange(vocab, dtype=np.int64),
                                    table, vocab))
    names = [v.name for v in io._persistables(main)]
    for n in names:
        if n != "q_tbl":
            s.set_var(n, np.asarray(scope.find_var(n)))
    d = str(tmp_path / name)
    io.save_sharded(d, names, s, program=main, process_index=0)
    return d


def test_rot_row_rejected_last_good_serves(tmp_path):
    """rot_row flips a byte of a committed SelectedRows VALUES shard;
    the publish ladder must reject + quarantine it and the previous
    sparse snapshot keeps serving, digest-stamped."""
    from paddle_tpu.serving import ModelRegistry, publish
    from paddle_tpu.errors import ServingError

    monitor.enable()
    main, scope, d0 = _sparse_serving_model(tmp_path)
    reg = ModelRegistry(place=fluid.CPUPlace())
    reg.load("q", d0)
    feeds = {"ids": np.array([[1, 2, 3]], np.int64)}

    good = _sparse_snapshot(tmp_path, "snap-1", main, scope, bump=0.25)
    inj = FaultInjector("rot_row@1")
    inj.on_commit(good)  # ordinal 0: not the target
    publish(reg, "q", good)
    out_good = np.asarray(reg.acquire("q").run(feeds)[0]).copy()

    bad = _sparse_snapshot(tmp_path, "snap-2", main, scope, bump=0.5)
    inj.on_commit(bad)  # ordinal 1: flips a byte in the .vals. shard
    rotted = [f for f in os.listdir(bad) if ".vals." in f]
    assert rotted, "rot_row must target the SelectedRows values shard"
    with pytest.raises(ServingError, match="REJECTED"):
        publish(reg, "q", bad)
    out_after = np.asarray(reg.acquire("q").run(feeds)[0])
    np.testing.assert_array_equal(out_after, out_good)
    evs = [r for r in monitor.step_records()
           if r.get("kind") == "serving_event"]
    assert any(r.get("action") == "publish" and r.get("sparse_digest")
               for r in evs), "publish event must carry the sparse digest"
    assert any(r.get("action") == "publish_rejected" for r in evs)
    monitor.disable()
    monitor.reset()


def test_sparse_rung_rejects_structural_defects(tmp_path):
    """Non-monotone row ids and non-finite values both fail the sparse
    rung with a named defect (not a generic load error)."""
    from paddle_tpu.serving import ModelRegistry, publish
    from paddle_tpu.errors import ServingError

    main, scope, d0 = _sparse_serving_model(tmp_path)
    reg = ModelRegistry(place=fluid.CPUPlace())
    reg.load("q", d0)
    vocab = 24
    table = np.asarray(scope.find_var("q_tbl")).copy()
    table[3, 0] = np.nan
    s = fluid.Scope()
    s.set_var("q_tbl", SelectedRows(np.arange(vocab, dtype=np.int64),
                                    table, vocab))
    names = [v.name for v in io._persistables(main)]
    for n in names:
        if n != "q_tbl":
            s.set_var(n, np.asarray(scope.find_var(n)))
    d = str(tmp_path / "snap-nan")
    io.save_sharded(d, names, s, program=main, process_index=0)
    with pytest.raises(ServingError, match="sparse table rung"):
        publish(reg, "q", d)


# --- publish cadence under storage faults -----------------------------------

def _cadence_run(tmp_path, fault_spec, steps=12, period=3):
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = layers.data("x", [4], dtype="float32")
        y = layers.data("y", [1], dtype="float32")
        loss = fluid.layers.mean(fluid.layers.square_error_cost(
            layers.fc(x, 1, param_attr=fluid.ParamAttr(name="cw")), y))
        fluid.optimizer.SGD(0.1).minimize(loss)
    startup.random_seed = 3
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    exe.run(startup, scope=scope)
    rng = np.random.RandomState(0)
    feeds = [{"x": rng.rand(8, 4).astype("f4"),
              "y": rng.rand(8, 1).astype("f4")} for _ in range(steps)]
    pubs = []

    def hook(step):
        # through the io.py choke point: the injector's enospc window
        # fails this write exactly like a full disk would
        d = str(tmp_path / f"pub-{step}")
        io.save_vars(d, ["cw"], scope)
        pubs.append(step)

    stats = fluid.resilient_train_loop(
        exe, main, lambda: list(feeds), [loss], scope=scope,
        injector=FaultInjector(fault_spec) if fault_spec else None,
        publish_hook=hook, publish_period_steps=period,
        max_inflight=1, policy=fluid.RetryPolicy(backoff_base_s=0.0))
    return stats, pubs


def test_publish_cadence_survives_enospc(tmp_path):
    """enospc during a publish step fails THAT publish only: counted,
    staleness recorded on the publish_failed event, cadence resumes next
    period, training never stops."""
    monitor.enable()
    stats, pubs = _cadence_run(tmp_path, "enospc@6", steps=12, period=3)
    try:
        assert stats.steps == 12
        assert stats.publish_failures == 1
        assert stats.publishes >= 2 and 6 not in pubs
        evs = [r for r in monitor.step_records()
               if r.get("kind") == "resilience_event"]
        failed = [r for r in evs if r.get("action") == "publish_failed"]
        assert len(failed) == 1 and failed[0]["at_step"] == 6
        # staleness on the failure: step 6 ran 3 past the step-3 publish
        assert failed[0]["staleness"] == 3
        assert monitor.counter("serving.publish_errors").value == 1
    finally:
        monitor.disable()
        monitor.reset()


def test_publish_cadence_clean(tmp_path):
    # publish fires at the DISPATCH boundary, so 10 batches dispatch
    # steps 0..9 and the period-3 cadence lands on 3, 6, 9
    monitor.enable()
    stats, pubs = _cadence_run(tmp_path, None, steps=10, period=3)
    try:
        assert pubs == [3, 6, 9]
        assert stats.publishes == 3 and stats.publish_failures == 0
    finally:
        monitor.disable()
        monitor.reset()


# --- perf_report gates ------------------------------------------------------

def _write_stream(tmp_path, lines):
    p = str(tmp_path / "metrics.jsonl")
    with open(p, "w") as f:
        for r in lines:
            f.write(json.dumps(r) + "\n")
    return p


_STEPS = [{"kind": "step", "step": i, "recompiles_total": 1}
          for i in range(4)]


def test_gate_publish_staleness(tmp_path):
    ok = _write_stream(tmp_path, _STEPS + [
        {"kind": "resilience_event", "action": "publish", "at_step": 8},
        {"kind": "resilience_event", "action": "publish_failed",
         "at_step": 12, "staleness": 4},
    ])
    assert perf_report.check(ok, max_publish_staleness_steps=4) == 0
    assert perf_report.check(ok, max_publish_staleness_steps=3) == 1


def test_gate_publish_staleness_zero_evidence_fails(tmp_path):
    empty = _write_stream(tmp_path, _STEPS)
    assert perf_report.check(empty, max_publish_staleness_steps=100) == 1


def test_gate_host_lag(tmp_path):
    ok = _write_stream(tmp_path, _STEPS + [
        {"kind": "sparse_event", "action": "host_tier_degraded",
         "table": "t", "lag_steps": 2},
        {"kind": "sparse_event", "action": "host_tier_recovered",
         "table": "t", "lag_steps": 2},
    ])
    assert perf_report.check(ok, max_host_lag_steps=2) == 0
    assert perf_report.check(ok, max_host_lag_steps=1) == 1
    empty = _write_stream(tmp_path, _STEPS)
    assert perf_report.check(empty, max_host_lag_steps=5) == 1
