"""Gang telemetry plane: 2-process chaos suite (ISSUE 8 acceptance).

Real 2-process gangs on the CPU backend drive every flight-recorder
trigger path through tests/dist_worker_telemetry.py:

  1. kill_worker chaos — the victim's fsynced dump survives its SIGKILL,
     the survivor dumps on the peer-failure path, the supervisor harvests
     both, and `perf_report --postmortem` renders a merged timeline
     naming the dead rank;
  2. watchdog expiry — a stalled peer (stall > watchdog deadline) makes
     the blocked rank dump on CollectiveTimeoutError, and the LIVE
     straggler detector names the stalled rank in the survivor's metrics
     stream before the watchdog ever fires;
  3. SIGTERM drain — preemption drains the resilient loop and dumps;
  4. crash — an uncaught classified error hits the telemetry excepthook.

Wall-clock bounded by run_gang's supervision timeout, same as the PR-4
chaos suite; bootstrap-load flakes are absorbed with bounded retries.
"""
import json
import os
import sys

import pytest

from dist_harness import run_gang

HERE = os.path.dirname(os.path.abspath(__file__))
TELEMETRY_WORKER = os.path.join(HERE, "dist_worker_telemetry.py")
sys.path.insert(0, os.path.join(os.path.dirname(HERE), "tools"))

pytestmark = pytest.mark.skipif(
    not os.path.exists(TELEMETRY_WORKER), reason="worker script missing")

BASE_ENV = {
    "RUN_STEPS": "6",
    "FLAGS_dist_heartbeat_interval_s": "0.25",
    "FLAGS_dist_heartbeat_miss_factor": "12",
    "FLAGS_dist_watchdog_timeout_s": "60",
    "FLAGS_dist_bootstrap_timeout_s": "120",
}


def _run(tmp_path, tag, fault_spec, extra=None):
    root = str(tmp_path / tag)
    env = dict(BASE_ENV)
    env["FLAGS_fault_spec"] = fault_spec
    env.update(extra or {})
    res = run_gang([sys.executable, TELEMETRY_WORKER], 2,
                   checkpoint_root=root, extra_env=env,
                   max_restarts=0, timeout=240)
    return res, os.path.join(root, "telemetry")


def _lost_to_bootstrap_load(res):
    for inc in res.incidents:
        for tail in inc.get("stderr_tails", {}).values():
            if ("Gloo context initialization failed" in tail
                    or "GetKeyValue" in tail):
                return True
    return False


def _blackboxes(tel_root):
    """{rank: blackbox doc} across incarnation dirs."""
    out = {}
    for dirpath, _dirs, files in os.walk(tel_root):
        for f in files:
            if f.startswith("BLACKBOX.p") and f.endswith(".json"):
                rank = int(f[len("BLACKBOX.p"):-len(".json")])
                with open(os.path.join(dirpath, f)) as fh:
                    out[rank] = json.load(fh)
    return out


def _worker_stderr(res):
    return "\n".join((e or "") for _c, _o, e in res.workers)


def _retry(tmp_path, tag, fault_spec, extra=None, attempts=3, fired=None):
    """Bounded retries absorb pure load flakes: a loaded CI box can lose a
    whole incarnation to bootstrap skew or a coordination-service abort
    BEFORE the injected fault ever fires — then the incident under test
    never happened and the attempt proves nothing.  `fired(res)` says
    whether the scheduled fault actually went off."""
    res = tel = None
    for attempt in range(attempts):
        res, tel = _run(tmp_path, f"{tag}{attempt}", fault_spec, extra)
        if _lost_to_bootstrap_load(res):
            continue
        if fired is None or fired(res):
            break
    return res, tel


def test_kill_worker_blackbox_on_every_rank_and_postmortem(tmp_path, capsys):
    # `fired` also requires the survivor's CLASSIFIED exit: under heavy
    # machine load the gloo collective can abort (XlaRuntimeError) before
    # the heartbeat detector marks the peer dead, so the dump rides the
    # crash excepthook instead of the peer-failure path — a pure timing
    # race the PR-4 chaos suite absorbs the same way (bounded retries;
    # a genuine classification regression fails all attempts)
    res, tel = _retry(
        tmp_path, "kill", "kill_worker@3:1",
        fired=lambda r: ("firing (SIGKILL)" in _worker_stderr(r)
                         and "DIST_FAILURE PeerFailureError"
                         in _worker_stderr(r)))
    assert not res.ok
    assert res.telemetry_dir and os.path.isdir(res.telemetry_dir)

    boxes = _blackboxes(tel)
    # ISSUE 8 acceptance: BLACKBOX.p*.json on EVERY rank — the victim's
    # own pre-SIGKILL dump and the survivor's peer-failure dump
    assert set(boxes) == {0, 1}, sorted(boxes)
    assert boxes[1]["reason"].startswith("kill_worker@3:1")
    assert boxes[0]["reason"] == "peer_failure"
    # both rings carry the last steps before death, rank-stamped
    assert boxes[1]["rank"] == 1 and boxes[1]["steps"]
    assert any(s.get("kind", "step") == "step" for s in boxes[1]["steps"])
    # the survivor's ring includes the peer_failure dist_event with the
    # offender's last telemetry snapshot
    pf = [s for s in boxes[0]["steps"] if s.get("kind") == "dist_event"
          and s.get("action") == "peer_failure"]
    assert pf and pf[0]["peers"] == [1]
    assert "telemetry" in pf[0]

    # the supervisor harvested the boxes into its incident ledger
    inc_files = [f for f in os.listdir(tel) if f.startswith("INCIDENT.")]
    assert inc_files
    inc = json.load(open(os.path.join(tel, inc_files[0])))
    assert len(inc["blackboxes"]) == 2

    # perf_report --postmortem renders a merged timeline naming rank 1
    import perf_report

    assert perf_report.postmortem(tel) == 0
    out = capsys.readouterr().out
    assert "dead rank(s): [1]" in out  # the KILLED rank, not the reactor
    assert "peer-failure reactions (exit 43): [0]" in out
    assert "merged timeline" in out
    assert "peer_failure" in out

    # the per-rank metrics streams merge: the survivor streamed
    # csig-stamped step records trace_merge can correlate
    import trace_merge

    files = trace_merge.find_rank_files(tel)
    assert set(files["metrics"]) == {0, 1}
    recs0 = trace_merge.load_records(files["metrics"][0])
    assert any(r.get("csig") for r in recs0 if r.get("kind") == "step")
    assert any(r.get("kind") == "dist_event" for r in recs0)


def test_watchdog_expiry_blackbox_and_live_straggler_naming(tmp_path):
    # rank 1 stalls 20s at step 2; the watchdog deadline is 8s, so rank 0
    # dumps on expiry — but its straggler detector (3 consecutive 0.1s
    # beats of sustained lag) must have named rank 1 FIRST.  The deadline
    # must clear a cold XLA compile (~3s, worse on a loaded box): the
    # watchdog guards EVERY blocking dispatch, compiles included, and a
    # deadline under compile time fires before the stall even happens.
    # Whether it did is read from the run's own record, not from a clock:
    # the expiry names the train step it blocked at, and only one AT the
    # stall's step is the incident under test (an earlier one, a compile
    # that outlasted the deadline on a loaded box, proves nothing).
    stall_step = 2
    res, tel = _retry(
        tmp_path, "stall", f"stall_worker@{stall_step}:1:20",
        extra={"FLAGS_dist_watchdog_timeout_s": "8",
               "FLAGS_dist_heartbeat_interval_s": "0.1",
               "FLAGS_dist_heartbeat_miss_factor": "150"},
        fired=lambda r: "exceeded watchdog deadline" in _worker_stderr(r)
        and f"[step={stall_step}, phase=collective]" in _worker_stderr(r))
    assert not res.ok
    boxes = _blackboxes(tel)
    assert 0 in boxes, sorted(boxes)
    assert boxes[0]["reason"] == "watchdog_timeout"
    # the expiry record carries the whole gang's telemetry table
    to = [s for s in boxes[0]["steps"] if s.get("kind") == "dist_event"
          and s.get("action") == "collective_timeout"]
    assert to and "telemetry" in to[0]

    # live straggler attribution, before any deadline fired: rank 0's
    # stream names rank 1 with the step lag as the skew metric
    import trace_merge

    files = trace_merge.find_rank_files(tel)
    recs0 = trace_merge.load_records(files["metrics"][0])
    stragglers = [r for r in recs0 if r.get("kind") == "dist_event"
                  and r.get("action") == "straggler"]
    assert stragglers, "live detector never fired"
    assert stragglers[0]["rank"] == 1
    assert stragglers[0]["skew_frac"] >= 1
    counters = boxes[0]["counters"]
    assert counters.get("dist.straggler_suspects", 0) >= 1

    # the skew gate reads the same stream
    import perf_report

    path = files["metrics"][0][0]
    assert perf_report.check(path, max_step_skew_frac=0.5) == 1
    assert perf_report.check(path, max_step_skew_frac=10.0) == 0


def test_sigterm_drain_dumps_blackbox(tmp_path):
    # preempt@2 fires in BOTH ranks: each drains its resilient loop and
    # exits 0 — the gang completes "ok" with two sigterm_drain boxes
    res, tel = _retry(tmp_path, "drain", "preempt@2", fired=lambda r: r.ok)
    assert res.ok, res.workers
    boxes = _blackboxes(tel)
    assert set(boxes) == {0, 1}
    assert all(b["reason"] == "sigterm_drain" for b in boxes.values())
    for code, out, _err in res.workers:
        line = [l for l in out.splitlines() if l.startswith("RESULT ")][0]
        assert json.loads(line[len("RESULT "):])["preempted"] is True


def test_crash_excepthook_dumps_blackbox(tmp_path):
    # device@2 with a zero retry budget: both ranks raise an uncaught
    # TransientDeviceError -> the telemetry excepthook dumps, then the
    # traceback prints and the worker dies unclassified (exit 1)
    res, tel = _retry(tmp_path, "crash", "device@2",
                      fired=lambda r: "TransientDeviceError" in _worker_stderr(r))
    assert not res.ok
    boxes = _blackboxes(tel)
    assert boxes, "no crash blackbox written"
    # both ranks inject at step 2, but one can lose the race and die on
    # the peer-failure path instead — at least one must be a crash dump,
    # and nothing else is a legal reason here
    reasons = {b["reason"] for b in boxes.values()}
    assert any(r.startswith("crash:TransientDeviceError") for r in reasons)
    assert all(r.startswith(("crash:TransientDeviceError", "peer_failure"))
               for r in reasons), reasons
