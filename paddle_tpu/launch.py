"""Gang launcher with restart: the `paddle.distributed.launch` role,
grown a fault-tolerance story.

Promoted from tests/dist_harness.py (which now wraps this module): one
copy of the port allocation, the `PADDLE_TRAINER_*` env contract, and
worker spawning — plus what the test harness never had:

  * **leak-free spawning** — `Gang` is a context manager that always
    kills and reaps every worker on the way out (bounded per-worker join,
    SIGTERM then SIGKILL), so a failed spawn or a raising test body never
    strands live subprocesses;
  * **TOCTOU-free ports** — `allocate_port_block(n)` binds all `n`
    consecutive ports simultaneously before releasing them, retrying on
    `EADDRINUSE` with a fresh base instead of assuming `port+i` is free;
  * **gang restart** — `run_gang` supervises the workers, and when one
    dies (SIGKILL, classified resilience exit, crash) it kills the
    stragglers, clears uncommitted checkpoint debris, and relaunches the
    whole gang on a fresh port block with `PADDLE_RESTART_NUM` bumped —
    workers resume from the last *coordinated* checkpoint
    (`CheckpointManager` rank-0 COMMITTED marker) with `step_offset`
    continuity, so the restarted run's params are bit-identical to an
    uninterrupted one.  A worker driving `resilient_train_loop` over a
    checkpointable data source (ISSUE 5 stream-state protocol) resumes
    its input stream by O(1) seek too: the committed checkpoint's
    RESUME.json sidecar carries the pickled reader state, so a restart
    never replays the dataset to find its place.

  * **elastic gangs** (ISSUE 9) — with `elastic=True` (CLI `--elastic`)
    the relaunch follows capacity: an unclassified death shrinks the
    next incarnation to N−1 (classified 43/44 exits are survivors
    reacting, not lost capacity) and workers resume via the elastic
    checkpoint path (`CheckpointManager` N→M re-sharding + stream-cursor
    repartition, `paddle_tpu/elastic.py`); once the shrunk gang commits
    a fresh checkpoint and capacity returns, the supervisor drains it
    gracefully (SIGTERM → flush → exit 0) and grows back toward
    `--nproc`.  Every resize is a `gang_resize` dist_event gated by
    `perf_report --check --max-gang-resizes`.

The once-per-gang fault ledger (`PADDLE_FAULT_STATE_DIR`, exported per
run_gang call) also covers the data faults `corrupt_chunk@N` /
`truncated_file@N`: a restarted incarnation re-opens its RecordIO files,
and without the ledger the injector would re-corrupt them every
incarnation.

CLI (the reference `python -m paddle.distributed.launch` shape):

    python -m paddle_tpu.launch --nproc 2 --max-restarts 3 \
        [--devices-per-proc 1] [--metrics gang.jsonl] worker.py [args...]

Monitor surface: the launcher process emits `dist.gang_restarts` /
`dist.worker_deaths` counters and one `kind="dist_event"` record per
incident (`action="gang_restart"` / `"worker_death"` / `"gang_failed"`),
written to `--metrics` as JSONL — the file `tools/perf_report.py --check
--max-gang-restarts` gates in CI.

Telemetry plane (ISSUE 8): every incarnation also gets a rank-shared
telemetry directory (`--telemetry-root`, default under the checkpoint
root), exported as `PADDLE_TELEMETRY_DIR`; each worker's `fleet.init`
streams its rank-tagged metrics there and arms the flight recorder, the
supervisor harvests `BLACKBOX.p<rank>.json` dumps into
`INCIDENT.i<k>.json` ledgers across restarts, and `tools/trace_merge.py`
/ `perf_report --postmortem` turn the directory into a merged timeline
with straggler attribution.  See docs/observability.md §Debugging a gang.
"""
from __future__ import annotations

__all__ = ["allocate_port_block", "worker_env", "Gang", "GangResult",
           "run_gang", "run_serving_fleet", "main"]

import argparse
import errno
import os
import random
import shutil
import socket
import subprocess
import sys
import tempfile
import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence

from . import faults
from .monitor import MONITOR as _MON

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# dist_resilience's classified exits (peer failure / watchdog timeout);
# labels the `classified` field of incident records.  Restart policy is
# deliberately broader — ANY death restarts, because unclassified exits
# include real restartable cases (a raw SIGKILL, a bootstrap lost to
# machine load) and the once-per-gang fault ledger / max_restarts budget
# bound the damage of relaunching a deterministic crasher.
# EXIT_PEER_FAILURE, EXIT_COLLECTIVE_TIMEOUT, EXIT_INTEGRITY: all three
# are ranks REACTING to a condition the gang restart recovers from (a
# dead peer, a wedged collective, detected silent corruption) — not lost
# capacity, so the elastic supervisor relaunches them at full size
_CLASSIFIED_EXITS = (43, 44, 45)


def allocate_port_block(n: int, tries: int = 64,
                        low: int = 20000, high: int = 50000) -> int:
    """Base port of `n` CONSECUTIVE free TCP ports, verified by binding
    all of them simultaneously (close-then-reuse races shrink to the
    spawn window instead of `n` independent guesses).  The old
    `free_port() + i` scheme was a TOCTOU lottery: any daemon grabbing
    `port+i` between close and worker bind wedged the whole bootstrap
    with EADDRINUSE."""
    rng = random.Random(os.getpid() * 7919 + int(time.time() * 1e3) % 65536)
    last_err: Optional[OSError] = None
    for _ in range(tries):
        base = rng.randrange(low, high - n)
        socks = []
        try:
            for i in range(n):
                s = socket.socket()
                socks.append(s)
                s.bind(("127.0.0.1", base + i))
            return base
        except OSError as e:
            if e.errno not in (errno.EADDRINUSE, errno.EACCES):
                raise
            last_err = e
        finally:
            for s in socks:
                s.close()
    raise OSError(
        f"allocate_port_block: no free block of {n} consecutive ports in "
        f"[{low}, {high}) after {tries} tries (last: {last_err})")


def worker_env(rank: int, endpoints: Sequence[str],
               devices_per_proc: int = 1,
               extra: Optional[Dict[str, str]] = None) -> Dict[str, str]:
    """Env for one worker under the PADDLE_TRAINER_* contract, on the CPU
    virtual mesh: gang ranks are host-CPU processes today (a chip belongs
    to one process; several chips are driven from ONE process over a mesh,
    CompiledProgram.with_mesh).  Workers import the repo they were
    launched from."""
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO_ROOT
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices_per_proc}"
    env["PADDLE_TRAINER_ID"] = str(rank)
    env["PADDLE_TRAINER_ENDPOINTS"] = ",".join(endpoints)
    env["PADDLE_CURRENT_ENDPOINT"] = endpoints[rank]
    env.update(extra or {})
    return env


class Gang:
    """Spawn-and-always-reap context manager around one gang incarnation.

        with Gang([sys.executable, worker_py], n_procs=2) as gang:
            results = gang.communicate(timeout=600)

    On exit — success, failure, or mid-spawn exception — every live
    worker is killed (SIGTERM, then SIGKILL after `grace_s`) and reaped
    with a bounded join, so no orphan ever sits blocked inside
    jax.distributed.initialize holding its port."""

    def __init__(self, argv: Sequence[str], n_procs: int,
                 devices_per_proc: int = 1,
                 extra_env: Optional[Dict[str, str]] = None,
                 per_rank_env: Optional[Dict[int, Dict[str, str]]] = None,
                 grace_s: float = 3.0):
        self.argv = list(argv)
        self.n_procs = n_procs
        self.devices_per_proc = devices_per_proc
        self.extra_env = dict(extra_env or {})
        self.per_rank_env = {r: dict(e) for r, e in (per_rank_env or {}).items()}
        self.grace_s = grace_s
        self.procs: List[subprocess.Popen] = []
        self._files: List[tuple] = []  # (stdout, stderr) spool per worker
        self.base_port: Optional[int] = None
        self.endpoints: List[str] = []

    def __enter__(self) -> "Gang":
        self.base_port = allocate_port_block(self.n_procs)
        self.endpoints = [f"127.0.0.1:{self.base_port + i}"
                          for i in range(self.n_procs)]
        try:
            for rank in range(self.n_procs):
                extra = dict(self.extra_env)
                extra.update(self.per_rank_env.get(rank, {}))
                env = worker_env(rank, self.endpoints,
                                 self.devices_per_proc, extra)
                # worker output goes to spooled temp FILES, not pipes: a
                # pipe fills at ~64KB and a worker chatty past that (per-
                # step logs, repeated stack dumps) would block in write()
                # while the unsuspecting supervisor reads it as "alive"
                out_f = tempfile.TemporaryFile(mode="w+t")
                err_f = tempfile.TemporaryFile(mode="w+t")
                self._files.append((out_f, err_f))
                self.procs.append(subprocess.Popen(
                    self.argv, stdout=out_f, stderr=err_f, env=env,
                    text=True))
        except BaseException:
            self._reap()
            raise
        return self

    def __exit__(self, *exc):
        self._reap()
        return False

    def _reap(self):
        for p in self.procs:
            if p.poll() is None:
                p.terminate()
        deadline = time.monotonic() + self.grace_s
        for p in self.procs:
            if p.poll() is None:
                try:
                    p.wait(timeout=max(0.0, deadline - time.monotonic()))
                except subprocess.TimeoutExpired:
                    p.kill()
        for p in self.procs:
            if p.poll() is None:
                try:
                    p.wait(timeout=self.grace_s)
                except subprocess.TimeoutExpired:
                    pass  # unkillable (D-state); nothing more a user can do
        for of, ef in self._files:
            for f in (of, ef):
                try:
                    f.close()
                except OSError:
                    pass
        self._files = []

    def communicate(self, timeout: float = 600):
        """Wait for every worker and read its spooled output; returns
        [(returncode, stdout, stderr)].  Re-callable: the spools are
        seeked, not drained."""
        out = []
        for p, (of, ef) in zip(self.procs, self._files):
            try:
                p.wait(timeout=timeout)
            except subprocess.TimeoutExpired:
                p.kill()
                try:
                    p.wait(timeout=self.grace_s)
                except subprocess.TimeoutExpired:
                    pass
            o = e = ""
            for f, slot in ((of, "o"), (ef, "e")):
                try:
                    f.seek(0)
                    text = f.read()
                except (OSError, ValueError):
                    text = ""
                if slot == "o":
                    o = text
                else:
                    e = text
            out.append((p.returncode, o, e))
        return out

    def wait_any_death_or_exit(self, poll_s: float = 0.1,
                               timeout: float = 600):
        """Block until every worker exited cleanly, or any worker died
        (non-zero / signaled) — whichever first.  Returns (ok, ranks_done)
        where ok=False names a failed incarnation."""
        t0 = time.monotonic()
        while True:
            codes = [p.poll() for p in self.procs]
            if any(c not in (None, 0) for c in codes):
                return False, codes
            if all(c == 0 for c in codes):
                return True, codes
            if time.monotonic() - t0 > timeout:
                raise TimeoutError(
                    f"gang did not finish within {timeout}s (exit codes so "
                    f"far: {codes}) — watchdogs should have fired long ago")
            time.sleep(poll_s)


@dataclass
class GangResult:
    """What `run_gang` hands back."""

    ok: bool = False
    restarts: int = 0
    incarnations: int = 0
    # last incarnation's per-rank (returncode, stdout, stderr)
    workers: List[tuple] = field(default_factory=list)
    # one dict per death the supervisor observed across all incarnations
    incidents: List[dict] = field(default_factory=list)
    # telemetry root: one i<k> dir per incarnation holding each rank's
    # metrics.p<rank>.jsonl / BLACKBOX.p<rank>.json / trace.p<rank>.json,
    # plus the supervisor's INCIDENT.i<k>.json files — the input of
    # tools/trace_merge.py and perf_report --postmortem
    telemetry_dir: Optional[str] = None
    # elastic supervision (ISSUE 9): world-size changes across the run
    resizes: int = 0
    # one dict per resize: {"direction", "from_nprocs", "to_nprocs", ...}
    resize_events: List[dict] = field(default_factory=list)
    # gang size of each incarnation, in order (e.g. [2, 1, 2] for an
    # N -> N-1 -> N cycle)
    size_history: List[int] = field(default_factory=list)
    final_nprocs: int = 0
    # every incarnation's per-rank (returncode, stdout, stderr) — the
    # last entry aliases `workers`; elastic accounting (which steps each
    # incarnation actually trained) needs the full history
    history: List[List[tuple]] = field(default_factory=list)


def _latest_commit_step(checkpoint_root: Optional[str]) -> int:
    """Step of the newest COMMITTED checkpoint under `checkpoint_root`
    (-1 when none): the elastic supervisor's progress probe — growth only
    interrupts a shrunk gang once it has durably committed something, so
    a resize can never lose more work than a plain restart would."""
    if not checkpoint_root or not os.path.isdir(checkpoint_root):
        return -1
    best = -1
    for name in os.listdir(checkpoint_root):
        if not name.startswith("ckpt-") or name.endswith(".tmp"):
            continue
        if not os.path.exists(os.path.join(checkpoint_root, name,
                                           "COMMITTED")):
            continue
        try:
            best = max(best, int(name[len("ckpt-"):]))
        except ValueError:
            continue
    return best


def _clear_uncommitted(checkpoint_root: str):
    """Drop half-written checkpoint debris (.tmp dirs, stale shard/commit
    markers from the dead incarnation) so the restarted gang's saves can
    never rendezvous with a ghost's markers."""
    if not checkpoint_root or not os.path.isdir(checkpoint_root):
        return
    for name in os.listdir(checkpoint_root):
        if name.endswith(".tmp"):
            shutil.rmtree(os.path.join(checkpoint_root, name),
                          ignore_errors=True)


def run_gang(argv: Sequence[str], n_procs: int, *,
             devices_per_proc: int = 1,
             extra_env: Optional[Dict[str, str]] = None,
             max_restarts: int = 2,
             checkpoint_root: Optional[str] = None,
             heartbeat_dir: Optional[str] = None,
             telemetry_root: Optional[str] = None,
             timeout: float = 600,
             grace_s: float = 3.0,
             peer_grace_s: float = 15.0,
             elastic: bool = False,
             min_procs: int = 1,
             capacity_fn=None,
             log: bool = True) -> GangResult:
    """Supervise `n_procs` copies of `argv` with gang-restart semantics.

    Each incarnation gets a fresh port block and a fresh heartbeat
    directory (a dead incarnation's beats must not fake liveness into the
    next), plus `PADDLE_RESTART_NUM=<k>` so workers know they are a
    resume.  When any worker dies, every straggler is killed and reaped
    (they are wedged or about to classify-exit anyway), uncommitted
    checkpoint debris is cleared, and the gang relaunches — workers
    restore the last COMMITTED coordinated checkpoint and continue with
    global step numbering.  After `max_restarts` exhausted the last
    incarnation's outputs come back with ok=False.

    Elastic mode (ISSUE 9, `elastic=True`): the relaunch after a death
    follows CAPACITY instead of always reusing `n_procs`.

      * **shrink-on-death**: each unclassified death (SIGKILL, crash —
        NOT the classified 43/44 exits, which are survivors REACTING to a
        peer's death and relaunchable on the same host) is lost capacity;
        the next incarnation runs at `max(min_procs, cur - lost)` workers.
        Workers restore the last COMMITTED checkpoint elastically
        (CheckpointManager N->M re-sharding + cursor repartition) and the
        run CONTINUES at reduced size within the same grace window a
        fixed-size restart would need — never a same-size relaunch into
        the missing capacity.
      * **grow-on-capacity**: while running below `n_procs`, the
        supervisor watches for (a) a NEW committed checkpoint — proof the
        shrunk gang made durable progress, so growing cannot lose more
        work than a restart — and (b) available capacity
        (`capacity_fn()`, default: the target size, i.e. capacity returns
        as soon as the shrunk gang commits).  Both true -> the gang is
        drained gracefully (SIGTERM -> each worker's resilient loop
        flushes a coordinated checkpoint and exits 0) and relaunched at
        `min(n_procs, capacity)`.  Grows spend no restart budget.

    Every resize emits a `kind="dist_event" action="gang_resize"` record
    and bumps `dist.gang_resizes` (gated by `perf_report --check
    --max-gang-resizes`); `GangResult.size_history` / `resize_events` /
    `history` carry the full ledger."""
    result = GangResult()
    base_env = dict(extra_env or {})
    if checkpoint_root:
        base_env["PADDLE_CHECKPOINT_ROOT"] = checkpoint_root
    # once-per-gang fault ledger: ranked FLAGS_fault_spec entries
    # (kill_worker/stall_worker) record their firing here so a restarted
    # incarnation replaying the same step does not replay the fault
    if "PADDLE_FAULT_STATE_DIR" not in base_env:
        base_env["PADDLE_FAULT_STATE_DIR"] = (
            os.path.join(checkpoint_root, "fault-state") if checkpoint_root
            else tempfile.mkdtemp(prefix="pt-fault-state-"))
    os.makedirs(base_env["PADDLE_FAULT_STATE_DIR"], exist_ok=True)
    # ledger hygiene (ISSUE 20): a reused checkpoint_root keeps the
    # previous (now dead) gang's fired-* markers, which would wrongly
    # suppress this run's faults; aborted runs also leak one
    # pt-fault-state-* tempdir each.  Sweep dead-PID state here, at run
    # START only — between incarnations a SIGKILLed child's marker has a
    # dead PID by design and must keep suppressing its entry.
    faults.sweep_stale_ledgers(base_env["PADDLE_FAULT_STATE_DIR"])
    # telemetry plane (ISSUE 8): one rank-shared directory per incarnation;
    # workers (fleet.init -> monitor.init_worker_telemetry) stream their
    # rank-stamped metrics there and dump BLACKBOX.p<rank>.json on death.
    # Incarnation dirs are never cleared — a post-mortem wants the history.
    if telemetry_root is None:
        telemetry_root = (os.path.join(checkpoint_root, "telemetry")
                          if checkpoint_root
                          else tempfile.mkdtemp(prefix="pt-telemetry-"))
    os.makedirs(telemetry_root, exist_ok=True)
    result.telemetry_dir = telemetry_root
    target = int(n_procs)
    min_procs = max(1, int(min_procs))
    cur = target
    restarts_left = int(max_restarts)
    incarnation = 0
    while True:
        result.incarnations = incarnation + 1
        result.size_history.append(cur)
        env = dict(base_env)
        env["PADDLE_RESTART_NUM"] = str(incarnation)
        inc_tel = os.path.join(telemetry_root, f"i{incarnation}")
        env["PADDLE_TELEMETRY_DIR"] = inc_tel
        hb = heartbeat_dir or (checkpoint_root and
                               os.path.join(checkpoint_root, "hb"))
        if hb:
            inc_dir = os.path.join(hb, f"i{incarnation}")
            shutil.rmtree(inc_dir, ignore_errors=True)
            env["PADDLE_HEARTBEAT_DIR"] = inc_dir
        grow_to = None
        with Gang(argv, cur, devices_per_proc=devices_per_proc,
                  extra_env=env, grace_s=grace_s) as gang:
            # progress baseline for the grow decision: only a commit made
            # by THIS (shrunk) incarnation proves it is safe to interrupt
            commit_baseline = _latest_commit_step(checkpoint_root) \
                if elastic else None
            t0 = time.monotonic()
            ok = False
            while True:
                codes = [p.poll() for p in gang.procs]
                if any(c not in (None, 0) for c in codes):
                    ok = False
                    break
                if all(c == 0 for c in codes):
                    ok = True
                    break
                if time.monotonic() - t0 > timeout:
                    ok = False
                    break
                if (elastic and grow_to is None and cur < target
                        and checkpoint_root
                        and _latest_commit_step(checkpoint_root)
                        > commit_baseline):
                    try:
                        cap = int((capacity_fn or (lambda: target))())
                    except Exception:
                        cap = target
                    want = min(target, max(cur, cap))
                    if want > cur:
                        # capacity is back and the shrunk gang has durable
                        # progress: drain it gracefully (SIGTERM -> each
                        # worker flushes a coordinated checkpoint and
                        # exits 0) and relaunch at the grown size
                        grow_to = want
                        for p in gang.procs:
                            if p.poll() is None:
                                p.terminate()
                        if log:
                            print(f"paddle_tpu.launch: capacity returned — "
                                  f"draining the {cur}-worker gang to grow "
                                  f"back to {grow_to}",
                                  file=sys.stderr, flush=True)
                time.sleep(0.05)
            if not ok:
                # survivors are raising classified errors right now (their
                # watchdogs see the dead peer); give them one bounded
                # window to exit 43/44 on their own — the exit codes are
                # the incident record — before the reaper kills the rest
                deadline = time.monotonic() + peer_grace_s
                while (time.monotonic() < deadline
                       and any(p.poll() is None for p in gang.procs)):
                    time.sleep(0.05)
                codes = [p.poll() for p in gang.procs]
            result.workers = gang.communicate(timeout=grace_s)
            result.history.append(result.workers)
        if ok and grow_to is None:
            result.ok = True
            result.final_nprocs = cur
            return result
        if ok and grow_to is not None:
            # clean drain: every worker flushed and exited 0 — relaunch
            # bigger.  Spends no restart budget (nothing failed).
            resize = {"kind": "dist_event", "action": "gang_resize",
                      "direction": "grow", "from_nprocs": cur,
                      "to_nprocs": grow_to, "incarnation": incarnation + 1}
            result.resizes += 1
            result.resize_events.append(resize)
            _MON.counter("dist.gang_resizes").inc()
            _MON.record_step(resize)
            if log:
                print(f"paddle_tpu.launch: gang grown {cur} -> {grow_to} "
                      f"workers (resumed from the drain checkpoint)",
                      file=sys.stderr, flush=True)
            cur = grow_to
            incarnation += 1
            continue
        dead = [(r, c) for r, c in enumerate(codes) if c not in (None, 0)]
        incident = {
            "kind": "dist_event", "action": "worker_death",
            "incarnation": incarnation, "nprocs": cur,
            "dead": [{"rank": r, "returncode": c,
                      "classified": c in _CLASSIFIED_EXITS,
                      "signaled": (c is not None and c < 0)}
                     for r, c in dead],
            # per-worker stderr tails: the only forensic record of an
            # incarnation that is about to be replaced
            "stderr_tails": {r: (result.workers[r][2] or "")[-2000:]
                             for r in range(len(result.workers))},
        }
        # harvest the incarnation's black boxes: every rank that managed a
        # flight-recorder dump (injected kill, classified exit, crash hook)
        # left BLACKBOX.p<rank>.json in its telemetry dir; the supervisor
        # records the paths next to the death so `perf_report --postmortem
        # <telemetry_root>` can merge them across restarts
        try:
            incident["blackboxes"] = sorted(
                os.path.join(inc_tel, f) for f in os.listdir(inc_tel)
                if f.startswith("BLACKBOX.p") and f.endswith(".json"))
        except OSError:
            incident["blackboxes"] = []
        try:
            import json as _json

            with open(os.path.join(telemetry_root,
                                   f"INCIDENT.i{incarnation}.json"),
                      "w") as f:
                _json.dump(incident, f, indent=1)
        except OSError:
            pass
        result.incidents.append(incident)
        _MON.counter("dist.worker_deaths").inc(max(len(dead), 1))
        _MON.record_step(incident)
        if log:
            for r, c in dead:
                err = result.workers[r][2] if r < len(result.workers) else ""
                print(f"paddle_tpu.launch: worker {r} died "
                      f"(returncode {c}) in incarnation {incarnation}:\n"
                      f"{(err or '')[-2000:]}", file=sys.stderr, flush=True)
        if restarts_left == 0:
            break
        _clear_uncommitted(checkpoint_root or "")
        nxt = cur
        if elastic:
            # classified 43/44 exits are survivors REACTING to a peer's
            # death — relaunchable on the same host; only unclassified
            # deaths (SIGKILL, crash, a never-exiting straggler) are
            # capacity that actually left
            lost = sum(1 for _r, c in dead if c not in _CLASSIFIED_EXITS)
            if lost:
                nxt = max(min_procs, cur - lost)
        if nxt != cur:
            resize = {"kind": "dist_event", "action": "gang_resize",
                      "direction": "shrink", "from_nprocs": cur,
                      "to_nprocs": nxt, "incarnation": incarnation + 1,
                      "after_death_of": [r for r, _ in dead]}
            result.resizes += 1
            result.resize_events.append(resize)
            _MON.counter("dist.gang_resizes").inc()
            _MON.record_step(resize)
        restarts_left -= 1
        result.restarts += 1
        _MON.counter("dist.gang_restarts").inc()
        _MON.record_step({"kind": "dist_event", "action": "gang_restart",
                          "incarnation": incarnation + 1,
                          "nprocs": nxt,
                          "after_death_of": [r for r, _ in dead]})
        if log:
            what = (f"continuing at {nxt} workers (elastic shrink)"
                    if nxt != cur else f"relaunching {nxt} workers")
            print(f"paddle_tpu.launch: gang restart "
                  f"{result.restarts}/{max_restarts} — {what} from the "
                  f"last coordinated checkpoint",
                  file=sys.stderr, flush=True)
        cur = nxt
        incarnation += 1
    _MON.record_step({"kind": "dist_event", "action": "gang_failed",
                      "restarts": result.restarts})
    result.final_nprocs = cur
    return result


def run_serving_fleet(models: Dict[str, str], n_replicas: int = 2,
                      root: Optional[str] = None,
                      until=None, poll_s: float = 0.5, **fleet_kw) -> dict:
    """Serving-mode supervision (ISSUE 18): run a `ServingFleet` of
    `n_replicas` replica servers until SIGTERM/SIGINT (or the optional
    `until()` predicate turns true), then DRAIN — each replica gets
    SIGTERM, flips its beat to draining so the router stops dispatching,
    serves out its in-flight requests and exits 0.  An interrupted
    rolling publish found persisted in the fleet root is resumed (or
    converged back) before traffic supervision begins — the serving
    analogue of run_gang's restart-from-checkpoint recovery.

    Returns the final router ledger (`Router.stats()`)."""
    import signal as _signal
    import threading as _threading

    from .serving.fleet import ServingFleet

    stop = _threading.Event()
    prev = {}

    def _handler(sig, _frm):
        stop.set()

    for sig in (_signal.SIGTERM, _signal.SIGINT):
        try:
            prev[sig] = _signal.signal(sig, _handler)
        except ValueError:
            pass  # not the main thread: caller owns signal wiring
    fleet = ServingFleet(models, n_replicas=n_replicas, root=root,
                         **fleet_kw)
    try:
        fleet.resume_roll()
        fleet.wait_healthy(min_replicas=1)
        while not stop.wait(poll_s):
            if until is not None and until():
                break
    finally:
        fleet.stop()
        for sig, h in prev.items():
            try:
                _signal.signal(sig, h)
            except ValueError:
                pass
    return fleet.stats()


def _serve_main(argv: List[str]) -> int:
    """`python -m paddle_tpu.launch --serve` — fleet CLI."""
    ap = argparse.ArgumentParser(
        prog="python -m paddle_tpu.launch --serve",
        description="Run a supervised serving fleet (replica servers + "
                    "health-aware router + rolling publish) until "
                    "SIGTERM, then drain.")
    ap.add_argument("--serve", action="store_true", help=argparse.SUPPRESS)
    ap.add_argument("--model", action="append", default=[],
                    metavar="NAME=DIR", required=True,
                    help="model to serve (repeatable)")
    ap.add_argument("--nproc", type=int, default=2,
                    help="replica processes in the fleet")
    ap.add_argument("--fleet-root", default=None,
                    help="fleet state root (hb/, telemetry/, ACTIVE.json, "
                         "ROLL.json; default: a temp dir)")
    ap.add_argument("--buckets", default="1,4,8")
    ap.add_argument("--max-restarts", type=int, default=3,
                    help="per-replica restart budget")
    ap.add_argument("--hb-interval", type=float, default=0.5)
    ns = ap.parse_args(argv)

    models = {}
    for spec in ns.model:
        name, sep, path = spec.partition("=")
        if not sep or not name or not path:
            ap.error(f"--model wants NAME=DIR, got {spec!r}")
        models[name] = path
    from .serving.batcher import parse_buckets

    ledger = run_serving_fleet(
        models, n_replicas=ns.nproc, root=ns.fleet_root,
        buckets=parse_buckets(ns.buckets),
        max_restarts=ns.max_restarts, hb_interval_s=ns.hb_interval)
    print(f"paddle_tpu.launch --serve: drained; "
          f"{ledger['completed']}/{ledger['requests']} completed, "
          f"{ledger['errors']} classified errors", file=sys.stderr)
    return 0


def main(argv: Optional[Sequence[str]] = None) -> int:
    args = list(sys.argv[1:] if argv is None else argv)
    if "--serve" in args:
        return _serve_main(args)
    ap = argparse.ArgumentParser(
        prog="python -m paddle_tpu.launch",
        description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--nproc", type=int, default=2,
                    help="workers in the gang (PADDLE_TRAINERS_NUM role)")
    ap.add_argument("--devices-per-proc", type=int, default=1)
    ap.add_argument("--max-restarts", type=int, default=2)
    ap.add_argument("--elastic", action="store_true",
                    help="elastic supervision: continue at N-1 workers "
                         "after an unclassified death (instead of a "
                         "same-size relaunch) and grow back toward "
                         "--nproc once the shrunk gang commits a "
                         "checkpoint and capacity returns")
    ap.add_argument("--min-procs", type=int, default=1,
                    help="elastic floor: never shrink below this many "
                         "workers")
    ap.add_argument("--checkpoint-root", default=None,
                    help="coordinated-checkpoint directory (also exported "
                         "as PADDLE_CHECKPOINT_ROOT to workers)")
    ap.add_argument("--timeout", type=float, default=600)
    ap.add_argument("--telemetry-root", default=None,
                    help="gang telemetry root (per-incarnation worker "
                         "metrics/blackbox/trace dirs; default: "
                         "<checkpoint-root>/telemetry or a temp dir) — the "
                         "input of tools/trace_merge.py and perf_report "
                         "--postmortem")
    ap.add_argument("--metrics", default=None,
                    help="JSONL file for the launcher's dist_event records "
                         "+ final counter snapshot (perf_report --check "
                         "--max-gang-restarts input)")
    ap.add_argument("script", help="worker script")
    ap.add_argument("args", nargs=argparse.REMAINDER)
    ns = ap.parse_args(args)

    logger = None
    if ns.metrics:
        from . import monitor as _monitor
        from .monitor import MonitorLogger

        _monitor.enable()
        logger = _monitor.get_monitor().attach_logger(MonitorLogger(ns.metrics))
    res = run_gang([sys.executable, ns.script, *ns.args], ns.nproc,
                   devices_per_proc=ns.devices_per_proc,
                   max_restarts=ns.max_restarts,
                   checkpoint_root=ns.checkpoint_root,
                   telemetry_root=ns.telemetry_root,
                   timeout=ns.timeout,
                   elastic=ns.elastic, min_procs=ns.min_procs)
    for rank, (code, out, err) in enumerate(res.workers):
        sys.stdout.write(out or "")
        if code != 0:
            sys.stderr.write(f"-- worker {rank} (exit {code}) stderr tail --\n"
                             f"{(err or '')[-2000:]}\n")
    if logger is not None:
        logger.write_snapshot()
        from . import monitor as _monitor

        _monitor.get_monitor().detach_logger(logger)
    sizes = (f", sizes {res.size_history} ({res.resizes} resize(s))"
             if res.resizes else "")
    print(f"paddle_tpu.launch: {'ok' if res.ok else 'FAILED'} after "
          f"{res.incarnations} incarnation(s), {res.restarts} restart(s)"
          f"{sizes}; telemetry in {res.telemetry_dir}",
          file=sys.stderr)
    return 0 if res.ok else 1


if __name__ == "__main__":
    sys.exit(main())
