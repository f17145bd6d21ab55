"""Shared multiprocess-on-localhost harness (reference: test_dist_base.py
_run_cluster) used by tests/test_dist_multiprocess.py, tests/
test_dist_chaos.py, and __graft_entry__.dryrun_multiprocess.

The mechanics (port-block allocation with EADDRINUSE retry, the
PADDLE_* env contract, kill-and-reap spawning) now
live in `paddle_tpu.launch` — the harness keeps only the test-facing
conveniences: `worker_gang` (a context manager that can never leak live
subprocesses, even when a later spawn or the test body raises) and the
LOSSES-line parsing the parity tests key on."""
from __future__ import annotations

import contextlib
import json
import os

from paddle_tpu.launch import (Gang, allocate_port_block,  # noqa: F401
                               run_gang, worker_env as _launch_worker_env)

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
WORKER = os.path.join(HERE, "dist_worker.py")
RESILIENT_WORKER = os.path.join(HERE, "dist_worker_resilient.py")


def free_port() -> int:
    """One free port (TOCTOU-shrunk: verified by bind, like the block
    allocator).  Kept for callers that need a single ad-hoc port."""
    return allocate_port_block(1)


def worker_env(extra=None, devices_per_proc=2, rank=0, endpoints=None):
    """Back-compat shim over paddle_tpu.launch.worker_env for callers that
    build their own env (e.g. the RUN_LOCAL single-process reference)."""
    endpoints = endpoints or [f"127.0.0.1:{free_port()}"]
    env = _launch_worker_env(rank, endpoints, devices_per_proc, extra or {})
    if extra and "RUN_LOCAL" in extra:
        # the local reference run is not part of any gang: drop the contract
        for k in ("PADDLE_TRAINER_ID", "PADDLE_TRAINER_ENDPOINTS",
                  "PADDLE_CURRENT_ENDPOINT"):
            env.pop(k, None)
    return env


@contextlib.contextmanager
def worker_gang(n_procs: int, devices_per_proc: int = 2, extra_env=None,
                worker: str = WORKER):
    """Spawn n_procs workers wired through one coordinator; ALWAYS kills
    and reaps them on exit (bounded join, SIGTERM then SIGKILL) — the old
    `spawn_workers` list leaked live subprocesses whenever a later spawn
    or the test body failed before `collect`'s finally ran.  Yields the
    Gang; pass it to `collect` for the LOSSES-parsing result list."""
    import sys

    with Gang([sys.executable, worker], n_procs,
              devices_per_proc=devices_per_proc, extra_env=extra_env) as g:
        yield g


def parse_losses(out: str, err: str, tag: str) -> dict:
    for line in out.splitlines():
        if line.startswith("LOSSES "):
            return json.loads(line[len("LOSSES "):])
    raise AssertionError(
        f"{tag}: worker produced no LOSSES line.\nstdout:\n{out}\nstderr:\n{err[-3000:]}")


def collect(gang_or_procs, timeout=600):
    """Wait out every worker of a `worker_gang` Gang (or a legacy Popen
    list) and parse its LOSSES line; on any failure the stragglers are
    killed so no orphan sits blocked in jax.distributed.initialize."""
    if isinstance(gang_or_procs, Gang):
        results = []
        for tid, (code, out, err) in enumerate(
                gang_or_procs.communicate(timeout=timeout)):
            if code != 0:
                raise RuntimeError(f"worker {tid} failed:\n{(err or '')[-4000:]}")
            results.append(parse_losses(out, err or "", f"worker{tid}"))
        return results
    procs = gang_or_procs
    results = []
    try:
        for tid, p in enumerate(procs):
            out, err = p.communicate(timeout=timeout)
            if p.returncode != 0:
                raise RuntimeError(f"worker {tid} failed:\n{err[-4000:]}")
            results.append(parse_losses(out, err, f"worker{tid}"))
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
                p.wait()
    return results
