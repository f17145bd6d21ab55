"""Failure taxonomy for fault-tolerant training.

Long accelerator runs do not die from clean exits: they die from bad
records, numeric blow-ups, transient XLA/runtime failures, and pod
preemptions.  The reference runtime surfaced all of these as whatever
exception the failing layer happened to raise; nothing downstream could
tell "skip this batch" from "the program is miscompiled".  This module is
the shared vocabulary the resilience layer (paddle_tpu/resilience.py)
routes on:

    DataError             a batch the input pipeline could not produce or
                          parse — skippable within a budget
    NumericError          the FLAGS_check_nan_inf guard tripped (NaN/Inf
                          in a fetched value) — skippable / rollbackable
    TransientDeviceError  runtime failure the next attempt may not see
                          (XLA RESOURCE_EXHAUSTED / UNAVAILABLE / ...) —
                          retryable with backoff
    PreemptionError       the pod is going away — flush a checkpoint and
                          exit resumable
    FatalError            everything else — never retried
    LockTimeoutError      a named-lock acquisition blew FLAGS_lock_timeout_s
                          (core/locks.py) — names BOTH the wanted lock and
                          every lock the thread holds, with their declared
                          ranks, instead of hanging the worker forever —
                          never retried (the lock order is wrong, not the
                          run)
    ResourceError         the static resource planner predicts the program
                          cannot fit in device HBM (phase=build, raised
                          before any XLA compile/allocate, naming the ops
                          at the predicted peak) — never retried
    CheckpointError       a checkpoint that must not be loaded as asked
                          (world-size mismatch without elastic opt-in,
                          inconsistent rank cursors) — never retried
    StorageError          the storage layer itself failed an I/O operation
                          (phase="storage", routed through the io.py choke
                          point): TRANSIENT errnos (ENOSPC/EIO/EAGAIN/
                          ETIMEDOUT — a filling disk, a flaky NFS mount, a
                          throttled object store) are retried with seeded
                          backoff and, for checkpoints, degrade to
                          lag-bounded unprotected training instead of
                          killing the worker; TERMINAL errnos (EROFS/
                          EACCES) skip straight to the fallback dir /
                          degraded mode — no retry changes a read-only
                          mount
    IntegrityError        wrong-but-FINITE state (paddle_tpu/integrity.py):
                          a live cross-rank digest divergence named a
                          corrupt rank, or an at-rest sha256 in a
                          checkpoint/model manifest failed verification.
                          Recoverable when a clean COMMITTED checkpoint
                          predates the corruption window — the resilient
                          loop rolls back (restore + exact RNG/cursor
                          rewind) instead of training forward on corrupt
                          state; otherwise terminal
    ServingError          the serving runtime (paddle_tpu/serving/)
                          refused or failed a request/control action on
                          purpose: admission control shed it, its deadline
                          expired, a published snapshot failed
                          verification, or a model load would blow the
                          HBM budget.  `reason` carries the stable
                          machine-readable code clients route on

and, for the multi-worker tier (paddle_tpu/dist_resilience.py):

    DistributedError      base of the gang-level failures below — one
                          worker cannot fix these alone; the gang-restart
                          driver (paddle_tpu/launch.py) owns recovery
    PeerFailureError      a peer worker stopped heartbeating (crashed,
                          SIGKILLed, wedged) while this worker was inside
                          or about to enter a collective
    CollectiveTimeoutError a collective/barrier blew its armed deadline
                          with every peer still heartbeating (deadlocked
                          collective, pathological straggler)

Every class subclasses RuntimeError so legacy call sites catching
RuntimeError (the NaN guard's historical type) keep working.

`classify(exc)` maps an arbitrary exception onto this taxonomy, reading
context breadcrumbs (`attach_context`) that the executor's sticky
resolution errors, the pipeline's drain path, and the loader's producer
thread leave on exceptions they forward.
"""
from __future__ import annotations

__all__ = ["TrainingError", "DataError", "NumericError",
           "TransientDeviceError", "PreemptionError", "FatalError",
           "CheckpointError", "ServingError", "ResourceError",
           "LockTimeoutError", "IntegrityError", "StorageError",
           "DistributedError", "PeerFailureError", "CollectiveTimeoutError",
           "ParamServerError",
           "classify", "attach_context", "get_context",
           "TRANSIENT_STORAGE_ERRNOS", "TERMINAL_STORAGE_ERRNOS",
           "TRANSIENT_PS_ERRNOS"]

import errno as _errno
from typing import Optional

# The storage-failure split (ISSUE 15).  Transient: the next attempt may
# not see it (space is being freed, the mount is flapping, the store is
# throttling).  Terminal: retrying cannot help — the filesystem is
# read-only or the credentials are wrong; only a different destination
# (FLAGS_ckpt_fallback_dir) or an operator can.
TRANSIENT_STORAGE_ERRNOS = (_errno.ENOSPC, _errno.EIO, _errno.EAGAIN,
                            _errno.ETIMEDOUT)
TERMINAL_STORAGE_ERRNOS = (_errno.EROFS, _errno.EACCES)

# The pserver-failure split (ISSUE 19).  Transient: the socket died
# because the pserver process did (its supervisor is restarting it) or
# the network flapped — reconnect + retry is the answer.  A socket
# TimeoutError maps transient too (KVClient checks the type, not just
# the errno).  Anything else on the wire — protocol violations above
# all — is terminal.
TRANSIENT_PS_ERRNOS = (_errno.ECONNREFUSED, _errno.ECONNRESET,
                       _errno.ECONNABORTED, _errno.EPIPE,
                       _errno.ETIMEDOUT, _errno.EAGAIN,
                       _errno.EHOSTUNREACH)


class TrainingError(RuntimeError):
    """Base of the failure taxonomy.  Carries structured context — which
    train step / raw batch / layer the failure belongs to — so recovery
    can rewind to exactly the right point."""

    def __init__(self, message: str, *, step: Optional[int] = None,
                 batch_index: Optional[int] = None,
                 phase: Optional[str] = None):
        super().__init__(message)
        self.step = step
        self.batch_index = batch_index
        self.phase = phase

    def __str__(self):
        base = super().__str__()
        ctx = []
        if self.step is not None:
            ctx.append(f"step={self.step}")
        if self.batch_index is not None:
            ctx.append(f"batch={self.batch_index}")
        if self.phase:
            ctx.append(f"phase={self.phase}")
        return f"{base} [{', '.join(ctx)}]" if ctx else base


class DataError(TrainingError):
    """The input pipeline failed to produce a batch (parse error, corrupt
    record, injected bad batch).  Dropping the batch is sound; the
    resilient loop does so within `RetryPolicy.max_bad_batches`."""


class NumericError(TrainingError):
    """NaN/Inf reached a fetched value (the FLAGS_check_nan_inf guard).
    Since the step that produced it already wrote its (poisoned) update
    into the scope, recovery needs state restore, not just retry — see
    `resilient_train_loop`'s `nan_mode`."""


class TransientDeviceError(TrainingError):
    """Device/runtime failure a later attempt may not reproduce: XLA
    RESOURCE_EXHAUSTED (HBM pressure), UNAVAILABLE / ABORTED (a runtime
    hiccup), DEADLINE_EXCEEDED.  `resource_exhausted` marks the
    OOM flavor so the resilient loop can also shed in-flight depth."""

    def __init__(self, message: str, *, code: Optional[str] = None,
                 resource_exhausted: bool = False, **kw):
        super().__init__(message, **kw)
        self.code = code
        self.resource_exhausted = bool(resource_exhausted
                                       or code == "RESOURCE_EXHAUSTED")


class PreemptionError(TrainingError):
    """The process received its preemption notice (SIGTERM on TPU pods).
    Not an error to retry: flush a checkpoint, report where to resume."""


class FatalError(TrainingError):
    """Anything `classify` cannot place in a recoverable class: program
    bugs, INVALID_ARGUMENT compiles, user-code exceptions.  The resilient
    loop re-raises these untouched."""


class LockTimeoutError(FatalError):
    """A `locks.named_lock` acquisition did not complete within
    `FLAGS_lock_timeout_s` (core/locks.py).  A correctly ordered lock
    graph cannot deadlock, so a blown lock deadline means either a
    genuine deadlock (an acquisition path the concurrency lint did not
    see inverted the declared ranks) or a critical section holding a hot
    lock across blocking work — both program bugs, never retried.  The
    message and fields name BOTH sides: `wanted`/`wanted_rank` is the
    lock that timed out, `held` the [(name, rank), ...] this thread
    already holds — exactly what a deadlock report needs, captured while
    there is still a Python stack to read instead of a wedged worker to
    SIGKILL."""

    def __init__(self, message: str, *, wanted: Optional[str] = None,
                 wanted_rank: Optional[int] = None, held=None,
                 timeout_s: Optional[float] = None, **kw):
        kw.setdefault("phase", "locking")
        super().__init__(message, **kw)
        self.wanted = wanted
        self.wanted_rank = wanted_rank
        self.held = list(held or [])
        self.timeout_s = timeout_s


class ResourceError(FatalError):
    """The static resource planner (core/resource_plan.py) predicts the
    program cannot run within the device's HBM: the liveness-based
    peak-memory estimate exceeds the known limit.  Raised at compile-cache
    miss time, BEFORE any XLA compile or device allocation — the point is
    to name the ops and buffers at the predicted peak (`watermark_ops`)
    while there is still a Python stack to read, instead of an opaque
    allocator RESOURCE_EXHAUSTED mid-compile.  phase="build"; never
    retried (the program itself is too big, not the run — shrink the
    batch, enable remat/BuildStrategy.memory_optimize, build a block that
    runs several times as a `layers.Repeat(recompute=True)`, which keeps
    one pass's activations and not every pass's, or shard).

    Distinct from `TransientDeviceError(resource_exhausted=True)`: that is
    the RUNTIME allocator actually failing (fragmentation, co-residency),
    which a retry at lower in-flight depth may survive; this is a static
    prediction that no retry changes."""

    def __init__(self, message: str, *, needed_bytes: Optional[int] = None,
                 limit_bytes: Optional[int] = None, watermark_ops=None, **kw):
        kw.setdefault("phase", "build")
        super().__init__(message, **kw)
        self.needed_bytes = needed_bytes
        self.limit_bytes = limit_bytes
        self.watermark_ops = list(watermark_ops or [])


class CheckpointError(TrainingError):
    """A checkpoint cannot be safely loaded as asked: the saved world size
    does not match the restoring gang (and the caller did not opt into
    elastic re-sharding), rank cursors are mutually inconsistent, or the
    on-disk layout contradicts its own manifest.  Never retried — loading
    anyway would misposition shards or double-train data, which is worse
    than dying loudly.  `saved_world` / `current_world` carry the two
    sizes when a world-size mismatch is the cause."""

    def __init__(self, message: str, *, saved_world: Optional[int] = None,
                 current_world: Optional[int] = None, **kw):
        kw.setdefault("phase", "checkpoint")
        super().__init__(message, **kw)
        self.saved_world = saved_world
        self.current_world = current_world


class StorageError(TrainingError):
    """The storage layer failed an I/O operation (phase="storage" — every
    checkpoint/manifest/sidecar/model-store byte crosses the `paddle_tpu.
    io` choke point, which stamps the breadcrumb).  The `transient` bit is
    the routing decision the whole resilience tier keys on:

      * transient (ENOSPC, EIO, EAGAIN, ETIMEDOUT): retried with seeded
        backoff (`RetryPolicy.max_storage_retries`); a checkpoint save
        that exhausts its retries enters DEGRADED MODE — training
        continues, `resilience.ckpt_lag_steps` rises, and a bounded lag
        (`FLAGS_max_ckpt_lag_steps`) converts to this error re-raised
        terminal, so unprotected training cannot run forever;
      * terminal (EROFS, EACCES): retries are skipped — the fallback dir
        (`FLAGS_ckpt_fallback_dir`) is tried, then degraded mode.

    `op` is "read"/"write", `path` the failing file, `errno` the OS code
    (mirrors OSError).  A transient publish-source failure retries WITHOUT
    quarantining the snapshot (serving/publisher.py) — flaky I/O is not
    evidence of rot."""

    def __init__(self, message: str, *, op: Optional[str] = None,
                 path: Optional[str] = None, errno: Optional[int] = None,
                 transient: Optional[bool] = None, **kw):
        kw.setdefault("phase", "storage")
        super().__init__(message, **kw)
        self.op = op
        self.path = path
        self.errno = errno
        if transient is None:
            transient = errno in TRANSIENT_STORAGE_ERRNOS
        self.transient = bool(transient)

    def __str__(self):
        base = super().__str__()
        ctx = []
        if self.op:
            ctx.append(f"op={self.op}")
        if self.errno is not None:
            ctx.append(f"errno={_errno.errorcode.get(self.errno, self.errno)}")
        ctx.append("transient" if self.transient else "terminal")
        if self.path:
            ctx.append(f"path={self.path}")
        return f"{base} [{', '.join(ctx)}]"


class ParamServerError(TrainingError):
    """The host sparse-table tier (paddle_tpu/param_server.py) failed an
    RPC — the parameter-server mirror of `StorageError`, with the same
    transient/terminal split the resilience tier keys on:

      * transient (connection refused/reset, broken pipe, socket
        timeout, host unreachable): the pserver died or is being
        crash-restarted by its supervisor; the KVClient retries with
        reconnect + seeded backoff (`FLAGS_ps_retries`) and — because
        every push carries a per-client sequence number the server
        dedups — a retried sparse push applies EXACTLY once.  When the
        retry budget is exhausted, training enters bounded degraded
        mode (hot-shard-only steps, `sparse.host_lag_steps` gauge)
        instead of wedging;
      * terminal (protocol violation: bad magic, frame past
        `FLAGS_ps_max_frame_mb`, exhausted degraded-mode budget past
        `FLAGS_max_host_lag_steps`): retrying cannot help — the wire is
        corrupt or the contract is broken.

    `op` is the protocol op ("pull"/"push"/"create"/"fetch"/...),
    `endpoint` the pserver address, `errno` the OS code when an OSError
    is behind it."""

    def __init__(self, message: str, *, op: Optional[str] = None,
                 endpoint: Optional[str] = None,
                 errno: Optional[int] = None,
                 transient: Optional[bool] = None, **kw):
        kw.setdefault("phase", "pserver")
        super().__init__(message, **kw)
        self.op = op
        self.endpoint = endpoint
        self.errno = errno
        if transient is None:
            transient = errno in TRANSIENT_PS_ERRNOS
        self.transient = bool(transient)

    def __str__(self):
        base = super().__str__()
        ctx = []
        if self.op:
            ctx.append(f"op={self.op}")
        if self.errno is not None:
            ctx.append(f"errno={_errno.errorcode.get(self.errno, self.errno)}")
        ctx.append("transient" if self.transient else "terminal")
        if self.endpoint:
            ctx.append(f"endpoint={self.endpoint}")
        return f"{base} [{', '.join(ctx)}]"


class IntegrityError(TrainingError):
    """Silent data corruption made loud (paddle_tpu/integrity.py): state
    that is wrong but FINITE, which no NaN guard, CRC, or structure check
    can see.  Two sources:

      * a LIVE digest divergence — replicated dp state stopped agreeing
        bit-exactly across ranks.  `corrupt_ranks` names the voted
        offender(s) (`attributed=False` when the vote tied and the value
        plausibility tiebreak could not break it — e.g. a low-mantissa
        flip on a 2-rank gang), and `safe_step` is the newest step the
        digests PROVE clean: the resilient loop's rollback must restore a
        checkpoint at or before it (a later checkpoint may have committed
        the corruption);
      * an AT-REST digest mismatch — a file named by a checkpoint or
        inference-model manifest no longer hashes to its recorded sha256
        (`file` / `expected` / `actual`).  Restore walks back past it,
        publish quarantines it.

    Recoverable via rollback when a clean committed checkpoint exists;
    never "retried" in place — the in-memory (or on-disk) state itself is
    poison."""

    def __init__(self, message: str, *, corrupt_ranks=None,
                 attributed: bool = True, safe_step: Optional[int] = None,
                 file: Optional[str] = None, expected: Optional[str] = None,
                 actual: Optional[str] = None, **kw):
        kw.setdefault("phase", "integrity")
        super().__init__(message, **kw)
        self.corrupt_ranks = list(corrupt_ranks or [])
        self.attributed = bool(attributed)
        self.safe_step = safe_step
        self.file = file
        self.expected = expected
        self.actual = actual

    def __str__(self):
        base = super().__str__()
        ctx = []
        if self.corrupt_ranks:
            ctx.append(f"corrupt_ranks={self.corrupt_ranks}"
                       + ("" if self.attributed else " (unattributed)"))
        if self.safe_step is not None:
            ctx.append(f"safe_step={self.safe_step}")
        if self.file:
            ctx.append(f"file={self.file}")
        return f"{base} [{', '.join(ctx)}]" if ctx else base


class ServingError(TrainingError):
    """The serving runtime (paddle_tpu/serving/) refused or failed a
    request or control action BY DESIGN — these are the classified,
    expected failures that keep an overloaded or mid-reload server
    degrading gracefully instead of wedging:

        reason="overload"          admission control shed the request (the
                                   bounded queue was full; serving it would
                                   grow latency without bound)
        reason="timeout"           the request's deadline expired before a
                                   batch picked it up
        reason="oversize"          the request carries more rows than the
                                   largest compiled bucket; split it
        reason="bad_request"       the request itself is malformed (empty,
                                   scalar or mismatched batch dims, feed
                                   names/shapes off the model's contract) —
                                   rejected at admission so it can never
                                   poison the batch it would join
        reason="publish_rejected"  a staged snapshot failed verification
                                   (torn/corrupt files, program verifier,
                                   NaN weights, golden-smoke failure) and
                                   was quarantined — the old model keeps
                                   serving
        reason="publish_io"        transient STORE I/O (EIO/timeout while
                                   hashing or staging) exhausted the
                                   publish retry budget — the snapshot is
                                   NOT quarantined (flaky I/O is not
                                   evidence of rot); retry when the store
                                   settles
        reason="hbm_budget"        loading the model would exceed the HBM
                                   budget and eviction could not free
                                   enough
        reason="model_missing"     no model under that name (never loaded,
                                   unloaded, or evicted)
        reason="shutdown"          the server is draining/stopped
        reason="replica_down"      fleet routing (serving/router.py): the
                                   replica carrying this in-flight request
                                   died mid-request, or — for NEW traffic —
                                   no healthy replica remains to dispatch
                                   to.  New traffic only sees this when the
                                   whole fleet is down; a single replica
                                   death costs exactly its own in-flight
                                   requests and redistributes the rest
                                   within one heartbeat miss window
        reason="roll_halted"       a fleet rolling publish halted (a verify
                                   rung failed on some replica, or a
                                   replica lost mid-roll could not be
                                   recovered) and the fleet was converged
                                   back onto the last good version

    Never retried blindly: "overload"/"timeout" are backpressure the
    CLIENT routes on (retry elsewhere, degrade, drop); the rest are
    operator-facing.  `model` names the model involved, when any."""

    def __init__(self, message: str, *, reason: Optional[str] = None,
                 model: Optional[str] = None,
                 trace_id: Optional[str] = None, **kw):
        kw.setdefault("phase", "serving")
        super().__init__(message, **kw)
        self.reason = reason
        self.model = model
        # the request-flight trace id (serving/tracing.py) when the monitor
        # was on: the error a CLIENT caught names the exact trace
        # `serve_trace --request <id>` renders.  None with the monitor off.
        self.trace_id = trace_id

    def __str__(self):
        base = super().__str__()
        ctx = []
        if self.reason:
            ctx.append(f"reason={self.reason}")
        if self.model:
            ctx.append(f"model={self.model}")
        if self.trace_id:
            ctx.append(f"trace={self.trace_id}")
        return f"{base} [{', '.join(ctx)}]" if ctx else base


class DistributedError(TrainingError):
    """Base of the gang-level failures.  A single worker cannot recover
    from these (every peer is wedged in the same collective); the point of
    raising instead of hanging is to die LOUDLY and classified, so the
    gang-restart driver (paddle_tpu/launch.py) can kill the stragglers and
    relaunch from the last coordinated checkpoint.  Carries the local rank
    and, where known, the set of implicated peers."""

    def __init__(self, message: str, *, rank: Optional[int] = None,
                 peers=None, collective: Optional[str] = None, **kw):
        super().__init__(message, **kw)
        self.rank = rank
        self.peers = list(peers) if peers is not None else []
        self.collective = collective

    def __str__(self):
        base = super().__str__()
        ctx = []
        if self.rank is not None:
            ctx.append(f"rank={self.rank}")
        if self.peers:
            ctx.append(f"peers={self.peers}")
        if self.collective:
            ctx.append(f"collective={self.collective}")
        return f"{base} [{', '.join(ctx)}]" if ctx else base


class PeerFailureError(DistributedError):
    """A peer worker stopped heartbeating — crashed, OOM-killed, or wedged
    past the liveness deadline.  The next (or current) collective with that
    peer can never complete; the watchdog raises this instead of letting
    the process hang inside it.  `peers` lists the dead ranks."""


class CollectiveTimeoutError(DistributedError):
    """A collective/barrier exceeded its armed deadline while every peer
    still heartbeats: a deadlocked collective, divergent program order, or
    a straggler past the watchdog budget.  Thread stacks were dumped at
    raise time (`dist_resilience.dump_stacks`)."""


# XLA status codes whose failures are worth retrying.  INVALID_ARGUMENT /
# INTERNAL / UNIMPLEMENTED are deliberately absent: those reproduce.
_TRANSIENT_CODES = ("RESOURCE_EXHAUSTED", "UNAVAILABLE", "ABORTED",
                    "DEADLINE_EXCEEDED", "CANCELLED")


def attach_context(exc: BaseException, *, step: Optional[int] = None,
                   batch_index: Optional[int] = None,
                   phase: Optional[str] = None) -> BaseException:
    """Leave step/batch/phase breadcrumbs on an exception without changing
    its type (sticky errors must keep raising as themselves — pinned by
    the loader's propagate-as-itself contract).  First writer wins per
    key, so the layer closest to the failure names it."""
    try:
        ctx = exc.__dict__.setdefault("_pt_ctx", {})
    except AttributeError:  # exceptions with __slots__ / C extensions
        return exc
    for k, v in (("step", step), ("batch_index", batch_index),
                 ("phase", phase)):
        if v is not None and ctx.get(k) is None:
            ctx[k] = v
    if isinstance(exc, TrainingError):
        for k in ("step", "batch_index", "phase"):
            if getattr(exc, k, None) is None and ctx.get(k) is not None:
                setattr(exc, k, ctx[k])
    return exc


def get_context(exc: BaseException) -> dict:
    """The breadcrumbs `attach_context` left (empty dict if none)."""
    ctx = dict(getattr(exc, "_pt_ctx", None) or {})
    if isinstance(exc, TrainingError):
        for k in ("step", "batch_index", "phase"):
            if ctx.get(k) is None and getattr(exc, k, None) is not None:
                ctx[k] = getattr(exc, k)
    return ctx


def _eno_of(exc: BaseException) -> Optional[int]:
    return getattr(exc, "errno", None) if isinstance(exc, OSError) else None


def classify(exc: BaseException, wrap_unknown: bool = False) -> BaseException:
    """Map an exception onto the taxonomy.

    Returns the exception itself when it is already a `TrainingError` or
    when no specific class applies (so sticky errors keep their original
    type unless a mapping genuinely adds information).  With
    `wrap_unknown=True` unmapped exceptions come back wrapped in
    `FatalError` instead.  Mapped exceptions carry the original as
    `__cause__` and inherit any attached step/batch context."""
    if isinstance(exc, TrainingError):
        return exc
    ctx = get_context(exc)
    kw = {"step": ctx.get("step"), "batch_index": ctx.get("batch_index"),
          "phase": ctx.get("phase")}

    def _wrap(cls, **extra):
        e = cls(f"{type(exc).__name__}: {exc}", **kw, **extra)
        e.__cause__ = exc
        return e

    # KeyboardInterrupt / SystemExit are control flow, never classified.
    if not isinstance(exc, Exception):
        return exc
    msg = str(exc)
    # XLA runtime failures (jaxlib XlaRuntimeError subclasses RuntimeError
    # and spells its status code into the message) plus anything else that
    # carries a status-code-shaped message.  Checked BEFORE the loader
    # breadcrumb: an XLA RESOURCE_EXHAUSTED raised while the producer
    # thread stages a batch is an HBM problem, not skippable data.
    if isinstance(exc, (RuntimeError, OSError)):
        for code in _TRANSIENT_CODES:
            if code in msg:
                kw.pop("phase", None)
                return _wrap(TransientDeviceError, code=code, phase="device")
    # Parameter-server failures (ISSUE 19): an exception that crossed the
    # KVClient seam carries phase="pserver" and maps onto the pserver
    # transient/terminal split.  Checked BEFORE storage: a socket
    # ETIMEDOUT shares an errno with the transient STORAGE set, but the
    # verdict (retry the RPC / enter degraded sparse mode) belongs to the
    # pserver tier, not the checkpoint store.
    if ctx.get("phase") == "pserver" and isinstance(
            exc, (OSError, TimeoutError)):
        kw.pop("phase", None)
        transient = (isinstance(exc, TimeoutError)
                     or _eno_of(exc) in TRANSIENT_PS_ERRNOS
                     or isinstance(exc, ConnectionError))
        return _wrap(ParamServerError, errno=_eno_of(exc),
                     transient=transient, phase="pserver")
    # Storage-layer failures (ISSUE 15): an OSError that crossed the io.py
    # choke point carries phase="storage" and maps by errno onto the
    # transient/terminal split.  Checked BEFORE the loader breadcrumb so a
    # checkpoint read failing inside a producer thread stays a storage
    # failure; a bare OSError with a storage errno and NO phase breadcrumb
    # maps too (below, AFTER the loader check — an EIO while producing a
    # data batch is the data layer's problem, handled by its own budget).
    _eno = getattr(exc, "errno", None) if isinstance(exc, OSError) else None
    _storage_errno = _eno in TRANSIENT_STORAGE_ERRNOS \
        or _eno in TERMINAL_STORAGE_ERRNOS
    if _storage_errno and ctx.get("phase") == "storage":
        kw.pop("phase", None)
        return _wrap(StorageError, errno=_eno,
                     path=getattr(exc, "filename", None), phase="storage")
    # Producer-thread breadcrumb: the loader marks exceptions raised while
    # producing a batch, whatever their type (user generator bugs raise as
    # themselves but recovery treats them as data failures).  "feed" is the
    # FeedSpec validation boundary (reader.py): a dtype/shape-mismatched or
    # non-finite feed is a data failure caught before lowering.
    if ctx.get("phase") in ("loader", "feed"):
        return _wrap(DataError)
    if _storage_errno and ctx.get("phase") is None:
        kw.pop("phase", None)
        return _wrap(StorageError, errno=_eno,
                     path=getattr(exc, "filename", None), phase="storage")
    # The NaN/Inf guard's historical RuntimeError message.
    if isinstance(exc, (RuntimeError, FloatingPointError)) and "NaN/Inf" in msg:
        return _wrap(NumericError)
    if wrap_unknown:
        return _wrap(FatalError)
    return exc
