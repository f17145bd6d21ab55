"""Typed flag registry with FLAGS_* env passthrough.

Reference: gflags end-to-end — C++ DEFINE_* at point of use, Python collects
a whitelist and seeds it from the environment
(`python/paddle/fluid/__init__.py:154-216`), so the public config surface is
`FLAGS_xxx` env vars plus `fluid.set_flags`/`fluid.get_flags`.

TPU build: one registry.  Flags either drive real behavior here (NaN
checking, HLO dumps, compile-cache size) or are accepted no-ops kept for
source compatibility (allocator/cudnn knobs that PJRT/XLA own now — each
says so in its help string)."""
from __future__ import annotations

import os
from typing import Any, Dict, List

# the public surface (tools/print_signatures.py walks it): without this the
# walk picks up `typing.Any`, whose signature differs between Pythons
__all__ = [
    "CHECKOUT_CACHE_DIR", "DEFINE_bool", "DEFINE_float", "DEFINE_int",
    "DEFINE_string", "all_flags", "apply_compile_cache", "apply_xla_dump",
    "flag", "get_flags", "init_from_env", "set_flags",
]

_REGISTRY: Dict[str, dict] = {}


def _define(name: str, typ, default, help: str):
    _REGISTRY[name] = {"type": typ, "value": default, "default": default, "help": help}


def DEFINE_bool(name, default, help=""):
    _define(name, bool, default, help)


def DEFINE_int(name, default, help=""):
    _define(name, int, default, help)


def DEFINE_float(name, default, help=""):
    _define(name, float, default, help)


def DEFINE_string(name, default, help=""):
    _define(name, str, default, help)


def _coerce(typ, v):
    if typ is bool:
        if isinstance(v, str):
            return v.lower() in ("1", "true", "yes", "on")
        return bool(v)
    return typ(v)


def set_flags(flags: Dict[str, Any]):
    """fluid.set_flags({"FLAGS_check_nan_inf": True})"""
    for k, v in flags.items():
        if k not in _REGISTRY:
            raise KeyError(f"unknown flag {k!r}; known: {sorted(_REGISTRY)}")
        ent = _REGISTRY[k]
        ent["value"] = _coerce(ent["type"], v)
        if k == "FLAGS_xla_dump_to":
            apply_xla_dump()
        elif k == "FLAGS_compile_cache_dir":
            apply_compile_cache()
        elif k in ("FLAGS_lock_telemetry", "FLAGS_lock_timeout_s"):
            from .core import locks as _locks

            _locks.refresh_from_flags()


def get_flags(names) -> Dict[str, Any]:
    if isinstance(names, str):
        names = [names]
    return {n: _REGISTRY[n]["value"] for n in names}


def flag(name: str):
    return _REGISTRY[name]["value"]


def all_flags() -> List[str]:
    return sorted(_REGISTRY)


def init_from_env():
    """Seed every registered flag from its FLAGS_* env var (the reference's
    `core.init_gflags(["--tryfromenv=..."])` role)."""
    for name, ent in _REGISTRY.items():
        if name in os.environ:
            ent["value"] = _coerce(ent["type"], os.environ[name])


# --- the registry -----------------------------------------------------------

DEFINE_bool("FLAGS_check_nan_inf", False,
            "after each run, scan fetched values for NaN/Inf and raise "
            "(reference operator.cc:950 CheckTensorNANOrInf; here a per-fetch "
            "host guard)")
DEFINE_string("FLAGS_xla_dump_to", "",
              "directory for XLA HLO dumps of every compiled program "
              "(reference graphviz/debug dumps); set before first compile")
DEFINE_int("FLAGS_executor_cache_capacity", 128,
           "LRU capacity of the executor's compiled-program cache "
           "(reference use_program_cache)")
DEFINE_string("FLAGS_compile_cache_dir", "",
              "directory for XLA's persistent compilation cache: cold-start "
              "executor.compile cost (seconds per program signature, re-paid "
              "every process) is paid once per machine — the second process "
              "running the same program loads the compiled executable from "
              "disk.  Set before the first compile (env var or set_flags); "
              "ignored where JAX_COMPILATION_CACHE_DIR is set "
              "(apply_compile_cache).  "
              "Single-process only: init_distributed force-disables it for "
              "multi-process runs (cached cross-process executables corrupt "
              "the heap on the current backend)")
DEFINE_string("FLAGS_fault_spec", "",
              "deterministic fault-injection schedule for chaos testing the "
              "resilience layer (paddle_tpu/faults.py), e.g. "
              "'bad_batch@2;nan@5;device@7:RESOURCE_EXHAUSTED;preempt@11'. "
              "Each resilient_train_loop call builds one injector from the "
              "spec; every entry fires exactly once per injector (so once "
              "per call).  Empty (default) injects nothing")
DEFINE_int("FLAGS_data_corrupt_budget", 0,
           "number of corrupt/truncated RecordIO chunks one run may skip "
           "before the data layer aborts with a classified DataError "
           "(paddle_tpu/recordio.py; `data.corrupt_chunks` counts spends). "
           "0 (default) keeps strict behavior: the first corrupt chunk "
           "raises IOError immediately instead of being skipped")
DEFINE_string("FLAGS_verify_program", "structural",
              "static-analysis level applied to programs BEFORE lowering "
              "(paddle_tpu/core/analysis.py): 'off' trusts the builder "
              "(also disables append_op-time shape/dtype inference — the "
              "escape hatch if an infer rule wrongly rejects a program), "
              "'structural' (default) runs the program verifier "
              "(def-before-use, dangling vars, unregistered ops, orphan "
              "sub-blocks, duplicate parameter writes, feed/fetch targets) "
              "on every executor compile-cache miss and after every "
              "registered pass (PassBuilder/apply_pass), 'full' adds "
              "whole-program shape/dtype re-inference and the hazard lints "
              "(donation aliasing, recompile hazards, collective order, "
              "RNG determinism).  Error-severity findings raise classified "
              "ProgramVerificationError naming the op, var, and block")
DEFINE_string("FLAGS_resource_precheck", "on",
              "static OOM pre-check on every executor compile-cache miss "
              "(paddle_tpu/core/resource_plan.py): 'on' (default) plans the "
              "program's liveness-based peak HBM and raises a classified "
              "ResourceError naming the watermark ops when the plan exceeds "
              "FLAGS_resource_hbm_limit_mb — BEFORE any XLA compile or "
              "allocation; 'off' skips planning entirely.  With no limit "
              "set the check is a no-op: the plan is an upper bound (no "
              "fusion, no buffer reuse) and is not held against the limit "
              "the device reports")
DEFINE_float("FLAGS_resource_hbm_limit_mb", 0.0,
             "HBM limit (MB) the resource pre-check plans against; 0 "
             "(default) = no limit, no check")
DEFINE_string("FLAGS_feed_validation", "shape",
              "feed-boundary validation level at DataLoader/DataFeeder "
              "(paddle_tpu/reader.py FeedSpec): 'off' trusts the caller, "
              "'shape' (default) checks dtype-kind + shape against the feed "
              "vars and raises DataError naming the slot BEFORE lowering "
              "(a mismatched feed otherwise surfaces as an opaque XLA "
              "error), 'full' additionally scans floating feeds for "
              "NaN/Inf")
DEFINE_float("FLAGS_dist_heartbeat_interval_s", 0.5,
             "seconds between liveness beats each worker publishes to its "
             "peers (paddle_tpu/dist_resilience.py).  The transport rides "
             "the PADDLE_TRAINER_* endpoint contract: UDP to every peer "
             "endpoint, or files under PADDLE_HEARTBEAT_DIR when set "
             "(what paddle_tpu.launch uses on localhost)")
DEFINE_float("FLAGS_dist_heartbeat_miss_factor", 10.0,
             "a peer is declared dead after interval_s * miss_factor "
             "seconds without an observed beat; the collective watchdog "
             "then raises PeerFailureError instead of letting the next "
             "collective hang forever.  Keep the product in whole seconds: "
             "a beat thread can starve behind GIL-heavy import/compile "
             "phases, and a too-tight deadline reads starvation as death")
DEFINE_float("FLAGS_dist_straggler_lag_steps", 1.0,
             "live straggler detection (paddle_tpu/dist_resilience.py): a "
             "rank whose dispatch-attempt count lags the gang by at least "
             "this many steps across 3 consecutive heartbeats is named a "
             "straggler (dist.straggler_suspects counter, "
             "dist.step_skew_frac gauge, one 'straggler' dist_event) "
             "before any watchdog deadline fires.  Sync collectives bound "
             "the observable lag at ~1 (fast ranks block inside the "
             "collective), so 1.0 with the 3-beat hold-down is the "
             "sensitive-but-quiet default; raise it on pipelined meshes "
             "that legitimately run ranks ahead")
DEFINE_float("FLAGS_dist_watchdog_timeout_s", 120.0,
             "deadline armed around every collective/blocking device wait "
             "when the distributed health layer is active; on expiry all "
             "thread stacks are dumped and CollectiveTimeoutError raised")
DEFINE_float("FLAGS_dist_bootstrap_timeout_s", 120.0,
             "deadline on jax.distributed.initialize (the gen_nccl_id "
             "role): a gang whose worker never dials in raises "
             "CollectiveTimeoutError instead of blocking the others at "
             "startup")
DEFINE_float("FLAGS_dp_bucket_mb", 4.0,
             "gradient-bucket size cap (MB) for the backward-overlapped "
             "data-parallel all-reduce (parallel/distributed.py "
             "make_grad_sync, CompiledProgram.with_grad_overlap): grads "
             "are grouped reverse-topologically into buckets of at most "
             "this many bytes and each bucket is all-reduced as soon as "
             "its grads are ready, overlapping communication with the "
             "rest of the backward pass (the DDP bucketing strategy)")
DEFINE_int("FLAGS_serving_max_queue", 256,
           "admission-control bound on the serving runtime's request "
           "queue (paddle_tpu/serving/server.py): a submit() past this "
           "depth is SHED with a classified ServingError(reason="
           "'overload') instead of growing tail latency without bound "
           "(serving.shed counter; perf_report --check "
           "--max-shed-frac gates the rate).  Per-Server override via "
           "Server(max_queue=...)")
DEFINE_float("FLAGS_serving_default_deadline_ms", 0.0,
             "default per-request deadline for serving submits that do "
             "not pass their own deadline_ms: a request still queued when "
             "its deadline expires is cancelled with ServingError(reason="
             "'timeout') and the batch proceeds without it "
             "(serving.timeouts counter).  0 (default) = no deadline")
DEFINE_float("FLAGS_serving_hbm_budget_mb", 0.0,
             "HBM budget for multi-model co-residency in the serving "
             "model registry (paddle_tpu/serving/registry.py): loading a "
             "model past the budget first evicts cold (LRU, non-active) "
             "models, then refuses loudly with ServingError(reason="
             "'hbm_budget') — never OOMs the chip mid-request.  Live "
             "usage rides the monitor/memstats gauges.  0 (default) = "
             "unlimited")
DEFINE_float("FLAGS_serving_quant_atol", 5e-2,
             "accuracy-parity gate for publishing a QUANTIZED model over "
             "its fp32 parent (paddle_tpu/serving/publisher.py): during "
             "the golden smoke the staged low-precision snapshot's "
             "outputs are compared elementwise against the ACTIVE "
             "version's outputs on the same feeds; max |diff| past this "
             "tolerance REJECTS + QUARANTINES the snapshot exactly like "
             "NaN weights (the fp32 parent keeps serving bit-identically)."
             "  Only applies when the staged dir carries a __quant__.json "
             "manifest and an active version exists to compare against")
DEFINE_float("FLAGS_serving_slo_target", 0.99,
             "serving SLO good-fraction target the burn-rate gauges are "
             "computed against (paddle_tpu/serving/server.py): a request "
             "is GOOD when it completes within its deadline (no deadline "
             "= completing at all); burn_rate = bad_frac / (1 - target), "
             "so serving.slo_burn_rate > 1.0 means the server is "
             "spending its error budget faster than the SLO allows.  "
             "Sheds, timeouts, errors, and late completions all burn; "
             "admission-door rejections (bad_request/oversize/"
             "model_missing) are not SLO traffic")
DEFINE_string("FLAGS_serving_buckets", "1,2,4,8,16,32",
              "comma-separated pad-to-bucket batch sizes the serving "
              "runtime compiles (paddle_tpu/serving/batcher.py): a "
              "request batch pads up to the next bucket so a novel size "
              "NEVER triggers an inline recompile — buckets warm at "
              "model load (or in the publisher's pre-swap compile lane) "
              "and steady-state serving must keep executor.recompile "
              "flat (perf_report --check's recompile gate)")
DEFINE_int("FLAGS_integrity_check_period", 0,
           "live silent-corruption sentinel (paddle_tpu/integrity.py): "
           "every PERIOD steps the full parameter + optimizer state is "
           "content-digested, amortized chunk-wise so each step hashes "
           "only ~1/PERIOD of the bytes.  In multi-worker gangs the "
           "digest rides the heartbeat telemetry payload and replicated "
           "dp state must agree bit-exactly across ranks — a divergence "
           "majority-votes the corrupt rank, dumps the flight recorder, "
           "and raises a classified errors.IntegrityError that the "
           "resilient loop recovers from via checkpoint rollback.  0 "
           "(default) disables live digesting entirely: the training "
           "loop pays literally nothing")
DEFINE_bool("FLAGS_integrity_verify_load", True,
            "verify the per-file sha256 + byte-length stamps that "
            "io.save/save_sharded record in their manifests whenever a "
            "checkpoint or model directory is loaded (restore, "
            "load_sharded, load_vars, the serving publish ladder): a "
            "mismatch raises a classified errors.IntegrityError naming "
            "the file instead of silently serving rotted bytes.  "
            "Manifests written before the digests existed (no sha256 "
            "field) load unchecked.  Off trusts the disk — the escape "
            "hatch when re-reading every shard for hashing is too "
            "expensive for a given restore path")
DEFINE_string("FLAGS_ckpt_fallback_dir", "",
              "secondary checkpoint destination (a DIFFERENT filesystem — "
              "local scratch, a second mount) tried when a save to the "
              "primary root fails its storage retries or hits a terminal "
              "EROFS/EACCES (paddle_tpu/checkpoint_manager.py).  A "
              "fallback commit clears degraded mode like a primary one, "
              "and restore() merges both roots' checkpoints into one "
              "newest-first walk.  Single-process managers only "
              "(coordinated gang saves need every rank on one shared "
              "dir).  Empty (default) = no fallback: a failed save "
              "enters degraded mode directly.  The fault injector "
              "exempts paths under this dir — it models a different "
              "device, so an injected ENOSPC/EROFS on the primary must "
              "not also break it")
DEFINE_int("FLAGS_max_ckpt_lag_steps", 0,
           "degraded-mode bound (paddle_tpu/checkpoint_manager.py): the "
           "maximum number of steps training may run past its last "
           "COMMITTED checkpoint while storage is failing.  Saves past "
           "the bound raise a terminal classified errors.StorageError "
           "instead of degrading further — unprotected training cannot "
           "run forever on a dead store.  0 (default) = unbounded "
           "degraded mode (the resilience.ckpt_lag_steps gauge and "
           "storage_degraded events still go loud; gate them with "
           "perf_report --check --max-ckpt-lag-steps)")
DEFINE_bool("FLAGS_lock_telemetry", False,
            "per-lock contention telemetry for every named framework lock "
            "(paddle_tpu/core/locks.py): lock.<name>.acquires/contended/"
            "wait_us/hold_us monitor counters plus lock.order_inversions "
            "when an acquisition inverts the declared ranks.  OPT-IN: off "
            "(default) keeps acquire/release at one branch over the raw "
            "primitive (the monitor-overhead hot-path budget); gate the "
            "measured contention with perf_report --check "
            "--max-lock-wait-frac")
DEFINE_float("FLAGS_lock_timeout_s", 0.0,
             "deadline on every blocking named-lock acquisition "
             "(paddle_tpu/core/locks.py): past it the acquire raises a "
             "classified errors.LockTimeoutError naming the wanted lock "
             "AND every lock the thread holds (with declared ranks) "
             "instead of hanging the worker forever — a deadlock dies "
             "loudly and attributable.  0 (default) = no deadline")
DEFINE_float("FLAGS_ps_timeout_s", 10.0,
             "socket deadline on every parameter-server RPC "
             "(paddle_tpu/param_server.py): connect/send/recv past it "
             "raise a classified TRANSIENT errors.ParamServerError the "
             "KVClient retries with reconnect + backoff instead of "
             "wedging training on a dead pserver forever.  0 = no "
             "deadline (the pre-hardening behavior)")
DEFINE_int("FLAGS_ps_retries", 5,
           "KVClient retry budget per RPC (paddle_tpu/param_server.py): "
           "transient ParamServerErrors (timeout, connection refused/"
           "reset while the supervisor restarts the pserver) retry with "
           "seeded exponential backoff up to this many attempts; pushes "
           "carry per-client sequence numbers so a retried push applies "
           "EXACTLY once server-side.  Exhausting the budget raises the "
           "last error terminal")
DEFINE_int("FLAGS_ps_max_frame_mb", 256,
           "frame-size cap on the pserver wire protocol "
           "(paddle_tpu/param_server.py): a length prefix past the cap "
           "is a corrupt/hostile frame and raises a terminal classified "
           "ParamServerError instead of mallocing unbounded on either "
           "end of the socket")
DEFINE_int("FLAGS_ps_snapshot_every_ops", 256,
           "pserver durability cadence (paddle_tpu/param_server.py): a "
           "full table snapshot commits through the io.py atomic choke "
           "point every N journaled mutating ops; between snapshots the "
           "write-ahead op journal alone replays a crash-restarted "
           "pserver back to bit-identical tables.  0 = journal-only "
           "(snapshot only at stop())")
DEFINE_int("FLAGS_max_host_lag_steps", 0,
           "degraded-mode bound for the host sparse tier "
           "(paddle_tpu/parallel/embedding.py): the maximum number of "
           "consecutive steps training may run hot-shard-only (zero "
           "cold-tail rows, stale host tables) while the pserver is "
           "down.  Past the bound the next lookup raises a TERMINAL "
           "classified errors.ParamServerError — online learning cannot "
           "silently diverge from its cold tail forever.  0 (default) = "
           "unbounded degraded mode (the sparse.host_lag_steps gauge "
           "and host_tier_degraded events still go loud; gate them with "
           "perf_report --check --max-host-lag-steps)")
DEFINE_int("FLAGS_publish_period_steps", 0,
           "online-learning publish cadence (paddle_tpu/resilience.py): "
           "resilient_train_loop calls its publish hook every N steps, "
           "maintaining the serving.publish_staleness_steps gauge "
           "(trained step minus last successfully published step).  A "
           "transient storage failure inside the hook is absorbed "
           "(staleness grows, cadence resumes at the next period); "
           "content failures (quarantined snapshot) propagate.  0 "
           "(default) = no publish hook; gate the staleness with "
           "perf_report --check --max-publish-staleness-steps")
DEFINE_bool("FLAGS_cudnn_deterministic", True,
            "accepted no-op: XLA TPU lowerings are deterministic by default")
DEFINE_float("FLAGS_fraction_of_gpu_memory_to_use", 1.0,
             "accepted no-op: PJRT owns device memory")
DEFINE_string("FLAGS_allocator_strategy", "auto_growth",
              "accepted no-op: PJRT owns allocation")
DEFINE_int("FLAGS_paddle_num_threads", 1,
           "accepted no-op: XLA:CPU threading is runtime-managed")

def apply_xla_dump():
    """Wire FLAGS_xla_dump_to into XLA.  Effective for programs compiled
    after the flag is set (XLA reads XLA_FLAGS at backend init; when the
    backend is already up, per-compile env is still consulted by the
    compiler for dump options)."""
    d = flag("FLAGS_xla_dump_to")
    if d and f"--xla_dump_to={d}" not in os.environ.get("XLA_FLAGS", ""):
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "") + f" --xla_dump_to={d}"
        ).strip()


# The cache of an entry point that names none: one fixed path in the
# checkout (git-ignored).  The path is part of the cache's key, so a
# directory that moves between runs never hits.
CHECKOUT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), ".jax_cache")


def apply_compile_cache(default_dir: str = "", min_compile_secs: float = 0.0) -> str:
    """The one rule for where jax's persistent compilation cache lives;
    returns the directory in effect ("" = no cache).

    JAX_COMPILATION_CACHE_DIR set: jax reads it itself and this code sets
    no directory, whatever FLAGS_compile_cache_dir says — whoever runs the
    program placed the cache.  Unset: FLAGS_compile_cache_dir, else
    `default_dir` (the entry points chip_smoke.py and
    tests/conftest.py pass CHECKOUT_CACHE_DIR).  The min-compile-time floor
    drops to `min_compile_secs` so every program signature is cached — the
    framework compiles few, large programs; the test suite compiles
    thousands of tiny ones and keeps a floor.  Effective for programs
    compiled after the call."""
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR", "")
    if env_dir:
        return env_dir
    d = flag("FLAGS_compile_cache_dir") or default_dir
    if not d:
        return ""
    import jax

    jax.config.update("jax_compilation_cache_dir", d)
    jax.config.update("jax_persistent_cache_min_compile_time_secs",
                      float(min_compile_secs))
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return d


init_from_env()
apply_xla_dump()
apply_compile_cache()
