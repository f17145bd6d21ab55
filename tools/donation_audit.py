"""Buffer-donation audit over the model zoo's compiled train steps.

The executor donates every read+written persistable (params, optimizer
accumulators, BN running stats) to the XLA executable, so the update aliases
in place in HBM (`core/executor.py` `_CompiledStep.rw_names`,
donate_argnums).  A persistable that is written but NOT donated-and-aliased
is silently double-buffered: the step allocates a second copy of the buffer
and pays an extra HBM write every step — at BERT-base scale that is ~0.5 GB
of wasted traffic and residency per step.  The r5 chip record's `params_moved`
reported 18/198 BERT params "frozen", which is either exactly this class of
drop or a bench-probe artifact; this tool decides which, statically, for
every program in the zoo (verdict: probe artifact — see docs/performance.md
and tests/test_donation_audit.py).

Classification per written persistable (program order):

  donated            read + written, input/output avals identical -> XLA
                     aliases the update in place (donate_argnums covers it)
  copied_aval_drift  donated, but the written value's shape/dtype differs
                     from the input's -> XLA CANNOT alias; the "update" is
                     a fresh allocation every step (the r5 bf16+Adam freeze
                     shipped inside this class before register_opt pinned
                     output dtypes)
  copied_not_read    written but never read -> outside the donation set
                     entirely (steps>1 rejects these; steps=1 silently
                     double-buffers)

Trainable parameters that are never written at all are reported as
`never_updated` — the program's optimizer does not touch them (a genuinely
frozen param, as opposed to a bench probe reading sub-resolution updates
as frozen).

    python tools/donation_audit.py                 # report, full-size zoo
    python tools/donation_audit.py --tiny          # CI-size configs
    python tools/donation_audit.py --check --tiny  # exit 1 on any drop
    python tools/donation_audit.py --program bert --json
"""
from __future__ import annotations

import argparse
import json
import os
import sys

if __name__ == "__main__":
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


# --------------------------------------------------------------------------
# zoo builders (program + startup + example feed + fetch names)
# --------------------------------------------------------------------------


def build_zoo(tiny: bool = False, only=None):
    """[(name, main, startup, feed {name: np.ndarray}, fetch_names)].

    `tiny` shrinks every config to CI size (the audit is structural — the
    donation set does not depend on widths, so tiny results transfer)."""
    import paddle_tpu as fluid

    out = []

    def want(n):
        return only is None or n == only

    if want("mnist"):
        from paddle_tpu.models import mnist

        main, startup, feeds, fetches = mnist.build(learning_rate=1e-3)
        rng = np.random.RandomState(0)
        feed = {"img": rng.rand(4, 1, 28, 28).astype("f4"),
                "label": rng.randint(0, 10, (4, 1)).astype("i8")}
        out.append(("mnist", main, startup, feed, [fetches["loss"].name]))

    if want("resnet50"):
        from paddle_tpu.models import resnet

        if tiny:
            main, startup, feeds, fetches = resnet.build(
                depth=50, class_dim=10, image_shape=(3, 32, 32),
                with_optimizer=True)
            img = np.random.RandomState(0).rand(2, 3, 32, 32).astype("f4")
        else:
            main, startup, feeds, fetches = resnet.build(
                dtype="bfloat16", class_dim=1000, with_optimizer=True,
                stem="space_to_depth")
            img = np.random.RandomState(0).rand(2, 3, 224, 224).astype("f4")
        feed = {"img": img,
                "label": np.zeros((img.shape[0], 1), "i8")}
        out.append(("resnet50", main, startup, feed, [fetches["loss"].name]))

    if want("bert"):
        from paddle_tpu.models import transformer

        kw = (dict(vocab_size=200, seq_len=16, d_model=32, n_layers=2,
                   n_heads=2, d_ff=64) if tiny else
              dict(vocab_size=30522, seq_len=128, d_model=768, n_layers=12,
                   n_heads=12, d_ff=3072, dtype="bfloat16"))
        main, startup, feeds, fetches = transformer.build_bert(
            with_optimizer=True, **kw)
        b = transformer.make_fake_batch(2, kw["seq_len"], kw["vocab_size"],
                                        rng=np.random.RandomState(0))
        out.append(("bert", main, startup, dict(b), [fetches["loss"].name]))

    if want("nmt"):
        from paddle_tpu.lod import lod_var_name
        from paddle_tpu.models import nmt

        kw = (dict(src_vocab=80, tgt_vocab=80, d_model=32, n_layers=1,
                   n_heads=2, d_ff=64) if tiny else
              dict(src_vocab=8000, tgt_vocab=8000, d_model=512, n_layers=6,
                   n_heads=8, d_ff=2048))
        main, startup, feeds, fetches = nmt.build_transformer_nmt(
            dropout=0.1, learning_rate=2.0, **kw)
        rng = np.random.RandomState(0)
        b, T = 2, 12
        feed = {}
        for nm in ("src_word", "trg_word", "lbl_word"):
            feed[nm] = rng.randint(1, 80, (b, T, 1)).astype("i4")
            feed[lod_var_name(nm)] = np.full((b,), T, "i4")
        out.append(("nmt", main, startup, feed, [fetches["loss"].name]))

    if want("deepfm"):
        from paddle_tpu.models import deepfm

        kw = (dict(num_fields=4, vocab_size=50, embed_dim=4,
                   mlp_dims=(8,)) if tiny else
              dict(num_fields=26, vocab_size=200000, embed_dim=16,
                   mlp_dims=(400, 400, 400)))
        main, startup, feeds, fetches = deepfm.build(learning_rate=0.05, **kw)
        rng = np.random.RandomState(0)
        nf = kw["num_fields"]
        feed = {"feat_ids": rng.randint(0, kw["vocab_size"], (4, nf)).astype("i4"),
                "label": (rng.rand(4, 1) < 0.3).astype("f4")}
        out.append(("deepfm", main, startup, feed, [fetches["loss"].name]))

    return out


# --------------------------------------------------------------------------
# the audit
# --------------------------------------------------------------------------


def _aval(v):
    return (tuple(np.shape(v)), str(np.asarray(v).dtype) if not hasattr(v, "dtype") else str(v.dtype))


def audit_program(main, startup, feed, fetch_names, place=None):
    """Audit one program's compiled step; returns the classification dict.

    Builds the SAME `_CompiledStep` the executor would (no compile, no
    execute) and abstract-evaluates the step function to compare each
    written persistable's output aval against its input — identical avals
    inside the donation set is what lets XLA alias the update in place."""
    import jax

    import paddle_tpu as fluid
    from paddle_tpu.core.executor import _CompiledStep
    from paddle_tpu.core.scope import RNG_STATE_VAR

    scope = fluid.Scope()
    exe = fluid.Executor(place or fluid.CPUPlace())
    exe.run(startup, scope=scope)

    block = main.global_block()
    jfeed = {}
    for n, v in feed.items():
        arr = np.asarray(v)
        if block.has_var(n):
            from paddle_tpu.core.dtypes import as_np_dtype

            want = as_np_dtype(block.var(n).dtype)
            if want is not None and arr.dtype != want:
                arr = arr.astype(want)
        from paddle_tpu.ops.common import canon_dtype

        canon = canon_dtype(arr.dtype)
        if arr.dtype != canon:
            arr = arr.astype(canon)
        jfeed[n] = arr
    compiled = _CompiledStep(main, list(jfeed), list(fetch_names), scope,
                             platform="cpu",
                             feed_shapes={n: v.shape for n, v in jfeed.items()})

    state_rw = {n: scope.find_var(n) for n in compiled.rw_names}
    state_ro = {n: scope.find_var(n) for n in compiled.ro_names}
    key = scope.find_var(RNG_STATE_VAR)
    if key is None:
        key = jax.random.PRNGKey(main.random_seed or 0)
    _, out_state, _ = jax.eval_shape(compiled.jfn, state_rw, state_ro,
                                     jfeed, key)

    rw = set(compiled.rw_names)
    donated, drift, not_read = [], [], []
    for n in compiled.written_names:
        if n not in rw:
            not_read.append(n)
            continue
        in_aval = _aval(state_rw[n])
        out_aval = _aval(out_state[n])
        (donated if in_aval == out_aval else drift).append(n)

    written = set(compiled.written_names)
    trainable = [p.name for p in main.all_parameters()
                 if getattr(p, "trainable", True)]
    has_optimizer = any(op.type == "backward"
                        for op in main.global_block().ops)
    never = [p for p in trainable if p not in written] if has_optimizer else []

    return {
        "persistable_written": len(compiled.written_names),
        "donated": len(donated),
        "copied_aval_drift": sorted(drift),
        "copied_not_read": sorted(not_read),
        "never_updated": sorted(never),
        "trainable_params": len(trainable),
        "read_only_state": len(compiled.ro_names),
    }


def audit_zoo(tiny=False, only=None, place=None):
    """{model: report} over the zoo; each report gains `clean`."""
    reports = {}
    for name, main, startup, feed, fetches in build_zoo(tiny, only):
        r = audit_program(main, startup, feed, fetches, place=place)
        r["clean"] = not (r["copied_aval_drift"] or r["copied_not_read"]
                         or r["never_updated"])
        reports[name] = r
    return reports


def render(reports) -> str:
    lines = ["# donation audit (non-donated persistable updates are wasted "
             "HBM traffic + residency every step)"]
    for name, r in reports.items():
        verdict = "OK" if r["clean"] else "DROPS"
        lines.append(
            f"{name:10s} {verdict:6s} donated {r['donated']}/"
            f"{r['persistable_written']} written persistables, "
            f"{r['trainable_params']} trainable params, "
            f"{r['read_only_state']} read-only")
        for k in ("copied_aval_drift", "copied_not_read", "never_updated"):
            if r[k]:
                lines.append(f"  {k}: {r[k][:8]}"
                             + (" ..." if len(r[k]) > 8 else ""))
    return "\n".join(lines)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--check", action="store_true",
                    help="exit 1 unless every zoo program donates every "
                         "persistable update (the perf_report-adjacent CI "
                         "gate for ISSUE 7)")
    ap.add_argument("--tiny", action="store_true",
                    help="CI-size model configs (donation sets are "
                         "structural, so results transfer to full size)")
    ap.add_argument("--program", default=None,
                    help="audit one zoo program (mnist|resnet50|bert|nmt|"
                         "deepfm)")
    ap.add_argument("--json", action="store_true", help="machine output")
    args = ap.parse_args(argv)

    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    reports = audit_zoo(tiny=args.tiny, only=args.program)
    if args.json:
        print(json.dumps(reports))
    else:
        print(render(reports))
    if args.check:
        dirty = {n: r for n, r in reports.items() if not r["clean"]}
        if dirty:
            print(f"donation_audit --check: FAILED — non-donated updates in "
                  f"{sorted(dirty)}", file=sys.stderr)
            return 1
        print(f"donation_audit --check: OK — every persistable update in "
              f"{sorted(reports)} is donated and aliased in place",
              file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
