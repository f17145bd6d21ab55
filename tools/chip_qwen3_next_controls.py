"""Qwen3-Next's cell on the chip, what its comparison can and cannot tell: the
harness's own `benchmark.models.qwen3_next.compare` / `failed_limits` on the
program's check rows against the float32 reference, sound and then with a
fault put in, one at a time, so that each limit this PR brings has a reading it
must refuse beside the sound one (PERF.md, section 6, PR 69).

Seventeen faults go into THE REFERENCE (`qwen3_next.FAULTS`: the reference is then
another function than the program's, and the comparison has to say so; the
program's check rows run once): the decay left out (g = 0), the decay of head h
on head h + 1, the decay written over the channels (g / 128), beta left out, the
delta's correction left out (plain gated linear attention), value head h on key
head h mod 16 for h div 2, the L2 norm left out, q's 128^-0.5 left out, a
sigmoid for z's SiLU, three taps for four, the whole 256 of a head turned, 128
of it, theta 1e6, a sigmoid for the router's softmax, top 8 for top 10, the
renormalisation left out, the shared gate left out.

Eight go into THE PROGRAM (an op's seam or registered lowering changed, or the
program built again with another argument, and the check rows run through a
new executor on the SOUND program's parameters: the names are the same), where
only a stage on the program's own tensors can tell:

  * `scan_state_in_bf16`: the state a chunk hands the next rounded to bf16
    (`kda_kernels.carried`): `SCAN_RTOL`;
  * `scan_at_default_precision`: the scan's float32 products at the chip's
    default precision (bf16 operands, the nearest precision below):
    `SCAN_RTOL`;
  * `feature_gate_left_out`: the gate's sigmoid gives 1 for every feature;
    `a_heads_mean_for_the_feature_gate`: every feature of a head takes the mean
    of the head's gate columns: `GATED_RTOL` (end to end a gate a head moves the
    worst of 2048 positions by 0.25 where sound seeds read up to 0.17);
  * `query_head_j_on_kv_head_j_mod_2`: the query heads handed to the attention
    in the other grouping's order and its output handed back: `ATTENTION_RTOL`;
  * `router_in_bf16`: the router's float32 matrix rounded to bf16 before the
    logits' product: `ROUTER_RTOL`;
  * `sigmoid_router_in_the_program`, `renormalisation_left_out_in_the_program`:
    the program built with `scoring` sigmoid, with `norm_topk_prob` false: a
    softmax and a sigmoid order alike, so end to end only the held experts'
    weights move: `ROUTER_RTOL`.

    chiprun --timeout 3400 -- python3 tools/chip_qwen3_next_controls.py 3690000017      (PERF.md, PR 69)

Names after the seed run those controls alone, beside `sound`.  `ROWS=2` in the
environment compares two check rows for the runner's eight.  `DRY=1` rehearses
it tiny on the CPU; no number of that means anything.
"""
import contextlib
import gc
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

DRY = os.environ.get("DRY") == "1"

import jax
import jax.numpy as jnp
import numpy as np

import paddle_tpu as fluid
from benchmark import manifest as mf
from benchmark.models import lfm2, qwen3_next
from benchmark.runners import train as runner
from paddle_tpu.core.registry import get_op_def
from paddle_tpu.models import transformer
from paddle_tpu.ops import kda_kernels
from paddle_tpu.ops import linear_attention_ops as lao

TINY = (dict(hidden_size=32, vocab_size=128, head_dim=16, num_attention_heads=4, num_key_value_heads=2, linear_num_key_heads=2,
             linear_num_value_heads=4, linear_key_head_dim=8, linear_value_head_dim=8, moe_intermediate_size=16,
             shared_expert_intermediate_size=16, num_routed_experts=32, num_experts=8, num_experts_per_tok=10,
             compute_dtype="float32"),
        dict(seq_len=64, batch_per_chip=1, ring=4))


@contextlib.contextmanager
def lowered_as(op_type, wrong):
    """The registered lowering of `op_type` replaced by `wrong(real, ctx, op, ins)` for the length of the block."""
    definition = get_op_def(op_type)
    real = definition.lower
    definition.lower = lambda ctx, op, ins: wrong(real, ctx, op, ins)
    try:
        yield
    finally:
        definition.lower = real


@contextlib.contextmanager
def patched(module, name, value):
    real = getattr(module, name)
    setattr(module, name, value)
    try:
        yield
    finally:
        setattr(module, name, real)


def _round(t):
    return jax.lax.reduce_precision(t, 8, 7)


def in_the_gate(op):
    return "attention_gate" in (getattr(op, "attrs", {}).get("op_namescope") or "")


def gate_as(form):
    """The `sigmoid` op of the `attention_gate` scope lowered otherwise: ones, or every feature its head's mean column."""
    def wrong(real, ctx, op, ins):
        if not in_the_gate(op):
            return real(ctx, op, ins)
        columns = ins["X"][0]                                   # (B, L, H, dh) float32
        if form == "ones":
            return {"Out": jnp.ones_like(columns)}
        return real(ctx, op, {**ins, "X": [jnp.broadcast_to(jnp.mean(columns, -1, keepdims=True), columns.shape)]})
    return lambda: (lowered_as("sigmoid", wrong), None)


def other_grouping(real, ctx, op, ins):
    """Query head j on key/value head j mod Hkv: the kernels group neighbours, so the heads go in at (j mod Hkv) G + j div
    Hkv and the output comes back in their own order."""
    at = 2 if op.attr("layout", "bhld") == "blhd" else 1
    heads, kv_heads = ins["Q"][0].shape[at], ins["K"][0].shape[at]
    group = heads // kv_heads
    place = (np.arange(heads) % kv_heads) * group + np.arange(heads) // kv_heads     # where head j goes
    out = real(ctx, op, {**ins, "Q": [jnp.take(ins["Q"][0], np.argsort(place), axis=at)]})
    return {**out, "Out": jnp.take(out["Out"], place, axis=at)}


def main(seed: int, only=()):
    cfg = mf.read_json("benchmark/configs/qwen3-next-80b-a3b-instruct.json")
    job = mf.read_json("benchmark/traffic/train-gdn-s16384.json")
    if DRY:
        cfg.update(TINY[0])
        job.update(TINY[1])
        lfm2.LOGIT_SAMPLE = lfm2.ATTENTION_SAMPLE = qwen3_next.ATTENTION_SAMPLE = 16
    runner.CHECK_ROWS = int(os.environ.get("ROWS", 2 if DRY else runner.CHECK_ROWS))     # `build` gathers that many rows' logits

    def built(**arguments):     # the program, `build_causal_lm` handed other arguments where a fault says so
        real = transformer.build_causal_lm
        with fluid.unique_name.guard(), patched(transformer, "build_causal_lm", lambda **kw: real(**{**kw, **arguments})):
            return qwen3_next.build(cfg, job)

    program, startup, _, _, check_names = built()
    program.random_seed = startup.random_seed = seed
    scope = fluid.Scope()
    fluid.Executor(fluid.TPUPlace(0)).run(startup, scope=scope)
    rows = qwen3_next.make_batch(np.random.RandomState(seed % 2**32), cfg, job, runner.CHECK_ROWS)
    params = {p.name: scope.find_var(p.name) for p in program.all_parameters()}
    # nothing trains here: without Adam's moments (8 bytes a parameter) a second clone's program finds room beside the state
    scope.erase([n for n in scope.var_names() if "_moment" in n])
    batch = {k: np.asarray(v) for k, v in rows.items()}

    def reference(*faults):   # to the host at once: nothing of it stays on the chip beside a clone
        return [np.asarray(w) for w in jax.jit(lambda p, b: qwen3_next.reference(p, b, cfg, program, faults=faults))(params, batch)]

    def check_rows(of=None):   # a new executor and a new clone: nothing compiled under another fault is met again
        main_, _, _, _, names = of or (program, None, None, None, check_names)
        got = fluid.Executor(fluid.TPUPlace(0)).run(main_.clone(for_test=True), feed=rows, fetch_list=list(names), scope=scope)
        jax.clear_caches()      # ... and none stays loaded on the chip
        gc.collect()
        return got

    def report(name, mine, theirs):
        found = qwen3_next.compare(mine, theirs)
        refused = qwen3_next.failed_limits(found)
        print(json.dumps({"control": name, "seed": seed, "correct": not refused, "refused_by": refused, **found}), flush=True)

    def rounded_router(real, ctx, op, ins):
        return real(ctx, op, {**ins, "W": [_round(ins["W"][0])]})

    in_the_program = {
        # the seams are static arguments of the kernels' `jax.jit`s, found again by the function itself: a new one a fault
        # (inside a Pallas kernel a pair of casts, of which Mosaic takes none out: it has no `reduce_precision`)
        "scan_state_in_bf16": lambda: (patched(kda_kernels, "carried", lambda state: state.astype(jnp.bfloat16).astype(jnp.float32)), None),
        "scan_at_default_precision": lambda: (patched(lao, "_KDA_PRECISION", jax.lax.Precision.DEFAULT), None),
        "feature_gate_left_out": gate_as("ones"),
        "a_heads_mean_for_the_feature_gate": gate_as("mean"),
        "query_head_j_on_kv_head_j_mod_2": lambda: (lowered_as("fused_attention", other_grouping), None),
        "router_in_bf16": lambda: (lowered_as("moe_router", rounded_router), None),
        "sigmoid_router_in_the_program": lambda: (contextlib.nullcontext(), built(scoring="sigmoid")),
        "renormalisation_left_out_in_the_program": lambda: (contextlib.nullcontext(), built(norm_topk_prob=False)),
    }
    sound, want = check_rows(), reference()
    report("sound", sound, want)
    for name in qwen3_next.FAULTS:
        if not only or name in only:
            report(name, sound, reference(name))
    for name, fault in in_the_program.items():
        if not only or name in only:
            lowering, other = fault()
            with lowering:
                report(name, check_rows(other), want)


if __name__ == "__main__":
    main(int(sys.argv[1]) if len(sys.argv) > 1 else 3690000017, tuple(sys.argv[2:]))
