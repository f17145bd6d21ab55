"""Laguna-XS.2's cell on the chip, what its comparison can and cannot tell: the
harness's own `benchmark.models.laguna.compare` / `failed_limits` on the
program's check rows against the float32 reference, sound and then with a
fault put in, one at a time, so that each limit this PR brings has a reading it
must refuse beside the sound one (PERF.md, section 6, PR 65).  Most faults go
into THE PROGRAM (built again with the fault, or an op's registered lowering
wrapped, and the check rows run through a new executor on the SOUND program's
parameters: the names are the same); one goes into the weights the reference is
handed, one is the reference a precision lower:

  * `yarn_blend_left_out`: layer 0's (and layer 4's) frequencies theta's own,
    5e5^(-i/32), the factor on cos and sin kept: `QK_RTOL`;
  * `attention_factor_left_out`: cos and sin of the full layers not multiplied
    by 1.4158883: `QK_RTOL`;
  * `whole_head_turned_in_layer_0`: `partial_rotary_factor` 1 on the full
    layers; `half_head_turned_in_layer_1`: 0.5 on the windows: `QK_RTOL`;
  * `thetas_exchanged`: 1e4 on the full layers, 5e5 on the windows: `QK_RTOL`;
  * `angles_in_bf16`: the rotary angle rounded to bf16 before its sine and
    cosine (at position 16383 a bf16 angle is off by whole turns): `QK_RTOL`;
  * `window_of_511`, `window_of_513`: one key fewer, one more: `WINDOW_EDGE_MAX`
    (the stage's rule is made from positions here, and the error is measured
    along what each fault would add);
  * `gate_left_out`: the gate's sigmoid gives 1 for every head and token:
    `GATE_RTOL`; `gate_in_bf16`: its logits and its value rounded to bf16:
    `GATE_RTOL`; `gate_of_head_j_on_head_j_plus_1`: the product takes the
    neighbouring head's gate: `GATED_RTOL`;
  * `query_head_j_on_kv_head_j_mod_8`: the query heads handed to the attention
    in the other grouping's order and its output handed back, so that head j
    reads key/value head j mod 8 for j div (H / 8): `ATTENTION_RTOL`;
  * `softmax_for_sigmoid_in_the_router`, `factor_2_5_left_out`: the program
    built with `scoring_func` softmax, with `moe_routed_scaling_factor` 1:
    `ROUTER_RTOL` (and `ROUTER_TIE`: a softmax orders alike, so the first may
    not differ in its choice);
  * `router_in_bf16`: the router's float32 matrix rounded to bf16 before the
    logits' product: `ROUTER_RTOL`;
  * `shared_expert_left_out`: the reference handed a first sparse layer whose
    shared expert's down matrix is 0 (the errors are differences):
    `REFERENCE_RTOL`, end to end;
  * `attention_at_default_precision`: the reference's two attention products at
    the chip's default precision (bf16 operands), the nearest precision below
    the one the reference states: `REFERENCE_SELF_RTOL`, the reference's own
    first attention against float64 (end to end nothing tells it: the program
    rounds as much itself).

    chiprun --timeout 3400 -- python3 tools/chip_laguna_controls.py 3650000017      (PERF.md, PR 65)

Names after the seed run those controls alone, beside `sound`.
`DRY=1` rehearses it tiny on the CPU; no number of that means anything.
"""
import contextlib
import gc
import json
import os
import re
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

DRY = os.environ.get("DRY") == "1"

import jax
import jax.numpy as jnp
import numpy as np

import paddle_tpu as fluid
from benchmark import manifest as mf
from benchmark.models import laguna, lfm2
from benchmark.runners.train import CHECK_ROWS
from paddle_tpu.core.registry import get_op_def
from paddle_tpu.models import transformer
from paddle_tpu.ops import moe_ops

_KINDS = ["full_attention", "sliding_attention", "sliding_attention", "sliding_attention", "full_attention"]
TINY = (dict(hidden_size=32, num_key_value_heads=2, head_dim=16, intermediate_size=64, moe_intermediate_size=16,
             shared_expert_intermediate_size=16, num_experts=8, num_routed_experts=16, num_experts_per_tok=4, vocab_size=96,
             sliding_window=16, num_attention_heads_per_layer=[6, 8, 8, 8, 6], layer_types=_KINDS),
        dict(seq_len=64, batch_per_chip=1, ring=4))
GATE = re.compile(r"(^|/)attention_gate(_\d+)?(/|$)")


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


def in_the_gate(op):
    return bool(GATE.search(getattr(op, "attrs", {}).get("op_namescope") or ""))


def _round(t):
    return jax.lax.reduce_precision(t, 8, 7)


def gate_as(form):
    """The `sigmoid` ops of the `attention_gate` scopes lowered otherwise."""
    def wrong(real, ctx, op, ins):
        if not in_the_gate(op):
            return real(ctx, op, ins)
        if form == "ones":
            return {"Out": jnp.ones_like(ins["X"][0])}
        return {"Out": _round(real(ctx, op, {**ins, "X": [_round(ins["X"][0])]})["Out"])}
    return lambda: (lowered_as("sigmoid", wrong), None)


def neighbours_gate(real, ctx, op, ins):
    """The gate's product with the gate of the head before: Y is (B, L, H, 1) or (B, H, L, 1), the heads' axis the shorter."""
    if not in_the_gate(op):
        return real(ctx, op, ins)
    gate = ins["Y"][0]
    return real(ctx, op, {**ins, "Y": [jnp.roll(gate, 1, axis=1 if gate.shape[1] < gate.shape[2] else 2)]})


def other_grouping(real, ctx, op, ins):
    """Query head j on key/value head j mod Hkv: the kernels group neighbours, so the heads go in at (j mod Hkv) G + j div
    Hkv and the output comes back in their own order."""
    at = 2 if op.attr("layout", "bhld") == "blhd" else 1
    heads, kv_heads = ins["Q"][0].shape[at], ins["K"][0].shape[at]
    group = heads // kv_heads
    place = (np.arange(heads) % kv_heads) * group + np.arange(heads) // kv_heads     # where head j goes
    out = real(ctx, op, {**ins, "Q": [jnp.take(ins["Q"][0], np.argsort(place), axis=at)]})
    return {**out, "Out": jnp.take(out["Out"], place, axis=at)}


def angles_in_bf16(pos, half, theta, by_position, inv_freq=None, scale=1.0):
    """`ops/common.py: rotary_angles` with the angle rounded to bf16's eight bits."""
    inv_freq = theta ** (-np.arange(half, dtype=np.float32) / half) if inv_freq is None else np.asarray(inv_freq, np.float32)
    pos = pos.astype(jnp.float32)
    angle = _round((pos[:, :, None, None] if by_position else pos[:, None, :, None]) * inv_freq)
    return jnp.cos(angle) * np.float32(scale), jnp.sin(angle) * np.float32(scale)


def main(seed: int, only=()):
    cfg = mf.read_json("benchmark/configs/laguna-xs.2.json")
    job = mf.read_json("benchmark/traffic/train-gated-swa-s16384.json")
    if DRY:
        cfg.update(TINY[0])
        job.update(TINY[1])
        lfm2.LOGIT_SAMPLE = lfm2.ATTENTION_SAMPLE = 16

    def built(**over):
        with fluid.unique_name.guard():
            return laguna.build(dict(cfg, **over), job)

    def rope(kind, **over):   # the configuration with one kind's rotary description changed
        stated = cfg["rope_parameters"]
        return dict(rope_parameters={**stated, kind: {**stated[kind], **over}})

    program, startup, _, _, check_names = built()
    program.random_seed = startup.random_seed = seed
    scope = fluid.Scope()
    fluid.Executor(fluid.TPUPlace(0)).run(startup, scope=scope)
    rows = laguna.make_batch(np.random.RandomState(seed % 2**32), cfg, job, CHECK_ROWS)
    params = {p.name: scope.find_var(p.name) for p in program.all_parameters()}
    # nothing trains here: without Adam's moments (8 bytes a parameter) a second clone's program finds room beside the state
    scope.erase([n for n in scope.var_names() if "_moment" in n])
    batch = {k: np.asarray(v) for k, v in rows.items()}

    def reference(handed=None, **kw):   # to the host at once: nothing of it stays on the chip beside a clone
        return [np.asarray(w) for w in jax.jit(lambda p, b: laguna.reference(p, b, cfg, program, **kw))(handed or params, batch)]

    def check_rows(of=None):   # a new executor and a new clone: nothing compiled under another fault is met again
        main_, _, _, _, names = of or (program, None, None, None, check_names)
        got = fluid.Executor(fluid.TPUPlace(0)).run(main_.clone(for_test=True), feed=rows, fetch_list=list(names), scope=scope)
        jax.clear_caches()      # ... and none stays loaded on the chip: a clone's program reserves 11 GB of it
        gc.collect()
        return got

    def report(name, mine, theirs):
        found = laguna.compare(mine, theirs)
        refused = laguna.failed_limits(found)
        print(json.dumps({"control": name, "seed": seed, "correct": not refused, "refused_by": refused, **found}), flush=True)

    def rounded_router(real, ctx, op, ins):
        return real(ctx, op, {**ins, "W": [_round(ins["W"][0])]})

    def again(**over):
        return lambda: (contextlib.nullcontext(), built(**over))

    def own_frequencies(theta, rotary_dim, *_):
        return tuple(float(theta) ** (-np.arange(rotary_dim // 2, dtype=np.float64) / (rotary_dim // 2)))

    def without_the_blend():
        with patched(transformer, "yarn_frequencies", own_frequencies):
            return contextlib.nullcontext(), built()

    window = cfg["sliding_window"]
    full, sliding = cfg["rope_parameters"]["full_attention"], cfg["rope_parameters"]["sliding_attention"]
    exchanged = {**cfg["rope_parameters"], "full_attention": {**full, "rope_theta": sliding["rope_theta"]},
                 "sliding_attention": {**sliding, "rope_theta": full["rope_theta"]}}
    faults = {
        "yarn_blend_left_out": without_the_blend,
        "attention_factor_left_out": again(**rope("full_attention", attention_factor=1.0)),
        "whole_head_turned_in_layer_0": again(**rope("full_attention", partial_rotary_factor=1)),
        "half_head_turned_in_layer_1": again(**rope("sliding_attention", partial_rotary_factor=0.5)),
        "thetas_exchanged": again(rope_parameters=exchanged),
        "angles_in_bf16": lambda: (patched(moe_ops, "rotary_angles", angles_in_bf16), None),
        f"window_of_{window - 1}": again(sliding_window=window - 1),
        f"window_of_{window + 1}": again(sliding_window=window + 1),
        "gate_left_out": gate_as("ones"),
        "gate_in_bf16": gate_as("bf16"),
        "gate_of_head_j_on_head_j_plus_1": lambda: (lowered_as("elementwise_mul", neighbours_gate), None),
        "query_head_j_on_kv_head_j_mod_8": lambda: (lowered_as("fused_attention", other_grouping), None),
        "softmax_for_sigmoid_in_the_router": again(scoring_func="softmax"),
        "factor_2_5_left_out": again(moe_routed_scaling_factor=1.0),
        "router_in_bf16": lambda: (lowered_as("moe_router", rounded_router), None),
    }
    want, sound = reference(), check_rows()
    report("sound", sound, want)
    for name, fault in faults.items():
        if not only or name in only:
            lowering, other = fault()
            with lowering:
                report(name, check_rows(other), want)
    if not only or "shared_expert_left_out" in only:
        first_sparse = laguna._sparse_layers(cfg)[0]
        down = f"lm.l{first_sparse}.moe.shared.down.w"
        report("shared_expert_left_out", sound, reference({**params, down: jnp.zeros_like(params[down])}))
    if not only or "attention_at_default_precision" in only:
        report("attention_at_default_precision", sound, reference(attention_precision="default"))


if __name__ == "__main__":
    main(int(sys.argv[1]) if len(sys.argv) > 1 else 3650000017, tuple(sys.argv[2:]))
