"""SmallThinker's cell on the chip, what its comparison can and cannot tell: the
harness's own `benchmark.models.smallthinker.compare` / `failed_limits` on the
program's check rows against the float32 reference, sound and then with a
fault put in, one at a time, so that each limit this PR brings has a reading it
must refuse beside the sound one (PERF.md, section 6, PR 63).  Eight faults go
into THE PROGRAM (built again with the fault, or an op's registered lowering
wrapped, and the check rows run through a new executor on the SOUND program's
parameters: the names are the same), one is the reference a precision lower:

  * `router_in_bf16`: the router's float32 matrix rounded to bf16 before the
    logits' product: `ROUTER_RTOL`;
  * `router_reads_the_normed_input`: the router handed rms(x; ln1), what the
    attention reads, for the layer's input x (the norm's gains are 1, so a
    token's logits are scaled alike: mostly the same six at other weights, 1.13
    off; 546 tokens' six differ through the norm's bf16 rounding): `ROUTER_RTOL`,
    `ROUTER_TIE`;
  * `router_reads_the_post_attention_stream`: the router handed h = x + attention,
    what the experts' norm reads, where every other sparse layer of the
    framework routes: `ROUTER_TIE` (other experts), `ROUTER_RTOL`;
  * `rotation_in_layer_0`: `rope_layout` [1, 1, 1, 1], the full layer rotary
    too: `QK_RTOL`;
  * `no_rotation_in_layer_1`: `rope_layout` [0, 0, 1, 1]: `QK_RTOL`;
  * `window_of_4095`, `window_of_4097`: one key fewer, one more:
    `WINDOW_EDGE_MAX` (the stage's rule is made from positions here, and the
    error is measured along what each fault would add: one weight of 4096 is
    of the size of the output's own rounding, which `ATTENTION_RTOL` cannot tell);
  * `silu_for_relu`: the experts' activation: `EXPERTS_RTOL`;
  * `attention_at_default_precision`: the reference's two attention products at
    the chip's default precision (bf16 operands), the nearest precision below
    the one the reference states: `REFERENCE_SELF_RTOL`, the reference's own
    first attention against float64 (end to end nothing tells it: the program
    rounds as much itself).

    chiprun --timeout 3000 -- python3 tools/chip_smallthinker_controls.py 3630000017      (PERF.md, PR 63)

Names after the seed run those controls alone, beside `sound`.
`DRY=1` rehearses it tiny on the CPU; no number of that means anything.
"""
import contextlib
import json
import os
import sys
from types import SimpleNamespace

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

DRY = os.environ.get("DRY") == "1"

import jax
import numpy as np

import paddle_tpu as fluid
from benchmark import manifest as mf
from benchmark.models import lfm2, smallthinker
from benchmark.runners.train import CHECK_ROWS
from paddle_tpu import layers
from paddle_tpu.core.registry import get_op_def

TINY = (dict(hidden_size=32, num_attention_heads=4, num_key_value_heads=2, head_dim=16, moe_ffn_hidden_size=16,
             moe_num_primary_experts=4, num_routed_experts=16, moe_num_active_primary_experts=4, vocab_size=64,
             sliding_window_size=16),
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
def router_handed(which):
    """`layers.moe` handing its router the normed input or the post-attention stream for the layer's input."""
    real = layers.moe

    def wrong(input, *a, router_input=None, **kw):
        block = fluid.default_main_program().global_block()
        if which == "normed":      # what the layer's input norm made of the layer's input
            name = next(op for op in block.ops if op.type == "rms_norm" and op.inputs["X"] == [router_input.name]).outputs["Y"][0]
        else:                      # what the experts' norm reads
            name = next(op for op in block.ops if input.name in op.output_arg_names).inputs["X"][0]
        return real(input, *a, router_input=block.var(name), **kw)

    layers.moe = wrong
    try:
        yield
    finally:
        layers.moe = real


def with_attrs(op, **attrs):
    return SimpleNamespace(type=op.type, attr=lambda n, d=None: attrs.get(n, op.attr(n, d)), input=op.input, output=op.output,
                           inputs=op.inputs, outputs=op.outputs, attrs={**op.attrs, **attrs})


def main(seed: int, only=()):
    cfg = mf.read_json("benchmark/configs/smallthinker-21b-a3b.json")
    job = mf.read_json("benchmark/traffic/train-nope-swa-s16384.json")
    if DRY:
        cfg.update(TINY[0])
        job.update(TINY[1])
        lfm2.LOGIT_SAMPLE = lfm2.ATTENTION_SAMPLE = 16
    def built(**over):
        with fluid.unique_name.guard():
            return smallthinker.build(dict(cfg, **over), job)

    program, startup, _, _, check_names = built()
    program.random_seed = startup.random_seed = seed
    scope = fluid.Scope()
    fluid.Executor(fluid.TPUPlace(0)).run(startup, scope=scope)
    rows = smallthinker.make_batch(np.random.RandomState(seed % 2**32), cfg, job, CHECK_ROWS)
    params = {p.name: scope.find_var(p.name) for p in program.all_parameters()}
    batch = {k: np.asarray(v) for k, v in rows.items()}

    def reference(**kw):   # to the host at once: its float32 copies of the experts do not stay on the chip beside a clone
        return [np.asarray(w) for w in jax.jit(lambda p, b: smallthinker.reference(p, b, cfg, program, **kw))(params, batch)]

    def check_rows(of=None):   # a new executor and a new clone: nothing compiled under another fault is met again
        main_, _, _, _, names = of or (program, None, None, None, check_names)
        return fluid.Executor(fluid.TPUPlace(0)).run(main_.clone(for_test=True), feed=rows, fetch_list=list(names), scope=scope)

    def report(name, mine, theirs):
        found = smallthinker.compare(mine, theirs)
        refused = smallthinker.failed_limits(found)
        print(json.dumps({"control": name, "seed": seed, "correct": not refused, "refused_by": refused, **found}), flush=True)

    def rounded_router(real, ctx, op, ins):
        return real(ctx, op, {**ins, "W": [jax.lax.reduce_precision(ins["W"][0], 8, 7)]})

    def silu(real, ctx, op, ins):
        return real(ctx, with_attrs(op, activation="silu"), ins)

    def handed(which):
        with router_handed(which):
            return built()

    window = cfg["sliding_window_size"]
    faults = {
        "router_in_bf16": lambda: (lowered_as("moe_router", rounded_router), None),
        "router_reads_the_normed_input": lambda: (contextlib.nullcontext(), handed("normed")),
        "router_reads_the_post_attention_stream": lambda: (contextlib.nullcontext(), handed("post_attention")),
        "rotation_in_layer_0": lambda: (contextlib.nullcontext(), built(rope_layout=[1, 1, 1, 1])),
        "no_rotation_in_layer_1": lambda: (contextlib.nullcontext(), built(rope_layout=[0, 0, 1, 1])),
        f"window_of_{window - 1}": lambda: (contextlib.nullcontext(), built(sliding_window_size=window - 1)),
        f"window_of_{window + 1}": lambda: (contextlib.nullcontext(), built(sliding_window_size=window + 1)),
        "silu_for_relu": lambda: (lowered_as("moe_experts", silu), None),
    }
    want, sound = reference(), check_rows()
    report("sound", sound, want)
    for name, fault in faults.items():
        if not only or name in only:
            lowering, other = fault()
            with lowering:
                report(name, check_rows(other), want)
    if not only or "attention_at_default_precision" in only:
        report("attention_at_default_precision", sound, reference(attention_precision="default"))


if __name__ == "__main__":
    main(int(sys.argv[1]) if len(sys.argv) > 1 else 3630000017, tuple(sys.argv[2:]))
