"""On the chip: the routed experts' way back to token order (ops/moe_ops.py:
`_sum_by_token`; ops/moe_kernels.py: `token_sum`) at OLMoE's cell: 16384 tokens,
hidden 2048, 64 experts of width 1024, 8 a token, bf16 rows.  Under a uniform
router and under the cell's skew (the busiest expert 4.42 x the mean: ledger,
PR 48):

  * the op alone, `[131072, 2048]` rows to `[16384, 2048]` tokens: XLA's form (a
    gather that writes `[T, k, d]` and a sum that reads it) and the kernel at
    each `BLOCKS` = tokens:chunk pair, the module's own last;
  * one whole-layer `moe_experts` forward and through `jax.vjp` (the step's two
    calls: forward's, and the transpose of `_rows_by_expert`), XLA's form and
    the module's kernel;

every form's result compared with XLA's, as a share of the largest value.

    chiprun -- python3 tools/chip_token_sum.py       (PERF.md, PR 49)
    DRY=1 python3 tools/chip_token_sum.py            (a rehearsal here: tiny, interpreted)

A microbenchmark: a time here is the op's or the layer's alone, not the cell's.
"""
import json
import os
import sys
import time
from types import SimpleNamespace

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from paddle_tpu.core.lowering import LoweringContext
from paddle_tpu.core.registry import get_op_def
from paddle_tpu.ops import moe_kernels, moe_ops

DRY = os.environ.get("DRY") == "1"  # a rehearsal on the CPU: interpreted, tiny, no time worth reading
assert DRY or jax.devices()[0].platform == "tpu", jax.devices()
#: tokens, hidden, width, experts, experts a token
SHAPE = (512, 128, 64, 8, 2) if DRY else (16384, 2048, 1024, 64, 8)
OWN = (moe_kernels.TOKENS, moe_kernels.CHUNK)
BLOCKS = [tuple(int(n) for n in pair.split(":")) for pair in os.environ["BLOCKS"].split(",")] if os.environ.get("BLOCKS") \
    else [OWN] if DRY else [(64, 256), (128, 512), (256, 256), (256, 512), OWN]
#: the busiest expert's rows over the mean: a uniform router, and what the cell reads
ROUTERS = {"uniform": 1.0, "skewed": 4.42}


def routed(skew, seed=1):
    """TopKIndex [T, k], k distinct experts a token, expert 0 chosen by `skew` / k of the tokens."""
    tokens, _, _, experts, k = SHAPE
    rng = np.random.RandomState(seed)
    scores = rng.rand(tokens, experts)
    if skew > 1.0:
        scores[:, 0] = np.where(rng.rand(tokens) < skew * k / experts, 2.0, -1.0)
    return jnp.asarray(np.argsort(-scores, axis=1)[:, :k], jnp.int32)


def route_of(top_i):
    k = top_i.shape[1]
    order = jnp.argsort(top_i.reshape(-1), stable=True).astype(jnp.int32)
    return order, jnp.argsort(order).astype(jnp.int32), top_i.reshape(-1, k)


def ms(step, *args, runs=5, calls=10):
    """The median over `runs` of the time a call of `calls` back to back."""
    jax.block_until_ready(step(*args))
    times = []
    for _ in range(runs):
        t = time.perf_counter()
        out = [step(*args) for _ in range(calls)]
        jax.block_until_ready(out)
        times.append(1e3 * (time.perf_counter() - t) / calls)
    return round(float(np.median(times)), 3)


def differs(found, wanted):
    """The largest difference over the arrays, a share of the largest value."""
    return max(float(jnp.max(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32))) / (jnp.max(jnp.abs(b.astype(jnp.float32))) + 1e-30))
               for a, b in zip(jax.tree.leaves(found), jax.tree.leaves(wanted)))


def the_op_alone(top_i):
    tokens, hidden, _, experts, k = SHAPE
    rows = jax.random.normal(jax.random.PRNGKey(0), (tokens * k, hidden), jnp.bfloat16)
    route = route_of(top_i)
    xla = jax.jit(lambda rows: moe_ops._sum_by_token(rows, route, k))
    wanted = xla(rows)
    yield {"form": "xla", "ms": ms(xla, rows)}
    for block, chunk in BLOCKS:
        kernel = jax.jit(lambda rows: moe_kernels.token_sum(rows, route[1].reshape(-1, k), route[2], experts, DRY, block, chunk))
        t = time.perf_counter()
        found = jax.block_until_ready(kernel(rows))
        yield {"form": f"kernel-{block}:{chunk}", "compile_and_first_s": round(time.perf_counter() - t, 1),
               "differs": differs(found, wanted), "ms": ms(kernel, rows)}


def layer(path):
    """One whole-layer `moe_experts`, (forward, forward and the five gradients), its way back lowered by `path`."""
    op = SimpleNamespace(type="moe_experts", attr=lambda name, default=None: default)
    ctx = LoweringContext(jax.random.PRNGKey(0), platform=None if DRY else "tpu")
    own = moe_ops._token_sum_path

    def forward(x, top_p, w_gate, w_up, w_down, top_i, load):
        ins = {"X": [x], "TopKProb": [top_p], "TopKIndex": [top_i], "Load": [load],
               "WGate": [w_gate], "WUp": [w_up], "WDown": [w_down]}
        moe_ops._token_sum_path = lambda *a: path   # read as the layer is traced
        try:
            return get_op_def("moe_experts").lower(ctx, op, ins)["Out"]
        finally:
            moe_ops._token_sum_path = own

    def step(*args):
        out, pull = jax.vjp(lambda *a: forward(*a, *args[5:]), *args[:5])
        return (out,) + pull(jnp.ones_like(out))
    return jax.jit(forward), jax.jit(step)


def the_layer(top_i):
    tokens, hidden, width, experts, k = SHAPE
    keys = jax.random.split(jax.random.PRNGKey(0), 5)
    args = [jax.random.normal(keys[0], (tokens, hidden), jnp.bfloat16), jax.random.uniform(keys[1], (tokens, k), jnp.float32)] \
        + [0.02 * jax.random.normal(key, s, jnp.float32) for key, s in
           zip(keys[2:], ((experts, hidden, width), (experts, hidden, width), (experts, width, hidden)))] \
        + [top_i, jnp.bincount(top_i.reshape(-1), length=experts).astype(jnp.int32)]
    wanted = None
    for form, path in (("xla", "xla"), ("kernel", "interpret" if DRY else "kernel")):
        forward, step = layer(path)
        found = jax.block_until_ready(step(*args))
        wanted = found if wanted is None else wanted
        # one call a timing: ten calls' gradients (2.4 GB a call) do not fit beside each other
        yield {"form": form, "differs": differs(found, wanted), "forward_ms": ms(forward, *args), "forward_and_backward_ms": ms(step, *args, calls=1)}


def main():
    for router, skew in ROUTERS.items():
        top_i = routed(skew)
        load = np.bincount(np.asarray(top_i).reshape(-1), minlength=SHAPE[3])
        about = {"router": router, "load_max_over_mean": round(float(load.max() / load.mean()), 2)}
        for what, lines in (("op", the_op_alone), ("layer", the_layer)):
            for line in lines(top_i):
                print(json.dumps({**about, "what": what, **line}), flush=True)


if __name__ == "__main__":
    main()
