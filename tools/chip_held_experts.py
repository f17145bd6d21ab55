"""On the chip: one layer of `moe_experts` that holds a share of its experts
(ops/moe_ops.py: `_held_experts`), forward + backward, at the two cells that
run it: SDAR's (16384 positions, hidden 2048, 16 of 128 experts of width 768,
8 a token) and LFM2's (16384, 2048, 8 of 32 of width 1792, 4 a token), both
with a bound of 32768 rows.  A seeded router sends 16%, 25%, 50% and 100% of
the bound's rows to held experts, and 53% and 64%, which the cells read, and
the layer is timed with its two row operations

  * in one pass over the bound whatever is live (the form before PR 35),
  * both over the first rows only, as many whole passes of 2048, 4096 or 8192
    rows as hold a live row, one branch of a `lax.switch` a count of passes,
    the gathers with zeros in place of the rest (the module's own, a quarter of
    the bound a pass, is the third of them here),
  * the scatter-adds so and the gathers over the whole bound (here, where the
    tokens' 64 MB stay in VMEM, the faster form: a gather of half the bound
    reads 0.10 ms and the `pad` behind it 0.31, against 0.21 for the gather of
    all; in LFM2's step a gather of the bound reads 0.71 to 1.09 ms and this
    form is 1.3% of the step SLOWER: PERF.md, PR 35),
  * both in passes of 2048 rows under a loop that stops after the last pass
    that holds a live row: a gather writes its pass into a carried buffer, a
    scatter-add adds its pass into the carried sum (what ISSUE 35 asked for
    first; a pass costs twice a row what the one instruction does);

and every form's output and gradients are compared with the first's.

    chiprun -- python3 tools/chip_held_experts.py       (PERF.md, PR 35)

A microbenchmark: a time here is the layer's alone, not the cell's.
"""
import functools
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
from paddle_tpu.ops import moe_ops

DRY = os.environ.get("DRY") == "1"  # a rehearsal on the CPU: interpreted, tiny, no time worth reading
assert DRY or jax.devices()[0].platform == "tpu", jax.devices()
#: cell: tokens, hidden, width, experts, experts a token, experts held
SHAPES = ({"tiny": (256, 32, 16, 16, 4, 4)} if DRY else
          {"sdar": (16384, 2048, 768, 128, 8, 16), "lfm2": (16384, 2048, 1792, 32, 4, 8)})
PASSES = (128, 256) if DRY else (2048, 4096, 8192)
SHARES = (0.16, 0.25, 0.5, 0.53, 0.64, 1.0)   # 0.53 and 0.64: what LFM2's and SDAR's cells read


def one_pass_over_the_bound(n, live, over):
    return over(n)


def _transposes(rows_of_tokens, add_to_tokens):
    """The two as `jax.custom_vjp`s, each the other's transpose, as the module's are."""
    rows_of_tokens, add_to_tokens = (functools.partial(jax.custom_vjp, nondiff_argnums=(4,))(f) for f in (rows_of_tokens, add_to_tokens))
    rows_of_tokens.defvjp(lambda *a: (rows_of_tokens(*a), a[1:4]), lambda tokens, res, g: (add_to_tokens(g, *res, tokens), None, None, None))
    add_to_tokens.defvjp(lambda *a: (add_to_tokens(*a), a[1:4]), lambda tokens, res, g: (rows_of_tokens(g, *res, tokens), None, None, None))
    return rows_of_tokens, add_to_tokens


def gathers_over_the_bound():
    """`_rows_of_tokens` over the whole bound whatever is live, beside the module's `_add_to_tokens`."""
    def rows_of_tokens(x, token, target, live, tokens):
        return moe_ops._take_rows(x, token)

    return _transposes(rows_of_tokens, moe_ops._add_to_tokens.fun)


def looped(rows_a_pass):
    """Both as loops over passes."""
    def passes(n, live, one_pass, carry):
        return jax.lax.fori_loop(0, (live + rows_a_pass - 1) // rows_a_pass, lambda i, c: one_pass(i * rows_a_pass, c), carry)

    def rows_of_tokens(x, token, target, live, tokens):
        def one_pass(lo, out):
            mine = moe_ops._take_rows(x, jax.lax.dynamic_slice(token, (lo,), (rows_a_pass,)))
            return jax.lax.dynamic_update_slice(out, mine, (lo, 0))
        return passes(token.shape[0], live, one_pass, jnp.zeros((token.shape[0], x.shape[-1]), x.dtype))

    def add_to_tokens(rows, token, target, live, tokens):
        def one_pass(lo, out):
            to = jax.lax.dynamic_slice(target, (lo,), (rows_a_pass,))
            return out.at[to].add(jax.lax.dynamic_slice(rows, (lo, 0), (rows_a_pass, rows.shape[-1])), mode="drop")
        return passes(token.shape[0], live, one_pass, jnp.zeros((tokens, rows.shape[-1]), rows.dtype))

    return _transposes(rows_of_tokens, add_to_tokens)


def layer(held):
    op = SimpleNamespace(type="moe_experts", attr=lambda name, default=None: {"held": [0, held]}.get(name, default))
    ctx = LoweringContext(jax.random.PRNGKey(0), platform=None if DRY else "tpu")

    def forward(x, top_p, w_gate, w_up, w_down, top_i, load):
        ins = {"X": [x], "TopKProb": [top_p], "TopKIndex": [top_i], "Load": [load],
               "WGate": [w_gate], "WUp": [w_up], "WDown": [w_down]}
        return get_op_def("moe_experts").lower(ctx, op, ins)["Out"]

    def step(*args):
        out, pull = jax.vjp(lambda *a: forward(*a, *args[5:]), *args[:5])
        return (out,) + pull(jnp.ones_like(out))
    return jax.jit(step)


def operands(shape, seed=0):
    tokens, hidden, width, experts, k, held = shape
    keys = jax.random.split(jax.random.PRNGKey(seed), 5)
    x = jax.random.normal(keys[0], (tokens, hidden), jnp.bfloat16)
    top_p = jax.random.uniform(keys[1], (tokens, k), jnp.float32)
    masters = [0.02 * jax.random.normal(key, s, jnp.float32) for key, s in
               zip(keys[2:], ((held, hidden, width), (held, hidden, width), (held, width, hidden)))]
    return [x, top_p] + masters


def routed(shape, share, seed=1):
    """(TopKIndex, Load) with `share` of the bound's rows on held experts, spread evenly over them."""
    tokens, _, _, experts, k, held = shape
    rng = np.random.RandomState(seed)
    bound = moe_ops._held_rows_bound(tokens * k, held, experts)
    top_i = rng.randint(held, experts, size=tokens * k)
    mine = rng.permutation(tokens * k)[:int(round(share * bound))]
    top_i[mine] = np.arange(mine.size) % held
    load = np.bincount(top_i, minlength=experts)
    return jnp.asarray(top_i.reshape(tokens, k), jnp.int32), jnp.asarray(load, jnp.int32)


def ms(step, *args, runs=5):
    times = []
    for _ in range(runs):
        t = time.perf_counter()
        jax.block_until_ready(step(*args))
        times.append(1e3 * (time.perf_counter() - t))
    return float(np.median(times))


def main():
    own = {name: getattr(moe_ops, name) for name in ("_over_the_live_rows", "_pass_rows", "_rows_of_tokens", "_add_to_tokens")}
    forms = [("one-pass", {"_over_the_live_rows": one_pass_over_the_bound})] \
        + [(f"switch-{p}", {"_pass_rows": lambda n, p=p: min(n, p)}) for p in PASSES] \
        + [(f"switch-{PASSES[0]}-gathers-over-the-bound",
            dict(zip(("_rows_of_tokens", "_add_to_tokens"), gathers_over_the_bound()), _pass_rows=lambda n: min(n, PASSES[0]))),
           (f"loop-{PASSES[0]}", dict(zip(("_rows_of_tokens", "_add_to_tokens"), looped(PASSES[0])))), ("module", {})]
    for cell, shape in SHAPES.items():
        args = operands(shape)
        routings = {share: routed(shape, share) for share in SHARES}
        first = {}
        for name, form in forms:
            for attr, value in {**own, **form}.items():
                setattr(moe_ops, attr, value)
            step = layer(shape[-1])
            t = time.perf_counter()
            found = {share: jax.block_until_ready(step(*args, *routing)) for share, routing in routings.items()}
            line = {"cell": cell, "form": name, "compile_and_first_s": round(time.perf_counter() - t, 1)}
            if not first:
                first = found
            # the largest difference from the first form, over the output and the five gradients, a share of the largest value
            line["differs"] = {str(share): max(float(jnp.max(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32)))
                                                     / (jnp.max(jnp.abs(b.astype(jnp.float32))) + 1e-30))
                                               for a, b in zip(found[share], first[share])) for share in SHARES}
            line["ms_by_live_share"] = {str(share): round(ms(step, *args, *routing), 3) for share, routing in routings.items()}
            print(json.dumps(line), flush=True)
    for attr, value in own.items():
        setattr(moe_ops, attr, value)


if __name__ == "__main__":
    main()
