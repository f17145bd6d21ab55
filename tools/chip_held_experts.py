"""On the chip: one layer of `moe_experts` that holds a share of its experts
(ops/moe_ops.py: `_held_experts`), forward + backward, at the three cells that
run it: SDAR's (16384 positions, hidden 2048, 16 of 128 experts of width 768,
8 a token) and LFM2's (16384, 2048, 8 of 32 of width 1792, 4 a token), both
with a bound of 32768 rows, and Kimi Linear's (4096, 2304, 8 of 256 of width
1024, 8 a token: a bound of 2048).

**The way back to token order** (PR 53; what runs unless `ONLY=forms`): a seeded
router sends half and all of the bound's rows to held experts (and 53% and 64%,
which LFM2's and SDAR's cells read), and `_add_to_tokens` is timed ALONE, rows
[bound, hidden] to tokens, the rows past the live ones NaN as the grouped
kernels may leave them:

  * `xla`: the scatter-add over the live rows' passes, one branch of a
    `lax.switch` a count of passes (the CPU's form, a mesh's, the rare path's);
  * `kernel`: `ops/moe_kernels.py: token_sum` with the slots no held expert
    owns left out, as the module calls it: the eight rows of the tile that
    holds the first dead row are zeroed in place first (`_zeros_from`);
  * `kernel-where-over-the-rows`: every dead row zeroed by a `where`, a pass
    over the bound (the caller's answer as ISSUE 53 put it);
  * `kernel-nothing-zeroed`: given zeros for NaN: what no answer inside the
    kernel could beat, for it does nothing at all;

then the whole layer forward and through `jax.vjp` with the way back in each
of the four forms, and every result compared with XLA's.  Alone, the eight rows
written in place cost a COPY of the rows (XLA does not write a jit's parameter
in place: 0.41 ms of 134 MB each way at SDAR's shape, as dear as the `where`);
in the layer, where the rows are the grouped kernel's output, they are written
into it, so the layer's lines are the ones that price the answers (`differs`
may read NaN for `kernel-nothing-zeroed` there: that is what it risks).

**The forms of the two row operations** (PR 35; `ONLY=forms`, XLA's way back): a
seeded router sends 16%, 25%, 50% and 100% of the bound's rows to held experts,
and 53% and 64%, and SDAR's and LFM2's layers are timed with the two row
operations

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

    chiprun -- python3 tools/chip_held_experts.py                    (PERF.md, PR 53; ~4 min)
    chiprun -- env ONLY=forms python3 tools/chip_held_experts.py     (PERF.md, PR 35; ~7 min)
    DRY=1 python3 tools/chip_held_experts.py                         (a rehearsal here: tiny, interpreted)

A microbenchmark: a time here is the layer's alone, not the cell's.
"""
import contextlib
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
FORMS = os.environ.get("ONLY") == "forms"
#: cell: tokens, hidden, width, experts, experts a token, experts held
SHAPES = ({"tiny": (256, 128, 16, 16, 4, 4)} if DRY else
          {"sdar": (16384, 2048, 768, 128, 8, 16), "lfm2": (16384, 2048, 1792, 32, 4, 8), "kimi": (4096, 2304, 1024, 256, 8, 8)})
PASSES = (128, 256) if DRY else (2048, 4096, 8192)
#: 0.53 and 0.64: what LFM2's and SDAR's cells read
SHARES = (0.16, 0.25, 0.5, 0.53, 0.64, 1.0) if FORMS else (0.5, 0.53, 0.64, 1.0)


@contextlib.contextmanager
def standing_in(name, value):
    """`moe_ops.<name>` is `value` while something is traced: the module reads its own names as it is traced."""
    own = getattr(moe_ops, name)
    setattr(moe_ops, name, value)
    try:
        yield
    finally:
        setattr(moe_ops, name, own)


def one_pass_over_the_bound(n, live, over):
    return over(n)


def _transposes(rows_of_tokens, add_to_tokens):
    """The two as `jax.custom_vjp`s, each the other's transpose, as the module's are."""
    rows_of_tokens, add_to_tokens = (functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))(f) for f in (rows_of_tokens, add_to_tokens))
    rows_of_tokens.defvjp(lambda *a: (rows_of_tokens(*a), a[1:4]), lambda tokens, kernel, res, g: (add_to_tokens(g, *res, tokens), None, None, None))
    add_to_tokens.defvjp(lambda *a: (add_to_tokens(*a), a[1:4]), lambda tokens, kernel, res, g: (rows_of_tokens(g, *res, tokens), None, None, None))
    return rows_of_tokens, add_to_tokens


def gathers_over_the_bound():
    """`_rows_of_tokens` over the whole bound whatever is live, beside the module's `_add_to_tokens`."""
    def rows_of_tokens(x, token, target, live, tokens, kernel=None):
        return moe_ops._take_rows(x, token)

    return _transposes(rows_of_tokens, moe_ops._add_to_tokens.fun)


def looped(rows_a_pass):
    """Both as loops over passes."""
    def passes(n, live, one_pass, carry):
        return jax.lax.fori_loop(0, (live + rows_a_pass - 1) // rows_a_pass, lambda i, c: one_pass(i * rows_a_pass, c), carry)

    def rows_of_tokens(x, token, target, live, tokens, kernel=None):
        def one_pass(lo, out):
            mine = moe_ops._take_rows(x, jax.lax.dynamic_slice(token, (lo,), (rows_a_pass,)))
            return jax.lax.dynamic_update_slice(out, mine, (lo, 0))
        return passes(token.shape[0], live, one_pass, jnp.zeros((token.shape[0], x.shape[-1]), x.dtype))

    def add_to_tokens(rows, token, target, live, tokens, kernel=None):
        def one_pass(lo, out):
            to = jax.lax.dynamic_slice(target, (lo,), (rows_a_pass,))
            return out.at[to].add(jax.lax.dynamic_slice(rows, (lo, 0), (rows_a_pass, rows.shape[-1])), mode="drop")
        return passes(token.shape[0], live, one_pass, jnp.zeros((tokens, rows.shape[-1]), rows.dtype))

    return _transposes(rows_of_tokens, add_to_tokens)


def layer(held, path="xla"):
    """One layer that holds `held` experts, forward and the five gradients, its way back lowered by `path`."""
    op = SimpleNamespace(type="moe_experts", attr=lambda name, default=None: {"held": [0, held]}.get(name, default))
    ctx = LoweringContext(jax.random.PRNGKey(0), platform=None if DRY else "tpu")

    def forward(x, top_p, w_gate, w_up, w_down, top_i, load):
        ins = {"X": [x], "TopKProb": [top_p], "TopKIndex": [top_i], "Load": [load],
               "WGate": [w_gate], "WUp": [w_up], "WDown": [w_down]}
        with standing_in("_token_sum_path", lambda *a: path):
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


def ms(step, *args, runs=5, calls=1):
    """The median over `runs` of the time a call of `calls` back to back."""
    times = []
    for _ in range(runs):
        t = time.perf_counter()
        jax.block_until_ready([step(*args) for _ in range(calls)])
        times.append(1e3 * (time.perf_counter() - t) / calls)
    return float(np.median(times))


def differs(found, wanted):
    """The largest difference over the arrays, a share of the largest value (NaN where either holds one)."""
    return max(float(jnp.max(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32))) / (jnp.max(jnp.abs(b.astype(jnp.float32))) + 1e-30))
               for a, b in zip(jax.tree.leaves(found), jax.tree.leaves(wanted)))


def way_back_operands(shape, top_i, load):
    """What `_held_experts`' common pass hands `_add_to_tokens`: (rows [bound, hidden] with NaN past the live ones, the
    same with zeros there, token, XLA's target, the kernel's, live)."""
    tokens, hidden, _, experts, k, held = shape
    bound = moe_ops._held_rows_bound(tokens * k, held, experts)
    local = jnp.where(top_i.reshape(-1) < held, top_i.reshape(-1), held)
    order, _ = moe_ops._sort_by_key(local, jnp.zeros(local.shape, jnp.float32))
    place = jnp.argsort(order).astype(jnp.int32).reshape(tokens, k)
    live = jnp.minimum(jnp.sum(load[:held]), bound)
    is_live = jnp.arange(bound) < live
    token = jnp.minimum(order[:bound] // k, tokens - 1)
    owned = place < live
    zeros_past = jnp.where(is_live[:, None], jax.random.normal(jax.random.PRNGKey(0), (bound, hidden), jnp.bfloat16), 0)
    return (jnp.where(is_live[:, None], zeros_past, jnp.nan), zeros_past, token, jnp.where(is_live, token, tokens),
            (jnp.where(owned, place, -1), jnp.where(owned, local.reshape(tokens, k), held)), live)


#: form -> what stands in for `moe_ops._zeros_from` while the form is traced
ZEROED = {"kernel": moe_ops._zeros_from, "kernel-nothing-zeroed": lambda rows, live: rows,
          "kernel-where-over-the-rows": lambda rows, live: jnp.where(jnp.arange(rows.shape[0])[:, None] < live, rows, 0)}


def the_way_back_alone(shape, routing):
    """`_add_to_tokens` alone, a line a form."""
    tokens, held = shape[0], shape[-1]
    nan_past, zeros_past, token, xla_target, kernel_target, live = way_back_operands(shape, *routing)
    kernel = (held, DRY)
    xla = jax.jit(lambda rows, live: moe_ops._add_to_tokens(rows, token, xla_target, live, tokens))
    wanted = xla(nan_past, live)
    yield {"form": "xla", "ms": round(ms(xla, nan_past, live, calls=10), 3)}
    for form, zeros_from in ZEROED.items():
        call = jax.jit(lambda rows, live: moe_ops._add_to_tokens(rows, token, kernel_target, live, tokens, kernel))
        rows = zeros_past if form == "kernel-nothing-zeroed" else nan_past
        with standing_in("_zeros_from", zeros_from):
            found = jax.block_until_ready(call(rows, live))
        yield {"form": form, "differs": differs(found, wanted), "ms": round(ms(call, rows, live, calls=10), 3)}


def the_way_back():
    for cell, shape in SHAPES.items():
        args, steps, wanted = operands(shape), {}, {}
        for share in SHARES:
            routing = routed(shape, share)
            about = {"cell": cell, "live_share_of_the_bound": share}
            for line in the_way_back_alone(shape, routing):
                print(json.dumps({**about, "what": "op", **line}), flush=True)
            for form in ["xla"] + list(ZEROED):
                if form not in steps:
                    steps[form] = layer(shape[-1], "xla" if form == "xla" else "interpret" if DRY else "kernel")
                step = steps[form]
                with standing_in("_zeros_from", ZEROED.get(form, moe_ops._zeros_from)):
                    found = jax.block_until_ready(step(*args, *routing))
                wanted.setdefault(share, found)
                print(json.dumps({**about, "what": "layer", "form": form, "differs": differs(found, wanted[share]),
                                  "forward_and_backward_ms": round(ms(step, *args, *routing), 3)}), flush=True)


def main():
    if not FORMS:
        return the_way_back()
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
            line["differs"] = {str(share): differs(found[share], first[share]) for share in SHARES}   # from the first form
            line["ms_by_live_share"] = {str(share): round(ms(step, *args, *routing), 3) for share, routing in routings.items()}
            print(json.dumps(line), flush=True)
    for attr, value in own.items():
        setattr(moe_ops, attr, value)


if __name__ == "__main__":
    main()
