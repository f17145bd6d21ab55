"""On the chip: the block-sparse attention of `fused_attention` under the
block-diffusion mask (ops/masked_attention.py) priced at SDAR's cell:
(2, 32, 8192, 128) queries over (2, 4, 8192, 128) keys and values, bf16, block
length 4, forward + backward: the whole square through the stock kernels
against the clean keys through them and the own-block term through its own
(what `block_sparse_attention` takes from the rule's block), at rule's blocks
of 4, 16, 128 and 256, with the two forms' largest difference in the output
and the three gradients (QUICK=1 stops there; PERF.md, PR 33); then, over the
whole square (PR 32), by the grid's block; the same mask computed in the
kernel from positions instead of read from its distinct cut blocks; the
backward pass as one kernel instead of two.  Before all that (CAUSAL=1 stops
after it; PR 37) the CAUSAL attentions of OLMoE's cell, (4, 16, 4096, 128), and
LFM2's, (2, 32 on 8, 8192, 64): the stock flash kernel as `fused_attention`
calls it (k and v repeated at its edge) against `causal_attention`, and the
stock splash kernel by the grid's block, with the cut blocks stored or
computed in the kernel, dq and dkv apart or the fused backward.

    chiprun -- python3 tools/chip_block_attention.py       (PERF.md, PRs 32, 33 and 37)

WINDOW=1 prices the SLIDING-WINDOW rule alone and stops (PR 50): Phi-4-mini-flash's
window layer, (1, 40 on 20, 8192, 64) under a window of 512, `window_attention`
as `fused_attention` calls it against `causal_attention` over the same
operands (8x the pairs), the stock splash kernel under the stock local mask by
the grid's block, dq and dkv apart (grids shrunk to the band) or the fused
backward (its dkv grid unshrunk), and at 2048 positions the taken form's output
and gradients against dense float32.  WINDOW=4096 (PR 63) is the same at
SmallThinker's window layer, (1, 28 on 4, 16384, 128) under a window of 4096:
the op's own call at a block of 512 and of 1024 first, then the stock kernel by
block, and the taken form against dense float32 at 6144 positions.  WINDOW=512x128
(PR 65) is the same at Laguna-XS.2's window layer, (1, 64 on 8, 16384, 128)
under a window of 512 (128-wide heads in groups of eight, a band 1/32 of the
sequence), and its full layer's causal rule at (1, 48 on 8, 16384, 128), groups
of SIX: the one backward kernel with a group's dk and dv summed in VMEM against
outside, beside the stock fused backward.

STORED=1 (PR 68) prices the ONE backward kernel under the two rules whose mask
is STORED and stops: block diffusion's far + near at SDAR's (2, 32 on 4, 8192
queries against the 4096 clean keys, 128) and the selected rule under the
causal one at Keye-VL-2.0's (1, 32 on 4, 16384, 128) with about 2048 picks a
query; forward + backward of a layer alone, ours against the stock dq and dkv
pair, at grid blocks of 1024 and 512, with a byte or an int32 a pair of the
mask, a group's dk and dv summed in VMEM or outside, and ours against the pair
in dq, dk and dv.

A microbenchmark: a time here is a kernel's alone, not the cell's.
"""
import contextlib
import json
import os
import sys
import time
from unittest import mock

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental.pallas.ops.tpu.splash_attention import splash_attention_kernel as splash
from jax.experimental.pallas.ops.tpu.splash_attention import splash_attention_mask as mask_lib

from paddle_tpu.ops import attention_backward_kernels as onchip_kernels
from paddle_tpu.ops import masked_attention as ma
from tests.test_pallas_attention import flash_causal

DRY = os.environ.get("DRY") == "1"  # a rehearsal on the CPU: interpreted, tiny, no time printed
assert DRY or jax.devices()[0].platform == "tpu", jax.devices()
POSITIONS, BLOCK = (512, 4) if DRY else (8192, 4)
Q, KV = ((1, 4, POSITIONS, 128), (1, 2, POSITIONS, 128)) if DRY else ((2, 32, 8192, 128), (2, 4, 8192, 128))


def gradients(fn):
    return jax.jit(jax.grad(lambda *a: jnp.sum(fn(*a).astype(jnp.float32)), argnums=(0, 1, 2)))


def patched(fn):
    """`fn.patches` entered: what a form patches has to hold while its BACKWARD rule is traced too, which `jax.grad` does
    after the forward has returned (a patch inside the forward alone never reached `Plan.backward` or `kv_rows_fit`)."""
    stack = contextlib.ExitStack()
    for patch in getattr(fn, "patches", ()):
        stack.enter_context(patch)
    return stack


def ms(fn, *args, runs=5):
    """Forward + backward of sum(fn), the median of `runs` after one that compiles."""
    step = gradients(fn)
    with patched(fn):
        jax.block_until_ready(step(*args))
    times = []
    for _ in range(runs):
        t = time.perf_counter()
        jax.block_until_ready(step(*args))
        times.append(1e3 * (time.perf_counter() - t))
    return float(np.median(times))


def operands(q_shape, kv_shape, seed=0, v_width=None):
    keys = jax.random.split(jax.random.PRNGKey(seed), 3)
    v_shape = kv_shape[:-1] + (v_width or kv_shape[-1],)
    return [jax.random.normal(k, s, jnp.bfloat16) for k, s in zip(keys, (q_shape, kv_shape, v_shape))]


def onchip(plan, kv_rows=None):
    """`attention_under(plan, ...)` as the op calls it; `kv_rows` False: dk and dv
    a query head in float32 and a group's summed outside, what the one backward
    kernel (ops/attention_backward_kernels.py) falls back to where a key/value
    head's whole rows do not fit its VMEM (None: as the kernel takes it from
    the shapes)."""
    def attend(q, k, v):
        return ma.attention_under(plan, q, k, v, q.shape[-1] ** -0.5)
    attend.patches = [mock.patch.object(onchip_kernels, "kv_rows_fit", lambda *a: kv_rows)] if kv_rows is not None else []
    onchip_kernels.backward.clear_cache()      # the kernel's call is a `jax.jit` of its own: a trace under another patch is not this one's
    return attend


def splash_with(mask, heads, sizes):
    kernel = splash.make_splash_mha(mask_lib.MultiHeadMask([mask] * heads), block_sizes=sizes,
                                    head_shards=1, q_seq_shards=1, interpret=DRY)
    return lambda q, k, v: jax.vmap(kernel)(q * (q.shape[-1] ** -0.5), k, v)


def report(what, **fields):
    print(json.dumps({"what": what, "device": jax.devices()[0].device_kind, **fields}), flush=True)


def try_ms(fn, *args):
    try:
        took = ms(fn, *args)
        return None if DRY else took
    except Exception as e:  # a block that overruns the scoped VMEM is a finding, not a failure
        return f"{type(e).__name__}: {str(e)[:200]}"


def sizes_of(bq, bkv, compute=None, fused=False):
    backward = {} if fused else dict(block_q_dq=bq, block_kv_dq=bkv)
    return splash.BlockSizes(block_q=bq, block_kv=bkv, block_kv_compute=compute or bkv, block_q_dkv=bq, block_kv_dkv=bkv,
                             block_kv_dkv_compute=compute or bkv, use_fused_bwd_kernel=fused, **backward)


q, k, v = operands(Q, KV)
scale = Q[-1] ** -0.5


class Computed(mask_lib._ComputableMask):
    """The rule computed inside the kernel from the positions, for its price."""

    def __init__(self):
        super().__init__(shape=(POSITIONS, POSITIONS),
                         mask_function=lambda q, kv: ma.block_diffusion_allowed(q, kv, POSITIONS // 2, BLOCK))

    def __eq__(self, other):
        return isinstance(other, type(self))

    def __hash__(self):
        return hash(type(self).__name__)


def apart(got, want):
    """Largest difference over the largest magnitude, in float32."""
    got, want = got.astype(jnp.float32), want.astype(jnp.float32)
    return float(jnp.abs(got - want).max() / jnp.abs(want).max())


class StoredCausal(mask_lib.Mask):
    """The causal rule as a mask whose cut blocks are STORED, for its price:
    `causal_plan` takes the stock `CausalMask`, which the kernels compute."""

    def __init__(self, length):
        self.length = length

    @property
    def shape(self):
        return (self.length, self.length)

    def __getitem__(self, idx):
        q, kv = (np.arange(s.start or 0, n if s.stop is None else s.stop) for s, n in zip(idx, self.shape))
        return ma.causal_allowed(q[:, None], kv[None, :])

    def __eq__(self, other):
        return isinstance(other, type(self)) and self.shape == other.shape

    def __hash__(self):
        return hash((type(self).__name__, self.shape))


if os.environ.get("WIDE") == "256":
    # Qwen3-Next's full layer (PR 69): the causal rule at 16 query heads on 2 key/value heads of 256, 16384 keys: the widest
    # head the kernels have run.  A head's float32 dq and its output block hold 32 MiB of the backward kernel's VMEM, a
    # key/value head's dk and dv rows would hold 64 more (`kv_rows_fit` says no): dk and dv of a group of EIGHT query heads
    # go out in float32 a query head and are summed outside.  The op's own call; the grid's block at 256, 512 and 1024 with
    # dk and dv outside and (refused where it overruns the VMEM) in it; the stock splash pair and the stock fused backward.
    fq, fkv = ((1, 8, 512, 256), (1, 2, 512, 256)) if DRY else ((1, 16, 16384, 256), (1, 2, 16384, 256))
    fqkv = operands(fq, fkv, seed=5)
    length, group = fq[2], fq[1] // fkv[1]
    causal = lambda q, k, v: ma.causal_attention(q, k, v, q.shape[-1] ** -0.5, interpret=DRY)  # noqa: E731
    full = ma.causal_plan(length, fq[1], DRY, (fq[-1], fkv[-1]))

    def forward_ms(fn, runs=5):
        step = jax.jit(fn)
        jax.block_until_ready(step(*fqkv))
        times = []
        for _ in range(runs):
            t = time.perf_counter()
            jax.block_until_ready(step(*fqkv))
            times.append(1e3 * (time.perf_counter() - t))
        return None if DRY else float(np.median(times))

    report("causal_256_as_the_op_calls_it", q=fq, kv=fkv, grid_block=full.block, taken_backward=full.backward,
           dk_dv_summed_in_vmem=onchip_kernels.kv_rows_fit((length, length), full.widths, group),
           dq_vmem_mib=onchip_kernels.vmem_bytes((length, length), full.widths, False) / 2 ** 20,
           with_kv_rows_vmem_mib=onchip_kernels.vmem_bytes((length, length), full.widths, True) / 2 ** 20,
           forward_ms=forward_ms(causal), forward_backward_ms=try_ms(causal, *fqkv))
    for b in (128, 256) if DRY else (256, 512, 1024):
        at_block = lambda q, k, v, b=b: ma.attention_under(full._replace(block=b), q, k, v, q.shape[-1] ** -0.5)  # noqa: E731
        for kv_rows in (False, True):
            report("causal_256_fused_dq_on_the_chip", q=fq, grid_block=b, dk_dv_summed_in_vmem=kv_rows, forward_ms=forward_ms(at_block),
                   steps_a_head=int(ma._steps(full._replace(block=b)).q_block.size), ms=try_ms(onchip(full._replace(block=b), kv_rows), *fqkv))
    if not DRY:
        for b, compute, fused in ((1024, 512, True), (512, 512, True), (1024, 512, False), (512, 512, False)):
            report("causal_256_splash", q=fq, cut_blocks="computed", grid_block=b, block_kv_compute=compute, fused_backward=fused,
                   ms=try_ms(splash_with(mask_lib.CausalMask((length, length)), fq[1], sizes_of(b, b, compute, fused=fused)), *fqkv))
    sq, skv = ((1, 8, 512, 256), (1, 2, 512, 256)) if DRY else ((1, 16, 2048, 256), (1, 2, 2048, 256))
    sqkv = operands(sq, skv, seed=6)

    def dense(q, k, v):   # float32 scores of the whole square under the rule
        q, k, v = (t.astype(jnp.float32) for t in (q, k, v))
        k, v = (jnp.repeat(t, q.shape[1] // t.shape[1], axis=1) for t in (k, v))
        at = jnp.arange(q.shape[2])
        s = jnp.einsum("bhqd,bhkd->bhqk", q, k, precision="highest") * q.shape[-1] ** -0.5
        p = jax.nn.softmax(jnp.where(ma.causal_allowed(at[:, None], at[None, :]), s, -jnp.inf), -1)
        return jnp.einsum("bhqk,bhkd->bhqd", p, v, precision="highest")

    results = [(jax.jit(f)(*sqkv), *gradients(f)(*sqkv)) for f in (dense, causal)]
    report("causal_256_against_dense_float32", q=sq,
           apart=dict(zip(("out", "dq", "dk", "dv"), (apart(a, b) for a, b in zip(results[1], results[0])))),
           finite=all(bool(jnp.isfinite(x.astype(jnp.float32)).all()) for x in results[1]))
    sys.exit(0)


if os.environ.get("WINDOW") in ("1", "4096", "512x128"):
    wide = os.environ["WINDOW"] == "4096"
    narrow_band = os.environ["WINDOW"] == "512x128"     # Laguna-XS.2's: Phi's window on SmallThinker's kind of heads
    if wide:
        wq, wkv, window = ((1, 14, 1024, 128), (1, 2, 1024, 128), 256) if DRY else ((1, 28, 16384, 128), (1, 4, 16384, 128), 4096)
    elif narrow_band:
        wq, wkv, window = ((1, 16, 1024, 128), (1, 2, 1024, 128), 130) if DRY else ((1, 64, 16384, 128), (1, 8, 16384, 128), 512)
    else:
        wq, wkv, window = ((1, 4, 512, 64), (1, 2, 512, 64), 130) if DRY else ((1, 40, 8192, 64), (1, 20, 8192, 64), 512)
    wqkv = operands(wq, wkv, seed=2)
    length, heads = wq[2], wq[1]
    taken = lambda q, k, v: ma.window_attention(q, k, v, window, q.shape[-1] ** -0.5, interpret=DRY)  # noqa: E731
    causal = lambda q, k, v: ma.causal_attention(q, k, v, q.shape[-1] ** -0.5, interpret=DRY)  # noqa: E731
    plan = ma.window_plan(length, heads, window, DRY, (wq[-1], wkv[-1]))
    report("window", q=wq, kv=wkv, window=window, taken_block=plan.block, taken_backward=plan.backward,
           window_ms=try_ms(taken, *wqkv), causal_ms=try_ms(causal, *wqkv),
           pairs_allowed_over_causal=ma.window_pairs(length, window) / (length * (length + 1) / 2))
    if wide or narrow_band:   # the op's own call at each block that could be taken
        for b in (128, 256) if DRY else (512, 1024) if wide else (256, 512, 1024):
            at_block = lambda q, k, v, b=b: ma.attention_under(plan._replace(block=b), q, k, v, q.shape[-1] ** -0.5)  # noqa: E731
            report("window_as_the_op_calls_it", q=wq, window=window, grid_block=b, ms=try_ms(at_block, *wqkv))
    # the one backward kernel of our own, dq summed in VMEM, its grid the band's blocks alone, a block's keys one pass: by block, and under
    # grouped key/value heads with dk and dv summed over the group in VMEM (`kv_rows`) or outside in float32
    for b in (128,) if DRY else (512, 1024) if wide else (256, 512, 1024):
        for kv_rows in (True, False):
            report("window_fused_dq_on_the_chip", q=wq, window=window, grid_block=b, dk_dv_summed_in_vmem=kv_rows,
                   steps_a_head=int(ma._steps(plan._replace(block=b)).q_block.size),
                   ms=try_ms(onchip(plan._replace(block=b), kv_rows), *wqkv))
    if wide or narrow_band:   # the full layer's: the causal rule, ours against the stock fused backward
        fq = wq if wide else ((1, 12, 1024, 128) if DRY else (1, 48, 16384, 128))     # Laguna's full layer: groups of SIX
        fqkv = wqkv if wide else operands(fq, wkv, seed=4)
        full = ma.causal_plan(length, fq[1], DRY, (wq[-1], wkv[-1]))
        for kv_rows in (True, False):
            report("causal_fused_dq_on_the_chip", q=fq, grid_block=full.block, dk_dv_summed_in_vmem=kv_rows,
                   steps_a_head=int(ma._steps(full).q_block.size), ms=try_ms(onchip(full, kv_rows), *fqkv))
        if narrow_band:
            report("causal_as_the_op_calls_it", q=fq, grid_block=full.block, taken_backward=full.backward,
                   ms=try_ms(causal, *fqkv))
        if not DRY:
            report("causal_splash", q=fq, cut_blocks="computed", grid_block=1024, block_kv_compute=512, fused_backward=True,
                   ms=try_ms(splash_with(mask_lib.CausalMask((length, length)), fq[1], sizes_of(1024, 1024, 512, fused=True)), *fqkv))
    local = mask_lib.LocalMask((length, length), (window - 1, 0), 0)
    for b, compute, fused in ((128, 128, False), (128, 128, True)) if DRY else (
            (512, 512, False), (1024, 512, False), (1024, 1024, False), (2048, 512, False),
            (512, 512, True), (1024, 512, True)) if wide else (
            (256, 256, False), (512, 512, False), (1024, 512, False), (512, 512, True)) if narrow_band else (
            (128, 128, False), (256, 256, False), (512, 512, False), (512, 256, False), (1024, 512, False), (1024, 1024, False),
            (256, 256, True), (512, 512, True), (1024, 512, True)):
        blocks = 1 + -(-(window - 1) // b)
        report("window_splash", q=wq, grid_block=b, block_kv_compute=compute, fused_backward=fused,
               pairs_visited_over_allowed=round(blocks * b * length / ma.window_pairs(length, window), 3),
               ms=try_ms(splash_with(local, heads, sizes_of(b, b, compute, fused=fused)), *wqkv))
    sq, skv = ((1, 4, 512, 64), (1, 2, 512, 64)) if DRY else ((1, 8, 2048, 64), (1, 4, 2048, 64))
    if wide:   # the taken form against dense float32 where a window of 4096 is no causal rule: 6144 positions
        sq, skv = ((1, 14, 1024, 128), (1, 2, 1024, 128)) if DRY else ((1, 7, 6144, 128), (1, 1, 6144, 128))
    if narrow_band:   # groups of eight 128-wide heads
        sq, skv = ((1, 16, 1024, 128), (1, 2, 1024, 128)) if DRY else ((1, 16, 2048, 128), (1, 2, 2048, 128))
    sqkv = operands(sq, skv, seed=3)

    def dense(q, k, v):   # float32 scores of the whole square under the rule
        q, k, v = (t.astype(jnp.float32) for t in (q, k, v))
        k, v = (jnp.repeat(t, q.shape[1] // t.shape[1], axis=1) for t in (k, v))
        at = jnp.arange(q.shape[2])
        s = jnp.einsum("bhqd,bhkd->bhqk", q, k, precision="highest") * q.shape[-1] ** -0.5
        p = jax.nn.softmax(jnp.where(ma.window_allowed(at[:, None], at[None, :], window), s, -jnp.inf), -1)
        return jnp.einsum("bhqk,bhkd->bhqd", p, v, precision="highest")

    results = [(jax.jit(f)(*sqkv), *gradients(f)(*sqkv)) for f in (dense, taken)]
    report("window_against_dense_float32", q=sq, window=window,
           apart=dict(zip(("out", "dq", "dk", "dv"), (apart(a, b) for a, b in zip(results[1], results[0])))),
           finite=all(bool(jnp.isfinite(x.astype(jnp.float32)).all()) for x in results[1]))
    sys.exit(0)
if os.environ.get("STORED") == "1":
    # The ONE backward kernel reading a STORED block of the mask a step (PR 68) against the stock dq and dkv pair, forward +
    # backward of a layer alone: block diffusion's far + near at SDAR's (2, 32 on 4, 8192 against 4096 clean keys, 128) and
    # the selected rule under the causal one at Keye-VL-2.0's (1, 32 on 4, 16384, 128), about 2048 picks a query; by the
    # grid's block, by the mask's bytes a pair, and a group's dk and dv summed in VMEM or outside.
    from paddle_tpu.ops import sparse_index_ops as sio

    def form(plan, picks=None, kv_rows=None, pair=False, mask_dtype=jnp.int8):
        """Forward + backward under `plan` as the op calls it (`picks`: the selected rule), with the backward's form
        (`pair`: the stock dq and dkv kernels), its dk and dv (`kv_rows`, None: by the shapes) and the stored mask's dtype set."""
        def attend(q, k, v):
            if picks is None:
                return ma.attention_under(plan, q, k, v, scale)
            out, lse = ma._selected((q.astype(jnp.float32) * scale).astype(q.dtype), k, v, picks, plan, None)
            return out.astype(jnp.float32) + lse[..., None]      # the log-sum-exp is an output: its cotangent counts
        attend.patches = [mock.patch.object(ma.Plan, "backward", property(lambda self: "stock_pair"))] if pair else [
            mock.patch.object(onchip_kernels, "STORED_DTYPE", mask_dtype)] + (
            [mock.patch.object(onchip_kernels, "kv_rows_fit", lambda *a: kv_rows)] if kv_rows is not None else [])
        onchip_kernels.backward.clear_cache()
        ma._stored_blocks.cache_clear()
        ma.block_maps.cache_clear()          # the dq map is made where the plan's backward is the pair, and only there
        return attend

    def priced(what, plan, qkv, picks=None, **fields):
        forms = {"ours": {}, "ours_dk_dv_summed_outside": dict(kv_rows=False), "ours_int32_mask": dict(mask_dtype=jnp.int32),
                 "stock_pair": dict(pair=True)}
        blocks = (plan.block,) if DRY else (1024, 512)
        for b in blocks:
            at = plan._replace(block=b)
            took = {name: try_ms(form(at, picks, **how), *qkv) for name, how in forms.items()}
            report(what, grid_block=b, steps_a_head=int(ma._steps(at).q_block.size), ms=took, **fields)
        def gradients_of(**how):
            attend = form(plan, picks, **how)
            with patched(attend):
                return gradients(attend)(*qkv)

        ours, pair = gradients_of(), gradients_of(pair=True)
        report(what + "_ours_against_the_stock_pair", apart=dict(zip(("dq", "dk", "dv"), (apart(a, b) for a, b in zip(ours, pair)))),
               finite=all(bool(jnp.isfinite(x.astype(jnp.float32)).all()) for x in ours))

    priced("block_diffusion_stored", ma.plan_of(POSITIONS, Q[1], BLOCK, DRY, (Q[-1], KV[-1])), (q, k, v), q=Q, kv=KV)
    sq, skv = ((2, 4, 256, 128), (2, 2, 256, 128)) if DRY else ((1, 32, 16384, 128), (1, 4, 16384, 128))
    length, topk = sq[2], 32 if DRY else 2048
    at = jnp.arange(length)
    chosen = jax.random.uniform(jax.random.PRNGKey(5), (sq[0], length, length)) * (at[:, None] + 1) < topk      # ~topk of a query's keys so far
    picks = sio.pack_bits(chosen | (at[:, None] == at[None, :]))
    priced("selected_stored", ma.selected_plan(length, sq[1], True, DRY, (sq[-1], skv[-1])), operands(sq, skv, seed=6), picks, q=sq, kv=skv,
           picks_a_query=topk)
    sys.exit(0)
for cq, ckv, v_width in (((1, 4, 256, 128), (1, 4, 256, 128), 128), ((1, 8, 256, 64), (1, 2, 256, 64), 64),
                         ((1, 2, 256, 192), (1, 2, 256, 192), 128)) if DRY else (
        ((4, 16, 4096, 128), (4, 16, 4096, 128), 128), ((2, 32, 8192, 64), (2, 8, 8192, 64), 64),
        ((1, 32, 16384, 192), (1, 32, 16384, 192), 128)):          # OLMoE's, LFM2's, Kanana-2's latent attention (192 | 128)
    cqkv = operands(cq, ckv, seed=1, v_width=v_width)
    length, heads = cq[2], cq[1]
    taken = lambda q, k, v: ma.causal_attention(q, k, v, q.shape[-1] ** -0.5, interpret=DRY)  # noqa: E731
    one_width = v_width == cq[-1]      # the flash kernel was never given two widths
    report("causal", q=cq, kv=ckv, v_width=v_width, flash_ms=try_ms(flash_causal, *cqkv) if one_width and not DRY else None,
           block_causal_ms=try_ms(taken, *cqkv), taken_backward=ma.causal_plan(length, heads, DRY, (cq[-1], v_width)).backward)
    if one_width and not DRY:
        results = [(jax.jit(f)(*cqkv), *gradients(f)(*cqkv)) for f in (flash_causal, taken)]
        report("causal_flash_against_block_causal", q=cq,
               apart=dict(zip(("out", "dq", "dk", "dv"), (apart(a, b) for a, b in zip(results[1], results[0])))))
    for b in (128,) if DRY else (1024, 512):
        for kv_rows in (True, False) if cq[1] != ckv[1] else (None,):
            plan = ma.causal_plan(length, heads, DRY, (cq[-1], v_width))._replace(block=b)
            report("causal_fused_dq_on_the_chip", q=cq, grid_block=b, dk_dv_summed_in_vmem=kv_rows,
                   steps_a_head=int(ma._steps(plan).q_block.size), ms=try_ms(onchip(plan, kv_rows), *cqkv))
    for cut, mask in (("stored", StoredCausal(length)), ("computed", mask_lib.CausalMask((length, length)))):
        for b, compute, fused in ((128, 128, False),) if DRY else (
                (1024, 1024, False), (1024, 512, False), (512, 512, False), (2048, 512, False), (1024, 1024, True),
                (1024, 512, True), (512, 512, True)) if one_width else ((1024, 512, False), (1024, 512, True)):
            report("causal_splash", q=cq, cut_blocks=cut, grid_block=b, block_kv_compute=compute, fused_backward=fused,
                   ms=try_ms(splash_with(mask, heads, sizes_of(b, b, compute, fused=fused)), *cqkv))
if os.environ.get("CAUSAL") == "1":
    sys.exit(0)
for rule_block in (4, 16, 128, 256):
    split = ma.plan_of(POSITIONS, Q[1], rule_block, DRY)  # as `fused_attention` calls it
    whole = split._replace(block=ma.kernel_block(POSITIONS), first_key=0)
    forms = [lambda q, k, v, plan=plan: ma.attention_under(plan, q, k, v, scale) for plan in (whole, split)]
    results = [(jax.jit(form)(q, k, v), *gradients(form)(q, k, v)) for form in forms]
    report("whole_square_against_far_plus_near", rule_block=rule_block, split_taken=split.first_key > 0,
           whole_square_ms=try_ms(forms[0], q, k, v), far_plus_near_ms=try_ms(forms[1], q, k, v),
           apart=dict(zip(("out", "dq", "dk", "dv"), (apart(a, b) for a, b in zip(results[1], results[0])))),
           finite=all(bool(jnp.isfinite(x.astype(jnp.float32)).all()) for x in results[1]))
if os.environ.get("QUICK") == "1":
    sys.exit(0)
stored = ma._rule_mask(POSITIONS, 0, BLOCK)
for b in ((128,) if DRY else (256, 512, 1024)):
    report("two_backward_kernels", grid_block=b, ms=try_ms(splash_with(stored, Q[1], sizes_of(b, b)), q, k, v))
    report("the_mask_computed_in_the_kernel", grid_block=b, ms=try_ms(splash_with(Computed(), Q[1], sizes_of(b, b)), q, k, v))
if not DRY:
    for bq, bkv, compute in ((512, 1024, 512), (1024, 512, 512), (1024, 1024, 512), (1024, 2048, 1024), (2048, 1024, 1024)):
        report("block_sparse_attention_mixed", block_q=bq, block_kv=bkv, block_kv_compute=compute,
               ms=try_ms(splash_with(stored, Q[1], sizes_of(bq, bkv, compute)), q, k, v))
    for b in (512, 1024):
        report("block_sparse_attention_fused_backward", grid_block=b,
               ms=try_ms(splash_with(stored, Q[1], sizes_of(b, b, fused=True)), q, k, v))
