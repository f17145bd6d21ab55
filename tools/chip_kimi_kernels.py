"""Prices, on the chip, the two lowerings Kimi Linear's cell chose by a reading
(run through `chiprun -- python3 tools/chip_kimi_kernels.py`, ~5 min):

  * the chunked KDA op alone at the cell's shape (1, 4096, 32, 128) as the step
    runs it: the plain forward (the `for_test` clone's), the forward that is
    differentiated (which keeps what backward reads: the chunks' start states
    and T on the kernels' path, PR 45), the backward that reads it, and both
    together, through the Pallas kernels (`ops/kda_kernels.py`: what the op's
    rule takes on the chip, PR 44) and in the `jax.numpy` form, and how far
    each lies from the token-by-token float32 recurrence rounded as the op
    rounds; with SWEEP=1 the `jax.numpy` form at each precision of its
    float32 products and with the chunks' terms made 4, 8 or 16 chunks at a time;
  * the causal attention at latent attention's widths (192-wide queries and
    keys, 128-wide values, 32 heads, 4096 keys): the stock splash kernels with
    the widths as they are, with q and k padded to 256 by zeros, and XLA's.

  * ONLY=gdn: the op at Qwen3-Next's Gated DeltaNet shape, (1, 16384, 32, 128) with
    16 key heads and a decay of ONE number a head: as the op takes it (g [b, T,
    H], the key heads through the kernels' index map, the decay taken out of
    the chunk's Grams) at 4, 2 and 8 value heads a grid step (two share a key head: no fewer than 2), against the same
    decay written out over the 128 channels and q and k repeated to 32 heads
    in HBM (the decay-a-channel kernels, what the op ran before PR 69), forward |
    forward that keeps | backward | both, how far the two forms lie apart and
    the scalar form from the token-by-token recurrence.

Prints one JSON line a reading.  ONLY=kda or ONLY=attention runs one half;
ONLY=profile writes the own device time by HLO instruction (the kernels, the
state's `while`s, what is left in XLA's fusions) of the op's three programs,
the plain forward, the forward that keeps and the backward that reads, under
chiprun_out/; ONLY=terms prices what keeping one more of a chunk's terms from
forward could save (`terms`: the kernels with that term's products cut out,
an upper bound)."""
import json
import os
import shutil
import sys
import time

sys.path.insert(0, os.getcwd())

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.models import kimi_linear
from paddle_tpu.ops import kda_kernels
from paddle_tpu.ops import linear_attention_ops as lao
from paddle_tpu.ops import masked_attention, nn_ops

RUNS = 5
PRECISION, GROUP = lao._KDA_PRECISION, lao._KDA_GROUP   # the op's own


def say(**fields):
    print(json.dumps(fields, default=float), flush=True)


def timed(fn, *args):
    out = jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(RUNS):
        out = fn(*args)
    jax.block_until_ready(out)
    return 1e3 * (time.perf_counter() - t0) / RUNS, out


def kda_inputs(seed, b=1, T=4096, H=32, K=128):
    r = np.random.RandomState(seed)
    unit = lambda t: t / np.linalg.norm(t, axis=-1, keepdims=True)
    q, k = unit(r.randn(b, T, H, K)) * K ** -0.5, unit(r.randn(b, T, H, K))
    v = 0.5 * r.randn(b, T, H, K)
    g = -(r.uniform(1, 16, (1, 1, H, 1)) * np.exp(r.uniform(np.log(1e-3), np.log(1e-1), (b, T, H, K))))
    beta = 1 / (1 + np.exp(-r.randn(b, T, H)))
    low = lambda t: jnp.asarray(t, jnp.bfloat16)
    return low(q), low(k), low(v), jnp.asarray(g, jnp.float32), jnp.asarray(beta, jnp.float32)


def cotangent_of(args):
    """A d o for `kda_inputs`' v."""
    return jnp.asarray(np.random.RandomState(2).randn(*args[2].shape), jnp.bfloat16)


def bf16(t):
    """float32 holding bf16's eight bits; not a pair of casts, which XLA may take out."""
    return jax.lax.reduce_precision(t, exponent_bits=8, mantissa_bits=7)


def bf16_states(phi, B):
    """`linear_attention_ops._states` with the state rounded to bf16 at every chunk boundary."""
    def step(S, term):
        return bf16(lao._mm("hkj,hjv->hkv", term[0], S) + term[1]), S
    final, starts = jax.lax.scan(step, jnp.zeros(B.shape[1:], jnp.float32), (phi, B))
    return starts, final


def op_of(kernels):
    """The op as `_kda` calls it: through the kernels ("tpu") or in the `jax.numpy` form (None)."""
    def op(q, k, v, g, beta):
        return lao.chunked_kda(q, k, v, g, beta[..., None], lao._KDA_CHUNK, lao._KDA_SUB, kernels)[0]
    return op


def both_of(op):
    def both(*a):
        out, pull = jax.vjp(op, *a)
        return pull(out)
    return both


def kept_of(kernels):
    """The op's forward where it is differentiated (`custom_vjp`'s rule): ((o, the final state), what it MAKES for
    backward: the chunks' start states and T on the kernels' path, nothing in the `jax.numpy` form; the five inputs it
    keeps too are the caller's own arrays)."""
    def forward(q, k, v, g, beta):
        out, (_, kept) = lao._chunked_kda_fwd(q, k, v, g, beta[..., None], lao._KDA_CHUNK, lao._KDA_SUB, kernels)
        return out, kept
    return forward


def backward_of(kernels):
    """The op's backward alone: the five gradients of the inputs, what forward made for it and d o."""
    def backward(q, k, v, g, beta, kept, d_o):
        return lao._chunked_kda_bwd(lao._KDA_CHUNK, lao._KDA_SUB, kernels, None, ((q, k, v, g, beta[..., None]), kept), (d_o, None))
    return backward


def in_kernel_bf16(t):
    """bf16's eight bits inside a Pallas kernel: a pair of casts, of which Mosaic takes none out."""
    return t.astype(jnp.bfloat16).astype(jnp.float32)


cumulative_in_kernel = kda_kernels.cumulative


def bf16_cumulative_in_kernel(g, lower):
    return in_kernel_bf16(cumulative_in_kernel(g, lower))


def kda():
    args = kda_inputs(int(os.environ.get("SEED", "1")))

    def errors(out, inputs=args):   # as the cell's KDA stage reads them
        found = kimi_linear.kda_errors([inputs + (out,)])
        return {"error": found["kda_error"], "error_unrounded": found["kda_error_unrounded"],
                "recurrence_bf16_state": found["kda_error_bf16_state"]}

    outs = {}
    weigh = cotangent_of(args)
    heads_a_step = kda_kernels._HEADS
    forms = [("kernels", "tpu", heads_a_step), ("jax.numpy", None, None)]
    forms += [("kernels", "tpu", h) for h in (1, 2, 4, 8) if h != heads_a_step] if os.environ.get("HEADS") == "1" else []
    for form, kernels, heads in forms:
        kda_kernels._HEADS = heads or heads_a_step      # read when a kernel is traced
        jax.clear_caches()
        op = op_of(kernels)
        fwd_ms, out = timed(jax.jit(op), *args)
        kept_ms, (_, kept) = timed(jax.jit(kept_of(kernels)), *args)
        bwd_ms, _ = timed(jax.jit(backward_of(kernels)), *args, kept, weigh)
        both_ms, grads = timed(jax.jit(both_of(op)), *args)
        say(reading="kda_op", form=form, heads_a_grid_step=heads, precision="HIGHEST", fwd_ms=fwd_ms, fwd_kept_ms=kept_ms,
            bwd_ms=bwd_ms, fwd_bwd_ms=both_ms, kept_mb=sum(t.nbytes for t in kept) / 1e6, **errors(out))
        if form not in outs:
            outs[form], outs[form + ".grads"] = out, grads
    kda_kernels._HEADS = heads_a_step
    jax.clear_caches()
    rms = lambda t: float(np.sqrt(np.mean(np.square(np.asarray(t, "f4")))))
    say(reading="kda_kernels_against_jax_numpy", out=rms(outs["kernels"].astype(jnp.float32) - outs["jax.numpy"].astype(jnp.float32)) / rms(outs["jax.numpy"]),
        **{"d" + name: rms(mine.astype(jnp.float32) - theirs.astype(jnp.float32)) / max(rms(theirs), 1e-30)
           for name, mine, theirs in zip("q k v g beta".split(), outs["kernels.grads"], outs["jax.numpy.grads"])})
    if os.environ.get("SWEEP") == "1":
        for precision in ("HIGHEST", "HIGH"):
            for group in (8, 4, 16):
                lao._KDA_PRECISION, lao._KDA_GROUP = getattr(jax.lax.Precision, precision), group
                fwd_ms, out = timed(jax.jit(op_of(None)), *args)
                both_ms, _ = timed(jax.jit(both_of(op_of(None))), *args)
                say(reading="kda_op", form="jax.numpy", precision=precision, group=group, fwd_ms=fwd_ms, fwd_bwd_ms=both_ms, **errors(out))
        lao._KDA_PRECISION = jax.lax.Precision.DEFAULT
        say(reading="kda_op", form="jax.numpy", precision="DEFAULT", **errors(jax.jit(op_of(None))(*args)))
        lao._KDA_PRECISION, lao._KDA_GROUP = PRECISION, GROUP
    # the faults the stage's limit has to refuse, put into the OP as the chip runs it (the kernels)
    # (a new function a fault: `jax.jit` keeps what it traced by the function it was given)
    carried = kda_kernels.carried
    kda_kernels.carried = in_kernel_bf16
    say(reading="kda_op_bf16_state", **errors(jax.jit(op_of("tpu"))(*args)))
    kda_kernels.carried = carried
    kda_kernels.cumulative = bf16_cumulative_in_kernel
    say(reading="kda_op_bf16_cumulative_decay", **errors(jax.jit(op_of("tpu"))(*args)))
    kda_kernels.cumulative = cumulative_in_kernel
    say(reading="kda_op_no_decay", **errors(jax.jit(lambda q, k, v, g, b: op_of("tpu")(q, k, v, 0 * g, b))(*args)))
    # the rarer lowering of the blocks' own keys, forced: a channel that dies in one token
    strong = args[3].at[:, ::97, :, 5].set(-100.0)
    hard = args[:3] + (strong,) + args[4:]
    for form, kernels in (("kernels", "tpu"), ("jax.numpy", None)):
        fwd_ms, out = timed(jax.jit(op_of(kernels)), *hard)
        both_ms, _ = timed(jax.jit(both_of(op_of(kernels))), *hard)
        found = errors(out, hard)
        say(reading="kda_op_by_differences", form=form, fwd_ms=fwd_ms, fwd_bwd_ms=both_ms, error=found["error"],
            error_unrounded=found["error_unrounded"])


def gdn_inputs(seed, b=1, T=16384, key_heads=16, H=32, K=128):
    """q, k (`key_heads`), v, beta as `kda_inputs` draws them, and a log decay of one number a head a token."""
    q, k, v, _, beta = kda_inputs(seed, b, T, H, K)
    r = np.random.RandomState(seed + 1)
    g = -(r.uniform(1, 16, (1, 1, H)) * np.exp(r.uniform(np.log(1e-3), np.log(1e-1), (b, T, H))))
    return q[:, :, :key_heads], k[:, :, :key_heads], v, jnp.asarray(g, jnp.float32), beta


def gdn(T=16384, key_heads=16, H=32, K=128, kernels="tpu"):
    from benchmark.models import qwen3_next

    args = gdn_inputs(int(os.environ.get("SEED", "1")), 1, T, key_heads, H, K)
    q, k, v, g, beta = args
    written_out = (jnp.repeat(q, H // key_heads, 2), jnp.repeat(k, H // key_heads, 2), v, jnp.broadcast_to(g[..., None], v.shape[:3] + (K,)), beta)
    weigh = cotangent_of(args)
    heads_a_step = kda_kernels._HEADS
    rms = lambda t: float(np.sqrt(np.mean(np.square(np.asarray(t, "f4")))))
    outs = {}
    for form, operands, heads in [("a_decay_a_head", args, h) for h in (heads_a_step, 2, 8)] + [("written_out_over_channels", written_out, heads_a_step)]:
        kda_kernels._HEADS = heads      # read when a kernel is traced
        jax.clear_caches()
        op = op_of(kernels)
        fwd_ms, out = timed(jax.jit(op), *operands)
        kept_ms, (_, kept) = timed(jax.jit(kept_of(kernels)), *operands)
        bwd_ms, _ = timed(jax.jit(backward_of(kernels)), *operands, kept, weigh)
        both_ms, grads = timed(jax.jit(both_of(op)), *operands)
        say(reading="gdn_op", form=form, shape=(1, T, H, K), key_heads=key_heads, heads_a_grid_step=heads, fwd_ms=fwd_ms, fwd_kept_ms=kept_ms,
            bwd_ms=bwd_ms, fwd_bwd_ms=both_ms, kept_mb=sum(t.nbytes for t in kept) / 1e6,
            operands_mb=sum(t.nbytes for t in operands) / 1e6)
        outs.setdefault(form, (out, grads))
    kda_kernels._HEADS = heads_a_step
    jax.clear_caches()
    (mine, mine_grads), (theirs, their_grads) = outs["a_decay_a_head"], outs["written_out_over_channels"]
    share = H // key_heads
    summed = [their_grads[0].astype(jnp.float32).reshape(1, T, key_heads, share, K).sum(3), their_grads[1].astype(jnp.float32).reshape(1, T, key_heads, share, K).sum(3),
              their_grads[2], their_grads[3].sum(-1), their_grads[4]]
    say(reading="gdn_forms_apart", out=rms(mine.astype(jnp.float32) - theirs.astype(jnp.float32)) / rms(theirs),
        **{"d" + name: rms(a.astype(jnp.float32) - b.astype(jnp.float32)) / max(rms(b), 1e-30) for name, a, b in zip("q k v g beta".split(), mine_grads, summed)})
    want = np.asarray(jax.jit(qwen3_next.recurrence)(*(t[0] for t in written_out[:3]), g[0], beta[0]))
    rounded = np.asarray(jnp.asarray(want, jnp.bfloat16).astype(jnp.float32))
    say(reading="gdn_against_the_recurrence", **{form: rms(np.asarray(out[0].astype(jnp.float32)) - rounded) / rms(want) for form, (out, _) in outs.items()})


def attention():
    r = np.random.RandomState(2)
    b, h, L, dqk, dv = 1, 32, 4096, 192, 128
    q, k = (jnp.asarray(r.randn(b, h, L, dqk), jnp.bfloat16) for _ in range(2))
    v = jnp.asarray(r.randn(b, h, L, dv), jnp.bfloat16)
    scale = dqk ** -0.5

    def splash(q, k, v):
        return masked_attention.causal_attention(q, k, v, scale)

    def padded(q, k, v):
        wide = lambda t: jnp.pad(t, ((0, 0), (0, 0), (0, 0), (0, 256 - dqk)))
        return masked_attention.causal_attention(wide(q), wide(k), v, scale)

    def xla(q, k, v):
        return nn_ops._xla_attention(q, k, v, None, True, scale, None)

    def both(fn):
        def run(q, k, v):
            out, pull = jax.vjp(fn, q, k, v)
            return out, pull(out)
        return jax.jit(run)

    want = None
    for name, fn in (("xla", xla), ("splash_192_128", splash), ("splash_padded_256", padded)):
        ms, (out, _) = timed(both(fn), q, k, v)
        fwd_ms, _ = timed(jax.jit(fn), q, k, v)
        want = np.asarray(out, "f4") if want is None else want
        say(reading="causal_attention", kernel=name, fwd_ms=fwd_ms, fwd_bwd_ms=ms,
            max_error_from_xla=float(np.abs(np.asarray(out, "f4") - want).max() / np.abs(want).max()))


def own_ms_by_instruction(name, fn, *args, runs=3):
    """{HLO instruction: own device ms a run} of `fn(*args)`, from a trace of
    `runs` runs; the compiled text to look each up in goes to chiprun_out/."""
    from benchmark.metrics.recompute_ms_per_step import own_times

    jax.block_until_ready(fn(*args))
    os.makedirs("chiprun_out", exist_ok=True)
    open(f"chiprun_out/kda_{name}.hlo", "w").write(fn.lower(*args).compile().as_text())
    where = f"chiprun_out/kda_trace_{name}"
    jax.profiler.start_trace(where)
    for _ in range(runs):
        out = fn(*args)
    jax.block_until_ready(out)
    jax.profiler.stop_trace()
    found = [os.path.join(base, f) for base, _, files in os.walk(where) for f in files if f.endswith(".xplane.pb")]
    data = jax.profiler.ProfileData.from_file(max(found, key=os.path.getmtime))
    spent = {}
    for plane in data.planes:
        if not plane.name.startswith("/device:TPU:0"):
            continue
        for line in plane.lines:
            if line.name != "XLA Ops":
                continue
            events = [(e.name, e.start_ns, e.duration_ns) for e in line.events]
            for instruction, ns in own_times(events, (min(e[1] for e in events), max(e[1] + e[2] for e in events))):
                spent[instruction] = spent.get(instruction, 0.0) + ns / (runs * 1e6)
    shutil.rmtree(where)
    return spent


def profile():
    """Where the op spends its time as the step runs it: own device time by HLO
    instruction of the plain forward, of the forward that keeps what backward
    reads, and of the backward that reads it."""
    args = kda_inputs(1)
    kernels = None if os.environ.get("FORM") == "jax.numpy" else "tpu"
    weigh = cotangent_of(args)
    keeps = jax.jit(kept_of(kernels))
    programs = (("forward", jax.jit(op_of(kernels)), args), ("forward_kept", keeps, args),
                ("backward", jax.jit(backward_of(kernels)), (*args, keeps(*args)[1], weigh)))
    whole = {}
    for name, fn, operands in programs:
        spent = whole[name] = own_ms_by_instruction(name, fn, *operands)
        kinds = {"kernels": 0.0, "while": 0.0, "fusions": 0.0}
        for instruction, ms in spent.items():      # a Pallas kernel's instruction carries the call's name
            kind = "kernels" if instruction.startswith("kda_scan") else "while" if instruction.startswith("while") else "fusions"
            kinds[kind] += ms
        say(reading="kda_profile", program=name, own_ms_a_run=sum(spent.values()), instructions=len(spent), own_ms_by_kind=kinds,
            top=sorted(spent.items(), key=lambda kv: -kv[1])[:8])
    json.dump({name: sorted(spent.items(), key=lambda kv: -kv[1]) for name, spent in whole.items()},
              open("chiprun_out/kda_profile.json", "w"))


def terms():
    """What a chunk's term costs where a kernel makes it, so what keeping it from
    forward could save: the forward that keeps and the backward that reads, as
    they are and with the term's products cut out of `_Chunks` (other numbers,
    the same shapes and every other product).  The difference is an UPPER
    bound of the saving: reading the term back is a DMA more a grid step.  T's
    chain priced so (1.36 ms a layer of backward) is why forward keeps T since
    PR 45, and reads no difference in backward since."""
    args = kda_inputs(1)
    weigh = cotangent_of(args)
    kept = jax.jit(kept_of("tpu"))(*args)[1]

    def no_chain(self, As):         # T := I - the 2-blocks of beta M
        pairs = self.row // 2 == self.col // 2
        return [self.eye.astype(jnp.float32) - jnp.where(pairs, a, 0.0) for a in As]

    def no_product(self):           # [W | U] := the right-hand side itself
        return [jnp.concatenate([beta * k * e, beta * v], axis=1)
                for beta, k, e, v in zip(self.beta, self.k, self.from_start, self.v)]

    def price():
        jax.clear_caches()          # `_Chunks`' methods are read when a kernel is traced
        return {"fwd_kept_ms": timed(jax.jit(kept_of("tpu")), *args)[0],
                "bwd_ms": timed(jax.jit(backward_of("tpu")), *args, kept, weigh)[0]}

    found = {"as_it_is": price()}
    for term, method, without in (("T", "_unit_lower_inverses", no_chain), ("W|U", "_solved", no_product)):
        made = getattr(kda_kernels._Chunks, method)
        setattr(kda_kernels._Chunks, method, without)
        found["without_the_products_of_" + term] = price()
        setattr(kda_kernels._Chunks, method, made)
    found["as_it_is_again"] = price()
    say(reading="kda_terms_priced", **found)


if __name__ == "__main__":
    say(device=jax.devices()[0].device_kind, platform=jax.devices()[0].platform)
    only = os.environ.get("ONLY")
    if only == "profile":
        profile()
    if only == "terms":
        terms()
    if only == "gdn":
        gdn()
    if only in (None, "kda"):
        kda()
    if only in (None, "attention"):
        attention()
