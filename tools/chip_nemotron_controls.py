"""Nemotron-3-Super's cell on the chips, what its comparison can and cannot
tell: the harness's own `benchmark.models.nemotron_h.compare` / `reference_error`
on the program's check rows against the float32 reference, sound and then with
a fault put in, one at a time, so that each limit this PR brings has a reading
it must refuse beside the sound one (PERF.md, section 6, PR 60).  The program
runs ONCE a seed, sound; a fault is put into a STAGE (what the program fetched
of the stage's operands goes through a faulty form of the stage, and its result
takes the place of what the program fetched of the stage's output) or into THE
REFERENCE (the errors are differences):

  * `scan_bf16_state`: the scan's state rounded to bf16 where a chunk of 128
    tokens hands it to the next: `SCAN_STATE_RTOL`;
  * `scan_bf16_cumulative`: the decay's logarithm summed along a chunk rounded to
    bf16;
  * `scan_default_precision`: the three products with a float32 operand at the
    matrix unit's default precision, bf16 operands;
  * `scan_wrong_group`: every group's heads read their neighbour group's B;
    (the four through `faulty_ssd_scan`, this file's copy of the op's PLAIN
    chunked form, `ssd_ops.chunked_ssd_scan`, which with no fault is that form
    bit for bit.  Since PR 61 the cell's sound run goes through the Pallas
    kernels of `ops/ssd_kernels.py` and the faults through the copy:
    `copy_differs` is how far the kernels' output and last state lie from the
    plain form's on the program's own operands, float32's rounding of the state
    and a step of the output's bf16 at most)
  * `attention_mask_shifted`: a query also sees the key after it;
  * `attention_wrong_kv_head`: query head j reads key/value head j mod 2, not
    j // 16: `ATTENTION_RTOL` (both in float32 numpy on the program's q, k, v,
    rounded to bf16 as the kernel rounds its output);
  * `relu_for_relu2`: the reference's experts with relu for relu^2;
  * `norm_before_gate`: the reference's gated norm norming before it gates;
  * `reference_default_precision`: the reference's float32 products at the
    chip's default precision (bf16 operands), the nearest precision below the
    one the reference states.  (The first two move the stream, so they are also
    what `OTHER_CHOICE_MAX` and `OTHER_CHOICE_RTOL` have to refuse; the third
    reads as a sound run does: the program's own products are bf16.)

    chiprun --chips 4 --timeout 3400 -- python3 tools/chip_nemotron_controls.py 3600000701 3600000702 3600000703      (PERF.md, PR 60)

`ONLY=profile python3 tools/chip_nemotron_controls.py` (one chip, ~2 min) is the
op ALONE at a chip's shapes in the cell, no program round it: own device ms of
the forward kernel, of the forward that keeps the chunks' start states, of the
transposed kernel and of the plain form forward and through `jax.vjp`, how far
the kernels' results and seven gradients lie from the plain form's, and both
forms against the recurrence as the scan stage reads them: the split that
`ssd_ms_per_step` and `ssd_scan_roofline_share` do not give (PERF.md, PR 61).

Every control runs on the first seed; on each further seed the scan's controls
alone, against the scan stage's own limits (no reference is computed there).  Names
after the seeds run those controls alone, beside `sound`; where all of them are
the scan's, no seed computes a reference.  `DRY=1` rehearses it
tiny on the CPU's virtual mesh; no number of that means anything.
"""
import contextlib
import gc
import io
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

DRY = os.environ.get("DRY") == "1"
if DRY:
    os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import jax
import jax.numpy as jnp
import numpy as np

import paddle_tpu as fluid
from benchmark import manifest as mf
from benchmark.models import lfm2, nemotron_h

CHECK_ROWS = 8  # as benchmark/runners/train.py
TINY = (dict(hidden_size=64, num_attention_heads=4, num_key_value_heads=2, head_dim=16, vocab_size=96, mamba_num_heads=8,
             mamba_head_dim=8, ssm_state_size=16, n_groups=2, chunk_size=16, moe_latent_size=32, moe_intermediate_size=24,
             moe_shared_expert_intermediate_size=48, num_routed_experts=32, n_routed_experts=8, num_experts_per_tok=4,
             num_hidden_layers=5, hybrid_override_pattern="MEM*E", conv_taps_bound=4.0),
        dict(seq_len=64, batch_per_chip=1, ring=4))
SCAN_FAULTS = ("scan_bf16_state", "scan_bf16_cumulative", "scan_default_precision", "scan_wrong_group")
ATTENTION_FAULTS = ("attention_mask_shifted", "attention_wrong_kv_head")
REFERENCE_FAULTS = {"relu_for_relu2": dict(activation="relu"), "norm_before_gate": dict(gate_first=False),
                    "reference_default_precision": dict(precision="default")}
#: the scan's stage tensors (xs, dt, B, C, output, last state) of the first and the last Mamba-2 layer, and the
#: attention's (q, k, v, output), counted from the END of what `nemotron_h.build` has the program fetch
SCANS, ATTENTION = (slice(-16, -10), slice(-10, -4)), slice(-4, None)


def low(t):
    return jax.lax.reduce_precision(t, exponent_bits=8, mantissa_bits=7)   # XLA takes a pair of casts out


def faulty_ssd_scan(x, dt, a_log, b_t, c_t, d_skip, dt_bias, groups, chunk, fault=None):
    """(y in x's dtype, the float32 state after the last token [b, H, P, N]):
    `paddle_tpu.ops.ssd_ops.chunked_ssd_scan` line for line, with `fault` (one of
    `SCAN_FAULTS`, less its prefix) put in."""
    from paddle_tpu.ops.ssd_ops import _NO_STEP

    batch, T, width = x.shape
    heads, G, Q = a_log.shape[0], int(groups), min(int(chunk), T)
    P, N, per, n = width // heads, b_t.shape[-1] // G, heads // G, -(-T // Q)
    pad = n * Q - T
    precision = jax.lax.Precision.DEFAULT if fault == "default_precision" else jax.lax.Precision.HIGHEST

    def product(spec, left, right):
        return jnp.einsum(spec, left, right, precision=precision, preferred_element_type=jnp.float32)

    def chunks(t, fill=0.0):
        if pad:
            t = jnp.pad(t, ((0, 0), (0, pad), (0, 0)), constant_values=fill)
        return t.reshape(batch, n, Q, t.shape[-1])

    A = -jnp.exp(a_log.astype(jnp.float32))
    step = jax.nn.softplus(chunks(dt, _NO_STEP).astype(jnp.float32) + dt_bias.astype(jnp.float32))
    cum = jnp.cumsum(step * A, axis=2)
    if fault == "bf16_cumulative":
        cum = low(cum)
    x_c = chunks(x).reshape(batch, n, Q, G, per, P)
    b_c = chunks(b_t).reshape(batch, n, Q, G, N)
    if fault == "wrong_group":
        b_c = jnp.roll(b_c, 1, axis=3)
    c_c = chunks(c_t).reshape(batch, n, Q, G, N)
    by_head = cum.transpose(0, 1, 3, 2).reshape(batch, n, G, per, Q)
    below = jnp.tril(jnp.ones((Q, Q), bool))
    apart = jnp.where(below, by_head[..., :, None] - by_head[..., None, :], -jnp.inf)
    scores = jnp.einsum("bnigs,bnjgs->bngij", c_c, b_c, preferred_element_type=jnp.float32)
    enters = step.transpose(0, 1, 3, 2).reshape(batch, n, G, per, 1, Q)
    mixed = scores[:, :, :, None] * jnp.exp(apart) * enters
    y = product("bnghij,bnjghp->bnighp", mixed, x_c.astype(jnp.float32))
    last = cum[:, :, -1:, :]
    weight = (jnp.exp(last - cum) * step).reshape(batch, n, Q, G, per)
    added = product("bnjghp,bnjgs->bnghps", x_c.astype(jnp.float32) * weight[..., None], b_c.astype(jnp.float32))
    through = jnp.exp(last[:, :, 0]).reshape(batch, n, G, per)

    def carry(h, part):
        decay, more = part
        h_next = decay[..., None, None] * h + more
        return (low(h_next) if fault == "bf16_state" else h_next), h

    h0 = jnp.zeros((batch, G, per, P, N), jnp.float32)
    final, starts = jax.lax.scan(carry, h0, (through.swapaxes(0, 1), added.swapaxes(0, 1)))
    from_start = product("bnigs,bnghps->bnighp", c_c.astype(jnp.float32), starts.swapaxes(0, 1))
    y = y + from_start * jnp.exp(cum).reshape(batch, n, Q, G, per)[..., None]
    y = y.reshape(batch, n * Q, width)[:, :T] + jnp.repeat(d_skip.astype(jnp.float32), P) * x.astype(jnp.float32)
    return y.astype(x.dtype), final.reshape(batch, heads, P, N)


def scan_stage(fetched, parameters, groups, chunk, fault):
    """What the program fetched with both scans' output and last state made
    again from the fetched operands by `faulty_ssd_scan`."""
    made = list(fetched)
    form = jax.jit(faulty_ssd_scan, static_argnames=("groups", "chunk", "fault"))
    for at, (a_log, d_skip, dt_bias) in zip(SCANS, parameters):
        x, dt, b_t, c_t = (jnp.asarray(t) for t in made[at][:4])
        y, final = form(x, dt, a_log, b_t, c_t, d_skip, dt_bias, groups=groups, chunk=chunk, fault=fault)
        made[at] = [*made[at][:4], np.asarray(y), np.asarray(final)]
    return made


def attention_stage(fetched, fault):
    """... with the attention's output at `attention_sample`'s queries made
    again from the fetched q, k, v in float32 numpy with `fault` put in."""
    q, k, v, out = (np.asarray(t) for t in fetched[ATTENTION])            # (rows, L, heads, dh) as the program hands them
    rows, positions, heads, dh = q.shape
    kv = k.shape[2]
    sample = lfm2.attention_sample(positions)
    reach = sample + 1 if fault == "mask_shifted" else sample
    allowed = np.arange(positions)[None, :] <= reach[:, None]
    out = np.array(out)
    for r in range(rows):
        for j in range(heads):
            g = j % kv if fault == "wrong_kv_head" else j // (heads // kv)
            scores = np.where(allowed, q[r, sample, j].astype("f4") @ k[r, :, g].astype("f4").T / np.sqrt(dh), -np.inf)
            e = np.exp(scores - scores.max(-1, keepdims=True))
            out[r, sample, j] = ((e / e.sum(-1, keepdims=True)) @ v[r, :, g].astype("f4")).astype(out.dtype)
    made = list(fetched)
    made[ATTENTION] = [q, k, v, out]
    return made


def main(seeds, only=()):
    cfg = mf.read_json("benchmark/configs/nemotron-3-super-120b-a12b.json")
    job = mf.read_json("benchmark/traffic/train-ssd-fsdp4.json")
    if DRY:
        cfg.update(TINY[0])
        job.update(TINY[1])
        lfm2.LOGIT_SAMPLE = lfm2.ATTENTION_SAMPLE = 8
        nemotron_h.STAGE_CHANNELS, nemotron_h.STAGE_HEADS, nemotron_h.STAGE_TOKENS = 64, 8, 32
        nemotron_h.EXPERTS_SAMPLE, nemotron_h.OTHER_CHOICE_MAX = 64, 0.5
    cfg["layer_types"] = nemotron_h.layer_types(cfg)
    program, startup, _, _, check_names = nemotron_h.build(cfg, job)
    check = program.clone(for_test=True)
    exe = fluid.Executor(fluid.TPUPlace(0))
    reference = {}     # a control's keywords -> its jitted reference
    mambas = [i for i, kind in enumerate(cfg["layer_types"]) if kind == "mamba2"]
    groups = -(-nemotron_h.STAGE_HEADS // (cfg["mamba_num_heads"] // cfg["n_groups"]))
    limits = nemotron_h.LIMITS
    scan_limits = {k: v for k, v in limits.items() if k.startswith("scan_")}
    wanted = [name for name in (*SCAN_FAULTS, *ATTENTION_FAULTS, *REFERENCE_FAULTS) if not only or name in only]
    scan_alone = bool(only) and all(name in SCAN_FAULTS for name in only)

    def refused(found, held_to):
        return sorted({held_to[k] for k in held_to if not found[k] <= getattr(nemotron_h, held_to[k])})

    for nth, seed in enumerate(seeds):
        gc.collect()
        program.random_seed = startup.random_seed = seed
        scope = fluid.Scope()
        exe.run(startup, scope=scope)
        rows = nemotron_h.make_batch(np.random.RandomState(seed % 2**32), cfg, job, CHECK_ROWS)
        sound = exe.run(check, feed=rows, fetch_list=list(check_names), scope=scope)
        parameters = [tuple(np.asarray(scope.find_var(f"lm.l{i}.mamba2.{n}"))[:nemotron_h.STAGE_HEADS]
                            for n in ("a_log", "d", "dt_bias")) for i in (mambas[0], mambas[-1])]

        def staged(name):
            if name in SCAN_FAULTS:
                return scan_stage(sound, parameters, groups, cfg["chunk_size"], name[len("scan_"):])
            return attention_stage(sound, name[len("attention_"):])

        copy = scan_stage(sound, parameters, groups, cfg["chunk_size"], None)
        copy_differs = max(float(np.abs(np.asarray(mine, "f4") - np.asarray(theirs, "f4")).max())
                           for at in SCANS for mine, theirs in zip(copy[at][4:], sound[at][4:]))
        if nth or scan_alone:   # the scan stage alone, on its own operands: no reference
            for name in ("sound", *(n for n in wanted if n in SCAN_FAULTS)):
                fetched = sound if name == "sound" else staged(name)
                found = nemotron_h.scan_errors([fetched[at] for at in SCANS], *(np.stack(t) for t in zip(*parameters)),
                                               cfg["ssm_state_size"])
                print(json.dumps({"control": name, "seed": seed, "scan_stage_alone": True, "refused_by": refused(found, scan_limits),
                                  "copy_differs": copy_differs, **found}), flush=True)
        else:
            params = {p.name: scope.find_var(p.name) for p in program.all_parameters()}   # as they lie: split over the mesh
            batch = {k: np.asarray(v) for k, v in rows.items()}

            def want_of(**kw):
                key = tuple(sorted(kw.items()))
                if key not in reference:
                    reference[key] = jax.jit(lambda p, b: nemotron_h.reference(p, b, cfg, program, **kw))
                return [np.asarray(w) for w in reference[key](params, batch)]

            def report(name, mine, theirs):
                with contextlib.redirect_stdout(io.StringIO()) as said:
                    error = nemotron_h.reference_error(mine, theirs)
                found = json.loads(said.getvalue())
                found.pop("info")
                print(json.dumps({"control": name, "seed": seed, "correct": bool(error <= nemotron_h.REFERENCE_RTOL),
                                  "refused_by": refused(found, limits), "copy_differs": copy_differs, **found}), flush=True)

            want = want_of()
            report("sound", sound, want)
            for name in wanted:
                if name in REFERENCE_FAULTS:
                    report(name, sound, want_of(**REFERENCE_FAULTS[name]))
                else:
                    report(name, staged(name), want)
            del params
        del scope, sound, copy
        gc.collect()


def scan_alone_inputs(seed, rows, tokens, heads, width, groups, state):
    """The op's seven operands at a chip's shapes, bf16 activations: xs, B, C as
    a silu of unit normals leaves them, A = 1 .. 16 over the heads, the step's
    bias the inverse softplus of a log-uniform draw on [1e-3, 1e-1] (the
    mixer's own initialisation)."""
    r = np.random.RandomState(seed)
    silu = lambda t: t / (1.0 + np.exp(-t))                                               # noqa: E731
    step = np.exp(r.uniform(np.log(1e-3), np.log(1e-1), heads))
    activations = (silu(r.randn(rows, tokens, heads * width)), 0.5 * r.randn(rows, tokens, heads),
                   silu(r.randn(rows, tokens, groups * state)), silu(r.randn(rows, tokens, groups * state)))
    x, dt, b_t, c_t = (jnp.asarray(t, jnp.bfloat16) for t in activations)
    parameters = (np.log(np.linspace(1.0, 16.0, heads)), np.ones(heads), np.log(np.expm1(step)))
    a_log, d_skip, dt_bias = (jnp.asarray(t, jnp.float32) for t in parameters)
    return x, dt, a_log, b_t, c_t, d_skip, dt_bias


def own_ms(fn, *args, runs=3):
    """{HLO instruction: own device ms a run} of the jitted `fn(*args)`, from a
    trace of `runs` runs."""
    import shutil

    from benchmark.metrics.recompute_ms_per_step import own_times

    jax.block_until_ready(fn(*args))
    where = "chiprun_out/ssd_trace"
    os.makedirs(where, exist_ok=True)
    jax.profiler.start_trace(where)
    for _ in range(runs):
        out = fn(*args)
    jax.block_until_ready(out)
    jax.profiler.stop_trace()
    found = [os.path.join(base, f) for base, _, files in os.walk(where) for f in files if f.endswith(".xplane.pb")]
    spent = {}
    for plane in jax.profiler.ProfileData.from_file(max(found, key=os.path.getmtime)).planes:
        for line in plane.lines if plane.name.startswith("/device:TPU:0") else ():
            if line.name == "XLA Ops":
                events = [(e.name, e.start_ns, e.duration_ns) for e in line.events]
                for instruction, ns in own_times(events, (min(e[1] for e in events), max(e[1] + e[2] for e in events))):
                    spent[instruction] = spent.get(instruction, 0.0) + ns / (runs * 1e6)
    shutil.rmtree(where)
    return spent


def profile():
    """`ONLY=profile`: the op alone on one chip at a chip's shapes in the cell
    (a row of 8192 tokens, 128 heads of 64, a state of 128 in 8 groups, chunks
    of 128): own device ms of the forward kernel, of the forward that keeps the
    chunks' start states, of the transposed kernel, and of the plain form
    forward and through `jax.vjp`, each with its largest instructions; how far
    the kernels' results and seven gradients lie from the plain form's, and
    both forms' errors against the recurrence as the cell's scan stage reads
    them (on the first two groups' first 2048 tokens)."""
    from paddle_tpu.ops import ssd_ops

    rows, tokens, heads, width, groups, state, chunk = (2, 64, 8, 8, 2, 16, 16) if DRY else (1, 8192, 128, 64, 8, 128, 128)
    kernels = "interpret" if DRY else "tpu"
    args = scan_alone_inputs(1, rows, tokens, heads, width, groups, state)
    weigh = jnp.asarray(np.random.RandomState(2).randn(rows, tokens, heads * width), jnp.bfloat16)
    static = (groups, chunk, kernels, None)
    programs = {
        "kernel_forward": (jax.jit(lambda *a: ssd_ops.kernel_ssd_scan(*a, groups, chunk, kernels)), args),
        "kernel_forward_kept": (jax.jit(lambda *a: ssd_ops._kernel_scan_fwd(*a, *static)), args),
        "plain_forward": (jax.jit(lambda *a: ssd_ops.chunked_ssd_scan(*a, groups, chunk)), args),
        "plain_both": (jax.jit(lambda w, *a: jax.vjp(lambda *o: ssd_ops.chunked_ssd_scan(*o, groups, chunk)[0], *a)[1](w)), (weigh, *args)),
    }
    kept = programs["kernel_forward_kept"][0](*args)[1]
    programs["kernel_transposed"] = (jax.jit(lambda kept, w: ssd_ops._kernel_scan_bwd(*static, kept, (w, None, None))), (kept, weigh))
    results = {name: fn(*operands) for name, (fn, operands) in programs.items()}
    if not DRY:
        for name, (fn, operands) in programs.items():
            spent = own_ms(fn, *operands)
            print(json.dumps({"reading": "ssd_profile", "program": name, "own_ms_a_run": sum(spent.values()), "instructions": len(spent),
                              "top": sorted(spent.items(), key=lambda kv: -kv[1])[:6]}, default=float), flush=True)

    def apart(mine, theirs):
        mine, theirs = np.asarray(mine, "f4"), np.asarray(theirs, "f4")
        return float(np.sqrt(np.mean((mine - theirs) ** 2)) / max(np.sqrt(np.mean(theirs ** 2)), 1e-30))

    (y, final, means), (y_plain, final_plain, means_plain) = results["kernel_forward"], results["plain_forward"]
    names = ("x", "dt", "a_log", "b", "c", "d", "dt_bias")
    print(json.dumps({"reading": "ssd_kernels_from_plain", "y": apart(y, y_plain), "state": apart(final, final_plain),
                      "means": [apart(m, p) for m, p in zip(means, means_plain)],
                      "y_kept": apart(results["kernel_forward_kept"][0][0], y),
                      **{"d_" + n: apart(g, w) for n, g, w in zip(names, results["kernel_transposed"], results["plain_both"])}}), flush=True)
    x, dt, a_log, b_t, c_t, d_skip, dt_bias = args
    per, P = heads // groups, width
    cut = dict(t=min(tokens, 2048), h=min(heads, 2 * per), g=min(groups, 2))
    for name, (out, last) in {"kernels": (y, final), "plain": (y_plain, final_plain)}.items():
        stage = [np.asarray(t[:, :cut["t"], :w].astype(jnp.float32)) for t, w in
                 ((x, cut["h"] * P), (dt, cut["h"]), (b_t, cut["g"] * state), (c_t, cut["g"] * state))]
        form = ssd_ops.chunked_ssd_scan if name == "plain" else (lambda *a: ssd_ops.kernel_ssd_scan(*a, kernels=kernels))
        out, last, _ = jax.jit(form, static_argnums=(7, 8))(x[:, :cut["t"], :cut["h"] * P], dt[:, :cut["t"], :cut["h"]], a_log[:cut["h"]],
                                                            b_t[:, :cut["t"], :cut["g"] * state], c_t[:, :cut["t"], :cut["g"] * state],
                                                            d_skip[:cut["h"]], dt_bias[:cut["h"]], cut["g"], chunk)
        found = nemotron_h.scan_errors([[*stage, np.asarray(out), np.asarray(last)]], *(np.asarray(t[:cut["h"]])[None] for t in (a_log, d_skip, dt_bias)), state)
        print(json.dumps({"reading": "ssd_against_the_recurrence", "form": name,
                          **{k: found[k] for k in ("scan_state_error", "scan_error", "scan_error_unrounded")}}), flush=True)


if __name__ == "__main__":
    given = sys.argv[1:] or ["1"]
    if os.environ.get("ONLY") == "profile":
        profile()
    else:
        main([int(a) for a in given if a.isdigit()], tuple(a for a in given if not a.isdigit()))
