"""The index half of `index_alignment` ALONE on the chip: a chunk's term with its
three gradients (`ops/sparse_index_ops.py: chunk_divergence_and_gradients`) at a
chunk of Keye-VL-2.0's cell, 512 queries of 16 index heads of 64 in bf16
against 4096 / 8192 / 16384 keys, 2048 of them allowed a query (the causal edge
as a band's last chunk has it), a target that is zero on a tenth of them:

  * `jax.vjp`: what the op ran until PR 59, `jax.vjp` of `chunk_divergence`
    (the per-head products kept for backward, d_products [16, 512, keys]
    float32 through HBM);
  * `plain`: dI written out and `index_alignment_kernels.gradients_plain`
    (the products made again by XLA): what runs off the TPU;
  * `kernel`: the same with `index_alignment_kernels.gradients` (the products
    made again by key block in VMEM): what the chip runs.

ms a call, the median of five timings of eight calls in one program; each
form's d_qI, d_kI, d_w and term against `jax.vjp`'s on the chip (`differences`:
the largest difference over the largest value), since the benchmark's `correct`
reads the term and not its gradients; and the same comparison with a fault put
in, G formed WITHOUT the ReLU's mask (`without_relu_mask`;
`tools/chip_keye_controls.py`'s control `alignment_gradient_without_relu_mask`
reads it on the program's own operands), so that `GRADIENT_RTOL` stands between
a sound reading and a faulty one (PERF.md, section 6, PR 59).

    chiprun -- python3 tools/chip_index_alignment.py            DRY=1 rehearses it tiny on the CPU
"""
import contextlib
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

DRY = os.environ.get("DRY") == "1"

import jax
import jax.numpy as jnp
import numpy as np

from paddle_tpu.ops import index_alignment_kernels as iak
from paddle_tpu.ops import sparse_index_ops as sio

ROWS, HEADS, WIDTH, TOPK, CALLS = (128, 4, 64, 48, 2) if DRY else (512, 16, 64, 2048, 8)
WIDTHS = (256, 512) if DRY else (4096, 8192, 16384)
#: What a gradient of the kept form may differ from `jax.vjp`'s by, of its largest value: the sound readings on the chip
#: stand at 0.9e-4 to 1.2e-4 (d_w, float32 in both: dI sums to zero over a row, so r's last digit shows) and 2.9e-4 to
#: 7.8e-4 (d_qI, d_kI: a float32 G rounded to bf16 for the matrix unit in both, summed in another order), the fault's at
#: 0.28 to 1.09 (my chip runs, PR 59: PERF.md, section 6): forty times over the one, nine under the other.
GRADIENT_RTOL = 0.03
NAMES = ("term", "d_qI", "d_kI", "d_w")


def by_vjp(qi, ki, w, target, allowed):
    """(term, d_qI, d_kI, d_w) as `_alignment_row` made them until PR 59."""
    value, pull = jax.vjp(lambda *o: sio.chunk_divergence(*o, target, allowed), qi, ki, w)
    return (value,) + tuple(g.astype(jnp.float32) for g in pull(jnp.ones((), jnp.float32)))


def by_form(gradients):
    return lambda *operands: sio.chunk_divergence_and_gradients(*operands, gradients)


def kernel(*operands):
    return iak.gradients(*operands, interpret=DRY)


FORMS = {"jax.vjp": by_vjp, "plain": by_form(iak.gradients_plain), "kernel": by_form(kernel)}


@contextlib.contextmanager
def without_relu_mask():
    """The fault: both forms' G = w dI for every pair, whatever the product's sign."""
    real = iak._held
    iak._held = lambda products, d_scores: jnp.broadcast_to(d_scores, products.shape)
    jax.clear_caches()
    try:
        yield
    finally:
        iak._held = real
        jax.clear_caches()


def differences(got, want):
    """{name: max |got - want| / max |want|} of two forms' (term, d_qI, d_kI, d_w)."""
    return {name: float(jnp.abs(g.astype(jnp.float32) - t.astype(jnp.float32)).max() / jnp.abs(t.astype(jnp.float32)).max())
            for name, g, t in zip(NAMES, got, want)}


def operands(seed, keys):
    """CALLS chunks' (qI, kI, w, target, allowed): query r of a chunk sees keys 0 .. keys - ROWS + r and holds TOPK of them."""
    rng = np.random.RandomState(seed)
    qi = rng.randn(CALLS, ROWS, HEADS, WIDTH).astype("f4")
    ki = rng.randn(CALLS, keys, WIDTH).astype("f4")
    w = (rng.randn(CALLS, ROWS, HEADS) * HEADS ** -0.5 * WIDTH ** -0.5).astype("f4")
    causal = np.arange(keys) <= keys - ROWS + np.arange(ROWS)[:, None]
    chosen = np.where(causal, rng.rand(CALLS, ROWS, keys), 2.0)
    allowed = chosen <= np.sort(chosen, axis=-1)[..., TOPK - 1:TOPK]
    allowed[:, np.arange(ROWS), keys - ROWS + np.arange(ROWS)] = True
    target = np.where(allowed & (rng.rand(CALLS, ROWS, keys) > 0.1), rng.exponential(size=(CALLS, ROWS, keys)), 0.0)
    target[:, np.arange(ROWS), keys - ROWS + np.arange(ROWS)] += 1e-3       # no row without a held key
    target = (target / target.sum(-1, keepdims=True)).astype("f4")
    dtype = jnp.float32 if DRY else jnp.bfloat16
    return (jnp.asarray(qi, dtype), jnp.asarray(ki, dtype), jnp.asarray(w), jnp.asarray(target), jnp.asarray(allowed))


def ms_a_call(form, chunks):
    run = jax.jit(lambda chunks: jax.lax.map(lambda chunk: form(*chunk), chunks))
    jax.block_until_ready(run(chunks))
    timings = []
    for _ in range(5):
        start = time.perf_counter()
        jax.block_until_ready(run(chunks))
        timings.append((time.perf_counter() - start) * 1e3 / CALLS)
    return float(np.median(timings)), run(chunks)


def main():
    print(json.dumps({"info": "device", "platform": jax.devices()[0].platform, "kind": jax.devices()[0].device_kind}), flush=True)
    for keys in WIDTHS:
        chunks = operands(59 + keys, keys)
        line = {"rows": ROWS, "keys": keys, "heads": HEADS, "held": TOPK, "kernel_keys_a_block": iak._block(keys)}
        found = {}
        for name, form in FORMS.items():
            line[f"{name}: ms a call"], found[name] = ms_a_call(form, chunks)
        for name in ("plain", "kernel"):
            line[f"{name}: against jax.vjp"] = differences(found[name], found["jax.vjp"])
            with without_relu_mask():
                faulty = jax.jit(lambda chunks: jax.lax.map(lambda chunk: FORMS[name](*chunk), chunks))(chunks)
            line[f"{name}: against jax.vjp, without the ReLU's mask"] = differences(faulty, found["jax.vjp"])
        line["within GRADIENT_RTOL"] = all(v <= GRADIENT_RTOL for name in ("plain", "kernel")
                                           for v in line[f"{name}: against jax.vjp"].values())
        print(json.dumps({k: round(v, 4) if isinstance(v, float) else v for k, v in line.items()}), flush=True)


if __name__ == "__main__":
    main()
