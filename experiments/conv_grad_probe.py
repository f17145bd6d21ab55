"""Grad-filter conv probe for the hot ResNet-50 3x3 layers (bs=128):
compares XLA's native conv vjp against a manual shift+dot_general
formulation, chained K times inside one jit (arrays passed as ARGUMENTS —
closure capture would embed them as HLO constants and bloat the program
the compiler is handed)."""
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

SHAPES = [
    # (cin, hw, cout, k, stride, count): the 3x3 convs + the stem
    (3, 224, 64, 7, 2, 1),
    (64, 56, 64, 3, 1, 3),
    (128, 56, 128, 3, 2, 1),
    (128, 28, 128, 3, 1, 3),
    (256, 28, 256, 3, 2, 1),
    (256, 14, 256, 3, 1, 5),
    (512, 14, 512, 3, 2, 1),
    (512, 7, 512, 3, 1, 2),
]

BS = 128


def chain_time_k(make_step, arrs, k, reps=2):
    @jax.jit
    def run(s, n, *a):
        def body(i, ss):
            return make_step(ss, *a)
        return jax.lax.fori_loop(0, n, body, s)

    s = jnp.float32(0.0)
    n = jnp.int32(k)
    float(run(s, n, *arrs))  # compile+warm
    best = 1e9
    for _ in range(reps):
        t0 = time.perf_counter()
        r = float(run(s, n, *arrs))
        best = min(best, time.perf_counter() - t0)
        assert np.isfinite(r)
    return best


def chain_time(make_step, arrs):
    """Adaptive K: pilot at K=200, then size K so device work ~2s (the
    host dispatch jitter was ~±50ms on the r5 machine; bury it)."""
    pilot_k = 200
    t = chain_time_k(make_step, arrs, pilot_k, reps=1)
    per = max(t / pilot_k, 2e-6)
    k = int(min(max(2.0 / per, 200), 50000))
    return chain_time_k(make_step, arrs, k) / k


def main():
    rng = np.random.RandomState(0)
    base = chain_time(lambda s: s * 1.0000001, ())
    print(f"baseline per-iter overhead: {base*1e6:.1f} us", file=sys.stderr, flush=True)

    tot_gw = tot_man = 0.0
    for cin, hw, cout, k, stride, count in SHAPES:
        pad = (k - 1) // 2
        ohw = (hw + 2 * pad - k) // stride + 1
        x = jnp.asarray(rng.rand(BS, cin, hw, hw), jnp.bfloat16)
        w = jnp.asarray(rng.rand(cout, cin, k, k) * 0.01, jnp.bfloat16)
        dy = jnp.asarray(rng.rand(BS, cout, ohw, ohw) * 0.01, jnp.bfloat16)
        flops = 2 * BS * cout * cin * k * k * ohw * ohw

        def conv(xx, ww):
            return jax.lax.conv_general_dilated(
                xx, ww, window_strides=(stride, stride), padding=[(pad, pad), (pad, pad)],
                dimension_numbers=("NCHW", "OIHW", "NCHW"))

        def step_gw(s, xx, ww, dyy):
            def loss(wv):
                return jnp.sum(conv(xx * (1 + s * 1e-12).astype(xx.dtype), wv).astype(jnp.float32) * dyy.astype(jnp.float32))
            gw, = jax.vjp(loss, ww)[1](jnp.float32(1))
            return s + jnp.mean(gw.astype(jnp.float32)) * 1e-12

        def manual_gw(s, xx, dyy):
            xp = jnp.pad(xx * (1 + s * 1e-12).astype(xx.dtype),
                         ((0, 0), (0, 0), (pad, pad), (pad, pad)))
            outs = []
            for kh in range(k):
                for kw in range(k):
                    xs = jax.lax.slice(
                        xp, (0, 0, kh, kw),
                        (BS, cin, kh + (ohw - 1) * stride + 1, kw + (ohw - 1) * stride + 1),
                        (1, 1, stride, stride))
                    g = jax.lax.dot_general(
                        dyy, xs,
                        (((0, 2, 3), (0, 2, 3)), ((), ())),
                        preferred_element_type=jnp.float32)
                    outs.append(g)
            return jnp.stack(outs, -1).reshape(cout, cin, k, k)

        def step_man(s, xx, ww, dyy):
            g = manual_gw(s, xx, dyy)
            return s + jnp.mean(g) * 1e-12

        t_gw = chain_time(step_gw, (x, w, dy)) - base
        t_man = chain_time(step_man, (x, w, dy)) - base
        tot_gw += t_gw * count
        tot_man += t_man * count
        print(f"c{cin:4d} hw{hw:3d} c{cout:4d} k{k} s{stride} x{count}: "
              f"gw {flops/t_gw/1e12:6.1f}TF {t_gw*1e3:6.2f}ms | "
              f"man {flops/t_man/1e12:6.1f}TF {t_man*1e3:6.2f}ms",
              file=sys.stderr, flush=True)

    print(f"TOTAL weighted gw {tot_gw*1e3:.1f} ms vs manual {tot_man*1e3:.1f} ms",
          file=sys.stderr)


if __name__ == "__main__":
    main()
