"""Pallas TPU kernels for the chunked KDA scan (`ops/linear_attention_ops.py`
has the recurrence and the chunked form's equations; `_kda_path` there sends
the op here).

A grid step is a (row, group of heads, chunk), the chunks in order: it reads
its heads' 64 tokens of q, k, v [C, 128], the log decay g [C, 128] (or [C], one
number a head: below) and beta [C]
from the op's own `[b, T, H, .]` arrays (a 128-wide head is a whole lane tile
of `[b, T, H . 128]`: no transpose in HBM), makes in VMEM the cumulative decay
G, the decayed Grams M and P, T = (I + beta M)^-1, W, U, Kend and from them Phi,
B, Qe and the chunk's own output P U, and carries the float32 state S [K, V] a
head from chunk to chunk in VMEM scratch (the chunk axis is "arbitrary"):

  * `scan`: o = P U + Qe S in v's dtype and S' = Phi S + B; the state after the
    last token out.  With `keep` (the op's forward where it is differentiated:
    `linear_attention_ops._chunked_kda_fwd`) what backward reads too, all that
    is kept beside the inputs: the state every chunk STARTS from, [n, b, H, K,
    V] float32 from the scratch as the grid step finds it, and T, whose ten
    dependent products are a sixth of the transposed kernel's time where it
    makes them again (`_unit_lower_inverses`), [n, b, H, C / 2, 2 C] (its
    upper rows beside its lower: `_halves_side_by_side`);
  * `scan_transposed`: the chunks in REVERSE order, the five inputs, d o and
    what forward kept in; the other terms made again in VMEM, the state's
    cotangent lambda [K, V] a head carried in scratch (lambda_c = Phi_c^T
    lambda_{c+1} + Qe_c^T dO_c), and the transpose of the chunk's own terms
    written out by hand (a dozen products; T's transpose is a product with
    T^T; the decay's gradient from the Grams is x . dx - k . dk); dq, dk, dv,
    dg, dbeta out.

The decay is a channel's or a head's, and q and k may have fewer heads than
v (Gated DeltaNet: ISSUE 69).  A decay a head comes as g `[b, T, H]`, a head's
column of the block that beta's comes from; its cumulative sum G is a column
`[C, 1]`, every exponential of it one number a row, and it factors OUT of the
Grams: M = (k k^T) . D, P = (q k^T) . D, D[r, i] = exp(G[r] - G[i]) on and
under the diagonal (`_Chunks._scalar_grams`: one product a KEY head and a
`[C, C]` matrix a value head; no block of 16 rows, no key carried back, no
`lax.cond`, no exponent positive whatever the decay), and its gradient goes out
as the head's row `[1, C]` a chunk, as beta's does.  Where `shared` value heads
read one key head (value head h the key head h div shared), a grid step's
blocks of q and k hold its group's key heads alone (`heads / shared` of them:
the index map, nothing repeated in HBM) and a key head's dq and dk are its value
heads' summed in float32 before they are rounded and written.

Precision is the op's: float32 operands in VMEM, every product at the
precision `linear_attention_ops` states (`HIGHEST`: Mosaic's float32
contraction), the state float32, G a float32 sum (three exact bf16 products
with the triangle of ones).  No exponent is positive but the block's own
keys', carried back to the block's first row (`_decayed_grams`' form: at most
`safe` nats); a grid step in which a channel decays by more than that inside
a block of 16 takes, by ONE `lax.cond` INSIDE the kernel, the block's own pairs
from their differences instead, a key at a time (64 steps of a loop over
[C, 128]: rare, and priced as rare).

Each call is a `jax.jit` of its own so that a model's layers, the step and its
`for_test` clone share one lowering (`ops/pallas_attention.py: _fwd_call` has
why), and no index map divides.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

F32, BF16 = jnp.float32, jnp.bfloat16
_NN = (((1,), (0,)), ((), ()))
_NT = (((1,), (1,)), ((), ()))
_TN = (((0,), (0,)), ((), ()))

#: Heads a grid step: their chains of small products (T's ten above all)
#: interleave, and a DMA's rows are 4 x 256 bytes long.  TPU v5e, (1, 4096, 32,
#: 128), ms forward | backward of the op alone at 1, 2, 4 heads: 6.44 | 15.92,
#: 4.70 | 11.98, 4.07 | 10.70; at 8 the transposed kernel overruns its VMEM (my
#: chip run, PR 44; `HEADS=1 python3 tools/chip_kimi_kernels.py`).  At Qwen3-Next's
#: (1, 16384, 32, 128) with 16 key heads and a decay a HEAD (`ONLY=gdn` of the
#: tool, my chip run, PR 69), forward | backward at 2, 4, 8 value heads a step:
#: 16.09 | 20.88, 14.19 | 19.08, 13.76 | 18.63 (the scalar form's terms are small
#: enough for 8 to fit; 3% is not worth a second constant), against 16.45 | 25.67
#: for the same decay written out over the channels and q and k repeated to 32
#: heads in HBM through the decay-a-channel form at 4: 19% less both ways.
_HEADS = 4


def _iota(shape, axis):
    return jax.lax.broadcasted_iota(jnp.int32, shape, axis)


def _sums(ones, x, dims=_NN):
    """`ones` (a 0/1 matrix, exact in bf16) times float32 `x`, to float32's
    bits: x in three bf16 pieces, every product exact, summed in float32."""
    hi = x.astype(BF16)
    rest = x - hi.astype(F32)
    mid = rest.astype(BF16)
    low = (rest - mid.astype(F32)).astype(BF16)
    ones = ones.astype(BF16)
    return sum(jax.lax.dot_general(ones, piece, dims, preferred_element_type=F32) for piece in (hi, mid, low))


def cumulative(g, lower):
    """The log decay summed from the chunk's first token to each token,
    inclusive (a seam of its own, as `linear_attention_ops._cumulative` is, so
    that the controls can round it: tools/chip_kimi_controls.py)."""
    return _sums(lower, g)


def carried(state):
    """The state as one chunk hands it to the next: float32 (the other seam the
    controls round)."""
    return state


class _Chunks:
    """The terms of a grid step's heads, one chunk each, in VMEM: from lists of
    float32 q, k [C, K], v [C, V], g [C, K] and beta [C, 1] a head; `sub` rows a
    block, `seams` = (the products' precision, the cumulative decay's function,
    the carried state's); `T`: the heads' (I + beta M)^-1 where forward kept them."""

    def __init__(self, heads, sub, safe, seams, T=None, scalar=False, shared=1):
        self.precision, cumulative_fn, self.carried = seams
        self.heads = range(len(heads))
        self.q, self.k, self.v, _, self.beta = (list(t) for t in zip(*heads))
        C, K = self.k[0].shape
        self.C, self.K, self.sub, self.n, self.scalar, self.shared = C, K, sub, C // sub, scalar, shared
        row, col = self.row, self.col = _iota((C, C), 0), _iota((C, C), 1)
        self.lower, self.strict, self.eye = row >= col, row > col, row == col
        self.own = (row // sub) == (col // sub)                   # pairs inside one block
        self.at_row = row[:, :1]
        self.G = [cumulative_fn(g, self.lower) for _, _, _, g, _ in heads]
        if scalar:      # one decay a head: G a column [C, 1], and every term below that reads it spreads it over the channels
            self.G = [G[:, :1] for G in self.G]
        self.from_start = [jnp.exp(G) for G in self.G]
        self.to_end = [jnp.exp(G[C - 1:C] - G) for G in self.G]
        # the last row's, a row [1, K] either way (Mosaic spreads a [1, 1] over one axis, not over both)
        self.last = [jnp.broadcast_to(G[C - 1:C], (1, K)) for G in self.G]
        self.k_end = [k * e for k, e in zip(self.k, self.to_end)]
        if scalar:
            grams = self._scalar_grams()
        else:
            self._blocks(safe)
            grams = self._grams()
        self.M = [jnp.where(self.strict, M, 0.0) for M, _ in grams]
        self.P = [jnp.where(self.lower, P, 0.0) for _, P in grams]
        self.T = self._unit_lower_inverses([beta * M for beta, M in zip(self.beta, self.M)]) if T is None else T
        self.X = self._solved()                                                              # [W | U]
        # Phi [K, K], B [K, V], Qe [C, K], the chunk's own output P U [C, V]
        eye_K = self.eye_K = _iota((K, K), 0) == _iota((K, K), 1)
        ends = [self.dot(k_end, X, _TN) for k_end, X in zip(self.k_end, self.X)]             # [K, K + V]
        reads = [self.dot(P, X) for P, X in zip(self.P, self.X)]                             # [C, K + V]
        self.phi = [jnp.where(eye_K, jnp.exp(last), 0.0) - e[:, :K] for last, e in zip(self.last, ends)]
        self.B = [e[:, K:] for e in ends]
        self.q_eff = [q * s - r[:, :K] for q, s, r in zip(self.q, self.from_start, reads)]
        self.own_out = [r[:, K:] for r in reads]

    def _blocks(self, safe):
        """A decay a channel: a block's rows decayed from its first row, and every key carried to that row (back, for the
        block's own)."""
        sub = self.sub
        self.leave, self.back, self.strong = [], [], []
        for G in self.G:
            firsts = [G[a * sub:a * sub + 1] for a in range(self.n)]
            self.leave.append([jnp.exp(G[self.rows_of(a)] - firsts[a]) for a in range(self.n)])
            self.back.append([jnp.exp(jnp.minimum(firsts[a] - G, safe)) for a in range(self.n)])
            inside = jnp.concatenate([firsts[a] - G[(a + 1) * sub - 1:(a + 1) * sub] for a in range(self.n)], axis=0)
            self.strong.append(jnp.max(inside) > safe)
        # block a's rows of k over those of q, decayed from its first row [2 sub, K]; every key carried to that row [C, K]
        self.near = [[jnp.concatenate([k[self.rows_of(a)] * leave[a], q[self.rows_of(a)] * leave[a]], axis=0)
                      for a in range(self.n)] for q, k, leave in zip(self.q, self.k, self.leave)]
        self.keys_at = [[k * back[a] for a in range(self.n)] for k, back in zip(self.k, self.back)]
        self.any_strong = functools.reduce(jnp.logical_or, self.strong)

    def _scalar_grams(self):
        """A decay a head factors OUT of the Grams: M = (k k^T) . D and P = (q k^T) . D with D[r, i] = exp(G[r] - G[i])
        on and under the diagonal (no exponent positive), one product a KEY head (the value heads that share it take
        the same k k^T and q k^T) and a [C, C] matrix a value head: no block, no key carried back, no `lax.cond`."""
        self.decay, grams = [], []
        for h in self.heads:
            across = jnp.sum(jnp.where(self.eye, self.G[h], 0.0), axis=0, keepdims=True)      # G as a row [1, C]
            self.decay.append(jnp.where(self.lower, jnp.exp(jnp.where(self.lower, self.G[h] - across, 0.0)), 0.0))
            if h % self.shared == 0:
                both = self.dot(jnp.concatenate([self.k[h], self.q[h]], axis=0), self.k[h], _NT)   # [2 C, C]
            grams.append((both[:self.C] * self.decay[h], both[self.C:] * self.decay[h]))
        return grams

    def dot(self, a, b, dims=_NN):
        return jax.lax.dot_general(a, b, dims, precision=self.precision, preferred_element_type=F32)

    def rows_of(self, a):
        return slice(a * self.sub, (a + 1) * self.sub)

    def from_key(self, h, t):
        """(exp(G[r] - G[t]) for the rows r of t's block at or below it [C, K],
        none of them positive; k[t] [1, K]; those rows [C, 1]) of head h."""
        at = self.at_row == t
        G_t = jnp.sum(jnp.where(at, self.G[h], 0.0), axis=0, keepdims=True)
        k_t = jnp.sum(jnp.where(at, self.k[h], 0.0), axis=0, keepdims=True)
        below = (self.at_row >= t) & ((self.at_row // self.sub) == (t // self.sub))
        return jnp.where(below, jnp.exp(jnp.minimum(self.G[h] - G_t, 0.0)), 0.0), k_t, below

    def _grams(self):
        sub = self.sub
        grams = []
        for h in self.heads:
            strips = [self.dot(self.near[h][a], self.keys_at[h][a], _NT) for a in range(self.n)]   # [2 sub, C]
            grams += [jnp.concatenate([s[:sub] for s in strips], axis=0), jnp.concatenate([s[sub:] for s in strips], axis=0)]

        def by_differences(*grams):
            def key(t, own):
                out = []
                for h in self.heads:
                    decay, k_t, below = self.from_key(h, t)
                    here = (self.col == t) & below
                    out += [jnp.where(here, jnp.sum(self.k[h] * decay * k_t, axis=1, keepdims=True), own[2 * h]),
                            jnp.where(here, jnp.sum(self.q[h] * decay * k_t, axis=1, keepdims=True), own[2 * h + 1])]
                return tuple(out)

            own = jax.lax.fori_loop(0, self.C, key, tuple(jnp.zeros_like(t) for t in grams))
            return tuple(jax.lax.cond(self.strong[i // 2], lambda o, t: jnp.where(self.own, o, t), lambda o, t: t, o, t)
                         for i, (o, t) in enumerate(zip(own, grams)))

        grams = jax.lax.cond(self.any_strong, by_differences, lambda *grams: grams, *grams)
        return [(grams[2 * h], grams[2 * h + 1]) for h in self.heads]

    def _unit_lower_inverses(self, As):
        """(I + a)^-1 of each strictly lower triangular [C, C], C a power of two,
        from the diagonal blocks' inverses, doubling the block: the inverse of
        [[1, 0], [a21, 1]]-blocks of 2m rows is T - T a21 T of the m-row
        blocks' T (ten products, as many as the doubling of powers that
        `linear_attention_ops._unit_lower_inverse` makes; but every factor is an
        inverse, as small as the result, where the powers of a grow by
        binomials before they cancel: with the program's keys, neighbours'
        mixtures, that form read 4.7e-3 against the recurrence here where the
        `jax.numpy` form reads 6.2e-4: my chip run, PR 44).  The heads' chains
        a level at a time, so that their products interleave."""
        block = lambda m: self.row // m == self.col // m
        inverses, m = [self.eye.astype(F32) - jnp.where(block(2), a, 0.0) for a in As], 2
        while m < self.C:
            below = block(2 * m) & ~block(m)
            steps = [self.dot(t, jnp.where(below, a, 0.0)) for t, a in zip(inverses, As)]
            inverses = [t - self.dot(step, t) for t, step in zip(inverses, steps)]
            m *= 2
        return inverses

    def _solved(self):
        """[W | U] = T [beta k exp(G) | beta v], [C, K + V] a head."""
        return [self.dot(T, jnp.concatenate([beta * k * e, beta * v], axis=1))
                for T, beta, k, e, v in zip(self.T, self.beta, self.k, self.from_start, self.v)]

    def transposed(self, d_phi, d_b, d_qe, d_own):
        """Lists a head of (dq, dk, dv, dg [C, .], dbeta [C, 1]) from lists of the
        four outputs' cotangents."""
        K, sub = self.K, self.sub
        found, grams = [], []
        for h in self.heads:
            q, k, v, beta, X = self.q[h], self.k[h], self.v[h], self.beta[h], self.X[h]
            d_ends = jnp.concatenate([-d_phi[h], d_b[h]], axis=1)                             # [K, K + V]
            d_reads = jnp.concatenate([-d_qe[h], d_own[h]], axis=1)                           # [C, K + V]
            d_X = self.dot(self.k_end[h], d_ends) + self.dot(self.P[h], d_reads, _TN)
            d_k_end = self.dot(X, d_ends, _NT)                                                # [C, K]
            d_P = jnp.where(self.lower, self.dot(d_reads, X, _NT), 0.0)
            d_R = self.dot(self.T[h], d_X, _TN)                                               # T^T d[W | U]
            d_A = jnp.where(self.strict, -self.dot(d_R, X, _NT), 0.0)
            d_M = beta * d_A
            d_Rw, d_Ru = d_R[:, :K], d_R[:, K:]
            d_last = (jnp.sum(jnp.where(self.eye_K, d_phi[h], 0.0), axis=0, keepdims=True) * jnp.exp(self.last[h])
                      + jnp.sum(d_k_end * self.k_end[h], axis=0, keepdims=True))
            # the Grams' transpose, rows' side (k under M, q under P) and keys' side apart for the decay's gradient
            if self.scalar:
                d_strip = jnp.concatenate([d_M * self.decay[h], d_P * self.decay[h]], axis=0)  # [2 C, C]
                d_near = self.dot(d_strip, k)
                grams += [d_near[:self.C], d_near[self.C:], self.dot(d_strip, jnp.concatenate([k, q], axis=0), _TN)]
            else:   # the block's own pairs are left to the loop below where they came from it
                far = jnp.where(self.own, 1.0 - self.strong[h].astype(F32), 1.0)
                rows_k, rows_q, keys = [], [], jnp.zeros_like(k)
                for a in range(self.n):
                    d_strip = jnp.concatenate([d_M[self.rows_of(a)], d_P[self.rows_of(a)]], axis=0) * jnp.concatenate(
                        [far[self.rows_of(a)]] * 2, axis=0)                                   # [2 sub, C]
                    d_near = self.dot(d_strip, self.keys_at[h][a])
                    rows_k.append(d_near[:sub] * self.leave[h][a])
                    rows_q.append(d_near[sub:] * self.leave[h][a])
                    keys = keys + self.dot(d_strip, self.near[h][a], _TN) * self.back[h][a]
                grams += [jnp.concatenate(rows_k, axis=0), jnp.concatenate(rows_q, axis=0), keys]
            found.append((d_M, d_P, d_k_end, d_last, d_A, d_Rw, d_Ru))

        def by_differences(*grams):
            def key(t, sums):
                out = []
                for h in self.heads:
                    d_M, d_P = found[h][:2]
                    decay, k_t, below = self.from_key(h, t)
                    decay = decay * self.strong[h].astype(F32)
                    at = self.col == t
                    c_M = jnp.where(below, jnp.sum(jnp.where(at, d_M, 0.0), axis=1, keepdims=True), 0.0)
                    c_P = jnp.where(below, jnp.sum(jnp.where(at, d_P, 0.0), axis=1, keepdims=True), 0.0)
                    to_key = jnp.sum((c_M * self.k[h] + c_P * self.q[h]) * decay, axis=0, keepdims=True)
                    out += [sums[3 * h] + c_M * decay * k_t, sums[3 * h + 1] + c_P * decay * k_t,
                            sums[3 * h + 2] + jnp.where(self.at_row == t, to_key, 0.0)]
                return tuple(out)

            return jax.lax.fori_loop(0, self.C, key, grams)

        if not self.scalar:
            grams = jax.lax.cond(self.any_strong, by_differences, lambda *grams: grams, *grams)
        out = []
        for h in self.heads:
            q, k, v, beta = self.q[h], self.k[h], self.v[h], self.beta[h]
            rows_k, rows_q, keys = grams[3 * h:3 * h + 3]
            _, _, d_k_end, d_last, d_A, d_Rw, d_Ru = found[h]
            d_G = ((d_qe[h] * q + d_Rw * beta * k) * self.from_start[h] - d_k_end * self.k_end[h]
                   + k * rows_k + q * rows_q - k * keys + jnp.where(self.at_row == self.C - 1, d_last, 0.0))
            if self.scalar:     # the channels' sum, then the sum from each token on, as the head's ROW [1, C]: float32 sums
                d_g = jnp.sum(jnp.where(self.lower, jnp.sum(d_G, axis=1, keepdims=True), 0.0), axis=0, keepdims=True)
            else:
                d_g = _sums(self.lower, d_G, _TN)                                             # the sum from each token on
            d_q = d_qe[h] * self.from_start[h] + rows_q
            d_k = d_Rw * beta * self.from_start[h] + d_k_end * self.to_end[h] + rows_k + keys
            d_beta = (jnp.sum(d_A * self.M[h], axis=1, keepdims=True)
                      + jnp.sum(d_Rw * k * self.from_start[h], axis=1, keepdims=True) + jnp.sum(d_Ru * v, axis=1, keepdims=True))
            out.append((d_q, d_k, d_Ru * beta, d_g, d_beta))
        return out


def _halves_side_by_side(t):
    """[C, C] as [C / 2, 2 C], the upper rows beside the lower: how T is kept (a
    whole lane tile wide at C = 64; 64 lanes wide it is padded to twice its
    bytes in HBM: `peak_hbm_gb` 13.47 | 13.33 at the same rate, my chip runs, PR 45)."""
    half = t.shape[0] // 2
    return jnp.concatenate([t[:half], t[half:]], axis=1)


def _halves_stacked(t):
    """`_halves_side_by_side`'s inverse."""
    half = t.shape[1] // 2
    return jnp.concatenate([t[:, :half], t[:, half:]], axis=0)


def _heads_of(refs, heads, widths, group, scalar, shared):
    """A list, a VALUE head of the grid step's group, of float32 (q, k, v, g [C,
    .], beta [C, 1]) from the step's blocks: v `[1, C, heads . width]` of `[b, T,
    H . width]`, q and k `[1, C, heads / shared . width]` (`shared` value heads
    read one key head: head j of the group reads the block's key head j div
    shared), beta `[1, C, H]`; g as v where the decay is a channel's, as beta
    where it is ONE number a head (`[1, C, H]`: the head's column spread over
    the channels' width for the cumulative sum's product)."""
    q_ref, k_ref, v_ref, g_ref, beta_ref = refs
    k_width, v_width = widths

    def column(ref, j):     # [C, 1], the head's lane of a [1, C, H] block
        block = ref[0].astype(F32)
        return jnp.sum(jnp.where(_iota(block.shape, 1) == group * heads + j, block, 0.0), axis=1, keepdims=True)

    def lanes(ref, j, width):
        return ref[0, :, j * width:(j + 1) * width].astype(F32)

    return [(lanes(q_ref, j // shared, k_width), lanes(k_ref, j // shared, k_width), lanes(v_ref, j, v_width),
             jnp.broadcast_to(column(g_ref, j), (g_ref.shape[1], k_width)) if scalar else lanes(g_ref, j, k_width),
             column(beta_ref, j)) for j in range(heads)]


def _step_chunks(heads, sub, safe, seams, scalar, shared, refs, T=None):
    """(`_Chunks` of a grid step's value heads, K, V) from its five input blocks."""
    K, V = refs[0].shape[-1] * shared // heads, refs[2].shape[-1] // heads
    return _Chunks(_heads_of(refs, heads, (K, V), pl.program_id(1), scalar, shared), sub, safe, seams, T, scalar, shared), K, V


def _scan_kernel(heads, sub, safe, seams, scalar, shared, q_ref, k_ref, v_ref, g_ref, beta_ref, o_ref, final_ref, *rest):
    """`rest`: the blocks of the chunk's start states and T where they are kept, and the state's scratch."""
    chunks, K, V = _step_chunks(heads, sub, safe, seams, scalar, shared, (q_ref, k_ref, v_ref, g_ref, beta_ref))
    *kept, state = rest

    @pl.when(pl.program_id(2) == 0)
    def _():
        state[...] = jnp.zeros_like(state)

    for h in chunks.heads:
        S = state[h]
        if kept:
            kept[0][0, 0, h] = S
            kept[1][0, 0, h] = _halves_side_by_side(chunks.T[h])
        o_ref[0, :, h * V:(h + 1) * V] = (chunks.own_out[h] + chunks.dot(chunks.q_eff[h], S)).astype(o_ref.dtype)
        state[h] = chunks.carried(chunks.dot(chunks.phi[h], S) + chunks.B[h])

    @pl.when(pl.program_id(2) == pl.num_programs(2) - 1)
    def _():
        final_ref[0] = state[...]


def _transposed_kernel(heads, sub, safe, seams, scalar, shared, q_ref, k_ref, v_ref, g_ref, beta_ref, d_o_ref, starts_ref,
                       t_ref, dq_ref, dk_ref, dv_ref, dg_ref, dbeta_ref, after):
    chunks, K, V = _step_chunks(heads, sub, safe, seams, scalar, shared, (q_ref, k_ref, v_ref, g_ref, beta_ref),
                                T=[_halves_stacked(t_ref[0, 0, h]) for h in range(heads)])

    @pl.when(pl.program_id(2) == 0)
    def _():
        after[...] = jnp.zeros_like(after)

    d_own = [d_o_ref[0, :, h * V:(h + 1) * V].astype(F32) for h in chunks.heads]
    starts = [starts_ref[0, 0, h] for h in chunks.heads]
    ends = [after[h] for h in chunks.heads]        # the cotangent of the state this chunk ENDS in, which is B's
    d_phi = [chunks.dot(e, s, _NT) for e, s in zip(ends, starts)]
    d_qe = [chunks.dot(d, s, _NT) for d, s in zip(d_own, starts)]
    C = d_own[0].shape[0]
    diagonal = _iota((C, C), 0) == _iota((C, C), 1)
    found = chunks.transposed(d_phi, ends, d_qe, d_own)
    for j in range(heads // shared):    # a key head's gradient: its value heads' summed in float32, rounded once
        mine = found[j * shared:(j + 1) * shared]
        dq_ref[0, :, j * K:(j + 1) * K] = sum(d_q for d_q, *_ in mine).astype(dq_ref.dtype)
        dk_ref[0, :, j * K:(j + 1) * K] = sum(d_k for _, d_k, *_ in mine).astype(dk_ref.dtype)
    for h, (_, _, d_v, d_g, d_beta) in enumerate(found):
        dv_ref[0, :, h * V:(h + 1) * V] = d_v.astype(dv_ref.dtype)
        if scalar:      # as beta's: the (head, chunk)'s row [1, C]
            dg_ref[0, 0, 0, h:h + 1, :] = d_g
        else:
            dg_ref[0, :, h * K:(h + 1) * K] = d_g
        # beta's gradient as the (head, chunk)'s row [1, C]: the column laid on the diagonal and summed down
        dbeta_ref[0, 0, 0, h:h + 1, :] = jnp.sum(jnp.where(diagonal, d_beta, 0.0), axis=0, keepdims=True)
        after[h] = chunks.dot(chunks.phi[h], ends[h], _TN) + chunks.dot(chunks.q_eff[h], d_own[h], _TN)


def _flat(t):
    """[b, T, H, width] as [b, T, H . width]: the same bytes."""
    return t.reshape(t.shape[0], t.shape[1], -1)


def heads_a_step(H, shared=1):
    """Value heads a grid step, of `H` of which `shared` read one key head: a
    whole number of key heads; None where no count of (`_HEADS`, 2, 1) is."""
    return next((g for g in (_HEADS, 2, 1) if H % g == 0 and g % shared == 0), None)


def _cost(b, T, H, K, V, chunk, times, state_bytes):
    a_chunk = chunk * chunk * (4 * K + 20 * chunk + 4 * (K + V)) + 2 * chunk * K * (K + V) + 2 * (K + chunk) * K * V
    return pl.CostEstimate(flops=int(times * a_chunk * b * H * T // chunk), transcendentals=int(8 * b * T * H * K),
                           bytes_accessed=int(times * b * T * H * (8 * K + 4 * V) + state_bytes))


#: The transposed kernel at four heads a grid step holds more than the 16 MB a
#: kernel gets unasked (at eight it overran them alone: my chip run, PR 44).
_SEMANTICS = pltpu.CompilerParams(dimension_semantics=("parallel", "parallel", "arbitrary"), vmem_limit_bytes=48 * 2 ** 20)


@functools.partial(jax.jit, static_argnums=(5, 6, 7, 8, 9, 10))
def scan(q, k, v, g, beta, chunk, sub, safe, seams, keep, interpret):
    """(o [b, T, H, V] in v's dtype, the state after the last token [b, H, K, V]
    float32) of v [b, T, H, V], q, k [b, T, H / shared, K] (`shared` value
    heads read one key head, head h the key head h div shared: the index map
    hands a group its key heads, nothing is repeated in HBM), the float32 log
    decay g [b, T, H, K] a channel or [b, T, H] a head (its rank says which) and
    beta [b, T, H]; with `keep` the state every chunk starts from [n, b, H, K,
    V] and T [n, b, H, C / 2, 2 C] (`_halves_side_by_side`), float32, after
    them (the same kernel with two more output blocks a grid step: o and the
    final state are the plain call's)."""
    (b, T, key_heads, K), (H, V) = k.shape, v.shape[2:]
    scalar, shared = g.ndim == 3, H // key_heads
    n, heads = T // chunk, heads_a_step(H, shared)

    def tokens(width, heads=heads):      # [b, T, H . width]: the chunk's rows, the group's lanes
        return pl.BlockSpec((1, chunk, heads * width), lambda i, h, c: (i, c, h))

    a_head = pl.BlockSpec((1, chunk, H), lambda i, h, c: (i, c, 0))
    out_specs = [tokens(V), pl.BlockSpec((1, heads, K, V), lambda i, h, c: (i, h, 0, 0))]
    out_shape = [jax.ShapeDtypeStruct((b, T, H * V), v.dtype), jax.ShapeDtypeStruct((b, H, K, V), F32)]
    for rows, width in ((K, V), (chunk // 2, 2 * chunk)) if keep else ():
        out_specs.append(pl.BlockSpec((1, 1, heads, rows, width), lambda i, h, c: (c, i, h, 0, 0)))
        out_shape.append(jax.ShapeDtypeStruct((n, b, H, rows, width), F32))
    o, *rest = pl.pallas_call(
        functools.partial(_scan_kernel, heads, sub, safe, seams, scalar, shared), grid=(b, H // heads, n),
        in_specs=[tokens(K, heads // shared), tokens(K, heads // shared), tokens(V), a_head if scalar else tokens(K), a_head],
        out_specs=out_specs, out_shape=out_shape, scratch_shapes=[pltpu.VMEM((heads, K, V), F32)],
        compiler_params=_SEMANTICS, cost_estimate=_cost(b, T, H, K, V, chunk, 1, 4 * b * H * (K * V + n * keep * (K * V + chunk * chunk))),
        name="kda_scan", interpret=interpret,
    )(_flat(q), _flat(k), _flat(v), g if scalar else _flat(g), beta)
    return (o.reshape(v.shape), *rest)


@functools.partial(jax.jit, static_argnums=(8, 9, 10, 11, 12))
def scan_transposed(q, k, v, g, beta, d_o, starts, inverses, chunk, sub, safe, seams, interpret):
    """(dq, dk, dv, dg, dbeta) in the five inputs' shapes (dq, dk, dv in theirs'
    dtypes, dg and dbeta float32) of d o [b, T, H, V] and what `scan(keep=True)`
    kept from forward, the chunks' start states [n, b, H, K, V] and T
    [n, b, H, C / 2, 2 C], beside the inputs."""
    (b, T, key_heads, K), (H, V) = k.shape, v.shape[2:]
    scalar, shared = g.ndim == 3, H // key_heads
    n, heads = T // chunk, heads_a_step(H, shared)

    def tokens(width, heads=heads):      # the chunks in reverse order
        return pl.BlockSpec((1, chunk, heads * width), lambda i, h, c: (i, n - 1 - c, h))

    def kept(rows, width):
        return pl.BlockSpec((1, 1, heads, rows, width), lambda i, h, c: (n - 1 - c, i, h, 0, 0))

    a_head = pl.BlockSpec((1, chunk, H), lambda i, h, c: (i, n - 1 - c, 0))
    rows = pl.BlockSpec((1, 1, 1, heads, chunk), lambda i, h, c: (i, n - 1 - c, h, 0, 0))   # a head's row [1, C] a chunk
    a_row = jax.ShapeDtypeStruct((b, n, H // heads, heads, chunk), F32)
    dq, dk, dv, dg, dbeta = pl.pallas_call(
        functools.partial(_transposed_kernel, heads, sub, safe, seams, scalar, shared), grid=(b, H // heads, n),
        in_specs=[tokens(K, heads // shared), tokens(K, heads // shared), tokens(V), a_head if scalar else tokens(K), a_head,
                  tokens(V), kept(K, V), kept(chunk // 2, 2 * chunk)],
        out_specs=[tokens(K, heads // shared), tokens(K, heads // shared), tokens(V), rows if scalar else tokens(K), rows],
        out_shape=[jax.ShapeDtypeStruct(_flat(t).shape, t.dtype) for t in (q, k, v)]
        + [a_row if scalar else jax.ShapeDtypeStruct(_flat(g).shape, g.dtype), a_row],
        scratch_shapes=[pltpu.VMEM((heads, K, V), F32)], compiler_params=_SEMANTICS,
        cost_estimate=_cost(b, T, H, K, V, chunk, 3, 4 * b * H * n * (K * V + chunk * chunk)), name="kda_scan_transposed",
        interpret=interpret,
    )(_flat(q), _flat(k), _flat(v), g if scalar else _flat(g), beta, _flat(d_o), starts, inverses)

    def by_token(t):    # [b, n, H / heads, heads, C] -> [b, T, H]
        return t.reshape(b, n, H, chunk).swapaxes(2, 3).reshape(b, T, H)

    dbeta = by_token(dbeta)
    return dq.reshape(q.shape), dk.reshape(k.shape), dv.reshape(v.shape), by_token(dg) if scalar else dg.reshape(g.shape), dbeta
