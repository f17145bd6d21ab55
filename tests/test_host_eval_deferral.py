"""In-graph XXH64 goldens: the `hash` op's digest is vectorized uint32-pair
arithmetic inside the compiled program, pinned against the numpy spec oracle.

(The fetch-time host evaluation this file used to test — sink ops pruned out
of device programs for a runtime without host callbacks — went in PR 21:
chip_smoke.py shows jax.pure_callback running inside a TPU program.)
"""
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu.core.program import program_guard
from paddle_tpu.ops.misc_ops import _xxh64


# --- in-graph XXH64 (runs on any backend; no callback) ---------------------

@pytest.mark.parametrize("last,mod", [
    (2, 1000),            # short input (n < 32)
    (8, 2_000_000_011),   # exactly one 32-byte block, mod near 2^31
    (11, 999_983),        # block + 8-byte lane + 4-byte tail
    (9, 2**31 - 1),       # block + 4-byte tail, max mod
])
def test_hash_in_graph_matches_spec_oracle(last, mod):
    rng = np.random.RandomState(last)
    x = rng.randint(-2**31, 2**31, size=(5, last)).astype("int32")
    main, startup = fluid.Program(), fluid.Program()
    with program_guard(main, startup):
        xv = fluid.layers.data("x", [last], dtype="int32")
        out = fluid.layers.hash(xv, hash_size=mod, num_hash=2)
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    exe.run(startup, scope=scope)
    (got,) = exe.run(main, feed={"x": x}, fetch_list=[out], scope=scope)
    got = np.asarray(got)
    for r in range(5):
        for j in range(2):
            assert got[r, j] == _xxh64(x[r].tobytes(), j) % mod


def test_xxh64_published_vectors_via_jnp():
    # XXH64 official test vectors (xxhash spec): empty-seed cases need
    # byte granularity we don't feed, so pin 4- and 8-byte inputs against
    # the numpy oracle which itself is pinned to published vectors in
    # tests/test_ops_round4.py
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops.misc_ops import _xxh64_jnp

    for words_np, seed in [(np.array([[0x04030201]], np.int32), 0),
                           (np.array([[0x04030201, 0x08070605]], np.int32), 7)]:
        words = jax.lax.bitcast_convert_type(jnp.asarray(words_np), jnp.uint32)
        hi, lo = _xxh64_jnp(words, seed)
        got = (int(np.asarray(hi)[0]) << 32) | int(np.asarray(lo)[0])
        want = _xxh64(words_np.tobytes(), seed)
        assert got == want, (hex(got), hex(want))
