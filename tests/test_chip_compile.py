"""Every `pallas_call` the main path can reach, compiled by the TPU's own
compiler for a described (not attached) v5e at BERT-base / ResNet-50 widths,
forward and backward; since PR 26 also the grouped matmul and the flash kernel
at OLMoE-1B-7B's widths, and since then every cell's own kernels at its shapes.
This file is the kernels alone, a kernel or one op's kernels a case; the ops'
whole lowerings (`moe_experts`, `short_conv`, `ssd_scan`) stand in
`tests/test_chip_compile_ops.py` and the cells' whole steps in
`tests/test_chip_compile_steps.py`, which take the described chip (`host`,
`chip`) and `_no_persistent_cache` from here (ISSUE 66: `--dist loadfile` makes
a file the unit of balance; `docs/tier1_durations.md` has each file's seconds).

Interpret mode (each kernel's own test file) checks the numbers; it cannot
see what Mosaic refuses: a block that is not a whole (8|16, 128) tile, a
blocked rank-1 operand, a primitive with no TPU lowering, a kernel that
overruns scoped VMEM.  These compiles can, at no chip time.  Nothing runs,
so a pass here says nothing about results — `chip_smoke.py` does that on the
chip.
"""
import os
import re

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else libtpu logs to /tmp

import jax
import jax.numpy as jnp
import pytest
from jax.experimental.compilation_cache import compilation_cache
from jax.sharding import SingleDeviceSharding

from paddle_tpu.ops.pallas_attention import fused_sdpa

BF16, F32, I32 = jnp.bfloat16, jnp.float32, jnp.int32


@pytest.fixture(scope="module")
def host():
    """A described v5e 2x2 host: four devices, none attached."""
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except (ImportError, RuntimeError, ValueError, NotImplementedError) as e:
        pytest.skip(f"cannot describe a v5e:2x2 topology here: {e}")


@pytest.fixture(scope="module")
def chip(host):
    """Sharding on one device of the described host."""
    return SingleDeviceSharding(host.devices[0])


@pytest.fixture(autouse=True)
def _no_persistent_cache():
    """An executable for a described chip is written to the persistent
    cache but cannot be read back without the chip; every later compile
    would warn.  Keep the cache out of these compiles."""
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _flash(causal):
    """The stock flash kernel as the `fused_attention` op calls it on a TPU
    (ops/nn_ops.py: its block sizes, the bias broadcast per head in float32)."""
    from paddle_tpu.ops.nn_ops import _flash_attention_tpu

    def run(q, k, v, bias=None):
        return _flash_attention_tpu(q, k, v, bias, causal, q.shape[-1] ** -0.5)
    return run


def _block_sparse(q, k, v):
    from paddle_tpu.ops.masked_attention import block_sparse_attention

    return block_sparse_attention(q, k, v, 4, q.shape[-1] ** -0.5)


def _block_causal(q, k, v):
    from paddle_tpu.ops.masked_attention import causal_attention

    return causal_attention(q, k, v, q.shape[-1] ** -0.5)


def _window(q, k, v, window=512):
    from paddle_tpu.ops.masked_attention import window_attention

    return window_attention(q, k, v, window, q.shape[-1] ** -0.5)


def _window_4096(q, k, v):
    return _window(q, k, v, 4096)


def _kda(q, k, v, g, beta):
    """The chunked KDA op as `kda`'s lowering calls it on a TPU (ops/linear_attention_ops.py): `kda_scan` forward,
    `kda_scan_transposed` under `jax.grad`."""
    from paddle_tpu.ops.linear_attention_ops import chunked_kda

    return chunked_kda(q, k, v, g, beta, kernels="tpu")[0]


def _ssm(x, dt, a_log, b_t, c_t, d_skip, dt_bias):
    """The selective scan as `selective_scan`'s lowering calls it on a TPU (ops/ssm_ops.py): `ssm_kernels.scan`
    forward, `scan_transposed` under `jax.grad`."""
    from paddle_tpu.ops.ssm_ops import kernel_selective_scan

    return kernel_selective_scan(x, dt, a_log, b_t, c_t, d_skip, dt_bias)[0]


def _backward(fn, argnums):
    """`fn`'s gradient as a case of its own: the transposed kernel counts beside the forward one."""
    return jax.grad(lambda *a: jnp.sum(fn(*a).astype(F32)), argnums=argnums)


def _latent(name, **static):
    """One of the latent attention's four edge kernels (ops/latent_kernels.py) as `ops/latent_operands.py` calls it."""
    from paddle_tpu.ops import latent_kernels

    return lambda *a: getattr(latent_kernels, name)(*a, **static)


def _gmm(rows, weights, sizes):
    from paddle_tpu.ops.moe_ops import grouped_matmul

    return grouped_matmul(rows, weights, sizes, "tpu")


def _token_sum(experts):
    """The routed experts' way back to token order as `_sum_by_token` calls it on a TPU (ops/moe_kernels.py)."""
    from paddle_tpu.ops.moe_kernels import token_sum

    return lambda rows, index, expert: token_sum(rows, index, expert, experts)


# BERT-base: batch 256 x seq 128 rows (the `pretrain-s128` cell's), d_model 768,
# d_ff 3072, vocab 30522, 12 heads of 64.  ResNet-50: NCHW bf16, batch 128
# training and the serving buckets' batch 8.
_ROWS = 256 * 128
# name -> (fn, [(shape, dtype)], grad argnums; () compiles forward only)
CASES = {
    "fused_sdpa": (
        lambda q, k, v: fused_sdpa(q, k, v, None, False, 0.125),
        [((256, 12, 128, 64), BF16)] * 3, (0, 1, 2)),
    # every length `ops/nn_ops.py:_attention_path` sends to the whole-row
    # kernel, at BERT-base's heads and ~16k tokens: `bert-base.pretrain-s512`'s
    # own call, the two lengths of the crossing's runs (PERF.md, PR 30), and
    # what else the rule admits: a mask shared by the heads or one a head,
    # causal, queries and keys of different lengths
    "fused_sdpa_seq512": (
        lambda q, k, v: fused_sdpa(q, k, v, None, False, 0.125),
        [((32, 12, 512, 64), BF16)] * 3, (0, 1, 2)),
    "fused_sdpa_seq384": (
        lambda q, k, v: fused_sdpa(q, k, v, None, False, 0.125),
        [((48, 12, 384, 64), BF16)] * 3, (0, 1, 2)),
    "fused_sdpa_seq256": (
        lambda q, k, v: fused_sdpa(q, k, v, None, False, 0.125),
        [((64, 12, 256, 64), BF16)] * 3, (0, 1, 2)),
    "fused_sdpa_seq512_bias_causal": (
        lambda q, k, v, b: fused_sdpa(q, k, v, b, True, 0.125),
        [((32, 12, 512, 64), BF16)] * 3 + [((32, 1, 512, 512), F32)], (0, 1, 2)),
    "fused_sdpa_seq512_bias_per_head": (
        lambda q, k, v, b: fused_sdpa(q, k, v, b, False, 0.125),
        [((8, 12, 512, 64), BF16)] * 3 + [((8, 12, 512, 512), BF16)], (0, 1, 2)),
    "fused_sdpa_q256_k512": (
        lambda q, k, v: fused_sdpa(q, k, v, None, False, 0.125),
        [((32, 12, 256, 64), BF16)] + [((32, 12, 512, 64), BF16)] * 2, (0, 1, 2)),
    # the same kernel over the projections' own layout (B, L, H, dh), read as [B, L, H*dh] with the heads a grid step
    # side by side on the lanes and each head's tile a static lane slice (PR 39): `bert-base.pretrain-s512`'s call
    # since then, the rule's other length, the lengths `_ROW_KERNEL_MIN_SEQ`'s runs priced, a shared mask under a
    # causal one, a mask a head, queries and keys of different lengths
    "fused_sdpa_blhd_seq512": (
        lambda q, k, v: fused_sdpa(q, k, v, None, False, 0.125, False, "blhd"),
        [((32, 512, 12, 64), BF16)] * 3, (0, 1, 2)),
    "fused_sdpa_blhd_seq384": (
        lambda q, k, v: fused_sdpa(q, k, v, None, False, 0.125, False, "blhd"),
        [((48, 384, 12, 64), BF16)] * 3, (0, 1, 2)),
    "fused_sdpa_blhd_seq256": (
        lambda q, k, v: fused_sdpa(q, k, v, None, False, 0.125, False, "blhd"),
        [((64, 256, 12, 64), BF16)] * 3, (0, 1, 2)),
    "fused_sdpa_blhd_seq128": (
        lambda q, k, v: fused_sdpa(q, k, v, None, False, 0.125, False, "blhd"),
        [((256, 128, 12, 64), BF16)] * 3, (0, 1, 2)),
    "fused_sdpa_blhd_seq512_bias_causal": (
        lambda q, k, v, b: fused_sdpa(q, k, v, b, True, 0.125, False, "blhd"),
        [((32, 512, 12, 64), BF16)] * 3 + [((32, 1, 512, 512), F32)], (0, 1, 2)),
    "fused_sdpa_blhd_seq512_bias_per_head": (
        lambda q, k, v, b: fused_sdpa(q, k, v, b, False, 0.125, False, "blhd"),
        [((8, 512, 12, 64), BF16)] * 3 + [((8, 12, 512, 512), BF16)], (0, 1, 2)),
    "fused_sdpa_blhd_q384_k512": (
        lambda q, k, v: fused_sdpa(q, k, v, None, False, 0.125, False, "blhd"),
        [((32, 384, 12, 64), BF16)] + [((32, 512, 12, 64), BF16)] * 2, (0, 1, 2)),
    # through `_flash_block_sizes`: 1024-blocks without a bias, 512 with one
    # (1024 with a bias overruns the scoped VMEM in the dq kernel), the
    # kernel's default where the length is a multiple of neither
    "flash_attention_seq2048": (
        _flash(False), [((4, 12, 2048, 64), BF16)] * 3, (0, 1, 2)),
    "flash_attention_seq2048_bias": (
        _flash(False), [((4, 12, 2048, 64), BF16)] * 3 + [((4, 1, 2048, 2048), BF16)],
        (0, 1, 2, 3)),
    "flash_attention_seq2176_bias": (
        _flash(False), [((2, 12, 2176, 64), BF16)] * 3 + [((2, 1, 2176, 2176), F32)], (0, 1, 2)),
    # OLMoE-1B-7B: 4 x 4096 tokens x 8 experts a token = 131072 rows sorted
    # by expert, 64 experts of 2048 x 1024 (gate, up) and 1024 x 2048 (down),
    # 16 heads of 128 at 4096 keys
    "flash_attention_seq4096_causal": (
        _flash(True), [((4, 16, 4096, 128), BF16)] * 3, (0, 1, 2)),
    "flash_attention_seq4096_causal_bias": (
        _flash(True), [((1, 16, 4096, 128), BF16)] * 3 + [((1, 16, 4096, 4096), F32)], (0, 1, 2)),
    "grouped_matmul_olmoe_gate": (
        _gmm, [((131072, 2048), BF16), ((64, 2048, 1024), BF16), ((64,), I32)], (0, 1)),
    "grouped_matmul_olmoe_down": (
        _gmm, [((131072, 1024), BF16), ((64, 1024, 2048), BF16), ((64,), I32)], (0, 1)),
    # float32 masters under bf16 rows: `tgmm` writes its float32 accumulator, at a tile of its own
    "grouped_matmul_olmoe_gate_master": (
        _gmm, [((131072, 2048), BF16), ((64, 2048, 1024), F32), ((64,), I32)], (0, 1)),
    "grouped_matmul_olmoe_down_master": (
        _gmm, [((131072, 1024), BF16), ((64, 1024, 2048), F32), ((64,), I32)], (0, 1)),
    # the down product's rows back to token order, each token's 8 summed in VMEM (PR 49): OLMoE's cell, and float32
    # rows of 128 experts (the 0/1 product at the highest precision; twice the buffers)
    "token_sum_olmoe": (
        _token_sum(64), [((131072, 2048), BF16), ((16384, 8), I32), ((16384, 8), I32)], ()),
    "token_sum_float32_rows": (
        _token_sum(128), [((32768, 1024), F32), ((4096, 8), I32), ((4096, 8), I32)], ()),
    # ... and given the held experts' rows alone, a quarter or a sixteenth of the slots' (PR 53): SDAR's, LFM2's and Kimi
    # Linear's calls, the last with rows of 2304 = 18 lane tiles
    "token_sum_held_sdar": (
        _token_sum(16), [((32768, 2048), BF16), ((16384, 8), I32), ((16384, 8), I32)], ()),
    "token_sum_held_lfm2": (
        _token_sum(8), [((32768, 2048), BF16), ((16384, 4), I32), ((16384, 4), I32)], ()),
    "token_sum_held_kimi_linear": (
        _token_sum(8), [((2048, 2304), BF16), ((4096, 8), I32), ((4096, 8), I32)], ()),
    # Kimi-Linear-48B-A3B's KDA layers: one sequence of 4096 positions, 32 heads of 128-wide keys and values in
    # chunks of 64 (tools/chip_kimi_kernels.py times them on the chip; the whole step's compile is `-m slow`)
    "kda_scan_kimi_linear": (
        _kda, [((1, 4096, 32, 128), BF16)] * 3 + [((1, 4096, 32, 128), F32), ((1, 4096, 32, 1), F32)], ()),
    "kda_scan_transposed_kimi_linear": (
        _backward(_kda, (0, 1, 2, 3, 4)),
        [((1, 4096, 32, 128), BF16)] * 3 + [((1, 4096, 32, 128), F32), ((1, 4096, 32, 1), F32)], ()),
    # the selective scan at Jamba2-3B's and Phi-4-mini-flash's widths (the same in both cells: one row of 8192
    # positions a chip, 5120 channels, a state of 16): x and dt in bf16, A, B, C and the two channel vectors float32
    "selective_scan_jamba2_phi4flash": (
        _ssm, [((1, 8192, 5120), BF16)] * 2 + [((5120, 16), F32)] + [((1, 8192, 16), F32)] * 2 + [((5120,), F32)] * 2, ()),
    "selective_scan_transposed_jamba2_phi4flash": (
        _backward(_ssm, (0, 1, 2, 3, 4, 5, 6)),
        [((1, 8192, 5120), BF16)] * 2 + [((5120, 16), F32)] + [((1, 8192, 16), F32)] * 2 + [((5120,), F32)] * 2, ()),
    "selective_scan_phi4flash_check_rows": (   # what the reference check's clone hands the op: 8 rows a call
        _ssm, [((8, 8192, 5120), BF16)] * 2 + [((5120, 16), F32)] + [((8, 8192, 16), F32)] * 2 + [((5120,), F32)] * 2, ()),
    # Kanana-2-30B-A3B's latent attention: one sequence of 16384 positions, 32 heads of 128 + 64 (keys) and 128
    # (values), the rotary pairs interleaved (`shift` 1); the layer's own compile stands at 2048 positions below
    "latent_queries_kanana2": (
        _latent("queries", heads=32, nope=128, scale=192 ** -0.5, shift=1),
        [((1, 16384, 32 * 192), BF16), ((1, 16384, 128), F32), ((1, 16384, 128), F32)], ()),
    "latent_queries_back_kanana2": (
        _latent("queries_back", heads=32, nope=128, scale=192 ** -0.5, shift=1),
        [((1, 32, 16384, 192), BF16), ((1, 16384, 128), F32), ((1, 16384, 128), F32)], ()),
    "latent_keys_values_kanana2": (
        _latent("keys_values", heads=32, nope=128), [((1, 16384, 32 * 256), BF16), ((1, 16384, 64), BF16)], ()),
    "latent_up_back_kanana2": (
        _latent("up_back"), [((1, 32, 16384, 192), BF16), ((1, 32, 16384, 128), BF16)], ()),
    # the held experts' products in the five sparse cells whose shapes stood in no case: the bound's rows
    # (`ops.moe_ops._held_rows_bound`: twice the uniform share of tokens x k slots) on the held experts' float32
    # masters, gate | up and down.  SDAR and Keye-VL-2.0: 16 of 128 held, 2048 x 768; LFM2: 8 of 32, 2048 x 1792;
    # Kimi Linear: 8 of 256, 2304 x 1024; Kanana-2: 8 of 128, 2048 x 768 at 16384 positions x 6
    "grouped_matmul_sdar_gate": (
        _gmm, [((32768, 2048), BF16), ((16, 2048, 768), F32), ((16,), I32)], (0, 1)),
    "grouped_matmul_keye_vl2_down": (
        _gmm, [((32768, 768), BF16), ((16, 768, 2048), F32), ((16,), I32)], (0, 1)),
    "grouped_matmul_lfm2_gate": (
        _gmm, [((32768, 2048), BF16), ((8, 2048, 1792), F32), ((8,), I32)], (0, 1)),
    "grouped_matmul_kimi_linear_gate": (
        _gmm, [((2048, 2304), BF16), ((8, 2304, 1024), F32), ((8,), I32)], (0, 1)),
    "grouped_matmul_kanana2_down": (
        _gmm, [((12288, 768), BF16), ((8, 768, 2048), F32), ((8,), I32)], (0, 1)),
    "grouped_matmul_ragged_rows": (  # 1000 rows: padded to the kernel's row tile
        _gmm, [((1000, 256), BF16), ((8, 256, 384), BF16), ((8,), I32)], (0, 1)),
    # SDAR-30B-A3B-Chat's cell: 2 sequences of 8192 positions [noised ; clean], 32 query heads on
    # 4 key/value heads of 128, under the block-diffusion mask: the stock splash kernel with the
    # mask's rule (ops/masked_attention.py) forward, and backward the ONE kernel that reads a stored block of the mask a
    # step (PR 68): 8192 queries against the 4096 clean keys, a head's dq and a group's dk and dv rows in VMEM
    "block_sparse_attention_sdar": (
        _block_sparse, [((2, 32, 8192, 128), BF16)] + [((2, 4, 8192, 128), BF16)] * 2, (0, 1, 2)),
    "block_sparse_attention_128_blocks": (  # a length that is whole in the small block only
        _block_sparse, [((1, 8, 1280, 128), BF16)] + [((1, 8, 1280, 128), BF16)] * 2, (0, 1, 2)),
    # the same kernels under the causal rule (`_attention_path`: `block_causal`, PR 37): OLMoE's cell, and LFM2's
    # 64-wide heads, 32 on 8 key/value heads at 8192 keys; a length in the 128-blocks
    "block_causal_attention_olmoe": (
        _block_causal, [((4, 16, 4096, 128), BF16)] * 3, (0, 1, 2)),
    "block_causal_attention_lfm2": (
        _block_causal, [((2, 32, 8192, 64), BF16)] + [((2, 8, 8192, 64), BF16)] * 2, (0, 1, 2)),
    "block_causal_attention_128_blocks": (
        _block_causal, [((1, 8, 2176, 64), BF16)] + [((1, 2, 2176, 64), BF16)] * 2, (0, 1, 2)),
    # the same kernels under the sliding-window rule (PR 50): Phi-4-mini-flash's window layer, 40 query heads on 20
    # key/value heads of 64 at 8192 keys under a window of 512; forward, and dq and dkv each a kernel of its own
    "window_attention_phi4flash": (
        _window, [((1, 40, 8192, 64), BF16)] + [((1, 20, 8192, 64), BF16)] * 2, (0, 1, 2)),
    # the ONE backward kernel that keeps dq on the chip (ops/attention_backward_kernels.py, PR 64) where its VMEM is
    # largest: SmallThinker's window layer and its full layer, 28 query heads on 4 key/value heads of 128 at 16384
    # keys (a head's float32 dq 8 MB, a key/value head's dk and dv rows 16 MB more), and Kanana-2's latent
    # attention, 32 heads of 192-wide queries and keys beside 128-wide values (dq's rows padded to 256 lanes: 16 MB)
    "window_attention_smallthinker": (
        _window_4096, [((1, 28, 16384, 128), BF16)] + [((1, 4, 16384, 128), BF16)] * 2, (0, 1, 2)),
    "block_causal_attention_smallthinker": (
        _block_causal, [((1, 28, 16384, 128), BF16)] + [((1, 4, 16384, 128), BF16)] * 2, (0, 1, 2)),
    "block_causal_attention_kanana2_192_128": (
        _block_causal, [((1, 32, 16384, 192), BF16)] * 2 + [((1, 32, 16384, 128), BF16)], (0, 1, 2)),
    # Laguna-XS.2's two attentions (PR 65): a window of 512 under 64 query heads on 8 key/value heads of 128 at 16384
    # keys (groups of eight, a head's whole dq and a key/value head's dk and dv rows in VMEM for a band 512 wide), and
    # the causal rule under 48 on 8: groups of SIX
    "window_attention_laguna": (
        _window, [((1, 64, 16384, 128), BF16)] + [((1, 8, 16384, 128), BF16)] * 2, (0, 1, 2)),
    "block_causal_attention_laguna": (
        _block_causal, [((1, 48, 16384, 128), BF16)] + [((1, 8, 16384, 128), BF16)] * 2, (0, 1, 2)),
    # Qwen3-Next-80B-A3B's cell (PR 69): a Gated DeltaNet layer's scan, one sequence of 16384 positions, 16 key heads of
    # 128 feeding 32 value heads of 128, the log decay ONE float32 a head a token ([b, T, H]: the kernels' scalar form,
    # the key heads through the index map), forward and transposed; and its full layer's causal attention, 16 query
    # heads on 2 key/value heads of 256: the widest head compiled here, groups of EIGHT
    "kda_scan_qwen3_next": (
        _kda, [((1, 16384, 16, 128), BF16)] * 2 + [((1, 16384, 32, 128), BF16), ((1, 16384, 32), F32),
                                                    ((1, 16384, 32, 1), F32)], ()),
    "kda_scan_transposed_qwen3_next": (
        _backward(_kda, (0, 1, 2, 3, 4)),
        [((1, 16384, 16, 128), BF16)] * 2 + [((1, 16384, 32, 128), BF16), ((1, 16384, 32), F32), ((1, 16384, 32, 1), F32)], ()),
    "block_causal_attention_qwen3_next": (
        _block_causal, [((1, 16, 16384, 256), BF16)] + [((1, 2, 16384, 256), BF16)] * 2, (0, 1, 2)),
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_kernel_compiles_for_v5e(name, chip):
    fn, specs, argnums = CASES[name]
    args = [jax.ShapeDtypeStruct(s, d, sharding=chip) for s, d in specs]
    programs = [fn]
    if argnums:
        programs.append(_backward(fn, argnums))
    for program in programs:
        compiled = jax.jit(program).lower(*args).compile()
        assert "tpu_custom_call" in compiled.as_text(), (
            f"{name}: compiled without a Mosaic kernel")


def test_no_square_of_the_positions_is_in_the_compiled_attention(chip):
    """Forward and backward at the cell's shape: no array with 8192 x 8192
    elements, mask or scores, in any computation of the compiled program, nor
    a float32 one of 8192 x 4096 (the far term's scores), and the four kernel
    calls under the lowering's scope, where the benchmark's
    `attention_roofline_share` finds them: the stock forward kernel over the
    clean keys, the ONE backward kernel over them (PR 68: 8192 queries against
    4096 keys, its three byte blocks of the mask all of it on the device,
    inside `VMEM_LIMIT` or the compile fails) and the own-block term's two."""
    args = [jax.ShapeDtypeStruct(s, BF16, sharding=chip) for s in ((2, 32, 8192, 128), (2, 4, 8192, 128), (2, 4, 8192, 128))]
    text = jax.jit(jax.grad(lambda *a: jnp.sum(_block_sparse(*a).astype(F32)), argnums=(0, 1, 2))).lower(
        *args).compile().as_text()
    assert not re.findall(r"\[[\d,]*8192,8192\]", text)
    assert not re.findall(r"f32\[[\d,]*8192,4096\]", text)
    assert text.count("tpu_custom_call") == 4
    under_the_scope = re.findall(
        r'op_name="[^"]*block_sparse_attention[^"]*/(splash_mha_fwd|splash_mha_dq|splash_mha_dkv|attention_dq_dk_dv|own_block_join|own_block_backward)'
        r'[^"/]*/pallas_call"', text)
    assert set(under_the_scope) == {"splash_mha_fwd", "attention_dq_dk_dv", "own_block_join", "own_block_backward"}
    assert re.findall(r"s8\[3,1024,1024\]", text)      # the two distinct cut blocks and the block of ones, keys by queries


@pytest.mark.parametrize("q,kv", [((4, 16, 4096, 128), (4, 16, 4096, 128)), ((2, 32, 8192, 64), (2, 8, 8192, 64))],
                         ids=["olmoe", "lfm2"])
def test_no_square_of_the_positions_is_in_the_compiled_causal_attention(q, kv, chip):
    """Forward and backward at OLMoE's and LFM2's shapes: no array with a
    [keys, keys] square, mask or scores, in any computation of the compiled
    program, nor the flash path's lane-spread float32 `di` ([b, h, L, 1024]),
    and the temporaries well under half of that path's; two kernel calls (the
    stock forward, and the ONE backward kernel of `ops/attention_backward_kernels.py`,
    which sums dq in VMEM: no partial a block of keys) under the lowering's scope."""
    length = q[2]
    args = [jax.ShapeDtypeStruct(s, BF16, sharding=chip) for s in (q, kv, kv)]
    compiled = jax.jit(jax.grad(lambda *a: jnp.sum(_block_causal(*a).astype(F32)), argnums=(0, 1, 2))).lower(*args).compile()
    text = compiled.as_text()
    assert not re.findall(r"\[[\d,]*%d,%d\]" % (length, length), text)
    assert not re.findall(r"f32\[%d,%d,%d,1024\]" % q[:3], text)
    # 0.27 and 0.81 GB here (the stock fused backward's program, dq's partials a block of keys among them, 0.27 and
    # 1.34 GB; the flash kernel's 1.34 and 3.36 GB: ISSUE 37)
    assert compiled.memory_analysis().temp_size_in_bytes < (1.0e9 if kv != q else 0.45e9)
    assert not re.findall(r"\[%d,%d,%d,%d,%d\]" % ((length // 1024,) + q), text)         # dq's partials a block of keys
    assert text.count("tpu_custom_call") == 2
    under_the_scope = re.findall(
        r'op_name="[^"]*block_sparse_attention[^"]*/(splash_mha_fwd|splash_mha_dq|splash_mha_dkv|attention_dq_dk_dv)[^"/]*/pallas_call"', text)
    assert set(under_the_scope) == {"splash_mha_fwd", "attention_dq_dk_dv"}


def test_no_square_of_the_positions_is_in_the_compiled_window_attention(chip):
    """Forward and backward at Phi-4-mini-flash's window layer's shape: no array
    with an [8192, 8192] square, mask or scores, in any computation of the
    compiled program (XLA's attention would hold 10.7 GB of float32 scores
    there), temporaries under 0.4 GB (0.38 here), and the two kernel calls
    (the stock forward and the one backward kernel of
    `ops/attention_backward_kernels.py`) under the rule's own scope, where the
    benchmark's `window_attention_roofline_share` finds them."""
    args = [jax.ShapeDtypeStruct(s, BF16, sharding=chip) for s in ((1, 40, 8192, 64), (1, 20, 8192, 64), (1, 20, 8192, 64))]
    compiled = jax.jit(jax.grad(lambda *a: jnp.sum(_window(*a).astype(F32)), argnums=(0, 1, 2))).lower(*args).compile()
    text = compiled.as_text()
    assert not re.findall(r"\[[\d,]*8192,8192\]", text)
    assert compiled.memory_analysis().temp_size_in_bytes < 0.4e9
    assert text.count("tpu_custom_call") == 2
    under_the_scope = re.findall(
        r'op_name="[^"]*window_attention\)*/block_sparse_attention[^"]*/(splash_mha_fwd|splash_mha_dq|splash_mha_dkv|attention_dq_dk_dv)'
        r'[^"/]*/pallas_call"', text)
    assert set(under_the_scope) == {"splash_mha_fwd", "attention_dq_dk_dv"}


def test_the_selected_attentions_kernels_compile_at_keye_vl_2s_shape(chip):
    """The kernels under a mask that is the step's own picks
    (`ops/masked_attention.py: selected_attention`) at (1, 32 on 4, 16384, 128)
    bf16 with the picks as int32 words.  Forward the stock splash kernel on
    block maps made from the picks, its stored [512, 1024] block of the mask
    inside the scoped VMEM.  Backward the ONE kernel of
    `ops/attention_backward_kernels.py` (PR 68): every step's [1024, 1024] byte
    block of the row's transposed mask beside a head's dq and a group's dk and
    dv rows, inside `VMEM_LIMIT` (the compile fails where it is overrun), no
    stock dq or dkv kernel and no block layout of the mask made for them: one
    byte mask of the square for backward, 268 MB, beside the forward's own."""
    from paddle_tpu.ops import attention_backward_kernels as onchip
    from paddle_tpu.ops import masked_attention as ma

    def gradients(q, k, v, picks):
        def loss(q, k, v):
            out, lse = ma.selected_attention(q, k, v, picks, 128 ** -0.5, causal=True)
            return out.astype(F32).sum() + lse.sum()
        return jax.grad(loss, (0, 1, 2))(q, k, v)

    shapes = [jax.ShapeDtypeStruct(shape, dtype, sharding=chip) for shape, dtype in (
        ((1, 32, 16384, 128), BF16), ((1, 4, 16384, 128), BF16), ((1, 4, 16384, 128), BF16), ((1, 16384, 512), I32))]
    compiled = jax.jit(gradients).lower(*shapes).compile()
    text = compiled.as_text()
    assert "splash_mha_fwd" in text and "attention_dq_dk_dv" in text and "splash_mha_dq" not in text and "splash_mha_dkv" not in text
    assert re.findall(r"s8\[1,16384,16384\]", text)
    assert compiled.memory_analysis().temp_size_in_bytes < 3e9, compiled.memory_analysis().temp_size_in_bytes     # 2.55 GB (the pair: under 4)
    plan = ma.selected_plan(16384, 32, True)
    assert plan.sizes.block_q == 512 and plan.sizes.block_kv == 1024 and (plan.block, plan.backward) == (1024, "onchip_dq")
    assert ma._steps(plan).q_block.size == 136 and onchip.kv_rows_fit((16384, 16384), (128, 128), 8, 1024)


@pytest.mark.parametrize("keys", [4096, 16384])
def test_the_indexers_choice_of_a_chunk_compiles_to_the_counting_kernel_with_no_sort(keys, chip):
    """`sparse_index_ops.choose` as the op calls it on the TPU, on a chunk of
    Keye-VL-2.0's cell, [512, keys] float32 scores, 2048 picks a query: the
    kernel that counts (`ops/sparse_index_kernels.py: select`; its block of 2 MB
    of scores twice and of keys once fits the scoped VMEM) and no sort of the
    row (`lax.top_k` was one, 399 ms a step: PERF.md, PR 57)."""
    from paddle_tpu.ops import sparse_index_kernels as sik
    from paddle_tpu.ops import sparse_index_ops as sio

    scores = jax.ShapeDtypeStruct((512, keys), F32, sharding=chip)
    text = jax.jit(lambda s: sio.pack_bits(sio.choose(s, keys - 512, 2048, sik.select))).lower(scores).compile().as_text()
    assert "kth_by_counting" in text and "tpu_custom_call" in text
    assert " sort(" not in text and "TopK" not in text and " while(" not in text


@pytest.mark.parametrize("keys", [2048, 16384])
def test_the_alignment_gradients_of_a_chunk_compile_to_one_kernel_inside_its_vmem(keys, chip):
    """`index_alignment_kernels.gradients` as the op calls it on the TPU, on a
    chunk of Keye-VL-2.0's cell: 512 queries of 16 index heads of 64 in bf16
    against a band's keys, dI [512, keys] float32.  One Mosaic kernel (under a
    second here: its body is one group of 128 lanes' heads, looped), inside the
    32 MB of VMEM it asks for, and no [16, 512, keys] array beside it: what the
    program holds besides its operands and results is kI laid at each head's
    lanes, [2, keys, 128] bf16 (PERF.md, PR 59)."""
    from paddle_tpu.ops import index_alignment_kernels as iak

    shapes = [jax.ShapeDtypeStruct(shape, dtype, sharding=chip) for shape, dtype in (
        ((512, 16, 64), BF16), ((keys, 64), BF16), ((512, 16), F32), ((512, keys), F32))]
    assert iak.fits(512, keys, 16, 64) and iak._VMEM_LIMIT <= 32 * 2 ** 20
    compiled = jax.jit(lambda *operands: iak.gradients(*operands)).lower(*shapes).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 1 and "index_alignment_gradients" in text
    assert not re.search(rf"\[16,512,{keys}\]", text)
    assert compiled.memory_analysis().temp_size_in_bytes <= 2 * keys * 128 * 2 + 2 * 512 * 1024 * 4


@pytest.mark.parametrize("keys", [2048, 16384])
def test_the_alignment_target_of_a_chunk_compiles_to_one_kernel_and_no_per_head_array(keys, chip):
    """`sparse_index_ops.attention_target` as the op calls it on the TPU, on a
    chunk of Keye-VL-2.0's cell: 512 queries of 32 heads of 128 in bf16 over 4
    key/value heads against a band's keys.  One Mosaic kernel
    (`ops/alignment_target_kernels.py: target`), and no [8, 512, keys] array of
    a group's scores or exponentials beside it, where the plain form's program
    holds them in float32 (PERF.md, PR 62)."""
    from paddle_tpu.ops import alignment_target_kernels as atk
    from paddle_tpu.ops import sparse_index_ops as sio

    shapes = [jax.ShapeDtypeStruct(shape, dtype, sharding=chip) for shape, dtype in (
        ((32, 512, 128), BF16), ((4, keys, 128), BF16), ((32, 512), F32), ((512, keys), jnp.bool_))]
    assert atk.fits(512, keys, 32, 4, 128)
    compiled = jax.jit(lambda *operands: sio.attention_target(*operands, 128 ** -0.5, atk.target)).lower(*shapes).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 1 and "alignment_target" in text
    assert not re.search(rf"\[(4,)?8,512,{keys}\]", text)
    plain = jax.jit(lambda *operands: sio.attention_target(*operands, 128 ** -0.5)).lower(*shapes).compile()
    assert re.search(rf"f32\[(4,)?8,512,{keys}\]", plain.as_text())
