"""Every `pallas_call` the main path can reach, compiled by the TPU's own
compiler for a described (not attached) v5e at BERT-base / ResNet-50 widths,
forward and backward; since PR 26 also the grouped matmul and the flash kernel
at OLMoE-1B-7B's widths, since PR 28 the whole `moe_experts` lowering there,
with the passes over its rows that the optimised program may hold.

Interpret mode (each kernel's own test file) checks the numbers; it cannot
see what Mosaic refuses: a block that is not a whole (8|16, 128) tile, a
blocked rank-1 operand, a primitive with no TPU lowering, a kernel that
overruns scoped VMEM.  These compiles can, at no chip time.  Nothing runs,
so a pass here says nothing about results — `chip_smoke.py` does that on the
chip.
"""
import os
import re
from types import SimpleNamespace

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else libtpu logs to /tmp

import jax
import jax.numpy as jnp
import numpy as np
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
    # mask's rule (ops/masked_attention.py), forward, dq and dkv
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


def _moe_experts(x, top_p, top_i, load, w_gate, w_up, w_down):
    """The op's lowering as the interpreter calls it for a TPU."""
    from paddle_tpu.core.lowering import LoweringContext
    from paddle_tpu.core.registry import get_op_def

    op = SimpleNamespace(type="moe_experts", attr=lambda name, default=None: default)
    ctx = LoweringContext(jax.random.PRNGKey(0), platform="tpu")
    ins = {"X": [x], "TopKProb": [top_p], "TopKIndex": [top_i], "Load": [load],
           "WGate": [w_gate], "WUp": [w_up], "WDown": [w_down]}
    return get_op_def("moe_experts").lower(ctx, op, ins)["Out"]


#: OLMoE-1B-7B's layer of experts over 4 x 4096 tokens: tokens, hidden, width, experts, experts a token
OLMOE_EXPERTS = (4 * 4096, 2048, 1024, 64, 8)


def _moe_experts_args(chip):
    """`_moe_experts`' arguments at `OLMOE_EXPERTS`: bf16 activations, float32 masters."""
    tokens, hidden, width, experts, k = OLMOE_EXPERTS
    specs = [((tokens, hidden), BF16), ((tokens, k), F32), ((tokens, k), I32), ((experts,), I32),
             ((experts, hidden, width), F32), ((experts, hidden, width), F32), ((experts, width, hidden), F32)]
    return [jax.ShapeDtypeStruct(s, d, sharding=chip) for s, d in specs]


#: Temporaries of the program below as compiled here for the described v5e:
#: 1.881 GB at the parent of PR 28 (fill-mode gathers, the weighted combine in
#: token order), 1.614 GB without those, 1.883 GB with the matrices' gradients
#: float32 from `tgmm` on (each 268 MB more than a bf16 one, from its kernel
#: to the end of the program), 1.891 GB since the way back to token order is a
#: kernel (PR 49: the [tokens, 8, hidden] arrays it took away were never live
#: at the peak).  The bound is the last reading and a margin.
MOE_EXPERTS_TEMP_BYTES = 1.95e9


def test_moe_experts_at_olmoe_widths_passes_over_its_rows_no_more_than_it_must(chip):
    """OLMoE-1B-7B's layer of experts over 4 x 4096 tokens, forward and the
    gradients of X, TopKProb and the three float32 master matrices: outside
    the kernels no `select` writes an [rows, hidden] array (a gather that
    promises its indices has no fill value to select) and at most three
    instructions write one: the gather to rows and the rows' two gradients
    added (the third is room for one relayout); the two ways back to token
    order (forward: the output; backward: X's gradient) are two calls of the
    `token_sum` kernel, which write [tokens, hidden] and nothing of [tokens,
    8, hidden] (PR 49; two gathers and two sums until then).  The masters'
    gradients are the three `tgmm` calls' own float32 results: nothing else
    writes an f32[experts, ., .] array (no bf16 gradient widened).  PERF.md,
    PR 28."""
    tokens, hidden, width, experts, k = OLMOE_EXPERTS
    args = _moe_experts_args(chip)
    program = jax.value_and_grad(lambda *a: jnp.sum(jnp.square(_moe_experts(*a).astype(F32))),
                                 argnums=(0, 1, 4, 5, 6))
    compiled = jax.jit(program).lower(*args).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") >= 11  # three products, each with its two transposes, and the two ways back
    assert len(re.findall(r'custom_call_target="tpu_custom_call".*token_sum', text)) == 2
    assert not re.findall(rf"= \w+\[{tokens},{k},{hidden}\]", text)
    rows_by_hidden = rf"= \w+\[{tokens * k},{hidden}\]\S* "
    assert not re.findall(rows_by_hidden + r"select\(", text)
    entry = text[text.index("ENTRY"):]
    written = [line.split(" = ")[0].strip() for line in entry.splitlines()
               if re.search(rows_by_hidden + r"(?!parameter|bitcast|get-tuple-element)", line)
               and "tpu_custom_call" not in line]
    assert len(written) <= 3, written
    of_the_masters = [line for line in entry.splitlines()
                      if re.search(rf"= f32\[{experts},\d+,\d+\]\S* (?!parameter)", line)]
    assert len(of_the_masters) == 3 and all("tpu_custom_call" in line and "tgmm" in line
                                            for line in of_the_masters), of_the_masters
    assert compiled.memory_analysis().temp_size_in_bytes < MOE_EXPERTS_TEMP_BYTES


def test_moe_experts_cost_row_counts_the_passes_of_the_compiled_forward(chip):
    """`ops.moe_ops._ROW_PASSES`, which the op's cost row charges, against
    the forward program at OLMoE's widths: every [rows, hidden] and
    [rows, width] array an instruction of the entry computation reads or
    writes, kernels included."""
    from collections import Counter

    from paddle_tpu.ops.moe_ops import _ROW_PASSES

    tokens, hidden, width, experts, k = OLMOE_EXPERTS
    args = _moe_experts_args(chip)
    text = jax.jit(_moe_experts).lower(*args).compile().as_text()
    of_rows = rf"\w+\[{tokens * k},(\d+)\]"
    types, passes = {}, Counter()
    for line in text[text.index("ENTRY"):].splitlines():
        m = re.match(r"\s*(?:ROOT )?%?([\w.\-]+) = (\(.*?\)|\S+) ([\w\-]+)\((.*)$", line)
        if not m:
            continue
        name, result, opcode, rest = m.groups()
        types[name] = result
        if opcode in ("parameter", "bitcast", "tuple", "get-tuple-element"):
            continue
        operands = re.findall(r"%([\w.\-]+)", rest.split(", metadata=")[0].split("), ")[0])
        passes.update(int(n) for t in [result] + [types.get(o, "") for o in operands]
                      for n in re.findall(of_rows, t))
    assert passes == {hidden: _ROW_PASSES["hidden"], width: _ROW_PASSES["width"]}, passes


#: SDAR-30B-A3B-Chat's layer of experts over 2 x 8192 positions with 16 of its 128 experts held:
#: tokens, hidden, width, router outputs, experts a token, experts held
SDAR_EXPERTS = (2 * 8192, 2048, 768, 128, 8, 16)


def test_no_square_of_the_positions_is_in_the_compiled_attention(chip):
    """Forward and backward at the cell's shape: no array with 8192 x 8192
    elements, mask or scores, in any computation of the compiled program, nor
    a float32 one of 8192 x 4096 (the far term's scores), and the five kernel
    calls under the lowering's scope, where the benchmark's
    `attention_roofline_share` finds them: the stock kernel's three over the
    clean keys and the own-block term's two."""
    args = [jax.ShapeDtypeStruct(s, BF16, sharding=chip) for s in ((2, 32, 8192, 128), (2, 4, 8192, 128), (2, 4, 8192, 128))]
    text = jax.jit(jax.grad(lambda *a: jnp.sum(_block_sparse(*a).astype(F32)), argnums=(0, 1, 2))).lower(
        *args).compile().as_text()
    assert not re.findall(r"\[[\d,]*8192,8192\]", text)
    assert not re.findall(r"f32\[[\d,]*8192,4096\]", text)
    assert text.count("tpu_custom_call") == 5
    under_the_scope = re.findall(
        r'op_name="[^"]*block_sparse_attention[^"]*/(?:splash_mha_)?(fwd|dq|dkv|own_block_join|own_block_backward)[^"/]*/pallas_call"', text)
    assert set(under_the_scope) == {"fwd", "dq", "dkv", "own_block_join", "own_block_backward"}


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


#: Kimi-Linear-48B-A3B's: one sequence of 4096 positions, hidden 2304 (18 lane tiles), 8 of 256 experts held
KIMI_EXPERTS = (4096, 2304, 1024, 256, 8, 8)


@pytest.mark.parametrize("cell,shape,bound", [("sdar", SDAR_EXPERTS, 32768), ("kimi-linear", KIMI_EXPERTS, 2048)])
def test_moe_experts_with_a_share_held_passes_over_no_more_rows_than_its_bound(cell, shape, bound, chip):
    """16 of 128 experts held at 16384 positions: the 131072 (token, slot)
    assignments exist as vectors only (the sort's keys, order and weights, and
    since PR 53 each slot's place and group, [tokens, k]);
    every two-dimensional array of rows, in the common pass and in the rare
    path's loop alike, has the bound's 32768 rows (twice the uniform share:
    `ops.moe_ops._held_rows_bound`) or the tokens' 16384, forward and backward.
    Since PR 35 the gathers write a whole number of
    passes, from one to four (8192 rows each over the bound, 512 over a chunk
    of the rare path's 2048), each count a branch of a conditional that the
    step's own count of held rows picks: rows that belong to no token cost
    nothing past the last pass that holds a live one.  Since PR 53 the common
    pass's way back is the `token_sum` kernel, forward's call and the transpose
    of the gather (`lowering.held_token_sum_calls` reads two): the only
    scatter-adds of rows left stand in the rare path's loop.  The same at Kimi
    Linear's widths, where Mosaic meets rows of 2304 = 18 lane tiles and the
    bound's passes are the rare path's."""
    from paddle_tpu import monitor
    from paddle_tpu.ops.moe_ops import _HELD_REST_ROWS, _held_rows_bound, _pass_rows

    tokens, hidden, width, experts, k, held = shape
    assert _held_rows_bound(tokens * k, held, experts) == bound

    def moe(x, top_p, top_i, load, w_gate, w_up, w_down):
        from paddle_tpu.core.lowering import LoweringContext
        from paddle_tpu.core.registry import get_op_def

        op = SimpleNamespace(type="moe_experts", attr=lambda name, default=None: {"held": [0, held]}.get(name, default))
        ins = {"X": [x], "TopKProb": [top_p], "TopKIndex": [top_i], "Load": [load],
               "WGate": [w_gate], "WUp": [w_up], "WDown": [w_down]}
        return get_op_def("moe_experts").lower(LoweringContext(jax.random.PRNGKey(0), platform="tpu"), op, ins)["Out"]

    specs = [((tokens, hidden), BF16), ((tokens, k), F32), ((tokens, k), I32), ((experts,), I32),
             ((held, hidden, width), F32), ((held, hidden, width), F32), ((held, width, hidden), F32)]
    args = [jax.ShapeDtypeStruct(s, d, sharding=chip) for s, d in specs]
    program = jax.value_and_grad(lambda *a: jnp.sum(jnp.square(moe(*a).astype(F32))), argnums=(0, 1, 4, 5, 6))
    monitor.reset()
    monitor.enable()
    try:
        compiled = jax.jit(program).lower(*args).compile()
        assert monitor.get_monitor().counter_values().get("lowering.held_token_sum_calls") == 2
    finally:
        monitor.disable()
        monitor.reset()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") >= 11
    assert len(re.findall(r'custom_call_target="tpu_custom_call".*jit\(token_sum\)', text)) == 2
    rows_of = {int(n) for n in re.findall(r"= \w+\[(\d+),(?:%d|%d)\]" % (hidden, width), text)}
    assert max(rows_of) == max(bound, tokens), rows_of
    assert not re.findall(r"\[%d,\d+" % (tokens * k), text)
    assert compiled.memory_analysis().temp_size_in_bytes < 1.6e9
    shape_of = dict(re.findall(r"%(\S+) = \w+\[([\d,]*)\]", text))
    gathered = [shape for shape in re.findall(r"= \w+\[([\d,]*)\]\S* gather\(", text) if shape.endswith(",%d" % hidden)]
    # the rows'; the kernels' group metadata scatters too
    added = [(shape_of[updates], where) for updates, where in re.findall(r' scatter\(%\S+, %\S+, %([^\s,)]+)\).*op_name="([^"]*)"', text)
             if shape_of[updates].endswith(",%d" % hidden)]
    assert _pass_rows(bound) == bound // 4 and _pass_rows(_HELD_REST_ROWS) == 512

    def passes(n):
        return {"%d,%d" % (rows, hidden) for rows in range(_pass_rows(n), n + 1, _pass_rows(n))}

    assert len(passes(bound) | passes(_HELD_REST_ROWS)) == (8 if cell == "sdar" else 4)
    assert set(gathered) == passes(bound) | passes(_HELD_REST_ROWS), gathered
    assert {shape for shape, _ in added} == passes(_HELD_REST_ROWS) and len(added) == 8, added   # four counts of passes, forward and backward
    assert all("/while/body/" in where for _, where in added), added   # the rare path's loop; none in the common pass


#: LFM2-8B-A1B's cell: a sequence of 8192 positions at hidden size 2048, three taps
LFM2_CONV = (1, 8192, 2048, 3)


def test_the_short_convolution_is_passes_over_the_activations_dtype(chip):
    """`short_conv` at the cell's shape, forward and backward: plain jax.numpy
    that XLA fuses.  No float32 copy of the [b, T, 3d] in-projection and no
    padded copy of a product exists in the compiled program (the derived
    backward made both), and the temporaries stay under three of the op's own
    [b, T, d] float32 arrays."""
    from paddle_tpu.ops.moe_ops import _gated_short_conv

    b, t, d, taps = LFM2_CONV
    args = [jax.ShapeDtypeStruct(s, dt, sharding=chip) for s, dt in
            (((b, t, 3 * d), BF16), ((d, taps), F32), ((b, t, d), BF16))]

    def forward(x, w):   # as the executor differentiates it: the scopes are opened inside
        with jax.named_scope("fwd"):
            return _gated_short_conv(x, w)

    def step(x, w, g):
        out, vjp = jax.vjp(forward, x, w)
        return (out,) + vjp(g)

    compiled = jax.jit(step).lower(*args).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" not in text               # no kernel: the op is XLA's
    entry = text[text.index("ENTRY"):]                 # what exists in memory: the entry computation's results
    assert not re.findall(r"= [^=]*f32\[%d,%d,%d\][^=]* fusion\(" % (b, t, 3 * d), entry)
    assert not re.findall(r"= [^=]*f32\[%d,%d,%d\][^=]* fusion\(" % (b, t - 1, d), entry)
    assert compiled.memory_analysis().temp_size_in_bytes <= 3 * b * t * d * 4
    # forward and backward under the scope the benchmark's `short_conv_roofline_share` reads
    scoped = re.findall(r'op_name="[^"]*/gated_short_conv/[^"]*"', text)
    assert any("transpose(" in name for name in scoped) and any("transpose(" not in name for name in scoped)


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


def test_lfm2s_step_compiles_for_the_chip_and_its_planned_peak_leaves_room(chip):
    """The cell's whole train step (benchmark/models/lfm2.py: build, at the
    configuration's and the traffic's own sizes: two sequences) compiles for
    the described v5e, and XLA plans it under the 15.5 GB the cell allows
    itself and over the 12 GB it promises to fill (PERF.md, PR 34, has the
    three planned peaks: a third sequence plans 15.60).  What `cost_analysis()`
    counts for the step stays under what the HBM moves in 280 ms, the step's
    time on the chip: the whole-step roofline share the cell reports reads
    under 100% (it read 121.6% while the held experts' never-run branch was
    a bound's rows a pass)."""
    import paddle_tpu as fluid
    from benchmark import manifest as mf
    from benchmark.models import lfm2
    from paddle_tpu.core import executor as ex

    cfg = mf.read_json("benchmark/configs/lfm2-8b-a1b.json")
    job = mf.read_json("benchmark/traffic/train-s8192.json")
    with fluid.unique_name.guard():
        main, startup, _, loss, _ = lfm2.build(cfg, job)
    main.random_seed = startup.random_seed = 3
    scope = fluid.Scope()
    for v in startup.global_block().vars.values():
        if v.persistable:
            scope.set_var(v.name, jax.ShapeDtypeStruct(tuple(v.shape), v.dtype))
    feeds = {n: jax.ShapeDtypeStruct((job["batch_per_chip"], job["seq_len"]), I32) for n in lfm2.FEEDS}
    step = ex._CompiledStep(main, list(feeds), [loss.name], scope, platform="tpu",
                            feed_shapes={n: s.shape for n, s in feeds.items()})

    def on_chip(v):
        return jax.ShapeDtypeStruct(v.shape, v.dtype, sharding=chip)

    compiled = step.jfn.lower({n: on_chip(scope.find_var(n)) for n in step.rw_names},
                              {n: on_chip(scope.find_var(n)) for n in step.ro_names},
                              {n: on_chip(s) for n, s in feeds.items()},
                              on_chip(jax.random.PRNGKey(0))).compile()
    m = compiled.memory_analysis()
    peak = m.argument_size_in_bytes + m.output_size_in_bytes - m.alias_size_in_bytes + m.temp_size_in_bytes
    assert 12e9 <= peak <= 15.5e9, peak
    cost = compiled.cost_analysis()
    cost = cost[0] if isinstance(cost, (list, tuple)) else cost
    assert max(cost["bytes accessed"] / 819e9, cost["flops"] / 197e12) < 0.280
    text = compiled.as_text()
    # the one attention layer took the splash kernels under the causal rule (PR 37; the flash kernel until then)
    assert "splash_mha_fwd" in text and "attention_dq_dk_dv" in text and "splash_mha_d" not in text and "flash_mha" not in text
    assert text.count("/gated_short_conv/") > 0 and text.count("/expert_gemm/") > 0


def test_ouros_step_compiles_for_the_chip_as_one_loop_and_its_planned_peak_leaves_room(chip):
    """The looped cell's whole train step (benchmark/models/ouro.py: build, at
    the configuration's and the traffic's own sizes: one sequence of 4096
    through four passes of eight layers) compiles for the described v5e as a
    forward and a backward `while` (the `repeat` op's scan and its transpose),
    the pass's forward computed again inside the backward one, the splash
    kernels inside both, and XLA plans it under the 15.5 GB the cell allows
    itself and over the 25% of the chip a cell has to fill (PERF.md, PR 38:
    12.7 GB).  `cost_analysis()` counts a loop's body once, so what it counts
    stays far under what the chip does in the step's ~0.6 s: the whole-step
    roofline share the cell reports reads LOW, never over 100%."""
    import paddle_tpu as fluid
    from benchmark import manifest as mf
    from benchmark.models import ouro
    from paddle_tpu.core import executor as ex

    cfg = mf.read_json("benchmark/configs/ouro-2.6b.json")
    job = mf.read_json("benchmark/traffic/train-ut4-s4096.json")
    with fluid.unique_name.guard():
        main, startup, _, loss, _ = ouro.build(cfg, job)
    main.random_seed = startup.random_seed = 3
    scope = fluid.Scope()
    for v in startup.global_block().vars.values():
        if v.persistable:
            scope.set_var(v.name, jax.ShapeDtypeStruct(tuple(v.shape), v.dtype))
    feeds = {n: jax.ShapeDtypeStruct((job["batch_per_chip"], job["seq_len"]), I32) for n in ouro.FEEDS}
    step = ex._CompiledStep(main, list(feeds), [loss.name], scope, platform="tpu",
                            feed_shapes={n: s.shape for n, s in feeds.items()})

    def on_chip(v):
        return jax.ShapeDtypeStruct(v.shape, v.dtype, sharding=chip)

    compiled = step.jfn.lower({n: on_chip(scope.find_var(n)) for n in step.rw_names},
                              {n: on_chip(scope.find_var(n)) for n in step.ro_names},
                              {n: on_chip(s) for n, s in feeds.items()},
                              on_chip(jax.random.PRNGKey(0))).compile()
    m = compiled.memory_analysis()
    peak = m.argument_size_in_bytes + m.output_size_in_bytes - m.alias_size_in_bytes + m.temp_size_in_bytes
    assert 0.25 * 16.9e9 <= peak <= 15.5e9, peak
    assert m.argument_size_in_bytes == pytest.approx(3 * 4 * 461.4e6, rel=1e-3)      # masters and Adam's two moments
    cost = compiled.cost_analysis()
    cost = cost[0] if isinstance(cost, (list, tuple)) else cost
    assert max(cost["bytes accessed"] / 819e9, cost["flops"] / 197e12) < 0.5
    text = compiled.as_text()
    assert len(re.findall(r" while\(", text)) == 2
    assert "splash_mha_fwd" in text and "attention_dq_dk_dv" in text and "splash_mha_d" not in text and "flash_mha" not in text
    # the scopes the cell's readers find: the recomputed forward, the exits, the body's ops under the construct's
    assert text.count("/rematted_computation/") > 0
    # numbered where this process built a looped model before: sibling `name_scope`s of one name are
    assert re.search(r"/exit_head(_\d+)?/", text) and re.search(r"/exit_loss(_\d+)?/", text)
    assert re.search(r':repeat/[^"]*loop_pass/op\d+:fused_attention', text) and not re.search(r'loop_pass/[^"]*exit_head', text)
    assert re.search(r'transpose\([^"]*:repeat/[^"]*rematted_computation/[^"]*op\d+:mul', text)


@pytest.mark.slow   # 3 to 4.5 minutes of one compile on every core: run by name (`-m slow`), PERF.md PR 42 has its readings
def test_kimi_linears_step_compiles_for_the_chip_and_its_planned_peak_leaves_room(chip):
    """Kimi Linear's cell's whole train step (benchmark/models/kimi_linear.py:
    build, at the configuration's and the traffic's own sizes: one sequence of
    4096 through four KDA layers and a latent attention) compiles for the
    described v5e, and XLA plans it under the 15.5 GB the cell allows itself
    and over the 25% of the chip a cell has to fill (PERF.md, PR 42, has the
    planned peaks that chose the batch).  The latent attention took the splash
    kernels with its two widths as they are; the scans are the kernels of
    `ops/kda_kernels.py` since PR 44, two calls a layer since PR 45 (forward,
    which in the step writes the chunks' start states and T beside o, 0.17 GB
    a layer kept until backward, and the transpose, which reads them: no call
    makes the states again; four heads a grid step: they fit their VMEM inside the
    step, not only alone) under the scope their roofline share reads, forward
    and backward; the state and Adam's moments are 12 bytes of the 16 a
    parameter.  PERF.md, PR 45, has the planned peak."""
    import paddle_tpu as fluid
    from benchmark import manifest as mf
    from benchmark.models import kimi_linear
    from paddle_tpu.core import executor as ex

    cfg = mf.read_json("benchmark/configs/kimi-linear-48b-a3b.json")
    job = mf.read_json("benchmark/traffic/train-kda-s4096.json")
    with fluid.unique_name.guard():
        main, startup, _, loss, _ = kimi_linear.build(cfg, job)
    main.random_seed = startup.random_seed = 3
    scope = fluid.Scope()
    for v in startup.global_block().vars.values():
        if v.persistable:
            scope.set_var(v.name, jax.ShapeDtypeStruct(tuple(v.shape), v.dtype))
    feeds = {n: jax.ShapeDtypeStruct((job["batch_per_chip"], job["seq_len"]), I32) for n in kimi_linear.FEEDS}
    step = ex._CompiledStep(main, list(feeds), [loss.name], scope, platform="tpu",
                            feed_shapes={n: s.shape for n, s in feeds.items()})

    def on_chip(v):
        return jax.ShapeDtypeStruct(v.shape, v.dtype, sharding=chip)

    compiled = step.jfn.lower({n: on_chip(scope.find_var(n)) for n in step.rw_names},
                              {n: on_chip(scope.find_var(n)) for n in step.ro_names},
                              {n: on_chip(s) for n, s in feeds.items()},
                              on_chip(jax.random.PRNGKey(0))).compile()
    m = compiled.memory_analysis()
    peak = m.argument_size_in_bytes + m.output_size_in_bytes - m.alias_size_in_bytes + m.temp_size_in_bytes
    assert 0.25 * 16.9e9 <= peak <= 15.5e9, peak
    assert m.argument_size_in_bytes == pytest.approx(3 * 4 * cfg["parameters"], rel=1e-3)    # masters and Adam's two moments
    text = compiled.as_text()
    assert "splash_mha_fwd" in text and "attention_dq_dk_dv" in text and "splash_mha_d" not in text and "flash_mha" not in text
    scans = re.findall(r'op_name="([^"]*/kda_chunk_scan/[^"]*)"', text)
    assert any("transpose(" in name for name in scans) and any("transpose(" not in name for name in scans)
    assert all(any(name.endswith(f"/{kernel}/pallas_call") for name in scans) for kernel in ("kda_scan", "kda_scan_transposed"))
    assert "kda_scan_starts" not in text
    print(f"planned peak {peak / 1e9:.3f} GB, temporaries {m.temp_size_in_bytes / 1e9:.3f} GB")     # shown by `-s`
    assert not re.search(r"kda_chunk_scan/[^\"]*while", text)          # no `lax.scan` is left in the op
    assert re.search(r"/kda(_\d+)?/op\d+:kda/kda_chunk_scan/", text) and re.search(r"/latent_attention(_\d+)?/op\d+:fused_attention", text)
    assert re.search(r"/shared_expert(_\d+)?/op\d+:mul", text) and text.count("/plain_short_conv/") > 0


#: What a v5e reports as `memory_stats()["bytes_limit"]` (my chip run, PR 51, call 1): the limit `plan_kept` reads on
#: the chip, given to it here, where the CPU reports none, so that the step compiled here is the step the chip compiles.
V5E_BYTES_LIMIT = 16_909_336_064


@pytest.mark.parametrize("limit,made_again", [(0, 3), (V5E_BYTES_LIMIT, 0)], ids=["a-full-chip", "a-v5es-room"])
def test_the_benchmarks_readers_find_the_selective_scans_kernels_forward_recomputed_and_backward(chip, monkeypatch, limit, made_again):
    """A small Jamba (the configuration's period cut to four layers, 512 wide:
    1024 channels a mixer and a state of 16, which `_scan_path` sends to the
    kernels on the TPU; 64 tokens) trained one step, compiled for the described
    v5e: every call of the two kernels of `ops/ssm_kernels.py`, forward, made
    again under the layer's `recompute_scope` and transposed (the one in the
    `custom_vjp`'s backward), carries an `op_name` that the benchmark's readers
    `ssm_scan_roofline_share` and `ssm_ms_per_step` match (their own `SCOPE`s,
    imported), the recomputed ones `recompute_ms_per_step`'s too; no `while` is
    left under the op's scope.  The forward kernel is made again where the chip
    has no room for what `plan_kept` would keep (the cell's own thirteen at its
    size), and not at all where it has (this small model on a v5e: PR 51)."""
    import paddle_tpu as fluid
    from benchmark import manifest as mf
    from benchmark.metrics import recompute_ms_per_step, ssm_ms_per_step, ssm_scan_roofline_share
    from benchmark.models import jamba
    from paddle_tpu.core import executor as ex
    from paddle_tpu.monitor import memstats

    monkeypatch.setattr(memstats, "device_bytes_limit", lambda *a: limit)
    cfg = dict(mf.read_json("benchmark/configs/ai21-jamba2-3b.json"), hidden_size=512, intermediate_size=96, mamba_dt_rank=4,
               num_attention_heads=4, num_key_value_heads=1, vocab_size=96, num_hidden_layers=4, attn_layer_period=4,
               attn_layer_offset=2)
    cfg["layer_types"] = jamba.layer_types(cfg)
    job = dict(mf.read_json("benchmark/traffic/train-ssm-fsdp4.json"), seq_len=64, batch_per_chip=1)
    del job["mesh_shape"], job["mesh_axes"]
    with fluid.unique_name.guard():
        main, startup, _, loss, _ = jamba.build(cfg, job)
    scope = fluid.Scope()
    for v in startup.global_block().vars.values():
        if v.persistable:
            scope.set_var(v.name, jax.ShapeDtypeStruct(tuple(v.shape), v.dtype))
    feeds = {n: jax.ShapeDtypeStruct((1, 64), I32) for n in jamba.FEEDS}
    step = ex._CompiledStep(main, list(feeds), [loss.name], scope, platform="tpu", feed_shapes={n: s.shape for n, s in feeds.items()})

    def on_chip(v):
        return jax.ShapeDtypeStruct(v.shape, v.dtype, sharding=chip)

    text = step.jfn.lower({n: on_chip(scope.find_var(n)) for n in step.rw_names}, {n: on_chip(scope.find_var(n)) for n in step.ro_names},
                          {n: on_chip(s) for n, s in feeds.items()}, on_chip(jax.random.PRNGKey(0))).compile().as_text()
    kernels = sorted({name for name in recompute_ms_per_step.op_names(text).values()      # a call's operands' copies carry its name too
                      if name.endswith(("/selective_scan/pallas_call", "/selective_scan_transposed/pallas_call"))})
    assert all(ssm_scan_roofline_share.SCOPE.search(name) and ssm_ms_per_step.SCOPE.search(name) for name in kernels), kernels
    transposed = [name for name in kernels if name.endswith("/selective_scan_transposed/pallas_call")]
    again = [name for name in kernels if recompute_ms_per_step.SCOPE in name]
    forward = [name for name in kernels if name not in transposed and name not in again]
    assert len(forward) == len(transposed) == 3 and len(again) == made_again, kernels   # the three Mamba layers, each way
    assert all("transpose(" in name for name in transposed) and not any("transpose(" in name for name in forward)
    assert all(name.endswith("/selective_scan/pallas_call") for name in again)
    assert not re.search(r'op_name="[^"]*op\d+:selective_scan/[^"]*while', text)


def _kept_step(module, config, traffic, devices, monkeypatch, check_rows=None):
    """(the compiled train step of a cell at its configuration's and traffic's
    own sizes, for the described chip or mesh, with what `plan_kept` chose at
    the chip's own memory limit; the `lowering.recomputed_*` counters of its
    trace).  `check_rows`: the cell's `for_test` clone on that many rows with
    the variables its reference check fetches, instead of the step."""
    import importlib

    import paddle_tpu as fluid
    from benchmark import manifest as mf
    from paddle_tpu import monitor
    from paddle_tpu.core import executor as ex
    from paddle_tpu.monitor import memstats

    model = importlib.import_module(f"benchmark.models.{module}")
    cfg, job = mf.read_json(f"benchmark/configs/{config}.json"), mf.read_json(f"benchmark/traffic/{traffic}.json")
    monkeypatch.setattr(memstats, "device_bytes_limit", lambda *a: V5E_BYTES_LIMIT)
    mesh, make_mesh = None, fluid.parallel.make_mesh
    if "mesh_shape" in job:    # the builder's mesh over the described devices, not the CPU's
        monkeypatch.setattr(fluid.parallel, "make_mesh", lambda sizes, names, _=None: make_mesh(sizes, names, list(devices)))
        mesh = fluid.parallel.make_mesh(tuple(job["mesh_shape"]), tuple(job["mesh_axes"]))
    with fluid.unique_name.guard():
        main, startup, _, loss, compared = model.build(cfg, job)
    main.random_seed = startup.random_seed = 3
    program, fetched = (main, [loss.name]) if check_rows is None else (main.clone(for_test=True), list(compared))
    scope = fluid.Scope()
    for v in startup.global_block().vars.values():
        if v.persistable:
            scope.set_var(v.name, jax.ShapeDtypeStruct(tuple(v.shape), v.dtype))
    rows = check_rows or job["batch_per_chip"] * (mesh.size if mesh is not None else 1)
    feeds = {n: jax.ShapeDtypeStruct((rows, job["seq_len"]), I32) for n in model.FEEDS}
    step = ex._CompiledStep(program, list(feeds), fetched, scope, mesh=mesh, batch_axis=job.get("mesh_axes", ["dp"])[0],
                            platform="tpu", feed_shapes={n: s.shape for n, s in feeds.items()})
    one = SingleDeviceSharding(devices[0])

    def placed(v, sharding):
        return jax.ShapeDtypeStruct(v.shape, v.dtype, sharding=one if mesh is None else sharding)

    monitor.reset()
    monitor.enable()
    try:
        lowered = step.jfn.lower(
            {n: placed(scope.find_var(n), mesh and step.state_specs[n]) for n in step.rw_names},
            {n: placed(scope.find_var(n), mesh and step.state_specs[n]) for n in step.ro_names},
            {n: placed(s, mesh and step.feed_specs[n]) for n, s in feeds.items()},
            placed(jax.random.PRNGKey(0), mesh and step.key_spec))
        # (the counters that moved: `monitor.reset()` keeps the names an earlier test of this process counted under)
        counted = {k[len("lowering.recomputed_"):]: v for k, v in monitor.MONITOR.counter_values().items()
                   if k.startswith("lowering.recomputed_") and v}
    finally:
        monitor.disable()
        monitor.reset()
    return lowered.compile(), counted


def _planned_peak(compiled):
    m = compiled.memory_analysis()
    return m.argument_size_in_bytes + m.output_size_in_bytes - m.alias_size_in_bytes + m.temp_size_in_bytes


def _made_again(text):
    """The names of the instructions of the compiled step that stand in a
    rematerialised computation, as `recompute_ms_per_step` finds them."""
    from benchmark.metrics import recompute_ms_per_step

    return [name for name in recompute_ms_per_step.op_names(text).values() if recompute_ms_per_step.SCOPE in name]


def test_phi4_mini_flashs_step_keeps_every_product_and_kernel_residual_and_its_planned_peak_leaves_room(host, monkeypatch):
    """`phi-4-mini-flash-reasoning.train-sambay-s8192`'s whole step at the
    published widths, compiled for the described v5e with what `plan_kept`
    chooses at the chip's memory limit: all 37 candidates of the six segments
    (3.45 GB of the 4.27 the state leaves the kept values), planned under the
    14.5 GB the issue allows and over the parent's 10.5; in the rematerialised
    computations no product and no kernel call is left (ISSUE 51)."""
    compiled, counted = _kept_step("phi4flash", "phi-4-mini-flash-reasoning", "train-sambay-s8192", host.devices, monkeypatch)
    assert counted == {"segments": 6, "kept_values": 37, "kept_bytes": 3449552896, "candidates_bytes": 3449552896}
    peak = _planned_peak(compiled)
    print(f"planned peak {peak / 1e9:.3f} GB")     # shown by `-s`
    assert 12.5e9 <= peak <= 14.5e9, peak
    again = _made_again(compiled.as_text())
    assert again and not [name for name in again if name.endswith(("/dot_general", "/pallas_call"))]


def test_smallthinkers_step_compiles_for_the_chip_with_its_routers_ahead_and_a_window_of_4096(host, monkeypatch):
    """`smallthinker-21b-a3b.train-nope-swa-s16384`'s whole step at the published
    widths and 16384 tokens, compiled for the described v5e with what
    `plan_kept` chooses at the chip's memory limit: all 28 candidates of the
    four sparse segments (every product's output, the kernels' residuals, the
    expert products' outputs and the routers' logits: 1.74 GB), planned over the
    25% of the chip a cell has to fill and under 9 GB; the full layer took the
    causal splash kernels and the three window layers the window rule's, in
    blocks of 1024 at (28 on 4, 128) inside the scoped VMEM; every router's
    scope stands AHEAD of its layer's attention; and in the rematerialised
    computations no product and no attention kernel is left (ISSUE 63)."""
    compiled, counted = _kept_step("smallthinker", "smallthinker-21b-a3b", "train-nope-swa-s16384", host.devices, monkeypatch)
    assert counted == {"segments": 4, "sparse_segments": 4, "kept_values": 28, "kept_bytes": 1735393280,
                       "candidates_bytes": 1735393280}
    peak = _planned_peak(compiled)
    print(f"planned peak {peak / 1e9:.3f} GB")     # shown by `-s`
    assert 0.25 * 16.9e9 <= peak <= 9.0e9, peak
    text = compiled.as_text()
    assert "splash_mha_fwd" in text and "splash_mha_d" not in text and "flash_mha" not in text
    # ONE backward kernel an attention layer (ISSUE 64), and no partial dq a block of keys
    assert len(re.findall(r'custom_call_target="tpu_custom_call"[^\n]*/attention_dq_dk_dv["/]', text)) == 4
    assert not re.findall(r"\[16,28,16384,128\]", text)
    names = re.findall(r'op_name="([^"]*)"', text)
    window = {re.search(r"/(sliding_attention(?:_\d+)?)/", n).group(1) for n in names if "/window_attention/" in n}
    assert len(window) == 3                        # the three rotary layers, each under its own numbered scope
    assert any(re.search(r"/op\d+:fused_attention/block_sparse_attention/", n) for n in names)      # the full layer: no window scope
    routers = sorted({int(i) for n in names for i in re.findall(r"/op(\d+):moe_router", n)})
    attentions = sorted({int(i) for n in names for i in re.findall(r"/op(\d+):fused_attention", n)})
    assert len(routers) == len(attentions) == 4 and all(r < a for r, a in zip(routers, attentions))
    assert all(a < r for a, r in zip(attentions, routers[1:]))          # router, attention, router, attention, ...
    again = [name for name in _made_again(text) if "/cond/branch_" not in name]
    assert again and not [name for name in again if name.endswith("/dot_general") or "splash_mha" in name or "attention_dq_dk_dv" in name or "/expert_gemm/" in name]


def test_lagunas_step_compiles_for_the_chip_with_its_gates_its_two_head_counts_and_a_window_of_512(host, monkeypatch):
    """`laguna-xs.2.train-gated-swa-s16384`'s whole step at the published widths
    and 16384 tokens, compiled for the described v5e with what `plan_kept`
    chooses at the chip's memory limit: all 48 candidates of the five segments
    (4.0 GB), planned over the 25% of the chip a cell has to fill and under
    12.5 GB (11.77 with 16 experts held; 32 held planned 14.76 and its 8-row
    clone did not fit beside the moments: the configuration's `deployment`); the
    two full layers took the causal splash kernels at 48 heads on 8 and the
    three window layers the window rule's at 64 on 8, blocks of 512, ONE
    backward kernel a layer; five `attention_gate` scopes, each under its
    layer's; and in the rematerialised computations no product and no attention
    kernel is left (ISSUE 65)."""
    compiled, counted = _kept_step("laguna", "laguna-xs.2", "train-gated-swa-s16384", host.devices, monkeypatch)
    assert counted == {"segments": 5, "sparse_segments": 4, "kept_values": 48, "kept_bytes": 3997171712,
                       "candidates_bytes": 3997171712}
    peak = _planned_peak(compiled)
    print(f"planned peak {peak / 1e9:.3f} GB")     # shown by `-s`
    assert 0.25 * 16.9e9 <= peak <= 12.5e9, peak
    text = compiled.as_text()
    assert "splash_mha_fwd" in text and "splash_mha_d" not in text and "flash_mha" not in text
    assert len(re.findall(r'custom_call_target="tpu_custom_call"[^\n]*/attention_dq_dk_dv["/]', text)) == 5
    assert re.findall(r"bf16\[1,48,16384,128\]", text) and re.findall(r"bf16\[1,64,16384,128\]", text)
    names = re.findall(r'op_name="([^"]*)"', text)
    window = {re.search(r"/(sliding_attention(?:_\d+)?)/", n).group(1) for n in names if "/window_attention/" in n}
    assert len(window) == 3                        # the three window layers, each under its own numbered scope
    assert any(re.search(r"/op\d+:fused_attention/block_sparse_attention/", n) for n in names)      # the full layers: no window scope
    gates = {m.group(1) for n in names for m in [re.search(r"/((?:sliding_attention(?:_\d+)?/)?attention_gate(?:_\d+)?)/", n)] if m}
    assert len(gates) == 5 and sum(g.startswith("sliding_attention") for g in gates) == 3, gates
    again = [name for name in _made_again(text) if "/cond/branch_" not in name]
    assert again and not [name for name in again if name.endswith("/dot_general") or "splash_mha" in name or "attention_dq_dk_dv" in name or "/expert_gemm/" in name]


def test_one_latent_attention_layer_writes_each_kernel_operand_once(host):
    """ONE latent attention layer at Kanana-2's widths (H 32, 192 / 128) over
    2048 positions, forward and backward through `_CompiledStep`, compiled for
    the described v5e (ISSUE 55): the chain of ops between the projections and
    the attention went into the unit's four kernels (`ops/latent_kernels.py`),
    which write the arrays the attention's kernels and the projections' backward
    read and nothing else: q, k, v forward and again, dq and d_up backward.
    Beside them no instruction under the layer's scope that is no product, no
    kernel call and not the partials' sum writes 30 MB x (2048 / 16384) or more
    but the kept output's copy in its two layouts and the output's way back to
    (B, L, H, 128), forward and again; no `dot_general` stands under `/rotary/`
    (the rotation is a rotation of lanes inside a pass, not a product with a
    0/+-1 matrix of its own)."""
    from tools import chip_latent_edges as edge

    positions = 2048
    compiled, counted = edge.one_layer_step(host.devices, positions)
    assert counted["lowering.latent_operands_assembled"] == 1 and not counted.get("lowering.latent_operands_fallback")
    assert counted["lowering.attention_block_causal"] == counted["lowering.attention_backward_onchip_dq"] == 1 and counted["lowering.latent_rotary_ops"] == 2
    text = compiled.as_text()
    found = edge.edges(text, floor=edge.FLOOR * positions / 16384)
    mb = 2 * positions * 32 / 1e6       # of a (B, L, H, 1) slab in bf16
    kernels = sorted((way, kind, round(size / mb)) for way, kind, size, _, _ in found if kind.startswith("kernel:"))
    assert kernels == sorted([("forward", "kernel:latent_queries", 192), ("forward", "kernel:latent_keys_values", 192 + 128),
                              ("again", "kernel:latent_queries", 192), ("again", "kernel:latent_keys_values", 192 + 128),
                              ("backward", "kernel:latent_queries_back", 192), ("backward", "kernel:latent_up_back", 256 + 4)]), kernels   # + the float32 sum over the heads
    # (a projection's own cast of its weights, 16.8 MB whatever the positions, is no array of the edge's)
    rest = sorted((way, kind, round(size / mb)) for way, kind, size, _, name in found
                  if not kind.startswith("kernel:") and ":mul/" not in name)
    assert rest == [("again", "transpose", 128), ("forward", "reduce_precision", 256), ("forward", "transpose", 128)], rest
    names = re.findall(r'op_name="([^"]*)"', text)
    assert any("/rotary/" in name for name in names)
    assert not [name for name in names if "/rotary/" in name and name.endswith("/dot_general")]
    assert "splash_mha_fwd" in text and "attention_dq_dk_dv" in text and "splash_mha_d" not in text


@pytest.mark.slow   # one compile of ~70 s on every core: run by name (`-m slow`); PERF.md, PR 54, has its readings
def test_kanana2s_step_keeps_every_candidate_of_its_sparse_segments_and_its_planned_peak_leaves_room(host, monkeypatch):
    """`kanana-2-30b-a3b.train-mla-s16384`'s whole step at the published widths
    and 16384 tokens, compiled for the described v5e with what `plan_kept`
    chooses at the chip's memory limit: every candidate of the five segments,
    four of them sparse (the expert products' outputs and the routers' logits
    among them), planned under the 15.5 GB a cell allows itself and over 25% of
    the chip; the latent attention took the splash kernels at (192, 128) over
    16384 keys, the ten rotations stand under `latent_attention/rotary`, and in
    the rematerialised computations no product, no attention kernel and no
    grouped product of the held path's COMMON pass is left (ISSUE 54)."""
    compiled, counted = _kept_step("kanana", "kanana-2-30b-a3b", "train-mla-s16384", host.devices, monkeypatch)
    assert counted["segments"] == 5 and counted["sparse_segments"] == 4
    assert counted["kept_bytes"] == counted["candidates_bytes"] > 4e9
    peak = _planned_peak(compiled)
    print(f"planned peak {peak / 1e9:.3f} GB")     # shown by `-s`
    assert 0.25 * 16.9e9 <= peak <= 14.9e9, peak
    text = compiled.as_text()
    assert "splash_mha_fwd" in text and "attention_dq_dk_dv" in text and "splash_mha_d" not in text and "flash_mha" not in text
    assert len(set(re.findall(r"/(latent_attention(?:_\d+)?)/rotary/op\d+:rotary_embedding", text))) == 5
    # the edge of a sparse layer's latent attention, the unit's own kernels with it: 2.6 GB written or less, from 4.1
    # before the chain was lowered as one unit (ISSUE 55; `tools/chip_latent_edges.py` prints the table)
    from tools import chip_latent_edges as edge

    written = sum(size for _, _, size, _, _ in edge.edges(text, edge.LAYER))
    print(f"a sparse layer's edge writes {written / 1e3:.3f} GB")
    assert 1.5e3 <= written <= 2.6e3, written
    # (the rare path makes its own again, and a rotation's pair swap is a product with a constant, no kept matrix's)
    again = [name for name in _made_again(text) if "/cond/branch_" not in name and ":rotary_embedding/" not in name]
    assert again and not [name for name in again if name.endswith("/dot_general") or "splash_mha" in name or "attention_dq_dk_dv" in name or "/expert_gemm/" in name]


def test_the_selected_attentions_kernels_compile_at_keye_vl_2s_shape(chip):
    """The splash kernels on block maps made from the step's own picks
    (`ops/masked_attention.py: selected_attention`), forward, dq and dkv, at (1,
    32 on 4, 16384, 128) bf16 with the picks as int32 words: every grid step's
    stored [512, 1024] block of the mask fits the scoped VMEM beside its
    operands (the fused backward's [1024, 1024] did not: `_BLOCKS`' table), and
    no byte mask of the whole square outlives the row it was unpacked for
    (three block layouts of 268 MB each and the unpacking's own temporaries:
    under 4 GB)."""
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
    assert all(name in text for name in ("splash_mha_fwd", "splash_mha_dq", "splash_mha_dkv"))
    assert compiled.memory_analysis().temp_size_in_bytes < 4e9, compiled.memory_analysis().temp_size_in_bytes
    assert ma.selected_plan(16384, 32).sizes.block_q == 512 and ma.selected_plan(16384, 32).sizes.block_kv == 1024


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


@pytest.mark.slow   # two compiles, ~115 and ~80 s on every core: run by name (`-m slow`); PERF.md, PR 56, has their readings
def test_keye_vl_2s_step_and_its_eight_row_clone_plan_under_the_chips_memory(host, monkeypatch):
    """`keye-vl-2.0-30b-a3b.train-dsa-s16384`'s whole step at the published
    widths and 16384 tokens, compiled for the described v5e with what
    `plan_kept` chooses at the chip's memory limit: every candidate of the four
    segments, the four layers' picks among them (33.5 MB each, kept whatever the
    room), planned over 25% of the chip and under the 15.5 GB a cell allows
    itself; the attention took the splash kernels under the stored mask, no
    `reduce-window` spans a row of keys, and in the rematerialised computations
    no `top_k` of the indexer, no attention kernel and no product is left.  The 8-row
    `for_test` clone of the reference check, the tightest program of a
    16384-token cell (PERF.md, PR 54), plans with the optimizer's two moments
    beside it under the 16.9 GB the chip's runtime gives (ISSUE 56)."""
    compiled, counted = _kept_step("keye", "keye-vl-2.0-30b-a3b", "train-dsa-s16384", host.devices, monkeypatch)
    assert counted["segments"] == counted["sparse_segments"] == 4
    assert counted["kept_bytes"] == counted["candidates_bytes"] > 2e9
    peak = _planned_peak(compiled)
    print(f"the step's planned peak {peak / 1e9:.3f} GB")     # shown by `-s`
    assert 0.25 * 16.9e9 <= peak <= 15.5e9, f"the step plans {peak / 1e9:.3f} GB"
    text = compiled.as_text()
    assert all(name in text for name in ("splash_mha_fwd", "splash_mha_dq", "splash_mha_dkv")) and "flash_mha" not in text
    assert len(set(re.findall(r"/(sparse_index(?:_\d+)?)/op\d+:sparse_index/index_select/", text))) == 4
    assert len(set(re.findall(r"/(sparse_index(?:_\d+)?)/op\d+:index_alignment/", text))) == 4
    # the alignment's gradients are the kernel's in every layer (PR 59), and its target's (PR 62): a call a chunk loop's body,
    # and no float32 array of a group's scores or exponentials under the op
    assert len(set(re.findall(r"/(sparse_index(?:_\d+)?)/op\d+:index_alignment/[^\"]*index_alignment_gradients", text))) == 4
    targets = re.findall(r"custom_call_target=\"tpu_custom_call\"[^\n]*/(sparse_index(?:_\d+)?)/op\d+:index_alignment/[^\"\n]*selected_attention/[^\"\n]*alignment_target", text)
    assert len(set(targets)) == 4 and len(targets) == 4 * 8, (len(set(targets)), len(targets))          # eight bands a layer, a call a chunk
    widths = "|".join(str(keys) for keys in range(2048, 16385, 2048))
    assert not [line for line in text.splitlines() if "index_alignment" in line and re.search(rf"f32\[(4,)?8,512,({widths})\]", line)]
    windows = [int(n) for n in re.findall(r"reduce-window\([^\n]*window=\{size=[0-9x]*?x?(\d+) pad", text)]
    assert max(windows, default=0) < 2048, max(windows)      # no row's statistic is spread as one window over the row
    again = [name for name in _made_again(text) if "/cond/branch_" not in name]
    # (the router's own top-8 is made again with its layer, the same choice bit for bit: ISSUE 54; the INDEXER's never)
    assert again and not [name for name in again if name.endswith("/dot_general") or "splash_mha" in name
                          or (name.endswith("/top_k") and "moe_router" not in name)
                          or "index_select" in name or "index_alignment" in name]
    clone, _ = _kept_step("keye", "keye-vl-2.0-30b-a3b", "train-dsa-s16384", host.devices, monkeypatch, check_rows=8)
    moments = 2 * 4 * 465_391_104
    beside = _planned_peak(clone) + moments
    print(f"the 8-row clone's planned peak {_planned_peak(clone) / 1e9:.3f} GB, {beside / 1e9:.3f} with the moments")
    assert beside <= 16.9e9, f"the clone plans {_planned_peak(clone) / 1e9:.3f} GB beside {moments / 1e9:.3f} GB of moments"


@pytest.mark.slow   # one compile for four devices, ~3 minutes here: run by name (`-m slow`); PERF.md, PR 51, has its readings
def test_jamba2s_step_on_the_2x2_host_keeps_what_a_chips_room_holds_and_its_planned_peak_leaves_room(host, monkeypatch):
    """`ai21-jamba2-3b.train-ssm-fsdp4`'s whole step at the published widths
    on the described 2x2 host, ZeRO-3 over `dp`: of 98 candidates (9.38 GB a
    chip) the budget (half of what 4.80 GB of state leave of the chip) holds
    68, 6.04 GB: the attention's residuals and every product but the 13 step
    projections and the last layer's `up`; the 13 scans' forward kernels are
    still made again (their output and start states come last by operations a
    byte).  Planned under 14.5 GB a chip."""
    compiled, counted = _kept_step("jamba", "ai21-jamba2-3b", "train-ssm-fsdp4", host.devices, monkeypatch)
    assert counted == {"segments": 14, "kept_values": 68, "kept_bytes": 6035210240, "candidates_bytes": 9382264832}
    peak = _planned_peak(compiled)
    print(f"planned peak {peak / 1e9:.3f} GB a chip")
    assert 11e9 <= peak <= 14.5e9, peak
    again = _made_again(compiled.as_text())
    assert sum(name.endswith("/selective_scan/pallas_call") for name in again) >= 13
    assert not [name for name in again if name.endswith("/pallas_call") and "selective_scan" not in name]   # the attention's is kept


# -- ISSUE 60: the scalar-decay scan and the latent experts under the (4,) mesh, at Nemotron-3-Super's widths ------------

#: one row of 8192 positions a chip: 128 heads of 64, a state of 128 in 8 groups, chunks of 128 (x, B, C bf16; dt bf16)
SSD_SPECS = [((1, 8192, 8192), BF16), ((1, 8192, 128), BF16), ((128,), F32), ((1, 8192, 1024), BF16), ((1, 8192, 1024), BF16),
             ((128,), F32), ((128,), F32)]


def _ssd(x, dt, a_log, b_t, c_t, d_skip, dt_bias):
    from paddle_tpu.ops.ssd_ops import chunked_ssd_scan

    return chunked_ssd_scan(x, dt, a_log, b_t, c_t, d_skip, dt_bias, 8, 128)[0]


@pytest.mark.parametrize("way", ["forward", "backward"])
def test_the_scalar_decay_scan_compiles_for_v5e_at_nemotron3s_widths(way, chip):
    """`ssd_scan`'s chunked form (plain `jax.numpy`: no Mosaic kernel on THAT
    path, the CPU's and the odd shapes'; the chip's own path at these widths is
    the next test's) for one
    described chip: the 64 chunks' carried state is ONE `while` of 64 steps
    forward (its transpose a second one backward), the intra-chunk work batched
    products, and what it plans beside its operands stays under 4 GB a row."""
    args = [jax.ShapeDtypeStruct(s, d, sharding=chip) for s, d in SSD_SPECS]
    program = _ssd if way == "forward" else _backward(_ssd, (0, 1, 2, 3, 4, 5, 6))
    compiled = jax.jit(program).lower(*args).compile()
    text = compiled.as_text()
    whiles = len(re.findall(r"= [^\n]* while\(", text))
    temporaries = compiled.memory_analysis().temp_size_in_bytes
    print(f"ssd_scan {way}: {whiles} while(s), temporaries {temporaries / 1e9:.3f} GB")
    assert 1 <= whiles <= (1 if way == "forward" else 3), whiles
    assert temporaries < (2.5e9 if way == "forward" else 4.5e9), temporaries
    assert "tpu_custom_call" not in text


def _ssd_kernels(x, dt, a_log, b_t, c_t, d_skip, dt_bias):
    from paddle_tpu.ops import ssd_ops

    assert ssd_ops._scan_path("tpu", None, x, a_log, b_t, 8, 128) == "kernels"
    return ssd_ops.kernel_ssd_scan(x, dt, a_log, b_t, c_t, d_skip, dt_bias, 8, 128, "tpu")[0]


@pytest.mark.parametrize("way", ["forward", "backward"])
def test_the_scalar_decay_scans_kernels_compile_for_v5e_at_nemotron3s_widths(way, chip):
    """What `_scan_path` takes on the chip at these widths (ISSUE 61): the two
    kernels of `ops/ssd_kernels.py`, a group's sixteen heads a grid step in
    eight slabs of two.  One Mosaic call forward, two backward (the forward
    that keeps the chunks' start states, the transposed one), no `while` round
    the chunks (the chunk axis is the kernels' grid), and beside its operands
    the op plans only what it hands on: nothing forward, the start states
    ([64 chunks, 128 heads, 64, 128] float32, 0.27 GB) and the kernels' small
    operands backward, where the plain form plans 2.0 | 3.5 GB.  The kernels fit
    the scoped VMEM they ask for or Mosaic would refuse them here."""
    from paddle_tpu.ops import ssd_kernels

    args = [jax.ShapeDtypeStruct(s, d, sharding=chip) for s, d in SSD_SPECS]
    program = _ssd_kernels if way == "forward" else _backward(_ssd_kernels, (0, 1, 2, 3, 4, 5, 6))
    compiled = jax.jit(program).lower(*args).compile()
    text = compiled.as_text()
    calls, whiles = text.count("tpu_custom_call"), len(re.findall(r"= [^\n]* while\(", text))
    temporaries = compiled.memory_analysis().temp_size_in_bytes
    print(f"ssd_scan's kernels {way}: {calls} Mosaic call(s), {whiles} while(s), temporaries {temporaries / 1e9:.3f} GB")
    assert (calls, whiles) == ((1, 0) if way == "forward" else (2, 0)), (calls, whiles)
    assert temporaries < (0.1e9 if way == "forward" else 1e9), temporaries
    assert ssd_kernels._SEMANTICS.vmem_limit_bytes <= 100 * 2 ** 20       # of the v5e's 128 MiB


def test_the_latent_experts_under_the_rows_only_mesh_compile_for_the_2x2_host_with_the_kernels_on_a_chips_own_rows(host):
    """`moe_experts` at the cell's widths (a row of 8192 tokens a chip in the
    latent of 1024, 22 of 512 a token, experts 0-31 held as [32, 1024, 2688] and
    [32, 2688, 1024] float32 stacks split four ways along their first dimension)
    under the described host's (4,) mesh: the op runs in a `shard_map` over
    `dp`, the grouped products and the way back are Mosaic kernels on a chip's
    own rows, and the stacks are gathered whole (ZeRO-3's gather)."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from paddle_tpu.core.lowering import LoweringContext
    from paddle_tpu.core.registry import get_op_def

    mesh = Mesh(np.array(host.devices), ("dp",))
    attrs = {"held": [0, 32], "gated": False, "activation": "relu2", "num_experts": 512, "top_k": 22}
    op = SimpleNamespace(type="moe_experts", attr=lambda name, default=None: attrs.get(name, default))

    def layer(x, top_p, top_i, load, w_up, w_down):
        ctx = LoweringContext(jax.random.PRNGKey(0), platform="tpu", mesh=mesh, batch_axis="dp")
        ins = {"X": [x], "TopKProb": [top_p], "TopKIndex": [top_i], "Load": [load], "WUp": [w_up], "WDown": [w_down]}
        outs = get_op_def("moe_experts").lower(ctx, op, ins)
        return outs["Out"], outs["Held"], outs["Dropped"]

    rows, whole = NamedSharding(mesh, P("dp")), NamedSharding(mesh, P())
    specs = [((4, 8192, 1024), BF16, rows), ((4, 8192, 22), F32, rows), ((4, 8192, 22), I32, rows), ((512,), I32, whole),
             ((32, 1024, 2688), F32, rows), ((32, 2688, 1024), F32, rows)]
    args = [jax.ShapeDtypeStruct(s, d, sharding=sh) for s, d, sh in specs]
    for program in (layer, jax.grad(lambda *a: jnp.sum(layer(*a)[0].astype(F32)), argnums=(0, 4, 5))):
        text = jax.jit(program).lower(*args).compile().as_text()
        assert text.count("tpu_custom_call") >= 3, "the grouped products and the way back are kernels on a chip's rows"
        assert "all-gather" in text
    assert "reduce-scatter" in text or "all-reduce" in text      # the stacks' gradients, summed over the chips


@pytest.mark.slow   # two compiles for four devices, ~6 minutes here: run by name (`-m slow -k nemotron3`); PERF.md, PR 60, has its readings
def test_nemotron3_supers_step_and_its_check_rows_on_the_2x2_host_leave_room(host, monkeypatch):
    """`nemotron-3-super-120b-a12b.train-ssd-fsdp4`'s whole step at the published
    widths on the described 2x2 host, ZeRO-3 over `dp`, 32 experts held a layer
    (7.49 GB a chip of state), and the 8-row `for_test` clone its reference
    check runs beside that state: both planned under the chip's 16.9 GB.  Since
    PR 61 the five scans are kernels whose residuals (the output and the
    chunks' start states, 0.40 GB a layer) `plan_kept` holds with every other
    candidate: 34 values, 4.89 GB a chip (29 and 2.88 with the plain form, which
    offered nothing), planned 14.47 GB (13.46), and no scan is made again."""
    compiled, counted = _kept_step("nemotron_h", "nemotron-3-super-120b-a12b", "train-ssd-fsdp4", host.devices, monkeypatch)
    peak = _planned_peak(compiled)
    print(f"step: planned peak {peak / 1e9:.3f} GB a chip, kept {counted}")
    assert counted == {"segments": 11, "sparse_segments": 5, "kept_values": 34, "kept_bytes": 4891082752, "candidates_bytes": 4891082752}
    assert 14.2e9 <= peak <= 14.8e9, peak
    text = compiled.as_text()
    assert text.count("all-gather") and "tpu_custom_call" in text
    assert not [name for name in _made_again(text) if "ssd_scan" in name and name.endswith("/pallas_call")]
    clone, _ = _kept_step("nemotron_h", "nemotron-3-super-120b-a12b", "train-ssd-fsdp4", host.devices, monkeypatch, check_rows=8)
    moments = 2 * 4 * 1871531904 / 4     # Adam's two float32 moments lie beside the clone's own arguments, split four ways
    beside = _planned_peak(clone) + moments
    print(f"the 8-row clone's planned peak {_planned_peak(clone) / 1e9:.3f} GB, {beside / 1e9:.3f} with the moments")
    assert beside <= 16.9e9, beside
