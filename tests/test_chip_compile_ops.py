"""Whole op lowerings compiled for the described v5e (`tests/test_chip_compile.py`
has the chip and the kernels alone): `moe_experts` at OLMoE-1B-7B's widths with
the passes over its rows that the optimised program may hold (PR 28), with a
share of the experts held at SDAR's and Kimi Linear's (PR 35, PR 53) and under
the 2x2 host's rows-only mesh at Nemotron-3-Super's (PR 60), LFM2's short
convolution, and the scalar-decay scan in its plain form and through its
kernels.  Nothing runs: a pass says what the compiled program holds, not what
it computes.
"""
import re
from types import SimpleNamespace

from test_chip_compile import BF16, F32, I32, _backward, _no_persistent_cache, chip, host  # noqa: F401  (the fixtures by name)

import jax
import jax.numpy as jnp
import numpy as np
import pytest


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
