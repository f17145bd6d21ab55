"""`ops/ssm_kernels.py` (ISSUE 48), tiny and interpreted on the CPU: the Pallas
kernels behind `ssm_ops.kernel_selective_scan` against the XLA form (what the CPU
runs) and the token-by-token recurrence, over `tests/test_jamba.py`'s table of
shapes and by its cases' own bodies (`forms`): the output, the final state, the
statistics, all seven gradients (a kernel of their own) against `jax.grad` of
the XLA form; a strong step; the start states they keep; their two seams; the
whole operands' gradients under the four-device batch mesh.  The op's XLA form,
`_scan_path`'s rule and the whole model stand in `tests/test_jamba.py`, which
also has a whole train step through these kernels: this file is the kernels'
own so that a second worker has them (ISSUE 66; `docs/tier1_durations.md`).

Interpreted kernels show the arithmetic; what Mosaic refuses shows in
`tests/test_chip_compile.py`.
"""
from types import SimpleNamespace

import test_jamba as forms
from test_jamba import KERNEL_BLOCK, KERNEL_COUNTERS, agree, scan_inputs, scan_of

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import monitor
from paddle_tpu.core.lowering import LoweringContext
from paddle_tpu.ops import ssm_kernels, ssm_ops


@pytest.mark.parametrize("path", ["interpret"])
@pytest.mark.parametrize("rows,length,chunk,dtype,step_bias", forms.SCAN_CASES)
def test_the_chunked_scan_is_the_recurrence_forward_and_backward(rows, length, chunk, dtype, step_bias, path):
    forms.test_the_chunked_scan_is_the_recurrence_forward_and_backward(rows, length, chunk, dtype, step_bias, path)


@pytest.mark.parametrize("path", ["interpret"])
def test_a_step_of_sixty_nats_a_token_overflows_nothing(path):
    forms.test_a_step_of_sixty_nats_a_token_overflows_nothing(path)


def test_the_kernels_keep_the_state_every_chunk_starts_from():
    """What forward keeps where the op is differentiated: the XLA form's
    carried state at every chunk boundary (its final state on the tokens before
    it), in the kernels' own tiles."""
    inputs = scan_inputs(4, 2, 50, 16, 8)
    x, dt, b, c, a_log, d_skip, bias = inputs
    (y, final, _), (starts,) = ssm_ops._kernel_scan(x, dt, a_log, b, c, d_skip, bias, "interpret", 16, KERNEL_BLOCK, True)
    assert starts.shape == (4, 2, 2, 8, ssm_kernels.GROUP, 1)               # 50 tokens: three chunks of 16 and a padded tail
    starts = ssm_kernels.channels_last(starts)
    assert not np.asarray(starts[0]).any()
    for k in (1, 2, 3):
        before = ssm_ops.chunked_selective_scan(x[:, :16 * k], dt[:, :16 * k], a_log, b[:, :16 * k], c[:, :16 * k], d_skip, bias)[1]
        agree(starts[k], before, tol=1e-6)
    agree(final, ssm_ops.chunked_selective_scan(x, dt, a_log, b, c, d_skip, bias)[1], tol=1e-6)


@pytest.mark.parametrize("seam", ["step_of", "carried"])
def test_the_seams_bite_in_the_kernels(seam, monkeypatch):
    """`ssm_kernels.step_of` and `carried`, patched as
    tools/chip_jamba_controls.py patches them beside `ssm_ops`' pair, change what
    the interpreted kernels give (they are static arguments of the kernels'
    `jax.jit`s: a patched one is traced anew), by what the XLA form's change it."""
    inputs = scan_inputs(5, 1, 48, 16, 8)
    sound = scan_of("interpret", 16)(*inputs)[0]
    low = lambda t: t.astype(jnp.bfloat16).astype(jnp.float32)
    step, step_in_kernel = ssm_ops._step_of, ssm_kernels.step_of
    faults = {"step_of": lambda of: (lambda dt, bias: low(of(dt, bias))), "carried": lambda of: low}[seam]
    monkeypatch.setattr(ssm_ops, "_" + seam, faults(step))
    monkeypatch.setattr(ssm_kernels, seam, faults(step_in_kernel))
    faulty, faulty_xla = scan_of("interpret", 16)(*inputs)[0], scan_of("xla", ssm_ops._SSM_CHUNK)(*inputs)[0]
    moved = float(jnp.abs(faulty - sound).max() / jnp.abs(sound).max())
    assert moved > 1e-4, moved
    agree(faulty, faulty_xla, tol=2e-5)                                   # the state is handed on every eight tokens in both


def scan_op_gradients(ctx, rows):
    """(Out, the gradients of sum(sin(Out)) by ALog, D and DtBias) of the op
    lowered under `ctx` on `rows` rows of 24 tokens, 16 channels, a state of 8."""
    inputs = scan_inputs(6, rows, 24, 16, 8)
    op = SimpleNamespace(type="selective_scan", attr=lambda n, d=None: d)

    def out(*arrays):
        return ssm_ops._selective_scan(ctx, op, {k: [v] for k, v in zip(("X", "Dt", "B", "C", "ALog", "D", "DtBias"), arrays)})["Out"]

    return jax.jit(out)(*inputs), jax.jit(jax.grad(lambda *a: jnp.sum(jnp.sin(out(*a))), argnums=(4, 5, 6)))(*inputs)


def test_under_a_batch_mesh_the_kernels_run_on_a_chips_rows_and_the_whole_operands_gradients_are_summed_once(monkeypatch):
    """The `custom_vjp` stands inside the `shard_map`: ALog's, D's and DtBias'
    gradients under the four-device batch mesh are one device's on the same
    rows (each chip's share summed over the chips once), and the counters count
    the op's two kernels and the kept starts, which the CPU's path leaves at 0."""
    mesh = fluid.parallel.make_mesh((4,), ("dp",))
    monitor.reset()
    monitor.enable()
    try:
        xla = scan_op_gradients(LoweringContext(jax.random.PRNGKey(0)), 4)
        assert [monitor.counter(n).value for n in KERNEL_COUNTERS] == [0, 0, 0]
        monkeypatch.setattr(ssm_ops, "_scan_path", lambda *a, **k: "interpret")
        alone = scan_op_gradients(LoweringContext(jax.random.PRNGKey(0)), 4)
        assert [monitor.counter(n).value for n in KERNEL_COUNTERS] == [2, 1, 1]      # lowered twice: Out, and Out's gradients
        split = scan_op_gradients(LoweringContext(jax.random.PRNGKey(0), mesh=mesh, platform="cpu", batch_axis="dp"), 4)
        assert [monitor.counter(n).value for n in KERNEL_COUNTERS] == [4, 2, 2]
        assert monitor.counter("lowering.kernels_under_shard_map").value == 2
    finally:
        monitor.disable()
        monitor.reset()
    agree(split[0], alone[0], tol=1e-6)
    for mine, one_device, xla_form in zip(split[1], alone[1], xla[1]):
        agree(mine, one_device, tol=1e-5)
        agree(mine, xla_form, tol=2e-5)
