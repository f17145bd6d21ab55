"""The tools a chip call stands on, rehearsed without the chip (ISSUE 58; ROADMAP D4).

`tools/lowered_hash.py` is what "the other cells cannot move" rests on since
PR 51: its printed line for BERT's `pretrain-s128` cell is held to what
`tests/test_lowering_one_path.py` pins.  The `tools/chip_*.py` scripts are each
written for one `chiprun` call and no test imported them: each is run here the
way its own docstring says to rehearse it (`DRY=1`: tiny sizes, the kernels
interpreted, no time worth reading), so that a PR which breaks a tool finds out
before it spends a chip call on it.  This file has the kernels' tools; the
`chip_*_controls` tools that have no other rehearsal are in
`tests/test_chip_controls.py`, a file of their own so that two workers share the
subprocesses (ISSUE 66).  `chip_smoke.py` has `tests/test_chip_smoke.py`, the
controls of Ouro, Kimi Linear, Jamba, Kanana-2 and Keye-VL-2.0 and
`chip_latent_edges.py` have their cells' own test files.
"""
import hashlib
import json
import os
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def test_lowered_hash_prints_the_pinned_program_of_berts_s128_cell(capsys):
    from test_lowering_one_path import PARENTS_PROGRAMS
    from tools import lowered_hash

    cell = "bert-base.pretrain-s128"
    assert cell in lowered_hash.one_chip_train_cells()
    assert lowered_hash.main([cell]) == 0
    name, module, sha = capsys.readouterr().out.split()
    _, pinned_module, _, pinned_sha, attentions = PARENTS_PROGRAMS["bert-base-s128-fused"]
    assert (name, module) == (cell, pinned_module)
    # the pin's text is another lowering of the same trace: at 128 keys the twelve attentions are XLA's (no kernel in
    # the step), and such a program the pin lowers for the host's own platform, the tool always for the TPU.  Lowered
    # the pin's way, the tool's trace IS the pinned program; lowered the tool's way it is the line recorded at the
    # parent commit `41636dc` of PR 58.
    assert attentions == (0, 0, 12, 0)
    _, traced = lowered_hash.traced_step(cell)
    assert hashlib.sha256(traced.lower().as_text().encode()).hexdigest() == pinned_sha
    assert sha == "b782cbe3abb7d3330242baf72181dda127cee798d7a53bd66c3b90f2107ec9bd" != pinned_sha


def rehearsed(tool, *arguments, **environment):
    """The JSON lines `DRY=1 python3 tools/<tool>.py <arguments>` prints.  Each tool reads `DRY=1` as it is imported (its
    sizes are module constants), so each is a process of its own."""
    out = subprocess.run([sys.executable, os.path.join("tools", tool + ".py"), *arguments], cwd=REPO, capture_output=True,
                         text=True, timeout=600, stdin=subprocess.DEVNULL,
                         env=dict(os.environ, DRY="1", JAX_PLATFORMS="cpu", **environment))
    assert out.returncode == 0, out.stderr[-3000:]
    readings = [json.loads(line) for line in out.stdout.splitlines() if line.startswith("{")]
    assert readings, out.stdout[-2000:]                  # every one of them reports in JSON lines
    return readings


#: tool -> its arguments in a rehearsal (seeds)
REHEARSED = {
    "chip_alignment_target": (),
    "chip_block_attention": (),
    "chip_held_experts": (),
    "chip_index_alignment": (),
    "chip_index_select": (),
    "chip_row_attention": (),
    "chip_sdar_routing": ("1", "2"),
    "chip_token_sum": (),
}


@pytest.mark.parametrize("tool", sorted(REHEARSED))
def test_the_tool_still_runs_at_tiny_sizes_with_its_kernels_interpreted(tool):
    readings = rehearsed(tool, *REHEARSED[tool])
    assert not [r for r in readings if r.get("error")], readings


def test_chip_block_attentions_stored_masks_rehearse_and_the_one_kernel_is_the_stock_pair_there():
    """`STORED=1 tools/chip_block_attention.py` (ISSUE 68): both stored rules, each form priced (no time in a rehearsal)
    and the one backward kernel's dq, dk and dv beside the stock pair's, a bf16 rounding apart at the most (the patches
    hold while the BACKWARD rule is traced: a form patched inside its forward alone ran the unpatched backward)."""
    readings = {r["what"]: r for r in rehearsed("chip_block_attention", STORED="1")}
    assert set(readings) == {f"{rule}_stored{tail}" for rule in ("block_diffusion", "selected") for tail in ("", "_ours_against_the_stock_pair")}
    for rule in ("block_diffusion", "selected"):
        assert set(readings[f"{rule}_stored"]["ms"]) == {"ours", "ours_dk_dv_summed_outside", "ours_int32_mask", "stock_pair"}
        assert not [took for took in readings[f"{rule}_stored"]["ms"].values() if took is not None]      # an error is a string
        against = readings[f"{rule}_stored_ours_against_the_stock_pair"]
        assert against["finite"] and max(against["apart"].values()) <= 1e-2


def test_chip_kimi_kernels_ops_agree_at_tiny_sizes_with_the_kernels_interpreted():
    """`tools/chip_kimi_kernels.py` has no rehearsal of its own, but its pieces take their sizes as arguments: the op
    through the kernels (interpreted) against the `jax.numpy` form, forward, and the backward it times alone
    (`backward_of` on what `kept_of` made) against `jax.vjp` of the whole op."""
    import jax

    from tools import chip_kimi_kernels as tool

    args = tool.kda_inputs(3, b=1, T=128, H=2, K=128)
    d_o = tool.cotangent_of(args)
    plain, kernels = tool.op_of(None)(*args), tool.op_of("interpret")(*args)
    scale = float(np.abs(np.asarray(plain, "f4")).max())
    assert np.abs(np.asarray(kernels, "f4") - np.asarray(plain, "f4")).max() <= 2e-2 * scale
    (out, _), kept = tool.kept_of("interpret")(*args)
    assert np.array_equal(np.asarray(out, "f4"), np.asarray(kernels, "f4"))
    alone = tool.backward_of("interpret")(*args, kept, d_o)
    whole = jax.vjp(tool.op_of("interpret"), *args)[1](d_o)
    assert len(alone) == len(whole) == 5
    for a, w in zip(alone, whole):      # one rule, jitted twice: equal to float32's last digits
        w = np.asarray(w, "f4")
        a = np.asarray(a, "f4").reshape(w.shape)     # beta's gradient comes back [b, T, H, 1] from the rule itself
        assert np.abs(a - w).max() <= 1e-5 * max(np.abs(w).max(), 1.0)
