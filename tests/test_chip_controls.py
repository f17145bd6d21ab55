"""The `tools/chip_*_controls.py` scripts rehearsed without the chip, as `tests/test_chip_tools.py` rehearses the
kernels' tools (ISSUE 58; ROADMAP D4): each on its sound run and ONE named fault, so that a PR which breaks the tool or
the way a fault reaches the program finds out before a chip call is spent on it.  Which limit refuses which control
is held in process by the cell's own `tests/test_<model>.py` (`test_the_comparison_refuses_a_program_with[...]`):
a second fault here is a second build and compile for the same finding (ISSUE 66)."""
import pytest
from test_chip_tools import rehearsed

#: tool -> its arguments in a rehearsal (a seed; a seed and one control)
REHEARSED = {
    "chip_laguna_controls": ("1", "gate_in_bf16"),            # the sound run and a lowering wrapped by its name scope
    "chip_lfm2_controls": ("1",),
    "chip_nemotron_controls": ("1", "scan_wrong_group"),      # the sound run and a fault in the program
    "chip_phi4flash_controls": ("1",),
    "chip_qwen3_next_controls": ("1", "no_delta_correction", "a_heads_mean_for_the_feature_gate"),   # a fault in the reference, a lowering wrapped by its scope
    "chip_smallthinker_controls": ("1", "window_of_17"),      # the sound run and a program built again
}


@pytest.mark.parametrize("tool", sorted(REHEARSED))
def test_the_tool_still_runs_at_tiny_sizes_with_its_kernels_interpreted(tool):
    readings = rehearsed(tool, *REHEARSED[tool])
    assert not [r for r in readings if r.get("error")], readings


def test_chip_nemotron_controls_op_alone_mode_rehearses_with_the_kernels_interpreted():
    """`ONLY=profile python3 tools/chip_nemotron_controls.py` (ISSUE 61: the scan alone, its kernels beside the plain
    form): tiny and interpreted it times nothing, and its two readings of how far the kernels lie from the plain form
    and both from the recurrence are float32's rounding."""
    readings = {r["reading"] + r.get("form", ""): r for r in rehearsed("chip_nemotron_controls", ONLY="profile")}
    apart = readings["ssd_kernels_from_plain"]
    assert max(apart["y"], apart["state"], apart["y_kept"], *apart["means"]) < 1e-6 and max(apart["d_a_log"], apart["d_d"], apart["d_dt_bias"]) < 1e-4
    assert max(apart[k] for k in ("d_x", "d_dt", "d_b", "d_c")) < 1e-2                   # bf16 gradients: a step of theirs
    for form in ("kernels", "plain"):
        assert readings["ssd_against_the_recurrence" + form]["scan_state_error"] < 1e-5
