"""chip_smoke.py rehearsed without the chip.

Two halves of one rule.  The script itself, run as the driver runs it, must
refuse a machine without a TPU — non-zero exit, a message naming the missing
chip, no result line — because a smoke that falls back to the CPU proves
nothing.  And its phases must be sound code paths: each is imported and run
here at tiny sizes on the virtual CPU mesh, with the platform check stubbed
BY THE TEST (chip_smoke.PLATFORM), never by an option of the script.
"""
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402

TINY_BERT = dict(vocab_size=128, seq_len=16, d_model=64, n_layers=2,
                 n_heads=4, d_ff=128)


@pytest.mark.parametrize("argv", [[], ["--chips", "4"]],
                         ids=["default", "chips4"])
def test_exits_nonzero_naming_the_missing_chip(argv):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, "chip_smoke.py", *argv], cwd=REPO,
                         env=env, capture_output=True, text=True, timeout=300)
    assert out.returncode != 0
    assert "tpu chip" in out.stderr and "'cpu'" in out.stderr, out.stderr[-2000:]
    assert '"ok"' not in out.stdout, "printed a result without a chip"


@pytest.fixture
def on_cpu(monkeypatch):
    """The stub: the phases' own checks (every fetch and state array on
    PLATFORM devices) hold the CPU backend to 'cpu' instead of 'tpu'."""
    from paddle_tpu import monitor

    monkeypatch.setattr(chip_smoke, "PLATFORM", "cpu")
    monitor.reset()
    monitor.enable()  # main() does: the recompile counter, the compile spans
    yield
    monitor.disable()
    monitor.reset()


PHASES = {
    "trainer": lambda: chip_smoke.phase_trainer(
        bert=TINY_BERT, batch=8, k=2, dispatches=4),
    "server": lambda: chip_smoke.phase_server(
        depth=18, image=32, class_dim=10, buckets=(1, 4), sizes=(1, 4, 3)),
    "host_callback": lambda: chip_smoke.phase_host_callback(),
    "mesh_2x2": lambda: chip_smoke.phase_mesh(
        bert=TINY_BERT, batch=8, steps=3),
}


@pytest.mark.parametrize("phase", sorted(PHASES))
def test_phase_passes_at_tiny_sizes_on_the_cpu(phase, on_cpu):
    PHASES[phase]()


def test_a_phase_off_its_platform_fails(on_cpu, monkeypatch):
    """The no-fallback check itself: state that is not on PLATFORM devices
    is an error, not a printed field."""
    monkeypatch.setattr(chip_smoke, "PLATFORM", "tpu")
    with pytest.raises(AssertionError, match="not tpu"):
        chip_smoke.phase_host_callback()
