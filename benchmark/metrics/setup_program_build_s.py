"""Seconds of set-up the caller's thread spent building programs: the self
times of the program's `program.build` (`program_guard`), `program.backward`
(`append_backward`), `program.optimize` (`Optimizer.minimize`) and
`program.clone` spans, from process start to the window's first step.  Python
that no cache serves; only the front end can shorten it.
One partition with its four siblings: `benchmark/setup_timeline.py`."""
from benchmark import setup_timeline

LAYER = 'front end: program construction (core/program.py, core/autodiff.py, optimizer.py)'
UNIT = 's'
BETTER = 'lower'
SOURCE = 'program_span'
MOVES = 'setup_s'


def read(ctx: dict):
    return setup_timeline.read_metric(ctx, setup_timeline.BUILD)
