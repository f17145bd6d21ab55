"""Seconds of set-up inside the program's `executor.build`, `executor.lower`
and `executor.compile` spans: building, tracing and compiling, or loading
from the compile cache.  Falls to the warm figure on a cell's second run in
a checkout."""
LAYER = 'executor (core/executor.py)'
UNIT = 's'
BETTER = 'lower'
SOURCE = 'program_span'
MOVES = 'setup_s'


def read(ctx: dict):
    setup = ctx["monitor"].get("setup")
    if not setup:
        return None
    return sum(setup[k] for k in ("executor.build", "executor.lower", "executor.compile"))
