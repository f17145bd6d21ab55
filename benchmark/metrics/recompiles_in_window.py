"""The program's `executor.recompile` counter over the window.  Must be 0:
every shape is warm before the window opens."""
LAYER = 'executor (core/executor.py)'
UNIT = 'count'
BETTER = 'lower'
SOURCE = 'program_counter'
MOVES = 'train_samples_per_s'


def read(ctx: dict):
    return ctx["monitor"].get("window", {}).get("executor.recompile")
