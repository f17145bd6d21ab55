"""Mean of the `pipeline.dispatch` spans of the window's steps: what the
loop's thread pays to hand the device one step through `exe.run_async`.  Its
children `executor.feed_place` and `executor.enqueue` split it (PERF.md)."""
from benchmark import program_trace

LAYER = 'executor (core/executor.py)'
UNIT = 'ms'
BETTER = 'lower'
SOURCE = 'program_span'
MOVES = 'train_samples_per_s'


def read(ctx: dict):
    return program_trace.read_loop_metric(ctx, "dispatch_ms_per_step")
