"""Share of `bench.traced_window` in which the device ran nothing WHILE the
loop's thread was outside `pipeline.host_blocked` (pulling a batch,
dispatching, in a hook, or in no span): the idle time the host can be
answerable for.  Idle inside `host_blocked` is the device's own: gaps between
operations, synchronous collectives.  The window is cut to where the loop's
thread has spans (the annotation closes after the loop has returned).  The
median device."""
from benchmark import program_trace

LAYER = 'XLA: device'
UNIT = '%'
BETTER = 'lower'
SOURCE = 'device_trace'
MOVES = 'train_samples_per_s'


def read(ctx: dict):
    return program_trace.read_idle_metric(ctx, "idle_host_active_share")
