"""Share of the device's idle time in `bench.traced_window` that falls under
no program span at all (each gap goes to the innermost span over it, the
loop's thread first): how far the attribution of idle time to the host can
be trusted, the host's twin of `scoped_time_share`.  The window is cut to
where the loop's thread has spans.  The median device."""
from benchmark import program_trace

LAYER = 'XLA: device'
UNIT = '%'
BETTER = 'lower'
SOURCE = 'device_trace'
MOVES = 'train_samples_per_s'


def read(ctx: dict):
    return program_trace.read_idle_metric(ctx, "idle_unattributed_share")
