"""1 - (union of the intervals in which an operation ran on the device) /
(traced window), the median device of the cell's chips."""
LAYER = 'XLA: device'
UNIT = '%'
BETTER = 'lower'
SOURCE = 'device_trace'
MOVES = 'train_samples_per_s'


def read(ctx: dict):
    t = ctx["trace"]
    return 100.0 * t["idle_share"] if t.get("devices") else None
