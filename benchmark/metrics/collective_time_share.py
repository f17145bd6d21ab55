"""Share of the traced window in which a collective (all-reduce, all-gather,
reduce-scatter, collective-permute, all-to-all) was running or in flight on
the device, the median device."""
LAYER = 'multi-chip (parallel/*)'
UNIT = '%'
BETTER = 'lower'
SOURCE = 'device_trace'
MOVES = 'train_samples_per_s'


def read(ctx: dict):
    t = ctx["trace"]
    if not t.get("devices") or ctx["stats"].get("chips", 1) < 2:
        return None
    return 100.0 * t["collective_share"]
